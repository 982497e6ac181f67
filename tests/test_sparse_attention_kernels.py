"""The masked-attention kernels of ops/sparse_attention.py on their own
(tests/test_keye_vl.py has them inside the layer only): forward and backward
against a dense float32 reference under a random causal selection, tiled
against one tile, and the tile schedule the kernels build their grid from.
Interpreted on the CPU."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from deeplearning4j_tpu.ops.sparse_attention import (             # noqa: E402
    FIRST, LAST, grid_steps_per_tile, masked_attention, tile_schedule)

D = 16


def inputs(T, H, KV, gap, seed=0):
    """Seeded q, k, v, upstream gradients and a random causal selection
    that always keeps the diagonal and, for queries past `gap`, no key
    before t - gap / 2: rows whose first tiles keep nothing."""
    kq, kk, kv, km, ko = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(kq, (1, H, T, D), jnp.float32)
    k = jax.random.normal(kk, (1, KV, T, D), jnp.float32)
    v = jax.random.normal(kv, (1, KV, T, D), jnp.float32)
    t, s = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    keep = (jax.random.uniform(km, (T, T)) < 0.4) & (s <= t)
    keep = keep & ((t < gap) | (s >= t - gap // 2)) | (s == t)
    do = jax.random.normal(ko, (1, H, T, D), jnp.float32)
    return q, k, v, keep.astype(jnp.int8)[None], do


def dense(q, k, v, mask, scale):
    R = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, R, 1), jnp.repeat(v, R, 1)
    s = jnp.einsum("bhtd,bhsd->bhts", q, k,
                   precision=jax.lax.Precision.HIGHEST) * scale
    s = jnp.where(mask[:, None] != 0, s, -jnp.inf)
    lse = jax.nn.logsumexp(s, -1)
    o = jnp.einsum("bhts,bhsd->bhtd", jnp.exp(s - lse[..., None]), v,
                   precision=jax.lax.Precision.HIGHEST)
    return o, lse


def results(fn, q, k, v, mask, do):
    """o, lse and the gradients of sum(o * do) in q, k, v. (lse gets no
    cotangent: the layer feeds it to a stop_gradient only, and the kernels'
    backward takes none.)"""
    (o, lse), pull = jax.vjp(lambda q, k, v: fn(q, k, v, mask), q, k, v)
    return (o, lse) + pull((do, jnp.zeros_like(lse)))


# T of 2-4 blocks with bq != bk, both ways round; T under a block; T no
# multiple of the block asked for (the largest divisor under it is taken)
CASES = [(64, 16, 32, 4, 2), (64, 32, 16, 4, 2), (128, 32, 64, 2, 1),
         (96, 32, 16, 4, 4), (48, 16, 16, 6, 2), (24, 32, 64, 4, 2),
         (40, 16, 32, 2, 2)]


@pytest.mark.parametrize("T,bq,bk,H,KV", CASES)
def test_kernels_against_dense(T, bq, bk, H, KV):
    q, k, v, mask, do = inputs(T, H, KV, gap=max(bq, bk), seed=T + bq)
    scale = D ** -0.5
    want = results(lambda *a: dense(*a, scale), q, k, v, mask, do)
    tiled = results(lambda *a: masked_attention(*a, scale, bq, bk), q, k, v,
                    mask, do)
    whole = results(lambda *a: masked_attention(*a, scale, T, T), q, k, v,
                    mask, do)
    for name, w, t, one in zip(("o", "lse", "dq", "dk", "dv"), want, tiled,
                               whole):
        assert t.shape == w.shape, name
        np.testing.assert_allclose(t, w, rtol=2e-5, atol=2e-5, err_msg=name)
        # the tiling only reorders float32 sums
        np.testing.assert_allclose(t, one, rtol=2e-5, atol=2e-5,
                                   err_msg=name + " tiled against one tile")


def test_a_sequence_under_the_default_block_is_one_tile():
    q, k, v, mask, do = inputs(64, 4, 2, gap=16)
    scale = 0.25
    got = results(lambda *a: masked_attention(*a, scale), q, k, v, mask, do)
    one = results(lambda *a: masked_attention(*a, scale, 64, 64), q, k, v,
                  mask, do)
    for g, w in zip(got, one):
        np.testing.assert_array_equal(g, w)
    assert grid_steps_per_tile(64) == 1.0


def closed_form(T, bq, bk):
    nq = T // bq
    if bq == bk:
        return nq * (nq + 1) // 2
    if bq == 2 * bk:            # query block i sees key blocks 0 .. 2i + 1
        return nq * (nq + 1)
    assert bk == 2 * bq         # query block i sees key blocks 0 .. i // 2
    return sum(i // 2 + 1 for i in range(nq))


@pytest.mark.parametrize("T,bq,bk", [(8192, 512, 512), (8192, 1024, 512),
                                     (8192, 512, 1024), (256, 32, 32),
                                     (256, 64, 32), (256, 32, 64),
                                     (64, 64, 64)])
@pytest.mark.parametrize("heads", [1, 3])
def test_tile_schedule(T, bq, bk, heads):
    sched = tile_schedule(T, bq, bk, heads=heads)
    causal = {(i, j) for i in range(T // bq) for j in range(T // bk)
              if j * bk <= i * bq + bq - 1}
    n = closed_form(T, bq, bk)
    assert len(causal) == n
    assert sched["grid_steps"] == sched["computing_steps"] == n

    i, j, edge = sched["by_query"]
    assert all(a.dtype == np.int32 for a in (i, j, edge))
    assert sorted(zip(i.tolist(), j.tolist())) == sorted(causal)  # each once
    runs(list(i), edge)

    j, r, i, edge = sched["by_key"]
    assert len(j) == heads * n
    for h in range(heads):
        assert sorted(zip(i[r == h].tolist(), j[r == h].tolist())) == sorted(
            causal)
    runs(list(j), edge)


def runs(owner, edge):
    """The steps of one owner (the block whose result they add to) are
    contiguous, the first marked FIRST, the last LAST, none between."""
    seen, prev = set(), None
    for t, (o, e) in enumerate(zip(owner, edge)):
        starts = o != prev
        assert bool(e & FIRST) == starts, t
        if starts:
            assert o not in seen, f"owner {o} comes back at step {t}"
            seen.add(o)
        ends = t + 1 == len(owner) or owner[t + 1] != o
        assert bool(e & LAST) == ends, t
        prev = o


def test_gauge_reads_one_grid_step_a_tile():
    """Through `publish_layer_gauges()` on the tiny configuration, after a
    step of `fit`: the constant travels through the layer's state."""
    from test_keye_vl import batch_of, mds_of, trainer, weights
    net = trainer(weights())
    net.fit(mds_of(batch_of(0)))
    said = net.publish_layer_gauges()
    steps = {k: v for k, v in said.items()
             if k.endswith(".attend_grid_steps_per_tile")}
    assert sorted(steps) == [
        "sparseattention.l0_attn.attend_grid_steps_per_tile",
        "sparseattention.l1_attn.attend_grid_steps_per_tile"]
    assert set(steps.values()) == {1.0}
