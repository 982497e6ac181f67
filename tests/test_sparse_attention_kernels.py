"""The masked-attention kernels of ops/sparse_attention.py on their own
(tests/test_keye_vl.py has them inside the layer only): forward and backward
against a dense float32 reference under a random causal selection, tiled
against one tile, and the tile schedule the kernels build their grid from;
the index scores' hand-written backward (`index_scores_bwd`, PR 35) against
`jax.vjp` of the expression it replaced, alone and through the layer.
Interpreted on the CPU."""
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from deeplearning4j_tpu.nn.conf.layers import decoder             # noqa: E402
from deeplearning4j_tpu.ops.sparse_attention import (             # noqa: E402
    FIRST, LAST, grid_steps_per_tile, index_scores_bwd, masked_attention,
    tile_schedule)

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


# ------------------------------------------- the index scores' backward
def plain_index_scores(qi, ki, w):
    """`decoder.index_scores` as the parent of PR 35 had it, whose VJP XLA
    made: the reference of the kernel that took that VJP's place."""
    dots = jnp.einsum("chd,sd->hcs", qi, ki,
                      preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(dots)
                   * w.astype(jnp.float32).T[:, :, None], 0)


def index_inputs(C, S, HI=3, DI=8, dtype=jnp.float32, seed=0):
    """A chunk of C queries, the last C of S positions, against its prefix
    of S keys, and a cotangent that is zero above the diagonal (the KL's
    is: a masked pair has no gradient)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    qi = jax.random.normal(ks[0], (C, HI, DI), dtype)
    ki = jax.random.normal(ks[1], (S, DI), dtype)
    w = jax.random.normal(ks[2], (C, HI), dtype)
    g = jax.random.normal(ks[3], (C, S), jnp.float32)
    causal = jnp.arange(S)[None, :] <= S - C + jnp.arange(C)[:, None]
    return qi, ki, w, jnp.where(causal, g, 0.0)


# (C, S, bk): S one block, whole blocks, a ragged last block (40 = 32 + 8,
# 80 = 2 x 32 + 16), S under the block asked for
INDEX_CASES = [(16, 16, 16), (16, 64, 16), (16, 64, 32), (16, 40, 32),
               (32, 80, 32), (8, 24, 64), (16, 48, 16)]


@pytest.mark.parametrize("C,S,bk", INDEX_CASES)
def test_index_scores_bwd_against_the_plain_expressions_vjp(C, S, bk):
    qi, ki, w, g = index_inputs(C, S, seed=C + S + bk)
    want = jax.vjp(plain_index_scores, qi, ki, w)[1](g)
    got = index_scores_bwd(qi, ki, w, g, block_k=bk)
    for name, a, b in zip(("dqi", "dki", "dw"), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert np.abs(np.asarray(b)).max() > 1       # something to compare
        # the blocks only reorder float32 sums
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5, err_msg=name)
    # the keys a ragged last block reads past S are nobody's
    assert np.isfinite(np.asarray(got[1])).all()


@pytest.mark.parametrize("S,bk", [(64, 32), (40, 32)])
def test_index_scores_bwd_a_head_whose_dots_are_all_negative(S, bk):
    """Head 1's queries are minus the sum of |keys|' signs: every dot is
    negative, ReLU passes nothing, and the head gets no gradient at all."""
    qi, ki, w, g = index_inputs(16, S, seed=S)
    ki = jnp.abs(ki) + 0.1
    qi = qi.at[:, 1].set(-jnp.abs(qi[:, 1]) - 0.1)
    want = jax.vjp(plain_index_scores, qi, ki, w)[1](g)
    got = index_scores_bwd(qi, ki, w, g, block_k=bk)
    for a, b in zip(got, want):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)
    assert not np.asarray(got[0][:, 1]).any()        # dqi of the head
    assert not np.asarray(got[2][:, 1]).any()        # dw of the head
    assert np.asarray(got[2][:, 0]).any()


def test_index_scores_bwd_in_bfloat16_rounds_the_cotangent_as_the_mxu_does():
    """bf16 operands as the cell has them: the kernel hands the MXU
    bf16(g w_h) where the CPU's transposed dot keeps float32, so the two
    differ by that rounding and no more (2^-8 relative, summed over a
    head's pairs); the results come back in bf16."""
    qi, ki, w, g = index_inputs(32, 96, HI=4, DI=16, dtype=jnp.bfloat16)
    want = jax.vjp(plain_index_scores, qi, ki, w)[1](g)
    got = index_scores_bwd(qi, ki, w, g, block_k=32)
    for name, a, b in zip(("dqi", "dki", "dw"), got, want):
        assert a.dtype == b.dtype == jnp.bfloat16, name
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() <= 2 ** -6 * np.abs(b).max(), name


def test_index_scores_gradient_is_the_kernels_and_its_value_the_plain():
    qi, ki, w, g = index_inputs(16, 48, seed=5)
    out, pull = jax.vjp(decoder.index_scores, qi, ki, w)
    np.testing.assert_array_equal(out, plain_index_scores(qi, ki, w))
    for a, b in zip(pull(g), index_scores_bwd(qi, ki, w, g)):
        np.testing.assert_array_equal(a, b)
    # nothing of a head's [C, S] dots is kept for the backward: what the
    # pullback holds is the three operands
    assert sorted(a.shape for a in jax.tree.leaves(pull)) == sorted(
        [qi.shape, ki.shape, w.shape])


def test_index_scores_under_vmap_as_the_cells_probe_calls_it():
    """`benchmarks/drivers/train_vl.py program_selection`: the last `count`
    queries of every row against all of the row's keys, under `jax.vmap`,
    forward only."""
    rows, T, count = 2, 64, 16
    parts = [index_inputs(T, T, seed=r) for r in range(rows)]
    qi, ki, w = (jnp.stack([p[i] for p in parts]) for i in range(3))
    got = jax.jit(jax.vmap(lambda a, b, c: decoder.index_scores(
        a[T - count:], b, c[T - count:])))(qi, ki, w)
    assert got.shape == (rows, count, T) and got.dtype == jnp.float32
    for r in range(rows):
        np.testing.assert_allclose(got[r], plain_index_scores(
            qi[r, T - count:], ki[r], w[r, T - count:]), rtol=1e-6,
            atol=1e-6)


def test_the_layers_gradient_is_the_plain_expressions():
    """`jax.grad` of the layer's output and its indexer's loss on the tiny
    Keye graph's first attention layer, with `index_scores` as it is and
    as the parent had it (the plain expression under `jax.checkpoint`):
    the indexer's three matrices, which alone see the difference, and the
    rest, which must not."""
    from test_keye_vl import batch_of, conf_of, weights
    conf, w, b = conf_of(), weights(), batch_of(0)
    attn = conf.vertices["l0_attn"].conf
    x = conf.vertices["l0_norm1"].conf.forward(
        w["l0_norm1"], conf.vertices["embed"].conf.forward(
            w["embed"], b["ids"], extras=(b["image"],)))

    def loss(p):
        out, state = attn.forward_with_state(
            p, x, attn.init_state(), train=True, extras=(b["positions"],))
        return jnp.sum(out * out) + state["layer_loss"]

    got = jax.grad(loss)(w["l0_attn"])
    ours = decoder.index_scores
    decoder.index_scores = jax.checkpoint(plain_index_scores)
    try:
        want = jax.grad(loss)(w["l0_attn"])
    finally:
        decoder.index_scores = ours
    assert sorted(got) == sorted(want)
    for k in sorted(want):
        assert np.abs(np.asarray(want[k])).max() > 0, k
        scale = np.abs(np.asarray(want[k])).max()
        np.testing.assert_allclose(got[k], want[k], rtol=2e-5,
                                   atol=2e-6 * scale, err_msg=k)
        if k not in ("WqI", "WkI", "Ww"):
            np.testing.assert_array_equal(got[k], want[k], err_msg=k)


def test_the_backward_kernel_of_the_lowered_step_is_under_indexer():
    """What `train_indexer_device_ms` reads is a name on an operation's
    path: `_row` opens `indexer` around the call of `index_scores`, and the
    hand-written backward is traced under the call site's names (a scope
    opened again inside it would read `indexer/indexer`)."""
    from test_keye_vl import batch_of, conf_of, mds_of
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    text = ComputationGraph(conf_of()).init().lower_step(
        mds_of(batch_of(0))).as_text(debug_info=True)
    paths = set(re.findall(r'loc\("([^"]*/[^"]*)"', text))
    mine = {p for p in paths if "sparse_attention_index_bwd" in p}
    assert mine and all("indexer/sparse_attention_index_bwd" in p
                        for p in mine)
    assert not [p for p in mine if "indexer/indexer" in p]
