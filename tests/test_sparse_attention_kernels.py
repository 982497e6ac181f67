"""The masked-attention kernels of ops/sparse_attention.py on their own
(tests/test_keye_vl.py has them inside the layer only): forward and backward
against a dense float32 reference under a random causal selection, tiled
against one tile, and the tile schedule the kernels build their grid from;
the index scores' hand-written backward (`index_scores_bwd`, PR 35) against
`jax.vjp` of the expression it replaced, alone and through the layer.
The one-pass backward (PR 37) against the two kernels it took the place of,
bit for bit, and against dense attention. Interpreted on the CPU."""
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
from deeplearning4j_tpu.ops import sparse_attention as sa         # noqa: E402
from deeplearning4j_tpu.ops.sparse_attention import (             # noqa: E402
    FIRST, LAST, OWN_FIRST, OWN_LAST, backward_passes, grid_steps_per_tile,
    index_scores_bwd, masked_attention, shared_key_attention, tile_schedule)

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


# ------------------------------------------------- the one-pass backward
# (how the tile's mask comes, T, bq, bk, H, KV, window, shared keys): CASES
# under a mask operand; by position, plain causal and under a window that
# is, and is not, a multiple of the blocks; a shared key read by every head
# with `own` = H / KV = 1, 2 and 4 query heads a key/value head, and two
# shared keys (the grid's first axis is then the shared key's)
ONE_PASS = [("mask", *c, None, 0) for c in CASES] + [
    ("causal", 64, 16, 32, 4, 2, None, 0),
    ("causal", 96, 32, 16, 6, 1, None, 0),
    ("window", 96, 16, 32, 4, 2, 32, 0),
    ("window", 64, 16, 16, 4, 1, 24, 0),
    ("window", 128, 32, 32, 2, 2, 40, 0),
    ("shared", 64, 16, 32, 4, 4, None, 1),
    ("shared", 64, 32, 16, 8, 4, None, 1),
    ("shared", 96, 32, 32, 8, 2, None, 1),
    ("shared", 64, 16, 16, 8, 4, None, 2)]
D2 = 8      # slots of a shared key


def one_pass_case(how, T, H, KV, window, KS, gap, seed):
    """(fn of (q, k, v[, q2, k2]) -> (o, lse), its operands, do, the dense
    reference of the same)."""
    q, k, v, mask, do = inputs(T, H, KV, gap, seed)
    scale = (D + (D2 if KS else 0)) ** -0.5
    t, s = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    if how != "mask":
        mask = ((s <= t) & ((t - s < window) if window else True)).astype(
            jnp.int8)[None]
    if not KS:
        given = mask if how == "mask" else None
        return (lambda bq, bk: lambda q, k, v: masked_attention(
            q, k, v, given, scale, bq, bk, window=window), (q, k, v), do,
            lambda q, k, v: dense(q, k, v, mask, scale))
    k2, kq = jax.random.split(jax.random.PRNGKey(seed + 1))
    q2 = jax.random.normal(kq, (1, H, T, D2), jnp.float32)
    k2 = jax.random.normal(k2, (1, KS, T, D2), jnp.float32)
    rep = lambda a: jnp.repeat(a, H // a.shape[1], 1)
    return (lambda bq, bk: lambda *a: shared_key_attention(*a, scale, bq, bk),
            (q, k, v, q2, k2), do,
            lambda q, k, v, q2, k2: dense(
                jnp.concatenate([q, q2], -1),
                jnp.concatenate([rep(k), rep(k2)], -1), rep(v), mask, scale))


def gradients(fn, operands, do):
    (o, lse), pull = jax.vjp(fn, *operands)
    return pull((do, jnp.zeros_like(lse)))


@pytest.mark.parametrize("how,T,bq,bk,H,KV,window,KS", ONE_PASS)
def test_one_pass_backward_is_the_two_kernels_and_dense_attention(
        how, T, bq, bk, H, KV, window, KS, monkeypatch):
    """dQ, dK, dV (and a shared key's dQ2, dK2) of the one kernel are the
    two kernels' bit for bit: for a fixed key block `one_pass` adds into
    dK[j], dV[j], dK2[j] in `by_key`'s order (head r, then query block i),
    and into dQ in `by_query`'s (j); every product has the operands and
    the shape it had. Dense attention differs by float32 sums' order."""
    fn, operands, do, plain = one_pass_case(
        how, T, H, KV, window, KS, gap=max(bq, bk), seed=T + bq)
    assert backward_passes(T, D, D, D2 if KS else 0) == 1
    one = gradients(fn(bq, bk), operands, do)
    monkeypatch.setattr(sa, "SLAB_BUDGET", 0)       # nothing fits: two passes
    two = gradients(fn(bq, bk), operands, do)
    want = gradients(plain, operands, do)
    assert len(one) == len(operands)
    for name, a, b, w in zip(("dq", "dk", "dv", "dq2", "dk2"), one, two,
                             want):
        assert a.shape == w.shape and a.dtype == w.dtype, name
        assert np.abs(np.asarray(w)).max() > 0.1, name
        np.testing.assert_array_equal(a, b, err_msg=name)
        np.testing.assert_allclose(a, w, rtol=2e-5, atol=2e-5, err_msg=name)


def kernels_of(fn, *operands):
    """The names of the Pallas kernels in `fn`'s jaxpr."""
    return sorted(set(re.findall(r"sparse_attention_\w+",
                                 str(jax.make_jaxpr(fn)(*operands)))))


WALKS = {1: ["sparse_attention_bwd", "sparse_attention_fwd"],
         2: ["sparse_attention_dkv", "sparse_attention_dq",
             "sparse_attention_fwd"]}


# one head of 4,096 slots at 576 keys: slabs of 576 x 8,192 x 4 bytes = 18.9
# MB, over SLAB_BUDGET as it stands (16.8 MB); at 384 keys 12.6 MB, under
@pytest.mark.parametrize("T, passes", [(384, 1), (576, 2)])
def test_slabs_over_the_budget_take_the_two_kernels(T, passes, monkeypatch):
    d = 4096
    assert (T * 2 * d * 4 > sa.SLAB_BUDGET) == (passes == 2)
    assert backward_passes(T, d, d) == passes
    ks = jax.random.split(jax.random.PRNGKey(T), 4)
    q, k, v, do = (jax.random.normal(kk, (1, 1, T, d), jnp.float32)
                   for kk in ks)
    fn = lambda q, k, v: masked_attention(q, k, v, None, d ** -0.5, 192, 192)
    # a new function a call: `make_jaxpr` remembers the one it has traced
    pull = lambda: lambda q, k, v: gradients(fn, (q, k, v), do)
    assert kernels_of(pull(), q, k, v) == WALKS[passes]
    got = pull()(q, k, v)
    # the other walk, by a budget that is not the module's
    monkeypatch.setattr(sa, "SLAB_BUDGET",
                        0 if passes == 1 else T * 2 * d * 4)
    assert kernels_of(pull(), q, k, v) == WALKS[3 - passes]
    for name, a, b in zip(("dq", "dk", "dv"), got, pull()(q, k, v)):
        assert np.abs(np.asarray(a)).max() > 0, name
        np.testing.assert_array_equal(a, b, err_msg=name)


def test_the_choice_of_passes_reads_shapes_alone():
    """The benchmark's three geometries take one pass; at 128-wide heads the
    two kernels come back past T = 16,384."""
    assert backward_passes(8192, 128, 128) == 1             # train-vl8k
    assert backward_passes(16384, 128, 128) == 1            # train-lc16k
    assert backward_passes(8192, 128, 128, 64) == 1         # train-mtp8k
    assert backward_passes(16384 + 1024, 128, 128) == 2
    assert backward_passes(16384, 128, 128, 64) == 2


@pytest.mark.parametrize("head_dim, passes", [(16, 1.0), (4096, 2.0)])
def test_gauge_reads_the_backward_passes(head_dim, passes):
    """`attend_backward_passes` in the layer's state, beside
    `attend_grid_steps_per_tile`: 1.0 where the layer's call takes the one
    kernel, 2.0 where its slabs are over the budget."""
    layer = decoder.AttentionLayer(n_in=32, n_out=32, n_heads=1,
                                   n_kv_heads=1, head_dim=head_dim)
    params = layer.init_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 576, 32), jnp.float32)
    _, state = layer.forward_with_state(params, x, layer.init_state())
    assert sorted(state) == sorted(layer.init_state())
    assert layer.gauges(state)["attend_backward_passes"] == passes
    assert layer.gauges(state)["attend_grid_steps_per_tile"] == 1.0


@pytest.mark.parametrize("T,bq,bk,window", [(256, 32, 32, None),
                                            (256, 64, 32, None),
                                            (256, 32, 64, None),
                                            (256, 32, 32, 48),
                                            (64, 64, 64, None)])
@pytest.mark.parametrize("heads,own", [(1, None), (3, None), (4, 2), (4, 1)])
def test_one_pass_schedule(T, bq, bk, window, heads, own):
    """`one_pass` is `by_query` once a head; a key/value head's slabs are
    cleared at its first head's first step and written at its last head's
    last; and for a fixed key block the tiles come in `by_key`'s order."""
    sched = tile_schedule(T, bq, bk, heads=heads, window=window, own=own)
    i0, j0, e0 = sched["by_query"]
    r, i, j, e = sched["one_pass"]
    n = sched["grid_steps"]
    assert all(a.dtype == np.int32 and len(a) == heads * n
               for a in (r, i, j, e))
    group = own or heads
    for h in range(heads):
        mine = slice(h * n, h * n + n)
        assert (r[mine] == h).all()
        np.testing.assert_array_equal(i[mine], i0)
        np.testing.assert_array_equal(j[mine], j0)
        np.testing.assert_array_equal(e[mine] & ~(OWN_FIRST | OWN_LAST), e0)
        firsts = np.flatnonzero(e[mine] & OWN_FIRST)
        lasts = np.flatnonzero(e[mine] & OWN_LAST)
        assert firsts.tolist() == ([0] if h % group == 0 else [])
        assert lasts.tolist() == ([n - 1] if h % group == group - 1 else [])
    runs(list(zip(r.tolist(), i.tolist())), e)      # dQ's: a (head, block)
    kj, kr, ki, _ = sched["by_key"]
    for block in range(T // bk):
        assert list(zip(r[j == block], i[j == block])) == list(
            zip(kr[kj == block], ki[kj == block]))


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
    # and their backward walks them once (PR 37)
    assert [said[f"sparseattention.l{i}_attn.attend_backward_passes"]
            for i in (0, 1)] == [1.0, 1.0]


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
