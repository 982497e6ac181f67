"""ops/grouped_matmul.py: the grouped kernels, interpreted here, against
`lax.ragged_dot` (forward, dx and the weight gradient, float32 and
bfloat16, uneven and empty groups, a group across a row tile, rows past
the last group, a width that ends inside a lane tile); the rule that sends
a shape to the kernels or leaves it to `ragged_dot`; and
`held_experts_ffn` with the kernels against itself without them, gated SiLU
and two-matrix relu^2, gradients included. Their compile for the chip, and
that a step builds each distinct kernel once, are in
tests/test_kernels_compile_v5e.py."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from deeplearning4j_tpu.ops import grouped_matmul as gm          # noqa: E402
from deeplearning4j_tpu.parallel import moe                      # noqa: E402


def small(rows, K, N, G):
    """Blocks of 128 rows and 128 columns (a width that is no whole number
    of lane tiles whole), so that a few hundred rows are several row tiles
    and a product several column blocks."""
    lane = lambda W: 128 if W % 128 == 0 else W
    return gm.Tiles(rows=128, fwd_n=lane(N), dx_n=lane(K), dw_k=lane(K),
                    dw_n=lane(N))


# (rows, K, N, group sizes): uneven groups with an empty one, the second
# across row tiles 0-3 and the last ending inside a tile before rows past
# the last group; groups on tile bounds filling every row; one group; no
# rows at all; N 192 = 1.5 lane tiles, taken whole
CASES = {"uneven": (512, 256, 256, (100, 0, 300, 50)),
         "aligned": (512, 128, 256, (128, 256, 128)),
         "one_group": (384, 128, 128, (0, 200, 0)),
         "empty": (256, 128, 128, (0, 0)),
         "partial_lanes": (256, 128, 192, (70, 130, 56))}


def _operands(rows, K, N, sizes, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    lhs = jax.random.normal(ks[0], (rows, K), jnp.float32).astype(dtype)
    rhs = jax.random.normal(ks[1], (len(sizes), K, N),
                            jnp.float32).astype(dtype)
    ct = jax.random.normal(ks[2], (rows, N), jnp.float32)
    return lhs, rhs, jnp.asarray(sizes, jnp.int32), ct


def _rel(a, b):
    a, b = (np.asarray(x, np.float64) for x in (a, b))
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernels_are_ragged_dot_and_its_gradients(monkeypatch, case,
                                                      dtype):
    """Value, dx and dW of the kernels against `ragged_dot`'s, the
    cotangent rounded to the operands' dtype on both sides (the chip's
    default precision rounds a float32 operand so): float32 to the last
    bits, bfloat16 to its own rounding; rows past the last group 0."""
    monkeypatch.setattr(gm, "tiling", small)
    rows, K, N, sizes = CASES[case]
    dtype = jnp.dtype(dtype)
    lhs, rhs, s, ct = _operands(rows, K, N, sizes, dtype)
    ct = ct.astype(dtype).astype(jnp.float32)
    out, pull = jax.vjp(lambda a, b: gm.grouped_matmul(a, b, s), lhs, rhs)
    want, pull_want = jax.vjp(lambda a, b: jax.lax.ragged_dot(
        a, b, s, preferred_element_type=jnp.float32), lhs, rhs)
    assert out.dtype == jnp.float32 and out.shape == (rows, N)
    tol = 1e-6 if dtype == jnp.float32 else 4e-3
    assert _rel(out, want) < 1e-6
    got, exp = pull(ct), pull_want(ct)
    for g, w, a in zip(got, exp, (lhs, rhs)):
        assert g.dtype == a.dtype and g.shape == a.shape
        assert _rel(g, w) < tol, (case, dtype)
    total = sum(sizes)
    assert not np.any(np.asarray(out[total:]))
    assert not np.any(np.asarray(got[0][total:], np.float32))
    for g, n in enumerate(sizes):          # an empty group's weights get 0
        if n == 0:
            assert not np.any(np.asarray(got[1][g], np.float32))


def test_the_output_dtype_is_the_one_asked_for(monkeypatch):
    monkeypatch.setattr(gm, "tiling", small)
    lhs, rhs, s, _ = _operands(256, 128, 128, (64, 150), jnp.bfloat16)
    out = gm.grouped_matmul(lhs, rhs, s, preferred_element_type=jnp.bfloat16)
    want = jax.lax.ragged_dot(lhs, rhs, s,
                              preferred_element_type=jnp.float32)
    assert out.dtype == jnp.bfloat16
    assert _rel(out.astype(jnp.float32), want) < 4e-3


def test_the_schedule_visits_a_shared_tile_once_a_group():
    """Groups 100, 0, 300, 50 over 512 rows in tiles of 128: the first
    group's tile 0, the third's tiles 0-3, the fourth's tile 3, then the
    tail (group 4) none; with empty groups visited, the second gets one
    step; with the tail, rows past 450 that fill no tile get none."""
    sizes = jnp.asarray([100, 0, 300, 50], jnp.int32)
    starts, ends, group, tile, steps = gm._schedule(
        sizes, 512, 128, visit_empty=False, tail=True)
    n = int(steps)
    assert list(np.asarray(group[:n])) == [0, 2, 2, 2, 2, 3]
    assert list(np.asarray(tile[:n])) == [0, 0, 1, 2, 3, 3]
    assert list(np.asarray(starts)) == [0, 100, 100, 400, 450]
    assert list(np.asarray(ends)) == [100, 100, 400, 450, 450]
    _, _, group, tile, steps = gm._schedule(
        jnp.asarray([100, 0, 100], jnp.int32), 512, 128, visit_empty=True,
        tail=False)
    n = int(steps)
    assert list(np.asarray(group[:n])) == [0, 1, 2, 2]
    assert list(np.asarray(tile[:n])) == [0, 0, 0, 1]
    _, _, group, tile, steps = gm._schedule(
        jnp.asarray([100, 0, 100], jnp.int32), 512, 128, visit_empty=False,
        tail=True)
    n = int(steps)        # rows 200 .. 511: tiles 2 and 3 written 0
    assert list(np.asarray(group[:n])) == [0, 2, 2, 3, 3]
    assert list(np.asarray(tile[:n])) == [0, 0, 1, 2, 3]


# (block rows, width, expert width, held experts) of each decoder cell's
# walk, and shapes the rule leaves to `ragged_dot`
CELLS = {"train-ssm16k": (7680, 2688, 1856, 8),
         "train-vl8k": (20480, 2048, 768, 16),
         "train-lc16k": (20480, 2048, 512, 32),
         "train-mtp8k": (10240, 2048, 768, 16)}
RAGGED = {"the tests' presets": (512, 64, 32, 4),
          "rows no whole row tiles": (7000, 2048, 768, 16),
          "a width inside a lane tile": (1024, 2000, 768, 16),
          "an expert narrower than a lane tile": (1024, 2048, 96, 16),
          "an expert off the packed sublanes": (1024, 2048, 1000, 16),
          "a width too wide for VMEM": (1024, 32768, 1024, 8)}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_every_cells_walk_takes_the_kernels(cell):
    assert gm.inside_kernel(*CELLS[cell]) == 1.0


@pytest.mark.parametrize("why", sorted(RAGGED))
def test_other_shapes_keep_ragged_dot(why):
    assert gm.inside_kernel(*RAGGED[why]) == 0.0


def test_the_blocks_fit_and_take_a_whole_contraction():
    """At train-ssm16k's widths: an expert's whole [2688, 1856] a block
    forward, dx's whole 2,688 columns, the weight gradient's blocks the
    largest that fit; every step under `VMEM_BUDGET`."""
    up = gm.tiling(7680, 2688, 1856, 8)
    down = gm.tiling(7680, 1856, 2688, 8)
    assert up == gm.Tiles(256, 1856, 2688, 896, 1856)
    assert down == gm.Tiles(256, 2688, 1856, 1856, 896)
    for (K, N), t in (((2688, 1856), up), ((1856, 2688), down)):
        assert gm._gmm_need(t.rows, K, t.fwd_n) <= gm.VMEM_BUDGET
        assert gm._gmm_need(t.rows, N, t.dx_n, 2, 2) <= gm.VMEM_BUDGET
        assert gm._dw_need(t.rows, t.dw_k, t.dw_n) <= gm.VMEM_BUDGET


def _walk(gated, N=256, D=128, F=128, E=8, k=2, G=4, first=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (N, D), jnp.float32)
    wr = 0.3 * jax.random.normal(ks[1], (D, E), jnp.float32)
    wg, wu = (0.1 * jax.random.normal(kk, (G, D, F), jnp.float32)
              for kk in ks[2:4])
    wd = 0.1 * jax.random.normal(ks[4], (G, F, D), jnp.float32)
    ct = jax.random.normal(ks[5], (N, D), jnp.float32)

    def loss(x, wg, wu, wd):
        experts, gates = moe.route_all(wr, x, k)
        y = moe.held_experts_ffn(x, experts, gates, wg if gated else None,
                                 wu, wd, first, E, 256)[0]
        return jnp.sum(y * ct)
    return jax.value_and_grad(loss, argnums=(0, 1, 2, 3)), (x, wg, wu, wd)


@pytest.mark.parametrize("gated", [True, False], ids=["silu", "relu2"])
def test_the_walk_with_the_kernels_is_the_walk_without(monkeypatch, gated):
    """`held_experts_ffn` over blocks of 256 pairs whose experts are split
    across blocks, its products as the kernels (interpreted) against
    `ragged_dot`: value and every gradient alike in float32."""
    fn, args = _walk(gated)
    monkeypatch.setattr(gm, "inside_kernel", lambda *shape: 0.0)
    want = fn(*args)
    monkeypatch.setattr(gm, "inside_kernel", lambda *shape: 1.0)
    got = fn(*args)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)
    if not gated:           # the gate's weights are not read
        assert not np.any(np.asarray(got[1][1]))


@pytest.mark.parametrize("gated", [True, False], ids=["silu", "relu2"])
def test_the_tests_presets_lower_to_ragged_dot(gated):
    """At the widths the other tests use, the rule says 0 and the walk's
    jaxpr holds `ragged_dot` and no Pallas call."""
    fn, args = _walk(gated, D=64, F=32)
    text = str(jax.make_jaxpr(fn)(*args))
    assert "ragged_dot" in text and "pallas_call" not in text
    assert moe.experts_kernel(256, 2, 64, 32, 4, 8, 256) == 0.0
