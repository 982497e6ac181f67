"""Grouped matrix products over rows sorted by group, as Pallas kernels: the
held experts' products of `parallel/moe.py held_experts_ffn`.

    out[rows of group g] = lhs[rows of group g] @ rhs[g]

lhs [rows, K], rhs [G, K, N], `sizes` [G] int32: group g holds the
`sizes[g]` rows after those of the groups before it; rows past the last
group read as 0 (`lax.ragged_dot`'s answer, and its arithmetic: operands in
their dtype, float32 sums, the result in the dtype asked for). The
backward is two kernels: dx through the same kernel with rhs transposed
(the cotangent rounded to lhs's dtype first, as the chip's default
precision rounds a float32 operand), and the weight gradient [G, K, N]
summed over each group's rows by a transposed kernel, in rhs's dtype.

The grid walks (group, row tile) pairs in order (megablox's schedule,
`jax.experimental.pallas.ops.tpu.megablox`): a row tile that two groups
share is visited once by each, a visit keeps its group's rows, and the
output block stays in VMEM across the visits. A product holds a whole
contraction in a block (no K loop: at the cells' widths the experts'
matrices fit), so an expert's matrix is read once a column block, however
many row tiles its group has. Every row tile past the last group is
visited once and written 0, with no product. The weight gradient keeps its
group's [tk, tn] sum in float32 across the group's row tiles and masks a
tile's rows only where the tile is shared.

Built once per shape: every entry that reaches a `pallas_call` is a
module-level `jax.jit` (`gmm`, `gmm_backward`) whose tiling and dtypes are
static and hashable, so the same shape in another layer, in a
rematerialised forward or in the backward's recompute hits jax's trace
cache and its lowering cache: a step builds each distinct kernel once, not
once per call site. Tiles come from `tiling(rows, K, N, G)` alone;
`inside_kernel` says from shapes where the kernels run, and where they do
not the caller keeps `lax.ragged_dot`, which stays as the kernels'
reference in the tests.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import compiler_params
from .backend import resolve_interpret as _resolve_interpret

LANES = 128
ROW_TILE = 256          # rows a tile (a tile two groups share is computed
#                         twice, so a smaller tile wastes less of a walk
#                         whose groups are a few tiles each)
KERNEL_VMEM = 64 << 20  # the kernels' VMEM limit (scoped default 16, the
#                         chip's 128): an expert's whole [K, N] in bf16
#                         twice, 20 MB at train-ssm16k's widths
VMEM_BUDGET = 48 << 20  # what `_need` may ask of it, leaving Mosaic room


class Tiles(NamedTuple):
    """A product's blocks: rows a tile, the forward's output columns a
    block, dx's output columns a block, the weight gradient's K rows and N
    columns a block."""
    rows: int
    fwd_n: int
    dx_n: int
    dw_k: int
    dw_n: int


# ------------------------------------------------------------------ the rules
def _gmm_need(tm, K, tn, in_bytes=2, out_bytes=4):
    """VMEM of a product step: lhs [tm, K] and rhs [K, tn] and the output
    [tm, tn], two buffers each, and the product's float32 result."""
    return (2 * (tm * K + K * tn) * in_bytes + 2 * tm * tn * out_bytes
            + 2 * tm * tn * 4)


def _dw_need(tm, tk, tn, in_bytes=2, out_bytes=2):
    """VMEM of a weight-gradient step: lhs [tm, tk] and dy [tm, tn] two
    buffers each and their masked copies, the output [tk, tn] twice, the
    float32 sum and the product's float32 result."""
    return (3 * tm * (tk + tn) * in_bytes + 2 * tk * tn * out_bytes
            + 2 * tk * tn * 4)


def _blocks(W):
    """Widths of a block of W columns, widest first: W whole, then each
    whole number of lane tiles that divides W."""
    return [W] + [n for n in range(W - W % LANES, 0, -LANES)
                  if n < W and W % n == 0]


def _widest(W, fits):
    """The widest block of W columns that `fits`; the narrowest where none
    does."""
    options = _blocks(W)
    return next((n for n in options if fits(n)), options[-1])


def tiling(rows, K, N, G):
    """The blocks of out [rows, N] = lhs [rows, K] @ rhs [G, K, N], bf16
    operands, and of its backward: `ROW_TILE` rows (or all where fewer),
    then the widest column blocks whose steps fit `VMEM_BUDGET` (for the
    weight gradient the [tk, tn] of most area, the wider tn at a tie)."""
    del G       # the schedule adapts to the groups; the blocks do not
    tm = min(ROW_TILE, rows)
    fwd_n = _widest(N, lambda n: _gmm_need(tm, K, n) <= VMEM_BUDGET)
    dx_n = _widest(K, lambda n: _gmm_need(tm, N, n, 2, 2) <= VMEM_BUDGET)
    pairs = sorted(((k * n, n, k) for k in _blocks(K) for n in _blocks(N)),
                   reverse=True)
    _, dw_n, dw_k = next(
        (p for p in pairs if _dw_need(tm, p[2], p[1]) <= VMEM_BUDGET),
        pairs[-1])
    return Tiles(tm, fwd_n, dx_n, dw_k, dw_n)


def _fits(rows, K, N, G):
    """Every step of `tiling`'s blocks within `VMEM_BUDGET`."""
    t = tiling(rows, K, N, G)
    return max(_gmm_need(t.rows, K, t.fwd_n),
               _gmm_need(t.rows, N, t.dx_n, 2, 2),
               _dw_need(t.rows, t.dw_k, t.dw_n)) <= VMEM_BUDGET


def inside_kernel(rows, D, F, G):
    """1.0 where an expert layer's products over blocks of `rows` pairs (x
    [rows, D] through [G, D, F] and back through [G, F, D]) run as the
    kernels, 0.0 where as `lax.ragged_dot`: the rows whole row tiles, D
    whole lane tiles, F at least one lane tile and whole packed sublanes
    (1,856 = 14.5 lane tiles is taken whole in a block), and every step of
    both products and their backwards fits `VMEM_BUDGET`. Shapes alone
    decide."""
    ok = (rows % ROW_TILE == 0 and D % LANES == 0 and F >= LANES
          and F % 16 == 0 and _fits(rows, D, F, G) and _fits(rows, F, D, G))
    return 1.0 if ok else 0.0


# -------------------------------------------------------------- the schedule
def _schedule(sizes, rows, tm, *, visit_empty, tail):
    """The grid's walk, one step a (group, row tile) pair, groups in order
    and each group's tiles in order, so a tile two groups share is visited
    twice in a row. Returns (each group's first row [G + 1], one past its
    last [G + 1], the group of each step, the row tile of each step, the
    steps). With `visit_empty` an empty group gets a step (the weight
    gradient writes its zeros); with `tail` the row tiles past the last
    group get a step each, under group G (the product writes their
    zeros)."""
    G = sizes.shape[0]
    i32 = jnp.int32
    ends = jnp.cumsum(sizes).astype(i32)
    starts = ends - sizes
    first = starts // tm
    count = jnp.where(sizes > 0, (ends + tm - 1) // tm - first,
                      1 if visit_empty else 0).astype(i32)
    total = ends[-1]
    if tail:
        past = (total + tm - 1) // tm
        first = jnp.append(first, past)
        count = jnp.append(count, rows // tm - past)
    starts = jnp.append(starts, total)
    ends = jnp.append(ends, total)
    done = jnp.cumsum(count)
    step = jnp.arange(rows // tm + G, dtype=i32)
    group = jnp.minimum(jnp.sum(step[:, None] >= done[None, :], 1),
                        count.shape[0] - 1).astype(i32)
    tile = first[group] + step - (done - count)[group]
    return (starts, ends, group, jnp.clip(tile, 0, rows // tm - 1),
            done[-1])


# ---------------------------------------------------------------- the kernels
def _gmm_kernel(starts, ends, group, tile, lhs_ref, rhs_ref, out_ref, *,
                tm, G, dims):
    s = pl.program_id(1)
    g, m = group[s], tile[s]
    # the first visit of a row tile starts from zeros: no other group's
    # rows, nor rows past the last group, read anything else
    fresh = (s == 0) | (m != tile[jnp.maximum(s - 1, 0)])

    @pl.when(fresh)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(g < G)
    def _():
        acc = jax.lax.dot_general(lhs_ref[...], rhs_ref[...], (dims, ((), ())),
                                  preferred_element_type=jnp.float32)
        row = m * tm + jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
        mine = (row >= starts[g]) & (row < ends[g])
        out_ref[...] = jnp.where(mine, acc, out_ref[...].astype(
            jnp.float32)).astype(out_ref.dtype)


def _dw_kernel(starts, ends, group, tile, lhs_ref, dy_ref, out_ref, acc_ref,
               *, tm):
    s, last = pl.program_id(2), pl.num_programs(2) - 1
    g, m = group[s], tile[s]
    lo, hi = starts[g], ends[g]

    @pl.when((s == 0) | (g != group[jnp.maximum(s - 1, 0)]))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    add = lambda a, b: jax.lax.dot_general(
        a, b, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    whole = (lo <= m * tm) & (hi >= (m + 1) * tm)

    @pl.when(whole)
    def _():
        acc_ref[...] += add(lhs_ref[...], dy_ref[...])

    @pl.when(~whole & (hi > lo))
    def _():
        def mine(ref):
            row = m * tm + jax.lax.broadcasted_iota(jnp.int32, ref.shape, 0)
            return jnp.where((row >= lo) & (row < hi),
                             ref[...].astype(jnp.float32), 0).astype(ref.dtype)
        acc_ref[...] += add(mine(lhs_ref), mine(dy_ref))

    @pl.when((s == last) | (g != group[jnp.minimum(s + 1, last)]))
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _check(*extent_and_block):
    """Each extent a whole number of its blocks."""
    pairs = list(zip(extent_and_block[::2], extent_and_block[1::2]))
    if any(n % b for n, b in pairs):
        raise ValueError(f"blocks {pairs} do not tile their extents")


def _gmm_call(lhs, rhs, sizes, tm, tn, transpose_rhs, out_dtype, interpret):
    rows, K = lhs.shape
    G = rhs.shape[0]
    N = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    _check(rows, tm, N, tn)
    sched = _schedule(sizes, rows, tm, visit_empty=False, tail=True)
    if transpose_rhs:       # rhs [G, N, K]
        rhs_spec = pl.BlockSpec(
            (None, tn, K), lambda n, s, a, b, grp, t: (
                jnp.minimum(grp[s], G - 1), n, 0))
    else:
        rhs_spec = pl.BlockSpec(
            (None, K, tn), lambda n, s, a, b, grp, t: (
                jnp.minimum(grp[s], G - 1), 0, n))
    dims = ((1,), (1,)) if transpose_rhs else ((1,), (0,))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, G=G, dims=dims),
        out_shape=jax.ShapeDtypeStruct((rows, N), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(N // tn, sched[4]),
            in_specs=[pl.BlockSpec((tm, K),
                                   lambda n, s, a, b, grp, t: (t[s], 0)),
                      rhs_spec],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda n, s, a, b, grp, t: (t[s], n))),
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * K * N, transcendentals=0,
            bytes_accessed=(lhs.size * lhs.dtype.itemsize * (N // tn)
                            + rhs.size * rhs.dtype.itemsize
                            + rows * N * jnp.dtype(out_dtype).itemsize)),
        interpret=interpret, name="grouped_matmul",
        **compiler_params(interpret, ("parallel", "arbitrary"),
                          KERNEL_VMEM))(
            *sched[:4], lhs, rhs)


def _dw_call(lhs, dy, sizes, G, tm, tk, tn, out_dtype, interpret):
    rows, K = lhs.shape
    N = dy.shape[1]
    _check(rows, tm, K, tk, N, tn)
    sched = _schedule(sizes, rows, tm, visit_empty=True, tail=False)
    return pl.pallas_call(
        functools.partial(_dw_kernel, tm=tm),
        out_shape=jax.ShapeDtypeStruct((G, K, N), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(K // tk, N // tn, sched[4]),
            in_specs=[pl.BlockSpec((tm, tk),
                                   lambda k, n, s, a, b, grp, t: (t[s], k)),
                      pl.BlockSpec((tm, tn),
                                   lambda k, n, s, a, b, grp, t: (t[s], n))],
            out_specs=pl.BlockSpec((None, tk, tn),
                                   lambda k, n, s, a, b, grp, t: (
                                       grp[s], k, n)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * K * N, transcendentals=0,
            bytes_accessed=(lhs.size * lhs.dtype.itemsize * (N // tn)
                            + dy.size * dy.dtype.itemsize * (K // tk)
                            + G * K * N * jnp.dtype(out_dtype).itemsize)),
        interpret=interpret, name="grouped_matmul_dw",
        **compiler_params(interpret, ("parallel", "parallel", "arbitrary"),
                          KERNEL_VMEM))(
            *sched[:4], lhs, dy)


# ------------------------------------------------- the entries, jitted once
@functools.partial(jax.jit, static_argnames=("tiles", "out_dtype",
                                             "interpret"))
def gmm(lhs, rhs, sizes, *, tiles, out_dtype, interpret):
    """out [rows, N] = each group's rows of lhs [rows, K] @ its rhs [G, K,
    N], in `out_dtype`; rows past the last group 0."""
    with jax.named_scope("experts"):
        return _gmm_call(lhs, rhs, sizes, tiles.rows, tiles.fwd_n, False,
                         out_dtype, interpret)


@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def gmm_backward(lhs, rhs, sizes, g, *, tiles, interpret):
    """(dx [rows, K] in lhs's dtype, dW [G, K, N] in rhs's) of `gmm` for
    its cotangent g [rows, N]."""
    with jax.named_scope("experts"):
        g = g.astype(lhs.dtype)
        dx = _gmm_call(g, rhs, sizes, tiles.rows, tiles.dx_n, True,
                       lhs.dtype, interpret)
        dw = _dw_call(lhs, g, sizes, rhs.shape[0], tiles.rows, tiles.dw_k,
                      tiles.dw_n, rhs.dtype, interpret)
        return dx, dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _grouped(lhs, rhs, sizes, out_dtype, interpret):
    return gmm(lhs, rhs, sizes, tiles=tiling(*lhs.shape, rhs.shape[2],
                                              rhs.shape[0]),
               out_dtype=out_dtype, interpret=interpret)


def _grouped_fwd(lhs, rhs, sizes, out_dtype, interpret):
    return _grouped(lhs, rhs, sizes, out_dtype, interpret), (lhs, rhs, sizes)


def _grouped_bwd(out_dtype, interpret, res, g):
    lhs, rhs, sizes = res
    dx, dw = gmm_backward(lhs, rhs, sizes, g,
                          tiles=tiling(*lhs.shape, rhs.shape[2],
                                       rhs.shape[0]),
                          interpret=interpret)
    return dx, dw, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(lhs, rhs, sizes, preferred_element_type=jnp.float32):
    """`lax.ragged_dot(lhs, rhs, sizes, preferred_element_type=...)` as the
    kernels, with its gradients: lhs [rows, K], rhs [G, K, N], sizes [G]
    int32 summing to at most rows, which must be whole row tiles of
    `tiling`. On the CPU the kernels run in the Pallas interpreter."""
    return _grouped(lhs, rhs, sizes.astype(jnp.int32),
                    jnp.dtype(preferred_element_type),
                    _resolve_interpret(None))
