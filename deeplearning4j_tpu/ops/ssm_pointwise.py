"""The Mamba-2 mixer's two position-wise stages around its scan (the layer
kind `mamba2`, `nn/conf/layers/mamba2.py`), each as a pair of Pallas kernels
or in XLA:

    conv_silu   out_t = SiLU(b + sum_k w[:, k] x_{t-K+1+k}), depthwise and
                causal over x [B, T, C], zeros before position 0
    gated_norm  g = y SiLU(z), then RMSNorm over each of G equal runs of the
                channels, times w

Both work in float32 inside and give their input's dtype out, and each is a
`jax.custom_vjp` whose residuals are its inputs alone: the backward kernel
makes the forward's insides again in VMEM (the pre-activation, a group's
rsqrt) and nothing of them reaches HBM. Each way is one read of every
operand and one write of every result.

The kernels read and write every array of positions transposed, a position
down a column ([B, C, T], as `ops/ssd.py`'s kernels do), so that XLA keeps
the layer's arrays positions-minor around all three stages and copies none
of them; and both read their channels of a wider array where they lie
(the layer hands them W_in's product [z ; xBC ; dt] whole: the convolution
reads `lo` channels in, the gate the first; no slice of it is copied for
them), giving its other channels no gradient. The convolution walks a block of channels along its positions
block by block; a tap is a lane rotation of the block, and the K - 1
positions before a block come from the previous block's last lanes, carried
in VMEM (forward), or from a halo read of 128 lanes (backward, which walks
the blocks from the last, carrying the gradient's first lanes the other
way, and sums dw and db in float32 along the walk). The norm walks one
group's channels a block of positions at a time, a group's sums running
down the block's columns.

Where the shapes do not tile the chip (`conv_inside_kernel`,
`norm_inside_kernel`: shapes alone decide) the XLA forms run, each under
its own `jax.checkpoint` so that it keeps nothing of its float32 insides for
the backward; they are the kernels' reference in the tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import compiler_params
from .backend import resolve_interpret as _resolve_interpret

LANES = 128
CONV_ROWS = 256         # channels a convolution block, and
CONV_LANES = 2048       # positions, at most (tools/ssm_pointwise_times.py
#                         at the cell's widths on a v5e: 0.90 / 3.58 ms
#                         forward / both against 1.01 / 3.82 at 256 x 1024;
#                         512 x 2048 and 256 x 4096 pass `KERNEL_VMEM`)
NORM_BLOCK = 512 * 1024  # a norm block's channels x positions, at most
#                         (a group of 512 by 1,024 positions: 0.72 / 2.52
#                         ms against 0.75 / 2.63 by 512; by 2,048 the
#                         backward passes `KERNEL_VMEM`)
KERNEL_VMEM = 32 << 20  # the kernels' VMEM limit (the scoped default is 16)


# ------------------------------------------------------------ the XLA forms
def causal_depthwise_conv(x, w, b):
    """x [B, T, C]; w [C, K]; b [C]: y_t = b + sum_k w[:, k] x_{t-K+1+k},
    zeros before position 0, summed in float32."""
    K, T = w.shape[1], x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (K - 1, 0), (0, 0)))
    w = w.astype(jnp.float32)
    return b.astype(jnp.float32) + sum(
        padded[:, k:k + T] * w[:, k] for k in range(K))


def gated_group_norm(y, z, w, groups, eps):
    """y * SiLU(z), then RMSNorm over each of `groups` equal runs of the
    last axis, times w; float32 inside, y's dtype out."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    by = g.reshape(g.shape[:-1] + (groups, -1))
    by = by * jax.lax.rsqrt(jnp.mean(by * by, -1, keepdims=True) + eps)
    return (by.reshape(g.shape) * w.astype(jnp.float32)).astype(y.dtype)


# ------------------------------------------------------------------ the rules
def _block(T, most):
    """The most positions (a whole number of lane tiles, at most `most`)
    that divide T, or 0 where T is no whole number of lane tiles."""
    if T % LANES:
        return 0
    return max(n for n in range(LANES, most + 1, LANES) if T % n == 0)


def conv_inside_kernel(C, T, K, lo=0):
    """1.0 where a convolution of K taps over C channels (the first `lo`
    channels into the array it reads) and T positions runs as the Pallas
    kernels, 0.0 where as XLA: channels and their offset whole blocks of
    128, positions whole lane tiles, the taps' reach inside one tile.
    Shapes alone decide."""
    return 1.0 if (C % LANES == 0 and lo % LANES == 0 and T % LANES == 0
                   and 0 < K <= LANES) else 0.0


def norm_inside_kernel(width, groups, T):
    """1.0 where the gated norm of `width` channels in `groups` over T
    positions runs as the Pallas kernels, 0.0 where as XLA: a group whole
    packed sublanes and whole in a block of `NORM_BLOCK` by a lane tile,
    positions whole lane tiles. Shapes alone decide."""
    if groups <= 0 or width % groups:
        return 0.0
    run = width // groups
    return 1.0 if (run % 16 == 0 and run * LANES <= NORM_BLOCK
                   and T % LANES == 0) else 0.0


def _conv_rows(C, lo):
    """Channels a block: `CONV_ROWS` where they tile both the convolution's
    channels and the offset of its first in the array it reads, else 128."""
    return CONV_ROWS if C % CONV_ROWS == 0 and lo % CONV_ROWS == 0 else LANES


# --------------------------------------------------------------- in a block
def _across(a):
    """[b, T, C] <-> [b, C, T]: a position down a column."""
    return jnp.swapaxes(a, 1, 2)


def _tiles(a):
    """[rows, n 128] -> its n lane tiles [rows, 128]."""
    return [a[:, j * LANES:(j + 1) * LANES] for j in range(a.shape[1] // LANES)]


def _rolls(x, K, back=False):
    """A lane tile x [rows, 128] and its rotations by 1 .. K - 1 lanes: lane
    t of the s-th holds x's t - s (with `back`, t + s), mod 128. Each tile
    is rotated once and read by its own taps and its neighbour's."""
    return [x] + [pltpu.roll(x, LANES - s if back else s, 1)
                  for s in range(1, K)]


def _later(rolled, before, lane):
    """[x moved s positions later, for s = 0 .. K - 1] of a tile from its
    rotations and those of the tile before it: lane t holds x's t - s, the
    first s lanes the last s of the tile before."""
    return [rolled[0]] + [jnp.where(lane < s, before[s], rolled[s])
                          for s in range(1, len(rolled))]


def _earlier(rolled, after, lane):
    """[x moved s positions earlier, for s = 0 .. K - 1] from the `back`
    rotations of a tile and of the tile after it: lane t holds x's t + s,
    the last s lanes the first s of the tile after."""
    return [rolled[0]] + [jnp.where(lane >= LANES - s, after[s], rolled[s])
                          for s in range(1, len(rolled))]


def _pre(moved, w, bias):
    """b + sum_k w_k x_{t-K+1+k} of a tile, `moved` its K later moves; w a
    tap's [rows, 128] each, the bias [rows, 128]."""
    K, pre = len(w), bias
    for k in range(K):
        pre = pre + w[k] * moved[K - 1 - k]
    return pre


def _silu_grad(pre):
    """(SiLU(pre), its derivative), float32."""
    s = jax.nn.sigmoid(pre)
    return pre * s, s * (1.0 + pre * (1.0 - s))


def _lane_sum(a):
    """[rows, n 128] -> [rows, 128]: its lane tiles added (float32)."""
    return functools.reduce(jnp.add, _tiles(a))


def _columns(w_ref, b_ref):
    """The taps [K, rows, 1] and the bias [rows, 1] widened over a tile's
    lanes once a grid step, and the lanes' own index."""
    rows, K = b_ref.shape[0], w_ref.shape[0]
    wide = lambda c: jnp.broadcast_to(c, (rows, LANES))
    return ([wide(w_ref[k]) for k in range(K)], wide(b_ref[...]),
            jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1))


# ---------------------------------------------------------- the convolution
def _conv_fwd_kernel(x_ref, w_ref, b_ref, o_ref, tail):
    """One block of channels and positions: a tile at a time, each reading
    the last lanes of the tile before it (at the block's first, `tail`, the
    previous block's last tile; zeros before position 0)."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        tail[...] = jnp.zeros_like(tail)

    w, bias, lane = _columns(w_ref, b_ref)
    K = len(w)
    before, out = _rolls(tail[...], K), []
    for x in _tiles(x_ref[0].astype(jnp.float32)):
        rolled = _rolls(x, K)
        out.append(_silu_grad(_pre(_later(rolled, before, lane), w, bias))[0])
        before = rolled
    o_ref[0] = jnp.concatenate(out, 1).astype(o_ref.dtype)
    tail[...] = before[0]


def _conv_bwd_kernel(x_ref, halo_ref, w_ref, b_ref, d_ref, dx_ref, dw_ref,
                     db_ref, after, dw_acc, db_acc):
    """One block of channels and positions, the blocks walked from the last
    and the tiles of a block from its last: the pre-activation made again
    from x (the tile before the block is `halo_ref`, read for it, zeros at
    position 0), g = dout SiLU'(pre), dx_t = sum_k w_k g_{t+K-1-k} reading
    the first lanes of g's next tile (`after`: the later block's first
    tile, carried; zeros past the last position), dw and db summed in
    float32 over rows and positions, written at the walk's end."""
    r, c = pl.program_id(1), pl.program_id(2)
    f32 = jnp.float32

    @pl.when(c == 0)
    def _row():
        after[...] = jnp.zeros_like(after)

    @pl.when((r == 0) & (c == 0))
    def _init():
        dw_acc[...] = jnp.zeros_like(dw_acc)
        db_acc[...] = jnp.zeros_like(db_acc)

    w, bias, lane = _columns(w_ref, b_ref)
    K = len(w)
    first = c == pl.num_programs(2) - 1         # the block at position 0
    xs = [_rolls(x, K) for x in _tiles(x_ref[0].astype(f32))]
    halo = _rolls(jnp.where(first, 0.0, halo_ref[0].astype(f32)), K)
    ds = _tiles(d_ref[0].astype(f32))
    nxt, dx = _rolls(after[...], K, back=True), [None] * len(xs)
    dw = [jnp.zeros_like(bias) for _ in range(K)]
    db = jnp.zeros_like(bias)
    for j in reversed(range(len(xs))):
        moved = _later(xs[j], xs[j - 1] if j else halo, lane)
        g = ds[j] * _silu_grad(_pre(moved, w, bias))[1]
        rolled = _rolls(g, K, back=True)
        ahead = _earlier(rolled, nxt, lane)
        acc = w[0] * ahead[K - 1]
        for k in range(1, K):
            acc = acc + w[k] * ahead[K - 1 - k]
        dx[j] = acc
        for k in range(K):
            dw[k] = dw[k] + g * moved[K - 1 - k]
        db = db + g
        nxt = rolled
    dx_ref[0] = jnp.concatenate(dx, 1).astype(dx_ref.dtype)
    after[...] = nxt[0]
    for k in range(K):
        dw_acc[k] += dw[k]
    db_acc[...] += db

    @pl.when((r == pl.num_programs(1) - 1) & (c == pl.num_programs(2) - 1))
    def _emit():
        for k in range(K):
            dw_ref[k] = jnp.sum(dw_acc[k], 1, keepdims=True)
        db_ref[...] = jnp.sum(db_acc[...], 1, keepdims=True)


def _conv_operands(w, b):
    """w [C, K] as [K, C, 1] and b [C] as [C, 1], float32: a tap a column."""
    f32 = jnp.float32
    return jnp.transpose(w).astype(f32)[:, :, None], b.astype(f32)[:, None]


def _conv_fwd_call(x, w, b, lo, interpret):
    n, T, _ = x.shape
    C, K = w.shape
    rows, lanes = _conv_rows(C, lo), _block(T, CONV_LANES)
    at = lo // rows
    block = pl.BlockSpec((1, rows, lanes), lambda i, ch, c: (i, ch, c))
    out = pl.pallas_call(
        _conv_fwd_kernel, grid=(n, C // rows, T // lanes),
        in_specs=[pl.BlockSpec((1, rows, lanes),
                               lambda i, ch, c: (i, at + ch, c)),
                  pl.BlockSpec((K, rows, 1), lambda i, ch, c: (0, ch, 0)),
                  pl.BlockSpec((rows, 1), lambda i, ch, c: (ch, 0))],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((n, C, T), x.dtype),
        scratch_shapes=[pltpu.VMEM((rows, LANES), jnp.float32)],
        interpret=interpret, name="ssm_conv_fwd",
        **compiler_params(interpret, ("parallel", "parallel", "arbitrary"),
                          KERNEL_VMEM))(
            _across(x), *_conv_operands(w, b))
    return _across(out)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _conv_kernels(x, w, b, lo, interpret):
    return _conv_fwd_call(x, w, b, lo, interpret)


def _conv_kernels_fwd(x, w, b, lo, interpret):
    return _conv_fwd_call(x, w, b, lo, interpret), (x, w, b)


def _conv_kernels_bwd(lo, interpret, res, dout):
    x, w, b = res
    n, T, width = x.shape
    C, K = w.shape
    rows, lanes = _conv_rows(C, lo), _block(T, CONV_LANES)
    nb, tiles, first = T // lanes, lanes // LANES, lo // rows
    at = lambda c: nb - 1 - c                     # the blocks from the last
    block = pl.BlockSpec((1, rows, lanes), lambda ch, i, c: (i, ch, at(c)))
    source = pl.BlockSpec((1, rows, lanes),
                          lambda ch, i, c: (i, first + ch, at(c)))
    halo = pl.BlockSpec((1, rows, LANES), lambda ch, i, c: (
        i, first + ch, jnp.maximum(at(c) * tiles - 1, 0)))
    taps = pl.BlockSpec((K, rows, 1), lambda ch, i, c: (0, ch, 0))
    column = pl.BlockSpec((rows, 1), lambda ch, i, c: (ch, 0))
    f32 = jnp.float32
    xt = _across(x)
    dx, dw, db = pl.pallas_call(
        _conv_bwd_kernel, grid=(C // rows, n, nb),
        in_specs=[source, halo, taps, column, block],
        out_specs=[block, taps, column],
        out_shape=[jax.ShapeDtypeStruct((n, C, T), x.dtype),
                   jax.ShapeDtypeStruct((K, C, 1), f32),
                   jax.ShapeDtypeStruct((C, 1), f32)],
        scratch_shapes=[pltpu.VMEM((rows, LANES), f32),
                        pltpu.VMEM((K, rows, LANES), f32),
                        pltpu.VMEM((rows, LANES), f32)],
        interpret=interpret, name="ssm_conv_bwd",
        **compiler_params(interpret, ("parallel", "arbitrary", "arbitrary"),
                          KERNEL_VMEM))(
            xt, xt, *_conv_operands(w, b), _across(dout.astype(x.dtype)))
    # the channels read are [lo, lo + C) of x's; the others get nothing
    dx = jnp.pad(_across(dx), ((0, 0), (0, 0), (lo, width - lo - C)))
    return (dx, jnp.transpose(dw[:, :, 0]).astype(w.dtype),
            db[:, 0].astype(b.dtype))


_conv_kernels.defvjp(_conv_kernels_fwd, _conv_kernels_bwd)


def conv_silu(x, w, b, lo=0):
    """SiLU of the causal depthwise convolution over the channels [lo, lo +
    C) of x [B, T, W]; w [C, K]; b [C] -> [B, T, C] in x's dtype, float32
    inside. The kernels read those channels where they lie (`lo`: no slice
    of x is copied for them) and give the others no gradient. As the
    kernels `ssm_conv_fwd` / `ssm_conv_bwd` where `conv_inside_kernel` says
    so; else the XLA form, keeping only its inputs for the backward."""
    T, (C, K) = x.shape[1], w.shape
    if conv_inside_kernel(C, T, K, lo):
        return _conv_kernels(x, w, b, lo, _resolve_interpret(None))
    return jax.checkpoint(lambda a, w, b: jax.nn.silu(
        causal_depthwise_conv(a, w, b)).astype(a.dtype))(
            x[..., lo:lo + C], w, b)


# --------------------------------------------------------- the gated norm
def _normed(y_ref, z_ref, eps):
    """(g = y SiLU(z), the group's rsqrt a position [1, lanes], z's
    sigmoid), float32: the block is one group's channels."""
    f32 = jnp.float32
    y, z = y_ref[0].astype(f32), z_ref[0].astype(f32)
    s = jax.nn.sigmoid(z)
    g = y * z * s
    r = jax.lax.rsqrt(jnp.mean(g * g, 0, keepdims=True) + eps)
    return y, z, s, g, r


def _norm_fwd_kernel(y_ref, z_ref, w_ref, o_ref, *, eps):
    _, _, _, g, r = _normed(y_ref, z_ref, eps)
    o_ref[0] = (g * r * w_ref[...]).astype(o_ref.dtype)


def _norm_bwd_kernel(y_ref, z_ref, w_ref, d_ref, dy_ref, dz_ref, dw_ref,
                     acc, *, eps):
    """n = g r, out = n w: dn = dout w, dg = r (dn - n mean(dn n)) over the
    group, dy = dg z s, dz = dg y s (1 + z (1 - s)); dw = sum dout n over
    rows and positions, in float32 along the walk."""
    r_, c = pl.program_id(1), pl.program_id(2)

    @pl.when((r_ == 0) & (c == 0))
    def _init():
        acc[...] = jnp.zeros_like(acc)

    y, z, s, g, r = _normed(y_ref, z_ref, eps)
    d = d_ref[0].astype(jnp.float32)
    n = g * r
    dn = d * w_ref[...]
    dg = r * (dn - n * jnp.mean(dn * n, 0, keepdims=True))
    dy_ref[0] = (dg * z * s).astype(dy_ref.dtype)
    dz_ref[0] = (dg * y * s * (1.0 + z * (1.0 - s))).astype(dz_ref.dtype)
    acc[...] += _lane_sum(d * n)

    @pl.when((r_ == pl.num_programs(1) - 1) & (c == pl.num_programs(2) - 1))
    def _emit():
        dw_ref[...] = jnp.sum(acc[...], 1, keepdims=True)


def _norm_specs(width, groups, T, grid_of):
    """(the block of y, of its likes and of the gate's first channels; w's
    column; a group's channels, positions a block)."""
    run = width // groups
    lanes = _block(T, NORM_BLOCK // run // LANES * LANES)
    block = pl.BlockSpec((1, run, lanes), lambda *ix: grid_of(*ix))
    column = pl.BlockSpec((run, 1), lambda *ix: (grid_of(*ix)[1], 0))
    return block, column, run, lanes


def _norm_fwd_call(y, z, w, groups, eps, interpret):
    n, T, width = y.shape
    block, column, _, lanes = _norm_specs(width, groups, T,
                                          lambda i, g, c: (i, g, c))
    out = pl.pallas_call(
        functools.partial(_norm_fwd_kernel, eps=eps),
        grid=(n, groups, T // lanes), in_specs=[block, block, column],
        out_specs=block, out_shape=jax.ShapeDtypeStruct((n, width, T), y.dtype),
        interpret=interpret, name="ssm_norm_fwd",
        **compiler_params(interpret, ("parallel", "parallel", "parallel"),
                          KERNEL_VMEM))(
            _across(y), _across(z), w.astype(jnp.float32)[:, None])
    return _across(out)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _norm_kernels(y, z, w, groups, eps, interpret):
    return _norm_fwd_call(y, z, w, groups, eps, interpret)


def _norm_kernels_fwd(y, z, w, groups, eps, interpret):
    return _norm_fwd_call(y, z, w, groups, eps, interpret), (y, z, w)


def _norm_kernels_bwd(groups, eps, interpret, res, dout):
    y, z, w = res
    n, T, width = y.shape
    block, column, run, lanes = _norm_specs(width, groups, T,
                                            lambda g, i, c: (i, g, c))
    f32 = jnp.float32
    dy, dz, dw = pl.pallas_call(
        functools.partial(_norm_bwd_kernel, eps=eps),
        grid=(groups, n, T // lanes),
        in_specs=[block, block, column, block],
        out_specs=[block, block, column],
        out_shape=[jax.ShapeDtypeStruct((n, width, T), y.dtype),
                   jax.ShapeDtypeStruct((n, width, T), z.dtype),
                   jax.ShapeDtypeStruct((width, 1), f32)],
        scratch_shapes=[pltpu.VMEM((run, LANES), f32)],
        interpret=interpret, name="ssm_norm_bwd",
        **compiler_params(interpret, ("parallel", "arbitrary", "arbitrary"),
                          KERNEL_VMEM))(
            _across(y), _across(z), w.astype(f32)[:, None],
            _across(dout.astype(y.dtype)))
    # the gate is z's first `width` channels; the others get nothing
    dz = jnp.pad(_across(dz), ((0, 0), (0, 0), (0, z.shape[2] - width)))
    return _across(dy), dz, dw[:, 0].astype(w.dtype)


_norm_kernels.defvjp(_norm_kernels_fwd, _norm_kernels_bwd)


def gated_norm(y, z, w, groups, eps):
    """y * SiLU(z), then RMSNorm over each of `groups` equal runs of the
    channels, times w: y [B, T, W], the gate the first W channels of z [B,
    T, Wz]; w [W] -> [B, T, W] in y's dtype, float32 inside. The kernels
    read the gate where it lies (no slice of z is copied) and give z's
    other channels no gradient. As the kernels `ssm_norm_fwd` /
    `ssm_norm_bwd` where `norm_inside_kernel` says so; else the XLA form,
    keeping only its inputs for the backward."""
    _, T, width = y.shape
    if norm_inside_kernel(width, groups, T):
        return _norm_kernels(y, z, w, groups, eps, _resolve_interpret(None))
    return jax.checkpoint(lambda y, z, w: gated_group_norm(
        y, z, w, groups, eps))(y, z[..., :width], w)
