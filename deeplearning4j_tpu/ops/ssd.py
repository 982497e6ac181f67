"""The chunked state-space scan (SSD: Mamba-2, arXiv:2405.21060 section 6),
forward and backward, as two Pallas kernels or in XLA products.

A state-space head h carries a state S [P, N] along the sequence:

    S_t = a_t S_{t-1} + dt_t x_t (outer) B_t,   y_t = S_t C_t,   S_{-1} = 0

with a_t = exp(dt_t A_h) in (0, 1]. Position by position that is T
sequential steps; in chunks of L positions it is products on the MXU inside
a chunk and a recurrence over T / L chunk states between them. With
l_i the sum of log a over the chunk up to and including position i:

    inside a chunk     y_i += sum_{j<=i} (C_i . B_j) exp(l_i - l_j) dt_j x_j
    a chunk's state    S^c  = sum_j exp(l_L - l_j) dt_j x_j (outer) B_j
    across chunks      R_c  = exp(l_L of chunk c) R_{c-1} + S^c
    state to output    y_i += exp(l_i) C_i R_{c-1}

Every exponent is a DIFFERENCE of l taken before the exponential, so it is
<= 0 however small a decay is: never exp(l_i) x exp(-l_j). B and C come by
group (a group of H / G heads reads one B and one C) and are never repeated
in HBM: the head axis is carried as [G, H / G]. Operands of the four
products are in the inputs' dtype with float32 accumulation; l, the decays
and the chunk states are float32. Nothing of size [T, T] and no state a
position is made: the largest transient is the decay of every pair inside
a chunk, [T / L, H, L, L].

The backward is jax's own of the chunked form, but for the recurrence
across chunks, whose VJP is written by hand so that it needs nothing but
its own outputs: G_{c-1} = dR_{c-1} + a_c G_c is the same recurrence run
backwards, da_c = <G_c, R_{c-1}>. The entering states R_{c-1} are NAMED
(`KEEP`), so a rematerialising caller that keeps them
(`jax.checkpoint_policies.save_only_these_names`) runs the recurrence once
a step forward and once backward, and computes the inside of the chunks
again. The recurrence is a `lax.scan` over the T / L chunks: on the chip an
associative scan of the pairs (a, S), log2(T / L) levels each over all the
states, read three times dearer (PERF.md section 6, PR 38) and went.

Where the shapes allow (`inside_kernel`: shapes alone decide), the scan is
two Pallas kernels in place of all of the above (PR 39), over a grid of
(row, group, chunk): `ssd_fwd` walks a group's chunks in order with the
group's running state [H / G x P, N] float32 in VMEM and writes y, the
states entering each chunk and the last; `ssd_bwd` walks them from the last
with the state's gradient in VMEM, reads the entering states and dy, runs
no forward walk, and writes dx, d(dt), the gradient of the log decays (dA
is its dt-weighted sum, in XLA) and dB, dC summed over the group's heads.
The entering states are not named `KEEP` here: a rematerialising caller
needs y again, and `ssd_fwd` makes y and the states in one call, so a kept
copy would buy nothing but memory. A chunk's l (a running sum of log2(L) rotations and adds in
float32), C.B, the pairs' decays and their products live in VMEM only: no
[L, L] array of a chunk reaches HBM. The products' operands are in the
inputs' dtype with float32 sums, as above. The kernels read and write
every array of positions transposed, a position down a column ([H P, T],
[G N, T]): a head is then P rows of a block, and XLA may keep the layer's
arrays positions-minor around them, as it lays them out without them. The
XLA form stays for other shapes and is the kernels' reference in the
tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import compiler_params
from .backend import resolve_interpret as _resolve_interpret

KEEP = "ssd_states"     # checkpoint name of the states entering each chunk
BLOCK = 32              # chunks whose inside is computed at once (None: all;
#                         tools/ssd_times.py: 32 costs 1.5 ms of the scan's 24.6
#                         forward and backward at T = 16,384 and bounds the
#                         pairs' decays to 134 MB)


def _run(a, s, reverse=False):
    """R_c = a_c R_{c-1} + s_c over axis 1 (R_{-1} = 0; with `reverse`,
    R_c = a_c R_{c+1} + s_c from the far end): (the R entering each step,
    the last R). a [B, C, H], s [B, C, H, P, N], float32."""
    step = lambda r, e: (e[0] * r + e[1], r)
    last, entering = jax.lax.scan(
        step, jnp.zeros_like(s[:, 0]),
        (jnp.moveaxis(a[..., None, None], 1, 0), jnp.moveaxis(s, 1, 0)),
        reverse=reverse)
    return jnp.moveaxis(entering, 0, 1), last


@jax.custom_vjp
def chunk_recurrence(a, s):
    """The states across chunks: a [B, C, H] each chunk's whole decay,
    s [B, C, H, P, N] each chunk's own state -> (the state ENTERING each
    chunk [B, C, H, P, N], named `KEEP`; the state after the last)."""
    return _recurrence_fwd(a, s)[0]


def _recurrence_fwd(a, s):
    entering, last = _run(a, s)
    entering = checkpoint_name(entering, KEEP)
    return (entering, last), (a, entering)


def _recurrence_bwd(res, g):
    a, entering = res
    d_entering, d_last = g
    # G_c, the gradient of the state AFTER chunk c: what enters chunk c + 1
    # asked for, plus a_{c+1} G_{c+1}; after the last chunk, d_last
    a_next = jnp.concatenate([a[:, 1:], jnp.ones_like(a[:, :1])], 1)
    asked = jnp.concatenate([d_entering[:, 1:], d_last[:, None]], 1)
    after, first = _run(a_next, asked, reverse=True)
    # `_run` returns what ENTERS each reverse step; G_c is what leaves it
    grads = jnp.concatenate([first[:, None], after[:, :-1]], 1)
    return jnp.sum(grads * entering, (-2, -1)), grads


chunk_recurrence.defvjp(_recurrence_fwd, _recurrence_bwd)


def _inside(x, dt, A, Bc, Cc):
    """What a set of chunks gives alone. x [b, n, L, G, R, P], dt
    [b, n, L, G, R] float32, A [G, R], Bc, Cc [b, n, L, G, N] -> (each
    position's sum over the earlier positions of its chunk [b, n, L, G, R,
    P] float32; each chunk's own state [b, n, G, R, P, N] float32; l, the
    log decays summed along each chunk [b, n, L, G, R])."""
    f32, cdt, L = jnp.float32, x.dtype, x.shape[2]
    dot = functools.partial(jnp.einsum, preferred_element_type=f32)
    l = jnp.cumsum(dt * A, axis=2)
    weigh = lambda w: (x.astype(f32) * w[..., None]).astype(cdt)
    # position i reads j <= i at exp(l_i - l_j) dt_j
    lt = jnp.moveaxis(l, 2, -1)                         # [b, n, G, R, L]
    seen = jnp.tril(jnp.ones((L, L), bool))
    decay = jnp.exp(jnp.where(seen, lt[..., :, None] - lt[..., None, :],
                              -jnp.inf))
    cb = dot("bcign,bcjgn->bcgij", Cc, Bc)
    y = dot("bcgrij,bcjgrp->bcigrp", (cb[:, :, :, None] * decay).astype(cdt),
            weigh(dt))
    own = dot("bcjgrp,bcjgn->bcgrpn", weigh(dt * jnp.exp(l[:, :, -1:] - l)),
              Bc)
    return y, own, l


# ------------------------------------------------------------- the kernels
KERNEL_VMEM = 32 << 20  # the kernels' VMEM limit: at the cell's chunk (L 128,
#                         8 heads of 64 a group, state 128) a backward step's
#                         blocks, two buffers each, and its temporaries come
#                         to 7.8 MiB by `_vmem_need`; room above the scoped
#                         default of 16 for Mosaic's own, under the chip's 128


def _vmem_need(L, P, N, R):
    """Bytes of VMEM a backward grid step asks for, the larger of the two,
    at four bytes a value: its blocks twice (x, dy, dx; the entering state
    and the last's gradient; B, C, dB, dC; dt, a, d(dt), da, <dy, x>), the
    state's gradient, and the temporaries of a head ([L, L]) and of the
    group ([R P, L], [R P, N])."""
    blocks = 3 * L * R * P + 2 * R * P * N + 4 * L * N + 5 * R * L
    temps = 8 * L * L + 12 * L * R * P + 4 * R * P * N
    return 4 * (2 * blocks + R * P * N + temps)


def inside_kernel(L, P, N, heads_per_group):
    """1.0 where the chunks of L positions of heads of P in groups of
    `heads_per_group` over a state of N run as the Pallas kernels, 0.0 where
    they run as XLA products: the kernels' blocks must tile the chip (a
    chunk's positions and the state whole lanes, a head whole packed
    sublanes) and a grid step must fit `KERNEL_VMEM`. Shapes alone
    decide."""
    fits = (L % 128 == 0 and N % 128 == 0 and P % 16 == 0
            and _vmem_need(L, P, N, heads_per_group) <= KERNEL_VMEM)
    return 1.0 if fits else 0.0


def _dot(a, b, dims):
    """a . b contracting a's axis dims[0] with b's dims[1], float32 sums."""
    return jax.lax.dot_general(a, b, (((dims[0],), (dims[1],)), ((), ())),
                               preferred_element_type=jnp.float32)


def _pairs(L):
    """[L, L]: True where position i (a row) reads position j, j <= i."""
    i, j = (jax.lax.broadcasted_iota(jnp.int32, (L, L), k) for k in (0, 1))
    return i >= j


def _running_sum(a, reverse=False):
    """The sum along the lanes up to and including each lane (from each lane
    to the last, with `reverse`) of a [rows, L] float32: log2(L) rotations
    and adds, float32 throughout."""
    L = a.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
    s = 1
    while s < L:
        if reverse:     # lane i gains lane i + s
            a = a + jnp.where(lane < L - s, pltpu.roll(a, L - s, 1), 0.0)
        else:           # lane i gains lane i - s
            a = a + jnp.where(lane >= s, pltpu.roll(a, s, 1), 0.0)
        s *= 2
    return a


def _by_head(rows, a, P):
    """[R, L] x [R P, L] -> [R P, L]: head r's P rows of a times its row."""
    return jnp.concatenate([rows[r:r + 1] * a[r * P:(r + 1) * P]
                            for r in range(rows.shape[0])])


def _head_sums(a, P):
    """[R P, L] -> [R, L]: each head's P rows summed (float32)."""
    return jnp.concatenate([jnp.sum(a[r * P:(r + 1) * P], 0, keepdims=True)
                            for r in range(a.shape[0] // P)])


def _decayed(l, s, P):
    """[R P, N]: each head's P rows of s times its decay over the whole
    chunk, exp(l_L) (broadcast along the lanes first: Mosaic broadcasts a
    [1, 1] one way at a time)."""
    R, N = l.shape[0], s.shape[1]
    return _by_head(jnp.exp(jnp.broadcast_to(l[:, -1:], (R, N))), s, P)


def _chunk(a_ref, dt_ref):
    """What both kernels make of a chunk's time steps first, a head a row
    [R, L]: l, the log decays summed along the chunk (float32, and its
    transpose [L, R]), dt, exp(l_L - l) and dt exp(l_L - l), the weight of
    a position's input in the state the chunk leaves."""
    l, dt = _running_sum(a_ref[0, 0]), dt_ref[0, 0]
    left = jnp.exp(l[:, -1:] - l)
    return l, l.T, dt, left, dt * left


def _each_head(l, lT, dt, cb, P):
    """Per head r: (its P rows of a [R P, L] block, the decays of its
    chunk's pairs exp(l_i - l_j) [L, L] masked to j <= i, C.B x decays, and
    M = C.B x decays x dt_j: position j's weight in y_i)."""
    L, R = lT.shape
    seen = _pairs(L)
    for r in range(R):
        decay = jnp.exp(jnp.where(seen, lT[:, r:r + 1] - l[r:r + 1],
                                  -jnp.inf))
        w = cb * decay
        yield slice(r * P, (r + 1) * P), decay, w, w * dt[r:r + 1]


def _fwd_kernel(a_ref, dt_ref, x_ref, b_ref, c_ref, d_ref, y_ref,
                enter_ref, last_ref, state, *, P):
    """One chunk of one group, everything of a position down a column (x^T,
    B^T, C^T in, y^T out): the group's state [R P, N] float32, carried in
    `state` across the chunks (the innermost grid axis, walked in order),
    is written out as the state entering the chunk, gives its part of y
    (beside the skip D x), and leaves as exp(l_L) R + the chunk's own
    state."""
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    f32 = jnp.float32
    l, lT, dt, _, dt_left = _chunk(a_ref, dt_ref)
    x, Bm, Cm, cdt = x_ref[0], b_ref[0], c_ref[0], x_ref.dtype
    enter = state[...]
    enter_ref[0, 0] = enter
    inner = [_dot(x[rows], m.astype(cdt), (1, 1))           # [P, L]
             for rows, _, _, m in _each_head(l, lT, dt, _dot(Cm, Bm, (0, 0)),
                                             P)]
    xf = x.astype(f32)
    y_ref[0] = (jnp.concatenate(inner)
                + _by_head(jnp.exp(l), _dot(enter.astype(cdt), Cm, (1, 0)), P)
                + d_ref[...] * xf).astype(y_ref.dtype)
    xs = _by_head(dt_left, xf, P).astype(cdt)
    state[...] = _decayed(l, enter, P) + _dot(xs, Bm, (1, 1))

    @pl.when(c == pl.num_programs(2) - 1)
    def _emit():
        last_ref[0] = state[...]


def _bwd_kernel(a_ref, dt_ref, x_ref, b_ref, c_ref, d_ref, enter_ref,
                dy_ref, dlast_ref, dx_ref, ddt_ref, da_ref, dd_ref, db_ref,
                dc_ref, grad, *, P):
    """One chunk of one group, the chunks walked from the last, a position
    down a column as in the forward: `grad` holds the gradient dR of the
    state LEAVING the chunk (the last state's at the first step) and leaves
    as that of the state entering it. The chunk's pairs are made again from
    the kept entering state, nothing of the forward walk. Writes dx, d(dt)
    by dt's own uses, da (the gradient of the log decays a = dt A, the
    reverse running sum of l's), <dy, x> a position and head (D's gradient,
    summed in XLA) and dB, dC summed over the group's heads. What a head's
    positions get is made a head a row ([R, L]): a pair's sums over i as
    sums down the columns of its transpose, the state's as sums down a
    head's rows."""
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        grad[...] = dlast_ref[0]

    f32 = jnp.float32
    l, lT, dt, left, dt_left = _chunk(a_ref, dt_ref)
    x, Bm, Cm, g, cdt = x_ref[0], b_ref[0], c_ref[0], dy_ref[0], x_ref.dtype
    L = x.shape[1]
    enter, d_leave = enter_ref[0, 0], grad[...]
    enter_b, d_leave_b = enter.astype(cdt), d_leave.astype(cdt)
    down = lambda a: jnp.sum(a, 0, keepdims=True)
    d_cb = jnp.zeros((L, L), f32)
    d_inner, dl_rows, ddt_rows = [], [], []
    for rows, decay, w, m in _each_head(l, lT, dt, _dot(Cm, Bm, (0, 0)), P):
        r = rows.start // P
        d_inner.append(_dot(g[rows], m.astype(cdt), (1, 0)))  # [P, L]
        d_m = _dot(g[rows], x[rows], (0, 0))     # <dy_i, x_j> over r's P
        d_cb = d_cb + d_m * (decay * dt[r:r + 1])
        pw = d_m * w                            # d(dt_j) is its column's sum
        q = pw * dt[r:r + 1]        # d l_i += its row's sum, d l_j -= column's
        ddt_rows.append(down(pw))
        dl_rows.append(down(q.T) - down(q))
    xf, gf = x.astype(f32), g.astype(f32)
    up = jnp.exp(l)
    g_up = _by_head(up, gf, P).astype(cdt)
    d_own = _dot(d_leave_b, Bm, (1, 0))                     # dR B^T
    t = _head_sums(d_own * xf, P)                           # d(dt_j left_j)
    d_left = dt_left * t        # d l_j -= it, d l_L += their sum
    at_end = jnp.sum(d_left, 1, keepdims=True) + jnp.exp(l[:, -1:]) * jnp.sum(
        _head_sums(d_leave * enter, P), 1, keepdims=True)
    lane = jax.lax.broadcasted_iota(jnp.int32, dt.shape, 1)
    dl = (jnp.concatenate(dl_rows) - d_left
          + jnp.where(lane == L - 1, at_end, 0.0)
          + up * _head_sums(gf * _dot(enter_b, Cm, (1, 0)), P))
    ddt_ref[0, 0] = jnp.concatenate(ddt_rows) + left * t
    da_ref[0, 0] = _running_sum(dl, reverse=True)
    dd_ref[0, 0] = _head_sums(gf * xf, P)
    dx_ref[0] = (jnp.concatenate(d_inner) + _by_head(dt_left, d_own, P)
                 + d_ref[...] * gf).astype(dx_ref.dtype)
    d_cb = d_cb.astype(cdt)
    xs = _by_head(dt_left, xf, P).astype(cdt)
    dc_ref[0] = (_dot(Bm, d_cb, (1, 1))
                 + _dot(enter_b, g_up, (0, 0))).astype(dc_ref.dtype)
    db_ref[0] = (_dot(Cm, d_cb, (1, 0))
                 + _dot(d_leave_b, xs, (0, 0))).astype(db_ref.dtype)
    grad[...] = _decayed(l, d_leave, P) + _dot(g_up, Cm, (1, 1))


def _specs(b, T, H, P, G, N, L, reverse):
    """Block specs over the grid (row, group, chunk): (a and dt [b, G, R,
    T], x^T and its likes [b, H P, T], B^T and C^T [b, G N, T], a state a
    chunk [b, T / L, H P, N], a group's state [b, H P, N], D down a head's
    P rows [H P, 1]); with `reverse` the chunk axis is walked from the
    last."""
    R, nc = H // G, T // L
    at = (lambda c: nc - 1 - c) if reverse else (lambda c: c)
    vm = {"memory_space": pltpu.VMEM}
    return (pl.BlockSpec((1, 1, R, L), lambda i, g, c: (i, g, 0, at(c)), **vm),
            pl.BlockSpec((1, R * P, L), lambda i, g, c: (i, g, at(c)), **vm),
            pl.BlockSpec((1, N, L), lambda i, g, c: (i, g, at(c)), **vm),
            pl.BlockSpec((1, 1, R * P, N), lambda i, g, c: (i, at(c), g, 0),
                         **vm),
            pl.BlockSpec((1, R * P, N), lambda i, g, c: (i, g, 0), **vm),
            pl.BlockSpec((R * P, 1), lambda i, g, c: (g, 0), **vm))


def _across(a):
    """[b, T, C] <-> [b, C, T]: the kernels read and write a position down
    a column, so that XLA may keep the layer's arrays positions-minor."""
    return jnp.swapaxes(a, 1, 2)


def _kernel_operands(x, dt, A, B, C, D):
    """The kernels' views: a = dt A and dt as [b, G, R, T] float32 (a head's
    positions along lanes), x^T [b, H P, T], B^T and C^T [b, G N, T], D
    float32 down each head's P rows [H P, 1]."""
    b, T, H, P = x.shape
    G, N = B.shape[2:]
    f32 = jnp.float32
    dt_rows = jnp.moveaxis(dt.astype(f32), 1, 2).reshape(b, G, H // G, T)
    a_rows = dt_rows * A.astype(f32).reshape(1, G, H // G, 1)
    return (a_rows, dt_rows, _across(x.reshape(b, T, H * P)),
            _across(B.reshape(b, T, G * N)), _across(C.reshape(b, T, G * N)),
            jnp.repeat(D.astype(f32), P)[:, None])


def _scan_fwd_call(x, dt, A, B, C, D, L, interpret):
    """(y [b, T, H, P] in x's dtype, the last state [b, H, P, N], the state
    entering each chunk [b, T / L, H, P, N]; both float32)."""
    b, T, H, P = x.shape
    G, N = B.shape[2:]
    rows, tok, grp, per_chunk, per_group, skip = _specs(b, T, H, P, G, N, L,
                                                        False)
    f32 = jnp.float32
    y, enter, last = pl.pallas_call(
        functools.partial(_fwd_kernel, P=P), grid=(b, G, T // L),
        in_specs=[rows, rows, tok, grp, grp, skip],
        out_specs=[tok, per_chunk, per_group],
        out_shape=[jax.ShapeDtypeStruct((b, H * P, T), x.dtype),
                   jax.ShapeDtypeStruct((b, T // L, H * P, N), f32),
                   jax.ShapeDtypeStruct((b, H * P, N), f32)],
        scratch_shapes=[pltpu.VMEM((H // G * P, N), f32)],
        interpret=interpret, name="ssd_fwd", **compiler_params(
            interpret, ("parallel", "parallel", "arbitrary"), KERNEL_VMEM))(
            *_kernel_operands(x, dt, A, B, C, D))
    return (_across(y).reshape(b, T, H, P), last.reshape(b, H, P, N),
            enter.reshape(b, T // L, H, P, N))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan_kernels(x, dt, A, B, C, D, L, interpret):
    return _scan_kernels_fwd(x, dt, A, B, C, D, L, interpret)[0]


def _scan_kernels_fwd(x, dt, A, B, C, D, L, interpret):
    y, last, enter = _scan_fwd_call(x, dt, A, B, C, D, L, interpret)
    return (y, last), (x, dt, A, B, C, D, enter)


def _scan_kernels_bwd(L, interpret, res, g):
    x, dt, A, B, C, D, enter = res
    dy, d_last = g
    b, T, H, P = x.shape
    G, N = B.shape[2:]
    rows, tok, grp, per_chunk, per_group, skip = _specs(b, T, H, P, G, N, L,
                                                        True)
    f32 = jnp.float32
    operands = _kernel_operands(x, dt, A, B, C, D)
    by_position = jax.ShapeDtypeStruct(operands[1].shape, f32)
    dx, ddt, da, dD, dB, dC = pl.pallas_call(
        functools.partial(_bwd_kernel, P=P), grid=(b, G, T // L),
        in_specs=[rows, rows, tok, grp, grp, skip, per_chunk, tok, per_group],
        out_specs=[tok, rows, rows, rows, grp, grp],
        out_shape=[jax.ShapeDtypeStruct((b, H * P, T), x.dtype),
                   by_position, by_position, by_position,
                   jax.ShapeDtypeStruct((b, G * N, T), B.dtype),
                   jax.ShapeDtypeStruct((b, G * N, T), C.dtype)],
        scratch_shapes=[pltpu.VMEM((H // G * P, N), f32)],
        interpret=interpret, name="ssd_bwd", **compiler_params(
            interpret, ("parallel", "parallel", "arbitrary"), KERNEL_VMEM))(
            *operands, enter.reshape(b, T // L, H * P, N),
            _across(dy.astype(x.dtype).reshape(b, T, H * P)),
            d_last.astype(f32).reshape(b, H * P, N))
    # a = dt A: dt's gradient gains da A, A's is the sum of da dt
    A_rows = A.astype(f32).reshape(1, G, H // G, 1)
    ddt = jnp.moveaxis((ddt + da * A_rows).reshape(b, H, T), 1, 2)
    dA = jnp.sum(da * operands[1], (0, 3)).reshape(H)
    return (_across(dx).reshape(x.shape), ddt.astype(dt.dtype),
            dA.astype(A.dtype), _across(dB).reshape(B.shape),
            _across(dC).reshape(C.shape),
            jnp.sum(dD, (0, 3)).reshape(H).astype(D.dtype))


_scan_kernels.defvjp(_scan_kernels_fwd, _scan_kernels_bwd)


def ssd_scan(x, dt, A, B, C, D, chunk, block=BLOCK):
    """x [b, T, H, P]; dt [b, T, H] the time steps (after their softplus);
    A [H] < 0; B, C [b, T, G, N] by group, head h reading group
    h // (H / G); D [H] the skip (y_t += D x_t, summed in float32 with the
    rest). Returns (y [b, T, H, P] in x's dtype, the state after the last
    position [b, H, P, N] float32). T must be whole chunks. As the kernels
    where `inside_kernel` says so; else in XLA products, the
    inside of the chunks `block` chunks at a time where T has more (a
    `lax.map`, each block rematerialised in the backward: the pairs'
    decays, [block, H, L, L] float32, are the largest transient), else all
    at once."""
    b, T, H, P = x.shape
    G, N = B.shape[2:]
    L = chunk
    if T % L:
        raise ValueError(f"the sequence length T = {T} is not a multiple of "
                         f"the chunk size L = {L}")
    if H % G:
        raise ValueError(f"{H} heads are not whole groups of {G}")
    nc, R, f32, cdt = T // L, H // G, jnp.float32, x.dtype
    if inside_kernel(L, P, N, R):
        return _scan_kernels(x, dt, A, B, C, D, L, _resolve_interpret(None))
    parts = (x.reshape(b, nc, L, G, R, P),
             dt.astype(f32).reshape(b, nc, L, G, R),
             B.reshape(b, nc, L, G, N), C.reshape(b, nc, L, G, N))
    A = A.astype(f32).reshape(G, R)
    if block and nc > block and nc % block == 0:
        split = lambda a: jnp.moveaxis(
            a.reshape((b, nc // block, block) + a.shape[2:]), 1, 0)
        merge = lambda a: jnp.moveaxis(a, 0, 1).reshape(
            (b, nc) + a.shape[3:])
        y, own, l = jax.tree.map(merge, jax.lax.map(
            jax.checkpoint(lambda e: _inside(e[0], e[1], A, e[2], e[3])),
            jax.tree.map(split, parts)))
    else:
        y, own, l = _inside(parts[0], parts[1], A, parts[2], parts[3])

    # the recurrence across chunks, then the entering state's part of y
    entering, last = chunk_recurrence(
        jnp.exp(l[:, :, -1]).reshape(b, nc, H),
        own.reshape(b, nc, H, P, N))
    y = y + jnp.einsum("bcign,bcgrpn->bcigrp", parts[3],
                       entering.reshape(b, nc, G, R, P, N).astype(cdt),
                       preferred_element_type=f32) * jnp.exp(l)[..., None]
    y = y + D.astype(f32).reshape(G, R, 1) * parts[0].astype(f32)
    return y.reshape(b, T, H, P).astype(cdt), last
