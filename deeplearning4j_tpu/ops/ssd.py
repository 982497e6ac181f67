"""The chunked state-space scan (SSD: Mamba-2, arXiv:2405.21060 section 6),
forward and backward, in XLA products.

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
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

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


def ssd_scan(x, dt, A, B, C, chunk, block=BLOCK):
    """x [b, T, H, P]; dt [b, T, H] the time steps (after their softplus);
    A [H] < 0; B, C [b, T, G, N] by group, head h reading group
    h // (H / G). Returns (y [b, T, H, P] in x's dtype, the state after
    the last position [b, H, P, N] float32). T must be whole chunks. The
    inside of the chunks is computed `block` chunks at a time where T has
    more (a `lax.map`, each block rematerialised in the backward: the pairs'
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
    return y.reshape(b, T, H, P).astype(cdt), last
