"""Ring attention — sequence/context parallelism over a mesh axis.

Not present in the reference (SURVEY.md §5.7: the 2016 codebase predates
attention; its only long-sequence mechanism is truncated BPTT). This module
is the framework's first-class long-context path, designed TPU-native from
the start: the sequence axis is sharded over a mesh axis; each device holds
one Q/K/V chunk; K/V blocks rotate around the ring via `lax.ppermute` over
ICI while a flash-attention-style running softmax (max + log-sum-exp
accumulators) folds each block in. Peak memory per device is
O(T_local * T_local) instead of O(T^2), and compute/communication overlap on
the ring (the pattern of Liu et al.'s Ring Attention with Blockwise
Transformers).

`ring_self_attention(x, mesh, axis)` is the user entry: shard_map's the
per-device kernel over the mesh; plain `blockwise_attention` is the
single-device reference (identical math, used for equivalence tests).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def _mark_varying(tree, axis_name):
    """Mark replicated constants as axis-varying under shard_map (loop
    carries become varying)."""
    return lax.pcast(tree, axis_name, to="varying")


def _attend_block(q, k, v, bias):
    """Scores for one (Q-chunk, K-block) pair.
    q [B,Tq,H,D]; k,v [B,Tk,H,D]; bias [Tq,Tk] additive (0 or NEG_INF).
    Returns (scores [B,H,Tq,Tk], values v)."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k)
    return s + bias[None, None, :, :]


def _flash_fold(o, m, l, s, v):
    """Fold one block's scores into running (output, max, sumexp)."""
    m_blk = jnp.max(s, axis=-1)                        # [B,H,Tq]
    m_new = jnp.maximum(m, m_blk)
    scale = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])                  # [B,H,Tq,Tk]
    l_new = l * scale + jnp.sum(p, axis=-1)
    o_new = o * scale[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, v)
    return o_new, m_new, l_new


def ring_attention_kernel(q, k, v, kv_mask, axis_name, causal=False,
                          scale=None, use_flash=False, return_lse=False):
    """Per-device ring attention body (run under shard_map).

    q,k,v: [B, T_local, H, D] — this device's sequence chunk.
    kv_mask: [B, T_local] validity of this chunk's keys (rotates with K/V).
    Rotates K/V around `axis_name` N times, folding each block with the
    running-softmax accumulators. Causal masking uses global chunk offsets.

    use_flash: compute each hop's partial with the Pallas flash kernel
    (`ops/flash_attention.flash_attention_partial`) instead of the einsum
    block — the full long-context stack: sequence parallelism across
    devices x flash attention within each device. Requires an all-ones
    kv_mask (ring_self_attention enforces this).
    """
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    B, Tq, H, D = q.shape
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    acc_dt = jnp.float32 if use_flash else q.dtype
    if not use_flash:
        q = q * scale

    o0 = jnp.zeros((B, H, Tq, D), acc_dt)
    m0 = jnp.full((B, H, Tq), NEG_INF, acc_dt)
    l0 = jnp.zeros((B, H, Tq), acc_dt)
    o0, m0, l0 = _mark_varying((o0, m0, l0), axis_name)
    perm = [(j, (j + 1) % n) for j in range(n)]

    qpos = my * Tq + jnp.arange(Tq)                    # global q positions
    q_flat = q.transpose(0, 2, 1, 3).reshape(B * H, Tq, D)

    def body(i, carry):
        o, m, l, k_blk, v_blk, km_blk = carry
        src = (my - i) % n                             # origin chunk of k_blk
        if use_flash:
            from ..ops.flash_attention import flash_attention_partial
            flat = lambda a: a.transpose(0, 2, 1, 3).reshape(B * H, Tq, D)
            acc_b, m_b, l_b = flash_attention_partial(
                q_flat, flat(k_blk), flat(v_blk), my * Tq, src * Tq,
                causal=causal, scale=scale)
            acc_b = acc_b.reshape(B, H, Tq, D)
            m_b = m_b.reshape(B, H, Tq)
            l_b = l_b.reshape(B, H, Tq)
            m_new = jnp.maximum(m, m_b)
            a_run = jnp.exp(m - m_new)
            a_blk = jnp.exp(m_b - m_new)
            o = o * a_run[..., None] + acc_b * a_blk[..., None]
            l = l * a_run + l_b * a_blk
            m = m_new
        else:
            kpos = src * Tq + jnp.arange(Tq)
            if causal:
                bias = jnp.where(qpos[:, None] >= kpos[None, :], 0.0,
                                 NEG_INF)
            else:
                bias = jnp.zeros((Tq, Tq))
            s = _attend_block(q, k_blk, v_blk, bias.astype(q.dtype))
            # invalid keys: -inf for every query, per batch element
            s = s + jnp.where(km_blk > 0, 0.0,
                              NEG_INF)[:, None, None, :].astype(q.dtype)
            o, m, l = _flash_fold(o, m, l, s, v_blk)
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        km_blk = lax.ppermute(km_blk, axis_name, perm)
        return o, m, l, k_blk, v_blk, km_blk

    o, m, l, _, _, _ = lax.fori_loop(0, n, body, (o0, m0, l0, k, v, kv_mask))
    out = o / jnp.maximum(l, 1e-30)[..., None]         # [B,H,Tq,D]
    out_t = jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)  # [B,Tq,H,D]
    if return_lse:
        # GLOBAL per-row logsumexp (all hops folded) — the only extra
        # residual the fused ring backward needs
        lse = m + jnp.log(jnp.maximum(l, 1e-30))       # [B,H,Tq]
        return out_t, jnp.transpose(lse, (0, 2, 1)).astype(jnp.float32)
    return out_t


def ring_attention_bwd_kernel(q, k, v, o, lse, do, axis_name, causal=False,
                              scale=None):
    """Per-device FUSED ring backward (run under shard_map): the reverse
    of the forward rotation, every hop's contribution computed by the
    Pallas backward grid passes (`flash_attention_bwd_partial`).

    Per hop, the device holds its own (q, o, lse, do, delta) and the
    visiting (k, v) block: the dQ contribution accumulates locally; the
    dK/dV partials accumulate into buffers that ROTATE WITH the block, so
    after n hops each block's gradient arrives back at its home device
    with every device's contribution folded in — same communication
    volume as the forward (one extra 2x payload for the traveling
    gradients). The global lse makes each hop's probabilities exact
    (p = exp(s − lse_global)), so no cross-hop softmax refold is needed
    in the backward at all.

    q,k,v,o,do: [B, Tq, H, D] local chunks; lse: [B, Tq, H] f32 (from
    the forward's return_lse). Returns (dq, dk, dv) local chunks."""
    from ..ops.flash_attention import flash_attention_bwd_partial
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    B, Tq, H, D = q.shape
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    flat = lambda a: a.transpose(0, 2, 1, 3).reshape(B * H, Tq, D)
    qf, kf, vf, of, dof = flat(q), flat(k), flat(v), flat(o), flat(do)
    lse_f = lse.transpose(0, 2, 1).reshape(B * H, Tq, 1)
    delta = jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32),
                    -1, keepdims=True)
    perm = [(j, (j + 1) % n) for j in range(n)]

    z = jnp.zeros((B * H, Tq, D), jnp.float32)
    dq0, zk, zv = _mark_varying((z, z, z), axis_name)

    def body(i, carry):
        dq, dk_rot, dv_rot, k_blk, v_blk = carry
        src = (my - i) % n
        dq_p, dk_p, dv_p = flash_attention_bwd_partial(
            qf, k_blk, v_blk, delta, dof, lse_f, my * Tq, src * Tq,
            causal=causal, scale=scale)
        # partials arrive f32 by flash_attention_bwd_partial's out_dtype
        # contract — bf16 inputs are rounded ONCE after the ring, never
        # per hop (the accumulators below stay f32 end to end)
        assert dq_p.dtype == dk_p.dtype == dv_p.dtype == jnp.float32
        dq = dq + dq_p
        dk_rot = dk_rot + dk_p
        dv_rot = dv_rot + dv_p
        # gradients travel WITH their block: one more hop each iteration
        # brings them home after the loop
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        dk_rot = lax.ppermute(dk_rot, axis_name, perm)
        dv_rot = lax.ppermute(dv_rot, axis_name, perm)
        return dq, dk_rot, dv_rot, k_blk, v_blk

    dq, dk, dv, _, _ = lax.fori_loop(0, n, body, (dq0, zk, zv, kf, vf))
    unflat = lambda a, dt: a.reshape(B, H, Tq, D).transpose(
        0, 2, 1, 3).astype(dt)
    return unflat(dq, q.dtype), unflat(dk, k.dtype), unflat(dv, v.dtype)


def blockwise_attention(q, k, v, kv_mask=None, causal=False, scale=None):
    """Single-device reference with the same math (full T).
    q,k,v: [B,T,H,D]; kv_mask [B,T] key validity."""
    B, T, H, D = q.shape
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    q = q * scale
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k)
    if causal:
        pos = jnp.arange(T)
        s = jnp.where(pos[:, None] >= pos[None, :], s, NEG_INF)
    if kv_mask is not None:
        s = s + jnp.where(kv_mask > 0, 0.0,
                          NEG_INF)[:, None, None, :].astype(q.dtype)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bhqd", p, v)
    return jnp.transpose(out, (0, 2, 1, 3))


def ring_self_attention(q, k, v, mesh, axis="seq", causal=False,
                        kv_mask=None, use_flash=False):
    """Sequence-parallel attention over `mesh[axis]`.

    q,k,v: GLOBAL [B,T,H,D] arrays (or already sharded); T must divide by
    the axis size. kv_mask: [B,T] key validity. Returns global [B,T,H,D].
    use_flash: per-hop compute via the Pallas flash kernel (kv_mask not
    supported on that path)."""
    from jax.sharding import PartitionSpec as P

    if use_flash and kv_mask is not None:
        raise ValueError("use_flash does not support kv_mask; pad-free "
                         "sequences only")
    if kv_mask is None:
        kv_mask = jnp.ones(q.shape[:2], q.dtype)
    spec = P(None, axis, None, None)
    mspec = P(None, axis)

    def build(flash, return_lse=False):
        extra = {}
        if flash:
            # pallas_call outputs carry no vma annotation; disable the
            # check for the kernel path (the einsum path keeps it)
            extra["check_vma"] = False
        lse_spec = P(None, axis, None)
        return jax.shard_map(
            functools.partial(ring_attention_kernel, axis_name=axis,
                              causal=causal, use_flash=flash,
                              return_lse=return_lse),
            mesh=mesh, in_specs=(spec, spec, spec, mspec),
            out_specs=(spec, lse_spec) if return_lse else spec,
            **extra)

    if not use_flash:
        return build(False)(q, k, v, kv_mask)

    # Fused ring backward: the forward additionally saves the global
    # per-row logsumexp; the backward is its own reverse ring with the
    # Pallas dQ/dK+dV grid passes per hop and dK/dV partials rotating
    # home with their blocks (`ring_attention_bwd_kernel`) — long-context
    # TRAINING keeps the flash memory/compute profile across devices
    # (the r3 design recomputed the backward through the einsum ring,
    # materializing per-hop [T/n, T/n] score panels).
    @jax.custom_vjp
    def rsa(q, k, v):
        # primal (inference / no grad): skip the lse output entirely
        return build(True)(q, k, v, kv_mask)

    def rsa_fwd(q, k, v):
        out, lse = build(True, return_lse=True)(q, k, v, kv_mask)
        return out, (q, k, v, out, lse)

    def rsa_bwd(res, g):
        q, k, v, out, lse = res
        lse_spec = P(None, axis, None)
        bwd = jax.shard_map(
            functools.partial(ring_attention_bwd_kernel, axis_name=axis,
                              causal=causal),
            mesh=mesh,
            in_specs=(spec, spec, spec, spec, lse_spec, spec),
            out_specs=(spec, spec, spec), check_vma=False)
        return bwd(q, k, v, out, lse, g)

    rsa.defvjp(rsa_fwd, rsa_bwd)
    return rsa(q, k, v)
