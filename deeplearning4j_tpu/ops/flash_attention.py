"""Flash attention — Pallas TPU kernel for the long-context hot op.

The reference's attention-era equivalent is the hand-written native kernel
seam (libnd4j custom ops / cuDNN helpers); on TPU the hot op worth a
hand-written kernel is attention: XLA's lowering of softmax(QK^T)V
materializes the [T, T] score matrix in HBM, so at long sequence length the
op is bandwidth-bound on score traffic. This kernel never materializes it:
K/V stream through VMEM in blocks, the online-softmax running max/sum live
in VMEM scratch across the kv grid dimension, and only the [T, d] output
leaves the chip — O(T) HBM traffic instead of O(T^2).

Layout [B, T, H, D] matches `parallel/ring_attention.py`; this kernel is
the per-device block-compute of ring attention (sequence parallelism) and
the fast path for the transformer zoo model.

Grid: (B*H, T/block_q, T/block_k) — the kv axis is innermost, so each
(batch*head, q-block) revisits its output block while m/l/acc scratch
carries the online-softmax state (the canonical Pallas accumulation
pattern). Causal masking skips fully-masked kv blocks via `pl.when`.

Backward: fused Pallas kernels (`_bwd_dq_kernel`, `_bwd_dkv_kernel`) in
the FlashAttention-2 split — the forward additionally saves the per-row
logsumexp, the backward reconstructs each probability block as
exp(qkᵀ·scale − lse) and fuses dO·Vᵀ / Pᵀ·dO / dSᵀ·Q inside the grid, so
dQ accumulates across the kv dimension and dK/dV across the q dimension
entirely in VMEM scratch. Residual memory stays O(T·d) (q, k, v, o, lse)
and, unlike the r3 einsum-recompute VJP, no [bq, T] score panel ever
round-trips through autodiff. `_blockwise_attention_ckpt` remains as the
XLA-side long-T attention (ring attention's local fallback + test oracle).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")


def _resolve_interpret(interpret):
    """`interpret=None` follows the backend: the Mosaic kernel on TPU, the
    Pallas interpreter on CPU (tests and CPU meshes). Any other backend
    raises — an interpreter run there would be a silent, wrong-speed
    stand-in for a kernel that does not exist."""
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise NotImplementedError(
        f"flash attention has a TPU (Mosaic) kernel and a CPU interpreter "
        f"mode; backend {backend!r} has neither")


def _pallas_env(interpret):
    """Shared pallas_call scaffolding: (VMEM block-spec kwargs, SMEM spec,
    compiler-params extras). One definition so every grid pass compiles
    with identical memory-space and dimension-semantics settings: the
    two outer grid dims are independent, only the innermost carries
    accumulator state in VMEM scratch."""
    kw = {"memory_space": pltpu.VMEM}
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    extra = {}
    if not interpret:
        extra["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    return kw, smem, extra


def _online_softmax_step(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, *,
                         scale, causal, block_q, block_k, q_start, k_start,
                         neg):
    """The shared flash-attention grid step: init scratch at the first kv
    block, fold this (q-block, kv-block) pair into the running (m, l, acc)
    with the online softmax, skipping kv blocks entirely above the causal
    diagonal. `q_start`/`k_start` are GLOBAL positions (plain grid offsets
    for single-device attention; SMEM-prefetched chunk offsets for the
    ring-attention partial). `neg` is the masked-score constant (-inf for
    the normalized kernel; a finite stand-in for partials so ring folding
    of never-attended rows stays NaN-free)."""
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, neg)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def compute():
        # native-dtype (bf16) MXU matmuls with f32 accumulation — an f32
        # cast before the dot would quarter the MXU rate
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk] f32
        if causal:
            row = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            col = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(row >= col, s, neg)
        m_prev = m_ref[:, :1]                          # [bq, 1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)                         # [bq, bk]
        if neg != NEG_INF:
            # finite masked-score stand-in (ring partials): a row that has
            # attended to NOTHING so far still has m_cur == neg, so the
            # masked entries' exp(s - m_cur) = exp(0) = 1 would pour
            # garbage into l/acc. Zero them: never-attended rows keep
            # l = 0 / acc = 0 and the cross-hop fold treats them as empty
            # (real scores never approach neg/2, so the cut is safe).
            p = jnp.where(s > neg * 0.5, p, 0.0)
        l_ref[:, :1] = l_ref[:, :1] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:, :1] = m_cur

    if causal:
        # skip kv blocks entirely above the diagonal
        @pl.when(k_start <= q_start + block_q - 1)
        def _():
            compute()
    else:
        compute()


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale, causal, block_q, block_k):
    _online_softmax_step(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
                         scale=scale, causal=causal, block_q=block_q,
                         block_k=block_k,
                         q_start=pl.program_id(1) * block_q,
                         k_start=pl.program_id(2) * block_k, neg=NEG_INF)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _emit():
        o_ref[0] = (acc_ref[:] /
                    jnp.maximum(l_ref[:, :1], 1e-30)).astype(o_ref.dtype)


def _kernel_lse(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                *, scale, causal, block_q, block_k):
    """Forward kernel that ALSO emits the per-row logsumexp (m + log l) —
    the only forward residual the flash backward kernels need beyond
    q,k,v,o (FlashAttention-2's softmax_lse)."""
    _online_softmax_step(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
                         scale=scale, causal=causal, block_q=block_q,
                         block_k=block_k,
                         q_start=pl.program_id(1) * block_q,
                         k_start=pl.program_id(2) * block_k, neg=NEG_INF)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _emit():
        l_fin = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[:] / l_fin).astype(o_ref.dtype)
        lse_ref[0] = m_ref[:, :1] + jnp.log(l_fin)


def _flash_fwd_bthd(q, k, v, causal, scale, block_q, block_k, interpret,
                    with_lse=False):
    """q,k,v: [BH, T, d] (batch*heads flattened). with_lse=True adds the
    [BH, T, 1] f32 logsumexp output (training forward); inference keeps
    the single-output kernel r3 was measured with."""
    BH, T, d = q.shape
    # largest divisors of T within the requested block sizes (any T works;
    # powers of two get the full-size blocks the chip numbers were swept at)
    bq = _divisor_block(T, block_q)
    bk = _divisor_block(T, block_k)
    grid = (BH, T // bq, T // bk)
    kw, _, extra = _pallas_env(interpret)
    q_spec = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0), **kw)
    kv_spec = pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0), **kw)
    o_spec = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0), **kw)
    scratch = [
        pltpu.VMEM((bq, 128), jnp.float32),   # m (col 0 used)
        pltpu.VMEM((bq, 128), jnp.float32),   # l
        pltpu.VMEM((bq, d), jnp.float32),     # acc
    ]
    if with_lse:
        lse_spec = pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0), **kw)
        kernel = functools.partial(_kernel_lse, scale=scale, causal=causal,
                                   block_q=bq, block_k=bk)
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=[o_spec, lse_spec],
            out_shape=[jax.ShapeDtypeStruct((BH, T, d), q.dtype),
                       jax.ShapeDtypeStruct((BH, T, 1), jnp.float32)],
            scratch_shapes=scratch,
            interpret=interpret,
            **extra,
        )(q, k, v)
    kernel = functools.partial(_kernel, scale=scale, causal=causal,
                               block_q=bq, block_k=bk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((BH, T, d), q.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
        **extra,
    )(q, k, v)


_FINITE_NEG = -1e30   # finite -inf stand-in: keeps exp(m - m_new) NaN-free
#                       for rows that have seen no keys yet (ring warm-up)


def _partial_kernel(qo_ref, ko_ref, q_ref, k_ref, v_ref, o_ref, mo_ref,
                    lo_ref, m_ref, l_ref, acc_ref, *, scale, causal,
                    block_q, block_k):
    """Like `_kernel` but emits UNNORMALIZED (acc, m, l) so a ring-attention
    hop can fold partials across devices; causal masking uses the global
    offsets prefetched in SMEM (qo/ko: this chunk's global positions)."""
    _online_softmax_step(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref,
                         scale=scale, causal=causal, block_q=block_q,
                         block_k=block_k,
                         q_start=pl.program_id(1) * block_q + qo_ref[0],
                         k_start=pl.program_id(2) * block_k + ko_ref[0],
                         neg=_FINITE_NEG)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _emit():
        o_ref[0] = acc_ref[:]
        mo_ref[0] = jnp.broadcast_to(m_ref[:, :1], mo_ref.shape[1:])
        lo_ref[0] = jnp.broadcast_to(l_ref[:, :1], lo_ref.shape[1:])


def flash_attention_partial(q, k, v, q_off, k_off, causal=True, scale=None,
                            block_q=1024, block_k=1024, interpret=None):
    """Unnormalized flash partials for ring attention's per-hop compute.

    q [BH, Tq, d]; k, v [BH, Tk, d]; q_off/k_off: traced int32 scalars —
    the global sequence offset of this q chunk / visiting kv chunk (causal
    masking across devices). Returns (acc [BH,Tq,d] f32, m [BH,Tq,1] f32,
    l [BH,Tq,1] f32) for `_flash_fold`-style merging across hops."""
    BH, Tq, d = q.shape
    Tk = k.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    interpret = _resolve_interpret(interpret)
    bq = _divisor_block(Tq, block_q)
    bk = _divisor_block(Tk, block_k)
    grid = (BH, Tq // bq, Tk // bk)
    kw, smem, extra = _pallas_env(interpret)
    q_spec = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0), **kw)
    kv_spec = pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0), **kw)
    o_spec = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0), **kw)
    ml_spec = pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0), **kw)
    kernel = functools.partial(_partial_kernel, scale=scale, causal=causal,
                               block_q=bq, block_k=bk)
    acc, m, l = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[smem, smem, q_spec, kv_spec, kv_spec],
        out_specs=[o_spec, ml_spec, ml_spec],
        out_shape=[jax.ShapeDtypeStruct((BH, Tq, d), jnp.float32),
                   jax.ShapeDtypeStruct((BH, Tq, 1), jnp.float32),
                   jax.ShapeDtypeStruct((BH, Tq, 1), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
        **extra,
    )(jnp.asarray(q_off, jnp.int32).reshape(1),
      jnp.asarray(k_off, jnp.int32).reshape(1), q, k, v)
    return acc, m[..., 0], l[..., 0]


def _divisor_block(T, requested):
    b = min(requested, T)
    while T % b:
        b -= 1
    return b


# ---------------------------------------------------------------------------
# Flash backward — fused Pallas dQ / dK+dV kernels (FlashAttention-2 split)
#
# Residuals: q, k, v, o, lse (lse = per-row logsumexp from `_kernel_lse`).
# Per (q-block i, kv-block j) the probabilities are reconstructed exactly as
#   p = exp(q_i k_jᵀ·scale − lse_i)            (no second online softmax)
# and with D_i = rowsum(dO_i ∘ O_i):
#   dV_j = Σ_i p ᵀ dO_i
#   dS   = p ∘ (dO_i V_jᵀ − D_i)
#   dQ_i = Σ_j dS K_j · scale        (kv innermost — dq accumulates in VMEM)
#   dK_j = Σ_i dSᵀ Q_i · scale       (q innermost — dk/dv accumulate in VMEM)
# Two passes so every accumulator lives in VMEM scratch across its inner
# grid dimension — no HBM read-modify-write, O(T) HBM traffic like the
# forward. Causal skipping drops the strictly-masked half of each grid.
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(qo_ref, ko_ref, q_ref, k_ref, v_ref, delta_ref, do_ref,
                   lse_ref, dq_ref, dq_acc_ref, *, scale, causal, block_q,
                   block_k):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        dq_acc_ref[:] = jnp.zeros_like(dq_acc_ref)

    # global offsets from SMEM: 0 on the single-device path; the ring
    # backward prefetches each hop's chunk positions (causality across
    # devices)
    q_start = pl.program_id(1) * block_q + qo_ref[0]
    k_start = ik * block_k + ko_ref[0]

    def compute():
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale        # [bq, bk]
        if causal:
            row = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            col = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(row >= col, s, NEG_INF)
        p = jnp.exp(s - lse_ref[0])       # exact probs; masked -> exp(-inf)=0
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                # [bq, bk]
        ds = p * (dp - delta_ref[0])
        dq_acc_ref[:] += jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    if causal:
        @pl.when(k_start <= q_start + block_q - 1)
        def _():
            compute()
    else:
        compute()

    @pl.when(ik == pl.num_programs(2) - 1)
    def _emit():
        dq_ref[0] = dq_acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(qo_ref, ko_ref, q_ref, k_ref, v_ref, delta_ref, do_ref,
                    lse_ref, dk_ref, dv_ref, dk_acc_ref, dv_acc_ref, *,
                    scale, causal, block_q, block_k):
    iq = pl.program_id(2)

    @pl.when(iq == 0)
    def _init():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    k_start = pl.program_id(1) * block_k + ko_ref[0]
    q_start = iq * block_q + qo_ref[0]

    def compute():
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale        # [bq, bk]
        if causal:
            row = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            col = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(row >= col, s, NEG_INF)
        p = jnp.exp(s - lse_ref[0])                            # [bq, bk]
        # dV_j += pᵀ dO  (contract the q dim — no explicit transpose)
        dv_acc_ref[:] += jax.lax.dot_general(
            p.astype(do_ref.dtype), do_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # [bk, d]
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                # [bq, bk]
        ds = p * (dp - delta_ref[0])
        # dK_j += dSᵀ Q · scale
        dk_acc_ref[:] += jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    if causal:
        # skip q blocks strictly above this kv block's diagonal reach
        @pl.when(q_start + block_q - 1 >= k_start)
        def _():
            compute()
    else:
        compute()

    @pl.when(iq == pl.num_programs(2) - 1)
    def _emit():
        dk_ref[0] = dk_acc_ref[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[:].astype(dv_ref.dtype)


def _flash_bwd_bthd(q, k, v, o, lse, do, causal, scale, block_q, block_k,
                    interpret):
    """q,k,v,o,do: [BH, T, d]; lse: [BH, T, 1] f32. Returns (dq, dk, dv).

    delta = rowsum(dO ∘ O) is precomputed ONCE as [BH, T, 1] (XLA fuses
    the elementwise+reduce) and streamed into both kernels like lse —
    FlashAttention-2's delta pass; recomputing it per (kv, q) grid pair
    would redo the full [T] reduction T/bk times.

    Backward default blocks are half the forward's: the backward keeps
    three [bq, bk] f32 panels (p, dp, ds) live per step, so 512² blocks
    fit VMEM where the forward ran 1024² with one panel."""
    BH, T, d = q.shape
    bq = _divisor_block(T, block_q)
    bk = _divisor_block(T, block_k)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    -1, keepdims=True)                    # [BH, T, 1]
    zero = jnp.zeros((1,), jnp.int32)
    dq = _flash_bwd_dq_pass(q, k, v, delta, do, lse, zero, zero, causal,
                            scale, bq, bk, interpret)
    dk, dv = _flash_bwd_dkv_pass(q, k, v, delta, do, lse, zero, zero,
                                 causal, scale, bq, bk, interpret)
    return dq, dk, dv


def _flash_bwd_dq_pass(q, k, v, delta, do, lse, q_off, k_off, causal,
                       scale, bq, bk, interpret, out_dtype=None):
    """dQ grid pass (kv innermost). q [BH, Tq, d]; k/v [BH, Tk, d];
    q_off/k_off: int32 [1] global chunk offsets (SMEM) — zero on the
    single-device path, hop positions in the ring backward. out_dtype:
    gradient dtype (default q.dtype; the ring backward requests f32 so
    per-hop partials are rounded ONCE at the end, not once per hop)."""
    BH, Tq, d = q.shape
    Tk = k.shape[1]
    kw, smem, extra = _pallas_env(interpret)
    qb_spec = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0), **kw)
    kvb_spec = pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0), **kw)
    lse_q_spec = pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0), **kw)
    return pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk),
        grid=(BH, Tq // bq, Tk // bk),
        in_specs=[smem, smem, qb_spec, kvb_spec, kvb_spec, lse_q_spec,
                  qb_spec, lse_q_spec],
        out_specs=qb_spec,
        out_shape=jax.ShapeDtypeStruct((BH, Tq, d), out_dtype or q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        **extra,
    )(q_off, k_off, q, k, v, delta, do, lse)


def _flash_bwd_dkv_pass(q, k, v, delta, do, lse, q_off, k_off, causal,
                        scale, bq, bk, interpret, out_dtype=None):
    """dK/dV grid pass (q innermost); same offset/out_dtype contract as
    the dQ pass."""
    BH, Tq, d = q.shape
    Tk = k.shape[1]
    kw, smem, extra = _pallas_env(interpret)
    q_in_spec = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, j, 0), **kw)
    kv_out_spec = pl.BlockSpec((1, bk, d), lambda b, i, j: (b, i, 0), **kw)
    lse_in_spec = pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, j, 0), **kw)
    return pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk),
        grid=(BH, Tk // bk, Tq // bq),
        in_specs=[smem, smem, q_in_spec, kv_out_spec, kv_out_spec,
                  lse_in_spec, q_in_spec, lse_in_spec],
        out_specs=[kv_out_spec, kv_out_spec],
        out_shape=[jax.ShapeDtypeStruct((BH, Tk, d), out_dtype or k.dtype),
                   jax.ShapeDtypeStruct((BH, Tk, d), out_dtype or v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=interpret,
        **extra,
    )(q_off, k_off, q, k, v, delta, do, lse)


def flash_attention_bwd_partial(q, k, v, delta, do, lse, q_off, k_off,
                                causal=True, scale=None, block_q=512,
                                block_k=512, interpret=None):
    """One ring hop's backward contributions: (dq_partial, dk_partial,
    dv_partial) for the (q chunk at q_off) x (kv chunk at k_off) pair —
    both fused grid passes with global-offset causal masking. The ring
    backward accumulates dq locally and rotates dk/dv partials home.
    Shapes: q/do [BH, Tq, d]; k/v [BH, Tk, d]; delta/lse [BH, Tq, 1]
    f32 (delta = rowsum(dO ∘ O))."""
    BH, Tq, d = q.shape
    Tk = k.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    interpret = _resolve_interpret(interpret)
    bq = _divisor_block(Tq, block_q)
    bk = _divisor_block(Tk, block_k)
    qo = jnp.asarray(q_off, jnp.int32).reshape(1)
    ko = jnp.asarray(k_off, jnp.int32).reshape(1)
    # f32 partials: the ring accumulates across hops — round once at the
    # end, not per hop (matters for bf16 inputs)
    dq = _flash_bwd_dq_pass(q, k, v, delta, do, lse, qo, ko, causal,
                            scale, bq, bk, interpret,
                            out_dtype=jnp.float32)
    dk, dv = _flash_bwd_dkv_pass(q, k, v, delta, do, lse, qo, ko, causal,
                                 scale, bq, bk, interpret,
                                 out_dtype=jnp.float32)
    return dq, dk, dv


def _reference_attention(q, k, v, causal, scale):
    """Einsum reference ([B,T,H,D]); materializes [T,T] — test oracle and
    small-T backward only."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    if causal:
        T = q.shape[1]
        pos = jnp.arange(T)
        s = jnp.where(pos[:, None] >= pos[None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def _blockwise_attention_ckpt(q, k, v, causal, scale, block_q=1024):
    """Blockwise attention over q-blocks with a `jax.checkpoint` block body:
    same values as `_reference_attention`, but autodiff residuals are only
    (q_block, k, v) per block — O(T·d), not O(T²). Scores for one q-block
    ([bq, T]) exist transiently and are recomputed in the backward. This is
    the recompute target for flash_attention's custom VJP at long T, so
    TRAINING keeps the flash memory contract, not just inference."""
    B, T, H, D = q.shape
    bq = block_q
    while T % bq:
        bq //= 2
        if bq == 0:
            bq = T
            break
    nq = T // bq
    qb = q.reshape(B, nq, bq, H, D).transpose(1, 0, 2, 3, 4)  # [nq,B,bq,H,D]
    starts = jnp.arange(nq) * bq

    @jax.checkpoint
    def one_block(q_blk, q_start):
        s = jnp.einsum("bqhd,bkhd->bhqk", q_blk.astype(jnp.float32) * scale,
                       k.astype(jnp.float32))            # [B,H,bq,T]
        if causal:
            row = q_start + jnp.arange(bq)
            col = jnp.arange(T)
            s = jnp.where(row[:, None] >= col[None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
        return out.astype(q_blk.dtype)                   # [B,bq,H,D]

    out_blocks = jax.lax.map(lambda args: one_block(*args), (qb, starts))
    return out_blocks.transpose(1, 0, 2, 3, 4).reshape(B, T, H, D)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal=True, scale=None, block_q=1024,
                    block_k=1024, interpret=None):
    """Flash attention over [B, T, H, D] (ring_attention layout).

    scale defaults to 1/sqrt(D). `interpret=None` auto-selects: real
    Mosaic kernel on TPU, Pallas interpreter on CPU (so the same tests
    run on the CPU mesh); see `_resolve_interpret`."""
    return _flash_apply(q, k, v, causal, scale, block_q, block_k, interpret)


def _flash_apply(q, k, v, causal, scale, block_q, block_k, interpret):
    B, T, H, D = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    to_bhtd = lambda a: a.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    out = _flash_fwd_bthd(to_bhtd(q), to_bhtd(k), to_bhtd(v), causal,
                          scale, block_q, block_k,
                          _resolve_interpret(interpret))
    return out.reshape(B, H, T, D).transpose(0, 2, 1, 3)


def _fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    """Training forward: same grid as inference plus the [BH, T, 1] lse
    output — the residuals (q, k, v, o, lse) are everything the fused
    backward kernels need, keeping the O(T)-residual-memory contract."""
    B, T, H, D = q.shape
    sc = 1.0 / math.sqrt(D) if scale is None else scale
    to_bhtd = lambda a: a.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    out, lse = _flash_fwd_bthd(to_bhtd(q), to_bhtd(k), to_bhtd(v), causal,
                               sc, block_q, block_k,
                               _resolve_interpret(interpret), with_lse=True)
    out_bthd = out.reshape(B, H, T, D).transpose(0, 2, 1, 3)
    return out_bthd, (q, k, v, out_bthd, lse)


def _bwd(causal, scale, block_q, block_k, interpret, res, g):
    """Fused Pallas dQ/dK/dV (replaces the r3 einsum-recompute VJP, which
    paid a full re-softmax through autodiff: 0.86x/0.71x of dense training
    tok/s at T=2048/4096 — PERF.md 'Training trade-off')."""
    q, k, v, o, lse = res
    B, T, H, D = q.shape
    sc = 1.0 / math.sqrt(D) if scale is None else scale
    to_bhtd = lambda a: a.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    # backward blocks: half the forward's (three f32 [bq,bk] panels live),
    # floored at 256 but never above the caller's forward block — a caller
    # that shrank blocks below 256 did so for VMEM headroom, and the
    # backward must not silently exceed that
    bwd_bq = min(block_q, max(block_q // 2, 256))
    bwd_bk = min(block_k, max(block_k // 2, 256))
    dq, dk, dv = _flash_bwd_bthd(
        to_bhtd(q), to_bhtd(k), to_bhtd(v), to_bhtd(o), lse, to_bhtd(g),
        causal, sc, bwd_bq, bwd_bk, _resolve_interpret(interpret))
    back = lambda a: a.reshape(B, H, T, D).transpose(0, 2, 1, 3)
    return back(dq), back(dk), back(dv)


flash_attention.defvjp(_fwd, _bwd)
