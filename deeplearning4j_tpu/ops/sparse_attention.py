"""Attention under a per-pair selection mask: Pallas TPU kernels.

The main attention of a `sparseattention` layer
(`nn/conf/layers/decoder.py`) reads, for each query, only the keys its
indexer selected; the selection is one set for all heads, a bool [T, T] a
sequence (causality included). XLA's lowering would write every head's
[T, T] scores to HBM several times over; these kernels stream K/V blocks
through VMEM under the online softmax, as `ops/flash_attention.py` does for
plain causal attention, and read the mask a (block_q, block_k) tile at a
time. Grouped queries: query head a reads key/value head a // (H / KV),
chosen by the block index map, nothing is repeated in HBM.

- `masked_attention(q, k, v, mask)` -> (o, lse): forward and, through its
  custom VJP, the FlashAttention-2 backward in two grid passes (dQ with the
  key blocks innermost; dK/dV with the group's query heads and query blocks
  innermost, so the sum over a group's heads happens in VMEM).
- `head_summed_probs(q, k, lse, mask)` -> [B, T, T]: the probabilities of
  all heads added up, pair by pair: the target of the indexer's loss.
- `at_least_kth(scores, k)` -> int8 [R, S]: which entries of each row are at
  least the row's k-th largest (the top-k selection with ties kept), by a
  binary search over the floats' bits that reads the row once from HBM;
  `lax.top_k` at k = 2048 of 8192 is a full sort on this chip, 2.7 ms for
  512 rows, and was 30% of the step.

Key blocks wholly after a query block are skipped (the mask is causal);
inside the causal part every tile is computed, whatever its density.
`work_keye.py` of the benchmark counts the SELECTED pairs only, so a
roofline share read from these kernels counts the masked-out work as waste.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _divisor_block, _resolve_interpret

KEEP = "sparse_attention"    # checkpoint name of what a backward needs kept
NEG = -1e30      # a masked score; finite, so a row that has met no key yet
#                  (its first tiles all masked) folds without NaN


def _params(interpret, semantics):
    return {} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=semantics)}


def _scores(q_ref, k_ref, mask_ref, scale):
    s = jax.lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    keep = mask_ref[0].astype(jnp.int32) != 0
    return jnp.where(keep, s, NEG), keep


# ------------------------------------------------------------------ forward
def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref, m_ref, l_ref,
                acc_ref, *, scale, bq, bk):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(j * bk <= i * bq + bq - 1)
    def _step():
        s, keep = _scores(q_ref, k_ref, mask_ref, scale)
        m_prev = m_ref[:, :1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.where(keep, jnp.exp(s - m_cur), 0.0)
        l_ref[:, :1] = l_ref[:, :1] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:, :1] = m_cur

    @pl.when(j == pl.num_programs(2) - 1)
    def _emit():
        l_fin = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[:] / l_fin).astype(o_ref.dtype)
        lse_ref[0] = m_ref[:, :1] + jnp.log(l_fin)


def _specs(bq, bk, d, R, H):
    """Block specs of a (batch * head, query block i, key block j) grid:
    q-shaped, k/v-shaped (the group's head), the mask's tile, a per-row
    column. A key block wholly after the query block is not fetched: the
    index stays at the last block the queries see."""
    vm = {"memory_space": pltpu.VMEM}
    seen = lambda i, j: jnp.minimum(j, (i * bq + bq - 1) // bk)
    return (pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0), **vm),
            pl.BlockSpec((1, bk, d),
                         lambda b, i, j: (b // R, seen(i, j), 0), **vm),
            pl.BlockSpec((1, bq, bk),
                         lambda b, i, j: (b // H, i, seen(i, j)), **vm),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0), **vm))


def _fwd(q, k, v, mask, scale, bq, bk, interpret):
    """q [B*H, T, d], k/v [B*KV, T, d], mask int8 [B, T, T]."""
    BH, T, d = q.shape
    q_spec, kv_spec, mask_spec, row_spec = _specs(
        bq, bk, d, BH // k.shape[0], BH // mask.shape[0])
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, bq=bq, bk=bk),
        grid=(BH, T // bq, T // bk),
        in_specs=[q_spec, kv_spec, kv_spec, mask_spec],
        out_specs=[q_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct((BH, T, d), q.dtype),
                   jax.ShapeDtypeStruct((BH, T, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, 128), jnp.float32),
                        pltpu.VMEM((bq, 128), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret, name="sparse_attention_fwd",
        **_params(interpret, ("parallel", "parallel", "arbitrary")))(
            q, k, v, mask)


# ----------------------------------------------------------------- backward
def _dq_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, delta_ref,
               dq_ref, acc_ref, *, scale, bq, bk):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(j * bk <= i * bq + bq - 1)
    def _step():
        s, _ = _scores(q_ref, k_ref, mask_ref, scale)
        p = jnp.exp(s - lse_ref[0])
        dp = jax.lax.dot_general(do_ref[0], v_ref[0],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0])
        acc_ref[:] += jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(j == pl.num_programs(2) - 1)
    def _emit():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, scale, bq, bk, nq):
    j, t = pl.program_id(1), pl.program_id(2)
    i = t % nq                      # t walks the group's heads, then blocks

    @pl.when(t == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(i * bq + bq - 1 >= j * bk)
    def _step():
        s, _ = _scores(q_ref, k_ref, mask_ref, scale)
        p = jnp.exp(s - lse_ref[0])
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do_ref.dtype), do_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do_ref[0], v_ref[0],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0])
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(t == pl.num_programs(2) - 1)
    def _emit():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd(q, k, v, mask, o, lse, do, scale, bq, bk, interpret):
    BH, T, d = q.shape
    BKV = k.shape[0]
    H, R, KV = BH // mask.shape[0], BH // BKV, BKV // mask.shape[0]
    nq = T // bq
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1,
                    keepdims=True)
    vm = {"memory_space": pltpu.VMEM}
    q_spec, kvq_spec, mask_spec, row_spec = _specs(bq, bk, d, R, H)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, bq=bq, bk=bk),
        grid=(BH, nq, T // bk),
        in_specs=[q_spec, kvq_spec, kvq_spec, mask_spec, q_spec, row_spec,
                  row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((BH, T, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret, name="sparse_attention_dq",
        **_params(interpret, ("parallel", "parallel", "arbitrary")))(
            q, k, v, mask, do, lse, delta)
    # key block j of group g; inside, head r of the group and query block i
    first = lambda j: (j * bk) // bq        # query blocks before it see none
    qi = lambda j, t: jnp.maximum(t % nq, first(j))
    qh_spec = pl.BlockSpec(
        (1, bq, d), lambda g, j, t: (g * R + t // nq, qi(j, t), 0), **vm)
    rowh_spec = pl.BlockSpec(
        (1, bq, 1), lambda g, j, t: (g * R + t // nq, qi(j, t), 0), **vm)
    kv_spec = pl.BlockSpec((1, bk, d), lambda g, j, t: (g, j, 0), **vm)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, bq=bq, bk=bk, nq=nq),
        grid=(BKV, T // bk, R * nq),
        in_specs=[qh_spec, kv_spec, kv_spec,
                  pl.BlockSpec((1, bq, bk),
                               lambda g, j, t: (g // KV, qi(j, t), j), **vm),
                  qh_spec, rowh_spec, rowh_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=interpret, name="sparse_attention_dkv",
        **_params(interpret, ("parallel", "parallel", "arbitrary")))(
            q, k, v, mask, do, lse, delta)
    return dq, dk, dv


# --------------------------------------------------------------- public API
def _blocks(T, block_q, block_k):
    return _divisor_block(T, block_q), _divisor_block(T, block_k)


def _flat(a):
    return a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def masked_attention(q, k, v, mask, scale, block_q=512, block_k=512,
                     interpret=None):
    """softmax over the keys `mask` keeps of q k^T * scale, times v.
    q [B, H, T, d]; k, v [B, KV, T, d] (H a multiple of KV); mask int8
    [B, T, T], nonzero where query t reads key s, causal (s <= t) and with
    at least one key a query. Returns (o [B, H, T, d], lse [B, H, T] f32)."""
    return _masked_fwd(q, k, v, mask, scale, block_q, block_k, interpret)[0]


def _masked_fwd(q, k, v, mask, scale, block_q, block_k, interpret):
    B, H, T, d = q.shape
    bq, bk = _blocks(T, block_q, block_k)
    o, lse = _fwd(_flat(q), _flat(k), _flat(v), mask, scale, bq, bk,
                  _resolve_interpret(interpret))
    # named, so that a rematerialising caller can keep them (with the mask
    # it made) and not run the forward kernel again for the backward
    out = (checkpoint_name(o.reshape(q.shape), KEEP),
           checkpoint_name(lse.reshape(B, H, T), KEEP))
    return out, (q, k, v, mask, *out)


def _masked_bwd(scale, block_q, block_k, interpret, res, g):
    q, k, v, mask, o, lse = res
    B, H, T, d = q.shape
    bq, bk = _blocks(T, block_q, block_k)
    dq, dk, dv = _bwd(_flat(q), _flat(k), _flat(v), mask, _flat(o),
                      lse.reshape(B * H, T, 1), _flat(g[0]), scale, bq, bk,
                      _resolve_interpret(interpret))
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape), None


masked_attention.defvjp(_masked_fwd, _masked_bwd)


def _probs_kernel(q_ref, k_ref, mask_ref, lse_ref, out_ref, *, scale, bq, bk,
                  heads, rep):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j * bk > i * bq + bq - 1)
    def _skip():
        out_ref[0] = jnp.zeros_like(out_ref[0])

    @pl.when(j * bk <= i * bq + bq - 1)
    def _step():
        keep = mask_ref[0].astype(jnp.int32) != 0

        def head(h, acc):
            s = jax.lax.dot_general(
                q_ref[0, h], k_ref[0, h // rep], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            return acc + jnp.exp(jnp.where(keep, s, NEG) - lse_ref[0, h])

        out_ref[0] = jax.lax.fori_loop(
            0, heads, head, jnp.zeros((bq, bk), jnp.float32))


def head_summed_probs(q, k, lse, mask, scale, block_q=256, block_k=512,
                      interpret=None):
    """sum over heads a of exp(q_a k_{a // rep}^T * scale - lse_a) on the
    pairs `mask` keeps, 0 elsewhere: [B, T, T] float32. Shapes as
    `masked_attention`; not differentiated (the indexer's target)."""
    B, H, T, d = q.shape
    KV = k.shape[1]
    bq, bk = _blocks(T, block_q, block_k)
    interpret = _resolve_interpret(interpret)
    vm = {"memory_space": pltpu.VMEM}
    seen = lambda i, j: jnp.minimum(j, (i * bq + bq - 1) // bk)
    return pl.pallas_call(
        functools.partial(_probs_kernel, scale=scale, bq=bq, bk=bk, heads=H,
                          rep=H // KV),
        grid=(B, T // bq, T // bk),
        in_specs=[
            pl.BlockSpec((1, H, bq, d), lambda b, i, j: (b, 0, i, 0), **vm),
            pl.BlockSpec((1, KV, bk, d),
                         lambda b, i, j: (b, 0, seen(i, j), 0), **vm),
            pl.BlockSpec((1, bq, bk), lambda b, i, j: (b, i, seen(i, j)),
                         **vm),
            pl.BlockSpec((1, H, bq, 1), lambda b, i, j: (b, 0, i, 0), **vm)],
        out_specs=pl.BlockSpec((1, bq, bk), lambda b, i, j: (b, i, j), **vm),
        out_shape=jax.ShapeDtypeStruct((B, T, T), jnp.float32),
        interpret=interpret, name="sparse_attention_head_sum",
        **_params(interpret, ("parallel", "parallel", "arbitrary")))(
            q, k, mask, lse[..., None])


def _kth_kernel(x_ref, out_ref, *, k):
    x = x_ref[...]
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    # a signed integer that orders as the float does
    key = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    t = jnp.full((x.shape[0], 1), -2 ** 31, jnp.int32)
    for bit in range(31, -1, -1):       # the largest t with k keys >= t
        cand = t + jnp.int32(-2 ** 31 if bit == 31 else 1 << bit)
        n = jnp.sum((key >= cand).astype(jnp.int32), -1, keepdims=True)
        t = jnp.where(n >= k, cand, t)
    out_ref[...] = ((key >= t) & (x > -jnp.inf)).astype(jnp.int8)


def at_least_kth(scores, k, block_rows=64, interpret=None):
    """int8 [R, S]: 1 where scores[r, s] is at least the k-th largest of row
    r and is not -inf (a row with fewer than k entries above -inf keeps them
    all). scores float32 [R, S], -inf where a pair is not to be chosen."""
    R, S = scores.shape
    br = _divisor_block(R, block_rows)
    interpret = _resolve_interpret(interpret)
    spec = pl.BlockSpec((br, S), lambda i: (i, 0), memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_kth_kernel, k=k), grid=(R // br,),
        in_specs=[spec], out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((R, S), jnp.int8),
        interpret=interpret, name="sparse_attention_select",
        **_params(interpret, ("parallel",)))(scores)
