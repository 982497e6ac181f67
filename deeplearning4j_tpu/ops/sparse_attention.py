"""Attention under a per-pair mask, given or made from positions: Pallas TPU
kernels.

The main attention of a `sparseattention` layer
(`nn/conf/layers/decoder.py`) reads, for each query, only the keys its
indexer selected; the selection is one set for all heads, a bool [T, T] a
sequence (causality included). XLA's lowering would write every head's
[T, T] scores to HBM several times over; these kernels stream K/V blocks
through VMEM under the online softmax and read the mask a (block_q,
block_k) tile at a time. Grouped queries: query head a reads key/value head a // (H / KV),
chosen by the block index map, nothing is repeated in HBM.

- `masked_attention(q, k, v, mask)` -> (o, lse): forward and, through its
  custom VJP, the FlashAttention-2 backward in ONE grid pass (PR 37): a
  step makes its tile's scores, p, dp and ds once and from them the tile's
  part of dQ (summed over a query block's key blocks, innermost), of dK and
  of dV, which are summed in float32 slabs of all T keys that stay in VMEM
  while the group's query heads pass. Where the slabs, T x (d + dv + d2) x
  4 bytes, are over `SLAB_BUDGET` (16 MB: past T = 16,384 at 128-wide
  heads) the backward is the two passes it was before: dQ with the key
  blocks innermost; dK/dV with the group's query heads and query blocks
  innermost, so the sum over a group's heads happens in VMEM, and scores
  and dp made in both (7 products a tile for 5). `backward_passes` says
  which, from shapes alone.
- `shared_key_attention(q, k, v, q_shared, k_shared)` -> (o, lse): the same
  kernels for a latent attention, whose heads score over slots of
  their own AND over slots of ONE key that all of them share: two products
  a tile; the key's width need not be the value's (neither v nor o is
  padded to it); the shared key is read by index map, never repeated, and
  its gradient, a sum over every head that reads it, is summed in VMEM: in
  a third slab that stays for all of the backward's run, or inside one run
  of the two-pass dK/dV kernel.
- `head_summed_probs(q, k, lse, mask)` -> [B, T, T]: the probabilities of
  all heads added up, pair by pair: the target of the indexer's loss. A
  512 x 512 tile is walked in parts of 128 queries x 256 keys whose sum stays
  in registers while the heads pass, and the mask is applied once, to the
  sum; each head's lse is spread across the lanes once a query block. Its
  pallas_call sits under a module-level `jax.jit`, so a step traces and
  lowers it once, not once a layer and again in each rematerialisation.
- `at_least_kth(scores, k)` -> int8 [R, S]: which entries of each row are at
  least the row's k-th largest (the top-k selection with ties kept), by a
  binary search over the floats' bits that reads the row once from HBM;
  `lax.top_k` at k = 2048 of 8192 is a full sort on this chip, 2.7 ms for
  512 rows, and was 30% of the step.
- `index_scores_bwd(qi, ki, w, g)` -> (dqi, dki, dw): the backward of the
  indexer's scores I[c, s] = sum_h w[c, h] ReLU(qi[c, h] . ki[s]) for a
  chunk of queries against its prefix of keys, key block by key block: each
  head's [C, block] dots are made again in VMEM, and their three gradients
  summed there. XLA's transposed dots wrote f32[S, C, heads] and a `pred`
  of that shape out for every chunk (PR 35).

The same kernels serve an `attention` layer's plain causal and
windowed attention (`mask=None`): the tile's mask is then made inside the
kernel from the block indices and an iota (key j visible to query i iff
j <= i and, under a `window`, i - j < window), and only on the tiles that
the diagonal or the band's far edge crosses; no [T, T] array exists.
`flash_attention` ([B, T, H, d], `TransformerLM(attention="flash")`) and
`ring_hop` / `ring_hop_bwd` (each hop of `parallel/ring_attention.py`'s
kernel path) are the same kernels again; under `causal=False` the schedule
is the whole rectangle, no tile is CROSSED and no mask is made.

The mask is causal, so `masked_attention`'s kernels walk only the
(query block, key block) tiles with a visible pair (on or under the
diagonal and, under a window, inside the band): their grid is (batch x head,
step), and `tile_schedule` lists each step's tile in int32 tables that
`pltpu.PrefetchScalarGridSpec` hands to the index maps and to the kernel (a
run's first step clears the accumulators, its last writes the result). No
step is empty and no block is fetched unused; before PR 29 the grid was the
rectangle and 47% of its steps failed a `pl.when`.
`head_summed_probs` keeps the rectangle: its skipped steps write the zeros
of a full [T, T] result. Inside the causal part every tile is computed,
whatever its density. `work_keye.py` of the benchmark counts the SELECTED
pairs only, so a roofline share read from these kernels counts the
masked-out work, and the diagonal tiles' upper halves, as waste.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import compiler_params, divisor_block
from .backend import resolve_interpret as _resolve_interpret

KEEP = "sparse_attention"    # checkpoint name of what a backward needs kept
NEG = -1e30      # a masked score; finite, so a row that has met no key yet
#                  (its first tiles all masked) folds without NaN
FLOOR = -1e20    # where the forward's running maximum starts: under every
#                  real score and so far above NEG that exp(NEG - m) is 0 by
#                  itself; a row that has met no key keeps l = 0
BLOCK = 1024     # query and key block of masked_attention's kernels,
#                  under a mask operand and plain causal alike: the fastest
#                  of 256 .. 2048 a side on the chip at T = 8192, d = 128
#                  (PERF.md section 6, PR 29) and of those measured at
#                  T = 16384 with the mask made from positions (PR 32). The
#                  scoped VMEM default (16 MB) holds the forward's and the
#                  two-pass backward's 4 MB float32 tiles; the one-pass
#                  backward's four tiles beside its slabs ask for BWD_VMEM
WINDOW_BLOCK = (512, 512)     # (query, key) block under a window (PERF.md
#                  section 6, PR 32: measured alone on the chip at
#                  T = 16384, d = 128, window 512)
SLAB_BUDGET = 16 << 20      # the one-pass backward's float32 slabs of dK, dV
#                  (and a shared key's dK2), T x (d + dv + d2) x 4 bytes: up
#                  to here one kernel, past it the two (`backward_passes`);
#                  T = 16384 at d = dv = 128 is the budget exactly
HEAD_SUM_BLOCK = (512, 512)  # (query, key) block of `head_summed_probs`: the
#                  fastest of 128 x 512 .. 512 x 1,024 on the chip at
#                  T = 8192, 32 / 4 heads of 128 (PERF.md section 6)
HEAD_SUM_SUB = (128, 256)    # (rows, keys) of the part of its tile whose sum
#                  over the heads stays in registers: a head's scores and
#                  the sum, 32 vregs each; the fastest of 32 x 512 ..
#                  128 x 256 in 256 x 512 blocks on the chip
HEAD_SUM_VMEM = 48 << 20     # its VMEM: q's block and lse's (a column padded
#                  to the 128 lanes) two buffers each, lse spread across the
#                  lanes, the output's, k's and the mask's blocks: 35.5 MB
#                  at 512 x 512, 39 at 512 x 1,024, over the scoped default
#                  of 16, under the chip's 128
BWD_VMEM = 64 << 20         # the one-pass backward's VMEM: at the budget the
#                  slabs' 16 MB, their bf16 output blocks' two buffers 16
#                  and the 1024 x 1024 tiles need 48 MB (compiled for a
#                  described v5e: 40 is refused; at T = 8192 32 passes),
#                  over the scoped default of 16, under the chip's 128


def _scores(q_ref, k_ref, mask_ref, scale, band=None, tile=None, shared=()):
    """The tile's scaled scores, NEG where the pair is masked: by the mask
    operand's tile, or (`mask_ref` None) by position. `band` is (block_q,
    block_k, window or None), `tile` (query block, key block), or None for
    a tile that keeps every pair and builds no mask. `shared` is (q2_ref,
    k2_ref), further slots of the same pair's product whose key several
    key/value heads share: a second product of the tile, added before the
    scale."""
    qk = lambda a, b: jax.lax.dot_general(
        a[0], b[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    s = (qk(q_ref, k_ref) + qk(*shared) if shared
         else qk(q_ref, k_ref)) * scale
    if mask_ref is not None:
        return jnp.where(mask_ref[0].astype(jnp.int32) != 0, s, NEG)
    if tile is None:
        return s
    (bq, bk, window), (i, j) = band, tile
    # query position less key position, pair by pair
    d = (i * bq - j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
         - jax.lax.broadcasted_iota(jnp.int32, s.shape, 1))
    seen = d >= 0 if window is None else (d >= 0) & (d < window)
    return jnp.where(seen, s, NEG)


def _each_tile(mask_ref, e, tile, step):
    """Run `step`'s body once on this grid step: with a mask operand as it
    is; by position, in one of two branches, `step(tile)` where the
    diagonal or the band's far edge crosses the tile (CROSSED) and
    `step(None)`, which builds no mask, where it keeps every pair. A branch
    ends in the refs it writes; nothing the size of a tile leaves it (a
    `lax.cond` around the select alone cost 2.8 us a tile of 4 MB)."""
    if mask_ref is not None:
        return step(None)
    pl.when(e & CROSSED != 0)(lambda: step(tile))
    pl.when(e & CROSSED == 0)(lambda: step(None))


# ----------------------------------------------------------- tile schedule
FIRST, LAST = 1, 2      # bits of a step's `edge`: its run's first, its last
CROSSED = 4             # the diagonal or the band's far edge crosses the
#                         tile: some of its pairs are masked by position
OWN_FIRST, OWN_LAST = 8, 16     # the first and last step of ONE key/value
#                         head's run: in `by_key` under a shared key, inside
#                         the run of the shared key's block; in `one_pass`,
#                         inside the run of all heads that read a shared key
#                         (the whole of it where there is none)


def tile_schedule(T, bq, bk, heads=1, window=None, own=None, causal=True):
    """The tiles that `masked_attention`'s kernels visit at query blocks of
    `bq` and key blocks of `bk`: those with a visible pair (key j <= query
    i and, under a `window`, i - j < window), each once, and no other; with
    `causal` False every tile of the rectangle, none of them CROSSED.
    `grid_steps` is the length of the kernels' second grid axis a head (the
    first walks batch x head), and `computing_steps` how many of those steps
    hold such a pair: all of them. The tables are int32 numpy arrays, one
    entry a step:

    - `by_query` (i, j, edge): query block by query block, its key blocks
      first(i) .. last(i) in order (forward, dQ: one run a query block);
    - `by_key` (j, r, i, edge): key block by key block, inside it head r of
      `heads` (a key/value head's group of query heads), inside that the
      query blocks first(j) .. last(j) (the two-pass dK/dV: one run a key
      block, so the sum over the group's heads stays in VMEM);
    - `one_pass` (r, i, j, edge): `by_query` once for each head r of `heads`
      (the one-pass backward: dQ's run is a query block's, as in
      `by_query`; dK and dV are summed in slabs of all T keys that stay in
      VMEM for the `own` heads, all `heads` without it, that read one
      key/value head: OWN_FIRST .. OWN_LAST).

    `edge` marks a run's first step (FIRST: clear the accumulators), its
    last (LAST: write the result) and a tile with a masked pair (CROSSED:
    the diagonal or the band's far edge passes through it). With `own`
    (`shared_key_attention`: `heads` query heads read one shared key, each
    `own` of them one key/value head of their own) `by_key` also marks
    where a key/value head's own run inside the shared block's begins and
    ends (OWN_FIRST, OWN_LAST). For a fixed key block `one_pass` and
    `by_key` visit the tiles in the same order: head r, then query block
    i."""
    nq, nk = T // bq, T // bk
    far = T if window is None else window     # i - j < far is always asked
    # the key blocks query block i sees, the query blocks that see j
    keys = lambda i: range(max(0, i * bq - far + 1) // bk,
                           (i * bq + bq - 1) // bk + 1)
    queries = lambda j: range(j * bk // bq,
                              min(T - 1, j * bk + bk + far - 2) // bq + 1)
    # i - j over the tile spans lo .. hi; every pair is seen iff all of
    # it lies in 0 .. far - 1
    crossed = lambda i, j: CROSSED * (
        i * bq - (j * bk + bk - 1) < 0 or i * bq + bq - 1 - j * bk >= far)
    seen = lambda i, j: (j * bk <= i * bq + bq - 1
                         and i * bq - (j * bk + bk - 1) < far)
    if not causal:
        keys, queries = lambda i: range(nk), lambda j: range(nq)
        crossed, seen = (lambda i, j: 0), (lambda i, j: True)
    ends = lambda x, run: FIRST * (x == run[0]) | LAST * (x == run[-1])
    by_query = [(i, j, ends(j, keys(i)) | crossed(i, j))
                for i in range(nq) for j in keys(i)]
    owns = lambda r, i, run: 0 if own is None else (
        OWN_FIRST * (r % own == 0 and i == run[0])
        | OWN_LAST * (r % own == own - 1 and i == run[-1]))
    by_key = [(j, r, i, FIRST * (r == 0 and i == queries(j)[0])
               | LAST * (r == heads - 1 and i == queries(j)[-1])
               | crossed(i, j) | owns(r, i, queries(j)))
              for j in range(nk) for r in range(heads) for i in queries(j)]
    group = own or heads
    one_pass = [(r, i, j, e
                 | OWN_FIRST * (r % group == 0 and t == 0)
                 | OWN_LAST * (r % group == group - 1
                               and t == len(by_query) - 1))
                for r in range(heads) for t, (i, j, e) in enumerate(by_query)]
    cols = lambda rows: tuple(np.asarray(c, np.int32) for c in zip(*rows))
    return {"grid_steps": len(by_query),
            "computing_steps": sum(seen(i, j) for i, j, _ in by_query),
            "by_query": cols(by_query), "by_key": cols(by_key),
            "one_pass": cols(one_pass)}


def _call(kernel, tables, grid, in_specs, out_specs, out_shape, scratch,
          name, interpret, operands, aliases=None, vmem_limit_bytes=None):
    """One kernel over (batch x head, the steps of `tables`): the tables
    are prefetched scalars, read by the index maps and by the kernel;
    `aliases` {operand: result} are written in place."""
    return pl.pallas_call(
        kernel, out_shape=out_shape, interpret=interpret, name=name,
        input_output_aliases={len(tables) + a: b
                              for a, b in (aliases or {}).items()},
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables), grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch),
        **compiler_params(interpret, ("parallel", "arbitrary"),
                          vmem_limit_bytes))(
            *(jnp.asarray(t) for t in tables), *operands)


def _given(mask, qkv, its, rest=()):
    """A kernel's operands or specs in order: q, k, v, then the mask's
    (`its`) where there is a mask operand and nothing where the kernels
    make the mask from positions, then the rest."""
    return [*qkv, *([its] if mask is not None else []), *rest]


# ------------------------------------------------------------------ forward
def _fwd_kernel(i_tab, j_tab, edge, q_ref, k_ref, v_ref, *rest, scale,
                band=None, shared=False):
    *rest, o_ref, lse_ref, m_ref, l_ref, acc_ref = rest
    if shared:
        *rest, q2_ref, k2_ref = rest
    more = (q2_ref, k2_ref) if shared else ()
    mask_ref = rest[0] if rest else None
    t = pl.program_id(1)
    e = edge[t]

    @pl.when(e & FIRST != 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, FLOOR)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def step(tile):
        s = _scores(q_ref, k_ref, mask_ref, scale, band, tile, more)
        m_prev = m_ref[:, :1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)       # a masked pair: exp(NEG - m_cur) is 0
        l_ref[:, :1] = l_ref[:, :1] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:, :1] = m_cur

    _each_tile(mask_ref, e, (i_tab[t], j_tab[t]), step)

    @pl.when(e & LAST != 0)
    def _emit():
        l_fin = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[:] / l_fin).astype(o_ref.dtype)
        lse_ref[0] = m_ref[:, :1] + jnp.log(l_fin)


def _specs(bq, bk, d, R, H, dv, R2=None, d2=None):
    """Block specs of a (batch * head b, step t) grid whose step t is tile
    (i_tab[t], j_tab[t]) of `tile_schedule`'s `by_query`: q-shaped,
    k-shaped (the group's head), the mask's tile, a per-row column, then v-
    and o-shaped (the summed width `dv` need not be the scored width `d`)
    and, under a shared key of width `d2` that `R2` query heads read, its
    query- and key-shaped ones. No step lies outside the band, so nothing
    is fetched that is not used; a block whose index the next step keeps is
    not fetched again."""
    vm = {"memory_space": pltpu.VMEM}
    rows = lambda w: pl.BlockSpec(
        (1, bq, w), lambda b, t, i, j, e: (b, i[t], 0), **vm)
    keys = lambda w, rep: pl.BlockSpec(
        (1, bk, w), lambda b, t, i, j, e: (b // rep, j[t], 0), **vm)
    return (rows(d), keys(d, R),
            pl.BlockSpec((1, bq, bk),
                         lambda b, t, i, j, e: (b // H, i[t], j[t]), **vm),
            rows(1), keys(dv, R), rows(dv),
            *((rows(d2), keys(d2, R2)) if d2 else ()))


def _fwd(q, k, v, mask, scale, bq, bk, interpret, window=None, shared=(),
         causal=True, dtype=None):
    """q [B*H, T, d], k [B*KV, T, d], v [B*KV, T, dv], mask int8 [B, T, T]
    or None (by position: causal, inside `window`, or with `causal` False
    every pair); `shared` (q2 [B*H, T, d2], k2 [B*KS, T, d2]): the slots
    scored against a key that several key/value heads share. o is in
    `dtype`, q's where None."""
    BH, T, d = q.shape
    dv = v.shape[-1]
    q_spec, k_spec, mask_spec, row_spec, v_spec, o_spec, *shared_specs = \
        _specs(bq, bk, d, BH // k.shape[0],
               1 if mask is None else BH // mask.shape[0], dv,
               *((BH // shared[1].shape[0], shared[1].shape[-1])
                 if shared else ()))
    sched = tile_schedule(T, bq, bk, window=window, causal=causal)
    band = None if mask is not None else (bq, bk, window)
    return _call(
        functools.partial(_fwd_kernel, scale=scale, band=band,
                          shared=bool(shared)),
        sched["by_query"], (BH, sched["grid_steps"]),
        _given(mask, (q_spec, k_spec, v_spec), mask_spec, shared_specs),
        [o_spec, row_spec],
        [jax.ShapeDtypeStruct((BH, T, dv), dtype or q.dtype),
         jax.ShapeDtypeStruct((BH, T, 1), jnp.float32)],
        [pltpu.VMEM((bq, 128), jnp.float32),
         pltpu.VMEM((bq, 128), jnp.float32),
         pltpu.VMEM((bq, dv), jnp.float32)],
        "sparse_attention_fwd", interpret,
        _given(mask, (q, k, v), mask, shared))


# ----------------------------------------------------------------- backward
def _dq_kernel(i_tab, j_tab, edge, q_ref, k_ref, v_ref, *rest, scale,
               band=None, shared=False):
    if shared:
        *rest, q2_ref, k2_ref, dq_ref, dq2_ref, acc_ref, acc2_ref = rest
    else:
        *rest, dq_ref, acc_ref = rest
    more = (q2_ref, k2_ref) if shared else ()
    *mask_ref, do_ref, lse_ref, delta_ref = rest
    mask_ref = mask_ref[0] if mask_ref else None
    t = pl.program_id(1)
    e = edge[t]

    @pl.when(e & FIRST != 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        if shared:
            acc2_ref[:] = jnp.zeros_like(acc2_ref)

    def step(tile):
        p = jnp.exp(_scores(q_ref, k_ref, mask_ref, scale, band, tile, more)
                    - lse_ref[0])
        dp = jax.lax.dot_general(do_ref[0], v_ref[0],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0])
        acc_ref[:] += jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if shared:
            acc2_ref[:] += jax.lax.dot_general(
                ds.astype(k2_ref.dtype), k2_ref[0], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale

    _each_tile(mask_ref, e, (i_tab[t], j_tab[t]), step)

    @pl.when(e & LAST != 0)
    def _emit():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)
        if shared:
            dq2_ref[0] = acc2_ref[:].astype(dq2_ref.dtype)


def _dkv_kernel(j_tab, r_tab, i_tab, edge, q_ref, k_ref, v_ref, *rest,
                scale, band=None, shared=False):
    """One run a key block: dK and dV summed over the query heads that read
    the key/value head, in VMEM. Under a shared key the run is the SHARED
    key's block: all its query heads pass, dK2 is summed over them all, and
    each key/value head's dK and dV over its own stretch of the run
    (OWN_FIRST .. OWN_LAST)."""
    if shared:
        (*rest, q2_ref, k2_ref, dk_ref, dv_ref, dk2_ref, dk_acc, dv_acc,
         dk2_acc) = rest
    else:
        *rest, dk_ref, dv_ref, dk_acc, dv_acc = rest
    more = (q2_ref, k2_ref) if shared else ()
    *mask_ref, do_ref, lse_ref, delta_ref = rest
    mask_ref = mask_ref[0] if mask_ref else None
    t = pl.program_id(1)
    e = edge[t]
    first, last = (OWN_FIRST, OWN_LAST) if shared else (FIRST, LAST)

    @pl.when(e & first != 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    if shared:
        @pl.when(e & FIRST != 0)
        def _init_shared():
            dk2_acc[:] = jnp.zeros_like(dk2_acc)

    def step(tile):
        p = jnp.exp(_scores(q_ref, k_ref, mask_ref, scale, band, tile, more)
                    - lse_ref[0])
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
        if shared:
            dk2_acc[:] += jax.lax.dot_general(
                ds.astype(q2_ref.dtype), q2_ref[0], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale

    _each_tile(mask_ref, e, (i_tab[t], j_tab[t]), step)

    @pl.when(e & last != 0)
    def _emit():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    if shared:
        @pl.when(e & LAST != 0)
        def _emit_shared():
            dk2_ref[0] = dk2_acc[:].astype(dk2_ref.dtype)


def _bwd_kernel(r_tab, i_tab, j_tab, edge, q_ref, k_ref, v_ref, *rest, scale,
                bk, band=None, shared=False):
    """The whole backward of a tile in one step: its scores, p, dp and ds
    are made once, `ds k` joins the query block's dQ (one run a query
    block, as `_dq_kernel`'s), `p^T do` and `ds^T q` join rows j * bk .. of
    dV's and dK's float32 slabs of all T keys, which stay in VMEM while the
    heads that read the key/value head pass (OWN_FIRST .. OWN_LAST) and
    are written out once. Under a shared key all its query heads pass in
    one run of the grid's second axis, and dK2's slab stays for them all."""
    if shared:
        (*rest, q2_ref, k2_ref, dq_ref, dk_ref, dv_ref, dq2_ref, dk2_ref,
         dq_acc, dk_acc, dv_acc, dq2_acc, dk2_acc) = rest
    else:
        *rest, dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc = rest
    more = (q2_ref, k2_ref) if shared else ()
    *mask_ref, do_ref, lse_ref, delta_ref = rest
    mask_ref = mask_ref[0] if mask_ref else None
    t = pl.program_id(1)
    e = edge[t]

    @pl.when(e & FIRST != 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)
        if shared:
            dq2_acc[:] = jnp.zeros_like(dq2_acc)

    @pl.when(e & OWN_FIRST != 0)
    def _init_own():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    if shared:
        @pl.when(t == 0)
        def _init_shared():
            dk2_acc[:] = jnp.zeros_like(dk2_acc)

    def step(tile):
        keys = pl.ds(pl.multiple_of(j_tab[t] * bk, bk), bk)
        p = jnp.exp(_scores(q_ref, k_ref, mask_ref, scale, band, tile, more)
                    - lse_ref[0])
        dv_acc[keys] += jax.lax.dot_general(
            p.astype(do_ref.dtype), do_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do_ref[0], v_ref[0],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0])
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        dk_acc[keys] += jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if shared:
            dq2_acc[:] += jax.lax.dot_general(
                ds.astype(k2_ref.dtype), k2_ref[0], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            dk2_acc[keys] += jax.lax.dot_general(
                ds.astype(q2_ref.dtype), q2_ref[0], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale

    _each_tile(mask_ref, e, (i_tab[t], j_tab[t]), step)

    @pl.when(e & LAST != 0)
    def _emit():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)
        if shared:
            dq2_ref[0] = dq2_acc[:].astype(dq2_ref.dtype)

    @pl.when(e & OWN_LAST != 0)
    def _emit_own():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    if shared:
        @pl.when(t == pl.num_programs(1) - 1)
        def _emit_shared():
            dk2_ref[0] = dk2_acc[:].astype(dk2_ref.dtype)


def backward_passes(T, d, dv, d2=0):
    """How many times the backward walks its tiles at T keys of scored
    width `d` (+ `d2` against a shared key) and summed width `dv`: 1 where
    the one-pass kernel's float32 slabs of dK, dV (and dK2) fit SLAB_BUDGET,
    2 where `_bwd` keeps the dQ and the dK/dV kernel. Shapes alone decide."""
    return 1 if T * (d + dv + d2) * 4 <= SLAB_BUDGET else 2


def _bwd(q, k, v, mask, o, lse, do, scale, bq, bk, interpret, window=None,
         shared=(), causal=True, dtype=None):
    """(dq, dk, dv) or, with `shared`, (dq, dk, dv, dq2, dk2): by one kernel
    where `backward_passes` says 1, else by two. Each gradient is in its
    operand's dtype, or in `dtype` where one is given."""
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1,
                    keepdims=True)
    d2 = shared[1].shape[-1] if shared else 0
    walk = (_one_pass if backward_passes(q.shape[1], q.shape[2], v.shape[-1],
                                         d2) == 1 else _two_pass)
    return walk(q, k, v, mask, do, lse, delta, scale, bq, bk, interpret,
                window, shared, causal, dtype)


def _one_pass(q, k, v, mask, do, lse, delta, scale, bq, bk, interpret,
              window, shared, causal, dtype):
    BH, T, d = q.shape
    BKV, dv = k.shape[0], v.shape[-1]
    B = 1 if mask is None else mask.shape[0]     # only the mask's specs ask
    R, KV = BH // BKV, BKV // B
    BKS, d2 = shared[1].shape[::2] if shared else (BKV, None)
    R2 = BH // BKS              # query heads a run of the second axis passes
    vm = {"memory_space": pltpu.VMEM}
    # step t of key head g (the shared key's where there is one, else the
    # key/value head): head r[t] of the R2 query heads that read g, query
    # block i[t], key block j[t] (`tile_schedule`'s `one_pass`); the
    # key/value head is query head g * R2 + r[t]'s
    sched = tile_schedule(T, bq, bk, heads=R2, window=window,
                          own=R if shared else None, causal=causal)
    head = lambda g, r, t: g * R2 + r[t]
    of_query = lambda w: pl.BlockSpec(
        (1, bq, w), lambda g, t, r, i, j, e: (head(g, r, t), i[t], 0), **vm)
    of_key = lambda w: pl.BlockSpec(
        (1, bk, w), lambda g, t, r, i, j, e: (head(g, r, t) // R, j[t], 0),
        **vm)
    slab = lambda w: pl.BlockSpec(
        (1, T, w), lambda g, t, r, i, j, e: (head(g, r, t) // R, 0, 0), **vm)
    shared_block = pl.BlockSpec(
        (1, bk, d2), lambda g, t, r, i, j, e: (g, j[t], 0), **vm)
    shared_slab = pl.BlockSpec(
        (1, T, d2), lambda g, t, r, i, j, e: (g, 0, 0), **vm)
    shape = lambda a: jax.ShapeDtypeStruct(a.shape, dtype or a.dtype)
    operands = _given(mask, (q, k, v), mask, (do, lse, delta, *shared))
    return tuple(_call(
        functools.partial(_bwd_kernel, scale=scale, bk=bk,
                          band=None if mask is not None else (bq, bk, window),
                          shared=bool(shared)),
        sched["one_pass"], (BKS, R2 * sched["grid_steps"]),
        _given(mask, (of_query(d), of_key(d), of_key(dv)),
               pl.BlockSpec((1, bq, bk),
                            lambda g, t, r, i, j, e: (g // KV, i[t], j[t]),
                            **vm),
               (of_query(dv), of_query(1), of_query(1),
                *((of_query(d2), shared_block) if shared else ()))),
        [of_query(d), slab(d), slab(dv),
         *((of_query(d2), shared_slab) if shared else ())],
        [shape(q), shape(k), shape(v), *(shape(a) for a in shared)],
        [pltpu.VMEM(dims, jnp.float32) for dims in (
            (bq, d), (T, d), (T, dv),
            *(((bq, d2), (T, d2)) if shared else ()))],
        "sparse_attention_bwd", interpret, operands,
        # q, k, v and the shared pair, the last two operands, die with
        # this call: their gradients are written in their places (not so
        # where `dtype` is asked: a ring's hop passes k and v on)
        aliases=None if dtype else dict(zip(
            (0, 1, 2, len(operands) - 2, len(operands) - 1),
            range(3 + len(shared)))),
        vmem_limit_bytes=BWD_VMEM))


def _two_pass(q, k, v, mask, do, lse, delta, scale, bq, bk, interpret,
              window, shared, causal, dtype):
    BH, T, d = q.shape
    BKV, dv = k.shape[0], v.shape[-1]
    B = 1 if mask is None else mask.shape[0]     # only the mask's specs ask
    H, R, KV = BH // B, BH // BKV, BKV // B
    vm = {"memory_space": pltpu.VMEM}
    band = None if mask is not None else (bq, bk, window)
    operands = _given(mask, (q, k, v), mask, (do, lse, delta, *shared))
    BKS, d2 = shared[1].shape[::2] if shared else (BKV, None)
    R2 = BH // BKS              # query heads a run of `by_key` passes
    more = [d2] if shared else []       # the width of a third accumulator
    q_spec, k_spec, mask_spec, row_spec, v_spec, o_spec, *shared_specs = \
        _specs(bq, bk, d, R, H, dv, *((R2, d2) if shared else ()))
    sched = tile_schedule(T, bq, bk, window=window, causal=causal)
    shape = lambda a: jax.ShapeDtypeStruct(a.shape, dtype or a.dtype)
    dq = _call(
        functools.partial(_dq_kernel, scale=scale, band=band,
                          shared=bool(shared)),
        sched["by_query"], (BH, sched["grid_steps"]),
        _given(mask, (q_spec, k_spec, v_spec), mask_spec,
               (o_spec, row_spec, row_spec, *shared_specs)),
        [q_spec, shared_specs[0]] if shared else q_spec,
        [shape(q), shape(shared[0])] if shared else shape(q),
        [pltpu.VMEM((bq, w), jnp.float32) for w in (d, *more)],
        "sparse_attention_dq", interpret, operands)
    # step t of key head g (the shared key's where there is one, else the
    # key/value head): key block j[t], head r[t] of the R2 query heads that
    # read g, query block i[t] (`tile_schedule`'s `by_key`); a key/value
    # head's own blocks are those of query head g * R2 + r[t]
    sched = tile_schedule(T, bq, bk, heads=R2, window=window,
                          own=R if shared else None, causal=causal)
    of_query = lambda w: pl.BlockSpec(
        (1, bq, w), lambda g, t, j, r, i, e: (g * R2 + r[t], i[t], 0), **vm)
    of_key = lambda w: pl.BlockSpec(
        (1, bk, w), (lambda g, t, j, r, i, e: ((g * R2 + r[t]) // R, j[t], 0))
        if shared else (lambda g, t, j, r, i, e: (g, j[t], 0)), **vm)
    of_shared = lambda: pl.BlockSpec(
        (1, bk, d2), lambda g, t, j, r, i, e: (g, j[t], 0), **vm)
    outs = _call(
        functools.partial(_dkv_kernel, scale=scale, band=band,
                          shared=bool(shared)),
        sched["by_key"], (BKS, R2 * sched["grid_steps"]),
        _given(mask, (of_query(d), of_key(d), of_key(dv)),
               pl.BlockSpec((1, bq, bk),
                            lambda g, t, j, r, i, e: (g // KV, i[t], j[t]),
                            **vm),
               (of_query(dv), of_query(1), of_query(1),
                *((of_query(d2), of_shared()) if shared else ()))),
        [of_key(d), of_key(dv), *([of_shared()] if shared else [])],
        [shape(k), shape(v), *([shape(shared[1])] if shared else [])],
        [pltpu.VMEM((bk, w), jnp.float32) for w in (d, dv, *more)],
        "sparse_attention_dkv", interpret, operands)
    return (dq, *outs) if not shared else (dq[0], *outs[:2], dq[1], outs[2])


# --------------------------------------------------------------- public API
def _blocks(T, block_q, block_k, window=None):
    """The kernels' block shape at T: what was asked for, else the shape
    measured for the schedule (the causal walk, or the band under a
    window), each side cut to a divisor of T."""
    bq, bk = (BLOCK, BLOCK) if window is None else WINDOW_BLOCK
    return divisor_block(T, block_q or bq), divisor_block(T, block_k or bk)


def grid_steps_per_tile(T, block_q=None, block_k=None, window=None):
    """Grid steps over computing steps of `masked_attention`'s kernels at T:
    1.0 when no step is empty (PR 28's rectangular grid of 512 x 512 read
    256 / 136 = 1.88 at T = 8192)."""
    sched = tile_schedule(T, *_blocks(T, block_q, block_k, window),
                          window=window)
    return sched["grid_steps"] / sched["computing_steps"]


def _flat(a):
    return a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def masked_attention(q, k, v, mask, scale, block_q=None, block_k=None,
                     interpret=None, window=None, causal=True):
    """softmax over the keys `mask` keeps of q k^T * scale, times v.
    q [B, H, T, d]; k [B, KV, T, d], v [B, KV, T, dv] (H a multiple of KV;
    the summed width dv need not be the scored width d: o is dv wide and
    nothing is padded); mask int8
    [B, T, T], nonzero where query t reads key s, causal (s <= t) and with
    at least one key a query; or None: query t reads the keys s <= t and,
    under a `window`, t - s < window, the mask made inside the kernels
    from positions. With `causal` False every tile is walked: query t
    reads every key (mask None) or the keys `mask` keeps, whatever their
    place. A T no longer than a block is one tile. Returns
    (o [B, H, T, d], lse [B, H, T] f32)."""
    return _masked_fwd(q, k, v, mask, scale, block_q, block_k, interpret,
                       window, causal)[0]


def _masked_fwd(q, k, v, mask, scale, block_q, block_k, interpret, window,
                causal):
    if mask is not None and window is not None:
        raise ValueError("a mask operand carries its own window")
    if window is not None and not causal:
        raise ValueError("a window is causal")
    bq, bk = _blocks(q.shape[2], block_q, block_k, window)
    out = _kept(q, v, *_fwd(_flat(q), _flat(k), _flat(v), mask, scale, bq, bk,
                            _resolve_interpret(interpret), window,
                            causal=causal))
    return out, (q, k, v, mask, *out)


def _kept(q, v, o, lse):
    """The forward kernel's results in the caller's layout, o [B, H, T, dv]
    and lse [B, H, T], NAMED, so that a rematerialising caller can keep
    them (with the mask it made) and not run the forward kernel again for
    the backward."""
    return (checkpoint_name(o.reshape(q.shape[:3] + v.shape[3:]), KEEP),
            checkpoint_name(lse.reshape(q.shape[:3]), KEEP))


def _masked_bwd(scale, block_q, block_k, interpret, window, causal, res, g):
    q, k, v, mask, o, lse = res
    B, H, T, d = q.shape
    bq, bk = _blocks(T, block_q, block_k, window)
    dq, dk, dv = _bwd(_flat(q), _flat(k), _flat(v), mask, _flat(o),
                      lse.reshape(B * H, T, 1), _flat(g[0]), scale, bq, bk,
                      _resolve_interpret(interpret), window, causal=causal)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape), None


masked_attention.defvjp(_masked_fwd, _masked_bwd)


def flash_attention(q, k, v, causal=True, scale=None, block_q=1024,
                    block_k=1024, interpret=None):
    """Causal (or, `causal` False, full) attention over [B, T, H, d]: q, k
    and v as `parallel/ring_attention.py` lays them out, scale 1/sqrt(d)
    unless given; `masked_attention` without a mask, its heads moved to
    the second axis and back."""
    heads = lambda a: jnp.swapaxes(a, 1, 2)
    scale = 1.0 / math.sqrt(q.shape[-1]) if scale is None else scale
    o, _ = masked_attention(heads(q), heads(k), heads(v), None, scale,
                            block_q, block_k, interpret, None, causal)
    return heads(o)


def ring_hop(q, k, v, scale, causal, interpret=None):
    """One hop of ring attention (`parallel/ring_attention.py`): a chunk's
    queries against the keys of the chunk the ring has brought, q, k, v
    [BH, T, d], a key/value head a query head. `causal` True for the hop of
    the chunk itself (key j seen by query i iff j <= i); False for a chunk
    wholly before it, whose every pair is seen. (o [BH, T, d], lse [BH, T,
    1]), both float32: the ring folds its hops by lse and rounds once."""
    bq, bk = _blocks(q.shape[1], None, None)
    return _fwd(q, k, v, None, scale, bq, bk, _resolve_interpret(interpret),
                causal=causal, dtype=jnp.float32)


def ring_hop_bwd(q, k, v, o, lse, do, scale, causal, interpret=None):
    """The hop's parts of dq, dk, dv, float32, at the ring's o [BH, T, d]
    and lse [BH, T, 1]: those of all hops folded, which make each hop's
    probabilities exact. Shapes and `causal` as `ring_hop`."""
    bq, bk = _blocks(q.shape[1], None, None)
    return _bwd(q, k, v, None, o, lse, do, scale, bq, bk,
                _resolve_interpret(interpret), causal=causal,
                dtype=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def shared_key_attention(q, k, v, q_shared, k_shared, scale, block_q=None,
                         block_k=None, interpret=None):
    """Causal attention whose scores have a part of the head's own and a
    part against ONE key that several heads share (a latent attention's
    rotary key): softmax over s <= t of (q k^T + q_shared k_shared^T) *
    scale, times v. q [B, H, T, d], k [B, KV, T, d], v [B, KV, T, dv],
    q_shared [B, H, T, d2], k_shared [B, KS, T, d2]; KV a multiple of KS, H
    of KV. Two products a tile over the same three kernels as
    `masked_attention`; the shared key is never repeated in HBM, and its
    gradient, a sum over the H / KS heads that read it, is summed in VMEM
    inside one run of the dK/dV kernel. Returns (o [B, H, T, dv],
    lse [B, H, T] f32)."""
    return _shared_fwd(q, k, v, q_shared, k_shared, scale, block_q, block_k,
                       interpret)[0]


def _shared_fwd(q, k, v, q2, k2, scale, block_q, block_k, interpret):
    H, T = q.shape[1:3]
    if k.shape[1] % k2.shape[1] or H % k.shape[1]:
        raise ValueError(f"{H} query heads over {k.shape[1]} key/value "
                         f"heads over {k2.shape[1]} shared keys")
    bq, bk = _blocks(T, block_q, block_k)
    out = _kept(q, v, *_fwd(_flat(q), _flat(k), _flat(v), None, scale, bq, bk,
                            _resolve_interpret(interpret),
                            shared=(_flat(q2), _flat(k2))))
    return out, (q, k, v, q2, k2, *out)


def _shared_bwd(scale, block_q, block_k, interpret, res, g):
    q, k, v, q2, k2, o, lse = res
    B, H, T, _ = q.shape
    bq, bk = _blocks(T, block_q, block_k)
    grads = _bwd(_flat(q), _flat(k), _flat(v), None, _flat(o),
                 lse.reshape(B * H, T, 1), _flat(g[0]), scale, bq, bk,
                 _resolve_interpret(interpret),
                 shared=(_flat(q2), _flat(k2)))
    return tuple(a.reshape(b.shape) for a, b in zip(grads,
                                                    (q, k, v, q2, k2)))


shared_key_attention.defvjp(_shared_fwd, _shared_bwd)


def _probs_kernel(q_ref, k_ref, mask_ref, lse_ref, out_ref, lse_lanes, *,
                  scale, bq, bk, heads, rep, rows, keys):
    """A (query block, key block) tile in parts of `rows` x `keys` whose sum
    stays in registers while the heads pass, in order, so the float32 sum
    rounds as a walk head by head does. The mask is applied once, to the
    sum: an unkept pair adds its exp unmasked (a score that overflows adds
    inf, never NaN) and is written 0, as the walk that masked each head's
    score with NEG wrote exp(NEG - lse) = 0 for it."""
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)            # a query block's first step: every head's
    def _spread():              # lse across the lanes, once
        lse_lanes[...] = jnp.broadcast_to(lse_ref[0], lse_lanes.shape)

    @pl.when(j * bk > i * bq + bq - 1)
    def _skip():
        out_ref[0] = jnp.zeros_like(out_ref[0])

    @pl.when(j * bk <= i * bq + bq - 1)
    def _step():
        lanes = min(keys, 128)
        for r in range(0, bq, rows):
            for c in range(0, bk, keys):
                acc = jnp.zeros((rows, keys), jnp.float32)
                for h in range(heads):
                    s = jax.lax.dot_general(
                        q_ref[0, h, r:r + rows],
                        k_ref[0, h // rep, c:c + keys],
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    lse = lse_lanes[h, r:r + rows, :lanes]
                    acc = acc + jnp.exp(
                        s * scale - jnp.tile(lse, (1, keys // lanes)))
                keep = mask_ref[0, r:r + rows, c:c + keys].astype(
                    jnp.int32) != 0
                out_ref[0, r:r + rows, c:c + keys] = jnp.where(keep, acc, 0.0)


def _sub_tile(bq, bk):
    """(rows, keys) of `_probs_kernel`'s part of a tile: `HEAD_SUM_SUB`
    where the block takes it, else no more than a block, and keys no more
    than the 128 lanes."""
    rows, keys = HEAD_SUM_SUB
    return (divisor_block(bq, rows),
            keys if bk % keys == 0 else divisor_block(bk, 128))


@functools.partial(jax.jit, static_argnames=("scale", "bq", "bk", "rows",
                                             "keys", "interpret"))
def _head_sum(q, k, lse, mask, *, scale, bq, bk, rows, keys, interpret):
    B, H, T, d = q.shape
    KV = k.shape[1]
    vm = {"memory_space": pltpu.VMEM}
    seen = lambda i, j: jnp.minimum(j, (i * bq + bq - 1) // bk)
    return pl.pallas_call(
        functools.partial(_probs_kernel, scale=scale, bq=bq, bk=bk, heads=H,
                          rep=H // KV, rows=rows, keys=keys),
        grid=(B, T // bq, T // bk),
        in_specs=[
            pl.BlockSpec((1, H, bq, d), lambda b, i, j: (b, 0, i, 0), **vm),
            pl.BlockSpec((1, KV, bk, d),
                         lambda b, i, j: (b, 0, seen(i, j), 0), **vm),
            pl.BlockSpec((1, bq, bk), lambda b, i, j: (b, i, seen(i, j)),
                         **vm),
            pl.BlockSpec((1, H, bq, 1), lambda b, i, j: (b, 0, i, 0), **vm)],
        out_specs=pl.BlockSpec((1, bq, bk), lambda b, i, j: (b, i, j), **vm),
        scratch_shapes=[pltpu.VMEM((H, bq, 128), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((B, T, T), jnp.float32),
        interpret=interpret, name="sparse_attention_head_sum",
        **compiler_params(interpret, ("parallel", "parallel", "arbitrary"),
                          HEAD_SUM_VMEM))(q, k, mask, lse[..., None])


def head_summed_probs(q, k, lse, mask, scale, block_q=None, block_k=None,
                      interpret=None):
    """sum over heads a of exp(q_a k_{a // rep}^T * scale - lse_a) on the
    pairs `mask` keeps, 0 elsewhere: [B, T, T] float32. Shapes as
    `masked_attention`; not differentiated (the indexer's target). Blocks
    `HEAD_SUM_BLOCK` unless asked, each side cut to a divisor of T."""
    T = q.shape[2]
    bq = divisor_block(T, block_q or HEAD_SUM_BLOCK[0])
    bk = divisor_block(T, block_k or HEAD_SUM_BLOCK[1])
    rows, keys = _sub_tile(bq, bk)
    return _head_sum(q, k, lse, mask, scale=scale, bq=bq, bk=bk, rows=rows,
                     keys=keys, interpret=_resolve_interpret(interpret))


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
    br = divisor_block(R, block_rows)
    interpret = _resolve_interpret(interpret)
    spec = pl.BlockSpec((br, S), lambda i: (i, 0), memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_kth_kernel, k=k), grid=(R // br,),
        in_specs=[spec], out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((R, S), jnp.int8),
        interpret=interpret, name="sparse_attention_select",
        **compiler_params(interpret, ("parallel",)))(scores)


# ------------------------------------------- the index scores' backward
INDEX_BLOCK = 512       # key block of `index_scores_bwd`: the fastest of
#                         128 .. 2048 on the chip at C = 512, 16 heads of 64,
#                         a row's 16 prefixes of T = 8192 (PERF.md section 6,
#                         PR 35)
INDEX_VMEM = 48 << 20   # the kernel's VMEM: a head's float32 tiles, the
#                         cotangent's two buffers and the 64-wide arrays,
#                         padded to the 128 lanes, take 18 MB at 512 keys a
#                         block and 42 at 2,048, over the scoped default of
#                         16 and well under the chip's 128


def _index_bwd_kernel(qi_ref, ki_ref, w_ref, g_ref, dqi_ref, dki_ref, dw_ref,
                      dqi_acc, dki_acc, dw_acc, *, S, bk):
    """One key block of a chunk: head by head the dots d = qi_h ki_j^T once
    more, and from them the three gradients' parts. dqi and dw are summed
    over the key blocks in VMEM and written by the last step; dki_j is
    whole in its step, for all of the chunk's queries are in the block."""
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        dqi_acc[...] = jnp.zeros_like(dqi_acc)
        dw_acc[...] = jnp.zeros_like(dw_acc)

    g, ki = g_ref[...], ki_ref[...]
    if S % bk:      # the last block's keys past S hold whatever was there
        seen = lambda shape, axis: j * bk + jax.lax.broadcasted_iota(
            jnp.int32, shape, axis) < S
        g = jnp.where(seen(g.shape, 1), g, 0.0)
        ki = jnp.where(seen(ki.shape, 0), ki, jnp.zeros_like(ki))
    w = w_ref[...]
    column = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
    dki_acc[...] = jnp.zeros_like(dki_acc)

    def head(h, carry):
        q = qi_ref[h]
        d = jax.lax.dot_general(q, ki, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        pos = d > 0
        mine = column == h
        w_h = jnp.sum(jnp.where(mine, w, 0.0), -1, keepdims=True)
        # selects, not products with ReLU(d): a key past S may hold a NaN
        dw_acc[...] += jnp.where(mine, jnp.sum(
            jnp.where(pos, g * d, 0.0), -1, keepdims=True), 0.0)
        # the operand the MXU is given: what XLA's transposed dots round
        # the float32 cotangent to at the default precision
        dd = jnp.where(pos, g * w_h, 0.0).astype(ki.dtype)
        dqi_acc[h] += jax.lax.dot_general(
            dd, ki, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dki_acc[...] += jax.lax.dot_general(
            dd, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, qi_ref.shape[0], head, 0)
    dki_ref[...] = dki_acc[...].astype(dki_ref.dtype)

    @pl.when(j == pl.num_programs(0) - 1)
    def _emit():
        dqi_ref[...] = dqi_acc[...].astype(dqi_ref.dtype)
        dw_ref[...] = dw_acc[...].astype(dw_ref.dtype)


def index_scores_bwd(qi, ki, w, g, block_k=None, interpret=None):
    """The gradients of I = sum_h w[:, h, None] * ReLU(qi[:, h] ki^T) in qi
    [C, HI, DI], ki [S, DI] and w [C, HI] under the cotangent g [C, S]
    (float32): (dqi, dki, dw) in their operands' shapes and types, every
    sum in float32. The dots of a head against a block of `block_k` keys
    live in VMEM only; S need not be a multiple of the block."""
    C, HI, DI = qi.shape
    S = ki.shape[0]
    bk = min(block_k or INDEX_BLOCK, S)
    interpret = _resolve_interpret(interpret)
    vm = {"memory_space": pltpu.VMEM}
    whole = lambda *shape: pl.BlockSpec(shape, lambda j: (0,) * len(shape),
                                        **vm)
    keys = pl.BlockSpec((bk, DI), lambda j: (j, 0), **vm)
    dqi, dki, dw = pl.pallas_call(
        functools.partial(_index_bwd_kernel, S=S, bk=bk),
        grid=(pl.cdiv(S, bk),),
        in_specs=[whole(HI, C, DI), keys, whole(C, HI),
                  pl.BlockSpec((C, bk), lambda j: (0, j), **vm)],
        out_specs=[whole(HI, C, DI), keys, whole(C, HI)],
        out_shape=[jax.ShapeDtypeStruct((HI, C, DI), qi.dtype),
                   jax.ShapeDtypeStruct(ki.shape, ki.dtype),
                   jax.ShapeDtypeStruct(w.shape, w.dtype)],
        scratch_shapes=[pltpu.VMEM((HI, C, DI), jnp.float32),
                        pltpu.VMEM((bk, DI), jnp.float32),
                        pltpu.VMEM((C, HI), jnp.float32)],
        interpret=interpret, name="sparse_attention_index_bwd",
        **compiler_params(interpret, ("arbitrary",), INDEX_VMEM))(
            jnp.moveaxis(qi, 1, 0), ki, w.astype(jnp.float32),
            g.astype(jnp.float32))
    return jnp.moveaxis(dqi, 0, 1), dki, dw
