"""Decoder-only transformer LM — the pipeline-parallel flagship.

The reference's sequence model family tops out at stacked GravesLSTM
(e.g. GravesLSTMCharModellingExample); this model is the TPU-native
modern-equivalent: uniform pre-LN causal-attention blocks whose identical
[B, T, D] interface is exactly what pipeline parallelism
(`parallel/pipeline.py`) and ring attention (`parallel/ring_attention.py`)
need. Pure functional params (nested dicts) so the same block fn serves
single-chip jit, the GPipe schedule, and ring-attention sequence sharding.

Block = pre-LN multi-head causal self-attention + residual, then pre-LN
GeLU MLP + residual — all matmuls MXU-shaped ([B*T, D] x [D, *]).
"""
from __future__ import annotations

import collections
import math

import jax
import jax.numpy as jnp
import numpy as np

# generate_batch compiles one program per (B, P, n_new); bound the cache
# so unbounded shape variety in a serving workload cannot leak compiled
# executables and their device buffers
GEN_JIT_CACHE_SIZE = 8


def _layer_norm(x, g, b, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, -1, keepdims=True)
    return xc * jax.lax.rsqrt(var + eps) * g + b


def init_block(rng, d_model, n_heads, d_ff, dtype=jnp.float32):
    k = jax.random.split(rng, 4)
    s_attn = 1.0 / math.sqrt(d_model)
    s_ff = 1.0 / math.sqrt(d_ff)
    return {
        "ln1": {"g": jnp.ones(d_model, dtype), "b": jnp.zeros(d_model, dtype)},
        "attn": {
            "wqkv": (jax.random.normal(k[0], (d_model, 3 * d_model)) *
                     s_attn).astype(dtype),
            "wo": (jax.random.normal(k[1], (d_model, d_model)) *
                   s_attn).astype(dtype),
        },
        "ln2": {"g": jnp.ones(d_model, dtype), "b": jnp.zeros(d_model, dtype)},
        "mlp": {
            "w1": (jax.random.normal(k[2], (d_model, d_ff)) *
                   s_attn).astype(dtype),
            "b1": jnp.zeros(d_ff, dtype),
            "w2": (jax.random.normal(k[3], (d_ff, d_model)) *
                   s_ff).astype(dtype),
            "b2": jnp.zeros(d_model, dtype),
        },
    }


def causal_attention(x, wqkv, wo, n_heads, return_kv=False):
    """[B, T, D] causal MHA; one fused qkv matmul, one output matmul.
    return_kv=True also yields the [B, T, H, hd] k/v panels — the ONE
    source of the attention math that `generate_batch`'s parallel prefill
    reuses to fill the KV cache (so prefill can never drift from the
    training/forward block numerics)."""
    B, T, D = x.shape
    H = n_heads
    hd = D // H
    qkv = x @ wqkv                                     # [B, T, 3D]
    q, k, v = jnp.split(qkv, 3, axis=-1)
    panels = lambda a: a.reshape(B, T, H, hd)
    heads = lambda a: panels(a).transpose(0, 2, 1, 3)  # [B, H, T, hd]

    qh, kh, vh = heads(q), heads(k), heads(v)
    scores = (qh @ kh.transpose(0, 1, 3, 2)) / math.sqrt(hd)  # [B,H,T,T]
    mask = jnp.tril(jnp.ones((T, T), bool))
    scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
    att = jax.nn.softmax(scores.astype(jnp.float32), -1).astype(x.dtype)
    out = (att @ vh).transpose(0, 2, 1, 3).reshape(B, T, D)
    out = out @ wo
    if return_kv:
        return out, panels(k), panels(v)
    return out


def flash_causal_attention(x, wqkv, wo, n_heads):
    """causal_attention via the Pallas flash kernel (`ops/flash_attention`):
    never materializes the [T, T] scores — the long-context fast path."""
    from ...ops import flash_attention
    B, T, D = x.shape
    H = n_heads
    hd = D // H
    qkv = x @ wqkv
    q, k, v = jnp.split(qkv, 3, axis=-1)
    split = lambda a: a.reshape(B, T, H, hd)      # [B, T, H, hd]
    out = flash_attention(split(q), split(k), split(v), True)
    return out.reshape(B, T, D) @ wo


def make_block_fn(n_heads, attention="dense"):
    """Uniform transformer block closed over the (static) head count: the
    pipeline stage function. attention: "dense" (XLA softmax) or "flash"
    (Pallas kernel)."""
    attn = (flash_causal_attention if attention == "flash"
            else causal_attention)

    def block_fn(p, x):
        h = _layer_norm(x, p["ln1"]["g"], p["ln1"]["b"])
        x = x + attn(h, p["attn"]["wqkv"], p["attn"]["wo"], n_heads)
        h = _layer_norm(x, p["ln2"]["g"], p["ln2"]["b"])
        m = jax.nn.gelu(h @ p["mlp"]["w1"] + p["mlp"]["b1"])
        return x + m @ p["mlp"]["w2"] + p["mlp"]["b2"]

    return block_fn


def init_tp_block(rng, d_model, n_heads, d_ff, dtype=jnp.float32):
    """Block params in the TENSOR-PARALLEL layout: attention projections
    stored per-head ([H, D, 3*hd] / [H, hd, D]) so the head dim shards
    cleanly over a "model" mesh axis (Megatron split), and the MLP hidden
    dim shards on w1 columns / w2 rows. Numerics match `init_block`'s
    layout exactly — only the storage axes differ."""
    k = jax.random.split(rng, 4)
    hd = d_model // n_heads
    s_attn = 1.0 / math.sqrt(d_model)
    s_ff = 1.0 / math.sqrt(d_ff)
    return {
        "ln1": {"g": jnp.ones(d_model, dtype),
                "b": jnp.zeros(d_model, dtype)},
        "attn": {
            "wqkv": (jax.random.normal(k[0], (n_heads, d_model, 3 * hd)) *
                     s_attn).astype(dtype),
            "wo": (jax.random.normal(k[1], (n_heads, hd, d_model)) *
                   s_attn).astype(dtype),
        },
        "ln2": {"g": jnp.ones(d_model, dtype),
                "b": jnp.zeros(d_model, dtype)},
        "mlp": {
            "w1": (jax.random.normal(k[2], (d_model, d_ff)) *
                   s_attn).astype(dtype),
            "b1": jnp.zeros(d_ff, dtype),
            "w2": (jax.random.normal(k[3], (d_ff, d_model)) *
                   s_ff).astype(dtype),
            "b2": jnp.zeros(d_model, dtype),
        },
    }


def tp_block_specs(pipe_axis="pipe", model_axis="model"):
    """PartitionSpec pytree for STACKED `init_tp_block` params (leading
    stage axis over `pipe_axis`): attention head dim and MLP hidden dim
    over `model_axis`, LN/biases replicated across it — the Megatron
    sharding, expressed for `parallel.pipeline.gpipe(param_specs=...)`."""
    from jax.sharding import PartitionSpec as P
    return {
        "ln1": {"g": P(pipe_axis), "b": P(pipe_axis)},
        "attn": {"wqkv": P(pipe_axis, model_axis),
                 "wo": P(pipe_axis, model_axis)},
        "ln2": {"g": P(pipe_axis), "b": P(pipe_axis)},
        "mlp": {"w1": P(pipe_axis, None, model_axis),
                "b1": P(pipe_axis, model_axis),
                "w2": P(pipe_axis, model_axis, None),
                "b2": P(pipe_axis)},
    }


def make_tp_block_fn(n_heads_local, model_axis="model"):
    """Tensor-parallel transformer block for use INSIDE shard_map over a
    mesh with `model_axis`: each device computes its local head group and
    local MLP hidden slice; one psum after the attention output projection
    and one after the MLP down-projection reduce the partial sums — the
    Megatron recipe (two collectives per block), composable with the GPipe
    rotation because both run in the same shard_map body.

    n_heads_local: heads PER DEVICE (global heads / model-axis size);
    asserted against the local param shard so a mismatched mesh split
    fails loudly at trace time instead of silently reading stale docs."""

    def block_fn(p, x):
        B, T, D = x.shape
        assert p["attn"]["wqkv"].shape[0] == n_heads_local, \
            (p["attn"]["wqkv"].shape, n_heads_local)
        hd = p["attn"]["wqkv"].shape[2] // 3
        h = _layer_norm(x, p["ln1"]["g"], p["ln1"]["b"])
        # local heads: [B, T, Hl, 3*hd]
        qkv = jnp.einsum("btd,hdk->bthk", h, p["attn"]["wqkv"])
        q, k, v = jnp.split(qkv, 3, axis=-1)
        tr = lambda a: a.transpose(0, 2, 1, 3)          # [B, Hl, T, hd]
        q, k, v = tr(q), tr(k), tr(v)
        scores = (q @ k.transpose(0, 1, 3, 2)) / math.sqrt(hd)
        mask = jnp.tril(jnp.ones((T, T), bool))
        scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
        att = jax.nn.softmax(scores.astype(jnp.float32), -1).astype(x.dtype)
        out = (att @ v).transpose(0, 2, 1, 3)           # [B, T, Hl, hd]
        o_part = jnp.einsum("bthk,hkd->btd", out, p["attn"]["wo"])
        x = x + jax.lax.psum(o_part, model_axis)
        h = _layer_norm(x, p["ln2"]["g"], p["ln2"]["b"])
        m = jax.nn.gelu(h @ p["mlp"]["w1"] + p["mlp"]["b1"])  # local F/m
        y_part = m @ p["mlp"]["w2"]
        return x + jax.lax.psum(y_part, model_axis) + p["mlp"]["b2"]

    return block_fn


def make_moe_block_fn(n_heads, moe_apply):
    """Transformer block whose MLP is a mixture-of-experts: attention as in
    `make_block_fn`, the FFN replaced by `moe_apply(moe_params, tokens)`
    (dense or expert-parallel — `parallel/moe.py`). Stage params must carry
    a "moe" subtree instead of "mlp". Returns (y, aux_loss) so the trainer
    can add the load-balance term.

    This is the expert layer that DROPS: GeLU experts, top-1/top-2, fixed
    capacity, overflow left to the residual path (`moe_mlp_dense` /
    `moe_mlp_sharded`), used by this module's pipeline/EP trainers. The
    one that does not drop (`parallel/moe.py held_experts_ffn`: gated SiLU
    experts, top-k of all, one chip's share) is the containers' `moe`
    layer kind (`nn/conf/layers/decoder.py`, `models/zoo/keye_vl.py`);
    `parallel/moe.py`'s module docstring says why two remain."""

    def block_fn(p, x):
        B, T, D = x.shape
        h = _layer_norm(x, p["ln1"]["g"], p["ln1"]["b"])
        x = x + causal_attention(h, p["attn"]["wqkv"], p["attn"]["wo"],
                                 n_heads)
        h = _layer_norm(x, p["ln2"]["g"], p["ln2"]["b"])
        y, aux = moe_apply(p["moe"], h.reshape(B * T, D))
        return x + y.reshape(B, T, D), aux

    return block_fn


def init_moe_block(rng, d_model, n_heads, n_experts, d_ff,
                   dtype=jnp.float32):
    """Block params for `make_moe_block_fn`: attention + LNs as
    `init_block`, "mlp" replaced by a "moe" subtree."""
    from ...parallel.moe import init_moe
    p = init_block(rng, d_model, n_heads, d_ff, dtype)
    del p["mlp"]
    p["moe"] = init_moe(jax.random.fold_in(rng, 7), d_model, n_experts,
                        d_ff, dtype)
    return p


def make_decode_block_fn(n_heads):
    """Single-token decode step for one block with a KV cache.

    block_decode(p, x [B, D], cache {k,v: [B, L, H, hd]}, pos scalar)
      -> (y [B, D], updated cache)
    The query attends to cache positions <= pos (the new token's k/v are
    written at `pos` first). Shapes are static, so ONE compiled step
    serves the whole generation loop — the TPU serving pattern (contrast
    the O(T²)-per-token re-encode path)."""

    def block_decode(p, x, cache, pos):
        B, D = x.shape
        H = n_heads
        hd = D // H
        h = _layer_norm(x, p["ln1"]["g"], p["ln1"]["b"])
        qkv = h @ p["attn"]["wqkv"]                     # [B, 3D]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        k_cache = jax.lax.dynamic_update_index_in_dim(
            cache["k"], k.reshape(B, H, hd), pos, axis=1)
        v_cache = jax.lax.dynamic_update_index_in_dim(
            cache["v"], v.reshape(B, H, hd), pos, axis=1)
        qh = q.reshape(B, H, hd)
        scores = jnp.einsum("bhd,blhd->bhl", qh,
                            k_cache) / math.sqrt(hd)    # [B, H, L]
        L = k_cache.shape[1]
        mask = jnp.arange(L)[None, None, :] <= pos
        scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
        att = jax.nn.softmax(scores.astype(jnp.float32),
                             -1).astype(x.dtype)
        out = jnp.einsum("bhl,blhd->bhd", att, v_cache).reshape(B, D)
        x = x + out @ p["attn"]["wo"]
        h = _layer_norm(x, p["ln2"]["g"], p["ln2"]["b"])
        m = jax.nn.gelu(h @ p["mlp"]["w1"] + p["mlp"]["b1"])
        y = x + m @ p["mlp"]["w2"] + p["mlp"]["b2"]
        return y, {"k": k_cache, "v": v_cache}

    return block_decode


def gated_cache_rows(cache, idx, k_new, v_new, gate=None):
    """The ONE clip-gather / drop-scatter KV-cache row update, shared by
    the fixed-slot decode block (1 position/slot), the K-wide verify
    block, and the paged block-table programs — so the subtle part of
    serving cache writes lives in exactly one place.

    cache: {"k": ..., "v": ...}; idx: index tuple for `.at[idx]`
    addressing whole [..., H, hd] rows; k_new/v_new: replacement rows,
    shaped like the indexed selection.

    gate (broadcastable bool) selects per row between the new value and
    the row's CURRENT content: an inactive slot writes back the rows it
    already held, so its cache stays bit-identical while neighbours
    decode. The gather clips an out-of-range row to the last one (value
    unused: its write is dropped); the scatter DROPS out-of-range rows
    outright, so the duplicate-index clobber a clipped write would risk
    cannot happen.

    gate=None means the INDICES already encode gating (callers send
    suppressed rows out of range, where the drop-mode scatter discards
    them). The paged programs need this form: a free slot's stale block
    table may alias a live slot's physical block, and a stale write-back
    would race the live slot's new row inside one scatter — index
    gating writes nothing at all instead."""
    out = {}
    for name, new in (("k", k_new), ("v", v_new)):
        buf = cache[name]
        if gate is not None:
            old = buf.at[idx].get(mode="clip")
            new = jnp.where(gate, new, old)
        out[name] = buf.at[idx].set(new, mode="drop")
    return out


def make_slot_decode_block_fn(n_heads):
    """`make_decode_block_fn` generalized to a FIXED-SLOT serving batch:
    per-slot cache positions and an active mask, the decode unit of the
    continuous-batching scheduler (`serving/decode.py`).

    block_decode(p, x [S, D], cache {k,v: [S, L, H, hd]}, pos [S],
                 active [S] bool) -> (y [S, D], updated cache)

    Every slot's row is computed unconditionally (shapes stay static — ONE
    compiled program no matter which slots are occupied), but the cache
    write is GATED: an inactive slot writes back the rows it already held,
    so its cache stays bit-identical while neighbours decode. Each row
    depends only on its own x/cache/pos rows, which is what makes a
    request's token stream independent of who shares the batch (the
    continuous-decode determinism pin)."""

    def block_decode(p, x, cache, pos, active):
        S, D = x.shape
        H = n_heads
        hd = D // H
        h = _layer_norm(x, p["ln1"]["g"], p["ln1"]["b"])
        qkv = h @ p["attn"]["wqkv"]                     # [S, 3D]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        rows = jnp.arange(S)
        gate = active[:, None, None]
        cache = gated_cache_rows(cache, (rows, pos), k.reshape(S, H, hd),
                                 v.reshape(S, H, hd), gate)
        k_cache, v_cache = cache["k"], cache["v"]
        qh = q.reshape(S, H, hd)
        scores = jnp.einsum("shd,slhd->shl", qh,
                            k_cache) / math.sqrt(hd)    # [S, H, L]
        L = k_cache.shape[1]
        mask = jnp.arange(L)[None, None, :] <= pos[:, None, None]
        scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
        att = jax.nn.softmax(scores.astype(jnp.float32),
                             -1).astype(x.dtype)
        out = jnp.einsum("shl,slhd->shd", att, v_cache).reshape(S, D)
        x = x + out @ p["attn"]["wo"]
        h = _layer_norm(x, p["ln2"]["g"], p["ln2"]["b"])
        m = jax.nn.gelu(h @ p["mlp"]["w1"] + p["mlp"]["b1"])
        y = x + m @ p["mlp"]["w2"] + p["mlp"]["b2"]
        return y, {"k": k_cache, "v": v_cache}

    return block_decode


def make_slot_decode_fn(n_heads):
    """One ITERATION of continuous-batching decode, the whole model:

    step(aux, blocks, cache, pos [S], tok [S], active [S])
      -> (next_tok [S] i32, logits [S, V] f32, new cache, new pos)

    Greedy on-device argmax (f32 logits — tie-break parity with
    `generate_batch`); inactive slots compute but change nothing (gated
    cache writes, pos advances by `active`). The scheduler jits this ONCE
    per slot count and calls it every token iteration, swapping requests
    in and out of slots between calls — Orca-style iteration-level
    scheduling."""
    block_decode = make_slot_decode_block_fn(n_heads)

    def step(aux, blocks, cache, pos, tok, active):
        x = aux["tok"][tok] + aux["pos"][pos]           # [S, D]
        new_cache = []
        for p, c in zip(blocks, cache):
            x, c = block_decode(p, x, c, pos, active)
            new_cache.append(c)
        logits = logits_fn(aux, x).astype(jnp.float32)  # [S, V]
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        new_pos = pos + active.astype(pos.dtype)
        return nxt, logits, new_cache, new_pos

    return step


def make_slot_verify_block_fn(n_heads):
    """`make_slot_decode_block_fn` widened to K query positions per slot:
    the per-block unit of SPECULATIVE decoding's verify dispatch
    (`serving/speculate.py`).

    block_verify(p, x [S, K, D], cache {k,v: [S, L, H, hd]}, pos [S],
                 active [S] bool) -> (y [S, K, D], updated cache)

    Slot s's K inputs land at cache rows pos[s]..pos[s]+K-1 (all K k/v
    rows are written BEFORE attention, exactly as `prefill_forward` fills
    its window), and query i attends causally to rows <= pos[s]+i. The
    same two gates as the 1-token block keep the serving pins intact:
    inactive slots write back the rows they already held (bit-identical
    cache while neighbours decode), and rows beyond the cache length are
    dropped (`mode="drop"` scatter) — a verify dispatch near the end of
    the cache writes only the rows that exist, and the host never
    consumes tokens whose row would not fit (the submit() length guard).
    Masked-out score positions contribute EXACT zeros after softmax
    (exp underflows to 0.0), so widening the attended row set from the
    decode block's to the verify block's changes no accepted row's bits."""

    def block_verify(p, x, cache, pos, active):
        S, K, D = x.shape
        H = n_heads
        hd = D // H
        h = _layer_norm(x, p["ln1"]["g"], p["ln1"]["b"])
        qkv = h @ p["attn"]["wqkv"]                     # [S, K, 3D]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        L = cache["k"].shape[1]
        rows = jnp.arange(S)[:, None]                   # [S, 1]
        pcols = pos[:, None] + jnp.arange(K)[None, :]   # [S, K]
        gate = active[:, None, None, None]
        cache = gated_cache_rows(cache, (rows, pcols),
                                 k.reshape(S, K, H, hd),
                                 v.reshape(S, K, H, hd), gate)
        k_cache, v_cache = cache["k"], cache["v"]
        qh = q.reshape(S, K, H, hd)
        scores = jnp.einsum("skhd,slhd->shkl", qh,
                            k_cache) / math.sqrt(hd)    # [S, H, K, L]
        mask = (jnp.arange(L)[None, None, None, :]
                <= pcols[:, None, :, None])             # [S, 1, K, L]
        scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
        att = jax.nn.softmax(scores.astype(jnp.float32),
                             -1).astype(x.dtype)
        out = jnp.einsum("shkl,slhd->skhd", att, v_cache).reshape(S, K, D)
        x = x + out @ p["attn"]["wo"]
        h = _layer_norm(x, p["ln2"]["g"], p["ln2"]["b"])
        m = jax.nn.gelu(h @ p["mlp"]["w1"] + p["mlp"]["b1"])
        y = x + m @ p["mlp"]["w2"] + p["mlp"]["b2"]
        return y, {"k": k_cache, "v": v_cache}

    return block_verify


def make_slot_verify_fn(n_heads, k):
    """One SPECULATIVE iteration of continuous-batching decode — up to K
    tokens per device dispatch, the whole model:

    verify(aux, blocks, cache, pos [S], toks [S, K], active [S])
      -> (nxt [S, K] i32, n_acc [S] i32, logits [S, K, V] f32,
          new cache, new pos)

    toks[s, 0] is slot s's LAST ACCEPTED token and toks[s, 1:] are K-1
    draft tokens (any values — a garbage draft costs acceptance, never
    correctness). The K-wide causal forward writes their k/v at rows
    pos[s]..pos[s]+K-1 and emits greedy argmax at every position;
    nxt[s, i] is what plain greedy decode WOULD emit after stream prefix
    ..toks[s, :i+1], so acceptance-by-exact-match is computed on device:
    n_acc[s] = length of the longest prefix with nxt[s, i] == toks[s, i+1].
    The scheduler consumes nxt[s, :n_acc[s]+1] — the matched drafts plus
    one bonus token (the model's own choice at the first divergence) —
    so BY CONSTRUCTION the emitted stream is this program's own greedy
    argmax chain: a draft can only change the dispatch count, never the
    tokens. Identity with the 1-wide decode program's stream additionally
    rests on argmax parity across dispatch widths — the same measured
    cross-shape property the prefill/decode pin already relies on (gemm
    rows bit-stable across M on the tested backends; near-tie logits are
    the theoretical exposure) — and is pinned by test across K, draft
    sources, and batch compositions. pos advances by n_acc+1 per slot;
    rejected-suffix rows are dead cache rows the pointer never passed,
    overwritten by the next dispatch's writes before any query can attend
    to them (the bucket-prefill argument). k=1 degenerates to exactly one
    token per dispatch (no drafts, bonus only) — plain decode through the
    verify program."""
    block_verify = make_slot_verify_block_fn(n_heads)
    k = int(k)
    if k < 1:
        raise ValueError(f"speculative width k must be >= 1, got {k}")

    def verify(aux, blocks, cache, pos, toks, active):
        max_len = aux["pos"].shape[0]
        pcols = jnp.clip(pos[:, None] + jnp.arange(k)[None, :],
                         0, max_len - 1)
        x = aux["tok"][toks] + aux["pos"][pcols]        # [S, K, D]
        new_cache = []
        for p, c in zip(blocks, cache):
            x, c = block_verify(p, x, c, pos, active)
            new_cache.append(c)
        logits = logits_fn(aux, x).astype(jnp.float32)  # [S, K, V]
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)  # [S, K]
        match = (nxt[:, :k - 1] == toks[:, 1:]).astype(jnp.int32)
        n_acc = jnp.sum(jnp.cumprod(match, axis=1), axis=1)  # [S], 0..K-1
        new_pos = pos + jnp.where(active, n_acc + 1, 0).astype(pos.dtype)
        return nxt, n_acc.astype(jnp.int32), logits, new_cache, new_pos

    return verify


def prefill_panels(aux, blocks, tokens, n_heads):
    """The ONE causal prompt forward: embed `tokens` [B, P], run every
    block through the SHARED attention core
    (`causal_attention(return_kv=True)`), and return
    (h [B, P, D], [(kp, vp)] per layer, each [B, P, H, hd]).

    Both cache layouts install from these panels — `prefill_forward`
    scatters them into fixed-slot cache rows, `make_paged_prefill_fn`
    into block-table rows — so neither layout can drift from the
    training/forward block numerics."""
    h = embed_fn(aux, tokens)
    panels = []
    for p in blocks:
        hn = _layer_norm(h, p["ln1"]["g"], p["ln1"]["b"])
        att, kp, vp = causal_attention(
            hn, p["attn"]["wqkv"], p["attn"]["wo"], n_heads,
            return_kv=True)
        h = h + att
        hn = _layer_norm(h, p["ln2"]["g"], p["ln2"]["b"])
        m = jax.nn.gelu(hn @ p["mlp"]["w1"] + p["mlp"]["b1"])
        h = h + m @ p["mlp"]["w2"] + p["mlp"]["b2"]
        panels.append((kp, vp))
    return h, panels


def prefill_forward(aux, blocks, tokens, n_heads, cache_len):
    """One causal forward over `tokens` [B, P] filling rows [0, P) of a
    length-`cache_len` fixed-layout KV cache per layer. Returns
    (h [B, P, D], cache). `generate_batch` and the serving prefill
    programs both call it (via the shared `prefill_panels` core), so
    serving can never drift from the pinned generation numerics."""
    B, P = tokens.shape
    h, panels = prefill_panels(aux, blocks, tokens, n_heads)
    cache = []
    for kp, vp in panels:
        z = jnp.zeros((B, cache_len, n_heads, kp.shape[-1]), kp.dtype)
        cache.append({"k": z.at[:, :P].set(kp),
                      "v": z.at[:, :P].set(vp)})
    return h, cache


def make_prefill_fn(n_heads, cache_len):
    """Serving prefill program for ONE request, prompt right-padded to a
    length bucket:

    prefill(aux, blocks, prompt [1, Pb], length scalar)
      -> (logits [1, V] f32 at the last REAL token, cache rows)

    Causal masking makes positions < length independent of the padding
    tail; the tail's garbage k/v rows are installed too but are
    OVERWRITTEN by decode steps before any query can attend to them
    (decode writes position `pos` before attending through it), so
    bucket-padded prefill is exact, not approximate."""

    def prefill(aux, blocks, prompt, length):
        h, cache = prefill_forward(aux, blocks, prompt, n_heads, cache_len)
        logits = logits_fn(aux, h[:, length - 1]).astype(jnp.float32)
        return logits, cache

    return prefill


def init_kv_cache(n_layers, batch, max_len, d_model, n_heads,
                  dtype=jnp.float32):
    hd = d_model // n_heads
    z = lambda: jnp.zeros((batch, max_len, n_heads, hd), dtype)
    return [{"k": z(), "v": z()} for _ in range(n_layers)]


def init_paged_kv_cache(n_layers, n_blocks, block_size, d_model, n_heads,
                        dtype=jnp.float32):
    """PAGED KV arena: per layer {k, v: [n_blocks * block_size, H, hd]},
    flat row-major so physical row = block_id * block_size + offset.
    One preallocated arena shared by EVERY stream — which streams own
    which blocks is host state (`serving.kvpool.BlockPool` + per-slot
    block tables), not device state."""
    hd = d_model // n_heads
    rows = int(n_blocks) * int(block_size)
    z = lambda: jnp.zeros((rows, n_heads, hd), dtype)
    return [{"k": z(), "v": z()} for _ in range(n_layers)]


def make_paged_decode_block_fn(n_heads, block_size):
    """`make_slot_decode_block_fn` with the cache indirected through a
    BLOCK TABLE (vLLM PagedAttention, Kwon et al. SOSP'23): the per-slot
    unit of paged continuous-batching decode.

    block_decode(p, x [S, D], cache {k,v: [n_rows, H, hd]}, btab [S, NB],
                 pos [S], active [S] bool) -> (y [S, D], updated cache)

    `cache` is the SHARED flat arena; `btab[s, b]` maps slot s's logical
    block b to a physical block, so logical row l lives at physical row
    `btab[s, l // bs] * bs + l % bs`. The write lands at slot s's
    frontier row; gating is by INDEX, not write-back (`gated_cache_rows`
    gate=None): a free slot's stale table may alias a live slot's
    physical block, and a stale write-back would race the live slot's
    new row inside one scatter — inactive rows go out of range and the
    drop-mode scatter discards them. Attention then GATHERS the slot's
    whole logical window [S, NB*bs, H, hd] from the arena and runs the
    identical einsum/softmax as the fixed-slot block: per-logical-row
    values equal means per-slot bits equal, because masked positions
    contribute EXACT zeros after softmax (exp underflow) and appending
    exact zeros never changes a float sum — the window length (NB*bs vs
    max_len) is therefore free to differ between layouts. Shared prefix
    blocks are read-only by invariant (the pool copy-on-writes before
    any divergent append), so two slots gathering one physical block is
    just a shared read."""
    bs = int(block_size)

    def block_decode(p, x, cache, btab, pos, active):
        S, D = x.shape
        H = n_heads
        hd = D // H
        NB = btab.shape[1]
        L = NB * bs
        n_rows = cache["k"].shape[0]
        h = _layer_norm(x, p["ln1"]["g"], p["ln1"]["b"])
        qkv = h @ p["attn"]["wqkv"]                     # [S, 3D]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        blk = btab[jnp.arange(S), pos // bs]            # [S] physical blk
        pr = blk * bs + pos % bs                        # frontier row
        widx = jnp.where(active, pr, n_rows)            # inactive: drop
        cache = gated_cache_rows(cache, (widx,), k.reshape(S, H, hd),
                                 v.reshape(S, H, hd))
        # gather each slot's logical window from the arena
        flat = (btab[:, :, None] * bs +
                jnp.arange(bs)[None, None, :]).reshape(S, L)
        k_rows = jnp.take(cache["k"], flat, axis=0)     # [S, L, H, hd]
        v_rows = jnp.take(cache["v"], flat, axis=0)
        qh = q.reshape(S, H, hd)
        scores = jnp.einsum("shd,slhd->shl", qh,
                            k_rows) / math.sqrt(hd)     # [S, H, L]
        mask = jnp.arange(L)[None, None, :] <= pos[:, None, None]
        scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
        att = jax.nn.softmax(scores.astype(jnp.float32),
                             -1).astype(x.dtype)
        out = jnp.einsum("shl,slhd->shd", att, v_rows).reshape(S, D)
        x = x + out @ p["attn"]["wo"]
        h = _layer_norm(x, p["ln2"]["g"], p["ln2"]["b"])
        m = jax.nn.gelu(h @ p["mlp"]["w1"] + p["mlp"]["b1"])
        y = x + m @ p["mlp"]["w2"] + p["mlp"]["b2"]
        return y, cache

    return block_decode


def make_paged_decode_fn(n_heads, block_size):
    """One ITERATION of continuous-batching decode over the PAGED cache,
    the whole model:

    step(aux, blocks, cache, btabs [S, NB], pos [S], tok [S], active [S])
      -> (next_tok [S] i32, logits [S, V] f32, new cache, new pos)

    Same contract as `make_slot_decode_fn` (greedy f32 argmax, gated
    writes, pos advances by `active`, ONE compiled program per slot
    count) with the cache swapped for arena + block tables: slot count S
    is a pure SCHEDULING width — memory is the arena, and admission is
    gated by free blocks (`serving.kvpool.BlockPool`), not free slots.
    The block table rides in as a [S, NB] i32 argument each dispatch
    (host state, like `tok`/`active`) — no extra device dispatch."""
    block_decode = make_paged_decode_block_fn(n_heads, block_size)

    def step(aux, blocks, cache, btabs, pos, tok, active):
        x = aux["tok"][tok] + aux["pos"][pos]           # [S, D]
        new_cache = []
        for p, c in zip(blocks, cache):
            x, c = block_decode(p, x, c, btabs, pos, active)
            new_cache.append(c)
        logits = logits_fn(aux, x).astype(jnp.float32)  # [S, V]
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        new_pos = pos + active.astype(pos.dtype)
        return nxt, logits, new_cache, new_pos

    return step


def make_fused_decode_fn(n_heads, k):
    """K iterations of continuous-batching decode scanned into ONE device
    dispatch — `nn/fused.py`'s fused_steps applied to serving. The scan
    body IS `make_slot_decode_fn`'s step (same block program, same embed,
    same f32 argmax), so each unrolled iteration computes bit-identical
    values to one host-scheduled dispatch; the only new machinery is the
    per-slot step BUDGET.

    window(aux, blocks, cache, pos [S], tok [S], active [S], steps [S])
      -> (toks [K, S] i32, new cache, new pos)

    Slot membership is STATIC inside the window (the scheduler admits,
    evicts, and sweeps deadlines only at window boundaries), but slots
    finish at different times, so step i gates each slot on
    `active & (i < steps)`: once a slot's budget is spent it behaves
    exactly like an inactive slot — frozen tok/pos, write-back-gated
    cache rows — which is the SAME device state a host scheduler leaves
    when it frees the slot between iterations and keeps dispatching its
    neighbours (stale host-side tok/pos, gated writes). Per-row
    independence (the continuous-decode determinism pin) then makes
    every live slot's bits equal to the host-scheduled stream's.
    toks[i, s] is garbage for i >= steps[s]; the host consumes
    toks[:steps[s], s] only. K is static (ONE compiled program per
    (slot count, K)); k < 2 is refused because a 1-step window is the
    plain program with scan overhead — use `make_slot_decode_fn`."""
    block_decode = make_slot_decode_block_fn(n_heads)
    k = int(k)
    if k < 2:
        raise ValueError(f"fused window k must be >= 2 (k=1 is the "
                         f"plain decode program), got {k}")

    def window(aux, blocks, cache, pos, tok, active, steps):
        def body(carry, i):
            cache, pos, tok = carry
            act = active & (i < steps)
            x = aux["tok"][tok] + aux["pos"][pos]       # [S, D]
            new_cache = []
            for p, c in zip(blocks, cache):
                x, c = block_decode(p, x, c, pos, act)
                new_cache.append(c)
            logits = logits_fn(aux, x).astype(jnp.float32)
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)
            tok = jnp.where(act, nxt, tok)
            pos = pos + act.astype(pos.dtype)
            return (new_cache, pos, tok), nxt

        (cache, pos, tok), toks = jax.lax.scan(
            body, (cache, pos, tok), jnp.arange(k))
        return toks, cache, pos

    return window


def make_paged_fused_decode_fn(n_heads, block_size, k):
    """`make_fused_decode_fn` re-addressed through the block table: K
    paged decode iterations in one dispatch. The scan body is
    `make_paged_decode_fn`'s step (same block program), and the block
    table stays STATIC across the window — only `pos` rides the carry,
    and the frontier row `btab[s, pos // bs] * bs + pos % bs` is
    recomputed from it each step, so the write pointer advances through
    the table without any host round-trip.

    window(aux, blocks, cache, btabs [S, NB], pos [S], tok [S],
           active [S], steps [S], wto [S])
      -> (toks [K, S] i32, new cache, new pos)

    Step gating adds `pos < wto` (the slot's reserved row capacity,
    `BlockPool.writable_rows`) to the fixed window's budget gate: a
    window is CLAMPED by the scheduler so it never crosses an
    unreserved block, and the in-program gate makes an overshoot write
    impossible anyway — past wto the frontier would resolve through a
    zeroed table entry into block 0 and corrupt whichever stream owns
    it (the same hazard the K-wide verify window gates against). A
    CoW-shared partial block must be materialized BEFORE the window's
    dispatch, exactly as before a 1-wide append — the first scanned
    step writes at the frontier, inside that block. Budget-spent and
    capacity-capped slots freeze like inactive ones (index-gated
    writes, frozen tok/pos), preserving the host-scheduled bits for
    every neighbour."""
    block_decode = make_paged_decode_block_fn(n_heads, block_size)
    k = int(k)
    if k < 2:
        raise ValueError(f"fused window k must be >= 2 (k=1 is the "
                         f"plain decode program), got {k}")

    def window(aux, blocks, cache, btabs, pos, tok, active, steps, wto):
        def body(carry, i):
            cache, pos, tok = carry
            act = active & (i < steps) & (pos < wto)
            x = aux["tok"][tok] + aux["pos"][pos]       # [S, D]
            new_cache = []
            for p, c in zip(blocks, cache):
                x, c = block_decode(p, x, c, btabs, pos, act)
                new_cache.append(c)
            logits = logits_fn(aux, x).astype(jnp.float32)
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)
            tok = jnp.where(act, nxt, tok)
            pos = pos + act.astype(pos.dtype)
            return (new_cache, pos, tok), nxt

        (cache, pos, tok), toks = jax.lax.scan(
            body, (cache, pos, tok), jnp.arange(k))
        return toks, cache, pos

    return window


def make_paged_prefill_fn(n_heads):
    """Serving prefill for ONE request over the PAGED cache — the pure
    COMPUTE half: the forward runs over the whole padded prompt through
    the ONE `prefill_panels` implementation and returns the k/v panels;
    `make_paged_install_fn` scatters them into the arena in a separate
    DONATED program. The split matters: a fused prefill+install would
    have to take the arena UNDONATED (an admission-time failure must
    fail only that request, so the arena has to survive a failed call),
    and an undonated arena output copies every untouched row — the
    whole pool's bytes — on every admission.

    prefill(aux, blocks, prompt [1, Pb], length)
      -> (logits [1, V] f32 at the last REAL token,
          panels [(kp, vp)] per layer, each [1, Pb, H, hd])

    The bucket floor of 2 applies to paged prompt buckets exactly as to
    fixed ones: Pb=1 would take XLA:CPU's differently-accumulating gemv
    path."""

    def prefill(aux, blocks, prompt, length):
        h, panels = prefill_panels(aux, blocks, prompt, n_heads)
        logits = logits_fn(aux, h[:, length - 1]).astype(jnp.float32)
        return logits, panels

    return prefill


def make_paged_install_fn(block_size):
    """Install half of the paged prefill: scatter the prompt's k/v
    panels to their block-table rows. The caller jits this with the
    arena DONATED (aliased in place, exactly like the fixed path's
    install scatter) and runs it only AFTER the prefill dispatch
    succeeded, preserving per-request failure isolation.

    install(cache, panels, btab [NB], length, shared_len) -> new cache

    Three row classes never install: the bucket-padding tail (rows >=
    `length` — overwritten-before-attended, the standard bucket
    argument), rows < `shared_len` (the PREFIX-CACHE hit: physically
    resident blocks another stream already filled, possibly refcount
    > 1 — recomputed k/v for those rows equal the resident bits because
    per-row bits are independent of batch shape, the measured property
    every padding pin rests on, so skipping their install changes only
    the write set), and nothing else — all suppressed by index (sent
    out of range, drop-mode scatter)."""
    bs = int(block_size)

    def install(cache, panels, btab, length, shared_len):
        P = panels[0][0].shape[1]
        r = jnp.arange(P)
        pr = btab[r // bs] * bs + r % bs                # [P] physical
        n_rows = cache[0]["k"].shape[0]
        write = (r >= shared_len) & (r < length)
        widx = jnp.where(write, pr, n_rows)             # suppressed: drop
        return [gated_cache_rows(c, (widx,), kp[0], vp[0])
                for c, (kp, vp) in zip(cache, panels)]

    return install


def make_block_extract_fn(block_size):
    """Extract half of durable KV state (serving/kvstate.py): gather a
    block-table's rows OUT of the arena into a host-bound panel — the
    exact inverse of `make_paged_install_fn`'s scatter. One pure READ
    program (the arena is not donated and not returned: a failed
    extract trivially leaves it valid, mirroring the pure-prefill
    failure-isolation argument), jitted once per table width because
    the caller always passes the server's full `[NB]` table, zero-padded
    past the allocation like every paged dispatch.

    extract(cache, btab [NB]) -> panels [(k, v)] per layer,
                                 each [NB * bs, H, hd]

    Row r of a panel is LOGICAL row r of the table's request (physical
    `btab[r // bs] * bs + r % bs`). The host slices `[:pos]` — rows at
    or past the request's frontier are dead rows (never passed by the
    pointer: rejected speculative suffixes, chunk padding) or rows
    resolved through zeroed table entries into block 0; both are
    garbage by contract and must not enter a durable artifact. Shared
    leading blocks (refcount > 1) and a still-pending CoW partial block
    are READ here, never written — a gather cannot violate the CoW
    rule, so extraction needs no materialization (the restore side
    re-acquires shared rows via the prefix index instead of duplicating
    them, or re-installs them into private blocks)."""
    bs = int(block_size)

    def extract(cache, btab):
        flat = (btab[:, None] * bs +
                jnp.arange(bs)[None, :]).reshape(-1)    # [NB*bs]
        return [(c["k"][flat], c["v"][flat]) for c in cache]

    return extract


def make_paged_verify_block_fn(n_heads, block_size):
    """`make_paged_decode_block_fn` widened to K query positions per
    slot: the per-block unit of the K-wide programs over the PAGED
    cache — speculative decoding's VERIFY dispatch and chunked
    prefill's chunk dispatch share it, exactly as the fixed layout's
    `make_slot_verify_block_fn` is shared by its verify and chunk
    programs (one K-wide program per layout, so the two roles can
    never drift).

    block_verify(p, x [S, K, D], cache {k,v: [n_rows, H, hd]},
                 btab [S, NB], pos [S], active [S] bool,
                 wfrom [S], wto [S]) -> (y [S, K, D], updated cache)

    Slot s's K inputs sit at LOGICAL rows pos[s]..pos[s]+K-1; their k/v
    land at the table-mapped physical rows, all written BEFORE attention
    (exactly as the fixed verify block fills its window), and query i
    attends causally to logical rows <= pos[s]+i through the
    block-table gather. Gating is by INDEX like every paged write
    (gated_cache_rows gate=None): a row writes only when its slot is
    active AND its logical position falls in [wfrom[s], wto[s]) — the
    write window. The window is what makes the K-wide shape SAFE over
    a block table: rows below wfrom are a prefix-cache hit (physically
    resident, possibly refcount > 1 — recomputed bits equal the
    resident bits, the measured per-row batch-shape independence, so
    they are computed for attention but never written; the verify
    caller passes wfrom = pos, every verify row being a new write),
    and rows at or past wto — chunk bucket padding, or a speculative
    round's overhang near the end of a request's reservation — have a
    logical position that may exceed the request's RESERVED block
    table: an ungated write there would resolve through a zeroed table
    entry to physical block 0 and corrupt whichever stream owns it.
    Suppressed rows go out of range; the drop-mode scatter discards
    them."""
    bs = int(block_size)

    def block_verify(p, x, cache, btab, pos, active, wfrom, wto):
        S, K, D = x.shape
        H = n_heads
        hd = D // H
        NB = btab.shape[1]
        L = NB * bs
        n_rows = cache["k"].shape[0]
        h = _layer_norm(x, p["ln1"]["g"], p["ln1"]["b"])
        qkv = h @ p["attn"]["wqkv"]                     # [S, K, 3D]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        lrows = pos[:, None] + jnp.arange(K)[None, :]   # [S, K] logical
        blk = btab[jnp.arange(S)[:, None],
                   jnp.clip(lrows // bs, 0, NB - 1)]
        pr = blk * bs + lrows % bs                      # physical rows
        ok = (active[:, None] & (lrows >= wfrom[:, None])
              & (lrows < wto[:, None]) & (lrows < L))
        widx = jnp.where(ok, pr, n_rows)                # suppressed: drop
        cache = gated_cache_rows(cache, (widx,),
                                 k.reshape(S, K, H, hd),
                                 v.reshape(S, K, H, hd))
        # gather each slot's logical window from the arena (identical to
        # the 1-wide paged decode block; rows past the reserved table
        # resolve to block 0 but are masked to exact softmax zeros)
        flat = (btab[:, :, None] * bs +
                jnp.arange(bs)[None, None, :]).reshape(S, L)
        k_rows = jnp.take(cache["k"], flat, axis=0)     # [S, L, H, hd]
        v_rows = jnp.take(cache["v"], flat, axis=0)
        qh = q.reshape(S, K, H, hd)
        scores = jnp.einsum("skhd,slhd->shkl", qh,
                            k_rows) / math.sqrt(hd)     # [S, H, K, L]
        mask = (jnp.arange(L)[None, None, None, :]
                <= lrows[:, None, :, None])             # [S, 1, K, L]
        scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
        att = jax.nn.softmax(scores.astype(jnp.float32),
                             -1).astype(x.dtype)
        out = jnp.einsum("shkl,slhd->skhd", att, v_rows).reshape(S, K, D)
        x = x + out @ p["attn"]["wo"]
        h = _layer_norm(x, p["ln2"]["g"], p["ln2"]["b"])
        m = jax.nn.gelu(h @ p["mlp"]["w1"] + p["mlp"]["b1"])
        y = x + m @ p["mlp"]["w2"] + p["mlp"]["b2"]
        return y, cache

    return block_verify


def make_paged_chunk_block_fn(n_heads, block_size):
    """Chunked prefill's per-block unit over the paged cache: the ONE
    K-wide paged block program (`make_paged_verify_block_fn`) under its
    chunk-role name — kept so the two roles are named at their call
    sites while the program itself cannot drift."""
    return make_paged_verify_block_fn(n_heads, block_size)


def make_paged_verify_fn(n_heads, k, block_size):
    """`make_slot_verify_fn` re-addressed through the block table: one
    SPECULATIVE iteration of paged continuous-batching decode — up to K
    tokens per device dispatch, the whole model:

    verify(aux, blocks, cache, btabs [S, NB], pos [S], toks [S, K],
           active [S], wto [S])
      -> (nxt [S, K] i32, n_acc [S] i32, logits [S, K, V] f32,
          new cache, new pos)

    Identical contract to the fixed-layout verify — toks[s, 0] is the
    last accepted token, toks[s, 1:] are K-1 drafts, all K k/v rows are
    written before attention, acceptance is the on-device
    longest-prefix argmax match, pos advances n_acc+1 — with the cache
    swapped for arena + block tables. Writes land at the table-mapped
    frontier rows pos[s]..pos[s]+K-1 under the SAME [wfrom, wto)
    index gating the paged chunk program uses (wfrom = pos: every
    verify row is a new write; wto = the slot's reserved row capacity,
    `BlockPool.writable_rows` — an ungated overhang write near the end
    of a reservation would resolve through btab entry 0 into another
    stream's block); attention gathers the slot's whole logical window
    through the table and runs the identical einsum/softmax, so
    per-logical-row bits equal the fixed verify's (masked rows are
    exact softmax zeros — the window length is free to differ).
    Rejected-suffix rows are dead rows inside blocks the request
    already owns: the pointer never passed them and the next round's
    K-wide write covers them before any query attends (the fixed
    verify's bucket-prefill argument, unchanged by paging). A round
    that crosses a block boundary writes into blocks the reservation
    already holds — `admit()` reserved every row the request will ever
    write, so speculation adds NO allocation path — and a CoW-shared
    partial block must be materialized by the scheduler BEFORE the
    first verify dispatch, exactly as before the first 1-wide append
    (the K-wide write starts at the frontier, inside that block).
    Consumed tokens need their query's whole row range written:
    positions past the reservation emit garbage logits, but the host's
    `take = min(n_acc+1, remaining budget)` cap — the same cap the
    fixed path applies — stops consumption at rows the reservation
    covers, so gating changes no consumed token's bits."""
    block_verify = make_paged_verify_block_fn(n_heads, block_size)
    k = int(k)
    if k < 1:
        raise ValueError(f"speculative width k must be >= 1, got {k}")

    def verify(aux, blocks, cache, btabs, pos, toks, active, wto):
        max_len = aux["pos"].shape[0]
        pcols = jnp.clip(pos[:, None] + jnp.arange(k)[None, :],
                         0, max_len - 1)
        x = aux["tok"][toks] + aux["pos"][pcols]        # [S, K, D]
        new_cache = []
        for p, c in zip(blocks, cache):
            x, c = block_verify(p, x, c, btabs, pos, active, pos, wto)
            new_cache.append(c)
        logits = logits_fn(aux, x).astype(jnp.float32)  # [S, K, V]
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)  # [S, K]
        match = (nxt[:, :k - 1] == toks[:, 1:]).astype(jnp.int32)
        n_acc = jnp.sum(jnp.cumprod(match, axis=1), axis=1)  # 0..K-1
        new_pos = pos + jnp.where(active, n_acc + 1, 0).astype(pos.dtype)
        return nxt, n_acc.astype(jnp.int32), logits, new_cache, new_pos

    return verify


def make_chunked_prefill_fn(n_heads, chunk, block_size=None):
    """CHUNKED prefill: one decode-iteration-sized slice of a prompt per
    dispatch, attending into the rows earlier chunks already installed —
    the head-of-line surgery program (a long joiner stops stalling every
    co-resident stream for its whole prompt; it stalls them one chunk at
    a time instead, and the scheduler interleaves decode iterations
    between chunks).

    block_size=None builds the FIXED-SLOT layout:

      step(aux, blocks, cache, pos [S], toks [S, C], nrows [S],
           active [S]) -> (nxt [S, C] i32, new cache, new pos)

    an int builds the PAGED block-table layout:

      step(aux, blocks, cache, btabs [S, NB], pos [S], toks [S, C],
           nrows [S], active [S], wfrom [S], wto [S])
        -> (nxt [S, C] i32, new cache, new pos)

    Both are the VERIFY program's shape with prefill semantics: the C
    chunk tokens' k/v are written at rows pos..pos+C-1 before attention
    (fixed: the verify block itself, so chunked prefill can never drift
    from the pinned K-wide program; paged: `make_paged_chunk_block_fn`,
    its block-table twin), every position emits a greedy f32 argmax, and
    pos advances by nrows (the REAL rows this chunk carried — the final
    chunk is bucket-padded up to C). The host consumes nxt[s, nrows-1]
    of the LAST chunk only: that argmax IS the request's first generated
    token, exactly as the one-shot prefill's last-real-row argmax is.

    Bit-identity with one-shot prefill rests on the two measured
    properties every serving pin already uses: per-row gemm bits are
    independent of batch shape (a chunk's rows see the same qkv bits the
    full-prompt forward computes — hence the chunk floor of 2: C=1 would
    take XLA:CPU's differently-accumulating gemv path), and masked
    positions contribute EXACT softmax zeros, so attending through the
    cache window instead of the in-flight forward changes no row's sum.
    Chunk padding rows (the last chunk past nrows) write dead rows the
    decode pointer overwrites before attending — the verify program's
    rejected-suffix argument; in the paged layout they are additionally
    index-gated off by the [wfrom, wto) write window (see
    `make_paged_chunk_block_fn` — an ungated padding write could alias
    another stream's block 0). wfrom > pos composes chunked prefill with
    PREFIX REUSE: resident shared rows are attended, recomputed only in
    the final chunk's window when needed for logits, and never
    re-written — the partial-prefill compute reuse the paged subsystem
    left open."""
    C = int(chunk)
    if C < 2:
        # same floor as the padding buckets: a 1-row chunk is a gemv
        # with a different accumulation order, silently breaking the
        # chunked == one-shot bit-identity pin
        raise ValueError(f"chunk size must be >= 2 (the XLA:CPU gemv "
                         f"floor), got {chunk}")
    if block_size is None:
        block_verify = make_slot_verify_block_fn(n_heads)

        def step(aux, blocks, cache, pos, toks, nrows, active):
            max_len = aux["pos"].shape[0]
            pcols = jnp.clip(pos[:, None] + jnp.arange(C)[None, :],
                             0, max_len - 1)
            x = aux["tok"][toks] + aux["pos"][pcols]    # [S, C, D]
            new_cache = []
            for p, c in zip(blocks, cache):
                x, c = block_verify(p, x, c, pos, active)
                new_cache.append(c)
            logits = logits_fn(aux, x).astype(jnp.float32)
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)   # [S, C]
            new_pos = pos + jnp.where(active, nrows, 0).astype(pos.dtype)
            return nxt, new_cache, new_pos

        return step

    block_chunk = make_paged_chunk_block_fn(n_heads, block_size)

    def step(aux, blocks, cache, btabs, pos, toks, nrows, active,
             wfrom, wto):
        max_len = aux["pos"].shape[0]
        pcols = jnp.clip(pos[:, None] + jnp.arange(C)[None, :],
                         0, max_len - 1)
        x = aux["tok"][toks] + aux["pos"][pcols]        # [S, C, D]
        new_cache = []
        for p, c in zip(blocks, cache):
            x, c = block_chunk(p, x, c, btabs, pos, active, wfrom, wto)
            new_cache.append(c)
        logits = logits_fn(aux, x).astype(jnp.float32)
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)       # [S, C]
        new_pos = pos + jnp.where(active, nrows, 0).astype(pos.dtype)
        return nxt, new_cache, new_pos

    return step


def make_block_copy_fn(block_size):
    """Copy-on-write worker: copy one physical block's rows (all layers)
    to another — the device half of the pool's lazy CoW (a stream about
    to append into a SHARED partial block gets a private copy first).
    One compiled program serves every (src, dst) pair; rows past the
    shared content it copies are dead rows the new owner overwrites
    before any query attends to them (the bucket-prefill argument)."""
    bs = int(block_size)

    def copy(cache, src, dst):
        s_rows = src * bs + jnp.arange(bs)
        d_rows = dst * bs + jnp.arange(bs)
        return [{"k": c["k"].at[d_rows].set(c["k"][s_rows]),
                 "v": c["v"].at[d_rows].set(c["v"][s_rows])}
                for c in cache]

    return copy


def init_lm(vocab_size, d_model=128, n_heads=4, n_layers=4, d_ff=None,
            max_len=256, seed=0, dtype=jnp.float32):
    """Returns (aux, blocks): aux = embedding + final LN + LM head;
    blocks = list of uniform block params (the pipeline stages)."""
    d_ff = d_ff or 4 * d_model
    rng = jax.random.PRNGKey(seed)
    ks = jax.random.split(rng, n_layers + 3)
    aux = {
        "tok": (jax.random.normal(ks[0], (vocab_size, d_model)) *
                0.02).astype(dtype),
        "pos": (jax.random.normal(ks[1], (max_len, d_model)) *
                0.02).astype(dtype),
        "lnf": {"g": jnp.ones(d_model, dtype), "b": jnp.zeros(d_model, dtype)},
        "head": (jax.random.normal(ks[2], (d_model, vocab_size)) /
                 math.sqrt(d_model)).astype(dtype),
    }
    blocks = [init_block(ks[3 + i], d_model, n_heads, d_ff, dtype)
              for i in range(n_layers)]
    return aux, blocks


def embed_fn(aux, tokens):
    """[B, T] int tokens -> [B, T, D] activations."""
    T = tokens.shape[-1]
    return aux["tok"][tokens] + aux["pos"][:T]


def logits_fn(aux, h):
    h = _layer_norm(h, aux["lnf"]["g"], aux["lnf"]["b"])
    return h @ aux["head"]


def lm_loss(aux, h, targets):
    """Mean next-token cross entropy; h [B, T, D], targets [B, T] ints."""
    logits = logits_fn(aux, h).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, -1)
    nll = -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]
    return jnp.mean(nll)


class TransformerLM:
    """Single-chip reference driver (the pipeline path lives in
    `parallel.pipeline.PipelineParallel`; see tests/test_pipeline.py for the
    dp+pp wiring)."""

    def __init__(self, vocab_size, d_model=128, n_heads=4, n_layers=4,
                 d_ff=None, max_len=256, seed=0, dtype=jnp.float32,
                 learning_rate=0.1, momentum=0.9, attention="dense"):
        self.aux, self.blocks = init_lm(vocab_size, d_model, n_heads,
                                        n_layers, d_ff, max_len, seed, dtype)
        self.block_fn = make_block_fn(n_heads, attention=attention)
        self.n_heads = int(n_heads)
        self.lr, self.mu = float(learning_rate), float(momentum)
        self._vel = None
        self._jit_step = None
        self._jit_decode = None

    def _loss(self, aux, blocks, x, y):
        h = embed_fn(aux, x)
        for p in blocks:
            h = self.block_fn(p, h)
        return lm_loss(aux, h, y)

    def fit_batch(self, x, y):
        if self._vel is None:
            self._vel = jax.tree.map(jnp.zeros_like, (self.aux, self.blocks))
        if self._jit_step is None:
            lr, mu = self.lr, self.mu

            from ...parallel.pipeline import sgd_momentum_update

            def step(aux, blocks, vel, x, y):
                loss, g = jax.value_and_grad(self._loss, argnums=(0, 1))(
                    aux, blocks, x, y)
                (aux, blocks), vel = sgd_momentum_update(
                    (aux, blocks), vel, g, lr, mu)
                return aux, blocks, vel, loss

            self._jit_step = jax.jit(step, donate_argnums=(0, 1, 2))
        x = jnp.asarray(np.asarray(x), jnp.int32)
        y = jnp.asarray(np.asarray(y), jnp.int32)
        (self.aux, self.blocks, self._vel,
         loss) = self._jit_step(self.aux, self.blocks, self._vel, x, y)
        return float(loss)

    def logits(self, x):
        x = jnp.asarray(np.asarray(x), jnp.int32)
        h = embed_fn(self.aux, x)
        for p in self.blocks:
            h = self.block_fn(p, h)
        return logits_fn(self.aux, h)

    def _decode_step(self):
        """The ONE jitted single-token KV-cache decode step (lazy): shared
        by generate(use_cache=True) and the speculative path's prefill so
        the two can never drift."""
        if self._jit_decode is None:
            block_decode = make_decode_block_fn(self.n_heads)

            def step(aux, blocks, cache, pos, token):
                x = aux["tok"][token] + aux["pos"][pos]      # [1, D]
                new_cache = []
                for p, c in zip(blocks, cache):
                    x, c = block_decode(p, x, c, pos)
                    new_cache.append(c)
                return logits_fn(aux, x)[0], new_cache

            self._jit_decode = jax.jit(step, donate_argnums=(2,))
        return self._jit_decode

    def _spec_verify(self, k):
        """Jitted K-wide verify program per speculative width (cache and
        pos donated — they are the decode state, rebound every call). One
        program per k; batch size retraces inside the same jit."""
        progs = getattr(self, "_spec_verify_cache", None)
        if progs is None:
            progs = self._spec_verify_cache = {}
        prog = progs.get(int(k))
        if prog is None:
            prog = progs[int(k)] = jax.jit(
                make_slot_verify_fn(self.n_heads, k),
                donate_argnums=(2, 3))
        return prog

    @staticmethod
    def _unwrap_draft(draft, k):
        """Accept a bare DraftSource or a serving.speculate.Speculator
        bundle (duck-typed: has .draft and .k) for the `draft=` kwarg."""
        if hasattr(draft, "draft") and hasattr(draft, "k"):
            return draft.draft, int(draft.k)
        return draft, int(k)

    def generate(self, prompt, max_new_tokens=32, temperature=0.0, seed=0,
                 use_cache=False, draft=None, speculate_k=4):
        """Autoregressive continuation of `prompt` (list/array of token
        ids). temperature 0 = greedy argmax; >0 = sampled.

        use_cache=False: the context is re-encoded per step (simple,
        O(T²) per token). use_cache=True: ONE jitted single-token decode
        step with a device-resident KV cache (`make_decode_block_fn`) —
        O(T) per token, the serving path. Both produce identical greedy
        outputs (pinned by test); generation is capped at max_len with a
        cache (no sliding window).

        draft=<DraftSource or Speculator> (serving/speculate.py) turns on
        SPECULATIVE decoding: `speculate_k`-wide verify dispatches accept
        up to K tokens each (greedy-only; the token stream is pinned
        bit-identical to the non-speculative paths — acceptance is by
        exact argmax match, so a bad draft costs throughput, never
        correctness)."""
        toks = list(np.asarray(prompt).ravel().astype(int))
        if not toks:
            raise ValueError("prompt must contain at least one token")
        if draft is not None:
            return self._spec_generate(toks, int(max_new_tokens), draft,
                                       speculate_k, temperature)
        rng = np.random.default_rng(seed)
        max_len = self.aux["pos"].shape[0]

        def pick(logit):
            logit = np.asarray(logit, np.float32)
            if temperature <= 0.0:
                return int(logit.argmax())
            p = np.exp((logit - logit.max()) / temperature)
            return int(rng.choice(len(p), p=p / p.sum()))

        if not use_cache:
            for _ in range(int(max_new_tokens)):
                ctx = toks[-max_len:]
                toks.append(pick(self.logits(
                    np.asarray(ctx)[None, :])[0, -1]))
            return toks

        if len(toks) + int(max_new_tokens) > max_len:
            raise ValueError(
                f"prompt+new tokens ({len(toks)}+{max_new_tokens}) exceed "
                f"max_len {max_len} (the KV cache has no sliding window)")
        step = self._decode_step()
        cache = init_kv_cache(len(self.blocks), 1, max_len,
                              self.aux["tok"].shape[1], self.n_heads,
                              self.aux["tok"].dtype)
        # prefill: feed the prompt one token at a time through the same
        # compiled step (simple; a batched prefill is the known next step)
        logit = None
        for pos, t in enumerate(toks):
            logit, cache = step(
                self.aux, self.blocks, cache, jnp.asarray(pos, jnp.int32),
                jnp.asarray([t], jnp.int32))
        n_new = int(max_new_tokens)
        for i in range(n_new):
            toks.append(pick(logit))
            if i < n_new - 1:    # no decode needed after the last token
                logit, cache = step(
                    self.aux, self.blocks, cache,
                    jnp.asarray(len(toks) - 1, jnp.int32),
                    jnp.asarray([toks[-1]], jnp.int32))
        return toks

    def _spec_generate(self, toks, n_new, draft, k, temperature):
        """generate(draft=...): single-request speculative greedy decode.
        Prefill rides the SAME sequential single-token step as
        generate(use_cache=True) (first emitted token trivially
        bit-identical); then each `verify` dispatch accepts 1..K tokens."""
        if float(temperature) > 0.0:
            raise ValueError("speculative decoding is greedy-only "
                             "(acceptance is by exact argmax match); got "
                             f"temperature={temperature}")
        draft, k = self._unwrap_draft(draft, k)
        if n_new < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {n_new}")
        max_len = self.aux["pos"].shape[0]
        if len(toks) + n_new > max_len:
            raise ValueError(
                f"prompt+new tokens ({len(toks)}+{n_new}) exceed "
                f"max_len {max_len} (the KV cache has no sliding window)")
        step = self._decode_step()
        cache = init_kv_cache(len(self.blocks), 1, max_len,
                              self.aux["tok"].shape[1], self.n_heads,
                              self.aux["tok"].dtype)
        logit = None
        for pos, t in enumerate(toks):
            logit, cache = step(
                self.aux, self.blocks, cache, jnp.asarray(pos, jnp.int32),
                jnp.asarray([t], jnp.int32))
        out = list(toks)
        out.append(int(np.asarray(logit, np.float32).argmax()))
        if n_new == 1:
            return out
        verify = self._spec_verify(k)
        key = object()                      # per-call draft stream
        draft.start(key, out)               # prompt + first accepted token
        pos_arr = jnp.asarray([len(toks)], jnp.int32)
        active = jnp.ones((1,), bool)
        n_out = 1
        try:
            while n_out < n_new:
                # never draft past the remaining budget (a ModelDraft
                # would pay dispatches for tokens that can't be taken)
                dr = list(draft.propose(
                    key, min(k - 1, n_new - n_out - 1)))[:k - 1]
                row = [out[-1]] + dr + [0] * (k - 1 - len(dr))
                nxt, n_acc, _, cache, pos_arr = verify(
                    self.aux, self.blocks, cache, pos_arr,
                    jnp.asarray([row], jnp.int32), active)
                take = min(int(np.asarray(n_acc)[0]) + 1, n_new - n_out)
                acc = [int(t) for t in np.asarray(nxt)[0, :take]]
                out.extend(acc)
                n_out += take
                if n_out < n_new:
                    draft.observe(key, acc)
        finally:
            draft.stop(key)
        return out

    def _spec_generate_batch(self, prompts, n_new, draft, k, temperature):
        """generate_batch(draft=...): batched speculative greedy decode.
        One parallel prefill (the SHARED `prefill_forward`), then K-wide
        verify dispatches over all rows; rows advance 1..K tokens per
        dispatch independently (per-row positions) and finished rows go
        inactive until every row has its n_new tokens."""
        if float(temperature) > 0.0:
            raise ValueError("speculative decoding is greedy-only "
                             "(acceptance is by exact argmax match); got "
                             f"temperature={temperature}")
        draft, k = self._unwrap_draft(draft, k)
        prompts = jnp.asarray(np.asarray(prompts), jnp.int32)
        B, P = prompts.shape
        if n_new < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {n_new}")
        max_len = self.aux["pos"].shape[0]
        if P + n_new > max_len:
            raise ValueError(
                f"prompt+new tokens ({P}+{n_new}) exceed max_len "
                f"{max_len} (the KV cache has no sliding window)")
        prog = getattr(self, "_spec_prefill", None)
        if prog is None:
            n_heads = self.n_heads

            def pre(aux, blocks, prompts):
                h, cache = prefill_forward(aux, blocks, prompts, n_heads,
                                           aux["pos"].shape[0])
                return logits_fn(aux, h[:, -1]).astype(jnp.float32), cache

            prog = self._spec_prefill = jax.jit(pre)
        logit, cache = prog(self.aux, self.blocks, prompts)
        first = np.argmax(np.asarray(logit), -1)
        prompts_np = np.asarray(prompts)
        gens = [[int(first[i])] for i in range(B)]
        keys = [object() for _ in range(B)]
        for i in range(B):
            draft.start(keys[i], prompts_np[i].tolist() + gens[i])
        verify = self._spec_verify(k)
        pos = jnp.full((B,), P, jnp.int32)
        try:
            while any(len(g) < n_new for g in gens):
                toks_np = np.zeros((B, k), np.int32)
                active_np = np.zeros((B,), bool)
                for i, g in enumerate(gens):
                    if len(g) >= n_new:
                        continue
                    active_np[i] = True
                    dr = list(draft.propose(
                        keys[i], min(k - 1, n_new - len(g) - 1)))[:k - 1]
                    toks_np[i, :1 + len(dr)] = [g[-1]] + dr
                nxt, n_acc, _, cache, pos = verify(
                    self.aux, self.blocks, cache, pos,
                    jnp.asarray(toks_np), jnp.asarray(active_np))
                nxt_np, nacc_np = np.asarray(nxt), np.asarray(n_acc)
                for i, g in enumerate(gens):
                    if not active_np[i]:
                        continue
                    take = min(int(nacc_np[i]) + 1, n_new - len(g))
                    acc = [int(t) for t in nxt_np[i, :take]]
                    g.extend(acc)
                    if len(g) < n_new:
                        draft.observe(keys[i], acc)
        finally:
            for key in keys:
                draft.stop(key)
        return np.concatenate(
            [prompts_np, np.asarray(gens, np.int32)], 1)

    def generate_batch(self, prompts, max_new_tokens, temperature=0.0,
                       seed=0, draft=None, speculate_k=4):
        """Batched KV-cache decode, entire generation in ONE jitted
        program: a PARALLEL prefill (one causal forward over the whole
        prompt fills every layer's cache — MXU-shaped, not P sequential
        steps) followed by a `lax.scan` over the new tokens.

        Contrast `generate(use_cache=True)`: that path round-trips
        host<->device per token to pick the next token in numpy. Here token
        selection folds into the scan, so the host sees the device exactly
        once per call. temperature<=0 = greedy argmax, pinned identical to
        `generate(use_cache=True)` row-by-row by test; temperature>0 =
        on-device categorical sampling (`jax.random.categorical`, keyed by
        `seed` — deterministic per (seed, shapes), independent rows).

        prompts: [B, P] int array (equal-length prompts; the serving
        batcher pads/buckets upstream). Returns [B, P + max_new_tokens].
        reference parity: MultiLayerNetwork.rnnTimeStep
        (MultiLayerNetwork.java:2196) — O(1)-state streaming inference,
        attention era.

        draft=<DraftSource or Speculator> switches to SPECULATIVE greedy
        decode (`_spec_generate_batch`): up to `speculate_k` tokens per
        verify dispatch per row, token streams pinned bit-identical to
        this path's greedy rows."""
        if draft is not None:
            return self._spec_generate_batch(prompts, int(max_new_tokens),
                                             draft, speculate_k,
                                             temperature)
        prompts = jnp.asarray(np.asarray(prompts), jnp.int32)
        B, P = prompts.shape
        n_new = int(max_new_tokens)
        if n_new < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {n_new}")
        sampled = float(temperature) > 0.0
        max_len = self.aux["pos"].shape[0]
        if P + n_new > max_len:
            raise ValueError(
                f"prompt+new tokens ({P}+{n_new}) exceed max_len "
                f"{max_len} (the KV cache has no sliding window)")
        cache = getattr(self, "_jit_gen_cache", None)
        if cache is None:
            cache = self._jit_gen_cache = collections.OrderedDict()
        key = (B, P, n_new, sampled)
        if key in cache:
            cache.move_to_end(key)          # LRU touch
        else:
            block_decode = make_decode_block_fn(self.n_heads)
            n_heads = self.n_heads

            def step_token(aux, blocks, cache, pos, tok):      # tok [B]
                x = aux["tok"][tok] + aux["pos"][pos]          # [B, D]
                new_cache = []
                for p, c in zip(blocks, cache):
                    x, c = block_decode(p, x, c, pos)
                    new_cache.append(c)
                # fp32 argmax for tie-break parity with generate()'s
                # numpy pick()
                return logits_fn(aux, x).astype(jnp.float32), new_cache

            def gen(aux, blocks, prompts, temp, rng):
                # parallel prefill: one causal pass fills the caches (the
                # SHARED implementation serving's prefill programs use)
                h, cache = prefill_forward(aux, blocks, prompts, n_heads,
                                           max_len)
                logit = logits_fn(aux, h[:, -1]).astype(jnp.float32)
                pos = jnp.asarray(P, jnp.int32)

                def pick(logit, rng):
                    if not sampled:            # static: greedy program
                        return jnp.argmax(logit, -1).astype(jnp.int32)
                    return jax.random.categorical(
                        rng, logit / temp, -1).astype(jnp.int32)

                def dec_body(carry, _):
                    cache, pos, logit, rng = carry
                    rng, krng = jax.random.split(rng)
                    tok = pick(logit, krng)
                    logit, cache = step_token(aux, blocks, cache, pos,
                                              tok)
                    return (cache, pos + 1, logit, rng), tok

                (_, _, logit, rng), toks = jax.lax.scan(
                    dec_body, (cache, pos, logit, rng), None,
                    length=n_new - 1)
                last = pick(logit, jax.random.split(rng)[1])
                return jnp.concatenate(
                    [toks, last[None, :]], 0).T            # [B, n_new]

            # keyed LRU: alternating (B, P, n_new) shapes (e.g. a serving
            # batcher flipping batch sizes) must not re-trace, but a
            # workload with unbounded shape variety must not accumulate
            # compiled programs + device buffers without bound either —
            # bucket prompt lengths upstream to stay under the cap
            cache[key] = jax.jit(gen)
            while len(cache) > GEN_JIT_CACHE_SIZE:
                cache.popitem(last=False)
        new = cache[key](self.aux, self.blocks, prompts,
                         jnp.asarray(max(float(temperature), 1e-6),
                                     jnp.float32),
                         jax.random.PRNGKey(int(seed)))
        return np.concatenate([np.asarray(prompts), np.asarray(new)], 1)
