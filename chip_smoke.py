#!/usr/bin/env python3
"""chip_smoke.py — does the system still start on the chip?

Drives the repo's main paths once, through the entry points a user calls, at
the full width of the models they are written for (depth and weights as
published / seeded), and checks what comes out by the repo's own means:

  trainer_resnet50   resnet50(bf16) b128 224x224 through
                     ComputationGraph.fit(iterator): loss finite at every
                     step and lower after the steps than before, parameters
                     finite, bf16 forward of 8 images against a float32
                     "highest"-precision forward of the same parameters.
  decode_server      TransformerLM at GPT-2-small's published widths behind
                     ContinuousDecodeServer(paged, prefix cache, chunked
                     prefill, admission) — the production configuration:
                     seeded shared-prefix requests through submit(); every
                     future resolves, lengths/vocabulary/counters right, a
                     repeated request repeats its stream, and the serve
                     programs' LOGITS (not tokens: random weights tie)
                     against TransformerLM.logits in float32/highest.
  serve_contracts    OBSERVES (does not assert) the three stream identities
                     tier-1 pins on XLA:CPU — co-batched == solo, paged ==
                     fixed-slot, fused K == plain — on this device.
  lm_flash_trainer   TransformerLM(attention="flash").fit_batch at B=8
                     T=1024: the forward and both backward Pallas kernels
                     through the normal trainer.
  flash_kernels      every kernel in ops/flash_attention.py compiled by
                     Mosaic (interpret=False) at D=64 and D=128 against the
                     float32 blockwise reference.
  multichip_wrapper  (>= 4 devices) ParallelWrapper over four chips on
  multichip_modes    ResNet-50, and __graft_entry__.run_multichip_modes on
                     the real devices.

One process; nothing here starts a child that touches JAX (a chip belongs
to one process at a time). Every phase runs even after one fails. The exit
status is 0 only when every phase ran and passed on a `tpu`, 1 when a phase
failed; on a machine without a TPU nothing is printed on stdout at all (exit
4 — not 2 or 3, which the chip tool uses for its own refusals).

`--rehearsal` runs the same control flow at toy widths on the CPU (Pallas
interpreter) so a change can be debugged before chip time is spent: every
line says `rehearsal`, and it cannot end in the passing verdict. `--phases
a,b` restricts a run to some phases while debugging; it cannot end in the
passing verdict either. Both exit 5 when what they ran passed, 1 when not.

No number printed here is a speed result: seconds are set-up and wall time
of a smoke run, reported so that a cold and a warm compile cache can be told
apart.
"""
from __future__ import annotations

import argparse
import contextlib
import copy
import gc
import importlib.metadata
import json
import random
import sys
import time
import traceback

# ---------------------------------------------------------------------------
# sizes: FULL is what the chip runs; REHEARSAL is the CPU debugging aid
# ---------------------------------------------------------------------------
# GPT-2-small's published widths (L=12, d=768, 12 heads x 64, d_ff=3072,
# vocab 50,257, 1,024 positions); 163 M parameters with the untied head.
_GPT2_SMALL = dict(vocab=50257, d_model=768, n_heads=12, n_layers=12,
                   max_len=1024)

# The trainer's learning rate is not a width. The zoo default (0.1, Nesterov
# 0.9, no warm-up) overshoots on a fixed random batch for its first six steps
# or so (224x224, CPU, PR 21: 7.9, 3.8, 10.8, 19.9, 30.1, 21.3, 13.4), which
# says nothing about whether the step is right; at 0.01 the same batch falls
# monotonically (7.9, 6.5, 4.9, 3.9, 3.5), so a four-step smoke uses that.
FULL = dict(
    interpret=False,
    resnet=dict(batch=128, hw=224, classes=1000, steps=4, ref_images=8,
                learning_rate=0.01),
    lm=_GPT2_SMALL,
    serve=dict(slots=8, block=16, chunk=64, buckets=(16, 32, 64, 512),
               prefix_blocks=8, n_shared=28, suffix=(1, 200), new=(8, 33),
               short_prompts=(5, 20, 50, 61), ref_prefill=48),
    lm_train=dict(batch=8, seq=1024, steps=3),
    # a long-context flash shape, and the same at the 128-wide heads every
    # model in ROADMAP Queue 2 has
    kernels=(dict(B=4, T=8192, H=8, D=64), dict(B=2, T=8192, H=8, D=128)),
    multichip=dict(batch=128, hw=224, classes=1000, learning_rate=0.01),
)
REHEARSAL = dict(
    interpret=True,
    resnet=dict(batch=8, hw=32, classes=10, steps=4, ref_images=4,
                learning_rate=0.003),
    lm=dict(vocab=211, d_model=64, n_heads=4, n_layers=2, max_len=128),
    serve=dict(slots=4, block=8, chunk=16, buckets=(8, 16, 64),
               prefix_blocks=2, n_shared=8, suffix=(1, 30), new=(4, 9),
               short_prompts=(3, 11, 15), ref_prefill=12),
    lm_train=dict(batch=2, seq=128, steps=3),
    kernels=(dict(B=1, T=256, H=2, D=64), dict(B=1, T=256, H=2, D=128)),
    multichip=dict(batch=16, hw=32, classes=10, learning_rate=0.003),
)

# ---------------------------------------------------------------------------
# tolerances, each with its reason (measured values are in PERF.md, PR 21)
# ---------------------------------------------------------------------------
# Logits of a bf16 forward against the float32/"highest" forward of the same
# parameters: the error's L2 norm over the reference's L2 norm (ResNet:
# after centring each row, since log-probabilities are logits up to a
# constant). bf16 keeps 8 significant bits (eps 2^-8 = 3.9e-3) and every
# layer rounds its weights and activations once, so the error grows with
# depth; batch-norm divides by a batch deviation and centring cancels the
# common part, which both raise the RELATIVE error. The bounds are two to
# three times what bf16 measures on the v5e (PERF.md, PR 21). A format
# narrower than stated has an eps 16x larger or more (fp8 e4m3: 2^-4) and
# would land near or above 1; so would wrong weights or a wrong mask.
REL_L2_BF16_RESNET = 0.2      # measured 0.113 (v5e), 0.084 (XLA:CPU)
REL_L2_BF16_LM = 0.03         # measured 0.0076-0.0095 (v5e, full width)
# Flash kernels against the float32 blockwise oracle, inputs bf16: the
# output is rounded to bf16 once (2^-9 relative) and the probability panel is
# rounded to bf16 before the P.V / P^T.dO matmuls. Every entry must land
# within 3% of the largest reference entry — the bound
# tests/test_flash_attention.py already holds bf16 gradients to on the
# interpreter. A wrong mask, a dropped block or a wrong offset moves whole
# rows by O(1) of that scale.
FLASH_MAX_ERR_OVER_MAX_REF = 0.03
# The flash LM's first-step loss against the float32/highest dense-attention
# loss of the same parameters on the same batch: a mean over B*T tokens of
# per-token losses that each carry the forward's bf16 error, so it averages
# far below the logit tolerance; 1% of a loss near ln(vocab) is ample and
# still catches a broken kernel (which moves the loss by tenths).
REL_LOSS_FLASH_VS_DENSE = 0.01
# Four chips against one chip, same 128 images, same seed: the same bf16
# arithmetic with the batch-norm and gradient sums taken in another order.
REL_SCORE_4CHIP_VS_1CHIP = 0.02


EXIT_PHASE_FAILED, EXIT_NO_TPU, EXIT_NOT_A_VERDICT = 1, 4, 5


class SmokeFailure(Exception):
    """A phase's check did not hold."""


def check(cond, what):
    if not cond:
        raise SmokeFailure(what)


def rel_l2(got, ref):
    import numpy as np
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


def max_err_over_max_ref(got, ref):
    import numpy as np
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.abs(got - ref).max() / np.abs(ref).max())


def all_finite(tree):
    import jax
    import jax.numpy as jnp
    return all(bool(jnp.all(jnp.isfinite(a))) for a in jax.tree.leaves(tree)
               if jnp.issubdtype(a.dtype, jnp.floating))


# ---------------------------------------------------------------------------
# phase 1: the trainer
# ---------------------------------------------------------------------------
def _resnet_batch(n, hw, classes, seed=0):
    import numpy as np
    rng = np.random.default_rng(seed)
    x = rng.random((n, hw, hw, 3)).astype(np.float32)
    y = np.eye(classes, dtype=np.float32)[rng.integers(0, classes, n)]
    return x, y


def phase_trainer_resnet50(cfg):
    import jax
    import numpy as np

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.models.zoo.resnet import resnet50, resnet50_conf
    from deeplearning4j_tpu.nn.graph.computation_graph import \
        ComputationGraph
    from deeplearning4j_tpu.optimize.listeners import \
        CollectScoresIterationListener

    c = cfg["resnet"]
    shape = dict(height=c["hw"], width=c["hw"], channels=3,
                 num_classes=c["classes"])
    net = resnet50(data_type="bfloat16", learning_rate=c["learning_rate"],
                   **shape)
    scores = CollectScoresIterationListener()
    net.set_listeners(scores)
    x, y = _resnet_batch(c["batch"], c["hw"], c["classes"])
    # the same seeded batch steps+1 times: the score fit() reports at step k
    # is the loss BEFORE update k, so the last one is the loss after `steps`
    net.fit(ListDataSetIterator([DataSet(x, y)] * (c["steps"] + 1)))
    losses = [s for _, s in scores.scores]
    check(len(losses) == c["steps"] + 1,
          f"expected {c['steps'] + 1} scores, got {len(losses)}")
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall on the fixed batch: {losses}")
    check(all_finite(net._params), "non-finite parameter after training")

    # bf16 forward of 8 images against float32/highest of the SAME
    # parameters. Training-mode forward (batch statistics): a few steps in,
    # the running statistics inference would use are still near their
    # initial values and the softmax saturates to exact zeros in either
    # precision. The head is a softmax; log-probabilities are the logits up
    # to a per-row constant.
    xs = x[:c["ref_images"]]
    got = np.log(np.asarray(net.output(xs, train=True)[0], np.float32))
    ref_net = ComputationGraph(
        resnet50_conf(data_type="float32", **shape)).init(net.params())
    with jax.default_matmul_precision("highest"):
        ref = np.log(np.asarray(ref_net.output(xs, train=True)[0]))
    check(got.shape == (c["ref_images"], c["classes"]),
          f"forward shape {got.shape}")
    check(np.isfinite(got).all() and np.isfinite(ref).all(),
          "non-finite log-probabilities")
    err = rel_l2(got - got.mean(-1, keepdims=True),
                 ref - ref.mean(-1, keepdims=True))
    check(err <= REL_L2_BF16_RESNET,
          f"bf16 vs float32 logits rel-L2 {err:.4f} > {REL_L2_BF16_RESNET}")
    return {"checked": "loss finite each step and lower after; params "
                       "finite; bf16 logits vs float32/highest",
            "losses": [round(s, 4) for s in losses],
            "logits_rel_l2": round(err, 5),
            "logits_rel_l2_bound": REL_L2_BF16_RESNET}


# ---------------------------------------------------------------------------
# phases 2 and 3: the decode server
# ---------------------------------------------------------------------------
def _build_lm(cfg, **kw):
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.zoo.transformer import TransformerLM
    c = cfg["lm"]
    return TransformerLM(c["vocab"], d_model=c["d_model"],
                         n_heads=c["n_heads"], n_layers=c["n_layers"],
                         max_len=c["max_len"], seed=0, dtype=jnp.bfloat16,
                         **kw)


def _float32_reference(lm):
    """The same parameters in float32 behind the plain dense-attention
    forward (`TransformerLM.logits`); call it under
    jax.default_matmul_precision("highest")."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.zoo.transformer import make_block_fn
    ref = copy.copy(lm)
    ref.aux, ref.blocks = jax.tree.map(
        lambda a: a.astype(jnp.float32), (lm.aux, lm.blocks))
    ref.block_fn = make_block_fn(lm.n_heads, attention="dense")
    return ref


def _production_server(lm, cfg, **overrides):
    from deeplearning4j_tpu.serving import ContinuousDecodeServer
    s = cfg["serve"]
    kw = dict(slots=s["slots"], prompt_buckets=s["buckets"], max_queue=256,
              paged=True, block_size=s["block"], chunked_prefill=s["chunk"],
              admission=True)           # prefix_cache=True is the default
    kw.update(overrides)
    return ContinuousDecodeServer(lm, **kw)


def _shared_prefix_requests(cfg, n):
    """Seeded requests from the repo's own workload generator: one system
    prefix of whole blocks, mixed suffix and output lengths."""
    from deeplearning4j_tpu.serving import SharedPrefixMix
    s = cfg["serve"]
    mix = SharedPrefixMix(
        n_prefixes=1, prefix_blocks=(s["prefix_blocks"],
                                     s["prefix_blocks"] + 1),
        block_size=s["block"], suffix=s["suffix"], new=s["new"],
        vocab=cfg["lm"]["vocab"], seed=0)
    rng = random.Random(0)
    return [mix.sample(rng) for _ in range(n)]


def _check_stream(stream, prompt, max_new, vocab):
    check(len(stream) == len(prompt) + max_new,
          f"stream length {len(stream)} != {len(prompt)}+{max_new}")
    check(list(stream[:len(prompt)]) == list(prompt),
          "stream does not start with its prompt")
    check(all(0 <= int(t) < vocab for t in stream),
          "token outside the vocabulary")


def phase_decode_server(cfg):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.models.zoo.transformer import (
        init_paged_kv_cache, make_paged_decode_fn, make_paged_install_fn,
        make_paged_prefill_fn, make_paged_verify_fn)

    s, vocab = cfg["serve"], cfg["lm"]["vocab"]
    lm = _build_lm(cfg)
    reqs = _shared_prefix_requests(cfg, s["n_shared"])
    rng = random.Random(1)
    # short prompts with no shared prefix take the one-shot bucket prefill;
    # everything longer than one chunk takes the chunked-prefill program
    reqs += [{"prompt": tuple(rng.randrange(1, vocab) for _ in range(n)),
              "max_new": 8} for n in s["short_prompts"]]
    srv = _production_server(lm, cfg)
    srv.start()
    try:
        futs = [srv.submit(r["prompt"], r["max_new"]) for r in reqs]
        streams = [f.result(timeout=900) for f in futs]
        for r, st in zip(reqs, streams):
            _check_stream(st, r["prompt"], r["max_new"], vocab)
        # the same request twice more: now a full prefix-cache hit
        for _ in range(2):
            again = srv.submit(reqs[0]["prompt"],
                               reqs[0]["max_new"]).result(timeout=900)
            check(list(again) == list(streams[0]),
                  "a repeated request did not repeat its stream")
        snap = srv.metrics.snapshot()
    finally:
        srv.stop()
    sent = len(reqs) + 2
    check(snap["completed"] == sent,
          f"completed {snap['completed']} != sent {sent}")
    check(snap["prefix_rows_hit"] > 0, "prefix-hit counter did not move")

    # --- logits against the float32 reference, through the programs the
    # server dispatches (built and jitted the way its constructor does) ---
    H, bs, C = lm.n_heads, s["block"], s["chunk"]
    P = s["ref_prefill"]
    toks = np.asarray(reqs[0]["prompt"][:C + 1], np.int32)
    check(len(toks) == C + 1 and P < C, "reference prompt too short")
    ref = _float32_reference(lm)
    with jax.default_matmul_precision("highest"):
        ref_logits = np.asarray(ref.logits(toks[None])[0], np.float32)
    # (a) one-shot paged prefill, prompt right-padded to its bucket: the
    # last REAL position's logits
    bucket = next(b for b in s["buckets"] if b >= P)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :P] = toks[:P]
    pre_logits, panels = jax.jit(make_paged_prefill_fn(H))(
        lm.aux, lm.blocks, jnp.asarray(padded), jnp.asarray(P, jnp.int32))
    errs = {"prefill_last": rel_l2(pre_logits[0], ref_logits[P - 1])}
    # (b) install the panels, then ONE decode step through the block table
    # (2 slots, one active) against the full forward at that position
    nb = -(-(C + 1) // bs)
    cache = init_paged_kv_cache(len(lm.blocks), 2 * nb, bs,
                                cfg["lm"]["d_model"], H, dtype=jnp.bfloat16)
    table = np.arange(nb, dtype=np.int32)
    cache = jax.jit(make_paged_install_fn(bs), donate_argnums=(0,))(
        cache, panels, jnp.asarray(table), jnp.asarray(P, jnp.int32),
        jnp.asarray(0, jnp.int32))
    btabs = np.stack([table, table + nb])
    _, dec_logits, cache, _ = jax.jit(
        make_paged_decode_fn(H, bs), donate_argnums=(2, 4))(
        lm.aux, lm.blocks, cache, jnp.asarray(btabs),
        jnp.asarray([P, 0], jnp.int32),
        jnp.asarray([toks[P], 0], jnp.int32), jnp.asarray([True, False]))
    errs["decode_through_cache"] = rel_l2(dec_logits[0], ref_logits[P])
    # (c) the C-wide block program chunked prefill and speculative verify
    # share, from an empty cache: every chunk position's logits
    _, _, chunk_logits, cache, _ = jax.jit(
        make_paged_verify_fn(H, C, bs), donate_argnums=(2, 4))(
        lm.aux, lm.blocks, cache, jnp.asarray(btabs),
        jnp.asarray([0, 0], jnp.int32),
        jnp.asarray(np.stack([toks[:C], toks[:C]])),
        jnp.asarray([False, True]), jnp.asarray([0, C], jnp.int32))
    errs["chunk_rows"] = rel_l2(chunk_logits[1], ref_logits[:C])
    for name, e in errs.items():
        check(np.isfinite(e) and e <= REL_L2_BF16_LM,
              f"{name}: bf16 vs float32 logits rel-L2 {e:.4f} > "
              f"{REL_L2_BF16_LM}")
    return {"checked": "every future resolved; stream lengths, prompts, "
                       "vocabulary; completed == sent; prefix hits; repeat "
                       "== first; serve-program logits vs float32/highest",
            "requests": sent,
            "prefix_rows_hit": snap["prefix_rows_hit"],
            "prefix_rows_total": snap["prefix_rows_total"],
            "chunk_dispatches": snap.get("chunk_dispatches"),
            "logits_rel_l2": {k: round(v, 5) for k, v in errs.items()},
            "logits_rel_l2_bound": REL_L2_BF16_LM}


def phase_serve_contracts(cfg):
    """The stream identities tier-1 pins bit-for-bit on XLA:CPU, observed on
    this device. They are reported, not asserted: whether an MXU keeps them
    is a finding (PERF.md), not something the smoke may assume. The phase
    fails only if a server fails to serve."""
    s, vocab = cfg["serve"], cfg["lm"]["vocab"]
    lm = _build_lm(cfg)
    # four prompts longer than one chunk (so every server runs the same two
    # programs: chunked prefill + decode); decode lengths = 1 mod K keep the
    # fused windows full
    reqs = [r for r in _shared_prefix_requests(cfg, 4 * s["n_shared"])
            if len(r["prompt"]) > s["chunk"]][:4]
    for r in reqs:
        r["max_new"] = 9

    def serve(requests, **overrides):
        srv = _production_server(lm, cfg, **overrides)
        srv.start()
        try:
            futs = [srv.submit(r["prompt"], r["max_new"]) for r in requests]
            out = [list(f.result(timeout=900)) for f in futs]
        finally:
            srv.stop()
        for r, st in zip(requests, out):
            _check_stream(st, r["prompt"], r["max_new"], vocab)
        return out

    together = serve(reqs)
    solo = [serve([r])[0] for r in reqs]        # fresh server, cold cache
    fixed = serve(reqs, paged=False)
    fused = serve(reqs, fused_serve=4)
    same = lambda other: sum(a == b for a, b in zip(together, other))
    return {"checked": "all four server variants served; identities "
                       "OBSERVED, not asserted",
            "streams": len(reqs),
            "cobatched_equals_solo": same(solo),
            "paged_equals_fixed": same(fixed),
            "fused4_equals_plain": same(fused)}


# ---------------------------------------------------------------------------
# phase 4: the LM trainer through the Pallas kernels
# ---------------------------------------------------------------------------
def phase_lm_flash_trainer(cfg):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.models.zoo.transformer import (embed_fn,
                                                           lm_loss,
                                                           make_block_fn)

    t, vocab = cfg["lm_train"], cfg["lm"]["vocab"]
    lm = _build_lm(cfg, attention="flash")
    rng = np.random.default_rng(0)
    x = rng.integers(0, vocab, (t["batch"], t["seq"])).astype(np.int32)
    y = np.roll(x, -1, axis=1)

    # reference loss BEFORE any update: float32 parameters, dense attention,
    # "highest" matmuls, same batch
    dense_block = make_block_fn(lm.n_heads, attention="dense")

    def dense_loss(aux, blocks, x, y):
        h = embed_fn(aux, x)
        for p in blocks:
            h = dense_block(p, h)
        return lm_loss(aux, h, y)

    ref = _float32_reference(lm)
    with jax.default_matmul_precision("highest"):
        ref_loss = float(jax.jit(dense_loss)(ref.aux, ref.blocks,
                                             jnp.asarray(x), jnp.asarray(y)))
    del ref
    losses = [lm.fit_batch(x, y) for _ in range(t["steps"])]
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(all_finite((lm.aux, lm.blocks)),
          "non-finite parameter after training")
    err = abs(losses[0] - ref_loss) / abs(ref_loss)
    check(err <= REL_LOSS_FLASH_VS_DENSE,
          f"flash first-step loss {losses[0]:.4f} vs float32 dense "
          f"{ref_loss:.4f}: rel {err:.4f} > {REL_LOSS_FLASH_VS_DENSE}")
    check(losses[-1] < losses[0],
          f"loss did not fall on the fixed batch: {losses}")
    return {"checked": "loss finite each step and lower after; params "
                       "finite; first-step loss vs float32/highest dense "
                       "attention",
            "losses": [round(v, 4) for v in losses],
            "ref_loss": round(ref_loss, 4), "loss_rel_err": round(err, 6),
            "loss_rel_err_bound": REL_LOSS_FLASH_VS_DENSE}


# ---------------------------------------------------------------------------
# phase 5: every flash kernel, compiled, against the float32 oracle
# ---------------------------------------------------------------------------
def _flash_kernel_errors(shape, interpret):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.ops.flash_attention import (
        _blockwise_attention_ckpt, flash_attention,
        flash_attention_bwd_partial, flash_attention_partial)

    B, T, H, D = shape["B"], shape["T"], shape["H"], shape["D"]
    scale = 1.0 / D ** 0.5
    rng = np.random.default_rng(D)
    mk = lambda: jnp.asarray(rng.standard_normal((B, T, H, D)),
                             jnp.bfloat16)
    q, k, v, g = mk(), mk(), mk(), mk()       # g: the output cotangent
    f32 = lambda a: a.astype(jnp.float32)

    # float32 oracle: blockwise (never holds [T, T]), "highest" matmuls
    def oracle(q, k, v):
        return _blockwise_attention_ckpt(q, k, v, True, scale)

    with jax.default_matmul_precision("highest"):
        o_ref, vjp = jax.vjp(jax.jit(oracle), f32(q), f32(k), f32(v))
        dq_ref, dk_ref, dv_ref = vjp(f32(g))

    # forward-only kernel (`_kernel`), then forward+lse and the dQ and dK/dV
    # kernels through jax.vjp of the public op — default blocks: 1024^2
    # forward, 512^2 backward
    flash = lambda q, k, v: flash_attention(q, k, v, True, None, 1024, 1024,
                                            interpret)
    errs = {"fwd": max_err_over_max_ref(jax.jit(flash)(q, k, v), o_ref)}
    o, vjp = jax.vjp(jax.jit(flash), q, k, v)
    dq, dk, dv = vjp(g)
    errs["fwd_lse"] = max_err_over_max_ref(o, o_ref)
    errs["dq"] = max_err_over_max_ref(dq, dq_ref)
    errs["dk"] = max_err_over_max_ref(dk, dk_ref)
    errs["dv"] = max_err_over_max_ref(dv, dv_ref)

    # the ring-attention hop kernels on one whole-sequence chunk. Equal
    # non-zero offsets mask exactly like zero ones, and prove the SMEM
    # scalars are read.
    flat = lambda a: a.transpose(0, 2, 1, 3).reshape(B * H, T, D)
    unflat = lambda a: a.reshape(B, H, T, D).transpose(0, 2, 1, 3)
    off = jnp.asarray(T, jnp.int32)
    acc, m, l = jax.jit(lambda q, k, v: flash_attention_partial(
        q, k, v, off, off, causal=True, interpret=interpret))(
        flat(q), flat(k), flat(v))
    o_part = acc / l[..., None]
    errs["partial"] = max_err_over_max_ref(unflat(o_part), o_ref)
    lse = (m + jnp.log(l))[..., None]
    delta = jnp.sum(f32(flat(g)) * o_part, -1, keepdims=True)
    dqp, dkp, dvp = jax.jit(
        lambda q, k, v, delta, do, lse: flash_attention_bwd_partial(
            q, k, v, delta, do, lse, off, off, causal=True,
            interpret=interpret))(flat(q), flat(k), flat(v), delta, flat(g),
                                  lse)
    errs["bwd_partial_dq"] = max_err_over_max_ref(unflat(dqp), dq_ref)
    errs["bwd_partial_dk"] = max_err_over_max_ref(unflat(dkp), dk_ref)
    errs["bwd_partial_dv"] = max_err_over_max_ref(unflat(dvp), dv_ref)
    return errs


def phase_flash_kernels(cfg):
    import numpy as np
    out = {}
    for shape in cfg["kernels"]:
        errs = _flash_kernel_errors(shape, cfg["interpret"])
        name = "B{B}_T{T}_H{H}_D{D}".format(**shape)
        out[name] = {k: round(v, 5) for k, v in errs.items()}
        for k, e in errs.items():
            check(np.isfinite(e) and e <= FLASH_MAX_ERR_OVER_MAX_REF,
                  f"{name} {k}: max err / max ref {e:.4f} > "
                  f"{FLASH_MAX_ERR_OVER_MAX_REF}")
    return {"checked": "forward, forward+lse, dQ, dK/dV, partial and "
                       "backward-partial kernels vs the float32 blockwise "
                       "oracle" + ("" if cfg["interpret"]
                                   else ", Mosaic-compiled (interpret=False)"),
            "max_err_over_max_ref": out,
            "bound": FLASH_MAX_ERR_OVER_MAX_REF}


# ---------------------------------------------------------------------------
# phases 6 and 7: four chips
# ---------------------------------------------------------------------------
def _span(tree):
    """(devices spanned by every leaf, smallest shard / whole leaf)."""
    import jax
    leaves = jax.tree.leaves(tree)
    n_dev = min(len(a.sharding.device_set) for a in leaves)
    frac = min(a.addressable_shards[0].data.size / max(a.size, 1)
               for a in leaves)
    return n_dev, frac


def phase_multichip_wrapper(cfg):
    import jax
    import numpy as np

    from deeplearning4j_tpu.common.health import TrainingHealthPolicy
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.models.zoo.resnet import resnet50
    from deeplearning4j_tpu.parallel.parallel_wrapper import ParallelWrapper

    m = cfg["multichip"]
    shape = dict(height=m["hw"], width=m["hw"], channels=3,
                 num_classes=m["classes"])
    x, y = _resnet_batch(m["batch"], m["hw"], m["classes"])
    ds = DataSet(x, y)
    new_net = lambda: resnet50(data_type="bfloat16",
                               learning_rate=m["learning_rate"], **shape)

    one = new_net()
    one.fit(ds)                                  # one chip, one step
    score1 = float(one._score)
    check(np.isfinite(score1), f"one-chip score {score1}")
    del one
    out = {"devices": 4, "score_one_chip": round(score1, 5)}

    def first_step(build):
        net = new_net()
        pw = build(ParallelWrapper.Builder(net).workers(4)).build()
        pw.fit(ds)
        score = float(net._score)
        check(abs(score - score1) / abs(score1) <= REL_SCORE_4CHIP_VS_1CHIP,
              f"four-chip first-step score {score:.5f} vs one-chip "
              f"{score1:.5f}")
        check(all_finite(net._params), "non-finite parameter")
        batch = pw._put_batch(x)         # the placement fit() gives a batch
        check(len(batch.sharding.device_set) == 4
              and batch.addressable_shards[0].data.shape[0]
              == m["batch"] // 4, "batch is not split over four devices")
        return net, score

    # per-step GSPMD all-reduce: everything replicated, the batch split
    net, score = first_step(lambda b: b.averaging_frequency(1))
    p_dev, _ = _span(net._params)
    u_dev, _ = _span(net._updater_state)
    check(p_dev == 4 and u_dev == 4, "state does not span four devices")
    out["allreduce"] = {"score": round(score, 5), "param_devices": p_dev,
                        "updater_devices": u_dev,
                        "batch_rows_per_device": m["batch"] // 4}
    # ZeRO-1: optimizer state partitioned over the data axis
    net, score = first_step(
        lambda b: b.averaging_frequency(1).sharded_updater_state(True))
    p_dev, _ = _span(net._params)
    u_dev, u_frac = _span(net._updater_state)
    check(p_dev == 4 and u_dev == 4, "state does not span four devices")
    check(u_frac <= 0.25 + 1e-9,
          f"ZeRO-1 left a whole optimizer leaf on one device ({u_frac})")
    out["zero1"] = {"score": round(score, 5), "param_devices": p_dev,
                    "updater_devices": u_dev,
                    "smallest_updater_shard_fraction": round(u_frac, 4)}
    # k local steps in shard_map then pmean, without and with the watchdog
    half = m["batch"] // 2
    pair = [DataSet(x[:half], y[:half]), DataSet(x[half:], y[half:])]
    for key, policy in (("kstep", None),
                        ("kstep_health", TrainingHealthPolicy())):
        net = new_net()
        b = ParallelWrapper.Builder(net).workers(4).averaging_frequency(2)
        if policy is not None:
            b = b.health_policy(policy)
        b.build().fit(ListDataSetIterator(pair))
        score = float(net._score)
        check(np.isfinite(score) and all_finite(net._params),
              f"{key}: non-finite score or parameter")
        p_dev, _ = _span(net._params)
        check(p_dev == 4, f"{key}: params do not span four devices")
        check(net.conf.iteration_count == 2,
              f"{key}: {net.conf.iteration_count} iterations, not 2")
        out[key] = {"score": round(score, 5), "param_devices": p_dev}
        if policy is not None:
            check(policy.counts["aborts"] == 0 and policy.counts["skips"]
                  == 0, f"watchdog fired on clean data: {policy.counts}")
    out["checked"] = ("params, optimizer state and batch span 4 devices; "
                      "first-step score == one-chip score; ZeRO-1 shards; "
                      "k-step with and without TrainingHealthPolicy")
    out["score_rel_bound"] = REL_SCORE_4CHIP_VS_1CHIP
    return out


def phase_multichip_modes(cfg):
    import jax

    import __graft_entry__ as graft
    # the modes compare float32 results at atol 1e-4: hold the matmuls to
    # float32 too (a TPU's default is one bf16 pass). Their own report lines
    # go to stderr; stdout stays one JSON line per phase.
    with jax.default_matmul_precision("highest"), \
            contextlib.redirect_stdout(sys.stderr):
        graft.run_multichip_modes(jax.devices()[:4])
    return {"checked": "dp+tp ZeRO-1, parameter averaging, ring attention "
                       "(einsum and Pallas flash hops), GPipe pipe=4, MoE "
                       "all_to_all expert=4, dp x ep top-2, model-sharded "
                       "Word2Vec — one step each on 4 real devices",
            "devices": 4}


PHASES = (
    ("trainer_resnet50", phase_trainer_resnet50, 1),
    ("decode_server", phase_decode_server, 1),
    ("serve_contracts", phase_serve_contracts, 1),
    ("lm_flash_trainer", phase_lm_flash_trainer, 1),
    ("flash_kernels", phase_flash_kernels, 1),
    ("multichip_wrapper", phase_multichip_wrapper, 4),
    ("multichip_modes", phase_multichip_modes, 4),
)


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------
class CompileMeter:
    """Seconds the backend spent compiling (or fetching from the persistent
    cache) and the cache's hits and misses, from jax.monitoring."""

    def __init__(self):
        from jax import monitoring
        self.compile_s = 0.0
        self.hits = self.misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += seconds

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def take(self):
        out = {"compile_s": round(self.compile_s, 1),
               "cache_hits": self.hits, "cache_misses": self.misses}
        self.compile_s, self.hits, self.misses = 0.0, 0, 0
        return out


def run_phase(name, fn, cfg, meter, emit):
    import jax
    rec = {"phase": name}
    t0 = time.perf_counter()
    try:
        rec.update(fn(cfg))
        rec["pass"] = True
    except Exception:   # noqa: BLE001 — the one handler: record the
        #                 failure, run the remaining phases, exit non-zero
        rec["pass"] = False
        rec["error"] = traceback.format_exc()[-3000:]
    rec["wall_s"] = round(time.perf_counter() - t0, 1)
    rec["setup"] = meter.take()
    stats = jax.devices()[0].memory_stats()
    if stats and "peak_bytes_in_use" in stats:
        rec["peak_bytes_in_use_so_far"] = stats["peak_bytes_in_use"]
    emit(rec)
    jax.clear_caches()          # drop this phase's programs and buffers
    gc.collect()                # before the next phase allocates
    return rec["pass"]


def _version(dist):
    try:
        return importlib.metadata.version(dist)
    except importlib.metadata.PackageNotFoundError:
        return None


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="toy widths on the CPU; every line is labelled and "
                         "the run cannot end in the passing verdict")
    ap.add_argument("--phases", default=None,
                    help="comma-separated subset of phases (debugging; "
                         "cannot end in the passing verdict)")
    args = ap.parse_args(argv)
    known = [name for name, _, _ in PHASES]
    wanted = known if args.phases is None else args.phases.split(",")
    unknown = [p for p in wanted if p not in known]
    if unknown:
        ap.error(f"unknown phase(s) {unknown}; phases are {known}")

    from deeplearning4j_tpu.common.compile_cache import (cache_entries,
                                                         enable_compile_cache)
    cache_dir = enable_compile_cache()
    import jax
    import jaxlib
    meter = CompileMeter()
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    header = {"chip_smoke": "rehearsal" if args.rehearsal else "start",
              "jax": jax.__version__, "jaxlib": jaxlib.__version__,
              "libtpu": _version("libtpu"), "device": device,
              "compile_cache_dir": cache_dir,
              "compile_cache_entries_before": cache_entries(cache_dir)}
    if args.rehearsal:
        if device["platform"] != "cpu":
            print(f"chip_smoke: --rehearsal is the CPU debugging aid; this "
                  f"process holds {device['platform']!r} "
                  f"(set JAX_PLATFORMS=cpu)", file=sys.stderr)
            return EXIT_NO_TPU
    elif device["platform"] != "tpu":
        # no accelerator: say why on stderr, print NO result, run no phase
        print(f"chip_smoke: no TPU — jax.devices()[0].platform is "
              f"{device['platform']!r} ({json.dumps(header)}). The smoke "
              f"only passes on the chip; `--rehearsal` runs toy widths on "
              f"the CPU.", file=sys.stderr)
        return EXIT_NO_TPU

    def emit(line):
        if args.rehearsal:
            line["rehearsal"] = True     # every line a rehearsal prints
        print(json.dumps(line), flush=True)

    emit(header)
    # native runtime: built here from the tracked source, loudly
    from deeplearning4j_tpu.common import native_ops
    built, detail = native_ops.build(force=True)
    in_use = native_ops.available()
    emit({"native_lib_built": built, "detail": detail,
          "native_code_in_use": in_use})

    cfg = REHEARSAL if args.rehearsal else FULL
    verdicts = {"native_lib": built and in_use}
    for name, fn, needs in PHASES:
        if name not in wanted:
            continue
        if len(devices) < needs:
            emit({"phase": name, "ran": False,
                  "why": f"needs {needs} devices, this machine has "
                         f"{len(devices)}"})
            continue
        verdicts[name] = run_phase(name, fn, cfg, meter, emit)

    emit({"phases": verdicts,
          "compile_cache_entries_after": cache_entries(cache_dir)})
    ok = all(verdicts.values())
    if args.rehearsal or args.phases is not None:
        # a rehearsal or a partial run is never the passing verdict
        emit({"ok": False, "device": device,
              "why": "rehearsal" if args.rehearsal
              else "partial run (--phases)", "all_phases_run_passed": ok})
        return EXIT_NOT_A_VERDICT if ok else EXIT_PHASE_FAILED
    emit({"ok": ok, "device": device})
    return 0 if ok else EXIT_PHASE_FAILED


if __name__ == "__main__":
    sys.exit(main())
