"""Benchmark driver — prints complete JSON lines, primary first.

Measures the five BASELINE.md configs on the TPU this machine holds. It
measures there or it fails: no chip, or a config that fails, is a non-zero
exit — never a CPU number under the chip metric's name. `--small` runs
reduced shapes on whatever backend JAX finds (the CPU rehearsal), and every
record it prints says so.

  1. LeNet-MNIST        MultiLayerNetwork.fit()  (conv path)
  2. ResNet-50          ComputationGraph.fit()   (primary metric)
  3. char-RNN LSTM      GravesLSTM TBPTT scan    (LSTMHelpers.java loop)
  4. Word2Vec SkipGram  batched negative-sampling kernel (AggregateSkipGram)
  5. ParallelWrapper    GSPMD data-parallel ResNet-50 step (multi-chip path;
                        on a single chip this exercises the sharded program
                        with a 1-device mesh)

plus beyond-reference extras (budget permitting, skipped first):

  6. resnet50_pipeline  ResNet-50 fit() fed by the REAL AsyncDataSetIterator
                        host->HBM path (the number users get) next to the
                        staged-batch primary
  7. flash_attention_8k Pallas flash kernel vs XLA softmax at T=8192
                        (vs_baseline = measured speedup over XLA)
  8. decode_tokens_sec  TransformerLM KV-cache decode tokens/s (batch 1 / 8)
  9. served_throughput  end-to-end serving: ContinuousDecodeServer
                        (iteration-level batching) vs static gang batching
                        over mixed-length requests, tokens/s + request
                        p50/p99 (the SLO view; serving/ subsystem)
 10. speculative_decode ContinuousDecodeServer speculative (K=4 n-gram
                        draft, one K-wide verify dispatch) vs plain
                        greedy decode on repetitive text — tokens/s,
                        acceptance rate, dispatches/token (streams
                        pinned bit-identical)
 11. paged_decode       paged block-table KV cache (serving/kvpool.py,
                        vLLM-style) vs the fixed-slot cache at EQUAL
                        ARENA BYTES, mixed lengths behind a shared
                        system prefix — max concurrent streams, prefix
                        hit rate, tokens/s (streams pinned bit-identical)
 11b. paged_speculative_decode  speculation OVER the paged cache
                        (ISSUE 10: block-table verify program) vs paged
                        plain decode, same arena both arms —
                        dispatches/token + tokens/s headline, the PR 5
                        amortization on the PR 8 memory model (streams
                        pinned bit-identical)
 11c. preempt_vs_shed   durable-KV preemption (ISSUE 11: serving/
                        kvstate.py) vs shed-only at FULL block
                        occupancy — batch-class slots spill to host and
                        resume bit-identically while interactive
                        requests take their blocks; interactive
                        goodput-under-deadline + completion p99 vs the
                        blocked/shed baseline
 12. load_sweep         production-traffic harness (serving/loadgen.py):
                        seeded Poisson arrivals at a 3-rate ladder
                        through the ContinuousDecodeServer — achieved
                        tokens/s, request p99, TTFT p99, goodput-under-
                        SLO per rate + the saturation knee; one pinned
                        sweep point per record (tools/load_sweep.py is
                        the full standalone), plus the PR 9 overload A/B
                        (chunked prefill + deadline admission) at the
                        past-knee rate

Output protocol:

  * The parent process NEVER imports jax: a chip belongs to one process at
    a time, so the parent stays off it and every config — including the
    primary — runs in its own child, one after the other, each with a hard
    timeout. A crashing config costs that config, and the exit status.
  * Every config's record carries `device`: the platform, `device_kind`
    and device count as THAT child saw them. A child that finds no TPU
    fails (unless `--small` was asked for).
  * After each config finishes, the FULL line (same primary values,
    `secondary` grown by one entry) is re-printed, flushed. Every
    printed line is a complete record; a parser taking the last JSON
    line gets the most complete result.
  * A wall-clock budget (BENCH_BUDGET_S, default 660 s) gates each
    config. The process exits non-zero if any config failed.

vs_baseline: the reference publishes no numbers (BASELINE.md). Stand-in
figures below are conservative estimates for the 2016 dl4j stack on V100
(ResNet-50: 300 img/s with cuDNN 5) / host CPU (others); they are floors to
beat, not measured reference numbers — see PERF.md for the roofline analysis
of what the TPU numbers mean.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

BASELINE_RESNET50_IMAGES_PER_SEC = 300.0     # dl4j-0.6-era V100 stand-in
BASELINE_LENET_IMAGES_PER_SEC = 3000.0       # nd4j-native host stand-in
BASELINE_CHARRNN_CHARS_PER_SEC = 20000.0     # LSTMHelpers per-step loop stand-in
BASELINE_W2V_PAIRS_PER_SEC = 500000.0        # native hogwild AggregateSkipGram stand-in
BASELINE_DECODE_TOKENS_PER_SEC = 1000.0      # rnnTimeStep-era streaming stand-in

# ResNet-50 batch-128 training step: 2.86 TFLOP by XLA cost analysis
# (PERF.md). Used for the primary's "mfu" field, divided by the peak of
# the device the config actually ran on.
RESNET50_FLOPS_PER_IMAGE = 2.86e12 / 128

# exact jax `device_kind` -> peak bf16 FLOP/s. A kind enters this table
# when a run on that device has printed its `device_kind` verbatim and the
# peak has a public source; a device that is not here is an error, not a
# default. "TPU v5 lite" is what the v5e reports (chip_smoke.py, PR 21);
# 197 TFLOP/s: Google Cloud documentation, "TPU v5e".
TPU_PEAK_BF16_FLOPS = {
    "TPU v5 lite": 197e12,
}


def _peak_flops():
    """Peak bf16 FLOP/s of the device this process runs on."""
    import jax
    kind = jax.devices()[0].device_kind
    if kind not in TPU_PEAK_BF16_FLOPS:
        raise KeyError(
            f"no peak FLOP/s recorded for device_kind {kind!r}: add it to "
            f"TPU_PEAK_BF16_FLOPS with its source before reporting mfu")
    return TPU_PEAK_BF16_FLOPS[kind]


def _interleaved_median(arms, segments=5):
    """Interleaved same-process A/B protocol (the ParallelWrapper fix
    that collapsed a fake 12% inter-process gap to 0.58%, PERF.md r5;
    now the standard for every dispatch-bound config): run SHORT timed
    segments of each arm alternating A B A B ... inside ONE process, so
    host jitter hits all arms equally, and report the per-arm MEDIAN
    over segments (robust to a single latency spike where best-of takes
    the flattering outlier and mean takes the damage).

    arms: {name: zero-arg callable returning one segment's rate}.
    Returns {name: {"median": rate, "segments": [rates...]}}."""
    import statistics
    results = {name: [] for name in arms}
    for _ in range(segments):
        for name, fn in arms.items():
            results[name].append(fn())
    return {name: {"median": round(statistics.median(v), 1),
                   "segments": [round(x, 1) for x in v]}
            for name, v in results.items()}


def _bench_net(net, x, y, warmup=2, iters=10, reps=2):
    """Best of `reps` timed segments (the first segments run slower while
    the pipeline warms)."""
    import jax

    from deeplearning4j_tpu.datasets.dataset import DataSet

    ds = DataSet(jax.device_put(x), jax.device_put(y))
    for _ in range(warmup):
        net.fit(ds)
    float(net._score)              # scalar readback: execution barrier
    best = 0.0
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(iters):
            net.fit(ds)
        float(net._score)
        dt = time.perf_counter() - t0
        best = max(best, x.shape[0] * iters / dt)
    return best


def bench_lenet(rng, small=False):
    """Primary value keeps the historical protocol (staged fit(DataSet)
    loop, comparable to the r5 record); a fused_steps A/B arm measures
    the K-batches-per-dispatch fit loop against the single-step loop,
    interleaved in the same process (both arms iterator-driven so the
    comparison isolates the dispatch batching)."""
    import jax
    import numpy as np

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.models.zoo.lenet import lenet
    batch = 64 if small else 512
    net = lenet(data_type="bfloat16")
    x = rng.random((batch, 784)).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, batch)]
    ips = _bench_net(net, x, y, warmup=1 if small else 3,
                     iters=5 if small else 30, reps=1 if small else 2)

    # fused_steps A/B: K=8 batches per device dispatch vs one-per-dispatch
    K = 8
    n_batches = K * (1 if small else 2)
    ds = DataSet(jax.device_put(x), jax.device_put(y))
    net1 = lenet(data_type="bfloat16")
    net8 = lenet(data_type="bfloat16").fused_steps(K)

    def seg(n):
        def run():
            t0 = time.perf_counter()
            n.fit(ListDataSetIterator([ds] * n_batches))
            float(n._score)
            return batch * n_batches / (time.perf_counter() - t0)
        return run

    for n in (net1, net8):
        seg(n)()                       # compile + warm staging
    ab = _interleaved_median({"fused1": seg(net1), "fused8": seg(net8)},
                             segments=3 if small else 5)
    return {"value": round(ips, 1), "unit": "images/sec",
            "config": f"batch {batch}, bf16; fused_steps A/B "
                      f"(interleaved median): fused1 "
                      f"{ab['fused1']['median']} vs fused8 "
                      f"{ab['fused8']['median']} img/s",
            "fused_ab": ab,
            "fused_speedup": round(ab["fused8"]["median"]
                                   / max(ab["fused1"]["median"], 1e-9), 3),
            "vs_baseline": round(ips / BASELINE_LENET_IMAGES_PER_SEC, 3)}


def _bench_resnet50_arm(rng, small, remat):
    import numpy as np

    from deeplearning4j_tpu.models.zoo.resnet import resnet50
    batch = 4 if small else 128
    # r3 interleaved sweep: 128 -> 2633-2641 img/s, 256 -> ~2535,
    # 192 -> ~2350 (bias-free convs + fused BN)
    net = resnet50(data_type="bfloat16", remat=remat)
    x = rng.random((batch, 224, 224, 3)).astype(np.float32)
    y = np.eye(1000, dtype=np.float32)[rng.integers(0, 1000, batch)]
    # 3 reps x 15 iters: the first timed segments run slower while the
    # pipeline warms; best-of-3 matches the interleaved steady state
    ips = _bench_net(net, x, y, warmup=1 if small else 3,
                     iters=2 if small else 15, reps=1 if small else 3)
    return ips, batch


def _add_mfu(rec, ips, small):
    """Attach "mfu" — the ONE place the peak table is consulted, so the
    primary and the remat A/B can never drift apart on the formula. A
    `--small` run is not a chip measurement and carries none."""
    if not small:
        rec["mfu"] = round(ips * RESNET50_FLOPS_PER_IMAGE / _peak_flops(), 4)
    return rec


def bench_resnet50(rng, small=False):
    ips, batch = _bench_resnet50_arm(rng, small, remat=False)
    return _add_mfu(
        {"value": round(ips, 1), "unit": "images/sec",
         "config": f"batch {batch}, 224x224, bf16",
         "vs_baseline": round(ips / BASELINE_RESNET50_IMAGES_PER_SEC, 3)},
        ips, small)


def bench_resnet50_remat(rng, small=False):
    """The r4 structural bytes/step lever, measured as its own config (a
    fresh subprocess, same protocol as the primary, so the A/B is fair):
    segment gradient checkpointing recomputes bottleneck interiors in the
    backward, trading FLOPs for HBM activation traffic — PERF.md
    roofline says the step is bandwidth-bound. Compare `value` against
    the primary record's."""
    ips, batch = _bench_resnet50_arm(rng, small, remat=True)
    return _add_mfu(
        {"value": round(ips, 1), "unit": "images/sec",
         "config": f"remat-segments, batch {batch}, 224x224, bf16 "
                   f"(A/B vs primary)",
         "vs_baseline": round(ips / BASELINE_RESNET50_IMAGES_PER_SEC, 3)},
        ips, small)


def bench_resnet50_pipeline(rng, small=False):
    """ResNet-50 fit() fed by the real AsyncDataSetIterator host->HBM
    pipeline — the number users get from fit(DataSetIterator) with async
    prefetch (AsyncDataSetIterator.java:75-76) — vs the staged-batch
    primary that isolates step time.

    Headline arm is the TPU-first wire format (r5): raw uint8 pixels +
    ImagePreProcessingScaler.device_apply on chip + bf16 label transfer —
    4x fewer host->HBM bytes than the f32 arm (the reference-default wire,
    also measured). A host->device bandwidth probe is reported next to it
    so the fed number can be read against what the link delivers."""
    import numpy as np

    from deeplearning4j_tpu.datasets.iterators import (
        ArraysDataSetIterator, AsyncDataSetIterator)
    from deeplearning4j_tpu.datasets.normalizers import (
        ImagePreProcessingScaler)
    from deeplearning4j_tpu.models.zoo.resnet import resnet50

    batch = 4 if small else 128
    n_batches = 2 if small else 6
    n = batch * n_batches
    net = resnet50(data_type="bfloat16")
    x8 = rng.integers(0, 256, (n, 224, 224, 3), dtype=np.uint8)
    y = np.eye(1000, dtype=np.float32)[rng.integers(0, 1000, n)]

    # --- wire-bandwidth probe: one staged f32 batch, timed ---
    import jax
    probe = np.ascontiguousarray(
        (x8[:batch].astype(np.float32) / 255.0))
    jax.block_until_ready(jax.device_put(probe[:1]))   # first-transfer warm
    t0 = time.perf_counter()
    jax.block_until_ready(jax.device_put(probe))
    wire_mbps = probe.nbytes / (time.perf_counter() - t0) / 1e6

    def run(make_it, epochs):
        net.fit(make_it())                     # compile + warm prefetch
        float(net._score)
        t0 = time.perf_counter()
        net.fit(make_it(), num_epochs=epochs)
        float(net._score)
        return n * epochs / (time.perf_counter() - t0)

    scaler = ImagePreProcessingScaler()
    u8_base = ArraysDataSetIterator((x8, y), batch_size=batch)
    ips = run(lambda: AsyncDataSetIterator(
        u8_base, queue_size=4, transfer_dtype="bfloat16",
        device_transform=scaler.as_device_transform("bfloat16")),
        epochs=1 if small else 2)

    xf = (x8.astype(np.float32) / 255.0)
    f32_base = ArraysDataSetIterator((xf, y), batch_size=batch)
    ips_f32 = run(lambda: AsyncDataSetIterator(f32_base, queue_size=4),
                  epochs=1)
    return {"value": round(ips, 1), "unit": "images/sec",
            "config": f"fit(AsyncDataSetIterator), uint8 wire + on-device "
                      f"scale, batch {batch}, bf16; f32-wire arm "
                      f"{ips_f32:.1f} img/s; host->device wire "
                      f"{wire_mbps:.0f} MB/s",
            "vs_baseline": round(ips / BASELINE_RESNET50_IMAGES_PER_SEC, 3)}


def _bench_char_rnn_arm(rng, small, scan_unroll):
    import jax
    import numpy as np

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.models.zoo.char_rnn import char_rnn
    V, B, T = (77, 8, 50) if small else (77, 64, 200)
    net = char_rnn(data_type="bfloat16", scan_unroll=scan_unroll)
    x = np.eye(V, dtype=np.float32)[rng.integers(0, V, (B, T))]
    y = np.eye(V, dtype=np.float32)[rng.integers(0, V, (B, T))]
    ds = DataSet(jax.device_put(x), jax.device_put(y))
    for _ in range(1 if small else 3):
        net.fit(ds)
    float(net._score)
    iters = 3 if small else 20
    cps = 0.0
    for _ in range(1 if small else 2):   # best-of-2 (see _bench_net)
        t0 = time.perf_counter()
        for _ in range(iters):
            net.fit(ds)
        float(net._score)
        dt = time.perf_counter() - t0
        cps = max(cps, B * T * iters / dt)
    return cps, B, T


def bench_char_rnn(rng, small=False):
    """Interleaved same-process fused_steps A/B (_interleaved_median):
    fused8 scans up to 8 TBPTT segments (T=200 / tbptt 50 -> the whole
    4-segment sequence) in ONE dispatch per fit, carries threaded
    through the scan; fused1 is today's one-dispatch-per-segment loop.
    Headline `value` stays the single-step number (comparable to the r5
    record); at T=50 (`--small`) the sequence is one segment and the
    arms coincide."""
    import jax
    import numpy as np

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.models.zoo.char_rnn import char_rnn
    V, B, T = (77, 8, 50) if small else (77, 64, 200)
    x = np.eye(V, dtype=np.float32)[rng.integers(0, V, (B, T))]
    y = np.eye(V, dtype=np.float32)[rng.integers(0, V, (B, T))]
    ds = DataSet(jax.device_put(x), jax.device_put(y))
    net1 = char_rnn(data_type="bfloat16")
    net8 = char_rnn(data_type="bfloat16").fused_steps(8)
    iters = 3 if small else 20

    def seg(n):
        def run():
            t0 = time.perf_counter()
            for _ in range(iters):
                n.fit(ds)
            float(n._score)
            return B * T * iters / (time.perf_counter() - t0)
        return run

    for n in (net1, net8):       # compile both programs off the clock
        n.fit(ds)
        float(n._score)
    ab = _interleaved_median({"fused1": seg(net1), "fused8": seg(net8)},
                             segments=3 if small else 5)
    # headline keeps the HISTORICAL best-of protocol (max over segments,
    # = r5's best-of-reps) so vs_baseline stays comparable across
    # captures; the A/B comparison uses the interleaved MEDIANS
    cps = max(ab["fused1"]["segments"])
    return {"value": round(cps, 0), "unit": "chars/sec",
            "config": f"2x200 GravesLSTM, batch {B}, seq {T}, tbptt 50, "
                      f"bf16; fused_steps A/B (interleaved median): "
                      f"fused1 {ab['fused1']['median']} vs fused8 "
                      f"{ab['fused8']['median']} chars/s",
            "fused_ab": ab,
            "fused_speedup": round(ab["fused8"]["median"]
                                   / max(ab["fused1"]["median"], 1e-9), 3),
            "vs_baseline": round(cps / BASELINE_CHARRNN_CHARS_PER_SEC, 3)}


def bench_char_rnn_unroll(rng, small=False):
    """A/B vs `char_rnn_lstm`: lax.scan unroll=8 fuses 8 timesteps per
    loop body — the obvious LSTM lever for the per-step loop the scan
    replaces (LSTMHelpers.java:157-171). Identical numerics; compare
    `value` against the char_rnn_lstm record's."""
    cps, B, T = _bench_char_rnn_arm(rng, small, scan_unroll=8)
    return {"value": round(cps, 0), "unit": "chars/sec",
            "config": f"2x200 GravesLSTM scan-unroll=8, batch {B}, seq {T}, "
                      f"tbptt 50, bf16 (A/B vs char_rnn_lstm)",
            "vs_baseline": round(cps / BASELINE_CHARRNN_CHARS_PER_SEC, 3)}


def bench_word2vec(rng, small=False):
    """Interleaved same-process A/B (_interleaved_median) over the
    dispatch-batching lever itself — batch_pairs 65536 vs 4096 flushes
    (the AggregateSkipGram-style K-pairs-per-native-call knob): short
    alternating segments on identical sequence chunks, median-of-k
    per arm, so host jitter cannot fake a swing between captures.
    Headline `value` = the 65536 arm's best segment (the
    historical best-of protocol, comparable across captures); the A/B
    comparison uses the medians."""
    import jax
    import numpy as np

    from deeplearning4j_tpu.models.embeddings.learning import SkipGram
    from deeplearning4j_tpu.models.embeddings.lookup_table import \
        InMemoryLookupTable
    from deeplearning4j_tpu.models.word2vec.vocab import VocabCache

    V, D = (2000, 50) if small else (10000, 100)
    vocab = VocabCache()
    for i in range(V):
        vocab.add_token(f"w{i}", count=int(rng.zipf(1.5)))
    vocab.finish()

    from deeplearning4j_tpu.common import native_ops
    # touching the library BEFORE the timed loop: a cold checkout would
    # otherwise pay the one-time `make` inside rep 0's timing window
    native_available = native_ops.available()

    def make_arm(batch_pairs):
        table = InMemoryLookupTable(vocab, vector_length=D, seed=1,
                                    negative=5, use_hs=False)
        table.reset_weights()
        sg = SkipGram(batch_pairs=batch_pairs)
        sg.configure(vocab, table, window=5, negative=5, use_hs=False,
                     seed=1)
        return sg

    arms = {"batch65536": make_arm(65536), "batch4096": make_arm(4096)}
    segments = 3 if small else 5
    per_seg = 120 if small else 640
    n_seqs = 100 + segments * per_seg
    seqs = [rng.integers(0, V, 40).tolist() for _ in range(n_seqs)]
    for sg in arms.values():        # warm: compile both flush programs
        for s in seqs[:100]:
            sg.learn_sequence(s, 0.025)
        sg._flush(force=True)
        jax.block_until_ready(sg._syn0)
    seg_idx = {name: [0] for name in arms}

    def seg(name, sg):
        def run():
            i = seg_idx[name][0]
            seg_idx[name][0] += 1
            # both arms consume the SAME chunk per segment (fair A/B)
            chunk = seqs[100 + per_seg * i:100 + per_seg * (i + 1)]
            base = sg._flushed_pairs
            t0 = time.perf_counter()
            # corpus-chunk path: C++ pair generation feeding the batched
            # TPU kernel (numpy fallback without the toolchain) — the
            # path SequenceVectors.fit drives
            for j in range(0, len(chunk), 256):
                sg.learn_sequences_batch(chunk[j:j + 256], 0.025)
            sg._flush(force=True)
            jax.block_until_ready(sg._syn0)
            return (sg._flushed_pairs - base) / (time.perf_counter() - t0)
        return run

    ab = _interleaved_median(
        {name: seg(name, sg) for name, sg in arms.items()},
        segments=segments)
    # headline = best segment of the 65536 arm (the historical best-of
    # protocol, comparable to the r5 record); medians drive the A/B
    pps = max(ab["batch65536"]["segments"])
    gen = ("native pairgen" if native_available
           else "numpy pairgen (no native lib)")
    return {"value": round(pps, 0), "unit": "pairs/sec",
            "config": f"V={V}, dim {D}, neg 5, {gen}; flush-batch A/B "
                      f"(interleaved median): 65536 "
                      f"{ab['batch65536']['median']} vs 4096 "
                      f"{ab['batch4096']['median']} pairs/s",
            "flush_ab": ab,
            "vs_baseline": round(pps / BASELINE_W2V_PAIRS_PER_SEC, 3)}


def bench_flash_attention(rng, small=False):
    """Long-context attention: the Pallas flash kernel vs XLA's softmax
    lowering at T=8192 (beyond-reference workload — the 2016 stack predates
    attention; vs_baseline reports the measured speedup over XLA)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops import flash_attention
    from deeplearning4j_tpu.parallel.ring_attention import \
        blockwise_attention

    if small:
        # the Pallas kernel needs a real TPU (interpreter mode is minutes
        # at any useful T); keep the record honest instead of fake-fast
        return {"skipped": "flash kernel requires TPU (--small run)"}

    B, T, H, D = 4, 8192, 8, 64
    mk = lambda: jnp.asarray(rng.standard_normal((B, T, H, D)),
                             jnp.bfloat16)
    q, k, v = mk(), mk(), mk()

    def timed(fn):
        f = jax.jit(lambda q, k, v: jnp.sum(fn(q, k, v)
                                            .astype(jnp.float32)))
        float(f(q, k, v))
        best = 1e9
        for _ in range(2):
            t0 = time.perf_counter()
            for _ in range(10):
                s = f(q, k, v)
            float(s)
            best = min(best, (time.perf_counter() - t0) / 10)
        return best

    t_flash = timed(lambda q, k, v: flash_attention(q, k, v, True))
    t_xla = timed(lambda q, k, v: blockwise_attention(q, k, v, causal=True))
    tok_s = B * T / t_flash
    return {"value": round(tok_s, 0), "unit": "tokens/sec",
            "config": f"causal flash attention B={B} T={T} H={H} D={D} "
                      f"bf16; XLA softmax {t_xla * 1e3:.1f} ms vs "
                      f"flash {t_flash * 1e3:.1f} ms",
            "vs_baseline": round(t_xla / t_flash, 3)}


def bench_decode(rng, small=False):
    """KV-cache incremental decode throughput — the attention-era
    equivalent of the reference's O(1)-per-step streaming inference
    (MultiLayerNetwork.rnnTimeStep, MultiLayerNetwork.java:2196).

    Interleaved same-process protocol (_interleaved_median): batch-1 and
    batch-8 segments alternate so a host hiccup cannot skew one arm, and
    every generate_batch call's wall time becomes a LATENCY SAMPLE —
    p50/p99 per-token latency is reported per batch size next to the
    throughput (a serving SLO is a percentile, not a mean)."""
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.models.zoo.transformer import TransformerLM

    V, L, D, H = (256, 2, 128, 4) if small else (512, 4, 512, 8)
    steps = 16 if small else 128
    lm = TransformerLM(V, d_model=D, n_heads=H, n_layers=L,
                       max_len=max(steps + 16, 64), dtype=jnp.bfloat16)
    prompts = {b: rng.integers(0, V, (b, 8)).astype(np.int32)
               for b in (1, 8)}
    for p in prompts.values():     # compile both programs off the clock
        lm.generate_batch(p, max_new_tokens=steps)
    lat_ms = {b: [] for b in prompts}    # per-CALL per-token latency

    def seg(batch):
        prompt = prompts[batch]
        calls = 3 if small else 5

        def run():
            t0 = time.perf_counter()
            for _ in range(calls):
                c0 = time.perf_counter()
                lm.generate_batch(prompt, max_new_tokens=steps)
                lat_ms[batch].append(
                    (time.perf_counter() - c0) * 1e3 / steps)
            return batch * steps * calls / (time.perf_counter() - t0)
        return run

    ab = _interleaved_median({"batch1": seg(1), "batch8": seg(8)},
                             segments=3 if small else 5)

    def pct(samples, q):
        return round(float(np.percentile(np.asarray(samples), q)), 3)

    rec = {"value": ab["batch8"]["median"], "unit": "tokens/sec",
           "config": f"KV-cache decode (one on-device scan program), "
                     f"TransformerLM L={L} d={D}, {steps} new tokens, "
                     f"interleaved median; batch1="
                     f"{ab['batch1']['median']} tok/s",
           "decode_ab": ab,
           "vs_baseline": round(ab["batch8"]["median"]
                                / BASELINE_DECODE_TOKENS_PER_SEC, 3)}
    for b in (1, 8):
        rec[f"p50_ms_per_token_batch{b}"] = pct(lat_ms[b], 50)
        rec[f"p99_ms_per_token_batch{b}"] = pct(lat_ms[b], 99)
        rec[f"latency_samples_batch{b}"] = len(lat_ms[b])
    return rec


def bench_served(rng, small=False):
    """End-to-end SERVING throughput: the ContinuousDecodeServer
    (iteration-level batching, serving/decode.py) against the same
    machinery in static gang-batching mode, over a mixed-length request
    stream — the workload shape where continuous batching earns its keep.
    Interleaved same-process protocol; request-level p50/p99 come from
    the servers' own ServingMetrics (a serving SLO is a percentile).
    CPU-backend numbers + protocol in PERF.md; tools/serve_ab.py is the
    richer standalone version of this config."""
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.models.zoo.transformer import TransformerLM
    from deeplearning4j_tpu.serving import ContinuousDecodeServer

    V, L, D, H = (96, 2, 32, 2) if small else (512, 4, 256, 8)
    max_len = 64 if small else 160
    slots = 4 if small else 8
    # the backlog must stay several waves deep or both schedulers converge
    # (continuous earns its margin REFILLING slots from a queue)
    n_req = 16 if small else 24
    lm = TransformerLM(V, d_model=D, n_heads=H, n_layers=L,
                       max_len=max_len, dtype=jnp.float32)
    # 100 ms request SLO on CPU: attainment/goodput-under-SLO come out of
    # the PR 6 ServingMetrics counters next to raw tokens/s, so the
    # ROADMAP traffic-harness round starts from a pinned metric
    slo_ms = 100.0
    from deeplearning4j_tpu.serving import ServingMetrics
    servers = {
        "continuous": ContinuousDecodeServer(
            lm, slots=slots, prompt_buckets=(8, 16),
            max_queue=4 * n_req,
            metrics=ServingMetrics(slo_target_ms=slo_ms)).start(),
        "static": ContinuousDecodeServer(
            lm, slots=slots, prompt_buckets=(8, 16), max_queue=4 * n_req,
            static_batching=True,
            metrics=ServingMetrics(slo_target_ms=slo_ms)).start(),
    }

    def workload(seed, n):
        r = np.random.default_rng(seed)
        return [(r.integers(1, V, int(r.integers(3, 16))).tolist(),
                 int(r.integers(4, max_len - 16 - 4)))
                for _ in range(n)]

    for srv in servers.values():       # compile off the clock
        for p, n in workload(0, 4):
            srv.generate(p, n, timeout=300)
    # SLO baseline after warm-up: the counters are all-time, and the
    # warm requests' compile latency is a guaranteed SLO miss that must
    # not deflate the measured attainment
    base = {n: servers[n].metrics.snapshot() for n in servers}

    seg_idx = {name: [0] for name in servers}

    def seg(name):
        srv = servers[name]

        def run():
            work = workload(100 + seg_idx[name][0], n_req)
            seg_idx[name][0] += 1
            toks = sum(n for _, n in work)
            t0 = time.perf_counter()
            for f in [srv.submit(p, n) for p, n in work]:
                f.result(600)
            return toks / (time.perf_counter() - t0)
        return run

    ab = _interleaved_median({n: seg(n) for n in servers},
                             segments=3 if small else 5)
    snaps = {n: servers[n].metrics.snapshot() for n in servers}
    for srv in servers.values():
        srv.stop()
    rec = {"value": ab["continuous"]["median"], "unit": "tokens/sec",
           "config": f"ContinuousDecodeServer L={L} d={D} slots={slots}, "
                     f"mixed prompts/decode lengths, {n_req} reqs/seg, "
                     f"interleaved median vs static gang batching",
           "serving_ab": ab,
           "continuous_over_static": round(
               ab["continuous"]["median"] / ab["static"]["median"], 3),
           "vs_baseline": round(ab["continuous"]["median"]
                                / BASELINE_DECODE_TOKENS_PER_SEC, 3)}
    from deeplearning4j_tpu.obs.registry import fmt
    from deeplearning4j_tpu.serving.metrics import slo_view
    for n, s in snaps.items():
        rec[f"p50_request_ms_{n}"] = fmt(s["latency_ms_p50"])
        rec[f"p99_request_ms_{n}"] = fmt(s["latency_ms_p99"])
        rec[f"occupancy_{n}"] = fmt(s["batch_occupancy_mean"])
        view = slo_view(s, ab[n]["median"], base[n])
        rec[f"slo_attainment_{n}"] = view["attainment"]
        rec[f"goodput_tokens_per_sec_{n}"] = view.get(
            "goodput_tokens_per_sec")
    rec["slo_ms"] = slo_ms
    return rec


def bench_speculative(rng, small=False):
    """Speculative vs plain greedy decode through the REAL
    ContinuousDecodeServer (serving/speculate.py): same model, same slot
    machinery, same per-segment workload — the spec arm adds a K=4
    n-gram prompt-lookup draft (zero extra model, zero extra dispatch)
    whose drafts are verified in ONE K-wide dispatch. Token streams are
    pinned bit-identical (tests/test_speculative.py — acceptance by
    exact argmax match), so the A/B isolates pure dispatch amortization.

    Workload is REPETITIVE text (short cyclic patterns the model is
    briefly trained to continue) — the prompt-lookup regime (code,
    templated text, quoting prompts); acceptance rate and
    dispatches/token are reported so the number can be read against the
    workload's self-similarity."""
    import numpy as np

    from deeplearning4j_tpu.models.zoo.transformer import TransformerLM
    from deeplearning4j_tpu.serving import (ContinuousDecodeServer,
                                            NGramDraft, Speculator)

    V, L, D, H = (96, 2, 32, 2) if small else (256, 4, 256, 8)
    max_len = 96 if small else 160
    slots = 4 if small else 8
    n_req = 16 if small else 24
    train_steps = 60 if small else 150
    lm = TransformerLM(V, d_model=D, n_heads=H, n_layers=L,
                       max_len=max_len, seed=5, learning_rate=0.3)
    # teach short-cycle continuation (off the clock): a few tiny steps
    # stand in for "trained model on self-similar text"
    T = 32
    r = np.random.default_rng(0)
    for _ in range(train_steps):
        xs = []
        for _ in range(16):
            pat = r.integers(1, V, int(r.integers(2, 5))).tolist()
            xs.append((pat * (T // len(pat) + 2))[:T + 1])
        xs = np.asarray(xs, np.int32)
        lm.fit_batch(xs[:, :-1], xs[:, 1:])

    def workload(seed, n):
        rr = np.random.default_rng(seed)
        out = []
        for _ in range(n):
            pat = rr.integers(1, V, int(rr.integers(2, 5))).tolist()
            p = (pat * 8)[:int(rr.integers(6, 16))]
            out.append((p, int(rr.integers(16, max_len - 16 - 4))))
        return out

    slo_ms = 100.0
    from deeplearning4j_tpu.serving import ServingMetrics
    servers = {
        "speculative": ContinuousDecodeServer(
            lm, slots=slots, prompt_buckets=(8, 16), max_queue=4 * n_req,
            speculate=Speculator(NGramDraft(n=3), k=4),
            metrics=ServingMetrics(slo_target_ms=slo_ms)).start(),
        "plain": ContinuousDecodeServer(
            lm, slots=slots, prompt_buckets=(8, 16),
            max_queue=4 * n_req,
            metrics=ServingMetrics(slo_target_ms=slo_ms)).start(),
    }
    for srv in servers.values():       # compile off the clock
        for p, n in workload(0, 4):
            srv.generate(p, n, timeout=300)
    # SLO baseline after warm-up (see bench_served)
    base = {n: servers[n].metrics.snapshot() for n in servers}

    seg_idx = {name: [0] for name in servers}

    def seg(name):
        srv = servers[name]

        def run():
            work = workload(100 + seg_idx[name][0], n_req)
            seg_idx[name][0] += 1
            toks = sum(n for _, n in work)
            t0 = time.perf_counter()
            for f in [srv.submit(p, n) for p, n in work]:
                f.result(600)
            return toks / (time.perf_counter() - t0)
        return run

    ab = _interleaved_median({n: seg(n) for n in servers},
                             segments=3 if small else 5)
    snaps = {n: servers[n].metrics.snapshot() for n in servers}
    for srv in servers.values():
        srv.stop()
    rec = {"value": ab["speculative"]["median"], "unit": "tokens/sec",
           "config": f"ContinuousDecodeServer L={L} d={D} slots={slots}, "
                     f"n-gram draft K=4, repetitive-text workload, "
                     f"{n_req} reqs/seg, interleaved median vs plain "
                     f"decode (streams bit-identical)",
           "speculative_ab": ab,
           "speedup_spec_over_plain": round(
               ab["speculative"]["median"] / ab["plain"]["median"], 3),
           "vs_baseline": round(ab["speculative"]["median"]
                                / BASELINE_DECODE_TOKENS_PER_SEC, 3)}
    from deeplearning4j_tpu.obs.registry import fmt
    from deeplearning4j_tpu.serving.metrics import slo_view
    for n, s in snaps.items():
        rec[f"p50_request_ms_{n}"] = fmt(s["latency_ms_p50"])
        rec[f"p99_request_ms_{n}"] = fmt(s["latency_ms_p99"])
        rec[f"dispatches_per_token_{n}"] = fmt(
            s["dispatches_per_token"], 4)
        view = slo_view(s, ab[n]["median"], base[n])
        rec[f"slo_attainment_{n}"] = view["attainment"]
        rec[f"goodput_tokens_per_sec_{n}"] = view.get(
            "goodput_tokens_per_sec")
    rec["slo_ms"] = slo_ms
    s = snaps["speculative"]
    rec["acceptance_rate"] = fmt(s["spec_acceptance_rate_mean"], 4)
    rec["accepted_per_dispatch"] = fmt(
        s["spec_accepted_per_dispatch_mean"], 3)
    return rec


def bench_paged_decode(rng, small=False):
    """Paged block-table KV cache vs the fixed-slot cache through the
    REAL ContinuousDecodeServer at EQUAL ARENA BYTES (serving/kvpool.py
    + the zoo's paged programs; tools/serve_ab.py `paged_vs_fixed` is
    the richer standalone). Fixed mode reserves slots x max_len rows up
    front, so its concurrency IS its slot count; paged mode holds the
    same rows as free-listed blocks, slots become a scheduling width,
    and admission gates on blocks actually reserved. The workload —
    mixed lengths behind one shared system prefix, stored once by the
    prefix cache — is the shape real traffic has. Streams are pinned
    bit-identical and paging adds zero decode dispatches per token
    (tests/test_paged.py), so the A/B isolates CONCURRENCY at fixed
    memory: max live streams is the headline next to tokens/s."""
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.models.zoo.transformer import TransformerLM
    from deeplearning4j_tpu.serving import (ContinuousDecodeServer,
                                            ServingMetrics)

    V, L, D, H = (96, 2, 32, 2) if small else (512, 4, 256, 8)
    max_len = 64 if small else 160
    fixed_slots = 4 if small else 8
    bs = 8 if small else 16
    n_blocks = fixed_slots * max_len // bs      # EQUAL arena rows
    paged_slots = 4 * fixed_slots
    n_req = 16 if small else 32
    n_prefix = 16
    bucket = 24 if small else 32
    dec_hi = 28 if small else 60
    lm = TransformerLM(V, d_model=D, n_heads=H, n_layers=L,
                       max_len=max_len, dtype=jnp.float32)
    sys_prefix = np.random.default_rng(7).integers(
        1, V, n_prefix).tolist()

    def workload(seed, n):
        r = np.random.default_rng(seed)
        return [(sys_prefix
                 + r.integers(1, V, int(r.integers(1, 8))).tolist(),
                 int(r.integers(4, dec_hi))) for _ in range(n)]

    slo_ms = 100.0
    servers = {
        "paged": ContinuousDecodeServer(
            lm, slots=paged_slots, prompt_buckets=(bucket,),
            max_queue=4 * n_req, paged=True, block_size=bs,
            n_blocks=n_blocks,
            metrics=ServingMetrics(slo_target_ms=slo_ms)).start(),
        "fixed": ContinuousDecodeServer(
            lm, slots=fixed_slots, prompt_buckets=(bucket,),
            max_queue=4 * n_req,
            metrics=ServingMetrics(slo_target_ms=slo_ms)).start(),
    }
    for srv in servers.values():       # compile off the clock
        for p, n in workload(0, 4):
            srv.generate(p, n, timeout=300)
    base = {n: servers[n].metrics.snapshot() for n in servers}

    seg_idx = {name: [0] for name in servers}

    def seg(name):
        srv = servers[name]

        def run():
            work = workload(100 + seg_idx[name][0], n_req)
            seg_idx[name][0] += 1
            toks = sum(n for _, n in work)
            t0 = time.perf_counter()
            for f in [srv.submit(p, n) for p, n in work]:
                f.result(600)
            return toks / (time.perf_counter() - t0)
        return run

    ab = _interleaved_median({n: seg(n) for n in servers},
                             segments=3 if small else 5)
    snaps = {n: servers[n].metrics.snapshot() for n in servers}
    for srv in servers.values():
        srv.stop()
    streams = {n: snaps[n]["live_streams_max"] for n in snaps}
    p = snaps["paged"]
    rec = {"value": ab["paged"]["median"], "unit": "tokens/sec",
           "config": f"ContinuousDecodeServer L={L} d={D}, equal arena "
                     f"{n_blocks * bs} KV rows: fixed {fixed_slots} "
                     f"slots x {max_len} vs paged {n_blocks} blocks x "
                     f"{bs} (slots={paged_slots} scheduling width), "
                     f"{n_prefix}-token shared prefix, {n_req} reqs/seg",
           "paged_ab": ab,
           "paged_over_fixed": round(
               ab["paged"]["median"] / ab["fixed"]["median"], 3),
           "max_concurrent_streams": streams,
           "streams_paged_over_fixed": round(
               streams["paged"] / max(1, streams["fixed"]), 2),
           "blocks_in_use_max": p["blocks_in_use_max"],
           "pool_blocks": p["pool_blocks"],
           "blocked_on_memory": p["blocked_on_memory"],
           "vs_baseline": round(ab["paged"]["median"]
                                / BASELINE_DECODE_TOKENS_PER_SEC, 3)}
    from deeplearning4j_tpu.obs.registry import fmt
    from deeplearning4j_tpu.serving.metrics import slo_view
    rec["prefix_hit_rate"] = fmt(p["prefix_hit_rate"], 4)
    rec["dispatches_per_token"] = {
        n: fmt(snaps[n]["dispatches_per_token"], 4) for n in snaps}
    for n, s in snaps.items():
        view = slo_view(s, ab[n]["median"], base[n])
        rec[f"slo_attainment_{n}"] = view["attainment"]
        rec[f"goodput_tokens_per_sec_{n}"] = view.get(
            "goodput_tokens_per_sec")
    rec["slo_ms"] = slo_ms
    return rec


def bench_paged_speculative(rng, small=False):
    """Speculative decode OVER the paged KV cache vs paged plain decode
    (ISSUE 10: the block-table verify program — the PR 5 dispatch
    amortization re-measured on the PR 8 memory model;
    tools/serve_ab.py `paged_spec_vs_paged` is the richer standalone).
    BOTH arms run the identical paged server config (block-table arena,
    shared system prefix stored once, slots a scheduling width); only
    the spec arm drafts (K=4 n-gram prompt-lookup) and verifies K
    tokens per dispatch through `make_paged_verify_fn`. Streams are
    pinned bit-identical (tests/test_paged.py), so the headline is
    dispatches/token vs the paged baseline next to tokens/s."""
    import numpy as np

    from deeplearning4j_tpu.models.zoo.transformer import TransformerLM
    from deeplearning4j_tpu.serving import (ContinuousDecodeServer,
                                            NGramDraft, ServingMetrics,
                                            Speculator)

    V, L, D, H = (96, 2, 32, 2) if small else (256, 4, 256, 8)
    max_len = 96 if small else 160
    slots = 8 if small else 16
    bs = 8 if small else 16
    n_blocks = (48 if small else 80)     # arena rows = n_blocks * bs
    n_req = 16 if small else 24
    train_steps = 60 if small else 150
    lm = TransformerLM(V, d_model=D, n_heads=H, n_layers=L,
                       max_len=max_len, seed=5, learning_rate=0.3)
    T = 32
    r = np.random.default_rng(0)
    for _ in range(train_steps):        # off the clock: cycle continuation
        xs = []
        for _ in range(16):
            pat = r.integers(1, V, int(r.integers(2, 5))).tolist()
            xs.append((pat * (T // len(pat) + 2))[:T + 1])
        xs = np.asarray(xs, np.int32)
        lm.fit_batch(xs[:, :-1], xs[:, 1:])
    sys_prefix = np.random.default_rng(7).integers(1, V, 16).tolist()

    def workload(seed, n):
        rr = np.random.default_rng(seed)
        out = []
        for _ in range(n):
            pat = rr.integers(1, V, int(rr.integers(2, 5))).tolist()
            p = sys_prefix + (pat * 8)[:int(rr.integers(4, 15))]
            out.append((p, int(rr.integers(16, 41))))
        return out

    slo_ms = 100.0
    paged_kw = dict(slots=slots, prompt_buckets=(32,),
                    max_queue=4 * n_req, paged=True, block_size=bs,
                    n_blocks=n_blocks)
    servers = {
        "paged_spec": ContinuousDecodeServer(
            lm, speculate=Speculator(NGramDraft(n=3), k=4),
            metrics=ServingMetrics(slo_target_ms=slo_ms),
            **paged_kw).start(),
        "paged": ContinuousDecodeServer(
            lm, metrics=ServingMetrics(slo_target_ms=slo_ms),
            **paged_kw).start(),
    }
    for srv in servers.values():       # compile off the clock
        for p, n in workload(0, 4):
            srv.generate(p, n, timeout=300)
    base = {n: servers[n].metrics.snapshot() for n in servers}

    seg_idx = {name: [0] for name in servers}

    def seg(name):
        srv = servers[name]

        def run():
            work = workload(100 + seg_idx[name][0], n_req)
            seg_idx[name][0] += 1
            toks = sum(n for _, n in work)
            t0 = time.perf_counter()
            for f in [srv.submit(p, n) for p, n in work]:
                f.result(600)
            return toks / (time.perf_counter() - t0)
        return run

    ab = _interleaved_median({n: seg(n) for n in servers},
                             segments=3 if small else 5)
    snaps = {n: servers[n].metrics.snapshot() for n in servers}
    for srv in servers.values():
        srv.stop()
    s = snaps["paged_spec"]
    dpt = {n: snaps[n]["dispatches_per_token"] for n in snaps}
    rec = {"value": ab["paged_spec"]["median"], "unit": "tokens/sec",
           "config": f"ContinuousDecodeServer L={L} d={D}, BOTH arms "
                     f"paged {n_blocks} blocks x {bs} (slots={slots} "
                     f"scheduling width), 16-token shared prefix + "
                     f"repetitive prompts, n-gram draft K=4 on the "
                     f"spec arm, {n_req} reqs/seg (streams "
                     f"bit-identical)",
           "paged_spec_ab": ab,
           "speedup_spec_over_paged": round(
               ab["paged_spec"]["median"] / ab["paged"]["median"], 3),
           "dispatches_per_token_ratio": round(
               dpt["paged_spec"] / dpt["paged"], 3),
           "vs_baseline": round(ab["paged_spec"]["median"]
                                / BASELINE_DECODE_TOKENS_PER_SEC, 3)}
    from deeplearning4j_tpu.obs.registry import fmt
    from deeplearning4j_tpu.serving.metrics import slo_view
    for n, snp in snaps.items():
        rec[f"dispatches_per_token_{n}"] = fmt(dpt[n], 4)
        rec[f"p50_request_ms_{n}"] = fmt(snp["latency_ms_p50"])
        rec[f"p99_request_ms_{n}"] = fmt(snp["latency_ms_p99"])
        rec[f"live_streams_max_{n}"] = snp["live_streams_max"]
        view = slo_view(snp, ab[n]["median"], base[n])
        rec[f"slo_attainment_{n}"] = view["attainment"]
        rec[f"goodput_tokens_per_sec_{n}"] = view.get(
            "goodput_tokens_per_sec")
    rec["slo_ms"] = slo_ms
    rec["acceptance_rate"] = fmt(s["spec_acceptance_rate_mean"], 4)
    rec["accepted_per_dispatch"] = fmt(
        s["spec_accepted_per_dispatch_mean"], 3)
    rec["prefix_hit_rate"] = fmt(s["prefix_hit_rate"], 4)
    rec["cow_copies"] = s["cow_copies"]
    return rec


def bench_fused_decode(rng, small=False):
    """Fused decode windows vs per-iteration dispatch (ISSUE 18:
    `fused_serve=K` — `lax.scan` runs K serve iterations on-device in
    ONE dispatch, static slot membership inside the window;
    tools/serve_ab.py `fused_serve_vs_plain` is the richer standalone).
    BOTH arms run the identical paged server config; only the fused arm
    scans K=4 iterations per dispatch. Streams are pinned bit-identical
    (tests/test_fused_serve.py) and there is no model-dependence
    (unlike speculation there is no acceptance rate), so the headline
    is the pure dispatch amortization: dispatches/token at 1/K of the
    unfused baseline (decode lengths ≡ 1 mod K keep every window full)
    next to tokens/s."""
    import numpy as np

    from deeplearning4j_tpu.models.zoo.transformer import TransformerLM
    from deeplearning4j_tpu.serving import (ContinuousDecodeServer,
                                            ServingMetrics)

    K = 4
    V, L, D, H = (96, 2, 32, 2) if small else (256, 4, 256, 8)
    max_len = 64 if small else 160
    slots = 16
    bs = 8 if small else 16
    n_blocks = 48 if small else 80
    n_req = 16 if small else 24
    # every choice ≡ 1 (mod K): prefill emits token 1, the remaining
    # n_new - 1 iterations divide evenly into full K-windows
    dec_choices = (17, 21, 25, 29, 33) if small else (33, 41, 49, 57)
    lm = TransformerLM(V, d_model=D, n_heads=H, n_layers=L,
                       max_len=max_len, seed=5)
    sys_prefix = np.random.default_rng(7).integers(1, V, 16).tolist()

    def workload(seed, n):
        rr = np.random.default_rng(seed)
        out = []
        for _ in range(n):
            own = rr.integers(1, V, int(rr.integers(1, 8))).tolist()
            out.append((sys_prefix + own, int(rr.choice(dec_choices))))
        return out

    slo_ms = 100.0
    paged_kw = dict(slots=slots, prompt_buckets=(24,),
                    max_queue=4 * n_req, paged=True, block_size=bs,
                    n_blocks=n_blocks)
    servers = {
        "fused": ContinuousDecodeServer(
            lm, fused_serve=K,
            metrics=ServingMetrics(slo_target_ms=slo_ms),
            **paged_kw).start(),
        "plain": ContinuousDecodeServer(
            lm, metrics=ServingMetrics(slo_target_ms=slo_ms),
            **paged_kw).start(),
    }
    for srv in servers.values():       # compile off the clock
        for p, n in workload(0, 4):
            srv.generate(p, n, timeout=300)
    base = {n: servers[n].metrics.snapshot() for n in servers}

    seg_idx = {name: [0] for name in servers}

    def seg(name):
        srv = servers[name]

        def run():
            work = workload(100 + seg_idx[name][0], n_req)
            seg_idx[name][0] += 1
            toks = sum(n for _, n in work)
            t0 = time.perf_counter()
            for f in [srv.submit(p, n) for p, n in work]:
                f.result(600)
            return toks / (time.perf_counter() - t0)
        return run

    ab = _interleaved_median({n: seg(n) for n in servers},
                             segments=3 if small else 5)
    snaps = {n: servers[n].metrics.snapshot() for n in servers}
    for srv in servers.values():
        srv.stop()
    dpt = {n: snaps[n]["dispatches_per_token"] for n in snaps}
    rec = {"value": ab["fused"]["median"], "unit": "tokens/sec",
           "config": f"ContinuousDecodeServer L={L} d={D}, BOTH arms "
                     f"paged {n_blocks} blocks x {bs} (slots={slots} "
                     f"scheduling width), 16-token shared prefix, "
                     f"decode lengths ≡1 mod {K}, fused_serve={K} on "
                     f"the fused arm, {n_req} reqs/seg (streams "
                     f"bit-identical)",
           "fused_ab": ab,
           "speedup_fused_over_plain": round(
               ab["fused"]["median"] / ab["plain"]["median"], 3),
           "dispatches_per_token_ratio": round(
               dpt["fused"] / dpt["plain"], 3) if dpt["plain"] else None,
           "target_ratio": round(1.0 / K, 3),
           "fused_windows": snaps["fused"]["fused_windows"],
           "vs_baseline": round(ab["fused"]["median"]
                                / BASELINE_DECODE_TOKENS_PER_SEC, 3)}
    from deeplearning4j_tpu.obs.registry import fmt
    from deeplearning4j_tpu.serving.metrics import slo_view
    for n, snp in snaps.items():
        rec[f"dispatches_per_token_{n}"] = fmt(dpt[n], 4)
        rec[f"iterations_per_dispatch_{n}"] = fmt(
            snp["iterations_per_dispatch"], 3)
        rec[f"p50_request_ms_{n}"] = fmt(snp["latency_ms_p50"])
        rec[f"p99_request_ms_{n}"] = fmt(snp["latency_ms_p99"])
        view = slo_view(snp, ab[n]["median"], base[n])
        rec[f"slo_attainment_{n}"] = view["attainment"]
        rec[f"goodput_tokens_per_sec_{n}"] = view.get(
            "goodput_tokens_per_sec")
    rec["slo_ms"] = slo_ms
    return rec


def bench_preempt_vs_shed(rng, small=False):
    """Durable-KV preemption A/B (ISSUE 11): at FULL block occupancy,
    interactive-class goodput-under-deadline with preemption (batch
    slots spill to host, resume bit-identically) vs the shed-only
    baseline where blocked interactive work can only wait out the batch
    or die at its deadline. tools/serve_ab.py `preempt_vs_shed` is the
    implementation (client-side per-class accounting); the headline is
    the preempt arm's interactive goodput with the ratio over shed-only
    alongside — the acceptance bar is ratio > 1 (strictly more
    interactive tokens landed in-deadline than shedding alone)."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    from serve_ab import bench_preempt_ab

    segments = 3 if small else 5
    body, snaps, _ = bench_preempt_ab(segments,
                                      reqs_per_seg=8 if small else 12)
    ab = body["ab"]
    return {"value": ab["preempt"]["median"],
            "unit": "interactive goodput tokens/sec (within deadline)",
            "config": body["config"] + f", {segments} segments",
            "preempt_ab": ab,
            "interactive_goodput_preempt_over_shed":
                body["interactive_goodput_preempt_over_shed"],
            "interactive_completion_ms":
                body["interactive_completion_ms"],
            "preempted": body["preempted"]["preempt"],
            "resumed": body["resumed"]["preempt"],
            "spill_bytes": body["spill_bytes"]["preempt"],
            "sheds": body["sheds"]}


def bench_load_sweep(rng, small=False):
    """One pinned traffic-harness sweep point (the ISSUE 7 acceptance
    metric): seeded open-loop Poisson arrivals through the REAL
    ContinuousDecodeServer at a 3-rate ladder spanning under-load to
    past-saturation, reporting per rate what `tools/load_sweep.py`
    reports — achieved tokens/s, request p50/p99, TTFT p99, SLO
    attainment, goodput-under-SLO — plus the saturation knee. The
    headline value is the achieved tokens/s at the knee (the highest
    SUSTAINED rate), which is the capacity number raw-backlog A/Bs
    overstate: arrivals pay queueing, backlogs don't."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools"))
    from load_sweep import sweep_decode

    if small:
        lm, rates, n_req, slots = None, (60.0, 240.0, 960.0), 32, 4
    else:
        import jax.numpy as jnp

        from deeplearning4j_tpu.models.zoo.transformer import \
            TransformerLM
        lm = TransformerLM(512, d_model=256, n_heads=8, n_layers=4,
                           max_len=160, dtype=jnp.float32)
        rates, n_req, slots = (100.0, 400.0, 1600.0), 48, 8
    body, _snap = sweep_decode(rates, n_req=n_req, slo_ms=150.0, seed=0,
                               tracer=None, lm=lm, slots=slots)
    # overload-control arm (PR 9): the TOP (past-knee) rate replayed
    # with chunked prefill + deadline-aware admission — the goodput
    # those levers recover is the record's robustness read-out
    # seed offset: sweep_decode seeds rung i with seed+i, so the
    # single-rate controlled replay must start where the baseline's TOP
    # rung landed — otherwise the A/B compares different schedules
    body_c, _ = sweep_decode((rates[-1],), n_req=n_req, slo_ms=150.0,
                             seed=len(rates) - 1, tracer=None, lm=lm,
                             slots=slots, chunked_prefill=8,
                             admission=True)
    pts, knee = body["curve"], body["knee"]
    pinned = next((p for p in pts
                   if p["offered_rate_target"]
                   == knee["knee_offered_rate"]), pts[0])
    slo = pinned.get("slo") or {}
    rec = {"value": pinned["tokens_per_sec"], "unit": "tokens/sec",
           "config": body["config"] + f", Poisson rates {rates} rps, "
                     f"pinned point = knee",
           "knee": knee,
           "pinned_offered_rps": pinned["offered_rate_target"],
           "pinned_p99_request_ms": pinned["latency_ms"]["p99"],
           "pinned_ttft_ms_p99": pinned.get("ttft_ms_p99"),
           "pinned_slo_attainment": slo.get("attainment"),
           "pinned_goodput_tokens_per_sec": slo.get(
               "goodput_tokens_per_sec"),
           "curve": [{
               "offered_rps": p["offered_rate_target"],
               "offered_tokens_per_sec":
                   p["schedule"]["offered_tokens_per_sec"],
               "tokens_per_sec": p["tokens_per_sec"],
               "sustained_ratio": p.get("sustained_ratio"),
               "p50_ms": p["latency_ms"]["p50"],
               "p99_ms": p["latency_ms"]["p99"],
               "ttft_ms_p99": p.get("ttft_ms_p99"),
               "attainment": (p.get("slo") or {}).get("attainment"),
               "goodput_tokens_per_sec":
                   (p.get("slo") or {}).get("goodput_tokens_per_sec"),
               "shed": p["shed_at_submit"],
               "sheds": p.get("sheds")} for p in pts],
           "vs_baseline": round(pinned["tokens_per_sec"]
                                / BASELINE_DECODE_TOKENS_PER_SEC, 3)}
    ctrl = body_c["curve"][0]
    rec["overload_ab"] = {
        "offered_rps": rates[-1],
        "controlled": "chunked_prefill=8 + deadline-aware admission "
                      "(deadline = SLO)",
        "goodput_tokens_per_sec": {
            "baseline": (pts[-1].get("slo") or {}).get(
                "goodput_tokens_per_sec"),
            "controlled": (ctrl.get("slo") or {}).get(
                "goodput_tokens_per_sec")},
        "ttft_ms_p99": {"baseline": pts[-1].get("ttft_ms_p99"),
                        "controlled": ctrl.get("ttft_ms_p99")},
        "sheds_controlled": ctrl.get("sheds")}
    return rec


def bench_parallel_wrapper(rng, small=False):
    import jax
    import numpy as np

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.models.zoo.resnet import resnet50
    from deeplearning4j_tpu.parallel.parallel_wrapper import ParallelWrapper

    n_dev = len(jax.devices())
    batch = (4 if small else 128) * n_dev
    net = resnet50(data_type="bfloat16")
    pw = (ParallelWrapper.Builder(net)
          .workers(n_dev).averaging_frequency(1).build())
    x = rng.random((batch, 224, 224, 3)).astype(np.float32)
    y = np.eye(1000, dtype=np.float32)[rng.integers(0, 1000, batch)]
    # stage once: steady-state input feeding is double-buffered off the timed
    # path (AsyncDataSetIterator role; bench_resnet50_pipeline measures the
    # fed path) — this config isolates the sharded training step
    ds = DataSet(jax.device_put(x), jax.device_put(y))
    for _ in range(1 if small else 2):
        pw.fit(ds)
    float(net._score)
    iters = 2 if small else 10
    t0 = time.perf_counter()
    for _ in range(iters):
        pw.fit(ds)
    float(net._score)
    dt = time.perf_counter() - t0
    ips = batch * iters / dt
    return {"value": round(ips, 1), "unit": "images/sec",
            "config": f"GSPMD allreduce, {n_dev} device(s), "
                      f"global batch {batch}, bf16",
            "vs_baseline": round(
                ips / (BASELINE_RESNET50_IMAGES_PER_SEC * n_dev), 3)}


# name -> (bench fn, conservative compile+run seconds);
# ORDER IS PRIORITY under the time budget: round-mandated A/B first, then
# the BASELINE configs cheapest-first, beyond-reference extras last
# (skipped first); consumed by main() AND run_single_config
SECONDARY_CONFIGS = {
    # FIRST: the round-4 mandated A/B (VERDICT r3 item 3) — measured
    # before the cheap configs so a tight budget cannot skip it.
    # Estimates are r5 on-chip measurements WITH the shared compilation
    # cache (pre-cache values were ~2x these and made the 660 s driver
    # budget skip the last two configs).
    "resnet50_remat": (bench_resnet50_remat, 120),
    # estimates below grew with the r6 interleaved A/B protocol (each
    # config now times two arms x 5 segments in one process)
    "lenet_mnist": (bench_lenet, 90),
    "char_rnn_lstm": (bench_char_rnn, 120),
    "word2vec_skipgram": (bench_word2vec, 90),
    "decode_tokens_sec": (bench_decode, 100),
    "served_throughput": (bench_served, 110),
    "speculative_decode": (bench_speculative, 120),
    # paged KV cache (ISSUE 8): concurrency at equal arena bytes —
    # max live streams + tokens/s, paged vs fixed-slot cache
    "paged_decode": (bench_paged_decode, 110),
    # speculation over the paged cache (ISSUE 10): dispatches/token +
    # tokens/s vs the paged baseline — the PR 5 amortization on the
    # PR 8 memory model (the production configuration)
    "paged_speculative_decode": (bench_paged_speculative, 120),
    # fused decode windows (ISSUE 18): K serve iterations scanned into
    # one dispatch — dispatches/token at 1/K of the unfused paged
    # baseline
    "fused_decode": (bench_fused_decode, 110),
    # durable-KV preemption (ISSUE 11): interactive goodput-under-
    # deadline at full block occupancy, preempt vs shed-only — the
    # robustness lever queue-depth admission cannot supply
    "preempt_vs_shed": (bench_preempt_vs_shed, 100),
    # the traffic-harness pinned sweep point (ISSUE 7): arrivals +
    # queueing, not backlog replay — knee + goodput-under-SLO per
    # record, plus the PR 9 overload-control goodput A/B at the top rate
    "load_sweep": (bench_load_sweep, 130),
    "resnet50_fit_pipeline": (bench_resnet50_pipeline, 150),
    "flash_attention_8k": (bench_flash_attention, 110),
    "parallel_wrapper_resnet50": (bench_parallel_wrapper, 120),
    # LAST (skipped first): the unroll A/B duplicates perf_sweep.py's
    # richer 1/4/8/16 sweep — measured r5 on chip: unroll=1 wins, so this
    # config only re-confirms the default
    "char_rnn_lstm_unroll": (bench_char_rnn_unroll, 90),
}

_NO_TPU_RC = 4      # child exit status: found no TPU and no --small


def _run_config_subprocess(name, timeout, small=False):
    """Run one config in a fresh child process and return its record.

    ONE PROCESS PER CHIP: the chip belongs to one process at a time, and a
    parent that has touched JAX holds it — a child that needs it then fails
    or hangs. This parent therefore never imports jax, and runs one child
    at a time; each child takes the chip, measures, and gives it back by
    exiting. (Isolation is the second reason: dispatch-bound configs
    measured in-process after the big ResNet program ran up to 5x slower,
    r3: standalone w2v 3.5M pairs/s vs 0.5-0.6M in-process.)

    The children share one persistent compilation cache
    (common/compile_cache.py, enabled in run_single_config), so the A/B
    and pipeline configs reuse the primary's programs. A child that exits
    non-zero, prints no record, or times out yields {"error": ...}."""
    argv = [sys.executable, os.path.abspath(__file__), "--config", name]
    if small:
        argv.append("--small")
    try:
        p = subprocess.run(
            argv, capture_output=True, text=True, timeout=timeout,
            cwd=os.path.dirname(os.path.abspath(__file__)))
    except subprocess.TimeoutExpired:
        return {"error": f"config timed out after {timeout:.0f}s"}
    if p.returncode == _NO_TPU_RC:
        sys.exit(p.stderr.strip())   # every other child would refuse too
    if p.returncode == 0:
        for line in reversed(p.stdout.strip().splitlines()):
            try:
                return json.loads(line)
            except ValueError:
                continue
    return {"error": f"rc={p.returncode}: {(p.stderr or p.stdout)[-300:]}"}


def main(small=False):
    t_start = time.perf_counter()
    budget_s = float(os.environ.get("BENCH_BUDGET_S", "660"))
    deadline = t_start + budget_s
    batch = 4 if small else 128
    record = {
        "metric": f"ResNet-50 train images/sec (batch {batch}, 224x224, "
                  f"bf16)",
        "value": 0.0, "unit": "images/sec", "vs_baseline": 0.0,
        "secondary": {},
    }
    if small:
        record["small"] = ("reduced shapes on whatever backend JAX found — "
                           "a rehearsal, not a chip measurement")
    failed = []

    def emit():
        print(json.dumps(record), flush=True)

    # --- primary FIRST, in its own child ---
    res = _run_config_subprocess(
        "resnet50", timeout=min(deadline - time.perf_counter(), 300),
        small=small)
    if "value" in res:
        record.update({k: res[k] for k in ("value", "vs_baseline", "mfu",
                                           "device") if k in res})
        record["status"] = "primary complete"
    else:
        failed.append("resnet50")
        record["status"] = f"primary failed: {res.get('error', res)!s:.300}"
    emit()

    # --- secondaries in priority order, each gated by the remaining budget ---
    for name, (_, est_s) in SECONDARY_CONFIGS.items():
        remaining = deadline - time.perf_counter()
        if remaining < (30 if small else est_s):
            record["secondary"][name] = {
                "skipped": f"time budget ({remaining:.0f}s left < "
                           f"{est_s}s estimate)"}
            emit()
            continue
        res = _run_config_subprocess(
            name, timeout=min(remaining, est_s * 2.5), small=small)
        record["secondary"][name] = res
        if "error" in res:
            failed.append(name)
        emit()
    if failed:
        print(f"bench: {len(failed)} config(s) failed: {', '.join(failed)}",
              file=sys.stderr)
    return 1 if failed else 0


def run_single_config(name, small=False):
    """Child-process entry: take the chip, run one config, print its record
    with the device it ran on. Anything but a TPU is refused unless the
    caller asked for the labelled small-shapes run."""
    from deeplearning4j_tpu.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    import numpy as np
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu" and not small:
        print(f"bench: config {name!r} found platform {dev.platform!r}, not "
              f"a TPU; refusing to measure (use --small for the labelled "
              f"small-shapes rehearsal)", file=sys.stderr)
        sys.exit(_NO_TPU_RC)
    rng = np.random.default_rng(0)
    fn = (bench_resnet50 if name == "resnet50"
          else SECONDARY_CONFIGS[name][0])
    rec = fn(rng, small=small)
    rec["device"] = device
    if small:
        rec["small"] = True
    print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    _small = "--small" in sys.argv[1:]
    if len(sys.argv) >= 3 and sys.argv[1] == "--config":
        run_single_config(sys.argv[2], small=_small)
    else:
        sys.exit(main(small=_small))
