"""Serving-layer A/B on the CPU backend (no chip needed).

Two questions the serving subsystem (`deeplearning4j_tpu/serving/`)
exists to answer, measured through the REAL servers with the interleaved
same-process protocol (bench.py `_interleaved_median`: alternating short
segments, median per arm — host jitter hits both arms equally):

  * decode_continuous_vs_static — the SAME fixed-slot decode machinery
    with iteration-level scheduling (requests join/leave at token
    granularity, Orca) vs gang admission (a new batch only forms when
    every slot is free — classic static request batching). Mixed decode
    lengths are the point: under static batching a 4-token reply's slot
    idles while a 28-token reply finishes; continuous refills it.
  * speculative_vs_plain — the SAME continuous-batching scheduler with a
    K=4 n-gram prompt-lookup draft verified in one K-wide dispatch
    (serving/speculate.py) vs plain one-token-per-dispatch decode, on
    repetitive text. Token streams are pinned bit-identical;
    the A/B isolates dispatch amortization (dispatches/token, acceptance
    rate reported next to tokens/s).
  * paged_vs_fixed — the SAME continuous-decode scheduler over the paged
    block-table KV cache (serving/kvpool.py, `paged=True`) vs the
    fixed-slot cache, at EQUAL ARENA BYTES: fixed reserves
    slots x max_len rows up front, paged holds the same rows as
    free-listed blocks with slot count a pure scheduling width. The
    workload is mixed-length requests behind one shared system prefix
    (the dominant real-traffic shape), so the paged arm also exercises
    prefix reuse. Token streams are pinned bit-identical
    (tests/test_paged.py); the A/B isolates CONCURRENCY: max live
    streams (live_streams_max) and tokens/s at the same memory.
  * paged_spec_vs_paged — the SAME paged server config with and without
    a K=4 n-gram draft verified through the BLOCK-TABLE verify program
    (ISSUE 10: `make_paged_verify_fn` — speculation over the paged KV
    cache, the two biggest serving wins composed). Streams pinned
    bit-identical; the A/B isolates dispatch amortization on the paged
    layout (dispatches/token vs the paged baseline, acceptance, and the
    equal-arena concurrency class that must survive speculation).
  * fused_serve_vs_plain — the SAME paged continuous-decode scheduler
    with and without fused decode windows (ISSUE 18: `fused_serve=4` —
    `lax.scan` runs K=4 serve iterations on-device in ONE dispatch,
    static slot membership inside the window, admissions/evictions at
    window boundaries). Streams are pinned bit-identical
    (tests/test_fused_serve.py); the A/B isolates pure dispatch
    amortization on the dispatch-bound config: decode lengths are
    chosen ≡ 1 (mod K) so every window retires exactly K iterations
    and dispatches/token lands at 1/K of the unfused paged baseline,
    with tokens/s at parity or better even on compute-bound CPU.
  * preempt_vs_shed — durable-KV preemption (ISSUE 11: serving/
    kvstate.py) vs shed-only overload handling at FULL BLOCK OCCUPANCY:
    both arms run the same paged server with a brownout class ranking
    and the same workload — three long batch-class requests each
    reserving half the block pool (two resident cover it; the third
    sustains the pressure), then a stream of short deadline-carrying
    interactive requests. The shed-only arm's interactive
    requests park on the memory gate until the batch work completes or
    their deadlines expire; the preempt arm spills a batch slot to host
    (resumed later bit-identically) and admits them. The A/B isolates
    what preemption buys: INTERACTIVE-class goodput-under-deadline and
    completion p99 (a tight TTFT bound — interactive requests are 4
    tokens) at the occupancy regime queue-depth admission cannot help.
  * affinity_vs_least_backlog — the SAME seeded shared-system-prompt
    schedule (SharedPrefixMix) through two 2-replica paged fleets:
    FleetManager prefix-affinity routing (consistent-hash the block-
    aligned prefix key, load-aware spill, fleet prefix tier pulls) vs
    the least-backlog baseline (ISSUE 20). The A/B isolates what
    stickiness buys — fleet prefix hit rate (baseline decays toward
    ~1/N) at goodput parity or better; routing verdicts and pull
    counters reported alongside.
  * overload_vs_baseline — the SAME seeded past-knee arrival schedule
    (serving/loadgen.py, NOT a backlog: overload is a queueing
    phenomenon) through an uncontrolled decode server vs one with
    chunked prefill + deadline-aware admission (PR 9,
    serving/admission.py). The controlled arm sheds predicted deadline
    misses at enqueue instead of letting the queue eat the SLO, so the
    A/B isolates GOODPUT-under-SLO at saturation — raw tokens/s is the
    number overload control deliberately spends (shed breakdown
    reported per cause next to it).
  * microbatch_vs_per_request — InferenceServer's adaptive micro-batching
    (Clipper) vs the bare per-request `output()` loop the reference
    shipped. Dispatch-overhead-dominated small models are exactly the
    serving regime: N/8 batched dispatches beat N solo dispatches.
  * tracing_on_vs_off — the SAME continuous-decode scheduler with the
    obs tracer enabled vs disabled (the shipping default). Disabled is a
    few attribute checks per iteration (nanosecond-scale, pinned by
    tests/test_obs.py) — this arm bounds even the ENABLED cost, and pins
    that tracing adds zero device dispatches (dispatch counters must
    match across arms for the same workload).

Every arm reports deadline attainment and goodput-under-SLO
(`--slo-ms`, default 100 ms request SLO) next to raw tokens/s — the
pinned starting metric for the ROADMAP traffic-harness round. Metrics
read-outs are None-guarded through the shared `obs.registry.fmt` helper
(empty reservoirs report None, not a crash). `--report PATH` writes the
combined tools/obs_report.py view (host-span timeline + metrics
snapshots, plus the tracing arm's Chrome trace alongside).

Run:  JAX_PLATFORMS=cpu python tools/serve_ab.py [--segments N]
These are XLA:CPU counts and timings at toy width — not speed results
(ROADMAP S2 measures the server on the chip).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# the ONE protocol implementation (see tools/fused_ab.py)
from bench import _interleaved_median as _interleaved  # noqa: E402
from deeplearning4j_tpu.obs.registry import fmt  # noqa: E402
# the ONE attainment/goodput implementation (shared with bench.py)
from deeplearning4j_tpu.serving.metrics import \
    slo_view as _slo_view  # noqa: E402
# the ONE shed-reason breakdown (PR 9; shared with loadgen/bench.py)
from deeplearning4j_tpu.serving.metrics import \
    shed_view as _shed_view  # noqa: E402


def _lm():
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.zoo.transformer import TransformerLM
    return TransformerLM(96, d_model=32, n_heads=2, n_layers=2,
                         max_len=64, seed=5, dtype=jnp.float32)


def _mlp():
    from deeplearning4j_tpu import (InputType, MultiLayerNetwork,
                                    NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    conf = (NeuralNetConfiguration.Builder().seed(7)
            .updater("adam").learning_rate(0.01).list()
            .layer(0, DenseLayer(n_out=64, activation="relu"))
            .layer(1, OutputLayer(n_out=10, activation="softmax",
                                  loss_function="mcxent"))
            .set_input_type(InputType.feed_forward(32))
            .build())
    return MultiLayerNetwork(conf).init()


def _decode_workload(rng, n):
    """Mixed sequence lengths — prompts spanning two buckets, decode
    lengths 4..43 (the spread static batching pays for)."""
    out = []
    for _ in range(n):
        p_len = int(rng.integers(3, 16))
        n_new = int(rng.integers(4, 44))
        out.append((rng.integers(1, 96, p_len).tolist(), n_new))
    return out


def bench_decode_ab(segments, reqs_per_seg=16, slo_ms=100.0):
    """continuous vs static decode batching: same model params, same slot
    program, same per-segment workload — only the SCHEDULER differs."""
    import numpy as np

    from deeplearning4j_tpu.serving import (ContinuousDecodeServer,
                                            ServingMetrics)

    lm = _lm()
    servers = {
        "continuous": ContinuousDecodeServer(
            lm, slots=4, prompt_buckets=(8, 16), max_queue=256,
            metrics=ServingMetrics(slo_target_ms=slo_ms)).start(),
        "static": ContinuousDecodeServer(
            lm, slots=4, prompt_buckets=(8, 16), max_queue=256,
            static_batching=True,
            metrics=ServingMetrics(slo_target_ms=slo_ms)).start(),
    }
    warm = _decode_workload(np.random.default_rng(0), 6)
    for srv in servers.values():        # compile off the clock
        for p, n in warm:
            srv.generate(p, n, timeout=120)
    # SLO baseline after warm-up: compile-latency misses stay off the books
    base = {n: servers[n].metrics.snapshot() for n in servers}

    seg_idx = {"continuous": [0], "static": [0]}

    def seg(name):
        srv = servers[name]

        def run():
            # identical per-segment workload for both arms, fresh per
            # segment index so neither arm replays a cached rng stream
            rng = np.random.default_rng(100 + seg_idx[name][0])
            seg_idx[name][0] += 1
            work = _decode_workload(rng, reqs_per_seg)
            toks = sum(n for _, n in work)
            t0 = time.perf_counter()
            futs = [srv.submit(p, n) for p, n in work]
            for f in futs:
                f.result(300)
            return toks / (time.perf_counter() - t0)
        return run

    ab = _interleaved({n: seg(n) for n in servers}, segments=segments)
    lat = {n: servers[n].metrics.snapshot() for n in servers}
    for srv in servers.values():
        srv.stop()
    return {
        "config": "TransformerLM L=2 d=32 slots=4, mixed prompts 3-15 / "
                  "decode 4-43 tokens, 16 reqs/segment, greedy",
        "unit": "generated tokens/sec",
        "ab": ab,
        "speedup_continuous_over_static": round(
            ab["continuous"]["median"] / ab["static"]["median"], 3),
        "request_latency_ms": {
            n: {"p50": fmt(lat[n]["latency_ms_p50"]),
                "p99": fmt(lat[n]["latency_ms_p99"])} for n in lat},
        "slot_occupancy_mean": {
            n: fmt(lat[n]["batch_occupancy_mean"]) for n in lat},
        "slo_ms": slo_ms,
        "slo": {n: _slo_view(lat[n], ab[n]["median"], base[n])
                for n in lat},
    }, lat, None


def bench_paged_ab(segments, reqs_per_seg=16, slo_ms=100.0):
    """paged vs fixed-slot decode cache at EQUAL ARENA BYTES: fixed =
    4 slots x 64 rows; paged = 32 blocks x 8 rows (the same 256 KV rows)
    with slots=16 as pure scheduling width. Requests share a 16-token
    system prefix (two full blocks — stored once in the paged arm) and
    spread over mixed prompt/decode lengths, so fixed mode is bounded by
    4 worst-case slots while paged admission is bounded by rows actually
    reserved. Streams are pinned bit-identical (tests/test_paged.py);
    here we measure what paging buys: max concurrent streams at the same
    memory, and the tokens/s that concurrency carries."""
    import numpy as np

    from deeplearning4j_tpu.serving import (ContinuousDecodeServer,
                                            ServingMetrics)

    lm = _lm()                          # max_len=64
    sys_prefix = np.random.default_rng(7).integers(1, 96, 16).tolist()

    def workload(rng, n):
        out = []
        for _ in range(n):
            own = rng.integers(1, 96, int(rng.integers(1, 8))).tolist()
            out.append((sys_prefix + own, int(rng.integers(4, 28))))
        return out

    servers = {
        "paged": ContinuousDecodeServer(
            lm, slots=16, prompt_buckets=(24,), max_queue=256,
            paged=True, block_size=8, n_blocks=32,
            metrics=ServingMetrics(slo_target_ms=slo_ms)).start(),
        "fixed": ContinuousDecodeServer(
            lm, slots=4, prompt_buckets=(24,), max_queue=256,
            metrics=ServingMetrics(slo_target_ms=slo_ms)).start(),
    }
    warm = workload(np.random.default_rng(0), 6)
    for srv in servers.values():        # compile off the clock
        for p, n in warm:
            srv.generate(p, n, timeout=120)
    # SLO baseline after warm-up: compile-latency misses stay off the books
    base = {n: servers[n].metrics.snapshot() for n in servers}

    seg_idx = {name: [0] for name in servers}

    def seg(name):
        srv = servers[name]

        def run():
            rng = np.random.default_rng(100 + seg_idx[name][0])
            seg_idx[name][0] += 1
            work = workload(rng, reqs_per_seg)
            toks = sum(n for _, n in work)
            t0 = time.perf_counter()
            futs = [srv.submit(p, n) for p, n in work]
            for f in futs:
                f.result(300)
            return toks / (time.perf_counter() - t0)
        return run

    ab = _interleaved({n: seg(n) for n in servers}, segments=segments)
    snaps = {n: servers[n].metrics.snapshot() for n in servers}
    for srv in servers.values():
        srv.stop()
    p = snaps["paged"]
    streams = {n: snaps[n]["live_streams_max"] for n in snaps}
    return {
        "config": "TransformerLM L=2 d=32, EQUAL ARENA (256 KV rows): "
                  "fixed 4 slots x 64 rows vs paged 32 blocks x 8 rows "
                  "(slots=16 scheduling width), 16-token shared system "
                  "prefix + mixed own prompts 1-7 / decode 4-27, "
                  "16 reqs/segment, greedy",
        "unit": "generated tokens/sec",
        "ab": ab,
        "speedup_paged_over_fixed": round(
            ab["paged"]["median"] / ab["fixed"]["median"], 3),
        "max_concurrent_streams": streams,
        "streams_paged_over_fixed": round(
            streams["paged"] / max(1, streams["fixed"]), 2),
        "arena_rows": {"paged": 32 * 8, "fixed": 4 * 64},
        "blocks_in_use_max": p["blocks_in_use_max"],
        "pool_blocks": p["pool_blocks"],
        "prefix_hit_rate": fmt(p["prefix_hit_rate"], 4),
        "cow_copies": p["cow_copies"],
        "blocked_on_memory": p["blocked_on_memory"],
        "dispatches_per_token": {
            n: fmt(snaps[n]["dispatches_per_token"], 4) for n in snaps},
        "request_latency_ms": {
            n: {"p50": fmt(snaps[n]["latency_ms_p50"]),
                "p99": fmt(snaps[n]["latency_ms_p99"])} for n in snaps},
        "slo_ms": slo_ms,
        "slo": {n: _slo_view(snaps[n], ab[n]["median"], base[n])
                for n in snaps},
    }, snaps, None


def bench_speculative_ab(segments, reqs_per_seg=16, slo_ms=100.0):
    """speculative vs plain greedy decode through the continuous-batching
    server: same model, same slot machinery, same per-segment workload —
    only the spec arm drafts (K=4 n-gram prompt-lookup) and verifies K
    tokens per dispatch. Streams are pinned bit-identical
    (tests/test_speculative.py), so the A/B isolates dispatch
    amortization: watch dispatches/token and acceptance next to tokens/s.
    Workload is repetitive text (short cyclic patterns the model is
    briefly trained to continue) — the prompt-lookup regime."""
    import numpy as np

    from deeplearning4j_tpu.models.zoo.transformer import TransformerLM
    from deeplearning4j_tpu.serving import (ContinuousDecodeServer,
                                            NGramDraft, ServingMetrics,
                                            Speculator)

    V, max_len = 96, 96
    lm = TransformerLM(V, d_model=32, n_heads=2, n_layers=2,
                       max_len=max_len, seed=5, learning_rate=0.3)
    T = 32
    r = np.random.default_rng(0)
    for _ in range(60):                 # off the clock: cycle continuation
        xs = []
        for _ in range(16):
            pat = r.integers(1, V, int(r.integers(2, 5))).tolist()
            xs.append((pat * (T // len(pat) + 2))[:T + 1])
        xs = np.asarray(xs, np.int32)
        lm.fit_batch(xs[:, :-1], xs[:, 1:])

    def workload(rng, n):
        out = []
        for _ in range(n):
            pat = rng.integers(1, V, int(rng.integers(2, 5))).tolist()
            p = (pat * 8)[:int(rng.integers(6, 16))]
            out.append((p, int(rng.integers(16, max_len - 16 - 4))))
        return out

    servers = {
        "speculative": ContinuousDecodeServer(
            lm, slots=4, prompt_buckets=(8, 16), max_queue=256,
            speculate=Speculator(NGramDraft(n=3), k=4),
            metrics=ServingMetrics(slo_target_ms=slo_ms)).start(),
        "plain": ContinuousDecodeServer(
            lm, slots=4, prompt_buckets=(8, 16), max_queue=256,
            metrics=ServingMetrics(slo_target_ms=slo_ms)).start(),
    }
    warm = workload(np.random.default_rng(0), 6)
    for srv in servers.values():        # compile off the clock
        for p, n in warm:
            srv.generate(p, n, timeout=120)
    # SLO baseline after warm-up: compile-latency misses stay off the books
    base = {n: servers[n].metrics.snapshot() for n in servers}

    seg_idx = {name: [0] for name in servers}

    def seg(name):
        srv = servers[name]

        def run():
            rng = np.random.default_rng(100 + seg_idx[name][0])
            seg_idx[name][0] += 1
            work = workload(rng, reqs_per_seg)
            toks = sum(n for _, n in work)
            t0 = time.perf_counter()
            futs = [srv.submit(p, n) for p, n in work]
            for f in futs:
                f.result(300)
            return toks / (time.perf_counter() - t0)
        return run

    ab = _interleaved({n: seg(n) for n in servers}, segments=segments)
    snaps = {n: servers[n].metrics.snapshot() for n in servers}
    for srv in servers.values():
        srv.stop()
    s = snaps["speculative"]
    return {
        "config": "TransformerLM L=2 d=32 slots=4 (trained on cyclic "
                  "patterns), n-gram draft K=4, repetitive prompts 6-15 / "
                  "decode 16-75 tokens, 16 reqs/segment, greedy",
        "unit": "generated tokens/sec",
        "ab": ab,
        "speedup_spec_over_plain": round(
            ab["speculative"]["median"] / ab["plain"]["median"], 3),
        "dispatches_per_token": {
            n: fmt(snaps[n]["dispatches_per_token"], 4) for n in snaps},
        "acceptance_rate": fmt(s["spec_acceptance_rate_mean"], 4),
        "accepted_per_dispatch": fmt(
            s["spec_accepted_per_dispatch_mean"], 3),
        "request_latency_ms": {
            n: {"p50": fmt(snaps[n]["latency_ms_p50"]),
                "p99": fmt(snaps[n]["latency_ms_p99"])} for n in snaps},
        "slo_ms": slo_ms,
        "slo": {n: _slo_view(snaps[n], ab[n]["median"], base[n])
                for n in snaps},
    }, snaps, None


def bench_paged_spec_ab(segments, reqs_per_seg=16, slo_ms=100.0):
    """paged+speculative vs paged plain decode (ISSUE 10): the SAME
    paged server config — block-table arena, 16-token shared system
    prefix stored once, slots a pure scheduling width — with and
    without a K=4 n-gram draft verified through the BLOCK-TABLE verify
    program (`make_paged_verify_fn`). Streams are pinned bit-identical
    (tests/test_paged.py), so the A/B isolates dispatch amortization ON
    the paged layout: the PR 5 win (dispatches/token 0.32 -> 0.14)
    re-measured over the PR 8 memory model, the two serving wins
    composed. Workload is repetitive text behind the shared prefix (the
    prompt-lookup regime on the real-traffic shape); watch
    dispatches/token spec vs plain (target <= 0.6x), tokens/s (>=
    parity on compute-bound CPU), and live_streams_max (the equal-arena
    concurrency class must survive speculation)."""
    import numpy as np

    from deeplearning4j_tpu.models.zoo.transformer import TransformerLM
    from deeplearning4j_tpu.serving import (ContinuousDecodeServer,
                                            NGramDraft, ServingMetrics,
                                            Speculator)

    V, max_len = 96, 96
    lm = TransformerLM(V, d_model=32, n_heads=2, n_layers=2,
                       max_len=max_len, seed=5, learning_rate=0.3)
    T = 32
    r = np.random.default_rng(0)
    for _ in range(60):                 # off the clock: cycle continuation
        xs = []
        for _ in range(16):
            pat = r.integers(1, V, int(r.integers(2, 5))).tolist()
            xs.append((pat * (T // len(pat) + 2))[:T + 1])
        xs = np.asarray(xs, np.int32)
        lm.fit_batch(xs[:, :-1], xs[:, 1:])
    sys_prefix = np.random.default_rng(7).integers(1, V, 16).tolist()

    def workload(rng, n):
        out = []
        for _ in range(n):
            pat = rng.integers(1, V, int(rng.integers(2, 5))).tolist()
            p = sys_prefix + (pat * 8)[:int(rng.integers(4, 15))]
            out.append((p, int(rng.integers(16, 41))))
        return out

    paged_kw = dict(slots=16, prompt_buckets=(32,), max_queue=256,
                    paged=True, block_size=8, n_blocks=48)
    servers = {
        "paged_spec": ContinuousDecodeServer(
            lm, speculate=Speculator(NGramDraft(n=3), k=4),
            metrics=ServingMetrics(slo_target_ms=slo_ms),
            **paged_kw).start(),
        "paged": ContinuousDecodeServer(
            lm, metrics=ServingMetrics(slo_target_ms=slo_ms),
            **paged_kw).start(),
    }
    warm = workload(np.random.default_rng(0), 6)
    for srv in servers.values():        # compile off the clock
        for p, n in warm:
            srv.generate(p, n, timeout=120)
    # SLO baseline after warm-up: compile-latency misses stay off the books
    base = {n: servers[n].metrics.snapshot() for n in servers}

    seg_idx = {name: [0] for name in servers}

    def seg(name):
        srv = servers[name]

        def run():
            rng = np.random.default_rng(100 + seg_idx[name][0])
            seg_idx[name][0] += 1
            work = workload(rng, reqs_per_seg)
            toks = sum(n for _, n in work)
            t0 = time.perf_counter()
            futs = [srv.submit(p, n) for p, n in work]
            for f in futs:
                f.result(300)
            return toks / (time.perf_counter() - t0)
        return run

    ab = _interleaved({n: seg(n) for n in servers}, segments=segments)
    snaps = {n: servers[n].metrics.snapshot() for n in servers}
    for srv in servers.values():
        srv.stop()
    s = snaps["paged_spec"]
    dpt = {n: snaps[n]["dispatches_per_token"] for n in snaps}
    return {
        "config": "TransformerLM L=2 d=32 (trained on cyclic patterns), "
                  "BOTH arms paged 48 blocks x 8 rows (slots=16 "
                  "scheduling width), 16-token shared system prefix + "
                  "repetitive own prompts 4-14 / decode 16-40, n-gram "
                  "draft K=4 on the spec arm, 16 reqs/segment, greedy",
        "unit": "generated tokens/sec",
        "ab": ab,
        "speedup_spec_over_paged": round(
            ab["paged_spec"]["median"] / ab["paged"]["median"], 3),
        "dispatches_per_token": {n: fmt(dpt[n], 4) for n in dpt},
        "dispatches_per_token_ratio": round(
            dpt["paged_spec"] / dpt["paged"], 3),
        "acceptance_rate": fmt(s["spec_acceptance_rate_mean"], 4),
        "accepted_per_dispatch": fmt(
            s["spec_accepted_per_dispatch_mean"], 3),
        "max_concurrent_streams": {
            n: snaps[n]["live_streams_max"] for n in snaps},
        "prefix_hit_rate": {
            n: fmt(snaps[n]["prefix_hit_rate"], 4) for n in snaps},
        "cow_copies": {n: snaps[n]["cow_copies"] for n in snaps},
        "blocked_on_memory": {
            n: snaps[n]["blocked_on_memory"] for n in snaps},
        "request_latency_ms": {
            n: {"p50": fmt(snaps[n]["latency_ms_p50"]),
                "p99": fmt(snaps[n]["latency_ms_p99"])} for n in snaps},
        "slo_ms": slo_ms,
        "slo": {n: _slo_view(snaps[n], ab[n]["median"], base[n])
                for n in snaps},
    }, snaps, None


def bench_fused_serve_ab(segments, reqs_per_seg=16, slo_ms=100.0):
    """fused windows vs plain iteration dispatch (ISSUE 18): the SAME
    paged server config — block-table arena, 16-token shared system
    prefix, slots a pure scheduling width — with and without
    `fused_serve=4` (K serve iterations scanned into one device
    dispatch, static slot membership inside the window). Streams are
    pinned bit-identical (tests/test_fused_serve.py), so the A/B
    isolates dispatch amortization with NO model-dependence (unlike
    speculation there is no acceptance rate: the win is purely
    dispatches/token). Decode lengths are all ≡ 1 (mod 4) so every
    request's post-prefill iteration count is a multiple of K and every
    window retires exactly K iterations — the measured
    dispatches/token ratio is the clean 1/K floor, not a
    ragged-tail approximation. Watch dispatches/token fused vs plain
    (target <= 1/K) and tokens/s (>= parity on compute-bound CPU)."""
    import numpy as np

    from deeplearning4j_tpu.serving import (ContinuousDecodeServer,
                                            ServingMetrics)

    K = 4
    lm = _lm()                          # max_len=64
    sys_prefix = np.random.default_rng(7).integers(1, 96, 16).tolist()

    def workload(rng, n):
        # prompt 16+1..7 = 17..23 rows; decode lengths 17/21/25/29/33
        # (all ≡ 1 mod K: prefill emits token 1, the remaining
        # n_new - 1 iterations divide evenly into full K-windows)
        out = []
        for _ in range(n):
            own = rng.integers(1, 96, int(rng.integers(1, 8))).tolist()
            n_new = int(rng.choice((17, 21, 25, 29, 33)))
            out.append((sys_prefix + own, n_new))
        return out

    paged_kw = dict(slots=16, prompt_buckets=(24,), max_queue=256,
                    paged=True, block_size=8, n_blocks=48)
    servers = {
        "fused": ContinuousDecodeServer(
            lm, fused_serve=K,
            metrics=ServingMetrics(slo_target_ms=slo_ms),
            **paged_kw).start(),
        "plain": ContinuousDecodeServer(
            lm, metrics=ServingMetrics(slo_target_ms=slo_ms),
            **paged_kw).start(),
    }
    warm = workload(np.random.default_rng(0), 6)
    for srv in servers.values():        # compile off the clock
        for p, n in warm:
            srv.generate(p, n, timeout=120)
    # SLO baseline after warm-up: compile-latency misses stay off the books
    base = {n: servers[n].metrics.snapshot() for n in servers}

    seg_idx = {name: [0] for name in servers}

    def seg(name):
        srv = servers[name]

        def run():
            rng = np.random.default_rng(100 + seg_idx[name][0])
            seg_idx[name][0] += 1
            work = workload(rng, reqs_per_seg)
            toks = sum(n for _, n in work)
            t0 = time.perf_counter()
            futs = [srv.submit(p, n) for p, n in work]
            for f in futs:
                f.result(300)
            return toks / (time.perf_counter() - t0)
        return run

    ab = _interleaved({n: seg(n) for n in servers}, segments=segments)
    snaps = {n: servers[n].metrics.snapshot() for n in servers}
    for srv in servers.values():
        srv.stop()
    dpt = {n: snaps[n]["dispatches_per_token"] for n in snaps}
    return {
        "config": f"TransformerLM L=2 d=32, BOTH arms paged 48 blocks "
                  f"x 8 rows (slots=16 scheduling width), 16-token "
                  f"shared system prefix + mixed own prompts 1-7 / "
                  f"decode 17-33 (≡1 mod {K}), fused_serve={K} on the "
                  f"fused arm, {reqs_per_seg} reqs/segment, greedy",
        "unit": "generated tokens/sec",
        "ab": ab,
        "speedup_fused_over_plain": round(
            ab["fused"]["median"] / ab["plain"]["median"], 3),
        "fused_k": K,
        "dispatches_per_token": {n: fmt(dpt[n], 4) for n in dpt},
        # the acceptance pin: fused dpt at or below 1/K of unfused
        "dispatches_per_token_ratio": round(dpt["fused"] / dpt["plain"],
                                            4) if dpt["plain"] else None,
        "target_ratio": round(1.0 / K, 4),
        "fused_windows": snaps["fused"]["fused_windows"],
        "iterations_per_dispatch": {
            n: fmt(snaps[n]["iterations_per_dispatch"], 3)
            for n in snaps},
        "max_concurrent_streams": {
            n: snaps[n]["live_streams_max"] for n in snaps},
        "blocked_on_memory": {
            n: snaps[n]["blocked_on_memory"] for n in snaps},
        "request_latency_ms": {
            n: {"p50": fmt(snaps[n]["latency_ms_p50"]),
                "p99": fmt(snaps[n]["latency_ms_p99"])} for n in snaps},
        "slo_ms": slo_ms,
        "slo": {n: _slo_view(snaps[n], ab[n]["median"], base[n])
                for n in snaps},
    }, snaps, None


def bench_preempt_ab(segments, reqs_per_seg=12, slo_ms=60.0):
    """Preemption vs shed-only at full block occupancy (module
    docstring). Per segment: 3 batch-class requests of 14 blocks each
    against a 28-block pool (two resident reserve it WHOLE, the third
    keeps it full when one completes), then `reqs_per_seg` interactive
    requests (4 tokens each, deadline = slo); the metric is interactive-class
    goodput-under-deadline, computed CLIENT-side per class (deadline
    known at submit, completion observed, tokens known) because the
    server's SLO counters aggregate classes. Both arms also report the
    interactive completion p99 — a tight TTFT bound at 4 tokens — and
    the preempt arm's spill accounting. The shed-only arm's interactive
    requests can only park on the memory gate until batch work
    completes or their deadline sweeps them; the preempt arm spills a
    batch slot and serves them inside the deadline."""
    import numpy as np

    from deeplearning4j_tpu.models.zoo.transformer import TransformerLM
    from deeplearning4j_tpu.serving import (BrownoutPolicy,
                                            ContinuousDecodeServer,
                                            ServingMetrics)

    # a somewhat bigger model than the other arms': batch occupancy
    # must OUTLAST the interactive deadline for full occupancy to be a
    # regime rather than a blip (the tiny shared model finishes 44
    # tokens inside the deadline and both arms trivially tie)
    lm = TransformerLM(96, d_model=64, n_heads=4, n_layers=3,
                       max_len=128, seed=5)

    def mk(preempt):
        return ContinuousDecodeServer(
            lm, slots=4, prompt_buckets=(8, 16), max_queue=256,
            paged=True, block_size=8, n_blocks=28,
            brownout=BrownoutPolicy(classes={"batch": (0.9, 1.01)}),
            preempt=preempt,
            metrics=ServingMetrics(slo_target_ms=slo_ms)).start()

    servers = {"preempt": mk(True), "shed_only": mk(False)}
    for name, srv in servers.items():   # compile off the clock —
        # including the preempt arm's extract/restore programs: one
        # full-pool batch pair + one preempting interactive request
        srv.generate([1, 2, 3, 4], 4, timeout=300)
        srv.generate(list(range(1, 11)), 4, timeout=300)
        warm_b = [srv.submit(list(range(1, 10)), 100, klass="batch")
                  for _ in range(2)]
        time.sleep(0.02)
        try:
            srv.generate([5, 6, 7], 4, deadline_ms=10_000, timeout=300)
        except Exception:               # noqa: BLE001 — shed arm: parks
            pass
        for f in warm_b:
            f.result(600)
    base = {n: servers[n].metrics.snapshot() for n in servers}
    seg_idx = {n: [0] for n in servers}
    inter_lat = {n: [] for n in servers}    # interactive completion ms

    def seg(name):
        srv = servers[name]

        def run():
            rng = np.random.default_rng(300 + seg_idx[name][0])
            seg_idx[name][0] += 1
            t0 = time.perf_counter()
            # three batch requests, each reserving HALF the pool
            # (prompt 9 + 100 new = 108 reserved rows = 14 blocks): two
            # run, the third keeps the pool full when one completes —
            # occupancy pressure lasts the whole interactive stream (no
            # deadline: batch is throughput work)
            batch = [srv.submit(
                rng.integers(1, 96, 9).tolist(), 100, klass="batch")
                for _ in range(3)]
            time.sleep(0.02)            # let them admit + occupy
            inter = []
            for _ in range(reqs_per_seg):
                p = rng.integers(1, 96, int(rng.integers(3, 8))).tolist()
                dl = time.perf_counter()
                try:
                    f = srv.submit(p, 4, deadline_ms=slo_ms,
                                   klass="interactive")
                except Exception:       # noqa: BLE001 — shed: a miss
                    inter.append((None, dl, 4))
                    continue
                inter.append((f, dl, 4))
                time.sleep(0.004)
            good_tokens = 0
            for f, t_sub, toks in inter:
                if f is None:
                    continue
                try:
                    f.result(300)
                except Exception:       # noqa: BLE001 — shed/evicted
                    continue
                done = time.perf_counter()
                inter_lat[name].append((done - t_sub) * 1e3)
                if (done - t_sub) * 1e3 <= slo_ms:
                    good_tokens += toks
            for f in batch:             # drain: pool clean per segment
                f.result(600)
            return good_tokens / (time.perf_counter() - t0)
        return run

    ab = _interleaved({n: seg(n) for n in servers}, segments=segments)
    snaps = {n: servers[n].metrics.snapshot() for n in servers}
    for srv in servers.values():
        srv.stop(timeout=120)

    def pct(xs, q):
        xs = sorted(xs)
        return fmt(xs[min(len(xs) - 1, int(q / 100 * len(xs)))]) \
            if xs else None

    d = {n: snaps[n]["dispatches"] - base[n]["dispatches"]
         for n in snaps}
    return {
        "config": f"TransformerLM L=3 d=64 paged 28 blocks x 8 rows, "
                  f"3 batch reqs (14 blocks each, 100 tokens) + "
                  f"{reqs_per_seg} interactive 4-token reqs/segment at "
                  f"deadline {slo_ms:g}ms; brownout ranks batch < "
                  f"interactive, preempt arm spills batch to host",
        "unit": "interactive goodput tokens/sec (within deadline)",
        "ab": ab,
        "interactive_goodput_preempt_over_shed": round(
            ab["preempt"]["median"] / ab["shed_only"]["median"], 3)
        if ab["shed_only"]["median"] else None,
        "interactive_completion_ms": {
            n: {"p50": pct(inter_lat[n], 50),
                "p99": pct(inter_lat[n], 99)} for n in inter_lat},
        "preempted": {n: snaps[n]["preempted"] for n in snaps},
        "resumed": {n: snaps[n]["resumed"] for n in snaps},
        "spill_bytes": {n: snaps[n]["spill_bytes"] for n in snaps},
        "blocked_on_memory": {
            n: snaps[n]["blocked_on_memory"] - base[n][
                "blocked_on_memory"] for n in snaps},
        "sheds": {n: _shed_view(snaps[n], base[n]) for n in snaps},
        "measured_dispatches": d,
        "slo_ms": slo_ms,
        "slo": {n: _slo_view(snaps[n], None, base[n]) for n in snaps},
    }, snaps, None


def bench_affinity_ab(segments, reqs_per_seg=24, slo_ms=250.0):
    """Prefix-affinity routing A/B (ISSUE 20): the SAME seeded
    shared-system-prompt schedule (`serving.loadgen.SharedPrefixMix`)
    replayed per segment through two 2-replica paged fleets —
    `FleetManager(policy="affinity")` (consistent-hash prefix routing
    with load-aware spill + the fleet prefix tier) vs
    `policy="least_backlog"` (the prefix-blind baseline). Per-segment
    metric: fleet goodput-under-SLO. The record carries each arm's
    fleet prefix HIT RATE over the measured segments (counter deltas —
    warmup and the per-arm steady-state preload excluded) and the
    affinity arm's routing/pull counters: stickiness must BUY reuse
    (hit rate above the baseline's) without costing goodput."""
    from deeplearning4j_tpu.serving import (ContinuousDecodeServer,
                                            FleetManager,
                                            PoissonProcess,
                                            ServingMetrics,
                                            SharedPrefixMix,
                                            build_schedule, run_load)

    lm = _lm()
    mix = SharedPrefixMix(n_prefixes=4, prefix_blocks=(1, 3),
                          block_size=8, suffix=(1, 9), new=(4, 16),
                          vocab=96, seed=11)
    rate = 40.0     # near the 2-replica knee: enough concurrency that
    # routing placement matters, while goodput-under-SLO stays nonzero
    # (far past it every arm's goodput is 0 and the A/B reads nothing)

    def factory(name):
        return ContinuousDecodeServer(
            lm, slots=2, prompt_buckets=(16, 32), max_queue=1024,
            metrics=ServingMetrics(slo_target_ms=slo_ms, name=name),
            instance=name, admission=True, default_deadline_ms=slo_ms,
            paged=True, block_size=8)

    def warmup(srv):
        for p in ([1, 2, 3, 4], list(range(1, 25))):
            srv.generate(p, 4, deadline_ms=600_000, timeout=300)

    mgrs = {
        "affinity": FleetManager(
            factory, n_replicas=2, policy="affinity", warmup=warmup,
            metrics=ServingMetrics(name="fleet")),
        "least_backlog": FleetManager(
            factory, n_replicas=2, policy="least_backlog",
            warmup=warmup, metrics=ServingMetrics(name="fleet")),
    }
    for m in mgrs.values():
        m.start()
        # steady-state preload through the arm's OWN router: cold
        # first-touch misses are placement noise, not policy signal
        for p in mix.prefixes:
            m.generate(list(p) + [1, 2], 4, deadline_ms=600_000,
                       timeout=300)

    def tier(m):
        out = {"hit": 0, "total": 0}
        for n in list(m.replicas):
            s = m.replica(n).metrics.snapshot()
            out["hit"] += int(s.get("prefix_rows_hit") or 0)
            out["total"] += int(s.get("prefix_rows_total") or 0)
        return out

    base = {n: tier(m) for n, m in mgrs.items()}
    base_fleet = {n: m.fleet_snapshot() for n, m in mgrs.items()}
    seg_idx = {n: [0] for n in mgrs}
    last = {n: None for n in mgrs}

    def seg(name):
        m = mgrs[name]

        def run():
            sched = build_schedule(PoissonProcess(rate), mix,
                                   reqs_per_seg,
                                   seed=70 + seg_idx[name][0])
            seg_idx[name][0] += 1
            # fleet goodput = FEDERATED within-SLO tokens over the
            # segment (run_load's own slo view reads the MANAGER's
            # metrics, which never see the replicas' slo counters)
            g0 = m.fleet_view().counter("slo_tokens_met")
            pt = run_load(m, sched)
            last[name] = pt
            g1 = m.fleet_view().counter("slo_tokens_met")
            return (g1 - g0) / max(float(pt["duration_s"]), 1e-9)
        return run

    ab = _interleaved({n: seg(n) for n in mgrs}, segments=segments)
    tiers = {n: tier(m) for n, m in mgrs.items()}
    fleets = {n: m.fleet_snapshot() for n, m in mgrs.items()}
    snaps = {}
    for n, m in mgrs.items():
        for rn in list(m.replicas):
            snaps[f"{n}.{rn}"] = m.replica(rn).metrics.snapshot()
    for m in mgrs.values():
        m.stop(timeout=120)
    hr = {}
    for n in mgrs:
        h = tiers[n]["hit"] - base[n]["hit"]
        t = tiers[n]["total"] - base[n]["total"]
        hr[n] = (h / t) if t else None
    ga, gb = ab["affinity"]["median"], ab["least_backlog"]["median"]
    af, bf = fleets["affinity"], base_fleet["affinity"]
    return {
        "config": f"2x FleetManager over 2 paged (bs=8) replicas "
                  f"each, SharedPrefixMix P=4, Poisson {rate:g} rps, "
                  f"{reqs_per_seg} reqs/segment, slo={slo_ms:g}ms; "
                  f"affinity = consistent-hash prefix routing + "
                  f"fleet prefix tier vs least-backlog",
        "unit": "goodput tokens/sec (within-SLO, fleet)",
        "ab": ab,
        "goodput_affinity_over_least_backlog": round(ga / gb, 3)
        if gb else None,
        "fleet_prefix_hit_rate": {n: fmt(hr[n], 4) for n in hr},
        "routing": {
            "routed_affinity": af["fleet_routed_affinity"]
            - bf["fleet_routed_affinity"],
            "routed_spill": af["fleet_routed_spill"]
            - bf["fleet_routed_spill"],
            "prefix_pull_hits": af["fleet_prefix_pull_hits"]
            - bf["fleet_prefix_pull_hits"],
            "prefix_pull_bytes": af["fleet_prefix_pull_bytes"]
            - bf["fleet_prefix_pull_bytes"]},
        "tokens_per_sec_last_segment": {
            n: last[n] and last[n]["tokens_per_sec"] for n in last},
        "slo_ms": slo_ms,
    }, snaps, None


def bench_overload_ab(segments, reqs_per_seg=320, slo_ms=120.0):
    """Overload robustness A/B (PR 9): the SAME seeded Poisson schedule,
    offered well past the tiny model's saturation knee, replayed per
    segment through an uncontrolled baseline decode server and one with
    chunked prefill + deadline-aware admission. The per-segment metric
    is GOODPUT-under-SLO (tokens/s landing within deadline) — the
    number the PR 7 curve showed collapsing past the knee; raw
    throughput is reported alongside (the controlled arm deliberately
    spends it on sheds). Interleaved same-process protocol like every
    other arm."""
    from deeplearning4j_tpu.serving import (ContinuousDecodeServer,
                                            DecodeSizeMix,
                                            PoissonProcess,
                                            ServingMetrics,
                                            build_schedule, run_load)

    lm = _lm()
    mix = DecodeSizeMix(((0.8, (3, 12), (4, 24)),
                         (0.2, (8, 16), (24, 44))), vocab=96)
    rate = 1600.0   # far past the tiny model's knee: the arrival
    # window offers several seconds of work in ~0.2 s, so every segment
    # spends most of its life in the saturated regime the arm measures
    servers = {
        "baseline": ContinuousDecodeServer(
            lm, slots=4, prompt_buckets=(8, 16), max_queue=1024,
            metrics=ServingMetrics(slo_target_ms=slo_ms)).start(),
        "controlled": ContinuousDecodeServer(
            lm, slots=4, prompt_buckets=(8, 16), max_queue=1024,
            chunked_prefill=8, admission=True,
            default_deadline_ms=slo_ms,
            metrics=ServingMetrics(slo_target_ms=slo_ms)).start(),
    }
    for srv in servers.values():        # compile off the clock
        # explicit generous deadline: the controlled arm's DEFAULT
        # deadline is the SLO, which first-compile latency would blow
        for p in ([1, 2, 3, 4], list(range(1, 13))):
            srv.generate(p, 4, deadline_ms=600_000, timeout=300)
    base = {n: servers[n].metrics.snapshot() for n in servers}

    seg_idx = {n: [0] for n in servers}
    last = {n: None for n in servers}

    def seg(name):
        srv = servers[name]

        def run():
            sched = build_schedule(PoissonProcess(rate), mix,
                                   reqs_per_seg,
                                   seed=40 + seg_idx[name][0])
            seg_idx[name][0] += 1
            pt = run_load(srv, sched)
            last[name] = pt
            return (pt["slo"].get("goodput_tokens_per_sec") or 0.0)
        return run

    ab = _interleaved({n: seg(n) for n in servers}, segments=segments)
    snaps = {n: servers[n].metrics.snapshot() for n in servers}
    for srv in servers.values():
        srv.stop(timeout=120)
    gb, gc = ab["baseline"]["median"], ab["controlled"]["median"]
    return {
        "config": f"TransformerLM L=2 d=32 slots=4, Poisson {rate:g} "
                  f"rps (far past knee), {reqs_per_seg} reqs/segment, "
                  f"slo={slo_ms:g}ms; controlled = chunk=8 + "
                  f"deadline-aware admission",
        "unit": "goodput tokens/sec (within-SLO)",
        "ab": ab,
        "goodput_controlled_over_baseline": round(gc / gb, 3) if gb
        else None,
        "tokens_per_sec_last_segment": {
            n: last[n] and last[n]["tokens_per_sec"] for n in last},
        "ttft_ms_p99_last_segment": {
            n: last[n] and last[n].get("ttft_ms_p99") for n in last},
        "sheds": {n: _shed_view(snaps[n], base[n]) for n in snaps},
        "admission_error_ms": {
            "p50": fmt(snaps["controlled"]["admission_error_ms_p50"]),
            "p99": fmt(snaps["controlled"]["admission_error_ms_p99"]),
            "count": snaps["controlled"]["admission_error_ms_count"]},
        "service_rate_tokens_per_sec": fmt(
            snaps["controlled"]["service_rate_tokens_per_sec"], 1),
        "slo_ms": slo_ms,
        "slo": {n: _slo_view(snaps[n], None, base[n]) for n in snaps},
    }, snaps, None


def bench_microbatch_ab(segments, reqs_per_seg=96, slo_ms=100.0):
    """InferenceServer micro-batching vs a bare per-request output()
    loop over the same request stream."""
    import numpy as np

    from deeplearning4j_tpu.serving import InferenceServer, ServingMetrics

    net = _mlp()
    rng = np.random.default_rng(1)
    xs = rng.standard_normal((reqs_per_seg, 32)).astype(np.float32)
    srv = InferenceServer(net, max_batch=8, max_wait_ms=2.0,
                          max_queue=2 * reqs_per_seg,
                          metrics=ServingMetrics(
                              slo_target_ms=slo_ms)).start()
    # compile EVERY bucket program + the per-request jit off the clock
    for burst in (1, 4, 8):
        for f in [srv.submit(x) for x in xs[:burst]]:
            f.result(60)
    net.output(xs[:1])
    # SLO baseline after warm-up: compile-latency misses stay off the books
    base = srv.metrics.snapshot()

    def seg_server():
        t0 = time.perf_counter()
        futs = [srv.submit(x) for x in xs]
        for f in futs:
            f.result(120)
        return reqs_per_seg / (time.perf_counter() - t0)

    def seg_per_request():
        t0 = time.perf_counter()
        for x in xs:
            np.asarray(net.output(x[None]))
        return reqs_per_seg / (time.perf_counter() - t0)

    ab = _interleaved({"microbatch": seg_server,
                       "per_request": seg_per_request},
                      segments=segments)
    snap = srv.metrics.snapshot()
    srv.stop()
    return {
        "config": "MLP 32->64->10, 96 requests/segment, max_batch=8 "
                  "max_wait=2ms buckets(2,4,8)",
        "unit": "requests/sec",
        "ab": ab,
        "speedup_microbatch_over_per_request": round(
            ab["microbatch"]["median"] / ab["per_request"]["median"], 3),
        "request_latency_ms": {"p50": fmt(snap["latency_ms_p50"]),
                               "p99": fmt(snap["latency_ms_p99"])},
        "batch_size_mean": fmt(snap["batch_size_mean"], 2),
        "slo_ms": slo_ms,
        "slo": {"microbatch": _slo_view(snap, ab["microbatch"]["median"],
                                        base)},
    }, {"microbatch": snap}, None


def bench_tracing_ab(segments, reqs_per_seg=16, slo_ms=100.0):
    """Tracing-enabled vs tracing-disabled through the SAME continuous
    decode scheduler: the disabled arm is the shipping default (a few
    attribute checks per call site — the claim "tracing off adds ~zero
    over the pre-obs serve path" rests on the nanosecond-scale disabled
    span pin in tests/test_obs.py); this A/B bounds the ENABLED cost and
    pins that spans add ZERO device dispatches (the two arms' dispatch
    counters must agree for the same workload). Returns the enabled
    arm's tracer so main() can write a real Chrome trace."""
    import numpy as np

    from deeplearning4j_tpu.obs import Tracer
    from deeplearning4j_tpu.serving import (ContinuousDecodeServer,
                                            ServingMetrics)

    lm = _lm()
    tracer_on = Tracer(capacity=1 << 16, enabled=True)
    tracer_off = Tracer(enabled=False)
    servers = {
        "tracing_off": ContinuousDecodeServer(
            lm, slots=4, prompt_buckets=(8, 16), max_queue=256,
            tracer=tracer_off,
            metrics=ServingMetrics(slo_target_ms=slo_ms)).start(),
        "tracing_on": ContinuousDecodeServer(
            lm, slots=4, prompt_buckets=(8, 16), max_queue=256,
            tracer=tracer_on,
            metrics=ServingMetrics(slo_target_ms=slo_ms)).start(),
    }
    warm = _decode_workload(np.random.default_rng(0), 6)
    for srv in servers.values():        # compile off the clock
        for p, n in warm:
            srv.generate(p, n, timeout=120)
    # baseline after warm-up: both the dispatch-equality pin and the SLO
    # read-outs cover only the measured workload
    base = {n: s.metrics.snapshot() for n, s in servers.items()}

    seg_idx = {n: [0] for n in servers}

    def seg(name):
        srv = servers[name]

        def run():
            rng = np.random.default_rng(100 + seg_idx[name][0])
            seg_idx[name][0] += 1
            work = _decode_workload(rng, reqs_per_seg)
            toks = sum(n for _, n in work)
            t0 = time.perf_counter()
            futs = [srv.submit(p, n) for p, n in work]
            for f in futs:
                f.result(300)
            return toks / (time.perf_counter() - t0)
        return run

    ab = _interleaved({n: seg(n) for n in servers}, segments=segments)
    snaps = {n: servers[n].metrics.snapshot() for n in servers}
    disp = {n: snaps[n]["dispatches"] - base[n]["dispatches"]
            for n in snaps}
    for srv in servers.values():
        srv.stop()
    return {
        "config": "TransformerLM L=2 d=32 slots=4, same mixed workload "
                  "as decode A/B; obs tracer on vs off (off = shipping "
                  "default)",
        "unit": "generated tokens/sec",
        "ab": ab,
        "tracing_on_over_off": round(
            ab["tracing_on"]["median"] / ab["tracing_off"]["median"], 3),
        # span recording must never change WHAT runs on the device:
        # identical workload -> identical dispatch count
        "measured_dispatches": disp,
        "zero_extra_dispatches": disp["tracing_on"] == disp[
            "tracing_off"],
        "spans_recorded": len(tracer_on),
        "slo_ms": slo_ms,
        "slo": {n: _slo_view(snaps[n], ab[n]["median"], base[n])
                for n in snaps},
    }, snaps, tracer_on


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--segments", type=int, default=5)
    ap.add_argument("--slo-ms", type=float, default=100.0,
                    help="request SLO for attainment/goodput read-outs")
    ap.add_argument("--report", default=None,
                    help="write the combined obs report (text + JSON + "
                         "Chrome trace) under this path prefix")
    args = ap.parse_args()
    from deeplearning4j_tpu.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    all_snaps = {}
    tracer = None
    benches = (("decode_continuous_vs_static", bench_decode_ab),
               ("paged_vs_fixed", bench_paged_ab),
               ("preempt_vs_shed", bench_preempt_ab),
               ("overload_vs_baseline", bench_overload_ab),
               ("speculative_vs_plain", bench_speculative_ab),
               ("paged_spec_vs_paged", bench_paged_spec_ab),
               ("fused_serve_vs_plain", bench_fused_serve_ab),
               ("affinity_vs_least_backlog", bench_affinity_ab),
               ("microbatch_vs_per_request", bench_microbatch_ab),
               ("tracing_on_vs_off", bench_tracing_ab))
    for name, fn in benches:
        rec = {"name": name}
        # uniform contract: every bench returns (body, snaps, tracer-or-
        # None); only the tracing A/B carries a tracer for the report
        body, snaps, tracer_arm = fn(args.segments, slo_ms=args.slo_ms)
        if tracer_arm is not None:
            tracer = tracer_arm
        rec.update(body)
        for arm, snap in snaps.items():
            all_snaps[f"{name}.{arm}"] = snap
        print(json.dumps(rec))
    if args.report:
        # the combined tools/obs_report.py view replaces the old
        # print-only summaries: host spans (from the tracing arm) +
        # every arm's metrics snapshot, one text + one JSON + the raw
        # Chrome trace for Perfetto
        from obs_report import build_report, format_report
        report = build_report(spans=tracer, metrics=all_snaps)
        with open(args.report + ".json", "w") as fh:
            json.dump(report, fh)
        with open(args.report + ".txt", "w") as fh:
            fh.write(format_report(report) + "\n")
        if tracer is not None:
            tracer.save(args.report + ".trace.json")
        print(json.dumps({"report": args.report + ".{json,txt}",
                          "trace": args.report + ".trace.json"}))


if __name__ == "__main__":
    main()
