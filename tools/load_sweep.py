"""Throughput–latency sweep: offered rate -> what the servers deliver.

The traffic-harness headline tool: drive a REAL server
(`ContinuousDecodeServer` and/or `InferenceServer`) with seeded arrival
schedules (`serving/loadgen.py`) at a ladder of offered rates, and emit
the curve every serving claim should be judged on:

  offered rate -> achieved tokens/s (requests/s for the micro-batch
  server), request p50/p99, TTFT p99, inter-token p99, SLO attainment,
  goodput-under-SLO, shed counts, submit-lateness (open-loop fidelity)

plus the SATURATION KNEE — the highest offered rate the server still
sustains (achieved >= 90% of offered). Below the knee latency is flat;
past it the queue grows without bound and p99/sheds are the story. The
combined `tools/obs_report.py` view (host spans + span-derived latency
decomposition + per-rate metrics) is written with `--report`.

Run (CPU backend, no chip needed):

    JAX_PLATFORMS=cpu python tools/load_sweep.py \
        [--server both] [--rates 50,100,200,400,800] \
        [--process poisson|onoff|closed] [--requests 64] \
        [--slo-ms 150] [--seed 0] [--report /tmp/sweep] [--no-trace] \
        [--chunked-prefill C] [--admission] [--overload-ab] \
        [--paged] [--speculate K] [--preempt] [--fleet N]
        [--fleet-control [--fleet-min A --fleet-max B]]
        [--fleet-procs N [--chaos [--chaos-events E] [--cascade]]]
        [--affinity [--fleet-procs N]]

`--process onoff` keeps the same MEAN rate but bursts at 2x with a 50%
duty cycle (the p99 stressor); `--process closed` reinterprets each
"rate" as a fixed concurrency (the coordinated-omission contrast).
`--overload-ab` replays the decode ladder through an uncontrolled
baseline AND a chunked-prefill + deadline-admission arm (PR 9) and
appends a comparison record: per-rate goodput/TTFT both arms, the
controlled arm's shed-reason breakdown, and the monotonicity verdict
(goodput must not collapse past the knee).
`--cascade` (with `--chaos`, `--fleet-procs` >= 3) runs the
blast-radius-containment arm: poison-pill quarantine, the spawn
circuit breaker's factory-failure window, and the shared retry budget
composed with the manager kill (ISSUE 17).
tests/test_loadgen.py runs the smoke version in tier-1 and CI uploads
its report JSON.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from deeplearning4j_tpu.common.compile_cache import (  # noqa: E402
    enable_compile_cache)
from deeplearning4j_tpu.obs.registry import fmt  # noqa: E402

KNEE_THRESH = 0.9


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


def _process_for(process, rate):
    """Map one sweep 'rate' onto an arrival process. onoff keeps the
    same MEAN rate but bursts at 2x with a 50% duty cycle; closed
    reinterprets rate as a concurrency level."""
    from deeplearning4j_tpu.serving import (ClosedLoop, OnOffProcess,
                                            PoissonProcess)
    if process == "poisson":
        return PoissonProcess(rate)
    if process == "onoff":
        return OnOffProcess(2.0 * rate, on_s=0.5, off_s=0.5)
    if process == "closed":
        return ClosedLoop(max(1, int(rate)))
    raise ValueError(f"unknown process {process!r}")


def _knee(curve):
    """Saturation knee over annotated points (each carries `_offered` /
    `_achieved`): the last point before the first unsustained one."""
    knee = first_bad = None
    for pt in curve:
        off, ach = pt.pop("_offered", None), pt.pop("_achieved", None)
        if not off or ach is None:
            continue
        pt["sustained_ratio"] = round(ach / off, 3)
        if first_bad is None:
            if ach / off >= KNEE_THRESH:
                knee = pt
            else:
                first_bad = pt
    return {
        "criterion": f"achieved >= {KNEE_THRESH:g} x offered",
        "knee_offered_rate": knee and knee["offered_rate_target"],
        "knee_achieved": knee and (knee.get("tokens_per_sec")
                                   or knee.get("requests_per_sec")),
        "first_unsustained_rate": (
            first_bad and first_bad["offered_rate_target"]),
    }


def sweep_decode(rates, n_req=64, slo_ms=150.0, seed=0,
                 process="poisson", tracer=None, lm=None, slots=4,
                 paged=False, block_size=8, chunked_prefill=None,
                 admission=None, brownout=None, deadline_ms=None,
                 speculate_k=None, preempt=False, fused_serve=None):
    """Rate ladder over the ContinuousDecodeServer. One server serves
    every rate (compile once); per-point accounting is delta-based
    (loadgen baselines at entry), so points never contaminate each
    other. Offered/achieved compare in TOKENS/s — the decode server's
    capacity is token throughput, not request admission.

    `paged=True` swaps in the block-table KV cache (serving/kvpool.py)
    at the default equal-bytes arena: the same sweep drives the
    block-gated admission path instead of the slot-gated one — the
    tier-1 smoke sweep runs one paged rate so CI exercises it.

    `speculate_k=K` adds a K-wide n-gram speculative decode (both
    layouts — paged speculation is the ISSUE 10 composition; the
    tier-1 smoke sweep runs one paged+speculate rate so CI exercises
    the block-table verify program under real arrivals).

    `fused_serve=K` scans K decode iterations into one device dispatch
    (ISSUE 18 — both layouts; excludes speculate_k, the server refuses
    the combination loudly). The tier-1 smoke sweep runs one
    fused_serve=4 rate so CI exercises the windowed scheduler under
    real arrivals, deadlines included.

    `n_req` may be a sequence (one count per rate): the overload A/B
    scales requests WITH rate so every rung offers the same DURATION of
    traffic — at a fixed count, higher rates compress the arrival
    window and the total in-SLO-completable work shrinks with rate, so
    absolute goodput would decline past the knee for ANY controller
    (a finite-burst accounting artifact, not an overload verdict).

    Overload-control arm (PR 9): `chunked_prefill=C` slices prompts
    into C-row chunks, `admission=True` (or an AdmissionController)
    sheds predicted deadline misses at enqueue, and `deadline_ms` gives
    every request a real deadline (default: the SLO itself, the
    goodput-under-SLO semantics made enforceable) — together the
    protected arm of the `--overload-ab` comparison."""
    from deeplearning4j_tpu.serving import (BrownoutPolicy,
                                            ContinuousDecodeServer,
                                            DecodeSizeMix, NGramDraft,
                                            ServingMetrics, Speculator,
                                            build_schedule, run_load)
    lm = lm if lm is not None else _lm()
    metrics = ServingMetrics(slo_target_ms=slo_ms)
    if preempt:
        # preemption needs the paged pool (a block set to spill) and a
        # class ranking; the sweep's canonical mixed-class shape is the
        # short/long split below with the long tail as batch class
        paged = True
        if brownout is None:
            brownout = BrownoutPolicy(classes={"batch": (0.9, 1.01)})
    controlled = (chunked_prefill is not None or admission or
                  brownout is not None)
    spec = (None if speculate_k is None
            else Speculator(NGramDraft(n=3), k=int(speculate_k)))
    srv = ContinuousDecodeServer(
        lm, slots=slots, prompt_buckets=(8, 16), max_queue=1024,
        metrics=metrics, tracer=tracer, paged=paged,
        block_size=block_size, chunked_prefill=chunked_prefill,
        admission=admission, brownout=brownout, speculate=spec,
        preempt=preempt, fused_serve=fused_serve,
        default_deadline_ms=(deadline_ms if deadline_ms is not None
                             else (slo_ms if admission else None))
        ).start()
    # mostly short chat turns + a tail of long generations — the mixed-
    # length shape continuous batching exists for. With preemption the
    # same split becomes the mixed-CLASS shape: the short turns are the
    # interactive class whose TTFT preemption bounds, the long tail is
    # the preemptible batch class.
    if preempt:
        mix = DecodeSizeMix(((0.8, (3, 12), (4, 24), "interactive"),
                             (0.2, (8, 16), (24, 44), "batch")),
                            vocab=96)
    else:
        mix = DecodeSizeMix(((0.8, (3, 12), (4, 24)),
                             (0.2, (8, 16), (24, 44))), vocab=96)
    try:
        # compile both prompt buckets + the decode step off the clock
        # (explicit generous deadline: the controlled arm's DEFAULT
        # deadline is the SLO, which first-compile latency would blow)
        for p in ([1, 2, 3, 4], list(range(1, 13))):
            srv.generate(p, 4, deadline_ms=600_000, timeout=300)
        curve = []
        n_reqs = (list(n_req) if isinstance(n_req, (list, tuple))
                  else [n_req] * len(rates))
        for i, rate in enumerate(rates):
            sched = build_schedule(_process_for(process, rate), mix,
                                   n_reqs[i], seed=seed + i)
            pt = run_load(srv, sched)
            pt["offered_rate_target"] = rate
            pt["_offered"] = pt["schedule"]["offered_tokens_per_sec"]
            pt["_achieved"] = pt["tokens_per_sec"]
            curve.append(pt)
        snap = metrics.snapshot()
    finally:
        srv.stop(timeout=120)
    # describe the model actually measured
    d_model = int(lm.aux["tok"].shape[1])
    cache = (f"paged bs={block_size}" if paged else "fixed-slot")
    ctrl = ""
    if controlled:
        ctrl = (f", overload control: chunk={chunked_prefill} "
                f"admission={'on' if admission else 'off'} "
                f"deadline={deadline_ms if deadline_ms is not None else slo_ms:g}ms")
    if spec is not None:
        ctrl += f", speculate k={spec.k} (n-gram)"
    if preempt:
        ctrl += ", preempt=on (batch class spillable)"
    if fused_serve is not None and int(fused_serve) > 1:
        ctrl += f", fused_serve={int(fused_serve)}"
    return {"server": "decode", "process": process, "paged": bool(paged),
            "overload_control": bool(controlled),
            "speculate_k": speculate_k, "preempt": bool(preempt),
            "fused_serve": fused_serve,
            "config": f"TransformerLM L={len(lm.blocks)} d={d_model} "
                      f"slots={slots} cache={cache}, mix 80% "
                      f"short(p3-11/n4-23) + 20% long(p8-15/n24-43), "
                      f"{n_req} reqs/rate, slo={slo_ms:g}ms{ctrl}",
            "unit": "generated tokens/sec",
            "curve": curve, "knee": _knee(curve)}, snap


def sweep_fleet(rates, n_replicas=2, n_req=64, slo_ms=250.0, seed=0,
                process="poisson", trace=True, slots=2, lm=None,
                obs_per_rate=6, slice_s=0.25, signal=None):
    """Rate ladder over N in-process `ContinuousDecodeServer` replicas
    behind a round-robin splitter — the `--fleet N` scenario that
    exercises the whole fleet observability plane end to end:

      * every replica is a NAMED instance (`instance="i<k>"`): its
        metrics federate under that name, its tracer exports its own
        process group, and its request ids are fleet-unique;
      * each rate rung is served as `obs_per_rate` schedule slices;
        after each slice the merged fleet snapshot
        (`obs.fleet.FleetView` over every replica's kind_snapshot) is
        fed to ONE `AutoscaleSignal`, so the ladder drives the
        detector through a real two-regime trace: below the knee sheds
        stay quiet (hold), past it `shed_predicted` accrues while the
        fleet service-rate estimate stays flat at capacity (scale_up —
        the tier-1 fleet smoke pins exactly this);
      * replicas run deadline-aware admission (deadline = SLO), the
        shed_predicted producer the detector reads.

    Returns (body, per_instance_snaps, merged_trace_or_None): `body`
    carries the per-rate curve (each point with its in-rung decision
    sequence and final decision) plus the final fleet snapshot;
    `merged_trace` is the clock-anchor-stitched Chrome trace of every
    replica (None with trace=False)."""
    from deeplearning4j_tpu.obs import Tracer
    from deeplearning4j_tpu.obs.fleet import (AutoscaleSignal, FleetView,
                                              merge_traces)
    from deeplearning4j_tpu.serving import (ContinuousDecodeServer,
                                            DecodeSizeMix,
                                            RoundRobinSplitter,
                                            ServingMetrics,
                                            build_schedule, run_load)
    lm = lm if lm is not None else _lm()
    names = [f"i{k}" for k in range(int(n_replicas))]
    tracers = {n: (Tracer(capacity=1 << 15, enabled=True, instance=n)
                   if trace else Tracer(enabled=False, instance=n))
               for n in names}
    sig = signal if signal is not None else AutoscaleSignal()
    servers = []
    mix = DecodeSizeMix(((0.8, (3, 12), (4, 24)),
                         (0.2, (8, 16), (24, 44))), vocab=96)

    def _fleet_snapshot():
        fv = FleetView(signal=sig)
        for n, s in zip(names, servers):
            fv.add(n, s.metrics)
        return fv.snapshot()

    try:
        # construction INSIDE the try: if replica k's constructor or
        # first compile raises, the finally still stops replicas
        # 0..k-1 instead of leaking their serve loops into the caller
        # process (the tier-1 smoke runs in-process)
        for n in names:
            servers.append(ContinuousDecodeServer(
                lm, slots=slots, prompt_buckets=(8, 16), max_queue=1024,
                metrics=ServingMetrics(slo_target_ms=slo_ms, name=n),
                tracer=tracers[n], instance=n, admission=True,
                default_deadline_ms=slo_ms).start())
        # the PR 12 splitter, now the package's own baseline router
        # (serving/fleet.py promoted it; the closed-loop arm below uses
        # the full FleetManager instead)
        splitter = RoundRobinSplitter(servers)
        # compile both prompt buckets off the clock on EVERY replica
        # (each jits its own programs), with a generous deadline so the
        # admission default (the SLO) never sheds a first-compile
        for srv in servers:
            for p in ([1, 2, 3, 4], list(range(1, 13))):
                srv.generate(p, 4, deadline_ms=600_000, timeout=300)
        curve = []
        for i, rate in enumerate(rates):
            # EQUAL OFFERED DURATION per slice (the overload-AB rule):
            # each observation window sustains the offered rate for
            # ~slice_s seconds, so a past-knee rung really backlogs the
            # fleet inside every window instead of lobbing a burst the
            # replicas drain between slices — at a fixed count the
            # detector would never see sheds ACCRUE (measured). n_req
            # keeps a floor for the low-rate rungs; 400/slice caps the
            # submit storm.
            slice_n = max(2, int(n_req) // int(obs_per_rate),
                          min(int(rate * slice_s), 400))
            decisions, toks, dur = [], 0, 0.0
            offered = None
            for k in range(int(obs_per_rate)):
                sched = build_schedule(
                    _process_for(process, rate), mix, slice_n,
                    seed=seed + i * 1000 + k)
                if offered is None:
                    offered = sched.offered_tokens_per_sec()
                pt = run_load(splitter, sched, metrics=None)
                toks += pt["tokens_out"]
                dur += float(pt["duration_s"])
                decisions.append(sig.observe(_fleet_snapshot()))
            snap = _fleet_snapshot()
            point = {
                "offered_rate_target": rate,
                "tokens_per_sec": fmt(toks / dur if dur else 0.0, 1),
                "tokens_out": toks,
                "autoscale_decisions": decisions,
                "autoscale_decision": decisions[-1],
                "fleet_shed_predicted": snap["fleet_shed_predicted"],
                "fleet_service_rate_tokens_per_sec": fmt(
                    snap["fleet_service_rate_tokens_per_sec"], 1),
                "fleet_slo_attainment": fmt(
                    snap["fleet_slo_attainment"], 4),
                "_offered": offered,
                "_achieved": toks / dur if dur else 0.0,
            }
            curve.append(point)
        fleet_snap = _fleet_snapshot()
        snaps = {n: s.metrics.snapshot()
                 for n, s in zip(names, servers)}
    finally:
        for srv in servers:
            srv.stop(timeout=120)
    merged = (merge_traces([tracers[n].chrome_trace() for n in names],
                           names=names) if trace else None)
    d_model = int(lm.aux["tok"].shape[1])
    body = {"server": "fleet", "n_replicas": int(n_replicas),
            "process": process,
            "config": f"{n_replicas}x TransformerLM L={len(lm.blocks)} "
                      f"d={d_model} slots={slots} round-robin, "
                      f"admission deadline={slo_ms:g}ms, "
                      f"{obs_per_rate} observation slices/rate",
            "unit": "generated tokens/sec (fleet)",
            "curve": curve, "knee": _knee(curve),
            "fleet": fleet_snap,
            "autoscale_transitions": sig.transitions}
    return body, snaps, merged


def sweep_fleet_control(rates, n_replicas=2, n_req=64, slo_ms=250.0,
                        seed=0, process="poisson", trace=True, slots=2,
                        lm=None, obs_per_rate=6, slice_s=0.25,
                        signal=None, fault_injector=None,
                        min_replicas=None, max_replicas=None):
    """The CLOSED-LOOP fleet arm (`--fleet-control`): the same rate
    ladder as `sweep_fleet`, but replica count is driven by a
    `serving.fleet.FleetManager` — each schedule slice ends in one
    `control_tick()` that federates the fleet snapshot, consults the
    `AutoscaleSignal`, and ACTS (scale_up spawns a warmed replica,
    scale_down drains one with live-request migration; replica deaths
    — injected via `fault_injector` at the `fleet.replica` site — fail
    over in-flight requests to survivors by prompt replay).

    The convergence record (`body["fleet_control"]`) carries the
    ISSUE 13 pins: within the first rung that scaled up, mean
    per-slice goodput AFTER the spawn vs BEFORE it
    (`goodput_recovery_x` — the added replica must recover >= 0.8x,
    and in practice exceeds 1x, of the saturated pre-scale goodput),
    and the quiet-tail return to `min_replicas`
    (`returned_to_min`). Default signal: AutoscaleSignal(window=4,
    hysteresis=1) — the reset-after-action rule makes a short window
    safe (one action per argued regime), and the smoke budget needs
    decisions inside a 6-slice rung."""
    from deeplearning4j_tpu.obs import Tracer
    from deeplearning4j_tpu.obs.fleet import AutoscaleSignal, merge_traces
    from deeplearning4j_tpu.serving import (ContinuousDecodeServer,
                                            DecodeSizeMix, FleetManager,
                                            ServingMetrics,
                                            build_schedule, run_load)
    lm = lm if lm is not None else _lm()
    tracers = {}

    def factory(name):
        tr = tracers[name] = (
            Tracer(capacity=1 << 15, enabled=True, instance=name)
            if trace else Tracer(enabled=False, instance=name))
        return ContinuousDecodeServer(
            lm, slots=slots, prompt_buckets=(8, 16), max_queue=1024,
            metrics=ServingMetrics(slo_target_ms=slo_ms, name=name),
            tracer=tr, instance=name, admission=True,
            default_deadline_ms=slo_ms)

    def warmup(srv):
        # compile both prompt buckets + the decode step off the
        # serving clock on EVERY spawn (a cold spawned replica would
        # blow its first requests' SLO on compiles, reading as a
        # degraded replica the moment it joins)
        for p in ([1, 2, 3, 4], list(range(1, 13))):
            srv.generate(p, 4, deadline_ms=600_000, timeout=300)

    sig = signal if signal is not None else AutoscaleSignal(
        window=4, hysteresis=1)
    mgr = FleetManager(factory, n_replicas=n_replicas, signal=sig,
                       fault_injector=fault_injector, warmup=warmup,
                       min_replicas=min_replicas,
                       max_replicas=max_replicas,
                       metrics=ServingMetrics(name="fleet"))
    mix = DecodeSizeMix(((0.8, (3, 12), (4, 24)),
                         (0.2, (8, 16), (24, 44))), vocab=96)
    curve = []
    scale_rung = None       # (rung index, slice goodputs pre/post)
    try:
        mgr.start()
        for i, rate in enumerate(rates):
            # EQUAL OFFERED DURATION per slice (the sweep_fleet rule)
            slice_n = max(2, int(n_req) // int(obs_per_rate),
                          min(int(rate * slice_s), 400))
            ticks, goodputs = [], []
            toks, dur, offered = 0, 0.0, None
            admitted = completed = failed = 0
            for k in range(int(obs_per_rate)):
                sched = build_schedule(
                    _process_for(process, rate), mix, slice_n,
                    seed=seed + i * 1000 + k)
                if offered is None:
                    offered = sched.offered_tokens_per_sec()
                g0 = mgr.fleet_view().counter("slo_tokens_met")
                pt = run_load(mgr, sched, metrics=None)
                toks += pt["tokens_out"]
                dur += float(pt["duration_s"])
                admitted += pt["admitted"]
                completed += pt["completed"]
                failed += pt["failed"]
                g1 = mgr.fleet_view().counter("slo_tokens_met")
                goodputs.append(
                    (g1 - g0) / max(float(pt["duration_s"]), 1e-9))
                ticks.append(mgr.control_tick())
            if scale_rung is None and any(
                    t["acted"] == "scale_up" for t in ticks):
                at = next(k for k, t in enumerate(ticks)
                          if t["acted"] == "scale_up")
                scale_rung = {"rung": i, "slice": at,
                              "pre": goodputs[:at + 1],
                              "post": goodputs[at + 1:]}
            snap = mgr.fleet_snapshot()
            curve.append({
                "offered_rate_target": rate,
                "tokens_per_sec": fmt(toks / dur if dur else 0.0, 1),
                "tokens_out": toks,
                "admitted": admitted, "completed": completed,
                "failed": failed,
                "slice_goodput_tokens_per_sec": [fmt(g, 1)
                                                 for g in goodputs],
                "autoscale_decisions": [t["decision"] for t in ticks],
                "autoscale_acted": [t["acted"] for t in ticks],
                "n_replicas": [t["n_replicas"] for t in ticks],
                "fleet_shed_predicted": snap["fleet_shed_predicted"],
                "_offered": offered,
                "_achieved": toks / dur if dur else 0.0,
            })
        final_snap = mgr.fleet_snapshot()
        snaps = {n: mgr.replica(n).metrics.snapshot()
                 for n in mgr.replicas}
        states = mgr.states()
        n_final = mgr.n_alive()
    finally:
        mgr.stop(timeout=120)
    merged = (merge_traces([t.chrome_trace() for t in tracers.values()],
                           names=list(tracers))
              if trace and tracers else None)
    recovery = None
    if scale_rung and scale_rung["pre"] and scale_rung["post"]:
        pre = sum(scale_rung["pre"]) / len(scale_rung["pre"])
        post = sum(scale_rung["post"]) / len(scale_rung["post"])
        recovery = (post / pre) if pre > 0 else None
    d_model = int(lm.aux["tok"].shape[1])
    body = {"server": "fleet_control", "n_replicas": int(n_replicas),
            "process": process,
            "config": f"FleetManager over {n_replicas}x TransformerLM "
                      f"L={len(lm.blocks)} d={d_model} slots={slots}, "
                      f"least-backlog router, admission deadline="
                      f"{slo_ms:g}ms, {obs_per_rate} control ticks/"
                      f"rate, min={mgr.min_replicas} "
                      f"max={mgr.max_replicas}",
            "unit": "generated tokens/sec (fleet)",
            "curve": curve, "knee": _knee(curve),
            "fleet": final_snap,
            "fleet_control": {
                "replica_spawned": final_snap["fleet_replica_spawned"],
                "replica_drained": final_snap["fleet_replica_drained"],
                "replica_dead": final_snap["fleet_replica_dead"],
                "failover_resubmitted":
                    final_snap["fleet_failover_resubmitted"],
                "scale_up_at": ({"rung": scale_rung["rung"],
                                 "slice": scale_rung["slice"]}
                                if scale_rung else None),
                "goodput_recovery_x": fmt(recovery, 3),
                # the ISSUE 13 convergence criterion; captures land
                # well above it (an added replica raises capacity ~1.5x)
                "goodput_recovered_08": (recovery is not None
                                         and recovery >= 0.8),
                "n_replicas_final": n_final,
                "returned_to_min": n_final == mgr.min_replicas,
                "states": states},
            "autoscale_transitions": sig.transitions}
    return body, snaps, merged


def _spawn_replica(cmd):
    """Start one `--replica-serve` child in the parent's own environment.

    A chip belongs to one process at a time, and this parent has already
    initialised JAX (it builds the reference model and schedules): on an
    accelerator the children could not take the chip the parent holds, and
    forcing them onto the CPU would serve the "fleet" from CPUs behind the
    caller's back. The cross-process fleet is a control-plane harness, so
    it runs where the parent's backend is the CPU and refuses elsewhere
    (the on-chip fleet is in-process replicas, one device each)."""
    import subprocess

    import jax
    backend = jax.default_backend()
    if backend != "cpu":
        raise SystemExit(
            f"load_sweep: replica child processes need the parent on the "
            f"CPU backend, but this process holds {backend!r} (one process "
            f"per chip). Run the cross-process fleet arms with "
            f"JAX_PLATFORMS=cpu; on a chip use the in-process --fleet N.")
    return subprocess.Popen(cmd)


def _replica_serve_main(argv):
    """Child-process entry for `--fleet-procs` (hidden flag
    `--replica-serve`): build the SAME deterministic model the parent
    knows (fixed seed ⇒ identical weights ⇒ identical param
    fingerprint across processes — migrations tag-check against it),
    wrap one decode server in a `ReplicaServer`, publish the bound
    port, and serve until the parent's STOP/KILL/DRAIN. A graceful
    exit saves this process's own Chrome trace — the parent stitches
    every replica's file into ONE merged timeline."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--replica-serve", action="store_true")
    ap.add_argument("--instance", required=True)
    ap.add_argument("--port-file", required=True)
    ap.add_argument("--trace-out", default=None)
    ap.add_argument("--identity-file", default=None)
    ap.add_argument("--slo-ms", type=float, default=250.0)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--paged", action="store_true")
    ap.add_argument("--prompt-buckets", default="8,16",
                    help="comma-separated prefill bucket rows (the "
                         "affinity arm's shared-prefix prompts need "
                         "16,32)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    from deeplearning4j_tpu.obs import Tracer
    from deeplearning4j_tpu.serving import (ContinuousDecodeServer,
                                            ServingMetrics,
                                            run_replica_server)
    lm = _lm()
    tr = Tracer(capacity=1 << 15, enabled=args.trace_out is not None,
                instance=args.instance)
    buckets = tuple(int(b) for b in args.prompt_buckets.split(","))
    srv = ContinuousDecodeServer(
        lm, slots=args.slots, prompt_buckets=buckets, max_queue=1024,
        metrics=ServingMetrics(slo_target_ms=args.slo_ms,
                               name=args.instance),
        tracer=tr, instance=args.instance, admission=True,
        default_deadline_ms=args.slo_ms, paged=args.paged, block_size=8)
    run_replica_server(srv, port_file=args.port_file, tracer=tr,
                       trace_out=args.trace_out,
                       identity_file=args.identity_file)


def sweep_fleet_procs(rates, n_replicas=2, n_req=64, slo_ms=250.0,
                      seed=0, process="poisson", trace=True, slots=2,
                      obs_per_rate=4, slice_s=0.2, fault_injector=None,
                      inject_sever=True, paged=False,
                      sever_site="serve.wire.stream"):
    """The CROSS-PROCESS fleet arm (`--fleet-procs N`): every replica
    is a REAL child process (`--replica-serve`) behind a
    `serving.wire.RemoteReplica`, routed by the same `FleetManager`
    the in-process sweeps use — the whole wire path (SUBMIT/STREAM
    frames, SNAPSHOT-federated metrics, heartbeat liveness,
    reconnect-with-dedup) under real arrivals.

    After the rate rungs, the FAULT PHASE injects one socket sever at
    `sever_site` (default: the result frame mid-stream) while a batch
    of requests is in flight and pins the ISSUE 14 acceptance: every
    admitted future resolves, and the faulted prompt's stream is
    BIT-IDENTICAL to the same prompt served on the quiet fleet
    (deterministic greedy ⇒ dedup re-delivery and failover replay are
    indistinguishable from an undisturbed run). The record carries the
    wire counters (`wire_reconnects`/`wire_retries`) so the sever is
    visibly exercised, and the merged trace covers every replica
    PROCESS (distinct pids in Perfetto).

    Returns (body, per_instance_snaps, merged_trace_or_None)."""
    import tempfile

    from deeplearning4j_tpu.common.resilience import (FaultInjector,
                                                      RetryPolicy)
    from deeplearning4j_tpu.obs.fleet import merge_traces
    from deeplearning4j_tpu.serving import (DecodeSizeMix, FleetManager,
                                            RemoteReplica,
                                            ServingMetrics,
                                            build_schedule, run_load)
    if fault_injector is None and inject_sever:
        fault_injector = FaultInjector()
    tmpdir = tempfile.mkdtemp(prefix="fleet_procs_")
    here = os.path.abspath(__file__)
    procs, trace_files = {}, {}

    def launch(name):
        port_file = os.path.join(tmpdir, f"{name}.port")
        trace_out = (os.path.join(tmpdir, f"{name}.trace.json")
                     if trace else None)
        cmd = [sys.executable, here, "--replica-serve",
               "--instance", name, "--port-file", port_file,
               "--slo-ms", str(slo_ms), "--slots", str(slots)]
        if paged:
            # paged children make drains MIGRATE artifact bytes over
            # the wire (non-paged replicas degrade drains to replay)
            cmd.append("--paged")
        if trace_out:
            cmd += ["--trace-out", trace_out]
        procs[name] = _spawn_replica(cmd)
        trace_files[name] = trace_out
        return port_file

    def wait_port(name, port_file, timeout=300.0):
        t0 = time.monotonic()
        while time.monotonic() - t0 < timeout:
            if os.path.exists(port_file):
                return int(open(port_file).read().strip())
            if procs[name].poll() is not None:
                raise RuntimeError(
                    f"replica process {name} exited rc="
                    f"{procs[name].returncode} before binding")
            time.sleep(0.05)
        raise TimeoutError(f"replica {name} never published its port")

    names = [f"i{k}" for k in range(int(n_replicas))]
    # pre-launch every expected replica so the N jax imports + compiles
    # overlap instead of serializing through the factory
    ports = {n: launch(n) for n in names}

    def factory(name):
        port_file = ports.pop(name, None)
        if port_file is None:
            port_file = launch(name)        # backfill beyond the batch
        port = wait_port(name, port_file)
        return RemoteReplica(
            "127.0.0.1", port, name=name,
            retry_policy=RetryPolicy(max_retries=4, base_delay=0.05,
                                     max_delay=0.5, jitter=0.0),
            heartbeat_interval=0.1, fault_injector=fault_injector,
            process=procs[name])

    def warmup(srv):
        # compile the child's prompt buckets + decode step off the
        # serving clock, over the wire
        for p in ([1, 2, 3, 4], list(range(1, 13))):
            srv.generate(p, 4, deadline_ms=600_000, timeout=300)

    mgr = FleetManager(factory, n_replicas=n_replicas, warmup=warmup,
                       heartbeat_timeout=2.0,
                       metrics=ServingMetrics(name="fleet"))
    mix = DecodeSizeMix(((0.8, (3, 12), (4, 24)),
                         (0.2, (8, 16), (24, 44))), vocab=96)
    curve = []
    try:
        mgr.start()
        for i, rate in enumerate(rates):
            slice_n = max(2, int(n_req) // int(obs_per_rate),
                          min(int(rate * slice_s), 400))
            toks, dur, offered = 0, 0.0, None
            admitted = completed = failed = 0
            for k in range(int(obs_per_rate)):
                sched = build_schedule(
                    _process_for(process, rate), mix, slice_n,
                    seed=seed + i * 1000 + k)
                if offered is None:
                    offered = sched.offered_tokens_per_sec()
                pt = run_load(mgr, sched, metrics=None)
                toks += pt["tokens_out"]
                dur += float(pt["duration_s"])
                admitted += pt["admitted"]
                completed += pt["completed"]
                failed += pt["failed"]
                mgr.control_tick()          # the health/liveness probe
            curve.append({
                "offered_rate_target": rate,
                "tokens_per_sec": fmt(toks / dur if dur else 0.0, 1),
                "tokens_out": toks,
                "admitted": admitted, "completed": completed,
                "failed": failed,
                "_offered": offered,
                "_achieved": toks / dur if dur else 0.0,
            })
        # -- FAULT PHASE: one injected socket sever mid-stream --------
        fault_rec = None
        if inject_sever and fault_injector is not None:
            # quiet-fleet references first: deterministic greedy on
            # identical weights makes every replica's stream for a
            # prompt THE stream, so the fault batch must reproduce
            # them bit-for-bit no matter which request the sever hits
            prompts = [[1, 2, 3]] + [[4 + j, 5, 6] for j in range(5)]
            refs = [list(mgr.generate(p, 24, deadline_ms=600_000,
                                      timeout=300)) for p in prompts]
            base = mgr.fleet_snapshot()
            fault_injector.plan(sever_site,
                                on_call=fault_injector.calls(sever_site),
                                sever=True, exc=None)
            futs = [mgr.submit(p, 24, deadline_ms=600_000)
                    for p in prompts]
            results = [list(f.result(300)) for f in futs]  # ALL resolve
            snap = mgr.fleet_snapshot()
            fault_rec = {
                "site": sever_site,
                "severed": len(fault_injector.fired(sever_site)),
                "all_futures_resolved": True,
                "streams_bit_identical": results == refs,
                "wire_reconnects": snap["fleet_wire_reconnects"]
                - base["fleet_wire_reconnects"],
                "wire_retries": snap["fleet_wire_retries"]
                - base["fleet_wire_retries"],
            }
        final_snap = mgr.fleet_snapshot()
        snaps = {n: mgr.replica(n).metrics.snapshot()
                 for n in mgr.replicas}
        pids = {n: procs[n].pid for n in procs}
    finally:
        mgr.stop(timeout=120)
        for p in procs.values():        # belt and braces
            if p.poll() is None:
                p.terminate()
        for p in procs.values():
            try:
                p.wait(timeout=30)
            except Exception:   # noqa: BLE001
                p.kill()
    merged = None
    if trace:
        saved = []
        tnames = []
        for n, path in trace_files.items():
            if path and os.path.exists(path):
                with open(path) as fh:
                    saved.append(json.load(fh))
                tnames.append(n)
        if saved:
            merged = merge_traces(saved, names=tnames)
    # the scratch dir (port files + per-replica traces) is spent once
    # the traces are merged — repeated sweeps must not accumulate it
    shutil.rmtree(tmpdir, ignore_errors=True)
    body = {"server": "fleet_procs", "n_replicas": int(n_replicas),
            "process": process, "paged": bool(paged),
            "config": f"FleetManager over {n_replicas} replica "
                      f"PROCESSES (serving/wire.py), slots={slots}, "
                      f"cache={'paged bs=8' if paged else 'fixed-slot'}"
                      f", admission deadline={slo_ms:g}ms, heartbeat "
                      f"timeout 2s, {obs_per_rate} slices/rate",
            "unit": "generated tokens/sec (fleet)",
            "curve": curve, "knee": _knee(curve),
            "fleet": final_snap,
            "replica_pids": pids,
            "wire_fault": fault_rec}
    return body, snaps, merged


def sweep_fleet_affinity(rates, n_replicas=3, n_req=48, slo_ms=250.0,
                         seed=0, process="poisson", trace=False,
                         slots=2, lm=None, obs_per_rate=2,
                         slice_s=0.25, procs=0, n_prefixes=4,
                         dispatch_reqs=10):
    """The PREFIX-AFFINITY arm (`--affinity`, ISSUE 20): a seeded
    shared-system-prompt workload (`serving.loadgen.SharedPrefixMix` —
    P block-aligned prefixes drawn on their own stream) over paged
    replicas, served three ways on IDENTICAL schedules:

      * **solo reference** — ONE paged replica; its prefix hit rate is
        the ceiling any router can retain;
      * **affinity** — `FleetManager(policy="affinity")`: consistent-
        hash routing of the block-aligned prefix key with load-aware
        spill, plus the fleet prefix tier (a spilled/missing replica
        PULLS a peer's resident blocks over `prefix_export`/
        `prefix_adopt` instead of recomputing);
      * **least_backlog** — the prefix-blind baseline whose fleet hit
        rate decays toward ~1/N as replicas dilute the cache.

    The record carries the per-arm fleet hit rate (counter DELTAS over
    the measured rungs — warmup traffic excluded), the routing
    verdicts (`routed_affinity`/`routed_spill`), the prefix-tier
    traffic (`prefix_pull_hits`/`_refused`/`_bytes`), goodput per arm,
    and `hit_rate_ratio_vs_solo` — the ISSUE 20 acceptance pins it
    >= 0.9 at 3 replicas.

    The DISPATCH A/B pins the no-pull affinity path at ZERO added
    device dispatches per token: the same fixed request list is served
    one-at-a-time through two fleets-of-one — `policy="affinity"`
    (prefix_pull off) vs `policy="least_backlog"` — and the
    `dispatches`+`chunk_dispatches` deltas must match exactly (routing
    is host-side hashing; nothing touches the device).

    `procs=N` (the `--fleet-procs N --affinity` spelling) runs the two
    FLEET arms as N real replica PROCESSES behind the serving wire —
    block pulls become PREFIX_PULL/PREFIX_PUSH artifact frames — while
    the solo reference and dispatch A/B stay in-process (they measure
    cache/compute properties the wire cannot change). Span tracing is
    not wired through this arm (`trace` is accepted for signature
    parity); the counters are the record. Returns
    (body, per_instance_snaps, None)."""
    import random
    import tempfile

    from deeplearning4j_tpu.common.resilience import RetryPolicy
    from deeplearning4j_tpu.serving import (ContinuousDecodeServer,
                                            FleetManager, RemoteReplica,
                                            ServingMetrics,
                                            SharedPrefixMix,
                                            build_schedule, run_load)
    del trace
    lm = lm if lm is not None else _lm()
    bs = 8
    mix = SharedPrefixMix(n_prefixes=n_prefixes, prefix_blocks=(1, 3),
                          block_size=bs, suffix=(1, 9), new=(4, 16),
                          vocab=96, seed=seed)
    buckets = (16, 32)
    here = os.path.abspath(__file__)
    # the dispatch-A/B request list: drawn ONCE, replayed verbatim
    # through both fleets-of-one (identical work is the whole point)
    rng = random.Random(f"load_sweep.affinity.dispatch:{seed}")
    ab_reqs = [mix.sample(rng) for _ in range(int(dispatch_reqs))]

    def local_factory(name):
        return ContinuousDecodeServer(
            lm, slots=slots, prompt_buckets=buckets, max_queue=1024,
            metrics=ServingMetrics(slo_target_ms=slo_ms, name=name),
            instance=name, admission=True, default_deadline_ms=slo_ms,
            paged=True, block_size=bs)

    def warmup(srv):
        # compile BOTH prefill buckets + the decode step off the
        # serving clock (the shared-prefix prompts span 9..32 rows)
        for p in ([1, 2, 3, 4], list(range(1, 25))):
            srv.generate(p, 4, deadline_ms=600_000, timeout=300)

    TIER_KEYS = ("prefix_rows_hit", "prefix_rows_total",
                 "prefix_pull_hits", "prefix_pull_refused",
                 "prefix_pull_bytes")

    def tier_counters(mgr):
        out = dict.fromkeys(TIER_KEYS, 0)
        for n in list(mgr.replicas):
            snap = mgr.replica(n).metrics.snapshot()
            for k in TIER_KEYS:
                out[k] += int(snap.get(k) or 0)
        return out

    def run_arm(policy, n, use_procs, pull, tag, do_rungs=True,
                do_dispatch=False):
        procs_map, tmpdir = {}, None
        if use_procs:
            tmpdir = tempfile.mkdtemp(prefix=f"fleet_affinity_{tag}_")

            def launch(name):
                port_file = os.path.join(tmpdir, f"{name}.port")
                cmd = [sys.executable, here, "--replica-serve",
                       "--instance", name, "--port-file", port_file,
                       "--slo-ms", str(slo_ms), "--slots", str(slots),
                       "--paged", "--prompt-buckets",
                       ",".join(str(b) for b in buckets)]
                procs_map[name] = _spawn_replica(cmd)
                return port_file

            def wait_port(name, port_file, timeout=300.0):
                t0 = time.monotonic()
                while time.monotonic() - t0 < timeout:
                    if os.path.exists(port_file):
                        return int(open(port_file).read().strip())
                    if procs_map[name].poll() is not None:
                        raise RuntimeError(
                            f"replica process {name} exited rc="
                            f"{procs_map[name].returncode} before "
                            f"binding")
                    time.sleep(0.05)
                raise TimeoutError(
                    f"replica {name} never published its port")

            ports = {f"i{k}": None for k in range(int(n))}
            for name in ports:
                ports[name] = launch(name)

            def factory(name):
                port_file = ports.pop(name, None) or launch(name)
                port = wait_port(name, port_file)
                return RemoteReplica(
                    "127.0.0.1", port, name=name,
                    retry_policy=RetryPolicy(max_retries=4,
                                             base_delay=0.05,
                                             max_delay=0.5, jitter=0.0),
                    heartbeat_interval=0.1, process=procs_map[name])
        else:
            factory = local_factory
        mgr = FleetManager(factory, n_replicas=n, policy=policy,
                           prefix_pull=pull, warmup=warmup,
                           heartbeat_timeout=2.0 if use_procs else None,
                           metrics=ServingMetrics(name="fleet"))
        try:
            mgr.start()
            dispatch_rec = None
            if do_dispatch:
                fv0 = mgr.fleet_view()
                d0 = (fv0.counter("dispatches")
                      + fv0.counter("chunk_dispatches"))
                toks = 0
                for r in ab_reqs:
                    toks += len(mgr.generate(r["prompt"], r["max_new"],
                                             deadline_ms=600_000,
                                             timeout=300))
                fv1 = mgr.fleet_view()
                d1 = (fv1.counter("dispatches")
                      + fv1.counter("chunk_dispatches"))
                dispatch_rec = {"dispatches": d1 - d0, "tokens": toks}
            # steady-state preload: route one request per shared
            # prefix through THIS arm's own policy before the
            # measurement baseline, so every arm measures its steady
            # state rather than its cold start (the dispatch A/B above
            # already warmed the solo arm's single replica — without
            # this the hit-rate comparison would be rigged against the
            # fleet arms, which pay one cold miss per prefix per home)
            for p in mix.prefixes:
                mgr.generate(list(p) + [1, 2], 4, deadline_ms=600_000,
                             timeout=300)
            curve = []
            base = tier_counters(mgr)
            base_fleet = mgr.fleet_snapshot()
            toks_all, dur_all = 0, 0.0
            admitted = completed = failed = 0
            if do_rungs:
                for i, rate in enumerate(rates):
                    slice_n = max(2, int(n_req) // int(obs_per_rate),
                                  min(int(rate * slice_s), 400))
                    toks, dur, offered = 0, 0.0, None
                    adm = com = fai = 0
                    for k in range(int(obs_per_rate)):
                        sched = build_schedule(
                            _process_for(process, rate), mix, slice_n,
                            seed=seed + i * 1000 + k)
                        if offered is None:
                            offered = sched.offered_tokens_per_sec()
                        pt = run_load(mgr, sched, metrics=None)
                        toks += pt["tokens_out"]
                        dur += float(pt["duration_s"])
                        adm += pt["admitted"]
                        com += pt["completed"]
                        fai += pt["failed"]
                    curve.append({
                        "offered_rate_target": rate,
                        "tokens_per_sec": fmt(toks / dur if dur
                                              else 0.0, 1),
                        "tokens_out": toks,
                        "admitted": adm, "completed": com,
                        "failed": fai,
                        "_offered": offered,
                        "_achieved": toks / dur if dur else 0.0,
                    })
                    toks_all += toks
                    dur_all += dur
                    admitted += adm
                    completed += com
                    failed += fai
            tier = tier_counters(mgr)
            fleet_snap = mgr.fleet_snapshot()
            # -- RING-CHURN phase (affinity + pull arms only): spawn
            # replicas until the ring remaps at least one shared
            # prefix onto a newcomer, PREFETCH the moved keys (the
            # fleet tier pulls the warm blocks from their old homes —
            # synchronously, through the same budget and counters the
            # dispatch-time pull uses), then request the moved
            # prefixes: they must HIT on the adopted rows without the
            # newcomer ever recomputing them. Measured AFTER the
            # steady-state counters above so the rung hit rates stay
            # churn-free.
            churn_rec = None
            if policy == "affinity" and pull and do_rungs and n >= 2:
                from deeplearning4j_tpu.serving.fleet import (
                    _build_ring, _ring_hash, _ring_lookup)
                nb = mgr.affinity_block * mgr.affinity_blocks
                keys = [tuple(p[:nb]) for p in mix.prefixes]
                owner0 = {
                    k: _ring_lookup(_build_ring(list(mgr.replicas)),
                                    _ring_hash(k)) for k in keys}
                added, moved = [], []
                for _ in range(4):
                    added.append(mgr.scale_up())
                    ring = _build_ring(list(mgr.replicas))
                    moved = [i for i, k in enumerate(keys)
                             if _ring_lookup(ring, _ring_hash(k))
                             != owner0[k]]
                    if moved:
                        break
                pre = tier_counters(mgr)
                pulled_blocks = sum(
                    mgr.prefetch(list(mix.prefixes[i])) for i in moved)
                h0 = tier_counters(mgr)
                for i in moved:
                    mgr.generate(list(mix.prefixes[i]) + [3, 4], 4,
                                 deadline_ms=600_000, timeout=300)
                post = tier_counters(mgr)
                churn_rec = {
                    "replicas_added": added,
                    "keys_moved": len(moved),
                    "pulled_blocks": pulled_blocks,
                    "prefix_pull_hits": post["prefix_pull_hits"]
                    - pre["prefix_pull_hits"],
                    "prefix_pull_refused": post["prefix_pull_refused"]
                    - pre["prefix_pull_refused"],
                    "prefix_pull_bytes": post["prefix_pull_bytes"]
                    - pre["prefix_pull_bytes"],
                    "rehit_rows_after_pull":
                        post["prefix_rows_hit"] - h0["prefix_rows_hit"],
                }
            snaps = {f"{tag}_{n}": mgr.replica(n).metrics.snapshot()
                     for n in list(mgr.replicas)}
        finally:
            mgr.stop(timeout=120)
            for p in procs_map.values():        # belt and braces
                if p.poll() is None:
                    p.terminate()
            for p in procs_map.values():
                try:
                    p.wait(timeout=30)
                except Exception:   # noqa: BLE001
                    p.kill()
            if tmpdir:
                shutil.rmtree(tmpdir, ignore_errors=True)
        hit = tier["prefix_rows_hit"] - base["prefix_rows_hit"]
        tot = tier["prefix_rows_total"] - base["prefix_rows_total"]
        rec = {
            "policy": policy, "n_replicas": int(n),
            "procs": bool(use_procs), "curve": curve,
            "tokens_per_sec": fmt(toks_all / dur_all if dur_all
                                  else 0.0, 1),
            "admitted": admitted, "completed": completed,
            "failed": failed, "lost": admitted - completed - failed,
            "prefix_rows_hit": hit, "prefix_rows_total": tot,
            "hit_rate": fmt(hit / tot if tot else None, 4),
            "routed_affinity": fleet_snap["fleet_routed_affinity"]
            - base_fleet["fleet_routed_affinity"],
            "routed_spill": fleet_snap["fleet_routed_spill"]
            - base_fleet["fleet_routed_spill"],
            "prefix_pull_hits": tier["prefix_pull_hits"]
            - base["prefix_pull_hits"],
            "prefix_pull_refused": tier["prefix_pull_refused"]
            - base["prefix_pull_refused"],
            "prefix_pull_bytes": tier["prefix_pull_bytes"]
            - base["prefix_pull_bytes"],
            "ring_churn": churn_rec,
            "_achieved": toks_all / dur_all if dur_all else 0.0,
        }
        return rec, snaps, dispatch_rec, fleet_snap

    use_procs = int(procs) >= 2
    n_fleet = int(procs) if use_procs else int(n_replicas)
    # solo reference doubles as the AFFINITY side of the dispatch A/B
    # (a fleet of one routed by the affinity policy IS the solo server,
    # plus the routing code under test)
    solo_rec, solo_snaps, ab_aff, _ = run_arm(
        "affinity", 1, False, False, "solo", do_dispatch=True)
    _, _, ab_base, _ = run_arm(
        "least_backlog", 1, False, False, "dispatch_baseline",
        do_rungs=False, do_dispatch=True)
    aff_rec, aff_snaps, _, aff_fleet = run_arm(
        "affinity", n_fleet, use_procs, True, "affinity")
    lb_rec, lb_snaps, _, _ = run_arm(
        "least_backlog", n_fleet, use_procs, False, "least_backlog")

    def per_tok(rec):
        return rec["dispatches"] / rec["tokens"] if rec["tokens"] \
            else None
    apt, bpt = per_tok(ab_aff), per_tok(ab_base)
    dispatch_ab = {
        "affinity_dispatches": ab_aff["dispatches"],
        "affinity_tokens": ab_aff["tokens"],
        "affinity_dispatches_per_token": fmt(apt, 4),
        "least_backlog_dispatches": ab_base["dispatches"],
        "least_backlog_tokens": ab_base["tokens"],
        "least_backlog_dispatches_per_token": fmt(bpt, 4),
        # the acceptance pin: routing by hash is host-side work — the
        # no-pull affinity path must not add a single device dispatch
        "zero_added_dispatches": (apt is not None and bpt is not None
                                  and apt <= bpt + 1e-9),
    }
    solo_hr = solo_rec["hit_rate"]
    aff_hr = aff_rec["hit_rate"]
    ratio = (aff_hr / solo_hr if solo_hr else None)
    lb_tps = lb_rec["_achieved"]
    goodput_ratio = (aff_rec["_achieved"] / lb_tps if lb_tps else None)
    snaps = {}
    for s in (solo_snaps, aff_snaps, lb_snaps):
        snaps.update(s)
    body = {"server": "fleet_affinity", "n_replicas": n_fleet,
            "process": process, "procs": int(procs),
            "config": f"{n_fleet}x paged bs={bs} "
                      f"{'replica PROCESSES' if use_procs else 'in-process replicas'}"
                      f", SharedPrefixMix P={n_prefixes} "
                      f"blocks=1..2, affinity vs least_backlog vs "
                      f"solo on identical seeded schedules, "
                      f"admission deadline={slo_ms:g}ms",
            "unit": "generated tokens/sec (fleet)",
            "solo": solo_rec, "affinity": aff_rec,
            "least_backlog": lb_rec,
            "hit_rate_ratio_vs_solo": fmt(ratio, 3),
            "hit_rate_retained_09": (ratio is not None
                                     and ratio >= 0.9),
            "goodput_ratio_vs_least_backlog": fmt(goodput_ratio, 3),
            "dispatch_ab": dispatch_ab,
            "curve": aff_rec["curve"], "knee": _knee(aff_rec["curve"]),
            "fleet": aff_fleet}
    return body, snaps, None


def sweep_fleet_chaos(rates, n_replicas=2, n_req=48, slo_ms=250.0,
                      seed=0, process="poisson", trace=False, slots=2,
                      chaos_events=5, slice_s=0.2, cascade=False):
    """The DURABLE-CONTROL-PLANE arm (`--chaos`, needs
    `--fleet-procs N`): the same replica-process fleet as
    `sweep_fleet_procs`, but the manager journals every state
    transition (`serving/fleetjournal.py`) and a SEEDED chaos schedule
    (`serving.loadgen.build_chaos_schedule`) fires between load slices:
    socket severs at the wire fault sites, one injected replica crash,
    and — always — one MANAGER KILL. The kill abandons the live
    `FleetManager` mid-fleet exactly the way a dead process would
    (journal handle gone, sockets half-open) and `FleetManager.recover`
    builds the successor from the journal: live replicas are re-adopted
    over identity-verified HELLOs, the new epoch fences the predecessor
    out (its next control op gets a typed `StaleEpochError`), and any
    shortfall is backfilled.

    The record pins the ISSUE 16 acceptance: every admitted future
    resolves (bit-identical to the quiet-fleet references or failed
    loudly), admitted == completed + failed globally, re-adopted
    replicas' counters stay monotone across the restart, and the
    fenced op is refused with the typed error while zero requests are
    lost. The schedule digest makes the whole run replayable from
    (seed, chaos_events) alone.

    `cascade=True` is the BLAST-RADIUS-CONTAINMENT arm (`--cascade`,
    ISSUE 17): the schedule adds the `poison` action (a request whose
    decode deterministically kills the replica it lands on, via the
    manager's kill hook — two kills convict it, `PoisonPillError`,
    quarantine) and `spawn_fail` (a factory-failure window — the spawn
    circuit breaker opens after K consecutive infant strikes and the
    fleet serves DEGRADED on its survivors instead of crash-looping),
    both composed with the manager kill above. The record pins the
    cascade: the poison request is the ONLY request lost (typed
    verdict), its two kills are the only deaths it causes,
    re-submissions shed at the door (before AND after manager
    recovery — the quarantine is journaled), spawn attempts in the
    breaker window stay <= K, and the accounting still balances.

    Returns (body, per_instance_snaps, merged_trace_or_None)."""
    import concurrent.futures as cf
    import tempfile

    from deeplearning4j_tpu.common.resilience import (FaultInjector,
                                                      RetryBudget,
                                                      RetryPolicy)
    from deeplearning4j_tpu.obs.fleet import merge_traces
    from deeplearning4j_tpu.serving import (CHAOS_ACTIONS, DecodeSizeMix,
                                            FleetManager, PoisonPillError,
                                            RemoteReplica,
                                            ServerClosedError,
                                            ServingMetrics,
                                            StaleEpochError,
                                            build_chaos_schedule,
                                            build_schedule, run_load)
    injector = FaultInjector()
    # cascade: a generous shared budget — wire resends, reconnects and
    # failover replays all spend from it; sized so the seeded storm
    # never exhausts it (exhaustion is a unit-tested verdict, the sweep
    # pins that the machinery runs end-to-end without changing outcomes)
    budget = RetryBudget(capacity=512, initial=512) if cascade else None
    retry = RetryPolicy(max_retries=4, base_delay=0.05, max_delay=0.5,
                        jitter=0.0, budget=budget)
    tmpdir = tempfile.mkdtemp(prefix="fleet_chaos_")
    jpath = os.path.join(tmpdir, "fleet.journal")
    here = os.path.abspath(__file__)
    procs, trace_files = {}, {}

    def launch(name):
        port_file = os.path.join(tmpdir, f"{name}.port")
        trace_out = (os.path.join(tmpdir, f"{name}.trace.json")
                     if trace else None)
        cmd = [sys.executable, here, "--replica-serve",
               "--instance", name, "--port-file", port_file,
               "--identity-file", os.path.join(tmpdir, f"{name}.json"),
               "--slo-ms", str(slo_ms), "--slots", str(slots)]
        if trace_out:
            cmd += ["--trace-out", trace_out]
        procs[name] = _spawn_replica(cmd)
        trace_files[name] = trace_out
        return port_file

    def wait_port(name, port_file, timeout=300.0):
        t0 = time.monotonic()
        while time.monotonic() - t0 < timeout:
            if os.path.exists(port_file):
                return int(open(port_file).read().strip())
            if procs[name].poll() is not None:
                raise RuntimeError(
                    f"replica process {name} exited rc="
                    f"{procs[name].returncode} before binding")
            time.sleep(0.05)
        raise TimeoutError(f"replica {name} never published its port")

    names = [f"i{k}" for k in range(int(n_replicas))]
    ports = {n: launch(n) for n in names}

    spawn_calls = {"n": 0}          # every factory invocation
    spawn_fail_arm = {"on": False}  # the chaos spawn_fail window

    def factory(name):
        spawn_calls["n"] += 1
        if spawn_fail_arm["on"]:
            raise RuntimeError(
                "chaos spawn_fail window: factory refused to spawn")
        port_file = ports.pop(name, None)
        if port_file is None:
            port_file = launch(name)        # backfill / crash respawn
        port = wait_port(name, port_file)
        return RemoteReplica("127.0.0.1", port, name=name,
                             retry_policy=retry, heartbeat_interval=0.1,
                             fault_injector=injector,
                             process=procs[name])

    def redial(name, ident):
        # recovery re-dial: NO name= — the identity check must read the
        # instance the replica CLAIMS in its HELLO, not our expectation
        return RemoteReplica(ident.get("host") or "127.0.0.1",
                             ident["port"], retry_policy=retry,
                             heartbeat_interval=0.1,
                             fault_injector=injector,
                             process=procs.get(name))

    def warmup(srv):
        for p in ([1, 2, 3, 4], list(range(1, 13))):
            srv.generate(p, 4, deadline_ms=600_000, timeout=300)

    if cascade:
        # the containment pool: poison + spawn_fail ride along with
        # wire severs and the guaranteed manager kill (replica_crash
        # stays out — the poison's own kills are the deaths this arm
        # measures). require= fills any action the draw missed, inside
        # the builder, so the digest still pins the timeline.
        schedule = build_chaos_schedule(
            duration_s=max(1.0, float(chaos_events)),
            n_events=max(int(chaos_events), 3), seed=seed,
            actions=("sever_submit", "sever_stream", "poison",
                     "spawn_fail", "manager_kill"),
            require=("poison", "spawn_fail", "manager_kill"))
    else:
        schedule = build_chaos_schedule(
            duration_s=max(1.0, float(chaos_events)),
            n_events=int(chaos_events), seed=seed,
            actions=("sever_submit", "sever_stream", "sever_heartbeat",
                     "replica_crash", "manager_kill"))
    mix = DecodeSizeMix(((0.8, (3, 12), (4, 24)),
                         (0.2, (8, 16), (24, 44))), vocab=96)
    prompts = [[1, 2, 3]] + [[4 + j, 5, 6] for j in range(5)]
    poison_prompt = [13, 13, 13]    # never among the reference prompts

    def kill_hook(prompt, replica_name):
        return list(prompt) == poison_prompt

    # cascade containment knobs: short infancy + backoff so the breaker
    # opens, probes, and closes inside the smoke budget; a journal
    # compaction threshold small enough that the chaos run's record
    # volume actually triggers a fold+rotate before the manager kill
    containment_kw = dict(
        kill_hook=kill_hook, retry_budget=budget,
        infant_mortality_s=0.4, breaker_backoff_s=0.3,
        journal_compact_bytes=768) if cascade else {}
    mgr = FleetManager(factory, n_replicas=n_replicas, warmup=warmup,
                       heartbeat_timeout=2.0, fault_injector=injector,
                       metrics=ServingMetrics(name="fleet"),
                       journal=jpath, **containment_kw)
    stale = None
    admitted = completed = failed = 0
    chaos_log = []
    recovery_rec = None
    poison_fired = False
    cascade_rec = {}

    def fault_batch(tag):
        # plant-then-drive: a planted sever only matters to traffic
        # that crosses the site, so every fault event drives the SAME
        # reference prompts through the disturbed fleet and pins them
        # bit-identical (dedup re-delivery, retry, and failover replay
        # are invisible under deterministic greedy) — or failed LOUDLY
        nonlocal admitted, completed, failed
        futs = [mgr.submit(p, 24, deadline_ms=600_000) for p in prompts]
        admitted += len(futs)
        results, resolved, loud = [], 0, 0
        for f in futs:
            try:
                results.append(list(f.result(300)))
                resolved += 1
            except (cf.TimeoutError, TimeoutError):
                results.append(None)        # the one unacceptable end
            except Exception:   # noqa: BLE001 — loud failure resolves
                results.append(None)
                resolved += 1
                loud += 1
        completed += resolved - loud
        failed += loud
        return {"tag": tag, "all_resolved": resolved == len(futs),
                "loud_failures": loud,
                "bit_identical": results == refs}
    try:
        mgr.start()
        # quiet-fleet references: THE streams every disturbed replay
        # must reproduce (fixed-seed weights ⇒ fleet-wide determinism)
        refs = [list(mgr.generate(p, 24, deadline_ms=600_000,
                                  timeout=300)) for p in prompts]
        slice_n = max(2, int(n_req) // max(1, schedule.n))
        for ev_i, ev in enumerate(schedule.events):
            # real arrivals between faults: one seeded schedule slice
            rate = rates[ev_i % len(rates)]
            sched = build_schedule(_process_for(process, rate), mix,
                                   slice_n, seed=seed + ev_i * 1000)
            pt = run_load(mgr, sched, metrics=None)
            admitted += pt["admitted"]
            completed += pt["completed"]
            failed += pt["failed"]
            action = ev["action"]
            rec = {"t": ev["t"], "action": action}
            if action == "manager_kill":
                pre_fv = mgr.fleet_view()
                pre_done = {n: pre_fv.flat(n).get("completed") or 0
                            for n in pre_fv.instances}
                stale, mgr = mgr, None
                # simulate the manager process dying mid-fleet: its
                # journal handle vanishes with it; its replica sockets
                # stay half-open (the zombie the fencing exists for)
                j, stale._journal = stale._journal, None
                if j is not None:
                    j.close()
                mgr = FleetManager.recover(
                    factory, jpath, redial=redial, identity_dir=tmpdir,
                    n_replicas=n_replicas, warmup=warmup,
                    heartbeat_timeout=2.0, fault_injector=injector,
                    metrics=ServingMetrics(name="fleet"),
                    **containment_kw)
                snap = mgr.fleet_snapshot()
                post_fv = mgr.fleet_view()
                monotone = all(
                    (post_fv.flat(n).get("completed") or 0)
                    >= pre_done.get(n, 0)
                    for n in post_fv.instances if n in pre_done)
                # fencing pin: the predecessor's next control op must
                # be refused with the TYPED error, not half-obeyed
                fenced = None
                victims = [n for n in stale.replicas
                           if n in mgr.replicas]
                if victims:
                    try:
                        stale.replica(victims[0]).drain(timeout=5.0)
                        fenced = False
                    except StaleEpochError:
                        fenced = True
                    except Exception as e:  # noqa: BLE001
                        fenced = f"wrong error: {type(e).__name__}"
                # the zombie's wire halves close LOCALLY only — a
                # STOP/KILL frame from it at live replicas is exactly
                # what the epoch fence forbids
                for n in list(stale.replicas):
                    try:
                        stale.replica(n)._shutdown_local(
                            ServerClosedError(
                                "superseded by recovered manager"),
                            dead=False)
                    except Exception:   # noqa: BLE001
                        pass
                stale._running = False
                recovery_rec = {
                    "epoch": mgr.epoch,
                    "replicas_adopted": snap["fleet_replicas_adopted"],
                    "fenced_op_refused": fenced,
                    "fenced_ops_counted": mgr.fleet_snapshot()[
                        "fleet_fenced_ops"],
                    "counters_monotone_across_restart": monotone,
                }
                if cascade:
                    # the quarantine is journaled: a successor built
                    # from the journal must keep shedding the convicted
                    # prompt at the door, NOT resurrect it onto the
                    # fresh fleet (where its decode would kill again)
                    inherited = None
                    if poison_fired:
                        try:
                            f = mgr.submit(poison_prompt, 12,
                                           deadline_ms=600_000)
                            admitted += 1
                            inherited = False
                            try:
                                f.result(300)
                                completed += 1
                            except Exception:   # noqa: BLE001
                                failed += 1
                        except PoisonPillError:
                            inherited = True
                    recovery_rec["quarantine_inherited"] = inherited
                    recovery_rec["breaker_state_inherited"] = \
                        mgr.breaker_state
                rec["recovery"] = recovery_rec
                rec.update(fault_batch("post_recovery"))
            elif action == "poison":
                # the poison pill: its decode kills the replica it
                # lands on (kill hook), its replay kills the next one,
                # the second death convicts it — PoisonPillError on the
                # outer future, fingerprint quarantined + journaled
                pre_dead = mgr.fleet_snapshot()["fleet_replica_dead"]
                pf = mgr.submit(poison_prompt, 12, deadline_ms=600_000)
                admitted += 1
                try:
                    pf.result(300)
                    verdict = "completed"   # unacceptable — recorded
                    completed += 1
                except PoisonPillError:
                    verdict = "poison_pill"
                    failed += 1
                except Exception as e:      # noqa: BLE001
                    verdict = f"wrong error: {type(e).__name__}"
                    failed += 1
                # a re-submission of the convicted prompt sheds at the
                # door — it must never reach (and kill) a third replica
                reshed = None
                try:
                    f2 = mgr.submit(poison_prompt, 12,
                                    deadline_ms=600_000)
                    admitted += 1
                    reshed = False
                    try:
                        f2.result(300)
                        completed += 1
                    except Exception:       # noqa: BLE001
                        failed += 1
                except PoisonPillError:
                    reshed = True
                mgr.control_tick()  # backfill past the poison's kills
                poison_fired = True
                fsnap = mgr.fleet_snapshot()
                rec["poison"] = {
                    "verdict": verdict,
                    "deaths": fsnap["fleet_replica_dead"] - pre_dead,
                    "resubmission_shed": reshed,
                    "quarantined_counter":
                        fsnap["fleet_requests_quarantined"]}
                rec.update(fault_batch("post_poison"))
            elif action == "spawn_fail":
                # factory-failure window: crash one replica so the
                # control loop must backfill, with every spawn attempt
                # refused — K consecutive strikes OPEN the breaker and
                # the fleet serves degraded on its survivors instead of
                # crash-looping one spawn per tick
                attempts0 = spawn_calls["n"]
                spawn_fail_arm["on"] = True
                victim = mgr.replicas[0]
                mgr._crash(victim, reason="chaos: spawn_fail window")
                mgr.control_tick()  # strikes accumulate; breaker opens
                opened = mgr.breaker_state
                mgr.control_tick()  # OPEN: these ticks may not spawn
                mgr.control_tick()
                attempts = spawn_calls["n"] - attempts0
                rec["breaker"] = {
                    "state_after_window": opened,
                    "spawn_attempts_in_window": attempts,
                    "bounded": attempts <= mgr.breaker_strikes}
                rec.update(fault_batch("degraded"))
                # heal: the window closes, the half-open probe spawns
                # after the backoff, survives infancy, and the breaker
                # closes with the fleet restored to full strength
                spawn_fail_arm["on"] = False
                deadline = time.monotonic() + 60.0
                while (mgr.breaker_state != "closed"
                       or mgr.n_alive() < n_replicas) \
                        and time.monotonic() < deadline:
                    mgr.control_tick()
                    time.sleep(0.05)
                fsnap = mgr.fleet_snapshot()
                rec["breaker"]["recovered_state"] = mgr.breaker_state
                rec["breaker"]["n_alive_after"] = mgr.n_alive()
                rec["breaker"]["breaker_open_total"] = \
                    fsnap["fleet_breaker_open_total"]
                rec["breaker"]["degraded_mode_ticks"] = \
                    fsnap["fleet_degraded_mode_ticks"]
            elif action == "replica_crash":
                injector.plan("fleet.replica",
                              on_call=injector.calls("fleet.replica"),
                              sever=True, exc=None)
                mgr.control_tick()      # fires the crash + backfills
                rec["n_alive_after"] = mgr.n_alive()
                rec.update(fault_batch("post_crash"))
            else:
                site = CHAOS_ACTIONS[action]
                injector.plan(site, on_call=injector.calls(site),
                              sever=True, exc=None)
                rec["site"] = site
                rec.update(fault_batch(action))
            chaos_log.append(rec)
        # the closing wave: the recovered fleet, quiet again, must
        # still serve the reference streams bit-for-bit
        chaos_log.append(fault_batch("final_quiet"))
        final_snap = mgr.fleet_snapshot()
        snaps = {n: mgr.replica(n).metrics.snapshot()
                 for n in mgr.replicas}
        pids = {n: procs[n].pid for n in procs}
        if cascade:
            # journal facts read BEFORE the tmpdir vanishes: a
            # `snapshot` record means compact() folded + rotated the
            # file mid-run (the compaction threshold is set low enough
            # that the chaos run's record volume crosses it)
            from deeplearning4j_tpu.serving import replay_journal
            cascade_rec = {
                "journal_bytes": os.path.getsize(jpath),
                "journal_compacted": any(
                    r.get("kind") == "snapshot"
                    for r in replay_journal(jpath))}
    finally:
        if mgr is not None:
            mgr.stop(timeout=120)
        if stale is not None:
            stale._running = False
        for p in procs.values():        # belt and braces
            if p.poll() is None:
                p.terminate()
        for p in procs.values():
            try:
                p.wait(timeout=30)
            except Exception:   # noqa: BLE001
                p.kill()
    merged = None
    if trace:
        saved, tnames = [], []
        for n, path in trace_files.items():
            if path and os.path.exists(path):
                with open(path) as fh:
                    saved.append(json.load(fh))
                tnames.append(n)
        if saved:
            merged = merge_traces(saved, names=tnames)
    shutil.rmtree(tmpdir, ignore_errors=True)
    body = {"server": "fleet_chaos", "n_replicas": int(n_replicas),
            "process": process,
            "config": f"journaled FleetManager over {n_replicas} "
                      f"replica PROCESSES, slots={slots}, seeded chaos "
                      f"schedule ({schedule.n} events, digest "
                      f"{schedule.digest()[:12]}), one manager "
                      f"kill+recover, admission deadline={slo_ms:g}ms"
                      + (", CASCADE containment arm (poison + "
                         "spawn_fail + shared retry budget)"
                         if cascade else ""),
            "unit": "resolved futures under chaos",
            "chaos": {"seed": seed, "n_events": schedule.n,
                      "digest": schedule.digest(),
                      "events": schedule.events, "log": chaos_log},
            "accounting": {"admitted": admitted, "completed": completed,
                           "failed": failed,
                           "balanced": admitted == completed + failed},
            "recovery": recovery_rec,
            "fleet": final_snap,
            "replica_pids": pids}
    if cascade:
        body["cascade"] = dict(
            cascade_rec,
            poison_prompt=poison_prompt,
            spawn_attempts_total=spawn_calls["n"],
            retry_budget={
                "capacity": budget.capacity,
                "tokens_remaining": budget.tokens,
                "denied": budget.denied})
    return body, snaps, merged


def sweep_microbatch(rates, n_req=96, slo_ms=50.0, seed=0,
                     process="poisson", tracer=None):
    """Rate ladder over the InferenceServer (requests/s domain)."""
    import numpy as np

    from deeplearning4j_tpu.serving import (InferenceServer,
                                            InferenceSizeMix,
                                            ServingMetrics,
                                            build_schedule, run_load)
    net = _mlp()
    metrics = ServingMetrics(slo_target_ms=slo_ms)
    srv = InferenceServer(net, max_batch=8, max_wait_ms=2.0,
                          max_queue=1024, metrics=metrics,
                          tracer=tracer).start()
    mix = InferenceSizeMix(32)
    try:
        # compile every bucket program off the clock
        rng = np.random.default_rng(1)
        xs = rng.standard_normal((8, 32)).astype(np.float32)
        for burst in (1, 4, 8):
            for f in [srv.submit(x) for x in xs[:burst]]:
                f.result(120)
        curve = []
        for i, rate in enumerate(rates):
            sched = build_schedule(_process_for(process, rate), mix,
                                   n_req, seed=seed + i)
            pt = run_load(srv, sched)
            pt["offered_rate_target"] = rate
            pt["_offered"] = pt["schedule"]["offered_rps"]
            pt["_achieved"] = pt["requests_per_sec"]
            curve.append(pt)
        snap = metrics.snapshot()
    finally:
        srv.stop(timeout=120)
    return {"server": "microbatch", "process": process,
            "config": "MLP 32->64->10, max_batch=8 max_wait=2ms, "
                      f"{n_req} reqs/rate, slo={slo_ms:g}ms",
            "unit": "requests/sec",
            "curve": curve, "knee": _knee(curve)}, snap


def _goodput(pt):
    slo = pt.get("slo") or {}
    return slo.get("goodput_tokens_per_sec") or 0.0


# measurement slack for the monotonicity verdict: goodput at the next
# rung may dip this fraction below the previous rung before the curve
# counts as collapsed. The band is wide because it must separate
# CONTROL failure from MACHINE weather: on the shared-CPU measurement
# host, back-to-back identical baseline runs at one rate vary by >2x
# (measured), so a tight slack would assert the scheduler's mood, not
# the controller's. The thing being excluded is unambiguous — the
# uncontrolled baseline drops 4-15x past the knee and fails this
# verdict in every capture; the controlled arm's worst observed
# successive-rung ratio is 0.64.
MONOTONE_SLACK = 0.6


def overload_compare(baseline, controlled, dec_base=None, dec_ctrl=None):
    """The PR 9 acceptance record: the SAME rate ladder through an
    uncontrolled server (the PR 7 baseline semantics) and one with
    chunked prefill + deadline-aware admission. Columns per rate:
    goodput-under-SLO and TTFT p99 for both arms plus the controlled
    arm's shed-reason breakdown; verdicts: controlled goodput
    monotone-nondecreasing past the knee (vs the baseline collapse) and
    TTFT p99 bounded. `dec_base`/`dec_ctrl` are optional span
    decompositions — the sched_gap fraction is chunking's direct
    before/after metric."""
    rows = []
    for b, c in zip(baseline["curve"], controlled["curve"]):
        rows.append({
            "offered_rps": b["offered_rate_target"],
            "goodput_baseline": _goodput(b),
            "goodput_controlled": _goodput(c),
            "ttft_ms_p99_baseline": b.get("ttft_ms_p99"),
            "ttft_ms_p99_controlled": c.get("ttft_ms_p99"),
            "sheds_controlled": c.get("sheds")})
    knee_rate = baseline["knee"]["knee_offered_rate"]
    g_all = [r["goodput_controlled"] for r in rows]
    # past-knee slice: the knee point itself plus everything beyond
    start = next((i for i, r in enumerate(rows)
                  if knee_rate is None or r["offered_rps"] >= knee_rate),
                 0)
    g = g_all[start:]
    monotone = all(g[i + 1] >= MONOTONE_SLACK * g[i]
                   for i in range(len(g) - 1))
    gb = [r["goodput_baseline"] for r in rows[start:]]
    collapse = (round(max(gb) / min(gb), 2)
                if gb and min(gb) > 0 else None)
    ttft_c = [r["ttft_ms_p99_controlled"] for r in rows
              if r["ttft_ms_p99_controlled"] is not None]
    ttft_b = [r["ttft_ms_p99_baseline"] for r in rows
              if r["ttft_ms_p99_baseline"] is not None]
    out = {"server": "decode_overload_ab",
           "knee_offered_rate": knee_rate,
           "rows": rows,
           "controlled_goodput_monotone_past_knee": monotone,
           "monotone_slack": MONOTONE_SLACK,
           "baseline_goodput_collapse_x": collapse,
           "ttft_ms_p99_max": {"baseline": max(ttft_b, default=None),
                               "controlled": max(ttft_c, default=None)}}
    if dec_base and dec_ctrl:
        out["sched_gap_fraction"] = {
            "baseline": (dec_base.get("fractions") or {}).get(
                "sched_gap_ms"),
            "controlled": (dec_ctrl.get("fractions") or {}).get(
                "sched_gap_ms")}
    return out


def run_sweep(server="both", rates=(50, 100, 200, 400, 800),
              process="poisson", n_req=64, slo_ms=150.0, seed=0,
              trace=True, report_path=None, paged=False,
              chunked_prefill=None, admission=None, overload_ab=False,
              speculate_k=None, preempt=False, fused_serve=None,
              fleet=0,
              fleet_obs_per_rate=6, fleet_slice_s=0.25,
              fleet_control=False, fleet_injector=None,
              fleet_min=None, fleet_max=None, fleet_procs=0,
              chaos=False, chaos_events=5, cascade=False,
              affinity=False):
    """Drive the sweep(s) and (optionally) write the combined
    obs_report (JSON + text + Chrome trace). Returns the results list.
    The tier-1 smoke test calls this with tiny parameters (and once
    with paged=True so CI exercises the block-gated admission path).
    `overload_ab=True` replays the decode ladder through BOTH an
    uncontrolled baseline and a chunked+admission arm and appends the
    comparison record (goodput monotonicity past the knee — the PR 9
    acceptance pin). `fleet=N` (N >= 2) replaces the single decode
    server with N round-robin replicas + the fleet observability plane
    (sweep_fleet): the report's trace becomes the clock-anchor-MERGED
    multi-instance trace (written as `<report>.trace.merged.json`) and
    every rate rung carries the autoscale decision sequence."""
    from deeplearning4j_tpu.obs import Tracer, decompose
    fleet = int(fleet or 0)
    fleet_procs = int(fleet_procs or 0)
    if fleet_procs == 1:
        raise ValueError("--fleet-procs needs N >= 2 replica processes "
                         "(a fleet of one is the plain decode sweep — "
                         "drop the flag)")
    if fleet_procs and (fleet or fleet_control or overload_ab):
        raise ValueError("--fleet-procs is its own scenario: drop "
                         "--fleet/--fleet-control/--overload-ab")
    if affinity and (fleet or fleet_control or overload_ab or chaos):
        raise ValueError("--affinity is its own scenario (solo vs "
                         "affinity vs least_backlog on one shared-"
                         "prefix workload): drop --fleet/"
                         "--fleet-control/--overload-ab/--chaos")
    if affinity and server not in ("decode", "both"):
        raise ValueError("--affinity needs --server decode (or both): "
                         "the prefix-affinity arm drives paged DECODE "
                         "replicas")
    if chaos and fleet_procs < 2:
        raise ValueError("--chaos needs --fleet-procs N (>= 2): the "
                         "chaos schedule kills and recovers the "
                         "manager of a replica-PROCESS fleet — "
                         "silently running without it would discard "
                         "the flag")
    if cascade and not chaos:
        raise ValueError("--cascade extends the --chaos schedule with "
                         "poison + spawn_fail: add --chaos (and "
                         "--fleet-procs N >= 3)")
    if cascade and fleet_procs < 3:
        raise ValueError("--cascade needs --fleet-procs N (>= 3): the "
                         "poison pill kills TWO replicas before it is "
                         "convicted, and a survivor must keep serving "
                         "the co-victims it failed over")
    if fleet_procs and server not in ("decode", "both"):
        raise ValueError("--fleet-procs needs --server decode (or "
                         "both): the wire fleet drives DECODE replica "
                         "processes")
    if fleet == 1:
        raise ValueError("--fleet needs N >= 2 replicas (a fleet of "
                         "one is the plain decode sweep — drop the "
                         "flag)")
    if fleet_control and fleet < 2:
        raise ValueError("--fleet-control needs --fleet N (>= 2): the "
                         "closed loop drives a replica FLEET")
    fleet_mode = fleet >= 2 and server in ("decode", "both")
    if fleet_control and not fleet_mode:
        raise ValueError("--fleet-control needs --server decode (or "
                         "both): the closed loop drives DECODE "
                         "replicas — silently running the plain "
                         f"{server!r} ladder would discard the flag")
    if fleet_mode and overload_ab:
        raise ValueError("--fleet and --overload-ab are mutually "
                         "exclusive: the overload A/B compares one "
                         "controlled server against one baseline — "
                         "run them as separate sweeps")
    tracer = (Tracer(capacity=1 << 16, enabled=True)
              if trace and not (fleet_mode or fleet_procs or affinity)
              else None)
    fleet_trace = None
    results, snaps = [], {}
    if affinity:
        body, inst_snaps, fleet_trace = sweep_fleet_affinity(
            rates, n_replicas=3, n_req=n_req, slo_ms=slo_ms, seed=seed,
            process=process, trace=trace, procs=fleet_procs,
            obs_per_rate=fleet_obs_per_rate, slice_s=fleet_slice_s)
        results.append(body)
        snaps.update({f"fleet_{n}": s for n, s in inst_snaps.items()})
    elif fleet_procs >= 2 and chaos:
        body, inst_snaps, fleet_trace = sweep_fleet_chaos(
            rates, n_replicas=fleet_procs, n_req=n_req, slo_ms=slo_ms,
            seed=seed, process=process, trace=trace,
            chaos_events=chaos_events, cascade=cascade)
        results.append(body)
        snaps.update({f"fleet_{n}": s for n, s in inst_snaps.items()})
    elif fleet_procs >= 2:
        body, inst_snaps, fleet_trace = sweep_fleet_procs(
            rates, n_replicas=fleet_procs, n_req=n_req, slo_ms=slo_ms,
            seed=seed, process=process, trace=trace, paged=paged,
            obs_per_rate=fleet_obs_per_rate, slice_s=fleet_slice_s,
            fault_injector=fleet_injector)
        results.append(body)
        snaps.update({f"fleet_{n}": s for n, s in inst_snaps.items()})
    elif fleet_mode and fleet_control:
        body, inst_snaps, fleet_trace = sweep_fleet_control(
            rates, n_replicas=fleet, n_req=n_req, slo_ms=slo_ms,
            seed=seed, process=process, trace=trace,
            obs_per_rate=fleet_obs_per_rate, slice_s=fleet_slice_s,
            fault_injector=fleet_injector, min_replicas=fleet_min,
            max_replicas=fleet_max)
        results.append(body)
        snaps.update({f"fleet_{n}": s for n, s in inst_snaps.items()})
    elif fleet_mode:
        body, inst_snaps, fleet_trace = sweep_fleet(
            rates, n_replicas=fleet, n_req=n_req, slo_ms=slo_ms,
            seed=seed, process=process, trace=trace,
            obs_per_rate=fleet_obs_per_rate, slice_s=fleet_slice_s)
        results.append(body)
        snaps.update({f"fleet_{n}": s for n, s in inst_snaps.items()})
    elif overload_ab and server in ("decode", "both"):
        # EQUAL OFFERED DURATION per rung, both arms on identical
        # schedules: requests scale with rate (~1.5 s of traffic each),
        # because at a fixed count higher rates compress the arrival
        # window and shrink the in-SLO-completable work — absolute
        # goodput would decline past the knee for ANY controller. The
        # window is long enough that the admission loop's feedback
        # (bias, hysteresis, saturated-capacity) reaches equilibrium
        # inside each rung instead of measuring its transient.
        n_list = [min(max(24, int(r * 1.5)), 1500) for r in rates]
        print(json.dumps({"overload_ab_requests_per_rung": n_list,
                          "note": "--requests is overridden: equal "
                                  "offered duration per rung"}),
              file=sys.stderr)
        body_b, snap_b = sweep_decode(rates, n_req=n_list,
                                      slo_ms=slo_ms,
                                      seed=seed, process=process,
                                      tracer=tracer, paged=paged)
        tracer_c = Tracer(capacity=1 << 16, enabled=True) if trace \
            else None
        body_c, snap_c = sweep_decode(
            rates, n_req=n_list, slo_ms=slo_ms, seed=seed,
            process=process, tracer=tracer_c, paged=paged,
            chunked_prefill=(chunked_prefill or 8), admission=True)
        cmp_rec = overload_compare(
            body_b, body_c,
            decompose(tracer) if tracer else None,
            decompose(tracer_c) if tracer_c else None)
        results.extend([body_b, body_c, cmp_rec])
        snaps["decode_baseline"] = snap_b
        snaps["decode_controlled"] = snap_c
    elif server in ("decode", "both"):
        body, snap = sweep_decode(rates, n_req=n_req, slo_ms=slo_ms,
                                  seed=seed, process=process,
                                  tracer=tracer, paged=paged,
                                  chunked_prefill=chunked_prefill,
                                  admission=admission,
                                  speculate_k=speculate_k,
                                  preempt=preempt,
                                  fused_serve=fused_serve)
        results.append(body)
        snaps["decode"] = snap
    if server in ("microbatch", "both"):
        # the micro-batch rates ride the same ladder; its own tracer
        # would collide with the decode server's req-<id> lanes, so the
        # shared tracer is decode-only and decomposition covers decode
        mb_rates = tuple(max(20, r // 2) for r in rates)
        body, snap = sweep_microbatch(mb_rates, n_req=n_req,
                                      slo_ms=min(slo_ms, 50.0),
                                      seed=seed, process=process)
        results.append(body)
        snaps["microbatch"] = snap
    if report_path:
        # obs_report lives next to this file, not under the repo-root
        # entry this module inserts — `python -m tools.load_sweep` or an
        # importing test must not lose a finished sweep at report time
        tools_dir = os.path.dirname(os.path.abspath(__file__))
        if tools_dir not in sys.path:
            sys.path.insert(0, tools_dir)
        from obs_report import build_report, format_report
        report = build_report(
            spans=fleet_trace if fleet_trace is not None else tracer,
            metrics=snaps)
        report["sweep"] = results
        with open(report_path + ".json", "w") as fh:
            json.dump(report, fh)
        with open(report_path + ".txt", "w") as fh:
            fh.write(format_report(report) + "\n")
            for r in results:
                fh.write(f"\n== sweep: {r['server']} "
                         f"({r.get('process', 'comparison')}) ==\n")
                for pt in r.get("curve") or r.get("rows") or ():
                    fh.write(json.dumps(pt) + "\n")
                if "knee" in r:
                    fh.write(json.dumps(r["knee"]) + "\n")
        if fleet_trace is not None:
            # the fleet's one trace artifact IS the merged trace: every
            # replica's process group on one clock-anchored timeline
            with open(report_path + ".trace.merged.json", "w") as fh:
                json.dump(fleet_trace, fh)
        if tracer is not None:
            tracer.save(report_path + ".trace.json")
    return results


def main():
    if "--replica-serve" in sys.argv:
        # child-process mode: this invocation IS one wire replica
        return _replica_serve_main(sys.argv[1:])
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--server", default="both",
                    choices=("decode", "microbatch", "both"))
    ap.add_argument("--rates", default="50,100,200,400,800",
                    help="comma-separated offered rates (requests/sec; "
                         "concurrency levels for --process closed)")
    ap.add_argument("--process", default="poisson",
                    choices=("poisson", "onoff", "closed"))
    ap.add_argument("--requests", type=int, default=64,
                    help="requests per sweep point")
    ap.add_argument("--slo-ms", type=float, default=150.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--report", default=None,
                    help="write obs_report JSON/text/trace under this "
                         "path prefix")
    ap.add_argument("--no-trace", action="store_true",
                    help="disable span tracing (no decomposition in "
                         "the report)")
    ap.add_argument("--paged", action="store_true",
                    help="decode server uses the paged block-table KV "
                         "cache (equal-bytes arena) instead of fixed "
                         "slots")
    ap.add_argument("--speculate", type=int, default=None, metavar="K",
                    help="K-wide n-gram speculative decode on the "
                         "decode server (composes with --paged: the "
                         "block-table verify program)")
    ap.add_argument("--fused-serve", type=int, default=None,
                    metavar="K",
                    help="scan K decode iterations into one device "
                         "dispatch on the decode server (composes "
                         "with --paged; excludes --speculate — the "
                         "server refuses the combination)")
    ap.add_argument("--fleet", type=int, default=0, metavar="N",
                    help="drive N in-process decode replicas behind a "
                         "round-robin splitter (named instances, "
                         "federated metrics, one AutoscaleSignal fed "
                         "per schedule slice, clock-anchor-merged "
                         "trace) instead of one decode server")
    ap.add_argument("--fleet-control", action="store_true",
                    help="CLOSED-LOOP fleet arm (needs --fleet N): a "
                         "FleetManager drives replica count — one "
                         "control tick per schedule slice ACTS on the "
                         "AutoscaleSignal (scale_up spawns a warmed "
                         "replica, scale_down drains one with live-"
                         "request migration); the record pins goodput "
                         "recovery after the spawn and the quiet-tail "
                         "return to min replicas")
    ap.add_argument("--fleet-min", type=int, default=None,
                    help="fleet-control floor (default: the initial N)")
    ap.add_argument("--fleet-max", type=int, default=None,
                    help="fleet-control ceiling (default: N + 4)")
    ap.add_argument("--fleet-procs", type=int, default=0, metavar="N",
                    help="drive N replica PROCESSES behind the serving "
                         "wire (serving/wire.py): each replica is a "
                         "real child process serving the socket "
                         "protocol, routed by the FleetManager; after "
                         "the rate rungs one socket sever is injected "
                         "mid-stream and the record pins zero lost "
                         "requests + bit-identical streams + the "
                         "merged trace covering every replica pid")
    ap.add_argument("--chaos", action="store_true",
                    help="DURABLE-CONTROL-PLANE arm (needs "
                         "--fleet-procs N): journal every fleet state "
                         "transition, fire a seeded chaos schedule "
                         "(socket severs, a replica crash, one MANAGER "
                         "kill) between load slices, recover the "
                         "manager from the journal with replica "
                         "re-adoption, and pin: every admitted future "
                         "resolves (bit-identical or loudly failed), "
                         "admitted == completed + failed, the stale "
                         "manager's next control op is epoch-fenced")
    ap.add_argument("--chaos-events", type=int, default=5, metavar="E",
                    help="chaos schedule length (>= 1; one is always "
                         "a manager kill)")
    ap.add_argument("--affinity", action="store_true",
                    help="PREFIX-AFFINITY arm: a seeded shared-system-"
                         "prompt workload (SharedPrefixMix) over 3 "
                         "paged replicas (or --fleet-procs N replica "
                         "PROCESSES) three ways — solo reference, "
                         "consistent-hash affinity routing with the "
                         "fleet prefix tier (cross-replica block "
                         "pulls), least-backlog baseline — recording "
                         "fleet hit rate vs solo, pull counts/bytes, "
                         "goodput vs baseline, and the zero-added-"
                         "dispatch A/B for the no-pull path")
    ap.add_argument("--cascade", action="store_true",
                    help="BLAST-RADIUS-CONTAINMENT arm (needs --chaos "
                         "and --fleet-procs N >= 3): the schedule adds "
                         "a poison request (its decode kills the "
                         "replica it lands on; two kills convict it — "
                         "typed PoisonPillError + journaled "
                         "quarantine) and a spawn_fail factory window "
                         "(the spawn circuit breaker opens after K "
                         "strikes; the fleet serves degraded instead "
                         "of crash-looping), with a shared fleet-wide "
                         "retry budget gating resends and replays")
    ap.add_argument("--preempt", action="store_true",
                    help="durable-KV preemption (implies --paged): the "
                         "mix's long tail submits as a spillable batch "
                         "class, short turns as interactive — batch "
                         "slots spill to host when interactive work "
                         "is blocked on KV blocks")
    ap.add_argument("--chunked-prefill", type=int, default=None,
                    metavar="C",
                    help="slice prompts into C-row prefill chunks "
                         "(head-of-line surgery; >= 2)")
    ap.add_argument("--admission", action="store_true",
                    help="deadline-aware admission: shed predicted "
                         "deadline misses at enqueue (requests get the "
                         "SLO as their deadline)")
    ap.add_argument("--overload-ab", action="store_true",
                    help="run the decode ladder through BOTH a baseline "
                         "and a chunked+admission arm and append the "
                         "goodput-monotonicity comparison record. "
                         "OVERRIDES --requests: each rung offers ~1.5 s "
                         "of traffic (requests scale with rate) so "
                         "goodput is comparable across rungs")
    args = ap.parse_args()
    enable_compile_cache()
    rates = tuple(float(r) for r in args.rates.split(","))
    t0 = time.perf_counter()
    results = run_sweep(server=args.server, rates=rates,
                        process=args.process, n_req=args.requests,
                        slo_ms=args.slo_ms, seed=args.seed,
                        trace=not args.no_trace,
                        report_path=args.report, paged=args.paged,
                        chunked_prefill=args.chunked_prefill,
                        admission=args.admission,
                        overload_ab=args.overload_ab,
                        speculate_k=args.speculate,
                        fused_serve=args.fused_serve,
                        preempt=args.preempt, fleet=args.fleet,
                        fleet_control=args.fleet_control,
                        fleet_min=args.fleet_min,
                        fleet_max=args.fleet_max,
                        fleet_procs=args.fleet_procs,
                        chaos=args.chaos,
                        chaos_events=args.chaos_events,
                        cascade=args.cascade,
                        affinity=args.affinity)
    for r in results:
        print(json.dumps(r))
    print(json.dumps({"elapsed_s": fmt(time.perf_counter() - t0, 1),
                      "report": args.report and args.report
                      + ".{json,txt,trace.json}"}))


if __name__ == "__main__":
    main()
