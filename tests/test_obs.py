"""Observability-layer pins (ISSUE 6 acceptance criteria).

  (a) Export schemas: Chrome trace-event JSON carries name/cat/ph/ts/dur
      on every complete event (loads in Perfetto), and the Prometheus
      text route on ui/server.py serves registry counters/summaries.
  (b) Correct nesting: a served request's queue-wait span sits inside
      its request span; a fused training dispatch sits inside its
      fused-group span.
  (c) Cost pins: a DISABLED tracer's span() is nanosecond-scale per
      call, and tracing (on or off) adds ZERO device dispatches — the
      obs package never imports jax/numpy (structural pin) and a traced
      serve run's dispatch counter equals an untraced one's.
  (d) MetricsRegistry storage keys through ui.stats.ServingStatsReporter
      are pinned so renames fail a test; SLO counters (deadline
      attainment, goodput) and the queue-depth-at-enqueue staleness fix
      are pinned through the real servers.
  (e) Flight recorder: rolling-p99 threshold arms the tracer for the
      next N spans and stores the capture.
"""
import contextlib
import json
import os
import time
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import (InputType, MultiLayerNetwork,
                                NeuralNetConfiguration)
from deeplearning4j_tpu import obs
from deeplearning4j_tpu.models.zoo.transformer import TransformerLM
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.obs import FlightRecorder, MetricsRegistry, Tracer
from deeplearning4j_tpu.obs.registry import (default_registry, fmt,
                                             reset_default_registry,
                                             sanitize)
from deeplearning4j_tpu.serving import (ContinuousDecodeServer,
                                        InferenceServer, ServingMetrics)


def _mln(seed=7, n_in=6, n_out=4):
    conf = (NeuralNetConfiguration.Builder().seed(seed)
            .updater("adam").learning_rate(0.01).list()
            .layer(0, DenseLayer(n_out=16, activation="relu"))
            .layer(1, OutputLayer(n_out=n_out, activation="softmax",
                                  loss_function="mcxent"))
            .set_input_type(InputType.feed_forward(n_in))
            .build())
    return MultiLayerNetwork(conf).init()


def _lm(seed=3):
    return TransformerLM(64, d_model=16, n_heads=2, n_layers=1,
                         max_len=48, seed=seed)


@contextlib.contextmanager
def _global_tracer(tracer):
    """Swap the process-wide tracer (the one the fit loops record on)."""
    old = obs.TRACER
    obs.TRACER = tracer
    try:
        yield tracer
    finally:
        obs.TRACER = old


def _events(tracer, name=None, ph="X"):
    evs = [e for e in tracer.chrome_trace()["traceEvents"]
           if e.get("ph") == ph]
    return evs if name is None else [e for e in evs if e["name"] == name]


def _contains(outer, inner, slack_us=1.0):
    return (inner["ts"] >= outer["ts"] - slack_us
            and inner["ts"] + inner["dur"]
            <= outer["ts"] + outer["dur"] + slack_us)


# ---------------------------------------------------------------------------
# (a) export schemas
# ---------------------------------------------------------------------------
class TestTraceSchema:
    def test_chrome_trace_event_schema(self):
        """The pinned trace-event contract: complete events carry
        name/cat/ph/ts/dur (+pid/tid), metadata events name the tracks —
        exactly what Perfetto/chrome://tracing load."""
        t = Tracer(enabled=True)
        with t.span("outer", cat="test", track="lane", k=2):
            with t.span("inner", cat="test", track="lane"):
                pass
        t.instant("marker", cat="test")
        ct = t.chrome_trace(process_name="proc")
        assert set(ct) == {"traceEvents", "displayTimeUnit"}
        xs = [e for e in ct["traceEvents"] if e.get("ph") == "X"]
        assert len(xs) == 3          # outer, inner, marker(dur 0)
        for e in xs:
            for key in ("name", "cat", "ph", "ts", "dur", "pid", "tid",
                        "args"):
                assert key in e, f"missing {key} in {e}"
            assert isinstance(e["ts"], (int, float))
            assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
        metas = [e for e in ct["traceEvents"] if e.get("ph") == "M"]
        assert {m["name"] for m in metas} >= {"process_name",
                                              "thread_name"}
        # inner nests inside outer on the same tid
        outer = next(e for e in xs if e["name"] == "outer")
        inner = next(e for e in xs if e["name"] == "inner")
        assert outer["tid"] == inner["tid"]
        assert _contains(outer, inner)
        assert outer["args"]["k"] == 2

    def test_save_round_trips_as_json(self, tmp_path):
        t = Tracer(enabled=True)
        with t.span("a"):
            pass
        path = t.save(str(tmp_path / "t.trace.json"))
        with open(path) as fh:
            data = json.load(fh)
        assert any(e.get("ph") == "X" and e["name"] == "a"
                   for e in data["traceEvents"])

    def test_ring_is_bounded(self):
        t = Tracer(capacity=16, enabled=True)
        for i in range(100):
            t.emit(f"s{i}", i, 1)
        spans = t.spans()
        assert len(spans) == 16
        assert spans[0].name == "s84"       # oldest fell off the far end

    def test_registry_prometheus_text(self):
        reg = MetricsRegistry()
        reg.counter("serve.requests").inc(5)
        reg.gauge("queue.depth").set(3)
        res = reg.reservoir("latency_ms", window=16)
        for v in (1.0, 2.0, 3.0, 4.0):
            res.record(v)
        text = reg.prometheus_text(namespace="dl4j_tpu")
        assert "# TYPE dl4j_tpu_serve_requests counter" in text
        assert "dl4j_tpu_serve_requests 5" in text
        assert "# TYPE dl4j_tpu_queue_depth gauge" in text
        assert "dl4j_tpu_queue_depth 3.0" in text
        assert "# TYPE dl4j_tpu_latency_ms summary" in text
        assert 'dl4j_tpu_latency_ms{quantile="0.5"}' in text
        assert 'dl4j_tpu_latency_ms{quantile="0.99"}' in text
        assert "dl4j_tpu_latency_ms_count 4" in text

    def test_registry_kind_conflict_raises(self):
        reg = MetricsRegistry()
        reg.counter("x")
        with pytest.raises(TypeError):
            reg.gauge("x")
        reg.histogram("h")
        with pytest.raises(TypeError):
            reg.reservoir("h")

    def test_histogram_buckets_and_quantiles(self):
        """The fixed-bucket histogram kind (ISSUE 7): cumulative
        `_bucket{le=}`/`_sum`/`_count` exposition, interpolated
        quantile estimates, overflow clamped to the largest bound."""
        from deeplearning4j_tpu.obs import Histogram
        h = Histogram("lat", buckets=(1, 2, 5, 10))
        assert h.quantile(50) is None           # empty: no data
        for v in (0.5, 1.5, 3.0, 4.0, 7.0, 50.0):
            h.observe(v)
        assert h.counts() == [1, 1, 2, 1, 1]    # last = +Inf overflow
        assert h.total == 6 and h.sum == 66.0
        # interpolated within the (2, 5] bucket holding the median
        assert 2.0 < h.quantile(50) <= 5.0
        assert h.quantile(99) == 10.0           # overflow clamps
        assert h.mean() == pytest.approx(11.0)

    def test_histogram_prometheus_exposition(self):
        reg = MetricsRegistry()
        h = reg.histogram("req.ttft_ms", buckets=(1, 10, 100))
        h.observe(5.0)
        h.observe(500.0)
        text = reg.prometheus_text(namespace="dl4j_tpu")
        assert "# TYPE dl4j_tpu_req_ttft_ms histogram" in text
        assert 'dl4j_tpu_req_ttft_ms_bucket{le="1"} 0' in text
        assert 'dl4j_tpu_req_ttft_ms_bucket{le="10"} 1' in text
        assert 'dl4j_tpu_req_ttft_ms_bucket{le="100"} 1' in text
        assert 'dl4j_tpu_req_ttft_ms_bucket{le="+Inf"} 2' in text
        assert "dl4j_tpu_req_ttft_ms_sum 505.0" in text
        assert "dl4j_tpu_req_ttft_ms_count 2" in text
        snap = reg.snapshot()
        assert snap["req.ttft_ms_count"] == 2
        assert snap["req.ttft_ms_p50"] is not None

    def test_clock_sync_anchors_traces_for_alignment(self):
        """Trace-alignment fix (ISSUE 7): spans are timed on the bare
        monotonic clock, so two saved traces were un-alignable. Every
        chrome_trace() now carries a `clock_sync` metadata event whose
        `wallclock_ns_at_ts0` anchors ts=0 to the wall clock; two
        traces align by shifting one by the anchor difference."""
        t1 = Tracer(enabled=True)
        with t1.span("a"):
            pass
        time.sleep(0.05)
        t2 = Tracer(enabled=True)
        with t2.span("b"):
            pass

        def anchor(t):
            (cs,) = [e for e in t.chrome_trace()["traceEvents"]
                     if e.get("name") == "clock_sync"]
            assert cs["ph"] == "M"
            assert "wallclock_iso" in cs["args"]
            return (cs["args"]["wallclock_ns_at_ts0"],
                    cs["args"]["monotonic_ns_at_ts0"])
        w1, m1 = anchor(t1)
        w2, m2 = anchor(t2)
        # the anchors agree with the real elapsed time: wall-clock
        # difference == monotonic difference (same process, so the two
        # clocks tick together; 10ms slack for clock-read jitter)
        assert w2 > w1 and m2 > m1
        assert abs((w2 - w1) - (m2 - m1)) < 10e6
        # and the anchor is an actual recent wallclock time
        assert abs(time.time_ns() - w2) < 60e9

    def test_sanitize_and_fmt(self):
        assert sanitize("a.b-c d") == "a_b_c_d"
        assert sanitize("9lives")[0] == "_"
        assert fmt(None) is None
        assert fmt(1.23456) == 1.235
        assert fmt(1.23456, 1) == 1.2

    def test_histogram_bucketwise_merge_is_pooled(self):
        """The aggregability contract federation depends on (ISSUE 12):
        element-wise summing two histograms' bucket counts gives
        `bucket_quantile` results EQUAL to a single histogram that
        observed the pooled samples — merged counts ARE the pooled
        histogram's counts, so the invariant is exact, not
        approximate."""
        import random

        from deeplearning4j_tpu.obs import Histogram
        from deeplearning4j_tpu.obs.registry import bucket_quantile
        grid = (1, 5, 25, 100, 500)
        h1, h2, pooled = (Histogram(n, buckets=grid)
                          for n in ("a", "b", "p"))
        rng = random.Random("agg-pin")
        for _ in range(300):
            v = rng.uniform(0.0, 700.0)
            (h1 if rng.random() < 0.4 else h2).observe(v)
            pooled.observe(v)
        merged = [a + b for a, b in zip(h1.counts(), h2.counts())]
        assert merged == pooled.counts()
        assert sum(merged) == 300
        for q in (1, 25, 50, 75, 99):
            assert bucket_quantile(grid, merged, q) == \
                pooled.quantile(q)

    def test_chrome_trace_pid_and_instance_metadata(self):
        """Satellite pin (ISSUE 12): every event carries an explicit
        pid (settable, default 0) and process_name defaults to the
        tracer's instance name — the hooks merged multi-server traces
        need — while the default export stays schema-compatible with
        every existing consumer."""
        t = Tracer(enabled=True)
        with t.span("x"):
            pass
        ct = t.chrome_trace()
        assert all(e["pid"] == 0 for e in ct["traceEvents"])
        (pn,) = [e for e in ct["traceEvents"]
                 if e.get("ph") == "M" and e["name"] == "process_name"]
        assert pn["args"]["name"] == "deeplearning4j_tpu"

        ti = Tracer(enabled=True, instance="i3")
        with ti.span("y"):
            pass
        ct3 = ti.chrome_trace(pid=7)
        assert all(e["pid"] == 7 for e in ct3["traceEvents"])
        (pn3,) = [e for e in ct3["traceEvents"]
                  if e.get("ph") == "M" and e["name"] == "process_name"]
        assert pn3["args"]["name"] == "i3"
        (cs,) = [e for e in ct3["traceEvents"]
                 if e.get("ph") == "M" and e["name"] == "clock_sync"]
        assert cs["args"]["instance"] == "i3"

    def test_prometheus_instance_label(self):
        """instance= labels EVERY exposition sample (counter, gauge,
        histogram buckets incl. +Inf, summary quantiles) and composes
        with existing labels; default output is label-free."""
        reg = MetricsRegistry()
        reg.counter("c").inc(2)
        reg.gauge("g").set(1.5)
        reg.histogram("h", buckets=(1, 10)).observe(5.0)
        res = reg.reservoir("r", window=8)
        res.record(3.0)
        text = reg.prometheus_text(namespace="ns", instance="i0")
        assert 'ns_c{instance="i0"} 2' in text
        assert 'ns_g{instance="i0"} 1.5' in text
        assert 'ns_h_bucket{le="1",instance="i0"} 0' in text
        assert 'ns_h_bucket{le="+Inf",instance="i0"} 1' in text
        assert 'ns_h_count{instance="i0"} 1' in text
        assert 'ns_r{quantile="0.5",instance="i0"} 3.0' in text
        assert 'ns_r_count{instance="i0"} 1' in text
        plain = reg.prometheus_text(namespace="ns")
        assert "instance=" not in plain


class TestPrometheusRoute:
    def test_metrics_route_serves_registry(self):
        from deeplearning4j_tpu.ui import UIServer
        reg = MetricsRegistry()
        reg.counter("train.health.ok").inc(7)
        m = ServingMetrics(registry=reg, name="s1", slo_target_ms=50)
        m.record_request(10.0, tokens=4)
        server = UIServer(port=0).attach_metrics(reg).start()
        try:
            url = f"http://127.0.0.1:{server.port}/metrics"
            with urllib.request.urlopen(url) as r:
                assert r.headers["Content-Type"].startswith("text/plain")
                text = r.read().decode()
            assert "dl4j_tpu_train_health_ok 7" in text
            # ServingMetrics built over a shared registry exports its
            # counters on the same route, namespaced by endpoint name
            assert "dl4j_tpu_serving_s1_completed 1" in text
            assert "dl4j_tpu_serving_s1_slo_met 1" in text
            assert 'dl4j_tpu_serving_s1_latency_ms{quantile="0.5"} 10.0' \
                in text
        finally:
            server.stop()

    def test_metrics_route_with_instance_label(self):
        """attach_metrics(..., instance=) labels every sample — the
        federation-friendly exposition a fleet's per-replica routes
        serve, round-trippable by obs.fleet.parse_prometheus_text."""
        from deeplearning4j_tpu.obs.fleet import FleetView
        from deeplearning4j_tpu.ui import UIServer
        reg = MetricsRegistry()
        m = ServingMetrics(registry=reg, name="r0", slo_target_ms=50)
        m.record_request(10.0, tokens=4)
        server = UIServer(port=0).attach_metrics(
            reg, instance="replica-0").start()
        try:
            url = f"http://127.0.0.1:{server.port}/metrics"
            with urllib.request.urlopen(url) as r:
                text = r.read().decode()
            assert 'instance="replica-0"' in text
            assert 'dl4j_tpu_serving_r0_completed{instance="replica-0"}'\
                ' 1' in text
            fv = FleetView().add(
                "replica-0", text,
                strip_prefix="dl4j_tpu_serving_r0_")
            assert fv.counter("completed") == 1
        finally:
            server.stop()


# ---------------------------------------------------------------------------
# (b) correct nesting through the real servers / fit loops
# ---------------------------------------------------------------------------
class TestServedRequestTrace:
    def test_decode_request_spans_nest(self, tmp_path):
        t = Tracer(enabled=True)
        lm = _lm()
        with ContinuousDecodeServer(lm, slots=2, prompt_buckets=(8,),
                                    tracer=t) as srv:
            srv.generate([1, 2, 3], 6, timeout=120)
        req = _events(t, "serve.request")
        qw = _events(t, "serve.queue_wait")
        assert len(req) == 1 and len(qw) == 1
        assert req[0]["tid"] == qw[0]["tid"]    # same req-<id> lane
        assert _contains(req[0], qw[0])
        assert req[0]["args"]["tokens"] == 6
        # one span per decode iteration, tagged with occupancy and
        # accepted-token count (5 iterations: token 1 came from prefill)
        iters = _events(t, "decode.iteration")
        assert len(iters) == 5
        for e in iters:
            assert 0.0 < e["args"]["slot_occupancy"] <= 1.0
            assert e["args"]["accepted"] >= 1
        assert len(_events(t, "decode.prefill")) == 1
        assert len(_events(t, "decode.dispatch")) == 5
        # and the whole thing round-trips to a Perfetto-loadable file
        with open(t.save(str(tmp_path / "serve.trace.json"))) as fh:
            assert json.load(fh)["traceEvents"]

    def test_microbatch_request_spans_nest(self):
        t = Tracer(enabled=True)
        net = _mln()
        rng = np.random.default_rng(0)
        with InferenceServer(net, max_batch=4, max_wait_ms=1.0,
                             tracer=t) as srv:
            for _ in range(3):
                srv.predict(rng.standard_normal(6).astype(np.float32),
                            timeout=60)
        reqs = _events(t, "serve.request")
        qws = _events(t, "serve.queue_wait")
        assert len(reqs) == 3 and len(qws) == 3
        by_tid = {e["tid"]: e for e in reqs}
        for q in qws:
            assert _contains(by_tid[q["tid"]], q)
        # dispatch nests inside its batch span on the server lane
        batch = _events(t, "serve.batch")
        disp = _events(t, "serve.dispatch")
        assert batch and disp
        assert _contains(batch[0], disp[0])


class TestTrainingTrace:
    def test_fused_fit_spans_nest(self, tmp_path):
        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.datasets.iterators import \
            ListDataSetIterator
        rng = np.random.default_rng(1)
        x = rng.standard_normal((32, 6)).astype(np.float32)
        y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 32)]
        it = ListDataSetIterator(list(DataSet(x, y).batch_by(4)), 4)
        net = _mln().fused_steps(4)
        with _global_tracer(Tracer(enabled=True)) as t:
            net.fit(it, num_epochs=1)
        groups = _events(t, "train.fused_group")
        disp = _events(t, "train.dispatch")
        stage = _events(t, "train.stage")
        assert len(groups) == 2          # 8 batches / K=4
        assert len(disp) == 2 and len(stage) == 2
        for g in groups:
            assert g["args"]["k"] == 4
            assert any(_contains(g, d) for d in disp)
        # staging and dispatch never overlap: the staged group is handed
        # to exactly one dispatch
        assert all(not _contains(g, s) for g in groups for s in stage)
        assert _events(t, "train.compile")  # first build of the program
        with open(t.save(str(tmp_path / "train.trace.json"))) as fh:
            assert json.load(fh)["traceEvents"]

    def test_single_step_fit_emits_dispatch_spans(self):
        from deeplearning4j_tpu.datasets.dataset import DataSet
        rng = np.random.default_rng(2)
        x = rng.standard_normal((8, 6)).astype(np.float32)
        y = np.eye(4, dtype=np.float32)[rng.integers(0, 4, 8)]
        net = _mln()
        with _global_tracer(Tracer(enabled=True)) as t:
            net.fit(DataSet(x, y))
        assert len(_events(t, "train.dispatch")) == 1


# ---------------------------------------------------------------------------
# (c) cost pins: disabled overhead + zero device work
# ---------------------------------------------------------------------------
class TestCostPins:
    def test_disabled_span_is_nanosecond_scale(self):
        """The tentpole claim: a disabled tracer's span() is ONE
        attribute check returning a shared no-op. Pin the per-call cost
        well under 2 microseconds (measured ~0.1-0.2 us; min over trials
        rejects scheduler noise)."""
        t = Tracer(enabled=False)
        n = 50_000
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(n):
                t.span("x")
            best = min(best, (time.perf_counter() - t0) / n)
        assert best < 2e-6, f"disabled span() cost {best * 1e9:.0f}ns"
        assert len(t) == 0                    # nothing recorded
        # the with-statement path stays no-op too
        with t.span("x", k=1):
            pass
        assert len(t) == 0

    def test_a_dispatch_that_does_not_compile_costs_its_bracket_only(self):
        """ISSUE 36: every dispatch site of a training program brackets
        its call with obs.compiles.mark() / dispatched(). Where nothing
        compiled in between that is two reads of a thread-local, one of
        the clock and a compare: no span, no counter, under 2
        microseconds (measured ~0.3 us), tracing on or off."""
        step = lambda: None  # noqa: E731
        tracer = Tracer(enabled=True)
        n = 50_000
        best = float("inf")
        with _global_tracer(tracer):
            before = default_registry().snapshot("train.compile")
            for _ in range(5):
                t0 = time.perf_counter()
                for _ in range(n):
                    mark = obs.compiles.mark()
                    step()
                    obs.compiles.dispatched(mark, step, k=1)
                best = min(best, (time.perf_counter() - t0) / n)
            assert default_registry().snapshot("train.compile") == before
        assert best < 2e-6, f"bracket cost {best * 1e9:.0f}ns"
        assert len(tracer) == 0

    def test_obs_package_never_imports_device_code(self):
        """Structural zero-device-dispatch pin: recording a span or a
        metric can never touch jax/numpy because the obs package does
        not import them. Since ISSUE 15 this is a thin wrapper over
        the graftlint layering pass — tools/analyze/layers.toml's
        'obs-stdlib-only' rule is the single source of truth (the
        pass resolves relative AND function-local imports, which the
        old regex pin could only approximate); check_layer_rules
        raises if the rule is renamed away, so this cannot pass
        vacuously."""
        from tools.analyze import check_layer_rules
        findings = check_layer_rules(["obs-stdlib-only",
                                      "obs-below-serving"])
        assert not findings, \
            "\n".join(f"{f.path}:{f.line}: {f.message}"
                      for f in findings)

    def test_tracing_adds_zero_device_dispatches(self):
        """Same sequential workload through a traced and an untraced
        decode server: the dispatch counters must be IDENTICAL — spans
        observe the schedule, never alter it."""
        counts = {}
        for name, tracer in (("off", Tracer(enabled=False)),
                             ("on", Tracer(enabled=True))):
            lm = _lm()
            with ContinuousDecodeServer(lm, slots=2, prompt_buckets=(8,),
                                        tracer=tracer) as srv:
                for i in range(3):
                    srv.generate([1 + i, 2, 3], 5, timeout=120)
            snap = srv.metrics.snapshot()
            counts[name] = (snap["dispatches"], snap["tokens_out"])
        assert counts["on"] == counts["off"]


# ---------------------------------------------------------------------------
# (d) metrics: storage keys, SLO counters, queue-depth staleness fix
# ---------------------------------------------------------------------------
class TestMetricsPins:
    # the ONE export surface: every consumer (UI storage,
    # tools/load_sweep.py, tools/obs_report.py) reads these names — a
    # rename must fail here before it silently breaks a dashboard
    PINNED_KEYS = (
        "completed", "latency_ms_p50", "latency_ms_p99",
        "queue_wait_ms_p50", "queue_wait_ms_p99",
        "queue_depth_last", "queue_depth_max",
        "batch_occupancy_mean", "batch_size_mean",
        "spec_accepted_per_dispatch_mean", "spec_acceptance_rate_mean",
        "dispatches_per_token", "device_dispatches_per_token",
        # fused decode windows (serving/decode.py fused_serve=K,
        # ISSUE 18): window count, realized decode iterations, and the
        # amortization ratio (~1.0 unfused, ~K fused) — consumed by
        # tools/load_sweep.py's fused rate and the Prometheus route
        "fused_windows", "decode_iterations", "iterations_per_dispatch",
        # paged KV-cache pool view (serving/kvpool.py): arena pressure,
        # measured concurrency, prefix-cache hit rate, CoW and
        # memory-gate accounting — consumed by tools/load_sweep.py's
        # paged rate
        "pool_blocks", "blocks_in_use_last", "blocks_in_use_max",
        "live_streams_max", "prefix_rows_hit", "prefix_rows_total",
        "prefix_hit_rate", "cow_copies", "blocked_on_memory",
        "shed_blocks",
        # overload-control view (serving/admission.py): shed-by-cause
        # counters, brownout deferral, chunk dispatches, the live
        # service-rate gauge, and the admission estimator's signed
        # (predicted - actual) error histogram — consumed by the
        # load_sweep overload A/B and the Prometheus route
        "shed_predicted", "shed_brownout", "deferred",
        "chunk_dispatches", "service_rate_tokens_per_sec",
        # prefix-hit priority admission (serving/decode.py, PR 10):
        # always-present since then but never pinned — surfaced by
        # the graftlint metrics-keys reverse check (ISSUE 15)
        "admitted_prefix_priority",
        # durable KV state (serving/kvstate.py): preempt/resume/migrate
        # event counts, host bytes spilled, restored-prefix hits —
        # consumed by tools/load_sweep.py's preempt rate and the
        # Prometheus route (eagerly created, so a server that never
        # preempted scrapes zero, not absence)
        "preempted", "resumed", "migrated", "migrated_out",
        "spill_bytes", "prefix_restore_hits",
        # fleet-control events (serving/fleet.py FleetManager):
        # spawn/drain/death, failover replays, canary rollbacks —
        # consumed by tools/fleet_report.py and the load_sweep
        # --fleet-control record (eagerly created: a fleet that never
        # failed over scrapes zero, not absence)
        "replica_spawned", "replica_drained", "replica_dead",
        "replica_degraded", "failover_resubmitted", "canary_rollbacks",
        # serving-wire transport (serving/wire.py RemoteReplica via the
        # fleet manager's metrics): reconnects, at-most-once resends,
        # refused migrations — consumed by tools/fleet_report.py and
        # the load_sweep --fleet-procs record (eagerly created: a fleet
        # that never lost a connection scrapes zero, not absence)
        "wire_reconnects", "wire_retries", "migrate_refused",
        # durable control plane (serving/fleetjournal.py + recovery
        # and epoch fencing in serving/fleet.py / serving/wire.py):
        # manager generation, recovery re-adoptions, fenced stale-
        # manager control ops, journal records — consumed by
        # tools/fleet_report.py's control section and the load_sweep
        # --chaos record (eagerly created: a fleet whose manager never
        # restarted scrapes zero, not absence)
        "manager_epoch", "replicas_adopted", "fenced_ops",
        "journal_records",
        # blast-radius containment (serving/fleet.py, ISSUE 17):
        # poison-pill quarantine verdicts, the spawn circuit breaker
        # (open events + live state gauge), fleet retry-budget denials,
        # degraded-mode time, infant deaths — consumed by
        # tools/fleet_report.py's containment section and the
        # load_sweep --cascade record (eagerly created: a fleet that
        # never contained anything scrapes zero, not absence)
        "requests_quarantined", "breaker_open_total", "breaker_state",
        "retry_budget_exhausted", "degraded_mode_ticks",
        "infant_deaths",
        # prefix-affinity routing + fleet prefix tier (serving/fleet.py
        # affinity policy, serving/decode.py prefix_export/prefix_adopt,
        # serving/wire.py PREFIX ops, ISSUE 20): routing verdicts and
        # cross-replica block traffic — consumed by
        # tools/fleet_report.py's control section and the load_sweep
        # --affinity record (eagerly created: a fleet that never
        # spilled or pulled scrapes zero, not absence)
        "routed_affinity", "routed_spill", "prefix_pull_hits",
        "prefix_pull_refused", "prefix_pull_bytes",
        "admission_error_ms_p50", "admission_error_ms_p99",
        "admission_error_ms_mean", "admission_error_ms_count",
        "slo_total", "slo_met", "slo_tokens_met", "slo_attainment",
        "ttft_ms_p50", "ttft_ms_p99", "ttft_ms_mean", "ttft_ms_count",
        "inter_token_ms_p50", "inter_token_ms_p99",
        "inter_token_ms_mean", "inter_token_ms_count",
    )

    # fleet federation read-outs (obs/fleet.py): ALWAYS-PRESENT keys on
    # FleetView.snapshot() — the tools/fleet_report.py surface and the
    # AutoscaleSignal's inputs; a rename must fail here before it
    # silently breaks the fleet report or the detector
    FLEET_PINNED_KEYS = (
        "fleet_instances", "fleet_slo_attainment",
        "fleet_goodput_tokens_per_sec", "autoscale_decision",
        "fleet_service_rate_tokens_per_sec", "fleet_shed_predicted",
        "fleet_sheds_total", "fleet_shed_share",
        "fleet_occupancy_mean", "fleet_tokens_out",
        # fleet-control event counters (serving/fleet.py): summed like
        # any counter; FleetManager.fleet_snapshot() overlays its own
        "fleet_replica_spawned", "fleet_replica_drained",
        "fleet_replica_dead", "fleet_failover_resubmitted",
        "fleet_canary_rollbacks",
        # serving-wire transport counters (serving/wire.py): summed the
        # same way, overlaid live by FleetManager.fleet_snapshot()
        "fleet_wire_reconnects", "fleet_wire_retries",
        "fleet_migrate_refused",
        # durable-control-plane counters (serving/fleetjournal.py and
        # the recovery/fencing paths): summed the same way, overlaid
        # live by FleetManager.fleet_snapshot()
        "fleet_manager_epoch", "fleet_replicas_adopted",
        "fleet_fenced_ops", "fleet_journal_records",
        # blast-radius containment counters (serving/fleet.py): summed
        # the same way; fleet_breaker_state is the per-instance MAX of
        # the breaker gauge (any open breaker reads open) until
        # FleetManager.fleet_snapshot() overlays its live state
        "fleet_requests_quarantined", "fleet_breaker_open_total",
        "fleet_retry_budget_exhausted", "fleet_degraded_mode_ticks",
        "fleet_infant_deaths", "fleet_breaker_state",
        # fused decode windows (serving/decode.py fused_serve=K):
        # window/iteration counters summed like any counter; the
        # amortization ratio is re-derived from the MERGED counters so
        # it weights instances by dispatch volume
        "fleet_fused_windows", "fleet_decode_iterations",
        "fleet_iterations_per_dispatch",
        # prefix-affinity routing + fleet prefix tier (ISSUE 20):
        # routed_* summed then overlaid live by the manager (its own
        # verbs); prefix_pull_* stay federated — the ADOPTING replica
        # counts hits/bytes/refusals
        "fleet_routed_affinity", "fleet_routed_spill",
        "fleet_prefix_pull_hits", "fleet_prefix_pull_refused",
        "fleet_prefix_pull_bytes",
    )

    def test_fleet_snapshot_keys_pinned(self):
        from deeplearning4j_tpu.obs.fleet import FleetView
        # empty fleet AND a populated one: the keys never depend on
        # what traffic happened to flow
        for fv in (FleetView(),
                   FleetView().add("i0", ServingMetrics(
                       name="i0", slo_target_ms=50))):
            snap = fv.snapshot()
            for key in self.FLEET_PINNED_KEYS:
                assert key in snap, f"missing fleet snapshot key {key}"

    def test_registry_storage_keys_via_stats_reporter(self):
        from deeplearning4j_tpu.ui.stats import ServingStatsReporter
        from deeplearning4j_tpu.ui.storage import InMemoryStatsStorage
        m = ServingMetrics(slo_target_ms=100)
        m.record_request(12.0, queue_wait_ms=3.0, tokens=5)
        m.record_batch(3, 4, 1)
        storage = InMemoryStatsStorage()
        rep = ServingStatsReporter(storage, session_id="obs_pin")
        rep.report(m.snapshot())
        serving = storage.get_latest_update("obs_pin")["serving"]
        for key in self.PINNED_KEYS:
            assert key in serving, f"renamed/missing snapshot key {key}"
        assert serving["completed"] == 1
        assert serving["slo_total"] == 1 and serving["slo_met"] == 1
        assert serving["slo_tokens_met"] == 5
        assert serving["slo_attainment"] == 1.0

    def test_slo_counters_from_latency_target(self):
        m = ServingMetrics(slo_target_ms=50)
        m.record_request(10.0, tokens=4)     # met
        m.record_request(80.0, tokens=4)     # missed
        m.record_slo_miss()                  # shed deadline-carrying req
        snap = m.snapshot()
        assert snap["slo_total"] == 3
        assert snap["slo_met"] == 1
        assert snap["slo_tokens_met"] == 4
        assert snap["slo_attainment"] == pytest.approx(1 / 3)

    def test_explicit_deadline_overrides_latency_target(self):
        m = ServingMetrics(slo_target_ms=1.0)
        # the server KNOWS the request's deadline was met — the latency
        # target must not re-classify it
        m.record_request(500.0, tokens=2, deadline_met=True)
        snap = m.snapshot()
        assert snap["slo_met"] == 1 and snap["slo_total"] == 1

    def test_no_slo_configured_reports_none(self):
        m = ServingMetrics()
        m.record_request(10.0)
        snap = m.snapshot()
        assert snap["slo_total"] == 0
        assert snap["slo_attainment"] is None

    def test_deadline_eviction_counts_slo_miss(self):
        from deeplearning4j_tpu.serving import DeadlineExceededError
        lm = _lm()
        with ContinuousDecodeServer(lm, slots=2,
                                    prompt_buckets=(8,)) as srv:
            srv.generate([1, 2, 3], 4, timeout=120)   # warm compile
            # 40 tokens cannot finish in 2ms: shed at admission or
            # evicted mid-decode — either way an SLO miss is counted
            fut = srv.submit([4, 5, 6], 40, deadline_ms=2)
            with pytest.raises(DeadlineExceededError):
                fut.result(60)
        snap = srv.metrics.snapshot()
        assert snap["slo_total"] >= 1
        assert snap["slo_met"] <= snap["slo_total"] - 1

    def test_queue_depth_sampled_at_enqueue(self):
        """The staleness fix: depth must be observable BEFORE any batch
        forms. A burst into a long-max-wait server shows non-zero depth
        immediately; the old batch-formation-only sampling reported 0
        until the first dispatch."""
        net = _mln()
        srv = InferenceServer(net, max_batch=32, max_wait_ms=400.0,
                              max_queue=64).start()
        try:
            rng = np.random.default_rng(3)
            futs = [srv.submit(rng.standard_normal(6).astype(np.float32))
                    for _ in range(4)]
            snap = srv.metrics.snapshot()
            assert snap.get("batches", 0) == 0      # no batch formed yet
            assert snap["queue_depth_max"] >= 1     # ...but depth seen
            for f in futs:
                f.result(60)
        finally:
            srv.stop()

    def test_queue_full_shed_records_depth(self):
        """Queue-full backpressure on a busy decode server (one long
        request holds the only slot, so the queue really fills) records
        the full depth — the shed IS a depth observation."""
        from deeplearning4j_tpu.serving import ServerOverloadedError
        lm = _lm()
        srv = ContinuousDecodeServer(lm, slots=1, prompt_buckets=(8,),
                                     max_queue=2).start()
        try:
            srv.generate([1, 2, 3], 2, timeout=120)   # warm compile
            hog = srv.submit([4, 5, 6], 40)           # occupies the slot
            time.sleep(0.05)                          # let it be admitted
            with pytest.raises(ServerOverloadedError):
                for i in range(4):
                    srv.submit([7 + i, 8, 9], 40)
            assert srv.metrics.snapshot()["queue_depth_max"] >= 2
            hog.result(120)
        finally:
            srv.stop(timeout=60)

    def test_health_counters_reach_default_registry(self):
        from deeplearning4j_tpu.common.health import TrainingHealthPolicy
        reg = reset_default_registry()
        try:
            pol = TrainingHealthPolicy(warmup_steps=1)
            pol.observe({"score": 1.0, "grad_norm": 1.0,
                         "all_finite": True})
            pol.observe({"score": float("nan"), "grad_norm": 1.0,
                         "all_finite": False})
            assert reg.counter("train.health.ok").value == 1
            assert reg.counter("train.health.skips").value == 1
        finally:
            reset_default_registry()

    def test_retry_publishes_to_default_registry(self):
        from deeplearning4j_tpu.common.resilience import RetryPolicy
        reg = reset_default_registry()
        try:
            calls = [0]

            def flaky():
                calls[0] += 1
                if calls[0] < 3:
                    raise ConnectionError("transient")
                return "ok"

            pol = RetryPolicy(max_retries=5, base_delay=0.0, jitter=0.0,
                              metric="unit_test")
            assert pol.call(flaky) == "ok"
            assert reg.counter("resilience.retries").value == 2
            assert reg.counter(
                "resilience.retries.unit_test").value == 2
        finally:
            reset_default_registry()


# ---------------------------------------------------------------------------
# (e) flight recorder
# ---------------------------------------------------------------------------
class TestFlightRecorder:
    def test_p99_threshold_arms_capture(self):
        t = Tracer(enabled=False)
        rec = FlightRecorder(t, threshold_ms=50, window=32, min_samples=8,
                             capture_spans=3, cooldown_s=0.0)
        for _ in range(10):
            rec.observe(10.0)               # healthy: below threshold
        assert rec.triggers == 0 and not t.enabled
        for _ in range(10):
            rec.observe(120.0)              # SLO violation
            if rec.triggers:
                break
        assert rec.triggers == 1
        assert t.enabled                     # armed for the next N spans
        for i in range(3):
            t.emit(f"cap{i}", i, 1)
        assert not t.enabled                 # auto-disarmed after N
        assert len(rec.captures) == 1
        cap = rec.captures[0]
        names = [s.name for s in cap["spans"]]
        assert "flight.trigger" in names
        assert {"cap0", "cap1", "cap2"} <= set(names)
        assert cap["p99_ms"] >= 50

    def test_spike_before_min_samples_still_triggers(self):
        """Regression: the O(1) pre-filter must not suppress a capture
        when the samples that pushed the window p99 over threshold
        arrived during warmup — later all-fast traffic still triggers,
        because the spike IS the window's p99 until it ages out."""
        t = Tracer(enabled=False)
        rec = FlightRecorder(t, threshold_ms=50, window=64,
                             min_samples=32, capture_spans=2,
                             cooldown_s=0.0)
        for _ in range(5):
            rec.observe(500.0)          # spikes land before min_samples
        for _ in range(40):
            rec.observe(10.0)           # then only fast requests
        assert rec.triggers == 1        # p99 is still the 500ms spike

    def test_already_enabled_tracer_stays_enabled(self):
        t = Tracer(enabled=True)
        rec = FlightRecorder(t, threshold_ms=10, window=8, min_samples=2,
                             capture_spans=2, cooldown_s=0.0)
        rec.observe(100.0)
        rec.observe(100.0)
        assert rec.triggers == 1
        t.emit("a", 0, 1)
        t.emit("b", 1, 1)
        assert t.enabled                     # restored to previous state

    def test_flight_recorder_on_live_server(self):
        """Slow real requests (tiny deadline-free decode on CPU) trip a
        sub-ms threshold: the recorder arms the server's OWN tracer and
        the capture self-documents with real serve spans."""
        t = Tracer(enabled=False)
        rec = FlightRecorder(t, threshold_ms=0.5, window=16,
                             min_samples=2, capture_spans=8,
                             cooldown_s=0.0)
        lm = _lm()
        with ContinuousDecodeServer(lm, slots=2, prompt_buckets=(8,),
                                    tracer=t, flight_recorder=rec) as srv:
            for i in range(4):
                srv.generate([1 + i, 2, 3], 6, timeout=120)
        assert rec.triggers >= 1
        assert rec.captures or t.enabled     # capture done or still armed


# ---------------------------------------------------------------------------
# combined report (tools/obs_report.py)
# ---------------------------------------------------------------------------
class TestObsReport:
    def _mod(self):
        import importlib
        import sys
        tools = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools")
        if tools not in sys.path:
            sys.path.insert(0, tools)
        return importlib.import_module("obs_report")

    def test_build_and_format(self):
        mod = self._mod()
        t = Tracer(enabled=True)
        for _ in range(3):
            with t.span("serve.dispatch"):
                pass
        m = ServingMetrics(slo_target_ms=100)
        m.record_request(5.0, tokens=2)
        report = mod.build_report(spans=t,
                                  metrics={"arm": m.snapshot()})
        row = next(r for r in report["spans"]
                   if r["name"] == "serve.dispatch")
        assert row["count"] == 3
        assert row["total_ms"] is not None
        assert report["metrics"]["arm"]["completed"] == 1
        text = mod.format_report(report)
        assert "serve.dispatch" in text and "completed" in text

    def test_report_survives_missing_profile(self, tmp_path):
        mod = self._mod()
        report = mod.build_report(spans=[], metrics=None,
                                  profile_logdir=str(tmp_path / "nope"))
        assert report["device_ops"] is None
        assert "device_ops_error" in report
        assert isinstance(mod.format_report(report), str)

    def test_chrome_trace_input(self):
        mod = self._mod()
        t = Tracer(enabled=True)
        with t.span("x"):
            pass
        rows = mod.span_summary(t.chrome_trace())
        assert rows[0]["name"] == "x" and rows[0]["count"] == 1

    def test_multi_trace_merge_plumbing(self, tmp_path):
        """Satellite pin (ISSUE 12): obs_report accepts MULTIPLE trace
        files — merge_trace_files stitches them on the clock anchors
        and the merged dict feeds build_report like any single trace."""
        mod = self._mod()
        t1 = Tracer(enabled=True, instance="a")
        with t1.span("serve.dispatch"):
            pass
        time.sleep(0.02)
        t2 = Tracer(enabled=True, instance="b")
        with t2.span("serve.dispatch"):
            pass
        p1 = t1.save(str(tmp_path / "a.trace.json"))
        p2 = t2.save(str(tmp_path / "b.trace.json"))
        merged = mod.merge_trace_files([p1, p2])
        xs = [e for e in merged["traceEvents"] if e.get("ph") == "X"]
        assert sorted({e["pid"] for e in xs}) == [1, 2]
        report = mod.build_report(spans=merged)
        row = next(r for r in report["spans"]
                   if r["name"] == "serve.dispatch")
        assert row["count"] == 2


# ---------------------------------------------------------------------------
# (f) the annotation sink (ISSUE 26): spans are profiler annotations too
# ---------------------------------------------------------------------------
class _FakeAnnotations:
    """An annotation factory that records what enters and exits it."""

    def __init__(self):
        self.entered, self.exited = [], []

    def __call__(self, name, **args):
        log = self

        class Ctx:
            def __enter__(self):
                log.entered.append((name, args))
                return self

            def __exit__(self, *exc):
                log.exited.append(name)
                return False

        self.last = Ctx()
        return self.last

    def names(self):
        return [n for n, _ in self.entered]


def _tiny_graph():
    from deeplearning4j_tpu import ComputationGraph
    gb = (NeuralNetConfiguration.Builder().seed(3).updater("sgd")
          .learning_rate(0.05).graph_builder().add_inputs("in"))
    gb.add_layer("d", DenseLayer(n_out=8, activation="relu"), "in")
    gb.add_layer("out", OutputLayer(n_out=4, activation="softmax",
                                    loss_function="mcxent"), "d")
    conf = (gb.set_outputs("out")
            .set_input_types(InputType.feed_forward(6)).build())
    return ComputationGraph(conf).init()


def _batches(n, rows=8):
    from deeplearning4j_tpu.datasets.dataset import DataSet
    rng = np.random.default_rng(4)
    return [DataSet(rng.standard_normal((rows, 6)).astype(np.float32),
                    np.eye(4, dtype=np.float32)[rng.integers(0, 4, rows)])
            for _ in range(n)]


class TestAnnotationSink:
    @pytest.mark.parametrize("ring", [False, True])
    def test_factory_sees_one_enter_and_exit_per_span(self, ring):
        fake = _FakeAnnotations()
        t = Tracer(enabled=ring, annotate=fake)
        for i in range(3):
            with t.span("train.dispatch", cat="train", k=i) as ctx:
                assert len(fake.entered) == i + 1 and len(fake.exited) == i
                # ring off: the annotation itself, no wrapper object
                assert (ctx is fake.last) == (not ring)
        assert fake.entered == [("train.dispatch", {"k": i})
                                for i in range(3)]
        assert fake.exited == ["train.dispatch"] * 3
        # the ring records exactly when it is on, the same spans
        assert [s.name for s in t.spans()] == \
            (["train.dispatch"] * 3 if ring else [])

    def test_ring_span_and_annotation_cover_the_same_interval(self):
        stamps = {}

        class Clocked:
            def __init__(self, name, **args):
                pass

            def __enter__(self):
                stamps["enter"] = time.monotonic_ns()

            def __exit__(self, *exc):
                stamps["exit"] = time.monotonic_ns()

        t = Tracer(enabled=True).annotate_with(Clocked)
        with t.span("x"):
            time.sleep(0.002)
        (s,) = t.spans()
        assert stamps["enter"] <= s.t0_ns
        assert s.t0_ns + s.dur_ns <= stamps["exit"]
        assert s.dur_ns >= 0.9 * (stamps["exit"] - stamps["enter"]) - 50_000

    def test_emit_and_instant_stay_ring_only(self):
        fake = _FakeAnnotations()
        t = Tracer(enabled=True, annotate=fake)
        t.emit("serve.queue_wait", time.monotonic_ns() - 1000, 1000)
        t.instant("flight.trigger")
        assert fake.entered == [] and fake.exited == []
        assert [s.name for s in t.spans()] == ["serve.queue_wait",
                                               "flight.trigger"]

    def test_without_a_factory_the_disabled_span_is_the_shared_noop(self):
        from deeplearning4j_tpu.obs.trace import _NOOP
        t = Tracer(enabled=False)
        assert t.span("x") is _NOOP and t.span("y", k=1) is _NOOP
        # and a factory can be taken away again
        t.annotate_with(_FakeAnnotations()).annotate_with(None)
        assert t.span("x") is _NOOP

    def test_an_exception_inside_a_span_exits_the_annotation(self):
        fake = _FakeAnnotations()
        t = Tracer(enabled=True, annotate=fake)
        with pytest.raises(ValueError):
            with t.span("x"):
                raise ValueError("boom")
        assert fake.exited == ["x"] and len(t.spans("x")) == 1

    def test_the_process_wide_tracer_writes_into_a_profiler_session(
            self, tmp_path):
        """The package root installs jax.profiler.TraceAnnotation on
        obs.TRACER: with the ring OFF, a span is an event of the host
        plane of a running profiler session, on the trace's clock."""
        import glob

        import jax
        from jax.profiler import ProfileData
        assert not obs.TRACER.enabled
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            for i in range(3):
                with obs.TRACER.span("pr26.probe", cat="train", k=i):
                    pass
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                                / "*.xplane.pb"))
        host = [e.name for plane in ProfileData.from_file(path).planes
                if plane.name == "/host:CPU"
                for line in plane.lines for e in line.events]
        assert sum(n.split("#")[0] == "pr26.probe" for n in host) == 3
        assert len(obs.TRACER) == 0

    def test_graph_fit_annotates_one_dispatch_a_step(self):
        net, fake = _tiny_graph(), _FakeAnnotations()
        with _global_tracer(Tracer(annotate=fake)) as t:
            for ds in _batches(3):
                net.fit(ds)
        assert fake.names() == ["train.dispatch"] * 3
        assert fake.exited == fake.names() and len(t) == 0

    @pytest.mark.parametrize("k", [1, 2])
    def test_parallel_wrapper_annotates_its_host_work_once_a_round(self, k):
        from deeplearning4j_tpu.datasets.iterators import \
            ListDataSetIterator
        from deeplearning4j_tpu.parallel.parallel_wrapper import \
            ParallelWrapper
        pw = (ParallelWrapper.Builder(_tiny_graph()).workers(2)
              .averaging_frequency(k).build())
        fake = _FakeAnnotations()
        with _global_tracer(Tracer(annotate=fake)):
            pw.fit(ListDataSetIterator(_batches(4), 8))
        rounds = 4 // k
        assert fake.names() == ["parallel.stage", "parallel.dispatch",
                                "parallel.checkpoint"] * rounds
        assert fake.exited == fake.names()
        if k > 1:
            assert all(a == {"k": k} for n, a in fake.entered
                       if n != "parallel.checkpoint")

    def test_parallel_wrapper_health_is_a_span_when_armed(self):
        from deeplearning4j_tpu.datasets.iterators import \
            ListDataSetIterator
        from deeplearning4j_tpu.parallel.parallel_wrapper import \
            ParallelWrapper
        pw = (ParallelWrapper.Builder(_tiny_graph()).workers(2)
              .health_policy(True).build())
        with _global_tracer(Tracer(enabled=True)) as t:
            pw.fit(ListDataSetIterator(_batches(2), 8))
        for name in ("parallel.stage", "parallel.dispatch",
                     "parallel.health", "parallel.checkpoint"):
            assert len(t.spans(name)) == 2, name
        # stage ends before its dispatch starts: never nested
        for st, d in zip(t.spans("parallel.stage"),
                         t.spans("parallel.dispatch")):
            assert st.t0_ns + st.dur_ns <= d.t0_ns
