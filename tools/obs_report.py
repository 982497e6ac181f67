"""Combined observability report: host spans + device ops + metrics.

One place that joins the three telemetry surfaces PR 6 standardized:

  * host span timeline (obs.trace.Tracer / a saved Chrome trace JSON) —
    aggregated per span name: count, total/mean/p99 ms;
  * span-derived latency decomposition (`obs.decompose`): each served
    request's total attributed to queue-wait / prefill / decode /
    scheduling-gap phases, aggregated per phase — included automatically
    whenever the spans contain `serve.request` lanes;
  * the device-op table from `optimize.profiler.summarize_trace` (an
    xplane/trace capture directory, when one exists), and with the
    compiled step's HLO text (`--hlo`: what
    `net.lower_step(ds).compile().as_text()` returns, saved to a file)
    the same device time by LAYER kind, forward and backward apart
    (`summarize_layers`: the containers' `jax.named_scope`s, joined to
    the trace by instruction name);
  * one or more metrics snapshots (`ServingMetrics.snapshot()` dicts or
    a `MetricsRegistry.snapshot()`), None-guarded via the shared
    `obs.registry.fmt` helper.

`tools/load_sweep.py` routes its summaries through `format_report`, and
the CLI below renders a saved trace + profile dir + metrics JSON from disk:

    python tools/obs_report.py --trace /tmp/serve.trace.json \
        [--profile /tmp/prof [--hlo /tmp/step.hlo.txt]] \
        [--metrics /tmp/snapshot.json]

`--trace` repeats: two or more saved traces are stitched on their
`clock_sync` wall-clock anchors into ONE Perfetto-loadable file
(`obs.fleet.merge_traces` — per-instance process groups, shared trace
ids intact), written next to the report (`--merged-trace` overrides
the path) and used as the report's span input — so a migrated
request's cross-server timeline feeds the same span summary and
decomposition a single-server trace does.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from deeplearning4j_tpu.obs.registry import fmt, percentile  # noqa: E402


def _normalize_spans(spans_or_trace):
    """-> list of (name, dur_ms) from a Tracer, a list of Span tuples,
    or a Chrome trace dict ({"traceEvents": [...]})."""
    if spans_or_trace is None:
        return []
    if hasattr(spans_or_trace, "spans"):        # Tracer
        spans_or_trace = spans_or_trace.spans()
    if isinstance(spans_or_trace, dict):        # chrome trace JSON
        return [(e.get("name", "?"), e.get("dur", 0) / 1e3)
                for e in spans_or_trace.get("traceEvents", [])
                if e.get("ph") == "X"]
    out = []
    for s in spans_or_trace:                    # Span namedtuples
        out.append((s.name, s.dur_ns / 1e6))
    return out


def span_summary(spans_or_trace):
    """Per-name aggregation of host spans, sorted by total time desc:
    [{"name", "count", "total_ms", "mean_ms", "p99_ms"}]."""
    durs = defaultdict(list)
    for name, ms in _normalize_spans(spans_or_trace):
        durs[name].append(ms)
    rows = []
    for name, ds in durs.items():
        ds.sort()
        rows.append({"name": name, "count": len(ds),
                     "total_ms": fmt(sum(ds)),
                     "mean_ms": fmt(sum(ds) / len(ds)),
                     "p99_ms": fmt(percentile(ds, 99))})
    rows.sort(key=lambda r: -(r["total_ms"] or 0.0))
    return rows


def build_report(spans=None, profile_logdir=None, metrics=None,
                 hlo_text=None):
    """Assemble the combined report dict. `metrics` is a snapshot dict
    or {label: snapshot}; `profile_logdir` is summarized when readable
    (missing/unparsable traces degrade to None, never raise — the host
    report must survive a profile that was never captured); `hlo_text`
    (the traced step's compiled text) adds the by-layer table."""
    report = {"spans": span_summary(spans) if spans is not None else None,
              "device_ops": None, "device_layers": None, "metrics": None,
              "decomposition": None}
    if spans is not None:
        from deeplearning4j_tpu.obs.decompose import decompose
        dec = decompose(spans)
        if dec["n_requests"]:
            report["decomposition"] = dec
    if profile_logdir is not None:
        try:
            from deeplearning4j_tpu.optimize.profiler import (
                op_scopes, summarize_layers, summarize_trace)
            report["device_ops"] = summarize_trace(profile_logdir)
            if hlo_text is not None:
                report["device_layers"] = summarize_layers(
                    profile_logdir, op_scopes(hlo_text))
        except Exception as e:      # no trace / no schema: degrade
            report["device_ops_error"] = str(e)
    if metrics is not None:
        if metrics and not any(isinstance(v, dict)
                               for v in metrics.values()):
            metrics = {"metrics": metrics}
        report["metrics"] = {
            label: {k: fmt(v, 4) for k, v in snap.items()}
            for label, snap in metrics.items()}
    return report


def merge_trace_files(paths, names=None):
    """Load N saved Chrome traces and stitch them on their clock_sync
    anchors (`obs.fleet.merge_traces`) — the multi-`--trace` plumbing,
    importable so tools/fleet_report.py and tests share it."""
    from deeplearning4j_tpu.obs.fleet import merge_traces
    traces = []
    for p in paths:
        with open(p) as fh:
            traces.append(json.load(fh))
    return merge_traces(traces, names=names)


def _table(rows, cols, title, limit=None):
    out = [f"== {title} =="]
    if not rows:
        out.append("  (none)")
        return out
    widths = {c: max(len(c), *(len(str(r.get(c))) for r in rows))
              for c in cols}
    out.append("  " + "  ".join(c.ljust(widths[c]) for c in cols))
    for r in rows[:limit]:
        out.append("  " + "  ".join(
            str(r.get(c)).ljust(widths[c]) for c in cols))
    if limit is not None and len(rows) > limit:
        out.append(f"  ... {len(rows) - limit} more")
    return out


def format_report(report, top=20):
    """Human-readable text rendering of `build_report`'s dict."""
    lines = []
    if report.get("spans") is not None:
        lines += _table(report["spans"],
                        ["name", "count", "total_ms", "mean_ms",
                         "p99_ms"], "host spans", limit=top)
    if report.get("decomposition"):
        dec = report["decomposition"]
        rows = [{"phase": ph, **stats,
                 "fraction": dec["fractions"].get(ph)}
                for ph, stats in dec["phases"].items()]
        lines += _table(rows, ["phase", "total_ms", "mean_ms", "p50_ms",
                               "p99_ms", "fraction"],
                        f"latency decomposition "
                        f"({dec['n_requests']} requests)")
    if report.get("device_ops") is not None:
        lines += _table(report["device_ops"],
                        ["name", "total_ms", "count", "pct"],
                        "device ops", limit=top)
        if report.get("device_layers") is not None:
            lines += _table(report["device_layers"],
                            ["name", "total_ms", "count", "pct"],
                            "device time by layer")
    elif report.get("device_ops_error"):
        lines.append(f"== device ops ==\n  unavailable: "
                     f"{report['device_ops_error']}")
    if report.get("metrics"):
        for label, snap in report["metrics"].items():
            lines.append(f"== metrics: {label} ==")
            for k in sorted(snap):
                lines.append(f"  {k} = {snap[k]}")
    return "\n".join(lines)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--trace", action="append", default=None,
                    help="saved Chrome trace JSON (Tracer.save output); "
                         "repeat to stitch multiple traces on their "
                         "clock_sync anchors into one merged trace")
    ap.add_argument("--merged-trace", default=None,
                    help="where to write the merged trace when more "
                         "than one --trace is given (default: "
                         "<first-trace>.merged.json)")
    ap.add_argument("--profile", help="jax.profiler logdir to summarize")
    ap.add_argument("--hlo", help="compiled text of the traced step "
                    "(net.lower_step(ds).compile().as_text()): adds the "
                    "device time by layer to --profile's table")
    ap.add_argument("--metrics", help="metrics snapshot JSON file")
    ap.add_argument("--json", action="store_true",
                    help="emit the report as JSON instead of text")
    args = ap.parse_args()
    spans = None
    if args.trace and len(args.trace) > 1:
        spans = merge_trace_files(args.trace)
        out = args.merged_trace or args.trace[0] + ".merged.json"
        with open(out, "w") as fh:
            json.dump(spans, fh)
        print(f"merged trace ({len(args.trace)} inputs) -> {out}",
              file=sys.stderr)
    elif args.trace:
        with open(args.trace[0]) as fh:
            spans = json.load(fh)
    metrics = None
    if args.metrics:
        with open(args.metrics) as fh:
            metrics = json.load(fh)
    hlo_text = None
    if args.hlo:
        with open(args.hlo) as fh:
            hlo_text = fh.read()
    report = build_report(spans=spans, profile_logdir=args.profile,
                          metrics=metrics, hlo_text=hlo_text)
    print(json.dumps(report) if args.json else format_report(report))


if __name__ == "__main__":
    main()
