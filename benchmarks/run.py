#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json, in a process of its own:

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds the cell from the seed, warms the cell's own shapes (set-up), measures
for --seconds, checks what the timed path produced against the plain
reference, and prints one JSON object as the last line of stdout. With
--trace 0 the metrics are the cell's end-to-end metrics, with --trace 1 its
per-layer metrics. It measures on a TPU with at least the cell's chips, or
exits non-zero and prints no result.

`--rehearse <config>:<traffic>[:chips]` is the builder's CPU rehearsal of the
control flow on a tiny configuration that no cell names: JAX_PLATFORMS=cpu
only, every line says "rehearsal" and names the cpu as its device.
"""
import time

T_PROCESS_START = time.monotonic()

import argparse   # noqa: E402
import json       # noqa: E402
import os         # noqa: E402
import sys        # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXIT_NO_CHIP = 4


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--rehearse", metavar="CONFIG:TRAFFIC[:CHIPS]")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if (args.workload is None) == (args.rehearse is None):
        ap.error("give --workload or --rehearse")
    return args


def find_cell(args):
    from benchmarks.harness import loader
    if args.workload is not None:
        return loader.cell(args.workload), loader.benchmark()["run_seconds"]
    return loader.rehearsal_cell(args.rehearse, metrics=True), 3


def main(argv=None):
    args = parse(argv)
    if not os.path.isdir(os.path.join(ROOT, "deeplearning4j_tpu")):
        print("benchmarks: the program (deeplearning4j_tpu/) is not in this "
              "checkout; nothing to measure", file=sys.stderr)
        return 3
    sys.path.insert(0, ROOT)
    cell, default_seconds = find_cell(args)
    seconds = args.seconds if args.seconds is not None else default_seconds

    # the one persistent compile cache, inside the checkout (or where
    # JAX_COMPILATION_CACHE_DIR says): only a cell's first run compiles
    from deeplearning4j_tpu.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    rehearsal = args.rehearse is not None
    if rehearsal and device["platform"] != "cpu":
        print("benchmarks: --rehearse is the CPU rehearsal "
              "(set JAX_PLATFORMS=cpu)", file=sys.stderr)
        return EXIT_NO_CHIP
    if not rehearsal and (device["platform"] != "tpu"
                          or len(devices) < cell["chips"]):
        print(f"benchmarks: {cell['name']} needs {cell['chips']} TPU "
              f"chip(s); JAX found {json.dumps(device)}. Refusing to "
              f"measure.", file=sys.stderr)
        return EXIT_NO_CHIP
    device["count"] = cell["chips"]

    from benchmarks.harness import loader
    from benchmarks.harness.window import Tracer
    tracer = Tracer(args.trace, os.path.join(ROOT, ".bench_trace"))
    tracer.seconds = min(seconds, cell["traffic"].get("trace_seconds", 6))
    marks = {}

    def setup_done(t=None):
        """The window opens: now, or at the time the driver's schedule
        fixed for it."""
        marks["t_start"] = time.monotonic() if t is None else t
        return marks["t_start"]

    out = loader.driver(cell["config"]).run(cell, args.seed, seconds, tracer,
                                            setup_done)
    values = dict(out["end_to_end"])
    values["setup_s"] = marks["t_start"] - T_PROCESS_START
    device["memory_peak_bytes"] = out["memory_peak_bytes"]
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"]}
    if args.trace:
        from benchmarks.harness.peaks import PEAKS, peaks_for
        peaks = (PEAKS["TPU v5 lite"] if rehearsal
                 else peaks_for(device["kind"]))
        ctx = dict(out["ctx"], end_to_end=values, device=device, peaks=peaks)
        tr = ctx["trace"]
        device["busy_s"], device["window_s"] = tr["busy_s"], tr["window_s"]
        metrics = {}
        for m in cell["per_layer"]:
            v = loader.metric_reader(m["name"])(ctx)
            if v is not None:               # nothing to read: left out
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        from benchmarks.harness import trace as T
        result["breakdown"] = {
            "device_ops": T.top_ops(tr["trace"], tr["t0"], tr["t1"]),
            "idle_gaps": T.idle_gaps(tr["trace"], tr["t0"], tr["t1"])}
    else:
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell["end_to_end"] if m["name"] in values}
    result["metrics"] = metrics
    result["device"] = device
    if rehearsal:
        result["rehearsal"] = True
    # how the run's wall time divides; the reference's share is not set-up
    result["seconds"] = {"setup": values["setup_s"], "window": seconds,
                         "drain": out.get("drain_s", 0.0),
                         "check": out["check_s"],
                         "trace_stop": tracer.stop_s,
                         "trace_read": tracer.read_s,
                         "total": time.monotonic() - T_PROCESS_START}
    # numbers read beside the reference that have no limit (PERF.md says
    # why), then each number compared beside its limit
    result["read"] = out.get("read", {})
    for name, v in result["read"].items():
        print(f"read {name} {v!r} (not compared)", file=sys.stderr)
    result["compared"] = out["compared"]
    for name, c in out["compared"].items():
        print(f"compared {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"correct {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
