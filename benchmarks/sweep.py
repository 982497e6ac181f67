#!/usr/bin/env python3
"""The rate sweep an open-loop cell's rate is fixed from, made once, by the
builder of the cell, on the chip:

    python3 benchmarks/sweep.py --workload <name> --rates 1.0,1.5,2.0 --seconds 40

One process, one window for each rate, each at the traffic file's own mix
with only `rate_per_s` replaced. A rate is sustained when its backlog does
not grow over the window: the second half's requests wait no longer for
their first token than the first half's, and the drain after the close is
short. The cell's rate is then written into the traffic file by hand; the
benchmark's runs never search for it.
"""
import argparse
import gc
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--rehearse", metavar="CONFIG:TRAFFIC")
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from deeplearning4j_tpu.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    from benchmarks.harness import loader
    from benchmarks.harness.window import Tracer
    cell = (loader.rehearsal_cell(args.rehearse) if args.rehearse
            else loader.cell(args.workload))
    print(json.dumps({"device": jax.devices()[0].device_kind}), flush=True)
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        cell["traffic"] = dict(cell["traffic"], rate_per_s=rate)
        tracer = Tracer(args.trace, os.path.join(ROOT, ".bench_trace"))
        tracer.seconds = min(args.seconds, 8)
        out = loader.driver(cell["config"]).run(
            cell, args.seed + k, args.seconds, tracer, lambda t: None)
        ctx = out["ctx"]
        extra = {}
        if args.trace:
            from benchmarks.harness import serve_trace, trace as T
            tr = ctx["trace"]
            decode, chunk = serve_trace.step_programs(ctx)
            runs = T.program_runs(tr["trace"], tr["t0"], tr["t1"])
            extra = {
                "busy_s": tr["busy_s"], "window_s": tr["window_s"],
                "programs": {n: [len(r), statistics.median(T.seconds(r)),
                                 sum(T.seconds(r))]
                             for n, r in runs.items()},
                "decode_ms": statistics.median(T.seconds(decode)) * 1e3
                if decode else None,
                "chunk_ms": statistics.median(T.seconds(chunk)) * 1e3
                if chunk else None,
                "top_ops": T.top_ops(tr["trace"], tr["t0"], tr["t1"]),
                "idle_gaps": T.idle_gaps(tr["trace"], tr["t0"], tr["t1"])}
        itl = sorted(ms for t, ms in ctx["recorder"].inter_token
                     if ctx["t_start"] <= t < ctx["t_end"])
        m0, m1 = ctx["marks"]["start"], ctx["marks"]["end"]
        log, first = ctx["log"], ctx["first"]
        ids = [i for i in ctx["in_window"] if i in first]
        ttft = [(first[i][0] - log[i]["due"]) * 1e3 for i in ids]
        half = len(ttft) // 2
        occ = [n for t, n in ctx["recorder"].occupancy
               if ctx["t_start"] <= t < ctx["t_end"]]
        print(json.dumps({
            "rate_per_s": rate, "due": out["attempted"],
            "failed": out["failed"], "correct": out["correct"],
            "finished_by_close": sum(
                1 for i in ids if i in ctx["done"]
                and ctx["done"][i][0] < ctx["t_end"]),
            "ttft_ms_p50_first_half": statistics.median(ttft[:half]),
            "ttft_ms_p50_second_half": statistics.median(ttft[half:]),
            "ttft_ms_max": max(ttft),
            "drain_s": ctx["t_drained"] - ctx["t_end"],
            "itl_ms_p50": itl[len(itl) // 2] if itl else None,
            "itl_ms_by_20th": itl[::max(1, len(itl) // 20)],
            "ttft_ms_sorted": sorted(round(t) for t in ttft),
            "counters": {k: m1[k] - m0[k] for k in m0},
            "memory_peak_bytes": out["memory_peak_bytes"], **extra,
            "occupancy_mean": sum(occ) / max(len(occ), 1),
            "occupancy_max": max(occ, default=0),
            **out["end_to_end"]}), flush=True)
        del out, ctx, log, first
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
