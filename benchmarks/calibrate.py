#!/usr/bin/env python3
"""The readings a cell's limits are set from (How `correct` is decided,
steps 3 to 5): many seeds in ONE process at the cell's own size, the program
against the reference, then the control and each planted fault against it.
The benchmark's runs never call this; the builder of a cell does, once:

    python3 benchmarks/calibrate.py --workload <name> --seeds 1,2,3 [--seconds s]

Prints one JSON line per seed and reading, and a summary (largest of the
program's, smallest of each control's) at the end.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--rehearse", metavar="CONFIG:TRAFFIC[:CHIPS]")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from deeplearning4j_tpu.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    from benchmarks.harness import loader
    cell = (loader.rehearsal_cell(args.rehearse) if args.rehearse
            else loader.cell(args.workload))
    print(json.dumps({"device": jax.devices()[0].device_kind,
                      "count": len(jax.devices())}), flush=True)
    readings = {}

    def emit(seed, what, numbers):
        print(json.dumps({"seed": seed, "reading": what, **numbers}),
              flush=True)
        for k, v in numbers.items():
            readings.setdefault(what, {}).setdefault(k, []).append(v)

    seeds = [int(s) for s in args.seeds.split(",")]
    loader.driver(cell["config"]).calibrate(cell, seeds, emit,
                                            seconds=args.seconds)
    summary = {what: {k: (max(v) if what == "program" else min(v))
                      for k, v in nums.items()}
               for what, nums in readings.items()}
    print(json.dumps({"summary_max_program_min_others": summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
