"""Finds everything a cell needs BY NAME: the cell in BENCHMARK.json, its
configuration and traffic files, its driver and reference modules and the
reader of each per-layer metric. Adding a cell, a configuration, a traffic
mix or a metric is adding files and entries; nothing here changes."""
import importlib
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(*parts):
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def make_cell(name, config, traffic, chips, bench=None):
    """The cell as the drivers see it: its two data files and the metrics
    declared for it (an entry without a `workloads` key is for every cell
    that reports the end-to-end metric it names)."""
    bench = bench or {"end_to_end": [], "per_layer": []}
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return {"name": name, "chips": int(chips),
            "config": load_json("configs", config + ".json"),
            "traffic": load_json("traffic", traffic + ".json"),
            "end_to_end": e2e, "per_layer": per_layer}


def cell(workload):
    bench = benchmark()
    for w in bench["workloads"]:
        if w["name"] == workload:
            return make_cell(w["name"], w["config"], w["traffic"],
                             w["chips"], bench)
    raise SystemExit(f"benchmarks: no workload {workload!r} in "
                     f"BENCHMARK.json")


def rehearsal_cell(spec, metrics=False):
    """The cell of `--rehearse <config>:<traffic>[:chips]`: a configuration
    that no cell names under a cell's traffic mix, on the CPU. With
    `metrics`, it reports what the cells of that traffic mix report."""
    config, traffic, *chips = spec.split(":")
    name = "rehearsal." + traffic
    bench = None
    if metrics:
        bench = benchmark()
        like = {w["name"] for w in bench["workloads"]
                if w["traffic"] == traffic}
        declared = lambda m: like & set(m.get("workloads", like))
        bench = {k: [dict(m, workloads=[name]) for m in bench[k]
                     if declared(m)] for k in ("end_to_end", "per_layer")}
    return make_cell(name, config, traffic, int(chips[0]) if chips else 1,
                     bench)


def driver(cfg):
    return importlib.import_module(f"benchmarks.drivers.{cfg['driver']}")


def reference(cfg):
    return importlib.import_module(
        f"benchmarks.references.{cfg['reference']}")


def metric_reader(name):
    """benchmarks/metrics/<name>.py, '.' and '-' in a metric's name read as
    '_' in the file's."""
    mod = name.replace(".", "_").replace("-", "_")
    return importlib.import_module(f"benchmarks.metrics.{mod}").read
