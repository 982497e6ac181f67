"""Tier-1 tests of the benchmark's own yardstick (benchmarks/harness), on the
CPU in seconds: the trace reducer on a small hand-made trace and on one
recorded on a v5e, the work counts against hand values, the traffic
generator's same-multiset rule, BENCHMARK.json against the limits of its
contract, and run.py's refusal to measure without a TPU. They drive nothing
of the program's insides, so a later PR that changes the program cannot
break them; the tests that do (the controls and the planted faults) are in
benchmarks/tests/.
"""
import collections
import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.harness import compare, loader, traffic, work  # noqa: E402
from benchmarks.harness import trace as T                      # noqa: E402

BENCH = loader.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")


# ------------------------------------------------------- the trace reducer
def hand_trace():
    """Two operations that overlap, a third after a gap, the two module
    events that cover them (lines NEST), and the host's annotations."""
    ops = [["fusion.1", 0, 100], ["copy.2", 50, 100], ["fusion.3", 300, 50]]
    modules = [["jit_step(123)", 0, 150], ["jit_other(9)", 300, 50]]
    host = [[T_WINDOW, 0, 400], ["bench.submit", 160, 100],
            ["PjitFunction(step)", 170, 20]]
    return {"devices": {"0": {"ops": ops, "modules": modules}}, "host": host}


T_WINDOW = "bench.traced_window"


def test_busy_is_the_union_of_the_operation_line_not_a_sum():
    tr = hand_trace()
    t0, t1 = T.annotation_window(tr, T_WINDOW)
    assert (t0, t1) == (0, 400)
    # union 150 + 50; a sum over the line gives 250, over both lines 450
    assert T.busy_seconds(tr, t0, t1) == pytest.approx(200e-9)
    idle_share = 1 - T.busy_seconds(tr, t0, t1) / ((t1 - t0) / 1e9)
    assert idle_share == pytest.approx(0.5)


def test_busy_is_clipped_to_the_window_and_averaged_over_devices():
    tr = hand_trace()
    tr["devices"]["1"] = {"ops": [["fusion.1", 0, 400]], "modules": []}
    assert T.busy_seconds(tr, 0, 400) == pytest.approx(300e-9)
    assert T.busy_seconds(tr, 100, 350) == pytest.approx(
        ((50 + 50) + 250) / 2 * 1e-9)


def test_a_named_programs_time_is_its_module_events():
    runs = T.program_runs(hand_trace(), 0, 400)
    assert {T.short_name(n): T.seconds(r) for n, r in runs.items()} == {
        "jit_step": [pytest.approx(150e-9)],
        "jit_other": [pytest.approx(50e-9)]}
    # a run that straddles the window's edge is not a whole run
    assert "jit_step(123)" not in T.program_runs(hand_trace(), 10, 400)


def test_idle_gaps_are_named_by_the_shortest_covering_host_event():
    gaps = dict(T.idle_gaps(hand_trace(), 0, 400))
    # [150, 300): its middle, 225, lies in bench.submit only
    assert gaps == {"bench.submit": pytest.approx(150e-9),
                    T_WINDOW: pytest.approx(50e-9)}
    ops = dict(T.top_ops(hand_trace(), 0, 400))
    assert ops["copy x1"] == pytest.approx(100e-9)
    assert ops["fusion x2"] == pytest.approx(150e-9)


def test_op_label_keeps_kind_and_largest_output():
    assert T.op_label(
        "%fusion.54 = (f32[256]{0:T(256)S(1)}, bf16[128,56,56,256]"
        "{3,0,2,1:T(8,128)(2,1)}) fusion(bf16[128,56,56,256]{3,0,2,1} "
        "%get-tuple-element.1290), kind=kOutput, calls=%fused.82") == \
        "fusion bf16[128,56,56,256]"
    assert T.op_label("%copy.12 = bf16[32768,25,64]{2,1,0:T(8,128)(2,1)} "
                      "copy(bf16[32768,25,64]{1,2,0} %p)") == \
        "copy bf16[32768,25,64]"
    assert T.op_label("dot.2") == "dot"


def test_reducer_on_a_trace_recorded_on_the_chip():
    """benchmarks/tests/data/small.xplane.pb: four rounds of two jitted
    programs on one TPU v5e, recorded through harness/window.py's options
    (PR 24). The reducer finds the device's lines and both programs, and
    busy time is under the window and under the sum of the lines."""
    pytest.importorskip("jax")
    data = os.path.join(ROOT, "benchmarks", "tests", "data")
    tr = T.read_xplane(data)
    assert list(tr["devices"]) == ["0"]
    t0, t1 = T.annotation_window(tr, T_WINDOW)
    window_s = (t1 - t0) / 1e9
    busy = T.busy_seconds(tr, t0, t1)
    dev = tr["devices"]["0"]
    both_lines = sum(d for _, _, d in T.clip(dev["ops"], t0, t1)
                     + T.clip(dev["modules"], t0, t1)) / 1e9
    assert 0 < busy < window_s
    assert busy < both_lines
    runs = {T.short_name(n): r
            for n, r in T.program_runs(tr, t0, t1).items()}
    # four rounds; the device's clock runs 0.8 ms ahead of the host's, so
    # the first run of the first program starts before the annotation
    # does and is not a whole run of the window
    assert len(runs["jit_small_step"]) == 3
    assert len(runs["jit_other_prog"]) == 4
    assert all(d == pytest.approx(15e-6, rel=0.01)
               for d in T.seconds(runs["jit_small_step"]))
    # the programs' time is device time: no more than the busy time
    assert sum(sum(T.seconds(r)) for r in runs.values()) <= busy * 1.001
    assert T.idle_gaps(tr, t0, t1) and T.top_ops(tr, t0, t1)


# --------------------------------------------------------- the work counts
GPT2_XL = loader.load_json("configs", "gpt2-xl.json")["model"]
RESNET50 = loader.load_json("configs", "resnet50.json")["model"]


def test_gpt2_xl_counts_against_hand_values():
    d, ff, v, layers = 1600, 6400, 50257, 48
    per_block = 4 * d * d + 2 * d * ff              # 30,720,000
    assert work.lm_matmul_params(GPT2_XL) == layers * per_block + d * v
    assert work.lm_matmul_params(GPT2_XL) == 1_554_971_200
    # with biases, layer norms, both embeddings and the untied head
    assert work.lm_param_count(GPT2_XL) == 1_637_715_200
    # one position's keys and values over 48 layers in bf16
    assert work.lm_kv_row_bytes(GPT2_XL) == 48 * 2 * 1600 * 2 == 307_200


def test_decode_step_work_counts_live_rows_and_weights_only():
    live = [200] * 32
    flops, nbytes = work.lm_decode_step_work(GPT2_XL, live)
    weights = 2 * 1_554_971_200
    assert nbytes == weights + 307_200 * (32 * 200 + 32)
    assert nbytes < 5.2e9          # never the 1,024-row table: 13.2e9
    assert flops == 32 * (2 * 1_554_971_200 + 4 * 48 * 1600 * 200)
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    least, bound = work.roofline_seconds(flops, nbytes, peaks)
    assert bound == "memory" and least == pytest.approx(nbytes / 819e9)


def test_resnet50_counts_against_hand_values():
    # He et al.'s 3.8e9 multiply-adds (v1, stride on the first 1x1) and
    # torchvision's 25,557,032 parameters
    macs = sum(ho * wo * kh * kw * ci * co for _, ho, wo, kh, kw, ci, co
               in work.resnet_conv_shapes(RESNET50))
    assert macs == 3_855_925_248
    assert len(work.resnet_conv_shapes(RESNET50)) == 53
    assert work.resnet_param_count(RESNET50) == 25_557_032
    stem = 112 * 112 * 7 * 7 * 3 * 64
    fc = 2048 * 1000
    assert work.resnet_train_flops_per_image(RESNET50) == \
        2 * (3 * (macs + fc) - stem) == 22_911_811_584


# -------------------------------------------------------------- the traffic
def lengths(plan, phase):
    return collections.Counter(
        (len(r["prompt"]), r["max_new"]) for r in plan[phase])


def gaps(plan, phase, length):
    """The gaps between arrivals, the one from the last arrival to the
    phase's end among them (they add up to the phase's length)."""
    due = sorted(r["due"] for r in plan[phase]) + [length]
    return sorted(round(b - a, 9) for a, b in zip(due, due[1:]))


@pytest.mark.parametrize("mix", ["chat-open", "docs-closed"])
def test_same_multiset_under_two_seeds_in_another_order(mix):
    tr = loader.load_json("traffic", mix + ".json")
    a = traffic.build(tr, 20.0, 5, 50257, 1024)
    b = traffic.build(tr, 20.0, 2**31 + 12345, 50257, 1024)
    for phase in ("ramp", "window"):
        assert lengths(a, phase) == lengths(b, phase)
    order = lambda p: [(len(r["prompt"]), r["max_new"]) for r in p["window"]]
    assert order(a) != order(b)
    assert any((x["prompt"] != y["prompt"]).any()
               for x, y in zip(sorted(a["window"], key=lambda r: len(r["prompt"])),
                               sorted(b["window"], key=lambda r: len(r["prompt"])))
               if len(x["prompt"]) == len(y["prompt"]))
    if tr["kind"] == "open_loop":
        assert gaps(a, "window", 20.0) == gaps(b, "window", 20.0)
        assert gaps(a, "ramp", tr["ramp_seconds"]) == \
            gaps(b, "ramp", tr["ramp_seconds"])
        assert [r["due"] for r in a["window"]] != \
            [r["due"] for r in b["window"]]
        assert all(0 <= r["due"] < 20.0 for r in a["window"])
        assert len(a["window"]) == round(tr["rate_per_s"] * 20.0)


def test_same_seed_same_traffic():
    tr = loader.load_json("traffic", "chat-open.json")
    a = traffic.build(tr, 10.0, 77, 50257, 1024)
    b = traffic.build(tr, 10.0, 77, 50257, 1024)
    assert all((x["prompt"] == y["prompt"]).all() and x["due"] == y["due"]
               for x, y in zip(a["window"], b["window"]))


def test_percentile_is_a_plain_percentile_of_raw_samples():
    v = list(range(1, 101))
    assert compare.percentile(v, 90) == 90
    assert compare.percentile(v, 95) == 95
    assert compare.percentile([3.0], 99) == 3.0
    assert compare.percentile([], 90) is None


# ------------------------------------------------- BENCHMARK.json's contract
def test_benchmark_json_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    assert len(BENCH["command"]) <= 32
    assert any(w.startswith(p + "/") for w in BENCH["command"]
               for p in BENCH["paths"])
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)


def test_every_name_and_unit_uses_the_allowed_characters():
    names = [c["name"] for c in BENCH["configs"]]
    names += [k for c in BENCH["configs"] for k in c["reduced"]]
    for w in BENCH["workloads"]:
        names += [w["name"], w["config"], w["traffic"]]
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        names.append(m["name"])
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for n in names:
        assert NAME.match(n), n
    for kind in ("configs", "workloads"):
        got = [x["name"] for x in BENCH[kind]]
        assert len(got) == len(set(got))
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(metrics) == len(set(metrics))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_metrics_are_declared_as_the_contract_asks():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", [])) <= cells
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e
        assert 1 <= len(m["layer"]) <= 200
        # every cell a metric lists reports the end-to-end metric it moves
        moved = e2e[m["moves"]].get("workloads", cells)
        assert set(m["workloads"]) <= set(moved), m["name"]
        if m["unit"] == "%" and ("roofline" in m["name"]
                                 or "mfu" in m["name"]):
            assert m["better"] == "higher"
    # beside every roofline that moves an end-to-end metric, a whole step's
    # share of the peak (mfu in its name) that moves the same metric
    for m in BENCH["per_layer"]:
        if m["name"].endswith("_roofline"):
            assert any("mfu" in re.split(r"[._]", o["name"])
                       and o["moves"] == m["moves"]
                       for o in BENCH["per_layer"])


def test_every_cell_resolves_to_files_that_exist():
    configs = {c["name"]: c for c in BENCH["configs"]}
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    used = set()
    for w in BENCH["workloads"]:
        cell = loader.cell(w["name"])
        used.add(w["config"])
        assert configs[w["config"]]["file"] == \
            f"benchmarks/configs/{w['config']}.json"
        assert cell["config"]["reduced"] == configs[w["config"]]["reduced"]
        assert hasattr(loader.driver(cell["config"]), "run")
        assert loader.reference(cell["config"])
        names = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2
        assert cell["per_layer"], w["name"]
        for m in cell["per_layer"]:
            assert callable(loader.metric_reader(m["name"]))
        assert set(cell["config"]["limits"])
    assert used == set(configs)
    for c in BENCH["configs"]:
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        assert c["source"] and len(c["source"]) <= 200


# ------------------------------------------------- run.py without a chip
def run_py(*args, cwd=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmarks", "run.py"), *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_py_refuses_to_measure_on_the_cpu():
    cell = BENCH["workloads"][0]["name"]
    r = run_py("--workload", cell, "--seed", str(2**31 + 5), "--seconds",
               "1", "--trace", "0")
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "Refusing to measure" in r.stderr


def test_run_py_fails_where_the_program_is_missing(tmp_path):
    """A directory that holds only BENCHMARK.json and the files under
    `paths`: another exit code than 0 and no result."""
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = run_py("--workload", BENCH["workloads"][0]["name"], "--seed", "1",
               "--seconds", "1", "--trace", "0", cwd=str(tmp_path))
    assert r.returncode != 0 and r.stdout.strip() == ""
