"""The cell `laguna-xs.2.train-lc16k` (PR 32) as the benchmark declares it:
its work counts against hand values (harness/work_laguna.py), its
configuration against the catalog's row, its declaration in BENCHMARK.json,
its readers on a program without their scopes, and the control flow of its
driver on the CPU (`--rehearse tiny-laguna:train-lc16k`). Nothing of the
program is imported here.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.drivers.train_vl import model_of               # noqa: E402
from benchmarks.harness import loader, work_laguna as W        # noqa: E402

BENCH = loader.benchmark()
CELL = "laguna-xs.2.train-lc16k"
CFG = loader.load_json("configs", "laguna-xs.2.json")
TRAFFIC = loader.load_json("traffic", "train-lc16k.json")
MODEL = model_of(CFG)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ["train_full_attn_device_ms", "train_window_attn_device_ms",
       "train_full_attn_roofline", "train_window_attn_roofline",
       "train_expert_layers_device_ms", "train_expert_layers_roofline",
       "expert_load_max_over_mean"]


def whole():
    """The published model: 40 layers of the pattern, all 256 experts, the
    whole vocabulary."""
    types = (["full_attention"] + ["sliding_attention"] * 3) * 10
    return dict(
        MODEL, num_hidden_layers=40, num_experts=256, vocab_size=100_352,
        layer_types=types, mlp_layer_types=["dense"] + ["sparse"] * 39,
        num_attention_heads_per_layer=[
            48 if t == "full_attention" else 64 for t in types])


def test_parameters_at_this_cut_and_whole():
    full = 2 * 2048 * 48 * 128 + 2 * 2048 * 8 * 128 + 2048 * 48
    window = 2 * 2048 * 64 * 128 + 2 * 2048 * 8 * 128 + 2048 * 64
    assert (full, window) == (29_458_432, 37_879_808)
    assert W.attention_params(MODEL, 48) == full
    assert W.attention_params(MODEL, 64) == window
    dense = 3 * 2048 * 8192
    sparse = 2048 * 256 + 3 * 2048 * 512 + 32 * 3 * 2048 * 512
    assert (dense, sparse) == (50_331_648, 104_333_312)
    norms = 2 * 2048
    assert full + dense + norms == 79_794_176              # layer 0
    assert window + sparse + norms == 142_217_216          # a window layer
    assert full + sparse + norms == 133_795_840            # the full sparse
    ends = 2 * 12_544 * 2048 + 2048
    assert ends == 51_382_272
    assert W.param_count(MODEL) == 79_794_176 + 3 * 142_217_216 \
        + 133_795_840 + ends == 691_623_936
    # 16 bytes a parameter: master, gradient, Adam's two moments
    assert 16 * W.param_count(MODEL) == 11_065_982_976     # 69% of 16 GB
    # the published 40 layers with a per-head gate: the catalog's "33.4B"
    assert W.param_count(whole()) == 33_442_596_864
    elementwise = W.param_count(whole()) + sum(
        2048 * h * 127 for h in whole()["num_attention_heads_per_layer"])
    assert round(elementwise / 1e9, 2) == 34.07            # not the 33.4B


def test_the_reference_holds_the_same_parameters():
    from benchmarks.references import laguna
    shapes = laguna.param_shapes(MODEL)
    total = 0
    for leaves in shapes.values():
        for shape in leaves.values():
            n = 1
            for d in shape:
                n *= d
            total += n
    assert total == W.param_count(MODEL)
    assert shapes["l1_mlp"]["Wr"] == (2048, 256)    # the router's own width
    assert shapes["l4_mlp"]["Wg"] == (32, 2048, 512)
    assert shapes["l0_mlp"]["Wg"] == (2048, 8192)
    assert shapes["l0_attn"]["Wgate"] == (2048, 48)
    assert shapes["l2_attn"]["Wq"] == (2048, 64 * 128)
    assert shapes["head"]["W"] == (2048, 12_544)


def test_pairs_and_flops_a_row():
    t = TRAFFIC["seq_len"]
    assert W.visible_pairs(t) == t * (t + 1) // 2 == 134_225_920
    assert W.visible_pairs(t, 512) == 512 * 513 // 2 + (t - 512) * 512 \
        == sum(min(q + 1, 512) for q in range(t)) == 8_257_792
    assert W.visible_pairs(64, 512) == W.visible_pairs(64) == 2080
    assert W.held_pairs(MODEL, t) == t * 8 * 32 // 256 == 16_384
    assert W.attention_train_flops(MODEL, t, 48) == \
        3 * 4 * 48 * 128 * 134_225_920 == 9_896_208_629_760
    assert W.attention_train_flops(MODEL, t, 64, 512) == \
        3 * 4 * 64 * 128 * 8_257_792 == 811_773_984_768
    full, window = (W.attention_train_work(MODEL, t, w) for w in (False, True))
    assert full == (2 * 9_896_208_629_760, 2 * 1_409_286_144)
    assert window == (3 * 811_773_984_768, 3 * 1_811_939_328)
    assert W.experts_train_flops(MODEL, t) == \
        3 * 3 * 2 * 2048 * (16_384 * 512 + t * 512) == 618_475_290_624
    assert W.experts_train_bytes(MODEL, t) == \
        4 * 3 * 2048 * 33 * 512 * 2 + 3 * 2 * 16_384 * 2 * 5120 \
        == 1_837_105_152
    assert W.sparse_layers(MODEL) == 4
    row = W.train_flops_per_row(MODEL, t, t - 1)
    assert row == 49_343_861_096_448                # 49.3 TFLOP a row
    # at 16k attention is 45% of the algorithm: the full layers' 40%, the
    # window layers' 5% at the same projections
    assert round(full[0] / row, 3) == 0.401
    assert round(window[0] / row, 3) == 0.049


def test_configuration_is_the_published_one_but_for_its_cuts():
    assert CFG["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size", "layer_types",
        "mlp_layer_types", "num_attention_heads_per_layer"]
    assert (CFG["num_hidden_layers"], CFG["num_experts"],
            CFG["vocab_size"]) == (5, 32, 12_544)
    assert CFG["vocab_size"] * 8 == CFG["published"]["vocab_size"]
    assert CFG["layer_types"] == ["full_attention"] + \
        ["sliding_attention"] * 3 + ["full_attention"]
    assert CFG["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert CFG["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    dep = CFG["deployment"]
    assert (dep["chips_per_layer"], dep["router_width"], dep["first_held"],
            dep["layers"]) == (8, 256, 0, [0, 1, 2, 3, 4])
    assert dep["router_width"] == CFG["published"]["num_experts"]
    args = CFG["program"]["args"]
    for key in ("hidden_size", "num_key_value_heads", "head_dim",
                "intermediate_size", "moe_intermediate_size",
                "shared_expert_intermediate_size", "num_experts_per_tok",
                "moe_routed_scaling_factor", "sliding_window",
                "rms_norm_eps", "rope_parameters"):
        assert args[key] == CFG[key], key
    assert (args["layers"], args["experts_held"], args["first_held"],
            args["vocab_rows"], args["num_experts"], args["vocab_size"],
            args["num_hidden_layers"]) == \
        ([0, 1, 2, 3, 4], 32, 0, 12_544, 256, 100_352, 40)
    for i in args["layers"]:        # the kept layers' entries are the file's
        for key in ("layer_types", "mlp_layer_types",
                    "num_attention_heads_per_layer"):
            assert args[key][i] == CFG[key][i], key
    for key in ("gate", "router", "shared_expert", "activation", "window",
                "rotary", "optimizer", "weights"):
        assert CFG["assumed"][key]
    assert set(CFG["limits"]) == {
        "loss1_rel", "loss2_rel", "loss3_rel", "grad_norm_gap",
        "grad_norm_gap_p50", "grad_norm_gap_w50", "change_norm_gap",
        "change_norm_gap_w50"}
    # each lies under the fp8 control's or a planted fault's reading
    # (PERF.md section 2), none at what only an unchanged state fails
    assert max(CFG["limits"].values()) < 0.005
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Laguna-XS.2")
    entry = next(c for c in BENCH["configs"] if c["name"] == CFG["name"])
    assert entry["source"] == CFG["source"] == row["source_url"]
    assert entry["reduced"] == CFG["reduced"]
    for key, value in row["config"].items():
        if key not in CFG["reduced"]:
            assert CFG[key] == value, key
        else:
            assert args[key] == value, key      # the program is told both


def test_the_cells_declaration():
    cell = loader.cell(CELL)
    (w,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert (w["config"], w["traffic"], w["chips"]) == \
        ("laguna-xs.2", "train-lc16k", 1)
    assert "more than its share" in w["why"]
    assert [m["name"] for m in cell["end_to_end"]] == ["images_per_s",
                                                       "setup_s"]
    assert [m["name"] for m in cell["per_layer"]] == [
        "train_step_device_ms", "train_mfu"] + NEW
    new = [m for m in BENCH["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in new] == NEW
    assert all(m["layer"] == "trainer containers"
               and m["moves"] == "images_per_s" for m in new)
    assert [m["unit"] for m in new] == ["ms", "ms", "%", "%", "ms", "%",
                                        "ratio"]
    # what was there stays first and in its order; one cell of four on four
    assert [w["name"] for w in BENCH["workloads"]][-1] == CELL
    assert [m["name"] for m in BENCH["per_layer"]][-7:] == NEW
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    assert TRAFFIC == {**TRAFFIC, "kind": "train_ring", "ring": 8, "rows": 1,
                       "seq_len": 16_384, "trace_seconds": 10}


def test_readers_find_nothing_on_a_program_without_their_scopes():
    """A program without the scopes, the text or the gauges (the parent's):
    every new reader returns None and none raises."""
    ctx = {"trace": None, "gauges": None, "model": MODEL, "rows": 1,
           "seq_len": 16_384, "peaks": {"bf16_flops": 197e12,
                                        "hbm_bytes_per_s": 819e9}}
    for name in NEW:
        assert loader.metric_reader(name)(dict(ctx)) is None, name
    # Keye's step has `moe` and `experts` and neither `shared` nor
    # `attend_full`: the attention readers stay silent there
    ctx["inner_times"] = {frozenset({"moe", "experts"}): 60.0,
                          frozenset({"sparseattention", "attend"}): 260.0}
    for name in NEW[:4]:
        assert loader.metric_reader(name)(dict(ctx)) is None, name


def test_readers_divide_the_work_by_the_scopes_times():
    """Hand times by scope: the rooflines are the work's least time at the
    peaks over them, in percent, never clipped."""
    ctx = {"gauges": {"moe.l1_mlp.held_pairs_max": 600.0,
                      "moe.l1_mlp.held_pairs_mean": 500.0,
                      "moe.l2_mlp.held_pairs_max": 550.0,
                      "moe.l2_mlp.held_pairs_mean": 500.0},
           "model": MODEL, "rows": 1, "seq_len": 16_384,
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
           "inner_times": {
               frozenset({"attention", "attend_full"}): 200.0,
               frozenset({"attention", "attend_window"}): 50.0,
               frozenset({"attention", "rotary"}): 30.0,
               frozenset({"moe", "experts"}): 40.0,
               frozenset({"moe", "shared"}): 10.0,
               frozenset({"moe", "router"}): 5.0,
               frozenset({"moe"}): 20.0}}
    read = lambda name: loader.metric_reader(name)(dict(ctx))
    assert read("train_full_attn_device_ms") == 200.0
    assert read("train_window_attn_device_ms") == 50.0
    assert read("train_expert_layers_device_ms") == 75.0
    assert read("expert_load_max_over_mean") == pytest.approx(1.2)
    # 19.79 TFLOP at 197 TFLOP/s is 100.47 ms (compute-bound): 50.2% of 200
    assert read("train_full_attn_roofline") == pytest.approx(
        100 * (2 * 9_896_208_629_760 / 197e12) / 0.2)
    # the window layers: 2.44 TFLOP is 12.36 ms, their 5.44 GB 6.64 ms
    assert read("train_window_attn_roofline") == pytest.approx(
        100 * (3 * 811_773_984_768 / 197e12) / 0.05)
    assert read("train_expert_layers_roofline") == pytest.approx(
        100 * (4 * 618_475_290_624 / 197e12) / 0.05)


def test_rehearsal_runs_the_cells_control_flow_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--rehearse", "tiny-laguna:train-lc16k", "--seed", str(2**31 + 77),
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"images_per_s", "setup_s"}
    assert line["attempted"] % 8 == 0               # whole rings of 8
    assert line["read"]["loss1_rel"] < 1e-3
    assert line["read"]["expert_load_max_over_mean"] == pytest.approx(
        line["read"]["expert_load_max_over_mean_ref"], rel=0.2)
