"""The cell `keye-vl-2.0-30b-a3b.train-vl8k` (PR 28) as the benchmark declares
it: its work counts against hand values (harness/work_keye.py), its
configuration against the published one, its declaration in BENCHMARK.json,
its readers on a program without their scopes, and the control flow of its
driver on the CPU (`--rehearse tiny-keye:train-vl8k`).
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
from benchmarks.harness import loader, work_keye as W          # noqa: E402

BENCH = loader.benchmark()
CELL = "keye-vl-2.0-30b-a3b.train-vl8k"
CFG = loader.load_json("configs", "keye-vl-2.0-30b-a3b.json")
TRAFFIC = loader.load_json("traffic", "train-vl8k.json")
MODEL = model_of(CFG)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_parameters_at_this_cut_and_whole():
    layer = (2 * 2048 * 4096 + 2 * 2048 * 512 + 2 * 128     # q, o, k, v, norms
             + 2048 * 1024 + 2048 * 64 + 2048 * 16          # the indexer
             + 2048 * 128 + 16 * 3 * 2048 * 768             # router, 16 held
             + 2 * 2048)
    assert layer == 96_899_328
    assert W.param_count(MODEL) == 6 * layer + 2 * 18_992 * 2048 + 2048 \
        == 659_189_248
    whole = dict(MODEL, num_hidden_layers=48, num_local_experts=128,
                 vocab_size=151_936)
    assert W.param_count(whole) == 30_640_650_240          # "30B-A3B"
    # 16 bytes a parameter: master, gradient, Adam's two moments
    assert 16 * W.param_count(MODEL) == 10_547_027_968     # 66% of 16 GB


def test_the_reference_holds_the_same_parameters():
    from benchmarks.references import keye_vl
    shapes = keye_vl.param_shapes(MODEL)
    total = 0
    for leaves in shapes.values():
        for shape in leaves.values():
            n = 1
            for d in shape:
                n *= d
            total += n
    assert total == W.param_count(MODEL)
    assert shapes["l0_moe"]["Wr"] == (2048, 128)    # the router's own width
    assert shapes["l5_moe"]["Wg"] == (16, 2048, 768)
    assert shapes["head"]["W"] == (2048, 18_992)


def test_pairs_and_flops_a_row():
    t = TRAFFIC["seq_len"]
    assert W.selected_pairs(t, 2048) == 2048 * 2049 // 2 + 6144 * 2048 \
        == 14_681_088                              # 1,792 keys a query
    assert W.selected_pairs(64, 2048) == W.causal_pairs(64) == 2080
    assert W.causal_pairs(t) == 33_558_528
    assert W.held_pairs(MODEL, 2 * t) == 2 * t * 8 * 16 // 128 == 16_384
    assert W.attention_train_flops(MODEL, t) == 3 * 4 * 4096 * 14_681_088 \
        == 721_604_837_376
    assert W.indexer_train_flops(MODEL, t) == \
        2 * 2 * 2_260_992 * t + 3 * 2 * 1024 * 33_558_528 == 280_271_781_888
    assert W.experts_train_flops(MODEL, 2 * t) == \
        3 * 16_384 * 3 * 2 * 2048 * 768 == 463_856_467_968
    labels = t - 32 * 32 - 1
    assert W.train_flops_per_row(MODEL, t, labels) == 14_719_005_425_664
    # the new mechanisms are most of a layer's arithmetic
    proj = 3 * 2 * 18_874_368 * t
    layer = (W.train_flops_per_row(MODEL, t, 0) // 6)
    assert 0.5 < 1 - proj / layer < 0.65
    assert W.attention_train_bytes(MODEL, t) == 461_374_464
    assert W.experts_train_bytes(MODEL, 2 * t) == 1_157_627_904


def test_configuration_is_the_published_one_but_for_its_cuts():
    assert CFG["reduced"] == ["num_hidden_layers", "num_experts",
                              "num_local_experts", "vocab_size"]
    assert (CFG["num_hidden_layers"], CFG["num_local_experts"],
            CFG["num_experts"], CFG["vocab_size"]) == (6, 16, 16, 18_992)
    assert CFG["vocab_size"] * 8 == CFG["published"]["vocab_size"]
    dep = CFG["deployment"]
    assert (dep["chips_per_layer"], dep["router_width"]) == (8, 128)
    assert dep["router_width"] == CFG["published"]["num_experts"]
    args = CFG["program"]["args"]
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "head_dim", "moe_intermediate_size", "num_experts_per_tok",
                "rope_theta", "norm_topk_prob", "rms_norm_eps"):
        assert args[key] == CFG[key], key
    assert args["mrope_section"] == CFG["rope_scaling"]["mrope_section"]
    assert (args["topk"], args["indexer_num_heads"],
            args["indexer_head_dim"]) == (2048, 16, 64)
    assert (args["n_layers"], args["experts_held"], args["vocab_rows"],
            args["num_experts"]) == (6, 16, 18_992, 128)
    for key in ("qk_norm", "indexer", "selection", "indexer_loss",
                "optimizer", "weights", "vision_tower"):
        assert CFG["assumed"][key]
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Keye-VL-2.0-30B-A3B")
    entry = next(c for c in BENCH["configs"] if c["name"] == CFG["name"])
    assert entry["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in CFG["reduced"]:
            assert CFG[key] == value, key


def test_the_cells_declaration():
    cell = loader.cell(CELL)
    assert cell["chips"] == 1
    assert [m["name"] for m in cell["end_to_end"]] == ["images_per_s",
                                                       "setup_s"]
    assert [m["name"] for m in cell["per_layer"]] == [
        "train_step_device_ms", "train_mfu", "train_sparse_attn_device_ms",
        "train_indexer_device_ms", "train_moe_device_ms",
        "train_sparse_attn_roofline", "train_moe_roofline",
        "moe_load_max_over_mean"]
    # `train_update_device_ms` and `train_dispatch_host_ms` are not joined:
    # test_scope_metrics.py, which this PR may not edit, pins their lists
    new = [m for m in BENCH["per_layer"] if m["workloads"] == [CELL]]
    assert len(new) == 6
    assert all(m["layer"] == "trainer containers"
               and m["moves"] == "images_per_s" for m in new)
    assert (TRAFFIC["kind"], TRAFFIC["ring"], TRAFFIC["rows"],
            TRAFFIC["seq_len"], TRAFFIC["image_grid"]) == \
        ("train_ring", 8, 2, 8192, [32, 32])
    # eight or more whole steps in the traced part at a second a step
    assert TRAFFIC["trace_seconds"] == 5


def test_readers_find_nothing_on_a_program_without_their_scopes():
    """The parent's program has no such scopes, text or gauges: every new
    reader returns None and none raises."""
    ctx = {"trace": None, "gauges": None, "model": MODEL, "rows": 2,
           "seq_len": 8192, "peaks": {"bf16_flops": 197e12,
                                      "hbm_bytes_per_s": 819e9}}
    for name in ("train_sparse_attn_device_ms", "train_indexer_device_ms",
                 "train_sparse_attn_roofline", "train_moe_roofline",
                 "moe_load_max_over_mean"):
        assert loader.metric_reader(name)(dict(ctx)) is None, name


def test_inner_scopes_join_a_hand_trace_to_a_hand_text():
    pytest.importorskip("jax")
    from benchmarks.harness import inner_scopes
    text = "\n".join([
        'ENTRY %main (p: f32[8]) -> f32[8] {',
        '  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, '
        'calls=%f1, metadata={op_name="jit(step)/jvp(sparseattention.l0_attn)'
        '/while/body/checkpoint/indexer/dot_general"}',
        '  %custom-call.2 = f32[8]{0} custom-call(f32[8]{0} %fusion.1), '
        'custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/'
        'transpose(jvp(jvp()))/checkpoint/rematted_computation/attend/'
        'pallas_call"}',
        '  %fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%f3,'
        ' metadata={op_name="jit(step)/jvp(moe.l0_moe)/experts/while/body/'
        'ragged_dot"}',
        '  %while.4 = f32[8]{0} while(f32[8]{0} %p), body=%b, condition=%c,'
        ' metadata={op_name="jit(step)/jvp(moe.l0_moe)/experts/while"}',
        '}'])
    ops = [["%fusion.1 = f32[8]{0} fusion(...)", 10, 40],
           ["%while.4 = f32[8]{0} while(...)", 165, 30],
           ["%custom-call.2 = f32[8]{0} custom-call(...)", 60, 100],
           ["%fusion.3 = f32[8]{0} fusion(...)", 170, 20],
           ["%fusion.1 = f32[8]{0} fusion(...)", 400, 40]]    # outside
    trace = {"devices": {"0": {"ops": ops,
                               "modules": [["jit_step(1)", 0, 200]]}},
             "host": []}
    ctx = {"step_text": text,
           "trace": {"trace": trace, "t0": 0, "t1": 300}}
    ms = lambda *names: inner_scopes.inner_ms(ctx, *names)
    assert ms("indexer") == ms("indexer", "select") == pytest.approx(40e-6)
    assert ms("attend") == pytest.approx(100e-6)
    # an operation on two of the names counts once; the loop that only
    # contains others not at all
    assert ms("experts") == ms("moe", "router", "experts") == \
        pytest.approx(20e-6)
    assert ms("sparseattention") == pytest.approx(40e-6)
    assert ms("router") is None


def test_rehearsal_runs_the_cells_control_flow_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--rehearse", "tiny-keye:train-vl8k", "--seed", str(2**31 + 77),
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"images_per_s", "setup_s"}
    assert line["read"]["selection_agreement"] > 0.97
    assert line["read"]["indexer_loss_rel"] < 0.05
