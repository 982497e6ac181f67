"""The cell `joyai-llm-flash.train-mtp8k` (PR 34) as the benchmark declares
it: its work counts against hand values (harness/work_joyai.py), its
configuration against the catalog's row, its declaration in BENCHMARK.json
by MEMBERSHIP (never as the last entry, never by `workloads == [cell]`: the
next configuration needs no edit here), its readers on a synthetic trace and on
a program without their scopes, and the control flow of its driver on the
CPU (`--rehearse tiny-joyai:train-mtp8k`). Nothing of the program is
imported here.
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
from benchmarks.harness import loader, work_joyai as W         # noqa: E402

BENCH = loader.benchmark()
CELL = "joyai-llm-flash.train-mtp8k"
CFG = loader.load_json("configs", "joyai-llm-flash.json")
TINY = loader.load_json("configs", "tiny-joyai.json")
TRAFFIC = loader.load_json("traffic", "train-mtp8k.json")
MODEL = model_of(CFG)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ["train_latent_attn_device_ms", "train_latent_attn_roofline",
       "train_latent_proj_device_ms", "train_mtp_device_ms"]
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def whole():
    """The published model: 40 layers, all 256 experts, the whole
    vocabulary, each layer on one chip."""
    return dict(MODEL, num_hidden_layers=40, n_routed_experts=256,
                vocab_size=129_280,
                deployment={"router_width": 256, "first_held": 0})


def test_parameters_at_this_cut_and_whole():
    attn = (2048 * 1536 + 1536 + 1536 * 32 * 192 + 2048 * 576 + 512
            + 512 * 32 * 256 + 4096 * 2048)
    assert attn == W.attention_params(MODEL) == 26_347_520
    expert = 3 * 2048 * 768
    assert expert == 4_718_592
    dense = 3 * 2048 * 7168
    sparse = 2048 * 256 + expert + 16 * expert
    norms = 2 * 2048
    assert attn + dense + norms == 70_391_808               # the dense layer
    # an expert layer here: the issue's 107,092,224 counts the 256 entries
    # of its selection bias, which are state and no trained leaf
    assert attn + sparse + norms + 256 == 107_092_224
    ends = 2 * 16_160 * 2048 + 2048
    module = 2 * 2048 * 2048 + 3 * 2048
    assert (ends, module) == (66_193_408, 8_394_752)
    trained = 70_391_808 + 5 * (107_092_224 - 256) + module + ends
    assert W.param_count(MODEL) == trained == 680_439_808
    assert trained + 5 * 256 == 680_441_088                 # the issue's
    # 16 bytes a parameter: master, gradient, Adam's two moments
    assert 16 * trained == 10_887_036_928                   # 68% of 16 GB
    # published: the main model 48.94B (the catalog's 48B), 50.19B with
    # the module (on the main table and head: neither counted again)
    main = dict(whole(), num_nextn_predict_layers=0)
    bias = 39 * 256
    assert round((W.param_count(main) + bias) / 1e9, 2) == 48.94
    with_module = W.param_count(whole()) + 40 * 256
    assert round(with_module / 1e9, 2) == 50.19
    layer = attn + 2048 * 256 + 257 * expert + norms + 256
    assert layer == 1_239_554_304


def test_the_reference_holds_the_same_parameters():
    from benchmarks.references import joyai
    shapes = joyai.param_shapes(MODEL)
    total = 0
    for leaves in shapes.values():
        for shape in leaves.values():
            n = 1
            for d in shape:
                n *= d
            total += n
    assert total == W.param_count(MODEL)
    # one table and one head: the module has no leaf for either
    assert not [n for n in shapes if n in ("mtp_embed", "mtp_head")]
    assert shapes["embed"]["W"] == (16_160, 2048)
    assert shapes["head"]["W"] == (2048, 16_160)
    assert shapes["l1_mlp"]["Wr"] == (2048, 256)    # the router's own width
    assert shapes["mtp_mlp"]["Wg"] == (16, 2048, 768)
    assert shapes["l0_mlp"]["Wg"] == (2048, 7168)
    assert shapes["l0_attn"]["Wq_b"] == (1536, 32 * 192)
    assert shapes["mtp_attn"]["Wkv_a"] == (2048, 512 + 64)
    assert shapes["l4_attn"]["Wkv_b"] == (512, 32 * 256)
    assert shapes["mtp_proj"]["W"] == (4096, 2048)
    assert joyai.sparse_names(joyai.sizes(MODEL)) == [
        "l1_mlp", "l2_mlp", "l3_mlp", "l4_mlp", "mtp_mlp"]


def test_pairs_and_flops_a_row():
    t = TRAFFIC["seq_len"]
    assert W.causal_pairs(t) == t * (t + 1) // 2 == 33_558_528
    assert W.held_pairs(MODEL, t) == t * 8 * 16 // 256 == 4096
    # a causal pair costs 2 x 192 + 2 x 128 forward a head
    assert W.latent_attention_train_flops(MODEL, t) == \
        3 * 32 * 33_558_528 * 640 == 2_061_835_960_320
    assert W.latent_attention_train_bytes(MODEL, t) == 2 * (
        t * 32 * (192 + 128 + 128 + 128) + t * 64
        + t * 32 * (192 + 128 + 128 + 2 * 128) + t * 64
        + t * 32 * (192 + 128 + 128) + t * 64) == 909_115_392
    assert W.latent_attention_train_work(MODEL, t) == (
        6 * 2_061_835_960_320, 6 * 909_115_392)
    assert W.experts_train_flops(MODEL, t) == \
        3 * 3 * 2 * 2048 * (4096 * 768 + t * 768) == 347_892_350_976
    assert W.layers(MODEL) == ["dense"] + ["sparse"] * 5
    row = W.train_flops_per_row(MODEL, t)
    assert row == 27_838_744_756_224                # 55.7 TFLOP a step of 2
    kernels = 6 * 2_061_835_960_320
    projections = 6 * 3 * 2 * (26_347_520 - 2048) * t
    assert round(kernels / row, 2) == 0.44
    assert round(projections / row, 2) == 0.28
    assert round((kernels + projections) / row, 2) == 0.72


def test_work_counts_at_the_tiny_size_by_hand():
    m = model_of(TINY)
    t = 128
    attn = (64 * 24 + 24 + 24 * 4 * 24 + 64 * 24 + 16 + 16 * 4 * 28
            + 4 * 12 * 64)
    assert W.attention_params(m) == attn == 10_280
    assert W.layers(m) == ["dense", "sparse", "sparse"]
    dense, expert = 3 * 64 * 96, 3 * 64 * 32
    sparse = 64 * 16 + expert + 4 * expert
    assert W.param_count(m) == 3 * (attn + 128) + dense + 2 * sparse \
        + 2 * 128 * 64 + 64 + 2 * 64 * 64 + 3 * 64
    pairs = 128 * 129 // 2
    assert W.latent_attention_train_flops(m, t) == \
        3 * 4 * pairs * (2 * 24 + 2 * 12)
    assert W.held_pairs(m, t) == 128 * 4 * 4 // 16
    mats = attn - 24 - 16
    want = (3 * 2 * 64 * 128 * (127 + 126) + 3 * 2 * 2 * 64 * 64 * t
            + 3 * (3 * 2 * mats * t + 3 * 4 * pairs * 72)
            + 3 * 2 * dense * t
            + 2 * (3 * 2 * 64 * 16 * t
                   + 3 * 3 * 2 * 64 * (128 * 32 + t * 32)))
    assert W.train_flops_per_row(m, t) == want


def test_configuration_is_the_published_one_but_for_its_cuts():
    assert CFG["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert (CFG["num_hidden_layers"], CFG["n_routed_experts"],
            CFG["vocab_size"]) == (5, 16, 16_160)
    assert CFG["vocab_size"] * 8 == CFG["published"]["vocab_size"]
    assert (CFG["hidden_size"], CFG["num_attention_heads"],
            CFG["q_lora_rank"], CFG["kv_lora_rank"], CFG["qk_nope_head_dim"],
            CFG["qk_rope_head_dim"], CFG["v_head_dim"],
            CFG["moe_intermediate_size"], CFG["num_experts_per_tok"],
            CFG["routed_scaling_factor"], CFG["intermediate_size"]) == \
        (2048, 32, 1536, 512, 128, 64, 128, 768, 8, 2.5, 7168)
    dep = CFG["deployment"]
    assert (dep["chips_per_layer"], dep["router_width"], dep["first_held"],
            dep["layers"]) == (16, 256, 0, [0, 1, 2, 3, 4])
    assert dep["router_width"] == CFG["published"]["n_routed_experts"]
    args = CFG["program"]["args"]
    for key in ("hidden_size", "num_attention_heads", "q_lora_rank",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "rope_theta", "intermediate_size",
                "moe_intermediate_size", "n_shared_experts",
                "num_experts_per_tok", "routed_scaling_factor",
                "rms_norm_eps", "scoring_func", "norm_topk_prob",
                "first_k_dense_replace", "num_nextn_predict_layers",
                "bias_update_rate", "mtp_loss_weight"):
        assert args[key] == CFG[key], key
    assert (args["layers"], args["experts_held"], args["first_held"],
            args["vocab_rows"], args["n_routed_experts"], args["vocab_size"],
            args["num_hidden_layers"]) == \
        ([0, 1, 2, 3, 4], 16, 0, 16_160, 256, 129_280, 40)
    assert (CFG["bias_update_rate"], CFG["mtp_loss_weight"]) == (0.001, 0.3)
    for key in ("bias_update_rate", "mtp_loss_weight", "mtp_concatenation",
                "mtp_input", "router", "shared_expert", "rotary",
                "optimizer", "weights"):
        assert CFG["assumed"][key]
    # both losses apart at every followed step, and the leaves' gaps
    assert {f"loss_{part}{i}_rel" for part in ("main", "mtp")
            for i in (1, 2, 3)} | {"grad_norm_gap", "change_norm_gap"} \
        <= set(CFG["limits"])
    # the medians under the fp8 control's readings, the worst leaf under a
    # planted fault's (PERF.md section 2), none at what only an unchanged
    # state fails
    assert max(CFG["limits"].values()) == CFG["limits"]["grad_norm_gap"] \
        < 0.05
    assert max(v for k, v in CFG["limits"].items()
               if k.endswith(("_p50", "_w50", "_rel"))) <= 0.001
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "JoyAI-LLM-Flash")
    entry = next(c for c in BENCH["configs"] if c["name"] == CFG["name"])
    assert entry["source"] == CFG["source"] == row["source_url"]
    assert entry["reduced"] == CFG["reduced"]
    for key, value in row["config"].items():
        if key not in CFG["reduced"]:
            assert CFG[key] == value, key
        else:
            assert args[key] == value, key      # the program is told both


def test_the_cells_declaration_by_membership():
    cell = loader.cell(CELL)
    (w,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert (w["config"], w["traffic"], w["chips"]) == \
        ("joyai-llm-flash", "train-mtp8k", 1)
    assert "more than its share" in w["why"] and len(w["why"]) <= 200
    assert {m["name"] for m in cell["end_to_end"]} == {"images_per_s",
                                                       "setup_s"}
    # a superset of the six it reports: a later PR may give it more
    assert {m["name"] for m in cell["per_layer"]} >= {
        "train_step_device_ms", "train_mfu", *NEW}
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW:
        m = by_name[name]
        assert CELL in m["workloads"]
        assert (m["layer"], m["moves"], m["source"]) == \
            ("trainer containers", "images_per_s", "device_trace")
    assert [by_name[n]["unit"] for n in NEW] == ["ms", "%", "ms", "ms"]
    # it joins neither of the other decoders' expert metrics
    for name in ("train_moe_device_ms", "train_moe_roofline",
                 "moe_load_max_over_mean", "train_expert_layers_device_ms",
                 "train_expert_layers_roofline",
                 "expert_load_max_over_mean"):
        assert CELL not in by_name[name]["workloads"]
    assert TRAFFIC == {**TRAFFIC, "kind": "train_ring", "ring": 8, "rows": 2,
                       "seq_len": 8192, "trace_seconds": 10}


def test_what_was_there_stands_before_what_this_cell_added():
    """New entries go at the END of their lists (the driver refuses one put
    in the middle as a move of what was there). `test_laguna_cell.py` pins
    Laguna's entries as the LAST ones and may not be edited here, so
    `tests/conftest.py` expects that one test to fail; everything else it
    asserts of Laguna's declaration is held here, by membership and order."""
    laguna = "laguna-xs.2.train-lc16k"
    seven = ["train_full_attn_device_ms", "train_window_attn_device_ms",
             "train_full_attn_roofline", "train_window_attn_roofline",
             "train_expert_layers_device_ms", "train_expert_layers_roofline",
             "expert_load_max_over_mean"]
    (w,) = [w for w in BENCH["workloads"] if w["name"] == laguna]
    assert (w["config"], w["traffic"], w["chips"]) == \
        ("laguna-xs.2", "train-lc16k", 1)
    assert "more than its share" in w["why"]
    there = loader.cell(laguna)
    assert [m["name"] for m in there["end_to_end"]] == ["images_per_s",
                                                        "setup_s"]
    assert [m["name"] for m in there["per_layer"]] == [
        "train_step_device_ms", "train_mfu"] + seven
    own = [m for m in BENCH["per_layer"] if m.get("workloads") == [laguna]]
    assert [m["name"] for m in own] == seven
    assert all(m["layer"] == "trainer containers"
               and m["moves"] == "images_per_s" for m in own)
    assert [m["unit"] for m in own] == ["ms", "ms", "%", "%", "ms", "%",
                                        "ratio"]
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    cells = [w["name"] for w in BENCH["workloads"]]
    assert cells[:4] == ["resnet50.train-staged", "resnet50.train-dp4",
                         "keye-vl-2.0-30b-a3b.train-vl8k", laguna]
    assert cells.index(CELL) > cells.index(laguna)
    names = [m["name"] for m in BENCH["per_layer"]]
    assert all(names.index(n) > names.index(seven[-1]) for n in NEW)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        if CELL in m.get("workloads", []) and laguna in m["workloads"]:
            assert m["workloads"].index(CELL) > m["workloads"].index(laguna)


def test_readers_find_nothing_on_a_program_without_their_scopes():
    """A program without the scopes or the text (the parent's): every new
    reader returns None and none raises."""
    ctx = {"trace": None, "gauges": None, "model": MODEL, "rows": 2,
           "seq_len": 8192, "peaks": PEAKS}
    for name in NEW:
        assert loader.metric_reader(name)(dict(ctx)) is None, name
    # Laguna's step has `attention`, `rotary` and `moe` and neither
    # `latent` nor `attend_latent`, and no vertex of a module
    ctx["inner_times"] = {frozenset({"attention", "attend_full"}): 200.0,
                          frozenset({"moe", "experts"}): 60.0}
    ctx["vertex_times"] = {frozenset({"attention.l0_attn"}): 200.0,
                           frozenset({"loss.head"}): 20.0}
    for name in (NEW[0], NEW[1], NEW[3]):
        assert loader.metric_reader(name)(dict(ctx)) is None, name


def test_readers_divide_the_work_by_the_scopes_times():
    """Hand times by scope: the roofline is the work's least time at the
    peaks over the kernels' time, in percent, never clipped."""
    ctx = {"model": MODEL, "rows": 2, "seq_len": 8192, "peaks": PEAKS,
           "inner_times": {
               frozenset({"latentattention", "attend_latent"}): 300.0,
               frozenset({"latentattention", "latent"}): 120.0,
               frozenset({"latentattention", "rotary"}): 15.0,
               frozenset({"latentattention"}): 9.0,
               frozenset({"moe", "experts"}): 40.0},
           "vertex_times": {
               frozenset({"latentattention.mtp_attn"}): 70.0,
               frozenset({"moe.mtp_mlp"}): 12.0,
               frozenset({"tokenembedding.mtp_embed"}): 1.0,
               frozenset({"loss.mtp_head"}): 20.0,
               frozenset({"loss.mtp_head", "lmhead.mtp_head"}): 2.0,
               frozenset({"loss.head"}): 21.0,
               frozenset({"latentattention.l4_attn"}): 70.0}}
    read = lambda name: loader.metric_reader(name)(dict(ctx))
    assert read("train_latent_attn_device_ms") == 300.0
    assert read("train_latent_proj_device_ms") == 135.0
    assert read("train_mtp_device_ms") == 105.0
    # 24.74 TFLOP at 197 TFLOP/s is 125.6 ms (compute-bound; its 10.9 GB
    # are 13.3 ms): 41.9% of 300 ms
    assert read("train_latent_attn_roofline") == pytest.approx(
        100 * (2 * 6 * 2_061_835_960_320 / 197e12) / 0.3)
    assert 41 < read("train_latent_attn_roofline") < 42


def test_the_vertex_join_reads_names_from_a_synthetic_trace():
    """`harness/vertex_scopes.py` on a two-instruction step: the fusion
    under the module's attention counts for the module, the main layer's
    does not; a loop's own event is left out."""
    from benchmarks.harness import vertex_scopes
    text = "\n".join([
        "HloModule jit_step",
        'ENTRY main {',
        '  %fusion.1 = f32[8]{0} fusion(), kind=kLoop, metadata={op_name='
        '"jit(step)/jit(main)/jvp(latentattention.mtp_attn)/latent/dot"}',
        '  %fusion.2 = f32[8]{0} fusion(), kind=kLoop, metadata={op_name='
        '"jit(step)/jit(main)/transpose(jvp(latentattention.l0_attn))/'
        'latent/dot"}',
        '  %fusion.3 = f32[8]{0} fusion(), kind=kLoop, metadata={op_name='
        '"jit(step)/jit(main)/loss.mtp_head/reduce"}',
        '  %while.4 = f32[8]{0} while(), metadata={op_name='
        '"jit(step)/jit(main)/moe.mtp_mlp/experts/while"}',
        '}'])
    ops = [("fusion.1", 1_000, 2_000_000), ("fusion.2", 3_100_000, 4_000_000),
           ("fusion.3", 7_200_000, 1_000_000), ("while.4", 8_300_000, 500_000)]
    trace = {"devices": {"0": {"ops": ops}}, "host": []}
    ctx = {"step_text": text,
           "trace": {"trace": trace, "t0": 0, "t1": 10_000_000}}
    from benchmarks.harness import scopes
    real = scopes.step_intervals
    scopes.step_intervals = lambda ctx: [(0, 10_000_000)]
    try:
        times = vertex_scopes.vertex_times(ctx)
        assert times == {
            frozenset({"latentattention.mtp_attn"}): 2.0,
            frozenset({"latentattention.l0_attn"}): 4.0,
            frozenset({"loss.mtp_head"}): 1.0}
        assert loader.metric_reader("train_mtp_device_ms")(ctx) == 3.0
    finally:
        scopes.step_intervals = real


def test_rehearsal_runs_the_cells_control_flow_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--rehearse", "tiny-joyai:train-mtp8k", "--seed", str(2**31 + 77),
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"images_per_s", "setup_s"}
    assert line["attempted"] % 8 == 0               # whole rings of 8
    read = line["read"]
    for i in (1, 2, 3):                 # both losses apart, every step
        assert read[f"loss_main{i}_rel"] < 1e-3
        assert {**read, **{k: c["value"] for k, c in
                           line["compared"].items()}}[
                               f"loss_mtp{i}_rel"] < 1e-3
    assert read["bias_abs_max"] <= 0.003 + 1e-7
    assert read["bias_equal_share"] > 0.5
    assert read["expert_load_max_over_mean"] == pytest.approx(
        read["expert_load_max_over_mean_ref"], rel=0.2)
