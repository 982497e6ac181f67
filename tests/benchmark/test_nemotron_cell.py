"""The cell `nemotron-3-nano-30b-a3b.train-ssm16k` (PR 38) as the benchmark
declares it: its work counts against hand values (harness/work_nemotron.py),
its configuration against the catalog's row, its declaration in
BENCHMARK.json by MEMBERSHIP (never as the last entry, never by
`workloads == [cell]`: the next configuration needs no edit here), its
readers on a synthetic trace and on a program without their scopes, and the
control flow of its driver on the CPU (`--rehearse
tiny-nemotron:train-ssm16k`). Nothing of the program is imported here.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.drivers.train_vl import model_of                # noqa: E402
from benchmarks.harness import loader, work_nemotron as W       # noqa: E402

BENCH = loader.benchmark()
CELL = "nemotron-3-nano-30b-a3b.train-ssm16k"
CFG = loader.load_json("configs", "nemotron-3-nano-30b-a3b.json")
TINY = loader.load_json("configs", "tiny-nemotron.json")
TRAFFIC = loader.load_json("traffic", "train-ssm16k.json")
MODEL = model_of(CFG)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ["train_ssm_layers_device_ms", "train_ssd_device_ms",
       "train_ssd_roofline", "train_ssm_pointwise_device_ms"]
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def test_parameters_at_this_cut_and_whole():
    mamba = (2688 * 10_304 + 4096 * 2688 + 6144 * 4 + 6144 + 3 * 64 + 4096)
    assert mamba + 2688 == 38_744_896
    z = W.sizes(MODEL)
    assert W.mixer_params(z, "M") == mamba
    attn = 2 * 2688 * 4096 + 2 * 2688 * 256
    assert W.mixer_params(z, "*") == attn and attn + 2688 == 23_399_040
    expert, shared = 2 * 2688 * 1856, 2 * 2688 * 3712
    assert (expert, shared) == (9_977_856, 19_955_712)
    held = 8 * expert + shared + 2688 * 128
    assert W.mixer_params(z, "E") == held and held + 2688 == 100_125_312
    assert 128 * expert + shared + 2688 * 128 + 2688 == 1_297_468_032
    ends = 2 * 16_384 * 2688
    assert ends == 88_080_384
    trained = 4 * 38_744_896 + 23_399_040 + 4 * 100_125_312 + ends + 2688
    assert W.param_count(MODEL) == trained == 666_962_944
    # 16 bytes a parameter: master, gradient, Adam's two moments
    assert 16 * trained == 10_671_407_104                   # 66.7% of 16 GB
    # published: 23 M, 23 E, 6 *, the whole vocabulary: the catalog's 31.6B
    assert W.param_count(MODEL, CFG["published"]) == 31_577_937_344 == \
        23 * 38_744_896 + 6 * 23_399_040 + 23 * 1_297_468_032 \
        + 704_643_072 + 2688
    # 16 experts a layer would not fit beside a step's activations
    assert 16 * (trained + 4 * 8 * expert) > 15.7e9


def test_the_reference_holds_the_same_parameters():
    from benchmarks.references import nemotron_h
    shapes = nemotron_h.param_shapes(MODEL)
    total = 0
    for leaves in shapes.values():
        for shape in leaves.values():
            n = 1
            for d in shape:
                n *= d
            total += n
    assert total == W.param_count(MODEL)
    assert shapes["embed"]["W"] == (16_384, 2688)
    assert shapes["head"]["W"] == (2688, 16_384)
    assert shapes["l0_mixer"]["W_in"] == (2688, 4096 + 6144 + 64)
    assert shapes["l0_mixer"]["w_c"] == (6144, 4)
    assert shapes["l7_mixer"]["W_out"] == (4096, 2688)
    assert shapes["l1_mixer"]["Wr"] == (2688, 128)  # the router's own width
    assert shapes["l8_mixer"]["Wu"] == (8, 2688, 1856)
    assert shapes["l3_mixer"]["Sd"] == (3712, 2688)
    assert "Wg" not in shapes["l1_mixer"] and "Sg" not in shapes["l1_mixer"]
    assert shapes["l5_mixer"] == {
        "Wq": (2688, 4096), "Wk": (2688, 256), "Wv": (2688, 256),
        "Wo": (4096, 2688)}                         # no Wgate
    z = nemotron_h.sizes(MODEL)
    assert nemotron_h.sparse_names(z) == ["l1_mixer", "l3_mixer", "l6_mixer",
                                          "l8_mixer"]
    assert [k for _, k in nemotron_h.layer_names(z)] == list("MEMEM*EME")


def test_pairs_flops_and_bytes_a_row():
    t = TRAFFIC["seq_len"]
    assert W.causal_pairs(t) == t * (t + 1) // 2 == 134_225_920
    assert W.held_pairs(MODEL, t) == t * 6 * 8 // 128 == 6144  # 768 an expert
    # a causal pair costs 2 x 128 + 2 x 128 forward a head
    assert W.attention_train_flops(MODEL, t) == \
        3 * 32 * 134_225_920 * 512 == 6_597_472_419_840
    assert W.experts_train_flops(MODEL, t) == \
        3 * 2 * 2 * 2688 * (6144 * 1856 + t * 3712) == 2_329_549_996_032
    # the scan: 128 chunks; inside one the 8,256 pairs j <= i at 2 x 128 a
    # group (C.B) and 2 x 64 a head (the weighted sum), then the chunk's
    # state and the entering state's part at 2 x 128 x 64 x 128 a head each
    inside = 8256 * 2 * (8 * 128 + 64 * 64)
    states = 2 * 2 * 128 * 64 * 64 * 128
    assert W.ssd_train_flops(MODEL, t) == 3 * 128 * (inside + states) \
        == 135_543_128_064
    inputs = t * ((4096 + 2048) * 2 + 64 * 4)
    y, chunk_states = t * 4096 * 2, 128 * 64 * 64 * 128 * 4
    assert W.ssd_train_bytes(MODEL, t) == 3 * inputs + 2 * y \
        + 5 * chunk_states == 2_227_175_424
    assert W.ssd_train_work(MODEL, t) == (4 * 135_543_128_064,
                                          4 * 2_227_175_424)
    # bytes bound the scan: 2.72 ms a layer at 819 GB/s against 0.69 at
    # 197 TFLOP/s
    assert 2_227_175_424 / 819e9 > 3 * 135_543_128_064 / 197e12
    assert W.pointwise_train_bytes(MODEL, t) == t * 2 * (5 * 6144 + 8 * 4096)
    row = W.train_flops_per_row(MODEL, t, t - 1)
    assert row == 38_442_444_521_472                # 38.4 TFLOP a step of 1
    share = lambda flops: round(flops / row, 3)
    proj = 4 * 3 * 2 * (2688 * 10_304 + 4096 * 2688) * t
    assert share(proj) == 0.396
    assert share(4 * 135_543_128_064) == 0.014
    assert share(4 * 2_329_549_996_032) == 0.242
    assert share(6_597_472_419_840) == 0.172
    assert share(3 * 2 * 2688 * 16_384 * (t - 1)) == 0.113


def test_work_counts_at_the_tiny_size_by_hand():
    m = model_of(TINY)
    t, D = 64, 64
    z = W.sizes(m)
    inner, conv = 8 * 8, 8 * 8 + 2 * 2 * 16
    mamba = D * (inner + conv + 8) + inner * D + conv * 4 + conv + 3 * 8 \
        + inner
    assert W.mixer_params(z, "M") == mamba == 17_624
    attn = 2 * D * 8 * 16 + 2 * D * 2 * 16
    expert_layer = D * 8 + 2 * D * (4 * 32 + 64)
    assert W.param_count(m) == 4 * mamba + attn + 4 * expert_layer \
        + 9 * D + 2 * 128 * D + D
    assert (W.count(m, "M"), W.count(m, "E"), W.count(m, "*")) == (4, 4, 1)
    pairs = 8 * 9 // 2
    ssd = 3 * (t // 8) * (pairs * 2 * (2 * 16 + 8 * 8)
                          + 2 * 2 * 8 * 8 * 8 * 16)
    assert W.ssd_train_flops(m, t) == ssd
    assert W.ssd_train_bytes(m, t) == \
        3 * t * ((64 + 64) * 2 + 8 * 4) + 2 * t * 64 * 2 \
        + 5 * (t // 8) * 8 * 8 * 16 * 4
    assert W.held_pairs(m, t) == t * 2 * 4 // 8
    proj = D * (2 * inner + 2 * 2 * 16 + 8) + inner * D
    want = (3 * 2 * D * 128 * (t - 1)
            + 4 * (3 * 2 * proj * t + ssd)
            + 4 * (3 * 2 * D * 8 * t
                   + 3 * 2 * 2 * D * (t * 2 * 4 // 8 * 32 + t * 64))
            + 3 * 2 * attn * t + 3 * 4 * 8 * 16 * (t * (t + 1) // 2))
    assert W.train_flops_per_row(m, t, t - 1) == want


def test_configuration_is_the_published_one_but_for_its_cuts():
    assert CFG["reduced"] == ["num_hidden_layers", "hybrid_override_pattern",
                              "n_routed_experts", "vocab_size"]
    assert (CFG["num_hidden_layers"], CFG["hybrid_override_pattern"],
            CFG["n_routed_experts"], CFG["vocab_size"]) == \
        (9, "MEMEM*EME", 8, 16_384)
    assert CFG["published"]["hybrid_override_pattern"] == PATTERN
    assert PATTERN[:9] == CFG["hybrid_override_pattern"]
    assert CFG["vocab_size"] * 8 == CFG["published"]["vocab_size"]
    # the published widths, unchanged
    assert (CFG["hidden_size"], CFG["mamba_num_heads"], CFG["mamba_head_dim"],
            CFG["ssm_state_size"], CFG["n_groups"], CFG["conv_kernel"],
            CFG["chunk_size"], CFG["num_attention_heads"],
            CFG["num_key_value_heads"], CFG["head_dim"],
            CFG["moe_intermediate_size"], CFG["num_experts_per_tok"],
            CFG["moe_shared_expert_intermediate_size"],
            CFG["routed_scaling_factor"], CFG["mlp_hidden_act"]) == \
        (2688, 64, 64, 128, 8, 4, 128, 32, 2, 128, 1856, 6, 3712, 2.5,
         "relu2")
    dep = CFG["deployment"]
    assert (dep["chips_per_layer"], dep["router_width"], dep["first_held"],
            dep["layers"]) == (16, 128, 0, list(range(9)))
    assert dep["router_width"] == CFG["published"]["n_routed_experts"]
    args = CFG["program"]["args"]
    for key in ("hidden_size", "mamba_num_heads", "mamba_head_dim",
                "ssm_state_size", "n_groups", "conv_kernel", "chunk_size",
                "time_step_min", "time_step_max", "time_step_floor",
                "num_attention_heads", "num_key_value_heads", "head_dim",
                "num_experts_per_tok", "moe_intermediate_size",
                "moe_shared_expert_intermediate_size", "mlp_hidden_act",
                "routed_scaling_factor", "norm_topk_prob",
                "layer_norm_epsilon", "bias_update_rate"):
        assert args[key] == CFG[key], key
    assert (args["layers"], args["experts_held"], args["first_held"],
            args["vocab_rows"], args["n_routed_experts"], args["vocab_size"],
            args["hybrid_override_pattern"]) == \
        (list(range(9)), 8, 0, 16_384, 128, 131_072, PATTERN)
    assert CFG["bias_update_rate"] == 0.001
    for key in ("positions", "bias_update_rate", "router", "experts",
                "time_steps", "mamba_layout", "norms", "optimizer",
                "weights", "memory"):
        assert CFG["assumed"][key]
    assert "no positional encoding" in CFG["assumed"]["positions"]
    assert "norm_before_gate false" in CFG["assumed"]["mamba_layout"]
    # residual projections at 0.02 / sqrt(2 x 52)
    assert CFG["trainer"]["seeded_std"]["residual_out"] == pytest.approx(
        0.02 / (2 * 52) ** 0.5, rel=1e-4)
    # each followed step's loss, the leaves' gaps, and the Mamba layers' own
    # leaves as a group; the medians under the fp8 control's readings, the
    # worst leaf under a planted fault's (PERF.md section 2), none at what
    # only an unchanged state fails
    assert {"loss1_rel", "loss2_rel", "loss3_rel", "grad_norm_gap",
            "grad_norm_gap_ssm", "grad_norm_gap_p50", "grad_norm_gap_w50",
            "change_norm_gap", "change_norm_gap_w50"} <= set(CFG["limits"])
    assert max(CFG["limits"].values()) == CFG["limits"]["change_norm_gap"] \
        <= 0.01
    assert CFG["limits"]["grad_norm_gap_ssm"] < CFG["limits"]["grad_norm_gap"]
    assert max(v for k, v in CFG["limits"].items()
               if k.endswith(("_p50", "_w50", "_rel"))) <= 0.001
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    entry = next(c for c in BENCH["configs"] if c["name"] == CFG["name"])
    assert entry["source"] == CFG["source"] == row["source_url"]
    assert entry["reduced"] == CFG["reduced"]
    assert entry["file"] == "benchmarks/configs/nemotron-3-nano-30b-a3b.json"
    for key, value in row["config"].items():
        if key not in CFG["reduced"]:
            assert CFG[key] == value, key
        elif key != "num_hidden_layers":
            assert args[key] == value, key      # the program is told both
    assert len(row["config"]["hybrid_override_pattern"]) == \
        row["config"]["num_hidden_layers"] == 52


def test_the_cells_declaration_by_membership():
    cell = loader.cell(CELL)
    (w,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert (w["config"], w["traffic"], w["chips"]) == \
        ("nemotron-3-nano-30b-a3b", "train-ssm16k", 1)
    assert "more than their share" in w["why"] and len(w["why"]) <= 200
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
    assert [by_name[n]["unit"] for n in NEW] == ["ms", "ms", "%", "ms"]
    assert [by_name[n]["better"] for n in NEW] == ["lower", "lower",
                                                   "higher", "lower"]
    # it joins none of the other decoders' own metrics
    for name in ("train_moe_device_ms", "train_moe_roofline",
                 "moe_load_max_over_mean", "train_expert_layers_device_ms",
                 "train_expert_layers_roofline", "expert_load_max_over_mean",
                 "train_full_attn_device_ms", "train_full_attn_roofline",
                 "train_latent_attn_device_ms"):
        assert CELL not in by_name[name]["workloads"]
    # what was there stands before what this cell added
    cells = [w["name"] for w in BENCH["workloads"]]
    assert cells.index(CELL) > cells.index("joyai-llm-flash.train-mtp8k")
    names = [m["name"] for m in BENCH["per_layer"]]
    assert all(names.index(n) > names.index("train_step_compiles")
               for n in NEW)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    assert TRAFFIC == {**TRAFFIC, "kind": "train_ring", "ring": 8, "rows": 1,
                       "seq_len": 16_384, "trace_seconds": 10}


def test_readers_find_nothing_on_a_program_without_their_scopes():
    """A program without the scopes or the text (the parent's): every new
    reader returns None and none raises."""
    ctx = {"trace": None, "gauges": None, "model": MODEL, "rows": 1,
           "seq_len": 16_384, "peaks": PEAKS}
    for name in NEW:
        assert loader.metric_reader(name)(dict(ctx)) is None, name
    # Laguna's step has `attention` and `moe` and no state-space scope
    ctx["inner_times"] = {frozenset({"attention", "attend_full"}): 200.0,
                          frozenset({"moe", "experts"}): 60.0}
    for name in NEW:
        assert loader.metric_reader(name)(dict(ctx)) is None, name


def test_readers_divide_the_work_by_the_scopes_times():
    """Hand times by scope: the roofline is the work's least time at the
    peaks over the scan's time, in percent, never clipped."""
    ctx = {"model": MODEL, "rows": 1, "seq_len": 16_384, "peaks": PEAKS,
           "inner_times": {
               frozenset({"ssd"}): 50.0,
               frozenset({"ssd", "checkpoint"}): 10.0,
               frozenset({"ssm_proj"}): 120.0,
               frozenset({"ssm_conv"}): 14.0,
               frozenset({"ssm_norm"}): 11.0,
               frozenset({"attention", "attend_full"}): 100.0,
               frozenset({"moe", "experts"}): 40.0}}
    read = lambda name: loader.metric_reader(name)(dict(ctx))
    assert read("train_ssd_device_ms") == 60.0
    assert read("train_ssm_pointwise_device_ms") == 25.0
    assert read("train_ssm_layers_device_ms") == 205.0
    # 4 x 2.227 GB at 819 GB/s is 10.88 ms (memory-bound; its 0.54 TFLOP
    # are 2.75 ms): 18.1% of 60 ms
    assert read("train_ssd_roofline") == pytest.approx(
        100 * (4 * 2_227_175_424 / 819e9) / 0.06)
    assert 18 < read("train_ssd_roofline") < 18.2
    # never clipped: a scan faster than its bytes allow reads over 100
    ctx["inner_times"] = {frozenset({"ssd"}): 5.0}
    assert read("train_ssd_roofline") > 200


def test_the_inner_join_reads_the_scopes_from_a_synthetic_trace():
    """`harness/inner_scopes.py` on a small step: the kind's own name has a
    digit and is not read as a scope, its four inner scopes are; a loop's
    own event is left out."""
    from benchmarks.harness import inner_scopes, scopes
    op = lambda i, kind, path: (
        f'  %fusion.{i} = f32[8]{{0}} {kind}(), metadata={{op_name='
        f'"jit(step)/jit(main)/{path}"}}')
    text = "\n".join([
        "HloModule jit_step", "ENTRY main {",
        op(1, "fusion", "jvp(mamba2.l0_mixer)/ssm_proj/dot_general"),
        op(2, "fusion", "transpose(jvp(mamba2.l0_mixer))/ssd/mul"),
        op(3, "fusion", "checkpoint/rematted_computation/ssm_conv/mul"),
        op(4, "fusion", "jvp(mamba2.l2_mixer)/ssm_norm/rsqrt"),
        op(5, "fusion", "jvp(moe.l1_mixer)/experts/ragged_dot"),
        '  %while.6 = f32[8]{0} while(), metadata={op_name='
        '"jit(step)/jit(main)/jvp(mamba2.l0_mixer)/ssd/while"}',
        "}"])
    ops = [("fusion.1", 1_000, 3_000_000), ("fusion.2", 3_100_000, 2_000_000),
           ("fusion.3", 5_200_000, 1_000_000),
           ("fusion.4", 6_300_000, 500_000),
           ("fusion.5", 7_000_000, 1_500_000),
           ("while.6", 8_600_000, 900_000)]
    trace = {"devices": {"0": {"ops": ops}}, "host": []}
    ctx = {"step_text": text, "model": MODEL, "rows": 1, "seq_len": 16_384,
           "peaks": PEAKS,
           "trace": {"trace": trace, "t0": 0, "t1": 10_000_000}}
    real = scopes.step_intervals
    scopes.step_intervals = lambda ctx: [(0, 10_000_000)]
    try:
        read = lambda name: loader.metric_reader(name)(ctx)
        assert read("train_ssd_device_ms") == 2.0
        assert read("train_ssm_pointwise_device_ms") == 1.5
        assert read("train_ssm_layers_device_ms") == 6.5
        assert inner_scopes.inner_ms(ctx, "mamba") is None
        assert read("train_ssd_roofline") == pytest.approx(
            100 * (4 * 2_227_175_424 / 819e9) / 0.002)
    finally:
        scopes.step_intervals = real


def test_rehearsal_runs_the_cells_control_flow_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--rehearse", "tiny-nemotron:train-ssm16k", "--seed",
         str(2**31 + 77), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"images_per_s", "setup_s"}
    assert line["attempted"] % 8 == 0               # whole rings of 8
    read = {**line["read"],
            **{k: c["value"] for k, c in line["compared"].items()}}
    for i in (1, 2, 3):
        assert read[f"loss{i}_rel"] < 1e-3
    assert read["grad_norm_gap_ssm"] < 0.05
    assert read["bias_abs_max"] <= 0.003 + 1e-7
    assert read["bias_equal_share"] > 0.5
    assert read["expert_load_max_over_mean"] == pytest.approx(
        read["expert_load_max_over_mean_ref"], rel=0.2)
    assert read["ssm_chunks"] == 256 // 8
    assert read["attend_backward_passes"] == 1
    assert read["attend_grid_steps_per_tile"] == 1
    assert read["ssm_decay_min"] == pytest.approx(read["ssm_decay_min_ref"],
                                                  rel=0.05)
    assert read["ssm_dt_mean_rel"] < 0.02 and read["ssm_state_rms_rel"] < 0.02
