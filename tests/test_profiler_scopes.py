"""Layer scopes in the compiled step, and their operator-side reading
(ISSUE 26): every layer of both containers traces under
`jax.named_scope("<kind>.<name>")`, the loss under `loss.<output>`, the
optimizer under `update`; `optimize/profiler.py` joins a device trace to
the compiled text by instruction name (`op_scopes`, `summarize_layers`) and
`summarize_trace` reads the operation line alone.
"""
import os
import re

import jax
import numpy as np
import pytest

from deeplearning4j_tpu import (ComputationGraph, InputType,
                                MultiLayerNetwork, NeuralNetConfiguration)
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.conf.graph_vertices import ElementWiseVertex
from deeplearning4j_tpu.nn.conf.layers import (ActivationLayer,
                                               BatchNormalization,
                                               DenseLayer, OutputLayer)
from deeplearning4j_tpu.optimize import profiler as P
from deeplearning4j_tpu.parallel.parallel_wrapper import ParallelWrapper

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(ROOT, "benchmarks", "tests", "data")
# the fixture's device lines, in ns (read by hand with ProfileData, PR 26)
OPS_LINE_NS, MODULES_LINE_NS = 80010, 80050

# the head's own forward is dead code in the step (the loss recomputes it
# on the pre-head activation): `out` lives under `loss.out`
VERTICES = {"d1": "dense", "bn": "batchnorm", "act": "activation",
            "d2": "dense", "add": "elementwise"}


def tiny_graph():
    gb = (NeuralNetConfiguration.Builder().seed(5).updater("nesterovs")
          .momentum(0.9).learning_rate(0.05).graph_builder()
          .add_inputs("in"))
    gb.add_layer("d1", DenseLayer(n_out=8, activation="identity"), "in")
    gb.add_layer("bn", BatchNormalization(), "d1")
    gb.add_layer("act", ActivationLayer(activation="relu"), "bn")
    gb.add_layer("d2", DenseLayer(n_out=8, activation="tanh"), "act")
    gb.add_vertex("add", ElementWiseVertex(op="add"), "d2", "act")
    gb.add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                    loss_function="mcxent"), "add")
    conf = (gb.set_outputs("out")
            .set_input_types(InputType.feed_forward(6)).build())
    return ComputationGraph(conf).init()


def batch(n=8, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, 6)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)]
    return DataSet(x, y)


def op_names(lowered):
    """Every op_name path of a lowered step's locations."""
    return set(re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True)))


# ------------------------------------------------------ scopes in the step
@pytest.fixture(scope="module")
def graph_paths():
    return op_names(tiny_graph().lower_step(batch()))


@pytest.mark.parametrize("name,kind", sorted(VERTICES.items()))
def test_the_lowered_step_names_every_vertex_forward(graph_paths, name,
                                                     kind):
    assert any(f"/jvp({kind}.{name})/" in p for p in graph_paths), name


@pytest.mark.parametrize("name", ["d1", "bn", "act", "d2"])
def test_the_backward_of_a_vertex_is_its_transposed_scope(graph_paths,
                                                          name):
    scope = f"transpose(jvp({VERTICES[name]}.{name}))"
    assert any(f"/{scope}/" in p for p in graph_paths), name


def test_loss_and_update_have_scopes_of_their_own(graph_paths):
    assert any("/jvp(loss.out)/" in p for p in graph_paths)
    assert any("/update/" in p for p in graph_paths)
    # the update is outside autodiff: never under jvp
    assert not any("jvp(update)" in p for p in graph_paths)


def test_health_is_scoped_when_the_watchdog_is_armed():
    net = tiny_graph().training_health(True)
    assert any("/health/" in p for p in op_names(net.lower_step(batch())))


def test_multilayer_layers_are_scoped_by_kind_and_index():
    conf = (NeuralNetConfiguration.Builder().seed(7).updater("adam")
            .learning_rate(0.01).list()
            .layer(0, DenseLayer(n_out=16, activation="relu"))
            .layer(1, OutputLayer(n_out=3, activation="softmax",
                                  loss_function="mcxent"))
            .set_input_type(InputType.feed_forward(6)).build())
    net = MultiLayerNetwork(conf).init()
    ds = batch()
    net.fit(ds)                         # builds the jitted step
    paths = op_names(net._jit_step.lower(
        net._params, net._updater_state, net._model_state, net._loop,
        ds.features, ds.labels, None, None))
    assert any("/jvp(dense.0)/" in p for p in paths)
    assert any("/transpose(jvp(dense.0))/" in p for p in paths)
    assert any("/jvp(loss.1)/" in p for p in paths)
    assert any("/update/" in p for p in paths)


def test_lower_step_runs_nothing_and_consumes_nothing():
    a, b = tiny_graph(), tiny_graph()
    a.lower_step(batch())
    assert a.conf.iteration_count == 0 and a._loop is None
    for net in (a, b):
        net.fit(batch())
    for n in a._params:
        for k in a._params[n]:
            np.testing.assert_array_equal(np.asarray(a._params[n][k]),
                                          np.asarray(b._params[n][k]))


def test_the_sharded_step_carries_the_same_scopes():
    pw = ParallelWrapper.Builder(tiny_graph()).workers(2) \
        .averaging_frequency(1).build()
    table = P.op_scopes(pw.lower_step(batch()).compile().as_text())
    kinds = {s[0] for s in map(P.scope_of, table.values()) if s}
    assert {"dense", "batchnorm", "update"} <= kinds
    # GSPMD's all-reduces keep the scope of what they reduce
    reduces = {n: P.scope_of(p) for n, p in table.items()
               if n.startswith("all-reduce")}
    assert reduces and all(reduces.values()), reduces


# ---------------------------------------------------- op_scopes, scope_of
def test_op_scopes_reads_instruction_names_from_compiled_text():
    text = tiny_graph().lower_step(batch()).compile().as_text()
    table = P.op_scopes(text)
    assert table
    for name, path in table.items():
        assert re.fullmatch(r"[\w.\-]+", name), name
        assert f"{name} = " in text
    found = [s for s in map(P.scope_of, table.values()) if s]
    assert {("dense", "d1", "forward"), ("batchnorm", "bn", "forward"),
            ("update", None, "")} <= set(found)
    assert any(s[2] == "backward" for s in found)


def test_op_scopes_on_the_chips_form_of_an_instruction():
    text = (
        'ENTRY %main {\n'
        '  %p = f32[8]{0} parameter(0), metadata={op_name="x"}\n'
        '  %fusion.54 = (f32[256]{0:T(256)}, bf16[8,8]{1,0:T(8,128)(2,1)}) '
        'fusion(%p), kind=kOutput, calls=%fused.82, metadata={op_name='
        '"jit(step)/jit(main)/transpose(jvp(batchnorm.s2b0_a_bn))/'
        'reduce_sum" source_file="a.py" source_line=3}\n'
        '  ROOT %all-reduce.7 = f32[8]{0} all-reduce(%p), replica_groups={}, '
        'to_apply=%add, metadata={op_name="jit(step)/update/sub"}\n'
        '  %copy.1 = f32[8]{0} copy(%p)\n}\n')
    assert P.op_scopes(text) == {
        "p": "x",
        "fusion.54": "jit(step)/jit(main)/transpose(jvp(batchnorm."
                     "s2b0_a_bn))/reduce_sum",
        "all-reduce.7": "jit(step)/update/sub"}
    assert P.instruction_name(
        "%fusion.54 = (f32[256]{0:T(256)}, bf16[8,8]{1,0}) fusion(f32[8]{0} "
        "%p), kind=kOutput, calls=%fused.82") == "fusion.54"
    assert P.instruction_name("fusion.3") == "fusion.3"


def test_fusion_contents_says_what_else_a_fusion_holds():
    text = (
        '%fused.82 (p.1: f32[8]) -> f32[8] {\n'
        '  %p.1 = f32[8]{0} parameter(0)\n'
        '  %conv.3 = f32[8]{0} convolution(%p.1), metadata={op_name='
        '"jit(step)/jvp(convolution.a)/conv_general_dilated"}\n'
        '  ROOT %reduce.4 = f32[8]{0} reduce(%conv.3), metadata={op_name='
        '"jit(step)/jvp(batchnorm.a_bn)/reduce_sum"}\n'
        '}\n\n'
        'ENTRY %main (p: f32[8]) -> f32[8] {\n'
        '  %p = f32[8]{0} parameter(0)\n'
        '  ROOT %fusion.54 = f32[8]{0} fusion(%p), kind=kOutput, '
        'calls=%fused.82, metadata={op_name='
        '"jit(step)/jvp(convolution.a)/conv_general_dilated"}\n'
        '}\n')
    assert P.fusion_contents(text) == {"fusion.54": [
        "jit(step)/jvp(convolution.a)/conv_general_dilated",
        "jit(step)/jvp(batchnorm.a_bn)/reduce_sum"]}
    # on a real step: every fusion of the compiled text resolves
    real = tiny_graph().lower_step(batch()).compile().as_text()
    contents = P.fusion_contents(real)
    assert contents and any(contents.values())
    assert set(contents) <= {m.group(1) for m in re.finditer(
        r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ", real, re.M)}


@pytest.mark.parametrize("path,want", [
    ("jit(step)/jvp(batchnorm.stem_bn)/mul",
     ("batchnorm", "stem_bn", "forward")),
    ("jit(step)/jit(main)/transpose(jvp(convolution.s2b0_a_conv))/"
     "conv_general_dilated", ("convolution", "s2b0_a_conv", "backward")),
    ("jit(step)/jvp(activation.stem_act)/jit(relu)/max",
     ("activation", "stem_act", "forward")),
    ("jit(step)/update/sub", ("update", None, "")),
    ("jit(step)/health/is_finite", ("health", None, "")),
    ("jit(fwd)/dense.0/dot_general", ("dense", "0", "forward")),
    # what the compiler adds itself is named like an instruction
    ("broadcast.202", None),
    ("jit(step)/ComputationGraph._make_step.<locals>.step/add", None),
    ("jit(step)/jit(_threefry_split)/while/body/add", None),
    # an inner jit's operations carry the function they were first traced in
    ("jit(step)/jit(_threefry_split)/image_ring.<locals>.make/while/add",
     None),
    ("jit(raw)/make_raw_step.<locals>.step/add", None),
    ("", None)])
def test_scope_of(path, want):
    assert P.scope_of(path) == want


# ------------------------------ the trace reading, on the chip's fixture
def test_summarize_trace_adds_up_to_the_operation_line_alone():
    pytest.importorskip("jax.profiler")
    rows = P.summarize_trace(FIXTURE)
    total_ns = sum(r["total_ms"] for r in rows) * 1e6
    # to the rows' rounding (a microsecond each)
    assert total_ns == pytest.approx(OPS_LINE_NS, abs=1000 * len(rows))
    assert total_ns < 0.6 * (OPS_LINE_NS + MODULES_LINE_NS)
    assert sum(r["count"] for r in rows) == 16
    assert [r["name"] for r in rows][:2] == ["fusion",
                                             "multiply_reduce_fusion"]
    assert sum(r["pct"] for r in rows) == pytest.approx(100, abs=0.1)
    unmerged = P.summarize_trace(FIXTURE, merge_fusion_names=False)
    assert sum(r["count"] for r in unmerged) == 16


def test_summarize_layers_splits_the_same_line_by_scope():
    table = {"fusion": "jit(small_step)/jvp(batchnorm.a)/mul",
             "multiply_reduce_fusion":
                 "jit(small_step)/transpose(jvp(convolution.b))/dot"}
    rows = {r["name"]: r for r in P.summarize_layers(FIXTURE, table)}
    assert set(rows) == {"batchnorm forward", "convolution backward",
                         P.UNSCOPED}
    ops = {r["name"]: r for r in P.summarize_trace(FIXTURE)}
    assert rows["batchnorm forward"]["total_ms"] == \
        ops["fusion"]["total_ms"]
    assert rows["convolution backward"]["count"] == 4
    assert rows[P.UNSCOPED]["count"] == 8        # the copy's two halves
    assert sum(r["total_ms"] for r in rows.values()) == pytest.approx(
        sum(r["total_ms"] for r in ops.values()), abs=0.004)


def test_summarize_trace_without_a_trace_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        P.summarize_trace(str(tmp_path))


def test_obs_report_prints_the_by_layer_table(tmp_path):
    import importlib
    import sys
    tools = os.path.join(ROOT, "tools")
    if tools not in sys.path:
        sys.path.insert(0, tools)
    mod = importlib.import_module("obs_report")
    hlo = ('  %fusion = bf16[] fusion(%p), kind=kOutput, metadata={op_name='
           '"jit(small_step)/jvp(batchnorm.a)/mul"}\n')
    report = mod.build_report(profile_logdir=FIXTURE, hlo_text=hlo)
    assert report["device_ops"] and report["device_layers"]
    assert report["device_layers"][0]["name"] == "batchnorm forward"
    text = mod.format_report(report)
    assert "device time by layer" in text and "batchnorm forward" in text
    # without the text the report is what it was
    assert mod.build_report(profile_logdir=FIXTURE)["device_layers"] is None


def test_a_fit_under_the_profiler_reads_back_by_layer(tmp_path):
    """End to end on the CPU backend: the trace has no device plane here, so
    the tables are empty, but the capture, the lowering and the join run."""
    net = tiny_graph()
    ds = batch()
    net.fit(ds)
    with P.trace(str(tmp_path)):
        net.fit(ds)
        jax.block_until_ready(net._params)
    table = P.op_scopes(net.lower_step(ds).compile().as_text())
    assert P.summarize_layers(str(tmp_path), table) == []
    assert P.summarize_trace(str(tmp_path)) == []
