"""What a start-up is made of (ISSUE 36): jax's compile events into the
registry and the tracer (obs/compiles.py), `train.compile` from the dispatch
sites, `train.init`, and the five set-up metrics that read them.

  (a) the sink: a `jax.jit` call is one program and a second call none; a
      persistent cache's miss and hit; a `compile.backend` span by the
      function's name, ring only while tracing is on.
  (b) the trainer, both containers: one `train.compile` a first `fit`, none
      for the same shapes, one more for another batch size; a fused group
      and both of `ParallelWrapper`'s programs one each; `train.init` once
      a net.
  (c) obs/compiles.py imports nothing but the stdlib and obs/ (the layering
      rule of tests/test_obs.py covers it: held here by name); the cost of a
      dispatch that does not compile is pinned in tests/test_obs.py.
  (d) the five readers and their entries in BENCHMARK.json.
"""
import ast
import contextlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import (ComputationGraph, InputType,
                                MultiLayerNetwork, NeuralNetConfiguration,
                                obs)
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.obs import MetricsRegistry, Tracer, registry
from deeplearning4j_tpu.parallel.parallel_wrapper import ParallelWrapper

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.harness import loader                            # noqa: E402

CELLS = ["resnet50.train-staged", "joyai-llm-flash.train-mtp8k"]
READERS = {"setup_init_s": "train.init_s",
           "setup_step_build_s": "train.compile_s",
           "setup_step_backend_s": "train.compile_backend_s",
           "setup_step_cache_misses": "train.compile_cache_misses",
           "train_step_compiles": "train.compiles"}


def value(name):
    c = obs.default_registry().get(name)
    return 0 if c is None else c.value


@contextlib.contextmanager
def tracing(enabled=True):
    """Swap the process-wide tracer, as tests/test_obs.py does."""
    old, obs.TRACER = obs.TRACER, Tracer(enabled=enabled)
    try:
        yield obs.TRACER
    finally:
        obs.TRACER = old


@pytest.fixture
def fresh_registry(monkeypatch):
    monkeypatch.setattr(registry, "_default", MetricsRegistry())
    return registry.default_registry()


def mln(seed=7):
    conf = (NeuralNetConfiguration.Builder().seed(seed).updater("adam")
            .learning_rate(0.01).list()
            .layer(0, DenseLayer(n_out=16, activation="relu"))
            .layer(1, OutputLayer(n_out=4, activation="softmax",
                                  loss_function="mcxent"))
            .set_input_type(InputType.feed_forward(6)).build())
    return MultiLayerNetwork(conf)


def graph(seed=3):
    gb = (NeuralNetConfiguration.Builder().seed(seed).updater("sgd")
          .learning_rate(0.05).graph_builder().add_inputs("in"))
    gb.add_layer("d", DenseLayer(n_out=8, activation="relu"), "in")
    gb.add_layer("out", OutputLayer(n_out=4, activation="softmax",
                                    loss_function="mcxent"), "d")
    conf = (gb.set_outputs("out")
            .set_input_types(InputType.feed_forward(6)).build())
    return ComputationGraph(conf)


def batches(n, rows=8, seed=4):
    rng = np.random.default_rng(seed)
    return [DataSet(rng.standard_normal((rows, 6)).astype(np.float32),
                    np.eye(4, dtype=np.float32)[rng.integers(0, 4, rows)])
            for _ in range(n)]


# ---------------------------------------------------------------------------
# (a) the sink
# ---------------------------------------------------------------------------
def test_a_jit_call_is_one_program_and_a_second_call_none():
    x = jnp.ones(5) + 0           # the input's own programs come first
    f = jax.jit(lambda a: a * 3 + 1)
    before = (value("compile.programs"), value("compile.trace_s"),
              value("compile.lower_s"), value("compile.backend_s"))
    f(x)
    after = (value("compile.programs"), value("compile.trace_s"),
             value("compile.lower_s"), value("compile.backend_s"))
    assert after[0] == before[0] + 1
    assert all(a > b for a, b in zip(after[1:], before[1:]))
    f(x)
    assert value("compile.programs") == after[0]
    assert value("compile.trace_s") == after[1]


@pytest.fixture
def cache_dir(tmp_path):
    """A persistent cache of this test's own that stores every program."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    old = [getattr(jax.config, k) for k in keys]
    for k, v in zip(keys, (str(tmp_path), 0.0, 0)):
        jax.config.update(k, v)
    cc.reset_cache()
    yield tmp_path
    for k, v in zip(keys, old):
        jax.config.update(k, v)
    cc.reset_cache()


def test_a_persistent_cache_counts_a_miss_then_a_hit(cache_dir):
    x = jnp.arange(7.0) + 0
    f = jax.jit(lambda a: jnp.sin(a) * 5 - 2)
    names = ("compile.cache_misses", "compile.cache_hits",
             "compile.cache_retrieval_s")
    n0 = [value(n) for n in names]
    f(x)
    n1 = [value(n) for n in names]
    assert (n1[0], n1[1]) == (n0[0] + 1, n0[1])
    jax.clear_caches()
    f(x)
    n2 = [value(n) for n in names]
    assert (n2[0], n2[1]) == (n1[0], n1[1] + 1)
    assert n2[2] > n1[2]


def test_a_backend_compile_is_a_span_by_name_only_while_tracing():
    x = jnp.ones(3) + 0

    def pr36_unseen(a):
        return a * 7 - 1

    def pr36_probe(a):
        return a * 9 - 1

    with tracing(False) as off:
        jax.jit(pr36_unseen)(x)
    assert len(off) == 0
    with tracing() as on:
        t0 = obs.trace.monotonic_ns()
        jax.jit(pr36_probe)(x)
        t1 = obs.trace.monotonic_ns()
    (s,) = on.spans("compile.backend")
    assert s.cat == "compile" and s.args["fun_name"] == "jit(pr36_probe)"
    assert s.args["seconds"] > 0
    assert abs(s.dur_ns - s.args["seconds"] * 1e9) < 2
    # moved onto the tracer's clock: inside the call, to the wall clock's ms
    assert t0 - 5e6 <= s.t0_ns and s.t0_ns + s.dur_ns <= t1 + 5e6


def test_seconds_are_exclusive_of_the_phases_inside():
    """jax's trace events nest; a second belongs to the innermost phase, so
    the three phase counters of a call add up to no more than its length."""
    inner = jax.jit(lambda a: jnp.tanh(a) * 2)
    x = jnp.ones(4) + 0
    names = ("compile.trace_s", "compile.lower_s", "compile.backend_s")
    before = [value(n) for n in names]
    t0 = obs.trace.monotonic_ns()
    jax.jit(lambda a: inner(a) + jax.nn.relu(a) + inner(a * 2))(x)
    wall = (obs.trace.monotonic_ns() - t0) / 1e9
    spent = sum(value(n) - b for n, b in zip(names, before))
    assert 0 < spent <= wall + 1e-3


def test_a_thread_that_compiles_does_not_move_anothers_mark():
    import threading
    mark = obs.compiles.mark()
    th = threading.Thread(
        target=lambda: jax.jit(lambda a: a - 11)(np.ones(3, np.float32)))
    programs = value("compile.programs")
    th.start()
    th.join()
    assert value("compile.programs") > programs     # process-wide: counted
    assert obs.compiles.dispatched(mark, print) is False


# ---------------------------------------------------------------------------
# (b) the trainer
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("build", [mln, graph], ids=["multilayer", "graph"])
def test_first_fit_is_one_compile_and_a_new_shape_one_more(build):
    with tracing() as t:
        n0, s0 = value("train.compiles"), value("train.compile_s")
        net = build().init()
        a, b, c = batches(3)
        net.fit(a)
        (s,) = t.spans("train.compile")
        assert s.cat == "train" and s.args["program"] == "step"
        assert s.args["cache"] in ("hit", "miss", "off")
        assert s.args["programs"] >= 1 and s.args["backend_s"] > 0
        phases = (s.args["trace_s"] + s.args["lower_s"]
                  + s.args["backend_s"])
        assert 0 < phases <= s.dur_ns / 1e9 + 1e-3
        assert s.args["retrieval_s"] <= s.args["backend_s"] + 1e-6
        assert value("train.compiles") == n0 + 1
        assert value("train.compile_s") == \
            pytest.approx(s0 + s.dur_ns / 1e9)
        # the span lies inside its dispatch
        (d,) = t.spans("train.dispatch")
        assert d.t0_ns <= s.t0_ns
        assert s.t0_ns + s.dur_ns <= d.t0_ns + d.dur_ns
        net.fit(b)
        net.fit(c)
        assert value("train.compiles") == n0 + 1
        assert len(t.spans("train.compile")) == 1
        assert len(t.spans("train.dispatch")) == 3
        net.fit(batches(1, rows=12)[0])
        assert value("train.compiles") == n0 + 2
        assert len(t.spans("train.compile")) == 2


def test_the_counters_move_with_tracing_off():
    n0, b0 = value("train.compiles"), value("train.compile_backend_s")
    with tracing(False) as t:
        mln(seed=11).init().fit(batches(1)[0])
    assert len(t) == 0
    assert value("train.compiles") == n0 + 1
    assert value("train.compile_backend_s") > b0


@pytest.mark.parametrize("build", [mln, graph], ids=["multilayer", "graph"])
def test_a_fused_group_is_one_compile(build):
    net = build().init().fused_steps(4)
    n0 = value("train.compiles")
    with tracing() as t:
        net.fit(ListDataSetIterator(batches(8), 8), num_epochs=1)
    assert len(t.spans("train.fused_group")) == 2
    (s,) = t.spans("train.compile")
    assert s.args["k"] == 4 and s.args["program"] == "prog"
    assert value("train.compiles") == n0 + 1


@pytest.mark.parametrize("k", [1, 2])
def test_parallel_wrapper_leaves_one_compile(k):
    pw = (ParallelWrapper.Builder(graph().init()).workers(2)
          .averaging_frequency(k).build())
    n0 = value("train.compiles")
    with tracing() as t:
        pw.fit(ListDataSetIterator(batches(4), 8))
    assert len(t.spans("parallel.dispatch")) == 4 // k
    (s,) = t.spans("train.compile")
    assert s.args["backend_s"] > 0
    assert value("train.compiles") == n0 + 1


@pytest.mark.parametrize("build", [mln, graph], ids=["multilayer", "graph"])
def test_init_is_a_span_once_a_net(build):
    s0 = value("train.init_s")
    with tracing() as t:
        net = build().init()
        (s,) = t.spans("train.init")
        assert s.cat == "train"
        assert value("train.init_s") == pytest.approx(
            s0 + s.dur_ns / 1e9, abs=1e-3)
        net.init()
        net.fit(batches(1)[0])
        assert len(t.spans("train.init")) == 1


def test_fused_program_opens_no_span_of_its_own():
    """`train.compile` has one meaning and comes from the dispatch sites."""
    with open(os.path.join(ROOT, "deeplearning4j_tpu", "nn",
                           "fused.py")) as fh:
        text = fh.read()
    assert 'span("train.compile"' not in text


# ---------------------------------------------------------------------------
# (c) obs/ stays below jax
# ---------------------------------------------------------------------------
def test_the_sink_imports_the_stdlib_and_obs_alone():
    path = os.path.join(ROOT, "deeplearning4j_tpu", "obs", "compiles.py")
    with open(path) as fh:
        tree = ast.parse(fh.read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            names.add(node.module.split(".")[0])
        elif isinstance(node, ast.ImportFrom):
            assert node.level == 1          # obs/ itself, nothing above it
    assert names <= {"__future__", "collections", "threading", "time"}
    from tools.analyze import check_layer_rules
    assert not check_layer_rules(["obs-stdlib-only", "obs-below-serving"])


# ---------------------------------------------------------------------------
# (d) the five readers
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_returns_none_without_its_counter_else_its_value(
        name, fresh_registry):
    read = loader.metric_reader(name)
    assert read({}) is None
    fresh_registry.counter("train.unrelated").inc()
    assert read({}) is None
    fresh_registry.counter(READERS[name]).inc(2.5)
    assert read({}) == 2.5


def test_the_five_entries_stand_together_and_name_two_cells():
    """In the order PR 36 appended them; by name, not as the list's last
    five: a later PR's entries stand after them (PR 38's do)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    first = [m["name"] for m in bench["per_layer"]].index(list(READERS)[0])
    last = bench["per_layer"][first:first + 5]
    assert [m["name"] for m in last] == list(READERS)
    for m in last:
        assert m["workloads"] == CELLS
        assert (m["layer"], m["moves"], m["better"]) == \
            ("trainer containers", "setup_s", "lower")
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert [m["unit"] for m in last] == ["s", "s", "s", "count", "count"]
    assert [m["source"] for m in last] == [
        "program_span", "program_span", "program_counter",
        "program_counter", "program_counter"]
    for cell in CELLS:
        assert set(READERS) <= {m["name"]
                                for m in loader.cell(cell)["per_layer"]}
    for w in bench["workloads"]:
        if w["name"] not in CELLS:
            assert not set(READERS) & {
                m["name"] for m in loader.cell(w["name"])["per_layer"]}


def test_after_a_first_fit_all_five_read_numbers(fresh_registry):
    graph(seed=5).init().fit(batches(1)[0])
    got = {name: loader.metric_reader(name)({}) for name in READERS}
    assert all(isinstance(v, (int, float)) for v in got.values()), got
    assert got["train_step_compiles"] == 1
    assert got["setup_init_s"] > 0
    assert 0 < got["setup_step_backend_s"] <= got["setup_step_build_s"]
    assert got["setup_step_cache_misses"] >= 0
