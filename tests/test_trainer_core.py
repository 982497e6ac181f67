"""The one trainer behind MultiLayerNetwork, ComputationGraph and
ParallelWrapper (nn/trainer.py): the same layers train to the same bits
through either container, every shared method IS the same function, and the
wrapper makes one call for both.
"""
import inspect
import pathlib
import re

import jax
import numpy as np
import pytest

import deeplearning4j_tpu
from deeplearning4j_tpu import (InputType, MultiLayerNetwork,
                                NeuralNetConfiguration)
from deeplearning4j_tpu.common.health import TrainingHealthPolicy
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.trainer import Trainer
from deeplearning4j_tpu.parallel import ParallelWrapper


def _builder():
    return (NeuralNetConfiguration.Builder().seed(7).learning_rate(0.05)
            .updater("adam"))


def _layers():
    return (DenseLayer(n_out=12, activation="tanh"),
            DenseLayer(n_out=8, activation="relu"),
            OutputLayer(n_out=3, activation="softmax",
                        loss_function="mcxent"))


def list_net():
    lb = _builder().list()
    for i, layer in enumerate(_layers()):
        lb = lb.layer(i, layer)
    return MultiLayerNetwork(
        lb.set_input_type(InputType.feed_forward(5)).build()).init()


def graph_net():
    gb, prev = _builder().graph_builder().add_inputs("in"), "in"
    for i, layer in enumerate(_layers()):
        gb.add_layer(f"l{i}", layer, prev)
        prev = f"l{i}"
    return ComputationGraph(
        gb.set_outputs(prev).set_input_types(InputType.feed_forward(5))
        .build()).init()


def batches(n=3, rows=8, seed=0):
    rng = np.random.default_rng(seed)
    return [DataSet(rng.normal(size=(rows, 5)).astype(np.float32),
                    np.eye(3, dtype=np.float32)[rng.integers(0, 3, rows)])
            for _ in range(n)]


ARM = {"plain": lambda net: net,
       "health": lambda net: net.training_health(),
       "fused2": lambda net: net.fused_steps(2)}


@pytest.mark.parametrize("mode", list(ARM))
def test_a_linear_graph_and_the_list_of_its_layers_train_to_the_same_bits(
        mode):
    """Three `fit` steps (a fused pair and its ragged tail under
    `fused_steps(2)`) on copied weights, no dropout: parameters, updater
    state and score are bit-equal."""
    one, two = ARM[mode](list_net()), ARM[mode](graph_net())
    two.set_params(one.params())
    for net in (one, two):
        net.fit(ListDataSetIterator(batches()))
    assert one.conf.iteration_count == two.conf.iteration_count == 3
    assert float(one._score) == float(two._score)
    np.testing.assert_array_equal(one.params(), two.params())
    for (i, _), (name, _) in zip(one._layer_items(), two._layer_items()):
        a, b = one._updater_state[i], two._updater_state[name]
        assert jax.tree.structure(a) == jax.tree.structure(b)
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


TWINS = ["init", "make_grad_fn", "make_apply_fn", "make_raw_step",
         "_make_step", "_loop_state", "_fused_k", "fused_steps",
         "training_health", "_fit_iterator", "_fit_batch", "_fit_group",
         "_fit_tbptt", "_fit_tbptt_fused", "finish_step", "_param_leaves",
         "params", "set_params", "num_params", "unflatten_params",
         "make_flat_score_fn", "flatten_gradients", "score",
         "compute_gradient_and_score", "clone", "set_listeners"]


@pytest.mark.parametrize("name", TWINS)
def test_the_trainers_methods_exist_once(name):
    """Both containers resolve `name` to the Trainer's function, and
    neither container's file defines it."""
    assert (getattr(MultiLayerNetwork, name) is getattr(ComputationGraph,
                                                         name)
            is getattr(Trainer, name))
    for cls in (MultiLayerNetwork, ComputationGraph):
        assert name not in vars(cls)
        assert not re.search(rf"^\s*def {name}\(",
                             inspect.getsource(inspect.getmodule(cls)),
                             re.M), (cls.__name__, name)


def test_no_caller_asks_a_container_for_its_class_or_guesses_its_state():
    """The trainer's state is set in an `__init__` and read plainly; the
    parameter container's type is nobody's switch."""
    root = pathlib.Path(deeplearning4j_tpu.__file__).parent
    guess = re.compile(
        r'getattr\([\w.]+,\s*"_(health|act_stats|fused|step_emits|loop)\w*"'
        r'\s*,')
    asks = re.compile(r"isinstance\([\w.]+\._params,\s*(dict|list)\)")
    found = [f"{p.relative_to(root)}:{i}"
             for p in sorted(root.rglob("*.py"))
             for i, line in enumerate(p.read_text().splitlines(), 1)
             if guess.search(line) or asks.search(line)]
    assert not found, found


class _Epochs:
    def __init__(self):
        self.said = []

    def iteration_done(self, model, iteration):
        self.said.append(iteration)

    def on_epoch_start(self, model):
        self.said.append("start")

    def on_epoch_end(self, model):
        self.said.append("end")


@pytest.mark.parametrize("make", [list_net, graph_net],
                         ids=["list", "graph"])
def test_the_one_loop_tells_a_listener_of_each_epoch(make):
    net, heard = make(), _Epochs()
    net.set_listeners(heard)
    net.fit(ListDataSetIterator(batches(2)), num_epochs=2)
    assert heard.said == ["start", 0, 1, "end", "start", 2, 3, "end"]
    assert net.conf.epoch_count == 2


def _spy_raw_step(net, calls):
    make = net.make_raw_step

    def spy(*args, **kw):
        calls.append((args, kw))
        return make(*args, **kw)

    net.make_raw_step = spy


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 virtual devices")
@pytest.mark.parametrize("kind", ["graph_health", "list_act_stats"])
def test_the_wrapper_makes_one_keyword_call_for_either_container(kind):
    """One allreduce step on 4 devices: a graph with `health_policy` set, a
    list container with activation statistics armed."""
    calls = []
    if kind == "graph_health":
        net, policy = graph_net(), TrainingHealthPolicy()
        _spy_raw_step(net, calls)
        pw = (ParallelWrapper.Builder(net).workers(4).averaging_frequency(1)
              .health_policy(policy).build())
        want = {"collect_acts": False, "emit_health": True}
    else:
        net = list_net().collect_activation_stats()
        _spy_raw_step(net, calls)
        pw = (ParallelWrapper.Builder(net).workers(4).averaging_frequency(1)
              .build())
        want = {"collect_acts": True, "emit_health": False}
    pw.fit(batches(1)[0])
    assert calls == [((), want)]
    assert net.conf.iteration_count == 1 and np.isfinite(float(net._score))
    if kind == "graph_health":
        assert policy.snapshot()["consecutiveBad"] == 0
        assert net._last_activation_stats is None
    else:
        assert len(net._last_activation_stats) == 2    # the hidden layers
