"""Segment gradient checkpointing (ComputationGraph remat_segments) —
the structural bytes/step lever for HBM-bound CNN training (PERF.md r4).
Numerics must be IDENTICAL to the default path: remat changes what the
backward stores, never what it computes. A segment keeps what a layer of
it names for keeping (`LayerConf.remat_keeps`: the `attention` kind alone,
tests/test_laguna.py); every other kind's segments lower as they did."""
import hashlib
import os
import sys

import numpy as np
import pytest

jax = __import__("jax")
jnp = jax.numpy

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deeplearning4j_tpu import InputType, NeuralNetConfiguration, obs
from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet
from deeplearning4j_tpu.nn.conf.graph_vertices import ElementWiseVertex
from deeplearning4j_tpu.nn.conf.layers import (ActivationLayer,
                                               BatchNormalization,
                                               ConvolutionLayer,
                                               GlobalPoolingLayer,
                                               OutputLayer)
from deeplearning4j_tpu.nn.graph import ComputationGraph


def _residual_conf(seed=7):
    """Two residual blocks: conv->BN->relu chains + adds (the ResNet
    shape at toy scale)."""
    gb = (NeuralNetConfiguration.Builder().seed(seed).updater("sgd")
          .learning_rate(0.1).weight_init("relu").graph_builder()
          .add_inputs("input"))
    gb.add_layer("c0", ConvolutionLayer(n_out=8, kernel_size=(3, 3),
                                        convolution_mode="same"), "input")
    x = "c0"
    for b in range(2):
        gb.add_layer(f"b{b}_c1", ConvolutionLayer(
            n_out=8, kernel_size=(3, 3), convolution_mode="same"), x)
        gb.add_layer(f"b{b}_bn", BatchNormalization(), f"b{b}_c1")
        gb.add_layer(f"b{b}_r", ActivationLayer(activation="relu"),
                     f"b{b}_bn")
        gb.add_layer(f"b{b}_c2", ConvolutionLayer(
            n_out=8, kernel_size=(3, 3), convolution_mode="same"),
            f"b{b}_r")
        gb.add_vertex(f"b{b}_add", ElementWiseVertex(op="add"),
                      f"b{b}_c2", x)
        gb.add_layer(f"b{b}_out", ActivationLayer(activation="relu"),
                     f"b{b}_add")
        x = f"b{b}_out"
    gb.add_layer("pool", GlobalPoolingLayer(pooling_type="avg"), x)
    gb.add_layer("fc", OutputLayer(n_out=3, activation="softmax",
                                   loss_function="mcxent"), "pool")
    return (gb.set_outputs("fc")
            .set_input_types(InputType.convolutional(8, 8, 2)).build())


def _data(seed=0):
    rng = np.random.default_rng(seed)
    x = rng.random((8, 8, 8, 2)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 8)]
    return x, y


class TestRematSegments:
    def test_plan_segments_at_adds(self):
        net = ComputationGraph(_residual_conf(), remat_segments=True).init()
        seg_of, n_seg, keeps = net._remat_plan()
        assert keeps == {}                      # no kind here names any
        assert n_seg == 3                       # two adds -> three segments
        assert seg_of["b0_c1"] == 0
        assert seg_of["b0_out"] == 1            # first vertex after add 0
        assert seg_of["fc"] == 2

    def test_training_identical_to_default(self):
        """Same seed, same data: per-step scores and final params match
        the non-remat path bit-for-bit-ish (fp tolerance)."""
        x, y = _data()
        nets = [ComputationGraph(_residual_conf(), remat_segments=r).init()
                for r in (False, True)]
        scores = [[], []]
        for i, net in enumerate(nets):
            for _ in range(4):
                net.fit(DataSet(x, y))
                scores[i].append(float(net._score))
        np.testing.assert_allclose(scores[0], scores[1], rtol=1e-5)
        for a, b in zip(jax.tree.leaves(nets[0]._params),
                        jax.tree.leaves(nets[1]._params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-5)

    def test_bn_running_stats_still_update(self):
        x, y = _data()
        net = ComputationGraph(_residual_conf(), remat_segments=True).init()
        before = np.asarray(net._model_state["b0_bn"]["mean"]).copy()
        net.fit(DataSet(x, y))
        after = np.asarray(net._model_state["b0_bn"]["mean"])
        assert not np.allclose(before, after)

    def test_inference_output_matches(self):
        x, _ = _data()
        n0 = ComputationGraph(_residual_conf(), remat_segments=False).init()
        n1 = ComputationGraph(_residual_conf(), remat_segments=True).init()
        np.testing.assert_allclose(np.asarray(n0.output(x)),
                                   np.asarray(n1.output(x)), atol=1e-6)

    def test_resnet50_factory_flag(self):
        from deeplearning4j_tpu.models.zoo.resnet import resnet50_conf
        conf = resnet50_conf(height=32, width=32, num_classes=4,
                             data_type="float32")
        net = ComputationGraph(conf, remat_segments=True)
        _, n_seg, _ = net._remat_plan()
        assert n_seg == 17                      # 16 bottleneck adds + head


def _tiny_keye():
    """The rehearsal's Keye (benchmarks/configs/tiny-keye.json) and a batch
    of two rows of T = 128, the first 16 positions an image's."""
    from benchmarks.drivers import train_vl
    from benchmarks.harness import loader
    rows, t = 2, 128
    mds = MultiDataSet(
        [jnp.zeros((rows, t), jnp.int32),
         jnp.zeros((rows, 16, 64), jnp.bfloat16),
         jnp.zeros((rows, t, 3), jnp.int32)],
        [jnp.zeros((rows, t), jnp.int32)],
        labels_masks=[jnp.ones((rows, t), jnp.float32)])
    return train_vl.build(loader.load_json("configs", "tiny-keye.json")), mds


def _residual_cnn():
    x, y = _data()
    return (ComputationGraph(_residual_conf(), remat_segments=True).init(),
            DataSet(x, y))


def _small_resnet50():
    from deeplearning4j_tpu.models.zoo.resnet import resnet50_conf
    conf = resnet50_conf(height=32, width=32, num_classes=10)
    return (ComputationGraph(conf, remat_segments=True).init(),
            DataSet(jnp.zeros((2, 32, 32, 3), jnp.bfloat16),
                    jnp.zeros((2, 10), jnp.float32)))


# sha256 of the step's lowered text at the parent commit of PR 33 (ed8814c),
# under the suite's x64, as `RESNET_STEP_SHA256` in tests/test_keye_vl.py
# holds the step without rematerialisation. A PR that changes one of these
# steps on purpose computes its hash anew (the body of the test below):
# PR 35 did for "tiny-keye", whose index scores got a backward of their own
# (`decoder.index_scores`), and PR 37, whose attention backward became one
# kernel (`sparse_attention_bwd`) and whose layers' state gained
# `attend_backward_passes`; the two CNNs' are the parent's still.
REMAT_STEP_SHA256 = {
    "tiny-keye":
        "a3a2588f051145ec25f9e3e2bf55d85cd9a91aa6aadebdfe674179e8a668f2eb",
    "residual-cnn":
        "9a7f306c2963ca5d54481136528d1580c1fdf06d524de743784f6a466d7a2cb1",
    "resnet50":
        "144a668f3bc9be153199e831b97730cad49fda295c0a7eb130344fb296fb304b",
}
GRAPHS = {"tiny-keye": _tiny_keye, "residual-cnn": _residual_cnn,
          "resnet50": _small_resnet50}


@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_segments_that_keep_nothing_lower_to_the_text_they_lowered_to(name):
    """`sparseattention` (its row is its own checkpoint, with its own
    policy), `moe` and the CNN kinds name nothing: their segments are
    `jax.checkpoint(seg_fn)` letter for letter, the gauge reads 0."""
    net, ds = GRAPHS[name]()
    text = net.lower_step(ds).as_text()
    assert net._remat_plan()[2] == {}
    assert obs.default_registry().gauge(
        "train.remat_kept_segments").value == 0
    assert hashlib.sha256(text.encode()).hexdigest() == \
        REMAT_STEP_SHA256[name]
