"""Recurrent layer tests: GravesLSTM / bidirectional / masking / TBPTT /
rnnTimeStep.

Mirrors reference suites GradientCheckTests (LSTM), GradientCheckTestsMasking,
nn/layers/recurrent tests, and MultiLayerNetwork TBPTT tests (SURVEY.md §4).
"""
import numpy as np
import pytest

from deeplearning4j_tpu import (InputType, MultiLayerNetwork,
                                NeuralNetConfiguration)
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.gradientcheck.gradient_check_util import check_gradients
from deeplearning4j_tpu.nn.conf.layers import (GravesBidirectionalLSTM,
                                               GravesLSTM, RnnOutputLayer,
                                               SimpleRnn)


def rnn_conf(layer, n_in=3, n_classes=3, data_type="float64", **kwargs):
    b = (NeuralNetConfiguration.Builder().seed(12345).data_type(data_type)
         .learning_rate(0.1).weight_init("xavier"))
    lb = b.list().layer(0, layer).layer(
        1, RnnOutputLayer(n_out=n_classes, activation="softmax",
                          loss_function="mcxent"))
    for k, v in kwargs.items():
        getattr(lb, k)(v)
    return lb.set_input_type(InputType.recurrent(n_in)).build()


def seq_data(n=4, t=6, f=3, n_classes=3, seed=0, dtype=np.float64):
    rng = np.random.default_rng(seed)
    x = rng.normal(0, 1, (n, t, f)).astype(dtype)
    y = np.eye(n_classes, dtype=dtype)[rng.integers(0, n_classes, (n, t))]
    return x, y


class TestLSTMShapes:
    def test_lstm_output_shape(self):
        net = MultiLayerNetwork(rnn_conf(GravesLSTM(n_out=5),
                                         data_type="float32")).init()
        x, _ = seq_data(dtype=np.float32)
        out = np.asarray(net.output(x))
        assert out.shape == (4, 6, 3)
        np.testing.assert_allclose(out.sum(-1), 1.0, atol=1e-4)

    def test_lstm_param_count(self):
        net = MultiLayerNetwork(rnn_conf(GravesLSTM(n_out=5))).init()
        # W 3*20 + RW 5*20 + b 20 + peep 15 = 60+100+20+15 = 195; out 5*3+3=18
        assert net.num_params() == 195 + 18

    @pytest.mark.slow
    def test_scan_unroll_equivalent_numerics(self):
        """scan_unroll is a scheduling knob (lax.scan unroll=N): the same
        math with different XLA fusion, so forward and a masked training
        step match unroll=1 to float-reassociation tolerance. Full tier: the
        knob is off by default."""
        x, y = seq_data(dtype=np.float32)
        mask = np.ones((4, 6), np.float32)
        mask[2, 4:] = 0.0
        outs, scores = [], []
        for unroll in (1, 4):
            net = MultiLayerNetwork(rnn_conf(
                GravesLSTM(n_out=5, scan_unroll=unroll),
                data_type="float32")).init()
            outs.append(np.asarray(net.output(x, features_mask=mask)))
            net.fit(DataSet(x, y, features_mask=mask))
            scores.append(float(net._score))
        np.testing.assert_allclose(outs[0], outs[1], atol=1e-6)
        assert abs(scores[0] - scores[1]) < 1e-5

    def test_bidirectional_shape(self):
        net = MultiLayerNetwork(rnn_conf(GravesBidirectionalLSTM(n_out=5),
                                         data_type="float32")).init()
        x, _ = seq_data(dtype=np.float32)
        assert np.asarray(net.output(x)).shape == (4, 6, 3)


class TestLSTMGradients:
    def test_gradcheck_lstm(self):
        x, y = seq_data()
        net = MultiLayerNetwork(rnn_conf(GravesLSTM(n_out=4))).init()
        assert check_gradients(net, x, y, max_rel_error=1e-4, subset=60)

    def test_gradcheck_simple_rnn(self):
        x, y = seq_data()
        net = MultiLayerNetwork(rnn_conf(SimpleRnn(n_out=4))).init()
        assert check_gradients(net, x, y, max_rel_error=1e-4, subset=40)

    @pytest.mark.slow
    def test_gradcheck_bidirectional(self):
        x, y = seq_data()
        net = MultiLayerNetwork(
            rnn_conf(GravesBidirectionalLSTM(n_out=3))).init()
        assert check_gradients(net, x, y, max_rel_error=1e-4, subset=60)

    def test_gradcheck_lstm_masked(self):
        x, y = seq_data()
        lmask = np.ones((4, 6))
        lmask[2, 3:] = 0
        lmask[3, 1:] = 0
        fmask = lmask.copy()
        net = MultiLayerNetwork(rnn_conf(GravesLSTM(n_out=4))).init()
        assert check_gradients(net, x, y, fmask=fmask, lmask=lmask,
                               max_rel_error=1e-4, subset=50)


class TestMaskingSemantics:
    def test_masked_steps_zero_output(self):
        layer = GravesLSTM(n_in=3, n_out=4)
        layer = layer.apply_global_defaults({"activation": "tanh"})
        import jax
        params = layer.init_params(jax.random.PRNGKey(0))
        x = np.random.default_rng(0).normal(size=(2, 5, 3)).astype(np.float32)
        mask = np.ones((2, 5), np.float32)
        mask[1, 2:] = 0
        out, carry = layer.forward_with_carry(
            params, x, layer.init_carry(2), mask=mask)
        out = np.asarray(out)
        assert np.all(out[1, 2:] == 0.0)
        assert np.any(out[1, :2] != 0.0)

    def test_masked_state_carried(self):
        """State at masked steps must hold the last unmasked value."""
        import jax
        layer = GravesLSTM(n_in=3, n_out=4).apply_global_defaults(
            {"activation": "tanh"})
        params = layer.init_params(jax.random.PRNGKey(0))
        x = np.random.default_rng(0).normal(size=(1, 5, 3)).astype(np.float32)
        mask = np.array([[1, 1, 0, 0, 0]], np.float32)
        _, carry_masked = layer.forward_with_carry(
            params, x, layer.init_carry(1), mask=mask)
        _, carry_short = layer.forward_with_carry(
            params, x[:, :2], layer.init_carry(1))
        np.testing.assert_allclose(np.asarray(carry_masked["h"]),
                                   np.asarray(carry_short["h"]), rtol=1e-5)


class TestRnnTimeStep:
    def test_time_step_matches_full_forward(self):
        net = MultiLayerNetwork(rnn_conf(GravesLSTM(n_out=4),
                                         data_type="float32")).init()
        x, _ = seq_data(n=2, t=5, dtype=np.float32)
        full = np.asarray(net.output(x))
        net.rnn_clear_previous_state()
        step_outs = []
        for t in range(5):
            step_outs.append(np.asarray(net.rnn_time_step(x[:, t])))
        stepped = np.stack(step_outs, axis=1)
        np.testing.assert_allclose(full, stepped, rtol=1e-4, atol=1e-5)

    def test_clear_state_resets(self):
        net = MultiLayerNetwork(rnn_conf(GravesLSTM(n_out=4),
                                         data_type="float32")).init()
        x, _ = seq_data(n=2, t=3, dtype=np.float32)
        o1 = np.asarray(net.rnn_time_step(x[:, 0]))
        net.rnn_clear_previous_state()
        o2 = np.asarray(net.rnn_time_step(x[:, 0]))
        np.testing.assert_allclose(o1, o2, rtol=1e-5)


class TestTBPTT:
    def test_tbptt_runs_and_learns(self):
        x, y = seq_data(n=8, t=12, dtype=np.float32)
        conf = rnn_conf(GravesLSTM(n_out=8), data_type="float32",
                        backprop_type="tbptt", t_bptt_forward_length=4)
        net = MultiLayerNetwork(conf).init()
        ds = DataSet(x, y)
        s0 = net.score(ds)
        for _ in range(10):
            net.fit(ds)
        # 3 segments per fit * 10 fits
        assert net.conf.iteration_count == 30
        assert net.score(ds) < s0
