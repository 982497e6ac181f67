"""The pair (expanding 1x1 convolution -> train-mode batch norm) as one
function whose backward never reads the convolution's output
(`_conv1x1_bn_train_fused`), the rule by which `ComputationGraph` engages it
(`_convbn_plan`), and `tools/step_bytes.py`, which sized it. The mathematics
must be the unpaired layers' own: the pair changes what the backward reads,
never what it computes."""
import json

import numpy as np
import pytest

jax = __import__("jax")
jnp = jax.numpy

from deeplearning4j_tpu import InputType, NeuralNetConfiguration, obs
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
from deeplearning4j_tpu.gradientcheck.gradient_check_util import (
    check_gradients)
from deeplearning4j_tpu.models.zoo import resnet as R
from deeplearning4j_tpu.nn.conf.graph_vertices import ElementWiseVertex
from deeplearning4j_tpu.nn.conf.layers import (ActivationLayer,
                                               BatchNormalization,
                                               ConvolutionLayer,
                                               GlobalPoolingLayer,
                                               OutputLayer)
from deeplearning4j_tpu.nn.conf.layers.normalization import (
    _bn_train_fused, _conv1x1_bn_train_fused)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.parallel import ParallelWrapper

EPS = 1e-5


# ----------------------------------------------------------------------
# the function, against autodiff through the unpaired layers' operations
# ----------------------------------------------------------------------
def _operands(dtype, cin, cout):
    k = jax.random.split(jax.random.PRNGKey(3), 5)
    a = jax.random.normal(k[0], (3, 7, 6, cin), dtype) + 0.3
    w = jax.random.normal(k[1], (1, 1, cin, cout), dtype) * 0.5
    gamma = 1 + 0.1 * jax.random.normal(k[2], (cout,), dtype)
    beta = 0.1 * jax.random.normal(k[3], (cout,), dtype)
    return (a, w, gamma, beta), k[4]


def _unpaired(stride, fast):
    def f(a, w, gamma, beta):
        x = jax.lax.conv_general_dilated(
            a, w, (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return _bn_train_fused(EPS, (0, 1, 2), fast)(x, gamma, beta)
    return f


@pytest.mark.parametrize("fast", [True, False], ids=["fastvar", "twopass"])
@pytest.mark.parametrize("cin,cout", [(8, 8), (4, 12)],
                         ids=["square", "expanding"])
@pytest.mark.parametrize("stride", [1, 2], ids=["s1", "s2"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float64, 1e-9),
                                       (jnp.float32, 1e-5)],
                         ids=["f64", "f32"])
def test_pair_matches_autodiff_of_the_unpaired_layers(dtype, tol, stride,
                                                      cin, cout, fast):
    args, kct = _operands(dtype, cin, cout)
    ref = _unpaired(stride, fast)
    new = _conv1x1_bn_train_fused(EPS, fast, (stride, stride))
    # forward: the same operations, so the same numbers (and the running
    # statistics with them)
    for r, n in zip(ref(*args), new(*args)):
        assert r.dtype == n.dtype
        np.testing.assert_array_equal(np.asarray(r), np.asarray(n))
    ct = jax.random.normal(kct, ref(*args)[0].shape, dtype)
    loss = lambda f: (lambda *p: jnp.sum(f(*p)[0] * ct))
    g_ref = jax.grad(loss(ref), (0, 1, 2, 3))(*args)
    g_new = jax.grad(loss(new), (0, 1, 2, 3))(*args)
    for name, r, n in zip(("da", "dW", "dgamma", "dbeta"), g_ref, g_new):
        assert r.shape == n.shape and r.dtype == n.dtype, name
        scale = float(jnp.max(jnp.abs(r)))
        assert float(jnp.max(jnp.abs(r - n))) <= tol * scale, name


def test_pair_backward_holds_no_tensor_of_the_output_width():
    """What the pair is for: its residuals are the narrow input and
    per-channel vectors, never the [N,H,W,Cout] output."""
    args, _ = _operands(jnp.float32, 4, 12)
    _, vjp = jax.vjp(_conv1x1_bn_train_fused(EPS, True, (1, 1)), *args)
    shapes = {tuple(l.shape) for l in jax.tree.leaves(vjp)
              if hasattr(l, "shape")}
    assert (3, 7, 6, 4) in shapes
    assert (3, 7, 6, 12) not in shapes


# ----------------------------------------------------------------------
# the rule
# ----------------------------------------------------------------------
def _graph(conv=None, bn=None, tap=False, n_in=4, data_type="float64"):
    """input -> 3x3 conv (n_in) -> relu -> [1x1 conv -> bn] -> pool -> fc:
    one pair by the rule; `conv`/`bn` override the pair's layers, `tap` adds
    a second consumer of the convolution's output."""
    gb = (NeuralNetConfiguration.Builder().seed(11).updater("sgd")
          .learning_rate(0.1).weight_init("relu").data_type(data_type)
          .graph_builder().add_inputs("input"))
    gb.add_layer("c0", ConvolutionLayer(n_out=n_in, kernel_size=(3, 3),
                                        convolution_mode="same",
                                        activation="relu"), "input")
    pc = dict(n_out=8, kernel_size=(1, 1), convolution_mode="same",
              activation="identity", has_bias=False)
    pc.update(conv or {})
    gb.add_layer("p_conv", ConvolutionLayer(**pc), "c0")
    gb.add_layer("p_bn", BatchNormalization(**(bn or {})), "p_conv")
    x = "p_bn"
    if tap:
        gb.add_vertex("tap", ElementWiseVertex(op="add"), "p_bn", "p_conv")
        x = "tap"
    gb.add_layer("pool", GlobalPoolingLayer(pooling_type="avg"), x)
    gb.add_layer("fc", OutputLayer(n_out=3, activation="softmax",
                                   loss_function="mcxent"), "pool")
    return (gb.set_outputs("fc")
            .set_input_types(InputType.convolutional(6, 6, 2)).build())


def _batch(seed=0, n=8, hw=6, c=2, classes=3):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, hw, hw, c)).astype(np.float32)
    y = np.eye(classes, dtype=np.float32)[rng.integers(0, classes, n)]
    return x, y


def _unpaired_twin(net, remat=False):
    """The same container with the plan emptied: the code before the pair."""
    twin = ComputationGraph(net.conf, remat_segments=remat).init()
    twin.set_params(net.params())
    twin._convbn_plan_cache = {}
    return twin


def test_resnet50_pairs_its_twenty_expanding_units():
    net = ComputationGraph(R.resnet50_conf(height=32, width=32,
                                           num_classes=10))
    plan = net._convbn_plan()
    blocks = [f"s{si + 2}b{bi}" for si, (n, _) in enumerate(R.STAGES)
              for bi in range(n)]
    want = {f"{b}_c_bn": f"{b}_c_conv" for b in blocks}
    want.update({f"s{s}b0_sc_bn": f"s{s}b0_sc_conv" for s in (2, 3, 4, 5)})
    assert plan == want and len(plan) == 20
    assert obs.default_registry().gauge("train.convbn_pairs").value == 20


def test_the_matching_graph_pairs_one():
    net = ComputationGraph(_graph()).init()
    assert net._convbn_plan() == {"p_bn": "p_conv"}
    assert obs.default_registry().gauge("train.convbn_pairs").value == 1


BROKEN = {
    "bias": dict(conv={"has_bias": True}),
    "kernel3x3": dict(conv={"kernel_size": (3, 3)}),
    "activation": dict(conv={"activation": "relu"}),
    "dropout": dict(conv={"dropout": 0.8}),
    "second_consumer": dict(tap=True),
    "narrowing": dict(n_in=12),
    "square": dict(n_in=8),
    "padding": dict(conv={"convolution_mode": "truncate",
                          "padding": (1, 1)}),
    "lock_gamma_beta": dict(bn={"lock_gamma_beta": True}),
    "autodiff_backward": dict(bn={"fused_backward": False}),
}


@pytest.mark.parametrize("case", sorted(BROKEN))
def test_one_broken_condition_pairs_nothing(case):
    """Each case breaks one condition of the rule: no pair, the gauge reads
    0, and the step lowers to the program it lowers to with the plan
    emptied by hand (today's program)."""
    net = ComputationGraph(_graph(**BROKEN[case])).init()
    assert net._convbn_plan() == {}
    assert obs.default_registry().gauge("train.convbn_pairs").value == 0
    ds = DataSet(*_batch())
    assert (net.lower_step(ds).as_text()
            == _unpaired_twin(net).lower_step(ds).as_text())


def test_inference_and_evaluation_do_not_pair():
    """train=False breaks the rule too: the forward is the layers' own."""
    net = ComputationGraph(_graph()).init()
    x, _ = _batch()
    assert net._pair_of("p_bn", False, net._params, {"c0": x}) is None
    assert net._pair_of("p_bn", True, net._params, {"c0": x}) is not None
    twin = _unpaired_twin(net)
    np.testing.assert_array_equal(np.asarray(net.output(x)[0]),
                                  np.asarray(twin.output(x)[0]))


def test_feed_forward_keeps_the_convolutions_output():
    """The conv vertex's activation stays the convolution's output for
    whoever asks, in a training forward too."""
    net = ComputationGraph(_graph()).init()
    twin = _unpaired_twin(net)
    x, _ = _batch()
    acts, ref = (n.feed_forward(x, train=True) for n in (net, twin))
    assert set(acts) == set(ref)
    for name in ref:
        np.testing.assert_allclose(np.asarray(acts[name]),
                                   np.asarray(ref[name]), rtol=1e-12,
                                   atol=1e-12, err_msg=name)


def test_numeric_gradient_check_through_the_pair():
    net = ComputationGraph(_graph()).init()
    assert net._convbn_plan()
    x, y = _batch(n=4)
    assert check_gradients(net, x.astype(np.float64), y.astype(np.float64),
                           epsilon=1e-6, max_rel_error=1e-5)


# ----------------------------------------------------------------------
# training: a small residual graph of the tiny-resnet configuration's shape
# ----------------------------------------------------------------------
def _small_resnet(data_type="float32"):
    """Stem, a projection block, an identity block and a strided projection
    block, built by the zoo's own helpers: 5 pairs (3 `c`, 2 `sc`, one of
    them strided); the 3x3, the narrowing and the square units stay out."""
    gb = (NeuralNetConfiguration.Builder().seed(5).updater("nesterovs")
          .momentum(0.9).learning_rate(0.01).weight_init("relu")
          .data_type(data_type).graph_builder().add_inputs("input"))
    x = R._conv_bn(gb, "stem", "input", 8, (3, 3), (1, 1), "relu")
    x = R._bottleneck(gb, "s2b0", x, 8, 1, True)
    x = R._bottleneck(gb, "s2b1", x, 8, 1, False)
    x = R._bottleneck(gb, "s3b0", x, 16, 2, True)
    gb.add_layer("avgpool", GlobalPoolingLayer(pooling_type="avg"), x)
    gb.add_layer("fc", OutputLayer(n_out=5, activation="softmax",
                                   loss_function="mcxent"), "avgpool")
    return (gb.set_outputs("fc")
            .set_input_types(InputType.convolutional(8, 8, 3)).build())


PAIRS = {"s2b0_c_bn", "s2b0_sc_bn", "s2b1_c_bn", "s3b0_c_bn", "s3b0_sc_bn"}


def _assert_same_training(a, b, rtol, atol):
    for tree in ("_params", "_model_state"):
        ta, tb = getattr(a, tree), getattr(b, tree)
        for name in ta:
            for k in ta[name]:
                np.testing.assert_allclose(
                    np.asarray(ta[name][k]), np.asarray(tb[name][k]),
                    rtol=rtol, atol=atol, err_msg=f"{tree} {name}.{k}")


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_three_fit_steps_paired_and_unpaired_agree(remat):
    net = ComputationGraph(_small_resnet(), remat_segments=remat).init()
    assert set(net._convbn_plan()) == PAIRS
    twin = _unpaired_twin(net, remat)
    losses = ([], [])
    for step in range(3):
        ds = DataSet(*_batch(seed=step, hw=8, c=3, classes=5))
        for n, seen in zip((net, twin), losses):
            n.fit(ds)
            seen.append(float(n._score))
    np.testing.assert_allclose(losses[0], losses[1], rtol=1e-5)
    # running mean and variance with the parameters: the pair's forward is
    # the unpaired layers' own
    _assert_same_training(net, twin, rtol=2e-4, atol=2e-6)


def test_parallel_wrapper_on_four_devices_matches_one():
    """The sharded step is built from the same `make_raw_step`: the pair's
    batch sums (s1, sa, A1, G) are all-reduced by GSPMD, the statistics stay
    over the whole batch."""
    one = ComputationGraph(_small_resnet()).init()
    four = ComputationGraph(one.conf).init()
    four.set_params(one.params())
    pw = (ParallelWrapper.Builder(four).workers(4).averaging_frequency(1)
          .build())
    assert set(four._convbn_plan()) == PAIRS
    ds = DataSet(*_batch(n=16, hw=8, c=3, classes=5))
    pw.fit(ListDataSetIterator(ds, 16), num_epochs=3)
    for _ in range(3):
        one.fit(ds)
    _assert_same_training(four, one, rtol=2e-4, atol=2e-6)


# ----------------------------------------------------------------------
# tools/step_bytes.py, on the CPU backend's text
# ----------------------------------------------------------------------
def test_step_bytes_reads_the_cpu_backends_compile(capsys):
    from tools import step_bytes as SB
    assert SB.shape_bytes("(bf16[2,3]{1,0}, f32[4]{0})") == 12 + 16
    assert SB.shape_bytes("f32[]") == 4
    hlo = "\n".join([
        "HloModule m", "",
        "%fused (p: f32[8]) -> f32[8] {",
        "  %p = f32[8]{0} parameter(0)",
        "  ROOT %n = f32[8]{0} negate(%p)",
        "}", "",
        "ENTRY %main (a: f32[8], b: f32[8]) -> f32[8] {",
        "  %a = f32[8]{0} parameter(0)",
        "  %b = f32[8]{0} parameter(1)",
        '  %f = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused, '
        'metadata={op_name="jit(step)/jvp(convolution.c)/neg"}',
        "  %cs = (f32[8]{0}, f32[8]{0:S(1)}, u32[]{:S(2)}) copy-start(%b)",
        "  %cd = f32[8]{0:S(1)} copy-done(%cs)",
        '  ROOT %s = f32[8]{0} add(f32[8]{0} %f, f32[8]{0} %cd), '
        'metadata={op_name="jit(step)/transpose(jvp(batchnorm.b))/add"}',
        "}"])
    # an asynchronous copy once, at its start: what it reads + what it
    # writes (its context word with it)
    assert SB.entry_instructions(hlo) == [("f", "fusion", 64),
                                          ("cs", "copy-start", 68),
                                          ("s", "add", 96)]
    assert SB.bytes_by_scope(hlo) == {"batchnorm backward": 96,
                                      "(no scope)": 68,
                                      "convolution forward": 64}

    SB.main(["--conf", f"{__name__}:_small_resnet", "--batch", "4",
             "--topology", "cpu"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["convbn_pairs"] == 5 and line["topology"] == "cpu"
    assert line["temp_size_in_bytes"] > 0
    by_scope = line["entry_bytes_by_scope"]
    assert line["entry_bytes"] == sum(by_scope.values()) > 0
    assert any(k.startswith("convolution") for k in by_scope)
    assert "entry_ms_at_hbm_peak" not in line     # no peak for a CPU
