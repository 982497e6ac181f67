"""NVIDIA-Nemotron-3-Nano-30B-A3B (models/zoo/nemotron_h.py: the layer kind
`mamba2` of nn/conf/layers/mamba2.py over the chunked scan of ops/ssd.py,
the two-matrix relu^2 experts of `moe` and parallel/moe.py, `attention`
without positions or gate) against its plain reference
(benchmarks/references/nemotron_h.py, whose state-space layer is the
recurrence position by position), on seeded weights at a small size in
float32 through `ComputationGraph.fit`: the scan alone at three chunk sizes
with decays from under 1e-3 to over 0.999, the `mamba2` layer's value and
every gradient, causality through the convolution and the scan, the
two-matrix experts forward and through the walk's backward, attention
without a turn, the share test that ties one chip's experts to the whole
layer, three steps of `fit` with the routers' bias, the scopes on the
lowered step; and the layers other models use lower as the parent commit's.
"""
import hashlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.references import nemotron_h as ref               # noqa: E402
from deeplearning4j_tpu.datasets.dataset import MultiDataSet      # noqa: E402
from deeplearning4j_tpu.models.zoo import nemotron_h_conf         # noqa: E402
from deeplearning4j_tpu.nn.conf.layers import decoder, mamba2     # noqa: E402
from deeplearning4j_tpu.nn.graph import ComputationGraph          # noqa: E402
from deeplearning4j_tpu.ops import sparse_attention as sa         # noqa: E402
from deeplearning4j_tpu.ops import ssd, ssm_pointwise             # noqa: E402
from deeplearning4j_tpu.parallel.moe import held_experts_ffn, route_all  # noqa: E402

# the published layers 0-8 (4 state-space, 4 expert, 1 attention) at hidden
# 64: 8 state-space heads of 8 in 2 groups over a state of 16, chunks of 8;
# 8 query heads over 2 key/value heads of 16; 16 experts top-4 of which 4
# are held (4..7) beside a shared one, half the vocabulary; T = 64
MODEL = {
    "hidden_size": 64, "hybrid_override_pattern": "MEMEM*EME",
    "mamba_num_heads": 8, "mamba_head_dim": 8, "ssm_state_size": 16,
    "n_groups": 2, "conv_kernel": 4, "chunk_size": 8,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
    "n_routed_experts": 4, "num_experts_per_tok": 4,
    "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 48,
    "routed_scaling_factor": 2.5, "layer_norm_epsilon": 1e-5,
    "vocab_size": 128, "bias_update_rate": 0.001,
    "deployment": {"router_width": 16, "first_held": 4,
                   "layers": list(range(9))}}
WHOLE = {**MODEL, "n_routed_experts": 16,
         "deployment": {"router_width": 16, "first_held": 0,
                        "layers": list(range(9))}}
TRAINER = {"learning_rate": 1e-3}
B, T = 2, 64
MAMBA = ("l0_mixer", "l2_mixer", "l4_mixer", "l7_mixer")
SPARSE = ("l1_mixer", "l3_mixer", "l6_mixer", "l8_mixer")
CLOSE = dict(rtol=2e-4, atol=2e-6)      # float32 against float32 `highest`

# value-and-gradient texts of the layers OTHER models use, on the parent
# commit (3e821fd) under the suite's x64: the turned, gated `attention`
# (Laguna's) and the gated `moe` beside a shared expert under a biased
# sigmoid router (JoyAI's). A PR that changes either layer on purpose
# computes them anew.
GATED_ATTENTION_SHA256 = \
    "6511376f5aec31639632a4d82d08a78496f963435c13db1322b5434a2140bded"
GATED_MOE_SHA256 = \
    "d40a1c9ecfd2be351136dc732f9715cee3a94e072aae0a2e0c646b474684f4df"


def conf_of(model=MODEL, **over):
    dep = model["deployment"]
    kw = {k: model[k] for k in (
        "hidden_size", "hybrid_override_pattern", "mamba_num_heads",
        "mamba_head_dim", "ssm_state_size", "n_groups", "conv_kernel",
        "chunk_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "num_experts_per_tok", "moe_intermediate_size",
        "moe_shared_expert_intermediate_size", "routed_scaling_factor",
        "layer_norm_epsilon", "bias_update_rate")}
    kw.update(n_routed_experts=dep["router_width"],
              experts_held=model["n_routed_experts"],
              first_held=dep["first_held"], layers=dep["layers"],
              vocab_rows=model["vocab_size"],
              learning_rate=TRAINER["learning_rate"], data_type="float32")
    kw.update(over)
    return nemotron_h_conf(**kw)


def weights(model=MODEL, seed=0):
    """Seeded leaves: matrices large enough that the router discriminates
    at this size, norm weights near 1 and not at it, the mixer's own spread
    over the decays a trained model has (A in [-16, -1], time steps from
    0.001 to 0.5)."""
    shapes = ref.param_shapes(model)
    flat = [(n, k) for n in sorted(shapes) for k in sorted(shapes[n])]
    keys = jax.random.split(jax.random.PRNGKey(seed), len(flat))
    out = {n: {} for n in shapes}
    for (n, k), kk in zip(flat, keys):
        shape = shapes[n][k]
        a = jax.random.normal(kk, shape, jnp.float32)
        u = jax.random.uniform(kk, shape, jnp.float32)
        if k == "A_log":
            out[n][k] = jnp.log(1.0 + 15.0 * u)
        elif k == "dt_bias":
            dt = jnp.exp(jnp.float32(np.log(0.001))
                         + u * jnp.float32(np.log(500.0)))
            out[n][k] = dt + jnp.log(-jnp.expm1(-dt))
        elif k in ("w_c", "b_c"):
            out[n][k] = u - 0.5
        else:
            out[n][k] = 1.0 + 0.1 * a if len(shape) == 1 else 0.2 * a
    return out


def batch_of(seed, model=MODEL, t=T, rows=B):
    ids = jax.random.randint(jax.random.PRNGKey(100 + seed), (rows, t), 0,
                             model["vocab_size"], jnp.int32)
    mask = jnp.broadcast_to((jnp.arange(t) < t - 1).astype(jnp.float32),
                            (rows, t))
    return {"ids": ids, "labels": jnp.roll(ids, -1, 1), "mask": mask}


def mds_of(b):
    return MultiDataSet([b["ids"]], [b["labels"]], labels_masks=[b["mask"]])


def trainer(w, **over):
    net = ComputationGraph(conf_of(**over)).init()
    assert {n: {k: a.shape for k, a in d.items()}
            for n, d in net._params.items()} == \
        {n: {k: a.shape for k, a in d.items()} for n, d in w.items()}
    net._params = jax.tree.map(jnp.array, w)
    return net


# ---------------------------------------------------------- the scan alone
def scan_inputs(t=32, heads=4, groups=2, p=8, n=16, rows=2, seed=0):
    """Time steps log-uniform over six decades against A in [-16, -1]:
    decays from 0 to rounding up to within 1e-5 of 1."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (rows, t, heads, p), jnp.float32)
    dt = jnp.exp(jax.random.uniform(ks[1], (rows, t, heads), jnp.float32,
                                    float(np.log(1e-5)), float(np.log(10.0))))
    A = -jnp.exp(jax.random.uniform(ks[2], (heads,), jnp.float32, 0.0,
                                    float(np.log(16.0))))
    Bm = jax.random.normal(ks[3], (rows, t, groups, n), jnp.float32)
    Cm = jax.random.normal(ks[4], (rows, t, groups, n), jnp.float32)
    return x, dt, A, Bm, Cm


def recurrence(x, dt, A, Bm, Cm):
    """The reference's position-by-position form, row by row."""
    ys, lasts = zip(*(ref.ssm_recurrence(x[b], dt[b], A, Bm[b], Cm[b])
                      for b in range(x.shape[0])))
    return jnp.stack(ys), jnp.stack(lasts)


def scan(x, dt, A, Bm, Cm, chunk, **kw):
    """`ssd.ssd_scan` with no skip (D = 0)."""
    return ssd.ssd_scan(x, dt, A, Bm, Cm, jnp.zeros(A.shape, jnp.float32),
                        chunk, **kw)


def scan_by(monkeypatch, inside):
    """The scan's rule set to run `inside` ("kernel": the Pallas kernels,
    interpreted here; "xla": the XLA products), whatever the shapes say."""
    monkeypatch.setattr(ssd, "inside_kernel",
                        lambda *shape: float(inside == "kernel"))


def pallas_calls(fn, *args):
    """{kernel name: its pallas_calls} anywhere in the jaxpr of fn(*args)."""
    out = {}

    def walk(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                name = e.params["name"]
                out[name] = out.get(name, 0) + 1
            for sub in jax.core.jaxprs_in_params(e.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return out


@pytest.mark.parametrize("inside", ["kernel", "xla"])
@pytest.mark.parametrize("chunk", [4, 8, 16])
def test_the_chunked_scan_is_the_recurrence_at_every_chunk_size(
        chunk, inside, monkeypatch):
    scan_by(monkeypatch, inside)
    args = scan_inputs()
    decay = np.exp(np.asarray(args[1]) * np.asarray(args[2]))
    assert (decay < 1e-3).any() and (decay > 0.999).any()
    with jax.default_matmul_precision("highest"):
        want_y, want_last = recurrence(*args)
        y, last = scan(*args, chunk)
    np.testing.assert_allclose(y, want_y, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(last, want_last, rtol=2e-4, atol=2e-5)
    assert last.dtype == jnp.float32 and y.dtype == args[0].dtype


@pytest.mark.parametrize("inside, block", [("xla", None), ("xla", 2),
                                           ("kernel", None)])
def test_the_chunked_scans_gradients_are_the_recurrences(inside, block,
                                                         monkeypatch):
    """The kernels; the XLA form with all chunks' insides at once, and two
    chunks a rematerialised step; with the skip D x."""
    scan_by(monkeypatch, inside)
    args = scan_inputs(seed=1) + (
        jax.random.uniform(jax.random.PRNGKey(7), (4,), jnp.float32, 0.5,
                           1.5),)
    w = jax.random.normal(jax.random.PRNGKey(5), args[0].shape, jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(6), (2, 4, 8, 16), jnp.float32)

    def scored(fn):
        def f(*a):
            y, last = fn(*a)
            return jnp.sum(w * y) + jnp.sum(v * last)
        return jax.grad(f, range(6))

    def skipped(x, dt, A, Bm, Cm, D):
        y, last = recurrence(x, dt, A, Bm, Cm)
        return y + D[:, None] * x, last

    with jax.default_matmul_precision("highest"):
        want = scored(skipped)(*args)
        got = scored(lambda *a: ssd.ssd_scan(*a, 8, block=block))(*args)
    for name, a, b in zip(("x", "dt", "A", "B", "C", "D"), got, want):
        np.testing.assert_allclose(
            a, b, rtol=2e-3, atol=2e-5 * float(jnp.max(jnp.abs(b))),
            err_msg=name)
        assert float(jnp.max(jnp.abs(b))) > 0, name


@pytest.mark.parametrize("inside", ["kernel", "xla"])
def test_the_recurrence_across_chunks_keeps_only_its_named_states(
        inside, monkeypatch):
    """Under a checkpoint that saves `ssd.KEEP` alone, scored by y**2, whose
    backward needs y again: the XLA form keeps the entering states and
    remakes y from them without running the forward recurrence again (one
    forward loop over chunks and one backward in the whole program). The
    kernels name nothing to keep: y comes again only from `ssd_fwd`, which
    makes the states beside it, so the backward walks forward once more
    (`ssd_fwd` twice, `ssd_bwd` once), under a score linear in y too."""
    scan_by(monkeypatch, inside)
    args = scan_inputs()
    keep = jax.checkpoint_policies.save_only_these_names(ssd.KEEP)
    score = lambda *a: jnp.sum(scan(*a, 8)[0] ** 2)
    grad = jax.grad(jax.checkpoint(score, policy=keep), range(5))
    if inside == "xla":
        text = jax.jit(grad).lower(*args).as_text()
        assert len(re.findall(r"stablehlo\.while", text)) == 2
    else:
        w = jax.random.normal(jax.random.PRNGKey(5), args[0].shape,
                              jnp.float32)
        linear = lambda *a: jnp.sum(w * scan(*a, 8)[0])
        for fn in (score, linear):
            assert pallas_calls(jax.grad(jax.checkpoint(fn, policy=keep),
                                         range(5)), *args) == {
                "ssd_fwd": 2, "ssd_bwd": 1}
    want = jax.grad(score, range(5))(*args)
    for a, b in zip(grad(*args), want):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("inside", ["kernel", "xla"])
def test_decays_under_1e_20_beside_decays_near_1(inside, monkeypatch):
    """A near -16 with time steps from 1e-4 to 4 in every head: a position's
    decay from under 1e-20 (exp(-64)) to over 0.998, so l, summed along a
    chunk, reaches hundreds while two neighbours' difference is a
    thousandth. l, the decays and the states in float32 keep the value
    and every gradient at the recurrence's (a bfloat16 l, or a product of
    the time steps rounded to bfloat16, would not)."""
    scan_by(monkeypatch, inside)
    x, dt, A, Bm, Cm = scan_inputs(seed=2)
    dt = jnp.exp(jax.random.uniform(jax.random.PRNGKey(9), dt.shape,
                                    jnp.float32, float(np.log(1e-4)),
                                    float(np.log(4.0))))
    A = -16.0 + jnp.arange(A.shape[0], dtype=jnp.float32) * 0.1
    decay = np.exp(np.asarray(dt) * np.asarray(A))
    assert (decay < 1e-20).any() and (decay > 0.998).any()
    w = jax.random.normal(jax.random.PRNGKey(5), x.shape, jnp.float32)
    score = lambda fn: lambda *a: jnp.sum(w * fn(*a)[0]) + jnp.sum(fn(*a)[1])
    with jax.default_matmul_precision("highest"):
        (want_y, want_last), want = (recurrence(x, dt, A, Bm, Cm),
                                     jax.grad(score(recurrence), range(5))(
                                         x, dt, A, Bm, Cm))
        mine = lambda *a: scan(*a, 8)
        (y, last), got = (mine(x, dt, A, Bm, Cm),
                          jax.grad(score(mine), range(5))(x, dt, A, Bm, Cm))
    np.testing.assert_allclose(y, want_y, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(last, want_last, rtol=2e-4, atol=2e-5)
    for name, a, b in zip(("x", "dt", "A", "B", "C"), got, want):
        np.testing.assert_allclose(
            a, b, rtol=2e-3, atol=2e-5 * float(jnp.max(jnp.abs(b))),
            err_msg=name)


def test_the_rule_runs_the_kernels_at_the_cells_chunks():
    """Shapes alone decide: the cell's chunks of 128 over heads of 64, 8 a
    group, and a state of 128 run as the kernels (and so does a group a
    head); chunks or states that do not tile the chip's lanes, heads that
    do not tile its packed sublanes, or a grid step past the VMEM limit
    run as XLA products."""
    for shape in ((128, 64, 128, 8), (256, 128, 128, 4), (128, 64, 128, 1)):
        assert ssd.inside_kernel(*shape) == 1.0, shape
    for shape in ((8, 8, 16, 2), (64, 64, 128, 8), (128, 64, 64, 8),
                  (128, 40, 128, 8), (128, 64, 128, 64)):
        assert ssd.inside_kernel(*shape) == 0.0, shape


@pytest.mark.parametrize("t, chunk", [(20, 8), (33, 16), (7, 8)])
def test_a_length_that_is_no_multiple_of_the_chunk_is_refused(t, chunk):
    args = scan_inputs(t=t)
    with pytest.raises(ValueError, match=rf"T = {t}.*L = {chunk}"):
        scan(*args, chunk)


def test_group_sizes_are_checked():
    x, dt, A, Bm, Cm = scan_inputs(heads=4, groups=2)
    with pytest.raises(ValueError, match="whole groups"):
        scan(x[:, :, :3], dt[:, :, :3], A[:3], Bm, Cm, 8)


# ---------------------------------------------------------------- the layer
def mamba_layer(**over):
    kw = dict(n_in=64, n_out=64, mamba_num_heads=8, mamba_head_dim=8,
              ssm_state_size=16, n_groups=2, conv_kernel=4, chunk_size=8)
    kw.update(over)
    return mamba2.Mamba2Layer(**kw)


def test_the_mamba2_layer_is_the_references_value_and_every_gradient():
    layer = mamba_layer()
    p = weights()["l0_mixer"]
    x = jax.random.normal(jax.random.PRNGKey(7), (B, T, 64), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(8), (B, T, 64), jnp.float32)
    z, lin = ref.sizes(MODEL), ref.linear(False)
    mine = lambda p, x: jnp.sum(w * layer.forward_with_state(
        p, x, layer.init_state())[0])
    plain = lambda p, x: jnp.sum(w * ref.mamba_mixer(p, x, z, lin)[0])
    with jax.default_matmul_precision("highest"):
        got, said = layer.forward_with_state(p, x, layer.init_state())
        want, theirs = ref.mamba_mixer(p, x, z, lin)
        np.testing.assert_allclose(got, want, **CLOSE)
        g_got = jax.grad(mine, (0, 1))(p, x)
        g_want = jax.grad(plain, (0, 1))(p, x)
    assert sorted(p) == ["A_log", "D", "W_in", "W_out", "b_c", "dt_bias",
                         "w_c", "w_n"]
    for k in p:
        np.testing.assert_allclose(
            g_got[0][k], g_want[0][k], rtol=2e-3,
            atol=2e-5 * float(jnp.max(jnp.abs(g_want[0][k]))), err_msg=k)
        assert float(jnp.max(jnp.abs(g_want[0][k]))) > 0, f"{k} is dead"
    np.testing.assert_allclose(g_got[1], g_want[1], rtol=2e-3, atol=1e-5)
    # what the layer says of its step is the reference's
    for k in ("dt_mean", "decay_min", "state_rms"):
        np.testing.assert_allclose(said[k], theirs[k], rtol=1e-4)
    assert float(said["chunks"]) == T // 8


@pytest.mark.parametrize("fault", [None, "norm_all_channels",
                                   "conv_one_late"])
def test_the_reference_is_the_same_one_group_at_a_time(monkeypatch, fault):
    """The reference runs the mixer a set of groups at a time to fit the
    chip; a set of one and a set of all give one result, value and
    gradient, with and without a planted fault."""
    p = weights()["l0_mixer"]
    x = jax.random.normal(jax.random.PRNGKey(7), (B, T, 64), jnp.float32)
    z, lin = ref.sizes(MODEL), ref.linear(False)
    f = lambda p: jnp.sum(ref.mamba_mixer(p, x, z, lin, fault)[0] ** 2)
    whole, g_whole = jax.value_and_grad(f)(p)
    monkeypatch.setattr(ref, "GROUPS_AT_ONCE", 1)
    assert ref.by_group_set(p, z)["W_in"].shape == (2, 64, 32 + 32 + 32 + 4)
    apart, g_apart = jax.value_and_grad(f)(p)
    np.testing.assert_allclose(apart, whole, rtol=1e-5)
    for k in p:
        np.testing.assert_allclose(
            g_apart[k], g_whole[k], rtol=1e-4,
            atol=1e-5 * float(jnp.max(jnp.abs(g_whole[k]))), err_msg=k)


@pytest.mark.parametrize("fault", ["norm_all_channels", "conv_one_late"])
def test_a_planted_fault_of_the_reference_moves_the_layer(fault):
    """The two faults the calibration plants are faults: each moves the
    reference's own layer far past the program's distance from it."""
    p = weights()["l0_mixer"]
    x = jax.random.normal(jax.random.PRNGKey(7), (B, T, 64), jnp.float32)
    z, lin = ref.sizes(MODEL), ref.linear(False)
    right = ref.mamba_mixer(p, x, z, lin)[0]
    wrong = ref.mamba_mixer(p, x, z, lin, fault)[0]
    assert float(jnp.max(jnp.abs(wrong - right))) \
        > 1e-2 * float(jnp.max(jnp.abs(right)))


@pytest.mark.parametrize("at", [0, 1, 7, 8, 9, 31, 63])
def test_a_position_changes_no_output_before_it(at):
    """Through the convolution's window and the scan, inside a chunk and
    across chunk boundaries (chunks of 8)."""
    layer = mamba_layer()
    p = weights()["l2_mixer"]
    x = jax.random.normal(jax.random.PRNGKey(9), (1, T, 64), jnp.float32)
    run = jax.jit(lambda x: layer.forward_with_state(
        p, x, layer.init_state())[0])
    base, moved = run(x), run(x.at[0, at].add(1.0))
    np.testing.assert_array_equal(moved[0, :at], base[0, :at])
    assert float(jnp.max(jnp.abs(moved[0, at] - base[0, at]))) > 1e-4
    if at + 1 < T:      # and it reaches every later position it should
        assert float(jnp.max(jnp.abs(moved[0, at + 1:] - base[0, at + 1:]))) \
            > 1e-6


def test_the_convolution_is_depthwise_causal_and_four_taps_wide():
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 10, 3), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(2), (3, 4), jnp.float32)
    b = jnp.asarray([0.5, -1.0, 2.0])
    got = np.asarray(ssm_pointwise.causal_depthwise_conv(x, w, b))[0]
    xn, wn = np.asarray(x)[0], np.asarray(w)
    for t in range(10):
        want = np.asarray(b).copy()
        for k in range(4):
            if t - 3 + k >= 0:
                want += wn[:, k] * xn[t - 3 + k]
        np.testing.assert_allclose(got[t], want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got, ref.causal_conv(x[0], w, b), rtol=1e-5,
                               atol=1e-6)


def test_the_gate_comes_before_the_norm_and_the_norm_is_by_group():
    y = jax.random.normal(jax.random.PRNGKey(1), (1, 5, 64), jnp.float32)
    z = jax.random.normal(jax.random.PRNGKey(2), (1, 5, 64), jnp.float32)
    w = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(3), (64,))
    got = np.asarray(ssm_pointwise.gated_group_norm(y, z, w, 2, 1e-5))
    g = np.asarray(y * jax.nn.silu(z))
    for lo in (0, 32):
        run = g[..., lo:lo + 32]
        want = run / np.sqrt((run ** 2).mean(-1, keepdims=True) + 1e-5) \
            * np.asarray(w)[lo:lo + 32]
        np.testing.assert_allclose(got[..., lo:lo + 32], want, rtol=1e-5,
                                   atol=1e-6)


def test_the_mixers_initialisers_are_mamba2s():
    layer = mamba2.Mamba2Layer(n_in=64, n_out=64)
    p = layer.init_params(jax.random.PRNGKey(0))
    assert {k: v.shape for k, v in p.items()} == {
        "W_in": (64, 4096 + 6144 + 64), "W_out": (4096, 64),
        "w_c": (6144, 4), "b_c": (6144,), "dt_bias": (64,), "A_log": (64,),
        "D": (64,), "w_n": (4096,)}
    a = np.exp(np.asarray(p["A_log"]))
    assert a.min() >= 1.0 and a.max() <= 16.0 and a.std() > 1.0
    dt = np.asarray(jax.nn.softplus(p["dt_bias"]))
    assert dt.min() >= 1e-4 * (1 - 1e-4) and dt.max() <= 0.1 * (1 + 1e-4)
    assert np.log(dt).std() > 0.5                   # log-uniform, not a point
    assert np.abs(np.asarray(p["w_c"])).max() <= 0.5
    assert (np.asarray(p["D"]) == 1).all() and (np.asarray(p["w_n"]) == 1).all()


# --------------------------------------------------------------- the experts
def moe_layer(held, first, **over):
    kw = dict(n_in=64, n_out=64, n_experts=16, experts_per_token=4,
              expert_width=32, experts_held=held, first_held=first,
              shared_width=48, routed_scale=2.5, scoring="sigmoid",
              bias_update_rate=0.001, activation="relu2")
    kw.update(over)
    return decoder.MoELayer(**kw)


def test_two_matrix_experts_are_the_references_forward_and_backward():
    """`held_experts_ffn` without a gate matrix against every token through
    every held expert, and the walk's hand-written backward against the
    reference's own gradient, in several blocks."""
    w = weights()["l1_mixer"]
    u = jax.random.normal(jax.random.PRNGKey(3), (B * T, 64), jnp.float32)
    z, lin = ref.sizes(MODEL), ref.linear(False)
    bias = jnp.zeros((16,), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(4), (B * T, 64), jnp.float32)

    def mine(wu, wd, u):
        experts, gates = route_all(w["Wr"], u, 4, True, scoring="sigmoid",
                                   bias=bias)
        return held_experts_ffn(u, experts, gates * 2.5, None, wu, wd, 4, 16,
                                block_rows=32)

    plain = lambda wu, wd, u: ref.routed_part(
        {**w, "Wu": wu, "Wd": wd}, u, z, lin, bias)
    with jax.default_matmul_precision("highest"):
        y, counts, n_run = mine(w["Wu"], w["Wd"], u)
        want, held, _ = plain(w["Wu"], w["Wd"], u)
        np.testing.assert_allclose(y, want, **CLOSE)
        np.testing.assert_array_equal(counts, held)
        assert int(n_run) == -(-int(held.sum()) // 32) > 1
        got = jax.grad(lambda *a: jnp.sum(v * mine(*a)[0]), (0, 1, 2))(
            w["Wu"], w["Wd"], u)
        ref_g = jax.grad(lambda *a: jnp.sum(v * plain(*a)[0]), (0, 1, 2))(
            w["Wu"], w["Wd"], u)
    for name, a, b in zip(("Wu", "Wd", "x"), got, ref_g):
        np.testing.assert_allclose(
            a, b, rtol=2e-3, atol=2e-5 * float(jnp.max(jnp.abs(b))),
            err_msg=name)


def test_a_two_matrix_layer_has_no_gate_leaves_at_all():
    layer = moe_layer(4, 4)
    assert sorted(layer.init_params(jax.random.PRNGKey(0))) == [
        "Sd", "Su", "Wd", "Wr", "Wu"]
    net = ComputationGraph(conf_of()).init()
    for tree in (net._params, net._updater_state):
        assert set(tree["l1_mixer"]) == {"Wr", "Wu", "Wd", "Su", "Sd"}
    assert net._model_state["l1_mixer"]["bias"].shape == (16,)
    # and the gated form keeps its seven
    gated = moe_layer(4, 4, activation=None)
    assert sorted(gated.init_params(jax.random.PRNGKey(0))) == [
        "Sd", "Sg", "Su", "Wd", "Wg", "Wr", "Wu"]


def test_the_gated_expert_layer_lowers_as_the_parents():
    layer = decoder.MoELayer(
        n_in=64, n_out=64, n_experts=8, experts_per_token=2, expert_width=32,
        experts_held=4, first_held=2, shared_width=32, routed_scale=2.5,
        scoring="sigmoid", bias_update_rate=0.001)
    p = layer.init_params(jax.random.PRNGKey(0))

    def loss(p, x):
        y, st = layer.forward_with_state(p, x, layer.init_state(),
                                         train=True)
        return jnp.sum(y * y) + jnp.sum(st["held_pairs"])

    text = jax.jit(jax.value_and_grad(loss)).lower(
        p, jnp.zeros((2, 32, 64), jnp.float32)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == GATED_MOE_SHA256


def test_the_shares_of_the_experts_add_up_to_the_whole_layer():
    """Four shares of four experts each, computed by the program's layer
    with ITS experts' weights; every share computes the shared expert
    alike, so it is counted once: the sum is the uncut reference's layer
    over all 16, under a bias that is not nought."""
    w = weights(WHOLE)["l1_mixer"]
    u = jax.random.normal(jax.random.PRNGKey(3), (B, T, 64), jnp.float32)
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(9), (16,),
                                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        z, lin = ref.sizes(WHOLE), ref.linear(False)
        flat = u.reshape(B * T, 64)
        want, counts, every = ref.experts_part(w, flat, z, lin, bias)
        alone = ref.relu2_mlp(flat, w["Su"], w["Sd"], lin)
        total = 0.0
        for first in range(0, 16, 4):
            layer = moe_layer(4, first)
            share = {**{k: w[k] for k in ("Wr", "Su", "Sd")},
                     **{k: w[k][first:first + 4] for k in ("Wu", "Wd")}}
            y, st = layer.forward_with_state(
                share, u, {**layer.init_state(), "bias": bias})
            np.testing.assert_array_equal(st["held_pairs"],
                                          counts[first:first + 4])
            total = total + y.reshape(B * T, 64) - alone
    np.testing.assert_allclose(total + alone, want, **CLOSE)
    np.testing.assert_array_equal(every, counts)
    assert int(counts.sum()) == B * T * 4
    assert float(jnp.max(jnp.abs(alone))) > 1e-2


# ---------------------------------------------------------------- attention
def test_attention_without_turn_or_gate_is_the_references(monkeypatch):
    """16 query heads... here 4 a key/value head, in blocks of 32 so that
    the walk has runs of several tiles."""
    monkeypatch.setattr(sa, "BLOCK", 32)
    layer = decoder.AttentionLayer(n_in=64, n_out=64, n_heads=8,
                                   n_kv_heads=2, head_dim=16,
                                   rope_theta=None, gate=False)
    p = weights()["l5_mixer"]
    assert sorted(p) == sorted(layer.init_params(jax.random.PRNGKey(0))) \
        == ["Wk", "Wo", "Wq", "Wv"]
    x = jax.random.normal(jax.random.PRNGKey(7), (B, T, 64), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(8), (B, T, 64), jnp.float32)
    z, lin = ref.sizes(MODEL), ref.linear(False)
    mine = lambda p, x: jnp.sum(w * layer.forward_with_state(
        p, x, layer.init_state())[0])
    plain = lambda p, x: jnp.sum(w * ref.attention(p, x, z, lin))
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            layer.forward_with_state(p, x, layer.init_state())[0],
            ref.attention(p, x, z, lin), **CLOSE)
        g_got, g_want = (jax.grad(f, (0, 1))(p, x) for f in (mine, plain))
    for k in p:
        np.testing.assert_allclose(
            g_got[0][k], g_want[0][k], rtol=2e-3,
            atol=1e-5 * float(jnp.max(jnp.abs(g_want[0][k]))), err_msg=k)
    np.testing.assert_allclose(g_got[1], g_want[1], rtol=2e-3, atol=1e-5)
    # no positions: a permutation of the EARLIER positions leaves the last
    # position's output as it was
    perm = jnp.concatenate([jnp.arange(T - 1)[::-1], jnp.asarray([T - 1])])
    run = lambda x: layer.forward_with_state(p, x, layer.init_state())[0]
    np.testing.assert_allclose(run(x[:, perm])[:, -1], run(x)[:, -1],
                               rtol=1e-4, atol=1e-5)


def test_sixteen_query_heads_read_one_key_value_head():
    """The cell's head geometry (32 over 2) at a small width: query head h
    reads key/value head h // 16."""
    q, k, v = (jax.random.normal(kk, s, jnp.float32) for kk, s in zip(
        jax.random.split(jax.random.PRNGKey(0), 3),
        ((1, 32, 64, 8), (1, 2, 64, 8), (1, 2, 64, 8))))
    o, _ = sa.masked_attention(q, k, v, None, 8 ** -0.5, 32, 32)
    s = jnp.einsum("bhtd,bhsd->bhts", q, jnp.repeat(k, 16, 1)) * 8 ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((64, 64), bool)), s, -jnp.inf)
    want = jnp.einsum("bhts,bhsd->bhtd", jax.nn.softmax(s, -1),
                      jnp.repeat(v, 16, 1))
    np.testing.assert_allclose(o, want, rtol=1e-4, atol=1e-5)


def test_the_turned_gated_attention_layer_lowers_as_the_parents():
    layer = decoder.AttentionLayer(
        n_in=64, n_out=64, n_heads=6, n_kv_heads=2, head_dim=16,
        rope_theta=500000.0, rotary_dim=8,
        yarn=(64, 4096, 64, 1, 1.4158883083359672))
    p = layer.init_params(jax.random.PRNGKey(0))
    assert sorted(p) == ["Wgate", "Wk", "Wo", "Wq", "Wv"]

    def loss(p, x):
        y, _ = layer.forward_with_state(p, x, layer.init_state())
        return jnp.sum(y * y)

    text = jax.jit(jax.value_and_grad(loss)).lower(
        p, jnp.zeros((2, 32, 64), jnp.float32)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == \
        GATED_ATTENTION_SHA256


# ------------------------------------------------------- three steps of fit
@pytest.fixture(scope="module")
def followed():
    """Three steps of `fit` on three batches, and the reference's."""
    w = weights()
    batches = [batch_of(i) for i in range(3)]
    net = trainer(w)
    got = {"losses": []}
    for i, b in enumerate(batches):
        net.fit(mds_of(b))
        got["losses"].append(float(net._score))
        if i == 0:
            got["m1"] = jax.tree.map(np.asarray, net._updater_state)
            got["gauges"] = net.publish_layer_gauges()
    got["params"] = jax.tree.map(np.asarray, net._params)
    got["bias"] = np.stack([net._model_state[n]["bias"] for n in SPARSE])
    with jax.default_matmul_precision("highest"):
        want = {}
        (_, want["aux1"]), want["g1"] = jax.value_and_grad(
            ref.loss, has_aux=True)(w, batches[0], MODEL)
        # the reference's own three steps (it is handed a copy: it donates)
        losses, g1, change, aux = ref.train_steps(
            jax.tree.map(jnp.array, w), batches, MODEL, TRAINER,
            remake=lambda: w)
        want.update(losses=np.asarray(losses), change=np.asarray(change),
                    bias=np.asarray(aux["bias"]))
        got["change"] = np.asarray(ref.leaf_norms(
            jax.tree.map(lambda a, b: a - b, got["params"], w)))
    return got, want


@pytest.mark.parametrize("what", ["loss", "gradient", "three_adam_steps",
                                  "counters", "bias", "ssm_gauges"])
def test_fit_agrees_with_the_reference(followed, what):
    got, want = followed
    if what == "loss":
        np.testing.assert_allclose(got["losses"], want["losses"], rtol=1e-5)
    elif what == "gradient":
        # the first gradient as Adam got it: m1 = 0.1 g, every leaf
        for n, leaves in want["g1"].items():
            for k, g in leaves.items():
                np.testing.assert_allclose(
                    got["m1"][n][k]["m"] / 0.1, g, rtol=2e-3,
                    atol=2e-5 * float(jnp.max(jnp.abs(g))) + 1e-9,
                    err_msg=f"{n}.{k}")
                assert float(jnp.max(jnp.abs(g))) > 0, f"{n}.{k} is dead"
    elif what == "three_adam_steps":
        np.testing.assert_allclose(got["change"], want["change"], rtol=2e-3)
    elif what == "counters":
        held = np.asarray(want["aux1"]["held_pairs"])       # [4 layers, 4]
        for name, row in zip(SPARSE, held):
            g = lambda leaf: got["gauges"][f"moe.{name}.{leaf}"]
            assert g("held_pairs_max") == row.max()
            assert g("held_pairs_mean") == pytest.approx(row.mean())
            assert g("absent_pairs") + row.sum() == B * T * 4
            assert g("bias_abs_max") == pytest.approx(0.001)
        assert got["gauges"][
            "attention.l5_mixer.attend_grid_steps_per_tile"] == 1
        assert got["gauges"]["attention.l5_mixer.attend_backward_passes"] == 1
    elif what == "bias":
        # after three steps every entry is within 3 gamma of zero and is
        # the reference's; it moved, and not all one way
        np.testing.assert_allclose(got["bias"], want["bias"], atol=1e-7)
        assert np.abs(got["bias"]).max() <= 0.003 + 1e-7
        assert (got["bias"] > 0).any() and (got["bias"] < 0).any()
    else:
        for name, said in zip(MAMBA, want["aux1"]["ssm"]):
            g = lambda leaf: got["gauges"][f"mamba2.{name}.{leaf}"]
            assert g("chunks") == T // 8
            assert g("ssd_kernel") == 0.0       # chunks of 8: XLA's products
            assert g("pointwise_kernel") == 0.0     # T = 64: the XLA forms
            for leaf in ("dt_mean", "decay_min", "state_rms"):
                assert g(leaf) == pytest.approx(float(said[leaf]), rel=1e-4)
            assert 0 < g("decay_min") < 1 and g("state_rms") > 0


# ------------------------------------------------------- the lowered step
@pytest.fixture(scope="module")
def lowered():
    net = ComputationGraph(conf_of()).init()
    return net.lower_step(mds_of(batch_of(0))).as_text(debug_info=True)


def paths_of(text):
    return set(re.findall(r'loc\("([^"]*/[^"]*)"', text))


def test_configuration_names_its_kinds_and_the_configs_keys():
    conf = conf_of()
    kinds = {s.conf.layer_type for s in conf.vertices.values() if s.is_layer}
    assert kinds == {"tokenembedding", "rmsnorm", "mamba2", "moe",
                     "attention", "lmhead"}
    by_kind = lambda kind: [n for n, s in conf.vertices.items()
                            if s.is_layer and s.conf.layer_type == kind]
    assert tuple(by_kind("mamba2")) == MAMBA
    assert tuple(by_kind("moe")) == SPARSE
    assert by_kind("attention") == ["l5_mixer"]
    m = conf.vertices["l0_mixer"].conf
    assert (m.mamba_num_heads, m.mamba_head_dim, m.ssm_state_size,
            m.n_groups, m.conv_kernel, m.chunk_size, m.eps) == (
                8, 8, 16, 2, 4, 8, 1e-5)
    moe = conf.vertices["l1_mixer"].conf
    assert (moe.scoring, moe.bias_update_rate, moe.shared_width,
            moe.routed_scale, moe.activation) == (
                "sigmoid", 0.001, 48, 2.5, "relu2")
    attn = conf.vertices["l5_mixer"].conf
    assert attn.rope_theta is None and attn.gate is False
    # each layer is one mixer under a pre-norm and an add
    for i in range(9):
        assert conf.vertices[f"l{i}_mixer"].inputs == [f"l{i}_norm"]
        assert set(conf.vertices[f"l{i}_add"].inputs) == {
            f"l{i}_mixer", "embed" if i == 0 else f"l{i - 1}_add"}
    twin = type(conf).from_json(conf.to_json())
    assert twin.to_dict() == conf.to_dict()
    # the published model: 23 M, 23 E, 6 *
    from deeplearning4j_tpu.models.zoo.nemotron_h import PATTERN
    assert (len(PATTERN), PATTERN.count("M"), PATTERN.count("E"),
            PATTERN.count("*")) == (52, 23, 23, 6)
    assert PATTERN[:9] == "MEMEM*EME"
    with pytest.raises(ValueError, match="one of"):
        conf_of(hybrid_override_pattern="MEX")


def test_the_scopes_are_on_the_lowered_step_forward_and_backward(lowered):
    paths = paths_of(lowered)
    for scope in ("ssm_proj", "ssm_conv", "ssd", "ssm_norm"):
        mine = {p for p in paths if f"/{scope}/" in p}
        assert any("transpose(" in p for p in mine), scope     # a backward
        assert any("transpose(" not in p for p in mine), scope  # a forward
        for v in MAMBA:
            assert any(f"mamba2.{v}" in p for p in mine), (scope, v)
    # everything a `mamba2` layer traces is under one of its four scopes
    inside = {p for p in paths if re.search(r"mamba2\.l\d_mixer", p)}
    assert inside and all(re.search(r"/(ssm_proj|ssm_conv|ssd|ssm_norm)/", p)
                          for p in inside)
    for scope, kind in (("router", "moe"), ("shared", "moe"),
                        ("experts", "moe"), ("attend_full", "attention")):
        mine = {p for p in paths if f"/{scope}/" in p}
        assert any("transpose(" in p for p in mine), scope
        assert any(f"{kind}." in p for p in mine), scope
    assert not any("/rotary/" in p or "/gate/" in p for p in paths)
    kernels = {p for p in paths if p.endswith("/pallas_call")}
    assert kernels and all("/attend_full/" in p for p in kernels)


@pytest.fixture(scope="module")
def lowered_kernels():
    """The same step with the scan's rule saying 1, as the cell's shapes
    make it say: its kernels are interpreted here."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ssd, "inside_kernel", lambda *shape: 1.0)
        net = ComputationGraph(conf_of()).init()
        return net.lower_step(mds_of(batch_of(0))).as_text(debug_info=True)


def test_the_kernels_carry_the_ssd_scope_forward_and_backward(
        lowered_kernels):
    """`train_ssd_device_ms` sums what runs under `ssd`: each layer's
    `ssd_fwd` is there in the step's forward and again in its
    rematerialised segment, its `ssd_bwd` in the transpose, and every
    kernel of the step is the scan's or the attention's."""
    kernels = {p for p in paths_of(lowered_kernels)
               if p.endswith("/pallas_call")}
    for v in MAMBA:
        mine = {p for p in kernels if f"mamba2.{v}" in p}
        fwd = {p for p in mine if p.endswith("/ssd/ssd_fwd/pallas_call")}
        bwd = {p for p in mine if p.endswith("/ssd/ssd_bwd/pallas_call")}
        assert len(fwd) == 2 and len(bwd) == 1 and fwd | bwd == mine, v
        assert all("transpose(" in p for p in bwd), v
        assert sum("/rematted_computation/" in p for p in fwd) == 1, v
    assert all(re.search(r"/(ssd|attend_full)/", p) for p in kernels)


@pytest.mark.parametrize("scope", ["ssm_conv", "ssm_norm"])
def test_the_pointwise_kernels_carry_their_scopes_forward_and_backward(
        scope):
    """At widths that tile (heads of 16 and a state of 32: a convolution of
    256 channels read 128 into the in-projection, groups of 64; T = 128)
    `train_ssm_pointwise_device_ms` sums what runs under `ssm_conv` and
    `ssm_norm`: each layer's forward kernel is there in the step's forward
    and again in its rematerialised segment, its backward kernel in the
    transpose, and nothing else of either scope is a kernel."""
    net = ComputationGraph(conf_of(mamba_head_dim=16,
                                   ssm_state_size=32)).init()
    text = net.lower_step(mds_of(batch_of(0, t=128))).as_text(
        debug_info=True)
    kernels = {p for p in paths_of(text) if p.endswith("/pallas_call")}
    for v in MAMBA:
        mine = {p for p in kernels
                if f"mamba2.{v}" in p and f"/{scope}/" in p}
        fwd = {p for p in mine if p.endswith(f"/{scope}_fwd/pallas_call")}
        bwd = {p for p in mine if p.endswith(f"/{scope}_bwd/pallas_call")}
        assert len(fwd) == 2 and len(bwd) == 1 and fwd | bwd == mine, v
        assert all("transpose(" in p for p in bwd), v
        assert sum("/rematted_computation/" in p for p in fwd) == 1, v


def test_the_ssd_kernel_gauge_carries_the_rule(monkeypatch):
    """The layer says whether its scan ran as the kernels: the rule's
    answer for its own chunk, head, state and heads a group (chunks of 8,
    heads of 8, a state of 16, 4 heads a group: 0.0, the XLA products; 1.0
    where the rule says 1; the cell's geometry gives 1.0, as
    `test_the_rule_runs_the_kernels_at_the_cells_chunks` shows)."""
    layer = mamba_layer()
    p = weights()["l0_mixer"]
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 64), jnp.float32)
    said = lambda: float(layer.forward_with_state(
        p, x, layer.init_state())[1]["ssd_kernel"])
    assert said() == 0.0
    asked = []
    monkeypatch.setattr(ssd, "inside_kernel",
                        lambda *shape: asked.append(shape) or 1.0)
    assert said() == 1.0 and set(asked) == {(8, 8, 16, 4)}


def test_nothing_of_size_t_by_t_is_made_outside_the_attention(monkeypatch):
    """T = 96 (no width of the model) in chunks of 8 with the attention in
    blocks of 32: no array has two axes of 96 (nor one of 96 x 96 folded),
    in the whole step, and none has a state a position."""
    monkeypatch.setattr(sa, "BLOCK", 32)
    net = ComputationGraph(conf_of()).init()
    text = net.lower_step(mds_of(batch_of(0, t=96, rows=1))).as_text()
    assert re.search(r"tensor<(\d+x)*8x8xf32>", text)          # a chunk's pairs
    assert re.search(r"tensor<1x12x8x8x16xf32>", text)         # chunk states
    assert not re.search(r"tensor<(\d+x)*96x96x", text)
    assert not re.search(r"tensor<(\d+x)*9216x", text)
    assert not re.search(r"tensor<(\d+x)*96x8x8x16x", text)


def test_the_kept_arrays_are_the_attentions_and_the_chunk_states(lowered):
    """`mamba2` names the states entering each chunk, `attention` the
    kernel's o and lse: the forward loop over chunks and the forward kernel
    are in the step once a layer, outside their segment's
    rematerialisation."""
    paths = paths_of(lowered)
    again = {p for p in paths if "/rematted_computation/" in p}
    fwd = {p for p in paths if p.endswith("sparse_attention_fwd/pallas_call")}
    assert len(fwd) == 1 and not fwd & again
    for v in MAMBA:
        mine = {p for p in paths if f"mamba2.{v}" in p}
        assert any("/ssd/" in p for p in mine & again), v   # the chunks again
        loops = {p for p in mine if p.endswith("/while")}
        assert loops and not any("transpose(" not in p for p in loops & again)
    from deeplearning4j_tpu import obs
    net = ComputationGraph(conf_of()).init()
    net._remat_plan()
    names = [n for n, s in net.conf.vertices.items()
             if s.is_layer and s.conf.remat_keeps()]
    assert sorted(names) == sorted(MAMBA + ("l5_mixer",))
    assert obs.default_registry().gauge(
        "train.remat_kept_segments").value == len(names)
