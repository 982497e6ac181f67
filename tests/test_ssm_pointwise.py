"""The Mamba-2 layer's convolution and gated norm as Pallas kernels
(ops/ssm_pointwise.py), interpreted here, against the XLA forms they replace
(`causal_depthwise_conv`, `gated_group_norm`, which tests/test_nemotron_h.py
holds to the plain reference): the forward and every gradient in float32 and
bfloat16, one block and three, channels read at an offset into a wider array
(the in-projection's layout), the shapes that fall back, the rules at the
cell's widths, and a whole `mamba2` layer with both kernels against the same
layer in XLA."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from deeplearning4j_tpu.nn.conf.layers import mamba2               # noqa: E402
from deeplearning4j_tpu.ops import ssm_pointwise as sp             # noqa: E402

K = 4
# float32 against float32: the kernels sum in another order than XLA (a
# weight's gradient is a sum over every row and position: entries that
# nearly cancel keep an absolute error of the sum's size); bfloat16: the
# outputs round to bf16 on both sides, from float32 sums
CLOSE = {jnp.float32: (2e-5, 2e-6), jnp.bfloat16: (2e-2, 1e-2)}


def assert_close(got, want, dtype, name):
    """Within CLOSE[dtype]: (rtol, atol as a share of want's largest)."""
    rtol, atol = CLOSE[dtype]
    want = np.asarray(want, np.float32)
    np.testing.assert_allclose(np.asarray(got, np.float32), want, rtol=rtol,
                               atol=atol * float(np.abs(want).max()),
                               err_msg=name)


def rel(a, b):
    a, b = (np.asarray(v, np.float32) for v in (a, b))
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def pallas_calls(fn, *args):
    """{kernel name: its pallas_calls} in the jaxpr of fn(*args)."""
    out = {}

    def walk(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                out[e.params["name"]] = out.get(e.params["name"], 0) + 1
            for sub in jax.core.jaxprs_in_params(e.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return out


def both_ways(fn, args, ct):
    """fn's value and its VJP against the cotangent ct, float32 out."""
    out, vjp = jax.vjp(fn, *args)
    return [out] + list(vjp(ct.astype(out.dtype)))


def conv_args(dtype, C, T, lo, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (2, T, lo + C + 64), jnp.float32)
    w = jax.random.uniform(ks[1], (C, K), jnp.float32, -0.5, 0.5)
    b = jax.random.uniform(ks[2], (C,), jnp.float32, -0.5, 0.5)
    ct = jax.random.normal(ks[3], (2, T, C), jnp.float32)
    return [a.astype(dtype) for a in (x, w, b)], ct


def conv_xla(x, w, b, lo):
    C = w.shape[0]
    return jax.nn.silu(sp.causal_depthwise_conv(
        x[..., lo:lo + C], w, b)).astype(x.dtype)


# (C, T, positions a block, offset of the first channel): one block of one
# lane tile; three blocks of one tile; three blocks of two tiles, the
# channels 128 into a wider array (the halo and the carry cross every block
# edge, and the channel blocks are 128 where 256 do not divide the offset)
CONV_CASES = [(256, 128, 2048, 0), (384, 384, 128, 0), (256, 768, 256, 128),
              (384, 384, 128, 256)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("C,T,lanes,lo", CONV_CASES,
                         ids=[f"C{c}-T{t}-by{n}-at{lo}"
                              for c, t, n, lo in CONV_CASES])
def test_the_convolution_kernels_are_the_xla_form(monkeypatch, dtype, C, T,
                                                  lanes, lo):
    monkeypatch.setattr(sp, "CONV_LANES", lanes)
    assert sp.conv_inside_kernel(C, T, K, lo) == 1.0
    (x, w, b), ct = conv_args(dtype, C, T, lo)
    mine = lambda x, w, b: sp.conv_silu(x, w, b, lo=lo)
    assert pallas_calls(lambda *a: both_ways(mine, a, ct), x, w, b) == {
        "ssm_conv_fwd": 1, "ssm_conv_bwd": 1}
    got = both_ways(mine, (x, w, b), ct)
    want = both_ways(lambda x, w, b: conv_xla(x, w, b, lo), (x, w, b), ct)
    for name, g, v in zip(("out", "dx", "dw", "db"), got, want):
        assert g.dtype == v.dtype and g.shape == v.shape, name
        assert_close(g, v, dtype, name)
        assert rel(g, v) < (1e-6 if dtype == jnp.float32 else 1e-2), name
    # the channels outside [lo, lo + C) get no gradient
    dx = np.asarray(got[1], np.float32)
    assert not dx[..., :lo].any() and not dx[..., lo + C:].any()


def norm_args(dtype, width, T, wider, seed=1):
    """y, the gate z (`wider` channels more than y: the gate is its first)
    and w, and a cotangent."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    y = jax.random.normal(ks[0], (2, T, width), jnp.float32)
    z = jax.random.normal(ks[1], (2, T, width + wider), jnp.float32)
    w = 1.0 + 0.1 * jax.random.normal(ks[2], (width,), jnp.float32)
    ct = jax.random.normal(ks[3], (2, T, width), jnp.float32)
    return [a.astype(dtype) for a in (y, z, w)], ct


# (width, groups, T, positions a block, channels of the gate's array past
# the gate): one block; three blocks, the gate the first of a wider array
# (the in-projection's layout); groups of 16
NORM_CASES = [(512, 2, 128, 1024, 0), (256, 2, 384, 128, 192),
              (128, 8, 256, 128, 0)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("width,groups,T,lanes,wider", NORM_CASES,
                         ids=[f"W{w}-G{g}-T{t}-by{n}-more{m}"
                              for w, g, t, n, m in NORM_CASES])
def test_the_norm_kernels_are_the_xla_form(monkeypatch, dtype, width, groups,
                                           T, lanes, wider):
    monkeypatch.setattr(sp, "NORM_BLOCK", width // groups * lanes)
    assert sp.norm_inside_kernel(width, groups, T) == 1.0
    (y, z, w), ct = norm_args(dtype, width, T, wider)
    mine = lambda y, z, w: sp.gated_norm(y, z, w, groups, 1e-5)
    assert pallas_calls(lambda *a: both_ways(mine, a, ct), y, z, w) == {
        "ssm_norm_fwd": 1, "ssm_norm_bwd": 1}
    got = both_ways(mine, (y, z, w), ct)
    want = both_ways(lambda y, z, w: sp.gated_group_norm(
        y, z[..., :width], w, groups, 1e-5), (y, z, w), ct)
    for name, g, v in zip(("out", "dy", "dz", "dw"), got, want):
        assert g.dtype == v.dtype and g.shape == v.shape, name
        assert_close(g, v, dtype, name)
        assert rel(g, v) < (1e-6 if dtype == jnp.float32 else 1e-2), name
    assert not np.asarray(got[2], np.float32)[..., width:].any()


# shapes that do not tile the chip: two positions past a block edge (fewer
# than the K - 1 a tap reaches back), channels no whole block of 128, an
# offset off the blocks; a group no whole packed sublanes, a group wider than
# a block holds, positions no whole lane tile
FALLBACKS = {
    "conv_T130": ("conv", dict(C=256, T=130, lo=0)),
    "conv_C200": ("conv", dict(C=200, T=128, lo=0)),
    "conv_at64": ("conv", dict(C=256, T=128, lo=64)),
    "norm_group24": ("norm", dict(width=48, groups=2, T=128)),
    "norm_group8192": ("norm", dict(width=8192, groups=1, T=128)),
    "norm_T100": ("norm", dict(width=256, groups=2, T=100)),
}


@pytest.mark.parametrize("case", sorted(FALLBACKS))
def test_shapes_that_do_not_tile_fall_back_to_the_xla_form(case):
    op, kw = FALLBACKS[case]
    if op == "conv":
        C, T, lo = kw["C"], kw["T"], kw["lo"]
        assert sp.conv_inside_kernel(C, T, K, lo) == 0.0
        args, ct = conv_args(jnp.float32, C, T, lo)
        mine = lambda x, w, b: sp.conv_silu(x, w, b, lo=lo)
        xla = lambda x, w, b: conv_xla(x, w, b, lo)
    else:
        width, groups, T = (kw[k] for k in ("width", "groups", "T"))
        assert sp.norm_inside_kernel(width, groups, T) == 0.0
        args, ct = norm_args(jnp.float32, width, T, 64)
        mine = lambda y, z, w: sp.gated_norm(y, z, w, groups, 1e-5)
        xla = lambda y, z, w: sp.gated_group_norm(
            y, z[..., :width], w, groups, 1e-5)
    assert pallas_calls(lambda *a: both_ways(mine, a, ct), *args) == {}
    for g, v in zip(both_ways(mine, args, ct), both_ways(xla, args, ct)):
        assert_close(g, v, jnp.float32, case)


def test_the_rules_run_the_kernels_at_the_cells_widths():
    """`train-ssm16k`: 6,144 channels 4,096 into the in-projection, T =
    16,384, 4 taps; 4,096 channels in 8 groups of 512. The layers of
    tests/test_nemotron_h.py (T = 64, 64 inner channels) run XLA."""
    assert sp.conv_inside_kernel(6144, 16384, 4, 4096) == 1.0
    assert sp.norm_inside_kernel(4096, 8, 16384) == 1.0
    assert sp.conv_inside_kernel(128, 64, 4, 64) == 0.0
    assert sp.norm_inside_kernel(64, 2, 64) == 0.0


# a `mamba2` layer whose widths tile: 8 heads of 16 (128 inner channels), 2
# groups of 64, a state of 32 (a convolution of 256 channels read 128 into
# the in-projection), T = 256 in chunks of 8
LAYER = dict(n_in=64, n_out=64, mamba_num_heads=8, mamba_head_dim=16,
             ssm_state_size=32, n_groups=2, conv_kernel=4, chunk_size=8)


@pytest.fixture(scope="module")
def layer_both_ways():
    layer = mamba2.Mamba2Layer(**LAYER)
    p = layer.init_params(jax.random.PRNGKey(3))
    p["w_n"] = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(4), (128,))
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 256, 64), jnp.float32)
    ct = jax.random.normal(jax.random.PRNGKey(6), (1, 256, 64), jnp.float32)

    def run():
        f = lambda p, x: layer.forward_with_state(p, x, layer.init_state())
        (out, said), vjp = jax.vjp(f, p, x)
        return out, said, vjp((ct, jax.tree.map(jnp.zeros_like, said)))

    with jax.default_matmul_precision("highest"):
        kernels = run()
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(sp, "conv_inside_kernel", lambda *shape: 0.0)
            mp.setattr(sp, "norm_inside_kernel", lambda *shape: 0.0)
            xla = run()
    return kernels, xla


@pytest.mark.parametrize("what", ["value", "gradients", "gauge"])
def test_a_layer_with_both_kernels_is_the_layer_in_xla(layer_both_ways, what):
    (out, said, (gp, gx)), (out_x, said_x, (gp_x, gx_x)) = layer_both_ways
    if what == "value":
        np.testing.assert_allclose(out, out_x, rtol=1e-5, atol=1e-6)
    elif what == "gradients":
        np.testing.assert_allclose(gx, gx_x, rtol=1e-5, atol=1e-6)
        for k in gp:
            assert rel(gp[k], gp_x[k]) < 1e-5, k
            assert float(jnp.max(jnp.abs(gp_x[k]))) > 0, f"{k} is dead"
    else:
        assert float(said["pointwise_kernel"]) == 1.0
        assert float(said_x["pointwise_kernel"]) == 0.0
