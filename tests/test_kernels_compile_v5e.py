"""Kernels of the main path compiled at a cell's real widths for a TPU v5e
that is described and not attached: what Mosaic refuses (a slice off the
tiling, more VMEM than a kernel may take), it refuses here, at no chip time;
interpret mode passes both. Nothing runs, so nothing here is a result or a
time. One file on purpose: the worker that is given it loads the TPU's
library, inside a fixture, and no other does."""
import os
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from deeplearning4j_tpu.nn.conf.layers import decoder            # noqa: E402
from deeplearning4j_tpu.ops import grouped_matmul as gm          # noqa: E402
from deeplearning4j_tpu.ops import sparse_attention as sa         # noqa: E402
from deeplearning4j_tpu.ops import ssm_pointwise as sp            # noqa: E402
from deeplearning4j_tpu.ops.sparse_attention import (             # noqa: E402
    index_scores_bwd)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_on_the_chip():
    """Such a compile is written to the persistent cache and cannot be read
    back without a chip: the cache is off around it. So is the suite's x64,
    which no cell runs under and Mosaic's lowering does not take."""
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with jax.enable_x64(False):
        yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


# `train-vl8k`'s indexer: a chunk of 512 queries, 16 heads of 64, against
# its prefix of S keys; the first chunk, a prefix that is no whole number
# of 1,024-key blocks, the last chunk at the module's own block
@pytest.mark.parametrize("S,block_k", [(512, None), (1536, 1024),
                                       (8192, None)])
def test_index_scores_bwd_compiles_at_the_cells_shapes(one_chip,
                                                       as_on_the_chip, S,
                                                       block_k):
    C, HI, DI = 512, 16, 64
    shape = lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype,
                                                     sharding=one_chip)
    compiled = jax.jit(lambda qi, ki, w, g: index_scores_bwd(
        qi, ki, w, g, block_k=block_k, interpret=False)).lower(
            shape((C, HI, DI), jnp.bfloat16), shape((S, DI), jnp.bfloat16),
            shape((C, HI), jnp.bfloat16), shape((C, S), jnp.float32)
        ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "sparse_attention_index_bwd" in text
    # nothing of a head's dots leaves the kernel
    assert f"[{S},{C},{HI}]" not in text and f"[{HI},{C},{S}]" not in text


# the one-pass backward (PR 37) a sequence of each decoder cell's attention:
# (T, query heads, key/value heads, window); `mask` is `train-vl8k`'s (a
# mask operand), `causal` and `window` `train-lc16k`'s full and window
# layers, `latent` `train-mtp8k`'s (64 more slots against one shared key)
BWD_SHAPES = {"mask": (8192, 32, 4, None), "causal": (16384, 48, 8, None),
              "window": (16384, 64, 8, 512), "latent": (8192, 32, 32, None)}


@pytest.mark.parametrize("schedule", sorted(BWD_SHAPES))
def test_the_one_pass_backward_compiles_at_the_cells_shapes(
        one_chip, as_on_the_chip, schedule):
    """Its slabs of all T keys, the output blocks' two buffers and four
    float32 tiles fit `BWD_VMEM` at the module's own blocks, and the call
    is one kernel: no dQ's, no dK/dV's."""
    T, H, KV, window = BWD_SHAPES[schedule]
    d, d2 = 128, 64 if schedule == "latent" else 0
    assert sa.backward_passes(T, d, d, d2) == 1
    shape = lambda dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_chip)
    more = {"mask": [shape((1, T, T), jnp.int8)],
            "latent": [shape((H, T, d2)), shape((1, T, d2))]}.get(
                schedule, [])

    def bwd(q, k, v, o, lse, do, *more):
        return sa._bwd(q, k, v, more[0] if schedule == "mask" else None, o,
                       lse, do, (d + d2) ** -0.5,
                       *sa._blocks(T, None, None, window), False, window,
                       tuple(more) if schedule == "latent" else ())

    text = jax.jit(bwd).lower(
        shape((H, T, d)), shape((KV, T, d)), shape((KV, T, d)),
        shape((H, T, d)), shape((H, T, 1), jnp.float32), shape((H, T, d)),
        *more).compile().as_text()
    assert "tpu_custom_call" in text and "sparse_attention_bwd" in text
    assert "sparse_attention_dq" not in text
    assert "sparse_attention_dkv" not in text


# `train-vl8k`'s indexer target: one row, 32 query heads over 4 key/value
# heads of 128, T = 8,192, at the module's blocks and at the largest that
# `tools/attend_kernel_times.py` is asked to time
@pytest.mark.parametrize("block", [(None, None), (512, 1024)])
def test_the_head_sum_kernel_compiles_at_the_cells_shapes(
        one_chip, as_on_the_chip, block):
    """Its blocks, lse spread across the lanes and the unrolled parts of a
    tile fit `HEAD_SUM_VMEM`, and the call is one kernel."""
    T, H, KV, d = 8192, 32, 4, 128
    shape = lambda dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_chip)
    text = jax.jit(lambda q, k, lse, mask: sa.head_summed_probs(
        q, k, lse, mask, d ** -0.5, *block, interpret=False)).lower(
            shape((1, H, T, d)), shape((1, KV, T, d)),
            shape((1, H, T), jnp.float32), shape((1, T, T), jnp.int8)
        ).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert "sparse_attention_head_sum" in text


def test_a_step_lowers_the_head_sum_kernel_once(one_chip, as_on_the_chip,
                                                monkeypatch):
    """Calls of the same shapes, each in a segment of its own that the
    backward rematerialises, as `_row` calls it a layer: the lowered text
    holds the kernel once, not once a call, however many calls there are."""
    monkeypatch.setattr(sa, "_resolve_interpret", lambda interpret: False)
    T, H, KV, d = 1024, 32, 4, 128
    shape = lambda dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_chip)

    def kernels(n):
        def loss(q, k, lse, mask):
            out = jnp.zeros((), jnp.float32)
            for i in range(n):
                def one(q):
                    sg = jax.lax.stop_gradient
                    target = sa.head_summed_probs(sg(q), k, lse, mask,
                                                  d ** -0.5)
                    return jnp.sum(target * q[0, 0, :, :1].astype(
                        jnp.float32))
                out = out + jax.checkpoint(one)(q * (i + 1))
            return out
        return jax.jit(jax.grad(loss)).lower(
            shape((1, H, T, d)), shape((1, KV, T, d)),
            shape((1, H, T), jnp.float32), shape((1, T, T), jnp.int8)
        ).as_text().count("tpu_custom_call")

    assert kernels(1) == 1
    assert kernels(3) == 1


# `train-ssm16k`'s convolution (4 taps over 6,144 channels read 4,096 into
# the in-projection's 10,304) and gated norm (8 groups of 512 over 4,096
# channels, the gate at 0 of the same projection), T = 16,384; the operands
# made a position down a column, as the layer's arrays lie on the chip
POINTWISE = {"conv": ("ssm_conv", lambda zxd, w, b: sp.conv_silu(
                 zxd, w, b, lo=4096), ((6144, 4), (6144,)), 6144),
             "norm": ("ssm_norm", lambda y, zxd, w: sp.gated_norm(
                 y, zxd, w, 8, 1e-5), ((4096,),), 4096)}


@pytest.mark.parametrize("op", sorted(POINTWISE))
def test_the_pointwise_kernels_compile_at_the_cells_widths(
        one_chip, as_on_the_chip, monkeypatch, op):
    """Forward and backward fit `KERNEL_VMEM` at the module's own blocks,
    each is one kernel, and no `[16384, C]` array is copied or transposed
    around them."""
    monkeypatch.setattr(sp, "_resolve_interpret", lambda interpret: False)
    name, fn, small, C = POINTWISE[op]
    T = 16384
    shape = lambda dims: jax.ShapeDtypeStruct(dims, jnp.bfloat16,
                                              sharding=one_chip)
    across = lambda a: jnp.swapaxes(a, 1, 2)
    big = [shape((1, 10304, T))] if op == "conv" else \
        [shape((1, C, T)), shape((1, 10304, T))]

    def both(*args):
        *operands, ct = args
        operands = [across(a) if a.ndim == 3 else a for a in operands]
        out, vjp = jax.vjp(fn, *operands)
        return across(out), [across(g) if g.ndim == 3 else g
                             for g in vjp(across(ct))]

    text = jax.jit(both).lower(*big, *(shape(d) for d in small),
                               shape((1, C, T))).compile().as_text()
    assert f"{name}_fwd" in text and f"{name}_bwd" in text
    for line in text.splitlines():
        if " copy(" in line or " transpose(" in line:
            assert f"{T},{C}]" not in line and f"{C},{T}]" not in line, line


# the held experts' walk of each decoder cell: (block rows, width, expert
# width, held experts); a block's products x [rows, D] through [G, D, F]
# and back through [G, F, D]
EXPERTS = {"train-ssm16k": (7680, 2688, 1856, 8),
           "train-vl8k": (20480, 2048, 768, 16),
           "train-lc16k": (20480, 2048, 512, 32),
           "train-mtp8k": (10240, 2048, 768, 16)}


@pytest.mark.parametrize("cell", sorted(EXPERTS))
def test_the_grouped_kernels_compile_at_the_cells_widths(
        one_chip, as_on_the_chip, monkeypatch, cell):
    """Both products of a block, forward and backward, at the module's own
    blocks: every step fits `KERNEL_VMEM`, and no `ragged_dot` is left."""
    monkeypatch.setattr(gm, "_resolve_interpret", lambda interpret: False)
    rows, D, F, G = EXPERTS[cell]
    assert gm.inside_kernel(rows, D, F, G) == 1.0
    shape = lambda dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_chip)

    def both(x, w_up, w_down, sizes, ct):
        def block(x, w_up, w_down):
            h = jnp.square(gm.grouped_matmul(x, w_up, sizes))
            return gm.grouped_matmul(h.astype(x.dtype), w_down, sizes)
        out, vjp = jax.vjp(block, x, w_up, w_down)
        return out, vjp(ct)

    text = jax.jit(both).lower(
        shape((rows, D)), shape((G, D, F)), shape((G, F, D)),
        shape((G,), jnp.int32), shape((rows, D), jnp.float32)
    ).compile().as_text()
    assert "grouped_matmul" in text and "grouped_matmul_dw" in text
    assert "ragged" not in text


def test_a_step_builds_each_grouped_kernel_once(one_chip, as_on_the_chip,
                                                monkeypatch):
    """Two `moe` layers of the same widths, each rematerialised, value and
    gradients lowered for the chip: the text holds the six distinct
    kernels of one layer (two products forward, their dx and dW), not one
    a call site, however many layers call them."""
    monkeypatch.setattr(gm, "_resolve_interpret", lambda interpret: False)
    layer = decoder.MoELayer(n_in=128, n_out=128, n_experts=8,
                             experts_per_token=2, expert_width=256,
                             experts_held=4)
    shape = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), tree)

    def kernels(n):
        def loss(ps, x):
            for p in ps:
                def one(p, x):
                    return x + layer.forward_with_state(
                        p, x, layer.init_state(), train=True)[0]
                x = jax.checkpoint(one)(p, x)
            return jnp.sum(x.astype(jnp.float32) ** 2)
        ps = [jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                           layer.init_params(jax.random.PRNGKey(i)))
              for i in range(n)]
        text = jax.jit(jax.value_and_grad(loss)).lower(
            shape(ps), shape(jnp.zeros((1, 512, 128), jnp.bfloat16))
        ).as_text()
        return text.count("tpu_custom_call")

    # 512 tokens, 2 experts each, 4 of 8 held: blocks of 1,024 pairs
    assert gm.inside_kernel(1024, 128, 256, 4) == 1.0
    assert kernels(1) == 6
    assert kernels(2) == kernels(1)


def test_a_ring_hops_rectangle_compiles_at_a_hop_of_8192(one_chip,
                                                          as_on_the_chip):
    """A ring's hop of a chunk before its own at T_local = 8,192 and
    128-wide heads, bfloat16 in: the whole rectangle's forward and its
    one-pass backward, float32 out, fit at the module's blocks, one kernel
    each."""
    T, BH, d = 8192, 8, 128
    assert sa.backward_passes(T, d, d) == 1
    shape = lambda: jax.ShapeDtypeStruct((BH, T, d), jnp.bfloat16,
                                         sharding=one_chip)

    def hop(q, k, v, do):
        o, lse = sa.ring_hop(q, k, v, d ** -0.5, False, interpret=False)
        return o, lse, sa.ring_hop_bwd(q, k, v, o, lse, do, d ** -0.5,
                                       False, interpret=False)

    operands = [shape() for _ in range(4)]
    assert {a.dtype for a in jax.tree.leaves(jax.eval_shape(
        hop, *operands))} == {jnp.dtype(jnp.float32)}
    text = jax.jit(hop).lower(*operands).compile().as_text()
    assert "sparse_attention_fwd" in text and "sparse_attention_bwd" in text
    assert "sparse_attention_dq" not in text
    assert "sparse_attention_dkv" not in text
