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

from deeplearning4j_tpu.ops import sparse_attention as sa         # noqa: E402
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
