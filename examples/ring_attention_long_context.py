"""Long-context attention: the sequence axis sharded over the mesh (ring
attention — K/V blocks rotate over ICI while an online softmax folds each
block), with the Pallas kernels of ops/sparse_attention.py as each
device's block compute (a hop is the causal diagonal, the whole rectangle
or nothing).

No reference equivalent (the 2016 stack predates attention; its only
long-sequence tool is truncated BPTT) — TPU-first extension.
"""
import _common  # noqa: F401

import jax
import numpy as np
import jax.numpy as jnp
from jax.sharding import Mesh

from deeplearning4j_tpu.parallel.ring_attention import (
    blockwise_attention, ring_self_attention)

mesh = Mesh(np.array(jax.devices()), ("seq",))
rng = np.random.default_rng(1)
B, T, H, D = 2, 128, 4, 16                      # T shards over 8 devices
q = jnp.asarray(rng.standard_normal((B, T, H, D)), jnp.float32)

ring = ring_self_attention(q, q, q, mesh, axis="seq", causal=True)
flash = ring_self_attention(q, q, q, mesh, axis="seq", causal=True,
                            use_flash=True)
full = blockwise_attention(q, q, q, causal=True)
print("ring == full:", bool(jnp.allclose(ring, full, atol=1e-4)),
      " ring+flash == full:", bool(jnp.allclose(flash, full, atol=1e-4)))

# TRAINING on the kernel path: gradients come from the fused ring backward
# (a reverse ring over each hop's Pallas backward — no score panel is
# ever materialized, forward or backward)
g_flash = jax.grad(lambda q: jnp.mean(ring_self_attention(
    q, q, q, mesh, axis="seq", causal=True, use_flash=True) ** 2))(q)
g_full = jax.grad(lambda q: jnp.mean(blockwise_attention(
    q, q, q, causal=True) ** 2))(q)
grads_match = bool(jnp.allclose(g_flash, g_full, atol=1e-4))
print("fused ring backward grads == single-device grads:", grads_match)
print(grads_match)
