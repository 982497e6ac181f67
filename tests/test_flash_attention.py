"""`ops.flash_attention`: the tile kernels of ops/sparse_attention.py over
[B, T, H, D] (interpreter mode on the CPU mesh; the same kernels compile via
Mosaic on TPU).

Pinned against `blockwise_attention` (the ring-attention single-device
reference): forward exact in f32, causal masking and the whole rectangle,
block-size obliviousness, and the FUSED Pallas backward == autodiff of the
reference — both masks, any divisor tiling, uneven T, bf16 inputs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops import flash_attention
from deeplearning4j_tpu.parallel.ring_attention import blockwise_attention

B, T, H, D = 2, 256, 4, 64


def _qkv(seed=0, t=T, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.standard_normal((B, t, H, D)), dtype)
    return mk(), mk(), mk()


class TestFlashForward:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, causal):
        q, k, v = _qkv()
        out = flash_attention(q, k, v, causal, None, 128, 128, True)
        ref = blockwise_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-6)

    def test_block_size_oblivious(self):
        """Any divisor block size gives the same numbers (online softmax
        is associative over blocks)."""
        q, k, v = _qkv(1)
        outs = [flash_attention(q, k, v, True, None, bq, bk, True)
                for bq, bk in ((256, 256), (64, 128), (128, 32))]
        for o in outs[1:]:
            np.testing.assert_allclose(np.asarray(o), np.asarray(outs[0]),
                                       atol=2e-6)

    def test_non_divisible_seq_auto_picks_divisor_block(self):
        # T=96 with requested block 64 -> largest divisor 48 is used; the
        # values still match the reference exactly
        q, k, v = _qkv(2, t=96)
        out = flash_attention(q, k, v, True, None, 64, 64, True)
        ref = blockwise_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-6)

    def test_scale_override(self):
        q, k, v = _qkv(3)
        out = flash_attention(q, k, v, False, 0.5, 128, 128, True)
        ref = blockwise_attention(q, k, v, causal=False, scale=0.5)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=5e-6)


class TestFlashBackward:
    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_reference_autodiff_both_masks(self, causal):
        """Fused Pallas dQ/dK/dV == autodiff of the XLA reference, causal
        and full attention."""
        q, k, v = _qkv(7)

        def loss_f(q, k, v):
            return jnp.mean(
                flash_attention(q, k, v, causal, None, 128, 128, True) ** 2)

        def loss_r(q, k, v):
            return jnp.mean(
                blockwise_attention(q, k, v, causal=causal) ** 2)

        gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)

    @pytest.mark.slow
    def test_backward_block_size_oblivious(self):
        """Backward accumulation is associative over (bq, bk) tilings —
        any divisor blocks give the same gradients."""
        q, k, v = _qkv(8)

        def g(bq, bk):
            def loss(q, k, v):
                return jnp.mean(
                    flash_attention(q, k, v, True, None, bq, bk, True) ** 2)
            return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

        ref = g(256, 256)
        for bq, bk in ((64, 128), (128, 32)):
            for a, b in zip(g(bq, bk), ref):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           atol=2e-5)

    @pytest.mark.slow
    def test_backward_non_divisible_seq(self):
        q, k, v = _qkv(9, t=96)

        def loss_f(q, k, v):
            return jnp.mean(
                flash_attention(q, k, v, True, None, 64, 64, True) ** 2)

        def loss_r(q, k, v):
            return jnp.mean(blockwise_attention(q, k, v, causal=True) ** 2)

        gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)

    def test_backward_bf16_inputs(self):
        """bf16 q/k/v (the training dtype on chip): grads keep the input
        dtype and track the f32 reference within bf16 resolution."""
        q, k, v = _qkv(10, dtype=jnp.bfloat16)

        def loss_f(q, k, v):
            return jnp.mean(flash_attention(
                q, k, v, True, None, 128, 128, True).astype(jnp.float32)
                ** 2)

        gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
        qf, kf, vf = (a.astype(jnp.float32) for a in (q, k, v))

        def loss_r(q, k, v):
            return jnp.mean(blockwise_attention(q, k, v, causal=True) ** 2)

        gr = jax.grad(loss_r, argnums=(0, 1, 2))(qf, kf, vf)
        for a, b in zip(gf, gr):
            assert a.dtype == jnp.bfloat16
            # tolerance SCALED to the gradient magnitude (grads here are
            # ~1e-4; an absolute atol would be vacuous): every entry must
            # land within 3% of the largest reference gradient
            scale = np.abs(np.asarray(b)).max()
            assert scale > 0
            np.testing.assert_allclose(
                np.asarray(a, np.float32) / scale,
                np.asarray(b) / scale, atol=0.03)

    @pytest.mark.slow
    def test_trains_in_transformer_block(self):
        """flash attention drops into the zoo transformer block and the LM
        still learns (attention='flash' path)."""
        from deeplearning4j_tpu.models.zoo.transformer import TransformerLM
        lm = TransformerLM(11, d_model=32, n_heads=4, n_layers=2,
                           max_len=16, learning_rate=0.2, momentum=0.9,
                           attention="flash")
        rng = np.random.default_rng(0)
        x = rng.integers(0, 11, (16, 16)).astype(np.int32)
        y = (x + 1) % 11
        first = lm.fit_batch(x, y)
        for _ in range(60):
            last = lm.fit_batch(x, y)
        assert last < first * 0.5
