"""Ring attention (sequence parallelism) + SelfAttentionLayer:
- ring kernel over the virtual 8-device mesh == single-device attention
- causal + key-mask correctness
- layer gradient check, training, and mesh-parallel layer path
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from deeplearning4j_tpu.parallel.ring_attention import (blockwise_attention,
                                                        ring_self_attention)


def _seq_mesh(n=4):
    return Mesh(np.array(jax.devices()[:n]), ("seq",))


def _qkv(B=2, T=16, H=2, D=8, seed=0):
    r = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(r.standard_normal((B, T, H, D)), jnp.float32)
    return mk(), mk(), mk()


class TestRingAttention:
    def test_matches_full_attention(self):
        q, k, v = _qkv()
        mesh = _seq_mesh(4)
        full = blockwise_attention(q, k, v)
        ring = ring_self_attention(q, k, v, mesh, axis="seq")
        assert np.allclose(np.asarray(full), np.asarray(ring), atol=1e-5), \
            np.abs(np.asarray(full) - np.asarray(ring)).max()

    @pytest.mark.slow
    def test_causal_matches(self):
        q, k, v = _qkv(T=24, seed=1)
        mesh = _seq_mesh(4)
        full = blockwise_attention(q, k, v, causal=True)
        ring = ring_self_attention(q, k, v, mesh, axis="seq", causal=True)
        assert np.allclose(np.asarray(full), np.asarray(ring), atol=1e-5)

    @pytest.mark.slow
    def test_causality_actually_holds(self):
        """Changing future keys must not change past outputs."""
        q, k, v = _qkv(T=16, seed=2)
        mesh = _seq_mesh(4)
        out1 = np.asarray(ring_self_attention(q, k, v, mesh, axis="seq",
                                              causal=True))
        k2 = k.at[:, 12:].set(99.0)
        v2 = v.at[:, 12:].set(-99.0)
        out2 = np.asarray(ring_self_attention(q, k2, v2, mesh, axis="seq",
                                              causal=True))
        assert np.allclose(out1[:, :12], out2[:, :12], atol=1e-5)
        assert not np.allclose(out1[:, 12:], out2[:, 12:])

    @pytest.mark.slow
    def test_key_mask(self):
        q, k, v = _qkv(T=16, seed=3)
        mesh = _seq_mesh(4)
        kv_mask = jnp.asarray(
            np.repeat([[1] * 10 + [0] * 6], 2, axis=0), jnp.float32)
        full = blockwise_attention(q, k, v, kv_mask=kv_mask)
        ring = ring_self_attention(q, k, v, mesh, axis="seq",
                                   kv_mask=kv_mask)
        assert np.allclose(np.asarray(full), np.asarray(ring), atol=1e-5)
        # masked keys are ignored: result equals attention over first 10 only
        trunc = blockwise_attention(q, k[:, :10], v[:, :10])
        assert np.allclose(np.asarray(full), np.asarray(trunc), atol=1e-5)

    @pytest.mark.slow
    def test_gradients_flow_through_ring(self):
        q, k, v = _qkv(T=8, seed=4)
        mesh = _seq_mesh(4)

        def loss_ring(q, k, v):
            return jnp.sum(ring_self_attention(q, k, v, mesh, axis="seq") ** 2)

        def loss_full(q, k, v):
            return jnp.sum(blockwise_attention(q, k, v) ** 2)

        g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        g_full = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
        for gr, gf in zip(g_ring, g_full):
            assert np.allclose(np.asarray(gr), np.asarray(gf), atol=1e-4)


class TestSelfAttentionLayer:
    def _conf(self, causal=False):
        from deeplearning4j_tpu import InputType, NeuralNetConfiguration
        from deeplearning4j_tpu.nn.conf.layers import (RnnOutputLayer,
                                                       SelfAttentionLayer)
        return (NeuralNetConfiguration.Builder().seed(5)
                .data_type("float64").updater("sgd").learning_rate(0.05)
                .list()
                .layer(0, SelfAttentionLayer(n_heads=2, causal=causal,
                                             activation="identity"))
                .layer(1, RnnOutputLayer(n_out=3, activation="softmax",
                                         loss_function="mcxent"))
                .set_input_type(InputType.recurrent(6))
                .build())

    @pytest.mark.slow
    def test_gradient_check(self):
        from deeplearning4j_tpu import MultiLayerNetwork
        from deeplearning4j_tpu.gradientcheck.gradient_check_util import \
            check_gradients
        net = MultiLayerNetwork(self._conf()).init()
        r = np.random.default_rng(0)
        x = r.random((3, 5, 6)).astype(np.float64)
        y = np.zeros((3, 5, 3))
        y[np.arange(3)[:, None], np.arange(5)[None, :],
          r.integers(0, 3, (3, 5))] = 1.0
        assert check_gradients(net, x, y, max_rel_error=1e-4, subset=60)

    def test_trains(self):
        from deeplearning4j_tpu import MultiLayerNetwork
        from deeplearning4j_tpu.datasets.dataset import DataSet
        net = MultiLayerNetwork(self._conf(causal=True)).init()
        r = np.random.default_rng(1)
        x = r.random((4, 6, 6)).astype(np.float64)
        y = np.zeros((4, 6, 3))
        y[np.arange(4)[:, None], np.arange(6)[None, :],
          r.integers(0, 3, (4, 6))] = 1.0
        ds = DataSet(x, y)
        s0 = net.score(ds)
        for _ in range(30):
            net.fit(ds)
        assert net.score(ds) < s0

    @pytest.mark.slow
    def test_sequence_parallel_layer_matches_local(self):
        from deeplearning4j_tpu.nn.conf.layers import SelfAttentionLayer
        layer = SelfAttentionLayer(n_in=8, n_out=8, n_heads=2,
                                   activation="identity")
        layer = layer.apply_global_defaults({})
        params = layer.init_params(jax.random.PRNGKey(0), jnp.float32)
        r = np.random.default_rng(2)
        x = jnp.asarray(r.standard_normal((2, 16, 8)), jnp.float32)
        out_local = np.asarray(layer.forward(params, x))
        layer_sp = SelfAttentionLayer(n_in=8, n_out=8, n_heads=2,
                                      activation="identity")
        layer_sp = layer_sp.apply_global_defaults({})
        layer_sp.with_sequence_parallel(_seq_mesh(4), "seq")
        out_sp = np.asarray(layer_sp.forward(params, x))
        assert np.allclose(out_local, out_sp, atol=1e-5)


class TestRingFlashPath:
    """use_flash=True: per-hop compute via the Pallas kernels of
    ops/sparse_attention.py (interpreter on CPU, Mosaic on TPU) — the full
    long-context stack (sequence parallelism x tile kernels)."""

    @pytest.mark.slow
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_full_attention(self, causal):
        q, k, v = _qkv(T=32, seed=3)
        mesh = _seq_mesh(4)
        full = blockwise_attention(q, k, v, causal=causal)
        ring = ring_self_attention(q, k, v, mesh, axis="seq",
                                   causal=causal, use_flash=True)
        assert np.allclose(np.asarray(full), np.asarray(ring), atol=1e-5), \
            np.abs(np.asarray(full) - np.asarray(ring)).max()

    @pytest.mark.slow
    def test_eight_device_ring(self):
        q, k, v = _qkv(T=64, seed=4)
        mesh = _seq_mesh(8)
        full = blockwise_attention(q, k, v, causal=True)
        ring = ring_self_attention(q, k, v, mesh, axis="seq", causal=True,
                                   use_flash=True)
        assert np.allclose(np.asarray(full), np.asarray(ring), atol=1e-5)

    def test_two_device_causal_ring_of_tiles(self, monkeypatch):
        """Two devices, causal, chunks of four 8-row blocks: hop 0 of each
        device is the diagonal's schedule, device 1's second hop the whole
        rectangle, device 0's the hop skipped; forward and gradients as the
        single-device reference's."""
        from deeplearning4j_tpu.ops import sparse_attention as sa
        monkeypatch.setattr(sa, "BLOCK", 8)
        q, k, v = _qkv(B=1, T=64, seed=12)
        mesh = _seq_mesh(2)
        ring = lambda q, k, v: ring_self_attention(
            q, k, v, mesh, axis="seq", causal=True, use_flash=True)
        full = lambda q, k, v: blockwise_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(ring(q, k, v)),
                                   np.asarray(full(q, k, v)), atol=1e-5)
        loss = lambda f: lambda q, k, v: jnp.mean(f(q, k, v) ** 2)
        gf = jax.grad(loss(ring), argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss(full), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)

    def test_kv_mask_rejected(self):
        q, k, v = _qkv(seed=5)
        mesh = _seq_mesh(4)
        with pytest.raises(ValueError):
            ring_self_attention(q, k, v, mesh, axis="seq", use_flash=True,
                                kv_mask=jnp.ones(q.shape[:2]))

    @pytest.mark.slow
    @pytest.mark.parametrize("causal", [False, True])
    def test_flash_path_differentiable(self, causal):
        """use_flash trains with the FUSED ring backward (r4: reverse
        ring feeding the Pallas dQ/dK+dV grid passes per hop, dK/dV
        partials rotating home with their blocks; global lse saved by the
        forward makes each hop's probabilities exact) — grads match the
        einsum ring's autodiff."""
        q, k, v = _qkv(T=32, seed=6)
        mesh = _seq_mesh(4)

        def loss_flash(q, k, v):
            return jnp.mean(ring_self_attention(
                q, k, v, mesh, axis="seq", causal=causal,
                use_flash=True) ** 2)

        def loss_ref(q, k, v):
            return jnp.mean(ring_self_attention(
                q, k, v, mesh, axis="seq", causal=causal) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)

    @pytest.mark.slow
    def test_fused_ring_backward_bf16(self):
        """bf16 chunks: per-hop partials come back f32 and are rounded
        ONCE after the ring, tracking the f32 reference within bf16
        resolution (scaled tolerance)."""
        r = np.random.default_rng(11)
        mk = lambda: jnp.asarray(r.standard_normal((2, 32, 2, 8)),
                                 jnp.bfloat16)
        q, k, v = mk(), mk(), mk()
        mesh = _seq_mesh(4)

        def loss_flash(q, k, v):
            return jnp.mean(ring_self_attention(
                q, k, v, mesh, axis="seq", causal=True,
                use_flash=True).astype(jnp.float32) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        qf, kf, vf = (a.astype(jnp.float32) for a in (q, k, v))

        def loss_ref(q, k, v):
            return jnp.mean(blockwise_attention(q, k, v,
                                                causal=True) ** 2)

        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(qf, kf, vf)
        for a, b in zip(gf, gr):
            assert a.dtype == jnp.bfloat16
            scale = np.abs(np.asarray(b)).max()
            assert scale > 0
            np.testing.assert_allclose(
                np.asarray(a, np.float32) / scale,
                np.asarray(b) / scale, atol=0.03)

    @pytest.mark.slow
    def test_fused_ring_backward_eight_devices(self):
        """The rotating dK/dV accumulators come home correctly over a
        longer ring (8 hops) — grads match the single-device reference."""
        q, k, v = _qkv(T=64, seed=7)
        mesh = _seq_mesh(8)

        def loss_flash(q, k, v):
            return jnp.mean(ring_self_attention(
                q, k, v, mesh, axis="seq", causal=True,
                use_flash=True) ** 2)

        def loss_single(q, k, v):
            return jnp.mean(blockwise_attention(q, k, v,
                                                causal=True) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_single, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)
