"""dp x tp x pp composition in ONE program (8-device CPU mesh).

The reference's distributed story is data-parallel only (SURVEY.md §2.5);
r3 proved each extra strategy separately. These tests pin the 3-axis
composition: Megatron tensor-parallel blocks (`make_tp_block_fn`, head-
and hidden-sharded with two psums) INSIDE the GPipe rotation
(`gpipe(param_specs=...)`), batch sharded over "data" — all in a single
shard_map program, the scaling-book mesh recipe."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from deeplearning4j_tpu.models.zoo.transformer import (
    embed_fn, init_lm, init_tp_block, lm_loss, make_block_fn,
    make_tp_block_fn, tp_block_specs)
from deeplearning4j_tpu.parallel.pipeline import (
    PipelineParallel, make_pipeline_mesh, microbatch, stack_stage_params)

pytestmark = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs 8 virtual devices")

D_MODEL, HEADS, D_FF = 32, 4, 64


def _dense_params_from_tp(tp):
    """Reassemble `init_tp_block` storage into `init_block` layout."""
    H, D, three_hd = tp["attn"]["wqkv"].shape
    hd = three_hd // 3
    w = tp["attn"]["wqkv"]
    dense_wqkv = jnp.concatenate(
        [jnp.concatenate([w[h, :, i * hd:(i + 1) * hd] for h in range(H)],
                         axis=1) for i in range(3)], axis=1)
    dense_wo = tp["attn"]["wo"].reshape(H * hd, D)
    return {"ln1": tp["ln1"], "ln2": tp["ln2"],
            "attn": {"wqkv": dense_wqkv, "wo": dense_wo},
            "mlp": tp["mlp"]}


class TestTensorParallelBlock:
    @pytest.mark.slow
    def test_tp_block_matches_dense_block(self):
        """Head/hidden-sharded block over a 4-way model axis == the dense
        single-device block, to float tolerance."""
        rng = jax.random.PRNGKey(0)
        tp = init_tp_block(rng, D_MODEL, HEADS, D_FF)
        dense = _dense_params_from_tp(tp)
        x = jnp.asarray(np.random.default_rng(1).standard_normal(
            (2, 8, D_MODEL)), jnp.float32)
        ref = make_block_fn(HEADS)(dense, x)

        mesh = Mesh(np.asarray(jax.devices()[:4]), ("model",))
        block = make_tp_block_fn(HEADS // 4, "model")
        specs = {
            "ln1": {"g": P(), "b": P()},
            "attn": {"wqkv": P("model"), "wo": P("model")},
            "ln2": {"g": P(), "b": P()},
            "mlp": {"w1": P(None, "model"), "b1": P("model"),
                    "w2": P("model", None), "b2": P()},
        }
        fn = jax.shard_map(block, mesh=mesh, in_specs=(specs, P()),
                           out_specs=P(), check_vma=False)
        got = jax.jit(fn)(tp, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5)


class TestThreeAxisPipeline:
    def _build(self, n_data, n_model, n_pipe, lr=0.0):
        mesh = make_pipeline_mesh(n_pipe=n_pipe, n_data=n_data,
                                  n_model=n_model)
        assert mesh.axis_names == ("data", "model", "pipe")
        rng = jax.random.PRNGKey(3)
        blocks = [init_tp_block(jax.random.fold_in(rng, i), D_MODEL,
                                HEADS, D_FF) for i in range(n_pipe)]
        aux, _ = init_lm(11, d_model=D_MODEL, n_heads=HEADS,
                         n_layers=1, max_len=16, seed=5)
        pp = PipelineParallel(
            make_tp_block_fn(HEADS // n_model, "model"), blocks, mesh,
            loss_fn=lm_loss, aux_params=aux, pre_fn=embed_fn, n_micro=2,
            data_axis="data", learning_rate=lr, momentum=0.9,
            param_specs=tp_block_specs("pipe", "model"))
        return pp, aux, blocks

    @pytest.mark.slow
    def test_loss_matches_sequential(self):
        """(data=2, model=2, pipe=2) pipelined+TP loss == running the
        dense-layout blocks sequentially on one device."""
        pp, aux, blocks = self._build(2, 2, 2)
        rng = np.random.default_rng(0)
        x = rng.integers(0, 11, (8, 16)).astype(np.int32)
        y = (x + 1) % 11
        xs = microbatch(jnp.asarray(x), 2)
        ys = microbatch(jnp.asarray(y), 2)
        loss_pipe = float(jax.jit(pp._loss)(pp.stacked, pp.aux, xs, ys))
        h = embed_fn(aux, jnp.asarray(x))
        dense_fn = make_block_fn(HEADS)
        for b in blocks:
            h = dense_fn(_dense_params_from_tp(b), h)
        loss_seq = float(lm_loss(aux, h, jnp.asarray(y)))
        assert abs(loss_pipe - loss_seq) < 1e-4, (loss_pipe, loss_seq)

    def test_param_shardings_cover_three_axes(self):
        pp, _, _ = self._build(2, 2, 2)
        wqkv = pp.stacked["attn"]["wqkv"]         # [S, H, D, 3hd]
        spec = tuple(wqkv.sharding.spec)
        assert spec[0] == "pipe" and spec[1] == "model"
        w1 = pp.stacked["mlp"]["w1"]
        assert tuple(w1.sharding.spec)[2] == "model"

    @pytest.mark.slow
    def test_three_axis_training_learns(self):
        pp, _, _ = self._build(2, 2, 2, lr=0.5)
        rng = np.random.default_rng(0)
        x = rng.integers(0, 11, (16, 16)).astype(np.int32)
        y = (x + 1) % 11
        first = pp.fit_batch(x, y)
        for _ in range(30):
            last = pp.fit_batch(x, y)
        assert last < first * 0.6, (first, last)
