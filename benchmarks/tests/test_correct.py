"""`correct` has to come out false when it should: the control (the reference
in the program's place, computed in fp8, the nearest precision below the
bfloat16 the configurations state) and each fault a cell can have, planted
under the harness with the rest of a run driven as run.py drives it (only the
look for a chip is skipped). At a size a test run can hold: the `tiny-*`
configurations, whose limits were set from CPU readings at that size the way
the cells' limits were set from chip readings (PERF.md, limits).
"""
import time

import jax
import pytest

from benchmarks.drivers import serve, train
from benchmarks.harness import compare, loader, weights
from benchmarks.harness.window import Tracer

SEED = 2**31 + 77


def drive(cell, seconds=2.0, seed=SEED, **kw):
    return loader.driver(cell["config"]).run(
        cell, seed, seconds, Tracer(False, None),
        lambda t=None: time.monotonic() if t is None else t, **kw)


# ---------------------------------------------------------------- training
def train_cell(chips):
    return loader.make_cell("test.train", "tiny-resnet",
                            "train-staged" if chips == 1 else "train-dp4",
                            chips)


def broken_build(monkeypatch, wrap):
    """The driver's `_build` with the step it hands to the window broken."""
    real = train._build

    def build(cfg, chips):
        net, fit, mesh = real(cfg, chips)
        return net, wrap(net, fit, chips), mesh
    monkeypatch.setattr(train, "_build", build)


def state_unchanged(net, fit, chips):
    copy = jax.jit(lambda t: jax.tree.map(lambda a: a + 0, t))

    def step(ds):
        params, state = copy(net._params), copy(net._updater_state)
        fit(ds)
        net._params, net._updater_state = params, state
    return step


def half_batch_left_out(net, fit, chips):
    from deeplearning4j_tpu.datasets.dataset import DataSet

    def step(ds):
        n = ds.features.shape[0] // 2
        fit(DataSet(ds.features[:n], ds.labels[:n]))
    return step


def exchange_left_out(net, fit, chips):
    """Every chip given the first chip's rows: the mean over the chips is
    then what the first chip alone computes, as with no all-reduce."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.datasets.dataset import DataSet

    def step(ds):
        n = ds.features.shape[0] // chips
        tile = lambda a: jnp.concatenate([a[:n]] * chips)
        fit(DataSet(tile(ds.features), tile(ds.labels)))
    return step


@pytest.mark.parametrize("chips", [1, 4])
def test_training_run_is_correct(chips):
    out = drive(train_cell(chips))
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["end_to_end"]["images_per_s"] > 0


@pytest.mark.parametrize("chips,fault", [
    (1, state_unchanged), (1, half_batch_left_out),
    (4, half_batch_left_out), (4, exchange_left_out)])
def test_planted_training_fault_is_not_correct(monkeypatch, chips, fault):
    broken_build(monkeypatch, fault)
    out = drive(train_cell(chips))
    assert not out["correct"], out["compared"]


def test_training_control_in_fp8_is_not_correct():
    cell = train_cell(1)
    cfg = cell["config"]
    model, trainer = cfg["model"], cfg["trainer"]
    ref = loader.reference(cfg)
    wide = train.matrix_leaves(ref, model)
    for seed in (1, 2, 3):
        w0 = weights.resnet_weights(seed, ref.param_shapes(model))
        xs, ys = weights.image_ring(seed, train.FOLLOWED,
                                    trainer["batch_per_chip"], model)
        want = train.reference_steps(ref, w0, xs, ys, model, trainer)
        low = train.reference_steps(ref, w0, xs, ys, model, trainer,
                                    quant=True)
        ok, compared = compare.judge(
            compare.training_numbers(low, want, wide), cfg["limits"])
        assert not ok, compared


# ----------------------------------------------------------------- serving
def serve_cell(mix):
    return loader.make_cell("test." + mix, "tiny-lm", mix, 1)


@pytest.mark.parametrize("mix", ["chat-open", "docs-closed"])
def test_serving_run_is_correct(mix):
    out = drive(serve_cell(mix), seconds=4.0)
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0


@pytest.mark.parametrize("mix", ["chat-open", "docs-closed"])
def test_altered_token_is_not_correct(mix):
    def alter(streams):
        """One served token of the longest answer replaced where the answers
        leave the server."""
        i = max(streams, key=lambda k: len(streams[k]))
        s = streams[i].copy()
        s[-2] = (s[-2] + 1) % 200 + 1
        return {**streams, i: s}
    out = drive(serve_cell(mix), seconds=4.0, on_served=alter)
    assert not out["correct"], out["compared"]


def test_truncated_answer_is_not_correct():
    cut = lambda streams: {i: s[:-1] for i, s in streams.items()}
    out = drive(serve_cell("chat-open"), seconds=4.0, on_served=cut)
    assert not out["correct"]
    assert out["compared"]["malformed_streams"]["value"] > 0


def test_serving_control_in_fp8_is_not_correct():
    cell = serve_cell("chat-open")
    cfg = cell["config"]
    readings = []
    serve.calibrate(cell, [3, SEED], lambda seed, what, nums:
                    readings.append((what, nums)), seconds=4.0)
    for what, nums in readings:
        ok, _ = compare.judge({"logit_gap": nums["logit_gap"],
                               "malformed_streams": 0.0}, cfg["limits"])
        assert ok == (what == "program"), (what, nums)
