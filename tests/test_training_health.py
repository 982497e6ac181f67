"""Training-health watchdog (ISSUE 2 acceptance criteria).

Every numerical failure mode is driven through the REAL code path with
`common.resilience.FaultInjector`'s `corrupt` action (NaN/Inf/value-poison
a payload at a named data-path site) — no mocks:

  (a) an injected NaN gradient is SKIPPED on device: params bit-identical
      to the pre-step values for that round, counters still aligned;
  (b) an injected divergence (finite-but-huge batch) triggers ROLLBACK to
      the last good round via the ShardedCheckpointManager seam, the run
      completes, and the post-rollback stream is bit-comparable to a run
      that never saw the poisoned batch;
  (c) N consecutive faults ABORT with a TrainingDivergedError diagnostic
      naming the offending rounds;
  (d) with the watchdog disabled, the fused step's lowered HLO is
      UNCHANGED from today (pinned, like the stats-emission contract);
  (e) the iterator boundary validates batches (shape/dtype/finiteness)
      with raise/skip/count policies, through the async staging path;
  (f) watchdog events reach the StatsListener storage (UI run health).
"""
import numpy as np
import pytest

from deeplearning4j_tpu import (InputType, MultiLayerNetwork,
                                NeuralNetConfiguration)
from deeplearning4j_tpu.common.health import (TrainingDivergedError,
                                              TrainingHealthPolicy)
from deeplearning4j_tpu.common.resilience import FaultInjector
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import (AsyncDataSetIterator,
                                                   BatchValidationError,
                                                   DataSetValidator,
                                                   ListDataSetIterator,
                                                   ValidatingDataSetIterator)
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer


def _net(seed=7):
    conf = (NeuralNetConfiguration.Builder().seed(seed)
            .updater("adam").learning_rate(0.01).list()
            .layer(0, DenseLayer(n_out=8, activation="relu"))
            .layer(1, OutputLayer(n_out=3, activation="softmax",
                                  loss_function="mcxent"))
            .set_input_type(InputType.feed_forward(5))
            .build())
    return MultiLayerNetwork(conf).init()


def _data(n=128, seed=0):
    r = np.random.default_rng(seed)
    x = r.random((n, 5)).astype(np.float32)
    w = r.random((5, 3))
    y = np.eye(3, dtype=np.float32)[np.argmax(x @ w, axis=1)]
    return DataSet(x, y)


def _reg_net(seed=7):
    """MSE regression head: loss and gradients scale with the feature
    magnitude, so a value-poisoned batch deterministically explodes the
    gradient norm (a softmax head can saturate to near-zero gradients on
    huge inputs, which would make divergence injection data-dependent)."""
    conf = (NeuralNetConfiguration.Builder().seed(seed)
            .updater("adam").learning_rate(0.01).list()
            .layer(0, DenseLayer(n_out=8, activation="identity"))
            .layer(1, OutputLayer(n_out=3, activation="identity",
                                  loss_function="mse"))
            .set_input_type(InputType.feed_forward(5))
            .build())
    return MultiLayerNetwork(conf).init()


def _nan_batch(n=16):
    return DataSet(np.full((n, 5), np.nan, np.float32),
                   np.eye(3, dtype=np.float32)[np.zeros(n, int)])


# ---------------------------------------------------------------------------
# FaultInjector `corrupt` action
# ---------------------------------------------------------------------------

def test_fault_injector_corrupt_poisons_copy_not_original():
    inj = FaultInjector(seed=0)
    inj.plan("d", on_call=1, corrupt="nan")
    arr = np.ones((4, 3), np.float32)
    assert inj.fire("d", payload=arr) is arr        # call 0: untouched
    out = inj.fire("d", payload=arr)                # call 1: poisoned COPY
    assert np.isnan(out).all()
    assert (arr == 1.0).all()                       # original never mutated
    assert inj.fired("d") == [("d", 1)]


def test_fault_injector_corrupt_variants_and_no_raise():
    inj = FaultInjector(seed=0)
    inj.plan("a", on_call=0, corrupt="inf")
    inj.plan("b", on_call=0, corrupt=42.5)
    a = inj.fire("a", payload=np.zeros(3, np.float32))  # no raise: the
    b = inj.fire("b", payload=np.zeros(3, np.float32))  # poison IS the fault
    assert np.isinf(a).all()
    assert (b == 42.5).all()
    # call-indexed and capped exactly like drop/delay/sever
    inj2 = FaultInjector(seed=0)
    inj2.plan("c", on_calls=[0, 2], corrupt=1.0)
    hits = [i for i in range(4)
            if (inj2.fire("c", payload=np.zeros(1)) != 0).any()]
    assert hits == [0, 2]


# ---------------------------------------------------------------------------
# (d) disabled watchdog: lowered HLO unchanged (the collect_acts contract)
# ---------------------------------------------------------------------------

def _mln_lowered(net, **kwargs):
    import jax
    batch = {"features": np.zeros((4, 5), np.float32),
             "labels": np.zeros((4, 3), np.float32),
             "fmask": None, "lmask": None, "iteration": np.float32(0),
             "rng": jax.random.PRNGKey(0), "carries": None}
    return jax.jit(net.make_raw_step(**kwargs)).lower(
        net._params, net._updater_state, net._model_state, batch).as_text()


def test_disabled_watchdog_hlo_unchanged_multilayer():
    net = _net()
    t_default = _mln_lowered(net)
    t_off = _mln_lowered(net, emit_health=False)
    t_on = _mln_lowered(net, emit_health=True)
    assert t_off == t_default          # disabled path == today's program
    assert "is_finite" not in t_default  # today's program has no sentinel
    assert "is_finite" in t_on and t_on != t_default


def test_disabled_watchdog_hlo_unchanged_computation_graph():
    import jax
    from deeplearning4j_tpu import ComputationGraph
    conf = (NeuralNetConfiguration.Builder().seed(3)
            .updater("sgd").learning_rate(0.1).graph_builder()
            .add_inputs("in")
            .add_layer("d", DenseLayer(n_out=6, activation="relu"), "in")
            .add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                          loss_function="mcxent"), "d")
            .set_outputs("out")
            .set_input_types(InputType.feed_forward(5))
            .build())
    net = ComputationGraph(conf).init()
    batch = {"features": {"in": np.zeros((4, 5), np.float32)},
             "labels": [np.zeros((4, 3), np.float32)],
             "fmask": None, "lmask": None, "iteration": np.float32(0),
             "rng": jax.random.PRNGKey(0), "carries": None}

    def lower(**kw):
        return jax.jit(net.make_raw_step(**kw)).lower(
            net._params, net._updater_state, net._model_state,
            batch).as_text()

    t_default, t_off, t_on = lower(), lower(emit_health=False), \
        lower(emit_health=True)
    assert t_off == t_default
    assert "is_finite" not in t_default
    assert "is_finite" in t_on and t_on != t_default


# ---------------------------------------------------------------------------
# policy classification (host side)
# ---------------------------------------------------------------------------

def _h(score, grad_norm=1.0, finite=True):
    return {"score": score, "grad_norm": grad_norm, "all_finite": finite}


def test_policy_ema_spike_classification():
    pol = TrainingHealthPolicy(spike_zscore=4.0, ema_decay=0.5,
                               warmup_steps=5, max_consecutive_bad=10)
    for i in range(8):          # stable baseline around 1.0
        assert pol.observe(_h(1.0 + 0.01 * (i % 3)), i) == "ok"
    assert pol.observe(_h(100.0), 8) == "rollback"      # massive spike
    assert pol.counts["spikes"] == 1
    # the spike never entered the EMA: the next normal step is healthy
    assert pol.observe(_h(1.0), 9) == "ok"
    assert pol.consecutive_bad == 0


def test_policy_grad_norm_limit_and_rollback_degrade():
    pol = TrainingHealthPolicy(grad_norm_limit=10.0, rollback_on_spike=False,
                               max_consecutive_bad=10)
    assert pol.observe(_h(1.0, grad_norm=50.0), 0) == "spike"
    pol2 = TrainingHealthPolicy(grad_norm_limit=10.0)
    assert pol2.observe(_h(1.0, grad_norm=50.0), 0) == "rollback"


def test_policy_abort_after_n_consecutive_names_rounds():
    pol = TrainingHealthPolicy(max_consecutive_bad=3)
    assert pol.observe(_h(np.nan, finite=False), 4) == "skip"
    assert pol.observe(_h(np.nan, finite=False), 5) == "skip"
    assert pol.observe(_h(np.nan, finite=False), 6) == "abort"
    msg = pol.diagnose()
    assert "3 consecutive" in msg
    assert "[4, 5, 6]" in msg          # the offending rounds, by name
    assert pol.counts["aborts"] == 1


# ---------------------------------------------------------------------------
# (a) NaN gradient skipped on device — params bit-identical for that round
# ---------------------------------------------------------------------------

def test_injected_nan_batch_skipped_params_bit_identical():
    inj = FaultInjector(seed=0)
    inj.plan("data.batch", on_call=2, corrupt="nan")   # poison 3rd batch
    validator = DataSetValidator(policy="count", check_finite=False,
                                 fault_injector=inj)
    batches = list(_data(64, seed=1).batch_by(16))     # 4 batches
    it = ValidatingDataSetIterator(ListDataSetIterator(batches), validator)

    pol = TrainingHealthPolicy(max_consecutive_bad=5)
    net = _net(seed=11).training_health(pol)
    snaps = []

    class Snap:
        def iteration_done(self, model, iteration):
            snaps.append(model.params())

    net.add_listener(Snap())
    net.fit(it)

    assert len(inj.fired("data.batch")) == 1
    assert pol.counts == {"ok": 3, "skips": 1, "spikes": 0, "rollbacks": 0,
                          "aborts": 0, "validation_rejects": 0}
    # the poisoned round's update was withheld ON DEVICE: params after the
    # bad step are bit-identical to the pre-step values for that round
    np.testing.assert_array_equal(snaps[2], snaps[1])
    assert not np.array_equal(snaps[3], snaps[2])      # training resumed
    assert np.isfinite(net.params()).all()
    # bookkeeping stays ALIGNED across the skip: the host counter and the
    # device-resident loop counter advanced in lockstep
    assert net.conf.iteration_count == 4
    assert float(net._loop["iteration"]) == 4.0
    assert np.isfinite(float(net.score()))   # _score kept at last good


def test_skip_keeps_score_and_epoch_bookkeeping_consistent():
    pol = TrainingHealthPolicy(max_consecutive_bad=5)
    net = _net(seed=2).training_health(pol)
    net.fit(_data(32, seed=2))
    good_score = float(net.score())
    epochs = net.conf.epoch_count
    net.fit(_nan_batch())
    assert pol.counts["skips"] == 1
    assert float(net.score()) == good_score   # NaN never became the score
    assert net.conf.epoch_count == epochs     # fit(DataSet) is epoch-free


# ---------------------------------------------------------------------------
# (b/c) rollback + abort in the single-process fit loop
#       (ShardedCheckpointManager seam)
# ---------------------------------------------------------------------------

def test_fit_loop_rollback_via_checkpoint_seam(tmp_path):
    inj = FaultInjector(seed=0)
    inj.plan("data.batch", on_call=4, corrupt=500.0)   # finite divergence
    validator = DataSetValidator(policy="count", check_finite=False,
                                 fault_injector=inj)
    batches = list(_data(128, seed=3).batch_by(16))    # 8 batches
    it = ValidatingDataSetIterator(ListDataSetIterator(batches), validator)

    pol = TrainingHealthPolicy(grad_norm_limit=50.0, max_consecutive_bad=4)
    net = _reg_net(seed=4).training_health(pol,
                                           checkpoint_dir=tmp_path / "hk",
                                           checkpoint_every=2)
    net.fit(it)

    assert pol.counts["spikes"] == 1
    assert pol.counts["rollbacks"] == 1
    rb = [e for e in pol.events if e["kind"] == "rollback"]
    assert rb and rb[0]["restoredRound"] == 4  # last even (every=2) round
    # the spiked round rolled back and its batch was abandoned: 8 batches,
    # one consumed without surviving -> 7 applied iterations
    assert net.conf.iteration_count == 7
    assert float(net._loop["iteration"]) == 7.0
    assert np.isfinite(net.params()).all()


def test_fit_loop_abort_names_offending_rounds():
    pol = TrainingHealthPolicy(max_consecutive_bad=2)
    net = _net(seed=6).training_health(pol)
    net.fit(_data(32, seed=6))
    bad = ListDataSetIterator([_nan_batch(), _nan_batch(), _nan_batch()])
    with pytest.raises(TrainingDivergedError, match="offending rounds"):
        net.fit(bad)
    assert pol.counts["aborts"] == 1


# ---------------------------------------------------------------------------
# (b) ParallelWrapper divergence rollback: completes AND the post-rollback
#     stream is bit-comparable to a run that never saw the poisoned batch
# ---------------------------------------------------------------------------

def _wrapper(net, ckpt=None, inj=None, pol=None):
    from deeplearning4j_tpu.parallel import ParallelWrapper
    b = ParallelWrapper.Builder(net).workers(4)
    if ckpt is not None:
        b = b.checkpointing(str(ckpt))
    if inj is not None:
        b = b.fault_injector(inj)
    if pol is not None:
        b = b.health_policy(pol)
    return b.build()


def test_wrapper_rollback_completes_and_is_bit_comparable(tmp_path):
    batches = list(_data(128, seed=5).batch_by(16))    # 8 batches

    inj = FaultInjector(seed=0)
    inj.plan("wrapper.batch", on_call=5, corrupt=200.0)  # finite divergence
    pol = TrainingHealthPolicy(grad_norm_limit=50.0, max_consecutive_bad=4)
    net = _reg_net(seed=5)
    pw = _wrapper(net, ckpt=tmp_path / "ck", inj=inj, pol=pol)
    pw.fit(ListDataSetIterator(batches))               # completes

    assert pol.counts["spikes"] == 1
    assert pol.counts["rollbacks"] == 1
    rb = [e for e in pol.events if e["kind"] == "rollback"][0]
    assert rb["restoredRound"] == 5      # the last good round, by name
    assert net.conf.iteration_count == 7
    assert np.isfinite(net.params()).all()

    # bit-comparability bar (the PR 1 crash-resume standard): the rollback
    # restored rng AND counters, so the run equals one whose stream simply
    # never contained the poisoned batch. The reference arms the same
    # watchdog (which never fires on the clean stream): the health-emitting
    # step is a different XLA program from the plain step, and XLA:CPU under
    # jax 0.9.0 rounds 4 of the 75 parameters 1 ulp (<= 7.5e-9) apart
    # between the two with no fault and no restore involved (measured, PR
    # 21) — holding the program fixed isolates what this test is about, the
    # orbax restore, which IS bit-exact.
    ref = _reg_net(seed=5)
    ref_pol = TrainingHealthPolicy(grad_norm_limit=50.0,
                                   max_consecutive_bad=4)
    _wrapper(ref, pol=ref_pol).fit(
        ListDataSetIterator(batches[:5] + batches[6:]))
    assert ref_pol.counts["spikes"] == 0
    assert ref.conf.iteration_count == net.conf.iteration_count
    np.testing.assert_array_equal(np.asarray(net.params()),
                                  np.asarray(ref.params()))


def test_wrapper_nan_round_skipped_params_identical(tmp_path):
    batches = list(_data(64, seed=8).batch_by(16))     # 4 batches
    inj = FaultInjector(seed=0)
    inj.plan("wrapper.batch", on_call=1, corrupt="nan")
    pol = TrainingHealthPolicy(max_consecutive_bad=4)
    net = _net(seed=8)
    pw = _wrapper(net, inj=inj, pol=pol)
    snaps = []

    class Snap:
        def iteration_done(self, model, iteration):
            snaps.append(model.params())

    net.add_listener(Snap())
    pw.fit(ListDataSetIterator(batches))
    assert pol.counts["skips"] == 1
    np.testing.assert_array_equal(snaps[1], snaps[0])  # round 2 withheld
    assert not np.array_equal(snaps[2], snaps[1])
    assert np.isfinite(net.params()).all()


def test_wrapper_consecutive_faults_abort_with_diagnostic(tmp_path):
    batches = list(_data(128, seed=9).batch_by(16))
    inj = FaultInjector(seed=0)
    inj.plan("wrapper.batch", on_calls=[2, 3], corrupt="nan")
    pol = TrainingHealthPolicy(max_consecutive_bad=2)
    net = _net(seed=9)
    pw = _wrapper(net, inj=inj, pol=pol)
    with pytest.raises(TrainingDivergedError, match="offending rounds"):
        pw.fit(ListDataSetIterator(batches))
    assert pol.counts["aborts"] == 1
    # the diagnostic names the offending rounds (1-based round numbers)
    assert "[3, 4]" in pol.diagnose()


def test_wrapper_rollback_without_checkpoint_degrades_to_count(tmp_path):
    batches = list(_data(64, seed=10).batch_by(16))
    inj = FaultInjector(seed=0)
    inj.plan("wrapper.batch", on_call=1, corrupt=200.0)
    pol = TrainingHealthPolicy(grad_norm_limit=50.0, max_consecutive_bad=4)
    net = _reg_net(seed=10)
    pw = _wrapper(net, inj=inj, pol=pol)     # no checkpointing configured
    pw.fit(ListDataSetIterator(batches))     # completes anyway
    assert pol.counts["spikes"] == 1
    assert pol.counts["rollbacks"] == 0      # no seam: counted, continued
    assert net.conf.iteration_count == 4


# ---------------------------------------------------------------------------
# TrainingMaster path (k-local-steps mode: per-step device skip inside the
# scan, round-level health, rollback through the master's checkpoint seam)
# ---------------------------------------------------------------------------

def _master(ckpt=None, inj=None, pol=None):
    from deeplearning4j_tpu.parallel import ParameterAveragingTrainingMaster
    b = (ParameterAveragingTrainingMaster.Builder(batch_size_per_worker=4)
         .workers(4).averaging_frequency(2).rdd_training_approach("direct"))
    if ckpt is not None:
        b = b.checkpoint_directory(str(ckpt))
    if inj is not None:
        b = b.fault_injector(inj)
    if pol is not None:
        b = b.health_policy(pol)
    return b.build()


def test_master_kstep_nan_skip_and_divergence_rollback(tmp_path):
    ds = _data(128, seed=12)        # 8 global batches -> 4 rounds of k=2

    # round 2 (batch idx 2) gets a NaN batch: skipped on device; round 4
    # (batch idx 6) diverges: rolled back through the MASTER's checkpoints
    inj = FaultInjector(seed=0)
    inj.plan("wrapper.batch", on_call=2, corrupt="nan")
    inj.plan("wrapper.batch", on_call=6, corrupt=300.0)
    pol = TrainingHealthPolicy(grad_norm_limit=50.0, max_consecutive_bad=4)
    net = _reg_net(seed=12)
    tm = _master(ckpt=tmp_path / "ck", inj=inj, pol=pol)
    tm.execute_training(net, ds)                       # completes

    # the poisoned global batch = one skipped LOCAL step on each of the
    # 4 devices; the round is PARTIAL (4/8 steps bad): counted without
    # escalating, round score stays finite, checkpoint cadence unbroken
    assert pol.counts["skips"] == 4
    # the partial round did not escalate: only the FINAL round's spike
    # contributes to the consecutive-bad streak
    assert pol.consecutive_bad == 1
    partial = [e for e in pol.events
               if e["kind"] == "skip" and "partial" in e["reason"]]
    assert partial and partial[0]["reason"].startswith("4/8")
    assert np.isfinite(partial[0]["score"])
    assert pol.counts["spikes"] == 1
    assert pol.counts["rollbacks"] == 1
    assert np.isfinite(net.params()).all()
    rb = [e for e in pol.events if e["kind"] == "rollback"][0]
    assert rb["restoredRound"] is not None


# ---------------------------------------------------------------------------
# (e) iterator-boundary batch validation
# ---------------------------------------------------------------------------

def test_validator_raise_skip_count_policies():
    good = _data(16, seed=0)
    bad = _nan_batch()

    with pytest.raises(BatchValidationError, match="non-finite"):
        DataSetValidator(policy="raise").validate(bad)

    pol = TrainingHealthPolicy()
    v = DataSetValidator(policy="skip", health_policy=pol)
    assert v.validate(bad) is None
    assert v.validate(good) is good
    assert (v.rejected, v.passed) == (1, 1)
    assert pol.counts["validation_rejects"] == 1

    v2 = DataSetValidator(policy="count")
    assert v2.validate(bad) is bad            # passes through, counted
    assert v2.rejected == 1


def test_validator_shape_and_dtype_checks():
    ds = _data(8, seed=0)
    with pytest.raises(BatchValidationError, match="feature shape"):
        DataSetValidator(policy="raise", feature_shape=(7,)).validate(ds)
    with pytest.raises(BatchValidationError, match="label shape"):
        DataSetValidator(policy="raise", label_shape=(5,)).validate(ds)
    with pytest.raises(BatchValidationError, match="dtype"):
        DataSetValidator(policy="raise", dtypes="iu").validate(ds)
    # misaligned labels
    mis = DataSet(np.zeros((8, 5), np.float32), np.zeros((4, 3), np.float32))
    with pytest.raises(BatchValidationError, match="disagrees"):
        DataSetValidator(policy="raise").validate(mis)
    # a clean batch passes all configured checks
    ok = DataSetValidator(policy="raise", feature_shape=(5,),
                          label_shape=(3,), dtypes="f").validate(ds)
    assert ok is ds


def test_validator_skip_works_through_async_staging():
    inj = FaultInjector(seed=0)
    inj.plan("data.batch", on_call=3, corrupt="nan")
    pol = TrainingHealthPolicy()
    v = DataSetValidator(policy="skip", fault_injector=inj,
                         health_policy=pol)
    batches = list(_data(96, seed=1).batch_by(16))     # 6 batches
    it = AsyncDataSetIterator(ListDataSetIterator(batches), validator=v,
                              device_put=False)
    seen = [it.next_batch() for _ in iter(lambda: it.has_next(), False)]
    assert len(seen) == 5                    # the poisoned batch vanished
    assert v.rejected == 1
    assert pol.counts["validation_rejects"] == 1
    assert all(np.isfinite(np.asarray(b.features)).all() for b in seen)


def test_validator_raise_surfaces_through_async_not_hangs():
    inj = FaultInjector(seed=0)
    inj.plan("data.batch", on_call=1, corrupt="inf")
    v = DataSetValidator(policy="raise", fault_injector=inj)
    batches = list(_data(64, seed=2).batch_by(16))
    it = AsyncDataSetIterator(ListDataSetIterator(batches), validator=v,
                              device_put=False)
    with pytest.raises(RuntimeError) as ei:
        while it.has_next():
            it.next_batch()
    assert isinstance(ei.value.__cause__, BatchValidationError)


# ---------------------------------------------------------------------------
# (f) watchdog events reach the StatsListener storage
# ---------------------------------------------------------------------------

def test_stats_listener_reports_run_health():
    from deeplearning4j_tpu.ui.stats import StatsListener
    from deeplearning4j_tpu.ui.storage import InMemoryStatsStorage

    storage = InMemoryStatsStorage()
    pol = TrainingHealthPolicy(max_consecutive_bad=5)
    net = _net(seed=13).training_health(pol)
    net.set_listeners(StatsListener(storage, session_id="health_s"))
    net.fit(_data(32, seed=13))
    net.fit(_nan_batch())

    updates = storage.get_all_updates("health_s")
    assert updates, "no reports reached storage"
    last = updates[-1]
    assert last["health"]["counts"]["skips"] == 1
    assert last["health"]["lastEvent"]["kind"] == "skip"
