"""ParallelWrapper — multi-chip data-parallel training.

TPU-native equivalent of reference
deeplearning4j-scaleout-parallelwrapper/.../ParallelWrapper.java:44-797.

The reference spawns T threads each holding a model replica and calls
`Nd4j.averageAndPropagate` every `averagingFrequency` iterations (:179,:381),
optionally averaging updater state (:200-212). Here there are NO replicas and
NO averaging kernel: the SAME jitted training step is partitioned over a
`jax.sharding.Mesh`:

- averaging_frequency == 1 (recommended): the batch is sharded over the
  "data" axis, params replicated; XLA GSPMD inserts the gradient all-reduce
  (psum over ICI) inside the one compiled step. With common starting params
  this is mathematically the same as per-iteration parameter averaging, minus
  the replicas and the averaging kernel.

- averaging_frequency k > 1: reference semantics preserved — each device runs
  k *local* steps on its own data shard (lax.scan inside shard_map), then
  parameters (and optionally updater state, mirroring :200-212) are averaged
  via `pmean` over the data axis — ICI doing what averageAndPropagate's
  CUDA-P2P/host route did.

Builder API mirrors the reference so user code translates 1:1. Tensor
parallelism (absent in the reference, SURVEY.md §2.5) is available via
`.tensor_parallel(True)`: big dense/conv weights column-shard over the
"model" axis (see sharding.py).
"""
from __future__ import annotations

import logging

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import obs
from ..datasets.dataset import DataSet
from ..datasets.iterators import ListDataSetIterator, next_processed
from .sharding import make_mesh, put_sharded, replicate, shard_params

log = logging.getLogger(__name__)


class ParallelWrapper:
    class Builder:
        def __init__(self, model):
            self.model = model
            self._workers = None
            self._avg_freq = 1
            self._prefetch = 2
            self._avg_updaters = True
            self._tensor_parallel = False
            self._sharded_updater_state = False
            self._mesh = None
            self._checkpoint = None
            self._fault_injector = None
            self._health_policy = None

        def checkpointing(self, directory, every_n_rounds=1, keep_last=3,
                          resume=True):
            """Periodic checkpoint + crash-resume: every `every_n_rounds`
            averaging rounds (allreduce mode: one round = one batch;
            k-local-steps mode: one round = one k-group) the model's full
            training state is saved to a ShardedCheckpointManager under
            `directory`. When `resume` (default) and the directory already
            holds checkpoints, a fit() on a FRESH model restores the
            newest one and fast-forwards through the rounds it covers —
            re-running the same fit command after a crash resumes
            mid-epoch instead of restarting. Use a fresh directory for a
            genuinely new run."""
            self._checkpoint = {"directory": str(directory),
                                "every": max(1, int(every_n_rounds)),
                                "keep_last": max(1, int(keep_last)),
                                "resume": bool(resume)}
            return self

        def fault_injector(self, inj):
            """Install a `common.resilience.FaultInjector`; the wrapper
            fires site "wrapper.round" before each averaging round (the
            crash seam) and site "wrapper.batch" on every batch's
            features BEFORE staging (payload-corruption seam: a planned
            `corrupt` rule NaN/Inf/value-poisons the batch through the
            real step, exercising the training-health watchdog)."""
            self._fault_injector = inj; return self

        def health_policy(self, policy):
            """Arm the training-health watchdog
            (`common.health.TrainingHealthPolicy`, or True for defaults):
            the sharded step emits grad norms + finite flags and skips
            non-finite updates on device; the wrapper classifies each
            round and responds — count-and-skip, rollback to the last
            checkpointed round (requires `.checkpointing(...)`; a master
            may install its own seam via `_ext_rollback`), abort after N
            consecutive bad rounds."""
            self._health_policy = policy; return self

        def workers(self, n):
            self._workers = int(n); return self

        def averaging_frequency(self, k):
            self._avg_freq = max(1, int(k)); return self

        averagingFrequency = averaging_frequency

        def prefetch_buffer(self, n):
            self._prefetch = int(n); return self

        prefetchBuffer = prefetch_buffer

        def average_updaters(self, v):
            self._avg_updaters = bool(v); return self

        averageUpdaters = average_updaters

        def report_score_after_averaging(self, v):
            return self  # scores always reported

        reportScoreAfterAveraging = report_score_after_averaging

        def tensor_parallel(self, v):
            self._tensor_parallel = bool(v); return self

        def sharded_updater_state(self, v):
            """ZeRO-1 analog: partition optimizer state over the data axis
            (each device stores 1/N of the moments). Requires
            averaging_frequency == 1 (the k-local-steps path carries state
            device-locally inside shard_map)."""
            self._sharded_updater_state = bool(v); return self

        def mesh(self, mesh):
            self._mesh = mesh; return self

        def build(self):
            return ParallelWrapper(self.model, self._workers, self._avg_freq,
                                   self._avg_updaters, self._tensor_parallel,
                                   self._mesh, self._sharded_updater_state,
                                   self._checkpoint, self._fault_injector,
                                   self._health_policy)

    def __init__(self, model, workers=None, averaging_frequency=1,
                 average_updaters=True, tensor_parallel=False, mesh=None,
                 sharded_updater_state=False, checkpoint=None,
                 fault_injector=None, health_policy=None):
        self.model = model
        model._ensure_init()
        if mesh is None:
            n = workers or len(jax.devices())
            n_model = 2 if (tensor_parallel and n % 2 == 0) else 1
            mesh = make_mesh(n_data=n // n_model, n_model=n_model,
                             devices=jax.devices()[:n])
        self.mesh = mesh
        self.workers = int(mesh.shape["data"])
        self.averaging_frequency = int(averaging_frequency)
        self.average_updaters = average_updaters
        self.tensor_parallel = tensor_parallel
        self.sharded_updater_state = bool(sharded_updater_state)
        if self.sharded_updater_state and self.averaging_frequency != 1:
            raise ValueError(
                "sharded_updater_state requires averaging_frequency=1 "
                "(k-local-steps carries updater state device-locally)")
        self.checkpoint = checkpoint
        self.fault_injector = fault_injector
        # round counter + checkpoint/resume gate (one shared protocol —
        # see util.sharded_checkpoint.RoundCheckpointer); rounds are
        # monotonic across fit() calls/epochs
        from ..util.sharded_checkpoint import RoundCheckpointer
        cp = checkpoint or {}
        self._gate = RoundCheckpointer(cp.get("directory"),
                                       every=cp.get("every", 1),
                                       keep_last=cp.get("keep_last", 3),
                                       resume=cp.get("resume", True),
                                       owner="parallel wrapper")
        # training-health watchdog: arm the NET (the step emits health, the
        # policy lives on the model so StatsListener finds it); the wrapper
        # supplies the rollback seam — its own round checkpoints, or an
        # externally installed (manager, on_restored) pair (TrainingMaster)
        if health_policy is not None:
            from ..common import health as H
            H.install(model, health_policy)
        self._ext_rollback = None
        self._sharded = False
        # each compiled program with the net's generations it was built at
        # (an activation-stats / watchdog toggle rebuilds it) and what its
        # tail carries
        self._jit_step = None
        self._act_gen = self._health_gen = 0
        self._emits_health = False
        self._jit_kstep = None
        self._kstep_health_gen = 0
        self._kstep_emits_health = False

    # ------------------------------------------------------------------
    def _ensure_sharded(self):
        if self._sharded:
            return
        net = self.model
        net._params, self._param_shardings = shard_params(
            net, self.mesh, self.tensor_parallel)
        if self.sharded_updater_state:
            from .sharding import zero_state_sharding
            self._ustate_shardings = zero_state_sharding(
                net._updater_state, self.mesh)
            net._updater_state = jax.tree.map(
                lambda a, sh: put_sharded(a, sh, full_array=True),
                net._updater_state, self._ustate_shardings)
        else:
            self._ustate_shardings = None
            net._updater_state = replicate(net._updater_state, self.mesh)
        net._model_state = replicate(net._model_state, self.mesh)
        self._sharded = True

    def _put_batch(self, arr):
        """Shard a batch over the "data" axis. On a multi-host mesh `arr` is
        the process-LOCAL slice of the global batch (each host feeds its own
        shard; see distributed.process_local_batch_slice)."""
        if arr is None:
            return None
        spec = [None] * np.ndim(arr)
        spec[0] = "data"
        return put_sharded(arr, NamedSharding(self.mesh, P(*spec)))

    # -- checkpoint / crash-resume (resilience layer) -------------------
    @property
    def _round(self):
        return self._gate.round

    @property
    def _resume_round(self):
        return self._gate.resume_round

    def _round_starts(self):
        """True when this averaging round must actually run; False when a
        restored checkpoint already contains it (the round's batches are
        still consumed from the iterator so the stream stays aligned)."""
        if not self._gate.round_starts():
            return False
        if self.fault_injector is not None:
            self.fault_injector.fire("wrapper.round")
        return True

    def _round_done(self):
        with obs.TRACER.span("parallel.checkpoint", cat="train"):
            self._gate.round_done(self.model)

    def _inject_batch(self, ds):
        """Payload-corruption seam: site "wrapper.batch" over the shared
        poison-copy/rebind helper (see iterators.inject_features)."""
        from ..datasets.iterators import inject_features
        return inject_features(self.fault_injector, "wrapper.batch", ds)

    def _handle_health(self, health, round_index):
        """Classify one round's health and act. Rollback goes through the
        round-checkpoint seam; returns the action taken (abort raises)."""
        from ..common import health as H
        return H.apply_policy(self.model._health_policy, health,
                              round_index=round_index,
                              rollback=self._health_rollback)

    def _classify_round(self, health, score):
        with obs.TRACER.span("parallel.health", cat="train"):
            action = self._handle_health(health, self._gate.round)
        if action not in ("skip", "rollback"):
            self.model._score = score
        return action

    def _health_rollback(self):
        """Restore the last checkpointed round — the wrapper's own
        `.checkpointing(...)` manager, or an externally installed seam
        (`self._ext_rollback = (manager, on_restored)`, the
        TrainingMaster hookup). The restore rewinds params, updater/model
        state, rng AND counters, then the normal sharding pass
        redistributes (restoring straight into mesh-sharded donated
        buffers is not supported — same constraint as crash-resume).
        Returns the restored round, or False when no checkpoint exists."""
        net = self.model
        if self._ext_rollback is not None:
            mgr, on_restored = self._ext_rollback
        else:
            mgr = self._gate.manager()
            on_restored = lambda s: setattr(self._gate, "round", int(s))  # noqa: E731
        if mgr is None or mgr.latest_step() is None:
            return False
        last = mgr.latest_step()
        # materialize to host so the restore template is unsharded, then
        # re-run the sharding pass (single-process meshes; a multi-host
        # rollback would restore sharded directly like crash-resume)
        for attr in ("_params", "_updater_state", "_model_state"):
            setattr(net, attr,
                    jax.tree.map(lambda a: np.asarray(a),
                                 getattr(net, attr)))
        mgr.restore(net, last)
        self._sharded = False
        self._ensure_sharded()
        on_restored(last)
        return last

    # ------------------------------------------------------------------
    def fit(self, data, num_epochs=1):
        net = self.model
        # resume BEFORE sharding: the restore then lands on host/default-
        # device arrays and the normal sharding pass distributes them —
        # identical to the fresh-net flow (restoring into already-mesh-
        # sharded donated buffers aborts XLA CPU)
        self._gate.maybe_resume(net)
        self._ensure_sharded()
        from ..datasets.dataset import MultiDataSet
        if isinstance(data, (DataSet, MultiDataSet)):
            data = ListDataSetIterator([data])
        for _ in range(num_epochs):
            data.reset()
            if self.averaging_frequency == 1:
                self._fit_allreduce(data)
            else:
                self._fit_local_steps(data)
        return self

    # -- mode 1: per-step gradient allreduce (GSPMD via shardings) -----
    def _ensure_allreduce_step(self):
        net = self.model
        gens = (net._act_stats_gen, net._health_gen)
        if (self._act_gen, self._health_gen) != gens:
            self._jit_step = None     # activation-stats / watchdog toggle
        if self._jit_step is None:
            self._act_gen, self._health_gen = gens
            self._emits_health = net._health_policy is not None
            # honor the net's activation-stats mode (StatsListener arming
            # works identically under the sharded path); the k-local-steps
            # mode does NOT collect (k batches per program — see
            # collect_activation_stats docstring). The psum'd gradients are
            # replicated, so the health predicate — and the on-device
            # skip — is identical on every device.
            raw = net.make_raw_step(
                collect_acts=net._act_stats_cfg is not None,
                emit_health=self._emits_health)
            if self._ustate_shardings is not None:
                inner, shardings = raw, self._ustate_shardings

                def raw(params, ustate, state, batch):
                    p, u, s, score, car, *extras = inner(params, ustate,
                                                         state, batch)
                    # pin the ZeRO layout on the state OUTPUT so GSPMD keeps
                    # the optimizer update partitioned (and the donated input
                    # buffer is reusable) instead of re-replicating it
                    u = jax.tree.map(jax.lax.with_sharding_constraint, u,
                                     shardings)
                    return (p, u, s, score, car) + tuple(extras)
            self._jit_step = jax.jit(raw, donate_argnums=(0, 1, 2))
        return self._jit_step

    def _sharded_batch(self, ds, step_rng):
        net = self.model
        feats, labels, fm, lm = net._batch_parts(ds)
        put = self._put_batch
        batch = {
            "features": jax.tree.map(put, feats),
            "labels": jax.tree.map(put, labels),
            "fmask": jax.tree.map(put, fm) if fm is not None else None,
            "lmask": jax.tree.map(put, lm) if lm is not None else None,
            "iteration": jnp.asarray(net.conf.iteration_count, jnp.float32),
            "rng": step_rng,
        }
        from .sharding import is_multiprocess_mesh
        if is_multiprocess_mesh(self.mesh):
            # host-committed scalars (same value on every process) are
            # what a multi-process jit accepts; local device arrays are
            # not addressable across hosts
            batch["iteration"] = np.float32(net.conf.iteration_count)
            batch["rng"] = np.asarray(step_rng)
        return batch, feats

    def lower_step(self, ds):
        """Lower (trace+compile without executing) the sharded allreduce
        step for one DataSet — the mesh-cost profiling hook
        (`mesh_cost.hlo_collective_footprint` reads collective counts/bytes
        off the compiled HLO to catch sharding regressions without
        hardware)."""
        net = self.model
        self._ensure_sharded()
        step = self._ensure_allreduce_step()
        batch, _ = self._sharded_batch(ds, jax.random.PRNGKey(0))
        return step.lower(net._params, net._updater_state,
                          net._model_state, batch)

    def _fit_allreduce(self, it):
        net = self.model
        while it.has_next():
            # re-checked per batch: a StatsListener may arm activation
            # stats from iteration_done mid-fit (generation bump); the
            # cached-step fast path is one attribute compare
            step = self._ensure_allreduce_step()
            ds = next_processed(it)
            if not self._round_starts():
                continue      # round covered by the restored checkpoint
            ds = self._inject_batch(ds)
            with obs.TRACER.span("parallel.stage", cat="train"):
                net._rng, step_rng = jax.random.split(net._rng)
                batch, feats = self._sharded_batch(ds, step_rng)
            net._last_batch_size = int(
                jax.tree.leaves(feats)[0].shape[0])
            with obs.TRACER.span("parallel.dispatch", cat="train"):
                mark = obs.compiles.mark()
                (net._params, net._updater_state, net._model_state, score,
                 _, *extras) = step(net._params, net._updater_state,
                                    net._model_state, batch)
                obs.compiles.dispatched(mark, step)
            # the trainer's epilogue over the wrapper's seam: a rolled-back
            # round rewound counters/rng, and the next batch retrains
            net.finish_step(score, extras, self._emits_health,
                            classify=self._classify_round,
                            checkpoint=self._round_done)

    # -- mode 2: k local steps then parameter averaging ----------------
    def _fit_local_steps(self, it):
        k = self.averaging_frequency
        pending = []
        while it.has_next():
            pending.append(self._inject_batch(next_processed(it)))
            if len(pending) == k:
                if self._round_starts():
                    if self._run_kstep(pending) == "ok":
                        self._round_done()
                pending = []
        if pending:
            # ragged tail: run the true remaining batches (the jitted k-step
            # retraces for the smaller leading axis) — no duplicated steps.
            if self._round_starts():
                if self._run_kstep(pending) == "ok":
                    self._round_done()

    @staticmethod
    def _pad_to(arr, b):
        """Pad a ragged tail batch up to size b by wrapping rows (keeps shapes
        static for the compiled k-step)."""
        if arr is None or arr.shape[0] == b:
            return arr
        idx = np.resize(np.arange(arr.shape[0]), b)
        return arr[idx]

    def _build_kstep(self):
        net = self.model
        mesh = self.mesh
        avg_upd = self.average_updaters
        emit_h = net._health_policy is not None
        self._kstep_emits_health = emit_h
        raw = net.make_raw_step(emit_health=emit_h)

        def local_steps(params, ustate, state, batches):
            def body(carry, batch_t):
                p, u, s = carry
                p, u, s, score, _, *h = raw(p, u, s, batch_t)
                return (p, u, s), ((score, h[0]) if emit_h else score)
            # params arrive replicated (unvarying over "data") but every
            # local step folds in this device's shard, so the carry is
            # device-varying from step one: say so on the way in, or the
            # scan's carry-in / carry-out types disagree under check_vma
            init = jax.lax.pcast((params, ustate, state), "data",
                                 to="varying")
            (p, u, s), ys = jax.lax.scan(body, init, batches)
            scores = ys[0] if emit_h else ys
            # the TPU-native averageAndPropagate: pmean over ICI
            p = jax.lax.pmean(p, "data")
            if avg_upd:
                u = jax.lax.pmean(u, "data")
            s = jax.lax.pmean(s, "data")
            if not emit_h:
                score = jax.lax.pmean(jnp.mean(scores), "data")
                return p, u, s, score
            # each device skipped ITS bad local steps independently (its
            # shard, its predicate); the pmean then averages the healthy
            # survivors. The round score averages the FINITE step scores
            # only — a skipped step's NaN must not poison the score of a
            # round whose averaged params are healthy. The emitted health
            # is the round's WORST case across the k steps and the data
            # axis plus a skipped-step count, so the host policy can tell
            # a partial round (some steps skipped, progress made) from a
            # fully-poisoned one.
            hs = ys[1]
            fin = hs["all_finite"]                       # [k] per device
            n_ok = jax.lax.psum(jnp.sum(fin.astype(jnp.float32)), "data")
            s_sum = jax.lax.psum(jnp.sum(jnp.where(fin, scores, 0.0)),
                                 "data")
            score = jnp.where(n_ok > 0, s_sum / jnp.maximum(n_ok, 1.0),
                              jnp.float32(jnp.nan))
            health = {
                "score": score,
                "grad_norm": jax.lax.pmax(jnp.max(hs["grad_norm"]), "data"),
                "layer_grad_norms": jax.tree.map(
                    lambda a: jax.lax.pmax(jnp.max(a), "data"),
                    hs["layer_grad_norms"]),
                "bad_steps": jax.lax.psum(
                    jnp.sum(1 - fin.astype(jnp.int32)), "data"),
                "steps": fin.shape[0] * jax.lax.psum(1, "data"),
                "all_finite": jax.lax.pmin(
                    jnp.all(fin).astype(jnp.int32), "data"),
            }
            return p, u, s, score, health

        repl = P()
        _SHARDED_KEYS = ("features", "labels", "fmask", "lmask")

        def build(batches_tree):
            pspec = jax.tree.map(lambda _: repl, net._params)
            uspec = jax.tree.map(lambda _: repl, net._updater_state)
            sspec = jax.tree.map(lambda _: repl, net._model_state)
            bspec = {k: (P(None, "data") if k in _SHARDED_KEYS else P())
                     for k, v in batches_tree.items() if v is not None}
            out_specs = (pspec, uspec, sspec, repl)
            if emit_h:
                out_specs = out_specs + (repl,)   # prefix for the health dict
            fn = jax.shard_map(local_steps, mesh=mesh,
                               in_specs=(pspec, uspec, sspec, bspec),
                               out_specs=out_specs)
            return jax.jit(fn, donate_argnums=(0, 1, 2))
        return build

    def _kstep_batches(self, batches, advance_rng=True):
        """Stack k DataSets into the k-step program's batches_tree
        (ragged tail rows pad by wrapping; multi-host leaves become
        global arrays). Shared by `_run_kstep` and `lower_kstep`
        (which passes advance_rng=False — lowering must not consume
        the model's rng stream). Returns (batches_tree, B)."""
        net = self.model
        k = len(batches)
        parts = [net._batch_parts(b) for b in batches]
        # batch size from the first FEATURE leaf so multi-input feature
        # dicts/lists (ComputationGraph / MultiDataSet) size correctly
        B = max(int(jax.tree.leaves(p[0])[0].shape[0]) for p in parts)

        def stack(*leaves):
            return jnp.asarray(np.stack(
                [self._pad_to(np.asarray(x), B) for x in leaves]))

        feats = jax.tree.map(stack, *[p[0] for p in parts])  # [k, B, ...]
        labs = jax.tree.map(stack, *[p[1] for p in parts])
        if advance_rng:
            net._rng, sub = jax.random.split(net._rng)
        else:
            sub = jax.random.PRNGKey(0)
        rngs = jax.random.split(sub, k)
        batches_tree = {
            "features": feats,   # [k, B, ...]
            "labels": labs,
            "iteration": jnp.arange(net.conf.iteration_count,
                                    net.conf.iteration_count + k,
                                    dtype=jnp.float32),
            "rng": rngs,
        }
        if parts[0][2] is not None:
            batches_tree["fmask"] = jax.tree.map(stack,
                                                 *[p[2] for p in parts])
        if parts[0][3] is not None:
            batches_tree["lmask"] = jax.tree.map(stack,
                                                 *[p[3] for p in parts])
        from .sharding import is_multiprocess_mesh
        if is_multiprocess_mesh(self.mesh):
            # multi-host: leaves must be global arrays before the jit call
            # (each process contributed its local [k, B_local, ...] stack)
            shard_keys = ("features", "labels", "fmask", "lmask")
            for key in list(batches_tree):
                sp = (P(None, "data") if key in shard_keys else P())
                batches_tree[key] = jax.tree.map(
                    lambda a: put_sharded(a, NamedSharding(self.mesh, sp)),
                    batches_tree[key])
        return batches_tree, B

    def lower_kstep(self, batches):
        """Lower (trace+compile without executing) the k-local-steps
        parameter-averaging program for a list of k DataSets — the
        mesh-cost profiling hook for averaging_frequency > 1, sibling of
        `lower_step` (the collective-budget net pins its footprint)."""
        self._ensure_sharded()
        batches_tree, _ = self._kstep_batches(batches, advance_rng=False)
        return self._build_kstep()(batches_tree).lower(
            self.model._params, self.model._updater_state,
            self.model._model_state, batches_tree)

    def _run_kstep(self, batches):
        net = self.model
        k = len(batches)
        with obs.TRACER.span("parallel.stage", cat="train", k=k):
            batches_tree, B = self._kstep_batches(batches)
        if self._kstep_health_gen != net._health_gen:
            self._jit_kstep = None         # watchdog toggled mid-life
            self._kstep_health_gen = net._health_gen
        mark = obs.compiles.mark()     # ahead of the build: what it compiles
        if self._jit_kstep is None:    # ahead of time is this dispatch's
            self._jit_kstep = self._build_kstep()(batches_tree)
        with obs.TRACER.span("parallel.dispatch", cat="train", k=k):
            (net._params, net._updater_state, net._model_state,
             score, *extra) = self._jit_kstep(
                 net._params, net._updater_state, net._model_state,
                 batches_tree)
            obs.compiles.dispatched(mark, self._jit_kstep, k=k)
        action = "ok"
        if self._kstep_emits_health:
            with obs.TRACER.span("parallel.health", cat="train", k=k):
                action = self._handle_health(extra[0], self._gate.round)
            if action == "rollback":
                return action   # counters/rng rewound by the restore
        if action != "skip":
            net._score = score
        net._last_batch_size = B
        net.conf.iteration_count += k
        for l in net.listeners:
            l.iteration_done(net, net.conf.iteration_count - 1)
        return action
