"""Trainer — the one training path behind both network containers.

`MultiLayerNetwork` (parameters as a list, one entry a layer) and
`ComputationGraph` (a dict, one entry a layer vertex) inherit it, and
`ParallelWrapper` shards the raw step it builds. The ENTIRE training step

    (params, updater_state, model_state, batch) ->
        (params', updater_state', model_state', score)

is ONE donated, jit-compiled XLA program: forward + loss + autodiff backward
+ updater math + parameter update fuse together. What lives here, once: the
gradient / updater / raw-step builders, the jitted step with its
device-resident loop state, the fit loops (iterator, single step, fused
group, TBPTT and fused TBPTT) with their epilogue, and the reference's
flattened-parameter contract (params()/set_params() expose one flat vector
in layer order; device-side storage is the per-layer pytree, which is what
lets XLA donate and alias buffers).

A container supplies only what differs between a list and a graph:

  * `_loss_fn(params, state, features, labels, fmask, lmask, rng, train,
    carries=None, ...) -> (score, (state', carries', ...))`
  * `_layer_items()`: its trainable layers in flattened-parameter order as
    `(key, LayerConf)` pairs (index / vertex name), and `_per_layer(values)`:
    one value a layer, in that order, as its container (list / dict)
  * `_canon_batch(features, labels, fmask, lmask)`: loose arrays in the raw
    step's layout (bare arrays / name-keyed dict + label list)
  * `_init_carries(batch_size)`: fresh RNN carries for TBPTT

Solver semantics: OptimizationAlgorithm.STOCHASTIC_GRADIENT_DESCENT with
numIterations repeats per minibatch, matching
optimize/solvers/StochasticGradientDescent.java:51-72.
"""
from __future__ import annotations

import copy
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..common import health as H
from ..datasets.dataset import DataSet, MultiDataSet
from ..datasets.iterators import (AsyncDataSetIterator, DataSetIterator,
                                  next_processed, wrap_async_for_fit)
from . import fused as F
from .updater import updaters as U

_PARTS = ("features", "labels", "fmask", "lmask")


class Trainer:
    def __init__(self, conf):
        self.conf = conf
        g = conf.global_conf
        dt = str(g.get("data_type", "float32"))
        self.compute_dtype = {"bfloat16": jnp.bfloat16,
                              "float64": jnp.float64}.get(dt, jnp.float32)
        # param storage dtype: float32 unless float64 requested (gradient
        # checks force double, like the reference's GradientCheckUtil)
        self.param_dtype = jnp.float64 if dt == "float64" else jnp.float32
        self._params = None          # per layer: dict[str, Array]
        self._updater_state = None   # per layer: dict[var, state-dict]
        self._model_state = None     # per layer: dict (BN running stats)
        self._rng = jax.random.PRNGKey(int(g.get("seed", 123)))
        self.listeners = []
        self._score = None
        self._last_batch_size = 0
        self._jit_step = None
        self._jit_forward = {}
        self._rnn_state = None       # carried state for rnnTimeStep
        self._loop = None            # device-resident {iteration, rng}
        # what common/health.py, nn/fused.py and the list container's
        # activation statistics arm; ParallelWrapper watches the two
        # generations to rebuild its own compiled step
        self._health_policy = None
        self._health_gen = 0
        self._health_ckpt = None
        self._step_emits_health = False
        self._act_stats_cfg = None   # (max_channels, max_size) when on
        self._act_stats_gen = 0
        self._last_activation_stats = None
        self._fused_steps = 1
        self._fused_cache = None

    # ------------------------------------------------------------------
    # Init — reference MultiLayerNetwork.init():398-465 /
    # ComputationGraph.init:281-345
    # ------------------------------------------------------------------
    def init(self, parameters=None, clone_parameters=False):
        if self._params is None:
            t0 = time.monotonic()
            with obs.TRACER.span("train.init", cat="train"):
                layers = [layer for _, layer in self._layer_items()]
                keys = jax.random.split(self._rng, len(layers) + 1)
                self._rng = keys[0]
                self._params = self._per_layer(
                    layer.init_params(keys[i + 1], self.param_dtype)
                    for i, layer in enumerate(layers))
                self._model_state = self._initial_state()
                self._init_updater_state()
            obs.default_registry().counter("train.init_s").inc(
                time.monotonic() - t0)
        if parameters is not None:
            self.set_params(parameters)
        return self

    def _initial_state(self):
        """Every layer's state as it starts, in the container's layout (a
        graph adds the vertices that hold state and no parameters)."""
        return self._per_layer(layer.init_state()
                               for _, layer in self._layer_items())

    def _init_updater_state(self):
        sd = self.conf.global_conf.get("updater_state_dtype")

        def state_of(key, layer):
            init_fn, _ = U.get(layer.updater or "sgd")
            st = {k: init_fn(v) for k, v in self._params[key].items()}
            return U.cast_updater_state(st, sd)

        self._updater_state = self._per_layer(
            state_of(key, layer) for key, layer in self._layer_items())

    def _ensure_init(self):
        if self._params is None:
            self.init()

    # ------------------------------------------------------------------
    # The fused train step (jitted, donated)
    # ------------------------------------------------------------------
    def make_grad_fn(self, collect_acts=False):
        """(params, state, batch) -> (grads, score, new_state, new_carries
        [, act_summaries]). The gradient half of the step — what an async
        parameter-server worker computes on a (possibly stale) parameter
        snapshot (reference ParameterServerParallelWrapper.java worker push
        path). collect_acts=True (the list container's activation
        statistics) appends the on-device activation summaries of the
        training forward (BaseStatsListener role)."""
        more = (True,) if collect_acts else ()

        def grad_fn(params, state, batch):
            (score, aux), grads = jax.value_and_grad(
                self._loss_fn, has_aux=True)(
                    params, state, batch["features"], batch["labels"],
                    batch.get("fmask"), batch.get("lmask"), batch["rng"],
                    True, batch.get("carries"), *more)
            return (grads, score) + tuple(aux)
        return grad_fn

    def make_apply_fn(self):
        """(params, ustate, grads, iteration) -> (new_params, new_ustate).
        The updater half of the step — gradient normalization, LR schedule,
        per-variable updater state machine (reference LayerUpdater.java:72 /
        ComputationGraphUpdater)."""
        items = self._layer_items()

        @jax.named_scope("update")
        def apply_updates(params, ustate, grads, iteration):
            minimize = self.conf.global_conf.get("minimize", True)
            # into copies of what came in: a list stays a list, a dict a dict
            new_params = copy.copy(params)
            new_ustate = copy.copy(ustate)
            for key, layer in items:
                g_l = U.normalize_gradients(
                    grads[key], layer.gradient_normalization,
                    layer.gradient_normalization_threshold or 1.0)
                _, apply_fn = U.get(layer.updater or "sgd")
                hp = layer.updater_hp()
                p_new, s_new = {}, {}
                for k, p in params[key].items():
                    base_lr = layer.learning_rate or 0.1
                    if k in ("b", "beta") and layer.bias_learning_rate is not None:
                        base_lr = layer.bias_learning_rate
                    lr = U.schedule_lr(
                        base_lr, layer.lr_policy or "none", iteration,
                        decay_rate=layer.lr_policy_decay_rate or 0.0,
                        steps=layer.lr_policy_steps or 1.0,
                        power=layer.lr_policy_power or 1.0,
                        schedule_map=layer.lr_schedule,
                        max_iterations=layer.lr_policy_max_iterations)
                    upd, s_k = apply_fn(ustate[key][k], g_l[k], lr, hp)
                    p_new[k] = p - upd if minimize else p + upd
                    # keep the stored state dtype (bf16 when
                    # updater_state_dtype is set; math promotes to f32)
                    s_new[k] = jax.tree.map(
                        lambda a, old: a.astype(old.dtype), s_k,
                        ustate[key][k])
                new_params[key] = p_new
                new_ustate[key] = s_new
            return new_params, new_ustate

        return apply_updates

    def make_raw_step(self, collect_acts=False, emit_health=False):
        """The un-jitted training step over a batch dict — the compilation
        unit shared by the single-chip path, ParallelWrapper's sharded paths,
        and TrainingMaster. batch keys: features, labels, fmask, lmask,
        iteration, rng, carries (optional). collect_acts=True appends the
        on-device activation summaries to the return tuple; emit_health=True
        appends (LAST) the scalar health pytree (grad norms, score, finite
        flag) and applies the update CONDITIONALLY — `jnp.where` on the
        all-finite predicate, so a NaN/Inf batch leaves params, updater
        state, model state and carries bit-identical without a host
        round-trip (the training-health watchdog's on-device sentinel).
        With both flags False the tuple shape — and compiled program — is
        untouched."""
        grad_fn = self.make_grad_fn(collect_acts)
        apply_updates = self.make_apply_fn()

        def step(params, ustate, state, batch):
            grads, score, new_state, new_carries, *acts = grad_fn(
                params, state, batch)
            new_params, new_ustate = apply_updates(params, ustate, grads,
                                                   batch["iteration"])
            if emit_health:
                with jax.named_scope("health"):
                    health = H.grad_health(grads, score)
                    ok = health["all_finite"]
                    new_params = H.gate_update(ok, new_params, params)
                    new_ustate = H.gate_update(ok, new_ustate, ustate)
                    new_state = H.gate_update(ok, new_state, state)
                    if batch.get("carries") is not None:
                        new_carries = H.gate_update(ok, new_carries,
                                                    batch["carries"])
                return ((new_params, new_ustate, new_state, score,
                         new_carries) + tuple(acts) + (health,))
            return ((new_params, new_ustate, new_state, score, new_carries)
                    + tuple(acts))

        return step

    def _make_step(self):
        emit_health = self._health_policy is not None
        self._step_emits_health = emit_health
        raw = self.make_raw_step(self._act_stats_cfg is not None,
                                 emit_health)

        def step(params, ustate, state, loop, features, labels, fmask,
                 lmask, carries=None):
            # `loop` = {"iteration": f32[], "rng": key} is device-resident
            # train-loop state: the iteration counter (LR schedules) and the
            # PRNG key advance INSIDE the compiled step, so the host never
            # ships a scalar or splits a key per iteration (each of those is
            # its own host dispatch).
            rng, next_rng = jax.random.split(loop["rng"])
            batch = {"features": features, "labels": labels, "fmask": fmask,
                     "lmask": lmask, "iteration": loop["iteration"],
                     "rng": rng, "carries": carries}
            p, u, s, score, car, *extras = raw(params, ustate, state, batch)
            # the loop counter/rng advance on a SKIPPED step too: skips
            # consume an iteration (PaLM-style skip-and-continue), keeping
            # the device counter and the host's iteration_count in lockstep
            new_loop = {"iteration": loop["iteration"] + 1.0, "rng": next_rng}
            return (p, u, s, score, car, new_loop) + tuple(extras)

        return jax.jit(step, donate_argnums=(0, 1, 2, 3))

    def _single_step(self):
        """The jitted step, built on first use and again after a toggle: a
        StatsListener may arm activation stats from iteration_done MID-fit
        (invalidating the step); rebuild rather than crash on the next
        iteration."""
        if self._jit_step is None:
            self._jit_step = self._make_step()
        return self._jit_step

    def training_health(self, policy=True, checkpoint_dir=None,
                        checkpoint_every=10, keep_checkpoints=3):
        """Arm the training-health watchdog: the fused step emits grad
        norms + finite flags and SKIPS non-finite updates on device
        (`jnp.where`, no host round-trip); the fit loop classifies each
        step through the policy (NaN/Inf skip, EMA-z-score loss spike,
        grad-norm explosion) and responds — count-and-skip, rollback to
        the last good round (when `checkpoint_dir` gives the fit loop a
        ShardedCheckpointManager seam), abort after N consecutive bad
        steps with a diagnostic naming the offending rounds. policy=True
        uses TrainingHealthPolicy defaults; None/False disarms. One
        recompile per toggle; disarmed compiles the identical HLO as
        never-armed."""
        H.install(self, policy, checkpoint_dir, checkpoint_every,
                  keep_checkpoints)
        return self

    def fused_steps(self, k=8):
        """Fuse K optimizer steps into ONE device dispatch: the fit loops
        stage K batches (the AsyncDataSetIterator prefetch/wire machinery,
        unchanged), stack them into a [K, B, ...] super-batch (multi-input
        feature dicts and multi-output label lists stack per leaf), and run
        a single donated jitted program that `lax.scan`s the SAME raw step
        over the K batches — one host round-trip per K steps instead of
        per step (the dispatch-overhead lever for small-step configs;
        see nn/fused.py for the CPU-backend caveat on compute-bound
        steps). TBPTT fuses K segments of a sequence per dispatch, with
        RNN carries threaded through the scan.

        Semantics are pinned: `fused_steps(K)` is bit-identical to K
        sequential dispatches (params, updater state, rng stream, health
        counters); `fused_steps(1)` — the default — leaves the
        single-step program untouched (identical HLO). Ragged tails (K
        not dividing the epoch, or a short last batch) fall back to
        single-step dispatches; a health checkpoint seam clips groups at
        checkpoint boundaries so the save cadence stays counted in
        optimizer steps. Activation-stats collection
        (`collect_activation_stats`) and `num_iterations != 1` force the
        single-step path for the affected batches."""
        return F.install(self, k)

    def _fused_k(self):
        """Effective fused depth for the CURRENT batch: 1 (single-step
        path) unless armed, act-stats off and num_iterations == 1."""
        k = self._fused_steps
        if (k <= 1 or self._act_stats_cfg is not None
                or int(self.conf.global_conf.get("num_iterations", 1)) != 1):
            return 1
        return k

    def _loop_state(self):
        if self._loop is None:
            self._rng, k = jax.random.split(self._rng)
            self._loop = {
                "iteration": jnp.asarray(self.conf.iteration_count,
                                         jnp.float32),
                "rng": k,
            }
        return self._loop

    # ------------------------------------------------------------------
    # fit loops — reference MultiLayerNetwork.fit(:978) /
    # ComputationGraph.fit:809
    # ------------------------------------------------------------------
    def _batch_parts(self, ds):
        """One DataSet / MultiDataSet -> its (features, labels, fmask,
        lmask) in the raw step's layout, placed nowhere yet: the fit loops
        put each leaf on the default device, ParallelWrapper shards it."""
        if isinstance(ds, MultiDataSet):
            return self._canon_batch(ds.features, ds.labels,
                                     ds.features_masks, ds.labels_masks)
        return self._canon_batch(ds.features, ds.labels, ds.features_mask,
                                 ds.labels_mask)

    def _fit_iterator(self, data, num_epochs=1):
        """`num_epochs` passes over an iterator of DataSet / MultiDataSet
        batches: prefetch + stage off the training thread like the
        reference (fit wraps in Async(Multi)DataSetIterator), with the bf16
        feature wire for bf16 models (bit-identical — the step casts
        features anyway)."""
        wrapped_here = False
        if isinstance(data, DataSetIterator):
            # a CALLER-supplied iterator may be mid-stream and must start
            # the first epoch from position 0 (ADVICE r5): plain iterators
            # are reset BEFORE wrapping (so the fresh wrapper prefetches
            # from 0 and the epoch-0 reset skip below is trivially safe);
            # an async iterator the caller built themselves resets in the
            # loop
            wrapped_here = not isinstance(data, AsyncDataSetIterator)
            if wrapped_here:
                data.reset()
            # fused mode stages a whole super-batch ahead: deepen the
            # prefetch queue so the staging thread can fill group K+1
            # while K runs
            data = wrap_async_for_fit(
                data, self.compute_dtype,
                queue_size=max(2, self._fused_steps + 1))
        streams = hasattr(data, "has_next")
        for epoch in range(num_epochs):
            # a fresh async wrapper created here is already prefetching;
            # resetting it on epoch 0 would drain (and stage) one full pass
            # unseen
            if hasattr(data, "reset") and (
                    epoch > 0 or not wrapped_here
                    or not (streams and data.has_next())):
                data.reset()
            for l in self.listeners:
                if hasattr(l, "on_epoch_start"):
                    l.on_epoch_start(self)
            if streams:
                self._fit_stream(data)
            else:
                for ds in data:
                    self._fit_batch(ds)
            for l in self.listeners:
                if hasattr(l, "on_epoch_end"):
                    l.on_epoch_end(self)
            self.conf.epoch_count += 1
        return self

    def _fit_stream(self, it):
        while it.has_next():
            k = (self._fused_k()
                 if self.conf.backprop_type != "tbptt" else 1)
            if k <= 1:
                self._fit_batch(next_processed(it))
                continue
            group = []
            g = F.group_size(self, k)
            with obs.TRACER.span("train.stage", cat="train", k=g):
                while len(group) < g and it.has_next():
                    group.append(next_processed(it))
            if len(group) == g and F.uniform_group(group):
                self._fit_group(group)
            else:
                # ragged tail (K not dividing the epoch) or mixed batch
                # shapes: single-step dispatches, same stream
                for ds in group:
                    self._fit_batch(ds)

    def _staged(self, ds):
        parts = jax.tree.map(jnp.asarray, self._batch_parts(ds))
        self._last_batch_size = int(jax.tree.leaves(parts[0])[0].shape[0])
        return parts

    def _dispatch(self, step, *batch, **span_args):
        """The ONE call of a compiled training program: the single step and
        the fused ones alike take and return the donated training state and
        the loop state around their batch. A dispatch that traced or
        compiled is a `train.compile` span too (obs/compiles.py). Returns
        (score(s), carries, extras)."""
        with obs.TRACER.span("train.dispatch", cat="train", **span_args):
            mark = obs.compiles.mark()
            (self._params, self._updater_state, self._model_state, score,
             carries, self._loop, *extras) = step(
                 self._params, self._updater_state, self._model_state,
                 self._loop_state(), *batch)
            obs.compiles.dispatched(mark, step, **span_args)
        return score, carries, extras

    def finish_step(self, score, extras, emits_health, classify=None,
                    checkpoint=None):
        """The epilogue of one dispatched step, for the fit loops and for
        ParallelWrapper's allreduce round: take the health pytree (LAST of
        `extras` when the step emits it) and the activation summaries off
        the step's tail, classify, set the score, count the iteration, tell
        the listeners, checkpoint. Returns the action; "rollback" means
        counters/rng were already restored and the caller abandons the
        current batch / sequence.

        `classify(health, score) -> action` sets the score itself unless it
        skips or rolls back, and `checkpoint()` runs after a healthy step:
        without them the fit loops' own seam (`common.health` under the
        `train.*` spans, checkpoints only while armed), with them the
        wrapper's round checkpoints."""
        health = extras.pop() if emits_health else None
        if extras:
            self._last_activation_stats = extras[0]
            self._last_activation_stats_iter = self.conf.iteration_count
        action = H.OK
        if health is None:
            self._score = score
        elif classify is not None:
            action = classify(health, score)
        else:
            with obs.TRACER.span("train.health", cat="train"):
                action = H.finish_step(self, health, score)
        if action == H.ROLLBACK:
            return action
        self.conf.iteration_count += 1
        for l in self.listeners:
            l.iteration_done(self, self.conf.iteration_count - 1)
        if action == H.OK:
            # a skipped/diverged step is never checkpointed — the
            # last-good-round invariant the rollback seam relies on
            if checkpoint is not None:
                checkpoint()
            elif health is not None:
                with obs.TRACER.span("train.checkpoint", cat="train"):
                    H.fit_loop_checkpoint(self)
        return action

    def _fit_batch(self, ds):
        parts = self._staged(ds)
        if self.conf.backprop_type == "tbptt":
            return self._fit_tbptt(*parts)
        for _ in range(int(self.conf.global_conf.get("num_iterations", 1))):
            score, _, extras = self._dispatch(self._single_step(), *parts)
            if self.finish_step(score, extras,
                                self._step_emits_health) == H.ROLLBACK:
                break           # counters/rng restored; next batch
        return self

    def _fused_raw(self, body):
        """`body(raw, ...)` as a donated jitted program over the raw step
        the fused paths scan: never with activation summaries (`_fused_k`
        keeps an armed net on the single step)."""
        raw = self.make_raw_step(False, self._health_policy is not None)

        def prog(params, ustate, state, loop, *batch):
            return body(raw, params, ustate, state, loop, *batch)

        return jax.jit(prog, donate_argnums=(0, 1, 2, 3))

    def _finish_fused(self, scores, extras, g):
        with obs.TRACER.span("train.health", cat="train", k=g):
            return H.finish_fused(
                self, scores,
                extras[-1] if self._health_policy is not None else None, g)

    def _fit_group(self, group):
        """ONE dispatch for len(group) staged batches: stack on device,
        scan the raw step, then walk the stacked per-step scores/health
        on the host (`common.health.finish_fused` — listeners and the
        watchdog see every optimizer step). On a mid-group rollback the
        remaining staged batches re-run single-step from the restored
        state, exactly as the sequential loop would."""
        g = len(group)
        step = F.fused_program(self, ("batch", g),
                               lambda: self._fused_raw(F.scan_batches))
        batch_list = tuple(dict(zip(_PARTS, self._staged(ds)))
                           for ds in group)
        with obs.TRACER.span("train.fused_group", cat="train", k=g):
            scores, _, extras = self._dispatch(step, batch_list, k=g)
            rb = self._finish_fused(scores, extras, g)
        if rb is not None:
            for ds in group[rb + 1:]:   # counters/rng restored; replay
                self._fit_batch(ds)
        return self

    def _fit_tbptt(self, features, labels, fmask, lmask):
        """Truncated BPTT: slice the time axis into tbptt_fwd_length
        segments, carrying RNN cell state (but not gradients: carries are
        fresh inputs to the next jitted call) across segments. reference:
        MultiLayerNetwork.doTruncatedBPTT:1140 +
        updateRnnStateWithTBPTTState:1196, and ComputationGraph's TBPTT."""
        parts = (features, labels, fmask, lmask)
        T = int([f for f in jax.tree.leaves(features)
                 if f.ndim >= 3][0].shape[1])
        L = self.conf.tbptt_fwd_length
        carries = self._init_carries(self._last_batch_size)
        t0 = 0
        while t0 < T:
            # fused TBPTT: K full segments per dispatch, carries threaded
            # through the scan; the short tail segment (L not dividing T)
            # and act-stats-armed runs stay single-step
            k = self._fused_k()
            g = min(F.group_size(self, k), (T - t0) // L) if k > 1 else 1
            if g > 1:
                carries, rolled_back = self._fit_tbptt_fused(
                    parts, carries, t0, g, T, L)
                if rolled_back:     # abandon this sequence
                    return self
                t0 += g * L
                continue
            seg = _time_slices(parts, T, lambda a: a[:, t0:t0 + L])
            score, carries, extras = self._dispatch(
                self._single_step(), *seg, carries, tbptt=True)
            if self.finish_step(score, extras,
                                self._step_emits_health) == H.ROLLBACK:
                break           # abandon the rest of this sequence
            t0 += L
        return self

    def _fit_tbptt_fused(self, parts, carries, t0, g, T, L):
        """ONE dispatch for g full TBPTT segments starting at t0: the
        scan body dynamic-slices each segment out of the full sequence
        (no host-side restacking — the data crossed the wire once) and
        threads the RNN carries through the scan carry. Returns
        (carries', rolled_back)."""
        def body(raw, params, ustate, state, loop, parts, carries, t0s):
            def make_batch(s):
                return dict(zip(_PARTS, _time_slices(
                    parts, T, lambda a: jax.lax.dynamic_slice_in_dim(
                        a, s, L, axis=1))))

            return F.scan_steps(raw, params, ustate, state, loop, carries,
                                t0s, make_batch)

        key = ("tbptt", g, T, L, parts[2] is not None, parts[3] is not None)
        step = F.fused_program(self, key, lambda: self._fused_raw(body))
        t0s = jnp.arange(t0, t0 + g * L, L, dtype=jnp.int32)
        with obs.TRACER.span("train.fused_group", cat="train", k=g,
                             tbptt=True):
            scores, carries, extras = self._dispatch(
                step, parts, carries, t0s, k=g, tbptt=True)
            rb = self._finish_fused(scores, extras, g)
        return carries, rb is not None

    # ------------------------------------------------------------------
    # Score / gradients — reference computeGradientAndScore(:1807 / :952)
    # ------------------------------------------------------------------
    def score(self, data=None, training=False):
        if data is None:
            return float(self._score) if self._score is not None else float("nan")
        self._ensure_init()
        if isinstance(data, tuple):
            data = DataSet(*data)
        # masks included — dropping them silently skews validation loss on
        # variable-length sequence data
        parts = jax.tree.map(jnp.asarray, self._batch_parts(data))
        self._rng, rng = jax.random.split(self._rng)
        s, _ = self._loss_fn(self._params, self._model_state, *parts, rng,
                             training)
        return float(s)

    def compute_gradient_and_score(self, features, labels, fmask=None,
                                   lmask=None, train=True):
        """Returns (grads pytree, score). Deterministic rng for gradient checks."""
        self._ensure_init()
        parts = jax.tree.map(jnp.asarray, self._canon_batch(
            features, labels, fmask, lmask))
        (score, _), grads = jax.value_and_grad(self._loss_fn, has_aux=True)(
            self._params, self._model_state, *parts, jax.random.PRNGKey(0),
            train)
        return grads, float(score)

    # ------------------------------------------------------------------
    # Flattened-params API parity — reference init:398-465 / :281-345
    # ------------------------------------------------------------------
    def _param_leaves(self, params=None):
        params = self._params if params is None else params
        return [((key, k), params[key][k])
                for key, _ in self._layer_items()
                for k in sorted(params[key], key=_param_sort_key)]

    def params(self):
        self._ensure_init()
        vecs = [np.asarray(v).ravel() for _, v in self._param_leaves()]
        if not vecs:
            return np.zeros((0,), np.float32)
        return np.concatenate(vecs)

    def _from_flat(self, flat, leaf):
        """The parameter pytree whose leaves are `leaf(chunk, like)` of
        consecutive chunks of `flat`; returns it with the length consumed."""
        offset = 0
        out = self._per_layer(dict(self._params[key])
                              for key, _ in self._layer_items())
        for (key, k), v in self._param_leaves():
            n = int(np.prod(v.shape)) if v.shape else 1
            out[key][k] = leaf(flat[offset:offset + n].reshape(v.shape), v)
            offset += n
        return out, offset

    def set_params(self, flat):
        self._ensure_init()
        flat = np.asarray(flat).ravel()
        new_params, n = self._from_flat(
            flat, lambda chunk, v: jnp.asarray(chunk, v.dtype))
        if n != flat.size:
            raise ValueError(f"Expected {n} params, got {flat.size}")
        self._params = new_params

    setParams = set_params

    def num_params(self):
        return int(sum(int(np.prod(v.shape)) for _, v in self._param_leaves()))

    numParams = num_params

    def unflatten_params(self, flat):
        """flat vector -> per-layer param pytree (jit-traceable)."""
        return self._from_flat(
            flat, lambda chunk, v: chunk.astype(v.dtype))[0]

    def make_flat_score_fn(self, features, labels, fmask=None, lmask=None,
                           train=True):
        """Jitted score(flat_params) -> scalar, for gradient checking."""
        parts = jax.tree.map(jnp.asarray, self._canon_batch(
            features, labels, fmask, lmask))
        rng = jax.random.PRNGKey(0)

        def score_fn(flat):
            s, _ = self._loss_fn(self.unflatten_params(flat),
                                 self._model_state, *parts, rng, train)
            return s

        return jax.jit(score_fn)

    def flatten_gradients(self, grads):
        vecs = [np.asarray(g, np.float64).ravel()
                for _, g in self._param_leaves(grads)]
        return np.concatenate(vecs) if vecs else np.zeros((0,))

    # ------------------------------------------------------------------
    # Listeners / cloning
    # ------------------------------------------------------------------
    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    setListeners = set_listeners

    def clone(self):
        net = type(self)(self.conf.clone())
        if self._params is not None:
            net.init()
            # materialize COPIES: aliasing the live arrays would let the
            # next donated train step delete the clone's buffers with it
            net._params = jax.tree.map(jnp.copy, self._params)
            net._updater_state = jax.tree.map(jnp.copy, self._updater_state)
            net._model_state = jax.tree.map(jnp.copy, self._model_state)
        return net


def _time_slices(parts, T, cut):
    """One TBPTT segment of a batch's (features, labels, fmask, lmask):
    `cut(a)` of every leaf with the full time axis — features and labels
    when sequence-shaped (ndim >= 3), masks from ndim >= 2; static inputs,
    labels and masks pass through whole."""
    def cut_from(min_ndim):
        return lambda a: (cut(a) if a.ndim >= min_ndim and a.shape[1] >= T
                          else a)

    features, labels, fmask, lmask = parts
    return (jax.tree.map(cut_from(3), features),
            jax.tree.map(cut_from(3), labels),
            jax.tree.map(cut_from(2), fmask),
            jax.tree.map(cut_from(2), lmask))


def _param_sort_key(k):
    # canonical variable order: W-like first, then recurrent, then biases —
    # mirrors the reference's per-layer param layout (DefaultParamInitializer:
    # weights then bias).
    order = {"W": 0, "RW": 1, "b": 2, "gamma": 0, "beta": 1, "mean": 2, "var": 3,
             "vb": 3}
    return (order.get(k, 9), k)
