"""MultiLayerNetwork — the sequential network container.

TPU-native equivalent of reference nn/multilayer/MultiLayerNetwork.java (2,486
LoC): init (:398-465 flattened params), fit(DataSetIterator) (:978), backprop
(:1064), output/feedForward (:1521/:657), computeGradientAndScore (:1807),
evaluate (:1574), TBPTT (:1140).

TPU-first redesign (SURVEY.md §7.1.3): instead of the reference's op-by-op
execution (per-layer activate/backpropGradient + separate updater ops +
in-place stepFunction on a flattened params vector), the ENTIRE training step

    (params, updater_state, model_state, batch) ->
        (params', updater_state', model_state', score)

is ONE donated, jit-compiled XLA program: forward + loss + autodiff backward +
updater math + parameter update fuse together; XLA schedules matmuls on the
MXU and fuses elementwise chains. The reference's flattened-params contract is
preserved at the API level (params()/set_params() expose a single flat vector
in layer order) but device-side storage is the natural per-layer pytree, which
is what lets XLA donate and alias buffers.

Solver semantics: OptimizationAlgorithm.STOCHASTIC_GRADIENT_DESCENT with
numIterations repeats per minibatch, matching
optimize/solvers/StochasticGradientDescent.java:51-72. (LBFGS/CG/line-search
variants live in optimize/solvers.py.)
"""
from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..datasets.dataset import DataSet
from ..datasets.iterators import (AsyncDataSetIterator, DataSetIterator,
                                  ListDataSetIterator, next_processed)
from .conf.layers.base import layer_scope
from .conf.neural_net_configuration import MultiLayerConfiguration
from .updater import updaters as U

log = logging.getLogger(__name__)


class MultiLayerNetwork:
    def __init__(self, conf: MultiLayerConfiguration):
        self.conf = conf
        self.layers = conf.layers
        g = conf.global_conf
        dt = str(g.get("data_type", "float32"))
        self.compute_dtype = {"bfloat16": jnp.bfloat16,
                              "float64": jnp.float64}.get(dt, jnp.float32)
        # param storage dtype: float32 unless float64 requested (gradient
        # checks force double, like the reference's GradientCheckUtil)
        self.param_dtype = jnp.float64 if dt == "float64" else jnp.float32
        self._params = None          # list[dict[str, Array]] per layer
        self._updater_state = None   # list[dict[var, state-dict]]
        self._model_state = None     # list[dict] (e.g. BN running stats)
        self._rng = jax.random.PRNGKey(int(g.get("seed", 123)))
        self.listeners = []
        self._score = None
        self._last_batch_size = 0
        self._jit_step = None
        self._jit_forward = {}
        self._rnn_state = None       # per-layer carried state for rnnTimeStep
        self._loop = None            # device-resident {iteration, rng}
        self._act_stats_cfg = None   # (max_channels, max_size) when stats on
        self._last_activation_stats = None

    # ------------------------------------------------------------------
    # Init — reference MultiLayerNetwork.init():398-465
    # ------------------------------------------------------------------
    def init(self, parameters=None, clone_parameters=False):
        if self._params is None:
            keys = jax.random.split(self._rng, len(self.layers) + 1)
            self._rng = keys[0]
            self._params = [layer.init_params(keys[i + 1], self.param_dtype)
                            for i, layer in enumerate(self.layers)]
            self._model_state = [layer.init_state() for layer in self.layers]
            self._init_updater_state()
        if parameters is not None:
            self.set_params(parameters)
        return self

    def _init_updater_state(self):
        sd = self.conf.global_conf.get("updater_state_dtype")
        self._updater_state = []
        for layer, p in zip(self.layers, self._params):
            init_fn, _ = U.get(layer.updater or "sgd")
            st = {k: init_fn(v) for k, v in p.items()}
            self._updater_state.append(U.cast_updater_state(st, sd))

    def _ensure_init(self):
        if self._params is None:
            self.init()

    # ------------------------------------------------------------------
    # Forward — reference feedForwardToLayer(:694) / output(:1521)
    # ------------------------------------------------------------------
    def _apply_layers(self, params, state, x, *, train, rng, fmask=None,
                      upto=None, carries=None):
        """Pure forward through layers [0, upto).
        Returns (activations, state', carries')."""
        from .conf.layers.recurrent import BaseRecurrentLayer
        n = len(self.layers) if upto is None else upto
        acts = []
        new_state = list(state)
        new_carries = list(carries) if carries is not None else None
        cdt = self.compute_dtype
        if jnp.issubdtype(x.dtype, jnp.floating):
            x = x.astype(cdt)
        for i in range(n):
            layer = self.layers[i]
            lrng = jax.random.fold_in(rng, i) if rng is not None else None
            # everything the layer traces is under `<kind>.<name or index>`
            with layer_scope(layer, i if layer.name is None else layer.name):
                if i in self.conf.preprocessors:
                    x = self.conf.preprocessors[i].pre_process(x)
                p = jax.tree.map(
                    lambda a: a.astype(cdt)
                    if jnp.issubdtype(a.dtype, jnp.floating) else a,
                    params[i])
                if (isinstance(layer, BaseRecurrentLayer)
                        and carries is not None):
                    x, c = layer.forward_with_carry(
                        p, x, carries[i], train=train, rng=lrng, mask=fmask)
                    new_carries[i] = c
                elif layer.has_state():
                    x, st = layer.forward_with_state(
                        p, x, state[i], train=train, rng=lrng, mask=fmask)
                    new_state[i] = st
                else:
                    x = layer.forward(p, x, train=train, rng=lrng,
                                      mask=fmask)
            acts.append(x)
        return acts, new_state, new_carries

    def _output_layer_input(self, params, state, x, *, train, rng, fmask=None,
                            carries=None):
        """(h, state', carries', acts): the output layer's input after the
        last preprocessor, plus the full interior activation list (the ONE
        forward shared by loss, inference and rnnTimeStep paths)."""
        acts, new_state, new_carries = self._apply_layers(
            params, state, x, train=train, rng=rng, fmask=fmask,
            upto=len(self.layers) - 1, carries=carries)
        h = acts[-1] if acts else x
        i = len(self.layers) - 1
        if i in self.conf.preprocessors:
            h = self.conf.preprocessors[i].pre_process(h)
        return h, new_state, new_carries, acts

    def _act_summaries(self, acts):
        """ON-DEVICE per-layer activation summaries for the stats pipeline
        (reference BaseStatsListener.java:273-420 captures activations from
        the live training forward; here the fused step emits compact
        summaries instead of shipping full activations to the host):
        f32 mean/stdev/mean-magnitude per layer, plus a downsampled
        first-example channel grid for 4-D (NHWC conv) outputs — the
        ConvolutionalIterationListener image source."""
        max_ch, max_size = self._act_stats_cfg
        out = []
        for a in acts:
            a32 = a.astype(jnp.float32)
            s = {"mean": jnp.mean(a32), "stdev": jnp.std(a32),
                 "meanMagnitude": jnp.mean(jnp.abs(a32))}
            if a32.ndim == 4:
                g = a32[0]
                step = max(1, max(g.shape[0], g.shape[1]) // max_size)
                s["grid"] = g[::step, ::step, :max_ch]
            out.append(s)
        return out

    def _loss_fn(self, params, state, features, labels, fmask, lmask, rng,
                 train, carries=None, collect_acts=False):
        h, new_state, new_carries, acts = self._output_layer_input(
            params, state, features, train=train, rng=rng, fmask=fmask,
            carries=carries)
        out_layer = self.layers[-1]
        i = len(self.layers) - 1
        lrng = jax.random.fold_in(rng, i) if rng is not None else None
        with jax.named_scope(
                f"loss.{i if out_layer.name is None else out_layer.name}"):
            p_out = jax.tree.map(
                lambda a: a.astype(self.compute_dtype)
                if jnp.issubdtype(a.dtype, jnp.floating) else a, params[i])
            per_ex = out_layer.compute_score_per_example(
                p_out, h, labels, train=train, rng=lrng, mask=lmask)
            if per_ex.dtype == jnp.bfloat16:
                per_ex = per_ex.astype(jnp.float32)
            score = jnp.mean(per_ex)
        reg = 0.0
        for layer, p in zip(self.layers, params):
            reg = reg + layer.reg_score(p)
        score = score + reg
        if collect_acts:
            # aux grows a third slot ONLY on the stats-collecting step
            # variant — every default-path caller keeps the 2-tuple aux
            return score, (new_state, new_carries,
                           self._act_summaries(acts))
        return score, (new_state, new_carries)

    # ------------------------------------------------------------------
    # The fused train step (jitted, donated)
    # ------------------------------------------------------------------
    def make_grad_fn(self, collect_acts=False):
        """(params, state, batch) -> (grads, score, new_state, new_carries
        [, act_summaries]). The gradient half of the step — what an async
        parameter-server worker computes on a (possibly stale) parameter
        snapshot (reference ParameterServerParallelWrapper.java worker push
        path). collect_acts=True appends the on-device activation
        summaries of the training forward (BaseStatsListener role)."""
        def grad_fn(params, state, batch):
            (score, aux), grads = jax.value_and_grad(
                self._loss_fn, has_aux=True)(
                    params, state, batch["features"], batch["labels"],
                    batch.get("fmask"), batch.get("lmask"), batch["rng"],
                    True, batch.get("carries"), collect_acts)
            return (grads, score) + tuple(aux)
        return grad_fn

    def make_apply_fn(self):
        """(params, ustate, grads, iteration) -> (new_params, new_ustate).
        The updater half of the step — gradient normalization, LR schedule,
        per-variable updater state machine (reference LayerUpdater.java:72)."""
        layers = self.layers

        @jax.named_scope("update")
        def apply_updates(params, ustate, grads, iteration):
            new_params = []
            new_ustate = []
            minimize = self.conf.global_conf.get("minimize", True)
            for i, layer in enumerate(layers):
                g_i = grads[i]
                g_i = U.normalize_gradients(
                    g_i, layer.gradient_normalization,
                    layer.gradient_normalization_threshold or 1.0)
                _, apply_fn = U.get(layer.updater or "sgd")
                hp = layer.updater_hp()
                p_new, s_new = {}, {}
                for k, p in params[i].items():
                    base_lr = layer.learning_rate or 0.1
                    if k in ("b", "beta") and layer.bias_learning_rate is not None:
                        base_lr = layer.bias_learning_rate
                    lr = U.schedule_lr(
                        base_lr, layer.lr_policy or "none", iteration,
                        decay_rate=layer.lr_policy_decay_rate or 0.0,
                        steps=layer.lr_policy_steps or 1.0,
                        power=layer.lr_policy_power or 1.0,
                        schedule_map=layer.lr_schedule,
                        max_iterations=layer.lr_policy_max_iterations,
                    )
                    upd, s_k = apply_fn(ustate[i][k], g_i[k], lr, hp)
                    p_new[k] = p - upd if minimize else p + upd
                    # keep the stored state dtype (bf16 when
                    # updater_state_dtype is set; math promotes to f32)
                    s_new[k] = jax.tree.map(
                        lambda a, old: a.astype(old.dtype), s_k, ustate[i][k])
                new_params.append(p_new)
                new_ustate.append(s_new)
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
                from ..common import health as H
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
        collect_acts = self._act_stats_cfg is not None
        emit_health = getattr(self, "_health_policy", None) is not None
        self._step_emits_acts = collect_acts
        self._step_emits_health = emit_health
        raw = self.make_raw_step(collect_acts, emit_health)

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

    def collect_activation_stats(self, enabled=True, max_channels=8,
                                 max_size=48):
        """Make the fused train step ALSO emit per-layer activation
        summaries of the REAL training batch (reference
        BaseStatsListener.java:273-420 / ConvolutionalIterationListener —
        activations come from the live forward pass, no extra probe
        forward). Costs one recompile on toggle plus a few scalars (and
        small conv grids) of device->host traffic per step; the disabled
        path compiles the exact same program as before."""
        cfg = (int(max_channels), int(max_size)) if enabled else None
        if cfg != self._act_stats_cfg:
            self._act_stats_cfg = cfg
            self._jit_step = None              # recompile with/without aux
            # bump the generation so wrappers caching their own compiled
            # step (ParallelWrapper) rebuild too
            self._act_stats_gen = getattr(self, "_act_stats_gen", 0) + 1
            if not enabled:
                self._last_activation_stats = None
        return self

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
        from ..common import health as H
        H.install(self, policy, checkpoint_dir, checkpoint_every,
                  keep_checkpoints)
        return self

    def fused_steps(self, k=8):
        """Fuse K optimizer steps into ONE device dispatch: the fit loops
        stage K batches (the AsyncDataSetIterator prefetch/wire machinery,
        unchanged), stack them into a [K, B, ...] super-batch, and run a
        single donated jitted program that `lax.scan`s the SAME raw step
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
        from . import fused as F
        return F.install(self, k)

    def _fused_k(self):
        """Effective fused depth for the CURRENT batch: 1 (single-step
        path) unless armed, act-stats off and num_iterations == 1."""
        k = getattr(self, "_fused_steps", 1)
        if (k <= 1 or self._act_stats_cfg is not None
                or int(self.conf.global_conf.get("num_iterations", 1)) != 1):
            return 1
        return k

    def _loop_state(self):
        if getattr(self, "_loop", None) is None:
            self._rng, k = jax.random.split(self._rng)
            self._loop = {
                "iteration": jnp.asarray(self.conf.iteration_count,
                                         jnp.float32),
                "rng": k,
            }
        return self._loop

    # ------------------------------------------------------------------
    # fit — reference MultiLayerNetwork.fit(:978)
    # ------------------------------------------------------------------
    def fit(self, data, labels=None, features_mask=None, labels_mask=None,
            num_epochs=1):
        self._ensure_init()
        if labels is not None:
            data = DataSet(data, labels, features_mask, labels_mask)
        if isinstance(data, DataSet):
            # single in-memory batch: no prefetch pipeline needed (the
            # reference's fit(DataSet) path is likewise direct)
            if self._jit_step is None:
                self._jit_step = self._make_step()
            for _ in range(num_epochs):
                self._fit_batch(data)
            return self
        if isinstance(data, DataSetIterator):
            return self._fit_iterator(data, num_epochs)
        raise TypeError(f"Cannot fit on {type(data)}")

    def _fit_iterator(self, it, num_epochs=1):
        from ..datasets.iterators import (AsyncDataSetIterator,
                                          wrap_async_for_fit)
        # a CALLER-supplied iterator may be mid-stream and must start the
        # first epoch from position 0 (ADVICE r5): plain iterators are
        # reset BEFORE wrapping (so the fresh wrapper prefetches from 0
        # and the epoch-0 reset skip below is trivially safe); an async
        # iterator the caller built themselves resets in the loop
        wrapped_here = not isinstance(it, AsyncDataSetIterator)
        if wrapped_here:
            it.reset()
        # fused mode stages a whole super-batch ahead: deepen the prefetch
        # queue so the staging thread can fill group K+1 while K runs
        async_it = wrap_async_for_fit(
            it, self.compute_dtype,
            queue_size=max(2, getattr(self, "_fused_steps", 1) + 1))
        if self._jit_step is None:
            self._jit_step = self._make_step()
        for epoch in range(num_epochs):
            if epoch > 0 or not wrapped_here or not async_it.has_next():
                async_it.reset()
            for l in self.listeners:
                if hasattr(l, "on_epoch_start"):
                    l.on_epoch_start(self)
            while async_it.has_next():
                k = (self._fused_k()
                     if self.conf.backprop_type != "tbptt" else 1)
                if k <= 1:
                    self._fit_batch(next_processed(async_it))
                    continue
                from . import fused as F
                group = []
                g = F.group_size(self, k)
                with obs.TRACER.span("train.stage", cat="train", k=g):
                    while len(group) < g and async_it.has_next():
                        group.append(next_processed(async_it))
                if len(group) == g and F.uniform_group(group):
                    self._fit_super_batch(group)
                else:
                    # ragged tail (K not dividing the epoch) or mixed
                    # batch shapes: single-step dispatches, same stream
                    for ds in group:
                        self._fit_batch(ds)
            for l in self.listeners:
                if hasattr(l, "on_epoch_end"):
                    l.on_epoch_end(self)
            self.conf.epoch_count += 1
        return self

    def _fit_super_batch(self, group):
        """ONE dispatch for len(group) staged batches: stack on device,
        scan the raw step, then walk the stacked per-step scores/health
        on the host (`common.health.finish_fused` — listeners and the
        watchdog see every optimizer step). On a mid-super-batch
        rollback the remaining staged batches re-run single-step from
        the restored state, exactly as the sequential loop would."""
        from . import fused as F
        emit_health = getattr(self, "_health_policy", None) is not None
        g = len(group)

        def build():
            raw = self.make_raw_step(False, emit_health)

            def prog(params, ustate, state, loop, batch_list):
                return F.scan_batches(raw, params, ustate, state, loop,
                                      batch_list)

            return jax.jit(prog, donate_argnums=(0, 1, 2, 3))

        step = F.fused_program(self, ("batch", g), build)
        batch_list = tuple(
            {"features": ds.features, "labels": ds.labels,
             "fmask": ds.features_mask, "lmask": ds.labels_mask}
            for ds in group)
        self._last_batch_size = int(np.shape(group[0].features)[0])
        with obs.TRACER.span("train.fused_group", cat="train", k=g):
            with obs.TRACER.span("train.dispatch", cat="train", k=g):
                (self._params, self._updater_state, self._model_state,
                 scores, _, self._loop, *extras) = step(
                     self._params, self._updater_state, self._model_state,
                     self._loop_state(), batch_list)
            from ..common import health as H
            with obs.TRACER.span("train.health", cat="train", k=g):
                rb = H.finish_fused(self, scores,
                                    extras[-1] if emit_health else None, g)
        if rb is not None:
            for ds in group[rb + 1:]:   # counters/rng restored; replay
                self._fit_batch(ds)
        return self

    def _fit_batch(self, ds: DataSet):
        if self.conf.backprop_type == "tbptt":
            return self._fit_tbptt(ds)
        num_iterations = int(self.conf.global_conf.get("num_iterations", 1))
        features = jnp.asarray(ds.features)
        labels = jnp.asarray(ds.labels)
        fmask = jnp.asarray(ds.features_mask) if ds.features_mask is not None else None
        lmask = jnp.asarray(ds.labels_mask) if ds.labels_mask is not None else None
        self._last_batch_size = int(features.shape[0])
        for _ in range(num_iterations):
            if self._jit_step is None:
                # a StatsListener may arm activation stats from
                # iteration_done MID-fit (invalidating the step); rebuild
                # rather than crash on the next iteration
                self._jit_step = self._make_step()
            with obs.TRACER.span("train.dispatch", cat="train"):
                (self._params, self._updater_state, self._model_state,
                 score, _, self._loop, *extras) = self._jit_step(
                     self._params, self._updater_state, self._model_state,
                     self._loop_state(), features, labels, fmask, lmask)
            health = (extras.pop() if getattr(self, "_step_emits_health",
                                              False) else None)
            if extras:
                self._last_activation_stats = extras[0]
                self._last_activation_stats_iter = self.conf.iteration_count
            action = "ok"
            if health is None:
                self._score = score
            else:
                from ..common import health as H
                with obs.TRACER.span("train.health", cat="train"):
                    action = H.finish_step(self, health, score)
                if action == "rollback":
                    break           # counters/rng restored; next batch
            self.conf.iteration_count += 1
            for l in self.listeners:
                l.iteration_done(self, self.conf.iteration_count - 1)
            if health is not None and action == "ok":
                from ..common.health import fit_loop_checkpoint
                with obs.TRACER.span("train.checkpoint", cat="train"):
                    fit_loop_checkpoint(self)
        return self

    def _init_carries(self, batch_size):
        from .conf.layers.recurrent import BaseRecurrentLayer
        # COMPUTE dtype, not param dtype: forward_with_carry casts the
        # incoming carry to x.dtype anyway (values identical), and the
        # returned carry IS compute dtype — a f32 init on a bf16 model
        # silently retraced the sequential TBPTT step after segment 1 and
        # breaks the fused scan's carry-dtype invariance
        return [layer.init_carry(batch_size, self.compute_dtype)
                if isinstance(layer, BaseRecurrentLayer) else {}
                for layer in self.layers]

    def _fit_tbptt(self, ds: DataSet):
        """Truncated BPTT: slice the time axis into tbptt_fwd_length segments,
        carrying RNN cell state (but not gradients) across segments.
        reference: MultiLayerNetwork.doTruncatedBPTT:1140 +
        updateRnnStateWithTBPTTState:1196."""
        T = ds.features.shape[1]
        L = self.conf.tbptt_fwd_length
        if self._jit_step is None:
            self._jit_step = self._make_step()
        B = int(ds.features.shape[0])
        carries = self._init_carries(B)
        features = jnp.asarray(ds.features)
        labels = jnp.asarray(ds.labels)
        fmask = jnp.asarray(ds.features_mask) if ds.features_mask is not None else None
        lmask = jnp.asarray(ds.labels_mask) if ds.labels_mask is not None else None
        self._last_batch_size = B
        seq_labels = labels.ndim >= 3
        t0 = 0
        while t0 < T:
            # fused TBPTT: K full segments per dispatch, carries threaded
            # through the scan; the short tail segment (L not dividing T)
            # and act-stats-armed runs stay single-step
            k = self._fused_k()
            if k > 1:
                from . import fused as F
                g = min(F.group_size(self, k), (T - t0) // L)
                if g > 1:
                    carries, t0, done = self._fit_tbptt_fused(
                        features, labels, fmask, lmask, carries, t0, g,
                        seq_labels, L)
                    if done:        # rollback: abandon this sequence
                        return self
                    continue
            if self._jit_step is None:     # mid-fit arming (see _fit_batch)
                self._jit_step = self._make_step()
            f_seg = features[:, t0:t0 + L]
            l_seg = labels[:, t0:t0 + L] if seq_labels else labels
            fm_seg = fmask[:, t0:t0 + L] if fmask is not None else None
            lm_seg = lmask[:, t0:t0 + L] if lmask is not None else None
            with obs.TRACER.span("train.dispatch", cat="train",
                                 tbptt=True):
                (self._params, self._updater_state, self._model_state,
                 score, carries, self._loop, *extras) = self._jit_step(
                     self._params, self._updater_state, self._model_state,
                     self._loop_state(), f_seg, l_seg, fm_seg, lm_seg,
                     carries)
            health = (extras.pop() if getattr(self, "_step_emits_health",
                                              False) else None)
            if extras:
                self._last_activation_stats = extras[0]
                self._last_activation_stats_iter = self.conf.iteration_count
            # stop gradient flow across segments (truncation) — carries are
            # fresh inputs to the next jitted call, so this is automatic.
            action = "ok"
            if health is None:
                self._score = score
            else:
                from ..common import health as H
                with obs.TRACER.span("train.health", cat="train"):
                    action = H.finish_step(self, health, score)
                if action == "rollback":
                    break       # abandon the rest of this sequence
            self.conf.iteration_count += 1
            for l in self.listeners:
                l.iteration_done(self, self.conf.iteration_count - 1)
            if health is not None and action == "ok":
                from ..common.health import fit_loop_checkpoint
                with obs.TRACER.span("train.checkpoint", cat="train"):
                    fit_loop_checkpoint(self)
            t0 += L
        return self

    def _fit_tbptt_fused(self, features, labels, fmask, lmask, carries,
                         t0, g, seq_labels, L):
        """ONE dispatch for g full TBPTT segments starting at t0: the
        scan body dynamic-slices each segment out of the full sequence
        (no host-side restacking — the data crossed the wire once) and
        threads the RNN carries through the scan carry. Returns
        (carries', next_t0, rolled_back)."""
        from . import fused as F
        emit_health = getattr(self, "_health_policy", None) is not None

        def build():
            raw = self.make_raw_step(False, emit_health)

            def prog(params, ustate, state, loop, features, labels,
                     fmask, lmask, carries, t0s):
                def make_batch(s):
                    sl = (lambda a: None if a is None else
                          jax.lax.dynamic_slice_in_dim(a, s, L, axis=1))
                    return {"features": sl(features),
                            "labels": sl(labels) if seq_labels else labels,
                            "fmask": sl(fmask), "lmask": sl(lmask)}

                return F.scan_steps(raw, params, ustate, state, loop,
                                    carries, t0s, make_batch)

            return jax.jit(prog, donate_argnums=(0, 1, 2, 3))

        key = ("tbptt", g, L, bool(seq_labels),
               fmask is not None, lmask is not None)
        step = F.fused_program(self, key, build)
        t0s = jnp.arange(t0, t0 + g * L, L, dtype=jnp.int32)
        with obs.TRACER.span("train.fused_group", cat="train", k=g,
                             tbptt=True):
            with obs.TRACER.span("train.dispatch", cat="train", k=g,
                                 tbptt=True):
                (self._params, self._updater_state, self._model_state,
                 scores, carries, self._loop, *extras) = step(
                     self._params, self._updater_state, self._model_state,
                     self._loop_state(), features, labels, fmask, lmask,
                     carries, t0s)
            from ..common import health as H
            with obs.TRACER.span("train.health", cat="train", k=g):
                rb = H.finish_fused(self, scores,
                                    extras[-1] if emit_health else None, g)
        return carries, t0 + g * L, rb is not None

    # ------------------------------------------------------------------
    # Layerwise pretraining — reference MultiLayerNetwork.pretrain /
    # pretrainLayer(:183): greedy unsupervised training of each pretrainable
    # layer (AutoEncoder / RBM / VAE) on the activations from below.
    # ------------------------------------------------------------------
    def pretrain(self, data, num_epochs=1):
        self._ensure_init()
        for i, layer in enumerate(self.layers):
            if hasattr(layer, "pretrain_loss") or hasattr(layer,
                                                          "pretrain_grads"):
                self.pretrain_layer(i, data, num_epochs)
        return self

    def pretrain_layer(self, i, data, num_epochs=1):
        """One fused jitted step per batch: feed-forward to layer i (frozen),
        unsupervised grads for layer i (autodiff of pretrain_loss, or the
        layer's own pretrain_grads e.g. RBM contrastive divergence), updater
        apply — all one XLA program."""
        self._ensure_init()
        layer = self.layers[i]
        use_cd = hasattr(layer, "pretrain_grads")
        if not (use_cd or hasattr(layer, "pretrain_loss")):
            raise ValueError(f"Layer {i} ({type(layer).__name__}) is not "
                             "pretrainable")
        init_fn, apply_fn = U.get(layer.updater or "sgd")
        hp = layer.updater_hp()
        lr = layer.learning_rate or 0.1
        ustate = {k: init_fn(v) for k, v in self._params[i].items()}
        cdt = self.compute_dtype

        def step(params, ustate, state, x, rng):
            h, _, _ = self._apply_layers(params, state, x, train=False,
                                         rng=rng, upto=i)
            h = h[-1] if i > 0 and h else (
                x.astype(cdt) if jnp.issubdtype(x.dtype, jnp.floating) else x)
            if i in self.conf.preprocessors:
                h = self.conf.preprocessors[i].pre_process(h)
            p_i = jax.tree.map(
                lambda a: a.astype(cdt)
                if jnp.issubdtype(a.dtype, jnp.floating) else a, params[i])
            if use_cd:
                grads = layer.pretrain_grads(p_i, h, rng=rng)
                loss = layer.pretrain_loss(p_i, h, rng=rng)
            else:
                loss, grads = jax.value_and_grad(
                    lambda p: layer.pretrain_loss(p, h, rng=rng))(p_i)
            new_p, new_u = {}, {}
            for k, p in params[i].items():
                upd, s_k = apply_fn(ustate[k], grads[k].astype(p.dtype), lr,
                                    hp)
                new_p[k] = p - upd
                new_u[k] = s_k
            return new_p, new_u, loss

        jit_step = jax.jit(step, donate_argnums=(1,))
        if isinstance(data, DataSet):
            data = ListDataSetIterator([data])
        for _ in range(num_epochs):
            data.reset()
            while data.has_next():
                ds = next_processed(data)
                self._rng, rng = jax.random.split(self._rng)
                new_p, ustate, loss = jit_step(
                    self._params, ustate, self._model_state,
                    jnp.asarray(ds.features), rng)
                self._params = (self._params[:i] + [new_p]
                                + self._params[i + 1:])
                self._score = loss
        return self

    pretrainLayer = pretrain_layer

    # ------------------------------------------------------------------
    # Inference — reference output(:1521)/feedForward(:657)
    # ------------------------------------------------------------------
    def _forward_out(self, params, state, x, *, train, rng, fmask=None):
        """Pure forward to the OUTPUT layer's activation — the ONE
        implementation behind `output()` and `make_inference_fn()` (a fix
        in one must reach the other or the serving layer's bit-identity
        pin against `output()` silently breaks)."""
        h, _, _, _ = self._output_layer_input(params, state, x,
                                              train=train, rng=rng,
                                              fmask=fmask)
        out_layer = self.layers[-1]
        i = len(self.layers) - 1
        p = jax.tree.map(lambda a: a.astype(self.compute_dtype)
                         if jnp.issubdtype(a.dtype, jnp.floating) else a,
                         params[i])
        lrng = jax.random.fold_in(rng, i)
        if out_layer.has_state():
            out, _ = out_layer.forward_with_state(
                p, h, state[i], train=train, rng=lrng)
            return out
        return out_layer.forward(p, h, train=train, rng=lrng)

    def output(self, x, train=False, features_mask=None):
        """Forward pass to the output layer. `features_mask` carries
        variable-length sequence masks through recurrent layers, matching the
        reference's output(input, train, featuresMask, labelsMask)."""
        self._ensure_init()
        x = jnp.asarray(x)
        fmask = jnp.asarray(features_mask) if features_mask is not None else None
        key = ("output", bool(train), fmask is not None)
        if key not in self._jit_forward:
            def fwd(params, state, x, fmask, rng):
                return self._forward_out(params, state, x, train=train,
                                         rng=rng, fmask=fmask)
            self._jit_forward[key] = jax.jit(fwd)
        self._rng, rng = jax.random.split(self._rng)
        return self._jit_forward[key](self._params, self._model_state, x,
                                      fmask, rng)

    def feed_forward(self, x, train=False):
        """Returns list of activations per layer, input first (reference :657)."""
        self._ensure_init()
        x = jnp.asarray(x)
        self._rng, rng = jax.random.split(self._rng)
        acts, _, _ = self._apply_layers(self._params, self._model_state, x,
                                        train=train, rng=rng)
        return [x] + acts

    feedForward = feed_forward

    def make_inference_fn(self):
        """PURE inference step `(params, state, x) -> out` — the compilation
        unit the serving layer (`serving/InferenceServer`) jits per padding
        bucket. train=False with a CONSTANT rng key: dropout is inactive at
        inference, so the rng never reaches the math and the program is a
        pure function of (params, state, x) — two calls with the same
        arguments return bit-identical outputs, which is what lets the
        server pin micro-batched results against a batch-1 call. Params and
        model state are ARGUMENTS (not captured), so a hot model swap is a
        new argument, not a recompile."""
        self._ensure_init()

        def infer(params, state, x):
            return self._forward_out(params, state, x, train=False,
                                     rng=jax.random.PRNGKey(0))

        return infer

    # ------------------------------------------------------------------
    # Streaming RNN inference — reference rnnTimeStep(:2196): O(1) per step,
    # hidden state stashed per layer across calls.
    # ------------------------------------------------------------------
    def rnn_time_step(self, x):
        """x: [B, F] single step or [B, T, F] multi-step. Returns output with
        the same time rank; recurrent layer state carries across calls."""
        self._ensure_init()
        x = jnp.asarray(x)
        single = x.ndim == 2
        if single:
            x = x[:, None, :]
        B = int(x.shape[0])
        if self._rnn_state is None:
            self._rnn_state = self._init_carries(B)
        if "rnn_step" not in self._jit_forward:
            def fwd(params, state, x, rng, carries):
                h, _, new_carries, _ = self._output_layer_input(
                    params, state, x, train=False, rng=rng, carries=carries)
                out_layer = self.layers[-1]
                i = len(self.layers) - 1
                p = jax.tree.map(lambda a: a.astype(self.compute_dtype)
                                 if jnp.issubdtype(a.dtype, jnp.floating) else a,
                                 params[i])
                out = out_layer.forward(p, h, train=False,
                                        rng=jax.random.fold_in(rng, i))
                return out, new_carries
            self._jit_forward["rnn_step"] = jax.jit(fwd)
        self._rng, rng = jax.random.split(self._rng)
        out, self._rnn_state = self._jit_forward["rnn_step"](
            self._params, self._model_state, x, rng, self._rnn_state)
        return out[:, 0] if single else out

    rnnTimeStep = rnn_time_step

    def rnn_clear_previous_state(self):
        """reference: MultiLayerNetwork.rnnClearPreviousState"""
        self._rnn_state = None

    rnnClearPreviousState = rnn_clear_previous_state

    def predict(self, x):
        out = self.output(x)
        return np.asarray(jnp.argmax(out, axis=-1))

    # ------------------------------------------------------------------
    # Score / gradients — reference computeGradientAndScore(:1807)
    # ------------------------------------------------------------------
    def score(self, data=None, training=False):
        if data is None:
            return float(self._score) if self._score is not None else float("nan")
        self._ensure_init()
        if isinstance(data, tuple):
            data = DataSet(*data)
        self._rng, rng = jax.random.split(self._rng)
        s, _ = self._loss_fn(self._params, self._model_state,
                             jnp.asarray(data.features), jnp.asarray(data.labels),
                             jnp.asarray(data.features_mask) if data.features_mask is not None else None,
                             jnp.asarray(data.labels_mask) if data.labels_mask is not None else None,
                             rng, training)
        return float(s)

    def compute_gradient_and_score(self, features, labels, fmask=None, lmask=None,
                                   train=True):
        """Returns (grads pytree, score). Deterministic rng for gradient checks."""
        self._ensure_init()
        rng = jax.random.PRNGKey(0)
        (score, _), grads = jax.value_and_grad(self._loss_fn, has_aux=True)(
            self._params, self._model_state, jnp.asarray(features),
            jnp.asarray(labels),
            jnp.asarray(fmask) if fmask is not None else None,
            jnp.asarray(lmask) if lmask is not None else None, rng, train)
        return grads, float(score)

    # ------------------------------------------------------------------
    # Flattened-params API parity — reference init:398-465 contract
    # ------------------------------------------------------------------
    def _param_leaves(self):
        leaves = []
        for i, p in enumerate(self._params):
            for k in sorted(p.keys(), key=_param_sort_key):
                leaves.append(((i, k), p[k]))
        return leaves

    def params(self):
        self._ensure_init()
        vecs = [np.asarray(v).ravel() for _, v in self._param_leaves()]
        if not vecs:
            return np.zeros((0,), np.float32)
        return np.concatenate(vecs)

    def set_params(self, flat):
        self._ensure_init()
        flat = np.asarray(flat).ravel()
        offset = 0
        new_params = [dict(p) for p in self._params]
        for (i, k), v in self._param_leaves():
            n = int(np.prod(v.shape)) if v.shape else 1
            chunk = flat[offset:offset + n].reshape(v.shape)
            new_params[i][k] = jnp.asarray(chunk, v.dtype)
            offset += n
        if offset != flat.size:
            raise ValueError(f"Expected {offset} params, got {flat.size}")
        self._params = new_params

    setParams = set_params

    def num_params(self):
        return int(sum(int(np.prod(v.shape)) for _, v in self._param_leaves()))

    numParams = num_params

    def unflatten_params(self, flat):
        """flat vector -> per-layer param pytree (jit-traceable)."""
        offset = 0
        out = []
        for i, p in enumerate(self._params):
            d = {}
            for k in sorted(p.keys(), key=_param_sort_key):
                v = p[k]
                n = int(np.prod(v.shape)) if v.shape else 1
                d[k] = flat[offset:offset + n].reshape(v.shape).astype(v.dtype)
                offset += n
            out.append(d)
        return out

    def make_flat_score_fn(self, features, labels, fmask=None, lmask=None,
                           train=True):
        """Jitted score(flat_params) -> scalar, for gradient checking."""
        features = jnp.asarray(features)
        labels = jnp.asarray(labels)
        fmask = jnp.asarray(fmask) if fmask is not None else None
        lmask = jnp.asarray(lmask) if lmask is not None else None
        rng = jax.random.PRNGKey(0)

        def score_fn(flat):
            params = self.unflatten_params(flat)
            s, _ = self._loss_fn(params, self._model_state, features, labels,
                                 fmask, lmask, rng, train)
            return s

        return jax.jit(score_fn)

    def flatten_gradients(self, grads):
        vecs = []
        for i, p in enumerate(grads):
            for k in sorted(p.keys(), key=_param_sort_key):
                vecs.append(np.asarray(p[k], np.float64).ravel())
        return np.concatenate(vecs) if vecs else np.zeros((0,))

    # ------------------------------------------------------------------
    # Evaluation — reference evaluate(:1574)
    # ------------------------------------------------------------------
    def evaluate(self, data, meta=None):
        """`meta`: optional per-example metadata (list over ALL examples in
        iteration order, or per-DataSet `example_metas` attribute) enabling
        Evaluation's Prediction error-analysis queries — reference
        MultiLayerNetwork.evaluate + eval(..., List<Serializable> meta)."""
        from ..datasets.iterators import wrap_async_for_fit
        from ..eval.evaluation import Evaluation
        ev = Evaluation()
        if isinstance(data, DataSet):
            data = ListDataSetIterator([data])
        if isinstance(data, DataSetIterator):
            # full-pass guarantee first (the old base-__iter__ behavior —
            # also keeps positional `meta` aligned with example 0), then
            # prefetch + device staging overlap eval compute (and the
            # bf16 feature wire for bf16 models — inference casts features
            # to the compute dtype anyway, so outputs are bit-identical)
            data.reset()
            data = wrap_async_for_fit(data, self.compute_dtype)
        pos = 0
        for ds in data:
            out = self.output(ds.features, features_mask=ds.features_mask)
            batch_meta = getattr(ds, "example_metas", None)
            if batch_meta is None and meta is not None:
                batch_meta = meta[pos:pos + ds.num_examples()]
            pos += ds.num_examples()
            ev.eval(ds.labels, np.asarray(out), mask=ds.labels_mask,
                    meta=batch_meta)
        return ev

    def evaluate_regression(self, data):
        from ..datasets.iterators import wrap_async_for_fit
        from ..eval.regression import RegressionEvaluation
        ev = None
        if isinstance(data, DataSet):
            data = ListDataSetIterator([data])
        if isinstance(data, DataSetIterator):
            data.reset()                    # full-pass guarantee
            data = wrap_async_for_fit(data, self.compute_dtype)
        for ds in data:
            out = self.output(ds.features, features_mask=ds.features_mask)
            if ev is None:
                ev = RegressionEvaluation(int(ds.labels.shape[-1]))
            ev.eval(ds.labels, np.asarray(out))
        return ev

    # ------------------------------------------------------------------
    # Listeners — reference setListeners
    # ------------------------------------------------------------------
    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    setListeners = set_listeners

    def add_listener(self, listener):
        self.listeners.append(listener)
        return self

    # ------------------------------------------------------------------
    # Cloning / serde helpers
    # ------------------------------------------------------------------
    def clone(self):
        net = MultiLayerNetwork(self.conf.clone())
        if self._params is not None:
            net.init()
            # materialize COPIES: aliasing the live arrays would let the
            # next donated train step delete the clone's buffers with it
            net._params = jax.tree.map(jnp.copy, self._params)
            net._updater_state = jax.tree.map(jnp.copy, self._updater_state)
            net._model_state = jax.tree.map(jnp.copy, self._model_state)
        return net

    def get_layer(self, i):
        return self.layers[i]

    @property
    def n_layers(self):
        return len(self.layers)


def _param_sort_key(k):
    # canonical variable order: W-like first, then recurrent, then biases —
    # mirrors the reference's per-layer param layout (DefaultParamInitializer:
    # weights then bias).
    order = {"W": 0, "RW": 1, "b": 2, "gamma": 0, "beta": 1, "mean": 2, "var": 3,
             "vb": 3}
    return (order.get(k, 9), k)
