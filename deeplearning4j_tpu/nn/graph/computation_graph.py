"""ComputationGraph — the DAG network container.

TPU-native equivalent of reference nn/graph/ComputationGraph.java (2,280 LoC):
topological forward (doForward per vertex, GraphVertex.java:117), autodiff
backward replacing doBackward (:123), multi-input/multi-output with
MultiDataSet, fit (:809), computeGradientAndScore (:952), flattened-params
contract (:281-345).

Same TPU-first redesign as MultiLayerNetwork: the whole training step
(params, updater_state, model_state, batch) -> (params', ...) is ONE donated
jit-compiled XLA program; the DAG structure is unrolled at trace time (the
topological order is static), so XLA sees a flat fused computation regardless
of graph shape.
"""
from __future__ import annotations

import logging

import jax
import jax.numpy as jnp
import numpy as np

from ... import obs
from ...datasets.dataset import DataSet, MultiDataSet
from ...datasets.iterators import next_processed
from .. import activations
from ..conf.computation_graph_configuration import ComputationGraphConfiguration
from ..conf.layers.base import LayerConf, layer_scope
from ..conf.layers.convolution import ConvolutionLayer
from ..conf.layers.normalization import (BatchNormalization,
                                         _conv1x1_bn_train_fused)
from ..conf.layers.recurrent import BaseRecurrentLayer
from ..updater import updaters as U

log = logging.getLogger(__name__)


class ComputationGraph:
    def __init__(self, conf: ComputationGraphConfiguration,
                 remat_segments=False):
        """remat_segments=True: gradient-checkpoint the graph in segments
        bounded by element-wise (residual-add) vertices — the backward
        recomputes each segment's conv→BN→ReLU chain from the segment
        boundary instead of re-reading every intermediate activation from
        HBM. Structural bytes/step lever for bandwidth-bound CNNs
        (PERF.md r2 roofline: ResNet-50 is HBM-bound); trades ~1/3 more
        forward FLOPs for activation traffic. Numerics are identical
        (pinned by test). The reference has no equivalent (it stores all
        activations; workspace reuse is its only memory lever —
        WorkspaceMode in MultiLayerConfiguration.java)."""
        self.conf = conf
        g = conf.global_conf
        # the configuration may ask for it too (`remat_segments(True)` on
        # the builder): a model that only fits rematerialised says so itself
        self._remat = bool(remat_segments or g.get("remat_segments"))
        dt = str(g.get("data_type", "float32"))
        self.compute_dtype = {"bfloat16": jnp.bfloat16,
                              "float64": jnp.float64}.get(dt, jnp.float32)
        self.param_dtype = jnp.float64 if dt == "float64" else jnp.float32
        self._params = None          # dict name -> param dict (layer vertices)
        self._updater_state = None
        self._model_state = None     # dict name -> state dict
        self._rng = jax.random.PRNGKey(int(g.get("seed", 123)))
        self.listeners = []
        self._score = None
        self._last_batch_size = 0
        self._jit_step = None
        self._jit_forward = {}
        self._loop = None            # device-resident {iteration, rng}

    # ------------------------------------------------------------------
    def _layer_names(self):
        """Layer vertices in topological order (the flattened-params order —
        reference ComputationGraph.init:281-345 uses topological order too)."""
        return [n for n in self.conf.topological_order
                if self.conf.vertices[n].is_layer]

    def init(self, parameters=None, clone_parameters=False):
        if self._params is None:
            names = self._layer_names()
            keys = jax.random.split(self._rng, len(names) + 1)
            self._rng = keys[0]
            self._params = {}
            self._model_state = {}
            for i, n in enumerate(names):
                layer = self.conf.vertices[n].conf
                self._params[n] = layer.init_params(keys[i + 1], self.param_dtype)
                self._model_state[n] = layer.init_state()
            self._init_updater_state()
        if parameters is not None:
            self.set_params(parameters)
        return self

    def _init_updater_state(self):
        sd = self.conf.global_conf.get("updater_state_dtype")
        self._updater_state = {}
        for n in self._layer_names():
            layer = self.conf.vertices[n].conf
            init_fn, _ = U.get(layer.updater or "sgd")
            st = {k: init_fn(v) for k, v in self._params[n].items()}
            self._updater_state[n] = U.cast_updater_state(st, sd)

    def _ensure_init(self):
        if self._params is None:
            self.init()

    # ------------------------------------------------------------------
    # Forward — reference: per-vertex doForward in topological order
    # ------------------------------------------------------------------
    def _apply_graph(self, params, state, inputs, *, train, rng, fmasks=None,
                     stop_at=None, carries=None, allow_remat=False):
        """Pure forward over the DAG.

        inputs: dict input-name -> array. fmasks: dict input-name -> mask.
        carries: dict layer-name -> RNN carry (TBPTT / rnnTimeStep state).
        Returns (activations dict incl. inputs, new_state dict, masks dict,
        new_carries dict).
        """
        cdt = self.compute_dtype
        # remat only wraps the TRAINING-STEP forward (allow_remat is set
        # by _loss_fn alone — what the backward stores); inference AND
        # inspection (feed_forward/UI activation capture, any train flag)
        # keep the full per-vertex activation contract
        if (self._remat and allow_remat and train and stop_at is None
                and carries is None
                and not (fmasks and any(m is not None
                                        for m in fmasks.values()))):
            return self._apply_graph_remat(params, state, inputs,
                                           train=train, rng=rng)
        acts = {}
        masks = {}
        for name in self.conf.network_inputs:
            x = inputs[name]
            if jnp.issubdtype(x.dtype, jnp.floating):
                x = x.astype(cdt)
            acts[name] = x
            masks[name] = fmasks.get(name) if fmasks else None
        new_state = dict(state)
        new_carries = dict(carries) if carries is not None else None
        for vi, name in enumerate(self.conf.topological_order):
            spec = self.conf.vertices[name]
            in_acts = [acts[i] for i in spec.inputs]
            in_masks = [masks.get(i) for i in spec.inputs]
            lrng = jax.random.fold_in(rng, vi) if rng is not None else None
            out, st, c = self._forward_vertex(
                spec, params.get(name), in_acts, in_masks, train=train,
                lrng=lrng, state_entry=state.get(name),
                carry_entry=(carries or {}).get(name)
                if carries is not None else None,
                pair=self._pair_of(name, train, params, acts))
            acts[name] = out
            if st is not None:
                new_state[name] = st
            if c is not None:
                new_carries[name] = c
            if spec.is_layer:
                masks[name] = (in_masks[0]
                               if _keeps_time_axis(spec.conf) else None)
            else:
                masks[name] = spec.conf.output_mask(in_masks)
            if stop_at is not None and name == stop_at:
                break
        return acts, new_state, masks, new_carries

    def _cast_params(self, p):
        cdt = self.compute_dtype
        return jax.tree.map(
            lambda a: a.astype(cdt)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, p)

    def _forward_vertex(self, spec, p, in_acts, in_masks, *, train, lrng,
                        state_entry=None, carry_entry=None, pair=None):
        """One vertex's forward — the SINGLE dispatch (preprocessor, param
        cast, carry/state/stateless/paired branches) shared by
        `_apply_graph` and the remat segment body, so the two forward paths
        cannot drift. Returns (out, new_state | None, new_carry | None).
        Everything it traces is under the scope `<kind>.<vertex name>`;
        `pair` is `_pair_of`'s answer for this vertex."""
        if pair is not None:
            # the batch norm of a planned pair: convolution and batch norm
            # as one function of the convolution's INPUT, under the
            # convolution's scope (its backward is convolution work); the
            # running statistics under the batch norm's
            cspec, cp, a = pair
            with layer_scope(cspec.conf, cspec.name):
                if cspec.preprocessor is not None:
                    a = cspec.preprocessor.pre_process(a)
                p = self._cast_params(p)
                y, mean, var = _conv1x1_bn_train_fused(
                    spec.conf.eps, spec.conf.use_fast_variance,
                    cspec.conf.stride)(
                        a, self._cast_params(cp)["W"], p["gamma"], p["beta"])
            with layer_scope(spec.conf, spec.name):
                return y, spec.conf.running_stats(state_entry, mean,
                                                  var), None
        with layer_scope(spec.conf, spec.name):
            if spec.is_layer:
                layer = spec.conf
                x = in_acts[0]
                if spec.preprocessor is not None:
                    x = spec.preprocessor.pre_process(x)
                p = self._cast_params(p)
                m = in_masks[0]
                if (isinstance(layer, BaseRecurrentLayer)
                        and carry_entry is not None):
                    out, c = layer.forward_with_carry(
                        p, x, carry_entry, train=train, rng=lrng, mask=m)
                    return out, None, c
                # a layer wired to further inputs (positions, an image's
                # embeddings) is handed them as `extras`
                more = {"extras": in_acts[1:]} if len(in_acts) > 1 else {}
                if layer.has_state():
                    out, st = layer.forward_with_state(
                        p, x, state_entry, train=train, rng=lrng, mask=m,
                        **more)
                    return out, st, None
                return (layer.forward(p, x, train=train, rng=lrng, mask=m,
                                      **more),
                        None, None)
            return (spec.conf.forward(in_acts, masks=in_masks, train=train,
                                      rng=lrng), None, None)

    def _convbn_plan(self):
        """{batch-norm vertex: convolution vertex} for every pair the
        training forward computes as one function
        (`_conv1x1_bn_train_fused`: a backward that never reads the
        convolution's output; the step is HBM-bound). The rule is read from
        the graph, once a container: an EXPANDING 1x1 convolution
        (n_out > n_in: the output is the wide tensor, and the backward's
        extra Cin x Cin product stays under the convolution's own work)
        with no bias, no padding, identity activation and no input dropout,
        whose only consumer is a batch norm with learned scale and shift,
        the fused backward and no preprocessor. Every other convolution and
        batch norm runs its own layer's code. The gauge
        `train.convbn_pairs` says how many the plan holds."""
        if getattr(self, "_convbn_plan_cache", None) is None:
            verts = self.conf.vertices
            consumers = {}
            for name, spec in verts.items():
                for i in spec.inputs:
                    consumers.setdefault(i, []).append(name)
            plan = {}
            for name, spec in verts.items():
                bn = spec.conf
                cspec = verts.get(spec.inputs[0]) if spec.inputs else None
                if (type(bn) is not BatchNormalization or cspec is None
                        or type(cspec.conf) is not ConvolutionLayer):
                    continue
                conv = cspec.conf
                if (conv.kernel_size == (1, 1) and not conv.has_bias
                        and (conv.padding == (0, 0)
                             or str(conv.convolution_mode).lower() == "same")
                        and (activations.get(conv.activation)
                             is activations.identity)
                        and not conv.dropout
                        and conv.n_out > conv.n_in
                        and consumers[cspec.name] == [name]
                        and cspec.name not in self.conf.network_outputs
                        and spec.preprocessor is None
                        and bn.fused_backward and not bn.lock_gamma_beta):
                    plan[name] = cspec.name
            self._convbn_plan_cache = plan
            obs.default_registry().gauge("train.convbn_pairs").set(len(plan))
        return self._convbn_plan_cache

    def _pair_of(self, name, train, params, acts):
        """(convolution's spec, its parameters, its input) when `name` is
        the batch norm of a planned pair and this forward trains; None
        otherwise, and where the convolution's side is not in reach (a
        remat segment that holds the batch norm alone)."""
        conv = self._convbn_plan().get(name) if train else None
        if conv is None:
            return None
        cspec = self.conf.vertices[conv]
        a = acts.get(cspec.inputs[0])
        if a is None or not params.get(conv) or not params.get(name):
            return None
        return cspec, params[conv], a

    def _remat_plan(self):
        """Segment the topological order at element-wise (residual-add)
        vertex boundaries. Returns (segment-id per vertex, n_segments)."""
        if getattr(self, "_remat_plan_cache", None) is None:
            from ..conf.graph_vertices import ElementWiseVertex
            seg, s = {}, 0
            for name in self.conf.topological_order:
                seg[name] = s
                spec = self.conf.vertices[name]
                if (not spec.is_layer
                        and isinstance(spec.conf, ElementWiseVertex)):
                    s += 1
            self._remat_plan_cache = (seg, s + 1)
        return self._remat_plan_cache

    def _apply_graph_remat(self, params, state, inputs, *, train, rng):
        """`_apply_graph` with each residual segment under `jax.checkpoint`:
        only segment-boundary activations become autodiff residuals; the
        interior (conv outputs, BN normalized, ReLU) is recomputed during
        the backward. Only reached for mask-free, carry-free graphs (the
        CNN shape this lever targets)."""
        cdt = self.compute_dtype
        seg_of, n_seg = self._remat_plan()
        order = self.conf.topological_order
        segments = [[] for _ in range(n_seg)]
        for name in order:
            segments[seg_of[name]].append(name)
        # activations needed beyond their own segment stay live; output
        # heads' INPUTS too — _loss_fn recomputes each head on its
        # pre-head activation to attach the loss
        needed_later = set(self.conf.network_outputs)
        for out in self.conf.network_outputs:
            needed_later.update(self.conf.vertices[out].inputs)
        for name in order:
            for inp in self.conf.vertices[name].inputs:
                if seg_of.get(inp, -1) != seg_of[name]:
                    needed_later.add(inp)
        vi_of = {name: i for i, name in enumerate(order)}
        acts = {}
        for name in self.conf.network_inputs:
            x = inputs[name]
            if jnp.issubdtype(x.dtype, jnp.floating):
                x = x.astype(cdt)
            acts[name] = x
        new_state = dict(state)

        for si, seg_names in enumerate(segments):
            if not seg_names:
                continue
            ext_in = sorted({i for n in seg_names
                             for i in self.conf.vertices[n].inputs
                             if seg_of.get(i, -1) != si})
            layer_names = tuple(n for n in seg_names
                                if self.conf.vertices[n].is_layer)
            stateful = tuple(n for n in layer_names
                             if self.conf.vertices[n].conf.has_state())
            out_names = tuple(n for n in seg_names if n in needed_later)

            def seg_fn(p_sub, st_sub, in_list, _names=tuple(seg_names),
                       _ext=tuple(ext_in), _outs=out_names):
                local = dict(zip(_ext, in_list))
                st_new = {}
                for name in _names:
                    spec = self.conf.vertices[name]
                    in_acts = [local[i] for i in spec.inputs]
                    lrng = (jax.random.fold_in(rng, vi_of[name])
                            if rng is not None else None)
                    # same vertex dispatch as the default path — shared
                    # helper, so the two forwards cannot drift
                    out, st, _ = self._forward_vertex(
                        spec, p_sub.get(name), in_acts,
                        [None] * len(in_acts), train=train, lrng=lrng,
                        state_entry=st_sub.get(name),
                        pair=self._pair_of(name, train, p_sub, local))
                    if st is not None:
                        st_new[name] = st
                    local[name] = out
                return [local[o] for o in _outs], st_new

            # the final segment (head + loss inputs) gains nothing from
            # recompute — its residuals back the loss directly
            call = jax.checkpoint(seg_fn) if si < n_seg - 1 else seg_fn
            outs, st_new = call({n: params[n] for n in layer_names},
                                {n: state[n] for n in stateful},
                                [acts[i] for i in ext_in])
            acts.update(zip(out_names, outs))
            new_state.update(st_new)
        masks = {name: None for name in acts}
        return acts, new_state, masks, None

    def _canon_inputs(self, features):
        if isinstance(features, dict):
            return features
        if not isinstance(features, (list, tuple)):
            features = [features]
        if len(features) != len(self.conf.network_inputs):
            raise ValueError(
                f"Graph has {len(self.conf.network_inputs)} inputs "
                f"{self.conf.network_inputs}, got {len(features)} arrays")
        return dict(zip(self.conf.network_inputs, features))

    def _canon_masks(self, masks):
        if masks is None:
            return None
        if isinstance(masks, dict):
            return masks
        if not isinstance(masks, (list, tuple)):
            masks = [masks]
        return {n: m for n, m in zip(self.conf.network_inputs, masks)
                if m is not None}

    # ------------------------------------------------------------------
    # Loss over output vertices
    # ------------------------------------------------------------------
    def _loss_fn(self, params, state, features, labels, fmasks, lmasks, rng,
                 train, carries=None):
        """features: dict name->arr; labels: list aligned with network_outputs."""
        acts, new_state, masks, new_carries = self._apply_graph(
            params, state, features, train=train, rng=rng, fmasks=fmasks,
            carries=carries, allow_remat=True)
        total = 0.0
        order = {n: i for i, n in enumerate(self.conf.topological_order)}
        for oi, out_name in enumerate(self.conf.network_outputs):
            spec = self.conf.vertices[out_name]
            layer = spec.conf
            if not hasattr(layer, "compute_score_per_example"):
                continue  # non-loss output (pure inference head)
            # recompute the head on its pre-head input to attach the loss
            x = acts[spec.inputs[0]]
            lrng = (jax.random.fold_in(rng, order[out_name])
                    if rng is not None else None)
            lmask = None
            if lmasks:
                lmask = (lmasks[oi] if isinstance(lmasks, (list, tuple))
                         else lmasks.get(out_name))
            with jax.named_scope(f"loss.{out_name}"):
                if spec.preprocessor is not None:
                    x = spec.preprocessor.pre_process(x)
                p = self._cast_params(params[out_name])
                per_ex = layer.compute_score_per_example(
                    p, x, labels[oi], train=train, rng=lrng, mask=lmask)
                if per_ex.dtype == jnp.bfloat16:
                    per_ex = per_ex.astype(jnp.float32)
                total = total + jnp.mean(per_ex)
        reg = 0.0
        for n in self._layer_names():
            layer = self.conf.vertices[n].conf
            reg = reg + layer.reg_score(params[n])
            # a loss that depends on a layer's ACTIVATIONS (a sparse
            # attention's indexer learns from the attention it serves):
            # the layer returns it in its state, the score takes it
            if layer.has_layer_loss:
                total = total + new_state[n]["layer_loss"]
        return total + reg, (new_state, new_carries)

    # ------------------------------------------------------------------
    # Fused train step (same contract as MultiLayerNetwork.make_raw_step)
    # ------------------------------------------------------------------
    def make_grad_fn(self):
        """(params, state, batch) -> (grads, score, new_state, new_carries) —
        gradient half of the step (async-PS worker compute; see
        multilayer.make_grad_fn)."""
        def grad_fn(params, state, batch):
            (score, (new_state, new_carries)), grads = jax.value_and_grad(
                self._loss_fn, has_aux=True)(
                    params, state, batch["features"], batch["labels"],
                    batch.get("fmask"), batch.get("lmask"), batch["rng"],
                    True, batch.get("carries"))
            return grads, score, new_state, new_carries
        return grad_fn

    def make_apply_fn(self):
        """(params, ustate, grads, iteration) -> (new_params, new_ustate) —
        updater half of the step (reference ComputationGraphUpdater)."""
        names = self._layer_names()

        @jax.named_scope("update")
        def apply_updates(params, ustate, grads, iteration):
            minimize = self.conf.global_conf.get("minimize", True)
            new_params = dict(params)
            new_ustate = dict(ustate)
            for n in names:
                layer = self.conf.vertices[n].conf
                g_n = U.normalize_gradients(
                    grads[n], layer.gradient_normalization,
                    layer.gradient_normalization_threshold or 1.0)
                _, apply_fn = U.get(layer.updater or "sgd")
                hp = layer.updater_hp()
                p_new, s_new = {}, {}
                for k, p in params[n].items():
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
                    upd, s_k = apply_fn(ustate[n][k], g_n[k], lr, hp)
                    p_new[k] = p - upd if minimize else p + upd
                    # keep the stored state dtype (bf16 when
                    # updater_state_dtype is set; math promotes to f32)
                    s_new[k] = jax.tree.map(
                        lambda a, old: a.astype(old.dtype), s_k, ustate[n][k])
                new_params[n] = p_new
                new_ustate[n] = s_new
            return new_params, new_ustate

        return apply_updates

    def make_raw_step(self, emit_health=False):
        """Same contract as MultiLayerNetwork.make_raw_step:
        emit_health=True appends the scalar health pytree to the return
        tuple and gates the whole update on the all-finite predicate
        (`jnp.where` — a poisoned batch is skipped on device); False
        compiles the identical program as before."""
        grad_fn = self.make_grad_fn()
        apply_updates = self.make_apply_fn()

        def step(params, ustate, state, batch):
            grads, score, new_state, new_carries = grad_fn(params, state,
                                                           batch)
            new_params, new_ustate = apply_updates(params, ustate, grads,
                                                   batch["iteration"])
            if emit_health:
                from ...common import health as H
                with jax.named_scope("health"):
                    health = H.grad_health(grads, score)
                    ok = health["all_finite"]
                    new_params = H.gate_update(ok, new_params, params)
                    new_ustate = H.gate_update(ok, new_ustate, ustate)
                    new_state = H.gate_update(ok, new_state, state)
                    if batch.get("carries") is not None:
                        new_carries = H.gate_update(ok, new_carries,
                                                    batch["carries"])
                return (new_params, new_ustate, new_state, score,
                        new_carries, health)
            return new_params, new_ustate, new_state, score, new_carries

        return step

    def _make_step(self):
        emit_health = getattr(self, "_health_policy", None) is not None
        self._step_emits_health = emit_health
        raw = self.make_raw_step(emit_health)

        def step(params, ustate, state, loop, features, labels, fmask, lmask,
                 carries=None):
            # device-resident loop state (iteration counter + PRNG key):
            # advances inside the compiled step — no per-iteration host
            # scalar transfer or key-split dispatch (see multilayer.py)
            rng, next_rng = jax.random.split(loop["rng"])
            batch = {"features": features, "labels": labels, "fmask": fmask,
                     "lmask": lmask, "iteration": loop["iteration"],
                     "rng": rng, "carries": carries}
            p, u, s, score, car, *extras = raw(params, ustate, state, batch)
            # loop state advances on skipped steps too (see multilayer.py)
            new_loop = {"iteration": loop["iteration"] + 1.0, "rng": next_rng}
            return (p, u, s, score, car, new_loop) + tuple(extras)

        return jax.jit(step, donate_argnums=(0, 1, 2, 3))

    def publish_layer_gauges(self, registry=None):
        """Set `<kind>.<vertex>.<name>` gauges from every layer's
        `gauges(state)` (a `moe` layer's routed pairs, a `sparseattention`'s
        selected keys a query and indexer loss), as the last step left
        them. One host read; call it outside a timed window. Returns
        {gauge name: value}."""
        registry = registry or obs.default_registry()
        out = {}
        for n in self._layer_names():
            layer = self.conf.vertices[n].conf
            for k, v in layer.gauges(self._model_state[n]).items():
                name = f"{layer.layer_type}.{n}.{k}"
                out[name] = float(v)
                registry.gauge(name).set(out[name])
        return out

    def training_health(self, policy=True, checkpoint_dir=None,
                        checkpoint_every=10, keep_checkpoints=3):
        """Arm the training-health watchdog (see
        MultiLayerNetwork.training_health — identical contract)."""
        from ...common import health as H
        H.install(self, policy, checkpoint_dir, checkpoint_every,
                  keep_checkpoints)
        return self

    def fused_steps(self, k=8):
        """Fuse K optimizer steps into one device dispatch (see
        MultiLayerNetwork.fused_steps — identical contract; multi-input
        feature dicts and multi-output label lists stack per leaf)."""
        from .. import fused as F
        return F.install(self, k)

    def _fused_k(self):
        k = getattr(self, "_fused_steps", 1)
        if (k <= 1
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
    # fit — reference ComputationGraph.fit:809
    # ------------------------------------------------------------------
    def fit(self, data, labels=None, num_epochs=1):
        self._ensure_init()
        if labels is not None:
            data = MultiDataSet(data, labels)
        if isinstance(data, DataSet):
            data = _dataset_to_mds(data)
        if isinstance(data, MultiDataSet):
            return self._fit_mds(data)
        # iterator of DataSet / MultiDataSet: prefetch + stage off the
        # training thread like the reference (ComputationGraph.fit wraps
        # in Async(Multi)DataSetIterator), with the bf16 feature wire for
        # bf16 models (bit-identical — the step casts features anyway)
        from ...datasets.iterators import (AsyncDataSetIterator,
                                           DataSetIterator,
                                           wrap_async_for_fit)
        wrapped_here = False
        if isinstance(data, DataSetIterator):
            # the wrapper stages DataSet AND MultiDataSet batches
            # (per-batch dispatch), so one class covers both protocols.
            # A caller-supplied plain iterator may be mid-stream: reset
            # BEFORE wrapping so the fresh wrapper prefetches from 0 and
            # the epoch-0 reset skip is trivially safe (ADVICE r5)
            wrapped_here = not isinstance(data, AsyncDataSetIterator)
            if wrapped_here:
                data.reset()
            data = wrap_async_for_fit(
                data, self.compute_dtype,
                queue_size=max(2, getattr(self, "_fused_steps", 1) + 1))
        for epoch in range(num_epochs):
            # a fresh async wrapper fit() itself created is already
            # prefetching; resetting it on epoch 0 would drain (and
            # stage) one full pass unseen. CALLER-supplied iterators may
            # be mid-stream and reset unconditionally (ADVICE r5)
            if hasattr(data, "reset") and (
                    epoch > 0 or not wrapped_here
                    or not getattr(data, "has_next", lambda: False)()):
                data.reset()
            it = iter(data) if not hasattr(data, "has_next") else None
            if it is not None:
                for ds in it:
                    self._fit_mds(_dataset_to_mds(ds)
                                  if isinstance(ds, DataSet) else ds)
            else:
                while data.has_next():
                    k = (self._fused_k()
                         if self.conf.backprop_type != "tbptt" else 1)
                    if k <= 1:
                        ds = next_processed(data)
                        self._fit_mds(_dataset_to_mds(ds)
                                      if isinstance(ds, DataSet) else ds)
                        continue
                    from .. import fused as F
                    group = []
                    g = F.group_size(self, k)
                    with obs.TRACER.span("train.stage", cat="train", k=g):
                        while len(group) < g and data.has_next():
                            ds = next_processed(data)
                            group.append(_dataset_to_mds(ds)
                                         if isinstance(ds, DataSet) else ds)
                    if len(group) == g and F.uniform_group(group):
                        self._fit_mds_fused(group)
                    else:
                        # ragged tail / mixed shapes: single-step stream
                        for mds in group:
                            self._fit_mds(mds)
            self.conf.epoch_count += 1
        return self

    def _canon_mds(self, mds):
        """One MultiDataSet -> the raw-step batch pieces (name-keyed
        feature dict, label list, mask trees) — the _fit_mds conversion,
        shared with the fused super-batch path."""
        features = {n: jnp.asarray(f)
                    for n, f in zip(self.conf.network_inputs, mds.features)}
        labels = [jnp.asarray(l) for l in mds.labels]
        fmasks = None
        if mds.features_masks:
            fmasks = {n: jnp.asarray(m) if m is not None else None
                      for n, m in zip(self.conf.network_inputs,
                                      mds.features_masks)}
        lmasks = None
        if mds.labels_masks:
            lmasks = [jnp.asarray(m) if m is not None else None
                      for m in mds.labels_masks]
        return features, labels, fmasks, lmasks

    def _fit_mds_fused(self, group):
        """ONE dispatch for len(group) staged MultiDataSets (see
        MultiLayerNetwork._fit_super_batch — same contract, tree-stacked
        multi-input/multi-output batch pieces)."""
        from .. import fused as F
        emit_health = getattr(self, "_health_policy", None) is not None
        g = len(group)
        parts = [self._canon_mds(mds) for mds in group]

        def build():
            raw = self.make_raw_step(emit_health)

            def prog(params, ustate, state, loop, batch_list):
                return F.scan_batches(raw, params, ustate, state, loop,
                                      batch_list)

            return jax.jit(prog, donate_argnums=(0, 1, 2, 3))

        step = F.fused_program(self, ("batch", g), build)
        batch_list = tuple(
            {"features": p[0], "labels": p[1], "fmask": p[2],
             "lmask": p[3]} for p in parts)
        self._last_batch_size = int(
            jax.tree.leaves(parts[0][0])[0].shape[0])
        with obs.TRACER.span("train.fused_group", cat="train", k=g):
            with obs.TRACER.span("train.dispatch", cat="train", k=g):
                (self._params, self._updater_state, self._model_state,
                 scores, _, self._loop, *extras) = step(
                     self._params, self._updater_state, self._model_state,
                     self._loop_state(), batch_list)
            from ...common import health as H
            with obs.TRACER.span("train.health", cat="train", k=g):
                rb = H.finish_fused(self, scores,
                                    extras[-1] if emit_health else None, g)
        if rb is not None:
            for mds in group[rb + 1:]:  # counters/rng restored; replay
                self._fit_mds(mds)
        return self

    def lower_step(self, ds, sharding=None):
        """Lower (trace without running) the jitted step `fit` calls for one
        DataSet / MultiDataSet of this shape, as `ParallelWrapper.lower_step`
        does for the sharded step: `.compile().as_text()` is the compiled
        HLO whose `op_name` metadata carries the layer scopes
        (optimize/profiler.py `op_scopes`). Consumes nothing: the loop
        state and the rng stream are left as they are. With `sharding`
        every argument is lowered as a shape placed on it: a device that is
        described and not attached holds no array (tools/step_bytes.py)."""
        self._ensure_init()
        if self._jit_step is None:
            self._jit_step = self._make_step()
        if isinstance(ds, DataSet):
            ds = _dataset_to_mds(ds)
        loop = self._loop or {"iteration": jnp.zeros((), jnp.float32),
                              "rng": self._rng}
        args = (self._params, self._updater_state, self._model_state, loop,
                *self._canon_mds(ds))
        if sharding is not None:
            args = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=sharding), args)
        return self._jit_step.lower(*args)

    def _fit_mds(self, mds: MultiDataSet):
        if self._jit_step is None:
            self._jit_step = self._make_step()
        features, labels, fmasks, lmasks = self._canon_mds(mds)
        self._last_batch_size = int(mds.features[0].shape[0])
        if self.conf.backprop_type == "tbptt":
            return self._fit_tbptt(features, labels, fmasks, lmasks)
        num_iterations = int(self.conf.global_conf.get("num_iterations", 1))
        for _ in range(num_iterations):
            with obs.TRACER.span("train.dispatch", cat="train"):
                (self._params, self._updater_state, self._model_state,
                 score, _, self._loop, *extras) = self._jit_step(
                     self._params, self._updater_state, self._model_state,
                     self._loop_state(), features, labels, fmasks, lmasks)
            action = "ok"
            if not getattr(self, "_step_emits_health", False):
                self._score = score
            else:
                from ...common import health as H
                with obs.TRACER.span("train.health", cat="train"):
                    action = H.finish_step(self, extras[-1], score)
                if action == "rollback":
                    break           # counters/rng restored; next batch
            self.conf.iteration_count += 1
            for l in self.listeners:
                l.iteration_done(self, self.conf.iteration_count - 1)
            if action == "ok" and getattr(self, "_step_emits_health", False):
                from ...common.health import fit_loop_checkpoint
                with obs.TRACER.span("train.checkpoint", cat="train"):
                    fit_loop_checkpoint(self)
        return self

    # ------------------------------------------------------------------
    # TBPTT + streaming RNN state — reference ComputationGraph TBPTT path
    # + rnnTimeStep
    # ------------------------------------------------------------------
    def _recurrent_names(self):
        return [n for n in self._layer_names()
                if isinstance(self.conf.vertices[n].conf, BaseRecurrentLayer)]

    def _init_carries(self, batch_size):
        # compute dtype, not param dtype — see MultiLayerNetwork
        # ._init_carries (cast-on-entry makes values identical; the
        # returned carry is compute dtype, which the fused scan requires)
        return {n: self.conf.vertices[n].conf.init_carry(batch_size,
                                                         self.compute_dtype)
                for n in self._recurrent_names()}

    def _fit_tbptt(self, features, labels, fmasks, lmasks):
        """Slice the time axis into tbptt_fwd_length segments, carrying RNN
        state (not gradients) across segments — reference ComputationGraph
        TBPTT (same semantics as MultiLayerNetwork.doTruncatedBPTT:1140)."""
        seq_names = [n for n, f in features.items() if f.ndim >= 3]
        T = int(features[seq_names[0]].shape[1])
        L = self.conf.tbptt_fwd_length
        B = int(next(iter(features.values())).shape[0])
        carries = self._init_carries(B)
        t0 = 0
        while t0 < T:
            k = self._fused_k()
            if k > 1:
                from .. import fused as F
                g = min(F.group_size(self, k), (T - t0) // L)
                if g > 1:
                    carries, t0, done = self._fit_tbptt_fused(
                        features, labels, fmasks, lmasks, carries, t0, g,
                        T, L)
                    if done:        # rollback: abandon this sequence
                        return self
                    continue
            def _seg(a):
                # only sequence-shaped arrays have a time axis to slice;
                # static inputs/labels/masks pass through whole
                if a is None or a.ndim < 2 or a.shape[1] < T:
                    return a
                return a[:, t0:t0 + L]

            f_seg = {n: (_seg(f) if f.ndim >= 3 else f)
                     for n, f in features.items()}
            l_seg = [(_seg(l) if l.ndim >= 3 else l) for l in labels]
            fm_seg = ({n: _seg(m) for n, m in fmasks.items()}
                      if fmasks else None)
            lm_seg = ([_seg(m) for m in lmasks] if lmasks else None)
            with obs.TRACER.span("train.dispatch", cat="train",
                                 tbptt=True):
                (self._params, self._updater_state, self._model_state,
                 score, carries, self._loop, *extras) = self._jit_step(
                     self._params, self._updater_state, self._model_state,
                     self._loop_state(), f_seg, l_seg, fm_seg, lm_seg,
                     carries)
            action = "ok"
            if not getattr(self, "_step_emits_health", False):
                self._score = score
            else:
                from ...common import health as H
                action = H.finish_step(self, extras[-1], score)
                if action == "rollback":
                    break       # abandon the rest of this sequence
            self.conf.iteration_count += 1
            for l in self.listeners:
                l.iteration_done(self, self.conf.iteration_count - 1)
            if action == "ok" and getattr(self, "_step_emits_health", False):
                from ...common.health import fit_loop_checkpoint
                with obs.TRACER.span("train.checkpoint", cat="train"):
                    fit_loop_checkpoint(self)
            t0 += L
        return self

    def _fit_tbptt_fused(self, features, labels, fmasks, lmasks, carries,
                         t0, g, T, L):
        """ONE dispatch for g full TBPTT segments (see
        MultiLayerNetwork._fit_tbptt_fused): the scan body dynamic-slices
        sequence-shaped arrays (static inputs/labels/masks pass through
        whole, as in the sequential loop) and threads the RNN carries
        through the scan carry. Returns (carries', next_t0, rolled_back)."""
        from .. import fused as F
        emit_health = getattr(self, "_health_policy", None) is not None

        def build():
            raw = self.make_raw_step(emit_health)

            def prog(params, ustate, state, loop, features, labels,
                     fmask, lmask, carries, t0s):
                def make_batch(s):
                    def sl(a, min_ndim):
                        # same slice conditions as the sequential loop's
                        # _seg (static at trace time): features/labels
                        # only when sequence-shaped (ndim >= 3), masks
                        # from ndim >= 2; arrays without a full time
                        # axis pass through whole
                        if (a is None or a.ndim < min_ndim
                                or a.ndim < 2 or a.shape[1] < T):
                            return a
                        return jax.lax.dynamic_slice_in_dim(a, s, L, axis=1)

                    return {"features": jax.tree.map(
                                lambda a: sl(a, 3), features),
                            "labels": jax.tree.map(
                                lambda a: sl(a, 3), labels),
                            "fmask": (jax.tree.map(
                                lambda a: sl(a, 2), fmask)
                                if fmask is not None else None),
                            "lmask": (jax.tree.map(
                                lambda a: sl(a, 2), lmask)
                                if lmask is not None else None)}

                return F.scan_steps(raw, params, ustate, state, loop,
                                    carries, t0s, make_batch)

            return jax.jit(prog, donate_argnums=(0, 1, 2, 3))

        key = ("tbptt", g, T, L,
               fmasks is not None, lmasks is not None)
        step = F.fused_program(self, key, build)
        t0s = jnp.arange(t0, t0 + g * L, L, dtype=jnp.int32)
        with obs.TRACER.span("train.fused_group", cat="train", k=g,
                             tbptt=True):
            with obs.TRACER.span("train.dispatch", cat="train", k=g,
                                 tbptt=True):
                (self._params, self._updater_state, self._model_state,
                 scores, carries, self._loop, *extras) = step(
                     self._params, self._updater_state, self._model_state,
                     self._loop_state(), features, labels, fmasks, lmasks,
                     carries, t0s)
            from ...common import health as H
            with obs.TRACER.span("train.health", cat="train", k=g):
                rb = H.finish_fused(self, scores,
                                    extras[-1] if emit_health else None, g)
        return carries, t0 + g * L, rb is not None

    def rnn_time_step(self, *features):
        """Single/multi-step streaming inference with carried RNN state
        (reference: ComputationGraph.rnnTimeStep). Returns the list of
        output activations."""
        self._ensure_init()
        if len(features) == 1 and isinstance(features[0], (list, tuple, dict)):
            features = features[0]
        inputs = {n: jnp.asarray(x)
                  for n, x in self._canon_inputs(features).items()}
        single = all(x.ndim == 2 for x in inputs.values())
        if single:
            inputs = {n: x[:, None, :] for n, x in inputs.items()}
        B = int(next(iter(inputs.values())).shape[0])
        state = getattr(self, "_rnn_state", None)
        if state is not None:
            held = next(iter(next(iter(state.values())).values())).shape[0] \
                if state else B
            if held != B:
                raise ValueError(
                    f"rnn_time_step batch size changed ({held} -> {B}); "
                    "call rnn_clear_previous_state() first")
        if state is None:
            self._rnn_state = self._init_carries(B)
        if "rnn_step" not in self._jit_forward:
            def fwd(params, state, inputs, rng, carries):
                acts, _, _, new_carries = self._apply_graph(
                    params, state, inputs, train=False, rng=rng,
                    carries=carries)
                return ([acts[n] for n in self.conf.network_outputs],
                        new_carries)
            self._jit_forward["rnn_step"] = jax.jit(fwd)
        self._rng, rng = jax.random.split(self._rng)
        outs, self._rnn_state = self._jit_forward["rnn_step"](
            self._params, self._model_state, inputs, rng, self._rnn_state)
        if single:
            outs = [o[:, 0] if o.ndim >= 3 else o for o in outs]
        return outs

    rnnTimeStep = rnn_time_step

    def rnn_clear_previous_state(self):
        self._rnn_state = None

    rnnClearPreviousState = rnn_clear_previous_state

    # ------------------------------------------------------------------
    # Inference — reference ComputationGraph.output
    # ------------------------------------------------------------------
    def output(self, *features, train=False, features_masks=None):
        """Returns list of output activations aligned with network_outputs."""
        self._ensure_init()
        if len(features) == 1 and isinstance(features[0], (list, tuple, dict)):
            features = features[0]
        inputs = {n: jnp.asarray(x)
                  for n, x in self._canon_inputs(features).items()}
        fmasks = self._canon_masks(features_masks)
        if fmasks:
            fmasks = {n: jnp.asarray(m) for n, m in fmasks.items()}
        key = ("output", bool(train), fmasks is not None)
        if key not in self._jit_forward:
            def fwd(params, state, inputs, fmasks, rng):
                acts, _, _, _ = self._apply_graph(params, state, inputs,
                                                  train=train, rng=rng,
                                                  fmasks=fmasks)
                return [acts[n] for n in self.conf.network_outputs]
            self._jit_forward[key] = jax.jit(fwd)
        self._rng, rng = jax.random.split(self._rng)
        return self._jit_forward[key](self._params, self._model_state, inputs,
                                      fmasks, rng)

    def feed_forward(self, *features, train=False):
        """Returns dict vertex-name -> activation."""
        self._ensure_init()
        if len(features) == 1 and isinstance(features[0], (list, tuple, dict)):
            features = features[0]
        inputs = {n: jnp.asarray(x)
                  for n, x in self._canon_inputs(features).items()}
        self._rng, rng = jax.random.split(self._rng)
        acts, _, _, _ = self._apply_graph(self._params, self._model_state,
                                          inputs, train=train, rng=rng)
        return acts

    feedForward = feed_forward

    def make_inference_fn(self):
        """PURE inference step `(params, state, x) -> [outputs]` — the
        MultiLayerNetwork.make_inference_fn twin for the serving layer.
        `x` is a single array (single-input graphs — the serving batcher
        coalesces one request tensor) or a dict name->array for
        multi-input graphs. train=False + constant rng: pure in
        (params, state, x), so serving determinism pins hold; params are
        arguments, so hot swap needs no recompile."""
        self._ensure_init()
        in_names = list(self.conf.network_inputs)

        def infer(params, state, x):
            inputs = x if isinstance(x, dict) else {in_names[0]: x}
            rng = jax.random.PRNGKey(0)
            acts, _, _, _ = self._apply_graph(params, state, inputs,
                                              train=False, rng=rng)
            return [acts[n] for n in self.conf.network_outputs]

        return infer

    # ------------------------------------------------------------------
    # Score / gradients (gradient-check compatible API)
    # ------------------------------------------------------------------
    def score(self, data=None, training=False):
        if data is None:
            return float(self._score) if self._score is not None else float("nan")
        self._ensure_init()
        if isinstance(data, DataSet):
            data = _dataset_to_mds(data)
        features = {n: jnp.asarray(f)
                    for n, f in zip(self.conf.network_inputs, data.features)}
        labels = [jnp.asarray(l) for l in data.labels]
        # Honor DataSet/MultiDataSet masks (same as _fit_mds) — dropping them
        # silently skews validation loss on variable-length sequence data.
        fmasks = None
        if data.features_masks:
            fmasks = {n: jnp.asarray(m) if m is not None else None
                      for n, m in zip(self.conf.network_inputs,
                                      data.features_masks)}
        lmasks = None
        if data.labels_masks:
            lmasks = [jnp.asarray(m) if m is not None else None
                      for m in data.labels_masks]
        self._rng, rng = jax.random.split(self._rng)
        s, _ = self._loss_fn(self._params, self._model_state, features, labels,
                             fmasks, lmasks, rng, training)
        return float(s)

    def compute_gradient_and_score(self, features, labels, fmask=None,
                                   lmask=None, train=True):
        self._ensure_init()
        rng = jax.random.PRNGKey(0)
        features = {n: jnp.asarray(f) for n, f in
                    self._canon_inputs(features).items()}
        labels = [jnp.asarray(l) for l in _as_list(labels)]
        fmasks = self._canon_masks(fmask)
        if fmasks:
            fmasks = {n: jnp.asarray(m) for n, m in fmasks.items()}
        lmasks = ([jnp.asarray(m) if m is not None else None
                   for m in _as_list(lmask)] if lmask is not None else None)
        (score, _), grads = jax.value_and_grad(self._loss_fn, has_aux=True)(
            self._params, self._model_state, features, labels, fmasks, lmasks,
            rng, train)
        return grads, float(score)

    # ------------------------------------------------------------------
    # Flattened-params contract — reference init:281-345
    # ------------------------------------------------------------------
    def _param_leaves(self):
        leaves = []
        for n in self._layer_names():
            p = self._params[n]
            for k in sorted(p.keys(), key=_param_sort_key):
                leaves.append(((n, k), p[k]))
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
        new_params = {n: dict(p) for n, p in self._params.items()}
        for (n, k), v in self._param_leaves():
            sz = int(np.prod(v.shape)) if v.shape else 1
            new_params[n][k] = jnp.asarray(
                flat[offset:offset + sz].reshape(v.shape), v.dtype)
            offset += sz
        if offset != flat.size:
            raise ValueError(f"Expected {offset} params, got {flat.size}")
        self._params = new_params

    setParams = set_params

    def num_params(self):
        return int(sum(int(np.prod(v.shape)) for _, v in self._param_leaves()))

    numParams = num_params

    def unflatten_params(self, flat):
        offset = 0
        out = {n: dict(p) for n, p in self._params.items()}
        for n in self._layer_names():
            p = self._params[n]
            for k in sorted(p.keys(), key=_param_sort_key):
                v = p[k]
                sz = int(np.prod(v.shape)) if v.shape else 1
                out[n][k] = flat[offset:offset + sz].reshape(v.shape).astype(v.dtype)
                offset += sz
        return out

    def make_flat_score_fn(self, features, labels, fmask=None, lmask=None,
                           train=True):
        features = {n: jnp.asarray(f) for n, f in
                    self._canon_inputs(features).items()}
        labels = [jnp.asarray(l) for l in _as_list(labels)]
        fmasks = self._canon_masks(fmask)
        if fmasks:
            fmasks = {n: jnp.asarray(m) for n, m in fmasks.items()}
        lmasks = ([jnp.asarray(m) if m is not None else None
                   for m in _as_list(lmask)] if lmask is not None else None)
        rng = jax.random.PRNGKey(0)

        def score_fn(flat):
            params = self.unflatten_params(flat)
            s, _ = self._loss_fn(params, self._model_state, features, labels,
                                 fmasks, lmasks, rng, train)
            return s

        return jax.jit(score_fn)

    def flatten_gradients(self, grads):
        vecs = []
        for n in self._layer_names():
            p = grads[n]
            for k in sorted(p.keys(), key=_param_sort_key):
                vecs.append(np.asarray(p[k], np.float64).ravel())
        return np.concatenate(vecs) if vecs else np.zeros((0,))

    # ------------------------------------------------------------------
    # Evaluation
    # ------------------------------------------------------------------
    def evaluate(self, data, output_index=0):
        from ...eval.evaluation import Evaluation
        from ...datasets.iterators import (DataSetIterator,
                                           wrap_async_for_fit)
        ev = Evaluation()
        if isinstance(data, (DataSet, MultiDataSet)):
            data = [data]
        if isinstance(data, DataSetIterator):
            # full-pass guarantee (the old explicit reset), then stream
            # through the async wrapper (prefetch + staging overlap the
            # eval compute; one batch resident instead of the whole set)
            data.reset()
            data = wrap_async_for_fit(data, self.compute_dtype)
        for ds in data:
            mds = _dataset_to_mds(ds) if isinstance(ds, DataSet) else ds
            outs = self.output(mds.features,
                               features_masks=mds.features_masks)
            lmask = (mds.labels_masks[output_index]
                     if mds.labels_masks else None)
            ev.eval(mds.labels[output_index],
                    np.asarray(outs[output_index]), mask=lmask)
        return ev

    # ------------------------------------------------------------------
    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    setListeners = set_listeners

    def clone(self):
        net = ComputationGraph(self.conf.clone())
        if self._params is not None:
            net.init()
            # materialize COPIES: aliasing the live arrays would let the
            # next donated train step delete the clone's buffers with it
            net._params = jax.tree.map(jnp.copy, self._params)
            net._updater_state = jax.tree.map(jnp.copy, self._updater_state)
            net._model_state = jax.tree.map(jnp.copy, self._model_state)
        return net

    def get_layer(self, name):
        return self.conf.vertices[name].conf


def _keeps_time_axis(layer):
    """Whether the layer's output still has the input's time axis (mask
    stays meaningful). Recurrent layers and per-timestep heads do."""
    from ..conf.input_type import RecurrentInputType
    if isinstance(layer, BaseRecurrentLayer):
        return True
    return getattr(layer, "layer_type", "") in ("rnnoutput", "activation",
                                                "dropoutlayer", "batchnorm",
                                                "loss")


def _dataset_to_mds(ds: DataSet) -> MultiDataSet:
    return MultiDataSet(
        [ds.features], [ds.labels],
        [ds.features_mask] if ds.features_mask is not None else None,
        [ds.labels_mask] if ds.labels_mask is not None else None)


def _as_list(x):
    if isinstance(x, (list, tuple)):
        return list(x)
    return [x]


def _param_sort_key(k):
    order = {"W": 0, "RW": 1, "b": 2, "gamma": 0, "beta": 1, "vb": 3}
    return (order.get(k, 9), k)
