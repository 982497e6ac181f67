"""ComputationGraph — the DAG network container.

TPU-native equivalent of reference nn/graph/ComputationGraph.java (2,280 LoC):
topological forward (doForward per vertex, GraphVertex.java:117), autodiff
backward replacing doBackward (:123), multi-input/multi-output with
MultiDataSet, fit (:809), computeGradientAndScore (:952), flattened-params
contract (:281-345).

Same TPU-first redesign as MultiLayerNetwork, and the same Trainer
(nn/trainer.py: the step, its loop state, the fit loops, the flattened-params
contract): the whole training step (params, updater_state, model_state,
batch) -> (params', ...) is ONE donated jit-compiled XLA program; the DAG
structure is unrolled at trace time (the topological order is static), so XLA
sees a flat fused computation regardless of graph shape. This file holds what
a GRAPH of vertices is: the forward over the DAG (remat segments, the planned
convolution -> batch-norm pairs, extra inputs), the loss over the output
vertices with the layers' own losses, multi-input canonicalisation,
`lower_step`, the layer gauges, inference and evaluation.
"""
from __future__ import annotations

import logging

import jax
import jax.numpy as jnp
import numpy as np

from ... import obs
from ...datasets.dataset import DataSet, MultiDataSet
from ..conf.computation_graph_configuration import ComputationGraphConfiguration
from ..conf.layers.base import layer_scope
from ..conf.layers.normalization import convbn_pair_forward, convbn_pairs
from ..conf.layers.recurrent import BaseRecurrentLayer
from ..trainer import Trainer

log = logging.getLogger(__name__)


class ComputationGraph(Trainer):
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
        super().__init__(conf)
        # the configuration may ask for it too (`remat_segments(True)` on
        # the builder): a model that only fits rematerialised says so itself
        self._remat = bool(remat_segments
                           or conf.global_conf.get("remat_segments"))
        self._convbn_plan_cache = None
        self._remat_plan_cache = None

    # ------------------------------------------------------------------
    # What a graph of vertices supplies to the trainer (nn/trainer.py)
    # ------------------------------------------------------------------
    def _layer_names(self):
        """Layer vertices that hold parameters, in topological order (the
        flattened-params order — reference ComputationGraph.init:281-345
        uses topological order too). A vertex tied to another's parameters
        (`GraphVertexSpec.params_of`) has no entry in the parameters, the
        gradient or the updater's state: it is not here."""
        return [n for n in self.conf.topological_order
                if self.conf.vertices[n].is_layer
                and self.conf.vertices[n].params_of is None]

    def _tied_names(self):
        return [n for n in self.conf.topological_order
                if self.conf.vertices[n].params_of is not None]

    def _initial_state(self):
        # a tied vertex has state of its own, as any layer
        return {n: self.conf.vertices[n].conf.init_state()
                for n in self._layer_names() + self._tied_names()}

    def _layer_items(self):
        return [(n, self.conf.vertices[n].conf) for n in self._layer_names()]

    def _per_layer(self, values):
        return dict(zip(self._layer_names(), values))

    def _canon_batch(self, features, labels, fmask=None, lmask=None):
        """Loose arrays (one array, a list in `network_inputs` order or a
        name-keyed dict; one label an output) -> the raw step's name-keyed
        feature dict, label list and mask trees."""
        return (self._canon_inputs(features), _as_list(labels),
                self._canon_masks(fmask),
                None if lmask is None else _as_list(lmask))

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
                spec, params.get(spec.params_name), in_acts, in_masks,
                train=train,
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
            out, st = convbn_pair_forward(spec, p, state_entry, *pair,
                                          cast=self._cast_params)
            return out, st, None
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
        training forward computes as one function: `convbn_pairs` (beside
        the operation, in conf/layers/normalization.py) reads the rule from
        the graph, once a container. The gauge `train.convbn_pairs` says
        how many the plan holds."""
        if self._convbn_plan_cache is None:
            self._convbn_plan_cache = convbn_pairs(self.conf)
            obs.default_registry().gauge("train.convbn_pairs").set(
                len(self._convbn_plan_cache))
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
        vertex boundaries. Returns (segment-id per vertex, n_segments,
        {segment-id: the checkpoint names its layers keep across the
        segment's forward and backward (`LayerConf.remat_keeps`)}, the
        segments that keep nothing left out). The gauge
        `train.remat_kept_segments` says how many checkpoints carry such a
        policy (the final segment has no checkpoint)."""
        if self._remat_plan_cache is None:
            from ..conf.graph_vertices import ElementWiseVertex
            seg, s, keeps = {}, 0, {}
            for name in self.conf.topological_order:
                seg[name] = s
                spec = self.conf.vertices[name]
                names = spec.conf.remat_keeps() if spec.is_layer else ()
                if names:
                    keeps[s] = tuple(sorted({*keeps.get(s, ()), *names}))
                if (not spec.is_layer
                        and isinstance(spec.conf, ElementWiseVertex)):
                    s += 1
            keeps.pop(s, None)
            self._remat_plan_cache = (seg, s + 1, keeps)
            obs.default_registry().gauge("train.remat_kept_segments").set(
                len(keeps))
        return self._remat_plan_cache

    def _apply_graph_remat(self, params, state, inputs, *, train, rng):
        """`_apply_graph` with each residual segment under `jax.checkpoint`:
        only segment-boundary activations become autodiff residuals; the
        interior (conv outputs, BN normalized, ReLU) is recomputed during
        the backward, but for what a layer names for keeping
        (`_remat_plan`). Only reached for mask-free, carry-free graphs (the
        CNN shape this lever targets)."""
        cdt = self.compute_dtype
        seg_of, n_seg, keeps = self._remat_plan()
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
                        spec, p_sub.get(spec.params_name), in_acts,
                        [None] * len(in_acts), train=train, lrng=lrng,
                        state_entry=st_sub.get(name),
                        pair=self._pair_of(name, train, p_sub, local))
                    if st is not None:
                        st_new[name] = st
                    local[name] = out
                return [local[o] for o in _outs], st_new

            # the final segment (head + loss inputs) gains nothing from
            # recompute — its residuals back the loss directly. Where a
            # layer names arrays for keeping, the segment recomputes all
            # but those (no policy is `jax.checkpoint(seg_fn)`)
            policy = (jax.checkpoint_policies.save_only_these_names(
                *keeps[si]) if si in keeps else None)
            call = (jax.checkpoint(seg_fn, policy=policy)
                    if si < n_seg - 1 else seg_fn)
            outs, st_new = call({n: params[n] for n in dict.fromkeys(
                                     self.conf.vertices[m].params_name
                                     for m in layer_names)},
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
                p = self._cast_params(params[spec.params_name])
                per_ex = layer.compute_score_per_example(
                    p, x, labels[oi], train=train, rng=lrng, mask=lmask)
                if per_ex.dtype == jnp.bfloat16:
                    per_ex = per_ex.astype(jnp.float32)
                part = jnp.mean(per_ex)
                # an output that carries a weight says its own loss, as it
                # is before the weight, in its state
                weight = getattr(layer, "loss_weight", None)
                if weight is not None:
                    new_state[out_name] = {"loss": part}
                    part = weight * part
                total = total + part
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

    def publish_layer_gauges(self, registry=None):
        """Set `<kind>.<vertex>.<name>` gauges from every layer's
        `gauges(state)` (a `moe` layer's routed pairs, a `sparseattention`'s
        selected keys a query and indexer loss), as the last step left
        them. One host read; call it outside a timed window. Returns
        {gauge name: value}."""
        registry = registry or obs.default_registry()
        out = {}
        for n in self._layer_names() + self._tied_names():
            layer = self.conf.vertices[n].conf
            for k, v in layer.gauges(self._model_state[n]).items():
                name = f"{layer.layer_type}.{n}.{k}"
                out[name] = float(v)
                registry.gauge(name).set(out[name])
        return out

    # ------------------------------------------------------------------
    # fit — reference ComputationGraph.fit:809
    # ------------------------------------------------------------------
    def fit(self, data, labels=None, num_epochs=1):
        self._ensure_init()
        if labels is not None:
            data = MultiDataSet(data, labels)
        if isinstance(data, (DataSet, MultiDataSet)):
            return self._fit_batch(data)
        return self._fit_iterator(data, num_epochs)

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
        loop = self._loop or {"iteration": jnp.zeros((), jnp.float32),
                              "rng": self._rng}
        args = (self._params, self._updater_state, self._model_state, loop,
                *jax.tree.map(jnp.asarray, self._batch_parts(ds)))
        if sharding is not None:
            args = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=sharding), args)
        return self._single_step().lower(*args)

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
        state = self._rnn_state
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

