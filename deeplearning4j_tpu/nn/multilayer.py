"""MultiLayerNetwork — the sequential network container.

TPU-native equivalent of reference nn/multilayer/MultiLayerNetwork.java (2,486
LoC): init (:398-465 flattened params), fit(DataSetIterator) (:978), backprop
(:1064), output/feedForward (:1521/:657), computeGradientAndScore (:1807),
evaluate (:1574), TBPTT (:1140).

TPU-first redesign (SURVEY.md §7.1.3): instead of the reference's op-by-op
execution (per-layer activate/backpropGradient + separate updater ops +
in-place stepFunction on a flattened params vector), the ENTIRE training step
is ONE donated, jit-compiled XLA program. The step, its loop state, the fit
loops and the flattened-params contract are the Trainer's (nn/trainer.py),
shared with ComputationGraph; this file holds what a LIST of layers is: the
forward and the loss over it, activation statistics of the training forward,
layerwise pretraining, inference and evaluation. (LBFGS/CG/line-search
variants live in optimize/solvers.py.)
"""
from __future__ import annotations

import logging

import jax
import jax.numpy as jnp
import numpy as np

from ..datasets.dataset import DataSet
from ..datasets.iterators import (DataSetIterator, ListDataSetIterator,
                                  next_processed)
from .conf.layers.base import layer_scope
from .conf.neural_net_configuration import MultiLayerConfiguration
from .trainer import Trainer
from .updater import updaters as U

log = logging.getLogger(__name__)


class MultiLayerNetwork(Trainer):
    def __init__(self, conf: MultiLayerConfiguration):
        super().__init__(conf)
        self.layers = conf.layers

    # ------------------------------------------------------------------
    # What a list of layers supplies to the trainer (nn/trainer.py)
    # ------------------------------------------------------------------
    def _layer_items(self):
        return list(enumerate(self.layers))

    def _per_layer(self, values):
        return list(values)

    def _canon_batch(self, features, labels, fmask=None, lmask=None):
        return features, labels, fmask, lmask

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
            self._act_stats_gen += 1
            if not enabled:
                self._last_activation_stats = None
        return self

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
            for _ in range(num_epochs):
                self._fit_batch(data)
            return self
        if isinstance(data, DataSetIterator):
            return self._fit_iterator(data, num_epochs)
        raise TypeError(f"Cannot fit on {type(data)}")

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

    def add_listener(self, listener):
        self.listeners.append(listener)
        return self

    def get_layer(self, i):
        return self.layers[i]

    @property
    def n_layers(self):
        return len(self.layers)

