"""Pipeline parallelism (GPipe-style) over a "pipe" mesh axis.

The reference has NO pipeline parallelism (SURVEY.md §2.5 marks PP as
absent/optional) — this is a TPU-first extension: the canonical way to scale
past what tensor parallelism's per-layer collectives can feed over ICI.

Design (the "collective pipelining" recipe, jax-ml scaling-book style):

- Stages are SPMD shards of ONE jitted program over a mesh axis ``pipe``:
  stage s's parameters live on mesh slice s (stacked leading-axis-S pytree,
  sharded ``P("pipe")``), so each device stores 1/S of the model.
- A microbatched input [M, B, ...] flows through a ``lax.scan`` over
  T = M + S - 1 ticks. Each tick every stage computes on its current
  activation buffer, then buffers rotate one hop over ICI via
  ``lax.ppermute`` — the classic pipeline schedule expressed as data flow,
  with the bubble (S-1 idle ticks) explicit.
- The BACKWARD pipeline is not hand-written: ``jax.grad`` differentiates
  through scan+ppermute, and the transpose of a +1 rotation is a -1
  rotation, so XLA emits the reverse schedule automatically.
- Combine with data parallelism by giving the mesh a "data" axis: the
  per-microbatch batch dim shards over it and the loss/grads psum over it
  (GSPMD inserts the allreduce).

Stages must share one activation interface (same shape/dtype in and out) —
the same constraint real TPU pipelines impose (uniform transformer blocks);
heterogeneous embed/head layers run outside the pipelined region.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_pipeline_mesh(n_pipe, n_data=1, n_model=1, devices=None):
    """(data, pipe) mesh — or the 3-axis (data, model, pipe) mesh when
    n_model > 1 (dp x tp x pp in ONE program). pipe is the fastest-varying
    axis so neighbouring stages land on neighbouring devices (ppermute
    hops ride single ICI links on a real torus); model sits between so a
    stage's tensor-parallel group is also ICI-adjacent."""
    devices = devices if devices is not None else jax.devices()
    n = n_data * n_model * n_pipe
    if len(devices) < n:
        raise ValueError(f"need {n} devices, have {len(devices)}")
    if n_model > 1:
        arr = np.asarray(devices[:n]).reshape(n_data, n_model, n_pipe)
        return Mesh(arr, ("data", "model", "pipe"))
    arr = np.asarray(devices[:n]).reshape(n_data, n_pipe)
    return Mesh(arr, ("data", "pipe"))


def stack_stage_params(params_list):
    """Stack per-stage parameter pytrees (identical structure) into one
    leading-axis-S pytree — the sharded storage layout."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *params_list)


def unstack_stage_params(stacked, n_stages):
    return [jax.tree.map(lambda a, i=i: a[i], stacked)
            for i in range(n_stages)]


def _rotation(n):
    return [(i, (i + 1) % n) for i in range(n)]


def gpipe(stage_fn, mesh, axis="pipe", data_axis=None, param_specs=None):
    """Build ``pipelined(stacked_params, xs) -> ys``.

    stage_fn(stage_params, x[B, ...]) -> y[B, ...] (uniform interface).
    xs: [M, B, ...] microbatched input; ys: same shape, equal to applying
    the S stages sequentially to every microbatch.

    param_specs: optional PartitionSpec pytree for the stacked params
    (leading stage axis must map to `axis`) — how tensor parallelism
    composes: shard weight columns over a "model" mesh axis and have
    stage_fn psum over it (e.g. `models.zoo.transformer.make_tp_block_fn`
    + `tp_block_specs`); both the TP collectives and the pipe rotation
    then live in the same shard_map body. Default: P(axis) per leaf
    (pipe-sharded, model-replicated).

    Differentiable end-to-end; donate/jit at the caller.
    """
    S = mesh.shape[axis]
    perm = _rotation(S)

    def spmd(params_blk, xs):
        # local param block [1, ...] -> this stage's params
        p_local = jax.tree.map(lambda a: a[0], params_blk)
        idx = jax.lax.axis_index(axis)
        M = xs.shape[0]
        T = M + S - 1
        state = jnp.zeros(xs.shape[1:], xs.dtype)
        outputs = jnp.zeros_like(xs)

        def tick(carry, t):
            state, outputs = carry
            # stage 0 ingests microbatch t (clamped gather; masked past M)
            x_t = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(t, 0, M - 1), axis=0, keepdims=False)
            state = jnp.where(idx == 0, jnp.where(t < M, x_t, state), state)
            y = stage_fn(p_local, state)
            # last stage emits microbatch t-(S-1)
            o_t = t - (S - 1)
            valid = jnp.logical_and(idx == S - 1, o_t >= 0)
            upd = jax.lax.dynamic_update_index_in_dim(
                outputs, y.astype(outputs.dtype), jnp.clip(o_t, 0, M - 1), 0)
            outputs = jnp.where(valid, upd, outputs)
            # rotate activations one hop over ICI
            state = jax.lax.ppermute(y, axis, perm)
            return (state, outputs), None

        (_, outputs), _ = jax.lax.scan(tick, (state, outputs),
                                       jnp.arange(T))
        # only the last stage holds real outputs; broadcast via masked psum
        mask = (idx == S - 1).astype(outputs.dtype)
        return jax.lax.psum(outputs * mask, axis)

    # specs: stage-stacked params shard over pipe; microbatch batch dim
    # over data (when given); outputs replicated over pipe
    pspec_leaf = P(axis)
    if data_axis is not None:
        xspec = P(None, data_axis)
        ospec = P(None, data_axis)
    else:
        xspec = P()
        ospec = P()

    def pipelined(stacked_params, xs):
        pspec = (param_specs if param_specs is not None
                 else jax.tree.map(lambda _: pspec_leaf, stacked_params))
        fn = jax.shard_map(spmd, mesh=mesh, in_specs=(pspec, xspec),
                           out_specs=ospec, check_vma=False)
        return fn(stacked_params, xs)

    return pipelined


def sgd_momentum_update(params, vel, grads, lr, mu):
    """Shared pytree SGD-with-momentum update (used by PipelineParallel and
    the zoo TransformerLM driver): v <- mu*v + g; p <- p - lr*v."""
    vel = jax.tree.map(lambda v, g: mu * v + g, vel, grads)
    params = jax.tree.map(lambda p, v: p - lr * v, params, vel)
    return params, vel


def microbatch(x, n_micro):
    """[B_total, ...] -> [M, B_total/M, ...]."""
    B = x.shape[0]
    if B % n_micro != 0:
        raise ValueError(f"batch {B} not divisible by {n_micro} microbatches")
    return x.reshape((n_micro, B // n_micro) + x.shape[1:])


class PipelineParallel:
    """Training driver for a pipelined stack of uniform stages.

    The pipelined region covers the uniform middle of the network; the
    heterogeneous ends run replicated (sharded over the data axis via GSPMD
    when the mesh has one):

      pre_fn(aux, x_micro)  -> activations [B, ...]   (e.g. token embedding)
      stage_fn(stage_p, h)  -> h                      (uniform interface)
      loss_fn(aux, out[B*, ...], labels[B*, ...]) -> scalar mean loss

    Updates are SGD/momentum on the sharded stage params — each device
    updates only its own stage block, so stage optimizer state is
    pipeline-sharded for free (ZeRO-like along "pipe").
    """

    def __init__(self, stage_fn, stage_params, mesh, *, loss_fn,
                 aux_params=None, pre_fn=None, n_micro, axis="pipe",
                 data_axis=None, learning_rate=0.1, momentum=0.0,
                 param_specs=None):
        self.mesh = mesh
        self.axis = axis
        self.data_axis = data_axis
        self.n_micro = int(n_micro)
        self.S = mesh.shape[axis]
        if len(stage_params) != self.S:
            raise ValueError(f"{len(stage_params)} stages != mesh "
                             f"{axis}={self.S}")
        from .sharding import put_sharded, replicate
        stacked = stack_stage_params(stage_params)
        # put_sharded/replicate handle multi-host meshes (each process
        # contributes its addressable shards; plain device_put cannot)
        if param_specs is not None:
            self.stacked = jax.tree.map(
                lambda a, sp: put_sharded(a, NamedSharding(mesh, sp),
                                          full_array=True),
                stacked, param_specs)
        else:
            sh = NamedSharding(mesh, P(axis))
            self.stacked = jax.tree.map(
                lambda a: put_sharded(a, sh, full_array=True), stacked)
        self.aux = replicate(aux_params if aux_params is not None else {},
                             mesh)
        self._pipe = gpipe(stage_fn, mesh, axis=axis, data_axis=data_axis,
                           param_specs=param_specs)
        self.pre_fn = pre_fn
        self.loss_fn = loss_fn
        self.lr = float(learning_rate)
        self.mu = float(momentum)
        self._vel = None
        self._jit_step = None
        self._jit_fwd = None

    # -- functional pieces ------------------------------------------------
    def _embed(self, aux, xs):
        if self.pre_fn is None:
            return xs
        return jax.vmap(lambda x: self.pre_fn(aux, x))(xs)

    def _loss(self, stacked, aux, xs, ys):
        out = self._pipe(stacked, self._embed(aux, xs))
        flat_o = out.reshape((-1,) + out.shape[2:])
        flat_y = ys.reshape((-1,) + ys.shape[2:])
        return self.loss_fn(aux, flat_o, flat_y)

    def forward(self, x):
        """Full-batch forward through the pipeline (inference); returns the
        pipeline-output activations (apply your own head for logits)."""
        if self._jit_fwd is None:
            self._jit_fwd = jax.jit(
                lambda stk, aux, xs: self._pipe(stk, self._embed(aux, xs)))
        xs = self._put_micro(microbatch(np.asarray(x), self.n_micro))
        out = self._jit_fwd(self.stacked, self.aux, xs)
        return out.reshape((-1,) + out.shape[2:])

    def _ensure_step(self):
        if self._vel is None:
            self._vel = jax.tree.map(jnp.zeros_like,
                                     (self.stacked, self.aux))
        if self._jit_step is None:
            lr, mu = self.lr, self.mu

            def step(stacked, aux, vel, xs, ys):
                loss, grads = jax.value_and_grad(self._loss,
                                                 argnums=(0, 1))(
                    stacked, aux, xs, ys)
                (stacked, aux), vel = sgd_momentum_update(
                    (stacked, aux), vel, grads, lr, mu)
                return stacked, aux, vel, loss

            self._jit_step = jax.jit(step, donate_argnums=(0, 1, 2))
        return self._jit_step

    def fit_batch(self, x, y):
        """One optimization step over a global batch; returns the loss."""
        step = self._ensure_step()
        xs = self._put_micro(microbatch(np.asarray(x), self.n_micro))
        ys = self._put_micro(microbatch(np.asarray(y), self.n_micro))
        (self.stacked, self.aux, self._vel,
         loss) = step(self.stacked, self.aux, self._vel, xs, ys)
        return float(loss)

    def lower_step(self, x, y):
        """Lower (trace+compile without executing) the pipeline step for a
        global batch — the mesh-cost profiling hook: the caller reads
        collective counts/bytes off the compiled HLO
        (`mesh_cost.hlo_collective_footprint`) to catch sharding
        regressions without hardware."""
        step = self._ensure_step()
        xs = self._put_micro(microbatch(np.asarray(x), self.n_micro))
        ys = self._put_micro(microbatch(np.asarray(y), self.n_micro))
        return step.lower(self.stacked, self.aux, self._vel, xs, ys)

    def _put_micro(self, a):
        """Place a microbatched [M, B_local, ...] numpy array on the mesh.
        On a multi-host mesh each process passes its LOCAL slice of the
        batch dim (the data axis); single-host hands the host array to jit
        directly (one H2D, no round-trip)."""
        from .sharding import is_multiprocess_mesh, put_sharded
        if not is_multiprocess_mesh(self.mesh):
            return a
        spec = [None] * a.ndim
        if self.data_axis is not None:
            spec[1] = self.data_axis
        return put_sharded(a, NamedSharding(self.mesh, P(*spec)),
                           full_array=self.data_axis is None)

    def stage_params(self):
        return unstack_stage_params(self.stacked, self.S)
