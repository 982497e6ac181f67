"""Normalization layers: BatchNormalization, LocalResponseNormalization.

TPU-native equivalents of reference nn/conf/layers/BatchNormalization.java +
impl nn/layers/normalization/BatchNormalization.java (452 LoC) and
LocalResponseNormalization.java, plus the cuDNN helpers
(CudnnBatchNormalizationHelper.java:48, CudnnLocalResponseNormalizationHelper.java:46).

BatchNorm carries non-trainable running statistics; in this functional design
those live in the layer `state` pytree threaded through the jitted train step
(forward_with_state) — the TPU-idiomatic replacement for the reference's
mutable global-mean/var INDArrays. Training uses batch stats + EMA update with
`decay`; inference uses running stats (reference useBatchMean/global stats
semantics).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp

import jax

from ... import activations
from ..input_type import ConvolutionalInputType, FeedForwardInputType, InputType
from .base import LayerConf, layer_scope, register_layer
from .convolution import ConvolutionLayer


def _bn_train_stats(x, gamma, beta, eps, axes, fast_var):
    """Train-mode batch norm of `x` over `axes`: (y, mean, var, rstd), the
    statistics accumulated in >= f32. One-pass E[x]/E[x^2] variance when
    `fast_var` (both reductions over the SAME read of x)."""
    acc = jnp.promote_types(x.dtype, jnp.float32)
    xf = x.astype(acc)
    mean = jnp.mean(xf, axis=axes)
    if fast_var:
        var = jnp.maximum(jnp.mean(xf * xf, axis=axes) - mean * mean, 0.0)
    else:
        var = jnp.var(xf, axis=axes)
    rstd = jax.lax.rsqrt(var + eps)
    xn = (xf - mean) * rstd * gamma.astype(acc) + beta.astype(acc)
    return xn.astype(x.dtype), mean, var, rstd


def _bn_train_fused(eps, axes, fast_var):
    """Batch-norm train-mode core with a hand-fused VJP.

    Forward: one-pass E[x]/E[x^2] statistics (PERF.md r2 optimization).
    Backward: the closed-form BN gradient
        dx = gamma*rstd*(dy - mean(dy) - xhat*mean(dy*xhat))
    computed as TWO twin reductions (sum dy, sum dy*(x-mean)) over the SAME
    read of (x, dy) followed by one elementwise pass — instead of XLA's
    autodiff chain through mean/var, which issues its reduction passes
    separately (the same missed-fusion the forward one-pass stats fixed).
    Reductions accumulate in f32 under bf16 compute.

    Returns (y, mean, var); the mean/var outputs feed the EMA running-stats
    update, which takes no gradient (cotangents ignored — matching the
    autodiff behavior where new_state is an aux output).
    reference seam: CudnnBatchNormalizationHelper.java:48 (the layer the
    reference hands to fused native kernels).
    """
    @jax.custom_vjp
    def f(x, gamma, beta):
        return _bn_train_stats(x, gamma, beta, eps, axes, fast_var)[:3]

    def fwd(x, gamma, beta):
        y, mean, var, rstd = _bn_train_stats(x, gamma, beta, eps, axes,
                                             fast_var)
        return (y, mean, var), (x, gamma, mean, rstd)

    def bwd(res, cts):
        dy, _dmean, _dvar = cts      # EMA path carries no gradient
        x, gamma, mean, rstd = res
        acc = jnp.promote_types(x.dtype, jnp.float32)
        dyf = dy.astype(acc)
        xc = x.astype(acc) - mean
        n = 1.0
        for a in axes:
            n *= x.shape[a]
        s1 = jnp.sum(dyf, axis=axes)
        s2 = jnp.sum(dyf * xc, axis=axes)
        g = gamma.astype(acc)
        dx = (g * rstd) * (dyf - s1 / n - xc * (rstd * rstd) * (s2 / n))
        return (dx.astype(x.dtype), (s2 * rstd).astype(gamma.dtype),
                s1.astype(gamma.dtype))

    f.defvjp(fwd, bwd)
    return f


def _conv1x1_bn_train_fused(eps, fast_var, stride):
    """The PAIR (1x1 convolution without bias -> train-mode batch norm) as
    one custom VJP whose backward never reads the convolution's output.

    `_bn_train_fused`'s backward needs x at every position, and x is the
    wide tensor of an expanding 1x1 convolution: XLA re-reads it in the
    batch-norm sums and in the prologue of both of the convolution's
    gradient fusions, on a step that is HBM-bound (PERF.md section 5). But
    x = a.W is a linear function of the narrow input `a`, so with
        s1 = sum dy [Cout]            sa = sum a [Cin]
        A1 = a^T.dy [Cin,Cout]        G  = a^T.a [Cin,Cin]
        s2 = sum_j A1[j,:]*W[j,:] - mean*s1      (= sum dy*(x - mean))
        k  = gamma*rstd ;  c2 = rstd^2*s2/n
    the same gradients are
        dW = k*(A1 - sa(x)s1/n - (G.W - sa(x)mean)*c2)
        da = dy.(W diag(k))^T - a.M + (-k*s1/n + mean*k*c2).W^T ,
             M = W diag(k*c2) W^T [Cin,Cin]
        dgamma = s2*rstd ;  dbeta = s1
    and the residuals are (a, W, gamma, mean, rstd): x is dead after the
    forward. G costs n*Cin^2 multiply-adds, under the convolution's own
    n*Cin*Cout while Cout > Cin, which is where `convbn_pairs` engages this.
    Operands stay in the compute dtype;
    the sums, A1, G and all [C,C] algebra are >= f32.

    f(a, w, gamma, beta) -> (y, mean, var) with a [N,H,W,Cin] and w
    [1,1,Cin,Cout]; the forward is the strided convolution followed by
    `_bn_train_stats`, the unpaired layers' own operations. In the backward
    a stride subsamples `a` first (XLA reads the rows it needs, not the
    tensor). mean/var feed the running statistics and take no gradient, as
    in `_bn_train_fused`.
    """
    sh, sw = stride
    axes = (0, 1, 2)

    def _forward(a, w, gamma, beta):
        x = jax.lax.conv_general_dilated(
            a, w, window_strides=(sh, sw), padding="VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return _bn_train_stats(x, gamma, beta, eps, axes, fast_var)

    @jax.custom_vjp
    def f(a, w, gamma, beta):
        return _forward(a, w, gamma, beta)[:3]

    def fwd(a, w, gamma, beta):
        y, mean, var, rstd = _forward(a, w, gamma, beta)
        return (y, mean, var), (a, w, gamma, mean, rstd)

    def bwd(res, cts):
        dy, _dmean, _dvar = cts      # EMA path carries no gradient
        a, w, gamma, mean, rstd = res
        cdt = a.dtype
        acc = jnp.promote_types(cdt, jnp.float32)
        hi = jax.lax.Precision.HIGHEST
        a_s, unstride = jax.vjp(
            lambda t: jax.lax.slice(t, (0, 0, 0, 0), t.shape,
                                    (1, sh, sw, 1)), a)
        n = a_s.shape[0] * a_s.shape[1] * a_s.shape[2]
        wm = w[0, 0].astype(acc)                          # [Cin, Cout]
        s1 = jnp.sum(dy.astype(acc), axis=axes)
        sa = jnp.sum(a_s.astype(acc), axis=axes)
        a1 = jnp.einsum("nhwi,nhwo->io", a_s, dy,
                        preferred_element_type=acc)
        g = jnp.einsum("nhwi,nhwj->ij", a_s, a_s,
                       preferred_element_type=acc)
        s2 = jnp.sum(a1 * wm, axis=0) - mean * s1
        k = gamma.astype(acc) * rstd
        c2 = rstd * rstd * (s2 / n)
        gw = jnp.dot(g, wm, precision=hi)
        dw = k * (a1 - jnp.outer(sa, s1 / n)
                  - (gw - jnp.outer(sa, mean)) * c2)
        m = jnp.dot(wm * (k * c2), wm.T, precision=hi)    # [Cin, Cin]
        const = jnp.dot(mean * k * c2 - k * s1 / n, wm.T, precision=hi)
        # da in two products: the narrow one first, stored in the compute
        # dtype as every activation and cotangent of the step is, so the
        # wide one takes it as its epilogue's operand (in f32 XLA writes
        # the wide product out and reads it back: 2 more passes over a)
        t = jnp.einsum("nhwi,ij->nhwj", a_s, (-m).astype(cdt))
        da_s = (jnp.einsum("nhwo,io->nhwi", dy, (wm * k).astype(cdt),
                           preferred_element_type=acc)
                + t.astype(acc) + const).astype(cdt)
        da, = unstride(da_s)
        return (da, dw[None, None].astype(w.dtype),
                (s2 * rstd).astype(gamma.dtype), s1.astype(gamma.dtype))

    f.defvjp(fwd, bwd)
    return f


def convbn_pairs(conf):
    """{batch-norm vertex: convolution vertex} for every pair of a graph
    configuration that the training forward computes as one function
    (`_conv1x1_bn_train_fused`: a backward that never reads the
    convolution's output; the step is HBM-bound). The rule: an EXPANDING
    1x1 convolution (n_out > n_in: the output is the wide tensor, and the
    backward's extra Cin x Cin product stays under the convolution's own
    work) with no bias, no padding, identity activation and no input
    dropout, whose only consumer is a batch norm with learned scale and
    shift, the fused backward and no preprocessor. Every other convolution
    and batch norm runs its own layer's code."""
    verts = conf.vertices
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
                and cspec.name not in conf.network_outputs
                and spec.preprocessor is None
                and bn.fused_backward and not bn.lock_gamma_beta):
            plan[name] = cspec.name
    return plan


def convbn_pair_forward(spec, p, state, cspec, cp, a, *, cast):
    """The training forward of the batch norm `spec` of a planned pair, as
    one function of its convolution's (`cspec`, parameters `cp`) INPUT `a`,
    under the convolution's scope (its backward is convolution work); the
    running statistics under the batch norm's. `cast` is the container's
    cast of stored parameters to the compute dtype. Returns (y, state')."""
    with layer_scope(cspec.conf, cspec.name):
        if cspec.preprocessor is not None:
            a = cspec.preprocessor.pre_process(a)
        p = cast(p)
        y, mean, var = _conv1x1_bn_train_fused(
            spec.conf.eps, spec.conf.use_fast_variance, cspec.conf.stride)(
                a, cast(cp)["W"], p["gamma"], p["beta"])
    with layer_scope(spec.conf, spec.name):
        return y, spec.conf.running_stats(state, mean, var)


@register_layer("batchnorm")
@dataclass
class BatchNormalization(LayerConf):
    decay: float = 0.9
    eps: float = 1e-5
    is_mini_batch: bool = True
    lock_gamma_beta: bool = False
    gamma_init: float = 1.0
    beta_init: float = 0.0
    n_out: int = None  # feature count, inferred
    # one-pass E[x^2]-E[x]^2 statistics (industry-standard TPU BN; saves a
    # full HBM read of the input per step — see PERF.md). Trades off f32
    # cancellation when |mean| >> std: E[x^2] and mean^2 become nearly equal
    # large numbers, the subtraction loses all significant bits, the clamp
    # floors var at 0 and the normalizer becomes rsqrt(eps) — a large-gain
    # blowup rather than a graceful degradation. Set False for the two-pass
    # jnp.var form (the reference's two-pass variance) when activations can
    # have |mean| orders of magnitude above their spread.
    use_fast_variance: bool = True
    # hand-fused closed-form backward (_bn_train_fused) instead of XLA
    # autodiff through the statistics chain; False restores pure autodiff
    fused_backward: bool = True

    def set_n_in(self, input_type, override=True):
        if self.n_out is None or override:
            if isinstance(input_type, ConvolutionalInputType):
                self.n_out = input_type.channels
            elif isinstance(input_type, FeedForwardInputType):
                self.n_out = input_type.size
            else:
                from ..input_type import RecurrentInputType
                if isinstance(input_type, RecurrentInputType):
                    self.n_out = input_type.size

    def get_output_type(self, input_type):
        return input_type

    def init_params(self, key, dtype=jnp.float32):
        if self.lock_gamma_beta:
            return {}
        return {"gamma": jnp.full((self.n_out,), float(self.gamma_init), dtype),
                "beta": jnp.full((self.n_out,), float(self.beta_init), dtype)}

    def has_state(self):
        return True

    def init_state(self):
        return {"mean": jnp.zeros((self.n_out,), jnp.float32),
                "var": jnp.ones((self.n_out,), jnp.float32)}

    def running_stats(self, state, mean, var):
        """The EMA update of the running statistics from one batch's."""
        return {"mean": self.decay * state["mean"] + (1 - self.decay) * mean,
                "var": self.decay * state["var"] + (1 - self.decay) * var}

    def forward_with_state(self, params, x, state, *, train=False, rng=None,
                           mask=None):
        axes = tuple(range(x.ndim - 1))  # all but channel/feature axis
        if train and self.fused_backward and params \
                and not self.lock_gamma_beta:
            y, mean, var = _bn_train_fused(
                self.eps, axes, self.use_fast_variance)(
                    x, params["gamma"], params["beta"])
            return y, self.running_stats(state, mean, var)
        if train:
            # One-pass statistics: E[x] and E[x^2] reduce over the SAME read
            # of x (XLA fuses the two reductions into a single pass), vs
            # jnp.var's mean-then-squared-deviations which re-reads x after
            # the mean is known. The step is HBM-bound (see PERF.md) — one
            # fewer full pass over every conv output is a direct win.
            # Accumulate in >= f32 (stability under bf16 compute).
            xf = x.astype(jnp.promote_types(x.dtype, jnp.float32))
            mean = jnp.mean(xf, axis=axes)
            if self.use_fast_variance:
                var = jnp.maximum(
                    jnp.mean(xf * xf, axis=axes) - mean * mean, 0.0)
            else:
                var = jnp.var(xf, axis=axes)
            new_state = self.running_stats(state, mean, var)
        else:
            mean, var = state["mean"], state["var"]
            new_state = state
        mean = mean.astype(x.dtype)
        var = var.astype(x.dtype)
        xn = (x - mean) / jnp.sqrt(var + self.eps)
        if not self.lock_gamma_beta and params:
            xn = xn * params["gamma"] + params["beta"]
        # No activation: the reference BatchNormalization.activate
        # (nn/layers/normalization/BatchNormalization.java:227) returns
        # preOutput untransformed, regardless of the global default.
        return xn, new_state

    def forward(self, params, x, *, train=False, rng=None, mask=None, state=None):
        out, _ = self.forward_with_state(params, x, state or self.init_state(),
                                         train=train, rng=rng, mask=mask)
        return out


@register_layer("lrn")
@dataclass
class LocalResponseNormalization(LayerConf):
    """Across-channel LRN (AlexNet-style).
    reference: nn/layers/normalization/LocalResponseNormalization.java —
    out = x / (k + alpha * sum_{j in window} x_j^2)^beta over channel axis."""
    k: float = 2.0
    n: float = 5.0
    alpha: float = 1e-4
    beta: float = 0.75

    def get_output_type(self, input_type):
        return input_type

    def forward(self, params, x, *, train=False, rng=None, mask=None, state=None):
        half = int(self.n) // 2
        sq = x * x
        c = x.shape[-1]
        # pad channel axis, windowed sum via static slicing (unrolled — n is
        # tiny and static, XLA fuses this into one kernel)
        pad_width = [(0, 0)] * (x.ndim - 1) + [(half, half)]
        padded = jnp.pad(sq, pad_width)
        acc = sum(padded[..., i:i + c] for i in range(int(self.n)))
        denom = (self.k + self.alpha * acc) ** self.beta
        return x / denom
