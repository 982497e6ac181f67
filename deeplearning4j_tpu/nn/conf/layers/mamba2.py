"""The Mamba-2 mixer as a layer kind of the containers (`mamba2`).

A state-space layer (Dao and Gu, arXiv:2405.21060 sections 5-7) as the
`nemotron_h` family configures it; its fields are that config's keys. With
H = `mamba_num_heads` heads of P = `mamba_head_dim`, G = `n_groups` groups
and N = `ssm_state_size`, x [B, T, n_in] the layer's (already normed) input:

    [z (H P) ; xBC (H P + 2 G N) ; dt (H)] = x W_in      in that order
    xBC_t <- SiLU(b_c + sum_k w_c[:, k] xBC_{t - K + 1 + k})   depthwise,
        causal, K = `conv_kernel` taps, zeros before position 0
    [xs (H P) ; B (G N) ; C (G N)] = xBC
    dt <- softplus(dt + dt_bias);  A = -exp(A_log);  a_t = exp(dt_t A)
    S_t = a_t S_{t-1} + dt_t xs_t (outer) B_t;  y_t = S_t C_t + D xs_t
        (head h reads group h // (H / G); `ops/ssd.py`, in chunks of
        `chunk_size`)
    y <- y * SiLU(z), then RMSNorm over each of the G groups of H P / G
        channels with weight w_n (the gate BEFORE the norm)
    out = y W_out

Inner scopes, which the benchmark's readers sum by: `ssm_proj` (W_in,
W_out), `ssm_conv` (the convolution and its SiLU), `ssd` (time steps,
decays, the scan, the D skip), `ssm_norm` (gate and grouped norm).
Everything the layer traces is inside one of the four. The scan, the
convolution and the gated norm run as Pallas kernels where their shapes
tile the chip (`ops/ssd.py`, `ops/ssm_pointwise.py`); the convolution and
the norm read their channels of W_in's product where they lie.

Initialisers are Mamba-2's, not normal draws: `A_log` the log of a uniform
draw in [1, 16], `dt_bias` the inverse softplus of a log-uniform draw in
[`time_step_min`, `time_step_max`] floored at `time_step_floor`, `D` 1, the
norm's weight 1, `w_c` and `b_c` uniform in +-1 / sqrt(K) (a depthwise
Conv1d's default).
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .base import register_layer
from .decoder import _StatefulSequenceLayer, _normal


@register_layer("mamba2")
@dataclass
class Mamba2Layer(_StatefulSequenceLayer):
    n_in: int = None
    n_out: int = None
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    eps: float = 1e-5
    init_std: float = 0.02

    def _widths(self):
        """(inner width H P, the convolution's channels H P + 2 G N)."""
        inner = self.mamba_num_heads * self.mamba_head_dim
        return inner, inner + 2 * self.n_groups * self.ssm_state_size

    def init_state(self):
        return {k: jnp.zeros((), jnp.float32)
                for k in ("dt_mean", "decay_min", "state_rms", "chunks",
                          "ssd_kernel", "pointwise_kernel")}

    def gauges(self, state):
        return dict(state)

    def remat_keeps(self):
        # the states entering each chunk, which `ssd_scan`'s XLA form names:
        # with them kept its backward does not run the recurrence across
        # chunks a second time (the kernels name none: their rematerialised
        # `ssd_fwd` makes the states beside y either way)
        from ....ops.ssd import KEEP
        return (KEEP,)

    def init_params(self, key, dtype=jnp.float32):
        D, H, K = self.n_in, self.mamba_num_heads, self.conv_kernel
        inner, conv = self._widths()
        k = jax.random.split(key, 6)
        u = lambda kk, shape, lo, hi: jax.random.uniform(
            kk, shape, jnp.float32, lo, hi)
        dt = jnp.maximum(jnp.exp(u(k[3], (H,), math.log(self.time_step_min),
                                   math.log(self.time_step_max))),
                         self.time_step_floor)
        return {"W_in": _normal(k[0], (D, inner + conv + H), self.init_std,
                                dtype),
                "W_out": _normal(k[1], (inner, D), self.init_std, dtype),
                "w_c": u(k[2], (conv, K), -1 / math.sqrt(K),
                         1 / math.sqrt(K)).astype(dtype),
                "b_c": u(k[5], (conv,), -1 / math.sqrt(K),
                         1 / math.sqrt(K)).astype(dtype),
                "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
                "A_log": jnp.log(u(k[4], (H,), 1.0, 16.0)).astype(dtype),
                "D": jnp.ones((H,), dtype),
                "w_n": jnp.ones((inner,), dtype)}

    def forward_with_state(self, params, x, state, *, train=False, rng=None,
                           mask=None):
        from ....ops import ssm_pointwise as pw
        from ....ops.ssd import inside_kernel, ssd_scan
        B, T, _ = x.shape
        H, P, G, N = (self.mamba_num_heads, self.mamba_head_dim,
                      self.n_groups, self.ssm_state_size)
        inner, conv = self._widths()
        f32 = jnp.float32
        with jax.named_scope("ssm_proj"):
            # [z ; xBC ; dt]: the convolution and the gated norm read their
            # channels of it in place
            zxd = x @ params["W_in"]
            dt = zxd[..., inner + conv:]
        # the convolution and the gated norm keep their (bfloat16) inputs
        # for the backward and nothing of their float32 insides
        with jax.named_scope("ssm_conv"):
            xbc = pw.conv_silu(zxd, params["w_c"], params["b_c"], lo=inner)
        with jax.named_scope("ssd"):
            xs, Bm, Cm = jnp.split(xbc, [inner, inner + G * N], -1)
            xs = xs.reshape(B, T, H, P)
            dt = jax.nn.softplus(dt.astype(f32)
                                 + params["dt_bias"].astype(f32))
            A = -jnp.exp(params["A_log"].astype(f32))
            y, last = ssd_scan(xs, dt, A, Bm.reshape(B, T, G, N),
                               Cm.reshape(B, T, G, N),
                               params["D"].astype(f32), self.chunk_size)
            said = {"dt_mean": jnp.mean(dt),
                    "decay_min": jnp.exp(jnp.min(dt * A)),
                    "state_rms": jnp.sqrt(jnp.mean(last * last)),
                    # a function of T: a constant of the trace
                    "chunks": jnp.float32(T // self.chunk_size),
                    # whether the scan ran as the Pallas kernels: shapes
                    # decide, a constant of the trace
                    "ssd_kernel": jnp.float32(inside_kernel(
                        self.chunk_size, P, N, H // G)),
                    # whether the convolution and the gated norm ran as
                    # their Pallas kernels (the mean of the two rules: 0.5
                    # where one did), a constant of the trace
                    "pointwise_kernel": jnp.float32(
                        (pw.conv_inside_kernel(conv, T, self.conv_kernel,
                                               inner)
                         + pw.norm_inside_kernel(inner, G, T)) / 2)}
        with jax.named_scope("ssm_norm"):
            y = pw.gated_norm(y.reshape(B, T, inner), zxd, params["w_n"], G,
                              self.eps)
        with jax.named_scope("ssm_proj"):
            out = y @ params["W_out"]
        return out, jax.lax.stop_gradient(said)
