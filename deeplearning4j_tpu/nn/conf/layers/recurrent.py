"""Recurrent layers: GravesLSTM, GravesBidirectionalLSTM, simple RNN.

TPU-native equivalents of reference nn/conf/layers/{GravesLSTM,
GravesBidirectionalLSTM}.java with the math of
nn/layers/recurrent/LSTMHelpers.java:58 (activateHelper; per-timestep gemm loop
:157-171; BPTT loop :311-459).

TPU-first redesign: the reference's Java per-timestep loop (one gemm per step,
one op dispatch each) becomes a single `lax.scan` inside the jitted step —
XLA compiles the whole sequence into one fused while-loop with the input
projection x @ W hoisted OUT of the scan as one big [B*T, 4H] matmul on the
MXU (the scan body then only does the [B,H]x[H,4H] recurrent gemm). This is
the design SURVEY.md §7.3.4 calls for. The hand-written BPTT loop is replaced
by autodiff through the scan.

Semantics match Graves-formulation LSTM with peepholes (as the reference):
  a = actFn(x W_a + h_{t-1} U_a + b_a)                (block input)
  i = gateFn(x W_i + h U_i + p_i * c_{t-1} + b_i)
  f = gateFn(x W_f + h U_f + p_f * c_{t-1} + b_f)
  c_t = f * c_{t-1} + i * a
  o = gateFn(x W_o + h U_o + p_o * c_t + b_o)
  h_t = o * actFn(c_t)
Param layout: W [nIn,4H] (gate order a,i,f,o), RW [H,4H], peepholes pi,pf,po
[H], b [4H] with forget-gate bias initialized to forgetGateBiasInit
(reference GravesLSTM.Builder.forgetGateBiasInit, default 1.0).

Masking (per-example variable length): masked timesteps emit zero output and
carry state through unchanged (reference mask semantics in LSTMHelpers +
GradientCheckTestsMasking).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from ... import activations, weights
from ..input_type import InputType, RecurrentInputType
from .base import LayerConf, apply_input_dropout, register_layer


class BaseRecurrentLayer(LayerConf):
    """Marker base for layers that carry sequence state (TBPTT / rnnTimeStep).

    reference: nn/api/layers/RecurrentLayer.java (rnnTimeStep,
    rnnActivateUsingStoredState, tbpttStateView).
    """

    def init_carry(self, batch_size, dtype=jnp.float32):
        raise NotImplementedError

    def forward_with_carry(self, params, x, carry, *, train=False, rng=None,
                           mask=None):
        raise NotImplementedError

    def is_recurrent(self):
        return True


def _split_gates(z):
    return jnp.split(z, 4, axis=-1)   # a, i, f, o


@register_layer("graveslstm")
@dataclass
class GravesLSTM(BaseRecurrentLayer):
    n_in: int = None
    n_out: int = None
    forget_gate_bias_init: float = 1.0
    gate_activation: str = "sigmoid"
    # lax.scan unroll factor: >1 lets XLA fuse several timesteps into one
    # loop body (fewer loop-carried DMA round trips on TPU, bigger fused
    # elementwise chains) at compile-time/code-size cost. Same math,
    # different fusion — equivalent to float-reassociation tolerance.
    # reference seam: LSTMHelpers.java:157-171 (the per-timestep loop
    # this scan replaces).
    scan_unroll: int = 1

    def set_n_in(self, input_type, override=True):
        if self.n_in is None or override:
            if isinstance(input_type, RecurrentInputType):
                self.n_in = input_type.size
            else:
                from .feedforward import _ff_size
                self.n_in = _ff_size(input_type)

    def get_output_type(self, input_type):
        return InputType.recurrent(self.n_out)

    def init_params(self, key, dtype=jnp.float32):
        H = self.n_out
        k1, k2, k3 = jax.random.split(key, 3)
        W = weights.init(k1, (self.n_in, 4 * H), self.n_in, H,
                         self.weight_init, self.dist, dtype)
        RW = weights.init(k2, (H, 4 * H), H, H, self.weight_init, self.dist,
                          dtype)
        peep = 0.0 * jax.random.normal(k3, (3 * H,), dtype)
        b = jnp.zeros((4 * H,), dtype)
        # forget gate bias (gate slot 2 in a,i,f,o)
        b = b.at[2 * H:3 * H].set(float(self.forget_gate_bias_init))
        return {"W": W, "RW": RW, "b": b, "peep": peep}

    def init_carry(self, batch_size, dtype=jnp.float32):
        H = self.n_out
        return {"h": jnp.zeros((batch_size, H), dtype),
                "c": jnp.zeros((batch_size, H), dtype)}

    def _cell(self, params, xz_t, h, c, act, gate):
        """One timestep. xz_t: precomputed x_t @ W + b, shape [B, 4H]."""
        H = self.n_out
        peep = params["peep"]
        pi, pf, po = peep[:H], peep[H:2 * H], peep[2 * H:]
        z = xz_t + h @ params["RW"]
        za, zi, zf, zo = _split_gates(z)
        a = act(za)
        i = gate(zi + pi * c)
        f = gate(zf + pf * c)
        c_new = f * c + i * a
        o = gate(zo + po * c_new)
        h_new = o * act(c_new)
        return h_new, c_new

    def forward_with_carry(self, params, x, carry, *, train=False, rng=None,
                           mask=None):
        """x: [B, T, nIn] -> ([B, T, H], final_carry)."""
        act = activations.get(self.activation or "tanh")
        gate = activations.get(self.gate_activation)
        x = apply_input_dropout(self, x, train, rng)
        B, T, _ = x.shape
        # hoist input projection out of the scan: one big MXU matmul
        xz = x @ params["W"] + params["b"]          # [B, T, 4H]
        xz_t = jnp.swapaxes(xz, 0, 1)               # [T, B, 4H] scan-major
        mask_t = (jnp.swapaxes(mask, 0, 1)[..., None].astype(x.dtype)
                  if mask is not None else None)

        h0 = carry["h"].astype(x.dtype)
        c0 = carry["c"].astype(x.dtype)

        def step(hc, inputs):
            h, c = hc
            if mask_t is None:
                xz_step = inputs
                h_new, c_new = self._cell(params, xz_step, h, c, act, gate)
                return (h_new, c_new), h_new
            xz_step, m = inputs
            h_new, c_new = self._cell(params, xz_step, h, c, act, gate)
            h_keep = m * h_new + (1.0 - m) * h
            c_keep = m * c_new + (1.0 - m) * c
            return (h_keep, c_keep), m * h_new

        xs = xz_t if mask_t is None else (xz_t, mask_t)
        (hT, cT), out_t = lax.scan(step, (h0, c0), xs,
                                   unroll=max(1, int(self.scan_unroll or 1)))
        out = jnp.swapaxes(out_t, 0, 1)             # [B, T, H]
        return out, {"h": hT, "c": cT}

    def forward(self, params, x, *, train=False, rng=None, mask=None,
                state=None):
        carry = self.init_carry(x.shape[0], x.dtype)
        out, _ = self.forward_with_carry(params, x, carry, train=train,
                                         rng=rng, mask=mask)
        return out


@register_layer("gravesbidirectionallstm")
@dataclass
class GravesBidirectionalLSTM(BaseRecurrentLayer):
    """Two GravesLSTM passes (forward + time-reversed), outputs summed
    (reference: nn/layers/recurrent/GravesBidirectionalLSTM.java — forward and
    backward activations are added)."""
    n_in: int = None
    n_out: int = None
    forget_gate_bias_init: float = 1.0
    gate_activation: str = "sigmoid"
    scan_unroll: int = 1                 # see GravesLSTM.scan_unroll

    def _sub(self):
        l = GravesLSTM(n_in=self.n_in, n_out=self.n_out,
                       forget_gate_bias_init=self.forget_gate_bias_init,
                       gate_activation=self.gate_activation,
                       scan_unroll=self.scan_unroll)
        l.activation = self.activation
        l.weight_init = self.weight_init
        l.dist = self.dist
        l.dropout = None  # applied once here, not per direction
        return l

    def set_n_in(self, input_type, override=True):
        if self.n_in is None or override:
            if isinstance(input_type, RecurrentInputType):
                self.n_in = input_type.size

    def get_output_type(self, input_type):
        return InputType.recurrent(self.n_out)

    def init_params(self, key, dtype=jnp.float32):
        kf, kb = jax.random.split(key)
        sub = self._sub()
        pf = sub.init_params(kf, dtype)
        pb = sub.init_params(kb, dtype)
        return {"W": pf["W"], "RW": pf["RW"], "b": pf["b"], "peep": pf["peep"],
                "W_bw": pb["W"], "RW_bw": pb["RW"], "b_bw": pb["b"],
                "peep_bw": pb["peep"]}

    def init_carry(self, batch_size, dtype=jnp.float32):
        H = self.n_out
        z = jnp.zeros((batch_size, H), dtype)
        return {"h": z, "c": z, "h_bw": z, "c_bw": z}

    def forward_with_carry(self, params, x, carry, *, train=False, rng=None,
                           mask=None):
        sub = self._sub()
        x = apply_input_dropout(self, x, train, rng)
        pf = {"W": params["W"], "RW": params["RW"], "b": params["b"],
              "peep": params["peep"]}
        pb = {"W": params["W_bw"], "RW": params["RW_bw"], "b": params["b_bw"],
              "peep": params["peep_bw"]}
        out_f, cf = sub.forward_with_carry(
            pf, x, {"h": carry["h"], "c": carry["c"]}, train=False, rng=rng,
            mask=mask)
        x_rev = jnp.flip(x, axis=1)
        mask_rev = jnp.flip(mask, axis=1) if mask is not None else None
        out_b, cb = sub.forward_with_carry(
            pb, x_rev, {"h": carry["h_bw"], "c": carry["c_bw"]}, train=False,
            rng=rng, mask=mask_rev)
        out = out_f + jnp.flip(out_b, axis=1)
        return out, {"h": cf["h"], "c": cf["c"], "h_bw": cb["h"],
                     "c_bw": cb["c"]}

    def forward(self, params, x, *, train=False, rng=None, mask=None,
                state=None):
        out, _ = self.forward_with_carry(
            params, x, self.init_carry(x.shape[0], x.dtype), train=train,
            rng=rng, mask=mask)
        return out


@register_layer("simplernn")
@dataclass
class SimpleRnn(BaseRecurrentLayer):
    """Vanilla RNN: h_t = act(x W + h_{t-1} RW + b). (The reference's base
    recurrent machinery without LSTM gating; useful for tests and parity with
    BaseRecurrentLayer semantics.)"""
    n_in: int = None
    n_out: int = None

    def set_n_in(self, input_type, override=True):
        if self.n_in is None or override:
            if isinstance(input_type, RecurrentInputType):
                self.n_in = input_type.size

    def get_output_type(self, input_type):
        return InputType.recurrent(self.n_out)

    def init_params(self, key, dtype=jnp.float32):
        k1, k2 = jax.random.split(key)
        W = weights.init(k1, (self.n_in, self.n_out), self.n_in, self.n_out,
                         self.weight_init, self.dist, dtype)
        RW = weights.init(k2, (self.n_out, self.n_out), self.n_out, self.n_out,
                          self.weight_init, self.dist, dtype)
        return {"W": W, "RW": RW, "b": jnp.zeros((self.n_out,), dtype)}

    def init_carry(self, batch_size, dtype=jnp.float32):
        return {"h": jnp.zeros((batch_size, self.n_out), dtype)}

    def forward_with_carry(self, params, x, carry, *, train=False, rng=None,
                           mask=None):
        act = activations.get(self.activation or "tanh")
        x = apply_input_dropout(self, x, train, rng)
        xz = x @ params["W"] + params["b"]
        xz_t = jnp.swapaxes(xz, 0, 1)
        mask_t = (jnp.swapaxes(mask, 0, 1)[..., None].astype(x.dtype)
                  if mask is not None else None)
        h0 = carry["h"].astype(x.dtype)

        def step(h, inputs):
            if mask_t is None:
                h_new = act(inputs + h @ params["RW"])
                return h_new, h_new
            xz_step, m = inputs
            h_new = act(xz_step + h @ params["RW"])
            h_keep = m * h_new + (1.0 - m) * h
            return h_keep, m * h_new

        xs = xz_t if mask_t is None else (xz_t, mask_t)
        hT, out_t = lax.scan(step, h0, xs)
        return jnp.swapaxes(out_t, 0, 1), {"h": hT}

    def forward(self, params, x, *, train=False, rng=None, mask=None,
                state=None):
        out, _ = self.forward_with_carry(
            params, x, self.init_carry(x.shape[0], x.dtype), train=train,
            rng=rng, mask=mask)
        return out
