"""Layers of a mixture-of-experts decoder, for the containers.

Beyond-reference capability (the reference predates all of it). Nine layer
kinds here (a tenth, the `mamba2` state-space mixer, in `mamba2.py`), each traced under its own `<kind>.<vertex>` scope by the container:

- `tokenembedding`: ids [B, T] -> rows of a table; a second input
  (`extras[0]`, [B, P, D]: an image's embeddings) replaces the rows at the
  first P positions.
- `rmsnorm`: x * rsqrt(mean(x^2) + eps) * g, computed in float32.
- `sparseattention`: grouped-query attention with per-head RMSNorm of q and
  k and three-axis rotary positions (second input, [B, T, 3]), under a
  LEARNED sparse selection: a small indexer scores every causal pair, the
  `topk` best keys of a query are kept, and the main attention reads only
  those. The selection is discrete, so the indexer learns from a loss of its
  own (KL from the main attention's probabilities, summed over heads, to the
  indexer's softmax over the selected keys), which the layer returns through
  its state's `layer_loss` entry; the container adds it to the score.
- `attention`: dense grouped-query attention, plain causal or inside a
  `window` (key j visible to query i iff 0 <= i - j < window), over the
  same kernels as `sparseattention` with the mask made from positions
  inside them (inner scope `attend_full` or `attend_window`); a rotary turn
  of the first `rotary_dim` slots of a head (inner scope `rotary`), plain
  or YaRN-scaled; a per-head sigmoid gate on the attention's output, read
  from the layer's input (inner scope `gate`).
- `latentattention`: multi-head latent attention, expanded (training): the
  queries through a low-rank chain with an RMSNorm on the latent, keys and
  values through another whose latent is what a server would cache (inner
  scope `latent`, Wo with them); a head scores over `qk_nope_head_dim` slots
  of its own and `qk_rope_head_dim` slots against ONE rotary key that all
  heads share, turned as interleaved pairs (inner scope `rotary`), and sums
  values `v_head_dim` wide (inner scope `attend_latent`:
  `ops/sparse_attention.py shared_key_attention`).
- `gatedmlp`: (SiLU(x Wg) * (x Wu)) Wd, the dense layer of a decoder.
- `moe`: router over ALL experts, the top k a token, and this chip's share
  of the experts (`parallel/moe.py` `held_experts_ffn`: nothing dropped);
  with `shared_width`, a shared expert that every token passes, added
  unscaled (inner scope `shared`); `routed_scale` multiplies the routed
  weights. With `activation` "relu2" an expert is relu(x Wu)^2 Wd, two
  matrices and no gate. `scoring` "sigmoid" scores each expert alone, and with
  `bias_update_rate` the top k are chosen by score PLUS a bias that is the
  layer's state, not a parameter: no gradient reaches it, each training
  forward moves it towards an even load.
- `projection`: x W, no bias (a multi-token prediction module's `W_eh`).
- `lmhead`: logits over a vocabulary (slice) and the masked mean
  cross-entropy over a sequence with integer labels, in token chunks so that
  no [tokens, vocabulary] array outlives a chunk; with `loss_weight` the
  container weights its loss and reports it in the layer's state.

Layout [batch, time, features], as the recurrent and attention layers.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..input_type import InputType
from .base import LayerConf, register_layer

NEG = -1e30          # a masked score: exp() of it is 0, and it is finite


def _normal(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def rms_norm(x, g, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


class _SequenceLayer(LayerConf):
    """[B, T, n_in] -> [B, T, n_out]; sizes are given, not inferred."""

    def get_output_type(self, input_type):
        return InputType.recurrent(self.n_out or self.n_in,
                                   getattr(input_type, "time_series_length",
                                           -1))


class _StatefulSequenceLayer(_SequenceLayer):
    """A layer whose state is what its last forward SAID (a loss of its
    own, counters), never what the next one reads."""

    def has_state(self):
        return True

    def forward(self, params, x, *, state=None, **kw):
        return self.forward_with_state(params, x, state, **kw)[0]


@register_layer("tokenembedding")
@dataclass
class TokenEmbeddingLayer(_SequenceLayer):
    n_in: int = None            # rows of the table (the vocabulary slice)
    n_out: int = None
    init_std: float = 0.02

    def init_params(self, key, dtype=jnp.float32):
        return {"W": _normal(key, (self.n_in, self.n_out), self.init_std,
                             dtype)}

    def forward(self, params, x, *, train=False, rng=None, mask=None,
                state=None, extras=()):
        emb = jnp.take(params["W"], x.astype(jnp.int32), axis=0)
        if extras:
            emb = jax.lax.dynamic_update_slice_in_dim(
                emb, extras[0].astype(emb.dtype), 0, axis=1)
        return emb


@register_layer("rmsnorm")
@dataclass
class RMSNormLayer(_SequenceLayer):
    n_in: int = None
    n_out: int = None
    eps: float = 1e-6

    def init_params(self, key, dtype=jnp.float32):
        return {"g": jnp.ones((self.n_in,), dtype)}

    def forward(self, params, x, *, train=False, rng=None, mask=None,
                state=None):
        return rms_norm(x, params["g"], self.eps)


# ---------------------------------------------------------------------------
# sparse attention
# ---------------------------------------------------------------------------
def mrope_angles(positions, head_dim, theta, sections):
    """cos, sin [B, T, head_dim/2] (float32) of the three-axis rotary turn:
    frequency slot i turns by the position on the axis its section names
    (`sections` slots for t, then h, then w)."""
    half = head_dim // 2
    inv = theta ** (-(2.0 * jnp.arange(half, dtype=jnp.float32)) / head_dim)
    axis = jnp.repeat(jnp.arange(3), jnp.asarray(sections),
                      total_repeat_length=half)
    ang = jnp.take(positions.astype(jnp.float32), axis, axis=-1) * inv
    return jnp.cos(ang), jnp.sin(ang)


def rotate_half(x, cos, sin):
    """x [B, T, H, Dh]; pairs (i, i + Dh/2)."""
    xf = x.astype(jnp.float32)
    a, b = jnp.split(xf, 2, axis=-1)
    c, s = cos[:, :, None, :], sin[:, :, None, :]
    return jnp.concatenate([a * c - b * s, b * c + a * s], -1).astype(x.dtype)


@jax.custom_vjp
def index_scores(qi, ki, w):
    """I[c, s] = sum_j w[c, j] ReLU(qi[c, j] . ki[s]) in float32.
    qi [C, HI, DI], ki [S, DI], w [C, HI] -> [C, S]. XLA fuses this forward
    into one pass that keeps the HI heads' dots on chip; its transpose
    wrote them out, f32[S, C, HI] and their signs beside them, so the
    backward is a kernel that makes them again a block of keys at a time
    (`ops/sparse_attention.py index_scores_bwd`) and keeps qi, ki, w alone."""
    dots = jnp.einsum("chd,sd->hcs", qi, ki,
                      preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(dots)
                   * w.astype(jnp.float32).T[:, :, None], 0)


def _index_scores_bwd(res, g):
    # traced under the call site's scopes (`_row` opens `indexer` around the
    # call, not inside it), so the metrics that read the scope count it
    from ....ops.sparse_attention import index_scores_bwd
    return index_scores_bwd(*res, g)


index_scores.defvjp(lambda qi, ki, w: (index_scores(qi, ki, w), (qi, ki, w)),
                    _index_scores_bwd)


def select_keys(scores, q_pos, topk):
    """The selection of each query row of `scores` [C, S] (keys 0..S-1,
    queries at positions `q_pos` [C]): the causal keys whose score is at
    least the `topk`-th largest (ties with it are kept), all causal keys
    where there are no more than `topk`. One set for every head. Returns a
    bool mask [C, S]."""
    from ....ops.sparse_attention import at_least_kth
    causal = jnp.arange(scores.shape[1])[None, :] <= q_pos[:, None]
    if scores.shape[1] <= topk:
        return causal
    masked = jnp.where(causal, jax.lax.stop_gradient(scores), -jnp.inf)
    return at_least_kth(masked, topk) != 0


@register_layer("sparseattention")
@dataclass
class SparseAttentionLayer(_StatefulSequenceLayer):
    n_in: int = None
    n_out: int = None
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    eps: float = 1e-6
    rope_theta: float = 1e7
    mrope_section: tuple = (16, 24, 24)
    indexer_heads: int = 16
    indexer_head_dim: int = 64
    topk: int = 2048
    q_chunk_size: int = 512
    init_std: float = 0.02

    has_layer_loss = True           # state["layer_loss"] joins the score

    def init_state(self):
        return {"layer_loss": jnp.zeros((), jnp.float32),
                "selected_keys": jnp.zeros((), jnp.float32),
                "attend_grid_steps_per_tile": jnp.zeros((), jnp.float32),
                "attend_backward_passes": jnp.zeros((), jnp.float32)}

    def gauges(self, state):
        return {"selected_keys_per_query": state["selected_keys"],
                "indexer_loss": state["layer_loss"],
                "attend_grid_steps_per_tile":
                    state["attend_grid_steps_per_tile"],
                "attend_backward_passes": state["attend_backward_passes"]}

    def init_params(self, key, dtype=jnp.float32):
        D, H, KV, Dh = self.n_in, self.n_heads, self.n_kv_heads, self.head_dim
        HI, DI = self.indexer_heads, self.indexer_head_dim
        k = jax.random.split(key, 7)
        mk = lambda kk, shape: _normal(kk, shape, self.init_std, dtype)
        return {"Wq": mk(k[0], (D, H * Dh)), "Wk": mk(k[1], (D, KV * Dh)),
                "Wv": mk(k[2], (D, KV * Dh)), "Wo": mk(k[3], (H * Dh, D)),
                "q_norm": jnp.ones((Dh,), dtype),
                "k_norm": jnp.ones((Dh,), dtype),
                "WqI": mk(k[4], (D, HI * DI)), "WkI": mk(k[5], (D, DI)),
                "Ww": mk(k[6], (D, HI))}

    # -- pieces, public so that a check can call what the step calls -------
    def project(self, p, h, positions):
        """q [B,T,KV,R,Dh], k, v [B,T,KV,Dh] (normed, turned) and the
        indexer's qi [B,T,HI,DI], ki [B,T,DI], w [B,T,HI] (from
        stop_gradient(h))."""
        B, T, _ = h.shape
        H, KV, Dh = self.n_heads, self.n_kv_heads, self.head_dim
        cos, sin = mrope_angles(positions, Dh, self.rope_theta,
                                self.mrope_section)
        q = rms_norm((h @ p["Wq"]).reshape(B, T, H, Dh), p["q_norm"],
                     self.eps)
        k = rms_norm((h @ p["Wk"]).reshape(B, T, KV, Dh), p["k_norm"],
                     self.eps)
        q = rotate_half(q, cos, sin).reshape(B, T, KV, H // KV, Dh)
        k = rotate_half(k, cos, sin)
        v = (h @ p["Wv"]).reshape(B, T, KV, Dh)
        with jax.named_scope("indexer"):
            hb = jax.lax.stop_gradient(h)
            qi = (hb @ p["WqI"]).reshape(B, T, self.indexer_heads,
                                         self.indexer_head_dim)
            ki, w = hb @ p["WkI"], hb @ p["Ww"]
        return q, k, v, qi, ki, w

    def _row(self, q, k, v, qi, ki, w):
        """One sequence: (o [T, H, Dh], the sum of its queries' KL, its
        selected pairs). Index scores and the selection a chunk of queries
        at a time, each against its causal prefix of keys (a chunk keeps
        nothing for the backward but its inputs: `index_scores`' own VJP
        makes the heads' dots again inside its kernel); then the main
        attention over the selected pairs and the indexer's target, the
        probabilities of all heads added up, as Pallas kernels
        (ops/sparse_attention.py) that never write a head's [T, T] scores
        out."""
        from ....ops.sparse_attention import (KEEP, head_summed_probs,
                                              masked_attention)
        T, KV, R, Dh = q.shape
        C = min(self.q_chunk_size, T)
        scores, sel = [], []
        for a in range(0, T, C):
            b = min(a + C, T)
            with jax.named_scope("indexer"):
                s = index_scores(qi[a:b], ki[:b], w[a:b])
            with jax.named_scope("select"):
                m = select_keys(s, jnp.arange(a, b), self.topk)
            scores.append(jnp.pad(s, ((0, 0), (0, T - b))))
            sel.append(jnp.pad(m, ((0, 0), (0, T - b))))
        scores, sel = jnp.concatenate(scores), jnp.concatenate(sel)
        mask = checkpoint_name(
            jax.lax.stop_gradient(sel.astype(jnp.int8))[None], KEEP)
        heads = lambda a: jnp.moveaxis(a.reshape(T, -1, Dh), 0, 1)[None]
        qh, kh, vh = heads(q), heads(k), heads(v)
        scale = 1.0 / math.sqrt(Dh)
        with jax.named_scope("attend"):
            o, lse = masked_attention(qh, kh, vh, mask, scale)
        with jax.named_scope("indexer"):
            sg = jax.lax.stop_gradient
            # the main attention's probabilities over all heads, as the
            # indexer's target; L1-normalised over the selection
            target = sg(head_summed_probs(sg(qh), sg(kh), sg(lse), mask,
                                          scale))[0] / (KV * R)
            logq = jax.nn.log_softmax(jnp.where(sel, scores, NEG), -1)
            kl = jnp.sum(jnp.where(
                sel, jax.scipy.special.xlogy(target, target) - target * logq,
                0.0))
        return (jnp.moveaxis(o[0], 0, 1), kl,
                jnp.sum(sel, dtype=jnp.float32))

    def forward_with_state(self, params, x, state, *, train=False, rng=None,
                           mask=None, extras=()):
        B, T, _ = x.shape
        parts = self.project(params, x, extras[0])
        # a row keeps for its backward its inputs, the selection and the
        # kernel's output; its scores and the target are computed again
        from ....ops.sparse_attention import (KEEP, backward_passes,
                                              grid_steps_per_tile)
        row = jax.checkpoint(
            self._row,
            policy=jax.checkpoint_policies.save_only_these_names(KEEP))
        o, kl, n = jax.lax.map(lambda parts: row(*parts), parts)
        out = o.reshape(B, T, -1) @ params["Wo"]
        # the kernels' schedule is a function of T: a constant of the trace
        return out, {"layer_loss": jnp.sum(kl) / (B * T),
                     "selected_keys": jnp.sum(n) / (B * T),
                     "attend_grid_steps_per_tile": jnp.float32(
                         grid_steps_per_tile(T)),
                     "attend_backward_passes": jnp.float32(backward_passes(
                         T, self.head_dim, self.head_dim))}


# ---------------------------------------------------------------------------
# dense attention, plain causal or windowed
# ---------------------------------------------------------------------------
def yarn_correction_range(rotary_dim, theta, original_max, beta_fast,
                          beta_slow):
    """(low, high): the slots between which YaRN's ramp runs, floored and
    ceiled, clamped to the slots there are (`transformers`'
    `_compute_yarn_parameters`, `truncate` true)."""
    slot = lambda turns: (rotary_dim * math.log(
        original_max / (turns * 2 * math.pi))) / (2 * math.log(theta))
    return (max(math.floor(slot(beta_fast)), 0),
            min(math.ceil(slot(beta_slow)), rotary_dim - 1))


def rotary_inv_freq(rotary_dim, theta, yarn=None):
    """The rotary_dim / 2 inverse frequencies (float32) and the factor cos
    and sin are multiplied by. `yarn` (factor, original_max_position_
    embeddings, beta_fast, beta_slow, attention_factor) blends each
    frequency with its `factor`-th along a ramp over the slots."""
    inv = theta ** (-(2.0 * jnp.arange(rotary_dim // 2, dtype=jnp.float32))
                    / rotary_dim)
    if yarn is None:
        return inv, 1.0
    factor, original_max, beta_fast, beta_slow, attention_factor = yarn
    low, high = yarn_correction_range(rotary_dim, theta, original_max,
                                      beta_fast, beta_slow)
    ramp = jnp.clip((jnp.arange(rotary_dim // 2, dtype=jnp.float32) - low)
                    / (high - low + (0.001 if low == high else 0.0)), 0, 1)
    return inv / factor * ramp + inv * (1 - ramp), attention_factor


@register_layer("attention")
@dataclass
class AttentionLayer(_StatefulSequenceLayer):
    """Grouped-query attention over positions 0 .. T-1: causal, inside
    `window` where it is set. `n_heads` may differ from layer to layer of a
    model over the same `n_kv_heads`. With `rope_theta` None nothing is
    turned (a model whose other layers carry the order: no `rotary` scope);
    with `gate` false the output passes no gate (no `Wgate` leaf, no `gate`
    scope)."""
    n_in: int = None
    n_out: int = None
    n_heads: int = 48
    n_kv_heads: int = 8
    head_dim: int = 128
    window: int = None          # None: every key up to the query
    rope_theta: float = 1e4     # None: no positional encoding
    rotary_dim: int = None      # the slots turned; None: all of a head's
    yarn: tuple = None          # see `rotary_inv_freq`
    gate: bool = True           # the per-head sigmoid gate on the output
    init_std: float = 0.02

    def to_dict(self):
        d = super().to_dict()
        d.setdefault("rope_theta", None)    # None is a value here, not "unset"
        return d

    def init_state(self):
        return {"attend_grid_steps_per_tile": jnp.zeros((), jnp.float32),
                "attend_backward_passes": jnp.zeros((), jnp.float32)}

    def gauges(self, state):
        return dict(state)

    def remat_keeps(self):
        # the kernel's o and lse, which `masked_attention` names: its
        # backward's operands, so the container's segment does not run the
        # forward kernel a second time (`sparseattention` keeps them across
        # its own row's checkpoint and names nothing here)
        from ....ops.sparse_attention import KEEP
        return (KEEP,)

    def init_params(self, key, dtype=jnp.float32):
        D, H, KV, Dh = self.n_in, self.n_heads, self.n_kv_heads, self.head_dim
        k = jax.random.split(key, 5)
        mk = lambda kk, shape: _normal(kk, shape, self.init_std, dtype)
        p = {"Wq": mk(k[0], (D, H * Dh)), "Wk": mk(k[1], (D, KV * Dh)),
             "Wv": mk(k[2], (D, KV * Dh)), "Wo": mk(k[3], (H * Dh, D))}
        if self.gate:
            p["Wgate"] = mk(k[4], (D, H))
        return p

    def turn(self, x, positions):
        """x [B, T, heads, Dh]: the first `rotary_dim` slots turned by
        position (pairs (i, i + rotary_dim / 2)), the rest passed on."""
        n = self.rotary_dim or self.head_dim
        inv, factor = rotary_inv_freq(
            n, self.rope_theta, self.yarn and tuple(self.yarn))
        ang = positions.astype(jnp.float32)[..., None] * inv
        turned = rotate_half(x[..., :n], jnp.cos(ang) * factor,
                             jnp.sin(ang) * factor)
        return turned if n == self.head_dim else jnp.concatenate(
            [turned, x[..., n:]], -1)

    def forward_with_state(self, params, x, state, *, train=False, rng=None,
                           mask=None):
        from ....ops.sparse_attention import (backward_passes,
                                              grid_steps_per_tile,
                                              masked_attention)
        B, T, _ = x.shape
        H, KV, Dh = self.n_heads, self.n_kv_heads, self.head_dim
        q = (x @ params["Wq"]).reshape(B, T, H, Dh)
        k = (x @ params["Wk"]).reshape(B, T, KV, Dh)
        v = (x @ params["Wv"]).reshape(B, T, KV, Dh)
        if self.rope_theta is not None:
            with jax.named_scope("rotary"):
                pos = jnp.broadcast_to(jnp.arange(T), (B, T))
                q, k = self.turn(q, pos), self.turn(k, pos)
        heads = lambda a: jnp.moveaxis(a, 1, 2)         # [B, heads, T, Dh]
        with jax.named_scope(
                "attend_full" if self.window is None else "attend_window"):
            o, _ = masked_attention(heads(q), heads(k), heads(v), None,
                                    1.0 / math.sqrt(Dh), window=self.window)
        o = jnp.moveaxis(o, 1, 2)                       # [B, T, H, Dh]
        if self.gate:
            with jax.named_scope("gate"):
                g = jax.nn.sigmoid(jnp.dot(
                    x, params["Wgate"], preferred_element_type=jnp.float32))
                o = (o * g[..., None]).astype(x.dtype)
        # the kernels' schedule is a function of T: a constant of the trace
        return o.reshape(B, T, H * Dh) @ params["Wo"], {
            "attend_grid_steps_per_tile": jnp.float32(grid_steps_per_tile(
                T, window=self.window)),
            "attend_backward_passes": jnp.float32(backward_passes(T, Dh, Dh))}


@register_layer("latentattention")
@dataclass
class LatentAttentionLayer(_StatefulSequenceLayer):
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 section
    2.1) over positions 0 .. T-1, causal, run expanded: every head's keys
    and values are made from the latent, as training does. Its fields are
    the published config's keys."""
    n_in: int = None
    n_out: int = None
    n_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 32e6
    eps: float = 1e-6
    init_std: float = 0.02

    def init_state(self):
        return {"attend_grid_steps_per_tile": jnp.zeros((), jnp.float32),
                "attend_backward_passes": jnp.zeros((), jnp.float32)}

    def gauges(self, state):
        return dict(state)

    def remat_keeps(self):
        # as `attention`: the kernel's o and lse are its backward's operands
        from ....ops.sparse_attention import KEEP
        return (KEEP,)

    def init_params(self, key, dtype=jnp.float32):
        D, H = self.n_in, self.n_heads
        dn, dr, dv = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                      self.v_head_dim)
        k = jax.random.split(key, 5)
        mk = lambda kk, shape: _normal(kk, shape, self.init_std, dtype)
        return {"Wq_a": mk(k[0], (D, self.q_lora_rank)),
                "q_norm": jnp.ones((self.q_lora_rank,), dtype),
                "Wq_b": mk(k[1], (self.q_lora_rank, H * (dn + dr))),
                "Wkv_a": mk(k[2], (D, self.kv_lora_rank + dr)),
                "kv_norm": jnp.ones((self.kv_lora_rank,), dtype),
                "Wkv_b": mk(k[3], (self.kv_lora_rank, H * (dn + dv))),
                "Wo": mk(k[4], (H * dv, D))}

    def turn(self, x, positions):
        """x [B, T, heads, qk_rope_head_dim] turned by position as
        interleaved pairs: slots (2i, 2i + 1) by the angle
        position * theta^(-2i / qk_rope_head_dim), in place (a rotate-half
        lowering moves the slots, the same way in q and k: same scores)."""
        n = self.qk_rope_head_dim
        inv = self.rope_theta ** (
            -(2.0 * jnp.arange(n // 2, dtype=jnp.float32)) / n)
        ang = positions.astype(jnp.float32)[..., None, None] * inv
        c, s = jnp.cos(ang), jnp.sin(ang)
        pair = x.astype(jnp.float32).reshape(x.shape[:-1] + (n // 2, 2))
        a, b = pair[..., 0], pair[..., 1]
        return jnp.stack([a * c - b * s, b * c + a * s], -1).reshape(
            x.shape).astype(x.dtype)

    def forward_with_state(self, params, x, state, *, train=False, rng=None,
                           mask=None):
        from ....ops.sparse_attention import (backward_passes,
                                              grid_steps_per_tile,
                                              shared_key_attention)
        B, T, _ = x.shape
        H, dn, dr, dv = (self.n_heads, self.qk_nope_head_dim,
                         self.qk_rope_head_dim, self.v_head_dim)
        with jax.named_scope("latent"):
            c_q = rms_norm(x @ params["Wq_a"], params["q_norm"], self.eps)
            q = (c_q @ params["Wq_b"]).reshape(B, T, H, dn + dr)
            kv_a = x @ params["Wkv_a"]
            c_kv = rms_norm(kv_a[..., :self.kv_lora_rank], params["kv_norm"],
                            self.eps)
            kv = (c_kv @ params["Wkv_b"]).reshape(B, T, H, dn + dv)
        with jax.named_scope("rotary"):
            pos = jnp.broadcast_to(jnp.arange(T), (B, T))
            q_rope = self.turn(q[..., dn:], pos)
            k_rope = self.turn(kv_a[..., None, self.kv_lora_rank:], pos)
        heads = lambda a: jnp.moveaxis(a, 1, 2)         # [B, heads, T, d]
        with jax.named_scope("attend_latent"):
            o, _ = shared_key_attention(
                heads(q[..., :dn]), heads(kv[..., :dn]), heads(kv[..., dn:]),
                heads(q_rope), heads(k_rope), 1.0 / math.sqrt(dn + dr))
        with jax.named_scope("latent"):
            out = jnp.moveaxis(o, 1, 2).reshape(B, T, H * dv) @ params["Wo"]
        # the kernels' schedule is a function of T: a constant of the trace
        return out, {"attend_grid_steps_per_tile": jnp.float32(
            grid_steps_per_tile(T)),
                     "attend_backward_passes": jnp.float32(
                         backward_passes(T, dn, dv, dr))}


@register_layer("projection")
@dataclass
class ProjectionLayer(_SequenceLayer):
    """x W, [B, T, n_in] -> [B, T, n_out], no bias."""
    n_in: int = None
    n_out: int = None
    init_std: float = 0.02

    def init_params(self, key, dtype=jnp.float32):
        return {"W": _normal(key, (self.n_in, self.n_out), self.init_std,
                             dtype)}

    def forward(self, params, x, *, train=False, rng=None, mask=None,
                state=None):
        return x @ params["W"]


@register_layer("gatedmlp")
@dataclass
class GatedMLPLayer(_SequenceLayer):
    n_in: int = None
    n_out: int = None
    width: int = 8192
    init_std: float = 0.02

    def init_params(self, key, dtype=jnp.float32):
        k = jax.random.split(key, 3)
        mk = lambda kk, shape: _normal(kk, shape, self.init_std, dtype)
        return {"Wg": mk(k[0], (self.n_in, self.width)),
                "Wu": mk(k[1], (self.n_in, self.width)),
                "Wd": mk(k[2], (self.width, self.n_in))}

    def forward(self, params, x, *, train=False, rng=None, mask=None,
                state=None):
        return gated_mlp(x, params["Wg"], params["Wu"], params["Wd"])


def gated_mlp(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def relu2_mlp(x, w_up, w_down):
    return jnp.square(jax.nn.relu(x @ w_up)) @ w_down


# ---------------------------------------------------------------------------
# experts
# ---------------------------------------------------------------------------
@register_layer("moe")
@dataclass
class MoELayer(_StatefulSequenceLayer):
    """Routes over `n_experts`, `experts_per_token` a token; holds experts
    `first_held .. first_held + experts_held - 1` (all of them by default)
    and computes their part of the result, every routed pair of it. The
    routed weights are multiplied by `routed_scale`; with `shared_width` a
    shared expert of that width, which every chip of a deployment computes
    alike for its own tokens, is added unscaled. `scoring` is the router's:
    "softmax" over all experts, or "sigmoid" of each. With
    `bias_update_rate` gamma (DeepSeek-V3, arXiv:2412.19437 section 2.1.2)
    the top k are those of score + b while the weights stay the scores'; b
    [n_experts] is state, zero at first, and every training forward leaves
    b + gamma sign(mean(c) - c), c its own tokens' pairs by expert (a
    deployment adds the other chips' counts before the sign). An expert,
    and the shared one, is the gated (SiLU(x Wg) * (x Wu)) Wd; with
    `activation` "relu2" it is relu(x Wu)^2 Wd, two matrices and no gate:
    no `Wg`, `Sg` leaves at all."""
    n_in: int = None
    n_out: int = None
    n_experts: int = 128
    experts_per_token: int = 8
    expert_width: int = 768
    norm_topk_prob: bool = True
    experts_held: int = None
    first_held: int = 0
    shared_width: int = None
    routed_scale: float = 1.0
    scoring: str = "softmax"
    bias_update_rate: float = None
    init_std: float = 0.02

    def _held(self):
        return self.experts_held or self.n_experts

    def init_state(self):
        state = {"held_pairs": jnp.zeros((self._held(),), jnp.float32),
                 "absent_pairs": jnp.zeros((), jnp.float32),
                 "blocks_run": jnp.zeros((), jnp.float32)}
        if self.bias_update_rate is not None:
            state["bias"] = jnp.zeros((self.n_experts,), jnp.float32)
        return state

    def gauges(self, state):
        held = state["held_pairs"]
        out = {"held_pairs_max": jnp.max(held),
               "held_pairs_mean": jnp.mean(held),
               "absent_pairs": state["absent_pairs"],
               "blocks_run": state["blocks_run"]}
        if "bias" in state:
            out["bias_abs_max"] = jnp.max(jnp.abs(state["bias"]))
        return out

    def init_params(self, key, dtype=jnp.float32):
        D, F, G = self.n_in, self.expert_width, self._held()
        k = jax.random.split(key, 4)
        mk = lambda kk, shape: _normal(kk, shape, self.init_std, dtype)
        gated = not self._two_matrix()
        p = {"Wr": mk(k[0], (D, self.n_experts)),
             "Wu": mk(k[2], (G, D, F)), "Wd": mk(k[3], (G, F, D))}
        if gated:
            p["Wg"] = mk(k[1], (G, D, F))
        if self.shared_width:
            S = self.shared_width
            k = jax.random.split(jax.random.fold_in(key, 1), 3)
            p.update(Su=mk(k[1], (D, S)), Sd=mk(k[2], (S, D)))
            if gated:
                p["Sg"] = mk(k[0], (D, S))
        return p

    def _two_matrix(self):
        return self.activation == "relu2"

    def forward_with_state(self, params, x, state, *, train=False, rng=None,
                           mask=None):
        from ....parallel.moe import held_experts_ffn, route_all
        B, T, D = x.shape
        tokens = x.reshape(B * T, D)
        biased = self.bias_update_rate is not None
        with jax.named_scope("router"):
            experts, gates = route_all(
                params["Wr"], tokens, self.experts_per_token,
                self.norm_topk_prob, self.scoring,
                state["bias"] if biased else None)
            if self.routed_scale != 1.0:
                gates = gates * self.routed_scale
            more = {}
            if biased:
                more["bias"] = state["bias"] if not train else (
                    state["bias"] + self.bias_update_rate * jnp.sign(
                        B * T * self.experts_per_token / self.n_experts
                        - jnp.bincount(experts.reshape(-1),
                                       length=self.n_experts)))
        y, counts, n_run = held_experts_ffn(
            tokens, experts, gates, params.get("Wg"), params["Wu"],
            params["Wd"], self.first_held, self.n_experts)
        if self.shared_width:
            with jax.named_scope("shared"):
                y = y + (relu2_mlp(tokens, params["Su"], params["Sd"])
                         if self._two_matrix() else
                         gated_mlp(tokens, params["Sg"], params["Su"],
                                   params["Sd"]))
        counts = counts.astype(jnp.float32)
        return y.astype(x.dtype).reshape(B, T, D), {
            "held_pairs": counts,
            "absent_pairs": B * T * self.experts_per_token - jnp.sum(counts),
            "blocks_run": n_run.astype(jnp.float32), **more}


# ---------------------------------------------------------------------------
# head
# ---------------------------------------------------------------------------
@register_layer("lmhead")
@dataclass
class LMHeadLayer(_SequenceLayer):
    """Logits over `n_out` rows of a vocabulary; the loss is the mean
    cross-entropy over the positions the label mask keeps (integer labels
    [B, T]), whatever row they are in."""
    n_in: int = None
    n_out: int = None
    token_chunk: int = 2048
    init_std: float = 0.02
    loss_weight: float = None   # set: the container multiplies this
    #                             output's loss by it and leaves the
    #                             unweighted loss in the state's `loss`

    def has_state(self):
        return self.loss_weight is not None

    def init_state(self):
        return {"loss": jnp.zeros((), jnp.float32)} if self.has_state() \
            else {}

    def gauges(self, state):
        return dict(state)

    def init_params(self, key, dtype=jnp.float32):
        return {"W": _normal(key, (self.n_in, self.n_out), self.init_std,
                             dtype)}

    def forward(self, params, x, *, train=False, rng=None, mask=None,
                state=None):
        return jnp.dot(x, params["W"], preferred_element_type=jnp.float32)

    def forward_with_state(self, params, x, state, **kw):
        return self.forward(params, x, **kw), state

    def compute_score_per_example(self, params, x, labels, *, train=False,
                                  rng=None, mask=None):
        B, T, D = x.shape
        keep = (jnp.ones((B, T), jnp.float32) if mask is None
                else mask.astype(jnp.float32))
        C = min(self.token_chunk, T)
        pad = -T % C
        chunks = lambda a: jnp.moveaxis(jnp.pad(
            a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)).reshape(
                (B, (T + pad) // C, C) + a.shape[2:]), 1, 0)

        @jax.checkpoint
        def chunk_loss(args):
            xc, yc, mc = args
            logits = jnp.dot(xc, params["W"],
                             preferred_element_type=jnp.float32)
            picked = jnp.take_along_axis(
                logits, yc.astype(jnp.int32)[..., None], -1)[..., 0]
            return jnp.sum((jax.nn.logsumexp(logits, -1) - picked) * mc, -1)

        per_row = jnp.sum(jax.lax.map(
            chunk_loss, (chunks(x), chunks(labels), chunks(keep))), 0)
        # the container takes the mean over rows: scale so that it is the
        # mean over the kept positions of the whole batch
        return per_row * (B / jnp.maximum(jnp.sum(keep), 1.0))
