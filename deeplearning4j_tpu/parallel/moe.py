"""Mixture-of-Experts: two expert layers, one that drops and one that does not.

**`moe_mlp_sharded` / `moe_mlp_dense` (GeLU experts with biases, top-1 or
top-2, FIXED CAPACITY, overflow DROPPED)**: expert parallelism over an
"expert" mesh axis. The reference has NO MoE (SURVEY.md §2.5 marks EP as
absent/optional) — this is a TPU-first extension: top-1 routing with raw
router-prob gates (the Switch Transformer recipe) or top-k with
renormalized combine weights (GShard/Mixtral, ``k=2``), and an
``lax.all_to_all`` token shuffle over ICI so each device hosts exactly one
(or E/devices) expert's FFN. The `[E, C, D]` send buffer's static capacity
is what makes the exchange one `all_to_all`; units past it are dropped. The
dense einsum path (`moe_mlp_dense`) is the single-chip reference the sharded
path is tested against, at every k. Used by `models/zoo/transformer.py`
`make_moe_block_fn` (the pipeline/EP trainer of `examples/three_axis_mesh.py`
and `__graft_entry__.dryrun_multichip`), never by the containers.

**`held_experts_ffn` (gated SiLU or two-matrix relu^2 experts without
biases, top-k of all E, NO CAPACITY, NOTHING DROPPED)**: one chip's share of an expert-parallel
layer. It routes over all E experts, is told which contiguous range it
holds, and computes every routed (token, choice) pair of a held expert:
pairs sorted by expert, the three (or two) products as `lax.ragged_dot` over the
ragged groups, in row blocks of static size, as many of them as hold a pair:
the loop's trip count is read from the routing on the device, forward and
in its hand-written backward. It has no exchange and nothing that stands in
for the absent chips: what their experts would add is left out. Used by the
`moe` layer kind of the containers (`nn/conf/layers/decoder.py`,
`models/zoo/keye_vl.py`). Two remain because the first one's fixed-capacity
buffers ARE its exchange format (and its tests pin the drop rule), while a
trainer at the sizes of a 128-expert model may not drop.

Shapes (first layer): tokens [B, D]; E experts, capacity C per (source
device, expert). Dispatch (per device, inside shard_map over axis "expert"):

  1. gate logits -> top-k experts + combine weights per token
  2. each (token, choice) dispatch unit scatters into a [E, C, D] send
     buffer, token-major (position = rank within its expert group;
     overflow units are DROPPED — the residual path passes those tokens
     through, standard Switch behavior)
  3. all_to_all: device e receives every device's buffer-for-e -> [n, C, D]
  4. local expert FFN over the received tokens (one big MXU matmul)
  5. reverse all_to_all; each token sums its k gated returns
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_expert_mesh(n_expert, devices=None):
    devices = devices if devices is not None else jax.devices()
    if len(devices) < n_expert:
        raise ValueError(f"need {n_expert} devices, have {len(devices)}")
    return Mesh(np.asarray(devices[:n_expert]), ("expert",))


def init_moe(rng, d_model, n_experts, d_ff, dtype=jnp.float32):
    """Gate + stacked expert FFN params ([E, ...] leading expert axis)."""
    k = jax.random.split(rng, 3)
    s_in = 1.0 / math.sqrt(d_model)
    s_out = 1.0 / math.sqrt(d_ff)
    return {
        "gate": (jax.random.normal(k[0], (d_model, n_experts)) *
                 s_in).astype(dtype),
        "w1": (jax.random.normal(k[1], (n_experts, d_model, d_ff)) *
               s_in).astype(dtype),
        "b1": jnp.zeros((n_experts, d_ff), dtype),
        "w2": (jax.random.normal(k[2], (n_experts, d_ff, d_model)) *
               s_out).astype(dtype),
        "b2": jnp.zeros((n_experts, d_model), dtype),
    }


def _expert_ffn(w1, b1, w2, b2, x):
    return jax.nn.gelu(x @ w1 + b1) @ w2 + b2


def _route_topk(gate_w, x, k):
    """Top-k routing (GShard/Mixtral shape): expert ids [B, k] by
    descending router prob, gates renormalized over the k winners so each
    token's combine weights sum to 1, full probs [B, E] for the aux loss
    (which stays over the TOP-1 assignment, the standard choice)."""
    probs = jax.nn.softmax((x @ gate_w).astype(jnp.float32), -1)
    top_p, experts = jax.lax.top_k(probs, k)                  # [B, k]
    if k == 1:
        gates = top_p            # Switch: raw router prob as the weight
    else:
        # GShard/Mixtral: combine weights renormalized over the winners
        gates = top_p / jnp.maximum(jnp.sum(top_p, -1, keepdims=True),
                                    1e-9)
    return experts, gates.astype(x.dtype), probs


def _route_fractions(probs, expert, n_experts):
    """(f, P): fraction of tokens routed to each expert, mean router prob
    per expert — the two means the Switch aux loss is built from (shared
    by the dense loss and the sharded pmean-then-multiply path)."""
    f = jnp.mean(jax.nn.one_hot(expert, n_experts, dtype=probs.dtype), 0)
    p = jnp.mean(probs, 0)
    return f, p


def load_balance_loss(probs, expert, n_experts):
    """Switch aux loss: E * sum_e f_e * P_e (f = fraction of tokens routed
    to e, P = mean router prob for e). Encourages uniform expert load."""
    f, p = _route_fractions(probs, expert, n_experts)
    return n_experts * jnp.sum(f * p)


def moe_mlp_dense(params, x, capacity=None, n_shards=1, k=1):
    """Single-chip reference: every expert computes every token, the
    top-k mask selects (k=1 = Switch, k=2 = GShard/Mixtral combine).
    With `capacity`, (token, choice) dispatch units past an expert's
    capacity are dropped; ranking is computed token-major within each of
    `n_shards` contiguous batch shards, matching exactly how
    `moe_mlp_sharded` drops per (source shard, expert) — set n_shards =
    the mesh axis size for exact equality with the sharded dispatch.
    Returns (y, aux_loss); aux stays over the top-1 assignment."""
    E = params["w1"].shape[0]
    experts, gates, probs = _route_topk(params["gate"], x, k)   # [B, k]
    B = x.shape[0]
    # virtual dispatch units, token-major: (b0,c0),(b0,c1),(b1,c0),...
    ev = experts.reshape(B * k)
    gv = gates.reshape(B * k)
    onehot_v = jax.nn.one_hot(ev, E, dtype=x.dtype)             # [B*k, E]
    if capacity is not None:
        oh = onehot_v.reshape(n_shards, (B * k) // n_shards, E)
        pos = (jnp.cumsum(oh, 1) - oh).reshape(B * k, E)
        keep = (jnp.take_along_axis(pos, ev[:, None], -1)[:, 0]
                < capacity).astype(x.dtype)
        gv = gv * keep
    # [E, B, D] all-experts compute (fine for small E; the EP path exists
    # for when it is not)
    y_all = jax.vmap(_expert_ffn)(params["w1"], params["b1"], params["w2"],
                                  params["b2"],
                                  jnp.broadcast_to(x, (E,) + x.shape))
    combine = (onehot_v * gv[:, None]).reshape(B, k, E).sum(1)  # [B, E]
    y = jnp.einsum("ebd,be->bd", y_all, combine)
    return y, load_balance_loss(probs, experts[:, 0], E)


def moe_mlp_sharded(mesh, axis="expert", capacity=None, k=1,
                    data_axis=None):
    """Build the expert-parallel apply fn: tokens sharded over `axis`,
    expert FFNs one-per-device-slice, all_to_all dispatch/return.

    Returns fn(params_sharded, x[B, D]) -> (y[B, D], aux_loss). B must be
    divisible by the axis size (by the PRODUCT of both axis sizes when
    `data_axis` is set — the batch shards over the joint
    (data_axis, axis) grid). `capacity` bounds dispatch units per
    (source device, expert) buffer; units past it are dropped (that
    choice contributes 0 — the caller's residual connection passes the
    token through, Switch-style). Default None = k*B_local, which can
    never drop. k>1 = GShard/Mixtral top-k combine: each token ships to
    its k experts as k token-major virtual dispatch units through the
    SAME scatter/all_to_all machinery, and the returns sum weighted by
    the renormalized gates (pinned == `moe_mlp_dense(k=...)` by test).

    `data_axis`: dp x ep composition on a 2-axis mesh — the batch shards
    over (data_axis, axis) jointly, expert params replicate across
    `data_axis`, and each data slice runs its own expert all_to_all ring
    (collectives stay within the expert groups; the aux loss pmean's over
    BOTH axes so it is the global-batch value).
    """
    n = mesh.shape[axis]

    def spmd(prm, x_local):
        B_loc, D = x_local.shape
        E = prm["w1"].shape[0] * n          # global expert count
        e_per_dev = prm["w1"].shape[0]
        V = B_loc * k                       # virtual dispatch units
        C = V if capacity is None else min(int(capacity), V)
        experts, gates, probs = _route_topk(prm["gate"], x_local, k)
        # token-major virtual expansion (matches moe_mlp_dense exactly)
        expert = experts.reshape(V)
        gate = gates.reshape(V)
        x_v = jnp.repeat(x_local, k, axis=0)           # [V, D]
        onehot = jax.nn.one_hot(expert, E, dtype=x_local.dtype)
        pos = (jnp.cumsum(onehot, 0) - onehot)
        pos_t = jnp.take_along_axis(
            pos, expert[:, None], -1)[:, 0].astype(jnp.int32)
        keep = pos_t < C
        # scatter into [E, C, D] send buffer
        buf = jnp.zeros((E, C, D), x_local.dtype)
        buf = buf.at[expert, jnp.where(keep, pos_t, C - 1)].add(
            x_v * keep[:, None].astype(x_local.dtype))
        # group by destination device: [n, e_per_dev*C, D]
        buf = buf.reshape(n, e_per_dev * C, D)
        recv = jax.lax.all_to_all(buf, axis, split_axis=0, concat_axis=0,
                                  tiled=True)      # [n*e_per_dev*C, D] tiles
        recv = recv.reshape(n, e_per_dev, C, D)    # [src, local_e, C, D]
        # local experts compute over all sources' tokens
        def one_expert(w1, b1, w2, b2, toks):      # toks [n, C, D]
            t = toks.reshape(n * C, D)
            return _expert_ffn(w1, b1, w2, b2, t).reshape(n, C, D)
        y = jax.vmap(one_expert, in_axes=(0, 0, 0, 0, 1))(
            prm["w1"], prm["b1"], prm["w2"], prm["b2"], recv)
        # y [local_e, src, C, D] -> send back [src, local_e*C, D]
        y = y.transpose(1, 0, 2, 3).reshape(n, e_per_dev * C, D)
        back = jax.lax.all_to_all(y, axis, split_axis=0, concat_axis=0,
                                  tiled=True)
        back = back.reshape(E, C, D)
        out_v = back[expert, jnp.where(keep, pos_t, 0)] * \
            (gate * keep.astype(gate.dtype))[:, None]
        out = out_v.reshape(B_loc, k, D).sum(1)        # combine k returns
        # global-batch aux loss: pmean f and P separately FIRST, then form
        # E*sum(f*P). pmean of per-shard losses would differ (the product
        # is nonlinear in f, P); shards hold equal token counts, so the
        # pmean of per-shard means IS the global mean and aux matches
        # moe_mlp_dense exactly (pinned by test). Aux stays over top-1.
        f_loc, p_loc = _route_fractions(probs, experts[:, 0], E)
        mean_axes = (axis,) if data_axis is None else (data_axis, axis)
        aux = E * jnp.sum(jax.lax.pmean(f_loc, mean_axes) *
                          jax.lax.pmean(p_loc, mean_axes))
        return out, aux

    pspec = {"gate": P(), "w1": P(axis), "b1": P(axis), "w2": P(axis),
             "b2": P(axis)}
    batch_spec = (P(axis) if data_axis is None
                  else P((data_axis, axis)))
    fn = jax.shard_map(spmd, mesh=mesh,
                       in_specs=(pspec, batch_spec),
                       out_specs=(batch_spec, P()),
                       check_vma=False)

    def apply(params, x):
        return fn(params, x)

    return apply


def shard_moe_params(params, mesh, axis="expert"):
    """Place MoE params on the mesh: gate replicated, expert stacks split
    over `axis`. Works on multi-host meshes (each process contributes its
    addressable shards via `sharding.put_sharded`)."""
    from .sharding import put_sharded
    out = {}
    for k, v in params.items():
        spec = P() if k == "gate" else P(axis)
        out[k] = put_sharded(v, NamedSharding(mesh, spec), full_array=True)
    return out


# ---------------------------------------------------------------------------
# One chip's share of an expert-parallel layer: no capacity, nothing dropped
# ---------------------------------------------------------------------------
def route_all(router_w, x, k, norm_topk=True, scoring="softmax", bias=None):
    """Scores over ALL experts in float32 (`scoring`: "softmax" over them,
    or "sigmoid" of each), the top k, and the combine weights (`norm_topk`:
    divided by the sum over all k winners, held here or not). With `bias`
    [experts] the winners are those of score + bias and the weights stay
    the scores' (a selection bias: no gradient reaches it). Returns
    (experts [N, k] int32, gates [N, k] float32)."""
    logits = jnp.dot(x, router_w, preferred_element_type=jnp.float32)
    scores = (jax.nn.sigmoid(logits) if scoring == "sigmoid"
              else jax.nn.softmax(logits, -1))
    if bias is None:
        top_p, experts = jax.lax.top_k(scores, k)
    else:
        experts = jax.lax.top_k(
            jax.lax.stop_gradient(scores + bias.astype(jnp.float32)), k)[1]
        top_p = jnp.take_along_axis(scores, experts, -1)
    if norm_topk:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    return experts, top_p


def _block_out(lo, rows, k, order, starts, ends, x, gate_flat, weights):
    """The sorted pairs lo .. lo + rows - 1: (their gated results
    [rows, D] float32, zero past the last held pair; each row's token).
    `weights` (w_gate, w_up, w_down) is the gated SiLU expert, (w_up,
    w_down) the two-matrix relu(x W_up)^2 W_down."""
    *w_in, w_down = weights
    pair = jax.lax.dynamic_slice(order, (lo,), (rows,))
    valid = (lo + jnp.arange(rows) < ends[-1])[:, None]
    tok = pair // k
    sizes = jnp.clip(ends, lo, lo + rows) - jnp.clip(starts, lo, lo + rows)
    # rows past the last group are not the kernel's to define
    rd = lambda a, w: jnp.where(valid, jax.lax.ragged_dot(
        a, w, sizes, preferred_element_type=jnp.float32), 0.0)
    xs = jnp.where(valid, x[tok], 0)
    h = (jax.nn.silu(rd(xs, w_in[0])) * rd(xs, w_in[1]) if len(w_in) == 2
         else jnp.square(jax.nn.relu(rd(xs, w_in[0])))).astype(x.dtype)
    out = rd(h, w_down) * jnp.where(valid[:, 0], gate_flat[pair],
                                    0.0)[:, None]
    return out, tok


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _walk_blocks(rows, k, x, gate_flat, weights, order, starts, ends, n_run):
    """The first `n_run` blocks of `rows` sorted pairs, summed by token. A
    loop whose trip count is read from the routing has no reverse-mode rule,
    hence the hand-written backward: it keeps the inputs, nothing a block,
    and walks the same blocks last to first, each computed again."""
    def body(i, y):
        out, tok = _block_out(i * rows, rows, k, order, starts, ends, x,
                              gate_flat, weights)
        return y.at[tok].add(out)

    with jax.named_scope("experts"):
        return jax.lax.fori_loop(0, n_run, body,
                                 jnp.zeros(x.shape, jnp.float32))


def _walk_blocks_fwd(rows, k, *args):
    return _walk_blocks(rows, k, *args), args


def _walk_blocks_bwd(rows, k, args, y_bar):
    *diff, order, starts, ends, n_run = args

    def body(j, acc):
        _, pull, tok = jax.vjp(
            lambda *a: _block_out((n_run - 1 - j) * rows, rows, k, order,
                                  starts, ends, *a),
            *diff, has_aux=True)
        return jax.tree.map(jnp.add, acc, pull(y_bar[tok]))

    # the scope again: what is traced here is not under the forward's, and
    # the metrics that read it would gain what fell out of it
    with jax.named_scope("experts"):
        grads = jax.lax.fori_loop(0, n_run, body,
                                  jax.tree.map(jnp.zeros_like, tuple(diff)))
    return (*grads, None, None, None, None)


_walk_blocks.defvjp(_walk_blocks_fwd, _walk_blocks_bwd)


def held_experts_ffn(x, experts, gates, w_gate, w_up, w_down, first_held,
                     n_experts=None, block_rows=None):
    """y[t] = sum over the routed pairs (t, e) with e held here of
    gates[t, e] * W_down,e(SiLU(W_gate,e x[t]) * W_up,e x[t]) or, with
    `w_gate` None (a two-matrix expert), of gates[t, e] * W_down,e
    relu(W_up,e x[t])^2.

    x [N, D]; experts/gates [N, k] from `route_all`; w_gate/w_up [G, D, F],
    w_down [G, F, D] are experts first_held .. first_held + G - 1. EVERY
    pair of a held expert is computed, however the routing is skewed: the
    N*k pairs are sorted by expert (absent experts last) and walked in
    blocks of `block_rows` rows of static shape, as many of them as hold a
    held pair: the trip count ceil(held pairs / `block_rows`) is read from
    the routing on the device, forward and backward, so the work follows
    the routing, the memory is one block's, and a block without a pair is
    never entered. By default a block is 1.25 times the held experts'
    expected share of the pairs (N k G / `n_experts`): an even router takes
    one trip, a router that sends every pair here N k / `block_rows`.
    Returns (y [N, D] float32, pairs of each held expert [G], the trip
    count).
    """
    N, D = x.shape
    k = experts.shape[1]
    G = w_up.shape[0]
    local = experts.reshape(-1) - first_held
    key = jnp.where((local >= 0) & (local < G), local, G)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    counts = jnp.bincount(key, length=G + 1)[:G].astype(jnp.int32)
    ends = jnp.cumsum(counts)
    if block_rows is None:
        share = N * k * G / (n_experts or G)
        block_rows = -(-int(1.25 * share) // 512) * 512
    rows = min(int(block_rows), N * k)
    order = jnp.pad(order, (0, -(N * k) % rows))
    n_run = (ends[-1] + rows - 1) // rows
    weights = (w_up, w_down) if w_gate is None else (w_gate, w_up, w_down)
    y = _walk_blocks(rows, k, x, gates.reshape(-1), weights, order,
                     ends - counts, ends, n_run)
    return y, counts, n_run
