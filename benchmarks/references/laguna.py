"""Plain reference for Laguna-XS.2 in training: forward, loss, gradients and
Adam in straightforward jax.numpy, float32, every product at "highest"
precision. Imports nothing of the program, no kernels. It follows the
equations of ISSUE 32 / `configs/laguna-xs.2.json` (`assumed` lists what
the published config leaves open); layer l is of type `layer_types[l]` with
H_l = `num_attention_heads_per_layer[l]` query heads over 8 key/value heads:

  h = Embed(ids)                                 positions 0 .. T-1
  per layer:
    a = RMSNorm(h); q, k, v = a Wq, a Wk, a Wv   (H_l / 8 / 8 heads of 128)
    rotary turn, rotate-half pairing inside the turned slots:
      sliding_attention: theta 10,000, all 128 slots
      full_attention: the first 64 slots, pairs (i, i + 32), theta 500,000
        under YaRN (inv_freq_i blended with inv_freq_i / 64 along the ramp
        between the correction range of beta_fast 64 and beta_slow 1 over
        dim 64 at 4,096 original positions; cos and sin times
        attention_factor); slots 64-127 pass through
    scores q k^T / sqrt(128), head a reads key/value head a // (H_l / 8);
      key j visible to query i iff j <= i and, on sliding layers,
      i - j < 512; softmax; o = p v
    g = sigmoid(a Wgate) [T, H_l]; o_head *= g_head; h += concat(o) Wo
    m = RMSNorm(h)
    dense layer:  h += (SiLU(m Wg) * (m Wu)) Wd
    sparse layer: r = softmax(m Wr) over ALL experts; top 8, weights
      divided by their sum, times moe_routed_scaling_factor;
      h += sum over the routed experts HELD here of weight * expert_e(m)
           + shared(m)                      (absent experts left out)
  logits = RMSNorm(h) Whead over the vocabulary slice
  loss = mean over the masked positions of the next token's cross-entropy

Memory, not results: every layer is rematerialised; attention runs one
key/value head's group of query heads at a time and inside it a block of
1,024 queries at a time against every key, each block rematerialised (so
T = 16,384 in float32 fits beside Adam's state); the held experts one at a
time, every token through each, times its weight or 0 (plain, and 8 times
the program's work); the head 2,048 positions at a time.

`quant` is the control (`references/keye_vl.py linear`): every product with a
weight matrix (projections, the gate, dense MLP, experts, shared expert,
head; not the router, not the attention's own two products) as an fp8
trainer computes it: operands in e4m3 forward, the incoming gradient in
e5m2 backward, per-tensor scales. fp8 is the nearest precision below the
bfloat16 the configuration states.
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

# what is the same in every decoder's reference: the fp8 control's products
# (`linear(quant)`), RMSNorm, the leaf norms and the program's Adam
from .keye_vl import HI, NEG, adam, leaf_norms, linear, rms

Q_BLOCK, HEAD_BLOCK = 1024, 2048


def sizes(model):
    dep = model["deployment"]
    return dict(
        D=model["hidden_size"], KV=model["num_key_value_heads"],
        Dh=model["head_dim"], F=model["moe_intermediate_size"],
        S=model["shared_expert_intermediate_size"],
        I=model["intermediate_size"], V=model["vocab_size"],
        E=dep["router_width"], G=model["num_experts"],
        first=dep["first_held"], k=model["num_experts_per_tok"],
        scale=model["moe_routed_scaling_factor"],
        window=model["sliding_window"], eps=model["rms_norm_eps"],
        rope=model["rope_parameters"],
        layers=dep.get("layers", list(range(model["num_hidden_layers"]))),
        types=model["layer_types"], mlps=model["mlp_layer_types"],
        heads=model["num_attention_heads_per_layer"])


def param_shapes(model):
    """{vertex: {leaf: shape}}, named as the zoo names its vertices: layer
    n of the kept ones is published layer `layers[n]`."""
    z = sizes(model)
    D, Dh, KV = z["D"], z["Dh"], z["KV"]
    shapes = {"embed": {"W": (z["V"], D)}, "norm_f": {"g": (D,)},
              "head": {"W": (D, z["V"])}}
    for n, i in enumerate(z["layers"]):
        H = z["heads"][n]
        shapes[f"l{i}_norm1"] = {"g": (D,)}
        shapes[f"l{i}_norm2"] = {"g": (D,)}
        shapes[f"l{i}_attn"] = {
            "Wq": (D, H * Dh), "Wk": (D, KV * Dh), "Wv": (D, KV * Dh),
            "Wo": (H * Dh, D), "Wgate": (D, H)}
        shapes[f"l{i}_mlp"] = (
            {"Wg": (D, z["I"]), "Wu": (D, z["I"]), "Wd": (z["I"], D)}
            if z["mlps"][n] == "dense" else
            {"Wr": (D, z["E"]), "Wg": (z["G"], D, z["F"]),
             "Wu": (z["G"], D, z["F"]), "Wd": (z["G"], z["F"], D),
             "Sg": (D, z["S"]), "Su": (D, z["S"]), "Sd": (z["S"], D)})
    return shapes


# --------------------------------------------------------------- the model
def inv_freq(rope, head_dim):
    """(the turned slots' inverse frequencies [dim / 2], the factor on cos
    and sin) of one layer type's `rope_parameters`, as `transformers`
    initialises them."""
    dim = int(head_dim * rope["partial_rotary_factor"])
    inv = rope["rope_theta"] ** (-jnp.arange(0, dim, 2, dtype=jnp.float32)
                                 / dim)
    if rope["rope_type"] != "yarn":
        return inv, 1.0
    slot = lambda turns: dim * math.log(
        rope["original_max_position_embeddings"] / (turns * 2 * math.pi)) \
        / (2 * math.log(rope["rope_theta"]))
    low = max(math.floor(slot(rope["beta_fast"])), 0)
    high = min(math.ceil(slot(rope["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    return (inv / rope["factor"] * ramp + inv * (1 - ramp),
            rope["attention_factor"])


def rotary(x, rope):
    """x [T, heads, Dh] at positions 0 .. T-1."""
    inv, factor = inv_freq(rope, x.shape[-1])
    half = inv.shape[0]
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    c, s = (jnp.cos(ang) * factor)[:, None], (jnp.sin(ang) * factor)[:, None]
    a, b, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([a * c - b * s, b * c + a * s, rest], -1)


def visible(start, count, T, window):
    """[count, T]: which keys the queries start .. start + count - 1 see."""
    d = (start + jnp.arange(count))[:, None] - jnp.arange(T)[None, :]
    return (d >= 0) if window is None else (d >= 0) & (d < window)


def group_attention(a, wq, wk, wv, wgate, wo, rope, window, Dh, lin):
    """One key/value head and its R query heads over one sequence a [T, D]:
    their gated outputs through their rows of Wo, [T, D]."""
    T = a.shape[0]
    R = wq.shape[1] // Dh
    q = rotary(lin(a, wq).reshape(T, R, Dh), rope)
    k = rotary(lin(a, wk).reshape(T, 1, Dh), rope)[:, 0]
    v = lin(a, wv)
    C = min(Q_BLOCK, T)
    if T % C:
        raise ValueError(f"{T} positions are not whole blocks of {C}")

    @jax.checkpoint
    def block(args):
        qb, start = args
        s = jnp.einsum("crd,sd->rcs", qb, k, precision=HI) / math.sqrt(Dh)
        p = jax.nn.softmax(
            jnp.where(visible(start, C, T, window)[None], s, NEG), -1)
        return jnp.einsum("rcs,sd->crd", p, v, precision=HI)

    o = lax.map(block, (q.reshape(T // C, C, R, Dh), jnp.arange(0, T, C)))
    o = o.reshape(T, R, Dh)
    o = o * jax.nn.sigmoid(lin(a, wgate))[..., None]
    return lin(o.reshape(T, R * Dh), wo)


def attention(p, a, rope, window, z, lin):
    """a [B, T, D] -> [B, T, D]: the sum over the key/value heads of each
    one's group."""
    D, KV, Dh = z["D"], z["KV"], z["Dh"]
    by_group = lambda w: jnp.moveaxis(w.reshape(D, KV, -1), 1, 0)
    groups = (by_group(p["Wq"]), by_group(p["Wk"]), by_group(p["Wv"]),
              by_group(p["Wgate"]),
              p["Wo"].reshape(KV, -1, D))

    def row(a_row):
        one = jax.checkpoint(lambda g: group_attention(
            a_row, g[0], g[1], g[2], g[3], g[4], rope, window, Dh, lin))
        return jnp.sum(lax.map(one, groups), 0)

    return jnp.stack([row(a[b]) for b in range(a.shape[0])])


def gated_mlp(u, wg, wu, wd, lin):
    return lin(jax.nn.silu(lin(u, wg)) * lin(u, wu), wd)


def route(p, u, z):
    r = jax.nn.softmax(jnp.dot(u, p["Wr"], precision=HI), -1)
    top, experts = lax.top_k(r, z["k"])
    return experts, top / jnp.sum(top, -1, keepdims=True) * z["scale"]


def routed_part(p, u, z, lin, first=None):
    """The part of the layer's result that the experts whose matrices `p`
    holds, `first` (this chip's by default) and those after it, give for
    tokens u [N, D], and each one's routed pairs."""
    first = z["first"] if first is None else first
    experts, weight = route(p, u, z)
    ids = first + jnp.arange(p["Wg"].shape[0])
    hit = experts[None] == ids[:, None, None]                  # [G, N, k]
    share = jnp.sum(jnp.where(hit, weight[None], 0.0), -1)     # [G, N]
    one = jax.checkpoint(lambda wg, wu, wd, c: gated_mlp(u, wg, wu, wd, lin)
                         * c[:, None])

    def add(y, e):
        return y + one(*e), None

    y, _ = lax.scan(add, jnp.zeros_like(u),
                    (p["Wg"], p["Wu"], p["Wd"], share))
    return y, jnp.sum(hit, (1, 2))


def experts_part(p, u, z, lin):
    """Routed experts held here, and the shared expert."""
    y, counts = routed_part(p, u, z, lin)
    return y + gated_mlp(u, p["Sg"], p["Su"], p["Sd"], lin), counts


def hidden(params, batch, model, quant=False):
    """The final hidden state [B, T, D] and the held experts' pairs of
    every sparse layer [sparse layers, held]."""
    z, lin = sizes(model), linear(quant)
    x = params["embed"]["W"][batch["ids"]]
    B, T, D = x.shape
    counts = []
    for n, i in enumerate(z["layers"]):
        kind, dense = z["types"][n], z["mlps"][n] == "dense"
        window = z["window"] if kind == "sliding_attention" else None

        @jax.checkpoint
        def layer(x, pa, pm, g1, g2, kind=kind, window=window, dense=dense):
            x = x + attention(pa, rms(x, g1, z["eps"]), z["rope"][kind],
                              window, z, lin)
            u = rms(x, g2, z["eps"]).reshape(B * T, D)
            if dense:
                return x + gated_mlp(u, pm["Wg"], pm["Wu"], pm["Wd"],
                                     lin).reshape(B, T, D), None
            y, c = experts_part(pm, u, z, lin)
            return x + y.reshape(B, T, D), c

        x, c = layer(x, params[f"l{i}_attn"], params[f"l{i}_mlp"],
                     params[f"l{i}_norm1"]["g"], params[f"l{i}_norm2"]["g"])
        if c is not None:
            counts.append(c)
    return (rms(x, params["norm_f"]["g"], z["eps"]),
            jnp.stack(counts) if counts else jnp.zeros((0, 0)))


def logits(params, batch, model, quant=False):
    h = hidden(params, batch, model, quant)[0]
    return linear(quant)(h, params["head"]["W"])


def loss(params, batch, model, quant=False):
    """(mean cross-entropy over the masked positions, {"held_pairs"
    [sparse layers, held]})."""
    h, counts = hidden(params, batch, model, quant)
    lin = linear(quant)
    B, T, D = h.shape
    C = min(HEAD_BLOCK, T)
    if T % C:
        raise ValueError(f"{T} positions are not whole blocks of {C}")
    blocks = lambda a: jnp.moveaxis(
        a.reshape((B, T // C, C) + a.shape[2:]), 1, 0)

    @jax.checkpoint
    def one(args):
        hb, yb, mb = args
        lg = lin(hb.reshape(B * C, D), params["head"]["W"]).reshape(B, C, -1)
        picked = jnp.take_along_axis(lg, yb[..., None], -1)[..., 0]
        return jnp.sum((jax.nn.logsumexp(lg, -1) - picked) * mb)

    mask = batch["mask"].astype(jnp.float32)
    ce = jnp.sum(lax.map(one, (blocks(h), blocks(batch["labels"]),
                               blocks(mask)))) / jnp.sum(mask)
    return ce, {"held_pairs": counts}


# ------------------------------------------------------------- the trainer
def train_steps(params, batches, model, trainer, quant=False, remake=None):
    """Follow the first len(batches) steps from `params`, which are DONATED
    to the first step (the reference's own Adam state fills the chip);
    `remake()` returns them again for the change. Returns each step's loss
    (before its update), the per-leaf norms of the first gradient and of
    the parameters' change after the last step, and the first step's aux."""
    hp = {k: float(v) for k, v in trainer.items()
          if k in ("learning_rate", "beta1", "beta2", "epsilon")}

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(p, m, v, batch, t):
        (l, aux), g = jax.value_and_grad(loss, has_aux=True)(p, batch, model,
                                                             quant)
        out = jax.tree.map(lambda a, b, c, d: adam(a, b, c, d, t, hp),
                           p, m, v, g)
        pick = lambda i: jax.tree.map(lambda _, o: o[i], p, out)
        return pick(0), pick(1), pick(2), l, aux, leaf_norms(g)

    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
    p, m, v = params, zeros(params), zeros(params)
    del params
    losses, g1, aux1 = [], None, None
    for i, batch in enumerate(batches):
        p, m, v, l, aux, g = step(p, m, v, batch, float(i + 1))
        losses.append(l)
        if i == 0:
            g1, aux1 = g, aux
    del m, v
    start = remake()
    change = jax.jit(lambda a, b: leaf_norms(
        jax.tree.map(lambda c, d: c - d, a, b)))(p, start)
    return jnp.stack(losses), g1, change, aux1
