"""Plain reference for Keye-VL-2.0's language model in training: forward,
loss, gradients and Adam in straightforward jax.numpy, float32, every
product at "highest" precision. Imports nothing of the program. It follows
the equations of ISSUE 28 / `configs/keye-vl-2.0-30b-a3b.json` (`assumed`
lists what the published config leaves open):

  x0 = Embed(ids), the image's embeddings in place of rows at its positions
  per layer:
    h = RMSNorm(x); q, k, v = h Wq, h Wk, h Wv (32 / 4 / 4 heads of 128);
    per-head RMSNorm of q and k; three-axis rotary turn (slots 0-15 by t,
    16-39 by h, 40-63 by w; pairs (i, i + 64))
    indexer on stop_gradient(h): qI [16 x 64], kI [64], w [16];
      I[t, s] = sum_j w[t, j] ReLU(qI[t, j] . kI[s]),  s <= t
    S_t = the causal keys whose I is at least the 2048th largest (all of
      them while there are no more than 2048; keys tied with the 2048th are
      all kept); one set for all heads
    o = softmax over S_t of q k / sqrt(128), times v;  x += o Wo
    L_I = mean_t KL(pbar[t, S_t] || softmax(I[t, S_t])), pbar the main
      attention's probabilities summed over heads, L1-normalised, constant
    u = RMSNorm(x); r = softmax(u Wr) over ALL experts; top 8, weights
      divided by their sum; x += sum over the routed experts HELD here of
      weight * Wdown(SiLU(Wgate u) * Wup u)       (absent experts left out)
  logits = RMSNorm(x) Whead over the vocabulary slice
  loss = mean over the masked positions of CE + sum over layers of L_I

Every layer (and every block of 256 queries inside attention, every held
expert, every block of 2048 positions of the head) is rematerialised so
that 8192 positions in float32 fit beside Adam's state; that changes
memory, not results. The held experts are computed densely (every token
through every held expert, times its weight or 0): plain, and 8 times the
program's work.

`quant` is the control: every product with a weight matrix (projections,
indexer projections, experts, head; not the router, not the attention's own
two products) as an fp8 trainer computes it: operands in e4m3 forward, the
incoming gradient in e5m2 backward, per-tensor scales. fp8 is the nearest
precision below the bfloat16 the configuration states.
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
NEG = -1e30
Q_BLOCK, HEAD_BLOCK = 256, 2048


def sizes(model):
    dep, sa = model["deployment"], model["sa_config"]
    return dict(
        D=model["hidden_size"], H=model["num_attention_heads"],
        KV=model["num_key_value_heads"], Dh=model["head_dim"],
        F=model["moe_intermediate_size"], L=model["num_hidden_layers"],
        V=model["vocab_size"], E=dep["router_width"],
        G=model["num_local_experts"], first=dep["first_held"],
        k=model["num_experts_per_tok"], HI=sa["indexer_num_heads"],
        DI=sa["indexer_head_dim"], topk=sa["topk"],
        eps=model["rms_norm_eps"], theta=float(model["rope_theta"]),
        sections=tuple(model["rope_scaling"]["mrope_section"]))


def param_shapes(model):
    """{vertex: {leaf: shape}}, named as the zoo names its vertices."""
    z = sizes(model)
    D, Dh = z["D"], z["Dh"]
    shapes = {"embed": {"W": (z["V"], D)}, "norm_f": {"g": (D,)},
              "head": {"W": (D, z["V"])}}
    for i in range(z["L"]):
        shapes[f"l{i}_norm1"] = {"g": (D,)}
        shapes[f"l{i}_norm2"] = {"g": (D,)}
        shapes[f"l{i}_attn"] = {
            "Wq": (D, z["H"] * Dh), "Wk": (D, z["KV"] * Dh),
            "Wv": (D, z["KV"] * Dh), "Wo": (z["H"] * Dh, D),
            "q_norm": (Dh,), "k_norm": (Dh,),
            "WqI": (D, z["HI"] * z["DI"]), "WkI": (D, z["DI"]),
            "Ww": (D, z["HI"])}
        shapes[f"l{i}_moe"] = {
            "Wr": (D, z["E"]), "Wg": (z["G"], D, z["F"]),
            "Wu": (z["G"], D, z["F"]), "Wd": (z["G"], z["F"], D)}
    return shapes


# ------------------------------------------------------------ the control
def fp8(a, dtype=jnp.float8_e4m3fn):
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / float(jnp.finfo(dtype).max)
    return (a / s).astype(dtype).astype(a.dtype) * s


def in_fp8(op):
    @jax.custom_vjp
    def f(x, w):
        return op(fp8(x), fp8(w))

    def fwd(x, w):
        qx, qw = fp8(x), fp8(w)
        return op(qx, qw), (qx, qw)

    def bwd(res, dy):
        return jax.vjp(op, *res)[1](fp8(dy, jnp.float8_e5m2))
    f.defvjp(fwd, bwd)
    return f


def linear(quant):
    dot = lambda x, w: jnp.dot(x, w, precision=HI)
    return in_fp8(dot) if quant else dot


# --------------------------------------------------------------- the model
def rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rotary(x, positions, z):
    """x [B, T, heads, Dh]; positions [B, T, 3]."""
    half = z["Dh"] // 2
    inv = z["theta"] ** (-(2.0 * jnp.arange(half, dtype=jnp.float32))
                         / z["Dh"])
    axis = jnp.asarray([a for a, n in enumerate(z["sections"])
                        for _ in range(n)])
    ang = positions.astype(jnp.float32)[..., axis] * inv       # [B, T, half]
    c, s = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], -1)


def index_and_select(qi, ki, w, start, z):
    """qi [B, C, HI, DI] (queries start..start+C), ki [B, T, DI], w
    [B, C, HI] -> (I [B, C, T], the selection [B, C, T])."""
    dots = jnp.einsum("bchd,bsd->bchs", qi, ki, precision=HI)
    I = jnp.sum(jax.nn.relu(dots) * w[..., None], 2)
    T = ki.shape[1]
    causal = jnp.arange(T)[None, :] <= (start + jnp.arange(qi.shape[1]))[:, None]
    masked = jnp.where(causal, lax.stop_gradient(I), -jnp.inf)
    if T > z["topk"]:
        kth = lax.top_k(masked, z["topk"])[0][..., -1:]
        return I, causal & (masked >= kth)
    return I, jnp.broadcast_to(causal, I.shape)


def attention_block(q, k, v, qi, ki, w, start, z):
    """One block of queries against every key. -> (o [B, C, H, Dh], the
    block's sum of KL, its count of selected pairs)."""
    I, sel = index_and_select(qi, ki, w, start, z)
    rep = z["H"] // z["KV"]
    kk, vv = jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2)       # head a -> a // rep
    s = jnp.einsum("bchd,bshd->bhcs", q, kk, precision=HI) / math.sqrt(z["Dh"])
    p = jax.nn.softmax(jnp.where(sel[:, None], s, NEG), -1)
    o = jnp.einsum("bhcs,bshd->bchd", p, vv, precision=HI)
    pbar = lax.stop_gradient(jnp.sum(p, 1))
    pbar = pbar / jnp.sum(pbar, -1, keepdims=True)
    logq = jax.nn.log_softmax(jnp.where(sel, I, NEG), -1)
    kl = jnp.where(sel, jax.scipy.special.xlogy(pbar, pbar) - pbar * logq, 0.0)
    return o, jnp.sum(kl), jnp.sum(sel, dtype=jnp.float32)


def attention(p, h, positions, z, lin):
    B, T, D = h.shape
    q = rms(lin(h, p["Wq"]).reshape(B, T, z["H"], z["Dh"]), p["q_norm"], z["eps"])
    k = rms(lin(h, p["Wk"]).reshape(B, T, z["KV"], z["Dh"]), p["k_norm"], z["eps"])
    v = lin(h, p["Wv"]).reshape(B, T, z["KV"], z["Dh"])
    q, k = rotary(q, positions, z), rotary(k, positions, z)
    hb = lax.stop_gradient(h)
    qi = lin(hb, p["WqI"]).reshape(B, T, z["HI"], z["DI"])
    ki, w = lin(hb, p["WkI"]), lin(hb, p["Ww"])
    C = min(Q_BLOCK, T)
    if T % C:
        raise ValueError(f"{T} positions are not whole blocks of {C}")
    blocks = lambda a: jnp.moveaxis(a.reshape((B, T // C, C) + a.shape[2:]), 1, 0)

    @jax.checkpoint
    def one(args):
        qb, qib, wb, start = args
        return attention_block(qb, k, v, qib, ki, wb, start, z)

    o, kl, n = lax.map(one, (blocks(q), blocks(qi), blocks(w),
                             jnp.arange(0, T, C)))
    o = jnp.moveaxis(o, 0, 1).reshape(B, T, z["H"] * z["Dh"])
    return lin(o, p["Wo"]), jnp.sum(kl) / (B * T), jnp.sum(n) / (B * T)


def route(p, u, z):
    r = jax.nn.softmax(jnp.dot(u, p["Wr"], precision=HI), -1)
    top, experts = lax.top_k(r, z["k"])
    return experts, top / jnp.sum(top, -1, keepdims=True)


def experts_part(p, u, z, lin):
    """The held experts' part of the layer's result for tokens u [N, D],
    and the routed pairs of each held expert."""
    experts, weight = route(p, u, z)
    y, counts = jnp.zeros_like(u), []

    @jax.checkpoint
    def one(u, wg, wu, wd, c):
        return lin(jax.nn.silu(lin(u, wg)) * lin(u, wu), wd) * c[:, None]

    for e in range(z["G"]):
        hit = experts == z["first"] + e
        y = y + one(u, p["Wg"][e], p["Wu"][e], p["Wd"][e],
                    jnp.sum(jnp.where(hit, weight, 0.0), -1))
        counts.append(jnp.sum(hit))
    return y, jnp.stack(counts)


def embed(params, batch):
    x = params["embed"]["W"][batch["ids"]]
    return lax.dynamic_update_slice_in_dim(
        x, batch["image"].astype(jnp.float32), 0, axis=1)


def hidden(params, batch, model, quant=False):
    """The final hidden state [B, T, D], each layer's L_I, selected keys a
    query and held experts' pairs."""
    z, lin = sizes(model), linear(quant)
    x = embed(params, batch)
    B, T, D = x.shape

    @jax.checkpoint
    def layer(x, pa, pm, g1, g2):
        a, l_i, n_sel = attention(pa, rms(x, g1, z["eps"]),
                                  batch["positions"], z, lin)
        x = x + a
        y, counts = experts_part(pm, rms(x, g2, z["eps"]).reshape(B * T, D),
                                 z, lin)
        return x + y.reshape(B, T, D), l_i, n_sel, counts

    aux = []
    for i in range(z["L"]):
        x, *a = layer(x, params[f"l{i}_attn"], params[f"l{i}_moe"],
                      params[f"l{i}_norm1"]["g"], params[f"l{i}_norm2"]["g"])
        aux.append(a)
    l_i, n_sel, counts = (jnp.stack(c) for c in zip(*aux))
    return rms(x, params["norm_f"]["g"], z["eps"]), l_i, n_sel, counts


def logits(params, batch, model, quant=False):
    h = hidden(params, batch, model, quant)[0]
    return linear(quant)(h, params["head"]["W"])


def loss(params, batch, model, quant=False):
    """(CE over the masked positions + sum of L_I, {"ce", "indexer_loss"
    [layers], "selected_keys" [layers], "held_pairs" [layers, held]})."""
    h, l_i, n_sel, counts = hidden(params, batch, model, quant)
    lin = linear(quant)
    B, T, D = h.shape
    C = min(HEAD_BLOCK, T)
    if T % C:
        raise ValueError(f"{T} positions are not whole blocks of {C}")
    blocks = lambda a: jnp.moveaxis(a.reshape((B, T // C, C) + a.shape[2:]), 1, 0)

    @jax.checkpoint
    def one(args):
        hb, yb, mb = args
        lg = lin(hb.reshape(B * C, D), params["head"]["W"]).reshape(B, C, -1)
        picked = jnp.take_along_axis(lg, yb[..., None], -1)[..., 0]
        return jnp.sum((jax.nn.logsumexp(lg, -1) - picked) * mb)

    mask = batch["mask"].astype(jnp.float32)
    ce = jnp.sum(lax.map(one, (blocks(h), blocks(batch["labels"]),
                               blocks(mask)))) / jnp.sum(mask)
    return ce + jnp.sum(l_i), {"ce": ce, "indexer_loss": l_i,
                               "selected_keys": n_sel, "held_pairs": counts}


def first_layer_selection(params, batch, model, start, count):
    """The selection [B, count, T] of queries start..start+count in the
    first layer (its input is the embedding alone)."""
    z, lin = sizes(model), linear(False)
    p = params["l0_attn"]
    h = rms(embed(params, batch), params["l0_norm1"]["g"], z["eps"])
    B, T, _ = h.shape
    hq = h[:, start:start + count]
    qi = lin(hq, p["WqI"]).reshape(B, count, z["HI"], z["DI"])
    return index_and_select(qi, lin(h, p["WkI"]), lin(hq, p["Ww"]), start, z)[1]


# ------------------------------------------------------------- the trainer
def leaf_norms(tree):
    """Euclidean norm of every leaf, in the fixed order of sorted names."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
        tree[n][k].astype(jnp.float32))))
        for n in sorted(tree) for k in sorted(tree[n])])


def adam(p, m, v, g, t, trainer):
    """The program's `adam` updater, as it is (nn/updater/updaters.py):
    no bias-corrected epsilon, no decay."""
    b1, b2 = trainer.get("beta1", 0.9), trainer.get("beta2", 0.999)
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    alpha = trainer["learning_rate"] * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
    return p - alpha * m / (jnp.sqrt(v) + trainer.get("epsilon", 1e-8)), m, v


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
