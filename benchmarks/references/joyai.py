"""Plain reference for JoyAI-LLM-Flash in training: forward, both losses,
gradients and Adam in straightforward jax.numpy, float32, every product at
"highest" precision. Imports nothing of the program, no kernels. It follows
the equations of ISSUE 34 / `configs/joyai-llm-flash.json` (`assumed` lists
what the published config leaves open); x is a layer's RMS-normed input, no
biases, the softmax and the router in float32:

  h = Embed(ids)                                     positions 0 .. T-1
  per layer (DeepSeek-V2, arXiv:2405.04434 section 2.1, as `q_lora_rank` /
  `kv_lora_rank` / `qk_nope_head_dim` / `qk_rope_head_dim` / `v_head_dim`
  name it; 32 heads):
    c_q = RMSNorm(x Wq_a) [1536]
    [q_nope_h (128) ; q_rope_h (64)] = (c_q Wq_b)_h
    [c_kv (512) ; k_r (64)] = x Wkv_a;  c_kv <- RMSNorm(c_kv)
    [k_nope_h (128) ; v_h (128)] = (c_kv Wkv_b)_h
    q_rope_h <- RoPE(q_rope_h), k_rope = RoPE(k_r): ONE key for all heads,
      theta 32e6, slots (2i, 2i + 1) turned as a pair by the angle
      position * theta^(-2i / 64) (`rope_interleave`), no scaling
    s_h(t, j) = (q_nope_h(t) . k_nope_h(j) + q_rope_h(t) . k_rope(j))
                / sqrt(192) for j <= t;  o_h = softmax_j(s_h) v_h
    h += [o_1 .. o_32] Wo                            (4096 -> 2048)
    m = RMSNorm(h)
    dense layer (the first): h += (SiLU(m Wg) * (m Wu)) Wd
    sparse layer (DeepSeek-V3, arXiv:2412.19437 section 2.1.2: `sigmoid`,
    `noaux_tc`, one group): s = sigmoid(m Wr) [256]; chosen = top 8 of
      s + b; g_e = 2.5 s_e / sum over the 8 chosen of s (held here or not);
      h += sum over chosen AND held of g_e SwiGLU_e(m) + SwiGLU_shared(m)
      b [256] is state, not a parameter: no gradient; after every step
      b <- b + gamma sign(mean(c) - c), c the step's pairs by expert over
      all 256, of this chip's own tokens
  logits = RMSNorm(h) Whead over the vocabulary slice
  prediction module (DeepSeek-V3 section 2.2, depth 1), h the main model's
  output AFTER norm_f, Emb and Head the main model's own:
    u_i = [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(h_i)] W_eh     (4096 -> 2048)
    u' = one sparse decoder layer as above over u at positions 0 .. T-1
    logits'_i = Head(RMSNorm(u'_i))
  L = L_main + lambda L_mtp: L_main the mean cross-entropy of position i
  against t_{i+1} over the positions `mask` keeps (i <= T-2), L_mtp of
  logits'_i against t_{i+2} over `mask2` (i <= T-3).

Memory, not results: every layer is rematerialised; attention runs one
head at a time (its two chains' per-head columns, its rows of Wo; the
heads' parts of the output added up as they come) and inside it a block of
1,024 queries at a time against every key, each block rematerialised; the
held experts one at a time, every token through each, times its weight or
0 (plain, and 16 times the program's work); each pass of the head 2,048
positions at a time.

`quant` is the control (`references/keye_vl.py linear`): every product with
a weight matrix (both chains, Wo, the dense MLP, experts, shared expert,
W_eh, both passes of the head; not the router, not the attention's own two
products) as an fp8 trainer computes it: operands in e4m3 forward, the
incoming gradient in e5m2 backward, per-tensor scales. fp8 is the nearest
precision below the bfloat16 the configuration states. `faults` plants one
of two faults the control does not reach: "mtp_labels_by_one" scores the
module against t_{i+1}, "scale_128" divides the scores by sqrt(128).
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
MTP = "mtp"             # the module's decoder layer, named as the zoo does


def sizes(model):
    dep = model["deployment"]
    return dict(
        D=model["hidden_size"], H=model["num_attention_heads"],
        rq=model["q_lora_rank"], rkv=model["kv_lora_rank"],
        dn=model["qk_nope_head_dim"], dr=model["qk_rope_head_dim"],
        dv=model["v_head_dim"], theta=float(model["rope_theta"]),
        I=model["intermediate_size"], F=model["moe_intermediate_size"],
        S=model["moe_intermediate_size"] * model["n_shared_experts"],
        V=model["vocab_size"], E=dep["router_width"],
        G=model["n_routed_experts"], first=dep["first_held"],
        k=model["num_experts_per_tok"], scale=model["routed_scaling_factor"],
        eps=model["rms_norm_eps"], dense=model["first_k_dense_replace"],
        layers=dep.get("layers", list(range(model["num_hidden_layers"]))),
        mtp=model["num_nextn_predict_layers"],
        gamma=model["bias_update_rate"], lam=model["mtp_loss_weight"])


def layer_names(z):
    """(vertex prefix, dense?) of every decoder layer run here, the
    module's last."""
    return [(f"l{i}", i < z["dense"]) for i in z["layers"]] \
        + [(MTP, False)] * z["mtp"]


def sparse_names(z):
    return [at + "_mlp" for at, dense in layer_names(z) if not dense]


def param_shapes(model):
    """{vertex: {leaf: shape}}, named as the zoo names its vertices. The
    module's embedding and head are the main model's: no leaf of their
    own."""
    z = sizes(model)
    D, H = z["D"], z["H"]
    shapes = {"embed": {"W": (z["V"], D)}, "norm_f": {"g": (D,)},
              "head": {"W": (D, z["V"])}}
    for at, dense in layer_names(z):
        shapes[f"{at}_norm1"] = {"g": (D,)}
        shapes[f"{at}_norm2"] = {"g": (D,)}
        shapes[f"{at}_attn"] = {
            "Wq_a": (D, z["rq"]), "q_norm": (z["rq"],),
            "Wq_b": (z["rq"], H * (z["dn"] + z["dr"])),
            "Wkv_a": (D, z["rkv"] + z["dr"]), "kv_norm": (z["rkv"],),
            "Wkv_b": (z["rkv"], H * (z["dn"] + z["dv"])),
            "Wo": (H * z["dv"], D)}
        shapes[f"{at}_mlp"] = (
            {"Wg": (D, z["I"]), "Wu": (D, z["I"]), "Wd": (z["I"], D)}
            if dense else
            {"Wr": (D, z["E"]), "Wg": (z["G"], D, z["F"]),
             "Wu": (z["G"], D, z["F"]), "Wd": (z["G"], z["F"], D),
             "Sg": (D, z["S"]), "Su": (D, z["S"]), "Sd": (z["S"], D)})
    if z["mtp"]:
        shapes.update({"mtp_enorm": {"g": (D,)}, "mtp_hnorm": {"g": (D,)},
                       "mtp_proj": {"W": (2 * D, D)},
                       "mtp_norm": {"g": (D,)}})
    return shapes


def zero_bias(model):
    """{sparse vertex: b [router width]} as every layer starts."""
    z = sizes(model)
    return {n: jnp.zeros((z["E"],), jnp.float32) for n in sparse_names(z)}


# --------------------------------------------------------------- the model
def rotary(x, theta):
    """x [T, heads, n] at positions 0 .. T-1: slots (2i, 2i + 1) turned as
    a pair by position * theta^(-2i / n)."""
    T, n = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * c - b * s, b * c + a * s], -1).reshape(x.shape)


def head_attention(c_q, c_kv, k_rope, wq, wkv, wo, z, lin, scale):
    """One head over one sequence: c_q [T, rq], c_kv [T, rkv] (normed),
    k_rope [T, dr] (turned), the head's columns of Wq_b and Wkv_b and its
    rows of Wo -> its part of the layer's output [T, D]."""
    T = c_q.shape[0]
    q, kv = lin(c_q, wq), lin(c_kv, wkv)
    q_nope = q[:, :z["dn"]]
    q_rope = rotary(q[:, None, z["dn"]:], z["theta"])[:, 0]
    k_nope, v = kv[:, :z["dn"]], kv[:, z["dn"]:]
    C = min(Q_BLOCK, T)
    if T % C:
        raise ValueError(f"{T} positions are not whole blocks of {C}")

    @jax.checkpoint
    def block(args):
        qn, qr, start = args
        s = (jnp.dot(qn, k_nope.T, precision=HI)
             + jnp.dot(qr, k_rope.T, precision=HI)) * scale
        seen = (start + jnp.arange(C))[:, None] >= jnp.arange(T)[None, :]
        return jnp.dot(jax.nn.softmax(jnp.where(seen, s, NEG), -1), v,
                       precision=HI)

    o = lax.map(block, (q_nope.reshape(T // C, C, -1),
                        q_rope.reshape(T // C, C, -1), jnp.arange(0, T, C)))
    return lin(o.reshape(T, -1), wo)


def attention(p, x, z, lin, scale):
    """x [B, T, D] (normed) -> [B, T, D]: the sum over the heads."""
    H, dn, dr, dv = z["H"], z["dn"], z["dr"], z["dv"]
    by_head = lambda w, width: jnp.moveaxis(
        w.reshape(w.shape[0], H, width), 1, 0)
    heads = (by_head(p["Wq_b"], dn + dr), by_head(p["Wkv_b"], dn + dv),
             p["Wo"].reshape(H, dv, -1))

    def row(x_row):
        c_q = rms(lin(x_row, p["Wq_a"]), p["q_norm"], z["eps"])
        kv_a = lin(x_row, p["Wkv_a"])
        c_kv = rms(kv_a[:, :z["rkv"]], p["kv_norm"], z["eps"])
        k_rope = rotary(kv_a[:, None, z["rkv"]:], z["theta"])[:, 0]
        one = jax.checkpoint(lambda w: head_attention(
            c_q, c_kv, k_rope, w[0], w[1], w[2], z, lin, scale))
        # summed as they come: 32 heads' [T, D] parts side by side are 2 GB
        return lax.scan(lambda y, w: (y + one(w), None),
                        jnp.zeros_like(x_row), heads)[0]

    return jnp.stack([row(x[b]) for b in range(x.shape[0])])


def gated_mlp(u, wg, wu, wd, lin):
    return lin(jax.nn.silu(lin(u, wg)) * lin(u, wu), wd)


def route(p, u, z, bias):
    """(chosen experts [N, k], their weights): chosen by s + b, weighted by
    s alone, over the 8 chosen whether held here or not."""
    s = jax.nn.sigmoid(jnp.dot(u, p["Wr"], precision=HI))
    experts = lax.top_k(lax.stop_gradient(s) + bias, z["k"])[1]
    top = jnp.take_along_axis(s, experts, -1)
    return experts, top / jnp.sum(top, -1, keepdims=True) * z["scale"]


def routed_part(p, u, z, lin, bias, first=None):
    """The part of the layer's result that the experts whose matrices `p`
    holds, `first` (this chip's by default) and those after it, give for
    tokens u [N, D]; each one's routed pairs; the pairs of ALL experts."""
    first = z["first"] if first is None else first
    experts, weight = route(p, u, z, bias)
    ids = first + jnp.arange(p["Wg"].shape[0])
    hit = experts[None] == ids[:, None, None]                  # [G, N, k]
    share = jnp.sum(jnp.where(hit, weight[None], 0.0), -1)     # [G, N]
    one = jax.checkpoint(lambda wg, wu, wd, c: gated_mlp(u, wg, wu, wd, lin)
                         * c[:, None])

    def add(y, e):
        return y + one(*e), None

    y, _ = lax.scan(add, jnp.zeros_like(u),
                    (p["Wg"], p["Wu"], p["Wd"], share))
    every = jnp.sum(jax.nn.one_hot(experts, z["E"], dtype=jnp.float32),
                    (0, 1))
    return y, jnp.sum(hit, (1, 2)), every


def experts_part(p, u, z, lin, bias):
    """Routed experts held here, and the shared expert."""
    y, held, every = routed_part(p, u, z, lin, bias)
    return y + gated_mlp(u, p["Sg"], p["Su"], p["Sd"], lin), held, every


def decoder_layer(params, at, dense, x, z, lin, bias, scale):
    """x [B, T, D] -> (x', (held pairs, all pairs) or None)."""
    B, T, D = x.shape

    @jax.checkpoint
    def layer(x, pa, pm, g1, g2, b):
        x = x + attention(pa, rms(x, g1, z["eps"]), z, lin, scale)
        u = rms(x, g2, z["eps"]).reshape(B * T, D)
        if dense:
            return x + gated_mlp(u, pm["Wg"], pm["Wu"], pm["Wd"],
                                 lin).reshape(B, T, D), None
        y, held, every = experts_part(pm, u, z, lin, b)
        return x + y.reshape(B, T, D), (held, every)

    return layer(x, params[f"{at}_attn"], params[f"{at}_mlp"],
                 params[f"{at}_norm1"]["g"], params[f"{at}_norm2"]["g"],
                 None if dense else bias[f"{at}_mlp"])


def head_loss(params, h, labels, mask, lin):
    """Mean over the masked positions of the cross-entropy of Head(h)."""
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

    mask = mask.astype(jnp.float32)
    return jnp.sum(lax.map(one, (blocks(h), blocks(labels),
                                 blocks(mask)))) / jnp.sum(mask)


def loss(params, batch, model, quant=False, bias=None, fault=None):
    """(L_main + lambda L_mtp, {"loss_main", "loss_mtp", "held_pairs"
    [sparse layers, held], "all_pairs" [sparse layers, router width]}).
    batch: ids, next_ids (the token after each), labels and mask (the main
    head's), labels2 and mask2 (the module's). `bias` {sparse vertex: b}
    (zero where None)."""
    z, lin = sizes(model), linear(quant)
    bias = zero_bias(model) if bias is None else bias
    scale = 1.0 / math.sqrt(z["dn"] if fault == "scale_128"
                            else z["dn"] + z["dr"])
    x = params["embed"]["W"][batch["ids"]]
    counts = []
    for at, dense in layer_names(z):
        if at == MTP:
            h = rms(x, params["norm_f"]["g"], z["eps"])
            e = params["embed"]["W"][batch["next_ids"]]
            x = lin(jnp.concatenate(
                [rms(e, params["mtp_enorm"]["g"], z["eps"]),
                 rms(h, params["mtp_hnorm"]["g"], z["eps"])], -1),
                params["mtp_proj"]["W"])
        x, c = decoder_layer(params, at, dense, x, z, lin, bias, scale)
        if c is not None:
            counts.append(c)
    if not z["mtp"]:
        h = rms(x, params["norm_f"]["g"], z["eps"])
    l_main = head_loss(params, h, batch["labels"], batch["mask"], lin)
    l_mtp = 0.0
    if z["mtp"]:
        labels2 = batch["labels"] if fault == "mtp_labels_by_one" \
            else batch["labels2"]
        l_mtp = head_loss(params, rms(x, params["mtp_norm"]["g"], z["eps"]),
                          labels2, batch["mask2"], lin)
    return l_main + z["lam"] * l_mtp, {
        "loss_main": l_main, "loss_mtp": l_mtp,
        "held_pairs": jnp.stack([c[0] for c in counts]),
        "all_pairs": jnp.stack([c[1] for c in counts])}


def next_bias(bias, all_pairs, model):
    """b + gamma sign(mean(c) - c), layer by layer."""
    z = sizes(model)
    return {n: bias[n] + z["gamma"] * jnp.sign(jnp.mean(c) - c)
            for n, c in zip(sparse_names(z), all_pairs)}


# ------------------------------------------------------------- the trainer
def train_steps(params, batches, model, trainer, quant=False, remake=None,
                fault=None):
    """Follow the first len(batches) steps from `params`, which are DONATED
    to the first step (the reference's own Adam state fills the chip);
    `remake()` returns them again for the change. Returns each step's loss
    (before its update), the per-leaf norms of the first gradient and of
    the parameters' change after the last step, and aux: the first step's
    held pairs, every step's two losses [steps, 2], the bias after the last
    step [sparse layers, router width]."""
    hp = {k: float(v) for k, v in trainer.items()
          if k in ("learning_rate", "beta1", "beta2", "epsilon")}

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def step(p, m, v, bias, batch, t):
        (l, aux), g = jax.value_and_grad(loss, has_aux=True)(
            p, batch, model, quant, bias, fault)
        out = jax.tree.map(lambda a, b, c, d: adam(a, b, c, d, t, hp),
                           p, m, v, g)
        pick = lambda i: jax.tree.map(lambda _, o: o[i], p, out)
        return (pick(0), pick(1), pick(2),
                next_bias(bias, aux["all_pairs"], model), l, aux,
                leaf_norms(g))

    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
    p, m, v, bias = params, zeros(params), zeros(params), zero_bias(model)
    del params
    losses, parts, g1, held = [], [], None, None
    for i, batch in enumerate(batches):
        p, m, v, bias, l, aux, g = step(p, m, v, bias, batch, float(i + 1))
        losses.append(l)
        parts.append(jnp.stack([aux["loss_main"], aux["loss_mtp"]]))
        if i == 0:
            g1, held = g, aux["held_pairs"]
    del m, v
    start = remake()
    change = jax.jit(lambda a, b: leaf_norms(
        jax.tree.map(lambda c, d: c - d, a, b)))(p, start)
    names = sparse_names(sizes(model))
    return jnp.stack(losses), g1, change, {
        "held_pairs": held, "loss_parts": jnp.stack(parts),
        "bias": jnp.stack([bias[n] for n in names])}
