"""Plain reference for NVIDIA-Nemotron-3-Nano-30B-A3B (`nemotron_h`) in
training: forward, loss, gradients and Adam in straightforward jax.numpy,
float32, every product at "highest" precision. Imports nothing of the
program, no kernels. It follows the equations of ISSUE 38 /
`configs/nemotron-3-nano-30b-a3b.json` (`assumed` lists what the published
config leaves open). Every layer is ONE mixer: u the layer's input,
x = RMSNorm(u) at eps 1e-5, the layer returns u + mixer(x); its kind is the
layer's character of `hybrid_override_pattern`. No biases but the
convolution's and dt_bias; softmax, router, decays and state in float32.

  h = Embed(ids), unscaled                           positions 0 .. T-1
  `M`, the Mamba-2 mixer (Dao and Gu, arXiv:2405.21060 sections 5-7; the
  keys `mamba_num_heads` H = 64, `mamba_head_dim` P = 64, `n_groups` G = 8,
  `ssm_state_size` N = 128, `conv_kernel` 4):
    [z (4096) ; xBC (6144) ; dt (64)] = x W_in       in that order
    xBC_t <- SiLU(b_c + sum_{k=0..3} w_c[:, k] xBC_{t-3+k})   per channel,
      causal, zeros before position 0
    [xs (4096) ; B (8 x 128) ; C (8 x 128)] = xBC;  xs as [H, P]
    dt_{t,h} = softplus(dt_{t,h} + dt_bias_h);  A_h = -exp(A_log_h)
    a_{t,h} = exp(dt_{t,h} A_h);  g(h) = h // 8 the group of head h
    S_{t,h} = a_{t,h} S_{t-1,h} + dt_{t,h} xs_{t,h} (outer) B_{t,g(h)},
      S_{-1} = 0;  y_{t,h} = S_{t,h} C_{t,g(h)} + D_h xs_{t,h}
      THE RECURRENCE POSITION BY POSITION (a `lax.scan` over t), not the
      chunked form the program runs: the chunk algebra is held to the
      definition
    y <- y * SiLU(z) (the gate BEFORE the norm: `norm_before_gate` false);
      over each of the 8 groups of 512 channels
      y <- w_n * y / sqrt(mean(y^2 over the group) + 1e-5)
    out = y W_out                                    (4096 -> 2688)
  `E`, the expert layer (`NemotronHMOE`; routing as DeepSeek-V3,
  arXiv:2412.19437 section 2.1.2, one group):
    s = sigmoid(x W_r) [128]; chosen = top 6 of s + b;
    g_e = 2.5 s_e / sum over the 6 chosen of s (held here or not)
    out = sum over chosen AND held of g_e W_d,e relu(W_u,e x)^2
          + S_d relu(S_u x)^2        (`mlp_hidden_act` relu2: two matrices
          an expert, no gate; widths 1856 and 3712; the shared one unscaled)
    b [128] is state, not a parameter: no gradient; after every step
    b <- b + gamma sign(mean(c) - c), c this chip's own tokens' pairs
  `*`, grouped-query attention: q = x W_q as 32 heads of 128, k, v = x W_k,
    x W_v as 2 heads of 128, query head h reads key/value head h // 16;
    s(t, j) = q_t . k_j / sqrt(128) for j <= t, softmax, o = sum p v;
    out = [o_1 .. o_32] W_o. NO rotary turn and NO gate: Nemotron-H has no
    position embeddings (arXiv:2504.03624 section 2).
  logits = RMSNorm_f(h) W_head over the vocabulary slice
  loss = mean over the masked positions of the next token's cross-entropy

Memory, not results: every layer is rematerialised; a Mamba mixer runs two
of its 8 groups at a time, each pair rematerialised (a group's 8 heads read
only their own columns of W_in, channels of the convolution and rows of
W_out, and the gated norm is over a group's own 512 channels: the mixer is
a sum over groups, as attention is a sum over key/value heads); the
recurrence runs a block of 128 positions at a time, each block
rematerialised, so that its backward keeps a state a block and a state a
position of ONE block;
attention runs one key/value head's group of query heads at a time in
blocks of 256 queries (16 heads' scores of a block are 268 MB); the held experts one at a time, every token
through each, times its weight or 0; the head 2,048 positions at a time.

`quant` is the control (`references/keye_vl.py linear`): every product
with a weight matrix (W_in, W_out, attention's four, the experts', the
shared expert's, the head; not the router, not the attention's own two
products, not the recurrence) as an fp8 trainer computes it. `fault`
plants one of two faults the control does not reach: "norm_all_channels"
norms the gated y over all 4096 channels in place of 8 groups of 512,
"conv_one_late" reads the convolution's window one position late (taps
t-4 .. t-1).
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

# what is the same in every decoder's reference: the fp8 control's products
# (`linear(quant)`), RMSNorm, the leaf norms and the program's Adam
from .keye_vl import HI, NEG, adam, leaf_norms, linear, rms

Q_BLOCK, HEAD_BLOCK, SCAN_BLOCK, GROUPS_AT_ONCE = 256, 2048, 128, 2
SCALARS = ("A_log", "D", "b_c", "dt_bias", "w_c", "w_n")   # a Mamba layer's
#                  leaves that are no projection: compared as a group too


def sizes(model):
    dep = model["deployment"]
    pattern = model["hybrid_override_pattern"]
    return dict(
        D=model["hidden_size"], H=model["mamba_num_heads"],
        P=model["mamba_head_dim"], N=model["ssm_state_size"],
        Gs=model["n_groups"], K=model["conv_kernel"],
        QH=model["num_attention_heads"], KV=model["num_key_value_heads"],
        Dh=model["head_dim"], F=model["moe_intermediate_size"],
        S=model["moe_shared_expert_intermediate_size"],
        V=model["vocab_size"], E=dep["router_width"],
        G=model["n_routed_experts"], first=dep["first_held"],
        k=model["num_experts_per_tok"], scale=model["routed_scaling_factor"],
        eps=model["layer_norm_epsilon"], gamma=model["bias_update_rate"],
        layers=dep.get("layers", list(range(len(pattern)))), pattern=pattern)


def layer_names(z):
    """(vertex prefix, kind) of every layer run here: layer n of the kept
    ones is published layer `layers[n]`, its kind the n-th character."""
    return [(f"l{i}", z["pattern"][n]) for n, i in enumerate(z["layers"])]


def sparse_names(z):
    return [at + "_mixer" for at, kind in layer_names(z) if kind == "E"]


def mixer_shapes(z, kind):
    D, inner = z["D"], z["H"] * z["P"]
    if kind == "M":
        conv = inner + 2 * z["Gs"] * z["N"]
        return {"W_in": (D, inner + conv + z["H"]), "W_out": (inner, D),
                "w_c": (conv, z["K"]), "b_c": (conv,), "dt_bias": (z["H"],),
                "A_log": (z["H"],), "D": (z["H"],), "w_n": (inner,)}
    if kind == "E":
        return {"Wr": (D, z["E"]), "Wu": (z["G"], D, z["F"]),
                "Wd": (z["G"], z["F"], D), "Su": (D, z["S"]),
                "Sd": (z["S"], D)}
    return {"Wq": (D, z["QH"] * z["Dh"]), "Wk": (D, z["KV"] * z["Dh"]),
            "Wv": (D, z["KV"] * z["Dh"]), "Wo": (z["QH"] * z["Dh"], D)}


def param_shapes(model):
    """{vertex: {leaf: shape}}, named as the zoo names its vertices."""
    z = sizes(model)
    shapes = {"embed": {"W": (z["V"], z["D"])}, "norm_f": {"g": (z["D"],)},
              "head": {"W": (z["D"], z["V"])}}
    for at, kind in layer_names(z):
        shapes[f"{at}_norm"] = {"g": (z["D"],)}
        shapes[f"{at}_mixer"] = mixer_shapes(z, kind)
    return shapes


def zero_bias(model):
    """{sparse vertex: b [router width]} as every layer starts."""
    z = sizes(model)
    return {n: jnp.zeros((z["E"],), jnp.float32) for n in sparse_names(z)}


# ------------------------------------------------------- the Mamba-2 mixer
def causal_conv(x, w, b, late=0):
    """x [T, C], w [C, K], b [C]: y_t = b + sum_k w[:, k] x_{t-K+1+k-late},
    zeros before position 0."""
    T, K = x.shape[0], w.shape[1]
    padded = jnp.pad(x, ((K - 1 + late, 0), (0, 0)))
    return b + sum(padded[k:k + T] * w[:, k] for k in range(K))


def ssm_recurrence(xs, dt, A, B, C):
    """One sequence, position by position. xs [T, H, P]; dt [T, H] (after
    the softplus); A [H]; B, C [T, G, N], head h reading group h // (H / G).
    Returns (y [T, H, P] without the D skip, the last state [H, P, N])."""
    T, H, P = xs.shape
    G, N = B.shape[1:]
    rep = H // G
    block = min(SCAN_BLOCK, T)
    if T % block:
        raise ValueError(f"{T} positions are not whole blocks of {block}")

    def position(S, e):
        x_t, dt_t, b_t, c_t = e
        b_h, c_h = jnp.repeat(b_t, rep, 0), jnp.repeat(c_t, rep, 0)   # [H, N]
        S = jnp.exp(dt_t * A)[:, None, None] * S \
            + (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :]
        return S, jnp.sum(S * c_h[:, None, :], -1)

    run = jax.checkpoint(lambda S, es: lax.scan(position, S, es))
    blocks = jax.tree.map(
        lambda a: a.reshape((T // block, block) + a.shape[1:]),
        (xs, dt, B, C))
    last, y = lax.scan(run, jnp.zeros((H, P, N), xs.dtype), blocks)
    return y.reshape(T, H, P), last


def by_group_set(p, z):
    """The mixer's leaves cut into sets of `GROUPS_AT_ONCE` groups, stacked
    on a leading axis: a group's heads read only their own columns of W_in
    (their z, xs, B, C and dt), channels of the convolution, entries of
    dt_bias, A_log, D and w_n and rows of W_out, and the gated norm is over
    a group's own channels, so the mixer is a sum over groups."""
    H, P, G, N = z["H"], z["P"], z["Gs"], z["N"]
    inner, rep, per = H * P, H // G, min(GROUPS_AT_ONCE, G)
    sets = jnp.arange(G).reshape(G // per, per)

    def cols(start, width):         # [sets, per * width] column indices
        return (start + sets[:, :, None] * width
                + jnp.arange(width)).reshape(sets.shape[0], -1)

    xs, heads = cols(0, rep * P), cols(0, rep)
    conv = jnp.concatenate([xs, cols(inner, N), cols(inner + G * N, N)], -1)
    w_in = jnp.concatenate([xs, inner + conv, 2 * inner + 2 * G * N + heads],
                           -1)
    return {"W_in": jnp.moveaxis(p["W_in"][:, w_in], 1, 0),
            "w_c": p["w_c"][conv], "b_c": p["b_c"][conv],
            "dt_bias": p["dt_bias"][heads], "A_log": p["A_log"][heads],
            "D": p["D"][heads], "w_n": p["w_n"][xs], "W_out": p["W_out"][xs]}


def group_set_mixer(a, w, z, lin, late, scale=None):
    """One set of groups over one sequence a [T, D] (normed), `w` its
    leaves: (its part of the layer's output [T, D]; the sum of its time
    steps, its smallest log decay, the sum of squares of its last state;
    the sum over its channels of the gated y squared [T]). `scale` [T, 1]
    replaces the groups' own rsqrt(mean square) (the planted fault)."""
    P, N = z["P"], z["N"]
    T, h = a.shape[0], w["A_log"].shape[0]
    g = h // (z["H"] // z["Gs"])
    inner = h * P
    zg, xbc, dt = jnp.split(lin(a, w["W_in"]), [inner, 2 * inner + 2 * g * N],
                            -1)
    xbc = jax.nn.silu(causal_conv(xbc, w["w_c"], w["b_c"], late))
    xs, Bm, Cm = jnp.split(xbc, [inner, inner + g * N], -1)
    xs = xs.reshape(T, h, P)
    dt = jax.nn.softplus(dt + w["dt_bias"])
    A = -jnp.exp(w["A_log"])
    y, last = ssm_recurrence(xs, dt, A, Bm.reshape(T, g, N),
                             Cm.reshape(T, g, N))
    y = (y + w["D"][:, None] * xs).reshape(T, inner) * jax.nn.silu(zg)
    by = y.reshape(T, g, -1)
    if scale is None:
        scale = lax.rsqrt(jnp.mean(by * by, -1, keepdims=True) + z["eps"])
    else:
        scale = scale[:, :, None]
    out = lin((by * scale).reshape(T, inner) * w["w_n"], w["W_out"])
    return out, (jnp.sum(dt), jnp.min(dt * A), jnp.sum(last * last)), \
        jnp.sum(y * y, -1)


def mamba_mixer(p, x, z, lin, fault=None):
    """x [B, T, D] (normed) -> ([B, T, D], {"dt_mean", "decay_min",
    "state_rms"}): the sum over the sets of groups, each rematerialised."""
    H, P, N = z["H"], z["P"], z["N"]
    late = int(fault == "conv_one_late")
    sets = by_group_set(p, z)

    def row(x_row):
        T = x_row.shape[0]
        scale = None
        if fault == "norm_all_channels":    # one mean square for all groups
            square = jax.checkpoint(lambda w: group_set_mixer(
                x_row, w, z, lin, late)[2])
            total = lax.scan(lambda t, w: (t + square(w), None),
                             jnp.zeros((T,), x_row.dtype), sets)[0]
            scale = lax.rsqrt(total / (H * P) + z["eps"])[:, None]
        one = jax.checkpoint(lambda w: group_set_mixer(
            x_row, w, z, lin, late, scale)[:2])

        def add(carry, w):
            out, (dt, decay, last) = one(w)
            return (carry[0] + out, carry[1] + dt,
                    jnp.minimum(carry[2], decay), carry[3] + last), None

        zero = jnp.zeros((), x_row.dtype)
        return lax.scan(add, (jnp.zeros_like(x_row), zero, zero, zero),
                        sets)[0]

    out, dt, decay, last = (jnp.stack(a) for a in zip(
        *(row(x[b]) for b in range(x.shape[0]))))
    rows, T = x.shape[:2]
    return out, {"dt_mean": jnp.sum(dt) / (rows * T * H),
                 "decay_min": jnp.exp(jnp.min(decay)),
                 "state_rms": jnp.sqrt(jnp.sum(last) / (rows * H * P * N))}


# -------------------------------------------------------------- attention
def group_attention(a, wq, wk, wv, wo, Dh, lin):
    """One key/value head and its R query heads over one sequence a [T, D]:
    their outputs through their rows of Wo, [T, D]. No positions."""
    T = a.shape[0]
    R = wq.shape[1] // Dh
    q, k, v = lin(a, wq).reshape(T, R, Dh), lin(a, wk), lin(a, wv)
    C = min(Q_BLOCK, T)
    if T % C:
        raise ValueError(f"{T} positions are not whole blocks of {C}")

    @jax.checkpoint
    def block(args):
        qb, start = args
        s = jnp.einsum("crd,sd->rcs", qb, k, precision=HI) / math.sqrt(Dh)
        seen = (start + jnp.arange(C))[:, None] >= jnp.arange(T)[None, :]
        p = jax.nn.softmax(jnp.where(seen[None], s, NEG), -1)
        return jnp.einsum("rcs,sd->crd", p, v, precision=HI)

    o = lax.map(block, (q.reshape(T // C, C, R, Dh), jnp.arange(0, T, C)))
    return lin(o.reshape(T, R * Dh), wo)


def attention(p, a, z, lin):
    """a [B, T, D] (normed) -> [B, T, D]: the sum over the key/value heads
    of each one's group of query heads."""
    D, KV, Dh = z["D"], z["KV"], z["Dh"]
    by_group = lambda w: jnp.moveaxis(w.reshape(D, KV, -1), 1, 0)
    groups = (by_group(p["Wq"]), by_group(p["Wk"]), by_group(p["Wv"]),
              p["Wo"].reshape(KV, -1, D))

    def row(a_row):
        one = jax.checkpoint(lambda g: group_attention(
            a_row, g[0], g[1], g[2], g[3], Dh, lin))
        return jnp.sum(lax.map(one, groups), 0)

    return jnp.stack([row(a[b]) for b in range(a.shape[0])])


# ------------------------------------------------------------ expert layer
def relu2_mlp(u, wu, wd, lin):
    return lin(jnp.square(jax.nn.relu(lin(u, wu))), wd)


def route(p, u, z, bias):
    """(chosen experts [N, k], their weights): chosen by s + b, weighted by
    s alone, over the 6 chosen whether held here or not."""
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
    ids = first + jnp.arange(p["Wu"].shape[0])
    hit = experts[None] == ids[:, None, None]                  # [G, N, k]
    share = jnp.sum(jnp.where(hit, weight[None], 0.0), -1)     # [G, N]
    one = jax.checkpoint(lambda wu, wd, c: relu2_mlp(u, wu, wd, lin)
                         * c[:, None])
    y, _ = lax.scan(lambda y, e: (y + one(*e), None), jnp.zeros_like(u),
                    (p["Wu"], p["Wd"], share))
    every = jnp.sum(jax.nn.one_hot(experts, z["E"], dtype=jnp.float32),
                    (0, 1))
    return y, jnp.sum(hit, (1, 2)), every


def experts_part(p, u, z, lin, bias):
    """Routed experts held here, and the shared expert."""
    y, held, every = routed_part(p, u, z, lin, bias)
    return y + relu2_mlp(u, p["Su"], p["Sd"], lin), held, every


# ---------------------------------------------------------------- the model
def hidden(params, batch, model, quant=False, bias=None, fault=None):
    """(the final hidden state [B, T, D], the held experts' pairs of every
    expert layer [expert layers, held], all experts' pairs [expert layers,
    router width], what each Mamba layer said)."""
    z, lin = sizes(model), linear(quant)
    bias = zero_bias(model) if bias is None else bias
    x = params["embed"]["W"][batch["ids"]]
    B, T, D = x.shape
    held, every, said = [], [], []
    for at, kind in layer_names(z):

        @jax.checkpoint
        def layer(x, p, g, b, kind=kind):
            a = rms(x, g, z["eps"])
            if kind == "M":
                y, more = mamba_mixer(p, a, z, lin, fault)
            elif kind == "E":
                y, *more = experts_part(p, a.reshape(B * T, D), z, lin, b)
                y = y.reshape(B, T, D)
            else:
                y, more = attention(p, a, z, lin), None
            return x + y, more

        x, more = layer(x, params[f"{at}_mixer"], params[f"{at}_norm"]["g"],
                        bias.get(f"{at}_mixer"))
        if kind == "M":
            said.append(more)
        elif kind == "E":
            held.append(more[0])
            every.append(more[1])
    return (rms(x, params["norm_f"]["g"], z["eps"]), jnp.stack(held),
            jnp.stack(every), said)


def loss(params, batch, model, quant=False, bias=None, fault=None):
    """(mean cross-entropy over the masked positions, {"held_pairs"
    [expert layers, held], "all_pairs" [expert layers, router width],
    "ssm": what each Mamba layer said})."""
    h, held, every, said = hidden(params, batch, model, quant, bias, fault)
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
    return ce, {"held_pairs": held, "all_pairs": every,
                "ssm": lax.stop_gradient(said)}


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
    held pairs and what its Mamba layers said, the bias after the last step
    [expert layers, router width]."""
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
    losses, g1, first = [], None, None
    for i, batch in enumerate(batches):
        p, m, v, bias, l, aux, g = step(p, m, v, bias, batch, float(i + 1))
        losses.append(l)
        if i == 0:
            g1, first = g, aux
    del m, v
    start = remake()
    change = jax.jit(lambda a, b: leaf_norms(
        jax.tree.map(lambda c, d: c - d, a, b)))(p, start)
    names = sparse_names(sizes(model))
    return jnp.stack(losses), g1, change, {
        "held_pairs": first["held_pairs"], "ssm": first["ssm"],
        "bias": jnp.stack([bias[n] for n in names])}
