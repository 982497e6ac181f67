"""Operations and bytes the ALGORITHM of a decoder with multi-head latent
attention, a sigmoid router over small experts beside a shared one and a
multi-token prediction module needs in training, from shapes alone (the
`joyai` family: see references/joyai.py for the equations). Kept with the
benchmark so that every PR's roofline and MFU divide the same work,
whatever implements the step. `model` is a configuration file of that
family: its top level holds the published keys, `deployment` the share
this chip holds (`layers`: the main layers kept; the module is one more
layer, sparse, with `W_eh` before it and a second pass of the head after).

Counted: 2 FLOPs a multiply-add; training is forward plus both backward
products (3 x forward) of every product. A causal pair of a latent layer
costs, forward and a head, 2 x (qk_nope_head_dim + qk_rope_head_dim) for
its score and 2 x v_head_dim for its value: 2 x 192 + 2 x 128. The experts
count the pairs routed to the experts HELD here at an even router's share
(tokens x experts a token x held / all) and the shared expert on every
token; each pass of the head the positions that carry its label. Norms,
rotary turns, softmaxes, sigmoids, top-k, the bias's step and the optimizer
are not counted (under 1%). Work an implementation adds (masked-out pairs
inside a tile, rematerialised forwards) is not the algorithm's.
"""


def sizes(model):
    return dict(
        D=model["hidden_size"], H=model["num_attention_heads"],
        rq=model["q_lora_rank"], rkv=model["kv_lora_rank"],
        dn=model["qk_nope_head_dim"], dr=model["qk_rope_head_dim"],
        dv=model["v_head_dim"], I=model["intermediate_size"],
        F=model["moe_intermediate_size"],
        S=model["moe_intermediate_size"] * model["n_shared_experts"],
        V=model["vocab_size"], E=model["deployment"]["router_width"],
        G=model["n_routed_experts"], k=model["num_experts_per_tok"],
        mtp=model["num_nextn_predict_layers"])


def layers(model):
    """"dense" or "sparse" of each decoder layer run here, the module's
    last."""
    main = model["deployment"].get(
        "layers", list(range(model["num_hidden_layers"])))
    return ["dense" if i < model["first_k_dense_replace"] else "sparse"
            for i in main] + ["sparse"] * sizes(model)["mtp"]


def attention_params(model):
    """The seven leaves of a latent attention: five matrices, two norms."""
    z = sizes(model)
    D, H = z["D"], z["H"]
    return (D * z["rq"] + z["rq"] + z["rq"] * H * (z["dn"] + z["dr"])
            + D * (z["rkv"] + z["dr"]) + z["rkv"]
            + z["rkv"] * H * (z["dn"] + z["dv"]) + H * z["dv"] * D)


def attention_matrix_params(model):
    z = sizes(model)
    return attention_params(model) - z["rq"] - z["rkv"]


def mlp_params(model, kind):
    z = sizes(model)
    if kind == "dense":
        return 3 * z["D"] * z["I"]
    return (z["D"] * z["E"] + 3 * z["D"] * z["S"]
            + z["G"] * 3 * z["D"] * z["F"])


def param_count(model):
    """Parameters held on this chip: the layers kept and the module's, the
    experts held, the vocabulary slice (embedding and untied head, once:
    the module reads them), `W_eh`, every norm."""
    z = sizes(model)
    D = z["D"]
    return sum(attention_params(model) + mlp_params(model, m) + 2 * D
               for m in layers(model)) \
        + 2 * z["V"] * D + D + z["mtp"] * (2 * D * D + 3 * D)


def causal_pairs(seq_len):
    """(query, key) pairs one head reads in one sequence: key j <= query
    i."""
    return seq_len * (seq_len + 1) // 2


def held_pairs(model, tokens):
    """(token, expert) pairs of the held experts at an even router's
    share."""
    z = sizes(model)
    return tokens * z["k"] * z["G"] // z["E"]


def latent_attention_train_flops(model, seq_len):
    """The kernels' work of ONE layer over ONE sequence: scores over
    dn + dr slots and values dv wide over every causal pair of every head,
    forward and backward."""
    z = sizes(model)
    return 3 * z["H"] * causal_pairs(seq_len) * (
        2 * (z["dn"] + z["dr"]) + 2 * z["dv"])


def latent_attention_train_bytes(model, seq_len, bytes_per_value=2):
    """q (dn + dr a head), k_nope, v and the ONE rotary key read and o
    written forward; q, k_nope, v, the rotary key, o and do read and dq,
    dk_nope, dv and the rotary key's gradient written backward."""
    z = sizes(model)
    H = z["H"]
    q = seq_len * H * (z["dn"] + z["dr"])
    k, v, shared = seq_len * H * z["dn"], seq_len * H * z["dv"], \
        seq_len * z["dr"]
    forward = q + k + v + shared + v
    backward = (q + k + v + shared + 2 * v) + (q + k + v + shared)
    return (forward + backward) * bytes_per_value


def latent_attention_train_work(model, seq_len):
    """(FLOPs, bytes) of ONE sequence through the kernels of every latent
    layer run here."""
    n = len(layers(model))
    return (n * latent_attention_train_flops(model, seq_len),
            n * latent_attention_train_bytes(model, seq_len))


def experts_train_flops(model, tokens):
    """ONE sparse layer: the held experts' three products for the routed
    pairs, and the shared expert's for every token."""
    z = sizes(model)
    return 3 * 3 * 2 * z["D"] * (held_pairs(model, tokens) * z["F"]
                                 + tokens * z["S"])


def train_flops_per_row(model, seq_len):
    """One sequence through every layer kept, the module and both passes of
    the head (labels on seq_len - 1 and seq_len - 2 positions), training."""
    z = sizes(model)
    D = z["D"]
    total = 3 * 2 * D * z["V"] * (seq_len - 1)
    if z["mtp"]:
        total += 3 * 2 * D * z["V"] * (seq_len - 2)
        total += 3 * 2 * 2 * D * D * seq_len
    for kind in layers(model):
        total += 3 * 2 * attention_matrix_params(model) * seq_len
        total += latent_attention_train_flops(model, seq_len)
        if kind == "dense":
            total += 3 * 2 * mlp_params(model, kind) * seq_len
        else:
            total += 3 * 2 * D * z["E"] * seq_len
            total += experts_train_flops(model, seq_len)
    return total
