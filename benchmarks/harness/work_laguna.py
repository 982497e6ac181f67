"""Operations and bytes the ALGORITHM of a decoder with window and full
attention layers, a per-head output gate and small experts beside a shared
one needs in training, from shapes alone (the `laguna` family: see
references/laguna.py for the equations). Kept with the benchmark so that
every PR's roofline and MFU divide the same work, whatever implements the
step. `model` is a configuration file of that family: its top level holds
the published keys, the three per-layer lists one entry a layer KEPT,
`deployment` the share this chip holds.

Counted: 2 FLOPs a multiply-add; training is forward plus both backward
products (3 x forward) of every product. Attention counts the VISIBLE pairs
only: every causal pair on full layers, sum_t min(t + 1, window) on window
ones. The experts count the pairs routed to the experts HELD here at the
router's expected share (tokens x experts a token x held / all) and the
shared expert on every token; the head the positions that carry a label.
Norms, rotary turns, softmaxes, sigmoids, top-k and the optimizer are not
counted (under 1%). Work an implementation adds (masked-out pairs inside a
tile, rematerialised forwards) is not the algorithm's and is not counted.
"""


def sizes(model):
    return dict(
        D=model["hidden_size"], KV=model["num_key_value_heads"],
        Dh=model["head_dim"], F=model["moe_intermediate_size"],
        S=model["shared_expert_intermediate_size"],
        I=model["intermediate_size"], V=model["vocab_size"],
        E=model["deployment"]["router_width"], G=model["num_experts"],
        k=model["num_experts_per_tok"], window=model["sliding_window"])


def layers(model):
    """(window or None, query heads, "dense" or "sparse") of each layer
    kept."""
    z = sizes(model)
    return [(z["window"] if t == "sliding_attention" else None, h, m)
            for t, h, m in zip(model["layer_types"],
                               model["num_attention_heads_per_layer"],
                               model["mlp_layer_types"])]


def attention_params(model, heads):
    z = sizes(model)
    D, Dh = z["D"], z["Dh"]
    return 2 * D * heads * Dh + 2 * D * z["KV"] * Dh + D * heads


def mlp_params(model, kind):
    z = sizes(model)
    if kind == "dense":
        return 3 * z["D"] * z["I"]
    return (z["D"] * z["E"] + 3 * z["D"] * z["S"]
            + z["G"] * 3 * z["D"] * z["F"])


def param_count(model):
    """Parameters held on this chip: the layers kept, the experts held, the
    vocabulary slice (embedding and untied head), every norm."""
    z = sizes(model)
    return sum(attention_params(model, h) + mlp_params(model, m)
               + 2 * z["D"] for _, h, m in layers(model)) \
        + 2 * z["V"] * z["D"] + z["D"]


def visible_pairs(seq_len, window=None):
    """(query, key) pairs one head reads in one sequence: key j <= query i
    and, under a window, i - j < window."""
    w = seq_len if window is None else min(window, seq_len)
    return w * (w + 1) // 2 + (seq_len - w) * w


def held_pairs(model, tokens):
    """(token, expert) pairs of the held experts at the expected share."""
    z = sizes(model)
    return tokens * z["k"] * z["G"] // z["E"]


def attention_train_flops(model, seq_len, heads, window=None):
    """The attention of ONE layer over ONE sequence: q k^T and p v over the
    visible pairs, forward and backward."""
    z = sizes(model)
    return 3 * 4 * heads * z["Dh"] * visible_pairs(seq_len, window)


def attention_train_bytes(model, seq_len, heads, bytes_per_value=2):
    """q, k, v read and o written forward; q, k, v, o, do read and dq, dk,
    dv written backward. No mask is read: it is a function of position."""
    z = sizes(model)
    q = seq_len * heads * z["Dh"] * bytes_per_value
    kv = 2 * seq_len * z["KV"] * z["Dh"] * bytes_per_value
    return (2 * q + kv) + (4 * q + 2 * kv)


def attention_train_work(model, seq_len, windowed):
    """(FLOPs, bytes) of ONE sequence through the attention of every kept
    layer that is windowed (or, `windowed` false, that is not)."""
    kept = [(w, h) for w, h, _ in layers(model) if (w is not None) == windowed]
    return (sum(attention_train_flops(model, seq_len, h, w) for w, h in kept),
            sum(attention_train_bytes(model, seq_len, h) for _, h in kept))


def experts_train_flops(model, tokens):
    """ONE sparse layer: the held experts' three products for the routed
    pairs, and the shared expert's for every token."""
    z = sizes(model)
    return 3 * 3 * 2 * z["D"] * (held_pairs(model, tokens) * z["F"]
                                 + tokens * z["S"])


def experts_train_bytes(model, tokens, bytes_per_value=2):
    """Each held expert's (and the shared one's) three matrices read
    forward and twice backward (both products) and their gradients written;
    a pair's (a token's) input row read and output row written each way, its
    two hidden rows written forward and read backward."""
    z = sizes(model)
    weights = 3 * z["D"] * (z["G"] * z["F"] + z["S"]) * bytes_per_value
    rows = (held_pairs(model, tokens) * (2 * z["D"] + 2 * z["F"])
            + tokens * (2 * z["D"] + 2 * z["S"])) * bytes_per_value
    return 4 * weights + 3 * rows


def sparse_layers(model):
    return sum(m == "sparse" for _, _, m in layers(model))


def train_flops_per_row(model, seq_len, label_positions):
    """One sequence through every layer kept and the head, training."""
    z = sizes(model)
    D = z["D"]
    total = 3 * 2 * D * z["V"] * label_positions
    for window, heads, kind in layers(model):
        total += 3 * 2 * attention_params(model, heads) * seq_len
        total += attention_train_flops(model, seq_len, heads, window)
        if kind == "dense":
            total += 3 * 2 * mlp_params(model, kind) * seq_len
        else:
            total += 3 * 2 * D * z["E"] * seq_len
            total += experts_train_flops(model, seq_len)
    return total
