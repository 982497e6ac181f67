"""Operations and bytes the ALGORITHM of a sparse-attention mixture-of-experts
decoder needs in training, from shapes alone (the `keye_vl` family: see
references/keye_vl.py for the equations). Kept with the benchmark so that
every PR's roofline and MFU divide the same work, whatever implements the
step. `model` is a configuration file of that family (its top level holds
the published keys; `deployment` the share this chip holds).

Counted: 2 FLOPs a multiply-add; training is forward plus both backward
products (3 x forward) of every product, except the indexer's three
projections, which read stop_gradient(h) and have no input gradient (2 x).
Attention counts the SELECTED pairs only (a query's min(t + 1, topk) keys),
the indexer every causal pair, the experts the pairs routed to the experts
HELD here at the router's expected share (tokens x experts a token x held /
all), the head the positions that carry a label. Norms, rotary turns,
softmaxes, top-k and the optimizer are not counted (under 1%). Work an
implementation adds (masked-out pairs inside a tile, rematerialised
forwards) is not the algorithm's and is not counted.
"""


def sizes(model):
    sa, dep = model["sa_config"], model["deployment"]
    return dict(
        D=model["hidden_size"], H=model["num_attention_heads"],
        KV=model["num_key_value_heads"], Dh=model["head_dim"],
        F=model["moe_intermediate_size"], L=model["num_hidden_layers"],
        V=model["vocab_size"], E=dep["router_width"],
        G=model["num_local_experts"], k=model["num_experts_per_tok"],
        HI=sa["indexer_num_heads"], DI=sa["indexer_head_dim"],
        topk=sa["topk"])


def param_count(model):
    """Parameters held on this chip: the layers kept, the experts held, the
    vocabulary slice (embedding and untied head), every norm."""
    z = sizes(model)
    D, Dh = z["D"], z["Dh"]
    attn = 2 * D * z["H"] * Dh + 2 * D * z["KV"] * Dh + 2 * Dh
    indexer = D * z["HI"] * z["DI"] + D * z["DI"] + D * z["HI"]
    moe = D * z["E"] + z["G"] * 3 * D * z["F"]
    return z["L"] * (attn + indexer + moe + 2 * D) + 2 * z["V"] * D + D


def selected_pairs(seq_len, topk):
    """(query, key) pairs the main attention reads in one sequence."""
    full = min(seq_len, topk)
    return full * (full + 1) // 2 + (seq_len - full) * topk


def causal_pairs(seq_len):
    return seq_len * (seq_len + 1) // 2


def held_pairs(model, tokens):
    """(token, expert) pairs of the held experts at the expected share."""
    z = sizes(model)
    return tokens * z["k"] * z["G"] // z["E"]


def attention_train_flops(model, seq_len):
    """The main attention of ONE layer over ONE sequence: q k^T and p v over
    the selected pairs, forward and backward."""
    z = sizes(model)
    return 3 * 4 * z["H"] * z["Dh"] * selected_pairs(seq_len, z["topk"])


def attention_train_bytes(model, seq_len, bytes_per_value=2):
    """q, k, v read and o written forward; q, k, v, o, do read and dq, dk,
    dv written backward; the selection read once each way at a bit a causal
    pair."""
    z = sizes(model)
    q = seq_len * z["H"] * z["Dh"] * bytes_per_value
    kv = 2 * seq_len * z["KV"] * z["Dh"] * bytes_per_value
    return (2 * q + kv) + (4 * q + 2 * kv) + 2 * causal_pairs(seq_len) // 8


def indexer_train_flops(model, seq_len):
    z = sizes(model)
    proj = 2 * z["D"] * (z["HI"] * z["DI"] + z["DI"] + z["HI"])
    score = 2 * z["HI"] * z["DI"] * causal_pairs(seq_len)
    return 2 * proj * seq_len + 3 * score


def experts_train_flops(model, tokens):
    """The held experts' three products for the routed pairs, ONE layer."""
    z = sizes(model)
    return 3 * held_pairs(model, tokens) * 3 * 2 * z["D"] * z["F"]


def experts_train_bytes(model, tokens, bytes_per_value=2):
    """Each held expert's three matrices read forward and twice backward
    (both products) and their gradients written; a pair's input row read
    and output row written each way, its two hidden rows written forward
    and read backward."""
    z = sizes(model)
    weights = z["G"] * 3 * z["D"] * z["F"] * bytes_per_value
    rows = held_pairs(model, tokens) * (2 * z["D"] + 2 * z["F"]) \
        * bytes_per_value
    return 4 * weights + 3 * rows


def train_flops_per_row(model, seq_len, label_positions):
    """One sequence through every layer kept and the head, training."""
    z = sizes(model)
    D = z["D"]
    proj = 2 * (2 * D * z["H"] * z["Dh"] + 2 * D * z["KV"] * z["Dh"])
    router = 2 * D * z["E"]
    layer = (3 * (proj + router) * seq_len
             + attention_train_flops(model, seq_len)
             + indexer_train_flops(model, seq_len)
             + experts_train_flops(model, seq_len))
    return z["L"] * layer + 3 * 2 * D * z["V"] * label_positions
