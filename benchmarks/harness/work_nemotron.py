"""Operations and bytes the ALGORITHM of a state-space hybrid decoder needs
in training, from shapes alone (the `nemotron_h` family: Mamba-2 mixers,
two-matrix relu^2 experts beside a shared one, grouped-query attention
without positions, each layer one mixer; see references/nemotron_h.py for
the equations). Kept with the benchmark so that every PR's roofline and MFU
divide the same work, whatever implements the step. `model` is a
configuration file of that family: its top level holds the published keys,
`hybrid_override_pattern` one character a layer KEPT, `deployment` the
share this chip holds.

Counted: 2 FLOPs a multiply-add; training is forward plus both backward
products (3 x forward) of every product. A Mamba layer counts its two
projections and the chunked scan at the config's `chunk_size` L (Mamba-2,
arXiv:2405.21060 section 6): the four products a chunk, C.B and its
weighted sum over the pairs j <= i INSIDE a chunk (L (L + 1) / 2 of them,
as attention counts the visible pairs only), the chunk's own state and the
entering state's part of the output at L x P x N a head each. Attention
counts every causal pair. The experts count the pairs routed to the
experts HELD here at the router's expected share (tokens x experts a token
x held / all) at TWO products a pair, and the shared expert on every token;
the head the positions that carry a label. The convolution, norms, gates,
softmaxes, sigmoids, top-k, the recurrence across chunks (T / L
multiply-adds of a state) and the optimizer are not counted as FLOPs (under
1%); the scan, the convolution and the gated norm have BYTES of their own,
since bytes are what bound them. Work an implementation adds (the pairs
j > i inside a chunk, rematerialised forwards) is not the algorithm's and
is not counted.
"""


def sizes(model):
    return dict(
        D=model["hidden_size"], H=model["mamba_num_heads"],
        P=model["mamba_head_dim"], N=model["ssm_state_size"],
        Gs=model["n_groups"], K=model["conv_kernel"], L=model["chunk_size"],
        QH=model["num_attention_heads"], KV=model["num_key_value_heads"],
        Dh=model["head_dim"], F=model["moe_intermediate_size"],
        S=model["moe_shared_expert_intermediate_size"],
        V=model["vocab_size"], E=model["deployment"]["router_width"],
        G=model["n_routed_experts"], k=model["num_experts_per_tok"],
        pattern=model["hybrid_override_pattern"])


def mixer_params(z, kind, experts=None):
    """Parameters of one mixer of `kind` ("M", "E" or "*"), `experts` of an
    expert layer's routed experts held (all that `z` names by default)."""
    D, inner = z["D"], z["H"] * z["P"]
    if kind == "M":
        conv = inner + 2 * z["Gs"] * z["N"]
        return (D * (inner + conv + z["H"]) + inner * D + conv * z["K"] + conv
                + 3 * z["H"] + inner)
    if kind == "E":
        held = z["G"] if experts is None else experts
        return D * z["E"] + 2 * D * (held * z["F"] + z["S"])
    return 2 * D * z["QH"] * z["Dh"] + 2 * D * z["KV"] * z["Dh"]


def param_count(model, published=None):
    """Trained parameters held on this chip: the layers kept, the experts
    held, the vocabulary slice (embedding and untied head), every norm. With
    `published` (the configuration file's entry of that name: the
    published pattern, experts and vocabulary), of the whole model."""
    z = sizes(model)
    pattern, held, rows = z["pattern"], z["G"], z["V"]
    if published:
        pattern, held, rows = (published["hybrid_override_pattern"],
                               published["n_routed_experts"],
                               published["vocab_size"])
    return sum(mixer_params(z, kind, held) + z["D"] for kind in pattern) \
        + 2 * rows * z["D"] + z["D"]


def causal_pairs(n):
    return n * (n + 1) // 2


def held_pairs(model, tokens):
    """(token, expert) pairs of the held experts at the expected share."""
    z = sizes(model)
    return tokens * z["k"] * z["G"] // z["E"]


def count(model, kind):
    return sizes(model)["pattern"].count(kind)


# ------------------------------------------------------------ a Mamba layer
def ssd_train_flops(model, seq_len):
    """The chunked scan of ONE layer over ONE sequence, forward and
    backward: the four products a chunk."""
    z = sizes(model)
    L, H, P, N = z["L"], z["H"], z["P"], z["N"]
    inside = causal_pairs(L) * 2 * (z["Gs"] * N + H * P)
    states = 2 * 2 * L * H * P * N
    return 3 * (seq_len // L) * (inside + states)


def ssd_train_bytes(model, seq_len, bytes_per_value=2):
    """What the scan of ONE layer must move for ONE sequence: xs, dt (float32),
    B and C read and y written forward, the T / L chunk states (float32)
    written and read once; backward the same inputs read again with dy,
    their four gradients written, the kept states read and the states'
    gradients written and read once."""
    z = sizes(model)
    inputs = seq_len * ((z["H"] * z["P"] + 2 * z["Gs"] * z["N"])
                        * bytes_per_value + z["H"] * 4)
    y = seq_len * z["H"] * z["P"] * bytes_per_value
    states = (seq_len // z["L"]) * z["H"] * z["P"] * z["N"] * 4
    return (inputs + y + 2 * states) + (2 * inputs + y + 3 * states)


def ssd_train_work(model, seq_len):
    """(FLOPs, bytes) of ONE sequence through the scan of every Mamba layer
    kept."""
    n = count(model, "M")
    return (n * ssd_train_flops(model, seq_len),
            n * ssd_train_bytes(model, seq_len))


def pointwise_train_bytes(model, seq_len, bytes_per_value=2):
    """The convolution and the gated norm of ONE layer over ONE sequence:
    the convolution reads its channels and writes them forward, reads them
    and the incoming gradient and writes one backward; the gated norm reads
    y and z and writes y forward, reads both and the gradient and writes
    two backward."""
    z = sizes(model)
    inner = z["H"] * z["P"]
    conv = inner + 2 * z["Gs"] * z["N"]
    return seq_len * bytes_per_value * (5 * conv + 8 * inner)


# ------------------------------------------------- attention and the experts
def attention_train_flops(model, seq_len):
    """The attention of ONE layer over ONE sequence: q k^T and p v over the
    causal pairs, forward and backward."""
    z = sizes(model)
    return 3 * 4 * z["QH"] * z["Dh"] * causal_pairs(seq_len)


def experts_train_flops(model, tokens):
    """ONE expert layer: the held experts' two products for the routed
    pairs, and the shared expert's for every token."""
    z = sizes(model)
    return 3 * 2 * 2 * z["D"] * (held_pairs(model, tokens) * z["F"]
                                 + tokens * z["S"])


def train_flops_per_row(model, seq_len, label_positions):
    """One sequence through every layer kept and the head, training."""
    z = sizes(model)
    D, inner = z["D"], z["H"] * z["P"]
    total = 3 * 2 * D * z["V"] * label_positions
    for kind in z["pattern"]:
        if kind == "M":
            proj = D * (2 * inner + 2 * z["Gs"] * z["N"] + z["H"]) + inner * D
            total += 3 * 2 * proj * seq_len + ssd_train_flops(model, seq_len)
        elif kind == "E":
            total += 3 * 2 * D * z["E"] * seq_len
            total += experts_train_flops(model, seq_len)
        else:
            total += 3 * 2 * mixer_params(z, kind) * seq_len
            total += attention_train_flops(model, seq_len)
    return total
