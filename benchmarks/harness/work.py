"""Operations and bytes the ALGORITHM needs, from shapes alone. Kept with
the benchmark so that every PR's roofline and MFU divide the same work,
whatever later implements the step."""


# ---------------------------------------------------------------- ResNet
def resnet_conv_shapes(model):
    """(name, out_h, out_w, kh, kw, c_in, c_out) of every convolution of
    ResNet-50 v1 at the model's input size, in forward order."""
    h, w = model["height"], model["width"]
    up = lambda n, s: -(-n // s)                # SAME padding
    out = []
    h, w = up(h, 2), up(w, 2)
    out.append(("stem", h, w, 7, 7, model["channels"], 64))
    h, w = up(h, 2), up(w, 2)                   # 3x3/2 max pool
    c_in = 64
    for si, (blocks, width) in enumerate(model["stages"]):
        for bi in range(blocks):
            stride = 2 if (si > 0 and bi == 0) else 1
            name = f"s{si + 2}b{bi}"
            ho, wo = up(h, stride), up(w, stride)
            c_out = width * model["expansion"]
            out.append((name + "_a", ho, wo, 1, 1, c_in, width))
            out.append((name + "_b", ho, wo, 3, 3, width, width))
            out.append((name + "_c", ho, wo, 1, 1, width, c_out))
            if bi == 0:
                out.append((name + "_sc", ho, wo, 1, 1, c_in, c_out))
            h, w, c_in = ho, wo, c_out
    return out


def resnet_train_flops_per_image(model):
    """Forward plus both backward products of every convolution and of the
    classifier, 2 FLOPs a multiply-add; the stem needs no input gradient.
    Batch norm, activations and pooling are not counted (under 1%)."""
    convs = resnet_conv_shapes(model)
    macs = [ho * wo * kh * kw * ci * co
            for _, ho, wo, kh, kw, ci, co in convs]
    fc = convs[-1][6] * model["num_classes"]
    return 2 * (3 * (sum(macs) + fc) - macs[0])


def resnet_param_count(model):
    convs = resnet_conv_shapes(model)
    n = sum(kh * kw * ci * co + 2 * co for _, _, _, kh, kw, ci, co in convs)
    return n + convs[-1][6] * model["num_classes"] + model["num_classes"]


# ------------------------------------------------------------------- LM
def lm_matmul_params(model):
    """Parameters every token is multiplied by: the four block matrices of
    every layer and the output head (embeddings are looked up)."""
    d, ff = model["n_embd"], model["n_inner"]
    return model["n_layer"] * (4 * d * d + 2 * d * ff) \
        + d * model["vocab_size"]


def lm_param_count(model):
    d, ff = model["n_embd"], model["n_inner"]
    block = 4 * d * d + 2 * d * ff + ff + d + 4 * d
    return (model["n_layer"] * block + 2 * d
            + model["vocab_size"] * d + model["n_positions"] * d
            + (0 if model.get("tied_head") else d * model["vocab_size"]))


def lm_kv_row_bytes(model, bytes_per_value=2):
    """One position's keys and values over all layers."""
    return model["n_layer"] * 2 * model["n_embd"] * bytes_per_value


def lm_token_flops(model, context):
    """One token's forward: its matmuls, and attention's two products over
    `context` live positions in every layer."""
    return 2 * lm_matmul_params(model) \
        + 4 * model["n_layer"] * model["n_embd"] * context


def lm_decode_step_work(model, live_rows, bytes_per_value=2):
    """FLOPs and HBM bytes one decode step needs for slots whose live
    context lengths are `live_rows`: every weight read once, the live rows'
    keys and values read once, one new row written for each slot. Never the
    width of a table, never a gather's copy."""
    weights = lm_matmul_params(model) * bytes_per_value
    row = lm_kv_row_bytes(model, bytes_per_value)
    flops = sum(lm_token_flops(model, n) for n in live_rows)
    return flops, weights + row * (sum(live_rows) + len(live_rows))


def roofline_seconds(flops, hbm_bytes, peaks):
    """The least time the chip could take, and which bound sets it."""
    t_f = flops / peaks["bf16_flops"]
    t_b = hbm_bytes / peaks["hbm_bytes_per_s"]
    return max(t_f, t_b), ("compute" if t_f >= t_b else "memory")
