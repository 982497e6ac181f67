"""The expert layers' share of their roofline: the three products of every
routed pair of a held expert (the pairs at the router's expected share) and
of the shared expert on every token (harness/work_laguna.py), every sparse
layer of a step, at the chip's peaks, the larger of the two times, over the
device time of the `experts` and `shared` scopes of the `moe` layers. In
percent, never clipped."""
from ..harness import work_laguna
from ..harness.inner_scopes import inner_ms
from ..harness.work import roofline_seconds


def read(ctx):
    ms = inner_ms(ctx, "experts", "shared")
    if not ms:
        return None
    model, tokens = ctx["model"], ctx["rows"] * ctx["seq_len"]
    n = work_laguna.sparse_layers(model)
    least, _ = roofline_seconds(
        n * work_laguna.experts_train_flops(model, tokens),
        n * work_laguna.experts_train_bytes(model, tokens), ctx["peaks"])
    return 100.0 * least / (ms / 1e3)
