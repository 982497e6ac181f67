"""The held experts' share of their roofline: the three products of every
routed pair of a held expert (harness/work_keye.py, the pairs at the
router's expected share), every layer of a step, at the chip's peaks, the
larger of the two times, over the device time of the `experts` scope of the
`moe` layers. In percent."""
from ..harness import work_keye
from ..harness.inner_scopes import inner_ms
from ..harness.work import roofline_seconds


def read(ctx):
    ms = inner_ms(ctx, "experts")
    if not ms:
        return None
    model, tokens = ctx["model"], ctx["rows"] * ctx["seq_len"]
    n = model["num_hidden_layers"]
    least, _ = roofline_seconds(n * work_keye.experts_train_flops(model, tokens),
                                n * work_keye.experts_train_bytes(model, tokens),
                                ctx["peaks"])
    return 100.0 * least / (ms / 1e3)
