"""The full attention layers' share of their roofline: the work the
algorithm needs for every causal pair of those layers and every row of a
step (harness/work_laguna.py: FLOPs and bytes from shapes) at the chip's
peaks, the larger of the two times, over the device time of the
`attend_full` scope. In percent, never clipped; an implementation that
computes masked-out pairs or rematerialises reads lower."""
from ..harness import work_laguna
from ..harness.work import roofline_seconds
from .train_full_attn_device_ms import read as device_ms


def share(ctx, ms, windowed):
    if not ms:
        return None
    flops, hbm = work_laguna.attention_train_work(ctx["model"],
                                                  ctx["seq_len"], windowed)
    least, _ = roofline_seconds(ctx["rows"] * flops, ctx["rows"] * hbm,
                                ctx["peaks"])
    return 100.0 * least / (ms / 1e3)


def read(ctx):
    return share(ctx, device_ms(ctx), windowed=False)
