"""The state-space scan's share of its roofline: the work the algorithm
needs for the chunked scan of every Mamba layer and every row of a step
(harness/work_nemotron.py: the four products a chunk, and the bytes the
scan must move, from shapes) at the chip's peaks, the larger of the two
times, over the device time of the `ssd` scope. In percent, never clipped;
the same work whatever implements it, so an implementation that computes
the pairs j > i inside a chunk, writes the pairs' decays out or
rematerialises reads lower."""
from ..harness import work_nemotron
from ..harness.work import roofline_seconds
from .train_ssd_device_ms import read as device_ms


def read(ctx):
    ms = device_ms(ctx)
    if not ms:
        return None
    flops, hbm = work_nemotron.ssd_train_work(ctx["model"], ctx["seq_len"])
    least, _ = roofline_seconds(ctx["rows"] * flops, ctx["rows"] * hbm,
                                ctx["peaks"])
    return 100.0 * least / (ms / 1e3)
