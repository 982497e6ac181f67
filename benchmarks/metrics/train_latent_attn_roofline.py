"""The latent attention kernels' share of their roofline: the work the
algorithm needs for every causal pair of every latent layer and every row
of a step (harness/work_joyai.py: scores 192 wide, values 128 wide; FLOPs
and bytes from shapes) at the chip's peaks, the larger of the two times,
over the device time of the `attend_latent` scope. In percent, never
clipped; the same work whatever implements it, so an implementation that
computes masked-out pairs, pads a width or rematerialises reads lower."""
from ..harness import work_joyai
from ..harness.work import roofline_seconds
from .train_latent_attn_device_ms import read as device_ms


def read(ctx):
    ms = device_ms(ctx)
    if not ms:
        return None
    flops, hbm = work_joyai.latent_attention_train_work(ctx["model"],
                                                        ctx["seq_len"])
    least, _ = roofline_seconds(ctx["rows"] * flops, ctx["rows"] * hbm,
                                ctx["peaks"])
    return 100.0 * least / (ms / 1e3)
