"""The main attention's share of its roofline: the work the algorithm needs
for the SELECTED pairs of every layer and row of a step (harness/work_keye.py:
FLOPs and bytes from shapes) at the chip's peaks, the larger of the two
times, over the device time of the `attend` scope. In percent; an
implementation that computes masked-out pairs or rematerialises reads lower."""
from ..harness import work_keye
from ..harness.work import roofline_seconds
from .train_sparse_attn_device_ms import read as device_ms


def read(ctx):
    ms = device_ms(ctx)
    if not ms:
        return None
    model, t = ctx["model"], ctx["seq_len"]
    n = ctx["rows"] * model["num_hidden_layers"]
    least, _ = roofline_seconds(n * work_keye.attention_train_flops(model, t),
                                n * work_keye.attention_train_bytes(model, t),
                                ctx["peaks"])
    return 100.0 * least / (ms / 1e3)
