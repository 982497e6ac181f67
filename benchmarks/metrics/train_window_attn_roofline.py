"""The window attention layers' share of their roofline: the work the
algorithm needs for the pairs inside the window (sum over queries t of
min(t + 1, window)) of those layers and every row of a step
(harness/work_laguna.py) at the chip's peaks, the larger of the two times,
over the device time of the `attend_window` scope. In percent, never
clipped; pairs computed outside the band inside a tile are waste."""
from .train_full_attn_roofline import share
from .train_window_attn_device_ms import read as device_ms


def read(ctx):
    return share(ctx, device_ms(ctx), windowed=True)
