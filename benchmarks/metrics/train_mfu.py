"""The whole step's share of the chips' peak: the FLOPs the algorithm needs
for the images of the step-program runs that lie inside the traced window,
over that window's length, the chips and the peak. In percent."""
from .train_step_device_ms import step_runs


def read(ctx):
    runs = step_runs(ctx)
    if not runs:
        return None
    images = len(runs) * ctx["images"] / ctx["steps"]
    flops = images * ctx["flops_per_image"]
    return 100.0 * flops / (ctx["trace"]["window_s"] * ctx["chips"]
                            * ctx["peaks"]["bf16_flops"])
