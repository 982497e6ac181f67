"""Device time of the collective operations of one step, first device: the
all-reduce operations' time inside the traced window over the step-program
runs there. Nothing to read on one chip."""
from .train_step_device_ms import step_runs


def read(ctx):
    from ..harness import trace as T
    tr = ctx["trace"]
    if not tr["trace"]["devices"]:
        return None
    dev = tr["trace"]["devices"][sorted(tr["trace"]["devices"])[0]]
    coll = [d for n, _, d in T.clip(dev["ops"], tr["t0"], tr["t1"])
            if T.op_label(n).startswith(("all-reduce", "all-gather", "reduce-scatter",
                             "collective-permute", "all-to-all"))]
    runs = step_runs(ctx)
    if not coll or not runs:
        return None
    return sum(coll) / 1e6 / len(runs)
