"""Device time of one run of the step program: the median over the runs of
the module that took most of the traced window, on the first device."""
import statistics


def step_runs(ctx):
    from ..harness import trace as T
    tr = ctx["trace"]
    runs = T.program_runs(tr["trace"], tr["t0"], tr["t1"])
    return T.seconds(max(runs.values(), key=lambda r: sum(T.seconds(r)))) \
        if runs else []


def read(ctx):
    runs = step_runs(ctx)
    return statistics.median(runs) * 1e3 if runs else None
