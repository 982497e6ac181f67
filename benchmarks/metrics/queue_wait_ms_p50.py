"""Median wait between a request's submit and its admission to a slot, over
the admissions inside the window: the server's own `serve.queue_wait` spans
(its host tracer is on in a traced run only)."""
from ..harness.compare import median


def read(ctx):
    if ctx.get("spans") is None:
        return None
    waits = [s.dur_ns / 1e6 for s in ctx["spans"].spans("serve.queue_wait")
             if ctx["t_start"] <= (s.t0_ns + s.dur_ns) / 1e9 < ctx["t_end"]]
    return median(waits) if waits else None
