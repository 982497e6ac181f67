"""Device time of the chunked-prefill program, median per dispatch, traced
window, first device."""
import statistics


def read(ctx):
    from ..harness import serve_trace, trace as T
    _, chunk = serve_trace.step_programs(ctx)
    return statistics.median(T.seconds(chunk)) * 1e3 if chunk else None
