"""Device time of the paged decode program, median per dispatch, traced
window, first device."""
import statistics


def read(ctx):
    from ..harness import serve_trace, trace as T
    decode, _ = serve_trace.step_programs(ctx)
    return statistics.median(T.seconds(decode)) * 1e3 if decode else None
