"""Wall seconds of the dispatches of a training program that compiled (the spans
`train.compile`): tracing, lowering, the backend's compile or the cache's
load, and the first enqueue; the counter `train.compile_s` of the process."""
from .setup_init_s import counter


def read(ctx):
    return counter("train.compile_s")
