"""Programs that the dispatches of a training program compiled and wrote to the
persistent cache: 0 says the run's step came from the cache, 1 or more that
it compiled; the counter `train.compile_cache_misses` of the process."""
from .setup_init_s import counter


def read(ctx):
    return counter("train.compile_cache_misses")
