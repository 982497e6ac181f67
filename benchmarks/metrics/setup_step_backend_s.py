"""Of `setup_step_build_s`, the seconds inside jax's backend-compile events (on a
hit of the persistent cache: reading and loading the cached executable); the
counter `train.compile_backend_s` of the process."""
from .setup_init_s import counter


def read(ctx):
    return counter("train.compile_backend_s")
