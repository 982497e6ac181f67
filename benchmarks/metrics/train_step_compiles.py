"""Dispatches of a training program that traced or compiled, over the whole
process: 1 where one step of one shape runs; more is a step that compiled
again. The counter `train.compiles`."""
from .setup_init_s import counter


def read(ctx):
    return counter("train.compiles")
