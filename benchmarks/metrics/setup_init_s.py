"""Seconds of `Trainer.init` (the span `train.init`): a net's parameters, updater
state and model state as the trainer builds them, layer by layer, with the
small programs those run; the counter `train.init_s` of the process."""
from deeplearning4j_tpu.obs import default_registry


def counter(name):
    """The value of one counter of the program's registry, None where the
    program has no such counter (a parent that lacks it)."""
    c = default_registry().get(name)
    return None if c is None else c.value


def read(ctx):
    return counter("train.init_s")
