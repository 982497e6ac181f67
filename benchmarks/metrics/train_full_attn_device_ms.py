"""Device time a step of the full (plain causal) attention layers' kernels,
forward and backward (rematerialised forwards included): the operations
traced under the `attend_full` scope of the `attention` layers."""
from ..harness.inner_scopes import inner_ms


def read(ctx):
    return inner_ms(ctx, "attend_full")
