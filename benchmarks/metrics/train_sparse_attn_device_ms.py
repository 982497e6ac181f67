"""Device time a step of the main attention over the selected keys, forward
and backward (rematerialised forwards included): the operations traced under
the `attend` scope of the `sparseattention` layers."""
from ..harness.inner_scopes import inner_ms


def read(ctx):
    return inner_ms(ctx, "attend")
