"""Device time a step of the step program's operations rooted in the
optimizer's update (scope `update`)."""
from ..harness.scopes import kind_ms


def read(ctx):
    return kind_ms(ctx, "update")
