"""Device time a step of the step program's operations rooted in a
convolution layer (scope `convolution.<name>`), forward and both backward
products, with whatever XLA fused into them."""
from ..harness.scopes import kind_ms


def read(ctx):
    return kind_ms(ctx, "convolution")
