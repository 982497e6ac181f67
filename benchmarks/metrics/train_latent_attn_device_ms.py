"""Device time a step of the latent attention layers' kernels, forward and
backward: the operations traced under the `attend_latent` scope of the
`latentattention` layers (the prediction module's layer among them)."""
from ..harness.inner_scopes import inner_ms


def read(ctx):
    return inner_ms(ctx, "attend_latent")
