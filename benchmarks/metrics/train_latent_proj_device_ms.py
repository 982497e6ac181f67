"""Device time a step of the latent attention layers outside their kernels,
forward and backward (rematerialised forwards included): both low-rank
chains with the norms on their latents and the output projection (scope
`latent`) and the interleaved rotary turn of the 64 shared slots (scope
`rotary`)."""
from ..harness.inner_scopes import inner_ms


def read(ctx):
    return inner_ms(ctx, "latent", "rotary")
