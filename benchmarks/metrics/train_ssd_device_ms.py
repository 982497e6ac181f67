"""Device time a step of the state-space scan of the `mamba2` layers,
forward and backward (rematerialised forwards included): scope `ssd`, the
time steps, the decays, the chunked scan and the D skip."""
from ..harness.inner_scopes import inner_ms


def read(ctx):
    return inner_ms(ctx, "ssd")
