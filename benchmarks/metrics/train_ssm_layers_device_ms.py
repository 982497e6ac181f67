"""Device time a step of the `mamba2` layers, forward and backward
(rematerialised forwards included): every operation under one of the four
scopes the kind opens, `ssm_proj`, `ssm_conv`, `ssd` and `ssm_norm`, which
hold everything the layer traces (the kind's own name has a digit, which
`harness/inner_scopes.py` does not read as a scope). None where the step
has no such scope."""
from ..harness.inner_scopes import inner_ms


def read(ctx):
    return inner_ms(ctx, "ssm_proj", "ssm_conv", "ssd", "ssm_norm")
