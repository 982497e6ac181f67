"""Device time a step of the `mamba2` layers' byte-bound passes, forward
and backward (rematerialised forwards included): the causal depthwise
convolution with its SiLU (scope `ssm_conv`) and the gate with the grouped
norm (scope `ssm_norm`)."""
from ..harness.inner_scopes import inner_ms


def read(ctx):
    return inner_ms(ctx, "ssm_conv", "ssm_norm")
