"""Device time a step of the step program's operations that are ROOTED in a
batch-norm layer (scope `batchnorm.<name>`, `jvp(..)` and
`transpose(jvp(..))` alike): the statistics' reductions forward and
backward, not the normalisation XLA fuses into a convolution's output."""
from ..harness.scopes import kind_ms


def read(ctx):
    return kind_ms(ctx, "batchnorm")
