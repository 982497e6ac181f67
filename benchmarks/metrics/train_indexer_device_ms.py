"""Device time a step of the learned selection: the indexer's projections,
its scores over every causal pair, its loss against the main attention's
probabilities (scope `indexer`) and the top-k selection (scope `select`),
forward and backward, in the `sparseattention` layers."""
from ..harness.inner_scopes import inner_ms


def read(ctx):
    return inner_ms(ctx, "indexer", "select")
