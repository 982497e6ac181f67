"""Device time a step of the `moe` layers with a shared expert: router, the
sort of the routed pairs, the held experts' products, the shared expert
and the combine, forward and backward (the operations under a
`moe.<vertex>` scope or its `router` / `experts` / `shared` scopes; loops'
own events left out, harness/inner_scopes.py says why)."""
from ..harness.inner_scopes import inner_ms


def read(ctx):
    return inner_ms(ctx, "moe", "router", "experts", "shared")
