"""The busiest held expert's routed pairs over the mean of the held
experts', the worst layer, in a cell whose expert layers have a shared
expert: the program's gauges `moe.<vertex>.held_pairs_max` /
`held_pairs_mean`, as `moe_load_max_over_mean` reads them (two names until a
`benchmark` PR merges them: tests/benchmark/test_keye_cell.py pins the
count of metrics that list the other cell alone)."""
from .moe_load_max_over_mean import read  # noqa: F401
