"""Host time ParallelWrapper spends on one step before the device has it:
the median, over the steps inside the traced window, of `parallel.stage`
(key split, the batch put on the mesh) plus the `parallel.dispatch` that
follows it. Stderr says how the two divide."""
import bisect
import statistics

from ..harness.scopes import host_spans, say


def read(ctx):
    stages = host_spans(ctx, "parallel.stage")
    ends = [s + d for s, d in stages]
    steps = []
    for s, d in host_spans(ctx, "parallel.dispatch"):
        i = bisect.bisect_right(ends, s) - 1    # the stage that ended last
        if i >= 0:
            steps.append((stages[i][1], d))
    if not steps:
        return None
    say(f"{len(steps)} steps of the wrapper; medians, ms: parallel.stage "
        f"{statistics.median(a for a, _ in steps) / 1e6:.3f}, "
        f"parallel.dispatch {statistics.median(b for _, b in steps) / 1e6:.3f}")
    return statistics.median(a + b for a, b in steps) / 1e6
