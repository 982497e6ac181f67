"""Host time of one dispatch of the step: the median length of the
program's `train.dispatch` annotations inside the traced window."""
import statistics

from ..harness.scopes import host_spans


def read(ctx):
    spans = host_spans(ctx, "train.dispatch")
    return statistics.median(d for _, d in spans) / 1e6 if spans else None
