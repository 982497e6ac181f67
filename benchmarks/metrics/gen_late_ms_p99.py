"""How late the load generator ran: 99th percentile, over the requests due in
the window, of the clock just before `submit` minus the due time."""
from ..harness.compare import percentile


def read(ctx):
    late = [(ctx["log"][i]["t_before"] - ctx["log"][i]["due"]) * 1e3
            for i in ctx["in_window"]]
    return percentile(late, 99)
