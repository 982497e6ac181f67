"""Share of the prompt rows admitted in the window that the prefix cache
already held (the server's own counters, window delta). In percent."""


def read(ctx):
    a, b = ctx["marks"]["start"], ctx["marks"]["end"]
    total = b["prefix_rows_total"] - a["prefix_rows_total"]
    if total <= 0:
        return None
    return 100.0 * (b["prefix_rows_hit"] - a["prefix_rows_hit"]) / total
