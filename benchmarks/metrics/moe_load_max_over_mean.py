"""The busiest held expert's routed pairs over the mean of the held experts',
the worst layer: from the program's gauges
(`moe.<vertex>.held_pairs_max` / `held_pairs_mean`, set once after the
window from the last step's state)."""


def read(ctx):
    g = ctx.get("gauges") or {}
    ratios = [g[k] / g[k[:-len("max")] + "mean"] for k in g
              if k.startswith("moe.") and k.endswith(".held_pairs_max")
              and g.get(k[:-len("max")] + "mean")]
    return max(ratios) if ratios else None
