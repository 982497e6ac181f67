"""The comparisons that decide `correct`. Every number compared is returned
beside its limit; a run is correct when each is at or under its limit."""
import statistics

import numpy as np


def leaf_gaps(got, ref, keep=None):
    """The gap between two norms of every leaf, each measured against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero). The gap between norms, not the
    norm of a difference."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    gap = np.abs(got - ref) / np.maximum(ref, np.median(ref))
    return gap if keep is None else gap[np.asarray(keep)]


def moved_leaves(ref_grad_norms):
    """Leaves whose reference gradient is nought to rounding (under a
    thousandth of the median leaf's) move by round-off alone and are left
    out of the parameters' change: a rule on the gradient, not on names."""
    g = np.asarray(ref_grad_norms, np.float64)
    return g >= 1e-3 * np.median(g)


def training_numbers(got, ref, matrices=None):
    """got/ref: (losses[k], first-gradient leaf norms, change leaf norms);
    `matrices` marks the leaves with two axes or more (the products' own
    operands)."""
    out = {}
    for i, (a, b) in enumerate(zip(np.asarray(got[0], np.float64),
                                   np.asarray(ref[0], np.float64))):
        out[f"loss{i + 1}_rel"] = float(abs(a - b) / abs(b))
    # by the worst leaf, and by the median leaf (steadier from seed to seed)
    for name, gaps in (("grad", leaf_gaps(got[1], ref[1])),
                       ("change", leaf_gaps(got[2], ref[2],
                                            moved_leaves(ref[1])))):
        out[name + "_norm_gap"] = float(gaps.max())
        out[name + "_norm_gap_p50"] = float(np.median(gaps))
    if matrices is not None:
        # the median over the matrices alone: what a product computed in a
        # lower precision touches first, and the steadiest from seed to seed
        keep = np.asarray(matrices)
        out["grad_norm_gap_w50"] = float(np.median(
            leaf_gaps(got[1], ref[1], keep)))
        out["change_norm_gap_w50"] = float(np.median(
            leaf_gaps(got[2], ref[2], keep & moved_leaves(ref[1]))))
    return out


def judge(numbers, limits):
    """{name: {"value", "limit"}} for every number that has a limit, and
    whether all hold. A number that is not finite fails."""
    compared = {k: {"value": float(v), "limit": float(limits[k])}
                for k, v in numbers.items() if k in limits}
    missing = [k for k in limits if k not in numbers]
    ok = not missing and all(
        np.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in compared.values())
    return bool(ok), compared


def percentile(values, q):
    """Plain percentile over raw samples: the smallest sample with at least
    q% of the samples at or under it."""
    v = sorted(values)
    if not v:
        return None
    k = max(0, min(len(v) - 1, int(np.ceil(q / 100.0 * len(v))) - 1))
    return float(v[k])


def median(values):
    return float(statistics.median(values)) if len(values) else None
