"""The one general traffic generator. A traffic mix is a data file of
parameters; every run of it offers the SAME multiset of requests (length
pairs, shared documents) and of arrival gaps. `--seed` decides only their
order and the token ids. So runs differ by what a deployment's traffic
differs by from minute to minute, not by how much work they carry.
"""
import math
from statistics import NormalDist

import numpy as np


def quantile_lengths(spec, n):
    """n whole numbers: the (i + 1/2)/n quantiles of the distribution in
    `spec`, clipped to [min, max] and rounded to `multiple_of`."""
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        v = spec["min"] + u * (spec["max"] - spec["min"])
    elif spec["dist"] == "fixed":
        v = np.full(n, spec["value"], float)
    else:
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    m = spec.get("multiple_of", 1)
    v = np.round(v / m) * m
    return np.clip(v, spec["min"], spec["max"]).astype(int) \
        if "min" in spec else v.astype(int)


def exponential_gaps(n, total):
    """n gaps, the quantiles of an exponential, scaled to add up to `total`
    seconds: n arrivals at the running sums (the first at 0) all fall
    inside [0, total) whatever their order."""
    u = (np.arange(n) + 0.5) / n
    g = -np.log1p(-u)
    return g * (total / g.sum())


def request_multiset(traffic, n):
    """n (shared document or -1, own prompt length, output length), the same
    for every seed: quantile grids paired by the file's own pairing seed."""
    pair = np.random.default_rng(traffic.get("pairing_seed", 0))
    own = quantile_lengths(traffic["prompt"], n)
    out = pair.permutation(quantile_lengths(traffic["output"], n))
    docs = np.full(n, -1)
    if "documents" in traffic:
        docs = pair.permutation(np.arange(n) % traffic["documents"]["count"])
    return list(zip(docs.tolist(), own.tolist(), out.tolist()))


def build(traffic, seconds, seed, vocab, max_len):
    """{"ramp": [...], "window": [...]} of requests {"prompt", "max_new",
    "due" (seconds after its phase opens; None in a closed loop)}."""
    rng = np.random.default_rng(int(seed))
    docs = []
    if "documents" in traffic:
        d = traffic["documents"]
        docs = [rng.integers(1, vocab, n) for n in
                quantile_lengths(dict(d, dist="uniform"), d["count"])]

    def phase(n, length):
        reqs = request_multiset(traffic, n)
        order = rng.permutation(n)
        due = [None] * n
        if traffic["kind"] == "open_loop":
            gaps = exponential_gaps(n, length)[rng.permutation(n)]
            due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]).tolist()
        out = []
        for k, i in enumerate(order):
            doc, own, new = reqs[i]
            prompt = rng.integers(1, vocab, own)
            if doc >= 0:
                prompt = np.concatenate([docs[doc], prompt])
            if len(prompt) + new > max_len:
                raise ValueError("traffic file: prompt + output over the "
                                 "model's positions")
            out.append({"prompt": prompt.astype(np.int32), "max_new": int(new),
                        "due": due[k]})
        return out

    if traffic["kind"] == "open_loop":
        rate = traffic["rate_per_s"]
        ramp_s = traffic["ramp_seconds"]
        return {"ramp": phase(max(1, round(rate * ramp_s)), ramp_s),
                "window": phase(max(1, round(rate * seconds)), seconds)}
    if traffic["kind"] == "closed_loop":
        # clients cycle this sequence without end
        return {"ramp": [], "window": phase(traffic["requests"], None)}
    raise ValueError(f"unknown traffic kind {traffic['kind']!r}")
