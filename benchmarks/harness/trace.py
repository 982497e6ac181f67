"""From a profiler trace to device times. The reduction is a set of plain
functions on lists of [name, start_ns, duration_ns], so that it is checked
on a small recorded trace (tests/data) and every PR computes the same
number the same way.

A TPU plane carries several lines over the same time (steps, modules, ops):
they NEST, so time is never summed across lines. Busy time is the union of
the operation line's intervals; a program's time is its module events'.
"""
import glob
import math
import os
import re


def read_xplane(trace_dir):
    """{"devices": {id: {"ops": [...], "modules": [...]}}, "host": [...]}
    from the newest .xplane.pb under `trace_dir`."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        name = plane.name
        if name.startswith("/device:TPU:"):
            dev = out["devices"].setdefault(name.rsplit(":", 1)[1],
                                            {"ops": [], "modules": []})
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    dev[key] += [[e.name, e.start_ns, e.duration_ns]
                                 for e in line.events]
        elif name == "/host:CPU":
            for line in plane.lines:
                if line.name.startswith("tf_XLA"):
                    # the CPU backend's own worker threads: its "device"
                    # in a rehearsal, never reported as a device metric
                    ops = [[e.name, e.start_ns, e.duration_ns]
                           for e in line.events if e.duration_ns > 0]
                    if ops:
                        out["devices"].setdefault(
                            "cpu", {"ops": [], "modules": []})["ops"] += ops
                    continue
                out["host"] += [[e.name, e.start_ns, e.duration_ns]
                                for e in line.events if e.duration_ns > 0]
    return out


def union(intervals):
    """Sorted, merged [start, end) pairs of (start, duration) intervals."""
    merged = []
    for s, d in sorted((s, d) for _, s, d in intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], s + d)
        else:
            merged.append([s, s + d])
    return merged


def clip(events, t0, t1):
    """The parts of events inside [t0, t1)."""
    out = []
    for n, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append([n, a, b - a])
    return out


def busy_seconds(trace, t0, t1):
    """Seconds in which an operation ran, averaged over the devices."""
    per_dev = [sum(b - a for a, b in union(clip(d["ops"], t0, t1))) / 1e9
               for d in trace["devices"].values()]
    return sum(per_dev) / len(per_dev) if per_dev else 0.0


def short_name(name):
    """An event's name without its run-specific suffixes."""
    return name.split("(")[0].strip()


def op_label(name):
    """A device operation's kind and largest output, without what changes
    from compile to compile: the TPU names an event by its whole HLO line,
    `%fusion.54 = (f32[256]{..}, bf16[128,56,56,256]{..}) fusion(...)`,
    which reads here as `fusion bf16[128,56,56,256]`."""
    lhs, _, rhs = name.partition(" = ")
    kind = re.sub(r"\.\d+$", "", lhs.strip().lstrip("%"))
    if not rhs:
        return kind
    m = re.match(r"\((.*?)\) [\w\-]+\(", rhs)
    outs = re.findall(r"\w+\[[\d,]*\]", m.group(1) if m
                      else rhs.split(" ", 1)[0])
    size = lambda s: math.prod(int(d) for d in
                               re.findall(r"\d+", s.split("[", 1)[1]))
    return f"{kind} {max(outs, key=size)}" if outs else kind


def top_ops(trace, t0, t1, n=10):
    """[[label xCount, seconds]] of the operations that took most device
    time on the first device, instances of one label added up."""
    if not trace["devices"]:
        return []
    dev = trace["devices"][sorted(trace["devices"])[0]]
    total, count = {}, {}
    for name, _, d in clip(dev["ops"], t0, t1):
        k = op_label(name)
        total[k] = total.get(k, 0.0) + d / 1e9
        count[k] = count.get(k, 0) + 1
    return [[f"{k} x{count[k]}", v] for k, v in sorted(
        total.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace, t0, t1, n=10, named=100):
    """[[what the host was doing, seconds]]: the first device's idle gaps
    inside the window. Each of the `named` longest is named by the shortest
    host event that covers its middle, gaps of one name added up; the many
    short ones between operations are one entry (naming every gap of a
    step of some thousand operations would take minutes)."""
    if not trace["devices"]:
        return []
    dev = trace["devices"][sorted(trace["devices"])[0]]
    edges = [t0] + [t for ab in union(clip(dev["ops"], t0, t1))
                    for t in ab] + [t1]
    gaps = sorted(((b - a, a) for a, b in zip(edges[0::2], edges[1::2])
                   if b > a), reverse=True)
    host = sorted(trace["host"], key=lambda e: e[2])
    total = {}
    for length, a in gaps[:named]:
        mid = a + length / 2
        name = next((short_name(h[0]) for h in host
                     if h[1] <= mid < h[1] + h[2]), "unattributed")
        total[name] = total.get(name, 0.0) + length / 1e9
    if gaps[named:]:
        total[f"{len(gaps) - named} shorter gaps, each under "
              f"{gaps[named][0]:.0f} ns"] = sum(
                  g for g, _ in gaps[named:]) / 1e9
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]


def program_runs(trace, t0, t1, device=None):
    """{module name: [(start_ns, seconds) of each run wholly inside the
    window]} on one device (the first by default)."""
    if not trace["devices"]:
        return {}
    dev = trace["devices"][device or sorted(trace["devices"])[0]]
    runs = {}
    for name, s, d in dev["modules"]:
        if s >= t0 and s + d <= t1:
            runs.setdefault(name, []).append((s, d / 1e9))
    return runs


def seconds(runs):
    return [d for _, d in runs]


def annotation_window(trace, name):
    """(start_ns, end_ns) of the host annotation `name` (the traced part of
    the measured window), on the trace's clock."""
    hits = [(s, s + d) for n, s, d in trace["host"] if n == name]
    if not hits:
        raise RuntimeError(f"annotation {name!r} is not in the trace")
    return max(hits, key=lambda ab: ab[1] - ab[0])
