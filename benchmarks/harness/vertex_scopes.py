"""Device time of the step by VERTEX: the container's own scopes,
`<kind>.<vertex name>` and `loss.<output>`, name and all.
`harness/inner_scopes.py` reads the same join (the trace's operations by
instruction name against the compiled step's text, `ctx["step_text"]`) and
drops the vertex's name; a part of a model that is a set of vertices and no
kind of its own (a prediction module: its embedding, norms, projection,
decoder layer and head are kinds the main model has too) is told apart by
the names alone. As there: an operation that only contains others is left
out, a scope is looked for anywhere in the path, and a program without
such scopes gives nothing to read.
"""
import bisect
import re

from . import scopes
from .inner_scopes import CONTAINERS

PART = re.compile(r"(?:^|/)(?:transpose\()?(?:jvp\()?([a-z_]+\.\w+)\)*(?=/|$)")


def vertex_times(ctx):
    """{frozenset of the `<kind>.<vertex>` scopes on an operation's path:
    device ms a step} over the whole runs of the step module inside the
    traced window, computed once a run; None where there is no trace, no
    text or no scopes to join."""
    if "vertex_times" not in ctx:
        ctx["vertex_times"] = _vertex_times(ctx)
    return ctx["vertex_times"]


def _vertex_times(ctx):
    text = ctx.get("step_text")
    runs = scopes.step_intervals(ctx) if ctx.get("trace") else []
    if not text or not runs:
        return None
    try:
        from deeplearning4j_tpu.optimize.profiler import (instruction_name,
                                                          op_scopes)
    except ImportError:
        return None
    table = op_scopes(text)
    tr = ctx["trace"]
    dev = tr["trace"]["devices"][sorted(tr["trace"]["devices"])[0]]
    starts = [s for s, _ in runs]
    names, out = {}, {}
    for name, s, d in dev["ops"]:
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or s >= runs[i][1]:
            continue
        instr = instruction_name(name)
        if instr not in table or instr.startswith(CONTAINERS):
            continue
        if instr not in names:
            names[instr] = frozenset(PART.findall(table[instr]))
        out[names[instr]] = out.get(names[instr], 0.0) + d / 1e6 / len(runs)
    return out or None


def vertices_ms(ctx, wanted):
    """Device ms a step of the operations on whose path a scope stands
    whose vertex (the part after the kind's dot) `wanted` accepts, each
    operation once; None where no such vertex is in the step."""
    t = vertex_times(ctx)
    if t is None:
        return None
    hits = [ms for on_path, ms in t.items()
            if any(wanted(*n.split(".", 1)) for n in on_path)]
    return sum(hits) if hits else None
