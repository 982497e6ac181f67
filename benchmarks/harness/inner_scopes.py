"""Device time of the step by the program's INNER scopes: the
`jax.named_scope`s a layer opens inside its own `<kind>.<vertex>` scope
(`indexer`, `select`, `attend` in a `sparseattention` layer; `router`,
`experts` in a `moe` layer). `harness/scopes.py` attributes an operation to
its outermost layer scope; this reads the same join (the trace's operations
by instruction name against the compiled step's text, which the driver
supplies as `ctx["step_text"]`) for any name of the path, a layer's kind
among them. Two things differ from `scopes.py`, both because this model's
step has loops in it: an operation that only CONTAINS others (`while`,
`conditional`, `call`) is left out, since its children are on the same line
of the trace and its time is theirs again; and, since under
rematerialisation jax drops the layer's own scope from some paths and keeps
the inner one, a name is looked for anywhere in the path (the inner names
are used by these layers alone). A program without such scopes gives
nothing to read and every reader returns None.
"""
import bisect
import re

from . import scopes


CONTAINERS = ("while", "conditional", "call")


def inner_times(ctx):
    """{frozenset of the scope names and layer kinds on an operation's path:
    device ms a step} over the whole runs of the step module inside the
    traced window, computed once a run; None where there is no trace, no
    text or no scopes to join."""
    if "inner_times" not in ctx:
        ctx["inner_times"] = _inner_times(ctx)
    return ctx["inner_times"]


def _inner_times(ctx):
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
    part = re.compile(
        r"(?:^|/)(?:transpose\()?(?:jvp\()?([a-z_]+)(?:\.\w+)?\)*(?=/)")
    names, out = {}, {}
    for name, s, d in dev["ops"]:
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or s >= runs[i][1]:
            continue
        instr = instruction_name(name)
        if instr not in table or instr.startswith(CONTAINERS):
            continue
        if instr not in names:
            names[instr] = frozenset(part.findall(table[instr]))
        out[names[instr]] = out.get(names[instr], 0.0) + d / 1e6 / len(runs)
    if out:
        by_name = {}
        for on_path, ms in out.items():
            for n in on_path - {"jit", "checkpoint", "rematted_computation",
                                "while", "body", "cond", "closed_call",
                                "branch", "remat", "pjit"}:
                by_name[n] = by_name.get(n, 0.0) + ms
        scopes.say("ms a step by every name on an operation's path (loops' "
                   "own events left out; an operation counts under each of "
                   "its names): " + ", ".join(
                       f"{n} {v:.3f}" for n, v in sorted(
                           by_name.items(), key=lambda kv: -kv[1])[:24]))
    return out or None


def inner_ms(ctx, *names):
    """Device ms a step of the operations whose path holds any of `names`
    (inner scopes or layer kinds), each operation once; None where none of
    them is in the step."""
    t = inner_times(ctx)
    if t is None:
        return None
    hits = [ms for on_path, ms in t.items() if on_path & set(names)]
    return sum(hits) if hits else None
