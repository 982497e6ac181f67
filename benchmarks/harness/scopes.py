"""Device time of the step by LAYER. The program traces every layer under a
`jax.named_scope` ("<kind>.<name>", "loss.<output>", "update"), which the
compiled step keeps as `op_name` metadata of each instruction; the device
trace names an operation by its HLO line without metadata. The two are
joined by instruction name: the cell's trainer is rebuilt as the driver
builds it, its step lowered for one zero batch of the cell's shape and
compiled (a hit in the persistent compile cache: the window ran this
program), and the compiled text handed to the program's own `op_scopes`.

A fusion is attributed to the scope its own metadata names, its root's;
XLA fuses across layers (the next batch norm's statistics ride on a
convolution as its epilogue), so the stderr line also says how much of each
kind's time is in fusions that hold batch-norm operations too. A program
that has no scopes (no `op_scopes` in optimize/profiler.py) gives no text
and every reader of it returns None.
"""
import bisect
import sys

COVERAGE_FLOOR = 0.9        # of the step's device time, found in the table
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def say(msg):
    print(f"scopes: {msg}", file=sys.stderr, flush=True)


def compiled_text(cell):
    """The text of the cell's compiled step, its instructions' metadata
    naming the program's layer scopes; None where the program has none."""
    try:
        from deeplearning4j_tpu.optimize.profiler import op_scopes, scope_of
    except ImportError:
        say("the program has no op_scopes: no layer scopes to read")
        return None
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from ..drivers.train import _build
    cfg, chips = cell["config"], cell["chips"]
    model = cfg["model"]
    batch = cfg["trainer"]["batch_per_chip"] * chips
    net, fit, mesh = _build(cfg, chips)
    ds = DataSet(
        jnp.zeros((batch, model["height"], model["width"],
                   model["channels"]), jnp.bfloat16),
        jnp.zeros((batch, model["num_classes"]), jnp.float32))
    # one chip: the container's own step; several: the wrapper's
    stepper = net if mesh is None else fit.__self__

    def scoped(text):
        return any(scope_of(path) for path in op_scopes(text).values())

    text = stepper.lower_step(ds).compile().as_text()
    if not scoped(text):
        # the cache key leaves metadata out, so a cache that another build
        # of the program filled serves its text; compile under a key that
        # counts metadata (kept in the cache for the next run), lowered
        # anew because jax keeps a lowering's executable in memory
        say("the cached step carries no scopes; compiling it again")
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
        jax.clear_caches()
        try:
            text = stepper.lower_step(ds).compile().as_text()
        finally:
            jax.config.update(
                "jax_compilation_cache_include_metadata_in_key", False)
    return text if scoped(text) else None


def step_intervals(ctx):
    """Sorted (start_ns, end_ns) of the whole runs, inside the traced
    window, of the module that took most of it (first device)."""
    from . import trace as T
    tr = ctx["trace"]
    runs = T.program_runs(tr["trace"], tr["t0"], tr["t1"])
    if not runs:
        return []
    most = max(runs.values(), key=lambda r: sum(T.seconds(r)))
    return sorted((s, s + round(d * 1e9)) for s, d in most)


def layer_times(ctx):
    """What the three scope readers share, computed once a run:
    {"steps", "step_ms", "coverage", "ms": {(kind, direction): ms a step},
    "collective_ms": {kind or None: ms a step}, "holds_batchnorm_ms": the
    part of "ms" in fusions that hold batch-norm operations without being
    rooted in one}, or None where there is nothing to read or the join
    covers under COVERAGE_FLOOR of the step."""
    if "layer_times" not in ctx:
        ctx["layer_times"] = _layer_times(ctx)
    return ctx["layer_times"]


def _layer_times(ctx):
    tr = ctx["trace"]
    runs = step_intervals(ctx)
    if not runs:
        say("no whole run of a step module inside the traced window (a CPU "
            "rehearsal has no device plane): nothing to attribute")
        return None
    if "step_text" not in ctx:
        ctx["step_text"] = compiled_text(ctx["cell"])
    if not ctx["step_text"]:
        return None
    from deeplearning4j_tpu.optimize.profiler import (
        fusion_contents, instruction_name, op_scopes, scope_of)
    table = op_scopes(ctx["step_text"])
    contents = fusion_contents(ctx["step_text"])

    def attribute(instr):
        """((kind, direction) or None, is a collective, holds batch-norm
        operations without being rooted in one) of an instruction that the
        table has; a step runs each some thirty times, so looked up once."""
        scope = scope_of(table[instr])
        holds = bool(scope) and scope[0] != "batchnorm" and any(
            (scope_of(p) or ("",))[0] == "batchnorm"
            for p in contents.get(instr, ()))
        return (scope and (scope[0], scope[2]),
                instr.startswith(COLLECTIVES), holds)

    dev = tr["trace"]["devices"][sorted(tr["trace"]["devices"])[0]]
    starts = [s for s, _ in runs]
    total = found = 0
    ms, coll, holds_bn, seen = {}, {}, {}, {}
    for name, s, d in dev["ops"]:
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or s >= runs[i][1]:
            continue                    # not inside a whole run of the step
        total += d
        instr = instruction_name(name)
        if instr not in table:
            continue
        found += d
        if instr not in seen:
            seen[instr] = attribute(instr)
        key, collective, holds = seen[instr]
        a_step = d / 1e6 / len(runs)
        if key:
            ms[key] = ms.get(key, 0.0) + a_step
            if holds:
                holds_bn[key] = holds_bn.get(key, 0.0) + a_step
        if collective:
            kind = key[0] if key else None
            coll[kind] = coll.get(kind, 0.0) + a_step
    if not total:
        return None
    out = {"steps": len(runs), "step_ms": total / 1e6 / len(runs),
           "coverage": found / total, "ms": ms, "collective_ms": coll,
           "holds_batchnorm_ms": holds_bn}
    line = lambda by: ", ".join(
        f"{k or 'no scope'} {dr or '-'} {v:.3f}" for (k, dr), v in sorted(
            by.items(), key=lambda kv: -kv[1]))
    say(f"{len(runs)} steps, {out['step_ms']:.3f} ms of operations a step, "
        f"{100 * out['coverage']:.2f}% joined; ms a step by the scope of "
        f"each operation's root: {line(ms)}")
    if holds_bn:
        say("of which fusions rooted elsewhere that hold batch-norm "
            f"operations too: {line(holds_bn)}")
    if coll:
        say("collectives, ms a step by scope: " + ", ".join(
            f"{k or 'no scope'} {v:.3f}" for k, v in sorted(
                coll.items(), key=lambda kv: -kv[1])))
    if out["coverage"] < COVERAGE_FLOOR:
        say(f"under {100 * COVERAGE_FLOOR:.0f}% of the step's device time "
            f"joins the compiled text: the scope metrics are left out")
        return None
    return out


def kind_ms(ctx, kind):
    """Device ms a step of the operations scoped in `kind`, forward and
    backward alike; None where no operation of the step is."""
    lt = layer_times(ctx)
    if lt is None:
        return None
    hits = [v for (k, _), v in lt["ms"].items() if k == kind]
    return sum(hits) if hits else None


def host_spans(ctx, name):
    """Sorted (start_ns, ns) of the program's annotation `name` that lie
    wholly inside the traced window (host plane)."""
    tr = ctx["trace"]
    t0, t1 = tr["t0"], tr["t1"]
    return sorted((s, d) for n, s, d in tr["trace"]["host"]
                  if n.split("#")[0] == name and s >= t0 and s + d <= t1)
