"""XLA profiler integration — the "deep profiler" (SURVEY.md §5.1).

The reference's profiling story is wall-clock listeners (PerformanceListener,
BaseStatsListener timing, Spark phase timelines). On TPU the equivalent deep
tool is the XLA device trace: this module wraps `jax.profiler` so a trace can
be captured from benchmarks/run.py or mid-training via a listener, and adds a
host-side summarizer that aggregates device-op time straight from the
captured `.xplane.pb` (so no TensorBoard UI is needed to see where a step's
time goes), by operation and, joined with the compiled step's own HLO, by
layer.

Usage:
    from deeplearning4j_tpu.optimize.profiler import (
        op_scopes, summarize_layers, summarize_trace, trace)
    with trace("/tmp/prof"):
        net.fit(ds)
    for row in summarize_trace("/tmp/prof")[:20]:
        print(row)
    scopes = op_scopes(net.lower_step(ds).compile().as_text())
    for row in summarize_layers("/tmp/prof", scopes):
        print(row)

or attach `ProfilerListener("/tmp/prof", start_iteration=5, num_iterations=3)`
to any model — it starts the trace when the start iteration is reached and
stops it `num_iterations` later (the reference pattern of sampling a steady-
state window, not the compile-heavy first steps).
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
from collections import defaultdict

import jax

from .listeners import IterationListener


@contextlib.contextmanager
def trace(logdir):
    """Capture an XLA device trace into `logdir` (TensorBoard-compatible)."""
    jax.profiler.start_trace(logdir)
    try:
        yield logdir
    finally:
        jax.profiler.stop_trace()


class ProfilerListener(IterationListener):
    """Trace a steady-state window of training iterations.

    reference role: PerformanceListener tells you *that* iterations are slow;
    this tells you *why* (per-op device time)."""

    def __init__(self, logdir, start_iteration=5, num_iterations=3):
        self.logdir = str(logdir)
        self.start_iteration = int(start_iteration)
        self.num_iterations = int(num_iterations)
        self._seen = 0
        self._active = False
        self.done = False

    def iteration_done(self, model, iteration):
        self._seen += 1
        if self.done:
            return
        if not self._active and self._seen >= self.start_iteration:
            jax.profiler.start_trace(self.logdir)
            self._active = True
            self._stop_at = self._seen + self.num_iterations
        elif self._active and self._seen >= self._stop_at:
            # barrier so the traced window contains completed device work
            jax.block_until_ready(model._params)
            jax.profiler.stop_trace()
            self._active = False
            self.done = True


def _find(logdir, pattern):
    return sorted(glob.glob(os.path.join(
        str(logdir), "**", pattern), recursive=True))


def _rows(keyed_ms):
    """[{"name", "total_ms", "count", "pct"}] of (key, milliseconds) pairs,
    instances of one key added up, longest first."""
    totals, counts = defaultdict(float), defaultdict(int)
    for key, ms in keyed_ms:
        totals[key] += ms
        counts[key] += 1
    grand = sum(totals.values()) or 1.0
    rows = [{"name": k, "total_ms": round(v, 3), "count": counts[k],
             "pct": round(100.0 * v / grand, 2)}
            for k, v in totals.items()]
    rows.sort(key=lambda r: -r["total_ms"])
    return rows


def instruction_name(event_name):
    """The HLO instruction an operation event ran. The TPU names an event by
    its whole HLO line WITHOUT metadata (`%fusion.54 = bf16[..] fusion(..),
    kind=kOutput, calls=..`); the instruction's name is what stands before
    ` = `, and is what joins the event to the compiled module's text."""
    return event_name.partition(" = ")[0].strip().lstrip("%")


def _device_ops(logdir):
    """[(event name, milliseconds)] of the OPERATION line of every device
    plane in the newest `.xplane.pb` under `logdir`. A device plane carries
    several lines over the same time (steps, modules, operations; the
    asynchronous copies' line): they nest, so only this one line is read
    and no time is counted twice."""
    from jax.profiler import ProfileData
    paths = _find(logdir, "*.xplane.pb")
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    ops = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                ops += [(e.name, e.duration_ns / 1e6) for e in line.events]
    return ops


def summarize_trace(logdir, merge_fusion_names=True):
    """Aggregate per-op device time from the newest trace under `logdir`.

    Returns a list of dicts sorted by total device time descending:
    {"name", "total_ms", "count", "pct"}; the totals add up to the device's
    operation line. `merge_fusion_names` strips the trailing ".NN" so that
    repeated fusions aggregate ("fusion.123" -> "fusion"). Reads the
    `.xplane.pb` with `jax.profiler.ProfileData`: no TensorBoard, no
    TensorFlow.
    """
    strip = (lambda n: re.sub(r"\.\d+$", "", n)) if merge_fusion_names \
        else (lambda n: n)
    return _rows((strip(instruction_name(name)), ms)
                 for name, ms in _device_ops(logdir))


# ---------------------------------------------------------------------------
# by layer: the containers put every layer under `jax.named_scope`
# ("<kind>.<name>", nn/conf/layers/base.py layer_scope; "loss.<output>",
# "update", "health"), which the compiled HLO keeps as op_name metadata
# ---------------------------------------------------------------------------
_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?metadata=\{[^}]*?op_name="([^"]*)"',
    re.M)
_SCOPE = re.compile(
    r"^(transpose\()?(jvp\()?([a-z][a-z0-9_]*)(?:\.([^()<>]+))?\)*$")
UNSCOPED = "(no scope)"


def op_scopes(hlo_text):
    """{instruction name: op_name path} of every instruction of a compiled
    module's text (`lowered.compile().as_text()`) that carries metadata. A
    fusion's metadata is its root's, so a fusion belongs to the scope its
    root was traced in."""
    return {m.group(1): m.group(2) for m in _INSTRUCTION.finditer(hlo_text)}


_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) \(.*\) -> .*\{\s*$")
_CALLS = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?\bcalls=%?([\w.\-]+)")


def fusion_contents(hlo_text):
    """{fusion instruction name: [op_name paths of the instructions inside
    the computation it calls]}. A fusion's own metadata names one scope, its
    root's; XLA fuses across layers (a convolution with the next batch
    norm's statistics as its epilogue), and this says what else is in it."""
    inside, current, calls = {}, None, {}
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            current = inside.setdefault(m.group(1), [])
        elif line.startswith("}"):
            current = None
        else:
            c = _CALLS.match(line)
            if c:
                calls[c.group(1)] = c.group(2)
            if current is not None:
                i = _INSTRUCTION.match(line)
                if i:
                    current.append(i.group(2))
    return {name: inside.get(comp, []) for name, comp in calls.items()}


def scope_of(op_name):
    """(kind, name, direction) of the outermost layer scope in an op_name
    path, or None: `jit(step)/jvp(batchnorm.stem_bn)/mul` -> ("batchnorm",
    "stem_bn", "forward"); `.../transpose(jvp(convolution.a))/..` ->
    (.., "backward"); `jit(step)/update/sub` -> ("update", None, "").
    The last part is the primitive, never a scope (the compiler names what
    it adds itself `broadcast.202`), and a function's qualified name
    (`image_ring.<locals>.make`, which jax puts on an inner jit's
    operations) is not one either."""
    for part in op_name.split("/")[:-1]:
        m = _SCOPE.match(part)
        if m and (m.group(4) is not None
                  or m.group(3) in ("update", "health")):
            direction = ("backward" if m.group(1) else
                         "forward" if m.group(2) or m.group(4) else "")
            return m.group(3), m.group(4), direction
    return None


def scope_label(op_name):
    """"<kind> forward|backward" of an op_name path's layer scope (see
    `scope_of`), or `UNSCOPED`: the row an operation is summed under."""
    found = scope_of(op_name)
    return f"{found[0]} {found[2]}".strip() if found else UNSCOPED


def summarize_layers(logdir, scopes):
    """The same operation line by layer kind, forward and backward apart:
    rows {"name": "<kind> forward|backward", "total_ms", "count", "pct"}
    from `scopes` (see `op_scopes`; the compiled text of the step that was
    traced). Operations whose instruction is not in the table, or whose
    op_name holds no layer scope, are the row `UNSCOPED`."""
    return _rows((scope_label(scopes.get(instruction_name(name), "")), ms)
                 for name, ms in _device_ops(logdir))
