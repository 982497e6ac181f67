"""Compile events: what jax traces, lowers and compiles, counted and named.

jax publishes every trace, lowering and backend compile of the process
through `jax.monitoring`, with the function's name, and every hit or miss of
the persistent compilation cache. The package root registers this module's
four `on_*` functions as listeners there (obs/ itself imports no jax: the
listeners are handed plain numbers and strings), and from then on, with no
switch:

  * the process-wide counters of `default_registry()` move: `compile.programs`
    (backend-compile events: on a cache hit, the load of the executable),
    `compile.trace_s`, `compile.lower_s`, `compile.backend_s`,
    `compile.cache_hits`, `compile.cache_misses`, `compile.cache_retrieval_s`;
    `ui/server.py`'s `/metrics` route serves them as every other counter;
  * each backend compile is one span `compile.backend` (cat `compile`, args
    `fun_name` and `seconds`) on `obs.TRACER`, from the event's own start and
    end moved onto `time.monotonic_ns()`'s clock: with tracing on, every
    compile of the process lies by name beside `train.dispatch`;
  * a dispatch site of a training program brackets its call with
    `mark()` / `dispatched()`: when the calling thread traced or compiled
    anything in between, the site's dispatch becomes one `train.compile`
    span and moves the `train.compile*` counters. A dispatch that compiled
    nothing pays two reads of a thread-local, one of the clock and a compare.

Seconds are EXCLUSIVE: jax's trace events nest (a jitted function traced
inside another's trace fires its own event within the outer one's interval,
and an eager operation inside a trace compiles within it), so a phase that
ends gives its whole length to the phase it ran inside and keeps only the
rest for itself. The three phase counters therefore add up to no more than
the wall time of the thread they ran on. The scalar jax records when a phase
begins is what makes that exact. Totals are kept a thread, because "this
dispatch compiled" is a statement about the calling thread: a prefetch
thread that compiles a pre-processor never turns a step into a compile.
"""
from __future__ import annotations

import collections
import threading
import time

from .registry import default_registry
from .trace import monotonic_ns

# jax._src.dispatch's three phases of a compile, and the cache's events
_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_s",
}
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"

Totals = collections.namedtuple(
    "Totals", ["events", "programs", "trace_s", "lower_s", "backend_s",
               "cache_hits", "cache_misses", "cache_retrieval_s"])
_ZERO = Totals(0, 0, 0.0, 0.0, 0.0, 0, 0, 0.0)


class _Thread(threading.local):
    def __init__(self):
        self.open = []          # [phase, seconds of the phases inside it]
        self.totals = _ZERO     # replaced, never mutated: a read is a mark


_thread = _Thread()


def _add(**deltas):
    """Move the calling thread's totals and the process-wide counters."""
    t = _thread.totals
    _thread.totals = t._replace(
        events=t.events + 1,
        **{k: getattr(t, k) + v for k, v in deltas.items()})
    registry = default_registry()
    for k, v in deltas.items():
        registry.counter("compile." + k).inc(v)


# -- the four listeners (jax.monitoring's protocols) ----------------------
def on_scalar(event, value, **kw):
    """A phase begins (jax records its start time as a scalar)."""
    if event in _PHASES:
        _thread.open.append([_PHASES[event], 0.0])


def on_time_span(event, start_time, end_time, fun_name="", **kw):
    """A phase ends: its own seconds to its counter, its whole length to
    the phase around it; a backend compile is a span on the tracer too."""
    phase = _PHASES.get(event)
    if phase is None:
        return
    seconds, inside = end_time - start_time, 0.0
    stack = _thread.open
    while stack:                    # a lost end leaves its entry: skip it
        name, spent = stack.pop()
        if name == phase:
            inside = spent
            break
    if stack:
        stack[-1][1] += seconds
    own = max(seconds - inside, 0.0)
    if phase != "backend_s":
        _add(**{phase: own})
        return
    _add(programs=1, backend_s=own)
    from . import TRACER            # looked up now: tests swap it
    if TRACER.enabled:
        # the event's clock is time.time(): place it by its distance from now
        t0 = monotonic_ns() - int((time.time() - start_time) * 1e9)
        TRACER.emit("compile.backend", t0, int(seconds * 1e9), cat="compile",
                    args={"fun_name": fun_name, "seconds": seconds})


def on_duration(event, duration_secs, **kw):
    if event == _RETRIEVAL:
        _add(cache_retrieval_s=duration_secs)


def on_event(event, **kw):
    if event in _CACHE_EVENTS:
        _add(**{_CACHE_EVENTS[event]: 1})


# -- the dispatch sites' bracket ------------------------------------------
def mark():
    """Before a dispatch: the calling thread's totals and the clock."""
    return _thread.totals, monotonic_ns()


def dispatched(mark, step, **args):
    """After the dispatch `mark` was taken for: if the thread traced or
    compiled since, the dispatch is a `train.compile` span over that
    interval (args: the program's name, `cache` hit / miss / off, the
    exclusive seconds of each phase) and the `train.compile*` counters
    move. Returns whether it compiled."""
    before, t0 = mark
    after = _thread.totals
    if after is before:
        return False
    dur_ns = monotonic_ns() - t0
    d = Totals(*(a - b for a, b in zip(after, before)))
    registry = default_registry()
    for name, v in (("compiles", 1), ("compile_cache_misses", d.cache_misses),
                    ("compile_s", dur_ns / 1e9),
                    ("compile_trace_s", d.trace_s),
                    ("compile_lower_s", d.lower_s),
                    ("compile_backend_s", d.backend_s)):
        registry.counter("train." + name).inc(v)
    from . import TRACER
    TRACER.emit("train.compile", t0, dur_ns, cat="train", args=dict(
        args, program=getattr(step, "__name__", type(step).__name__),
        cache=("miss" if d.cache_misses else "hit" if d.cache_hits
               else "off"),
        programs=d.programs, trace_s=d.trace_s, lower_s=d.lower_s,
        backend_s=d.backend_s, retrieval_s=d.cache_retrieval_s))
    return True
