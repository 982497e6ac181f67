"""The profiler session of a `--trace 1` run: python tracing off (small
trace, undisturbed host), one annotation that marks the traced part of the
measured window on the trace's own clock."""
import os
import shutil
import time

TRACED = "bench.traced_window"


class Tracer:
    """start() before the window opens (the profiler's start-up is set-up),
    `with tracer.window():` around the traced part, result() after."""

    def __init__(self, enabled, out_dir):
        self.enabled = bool(enabled)
        self.dir = out_dir
        self.seconds = None
        self._trace = None
        self._stopped = False
        self.t_enter = None
        self.stop_s = self.read_s = 0.0

    def start(self):
        if not self.enabled:
            return
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def annotate(self, name):
        import contextlib
        if not self.enabled:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def window(self):
        self.t_enter = time.monotonic()
        return self.annotate(TRACED)

    def stop(self):
        """Stop the profiler. The trace is read later, by result(), once the
        window has closed: reading it is Python work that would take the
        interpreter from the threads that drive the load."""
        if self.enabled and not self._stopped:
            import jax
            t = time.monotonic()
            jax.profiler.stop_trace()
            self._stopped = True
            self.stop_s = time.monotonic() - t

    def result(self):
        """The reduced trace; the files are deleted (the host keeps every
        block once written). `clock_offset_s` takes a time on the trace's
        clock to time.monotonic()."""
        if not self.enabled:
            return None
        if self._trace is None:
            from . import trace as T
            self.stop()
            t = time.monotonic()
            tr = T.read_xplane(self.dir)
            self.read_s = time.monotonic() - t
            shutil.rmtree(self.dir, ignore_errors=True)
            t0, t1 = T.annotation_window(tr, TRACED)
            self._trace = {"trace": tr, "t0": t0, "t1": t1,
                           "window_s": (t1 - t0) / 1e9,
                           "busy_s": T.busy_seconds(tr, t0, t1),
                           "clock_offset_s": self.t_enter - t0 / 1e9}
        return self._trace


def now():
    return time.monotonic()


def memory_peak_bytes(devices):
    """The peak on the fullest chip. The allocator's `peak_bytes_in_use`
    counts buffers that live between programs (weights, state, caches,
    staged inputs) and NOT a running program's temporaries (PERF.md,
    Findings PR 24: a program with 512 MiB of temporaries moved it by 1 MiB),
    so the largest temporary allocation among the loaded programs is added:
    the step's activations are most of what a trainer holds. Read it before
    the reference compiles anything."""
    import jax.extend
    live = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    temps = [e.get_compiled_memory_stats().temp_size_in_bytes
             for e in jax.extend.backend.get_backend().live_executables()]
    return int(live + max(temps, default=0))
