"""Training listeners.

TPU-native equivalent of reference optimize/api/IterationListener +
TrainingListener and the stock implementations in optimize/listeners/
(ScoreIterationListener, PerformanceListener, CollectScoresIterationListener,
ComposableIterationListener).

Listener hooks fire on host between jitted steps; score device->host sync is
deferred (jax async dispatch) unless a listener actually reads it.
"""
from __future__ import annotations

import logging
import time

log = logging.getLogger(__name__)


class IterationListener:
    """reference: optimize/api/IterationListener.java"""

    def iteration_done(self, model, iteration):
        pass


class TrainingListener(IterationListener):
    """reference: optimize/api/TrainingListener.java (epoch/forward/backward hooks)"""

    def on_epoch_start(self, model):
        pass

    def on_epoch_end(self, model):
        pass

    def on_forward_pass(self, model, activations):
        pass

    def on_gradient_calculation(self, model):
        pass

    def on_backward_pass(self, model):
        pass


class ScoreIterationListener(IterationListener):
    """Log score every N iterations (reference:
    optimize/listeners/ScoreIterationListener.java)."""

    def __init__(self, print_iterations=10):
        self.print_iterations = max(1, int(print_iterations))

    def iteration_done(self, model, iteration):
        if iteration % self.print_iterations == 0:
            log.info("Score at iteration %d is %s", iteration, float(model.score()))


class PerformanceListener(IterationListener):
    """Throughput instrumentation (reference:
    optimize/listeners/PerformanceListener.java — time/batch, samples/sec,
    batches/sec)."""

    def __init__(self, frequency=1, report_score=False):
        self.frequency = max(1, int(frequency))
        self.report_score = report_score
        self.last_time = None
        self.samples_per_sec = 0.0
        self.batches_per_sec = 0.0
        self.history = []

    def iteration_done(self, model, iteration):
        now = time.perf_counter()
        if self.last_time is not None:
            dt = now - self.last_time
            batch_size = getattr(model, "_last_batch_size", 0)
            if dt > 0:
                self.samples_per_sec = batch_size / dt
                self.batches_per_sec = 1.0 / dt
                self.history.append((iteration, dt, self.samples_per_sec))
            if iteration % self.frequency == 0:
                msg = (f"iteration {iteration}; iteration time: {dt*1000:.2f} ms; "
                       f"samples/sec: {self.samples_per_sec:.2f}; "
                       f"batches/sec: {self.batches_per_sec:.2f}")
                if self.report_score:
                    msg += f"; score: {float(model.score())}"
                log.info(msg)
        self.last_time = now


class CollectScoresIterationListener(IterationListener):
    """reference: optimize/listeners/CollectScoresIterationListener.java"""

    def __init__(self, frequency=1):
        self.frequency = max(1, int(frequency))
        self.scores = []

    def iteration_done(self, model, iteration):
        if iteration % self.frequency == 0:
            self.scores.append((iteration, float(model.score())))


class ComposableIterationListener(IterationListener):
    """reference: optimize/listeners/ComposableIterationListener.java"""

    def __init__(self, *listeners):
        self.listeners = list(listeners)

    def iteration_done(self, model, iteration):
        for l in self.listeners:
            l.iteration_done(model, iteration)
