"""Training-health watchdog: numerical-fault classification and response.

PR 1 (`common/resilience.py`) made the stack survive *infrastructure*
faults; this module makes it survive *numerical* ones — the failure class
production training logs are full of (PaLM's skip-and-rollback on loss
spikes, Chowdhery et al. 2022; the OPT-175B logbook's manual
restart-below-the-spike loop, Zhang et al. 2022). Three pieces:

  * Device side (`grad_health`, `gate_update`): the fused train step
    optionally emits a scalar health pytree — global/per-layer gradient
    norms, the score, and an all-finite flag — and applies the parameter/
    updater/model-state update *conditionally* (`jnp.where` on the
    all-finite predicate), so a poisoned batch is skipped inside one
    compiled program with no host round-trip. With the watchdog disarmed
    the step compiles the identical HLO as before (same contract as the
    activation-stats emission; pinned by test).

  * Host side (`TrainingHealthPolicy`): stateful classification of each
    step's health dict — NaN/Inf (the device already skipped), EMA-z-score
    loss spike, gradient-norm explosion — into an action: count-and-skip,
    rollback-to-last-good-round, or abort-after-N-consecutive with a loud
    diagnostic naming the offending rounds. stdlib only; the health values
    it reads may be jnp scalars (one `float()` sync per step).

  * Loop driver (`apply_policy`, `install`): the one action-dispatch shared
    by every training loop (MultiLayerNetwork/ComputationGraph `fit`,
    ParallelWrapper allreduce and k-local-steps modes, TrainingMaster).
    Rollback goes through the PR 1 round-checkpoint seam — a
    `ShardedCheckpointManager` restore of the newest round, which also
    rewinds rng and counters so the post-rollback stream replays exactly
    (the crash-resume bit-comparability bar).

Watchdog events (skips, spikes, rollbacks, validation rejects) are kept in
the policy's bounded event log; `ui/stats.py` StatsListener reads
`snapshot()` into each report so run health reaches the UI storage.
"""
from __future__ import annotations

import collections
import logging
import math
import threading

log = logging.getLogger(__name__)

# actions returned by TrainingHealthPolicy.observe / apply_policy
OK = "ok"            # healthy step
SKIP = "skip"        # non-finite: the device already skipped the update
SPIKE = "spike"      # divergence counted but not undone (no rollback seam)
ROLLBACK = "rollback"  # divergence: restore the last good round
ABORT = "abort"      # N consecutive unhealthy steps: stop the run


class TrainingDivergedError(RuntimeError):
    """Raised when the watchdog aborts a run after `max_consecutive_bad`
    consecutive unhealthy steps. The message names the offending rounds."""


# ---------------------------------------------------------------------------
# Device side — used INSIDE the fused (jitted) train step
# ---------------------------------------------------------------------------

def grad_health(grads, score):
    """Scalar health pytree of one step, computed on device.

    `grads` is the container's gradient pytree (list-of-dicts for
    MultiLayerNetwork, name-keyed dict-of-dicts for ComputationGraph).
    Returns {"score", "grad_norm", "layer_grad_norms", "all_finite"} —
    a few f32/bool scalars per layer, negligible device->host traffic.

    Finiteness is read off the squared-norm accumulation: squares are
    non-negative (no cancellation), so the total is non-finite iff some
    gradient element is NaN/Inf — or the norm itself overflowed f32,
    which is a gradient explosion and equally skip-worthy.
    """
    import jax.numpy as jnp
    if isinstance(grads, dict):
        items = list(grads.items())
    else:
        items = [(str(i), g) for i, g in enumerate(grads)]
    layer_norms = {}
    total_sq = jnp.asarray(0.0, jnp.float32)
    for name, group in items:
        sq = jnp.asarray(0.0, jnp.float32)
        for leaf in _leaves(group):
            sq = sq + jnp.sum(jnp.square(leaf.astype(jnp.float32)))
        layer_norms[name] = jnp.sqrt(sq)
        total_sq = total_sq + sq
    score32 = jnp.asarray(score, jnp.float32)
    return {
        "score": score32,
        "grad_norm": jnp.sqrt(total_sq),
        "layer_grad_norms": layer_norms,
        "all_finite": jnp.isfinite(total_sq) & jnp.isfinite(score32),
    }


def _leaves(tree):
    import jax
    return jax.tree.leaves(tree)


def rowwise_finite(tree, batch_axis=0):
    """Per-example finiteness of an inference OUTPUT pytree: bool [B]
    numpy vector, True where every leaf's row `b` is all-finite. The
    serving layer's optional output screen (`InferenceServer(
    screen_outputs=True)`) uses it to fail ONLY the poisoned requests in
    a micro-batch instead of the whole dispatch — the inference-side
    analog of the training watchdog's NaN/Inf skip. Host-side numpy on
    results that are already being shipped to callers, so it adds no
    device round-trip."""
    import numpy as np
    ok = None
    for leaf in _leaves(tree):
        a = np.asarray(leaf)
        if np.issubdtype(a.dtype, np.integer) or a.dtype == np.bool_:
            continue                  # ints/bools can't be non-finite
        if a.dtype.kind not in "fc":
            # ml_dtypes bfloat16/f8 (kind 'V'): no native isfinite — the
            # f32 cast is exact for them. Native f16/f32/f64/complex are
            # checked in their OWN precision (casting f64 to f32 would
            # flag finite values beyond f32 range as inf).
            a = a.astype(np.float32)
        axes = tuple(i for i in range(a.ndim) if i != batch_axis)
        row_ok = np.isfinite(a).all(axis=axes)
        ok = row_ok if ok is None else (ok & row_ok)
    return ok


def gate_update(ok, new_tree, old_tree):
    """Conditionally apply an update inside the compiled step: every leaf
    becomes `jnp.where(ok, new, old)`, so a step whose health predicate is
    False leaves params/updater-state/model-state bit-identical — no host
    round-trip, no recompile, no branch."""
    import jax
    import jax.numpy as jnp
    return jax.tree.map(lambda n, o: jnp.where(ok, n, o),
                        new_tree, old_tree)


# ---------------------------------------------------------------------------
# Host side — classification policy
# ---------------------------------------------------------------------------

class TrainingHealthPolicy:
    """Classify per-step health and decide the response.

    Classification (in precedence order):
      1. non-finite score/gradients -> SKIP (the device already withheld
         the update; the host counts it and moves on);
      2. gradient-norm explosion (`grad_norm_limit`) or loss spike (score
         more than `spike_zscore` EW-standard-deviations above the
         exponential moving average of *healthy* scores, after
         `warmup_steps` healthy observations) -> ROLLBACK (or SPIKE when
         the caller has no rollback seam / `rollback_on_spike=False`);
      3. `max_consecutive_bad` consecutive unhealthy steps -> ABORT
         (raised as TrainingDivergedError by `apply_policy`).

    The EMA baseline only ingests healthy steps, so a spike cannot poison
    its own detector. Counters and a bounded event log feed the UI
    (`snapshot()`); `record_validation_reject` lets the data-pipeline
    validator aggregate into the same run-health view.
    """

    def __init__(self, spike_zscore=6.0, ema_decay=0.9, warmup_steps=8,
                 grad_norm_limit=None, max_consecutive_bad=5,
                 rollback_on_spike=True, max_events=64):
        if max_consecutive_bad < 1:
            raise ValueError("max_consecutive_bad must be >= 1")
        self.spike_zscore = float(spike_zscore)
        self.ema_decay = float(ema_decay)
        self.warmup_steps = int(warmup_steps)
        self.grad_norm_limit = (None if grad_norm_limit is None
                                else float(grad_norm_limit))
        self.max_consecutive_bad = int(max_consecutive_bad)
        self.rollback_on_spike = bool(rollback_on_spike)
        self.counts = {"ok": 0, "skips": 0, "spikes": 0, "rollbacks": 0,
                       "aborts": 0, "validation_rejects": 0}
        self.events = collections.deque(maxlen=int(max_events))
        self.consecutive_bad = 0
        self._ema = None
        self._var = 0.0
        self._healthy_seen = 0
        # observe() runs on the training thread, but validation rejects
        # arrive from the async staging pool's threads — guard the shared
        # counters/events so concurrent rejects don't lose increments
        self._lock = threading.Lock()

    def _count(self, key, n=1):
        """Increment a health counter AND mirror it onto the process-wide
        metrics registry (`train.health.<key>`), so run-health shows up
        on the ui/server.py `/metrics` Prometheus route next to serving
        and transport counters — the one named surface."""
        self.counts[key] += n
        from ..obs.registry import default_registry
        default_registry().counter("train.health." + key).inc(n)

    # -- classification -------------------------------------------------
    def observe(self, health, round_index=None):
        """Classify one step. Returns OK / SKIP / SPIKE / ROLLBACK /
        ABORT. `health` is the step's emitted dict (jnp or python
        scalars)."""
        score = float(health["score"])
        grad_norm = float(health["grad_norm"])
        finite = bool(health["all_finite"])
        if not finite:
            bad = int(health.get("bad_steps", 1))
            steps = int(health.get("steps", 1))
            if 0 < bad < steps:
                # k-local-steps partial round: only some of the round's
                # local device-steps were non-finite, and each was already
                # skipped on ITS device — the averaged round still
                # progressed and its score covers the healthy steps.
                # Count the skips; don't escalate, don't starve the
                # checkpoint cadence. (The round's pmax grad-norm is
                # contaminated by the skipped step, so spike checks are
                # meaningless here and deliberately not applied.)
                self._count("skips", bad)
                self.consecutive_bad = 0
                self._event("skip", round_index,
                            reason=f"{bad}/{steps} local steps non-finite "
                                   "(partial round, average applied)",
                            score=score, gradNorm=grad_norm)
                log.warning("training-health partial skip at round %s: "
                            "%d/%d local steps non-finite", round_index,
                            bad, steps)
                return OK
            return self._unhealthy(SKIP, "non-finite score/gradients",
                                   round_index, score, grad_norm)
        reason = None
        if (self.grad_norm_limit is not None
                and grad_norm > self.grad_norm_limit):
            reason = (f"gradient norm {grad_norm:.4g} exceeds limit "
                      f"{self.grad_norm_limit:.4g}")
        else:
            z = self._zscore(score)
            if z is not None and z > self.spike_zscore:
                reason = (f"loss spike: score {score:.4g} is {z:.1f} "
                          f"EW-stdev above EMA {self._ema:.4g}")
        if reason is not None:
            want = ROLLBACK if self.rollback_on_spike else SPIKE
            return self._unhealthy(want, reason, round_index, score,
                                   grad_norm)
        self._count("ok")
        self.consecutive_bad = 0
        self._ingest(score)
        return OK

    def _zscore(self, score):
        if self._ema is None or self._healthy_seen < self.warmup_steps:
            return None
        std = math.sqrt(max(self._var, 0.0))
        scale = max(std, abs(self._ema) * 1e-3, 1e-12)
        return (score - self._ema) / scale

    def _ingest(self, score):
        self._healthy_seen += 1
        if self._ema is None:
            self._ema = score
            return
        d = self.ema_decay
        delta = score - self._ema
        self._ema += (1.0 - d) * delta
        self._var = d * (self._var + (1.0 - d) * delta * delta)

    def _unhealthy(self, want, reason, round_index, score, grad_norm):
        kind = "skip" if want == SKIP else "spike"
        self._count(kind + "s")
        self.consecutive_bad += 1
        self._event(kind, round_index, reason=reason, score=score,
                    gradNorm=grad_norm)
        log.warning("training-health %s at round %s: %s", kind,
                    round_index, reason)
        if self.consecutive_bad >= self.max_consecutive_bad:
            self._count("aborts")
            self._event("abort", round_index, reason=reason)
            return ABORT
        return want

    # -- bookkeeping hooks ----------------------------------------------
    def record_rollback(self, round_index, restored_round):
        self._count("rollbacks")
        self._event("rollback", round_index,
                    restoredRound=int(restored_round))
        log.warning("training-health rollback: round %s restored from "
                    "checkpointed round %s", round_index, restored_round)

    def record_validation_reject(self, reason, batch_index=None):
        with self._lock:
            self._count("validation_rejects")
        self._event("validation_reject", batch_index, reason=str(reason))

    def _event(self, kind, round_index, **meta):
        e = {"kind": kind,
             "round": None if round_index is None else int(round_index)}
        e.update(meta)
        with self._lock:
            self.events.append(e)

    # -- reporting ------------------------------------------------------
    def snapshot(self):
        """JSON-able run-health summary for the StatsListener report."""
        with self._lock:
            return {"counts": dict(self.counts),
                    "consecutiveBad": int(self.consecutive_bad),
                    "lastEvent": self.events[-1] if self.events else None}

    def diagnose(self):
        """Loud abort diagnostic naming the offending rounds."""
        with self._lock:       # a staging thread may be appending events
            events = list(self.events)
        bad = [e for e in events if e["kind"] in ("skip", "spike")]
        rounds = [e["round"] for e in bad[-self.consecutive_bad:]]
        last = bad[-1] if bad else {}
        return (f"training diverged: {self.consecutive_bad} consecutive "
                f"unhealthy steps (limit {self.max_consecutive_bad}); "
                f"offending rounds {rounds}; last: round {last.get('round')}"
                f" ({last.get('reason', 'unknown')})")


# ---------------------------------------------------------------------------
# Loop driver — shared by every training loop
# ---------------------------------------------------------------------------

def apply_policy(policy, health, round_index, rollback=None):
    """Classify one step and drive the host-side action. Returns the
    action actually taken (OK / SKIP / SPIKE / ROLLBACK); raises
    TrainingDivergedError on ABORT.

    `rollback` is the loop's seam to the last good round: a zero-arg
    callable returning the restored round number, or False/None when no
    checkpoint exists (the action then degrades to SPIKE: counted, params
    left as-is, escalating to abort if divergence persists).
    """
    action = policy.observe(health, round_index)
    if action == ABORT:
        raise TrainingDivergedError(policy.diagnose())
    if action == ROLLBACK:
        restored = rollback() if rollback is not None else None
        if restored is None or restored is False:
            log.warning("training-health: divergence at round %s but no "
                        "checkpoint to roll back to; counting and "
                        "continuing", round_index)
            return SPIKE
        policy.record_rollback(round_index, restored)
        return ROLLBACK
    return action


def install(net, policy=True, checkpoint_dir=None, checkpoint_every=10,
            keep_checkpoints=3):
    """Arm (or disarm) the training-health watchdog on a network — the one
    implementation behind the containers' `training_health`
    (nn/trainer.py) and ParallelWrapper's `health_policy`.

    policy: a TrainingHealthPolicy, True for the defaults, or None/False
    to disarm. checkpoint_dir (optional) gives the single-process fit
    loops their rollback seam: a ShardedCheckpointManager under it saves
    the full training state every `checkpoint_every` healthy iterations,
    and a divergence restores the newest save (params, updater state, rng
    AND counters — the post-rollback step stream replays exactly).
    Without it, divergence degrades to count-and-continue; ParallelWrapper
    and TrainingMaster supply their own round-checkpoint seam instead.

    Arming/disarming costs one recompile (the step's return pytree gains/
    loses the health scalars); the disarmed step compiles the identical
    HLO as a never-armed one.
    """
    if policy is True:
        policy = TrainingHealthPolicy()
    elif policy is False:
        policy = None
    armed = policy is not None
    net._health_policy = policy
    net._health_gen += 1
    net._jit_step = None                 # recompile with/without health
    net._health_ckpt = None
    net._health_ckpt_every = max(1, int(checkpoint_every))
    if armed and checkpoint_dir is not None:
        from ..util.sharded_checkpoint import ShardedCheckpointManager
        net._health_ckpt = ShardedCheckpointManager(
            str(checkpoint_dir), keep_last=max(1, int(keep_checkpoints)))
    return net


def finish_step(net, health, score):
    """The armed fit-loop step epilogue shared by MultiLayerNetwork and
    ComputationGraph (batch AND TBPTT loops): classify the emitted
    health, drive the host action through the net's checkpoint seam, and
    gate the score update (a skipped step's NaN must not become
    net._score). Returns the action — "rollback" means counters/rng were
    already restored and the caller must abandon the current
    batch/sequence; ABORT raises TrainingDivergedError."""
    rollback = None
    if net._health_ckpt is not None:
        def rollback():
            return fit_loop_rollback(net)
    action = apply_policy(net._health_policy, health,
                          round_index=net.conf.iteration_count,
                          rollback=rollback)
    if action not in (ROLLBACK, SKIP):
        net._score = score
    return action


def split_stacked(health, n_steps):
    """Per-step classification over a stacked report: a fused K-step
    dispatch emits its health scalars as scan ys (leading axis K); this
    materializes the WHOLE report with one device->host sync and splits
    it into K per-step dicts for `observe`/`finish_step` — the host-side
    cost per dispatch is one transfer, not K scalar readbacks."""
    import jax
    import numpy as np
    host = jax.tree.map(np.asarray, health)
    return [jax.tree.map(lambda a: a[i], host) for i in range(n_steps)]


def finish_fused(net, scores, health_stack, n_steps):
    """The fused-dispatch epilogue shared by MultiLayerNetwork and
    ComputationGraph (super-batch AND TBPTT fused paths): walk the K
    inner steps of one dispatch in order, updating the score, counters
    and listeners per OPTIMIZER STEP (StatsListener sees every step, not
    every dispatch) and — when armed — classifying each step's health
    exactly as the sequential loop would.

    Returns the inner index whose classification triggered a ROLLBACK
    (counters/rng already restored; the caller re-runs the REMAINING
    staged batches from the restored state so the stream matches K
    sequential dispatches), or None when every step was consumed. ABORT
    raises TrainingDivergedError, as in the sequential loop."""
    import numpy as np
    if health_stack is None and not net.listeners:
        # nothing consumes per-step scalars: DON'T materialize the
        # stacked scores — the np.asarray would block the training
        # thread on the whole dispatch, serializing host group-staging
        # with device compute (the sequential loop never syncs). The
        # score is the super-batch's last step's, read lazily.
        net._score = scores[n_steps - 1]
        net.conf.iteration_count += n_steps
        return None
    scores_np = np.asarray(scores)
    healths = (split_stacked(health_stack, n_steps)
               if health_stack is not None else None)
    action = OK
    for i in range(n_steps):
        if healths is None:
            net._score = scores_np[i]
            action = OK
        else:
            action = finish_step(net, healths[i], scores_np[i])
            if action == ROLLBACK:
                return i
        net.conf.iteration_count += 1
        for l in net.listeners:
            l.iteration_done(net, net.conf.iteration_count - 1)
    # groups are clipped at checkpoint boundaries (fused.group_size), so
    # a due save can only land on the LAST inner step — where the net's
    # in-memory state IS the post-due-step state
    if healths is not None and action == OK:
        fit_loop_checkpoint(net)
    return None


def fit_loop_rollback(net):
    """Single-process fit loops' rollback seam: restore the newest health
    checkpoint INTO the net (counters, rng and device loop state
    included). Returns the restored round (iteration) number, or False
    when no checkpoint exists yet."""
    mgr = net._health_ckpt
    if mgr is None or mgr.latest_step() is None:
        return False
    last = mgr.latest_step()
    mgr.restore(net, last)
    return last


def fit_loop_checkpoint(net):
    """Periodic save for the fit-loop seam: checkpoint the full training
    state at the current iteration count when due."""
    mgr = net._health_ckpt
    if mgr is None:
        return
    it = int(net.conf.iteration_count)
    if it % net._health_ckpt_every == 0:
        score = getattr(net, "_score", None)
        score = None if score is None else float(score)
        if score is not None and not math.isfinite(score):
            score = None       # a NaN score must not enter best-step math
        mgr.save(net, it, score=score)
