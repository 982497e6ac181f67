"""Fused multi-step training: K optimizer steps per device dispatch.

The r5 trace work showed the dispatch-bound configs (LeNet, char-RNN,
decode — everything whose step is small) measure HOST DISPATCH, not the
framework: one jitted call per optimizer step is one host round-trip.
The reference's own answer was batching work behind one native call
(AggregateSkipGram's batched pair kernel, ParallelWrapper's
averaging-interval of local steps); `parallel/parallel_wrapper.py`
already runs k local steps in one `lax.scan` program — this module gives
the SINGLE-PROCESS fit loops (the Trainer's, nn/trainer.py, behind
MultiLayerNetwork.fit and ComputationGraph.fit) the same shape:

  * the fit loop stages K batches (the AsyncDataSetIterator machinery —
    prefetch thread, wire-dtype levers, device staging — unchanged),
    stacks them into a [K, B, ...] super-batch, and
  * ONE donated jitted program `lax.scan`s the container's existing raw
    step over the K batches: the per-step rng split, iteration advance,
    updater math and (when armed) the training-health `gate_update` skip
    all run INSIDE the scan, exactly as they run per-dispatch today.

Contracts (pinned by tests/test_fused_steps.py):

  * `fused_steps=K` is BIT-IDENTICAL to K sequential single-step
    dispatches — params, updater state, model state, rng stream,
    iteration counters, health counters. The scan body IS the raw step;
    nothing is reassociated.
  * `fused_steps=1` leaves the single-step program untouched — the fit
    loops never build a scan, and the compiled HLO is identical to
    today's (the `collect_acts`/`emit_health` pin style).
  * Per-inner-step health scalars come out as scan `ys`; the host
    classifies the stacked report step-by-step after the dispatch
    (`common.health.finish_fused`), so listeners/StatsListener see every
    optimizer step, not every dispatch.
  * A ragged tail (K not dividing the epoch, or a short last batch)
    falls back to single-step dispatches; when the health watchdog has a
    checkpoint seam, groups are clipped at checkpoint boundaries so the
    checkpoint cadence stays counted in OPTIMIZER STEPS and a due
    round's saved state is exact (not post-K).

CPU-backend honesty: XLA:CPU runs `while`-loop bodies single-threaded,
so fusing a COMPUTE-bound step (ResNet, LeNet bf16) can lose on the CPU
backend even though the dispatch count drops; the win there is real only
for dispatch-dominated steps. On TPU the scan body uses the same
hardware as the standalone step. See PERF.md "fused multi-step".
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def scan_steps(raw, params, ustate, state, loop, carries, xs, make_batch):
    """`lax.scan` the container's raw step over a stream of per-step xs.

    `raw` is `make_raw_step(...)`'s un-jitted step; `make_batch(x)` turns
    one scan slice into the raw step's batch dict (features/labels/masks
    — iteration/rng/carries are filled in here). Returns the single-step
    jit's tuple shape with stacked ys:
    (params', ustate', state', scores [K], carries', loop') + extras,
    where extras is the stacked health pytree when the raw step emits it.
    """
    def body(carry, x):
        params, ustate, state, loop, carries = carry
        # same per-step rng/iteration advance as the single-step program
        # (see Trainer._make_step) — the stream is bit-identical
        rng, next_rng = jax.random.split(loop["rng"])
        batch = make_batch(x)
        batch["iteration"] = loop["iteration"]
        batch["rng"] = rng
        batch["carries"] = carries
        p, u, s, score, car, *extras = raw(params, ustate, state, batch)
        new_loop = {"iteration": loop["iteration"] + 1.0, "rng": next_rng}
        return (p, u, s, new_loop, car), (score,) + tuple(extras)

    (p, u, s, loop, car), ys = jax.lax.scan(
        body, (params, ustate, state, loop, carries), xs)
    return (p, u, s, ys[0], car, loop) + tuple(ys[1:])


def scan_batches(raw, params, ustate, state, loop, batch_list):
    """scan_steps over a TUPLE of per-batch trees, stacked INSIDE the
    traced program: an eager jnp.stack on the host costs ~10 op
    dispatches per group (measured ~1 ms on the CPU backend — more than
    the dispatch overhead fusing removes); as jit arguments the K
    batches flatten into the one call and XLA materializes the [K, ...]
    stack on device."""
    xs = jax.tree.map(lambda *ls: jnp.stack(ls), *batch_list)
    return scan_steps(raw, params, ustate, state, loop, None, xs, dict)


def batch_signature(ds):
    """Shape/dtype signature of a DataSet/MultiDataSet used to decide
    whether K staged batches can share one compiled super-batch program
    (mismatch -> the group falls back to single-step dispatches). Reads
    shapes/dtypes off the (possibly device-resident) arrays without
    copying them to host."""
    def sig(a):
        if a is None:
            return None
        if isinstance(a, (list, tuple)):
            return tuple(sig(x) for x in a)
        if isinstance(a, dict):
            return tuple(sorted((k, sig(v)) for k, v in a.items()))
        return (tuple(np.shape(a)), str(getattr(a, "dtype", "")))

    masks = (getattr(ds, "features_mask", None),
             getattr(ds, "labels_mask", None),
             getattr(ds, "features_masks", None),
             getattr(ds, "labels_masks", None))
    return (sig(ds.features), sig(ds.labels), sig(masks))


def uniform_group(group):
    """True when every batch in the group matches the first one's
    signature (one compiled program covers the whole super-batch)."""
    first = batch_signature(group[0])
    return all(batch_signature(ds) == first for ds in group[1:])


def group_size(net, k):
    """Effective fused-group size at the net's current position: `k`,
    clipped to the next health-checkpoint boundary when the watchdog has
    a checkpoint seam — a due round's checkpoint must save the EXACT
    post-due-step state (which only exists at a dispatch boundary), and
    the cadence stays counted in optimizer steps, never stretched by K."""
    if net._health_ckpt is None:
        return k
    every = net._health_ckpt_every
    done = int(net.conf.iteration_count) % every
    return max(1, min(k, every - done))


def install(net, k):
    """The implementation behind the containers' `fused_steps`
    (nn/trainer.py): record K and invalidate the cached
    fused programs (the single-step program is untouched — fused_steps=1
    compiles the identical HLO as never-armed, pinned by test)."""
    k = max(1, int(k))
    if k != net._fused_steps:
        net._fused_steps = k
        net._fused_cache = None
    return net


def fused_program(net, key, builder):
    """Per-net cache of compiled fused programs, invalidated when the
    health watchdog or activation-stats mode toggles (the same
    generation counters ParallelWrapper watches)."""
    gen = (net._health_gen, net._act_stats_gen)
    cache = net._fused_cache
    if cache is None or cache.get("gen") != gen:
        cache = {"gen": gen}
        net._fused_cache = cache
    if key not in cache:
        # the build is a `jax.jit` wrapper and compiles at its first
        # dispatch like every other program: `Trainer._dispatch` is where
        # a trace shows it (`train.compile`), and a toggle that thrashes
        # this cache shows there as a dispatch that compiled again
        cache[key] = builder()
    return cache[key]
