"""Driver of the training cells: a ComputationGraph from the zoo, stepped by
`fit` (one chip) or `ParallelWrapper.fit` (several) through a ring of seeded
batches staged on the device, for the whole window.

Set-up builds ONE trainer, drives it through its first steps by the window's
own call and feed, and hands that same object to the window. After the
window the plain reference follows the first three steps from the same
seeded weights and the two are compared (harness/compare.py).
"""
import gc
import importlib

import jax
import jax.numpy as jnp
import numpy as np

from ..harness import compare, loader, weights, work
from ..harness.window import memory_peak_bytes, now

FOLLOWED = 3            # steps the reference follows


def _build(cfg, chips):
    mod, fn = cfg["program"]["conf"].split(":")
    conf = getattr(importlib.import_module(mod), fn)(**cfg["program"]["args"])
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    net = ComputationGraph(conf).init()
    if chips == 1:
        return net, net.fit, None
    from deeplearning4j_tpu.parallel.parallel_wrapper import ParallelWrapper
    pw = (ParallelWrapper.Builder(net).workers(chips)
          .averaging_frequency(1).build())
    return net, pw.fit, pw.mesh


def _install(net, w0):
    """The seeded weights into the trainer: same names, shapes and types as
    its own initialisation, or the configuration and the program disagree."""
    sig = lambda t: {n: {k: (a.shape, a.dtype) for k, a in d.items()}
                     for n, d in t.items() if d}
    if sig(net._params) != sig(w0):
        raise SystemExit("benchmarks: the reference's parameter shapes are "
                         "not the program's")
    fresh = jax.jit(lambda t: jax.tree.map(lambda a: a + 0, t))(w0)
    # layers without parameters keep their empty entries
    net._params = {n: fresh.get(n, d) for n, d in net._params.items()}


def first_steps(net, fit, datasets, lr):
    """Steps 1..FOLLOWED through the window's call. Returns what the
    comparison needs, copied out before later steps donate it: each loss,
    the first gradient as the optimizer got it (Nesterov's velocity after
    one step is -lr g) and the parameters after the last followed step."""
    copy = jax.jit(lambda t: jax.tree.map(lambda a: a + 0, t))
    losses, g1 = [], None
    for i in range(FOLLOWED):
        fit(datasets[i])
        losses.append(net._score)
        if i == 0:
            g1 = jax.jit(lambda u: {n: {k: -s["v"].astype(jnp.float32) / lr
                                        for k, s in leaves.items()}
                                    for n, leaves in u.items()})(
                net._updater_state)
    return losses, g1, copy(net._params)


def prepare(cell, seed, ring):
    """One trainer with the seeded weights installed, and `ring` seeded
    batches staged on the device (split over the chips' data axis)."""
    from deeplearning4j_tpu.datasets.dataset import DataSet
    cfg, chips = cell["config"], cell["chips"]
    model = cfg["model"]
    ref = loader.reference(cfg)
    net, fit, mesh = _build(cfg, chips)
    rep = rows = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        rep = NamedSharding(mesh, P())
        rows = NamedSharding(mesh, P(None, "data"))
    w0 = weights.resnet_weights(seed, ref.param_shapes(model), rep)
    _install(net, w0)
    batch = cfg["trainer"]["batch_per_chip"] * chips
    xs, ys = weights.image_ring(seed, ring, batch, model, rows)
    datasets = [DataSet(xs[i], ys[i]) for i in range(ring)]
    return ref, net, fit, w0, xs, ys, datasets


def program_norms(ref, losses, g1, p3, w0):
    """What the program's first steps left, in the reference's terms: the
    losses, the first gradient's leaf norms, the change's leaf norms."""
    change = jax.jit(lambda a, b: ref.leaf_norms(
        {n: jax.tree.map(lambda x, y: x - y, a[n], b[n]) for n in b}))(p3, w0)
    return (np.asarray([float(l) for l in losses]),
            np.asarray(jax.jit(ref.leaf_norms)(g1)), np.asarray(change))


def run(cell, seed, seconds, tracer, setup_done):
    cfg, traffic, chips = cell["config"], cell["traffic"], cell["chips"]
    model, trainer = cfg["model"], cfg["trainer"]
    ring, batch = traffic["ring"], trainer["batch_per_chip"] * chips
    ref, net, fit, w0, xs, ys, datasets = prepare(cell, seed, ring)

    losses, g1, p3 = first_steps(net, fit, datasets,
                                 trainer["learning_rate"])
    for ds in datasets[FOLLOWED:]:          # the rest of the ring: warm
        fit(ds)
    jax.block_until_ready(net._score)

    tracer.start()
    t_start = setup_done()
    steps, prev, traced = 0, None, tracer.enabled
    span = tracer.window()
    span.__enter__()
    while True:
        for ds in datasets:
            with tracer.annotate("bench.fit"):
                fit(ds)
        steps += ring
        last = net._score
        if prev is not None:
            prev.block_until_ready()        # at most two rings in flight
        prev = last
        elapsed = now() - t_start
        if traced and elapsed >= tracer.seconds:
            last.block_until_ready()
            span.__exit__(None, None, None)
            tracer.stop()
            traced = False
        if elapsed >= seconds:
            break
    last.block_until_ready()
    t_end = now()
    if traced:
        span.__exit__(None, None, None)
        tracer.stop()
    peak = memory_peak_bytes(jax.local_devices()[:chips])

    # ---- the window is closed; free the trainer, then the reference ----
    t_check = now()
    got = program_norms(ref, losses, g1, p3, w0)
    del net, fit, datasets, p3, g1
    gc.collect()
    want = reference_steps(ref, w0, xs, ys, model, trainer)
    numbers = compare.training_numbers(got, want, matrix_leaves(ref, model))
    ok, compared = compare.judge(numbers, cfg["limits"])
    images = steps * batch
    window_s = t_end - t_start
    return {
        "correct": ok, "compared": compared,
        "read": {k: v for k, v in numbers.items() if k not in compared},
        "attempted": steps, "failed": 0,
        "memory_peak_bytes": int(peak), "check_s": now() - t_check,
        "end_to_end": {"images_per_s": images / window_s},
        "ctx": {"cell": cell, "steps": steps, "images": images,
                "window_s": window_s, "chips": chips,
                "flops_per_image": work.resnet_train_flops_per_image(model),
                "trace": tracer.result()},
    }


def matrix_leaves(ref, model):
    """Which leaves, in leaf_norms' order, have two axes or more."""
    shapes = ref.param_shapes(model)
    return [len(shapes[n][k]) >= 2 for n in sorted(shapes)
            for k in sorted(shapes[n])]


def reference_steps(ref, w0, xs, ys, model, trainer, quant=False, rows=None):
    """The reference (or, with `quant`/`rows`, the control or the planted
    fault) over the first FOLLOWED batches, as numpy."""
    with jax.default_matmul_precision("highest"):
        out = ref.train_steps(w0, [xs[i] for i in range(FOLLOWED)],
                              [ys[i] for i in range(FOLLOWED)], model,
                              trainer, quant=quant, rows=rows)
    return tuple(np.asarray(a) for a in out)


def calibrate(cell, seeds, emit, seconds=None):
    """The readings the limits are set from, at the cell's own size, many
    seeds in one process: the program against the reference (lower), the
    fp8 control and each planted fault against it (upper)."""
    cfg, chips = cell["config"], cell["chips"]
    model, trainer = cfg["model"], cfg["trainer"]
    batch = trainer["batch_per_chip"] * chips
    for seed in seeds:
        ref, net, fit, w0, xs, ys, ds = prepare(cell, seed, FOLLOWED)
        losses, g1, p3 = first_steps(net, fit, ds, trainer["learning_rate"])
        got = program_norms(ref, losses, g1, p3, w0)
        del net, fit, ds, p3, g1
        gc.collect()
        want = reference_steps(ref, w0, xs, ys, model, trainer)
        wide = matrix_leaves(ref, model)
        emit(seed, "program", compare.training_numbers(got, want, wide))
        variants = {"control_fp8": dict(quant=True)}
        if seed in seeds[:3]:       # a fault is read on three seeds
            variants["fault_half_batch"] = dict(rows=batch // 2)
            if chips > 1:
                variants["fault_no_exchange"] = dict(rows=batch // chips)
        for name, kw in variants.items():
            alt = reference_steps(ref, w0, xs, ys, model, trainer, **kw)
            emit(seed, name, compare.training_numbers(alt, want, wide))
