"""Driver of the text-only training cells: a decoder from the zoo
(`models/zoo/laguna.py`) as a ComputationGraph, stepped by
`fit(MultiDataSet)` through a ring of seeded rows of token ids staged on the
device, for the whole window. Follows `drivers/train_vl.py` (ONE trainer
holding the seeded weights and no copy of them, its first steps through the
window's own call, the first gradient read from Adam's first moment, the
plain reference after the window from the weights made again) and takes
from it what does not know a row's layout: `model_of`, `build`,
`step_text`, `matrix_leaves`. A row is one sequence of `seq_len` token ids
at positions 0 .. seq_len - 1, its labels the next token, the last position
masked; `images_per_s` counts rows, as in `train-vl8k`.
"""
import gc
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..harness import compare, loader, work_laguna
from ..harness.weights import key_for
from ..harness.window import memory_peak_bytes, now
from .train_vl import build, matrix_leaves, model_of, step_text


def shapes_of(cell):
    """(rows, seq_len) of a step; a rehearsal's tiny configuration caps the
    traffic's length."""
    tr, cap = cell["traffic"], cell["config"]["trainer"]
    return tr["rows"], min(tr["seq_len"], cap.get("max_seq_len",
                                                  tr["seq_len"]))


def weights_maker(shapes, std):
    """key -> seeded normal weights, as `train_vl.weights_maker` makes them
    (whose rule is by exact leaf name): deviation `std["matrix"]`, but
    `std["residual_out"]` for every projection that writes to the residual
    stream (attention's Wo, the MLPs' and experts' Wd, the shared expert's
    Sd) and `std["embedding"]` for the table; norm weights 1 +- 0.1."""
    flat = [(n, k) for n in sorted(shapes) for k in sorted(shapes[n])]

    def deviation(n, k):
        if n == "embed":
            return std["embedding"]
        return (std["residual_out"] if k in ("Wo", "Wd", "Sd")
                else std["matrix"])

    def make(key):
        out = {n: {} for n in shapes}
        for (n, k), kk in zip(flat, jax.random.split(key, len(flat))):
            a = jax.random.normal(kk, shapes[n][k], jnp.float32)
            out[n][k] = (1.0 + 0.1 * a if len(shapes[n][k]) == 1
                         else deviation(n, k) * a)
        return out

    return make


def staged_ring(seed, ring, rows, seq_len, model):
    """`ring` batches on the device: ids from the vocabulary slice, labels
    (the next token) and their mask (every position whose next token
    exists)."""
    ids = jax.jit(lambda key: jax.random.randint(
        key, (ring, rows, seq_len), 0, model["vocab_size"], jnp.int32))(
            key_for(seed, 1))
    labels = jnp.roll(ids, -1, -1)
    mask = jnp.broadcast_to((np.arange(seq_len) < seq_len - 1)
                            .astype(np.float32), (rows, seq_len))
    return [{"ids": ids[i], "labels": labels[i], "mask": mask}
            for i in range(ring)]


def dataset(batch):
    from deeplearning4j_tpu.datasets.dataset import MultiDataSet
    return MultiDataSet([batch["ids"]], [batch["labels"]],
                        labels_masks=[batch["mask"]])


def prepare(cell, seed, ring):
    """One trainer holding the seeded weights (not kept elsewhere), the
    staged ring, `remake()`, which makes the weights again, and
    `change(params)`, the leaf norms of params less the seeded weights."""
    cfg = cell["config"]
    model, ref = model_of(cfg), loader.reference(cfg)
    rows, seq_len = shapes_of(cell)
    net = build(cfg)
    shapes = ref.param_shapes(model)
    sig = {n: {k: tuple(a.shape) for k, a in d.items()}
           for n, d in net._params.items()}
    if sig != {n: {k: tuple(s) for k, s in d.items()}
               for n, d in shapes.items()}:
        raise SystemExit("benchmarks: the reference's parameter shapes are "
                         "not the program's")
    make = weights_maker(shapes, cfg["trainer"]["seeded_std"])
    remake = lambda: jax.jit(make)(key_for(seed, 0))
    # the parameters' change without the seeded weights beside the
    # trainer's state: each leaf is made again inside the subtraction
    change = jax.jit(lambda p: ref.leaf_norms(
        {n: jax.tree.map(lambda x, y: x - y, p[n], w) for n, w in
         make(key_for(seed, 0)).items()}))
    net._params = None                      # room for the seeded ones
    net._params = remake()
    return (ref, net, model, staged_ring(seed, ring, rows, seq_len, model),
            remake, change)


def first_steps(ref, net, batches, followed, change):
    """Steps 1..followed through the window's call. Returns the losses, the
    first gradient's leaf norms (from Adam's first moment, m1 = 0.1 g), the
    change's leaf norms after the last followed step, and what the layers'
    state said after the first step."""
    norms = jax.jit(lambda u: ref.leaf_norms(
        {n: {k: s["m"].astype(jnp.float32) / 0.1 for k, s in leaves.items()}
         for n, leaves in u.items() if leaves}))
    losses, g1, said = [], None, None
    for i in range(followed):
        net.fit(dataset(batches[i]))
        losses.append(net._score)
        if i == 0:
            g1 = norms(net._updater_state)
            said = net.publish_layer_gauges()
    return (np.asarray([float(l) for l in losses]), np.asarray(g1),
            np.asarray(change(net._params))), said


def run(cell, seed, seconds, tracer, setup_done):
    cfg, traffic = cell["config"], cell["traffic"]
    trainer, ring = cfg["trainer"], traffic["ring"]
    followed = trainer["followed_steps"]
    rows, seq_len = shapes_of(cell)
    ref, net, model, batches, remake, change = prepare(cell, seed, ring)
    datasets = [dataset(b) for b in batches]
    got, said = first_steps(ref, net, batches, followed, change)
    for ds in datasets[followed:]:          # the rest of the ring: warm
        net.fit(ds)
    jax.block_until_ready(net._score)

    tracer.start()
    t_start = setup_done()
    steps, prev, traced = 0, None, tracer.enabled
    span = tracer.window()
    span.__enter__()
    while True:
        for ds in datasets:
            with tracer.annotate("bench.fit"):
                net.fit(ds)
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
    peak = memory_peak_bytes(jax.local_devices()[:1])
    failed = 0 if math.isfinite(float(last)) else steps
    gauges = net.publish_layer_gauges()     # one host read, window closed
    text = step_text(net, datasets[0]) if tracer.enabled else None

    # ---- the window is closed; free the trainer, then the reference ----
    t_check = now()
    del net, datasets, prev, last
    gc.collect()
    want, aux = reference_steps(ref, remake, batches, model, trainer)
    numbers = numbers_of(got, want, said, aux, matrix_leaves(ref, model))
    ok, compared = compare.judge(numbers, cfg["limits"])
    window_s = t_end - t_start
    return {
        "correct": ok and not failed, "compared": compared,
        "read": {**{k: v for k, v in numbers.items() if k not in compared},
                 "tokens_per_s": steps * rows * seq_len / window_s},
        "attempted": steps, "failed": failed,
        "memory_peak_bytes": int(peak), "check_s": now() - t_check,
        "end_to_end": {"images_per_s": steps * rows / window_s},
        "ctx": {"cell": cell, "steps": steps, "images": steps * rows,
                "window_s": window_s, "chips": 1,
                "flops_per_image": work_laguna.train_flops_per_row(
                    model, seq_len, seq_len - 1),
                "trace": tracer.result(), "step_text": text,
                "gauges": gauges, "model": model, "rows": rows,
                "seq_len": seq_len},
    }


def reference_steps(ref, remake, batches, model, trainer, quant=False):
    """The reference (or, with `quant`, the control) over the first
    followed batches from the weights made again."""
    with jax.default_matmul_precision("highest"):
        losses, g1, change, aux = ref.train_steps(
            remake(), batches[:trainer["followed_steps"]], model, trainer,
            quant=quant, remake=remake)
    return (tuple(np.asarray(a) for a in (losses, g1, change)),
            jax.tree.map(np.asarray, aux))


def numbers_of(got, want, said, aux, wide):
    """The numbers compared (those the configuration has limits for) and
    read: harness/compare.py's training numbers, and the busiest held
    expert over the mean after the first step, the program's beside the
    reference's."""
    out = compare.training_numbers(got, want, wide)
    mine = lambda leaf: np.asarray(
        [v for k, v in sorted(said.items())
         if k.startswith("moe.") and k.endswith("." + leaf)])
    held = aux["held_pairs"]
    out["expert_load_max_over_mean_ref"] = float(np.max(
        held.max(-1) / held.mean(-1)))
    out["expert_load_max_over_mean"] = float(np.max(
        mine("held_pairs_max") / mine("held_pairs_mean")))
    return out


def loss_faults(ref, remake, batch, model):
    """The reference's first loss under each planted fault of the loss: the
    labels not shifted (a position scored on its own token), and the last
    position, whose next token does not exist, counted."""
    first = jax.jit(lambda p, b: ref.loss(p, b, model)[0])
    faults = {"labels_unshifted": {**batch, "labels": batch["ids"]},
              "last_unmasked": {**batch,
                                "mask": jnp.ones_like(batch["mask"])}}
    with jax.default_matmul_precision("highest"):
        params = remake()
        return {name: float(first(params, b)) for name, b in faults.items()}


def calibrate(cell, seeds, emit, seconds=None):
    """The readings the limits are set from, at the cell's own size, many
    seeds in one process: the program against the reference (lower), the
    fp8 control against it (upper), and what each planted fault of the loss
    would read (the losses' upper reading: the control hardly moves them)."""
    trainer = cell["config"]["trainer"]
    followed = trainer["followed_steps"]
    for seed in seeds:
        ref, net, model, batches, remake, change = prepare(cell, seed,
                                                           followed)
        got, said = first_steps(ref, net, batches, followed, change)
        del net
        gc.collect()
        wide = matrix_leaves(ref, model)
        want, aux = reference_steps(ref, remake, batches, model, trainer)
        emit(seed, "program", numbers_of(got, want, said, aux, wide))
        alt, _ = reference_steps(ref, remake, batches, model, trainer,
                                 quant=True)
        emit(seed, "control_fp8", compare.training_numbers(alt, want, wide))
        del alt
        for name, l in loss_faults(ref, remake, batches[0], model).items():
            emit(seed, "fault_" + name,
                 {"loss1_rel": abs(l - float(want[0][0])) / float(want[0][0])})
