"""Driver of the training cells with a multi-token prediction module: a
decoder from the zoo (`models/zoo/joyai.py`) as a ComputationGraph with two
inputs and two scored outputs, stepped by `fit(MultiDataSet)` through a
ring of seeded rows of token ids staged on the device, for the whole
window. Follows `drivers/train_lm.py` (ONE trainer holding the seeded
weights and no copy of them, its first steps through the window's own
call, the first gradient read from Adam's first moment, the plain reference
after the window from the weights made again) and takes from it and from
`drivers/train_vl.py` what does not know a row's layout: `model_of`,
`build`, `step_text`, `matrix_leaves`, `shapes_of`, `weights_maker`.

A row is one sequence of `seq_len` token ids t_0 .. t_{T-1} at positions
0 .. T-1. The main head's labels are the next token (position T-1 masked),
the module's input is the next token too, its labels the token after next
(positions T-2 and T-1 masked): both label sets and both masks are made
here. `images_per_s` counts rows, as in `train-vl8k`. Each followed step's
two losses are read from the outputs' own state (`lmhead.<output>.loss`)
and compared apart; the routers' bias after the followed steps is read
beside the reference's.
"""
import gc
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..harness import compare, loader, work_joyai
from ..harness.weights import key_for
from ..harness.window import memory_peak_bytes, now
from .train_lm import shapes_of, weights_maker
from .train_vl import build, matrix_leaves, model_of, step_text

OUTPUTS = ("head", "mtp_head")          # the zoo's names of the two losses


def staged_ring(seed, ring, rows, seq_len, model):
    """`ring` batches on the device: ids from the vocabulary slice, the
    token after each, the token after next, and the two masks (every
    position whose label exists)."""
    ids = jax.jit(lambda key: jax.random.randint(
        key, (ring, rows, seq_len), 0, model["vocab_size"], jnp.int32))(
            key_for(seed, 1))
    nxt, nxt2 = jnp.roll(ids, -1, -1), jnp.roll(ids, -2, -1)
    keep = lambda last: jnp.broadcast_to(
        (np.arange(seq_len) < seq_len - last).astype(np.float32),
        (rows, seq_len))
    return [{"ids": ids[i], "next_ids": nxt[i], "labels": nxt[i],
             "mask": keep(1), "labels2": nxt2[i], "mask2": keep(2)}
            for i in range(ring)]


def dataset(batch):
    from deeplearning4j_tpu.datasets.dataset import MultiDataSet
    return MultiDataSet([batch["ids"], batch["next_ids"]],
                        [batch["labels"], batch["labels2"]],
                        labels_masks=[batch["mask"], batch["mask2"]])


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
    change = jax.jit(lambda p: ref.leaf_norms(
        {n: jax.tree.map(lambda x, y: x - y, p[n], w) for n, w in
         make(key_for(seed, 0)).items()}))
    net._params = None                      # room for the seeded ones
    net._params = remake()
    return (ref, net, model, staged_ring(seed, ring, rows, seq_len, model),
            remake, change)


def bias_of(net, ref, model):
    """[sparse layers, router width]: each router's bias as the last step
    left it, in the reference's order of layers."""
    return np.stack([np.asarray(net._model_state[n]["bias"])
                     for n in ref.sparse_names(ref.sizes(model))])


def first_steps(ref, net, model, batches, followed, change):
    """Steps 1..followed through the window's call. Returns (the losses,
    the first gradient's leaf norms (from Adam's first moment, m1 = 0.1 g),
    the change's leaf norms after the last followed step), and what the
    program said: the layers' gauges after the first step, each step's two
    losses [followed, 2], the bias after the last."""
    norms = jax.jit(lambda u: ref.leaf_norms(
        {n: {k: s["m"].astype(jnp.float32) / 0.1 for k, s in leaves.items()}
         for n, leaves in u.items() if leaves}))
    losses, parts, g1, said = [], [], None, None
    for i in range(followed):
        net.fit(dataset(batches[i]))
        losses.append(net._score)
        gauges = net.publish_layer_gauges()
        parts.append([gauges[f"lmhead.{o}.loss"] for o in OUTPUTS])
        if i == 0:
            g1, said = norms(net._updater_state), gauges
    return (np.asarray([float(l) for l in losses]), np.asarray(g1),
            np.asarray(change(net._params))), {
                "gauges": said, "loss_parts": np.asarray(parts),
                "bias": bias_of(net, ref, model)}


def run(cell, seed, seconds, tracer, setup_done):
    cfg, traffic = cell["config"], cell["traffic"]
    trainer, ring = cfg["trainer"], traffic["ring"]
    followed = trainer["followed_steps"]
    rows, seq_len = shapes_of(cell)
    ref, net, model, batches, remake, change = prepare(cell, seed, ring)
    datasets = [dataset(b) for b in batches]
    got, said = first_steps(ref, net, model, batches, followed, change)
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
                "flops_per_image": work_joyai.train_flops_per_row(
                    model, seq_len),
                "trace": tracer.result(), "step_text": text,
                "gauges": gauges, "model": model, "rows": rows,
                "seq_len": seq_len},
    }


def reference_steps(ref, remake, batches, model, trainer, quant=False,
                    fault=None):
    """The reference (or, with `quant`, the control; with `fault`, the
    reference with that fault planted) over the first followed batches from
    the weights made again."""
    with jax.default_matmul_precision("highest"):
        losses, g1, change, aux = ref.train_steps(
            remake(), batches[:trainer["followed_steps"]], model, trainer,
            quant=quant, remake=remake, fault=fault)
    return (tuple(np.asarray(a) for a in (losses, g1, change)),
            jax.tree.map(np.asarray, aux))


def against(alt, alt_aux, want, aux, wide):
    """Three followed steps (the program's, a control's or a planted
    fault's) against the reference's own: harness/compare.py's training
    numbers and both losses apart at every step."""
    out = compare.training_numbers(alt, want, wide)
    rel = np.abs(alt_aux["loss_parts"] - aux["loss_parts"]) \
        / np.abs(aux["loss_parts"])
    for i, (main, mtp) in enumerate(rel, start=1):
        out[f"loss_main{i}_rel"], out[f"loss_mtp{i}_rel"] = \
            float(main), float(mtp)
    return out


def numbers_of(got, want, said, aux, wide):
    """The numbers compared (those the configuration has limits for) and
    read: harness/compare.py's training numbers (their losses are the
    weighted sums); each step's two losses apart, `loss_main<i>_rel` and
    `loss_mtp<i>_rel`; the bias after the followed steps, its largest
    entry and the share of its entries that are the reference's; the
    busiest held expert over the mean after the first step, the program's
    beside the reference's."""
    out = against(got, said, want, aux, wide)
    out["bias_abs_max"] = float(np.abs(said["bias"]).max())
    out["bias_abs_max_ref"] = float(np.abs(aux["bias"]).max())
    out["bias_equal_share"] = float(np.mean(
        np.abs(said["bias"] - aux["bias"]) < 1e-7))
    mine = lambda leaf: np.asarray(
        [v for k, v in sorted(said["gauges"].items())
         if k.startswith("moe.") and k.endswith("." + leaf)])
    held = aux["held_pairs"]
    out["expert_load_max_over_mean_ref"] = float(np.max(
        held.max(-1) / held.mean(-1)))
    out["expert_load_max_over_mean"] = float(np.max(
        mine("held_pairs_max") / mine("held_pairs_mean")))
    return out


def calibrate(cell, seeds, emit, seconds=None):
    """The readings the limits are set from, at the cell's own size, many
    seeds in one process: the program against the reference (lower), the
    fp8 control against it (upper), and the reference with each of its two
    faults planted (the upper readings the control does not give: the
    module's labels shifted by one in place of two moves its loss, which
    the precision hardly moves; the scores divided by sqrt(128) in place of
    sqrt(192) moves the attention's leaves, whose worst the precision moves
    by under two)."""
    trainer = cell["config"]["trainer"]
    followed = trainer["followed_steps"]
    for seed in seeds:
        ref, net, model, batches, remake, change = prepare(cell, seed,
                                                           followed)
        got, said = first_steps(ref, net, model, batches, followed, change)
        del net
        gc.collect()
        wide = matrix_leaves(ref, model)
        want, aux = reference_steps(ref, remake, batches, model, trainer)
        emit(seed, "program", numbers_of(got, want, said, aux, wide))
        for name, planted in (
                ("control_fp8", {"quant": True}),
                ("fault_mtp_labels_by_one", {"fault": "mtp_labels_by_one"}),
                ("fault_scale_128", {"fault": "scale_128"})):
            alt, alt_aux = reference_steps(ref, remake, batches, model,
                                           trainer, **planted)
            emit(seed, name, against(alt, alt_aux, want, aux, wide))
            del alt
