"""Driver of the training cells of a state-space hybrid: a decoder from the
zoo (`models/zoo/nemotron_h.py`: Mamba-2 mixers, relu^2 experts under a
sigmoid router with a selection bias, attention without positions, a layer
a mixer) as a ComputationGraph, stepped by `fit(MultiDataSet)` through a
ring of seeded rows of token ids staged on the device, for the whole
window. Follows `drivers/train_lm.py` (ONE trainer holding the seeded
weights and no copy of them, its first steps through the window's own
call, the first gradient read from Adam's first moment, the plain reference
after the window from the weights made again) and takes from it and from
`drivers/train_vl.py` what does not know a model: `shapes_of`,
`staged_ring`, `dataset` (a row is `seq_len` token ids at positions
0 .. seq_len - 1, its labels the next token, the last position masked),
`model_of`, `build`, `step_text`, `matrix_leaves`.

Its own: the seeded weights (a Mamba layer's A_log, dt_bias and
convolution are not normal draws), the routers' bias after the followed
steps and what the Mamba layers said (time steps, smallest decay, last
state) read beside the reference's, and the gaps of the Mamba layers'
leaves that are no projection (A_log, dt_bias, D, the convolution, the
gated norm) as a group of their own, so that a wrong scan cannot hide under
the matrices.
"""
import gc
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..harness import compare, loader, work_nemotron
from ..harness.weights import key_for
from ..harness.window import memory_peak_bytes, now
from .train_lm import dataset, shapes_of, staged_ring
from .train_vl import build, matrix_leaves, model_of, step_text

FAULTS = ("norm_all_channels", "conv_one_late")


def weights_maker(shapes, std, model):
    """key -> seeded weights. Normal of deviation `std["matrix"]`, but
    `std["residual_out"]` for every projection that writes to the residual
    stream (W_out, Wo, Wd, Sd) and `std["embedding"]` for the table; a
    Mamba layer's own as Mamba-2 draws them (A_log the log of a uniform in
    [1, 16], dt_bias the inverse softplus of a log-uniform in
    [time_step_min, time_step_max] floored at time_step_floor, w_c and b_c
    uniform in +-1 / sqrt(taps)); every other one-axis leaf 1 +- 0.1. The
    configuration's `assumed.weights` says why each."""
    flat = [(n, k) for n in sorted(shapes) for k in sorted(shapes[n])]
    lo, hi = math.log(model["time_step_min"]), math.log(model["time_step_max"])
    tap = 1.0 / math.sqrt(model["conv_kernel"])

    def leaf(n, k, key):
        shape = shapes[n][k]
        u = lambda a, b: jax.random.uniform(key, shape, jnp.float32, a, b)
        if k == "A_log":
            return jnp.log(u(1.0, 16.0))
        if k == "dt_bias":
            dt = jnp.maximum(jnp.exp(u(lo, hi)), model["time_step_floor"])
            return dt + jnp.log(-jnp.expm1(-dt))
        if k in ("w_c", "b_c"):
            return u(-tap, tap)
        a = jax.random.normal(key, shape, jnp.float32)
        if len(shape) == 1:
            return 1.0 + 0.1 * a
        if n == "embed":
            return std["embedding"] * a
        return (std["residual_out"] if k in ("W_out", "Wo", "Wd", "Sd")
                else std["matrix"]) * a

    def make(key):
        out = {n: {} for n in shapes}
        for (n, k), kk in zip(flat, jax.random.split(key, len(flat))):
            out[n][k] = leaf(n, k, kk)
        return out

    return make


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
    make = weights_maker(shapes, cfg["trainer"]["seeded_std"], model)
    remake = lambda: jax.jit(make)(key_for(seed, 0))
    change = jax.jit(lambda p: ref.leaf_norms(
        {n: jax.tree.map(lambda x, y: x - y, p[n], w) for n, w in
         make(key_for(seed, 0)).items()}))
    net._params = None                      # room for the seeded ones
    net._params = remake()
    return (ref, net, model, staged_ring(seed, ring, rows, seq_len, model),
            remake, change)


def first_steps(ref, net, model, batches, followed, change):
    """Steps 1..followed through the window's call. Returns (the losses,
    the first gradient's leaf norms (from Adam's first moment, m1 = 0.1 g),
    the change's leaf norms after the last followed step), and what the
    program said: the layers' gauges after the first step, the bias after
    the last [expert layers, router width]."""
    norms = jax.jit(lambda u: ref.leaf_norms(
        {n: {k: s["m"].astype(jnp.float32) / 0.1 for k, s in leaves.items()}
         for n, leaves in u.items() if leaves}))
    losses, g1, said = [], None, None
    for i in range(followed):
        net.fit(dataset(batches[i]))
        losses.append(net._score)
        if i == 0:
            g1, said = norms(net._updater_state), net.publish_layer_gauges()
    bias = np.stack([np.asarray(net._model_state[n]["bias"])
                     for n in ref.sparse_names(ref.sizes(model))])
    return (np.asarray([float(l) for l in losses]), np.asarray(g1),
            np.asarray(change(net._params))), {"gauges": said, "bias": bias}


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
    numbers = numbers_of(got, want, said, aux, ref, model)
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
                "flops_per_image": work_nemotron.train_flops_per_row(
                    model, seq_len, seq_len - 1),
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


def ssm_leaves(ref, model):
    """Which leaves, in `leaf_norms`' order, are a Mamba layer's own and no
    projection (`ref.SCALARS`)."""
    shapes = ref.param_shapes(model)
    return np.asarray([k in ref.SCALARS for n in sorted(shapes)
                       for k in sorted(shapes[n])])


def against(alt, want, ref, model):
    """Three followed steps (the program's, a control's or a planted
    fault's) against the reference's own: harness/compare.py's training
    numbers, and the worst gap among the Mamba layers' own leaves."""
    out = compare.training_numbers(alt, want, matrix_leaves(ref, model))
    own = ssm_leaves(ref, model)
    out["grad_norm_gap_ssm"] = float(compare.leaf_gaps(
        alt[1], want[1], own).max())
    out["change_norm_gap_ssm"] = float(compare.leaf_gaps(
        alt[2], want[2], own & compare.moved_leaves(want[1])).max())
    return out


def numbers_of(got, want, said, aux, ref, model):
    """The numbers compared (those the configuration has limits for) and
    read: `against`'s; the bias after the followed steps, its largest entry
    and the share of its entries that are the reference's; the busiest held
    expert over the mean after the first step, the program's beside the
    reference's; the attention kernels' schedule (backward passes, grid
    steps a tile: constants of the trace); what the Mamba layers said after
    the first step (mean time step, smallest decay, the last state's rms,
    chunks), beside the reference's."""
    out = against(got, want, ref, model)
    out["bias_abs_max"] = float(np.abs(said["bias"]).max())
    out["bias_abs_max_ref"] = float(np.abs(aux["bias"]).max())
    out["bias_equal_share"] = float(np.mean(
        np.abs(said["bias"] - aux["bias"]) < 1e-7))
    mine = lambda kind, leaf: np.asarray(
        [v for k, v in sorted(said["gauges"].items())
         if k.startswith(kind + ".") and k.endswith("." + leaf)])
    held = aux["held_pairs"]
    out["expert_load_max_over_mean_ref"] = float(np.max(
        held.max(-1) / held.mean(-1)))
    out["expert_load_max_over_mean"] = float(np.max(
        mine("moe", "held_pairs_max") / mine("moe", "held_pairs_mean")))
    for leaf in ("attend_backward_passes", "attend_grid_steps_per_tile"):
        out[leaf] = float(mine("attention", leaf).max())
    theirs = lambda leaf: np.asarray([float(s[leaf]) for s in aux["ssm"]])
    out["ssm_chunks"] = float(mine("mamba2", "chunks").min())
    out["ssm_decay_min"] = float(mine("mamba2", "decay_min").min())
    out["ssm_decay_min_ref"] = float(theirs("decay_min").min())
    for leaf in ("dt_mean", "state_rms"):
        out[f"ssm_{leaf}_rel"] = float(np.max(
            np.abs(mine("mamba2", leaf) - theirs(leaf)) / theirs(leaf)))
    return out


def calibrate(cell, seeds, emit, seconds=None):
    """The readings the limits are set from, at the cell's own size, many
    seeds in one process: the program against the reference (lower), the
    fp8 control against it (upper), and the reference with each of its two
    faults planted (the upper readings the control does not give: the gated
    norm over all 4096 channels in place of 8 groups of 512, and the
    convolution's window one position late)."""
    trainer = cell["config"]["trainer"]
    followed = trainer["followed_steps"]
    for seed in seeds:
        ref, net, model, batches, remake, change = prepare(cell, seed,
                                                           followed)
        got, said = first_steps(ref, net, model, batches, followed, change)
        del net
        gc.collect()
        want, aux = reference_steps(ref, remake, batches, model, trainer)
        emit(seed, "program", numbers_of(got, want, said, aux, ref, model))
        for name, planted in (("control_fp8", {"quant": True}),
                              *((f"fault_{f}", {"fault": f})
                                for f in FAULTS)):
            alt, _ = reference_steps(ref, remake, batches, model, trainer,
                                     **planted)
            emit(seed, name, against(alt, want, ref, model))
            del alt
