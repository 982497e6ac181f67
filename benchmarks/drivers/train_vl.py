"""Driver of the vision-language training cells: a sparse-attention
mixture-of-experts decoder from the zoo (`models/zoo/keye_vl.py`) as a
ComputationGraph, stepped by `fit(MultiDataSet)` through a ring of seeded
rows staged on the device, for the whole window. Follows `drivers/train.py`:
ONE trainer, its first steps through the window's own call, the plain
reference after the window from the same seeded weights.

What differs, because Adam's state fills the chip: the seeded weights are
handed to the trainer and NOT kept (they are made again from the seed for
the parameters' change and for the reference); the first gradient is read
from Adam's first moment (m1 = 0.1 g) and reduced to leaf norms at once.
A row is one image (its merged-patch embeddings, seeded: the vision tower
is not run) followed by text; `images_per_s` counts rows.
"""
import gc
import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..harness import compare, loader, work_keye
from ..harness.weights import key_for
from ..harness.window import memory_peak_bytes, now

PROBE = 512             # queries whose selection is compared (the last ones)


def model_of(cfg):
    """The configuration's own keys (its top level) as the reference and the
    work counts read them."""
    skip = ("name", "source", "driver", "reference", "published", "reduced",
            "trainer", "program", "assumed", "limits", "limits_from")
    return {k: v for k, v in cfg.items() if k not in skip}


def shapes_of(cell):
    """(rows, seq_len, image grid) of a step; a rehearsal's tiny
    configuration caps the traffic's lengths."""
    tr, cap = cell["traffic"], cell["config"]["trainer"]
    t = min(tr["seq_len"], cap.get("max_seq_len", tr["seq_len"]))
    grid = [min(a, b) for a, b in zip(
        tr["image_grid"], cap.get("max_image_grid", tr["image_grid"]))]
    return tr["rows"], t, grid


def build(cfg):
    try:
        mod, fn = cfg["program"]["conf"].split(":")
        conf = getattr(importlib.import_module(mod), fn)(
            **cfg["program"]["args"])
    except (ImportError, AttributeError) as e:
        raise SystemExit(f"benchmarks: the program cannot build "
                         f"{cfg['name']}: {e}")
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    return ComputationGraph(conf).init()


def weights_maker(shapes, std):
    """key -> seeded normal weights: deviation `std["matrix"]`, but
    `std["residual_out"]` for the two projections that write to the
    residual stream (attention's Wo, the experts' Wd) and
    `std["embedding"]` for the table; norm weights 1 +- 0.1. The
    configuration's `assumed.weights` says why each."""
    flat = [(n, k) for n in sorted(shapes) for k in sorted(shapes[n])]

    def deviation(n, k):
        if n == "embed":
            return std["embedding"]
        return std["residual_out"] if k in ("Wo", "Wd") else std["matrix"]

    def make(key):
        out = {n: {} for n in shapes}
        for (n, k), kk in zip(flat, jax.random.split(key, len(flat))):
            a = jax.random.normal(kk, shapes[n][k], jnp.float32)
            out[n][k] = (1.0 + 0.1 * a if len(shapes[n][k]) == 1
                         else deviation(n, k) * a)
        return out

    return make


def positions(seq_len, grid):
    """[T, 3] (t, h, w): the image first (t 0, its rows and columns), then
    text, all three axes alike, from 1 + the largest position before it."""
    gh, gw = grid
    g = np.arange(gh * gw)
    img = np.stack([np.zeros_like(g), g // gw, g % gw], -1)
    txt = np.repeat((max(gh, gw) + np.arange(seq_len - gh * gw))[:, None],
                    3, 1)
    return np.concatenate([img, txt], 0).astype(np.int32)


def staged_ring(seed, ring, rows, seq_len, grid, model, dtype, std):
    """`ring` batches on the device: ids from the vocabulary slice, the
    image's embeddings (of the table's deviation `std`), positions, labels
    (the next token) and their mask (text positions whose next token
    exists)."""
    p = grid[0] * grid[1]

    def make(key):
        ki, ke = jax.random.split(key)
        ids = jax.random.randint(ki, (ring, rows, seq_len), 0,
                                 model["vocab_size"], jnp.int32)
        image = (std * jax.random.normal(
            ke, (ring, rows, p, model["hidden_size"]), jnp.float32)
                 ).astype(dtype)
        return ids, image, jnp.roll(ids, -1, -1)

    ids, image, labels = jax.jit(make)(key_for(seed, 1))
    pos = jnp.broadcast_to(positions(seq_len, grid), (rows, seq_len, 3))
    t = np.arange(seq_len)
    mask = jnp.broadcast_to(((t >= p) & (t < seq_len - 1))
                            .astype(np.float32), (rows, seq_len))
    return [{"ids": ids[i], "image": image[i], "positions": pos,
             "labels": labels[i], "mask": mask} for i in range(ring)]


def dataset(batch):
    from deeplearning4j_tpu.datasets.dataset import MultiDataSet
    return MultiDataSet([batch["ids"], batch["image"], batch["positions"]],
                        [batch["labels"]], labels_masks=[batch["mask"]])


def program_selection(net, batch, start, count):
    """The selection [B, count, T] of the first layer's queries
    start..start+count, by the layer's own pieces (what the step calls) on
    the trainer's weights in its compute type."""
    from deeplearning4j_tpu.nn.conf.layers.decoder import (index_scores,
                                                           select_keys)
    verts = net.conf.vertices
    attn = verts["l0_attn"].conf

    def run(params, ids, image, pos):
        p = {n: net._cast_params(params[n])
             for n in ("embed", "l0_norm1", "l0_attn")}
        x = verts["embed"].conf.forward(p["embed"], ids, extras=(image,))
        h = verts["l0_norm1"].conf.forward(p["l0_norm1"], x)
        *_, qi, ki, w = attn.project(p["l0_attn"], h, pos)
        rows = jnp.arange(start, start + count)
        return jax.vmap(lambda a, b, c: select_keys(
            index_scores(a[start:start + count], b, c[start:start + count]),
            rows, attn.topk))(qi, ki, w)

    return jax.jit(run)(net._params, batch["ids"], batch["image"],
                        batch["positions"])


def prepare(cell, seed, ring):
    """One trainer holding the seeded weights (not kept elsewhere), the
    staged ring, `remake()`, which makes the weights again, and
    `change(params)`, the leaf norms of params less the seeded weights."""
    cfg = cell["config"]
    model, ref = model_of(cfg), loader.reference(cfg)
    rows, seq_len, grid = shapes_of(cell)
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
    batches = staged_ring(seed, ring, rows, seq_len, grid, model,
                          net.compute_dtype,
                          cfg["trainer"]["seeded_std"]["embedding"])
    return ref, net, model, batches, remake, change


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


def step_text(net, ds):
    """The compiled step's text with the layer scopes in its metadata (a
    hit in the compile cache: the window ran this program); compiled anew
    under a key that counts metadata where another build's text is served
    (harness/scopes.py says why)."""
    from deeplearning4j_tpu.optimize.profiler import op_scopes, scope_of
    scoped = lambda t: any(scope_of(p) for p in op_scopes(t).values())
    text = net.lower_step(ds).compile().as_text()
    if not scoped(text):
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
        jax.clear_caches()
        try:
            text = net.lower_step(ds).compile().as_text()
        finally:
            jax.config.update(
                "jax_compilation_cache_include_metadata_in_key", False)
    return text if scoped(text) else None


def run(cell, seed, seconds, tracer, setup_done):
    cfg, traffic = cell["config"], cell["traffic"]
    trainer, ring = cfg["trainer"], traffic["ring"]
    followed = trainer["followed_steps"]
    rows, seq_len, grid = shapes_of(cell)
    ref, net, model, batches, remake, change = prepare(cell, seed, ring)
    probe = min(PROBE, seq_len)
    chosen = np.asarray(program_selection(net, batches[0], seq_len - probe,
                                          probe))
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
    want, aux, ref_chosen = reference_steps(ref, remake, batches, model,
                                            trainer, probe)
    numbers = numbers_of(got, want, said, aux, chosen, ref_chosen,
                         matrix_leaves(ref, model))
    ok, compared = compare.judge(numbers, cfg["limits"])
    window_s = t_end - t_start
    label_positions = seq_len - grid[0] * grid[1] - 1
    return {
        "correct": ok and not failed, "compared": compared,
        "read": {**{k: v for k, v in numbers.items() if k not in compared},
                 "tokens_per_s": steps * rows * seq_len / window_s},
        "attempted": steps, "failed": failed,
        "memory_peak_bytes": int(peak), "check_s": now() - t_check,
        "end_to_end": {"images_per_s": steps * rows / window_s},
        "ctx": {"cell": cell, "steps": steps, "images": steps * rows,
                "window_s": window_s, "chips": 1,
                "flops_per_image": work_keye.train_flops_per_row(
                    model, seq_len, label_positions),
                "trace": tracer.result(), "step_text": text,
                "gauges": gauges, "model": model, "rows": rows,
                "seq_len": seq_len},
    }


def matrix_leaves(ref, model):
    shapes = ref.param_shapes(model)
    return [len(shapes[n][k]) >= 2 for n in sorted(shapes)
            for k in sorted(shapes[n])]


def reference_steps(ref, remake, batches, model, trainer, probe,
                    quant=False):
    """The reference (or, with `quant`, the control) over the first
    followed batches from the weights made again, and its first layer's
    selection for the probed queries."""
    seq_len = batches[0]["ids"].shape[1]
    with jax.default_matmul_precision("highest"):
        chosen = np.asarray(jax.jit(
            lambda w, b: ref.first_layer_selection(
                w, b, model, seq_len - probe, probe))(remake(), batches[0]))
        losses, g1, change, aux = ref.train_steps(
            remake(), batches[:trainer["followed_steps"]], model, trainer,
            quant=quant, remake=remake)
    return (tuple(np.asarray(a) for a in (losses, g1, change)),
            jax.tree.map(np.asarray, aux), chosen)


def numbers_of(got, want, said, aux, chosen, ref_chosen, wide):
    """The numbers compared (those the configuration has limits for) and
    read: harness/compare.py's training numbers, the indexer's loss a
    layer, the share of the reference's selected keys that the program
    selected too, keys a query, the busiest held expert over the mean."""
    out = compare.training_numbers(got, want, wide)
    mine = lambda kind, leaf: np.asarray(
        [v for k, v in sorted(said.items())
         if k.startswith(kind + ".") and k.endswith("." + leaf)])
    l_i = mine("sparseattention", "indexer_loss")
    out["indexer_loss_rel"] = float(np.max(
        np.abs(l_i - aux["indexer_loss"]) / np.abs(aux["indexer_loss"])))
    out["selection_agreement"] = float(
        (chosen & ref_chosen).sum() / ref_chosen.sum())
    out["selected_keys_per_query"] = float(np.mean(
        mine("sparseattention", "selected_keys_per_query")))
    out["selected_keys_per_query_ref"] = float(np.mean(aux["selected_keys"]))
    held = aux["held_pairs"]
    out["moe_load_max_over_mean_ref"] = float(np.max(
        held.max(-1) / held.mean(-1)))
    out["moe_load_max_over_mean"] = float(np.max(
        mine("moe", "held_pairs_max") / mine("moe", "held_pairs_mean")))
    return out


def calibrate(cell, seeds, emit, seconds=None):
    """The readings the limits are set from, at the cell's own size, many
    seeds in one process: the program against the reference (lower), the
    fp8 control against it (upper)."""
    cfg = cell["config"]
    trainer = cfg["trainer"]
    followed = trainer["followed_steps"]
    _, seq_len, _ = shapes_of(cell)
    probe = min(PROBE, seq_len)
    for seed in seeds:
        ref, net, model, batches, remake, change = prepare(cell, seed,
                                                           followed)
        chosen = np.asarray(program_selection(
            net, batches[0], seq_len - probe, probe))
        got, said = first_steps(ref, net, batches, followed, change)
        del net
        gc.collect()
        wide = matrix_leaves(ref, model)
        want, aux, ref_chosen = reference_steps(ref, remake, batches, model,
                                                trainer, probe)
        emit(seed, "program", numbers_of(got, want, said, aux, chosen,
                                         ref_chosen, wide))
        alt, *_ = reference_steps(ref, remake, batches, model, trainer,
                                  probe, quant=True)
        emit(seed, "control_fp8", compare.training_numbers(alt, want, wide))
