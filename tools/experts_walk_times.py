"""One `moe` layer (router, sort, the held experts' walk over blocks;
parallel/moe.py `route_all` + `held_experts_ffn`), forward and backward,
timed alone on the chip at a cell's shape, block size by block size: what
PERF.md gives for "a layer-step" of the experts, and what the default block
size was kept from. One JSON line a block size and kind of product.
`--shape vl8k` (the default) is keye-vl-2.0-30b-a3b's layer, `lc16k`
laguna-xs.2's routed experts (32 of 256 held, width 512; the shared expert
is not the walk's), `mtp8k` joyai-llm-flash's (16 of 256, width 768),
`ssm16k` nemotron-3-nano-30b-a3b's two-matrix relu^2 experts (8 of 128,
width 1,856).

    chiprun -- python tools/experts_walk_times.py                  # this tree
    chiprun -- python tools/experts_walk_times.py --block-rows 0 \\
        --module .chip_tree/parent/deeplearning4j_tpu/parallel/moe.py
    python tools/experts_walk_times.py --shape ssm16k --block-rows 0 \\
        --kernel ragged,grouped --row-tile 128,256,512     # on a TPU

`--block-rows 0` is the function's own default. `--kernel` names the
products' form: `grouped` the Pallas kernels of ops/grouped_matmul.py,
`ragged` `lax.ragged_dot` (what `inside_kernel` would say is set aside
either way; a line says which ran and how far its value and gradients lie
from the first kind's on the same inputs); `--row-tile` the kernels' row
tiles to try. `--module` times another checkout's function in the same
process (a parent unpacked under .chip_tree/; it has the products it has).
`--skew` sends every pair to held experts (the static worst case: every
block runs). `--compile-only` compiles every size for a described v5e with
no chip attached and times nothing. `--lower-only` traces and lowers, for a
described v5e, one layer and `--layers` layers of the same widths (each
under `jax.checkpoint`, as a rematerialising step runs them), forward and
backward, and prints the host seconds of each and the distinct Pallas
kernels in the text: what the walk adds to a step's build, with no chip. A
time from the chip comes from a TPU or not at all.
"""
import argparse
import importlib.util
import json
import os
import statistics
import sys
import tempfile
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax                                                        # noqa: E402
import jax.numpy as jnp                                           # noqa: E402

# (tokens a step, width, expert width, experts, experts a token, held,
# gated): train-vl8k and train-mtp8k 2 rows of 8,192; train-lc16k and
# train-ssm16k 1 row of 16,384
SHAPES = {"vl8k": (16_384, 2048, 768, 128, 8, 16, True),
          "lc16k": (16_384, 2048, 512, 256, 8, 32, True),
          "mtp8k": (16_384, 2048, 768, 256, 8, 16, True),
          "ssm16k": (16_384, 2688, 1856, 128, 6, 8, False)}
N, D, F, E, K, G, GATED = SHAPES["vl8k"]
FIRST = 0
REPS, SETS = 10, 5      # calls a timing, timings a median


def load(path):
    if path is None:
        from deeplearning4j_tpu.parallel import moe
        return moe
    spec = importlib.util.spec_from_file_location(
        "deeplearning4j_tpu.parallel.moe_at_" + str(abs(hash(path))), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def layer_step(mod, block_rows):
    """value and gradients of one layer as `MoELayer` calls it."""
    def loss(x, wr, wg, wu, wd, ct):
        with jax.named_scope("router"):
            experts, gates = mod.route_all(wr, x, K)
        y = mod.held_experts_ffn(x, experts, gates, wg if GATED else None,
                                 wu, wd, FIRST, E, block_rows or None)[0]
        return jnp.sum(y * ct)
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)))


def layers_step(mod, n):
    """value and gradients of n such layers one after another, each its own
    weights and each under `jax.checkpoint`, as a rematerialising step runs
    them."""
    def loss(x, wr, wg, wu, wd, ct):
        for i in range(n):
            def layer(h, wr, wg, wu, wd):
                experts, gates = mod.route_all(wr, h, K)
                y = mod.held_experts_ffn(h, experts, gates,
                                         wg if GATED else None, wu, wd,
                                         FIRST, E)[0]
                return h + y.astype(h.dtype)
            x = jax.checkpoint(layer)(x, wr[i], wg[i], wu[i], wd[i])
        return jnp.sum(x.astype(jnp.float32) * ct)
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)))


def shapes(sharding=None, layers=None):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    n = () if layers is None else (layers,)
    bf = jnp.bfloat16
    return (s((N, D), bf), s(n + (D, E), bf), s(n + (G, D, F), bf),
            s(n + (G, D, F), bf), s(n + (G, F, D), bf),
            s((N, D), jnp.float32))


def arrays(skew, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x, wr, wg, wu, wd, ct = (
        (jax.random.normal(kk, s.shape, jnp.float32)
         * (1.0 if i in (0, 5) else 0.02)).astype(s.dtype)
        for i, (kk, s) in enumerate(zip(ks, shapes())))
    if skew:        # K held columns far above the rest, on positive rows
        x, wr = jnp.abs(x), wr.at[:, FIRST:FIRST + K].add(1.0)
    return x, wr, wg, wu, wd, ct


def as_kernel(kind):
    """A context in which `inside_kernel` says what `kind` asks for
    ("grouped": 1.0, "ragged": 0.0); "tree": what the shapes say."""
    import contextlib
    from deeplearning4j_tpu.ops import grouped_matmul as gm

    @contextlib.contextmanager
    def ctx():
        rule = gm.inside_kernel
        if kind != "tree":
            gm.inside_kernel = lambda *shape: float(kind == "grouped")
        try:
            yield
        finally:
            gm.inside_kernel = rule
    return ctx()


def device_split(compiled, operands):
    """(device ms a call, {last name of an operation's path: ms a call}),
    from a trace of REPS calls joined with the compiled text by instruction
    name; loops' own events left out, as `benchmarks/harness/inner_scopes.py`
    leaves them out."""
    from deeplearning4j_tpu.optimize.profiler import (
        _device_ops, instruction_name, op_scopes, trace)
    table = op_scopes(compiled.as_text())
    with tempfile.TemporaryDirectory() as logdir:
        with trace(logdir):
            for _ in range(REPS):
                out = compiled(*operands)
            jax.block_until_ready(out)
        ops = _device_ops(logdir)
    by = {}
    for name, ms in ops:
        instr = instruction_name(name)
        if instr.startswith(("while", "conditional", "call")):
            continue
        path = table.get(instr, "(no metadata)").split("/")
        key = ("backward " if any(p.startswith("transpose(") for p in path)
               else "") + ("experts " if "experts" in path else "") + path[-1]
        by[key] = by.get(key, 0.0) + ms / REPS
    top = sorted(by.items(), key=lambda kv: -kv[1])
    return sum(by.values()), {k: round(v, 3) for k, v in top[:16]}


def gap(a, b):
    """|a - b| / |b| over each output, the largest."""
    return max(float(jnp.linalg.norm((u.astype(jnp.float32)
                                      - v.astype(jnp.float32)).ravel())
                     / jnp.maximum(jnp.linalg.norm(
                         v.astype(jnp.float32).ravel()), 1e-30))
               for u, v in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def lower_only(mod, layers, kinds, say):
    """Trace and lower 1 and `layers` layers for a described v5e, each from
    empty caches: host seconds and the distinct Pallas kernels of each
    text."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from deeplearning4j_tpu.ops import grouped_matmul as gm
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    resolve, gm._resolve_interpret = gm._resolve_interpret, lambda i: False
    try:
        for kind in kinds:
            for n in sorted({1, layers}):
                jax.clear_caches()
                with as_kernel(kind):
                    fn = layers_step(mod, n)
                    t0 = time.perf_counter()
                    traced = fn.trace(*shapes(one, n))
                    t1 = time.perf_counter()
                    text = traced.lower().as_text()
                    t2 = time.perf_counter()
                say(kernel=kind, layers=n, trace_s=round(t1 - t0, 3),
                    lower_s=round(t2 - t1, 3),
                    text_mb=round(len(text) / 1e6, 3),
                    pallas_kernels=text.count("tpu_custom_call"))
    finally:
        gm._resolve_interpret = resolve


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--module", default=None)
    p.add_argument("--block-rows", default="0,8192,4096")
    p.add_argument("--skew", action="store_true")
    p.add_argument("--shape", choices=sorted(SHAPES), default="vl8k")
    p.add_argument("--kernel", default="tree",
                   help="comma-separated of tree, ragged, grouped")
    p.add_argument("--row-tile", default="",
                   help="the grouped kernels' row tiles to try, comma-"
                        "separated (default: the module's)")
    p.add_argument("--compile-only", action="store_true")
    p.add_argument("--lower-only", action="store_true")
    p.add_argument("--layers", type=int, default=4)
    a = p.parse_args()
    global N, D, F, E, K, G, GATED
    N, D, F, E, K, G, GATED = SHAPES[a.shape]
    mod = load(a.module)
    sizes = [int(b) for b in a.block_rows.split(",")]
    kinds = a.kernel.split(",")
    from deeplearning4j_tpu.ops import grouped_matmul as gm
    tiles = [int(t) for t in a.row_tile.split(",") if t] or [gm.ROW_TILE]
    say = lambda **kw: print(json.dumps(
        {"module": a.module or "this tree", "shape": a.shape,
         "skew": a.skew, **kw}),
        flush=True)

    if a.lower_only:
        lower_only(mod, a.layers, kinds, say)
        return 0

    if a.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        sh = shapes(SingleDeviceSharding(topo.devices[0]))
        resolve, gm._resolve_interpret = gm._resolve_interpret, \
            lambda i: False
        try:
            for rows in sizes:
                for kind in kinds:
                    with as_kernel(kind):
                        m = layer_step(mod, rows).lower(
                            *sh).compile().memory_analysis()
                    say(block_rows=rows, kernel=kind, compiled=True,
                        temp_bytes=m.temp_size_in_bytes)
        finally:
            gm._resolve_interpret = resolve
        return 0

    if jax.default_backend() != "tpu":
        print(f"found platform {jax.default_backend()!r}, not a TPU; "
              "refusing to measure", file=sys.stderr)
        return 4
    operands = arrays(a.skew)
    for rows in sizes:
        first = None
        for kind in kinds:
            for tile in (tiles if kind != "ragged" else tiles[:1]):
                gm.ROW_TILE, kept = tile, gm.ROW_TILE
                try:
                    with as_kernel(kind):
                        compiled = layer_step(mod, rows).lower(
                            *operands).compile()
                        ran = gm.inside_kernel(
                            mod.walk_rows(N, K, G, E, rows or None), D, F, G
                        ) if hasattr(mod, "walk_rows") else 0.0
                finally:
                    gm.ROW_TILE = kept
                out = jax.block_until_ready(compiled(*operands))
                if first is None:
                    first = out
                ms = []
                for _ in range(SETS):
                    t0 = time.perf_counter()
                    for _ in range(REPS):
                        res = compiled(*operands)
                    jax.block_until_ready(res)
                    ms.append((time.perf_counter() - t0) / REPS * 1e3)
                dev, split = device_split(compiled, operands)
                say(block_rows=rows, kernel=kind,
                    row_tile=tile if kind != "ragged" else None,
                    grouped=bool(ran) if kind == "tree" else
                    kind == "grouped", gap=gap(out, first),
                    ms=statistics.median(ms), ms_min=min(ms),
                    ms_max=max(ms), device_ms=dev, split=split,
                    device=jax.devices()[0].device_kind)
    return 0


if __name__ == "__main__":
    sys.exit(main())
