"""One `moe` layer (router, sort, the held experts' walk over blocks;
parallel/moe.py `route_all` + `held_experts_ffn`), forward and backward,
timed alone on the chip at a cell's shape, block size by block size: what
PERF.md gives for "a layer-step" of the experts, and what the default block
size was kept from. One JSON line a block size. `--shape vl8k` (the
default) is keye-vl-2.0-30b-a3b's layer, `--shape lc16k` laguna-xs.2's
routed experts (32 of 256 held, width 512; the shared expert is not the
walk's).

    chiprun -- python tools/experts_walk_times.py                  # this tree
    chiprun -- python tools/experts_walk_times.py --block-rows 0 \\
        --module .chip_tree/parent/deeplearning4j_tpu/parallel/moe.py

`--block-rows 0` is the function's own default. `--module` times another
checkout's function in the same process (a parent unpacked under
.chip_tree/). `--skew` sends every pair to held experts (the static worst
case: every block runs). `--compile-only` compiles every size for a
described v5e with no chip attached and times nothing. A time comes from a
TPU or not at all.
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

# (tokens a step, width, expert width, experts, experts a token, held):
# train-vl8k 2 rows of 8,192; train-lc16k 1 row of 16,384
SHAPES = {"vl8k": (16_384, 2048, 768, 128, 8, 16),
          "lc16k": (16_384, 2048, 512, 256, 8, 32)}
N, D, F, E, K, G = SHAPES["vl8k"]
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
        y = mod.held_experts_ffn(x, experts, gates, wg, wu, wd, FIRST, E,
                                 block_rows or None)[0]
        return jnp.sum(y * ct)
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)))


def shapes(sharding=None):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    bf = jnp.bfloat16
    return (s((N, D), bf), s((D, E), bf), s((G, D, F), bf), s((G, D, F), bf),
            s((G, F, D), bf), s((N, D), jnp.float32))


def arrays(skew, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x, wr, wg, wu, wd, ct = (
        (jax.random.normal(kk, s.shape, jnp.float32)
         * (1.0 if i in (0, 5) else 0.02)).astype(s.dtype)
        for i, (kk, s) in enumerate(zip(ks, shapes())))
    if skew:        # K held columns far above the rest, on positive rows
        x, wr = jnp.abs(x), wr.at[:, FIRST:FIRST + K].add(1.0)
    return x, wr, wg, wu, wd, ct


def device_split(fn, operands):
    """(device ms a call, {last name of an operation's path: ms a call}),
    from a trace of REPS calls joined with the compiled text by instruction
    name; loops' own events left out, as `benchmarks/harness/inner_scopes.py`
    leaves them out."""
    from deeplearning4j_tpu.optimize.profiler import (
        _device_ops, instruction_name, op_scopes, trace)
    table = op_scopes(fn.lower(*operands).compile().as_text())
    with tempfile.TemporaryDirectory() as logdir:
        with trace(logdir):
            for _ in range(REPS):
                out = fn(*operands)
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


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--module", default=None)
    p.add_argument("--block-rows", default="0,8192,4096")
    p.add_argument("--skew", action="store_true")
    p.add_argument("--shape", choices=sorted(SHAPES), default="vl8k")
    p.add_argument("--compile-only", action="store_true")
    a = p.parse_args()
    global N, D, F, E, K, G
    N, D, F, E, K, G = SHAPES[a.shape]
    mod = load(a.module)
    sizes = [int(b) for b in a.block_rows.split(",")]
    say = lambda **kw: print(json.dumps(
        {"module": a.module or "this tree", "shape": a.shape,
         "skew": a.skew, **kw}),
        flush=True)

    if a.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        sh = shapes(SingleDeviceSharding(topo.devices[0]))
        for rows in sizes:
            m = layer_step(mod, rows).lower(*sh).compile().memory_analysis()
            say(block_rows=rows, compiled=True,
                temp_bytes=m.temp_size_in_bytes)
        return 0

    if jax.default_backend() != "tpu":
        print(f"found platform {jax.default_backend()!r}, not a TPU; "
              "refusing to measure", file=sys.stderr)
        return 4
    operands = arrays(a.skew)
    for rows in sizes:
        fn = layer_step(mod, rows)
        jax.block_until_ready(fn(*operands))
        ms = []
        for _ in range(SETS):
            t0 = time.perf_counter()
            for _ in range(REPS):
                out = fn(*operands)
            jax.block_until_ready(out)
            ms.append((time.perf_counter() - t0) / REPS * 1e3)
        dev, split = device_split(fn, operands)
        say(block_rows=rows, ms=statistics.median(ms), ms_min=min(ms),
            ms_max=max(ms), device_ms=dev, split=split,
            device=jax.devices()[0].device_kind)
    return 0


if __name__ == "__main__":
    sys.exit(main())
