"""The chunked state-space scan alone (`ops/ssd.py ssd_scan`), forward and
forward + backward, timed on the chip at a cell's geometry, so that how many
chunks' insides are computed at once and what the backward keeps are chosen
from chip readings (PR 38 also timed an associative scan across the chunks
here: three times dearer, PERF.md section 6, and gone from the module). One
JSON line a variant. The geometry is `nemotron-3-nano-30b-a3b.train-ssm16k`'s:
1 row, T = 16,384, 64 heads of 64 in 8 groups, state 128, chunks of 128,
bfloat16 operands.

    chiprun -- python tools/ssd_times.py
    chiprun -- python tools/ssd_times.py --inside xla --block 0,16

A variant is (inside, block, keep): `inside` which form runs, "kernel" (the
Pallas kernels `ssd_fwd` and `ssd_bwd`, PR 39) or "xla" (the XLA products),
whatever `ssd.inside_kernel` would say at these shapes; `block`, for "xla"
only, chunks a `lax.map` step of the inside of the chunks (0: all at once);
`keep` what a rematerialising caller saves for the backward: "states" (the
states entering each chunk, `ssd.KEEP`: the backward runs no forward
recurrence), "nothing" (everything again) or "all" (no rematerialisation:
jax keeps every intermediate, the pairs' decays among them).
`--compile-only` compiles every variant for a described v5e with no chip
attached, prints its temporaries and times nothing. A time comes from a TPU
or not at all.
"""
import argparse
import json
import os
import statistics
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax                                                        # noqa: E402
import jax.numpy as jnp                                           # noqa: E402

from deeplearning4j_tpu.ops import ssd                            # noqa: E402

ROWS, T, H, P, G, N, L = 1, 16_384, 64, 64, 8, 128, 128
REPS, SETS = 5, 5       # calls a timing, timings a median


def scan_of(inside, block, keep):
    """(forward, value-and-gradients) of the scan in the form `inside` under
    what a rematerialising layer would keep."""
    def run(x, dt, A, B, C):
        rule, ssd.inside_kernel = ssd.inside_kernel, lambda *shape: float(
            inside == "kernel")
        try:
            return ssd.ssd_scan(x, dt, A, B, C, jnp.zeros(H, jnp.float32),
                                L, block=block or None)
        finally:
            ssd.inside_kernel = rule

    if keep != "all":
        policy = (jax.checkpoint_policies.save_only_these_names(ssd.KEEP)
                  if keep == "states" else None)
        run = jax.checkpoint(run, policy=policy)

    def loss(x, dt, A, B, C, ct):
        y, last = run(x, dt, A, B, C)
        return jnp.sum(y.astype(jnp.float32) * ct) + jnp.sum(last)

    return jax.jit(run), jax.jit(jax.value_and_grad(loss, argnums=range(5)))


def shapes(sharding=None):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    bf, f32 = jnp.bfloat16, jnp.float32
    return (s((ROWS, T, H, P), bf), s((ROWS, T, H), f32), s((H,), f32),
            s((ROWS, T, G, N), bf), s((ROWS, T, G, N), bf),
            s((ROWS, T, H, P), f32))


def arrays(seed=0):
    """Operands as a seeded layer makes them: time steps log-uniform in
    [0.001, 0.1], A in [-16, -1]."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    sh = shapes()
    normal = lambda k, s: jax.random.normal(k, s.shape, jnp.float32
                                            ).astype(s.dtype)
    dt = jnp.exp(jax.random.uniform(ks[1], sh[1].shape, jnp.float32,
                                    -6.9077, -2.3026))
    A = -jax.random.uniform(ks[2], sh[2].shape, jnp.float32, 1.0, 16.0)
    return (normal(ks[0], sh[0]), dt, A, normal(ks[3], sh[3]),
            normal(ks[4], sh[4]), normal(ks[5], sh[5]))


def timed(fn, operands):
    jax.block_until_ready(fn(*operands))
    ms = []
    for _ in range(SETS):
        t0 = time.perf_counter()
        for _ in range(REPS):
            out = fn(*operands)
        jax.block_until_ready(out)
        ms.append((time.perf_counter() - t0) / REPS * 1e3)
    return {"ms": statistics.median(ms), "ms_min": min(ms), "ms_max": max(ms)}


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--inside", default="kernel,xla")
    p.add_argument("--block", default="32")
    p.add_argument("--keep", default="states,nothing")
    p.add_argument("--compile-only", action="store_true")
    a = p.parse_args()
    variants = [(inside, block, keep)
                for inside in a.inside.split(",")
                for block in ([int(b) for b in a.block.split(",")]
                              if inside == "xla" else [0])
                for keep in a.keep.split(",")]
    say = lambda **kw: print(json.dumps(kw), flush=True)

    if a.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        sh = shapes(SingleDeviceSharding(topo.devices[0]))
        ssd._resolve_interpret = lambda interpret: False    # Mosaic, not CPU
        for inside, block, keep in variants:
            fwd, both = scan_of(inside, block, keep)
            say(inside=inside, block=block, keep=keep, compiled=True,
                fwd_temp_bytes=fwd.lower(*sh[:5]).compile()
                .memory_analysis().temp_size_in_bytes,
                temp_bytes=both.lower(*sh).compile()
                .memory_analysis().temp_size_in_bytes)
        return 0

    if jax.default_backend() != "tpu":
        print(f"found platform {jax.default_backend()!r}, not a TPU; "
              "refusing to measure", file=sys.stderr)
        return 4
    operands = arrays()
    for inside, block, keep in variants:
        fwd, both = scan_of(inside, block, keep)
        say(inside=inside, block=block, keep=keep,
            forward=timed(fwd, operands[:5]), both=timed(both, operands),
            device=jax.devices()[0].device_kind)
    return 0


if __name__ == "__main__":
    sys.exit(main())
