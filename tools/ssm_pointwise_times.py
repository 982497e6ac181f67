"""The Mamba-2 layer's convolution and gated norm alone
(`ops/ssm_pointwise.py conv_silu`, `gated_norm`), forward and forward +
backward, timed on the chip at a cell's geometry, as the XLA forms and as
the Pallas kernels, so that a change to either can be sized without the
cell. One JSON line a variant. The geometry is
`nemotron-3-nano-30b-a3b.train-ssm16k`'s: 1 row, T = 16,384; the
convolution 4 taps over 6,144 channels read at channel 4,096 of the
in-projection's 10,304, the norm 8 groups of 512 over 4,096 channels with
the gate at channel 0 of the same projection; bfloat16 operands.

    python tools/ssm_pointwise_times.py                 # on a TPU host
    python tools/ssm_pointwise_times.py --op conv --blocks 256x1024,512x1024
    JAX_PLATFORMS=cpu python tools/ssm_pointwise_times.py --compile-only

A variant is (op, form, block): `form` "kernel" (`ssm_conv_fwd` /
`ssm_conv_bwd`, `ssm_norm_fwd` / `ssm_norm_bwd`) or "xla" (the XLA form
under its own `jax.checkpoint`, as the layer ran it before the kernels),
whatever the module's rules would say at these shapes; `block`, for
"kernel" only, the convolution's channels x positions a block (`CONV_ROWS`
x `CONV_LANES`) or the norm's (`NORM_BLOCK`, their product: 512 x 1024 is a
group of 512 by 1,024 positions). Operands
are made a position down a column, as the layer's arrays lie between its
products, so no transpose stands around the timed call. "both" is the
forward and its VJP against a fixed cotangent, no loss; `gap` is each of
its outputs (the result, then each gradient) against the XLA form's, as
|a - b| / |b|. `--compile-only`
compiles every variant for a described v5e with no chip attached, prints
its temporaries and times nothing. A time comes from a TPU or not at all.
"""
import argparse
import contextlib
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

from deeplearning4j_tpu.ops import ssm_pointwise as sp            # noqa: E402

ROWS, T, INNER, CONV, DT, K, G = 1, 16_384, 4096, 6144, 64, 4, 8
WIDTH = INNER + CONV + DT
REPS, SETS = 10, 5      # calls a timing, timings a median


def _across(a):
    return jnp.swapaxes(a, 1, 2)


@contextlib.contextmanager
def steered(form, block):
    """The module's rules say `form` and its blocks are `block` while a
    variant traces, its backward included."""
    kept = (sp.conv_inside_kernel, sp.norm_inside_kernel, sp.CONV_ROWS,
            sp.CONV_LANES, sp.NORM_BLOCK)
    sp.conv_inside_kernel = sp.norm_inside_kernel = (
        lambda *shape: float(form == "kernel"))
    if block:
        sp.CONV_ROWS, sp.CONV_LANES = block
        sp.NORM_BLOCK = block[0] * block[1]
    try:
        yield
    finally:
        (sp.conv_inside_kernel, sp.norm_inside_kernel, sp.CONV_ROWS,
         sp.CONV_LANES, sp.NORM_BLOCK) = kept


def op_of(op, form, block):
    """(forward, forward and VJP) of `op` in `form`; arrays in and out a
    position down a column ([rows, channels, T])."""
    def run(*operands):
        if op == "conv":
            zxd, w, b = operands
            return _across(sp.conv_silu(_across(zxd), w, b, lo=INNER))
        y, zxd, w = operands
        return _across(sp.gated_norm(_across(y), _across(zxd), w, G, 1e-5))

    def fwd(*operands):
        with steered(form, block):
            return run(*operands)

    def both(*operands):
        with steered(form, block):
            out, vjp = jax.vjp(run, *operands[:-1])
            return out, vjp(operands[-1])

    return jax.jit(fwd), jax.jit(both)


def shapes(op, sharding=None):
    s = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dt, sharding=sharding)
    proj = s((ROWS, WIDTH, T))
    if op == "conv":
        return (proj, s((CONV, K)), s((CONV,)), s((ROWS, CONV, T)))
    return (s((ROWS, INNER, T)), proj, s((INNER,)), s((ROWS, INNER, T)))


def arrays(op, seed=0):
    """Operands as a seeded layer makes them: the taps and bias uniform in
    +-0.5, the norm's weight 1 +- 0.1, the rest normal."""
    sh = shapes(op)
    ks = jax.random.split(jax.random.PRNGKey(seed), len(sh))
    out = []
    for k, s in zip(ks, sh):
        if len(s.shape) < 3:
            base = (jax.random.uniform(k, s.shape) - 0.5 if op == "conv"
                    else 1.0 + 0.1 * jax.random.normal(k, s.shape))
        else:
            base = jax.random.normal(k, s.shape, jnp.float32)
        out.append(base.astype(s.dtype))
    return out


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
    p.add_argument("--op", default="conv,norm")
    p.add_argument("--form", default="kernel,xla")
    p.add_argument("--blocks", default="",
                   help="kernel blocks, channels x positions (the norm "
                        "reads their product), e.g. 256x1024,512x512")
    p.add_argument("--compile-only", action="store_true")
    a = p.parse_args()
    blocks = [tuple(int(n) for n in b.split("x"))
              for b in a.blocks.split(",") if b] or [None]
    variants = [(op, form, block)
                for op in a.op.split(",") for form in a.form.split(",")
                for block in (blocks if form == "kernel" else [None])]
    say = lambda **kw: print(json.dumps(kw), flush=True)

    if a.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        one = SingleDeviceSharding(topo.devices[0])
        sp._resolve_interpret = lambda interpret: False    # Mosaic, not CPU
        for op, form, block in variants:
            fwd, both = op_of(op, form, block)
            sh = shapes(op, one)
            try:
                temps = [f.lower(*args).compile().memory_analysis()
                         .temp_size_in_bytes
                         for f, args in ((fwd, sh[:-1]), (both, sh))]
            except jax.errors.JaxRuntimeError as e:   # e.g. VMEM exceeded
                say(op=op, form=form, block=block, compiled=False,
                    error=str(e).splitlines()[0][:300])
                continue
            say(op=op, form=form, block=block, compiled=True,
                fwd_temp_bytes=temps[0], temp_bytes=temps[1])
        return 0

    if jax.default_backend() != "tpu":
        print(f"found platform {jax.default_backend()!r}, not a TPU; "
              "refusing to measure", file=sys.stderr)
        return 4
    truth = {}
    for op, form, block in variants:
        operands = arrays(op)
        fwd, both = op_of(op, form, block)
        if op not in truth:
            truth[op] = jax.tree.leaves(op_of(op, "xla", None)[1](*operands))
        say(op=op, form=form, block=block,
            forward=timed(fwd, operands[:-1]), both=timed(both, operands),
            gap=[gap(a, b) for a, b in zip(jax.tree.leaves(both(*operands)),
                                           truth[op])],
            device=jax.devices()[0].device_kind)
    return 0


def gap(a, b):
    """|a - b| / |b| over the whole array (float32): a variant's output or
    gradient against the XLA form's."""
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


if __name__ == "__main__":
    sys.exit(main())
