"""One call of each masked-attention kernel (ops/sparse_attention.py: forward
and backward) timed alone on the chip at a cell's shape, block shape by
block shape: the numbers PERF.md gives for "a call", and what the module's
`BLOCK` and `WINDOW_BLOCK` were chosen from. One JSON line a (kernel, block
shape). `bwd` (PR 37) is the whole of `_bwd`, whatever kernels the module
makes it of (one since PR 37, dQ's and dK/dV's before it and past
`SLAB_BUDGET`): `device_ms` there is ALL the device's operations of a call,
`kernels_device_ms` the kernels' own, so that parent and change read on one
scale. `dq` and `dkv` call `_bwd` too and keep a part of its results: of a
two-pass module that leaves one kernel in the program, of a one-pass module
its one kernel, whole, either way.
`--schedule mask` (the default) is `train-vl8k`'s: a mask operand at
T = 8192, 32 / 4 heads; `causal` and `window` are `train-lc16k`'s
full and window layers: the mask made from positions at T = 16384, 48 or
64 query heads over 8 (`--T`, `--heads`, `--kv`, `--window` override);
`latent` and `latent192` are `train-mtp8k`'s latent attention at T = 8192,
32 heads, scores 192 wide over values 128 wide, fed in the two ways there
are: two products a tile (the head's own 128 slots, and 64 against the ONE
rotary key all heads share: `shared_key_attention`), or one 192-wide key a
head with the rotary key repeated 32 times in HBM (`masked_attention`, its
gradient's sum over the heads left to XLA). `indexer` (PR 35) is not an
attention kernel but the same cell's learned selection: one row's 16 chunks
of 512 queries of `index_scores`, each against its prefix of keys, at
T = 8192, 16 heads of 64: the forward as XLA fuses it (`index_fwd`), its VJP
as XLA makes it under `jax.checkpoint`, which is what the step ran before
PR 35 (`index_vjp_xla`), and `index_scores_bwd`, the kernel that took its
place, at `--blocks` key blocks (`index_bwd`; "x512,x1024", the query block
is the chunk); `device_ms` there is ALL the device's operations of a call.

    chiprun -- python tools/attend_kernel_times.py                  # this tree
    chiprun -- python tools/attend_kernel_times.py --schedule window \\
        --blocks 256x256,256x512,512x512
    chiprun -- python tools/attend_kernel_times.py \\
        --module .chip_tree/parent/deeplearning4j_tpu/ops/sparse_attention.py
    chiprun -- python tools/attend_kernel_times.py --schedule latent \\
        --blocks 1024x1024 --kernels fwd,bwd

`--module` times another checkout's kernels in the same process (a parent
unpacked under .chip_tree/). `--compile-only` compiles every shape for a
described v5e with no chip attached and times nothing (what Mosaic refuses,
it refuses here). A time comes from a TPU or not at all.
"""
import argparse
import importlib.util
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

# one sequence of a cell's attention, as a layer's row calls it: (T, query
# heads, key/value heads, window) by schedule
D, TOPK = 128, 2048
SHARED = 64             # slots of the latent attention's shared rotary key
SHAPES = {"mask": (8192, 32, 4, None), "causal": (16384, 48, 8, None),
          "window": (16384, 64, 8, 512), "latent": (8192, 32, 32, None),
          "latent192": (8192, 32, 32, None), "indexer": (8192, 16, 1, None)}
CHUNK, INDEX_DIM = 512, 64      # the indexer's query chunk and head width


def widths(a):
    """(scored width of the head's own key, slots of a shared key)."""
    return {"latent": (D, SHARED), "latent192": (D + SHARED, 0)}.get(
        a.schedule, (D, 0))


def scale_of(a):
    return sum(widths(a)) ** -0.5
REPS, SETS = 20, 5      # calls a timing, timings a median


def load(path):
    if path is None:
        from deeplearning4j_tpu.ops import sparse_attention
        return sparse_attention
    # under the package's name, so that its relative imports resolve
    spec = importlib.util.spec_from_file_location(
        "deeplearning4j_tpu.ops.sparse_attention_at_" + str(abs(hash(path))),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def kernels(mod, a):
    """(name, bq, bk, function of (q, k, v, mask, o, lse, do, q2, k2)) of
    every kernel and block shape asked for, one kernel a function: a result
    nobody reads takes its kernel out of the program."""
    for block in a.blocks.split(","):
        bq, bk = (int(x) for x in block.split("x"))
        for name, fn in one_each(mod, scale_of(a), bq, bk, a).items():
            if name in a.kernels.split(","):
                yield name, bq, bk, jax.jit(fn)


def one_each(mod, scale, bq, bk, a):
    flat = mod._flat
    # a mask operand, or (by position) none and the window
    by = {} if a.schedule == "mask" else {"window": a.window}
    given = (lambda m: m) if a.schedule == "mask" else (lambda m: None)
    shared = (lambda q2, k2: {"shared": (flat(q2), flat(k2))}
              ) if a.schedule == "latent" else (lambda q2, k2: {})

    def bwd(q, k, v, mask, o, lse, do, q2, k2):
        B, H, T, _ = q.shape
        return mod._bwd(flat(q), flat(k), flat(v), given(mask), flat(o),
                        lse.reshape(B * H, T, 1), flat(do), scale, bq, bk,
                        False, **by, **shared(q2, k2))

    # under a shared key dQ is (dq, dq2) and dK/dV (dk, dv, dk2)
    dq_of = (lambda g: (g[0], g[3])) if a.schedule == "latent" else (
        lambda g: g[0])
    dkv_of = (lambda g: (g[1], g[2], g[4])) if a.schedule == "latent" else (
        lambda g: g[1:])
    return {
        "fwd": lambda q, k, v, mask, o, lse, do, q2, k2: mod._fwd(
            flat(q), flat(k), flat(v), given(mask), scale, bq, bk, False,
            **by, **shared(q2, k2)),
        "dq": lambda *a: dq_of(bwd(*a)),
        "dkv": lambda *a: dkv_of(bwd(*a)),
        "bwd": bwd}


def plain_index_scores(qi, ki, w):
    """`decoder.index_scores` as it stood before PR 35 gave it a backward
    of its own: what XLA differentiates."""
    dots = jnp.einsum("chd,sd->hcs", qi, ki,
                      preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(dots)
                   * w.astype(jnp.float32).T[:, :, None], 0)


def indexer_shapes(a, sharding=None):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    return (s((a.T, a.heads, INDEX_DIM), jnp.bfloat16),
            s((a.T, INDEX_DIM), jnp.bfloat16), s((a.T, a.heads), jnp.bfloat16),
            s((a.T, a.T), jnp.float32))


def indexer_arrays(a, seed=0):
    """Seeded qi, ki, w and a cotangent that is zero above the diagonal, as
    the KL's is."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    qi, ki, w, g = (jax.random.normal(kk, s.shape, s.dtype)
                    for kk, s in zip(ks, indexer_shapes(a)))
    t = jnp.arange(a.T)
    return qi, ki, w, jnp.where(t[None, :] <= t[:, None], g, 0.0)


def indexer_kernels(mod, a):
    """(name, 0, key block, function of (qi, ki, w, g)): a row's chunks one
    after another, each result returned, as `_row` keeps each."""
    chunks = [(c, c + CHUNK) for c in range(0, a.T, CHUNK)]

    def each(fn):
        return jax.jit(lambda qi, ki, w, g: [
            fn(qi[lo:hi], ki[:hi], w[lo:hi], g[lo:hi, :hi])
            for lo, hi in chunks])

    def vjp_xla(qi, ki, w, g):
        return jax.vjp(jax.checkpoint(plain_index_scores), qi, ki, w)[1](g)

    wanted = a.kernels.split(",")
    if "index_fwd" in wanted:
        yield "index_fwd", 0, 0, each(
            lambda qi, ki, w, g: plain_index_scores(qi, ki, w))
    if "index_vjp_xla" in wanted:
        yield "index_vjp_xla", 0, 0, each(vjp_xla)
    if "index_bwd" in wanted and hasattr(mod, "index_scores_bwd"):
        for block in a.blocks.split(","):
            bk = int(block.split("x")[1])
            yield "index_bwd", 0, bk, each(
                lambda qi, ki, w, g, bk=bk: mod.index_scores_bwd(
                    qi, ki, w, g, block_k=bk, interpret=False))


def shapes(a, sharding=None):
    B, H, KV, T = 1, a.heads, a.kv, a.T
    d, d2 = widths(a)           # v, o and do are D wide whatever is scored
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    bf = jnp.bfloat16
    # by position there is no mask: a [1, 1, 1] stands in its place, and a
    # [1, 1, T, 1] in a shared key's where there is none
    M = T if a.schedule == "mask" else 1
    return (s((B, H, T, d), bf), s((B, KV, T, d), bf), s((B, KV, T, D), bf),
            s((B, M, M), jnp.int8), s((B, H, T, D), bf),
            s((B, H, T), jnp.float32), s((B, H, T, D), bf),
            s((B, H if d2 else 1, T, d2 or 1), bf), s((B, 1, T, d2 or 1), bf))


def arrays(a, seed=0):
    """Seeded inputs; the selection keeps `topk` random causal keys a query
    (all of them where there are no more), the diagonal among them. The
    kernels' time does not depend on it: every tile under the diagonal is
    computed."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    sh = shapes(a)
    q, k, v, do, q2, k2 = (
        jax.random.normal(kk, s.shape, s.dtype)
        for kk, s in zip(ks, (sh[0], sh[1], sh[2], sh[6], sh[7], sh[8])))
    if a.schedule != "mask":
        return q, k, v, jnp.ones(sh[3].shape, jnp.int8), do, q2, k2
    t = jnp.arange(a.T)
    u = jax.random.uniform(ks[4], (a.T, a.T))
    keep = (t[None, :] <= t[:, None]) & (
        (u * (t[:, None] + 1) < TOPK) | (t[None, :] == t[:, None]))
    return q, k, v, keep.astype(jnp.int8)[None], do, q2, k2


def device_ms(fn, operands, whole_call=False):
    """The kernel's own time on the device's operation line, a call: the
    median of REPS traced calls' `sparse_attention_*` events (the host's
    clock above adds the dispatch and, for dQ, the row sums' fusion). With
    `whole_call`, every operation of a call added up (a function XLA
    compiles to fusions of its own, or one that calls a kernel 16 times),
    and beside it the kernels' share of that."""
    import tempfile
    from deeplearning4j_tpu.optimize.profiler import _device_ops, trace
    with tempfile.TemporaryDirectory() as logdir:
        with trace(logdir):
            for _ in range(REPS):
                out = fn(*operands)
            jax.block_until_ready(out)
        ops = _device_ops(logdir)
    ms = [t for name, t in ops if "sparse_attention" in name]
    if whole_call:
        return {"device_ms": sum(t for _, t in ops) / REPS,
                "kernels_device_ms": sum(ms) / REPS}
    return {"device_ms": statistics.median(ms) if ms else None}


def attention_operands(mod, a):
    q, k, v, mask, do, q2, k2 = arrays(a)
    o, lse = jax.jit(lambda q, k, v, mask, q2, k2: (
        mod.masked_attention(q, k, v, mask, scale_of(a))
        if a.schedule == "mask" else
        mod.shared_key_attention(q, k, v, q2, k2, scale_of(a))
        if a.schedule == "latent" else
        mod.masked_attention(q, k, v, None, scale_of(a), window=a.window)))(
            q, k, v, mask, q2, k2)
    return q, k, v, mask, o, lse, do, q2, k2


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--module", default=None)
    p.add_argument("--blocks", default=None)
    p.add_argument("--kernels", default=None)
    p.add_argument("--schedule", choices=sorted(SHAPES), default="mask")
    p.add_argument("--T", type=int, default=None)
    p.add_argument("--heads", type=int, default=None)
    p.add_argument("--kv", type=int, default=None)
    p.add_argument("--window", type=int, default=None)
    p.add_argument("--compile-only", action="store_true")
    a = p.parse_args()
    for name, default in zip(("T", "heads", "kv", "window"),
                             SHAPES[a.schedule]):
        if getattr(a, name) is None:
            setattr(a, name, default)
    indexer = a.schedule == "indexer"
    a.blocks = a.blocks or ("x512,x1024" if indexer else
                            "512x512,1024x512,512x1024,1024x1024")
    a.kernels = a.kernels or ("index_fwd,index_vjp_xla,index_bwd" if indexer
                              else "fwd,bwd")
    mod = load(a.module)
    every = indexer_kernels if indexer else kernels
    say = lambda **kw: print(json.dumps(
        {"module": a.module or "this tree", "schedule": a.schedule,
         "T": a.T, "heads": a.heads, "window": a.window, **kw}), flush=True)

    if a.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        sh = (indexer_shapes if indexer else shapes)(
            a, SingleDeviceSharding(topo.devices[0]))
        for name, bq, bk, fn in every(mod, a):
            try:
                fn.lower(*sh).compile()
                say(kernel=name, bq=bq, bk=bk, compiled=True)
            except Exception as e:                  # the compiler's refusal
                say(kernel=name, bq=bq, bk=bk, compiled=False,
                    error=str(e).splitlines()[0][:300])
        return 0

    if jax.default_backend() != "tpu":
        print(f"found platform {jax.default_backend()!r}, not a TPU; "
              "refusing to measure", file=sys.stderr)
        return 4
    operands = indexer_arrays(a) if indexer else attention_operands(mod, a)
    for name, bq, bk, fn in every(mod, a):
        try:
            jax.block_until_ready(fn(*operands))
        except Exception as e:
            say(kernel=name, bq=bq, bk=bk, error=str(e).splitlines()[0][:300])
            continue
        ms = []
        for _ in range(SETS):
            t0 = time.perf_counter()
            for _ in range(REPS):
                out = fn(*operands)
            jax.block_until_ready(out)
            ms.append((time.perf_counter() - t0) / REPS * 1e3)
        say(kernel=name, bq=bq, bk=bk, ms=statistics.median(ms),
            ms_min=min(ms), ms_max=max(ms),
            **device_ms(fn, operands, whole_call=indexer or name == "bwd"),
            device=jax.devices()[0].device_kind)
    return 0


if __name__ == "__main__":
    sys.exit(main())
