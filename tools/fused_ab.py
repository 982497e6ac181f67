"""Fused multi-step dispatch A/B on the CPU backend (no chip needed).

The fused fit loop (net.fused_steps(K), nn/fused.py) exists to amortize
HOST DISPATCH — one jitted-call round-trip per K optimizer steps instead
of per step. On the CPU backend small-model steps are host-overhead-
dominated, so the win is measurable without the chip; this microbench
drives the REAL fit loops (fit(DataSetIterator) / fit(DataSet) TBPTT)
through the interleaved same-process A/B protocol (bench.py
_interleaved_median: alternating short segments, median per arm) and
prints one JSON line per config:

  * mlp_b64        — dispatch-DOMINATED (sub-ms step): where fusing wins
  * lenet_b64_bf16 — compute-dominated on CPU (bf16 conv emulation):
                     where fusing LOSES on this backend, because XLA:CPU
                     runs while-loop bodies single-threaded — a CPU
                     artifact, not a dispatch-model cost (the TPU scan
                     body uses the same hardware as the standalone step)
  * char_rnn_small — 4 fused TBPTT segments per dispatch

Run:  JAX_PLATFORMS=cpu python tools/fused_ab.py [--segments N]
Numbers recorded in PERF.md ("fused multi-step dispatch").
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

K = 8

# the ONE protocol implementation (bench.py is import-safe: no jax at
# import time, __main__ guarded) — a drift between the bench's A/B and
# this microbench would make the PERF.md numbers incomparable
from bench import _interleaved_median as _interleaved  # noqa: E402


def _mlp(seed=7):
    from deeplearning4j_tpu import (InputType, MultiLayerNetwork,
                                    NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    conf = (NeuralNetConfiguration.Builder().seed(seed)
            .updater("adam").learning_rate(0.01).list()
            .layer(0, DenseLayer(n_out=64, activation="relu"))
            .layer(1, OutputLayer(n_out=10, activation="softmax",
                                  loss_function="mcxent"))
            .set_input_type(InputType.feed_forward(32))
            .build())
    return MultiLayerNetwork(conf).init()


def bench_fit_iterator(make_net, x, y, n_batches, iters, segments):
    """A/B the iterator-driven fit loop: fused1 vs fused8 over the same
    staged batches, alternating segments, steps/sec medians."""
    import jax

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    ds = DataSet(jax.device_put(x), jax.device_put(y))
    nets = {"fused1": make_net(), "fused8": make_net().fused_steps(K)}

    def seg(net):
        def run():
            t0 = time.perf_counter()
            for _ in range(iters):
                net.fit(ListDataSetIterator([ds] * n_batches))
            float(net._score)
            return n_batches * iters / (time.perf_counter() - t0)
        return run

    for net in nets.values():      # compile + warm staging off the clock
        seg(net)()
    return _interleaved({n: seg(net) for n, net in nets.items()}, segments)


def config_mlp(segments):
    import numpy as np
    r = np.random.default_rng(0)
    x = r.random((64, 32)).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[r.integers(0, 10, 64)]
    ab = bench_fit_iterator(_mlp, x, y, n_batches=2 * K, iters=8,
                            segments=segments)
    return {"config": "mlp_b64 (32-64-10 f32, dispatch-dominated)",
            "unit": "steps/sec", **_verdict(ab)}


def config_lenet(segments):
    import numpy as np

    from deeplearning4j_tpu.models.zoo.lenet import lenet
    r = np.random.default_rng(0)
    x = r.random((64, 784)).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[r.integers(0, 10, 64)]
    ab = bench_fit_iterator(lambda: lenet(data_type="bfloat16"), x, y,
                            n_batches=K, iters=1, segments=segments)
    return {"config": "lenet_b64_bf16 (compute-dominated on CPU)",
            "unit": "steps/sec", **_verdict(ab)}


def config_char_rnn(segments):
    import jax
    import numpy as np

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.models.zoo.char_rnn import char_rnn
    r = np.random.default_rng(0)
    V, B, T = 77, 8, 200           # tbptt 50 -> 4 segments per fit
    x = np.eye(V, dtype=np.float32)[r.integers(0, V, (B, T))]
    y = np.eye(V, dtype=np.float32)[r.integers(0, V, (B, T))]
    ds = DataSet(jax.device_put(x), jax.device_put(y))
    nets = {"fused1": char_rnn(data_type="bfloat16"),
            "fused8": char_rnn(data_type="bfloat16").fused_steps(K)}

    def seg(net):
        def run():
            t0 = time.perf_counter()
            for _ in range(3):
                net.fit(ds)
            float(net._score)
            return 3 * 4 / (time.perf_counter() - t0)   # segments/sec
        return run

    for net in nets.values():
        net.fit(ds)
        float(net._score)
    ab = _interleaved({n: seg(net) for n, net in nets.items()}, segments)
    return {"config": "char_rnn_small (B8 T200 tbptt50, 4 fused "
                      "segments/dispatch)",
            "unit": "steps/sec", **_verdict(ab)}


def _verdict(ab):
    speedup = round(ab["fused8"]["median"]
                    / max(ab["fused1"]["median"], 1e-9), 3)
    return {"fused1": ab["fused1"], "fused8": ab["fused8"],
            "fused_speedup": speedup}


def main():
    segments = 5
    if "--segments" in sys.argv:
        segments = int(sys.argv[sys.argv.index("--segments") + 1])
    from deeplearning4j_tpu.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    print(json.dumps({"platform": jax.devices()[0].platform,
                      "fused_steps": K, "segments": segments,
                      "protocol": "interleaved same-process A/B, "
                                  "median-of-segments per arm"}),
          flush=True)
    for fn in (config_mlp, config_char_rnn, config_lenet):
        print(json.dumps(fn(segments)), flush=True)


if __name__ == "__main__":
    main()
