"""Interleaved ResNet-50 (batch x remat) sweep — the round-5 MFU push.

The r5 on-chip A/B showed remat LOSES 16% at batch 128 (2,209 vs 2,633
img/s): with HBM headroom to spare, segment recompute is pure added FLOPs.
But remat's actual purpose is shrinking the activation working set so a
LARGER batch fits behind the bandwidth wall — the r3 sweep showed plain
batch 256 regressing (~2,535) from spill. This measures whether
remat@256/384 beats the plain batch-128 champion, interleaved so drift
over the run can't bias an arm.

One JSON line per (batch, remat) arm + a final "winner" line.
Usage: python tools/remat_batch_sweep.py [--budget SECONDS]
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(budget_s=900.0):
    t0 = time.perf_counter()
    from deeplearning4j_tpu.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    import numpy as np

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.models.zoo.resnet import resnet50

    print(json.dumps({"sweep": "remat_batch",
                      "platform": jax.devices()[0].platform}), flush=True)
    rng = np.random.default_rng(0)

    ARMS = [(128, False), (256, False), (256, True), (384, True)]
    nets, data = {}, {}
    for batch, remat in ARMS:
        net = resnet50(data_type="bfloat16", remat=remat)
        x = rng.random((batch, 224, 224, 3)).astype(np.float32)
        y = np.eye(1000, dtype=np.float32)[rng.integers(0, 1000, batch)]
        ds = DataSet(jax.device_put(x), jax.device_put(y))
        try:
            net.fit(ds)            # compile (cache-shared across arms)
            float(net._score)
        except Exception as e:     # noqa: BLE001 — e.g. OOM at 384
            print(json.dumps({"batch": batch, "remat": remat,
                              "error": repr(e)[:200]}), flush=True)
            continue
        nets[(batch, remat)] = net
        data[(batch, remat)] = ds

    best = {}
    for seg in range(3):           # interleaved best-of-3 segments
        for key, net in nets.items():
            if time.perf_counter() - t0 > budget_s:
                break
            batch, remat = key
            iters = max(4, 1536 // batch)
            ds = data[key]
            net.fit(ds)            # warm after the previous arm's eviction
            float(net._score)
            t = time.perf_counter()
            for _ in range(iters):
                net.fit(ds)
            float(net._score)
            ips = batch * iters / (time.perf_counter() - t)
            best[key] = max(best.get(key, 0.0), ips)
            print(json.dumps({"batch": batch, "remat": remat, "seg": seg,
                              "images_per_sec": round(ips, 1)}), flush=True)
    if best:
        (batch, remat), ips = max(best.items(), key=lambda kv: kv[1])
        print(json.dumps({"winner": {"batch": batch, "remat": remat,
                                     "images_per_sec": round(ips, 1)}}),
              flush=True)


if __name__ == "__main__":
    budget = 900.0
    if "--budget" in sys.argv:
        budget = float(sys.argv[sys.argv.index("--budget") + 1])
    main(budget)
