"""On-chip perf sweep for the round-4/5 levers (one process: it takes the
chip; run it through the chip tool).

Interleaved A/B measurements that bench.py's fixed budget doesn't cover:

  1. TRAINING tok/s: flash (fused Pallas backward) vs dense attention in
     the zoo TransformerLM at T = 2048 / 4096 / 8192 — the r3 record
     showed flash at 0.86x/0.71x of dense with the einsum-recompute VJP
     and dense failing outright at 8192; this measures what the fused
     backward changed.
  2. LSTM scan-unroll sweep (r5 lever): char-RNN chars/sec at
     unroll = 1 / 4 / 8 / 16 — picks the bench default for the
     BASELINE config #3 path (LSTMHelpers.java:157-171 seam).

Prints one JSON line per measurement (records are self-contained; safe
under any timeout).
Usage: python perf_sweep.py [--budget SECONDS] [--skip-flash]
(--skip-flash: run only the LSTM sweep — the attention sweep needs a real
TPU; interpret-mode Pallas on CPU is minutes per step.)
"""
from __future__ import annotations

import json
import sys
import time


def main(budget_s=900.0, skip_flash=False):
    t0 = time.perf_counter()
    from deeplearning4j_tpu.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.models.zoo.transformer import TransformerLM

    platform = jax.devices()[0].platform
    print(json.dumps({"sweep": "start", "platform": platform}), flush=True)

    B, D_MODEL, HEADS, LAYERS = 4, 512, 8, 4
    rng = np.random.default_rng(0)

    def train_tok_s(attention, T, steps=10):
        lm = TransformerLM(512, d_model=D_MODEL, n_heads=HEADS,
                           n_layers=LAYERS, max_len=T,
                           dtype=jnp.bfloat16, attention=attention)
        x = rng.integers(0, 512, (B, T)).astype(np.int32)
        y = (x + 1) % 512
        lm.fit_batch(x, y)            # compile
        lm.fit_batch(x, y)            # warm
        best = 0.0
        for _ in range(2):            # best-of-2 segments
            t = time.perf_counter()
            for _ in range(steps):
                lm.fit_batch(x, y)
            dt = time.perf_counter() - t
            best = max(best, B * T * steps / dt)
        return best

    for T in (2048, 4096, 8192):
        if skip_flash:
            break
        if time.perf_counter() - t0 > budget_s - 120:
            print(json.dumps({"skipped": f"T={T}", "reason": "budget"}),
                  flush=True)
            continue
        rec = {"metric": f"transformer train tokens/sec T={T}",
               "config": f"B={B} d={D_MODEL} H={HEADS} L={LAYERS} bf16"}
        try:
            rec["flash"] = round(train_tok_s("flash", T), 0)
        except Exception as e:  # noqa: BLE001 — keep sweeping
            rec["flash_error"] = str(e)[:200]
        try:
            rec["dense"] = round(train_tok_s("dense", T), 0)
        except Exception as e:  # noqa: BLE001 — dense dies at long T
            rec["dense_error"] = str(e)[:200]
        if "flash" in rec and "dense" in rec:
            rec["flash_vs_dense"] = round(rec["flash"] / rec["dense"], 3)
        print(json.dumps(rec), flush=True)

    # --- r5: LSTM scan-unroll sweep (char-RNN, BASELINE config #3) ------
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.models.zoo.char_rnn import char_rnn

    def lstm_chars_s(unroll, Bc=64, Tc=200, steps=10):
        net = char_rnn(data_type="bfloat16", scan_unroll=unroll)
        x = np.eye(77, dtype=np.float32)[rng.integers(0, 77, (Bc, Tc))]
        y = np.eye(77, dtype=np.float32)[rng.integers(0, 77, (Bc, Tc))]
        ds = DataSet(jax.device_put(x), jax.device_put(y))
        for _ in range(2):
            net.fit(ds)
        float(net._score)
        best = 0.0
        for _ in range(2):
            t = time.perf_counter()
            for _ in range(steps):
                net.fit(ds)
            float(net._score)
            best = max(best, Bc * Tc * steps / (time.perf_counter() - t))
        return best

    lstm_rec = {"metric": "char-RNN chars/sec by scan unroll",
                "config": "2x200 GravesLSTM B=64 T=200 tbptt 50 bf16"}
    for unroll in (1, 4, 8, 16):
        if time.perf_counter() - t0 > budget_s - 90:
            lstm_rec[f"unroll{unroll}"] = "skipped (budget)"
            continue
        try:
            lstm_rec[f"unroll{unroll}"] = round(lstm_chars_s(unroll), 0)
        except Exception as e:  # noqa: BLE001 — keep sweeping
            lstm_rec[f"unroll{unroll}_error"] = str(e)[:200]
    print(json.dumps(lstm_rec), flush=True)

    print(json.dumps({"sweep": "done",
                      "wall_s": round(time.perf_counter() - t0, 1)}),
          flush=True)


if __name__ == "__main__":
    budget = 900.0
    if "--budget" in sys.argv:
        budget = float(sys.argv[sys.argv.index("--budget") + 1])
    # --skip-flash: the attention sweep needs a real TPU (interpret-mode
    # Pallas is minutes per step); the LSTM sweep runs anywhere
    main(budget, skip_flash="--skip-flash" in sys.argv)
