"""Batched KV-cache text generation: the WHOLE generation (prompt prefill
scan + greedy decode scan with on-device argmax) is one jitted program, so
the host touches the device once per call — the TPU serving pattern (naive
decoding pays a host round trip per token).

reference parity: MultiLayerNetwork.rnnTimeStep (O(1)-state streaming
inference), attention era.
"""
import _common  # noqa: F401

import numpy as np

from deeplearning4j_tpu.models.zoo.transformer import TransformerLM

V = 11
lm = TransformerLM(V, d_model=32, n_heads=4, n_layers=2, max_len=32,
                   learning_rate=0.2, momentum=0.9)

# teach the toy task: next token = current + 1 (mod V)
rng = np.random.default_rng(0)
x = rng.integers(0, V, (16, 16)).astype(np.int32)
for _ in range(120):
    loss = lm.fit_batch(x, (x + 1) % V)

prompts = np.array([[2, 3, 4], [7, 8, 9], [0, 1, 2], [5, 6, 7]], np.int32)
out = lm.generate_batch(prompts, max_new_tokens=6)
print("prompts:", prompts.tolist())
print("continuations:", out[:, 3:].tolist())

# greedy outputs are token-identical to the per-token cache decode
row0 = lm.generate(prompts[0], max_new_tokens=6, use_cache=True)
print("batch row 0 == per-token decode:", list(out[0]) == row0)
print(list(out[0]) == row0 and float(loss) < 1.0)
