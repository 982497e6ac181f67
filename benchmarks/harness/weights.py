"""Seeded weights and inputs, made ON THE DEVICE in one jitted call each, in
the type they are used in. The benchmark makes them (never the program), so
the program and the plain reference are handed the same numbers and neither
takes anything the other has made."""
import math

import jax
import jax.numpy as jnp


def key_for(seed, stream=0):
    """Any whole-number seed (the driver's pass 2**31) to a key; `stream`
    separates weights, inputs and token ids of one seed."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, seed >> 31), stream)


def _normal_tree(key, shapes, scales, dtype):
    """shapes/scales: {layer: {leaf: shape}} / {layer: {leaf: (mean, std)}}"""
    flat = [(n, k) for n in sorted(shapes) for k in sorted(shapes[n])]
    keys = jax.random.split(key, len(flat))
    out = {n: {} for n in shapes}
    for (n, k), kk in zip(flat, keys):
        mean, std = scales[n][k]
        out[n][k] = (mean + std * jax.random.normal(
            kk, shapes[n][k], jnp.float32)).astype(dtype)
    return out


def resnet_weights(seed, shapes, sharding=None):
    """He-normal convolutions and classifier; batch-norm scales near 1 and
    shifts near 0 but not AT them, so a mishandled gamma or beta shows. The
    last scale of each residual branch (`*_c_bn`) is small, as Goyal et al.
    2017 start it (there 0, here 0.2 so that its branch still has a
    gradient): with it near 1 every block doubles the signal's variance and
    the 16 blocks amplify bf16's rounding until the gradients of program and
    reference differ by their own size (PERF.md, limits)."""
    scales = {}
    for n, leaves in shapes.items():
        scales[n] = {}
        for k, shp in leaves.items():
            if k == "W":
                fan_in = math.prod(shp[:-1])
                scales[n][k] = (0.0, math.sqrt(2.0 / fan_in))
            elif k == "gamma":
                scales[n][k] = (0.2, 0.02) if n.endswith("_c_bn") \
                    else (1.0, 0.1)
            else:                               # beta, b
                scales[n][k] = (0.0, 0.1)
    make = jax.jit(lambda key: _normal_tree(key, shapes, scales, jnp.float32),
                   out_shardings=sharding)
    return make(key_for(seed, 0))


def image_ring(seed, ring, batch, model, sharding=None):
    """`ring` batches of `batch` seeded images (bf16, the wire type of a
    bf16 model) and one-hot labels; every row differs."""
    shape = (ring, batch, model["height"], model["width"], model["channels"])

    def make(key):
        kx, ky = jax.random.split(key)
        x = jax.random.uniform(kx, shape, jnp.float32).astype(jnp.bfloat16)
        y = jax.random.randint(ky, (ring, batch), 0, model["num_classes"])
        return x, jax.nn.one_hot(y, model["num_classes"], dtype=jnp.float32)

    shardings = None if sharding is None else (sharding, sharding)
    return jax.jit(make, out_shardings=shardings)(key_for(seed, 1))


def lm_weights(seed, model, dtype=jnp.bfloat16):
    """(aux, blocks) in the layout of the zoo's TransformerLM, GPT-2's
    initial scales (0.02 embeddings, 1/sqrt(fan_in) matrices)."""
    d, ff, v = model["n_embd"], model["n_inner"], model["vocab_size"]
    n_layer, n_pos = model["n_layer"], model["n_positions"]

    def make(key):
        ka, kb = jax.random.split(key)
        k = jax.random.split(ka, 3)
        nrm = lambda kk, shp, s: (s * jax.random.normal(
            kk, shp, jnp.float32)).astype(dtype)
        ln = lambda kk: {"g": (1.0 + 0.1 * jax.random.normal(
            kk, (d,), jnp.float32)).astype(dtype),
            "b": (0.1 * jax.random.normal(
                jax.random.fold_in(kk, 1), (d,), jnp.float32)).astype(dtype)}
        aux = {"tok": nrm(k[0], (v, d), 0.02), "pos": nrm(k[1], (n_pos, d),
                                                          0.02),
               "lnf": ln(jax.random.fold_in(ka, 7)),
               "head": nrm(k[2], (d, v), 1 / math.sqrt(d))}
        blocks = []
        for kl in jax.random.split(kb, n_layer):
            q = jax.random.split(kl, 8)
            blocks.append({
                "ln1": ln(q[0]),
                "attn": {"wqkv": nrm(q[1], (d, 3 * d), 1 / math.sqrt(d)),
                         "wo": nrm(q[2], (d, d), 1 / math.sqrt(d))},
                "ln2": ln(q[3]),
                "mlp": {"w1": nrm(q[4], (d, ff), 1 / math.sqrt(d)),
                        "b1": nrm(q[5], (ff,), 0.02),
                        "w2": nrm(q[6], (ff, d), 1 / math.sqrt(ff)),
                        "b2": nrm(q[7], (d,), 0.02)}})
        return aux, blocks

    return jax.jit(make)(key_for(seed, 0))
