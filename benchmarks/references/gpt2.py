"""Plain reference for GPT-2 (Radford et al. 2019; openai-community/gpt2-xl
config.json): the forward pass in straightforward jax.numpy, float32 with
matmuls at "highest" precision, dense causal attention, no cache, no
batching, one layer at a time so that it fits beside nothing. Imports nothing
of the program; it is handed the weights the benchmark made.

Departures from the published model, as the configuration file states them:
no biases on the attention projections, an untied output head, tanh-GELU.

`quant` is the control: every matmul's inputs and weights rounded to fp8
(e4m3, per-tensor scale), the nearest precision below the bfloat16 the
configuration states.
"""
import functools
import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def fp8(a):
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
    return (a / s).astype(jnp.float8_e4m3fn).astype(a.dtype) * s


def _ln(x, p, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["g"] + p["b"]


def _mm(a, b, q):
    return jnp.matmul(q(a), q(b), precision=HI)


@functools.partial(jax.jit, static_argnames=("n_heads", "quant"))
def block(p, x, n_heads, quant=False):
    """One transformer block on [T, D]."""
    q = fp8 if quant else (lambda a: a)
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    T, D = x.shape
    hd = D // n_heads
    h = _ln(x, p["ln1"])
    qkv = _mm(h, p["attn"]["wqkv"], q)
    qh, kh, vh = (a.reshape(T, n_heads, hd).transpose(1, 0, 2)
                  for a in jnp.split(qkv, 3, axis=-1))
    s = jnp.matmul(q(qh), q(kh).transpose(0, 2, 1), precision=HI) \
        / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    att = jnp.matmul(q(jax.nn.softmax(s, -1)), q(vh), precision=HI)
    x = x + _mm(att.transpose(1, 0, 2).reshape(T, D), p["attn"]["wo"], q)
    h = _ln(x, p["ln2"])
    m = jax.nn.gelu(_mm(h, p["mlp"]["w1"], q) + p["mlp"]["b1"])
    return x + _mm(m, p["mlp"]["w2"], q) + p["mlp"]["b2"]


@functools.partial(jax.jit, static_argnames=("quant",))
def _embed(aux, tokens, quant=False):
    T = tokens.shape[0]
    return (aux["tok"][tokens].astype(jnp.float32)
            + aux["pos"][:T].astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("quant",))
def _head(aux, x, quant=False):
    q = fp8 if quant else (lambda a: a)
    lnf = jax.tree.map(lambda a: a.astype(jnp.float32), aux["lnf"])
    return _mm(_ln(x, lnf), aux["head"].astype(jnp.float32), q)


def logits(aux, blocks, tokens, n_heads, quant=False):
    """[T] token ids -> [T, vocab] float32 logits."""
    x = _embed(aux, tokens)
    for p in blocks:
        x = block(p, x, n_heads=n_heads, quant=quant)
    return _head(aux, x, quant=quant)


@jax.jit
def served_gaps(ref_logits, nxt, valid):
    """At each position, how far the logit of the token that was served
    next (`nxt`) lies below the reference's best; 0 where not `valid`."""
    best = jnp.max(ref_logits, -1)
    took = jnp.take_along_axis(ref_logits, nxt[:, None], -1)[:, 0]
    return jnp.where(valid, best - took, 0.0)
