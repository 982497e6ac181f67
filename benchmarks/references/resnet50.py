"""Plain reference for ResNet-50 v1 training (He et al. 2015): forward, loss,
gradients and the Nesterov update in straightforward jax.numpy, float32 with
matmuls at "highest" precision. Imports nothing of the program. Departures
from the paper, matching what the configuration file states: no conv bias
(batch norm's beta subsumes it), SAME padding, batch statistics with
eps 1e-5, mean softmax cross-entropy, ND4J's Nesterov form.

Each bottleneck is rematerialised (jax.checkpoint) so that batch 128 in
float32 fits one chip; that changes memory, not results.

`quant` is the control: the same steps with every convolution and the
classifier computed as an fp8 trainer computes them (Micikevicius et al.
2022): inputs and weights rounded to e4m3 in the forward product, the
incoming gradient rounded to e5m2 in both backward products, per-tensor
scales, float32 accumulation. fp8 is the nearest precision below the
bfloat16 the configuration states.
"""
import jax
import jax.numpy as jnp
from jax import lax

EPS = 1e-5


def param_shapes(model):
    """{layer: {leaf: shape}}, named as the zoo names its layers."""
    shapes = {}

    def conv_bn(name, kh, kw, ci, co):
        shapes[name + "_conv"] = {"W": (kh, kw, ci, co)}
        shapes[name + "_bn"] = {"gamma": (co,), "beta": (co,)}

    conv_bn("stem", 7, 7, model["channels"], 64)
    c_in = 64
    for si, (blocks, width) in enumerate(model["stages"]):
        c_out = width * model["expansion"]
        for bi in range(blocks):
            n = f"s{si + 2}b{bi}"
            conv_bn(n + "_a", 1, 1, c_in, width)
            conv_bn(n + "_b", 3, 3, width, width)
            conv_bn(n + "_c", 1, 1, width, c_out)
            if bi == 0:
                conv_bn(n + "_sc", 1, 1, c_in, c_out)
            c_in = c_out
    shapes["fc"] = {"W": (c_in, model["num_classes"]),
                    "b": (model["num_classes"],)}
    return shapes


def fp8(a, dtype=jnp.float8_e4m3fn):
    """Per-tensor-scaled rounding to an fp8 type and back."""
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / float(jnp.finfo(dtype).max)
    return (a / s).astype(dtype).astype(a.dtype) * s


def in_fp8(op):
    """`op(x, w)` as an fp8 trainer runs it: e4m3 operands forward, the
    gradient in e5m2 against those same operands backward."""
    @jax.custom_vjp
    def f(x, w):
        return op(fp8(x), fp8(w))

    def fwd(x, w):
        qx, qw = fp8(x), fp8(w)
        return op(qx, qw), (qx, qw)

    def bwd(res, dy):
        return jax.vjp(op, *res)[1](fp8(dy, jnp.float8_e5m2))
    f.defvjp(fwd, bwd)
    return f


def _conv_bn(p, name, x, stride, relu, q):
    conv = lambda a, w: lax.conv_general_dilated(
        a, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST)
    y = q(conv)(x, p[name + "_conv"]["W"])
    mean = jnp.mean(y, (0, 1, 2))
    var = jnp.mean(jnp.square(y - mean), (0, 1, 2))
    y = (y - mean) * lax.rsqrt(var + EPS) * p[name + "_bn"]["gamma"] \
        + p[name + "_bn"]["beta"]
    return jax.nn.relu(y) if relu else y


def _bottleneck(p, name, x, stride, project, q):
    y = _conv_bn(p, name + "_a", x, stride, True, q)
    y = _conv_bn(p, name + "_b", y, 1, True, q)
    y = _conv_bn(p, name + "_c", y, 1, False, q)
    sc = _conv_bn(p, name + "_sc", x, stride, False, q) if project else x
    return jax.nn.relu(y + sc)


def logits(params, x, model, quant=False):
    q = in_fp8 if quant else (lambda op: op)
    x = x.astype(jnp.float32)
    x = _conv_bn(params, "stem", x, 2, True, q)
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          "SAME")
    for si, (blocks, _) in enumerate(model["stages"]):
        for bi in range(blocks):
            name = f"s{si + 2}b{bi}"
            stride = 2 if (si > 0 and bi == 0) else 1
            sub = {k: v for k, v in params.items() if k.startswith(name)}
            x = jax.checkpoint(
                lambda pp, xx, name=name, stride=stride, bi=bi:
                _bottleneck(pp, name, xx, stride, bi == 0, q))(sub, x)
    x = jnp.mean(x, (1, 2))
    dot = lambda a, w: jnp.dot(a, w, precision=lax.Precision.HIGHEST)
    return q(dot)(x, params["fc"]["W"]) + params["fc"]["b"]


def loss(params, x, y, model, quant=False):
    logp = jax.nn.log_softmax(logits(params, x, model, quant), -1)
    return jnp.mean(-jnp.sum(y * logp, -1))


def leaf_norms(tree):
    """Euclidean norm of every leaf, in the fixed order of sorted names."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
        tree[n][k].astype(jnp.float32))))
        for n in sorted(tree) for k in sorted(tree[n])])


def train_steps(params, xs, ys, model, trainer, quant=False, rows=None):
    """Follow the first len(xs) steps from `params`. Returns each step's loss
    (before its update), the per-leaf norms of the first gradient and of the
    parameters' change after the last step. `rows` plants the fault of a
    step that leaves part of its batch out (mean over the first `rows`)."""
    lr, mu = trainer["learning_rate"], trainer["momentum"]

    @jax.jit
    def step(p, v, x, y):
        if rows is not None:
            x, y = x[:rows], y[:rows]
        l, g = jax.value_and_grad(loss)(p, x, y, model, quant)
        # ND4J Nesterovs: v' = mu v - lr g; p -= mu v - (1 + mu) v'
        v_new = jax.tree.map(lambda a, b: mu * a - lr * b, v, g)
        p = jax.tree.map(lambda a, b, c: a - (mu * b - (1.0 + mu) * c),
                         p, v, v_new)
        return p, v_new, l, leaf_norms(g)

    p, v = params, jax.tree.map(jnp.zeros_like, params)
    losses, g1 = [], None
    for x, y in zip(xs, ys):
        p, v, l, g = step(p, v, x, y)
        losses.append(l)
        g1 = g if g1 is None else g1
    change = jax.jit(lambda a, b: leaf_norms(
        jax.tree.map(lambda c, d: c - d, a, b)))(p, params)
    return jnp.stack(losses), g1, change
