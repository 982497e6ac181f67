"""Layer configuration base classes + registry.

TPU-native equivalent of the reference's per-layer config classes
(reference: nn/conf/layers/Layer.java:67 abstract conf; each conf knows
instantiate()/initializer()/getOutputType()/setNIn()).

Design divergence (deliberate, TPU-first): config and implementation are one
class. The reference splits conf (nn/conf/layers/*) from impl
(nn/layers/*) because impls hold mutable INDArray state; here layers are
stateless pure functions over explicit param pytrees, so a single class carries
hyperparameters + `init_params` + `forward`. Backprop comes from jax autodiff
(replacing every hand-written backpropGradient), and the whole network forward
+ loss + updaters compiles into ONE XLA program (see multilayer.py).

Global-then-per-layer override semantics match the reference
(NeuralNetConfiguration.Builder globals applied to layers that didn't set
their own values — NeuralNetConfiguration.java:479-517).
"""
from __future__ import annotations

import copy
from dataclasses import dataclass, field, fields

import jax
import jax.numpy as jnp

from ... import activations as _acts  # noqa: F401  (registry warm)

LAYER_REGISTRY = {}

# Fields that participate in global-default override (reference:
# NeuralNetConfiguration.Builder globals). None on a layer = inherit global.
GLOBAL_OVERRIDABLE = (
    "activation", "weight_init", "dist", "learning_rate", "bias_learning_rate",
    "bias_init", "l1", "l2", "l1_bias", "l2_bias", "dropout", "updater", "momentum",
    "rho", "rms_decay", "epsilon", "adam_mean_decay", "adam_var_decay",
    "gradient_normalization", "gradient_normalization_threshold",
    "lr_policy", "lr_policy_decay_rate", "lr_policy_steps", "lr_policy_power",
    "lr_policy_max_iterations", "lr_schedule",
)


def layer_scope(conf, name):
    """`jax.named_scope("<kind>.<name>")` for one layer or vertex of a
    container: `kind` is the registered type tag of its class
    (`convolution`, `batchnorm`, `elementwise`, ...), `name` the vertex's
    name or the layer's index. The scope is in the `op_name` metadata of
    every operation the compiled step traces inside it — forward as
    `jvp(<kind>.<name>)`, backward as `transpose(jvp(<kind>.<name>))` —
    which is how device time is attributed to layers
    (optimize/profiler.py `op_scopes`). Metadata only: same operations."""
    kind = (getattr(conf, "layer_type", None)
            or getattr(conf, "vertex_type", None)
            or type(conf).__name__.lower())
    return jax.named_scope(f"{kind}.{name}")


def register_layer(name):
    def deco(cls):
        LAYER_REGISTRY[name] = cls
        cls.layer_type = name
        return cls
    return deco


@dataclass
class LayerConf:
    """Base for all layer configs. Fields default to None = 'inherit global'."""
    name: str = None
    activation: str = None
    weight_init: str = None
    dist: dict = None
    bias_init: float = None
    learning_rate: float = None
    bias_learning_rate: float = None
    l1: float = None
    l2: float = None
    l1_bias: float = None
    l2_bias: float = None
    dropout: float = None
    updater: str = None
    momentum: float = None
    rho: float = None
    rms_decay: float = None
    epsilon: float = None
    adam_mean_decay: float = None
    adam_var_decay: float = None
    gradient_normalization: str = None
    gradient_normalization_threshold: float = None
    lr_policy: str = None
    lr_policy_decay_rate: float = None
    lr_policy_steps: float = None
    lr_policy_power: float = None
    lr_policy_max_iterations: float = None  # horizon for 'poly' decay
    lr_schedule: dict = None

    # ------------------------------------------------------------------
    # Contract each concrete layer implements
    # ------------------------------------------------------------------
    def init_params(self, key, dtype=jnp.float32):
        """Return the param dict for this layer ({} for parameterless)."""
        return {}

    def forward(self, params, x, *, train=False, rng=None, mask=None, state=None):
        """Pure forward. Returns output (post-activation).

        Layers with inference-time statistics (BatchNorm) additionally accept /
        return `state` via forward_with_state.
        """
        raise NotImplementedError

    def get_output_type(self, input_type):
        raise NotImplementedError

    def set_n_in(self, input_type, override=True):
        """Infer nIn from the previous layer's output type (reference
        Layer.setNIn)."""
        return

    def has_state(self):
        """True if the layer carries non-trainable state (e.g. BN running stats)."""
        return False

    def init_state(self):
        return {}

    # A layer whose training loss depends on its ACTIVATIONS (not only on
    # its params, as `reg_score` does) sets this and returns that loss in
    # its state's "layer_loss" entry; ComputationGraph adds it to the score.
    has_layer_loss = False

    def gauges(self, state):
        """{name: scalar} worth publishing from the state the last step
        left (ComputationGraph.publish_layer_gauges)."""
        return {}

    def remat_keeps(self):
        """Checkpoint names (`jax.ad_checkpoint.checkpoint_name`) of arrays
        this layer's forward names and a rematerialised segment holding it
        keeps for the backward; all else is computed again
        (ComputationGraph._remat_plan). A property of the kind."""
        return ()

    # ------------------------------------------------------------------
    # Regularization score contribution (reference BaseLayer.calcL1/calcL2)
    # ------------------------------------------------------------------
    def reg_score(self, params):
        total = 0.0
        l1 = self.l1 or 0.0
        l2 = self.l2 or 0.0
        l1b = self.l1_bias if self.l1_bias is not None else 0.0
        l2b = self.l2_bias if self.l2_bias is not None else 0.0
        for k, v in params.items():
            is_bias = k in ("b", "beta")
            a1, a2 = (l1b, l2b) if is_bias else (l1, l2)
            if a1:
                total = total + a1 * jnp.sum(jnp.abs(v))
            if a2:
                total = total + 0.5 * a2 * jnp.sum(v * v)
        return total

    # ------------------------------------------------------------------
    # Serde
    # ------------------------------------------------------------------
    def to_dict(self):
        d = {"type": self.layer_type}
        for f in fields(self):
            v = getattr(self, f.name)
            if v is None:
                continue
            if isinstance(v, tuple):
                v = list(v)
            d[f.name] = v
        return d

    @classmethod
    def from_dict(cls, d):
        d = dict(d)
        typ = d.pop("type")
        if typ not in LAYER_REGISTRY:
            raise ValueError(f"Unknown layer type '{typ}'. "
                             f"Known: {sorted(LAYER_REGISTRY)}")
        klass = LAYER_REGISTRY[typ]
        valid = {f.name for f in fields(klass)}
        kwargs = {}
        for k, v in d.items():
            if k in valid:
                if isinstance(v, list):
                    v = tuple(v)
                kwargs[k] = v
        return klass(**kwargs)

    def apply_global_defaults(self, g):
        """Fill None fields from the global builder config `g` (a dict)."""
        out = copy.deepcopy(self)
        for fname in GLOBAL_OVERRIDABLE:
            if getattr(out, fname, None) is None and g.get(fname) is not None:
                setattr(out, fname, g[fname])
        if out.activation is None:
            out.activation = "sigmoid"       # reference default
        if out.weight_init is None:
            out.weight_init = "xavier"       # reference default
        if out.learning_rate is None:
            out.learning_rate = 0.1          # reference default
        if out.updater is None:
            out.updater = "sgd"              # reference default
        if out.bias_init is None:
            out.bias_init = 0.0
        if out.lr_policy is None:
            out.lr_policy = "none"
        return out

    # Updater hyperparameter dict consumed by updaters.apply
    def updater_hp(self):
        hp = {}
        if self.momentum is not None:
            hp["momentum"] = self.momentum
        if self.rho is not None:
            hp["rho"] = self.rho
        if self.rms_decay is not None:
            hp["rmsDecay"] = self.rms_decay
        if self.epsilon is not None:
            hp["epsilon"] = self.epsilon
        if self.adam_mean_decay is not None:
            hp["adamMeanDecay"] = self.adam_mean_decay
        if self.adam_var_decay is not None:
            hp["adamVarDecay"] = self.adam_var_decay
        return hp


def apply_input_dropout(conf: LayerConf, x, train, rng):
    """Inverted dropout on the layer *input*, matching the reference
    (util/Dropout.java applied in BaseLayer.preOutput when training).

    NOTE DL4J semantics: the dropout value is the probability of RETAINING an
    activation (ND4J DropOutInverted), not of dropping it.
    """
    import jax
    p = conf.dropout or 0.0
    if not train or p <= 0.0 or p >= 1.0 or rng is None:
        return x
    keep = p
    m = jax.random.bernoulli(rng, keep, x.shape)
    return jnp.where(m, x / keep, 0.0)
