"""deeplearning4j_tpu — a TPU-native deep learning framework.

A from-scratch JAX/XLA/Pallas re-design with the capabilities of
deeplearning4j (reference: OkSerIous/deeplearning4j @ 0.6.1-SNAPSHOT):
layer-based networks (MultiLayerNetwork), DAG networks (ComputationGraph),
configuration DSL with JSON round-trip, data-parallel + sharded training over
TPU meshes, embedding models (Word2Vec family), Keras import, evaluation,
early stopping, checkpointing, and a training UI.

Execution model: whole training steps compile to single XLA programs
(forward + autodiff backward + optimizer, buffers donated); multi-chip
scaling uses jax.sharding.Mesh + XLA collectives over ICI rather than the
reference's parameter-averaging threads / Spark / Aeron parameter server.
"""

__version__ = "0.1.0"

import jax.monitoring
import jax.profiler

from . import obs
from .nn.conf.computation_graph_configuration import \
    ComputationGraphConfiguration
from .nn.conf.input_type import InputType
from .nn.conf.neural_net_configuration import (MultiLayerConfiguration,
                                               NeuralNetConfiguration)
from .nn.graph import ComputationGraph
from .nn.multilayer import MultiLayerNetwork

# Every span of the process-wide tracer is a profiler annotation too (inert
# while no profiler session runs), so the containers' and the servers' host
# spans land on the device trace's clock. Installed here, the one module
# they all import, because obs/ itself imports no jax.
obs.TRACER.annotate_with(jax.profiler.TraceAnnotation)

# Every trace, lowering and backend compile of the process, and every hit
# or miss of the persistent cache, into obs/compiles.py's counters and
# spans: jax publishes them, obs/ counts them (always on, no switch).
jax.monitoring.register_scalar_listener(obs.compiles.on_scalar)
jax.monitoring.register_event_time_span_listener(obs.compiles.on_time_span)
jax.monitoring.register_event_duration_secs_listener(obs.compiles.on_duration)
jax.monitoring.register_event_listener(obs.compiles.on_event)

__all__ = [
    "ComputationGraph",
    "ComputationGraphConfiguration",
    "InputType",
    "MultiLayerConfiguration",
    "NeuralNetConfiguration",
    "MultiLayerNetwork",
]
