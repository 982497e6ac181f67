"""Sharding rules: map network parameters / batches onto a TPU device mesh.

TPU-native replacement for the reference's distribution machinery: instead of
model replicas on threads (ParallelWrapper.java:44) or Spark executors
(ParameterAveragingTrainingMaster.java:75), ONE jitted program is partitioned
over a `jax.sharding.Mesh` and XLA GSPMD inserts the ICI collectives
(SURVEY.md §5.8 north star).

Mesh axes:
- "data"  — data parallelism (batch axis sharded; gradient psum over ICI)
- "model" — tensor parallelism (large weight matrices column-sharded; the
  reference has NO model parallelism — SURVEY.md §2.5 — this is a TPU-first
  extension that the mislabeled README.md:33 "model parallelism" claim never
  delivered)

Per-layer-type tensor-parallel rules live here so containers stay agnostic.
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(n_data=None, n_model=1, devices=None):
    """Build a ("data", "model") mesh. Defaults to all devices on the data
    axis."""
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    if n_data is None:
        n_data = n // n_model
    if n_data * n_model != n:
        raise ValueError(f"mesh {n_data}x{n_model} != {n} devices")
    dev_array = np.asarray(devices).reshape(n_data, n_model)
    return Mesh(dev_array, ("data", "model"))


def batch_spec():
    return P("data")


def param_specs_for_layer(layer, tensor_parallel=False):
    """PartitionSpec per parameter of `layer`.

    Replicated by default; with tensor_parallel, output-feature axes of the
    big matmul weights shard over "model" (Megatron-style column parallel for
    dense/conv/embedding, gate-concatenated axis for LSTM).
    """
    lt = getattr(layer, "layer_type", "")
    specs = {}
    params = getattr(layer, "init_params", None)
    # derive from known layouts rather than materializing params
    if not tensor_parallel:
        return None  # means: replicate everything
    if lt in ("dense", "output", "autoencoder"):
        specs["W"] = P(None, "model")
        specs["b"] = P("model")
        if lt == "autoencoder":
            specs["vb"] = P()
    elif lt == "embedding":
        specs["W"] = P(None, "model")
        specs["b"] = P("model")
    elif lt == "convolution":
        specs["W"] = P(None, None, None, "model")   # HWIO: out-channel shard
        specs["b"] = P("model")
    elif lt in ("graveslstm", "simplernn"):
        # 4H gate axis sharding interacts with peepholes/split; replicate for
        # now (LSTM tensor parallel lands with a pallas kernel)
        return None
    else:
        return None
    return specs


def _layer_sharding(layer, p, mesh, tensor_parallel):
    specs = param_specs_for_layer(layer, tensor_parallel)
    d = {}
    for k, v in p.items():
        spec = specs.get(k, P()) if specs else P()
        # only shard axes that divide evenly; otherwise replicate
        for dim, axis in enumerate(spec):
            if axis is None:
                continue
            if v.shape[dim] % mesh.shape[axis] != 0:
                spec = P()
                break
        d[k] = NamedSharding(mesh, spec)
    return d


def shard_params(net, mesh, tensor_parallel=False):
    """Return (sharded_params, param_shardings) for a container's per-layer
    param pytree — list-shaped for MultiLayerNetwork, name-keyed dict for
    ComputationGraph."""
    items = net._layer_items()
    shardings = net._per_layer(
        _layer_sharding(layer, net._params[key], mesh, tensor_parallel)
        for key, layer in items)
    sharded = net._per_layer(
        {k: put_sharded(v, shardings[key][k], full_array=True)
         for k, v in net._params[key].items()}
        for key, _ in items)
    return sharded, shardings


def zero_state_sharding(ustate, mesh, axis="data"):
    """ZeRO-1-style shardings for the optimizer-state pytree: each leaf is
    sharded over the `axis` mesh axis on its first evenly-dividing dimension
    (replicated when none divides). Params stay replicated; only the
    updater state (momentum/Adam moments — the largest persistent tensors
    after params) is partitioned, so each device stores 1/N of it and XLA
    GSPMD shards the optimizer update compute the same way.

    The reference has no equivalent (updater state is replicated and
    averaged, ParallelWrapper.java:200-212); this is a TPU-first extension
    in the spirit of ZeRO stage 1 (SURVEY.md §2.5 "hybrid sharded
    optimizer: optional")."""
    n = mesh.shape[axis]

    def leaf_sharding(a):
        for dim, size in enumerate(a.shape):
            if size % n == 0 and size >= n:
                spec = [None] * a.ndim
                spec[dim] = axis
                return NamedSharding(mesh, P(*spec))
        return NamedSharding(mesh, P())

    return jax.tree.map(leaf_sharding, ustate)


def is_multiprocess_mesh(mesh):
    return len({d.process_index for d in mesh.devices.flat}) > 1


def put_sharded(arr, sharding, full_array=False):
    """Place an array under `sharding`, working on single-host AND
    multi-host meshes. Multi-host (jax.distributed) device_put cannot
    address other hosts' devices, so each process contributes data itself:

    - full_array=False (batches): `arr` is this process's LOCAL slice —
      make_array_from_process_local_data assembles the global array.
    - full_array=True (parameters): every process holds the FULL array —
      make_array_from_callback hands each addressable shard its global
      slice. (Passing a full array through the local-data path would
      mis-scale the global shape when a sharded axis spans processes.)

    This is the DCN-path seam: the same ParallelWrapper program runs on a
    global mesh spanning hosts (SURVEY.md §5.8)."""
    if arr is None:
        return None
    if is_multiprocess_mesh(sharding.mesh):
        a = np.asarray(arr)
        if full_array:
            return jax.make_array_from_callback(
                a.shape, sharding, lambda idx: a[idx])
        return jax.make_array_from_process_local_data(sharding, a)
    return jax.device_put(arr, sharding)


def replicate(tree, mesh):
    sh = NamedSharding(mesh, P())
    if is_multiprocess_mesh(mesh):
        return jax.tree.map(lambda a: put_sharded(a, sh, full_array=True),
                            tree)
    return jax.device_put(tree, sh)
