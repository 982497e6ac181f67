"""Size a ComputationGraph's training step in BYTES before chip time is spent.

ResNet-50's step runs at 84% of the v5e's HBM peak (PERF.md section 5): only
fewer bytes make it faster, and bytes are a property of the compiled program,
which the TPU's compiler builds here for a chip that is described and not
attached (`jax.experimental.topologies.get_topology_desc`). Prints one JSON
line: the compiler's `bytes accessed` and `flops`, the temporaries, and the
operand + result bytes of the entry computation's instructions summed by the
layer scope of each one's root (`optimize/profiler.py op_scopes`), with the
milliseconds those bytes take at the chip's HBM peak.

A compile-time reading, never a measurement: nothing runs.

Usage:
  JAX_PLATFORMS=cpu python tools/step_bytes.py --batch 256 \
      --conf deeplearning4j_tpu.models.zoo.resnet:resnet50_conf \
      --args '{"data_type": "bfloat16", "updater": "nesterovs"}'
  ... --topology cpu     # the CPU backend's own compile (what the test reads)
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HBM_BYTES_PER_S = {"v5e": 819e9}      # Google Cloud, "TPU v5e"

_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
             "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
             "f64": 8}
_ARRAY = re.compile(r"\b(" + "|".join(_ITEMSIZE) + r")\[([0-9,]*)\]")
_DEF = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*?) ([a-z][\w\-]*)\((.*)$")
_OPERAND = re.compile(r"%([\w.\-]+)")
# instructions that move no bytes of their own (an asynchronous copy is
# counted once, at its start)
_FREE = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast"}


def shape_bytes(shape_text):
    """Logical bytes of an HLO shape's text (an array or a tuple of them):
    elements times item size, layout padding not counted."""
    total = 0
    for dtype, dims in _ARRAY.findall(shape_text):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * _ITEMSIZE[dtype]
    return total


def entry_instructions(hlo_text):
    """(name, opcode, bytes) of each instruction of the ENTRY computation:
    its result's bytes plus its operands', the operands looked up by name.
    An upper reading: an operand a fusion slices with a stride, or finds in
    fast memory after a prefetch, is counted whole."""
    lines, inside = [], False
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY"):
            inside = True
        elif inside and line.startswith("}"):
            break
        elif inside:
            lines.append(line)
    defs = [m.groups() for m in map(_DEF.match, lines) if m]
    result = {name: shape_bytes(shape) for name, shape, _, _ in defs}
    out = []
    for name, _, opcode, rest in defs:
        if opcode in _FREE or opcode.endswith("-done"):
            continue
        read = sum(result.get(o, 0)
                   for o in _OPERAND.findall(rest.split("), ")[0]))
        # a `-start` returns (its operand, the destination, a context):
        # what it writes is the destination alone
        wrote = result[name] - read if opcode.endswith("-start") \
            else result[name]
        out.append((name, opcode, read + wrote))
    return out


def bytes_by_scope(hlo_text):
    """{"<kind> forward|backward": bytes} over the entry computation, an
    instruction under the scope of its root as `summarize_layers` puts its
    device time, and "(no scope)" for what the compiler adds."""
    from deeplearning4j_tpu.optimize.profiler import op_scopes, scope_label
    scopes = op_scopes(hlo_text)
    table = {}
    for name, _, nbytes in entry_instructions(hlo_text):
        key = scope_label(scopes.get(name, ""))
        table[key] = table.get(key, 0) + nbytes
    return dict(sorted(table.items(), key=lambda kv: -kv[1]))


def zero_batch(conf, batch):
    """One MultiDataSet of `batch` zero rows of the configuration's own
    input types and output widths."""
    import numpy as np

    from deeplearning4j_tpu.datasets.dataset import MultiDataSet
    return MultiDataSet(
        [np.zeros((batch, t.height, t.width, t.channels), np.float32)
         for t in conf.input_types],
        [np.zeros((batch, conf.vertices[o].conf.n_out), np.float32)
         for o in conf.network_outputs])


def report(compiled, hbm_bytes_per_s=None):
    """The JSON-ready reading of one compiled step."""
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    mem = compiled.memory_analysis()
    by_scope = bytes_by_scope(compiled.as_text())
    out = {"bytes_accessed": cost.get("bytes accessed"),
           "flops": cost.get("flops"),
           "temp_size_in_bytes": getattr(mem, "temp_size_in_bytes", None),
           "entry_bytes": sum(by_scope.values()),
           "entry_bytes_by_scope": by_scope}
    if hbm_bytes_per_s:
        out["entry_ms_at_hbm_peak"] = {
            k: round(v / hbm_bytes_per_s * 1e3, 3) for k, v in
            [("total", out["entry_bytes"]), *by_scope.items()]}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--conf", required=True,
                    help="module:function that returns the configuration")
    ap.add_argument("--args", default="{}", help="its arguments, JSON")
    ap.add_argument("--batch", type=int, required=True)
    ap.add_argument("--topology", default="v5e:2x2",
                    help="a TPU topology's name, or 'cpu'")
    ap.add_argument("--hlo", help="write the compiled text here")
    a = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    from deeplearning4j_tpu.nn.graph import ComputationGraph
    mod, fn = a.conf.split(":")
    conf = getattr(importlib.import_module(mod), fn)(**json.loads(a.args))
    net = ComputationGraph(conf).init()
    sharding = peak = None
    if a.topology != "cpu":
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        # a compile for a described chip cannot be read back from the cache
        jax.config.update("jax_enable_compilation_cache", False)
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name=a.topology)
        sharding = SingleDeviceSharding(topo.devices[0])
        peak = HBM_BYTES_PER_S.get(a.topology.split(":")[0])
    compiled = net.lower_step(zero_batch(conf, a.batch), sharding).compile()
    if a.hlo:
        with open(a.hlo, "w") as f:
            f.write(compiled.as_text())
    print(json.dumps({"conf": a.conf, "batch": a.batch,
                      "topology": a.topology,
                      "convbn_pairs": len(net._convbn_plan()),
                      **report(compiled, peak)}))


if __name__ == "__main__":
    main()
