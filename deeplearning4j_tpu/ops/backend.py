"""What every Pallas kernel of `ops/` decides the same way: whether it runs as
a Mosaic kernel or under the Pallas interpreter, what goes to the compiler,
and a block that divides its extent."""
from __future__ import annotations

import jax
from jax.experimental.pallas import tpu as pltpu


def resolve_interpret(interpret):
    """`interpret=None` follows the backend: the Mosaic kernel on TPU, the
    Pallas interpreter on CPU (tests and CPU meshes). Any other backend
    raises: an interpreter run there would be a silent, wrong-speed
    stand-in for a kernel that does not exist."""
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise NotImplementedError(
        f"the Pallas kernels have a TPU (Mosaic) form and a CPU interpreter "
        f"form; backend {backend!r} has neither")


def compiler_params(interpret, semantics, vmem_limit_bytes=None):
    """The keyword arguments of a `pallas_call` for the compiler: none under
    the interpreter; else the grid axes' `semantics` and, where given, the
    kernel's VMEM limit (the scoped default where not)."""
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=vmem_limit_bytes)}


def divisor_block(T, requested):
    """The largest block of at most `requested` that divides T."""
    b = min(requested, T)
    while T % b:
        b -= 1
    return b
