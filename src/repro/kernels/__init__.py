"""Pallas TPU kernels for the compute hot-spots.

<name>.py  — pl.pallas_call + explicit BlockSpec VMEM tiling
ops.py     — jit'd public wrappers (backend dispatch: pallas/interpret/ref)
ref.py     — pure-jnp oracles (semantics contract + CPU execution path)
spec.py    — KernelSpec: the declarative backend choice carried as
             ``ExecutionPlan.kernels`` (frozen, validated,
             JSON-round-trippable — the third leg of the
             scheduler/partitioner spec pattern)
backend.py — build_kernels registry resolving a spec into an executable
             backend (Pallas on TPU, interpret-mode fallback elsewhere)
split_chain.py — a key's split chain drawn on the TPU's scalar core (no
             spec: chosen by platform, the same bits as ``lax.scan``)
"""
from . import ops, ref  # noqa: F401
from .backend import (KERNEL_BACKENDS, PallasKernels,  # noqa: F401
                      ReferenceKernels, build_kernels)
from .spec import KERNEL_KINDS, KernelSpec  # noqa: F401

__all__ = [
    "ops", "ref", "KERNEL_KINDS", "KernelSpec", "KERNEL_BACKENDS",
    "ReferenceKernels", "PallasKernels", "build_kernels",
]
