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
entry_sweep.py — MF's indexed reads and segment sums over sparse entries
             as one-hot contractions on the MXU (no spec: chosen by
             platform, exact in float32)
split_chain.py — a key's split chain drawn on the TPU's scalar core (no
             spec: chosen by platform, the same bits as ``lax.scan``)
gibbs_scan.py — LDA's collapsed Gibbs token loop as one kernel, count
             rows fetched by DMA ahead of each draw (no spec: chosen by
             platform, the same draws as the XLA loop)
"""
from . import ops, ref  # noqa: F401
from .backend import (KERNEL_BACKENDS, PallasKernels,  # noqa: F401
                      ReferenceKernels, build_kernels)
from .spec import KERNEL_KINDS, KernelSpec  # noqa: F401

__all__ = [
    "ops", "ref", "KERNEL_KINDS", "KernelSpec", "KERNEL_BACKENDS",
    "ReferenceKernels", "PallasKernels", "build_kernels",
]
