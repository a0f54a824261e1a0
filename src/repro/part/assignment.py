"""The :class:`Assignment` value: which worker owns each model variable.

An assignment is the *output* of a partitioner — the paper's
variable→worker ownership map for the partitioned model store (1411.2305
calls these block owners; 1312.5766 rebalances them by load).  Like
:class:`~repro.part.spec.PartitionerSpec` it is a frozen, hashable value:
the engine keys its compiled-program caches on the active assignment, so
two runs (or two chunks of one run) under the same assignment share
programs and a rebalance is exactly one cache miss.

It round-trips two ways: ``to_json``/``from_json`` for artifacts
(dry-run records) and ``payload``/``from_payload``
as a flat dict of numpy arrays for ``checkpoint/npz`` — the
``{"state", "carry", "assignment"}`` checkpoints
``StradsEngine.execute`` writes at chunk boundaries.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Dict, Optional

import numpy as np


@dataclasses.dataclass(frozen=True)
class Assignment:
    """Variable→worker ownership: variable ``j`` lives on worker
    ``owner[j]``.

    ``version`` counts rebalances (0 = the initial assignment); it names
    artifacts and makes "did a rebalance happen?" a cheap question —
    equality/hashing still compare the full owner map, so two identical
    proposals at different versions never alias a compiled-program cache
    entry wrongly (equal owners at different versions are *different*
    keys, which only costs a recompile, never a wrong program).
    """
    owner: tuple
    num_workers: int
    version: int = 0

    def __post_init__(self):
        owner = tuple(int(o) for o in self.owner)
        object.__setattr__(self, "owner", owner)
        if not isinstance(self.num_workers, int) or self.num_workers < 1:
            raise ValueError(f"num_workers must be a positive int; got "
                             f"{self.num_workers!r}")
        bad = [o for o in owner if not 0 <= o < self.num_workers]
        if bad:
            raise ValueError(
                f"owner entries must be worker ids in [0, "
                f"{self.num_workers}); got {sorted(set(bad))}")
        if not isinstance(self.version, int) or self.version < 0:
            raise ValueError(f"version must be an int >= 0; got "
                             f"{self.version!r}")

    @property
    def num_vars(self) -> int:
        return len(self.owner)

    # -- accounting ----------------------------------------------------------

    def counts(self) -> np.ndarray:
        """(U,) variables owned per worker."""
        return np.bincount(np.asarray(self.owner, np.int64),
                           minlength=self.num_workers)

    def loads(self, weights) -> np.ndarray:
        """(U,) per-worker load: the sum of ``weights`` (per-variable
        activity, bytes, …) over each worker's owned variables."""
        w = np.asarray(weights, np.float64)
        if w.shape != (self.num_vars,):
            raise ValueError(f"weights must have shape ({self.num_vars},)"
                             f"; got {w.shape}")
        return np.bincount(np.asarray(self.owner, np.int64), weights=w,
                           minlength=self.num_workers)

    def spread(self, weights) -> float:
        """Relative per-worker load spread ``(max − min) / mean`` — the
        imbalance quantity ``PartitionerSpec.imbalance_threshold`` gates
        on (0 = perfectly balanced)."""
        loads = self.loads(weights)
        mean = float(loads.mean())
        if mean == 0.0:
            return 0.0
        return float((loads.max() - loads.min()) / mean)

    # -- serialization (artifacts) -------------------------------------------

    def to_json(self) -> dict:
        return {"owner": list(self.owner),
                "num_workers": self.num_workers,
                "version": self.version}

    @classmethod
    def from_json(cls, obj) -> "Assignment":
        if isinstance(obj, (str, bytes)):
            obj = json.loads(obj)
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(obj) - known
        if unknown:
            raise ValueError(f"unknown Assignment field(s): "
                             f"{sorted(unknown)}")
        return cls(**obj)

    # -- serialization (checkpoint/npz) --------------------------------------

    def payload(self) -> Dict[str, np.ndarray]:
        """Flat array dict for ``checkpoint/npz`` (the ``"assignment"``
        subtree of a chunked run's checkpoint)."""
        return {"owner": np.asarray(self.owner, np.int32),
                "num_workers": np.int32(self.num_workers),
                "version": np.int32(self.version)}

    @classmethod
    def from_payload(cls, payload: Dict[str, np.ndarray]
                     ) -> Optional["Assignment"]:
        if payload is None:
            return None
        return cls(owner=tuple(int(o) for o in
                               np.asarray(payload["owner"])),
                   num_workers=int(payload["num_workers"]),
                   version=int(payload["version"]))


def contiguous_assignment(num_vars: int, num_workers: int) -> Assignment:
    """The frozen contiguous partition: worker u owns
    ``[bounds[u], bounds[u+1])`` with ``bounds = round(linspace(0, J,
    U+1))`` — bit-identical to
    :attr:`repro.sched.schedulers.RotationScheduler.bounds`, so the
    static assignment and the rotation scheduler's variable→worker
    mapping can never disagree.  The edges are computed through the
    same jnp float32 linspace the rotation scheduler uses: a host-side
    float64 linspace rounds differently at vocab scale (J ≳ 10⁶), which
    would put boundary variables on the wrong worker."""
    import jax.numpy as jnp
    edges = np.asarray(
        jnp.round(jnp.linspace(0, num_vars, num_workers + 1))
        .astype(jnp.int32), np.int64)
    owner = np.searchsorted(edges[1:], np.arange(num_vars), side="right")
    return Assignment(owner=tuple(int(o) for o in owner),
                      num_workers=num_workers)
