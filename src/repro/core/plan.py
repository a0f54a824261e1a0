"""The declarative execution surface: :class:`ExecutionPlan`.

The paper's pitch is a *small, fixed* primitive set (``schedule`` /
``push`` / ``pull`` / ``sync``) that applications program against once
while the runtime freely swaps partitioning and update scheduling.  After
the executor zoo grew (host loop, scanned, pipelined, SSP) the call
surface no longer matched that pitch: every entry point had its own
kwargs, and validation ("staleness needs ssp", "pipelined needs rounds
divisible by the phase period") was scattered across call sites.

An :class:`ExecutionPlan` is the single declarative answer:

* **frozen + hashable** — a plan is a value, usable as a jit/cache key;
* **validated at construction** — every invalid executor/kwarg
  combination raises here, at plan-build time, never at trace time, and
  the error text lives in exactly one place;
* **JSON-round-trippable** — ``to_json``/``from_json`` are exact
  (defaults included), so plans live in checked-in files
  (``examples/plans/``) and CLI flags (``launch/train.py --plan``,
  ``launch/dryrun.py --plan``).

One engine entry point consumes it — ``StradsEngine.execute(state, data,
rng, plan)`` — and returns a uniform :class:`ExecutionReport` (final
state, per-round trace, SSP telemetry, resumable carry) regardless of
which executor ran.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Optional, Union

from ..kernels.spec import KernelSpec
from ..obs.spec import TelemetrySpec
from ..part.spec import PartitionerSpec
from ..sched.spec import SchedulerSpec

EXECUTORS = ("loop", "scan", "pipelined", "ssp")

# The one place the executor-name error is worded (apps/_exec.py used to
# carry a drifted copy that claimed 'loop' was acceptable but raised on
# it — see ISSUE 3).
_EXECUTOR_MSG = ("executor must be 'loop', 'scan', 'pipelined' or 'ssp'; "
                 "got {!r}")


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """Everything the engine needs to know about *how* to run R rounds.

    Fields
    ------
    executor:        ``"loop"`` (host loop, per-round dispatch),
                     ``"scan"`` (one ``lax.scan`` XLA program, BSP),
                     ``"pipelined"`` (scan + one-round-stale schedule
                     prefetch), ``"ssp"`` (bounded staleness, ``repro.ps``).
    rounds:          total BSP/SSP rounds the plan executes.
    staleness:       SSP bound ``s`` (reads ≤ s rounds stale); > 0 only
                     valid with ``executor="ssp"``.
    phase_unroll:    rounds unrolled per scan step, as a multiple of the
                     app's ``phase_period`` (1 = one phase cycle per scan
                     step — the default and the bit-identical baseline).
                     Only meaningful for the scanned executors.
    telemetry:       the observability policy, as a declarative
                     :class:`~repro.obs.spec.TelemetrySpec` (kind ∈
                     counters | trace).  ``False`` (the default) runs
                     uninstrumented; a spec makes **every** executor
                     return a populated
                     :class:`~repro.obs.report.RunReport` as
                     ``ExecutionReport.telemetry`` (device counters,
                     host events under ``kind="trace"``, and the SSP
                     staleness/byte section for ssp plans) — final model
                     state stays bit-identical either way.
    checkpoint_every: checkpoint cadence in rounds for
                     ``StradsEngine.execute(..., ckpt_dir=...)`` (0 = no
                     checkpointing); must tile the executor's step length.
    collect_every:   trace cadence in rounds for the app-level ``fit``
                     adapters (0 = no trace).  ``execute`` itself collects
                     per round whenever a collect fn is passed; this field
                     records the decimation cadence consumers apply.
    donate:          donate the input state buffers to the XLA program.
    workers:         expected ``data``-mesh width (placement override).
                     ``None`` = whatever mesh the engine was built with;
                     a value is validated against the engine's mesh and
                     used by drivers (``dryrun --plan``) to *build* the
                     mesh.
    scheduler:       the scheduling policy, as a declarative
                     :class:`~repro.sched.spec.SchedulerSpec` (kind ∈
                     round_robin | random | rotation | dynamic_priority |
                     block_structural plus its parameters).  ``None`` =
                     the app's ``default_scheduler_spec()``; a value is
                     resolved and injected by ``StradsEngine.execute``,
                     so ``fit(plan=...)`` overrides policy without
                     touching app config.
    partitioner:     the partition policy, as a declarative
                     :class:`~repro.part.spec.PartitionerSpec` (kind ∈
                     static | size_balanced | load_balanced plus its
                     parameters).  ``None`` = the app's
                     ``default_partitioner_spec()``; the resolved
                     partitioner owns the variable→worker
                     :class:`~repro.part.assignment.Assignment`, and the
                     engine checks it for rebalances at the
                     ``checkpoint_every`` chunk boundaries — the other
                     half of the paper's primitive pair, swappable from
                     the plan exactly like the scheduler.
    kernels:         the compute backend serving the round body's
                     hot-spots, as a declarative
                     :class:`~repro.kernels.spec.KernelSpec` (kind ∈
                     reference | pallas plus tile knobs).  ``None`` =
                     the app's ``default_kernel_spec()`` (falling back
                     to ``reference`` — the bit-identical
                     pre-KernelSpec behavior); a value is resolved via
                     ``repro.kernels.build_kernels`` and injected by
                     ``StradsEngine.execute``, with the Pallas kind
                     automatically running in interpret mode off-TPU —
                     the third leg of the "everything is a plan edit"
                     surface.
    """

    executor: str = "scan"
    rounds: int = 1
    staleness: int = 0
    phase_unroll: int = 1
    telemetry: Union[bool, TelemetrySpec] = False
    checkpoint_every: int = 0
    collect_every: int = 0
    donate: bool = True
    workers: Optional[int] = None
    scheduler: Optional[SchedulerSpec] = None
    partitioner: Optional[PartitionerSpec] = None
    kernels: Optional[KernelSpec] = None

    def __post_init__(self):
        if self.executor not in EXECUTORS:
            raise ValueError(_EXECUTOR_MSG.format(self.executor))
        if not isinstance(self.rounds, int) or self.rounds < 1:
            raise ValueError(f"rounds must be a positive int; got "
                             f"{self.rounds!r}")
        if not isinstance(self.staleness, int) or self.staleness < 0:
            raise ValueError(f"staleness must be an int >= 0; got "
                             f"{self.staleness!r}")
        if self.staleness > 0 and self.executor != "ssp":
            raise ValueError(
                f"staleness={self.staleness} requires executor='ssp'; got "
                f"executor={self.executor!r}")
        if not isinstance(self.phase_unroll, int) or self.phase_unroll < 1:
            raise ValueError(f"phase_unroll must be a positive int; got "
                             f"{self.phase_unroll!r}")
        if self.phase_unroll > 1 and self.executor not in ("scan",
                                                           "pipelined"):
            raise ValueError(
                f"phase_unroll={self.phase_unroll} only applies to the "
                f"scanned executors; got executor={self.executor!r}")
        # False (or None) is off; instrumentation is always a spec
        if self.telemetry is None:
            object.__setattr__(self, "telemetry", False)
        if self.telemetry is not False \
                and not isinstance(self.telemetry, TelemetrySpec):
            raise ValueError(
                f"telemetry must be False (off) or a repro.obs."
                f"TelemetrySpec (its own __post_init__ validates the "
                f"kind); got {self.telemetry!r}")
        for field in ("checkpoint_every", "collect_every"):
            v = getattr(self, field)
            if not isinstance(v, int) or v < 0:
                raise ValueError(f"{field} must be an int >= 0; got {v!r}")
        if not isinstance(self.donate, bool):
            raise ValueError(f"donate must be a bool; got {self.donate!r}")
        if self.workers is not None and (not isinstance(self.workers, int)
                                         or self.workers < 1):
            raise ValueError(f"workers must be None or a positive int; "
                             f"got {self.workers!r}")
        if self.scheduler is not None \
                and not isinstance(self.scheduler, SchedulerSpec):
            raise ValueError(
                f"scheduler must be None or a repro.sched.SchedulerSpec "
                f"(its own __post_init__ validates the policy); got "
                f"{type(self.scheduler).__name__}")
        if self.partitioner is not None \
                and not isinstance(self.partitioner, PartitionerSpec):
            raise ValueError(
                f"partitioner must be None or a repro.part.PartitionerSpec "
                f"(its own __post_init__ validates the policy); got "
                f"{type(self.partitioner).__name__}")
        if self.kernels is not None \
                and not isinstance(self.kernels, KernelSpec):
            raise ValueError(
                f"kernels must be None or a repro.kernels.KernelSpec "
                f"(its own __post_init__ validates the backend); got "
                f"{type(self.kernels).__name__}")

    # -- derived views -------------------------------------------------------

    @property
    def depth(self) -> int:
        """The schedule-prefetch depth this plan's executor runs at."""
        return 1 if self.executor == "pipelined" else 0

    # -- serialization -------------------------------------------------------

    def to_json(self) -> dict:
        """A plain JSON-safe dict (every field, defaults included) —
        ``from_json(to_json(p)) == p`` exactly."""
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, obj) -> "ExecutionPlan":
        """Rebuild from ``to_json`` output, a JSON string, or a partial
        dict (missing fields take their defaults; unknown keys raise)."""
        if isinstance(obj, (str, bytes)):
            obj = json.loads(obj)
        if not isinstance(obj, dict):
            raise TypeError(f"ExecutionPlan.from_json wants a dict or JSON "
                            f"string; got {type(obj).__name__}")
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(obj) - known
        if unknown:
            raise ValueError(f"unknown ExecutionPlan field(s): "
                             f"{sorted(unknown)}")
        if isinstance(obj.get("scheduler"), dict):
            obj = dict(obj,
                       scheduler=SchedulerSpec.from_json(obj["scheduler"]))
        if isinstance(obj.get("partitioner"), dict):
            obj = dict(obj, partitioner=PartitionerSpec.from_json(
                obj["partitioner"]))
        if isinstance(obj.get("kernels"), dict):
            obj = dict(obj, kernels=KernelSpec.from_json(obj["kernels"]))
        if isinstance(obj.get("telemetry"), dict):
            obj = dict(obj, telemetry=TelemetrySpec.from_json(
                obj["telemetry"]))
        return cls(**obj)


@dataclasses.dataclass
class ExecutionReport:
    """Uniform result of ``StradsEngine.execute`` — every executor fills
    the same four slots (unused ones stay ``None``).

    state:      final model state pytree.
    trace:      stacked per-round ``collect`` outputs (leading axis =
                rounds executed this call), or ``None`` without a collect
                fn.
    telemetry:  :class:`repro.obs.report.RunReport` when the plan
                carries a :class:`~repro.obs.spec.TelemetrySpec` — the
                uniform per-run metrics object (device counters, host
                events, and the SSP staleness/byte section as its
                ``.ssp`` for ssp plans); ``None`` uninstrumented.
    carry:      resumable executor carry — :class:`repro.ps.ssp.SSPCarry`
                for SSP, :class:`repro.core.engine.EngineCarry` for the
                loop/scanned executors.  Round-trips through
                ``checkpoint/npz``; pass it back to ``execute`` to
                continue the same plan bit-exactly.
    plan:       the plan that produced this report.
    stream:     the final stream-cursor payload (flat numpy:
                ``cursor``/``rows_in``/``rows_dropped``/``fill0``) when
                the run streamed data in via ``execute(..., stream=,
                source=)``; ``None`` for unstreamed runs.  The same
                dict rides each checkpoint as its ``"stream"`` subtree.
    """
    state: Any
    trace: Any = None
    telemetry: Any = None
    carry: Any = None
    plan: Optional[ExecutionPlan] = None
    stream: Optional[dict] = None
