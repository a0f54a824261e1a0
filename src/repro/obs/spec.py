"""The declarative observability surface: :class:`TelemetrySpec`.

The paper's dynamic-parallelism argument rests on *measured* system
behavior — staleness actually observed, bytes actually moved, load
actually imbalanced — so observability is a first-class policy on the
:class:`~repro.core.plan.ExecutionPlan`, declared exactly like the
scheduler/partitioner/kernel policies already are:

* **frozen + hashable** — a spec is a value, usable as a sweep key;
* **validated at construction** — every invalid kind/parameter
  combination raises here, at spec-build time, never at trace time;
* **JSON-round-trippable** — ``to_json``/``from_json`` are exact
  (defaults included), so specs live inside checked-in plan files
  (``examples/plans/``) and CLI flags (``launch/dryrun.py
  --telemetry``).

Two kinds, by cost:

* ``"counters"`` — device-side int32 counters threaded through every
  executor's scan carry (per-phase round counts, the ρ-filter's
  proposed/accepted/killed tallies, the app's visited/updated counts,
  plus SSP's staleness histogram).  Bit-neutral to model state.
* ``"trace"`` — counters **plus** host-side structured events: a
  :class:`~repro.obs.events.Recorder` collecting typed instants
  (compiled-program cache misses, rebalances with before/after load
  spreads, checkpoint writes) and wall-clock phase spans, compiles and
  collections, exportable as JSONL and as a Chrome-trace
  (``chrome://tracing``/Perfetto) file.  ``profiler=True`` additionally
  opens a ``jax.profiler`` TraceAnnotation around every span so the host
  phases line up inside an XLA profile.

What it costs, on one TPU v5e (``benchmarks/telemetry_cost.py``, the
benchmark cell ``lda-nytimes.1chip``, six 10 s windows of each mode in
one process): ``kind="trace"`` with the profiler off ran 39,586
updates/s against 40,614 with telemetry off, 2.5 % fewer.  ``execute``
returned in 3.5–4.1 ms against 2.4–2.8 ms, so about 1 ms of a 1.6 s
round is host work and the rest is the device: the counted program
compiles to a different Gibbs loop (one of its token arrays is no
longer placed in on-chip memory, by an AOT compile for v5e).
"""
from __future__ import annotations

import dataclasses
import json

TELEMETRY_KINDS = ("counters", "trace")

_KIND_MSG = "telemetry kind must be 'counters' or 'trace'; got {!r}"

# Which fields each kind consumes; everything else must stay at its zero
# default (a spec never carries silently-ignored knobs).
_FIELDS_BY_KIND = {
    "counters": (),
    "trace": ("profiler",),
}


@dataclasses.dataclass(frozen=True)
class TelemetrySpec:
    """Everything the engine needs to know about *what* to observe.

    Fields
    ------
    kind:     ``"counters"`` (device-side per-phase/schedule/ρ-filter
              counters in the executor carry — the hot-path-safe floor)
              or ``"trace"`` (counters + the host-side event
              :class:`~repro.obs.events.Recorder` with phase spans and
              Chrome-trace export).
    profiler: with ``kind="trace"``: open a ``jax.profiler``
              TraceAnnotation around every recorded span, so host
              phases appear inside an XLA device profile.
    """

    kind: str
    profiler: bool = False

    def __post_init__(self):
        if self.kind not in TELEMETRY_KINDS:
            raise ValueError(_KIND_MSG.format(self.kind))
        if not isinstance(self.profiler, bool):
            raise ValueError(f"profiler must be a bool; got "
                             f"{self.profiler!r}")
        used = _FIELDS_BY_KIND[self.kind]
        if "profiler" not in used and self.profiler:
            raise ValueError(
                f"profiler={self.profiler!r} does not apply to "
                f"kind={self.kind!r} (leave it at its default)")

    @property
    def events(self) -> bool:
        """True when this spec asks for the host-side event Recorder."""
        return self.kind == "trace"

    # -- serialization -------------------------------------------------------

    def to_json(self) -> dict:
        """A plain JSON-safe dict (every field, defaults included) —
        ``from_json(to_json(s)) == s`` exactly."""
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, obj) -> "TelemetrySpec":
        """Rebuild from ``to_json`` output, a JSON string, or a partial
        dict (missing fields take their defaults; unknown keys raise)."""
        if isinstance(obj, (str, bytes)):
            obj = json.loads(obj)
        if not isinstance(obj, dict):
            raise TypeError(f"TelemetrySpec.from_json wants a dict or "
                            f"JSON string; got {type(obj).__name__}")
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(obj) - known
        if unknown:
            raise ValueError(f"unknown TelemetrySpec field(s): "
                             f"{sorted(unknown)}")
        return cls(**obj)

    @classmethod
    def default_for(cls, kind: str, **overrides) -> "TelemetrySpec":
        """The conventional spec for a kind — the ONE defaults table the
        CLI surfaces (``dryrun --telemetry``) resolve flag-built specs
        from, so per-site copies cannot drift.  ``overrides`` replace
        individual fields on the conventional base."""
        if kind not in TELEMETRY_KINDS:
            raise ValueError(_KIND_MSG.format(kind))
        base = dict(kind=kind)
        base.update(overrides)
        return cls(**base)
