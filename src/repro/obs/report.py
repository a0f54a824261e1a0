"""The uniform per-run metrics surface: :class:`RunReport`.

``ExecutionReport.telemetry`` is one of these for **every** executor
whenever the plan carries a :class:`~repro.obs.spec.TelemetrySpec`:
the resolved spec, the device counters (summarized to host ints when
first read), the host event log (``kind="trace"``), and — for SSP runs —
the :class:`~repro.ps.telemetry.SSPTelemetry` staleness/byte section
that used to be the whole telemetry story.

A RunReport is JSON-first: ``to_json()`` is what dryrun/train/benchmark
artifacts embed and what ``python -m repro.launch.trace`` summarizes,
checks and re-exports (JSONL / Chrome trace) offline.
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, Optional

from .counters import summarize_counters
from .events import write_chrome_trace, write_jsonl
from .spec import TelemetrySpec


@dataclasses.dataclass
class RunReport:
    """One instrumented run, summarized uniformly across executors.

    Building one never waits for the device: the counters stay device
    arrays until first read (:attr:`counters`, :meth:`summary`,
    :meth:`to_json`), so an instrumented ``execute`` returns while its
    rounds still run, as an uninstrumented one does.

    spec:      the resolved :class:`TelemetrySpec` that instrumented
               the run.
    executor:  the plan's executor name.
    rounds:    the host's round index after the run.
    events:    the host event log (instants + strictly nested spans,
               microsecond timestamps) — ``[]`` under
               ``kind="counters"``.
    ssp:       the :class:`repro.ps.telemetry.SSPTelemetry` section
               (staleness histogram + byte accounting); ``None`` for
               the BSP executors.
    device_counters:
               the device counter pytree off the final carry, until
               :attr:`counters` first reads it.
    """
    spec: TelemetrySpec
    executor: str
    rounds: int
    events: List[dict] = dataclasses.field(default_factory=list)
    ssp: Any = None
    device_counters: Any = dataclasses.field(default=None, repr=False)
    _counters: Optional[dict] = dataclasses.field(default=None,
                                                  repr=False)

    @property
    def counters(self) -> dict:
        """Host-int summary of the device counters (see
        :func:`repro.obs.counters.summarize_counters`); ``{}`` only for
        runs that executed zero rounds.  The first read waits for the
        run."""
        if self._counters is None:
            self._counters = summarize_counters(self.device_counters)
            self.device_counters = None
        return self._counters

    @classmethod
    def build(cls, spec: TelemetrySpec, executor: str, rounds: int,
              device_counters: Any = None, recorder: Any = None,
              ssp: Any = None, events: Optional[List[dict]] = None
              ) -> "RunReport":
        """Assemble from the run's raw pieces: the device counter pytree
        off the final carry (kept, not read), the run's events (or the
        whole log of ``recorder``), and the SSP summary (or None)."""
        if events is None:
            events = (recorder.to_json_events()
                      if recorder is not None else [])
        return cls(spec=spec, executor=executor, rounds=rounds,
                   events=events, ssp=ssp, device_counters=device_counters)

    # -- export --------------------------------------------------------------

    def to_json(self) -> dict:
        return {"spec": self.spec.to_json(),
                "executor": self.executor,
                "rounds": self.rounds,
                "counters": dict(self.counters),
                "events": [dict(ev) for ev in self.events],
                "ssp": self.ssp.to_json() if self.ssp is not None
                else None}

    def summary(self) -> str:
        """One line per layer — what the trace CLI prints."""
        lines = [f"{self.executor}: {self.rounds} rounds "
                 f"(telemetry kind={self.spec.kind!r})"]
        c = self.counters
        if c:
            lines.append(
                f"  counters: rounds/phase {c['rounds_per_phase']}  "
                f"rho-filter {c['accepted']}/{c['proposed']} kept "
                f"({c['killed']} killed)  updated/visited "
                f"{c.get('updated', 0)}/{c.get('visited', 0)}")
        if self.events:
            spans = [e for e in self.events if e.get("ph") == "X"]
            inst = len(self.events) - len(spans)
            lines.append(f"  events: {len(spans)} spans, {inst} "
                         f"instants")
            for e in spans:
                if not _enclosed(e, spans):
                    lines.append(f"    {e['name']}: "
                                 f"{e['dur'] / 1e3:.2f} ms")
        if self.ssp is not None:
            s = self.ssp
            lines.append(
                f"  ssp: staleness<= {s.max_staleness}/"
                f"{s.staleness_bound}  hist {list(map(int, s.hist))}  "
                f"flushes {s.flushes}  pushed {s.bytes_pushed}B")
        return "\n".join(lines)

    def write_jsonl(self, path: str) -> str:
        return write_jsonl(self.events, path)

    def write_chrome_trace(self, path: str) -> str:
        return write_chrome_trace(self.events, path)


def _enclosed(ev: dict, spans: List[dict]) -> bool:
    return any(o is not ev and o["ts"] <= ev["ts"]
               and ev["ts"] + ev["dur"] <= o["ts"] + o["dur"]
               for o in spans)


def report_from_json(obj: dict) -> RunReport:
    """Rebuild a RunReport (sans the live SSPTelemetry object — its
    section stays a plain dict) from ``to_json()`` output; the trace CLI
    uses this to summarize/check/re-export saved artifacts."""
    spec = TelemetrySpec.from_json(obj["spec"])
    rep = RunReport(spec=spec, executor=obj["executor"],
                    rounds=int(obj["rounds"]),
                    events=list(obj.get("events") or []),
                    ssp=_DictSection(obj["ssp"]) if obj.get("ssp")
                    else None,
                    _counters=dict(obj.get("counters") or {}))
    return rep


class _DictSection:
    """A saved SSP section, re-animated just enough for summary()."""

    def __init__(self, d: dict):
        self._d = dict(d)

    def __getattr__(self, name):
        try:
            return self._d[name]
        except KeyError:
            raise AttributeError(name) from None

    def to_json(self) -> dict:
        return dict(self._d)
