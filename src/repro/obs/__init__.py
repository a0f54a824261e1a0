"""repro.obs — the unified observability subsystem.

Three layers behind one declarative :class:`TelemetrySpec` on the
:class:`~repro.core.plan.ExecutionPlan` (the telemetry-injection
contract, :mod:`repro.core.primitives`):

* :mod:`repro.obs.counters` — device-side int32 counters threaded
  through every executor's scan carry (per-phase rounds, schedule
  sizes, the ρ-filter ledger, SSP staleness histograms), bit-neutral to
  model state;
* :mod:`repro.obs.events` — the host-side :class:`Recorder` of typed
  instants and strictly nested wall-clock spans, exportable as JSONL
  and Chrome-trace files, for one ``execute`` or, made active by
  :func:`recording`, for a whole run;
* :mod:`repro.obs.report` — :class:`RunReport`, the uniform
  ``ExecutionReport.telemetry`` object every executor returns
  (``python -m repro.launch.trace`` summarizes/checks saved ones).
"""
from .counters import (init_counters, observe_read, observe_round,
                       staleness_init, summarize_counters)
from .events import (PHASES, Recorder, active, chrome_trace, recording,
                     validate_spans, write_chrome_trace, write_jsonl)
from .report import RunReport, report_from_json
from .spec import TELEMETRY_KINDS, TelemetrySpec

__all__ = [
    "PHASES", "TELEMETRY_KINDS", "TelemetrySpec", "Recorder", "RunReport",
    "active", "chrome_trace", "init_counters", "observe_read",
    "observe_round", "recording", "report_from_json", "staleness_init",
    "summarize_counters", "validate_spans", "write_chrome_trace",
    "write_jsonl",
]
