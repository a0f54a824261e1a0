"""What the program's own names say about a traced window: device time by
round phase, and idle gaps by the innermost host span.

A device trace names an operation by its HLO text (``%fusion.3 =
f32[4096]{0:T(1024)} fusion(...)``) and carries no scope.  The compiled
program's text (``jax.stages.Compiled.as_text()``) does: an instruction's
``metadata={op_name="..."}`` holds the ``jax.named_scope`` names it was
traced under, among them the round's phases (``repro.obs.PHASES``:
propose, schedule_stats, schedule, push, exchange, aggregate, pull,
sched_update).  :func:`phases_of` reads that text into a map from an
instruction's short name (:func:`bench.trace_reduce.short_name`: its name
and result type) to the innermost phase it ran under;
:func:`busy_by_phase` sums the window's self time per phase over the
operations of that program alone.

The program's host spans (``repro.obs.Recorder`` with ``profiler=True``:
``execute``, ``execute.resolve``, ``scan.call``, …) nest inside the
harness's; :func:`label_innermost` names an idle gap by the innermost of
them that covers most of it.

Where the program names no phases (a commit before they existed), every
reading here is empty or ``None``.
"""
from __future__ import annotations

import re

from bench import trace_reduce

_INSTR = re.compile(r"^\s+(?:ROOT\s+)?(%[^\s=]+ = .*)$")
_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\b(?:calls|body|condition|to_apply|true_computation|"
                    r"false_computation|branch_computations)="
                    r"(\{[^}]*\}|%[^\s,)]+)")
_MODULE = re.compile(r"^HloModule\s+([^\s,]+)")


def program_phases() -> tuple:
    """The phase names the program declares; ``()`` where it declares
    none."""
    import repro.obs
    return tuple(getattr(repro.obs, "PHASES", ()))


def module_name(text: str) -> str | None:
    m = _MODULE.match(text.lstrip())
    return m.group(1) if m else None


def _computations(text: str) -> tuple:
    """(computation → [(short name, own phase path, callees)], entry)."""
    comps: dict = {}
    cur, entry = None, None
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m and cur is not None:
            instr = m.group(1)
            op = _OP_NAME.search(instr)
            callees = [c for g in _CALLS.findall(instr)
                       for c in re.findall(r"%([^\s,{}]+)", g)]
            comps[cur].append((trace_reduce.short_name(instr),
                               op.group(1) if op else "", callees))
            continue
        h = _HEADER.match(line)
        if h:
            cur = h.group(1)
            comps[cur] = []
            if line.startswith("ENTRY"):
                entry = cur
    return comps, entry


def phases_of(text: str, phases: tuple) -> dict:
    """Short name of each instruction of the compiled ``text`` → the
    innermost of ``phases`` it ran under (instructions under no phase are
    left out).  An instruction the compiler made (it has no ``op_name``
    of a phase: bounds checks, copies) takes the phase of the instruction
    that calls its computation: a loop body's checks belong to the loop's
    phase."""
    want = set(phases)
    comps, entry = _computations(text)

    def own(op_name):
        inner = [p for p in op_name.split("/") if p in want]
        return inner[-1] if inner else None

    out: dict = {}
    seen = {entry}
    todo = [(entry, None)]
    while todo:
        comp, phase = todo.pop()
        for name, op_name, callees in comps.get(comp, []):
            p = own(op_name) or phase
            if p is not None:
                out[name] = p
            for c in callees:
                if c not in seen:
                    seen.add(c)
                    todo.append((c, p))
    return out


def program_trace(trace: trace_reduce.Trace, module: str
                  ) -> trace_reduce.Trace:
    """``trace`` with only the operations that ran inside a run of the
    program ``module`` (its ``XLA Modules`` events are named
    ``<module>(<id>)``)."""
    ops = {}
    for d, evs in trace.ops.items():
        runs = [(a, b) for n, a, b in trace.programs.get(d, [])
                if n.split("(", 1)[0] == module]
        ops[d] = [e for e in evs
                  if any(a <= e[1] and e[2] <= b for a, b in runs)]
    return trace_reduce.Trace(ops, trace.spans, trace.dropped_at,
                              trace.programs)


def busy_by_phase(trace: trace_reduce.Trace, text: str,
                  phases: tuple | None = None) -> dict:
    """Seconds of device self time (mean over devices) per innermost
    phase, over the operations of the program whose compiled ``text`` is
    given; ``None`` holds its time under no phase.  Empty where the
    program names no phases or its runs are not in the trace."""
    phases = program_phases() if phases is None else phases
    module = module_name(text)
    if not phases or module is None:
        return {}
    of = phases_of(text, phases)
    own = program_trace(trace, module)
    out: dict = {}
    for name, secs in own.self_times().items():
        key = of.get(name)
        out[key] = out.get(key, 0.0) + secs
    return out


def share(by_phase: dict, names) -> float | None:
    """Per cent of the program's device time under ``names``; ``None``
    where nothing was mapped."""
    total = sum(by_phase.values())
    if total <= 0 or not any(k is not None for k in by_phase):
        return None
    return 100.0 * sum(by_phase.get(n, 0.0) for n in names) / total


def label_innermost(spans: list, a: float, b: float) -> str:
    """The innermost of ``spans`` (``(name, start, end)``) that covers
    more than half of ``[a, b]``; else the one that covers most of it."""
    width = max(b - a, 0.0)
    best, cover, inner = "outside any span", 0.0, None
    for name, s, e in spans:
        c = min(b, e) - max(a, s)
        if c <= 0:
            continue
        if c > width / 2 and (inner is None or e - s < inner[1]):
            inner = (name, e - s)
        if c > cover:
            best, cover = name, c
    return inner[0] if inner is not None else best
