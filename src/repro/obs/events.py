"""Host-side structured events: the :class:`Recorder`.

The device counters (:mod:`repro.obs.counters`) answer *what the
compiled program did*; the Recorder answers *what the host runtime did
around it*: compiled-program cache misses on (SchedulerSpec, Assignment,
KernelSpec) keys, partition rebalances with before/after load spreads,
checkpoint writes, and wall-clock spans around every execution phase
(resolve → chunk → executor dispatch, per-round under the host loop).

Events are typed dicts with a microsecond timestamp relative to the
Recorder's origin (a ``time.perf_counter_ns()`` reading):

* **instants** — ``{"name", "ph": "i", "ts", "parent", "args"}``;
* **spans** — ``{"name", "ph": "X", "ts", "dur", "id", "parent",
  "args"}``, produced by the ``span()`` context manager.  The
  context-manager discipline makes nesting *structural*: a span closes
  only after everything it opened, so exported spans are strictly nested
  with non-negative durations, each inside its ``parent``
  (``tests/test_obs.py`` validates the export against exactly that).

A Recorder records where it is handed to (``RunReport`` of one
``execute``), or for a whole run once :func:`recording` makes it the
process's active Recorder: the engine's spans fall back to the active
one, which also records JAX's compile pipeline (``jit.trace``,
``jit.lower``, ``jit.compile``) and Python's garbage collections
(``gc``).  With none active, each of the engine's span sites costs one
null context.

Exports: ``to_json_events()`` (the portable list that rides
:class:`~repro.obs.report.RunReport`), JSONL (one event per line), and
the Chrome trace-event format (``chrome://tracing`` / Perfetto — see
:func:`chrome_trace`).  ``profiler=True`` additionally opens a
``jax.profiler.TraceAnnotation`` around every span, so host phases line
up inside an XLA device profile.
"""
from __future__ import annotations

import contextlib
import gc
import json
import time
from typing import List, Optional

#: the ``jax.named_scope`` names of a round's phases in the compiled
#: program (the engine's round body and the apps' ``exchange``); an
#: instruction's ``op_name`` metadata names the phases it ran under
PHASES = ("propose", "schedule_stats", "schedule", "push", "exchange",
          "aggregate", "pull", "sched_update")

#: ``jax.monitoring`` duration events of JAX's compile pipeline → the
#: span each becomes while a Recorder is active
JIT_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jit.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower",
    "/jax/core/compile/backend_compile_duration": "jit.compile",
}


class Recorder:
    """Collects typed instants and strictly nested wall-clock spans.

    Timestamps are microseconds since ``origin_ns``, a
    ``time.perf_counter_ns()`` reading taken at construction, so a caller
    holding its own ``perf_counter`` readings can place these events on
    its clock.  Every span carries an ``id`` and the ``id`` of the span
    open around it (``parent``, ``None`` at the top); instants carry
    ``parent`` too."""

    def __init__(self, profiler: bool = False):
        self.profiler = profiler
        self.origin_ns = time.perf_counter_ns()
        self._events: List[dict] = []
        self._stack: List[dict] = []   # open spans (strict nesting)
        self._next_id = 0
        self._gc_open: Optional[tuple] = None   # collections never nest

    def _now_us(self) -> float:
        return (time.perf_counter_ns() - self.origin_ns) / 1e3

    def _parent(self) -> Optional[int]:
        return self._stack[-1]["id"] if self._stack else None

    # -- recording -----------------------------------------------------------

    def instant(self, name: str, **args) -> dict:
        """Record a point event (cache miss, rebalance, checkpoint …)."""
        ev = {"name": name, "ph": "i", "ts": self._now_us(),
              "parent": self._parent(), "args": args}
        self._events.append(ev)
        return ev

    def _open(self, name: str, args: dict) -> tuple:
        ev = {"name": name, "ph": "X", "ts": self._now_us(), "dur": 0.0,
              "id": self._next_id, "parent": self._parent(), "args": args}
        self._next_id += 1
        self._stack.append(ev)
        ann = None
        if self.profiler:
            import jax.profiler
            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
        return ev, ann

    def _close(self, opened: tuple) -> None:
        ev, ann = opened
        # off the stack first: a collection from here on nests outside it
        i = max(k for k, e in enumerate(self._stack) if e is ev)
        del self._stack[i]
        if ann is not None:
            ann.__exit__(None, None, None)
        ev["dur"] = max(0.0, self._now_us() - ev["ts"])
        self._events.append(ev)

    @contextlib.contextmanager
    def span(self, name: str, **args):
        """Record a wall-clock phase span.  Spans opened inside close
        first (context-manager discipline), so the export is strictly
        nested by construction."""
        opened = self._open(name, args)
        try:
            yield opened[0]
        finally:
            self._close(opened)

    def span_ended(self, name: str, seconds: float, **args) -> dict:
        """Record a span that has just ended and lasted ``seconds`` (an
        event reported after the fact, such as a compile).  It lies
        inside the span open now (its start is clamped to that span's),
        and holds whole any span recorded under the same parent that
        straddles its start, so the log stays strictly nested."""
        end = self._now_us()
        ts = end - seconds * 1e6
        parent = self._parent()
        if self._stack:
            ts = max(ts, self._stack[-1]["ts"])
        for ev in reversed(self._events):      # in the order they ended
            if ev["ts"] + ev.get("dur", 0.0) <= ts:
                break
            if ev["ph"] == "X" and ev["parent"] == parent:
                ts = min(ts, ev["ts"])
        ev = {"name": name, "ph": "X", "ts": ts, "dur": end - ts,
              "id": self._next_id, "parent": parent, "args": args}
        self._next_id += 1
        self._events.append(ev)
        return ev

    # -- the events an active Recorder takes from JAX and the collector ----

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        name = JIT_EVENTS.get(event)
        if name is not None and active() is self:
            self.span_ended(name, duration, fun=kw.get("fun_name", ""))

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start" and active() is self:
            self._gc_open = self._open("gc",
                                       {"generation": info["generation"]})
        elif phase == "stop" and self._gc_open is not None:
            opened, self._gc_open = self._gc_open, None
            opened[0]["args"]["collected"] = info["collected"]
            self._close(opened)

    # -- export --------------------------------------------------------------

    def mark(self) -> int:
        """A position in the event log: :meth:`events_since` returns what
        was recorded after it."""
        return len(self._events)

    def events_since(self, mark: int) -> List[dict]:
        return _sorted_events(self._events[mark:])

    def to_json_events(self) -> List[dict]:
        """The portable event list (instants + completed spans), sorted
        by start time — what :class:`~repro.obs.report.RunReport`
        carries and the JSONL/Chrome exports derive from."""
        return self.events_since(0)

    def write_jsonl(self, path: str) -> str:
        return write_jsonl(self.to_json_events(), path)

    def write_chrome_trace(self, path: str) -> str:
        return write_chrome_trace(self.to_json_events(), path)


def _sorted_events(events) -> List[dict]:
    return sorted((dict(ev) for ev in events),
                  key=lambda e: (e["ts"], -e.get("dur", 0.0)))


# ---------------------------------------------------------------------------
# The process's active Recorder
# ---------------------------------------------------------------------------

_ACTIVE: List[Recorder] = []


def active() -> Optional[Recorder]:
    """The Recorder made active by the innermost :func:`recording`, or
    ``None``."""
    return _ACTIVE[-1] if _ACTIVE else None


@contextlib.contextmanager
def recording(rec: Recorder):
    """Make ``rec`` the process's active Recorder for the block: the
    engine's spans and events fall back to it, and it also records JAX's
    compile pipeline (``jit.trace``/``jit.lower``/``jit.compile`` spans
    from ``jax.monitoring``) and Python garbage collections (``gc``
    spans).  Blocks nest; the innermost Recorder records."""
    import jax.monitoring
    _ACTIVE.append(rec)
    jax.monitoring.register_event_duration_secs_listener(rec._on_duration)
    gc.callbacks.append(rec._on_gc)
    try:
        yield rec
    finally:
        gc.callbacks.remove(rec._on_gc)
        jax.monitoring.unregister_event_duration_listener(rec._on_duration)
        _ACTIVE.remove(rec)


# ---------------------------------------------------------------------------
# Format helpers (usable on saved event lists too — launch/trace CLI)
# ---------------------------------------------------------------------------

def write_jsonl(events: List[dict], path: str) -> str:
    """One event dict per line — greppable, streamable."""
    with open(path, "w") as f:
        for ev in events:
            f.write(json.dumps(ev) + "\n")
    return path


def chrome_trace(events: List[dict], pid: int = 0, tid: int = 0) -> dict:
    """The Chrome trace-event JSON (``chrome://tracing`` / Perfetto):
    spans become complete ("X") events, instants stay instant ("i")
    events, timestamps/durations in microseconds; a span's ``id`` and
    ``parent`` ride its ``args``."""
    out = []
    for ev in events:
        args = dict(ev.get("args", {}))
        for k in ("id", "parent"):
            if ev.get(k) is not None:
                args[k] = ev[k]
        rec = {"name": ev["name"], "ph": ev.get("ph", "i"),
               "ts": ev["ts"], "pid": pid, "tid": tid,
               "cat": "strads", "args": args}
        if rec["ph"] == "X":
            rec["dur"] = ev.get("dur", 0.0)
        else:
            rec["s"] = "t"
        out.append(rec)
    return {"traceEvents": out, "displayTimeUnit": "ms"}


def write_chrome_trace(events: List[dict], path: str,
                       pid: int = 0, tid: int = 0) -> str:
    with open(path, "w") as f:
        json.dump(chrome_trace(events, pid=pid, tid=tid), f, indent=1)
    return path


def validate_spans(events: List[dict]) -> Optional[str]:
    """``None`` when every span has a non-negative duration, the span
    set is strictly nested (any two spans are disjoint or one contains
    the other), span ids are unique and each span lies inside its
    ``parent``; else a human-readable reason — the ``launch/trace
    --check`` predicate.  A parent outside the log (one ``execute``'s
    excerpt of a longer recording) and events saved before spans had ids
    are checked for nesting alone."""
    spans = [ev for ev in events if ev.get("ph") == "X"]
    by_id: dict = {}
    for ev in spans:
        if ev.get("dur", 0.0) < 0.0:
            return f"span {ev['name']!r} has negative duration {ev['dur']}"
        if ev.get("ts", 0.0) < 0.0:
            return f"span {ev['name']!r} starts before the run ({ev['ts']})"
        if ev.get("id") is not None:
            if ev["id"] in by_id:
                return f"span id {ev['id']} is used twice"
            by_id[ev["id"]] = ev
    for ev in spans:
        par = by_id.get(ev.get("parent"))
        if par is None:       # top level, or a parent outside an excerpt
            continue
        if ev["ts"] < par["ts"] or \
                ev["ts"] + ev["dur"] > par["ts"] + par["dur"]:
            return (f"span {ev['name']!r} lies outside its parent "
                    f"{par['name']!r}")
    spans = sorted(spans, key=lambda e: (e["ts"], -e["dur"]))
    stack: List[dict] = []
    for ev in spans:
        while stack and ev["ts"] >= stack[-1]["ts"] + stack[-1]["dur"]:
            stack.pop()
        if stack:
            parent = stack[-1]
            if ev["ts"] + ev["dur"] > parent["ts"] + parent["dur"]:
                return (f"span {ev['name']!r} overlaps its enclosing "
                        f"{parent['name']!r} without nesting inside it")
        stack.append(ev)
    return None
