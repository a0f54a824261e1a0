"""From a JAX profiler trace (``.xplane.pb``) to the numbers the per-layer
readers take: device busy time, kernel time, collective exposure, and
idle gaps labelled by the harness's host spans.

``jax.profiler.ProfileData`` reads the file.  A device is a plane named
``/device:TPU:<i>``; its operations are the events of its ``XLA Ops``
line, and the programs it ran those of its ``XLA Modules`` line.  The
harness's own host spans are ``TraceAnnotation`` events on the host plane
(``/host:CPU``) whose names it passes in.  All timestamps are on the
trace's one clock.

The window is the interval from the start of the first harness span to
the end of the last: that is the timed window.  Busy time is the union of
the operation and program intervals inside it, per device; idle is the
rest.  The programs count because a device can lose a block of its
operation events without saying so (a one-round LDA trace on a TPU v5e
once lost 32,728 of its 1,900,578, its enclosing loop's among them), and
a program's event still covers the time its lost operations ran.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: the event a device writes when its trace buffer overflowed
DROPPED = "Trace Buffers Dropped"
#: operation names that move data between chips
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all|ppermute|"
                        r"\bsend\b|\brecv\b", re.IGNORECASE)


def union(intervals) -> list:
    """Merged, sorted, disjoint intervals."""
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals) -> float:
    return float(sum(b - a for a, b in intervals))


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def subtract(a_list, b_list) -> list:
    """Parts of the disjoint sorted intervals ``a_list`` that no interval
    of the disjoint sorted ``b_list`` covers."""
    out, j = [], 0
    for a, b in a_list:
        cur = a
        while j < len(b_list) and b_list[j][1] <= cur:
            j += 1
        k = j
        while k < len(b_list) and b_list[k][0] < b:
            s, e = b_list[k]
            if s > cur:
                out.append((cur, s))
            cur = max(cur, e)
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


class Trace:
    """One traced window, reduced.  Times in seconds."""

    def __init__(self, ops: dict, spans: list, dropped_at: float = None,
                 programs: dict = None):
        #: device index → [(name, start_s, end_s)] of operations
        self.ops = ops
        #: device index → [(name, start_s, end_s)] of programs run
        self.programs = programs or {}
        #: [(name, start_s, end_s)] of the harness's host spans
        self.spans = sorted(spans, key=lambda s: s[1])
        if not self.spans:
            raise ValueError("the trace holds none of the harness's spans")
        self.lo = self.spans[0][1]
        self.hi = max(s[2] for s in self.spans)
        #: where a device stopped recording (its buffer full), if it did:
        #: the window ends there, so that nothing unrecorded reads idle
        self.dropped_at = dropped_at
        if dropped_at is not None:
            self.hi = min(self.hi, dropped_at)
        self.window_s = self.hi - self.lo
        self.busy = {d: union(clip([(a, b) for _, a, b in
                                    evs + self.programs.get(d, [])],
                                   self.lo, self.hi))
                     for d, evs in ops.items()}
        self.busy_s = (sum(total(u) for u in self.busy.values())
                       / max(len(self.busy), 1))

    # -- readings --------------------------------------------------------------

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_time(self, pattern) -> tuple[float, int]:
        """Summed device time (mean over devices) and calls per device of
        the operations whose name matches ``pattern`` in the window."""
        rx = re.compile(pattern)
        secs = calls = 0.0
        for evs in self.ops.values():
            for name, a, b in evs:
                if rx.search(name) and b > self.lo and a < self.hi:
                    secs += b - a
                    calls += 1
        n = max(len(self.ops), 1)
        return secs / n, calls / n

    def collective_exposed_s(self) -> float:
        """Per device, the time in which a collective runs and no other
        operation does; the mean over devices."""
        out = []
        for evs in self.ops.values():
            coll = union(clip([(a, b) for n, a, b in evs
                               if COLLECTIVE.search(n)], self.lo, self.hi))
            comp = union(clip([(a, b) for n, a, b in evs
                               if not COLLECTIVE.search(n)],
                              self.lo, self.hi))
            out.append(total(subtract(coll, comp)))
        return sum(out) / max(len(out), 1)

    def idle_gaps(self, device: int | None = None) -> list:
        """(start, end) of each idle interval of one device (the first)
        inside the window."""
        if not self.busy:
            return []
        d = min(self.busy) if device is None else device
        return subtract([(self.lo, self.hi)], self.busy[d])

    def label(self, a: float, b: float) -> str:
        """The host span that covers most of [a, b]."""
        best, cover = "outside any span", 0.0
        for name, s, e in self.spans:
            c = min(b, e) - max(a, s)
            if c > cover:
                best, cover = name, c
        return best

    def self_times(self) -> dict:
        """Per operation (by :func:`short_name`), the device time no
        nested operation covers (a loop's events hold its body's), inside
        the window; the mean over devices."""
        out: dict = {}
        n = max(len(self.ops), 1)
        for evs in self.ops.values():
            stack: list = []        # [end, name, self seconds]

            def close(entry):
                out[entry[1]] = out.get(entry[1], 0.0) + entry[2] / n

            for name, a, b in sorted(clip_events(evs, self.lo, self.hi),
                                     key=lambda e: (e[1], -e[2])):
                while stack and stack[-1][0] <= a:
                    close(stack.pop())
                if stack:
                    stack[-1][2] -= b - a
                stack.append([b, short_name(name), b - a])
            while stack:
                close(stack.pop())
        return out

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.self_times().items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_gaps(), key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[self.label(a, b), b - a] for a, b in gaps]}

    def summary(self) -> dict:
        return {"window_s": self.window_s, "busy_s": self.busy_s,
                "devices": len(self.ops), "dropped_at": self.dropped_at,
                "ops": sum(len(v) for v in self.ops.values()),
                "programs": sum(len(v) for v in self.programs.values()),
                "spans": len(self.spans)}


def clip_events(evs, lo: float, hi: float) -> list:
    return [(n, max(a, lo), min(b, hi)) for n, a, b in evs
            if b > lo and a < hi]


def short_name(name: str) -> str:
    """An operation's HLO text up to its result type:
    ``%fusion.46 = f32[512,16384]``."""
    head, eq, rest = name.partition(" = ")
    if not eq:
        return name[:80]
    if rest.startswith("("):
        return f"{head} = (tuple)"
    return f"{head} = {rest.split('{', 1)[0].split(' ', 1)[0]}"


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def from_profile(pd, num_devices: int, spans: tuple) -> Trace:
    ops: dict = {}
    programs: dict = {}
    host: list = []
    drops: list = []
    want = set(spans)
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) < num_devices:
            d = int(m.group(1))
            evs = ops.setdefault(d, [])
            progs = programs.setdefault(d, [])
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    (evs if line.name == OPS_LINE else progs).extend(
                        (e.name, e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events)
                else:
                    drops.extend(e.start_ns * 1e-9 for e in line.events
                                 if e.name == DROPPED)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                            for e in line.events if e.name in want)
    return Trace(ops, host, min(drops) if drops else None, programs)


def reduce_dir(trace_dir: str, *, num_devices: int, spans: tuple) -> Trace:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(find_xplane(trace_dir)),
                        num_devices, spans)
