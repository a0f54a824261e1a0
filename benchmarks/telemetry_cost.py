#!/usr/bin/env python3
"""What telemetry costs the chip, and where set-up goes.

    python3 benchmarks/telemetry_cost.py --windows 6 --seconds 10

One process runs the benchmark cell ``lda-nytimes.1chip`` (its workload
file, corpus and engine from ``bench/``) and

1. times its set-up under an active :class:`repro.obs.Recorder`:
   ``import``, ``backend_start`` (the first ``jax.devices()``), ``setup``
   (``bench/apps/lda.py``'s job: the corpus, then the program's own
   ``shard_data`` and ``init_state`` spans) and ``warm`` (one chunk of
   each mode below, from the seed and resumed, with the program's
   ``execute`` and ``jit.*`` spans inside); it prints the seconds of every
   span at the top and their share of the whole set-up, and the seconds
   of every span by name;
2. then, with no Recorder active, runs ``--windows`` windows of
   ``--seconds`` in each of two modes, alternating: telemetry off (the
   benchmark's own plan) and ``TelemetrySpec(kind="trace")`` with the
   profiler off (device counters, a Recorder per ``execute``, the phase
   scopes).  Each chunk resumes from the previous carry, as the
   benchmark's window does; a window's device counters start at its
   first chunk.  Per window: updates/s (tokens sampled, counted on the
   host as the benchmark counts them), the host time of each ``execute``
   call until it returns, and under telemetry the device's ``updated``
   and ``visited`` counters.

It prints one JSON object per window, then a summary object last: the
median updates/s per mode and the relative cost of telemetry.
``--tiny`` runs the cell's tiny stand-in, for a run on the CPU.
"""
from __future__ import annotations

import time

T_START = time.perf_counter_ns()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

CELL = "lda-nytimes.1chip"


def setup_spans(rec, total_us: float) -> dict:
    """Seconds of each top-level span of the set-up and their share of
    the whole set-up, and the seconds of every span by name."""
    spans = [e for e in rec.to_json_events() if e["ph"] == "X"]

    def by_name(evs):
        out: dict = {}
        for e in evs:
            out[e["name"]] = out.get(e["name"], 0.0) + e["dur"] / 1e6
        return out
    top = [e for e in spans if e["parent"] is None]
    return {"setup_s": total_us / 1e6, "spans_s": by_name(top),
            "covered_share": sum(e["dur"] for e in top) / total_us,
            "all_spans_s": by_name(spans)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--windows", type=int, default=6)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=2 ** 31 + 17)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)

    from repro.obs import Recorder, recording      # imports JAX
    rec = Recorder()
    rec.origin_ns = T_START          # the set-up starts with the process
    with recording(rec):
        rec.span_ended("import", (time.perf_counter_ns() - T_START) / 1e9)
        with rec.span("import"):
            import jax
            from bench import run
            from repro.core import single_device_mesh
            from repro.obs import TelemetrySpec
        with rec.span("backend_start"):
            dev = jax.devices()[0]
        cell, config = run.load_cell(CELL)
        if args.tiny:
            small = cell["tiny"]
            cell = dict(cell, traffic=dict(cell["traffic"],
                                           **small["traffic"]))
            config = dict(config, **small["config"])
        with rec.span("setup"):
            job = run.app_module(config["app"]).setup(
                config, cell, single_device_mesh(), args.seed)
        spec = TelemetrySpec(kind="trace")
        off = job.plan
        plans = {"off": off,
                 "trace": lambda n: dataclasses.replace(off(n),
                                                        telemetry=spec)}

        def chunk(mode):
            """One chunk of ``mode``, resumed from the carry; the host
            seconds until ``execute`` returns."""
            if mode == "off" and job.carry is not None:
                job.carry = dataclasses.replace(job.carry, obs=None)
            job.plan = plans[mode]
            t = time.perf_counter()
            rep = job.run_chunk()
            host = time.perf_counter() - t
            jax.block_until_ready(rep.state)
            job.take(rep)
            return rep, host

        with rec.span("warm"):
            for mode in ("off", "off", "trace", "trace"):
                chunk(mode)
    setup = setup_spans(rec, (time.perf_counter_ns() - T_START) / 1e3)
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform,
                      **setup}), flush=True)

    rates: dict = {"off": [], "trace": []}
    for i in range(2 * args.windows):
        mode = ("off", "trace")[i % 2]
        job.carry = dataclasses.replace(job.carry, obs=None)
        c0 = job.counters()
        hosts = []
        w0 = time.perf_counter()
        while True:
            rep, host = chunk(mode)
            hosts.append(host)
            if time.perf_counter() - w0 >= args.seconds:
                break
        window = time.perf_counter() - w0
        updates = job.counters()["updates"] - c0["updates"]
        line = {"mode": mode, "chunks": len(hosts), "window_s": window,
                "updates": updates, "updates_per_s": updates / window,
                "execute_host_ms_mean": 1e3 * statistics.fmean(hosts),
                "execute_host_ms_max": 1e3 * max(hosts)}
        if mode == "trace":
            c = rep.telemetry.counters
            line.update(updated=c["updated"], visited=c["visited"],
                        events=len(rep.telemetry.events))
        rates[mode].append(line["updates_per_s"])
        print(json.dumps(line), flush=True)
    rate_off, rate_on = (statistics.median(rates[m])
                         for m in ("off", "trace"))
    print(json.dumps({"updates_per_s_off": rate_off,
                      "updates_per_s_trace": rate_on,
                      "trace_cost": 1.0 - rate_on / rate_off,
                      "windows": args.windows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
