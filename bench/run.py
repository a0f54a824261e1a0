#!/usr/bin/env python3
"""On-chip benchmark of the STRADS engine: one cell, one run.

    python3 bench/run.py --workload lda-nytimes.1chip --seed 7 \
        --seconds 10 --trace 0

A cell (``bench/workloads/<cell>.json``) names a configuration
(``bench/configs/<config>.json``), whose ``app`` names the adapter
(``bench/apps/<app>.py``) that builds the data from ``--seed``, the
engine and its state.  The run then

1. warms up two chunks of the cell's plan (``StradsEngine.execute`` with
   ``ExecutionPlan(executor="scan")``): the first from the seed, which
   the correctness check compares with the plain reference, and one
   resumed from its carry, the call every chunk of the window makes;
2. times a window of ``--seconds``: chunk after chunk, each resumed from
   the previous report's ``carry``, so every chunk runs the one compiled
   program; the chunk in flight when the window closes is finished and
   counted;
3. with ``--trace 1``, records the first chunks of the window (the
   workload's ``trace`` entry: how many) with the JAX profiler and
   reduces them (``bench/trace_reduce.py``) for the per-layer readers
   (``bench/metrics/<metric>.py``) that ``BENCHMARK.json`` lists;
4. frees the program's state and decides ``correct`` against the plain
   reference (``bench/reference/``), printing each number compared beside
   its limit on standard error;
5. prints one JSON object as the last line of standard output.

It exits nonzero, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

#: profiler annotations around the harness's own host work in the window
SPAN_DISPATCH = "bench.dispatch"
SPAN_BLOCK = "bench.block_until_ready"
SPAN_CARRY = "bench.carry"
SPAN_COUNTERS = "bench.counter_read"
SPANS = (SPAN_DISPATCH, SPAN_BLOCK, SPAN_CARRY, SPAN_COUNTERS)


class BenchError(RuntimeError):
    """The run cannot produce a result (wrong device, malformed cell)."""


def load_json(*parts: str) -> dict:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def load_cell(name: str) -> tuple[dict, dict]:
    """The workload file and the configuration file it names."""
    cell = load_json("workloads", f"{name}.json")
    return cell, load_json("configs", f"{cell['config']}.json")


def app_module(name: str):
    return importlib.import_module(f"bench.apps.{name}")


def metric_reader(name: str):
    return importlib.import_module(f"bench.metrics.{name}")


def peaks_for(kind: str) -> dict:
    table = load_json("peaks.json")
    if kind not in table:
        raise BenchError(f"no peaks for device kind {kind!r} in "
                         f"bench/peaks.json (known: {sorted(table)})")
    return table[kind]


class CompileLog:
    """Backend compilations seen through ``jax.monitoring``: one
    ``(perf_counter at the end, seconds)`` per compiled program (a
    persistent-cache read reports as one too), and the cache hits."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.events: list[tuple[float, float]] = []
        self.hits = 0
        self._jax = jax

        def on_duration(event, duration, **_):
            if event == self.EVENT:
                self.events.append((time.perf_counter(), float(duration)))

        def on_event(event, **_):
            if event == self.HIT:
                self.hits += 1

        self._cbs = (on_duration, on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def close(self):
        on_duration, on_event = self._cbs
        self._jax.monitoring.unregister_event_duration_listener(on_duration)
        self._jax.monitoring.unregister_event_listener(on_event)

    def between(self, t0: float, t1: float) -> list[float]:
        return [d for t, d in self.events if t0 <= t <= t1]


def _span(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def _device_bytes(devices, key: str) -> list:
    """``memory_stats()[key]`` per device; ``None`` where the backend
    reports none (the CPU)."""
    stats = [d.memory_stats() for d in devices]
    if any(s is None or key not in s for s in stats):
        return None
    return [int(s[key]) for s in stats]


def run_cell(name: str, cell: dict, config: dict, *, seed: int,
             seconds: float, trace: bool, spec: dict,
             t_start: float = T_START, on_chip: bool = True) -> dict:
    """One run of one cell; returns the result object (without printing).

    ``spec`` is ``BENCHMARK.json`` (which metrics apply to this cell).
    ``on_chip=False`` lets tests drive a tiny cell on the CPU; the result
    then carries CPU numbers and is never printed under a device metric.
    """
    import jax
    from repro.core import single_device_mesh, worker_mesh

    chips = int(cell["chips"])
    mesh = single_device_mesh() if chips == 1 else worker_mesh(chips)
    devices = list(mesh.devices.flat)
    app = app_module(config["app"])
    log = CompileLog()
    try:
        job = app.setup(config, cell, mesh, seed)
        # the warm chunk: the first chunk from the seed, through the
        # window's own call; the check compares it with the reference
        rep = job.run_chunk()
        jax.block_until_ready(rep.state)
        job.first_chunk_done(rep)
        job.take(rep)
        # a chunk resumed from a carry is a call of its own (its first
        # dispatch on the chip took ~10 ms more than later ones): warm it
        rep = job.run_chunk()
        jax.block_until_ready(rep.state)
        job.take(rep)
        gc.collect()
        in_use = _device_bytes(devices, "bytes_in_use")
        count0 = job.counters()
        t_setup_end = time.perf_counter()
        setup_s = t_setup_end - t_start
        compile_setup = log.between(t_start, t_setup_end)

        tdir, most = None, None
        if trace:
            # a traced window is the cell's first few chunks: the device
            # records every operation of every loop iteration, and its
            # buffer holds about five million
            most = int(cell["trace"]["chunks"])
            tdir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
        chunks = 0
        w0 = time.perf_counter()
        while True:
            with _span(SPAN_DISPATCH, trace):
                rep = job.run_chunk()
            with _span(SPAN_BLOCK, trace):
                jax.block_until_ready(rep.state)
            with _span(SPAN_CARRY, trace):
                job.take(rep)
            chunks += 1
            if time.perf_counter() - w0 >= seconds or chunks == most:
                break
        window_s = time.perf_counter() - w0
        with _span(SPAN_COUNTERS, trace):
            count1 = job.counters()
        w1 = time.perf_counter()
        if trace:
            jax.profiler.stop_trace()
        recompiles = len(log.between(w0, w1))
        peak = _device_bytes(devices, "peak_bytes_in_use")
        rounds = chunks * job.rounds_per_chunk
        window_counters = {k: count1[k] - count0[k] for k in count1}
        updates = window_counters["updates"]

        checks = job.check()       # frees the program's state first
    finally:
        log.close()

    correct = all(c["value"] <= c["limit"] for c in checks)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": max(peak) if peak else None}
    e2e = {
        "updates_per_s": {"value": updates / window_s,
                          "unit": "updates/s"},
        "setup_s": {"value": setup_s, "unit": "s"},
    }
    info = {"chunks": chunks, "rounds": rounds, "updates": updates,
            "window_s": window_s, "recompiles_in_window": recompiles,
            "bytes_in_use_at_window_start": in_use,
            "peak_bytes_in_use": peak,
            "compile_s_setup": sum(compile_setup),
            "programs_compiled_setup": len(compile_setup),
            "persistent_cache_hits": log.hits}
    result = {"correct": correct, "attempted": len(checks),
              "failed": sum(c["value"] > c["limit"] for c in checks)}
    if trace:
        from bench import trace_reduce
        try:
            tr = trace_reduce.reduce_dir(tdir, num_devices=len(devices),
                                         spans=SPANS)
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        # what a per-layer reader sees: the cell, the job (FLOPs from
        # shapes), the traced window's counts and the reduced trace
        ctx = types.SimpleNamespace(
            cell=name, config=config, traffic=cell["traffic"], job=job,
            chips=len(devices), peaks=peaks_for(dev.device_kind)
            if on_chip else None, window_s=window_s, rounds=rounds,
            updates=updates, counters=window_counters,
            compile_setup_s=sum(compile_setup),
            recompiles_in_window=recompiles, trace=tr)
        metrics = {}
        for m in spec["per_layer"]:
            if "workloads" in m and name not in m["workloads"]:
                continue
            value = metric_reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = tr.busy_s
        device["window_s"] = tr.window_s
        result["metrics"] = metrics
        result["breakdown"] = tr.breakdown()
        info["trace"] = tr.summary()
    else:
        result["metrics"] = {m["name"]: e2e[m["name"]]
                             for m in spec["end_to_end"]
                             if name in m.get("workloads", [name])}
    result["device"] = device
    result["info"] = info
    result["checks"] = {c["name"]: {"value": c["value"],
                                    "limit": c["limit"]} for c in checks}
    return result


def use_checkout_cache() -> str:
    """JAX's persistent compilation cache in the checkout, at a fixed
    path, holding every program (whatever ``JAX_COMPILATION_CACHE_DIR``
    says: the two sides of a comparison must share no cache).  No size
    limit: evicting under a limit made cache writes fail on the chip
    machine."""
    import jax
    path = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = load_json(os.pardir, "BENCHMARK.json")
    cell, config = load_cell(args.workload)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"bench: needs a TPU, but JAX found {len(devices)} "
              f"{dev.platform} device(s) ({dev.device_kind}); nothing was "
              f"run", file=sys.stderr)
        return 2
    if len(devices) < int(cell["chips"]):
        print(f"bench: {args.workload} needs {cell['chips']} TPU chips, "
              f"but JAX found {len(devices)} ({dev.device_kind})",
              file=sys.stderr)
        return 2
    peaks_for(dev.device_kind)
    use_checkout_cache()

    result = run_cell(args.workload, cell, config, seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace),
                      spec=spec)
    info = result.pop("info")
    checks = result.pop("checks")
    print("bench info: " + json.dumps(info), file=sys.stderr)
    for k, c in checks.items():
        print(f"check {k} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    result["checks"] = checks            # last key: numbers beside limits
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        sys.exit(2)
