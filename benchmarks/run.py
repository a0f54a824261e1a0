"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only lasso,mf,...]

Prints ``name,us_per_call,derived`` CSV rows (plus writes JSON payloads to
benchmarks/results/).  The roofline/dry-run tables render from the cached
dry-run artifacts if present (run launch/dryrun.py --all to regenerate).
"""
from __future__ import annotations

import argparse
import sys
import traceback

from repro.launch.cache import enable_compile_cache

from . import (bench_kernels, bench_lasso, bench_lda, bench_memory,
               bench_mf, bench_obs, bench_part, bench_pipeline,
               bench_scaling, bench_sched, bench_serve, bench_ssp,
               bench_stream)

BENCHES = {
    "lasso": bench_lasso,       # Fig 8/9 right
    "mf": bench_mf,             # Fig 8/9 center
    "lda": bench_lda,           # Fig 5 + Fig 8/9 left
    "memory": bench_memory,     # Fig 3
    "scaling": bench_scaling,   # Fig 10
    "pipeline": bench_pipeline,  # loop vs scan vs pipelined executor
    "ssp": bench_ssp,           # bounded staleness vs BSP (repro.ps)
    "sched": bench_sched,       # scheduler-policy ρ × U′ sweep (repro.sched)
    "part": bench_part,         # partition-policy static vs load_balanced
    "kernels": bench_kernels,   # kernel backend reference vs pallas
    "obs": bench_obs,           # telemetry overhead off/counters/trace
    "serve": bench_serve,       # serve-only vs serve-while-train (repro.serve)
    "stream": bench_stream,     # static vs streaming ingest (repro.stream)
}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale-ish sizes (slower)")
    ap.add_argument("--only", default="",
                    help="comma-separated subset of "
                         f"{','.join(BENCHES)},roofline")
    args = ap.parse_args(argv)
    only = set(args.only.split(",")) if args.only else None
    if only:
        # a typo'd name used to run nothing and exit 0 — fail loudly
        unknown = only - set(BENCHES) - {"roofline"}
        if unknown:
            ap.error(f"unknown benchmark name(s) {sorted(unknown)}; "
                     f"valid: {sorted(BENCHES) + ['roofline']}")

    enable_compile_cache()
    print("name,us_per_call,derived")
    failed = []
    for name, mod in BENCHES.items():
        if only and name not in only:
            continue
        try:
            out = mod.run(quick=not args.full)
            for row in mod.rows(out):
                print(f"{row[0]},{row[1]:.1f},{row[2]}")
            # benches may expose extra summary lines (e.g. the resolved
            # KernelSpec/backend dicts from bench_kernels)
            if hasattr(mod, "summary"):
                for line in mod.summary(out):
                    print(line)
        except Exception:
            traceback.print_exc()
            failed.append(name)

    if only is None or "roofline" in only:
        try:
            from . import roofline_report
            rows = roofline_report.load("baseline")
            ok = sum(1 for r in rows if "roofline" in r)
            sk = sum(1 for r in rows if "skipped" in r)
            print(f"roofline/dryrun_results,0.0,{ok}")
            print(f"roofline/dryrun_skipped,0.0,{sk}")
        except Exception:
            traceback.print_exc()
            failed.append("roofline")

    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
