#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, at a cell's own
size, on the chip: the program, the control, and planted faults.

    python3 bench/control.py --workload lda-nytimes.1chip --what program \
        --seeds 1,2,3 --seconds 10
    python3 bench/control.py --workload lda-nytimes.1chip --what control \
        --seeds 1,2,3 --window-rounds 7
    python3 bench/control.py --workload lda-nytimes.1chip \
        --what fault:half_batch --seeds 1,2,3 --seconds 2

``program`` runs the cell as ``bench/run.py`` does and reads its numbers
(the lower readings).  ``control`` puts the plain reference, computed in
bfloat16, in the program's place (``bench/apps/<app>.py``'s ``control``;
``--window-rounds`` as many rounds as a window runs).  ``fault:<name>``
runs the cell with one of its app adapter's ``FAULTS`` planted
(``bench/faults.py``).  One process
reads every seed; one JSON line per seed.  The benchmark's runs never
call this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(
    __file__)))]

from bench import run  # noqa: E402  (puts the program on the path)


def readings(name: str, what: str, seed: int, *, seconds: float,
             window_rounds: int, on_chip: bool = True,
             cell: dict | None = None, config: dict | None = None) -> dict:
    """The numbers ``correct`` compares for one seed, with their limits."""
    from repro.core import single_device_mesh
    from bench import faults
    if cell is None:
        cell, config = run.load_cell(name)
    spec = run.load_json(os.pardir, "BENCHMARK.json")
    if what == "control":
        nums = run.app_module(config["app"]).control(
            config, cell, single_device_mesh(), seed, window_rounds)
        # a number the cell does not compare is read with no limit
        return {k: {"value": v, "limit": cell["limits"].get(k)}
                for k, v in nums.items()}
    kw = dict(seed=seed, seconds=seconds, trace=False, spec=spec,
              on_chip=on_chip)
    if what == "program":
        return run.run_cell(name, cell, config, **kw)["checks"]
    fault = what.split(":", 1)[1]
    with faults.planted(config["app"], fault):
        return run.run_cell(name, cell, config, **kw)["checks"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--what", required=True,
                    help="program | control | fault:<name>")
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--window-rounds", type=int, default=0)
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    run.use_checkout_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            out = readings(args.workload, args.what, seed,
                           seconds=args.seconds,
                           window_rounds=args.window_rounds)
        except Exception as e:          # a control that crashes has failed
            out = {"error": f"{type(e).__name__}: {e}"}
        print(json.dumps({"workload": args.workload, "what": args.what,
                          "seed": seed, "checks": out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
