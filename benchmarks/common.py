"""Shared benchmark utilities: result I/O, subprocess runner for
multi-device benches (the parent process must keep 1 CPU device).

Every timing these benches record is a CPU timing (XLA's CPU backend,
forced host devices, Pallas in interpret mode), never a device metric."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RESULTS = os.path.join(ROOT, "benchmarks", "results")


def save(name: str, payload) -> str:
    os.makedirs(RESULTS, exist_ok=True)
    path = os.path.join(RESULTS, f"{name}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    return path


def run_sub(code: str, devices: int = 4, timeout: int = 540) -> str:
    """Run ``code`` in a subprocess on ``devices`` forced host CPU
    devices; returns stdout (the child prints a JSON payload on its last
    line).  The child is pinned to the CPU (``JAX_PLATFORMS=cpu``), so
    on a machine with a chip it never contends with its parent for the
    device: what it records is a CPU record."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    if out.returncode != 0:
        raise RuntimeError(out.stderr[-4000:])
    return out.stdout


class timer:
    def __enter__(self):
        self.t0 = time.time()
        return self

    def __exit__(self, *a):
        self.s = time.time() - self.t0
        return False
