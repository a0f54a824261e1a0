"""Production mesh builders.

Functions, not module-level constants — importing this module never
touches jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and only then calls these.
"""
from __future__ import annotations

import jax

from ..core.compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 (data, model) single pod; 2×16×16 (pod, data, model) for the
    two-pod 512-chip deployment."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(devices=None):
    """Whatever devices exist, as a (data, model) mesh — used by CPU
    integration tests (1 device → trivial mesh, 8 fake devices → 4×2)."""
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    model = 1
    for m in (4, 2, 1):
        if n % m == 0:
            model = m
            break
    return make_mesh((n // model, model), ("data", "model"))
