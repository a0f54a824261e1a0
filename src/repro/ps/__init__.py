"""Bounded-staleness parameter-server subsystem (SSP executor).

Layers, bottom up: ``server`` (server-/worker-resident classification of
the state over ``core/kvstore``, vector clocks), ``cache`` (worker-local
stale caches + the SSP consistency gate), ``ssp`` (the scanned
bounded-staleness executor behind ``StradsEngine.execute``), ``telemetry``
(staleness histograms, push/pull byte accounting).
"""
from .cache import StaleCache
from .server import ParameterServer, init_clocks, min_clock, tick
from .ssp import SSPCarry, rounds_per_step, ssp_fn
from .telemetry import SSPTelemetry

__all__ = [
    "StaleCache", "ParameterServer", "init_clocks", "min_clock", "tick",
    "SSPCarry", "rounds_per_step", "ssp_fn", "SSPTelemetry",
]
