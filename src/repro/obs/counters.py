"""Device-side telemetry counters, carried through every executor's scan.

One small int32 pytree rides the executor carry (``EngineCarry.obs`` /
``SSPCarry.obs``) and is folded forward once per round, entirely from the
round's *schedule* — never from model state or the PRNG stream, so an
instrumented run is **bit-identical** to an uninstrumented one (the
telemetry-on ≡ telemetry-off property ``tests/test_obs.py`` asserts on
every executor × app).

Counters
--------
``rounds``     (phase_period,) — rounds executed per static phase; the
               total must equal the rounds the plan ran (the hypothesis
               invariant: ``Σ rounds == R``).
``proposed``/``accepted``/``killed``
               the ρ-dependency-filter ledger (paper §3.3): candidates
               the scheduler proposed (U′ per round for the dynamic
               kinds), survivors of the dependency filter (for masked
               schedules the mask popcount; for dense schedules the
               static schedule width), and filtered casualties —
               ``accepted + killed == proposed`` by construction, and the
               property test keeps it that way.
``visited``/``updated``
               from the app's optional ``obs_counts(data, sched, phase)``
               hook, which reads only the data and the schedule: the
               items a round's push steps over and those it updates (LDA:
               valid tokens scanned, tokens sampled).  Each is a
               two-word count (``[high, low]``, low < 2**30) so it cannot
               wrap on a long run; 0 for apps without the hook.
``forwarded``  the hook's optional third count: reads a push serves from
               on-chip memory instead of HBM (LDA: the B and D row
               groups the Gibbs kernel's ring hands on, computed from the
               data and the ring depth); a two-word count, 0 where the
               hook gives two counts.

The SSP staleness histogram (``staleness_init``/``observe_read``) lives
here too — it is the same pattern (an int32 pytree in the scan carry,
asserted over what the compiled program actually did), generalized from
the original ``repro/ps/telemetry.py`` device half, which now re-exports
these for its summaries.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np


#: the low word of a two-word count holds this much
_LOW = 1 << 30


def init_counters(phase_period: int) -> Dict[str, jnp.ndarray]:
    """A fresh counter pytree for an app whose phases cycle with period
    ``phase_period`` (1 = phaseless)."""
    return {"rounds": jnp.zeros((phase_period,), jnp.int32),
            "proposed": jnp.int32(0),
            "accepted": jnp.int32(0),
            "killed": jnp.int32(0),
            "visited": jnp.zeros((2,), jnp.int32),
            "updated": jnp.zeros((2,), jnp.int32),
            "forwarded": jnp.zeros((2,), jnp.int32)}


def _add_wide(count, x):
    """``count`` (``[high, low]``) plus ``x`` (< 2**30)."""
    low = count[1] + jnp.asarray(x, jnp.int32)
    return jnp.stack([count[0] + low // _LOW, low % _LOW])


def observe_round(counters: Dict[str, jnp.ndarray], sched: Any,
                  phase: int, num_candidates: int = 0,
                  counts: Optional[tuple] = None) -> Dict[str, jnp.ndarray]:
    """Fold one executed round's schedule into the counters (traced —
    runs inside the executor's scan).

    Boolean leaves of the schedule pytree are keep-masks (the
    ρ-dependency filter's survivors): their popcount is the round's
    accepted count, ``num_candidates`` (the scheduler's static U′; 0 for
    policies without a proposal pool) the proposed count, and the
    difference the killed count.  Schedules without masks (rotation,
    MF rank blocks) contribute their static width and keep the
    filter ledger balanced with ``proposed == accepted``.  ``counts`` is
    the app hook's ``(visited, updated)`` for the round, if it has one.
    """
    c = dict(counters)
    c["rounds"] = c["rounds"].at[phase].add(1)
    leaves = jax.tree_util.tree_leaves(sched)
    masks = [x for x in leaves
             if jnp.asarray(x).dtype == jnp.bool_]
    if masks:
        acc = sum(jnp.sum(m.astype(jnp.int32)) for m in masks)
        prop = (jnp.int32(num_candidates) if num_candidates else acc)
        c["accepted"] = c["accepted"] + acc
        c["proposed"] = c["proposed"] + prop
        c["killed"] = c["killed"] + (prop - acc)
    else:
        width = int(sum(np.prod(jnp.shape(x), dtype=int)
                        for x in leaves))
        # no filter ran: the ledger stays balanced at proposed==accepted
        c["proposed"] = c["proposed"] + jnp.int32(width)
        c["accepted"] = c["accepted"] + jnp.int32(width)
    if counts is not None:
        c["visited"] = _add_wide(c["visited"], counts[0])
        c["updated"] = _add_wide(c["updated"], counts[1])
        if len(counts) > 2:
            c["forwarded"] = _add_wide(c["forwarded"], counts[2])
    return c


def _wide(count) -> int:
    high, low = (int(v) for v in np.asarray(count))
    return high * _LOW + low


def summarize_counters(counters: Optional[Dict[str, Any]]) -> dict:
    """Host ints out of the device counter pytree (empty dict for an
    uninstrumented run).  This reads the device: it waits for the run."""
    if counters is None:
        return {}
    per_phase = [int(v) for v in np.asarray(counters["rounds"])]
    return {"rounds": int(sum(per_phase)),
            "rounds_per_phase": per_phase,
            "proposed": int(counters["proposed"]),
            "accepted": int(counters["accepted"]),
            "killed": int(counters["killed"]),
            "visited": _wide(counters["visited"]),
            "updated": _wide(counters["updated"]),
            "forwarded": _wide(counters["forwarded"])}


# ---------------------------------------------------------------------------
# SSP staleness histogram (relocated device half of repro/ps/telemetry.py)
# ---------------------------------------------------------------------------

def staleness_init(staleness: int) -> Dict[str, jnp.ndarray]:
    """Scan-carried staleness telemetry: histogram over observed read
    staleness (bins 0..s) and the running max."""
    return {"hist": jnp.zeros((staleness + 1,), jnp.int32),
            "max_staleness": jnp.int32(0)}


def observe_read(telem: Dict[str, jnp.ndarray], clock,
                 cache_clock) -> Dict[str, jnp.ndarray]:
    """Record one SSP round's read: how stale was the cache it was
    served from?  (``clock`` and ``cache_clock`` are device scalars.)"""
    st = jnp.asarray(clock, jnp.int32) - jnp.asarray(cache_clock,
                                                     jnp.int32)
    return {"hist": telem["hist"].at[st].add(1),
            "max_staleness": jnp.maximum(telem["max_staleness"], st)}
