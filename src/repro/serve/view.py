"""The serving read path: :class:`ModelView`.

A ModelView is the bridge between the training loop and the request
frontend: training *publishes* committed state at its flush/chunk
boundaries (the only points where state is host-synced — the same
boundaries the partitioner and checkpointer already use), and serving
*reads* a view of the model whose consistency the
:class:`~repro.serve.spec.ServeSpec` declares:

* ``kind="stale"`` reuses the SSP read machinery verbatim: the
  server-resident leaves (the replicated KVStore half,
  :meth:`~repro.ps.server.ParameterServer.snapshot`) are served through
  a :class:`~repro.ps.cache.StaleCache`, refreshed lazily under the SSP
  gate ``clock − cache.clock ≤ max_staleness``; the worker-resident
  leaves come from the live state at the boundary (the read-my-writes
  half of SSP).  A read is therefore exactly as consistent as a worker's
  own training read — bounded staleness, verified at read time.
* ``kind="snapshot"`` pins the *entire* state (copied) at each publish,
  so the view is internally consistent (every leaf from the same clock)
  and stays valid across training chunks even when the executor donates
  the state buffers.

Every read is logged as ``{"t", "clock", "staleness"}`` — the measured
staleness-at-read is the quantity the acceptance bar (and the hypothesis
property test) is stated over, not an assumption.

Reads never write: the view holds copies (or boundary-scoped references)
of state and touches neither the training PRNG stream nor the engine
carry, which is what makes ``serve_while_training`` bit-identical to an
unserved ``execute()``.
"""
from __future__ import annotations

from typing import Any, List, Optional

import jax
import jax.numpy as jnp

from ..ps.cache import StaleCache
from ..ps.server import ParameterServer
from .spec import ServeSpec


class StaleReadError(RuntimeError):
    """A read was attempted that the ServeSpec's consistency contract
    cannot serve (nothing published yet, or the staleness gate failed to
    hold — the latter indicates a bug, since publish refreshes under the
    gate)."""


def _copy_tree(tree):
    # Served values must survive the executor donating the training
    # state's buffers on the next chunk, so pins/caches hold copies.
    return jax.tree.map(lambda x: jnp.array(x, copy=True), tree)


class ModelView:
    """A bounded-staleness view of an engine's model state.

    ``publish(state, t)`` is called by the training side at every
    flush/chunk boundary with the committed state and the round clock;
    ``read()`` returns ``(state_view, staleness_at_read)`` for the query
    programs.  The view never mutates what it is given.
    """

    def __init__(self, engine, spec: ServeSpec,
                 recorder: Optional[Any] = None):
        if not isinstance(spec, ServeSpec):
            raise TypeError(f"ModelView wants a ServeSpec; got "
                            f"{type(spec).__name__}")
        self.engine = engine
        self.spec = spec
        self.recorder = recorder
        self._server: Optional[ParameterServer] = None
        self._cache: Optional[StaleCache] = None   # stale: server leaves
        self._state = None                         # stale: boundary state
        self._pinned = None                        # snapshot: full state
        self._pinned_clock = 0
        self._clock = 0          # committed training rounds at last publish
        self.reads: List[dict] = []

    # -- the training side ---------------------------------------------------

    def publish(self, state, t: int) -> None:
        """Make the state committed through round ``t`` servable.  Must
        be called at a host boundary (state live on this side of any
        donation)."""
        self._clock = int(t)
        if self.spec.kind == "snapshot":
            self._pinned = _copy_tree(state)
            self._pinned_clock = self._clock
            if self.recorder is not None:
                self.recorder.instant("serve_pin", t=self._clock)
            return
        if self._server is None:
            app = self.engine.app
            self._server = ParameterServer.from_state(
                self.engine.mesh, state, app.state_specs(),
                roles=app.var_roles())
        self._state = state
        if self._cache is None or not bool(
                self._cache.fresh_enough(self._clock,
                                         self.spec.max_staleness)):
            # the SSP gate would be violated at this clock: refresh the
            # cache from the server-resident leaves (the "pull")
            self._cache = StaleCache(
                values=_copy_tree(self._server.snapshot(state)),
                clock=jnp.asarray(self._clock, jnp.int32))
            if self.recorder is not None:
                self.recorder.instant("serve_refresh", t=self._clock,
                                      nbytes=self._server.shared_nbytes())

    # -- the serving side ----------------------------------------------------

    @property
    def clock(self) -> int:
        """Committed training rounds as of the last publish."""
        return self._clock

    def read(self):
        """Serve one read: returns ``(state_view, staleness_at_read)``
        and logs the measured staleness.  ``stale`` merges the (possibly
        stale) server cache over the boundary state; ``snapshot``
        returns the pinned copy."""
        if self.spec.kind == "snapshot":
            if self._pinned is None:
                raise StaleReadError("read before the first publish — "
                                     "nothing is pinned yet")
            staleness = self._clock - self._pinned_clock
            view = self._pinned
        else:
            if self._cache is None:
                raise StaleReadError("read before the first publish — "
                                     "the serving cache is empty")
            staleness = int(self._cache.staleness(self._clock))
            if staleness > self.spec.max_staleness:
                raise StaleReadError(
                    f"staleness-at-read {staleness} exceeds the spec "
                    f"bound {self.spec.max_staleness} — publish() must "
                    f"run at every boundary")
            view = self._server.merge(self._state, self._cache.values)
        rec = {"t": self._clock,
               "clock": self._clock - staleness,
               "staleness": staleness}
        self.reads.append(rec)
        if self.recorder is not None:
            self.recorder.instant("serve_read", **rec)
        return view, staleness

    # -- measured-staleness accounting ---------------------------------------

    def staleness_hist(self) -> dict:
        """``{staleness: read count}`` over every read served so far —
        the staleness-at-read histogram."""
        hist: dict = {}
        for r in self.reads:
            hist[r["staleness"]] = hist.get(r["staleness"], 0) + 1
        return hist

    def max_staleness_read(self) -> int:
        """The worst staleness any read observed (0 when nothing was
        read)."""
        return max((r["staleness"] for r in self.reads), default=0)
