"""The micro-batching request frontend: :class:`ServeFrontend`.

Requests (per-example pytrees, e.g. ``{"x": (J,)}`` for Lasso predict)
queue up between training chunks; ``flush()`` assembles them into
batches of at most ``ServeSpec.max_batch``, reads a state view from the
:class:`~repro.serve.view.ModelView`, and runs the app's batched
``query()`` primitive as one jitted program.  Batching policy:

* a *full* batch (``max_batch`` queued requests) is served immediately;
* a *partial* batch waits up to ``batch_window_ms`` for more arrivals
  (measured from its oldest request), then is served anyway;
* ``flush(force=True)`` drains everything regardless of the window
  (end of run — no more arrivals are coming).

Query programs are jitted once and cached per ``(Assignment,
KernelSpec)`` — the same key the engine's compiled round programs use —
so a partition rebalance or kernel-backend swap is one cache miss, and
a swap back is a hit.  Per-request latency (submit → response ready) and
per-batch staleness-at-read are recorded for the p50/p99 + histogram
reporting in ``launch/serve.py``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import deque
from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .spec import ServeSpec
from .view import ModelView


@dataclasses.dataclass
class Request:
    """One queued query: a per-example payload pytree + submit time."""
    payload: Any
    t_submit: float


@dataclasses.dataclass
class Response:
    """One served query: the per-example result slice + bookkeeping."""
    result: Any
    latency_ms: float
    staleness: int


class ServeFrontend:
    """Queue → batch assembly → jitted per-app query program."""

    def __init__(self, engine, view: ModelView, spec: ServeSpec,
                 recorder: Optional[Any] = None,
                 clock: Callable[[], float] = time.monotonic):
        if view.spec != spec:
            raise ValueError("the frontend and its ModelView must share "
                             "one ServeSpec")
        self.engine = engine
        self.view = view
        self.spec = spec
        self.recorder = recorder
        self._clock = clock
        self._queue: deque = deque()
        self._programs: dict = {}    # (Assignment, KernelSpec) -> jitted
        self.responses: List[Response] = []
        self.latencies_ms: List[float] = []

    # -- queue ---------------------------------------------------------------

    def submit(self, payload) -> None:
        """Enqueue one per-example query payload (no leading batch dim —
        the frontend stacks)."""
        self._queue.append(Request(payload, self._clock()))

    def pending(self) -> int:
        return len(self._queue)

    # -- the jitted query program --------------------------------------------

    def _program(self):
        # cached per (Assignment, KernelSpec): the engine rebinds both
        # between chunks, and a query traced under one configuration
        # must not serve another (same rule as the engine's round cache)
        key = (self.engine._assignment, self.engine._active_kern_spec)
        prog = self._programs.get(key)
        if prog is None:
            app = self.engine.app
            prog = jax.jit(lambda state, batch: app.query(state, batch))
            self._programs[key] = prog
            if self.recorder is not None:
                self.recorder.instant(
                    "cache_miss", program="query",
                    kernels=(key[1].kind if key[1] is not None else None))
        return prog

    # -- batch assembly + serving --------------------------------------------

    def _take_batch(self, force: bool) -> Optional[List[Request]]:
        q, spec = self._queue, self.spec
        if not q:
            return None
        if len(q) < spec.max_batch and not force:
            waited_ms = (self._clock() - q[0].t_submit) * 1e3
            if waited_ms < spec.batch_window_ms:
                return None        # partial batch still inside its window
        n = min(len(q), spec.max_batch)
        return [q.popleft() for _ in range(n)]

    def flush(self, force: bool = False) -> int:
        """Serve every batch the batching policy allows right now;
        returns the number of requests served."""
        served = 0
        while True:
            batch = self._take_batch(force)
            if batch is None:
                return served
            view_state, staleness = self.view.read()
            stacked = jax.tree.map(lambda *xs: jnp.stack(xs),
                                   *[r.payload for r in batch])
            span = (self.recorder.span("serve_batch", size=len(batch),
                                       staleness=staleness)
                    if self.recorder is not None
                    else contextlib.nullcontext())
            with span:
                out = self._program()(view_state, stacked)
                out = jax.block_until_ready(out)
            done = self._clock()
            for i, req in enumerate(batch):
                lat = (done - req.t_submit) * 1e3
                self.latencies_ms.append(lat)
                self.responses.append(Response(
                    result=jax.tree.map(lambda x, i=i: x[i], out),
                    latency_ms=lat, staleness=staleness))
            served += len(batch)

    # -- reporting -----------------------------------------------------------

    def latency_percentiles(self) -> dict:
        """``{"p50_ms", "p99_ms"}`` over every served request (NaN when
        nothing was served)."""
        if not self.latencies_ms:
            return {"p50_ms": float("nan"), "p99_ms": float("nan")}
        lat = np.asarray(self.latencies_ms)
        return {"p50_ms": float(np.percentile(lat, 50)),
                "p99_ms": float(np.percentile(lat, 99))}
