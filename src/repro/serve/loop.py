"""The continuous-training loop: ``serve_while_training``.

The loop interleaves :meth:`~repro.core.engine.StradsEngine.execute`
chunks with serving reads at the SSP flush boundaries: the plan is
chunked into spans of the executor's step length (for ``"ssp"`` that is
``rounds_per_step = lcm(s+1, phase_period)`` — exactly one flush window,
so every publish point *is* a flush), each span resumes the previous
one's :class:`~repro.core.engine.EngineCarry`/``SSPCarry`` (the same
bit-exact resume path checkpointing uses), and between spans the
committed state is published to the :class:`~repro.serve.view.ModelView`
and the queued requests are served.

Bit-exactness is structural, not hoped-for: serving touches training
only through ``publish`` (which copies what it keeps) — never the PRNG
stream, the scheduler carry, or the state buffers — so the final trained
state of a served run is bit-identical to an unserved ``execute()`` of
the same plan (``tests/test_serve.py`` asserts it leaf by leaf).

Streaming requests fold in by due round: ``requests`` is a sequence of
``(t_due, payload)`` pairs, submitted to the frontend at the first
boundary whose clock reaches ``t_due`` — the serving analogue of the
windowed executor folding streaming mini-batches in at flush points.

Spans/instants ride a caller-supplied :class:`~repro.obs.events.Recorder`
(``train_chunk`` spans around each executor span, ``serve_batch`` spans
+ ``serve_read``/``serve_refresh``/``serve_pin`` instants between them),
so an exported Chrome trace shows serving interleaved with training.
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Sequence, Tuple

import contextlib

import jax
import jax.numpy as jnp

from ..core.plan import ExecutionPlan, ExecutionReport
from .frontend import ServeFrontend
from .spec import ServeSpec
from .view import ModelView


@dataclasses.dataclass
class ServeReport:
    """What a serving run produced: the training report (``None`` for
    ``serve_only``), every response, and the measured serving record."""
    report: Optional[ExecutionReport]
    responses: List[Any]
    latencies_ms: List[float]
    reads: List[dict]
    spec: ServeSpec
    #: final stream-cursor payload when training streamed data in
    #: (``serve_while_training(..., stream=, source=)``); None otherwise
    ingest: Optional[dict] = None

    def latency_percentiles(self) -> dict:
        import numpy as np
        if not self.latencies_ms:
            return {"p50_ms": float("nan"), "p99_ms": float("nan")}
        lat = np.asarray(self.latencies_ms)
        return {"p50_ms": float(np.percentile(lat, 50)),
                "p99_ms": float(np.percentile(lat, 99))}

    def staleness_hist(self) -> dict:
        hist: dict = {}
        for r in self.reads:
            hist[r["staleness"]] = hist.get(r["staleness"], 0) + 1
        return hist

    def max_staleness_read(self) -> int:
        return max((r["staleness"] for r in self.reads), default=0)


def _resolve_spec(spec, plan: Optional[ExecutionPlan]) -> ServeSpec:
    if spec is not None:
        if not isinstance(spec, ServeSpec):
            raise TypeError(f"wanted a ServeSpec; got "
                            f"{type(spec).__name__}")
        return spec
    # the conventional default ties the serving bound to the training
    # one: an SSP plan's reads are already s-stale, so serving at the
    # same bound adds no consistency loss
    s = plan.staleness if plan is not None and plan.executor == "ssp" else 0
    return ServeSpec.default_for("stale", max_staleness=s)


def _check_requests(requests) -> List[Tuple[int, Any]]:
    out = []
    for item in requests:
        if not (isinstance(item, tuple) and len(item) == 2
                and isinstance(item[0], int)):
            raise TypeError("serve_while_training wants requests as "
                            "(t_due, payload) pairs; got "
                            f"{type(item).__name__}")
        out.append(item)
    return sorted(out, key=lambda it: it[0])


def serve_while_training(engine, state, data, rng, plan: ExecutionPlan,
                         *, spec: Optional[ServeSpec] = None,
                         requests: Sequence[Tuple[int, Any]] = (),
                         collect=None, recorder=None,
                         chunk_rounds: Optional[int] = None,
                         stream=None, source=None,
                         stream_state: Optional[dict] = None
                         ) -> ServeReport:
    """Train ``plan`` to completion while serving ``requests`` between
    chunks.  Returns a :class:`ServeReport` whose ``report.state`` is
    bit-identical to ``engine.execute(state, data, rng, plan).state``.

    ``chunk_rounds`` overrides the publish cadence (must be a multiple
    of the executor's step length; default: exactly one step — for SSP,
    one flush window).

    ``stream`` (a :class:`~repro.stream.spec.StreamSpec`) + ``source``
    ingest data deltas at the same boundaries serving publishes at: each
    boundary ``t`` ingests *before* the chunk covering ``[t, t+chunk)``
    runs and before the clock-``t`` publish, the exact ordering
    ``engine.execute(..., stream=)`` uses — so a served streamed run's
    trained state is bit-identical to an unserved streamed one, and
    every published view includes all deltas due ≤ its clock.  The final
    cursor payload lands on the report as :attr:`ServeReport.ingest`."""
    spec = _resolve_spec(spec, plan)
    due = _check_requests(requests)
    step = engine._step_length(plan)
    chunk = chunk_rounds if chunk_rounds is not None else step
    if chunk < 1 or chunk % step:
        raise ValueError(f"chunk_rounds={chunk} must be a positive "
                         f"multiple of the {plan.executor!r} executor's "
                         f"step length {step}")
    for t_due, _ in due:
        if not 0 <= t_due <= plan.rounds:
            raise ValueError(f"request due round {t_due} outside the "
                             f"plan's 0..{plan.rounds}")
    if (stream is None) != (source is None):
        raise ValueError("stream= (a StreamSpec) and source= (a "
                         "DataSource) come as a pair — got only one")
    ing = None
    if stream is not None:
        from ..stream import Ingestor
        ing = Ingestor(stream, source)
        if stream_state is not None:
            ing.restore(stream_state)
        ing.bind(engine, data)
        if stream.ingest_every % chunk:
            raise ValueError(
                f"stream.ingest_every={stream.ingest_every} must be a "
                f"multiple of the serve chunk cadence {chunk} — ingest "
                f"boundaries land only where the loop syncs")
    elif stream_state is not None:
        raise ValueError("stream_state resumes a streamed run; pass "
                         "the stream=/source= pair with it")

    view = ModelView(engine, spec, recorder=recorder)
    frontend = ServeFrontend(engine, view, spec, recorder=recorder)

    def pump(t: int, force: bool) -> None:
        while due and due[0][0] <= t:
            frontend.submit(due.pop(0)[1])
        frontend.flush(force=force)

    # boundary 0 ingests first, so the clock-0 publish (serving before
    # any training commits) already includes the deltas due at 0
    if ing is not None:
        state, data = ing.step(engine, state, data, 0)
    view.publish(state, 0)
    pump(0, force=False)

    carry = None
    traces = []
    t = 0
    rep = None
    while t < plan.rounds:
        target = min(t + chunk, plan.rounds)
        span = (recorder.span("train_chunk", t0=t, t1=target)
                if recorder is not None else contextlib.nullcontext())
        with span:
            rep = engine.execute(state, data, rng,
                                 dataclasses.replace(plan, rounds=target),
                                 collect=collect, carry=carry)
        state, carry = rep.state, rep.carry
        rng = carry.rng
        t = int(carry.t)
        if rep.trace is not None:
            traces.append(rep.trace)
        if ing is not None and t < plan.rounds:
            state, data = ing.step(engine, state, data, t)
        view.publish(state, t)
        pump(t, force=(t >= plan.rounds))

    trace = (jax.tree.map(lambda *xs: jnp.concatenate(xs), *traces)
             if traces else None)
    report = ExecutionReport(state=state, trace=trace,
                             telemetry=rep.telemetry if rep is not None
                             else None, carry=carry, plan=plan,
                             stream=ing.payload() if ing is not None
                             else None)
    return ServeReport(report=report, responses=frontend.responses,
                       latencies_ms=frontend.latencies_ms,
                       reads=view.reads, spec=spec,
                       ingest=ing.payload() if ing is not None else None)


def serve_only(engine, state, *, spec: Optional[ServeSpec] = None,
               requests: Sequence[Any] = (), t: int = 0,
               recorder=None) -> ServeReport:
    """Serve ``requests`` (plain payloads, no due rounds) from a fixed
    trained state — the no-training baseline for serving latency.
    ``t`` stamps the clock the state is committed through."""
    spec = _resolve_spec(spec, None)
    view = ModelView(engine, spec, recorder=recorder)
    frontend = ServeFrontend(engine, view, spec, recorder=recorder)
    view.publish(state, t)
    for payload in requests:
        frontend.submit(payload)
    frontend.flush(force=True)
    return ServeReport(report=None, responses=frontend.responses,
                       latencies_ms=frontend.latencies_ms,
                       reads=view.reads, spec=spec)
