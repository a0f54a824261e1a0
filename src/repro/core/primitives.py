"""STRADS primitives: ``schedule``, ``push``, ``pull`` (+ automatic ``sync``).

The paper (Lee et al., 2014) defines a model-parallel round as

    sched = schedule()                      # pick U variables
    z_p   = push(worker=p, vars=sched)      # partial update on worker p
    x     = pull(sched, [z_1 .. z_P])       # aggregate + commit
    sync()                                  # automatic BSP refresh

On TPU/JAX we realize this with SPMD: ``schedule`` is computed *replicated*
(every device runs the same deterministic program with the same PRNG key, so
there is no scheduler machine and no star-topology bottleneck — the paper's
own §5 future-work item), ``push`` runs under ``shard_map`` over the ``data``
mesh axis, ``pull`` aggregation is a ``jax.lax.psum`` over that axis, and
``sync`` is implicit in SPMD program order (BSP, exactly the consistency
model the paper uses).

Round anatomy (executed by :mod:`repro.core.engine`):

    cand  = propose(state, carry, rng, t)                    # replicated
    stats = psum_p( schedule_stats(D_p, state, cand) )       # sharded, opt.
    sched = schedule(state, carry, cand, stats, rng, t)      # replicated
    z, local_p = push(D_p, state, sched)                     # sharded
    state = pull(state, sched, psum_p(z), local_p, D_p)      # commit + sync
    carry = sched_update(carry, state_before, state, sched)  # replicated

``z`` is the paper's partial result (summed across workers exactly as the
paper's Σ_p z_j^p); ``local_p`` carries per-shard state updates that never
cross workers (e.g. LDA's topic-assignment vector or a maintained residual)
— in 2014-STRADS those simply lived in worker memory, here they are the
sharded leaves of the state pytree.

``phase`` is a *static* Python int (``app.static_phase(t)``) enabling
schedules whose communication pattern changes per round (LDA's rotation
``ppermute`` needs a static permutation); apps with a fixed pattern return 0.
Apps declare the cycle length as ``phase_period`` (``static_phase(t)`` must
equal ``t % phase_period``): the scanned executor unrolls one full phase
cycle per ``lax.scan`` step so every phase stays static inside the trace.

The v2 scheduler-injection contract
-----------------------------------

Scheduling *policy* is not part of the app: it is a declarative
:class:`~repro.sched.spec.SchedulerSpec` on the
:class:`~repro.core.plan.ExecutionPlan` (or the app's
``default_scheduler_spec()`` when the plan leaves it ``None``).  The
engine resolves the spec into a :class:`~repro.sched.protocol.Scheduler`
(``repro.sched.build_scheduler``, using the app's ``num_schedulable()``
count and the mesh width) and injects it via ``use_scheduler()`` before
tracing; apps *consume* ``self.scheduler`` inside ``propose`` /
``schedule`` instead of hardcoding a policy.

The scheduler's on-device state (e.g. the dynamic-priority Δx history)
is the **engine-owned scheduler carry**:

* ``scheduler.init_carry()`` creates it; the engine threads it through
  every executor (host loop, ``lax.scan``, pipelined prefetch, SSP
  windows) and returns it as ``EngineCarry.sched_carry`` /
  ``SSPCarry.sched_carry`` — so it checkpoints and resumes bit-exactly
  through ``checkpoint/npz`` like the PRNG stream and round counter;
* ``propose(state, carry, ...)`` / ``schedule(state, carry, ...)`` read
  it (apps usually just forward it to ``self.scheduler``);
* ``sched_update(carry, state_before, state_after, sched, phase)`` folds
  the committed round back into it — the app computes the policy's
  feedback signal (e.g. Δβ over the scheduled block) and delegates to
  ``scheduler.update_carry``; the default keeps the carry unchanged;
* under SSP, ``scheduler.mark_scheduled(carry, candidates)`` applies the
  in-flight exclusion between the window's stale proposals (replacing
  the state-leaf ``var_roles()``/``role="priority"`` mechanism for
  injected schedulers; the VarTable path remains for apps that keep a
  priority table in their state).

The partition-injection contract
--------------------------------

Partitioning — the paper's *other* headline primitive — is declarative
too: a :class:`~repro.part.spec.PartitionerSpec` on the
:class:`~repro.core.plan.ExecutionPlan` (or the app's
``default_partitioner_spec()`` when the plan leaves it ``None``).  The
engine resolves it into a :class:`~repro.part.protocol.Partitioner`
(``repro.part.build_partitioner``, using the app's ``num_schedulable()``
count, the mesh width, and the optional per-variable byte vector
``partition_sizes()``) and injects the resulting variable→worker
:class:`~repro.part.assignment.Assignment` via ``use_partition()``
before tracing; apps read ``self.assignment`` if their primitives
consume ownership (the built-in apps' math is ownership-agnostic — the
assignment governs the model store's placement bookkeeping and the
Fig-3 byte accounting).

The repartition loop is **engine-owned and host-side**, mirroring the
scheduler-carry pattern one level up:

* the engine checks for rebalances at the ``plan.checkpoint_every``
  chunk boundaries of ``StradsEngine.execute`` — the one place state is
  already synced to the host, so a move is a ``KVStore.repartition``
  re-placement, never XLA-program surgery;
* the activity signal feeding the load balancer is the |Δ| of the app's
  ``partition_signal(state)`` (a ``(J,)`` per-variable statistic, e.g.
  Lasso's β) across each chunk — the partition-level twin of the
  priority signal ``sched_update`` feeds the dynamic scheduler;
* compiled-program caches are keyed per assignment (a rebalance is one
  cache miss, a swap back is a hit), and the SSP server/cache split in
  :mod:`repro.ps` re-derives from the repartitioned KVStore specs;
* the assignment (+ the partitioner's activity stats) rides the
  ``{"state", "carry", "assignment"}`` checkpoint payload, so a resumed
  run replays the same rebalance decisions bit-exactly
  (``execute(..., partition=...)``);
* apps declare which kinds they can host via
  ``supported_partitioner_kinds`` (e.g. LDA's rotation owns a frozen
  contiguous block map, so only ``"static"`` applies) — the engine
  rejects a plan naming an unlisted kind at injection time, never at
  trace time, exactly like ``supported_scheduler_kinds``.

The kernel-injection contract
-----------------------------

The round body's compute hot-spots (the push partials, the dynamic
scheduler's Gram block) are served by an injected **kernel backend**,
declared as a :class:`~repro.kernels.spec.KernelSpec` on the
:class:`~repro.core.plan.ExecutionPlan` (or the app's
``default_kernel_spec()`` when the plan leaves it ``None``; the engine
falls back to ``kind="reference"`` — the pure-jnp oracles, bit-identical
to the pre-KernelSpec round body).  The engine resolves the spec into a
backend object (``repro.kernels.build_kernels`` — Pallas kernels
compiled for Mosaic on TPU, automatically interpret-mode elsewhere) and
injects it via ``use_kernels()`` before tracing; apps call
``self.kernels.lasso_partial(...)`` / ``self.kernels.gram_block(...)``
inside ``push``/``schedule_stats`` and never branch on the backend
themselves.

Unlike the scheduler and partitioner, a kernel backend is **stateless**
— no carry, no checkpoint payload; the injection only changes what the
traced round lowers to.  The discipline it shares with the other two:

* apps declare which kinds they can dispatch via
  ``supported_kernel_kinds`` (e.g. LDA/MF have no Pallas hot-spot
  kernels yet, so only ``"reference"`` applies) — the engine rejects a
  plan naming an unlisted kind at injection time, never at trace time;
* compiled-program caches are keyed per (SchedulerSpec, Assignment,
  KernelSpec), so a backend sweep — ``BENCH_kernels``'s reference vs
  pallas arms — reuses each configuration's programs instead of
  retracing on every swap.

The telemetry-injection contract
--------------------------------

Observability follows the same declarative shape: a
:class:`~repro.obs.spec.TelemetrySpec` on the
:class:`~repro.core.plan.ExecutionPlan` (``plan.telemetry``; the legacy
boolean form still parses — ``True`` means ``kind="counters"`` with a
``DeprecationWarning``).  Unlike the scheduler/partitioner/kernel
contracts, instrumentation is engine-owned and rides *outside* the
primitives, so it can never change what a round computes; an app may
add one optional hook.

* **Device counters** (any spec) are an extra pytree leaf threaded
  through every executor's carry (``EngineCarry.obs`` /
  ``SSPCarry.obs``; ``None`` when telemetry is off, so old checkpoints
  restore unchanged).  Counters are derived *only* from the already-
  computed schedule pytree and the data — per-phase round counts, the
  ρ-filter ledger (``proposed = accepted + killed`` from the keep-mask
  popcounts), and the optional app hook ``obs_counts(data, sched,
  phase) -> (visited, updated)`` (the items a round's push steps over
  and those it updates) — never from model state or the PRNG stream,
  which is what makes the instrumented run **bit-identical** to the
  uninstrumented one.
* **Host events** (``kind="trace"``) go to the active
  :class:`~repro.obs.events.Recorder` (one made active for a whole run
  with :func:`repro.obs.recording`, else one of the call's own):
  ``execute`` > ``execute.resolve`` and the executor span >
  ``scan.program``/``scan.replicate``/``scan.call``, chunk/checkpoint
  spans, JAX's ``jit.*`` compile spans and ``gc`` collections,
  compiled-program cache misses keyed by the (SchedulerSpec,
  Assignment, KernelSpec) triple, and rebalance decisions — all
  recorded at host phase boundaries, never inside a traced program.
  With a Recorder active, ``init_state`` and ``shard_data`` record
  spans too, whatever the plan.
* **Phase scopes**: the round body runs its phases under
  ``jax.named_scope`` (``repro.obs.PHASES``: propose, schedule_stats,
  schedule, push, aggregate, pull, sched_update; apps add ``exchange``
  around a push's collectives), so the compiled program's ``op_name``
  metadata names the phase of every instruction.  Metadata only: the
  scopes change no computation.
* Every ``execute()`` returns the resolved telemetry as a uniform
  :class:`~repro.obs.report.RunReport` in
  ``ExecutionReport.telemetry`` (the SSP staleness summary becomes its
  ``.ssp`` section), built without waiting for the device;
  ``repro.launch.trace`` validates and re-exports saved reports
  offline.

The serving-injection contract
------------------------------

Serving (:mod:`repro.serve`) is the read-only fifth leg of the same
declarative surface: a :class:`~repro.serve.spec.ServeSpec` declares the
consistency a read gets (``"stale"`` — the SSP mixed view, server-
resident leaves through a :class:`~repro.ps.cache.StaleCache` under the
gate ``clock − cache.clock ≤ max_staleness``; ``"snapshot"`` — the full
state pinned at flush/chunk boundaries) and the micro-batching policy
(``max_batch``, ``batch_window_ms``).  Apps opt in with **one**
primitive, declared alongside ``state_specs()``/``var_roles()``:

* ``query(state, batch) -> result`` — one *batched* inference request
  against a (possibly stale) state view: ``batch`` is a pytree whose
  leaves carry a leading request dimension (the frontend stacks queued
  per-example payloads), and the result's leaves carry the same leading
  dimension (the frontend slices per-request responses back out).
  Lasso serves ``predict`` (ŷ = Xβ), LDA serves ``infer_topics`` (a
  fixed-iteration fold-in over the topic tables), MF serves
  ``recommend`` (top-k item scores for a user row).
* ``query`` must be **pure and deterministic** — jit-traceable, no PRNG
  stream of its own, and it never writes: the serving subsystem reads
  through copies/boundary references only, which is what makes
  ``serve_while_training`` bit-identical to an unserved ``execute()``.
* unlike the other four contracts nothing is injected *into* the app:
  the engine side of the contract is the publish boundary —
  ``serve_while_training`` publishes committed state to the
  :class:`~repro.serve.view.ModelView` at the same host-synced chunk
  boundaries the partitioner and checkpointer already use, and the
  frontend's jitted query programs are cached per (Assignment,
  KernelSpec) exactly like the engine's round programs.

The ingest-injection contract
-----------------------------

Streaming ingest (:mod:`repro.stream`) is the *write* half of the
serving story — the sixth leg of the same declarative surface.  A
:class:`~repro.stream.spec.StreamSpec` declares how new data flows in
(``"replace"`` — each delta names the row slots it overwrites;
``"extend"`` — rows append into a capacity-padded ring buffer behind the
app's validity mask, so data shapes stay static and compiled round
programs are reused, never recompiled) and the cadence
(``ingest_every``, aligned to the executor's step length exactly like
``checkpoint_every``).  Like ``ServeSpec`` it rides the entry points
(``execute(..., stream=, source=)``), never the ExecutionPlan.  Apps opt
in with two primitives:

* ``ingest_specs() -> {"leaves": (...), "valid": fn | None}`` — which
  data leaves stream (all share the row axis; their leading dimension is
  the ring capacity) and, for ``"extend"``, a host-side
  ``valid(data) -> (rows,) bool`` mask deriving which slots hold real
  rows (MF reads it off ``mask``, LDA off ``words >= 0``; lasso has no
  validity channel and therefore declares ``supported_stream_kinds =
  ("replace",)`` — the same injection-time rejection rule as
  ``supported_scheduler_kinds``).
* ``ingest(data, state, rows, delta) -> (data, state)`` — overwrite the
  ``rows`` slots of the streamable leaves with ``delta["data"]`` and
  bring *derived* state up to date in the same step (lasso rewrites the
  replaced residuals ``r = y − Xβ``; MF the replaced rows of ``R``; LDA
  decrements the old token's collapsed counts and increments the new
  one's from the per-row ``delta["z"]`` draw).  Leaves the delta does
  not touch must come back as the **same objects** — the
  :class:`~repro.stream.ingest.Ingestor` re-places only changed leaves
  with per-leaf ``device_put``, never a full ``shard_data`` rebuild.
  With ``state=None`` only the data-leaf writes apply (the
  deterministic-source replay path after a cross-process resume).

The engine side is the boundary loop: deltas land at host-synced chunk
boundaries (where the partitioner already rebalances, checkpoints
already save, the serve loop already publishes), the stream cursor rides
the checkpoint payload as its ``"stream"`` subtree, and ingest
spans/row counts ride the :mod:`repro.obs` Recorder.  A round never
observes a half-applied delta, and an empty source is bit-identical to
an unstreamed run.

The v2 write contract (VarTable-mediated push/pull)
---------------------------------------------------

Apps implement exactly the primitives above, **once**, and get every
executor — including bounded staleness — without SSP-specific hooks.  The
executors derive deferred-commit behavior from the app's *placement
declarations* (``state_specs()`` → :class:`~repro.core.kvstore.VarSpec`,
mediated by :class:`~repro.core.kvstore.VarTable`):

* a ``local`` leaf whose '/'-joined key path names a **worker-resident**
  state leaf (non-replicated VarSpec) *is* the committed new value of
  that leaf.  ``pull`` must treat such leaves as write-through: it writes
  them back verbatim (``{"z": local["z"], ...}``) and never assumes the
  pre-push state value survives.  Under BSP this is invisible; under SSP
  the executor commits those leaves **every round** (a worker always
  reads its own writes fresh — the SSP read-my-writes guarantee) and
  buffers only the *remaining* ``local`` leaves until the flush, where
  ``pull`` is replayed per deferred round with ``local`` reconstructed
  (commit-through entries read back from the live state, the rest from
  the buffer) and ``z`` freshly aggregated in ONE batched collective.
* server-resident writes (replicated VarSpecs) always flow through
  ``pull``; under SSP they commit at the flush, up to ``s`` rounds late.
* apps that keep a scheduling-priority table in their *state* declare it
  via ``var_roles() -> {leaf_path: "priority"}`` and get the VarTable
  in-flight exclusion; apps using an injected scheduler need neither —
  the carry-based ``mark_scheduled`` above covers it.

The v1 protocol's four ``ssp_commit_local`` / ``ssp_defer_local`` /
``ssp_commit_shared`` / ``ssp_mark_scheduled`` hook overrides are
deprecated: :mod:`repro.ps.ssp` still honors them (with a
``DeprecationWarning``) when an app defines any, but the built-in apps
rely purely on the derived behavior.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Protocol, runtime_checkable

import jax

# Type aliases -----------------------------------------------------------
ModelState = Any     # pytree of model variables x (the paper's KV store)
DataShard = Any      # pytree: this worker's partition of the data D
Schedule = Any       # pytree describing the scheduled variable block
Partial = Any        # pytree of partial results z_j^p
Stats = Any          # pytree of distributed statistics used by schedule()
SchedCarry = Any     # scheduler scan carry (engine-owned; None if stateless)


@runtime_checkable
class StradsApp(Protocol):
    """User-defined STRADS application (the paper's Figure 2)."""

    def init_state(self, rng: jax.Array) -> ModelState: ...

    def static_phase(self, t: int) -> int: ...

    def propose(self, state: ModelState, carry: SchedCarry,
                rng: jax.Array, t: jax.Array, phase: int) -> Schedule: ...

    def schedule_stats(self, data: DataShard, state: ModelState,
                       candidates: Schedule, phase: int) -> Stats: ...

    def schedule(self, state: ModelState, carry: SchedCarry,
                 candidates: Schedule, stats: Stats, rng: jax.Array,
                 t: jax.Array, phase: int) -> Schedule: ...

    def push(self, data: DataShard, state: ModelState, sched: Schedule,
             phase: int) -> tuple[Partial, Any]: ...

    def pull(self, state: ModelState, sched: Schedule, z: Partial,
             local: Any, data: DataShard, phase: int) -> ModelState: ...

    def sched_update(self, carry: SchedCarry, before: ModelState,
                     after: ModelState, sched: Schedule,
                     phase: int) -> SchedCarry: ...


class StradsAppBase:
    """Convenience base with the common defaults.

    Subclasses override what they need; ``schedule_stats`` is only invoked
    by the engine when overridden (data-independent schedules skip the
    extra shard_map pass entirely).  Apps with phase-dependent rounds set
    ``phase_period`` to the cycle length and keep ``static_phase(t) ==
    t % phase_period``.

    Scheduling policy arrives by **injection** (the v2 scheduler-injection
    contract — see the module docstring): the engine resolves the plan's
    ``SchedulerSpec`` (or ``default_scheduler_spec()``) and calls
    ``use_scheduler``; ``propose``/``schedule``/``sched_update`` consume
    ``self.scheduler`` and the engine-owned carry.

    SSP behavior is **derived, not overridden** (the v2 write contract):
    commit-through and deferral follow from the placement declared in
    ``state_specs()``; in-flight exclusion follows from the injected
    scheduler's ``mark_scheduled`` (or, for state-resident priority
    tables, from ``var_roles()``).
    """

    phase_period: int = 1

    #: the injected Scheduler (set by the engine; None = app self-schedules)
    scheduler = None

    #: which SchedulerSpec kinds this app can consume (None = any; the
    #: engine rejects a plan naming an unlisted kind at injection time,
    #: never at trace time)
    supported_scheduler_kinds = None

    #: the injected variable→worker Assignment (set by the engine; None =
    #: no partitioner resolved — the pre-subsystem behavior)
    assignment = None

    #: which PartitionerSpec kinds this app can host (None = any; same
    #: injection-time rejection rule as supported_scheduler_kinds)
    supported_partitioner_kinds = None

    #: the injected kernel backend (set by the engine; None until an
    #: engine resolves a spec — apps with kernel hot-spots should fall
    #: back to the reference oracles for engine-less direct calls)
    kernels = None

    #: which KernelSpec kinds this app can dispatch (None = any; same
    #: injection-time rejection rule as supported_scheduler_kinds)
    supported_kernel_kinds = None

    def static_phase(self, t: int) -> int:
        return 0

    # -- scheduler injection -------------------------------------------------

    def default_scheduler_spec(self) -> Optional[Any]:
        """The policy this app runs when the plan names none (a
        :class:`~repro.sched.spec.SchedulerSpec` or ``None`` for apps
        that schedule themselves)."""
        return None

    def num_schedulable(self) -> int:
        """How many schedulable variables the injected policy ranges over
        (Lasso: J coefficients, MF: K ranks, LDA: the padded vocab).
        Required whenever a scheduler spec is resolved for this app."""
        raise NotImplementedError(
            f"{type(self).__name__} must define num_schedulable() to "
            f"accept an injected SchedulerSpec")

    def use_scheduler(self, scheduler) -> None:
        """Receive the engine-resolved :class:`~repro.sched.Scheduler`."""
        self.scheduler = scheduler

    # -- partition injection -------------------------------------------------

    def default_partitioner_spec(self) -> Optional[Any]:
        """The partition policy this app runs when the plan names none
        (a :class:`~repro.part.spec.PartitionerSpec` or ``None`` for
        apps that manage placement entirely through ``state_specs()``
        with no variable-ownership story)."""
        return None

    def use_partition(self, assignment) -> None:
        """Receive the engine-resolved variable→worker
        :class:`~repro.part.assignment.Assignment` (``None`` clears
        it)."""
        self.assignment = assignment

    # -- kernel injection ----------------------------------------------------

    def default_kernel_spec(self) -> Optional[Any]:
        """The kernel backend this app runs when the plan names none
        (a :class:`~repro.kernels.spec.KernelSpec` or ``None`` to take
        the engine fallback, ``kind="reference"``)."""
        return None

    def use_kernels(self, kernels) -> None:
        """Receive the engine-resolved kernel backend
        (``repro.kernels.build_kernels`` output; never ``None`` — the
        engine always resolves at least the reference backend)."""
        self.kernels = kernels

    def partition_signal(self, state):
        """A ``(num_schedulable(),)`` per-variable statistic whose |Δ|
        across a chunk is the load balancer's activity measure (e.g.
        Lasso's β — |Δβ| is exactly the dynamic scheduler's priority
        signal).  ``None`` (the default) means the app emits no
        activity signal and cannot host a ``load_balanced``
        partitioner."""
        return None

    def partition_sizes(self):
        """Per-variable byte sizes for the ``size_balanced`` kind
        (``None`` = uniform)."""
        return None

    def query(self, state, batch):
        """One batched inference request against a (possibly stale)
        state view — the serving-injection contract (see the module
        docstring).  ``batch`` leaves carry a leading request dimension;
        so must the result's.  Default: the app declares no query
        primitive and cannot be served."""
        raise NotImplementedError(
            f"{type(self).__name__} declares no query() primitive — "
            f"serving (repro.serve) needs one; see the serving-injection "
            f"contract in repro.core.primitives")

    #: which StreamSpec kinds this app can ingest (None = any; same
    #: injection-time rejection rule as supported_scheduler_kinds.
    #: Apps without a validity channel cannot host "extend")
    supported_stream_kinds = None

    def ingest_specs(self) -> dict:
        """``{"leaves": (...), "valid": fn | None}`` — which data leaves
        stream and how to derive the extend-kind validity mask; the
        ingest-injection contract (see the module docstring).  Default:
        the app declares no ingest primitives and cannot stream."""
        raise NotImplementedError(
            f"{type(self).__name__} declares no ingest_specs() primitive "
            f"— streaming (repro.stream) needs one; see the "
            f"ingest-injection contract in repro.core.primitives")

    def ingest(self, data, state, rows, delta):
        """Overwrite the ``rows`` slots of the streamable leaves with
        ``delta["data"]`` and bring derived state up to date — the
        ingest-injection contract (see the module docstring).  Unchanged
        leaves must come back as the same objects; ``state=None``
        applies the data-leaf writes only.  Default: the app declares
        no ingest primitive and cannot stream."""
        raise NotImplementedError(
            f"{type(self).__name__} declares no ingest() primitive — "
            f"streaming (repro.stream) needs one; see the "
            f"ingest-injection contract in repro.core.primitives")

    def var_roles(self) -> dict:
        """Leaf-path → :class:`~repro.core.kvstore.VarSpec` role
        declarations beyond placement (currently only ``"priority"``:
        scheduling-priority tables kept in app *state*, which the SSP
        window scheduler masks for in-flight exclusion via VarTable).
        Apps with injected schedulers keep priorities in the engine carry
        instead and need no roles.  Default: none."""
        return {}

    # -- the primitives ------------------------------------------------------

    def propose(self, state, carry, rng, t, phase):
        return None

    def schedule_stats(self, data, state, candidates, phase):
        return None

    def schedule(self, state, carry, candidates, stats, rng, t, phase):
        return candidates

    def push(self, data, state, sched, phase):
        raise NotImplementedError

    def pull(self, state, sched, z, local, data, phase):
        raise NotImplementedError

    def sched_update(self, carry, before, after, sched, phase):
        """Fold the committed round into the scheduler carry.  Default:
        unchanged (stateless policies)."""
        return carry


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class RoundResult:
    """Output of one BSP round (a pytree, so it can cross jit)."""
    state: ModelState
    sched: Schedule
    aux: Any = None
    sched_carry: SchedCarry = None   # post-round engine-owned carry


def tree_psum(tree: Any, axis_name: str) -> Any:
    """psum every leaf of a pytree (the pull aggregation)."""
    return jax.tree.map(lambda x: jax.lax.psum(x, axis_name), tree)
