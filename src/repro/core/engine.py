"""The STRADS round executors behind one entry point.

Turns a :class:`~repro.core.primitives.StradsApp` into jitted programs
executing

    propose → [schedule_stats → psum] → schedule → push → psum → pull

with ``push``/``schedule_stats`` running under ``shard_map`` over the
``data`` mesh axis and schedule decisions replicated.  ``sync`` is
automatic: SPMD program order is the BSP barrier (DESIGN.md §3).

:meth:`StradsEngine.execute` is the one way to run rounds.  It takes a
declarative :class:`~repro.core.plan.ExecutionPlan` — rounds, unrolling,
staleness, checkpoint cadence, scheduling policy, and the executor as a
plain value, all validated at plan construction — and returns a uniform
:class:`~repro.core.plan.ExecutionReport` (state, trace, telemetry,
resumable carry).  The executors share one traced round body:

* ``"loop"`` — one jitted round (:meth:`StradsEngine.run_round`) per
  dispatch, a host↔device sync every round, a Python callback between
  rounds.  The debugging/metrics path.
* ``"scan"`` — R rounds rolled into a single ``jax.lax.scan`` (one XLA
  program, donated state buffers, zero per-round host round-trips).
  Bit-identical to the loop: same PRNG stream, same op order.
* ``"pipelined"`` — the paper's pipelined scheduler: inside scan step t
  the schedule for round t+1 is computed from the state *before* round
  t's update, so it carries no data dependency on round t's push/pull
  and XLA is free to overlap the two (software pipelining).  The
  schedule each round executes is therefore exactly one round stale —
  the STRADS stale-schedule guarantee (Lee et al. 2014 §pipelining;
  dynamic Lasso keeps converging because priorities c_j change slowly
  between adjacent rounds).
* ``"ssp"`` — bounded staleness, implemented by the parameter-server
  subsystem in :mod:`repro.ps`: reads of replicated state served from
  worker caches up to s rounds old, pushes aggregated lazily into one
  batched flush collective per s+1-round window.  ``staleness=0`` is
  bit-identical to ``"scan"``.

:meth:`StradsEngine.scanned_fn` and :meth:`StradsEngine.ssp_fn` hand out
the compiled multi-round programs themselves, for AOT
``.lower().compile()``.

Apps whose communication pattern cycles with period L (``phase_period``,
e.g. LDA's rotation over U workers, MF's H/W alternation) get L rounds
unrolled per scan step so every ``phase`` stays a static Python int (the
LDA ``ppermute`` needs a static permutation).

Scheduling policy is **injected** (the v2 scheduler-injection contract,
:mod:`repro.core.primitives`): the engine resolves
``plan.scheduler`` — or the app's ``default_scheduler_spec()`` — into a
:class:`~repro.sched.protocol.Scheduler` and hands it to the app before
tracing.  The scheduler's on-device state (e.g.
``DynamicPriorityScheduler``'s Δx history) is the engine-owned
**scheduler carry**: created by ``scheduler.init_carry()``, threaded
through every executor's scan carry, folded forward by the app's
``sched_update`` after each committed round, and returned (and resumed)
as :attr:`EngineCarry.sched_carry` — never an app-state stowaway, so it
checkpoints through ``checkpoint/npz`` with the PRNG stream and round
counter.

Partition policy is injected the same way (the partitioning contract,
:mod:`repro.core.primitives`): ``plan.partitioner`` — or the app's
``default_partitioner_spec()`` — resolves to a
:class:`~repro.part.protocol.Partitioner` whose variable→worker
:class:`~repro.part.assignment.Assignment` the engine owns.  Repartition
checks run host-side at the ``checkpoint_every`` chunk boundaries of
:meth:`StradsEngine.execute` (state is synced there, so a move is a
``KVStore.repartition`` re-placement); compiled-program caches are keyed
per (SchedulerSpec, Assignment, KernelSpec), and the assignment +
activity stats ride the ``{"state", "carry", "assignment"}`` checkpoint
payload (resumed via ``execute(..., partition=...)``).

Kernel backends complete the injection triple (the kernel-injection
contract, :mod:`repro.core.primitives`): ``plan.kernels`` — or the app's
``default_kernel_spec()``, falling back to ``kind="reference"`` —
resolves via ``repro.kernels.build_kernels`` into a backend object the
app's ``push``/``schedule_stats`` dispatch their hot-spots through
(``self.kernels.lasso_partial`` / ``.gram_block``).  The backend is
stateless (no carry, no checkpoint payload); it only changes what the
traced round lowers to — fused Pallas kernels on TPU, interpret-mode
automatically elsewhere, the pure-jnp oracles for ``"reference"``.

The engine runs identically on a single device (unit tests, laptop-scale
experiments) and on multi-chip meshes; the production 256/512-chip
lowering is exercised by ``launch/dryrun.py`` (``--engine`` mode for this
executor).
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import warnings
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..kernels import KernelSpec, build_kernels
from ..obs import RunReport, counters as obs_counters
from ..obs.events import Recorder, active, recording
from ..part import Assignment, PartitionerSpec, build_partitioner
from ..sched import SchedulerSpec, build_scheduler
from .compat import make_mesh, shard_map
from .kvstore import KVStore, store_from_tree
from .plan import ExecutionPlan, ExecutionReport
from .primitives import RoundResult, StradsApp, StradsAppBase, tree_psum

DATA_AXIS = "data"

_UNSET = object()
_NULL_CTX = contextlib.nullcontext()   # reusable no-op span


def _replicate_spec(tree: Any) -> Any:
    return jax.tree.map(lambda _: P(), tree)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EngineCarry:
    """Resumable carry of the loop/scanned executors: PRNG stream, next
    round index, the engine-owned scheduler carry (e.g. the Δx priority
    history; ``None`` for stateless policies), (pipelined only) the
    in-flight prefetched schedule, and — under a plan-level
    :class:`~repro.obs.spec.TelemetrySpec` — the device telemetry
    counters (:mod:`repro.obs.counters`; ``None`` uninstrumented, so an
    instrumented carry checkpoints/resumes the counters bit-exactly
    through ``checkpoint_every`` chunking while old checkpoints restore
    unchanged).  The SSP twin (with vector clocks) is
    :class:`repro.ps.ssp.SSPCarry`; both round-trip through
    ``checkpoint/npz``."""
    rng: jax.Array
    t: jax.Array                  # int32: next round index
    sched: Any = None             # depth-1 prefetched schedule (else None)
    sched_carry: Any = None       # scheduler carry (Δx history, …)
    obs: Any = None               # device telemetry counters (or None)


class StradsEngine:
    """Compiles a StradsApp into BSP round programs on a device mesh.

    Parameters
    ----------
    app:         the STRADS application.
    mesh:        device mesh with a ``data`` axis (workers = shards).
    data_specs:  PartitionSpec pytree for the data (the paper's 1/P split).
    state_specs: PartitionSpec pytree for model state.  Replicated leaves
                 (``P()``) behave like the paper's synced KV-store values;
                 sharded leaves are worker-local model partitions (model
                 parallelism — the Fig-3 memory win).
    scheduler:   optional :class:`~repro.sched.spec.SchedulerSpec`
                 overriding the app's ``default_scheduler_spec()`` from
                 construction time (``execute`` re-resolves per plan).
    partitioner: optional :class:`~repro.part.spec.PartitionerSpec`
                 overriding the app's ``default_partitioner_spec()``
                 the same way (plan > constructor > app).
    kernels:     optional :class:`~repro.kernels.spec.KernelSpec`
                 overriding the app's ``default_kernel_spec()`` the same
                 way (plan > constructor > app > ``reference``).
    """

    def __init__(self, app: StradsApp, mesh: Mesh, data_specs: Any,
                 state_specs: Any = None,
                 scheduler: Optional[SchedulerSpec] = None,
                 partitioner: Optional[PartitionerSpec] = None,
                 kernels: Optional[KernelSpec] = None):
        self.app = app
        self.mesh = mesh
        self.data_specs = data_specs
        self.state_specs = state_specs
        self._scan_cache: dict = {}
        self._active_spec: Optional[SchedulerSpec] = None
        self._round = None
        # a constructor spec outranks the app default whenever a plan
        # leaves its scheduler field None (plan > constructor > app)
        self._spec_override = scheduler
        self._part_override = partitioner
        self._kern_override = kernels
        self._active_part_spec: Optional[PartitionerSpec] = None
        self._active_kern_spec: Optional[KernelSpec] = None
        self.partitioner = None
        self._assignment: Optional[Assignment] = None
        self._part_stats = None
        self.set_kernels(None)    # before set_scheduler's first round-bind
        self.set_scheduler(None)
        self.set_partitioner(None)
        self.kvstore: Optional[KVStore] = None   # built by place_state

    # -- observability hooks (the telemetry-injection contract) --------------

    def _obs_event(self, name: str, **args):
        """Record a host event into the active Recorder (one made active
        for the run, or by a ``kind="trace"`` ``execute``) — a no-op
        otherwise, so event sites cost nothing uninstrumented."""
        rec = active()
        if rec is not None:
            rec.instant(name, **args)

    def _obs_span(self, name: str, **args):
        """A wall-clock phase span in the active Recorder, else a
        null context."""
        rec = active()
        if rec is not None:
            return rec.span(name, **args)
        return _NULL_CTX

    def _observe(self, obs, data, sched, phase: int, num_cand: int):
        """Fold one round into the device counters, with the app's
        ``obs_counts(data, sched, phase)`` (visited, updated) where it
        has the hook — it reads only the data and the schedule, so the
        instrumented round stays bit-identical."""
        hook = getattr(self.app, "obs_counts", None)
        counts = hook(data, sched, phase) if hook is not None else None
        return obs_counters.observe_round(obs, sched, phase, num_cand,
                                          counts)

    def _obs_num_candidates(self) -> int:
        """The active scheduler's static proposal-pool size U′ (0 for
        policies without one) — the ρ-filter ledger's 'proposed' term."""
        return int(getattr(self.scheduler, "num_candidates", 0) or 0)

    # -- scheduler injection (the v2 contract) -------------------------------

    def set_scheduler(self, spec: Optional[SchedulerSpec] = None):
        """Resolve a :class:`~repro.sched.spec.SchedulerSpec` (``None`` →
        the engine's constructor spec, else the app's
        ``default_scheduler_spec()``) into a
        :class:`~repro.sched.protocol.Scheduler`, inject it into the app,
        and rebind the traced round programs.  Idempotent for an
        unchanged spec, and compiled programs are cached per spec, so
        swapping policies back and forth never recompiles.  Returns the
        active scheduler (or ``None`` for self-scheduling apps)."""
        if spec is None:
            spec = self._spec_override
        resolved = spec if spec is not None else self._default_spec()
        if resolved == self._active_spec and self._round is not None:
            return self.scheduler
        sched = None
        if resolved is not None:
            kinds = getattr(self.app, "supported_scheduler_kinds", None)
            if kinds is not None and resolved.kind not in kinds:
                raise ValueError(
                    f"{type(self.app).__name__} cannot consume a "
                    f"{resolved.kind!r} scheduler (it supports "
                    f"{sorted(kinds)}); fix the plan's SchedulerSpec")
            sched = build_scheduler(
                resolved, num_vars=self.app.num_schedulable(),
                num_workers=self.mesh.shape[DATA_AXIS])
        if hasattr(self.app, "use_scheduler"):
            self.app.use_scheduler(sched)
        else:
            # protocol-only apps: always (re)assign, so resolving back
            # to a spec-less policy actually clears the old scheduler
            self.app.scheduler = sched
        self._active_spec = resolved
        self._needs_stats = getattr(
            self.app, "needs_schedule_stats",
            type(self.app).schedule_stats
            is not StradsAppBase.schedule_stats)
        # Compiled programs are cached PER SPEC and PER ASSIGNMENT
        # (every _scan_cache key carries both), so swapping policies —
        # a plan sweep — or rebalancing the partition reuses each
        # configuration's compiled programs instead of recompiling on
        # every switch.
        self._rebind_round()
        return sched

    def _rebind_round(self):
        """(Re)fetch the traced round program for the active
        (SchedulerSpec, Assignment, KernelSpec) triple — called whenever
        any of them changes, so a stale program can never serve a new
        policy, a moved partition, or a swapped kernel backend."""
        key = ("round", self._active_spec, self._assignment,
               self._active_kern_spec)
        self._round = self._scan_cache.get(key)
        if self._round is None:
            self._obs_event("cache_miss", program="round",
                            **self._cache_key_args())
            self._round = self._build_round()
            self._scan_cache[key] = self._round

    def _cache_key_args(self) -> dict:
        """The (SchedulerSpec, Assignment, KernelSpec) compiled-program
        cache key, JSON-safe — what cache-miss events carry."""
        asgn = self._assignment
        return {
            "scheduler": (self._active_spec.kind
                          if self._active_spec is not None else None),
            "assignment_version": (asgn.version if asgn is not None
                                   else None),
            "kernels": (self._active_kern_spec.kind
                        if self._active_kern_spec is not None else None),
        }

    def _default_spec(self) -> Optional[SchedulerSpec]:
        fn = getattr(self.app, "default_scheduler_spec", None)
        return fn() if callable(fn) else None

    @property
    def scheduler(self):
        """The injected :class:`~repro.sched.protocol.Scheduler` (``None``
        for apps that schedule themselves)."""
        return getattr(self.app, "scheduler", None)

    @property
    def scheduler_spec(self) -> Optional[SchedulerSpec]:
        """The resolved spec of the active scheduler (for artifacts)."""
        return self._active_spec

    def init_sched_carry(self):
        """A fresh engine-owned scheduler carry (``None`` when the policy
        is stateless or the app self-schedules)."""
        sched = self.scheduler
        return sched.init_carry() if sched is not None else None

    def mark_sched_carry(self, carry, candidates):
        """The SSP in-flight exclusion over the scheduler carry (identity
        without an injected scheduler — state-resident priority tables go
        through :class:`~repro.core.kvstore.VarTable` instead)."""
        sched = self.scheduler
        return (sched.mark_scheduled(carry, candidates)
                if sched is not None else carry)

    # -- partition injection (the partitioning contract) ---------------------

    def set_partitioner(self, spec: Optional[PartitionerSpec] = None):
        """Resolve a :class:`~repro.part.spec.PartitionerSpec` (``None``
        → the engine's constructor spec, else the app's
        ``default_partitioner_spec()``) into a
        :class:`~repro.part.protocol.Partitioner`, inject its initial
        variable→worker assignment into the app, and rebind the traced
        round programs.  Idempotent for an unchanged spec — crucially,
        it then *keeps* the current assignment and activity stats, so a
        resumed run continues the partition trajectory instead of
        resetting it.  Returns the active partitioner (or ``None`` for
        apps with no partition story)."""
        if spec is None:
            spec = self._part_override
        resolved = spec if spec is not None else self._default_part_spec()
        if resolved == self._active_part_spec:
            return self.partitioner
        if resolved is None:
            self.partitioner = None
            self._active_part_spec = None
            self._part_stats = None
            self._install_assignment(None)
            return None
        kinds = getattr(self.app, "supported_partitioner_kinds", None)
        if kinds is not None and resolved.kind not in kinds:
            raise ValueError(
                f"{type(self.app).__name__} cannot host a "
                f"{resolved.kind!r} partitioner (it supports "
                f"{sorted(kinds)}); fix the plan's PartitionerSpec")
        if resolved.kind == "load_balanced" \
                and not self._has_partition_signal():
            raise ValueError(
                f"kind='load_balanced' needs a per-variable activity "
                f"signal, but {type(self.app).__name__} does not define "
                f"partition_signal(state); declare one (see "
                f"repro.core.primitives) or use a static kind")
        sizes_fn = getattr(self.app, "partition_sizes", None)
        part = build_partitioner(
            resolved, num_vars=self.app.num_schedulable(),
            num_workers=self.mesh.shape[DATA_AXIS],
            sizes=sizes_fn() if callable(sizes_fn) else None)
        self.partitioner = part
        self._active_part_spec = resolved
        self._part_stats = part.init_stats()
        self._install_assignment(part.init_assignment())
        return part

    def _default_part_spec(self) -> Optional[PartitionerSpec]:
        fn = getattr(self.app, "default_partitioner_spec", None)
        return fn() if callable(fn) else None

    def _has_partition_signal(self) -> bool:
        fn = getattr(type(self.app), "partition_signal", None)
        return (fn is not None
                and fn is not StradsAppBase.partition_signal)

    def _install_assignment(self, assignment: Optional[Assignment]):
        self._assignment = assignment
        if hasattr(self.app, "use_partition"):
            self.app.use_partition(assignment)
        else:
            self.app.assignment = assignment
        self._rebind_round()

    @property
    def partitioner_spec(self) -> Optional[PartitionerSpec]:
        """The resolved spec of the active partitioner (for artifacts)."""
        return self._active_part_spec

    @property
    def partition_assignment(self) -> Optional[Assignment]:
        """The active variable→worker assignment (``None`` without a
        partitioner)."""
        return self._assignment

    @property
    def partition_stats(self):
        """The partitioner's host-side activity state (the load
        balancer's per-variable EMA; ``None`` for stateless kinds)."""
        return self._part_stats

    def reset_partition(self):
        """Back to the partitioner's initial assignment and fresh stats
        — what a fresh (carry-less, payload-less) ``execute`` does, so
        rebalances from a previous run can never leak into a new one."""
        part = self.partitioner
        if part is None:
            return
        self._part_stats = part.init_stats()
        init = part.init_assignment()
        if init != self._assignment:
            self._install_assignment(init)

    def apply_assignment(self, assignment: Assignment, state: Any = None):
        """Adopt a new assignment mid-run: the KV store re-derives its
        VarSpecs and re-places the worker-resident leaves
        (:meth:`~repro.core.kvstore.KVStore.repartition` — byte
        accounting stays truthful), the app receives the move via
        ``use_partition``, and the traced-program binding is refreshed
        (compiled caches are keyed per assignment, so this is one cache
        miss the first time and a hit ever after).  Returns the
        re-placed state when one is passed."""
        out = None
        if self.kvstore is not None:
            out = self.kvstore.repartition(assignment, state)
        elif state is not None:
            out = state
        self._install_assignment(assignment)
        return out

    def partition_payload(self) -> Optional[dict]:
        """The ``"assignment"`` subtree of a chunked run's
        ``{"state", "carry", "assignment"}`` checkpoint: the assignment
        arrays plus the partitioner's activity stats, flat for
        ``checkpoint/npz``.  ``None`` without a partitioner."""
        if self._assignment is None:
            return None
        payload = dict(self._assignment.payload())
        if isinstance(self._part_stats, dict):
            for k, v in self._part_stats.items():
                payload[f"stats_{k}"] = np.asarray(v)
        return payload

    def restore_partition(self, payload: dict):
        """Resume the partition trajectory from a checkpoint's
        ``"assignment"`` payload (``execute(..., partition=...)``): the
        saved assignment is re-applied and the activity stats restored,
        so the resumed run replays the remaining rebalance decisions
        bit-exactly."""
        if self.partitioner is None:
            raise ValueError(
                "restore_partition needs an active partitioner (the "
                "plan/app resolved none) — was this checkpoint written "
                "under a different plan?")
        asgn = Assignment.from_payload(
            {k: payload[k] for k in ("owner", "num_workers", "version")})
        num_workers = self.mesh.shape[DATA_AXIS]
        if asgn.num_workers != num_workers:
            raise ValueError(
                f"checkpointed assignment spans {asgn.num_workers} "
                f"workers but the engine mesh has {num_workers}")
        num_vars = self.partitioner.num_vars
        if asgn.num_vars != num_vars:
            raise ValueError(
                f"checkpointed assignment covers {asgn.num_vars} "
                f"variables but this app partitions {num_vars} — was "
                f"this checkpoint written for a different model size?")
        stats = {k[len("stats_"):]: np.asarray(v)
                 for k, v in payload.items() if k.startswith("stats_")}
        fresh = self.partitioner.init_stats()
        if (stats or fresh is not None) and set(stats) != \
                set(fresh or {}):
            raise ValueError(
                f"checkpointed partition stats {sorted(stats)} do not "
                f"match the resolved {self._active_part_spec.kind!r} "
                f"partitioner's {sorted(fresh or {})} — the "
                f"PartitionerSpec must match across resume")
        if stats:
            self._part_stats = stats
        self.apply_assignment(asgn)

    def _partition_signal_snapshot(self, state) -> Optional[np.ndarray]:
        """Host copy of the app's per-variable partition signal (taken
        *before* a chunk runs — donation consumes the device buffers)."""
        if self.partitioner is None:
            return None
        fn = getattr(self.app, "partition_signal", None)
        sig = fn(state) if callable(fn) else None
        if sig is None:
            return None
        return np.array(jax.device_get(sig))

    def _partition_step(self, state, sig_before, t: int,
                        allow_move: bool = True):
        """One chunk-boundary partition check: fold the chunk's observed
        activity |Δsignal| into the partitioner's stats, and rebalance
        (re-place + rebind) when the policy says so.  Host-side — state
        is already synced here.  Returns ``(state, sig_after)`` so the
        caller reuses the chunk-end snapshot as the next chunk's
        baseline instead of re-fetching it (``sig_before=None`` — no
        stateful policy or no app signal — skips the snapshot
        entirely).  ``allow_move=False`` still measures but never
        rebalances — the final chunk boundary, where a move would
        produce an assignment no round ever runs under."""
        part = self.partitioner
        sig_after = (self._partition_signal_snapshot(state)
                     if sig_before is not None else None)
        activity = (np.abs(sig_after - sig_before)
                    if sig_after is not None else None)
        self._part_stats = part.measure(self._part_stats,
                                        self._assignment, activity)
        if allow_move and part.should_rebalance(
                self._part_stats, self._assignment, t):
            new = part.propose_assignment(self._part_stats,
                                          self._assignment)
            if new.owner != self._assignment.owner:
                # the rebalance event carries the measured before/after
                # load spreads (the imbalance the move was for)
                weights = (self._part_stats.get("ema")
                           if isinstance(self._part_stats, dict)
                           else None)
                if weights is not None:
                    self._obs_event(
                        "rebalance", t=t,
                        spread_before=self._assignment.spread(weights),
                        spread_after=new.spread(weights),
                        version=new.version)
                else:
                    self._obs_event("rebalance", t=t,
                                    version=new.version)
                # re-placement keeps leaf values, so sig_after stays a
                # valid baseline for the next chunk
                state = self.apply_assignment(new, state)
        return state, sig_after

    # -- kernel injection (the kernel-injection contract) --------------------

    def set_kernels(self, spec: Optional[KernelSpec] = None):
        """Resolve a :class:`~repro.kernels.spec.KernelSpec` (``None``
        → the engine's constructor spec, else the app's
        ``default_kernel_spec()``, else ``kind="reference"`` — the
        bit-identical pre-KernelSpec round body) into an executable
        backend (``repro.kernels.build_kernels``: Pallas for Mosaic on
        TPU, interpret-mode automatically elsewhere), inject it into the
        app, and rebind the traced round programs.  Idempotent for an
        unchanged spec, and compiled programs are cached per spec, so a
        reference↔pallas sweep never recompiles.  Returns the active
        backend."""
        if spec is None:
            spec = self._kern_override
        resolved = spec if spec is not None else self._default_kern_spec()
        if resolved is None:
            resolved = KernelSpec(kind="reference")
        if resolved == self._active_kern_spec and self._round is not None:
            return self.kernels
        kinds = getattr(self.app, "supported_kernel_kinds", None)
        if kinds is not None and resolved.kind not in kinds:
            raise ValueError(
                f"{type(self.app).__name__} cannot dispatch a "
                f"{resolved.kind!r} kernel backend (it supports "
                f"{sorted(kinds)}); fix the plan's KernelSpec")
        backend = build_kernels(resolved)
        if hasattr(self.app, "use_kernels"):
            self.app.use_kernels(backend)
        else:
            # protocol-only apps: assign directly, mirroring the
            # scheduler fallback
            self.app.kernels = backend
        self._active_kern_spec = resolved
        # The very first round-bind belongs to set_scheduler (it also
        # derives _needs_stats); during __init__ this runs before the
        # scheduler exists, so only REbind here.
        if self._round is not None:
            self._rebind_round()
        return backend

    def _default_kern_spec(self) -> Optional[KernelSpec]:
        fn = getattr(self.app, "default_kernel_spec", None)
        return fn() if callable(fn) else None

    @property
    def kernels(self):
        """The injected kernel backend (never ``None`` once the engine
        is constructed — ``reference`` is the floor)."""
        return getattr(self.app, "kernels", None)

    @property
    def kernel_spec(self) -> Optional[KernelSpec]:
        """The resolved spec of the active kernel backend (for
        artifacts)."""
        return self._active_kern_spec

    # -- traced round pieces (shared by every executor) ---------------------

    @property
    def phase_period(self) -> int:
        """Length of the app's static-phase cycle (1 = phaseless)."""
        return int(getattr(self.app, "phase_period", 1))

    def _sspec(self, state):
        return (_replicate_spec(state) if self.state_specs is None
                else self.state_specs)

    def _make_schedule(self, state, carry, data, rng, t, phase):
        """propose → [schedule_stats → psum] → schedule (replicated)."""
        app = self.app
        r1, r2 = jax.random.split(rng)
        with jax.named_scope("propose"):
            cand = app.propose(state, carry, r1, t, phase)
        if self._needs_stats:
            def stats_fn(data, state, cand):
                s = app.schedule_stats(data, state, cand, phase)
                return tree_psum(s, DATA_AXIS)
            with jax.named_scope("schedule_stats"):
                stats = shard_map(
                    stats_fn, mesh=self.mesh,
                    in_specs=(self.data_specs, self._sspec(state),
                              _replicate_spec(cand)),
                    out_specs=P(),
                )(data, state, cand)
        else:
            stats = None
        with jax.named_scope("schedule"):
            return app.schedule(state, carry, cand, stats, r2, t, phase)

    def _apply(self, state, data, sched, phase):
        """push → psum → pull under shard_map (the BSP update + sync)."""
        app = self.app
        sspec = self._sspec(state)

        def push_pull(data, state, sched):
            with jax.named_scope("push"):
                z, local = app.push(data, state, sched, phase)
            with jax.named_scope("aggregate"):
                z = tree_psum(z, DATA_AXIS)  # pull aggregation Σ_p z^p
            with jax.named_scope("pull"):
                return app.pull(state, sched, z, local, data, phase)

        return shard_map(
            push_pull, mesh=self.mesh,
            in_specs=(self.data_specs, sspec, _replicate_spec(sched)),
            out_specs=sspec,
        )(data, state, sched)

    def _sched_update(self, carry, before, after, sched, phase):
        fn = getattr(self.app, "sched_update", None)
        if fn is None:
            return carry
        with jax.named_scope("sched_update"):
            return fn(carry, before, after, sched, phase)

    def _build_round(self):
        @partial(jax.jit, static_argnums=(4,))
        def round_fn(state, carry, data, rng, phase, t):
            sched = self._make_schedule(state, carry, data, rng, t, phase)
            new_state = self._apply(state, data, sched, phase)
            new_carry = self._sched_update(carry, state, new_state, sched,
                                           phase)
            return RoundResult(state=new_state, sched=sched,
                               sched_carry=new_carry)

        return round_fn

    # -- placement helpers ---------------------------------------------------

    def init_state(self, rng: jax.Array, **app_kwargs):
        """Initialize the app state and place it through the KV store
        (extra keyword args go to ``app.init_state`` — e.g. the Lasso
        residual seed ``y``)."""
        with self._obs_span("init_state"):
            with self._obs_span("app.init_state"):
                state = self.app.init_state(rng, **app_kwargs)
            return self.place_state(state)

    def app_roles(self) -> dict:
        """The app's declarative VarSpec role map (``var_roles()``; see
        :class:`~repro.core.kvstore.VarSpec` — ``"priority"`` leaves the
        SSP window scheduler masks for in-flight exclusion when an app
        keeps its priority table in state rather than the engine carry)."""
        fn = getattr(self.app, "var_roles", None)
        return dict(fn()) if callable(fn) else {}

    def place_state(self, state):
        """Place a state pytree via :class:`~repro.core.kvstore.KVStore`
        — the single source of variable placement and byte accounting
        (``self.kvstore`` afterwards answers Fig-3-style questions like
        ``bytes_per_device()``, and ``repro.ps`` derives the server-/
        worker-resident split from the same VarSpecs)."""
        with self._obs_span("place_state"):
            self.kvstore = store_from_tree(self.mesh, state,
                                           self._sspec(state),
                                           roles=self.app_roles())
            return self.kvstore.place_tree(state)

    def replicate(self, tree):
        """Commit a carry pytree (PRNG key, scheduler carry, counters,
        clocks) replicated over the mesh — the placement every executor
        returns it in — so a fresh run and a run resumed from a carry
        hand the compiled program the same input shardings and share
        one compilation."""
        return jax.device_put(tree, NamedSharding(self.mesh, P()))

    def shard_data(self, data):
        with self._obs_span("shard_data"):
            return jax.tree.map(
                lambda x, s: jax.device_put(x, NamedSharding(self.mesh, s)),
                data, self.data_specs)

    # -- execution: the round and the compiled programs --------------------

    def run_round(self, state, data, rng, t: int = 0,
                  sched_carry: Any = _UNSET) -> RoundResult:
        """One jitted BSP round — the loop executor's body.
        ``sched_carry`` defaults to a fresh ``scheduler.init_carry()``;
        thread ``result.sched_carry`` back in to keep a stateful policy's
        priorities evolving across rounds (omitting it at t > 0 warns —
        the priorities silently reset to uniform, which is almost never
        what a round loop wants; use :meth:`execute` for whole runs)."""
        phase = self.app.static_phase(t)
        if sched_carry is _UNSET:
            sched_carry = self.init_sched_carry()
            if t and sched_carry is not None:
                warnings.warn(
                    "run_round(t>0) without sched_carry reinitializes "
                    "the stateful scheduler's priorities every call; "
                    "thread result.sched_carry between rounds (or drive "
                    "the run through execute)", UserWarning,
                    stacklevel=2)
        return self._round(state, sched_carry, data, rng, phase,
                           jnp.int32(t))

    def scanned_fn(self, num_rounds: int, *, pipeline_depth: int = 0,
                   collect: Optional[Callable] = None,
                   donate: bool = True, unroll: int = 1):
        """The jitted ``(state, data, rng, t0, sched_carry, obs) →
        (state, rng, sched, sched_carry, obs, trace)`` multi-round
        program of the scan (``pipeline_depth=0``) or pipelined (``1``)
        executor, exposed for AOT ``.lower().compile()`` (the
        production-mesh dry-run in ``launch/dryrun.py``; pass
        ``engine.init_sched_carry()`` for a fresh run and ``None`` —
        or ``repro.obs.init_counters(engine.phase_period)`` — for
        ``obs``).  ``num_rounds`` must be a multiple of ``phase_period
        × unroll``."""
        num_steps, tail = divmod(num_rounds, self.phase_period * unroll)
        if tail or num_steps == 0:
            raise ValueError(
                f"num_rounds must be a positive multiple of phase_period "
                f"× unroll ({self.phase_period * unroll}); got "
                f"{num_rounds}")
        # pin the handle to the active policy: it traces lazily, and a
        # set_scheduler swap between fetch and first call would
        # otherwise bake the wrong scheduler into the per-spec cache
        return _SpecBoundFn(self, self._active_spec,
                            self._get_scan_fn(num_steps, pipeline_depth,
                                              collect, donate, unroll,
                                              False))

    def ssp_fn(self, num_rounds: int, *, staleness: int = 0,
               collect: Optional[Callable] = None, donate: bool = True):
        """The jitted multi-round SSP program, exposed for AOT
        ``.lower().compile()`` (``launch/dryrun.py --engine --staleness``).
        """
        from ..ps.ssp import ssp_fn
        return _SpecBoundFn(self, self._active_spec,
                            ssp_fn(self, num_rounds, staleness=staleness,
                                   collect=collect, donate=donate))

    # -- execution: the one entry point ---------------------------------------

    def execute(self, state, data, rng, plan: ExecutionPlan, *,
                collect: Optional[Callable[[Any], Any]] = None,
                callback=None, carry=None,
                ckpt_dir: Optional[str] = None,
                partition: Optional[dict] = None,
                stream=None, source=None,
                stream_state: Optional[dict] = None) -> ExecutionReport:
        """Run an :class:`~repro.core.plan.ExecutionPlan` — the one way
        to run rounds, whichever executor the plan names — and return a
        uniform :class:`~repro.core.plan.ExecutionReport`.

        ``plan.scheduler`` (a :class:`~repro.sched.spec.SchedulerSpec`)
        selects the scheduling policy; ``None`` resolves to the app's
        ``default_scheduler_spec()``.  Either way the resolved scheduler
        is injected before tracing and its carry is threaded through the
        run (and the report's resumable ``carry``).

        ``collect(state) -> pytree`` is evaluated after every executed
        round (the report's ``trace`` stacks the results).  ``callback(t,
        state, round_result)`` is the host-loop hook and therefore
        requires ``executor="loop"`` (return True to stop early).

        ``carry`` resumes a previous report's run of the *same* plan:
        rounds ``carry.t .. plan.rounds`` execute with the carried PRNG
        stream/clocks/scheduler carry/prefetched schedule, so an
        interrupted run matches an uninterrupted one bit-for-bit (``rng``
        is taken from the carry and the argument is ignored).

        ``plan.partitioner`` (a :class:`~repro.part.spec.PartitionerSpec`)
        selects the partition policy the same way (``None`` resolves to
        the app's ``default_partitioner_spec()``).  The resolved
        partitioner owns the variable→worker assignment; repartition
        checks run at the chunk boundaries below (state is host-synced
        there — see the partitioning contract in
        :mod:`repro.core.primitives`).  A fresh run (no ``carry``)
        starts from the partitioner's initial assignment; resuming
        passes the checkpoint's ``"assignment"`` payload as
        ``partition=`` so the trajectory continues bit-exactly.

        ``ckpt_dir`` + ``plan.checkpoint_every`` chunk the run and save a
        ``{"state", "carry"}`` checkpoint (plus ``"assignment"`` when a
        partitioner is active, plus ``"stream"`` when streaming) via
        :mod:`repro.checkpoint` every ``checkpoint_every`` rounds (the
        cadence must tile the executor's step length; each chunk reuses
        one compiled program).

        ``stream`` (a :class:`~repro.stream.spec.StreamSpec`) +
        ``source`` (a :class:`~repro.stream.source.DataSource`) ingest
        data deltas at the host-synced boundaries ``t %
        stream.ingest_every == 0`` — the streaming-injection surface
        (see the ingest contract in :mod:`repro.core.primitives`).
        Like ``ServeSpec`` it rides this entry point, never the plan,
        so it can't be silently ignored.  An empty source is
        bit-identical to not passing ``stream`` at all.  ``stream_state``
        resumes the ring cursor from a checkpoint's ``"stream"``
        payload (pair it with :func:`repro.stream.replay_data` when the
        resumed process no longer holds the streamed data pytree).
        """
        if not isinstance(plan, ExecutionPlan):
            raise TypeError(f"execute() wants an ExecutionPlan; got "
                            f"{type(plan).__name__}")
        # telemetry (the telemetry-injection contract): the resolved
        # TelemetrySpec turns on device counters for every executor;
        # kind="trace" records this call's spans and events (cache
        # misses, rebalances, checkpoints, compiles, collections) into
        # the active Recorder, or into one of its own made active for the
        # call.  The final report's .telemetry is a uniform RunReport,
        # built without waiting for the device.
        tspec = plan.telemetry or None
        own = (Recorder(profiler=tspec.profiler)
               if tspec is not None and tspec.events and active() is None
               else None)
        with recording(own) if own is not None else _NULL_CTX:
            rec = active()
            mark = rec.mark() if rec is not None else 0
            with self._obs_span("execute", executor=plan.executor,
                                rounds=plan.rounds):
                with self._obs_span("execute.resolve"):
                    t_done, rng, chunk, pspec, ingestor = self._resolve(
                        plan, state, data, rng, carry, callback, ckpt_dir,
                        partition, stream, source, stream_state)
                rep = self._execute_plan(state, data, rng, plan, t_done,
                                         carry, collect, callback, chunk,
                                         pspec, ckpt_dir, ingestor)
            events = (rec.events_since(mark)
                      if tspec is not None and tspec.events else [])
        if tspec is None:
            rep.telemetry = None
            return rep
        parts = rep.telemetry           # per-chunk SSP summaries
        if len(parts) > 1:
            from ..ps.telemetry import merge_summaries
            ssp = merge_summaries(parts)
        else:
            ssp = parts[0] if parts else None
        # the host's round index: every executor runs the plan to its end
        # but a host loop a callback stopped (whose carry.t is a host int)
        rounds = (int(rep.carry.t) if callback is not None
                  else plan.rounds)
        rep.telemetry = RunReport.build(
            tspec, plan.executor, rounds,
            device_counters=getattr(rep.carry, "obs", None), ssp=ssp,
            events=events)
        return rep

    def _resolve(self, plan: ExecutionPlan, state, data, rng, carry,
                 callback, ckpt_dir, partition, stream, source,
                 stream_state):
        """``execute``'s checks and policy resolution before any round
        runs: the set_* calls, the carry checks, the checkpoint and
        stream arguments.  Returns ``(t_done, rng, chunk, pspec,
        ingestor)``."""
        num_workers = self.mesh.shape[DATA_AXIS]
        if plan.workers is not None and plan.workers != num_workers:
            raise ValueError(
                f"plan.workers={plan.workers} but the engine mesh has "
                f"{num_workers} '{DATA_AXIS}' shards")
        if callback is not None and plan.executor != "loop":
            raise ValueError("callback is a host-loop hook; it requires "
                             f"executor='loop' (got {plan.executor!r})")
        self.set_scheduler(plan.scheduler)
        self.set_partitioner(plan.partitioner)
        self.set_kernels(plan.kernels)
        if partition is not None:
            self.restore_partition(partition)
        elif carry is None:
            # fresh run: rebalances from a previous execute of the same
            # spec must not leak in (in-process resumes keep them)
            self.reset_partition()
        t_done = 0
        if carry is not None:
            if plan.executor == "ssp" and not hasattr(carry, "clocks"):
                raise ValueError("resuming an ssp plan needs the SSPCarry "
                                 "a previous ssp report returned")
            if plan.executor in ("scan", "pipelined") \
                    and not hasattr(carry, "sched"):
                raise ValueError("resuming a scanned plan needs the "
                                 "EngineCarry a previous scan/pipelined "
                                 "report returned")
            if plan.executor == "scan" and carry.sched is not None:
                raise ValueError("carry.sched holds a pipelined run's "
                                 "in-flight schedule; resume it with "
                                 "executor='pipelined'")
            stateful = self.init_sched_carry() is not None
            prev_sc = getattr(carry, "sched_carry", None)
            if stateful and prev_sc is None:
                raise ValueError(
                    "resuming this plan needs the scheduler carry, but "
                    "carry.sched_carry is None — was this carry produced "
                    "under a different (stateless) SchedulerSpec?")
            if not stateful and prev_sc is not None:
                raise ValueError(
                    "carry.sched_carry holds a stateful scheduler's "
                    "history, but the plan's resolved policy is "
                    "stateless — the SchedulerSpec must match across "
                    "resume")
            if plan.executor == "pipelined" and carry.sched is None \
                    and not self._schedules_nothing(state, data, prev_sc):
                raise ValueError("resuming a pipelined plan needs the "
                                 "carried in-flight schedule (carry.sched "
                                 "is None — was this carry produced by a "
                                 "different executor?)")
            t_done = int(carry.t)
            if not 0 <= t_done < plan.rounds:
                raise ValueError(f"carry.t={t_done} leaves no rounds of "
                                 f"the plan's {plan.rounds} to run")
            # a resumed run starts on a phase boundary (and, under ssp, a
            # window boundary), so every phase stays a static int: the
            # step length less the unrolling
            align = self._step_length(plan) // plan.phase_unroll
            if t_done % align:
                raise ValueError(
                    f"carry.t={t_done} is not a multiple of {align}: the "
                    f"{plan.executor!r} executor starts each span at a t0 "
                    f"on a phase/window boundary")
            rng = carry.rng

        if ckpt_dir and not plan.checkpoint_every:
            raise ValueError("ckpt_dir was passed but plan.checkpoint_"
                             "every=0 — no checkpoint would ever be "
                             "written; set a cadence in the plan")
        if plan.checkpoint_every and not ckpt_dir:
            raise ValueError("plan.checkpoint_every="
                             f"{plan.checkpoint_every} but no ckpt_dir "
                             "was passed — the run would silently never "
                             "checkpoint")
        chunk = plan.checkpoint_every if ckpt_dir else 0
        if (stream is None) != (source is None):
            raise ValueError("stream= (a StreamSpec) and source= (a "
                             "DataSource) come as a pair — got only one")
        ingestor = None
        if stream is not None:
            from ..stream import Ingestor
            ingestor = Ingestor(stream, source)
            if stream_state is not None:
                ingestor.restore(stream_state)
            ingestor.bind(self, data)
        elif stream_state is not None:
            raise ValueError("stream_state resumes a streamed run; pass "
                             "the stream=/source= pair with it")
        pspec = self._active_part_spec
        if chunk and pspec is not None and pspec.rebalance_every \
                and pspec.rebalance_every % chunk:
            raise ValueError(
                f"partitioner.rebalance_every={pspec.rebalance_every} "
                f"must be a multiple of plan.checkpoint_every={chunk} — "
                f"repartition checks only run at chunk boundaries, so a "
                f"misaligned cadence would silently (almost) never fire")
        return t_done, rng, chunk, pspec, ingestor

    def _execute_plan(self, state, data, rng, plan: ExecutionPlan,
                      t_done: int, carry, collect, callback, chunk: int,
                      pspec, ckpt_dir, ingestor=None) -> ExecutionReport:
        """The executor dispatch of :meth:`execute` — whole-plan, or the
        boundary-chunked loop (checkpoint cadence, ingest cadence, or
        their gcd when both are active).  The returned report's
        ``telemetry`` is the list of per-chunk
        :class:`~repro.ps.telemetry.SSPTelemetry` summaries (empty off
        ssp or uninstrumented); ``execute`` merges them into the final
        :class:`RunReport`."""
        step_len = self._step_length(plan)
        left = plan.rounds - t_done
        if plan.executor in ("pipelined", "ssp") and left % step_len:
            # fail before any chunk runs or checkpoint is written
            raise ValueError(
                f"plan.rounds={plan.rounds} leaves {left} rounds from "
                f"round {t_done}, not divisible by the {plan.executor!r} "
                f"executor's step length {step_len}: it runs whole steps "
                f"only, so those rounds must be a multiple of {step_len}")
        ing_every = ingestor.spec.ingest_every if ingestor is not None \
            else 0
        if not chunk and not ing_every:
            if pspec is not None and pspec.kind == "load_balanced":
                warnings.warn(
                    "a load_balanced partitioner only rebalances at "
                    "checkpoint chunk boundaries; without plan."
                    "checkpoint_every + ckpt_dir the assignment stays "
                    "at its initial (static) value for the whole run",
                    UserWarning, stacklevel=3)
            state, trace, telem, carry = self._execute_span(
                state, data, rng, plan, left, t_done, carry, collect,
                callback)
            return ExecutionReport(state=state, trace=trace,
                                   telemetry=[] if telem is None else [telem],
                                   carry=carry, plan=plan)
        if chunk and chunk % step_len:
            raise ValueError(
                f"plan.checkpoint_every={chunk} must be a multiple of the "
                f"{plan.executor!r} executor's step length {step_len} "
                f"(phase/window alignment), so every chunk resumes on a "
                f"step boundary")
        if ing_every and ing_every % step_len:
            raise ValueError(
                f"stream.ingest_every={ing_every} must be a multiple of "
                f"the {plan.executor!r} executor's step length {step_len} "
                f"(phase/window alignment), so every ingest boundary is "
                f"host-synced")
        # with both cadences active, spans run boundary to boundary; the
        # plain checkpointed run keeps span == chunk exactly as before
        span = (math.gcd(chunk, ing_every) if chunk and ing_every
                else (chunk or ing_every))
        from ..checkpoint import save_checkpoint
        stops: list = []                        # callback early-stop marker
        cb = callback
        if callback is not None:
            def cb(t, s, out, _orig=callback):
                r = _orig(t, s, out)
                if r:
                    stops.append(t)
                return r
        traces = []
        ssp_parts: list = []          # per-chunk SSPTelemetry summaries
        t = t_done
        # the activity baseline is only worth a host sync when a
        # stateful policy will consume it (static/size_balanced measure
        # nothing); one snapshot here, then each chunk reuses the
        # previous boundary's
        sig0 = (self._partition_signal_snapshot(state)
                if self._part_stats is not None else None)
        while t < plan.rounds:
            if ingestor is not None:
                # ingest-at-top / checkpoint-at-bottom: the checkpoint
                # at t precedes the ingest at t, so a resumed run
                # re-ingests boundary t exactly like the uninterrupted
                # one did
                state, data = ingestor.step(self, state, data, t)
            n = min(span, plan.rounds - t)
            state, trace, telem, carry = self._execute_span(
                state, data, rng, plan, n, t, carry, collect, cb)
            rng = carry.rng
            if trace is not None:
                traces.append(trace)
            if telem is not None:
                ssp_parts.append(telem)
            t = int(carry.t)
            at_chunk = (not chunk or t % chunk == 0 or t >= plan.rounds
                        or bool(stops))
            if self.partitioner is not None and at_chunk:
                # the repartition check rides the chunk boundary: state
                # is host-synced here, so a move is a re-placement (the
                # next chunk fetches programs under the new assignment;
                # after the LAST chunk there is no next chunk, so only
                # measure — never move)
                state, sig0 = self._partition_step(
                    state, sig0, t, allow_move=t < plan.rounds)
            if ckpt_dir and at_chunk:
                payload = {"state": state, "carry": carry}
                if self.partitioner is not None:
                    payload["assignment"] = self.partition_payload()
                if ingestor is not None:
                    payload["stream"] = ingestor.payload()
                with self._obs_span("checkpoint", t=t):
                    save_checkpoint(ckpt_dir, t, payload)
            if stops:                           # honored across chunks
                break
        trace = (jax.tree.map(lambda *xs: jnp.concatenate(xs), *traces)
                 if traces else None)
        return ExecutionReport(state=state, trace=trace,
                               telemetry=ssp_parts,
                               carry=carry, plan=plan,
                               stream=(ingestor.payload()
                                       if ingestor is not None else None))

    def _schedules_nothing(self, state, data, sched_carry) -> bool:
        """Whether the app's schedule is an empty pytree (LDA's rotation
        schedules by phase alone), so a pipelined run carries no
        in-flight schedule.  Traced abstractly: nothing runs."""
        shape = jax.eval_shape(
            lambda s, c, d: self._make_schedule(
                s, c, d, jax.random.key(0), jnp.int32(0), 0),
            state, sched_carry, data)
        return not jax.tree.leaves(shape)

    def _step_length(self, plan: ExecutionPlan) -> int:
        """Rounds one compiled step of the plan's executor covers — the
        alignment unit for checkpoint chunking and resume points."""
        if plan.executor == "ssp":
            from ..ps.ssp import rounds_per_step
            return rounds_per_step(self, plan.staleness)
        if plan.executor in ("scan", "pipelined"):
            # chunks smaller than a full scan step would silently degrade
            # to per-round host-loop tails (scan tolerates a tail, but
            # 'each chunk reuses one compiled program' would be a lie)
            return self.phase_period * plan.phase_unroll
        return 1                                # loop: any round

    def _execute_span(self, state, data, rng, plan: ExecutionPlan,
                      rounds: int, t0: int, prev_carry, collect, callback):
        """One contiguous span of a plan (the whole plan, or one
        checkpoint chunk), run by the executor it names.  ``execute``
        has validated the plan, ``t0`` and ``prev_carry`` already.
        Returns ``(state, trace, telemetry, carry)``: ``telemetry`` is
        the span's SSP summary, ``None`` off ssp or uninstrumented."""
        sc = (prev_carry.sched_carry if prev_carry is not None
              else self.init_sched_carry())
        # device counters: resume the previous chunk's (bit-exact through
        # checkpoint_every chunking), else start fresh when the plan is
        # instrumented; None runs uninstrumented
        obs = getattr(prev_carry, "obs", None)
        if obs is None and plan.telemetry:
            obs = obs_counters.init_counters(self.phase_period)
        if plan.executor == "loop":
            with self._obs_span("loop", t0=t0, rounds=rounds):
                return self._run_loop(state, data, rng, rounds, t0, sc,
                                      obs, collect, callback)
        if plan.executor == "ssp":
            from ..ps.ssp import run_span
            with self._obs_span("ssp", t0=t0, rounds=rounds,
                                staleness=plan.staleness):
                return run_span(self, state, data, rng, plan, rounds, t0,
                                getattr(prev_carry, "clocks", None), sc,
                                obs, collect)
        with self._obs_span(plan.executor, t0=t0, rounds=rounds):
            return self._run_scan(state, data, rng, plan, rounds, t0,
                                  getattr(prev_carry, "sched", None), sc,
                                  obs, collect)

    def _run_loop(self, state, data, rng, rounds: int, t0: int, sc, obs,
                  collect, callback):
        """The loop executor: one :meth:`run_round` dispatch a round,
        ``callback(t, state, result)`` after each (True stops)."""
        cfn = None
        if collect is not None:
            # cached so checkpoint-chunked loop runs compile it once
            key = ("loop_collect", collect)
            cfn = self._scan_cache.get(key)
            if cfn is None:
                cfn = jax.jit(collect)
                self._scan_cache[key] = cfn
        ys: list = []
        executed = 0
        num_cand = self._obs_num_candidates()
        period = self.phase_period
        for k in range(rounds):
            t = t0 + k
            rng, sub = jax.random.split(rng)
            out = self.run_round(state, data, sub, t, sched_carry=sc)
            state, sc = out.state, out.sched_carry
            if obs is not None:
                obs = self._observe(obs, data, out.sched, t % period,
                                    num_cand)
            executed = k + 1
            if cfn is not None:
                ys.append(cfn(state))
            if callback is not None and callback(t, state, out):
                break
        trace = _stack_rounds(ys) if ys else None
        carry = EngineCarry(rng=rng, t=jnp.int32(t0 + executed),
                            sched_carry=sc, obs=obs)
        return state, trace, None, carry

    def _run_scan(self, state, data, rng, plan: ExecutionPlan, rounds: int,
                  t0: int, sched, sc, obs, collect):
        """The scan and pipelined executors: whole steps of
        ``phase_period × phase_unroll`` rounds as one compiled program
        (``sched`` is a resumed pipelined run's in-flight schedule), then
        — scan only — the rounds left over through the loop executor."""
        L = self.phase_period * plan.phase_unroll
        num_steps, tail = divmod(rounds, L)
        traces = []
        if num_steps:
            with self._obs_span("scan.program"):
                fn = self._get_scan_fn(num_steps, plan.depth, collect,
                                       plan.donate, plan.phase_unroll,
                                       sched is not None)
            with self._obs_span("scan.replicate"):
                rng, sc, obs = self.replicate((rng, sc, obs))
                args = (state, data, rng, jnp.int32(t0), sc, obs)
                if sched is not None:
                    args += (self.replicate(sched),)
            with self._obs_span("scan.call"):
                state, rng, sched, sc, obs, ys = fn(*args)
            if collect is not None:
                traces.append(ys)

        if tail:
            state, ys, _, last = self._run_loop(
                state, data, rng, tail, t0 + num_steps * L, sc, obs,
                collect, None)
            rng, sc, obs = last.rng, last.sched_carry, last.obs
            if collect is not None:
                traces.append(ys)

        trace = None
        if collect is not None:
            trace = (jax.tree.map(lambda *xs: jnp.concatenate(xs), *traces)
                     if len(traces) > 1 else traces[0])
        carry = EngineCarry(rng=rng, t=jnp.int32(t0 + rounds), sched=sched,
                            sched_carry=sc, obs=obs)
        return state, trace, None, carry

    def _get_scan_fn(self, num_steps: int, depth: int,
                     collect: Optional[Callable], donate: bool,
                     unroll: int = 1, with_sched0: bool = False):
        key = (self._active_spec, self._assignment,
               self._active_kern_spec, num_steps, depth,
               collect, donate, unroll, with_sched0)
        fn = self._scan_cache.get(key)
        if fn is None:
            self._obs_event("cache_miss", program="scan",
                            num_steps=num_steps, depth=depth,
                            **self._cache_key_args())
            fn = self._build_scan(num_steps, depth, collect, donate,
                                  unroll, with_sched0)
            self._scan_cache[key] = fn
        return fn

    def _build_scan(self, num_steps: int, depth: int,
                    collect: Optional[Callable], donate: bool,
                    unroll: int, with_sched0: bool):
        period = self.phase_period
        L = period * unroll           # rounds per scan step
        # telemetry is injected at trace time (the telemetry-injection
        # contract): counters observe only the schedule pytree, so the
        # state/PRNG stream is untouched — instrumented runs stay
        # bit-identical.  num_candidates is static per scheduler.
        num_cand = self._obs_num_candidates()

        def one_round(state, sc, data, rng, t, phase, obs, ys):
            # Depth-0 inner round: fresh schedule, then update — the exact
            # op/PRNG order of the host-loop round.
            sched = self._make_schedule(state, sc, data, rng, t, phase)
            if obs is not None:
                obs = self._observe(obs, data, sched, phase, num_cand)
            new_state = self._apply(state, data, sched, phase)
            sc = self._sched_update(sc, state, new_state, sched, phase)
            if collect is not None:
                ys.append(collect(new_state))
            return new_state, sc, obs

        def scanned(state, data, rng, t0, sc0, obs0=None, *sched0):
            if depth == 0:
                def step(carry, _):
                    state, rng, tc, sc, obs = carry
                    ys: list = []
                    for i in range(L):
                        rng, sub = jax.random.split(rng)
                        state, sc, obs = one_round(state, sc, data, sub,
                                                   tc + i, i % period,
                                                   obs, ys)
                    return ((state, rng, tc + L, sc, obs),
                            _stack_rounds(ys) if collect else None)

                (state, rng, _, sc, obs), ys = jax.lax.scan(
                    step, (state, rng, t0, sc0, obs0), None,
                    length=num_steps)
                sched = None
            else:
                # Pipelined: carry the next round's schedule.  At the top
                # of step t we compute sched_{t+1} from the *pre-update*
                # state and scheduler carry — it is independent of round
                # t's push/pull, so the two overlap; the executed schedule
                # is one round stale.
                if with_sched0:
                    sched = sched0[0]       # resumed in-flight schedule
                else:
                    rng, sub = jax.random.split(rng)
                    sched = self._make_schedule(state, sc0, data, sub,
                                                t0, 0)

                def step(carry, _):
                    state, rng, tc, sc, sched, obs = carry
                    ys: list = []
                    for i in range(L):
                        t = tc + i
                        rng, sub = jax.random.split(rng)
                        sched_next = self._make_schedule(
                            state, sc, data, sub, t + 1, (i + 1) % period)
                        if obs is not None:
                            # count the schedule the round EXECUTES (the
                            # one-round-stale one), not the prefetch
                            obs = self._observe(obs, data, sched,
                                                i % period, num_cand)
                        new_state = self._apply(state, data, sched,
                                                i % period)
                        sc = self._sched_update(sc, state, new_state,
                                                sched, i % period)
                        state = new_state
                        sched = sched_next
                        if collect is not None:
                            ys.append(collect(state))
                    return ((state, rng, tc + L, sc, sched, obs),
                            _stack_rounds(ys) if collect else None)

                (state, rng, _, sc, sched, obs), ys = jax.lax.scan(
                    step, (state, rng, t0, sc0, sched, obs0), None,
                    length=num_steps)

            if collect is not None:
                # (num_steps, L, ...) → (num_rounds, ...)
                ys = jax.tree.map(
                    lambda x: x.reshape((num_steps * L,) + x.shape[2:]),
                    ys)
            return state, rng, sched, sc, obs, ys

        return jax.jit(scanned, donate_argnums=(0,) if donate else ())


class _SpecBoundFn:
    """A compiled-program handle pinned to the (SchedulerSpec,
    Assignment, KernelSpec) triple it was requested under.  The
    underlying jit fn traces lazily (at first call/lower) against
    whatever scheduler, partition assignment, and kernel backend are
    then installed on the app, so a handle obtained before a
    ``set_scheduler``/``set_kernels`` swap or an ``apply_assignment``
    move would otherwise silently bake the *wrong* configuration into
    the per-key cache; this wrapper reinstalls its owning triple first
    (a cheap no-op when all are already active)."""

    def __init__(self, eng: "StradsEngine", spec, fn):
        self._eng, self._spec, self._fn = eng, spec, fn
        self._assignment = eng._assignment
        self._part_spec = eng._active_part_spec
        self._kern_spec = eng._active_kern_spec

    def _bind(self):
        self._eng.set_scheduler(self._spec)
        self._eng.set_kernels(self._kern_spec)
        if self._eng._active_part_spec != self._part_spec:
            # reinstalling the pinned assignment under a different
            # partitioner (or none) would desync assignment/stats/spec;
            # the handle is simply stale — refetch it
            raise RuntimeError(
                "this AOT handle was requested under PartitionerSpec "
                f"{self._part_spec!r} but the engine now runs "
                f"{self._eng._active_part_spec!r}; refetch scanned_fn/"
                f"ssp_fn after set_partitioner")
        if self._eng._assignment != self._assignment:
            self._eng.apply_assignment(self._assignment)

    def __call__(self, *args, **kw):
        self._bind()
        return self._fn(*args, **kw)

    def lower(self, *args, **kw):
        self._bind()
        return self._fn.lower(*args, **kw)


def _stack_rounds(ys: list):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *ys)


def single_device_mesh() -> Mesh:
    """A 1-device ``data`` mesh for laptop-scale runs and unit tests."""
    return make_mesh((1,), (DATA_AXIS,))


def worker_mesh(num_workers: int) -> Mesh:
    devs = jax.devices()
    if len(devs) < num_workers:
        hint = (" (on the CPU, set XLA_FLAGS=--xla_force_host_platform_"
                "device_count=N before importing jax)"
                if devs[0].platform == "cpu" else "")
        raise ValueError(
            f"mesh of {num_workers} workers needs ≥{num_workers} devices; "
            f"found {len(devs)} {devs[0].platform} device(s)"
            f" ({devs[0].device_kind}){hint}")
    return make_mesh((num_workers,), (DATA_AXIS,))
