"""The SSP executor: bounded-staleness push/pull on the scanned engine.

Stale-Synchronous Parallel (Xing et al. 2016; LightLDA, Yuan et al. 2014)
relaxes BSP by letting workers read shared parameters up to ``s`` clocks
stale.  On the STRADS primitives that becomes:

* **reads** of server-resident variables (the replicated state leaves —
  see ``repro.ps.server``) are served from a worker-local
  :class:`~repro.ps.cache.StaleCache` instead of the freshly committed
  value;
* **pushes** aggregate lazily: each round's partial results ``z`` go into
  a pending-update buffer (no collective), and only when the staleness
  gate ``clock - cache.clock <= s`` would be violated does a **flush**
  run — one batched psum for every deferred round, then the deferred
  commits (``ssp_commit_shared``, default ``pull``) replayed in round
  order, then a cache refresh;
* **worker-local** state stays exact: commit-through runs every round so
  a worker always sees its *own* writes immediately (the SSP
  read-my-writes guarantee) — only other workers' contributions arrive
  late.

Which writes commit through, which defer, and which schedule-priority
entries are masked for in-flight exclusion is **derived from the app's
placement declarations** (the v2 primitive protocol — see
:mod:`repro.core.primitives` and :class:`repro.core.kvstore.VarTable`):
a ``local`` leaf whose key path names a worker-resident (sharded) state
leaf is its committed value and commits every round; the remaining
``local`` leaves are buffered until the flush, where the app's own
``pull`` replays per deferred round with ``local`` reconstructed;
``role="priority"`` VarSpecs get the in-flight exclusion.  With an
injected scheduler (the v2 scheduler-injection contract) the priority
table lives in the engine-owned scheduler carry instead: the window
scheduler masks it via ``scheduler.mark_scheduled`` between stale
proposals, folds it forward via ``app.sched_update`` per replayed
commit, and returns it as ``SSPCarry.sched_carry``.  Apps that still
define the deprecated v1 ``ssp_*`` hook overrides are honored with a
``DeprecationWarning``.

Rounds therefore execute in windows of ``s + 1``: the first round of a
window reads a fresh snapshot (staleness 0), the last reads one that is
``s`` commits old.  Schedules for a whole window are computed up front
from the same snapshot — the direct generalization of the engine's
``pipeline_depth=1`` schedule prefetch (one-round-stale schedules) to
``≤ s``-round-stale schedules, with the window's ``schedule_stats``
reductions batched into a single collective.

At ``staleness=0`` every window is one round: the gate forces a flush
after every push, the batched psum degenerates to the BSP pull
aggregation, and the executor is **bit-identical** to
``StradsEngine.run_scanned(pipeline_depth=0)`` — the correctness anchor
(``tests/test_ssp.py``).  At ``s >= 1`` the program issues ~2 collectives
per window instead of ~2 per round; the price is staleness error in the
deferred commits, which ``benchmarks/bench_ssp.py`` measures as
objective-vs-round for ``s ∈ {0,1,2,4}``.

Built on the same ``lax.scan`` skeleton as ``run_scanned``: one XLA
program for all R rounds, donated state, no per-round host sync.  The
scan carries ``(state, rng, round counter, vector clocks, telemetry,
engine-wide counters)``; the carry is exposed as :class:`SSPCarry` so a
run can be checkpointed
and resumed exactly (``checkpoint/npz.py`` round-trips it, clocks
included).
"""
from __future__ import annotations

import dataclasses
import math
import warnings
from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.compat import shard_map
from ..core.engine import DATA_AXIS
from ..core.kvstore import VarTable
from . import telemetry as T
from .cache import StaleCache
from .server import ParameterServer, init_clocks, tick


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SSPCarry:
    """Resumable executor carry: PRNG stream, next round, vector clocks,
    the engine-owned scheduler carry (Δx priority history; ``None``
    for stateless policies), and — under a plan-level
    :class:`~repro.obs.spec.TelemetrySpec` — the device telemetry
    counters (:mod:`repro.obs.counters`; ``None`` uninstrumented) — the
    SSP twin of :class:`repro.core.engine.EngineCarry`."""
    rng: jax.Array
    t: jax.Array                 # int32: next round index
    clocks: jax.Array            # (num_workers,) per-worker vector clock
    sched_carry: Any = None      # scheduler carry (Δx history, …)
    obs: Any = None              # device telemetry counters (or None)


def rounds_per_step(engine, staleness: int) -> int:
    """Rounds one scan step unrolls: windows of ``s+1`` must tile the
    app's static-phase cycle, so it is lcm(s+1, phase_period)."""
    return math.lcm(staleness + 1, engine.phase_period)


# ---------------------------------------------------------------------------
# Collective batching
# ---------------------------------------------------------------------------

def _batched_psum(trees: List[Any], axis_name: str) -> List[Any]:
    """psum a list of pytrees in one collective per dtype: every leaf is
    raveled and concatenated, reduced once, and split back.  Elementwise
    sums are unchanged, so this is bit-identical to per-leaf psum — and a
    window's deferred pushes cost one launch.  Single-leaf groups skip
    the concat/split round-trip entirely."""
    flats, defs = zip(*(jax.tree_util.tree_flatten(t) for t in trees))
    leaves = [leaf for f in flats for leaf in f]
    summed: List[Any] = [None] * len(leaves)
    by_dtype: dict = {}
    for i, leaf in enumerate(leaves):
        by_dtype.setdefault(jnp.asarray(leaf).dtype, []).append(i)
    for _, idxs in by_dtype.items():
        if len(idxs) == 1:
            i = idxs[0]
            summed[i] = jax.lax.psum(leaves[i], axis_name)
            continue
        flat = jnp.concatenate([jnp.ravel(leaves[i]) for i in idxs])
        red = jax.lax.psum(flat, axis_name)
        off = 0
        for i in idxs:
            n = leaves[i].size
            summed[i] = red[off:off + n].reshape(leaves[i].shape)
            off += n
    out, k = [], 0
    for f, d in zip(flats, defs):
        out.append(jax.tree_util.tree_unflatten(d, summed[k:k + len(f)]))
        k += len(f)
    return out


# ---------------------------------------------------------------------------
# Commit/defer/exclusion — derived from placement (v2) or legacy hooks
# ---------------------------------------------------------------------------

_LEGACY_HOOKS = ("ssp_commit_local", "ssp_defer_local",
                 "ssp_commit_shared", "ssp_mark_scheduled")


class _DerivedHooks:
    """The v2 contract: everything follows from the VarSpec placement
    (commit-through of worker-resident ``local`` writes, deferral of the
    rest, flush-time replay of the app's own ``pull``, in-flight
    exclusion over ``role="priority"`` leaves)."""

    def __init__(self, app, table: VarTable):
        self.app = app
        self.table = table

    def commit_local(self, state, sched, local, data, phase):
        return self.table.commit_local(state, local, phase)

    def defer_local(self, local, phase):
        return self.table.defer_local(local, phase)

    def commit_shared(self, state, sched, z, keep, data, phase):
        local = self.table.rebuild_local(state, keep, phase)
        return self.app.pull(state, sched, z, local, data, phase)

    def mark_scheduled(self, view, candidates, phase):
        return self.table.mark_scheduled(view, candidates)


class _LegacyHooks:
    """v1 per-app ``ssp_*`` hook overrides (deprecated), with the old
    StradsAppBase defaults filled in for whichever hooks are missing."""

    def __init__(self, app):
        self.app = app

    def commit_local(self, state, sched, local, data, phase):
        fn = getattr(self.app, "ssp_commit_local", None)
        return fn(state, sched, local, data, phase) if fn else state

    def defer_local(self, local, phase):
        fn = getattr(self.app, "ssp_defer_local", None)
        return fn(local, phase) if fn else local

    def commit_shared(self, state, sched, z, keep, data, phase):
        fn = getattr(self.app, "ssp_commit_shared", None)
        if fn:
            return fn(state, sched, z, keep, data, phase)
        return self.app.pull(state, sched, z, keep, data, phase)

    def mark_scheduled(self, view, candidates, phase):
        fn = getattr(self.app, "ssp_mark_scheduled", None)
        return fn(view, candidates, phase) if fn else view


def _make_hooks(app, table: VarTable):
    legacy = [n for n in _LEGACY_HOOKS if callable(getattr(app, n, None))]
    if legacy:
        warnings.warn(
            f"{type(app).__name__} defines v1 SSP hook(s) {legacy}; they "
            f"are deprecated — the v2 protocol derives commit/defer/"
            f"exclusion from VarSpec placement (see repro.core.primitives)",
            DeprecationWarning, stacklevel=3)
        return _LegacyHooks(app)
    return _DerivedHooks(app, table)


# ---------------------------------------------------------------------------
# Round pieces (shard_map regions)
# ---------------------------------------------------------------------------

def _window_schedules(eng, hooks, view, sc, data, subs, ts, phases):
    """propose → [batched schedule_stats psum] → schedule for a whole
    window, all reading the same stale cache view and window-start
    scheduler carry (schedule staleness ≤ s — the generalization of the
    depth-1 pipeline prefetch).  Between proposals the view/carry pass
    through the in-flight exclusion (``scheduler.mark_scheduled`` on the
    engine-owned carry; ``role="priority"`` VarSpecs for state-resident
    tables) so later proposals in the window avoid variables already in
    flight; only later *proposals* see the marks — stats and the schedule
    decisions read the pristine stale view/carry."""
    app = eng.app
    keys = [jax.random.split(sub) for sub in subs]
    cands = []
    marked = view
    marked_sc = sc
    for i, ((r1, _), t, ph) in enumerate(zip(keys, ts, phases)):
        with jax.named_scope("propose"):
            c = app.propose(marked, marked_sc, r1, t, ph)
        cands.append(c)
        if i + 1 < len(subs):        # only later proposals see the mark
            marked = hooks.mark_scheduled(marked, c, ph)
            marked_sc = eng.mark_sched_carry(marked_sc, c)
    if eng._needs_stats:
        def stats_fn(data, st, cands):
            stats = [app.schedule_stats(data, st, c, ph)
                     for c, ph in zip(cands, phases)]
            return tuple(_batched_psum(stats, DATA_AXIS))
        with jax.named_scope("schedule_stats"):
            stats = shard_map(
                stats_fn, mesh=eng.mesh,
                in_specs=(eng.data_specs, eng._sspec(view), P()),
                out_specs=P(),
            )(data, view, tuple(cands))
    else:
        stats = [None] * len(subs)
    with jax.named_scope("schedule"):
        return [app.schedule(view, sc, c, s, r2, t, ph)
                for c, s, (_, r2), t, ph in zip(cands, stats, keys, ts,
                                                phases)]


def _fused_round(eng, hooks, view, data, sched, phase, nbytes_out: list):
    """``staleness=0`` fast path: the window is a single round, so defer
    nothing — push → commit-through → pull aggregation → shared commit in
    ONE shard_map region, structurally the BSP ``_apply`` round (without
    commit-through writes it is exactly push → psum → pull)."""
    app = eng.app
    sspec = eng._sspec(view)
    num_workers = eng.mesh.shape[DATA_AXIS]

    def f(data, st, sched):
        with jax.named_scope("push"):
            z, local = app.push(data, st, sched, phase)
            st = hooks.commit_local(st, sched, local, data, phase)
            keep = hooks.defer_local(local, phase)
        nbytes_out.append(_tree_nbytes(z) * num_workers)
        with jax.named_scope("aggregate"):
            Z = jax.tree.map(lambda a: jax.lax.psum(a, DATA_AXIS), z)
        with jax.named_scope("pull"):
            return hooks.commit_shared(st, sched, Z, keep, data, phase)

    return shard_map(f, mesh=eng.mesh,
                     in_specs=(eng.data_specs, sspec, P()),
                     out_specs=sspec)(data, view, sched)


def _push_round(eng, hooks, view, data, sched, phase):
    """push (no aggregation) + the immediate commit-through of
    worker-resident ``local`` writes.

    Partials and deferred locals come back with a leading worker axis
    (sharded over ``data``) — the pending-update buffer layout."""
    app = eng.app
    sspec = eng._sspec(view)

    def f(data, st, sched):
        with jax.named_scope("push"):
            z, local = app.push(data, st, sched, phase)
            st = hooks.commit_local(st, sched, local, data, phase)
            keep = hooks.defer_local(local, phase)
        pend = jax.tree.map(lambda a: jnp.asarray(a)[None], (z, keep))
        return pend, st

    (z_pend, keep_pend), state = shard_map(
        f, mesh=eng.mesh,
        in_specs=(eng.data_specs, sspec, P()),
        out_specs=(P(DATA_AXIS), sspec),
    )(data, view, sched)
    return z_pend, keep_pend, state


def _flush_aggregate(eng, z_pends):
    """The lazy push: one batched psum over every deferred partial."""
    def f(zs):
        own = [jax.tree.map(lambda a: a[0], z) for z in zs]
        with jax.named_scope("aggregate"):
            return tuple(_batched_psum(own, DATA_AXIS))

    return shard_map(f, mesh=eng.mesh, in_specs=(P(DATA_AXIS),),
                     out_specs=P())(tuple(z_pends))


def _commit_round(eng, hooks, state, data, sched, z, keep_pend, phase):
    """Replay one deferred commit with its aggregated partials (the app's
    own ``pull`` under the v2 protocol, with ``local`` reconstructed from
    the live state + the deferred buffer)."""
    sspec = eng._sspec(state)

    def f(data, st, sched, z, keep):
        local = jax.tree.map(lambda a: a[0], keep)
        with jax.named_scope("pull"):
            return hooks.commit_shared(st, sched, z, local, data, phase)

    return shard_map(
        f, mesh=eng.mesh,
        in_specs=(eng.data_specs, sspec, P(), P(), P(DATA_AXIS)),
        out_specs=sspec,
    )(data, state, sched, z, keep_pend)


# ---------------------------------------------------------------------------
# The scanned SSP program
# ---------------------------------------------------------------------------

def _tree_nbytes(tree: Any) -> int:
    return sum(leaf.size * jnp.asarray(leaf).dtype.itemsize
               for leaf in jax.tree_util.tree_leaves(tree))


def _build_ssp(eng, num_steps: int, staleness: int,
               collect: Optional[Callable], donate: bool, info: dict):
    W = staleness + 1
    period = eng.phase_period
    L = rounds_per_step(eng, staleness)

    def scanned(state, data, rng, t0, clocks, sc0, obs0=None):
        # The server/cache split follows the engine's KV store when one
        # was built (place_state) — a repartition re-derives that
        # store's VarSpecs, and the per-assignment program cache key
        # guarantees this trace re-runs after a move; engines driven
        # without place_state fall back to the app's declarations.
        if eng.kvstore is not None:
            server = ParameterServer(eng.mesh, eng.kvstore)
        else:
            server = ParameterServer.from_state(eng.mesh, state,
                                                eng._sspec(state),
                                                roles=eng.app_roles())
        hooks = _make_hooks(eng.app, VarTable(server.store))
        # engine-wide counters (the telemetry-injection contract):
        # observe only the schedule pytree, so the instrumented program
        # stays bit-identical in state/PRNG
        num_cand = eng._obs_num_candidates()

        def step(carry, _):
            state, rng, t, clocks, sc, telem, obs = carry
            ys: list = []
            cache = StaleCache(values=server.snapshot(state),
                               clock=jnp.asarray(t, jnp.int32))
            for w0 in range(0, L, W):
                phases = [(w0 + k) % period for k in range(W)]
                ts = []
                subs = []
                for k in range(W):
                    rng, sub = jax.random.split(rng)
                    subs.append(sub)
                    ts.append(t + (w0 + k))
                # The SSP gate, unrolled: this window's last read is
                # exactly at the bound (W - 1 == staleness clocks stale),
                # so the flush below is forced before the next round.
                assert W - 1 <= staleness

                view = server.merge(state, cache.values)
                scheds = _window_schedules(eng, hooks, view, sc, data,
                                           subs, ts, phases)

                if W == 1:
                    # single-round window: nothing to defer — fused path
                    zb: list = []
                    new_state = _fused_round(eng, hooks, view, data,
                                             scheds[0], phases[0], zb)
                    sc = eng._sched_update(sc, view, new_state, scheds[0],
                                           phases[0])
                    state = new_state
                    telem = T.observe_read(telem, ts[0], cache.clock)
                    if obs is not None:
                        obs = eng._observe(obs, data, scheds[0],
                                           phases[0], num_cand)
                    clocks = tick(clocks)
                    if not info.get("traced"):
                        info["deferred_bytes_peak"] = max(
                            info.get("deferred_bytes_peak", 0), sum(zb))
                        info["push_bytes_per_step"] = (
                            info.get("push_bytes_per_step", 0) + sum(zb))
                    if collect is not None:
                        ys.append(collect(state))
                    cache = cache.refresh(server.snapshot(state),
                                          ts[-1] + 1)
                    continue

                z_pends, keep_pends = [], []
                for k in range(W):
                    view = server.merge(state, cache.values)
                    zp, kp, state = _push_round(eng, hooks, view, data,
                                                scheds[k], phases[k])
                    z_pends.append(zp)
                    keep_pends.append(kp)
                    telem = T.observe_read(telem, ts[k], cache.clock)
                    if obs is not None:
                        obs = eng._observe(obs, data, scheds[k],
                                           phases[k], num_cand)
                    clocks = tick(clocks)

                # The staleness bound now forces a sync: flush the pending
                # buffer (one batched collective), replay the deferred
                # commits in round order, refresh the cache.  The
                # scheduler carry folds forward per replayed commit, in
                # round order — exactly when the deferred Δx commits.
                if not info.get("traced"):
                    wb = sum(_tree_nbytes(z) for z in z_pends)
                    info["deferred_bytes_peak"] = max(
                        info.get("deferred_bytes_peak", 0), wb)
                    info["push_bytes_per_step"] = (
                        info.get("push_bytes_per_step", 0) + wb)
                zs = _flush_aggregate(eng, z_pends)
                for k in range(W):
                    new_state = _commit_round(eng, hooks, state, data,
                                              scheds[k], zs[k],
                                              keep_pends[k], phases[k])
                    sc = eng._sched_update(sc, state, new_state,
                                           scheds[k], phases[k])
                    state = new_state
                    if collect is not None:
                        ys.append(collect(state))
                cache = cache.refresh(server.snapshot(state), ts[-1] + 1)

            out = None
            if collect is not None:
                out = jax.tree.map(lambda *xs: jnp.stack(xs), *ys)
            return (state, rng, t + L, clocks, sc, telem, obs), out

        telem0 = T.device_init(staleness)
        (state, rng, t, clocks, sc, telem, obs), ys = jax.lax.scan(
            step, (state, rng, jnp.asarray(t0, jnp.int32), clocks, sc0,
                   telem0, obs0),
            None, length=num_steps)
        if not info.get("traced"):
            info["traced"] = True
            info["num_steps"] = num_steps
            info["shared_bytes"] = server.shared_nbytes()
        if collect is not None:
            ys = jax.tree.map(
                lambda x: x.reshape((num_steps * L,) + x.shape[2:]), ys)
        return state, SSPCarry(rng=rng, t=t, clocks=clocks,
                               sched_carry=sc, obs=obs), telem, ys

    return jax.jit(scanned, donate_argnums=(0,) if donate else ())


def _get_ssp_fn(eng, num_steps: int, staleness: int,
                collect: Optional[Callable], donate: bool):
    # keyed per (SchedulerSpec, Assignment, KernelSpec): a partition move
    # re-derives the server/cache split from the repartitioned KVStore
    # specs at the next trace, and a swap back to a previous
    # configuration is a cache hit
    key = ("ssp", eng._active_spec, eng._assignment,
           eng._active_kern_spec, num_steps, staleness, collect, donate)
    hit = eng._scan_cache.get(key)
    if hit is None:
        eng._obs_event("cache_miss", program="ssp", num_steps=num_steps,
                       staleness=staleness, **eng._cache_key_args())
        info: dict = {}
        hit = (_build_ssp(eng, num_steps, staleness, collect, donate, info),
               info)
        eng._scan_cache[key] = hit
    return hit


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

def ssp_fn(eng, num_rounds: int, *, staleness: int = 0,
           collect: Optional[Callable] = None, donate: bool = True):
    """The jitted ``(state, data, rng, t0, clocks, sched_carry, obs) →
    (state, carry, telemetry, trace)`` SSP program, exposed for AOT
    ``.lower().compile()`` (``launch/dryrun.py --engine ... --staleness``;
    pass ``engine.init_sched_carry()`` for a fresh run and ``None`` — or
    ``repro.obs.init_counters(engine.phase_period)`` — for ``obs``).
    """
    num_steps = _check_rounds(eng, num_rounds, staleness)
    return _get_ssp_fn(eng, num_steps, staleness, collect, donate)[0]


def _check_rounds(eng, num_rounds: int, staleness: int) -> int:
    if staleness < 0:
        raise ValueError(f"staleness must be >= 0, got {staleness}")
    L = rounds_per_step(eng, staleness)
    num_steps, tail = divmod(num_rounds, L)
    if tail or num_steps == 0:
        raise ValueError(
            f"run_ssp needs num_rounds to be a positive multiple of "
            f"lcm(staleness+1, phase_period) = {L}; got {num_rounds}")
    return num_steps


_UNSET = object()


def run_ssp(eng, state, data, rng, num_rounds: int, *, staleness: int = 0,
            collect: Optional[Callable] = None, donate: bool = True,
            with_telemetry: bool = False, t0: int = 0,
            clocks: Optional[jax.Array] = None,
            sched_carry0: Any = _UNSET, obs0: Any = None,
            return_carry: bool = False):
    """Execute ``num_rounds`` rounds under bounded staleness ``s``.

    ``staleness=0`` reproduces ``run_scanned(pipeline_depth=0)`` (and the
    host loop) bit-for-bit — same PRNG stream, same op order.  At ``s>=1``
    reads of server-resident state are up to ``s`` rounds stale and pushes
    aggregate lazily (one batched collective per ``s+1``-round window).

    ``collect(state)`` is evaluated after every committed round inside
    the flush; the stacked trace has leading axis ``num_rounds``.

    ``t0``/``clocks``/``sched_carry0`` resume a previous run (pass the
    values from a saved :class:`SSPCarry`; ``t0`` must be a multiple of
    the step length, ``sched_carry0`` is the engine-owned scheduler
    carry — omitted, a fresh ``scheduler.init_carry()`` is used, which
    is only correct at ``t0=0``).  ``obs0`` threads the engine-wide
    device telemetry counters (:func:`repro.obs.counters.init_counters`,
    or a previous :class:`SSPCarry`'s ``obs``) through the scan;
    ``None`` runs uninstrumented.  ``return_carry=True`` appends the
    final carry to the return value; ``with_telemetry=True`` appends an
    :class:`~repro.ps.telemetry.SSPTelemetry`.
    """
    num_steps = _check_rounds(eng, num_rounds, staleness)
    L = rounds_per_step(eng, staleness)
    if t0 % L:
        raise ValueError(f"t0 must be a multiple of the step length {L} "
                         f"(phase/window alignment); got {t0}")
    num_workers = eng.mesh.shape[DATA_AXIS]
    if clocks is None:
        clocks = init_clocks(num_workers)
    if sched_carry0 is _UNSET:
        sched_carry0 = eng.init_sched_carry()
        if t0 and sched_carry0 is not None:
            warnings.warn(
                "run_ssp(t0>0) without sched_carry0 reinitializes the "
                "stateful scheduler's priorities; pass the "
                "SSPCarry.sched_carry a previous run returned for a "
                "bit-exact resume", UserWarning, stacklevel=2)
    fn, info = _get_ssp_fn(eng, num_steps, staleness, collect, donate)
    rng, clocks, sched_carry0, obs0 = eng.replicate(
        (rng, jnp.asarray(clocks), sched_carry0, obs0))
    state, carry, telem, ys = fn(state, data, rng, jnp.int32(t0), clocks,
                                 sched_carry0, obs0)

    ret = [state]
    if collect is not None:
        ret.append(ys)
    if with_telemetry:
        flushes = num_steps * (L // (staleness + 1))
        ret.append(T.summarize(telem, info, staleness=staleness,
                               rounds=num_rounds, flushes=flushes,
                               clocks=carry.clocks))
    if return_carry:
        ret.append(carry)
    return ret[0] if len(ret) == 1 else tuple(ret)
