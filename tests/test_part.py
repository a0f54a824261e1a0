"""The dynamic partitioning subsystem (`repro/part`):

* ``PartitionerSpec`` — construction validation (kinds, per-kind field
  rejection) and exact JSON round-trips;
* ``Assignment`` — validation, accounting, payload/JSON round-trips,
  and the static-assignment ≡ rotation-bounds consistency guarantee;
* the three policies behind ``build_partitioner`` (greedy balance
  determinism, EMA measurement, rebalance gating);
* engine wiring — ``plan.partitioner`` resolution, app×kind
  compatibility, ``PartitionerSpec(kind="static")`` (and
  ``partitioner=None``) bit-identical to the pre-subsystem behavior on
  every executor, and the chunk-boundary rebalance +
  ``{"state", "carry", "assignment"}`` checkpoint/resume path being
  bit-exact with the assignment restored.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.apps import lasso, lda, mf
from repro.checkpoint import restore_checkpoint
from repro.core import ExecutionPlan, single_device_mesh
from repro.part import (Assignment, PartitionerSpec, build_partitioner,
                        contiguous_assignment, greedy_balance)
from repro.sched.schedulers import RotationScheduler


# ---------------------------------------------------------------------------
# PartitionerSpec
# ---------------------------------------------------------------------------

def test_spec_valid_kinds_and_json_roundtrip():
    for spec in (PartitionerSpec(kind="static"),
                 PartitionerSpec(kind="size_balanced"),
                 PartitionerSpec(kind="load_balanced", rebalance_every=8,
                                 ema=0.5, imbalance_threshold=0.25)):
        d = spec.to_json()
        assert PartitionerSpec.from_json(d) == spec
        # every field present, defaults included (exact dumps — plan
        # files rely on it)
        assert set(d) == {"kind", "rebalance_every", "ema",
                          "imbalance_threshold"}


def test_spec_rejects_bad_kind_and_foreign_fields():
    with pytest.raises(ValueError, match="kind"):
        PartitionerSpec(kind="dynamic")
    # static/size_balanced consume no fields — a knob that would be
    # silently ignored is rejected at construction
    for kind in ("static", "size_balanced"):
        with pytest.raises(ValueError, match="does not apply"):
            PartitionerSpec(kind=kind, ema=0.5)
        with pytest.raises(ValueError, match="does not apply"):
            PartitionerSpec(kind=kind, rebalance_every=4)
    with pytest.raises(ValueError, match="ema"):
        PartitionerSpec(kind="load_balanced", ema=1.0)
    with pytest.raises(ValueError, match="rebalance_every"):
        PartitionerSpec(kind="load_balanced", rebalance_every=-1)
    with pytest.raises(ValueError, match="unknown"):
        PartitionerSpec.from_json({"kind": "static", "rho": 0.3})


def test_spec_default_for_matches_validation():
    for kind in ("static", "size_balanced", "load_balanced"):
        spec = PartitionerSpec.default_for(kind)
        assert spec.kind == kind
    assert PartitionerSpec.default_for(
        "load_balanced", imbalance_threshold=0.5).imbalance_threshold == 0.5


# ---------------------------------------------------------------------------
# Assignment
# ---------------------------------------------------------------------------

def test_assignment_validation_and_accounting():
    a = Assignment(owner=(0, 0, 1, 1), num_workers=2)
    assert a.num_vars == 4
    assert list(a.counts()) == [2, 2]
    loads = a.loads([1.0, 2.0, 3.0, 4.0])
    assert list(loads) == [3.0, 7.0]
    assert a.spread([1.0, 2.0, 3.0, 4.0]) == pytest.approx(4.0 / 5.0)
    assert a.spread([0.0, 0.0, 0.0, 0.0]) == 0.0
    with pytest.raises(ValueError, match="worker ids"):
        Assignment(owner=(0, 2), num_workers=2)
    with pytest.raises(ValueError, match="shape"):
        a.loads([1.0, 2.0])
    # hashable: usable as a compiled-program cache key
    assert hash(a) == hash(Assignment(owner=[0, 0, 1, 1], num_workers=2))
    assert a != Assignment(owner=(0, 0, 1, 1), num_workers=2, version=1)


def test_assignment_payload_and_json_roundtrip():
    a = Assignment(owner=(1, 0, 2, 1), num_workers=3, version=5)
    assert Assignment.from_json(a.to_json()) == a
    back = Assignment.from_payload(a.payload())
    assert back == a
    # the payload is flat numpy — exactly what checkpoint/npz stores
    p = a.payload()
    assert p["owner"].dtype == np.int32
    assert int(p["version"]) == 5


def test_static_assignment_matches_rotation_bounds():
    """The static partition and the LDA rotation scheduler must share
    one variable→worker map — a disagreement would desync the schedule's
    ppermute pattern from the ownership accounting."""
    # incl. a vocab-scale J where float32 vs float64 linspace rounding
    # diverges — the assignment must follow the scheduler's float32 path
    for J, U in ((10, 4), (16, 4), (7, 3), (5, 8), (1000003, 7)):
        a = contiguous_assignment(J, U)
        bounds = np.asarray(RotationScheduler(J, U).bounds)
        expect = np.searchsorted(bounds[1:], np.arange(J), side="right")
        assert a.owner == tuple(int(o) for o in expect)


# ---------------------------------------------------------------------------
# Policies
# ---------------------------------------------------------------------------

def test_greedy_balance_is_deterministic_and_capacity_bounded():
    w = np.array([10.0, 9.0, 1.0, 1.0, 1.0, 1.0, 1.0, 1.0])
    a = greedy_balance(w, 2)
    assert a == greedy_balance(w, 2)            # deterministic
    counts = a.counts()
    assert counts.max() - counts.min() <= 1     # balanced bins
    # the two heavy variables land on different workers (contiguous
    # static would pile both onto worker 0)
    assert a.owner[0] != a.owner[1]
    assert a.spread(w) < contiguous_assignment(8, 2).spread(w)


def test_size_balanced_uses_sizes():
    spec = PartitionerSpec(kind="size_balanced")
    part = build_partitioner(spec, num_vars=4, num_workers=2,
                             sizes=[100.0, 1.0, 1.0, 98.0])
    a = part.init_assignment()
    assert a.owner[0] != a.owner[3]             # big ones split
    assert not part.should_rebalance(part.init_stats(), a, 0)


def test_load_balanced_measure_and_rebalance_gating():
    spec = PartitionerSpec(kind="load_balanced", rebalance_every=4,
                           ema=0.5, imbalance_threshold=0.1)
    part = build_partitioner(spec, num_vars=4, num_workers=2)
    a = part.init_assignment()
    assert a == contiguous_assignment(4, 2)     # starts static
    stats = part.init_stats()
    # nothing measured yet → never rebalance
    assert not part.should_rebalance(stats, a, 4)
    act = np.array([8.0, 8.0, 0.0, 0.0])        # all load on worker 0
    stats = part.measure(stats, a, act)
    assert np.allclose(stats["ema"], 0.5 * act)
    stats = part.measure(stats, a, act)
    assert np.allclose(stats["ema"], 0.75 * act)
    # cadence gate: t=2 not a multiple of rebalance_every=4
    assert not part.should_rebalance(stats, a, 2)
    assert part.should_rebalance(stats, a, 4)
    new = part.propose_assignment(stats, a)
    assert new.version == 1
    assert new.spread(stats["ema"]) < a.spread(stats["ema"])
    # activity=None (no app signal) leaves the stats untouched
    assert part.measure(stats, a, None) is stats


def test_build_partitioner_validation():
    with pytest.raises(TypeError, match="PartitionerSpec"):
        build_partitioner({"kind": "static"}, num_vars=4, num_workers=2)
    with pytest.raises(ValueError, match="num_vars"):
        build_partitioner(PartitionerSpec(kind="static"), num_vars=0,
                          num_workers=2)


# ---------------------------------------------------------------------------
# Plan integration
# ---------------------------------------------------------------------------

def test_plan_carries_partitioner_and_roundtrips():
    spec = PartitionerSpec(kind="load_balanced", ema=0.5,
                           imbalance_threshold=0.2)
    plan = ExecutionPlan(executor="scan", rounds=4, partitioner=spec)
    d = plan.to_json()
    assert d["partitioner"]["kind"] == "load_balanced"
    assert ExecutionPlan.from_json(d) == plan
    with pytest.raises(ValueError, match="partitioner"):
        ExecutionPlan(executor="scan", rounds=4,
                      partitioner={"kind": "static"})


# ---------------------------------------------------------------------------
# Engine wiring
# ---------------------------------------------------------------------------

def _lasso_setup(rng, J=20):
    mesh = single_device_mesh()
    X, y, _ = lasso.synthetic_correlated(rng, n=40, J=J, k_true=3)
    cfg = lasso.LassoConfig(num_features=J, lam=0.02, block_size=4,
                            num_candidates=8, rho=0.3)
    eng = lasso.make_engine(cfg, mesh)
    data = eng.shard_data({"X": jnp.asarray(X), "y": jnp.asarray(y)})
    return eng, data, y


def test_engine_resolves_app_default_partitioner(rng):
    eng, data, y = _lasso_setup(rng)
    eng.init_state(jax.random.key(0), y=y)
    assert eng.partitioner_spec == PartitionerSpec(kind="static")
    asgn = eng.partition_assignment
    assert asgn is not None and asgn.version == 0
    assert asgn.num_vars == 20 and asgn.num_workers == 1
    # injected into the app too
    assert eng.app.assignment is asgn


@pytest.mark.parametrize("executor,rounds,kw", [
    ("loop", 6, {}), ("scan", 6, {}), ("pipelined", 6, {}),
    ("ssp", 6, {"staleness": 1}),
])
def test_static_partitioner_bit_identical_every_executor(rng, executor,
                                                         rounds, kw):
    """``PartitionerSpec(kind="static")`` — and a plan with
    ``partitioner=None`` resolving the app's static default — must run
    bit-identically to each other on every executor: ownership is
    bookkeeping, never math."""
    eng, data, y = _lasso_setup(rng)
    base = ExecutionPlan(executor=executor, rounds=rounds, **kw)
    explicit = dataclasses.replace(
        base, partitioner=PartitionerSpec(kind="static"))
    st = eng.execute(eng.init_state(jax.random.key(0), y=y), data,
                     jax.random.key(1), base).state
    st2 = eng.execute(eng.init_state(jax.random.key(0), y=y), data,
                      jax.random.key(1), explicit).state
    for k in st:
        assert (np.asarray(st[k]) == np.asarray(st2[k])).all(), k


def test_app_kind_compatibility_enforced(rng):
    # LDA's rotation owns a frozen contiguous block map — only static
    cfg = lda.LDAConfig(vocab=8, num_topics=2, num_workers=1,
                        tokens_per_worker=8, docs_per_worker=2)
    eng = lda.make_engine(cfg, single_device_mesh())
    with pytest.raises(ValueError, match="cannot host"):
        eng.set_partitioner(PartitionerSpec(kind="load_balanced",
                                            ema=0.5))
    # MF supports every kind (ranks are interchangeable)
    mcfg = mf.MFConfig(num_rows=8, num_cols=6, rank=4)
    meng = mf.make_engine(mcfg, single_device_mesh())
    meng.set_partitioner(PartitionerSpec(kind="load_balanced", ema=0.5))
    assert meng.partition_assignment.num_vars == 4
    # sizes flow from the app into the size_balanced policy
    meng.set_partitioner(PartitionerSpec(kind="size_balanced"))
    assert meng.partitioner.sizes is not None


def test_load_balanced_requires_partition_signal():
    from repro.core import StradsAppBase, StradsEngine

    class NoSignal(StradsAppBase):
        def num_schedulable(self):
            return 4

        def push(self, data, state, sched, phase):
            return None, None

    eng = StradsEngine(NoSignal(), single_device_mesh(), data_specs={})
    with pytest.raises(ValueError, match="partition_signal"):
        eng.set_partitioner(PartitionerSpec(kind="load_balanced",
                                            ema=0.5))


def test_unchunked_load_balanced_plan_warns(rng):
    eng, data, y = _lasso_setup(rng)
    plan = ExecutionPlan(
        executor="scan", rounds=2,
        partitioner=PartitionerSpec(kind="load_balanced", ema=0.5))
    with pytest.warns(UserWarning, match="chunk boundaries"):
        eng.execute(eng.init_state(jax.random.key(0), y=y), data,
                    jax.random.key(1), plan)


def test_misaligned_rebalance_cadence_rejected(rng, tmp_path):
    eng, data, y = _lasso_setup(rng)
    plan = ExecutionPlan(
        executor="scan", rounds=8, checkpoint_every=4,
        partitioner=PartitionerSpec(kind="load_balanced", ema=0.5,
                                    rebalance_every=6))   # 6 % 4 != 0
    with pytest.raises(ValueError, match="rebalance_every"):
        eng.execute(eng.init_state(jax.random.key(0), y=y), data,
                    jax.random.key(1), plan, ckpt_dir=str(tmp_path))


# ---------------------------------------------------------------------------
# Chunk-boundary rebalancing + checkpoint/resume
# ---------------------------------------------------------------------------

def _skewed_lasso(num_workers: int):
    """Power-law column activity concentrated on a contiguous hot block
    — the workload whose static contiguous partition is maximally
    unfair (bench_part's scenario, laptop-sized)."""
    from repro.core import worker_mesh
    rng = np.random.default_rng(0)
    n, J = 80, 32
    X = rng.normal(size=(n, J)).astype(np.float32)
    X -= X.mean(axis=0)
    X /= np.maximum(np.linalg.norm(X, axis=0), 1e-12)
    bstar = np.zeros((J,), np.float32)
    bstar[:8] = 5.0 * np.arange(1, 9, dtype=np.float32) ** -1.2
    y = (X @ bstar).astype(np.float32)
    y -= y.mean()
    cfg = lasso.LassoConfig(num_features=J, lam=0.01, block_size=4,
                            num_candidates=8)
    eng = lasso.make_engine(cfg, worker_mesh(num_workers))
    data = eng.shard_data({"X": jnp.asarray(X), "y": jnp.asarray(y)})
    return eng, data, y


_LOADBAL = PartitionerSpec(kind="load_balanced", ema=0.5,
                           imbalance_threshold=0.1)


def test_chunked_run_checkpoints_assignment_payload(rng, tmp_path):
    eng, data, y = _skewed_lasso(1)
    plan = ExecutionPlan(executor="scan", rounds=4, checkpoint_every=2,
                         partitioner=_LOADBAL)
    eng.execute(eng.init_state(jax.random.key(0), y=y), data,
                jax.random.key(1), plan, ckpt_dir=str(tmp_path))
    with np.load(str(tmp_path / "step_00000004.npz")) as z:
        keys = set(z.files)
    assert {"assignment/owner", "assignment/num_workers",
            "assignment/version", "assignment/stats_ema"} <= keys


def test_rebalance_fires_and_resumes_bit_exactly(tmp_path):
    """The acceptance path: a mid-run rebalance on the skewed workload,
    resumed from the ``{"state", "carry", "assignment"}`` checkpoint —
    final state AND final assignment/stats must match the uninterrupted
    run exactly.  Multi-worker spreads need >1 device; on a single
    device the partition trajectory still runs (one bin, no moves)."""
    workers = min(4, jax.device_count())
    eng, data, y = _skewed_lasso(workers)
    plan = ExecutionPlan(executor="scan", rounds=8, checkpoint_every=2,
                         partitioner=_LOADBAL)

    rep = eng.execute(eng.init_state(jax.random.key(0), y=y), data,
                      jax.random.key(1), plan, ckpt_dir=str(tmp_path))
    final_asgn = eng.partition_assignment
    final_ema = np.array(eng.partition_stats["ema"])
    if workers > 1:
        assert final_asgn.version > 0        # a rebalance actually fired
        ema = eng.partition_stats["ema"]
        assert final_asgn.spread(ema) \
            <= contiguous_assignment(32, workers).spread(ema)

    # resume from the mid checkpoint on a FRESH engine (fresh process
    # stand-in): state + carry + assignment all restored
    eng2, data2, _ = _skewed_lasso(workers)
    st2 = eng2.init_state(jax.random.key(0), y=y)
    eng2.set_partitioner(plan.partitioner)   # resolve before payload tmpl
    template = {"state": jax.tree.map(jnp.copy, st2), "carry": rep.carry,
                "assignment": eng.partition_payload()}
    back = restore_checkpoint(str(tmp_path), 4, template)
    assert int(back["carry"].t) == 4
    resumed = eng2.execute(back["state"], data2, jax.random.key(99), plan,
                           carry=back["carry"],
                           partition=back["assignment"],
                           ckpt_dir=str(tmp_path / "resumed"))
    for k in rep.state:
        assert (np.asarray(rep.state[k])
                == np.asarray(resumed.state[k])).all(), k
    assert eng2.partition_assignment == final_asgn
    assert np.array_equal(np.array(eng2.partition_stats["ema"]),
                          final_ema)


def test_fresh_execute_resets_partition_trajectory(tmp_path):
    """A fresh (carry-less) execute must start from the initial
    assignment — rebalances from a previous run of the same spec cannot
    leak in (runs would otherwise stop being reproducible)."""
    workers = min(4, jax.device_count())
    eng, data, y = _skewed_lasso(workers)
    plan = ExecutionPlan(executor="scan", rounds=8, checkpoint_every=2,
                         partitioner=_LOADBAL)
    eng.execute(eng.init_state(jax.random.key(0), y=y), data,
                jax.random.key(1), plan, ckpt_dir=str(tmp_path / "a"))
    v1 = eng.partition_assignment.version
    rep2 = eng.execute(eng.init_state(jax.random.key(0), y=y), data,
                       jax.random.key(1), plan,
                       ckpt_dir=str(tmp_path / "b"))
    assert eng.partition_assignment.version == v1   # same trajectory
    eng3, data3, _ = _skewed_lasso(workers)
    rep3 = eng3.execute(eng3.init_state(jax.random.key(0), y=y), data3,
                        jax.random.key(1), plan,
                        ckpt_dir=str(tmp_path / "c"))
    for k in rep2.state:
        assert (np.asarray(rep2.state[k])
                == np.asarray(rep3.state[k])).all(), k


def test_restore_partition_rejects_mismatches(rng):
    eng, data, y = _lasso_setup(rng)
    eng.init_state(jax.random.key(0), y=y)
    # static default resolved; a load_balanced payload (with stats) must
    # not silently restore into it
    payload = {"owner": np.zeros((20,), np.int32),
               "num_workers": np.int32(1), "version": np.int32(1),
               "stats_ema": np.zeros((20,), np.float64)}
    with pytest.raises(ValueError, match="PartitionerSpec must match"):
        eng.restore_partition(payload)
    # wrong mesh width
    eng.set_partitioner(PartitionerSpec(kind="load_balanced", ema=0.5))
    bad = dict(payload, num_workers=np.int32(4),
               owner=np.zeros((20,), np.int32))
    with pytest.raises(ValueError, match="workers"):
        eng.restore_partition(bad)
    # wrong model size: a 12-variable assignment into a 20-variable app
    bad2 = dict(payload, owner=np.zeros((12,), np.int32),
                stats_ema=np.zeros((12,), np.float64))
    with pytest.raises(ValueError, match="different model size"):
        eng.restore_partition(bad2)


def test_repartition_keeps_kvstore_accounting_truthful(rng):
    """KVStore.repartition re-derives VarSpec.specs — Fig-3 byte
    accounting must follow a placement move immediately."""
    from jax.sharding import PartitionSpec as P
    eng, data, y = _lasso_setup(rng)
    state = eng.init_state(jax.random.key(0), y=y)
    kv = eng.kvstore
    before = kv.bytes_per_device()
    asgn = contiguous_assignment(20, 1)
    # move the replicated beta to a (1-way) sharded spec: per-device
    # bytes unchanged on 1 device, but the spec must be re-derived
    state2 = kv.repartition(asgn, state,
                            leaf_specs={"beta": P("data")})
    assert kv.specs["beta"].spec == P("data")
    assert kv.assignment is asgn
    assert kv.bytes_per_device() == before          # 1-way shard
    assert (np.asarray(state2["beta"])
            == np.asarray(state["beta"])).all()
    with pytest.raises(ValueError, match="unknown variable"):
        kv.repartition(asgn, state, leaf_specs={"nope": P()})
