"""The pluggable scheduler subsystem (ISSUE 4 acceptance).

Contract under test:
  * ``SchedulerSpec`` is a frozen, hashable value; invalid
    kind/parameter combinations raise at construction (mirroring
    ``ExecutionPlan``), and ``to_json → from_json`` round-trips exactly,
    defaults included — standalone and nested in a plan;
  * ``dependency_filter`` property (hypothesis): every kept pair has
    |gram| < ρ, at most ``block_size`` kept, candidate 0 always admitted
    — for both gram backends (data Gram and structural distance);
  * a plan carrying an explicit ``SchedulerSpec`` equal to the app's old
    default is bit-identical to the default run on all four executors,
    and ``fit(plan=...)`` swaps policy without touching app config;
  * the scheduler carry is engine-owned: it returns in
    ``EngineCarry.sched_carry`` / ``SSPCarry.sched_carry`` and the SSP
    in-flight exclusion runs on it;
  * ``repro.core.schedulers`` / ``repro.core.block_scheduler`` still
    import, with a DeprecationWarning (the PR 3 shim pattern).
"""
import importlib
import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.apps import lasso, lda, mf
from repro.core import ExecutionPlan, single_device_mesh
from repro.sched import (BlockStructuralScheduler, Scheduler, SchedulerSpec,
                         build_scheduler, dependency_filter,
                         sample_candidates, structural_gram)
from repro.sched.block import BlockScheduleConfig, select_blocks


@pytest.fixture(scope="module")
def mesh():
    return single_device_mesh()


def _bit_identical(a_state, b_state):
    assert set(a_state) == set(b_state)
    for k in a_state:
        a, b = np.asarray(a_state[k]), np.asarray(b_state[k])
        assert (a == b).all(), (k, np.max(np.abs(a - b)))


def _dyn_spec(**kw):
    base = dict(kind="dynamic_priority", block_size=4, num_candidates=8,
                rho=0.3, eta=1e-6)
    base.update(kw)
    return SchedulerSpec(**base)


# ---------------------------------------------------------------------------
# construction-time validation (mirrors tests/test_plan.py)
# ---------------------------------------------------------------------------

def test_spec_is_hashable_value():
    a, b = _dyn_spec(), _dyn_spec()
    assert a == b and hash(a) == hash(b) and len({a, b}) == 1


def test_spec_rejects_unknown_kind_with_canonical_message():
    with pytest.raises(ValueError, match="scheduler kind must be "
                                         "'round_robin', 'random'"):
        SchedulerSpec(kind="warp", block_size=4)


@pytest.mark.parametrize("kw", [
    dict(kind="round_robin"),                       # needs block_size
    dict(kind="round_robin", block_size=4, rho=0.3),  # rho is dynamic-only
    dict(kind="random", block_size=0),
    dict(kind="random", block_size=4, num_candidates=8),
    dict(kind="rotation", block_size=4),            # rotation takes nothing
    dict(kind="dynamic_priority", block_size=8, num_candidates=4,
         rho=0.3, eta=1e-6),                        # U' < U
    dict(kind="dynamic_priority", block_size=4, num_candidates=8,
         rho=0.0, eta=1e-6),                        # needs rho > 0
    dict(kind="dynamic_priority", block_size=4, num_candidates=8,
         rho=-0.3, eta=1e-6),
    dict(kind="block_structural", block_size=2, num_candidates=4,
         rho=0.5, eta=-1e-3, min_distance=2, ema=0.9),  # eta >= 0
    dict(kind="dynamic_priority", block_size=4, num_candidates=8,
         rho=0.3, eta=1e-6, min_distance=2),        # structural-only
    dict(kind="block_structural", block_size=2, num_candidates=4,
         rho=0.5, eta=1e-3, min_distance=0, ema=0.9),  # needs distance >= 1
    dict(kind="block_structural", block_size=2, num_candidates=4,
         rho=0.5, eta=1e-3, min_distance=2, ema=1.0),  # ema < 1
    dict(kind="dynamic_priority", block_size=-1, num_candidates=8,
         rho=0.3, eta=1e-6),
])
def test_invalid_spec_combinations_raise_at_construction(kw):
    with pytest.raises(ValueError):
        SchedulerSpec(**kw)


# ---------------------------------------------------------------------------
# JSON round-trip (standalone and nested in ExecutionPlan)
# ---------------------------------------------------------------------------

def test_spec_json_roundtrip_exact_including_defaults():
    specs = [
        SchedulerSpec(kind="rotation"),
        SchedulerSpec(kind="round_robin", block_size=8),
        _dyn_spec(rho=0.6, num_candidates=64),
        SchedulerSpec(kind="block_structural", block_size=2,
                      num_candidates=4, rho=0.5, eta=1e-3,
                      min_distance=2, ema=0.9),
    ]
    for s in specs:
        d = s.to_json()
        assert SchedulerSpec.from_json(d) == s
        assert SchedulerSpec.from_json(json.dumps(d)) == s
    with pytest.raises(ValueError, match="unknown SchedulerSpec field"):
        SchedulerSpec.from_json({"kind": "random", "blocksize": 4})


def test_plan_json_roundtrips_with_and_without_scheduler():
    with_spec = ExecutionPlan(executor="ssp", rounds=12, staleness=2,
                              scheduler=_dyn_spec())
    without = ExecutionPlan(executor="ssp", rounds=12, staleness=2)
    for p in (with_spec, without):
        d = p.to_json()
        assert ExecutionPlan.from_json(d) == p
        assert ExecutionPlan.from_json(json.dumps(d)) == p
    # the nested spec serializes as a plain dict (JSON-safe all the way)
    assert with_spec.to_json()["scheduler"]["kind"] == "dynamic_priority"
    assert without.to_json()["scheduler"] is None
    # invalid nested specs raise through from_json (construction-time)
    with pytest.raises(ValueError, match="needs rho > 0"):
        ExecutionPlan.from_json({"executor": "scan", "rounds": 4,
                                 "scheduler": {"kind": "dynamic_priority",
                                               "block_size": 4,
                                               "num_candidates": 8,
                                               "eta": 1e-6}})
    # previously-legal degenerate configs stay constructible: eta=0
    # (no exploration floor) and rho>1 (filter disabled)
    assert SchedulerSpec.from_json(
        {"kind": "dynamic_priority", "block_size": 4,
         "num_candidates": 8, "rho": 1.5, "eta": 0.0}).rho == 1.5
    with pytest.raises(ValueError, match="SchedulerSpec"):
        ExecutionPlan(executor="scan", rounds=4, scheduler="dynamic")


# ---------------------------------------------------------------------------
# the dependency filter property (both gram backends)
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(st.integers(2, 12), st.floats(0.05, 0.95), st.integers(1, 8),
       st.integers(0, 2**31 - 1))
def test_dependency_filter_invariant(u, rho, max_sel, seed):
    """Every kept pair satisfies |gram| < ρ, at most ``max_select`` are
    kept, and candidate 0 is always admitted (greedy over an empty set)."""
    r = np.random.default_rng(seed)
    A = r.normal(size=(20, u)).astype(np.float32)
    A /= np.linalg.norm(A, axis=0, keepdims=True)
    gram = jnp.asarray(A.T @ A)
    keep = np.asarray(dependency_filter(gram, rho=rho, max_select=max_sel))
    assert keep.sum() <= max_sel
    assert keep[0]                       # greedy always admits the first
    kept = np.where(keep)[0]
    g = np.abs(np.asarray(gram))
    for a in kept:
        for b in kept:
            if a < b:
                assert g[a, b] < rho


@settings(max_examples=30, deadline=None)
@given(st.integers(2, 10), st.integers(1, 4), st.integers(1, 6),
       st.integers(0, 2**31 - 1))
def test_structural_backend_is_the_same_filter(u, min_dist, max_sel, seed):
    """The block scheduler's distance rule is literally
    ``dependency_filter`` fed the structural gram: every kept pair is
    ``min_distance`` apart, ≤ max_select kept, candidate 0 admitted."""
    r = np.random.default_rng(seed)
    cand = jnp.asarray(r.choice(32, size=u, replace=False).astype(np.int32))
    keep = np.asarray(dependency_filter(
        structural_gram(cand, min_dist), 0.5, max_sel))
    assert keep.sum() <= max_sel
    assert keep[0]
    kept = np.asarray(cand)[np.where(keep)[0]]
    for a in kept:
        for b in kept:
            if a != b:
                assert abs(int(a) - int(b)) >= min_dist


def test_select_blocks_goes_through_shared_filter():
    """The (num_blocks,) trainer mask equals the shared-filter keep set
    scattered onto candidate positions (no parallel f₂ implementation)."""
    cfg = BlockScheduleConfig(num_blocks=16, blocks_per_step=4,
                              candidates_per_step=8, min_distance=3)
    rng = jax.random.key(7)
    mask = np.asarray(select_blocks(cfg, jnp.ones(16), rng))
    cand = np.asarray(sample_candidates(rng, jnp.ones(16) + cfg.eta, 8))
    keep = np.asarray(dependency_filter(
        structural_gram(jnp.asarray(cand), 3), cfg.rho, 4))
    want = np.zeros(16, np.float32)
    want[cand] = keep.astype(np.float32)
    assert (mask == want).all()


# ---------------------------------------------------------------------------
# spec → scheduler construction and the protocol surface
# ---------------------------------------------------------------------------

def test_build_scheduler_dispatch_and_protocol():
    for spec, carryful in [
            (SchedulerSpec(kind="round_robin", block_size=4), False),
            (SchedulerSpec(kind="random", block_size=4), False),
            (SchedulerSpec(kind="rotation"), False),
            (_dyn_spec(), True),
            (SchedulerSpec(kind="block_structural", block_size=2,
                           num_candidates=4, rho=0.5, eta=1e-3,
                           min_distance=2, ema=0.9), True)]:
        sched = build_scheduler(spec, num_vars=20, num_workers=2)
        assert isinstance(sched, Scheduler), spec.kind
        carry = sched.init_carry()
        assert (carry is not None) == carryful, spec.kind
    with pytest.raises(TypeError, match="SchedulerSpec"):
        build_scheduler("dynamic_priority", num_vars=8, num_workers=1)


def test_block_structural_scheduler_respects_distance():
    sched = BlockStructuralScheduler(num_blocks=24, block_size=4,
                                     num_candidates=12, min_distance=3)
    carry = sched.init_carry()
    cand = sched.propose(carry, jax.random.key(0))
    idx, mask = sched.finalize(cand)
    idx, mask = np.asarray(idx), np.asarray(mask)
    kept = idx[mask]
    assert 1 <= len(kept) <= 4
    for a in kept:
        for b in kept:
            if a != b:
                assert abs(int(a) - int(b)) >= 3
    # carry update only moves scheduled entries
    new = np.asarray(sched.update_carry(carry, jnp.asarray(idx),
                                        jnp.asarray(mask),
                                        10.0 * jnp.ones(len(idx))))
    untouched = np.setdiff1d(np.arange(24), kept)
    assert (new[untouched] == 1.0).all()
    assert (new[kept] != 1.0).all()


def test_apps_declare_default_specs():
    assert lda.StradsLDA(lda.LDAConfig(
        vocab=30, num_topics=4, num_workers=1, tokens_per_worker=8,
        docs_per_worker=2)).default_scheduler_spec() == \
        SchedulerSpec(kind="rotation")
    assert mf.StradsMF(mf.MFConfig(
        num_rows=8, num_cols=6, rank=4,
        ranks_per_round=2)).default_scheduler_spec() == \
        SchedulerSpec(kind="round_robin", block_size=2)


# ---------------------------------------------------------------------------
# plan-carried policy ≡ app default (the acceptance bit-identity), and
# policy swaps without app edits
# ---------------------------------------------------------------------------

def test_explicit_default_spec_is_bit_identical_all_executors(mesh, rng):
    """A plan carrying an explicit SchedulerSpec equal to the app's
    default must run bit-identically to the spec-less plan on every
    executor (the redesign moved the policy without moving the math)."""
    X, y, _ = lasso.synthetic_correlated(rng, n=40, J=20, k_true=3)
    cfg = lasso.LassoConfig(num_features=20, lam=0.02, block_size=4,
                            num_candidates=8, rho=0.3)
    eng = lasso.make_engine(cfg, mesh)
    data = eng.shard_data({"X": jnp.asarray(X), "y": jnp.asarray(y)})
    spec = eng.app.default_scheduler_spec()

    for name, s in [("loop", 0), ("scan", 0), ("pipelined", 0),
                    ("ssp", 1)]:
        base = ExecutionPlan(executor=name, rounds=8, staleness=s)
        withspec = ExecutionPlan(executor=name, rounds=8, staleness=s,
                                 scheduler=spec)
        a = eng.execute(eng.init_state(jax.random.key(0), y=y), data,
                        jax.random.key(1), base)
        b = eng.execute(eng.init_state(jax.random.key(0), y=y), data,
                        jax.random.key(1), withspec)
        _bit_identical(a.state, b.state)
        assert (np.asarray(a.carry.sched_carry)
                == np.asarray(b.carry.sched_carry)).all(), name


def test_plan_swaps_policy_without_touching_app_config(mesh, rng):
    """fit(plan=...) with a different SchedulerSpec must override the
    config policy — and reproduce the config that names that policy."""
    X, y, _ = lasso.synthetic_correlated(rng, n=40, J=20, k_true=3)
    strads = lasso.LassoConfig(num_features=20, lam=0.02, block_size=4,
                               num_candidates=8, rho=0.3)
    cyclic = lasso.LassoConfig(num_features=20, lam=0.02, block_size=4,
                               scheduler="cyclic")
    # strads config + round_robin plan == cyclic config, bit for bit
    s_plan, _ = lasso.fit(strads, X, y, mesh, plan=ExecutionPlan(
        executor="scan", rounds=8,
        scheduler=SchedulerSpec(kind="round_robin", block_size=4)))
    s_cfg, _ = lasso.fit(cyclic, X, y, mesh,
                         plan=ExecutionPlan(executor="scan", rounds=8))
    _bit_identical(s_plan, s_cfg)
    # and a rho sweep point differs from the default (the knob is live)
    s_rho, _ = lasso.fit(strads, X, y, mesh, plan=ExecutionPlan(
        executor="scan", rounds=8,
        scheduler=SchedulerSpec(kind="dynamic_priority", block_size=4,
                                num_candidates=8, rho=0.05, eta=1e-6)))
    s_def, _ = lasso.fit(strads, X, y, mesh,
                         plan=ExecutionPlan(executor="scan", rounds=8))
    assert not (np.asarray(s_rho["beta"])
                == np.asarray(s_def["beta"])).all()


def test_mf_takes_injected_policy_via_plan(mesh, rng):
    """The rank dispatch is swappable too: a random-rank plan runs (and
    differs from round-robin), with no MF config surface involved — and
    stochastic policies still pair the two halves of each H/W cycle
    (the proposal key derives from the cycle index)."""
    A, mask = mf.synthetic_ratings(rng, 20, 15, true_rank=3, density=0.5)
    cfg = mf.MFConfig(num_rows=20, num_cols=15, rank=3, lam=0.05)
    # 12 rounds = 6 cycles: the cycle-keyed random draws provably leave
    # the round-robin sequence by cycle 5 (at 4 cycles they coincide)
    rr, _ = mf.fit(cfg, A, mask, mesh,
                   plan=ExecutionPlan(executor="scan", rounds=12))
    rnd, _ = mf.fit(cfg, A, mask, mesh, plan=ExecutionPlan(
        executor="scan", rounds=12,
        scheduler=SchedulerSpec(kind="random", block_size=1)))
    assert not (np.asarray(rr["H"]) == np.asarray(rnd["H"])).all()

    eng = mf.make_engine(cfg, mesh)
    eng.set_scheduler(SchedulerSpec(kind="random", block_size=1))
    data = eng.shard_data(eng.app.layout(A, mask))
    st = eng.init_state(jax.random.key(0), data=data)
    sc = eng.init_sched_carry()
    ranks = []
    for t in range(6):
        out = eng.run_round(st, data, jax.random.key(t), t,
                            sched_carry=sc)
        st, sc = out.state, out.sched_carry
        ranks.append(int(np.asarray(out.sched["ranks"])[0]))
    assert all(ranks[2 * i] == ranks[2 * i + 1] for i in range(3)), ranks


def test_ssp_in_flight_exclusion_runs_on_the_carry(mesh, rng):
    """At s >= 1 the window's later proposals must not re-pick the
    coordinates already in flight: propose from the marked carry never
    overlaps the first proposal (device-checked via the scheduler's own
    mark_scheduled semantics)."""
    spec = _dyn_spec(num_candidates=6, block_size=3)
    sched = build_scheduler(spec, num_vars=12, num_workers=1)
    carry = 10.0 * jnp.ones(12)                 # strong, uniform priority
    c1 = sched.propose(carry, jax.random.key(0))
    marked = sched.mark_scheduled(carry, c1)
    assert (np.asarray(marked)[np.asarray(c1)] == 0).all()
    # with eta tiny, the 6 unmarked coordinates win every draw
    c2 = np.asarray(sched.propose(marked, jax.random.key(1)))
    assert not set(c2.tolist()) & set(np.asarray(c1).tolist())


def test_engine_constructor_spec_outranks_app_default(mesh, rng):
    """StradsEngine(..., scheduler=spec) must actually govern plan-less
    and scheduler-less-plan runs (plan > constructor > app default)."""
    X, y, _ = lasso.synthetic_correlated(rng, n=40, J=20, k_true=3)
    cfg = lasso.LassoConfig(num_features=20, lam=0.02, block_size=4,
                            num_candidates=8, rho=0.3)
    rr_spec = SchedulerSpec(kind="random", block_size=4)
    eng = lasso.make_engine(cfg, mesh, scheduler=rr_spec)
    data = eng.shard_data({"X": jnp.asarray(X), "y": jnp.asarray(y)})
    got = eng.execute(eng.init_state(jax.random.key(0), y=y), data,
                      jax.random.key(1),
                      ExecutionPlan(executor="scan", rounds=8)).state

    want = eng.execute(eng.init_state(jax.random.key(0), y=y), data,
                       jax.random.key(1),
                       ExecutionPlan(executor="scan", rounds=8,
                                     scheduler=rr_spec)).state
    _bit_identical(got, want)
    assert eng.scheduler_spec == rr_spec


def test_stale_aot_handle_rebinds_its_spec(mesh, rng):
    """A scanned_fn/ssp_fn handle fetched under spec A must run policy A
    even if set_scheduler switched to B before the handle first traced
    (lazy tracing must not bake B into A's cache slot)."""
    X, y, _ = lasso.synthetic_correlated(rng, n=40, J=20, k_true=3)
    cfg = lasso.LassoConfig(num_features=20, lam=0.02, block_size=4,
                            num_candidates=8, rho=0.3)
    eng = lasso.make_engine(cfg, mesh)
    data = eng.shard_data({"X": jnp.asarray(X), "y": jnp.asarray(y)})
    spec_a = eng.app.default_scheduler_spec()          # dynamic_priority
    fn_a = eng.scanned_fn(4, donate=False)             # untraced handle
    eng.set_scheduler(SchedulerSpec(kind="random", block_size=4))
    carry_a = jnp.ones((20,), jnp.float32)             # A's init carry
    got = fn_a(eng.init_state(jax.random.key(0), y=y), data,
               jax.random.key(1), jnp.int32(0), carry_a)[0]
    assert eng.scheduler_spec == spec_a                # handle rebound A
    want = eng.execute(eng.init_state(jax.random.key(0), y=y), data,
                       jax.random.key(1),
                       ExecutionPlan(executor="scan", rounds=4,
                                     scheduler=spec_a,
                                     donate=False)).state
    _bit_identical(got, want)


def test_ssp_carry_returned_and_resumable(mesh, rng):
    X, y, _ = lasso.synthetic_correlated(rng, n=40, J=20, k_true=3)
    cfg = lasso.LassoConfig(num_features=20, lam=0.02, block_size=4,
                            num_candidates=8, rho=0.3)
    eng = lasso.make_engine(cfg, mesh)
    data = eng.shard_data({"X": jnp.asarray(X), "y": jnp.asarray(y)})
    plan = ExecutionPlan(executor="ssp", rounds=8, staleness=1)
    full = eng.execute(eng.init_state(jax.random.key(0), y=y), data,
                       jax.random.key(1), plan).state
    half = eng.execute(eng.init_state(jax.random.key(0), y=y), data,
                       jax.random.key(1),
                       ExecutionPlan(executor="ssp", rounds=4,
                                     staleness=1))
    carry = half.carry
    assert carry.sched_carry is not None
    resumed = eng.execute(half.state, data, None, plan, carry=carry).state
    _bit_identical(full, resumed)


def test_incompatible_app_policy_pairs_rejected_at_injection(mesh, rng):
    """A plan naming a kind the app cannot consume must fail at
    set_scheduler time with a readable error — never mid-trace."""
    X, y, _ = lasso.synthetic_correlated(rng, n=40, J=20, k_true=3)
    cfg = lasso.LassoConfig(num_features=20, lam=0.02, block_size=4,
                            num_candidates=8, rho=0.3)
    eng = lasso.make_engine(cfg, mesh)
    data = eng.shard_data({"X": jnp.asarray(X), "y": jnp.asarray(y)})
    state = eng.init_state(jax.random.key(0), y=y)
    plan = ExecutionPlan(executor="scan", rounds=4,
                         scheduler=SchedulerSpec(kind="rotation"))
    with pytest.raises(ValueError, match="cannot consume a 'rotation'"):
        eng.execute(state, data, jax.random.key(1), plan)
    # U' larger than the schedulable-variable count is caught too
    with pytest.raises(ValueError, match="num_candidates"):
        eng.set_scheduler(_dyn_spec(num_candidates=64, block_size=4))


def test_resume_with_mismatched_scheduler_spec_rejected(mesh, rng):
    """A checkpointed carry only resumes under the policy that produced
    it: stateless-carry → stateful-plan (and the reverse) error upfront
    instead of crashing mid-trace or silently threading stale state."""
    X, y, _ = lasso.synthetic_correlated(rng, n=40, J=20, k_true=3)
    cfg = lasso.LassoConfig(num_features=20, lam=0.02, block_size=4,
                            num_candidates=8, rho=0.3)
    eng = lasso.make_engine(cfg, mesh)
    data = eng.shard_data({"X": jnp.asarray(X), "y": jnp.asarray(y)})
    rr_spec = SchedulerSpec(kind="random", block_size=4)
    rr_carry = eng.execute(eng.init_state(jax.random.key(0), y=y), data,
                           jax.random.key(1),
                           ExecutionPlan(executor="scan", rounds=4,
                                         scheduler=rr_spec)).carry
    dyn_carry = eng.execute(eng.init_state(jax.random.key(0), y=y), data,
                            jax.random.key(1),
                            ExecutionPlan(executor="scan",
                                          rounds=4)).carry
    state = eng.init_state(jax.random.key(0), y=y)
    with pytest.raises(ValueError, match="sched_carry is None"):
        eng.execute(state, data, None,
                    ExecutionPlan(executor="scan", rounds=8),
                    carry=rr_carry)
    with pytest.raises(ValueError, match="stateless"):
        eng.execute(state, data, None,
                    ExecutionPlan(executor="scan", rounds=8,
                                  scheduler=rr_spec),
                    carry=dyn_carry)


# ---------------------------------------------------------------------------
# deprecation shims (the PR 3 pattern)
# ---------------------------------------------------------------------------

def test_old_import_paths_warn_but_work():
    import repro.core.schedulers as old_s
    import repro.core.block_scheduler as old_b
    with pytest.warns(DeprecationWarning, match="moved to repro.sched"):
        importlib.reload(old_s)
    with pytest.warns(DeprecationWarning, match="moved to repro.sched"):
        importlib.reload(old_b)
    from repro.sched.schedulers import DynamicPriorityScheduler
    from repro.sched.block import BlockScheduleConfig as NewCfg
    assert old_s.DynamicPriorityScheduler is DynamicPriorityScheduler
    assert old_b.BlockScheduleConfig is NewCfg


def test_core_package_import_does_not_warn():
    """Importing repro.core (or repro.sched) must NOT trip the shim
    warnings — only the legacy module paths do."""
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        importlib.import_module("repro.core")
        importlib.import_module("repro.sched")
