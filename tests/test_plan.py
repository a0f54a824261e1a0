"""The unified ExecutionPlan surface (ISSUE 3 acceptance).

Contract under test:
  * plans are frozen, hashable values; invalid executor/kwarg
    combinations raise at construction, never at trace time, with the
    executor-name message living in exactly one place;
  * ``to_json → from_json`` round-trips exactly, defaults included, and
    the checked-in ``examples/plans/*.json`` files parse;
  * ``StradsEngine.execute(plan)`` drives all four executors with one
    report contract, and the scan executor is bit-identical to the loop
    and ssp at s=0 to scan on Lasso — the per-app equivalence lives in
    tests/test_engine_scan.py and tests/test_ssp.py;
  * the derived v2 SSP behavior replaced the per-app ``ssp_*`` hook
    overrides (they are gone from the apps).
"""
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps import lasso, lda, mf
from repro.core import ExecutionPlan, ExecutionReport, single_device_mesh
from repro.obs import TelemetrySpec

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def mesh():
    return single_device_mesh()


def _bit_identical(a_state, b_state):
    assert set(a_state) == set(b_state)
    for k in a_state:
        a, b = np.asarray(a_state[k]), np.asarray(b_state[k])
        assert (a == b).all(), (k, np.max(np.abs(a - b)))


def _lasso_setup(rng, n=40, J=20):
    X, y, _ = lasso.synthetic_correlated(rng, n=n, J=J, k_true=3)
    cfg = lasso.LassoConfig(num_features=J, lam=0.02, block_size=4,
                            num_candidates=8, rho=0.3)
    return cfg, X, y


# ---------------------------------------------------------------------------
# construction-time validation (the single source of truth)
# ---------------------------------------------------------------------------

def test_plan_is_hashable_value():
    a = ExecutionPlan(executor="ssp", rounds=8, staleness=2)
    b = ExecutionPlan(executor="ssp", rounds=8, staleness=2)
    assert a == b and hash(a) == hash(b)
    assert len({a, b}) == 1


def test_plan_rejects_unknown_executor_with_canonical_message():
    with pytest.raises(ValueError, match="executor must be 'loop', "
                                         "'scan', 'pipelined' or 'ssp'"):
        ExecutionPlan(executor="warp", rounds=4)
    # 'loop' really is acceptable (the drifted apps/_exec.scan_depth
    # message claimed so but raised — ISSUE 3 satellite)
    assert ExecutionPlan(executor="loop", rounds=4).depth == 0


@pytest.mark.parametrize("kw", [
    dict(executor="scan", staleness=1),         # staleness needs ssp
    dict(executor="scan", rounds=0),
    dict(executor="scan", rounds=1, staleness=-1),
    dict(executor="loop", rounds=4, phase_unroll=2),
    dict(executor="ssp", rounds=4, phase_unroll=2),
    dict(executor="scan", rounds=4, telemetry="counters"),  # not a spec
    dict(executor="scan", rounds=4, telemetry=True),        # not a spec
    dict(executor="scan", rounds=4, workers=0),
    dict(executor="scan", rounds=4, collect_every=-1),
])
def test_invalid_combinations_raise_at_construction(kw):
    with pytest.raises(ValueError):
        ExecutionPlan(**kw)


def test_plan_depth_derivation():
    assert ExecutionPlan(executor="scan", rounds=2).depth == 0
    assert ExecutionPlan(executor="pipelined", rounds=2).depth == 1
    assert ExecutionPlan(executor="ssp", rounds=2).depth == 0


# ---------------------------------------------------------------------------
# JSON round-trip
# ---------------------------------------------------------------------------

def test_plan_json_roundtrip_exact_including_defaults():
    plans = [
        ExecutionPlan(),
        ExecutionPlan(executor="loop", rounds=3, collect_every=2),
        ExecutionPlan(executor="pipelined", rounds=8, phase_unroll=2,
                      donate=False),
        ExecutionPlan(executor="ssp", rounds=12, staleness=2,
                      telemetry=TelemetrySpec(kind="counters"),
                      checkpoint_every=6, workers=4),
    ]
    for p in plans:
        d = p.to_json()
        assert ExecutionPlan.from_json(d) == p
        # and through an actual JSON string
        assert ExecutionPlan.from_json(json.dumps(d)) == p


def test_plan_from_json_partial_and_unknown_keys():
    p = ExecutionPlan.from_json({"executor": "ssp", "rounds": 4,
                                 "staleness": 1})
    assert p == ExecutionPlan(executor="ssp", rounds=4, staleness=1)
    with pytest.raises(ValueError, match="unknown ExecutionPlan field"):
        ExecutionPlan.from_json({"executor": "scan", "depth": 1})
    # invalid combinations raise through from_json too (construction-time)
    with pytest.raises(ValueError, match="requires executor='ssp'"):
        ExecutionPlan.from_json({"executor": "scan", "rounds": 4,
                                 "staleness": 2})


def test_checked_in_example_plans_parse():
    paths = sorted(glob.glob(os.path.join(ROOT, "examples", "plans",
                                          "*.json")))
    assert len(paths) >= 2, "examples/plans/ must ship example plans"
    names = {os.path.basename(p) for p in paths}
    assert "ssp_s2.json" in names          # the CI dry-run smoke plan
    for path in paths:
        with open(path) as f:
            raw = json.load(f)
        plan = ExecutionPlan.from_json(raw)
        assert plan.to_json() == raw       # files are exact to_json dumps


# ---------------------------------------------------------------------------
# execute(plan): one report contract, all four executors
# ---------------------------------------------------------------------------

def test_execute_matches_legacy_entry_points_all_executors(mesh, rng):
    """Every executor fills the same report; scan is bit-identical to
    the loop, and ssp at s=0 to scan."""
    cfg, X, y = _lasso_setup(rng)
    eng = lasso.make_engine(cfg, mesh)
    data = eng.shard_data({"X": jnp.asarray(X), "y": jnp.asarray(y)})

    def init():
        return eng.init_state(jax.random.key(0), y=y)

    plans = {
        "loop": ExecutionPlan(executor="loop", rounds=8),
        "scan": ExecutionPlan(executor="scan", rounds=8),
        "pipelined": ExecutionPlan(executor="pipelined", rounds=8),
        "ssp": ExecutionPlan(executor="ssp", rounds=8, staleness=1),
        "ssp0": ExecutionPlan(executor="ssp", rounds=8),
    }
    states = {}
    for name, plan in plans.items():
        rep = eng.execute(init(), data, jax.random.key(1), plan)
        assert isinstance(rep, ExecutionReport)
        assert rep.plan is plan and rep.carry is not None
        assert int(rep.carry.t) == 8
        assert rep.trace is None and rep.telemetry is None
        states[name] = rep.state
    _bit_identical(states["loop"], states["scan"])
    _bit_identical(states["scan"], states["ssp0"])


def test_execute_validates_workers_and_callback(mesh, rng):
    cfg, X, y = _lasso_setup(rng)
    eng = lasso.make_engine(cfg, mesh)
    data = eng.shard_data({"X": jnp.asarray(X), "y": jnp.asarray(y)})
    state = eng.init_state(jax.random.key(0), y=y)
    with pytest.raises(ValueError, match="plan.workers"):
        eng.execute(state, data, jax.random.key(1),
                    ExecutionPlan(executor="scan", rounds=2, workers=7))
    with pytest.raises(ValueError, match="callback"):
        eng.execute(state, data, jax.random.key(1),
                    ExecutionPlan(executor="scan", rounds=2),
                    callback=lambda t, s, o: False)


def test_execute_phase_unroll_is_bit_identical(mesh, rng):
    cfg, X, y = _lasso_setup(rng)
    eng = lasso.make_engine(cfg, mesh)
    data = eng.shard_data({"X": jnp.asarray(X), "y": jnp.asarray(y)})

    def run(unroll):
        plan = ExecutionPlan(executor="scan", rounds=8,
                             phase_unroll=unroll, donate=False)
        return eng.execute(eng.init_state(jax.random.key(0), y=y), data,
                           jax.random.key(1), plan).state

    _bit_identical(run(1), run(4))


# ---------------------------------------------------------------------------
# the fit adapters
# ---------------------------------------------------------------------------

def test_fit_default_path_does_not_warn(mesh, rng):
    import warnings as W
    cfg, X, y = _lasso_setup(rng)
    with W.catch_warnings():
        W.simplefilter("error", DeprecationWarning)
        lasso.fit(cfg, X, y, mesh, num_rounds=2)


def test_fit_rejects_plan_plus_legacy_kwargs(mesh, rng):
    cfg, X, y = _lasso_setup(rng)
    plan = ExecutionPlan(executor="scan", rounds=4)
    with pytest.raises(ValueError, match="contradicts"):
        lasso.fit(cfg, X, y, mesh, num_rounds=5, plan=plan)


def test_fit_rejects_plan_fields_it_cannot_honor(mesh, rng):
    """fit() has no telemetry/checkpoint surface — silently dropping
    those plan fields would lie to the caller, so they are rejected."""
    cfg, X, y = _lasso_setup(rng)
    with pytest.raises(ValueError, match="telemetry"):
        lasso.fit(cfg, X, y, mesh,
                  plan=ExecutionPlan(executor="ssp", rounds=4,
                                     staleness=1,
                                     telemetry=TelemetrySpec(
                                         kind="counters")))
    with pytest.raises(ValueError, match="checkpoint"):
        lasso.fit(cfg, X, y, mesh,
                  plan=ExecutionPlan(executor="scan", rounds=4,
                                     checkpoint_every=2))


# ---------------------------------------------------------------------------
# v2 protocol: no SSP hooks in the apps
# ---------------------------------------------------------------------------

def test_apps_define_no_v1_ssp_hooks():
    for app_cls in (lasso.StradsLasso, lda.StradsLDA, mf.StradsMF):
        for hook in ("ssp_commit_local", "ssp_defer_local",
                     "ssp_commit_shared", "ssp_mark_scheduled"):
            assert not hasattr(app_cls, hook), (app_cls.__name__, hook)


def test_lasso_default_scheduler_specs_follow_config(rng):
    """The app's policy is declarative now: cfg.scheduler maps onto a
    default SchedulerSpec (and no state leaf carries priorities — the
    Δβ history is the engine-owned scheduler carry)."""
    from repro.sched import SchedulerSpec
    cfg, X, y = _lasso_setup(rng)
    assert lasso.StradsLasso(cfg).default_scheduler_spec() == \
        SchedulerSpec(kind="dynamic_priority", block_size=4,
                      num_candidates=8, rho=0.3, eta=1e-6)
    rr = lasso.LassoConfig(num_features=20, scheduler="rr")
    assert lasso.StradsLasso(rr).default_scheduler_spec() == \
        SchedulerSpec(kind="random", block_size=8)
    assert lasso.StradsLasso(cfg).var_roles() == {}
