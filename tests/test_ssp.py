"""The bounded-staleness parameter-server subsystem (repro/ps).

Contract under test (ISSUE 2 acceptance):
  * the ``"ssp"`` executor at ``staleness=0`` is bit-identical to
    ``"scan"`` on all three paper apps — the correctness anchor for the
    whole subsystem.
  * the staleness invariant: no read is ever served more than ``s``
    clocks stale, asserted over the *device-observed* telemetry for
    random schedules (hypothesis property; deterministic stub fallback).
  * ``s >= 1`` still converges (Lasso objective, LDA count conservation)
    — the SSP trade-off is error, never corruption.
  * KV-store wiring: placement + byte accounting flow from
    ``StradsEngine.place_state`` / ``core.kvstore``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.apps import lasso, lda, mf
from repro.core import ExecutionPlan, single_device_mesh
from repro.ps import ParameterServer, StaleCache, init_clocks


@pytest.fixture(scope="module")
def mesh():
    return single_device_mesh()


def _bit_identical(a_state, b_state):
    assert set(a_state) == set(b_state)
    for k in a_state:
        a, b = np.asarray(a_state[k]), np.asarray(b_state[k])
        assert (a == b).all(), (k, np.max(np.abs(a - b)))


def _lasso_problem(rng, n=60, J=30):
    X, y, _ = lasso.synthetic_correlated(rng, n=n, J=J, k_true=4)
    cfg = lasso.LassoConfig(num_features=J, lam=0.02, block_size=4,
                            num_candidates=12, rho=0.3)
    return cfg, X, y


# ---------------------------------------------------------------------------
# staleness 0: bit-identical to the BSP scan (hence to the host loop)
# ---------------------------------------------------------------------------

def test_lasso_ssp0_bit_identical_to_scan(mesh, rng):
    cfg, X, y = _lasso_problem(rng)
    s_scan, _ = lasso.fit(cfg, X, y, mesh,
                          plan=ExecutionPlan(executor="scan", rounds=20))
    s_ssp, _ = lasso.fit(cfg, X, y, mesh,
                         plan=ExecutionPlan(executor="ssp", rounds=20,
                                            staleness=0))
    _bit_identical(s_scan, s_ssp)


def test_lasso_ssp0_trace_matches_scan_trace(mesh, rng):
    cfg, X, y = _lasso_problem(rng)
    _, tr_scan = lasso.fit(cfg, X, y, mesh,
                           plan=ExecutionPlan(executor="scan", rounds=10,
                                              collect_every=2))
    _, tr_ssp = lasso.fit(cfg, X, y, mesh,
                          plan=ExecutionPlan(executor="ssp", rounds=10,
                                             staleness=0, collect_every=2))
    assert tr_scan == tr_ssp


def test_lda_ssp0_bit_identical_to_scan(mesh, rng):
    cfg = lda.LDAConfig(vocab=30, num_topics=4, num_workers=1,
                        tokens_per_worker=200, docs_per_worker=5)
    words, docs, z0 = lda.synthetic_corpus(rng, cfg, true_topics=4)
    s_scan, _, _ = lda.fit(cfg, words, docs, z0, mesh,
                           plan=ExecutionPlan(executor="scan", rounds=6))
    s_ssp, _, _ = lda.fit(cfg, words, docs, z0, mesh,
                          plan=ExecutionPlan(executor="ssp", rounds=6,
                                             staleness=0))
    _bit_identical(s_scan, s_ssp)


def test_mf_ssp0_bit_identical_to_scan(mesh, rng):
    A, mask = mf.synthetic_ratings(rng, 40, 30, true_rank=4, density=0.5)
    cfg = mf.MFConfig(num_rows=40, num_cols=30, rank=4, lam=0.05)
    s_scan, _ = mf.fit(cfg, A, mask, mesh,
                       plan=ExecutionPlan(executor="scan", rounds=8))
    s_ssp, _ = mf.fit(cfg, A, mask, mesh,
                      plan=ExecutionPlan(executor="ssp", rounds=8,
                                         staleness=0))
    _bit_identical(s_scan, s_ssp)


def test_mf_ssp1_window_equals_full_cycle_is_exact(mesh, rng):
    """At s=1 the MF window is exactly one H/W cycle: the H push reads a
    fresh snapshot and the W commit recomputes from flush-time state, so
    SSP introduces *zero* staleness error — bit-identical to BSP."""
    A, mask = mf.synthetic_ratings(rng, 40, 30, true_rank=4, density=0.5)
    cfg = mf.MFConfig(num_rows=40, num_cols=30, rank=4, lam=0.05)
    s_scan, _ = mf.fit(cfg, A, mask, mesh,
                       plan=ExecutionPlan(executor="scan", rounds=8))
    s_ssp, _ = mf.fit(cfg, A, mask, mesh,
                      plan=ExecutionPlan(executor="ssp", rounds=8,
                                         staleness=1))
    _bit_identical(s_scan, s_ssp)


# ---------------------------------------------------------------------------
# the staleness invariant (property over random schedules)
# ---------------------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(st.integers(min_value=0, max_value=4),
       st.integers(min_value=1, max_value=3),
       st.sampled_from(["strads", "rr", "cyclic"]))
def test_read_staleness_never_exceeds_bound(staleness, steps, scheduler):
    """max observed read-staleness ≤ s, asserted over the device-side
    telemetry the compiled program actually recorded — for random
    (staleness, length, scheduler) configurations."""
    mesh = single_device_mesh()
    r = np.random.default_rng(staleness * 7 + steps)
    X, y, _ = lasso.synthetic_correlated(r, n=24, J=12, k_true=3)
    cfg = lasso.LassoConfig(num_features=12, lam=0.02, block_size=3,
                            num_candidates=6, rho=0.5,
                            scheduler=scheduler)
    eng = lasso.make_engine(cfg, mesh)
    data = eng.shard_data({"X": jnp.asarray(X), "y": jnp.asarray(y)})
    state = eng.init_state(jax.random.key(0), y=y)
    R = (staleness + 1) * steps
    # invoked through the unified plan surface (ISSUE 3 acceptance);
    # .telemetry is a uniform RunReport now, with the staleness story
    # in its .ssp section (ISSUE 7)
    from repro.obs import RunReport, TelemetrySpec
    plan = ExecutionPlan(executor="ssp", rounds=R, staleness=staleness,
                         telemetry=TelemetrySpec(kind="counters"))
    report = eng.execute(state, data, jax.random.key(1), plan).telemetry
    assert isinstance(report, RunReport)
    assert report.counters["rounds"] == R
    telem = report.ssp
    assert telem.max_staleness <= staleness
    assert telem.hist.sum() == R == telem.rounds
    # each window serves exactly one read at every staleness 0..s
    assert (telem.hist == steps).all()
    assert telem.flushes == steps
    assert (telem.clocks == R).all()


def test_ssp_rejects_non_divisible_rounds(mesh, rng):
    cfg, X, y = _lasso_problem(rng)
    with pytest.raises(ValueError, match="multiple"):
        lasso.fit(cfg, X, y, mesh,
                  plan=ExecutionPlan(executor="ssp", rounds=5,
                                     staleness=1))


# ---------------------------------------------------------------------------
# s >= 1: bounded error, not corruption
# ---------------------------------------------------------------------------

def test_lasso_converges_under_staleness(mesh):
    r = np.random.default_rng(3)
    X, y, _ = lasso.synthetic_correlated(r, n=120, J=80, corr=0.9,
                                         k_true=8)
    cfg = lasso.LassoConfig(num_features=80, lam=0.02, block_size=8,
                            num_candidates=32, rho=0.3, eta=1e-3)
    _, tr = lasso.fit(cfg, X, y, mesh,
                      plan=ExecutionPlan(executor="ssp", rounds=42,
                                         staleness=2, collect_every=1))
    vals = [v for _, v in tr]
    assert len(vals) == 42
    assert vals[-1] < vals[0] * 0.7             # real progress under s=2


def test_lda_ssp_conserves_counts_and_sync(mesh, rng):
    """Deferred s-sync must still leave s == colsums(B) and conserve the
    token count at every flush boundary (the run ends on one)."""
    cfg = lda.LDAConfig(vocab=30, num_topics=4, num_workers=1,
                        tokens_per_worker=200, docs_per_worker=5)
    words, docs, z0 = lda.synthetic_corpus(rng, cfg, true_topics=4)
    state, tr, _ = lda.fit(cfg, words, docs, z0, mesh,
                           plan=ExecutionPlan(executor="ssp", rounds=8,
                                              staleness=1, collect_every=4))
    n_tok = int((words >= 0).sum())
    assert float(jnp.sum(state["B"])) == n_tok
    assert float(jnp.sum(state["D"])) == n_tok
    assert bool(jnp.allclose(state["s"], jnp.sum(state["B"], axis=0)))
    assert tr[-1][1] > tr[0][1]                 # likelihood still climbs


# ---------------------------------------------------------------------------
# parameter-server plumbing (server split, cache gate, KV-store wiring)
# ---------------------------------------------------------------------------

def test_server_split_and_byte_accounting(mesh, rng):
    cfg, X, y = _lasso_problem(rng, n=40, J=20)
    eng = lasso.make_engine(cfg, mesh)
    state = eng.init_state(jax.random.key(0), y=y)
    # engine placement now goes through the KV store; the Δβ priority
    # history is the engine-owned scheduler carry, not a state leaf
    assert eng.kvstore is not None
    assert set(eng.kvstore.specs) == {"beta", "r"}
    assert eng.kvstore.total_bytes() == (20 + 40) * 4
    srv = ParameterServer.from_state(eng.mesh, state, eng._sspec(state))
    assert srv.shared_names == {"beta"}              # r is worker-local
    assert srv.shared_nbytes() == 20 * 4
    snap = srv.snapshot(state)
    assert set(snap) == {"beta"}
    merged = srv.merge(state, snap)
    _bit_identical(merged, state)


def test_stale_cache_gate():
    c = StaleCache(values={"x": jnp.zeros(3)}, clock=jnp.int32(4))
    assert int(c.staleness(6)) == 2
    assert bool(c.fresh_enough(6, 2)) and not bool(c.fresh_enough(7, 2))
    c2 = c.refresh({"x": jnp.ones(3)}, 7)
    assert int(c2.staleness(7)) == 0


def test_init_clocks_lockstep():
    clocks = init_clocks(4)
    assert clocks.shape == (4,) and int(clocks.sum()) == 0
