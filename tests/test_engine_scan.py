"""The scanned multi-round executors (``execute`` with executor "scan"
and "pipelined").

Contract under test (PR 1 acceptance):
  * ``"scan"`` is bit-identical to the host loop ``"loop"`` on all three
    paper apps — same PRNG stream, same op order, one XLA program
    instead of R dispatches.
  * ``"pipelined"`` (schedule prefetch, one-round-stale schedules —
    the paper's §pipelining) still monotonically decreases the Lasso
    objective on a correlated design.
  * phase-period handling: apps whose round structure cycles (MF's H/W
    alternation, LDA's U-round rotation) scan a full cycle per step, and
    a non-divisible round count falls back to the host loop for the tail.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps import lasso, lda, mf
from repro.core import ExecutionPlan, single_device_mesh


@pytest.fixture(scope="module")
def mesh():
    return single_device_mesh()


def _bit_identical(a_state, b_state):
    for k in a_state:
        a, b = np.asarray(a_state[k]), np.asarray(b_state[k])
        assert (a == b).all(), (k, np.max(np.abs(a - b)))


# ---------------------------------------------------------------------------
# depth 0: bit-identical to the host loop
# ---------------------------------------------------------------------------

def test_lasso_scan_matches_host_loop(mesh, rng):
    X, y, _ = lasso.synthetic_correlated(rng, n=60, J=30, k_true=4)
    cfg = lasso.LassoConfig(num_features=30, lam=0.02, block_size=4,
                            num_candidates=12, rho=0.3)
    s_loop, _ = lasso.fit(cfg, X, y, mesh, num_rounds=20)
    s_scan, _ = lasso.fit(cfg, X, y, mesh,
                          plan=ExecutionPlan(executor="scan", rounds=20))
    _bit_identical(s_loop, s_scan)


def test_lasso_scan_trace_matches_host_trace(mesh, rng):
    X, y, _ = lasso.synthetic_correlated(rng, n=60, J=30, k_true=4)
    cfg = lasso.LassoConfig(num_features=30, lam=0.02, block_size=4,
                            num_candidates=12, rho=0.3)
    _, tr_loop = lasso.fit(cfg, X, y, mesh, num_rounds=10, trace_every=2)
    _, tr_scan = lasso.fit(cfg, X, y, mesh,
                           plan=ExecutionPlan(executor="scan", rounds=10,
                                              collect_every=2))
    assert [t for t, _ in tr_loop] == [t for t, _ in tr_scan]
    for (_, a), (_, b) in zip(tr_loop, tr_scan):
        assert a == pytest.approx(b, rel=1e-6)


def test_mf_scan_matches_host_loop_including_tail(mesh, rng):
    """9 rounds with phase_period=2: 4 scanned H/W cycles + 1 host-loop
    tail round must still match the pure host loop exactly."""
    A, mask = mf.synthetic_ratings(rng, 40, 30, true_rank=4, density=0.5)
    cfg = mf.MFConfig(num_rows=40, num_cols=30, rank=4, lam=0.05)
    s_loop, _ = mf.fit(cfg, A, mask, mesh, num_rounds=9)
    s_scan, _ = mf.fit(cfg, A, mask, mesh,
                       plan=ExecutionPlan(executor="scan", rounds=9))
    _bit_identical(s_loop, s_scan)


def test_lda_scan_matches_host_loop(mesh, rng):
    cfg = lda.LDAConfig(vocab=30, num_topics=4, num_workers=1,
                        tokens_per_worker=200, docs_per_worker=5)
    words, docs, z0 = lda.synthetic_corpus(rng, cfg, true_topics=4)
    s_loop, _, _ = lda.fit(cfg, words, docs, z0, mesh, num_rounds=6)
    s_scan, _, _ = lda.fit(cfg, words, docs, z0, mesh,
                           plan=ExecutionPlan(executor="scan", rounds=6))
    _bit_identical(s_loop, s_scan)


# ---------------------------------------------------------------------------
# depth 1: pipelined (one-round-stale schedules)
# ---------------------------------------------------------------------------

def test_pipelined_lasso_objective_monotone_on_correlated_design(mesh):
    """The STRADS stale-schedule guarantee: with the schedule computed one
    round behind (prefetched during the previous round's push/pull), the
    ρ-filtered dynamic schedule still descends every round on a strongly
    correlated design."""
    r = np.random.default_rng(3)
    X, y, _ = lasso.synthetic_correlated(r, n=120, J=80, corr=0.9,
                                         k_true=8)
    cfg = lasso.LassoConfig(num_features=80, lam=0.02, block_size=8,
                            num_candidates=32, rho=0.3, eta=1e-3)
    _, tr = lasso.fit(cfg, X, y, mesh,
                      plan=ExecutionPlan(executor="pipelined", rounds=40,
                                         collect_every=1))
    vals = [v for _, v in tr]
    assert len(vals) == 40
    for a, b in zip(vals, vals[1:]):
        assert b <= a + 1e-4                    # monotone descent
    assert vals[-1] < vals[0] * 0.7             # and real progress


def test_pipelined_lasso_matches_depth0_rng_stream(mesh, rng):
    """Depth 1 uses the same per-round schedule PRNG keys as depth 0 —
    only the state it reads is staler.  At round 0 there is no staleness
    yet, so the first-round schedules must coincide exactly."""
    X, y, _ = lasso.synthetic_correlated(rng, n=40, J=20, k_true=3)
    cfg = lasso.LassoConfig(num_features=20, lam=0.02, block_size=4,
                            num_candidates=8, rho=0.3)
    s0, _ = lasso.fit(cfg, X, y, mesh,
                      plan=ExecutionPlan(executor="scan", rounds=1))
    s1, _ = lasso.fit(cfg, X, y, mesh,
                      plan=ExecutionPlan(executor="pipelined", rounds=1))
    _bit_identical(s0, s1)


def test_pipelined_lda_conserves_counts(mesh, rng):
    """Count conservation is a per-round invariant of the Gibbs kernel and
    must survive pipelining (the schedule carries no counts)."""
    cfg = lda.LDAConfig(vocab=30, num_topics=4, num_workers=1,
                        tokens_per_worker=200, docs_per_worker=5)
    words, docs, z0 = lda.synthetic_corpus(rng, cfg, true_topics=4)
    state, tr, _ = lda.fit(cfg, words, docs, z0, mesh,
                           plan=ExecutionPlan(executor="pipelined",
                                              rounds=8, collect_every=4))
    n_tok = int((words >= 0).sum())
    assert float(jnp.sum(state["B"])) == n_tok
    assert float(jnp.sum(state["D"])) == n_tok
    assert bool(jnp.allclose(state["s"], jnp.sum(state["B"], axis=0)))
    assert tr[-1][1] > tr[0][1]                 # likelihood still climbs


def test_pipelined_mf_objective_decreases(mesh, rng):
    A, mask = mf.synthetic_ratings(rng, 40, 30, true_rank=4, density=0.5)
    cfg = mf.MFConfig(num_rows=40, num_cols=30, rank=4, lam=0.05)
    _, tr = mf.fit(cfg, A, mask, mesh,
                   plan=ExecutionPlan(executor="pipelined", rounds=20,
                                      collect_every=1))
    vals = [v for _, v in tr]
    assert vals[-1] < vals[0] * 0.6


# ---------------------------------------------------------------------------
# executor plumbing
# ---------------------------------------------------------------------------

def test_pipelined_rejects_non_divisible_rounds(mesh, rng):
    A, mask = mf.synthetic_ratings(rng, 20, 15, true_rank=3, density=0.5)
    cfg = mf.MFConfig(num_rows=20, num_cols=15, rank=3, lam=0.05)
    with pytest.raises(ValueError, match="divisible"):
        mf.fit(cfg, A, mask, mesh,
               plan=ExecutionPlan(executor="pipelined", rounds=7))


def test_run_scanned_without_collect_returns_state_only(mesh, rng):
    X, y, _ = lasso.synthetic_correlated(rng, n=40, J=20, k_true=3)
    cfg = lasso.LassoConfig(num_features=20, lam=0.02, block_size=4,
                            num_candidates=8, rho=0.3)
    eng = lasso.make_engine(cfg, mesh)
    data = eng.shard_data({"X": jnp.asarray(X), "y": jnp.asarray(y)})
    state = eng.app.init_state(jax.random.key(0), y=y)
    rep = eng.execute(state, data, jax.random.key(0),
                      ExecutionPlan(executor="scan", rounds=4))
    assert rep.trace is None
    assert isinstance(rep.state, dict) and set(rep.state) == {"beta", "r"}


def test_run_scanned_collect_trace_has_one_entry_per_round(mesh, rng):
    X, y, _ = lasso.synthetic_correlated(rng, n=40, J=20, k_true=3)
    cfg = lasso.LassoConfig(num_features=20, lam=0.02, block_size=4,
                            num_candidates=8, rho=0.3)
    eng = lasso.make_engine(cfg, mesh)
    data = eng.shard_data({"X": jnp.asarray(X), "y": jnp.asarray(y)})
    state = eng.app.init_state(jax.random.key(0), y=y)
    rep = eng.execute(state, data, jax.random.key(0),
                      ExecutionPlan(executor="scan", rounds=6,
                                    donate=False),
                      collect=eng.app.objective_collect())
    assert np.asarray(rep.trace).shape == (6,)


def test_scanned_fn_is_aot_lowerable(mesh, rng):
    """launch/dryrun.py --engine relies on .lower().compile() of the
    scanned program; keep that path working."""
    X, y, _ = lasso.synthetic_correlated(rng, n=40, J=20, k_true=3)
    cfg = lasso.LassoConfig(num_features=20, lam=0.02, block_size=4,
                            num_candidates=8, rho=0.3)
    eng = lasso.make_engine(cfg, mesh)
    data = eng.shard_data({"X": jnp.asarray(X), "y": jnp.asarray(y)})
    state = eng.app.init_state(jax.random.key(0), y=y)
    fn = eng.scanned_fn(4, pipeline_depth=1)
    compiled = fn.lower(state, data, jax.random.key(1), jnp.int32(0),
                        eng.init_sched_carry()).compile()
    assert compiled.cost_analysis() is not None
