"""STRADS MF over sparse per-shard ratings: exactness of the push/pull CD
update (the paper's "free from parallelization error" claim) against a
plain dense masked CCD reference, every executor, the layout and its
ingest, convergence, the Netflix-like generator, the ALS baseline."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps import mf
from repro.checkpoint import restore_checkpoint
from repro.core import ExecutionPlan, single_device_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def mesh():
    return single_device_mesh()


@pytest.fixture(scope="module")
def problem():
    r = np.random.default_rng(0)
    A, mask = mf.synthetic_ratings(r, 60, 40, true_rank=6, density=0.5)
    return A, mask


def ccd_reference(A, mask, W, H, rounds: int, lam: float,
                  ranks_per_round: int = 1):
    """Plain dense masked CCD in float32 at "highest" precision: round t
    updates the round-robin rank block of cycle t // 2, H on even rounds
    (summed by column), W on odd ones (summed by row), each rank of a
    block from the same residual.  Returns (W, H, R) with R dense."""
    K = W.shape[1]
    with jax.default_matmul_precision("highest"):
        A, mask, W, H = (jnp.asarray(x, jnp.float32)
                         for x in (A, mask, W, H))
        R = (A - W @ H) * mask
        for t in range(rounds):
            ks = jnp.asarray([(t // 2 * ranks_per_round + i) % K
                              for i in range(ranks_per_round)])
            Wk, Hk = W[:, ks], H[ks]
            if t % 2 == 0:
                b = (mask.T @ (Wk * Wk)).T                  # (b, M)
                Hn = (Wk.T @ R + b * Hk) / (lam + b)
                R = R - (Wk @ (Hn - Hk)) * mask
                H = H.at[ks].set(Hn)
            else:
                b = mask @ (Hk * Hk).T                      # (N, b)
                Wn = (R @ Hk.T + Wk * b) / (lam + b)
                R = R - ((Wn - Wk) @ Hk) * mask
                W = W.at[:, ks].set(Wn)
    return np.asarray(W), np.asarray(H), np.asarray(R)


def _check_against_reference(eng, data, st0, final, A, mask, rounds,
                             ranks_per_round=1, tol=2e-5):
    cfg = eng.app.cfg
    W, H, R = ccd_reference(A, mask, st0["W"], st0["H"], rounds, cfg.lam,
                            ranks_per_round)
    Rd, held = eng.app.to_dense(data, final["R"])
    assert np.array_equal(held, mask)
    for got, want in ((final["W"], W), (final["H"], H), (Rd, R)):
        np.testing.assert_allclose(np.asarray(got), want, rtol=tol,
                                   atol=tol)


def _bit_identical(a, b):
    for k in a:
        assert np.array_equal(np.asarray(a[k]), np.asarray(b[k])), k


def _setup(mesh, problem, cfg=None):
    A, mask = problem
    cfg = cfg or mf.MFConfig(num_rows=60, num_cols=40, rank=6, lam=0.05)
    eng = mf.make_engine(cfg, mesh)
    data = eng.shard_data(eng.app.layout(A, mask))
    st = eng.init_state(jax.random.key(1), data=data)
    st0 = {k: np.array(v) for k, v in st.items()}
    return eng, data, st, st0


def test_h_update_matches_closed_form(mesh, problem):
    """One H-phase round must equal eq. (3) exactly — zero parallelization
    error (claim C4)."""
    A, mask = problem
    eng, data, st, _ = _setup(mesh, problem)
    out = eng.run_round(st, data, jax.random.key(2), t=0)
    W, H = np.asarray(st["W"]), np.asarray(st["H"])
    R, _ = eng.app.to_dense(data, st["R"])
    np.testing.assert_allclose(R, (A - W @ H) * mask, rtol=1e-5,
                               atol=1e-5)
    k = 0
    num = np.einsum("i,ij->j", W[:, k], R * mask) \
        + np.einsum("ij,i->j", mask, W[:, k] ** 2) * H[k]
    den = 0.05 + np.einsum("ij,i->j", mask, W[:, k] ** 2)
    np.testing.assert_allclose(np.asarray(out.state["H"][k]), num / den,
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("executor", ["scan", "loop", "ssp", "resume"])
def test_sparse_layout_matches_dense_reference(executor, mesh, problem,
                                               tmp_path):
    """The sparse layout through ``execute`` agrees with the plain dense
    masked CCD reference on every executor; ssp s=0 is bit-identical to
    scan, and a run resumed from its mid-run checkpoint to the
    uninterrupted one."""
    A, mask = problem
    cfg = mf.MFConfig(num_rows=60, num_cols=40, rank=6, lam=0.05,
                      ranks_per_round=2)
    eng, data, st, st0 = _setup(mesh, problem, cfg)
    rounds = 8
    if executor == "resume":
        plan = ExecutionPlan(executor="scan", rounds=rounds,
                             checkpoint_every=4)
        rep = eng.execute(st, data, jax.random.key(3), plan,
                          ckpt_dir=str(tmp_path))
        restored = restore_checkpoint(
            str(tmp_path), 4, {"state": jax.tree.map(jnp.copy, rep.state),
                               "carry": rep.carry})
        final = eng.execute(restored["state"], data, jax.random.key(3),
                            plan, carry=restored["carry"],
                            ckpt_dir=str(tmp_path / "resumed")).state
        _bit_identical(final, rep.state)
    else:
        plan = ExecutionPlan(executor=executor, rounds=rounds)
        final = eng.execute(st, data, jax.random.key(3), plan).state
        if executor == "ssp":
            scan = eng.execute(
                eng.place_state({k: jnp.asarray(v) for k, v in st0.items()}),
                data, jax.random.key(3),
                ExecutionPlan(executor="scan", rounds=rounds)).state
            _bit_identical(final, scan)
    _check_against_reference(eng, data, st0, final, A, mask, rounds, 2)


@pytest.fixture
def kernel_sweeps(monkeypatch):
    """The sweeps' Pallas kernels in interpret mode, in place of XLA's
    gather and scatter that the CPU runs."""
    from functools import partial
    from repro.kernels import entry_sweep
    monkeypatch.setattr(entry_sweep, "take",
                        partial(entry_sweep.take_kernel, interpret=True))
    monkeypatch.setattr(entry_sweep, "segment_sums", partial(
        entry_sweep.segment_sums_kernel, interpret=True))


@pytest.mark.parametrize("case", ["ranks_1", "ranks_2", "after_ingest"])
def test_kernel_sweeps_agree_with_the_xla_path(case, mesh, problem,
                                               request):
    """Two H/W cycles through the sweeps' kernels (interpret mode) agree
    with the same cycles through XLA's gathers and segment sums: the
    reads are exact, the sums float32 in another order."""
    A, mask = problem
    cfg = mf.MFConfig(num_rows=60, num_cols=40, rank=6, lam=0.05,
                      ranks_per_round=2 if case == "ranks_2" else 1)
    eng, data, st, st0 = _setup(mesh, problem, cfg)
    if case == "after_ingest":
        delta = {"data": {"col": np.array([[0, 7, 39], [3, -1, -1]],
                                          np.int32),
                          "val": np.array([[4., 5., 2.], [1., 0., 0.]],
                                          np.float32)}}
        data, st = eng.app.ingest(data, st, np.array([11, 52]), delta)
        data = eng.shard_data(data)
        st0 = {k: np.array(v) for k, v in st.items()}
    plan = ExecutionPlan(executor="scan", rounds=4)
    want = eng.execute(st, data, jax.random.key(3), plan).state
    request.getfixturevalue("kernel_sweeps")
    eng = mf.make_engine(cfg, mesh)
    got = eng.execute(eng.place_state({k: jnp.asarray(v)
                                       for k, v in st0.items()}),
                      data, jax.random.key(3), plan).state
    for k in ("W", "H", "R"):
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want[k]),
                                   rtol=1e-5, atol=1e-6)


def test_four_devices_through_the_h_phase_psum():
    """Four row shards on four forced host devices: the H-phase partial
    sums meet in the psum, and the result agrees with the dense
    reference and with one shard."""
    code = f"""
        import sys
        sys.path.insert(0, {os.path.join(ROOT, "tests")!r})
        import numpy as np, jax
        from repro.apps import mf
        from repro.core import ExecutionPlan, single_device_mesh, worker_mesh
        from test_mf import ccd_reference
        assert len(jax.devices()) == 4
        r = np.random.default_rng(5)
        A, mask = mf.synthetic_ratings(r, 64, 40, true_rank=6, density=0.4)
        mask[3] = 0                          # an empty row on worker 0
        A = A * mask
        cfg = mf.MFConfig(num_rows=64, num_cols=40, rank=6, lam=0.05)
        out = {{}}
        for name, mesh in (("four", worker_mesh(4)),
                           ("one", single_device_mesh())):
            eng = mf.make_engine(cfg, mesh)
            data = eng.shard_data(eng.app.layout(A, mask))
            st = eng.init_state(jax.random.key(1), data=data)
            W0, H0 = np.asarray(st["W"]), np.asarray(st["H"])
            fin = eng.execute(st, data, jax.random.key(2),
                              ExecutionPlan(executor="scan", rounds=10)).state
            Rd, held = eng.app.to_dense(data, fin["R"])
            out[name] = (np.asarray(fin["W"]), np.asarray(fin["H"]), Rd)
            assert np.array_equal(held, mask)
        W, H, R = ccd_reference(A, mask, W0, H0, 10, 0.05)
        for got in out.values():
            for g, w in zip(got, (W, H, R)):
                np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-5)
        print("OK")
    """
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=540)
    assert p.returncode == 0, p.stderr[-4000:]
    assert "OK" in p.stdout


def test_residual_consistency(mesh, problem):
    """After several rounds, R must still equal (A − WH)·mask — the
    automatic sync keeps the maintained residual truthful."""
    A, mask = problem
    cfg = mf.MFConfig(num_rows=60, num_cols=40, rank=6, lam=0.05)
    state, _ = mf.fit(cfg, A, mask, mesh, num_rounds=20)
    W, H = np.asarray(state["W"]), np.asarray(state["H"])
    app = mf.StradsMF(cfg)
    R, _ = app.to_dense(app.layout(A, mask), state["R"])
    np.testing.assert_allclose(R, (A - W @ H) * mask, atol=1e-3)


def test_objective_decreases(mesh, problem):
    A, mask = problem
    cfg = mf.MFConfig(num_rows=60, num_cols=40, rank=6, lam=0.05)
    _, trace = mf.fit(cfg, A, mask, mesh, num_rounds=60, trace_every=10)
    vals = [v for _, v in trace]
    assert vals[-1] < vals[0] * 0.2           # big drop
    for a, b in zip(vals, vals[1:]):
        assert b <= a + 1e-3                  # monotone (exact CD)


def test_recovers_low_rank_signal(mesh):
    """With rank ≥ true rank, the masked fit error approaches the noise
    floor."""
    r = np.random.default_rng(3)
    A, mask = mf.synthetic_ratings(r, 80, 50, true_rank=4, density=0.6,
                                   noise=0.01)
    cfg = mf.MFConfig(num_rows=80, num_cols=50, rank=8, lam=0.01)
    state, _ = mf.fit(cfg, A, mask, mesh, num_rounds=200)
    R = np.asarray(state["R"])
    rmse = np.sqrt((R ** 2).sum() / mask.sum())
    assert rmse < 0.1


def test_als_baseline_converges(problem):
    A, mask = problem
    (_, _), trace = mf.als_fit(jnp.asarray(A), jnp.asarray(mask), 6, 0.05,
                               8, jax.random.key(0))
    vals = [v for _, v in trace]
    assert vals[-1] < vals[0] * 0.2
    for a, b in zip(vals, vals[1:]):
        assert b <= a + 1e-3


def test_strads_handles_larger_rank_than_als_budget(mesh):
    """Proxy for the paper's model-size claim: CD cost scales linearly in
    rank while ALS scales cubically (K×K solves).  We check the CD path
    runs rank 64 on a small matrix with a *decreasing* objective."""
    r = np.random.default_rng(4)
    A, mask = mf.synthetic_ratings(r, 60, 40, true_rank=6, density=0.5)
    cfg = mf.MFConfig(num_rows=60, num_cols=40, rank=64, lam=0.1)
    _, trace = mf.fit(cfg, A, mask, mesh, num_rounds=128, trace_every=127)
    assert trace[-1][1] < trace[0][1]


# ---------------------------------------------------------------------------
# the layout
# ---------------------------------------------------------------------------

def test_layout_shards_rows_and_pads_with_the_sentinel():
    rows = np.array([5, 0, 0, 2, 7, 6])
    cols = np.array([1, 3, 0, 2, 0, 4])
    vals = np.arange(6, dtype=np.float32) + 1
    d = mf.layout(rows, cols, vals, 8, num_workers=2, capacity=4)
    assert d["row"].shape == d["col"].shape == d["val"].shape == (8,)
    # worker 0 holds rows 0-3, sorted by (row, col); worker 1 rows 4-7
    # as local rows 0-3; padding rows are the sentinel 4
    np.testing.assert_array_equal(d["row"], [0, 0, 2, 4, 1, 2, 3, 4])
    np.testing.assert_array_equal(d["col"], [0, 3, 2, 0, 1, 4, 0, 0])
    np.testing.assert_array_equal(d["val"], [3, 2, 4, 0, 1, 6, 5, 0])
    np.testing.assert_array_equal(
        d["valid"], [1, 0, 1, 0, 0, 1, 1, 1])    # rows 1, 3, 4 are empty
    app = mf.StradsMF(mf.MFConfig(num_rows=8, num_cols=5, rank=2), 2)
    A, mask = app.to_dense(d)
    assert mask.sum() == 6 and A[5, 1] == 1 and A[0, 3] == 2
    assert mf.layout(rows, cols, vals, 8, num_workers=2)["row"].size \
        == 2 * mf.ENTRY_ALIGN
    with pytest.raises(ValueError, match="capacity"):
        mf.layout(rows, cols, vals, 8, num_workers=2, capacity=2)
    with pytest.raises(ValueError, match="do not split"):
        mf.layout(rows, cols, vals, 9, num_workers=2)


def test_padding_entries_and_empty_rows_are_inert(mesh):
    """More padding, and rows with no rating, change nothing: the same
    ratings in a larger capacity train to the same factors, and an empty
    row's W stays 0."""
    r = np.random.default_rng(2)
    A, mask = mf.synthetic_ratings(r, 24, 16, true_rank=3, density=0.5)
    mask[[4, 9]] = 0
    A = A * mask
    cfg = mf.MFConfig(num_rows=24, num_cols=16, rank=3, lam=0.05)
    outs = []
    for capacity in (None, 1024):
        eng = mf.make_engine(cfg, mesh)
        data = eng.shard_data(eng.app.layout(A, mask, capacity=capacity))
        st = eng.init_state(jax.random.key(0), data=data)
        fin = eng.execute(st, data, jax.random.key(1),
                          ExecutionPlan(executor="scan", rounds=6)).state
        outs.append(fin)
        R = np.asarray(fin["R"])
        assert (R[np.asarray(data["row"]) == 24] == 0).all()
    assert outs[1]["R"].shape == (1024,)
    for k in ("W", "H"):
        np.testing.assert_allclose(np.asarray(outs[0][k]),
                                   np.asarray(outs[1][k]), rtol=1e-6,
                                   atol=1e-6)
    np.testing.assert_array_equal(np.asarray(outs[0]["W"])[[4, 9]], 0.0)


def test_ingest_rewrites_rows_and_raises_past_capacity(mesh):
    """Replace-kind ingest invalidates a row's old entries, writes the new
    ones into free padding slots with true residuals, and raises on the
    host when a shard has too few free entries."""
    r = np.random.default_rng(6)
    A, mask = mf.synthetic_ratings(r, 8, 6, true_rank=2, density=0.5)
    cfg = mf.MFConfig(num_rows=8, num_cols=6, rank=2)
    eng = mf.make_engine(cfg, mesh)
    data = eng.shard_data(eng.app.layout(A, mask))
    st = eng.init_state(jax.random.key(0), data=data)
    C = int(data["row"].shape[0])
    delta = {"data": {"col": np.array([[0, 2, -1], [5, -1, -1]], np.int32),
                      "val": np.array([[4., 5., 0.], [1., 0., 0.]],
                                      np.float32)}}
    data2, st2 = eng.app.ingest(data, st, np.array([1, 3]), delta)
    A2, mask2 = eng.app.to_dense(data2)
    want_A, want_m = A * mask, mask.copy()
    want_A[[1, 3]], want_m[[1, 3]] = 0, 0
    want_A[1, [0, 2]], want_A[3, 5] = [4, 5], 1
    want_m[1, [0, 2]], want_m[3, 5] = 1, 1
    np.testing.assert_array_equal(A2, want_A)
    np.testing.assert_array_equal(mask2, want_m)
    W, H = np.asarray(st2["W"]), np.asarray(st2["H"])
    R2, _ = eng.app.to_dense(data2, st2["R"])
    np.testing.assert_allclose(R2, (want_A - W @ H) * want_m, atol=1e-5)
    full = {"data": {"col": np.tile(np.arange(6, dtype=np.int32),
                                    (8, 1)),
                     "val": np.ones((8, 6), np.float32)}}
    if C < 48:
        with pytest.raises(ValueError, match="free"):
            eng.app.ingest(data, st, np.arange(8), full)


def test_ingest_keeps_an_empty_delta_row_invalid(mesh):
    r = np.random.default_rng(7)
    A, mask = mf.synthetic_ratings(r, 8, 6, true_rank=2, density=0.5)
    cfg = mf.MFConfig(num_rows=8, num_cols=6, rank=2)
    eng = mf.make_engine(cfg, mesh)
    data = eng.shard_data(eng.app.layout(A, mask))
    delta = {"data": {"col": np.full((1, 6), -1, np.int32),
                      "val": np.zeros((1, 6), np.float32)}}
    data2, _ = eng.app.ingest(data, None, np.array([2]), delta)
    assert not np.asarray(data2["valid"])[2]
    assert eng.app.to_dense(data2)[1][2].sum() == 0


# ---------------------------------------------------------------------------
# the Netflix-like generator
# ---------------------------------------------------------------------------

def test_netflix_like_ratings_is_seeded_and_without_repeats():
    a = mf.netflix_like_ratings(np.random.default_rng(3), 200, 300)
    b = mf.netflix_like_ratings(np.random.default_rng(3), 200, 300)
    c = mf.netflix_like_ratings(np.random.default_rng(4), 200, 300)
    for x, y in zip(a[:3], b[:3]):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a[2][:100], c[2][:100])
    rows, cols, vals, info = a
    keys = rows.astype(np.int64) * 300 + cols
    assert (np.diff(keys) > 0).all()         # sorted, no repeated pair
    assert np.bincount(rows, minlength=200).min() >= 1
    assert info["kept"] == rows.size <= info["drawn"]
    assert set(np.unique(vals)) <= {1.0, 2.0, 3.0, 4.0, 5.0}


def test_netflix_like_ratings_moments():
    """At a mid size the moments land in a band around Netflix's: about
    209 ratings a user (median 96), a mean rating of about 3.6, a movie
    median about a tenth of the movie mean, the top movie a few tenths of
    a percent of all ratings."""
    N, M = 6000, 17770
    rows, cols, vals, info = mf.netflix_like_ratings(
        np.random.default_rng(11), N, M)
    deg = np.bincount(rows, minlength=N)
    assert 185 < deg.mean() < 215 and 85 < np.median(deg) < 105
    assert 3.5 < vals.mean() < 3.7
    per_movie = np.bincount(cols, minlength=M)
    assert 0.05 < np.median(per_movie) / per_movie.mean() < 0.25
    assert 0.001 < per_movie.max() / rows.size < 0.0025
    assert info["kept"] > 0.95 * deg.size * 190
