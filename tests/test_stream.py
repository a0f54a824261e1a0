"""The streaming data-ingest subsystem (repro/stream).

Contract under test (ISSUE 9 acceptance):
  * ``StreamSpec`` — the house spec rules: kind validation, per-kind
    unused-field rejection, exact JSON round-trip, ``default_for``.
  * empty-source bit-exactness: ``execute(..., stream=spec,
    source=EmptySource())`` ≡ the unstreamed ``execute()`` leaf by leaf,
    on all four executors × all three apps.
  * the extend ring: appends land in padding slots first (the
    ``ingest_specs()["valid"]`` fill), then wrap around and overwrite
    the oldest rows; a delta larger than the ring keeps only its tail
    and counts the rest dropped; padding rows are exactly inert until a
    delta lands (a capacity-padded run matches the unpadded one).
  * batching invariance: trajectories depend only on the
    (data, delta-schedule) pair — splitting one delta into several at
    the same boundary changes nothing (hypothesis property).
  * the serve loop: ``serve_while_training(..., stream=, source=)``
    trains bit-identically to the engine-streamed run and reports the
    cursor payload.
  * ``SyntheticLMSource`` and ``repro.data.synthetic_batches`` share
    one batch-derivation path.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.apps import lasso, lda, mf
from repro.core import ExecutionPlan, StradsAppBase, single_device_mesh
from repro.data.pipeline import SyntheticLMConfig, make_batch
from repro.obs import TelemetrySpec
from repro.serve import ServeSpec, serve_while_training
from repro.stream import (EmptySource, Ingestor, LassoDriftSource,
                          LDADriftSource, MFDriftSource, ScheduledSource,
                          StreamSpec, SyntheticLMSource, replay_data)

EXECUTORS = ("loop", "scan", "pipelined", "ssp")


@pytest.fixture(scope="module")
def mesh():
    return single_device_mesh()


def _bit_identical(a_state, b_state):
    assert set(a_state) == set(b_state)
    for k in a_state:
        a, b = np.asarray(a_state[k]), np.asarray(b_state[k])
        assert (a == b).all(), (k, np.max(np.abs(a - b)))


def _plan(executor, rounds, **kw):
    if executor == "ssp":
        kw.setdefault("staleness", 1)
    return ExecutionPlan(executor=executor, rounds=rounds, **kw)


def _lasso_setup(mesh, seed=0, n=48, J=24):
    r = np.random.default_rng(seed)
    X, y, _ = lasso.synthetic_correlated(r, n=n, J=J, k_true=4)
    cfg = lasso.LassoConfig(num_features=J, lam=0.05, block_size=4,
                            num_candidates=8, rho=0.5)
    eng = lasso.make_engine(cfg, mesh)
    data = eng.shard_data({"X": jnp.asarray(X), "y": jnp.asarray(y)})
    init = lambda: eng.init_state(jax.random.key(0), y=y)
    return eng, data, init, (X, y)


def _lda_setup(mesh, seed=0):
    cfg = lda.LDAConfig(vocab=20, num_topics=4, num_workers=1,
                        tokens_per_worker=24, docs_per_worker=4)
    r = np.random.default_rng(seed)
    words, docs, z0 = lda.synthetic_corpus(r, cfg, true_topics=4)
    eng = lda.make_engine(cfg, mesh)
    data = eng.shard_data({"words": jnp.asarray(words),
                           "docs": jnp.asarray(docs)})
    init = lambda: eng.init_state(jax.random.key(0), words=words,
                                  docs=docs, z0=z0)
    return eng, data, init, cfg


def _mf_setup(mesh, seed=0, N=12, M=10):
    r = np.random.default_rng(seed)
    A, mask = mf.synthetic_ratings(r, N, M, true_rank=2)
    cfg = mf.MFConfig(num_rows=N, num_cols=M, rank=3)
    eng = mf.make_engine(cfg, mesh)
    data = eng.shard_data(eng.app.layout(A, mask))
    init = lambda: eng.init_state(jax.random.key(0), data=data)
    return eng, data, init, (A, mask)


# ---------------------------------------------------------------------------
# StreamSpec: the house spec rules
# ---------------------------------------------------------------------------

def test_spec_rejects_bad_kind():
    with pytest.raises(ValueError, match="stream kind"):
        StreamSpec(kind="append")
    with pytest.raises(ValueError, match="stream kind"):
        StreamSpec.default_for("append")


def test_spec_rejects_unused_fields_per_kind():
    # capacity is an extend-only knob
    with pytest.raises(ValueError, match="does not apply"):
        StreamSpec(kind="replace", capacity=16)
    StreamSpec(kind="extend", ingest_every=2, capacity=16)
    StreamSpec(kind="replace", ingest_every=2)


def test_spec_validates_field_types():
    with pytest.raises(ValueError, match="ingest_every"):
        StreamSpec(kind="replace", ingest_every=0)
    with pytest.raises(ValueError, match="ingest_every"):
        StreamSpec(kind="replace", ingest_every=True)
    with pytest.raises(ValueError, match="capacity"):
        StreamSpec(kind="extend", capacity=-1)
    with pytest.raises(ValueError, match="capacity"):
        StreamSpec(kind="extend", capacity=True)


def test_spec_json_roundtrip_exact():
    for s in (StreamSpec(kind="replace", ingest_every=4),
              StreamSpec(kind="extend", ingest_every=2, capacity=64),
              StreamSpec.default_for("replace"),
              StreamSpec.default_for("extend")):
        assert StreamSpec.from_json(s.to_json()) == s
        assert StreamSpec.from_json(json.dumps(s.to_json())) == s


def test_spec_from_json_rejects_unknown_keys():
    with pytest.raises(ValueError, match="unknown StreamSpec field"):
        StreamSpec.from_json({"kind": "extend", "ring": 8})
    with pytest.raises(TypeError, match="dict or JSON"):
        StreamSpec.from_json(["extend"])


def test_spec_default_for_overrides():
    s = StreamSpec.default_for("extend", capacity=32)
    assert s.capacity == 32 and s.ingest_every == 1


def test_stream_spec_on_plan_json_is_rejected():
    # streaming is deliberately NOT an ExecutionPlan field: a plan
    # decides how to *train*; the StreamSpec rides the entry points
    # (execute/serve_while_training/CLIs) beside its DataSource.
    with pytest.raises(ValueError, match="unknown"):
        ExecutionPlan.from_json(
            {"executor": "ssp", "rounds": 6, "staleness": 1,
             "stream": {"kind": "extend"}})


# ---------------------------------------------------------------------------
# empty-source bit-exactness on every executor × every app
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("executor", EXECUTORS)
@pytest.mark.parametrize("app", ("lasso", "lda", "mf"))
def test_empty_source_bit_identical_to_unstreamed(executor, app, mesh):
    if app == "lasso":
        eng, data, init, _ = _lasso_setup(mesh)
        spec = StreamSpec(kind="replace", ingest_every=4)
    elif app == "lda":
        eng, data, init, _ = _lda_setup(mesh)
        spec = StreamSpec(kind="extend", ingest_every=4)
    else:
        eng, data, init, _ = _mf_setup(mesh)
        spec = StreamSpec(kind="extend", ingest_every=4)
    plan = _plan(executor, 8)
    ref = eng.execute(init(), data, jax.random.key(1), plan)
    rep = eng.execute(init(), data, jax.random.key(1), plan,
                      stream=spec, source=EmptySource())
    _bit_identical(ref.state, rep.state)
    assert rep.stream is not None
    assert int(rep.stream["rows_in"]) == 0


def test_drift_source_changes_the_trajectory(mesh):
    # guard against a silently-ignored source: real deltas must move
    # the trained state
    eng, data, init, _ = _lasso_setup(mesh)
    spec = StreamSpec(kind="replace", ingest_every=2)
    plan = _plan("scan", 8)
    ref = eng.execute(init(), data, jax.random.key(1), plan)
    rep = eng.execute(init(), data, jax.random.key(1), plan, stream=spec,
                      source=LassoDriftSource(num_rows=48,
                                              num_features=24,
                                              rows_per_ingest=8, seed=3))
    assert int(rep.stream["rows_in"]) == 8 * 3      # t = 2, 4, 6
    assert not (np.asarray(rep.state["beta"])
                == np.asarray(ref.state["beta"])).all()


# ---------------------------------------------------------------------------
# the extend ring: fill, wraparound, oversize deltas, inert padding
# ---------------------------------------------------------------------------

def _row_delta(vals, M):
    """An MF delta of rows rated in every column, each with the constant
    of ``vals``."""
    k = len(vals)
    return {"data": {
        "col": np.tile(np.arange(M, dtype=np.int32), (k, 1)),
        "val": np.tile(np.asarray(vals, np.float32)[:, None], (1, M))}}


def test_extend_ring_fills_padding_then_wraps(mesh):
    N, M, FILL = 8, 6, 5
    r = np.random.default_rng(0)
    A = np.concatenate([r.normal(size=(FILL, M)).astype(np.float32),
                        np.zeros((N - FILL, M), np.float32)])
    mask = np.concatenate([np.ones((FILL, M), np.float32),
                           np.zeros((N - FILL, M), np.float32)])
    eng = mf.make_engine(mf.MFConfig(num_rows=N, num_cols=M, rank=2),
                         mesh)
    data = eng.shard_data(eng.app.layout(A, mask))
    dense = lambda data: eng.app.to_dense(data)[0]
    src = ScheduledSource({0: _row_delta([100, 101], M),
                           1: _row_delta([102, 103, 104], M),
                           2: _row_delta(list(range(200, 210)), M)})
    ing = Ingestor(StreamSpec(kind="extend", ingest_every=1),
                   src).bind(eng, data)
    assert ing.capacity == N and ing.fill0 == FILL

    # boundary 0: two rows land in the padding slots 5, 6
    _, data = ing.step(eng, None, data, 0)
    np.testing.assert_array_equal(dense(data)[5], 100.0)
    np.testing.assert_array_equal(dense(data)[6], 101.0)
    assert (ing.cursor, ing.rows_in, ing.rows_dropped) == (2, 2, 0)

    # boundary 1: slot 7, then wrap to the oldest rows 0, 1
    _, data = ing.step(eng, None, data, 1)
    got = dense(data)[:, 0]
    np.testing.assert_array_equal(got[[7, 0, 1]], [102, 103, 104])
    assert (ing.cursor, ing.rows_in, ing.rows_dropped) == (5, 5, 0)

    # boundary 2: a delta larger than the whole ring keeps only its last
    # 8 rows (the earlier 2 would be overwritten before any round saw
    # them) — slot of sliced row i is (fill0 + cursor + dropped + i) % N
    _, data = ing.step(eng, None, data, 2)
    got = dense(data)[:, 0]
    for i in range(8):
        assert got[(FILL + 5 + 2 + i) % N] == 202 + i
    assert (ing.cursor, ing.rows_in, ing.rows_dropped) == (15, 13, 2)

    # the cursor payload round-trips; restore skips the valid() recount
    payload = ing.payload()
    assert sorted(payload) == ["cursor", "fill0", "rows_dropped",
                               "rows_in"]
    ing2 = Ingestor(StreamSpec(kind="extend", ingest_every=1),
                    EmptySource()).restore(payload).bind(eng, data)
    assert (ing2.cursor, ing2.fill0) == (15, FILL)


def test_extend_padding_rows_are_inert_until_a_delta_lands(mesh):
    """A capacity-padded MF problem (zero-mask rows absorbing future
    appends) must train exactly like the unpadded one: padded rows
    contribute nothing and their factors stay at zero."""
    N0, M = 4, 6
    r = np.random.default_rng(1)
    A, mask = mf.synthetic_ratings(r, N0, M, true_rank=2)
    small = mf.make_engine(mf.MFConfig(num_rows=N0, num_cols=M, rank=2),
                           mesh)
    sdata = small.shard_data(small.app.layout(A, mask))
    sstate = small.init_state(jax.random.key(0), data=sdata)
    # snapshot before execute: plan.donate would delete these buffers
    s0 = {k: np.array(np.asarray(v)) for k, v in sstate.items()}
    plan = ExecutionPlan(executor="scan", rounds=4)
    sfin = small.execute(sstate, sdata, jax.random.key(1), plan).state

    pad = np.zeros((4, M), np.float32)
    big = mf.make_engine(mf.MFConfig(num_rows=N0 + 4, num_cols=M,
                                     rank=2), mesh)
    bdata = big.shard_data(big.app.layout(np.concatenate([A, pad]),
                                          np.concatenate([mask, pad])))
    # the free rows hold no entry: the same entries, the same residuals
    np.testing.assert_array_equal(np.asarray(bdata["col"]),
                                  np.asarray(sdata["col"]))
    zW = np.zeros((4, 2), np.float32)
    bstate = {"W": jnp.asarray(np.concatenate([s0["W"], zW])),
              "H": jnp.asarray(s0["H"]),
              "R": jnp.asarray(s0["R"])}
    bfin = big.execute(bstate, bdata, jax.random.key(1), plan).state
    np.testing.assert_allclose(np.asarray(bfin["H"]),
                               np.asarray(sfin["H"]), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(bfin["W"])[:N0],
                               np.asarray(sfin["W"]), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(bfin["W"])[N0:], 0.0)


def test_extend_streamed_execute_end_to_end(mesh):
    # the full path: execute() with an extend drift source on the
    # capacity ring — shapes never change, cursor lands on the report
    eng, data, init, _ = _mf_setup(mesh)
    spec = StreamSpec(kind="extend", ingest_every=2)
    rep = eng.execute(init(), data, jax.random.key(1), _plan("scan", 8),
                      stream=spec,
                      source=MFDriftSource(num_rows=12, num_cols=10,
                                           rows_per_ingest=3, seed=5))
    assert np.asarray(rep.state["W"]).shape == (12, 3)
    assert int(rep.stream["rows_in"]) == 3 * 3      # t = 2, 4, 6
    assert int(rep.stream["rows_dropped"]) == 0


def test_extend_ingest_reuses_compiled_programs(mesh):
    """Fresh extend deltas never change a data shape: once one streamed
    run has compiled its programs, a second with other deltas compiles
    none."""
    eng, data, init, _ = _mf_setup(mesh)
    spec = StreamSpec(kind="extend", ingest_every=2)
    src = lambda seed: MFDriftSource(num_rows=12, num_cols=10,
                                     rows_per_ingest=3, seed=seed)

    def compiled():
        return sum(f._cache_size() for f in eng._scan_cache.values()
                   if hasattr(f, "_cache_size"))

    eng.execute(init(), data, jax.random.key(1), _plan("scan", 8),
                stream=spec, source=src(5))
    programs, compiles = len(eng._scan_cache), compiled()
    rep = eng.execute(init(), data, jax.random.key(1), _plan("scan", 8),
                      stream=spec, source=src(6))
    assert (len(eng._scan_cache), compiled()) == (programs, compiles)
    assert int(rep.stream["rows_in"]) == 3 * 3


def test_lda_ingest_keeps_collapsed_counts_exact(mesh):
    """After streamed ingest, the collapsed counts D/B/s must equal the
    counts materialized from scratch off (words, docs, z) — the exact
    invariant build_state establishes."""
    eng, data, init, cfg = _lda_setup(mesh)
    spec = StreamSpec(kind="extend", ingest_every=2)
    rep = eng.execute(init(), data, jax.random.key(1), _plan("scan", 4),
                      stream=spec,
                      source=LDADriftSource(num_tokens=24, vocab=20,
                                            num_topics=4,
                                            docs_per_worker=4,
                                            tokens_per_ingest=6, seed=7))
    assert int(rep.stream["rows_in"]) == 6          # t = 2 only
    st = rep.state
    z = np.asarray(st["z"])
    B = np.zeros_like(np.asarray(st["B"]))
    D = np.zeros_like(np.asarray(st["D"]))
    s = np.zeros_like(np.asarray(st["s"]))
    # data leaves were streamed — recount from the report's trajectory
    # inputs is impossible here, so recount from the final (words, z)
    # pair the engine actually holds: replay the data side
    data2, _ = replay_data(eng, data, spec,
                           LDADriftSource(num_tokens=24, vocab=20,
                                          num_topics=4, docs_per_worker=4,
                                          tokens_per_ingest=6, seed=7), 4)
    words = np.asarray(data2["words"])
    docs = np.asarray(data2["docs"])
    act = words >= 0
    np.add.at(B, (words[act], z[act]), 1)
    np.add.at(D, (docs[act], z[act]), 1)     # num_workers=1: global=local
    np.add.at(s, z[act], 1)
    np.testing.assert_array_equal(np.asarray(st["B"]), B)
    np.testing.assert_array_equal(np.asarray(st["D"]), D)
    np.testing.assert_array_equal(np.asarray(st["s"]), s)


# ---------------------------------------------------------------------------
# batching invariance: the trajectory sees the delta schedule, not how
# deltas were split
# ---------------------------------------------------------------------------

def _allclose_state(a_state, b_state, atol=1e-5):
    # the invariance is semantic, not bitwise: a split delta runs the
    # derived-state catch-up as two smaller matmuls, and XLA may block
    # a (6,J)@(J,) dot differently from two (3,J)@(J,) dots — same
    # math, last-ulp rounding differences
    assert set(a_state) == set(b_state)
    for k in a_state:
        np.testing.assert_allclose(np.asarray(a_state[k]),
                                   np.asarray(b_state[k]), rtol=1e-5,
                                   atol=atol, err_msg=k)


@settings(max_examples=6, deadline=None)
@given(st.integers(min_value=1, max_value=5),
       st.integers(min_value=0, max_value=99))
def test_trajectory_invariant_to_delta_batching(split, seed):
    mesh = single_device_mesh()
    eng, data, init, _ = _lasso_setup(mesh, seed=seed)
    r = np.random.default_rng(seed + 1)
    rows = np.sort(r.choice(48, size=6, replace=False))
    Xd = r.normal(size=(6, 24)).astype(np.float32)
    yd = r.normal(size=6).astype(np.float32)
    whole = {"rows": rows, "data": {"X": Xd, "y": yd}}
    parts = [{"rows": rows[:split],
              "data": {"X": Xd[:split], "y": yd[:split]}},
             {"rows": rows[split:],
              "data": {"X": Xd[split:], "y": yd[split:]}}]
    spec = StreamSpec(kind="replace", ingest_every=2)
    plan = ExecutionPlan(executor="scan", rounds=4)
    run = lambda src: eng.execute(init(), data, jax.random.key(1), plan,
                                  stream=spec, source=src)
    a = run(ScheduledSource({2: whole}))
    b = run(ScheduledSource({2: parts}))
    _allclose_state(a.state, b.state)
    assert all(int(a.stream[k]) == int(b.stream[k]) for k in a.stream)


def test_extend_split_delta_matches_whole_delta(mesh):
    # the ring cursor advances by the full delta size either way, so an
    # extend delta split in two lands on the same slots
    eng, data, init, _ = _mf_setup(mesh)
    d = _row_delta([300, 301, 302, 303], 10)
    halves = [{"data": {k: v[:2] for k, v in d["data"].items()}},
              {"data": {k: v[2:] for k, v in d["data"].items()}}]
    spec = StreamSpec(kind="extend", ingest_every=2)
    plan = ExecutionPlan(executor="scan", rounds=4)
    a = eng.execute(init(), data, jax.random.key(1), plan, stream=spec,
                    source=ScheduledSource({2: d}))
    b = eng.execute(init(), data, jax.random.key(1), plan, stream=spec,
                    source=ScheduledSource({2: halves}))
    _allclose_state(a.state, b.state)


# ---------------------------------------------------------------------------
# the serve loop streams at the same boundaries
# ---------------------------------------------------------------------------

def test_serve_while_training_streamed_matches_engine(mesh):
    eng, data, init, (X, y) = _lasso_setup(mesh)
    plan = _plan("ssp", 8)
    spec = StreamSpec(kind="replace", ingest_every=2)
    src = lambda: LassoDriftSource(num_rows=48, num_features=24,
                                   rows_per_ingest=4, seed=9)
    srep = serve_while_training(
        eng, init(), data, jax.random.key(1), plan, stream=spec,
        source=src(),
        requests=[(t, {"x": jnp.asarray(X[t])}) for t in (0, 4, 8)])
    ref = eng.execute(init(), data, jax.random.key(1), plan,
                      stream=spec, source=src())
    _bit_identical(srep.report.state, ref.state)
    assert srep.ingest is not None
    assert int(srep.ingest["rows_in"]) == int(ref.stream["rows_in"])
    assert len(srep.responses) == 3


def test_serve_while_training_streamed_holds_the_staleness_bound(mesh):
    """Reads served while drift ingest lands at the same boundaries stay
    within the ServeSpec bound, and some are stale."""
    eng, data, init, (X, y) = _lasso_setup(mesh)
    spec = ServeSpec(kind="stale", max_staleness=3, max_batch=8)
    srep = serve_while_training(
        eng, init(), data, jax.random.key(1), _plan("ssp", 8), spec=spec,
        requests=[(t, {"x": jnp.asarray(X[t])}) for t in range(8)],
        stream=StreamSpec(kind="replace", ingest_every=2),
        source=LassoDriftSource(num_rows=48, num_features=24,
                                rows_per_ingest=4, seed=9))
    assert len(srep.responses) == 8
    assert 0 < srep.max_staleness_read() <= spec.max_staleness
    assert int(srep.ingest["rows_in"]) > 0


def test_serve_while_training_rejects_misaligned_ingest(mesh):
    eng, data, init, _ = _lasso_setup(mesh)
    plan = _plan("ssp", 8)                          # chunk = window = 2
    with pytest.raises(ValueError, match="multiple"):
        serve_while_training(eng, init(), data, jax.random.key(1), plan,
                             stream=StreamSpec(kind="replace",
                                               ingest_every=3),
                             source=EmptySource())


# ---------------------------------------------------------------------------
# error paths: pairing, alignment, app support, delta validation
# ---------------------------------------------------------------------------

def test_execute_requires_stream_source_pair(mesh):
    eng, data, init, _ = _lasso_setup(mesh)
    plan = _plan("scan", 4)
    with pytest.raises(ValueError, match="come as a pair"):
        eng.execute(init(), data, jax.random.key(1), plan,
                    stream=StreamSpec(kind="replace"))
    with pytest.raises(ValueError, match="come as a pair"):
        eng.execute(init(), data, jax.random.key(1), plan,
                    source=EmptySource())
    with pytest.raises(ValueError, match="stream_state"):
        eng.execute(init(), data, jax.random.key(1), plan,
                    stream_state={"cursor": 0})


def test_execute_rejects_misaligned_ingest_cadence(mesh):
    eng, data, init, _ = _lasso_setup(mesh)
    plan = _plan("ssp", 8)                          # step length 2
    with pytest.raises(ValueError, match="ingest_every=3 must be a "
                                         "multiple"):
        eng.execute(init(), data, jax.random.key(1), plan,
                    stream=StreamSpec(kind="replace", ingest_every=3),
                    source=EmptySource())


def test_ingestor_type_and_lifecycle_errors(mesh):
    with pytest.raises(TypeError, match="StreamSpec"):
        Ingestor({"kind": "replace"}, EmptySource())
    with pytest.raises(TypeError, match="DataSource"):
        Ingestor(StreamSpec(kind="replace"), object())
    ing = Ingestor(StreamSpec(kind="replace"), EmptySource())
    with pytest.raises(RuntimeError, match="bind"):
        ing.step(None, None, {}, 0)
    with pytest.raises(ValueError, match="missing"):
        ing.restore({"cursor": 0})


def test_bind_rejects_apps_without_ingest_primitives():
    class NoIngest(StradsAppBase):
        pass

    class FakeEngine:
        app = NoIngest()
    with pytest.raises(NotImplementedError, match="ingest"):
        Ingestor(StreamSpec(kind="replace"),
                 EmptySource()).bind(FakeEngine(), {})


def test_bind_rejects_unsupported_kind_and_oversize_capacity(mesh):
    eng, data, init, _ = _lasso_setup(mesh)
    # lasso has no validity channel, so it declares replace-only
    with pytest.raises(ValueError, match="supports stream kinds"):
        Ingestor(StreamSpec(kind="extend"),
                 EmptySource()).bind(eng, data)
    meng, mdata, _, _ = _mf_setup(mesh)
    with pytest.raises(ValueError, match="exceeds"):
        Ingestor(StreamSpec(kind="extend", capacity=999),
                 EmptySource()).bind(meng, mdata)


def test_replace_delta_row_validation(mesh):
    eng, data, init, _ = _lasso_setup(mesh)         # 48 rows
    spec = StreamSpec(kind="replace", ingest_every=1)

    def bad(rows):
        k = len(rows)
        d = {"rows": np.asarray(rows),
             "data": {"X": np.zeros((k, 24), np.float32),
                      "y": np.zeros(k, np.float32)}}
        ing = Ingestor(spec, ScheduledSource({0: d})).bind(eng, data)
        ing.step(eng, None, data, 0)
    with pytest.raises(ValueError, match="unique"):
        bad([3, 3])
    with pytest.raises(ValueError, match="out of range"):
        bad([48])
    with pytest.raises(ValueError, match="out of range"):
        bad([-1])


def test_replay_data_verifies_cursor_against_checkpoint(mesh):
    eng, data, init, _ = _lasso_setup(mesh)
    spec = StreamSpec(kind="replace", ingest_every=2)
    src = lambda s: LassoDriftSource(num_rows=48, num_features=24,
                                     rows_per_ingest=4, seed=s)
    _, ing = replay_data(eng, data, spec, src(1), 6)
    # the right source verifies; a different seed (different stream)
    # would produce the same cursor counts here, so verify shape first
    replay_data(eng, data, spec, src(1), 6, stream_state=ing.payload())
    wrong = dict(ing.payload(), rows_in=np.int64(999))
    with pytest.raises(ValueError, match="rows_in"):
        replay_data(eng, data, spec, src(1), 6, stream_state=wrong)


# ---------------------------------------------------------------------------
# observability: ingest rides the Recorder
# ---------------------------------------------------------------------------

def test_ingest_events_ride_the_recorder(mesh):
    eng, data, init, _ = _lasso_setup(mesh)
    plan = ExecutionPlan(executor="scan", rounds=4,
                         telemetry=TelemetrySpec(kind="trace"))
    rep = eng.execute(init(), data, jax.random.key(1), plan,
                      stream=StreamSpec(kind="replace", ingest_every=2),
                      source=LassoDriftSource(num_rows=48,
                                              num_features=24,
                                              rows_per_ingest=4, seed=2))
    names = [e["name"] for e in rep.telemetry.events]
    assert "ingest" in names
    assert "ingest_rows" in names


# ---------------------------------------------------------------------------
# SyntheticLMSource ≡ repro.data.synthetic_batches (one derivation path)
# ---------------------------------------------------------------------------

def test_synthetic_lm_source_matches_pipeline():
    from repro.data.pipeline import synthetic_batches
    cfg = SyntheticLMConfig(vocab_size=50, seq_len=8, batch_size=2,
                            seed=3)
    src = SyntheticLMSource(cfg)
    assert src.peek(0) == 2
    delta = src.take(5)
    assert len(delta) == 1
    ref = make_batch(cfg, 5)
    for k in ref:
        np.testing.assert_array_equal(np.asarray(delta[0]["data"][k]),
                                      np.asarray(ref[k]))
    it = synthetic_batches(cfg)
    for step in range(3):
        got = next(it)
        want = make_batch(cfg, step)
        for k in want:
            np.testing.assert_array_equal(np.asarray(got[k]),
                                          np.asarray(want[k]))
