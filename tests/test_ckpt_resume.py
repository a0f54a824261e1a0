"""Engine checkpoint/resume: ``(state, scheduler carry, round counter,
SSP clocks)`` round-trip through ``checkpoint/npz`` — a resumed run must
match an uninterrupted one bit-for-bit (PRNG keys are serialized as key
data and re-wrapped, so the random stream continues exactly).  Also the
plan path (``StradsEngine.execute`` chunked by ``plan.checkpoint_every``,
``ExecutionReport.carry`` round-trips) and the trainer-level
``launch/train.py --resume --plan`` path.
"""
import dataclasses
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.apps import lasso, lda, mf
from repro.checkpoint import (latest_step, load_flat, restore_checkpoint,
                              save_checkpoint)
from repro.core import ExecutionPlan, single_device_mesh
from repro.stream import LassoDriftSource, StreamSpec, replay_data


def _bit_identical(a_state, b_state):
    for k in a_state:
        a, b = np.asarray(a_state[k]), np.asarray(b_state[k])
        assert (a == b).all(), (k, np.max(np.abs(a - b)))


def _setup(rng):
    mesh = single_device_mesh()
    X, y, _ = lasso.synthetic_correlated(rng, n=40, J=20, k_true=3)
    cfg = lasso.LassoConfig(num_features=20, lam=0.02, block_size=4,
                            num_candidates=8, rho=0.3)
    eng = lasso.make_engine(cfg, mesh)
    data = eng.shard_data({"X": jnp.asarray(X), "y": jnp.asarray(y)})
    return eng, data, y


def test_ssp_resume_matches_uninterrupted(tmp_path, rng):
    eng, data, y = _setup(rng)
    plan = ExecutionPlan(executor="ssp", rounds=8, staleness=1)

    # uninterrupted: 8 rounds in one go
    full = eng.execute(eng.init_state(jax.random.key(0), y=y), data,
                       jax.random.key(1), plan).state

    # interrupted: 4 rounds, checkpoint the full run state, restore into
    # a fresh template, continue to round 8
    half = eng.execute(eng.init_state(jax.random.key(0), y=y), data,
                       jax.random.key(1),
                       ExecutionPlan(executor="ssp", rounds=4, staleness=1))
    save_checkpoint(str(tmp_path), 4, {"state": half.state,
                                       "carry": half.carry})
    assert latest_step(str(tmp_path)) == 4

    template = {"state": jax.tree.map(jnp.copy, half.state),
                "carry": half.carry}
    restored = restore_checkpoint(str(tmp_path), 4, template)
    c = restored["carry"]
    assert int(c.t) == 4 and (np.asarray(c.clocks) == 4).all()
    # the scheduler carry (Δβ priority history) is part of SSPCarry now
    # and must resume with the rest of the carry for bit-exactness
    assert c.sched_carry is not None
    resumed = eng.execute(restored["state"], data, jax.random.key(99),
                          plan, carry=c).state
    _bit_identical(full, resumed)


def test_scanned_sched_carry_roundtrips_through_npz(tmp_path, rng):
    """The scheduler carry (Δx history) is an explicit EngineCarry field
    now — ``{"state", "carry"}`` round-trips it through checkpoint/npz,
    and resuming from it continues the dynamic schedule bit-exactly."""
    eng, data, y = _setup(rng)
    plan = ExecutionPlan(executor="scan", rounds=8, donate=False)

    full_rep = eng.execute(eng.init_state(jax.random.key(0), y=y), data,
                           jax.random.key(1), plan)
    full = full_rep.state

    half = eng.execute(eng.init_state(jax.random.key(0), y=y), data,
                       jax.random.key(1),
                       ExecutionPlan(executor="scan", rounds=4))
    carry = half.carry
    assert carry.sched_carry is not None        # the Δβ priority history
    save_checkpoint(str(tmp_path), 4, {"state": half.state, "carry": carry})
    template = {"state": jax.tree.map(jnp.copy, half.state), "carry": carry}
    back = restore_checkpoint(str(tmp_path), 4, template)
    c = back["carry"]
    assert (np.asarray(c.sched_carry)
            == np.asarray(carry.sched_carry)).all()
    res = eng.execute(back["state"], data, jax.random.key(99), plan,
                      carry=c)
    _bit_identical(full, res.state)
    # the final carries of full vs chunked runs agree exactly
    assert (np.asarray(full_rep.carry.sched_carry)
            == np.asarray(res.carry.sched_carry)).all()
    # the carry is load-bearing: resuming with a FRESH scheduler carry
    # (uniform priorities) must diverge from the uninterrupted dynamic
    # schedule — and a carry without one is refused
    fresh = dataclasses.replace(c, sched_carry=eng.init_sched_carry())
    diverged = eng.execute(back["state"], data, jax.random.key(99), plan,
                           carry=fresh).state
    assert not (np.asarray(diverged["beta"])
                == np.asarray(full["beta"])).all()
    with pytest.raises(ValueError, match="scheduler carry"):
        eng.execute(back["state"], data, jax.random.key(99), plan,
                    carry=dataclasses.replace(c, sched_carry=None))


def test_execute_plan_checkpoint_chunks_match_uninterrupted(tmp_path,
                                                            rng):
    """The plan path: ``execute(plan(checkpoint_every=4), ckpt_dir=...)``
    chunks an 8-round SSP run into two compiled spans with a full
    ``{"state", "carry"}`` checkpoint between them — and matches the
    unchunked run bit-for-bit; restoring the mid checkpoint and resuming
    via ``execute(..., carry=...)`` does too."""
    eng, data, y = _setup(rng)

    full = eng.execute(eng.init_state(jax.random.key(0), y=y), data,
                       jax.random.key(1),
                       ExecutionPlan(executor="ssp", rounds=8,
                                     staleness=1)).state

    plan = ExecutionPlan(executor="ssp", rounds=8, staleness=1,
                         checkpoint_every=4)
    rep = eng.execute(eng.init_state(jax.random.key(0), y=y), data,
                      jax.random.key(1), plan, ckpt_dir=str(tmp_path))
    _bit_identical(full, rep.state)
    assert latest_step(str(tmp_path)) == 8
    assert int(rep.carry.t) == 8

    # ExecutionReport.carry round-trips through checkpoint/npz: restore
    # the mid-run checkpoint and continue the same plan.
    template = {"state": jax.tree.map(jnp.copy, rep.state),
                "carry": rep.carry}
    restored = restore_checkpoint(str(tmp_path), 4, template)
    assert int(restored["carry"].t) == 4
    resumed = eng.execute(restored["state"], data, jax.random.key(99),
                          plan, carry=restored["carry"],
                          ckpt_dir=str(tmp_path / "resumed"))
    _bit_identical(full, resumed.state)


def test_streamed_resume_matches_uninterrupted(tmp_path, rng):
    """Mid-stream checkpoint/resume: the ``"stream"`` cursor payload
    rides the checkpoint beside ``"state"``/``"carry"``, and a resumed
    streamed run — data rebuilt with :func:`repro.stream.replay_data`,
    cursor restored via ``stream_state=`` — continues bit-exactly.
    (ingest-at-top/checkpoint-at-bottom: the checkpoint at t precedes
    the ingest at t, so the resume re-ingests boundary t exactly like
    the uninterrupted run did)."""
    eng, data, y = _setup(rng)
    spec = StreamSpec(kind="replace", ingest_every=2)
    src = lambda: LassoDriftSource(num_rows=40, num_features=20,
                                   rows_per_ingest=4, seed=3)

    full = eng.execute(eng.init_state(jax.random.key(0), y=y), data,
                       jax.random.key(1),
                       ExecutionPlan(executor="ssp", rounds=8,
                                     staleness=1),
                       stream=spec, source=src()).state

    plan = ExecutionPlan(executor="ssp", rounds=8, staleness=1,
                         checkpoint_every=4)
    rep = eng.execute(eng.init_state(jax.random.key(0), y=y), data,
                      jax.random.key(1), plan, ckpt_dir=str(tmp_path),
                      stream=spec, source=src())
    _bit_identical(full, rep.state)
    assert rep.stream is not None and int(rep.stream["rows_in"]) > 0

    # the mid checkpoint carries the cursor as a "stream" subtree
    flat = load_flat(str(tmp_path), 4)
    stream_state = {k.split("/", 1)[1]: v for k, v in flat.items()
                    if k.startswith("stream/")}
    assert set(stream_state) == {"cursor", "rows_in", "rows_dropped",
                                 "fill0"}

    # a resumed process no longer holds the streamed data: rebuild it
    # from the deterministic source, verified against the cursor
    data4, _ = replay_data(eng, data, spec, src(), 4,
                           stream_state=stream_state)

    template = {"state": jax.tree.map(jnp.copy, rep.state),
                "carry": rep.carry}
    restored = restore_checkpoint(str(tmp_path), 4, template)
    assert int(restored["carry"].t) == 4
    resumed = eng.execute(restored["state"], data4, jax.random.key(99),
                          plan, carry=restored["carry"],
                          ckpt_dir=str(tmp_path / "resumed"),
                          stream=spec, source=src(),
                          stream_state=stream_state)
    _bit_identical(full, resumed.state)
    # the resumed leg's final cursor agrees with the uninterrupted one
    assert int(resumed.stream["rows_in"]) == int(rep.stream["rows_in"])


def test_execute_pipelined_carry_resumes_inflight_schedule(tmp_path, rng):
    """Chunking the pipelined executor must carry the prefetched
    in-flight schedule across the chunk boundary (EngineCarry.sched) —
    without it, the resumed schedule would be fresh instead of one round
    stale and the runs would diverge."""
    eng, data, y = _setup(rng)

    plan_full = ExecutionPlan(executor="pipelined", rounds=8)
    full = eng.execute(eng.init_state(jax.random.key(0), y=y), data,
                       jax.random.key(1), plan_full).state

    plan = ExecutionPlan(executor="pipelined", rounds=8,
                         checkpoint_every=4)
    rep = eng.execute(eng.init_state(jax.random.key(0), y=y), data,
                      jax.random.key(1), plan, ckpt_dir=str(tmp_path))
    _bit_identical(full, rep.state)
    assert rep.carry.sched is not None          # the in-flight schedule

    template = {"state": jax.tree.map(jnp.copy, rep.state),
                "carry": rep.carry}
    restored = restore_checkpoint(str(tmp_path), 4, template)
    resumed = eng.execute(restored["state"], data, jax.random.key(99),
                          plan, carry=restored["carry"],
                          ckpt_dir=str(tmp_path / "resumed"))
    _bit_identical(full, resumed.state)


def test_execute_chunked_honors_callback_early_stop(tmp_path, rng):
    """A callback stop inside a checkpoint chunk must end the whole run
    (no skipped rounds, no further chunks) and checkpoint at the round
    actually reached."""
    eng, data, y = _setup(rng)
    plan = ExecutionPlan(executor="loop", rounds=6, checkpoint_every=2)
    seen = []

    def cb(t, s, out):
        seen.append(t)
        return t == 2                           # stop mid-chunk 2

    rep = eng.execute(eng.init_state(jax.random.key(0), y=y), data,
                      jax.random.key(1), plan, callback=cb,
                      ckpt_dir=str(tmp_path))
    assert seen == [0, 1, 2]
    assert int(rep.carry.t) == 3
    assert latest_step(str(tmp_path)) == 3

    # ... including when the stop lands exactly on a chunk boundary
    seen2 = []
    d2 = tmp_path / "boundary"
    rep2 = eng.execute(eng.init_state(jax.random.key(0), y=y), data,
                       jax.random.key(1), plan,
                       callback=lambda t, s, o: (seen2.append(t),
                                                 t == 1)[1],
                       ckpt_dir=str(d2))
    assert seen2 == [0, 1]
    assert int(rep2.carry.t) == 2
    assert latest_step(str(d2)) == 2


def test_execute_chunked_rejects_unrunnable_final_chunk(tmp_path, rng):
    """pipelined/ssp plans whose rounds don't tile the step length must
    fail before any chunk runs (without ckpt_dir the executor itself
    rejects them upfront — chunking must not defer that to the last
    chunk, after checkpoints were already written)."""
    eng, data, y = _setup(rng)
    state = eng.init_state(jax.random.key(0), y=y)
    plan = ExecutionPlan(executor="ssp", rounds=7, staleness=1,
                         checkpoint_every=2)    # 7 % 2 != 0
    with pytest.raises(ValueError, match="plan.rounds"):
        eng.execute(state, data, jax.random.key(1), plan,
                    ckpt_dir=str(tmp_path))
    assert latest_step(str(tmp_path)) is None


def test_execute_rejects_foreign_carry_types(tmp_path, rng):
    """Resuming a plan with a carry from a different executor must error,
    not silently diverge from the uninterrupted run."""
    eng, data, y = _setup(rng)

    ssp_rep = eng.execute(eng.init_state(jax.random.key(0), y=y), data,
                          jax.random.key(1),
                          ExecutionPlan(executor="ssp", rounds=4,
                                        staleness=1))
    scan_rep = eng.execute(eng.init_state(jax.random.key(0), y=y), data,
                           jax.random.key(1),
                           ExecutionPlan(executor="scan", rounds=4))
    state = eng.init_state(jax.random.key(0), y=y)
    # SSPCarry into a pipelined plan: no .sched
    with pytest.raises(ValueError, match="EngineCarry"):
        eng.execute(state, data, None,
                    ExecutionPlan(executor="pipelined", rounds=8),
                    carry=ssp_rep.carry)
    # depth-0 EngineCarry into a pipelined plan: sched is None
    with pytest.raises(ValueError, match="in-flight schedule"):
        eng.execute(state, data, None,
                    ExecutionPlan(executor="pipelined", rounds=8),
                    carry=scan_rep.carry)
    # EngineCarry into an ssp plan: no .clocks
    with pytest.raises(ValueError, match="SSPCarry"):
        eng.execute(state, data, None,
                    ExecutionPlan(executor="ssp", rounds=8, staleness=1),
                    carry=scan_rep.carry)


def test_execute_rejects_ckpt_dir_without_cadence(tmp_path, rng):
    """ckpt_dir with checkpoint_every=0 would be a silent no-op — reject
    it so a crash mid-run can't lose progress the caller believed was
    being checkpointed."""
    eng, data, y = _setup(rng)
    state = eng.init_state(jax.random.key(0), y=y)
    with pytest.raises(ValueError, match="checkpoint_every"):
        eng.execute(state, data, jax.random.key(1),
                    ExecutionPlan(executor="scan", rounds=4),
                    ckpt_dir=str(tmp_path))
    # ... and the converse: a checkpointing cadence without anywhere to
    # write would silently never checkpoint
    with pytest.raises(ValueError, match="ckpt_dir"):
        eng.execute(state, data, jax.random.key(1),
                    ExecutionPlan(executor="scan", rounds=4,
                                  checkpoint_every=2))


def test_execute_rejects_misaligned_checkpoint_cadence(tmp_path, rng):
    """checkpoint_every must tile the executor step length — rejected
    upfront, before any chunk runs or checkpoint is written."""
    eng, data, y = _setup(rng)
    state = eng.init_state(jax.random.key(0), y=y)
    plan = ExecutionPlan(executor="ssp", rounds=8, staleness=1,
                         checkpoint_every=3)    # SSP window is 2
    with pytest.raises(ValueError, match="checkpoint_every"):
        eng.execute(state, data, jax.random.key(1), plan,
                    ckpt_dir=str(tmp_path))
    assert latest_step(str(tmp_path)) is None


def test_ssp_resume_rejects_misaligned_t0(rng):
    """An SSPCarry whose round is off the s=1 window (length 2) cannot
    resume: its phases and windows would not line up with the steps."""
    eng, data, y = _setup(rng)
    rep = eng.execute(eng.init_state(jax.random.key(0), y=y), data,
                      jax.random.key(1),
                      ExecutionPlan(executor="ssp", rounds=4, staleness=1))
    carry = dataclasses.replace(rep.carry, t=jnp.int32(3))
    with pytest.raises(ValueError, match="t0"):
        eng.execute(rep.state, data, None,
                    ExecutionPlan(executor="ssp", rounds=8, staleness=1),
                    carry=carry)


def _lda_setup(rng):
    cfg = lda.LDAConfig(vocab=30, num_topics=4, num_workers=1,
                        tokens_per_worker=200, docs_per_worker=5)
    words, docs, z0 = lda.synthetic_corpus(rng, cfg, true_topics=4)
    eng = lda.make_engine(cfg, single_device_mesh())
    data = eng.shard_data({"words": jnp.asarray(words),
                           "docs": jnp.asarray(docs)})
    return eng, data, lambda: eng.init_state(jax.random.key(0),
                                             words=words, docs=docs, z0=z0)


def _mf_setup(rng):
    A, mask = mf.synthetic_ratings(rng, 40, 30, true_rank=4, density=0.5)
    cfg = mf.MFConfig(num_rows=40, num_cols=30, rank=4, lam=0.05)
    eng = mf.make_engine(cfg, single_device_mesh())
    data = eng.shard_data(eng.app.layout(A, mask))
    return eng, data, lambda: eng.init_state(jax.random.key(0), data=data)


@pytest.mark.parametrize("app,executor,staleness", [
    ("lda", "loop", 0), ("lda", "scan", 0), ("lda", "pipelined", 0),
    ("lda", "ssp", 1),
    ("mf", "loop", 0), ("mf", "pipelined", 0), ("mf", "ssp", 1),
])
def test_chunked_resume_matches_unchunked(tmp_path, rng, app, executor,
                                          staleness):
    """A run chunked by ``checkpoint_every``, and the same run resumed
    from its mid-run checkpoint with ``carry=``, each equal the
    unchunked run bit for bit — the resume every benchmark chunk makes.
    (MF under scan is ``test_mf.py``'s sparse-layout resume case.)"""
    eng, data, init = {"lda": _lda_setup, "mf": _mf_setup}[app](rng)
    kw = dict(executor=executor, rounds=8, staleness=staleness)
    full = eng.execute(init(), data, jax.random.key(1),
                       ExecutionPlan(**kw)).state

    plan = ExecutionPlan(checkpoint_every=4, **kw)
    rep = eng.execute(init(), data, jax.random.key(1), plan,
                      ckpt_dir=str(tmp_path))
    _bit_identical(full, rep.state)

    template = {"state": jax.tree.map(jnp.copy, rep.state),
                "carry": rep.carry}
    restored = restore_checkpoint(str(tmp_path), 4, template)
    assert int(restored["carry"].t) == 4
    resumed = eng.execute(restored["state"], data, jax.random.key(99),
                          plan, carry=restored["carry"],
                          ckpt_dir=str(tmp_path / "resumed"))
    _bit_identical(full, resumed.state)


@pytest.mark.slow
def test_train_resume_matches_uninterrupted(tmp_path):
    """launch/train.py --resume --plan: full-state checkpoints make the
    resumed run reproduce the uninterrupted loss exactly (deterministic
    synthetic batches are indexed by global step); the interrupted +
    resumed legs are driven by a checked-in-style ExecutionPlan JSON
    (rounds → steps, checkpoint_every → ckpt cadence)."""
    from repro.launch import train

    common = ["--arch", "xlstm-125m", "--preset", "reduced",
              "--steps", "4", "--batch", "2", "--seq", "16",
              "--log-every", "1", "--seed", "7"]
    full = train.main(common)

    plan = ExecutionPlan(executor="loop", rounds=4, checkpoint_every=2)
    plan_path = str(tmp_path / "plan.json")
    with open(plan_path, "w") as f:
        json.dump(plan.to_json(), f)

    d = str(tmp_path / "ck")
    train.main(common + ["--plan", plan_path, "--ckpt-dir", d])
    assert latest_step(d) == 4
    # wipe the final checkpoint so --resume restarts mid-run (step 2)
    import os
    os.remove(os.path.join(d, "step_00000004.npz"))
    resumed = train.main(common + ["--plan", plan_path, "--ckpt-dir", d,
                                   "--resume"])

    assert resumed[-1]["step"] == full[-1]["step"] == 3
    assert resumed[-1]["loss"] == pytest.approx(full[-1]["loss"],
                                                rel=1e-6, abs=0)
