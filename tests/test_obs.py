"""The unified observability subsystem (repro/obs).

Contract under test (ISSUE 7 acceptance):
  * telemetry is **bit-neutral**: every executor × every paper app
    produces the exact same final state with telemetry off, with device
    counters, and with the full trace recorder — instrumentation rides
    outside the primitives and can never change what a round computes.
  * the device-counter identities hold for arbitrary runs (hypothesis
    property): per-phase round totals sum to the run's rounds and the
    ρ-filter ledger balances (``accepted + killed == proposed``).
  * all four executors return a populated
    :class:`~repro.obs.report.RunReport` in
    ``ExecutionReport.telemetry`` carrying the resolved spec.
  * the Chrome-trace export is valid JSON whose spans are strictly
    nested with non-negative durations (``validate_spans``).
  * counters are bit-exact through ``checkpoint_every`` chunking and
    through a checkpoint/restore resume (``EngineCarry.obs`` rides the
    npz payload like every other carry leaf).
  * the plan field: ``False`` is off, a spec turns telemetry on for any
    executor, and plans round-trip through JSON with specs intact.
"""
import json

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax
import jax.numpy as jnp

from repro.apps import lasso, lda, mf
from repro.checkpoint import restore_checkpoint
from repro.core import ExecutionPlan, single_device_mesh
from repro.obs import (Recorder, RunReport, TelemetrySpec, chrome_trace,
                       report_from_json, validate_spans)
from repro.launch.trace import check_report, extract_report_dicts


@pytest.fixture(scope="module")
def mesh():
    return single_device_mesh()


def _bit_identical(a_state, b_state):
    assert set(a_state) == set(b_state)
    for k in a_state:
        a, b = np.asarray(a_state[k]), np.asarray(b_state[k])
        assert (a == b).all(), (k, np.max(np.abs(a - b)))


def _lasso_engine(rng, mesh, n=40, J=20):
    X, y, _ = lasso.synthetic_correlated(rng, n=n, J=J, k_true=3)
    cfg = lasso.LassoConfig(num_features=J, lam=0.02, block_size=4,
                            num_candidates=8, rho=0.3)
    eng = lasso.make_engine(cfg, mesh)
    data = eng.shard_data({"X": jnp.asarray(X), "y": jnp.asarray(y)})
    return eng, data, y


def _plan(executor, rounds, telemetry):
    kw = {"staleness": 1} if executor == "ssp" else {}
    return ExecutionPlan(executor=executor, rounds=rounds,
                         telemetry=telemetry, **kw)


# ---------------------------------------------------------------------------
# bit-neutrality: telemetry on ≡ off, every executor × every paper app
# ---------------------------------------------------------------------------

EXECUTORS = ("loop", "scan", "pipelined", "ssp")
SPECS = (False, TelemetrySpec(kind="counters"), TelemetrySpec(kind="trace"))


def _run_all_specs(eng, state, data, executor, rounds):
    """Final states for off / counters / trace runs of the same plan
    (fresh state copy per run — executors donate buffers)."""
    return [eng.execute(jax.tree.map(jnp.copy, state), data,
                        jax.random.key(1),
                        _plan(executor, rounds, t)).state
            for t in SPECS]


@pytest.mark.parametrize("executor", EXECUTORS)
def test_lasso_telemetry_is_bit_neutral(executor, mesh, rng):
    eng, data, y = _lasso_engine(rng, mesh)
    state = eng.init_state(jax.random.key(0), y=y)
    states = _run_all_specs(eng, state, data, executor, 8)
    _bit_identical(states[0], states[1])
    _bit_identical(states[0], states[2])


@pytest.mark.parametrize("executor", EXECUTORS)
def test_lda_telemetry_is_bit_neutral(executor, mesh, rng):
    cfg = lda.LDAConfig(vocab=30, num_topics=4, num_workers=1,
                        tokens_per_worker=200, docs_per_worker=5)
    words, docs, z0 = lda.synthetic_corpus(rng, cfg, true_topics=4)
    eng = lda.make_engine(cfg, mesh)
    data = eng.shard_data({"words": jnp.asarray(words),
                           "docs": jnp.asarray(docs)})
    state = eng.init_state(jax.random.key(0), words=words, docs=docs,
                           z0=z0)
    states = _run_all_specs(eng, state, data, executor, 6)
    _bit_identical(states[0], states[1])
    _bit_identical(states[0], states[2])


@pytest.mark.parametrize("executor", EXECUTORS)
def test_mf_telemetry_is_bit_neutral(executor, mesh, rng):
    A, mask = mf.synthetic_ratings(rng, 40, 30, true_rank=4, density=0.5)
    cfg = mf.MFConfig(num_rows=40, num_cols=30, rank=4, lam=0.05)
    eng = mf.make_engine(cfg, mesh)
    data = eng.shard_data(eng.app.layout(A, mask))
    state = eng.init_state(jax.random.key(0), data=data)
    states = _run_all_specs(eng, state, data, executor, 8)
    _bit_identical(states[0], states[1])
    _bit_identical(states[0], states[2])


# ---------------------------------------------------------------------------
# every executor returns a populated RunReport with the resolved spec
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("executor", EXECUTORS)
def test_every_executor_returns_runreport(executor, mesh, rng):
    eng, data, y = _lasso_engine(rng, mesh)
    spec = TelemetrySpec(kind="trace")
    rep = eng.execute(eng.init_state(jax.random.key(0), y=y), data,
                      jax.random.key(1), _plan(executor, 8, spec))
    report = rep.telemetry
    assert isinstance(report, RunReport)
    assert report.spec == spec
    assert report.executor == executor
    assert report.rounds == 8
    c = report.counters
    assert c["rounds"] == 8
    assert sum(c["rounds_per_phase"]) == 8
    assert c["accepted"] + c["killed"] == c["proposed"]
    assert c["accepted"] > 0
    # every trace run records at least the execute > executor span pair
    names = [e["name"] for e in report.events]
    assert "execute" in names
    assert validate_spans(report.events) is None
    # the SSP staleness section appears exactly for the ssp executor
    assert (report.ssp is not None) == (executor == "ssp")
    # check_report (the trace CLI's offline validator) agrees, both on
    # the live report and after a JSON round-trip
    assert check_report(report) is None
    assert check_report(report_from_json(report.to_json())) is None


def test_no_spec_means_no_report(mesh, rng):
    eng, data, y = _lasso_engine(rng, mesh)
    rep = eng.execute(eng.init_state(jax.random.key(0), y=y), data,
                      jax.random.key(1), _plan("scan", 4, False))
    assert rep.telemetry is None


def test_counters_kind_records_no_events(mesh, rng):
    eng, data, y = _lasso_engine(rng, mesh)
    rep = eng.execute(eng.init_state(jax.random.key(0), y=y), data,
                      jax.random.key(1),
                      _plan("scan", 4, TelemetrySpec(kind="counters")))
    assert rep.telemetry.events == []
    assert rep.telemetry.counters["rounds"] == 4


# ---------------------------------------------------------------------------
# the counter identities, as a property over run shapes (hypothesis)
# ---------------------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(st.integers(min_value=1, max_value=4),
       st.sampled_from(["loop", "scan", "ssp"]),
       st.sampled_from(["strads", "rr", "cyclic"]))
def test_counter_identities_hold(steps, executor, scheduler):
    """Σ per-phase rounds == rounds and accepted + killed == proposed,
    for random (length, executor, scheduler-policy) configurations."""
    mesh = single_device_mesh()
    r = np.random.default_rng(steps * 13 + len(executor))
    X, y, _ = lasso.synthetic_correlated(r, n=24, J=12, k_true=3)
    cfg = lasso.LassoConfig(num_features=12, lam=0.02, block_size=3,
                            num_candidates=6, rho=0.5,
                            scheduler=scheduler)
    eng = lasso.make_engine(cfg, mesh)
    data = eng.shard_data({"X": jnp.asarray(X), "y": jnp.asarray(y)})
    R = 2 * steps
    rep = eng.execute(eng.init_state(jax.random.key(0), y=y), data,
                      jax.random.key(1),
                      _plan(executor, R, TelemetrySpec(kind="counters")))
    c = rep.telemetry.counters
    assert c["rounds"] == R
    assert sum(c["rounds_per_phase"]) == R
    assert all(v >= 0 for v in c["rounds_per_phase"])
    assert c["accepted"] + c["killed"] == c["proposed"]
    assert 0 <= c["accepted"] <= c["proposed"]
    # accepted counts the admitted schedule entries: at most the
    # schedule's width a round
    assert c["accepted"] <= R * cfg.block_size
    if scheduler == "strads":
        # the dynamic-priority policy ρ-filters num_candidates per round
        assert c["proposed"] == R * cfg.num_candidates
    else:
        # rr/cyclic schedule fixed blocks: nothing proposed gets killed
        assert c["killed"] == 0 and c["proposed"] == c["accepted"]


# ---------------------------------------------------------------------------
# the Chrome-trace export: valid JSON, strictly nested spans
# ---------------------------------------------------------------------------

def test_chrome_trace_export_is_valid_and_nested(tmp_path, mesh, rng):
    eng, data, y = _lasso_engine(rng, mesh)
    plan = ExecutionPlan(executor="ssp", rounds=8, staleness=1,
                         checkpoint_every=4,
                         telemetry=TelemetrySpec(kind="trace"))
    rep = eng.execute(eng.init_state(jax.random.key(0), y=y), data,
                      jax.random.key(1), plan,
                      ckpt_dir=str(tmp_path / "ck"))
    events = rep.telemetry.events
    # chunking makes a real hierarchy: execute > {ssp × 2, checkpoint × 2}
    names = [e["name"] for e in events if e.get("ph") == "X"]
    assert names.count("ssp") == 2
    assert names.count("checkpoint") == 2
    assert validate_spans(events) is None

    out = rep.telemetry.write_chrome_trace(str(tmp_path / "t.json"))
    with open(out) as f:
        doc = json.load(f)                      # must parse
    assert doc["displayTimeUnit"] == "ms"
    tev = doc["traceEvents"]
    assert len(tev) == len(events)
    spans = [e for e in tev if e["ph"] == "X"]
    assert all(e["dur"] >= 0 and e["ts"] >= 0 for e in spans)
    # strict nesting: any two overlapping spans contain one another
    for a in spans:
        for b in spans:
            if a is b:
                continue
            a0, a1 = a["ts"], a["ts"] + a["dur"]
            b0, b1 = b["ts"], b["ts"] + b["dur"]
            overlap = max(a0, b0) < min(a1, b1)
            nested = (a0 <= b0 and b1 <= a1) or (b0 <= a0 and a1 <= b1)
            assert not overlap or nested, (a["name"], b["name"])


def test_validate_spans_flags_violations():
    ok = [{"name": "a", "ph": "X", "ts": 0.0, "dur": 10.0, "args": {}},
          {"name": "b", "ph": "X", "ts": 2.0, "dur": 3.0, "args": {}}]
    assert validate_spans(ok) is None
    crossing = ok + [{"name": "c", "ph": "X", "ts": 4.0, "dur": 10.0,
                      "args": {}}]
    assert validate_spans(crossing) is not None
    negative = [{"name": "a", "ph": "X", "ts": 0.0, "dur": -1.0,
                 "args": {}}]
    assert validate_spans(negative) is not None


def test_recorder_span_stack_discipline():
    rec = Recorder()
    with rec.span("outer", k=1):
        rec.instant("tick")
        with rec.span("inner"):
            pass
    ev = rec.to_json_events()
    assert [e["name"] for e in ev] == ["outer", "tick", "inner"]
    assert validate_spans(ev) is None
    doc = chrome_trace(ev)
    assert {e["name"] for e in doc["traceEvents"]} == \
        {"outer", "tick", "inner"}


# ---------------------------------------------------------------------------
# counters survive chunking and checkpoint/resume bit-exactly
# ---------------------------------------------------------------------------

def test_counters_bit_exact_through_chunking_and_resume(tmp_path, mesh,
                                                        rng):
    eng, data, y = _lasso_engine(rng, mesh)
    spec = TelemetrySpec(kind="counters")

    full = eng.execute(eng.init_state(jax.random.key(0), y=y), data,
                       jax.random.key(1), _plan("scan", 8, spec))

    plan = ExecutionPlan(executor="scan", rounds=8, telemetry=spec,
                         checkpoint_every=4)
    chunked = eng.execute(eng.init_state(jax.random.key(0), y=y), data,
                          jax.random.key(1), plan,
                          ckpt_dir=str(tmp_path))
    _bit_identical(full.state, chunked.state)
    assert chunked.telemetry.counters == full.telemetry.counters

    # EngineCarry.obs rides the npz payload: restore the mid checkpoint
    # and resume — the final counters must match the uninterrupted run
    template = {"state": jax.tree.map(jnp.copy, chunked.state),
                "carry": chunked.carry}
    restored = restore_checkpoint(str(tmp_path), 4, template)
    mid = restored["carry"]
    assert mid.obs is not None
    assert int(np.asarray(mid.obs["rounds"]).sum()) == 4
    resumed = eng.execute(restored["state"], data, jax.random.key(99),
                          plan, carry=mid,
                          ckpt_dir=str(tmp_path / "resumed"))
    _bit_identical(full.state, resumed.state)
    assert resumed.telemetry.counters == full.telemetry.counters


def test_ssp_counters_bit_exact_through_chunking(tmp_path, mesh, rng):
    eng, data, y = _lasso_engine(rng, mesh)
    spec = TelemetrySpec(kind="counters")
    full = eng.execute(eng.init_state(jax.random.key(0), y=y), data,
                       jax.random.key(1), _plan("ssp", 8, spec))
    plan = ExecutionPlan(executor="ssp", rounds=8, staleness=1,
                         telemetry=spec, checkpoint_every=4)
    chunked = eng.execute(eng.init_state(jax.random.key(0), y=y), data,
                          jax.random.key(1), plan,
                          ckpt_dir=str(tmp_path))
    _bit_identical(full.state, chunked.state)
    assert chunked.telemetry.counters == full.telemetry.counters
    # the per-chunk SSP staleness summaries merge into one section
    assert chunked.telemetry.ssp is not None
    assert (np.asarray(chunked.telemetry.ssp.hist)
            == np.asarray(full.telemetry.ssp.hist)).all()
    assert chunked.telemetry.ssp.flushes == full.telemetry.ssp.flushes


# ---------------------------------------------------------------------------
# the plan surface: spec field, JSON round-trip
# ---------------------------------------------------------------------------

def test_plan_bool_false_stays_falsy():
    plan = ExecutionPlan(executor="scan", rounds=4, telemetry=False)
    assert plan.telemetry is False
    assert (plan.telemetry or None) is None


def test_plan_rejects_non_spec_telemetry():
    with pytest.raises(ValueError, match="telemetry"):
        ExecutionPlan(executor="scan", rounds=4, telemetry="counters")


def test_plan_json_roundtrips_spec():
    plan = ExecutionPlan(executor="ssp", rounds=8, staleness=1,
                         telemetry=TelemetrySpec(kind="trace",
                                                 profiler=True))
    back = ExecutionPlan.from_json(json.loads(json.dumps(plan.to_json())))
    assert back == plan
    assert back.telemetry == TelemetrySpec(kind="trace", profiler=True)
    # and a plan with telemetry off serializes it as false
    off = ExecutionPlan.from_json(
        ExecutionPlan(executor="scan", rounds=4).to_json())
    assert off.telemetry is False


def test_non_ssp_executor_accepts_telemetry(mesh, rng):
    """Telemetry is not an ssp-only field: every executor takes a
    spec."""
    eng, data, y = _lasso_engine(rng, mesh)
    plan = ExecutionPlan(executor="scan", rounds=4,
                         telemetry=TelemetrySpec(kind="counters"))
    rep = eng.execute(eng.init_state(jax.random.key(0), y=y), data,
                      jax.random.key(1), plan)
    assert rep.telemetry.counters["rounds"] == 4


# ---------------------------------------------------------------------------
# TelemetrySpec validation + serialization
# ---------------------------------------------------------------------------

def test_spec_rejects_unknown_kind():
    with pytest.raises(ValueError, match="kind"):
        TelemetrySpec(kind="metrics")


def test_spec_rejects_profiler_for_counters():
    with pytest.raises(ValueError, match="profiler"):
        TelemetrySpec(kind="counters", profiler=True)


def test_spec_json_roundtrip_and_unknown_keys():
    s = TelemetrySpec(kind="trace", profiler=True)
    assert TelemetrySpec.from_json(s.to_json()) == s
    assert TelemetrySpec.from_json(json.dumps(s.to_json())) == s
    with pytest.raises(ValueError, match="unknown"):
        TelemetrySpec.from_json({"kind": "trace", "verbosity": 3})
    assert TelemetrySpec.default_for("counters") == \
        TelemetrySpec(kind="counters")
    assert not TelemetrySpec(kind="counters").events
    assert TelemetrySpec(kind="trace").events


# ---------------------------------------------------------------------------
# the trace CLI's offline validator
# ---------------------------------------------------------------------------

def _valid_report_dict():
    return {"spec": {"kind": "counters", "profiler": False},
            "executor": "scan", "rounds": 4,
            "counters": {"rounds": 4, "rounds_per_phase": [4],
                         "proposed": 24,
                         "accepted": 12, "killed": 12},
            "events": [], "ssp": None}


def test_check_report_catches_broken_identities():
    assert check_report(report_from_json(_valid_report_dict())) is None

    unbalanced = _valid_report_dict()
    unbalanced["counters"]["killed"] = 13
    assert "ledger" in check_report(report_from_json(unbalanced))

    phases = _valid_report_dict()
    phases["counters"]["rounds_per_phase"] = [3]
    assert "phase" in check_report(report_from_json(phases))

    negative = _valid_report_dict()
    negative["counters"]["accepted"] = -1
    negative["counters"]["killed"] = 25
    assert "negative" in check_report(report_from_json(negative))

    crossing = _valid_report_dict()
    crossing["spec"] = {"kind": "trace", "profiler": False}
    crossing["events"] = [
        {"name": "a", "ph": "X", "ts": 0.0, "dur": 10.0, "args": {}},
        {"name": "b", "ph": "X", "ts": 5.0, "dur": 10.0, "args": {}}]
    assert check_report(report_from_json(crossing)) is not None


def test_extract_report_dicts_walks_nested_artifacts():
    rep = _valid_report_dict()
    artifact = {"engine": "lasso", "run_report": rep,
                "ssp": {"2": {"telemetry": rep}},
                "rows": [{"telemetry": rep}]}
    found = extract_report_dicts(artifact)
    assert len(found) == 3
    assert extract_report_dicts({"no": "reports"}) == []
    # a bare to_json() dump is itself the report
    assert extract_report_dicts(rep) == [rep]


# ---------------------------------------------------------------------------
# span ids, the active Recorder, and what it records besides the engine
# ---------------------------------------------------------------------------

def test_spans_carry_ids_and_parents(tmp_path):
    rec = Recorder()
    with rec.span("outer"):
        rec.instant("tick")
        with rec.span("inner"):
            pass
        with rec.span("second"):
            pass
    ev = {e["name"]: e for e in rec.to_json_events()}
    outer, inner, second = ev["outer"], ev["inner"], ev["second"]
    assert len({outer["id"], inner["id"], second["id"]}) == 3
    assert outer["parent"] is None
    assert inner["parent"] == second["parent"] == outer["id"]
    assert ev["tick"]["parent"] == outer["id"]
    assert validate_spans(rec.to_json_events()) is None
    # both exporters carry the new fields
    doc = chrome_trace(rec.to_json_events())
    args = {e["name"]: e["args"] for e in doc["traceEvents"]}
    assert args["inner"]["parent"] == outer["id"]
    assert args["outer"]["id"] == outer["id"]
    lines = open(rec.write_jsonl(str(tmp_path / "e.jsonl"))).readlines()
    assert {json.loads(x)["name"]: json.loads(x).get("parent")
            for x in lines}["inner"] == outer["id"]


def test_validate_spans_checks_parents():
    outer = {"name": "a", "ph": "X", "ts": 0.0, "dur": 10.0, "id": 0,
             "parent": None, "args": {}}
    inside = {"name": "b", "ph": "X", "ts": 2.0, "dur": 3.0, "id": 1,
              "parent": 0, "args": {}}
    assert validate_spans([outer, inside]) is None
    # a parent outside the log (an excerpt of a longer recording) is fine
    assert validate_spans([dict(inside, parent=7)]) is None
    # a span outside its parent, and an id used twice, are not
    later = {"name": "c", "ph": "X", "ts": 12.0, "dur": 1.0, "id": 2,
             "parent": 0, "args": {}}
    assert "outside its parent" in validate_spans([outer, inside, later])
    assert "twice" in validate_spans([outer, dict(inside, id=0)])


def test_engine_spans_fall_back_to_the_active_recorder(mesh, rng):
    from repro.obs import active, recording
    X, y, _ = lasso.synthetic_correlated(rng, n=40, J=20, k_true=3)
    cfg = lasso.LassoConfig(num_features=20, lam=0.02, block_size=4,
                            num_candidates=8, rho=0.3)
    eng = lasso.make_engine(cfg, mesh)
    assert active() is None
    rec = Recorder()
    with recording(rec):
        assert active() is rec
        data = eng.shard_data({"X": jnp.asarray(X), "y": jnp.asarray(y)})
        state = eng.init_state(jax.random.key(0), y=y)
        rep = eng.execute(state, data, jax.random.key(1),
                          _plan("scan", 4, False))
    assert active() is None
    assert rep.telemetry is None             # no spec: no report
    ev = rec.to_json_events()
    assert validate_spans(ev) is None
    spans = {e["name"]: e for e in ev if e["ph"] == "X"}
    for child, parent in [("app.init_state", "init_state"),
                          ("place_state", "init_state"),
                          ("execute.resolve", "execute"),
                          ("scan", "execute"),
                          ("scan.program", "scan"),
                          ("scan.replicate", "scan"),
                          ("scan.call", "scan")]:
        assert spans[child]["parent"] == spans[parent]["id"], child
    assert spans["shard_data"]["parent"] is None
    assert any(e["name"] == "cache_miss" for e in ev)
    # the compile of the round program ran inside the jitted call
    calls = [e for e in ev if e["name"].startswith("jit.")]
    assert {"jit.trace", "jit.lower", "jit.compile"} <= \
        {e["name"] for e in calls}
    assert any(e["parent"] == spans["scan.call"]["id"] for e in calls)
    # nothing records once no Recorder is active
    n = len(rec.to_json_events())
    eng.execute(rep.state, data, jax.random.key(1), _plan("scan", 8, False),
                carry=rep.carry)
    assert len(rec.to_json_events()) == n


def test_trace_execute_reports_its_own_events(mesh, rng):
    from repro.obs import recording
    eng, data, y = _lasso_engine(rng, mesh)
    state = eng.init_state(jax.random.key(0), y=y)
    rec = Recorder()
    with recording(rec):
        with rec.span("outer"):
            rep = eng.execute(state, data, jax.random.key(1),
                              _plan("scan", 4, TelemetrySpec(kind="trace")))
    mine = rep.telemetry.events
    names = [e["name"] for e in mine]
    assert "execute" in names and "outer" not in names
    # the call's events are in the active Recorder's log, inside "outer"
    log = rec.to_json_events()
    ids = {e["id"] for e in log if e["ph"] == "X"}
    assert {e["id"] for e in mine if e["ph"] == "X"} <= ids
    outer = next(e for e in log if e["name"] == "outer")
    execute = next(e for e in mine if e["name"] == "execute")
    assert execute["parent"] == outer["id"]
    assert validate_spans(mine) is None and validate_spans(log) is None


def test_jit_and_gc_spans_while_active():
    import gc
    from repro.obs import recording
    rec = Recorder()
    scale = np.float32(np.random.default_rng().random())

    @jax.jit
    def fresh(x):                       # a program no test compiled yet
        return jnp.sin(x) * scale + x.size

    with recording(rec):
        fresh(jnp.arange(7.0)).block_until_ready()
        gc.collect()
    after = len(rec.to_json_events())
    gc.collect()                        # not active any more: not recorded
    ev = rec.to_json_events()
    assert len(ev) == after
    jit = [e for e in ev if e["name"].startswith("jit.")]
    assert [e["name"] for e in sorted(jit, key=lambda e: e["ts"])][:1] \
        == ["jit.trace"]
    assert {"jit.trace", "jit.lower", "jit.compile"} <= \
        {e["name"] for e in jit}
    assert all(e["dur"] >= 0 for e in jit)
    assert any("fresh" in e["args"]["fun"] for e in jit)
    full = [e for e in ev if e["name"] == "gc"
            and e["args"]["generation"] == 2]
    assert full and "collected" in full[-1]["args"]
    assert validate_spans(ev) is None


def test_span_ended_nests_what_it_covers():
    rec = Recorder()
    with rec.span("call"):
        with rec.span("gc"):
            pass
        ev = rec.span_ended("jit.trace", 10.0)     # longer than "call"
    log = rec.to_json_events()
    call = next(e for e in log if e["name"] == "call")
    assert ev["ts"] == call["ts"]              # clamped into its parent
    assert ev["parent"] == call["id"]
    assert validate_spans(log) is None


# ---------------------------------------------------------------------------
# the RunReport reads the device only when asked
# ---------------------------------------------------------------------------

def test_runreport_keeps_counters_on_the_device(mesh, rng):
    eng, data, y = _lasso_engine(rng, mesh)
    rep = eng.execute(eng.init_state(jax.random.key(0), y=y), data,
                      jax.random.key(1),
                      _plan("scan", 4, TelemetrySpec(kind="counters")))
    report = rep.telemetry
    assert report.rounds == 4                      # the host's index
    leaves = jax.tree_util.tree_leaves(report.device_counters)
    assert leaves and all(isinstance(x, jax.Array) for x in leaves)
    c = report.counters                            # the first read
    assert report.device_counters is None
    assert c["rounds"] == 4 and report.counters is c
    assert report.to_json()["counters"] == c


def test_wide_counts_do_not_wrap():
    from repro.obs.counters import _add_wide, _wide
    c = jnp.zeros((2,), jnp.int32)
    step = (1 << 30) - 1
    for _ in range(5):
        c = _add_wide(c, step)
    assert _wide(c) == 5 * step > 2 ** 31


# ---------------------------------------------------------------------------
# visited / updated: what LDA's Gibbs scans step over and sample
# ---------------------------------------------------------------------------

def test_lda_one_worker_samples_every_token_it_visits(mesh, rng):
    cfg = lda.LDAConfig(vocab=30, num_topics=4, num_workers=1,
                        tokens_per_worker=200, docs_per_worker=5)
    words, docs, z0 = lda.synthetic_corpus(rng, cfg, true_topics=4)
    words[::7] = -1                                 # padding slots
    valid = int((words >= 0).sum())
    eng = lda.make_engine(cfg, mesh)
    data = eng.shard_data({"words": jnp.asarray(words),
                           "docs": jnp.asarray(docs)})
    for executor in EXECUTORS:
        state = eng.init_state(jax.random.key(0), words=words, docs=docs,
                               z0=z0)
        c = eng.execute(state, data, jax.random.key(1),
                        _plan(executor, 6, TelemetrySpec(kind="counters"))
                        ).telemetry.counters
        assert c["visited"] == c["updated"] == 6 * valid, executor


def test_lda_four_workers_sample_a_quarter_of_what_they_visit():
    """Four forced host devices, one rotation (4 rounds): every worker
    scans all its tokens each round and samples those of its current
    block, so over a rotation ``updated`` is a quarter of ``visited`` —
    and the counters leave the state bit-identical."""
    import os
    import subprocess
    import sys
    import textwrap
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = textwrap.dedent("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.apps import lda
        from repro.core import ExecutionPlan, worker_mesh
        from repro.obs import TelemetrySpec
        cfg = lda.LDAConfig(vocab=40, num_topics=4, num_workers=4,
                            tokens_per_worker=64, docs_per_worker=4)
        words, docs, z0 = lda.synthetic_corpus(np.random.default_rng(0),
                                               cfg, true_topics=4)
        words[::5] = -1
        eng = lda.make_engine(cfg, worker_mesh(4))
        data = eng.shard_data({"words": jnp.asarray(words),
                               "docs": jnp.asarray(docs)})
        out = {}
        for tel in (False, TelemetrySpec(kind="counters")):
            st = eng.init_state(jax.random.key(0), words=words, docs=docs,
                                z0=z0)
            rep = eng.execute(st, data, jax.random.key(1),
                              ExecutionPlan(executor="scan", rounds=8,
                                            telemetry=tel))
            out[bool(tel)] = rep
        c = out[True].telemetry.counters
        valid = int((words >= 0).sum())
        assert c["visited"] == 8 * valid, c
        assert 4 * c["updated"] == c["visited"], c
        for k in out[False].state:
            a = np.asarray(out[False].state[k])
            b = np.asarray(out[True].state[k])
            assert (a == b).all(), k
        print("OK", c["visited"], c["updated"])
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=540)
    assert p.returncode == 0, p.stderr[-4000:]
    assert p.stdout.startswith("OK")


# ---------------------------------------------------------------------------
# visited / updated: the entries MF's sweeps step over, the coordinates
# they update
# ---------------------------------------------------------------------------

def test_mf_one_worker_counts_entries_and_coordinates(mesh, rng):
    """An H-phase updates M coordinates a rank, a W-phase the valid rows;
    every round visits all of the shard's entries, padding included."""
    A, mask = mf.synthetic_ratings(rng, 20, 12, true_rank=3, density=0.5)
    mask[[2, 7]] = 0                                # two empty rows
    cfg = mf.MFConfig(num_rows=20, num_cols=12, rank=3, ranks_per_round=2)
    eng = mf.make_engine(cfg, mesh)
    data = eng.shard_data(eng.app.layout(A * mask, mask))
    entries = int(data["row"].shape[0])
    assert entries >= mask.sum()
    for executor in EXECUTORS:
        state = eng.init_state(jax.random.key(0), data=data)
        c = eng.execute(state, data, jax.random.key(1),
                        _plan(executor, 6, TelemetrySpec(kind="counters"))
                        ).telemetry.counters
        assert c["visited"] == 6 * entries, executor
        assert c["updated"] == 2 * (3 * 12 + 3 * 18), executor


def test_mf_four_workers_count_and_stay_bit_identical():
    """Four forced host devices: the counters add every worker's entries
    and the valid rows of all shards, and leave the state bit-identical
    to an uncounted run."""
    import os
    import subprocess
    import sys
    import textwrap
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = textwrap.dedent("""
        import numpy as np, jax
        from repro.apps import mf
        from repro.core import ExecutionPlan, worker_mesh
        from repro.obs import TelemetrySpec
        A, mask = mf.synthetic_ratings(np.random.default_rng(0), 32, 10,
                                       true_rank=3, density=0.4)
        mask[[1, 17]] = 0
        cfg = mf.MFConfig(num_rows=32, num_cols=10, rank=4)
        eng = mf.make_engine(cfg, worker_mesh(4))
        data = eng.shard_data(eng.app.layout(A * mask, mask))
        out = {}
        for tel in (False, TelemetrySpec(kind="counters")):
            st = eng.init_state(jax.random.key(0), data=data)
            out[bool(tel)] = eng.execute(
                st, data, jax.random.key(1),
                ExecutionPlan(executor="scan", rounds=4, telemetry=tel))
        c = out[True].telemetry.counters
        entries = int(data["row"].shape[0])
        assert entries % 4 == 0 and entries >= mask.sum()
        assert c["visited"] == 4 * entries, c
        assert c["updated"] == 2 * 10 + 2 * 30, c
        for k in out[False].state:
            a = np.asarray(out[False].state[k])
            b = np.asarray(out[True].state[k])
            assert (a == b).all(), k
        print("OK", c["visited"], c["updated"])
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=540)
    assert p.returncode == 0, p.stderr[-4000:]
    assert p.stdout.startswith("OK")


def test_mf_layout_is_a_span_of_the_active_recorder(rng):
    from repro.obs import recording
    A, mask = mf.synthetic_ratings(rng, 8, 6, true_rank=2)
    rec = Recorder()
    with recording(rec):
        data = mf.StradsMF(mf.MFConfig(num_rows=8, num_cols=6, rank=2),
                           num_workers=2).layout(A, mask)
    spans = [e for e in rec.to_json_events() if e["name"] == "mf.layout"]
    assert len(spans) == 1 and spans[0]["ph"] == "X"
    assert spans[0]["args"]["ratings"] == int(mask.sum())
    # a sweep's contraction steps by row and by column, over both shards
    from repro.kernels.entry_sweep import sweep_steps
    for arg, key in (("row_steps", "row"), ("col_steps", "col")):
        assert spans[0]["args"][arg] == sum(
            sweep_steps(s) for s in data[key].reshape(2, -1)) == 2
    mf.layout(*np.nonzero(mask), A[mask > 0], 8)   # no recorder: no error


def test_telemetry_cost_script_runs_tiny():
    """``benchmarks/telemetry_cost.py`` on the CPU at the cell's tiny
    size: its set-up spans cover the set-up, and a traced window's device
    counter ``updated`` equals the tokens the host counts as sampled.
    (CPU timings: no cost is read here.)"""
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks",
                                      "telemetry_cost.py"),
         "--tiny", "--windows", "1", "--seconds", "0.2"],
        capture_output=True, text=True, env=env, timeout=540)
    assert p.returncode == 0, p.stderr[-4000:]
    lines = [json.loads(x) for x in p.stdout.strip().splitlines()]
    setup, windows, summary = lines[0], lines[1:-1], lines[-1]
    assert setup["platform"] == "cpu"
    assert set(setup["spans_s"]) == {"import", "backend_start", "setup",
                                     "warm"}
    assert setup["covered_share"] >= 0.95
    assert [w["mode"] for w in windows] == ["off", "trace"]
    traced = windows[1]
    assert traced["updated"] == traced["visited"] == traced["updates"] > 0
    assert traced["events"] > 0
    assert set(summary) >= {"updates_per_s_off", "updates_per_s_trace",
                            "trace_cost"}
