"""Device time by round phase, and idle gaps by the program's own spans
(``bench/phases.py``), on a synthetic compiled text and trace and on a
small trace recorded on a TPU v5e.  The numbers read here are counts of
recorded events, not measurements."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import phases, run, trace_reduce  # noqa: E402

TESTDATA = os.path.join(ROOT, "bench", "testdata")

TEXT = """HloModule jit_scanned, is_scheduled=true

%fused.1 (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  ROOT %add.9 = f32[4]{0} add(%p0, %p0), metadata={op_name="jit(scanned)/propose/add"}
}

%body.3 (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]{0}) parameter(0)
  %check.1 = pred[] compare(%i, %n), direction=LT
  ROOT %tuple.7 = (s32[], f32[4]{0}) tuple(%i, %x), metadata={op_name="jit(scanned)/while/body/push/tuple"}
}

ENTRY %main.9 (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %fusion.1 = f32[4]{0} fusion(%a), kind=kLoop, calls=%fused.1, metadata={op_name="jit(scanned)/propose/add" source_file="x.py" source_line=3}
  %while.2 = (s32[], f32[4]{0}) while(%init), condition=%cond.4, body=%body.3, metadata={op_name="jit(scanned)/push/while"}
  %collective-permute.3 = f32[4]{0} collective-permute(%a), channel_id=1, source_target_pairs={{0,1},{1,0}}, metadata={op_name="jit(scanned)/push/exchange/ppermute"}
  %all-reduce.4 = f32[4]{0} all-reduce(%a), to_apply=%sum.5, metadata={op_name="jit(scanned)/aggregate/psum"}
  %copy.5 = f32[4]{0} copy(%a)
  ROOT %tuple.6 = (f32[4]{0}) tuple(%copy.5)
}
"""

PHASES = ("propose", "schedule_stats", "schedule", "push", "exchange",
          "aggregate", "pull", "sched_update")


def test_the_program_declares_the_phases():
    assert phases.program_phases() == PHASES


def test_phases_of_names_the_innermost_phase():
    of = phases.phases_of(TEXT, PHASES)
    assert phases.module_name(TEXT) == "jit_scanned"
    assert of["%fusion.1 = f32[4]"] == "propose"
    assert of["%while.2 = (tuple)"] == "push"
    assert of["%collective-permute.3 = f32[4]"] == "exchange"
    assert of["%all-reduce.4 = f32[4]"] == "aggregate"
    # the compiler's own check in the loop body belongs to the loop's phase
    assert of["%check.1 = pred[]"] == "push"
    # under no phase: left out
    assert "%copy.5 = f32[4]" not in of
    assert phases.phases_of(TEXT, ()) == {}


def test_busy_by_phase_counts_the_program_alone():
    ops = {0: [
        ("%fusion.1 = f32[4]{0} fusion(f32[4]{0} %a)", 1.0, 1.5),
        ("%while.2 = (s32[], f32[4]{0}) while(%init)", 1.5, 3.5),
        ("%check.1 = pred[] compare(%i, %n)", 2.0, 2.5),
        ("%collective-permute.3 = f32[4]{0} collective-permute(%a)",
         3.5, 3.75),
        ("%copy.5 = f32[4]{0} copy(%a)", 3.75, 4.0),
        # another program that happens to name an instruction alike
        ("%fusion.1 = f32[4]{0} fusion(f32[4]{0} %b)", 5.0, 6.0)]}
    programs = {0: [("jit_scanned(77)", 1.0, 4.0),
                    ("jit_other(5)", 5.0, 6.0)]}
    spans = [(run.SPAN_DISPATCH, 0.0, 1.0), (run.SPAN_BLOCK, 1.0, 7.0)]
    tr = trace_reduce.Trace(ops, spans, programs=programs)
    by = phases.busy_by_phase(tr, TEXT, PHASES)
    assert by == pytest.approx({"propose": 0.5, "push": 2.0,
                                "exchange": 0.25, None: 0.25})
    assert phases.share(by, ("propose",)) == pytest.approx(100 * 0.5 / 3)
    # a commit whose program names no phases reads nothing
    assert phases.busy_by_phase(tr, TEXT, ()) == {}
    assert phases.share({None: 1.0}, ("push",)) is None


def test_label_innermost():
    spans = [("bench.dispatch", 0.0, 10.0), ("execute", 1.0, 9.0),
             ("scan.replicate", 2.0, 4.0), ("gc", 8.9, 9.5)]
    # a gap inside scan.replicate: every span covers it, the innermost
    # names it
    assert phases.label_innermost(spans, 2.5, 3.5) == "scan.replicate"
    # a gap mostly in execute and partly in scan.replicate
    assert phases.label_innermost(spans, 3.0, 6.0) == "execute"
    # no span covers more than half: the one that covers most
    assert phases.label_innermost(spans[3:], 9.2, 10.5) == "gc"
    assert phases.label_innermost(spans, 11.0, 12.0) == "outside any span"


def test_recorded_lda_trace_maps_its_busy_time_to_phases():
    """Two chunks of the tiny LDA cell (one worker, 512 tokens, K = 64,
    one round a chunk) under ``TelemetrySpec(kind="trace",
    profiler=True)``, recorded on a TPU v5e with the harness's spans
    around each chunk, and the compiled text of the program it ran
    (stack-frame tables and source locations stripped): 95 % or more of
    the program's device time maps to a phase, nearly all of it to the
    Gibbs scan in ``push``, and the program's host spans label idle gaps
    that the harness's spans alone call ``bench.dispatch``."""
    from jax.profiler import ProfileData
    program_spans = ("execute", "execute.resolve", "scan", "scan.program",
                     "scan.replicate", "scan.call")
    tr = trace_reduce.from_profile(
        ProfileData.from_file(os.path.join(TESTDATA,
                                           "lda_tiny_v5e.xplane.pb")),
        1, run.SPANS + program_spans)
    with open(os.path.join(TESTDATA, "lda_tiny_v5e.hlo.txt")) as f:
        text = f.read()
    by = phases.busy_by_phase(tr, text)
    total = sum(by.values())
    assert total > 0
    assert 1 - by.get(None, 0.0) / total >= 0.95
    assert phases.share(by, ("push",)) > 95
    assert set(by) - {None} <= set(PHASES)
    # both chunks of the program are in the window
    runs = [n for n, _, _ in tr.programs[0] if n.startswith("jit_scanned(")]
    assert len(runs) == 2
    assert {s[0] for s in tr.spans} >= set(program_spans)
    gaps = tr.idle_gaps()
    inner = [phases.label_innermost(tr.spans, a, b) for a, b in gaps]
    outer = [tr.label(a, b) for a, b in gaps]
    assert "scan.replicate" in inner and "scan.replicate" not in outer


def test_every_phase_reaches_the_compiled_program():
    """The round programs of lasso (dynamic priority: stats, ρ-filter,
    priority update) and LDA, compiled on the CPU, name every phase
    ``repro.obs.PHASES`` declares in their ``op_name`` metadata."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.apps import lasso, lda
    from repro.core import single_device_mesh
    mesh = single_device_mesh()
    r = np.random.default_rng(0)
    X, y, _ = lasso.synthetic_correlated(r, n=40, J=20, k_true=3)
    eng = lasso.make_engine(lasso.LassoConfig(
        num_features=20, lam=0.02, block_size=4, num_candidates=8,
        rho=0.3), mesh)
    data = eng.shard_data({"X": jnp.asarray(X), "y": jnp.asarray(y)})
    state = eng.init_state(jax.random.key(0), y=y)
    texts = [eng.scanned_fn(2).lower(
        state, data, jax.random.key(1), jnp.int32(0),
        eng.init_sched_carry(), None).compile().as_text()]
    cfg = lda.LDAConfig(vocab=30, num_topics=4, num_workers=1,
                        tokens_per_worker=64, docs_per_worker=5)
    words, docs, z0 = lda.synthetic_corpus(r, cfg, true_topics=4)
    eng = lda.make_engine(cfg, mesh)
    data = eng.shard_data({"words": jnp.asarray(words),
                           "docs": jnp.asarray(docs)})
    state = eng.init_state(jax.random.key(0), words=words, docs=docs,
                           z0=z0)
    texts.append(eng.scanned_fn(1).lower(
        state, data, jax.random.key(1), jnp.int32(0), None,
        None).compile().as_text())
    named = set()
    for text in texts:
        named |= set(phases.phases_of(text, PHASES).values())
    assert named == set(PHASES)
