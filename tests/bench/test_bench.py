"""The on-chip benchmark (``bench/``) on the CPU at tiny sizes.

The harness refuses the CPU, so these tests drive its pieces in-process:
every cell that ``BENCHMARK.json`` lists, at the tiny stand-in its
workload file gives (``tiny``), through set-up and window, the plain
reference, the control and every fault its app adapter lists, the FLOP
counts, and the trace reduction on a small trace recorded on a TPU v5e.
A cell that asks for more than one chip runs on as many forced host
devices, in a process of its own.  The numbers read here are CPU
numbers; none is a device metric.
"""
import json
import os
import shutil
import subprocess
import sys
import textwrap

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import control, faults, run, trace_reduce  # noqa: E402
from bench.seeds import jax_key  # noqa: E402

SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
SEED = 2 ** 33 + 11


def cells(spec=SPEC):
    return [w["name"] for w in spec["workloads"]]


def tiny(name):
    """The cell and its configuration with the workload file's ``tiny``
    stand-in laid over them."""
    cell, config = run.load_cell(name)
    small = cell["tiny"]
    return (dict(cell, traffic=dict(cell["traffic"], **small["traffic"])),
            dict(config, **small["config"]))


def cell_faults(spec=SPEC):
    out = []
    for name in cells(spec):
        cell, config = run.load_cell(name)
        out += [(name, f) for f in run.app_module(config["app"])
                .faults_for(cell)]
    return out


def _env(**extra):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **extra)
    return env


def _runs(name, cell, config, whats=("program",), trace=False,
          seconds=0.05):
    """Runs of the tiny cell, one for each of ``whats``: ``program`` or
    ``fault:<name>``.  Where the cell asks for more than one chip they
    run on as many forced host devices, in one process of their own."""
    chips = int(cell["chips"])
    if chips == 1:
        out = []
        kw = dict(seed=SEED, seconds=seconds, trace=trace, spec=SPEC,
                  on_chip=False)
        for what in whats:
            if what == "program":
                out.append(run.run_cell(name, cell, config, **kw))
                continue
            with faults.planted(config["app"], what.split(":", 1)[1]):
                out.append(run.run_cell(name, cell, config, **kw))
        return out
    script = textwrap.dedent(f"""
        import json, sys
        sys.path[:0] = [{ROOT!r}]
        from bench import faults, run
        name, whats = {name!r}, {list(whats)!r}
        cell, config, spec = json.loads({json.dumps([cell, config, SPEC])!r})
        kw = dict(seed={SEED}, seconds={seconds}, trace={trace}, spec=spec,
                  on_chip=False)
        out = []
        for what in whats:
            if what == "program":
                out.append(run.run_cell(name, cell, config, **kw))
                continue
            with faults.planted(config["app"], what.split(":", 1)[1]):
                out.append(run.run_cell(name, cell, config, **kw))
        print(json.dumps(out))
    """)
    p = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        cwd=ROOT, timeout=600, env=_env(
            PYTHONPATH=os.path.join(ROOT, "src"),
            XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}"))
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def _drive(name, cell, config, what="program", **kw):
    return _runs(name, cell, config, (what,), **kw)[0]


# -- the files a cell is made of ----------------------------------------------

def test_every_cell_names_files_that_exist():
    for c in SPEC["configs"]:
        path = os.path.join(ROOT, c["file"])
        assert json.load(open(path))["name"] == c["name"]
    for w in SPEC["workloads"]:
        cell, config = run.load_cell(w["name"])
        assert cell["config"] == w["config"] == config["name"]
        assert cell["chips"] == w["chips"]
        assert set(cell["tiny"]) == {"config", "traffic"}
        app = run.app_module(config["app"])
        assert callable(app.setup) and callable(app.control)
        assert set(app.faults_for(cell)) <= set(app.FAULTS)
    for m in SPEC["per_layer"]:
        assert callable(run.metric_reader(m["name"]).read)
        assert set(m.get("workloads", [])) <= set(cells())
    assert [m["name"] for m in SPEC["end_to_end"]] == [
        "updates_per_s", "setup_s"]


def test_a_new_cell_is_new_files_only(tmp_path, monkeypatch):
    """A cell added as one workload file and one entry of
    ``BENCHMARK.json`` is found by name and driven by these tests'
    functions, with no file of ``bench/`` edited."""
    bench = tmp_path / "bench"
    shutil.copytree(os.path.join(ROOT, "bench"), bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    old = cells()[0]
    cell = json.load(open(bench / "workloads" / f"{old}.json"))
    cell["tiny"]["config"]["num_topics"] = 32
    (bench / "workloads" / "new-cell.1chip.json").write_text(
        json.dumps(cell))
    spec = json.loads(json.dumps(SPEC))
    spec["workloads"].append(dict(spec["workloads"][0], name="new-cell.1chip"))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    monkeypatch.setattr(run, "BENCH", str(bench))
    assert "new-cell.1chip" in cells(spec)
    assert ("new-cell.1chip", "altered") in cell_faults(spec)
    got, config = tiny("new-cell.1chip")
    assert config["num_topics"] == 32
    res = run.run_cell("new-cell.1chip", got, config, seed=SEED,
                       seconds=0.01, trace=False, spec=spec, on_chip=False)
    assert res["correct"] is True, res["checks"]
    assert list(res["metrics"]) == ["updates_per_s", "setup_s"]


def test_run_refuses_the_cpu():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", cells()[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=_env(),
        cwd=ROOT, timeout=300)
    assert p.returncode != 0
    assert "cpu" in p.stderr
    assert p.stdout.strip() == ""


def test_unknown_device_kind_is_an_error():
    with pytest.raises(run.BenchError, match="no peaks"):
        run.peaks_for("TPU v0 imaginary")
    assert run.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_seeds_keep_all_their_bits():
    import jax
    a, b = jax_key(5), jax_key(2 ** 33 + 5)
    assert not np.array_equal(jax.random.key_data(a),
                              jax.random.key_data(b))
    assert np.array_equal(jax.random.key_data(jax_key(5)),
                          jax.random.key_data(a))


# -- a run of each cell ---------------------------------------------------------

@pytest.mark.parametrize("name", cells())
def test_cell_runs_and_is_correct(name):
    cell, config = tiny(name)
    res = _drive(name, cell, config)
    assert set(res) >= {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] == len(res["checks"])
    want = [m["name"] for m in SPEC["end_to_end"]
            if name in m.get("workloads", [name])]
    assert list(res["metrics"]) == want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["device"]["platform"] == "cpu"
    assert res["device"]["count"] == cell["chips"]
    assert res["info"]["recompiles_in_window"] == 0
    assert res["info"]["chunks"] >= 1


def test_traced_run_reports_per_layer_metrics():
    for name in cells():
        cell, config = tiny(name)
        res = _drive(name, cell, config, trace=True)
        assert res["correct"] is True
        # the CPU has no device plane and no peak: the trace readers stay
        # silent, and only what the host counts is read
        assert set(res["metrics"]) == {"compile_s", "recompiles_in_window"}
        assert res["metrics"]["recompiles_in_window"]["value"] == 0
        assert res["info"]["chunks"] == cell["trace"]["chunks"]
        assert {"busy_s", "window_s"} <= set(res["device"])
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


# -- correct: the control and the faults --------------------------------------

@pytest.mark.parametrize("name", cells())
def test_control_is_not_correct(name):
    cell, config = tiny(name)
    got = control.readings(name, "control", SEED, seconds=0.0,
                           window_rounds=4 * cell["traffic"]
                           ["rounds_per_chunk"], on_chip=False,
                           cell=cell, config=config)
    assert any(v["limit"] is not None and v["value"] > v["limit"]
               for v in got.values()), got


@pytest.mark.parametrize("name,fault", cell_faults())
def test_planted_fault_is_not_correct(name, fault):
    cell, config = tiny(name)
    res = _drive(name, cell, config, what=f"fault:{fault}", seconds=0.01)
    assert res["correct"] is False, res["checks"]
    assert res["failed"] >= 1


def test_faults_are_undone():
    from repro.apps.lda import StradsLDA
    before = StradsLDA.push
    with faults.planted("lda", "altered"):
        assert StradsLDA.push is not before
    assert StradsLDA.push is before
    with pytest.raises(ValueError, match="cannot have"):
        with faults.planted("lda", "no_such_fault"):
            pass


def test_four_worker_rotation_and_its_exchange_fault():
    """The LDA cell as four workers on four forced host devices, a
    rotation a chunk: correct as it is, not correct without the
    rotation's exchange, which only a cell over chips can have."""
    name = next(n for n in cells()
                if run.load_cell(n)[1]["app"] == "lda")
    cell, config = tiny(name)
    cell = dict(cell, chips=4, traffic=dict(cell["traffic"],
                                            rounds_per_chunk=4))
    config = dict(config, tokens_per_worker=1024, docs_per_worker=8)
    assert "exchange" in run.app_module("lda").faults_for(cell)
    assert "exchange" not in run.app_module("lda").faults_for(
        dict(cell, chips=1))
    res, fault = _runs(name, cell, config, ("program", "fault:exchange"),
                       seconds=0.01)
    assert res["correct"] is True, res["checks"]
    assert res["device"]["count"] == 4
    assert fault["correct"] is False, fault["checks"]


# -- costs from shapes ----------------------------------------------------------

def test_kernel_costs_match_hand_counts():
    from bench.apps import lda
    # a round at K = 1,000 over 2**16 tokens on one worker samples every
    # token: 6 · 1,000 · 65,536 = 393,216,000 FLOPs; over 4 workers a
    # round samples a quarter of them
    assert lda.round_flops(1000, 65536, 1) == 393_216_000.0
    assert lda.round_flops(1000, 4 * 65536, 4) == 393_216_000.0


def test_round_mfu_reads_the_trace_window():
    from types import SimpleNamespace
    from bench.metrics import round_mfu
    ops = {0: [("fusion.1", 0.0, 1.5)]}
    spans = [(run.SPAN_DISPATCH, 0.0, 0.5), (run.SPAN_BLOCK, 0.5, 2.0)]
    job = SimpleNamespace(round_flops=lambda: 197e12 * 0.5)
    ctx = SimpleNamespace(peaks=run.peaks_for("TPU v5 lite"), rounds=2,
                          chips=1, job=job,
                          trace=trace_reduce.Trace(ops, spans))
    # two rounds of half a second's peak work in a 2 s window: 50 %
    assert round_mfu.read(ctx) == pytest.approx(50.0)
    ctx.trace = trace_reduce.Trace(ops, spans, dropped_at=1.0)
    assert round_mfu.read(ctx) is None
    ctx.trace, ctx.peaks = trace_reduce.Trace(ops, spans), None
    assert round_mfu.read(ctx) is None


# -- the reduction from trace to numbers ----------------------------------------

def test_interval_arithmetic():
    u = trace_reduce.union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert u == [(0, 3), (5, 9)]
    assert trace_reduce.total(u) == 7
    assert trace_reduce.subtract([(0, 10)], u) == [(3, 5), (9, 10)]
    assert trace_reduce.clip(u, 2, 6) == [(2, 3), (5, 6)]


def test_trace_with_two_devices_and_a_collective():
    ops = {0: [("fusion.1", 1.0, 2.0), ("collective-permute.3", 2.0, 3.0),
               ("fusion.2", 2.5, 4.0), ("_gram_kernel", 5.0, 6.0)],
           1: [("fusion.1", 1.0, 1.5), ("all-reduce.7", 1.5, 2.5)]}
    spans = [(run.SPAN_DISPATCH, 0.0, 1.0), (run.SPAN_BLOCK, 1.0, 8.0)]
    tr = trace_reduce.Trace(ops, spans)
    assert tr.window_s == 8.0
    assert tr.busy_s == pytest.approx((4.0 + 1.5) / 2)
    assert tr.idle_share() == pytest.approx(1 - 2.75 / 8)
    # device 0: 2.0–2.5 exposed; device 1: 1.5–2.5 exposed
    assert tr.collective_exposed_s() == pytest.approx((0.5 + 1.0) / 2)
    assert tr.op_time("gram_kernel") == (0.5, 0.5)
    gaps = tr.breakdown()["idle_gaps"]
    assert gaps[0] == [run.SPAN_BLOCK, 2.0]          # 6.0 → 8.0
    assert [run.SPAN_DISPATCH, 1.0] in gaps


def test_a_dropped_trace_ends_its_window():
    ops = {0: [("fusion.1", 1.0, 2.0)]}
    spans = [(run.SPAN_DISPATCH, 0.0, 1.0), (run.SPAN_BLOCK, 1.0, 8.0)]
    tr = trace_reduce.Trace(ops, spans, dropped_at=3.0)
    assert tr.window_s == 3.0
    assert tr.idle_share() == pytest.approx(2.0 / 3.0)


def test_a_program_covers_its_lost_operations():
    """A device can lose operation events without a marker; the program
    they ran in still counts as busy."""
    spans = [(run.SPAN_DISPATCH, 0.0, 1.0), (run.SPAN_BLOCK, 1.0, 4.0)]
    # the loop's own event (1.0–3.0) was lost: only its first body op is
    # left
    ops = {0: [("fusion.1", 1.0, 1.5)]}
    assert trace_reduce.Trace(ops, spans).busy_s == pytest.approx(0.5)
    tr = trace_reduce.Trace(ops, spans,
                            programs={0: [("jit_scan", 1.0, 3.0)]})
    assert tr.busy_s == pytest.approx(2.0)
    assert tr.idle_share() == pytest.approx(0.5)


TESTDATA = os.path.join(ROOT, "bench", "testdata")


def test_recorded_chip_trace():
    """Three chunks of a tiny lasso run (1,024 × 4,096, U = 16 of
    U′ = 64, 4 rounds a chunk) recorded on a TPU v5e: the reduction
    agrees with a count over a 10 ns timeline of the same events."""
    from jax.profiler import ProfileData
    path = os.path.join(TESTDATA, "lasso_tiny_v5e.xplane.pb")
    tr = trace_reduce.from_profile(ProfileData.from_file(path), 1,
                                   run.SPANS)
    want = json.load(open(os.path.join(TESTDATA, "lasso_tiny_v5e.json")))
    assert tr.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert tr.busy_s == pytest.approx(want["busy_s"], rel=1e-3)
    assert 0 < tr.busy_s < tr.window_s
    secs, calls = tr.op_time(want["kernel_pattern"])
    assert calls == want["kernel_calls"] == 12
    assert secs == pytest.approx(want["kernel_s"], rel=1e-9)
    # self times share out the operations' busy time; the programs'
    # events add the gaps between operations inside a program
    assert sum(tr.self_times().values()) == pytest.approx(
        want["busy_ops_s"], rel=1e-3)
    assert tr.summary()["programs"] > 0


def test_reference_counts_match_a_loop():
    """The reference's counts rebuilt from assignments agree with a
    token-by-token count, and ``count_err`` reads the largest gap."""
    from bench.reference import lda as ref
    r = np.random.default_rng(3)
    W, Tp, Vp, dpw, K = 2, 50, 12, 4, 5
    words = r.integers(-1, Vp, size=W * Tp)
    docs = r.integers(0, dpw, size=W * Tp)
    z = r.integers(0, K, size=W * Tp)
    B, D, s = ref.counts(words, docs, z, W=W, Vp=Vp, dpw=dpw, K=K)
    B2, D2 = np.zeros((Vp, K)), np.zeros((W * dpw, K))
    for i, (v, d, k) in enumerate(zip(words, docs, z)):
        if v >= 0:
            B2[v, k] += 1
            D2[(i // Tp) * dpw + d, k] += 1
    assert np.array_equal(B, B2) and np.array_equal(D, D2)
    assert np.array_equal(s, B2.sum(0))
    state = {"z": z, "B": B, "D": D2, "s": s}
    assert ref.count_err(state, words, docs, W=W, Vp=Vp, dpw=dpw, K=K) == 0
    state["D"] = D2 + np.eye(W * dpw, K) * 3
    assert ref.count_err(state, words, docs, W=W, Vp=Vp, dpw=dpw, K=K) == 3
