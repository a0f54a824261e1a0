"""chip_smoke.py: its phases at tiny sizes on the CPU (interpret mode and
reference kernels are legitimate here), and its refusal to report a
result without a chip or without the program beside it."""
import importlib.util
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

from repro.core import single_device_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")

_spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
cs = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cs)

TINY_LASSO = dict(n=256, J=1024, rounds=4, U=8, Uc=32)
TINY_LDA = dict(vocab=300, topics=8, tokens_per_worker=1024,
                docs_per_worker=16, rotations=2, true_topics=5)


@pytest.fixture(scope="module")
def mesh():
    return single_device_mesh()


def _run(args, env_extra, cwd=ROOT, drop=("PYTHONPATH",)):
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env.update(env_extra)
    return subprocess.run([sys.executable] + args, capture_output=True,
                          text=True, env=env, cwd=cwd, timeout=300)


def test_lasso_phase_tiny(mesh):
    obj = cs.lasso_phase(mesh, on_chip=False, **TINY_LASSO)["objective"]
    assert obj[2] < obj[1] < obj[0]


def test_lda_phase_tiny(mesh):
    ll = cs.lda_phase(mesh, on_chip=False, **TINY_LDA)["loglik"]
    assert ll[2] > ll[0]


def test_mf_phase_tiny(mesh):
    loss = cs.mf_phase(mesh, users=64, items=48, rank=4, rounds=8,
                       requests=8, top_k=4, on_chip=False)["loss"]
    assert loss[1] < loss[0]


def test_lda_corpus_is_seeded_and_in_range():
    from repro.apps import lda
    cfg = lda.LDAConfig(vocab=50, num_topics=4, num_workers=2,
                        tokens_per_worker=300, docs_per_worker=6)
    a = cs.lda_corpus(cfg, 3, true_topics=5)
    b = cs.lda_corpus(cfg, 3, true_topics=5)
    for x, y in zip(a, b):
        assert (x == y).all()
    words, docs, z0 = a
    assert words.shape == docs.shape == z0.shape == (600,)
    assert words.min() >= 0 and words.max() < 50
    assert docs.min() >= 0 and docs.max() < 6
    assert z0.min() >= 0 and z0.max() < 4


def test_four_chip_phase_on_forced_host_devices():
    code = textwrap.dedent(f"""
        import importlib.util
        spec = importlib.util.spec_from_file_location("cs", {SCRIPT!r})
        cs = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cs)
        from repro.core import worker_mesh
        lasso_kw = dict(n=256, J=1024, rounds=6)
        cs.four_chip_phase(worker_mesh(4), lasso_kw=lasso_kw,
                           lda_kw={TINY_LDA!r}, on_chip=False, U=8, Uc=32)
    """)
    out = _run(["-c", code], {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert out.returncode == 0, out.stderr[-4000:]
    assert "ssp_s0_equals_scan_bitwise=True" in out.stdout
    assert "counts_equal_rebuilt_from_z=True" in out.stdout


def test_main_refuses_the_cpu():
    out = _run([SCRIPT], {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert "cpu" in out.stderr.lower()
    assert '"ok"' not in out.stdout


def test_script_alone_fails(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    out = _run([str(tmp_path / "chip_smoke.py")], {"JAX_PLATFORMS": "cpu"},
               cwd=tmp_path)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
