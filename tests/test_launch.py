"""Launch-layer tests: mesh builders, input specs, skip logic, roofline
HLO analyzer (validated against a hand-computable program), and a
small-mesh end-to-end sharded train step in a subprocess."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.configs import ARCHS, INPUT_SHAPES
from repro.launch import roofline as RL
from repro.launch.specs import skip_reason

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, devices: int = 8, timeout: int = 540) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


# ---------------------------------------------------------------------------
# skip logic / shape coverage
# ---------------------------------------------------------------------------

def test_skip_matrix():
    skips = {(a, s) for a in ARCHS for s in INPUT_SHAPES
             if skip_reason(a, s)}
    assert skips == {("hubert-xlarge", "decode_32k"),
                     ("hubert-xlarge", "long_500k")}


def test_input_shape_table():
    assert INPUT_SHAPES["train_4k"].seq_len == 4096
    assert INPUT_SHAPES["train_4k"].global_batch == 256
    assert INPUT_SHAPES["prefill_32k"].global_batch == 32
    assert INPUT_SHAPES["decode_32k"].global_batch == 128
    assert INPUT_SHAPES["long_500k"].seq_len == 524288
    assert INPUT_SHAPES["long_500k"].global_batch == 1


# ---------------------------------------------------------------------------
# roofline HLO analyzer
# ---------------------------------------------------------------------------

def test_hlo_analyzer_loop_and_collectives():
    """Loop-dependent matmul in a fori_loop on an 8-device mesh: the
    analyzer must charge flops × trip count and all-reduce wire bytes
    × trip count (XLA:CPU cost_analysis famously counts the body once)."""
    out = run_sub("""
        import jax, jax.numpy as jnp, json
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.compat import make_mesh
        from repro.launch.roofline import analyze_hlo
        mesh = make_mesh((2, 4), ("data", "model"))
        L, M, K, N = 7, 64, 128, 256
        def f(x, w):
            def body(i, acc):
                return acc + jnp.sum((x + i) @ w)
            return jax.lax.fori_loop(0, L, body, 0.0)
        xs = jax.ShapeDtypeStruct((M, K), jnp.float32)
        ws = jax.ShapeDtypeStruct((K, N), jnp.float32)
        lo = jax.jit(f, in_shardings=(
            NamedSharding(mesh, P(None, None)),
            NamedSharding(mesh, P(None, "model")))).lower(xs, ws)
        ana = analyze_hlo(lo.compile().as_text(), 8)
        print(json.dumps({"flops": ana.flops,
                          "wire": ana.wire_bytes,
                          "count": ana.collective_count}))
    """)
    res = json.loads(out.strip().splitlines()[-1])
    assert res["flops"] == 2 * 64 * 128 * (256 // 4) * 7
    assert res["count"] == 7
    assert res["wire"] == pytest.approx(7 * 2 * 4 * 3 / 4)


def test_shape_bytes_and_groups():
    assert RL._shape_bytes("bf16[2,3,4]{2,1,0}") == 48
    assert RL._shape_bytes("(f32[10], s32[2])") == 48
    assert RL._group_size("replica_groups={{0,1,2,3},{4,5,6,7}}, x", 99) == 4
    assert RL._group_size("replica_groups=[32,16]<=[512]", 99) == 16
    assert RL._group_size("no groups here", 7) == 7


def test_parse_instr_handles_tuple_comments():
    ln = ("  %while.34 = (s32[], bf16[65,2,512,1,64]{4,3,2,1,0}, "
          "/*index=5*/ f32[2,2064,2,64]{3,2,1,0}) while(%tuple.1), "
          "condition=%c, body=%b")
    name, typestr, op = RL._parse_instr(ln)
    assert name == "while.34" and op == "while"
    assert RL._shape_bytes(typestr) > 0


def test_model_flops_kinds():
    from repro.configs import get_config
    cfg = get_config("granite-3-2b")
    tr = RL.model_flops(cfg, INPUT_SHAPES["train_4k"])
    pf = RL.model_flops(cfg, INPUT_SHAPES["prefill_32k"])
    dc = RL.model_flops(cfg, INPUT_SHAPES["decode_32k"])
    assert tr == pytest.approx(3 * pf, rel=1e-6)  # 6ND vs 2ND, same tokens
    assert dc < pf / 1000                         # one token per sequence
    # MoE: active ≈ 6.6B of 42B total (nameplate)
    from repro.models.model import num_params
    moe = get_config("phi3.5-moe-42b-a6.6b")
    assert 30e9 < num_params(moe) < 60e9
    assert RL.active_params(moe) < 12e9


# ---------------------------------------------------------------------------
# the serving CLI split: launch/serve.py (STRADS bounded-staleness
# serving) vs launch/serve_lm.py (model-zoo LM decode) parse disjoint
# flag sets — examples/serve_decode.py broke once when serve grew the
# STRADS flags, so pin each CLI to its own surface
# ---------------------------------------------------------------------------

def test_serve_cli_flag_sets_are_disjoint():
    from repro.launch import serve, serve_lm
    # the STRADS serving CLI knows nothing about LM decode flags...
    with pytest.raises(SystemExit):
        serve.main(["--engine", "lasso", "--arch", "granite-3-2b"])
    # ...and the LM decode CLI knows nothing about STRADS flags
    with pytest.raises(SystemExit):
        serve_lm.main(["--arch", "granite-3-2b", "--engine", "lasso"])


def test_serve_cli_stream_flags_require_stream():
    from repro.launch import serve
    with pytest.raises(SystemExit, match="--stream"):
        serve.main(["--engine", "lasso", "--ingest-every", "2"])
    with pytest.raises(SystemExit, match="--stream"):
        serve.main(["--engine", "lasso", "--stream-kind", "extend"])


# ---------------------------------------------------------------------------
# sharded end-to-end step on a small forced mesh
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    """The pjit'd train step on a 4×2 mesh must agree numerically with the
    1-device run (same params, same batch) — SPMD must be semantics-free."""
    out = run_sub("""
        import json
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding
        from repro.configs import get_config
        from repro.launch.mesh import make_test_mesh
        from repro.models import model as M
        from repro.sharding.rules import activation_mesh
        from repro.train import TrainConfig, make_train_step
        from repro.train.step import init_train_state
        from repro.data import SyntheticLMConfig, make_batch

        cfg = get_config("granite-3-2b").reduced()
        tc = TrainConfig()
        state = init_train_state(cfg, tc, jax.random.PRNGKey(0))
        dc = SyntheticLMConfig(vocab_size=cfg.vocab_size, seq_len=32,
                               batch_size=8)
        batch = make_batch(dc, 0)

        # single-logical-device result
        s1, m1 = jax.jit(make_train_step(cfg, tc))(
            jax.tree.map(lambda x: x, state), batch)

        # sharded result
        mesh = make_test_mesh()
        assert mesh.size == 8, mesh
        pspecs = M.param_specs(cfg, mesh)
        put = lambda t, s: jax.device_put(t, s)
        state2 = {
            "params": jax.tree.map(put, state["params"], pspecs),
            "opt": {"m": jax.tree.map(put, state["opt"]["m"], pspecs),
                    "v": jax.tree.map(put, state["opt"]["v"], pspecs),
                    "count": state["opt"]["count"]},
            "step": state["step"],
        }
        with activation_mesh(mesh):
            s2, m2 = jax.jit(make_train_step(cfg, tc))(state2, batch)
        print(json.dumps({"l1": float(m1["loss"]), "l2": float(m2["loss"]),
                          "g1": float(m1["grad_norm"]),
                          "g2": float(m2["grad_norm"])}))
    """)
    res = json.loads(out.strip().splitlines()[-1])
    assert res["l1"] == pytest.approx(res["l2"], rel=2e-3)
    assert res["g1"] == pytest.approx(res["g2"], rel=2e-2)


# ---------------------------------------------------------------------------
# compile cache placement
# ---------------------------------------------------------------------------

def test_compile_cache_env_dir_stands_else_fixed_checkout_path(tmp_path):
    """JAX_COMPILATION_CACHE_DIR, when set, is where compiles land and
    nothing else is written; without it they land in <root>/.jax_cache."""
    code = textwrap.dedent(f"""
        import jax, jax.numpy as jnp
        from repro.launch.cache import enable_compile_cache
        print(enable_compile_cache({str(tmp_path / "root")!r}))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: jnp.sin(x) @ x)(jnp.ones((32, 32))).block_until_ready()
    """)
    env_dir = tmp_path / "env"
    for extra in ({"JAX_COMPILATION_CACHE_DIR": str(env_dir)}, {}):
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        env.update(extra, JAX_PLATFORMS="cpu",
                   PYTHONPATH=os.path.join(ROOT, "src"))
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, env=env,
                             timeout=120)
        assert out.returncode == 0, out.stderr[-4000:]
        if extra:
            assert out.stdout.split()[-1] == str(env_dir)
            assert any(env_dir.iterdir())
            assert not (tmp_path / "root").exists()
        else:
            assert out.stdout.split()[-1] == str(tmp_path / "root"
                                                 / ".jax_cache")
            assert any((tmp_path / "root" / ".jax_cache").iterdir())
