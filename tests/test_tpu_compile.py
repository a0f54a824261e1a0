"""Compiles for a described TPU v5e, without a chip: the lasso kernels at
the widths the main path hands them, one scanned lasso round program,
LDA's Gibbs kernel and round and MF's sparse sweeps at the benchmark
cells' shapes (and D at NYTimes' documents a worker).  The compiler
refuses here what the chip would refuse (tiles not aligned to the
chip's layout, more VMEM than a kernel may use).

The topology is described inside a module fixture, never at import, so
every test worker collects the same tests and only the worker running
this file loads the TPU compiler.
"""
import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.apps import lasso, lda, mf
from repro.kernels import build_kernels
from repro.kernels.lasso_cd import gram_block, lasso_partial

#: per-worker rows of the one-chip smoke (chip_smoke.py LASSO)
ROWS = 16384


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")      # no compiler logs in /tmp
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        cc.reset_cache()
        mp.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


# the smoke widths (U=128, U′=512) and the app default (U=8, U′=32)
@pytest.mark.parametrize("kernel,width", [
    ("lasso_partial", 128), ("gram_block", 512),
    ("lasso_partial", 8), ("gram_block", 32)])
def test_lasso_kernel_compiles_for_v5e(one_chip, kernel, width):
    X = jax.ShapeDtypeStruct((ROWS, width), jnp.float32, sharding=one_chip)
    if kernel == "lasso_partial":
        r = jax.ShapeDtypeStruct((ROWS,), jnp.float32, sharding=one_chip)
        lowered = jax.jit(lasso_partial).lower(X, r)
    else:
        lowered = jax.jit(gram_block).lower(X)
    assert "tpu_custom_call" in lowered.compile().as_text()


def test_scanned_lasso_round_compiles_for_v5e(topo):
    n, J, U, Uc, rounds = 2048, 4096, 128, 512, 4
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))
    cfg = lasso.LassoConfig(num_features=J, lam=0.05, block_size=U,
                            num_candidates=Uc, rho=0.3,
                            kernel_backend="pallas")
    eng = lasso.make_engine(cfg, mesh)
    # the live platform is the CPU, so the engine resolved interpret
    # mode; hand the app the kernels a TPU run resolves instead
    eng.app.use_kernels(build_kernels(eng.kernel_spec, platform="tpu"))

    def shaped(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    state = {"beta": shaped((J,), jnp.float32, P()),
             "r": shaped((n,), jnp.float32, P("data"))}
    data = {"X": shaped((n, J), jnp.float32, P("data")),
            "y": shaped((n,), jnp.float32, P("data"))}
    key = jax.eval_shape(lambda: jax.random.key(0))
    sc = jax.eval_shape(eng.init_sched_carry)
    compiled = eng.scanned_fn(rounds).lower(
        state, data, shaped(key.shape, key.dtype, P()),
        shaped((), jnp.int32, P()), shaped(sc.shape, sc.dtype, P()),
        None).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2
    # X is an argument of the round program; it fits one chip's HBM
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= n * J * 4
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16 * 2**30


#: instructions of compiled HLO that cost nothing on the chip
TRIVIAL_OPS = {"get-tuple-element", "bitcast", "constant", "parameter"}


def hlo_computations(text: str) -> dict:
    """Computation name → (opcode, line) of each of its instructions, from
    compiled HLO text."""
    comps, cur = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None and " = " in line:
            rhs = line.split(" = ", 1)[1]
            if rhs.startswith("("):        # a tuple type: skip to its end
                depth = 0
                for i, ch in enumerate(rhs):
                    depth += (ch == "(") - (ch == ")")
                    if depth == 0:
                        break
                rhs = rhs[i + 1:]
            else:
                rhs = rhs.split(" ", 1)[1]
            cur.append((re.match(r"\s*([\w\-]+)\(", rhs).group(1), line))
    return comps


def loop_bodies(text: str) -> dict:
    """Body name → (opcode, line) of each instruction, for every while
    loop in compiled HLO text."""
    comps = hlo_computations(text)
    names = [m.group(1) for ops in comps.values() for op, line in ops
             if op == "while"
             for m in [re.search(r"body=%?([\w.\-]+)", line)]]
    return {n: comps[n] for n in names}


#: ``lda-nytimes.1chip``: one worker, V = 102,660, K = 1,000, 2**16 tokens
LDA_CELL = dict(V=102660, K=1000, T=65536)
#: NYTimes' documents a worker of four holds (299,752 / 4)
NYTIMES_DOCS_PER_WORKER = 74938


def _gibbs_text(one_chip, docs: int) -> str:
    V, K, T = LDA_CELL["V"], LDA_CELL["K"], LDA_CELL["T"]
    cfg = lda.LDAConfig(vocab=V, num_topics=K, num_workers=1,
                        tokens_per_worker=T, docs_per_worker=docs)

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    key = jax.eval_shape(lambda: jax.random.key(0))
    return jax.jit(functools.partial(lda._gibbs_scan, cfg)).lower(
        shaped((V, K), jnp.float32), shaped((docs, K), jnp.float32),
        shaped((K,), jnp.float32), shaped((T,), jnp.int32),
        shaped((T,), jnp.int32), shaped((T,), jnp.int32),
        shaped((T,), jnp.bool_), shaped((), jnp.int32),
        shaped(key.shape, key.dtype)).compile().as_text()


def _copies(ops, shape: str) -> list:
    return [line for op, line in ops
            if op in ("copy", "copy-start") and
            line.split(" = ", 1)[1].startswith(shape)]


def test_gibbs_token_step_compiles_small_for_v5e(one_chip):
    """The Gibbs scan of ``lda-nytimes.1chip`` (200 documents): the token
    step is the ``gibbs_scan`` kernel, called once a chunk of tokens from
    an XLA loop that carries the word table in place (no copy of it in
    the loop, no per-token instructions, no scatter), beside the split
    chain's scalar-core kernel."""
    V, K = LDA_CELL["V"], LDA_CELL["K"]
    text = _gibbs_text(one_chip, 200)
    loops = [ops for ops in loop_bodies(text).values()
             if any(f"f32[{V},{K}]" in line for _, line in ops)]
    assert len(loops) == 1
    work = [op for op, _ in loops[0] if op not in TRIVIAL_OPS]
    assert "custom-call" in work and "scatter" not in work
    assert not _copies(loops[0], f"f32[{V},{K}]")
    kernels = [re.match(r"\s*%?([a-z_]+)", line).group(1)
               for comp in hlo_computations(text).values()
               for op, line in comp
               if op == "custom-call" and "tpu_custom_call" in line]
    assert "gibbs_scan" in kernels and len(kernels) == 2, kernels


def test_gibbs_kernel_compiles_for_v5e_with_nytimes_documents(one_chip):
    """With NYTimes' 74,938 documents a worker D is 300 MB: it stays in
    HBM and goes through the kernel's ring like B, so the kernel's VMEM
    does not grow with it."""
    text = _gibbs_text(one_chip, NYTIMES_DOCS_PER_WORKER)
    assert "gibbs_scan" in text
    assert not any(_copies(ops, f"f32[{NYTIMES_DOCS_PER_WORKER},")
                   for ops in loop_bodies(text).values())


def test_lda_round_compiles_for_v5e_without_copies_of_b_in_loops(topo):
    """One scanned round of ``lda-nytimes.1chip`` through the engine: the
    kernel updates B in place.  The round's copies of B all lie outside
    its loops: XLA keeps B column-major at the round's boundary (less
    padding for 102,660 × 1,000), so B is laid out by rows once before
    the kernel's chunk loop and back after it, and the rotation's two
    ``ppermute``s copy it on one chip."""
    V, K, T, dpw = LDA_CELL["V"], LDA_CELL["K"], LDA_CELL["T"], 200
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))
    cfg = lda.LDAConfig(vocab=V, num_topics=K, num_workers=1,
                        tokens_per_worker=T, docs_per_worker=dpw)
    eng = lda.make_engine(cfg, mesh)

    def shaped(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    state = {"z": shaped((T,), jnp.int32, P("data")),
             "D": shaped((dpw, K), jnp.float32, P("data")),
             "B": shaped((V, K), jnp.float32, P("data")),
             "s": shaped((K,), jnp.float32, P()),
             "s_err": shaped((), jnp.float32, P())}
    data = {"words": shaped((T,), jnp.int32, P("data")),
            "docs": shaped((T,), jnp.int32, P("data"))}
    key = jax.eval_shape(lambda: jax.random.key(0))
    compiled = eng.scanned_fn(1).lower(
        state, data, shaped(key.shape, key.dtype, P()),
        shaped((), jnp.int32, P()), None, None).compile()
    text = compiled.as_text()
    assert "gibbs_scan" in text
    B = f"f32[{V},{K}]"
    assert not any(_copies(ops, B) for ops in loop_bodies(text).values())
    copies = [c for ops in hlo_computations(text).values()
              for c in _copies(ops, B)]
    assert len(copies) <= 4, copies
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 2 * 2**30


def test_mf_sweeps_compile_for_v5e_at_the_cell_size(topo):
    """One H/W cycle of ``mf-netflix.1chip`` (120,048 users, 17,770
    movies, rank 1,000, 25.6 M entries): every indexed read and segment
    sum over the entries is an ``entry_sweep`` kernel, so no gather or
    scatter runs over the entries, and the program fits far inside one
    chip.  Every per-entry array stays 1-D: an entry array with a minor
    dimension of 2 is tiled to 128 lanes on the chip (13.8 GB of
    temporaries at this size, and a 38 s compile)."""
    N, M, K, C = 120048, 17770, 1000, 25_600_000
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))
    eng = mf.make_engine(mf.MFConfig(num_rows=N, num_cols=M, rank=K),
                         mesh)

    def shaped(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    state = {"W": shaped((N, K), jnp.float32, P("data")),
             "H": shaped((K, M), jnp.float32, P()),
             "R": shaped((C,), jnp.float32, P("data"))}
    data = {"row": shaped((C,), jnp.int32, P("data")),
            "col": shaped((C,), jnp.int32, P("data")),
            "val": shaped((C,), jnp.float32, P("data")),
            "valid": shaped((N,), jnp.bool_, P("data"))}
    key = jax.eval_shape(lambda: jax.random.key(0))
    compiled = eng.scanned_fn(2).lower(
        state, data, shaped(key.shape, key.dtype, P()),
        shaped((), jnp.int32, P()), None, None).compile()
    text = compiled.as_text()
    ops = [(op, f"[{C}]" in line) for comp in hlo_computations(text).values()
           for op, line in comp]
    assert ("gather", True) not in ops and ("scatter", True) not in ops, ops
    # a phase reads w and h, sums, then reads its Δ (the H-phase's pull
    # reads the same w as its push: one call)
    kernels = [re.match(r"\s*%?(entry_[a-z_]+)", line).group(1)
               for comp in hlo_computations(text).values()
               for op, line in comp
               if op == "custom-call" and "tpu_custom_call" in line]
    assert sorted(kernels) == ["entry_segment_sums"] * 2 + ["entry_take"] * 6
    assert f"[{C},2]" not in text
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 2 * 2**30
