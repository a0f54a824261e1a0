"""Compiles for a described TPU v5e, without a chip: the lasso kernels at
the widths the main path hands them, and one scanned lasso round
program.  The compiler refuses here what the chip would refuse (tiles
not aligned to the chip's layout, more VMEM than a kernel may use).

The topology is described inside a module fixture, never at import, so
every test worker collects the same tests and only the worker running
this file loads the TPU compiler.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.apps import lasso
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
