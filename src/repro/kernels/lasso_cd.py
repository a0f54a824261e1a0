"""Blocked kernels for the STRADS Lasso push hot-spots.

Two reductions over row tiles dominate the paper's Lasso round:

  * ``lasso_partial`` — the push partials  z_j = x_jᵀ r  over the
    scheduled block, a (1 × n)·(n × U) row-vector product reduced over
    row tiles.
  * ``gram_block``    — the ρ-dependency-filter Gram block
    G = X_Cᵀ X_C over the U′ candidates, a (n × U′)ᵀ·(n × U′) matmul
    reduced over row tiles.

Both stream row tiles through VMEM with a resident (1×U or U′×U′) f32
accumulator, so arbitrarily large n never leaves HBM more than once.

Tiling.  Every block spans the full U/U′ width (a block dimension equal
to the array's is legal at any width, so nothing is padded to 128
lanes).  The residual travels as a (1, n) row so that its row tile is a
lane-dense (1, block_n) block: the row tile is therefore rounded up to a
multiple of 128 (the default is 256), and an n that fits in one tile is
one whole-array block.  Rows past n in the last tile are zero-padded by
the wrappers and masked in the kernels.

Validated against ``ref.lasso_partial_ref`` / ``ref.gram_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_N = 256
LANES = 128


def _row_tiling(n: int, block_n: int):
    """(tile, padded rows): one whole-array tile when n fits in the
    lane-rounded ``block_n``, else lane-aligned tiles over padded rows."""
    tile = -(-block_n // LANES) * LANES
    if n <= tile:
        return n, n
    return tile, -(-n // tile) * tile


def _partial_kernel(r_ref, x_ref, z_ref, acc_ref, *, rows: int,
                    block_n: int):
    i = pl.program_id(0)
    ni = pl.num_programs(0)

    @pl.when(i == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)                     # (Bn, U)
    r = r_ref[...].astype(jnp.float32)                     # (1, Bn)
    row = i * block_n + jax.lax.broadcasted_iota(jnp.int32, (1, block_n), 1)
    r = jnp.where(row < rows, r, 0.0)                      # row padding
    acc_ref[...] += jnp.dot(r, x, preferred_element_type=jnp.float32)

    @pl.when(i == ni - 1)
    def _():
        z_ref[...] = acc_ref[...]


def lasso_partial(Xb: jax.Array, r: jax.Array,
                  block_n: int = DEFAULT_BLOCK_N,
                  interpret: bool = False) -> jax.Array:
    """z = Xbᵀ r : (n, U), (n,) → (U,) f32."""
    n, U = Xb.shape
    block_n, padded = _row_tiling(n, block_n)
    if padded > n:
        Xb = jnp.pad(Xb, ((0, padded - n), (0, 0)))
        r = jnp.pad(r, ((0, padded - n),))
    kernel = functools.partial(_partial_kernel, rows=n, block_n=block_n)
    z = pl.pallas_call(
        kernel,
        grid=(padded // block_n,),
        in_specs=[
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
            pl.BlockSpec((block_n, U), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, U), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, U), jnp.float32),
        name="lasso_partial",
        scratch_shapes=[pltpu.VMEM((1, U), jnp.float32)],
        interpret=interpret,
    )(r.reshape(1, padded), Xb)
    return z.reshape(U)


def _gram_kernel(x_ref, g_ref, acc_ref, *, rows: int, block_n: int):
    i = pl.program_id(0)
    ni = pl.num_programs(0)

    @pl.when(i == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)                     # (Bn, U')
    row = i * block_n + jax.lax.broadcasted_iota(
        jnp.int32, (block_n, 1), 0)
    x = jnp.where(row < rows, x, 0.0)
    acc_ref[...] += x.T @ x

    @pl.when(i == ni - 1)
    def _():
        g_ref[...] = acc_ref[...]


def gram_block(Xc: jax.Array, block_n: int = DEFAULT_BLOCK_N,
               interpret: bool = False) -> jax.Array:
    """G = Xcᵀ Xc : (n, U′) → (U′, U′) f32."""
    n, U = Xc.shape
    block_n, padded = _row_tiling(n, block_n)
    if padded > n:
        Xc = jnp.pad(Xc, ((0, padded - n), (0, 0)))
    kernel = functools.partial(_gram_kernel, rows=n, block_n=block_n)
    return pl.pallas_call(
        kernel,
        grid=(padded // block_n,),
        in_specs=[pl.BlockSpec((block_n, U), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((U, U), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((U, U), jnp.float32),
        scratch_shapes=[pltpu.VMEM((U, U), jnp.float32)],
        interpret=interpret,
        name="gram_block",
    )(Xc)
