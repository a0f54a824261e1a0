"""Indexed reads and sums over the entries of a sparse sweep, as one-hot
contractions on the matrix unit.

A sweep over C entries reads a factor vector ``v`` (n,) at each entry's
index and sums values by index.  XLA's TPU gather and scatter move one
scalar index at a time (about 8.5 ns an element on a v5e, whatever the
table's size or the indices' order).  Here the factor sits whole in
VMEM and each access is two levels of selection:

* an index splits as ``idx = 128·c + l``: chunk row ``c``, lane ``l``;
* :func:`take`: for each row of 128 entries the matrix unit contracts
  the table's chunk rows, 128 at a time (a *step*), against the one-hot
  of ``c`` (chunk rows on sublanes, the entries on lanes), which hands
  every entry its whole chunk row; the vector unit keeps lane ``l``;
* :func:`segment_sums`: the transpose.  Each value is laid on its lane
  ``l`` and contracted against the one-hot of ``c`` over a block's
  entries; the blocks' partial sums add into a VMEM-resident (steps,
  2·128, 128) total with compensated (Kahan) addition, so a popular
  index's thousands of partials lose no more than one.

Exact in float32: a float32 splits by bit masks into three bfloat16
parts hi + mid + lo (no rounding step for a compiler to elide), and the
matrix unit multiplies each part by a bfloat16 one-hot with float32
accumulation.  So :func:`take` returns ``v[idx]`` bit for bit and
:func:`segment_sums` adds float32 values in float32.

The window comes from the data: a block of :data:`BLOCK` entries
contracts only the steps from its least index's to its largest's,
computed on the device and handed in by scalar prefetch.  Indices sorted
within a block (MF's rows) take one step; random columns below 17,770
take two.  Unsorted or repeated indices are exact too; they only cost
more steps.  :func:`sweep_steps` counts the steps on the host.

On a TPU :func:`take` and :func:`segment_sums` run the kernels;
elsewhere XLA's gather and scatter (``lax.platform_dependent``, as
``split_chain.subkeys``), which are also the kernels' oracle.  Indices
must lie in ``[0, n)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
#: chunk rows one contraction step covers (the matrix unit's depth)
DEPTH = 128
#: indices one step covers
SPAN = LANES * DEPTH
#: rows of 128 entries a grid step takes
ROWS = 16
#: entries a grid step takes; a block's window is its indices' span
BLOCK = ROWS * LANES
#: bfloat16 parts of a float32
PARTS = 3
_VMEM_LIMIT = 48 * 2**20


def _split(x):
    """float32 ``x`` as hi, mid, lo (float32, each exactly a bfloat16,
    hi + mid + lo == x), by truncating the mantissa 8 bits at a time."""
    def trunc(v):
        bits = lax.bitcast_convert_type(v, jnp.int32) & jnp.int32(-65536)
        return lax.bitcast_convert_type(bits, jnp.float32)
    hi = trunc(x)
    r = x - hi
    mid = trunc(r)
    return hi, mid, r - mid


def _steps(n: int) -> int:
    return max(1, -(-n // SPAN))


def _rows(idx):
    """``idx`` padded with its last index to whole blocks (so the padding
    widens no window), as (rows, 128)."""
    idx = jnp.asarray(idx, jnp.int32)
    return jnp.pad(idx, (0, -idx.shape[0] % BLOCK),
                   mode="edge").reshape(-1, LANES)


def _windows(rows, steps: int):
    """The first and last step of each block of ``rows``."""
    b = rows.reshape(-1, BLOCK)
    lo = jnp.clip(jnp.min(b, axis=1) // SPAN, 0, steps - 1)
    hi = jnp.clip(jnp.max(b, axis=1) // SPAN, lo, steps - 1)
    return lo, hi


def sweep_steps(idx) -> int:
    """The contraction steps one :func:`take` or :func:`segment_sums`
    over the host array ``idx`` takes: each block's span in steps, from
    the same padding and blocks as the kernels' windows."""
    idx = np.asarray(idx, np.int64)
    b = np.pad(idx, (0, -idx.size % BLOCK), mode="edge").reshape(-1, BLOCK)
    return int(np.sum(b.max(axis=1) // SPAN - b.min(axis=1) // SPAN + 1))


def _params(semantics: str):
    return pltpu.CompilerParams(dimension_semantics=(semantics,),
                                vmem_limit_bytes=_VMEM_LIMIT)


# -- take: v[idx] ------------------------------------------------------------

def _table(v, steps: int):
    """(steps, 128 lanes, 3·128 chunk rows) bfloat16: step s, lane l, part
    p, chunk row c holds part p of v[s·SPAN + 128·c + l]."""
    t = jnp.pad(jnp.asarray(v, jnp.float32), (0, steps * SPAN - v.shape[0]))
    t = t.reshape(steps, DEPTH, LANES).transpose(0, 2, 1)
    return jnp.concatenate([p.astype(jnp.bfloat16) for p in _split(t)],
                           axis=2)


def _take_kernel(lo_ref, hi_ref, t_ref, idx_ref, out_ref, acc_ref):
    g = pl.program_id(0)
    sub = lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)

    def contract(j, first: bool):
        table = t_ref[j]                                   # (128, 3·128)
        for r in range(ROWS):
            c = (idx_ref[r:r + 1, :] >> 7) - j * DEPTH     # (1, 128)
            onehot = (sub == c).astype(jnp.bfloat16)       # (chunk, entry)
            got = jnp.dot(table, jnp.concatenate([onehot] * PARTS, axis=0),
                          preferred_element_type=jnp.float32)
            acc_ref[r] = got if first else acc_ref[r] + got

    contract(lo_ref[g], True)

    def more(j, carry):
        contract(j, False)
        return carry

    lax.fori_loop(lo_ref[g] + 1, hi_ref[g] + 1, more, 0)
    for r in range(ROWS):
        lane = idx_ref[r:r + 1, :] & (LANES - 1)
        out_ref[r:r + 1, :] = jnp.sum(jnp.where(sub == lane, acc_ref[r], 0.0),
                                      axis=0, keepdims=True)


def take_kernel(v, idx, *, interpret: bool = False):
    """``v[idx]`` for float32 ``v`` (n,) and int ``idx`` (C,) in [0, n),
    on the matrix unit (Pallas)."""
    C, steps = idx.shape[0], _steps(v.shape[0])
    rows = _rows(idx)
    lo, hi = _windows(rows, steps)
    fixed = lambda g, lo, hi: (0, 0, 0)
    blocked = lambda g, lo, hi: (g, 0)
    out = pl.pallas_call(
        _take_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(rows.shape[0] // ROWS,),
            in_specs=[pl.BlockSpec((steps, LANES, PARTS * DEPTH), fixed),
                      pl.BlockSpec((ROWS, LANES), blocked)],
            out_specs=pl.BlockSpec((ROWS, LANES), blocked),
            scratch_shapes=[pltpu.VMEM((ROWS, LANES, LANES), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(rows.shape, jnp.float32),
        compiler_params=_params("parallel"),
        name="entry_take",
        interpret=interpret,
    )(lo, hi, _table(v, steps), rows)
    return out.reshape(-1)[:C]


# -- segment_sums: Σx, Σy by idx -----------------------------------------------

def _sums_kernel(lo_ref, hi_ref, idx_ref, x_ref, y_ref, out_ref, v_ref,
                 o_ref, err_ref):
    g = pl.program_id(0)

    @pl.when(g == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)
        err_ref[...] = jnp.zeros_like(err_ref)

    sub = lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    # each value on its lane, the block's (entry, part) pairs along K
    for r in range(ROWS):
        on = sub == (idx_ref[r:r + 1, :] & (LANES - 1))    # (lane, entry)
        for q, val in enumerate((x_ref, y_ref)):
            for p, part in enumerate(_split(val[r:r + 1, :])):
                k = (r * PARTS + p) * LANES
                v_ref[q * LANES:(q + 1) * LANES, k:k + LANES] = jnp.where(
                    on, part, 0.0).astype(jnp.bfloat16)

    def step(j, carry):
        for r in range(ROWS):
            c = (idx_ref[r:r + 1, :] >> 7) - j * DEPTH
            onehot = (sub == c).astype(jnp.bfloat16)       # (chunk, entry)
            for p in range(PARTS):
                k = (r * PARTS + p) * LANES
                o_ref[:, k:k + LANES] = onehot
        part = lax.dot_general(
            v_ref[...], o_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # (2·lane, chunk)
        total, add = out_ref[j], part - err_ref[j]
        out_ref[j] = total + add
        err_ref[j] = (out_ref[j] - total) - add
        return carry

    lax.fori_loop(lo_ref[g], hi_ref[g] + 1, step, 0)


def segment_sums_kernel(x, y, idx, n: int, *, interpret: bool = False):
    """(Σ x, Σ y) by ``idx`` into ``n`` segments, for float32 ``x``, ``y``
    (C,) and int ``idx`` (C,) in [0, n), on the matrix unit (Pallas)."""
    steps = _steps(n)
    rows = _rows(idx)
    pad = rows.size - idx.shape[0]
    x, y = (jnp.pad(jnp.asarray(a, jnp.float32), (0, pad)).reshape(-1, LANES)
            for a in (x, y))
    lo, hi = _windows(rows, steps)
    blocked = lambda g, lo, hi: (g, 0)
    K = ROWS * PARTS * LANES
    out = pl.pallas_call(
        _sums_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(rows.shape[0] // ROWS,),
            in_specs=[pl.BlockSpec((ROWS, LANES), blocked)] * 3,
            out_specs=pl.BlockSpec((steps, 2 * LANES, DEPTH),
                                   lambda g, lo, hi: (0, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2 * LANES, K), jnp.bfloat16),
                            pltpu.VMEM((DEPTH, K), jnp.bfloat16),
                            pltpu.VMEM((steps, 2 * LANES, DEPTH),
                                       jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((steps, 2 * LANES, DEPTH),
                                       jnp.float32),
        compiler_params=_params("arbitrary"),
        name="entry_segment_sums",
        interpret=interpret,
    )(lo, hi, rows, x, y)
    s = out.reshape(steps, 2, LANES, DEPTH).transpose(1, 0, 3, 2)
    s = s.reshape(2, steps * SPAN)[:, :n]
    return s[0], s[1]


# -- by platform ---------------------------------------------------------------

def _take_xla(v, idx):
    return v[idx]


def _sums_xla(x, y, idx, n: int):
    # two 1-D scatters: a stacked (C, 2) pair would be tiled to 128 lanes
    # on a TPU, 64 times its size
    return jax.ops.segment_sum(x, idx, n), jax.ops.segment_sum(y, idx, n)


def take(v, idx):
    """``v[idx]``: :func:`take_kernel` on a TPU, XLA's gather elsewhere."""
    return lax.platform_dependent(v, idx, tpu=take_kernel, default=_take_xla)


def segment_sums(x, y, idx, n: int):
    """(Σ x, Σ y) by ``idx`` into ``n`` segments: :func:`segment_sums_kernel`
    on a TPU, XLA's scatter-adds elsewhere."""
    return lax.platform_dependent(
        x, y, idx, tpu=functools.partial(segment_sums_kernel, n=n),
        default=functools.partial(_sums_xla, n=n))
