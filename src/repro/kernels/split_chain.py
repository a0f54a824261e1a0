"""The subkeys of a split chain, all at once.

``subkeys(key, n)[i]`` is the key data of the ``i``-th ``sub`` of

    for i in range(n):
        key, sub = jax.random.split(key)

— what a sequential sampler draws with when it splits once a step.  The
chain depends on nothing but the key, so it can be drawn before the
sampler's loop; it cannot be vectorised, each key being a hash of the
last.

For threefry2x32 keys with ``jax_threefry_partitionable`` (the default)
a split hashes the key at counters (0, 0) for the next key and (0, 1)
for the subkey.  On a TPU both hashes run on the scalar core in one
Pallas kernel (about 0.1 us a step on a v5e, where XLA's split costs
about 2.8 us a step as ten vector instructions).  Elsewhere, and for any
other key implementation, the chain is a ``lax.scan`` of splits.  Both
give the same bits: threefry is integer arithmetic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: subkeys written per grid step (two int32 words each, in SMEM)
BLOCK = 2048
_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))
_PARITY = 0x1BD11BDA


def _threefry2x32(k0, k1, x0, x1):
    """Threefry-2x32, 20 rounds, on int32 scalars (two's complement adds
    and logical shifts give the uint32 bits)."""
    ks = (k0, k1, k0 ^ k1 ^ jnp.int32(_PARITY))
    x0, x1 = x0 + ks[0], x1 + ks[1]
    for i in range(5):
        for r in _ROTATIONS[i % 2]:
            x0 = x0 + x1
            x1 = (x1 << r) | lax.shift_right_logical(x1, jnp.int32(32 - r))
            x1 = x0 ^ x1
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + ks[(i + 2) % 3] + jnp.int32(i + 1)
    return x0, x1


def _chain_kernel(key_ref, out_ref, carry_ref):
    @pl.when(pl.program_id(0) == 0)
    def _():
        carry_ref[0] = key_ref[0]
        carry_ref[1] = key_ref[1]

    zero, one = jnp.int32(0), jnp.int32(1)

    def step(i, key):
        out_ref[2 * i], out_ref[2 * i + 1] = _threefry2x32(*key, zero, one)
        return _threefry2x32(*key, zero, zero)

    key = lax.fori_loop(0, BLOCK, step, (carry_ref[0], carry_ref[1]))
    carry_ref[0], carry_ref[1] = key


def _pallas_chain(key, n: int, interpret: bool = False):
    steps = -(-n // BLOCK)
    words = lax.bitcast_convert_type(jax.random.key_data(key), jnp.int32)
    out = pl.pallas_call(
        _chain_kernel,
        grid=(steps,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((2 * BLOCK,), lambda g: (g,),
                               memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((2 * steps * BLOCK,), jnp.int32),
        scratch_shapes=[pltpu.SMEM((2,), jnp.int32)],
        interpret=interpret,
    )(words)
    return lax.bitcast_convert_type(out.reshape(-1, 2)[:n], jnp.uint32)


def _scan_chain(key, n: int):
    def step(key, _):
        key, sub = jax.random.split(key)
        return key, jax.random.key_data(sub)
    return lax.scan(step, key, None, length=n)[1]


def subkeys(key, n: int):
    """(n, key words) uint32: the key data of the first ``n`` subkeys of
    ``key``'s split chain."""
    if (jax.random.key_impl(key) != "threefry2x32"
            or not jax.config.jax_threefry_partitionable):
        return _scan_chain(key, n)
    return lax.platform_dependent(
        key, tpu=functools.partial(_pallas_chain, n=n),
        default=functools.partial(_scan_chain, n=n))
