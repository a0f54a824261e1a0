"""Keys and generators from ``--seed``: any whole number (seeds may
exceed 32 bits) maps to its own stream, one stream per purpose."""
from __future__ import annotations

import zlib

import numpy as np


def seed_sequence(seed: int, purpose: str = "") -> np.random.SeedSequence:
    return np.random.SeedSequence(
        [int(seed) % 2 ** 64, zlib.crc32(purpose.encode())])


def numpy_rng(seed: int, purpose: str = "") -> np.random.Generator:
    return np.random.default_rng(seed_sequence(seed, purpose))


def jax_key(seed: int, purpose: str = ""):
    """A threefry key from both 32-bit words of the seed's stream
    (``jax.random.key(int)`` keeps only the low 32 bits)."""
    import jax
    words = seed_sequence(seed, purpose).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")
