"""The plain reference of STRADS LDA word rotation, independent of
``repro``.

Collapsed Gibbs: worker p owns documents and tokens of its own; at round
t it samples, in order, those of its tokens whose word lies in vocab
block (p + t) mod W, against that block's rows of the word–topic counts B,
its documents' counts D, and its own copy s̃ of the topic totals, which
starts each round at the synced s.  A token's topic is removed from the
counts, redrawn from (γ + B[v,k]) / (Vγ + s̃[k]) · (α + D[d,k]) and added
back.  After the round s = Σ_v B[v, ·].

The random numbers are the sampler's own: worker p's key at phase t is
``fold_in(fold_in(key(17), t), p)``, split once per token, and the draw is
``jax.random.categorical`` over the log-conditional.  Counts are float32
and exact (every cell < 2**24).  ``dtype=jnp.bfloat16`` is the control:
the same sampler computed one precision below the configuration's
float32.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

SAMPLER_KEY = 17


def _gibbs(B, D, s, words, docs, z, active, block_start, key, *, Vb, Vp,
           alpha, gamma):
    def body(carry, tok):
        B, D, st, key = carry
        v, d, zi, act = tok
        a = act.astype(B.dtype)
        vloc = jnp.clip(v - block_start, 0, Vb - 1)
        B = B.at[vloc, zi].add(-a)
        D = D.at[d, zi].add(-a)
        st = st.at[zi].add(-a)
        logits = (jnp.log(gamma + B[vloc]) - jnp.log(Vp * gamma + st)
                  + jnp.log(alpha + D[d]))
        key, sub = jax.random.split(key)
        znew = jax.random.categorical(sub, logits)
        znew = jnp.where(act, znew, zi).astype(zi.dtype)
        B = B.at[vloc, znew].add(a)
        D = D.at[d, znew].add(a)
        st = st.at[znew].add(a)
        return (B, D, st, key), znew

    (B, D, st, _), z = jax.lax.scan(body, (B, D, s, key),
                                    (words, docs, z, active))
    return B, D, z


@partial(jax.jit, static_argnames=("phases", "W", "Vb", "alpha", "gamma",
                                   "dtype"))
def _rounds(B, D, words, docs, z, *, phases, W, Vb, alpha, gamma, dtype):
    """``B`` (W, Vb, K) by block, ``D`` (W, dpw, K) and the corpus (W, Tp)
    by worker; one round per entry of ``phases``."""
    B, D = B.astype(dtype), D.astype(dtype)
    s = B.sum(axis=(0, 1))
    workers = jnp.arange(W)
    gibbs = jax.vmap(partial(_gibbs, Vb=Vb, Vp=W * Vb, alpha=alpha,
                             gamma=gamma),
                     in_axes=(0, 0, None, 0, 0, 0, 0, 0, 0))
    for phase in phases:
        blocks = (workers + phase) % W
        base = jax.random.fold_in(jax.random.key(SAMPLER_KEY), phase)
        keys = jax.vmap(lambda p: jax.random.fold_in(base, p))(workers)
        active = (words >= 0) & (words // Vb == blocks[:, None])
        Bw, D, z = gibbs(B[blocks], D, s, words, docs, z, active,
                         blocks * Vb, keys)
        B = B.at[blocks].set(Bw)
        s = B.sum(axis=(0, 1))
    return B, D, z


def counts(words, docs, z, *, W: int, Vp: int, dpw: int, K: int):
    """(B, D, s) rebuilt from the assignments (float32 host arrays, exact
    below 2**24)."""
    words, docs, z = (np.asarray(a).reshape(W, -1) for a in (words, docs, z))
    act = words >= 0
    u = np.broadcast_to(np.arange(W)[:, None], words.shape)[act]
    B = np.zeros((Vp, K), np.float32)
    D = np.zeros((W * dpw, K), np.float32)
    np.add.at(B, (words[act], z[act]), 1)
    np.add.at(D, (u * dpw + docs[act], z[act]), 1)
    return B, D, B.sum(axis=0)


def run_rounds(words, docs, z0, rounds: int, *, W: int, Vb: int, dpw: int,
               K: int, alpha: float, gamma: float, dtype=jnp.float32):
    """The state after ``rounds`` reference rounds from the assignments
    ``z0``: host arrays z (flat), B (V_p, K), D (W·docs, K), s (K,)."""
    Vp = W * Vb
    B, D, _ = counts(words, docs, z0, W=W, Vp=Vp, dpw=dpw, K=K)
    shape = (W, -1)
    B, D, z = _rounds(
        jnp.asarray(B.reshape(W, Vb, K), jnp.float32),
        jnp.asarray(D.reshape(W, dpw, K), jnp.float32),
        jnp.asarray(np.reshape(words, shape)),
        jnp.asarray(np.reshape(docs, shape)),
        jnp.asarray(np.reshape(z0, shape)),
        phases=tuple(t % W for t in range(rounds)), W=W, Vb=Vb,
        alpha=float(alpha), gamma=float(gamma), dtype=dtype)
    B = np.asarray(B.astype(jnp.float32)).reshape(Vp, K)
    return {"z": np.asarray(z).reshape(-1), "B": B,
            "D": np.asarray(D.astype(jnp.float32)).reshape(W * dpw, K),
            "s": B.sum(axis=0)}


def count_err(state: dict, words, docs, *, W: int, Vp: int, dpw: int,
              K: int) -> float:
    """The largest gap between the program's collapsed counts (B, D, s)
    and those its own assignments z imply; 0 when they are exact."""
    B, D, s = counts(words, docs, state["z"], W=W, Vp=Vp, dpw=dpw, K=K)
    return float(max(np.max(np.abs(np.asarray(state[k]) - want))
                     for k, want in (("B", B), ("D", D), ("s", s))))
