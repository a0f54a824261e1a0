"""STRADS LDA word rotation through ``StradsEngine.execute``.

The corpus is planted LDA drawn on the host with the vectorised generator
(copied from ``chip_smoke.lda_corpus``): per-document θ_d ~ Dir(α₀),
topic per token by ``searchsorted`` over the cumulative θ rows, word per
token over cumulative topic–word tables φ_t ∝ Gamma(shape)·Zipf(rank).
Each of the W workers holds its own tokens over its own documents.
Updates are tokens sampled: one rotation (W rounds) samples every token
once.

``correct`` (see ``bench/reference/lda.py``):

* ``z_mismatch`` — the first chunk (the warm-up, from the seed's
  assignments) against the reference's replay of the same rounds: the
  share of tokens whose topic differs;
* ``count_err`` — the state after the window: the largest gap between
  the collapsed counts (B, D, s) and those the final assignments imply.

``FAULTS`` are the faults ``bench/faults.py`` plants in the sampler.
"""
from __future__ import annotations

import gc

import jax.numpy as jnp
import numpy as np

from bench.apps._job import EngineJob
from bench.reference import lda as ref
from bench.seeds import jax_key, numpy_rng

from repro.apps import lda


def corpus(cfg: lda.LDAConfig, rng: np.random.Generator, *,
           true_topics: int, doc_alpha: float, topic_shape: float,
           zipf: float):
    """Flat (words, docs, z0): worker u's tokens are
    ``[u·T_p, (u+1)·T_p)``, its document ids local in ``[0, docs)``."""
    U, Tp, dpw = cfg.num_workers, cfg.tokens_per_worker, cfg.docs_per_worker
    V, T = cfg.vocab, true_topics
    zipf_w = 1.0 / np.arange(1, V + 1) ** zipf
    phi = rng.gamma(topic_shape, size=(T, V)) * zipf_w[rng.permutation(V)]
    phi /= phi.sum(axis=1, keepdims=True)
    theta = rng.dirichlet([doc_alpha] * T, size=U * dpw)
    N = U * Tp
    docs = rng.integers(0, dpw, size=N).astype(np.int32)
    g = np.repeat(np.arange(U), Tp) * dpw + docs          # global doc id
    # one searchsorted over all rows at once: row i lives in [i, i+1)
    flat_theta = (np.cumsum(theta, axis=1)
                  + np.arange(U * dpw)[:, None]).ravel()
    topic = np.searchsorted(flat_theta, g + rng.random(N)) - g * T
    topic = np.clip(topic, 0, T - 1)
    flat_phi = (np.cumsum(phi, axis=1) + np.arange(T)[:, None]).ravel()
    words = np.searchsorted(flat_phi, topic + rng.random(N)) - topic * V
    words = np.clip(words, 0, V - 1).astype(np.int32)
    z0 = rng.integers(0, cfg.num_topics, size=N).astype(np.int32)
    return words, docs, z0


def round_flops(num_topics: int, tokens: int, workers: int) -> float:
    """FLOPs one round requires (all workers): per sampled token, the
    K-wide log-conditional (two offsets, the three-term sum and the
    Gumbel offset) and the arg-max comparison, 6·K; one round samples
    1/W of the tokens."""
    return 6.0 * num_topics * tokens / workers


def lda_config(config: dict, workers: int) -> lda.LDAConfig:
    return lda.LDAConfig(
        vocab=int(config["vocab"]), num_topics=int(config["num_topics"]),
        num_workers=workers,
        tokens_per_worker=int(config["tokens_per_worker"]),
        docs_per_worker=int(config["docs_per_worker"]),
        alpha=float(config["alpha"]), gamma=float(config["gamma"]))


def seeded_corpus(config: dict, cfg: lda.LDAConfig, seed: int):
    p = config["corpus"]
    return corpus(cfg, numpy_rng(seed, "corpus"),
                  true_topics=int(p["true_topics"]),
                  doc_alpha=float(p["doc_alpha"]),
                  topic_shape=float(p["topic_shape"]), zipf=float(p["zipf"]))


class Job(EngineJob):
    """One LDA cell: W workers, one vocab block each."""

    def __init__(self, config: dict, cell: dict, mesh, seed: int):
        self.config, self.limits = config, cell["limits"]
        W = mesh.size
        self.cfg = lda_config(config, W)
        self.rounds_per_chunk = int(cell["traffic"]["rounds_per_chunk"])
        if self.rounds_per_chunk % W:
            raise ValueError(f"a chunk of {self.rounds_per_chunk} rounds "
                             f"is not whole rotations of {W} workers")
        self.words, self.docs, self.z0 = seeded_corpus(config, self.cfg,
                                                       seed)
        self.tokens = int((self.words >= 0).sum())
        self.key = jax_key(seed)
        self.eng = lda.make_engine(self.cfg, mesh)
        self.data = self.eng.shard_data({"words": jnp.asarray(self.words),
                                         "docs": jnp.asarray(self.docs)})
        self.state = self.eng.init_state(self.key, words=self.words,
                                         docs=self.docs, z0=self.z0)
        self.first_z = None

    def first_chunk_done(self, rep):
        self.first_z = np.asarray(rep.state["z"])

    def counters(self) -> dict:
        """Rounds and tokens sampled so far: a rotation of W rounds
        samples every token exactly once."""
        t = int(self.carry.t)
        return {"rounds": t,
                "updates": t * self.tokens // self.cfg.num_workers}

    def round_flops(self) -> float:
        return round_flops(self.cfg.num_topics, self.tokens,
                           self.cfg.num_workers)

    def check(self) -> list:
        """Free the program's state, then compare with the reference."""
        final = {k: np.asarray(v) for k, v in self.state.items()}
        del self.state, self.carry, self.data
        self.eng = None
        gc.collect()
        cfg = self.cfg
        shape = dict(W=cfg.num_workers, dpw=cfg.docs_per_worker,
                     K=cfg.num_topics)
        count_err = ref.count_err(final, self.words, self.docs,
                                  Vp=cfg.padded_vocab, **shape)
        del final
        want = ref.run_rounds(self.words, self.docs, self.z0,
                              self.rounds_per_chunk, Vb=cfg.block_vocab,
                              alpha=cfg.alpha, gamma=cfg.gamma, **shape)
        mismatch = float(np.mean(want["z"] != self.first_z))
        return [{"name": "z_mismatch", "value": mismatch,
                 "limit": self.limits["z_mismatch"]},
                {"name": "count_err", "value": count_err,
                 "limit": self.limits["count_err"]}]


def setup(config: dict, cell: dict, mesh, seed: int) -> Job:
    return Job(config, cell, mesh, seed)


def control(config: dict, cell: dict, mesh, seed: int,
            window_rounds: int) -> dict:
    """The numbers ``correct`` compares, read off the control: the
    reference in the program's place, computed in bfloat16 (on one
    device, whatever the cell's worker count)."""
    cfg = lda_config(config, int(cell["chips"]))
    words, docs, z0 = seeded_corpus(config, cfg, seed)
    shape = dict(W=cfg.num_workers, dpw=cfg.docs_per_worker,
                 K=cfg.num_topics, Vb=cfg.block_vocab, alpha=cfg.alpha,
                 gamma=cfg.gamma)
    R = int(cell["traffic"]["rounds_per_chunk"])
    want = ref.run_rounds(words, docs, z0, R, **shape)
    got = ref.run_rounds(words, docs, z0, R, dtype=jnp.bfloat16, **shape)
    late = ref.run_rounds(words, docs, z0, window_rounds,
                          dtype=jnp.bfloat16, **shape)
    return {"z_mismatch": float(np.mean(want["z"] != got["z"])),
            "count_err": ref.count_err(late, words, docs,
                                       Vp=cfg.padded_vocab,
                                       W=cfg.num_workers,
                                       dpw=cfg.docs_per_worker,
                                       K=cfg.num_topics)}


# -- faults (see ``bench/faults.py``) --------------------------------------------

def _unchanged():
    """Push returns the round's state as it came: no token is resampled."""
    from repro.apps.lda import StradsLDA

    def push(self, data, state, sched, phase):
        partial = {"s": jnp.sum(state["B"], axis=0)}
        return partial, {"z": state["z"], "D": state["D"],
                         "B": state["B"], "s_tilde": state["s"]}
    return StradsLDA, {"push": push}


def _half_batch():
    """The second half of each worker's tokens is never sampled."""
    from repro.apps.lda import StradsLDA
    push = StradsLDA.push

    def half(self, data, state, sched, phase):
        w = data["words"]
        keep = jnp.arange(w.shape[0]) < w.shape[0] // 2
        return push(self, dict(data, words=jnp.where(keep, w, -1)),
                    state, sched, phase)
    return StradsLDA, {"push": half}


def _altered():
    """The first token's new topic is moved by one where it is drawn."""
    from repro.apps.lda import StradsLDA
    push = StradsLDA.push

    def altered(self, data, state, sched, phase):
        partial, local = push(self, data, state, sched, phase)
        z = local["z"]
        z = z.at[0].set((z[0] + 1) % self.cfg.num_topics)
        return partial, dict(local, z=z)
    return StradsLDA, {"push": altered}


def _exchange():
    """The rotation's ``ppermute`` of B blocks becomes the identity."""
    from repro.sched.schedulers import RotationScheduler

    def identity(self, phase):
        return [(d, d) for d in range(self.num_workers)]
    return RotationScheduler, {"forward_perm": identity,
                               "backward_perm": identity}


FAULTS = {"unchanged": _unchanged, "half_batch": _half_batch,
          "altered": _altered, "exchange": _exchange}


def faults_for(cell: dict) -> list:
    """The faults a cell can have: the exchange only where the rotation
    spans chips."""
    return [f for f in FAULTS if f != "exchange" or int(cell["chips"]) > 1]
