"""STRADS LDA: count conservation, likelihood ascent, s-error bounds,
single-worker exactness, and the Gibbs scan against the plain reference."""
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps import lda
from repro.core import single_device_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.reference import lda as ref  # noqa: E402


@pytest.fixture(scope="module")
def mesh():
    return single_device_mesh()


@pytest.fixture(scope="module")
def setup():
    r = np.random.default_rng(0)
    cfg = lda.LDAConfig(vocab=50, num_topics=6, num_workers=1,
                        tokens_per_worker=1200, docs_per_worker=15)
    words, docs, z0 = lda.synthetic_corpus(r, cfg, true_topics=6)
    return cfg, words, docs, z0


def test_likelihood_increases(mesh, setup):
    cfg, words, docs, z0 = setup
    _, trace, _ = lda.fit(cfg, words, docs, z0, mesh, num_rounds=16,
                          trace_every=4)
    assert trace[-1][1] > trace[0][1] + 100    # clear ascent


def test_count_conservation(mesh, setup):
    """Token counts are conserved by every Gibbs round: ΣB = ΣD = #tokens
    and s = colsums(B)."""
    cfg, words, docs, z0 = setup
    state, _, _ = lda.fit(cfg, words, docs, z0, mesh, num_rounds=8)
    n_tok = int((words >= 0).sum())
    assert float(jnp.sum(state["B"])) == n_tok
    assert float(jnp.sum(state["D"])) == n_tok
    assert bool(jnp.allclose(state["s"], jnp.sum(state["B"], axis=0)))
    assert bool(jnp.all(state["B"] >= 0)) and bool(jnp.all(state["D"] >= 0))


def test_single_worker_zero_s_error(mesh, setup):
    """With one worker there is no staleness: Δ_t must be exactly 0 —
    the sampler is the exact sequential collapsed Gibbs sampler."""
    cfg, words, docs, z0 = setup
    _, _, serrs = lda.fit(cfg, words, docs, z0, mesh, num_rounds=6,
                          trace_every=1)
    assert all(v == 0.0 for _, v in serrs)


def test_assignments_in_range(mesh, setup):
    cfg, words, docs, z0 = setup
    state, _, _ = lda.fit(cfg, words, docs, z0, mesh, num_rounds=4)
    z = np.asarray(state["z"])
    assert ((0 <= z) & (z < cfg.num_topics)).all()


def test_baseline_runs_and_improves(mesh, setup):
    cfg, words, docs, z0 = setup
    _, trace, _ = lda.fit(cfg, words, docs, z0, mesh, num_rounds=8,
                          baseline=True, trace_every=2)
    assert trace[-1][1] > trace[0][1]


def test_build_state_matches_token_loop():
    """The vectorised counts equal a per-token loop's, -1 padding
    skipped, on several workers."""
    r = np.random.default_rng(1)
    cfg = lda.LDAConfig(vocab=40, num_topics=5, num_workers=3,
                        tokens_per_worker=200, docs_per_worker=7)
    words, docs, z0 = lda.synthetic_corpus(r, cfg, true_topics=4)
    words[r.random(words.shape) < 0.1] = -1
    U, Tp, dpw = cfg.num_workers, cfg.tokens_per_worker, cfg.docs_per_worker
    D = np.zeros((U * dpw, cfg.num_topics), np.float32)
    B = np.zeros((cfg.padded_vocab, cfg.num_topics), np.float32)
    for i in range(U * Tp):
        if words[i] >= 0:
            D[(i // Tp) * dpw + docs[i], z0[i]] += 1
            B[words[i], z0[i]] += 1
    got = lda.build_state(cfg, words, docs, z0)
    assert np.array_equal(np.asarray(got["D"]), D)
    assert np.array_equal(np.asarray(got["B"]), B)
    assert np.array_equal(np.asarray(got["s"]), B.sum(0))
    assert np.array_equal(np.asarray(got["z"]), z0)


def test_block_partition_covers_vocab():
    cfg = lda.LDAConfig(vocab=53, num_topics=4, num_workers=4,
                        tokens_per_worker=10, docs_per_worker=2)
    # padded vocab divisible into equal blocks covering every real word
    assert cfg.padded_vocab >= cfg.vocab
    assert cfg.padded_vocab == cfg.block_vocab * cfg.num_workers
    blocks = np.arange(cfg.vocab) // cfg.block_vocab
    assert blocks.max() < cfg.num_workers


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_gibbs_scan_bit_identical_to_reference(seed):
    """Each worker's Gibbs scan draws the plain reference's topics and
    leaves its counts, bit for bit, at the benchmark's tiny sizes: two
    vocab blocks (so a worker skips the other block's words), -1
    padding, the same word on runs of consecutive tokens (a row read
    right after it was written) and draws that keep the old topic."""
    V, K, T, dpw = 600, 64, 4096, 16
    cfg = lda.LDAConfig(vocab=V, num_topics=K, num_workers=2,
                        tokens_per_worker=T, docs_per_worker=dpw)
    Vb, Vp = cfg.block_vocab, cfg.padded_vocab
    r = np.random.default_rng(seed)
    # Zipf-like words, so a few rows carry most of the counts
    words = np.minimum(r.zipf(1.3, T) - 1, V - 1).astype(np.int32)
    runs = np.flatnonzero(r.random(T - 1) < 0.1)
    words[runs + 1] = words[runs]
    words[r.random(T) < 0.05] = -1
    docs = r.integers(0, dpw, T).astype(np.int32)
    # concentrated start, so many draws keep their topic
    z = r.integers(0, 4, T).astype(np.int32)
    B, D, s = ref.counts(words, docs, z, W=1, Vp=Vp, dpw=dpw, K=K)
    scan = jax.jit(functools.partial(lda._gibbs_scan, cfg))
    want_fn = jax.jit(functools.partial(ref._gibbs, Vb=Vb, Vp=Vp,
                                        alpha=cfg.alpha, gamma=cfg.gamma))
    for block in range(cfg.num_workers):
        active = (words >= 0) & (words // Vb == block)
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.key(ref.SAMPLER_KEY), 0), block)
        rows = slice(block * Vb, (block + 1) * Vb)
        got = scan(B[rows], D, s, words, docs, z, active,
                   jnp.int32(block * Vb), key)
        want = want_fn(B[rows], D, s, words, docs, z, active,
                       block * Vb, key)
        B_got, D_got, s_got, z_got = (np.asarray(x) for x in got)
        B_want, D_want, z_want = (np.asarray(x) for x in want)
        s_want = s + (B_want.sum(axis=0) - B[rows].sum(axis=0))
        assert np.array_equal(z_got, z_want)
        assert np.array_equal(B_got, B_want)
        assert np.array_equal(D_got, D_want)
        assert np.array_equal(s_got, s_want)
        # the cases the scan must get right were all exercised
        assert np.array_equal(z_got[~active], z[~active])
        assert np.any(active[runs] & active[runs + 1])
        kept, moved = active & (z_got == z), active & (z_got != z)
        assert kept.sum() > 10 and moved.sum() > 10
