"""STRADS LDA: count conservation, likelihood ascent, s-error bounds,
single-worker exactness."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps import lda
from repro.core import single_device_mesh


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
