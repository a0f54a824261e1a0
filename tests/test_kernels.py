"""Per-kernel validation: shape/dtype sweeps asserting allclose against the
ref.py pure-jnp oracles, in Pallas interpret mode (CPU container) — plus
the declarative KernelSpec surface (validation, JSON round-trip, the
build_kernels registry) and the plan-level contract: ``kernels=None``
resolves to the reference backend bit-identically on all four executors,
and a pallas plan agrees numerically end-to-end."""
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu
from hypothesis import given, settings, strategies as st

from repro.apps import lasso
from repro.core import ExecutionPlan, single_device_mesh
from repro.kernels import (KERNEL_KINDS, KernelSpec, PallasKernels,
                           ReferenceKernels, build_kernels, ops, ref)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.lasso_cd import DEFAULT_BLOCK_N, gram_block, lasso_partial
from repro.kernels.moe_gating import topk_gating
from repro.kernels import entry_sweep, gibbs_scan, split_chain
from repro.kernels.ssm_scan import ssm_scan

R = np.random.default_rng(42)


def randn(*shape, dtype=jnp.float32):
    return jnp.asarray(R.normal(size=shape), dtype)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

ATTN_CASES = [
    # B, Sq, Skv, Hq, Hkv, D, causal, window, bq, bk
    (2, 32, 32, 4, 2, 8, True, None, 16, 16),
    (1, 64, 64, 2, 2, 16, True, 8, 16, 16),
    (1, 1, 40, 4, 1, 8, True, None, 8, 16),     # decode
    (2, 17, 33, 2, 1, 8, False, None, 8, 8),    # ragged, full attn
    (1, 1, 64, 8, 2, 16, True, 16, 8, 16),      # decode + window
    (1, 24, 24, 1, 1, 4, True, None, 8, 8),
    (1, 16, 128, 4, 4, 8, True, 32, 8, 32),     # prefill suffix + window
]


@pytest.mark.parametrize("case", ATTN_CASES)
def test_flash_attention_matches_ref(case):
    B, Sq, Skv, Hq, Hkv, D, causal, window, bq, bk = case
    q = randn(B, Sq, Hq, D)
    k = randn(B, Skv, Hkv, D)
    v = randn(B, Skv, Hkv, D)
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    tr = lambda x: x.transpose(0, 2, 1, 3)
    got = tr(flash_attention(tr(q), tr(k), tr(v), causal=causal,
                             window=window, block_q=bq, block_k=bk,
                             interpret=True))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    q = randn(1, 32, 2, 8, dtype=dtype)
    k = randn(1, 32, 2, 8, dtype=dtype)
    v = randn(1, 32, 2, 8, dtype=dtype)
    want = ref.attention_ref(q, k, v, causal=True)
    tr = lambda x: x.transpose(0, 2, 1, 3)
    got = tr(flash_attention(tr(q), tr(k), tr(v), causal=True,
                             block_q=16, block_k=16, interpret=True))
    assert got.dtype == dtype
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 3), st.integers(1, 48), st.integers(1, 48),
       st.sampled_from([(4, 4), (4, 2), (4, 1), (2, 1)]),
       st.booleans(), st.sampled_from([None, 4, 16]))
def test_flash_attention_property(b, sq, skv, heads, causal, window):
    """Property sweep: arbitrary ragged shapes, GQA ratios, masks."""
    if causal and sq > skv:
        skv = sq      # causal suffix layout needs Skv >= Sq
    hq, hkv = heads
    q = randn(b, sq, hq, 8)
    k = randn(b, skv, hkv, 8)
    v = randn(b, skv, hkv, 8)
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    tr = lambda x: x.transpose(0, 2, 1, 3)
    got = tr(flash_attention(tr(q), tr(k), tr(v), causal=causal,
                             window=window, block_q=8, block_k=8,
                             interpret=True))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5, rtol=3e-5)


# ---------------------------------------------------------------------------
# ssm scan
# ---------------------------------------------------------------------------

SSM_CASES = [
    # B, S, C, N, chunk
    (2, 32, 8, 4, 8),
    (1, 17, 4, 8, 8),       # ragged seq
    (1, 1, 8, 16, 4),       # decode: single step
    (3, 64, 16, 8, 16),
]


@pytest.mark.parametrize("case", SSM_CASES)
def test_ssm_scan_matches_ref(case):
    B, S, C, N, chunk = case
    x = randn(B, S, C)
    dt = jnp.abs(randn(B, S, C)) * 0.1
    A = -jnp.abs(randn(C)) - 0.1
    Bm = randn(B, S, N)
    Cm = randn(B, S, N)
    y_want, h_want = ref.ssm_scan_ref(x, dt, A, Bm, Cm)
    y_got, h_got = ssm_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(y_got), np.asarray(y_want),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h_got), np.asarray(h_want),
                               atol=1e-4, rtol=1e-4)


def test_ssm_scan_initial_state_threading():
    """Chunked scan with h0 == running the ref in two halves."""
    B, S, C, N = 1, 24, 4, 4
    x, dt = randn(B, S, C), jnp.abs(randn(B, S, C)) * 0.1
    A = -jnp.abs(randn(C)) - 0.1
    Bm, Cm = randn(B, S, N), randn(B, S, N)
    y1, h1 = ref.ssm_scan_ref(x[:, :12], dt[:, :12], A, Bm[:, :12],
                              Cm[:, :12])
    y2, h2 = ref.ssm_scan_ref(x[:, 12:], dt[:, 12:], A, Bm[:, 12:],
                              Cm[:, 12:], h0=h1)
    y_got, h_got = ssm_scan(x, dt, A, Bm, Cm, chunk=6, interpret=True)
    np.testing.assert_allclose(np.asarray(y_got[:, 12:]), np.asarray(y2),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h_got), np.asarray(h2),
                               atol=1e-4, rtol=1e-4)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 2), st.integers(1, 40), st.sampled_from([2, 4, 8]),
       st.sampled_from([2, 4]), st.sampled_from([4, 8]))
def test_ssm_scan_property(b, s, c, n, chunk):
    x = randn(b, s, c)
    dt = jnp.abs(randn(b, s, c)) * 0.1
    A = -jnp.abs(randn(c)) - 0.1
    Bm, Cm = randn(b, s, n), randn(b, s, n)
    y_want, h_want = ref.ssm_scan_ref(x, dt, A, Bm, Cm)
    y_got, h_got = ssm_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(y_got), np.asarray(y_want),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# moe gating
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("T,E,k,bt", [
    (16, 8, 2, 8), (100, 16, 2, 32), (7, 128, 1, 8), (64, 16, 4, 16),
])
def test_topk_gating_matches_ref(T, E, k, bt):
    logits = randn(T, E)
    p_want, i_want = ref.topk_gating_ref(logits, k)
    p_got, i_got = topk_gating(logits, k, block_t=bt, interpret=True)
    np.testing.assert_allclose(np.asarray(p_got), np.asarray(p_want),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(i_got), np.asarray(i_want))


def test_topk_gating_probs_sum_to_one():
    logits = randn(33, 16)
    p, i = topk_gating(logits, 3, block_t=16, interpret=True)
    np.testing.assert_allclose(np.asarray(p.sum(-1)), 1.0, atol=1e-5)
    assert ((0 <= np.asarray(i)) & (np.asarray(i) < 16)).all()


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 50), st.sampled_from([4, 16, 64]),
       st.sampled_from([1, 2, 4]))
def test_topk_gating_property(t, e, k):
    logits = randn(t, e)
    p_want, i_want = ref.topk_gating_ref(logits, k)
    p_got, i_got = topk_gating(logits, k, block_t=16, interpret=True)
    np.testing.assert_allclose(np.asarray(p_got), np.asarray(p_want),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(i_got), np.asarray(i_want))


# ---------------------------------------------------------------------------
# lasso cd
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,U,bn", [(64, 8, 16), (100, 4, 32), (7, 16, 8),
                                    (256, 32, 64)])
def test_lasso_partial_matches_ref(n, U, bn):
    X, r = randn(n, U), randn(n)
    want = ref.lasso_partial_ref(X, r)
    got = lasso_partial(X, r, block_n=bn, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("n,U,bn", [(64, 8, 16), (100, 12, 32), (9, 4, 8)])
def test_gram_block_matches_ref(n, U, bn):
    X = randn(n, U)
    want = ref.gram_ref(X)
    got = gram_block(X, block_n=bn, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 80), st.integers(1, 16), st.sampled_from([8, 16, 32]))
def test_lasso_partial_property(n, u, bn):
    X, r = randn(n, u), randn(n)
    want = ref.lasso_partial_ref(X, r)
    got = lasso_partial(X, r, block_n=bn, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# split chain
# ---------------------------------------------------------------------------

def _split_loop(key, n):
    subs = []
    for _ in range(n):
        key, sub = jax.random.split(key)
        subs.append(np.asarray(jax.random.key_data(sub)))
    return np.stack(subs)


# within one block, exactly one, and over a block boundary
@pytest.mark.parametrize("seed,n", [(0, 5), (1, split_chain.BLOCK),
                                    (2**31 + 7, split_chain.BLOCK + 3)])
def test_split_chain_kernel_gives_the_split_bits(seed, n):
    """The scalar-core kernel (interpret mode) and the scan both give the
    subkeys a loop of ``jax.random.split`` gives, bit for bit."""
    key = jax.random.fold_in(jax.random.key(17), seed % 2**32)
    want = np.asarray(split_chain._scan_chain(key, n))
    np.testing.assert_array_equal(want[:5], _split_loop(key, 5))
    got = split_chain._pallas_chain(key, n, interpret=True)
    assert got.dtype == jnp.uint32 and got.shape == (n, 2)
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(
        np.asarray(split_chain.subkeys(key, n)), want)


def test_split_chain_other_key_impl_scans_splits():
    key = jax.random.key(3, impl="rbg")
    np.testing.assert_array_equal(np.asarray(split_chain.subkeys(key, 4)),
                                  _split_loop(key, 4))


# ---------------------------------------------------------------------------
# entry sweeps (MF's indexed reads and segment sums)
# ---------------------------------------------------------------------------

def _gapped_rows(n, C):
    """Sorted indices in three clusters steps apart: one block spans
    every step of an n = 120,049 table."""
    r = np.random.default_rng(5)
    parts = [r.integers(0, 100, C // 3), r.integers(40000, 40100, C // 3),
             r.integers(n - 60, n, C - 2 * (C // 3))]
    return np.sort(np.concatenate(parts))


SWEEP_CASES = {
    # name: (n, C, indices from (rng, n, C))
    "random_columns": (17770, 5000, lambda r, n, C: r.integers(0, n, C)),
    "sorted_rows": (120049, 9000,
                    lambda r, n, C: np.sort(r.integers(0, n, C))),
    "gapped_rows": (120049, entry_sweep.BLOCK,
                    lambda r, n, C: _gapped_rows(n, C)),
    "all_on_the_sentinel": (120049, 2000,
                            lambda r, n, C: np.full(C, n - 1)),
    "ragged": (300, 1000, lambda r, n, C: r.integers(0, n, C)),
}


def _sweep_case(name):
    n, C, draw = SWEEP_CASES[name]
    r = np.random.default_rng(sorted(SWEEP_CASES).index(name))
    idx = np.asarray(draw(r, n, C), np.int32)
    v = jnp.asarray(r.normal(size=n).astype(np.float32) * 0.03)
    x, y = (jnp.asarray(r.normal(size=C).astype(np.float32))
            for _ in range(2))
    return n, jnp.asarray(idx), v, x, y


@pytest.mark.parametrize("name", sorted(SWEEP_CASES))
def test_entry_take_is_bit_exact(name):
    """The one-hot contraction (interpret mode) returns v[idx] bit for
    bit: three bfloat16 parts of each float32, float32 accumulation."""
    n, idx, v, _, _ = _sweep_case(name)
    got = entry_sweep.take_kernel(v, idx, interpret=True)
    assert got.shape == idx.shape and got.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(v[idx]))


@pytest.mark.parametrize("name", sorted(SWEEP_CASES))
def test_entry_segment_sums_match_segment_sum(name):
    """Both sums agree with ``jax.ops.segment_sum`` within 1e-6 of the
    sum of the magnitudes in each segment (float32 added in another
    order)."""
    n, idx, _, x, y = _sweep_case(name)
    got = entry_sweep.segment_sums_kernel(x, y, idx, n, interpret=True)
    for a, val in zip(got, (x, y)):
        assert a.shape == (n,)
        want = jax.ops.segment_sum(val, idx, n)
        scale = jax.ops.segment_sum(jnp.abs(val), idx, n)
        assert np.all(np.abs(np.asarray(a - want))
                      <= 1e-6 * np.asarray(scale))


@pytest.mark.parametrize("name", sorted(SWEEP_CASES))
def test_entry_sweep_steps_count_the_kernels_windows(name):
    """The host count of contraction steps is the steps the kernels'
    windows (their scalar prefetch) make them take."""
    n, idx, _, _, _ = _sweep_case(name)
    lo, hi = entry_sweep._windows(entry_sweep._rows(idx),
                                  entry_sweep._steps(n))
    assert entry_sweep.sweep_steps(np.asarray(idx)) == int(
        jnp.sum(hi - lo + 1))


def test_entry_sweep_steps_follow_the_indices_span():
    """Sorted rows take about one step a block, random columns below
    17,770 two, and a block of rows in clusters steps apart every step
    between them."""
    r = np.random.default_rng(0)
    C, B = 40 * entry_sweep.BLOCK, entry_sweep.BLOCK
    rows = np.sort(r.integers(0, 120049, C))
    assert C // B <= entry_sweep.sweep_steps(rows) <= C // B + 8
    assert entry_sweep.sweep_steps(r.integers(0, 17770, C)) == 2 * C // B
    assert entry_sweep.sweep_steps(
        _gapped_rows(120049, entry_sweep.BLOCK)) == 8


def test_entry_sweep_off_the_tpu_is_xla():
    """Off a TPU the sweeps are XLA's gather and scatter-adds."""
    n, idx, v, x, y = _sweep_case("random_columns")
    np.testing.assert_array_equal(np.asarray(entry_sweep.take(v, idx)),
                                  np.asarray(v[idx]))
    a, b = jax.jit(entry_sweep.segment_sums, static_argnums=3)(x, y, idx, n)
    np.testing.assert_array_equal(np.asarray(a),
                                  np.asarray(jax.ops.segment_sum(x, idx, n)))
    np.testing.assert_array_equal(np.asarray(b),
                                  np.asarray(jax.ops.segment_sum(y, idx, n)))


# ---------------------------------------------------------------------------
# LDA's Gibbs token loop (kernels/gibbs_scan.py)
# ---------------------------------------------------------------------------

def _repeats(r, T, rows, n, spread):
    """``rows`` (T,) from ``n`` with the row groups of :data:`RING` + 2
    pairs repeated at distances 1, 2, ..., RING + 1 apart (the tokens
    between them in groups of their own), then a run of one row 40 long."""
    rows = rows.copy()
    groups = r.permutation(n // gibbs_scan.SUBLANES)
    at, g = 5, 0
    for d in range(1, gibbs_scan.RING + 2):
        for i in range(d + 1):
            rows[at + i] = groups[g % len(groups)] * gibbs_scan.SUBLANES \
                + r.integers(spread)
            g += 1
        rows[at + d] = rows[at]
        at += d + 3
    rows[at:at + 40] = rows[at]
    return rows


#: name: (K, V, docs, T, workers, block, BLOCK, CHUNK)
GIBBS_CASES = {
    # K a multiple of neither 128 nor the lanes' tile; one block; T not a
    # whole number of grid steps
    "k64": (64, 600, 96, 700, 1, 0, 512, 8192),
    # K = 200 (two lane tiles, the second part padding), a rotation's
    # second block: block_start != 0, the first block's tokens unscheduled
    "k200_second_block": (200, 600, 96, 700, 2, 1, 512, 8192),
    # chunks of 256 in grid steps of 128: the chunk loop, a ring that
    # does not carry across a chunk, a last chunk mostly padding
    "chunks": (64, 300, 96, 600, 1, 0, 128, 256),
}


def _gibbs_case(name, monkeypatch):
    from repro.apps import lda
    K, V, dpw, T, W, block, blk, chunk = GIBBS_CASES[name]
    monkeypatch.setattr(gibbs_scan, "BLOCK", blk)
    monkeypatch.setattr(gibbs_scan, "CHUNK", chunk)
    cfg = lda.LDAConfig(vocab=V, num_topics=K, num_workers=W,
                        tokens_per_worker=T, docs_per_worker=dpw)
    Vb = cfg.block_vocab
    r = np.random.default_rng(sorted(GIBBS_CASES).index(name))
    words = np.minimum(r.zipf(1.3, T) - 1, V - 1).astype(np.int32)
    words = block * Vb + _repeats(r, T, words % Vb, Vb, 3)
    docs = _repeats(r, T, r.integers(0, dpw, T), dpw, 8).astype(np.int32)
    if W > 1:                            # the other block's tokens
        other = r.random(T) < 0.3
        words[other] = (words[other] + Vb) % cfg.padded_vocab
    words[r.random(T) < 0.05] = -1
    words = np.minimum(words, V - 1).astype(np.int32)
    z = r.integers(0, min(K, 4), T).astype(np.int32)
    B = np.zeros((cfg.padded_vocab, K), np.float32)
    D = np.zeros((dpw, K), np.float32)
    on = words >= 0
    np.add.at(B, (words[on], z[on]), 1)
    np.add.at(D, (docs[on], z[on]), 1)
    s = B.sum(axis=0)
    active = (words >= 0) & (words // Vb == block)
    key = jax.random.fold_in(jax.random.key(17), block)
    vloc = np.clip(words - block * Vb, 0, Vb - 1)
    args = (jnp.asarray(B[block * Vb:(block + 1) * Vb]), jnp.asarray(D),
            jnp.asarray(s), jnp.asarray(vloc), jnp.asarray(docs),
            jnp.asarray(z), jnp.asarray(active))
    return cfg, args, key


def _gibbs_oracle(cfg, args, key):
    from repro.apps import lda
    keys = split_chain.subkeys(key, args[3].shape[0])
    return jax.jit(functools.partial(
        lda._gibbs_loop, cfg, impl=jax.random.key_impl(key)))(*args, keys)


def _gibbs_kernel(cfg, args, key, interpret):
    keys = jax.random.wrap_key_data(split_chain.subkeys(
        key, args[3].shape[0]), impl=jax.random.key_impl(key))
    return gibbs_scan.gibbs_scan_kernel(
        *args, keys, alpha=cfg.alpha, gamma=cfg.gamma,
        vgamma=cfg.padded_vocab * cfg.gamma, interpret=interpret)


@pytest.mark.parametrize("name", sorted(GIBBS_CASES))
def test_gibbs_kernel_is_the_loop_bit_for_bit(name, monkeypatch):
    """The kernel (Pallas' TPU interpreter: a DMA lands when it is waited
    for, unwritten memory reads NaN) draws the XLA loop's topics and
    leaves its B, D and s̃, bit for bit: rows and documents repeated at
    every distance up to past the ring, runs of one row, unscheduled
    tokens and padding."""
    cfg, args, key = _gibbs_case(name, monkeypatch)
    B, D, s, z, fwd = _gibbs_kernel(
        cfg, args, key, pltpu.InterpretParams())
    want = _gibbs_oracle(cfg, args, key)
    active = np.asarray(args[6])
    np.testing.assert_array_equal(np.asarray(B), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(D), np.asarray(want[1]))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(want[2]))
    np.testing.assert_array_equal(np.asarray(z)[active],
                                  np.asarray(want[3])[active])
    # the draws moved topics, and the ring served reads at every distance
    assert np.any(np.asarray(want[3]) != np.asarray(args[5]))
    flags = np.asarray(gibbs_scan.token_table(*args[3:]))[:, 3]
    for shift in (gibbs_scan._B_SHIFT, gibbs_scan._D_SHIFT):
        back = (flags >> shift) & gibbs_scan._DIST_MASK
        assert set(back[back > 0]) == set(range(1, gibbs_scan.RING))
    assert int(fwd) == int(gibbs_scan.forwarded(args[3], args[4], args[6]))


def test_gibbs_kernel_hazard_table_follows_the_ring(monkeypatch):
    """A token forwards a row group last used fewer than RING tokens
    before it (in its chunk, by an active token) and reads HBM otherwise;
    a token writes its group back unless an active token within RING
    after it forwards it."""
    monkeypatch.setattr(gibbs_scan, "CHUNK", 512)
    rows = np.array([0, 3, 16, 9, 0] + [64 + 8 * i for i in range(6)]
                    + [16, 17] + [200] * 499, np.int32)
    active = np.ones(rows.shape, bool)
    active[3] = False                   # unscheduled: in nobody's chain
    table = np.asarray(gibbs_scan.token_table(
        jnp.asarray(rows), jnp.asarray(rows), jnp.zeros_like(rows),
        jnp.asarray(active)))
    assert table.shape == (512, 4)
    flags = table[:, 3]
    back = (flags >> gibbs_scan._B_SHIFT) & gibbs_scan._DIST_MASK
    writes = flags & gibbs_scan._B_WRITE != 0
    np.testing.assert_array_equal(back[:13],
                                  [0, 1, 0, 0, 3, 0, 0, 0, 0, 0, 0, 0, 1])
    assert (flags[:13] & gibbs_scan._ACTIVE != 0).tolist() == \
        active[:13].tolist()
    # 16 at token 2 and token 11 are 9 apart: both go through HBM
    np.testing.assert_array_equal(writes[:13],
                                  [0, 0, 1, 0, 1, 1, 1, 1, 1, 1, 1, 0, 1])
    assert back[13:].tolist() == [0] + [1] * 498
    assert not writes[13:-1].any() and writes[-1]


def test_gibbs_noise_gives_categoricals_draws():
    """``noise`` drawn for a batch of subkeys at once gives each token the
    draw ``jax.random.categorical`` makes with its subkey."""
    K, n = 200, 64
    key = jax.random.key(5)
    subs = jax.random.wrap_key_data(split_chain.subkeys(key, n))
    logits = jnp.asarray(R.normal(size=(n, K)), jnp.float32)
    eps = gibbs_scan.noise(subs, K)
    assert eps.shape == (n, K) and eps.dtype == jnp.float32
    want = jnp.stack([jax.random.categorical(subs[i], logits[i])
                      for i in range(n)])
    np.testing.assert_array_equal(np.asarray(jnp.argmax(eps + logits, 1)),
                                  np.asarray(want))


def test_gibbs_forwarded_count_is_the_lda_counters(monkeypatch):
    """The kernel's own count of ring reads is what ``StradsLDA.obs_counts``
    reports as ``forwarded`` for the same tokens."""
    from repro.apps import lda
    from repro.core import single_device_mesh
    cfg, args, key = _gibbs_case("k64", monkeypatch)
    fwd = _gibbs_kernel(cfg, args, key, True)[4]
    eng = lda.make_engine(cfg, single_device_mesh())
    words = np.where(np.asarray(args[6]), np.asarray(args[3]), -1)
    data = {"words": jnp.asarray(words), "docs": args[4]}
    counts = eng.app.obs_counts(data, None, 0)
    assert len(counts) == 3
    assert int(counts[2]) == int(fwd) > 0
    assert int(counts[1]) == int(np.sum(np.asarray(args[6])))


def test_gibbs_scan_off_the_tpu_is_the_loop():
    """Off a TPU ``lda._gibbs_scan`` is the XLA loop: its compiled program
    holds no Pallas kernel but the split chain's off-TPU scan."""
    from repro.apps import lda
    cfg = lda.LDAConfig(vocab=40, num_topics=8, num_workers=1,
                        tokens_per_worker=64, docs_per_worker=4)
    T, K = 64, 8
    text = jax.jit(functools.partial(lda._gibbs_scan, cfg)).lower(
        jnp.zeros((40, K)), jnp.zeros((4, K)), jnp.zeros((K,)),
        jnp.zeros((T,), jnp.int32), jnp.zeros((T,), jnp.int32),
        jnp.zeros((T,), jnp.int32), jnp.ones((T,), bool), jnp.int32(0),
        jax.random.key(0)).as_text()
    assert "tpu_custom_call" not in text and "gibbs_scan" not in text


# ---------------------------------------------------------------------------
# ops dispatch
# ---------------------------------------------------------------------------

def test_ops_ref_and_interpret_agree():
    q = randn(1, 16, 2, 8)
    k = randn(1, 16, 1, 8)
    v = randn(1, 16, 1, 8)
    a = ops.attention(q, k, v, backend="ref")
    b = ops.attention(q, k, v, backend="interpret", block_q=8, block_k=8)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)

    logits = randn(12, 8)
    pa, ia = ops.topk_gating(logits, 2, backend="ref")
    pb, ib = ops.topk_gating(logits, 2, backend="interpret", block_t=8)
    np.testing.assert_allclose(np.asarray(pa), np.asarray(pb), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(ia), np.asarray(ib))


def test_ops_auto_resolves_to_ref_on_cpu():
    q = randn(1, 8, 1, 4)
    out = ops.attention(q, q, q)     # backend="auto" on CPU → ref path
    assert out.shape == (1, 8, 1, 4)


# ---------------------------------------------------------------------------
# KernelSpec: validation, JSON round-trip, defaults table
# ---------------------------------------------------------------------------

def test_kernel_spec_is_hashable_value():
    a = KernelSpec(kind="pallas", block_n=128)
    b = KernelSpec(kind="pallas", block_n=128)
    assert a == b and hash(a) == hash(b)
    assert len({a, b}) == 1
    with pytest.raises(Exception):       # frozen
        a.kind = "reference"


def test_kernel_spec_rejects_unknown_kind():
    with pytest.raises(ValueError,
                       match="kernel kind must be 'reference' or 'pallas'"):
        KernelSpec(kind="mosaic")


def test_kernel_spec_rejects_unused_fields_per_kind():
    # reference consumes no knobs — a nonzero block_n would be silently
    # ignored, so it raises instead
    with pytest.raises(ValueError, match="does not apply to kind="):
        KernelSpec(kind="reference", block_n=64)


@pytest.mark.parametrize("bad", [0, -1, 2.5, True, "256"])
def test_kernel_spec_pallas_needs_positive_int_block_n(bad):
    with pytest.raises(ValueError):
        KernelSpec(kind="pallas", block_n=bad)


def test_kernel_spec_json_round_trip_exact():
    for spec in (KernelSpec(kind="reference"),
                 KernelSpec(kind="pallas", block_n=64)):
        d = spec.to_json()
        assert KernelSpec.from_json(d) == spec
        assert KernelSpec.from_json(json.dumps(d)) == spec
        # every field present, defaults included
        assert set(d) == {"kind", "block_n"}


def test_kernel_spec_from_json_rejects_unknown_keys():
    with pytest.raises(ValueError, match="unknown KernelSpec field"):
        KernelSpec.from_json({"kind": "pallas", "block_n": 64,
                              "tile_m": 8})
    with pytest.raises(TypeError):
        KernelSpec.from_json([1, 2])


def test_kernel_spec_default_for():
    assert KernelSpec.default_for("reference") == KernelSpec(
        kind="reference")
    assert KernelSpec.default_for("pallas") == KernelSpec(
        kind="pallas", block_n=DEFAULT_BLOCK_N)
    assert KernelSpec.default_for("pallas", block_n=32).block_n == 32
    with pytest.raises(ValueError, match="kernel kind must be"):
        KernelSpec.default_for("mosaic")
    assert set(KERNEL_KINDS) == {"reference", "pallas"}


# ---------------------------------------------------------------------------
# build_kernels registry + backend agreement
# ---------------------------------------------------------------------------

def test_build_kernels_resolves_kinds_and_platform():
    rb = build_kernels(KernelSpec(kind="reference"))
    assert isinstance(rb, ReferenceKernels)
    pb = build_kernels(KernelSpec.default_for("pallas"), platform="cpu")
    assert isinstance(pb, PallasKernels) and pb.interpret
    pt = build_kernels(KernelSpec.default_for("pallas"), platform="tpu")
    assert not pt.interpret
    with pytest.raises(TypeError, match="wants a repro.kernels.KernelSpec"):
        build_kernels({"kind": "reference"})


@settings(max_examples=12, deadline=None)
@given(st.sampled_from([1, 7, 100, 127, 128, 129, 255, 300]),
       st.integers(1, 16), st.sampled_from([8, 128, DEFAULT_BLOCK_N]))
def test_backends_agree_lasso_partial(n, u, bn):
    """Pallas ≡ reference through the backend objects, including the
    128-lane padding edges (n ∈ {127, 128, 129})."""
    spec = KernelSpec(kind="pallas", block_n=bn)
    pb = build_kernels(spec, platform="cpu")
    rb = build_kernels(KernelSpec(kind="reference"))
    X, r = randn(n, u), randn(n)
    np.testing.assert_allclose(np.asarray(pb.lasso_partial(X, r)),
                               np.asarray(rb.lasso_partial(X, r)),
                               atol=1e-4, rtol=1e-4)


@settings(max_examples=12, deadline=None)
@given(st.sampled_from([1, 9, 100, 127, 128, 129, 300]),
       st.integers(1, 12), st.sampled_from([8, 128, DEFAULT_BLOCK_N]))
def test_backends_agree_gram_block(n, c, bn):
    spec = KernelSpec(kind="pallas", block_n=bn)
    pb = build_kernels(spec, platform="cpu")
    rb = build_kernels(KernelSpec(kind="reference"))
    X = randn(n, c)
    np.testing.assert_allclose(np.asarray(pb.gram_block(X)),
                               np.asarray(rb.gram_block(X)),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# plan-level contract: kernels on the ExecutionPlan
# ---------------------------------------------------------------------------

def _bit_identical(a_state, b_state):
    assert set(a_state) == set(b_state)
    for k in a_state:
        a, b = np.asarray(a_state[k]), np.asarray(b_state[k])
        assert (a == b).all(), (k, np.max(np.abs(a - b)))


@pytest.fixture(scope="module")
def mesh():
    return single_device_mesh()


@pytest.fixture(scope="module")
def lasso_setup():
    rng = np.random.default_rng(7)
    X, y, _ = lasso.synthetic_correlated(rng, n=40, J=20, k_true=3)
    cfg = lasso.LassoConfig(num_features=20, lam=0.02, block_size=4,
                            num_candidates=8, rho=0.3)
    return cfg, X, y


_EXEC_CASES = [("loop", 0), ("scan", 0), ("pipelined", 0), ("ssp", 1)]


@pytest.mark.parametrize("executor,staleness", _EXEC_CASES)
def test_plan_kernels_none_is_bit_identical_to_reference(
        mesh, lasso_setup, executor, staleness):
    """kernels=None resolves (app default → reference on CPU) to the
    exact pre-KernelSpec round body — bit-identical on every executor."""
    cfg, X, y = lasso_setup

    def run(spec):
        plan = ExecutionPlan(executor=executor, rounds=4,
                             staleness=staleness, kernels=spec)
        state, _ = lasso.fit(cfg, X, y, mesh, plan=plan)
        return state

    _bit_identical(run(None), run(KernelSpec(kind="reference")))


@pytest.mark.parametrize("executor,staleness", _EXEC_CASES)
def test_plan_kernels_pallas_agrees_on_every_executor(
        mesh, lasso_setup, executor, staleness):
    cfg, X, y = lasso_setup

    def run(spec):
        plan = ExecutionPlan(executor=executor, rounds=4,
                             staleness=staleness, kernels=spec)
        state, _ = lasso.fit(cfg, X, y, mesh, plan=plan)
        return state

    a = run(KernelSpec(kind="reference"))
    b = run(KernelSpec.default_for("pallas"))
    for k in a:
        np.testing.assert_allclose(np.asarray(a[k]), np.asarray(b[k]),
                                   atol=1e-5, rtol=1e-5)


def test_plan_validates_kernels_field():
    with pytest.raises(ValueError,
                       match="kernels must be None or a "
                             "repro.kernels.KernelSpec"):
        ExecutionPlan(executor="scan", rounds=2,
                      kernels={"kind": "reference"})
    p = ExecutionPlan(executor="scan", rounds=2,
                      kernels=KernelSpec.default_for("pallas"))
    assert ExecutionPlan.from_json(p.to_json()) == p
    assert ExecutionPlan.from_json(p.to_json()).kernels.block_n \
        == DEFAULT_BLOCK_N


def test_engine_installs_resolved_backend(mesh, lasso_setup):
    cfg, X, y = lasso_setup
    eng = lasso.make_engine(cfg, mesh)
    data = eng.shard_data({"X": jnp.asarray(X), "y": jnp.asarray(y)})
    state = eng.init_state(jax.random.key(0), y=y)
    plan = ExecutionPlan(executor="scan", rounds=2,
                         kernels=KernelSpec.default_for("pallas"))
    eng.execute(state, data, jax.random.key(1), plan)
    assert isinstance(eng.kernels, PallasKernels)
    assert eng.kernel_spec == KernelSpec.default_for("pallas")
    # back to a plan without kernels: the app default (reference on this
    # CPU container) is re-resolved, not left stale
    plan2 = ExecutionPlan(executor="scan", rounds=2)
    state = eng.init_state(jax.random.key(0), y=y)
    eng.execute(state, data, jax.random.key(1), plan2)
    assert isinstance(eng.kernels, ReferenceKernels)
    assert eng.kernel_spec == KernelSpec(kind="reference")


def test_apps_without_pallas_hotspots_reject_the_kind(mesh):
    """supported_kernel_kinds gates injection: LDA/MF have no Pallas
    hot-spot, so a pallas plan fails loudly at set time."""
    from repro.apps import mf
    cfg = mf.MFConfig(num_rows=8, num_cols=8, rank=4)
    eng = mf.make_engine(cfg, mesh)
    with pytest.raises(ValueError, match="cannot dispatch a 'pallas'"):
        eng.set_kernels(KernelSpec.default_for("pallas"))
    # the reference kind still installs fine
    assert isinstance(eng.set_kernels(KernelSpec(kind="reference")),
                      ReferenceKernels)


def test_lasso_default_kernel_spec_maps_legacy_backend_names():
    assert lasso.StradsLasso(
        lasso.LassoConfig(num_features=8, kernel_backend="ref")
    ).default_kernel_spec() == KernelSpec(kind="reference")
    for legacy in ("pallas", "interpret"):
        assert lasso.StradsLasso(
            lasso.LassoConfig(num_features=8, kernel_backend=legacy)
        ).default_kernel_spec() == KernelSpec.default_for("pallas")
    # "auto" picks by live platform — reference on this CPU container
    auto = lasso.StradsLasso(
        lasso.LassoConfig(num_features=8)).default_kernel_spec()
    assert auto.kind == ("pallas" if jax.default_backend() == "tpu"
                         else "reference")
    with pytest.raises(ValueError, match="kernel_backend must be"):
        lasso.StradsLasso(
            lasso.LassoConfig(num_features=8, kernel_backend="cuda")
        ).default_kernel_spec()
