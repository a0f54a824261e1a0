"""Model-substrate correctness: decode/forward parity, attention variants,
MoE dispatch equivalence, chunked-scan equivalence, sharding helpers.

The decode-parity tests are the strongest invariant in the system: running
prefill + N decode steps must reproduce the same logits as one full
forward pass, for every family (attention ring buffers, SSM states, xLSTM
matrix memories)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import ARCHS, get_config
from repro.core.compat import make_mesh
from repro.kernels import ref
from repro.models import model as M
from repro.models.layers import _chunked_attention, _sdpa_grouped
from repro.models.scan_utils import chunked_scan, default_chunk
from repro.sharding import rules


# ---------------------------------------------------------------------------
# decode parity: prefill + decode steps == full forward
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", [a for a in ARCHS
                                  if not get_config(a).encoder_only])
def test_decode_matches_forward(arch):
    cfg = get_config(arch).reduced()
    if cfg.family == "moe":
        # capacity accounting is per dispatch group, so drop patterns
        # differ between a 24-token forward and a 1-token decode; parity
        # is only defined in the no-drop regime.
        cfg = dataclasses.replace(cfg, capacity_factor=8.0)
    key = jax.random.PRNGKey(0)
    prm = M.init_params(cfg, key)
    B, S, T = 2, 24, 4                      # prompt 24, decode 4
    toks = jax.random.randint(key, (B, S + T), 0, cfg.vocab_size)
    batch = {"tokens": toks}
    n_front = 0
    if cfg.frontend == "vision":
        batch["frontend"] = jax.random.normal(
            key, (B, cfg.frontend_tokens, cfg.d_model), jnp.float32) * 0.02
        n_front = cfg.frontend_tokens

    full_logits, _ = M.forward(cfg, prm, batch)            # (B, S+T, Vp)

    pre = dict(batch, tokens=toks[:, :S])
    lg, cache = M.prefill(cfg, prm, pre, cache_len=S + T + n_front)
    got = [lg]
    for t in range(T - 1):
        lg, cache = M.decode_step(cfg, prm, cache, toks[:, S + t],
                                  jnp.int32(S + t + n_front))
        got.append(lg)
    want = full_logits[:, S - 1:S + T - 1]
    got = jnp.stack(got, axis=1)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=0, atol=2e-2)


def test_decode_ring_buffer_window_matches_forward():
    """Sliding-window decode with a ring-buffer cache smaller than the
    sequence must equal windowed full attention."""
    cfg = dataclasses.replace(get_config("granite-3-2b").reduced(),
                              window=8)
    key = jax.random.PRNGKey(1)
    prm = M.init_params(cfg, key)
    B, S, T, W = 2, 12, 6, 8
    toks = jax.random.randint(key, (B, S + T), 0, cfg.vocab_size)
    full_logits, _ = M.forward(cfg, prm, {"tokens": toks}, window=W)
    lg, cache = M.prefill(cfg, prm, {"tokens": toks[:, :S]}, cache_len=W,
                          window=W)
    got = [lg]
    for t in range(T - 1):
        lg, cache = M.decode_step(cfg, prm, cache, toks[:, S + t],
                                  jnp.int32(S + t), window=W)
        got.append(lg)
    want = full_logits[:, S - 1:S + T - 1]
    np.testing.assert_allclose(np.asarray(jnp.stack(got, 1), np.float32),
                               np.asarray(want, np.float32),
                               rtol=0, atol=2e-2)


# ---------------------------------------------------------------------------
# attention variants
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2), (6, 1)])
@pytest.mark.parametrize("window", [None, 7])
def test_grouped_sdpa_matches_ref(hq, hkv, window):
    key = jax.random.PRNGKey(0)
    B, S, D = 2, 33, 16
    q = jax.random.normal(key, (B, S, hq, D), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, hkv, D))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, hkv, D))
    want = ref.attention_ref(q, k, v, causal=True, window=window)
    got = _sdpa_grouped(q, k, v, causal=True, window=window, q_offset=0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=1e-5)


@pytest.mark.parametrize("sq,skv", [(64, 64), (40, 40), (16, 48)])
def test_chunked_attention_matches_full(sq, skv):
    key = jax.random.PRNGKey(3)
    B, H, K, D = 2, 4, 2, 8
    q = jax.random.normal(key, (B, sq, H, D), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, skv, K, D))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, skv, K, D))
    want = ref.attention_ref(q, k, v, causal=True)
    got = _chunked_attention(q, k, v, causal=True, window=None, chunk=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=1e-5)


# ---------------------------------------------------------------------------
# chunked_scan (sqrt remat) equivalence, incl. gradients
# ---------------------------------------------------------------------------

@given(st.integers(5, 70))
@settings(max_examples=10, deadline=None)
def test_chunked_scan_matches_plain(S):
    xs = jnp.sin(jnp.arange(S * 3, dtype=jnp.float32)).reshape(S, 3)

    def step(c, x):
        c = 0.9 * c + x
        return c, c * 2.0

    c0 = jnp.zeros((3,))
    want_c, want_y = jax.lax.scan(step, c0, xs)
    got_c, got_y = chunked_scan(step, c0, xs)
    np.testing.assert_allclose(np.asarray(got_c), np.asarray(want_c),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                               rtol=1e-6)

    g1 = jax.grad(lambda x: jax.lax.scan(step, c0, x)[1].sum())(xs)
    g2 = jax.grad(lambda x: chunked_scan(step, c0, x)[1].sum())(xs)
    np.testing.assert_allclose(np.asarray(g2), np.asarray(g1), rtol=1e-5)


def test_default_chunk_divides():
    for s in (1, 7, 64, 100, 4096, 32768):
        k = default_chunk(s)
        assert s % k == 0 and k >= 1


# ---------------------------------------------------------------------------
# MoE dispatch equivalence (einsum vs sort) and capacity drops
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["phi3.5-moe-42b-a6.6b",
                                  "llama4-maverick-400b-a17b"])
def test_moe_einsum_equals_sort_dispatch(arch):
    cfg = dataclasses.replace(get_config(arch).reduced(),
                              capacity_factor=8.0)   # no drops
    key = jax.random.PRNGKey(0)
    prm = M.init_params(cfg, key)
    batch = {"tokens": jax.random.randint(key, (2, 16), 0, cfg.vocab_size)}
    l1, a1 = M.forward(cfg, prm, batch)
    l2, a2 = M.forward(dataclasses.replace(cfg, moe_impl="sort"),
                       prm, batch)
    np.testing.assert_allclose(np.asarray(l1, np.float32),
                               np.asarray(l2, np.float32),
                               rtol=0, atol=2e-2)
    assert abs(float(a1) - float(a2)) < 1e-6


def test_moe_capacity_drops_tokens_not_nan():
    cfg = dataclasses.replace(get_config("phi3.5-moe-42b-a6.6b").reduced(),
                              capacity_factor=0.25)  # force overflow
    key = jax.random.PRNGKey(0)
    prm = M.init_params(cfg, key)
    batch = {"tokens": jax.random.randint(key, (2, 32), 0, cfg.vocab_size)}
    logits, aux = M.forward(cfg, prm, batch)
    assert not bool(jnp.isnan(logits).any())


# ---------------------------------------------------------------------------
# Mamba2 SSD chunked form (§Perf variant) == sequential scan oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,chunk", [(256, 64), (128, 128), (96, 32)])
def test_ssd_chunked_matches_scan(S, chunk):
    from repro.kernels.ref import ssm_scan_ref
    from repro.models.ssm import ssd_chunked
    key = jax.random.PRNGKey(0)
    B, H, hd, N = 2, 3, 32, 16
    C = H * hd
    x = jax.random.normal(key, (B, S, C), jnp.float32)
    dt = jnp.repeat(jax.nn.softplus(
        jax.random.normal(jax.random.fold_in(key, 1), (B, S, H))),
        hd, axis=-1)
    A = jnp.repeat(-jnp.exp(
        jax.random.normal(jax.random.fold_in(key, 2), (H,))), hd)
    Bm = jax.random.normal(jax.random.fold_in(key, 3), (B, S, N))
    Cm = jax.random.normal(jax.random.fold_in(key, 4), (B, S, N))
    h0 = jax.random.normal(jax.random.fold_in(key, 5), (B, C, N))
    y1, h1 = ssm_scan_ref(x, dt, A, Bm, Cm, h0)
    y2, h2 = ssd_chunked(x, dt, A, Bm, Cm, h0, head_dim=hd, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y1),
                               rtol=0, atol=2e-3)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h1),
                               rtol=0, atol=2e-3)


def test_zamba_ssd_variant_matches_scan_model_level():
    cfg = get_config("zamba2-2.7b").reduced()
    key = jax.random.PRNGKey(0)
    prm = M.init_params(cfg, key)
    batch = {"tokens": jax.random.randint(key, (2, 64), 0,
                                          cfg.vocab_size)}
    l1, _ = M.forward(cfg, prm, batch)
    l2, _ = M.forward(dataclasses.replace(cfg, ssm_impl="ssd"), prm, batch)
    np.testing.assert_allclose(np.asarray(l1, np.float32),
                               np.asarray(l2, np.float32),
                               rtol=0, atol=3e-2)


# ---------------------------------------------------------------------------
# chunkwise-parallel mLSTM (§Perf xlstm iteration) == sequential cell
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,chunk", [(256, 64), (128, 128)])
def test_mlstm_chunkwise_matches_sequential(S, chunk):
    from repro.models.xlstm import _mlstm_cell, mlstm_chunkwise
    key = jax.random.PRNGKey(0)
    B, H, hd = 2, 3, 32
    qf = jax.random.normal(key, (B, S, H, hd), jnp.float32)
    kf = jax.random.normal(jax.random.fold_in(key, 1),
                           (B, S, H, hd)) * hd ** -0.5
    vf = jax.random.normal(jax.random.fold_in(key, 2), (B, S, H, hd))
    ig = jax.random.normal(jax.random.fold_in(key, 3), (B, S, H)) * 2
    fg = jax.random.normal(jax.random.fold_in(key, 4), (B, S, H)) * 2 + 1
    state = (jnp.zeros((B, H, hd, hd)), jnp.zeros((B, H, hd)),
             jnp.full((B, H), -jnp.inf))

    def step(c, x):
        h, c = _mlstm_cell(*x, c)
        return c, h
    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (qf, kf, vf, ig, fg))
    (c1, n1, m1), hs1 = jax.lax.scan(step, state, xs)
    hs1 = jnp.moveaxis(hs1, 0, 1)
    hs2, (c2, n2, m2) = mlstm_chunkwise(qf, kf, vf, ig, fg, state,
                                        chunk=chunk)
    np.testing.assert_allclose(np.asarray(hs2), np.asarray(hs1),
                               rtol=0, atol=2e-3)
    np.testing.assert_allclose(np.asarray(c2), np.asarray(c1),
                               rtol=0, atol=1e-4)
    np.testing.assert_allclose(np.asarray(m2), np.asarray(m1),
                               rtol=0, atol=1e-4)


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------

def test_padded_heads():
    assert rules.padded_heads(40, 8) == (48, 8)      # llama4
    assert rules.padded_heads(36, 36) == (48, 48)    # minicpm
    assert rules.padded_heads(32, 2) == (32, 2)      # chatglm
    assert rules.padded_heads(32, 32) == (32, 32)
    hq, kv = rules.padded_heads(14, 2)               # internvl
    assert hq % 16 == 0 and hq % kv == 0


def test_padded_vocab_is_shardable():
    for v in (504, 32000, 49155, 65024, 122753, 151655, 202048):
        vp = rules.padded_vocab(v)
        assert vp >= v and vp % (128 * rules.MODEL_AXIS_SIZE) == 0


def test_resolve_drops_nondivisible():
    mesh = make_mesh((1, 1), ("data", "model"))
    # 1-way mesh: everything divides, spec resolves without error
    spec = rules.resolve(mesh, (rules.BATCH, rules.TENSOR), (4, 6))
    assert spec is not None


def test_vocab_padding_masked_in_loss():
    from repro.train.losses import cross_entropy
    B, S, V, VP = 2, 3, 5, 8
    logits = jnp.zeros((B, S, VP))
    # put huge mass on a padded class: loss must ignore it
    logits = logits.at[..., V + 1].set(100.0)
    labels = jnp.zeros((B, S), jnp.int32)
    loss, _ = cross_entropy(logits, labels, V)
    assert float(loss) == pytest.approx(np.log(V), abs=1e-4)
