#!/usr/bin/env python3
"""Smoke run of the STRADS main path on a TPU, at deployment size.

    python3 chip_smoke.py            # one chip: lasso, LDA, MF + serving
    python3 chip_smoke.py --chips 4  # four chips: sharded lasso (BSP scan
                                     # vs SSP s=0 and s=2) + LDA rotation

Each phase goes through the public surface (``make_engine``,
``shard_data``, ``init_state``, ``StradsEngine.execute`` with an
``ExecutionPlan``, ``serve_while_training``) on data generated from
``--seed``, and checks what comes out: the objective falls, the
collapsed LDA counts are exact, serving stays within its staleness bound
and leaves training bit-identical, the Pallas kernels agree with the
reference kernels.  Every line before the last is smoke output (sizes,
device bytes, compile seconds, seconds per round) — not a benchmark
figure.  The last line is one JSON object naming the device.

Exits nonzero, printing no result, when JAX finds no TPU, and on any
failed check.  Everything runs in this one process, which holds the
chip(s) for its lifetime.  The phase functions take their sizes, so
tests drive them at tiny sizes on the CPU; only :func:`main` requires
the chip.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time
from functools import partial

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.apps import lasso, lda, mf  # noqa: E402
from repro.core import (DATA_AXIS, ExecutionPlan,  # noqa: E402
                        single_device_mesh, worker_mesh)
from repro.kernels import KernelSpec, PallasKernels, build_kernels  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.serve import ServeSpec, serve_while_training  # noqa: E402

# The sizes main() runs (see the module docstring of each phase for why).
LASSO = dict(n=16384, J=65536, rounds=32)
LDA = dict(vocab=102660, topics=1000, tokens_per_worker=2 ** 20,
           docs_per_worker=16384, rotations=3)
MF = dict(users=32768, items=17770, rank=100, rounds=8, requests=32)
FOUR_LASSO = dict(n=65536, J=65536, rounds=24)
FOUR_LDA = dict(vocab=102660, topics=1000, tokens_per_worker=2 ** 20,
                docs_per_worker=16384, rotations=2)

#: |ssp(s=2) − scan| final objective, as a share of the scan run's decrease
SSP2_OBJECTIVE_TOL = 0.1
#: Pallas-vs-reference kernel agreement, relative to Σ|x·r| (or Σ|x·x|)
KERNEL_TOL = 1e-2


class SmokeError(AssertionError):
    """A smoke check failed."""


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeError(what)


def say(phase: str, **fields) -> None:
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"smoke {phase}: {body}", flush=True)


# -- compile accounting (jax.monitoring) ------------------------------------

_COMPILE = {"seconds": 0.0, "programs": 0, "cache_hits": 0}
_LISTENING = []


def _listen() -> None:
    if _LISTENING:
        return

    def on_duration(event, duration, **_):
        # backend compile, or the persistent-cache read that replaced it
        if event == "/jax/core/compile/backend_compile_duration":
            _COMPILE["seconds"] += duration
            _COMPILE["programs"] += 1

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            _COMPILE["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    _LISTENING.append(True)


class _Phase:
    """Per-phase compile seconds and device bytes, printed on exit."""

    def __init__(self, name: str, mesh):
        self.name = name
        self.devices = list(mesh.devices.flat)

    def __enter__(self):
        _listen()
        self.c0 = dict(_COMPILE)
        self.t0 = time.perf_counter()
        self.mem("start")
        return self

    def mem(self, at: str) -> list:
        """Print (and return) each device's ``memory_stats()`` bytes in
        use; ``[]`` where the backend reports none (the CPU)."""
        stats = [d.memory_stats() for d in self.devices]
        if any(s is None for s in stats):
            say(self.name, at=at, device_bytes_in_use="not reported")
            return []
        used = [int(s["bytes_in_use"]) for s in stats]
        peak = [int(s.get("peak_bytes_in_use", 0)) for s in stats]
        say(self.name, at=at, device_bytes_in_use=used,
            device_peak_bytes=peak)
        return used

    def __exit__(self, *exc):
        if exc[0] is None:
            self.mem("end")
            say(self.name,
                compile_s=round(_COMPILE["seconds"] - self.c0["seconds"], 3),
                programs_compiled=_COMPILE["programs"]
                - self.c0["programs"],
                persistent_cache_hits=_COMPILE["cache_hits"]
                - self.c0["cache_hits"],
                phase_s=round(time.perf_counter() - self.t0, 3))
        return False


def _timed(fn):
    t = time.perf_counter()
    out = fn()
    jax.block_until_ready(out.state if hasattr(out, "state") else out)
    return out, time.perf_counter() - t


def _nbytes(tree) -> int:
    return sum(int(x.nbytes) for x in jax.tree.leaves(tree))


def _host(tree) -> dict:
    return {k: np.asarray(v) for k, v in tree.items()}


def _bit_equal(a: dict, b: dict) -> bool:
    return set(a) == set(b) and all(
        a[k].shape == b[k].shape and a[k].dtype == b[k].dtype
        and a[k].tobytes() == b[k].tobytes() for k in a)


def check_placement(tree, specs, mesh) -> None:
    """Every leaf spans every device of ``mesh``, and a row-sharded leaf
    holds 1/P of its rows on each."""
    nd = mesh.size

    def one(x, spec):
        check(len(x.sharding.device_set) == nd,
              f"a leaf of shape {x.shape} sits on "
              f"{len(x.sharding.device_set)} device(s), not {nd}")
        if len(spec) and spec[0] == DATA_AXIS:
            rows = {s.data.shape[0] for s in x.addressable_shards}
            check(rows == {x.shape[0] // nd},
                  f"row-sharded leaf {x.shape} has shard rows {rows}")

    jax.tree.map(one, tree, specs)


def check_even_bytes(used: list, on_chip: bool) -> None:
    """Per-device bytes in use are each about 1/P of the total."""
    if not used:
        check(not on_chip, "the chip reported no memory_stats()")
        return
    mean = sum(used) / len(used)
    check(max(used) <= 1.25 * mean and min(used) >= 0.75 * mean,
          f"device bytes in use {used} are not even across devices")


# ---------------------------------------------------------------------------
# Lasso
# ---------------------------------------------------------------------------

def lasso_design(mesh, n: int, J: int, seed: int, **kw):
    """The paper's correlated design (``lasso.synthetic_correlated``'s
    construction), drawn on the device: (X, y), both row-sharded over
    ``mesh`` — no host copy of X exists."""
    return lasso_design_fn(mesh, n, J, **kw)(jax.random.key(seed))


def lasso_design_fn(mesh, n: int, J: int, *, corr: float = 0.9,
                    k_true: int = 64, noise: float = 0.1, block: int = 512):
    """The jitted generator behind :func:`lasso_design` (a key → (X, y)),
    drawn in column blocks: with prob ``corr`` column j is fresh U(0,1)
    noise, else 0.9·x_{j−1} + 0.1·noise; columns standardized to zero
    mean and unit L2 (per column, so exact blockwise); y from a
    ``k_true``-sparse β* plus noise, centered."""
    Jb = min(block, J)
    check(J % Jb == 0, f"J={J} must be a multiple of the block {Jb}")
    rows = NamedSharding(mesh, P(DATA_AXIS))
    cols_by_row = NamedSharding(mesh, P(None, DATA_AXIS))

    @partial(jax.jit, out_shardings=(rows, rows))
    def gen(key):
        kx, ks, kv, kn = jax.random.split(key, 4)

        def column_block(b, carry):
            X, prev = carry
            ke, kf = jax.random.split(jax.random.fold_in(kx, b))
            eps = jax.lax.with_sharding_constraint(
                jax.random.uniform(ke, (Jb, n), jnp.float32), cols_by_row)
            fresh = jax.random.uniform(kf, (Jb,)) < corr
            fresh = fresh.at[0].set(fresh[0] | (b == 0))

            def step(x_prev, inp):
                e, f = inp
                x = jnp.where(f, e, 0.9 * x_prev + 0.1 * e)
                return x, x

            last, cols = jax.lax.scan(step, prev, (eps, fresh))
            cols = cols - jnp.mean(cols, axis=1, keepdims=True)
            cols = cols / jnp.maximum(
                jnp.linalg.norm(cols, axis=1, keepdims=True), 1e-12)
            X = jax.lax.dynamic_update_slice(X, cols.T, (0, b * Jb))
            return X, last

        X0 = jax.lax.with_sharding_constraint(
            jnp.zeros((n, J), jnp.float32), rows)
        X, _ = jax.lax.fori_loop(0, J // Jb, column_block,
                                 (X0, jnp.zeros((n,), jnp.float32)))
        support = jax.random.choice(ks, J, (k_true,), replace=False)
        beta = jax.random.normal(kv, (k_true,), jnp.float32)
        y = (jnp.take(X, support, axis=1) @ beta
             + noise * jax.random.normal(kn, (n,), jnp.float32))
        return X, y - jnp.mean(y)

    return gen


def _kernel_agreement(kern, X, r, U: int, Uc: int, seed: int) -> dict:
    """``lasso_partial``/``gram_block`` of the engine's backend against
    the reference kernels at the round's shapes, both on the device and
    both against a float64 host product; errors are relative to
    Σ|x·r| (resp. Σ|x·x|), the scale a rounding error is bounded by."""
    cand = jax.random.choice(jax.random.key(seed + 1), X.shape[1], (Uc,),
                             replace=False)
    Xc = jnp.take(X, cand, axis=1)
    Xb = Xc[:, :U]
    refk = build_kernels(KernelSpec(kind="reference"))
    z_k = np.asarray(jax.jit(kern.lasso_partial)(Xb, r), np.float64)
    z_r = np.asarray(jax.jit(refk.lasso_partial)(Xb, r), np.float64)
    G_k = np.asarray(jax.jit(kern.gram_block)(Xc), np.float64)
    G_r = np.asarray(jax.jit(refk.gram_block)(Xc), np.float64)
    Xc64 = np.asarray(Xc, np.float64)
    r64 = np.asarray(r, np.float64)
    Xb64 = Xc64[:, :U]
    z64 = Xb64.T @ r64
    zs = np.maximum(np.abs(Xb64).T @ np.abs(r64), 1e-30)
    G64 = Xc64.T @ Xc64
    Gs = np.maximum(np.abs(Xc64).T @ np.abs(Xc64), 1e-30)
    return {
        "partial_kernel_vs_ref": float(np.max(np.abs(z_k - z_r) / zs)),
        "partial_kernel_vs_f64": float(np.max(np.abs(z_k - z64) / zs)),
        "partial_ref_vs_f64": float(np.max(np.abs(z_r - z64) / zs)),
        "gram_kernel_vs_ref": float(np.max(np.abs(G_k - G_r) / Gs)),
        "gram_kernel_vs_f64": float(np.max(np.abs(G_k - G64) / Gs)),
        "gram_ref_vs_f64": float(np.max(np.abs(G_r - G64) / Gs)),
    }


def lasso_phase(mesh, *, n: int, J: int, rounds: int, seed: int = 0,
                on_chip: bool = True, U: int = 128, Uc: int = 512,
                rho: float = 0.3, lam: float = 0.05) -> dict:
    """STRADS lasso (``dynamic_priority``, U/U′/ρ) on the app's default
    kernels: ``rounds`` scanned rounds (compile + run), then ``rounds``
    more resumed from the carry (timed).  On the chip the backend must
    be Pallas, compiled (not interpreted), with a ``tpu_custom_call`` in
    the round program."""
    with _Phase("lasso", mesh) as ph:
        say("lasso", n=n, J=J, U=U, U_cand=Uc, rho=rho, lam=lam,
            rounds=f"{rounds}+{rounds}", workers=mesh.size)
        X, y = lasso_design(mesh, n, J, seed)
        cfg = lasso.LassoConfig(num_features=J, lam=lam, block_size=U,
                                num_candidates=Uc, rho=rho)
        eng = lasso.make_engine(cfg, mesh)
        data = eng.shard_data({"X": X, "y": y})
        del X, y
        key = jax.random.key(seed)
        state = eng.init_state(key, y=jnp.array(data["y"], copy=True))
        say("lasso", data_bytes=_nbytes(data), state_bytes=_nbytes(state))
        ph.mem("placed")

        kern = eng.kernels
        interp = getattr(kern, "interpret", None)
        say("lasso", kernel_backend=type(kern).__name__, interpret=interp)
        if on_chip:
            check(isinstance(kern, PallasKernels) and interp is False,
                  f"lasso must run compiled Pallas kernels on the chip; "
                  f"got {type(kern).__name__} interpret={interp}")
            # lowered with execute()'s exact arguments, so execute finds
            # this compilation in the persistent cache
            t = time.perf_counter()
            text = eng.scanned_fn(rounds).lower(
                state, data, eng.replicate(key), jnp.int32(0),
                eng.replicate(eng.init_sched_carry()), None).compile(
            ).as_text()
            n_custom = text.count("tpu_custom_call")
            say("lasso", round_program_compile_s=round(
                time.perf_counter() - t, 3),
                tpu_custom_calls_in_round_program=n_custom)
            check(n_custom > 0, "no tpu_custom_call in the lasso round "
                  "program — the Pallas kernels did not reach Mosaic")

        objective = eng.app.objective_fn(mesh)
        obj0 = float(objective(state))
        rep, first_s = _timed(lambda: eng.execute(
            state, data, key, ExecutionPlan(executor="scan", rounds=rounds)))
        obj1 = float(objective(rep.state))
        rep, warm_s = _timed(lambda: eng.execute(
            rep.state, data, key,
            ExecutionPlan(executor="scan", rounds=2 * rounds),
            carry=rep.carry))
        obj2 = float(objective(rep.state))
        nnz = int(jnp.sum(rep.state["beta"] != 0))
        say("lasso", objective_before=obj0, objective_after_warmup=obj1,
            objective_after=obj2, nonzero_beta=nnz,
            first_execute_s=round(first_s, 3),
            s_per_round_warm=warm_s / rounds)
        check(np.isfinite([obj0, obj1, obj2]).all(),
              "lasso objective is not finite")
        check(obj2 < obj1 < obj0, f"lasso objective did not decrease: "
              f"{obj0} -> {obj1} -> {obj2}")

        if mesh.size == 1:
            agree = _kernel_agreement(kern, data["X"], rep.state["r"],
                                      U, Uc, seed)
            say("lasso", **agree)
            for k, v in agree.items():
                check(v <= KERNEL_TOL, f"lasso kernels disagree: {k}={v}")
        return {"objective": (obj0, obj1, obj2)}


# ---------------------------------------------------------------------------
# LDA
# ---------------------------------------------------------------------------

def lda_corpus(cfg: lda.LDAConfig, seed: int, *, true_topics: int = 100,
               doc_alpha: float = 0.1, topic_shape: float = 0.1,
               zipf: float = 1.0):
    """A planted-LDA corpus, vectorised: per-document θ_d ~ Dir(α), topic
    per token by ``searchsorted`` over the cumulative θ rows, word per
    token by ``searchsorted`` over cumulative topic–word tables φ_t ∝
    Gamma(shape)·Zipf(rank) (heavy-tailed word marginals).  Each of the
    U workers holds ``tokens_per_worker`` tokens over its own
    ``docs_per_worker`` documents.  Returns flat (words, docs, z0)."""
    rng = np.random.default_rng(seed)
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


def lda_phase(mesh, *, vocab: int, topics: int, tokens_per_worker: int,
              docs_per_worker: int, rotations: int, seed: int = 0,
              on_chip: bool = True, true_topics: int = 100) -> dict:
    """STRADS LDA word rotation over U = mesh-width vocab blocks: one
    rotation (compile + run), then ``rotations − 1`` more resumed
    (timed).  The collapsed counts must equal those rebuilt from the
    final assignments exactly; with one worker the s-error is 0."""
    check(rotations >= 2, "lda_phase needs a warm-up rotation and a "
          "timed one")
    with _Phase("lda", mesh) as ph:
        W = mesh.size
        cfg = lda.LDAConfig(vocab=vocab, num_topics=topics, num_workers=W,
                            tokens_per_worker=tokens_per_worker,
                            docs_per_worker=docs_per_worker)
        say("lda", vocab=vocab, topics=topics, workers=W,
            tokens=W * tokens_per_worker, docs=W * docs_per_worker,
            rounds=f"{W}+{W * (rotations - 1)}")
        t = time.perf_counter()
        words, docs, z0 = lda_corpus(cfg, seed, true_topics=true_topics)
        eng = lda.make_engine(cfg, mesh)
        data = eng.shard_data({"words": jnp.asarray(words),
                               "docs": jnp.asarray(docs)})
        state = eng.init_state(jax.random.key(seed), words=words,
                               docs=docs, z0=z0)
        jax.block_until_ready(state)
        say("lda", corpus_and_counts_s=round(time.perf_counter() - t, 3),
            data_bytes=_nbytes(data), state_bytes=_nbytes(state))
        used = ph.mem("placed")
        if W > 1:
            check_placement(data, eng.data_specs, mesh)
            check_placement(state, eng.state_specs, mesh)
            check_even_bytes(used, on_chip)

        loglik = eng.app.loglik_fn(mesh)
        ll0 = float(loglik(state))
        key = jax.random.key(seed)
        rep, first_s = _timed(lambda: eng.execute(
            state, data, key, ExecutionPlan(executor="scan", rounds=W)))
        ll1 = float(loglik(rep.state))
        timed_rounds = W * (rotations - 1)
        rep, warm_s = _timed(lambda: eng.execute(
            rep.state, data, key,
            ExecutionPlan(executor="scan", rounds=W * rotations),
            carry=rep.carry))
        ll2 = float(loglik(rep.state))
        say("lda", loglik_before=ll0, loglik_after_warmup=ll1,
            loglik_after=ll2, first_execute_s=round(first_s, 3),
            s_per_round_warm=warm_s / timed_rounds)
        check(np.isfinite([ll0, ll1, ll2]).all(),
              "LDA log-likelihood is not finite")
        check(ll2 > ll0, f"LDA log-likelihood did not rise: {ll0} -> {ll2}")

        final = _host(rep.state)
        want = _host(lda.build_state(cfg, words, docs, final["z"]))
        n_tok = int((words >= 0).sum())
        b_sum = float(final["B"].sum(dtype=np.float64))
        s_ok = bool(np.array_equal(final["s"],
                                   final["B"].sum(0, dtype=np.float64)))
        counts_ok = all(np.array_equal(final[k], want[k])
                        for k in ("B", "D", "s"))
        s_err = float(final["s_err"])
        say("lda", tokens=n_tok, B_sum=b_sum, s_equals_colsum_B=s_ok,
            counts_equal_rebuilt_from_z=counts_ok, s_err=s_err)
        check(b_sum == n_tok, f"B.sum()={b_sum} != tokens={n_tok}")
        check(s_ok, "s != B.sum(0)")
        check(counts_ok, "B/D/s differ from the counts rebuilt from z")
        if W == 1:
            check(s_err == 0.0, f"one worker but s_err={s_err}")
        return {"loglik": (ll0, ll1, ll2)}


# ---------------------------------------------------------------------------
# MF + serving
# ---------------------------------------------------------------------------

def _zipf_weights(key, n: int, a: float) -> jax.Array:
    """Zipf(a) weights with mean 1, in a random order."""
    w = 1.0 / jnp.arange(1, n + 1, dtype=jnp.float32) ** a
    return jax.random.permutation(key, w / jnp.mean(w))


def mf_ratings(mesh, N: int, M: int, seed: int, **kw):
    """Low-rank + noise ratings with a power-law observation mask, drawn
    on the device, row-sharded: (A, mask) dense (N, M) float32."""
    return mf_ratings_fn(mesh, N, M, **kw)(jax.random.key(seed))


def mf_ratings_fn(mesh, N: int, M: int, *, true_rank: int = 10,
                  density: float = 0.0117, activity: float = 0.5,
                  noise: float = 0.1):
    """The jitted generator behind :func:`mf_ratings` (a key → (A,
    mask)): the mask is user activity × item popularity, Zipf(``activity``)
    each, mean density ≈ ``density`` (the Netflix prize's 1.17 %)."""
    rows = NamedSharding(mesh, P(DATA_AXIS))

    @partial(jax.jit, out_shardings=(rows, rows))
    def gen(key):
        kw, kh, kn, km, ku, ki = jax.random.split(key, 6)
        Wt = jax.random.normal(kw, (N, true_rank), jnp.float32)
        Ht = jax.random.normal(kh, (true_rank, M), jnp.float32)
        p = (density * _zipf_weights(ku, N, activity)[:, None]
             * _zipf_weights(ki, M, activity)[None, :])
        mask = (jax.random.uniform(km, (N, M)) < p).astype(jnp.float32)
        A = (Wt @ Ht / math.sqrt(true_rank)
             + noise * jax.random.normal(kn, (N, M), jnp.float32))
        return A * mask, mask

    return gen


def mf_phase(mesh, *, users: int, items: int, rank: int, rounds: int,
             requests: int, seed: int = 0, on_chip: bool = True,
             max_staleness: int = 4, top_k: int = 8) -> dict:
    """STRADS MF under ``serve_while_training`` (scan plan, ``stale``
    ServeSpec) answering ``requests`` top-k ``recommend`` requests from
    Zipf-popular users; the served run's final state must be
    bit-identical to an unserved ``execute`` of the same plan, which is
    then repeated warm for the seconds per round."""
    with _Phase("mf", mesh) as ph:
        say("mf", users=users, items=items, rank=rank, rounds=rounds,
            requests=requests, serve="stale", max_staleness=max_staleness,
            workers=mesh.size)
        A, mask = mf_ratings(mesh, users, items, seed)
        cfg = mf.MFConfig(num_rows=users, num_cols=items, rank=rank,
                          lam=0.05, top_k=top_k)
        eng = mf.make_engine(cfg, mesh)
        data = eng.shard_data({"A": A, "mask": mask})
        del A, mask
        key = jax.random.key(seed)
        fresh = lambda: eng.init_state(key, A=data["A"], mask=data["mask"])
        state = fresh()
        say("mf", observed=int(jnp.sum(data["mask"])),
            data_bytes=_nbytes(data), state_bytes=_nbytes(state))
        ph.mem("placed")

        loss = eng.app.objective_fn(mesh)
        loss0 = float(loss(state))
        plan = ExecutionPlan(executor="scan", rounds=rounds)
        spec = ServeSpec(kind="stale", max_staleness=max_staleness,
                         max_batch=8)
        rng = np.random.default_rng(seed)
        popular = 1.0 / np.arange(1, users + 1)
        who = rng.choice(users, size=requests, p=popular / popular.sum())
        due = np.linspace(0, rounds, requests).astype(int)
        reqs = [(int(t), {"user": np.int32(u)}) for t, u in zip(due, who)]
        t = time.perf_counter()
        srep = serve_while_training(eng, state, data, key, plan, spec=spec,
                                    requests=reqs)
        jax.block_until_ready(srep.report.state)
        serve_s = time.perf_counter() - t
        del state
        loss1 = float(loss(srep.report.state))
        served = _host(srep.report.state)
        items_ = np.stack([np.asarray(r.result["items"])
                           for r in srep.responses])
        scores = np.stack([np.asarray(r.result["scores"])
                           for r in srep.responses])
        lat = srep.latency_percentiles()
        worst = srep.max_staleness_read()
        say("mf", loss_before=loss0, loss_after=loss1,
            served_run_s=round(serve_s, 3), answered=len(srep.responses),
            p50_ms=lat["p50_ms"], p99_ms=lat["p99_ms"],
            staleness_hist=json.dumps(srep.staleness_hist()),
            max_staleness_read=worst, staleness_bound=max_staleness)
        check(items_.shape == (requests, top_k),
              f"{len(srep.responses)} responses of shape "
              f"{items_.shape[1:]}; wanted {requests} × {top_k}")
        check(((items_ >= 0) & (items_ < items)).all(),
              "recommended item ids out of range")
        check(np.isfinite(scores).all()
              and (np.diff(scores, axis=1) <= 0).all(),
              "recommend scores are not finite and descending")
        check(worst <= max_staleness, f"a read was {worst} rounds stale; "
              f"bound {max_staleness}")
        check(np.isfinite([loss0, loss1]).all() and loss1 < loss0,
              f"MF loss did not decrease: {loss0} -> {loss1}")
        del srep
        gc.collect()

        rep, first_s = _timed(lambda: eng.execute(fresh(), data, key, plan))
        same = _bit_equal(served, _host(rep.state))
        del rep, served
        gc.collect()
        rep, warm_s = _timed(lambda: eng.execute(fresh(), data, key, plan))
        say("mf", served_equals_unserved_bitwise=same,
            unserved_first_execute_s=round(first_s, 3),
            s_per_round_warm=warm_s / rounds)
        check(same, "serve_while_training changed the trained state")
        return {"loss": (loss0, loss1)}


# ---------------------------------------------------------------------------
# Four chips: the sharded paths
# ---------------------------------------------------------------------------

def four_chip_phase(mesh, *, lasso_kw: dict, lda_kw: dict, seed: int = 0,
                    on_chip: bool = True, U: int = 128, Uc: int = 512,
                    rho: float = 0.3, lam: float = 0.05) -> dict:
    """Row-sharded lasso on every device of ``mesh`` under the BSP scan
    plan, SSP s=0 (must be bit-identical to scan) and SSP s=2 (final
    objective within ``SSP2_OBJECTIVE_TOL`` of scan's decrease); then the
    LDA rotation over one vocab block per device (:func:`lda_phase`).
    Every data and state leaf must span all devices, with even bytes."""
    n, J, rounds = lasso_kw["n"], lasso_kw["J"], lasso_kw["rounds"]
    with _Phase("lasso_sharded", mesh) as ph:
        say("lasso_sharded", n=n, J=J, U=U, U_cand=Uc, rho=rho, lam=lam,
            rounds=rounds, workers=mesh.size,
            plans="scan,ssp(s=0),ssp(s=2)")
        X, y = lasso_design(mesh, n, J, seed)
        cfg = lasso.LassoConfig(num_features=J, lam=lam, block_size=U,
                                num_candidates=Uc, rho=rho)
        eng = lasso.make_engine(cfg, mesh)
        data = eng.shard_data({"X": X, "y": y})
        del X, y
        key = jax.random.key(seed)
        fresh = lambda: eng.init_state(key,
                                       y=jnp.array(data["y"], copy=True))
        state = fresh()
        say("lasso_sharded", data_bytes=_nbytes(data),
            state_bytes=_nbytes(state),
            kernel_backend=type(eng.kernels).__name__,
            interpret=getattr(eng.kernels, "interpret", None))
        check_placement(data, eng.data_specs, mesh)
        check_placement(state, eng.state_specs, mesh)
        check_even_bytes(ph.mem("placed"), on_chip)
        objective = eng.app.objective_fn(mesh)
        obj0 = float(objective(state))
        del state
        runs = {}
        for name, plan in (
                ("scan", ExecutionPlan(executor="scan", rounds=rounds)),
                ("ssp_s0", ExecutionPlan(executor="ssp", rounds=rounds,
                                         staleness=0)),
                ("ssp_s2", ExecutionPlan(executor="ssp", rounds=rounds,
                                         staleness=2))):
            rep, first_s = _timed(lambda: eng.execute(fresh(), data, key,
                                                      plan))
            check_placement(rep.state, eng.state_specs, mesh)
            obj = float(objective(rep.state))
            runs[name] = (obj, _host(rep.state))
            del rep
            _, warm_s = _timed(lambda: eng.execute(fresh(), data, key,
                                                   plan))
            say("lasso_sharded", plan=name, objective_before=obj0,
                objective_after=obj, first_execute_s=round(first_s, 3),
                s_per_round_warm=warm_s / rounds)
        obj_scan, obj_s2 = runs["scan"][0], runs["ssp_s2"][0]
        same = _bit_equal(runs["scan"][1], runs["ssp_s0"][1])
        gap = abs(obj_s2 - obj_scan) / max(obj0 - obj_scan, 1e-30)
        say("lasso_sharded", ssp_s0_equals_scan_bitwise=same,
            ssp_s2_gap_share_of_scan_decrease=gap,
            tolerance=SSP2_OBJECTIVE_TOL)
        check(np.isfinite([obj0, obj_scan, obj_s2]).all()
              and obj_scan < obj0, f"sharded lasso objective did not "
              f"decrease: {obj0} -> {obj_scan}")
        check(same, "ssp s=0 is not bit-identical to the scan plan")
        check(gap <= SSP2_OBJECTIVE_TOL,
              f"ssp s=2 objective {obj_s2} is {gap:.3f} of the scan "
              f"decrease away from scan's {obj_scan}")
    del runs, data, eng
    gc.collect()
    lda_phase(mesh, seed=seed, on_chip=on_chip, **lda_kw)
    return {"objective": (obj0, obj_scan, obj_s2)}


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Smoke run of the STRADS main path on a TPU.")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded four-chip phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found {len(devices)} "
              f"{dev.platform} device(s) ({dev.device_kind}); nothing "
              f"was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"chips, but JAX found {len(devices)}", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    say("setup", platform=dev.platform, kind=repr(dev.device_kind),
        count=len(devices), chips_used=args.chips, jax=jax.__version__,
        compile_cache=cache, seed=args.seed)
    t = time.perf_counter()
    if args.chips == 4:
        four_chip_phase(worker_mesh(4), lasso_kw=FOUR_LASSO,
                        lda_kw=FOUR_LDA, seed=args.seed)
    else:
        mesh = single_device_mesh()
        lasso_phase(mesh, seed=args.seed, **LASSO)
        gc.collect()
        lda_phase(mesh, seed=args.seed, **LDA)
        gc.collect()
        mf_phase(mesh, seed=args.seed, **MF)
    say("done", total_s=round(time.perf_counter() - t, 3),
        compile_s=round(_COMPILE["seconds"], 3),
        persistent_cache_hits=_COMPILE["cache_hits"])
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
