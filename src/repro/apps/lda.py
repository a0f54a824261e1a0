"""STRADS LDA (paper §3.1): word-rotation collapsed Gibbs sampling.

Model variables are the topic assignments z_ij; sufficient statistics are
the doc-topic table D and the word-topic table B (+ its column sums s).

schedule (word rotation): the vocabulary is split into U contiguous blocks
V_1..V_U; at round t worker p processes block (p + t) mod U, so blocks
rotate and every token is sampled exactly once per U rounds while
concurrently-sampled tokens always have *disjoint words and disjoint
documents* — the conditional-independence argument that keeps the
parallelization error tiny (the only shared quantity is s, synced each
pull; its drift is the paper's Fig-5 s-error, which we measure).

Layout (model parallelism — the Fig-3 memory claim):
  * B is sharded by word block: home shard u holds rows of block u
    (``(U·V_b, K)`` sharded over ``data``).  At round t the blocks rotate
    to their processing worker via a *static* ``lax.ppermute`` and rotate
    home afterwards — this is the schedule's communication pattern, and
    it is exactly why per-machine memory falls as 1/U.
  * D and z shard with the documents (each doc lives on one worker).
  * s (K,) is the synced KV-store value, replicated.

push: sequential collapsed Gibbs over the worker's tokens whose word lies
in its current block (a ``lax.scan``; within-worker sampling is exact),
using the worker's stale local copy s̃ — paper f₁.
pull: commit z/D/B locally; s ← psum of per-block column sums — paper f₂;
the automatic sync makes s consistent again.  The round also reports the
s-error Δ_t = (1/PM) Σ_p ‖s̃_p − s‖₁ (paper eq. 1).

The data-parallel baseline (:class:`DataParallelLDA`, YahooLDA-style)
replicates the *full* B on every worker, samples all local tokens against
the stale replica and merges table deltas at the end of the round — more
parallel error (every word conflicts) and O(V·K) memory per machine
regardless of cluster size.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.special import gammaln
from jax.sharding import PartitionSpec as P

from repro.core import StradsAppBase, StradsEngine
from repro.core.compat import shard_map
from repro.kernels import gibbs_scan, split_chain
from repro.part import PartitionerSpec
from repro.sched import SchedulerSpec

from . import _exec


@dataclasses.dataclass(frozen=True)
class LDAConfig:
    vocab: int                   # V (padded up to U * block_vocab)
    num_topics: int              # K
    num_workers: int             # U (= data-axis size)
    tokens_per_worker: int       # T_p (padded)
    docs_per_worker: int         # local doc count
    alpha: float = 0.1           # doc-topic prior
    gamma: float = 0.1           # word-topic prior

    @property
    def block_vocab(self) -> int:
        return -(-self.vocab // self.num_workers)    # ceil

    @property
    def padded_vocab(self) -> int:
        return self.block_vocab * self.num_workers


def _gibbs_scan(cfg: LDAConfig, B, D, s, words, docs, z, active_mask,
                block_start, rng):
    """Sequential collapsed Gibbs over one worker's scheduled tokens.

    Exact within the worker (counts updated after every sample); the only
    stale quantity is s̃, which starts at the synced s.  ``B`` holds the
    word rows ``[block_start, block_start + B.shape[0])``.  Each token
    draws with its subkey of the sampler's split chain.

    On a TPU the token loop is one Pallas kernel
    (:mod:`repro.kernels.gibbs_scan`); elsewhere it is :func:`_gibbs_loop`,
    which gives the same bits.  Returns (B, D, s̃, z)."""
    vloc = jnp.clip(words - block_start, 0, B.shape[0] - 1)
    keys = split_chain.subkeys(rng, words.shape[0])
    impl = jax.random.key_impl(rng)

    def kernel(B, D, s, vloc, docs, z, active, keys):
        B, D, st, drawn, _ = gibbs_scan.gibbs_scan_kernel(
            B, D, s, vloc, docs, z, active,
            jax.random.wrap_key_data(keys, impl=impl), alpha=cfg.alpha,
            gamma=cfg.gamma, vgamma=cfg.padded_vocab * cfg.gamma)
        return B, D, st, jnp.where(active, drawn.astype(z.dtype), z)

    return jax.lax.platform_dependent(
        B, D, s, vloc, docs, z, active_mask, keys, tpu=kernel,
        default=functools.partial(_gibbs_loop, cfg, impl=impl))


def _gibbs_loop(cfg: LDAConfig, B, D, s, vloc, docs, z, active_mask, keys,
                *, impl):
    """The token loop as XLA instructions.

    A token step reads its word's row of B and its document's row of D
    once, stacks them with s̃, takes the token's topic out of all three,
    draws, adds the new topic back and writes each row once.  What does
    not depend on the counts is computed for every token before the loop
    and packed so that one slice fetches a token: its local word row, its
    document, its topic, an offset that turns off an unscheduled token
    and its subkey.  The draw is written back into the token's topic
    field."""
    K = cfg.num_topics
    # an unscheduled token takes topic K out and adds K back: no topic
    off = jnp.where(active_mask, 0, K)
    toks = jnp.concatenate(
        [jnp.stack([vloc, docs, z + off, off], axis=1).astype(jnp.uint32),
         keys], axis=1)
    topics = jax.lax.iota(jnp.uint32, K)
    zero = jnp.uint32(0)

    def step(i, carry):
        B, D, st, toks = carry
        tok = toks[i]
        v, d, zi, o = tok[0], tok[1], tok[2], tok[3]
        rows = jnp.stack([jax.lax.dynamic_slice(B, (v, zero), (1, K))[0],
                          jax.lax.dynamic_slice(D, (d, zero), (1, K))[0],
                          st])
        # remove the current assignment (x - 1 is the scatter's x + (-1))
        rows = jnp.where(topics == zi, rows - 1, rows)
        # conditional:  (γ+B[v,k]) / (Vγ+s̃[k]) · (α+D[d,k])
        logits = (jnp.log(cfg.gamma + rows[0]) -
                  jnp.log(cfg.padded_vocab * cfg.gamma + rows[2]) +
                  jnp.log(cfg.alpha + rows[1]))
        sub = jax.random.wrap_key_data(tok[4:], impl=impl)
        znew = jax.random.categorical(sub, logits).astype(jnp.uint32)
        # add back (nowhere for an unscheduled token)
        rows = jnp.where(topics - o == znew, rows + 1, rows)
        B = jax.lax.dynamic_update_slice(B, rows[0][None], (v, zero))
        D = jax.lax.dynamic_update_slice(D, rows[1][None], (d, zero))
        toks = jax.lax.dynamic_update_slice(toks, znew[None, None], (i, 2))
        return B, D, rows[2], toks

    # D goes through the loop as a copy one row longer: a buffer of its
    # own, which the compiler can keep on chip, where the state's
    # (donated) buffer stays in HBM
    B, D, st, toks = jax.lax.fori_loop(
        0, toks.shape[0], step,
        (B, jnp.pad(D, ((0, 1), (0, 0))), s, toks))
    z_new = jnp.where(active_mask, toks[:, 2].astype(z.dtype), z)
    return B, D[:-1], st, z_new


class StradsLDA(StradsAppBase):
    """Word-rotation model-parallel collapsed Gibbs on STRADS primitives."""

    supported_scheduler_kinds = ("rotation",)
    # the Gibbs token loop's Pallas kernel (and the split chain's) goes
    # by platform, not by plan: a plan asking for a kernel kind has
    # nothing to swap and is rejected at injection.
    supported_kernel_kinds = ("reference",)

    def __init__(self, cfg: LDAConfig):
        self.cfg = cfg
        # one full rotation = U rounds; the scanned executor unrolls a
        # whole rotation per scan step so each ppermute stays static
        self.phase_period = cfg.num_workers

    def default_scheduler_spec(self) -> SchedulerSpec:
        # word-rotation over the U disjoint vocabulary blocks
        return SchedulerSpec(kind="rotation")

    def num_schedulable(self) -> int:
        return self.cfg.padded_vocab

    # The rotation's ppermute pattern *is* a frozen contiguous word→
    # worker map (RotationScheduler.bounds); ownership cannot move
    # without retiling B, so only the static partitioner applies — the
    # engine rejects anything else at injection time.  The static
    # assignment is bit-identical to the rotation bounds
    # (repro.part.contiguous_assignment shares the linspace).
    supported_partitioner_kinds = ("static",)

    def default_partitioner_spec(self) -> PartitionerSpec:
        return PartitionerSpec(kind="static")

    def static_phase(self, t: int) -> int:
        return t % self.cfg.num_workers

    def init_state(self, rng, words=None, docs=None, z0=None):
        if words is None:
            raise ValueError("StradsLDA.init_state needs the corpus "
                             "(words=, docs=, z0=)")
        return build_state(self.cfg, words, docs, z0)

    def state_specs(self):
        return {"z": P("data"), "D": P("data"), "B": P("data"),
                "s": P(), "s_err": P()}

    def data_specs(self):
        return {"words": P("data"), "docs": P("data")}

    # -- push / pull ----------------------------------------------------------

    def push(self, data, state, sched, phase):
        cfg = self.cfg
        # the injected rotation policy owns the block↔worker assignment
        # and the (static) ppermute communication pattern it implies
        p_fwd = self.scheduler.forward_perm(phase)         # block → worker
        with jax.named_scope("exchange"):
            B = jax.lax.ppermute(state["B"], "data", p_fwd)

        p = jax.lax.axis_index("data")
        block = self.scheduler.block_for_worker(p, phase)
        block_start = block * cfg.block_vocab
        words, docs, z = data["words"], data["docs"], state["z"]
        active = (words >= 0) & (words // cfg.block_vocab == block)

        rng = jax.random.fold_in(jax.random.key(17), phase)
        rng = jax.random.fold_in(rng, p)

        B, D, s_tilde, z_new = _gibbs_scan(
            cfg, B, state["D"], state["s"], words, docs, z, active,
            block_start, rng)

        # send the processed block home
        p_bwd = self.scheduler.backward_perm(phase)
        with jax.named_scope("exchange"):
            B_home = jax.lax.ppermute(B, "data", p_bwd)

        # partials for pull: fresh column sums + s-error numerator
        s_partial = jnp.sum(B, axis=0)                    # this block's sums
        partial = {"s": s_partial}
        local = {"z": z_new, "D": D, "B": B_home, "s_tilde": s_tilde}
        return partial, local

    def obs_counts(self, data, sched, phase):
        """(visited, updated, forwarded) of one round, for the device
        counters: the valid tokens every worker's Gibbs scan steps over,
        those it samples (its word in the worker's current block), and the
        B and D row groups the Gibbs kernel takes from its ring instead of
        HBM (:func:`repro.kernels.gibbs_scan.forwarded`).  Reads only the
        data and the rotation."""
        cfg = self.cfg
        words = data["words"].reshape(cfg.num_workers, -1)
        docs = data["docs"].reshape(cfg.num_workers, -1)
        block = self.scheduler.block_for_worker(
            jnp.arange(cfg.num_workers), phase)
        valid = words >= 0
        active = valid & (words // cfg.block_vocab == block[:, None])
        return (jnp.sum(valid, dtype=jnp.int32),
                jnp.sum(active, dtype=jnp.int32),
                gibbs_scan.forwarded(words % cfg.block_vocab, docs, active))

    def pull(self, state, sched, z, local, data, phase):
        cfg = self.cfg
        s_new = z["s"]                                    # synced (psummed)
        # Fig-5 s-error: (1/PM) Σ_p ‖s̃_p − s_new‖₁   (M = total tokens)
        err_p = jnp.sum(jnp.abs(local["s_tilde"] - s_new))
        M = cfg.num_workers * cfg.tokens_per_worker
        s_err = jax.lax.psum(err_p, "data") / (cfg.num_workers * M)
        return {"z": local["z"], "D": local["D"], "B": local["B"],
                "s": s_new, "s_err": s_err}

    # SSP behavior is fully derived from the placement above (v2 write
    # contract, repro.core.primitives): ``local``'s z/D/B name the
    # worker-resident state leaves, so they commit through every round (a
    # worker's own Gibbs moves are never re-sampled from a stale table);
    # only ``s_tilde`` defers to the flush, where ``pull`` replays — the
    # LightLDA-style staleness-tolerant server, where s̃ is exactly the
    # stale quantity the paper's Fig-5 error bound is about.

    # -- serving (query primitive) -------------------------------------------

    #: fixed fold-in iterations for query() (static, so one jitted
    #: program serves every batch)
    query_iters: int = 8

    def query(self, state, batch):
        """``infer_topics``: fold a batch of unseen documents into the
        trained topics (batch ``{"words": (B, L)}``, -1-padded, →
        ``{"theta": (B, K), "top_topic": (B,)}``).

        A fixed-iteration mean-field fold-in (the deterministic twin of
        fold-in Gibbs): φ_lk ∝ (γ+B[v_l,k]) / (Vγ+s[k]) holds the topics
        fixed and θ is re-estimated ``query_iters`` times.  B is
        worker-resident (read live at the boundary); s is the
        server-resident leaf — so the only stale ingredient under
        ``kind="stale"`` is s̃, exactly the quantity the paper's Fig-5
        error bound is about."""
        cfg = self.cfg
        words = batch["words"]                              # (B, L)
        v = jnp.clip(words, 0, cfg.padded_vocab - 1)
        active = (words >= 0)[..., None]                    # (B, L, 1)
        phi = ((cfg.gamma + state["B"][v]) /
               (cfg.padded_vocab * cfg.gamma + state["s"]))  # (B, L, K)
        phi = jnp.where(active, phi, 1.0)
        theta = jnp.full(words.shape[:1] + (cfg.num_topics,),
                         1.0 / cfg.num_topics, jnp.float32)
        for _ in range(self.query_iters):
            q = phi * theta[:, None, :]
            q = q / jnp.maximum(jnp.sum(q, -1, keepdims=True), 1e-30)
            q = jnp.where(active, q, 0.0)
            theta = cfg.alpha + jnp.sum(q, axis=1)
            theta = theta / jnp.sum(theta, -1, keepdims=True)
        return {"theta": theta, "top_topic": jnp.argmax(theta, axis=-1)}

    # -- streaming (ingest primitives) ---------------------------------------

    #: token slots with word -1 are exactly the padding the Gibbs scan
    #: already skips (``active``), so they double as the extend-kind
    #: validity channel — 1411.2305-style doc-shard streaming
    supported_stream_kinds = ("replace", "extend")

    def ingest_specs(self):
        return {"leaves": ("words", "docs"),
                "valid": lambda data: np.asarray(data["words"]) >= 0}

    def ingest(self, data, state, rows, delta):
        """Swap token slots (new tokens into padding/oldest slots, or
        resampled replacements) and keep the collapsed counts exact:
        each displaced active token is decremented out of D/B/s, each
        incoming one (topic draw ``delta["z"]``) incremented in.  Word
        -1 in a delta deletes the slot's token."""
        cfg = self.cfg
        Tp, dpw = cfg.tokens_per_worker, cfg.docs_per_worker
        slots = np.asarray(rows, np.int64)
        w_new = np.asarray(delta["data"]["words"], np.int32)
        d_new = np.asarray(delta["data"]["docs"], np.int32)
        if w_new.max(initial=-1) >= cfg.vocab or \
                w_new.min(initial=0) < -1:
            raise ValueError(f"ingested words out of [-1, {cfg.vocab})")
        if d_new.size and (d_new.min() < 0 or d_new.max() >= dpw):
            raise ValueError(f"ingested docs out of [0, {dpw}) (doc ids "
                             f"are worker-local)")
        new_data = dict(data,
                        words=data["words"].at[slots].set(
                            jnp.asarray(w_new)),
                        docs=data["docs"].at[slots].set(
                            jnp.asarray(d_new)))
        if state is None:
            return new_data, None
        z_new = np.asarray(delta["z"], np.int32)
        if z_new.size and (z_new.min() < 0
                           or z_new.max() >= cfg.num_topics):
            raise ValueError(f"ingested z out of [0, {cfg.num_topics})")
        u = slots // Tp                        # owning worker per slot
        w_old = np.asarray(data["words"])[slots]
        d_old = np.asarray(data["docs"])[slots]
        z = np.array(np.asarray(state["z"]))
        z_old = z[slots]
        D = np.array(np.asarray(state["D"]))
        B = np.array(np.asarray(state["B"]))
        s = np.array(np.asarray(state["s"]))
        out = w_old >= 0                       # displaced active tokens
        np.add.at(B, (w_old[out], z_old[out]), -1)
        np.add.at(D, (u[out] * dpw + d_old[out], z_old[out]), -1)
        np.add.at(s, z_old[out], -1)
        inn = w_new >= 0                       # arriving active tokens
        np.add.at(B, (w_new[inn], z_new[inn]), 1)
        np.add.at(D, (u[inn] * dpw + d_new[inn], z_new[inn]), 1)
        np.add.at(s, z_new[inn], 1)
        z[slots] = z_new
        return new_data, dict(state, z=jnp.asarray(z), D=jnp.asarray(D),
                              B=jnp.asarray(B), s=jnp.asarray(s))

    # -- diagnostics ------------------------------------------------------------

    def loglik_fn(self, mesh):
        """Collapsed joint log P(W, Z) up to constants (convergence metric)."""
        cfg = self.cfg

        def local(B, D, s):
            lb = jnp.sum(gammaln(B + cfg.gamma))
            ld = jnp.sum(gammaln(D + cfg.alpha)) \
                - jnp.sum(gammaln(jnp.sum(D, 1) + cfg.num_topics * cfg.alpha))
            tot = jax.lax.psum(lb + ld, "data")
            return tot - jnp.sum(gammaln(s + cfg.padded_vocab * cfg.gamma))

        fn = shard_map(local, mesh=mesh,
                       in_specs=(P("data"), P("data"), P()),
                       out_specs=P())
        return jax.jit(lambda st: fn(st["B"], st["D"], st["s"]))


# ---------------------------------------------------------------------------
# Data-parallel baseline (YahooLDA-style)
# ---------------------------------------------------------------------------

class DataParallelLDAApp(StradsAppBase):
    """Working data-parallel baseline app."""

    def __init__(self, cfg: LDAConfig):
        self.cfg = cfg

    def init_state(self, rng, words=None, docs=None, z0=None):
        if words is None:
            raise ValueError("DataParallelLDAApp.init_state needs the "
                             "corpus (words=, docs=, z0=)")
        full = build_state(self.cfg, words, docs, z0)
        return {k: full[k] for k in ("z", "D", "B", "s")}

    def state_specs(self):
        return {"z": P("data"), "D": P("data"), "B": P(), "s": P()}

    def data_specs(self):
        return {"words": P("data"), "docs": P("data")}

    def push(self, data, state, sched, phase):
        cfg = self.cfg
        words, docs, z = data["words"], data["docs"], state["z"]
        active = words >= 0
        p = jax.lax.axis_index("data")
        rng = jax.random.fold_in(jax.random.key(23), p)
        # the full table is one block starting at word 0
        B, D, s_tilde, z_new = _gibbs_scan(
            cfg, state["B"], state["D"], state["s"], words, docs, z,
            active, 0, rng)
        partial = {"dB": B - state["B"]}
        local = {"z": z_new, "D": D}
        return partial, local

    def pull(self, state, sched, z, local, data, phase):
        B = state["B"] + z["dB"]                 # merge stale deltas
        s = jnp.sum(B, axis=0)
        return {"z": local["z"], "D": local["D"], "B": B, "s": s}


# ---------------------------------------------------------------------------
# Synthetic corpus + drivers
# ---------------------------------------------------------------------------

def synthetic_corpus(rng: np.random.Generator, cfg: LDAConfig,
                     true_topics: int = 10, concentration: float = 0.05):
    """Draw a corpus from a planted LDA model (so likelihood climbs are
    meaningful).  Returns (words, docs, z_init) flat arrays laid out as
    num_workers contiguous shards."""
    U, Tp, dpw = cfg.num_workers, cfg.tokens_per_worker, cfg.docs_per_worker
    V, K = cfg.vocab, cfg.num_topics
    topics = rng.dirichlet([concentration] * V, size=true_topics)
    words = np.full((U * Tp,), -1, np.int32)
    docs = np.zeros((U * Tp,), np.int32)
    for u in range(U):
        for i in range(Tp):
            d = rng.integers(dpw)
            theta = rng.dirichlet([0.3] * true_topics)
            k = rng.choice(true_topics, p=theta)
            v = rng.choice(V, p=topics[k])
            words[u * Tp + i] = v
            docs[u * Tp + i] = d
    z0 = rng.integers(0, K, size=(U * Tp,)).astype(np.int32)
    return words, docs, z0


def build_state(cfg: LDAConfig, words, docs, z0):
    """Materialize consistent D, B, s from the initial assignments."""
    U, Tp, dpw = cfg.num_workers, cfg.tokens_per_worker, cfg.docs_per_worker
    Vp, K = cfg.padded_vocab, cfg.num_topics
    w = np.asarray(words)[:U * Tp]
    d = np.asarray(docs)[:U * Tp]
    k = np.asarray(z0)[:U * Tp]
    act = w >= 0                                   # skip -1 padding
    u = np.repeat(np.arange(U), Tp)[act]           # owning worker
    D = np.zeros((U * dpw, K), np.float32)
    B = np.zeros((Vp, K), np.float32)
    # counts stay exact in float32 (every cell < 2**24)
    np.add.at(D, (u * dpw + d[act], k[act]), 1)
    np.add.at(B, (w[act], k[act]), 1)
    s = B.sum(axis=0).astype(np.float32)
    return {"z": jnp.asarray(z0), "D": jnp.asarray(D), "B": jnp.asarray(B),
            "s": jnp.asarray(s), "s_err": jnp.float32(0)}


def make_engine(cfg: LDAConfig, mesh, baseline: bool = False) -> StradsEngine:
    app = DataParallelLDAApp(cfg) if baseline else StradsLDA(cfg)
    return StradsEngine(app, mesh, data_specs=app.data_specs(),
                        state_specs=app.state_specs())


def _global_loglik(cfg: LDAConfig, state):
    """The collapsed log P(W, Z) as a plain global expression (equal to the
    shard_map reduction — psum of per-shard sums is the global sum), so it
    can run as an ``execute`` collect fn inside the scan."""
    lb = jnp.sum(gammaln(state["B"] + cfg.gamma))
    ld = jnp.sum(gammaln(state["D"] + cfg.alpha)) \
        - jnp.sum(gammaln(jnp.sum(state["D"], 1)
                          + cfg.num_topics * cfg.alpha))
    return lb + ld - jnp.sum(gammaln(state["s"]
                                     + cfg.padded_vocab * cfg.gamma))


def fit(cfg: LDAConfig, words, docs, z0, mesh, num_rounds=None,
        baseline: bool = False, trace_every=None, plan=None):
    """``plan``: an :class:`~repro.core.ExecutionPlan` (see lasso.fit).
    For "pipelined"/"ssp", the rounds must tile the rotation length U
    (and the SSP window)."""
    plan = _exec.resolve_plan(plan, num_rounds=num_rounds,
                              trace_every=trace_every)
    eng = make_engine(cfg, mesh, baseline=baseline)
    data = eng.shard_data({"words": jnp.asarray(words),
                           "docs": jnp.asarray(docs)})
    state = eng.init_state(jax.random.key(0), words=words, docs=docs,
                           z0=z0)
    every = plan.collect_every

    if plan.executor != "loop":
        collect = None
        if every:
            def collect(s):
                out = {"ll": _global_loglik(cfg, s)}
                if "s_err" in s:
                    out["s_err"] = s["s_err"]
                return out
        rep = eng.execute(state, data, jax.random.key(0), plan,
                          collect=collect)
        if collect is None:
            return rep.state, [], []
        ys = rep.trace
        trace = _exec.decimate(np.asarray(ys["ll"]), plan.rounds, every)
        s_errs = (_exec.decimate(np.asarray(ys["s_err"]), plan.rounds,
                                 every) if "s_err" in ys else [])
        return rep.state, trace, s_errs

    llfn = StradsLDA(cfg).loglik_fn(mesh) if not baseline else \
        _baseline_loglik(cfg, mesh)
    trace, s_errs = [], []

    def cb(t, s, out):
        if every and (t % every == 0 or t == plan.rounds - 1):
            trace.append((t, float(llfn(s))))
            if "s_err" in s:
                s_errs.append((t, float(s["s_err"])))
        return False

    rep = eng.execute(state, data, jax.random.key(0), plan, callback=cb)
    return rep.state, trace, s_errs


def _baseline_loglik(cfg: LDAConfig, mesh):
    def local(B, D, s):
        ld = jnp.sum(gammaln(D + cfg.alpha)) \
            - jnp.sum(gammaln(jnp.sum(D, 1) + cfg.num_topics * cfg.alpha))
        tot = jax.lax.psum(ld, "data")
        lb = jnp.sum(gammaln(B + cfg.gamma))
        return tot + lb - jnp.sum(gammaln(s + cfg.padded_vocab * cfg.gamma))

    fn = shard_map(local, mesh=mesh, in_specs=(P(), P("data"), P()),
                   out_specs=P())
    return jax.jit(lambda st: fn(st["B"], st["D"], st["s"]))
