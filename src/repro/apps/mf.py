"""STRADS Matrix Factorization (paper §3.2) and an ALS baseline.

Task:  min_{W,H}  Σ_{(i,j)∈Ω} (a_ij − wᵢhⱼ)² + λ(‖W‖_F² + ‖H‖_F²)
with W ∈ R^{N×K}, H ∈ R^{K×M} (paper eq. 2), solved by rank-wise parallel
coordinate descent (CCD-style, paper eq. 3).

schedule: round-robin over (matrix ∈ {W, H}) × (rank k) — the paper's
round-robin dispatch over the q_p / r_p index sets; with rows of A sharded
over workers, *all* columns of H can be updated concurrently for a fixed
rank k (they are mutually independent given W — the paper's "free from
parallelization error" argument), and symmetrically for W.

push (H-phase, rank k):   a_j^p = Σ_{i∈(Ω_j)_p} (r_ij + w_ik h_kj) w_ik   (g₁)
                          b_j^p = Σ_{i∈(Ω_j)_p} w_ik²                     (g₂)
pull:                     h_kj ← Σ_p a_j^p / (λ + Σ_p b_j^p)              (g₃)
sync (automatic):         r_ij ← r_ij − w_ik (h_kj_new − h_kj_old).

Layout: the ratings are sparse entries, rows sharded over the ``data``
axis.  Worker p holds its N/P user rows' ratings as ``capacity`` padded
entries (local row, column, value); the residual R is one value per entry,
state sharded with them.  A padding entry's row is the sentinel N/P: the
gathers read a zero factor there and the row sums drop it, so padding is
inert.  W shards with the rows (model partitioning — Fig 3); H is the
synced KV-store block (replicated; K×M is small beside W for N ≫ M).  The
W-phase uses the same row shards: for fixed k, w_ik ← Σ_j … over the
row's entries, which are whole on one worker, so it needs no cross-worker
sum (the paper's submatrix A^{q_p} storage).  Every sweep is indexed
reads of a factor vector, segment sums and an elementwise residual
update (``kernels/entry_sweep.py``: one-hot contractions on a TPU, exact
in float32; XLA's gathers and scatters elsewhere).
"""
from __future__ import annotations

import contextlib
import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import StradsAppBase, StradsEngine
from repro.core.compat import shard_map
from repro.kernels import entry_sweep
from repro.obs import active
from repro.part import PartitionerSpec
from repro.sched import SchedulerSpec

from . import _exec

#: a shard's entry capacity is a multiple of this (padding entries inert)
ENTRY_ALIGN = 128
#: entries per block where a residual is recomputed from W and H
RESIDUAL_BLOCK = 16384
#: rounds of fresh draws for the users whose ratings repeated a movie
TOP_UPS = 8


@dataclasses.dataclass(frozen=True)
class MFConfig:
    num_rows: int                # N (users)
    num_cols: int                # M (items)
    rank: int                    # K
    lam: float = 0.05
    ranks_per_round: int = 1     # how many rank indices per BSP round
    top_k: int = 8               # recommendations per query() request


# ---------------------------------------------------------------------------
# The layout: padded per-shard entries
# ---------------------------------------------------------------------------

def _span(name: str, **args):
    rec = active()
    return rec.span(name, **args) if rec is not None else \
        contextlib.nullcontext()


def layout(rows, cols, vals, num_rows: int, *, num_workers: int = 1,
           capacity: Optional[int] = None, valid=None) -> dict:
    """The data pytree of sparse ratings (global user ``rows``, item
    ``cols``, values ``vals``), on the host: each of ``num_workers`` row
    shards holds its ratings as ``capacity`` entries (default: the
    fullest shard's count, rounded up to :data:`ENTRY_ALIGN`), sorted by
    (row, column) and padded with inert entries whose local row is the
    sentinel N/P.

    ``row``/``col``/``val`` are ``(P·capacity,)``; ``valid`` ``(N,)``
    marks the rows that hold a user (default: those with a rating).  A
    span ``mf.layout`` in the active Recorder, whose args ``row_steps``
    and ``col_steps`` are the contraction steps one sweep by row and by
    column takes over the shards (:func:`entry_sweep.sweep_steps`)."""
    with _span("mf.layout", ratings=int(np.size(rows))) as ev:
        rows = np.asarray(rows, np.int64)
        cols = np.asarray(cols, np.int32)
        vals = np.asarray(vals, np.float32)
        U = int(num_workers)
        if num_rows % U:
            raise ValueError(f"{num_rows} rows do not split over {U} "
                             f"workers")
        Np = num_rows // U
        if rows.size and (rows.min() < 0 or rows.max() >= num_rows):
            raise ValueError(f"row ids out of range [0, {num_rows})")
        worker = rows // Np
        counts = np.bincount(worker, minlength=U)
        if capacity is None:
            capacity = max(ENTRY_ALIGN, -(-int(counts.max(initial=0))
                                          // ENTRY_ALIGN) * ENTRY_ALIGN)
        if counts.max(initial=0) > capacity:
            raise ValueError(f"a shard holds {int(counts.max())} ratings, "
                             f"more than the capacity {capacity}")
        if rows.size > 1 and not np.all(
                (rows[1:] > rows[:-1])
                | ((rows[1:] == rows[:-1]) & (cols[1:] >= cols[:-1]))):
            order = np.lexsort((cols, rows))
            rows, cols, vals = rows[order], cols[order], vals[order]
        out_row = np.full((U, capacity), Np, np.int32)
        out_col = np.zeros((U, capacity), np.int32)
        out_val = np.zeros((U, capacity), np.float32)
        end = np.cumsum(counts)
        for u, (a, n) in enumerate(zip(end - counts, counts)):
            out_row[u, :n] = rows[a:a + n] - u * Np
            out_col[u, :n] = cols[a:a + n]
            out_val[u, :n] = vals[a:a + n]
        if valid is None:
            valid = np.zeros(num_rows, bool)
            valid[rows] = True
        if ev is not None:
            ev["args"].update(
                row_steps=sum(map(entry_sweep.sweep_steps, out_row)),
                col_steps=sum(map(entry_sweep.sweep_steps, out_col)))
        return {"row": out_row.reshape(-1), "col": out_col.reshape(-1),
                "val": out_val.reshape(-1), "valid": np.asarray(valid, bool)}


@partial(jax.jit, static_argnames=("workers",))
def _residual(W, H, row, col, val, *, workers: int):
    """r = a − wᵢ·hⱼ for every held entry (0 for padding), from global W
    and H, over blocks of entries: an elementwise product and a sum in
    float32, so no matrix unit rounds it."""
    U = workers
    Np = W.shape[0] // U
    row, col, val = (x.reshape(U, -1) for x in (row, col, val))
    C = row.shape[1]
    b = min(C, RESIDUAL_BLOCK)
    W3 = W.reshape(U, Np, -1)
    HT = H.T

    def block(i, out):
        s = jnp.minimum(i * b, C - b)        # the last block overlaps
        r = jax.lax.dynamic_slice_in_dim(row, s, b, axis=1)
        c = jax.lax.dynamic_slice_in_dim(col, s, b, axis=1)
        v = jax.lax.dynamic_slice_in_dim(val, s, b, axis=1)
        held = r < Np
        wg = jax.vmap(lambda Wu, ru: Wu[jnp.minimum(ru, Np - 1)])(W3, r)
        res = v - jnp.sum(wg * HT[c], axis=-1)
        return jax.lax.dynamic_update_slice_in_dim(
            out, jnp.where(held, res, 0.0), s, axis=1)

    out = jax.lax.fori_loop(0, -(-C // b), block,
                            jnp.zeros((U, C), jnp.float32))
    return out.reshape(-1)


class StradsMF(StradsAppBase):
    """Round-robin rank-wise CD on STRADS primitives, over sparse
    per-shard ratings."""

    phase_period = 2                     # H-phase / W-phase alternation
    # rank blocks are mutually independent given the other factor — no
    # dependency filter applies, so only the stateless dispatch kinds
    supported_scheduler_kinds = ("round_robin", "random")
    # the sweeps' kernels (kernels/entry_sweep.py) go by platform, not by
    # KernelSpec — only the reference kind applies, enforced at injection
    supported_kernel_kinds = ("reference",)

    def __init__(self, cfg: MFConfig, num_workers: int = 1):
        if cfg.num_rows % num_workers:
            raise ValueError(f"{cfg.num_rows} rows do not split over "
                             f"{num_workers} workers")
        self.cfg = cfg
        self.num_workers = int(num_workers)
        self.rows_per_worker = cfg.num_rows // self.num_workers

    def layout(self, A, mask, capacity: Optional[int] = None) -> dict:
        """:func:`layout` of dense ratings ``A`` observed where ``mask``,
        over this app's workers; a row with no rating is a free slot
        (``valid`` false)."""
        A, mask = np.asarray(A), np.asarray(mask)
        i, j = np.nonzero(mask)
        return layout(i, j, A[i, j], A.shape[0],
                      num_workers=self.num_workers, capacity=capacity,
                      valid=mask.any(axis=1))

    def to_dense(self, data, values=None):
        """Dense ``(N, M)`` host arrays of the entries' ``values``
        (default: the ratings) and the observation mask."""
        U, Np, M = self.num_workers, self.rows_per_worker, self.cfg.num_cols
        row = np.asarray(data["row"]).reshape(U, -1).astype(np.int64)
        col = np.asarray(data["col"]).reshape(U, -1)
        v = np.asarray(data["val"] if values is None else values)
        held = row < Np
        g = (row + np.arange(U)[:, None] * Np)[held]
        out = np.zeros((self.cfg.num_rows, M), np.float32)
        mask = np.zeros((self.cfg.num_rows, M), np.float32)
        out[g, col[held]] = v.reshape(U, -1)[held]
        mask[g, col[held]] = 1.0
        return out, mask

    # state: W,R row-sharded; H replicated (synced KV block)
    def init_state(self, rng, data=None, W0=None, H0=None):
        """Random factors (or ``W0``/``H0``) and the residual of every
        held entry, r = a − wᵢ·hⱼ."""
        cfg = self.cfg
        if data is None:
            raise ValueError("StradsMF.init_state needs the ratings layout "
                             "(data=, for the residual)")
        kw, kh = jax.random.split(rng)
        if W0 is None:
            W0 = jax.random.normal(kw, (cfg.num_rows, cfg.rank),
                                   jnp.float32) / jnp.sqrt(cfg.rank)
        if H0 is None:
            H0 = jax.random.normal(kh, (cfg.rank, cfg.num_cols),
                                   jnp.float32) / jnp.sqrt(cfg.rank)
        W, H = jnp.asarray(W0, jnp.float32), jnp.asarray(H0, jnp.float32)
        R = _residual(W, H, jnp.asarray(data["row"]),
                      jnp.asarray(data["col"]), jnp.asarray(data["val"]),
                      workers=self.num_workers)
        return {"W": W, "H": H, "R": R}

    def state_specs(self):
        return {"W": P("data"), "H": P(), "R": P("data")}

    def data_specs(self):
        return {"row": P("data"), "col": P("data"), "val": P("data"),
                "valid": P("data")}

    # -- schedule: round-robin (phase, rank) --------------------------------

    def default_scheduler_spec(self) -> SchedulerSpec:
        # the paper's round-robin dispatch over the q_p / r_p index sets
        return SchedulerSpec(kind="round_robin",
                             block_size=self.cfg.ranks_per_round)

    def num_schedulable(self) -> int:
        return self.cfg.rank

    # -- partition injection -------------------------------------------------
    # Rank blocks are interchangeable (mutually independent given the
    # other factor), so ownership may move freely; the activity signal
    # is the per-rank L1 mass of H — rank rows that move a lot pull
    # their server load with them.

    supported_partitioner_kinds = ("static", "size_balanced",
                                   "load_balanced")

    def default_partitioner_spec(self) -> PartitionerSpec:
        return PartitionerSpec(kind="static")

    def partition_signal(self, state):
        return jnp.sum(jnp.abs(state["H"]), axis=1)

    def partition_sizes(self):
        # bytes per rank: a row of H (M floats) + a column of W (N)
        cfg = self.cfg
        return [4 * (cfg.num_cols + cfg.num_rows)] * cfg.rank

    def static_phase(self, t: int) -> int:
        # Alternate H-phase (0) and W-phase (1) every round.
        return t % 2

    def propose(self, state, carry, rng, t, phase):
        # rank block for this round: the injected policy over K ranks,
        # advanced once per H/W cycle (two BSP rounds share a rank
        # block).  Stochastic policies must draw the SAME block in both
        # halves of a cycle, so the proposal key derives from the cycle
        # index off a fixed base — the fold_in pattern LDA's Gibbs keys
        # use — not from the per-round engine stream; like those Gibbs
        # keys, the schedule sequence is therefore deterministic across
        # runs regardless of the fit seed.
        cyc = t // 2
        key = jax.random.fold_in(jax.random.key(29), cyc)
        ks = self.scheduler.propose(carry, key, cyc, phase)
        return {"ranks": ks}

    def obs_counts(self, data, sched, phase):
        """(visited, updated) of one round, for the device counters: the
        entries every worker's sweep steps over, padding included, and
        the coordinates it updates (M per rank in an H-phase, the valid
        rows per rank in a W-phase).  Reads only the data and the
        schedule."""
        ranks = int(np.prod(jnp.shape(sched["ranks"])))
        visited = jnp.int32(data["row"].shape[0])
        if phase == 0:
            return visited, jnp.int32(ranks * self.cfg.num_cols)
        return visited, ranks * jnp.sum(data["valid"], dtype=jnp.int32)

    # -- push / pull ----------------------------------------------------------
    # Inside shard_map: a worker's entries (row, col local to its shard),
    # its residuals R and its W rows.  One rank at a time, all from the
    # same R (a block of ranks is one Jacobi step).

    @staticmethod
    def _ext(v):
        """A factor vector with a zero at the padding sentinel."""
        return jnp.concatenate([v, jnp.zeros((1,), v.dtype)])

    def push(self, data, state, sched, phase):
        M = self.cfg.num_cols
        W, H, R = state["W"], state["H"], state["R"]
        row, col = data["row"], data["col"]
        ks = sched["ranks"]
        if phase == 0:
            # H-phase: numerator/denominator partial sums by column
            ab = []
            with jax.named_scope("sweep"):
                for i in range(ks.shape[0]):
                    w = entry_sweep.take(self._ext(W[:, ks[i]]), row)
                    h = entry_sweep.take(H[ks[i]], col)
                    ab.append(entry_sweep.segment_sums((R + w * h) * w,
                                                       w * w, col, M))
            return {"a": jnp.stack([x[0] for x in ab]),
                    "b": jnp.stack([x[1] for x in ab])}, None
        # W-phase: rows are whole on this worker — no cross-worker sum;
        # zero-shaped partials keep the round uniform
        return {"a": jnp.zeros((ks.shape[0], 1), jnp.float32),
                "b": jnp.zeros((ks.shape[0], 1), jnp.float32)}, None

    def pull(self, state, sched, z, local, data, phase):
        lam = self.cfg.lam
        W, H, R = state["W"], state["H"], state["R"]
        row, col = data["row"], data["col"]
        ks = sched["ranks"]
        Np = W.shape[0]
        if phase == 0:
            H_new = z["a"] / (lam + z["b"])                        # g₃
            with jax.named_scope("sweep"):
                for i in range(ks.shape[0]):                       # sync
                    w = entry_sweep.take(self._ext(W[:, ks[i]]), row)
                    R = R - w * entry_sweep.take(H_new[i] - H[ks[i]], col)
            return {"W": W, "H": H.at[ks].set(H_new), "R": R}
        # W-phase (local closed-form CD for the rank block on local rows)
        W_new, hs = [], []
        with jax.named_scope("sweep"):
            for i in range(ks.shape[0]):
                w = entry_sweep.take(self._ext(W[:, ks[i]]), row)
                h = entry_sweep.take(H[ks[i]], col)
                hs.append(h)
                num, den = entry_sweep.segment_sums((R + w * h) * h, h * h,
                                                    row, Np + 1)
                W_new.append((num / (lam + den))[:Np])
            for i in range(ks.shape[0]):
                dw = self._ext(W_new[i]) - self._ext(W[:, ks[i]])
                R = R - entry_sweep.take(dw, row) * hs[i]
        W = W.at[:, ks].set(jnp.stack(W_new, axis=1))
        return {"W": W, "H": H, "R": R}

    # -- serving (query primitive) -------------------------------------------

    def query(self, state, batch):
        """``recommend``: top-k item scores for each requested user row
        (batch ``{"user": (B,)}`` → ``{"items": (B, k), "scores":
        (B, k)}``).  Scores are w_uᵀh_j over all items; W is
        worker-resident (served live at the boundary), H is the
        server-resident leaf (the possibly-stale half under
        ``kind="stale"`` — the same split an SSP training read sees)."""
        k = min(self.cfg.top_k, self.cfg.num_cols)
        Wu = jnp.take(state["W"], batch["user"], axis=0)   # (B, K)
        scores = Wu @ state["H"]                           # (B, M)
        top_scores, top_items = jax.lax.top_k(scores, k)
        return {"items": top_items, "scores": top_scores}

    # -- streaming (ingest primitives) ---------------------------------------

    #: ``valid`` is the row-validity channel, so free user rows (no
    #: ratings) can absorb extend-kind appends — such rows hold no entry
    #: and are exactly inert until a delta lands (the W-phase keeps them
    #: at 0)
    supported_stream_kinds = ("replace", "extend")

    def ingest_specs(self):
        return {"leaves": ("valid",),
                "valid": lambda data: np.asarray(data["valid"])}

    def ingest(self, data, state, rows, delta):
        """Overwrite user rows (refreshed ratings, or new users landing
        in free slots).  A delta's ``data`` holds each row's ratings,
        padded: ``col`` ``(k, L)`` (−1 where none) and ``val`` ``(k, L)``.
        The rows' old entries become padding, their new ones take free
        padding entries of the row's shard (a shard that has too few
        raises here, at the chunk boundary), and the residual of exactly
        those entries is made true: r = a − wᵢ·hⱼ.  A shard's entries stay
        sorted by (row, column), padding last, as :func:`layout` leaves
        them, so the layout depends on the ratings alone and not on how
        the deltas were split.  The W row is kept as a warm start; the
        next W-phase refits it."""
        U, Np = self.num_workers, self.rows_per_worker
        row = np.array(data["row"]).reshape(U, -1)
        col = np.array(data["col"]).reshape(U, -1)
        val = np.array(data["val"]).reshape(U, -1)
        valid = np.array(data["valid"])
        C = row.shape[1]
        rows = np.asarray(rows, np.int64)
        d_col = np.asarray(delta["data"]["col"])
        d_val = np.asarray(delta["data"]["val"], np.float32)
        freed, placed = [], []
        perm = np.arange(U * C).reshape(U, C)
        for u in np.unique(rows // Np):
            mine = rows // Np == u
            old = np.flatnonzero(np.isin(row[u], rows[mine] - u * Np))
            row[u, old], col[u, old], val[u, old] = Np, 0, 0.0
            freed.append(u * C + old)
            free = np.flatnonzero(row[u] == Np)
            keep = d_col[mine] >= 0
            n = int(keep.sum())
            if n > free.size:
                raise ValueError(
                    f"ingest: worker {int(u)} needs {n} entries for its "
                    f"{int(mine.sum())} new rows but has {free.size} free "
                    f"of its capacity {C}")
            slots = free[:n]
            local = np.broadcast_to((rows[mine] - u * Np)[:, None],
                                    keep.shape)[keep]
            row[u, slots] = local
            col[u, slots] = d_col[mine][keep]
            val[u, slots] = d_val[mine][keep]
            placed.append(u * C + slots)
            valid[rows[mine]] = keep.any(axis=1)
            perm[u] = u * C + np.lexsort((col[u], row[u]))
        perm = perm.reshape(-1)
        new_data = dict(data, row=row.reshape(-1)[perm],
                        col=col.reshape(-1)[perm], val=val.reshape(-1)[perm],
                        valid=valid)
        if state is None:
            return new_data, None
        freed, placed = np.concatenate(freed), np.concatenate(placed)
        g = (row.reshape(-1)[placed].astype(np.int64)
             + placed // C * Np)
        c = col.reshape(-1)[placed]
        res = (jnp.asarray(val.reshape(-1)[placed])
               - jnp.sum(state["W"][g] * state["H"].T[c], axis=-1))
        R = state["R"].at[freed].set(0.0).at[placed].set(res)
        return new_data, dict(state, R=R[perm])

    def objective_fn(self, mesh):
        cfg = self.cfg

        def local(R, W, H):
            sse = jnp.sum(R * R)
            wn = jnp.sum(W * W)
            tot = jax.lax.psum(sse + cfg.lam * wn, "data")
            return tot + cfg.lam * jnp.sum(H * H)

        fn = shard_map(local, mesh=mesh,
                       in_specs=(P("data"), P("data"), P()),
                       out_specs=P())
        return jax.jit(lambda s: fn(s["R"], s["W"], s["H"]))

    def objective_collect(self):
        """Global-expression objective for ``run_scanned`` collect."""
        lam = self.cfg.lam
        return lambda s: (jnp.sum(s["R"] * s["R"])
                          + lam * jnp.sum(s["W"] * s["W"])
                          + lam * jnp.sum(s["H"] * s["H"]))


# ---------------------------------------------------------------------------
# ALS baseline (GraphLab-style alternating least squares)
# ---------------------------------------------------------------------------

def als_step(A, mask, W, H, lam):
    """One full ALS alternation (dense masked closed-form solves)."""
    K = W.shape[1]
    eye = jnp.eye(K, dtype=W.dtype) * lam

    def solve_rows(Wrow_unused, a_row, m_row):
        # solve (Hᵀ diag(m) H + λI) w = Hᵀ diag(m) a
        G = (H * m_row) @ H.T + eye
        b = (H * m_row) @ a_row
        return jnp.linalg.solve(G, b)

    W = jax.vmap(solve_rows)(W, A, mask)

    def solve_cols(h_col_unused, a_col, m_col):
        G = (W.T * m_col) @ W + eye
        b = (W.T * m_col) @ a_col
        return jnp.linalg.solve(G, b)

    H = jax.vmap(solve_cols, in_axes=(1, 1, 1), out_axes=1)(H, A, mask)
    return W, H


def als_fit(A, mask, rank, lam, num_iters, rng):
    kw, kh = jax.random.split(rng)
    N, M = A.shape
    W = jax.random.normal(kw, (N, rank), jnp.float32) / jnp.sqrt(rank)
    H = jax.random.normal(kh, (rank, M), jnp.float32) / jnp.sqrt(rank)
    step = jax.jit(lambda W, H: als_step(A, mask, W, H, lam))
    trace = []
    for it in range(num_iters):
        W, H = step(W, H)
        R = (A - W @ H) * mask
        obj = float(jnp.sum(R * R) + lam * (jnp.sum(W * W) + jnp.sum(H * H)))
        trace.append((it, obj))
    return (W, H), trace


# ---------------------------------------------------------------------------
# Data + driver
# ---------------------------------------------------------------------------

def synthetic_ratings(rng: np.random.Generator, N: int, M: int,
                      true_rank: int, density: float = 0.3,
                      noise: float = 0.05):
    """Low-rank + noise ratings with a sparse observation mask, dense
    (laptop-scale tests; :meth:`StradsMF.layout` makes them entries)."""
    Wt = rng.normal(0, 1, size=(N, true_rank)).astype(np.float32)
    Ht = rng.normal(0, 1, size=(true_rank, M)).astype(np.float32)
    A = (Wt @ Ht / np.sqrt(true_rank)).astype(np.float32)
    A += noise * rng.normal(0, 1, size=A.shape).astype(np.float32)
    mask = (rng.uniform(size=A.shape) < density).astype(np.float32)
    return A * mask, mask


def _capped(weights: np.ndarray, top_share: float) -> np.ndarray:
    """``weights`` capped so that the largest holds ``top_share`` of the
    capped total (bisection on the cap); uniform where no share above
    1/n can reach it."""
    if top_share * weights.size <= 1.0:
        return np.ones_like(weights)
    if weights.max() <= top_share * weights.sum():
        return weights
    lo, hi = 0.0, float(weights.max())
    for _ in range(60):
        cap = 0.5 * (lo + hi)
        if cap > top_share * np.minimum(weights, cap).sum():
            hi = cap
        else:
            lo = cap
    return np.minimum(weights, lo)


def _popularity(z: np.ndarray, median: float, mean: float,
                top_share: float) -> np.ndarray:
    """Lognormal weights exp(σ z), capped at ``top_share``, with σ set by
    bisection so that their median over their mean is ``median / mean``
    (the cap lowers the mean, so σ is found after it)."""
    lo, hi = 0.0, 8.0
    for _ in range(40):
        sd = 0.5 * (lo + hi)
        w = _capped(np.exp(sd * z), top_share)
        if np.median(w) / w.mean() > median / mean:
            lo = sd
        else:
            hi = sd
    return _capped(np.exp(lo * z), top_share)


def _alias_table(p: np.ndarray):
    """Walker's alias table of the distribution ``p``: draw j uniform,
    keep it with probability ``prob[j]``, else take ``alias[j]``."""
    n = p.size
    prob = p * n
    alias = np.arange(n)
    small = [i for i in range(n) if prob[i] < 1.0]
    large = [i for i in range(n) if prob[i] >= 1.0]
    while small and large:
        s, g = small.pop(), large.pop()
        alias[s] = g
        prob[g] -= 1.0 - prob[s]
        (small if prob[g] < 1.0 else large).append(g)
    for i in small + large:              # rounding leaves these at ~1
        prob[i] = 1.0
    return prob, alias


def netflix_like_ratings(rng: np.random.Generator, num_rows: int,
                         num_cols: int, *, user_median: float = 96.0,
                         user_mean: float = 209.0,
                         movie_median: float = 561.0,
                         movie_mean: float = 5654.0,
                         top_share: float = 0.0023, true_rank: int = 10,
                         signal: float = 0.9, noise: float = 0.6,
                         offset: float = 3.64):
    """Ratings with the Netflix Prize's skew, vectorised and seeded.

    User degrees are lognormal (the given median and mean ratings a
    user), clipped to ``[1, num_cols]``.  Each rating's movie is drawn in
    proportion to lognormal popularity weights whose median over mean is
    that of the given ratings a movie, capped so that the top movie's
    weight is ``top_share`` of the total.  Repeated (user, movie) pairs
    are dropped and the users short of their degree draw again, up to
    :data:`TOP_UPS` times.  Values are integers 1–5: ``offset`` + ``signal``
    · a planted rank-``true_rank`` score (unit variance) + ``noise`` ·
    N(0, 1), rounded and clipped.

    Returns ``(rows, cols, vals, info)``: the ratings sorted by (user,
    movie) and ``info`` with the pairs ``drawn`` and ``kept``."""
    mu = np.log(user_median)
    sd = np.sqrt(2.0 * (np.log(user_mean) - mu))
    deg = np.clip(np.rint(rng.lognormal(mu, sd, num_rows)), 1,
                  num_cols).astype(np.int64)
    pop = _popularity(rng.standard_normal(num_cols), movie_median,
                      movie_mean, top_share)
    prob, alias = _alias_table(pop / pop.sum())

    def movies(n):
        """``n`` movies drawn by popularity (Walker's alias method)."""
        j = rng.integers(0, num_cols, n)
        return np.where(rng.random(n) < prob[j], j, alias[j])

    def unseen(sorted_keys, new):
        at = np.minimum(np.searchsorted(sorted_keys, new),
                        max(sorted_keys.size - 1, 0))
        return new[sorted_keys[at] != new] if sorted_keys.size else new

    users = np.repeat(np.arange(num_rows, dtype=np.int64), deg)
    keys = np.unique(users * num_cols + movies(users.size))
    count = np.bincount(keys // num_cols, minlength=num_rows)
    drawn, extra = users.size, np.zeros(0, np.int64)
    for _ in range(TOP_UPS):             # draw again what repeats took
        want = deg - count
        if not want.any():
            break
        users = np.repeat(np.arange(num_rows, dtype=np.int64), want)
        drawn += users.size
        new = unseen(extra, unseen(keys, np.unique(
            users * num_cols + movies(users.size))))
        extra = np.union1d(extra, new)
        count += np.bincount(new // num_cols, minlength=num_rows)
    keys = np.concatenate([keys, extra])
    keys.sort(kind="stable")                  # a merge of two sorted runs
    rows = (keys // num_cols).astype(np.int32)
    cols = (keys % num_cols).astype(np.int32)
    U = rng.normal(size=(num_rows, true_rank)).astype(np.float32)
    V = rng.normal(size=(num_cols, true_rank)).astype(np.float32)
    vals = np.empty(keys.size, np.float32)
    step = 1 << 20
    for s in range(0, keys.size, step):       # bounded host memory
        r, c = rows[s:s + step], cols[s:s + step]
        score = np.einsum("ek,ek->e", U[r], V[c]) / np.sqrt(true_rank)
        vals[s:s + step] = np.clip(np.rint(
            offset + signal * score
            + noise * rng.normal(size=r.size)), 1, 5)
    return rows, cols, vals, {"drawn": drawn, "kept": int(keys.size)}


def make_engine(cfg: MFConfig, mesh) -> StradsEngine:
    app = StradsMF(cfg, num_workers=mesh.shape["data"])
    return StradsEngine(app, mesh, data_specs=app.data_specs(),
                        state_specs=app.state_specs())


def fit(cfg: MFConfig, A: np.ndarray, mask: np.ndarray, mesh,
        num_rounds: Optional[int] = None, rng: Optional[jax.Array] = None,
        trace_every=None, executor=None, staleness=None, plan=None):
    """``plan``: an :class:`~repro.core.ExecutionPlan` (see lasso.fit;
    legacy ``executor=``/``staleness=`` kwargs deprecated).  For
    "pipelined"/"ssp", the rounds must divide into H/W phase cycles (and
    SSP windows).  The dense ``(A, mask)`` becomes the sparse layout
    once, on the host."""
    plan = _exec.resolve_plan(plan, num_rounds=num_rounds,
                              executor=executor, staleness=staleness,
                              trace_every=trace_every)
    rng = rng if rng is not None else jax.random.key(0)
    eng = make_engine(cfg, mesh)
    data = eng.shard_data(eng.app.layout(A, mask))
    state = eng.init_state(rng, data=data)
    every = plan.collect_every

    if plan.executor != "loop":
        collect = eng.app.objective_collect() if every else None
        rep = eng.execute(state, data, rng, plan, collect=collect)
        if collect is None:
            return rep.state, []
        return rep.state, _exec.decimate(np.asarray(rep.trace),
                                         plan.rounds, every)

    obj = eng.app.objective_fn(mesh)
    trace = []

    def cb(t, s, out):
        if every and (t % every == 0 or t == plan.rounds - 1):
            trace.append((t, float(obj(s))))
        return False

    rep = eng.execute(state, data, rng, plan, callback=cb)
    return rep.state, trace
