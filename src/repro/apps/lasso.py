"""STRADS Lasso (paper §3.3) and the Lasso-RR baseline.

Problem:   min_β ½‖y − Xβ‖² + λ‖β‖₁        (X standardized, no intercept)
CD update: β_j ← S(x_jᵀy − Σ_{k≠j} x_jᵀx_k β_k, λ)   with soft-threshold S.

With columns normalized to unit L2 norm and residual r = y − Xβ the update
is β_j ← S(x_jᵀ r + β_j, λ), and the distributed push computes the partial
dot products  z_{j,p} = (x_j^p)ᵀ r^p  over worker p's row shard (paper
eq. 6, rearranged through the residual — algebraically identical, O(n·U)
per round instead of O(n·J)).

schedule (STRADS, dynamic — ``SchedulerSpec(kind="dynamic_priority")``):
  1. propose U′ candidates with prob c_j ∝ |β_j^(t−1) − β_j^(t−2)| + η  (f₁)
  2. schedule_stats: candidate Gram block G = Σ_p (X_C^p)ᵀ X_C^p  (psum)
  3. greedy ρ-filter: keep ≤ U candidates with pairwise |x_jᵀx_k| < ρ (f₂)

schedule (Lasso-RR baseline — ``kind="random"``): U uniform-random
coordinates, no filter — imitating Shotgun [Bradley et al. 2011], which
diverges on correlated designs when U is large.

push:  z_{j,p} = (x_j^p)ᵀ r^p                                  (f₃)
pull:  β_j ← S(Σ_p z_{j,p} + β_j, λ);  r^p ← r^p − X_B^p Δβ_B  (f₄ + sync)

The policy is injected (v2 scheduler-injection contract): the app only
declares its default ``SchedulerSpec`` (from ``cfg.scheduler``) and
consumes whatever the plan resolves — swapping ρ/U′/kind is a plan edit,
not an app change.  The Δβ priority history is the engine-owned scheduler
carry (``EngineCarry.sched_carry``), no longer a state leaf.

The compute hot-spots follow the same contract (kernel-injection): the
push partials and the ρ-filter Gram block dispatch through
``self.kernels`` — the backend the engine resolves from
``plan.kernels`` (a :class:`~repro.kernels.spec.KernelSpec`) — so
swapping the reference jnp oracles for the fused Pallas kernels is a
plan edit too.  ``cfg.kernel_backend`` survives as the *default* the
app declares when the plan leaves ``kernels=None``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import StradsAppBase, StradsEngine
from repro.core.compat import shard_map
from repro.kernels import KernelSpec, build_kernels
from repro.part import PartitionerSpec
from repro.sched import SchedulerSpec

from . import _exec


def soft_threshold(x: jax.Array, lam: float) -> jax.Array:
    """S(x, λ) = sign(x)·max(|x| − λ, 0)  (Friedman et al., 2007)."""
    return jnp.sign(x) * jnp.maximum(jnp.abs(x) - lam, 0.0)


@dataclasses.dataclass(frozen=True)
class LassoConfig:
    num_features: int            # J
    lam: float = 0.1             # λ
    block_size: int = 8          # U  — concurrent updates per round
    num_candidates: int = 32     # U′ — proposal pool (STRADS only)
    rho: float = 0.3             # ρ  — dependency threshold (STRADS only)
    eta: float = 1e-6            # η  — priority floor
    scheduler: str = "strads"    # "strads" | "rr" (random) | "cyclic"
    # Default hot-spot kernel backend when the plan leaves kernels=None
    # ("auto" = pallas on TPU, reference elsewhere); a plan-level
    # KernelSpec always wins.
    kernel_backend: str = "auto"  # auto | ref | interpret | pallas


class StradsLasso(StradsAppBase):
    """The paper's Lasso on STRADS primitives; the scheduler arrives by
    injection, so the Lasso-RR baseline is literally the same app with a
    ``kind="random"`` spec (exactly how the paper built its baseline)."""

    supported_scheduler_kinds = ("dynamic_priority", "random",
                                 "round_robin")
    supported_kernel_kinds = ("reference", "pallas")

    def __init__(self, cfg: LassoConfig):
        self.cfg = cfg

    # -- scheduler injection -------------------------------------------------

    def default_scheduler_spec(self) -> SchedulerSpec:
        cfg = self.cfg
        if cfg.scheduler == "strads":
            return SchedulerSpec(kind="dynamic_priority",
                                 block_size=cfg.block_size,
                                 num_candidates=cfg.num_candidates,
                                 rho=cfg.rho, eta=cfg.eta)
        if cfg.scheduler == "rr":
            return SchedulerSpec(kind="random", block_size=cfg.block_size)
        if cfg.scheduler == "cyclic":
            return SchedulerSpec(kind="round_robin",
                                 block_size=cfg.block_size)
        raise ValueError(f"LassoConfig.scheduler must be 'strads', 'rr' "
                         f"or 'cyclic'; got {cfg.scheduler!r}")

    def num_schedulable(self) -> int:
        return self.cfg.num_features

    # -- kernel injection ----------------------------------------------------

    def default_kernel_spec(self) -> KernelSpec:
        kb = self.cfg.kernel_backend
        if kb == "auto":
            if jax.default_backend() == "tpu":
                return KernelSpec.default_for("pallas")
            return KernelSpec(kind="reference")
        if kb == "ref":
            return KernelSpec(kind="reference")
        if kb in ("pallas", "interpret"):
            # build_kernels flips interpret mode from the live platform,
            # so both legacy names resolve to the same spec.
            return KernelSpec.default_for("pallas")
        raise ValueError(f"LassoConfig.kernel_backend must be 'auto', "
                         f"'ref', 'interpret' or 'pallas'; got {kb!r}")

    def _kernels(self):
        # Engine-less direct calls (tests poking push/schedule_stats)
        # lazily self-inject the config default; under an engine the
        # resolved plan backend is already installed via use_kernels.
        if self.kernels is None:
            self.kernels = build_kernels(self.default_kernel_spec())
        return self.kernels

    # -- partition injection -------------------------------------------------
    # Coefficients are interchangeable, so every partition kind applies:
    # the ownership map is model-store bookkeeping (which worker serves
    # β_j), and the load balancer's activity signal is |Δβ| — the same
    # quantity the dynamic scheduler's priorities track.

    supported_partitioner_kinds = ("static", "size_balanced",
                                   "load_balanced")

    def default_partitioner_spec(self) -> PartitionerSpec:
        return PartitionerSpec(kind="static")

    def partition_signal(self, state):
        return state["beta"]

    @property
    def needs_schedule_stats(self) -> bool:
        # the Gram ρ-filter is the only policy needing the stats psum
        return self.scheduler is not None and self.scheduler.needs_stats

    # -- state: β (replicated), r (row-sharded) ------------------------------
    # (the Δβ priority history is the injected scheduler's carry, owned by
    # the engine — see EngineCarry.sched_carry)

    def init_state(self, rng, y=None):
        J = self.cfg.num_features
        if y is None:
            raise ValueError("StradsLasso.init_state needs y (the initial "
                             "residual r = y at β = 0)")
        return {
            "beta": jnp.zeros((J,), jnp.float32),
            "r": jnp.asarray(y, jnp.float32),       # r = y − Xβ, β=0
        }

    def state_specs(self):
        return {"beta": P(), "r": P("data")}

    def data_specs(self):
        return {"X": P("data"), "y": P("data")}

    # -- schedule ------------------------------------------------------------

    def propose(self, state, carry, rng, t, phase):
        return self.scheduler.propose(carry, rng, t, phase)

    def schedule_stats(self, data, state, candidates, phase):
        # Candidate Gram block over this worker's rows: (X_C^p)ᵀ X_C^p —
        # the ρ-filter hot-spot, served by the injected gram_block kernel.
        Xc = jnp.take(data["X"], candidates, axis=1)
        return self._kernels().gram_block(Xc)

    def schedule(self, state, carry, candidates, stats, rng, t, phase):
        idx, mask = self.scheduler.finalize(candidates, stats)
        return {"idx": idx, "mask": mask}

    def sched_update(self, carry, before, after, sched, phase):
        # Feed the committed Δβ of the scheduled block back into the
        # policy (f₁'s priority signal); stateless policies ignore it.
        if carry is None:
            return carry
        idx, mask = sched["idx"], sched["mask"]
        dx = jnp.take(after["beta"], idx) - jnp.take(before["beta"], idx)
        return self.scheduler.update_carry(carry, idx, mask, dx)

    # -- push / pull ----------------------------------------------------------

    def push(self, data, state, sched, phase):
        # z_{j,p} = (x_j^p)ᵀ r^p for each scheduled j (paper f₃) — the
        # push hot-spot, served by the injected lasso_partial kernel.
        Xb = jnp.take(data["X"], sched["idx"], axis=1)   # (n_p, U)
        z = self._kernels().lasso_partial(Xb, state["r"])
        return z, None

    def pull(self, state, sched, z, local, data, phase):
        cfg = self.cfg
        idx, mask = sched["idx"], sched["mask"]
        beta_old = jnp.take(state["beta"], idx)
        beta_new = soft_threshold(z + beta_old, cfg.lam)
        beta_new = jnp.where(mask, beta_new, beta_old)
        d = beta_new - beta_old

        # Guard duplicate indices from masked padding: only first occurrence
        # applies (mask already ensures kept indices are distinct).
        beta = state["beta"].at[idx].set(
            jnp.where(mask, beta_new, jnp.take(state["beta"], idx)))

        # residual maintenance on this worker's rows (the automatic sync of
        # the shared quantity r):  r ← r − X_B Δβ
        Xb = jnp.take(data["X"], idx, axis=1)
        r = state["r"] - Xb @ (d * mask)
        return {"beta": beta, "r": r}

    # -- serving (query primitive) -------------------------------------------

    def query(self, state, batch):
        """``predict``: ŷ = xᵀβ per request row (batch ``{"x": (B, J)}``
        → ``{"y_hat": (B,)}``).  Only β is read — the server-resident
        leaf, so under ``ServeSpec(kind="stale")`` a prediction is
        exactly as stale as an SSP worker's own read of β."""
        return {"y_hat": batch["x"] @ state["beta"]}

    # -- streaming (ingest primitives) ---------------------------------------

    #: every observation row is real (no validity channel to derive an
    #: extend-kind ring mask from), so only in-place replacement streams
    supported_stream_kinds = ("replace",)

    def ingest_specs(self):
        return {"leaves": ("X", "y"), "valid": None}

    def ingest(self, data, state, rows, delta):
        """Overwrite observation rows and keep the residual invariant
        ``r = y − Xβ`` true on exactly those rows (β is untouched — the
        next scheduled rounds react to the new data through r)."""
        rows = jnp.asarray(rows)
        X_new = jnp.asarray(delta["data"]["X"], jnp.float32)
        y_new = jnp.asarray(delta["data"]["y"], jnp.float32)
        new_data = dict(data,
                        X=data["X"].at[rows].set(X_new),
                        y=data["y"].at[rows].set(y_new))
        if state is None:
            return new_data, None
        r = state["r"].at[rows].set(y_new - X_new @ state["beta"])
        return new_data, dict(state, r=r)

    # -- objective -------------------------------------------------------------

    def objective_fn(self, mesh):
        """½‖y−Xβ‖² + λ‖β‖₁ as a jitted distributed reduction."""
        cfg = self.cfg

        def local(r, beta):
            sse = 0.5 * jnp.sum(r * r)
            return jax.lax.psum(sse, "data") + cfg.lam * jnp.sum(jnp.abs(beta))

        fn = shard_map(local, mesh=mesh, in_specs=(P("data"), P()),
                       out_specs=P())
        return jax.jit(lambda state: fn(state["r"], state["beta"]))

    def objective_collect(self):
        """Same objective as a global (non-shard_map) expression, usable as
        an ``execute`` collect fn inside the scan trace."""
        lam = self.cfg.lam
        return lambda s: (0.5 * jnp.sum(s["r"] * s["r"])
                          + lam * jnp.sum(jnp.abs(s["beta"])))


# ---------------------------------------------------------------------------
# Data generation (paper §4.1) + driver
# ---------------------------------------------------------------------------

def synthetic_correlated(rng: np.random.Generator, n: int, J: int,
                         corr: float = 0.9, k_true: int = 10,
                         noise: float = 0.1):
    """The paper's correlated synthetic design, dense laptop-scale variant.

    x₁ ~ U(0,1) noise; for j ≥ 2, with prob ``corr`` x_j gets fresh noise,
    otherwise x_j = 0.9·ε_{j−1} + 0.1·U(0,1) — adjacent features strongly
    correlated, which is exactly what breaks naive parallel CD.  Columns
    are standardized (zero mean, unit L2), y from a k_true-sparse β*.
    """
    eps = rng.uniform(0, 1, size=(n, J)).astype(np.float32)
    X = np.empty((n, J), np.float32)
    X[:, 0] = eps[:, 0]
    for j in range(1, J):
        fresh = rng.uniform() < corr
        X[:, j] = eps[:, j] if fresh else 0.9 * X[:, j - 1] + 0.1 * eps[:, j]
    X -= X.mean(axis=0)
    X /= np.maximum(np.linalg.norm(X, axis=0), 1e-12)
    beta_star = np.zeros((J,), np.float32)
    support = rng.choice(J, size=k_true, replace=False)
    beta_star[support] = rng.normal(0, 1, size=k_true).astype(np.float32)
    y = X @ beta_star + noise * rng.normal(0, 1, size=n).astype(np.float32)
    y = (y - y.mean()).astype(np.float32)
    return X, y, beta_star


def make_engine(cfg: LassoConfig, mesh,
                scheduler: Optional[SchedulerSpec] = None) -> StradsEngine:
    app = StradsLasso(cfg)
    return StradsEngine(app, mesh, data_specs=app.data_specs(),
                        state_specs=app.state_specs(), scheduler=scheduler)


def fit(cfg: LassoConfig, X: np.ndarray, y: np.ndarray, mesh,
        num_rounds: Optional[int] = None, rng: Optional[jax.Array] = None,
        trace_every: Optional[int] = None, plan=None):
    """Run STRADS Lasso; returns (state, trace of objective values).

    ``plan`` (an :class:`~repro.core.ExecutionPlan`) declares how to run:
    executor (``"loop"`` host loop / ``"scan"`` one ``lax.scan`` program,
    bit-identical to the loop / ``"pipelined"`` one-round-stale schedule
    prefetch / ``"ssp"`` bounded staleness, at s=0 bit-identical to
    ``"scan"``), rounds, the ``collect_every`` trace cadence, and the
    scheduling policy (``plan.scheduler``, a ``SchedulerSpec`` — ``None``
    runs the config's default policy).  Without a plan, ``num_rounds``
    and ``trace_every`` run the host loop.
    """
    plan = _exec.resolve_plan(plan, num_rounds=num_rounds,
                              trace_every=trace_every)
    rng = rng if rng is not None else jax.random.key(0)
    eng = make_engine(cfg, mesh)
    data = eng.shard_data({"X": jnp.asarray(X), "y": jnp.asarray(y)})
    state = eng.init_state(rng, y=y)
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


def reference_cd(X: np.ndarray, y: np.ndarray, lam: float,
                 num_sweeps: int) -> np.ndarray:
    """Single-machine cyclic CD oracle (ground truth for tests)."""
    J = X.shape[1]
    beta = np.zeros((J,), np.float32)
    r = y.copy()
    for _ in range(num_sweeps):
        for j in range(J):
            zj = X[:, j] @ r + beta[j]
            bj = np.sign(zj) * max(abs(zj) - lam, 0.0)
            r -= X[:, j] * (bj - beta[j])
            beta[j] = bj
    return beta
