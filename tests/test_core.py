"""Unit + property tests for the STRADS core primitives."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import (DynamicPriorityScheduler, RandomScheduler,
                        RotationScheduler, RoundRobinScheduler,
                        dependency_filter, priority_weights,
                        sample_candidates)
from repro.sched.block import (BlockScheduleConfig, block_norms,
                               init_priority, mask_updates_by_block,
                               select_blocks, update_priority)


# ---------------------------------------------------------------------------
# Static schedulers
# ---------------------------------------------------------------------------

def test_round_robin_covers_all_vars():
    s = RoundRobinScheduler(num_vars=10, block_size=3)
    seen = set()
    for t in range(10):
        seen.update(np.asarray(s(jnp.int32(t))).tolist())
    assert seen == set(range(10))


def test_round_robin_indices_in_range():
    s = RoundRobinScheduler(num_vars=7, block_size=4)
    for t in range(20):
        idx = np.asarray(s(jnp.int32(t)))
        assert ((0 <= idx) & (idx < 7)).all()


def test_random_scheduler_distinct():
    s = RandomScheduler(num_vars=50, block_size=10)
    idx = np.asarray(s(jax.random.key(0)))
    assert len(set(idx.tolist())) == 10


def test_rotation_blocks_disjoint_and_complete():
    """At any round t, the blocks processed by the U workers partition the
    variable space — the LDA conditional-independence requirement."""
    s = RotationScheduler(num_vars=103, num_workers=4)
    b = np.asarray(s.bounds)
    assert b[0] == 0 and b[-1] == 103
    for t in range(4):
        masks = [np.asarray(s.block_mask(s.block_for_worker(p, t)))
                 for p in range(4)]
        total = np.stack(masks).sum(axis=0)
        assert (total == 1).all()       # disjoint cover


def test_rotation_every_worker_touches_every_block():
    s = RotationScheduler(num_vars=16, num_workers=4)
    for p in range(4):
        blocks = {int(s.block_for_worker(p, t)) for t in range(4)}
        assert blocks == {0, 1, 2, 3}


# ---------------------------------------------------------------------------
# Dynamic priority scheduling
# ---------------------------------------------------------------------------

def test_priority_weights_floor():
    w = priority_weights(jnp.zeros(5), eta=0.1)
    assert np.allclose(np.asarray(w), 0.1)


def test_sample_candidates_distinct_and_biased():
    weights = jnp.asarray([100.0, 100.0, 100.0, 0.001, 0.001])
    counts = np.zeros(5)
    for i in range(200):
        idx = np.asarray(sample_candidates(jax.random.key(i), weights, 2))
        assert len(set(idx.tolist())) == 2
        counts[idx] += 1
    # high-weight vars picked far more often
    assert counts[:3].min() > counts[3:].max()


def test_dependency_filter_blocks_correlated():
    # candidates 0 and 1 perfectly correlated: only one survives
    gram = jnp.asarray([[1.0, 0.99, 0.0],
                        [0.99, 1.0, 0.0],
                        [0.0, 0.0, 1.0]])
    keep = np.asarray(dependency_filter(gram, rho=0.5, max_select=3))
    assert keep[0] and not keep[1] and keep[2]


def test_dependency_filter_respects_max_select():
    gram = jnp.eye(8)
    keep = np.asarray(dependency_filter(gram, rho=0.5, max_select=3))
    assert keep.sum() == 3


@settings(max_examples=30, deadline=None)
@given(st.integers(2, 12), st.floats(0.05, 0.95), st.integers(1, 8),
       st.integers(0, 2**31 - 1))
def test_dependency_filter_invariant(u, rho, max_sel, seed):
    """Property: every admitted pair has correlation < ρ, and the kept set
    is maximal-greedy (first candidate always admitted)."""
    r = np.random.default_rng(seed)
    A = r.normal(size=(20, u)).astype(np.float32)
    A /= np.linalg.norm(A, axis=0, keepdims=True)
    gram = jnp.asarray(A.T @ A)
    keep = np.asarray(dependency_filter(gram, rho=rho, max_select=max_sel))
    assert keep.sum() <= max_sel
    assert keep[0]                       # greedy always admits the first
    kept = np.where(keep)[0]
    g = np.abs(np.asarray(gram))
    for a in kept:
        for b in kept:
            if a < b:
                assert g[a, b] < rho


def test_finalize_returns_static_shapes():
    dyn = DynamicPriorityScheduler(num_vars=100, num_candidates=16,
                                   block_size=4, rho=0.5)
    cand = dyn.propose(jnp.ones(100), jax.random.key(0))
    gram = jnp.eye(16)
    idx, mask = dyn.finalize(cand, gram)
    assert idx.shape == (4,) and mask.shape == (4,)
    assert mask.sum() <= 4


# ---------------------------------------------------------------------------
# Block scheduler (beyond-paper feature)
# ---------------------------------------------------------------------------

def test_select_blocks_distance_filter():
    cfg = BlockScheduleConfig(num_blocks=10, blocks_per_step=5,
                              candidates_per_step=10, min_distance=2)
    mask = np.asarray(select_blocks(cfg, init_priority(cfg),
                                    jax.random.key(0)))
    sel = np.where(mask > 0)[0]
    assert len(sel) >= 1
    assert len(sel) <= 5
    for a in sel:
        for b in sel:
            if a != b:
                assert abs(a - b) >= 2


def test_update_priority_only_touches_scheduled():
    cfg = BlockScheduleConfig(num_blocks=4, blocks_per_step=2,
                              candidates_per_step=4)
    pri = jnp.asarray([1.0, 2.0, 3.0, 4.0])
    norms = jnp.asarray([10.0, 10.0, 10.0, 10.0])
    sched = jnp.asarray([1.0, 0.0, 1.0, 0.0])
    new = np.asarray(update_priority(cfg, pri, norms, sched))
    assert new[1] == 2.0 and new[3] == 4.0     # unscheduled: unchanged
    assert new[0] > 1.0 and new[2] > 3.0       # scheduled: EMA toward norm


def test_mask_updates_by_block():
    updates = {"layer0": jnp.ones(3), "layer1": jnp.ones(3),
               "embed": jnp.ones(3)}
    block_of = {"layer0": 0, "layer1": 1}
    mask = jnp.asarray([0.0, 1.0])
    out = mask_updates_by_block(updates, block_of, mask)
    assert np.allclose(np.asarray(out["layer0"]), 0)
    assert np.allclose(np.asarray(out["layer1"]), 1)
    assert np.allclose(np.asarray(out["embed"]), 1)   # unmapped: untouched


def test_block_norms():
    updates = {"a": jnp.full((4,), 3.0), "b": jnp.full((9,), 2.0)}
    block_of = {"a": 0, "b": 1}
    n = np.asarray(block_norms(updates, block_of, 2))
    assert np.isclose(n[0], 6.0) and np.isclose(n[1], 6.0)


# ---------------------------------------------------------------------------
# Meshes: every axis Auto, so gathers on row-sharded state trace
# ---------------------------------------------------------------------------

def test_meshes_carry_auto_axes_and_mf_query_gathers():
    from jax.sharding import AxisType

    from repro.apps import mf
    from repro.core import single_device_mesh, worker_mesh
    from repro.launch.mesh import make_test_mesh

    mesh = single_device_mesh()
    assert mesh.axis_types == (AxisType.Auto,)
    assert worker_mesh(1).axis_types == (AxisType.Auto,)
    assert set(make_test_mesh().axis_types) == {AxisType.Auto}
    # MF recommend gathers rows of the row-sharded W (Explicit axes
    # raised ShardingTypeError here)
    cfg = mf.MFConfig(num_rows=8, num_cols=6, rank=2, top_k=3)
    A, mask = mf.synthetic_ratings(np.random.default_rng(0), 8, 6, 2)
    eng = mf.make_engine(cfg, mesh)
    state = eng.init_state(jax.random.key(0), A=jnp.asarray(A),
                           mask=jnp.asarray(mask))
    out = jax.jit(eng.app.query)(state,
                                 {"user": jnp.array([0, 5], jnp.int32)})
    assert out["items"].shape == (2, 3)
    want = np.argsort(-(np.asarray(state["W"])[[0, 5]]
                        @ np.asarray(state["H"])), axis=1)[:, :3]
    assert (np.asarray(out["items"]) == want).all()
