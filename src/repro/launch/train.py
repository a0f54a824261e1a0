"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch minicpm-2b \
        --preset reduced --steps 200 --batch 8 --seq 256

Runs the real substrate end to end on whatever devices exist (CPU here,
TPU pods via the same pjit path — the mesh is built from jax.devices()):
synthetic data pipeline → pjit'd train step (AdamW + schedule) →
checkpointing.  ``--strads`` turns on the paper's technique as
block-coordinate scheduled training (repro.sched.block); the block
policy is a declarative ``SchedulerSpec`` (``--scheduler``/``--rho``
flags or ``plan.scheduler`` — kind ``block_structural``).

``--scan-steps K`` rolls K train steps into a single ``lax.scan`` XLA
program with donated state (the training-substrate twin of
the engine's ``"scan"`` executor): one dispatch and one host sync per K
steps instead of per step.

``--staleness s`` (with ``--strads``) serves the block schedule from an
SSP-style stale cache: priorities are re-read and the schedule recomputed
only every s+1 steps (the trainer twin of the engine's ``"ssp"``
executor).

``--plan plan.json`` drives the same knobs declaratively from an
:class:`repro.core.ExecutionPlan` (rounds → steps, ``phase_unroll`` →
scan chunk, ``staleness``, ``checkpoint_every``), so one checked-in plan
file reproduces a run shape exactly — including across ``--resume``.

Checkpoints written via ``--ckpt-dir`` hold the *full* train state
(params, optimizer moments, step, and in strads mode the scheduler
priority/rng), so ``--resume`` continues bit-exactly: a resumed run
matches an uninterrupted one (tested in tests/test_ckpt_resume.py).
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from ..configs import ARCHS, get_config
from ..sched import SchedulerSpec
from ..sched.block import config_from_spec
from ..checkpoint import latest_step, restore_checkpoint, save_checkpoint
from ..data import SyntheticLMConfig, make_batch
from ..optim import AdamWConfig, cosine_schedule, wsd_schedule
from ..sharding.rules import activation_mesh
from ..train import TrainConfig, make_train_step, init_train_state
from ..train.step import init_strads_state, make_strads_train_step
from .cache import enable_compile_cache
from .mesh import make_test_mesh


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, default="minicpm-2b")
    ap.add_argument("--preset", choices=("reduced", "full"),
                    default="reduced")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--schedule", choices=("cosine", "wsd"), default=None)
    ap.add_argument("--strads", action="store_true",
                    help="STRADS block-coordinate scheduled updates")
    ap.add_argument("--scan-steps", type=int, default=1,
                    help="steps per lax.scan chunk (1 = host loop)")
    ap.add_argument("--blocks-per-step", type=int, default=0,
                    help="U for --strads (default: half the blocks)")
    ap.add_argument("--staleness", type=int, default=0,
                    help="SSP-style stale block schedule for --strads: "
                         "recompute the schedule every s+1 steps only")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--resume", action="store_true",
                    help="continue from the latest checkpoint in "
                         "--ckpt-dir (bit-exact: full state is saved)")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--plan", default="",
                    help="ExecutionPlan JSON driving the run shape: "
                         "rounds→steps, phase_unroll→scan-steps (scanned "
                         "executors), staleness→--staleness (implies "
                         "--strads), checkpoint_every→--ckpt-every, "
                         "scheduler→the --strads block policy; overrides "
                         "those flags")
    ap.add_argument("--scheduler", default="",
                    help="SchedulerSpec kind for the --strads block "
                         "schedule (only 'block_structural' has a "
                         "trainer lowering); implies --strads")
    ap.add_argument("--rho", type=float, default=None,
                    help="structural-filter threshold ρ for --scheduler "
                         "(with the 0/1 structural gram any value in "
                         "(0,1] is equivalent; min_distance is the real "
                         "knob)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.plan and (args.scheduler or args.rho is not None):
        ap.error("--scheduler/--rho conflict with --plan (the plan's "
                 "scheduler field — possibly null = default — is "
                 "authoritative); edit the plan file instead")
    sched_spec = None
    if args.plan:
        from ..core import ExecutionPlan
        with open(args.plan) as f:
            plan = ExecutionPlan.from_json(f.read())
        unsupported = [name for name, v in
                       (("telemetry", plan.telemetry),
                        ("collect_every", plan.collect_every),
                        ("workers", plan.workers),
                        # block-coordinate training has no variable-
                        # ownership store to repartition — only the
                        # paper apps consume plan.partitioner
                        ("partitioner", plan.partitioner),
                        # ...and no lasso_partial/gram_block hot-spots
                        # either: plan.kernels only drives the paper apps
                        ("kernels", plan.kernels)) if v]
        if unsupported:
            ap.error(f"--plan fields the trainer has no surface for "
                     f"(they would be silently dropped): {unsupported}")
        args.steps = plan.rounds
        args.scan_steps = (plan.phase_unroll
                           if plan.executor in ("scan", "pipelined")
                           else 1)
        args.staleness = plan.staleness
        if plan.staleness:
            args.strads = True           # stale schedules are strads-only
        if plan.checkpoint_every:
            args.ckpt_every = plan.checkpoint_every
        if plan.scheduler is not None:
            sched_spec = plan.scheduler
            args.strads = True           # a block policy is strads-only
        print(f"plan: {plan.to_json()}")
    elif args.scheduler or args.rho is not None:
        kind = args.scheduler or "block_structural"
        if kind != "block_structural":
            ap.error(f"the trainer's block-coordinate lowering only "
                     f"takes kind='block_structural'; got {kind!r} "
                     f"(the paper apps take any kind via their fit "
                     f"plans)")
        args.strads = True               # spec built once nblocks is known
    if sched_spec is not None and sched_spec.kind != "block_structural":
        ap.error(f"plan.scheduler kind {sched_spec.kind!r} has no "
                 f"trainer lowering (block-coordinate training needs "
                 f"'block_structural')")

    cfg = get_config(args.arch)
    if args.preset == "reduced":
        cfg = cfg.reduced()
    # default schedule: WSD for minicpm (its paper's schedule), else cosine
    sched_kind = args.schedule or ("wsd" if args.arch == "minicpm-2b"
                                   else "cosine")
    if sched_kind == "wsd":
        schedule = wsd_schedule(args.lr, args.steps // 10,
                                int(args.steps * 0.7),
                                args.steps - args.steps // 10
                                - int(args.steps * 0.7))
    else:
        schedule = cosine_schedule(args.lr, args.steps // 10, args.steps)
    tc = TrainConfig(adamw=AdamWConfig(), schedule=schedule)

    mesh = make_test_mesh()
    print(f"arch={cfg.name} preset={args.preset} devices={mesh.size} "
          f"mesh={dict(mesh.shape)}")

    rng = jax.random.PRNGKey(args.seed)
    if args.strads:
        from ..models.transformer import group_layout
        if cfg.family == "ssm":
            nblocks = cfg.num_layers + 1
        else:
            nblocks = group_layout(cfg)[0] + 1
        u = args.blocks_per_step or max(1, nblocks // 2)
        if sched_spec is None:
            # the conventional block_structural defaults, with the
            # trainer's historical adjacency radius of 1 layer-group
            sched_spec = SchedulerSpec.default_for(
                "block_structural", block_size=u,
                num_candidates=min(nblocks, 2 * u), min_distance=1,
                **({"rho": args.rho} if args.rho is not None else {}))
        sched = config_from_spec(sched_spec, nblocks)
        state = init_strads_state(cfg, tc, sched, rng,
                                  staleness=args.staleness)
        step_fn = make_strads_train_step(cfg, tc, sched,
                                         staleness=args.staleness)
        print(f"STRADS block scheduling: {sched.blocks_per_step}/"
              f"{nblocks} blocks per step "
              f"(spec: {sched_spec.to_json()})"
              + (f", schedule staleness {args.staleness}"
                 if args.staleness else ""))
    else:
        state = init_train_state(cfg, tc, rng)
        step_fn = make_train_step(cfg, tc)

    def chunk_fn(state, batches):
        # K steps as one scanned XLA program (the scan executor's sibling)
        def body(st, batch):
            return step_fn(st, batch)
        return jax.lax.scan(body, state, batches)

    with activation_mesh(mesh):
        if args.scan_steps > 1:
            chunk_jit = jax.jit(chunk_fn, donate_argnums=(0,))
        else:
            step_jit = jax.jit(step_fn, donate_argnums=(0,))

    dcfg = SyntheticLMConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                             batch_size=args.batch, seed=args.seed)
    dkw = {}
    if cfg.frontend == "audio":
        dkw = {"frames": True, "d_model": cfg.d_model}
    elif cfg.frontend == "vision":
        dkw = {"frontend_tokens": cfg.frontend_tokens,
               "d_model": cfg.d_model}

    def log_step(i, metrics, t0, history):
        m = {k: float(v) for k, v in metrics.items()}
        m["step"] = i
        m["wall_s"] = round(time.time() - t0, 1)
        history.append(m)
        print(f"step {i:5d}  loss {m['loss']:.4f}  acc {m['acc']:.3f}"
              f"  gnorm {m['grad_norm']:.2f}  lr {m['lr']:.2e}"
              f"  [{m['wall_s']}s]")

    def maybe_ckpt(i, chunk=None):
        # For a scanned chunk, fire if ANY step in it crossed a ckpt_every
        # boundary (the saved state is end-of-chunk — coarser cadence, but
        # no silently skipped checkpoints when the periods don't align).
        due = (any((j + 1) % args.ckpt_every == 0 for j in chunk)
               if chunk is not None else (i + 1) % args.ckpt_every == 0)
        if args.ckpt_dir and due:
            # full state (params + opt + step [+ scheduler]) so --resume
            # continues the exact run, optimizer moments included
            p = save_checkpoint(args.ckpt_dir, i + 1, state)
            print(f"checkpoint → {p}")

    start0 = 0
    if args.resume and args.ckpt_dir:
        last = latest_step(args.ckpt_dir)
        if last is not None:
            state = restore_checkpoint(args.ckpt_dir, last, state)
            start0 = last
            print(f"resumed from step {last} ({args.ckpt_dir})")

    history = []
    t0 = time.time()
    if args.scan_steps > 1:
        K = args.scan_steps
        for start in range(start0, args.steps, K):
            steps = range(start, min(start + K, args.steps))
            batches = [make_batch(dcfg, j, **dkw) for j in steps]
            stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *batches)
            state, ms = chunk_jit(state, stacked)
            last = steps[-1]
            if (any(j % args.log_every == 0 for j in steps)
                    or last == args.steps - 1):
                log_step(last, jax.tree.map(lambda v: v[-1], ms), t0,
                         history)
            maybe_ckpt(last, chunk=steps)
    else:
        for i in range(start0, args.steps):
            batch = make_batch(dcfg, i, **dkw)
            state, metrics = step_jit(state, batch)
            if i % args.log_every == 0 or i == args.steps - 1:
                log_step(i, metrics, t0, history)
            maybe_ckpt(i)
    if history:
        print(json.dumps({"first_loss": history[0]["loss"],
                          "last_loss": history[-1]["loss"],
                          "steps": args.steps,
                          "wall_s": history[-1]["wall_s"]}))
    return history


if __name__ == "__main__":
    main()
