import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the
# device count at first init); everything else follows.
"""Multi-pod dry-run: ``.lower().compile()`` every (architecture × input
shape) on the production meshes, record memory/cost/collective analysis.

    PYTHONPATH=src python -m repro.launch.dryrun --arch chatglm3-6b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

``--engine lasso|lda|mf`` instead lowers the multi-round STRADS executor
(``StradsEngine.scanned_fn``) on a worker mesh carved from the forced
512-device topology — proving that R rounds × U workers compile into ONE
XLA program (scan + psum + donated state) at production scale:

    PYTHONPATH=src python -m repro.launch.dryrun --engine lasso \
        --workers 16 --rounds 16 --pipeline-depth 1

``--staleness s`` lowers the bounded-staleness SSP program
(``StradsEngine.ssp_fn`` — worker caches, lazy pushes, batched flush
collectives) instead of the BSP scan:

    PYTHONPATH=src python -m repro.launch.dryrun --engine lda \
        --workers 16 --rounds 16 --staleness 2

``--scheduler``/``--rho``, ``--partitioner`` and ``--kernels`` override
the app's default scheduling/partitioning/kernel-backend policies from
flags; the resolved ``SchedulerSpec``/``PartitionerSpec``/``KernelSpec``
dicts (and the initial variable→worker assignment's shape) are recorded
in the artifact, along with the trip-count-aware HLO analysis and the
roofline terms (``launch/roofline.py`` renders/checks them):

    PYTHONPATH=src python -m repro.launch.dryrun --engine lasso \
        --workers 16 --rounds 16 --kernels pallas

``--plan plan.json`` (with ``--engine``) AOT-lowers a declarative
:class:`repro.core.ExecutionPlan` instead of the per-flag form — the
plan's executor/rounds/staleness/workers/scheduler/partitioner drive
the lowering and the plan dict is recorded in the result JSON:

    PYTHONPATH=src python -m repro.launch.dryrun --engine lasso \
        --plan examples/plans/ssp_s2.json

Streaming ingest (:mod:`repro.stream`) needs no dry-run mode of its
own: deltas land between compiled spans at host-synced boundaries, and
the ``"extend"`` ring keeps data shapes static, so a streamed run lowers
*exactly* the programs the unstreamed plan lowers — e.g.
``examples/plans/serve_stream.json`` (the CI-smoked serving+streaming
plan) dry-runs like any other SSP plan.

Results land in ``benchmarks/results/dryrun/<arch>__<shape>__<mesh>[__tag]
.json`` (existing files are skipped unless --force), which
``benchmarks/roofline.py`` renders into EXPERIMENTS.md §Dry-run/§Roofline.
"""
import argparse
import json
import time
import traceback

import jax

from ..configs import ARCHS, INPUT_SHAPES, get_config
from ..sharding.rules import activation_mesh
from . import roofline as RL
from .mesh import make_production_mesh
from .specs import build, skip_reason

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "benchmarks", "results", "dryrun")
# --engine records have a different schema (no arch/shape/mesh keys), so
# they live beside — not inside — the dryrun dir that roofline_report
# globs for its tables.
ENGINE_RESULTS_DIR = os.path.join(os.path.dirname(RESULTS_DIR), "engine")


def _result_path(arch, shape, mesh_name, tag):
    name = f"{arch}__{shape}__{mesh_name}"
    if tag:
        name += f"__{tag}"
    return os.path.join(RESULTS_DIR, name + ".json")


def run_one(arch: str, shape_name: str, mesh_name: str, tag: str = "",
            keep_hlo: bool = False) -> dict:
    multi = mesh_name == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    chips = mesh.size
    out = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "chips": chips, "tag": tag or "baseline"}

    reason = skip_reason(arch, shape_name)
    if reason:
        out["skipped"] = reason
        return out

    from .specs import apply_variant
    cfg = apply_variant(get_config(arch), tag or "baseline")
    shp = INPUT_SHAPES[shape_name]
    spec = build(arch, shape_name, mesh, variant=tag or "baseline")
    out["meta"] = spec.meta

    t0 = time.time()
    with activation_mesh(mesh):
        jitted = jax.jit(spec.fn, in_shardings=spec.in_shardings,
                         donate_argnums=spec.donate_argnums)
        lowered = jitted.lower(*spec.args)
    out["lower_s"] = round(time.time() - t0, 2)

    t0 = time.time()
    compiled = lowered.compile()
    out["compile_s"] = round(time.time() - t0, 2)

    # --- memory analysis (proves it fits) --------------------------------
    try:
        ma = compiled.memory_analysis()
        mem = {k: int(getattr(ma, k)) for k in
               ("argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "alias_size_in_bytes",
                "generated_code_size_in_bytes") if hasattr(ma, k)}
        mem["total_per_device"] = (mem.get("argument_size_in_bytes", 0)
                                   + mem.get("temp_size_in_bytes", 0)
                                   + mem.get("output_size_in_bytes", 0)
                                   - mem.get("alias_size_in_bytes", 0))
        out["memory"] = mem
    except Exception as e:                                   # pragma: no cover
        out["memory"] = {"error": repr(e)}

    # --- cost analysis (per-partition FLOPs / bytes) ---------------------
    try:
        ca = compiled.cost_analysis()
        out["cost"] = {"flops": float(ca.get("flops", 0.0)),
                       "bytes": float(ca.get("bytes accessed", 0.0)),
                       "transcendentals":
                           float(ca.get("transcendentals", 0.0))}
    except Exception as e:                                   # pragma: no cover
        out["cost"] = {"error": repr(e)}

    # --- full HLO analysis (loop-trip-count aware) ------------------------
    # XLA:CPU cost_analysis counts while bodies once; analyze_hlo walks the
    # call graph and charges every dot/collective by its enclosing trip
    # counts — see roofline.py.
    hlo = compiled.as_text()
    out["hlo_bytes"] = len(hlo)
    ana = RL.analyze_hlo(hlo, chips)
    out["hlo_analysis"] = ana.to_json()
    if keep_hlo:
        path = _result_path(arch, shape_name, mesh_name, tag) + ".hlo"
        with open(path, "w") as f:
            f.write(hlo)

    # --- roofline terms ---------------------------------------------------
    out["roofline"] = RL.roofline_terms(ana.flops, ana.bytes,
                                        ana.wire_bytes)
    out["roofline_raw_cost_analysis"] = RL.roofline_terms(
        out["cost"].get("flops", 0.0), out["cost"].get("bytes", 0.0),
        ana.wire_bytes)
    useful = RL.model_flops(cfg, shp)
    out["model_flops"] = useful
    hlo_flops_global = ana.flops * chips
    out["useful_flops_ratio"] = (useful / hlo_flops_global
                                 if hlo_flops_global else 0.0)
    return out


def _build_engine(engine: str, workers: int, mesh):
    """(eng, state, data, meta) for one of the three paper apps at a
    dry-run-friendly scale."""
    import numpy as np

    rng = np.random.default_rng(0)
    if engine == "lasso":
        from ..apps import lasso
        n, J = workers * 64, 1024
        X, y, _ = lasso.synthetic_correlated(rng, n=n, J=J, k_true=16)
        # The DEFAULT policy keeps the historical dry-run workload
        # (U=32, U'=128, rho=0.3 — a representative dynamic schedule, so
        # engine artifacts stay comparable across PRs), but it is no
        # longer baked in: run_engine resolves plan.scheduler /
        # --scheduler / --rho over this default via eng.set_scheduler
        # and records the spec that actually lowered in the artifact.
        cfg = lasso.LassoConfig(num_features=J, lam=0.02, block_size=32,
                                num_candidates=128)
        eng = lasso.make_engine(cfg, mesh)
        data = eng.shard_data({"X": X, "y": y})
        state = eng.init_state(jax.random.key(0), y=y)
        return eng, state, data, {"n": n, "J": J}
    if engine == "lda":
        from ..apps import lda
        cfg = lda.LDAConfig(vocab=workers * 64, num_topics=32,
                            num_workers=workers, tokens_per_worker=256,
                            docs_per_worker=16)
        words, docs, z0 = lda.synthetic_corpus(rng, cfg, true_topics=8)
        eng = lda.make_engine(cfg, mesh)
        data = eng.shard_data({"words": words, "docs": docs})
        state = eng.init_state(jax.random.key(0), words=words, docs=docs,
                               z0=z0)
        return eng, state, data, {"vocab": cfg.vocab,
                                  "topics": cfg.num_topics}
    if engine == "mf":
        from ..apps import mf
        N, M, K = workers * 64, 512, 16
        A, mask = mf.synthetic_ratings(rng, N, M, true_rank=K,
                                       density=0.2)
        cfg = mf.MFConfig(num_rows=N, num_cols=M, rank=K, lam=0.05)
        eng = mf.make_engine(cfg, mesh)
        data = eng.shard_data(eng.app.layout(A, mask))
        state = eng.init_state(jax.random.key(0), data=data)
        return eng, state, data, {"N": N, "M": M, "K": K}
    raise ValueError(f"unknown engine {engine!r}")


def engine_rounds(engine: str, workers: int, rounds: int,
                  staleness, unroll: int = 1) -> int:
    """Rounds actually lowered: the SSP program needs a whole number of
    lcm(staleness+1, phase_period) steps, the scanned program a whole
    number of phase_period × unroll steps — round up either way (the
    result names the artifact, keeping the skip-cache key honest)."""
    import math
    period = workers if engine == "lda" else {"lasso": 1, "mf": 2}[engine]
    L = (period * unroll if staleness is None
         else math.lcm(staleness + 1, period))
    return -(-rounds // L) * L


def run_engine(engine: str, workers: int, rounds: int, depth: int,
               staleness=None, unroll: int = 1, scheduler=None,
               sched_kind: str = "", rho=None, partitioner=None,
               part_kind: str = "", kernels=None,
               kern_kind: str = "", telemetry=None) -> dict:
    """Lower + compile the scanned (or, with ``staleness``, the SSP)
    STRADS executor on a ``workers``-wide data mesh (a slice of the
    forced-512 topology).  ``rounds`` must already be step-aligned
    (see :func:`engine_rounds`).  ``scheduler`` is an optional
    :class:`repro.sched.SchedulerSpec` overriding the app default;
    ``sched_kind``/``rho`` are the flag form, resolved against the app's
    own ``default_scheduler_spec()`` (so ``--rho`` alone moves only the
    threshold).  ``partitioner``/``part_kind`` do the same for the
    :class:`repro.part.PartitionerSpec` (flag form built by
    ``PartitionerSpec.default_for``), and ``kernels``/``kern_kind`` for
    the :class:`repro.kernels.KernelSpec` serving the round body's
    hot-spots.  ``telemetry`` (a :class:`repro.obs.TelemetrySpec`)
    instruments the lowering: the device counters ride the lowered
    program's scan carry (proving the instrumented program compiles at
    production scale), ``kind="trace"`` times the lower/compile phases
    with a host :class:`~repro.obs.events.Recorder`, and the resolved
    spec + a :class:`~repro.obs.report.RunReport` land in the artifact
    (``roofline --check``/``launch.trace`` read them back).  The
    resolved spec dicts — and the initial variable→worker assignment's
    shape — are recorded in the result, plus the trip-count-aware HLO
    analysis and roofline terms."""
    import numpy as np
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:workers]), ("data",))
    eng, state, data, meta = _build_engine(engine, workers, mesh)
    if scheduler is None and (sched_kind or rho is not None):
        scheduler = _override_spec(eng.app.default_scheduler_spec(),
                                   sched_kind, rho)
    eng.set_scheduler(scheduler)               # None → app default
    if partitioner is None and part_kind:
        from ..part import PartitionerSpec
        partitioner = PartitionerSpec.default_for(part_kind)
    eng.set_partitioner(partitioner)           # None → app default
    if kernels is None and kern_kind:
        from ..kernels import KernelSpec
        kernels = KernelSpec.default_for(kern_kind)
    eng.set_kernels(kernels)                   # None → app default → reference

    out = {"engine": engine, "workers": workers, "rounds": rounds,
           "pipeline_depth": depth, **meta}
    if eng.scheduler_spec is not None:
        out["scheduler"] = eng.scheduler_spec.to_json()
    if eng.partitioner_spec is not None:
        out["partitioner"] = eng.partitioner_spec.to_json()
        asgn = eng.partition_assignment
        out["assignment"] = {"num_vars": asgn.num_vars,
                             "num_workers": asgn.num_workers,
                             "version": asgn.version}
    if eng.kernel_spec is not None:
        out["kernels"] = eng.kernel_spec.to_json()
    if unroll != 1:
        out["phase_unroll"] = unroll
    import contextlib

    import jax.numpy as jnp
    rec = None
    obs0 = None
    if telemetry is not None:
        from ..obs import Recorder, init_counters
        out["telemetry"] = telemetry.to_json()
        obs0 = init_counters(eng.phase_period)
        if telemetry.events:
            rec = Recorder(profiler=telemetry.profiler)
    sc0 = eng.init_sched_carry()
    t0 = time.time()
    with rec.span("lower") if rec is not None else contextlib.nullcontext():
        if staleness is None:
            fn = eng.scanned_fn(rounds, pipeline_depth=depth,
                                unroll=unroll)
            lowered = fn.lower(state, data, jax.random.key(1),
                               jnp.int32(0), sc0, obs0)
        else:
            from .. import ps
            out["staleness"] = staleness
            fn = eng.ssp_fn(rounds, staleness=staleness)
            lowered = fn.lower(state, data, jax.random.key(1),
                               jnp.int32(0), ps.init_clocks(workers), sc0,
                               obs0)
    out["lower_s"] = round(time.time() - t0, 2)
    t0 = time.time()
    with (rec.span("compile") if rec is not None
          else contextlib.nullcontext()):
        compiled = lowered.compile()
    out["compile_s"] = round(time.time() - t0, 2)
    if telemetry is not None:
        from ..obs import RunReport
        executor = ("ssp" if staleness is not None
                    else ("pipelined" if depth else "scan"))
        out["run_report"] = RunReport.build(telemetry, executor, rounds,
                                            recorder=rec).to_json()
    try:
        ma = compiled.memory_analysis()
        out["memory"] = {k: int(getattr(ma, k)) for k in
                         ("argument_size_in_bytes", "output_size_in_bytes",
                          "temp_size_in_bytes", "alias_size_in_bytes")
                         if hasattr(ma, k)}
    except Exception as e:                                # pragma: no cover
        out["memory"] = {"error": repr(e)}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        out["cost"] = {"flops": float(ca.get("flops", 0.0)),
                       "bytes": float(ca.get("bytes accessed", 0.0))}
    except Exception as e:                                # pragma: no cover
        out["cost"] = {"error": repr(e)}
    # Trip-count-aware HLO analysis + roofline terms, same as run_one:
    # the R-round scan lowers to a while loop whose body XLA:CPU
    # cost_analysis counts once — analyze_hlo charges it R times, and
    # the psum collectives give the ring-model t_collective term that
    # `python -m repro.launch.roofline --check` asserts nonzero.
    hlo = compiled.as_text()
    out["hlo_bytes"] = len(hlo)
    ana = RL.analyze_hlo(hlo, workers)
    out["hlo_analysis"] = ana.to_json()
    out["roofline"] = RL.roofline_terms(ana.flops, ana.bytes,
                                        ana.wire_bytes)
    return out


def _override_spec(base, kind: str, rho):
    """Resolve the --scheduler/--rho flags against the app's OWN default
    policy: ``--rho`` alone keeps the default kind/U/U′ and moves only
    the threshold; a kind switch keeps the default block size and fills
    the remaining fields with that kind's conventional values."""
    import dataclasses as dc

    from ..sched import SchedulerSpec

    if kind and (base is None or kind != base.kind):
        bs = (base.block_size if base is not None and base.block_size
              else 32)
        nc = (base.num_candidates
              if base is not None and base.num_candidates >= bs
              else 0)
        base = SchedulerSpec.default_for(kind, block_size=bs,
                                         num_candidates=nc)
    if rho is not None:
        if base is None:
            raise SystemExit("--rho needs a policy to apply to: the app "
                             "has no default scheduler spec and no "
                             "--scheduler kind was given")
        base = dc.replace(base, rho=rho)   # spec validation guards kinds
    return base


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS)
    ap.add_argument("--shape", choices=list(INPUT_SHAPES))
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="single")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch × shape) pair")
    ap.add_argument("--tag", default="", help="variant tag (e.g. 'opt')")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--keep-hlo", action="store_true")
    ap.add_argument("--engine", choices=("lasso", "lda", "mf"),
                    help="lower the scanned STRADS executor instead of an "
                         "arch × shape spec")
    ap.add_argument("--workers", type=int, default=16)
    ap.add_argument("--rounds", type=int, default=16)
    ap.add_argument("--pipeline-depth", type=int, default=1,
                    choices=(0, 1))
    ap.add_argument("--staleness", type=int, default=None,
                    help="with --engine: lower the bounded-staleness SSP "
                         "executor (repro.ps) instead of the BSP scan")
    ap.add_argument("--plan", default="",
                    help="with --engine: an ExecutionPlan JSON file; its "
                         "executor/rounds/staleness/workers/scheduler "
                         "drive the lowering (overrides the per-flag "
                         "form)")
    ap.add_argument("--scheduler", default="",
                    help="with --engine: SchedulerSpec kind overriding "
                         "the app's default policy (round_robin|random|"
                         "rotation|dynamic_priority|block_structural)")
    ap.add_argument("--rho", type=float, default=None,
                    help="with --engine: dependency threshold ρ for the "
                         "dynamic scheduler kinds (overrides the app "
                         "default spec)")
    ap.add_argument("--partitioner", default="",
                    help="with --engine: PartitionerSpec kind overriding "
                         "the app's default partition policy (static|"
                         "size_balanced|load_balanced)")
    ap.add_argument("--kernels", default="",
                    choices=("", "reference", "pallas"),
                    help="with --engine: KernelSpec kind overriding the "
                         "app's default hot-spot backend (flag form "
                         "built by KernelSpec.default_for)")
    ap.add_argument("--telemetry", default="",
                    choices=("", "counters", "trace"),
                    help="with --engine: TelemetrySpec kind instrumenting "
                         "the lowering (device counters in the lowered "
                         "scan carry; 'trace' also times lower/compile "
                         "and embeds a RunReport in the artifact)")
    args = ap.parse_args()
    if args.plan and not args.engine:
        ap.error("--plan requires --engine (plans drive the STRADS "
                 "executor lowering, not the arch × shape specs)")
    if args.plan and (args.scheduler or args.rho is not None
                      or args.partitioner or args.kernels
                      or args.telemetry):
        ap.error("--scheduler/--rho/--partitioner/--kernels/--telemetry "
                 "conflict with --plan (the plan's scheduler/partitioner/"
                 "kernels/telemetry fields — possibly null/false = app "
                 "default/off — are authoritative); edit the plan file "
                 "instead")

    os.makedirs(RESULTS_DIR, exist_ok=True)

    if args.engine:
        os.makedirs(ENGINE_RESULTS_DIR, exist_ok=True)
        plan = None
        workers, rounds_req = args.workers, args.rounds
        depth, staleness, unroll = args.pipeline_depth, args.staleness, 1
        spec = None
        part_spec = None
        kern_spec = None
        tele_spec = None
        if args.plan:
            from ..core import ExecutionPlan
            with open(args.plan) as f:
                plan = ExecutionPlan.from_json(f.read())
            if plan.executor == "loop":
                raise SystemExit(
                    "a 'loop' plan is a per-round host loop — it has no "
                    "single-program lowering; use scan/pipelined/ssp")
            workers = plan.workers or args.workers
            rounds_req, depth = plan.rounds, plan.depth
            staleness = plan.staleness if plan.executor == "ssp" else None
            unroll = plan.phase_unroll
            spec = plan.scheduler         # None → the app's default policy
            part_spec = plan.partitioner  # None → the app's default
            kern_spec = plan.kernels      # None → app default → reference
            tele_spec = plan.telemetry or None   # False → uninstrumented
        elif args.telemetry:
            from ..obs import TelemetrySpec
            tele_spec = TelemetrySpec.default_for(args.telemetry)
        variant = (f"s{staleness}" if staleness is not None
                   else f"d{depth}")
        if spec is not None:
            variant += f"__{spec.kind}"
            if spec.rho:
                variant += f"-rho{spec.rho:g}"
        elif args.scheduler or args.rho is not None:
            variant += f"__{args.scheduler or 'default'}"
            if args.rho is not None:
                variant += f"-rho{args.rho:g}"
        if part_spec is not None:
            variant += f"__part-{part_spec.kind}"
        elif args.partitioner:
            variant += f"__part-{args.partitioner}"
        if kern_spec is not None:
            variant += f"__k-{kern_spec.kind}"
        elif args.kernels:
            variant += f"__k-{args.kernels}"
        if tele_spec is not None:
            variant += f"__obs-{tele_spec.kind}"
        rounds = engine_rounds(args.engine, workers, rounds_req, staleness,
                               unroll)
        if rounds != rounds_req:
            print(f"[note] rounds {rounds_req} → {rounds} "
                  f"(whole executor steps)")
        name = (f"strads-{args.engine}__U{workers}"
                f"__R{rounds}__{variant}")
        path = os.path.join(ENGINE_RESULTS_DIR, name + ".json")
        if os.path.exists(path) and not args.force:
            print(f"[skip-cached] {name}")
            return
        print(f"[dryrun] {name} ...", flush=True)
        res = run_engine(args.engine, workers, rounds, depth, staleness,
                         unroll=unroll, scheduler=spec,
                         sched_kind="" if args.plan else args.scheduler,
                         rho=None if args.plan else args.rho,
                         partitioner=part_spec,
                         part_kind="" if args.plan else args.partitioner,
                         kernels=kern_spec,
                         kern_kind="" if args.plan else args.kernels,
                         telemetry=tele_spec)
        if plan is not None:
            # record what actually ran: engine_rounds may have aligned
            # the round count to whole SSP steps
            import dataclasses
            res["plan"] = dataclasses.replace(plan, rounds=rounds).to_json()
        with open(path, "w") as f:
            json.dump(res, f, indent=1)
        print(f"  lower {res['lower_s']}s compile {res['compile_s']}s"
              f"  args {res['memory'].get('argument_size_in_bytes', -1)}B"
              f"  temp {res['memory'].get('temp_size_in_bytes', -1)}B")
        r = res["roofline"]
        print(f"  kernels {res.get('kernels', {}).get('kind', '?')}"
              f"  Tc {r['t_compute']*1e3:.2f}ms"
              f"  Tm {r['t_memory']*1e3:.2f}ms"
              f"  Tx {r['t_collective']*1e3:.2f}ms → {r['dominant']}")
        return
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    pairs = ([(a, s) for a in ARCHS for s in INPUT_SHAPES]
             if args.all else [(args.arch, args.shape)])

    failures = []
    for arch, shape in pairs:
        for mesh_name in meshes:
            path = _result_path(arch, shape, mesh_name, args.tag)
            if os.path.exists(path) and not args.force:
                print(f"[skip-cached] {arch} {shape} {mesh_name}")
                continue
            print(f"[dryrun] {arch} × {shape} × {mesh_name} ...",
                  flush=True)
            try:
                res = run_one(arch, shape, mesh_name, args.tag,
                              args.keep_hlo)
            except Exception:
                print(traceback.format_exc())
                failures.append((arch, shape, mesh_name))
                res = {"arch": arch, "shape": shape, "mesh": mesh_name,
                       "tag": args.tag or "baseline",
                       "error": traceback.format_exc(limit=3)}
            with open(path, "w") as f:
                json.dump(res, f, indent=1)
            if "skipped" in res:
                print(f"  skipped: {res['skipped']}")
            elif "error" not in res:
                r = res["roofline"]
                print(f"  lower {res['lower_s']}s compile {res['compile_s']}s"
                      f"  mem/dev {res['memory'].get('total_per_device', -1)/2**30:.2f} GiB"
                      f"  Tc {r['t_compute']*1e3:.2f}ms Tm {r['t_memory']*1e3:.2f}ms"
                      f"  Tx {r['t_collective']*1e3:.2f}ms → {r['dominant']}")
    if failures:
        print("FAILURES:", failures)
        raise SystemExit(1)
    print("dry-run complete")


if __name__ == "__main__":
    main()
