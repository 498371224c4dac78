"""Benchmark orchestrator — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only quality,...]

All partitioning benchmarks go through the unified engine
(``repro.partition``)::

    prob = PartitionProblem.from_mesh(mesh, k, epsilon=0.03)
    res  = partition(prob, method="geographer")     # or rcb/rib/sfc/mj
    res  = partition(prob, hierarchy=(8, 8))        # hierarchical k1 x k2

so every tool row is one ``partition(problem, method=...)`` call and the
hierarchical (coarse Geographer + batched vmap refinement) mode appears
as its own row/column where applicable.

Modules:
  quality     — Tables 1-2 + Fig 2 (partition quality vs RCB/RIB/HSFC/MJ
                + hierarchical k1xk2)
  scaling     — Fig 3a/3b (weak/strong scaling; flat vs hierarchical)
  repartition — dynamic repartitioning: warm-started Geographer vs cold
                restart on a drifting-hotspot workload (iterations,
                migration volume, per-step balance)
  serving     — multi-tenant PartitionServer: slot-bucketed batched
                dispatch + warm-state cache vs all-cold serving
                (throughput, request latency, warm-hit rate)
  experiments — §5 comparison matrix: every registered method × the
                expanded mesh zoo, sharded in-graph evaluation, with the
                paper-trend summary (geographer vs sfc/rcb comm volume)
  components  — §5.3.2 component shares + §4.3 bound-skip-rate claim
  moe_router  — paper Eq. (1) as MoE load balancing (framework integration)
  roofline    — §Roofline/§Dry-run aggregation from results/dryrun/*.json
"""
from __future__ import annotations

import argparse
import time
import traceback

ALL = ["quality", "scaling", "repartition", "serving", "experiments",
       "components", "moe_router", "roofline"]


def _prepare_env() -> None:
    """Expose 8 virtual CPU devices so the SPMD scaling section runs on
    single-CPU hosts, and turn on the persistent compilation cache. Must
    run before the first jax import — main() calls this before importing
    any benchmark module."""
    from repro.envflags import force_virtual_devices, use_compile_cache
    force_virtual_devices(8)
    use_compile_cache()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced sizes (CI-friendly)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: " + ",".join(ALL))
    ap.add_argument("--json", action="store_true",
                    help="also emit machine-readable BENCH_<name>.json "
                         "regression files (quality, scaling, "
                         "repartition, serving, experiments)")
    args = ap.parse_args()
    names = args.only.split(",") if args.only else ALL
    _prepare_env()

    failures = []
    for name in names:
        print(f"\n{'=' * 72}\n== benchmark: {name}\n{'=' * 72}")
        t0 = time.perf_counter()
        try:
            if name == "quality":
                from . import quality
                quality.run(quick=args.quick, json_out=args.json)
            elif name == "scaling":
                from . import scaling
                scaling.run(quick=args.quick, json_out=args.json)
            elif name == "repartition":
                from . import repartition
                repartition.run(quick=args.quick, json_out=args.json)
            elif name == "serving":
                from . import serving
                serving.run(quick=args.quick, json_out=args.json)
            elif name == "experiments":
                from . import experiments
                experiments.run(quick=args.quick, json_out=args.json)
            elif name == "components":
                from . import components
                components.run(quick=args.quick)
            elif name == "moe_router":
                from . import moe_router
                moe_router.run(quick=args.quick)
            elif name == "roofline":
                from . import roofline_table
                roofline_table.run(quick=args.quick)
            else:
                raise KeyError(name)
        except Exception:
            failures.append(name)
            traceback.print_exc()
        print(f"[{name}] done in {time.perf_counter() - t0:.1f}s")
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")


if __name__ == "__main__":
    main()
