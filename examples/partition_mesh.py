"""Full partitioning scenario through the unified engine: weighted 2.5D
climate-style mesh (the paper's motivating application), every registered
method, hierarchical k = 8 x 8 recursion, an optional SPMD distributed
run, and a dynamic-repartitioning time loop (drifting workload, warm vs
cold restart).

    PYTHONPATH=src python examples/partition_mesh.py [--n 30000] [--k 64]
    PYTHONPATH=src python examples/partition_mesh.py --quick
    PYTHONPATH=src python examples/partition_mesh.py --repartition
    PYTHONPATH=src python examples/partition_mesh.py --distributed
        (forces 8 host devices; run in a fresh process)

The single-host path is three lines of API::

    prob = PartitionProblem.from_mesh(mesh, k=64, epsilon=0.03)
    res  = partition(prob, method="geographer")       # or rcb/rib/sfc/mj
    res  = partition(prob, hierarchy=(8, 8))          # k1 x k2 recursive

``hierarchy=(8, 8)`` cuts 8 coarse blocks with Geographer, then refines
all 8 blocks into 8 sub-blocks each in ONE batched vmap dispatch; block b
owns labels [8b, 8b+8) and the measured global imbalance still respects
``epsilon``.
"""
from repro.envflags import use_compile_cache

use_compile_cache()          # before the first jax import

import argparse
import time

import numpy as np


def single_host(n: int, k: int):
    from repro.core import meshes
    from repro.partition import (PartitionProblem, available_methods,
                                 factor_k, partition)

    mesh = meshes.REGISTRY["climate25d"](n, seed=0)
    print(f"mesh: {mesh.name} n={mesh.n} m={mesh.m} "
          f"(node weights: vertical column depth)")
    prob = PartitionProblem.from_mesh(mesh, k, epsilon=0.03)

    for name in available_methods():
        t0 = time.perf_counter()
        res = partition(prob, method=name)
        dt = time.perf_counter() - t0
        ev = res.evaluate(with_diameter=True)
        print(f"{name:12s} t={dt:6.2f}s cut={ev['cut']:7d} "
              f"maxCV={ev['maxCommVol']:6d} sumCV={ev['totalCommVol']:7d} "
              f"diam={ev['diameter_harmonic_mean']:6.1f} "
              f"imb={ev['imbalance']:.4f}")

    # hierarchical k = k1 x k2 (e.g. 8 x 8 = 64 blocks): coarse Geographer
    # + all k1 refinements in one batched vmap dispatch
    k1, k2 = factor_k(k)
    t0 = time.perf_counter()
    res = partition(prob, hierarchy=(k1, k2))
    dt = time.perf_counter() - t0
    ev = res.evaluate(with_diameter=True)
    lvl = res.stats["levels"]
    print(f"{f'hier {k1}x{k2}':12s} t={dt:6.2f}s cut={ev['cut']:7d} "
          f"maxCV={ev['maxCommVol']:6d} sumCV={ev['totalCommVol']:7d} "
          f"diam={ev['diameter_harmonic_mean']:6.1f} "
          f"imb={ev['imbalance']:.4f} "
          f"(coarse imb={lvl[0]['imbalance']:.4f}, "
          f"refine dispatches={lvl[1]['dispatches']})")
    assert ev["imbalance"] <= prob.epsilon + 1e-6
    assert len(np.unique(res.labels)) == k1 * k2


def distributed(n: int, k: int, shards: int = 8):
    """The paper's SPMD structure through the engine front door
    (``devices=P``): points sharded round-robin, centers replicated,
    psum-only communication. Needs forced host devices -> fresh process."""
    from repro.envflags import force_virtual_devices
    force_virtual_devices(shards, override=True)
    from repro.core import meshes
    from repro.partition import PartitionProblem, partition

    m = meshes.REGISTRY["delaunay2d"](n, seed=0)
    prob = PartitionProblem.from_mesh(m, k, epsilon=0.03)
    ref = partition(prob, method="geographer")     # single-device reference
    for d in (1, shards):
        t0 = time.perf_counter()
        res = partition(prob, method="geographer", devices=d)
        dt = time.perf_counter() - t0
        agree = float(np.mean(res.labels == ref.labels))
        print(f"devices={d}: t={dt:.2f}s imbalance={res.imbalance():.4f} "
              f"label agreement vs single-device={agree:.4f}")
        assert res.imbalance() <= prob.epsilon + 1e-6


def dynamic(n: int, k: int, steps: int = 6):
    """Time loop: a drifting-hotspot load over a fixed mesh, repartitioned
    every step — warm-started Geographer vs a cold restart, reporting the
    migration each would cost (the dynamic repartitioning story,
    DESIGN.md §8)."""
    from repro.core import meshes
    from repro.core.timeseries import simulate_loadbalance
    from repro.partition import PartitionProblem

    mesh = meshes.REGISTRY["delaunay2d"](n, seed=0)
    prob = PartitionProblem.from_mesh(mesh, k, epsilon=0.03)
    workload = meshes.WORKLOADS["drifting_hotspot"]()
    print(f"mesh: {mesh.name} n={mesh.n} k={k} "
          f"workload={type(workload).__name__} T={steps}")
    for mode in ("warm", "cold"):
        sim = simulate_loadbalance(prob, workload, steps, mode=mode)
        s = sim["summary"]
        print(f"{mode:5s}: mean iters={s['mean_iters']:.2f} "
              f"mean migration={s['mean_migration_fraction']:.4f} "
              f"max imbalance={s['max_imbalance']:.4f} "
              f"(all balanced: {s['all_balanced']})")
        assert s["all_balanced"]


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=30_000)
    ap.add_argument("--k", type=int, default=64)
    ap.add_argument("--distributed", action="store_true")
    ap.add_argument("--repartition", action="store_true",
                    help="dynamic repartitioning time loop")
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized run of every section")
    args = ap.parse_args()
    if args.quick:
        args.n, args.k = min(args.n, 4_000), min(args.k, 16)
    if args.distributed:
        distributed(min(args.n, 20_000), min(args.k, 16))
    elif args.repartition:
        dynamic(args.n, min(args.k, 16), steps=4 if args.quick else 6)
    else:
        single_host(args.n, args.k)
        dynamic(min(args.n, 8_000), min(args.k, 16),
                steps=3 if args.quick else 6)
