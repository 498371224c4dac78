"""Quickstart: the unified engine in four calls — partition a 2D mesh
with Geographer (balanced k-means), compare against recursive coordinate
bisection, then track a drifting load with a warm-started repartition.

    PYTHONPATH=src python examples/quickstart.py [--quick]
"""
from repro.envflags import use_compile_cache

use_compile_cache()          # before the first jax import

import argparse

import numpy as np

from repro.core import meshes
from repro.partition import PartitionProblem, partition, repartition


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized run (smaller mesh)")
    args = ap.parse_args()
    n, k = (2_000, 8) if args.quick else (8_000, 16)

    mesh = meshes.REGISTRY["refined2d"](n, seed=0)
    print(f"mesh: {mesh.name}  n={mesh.n}  m={mesh.m}")
    prob = PartitionProblem.from_mesh(mesh, k, epsilon=0.03)

    ours = partition(prob, method="geographer", evaluate=True,
                     with_diameter=True)
    iters = int(np.asarray(ours.stats["levels"][0]["iters"]))
    print(f"\nGeographer  (iters={iters}, "
          f"imbalance={ours.stats['final_imbalance']:.4f}):")
    for kk, v in ours.quality.items():
        print(f"  {kk:24s} {v}")

    rcb = partition(prob, method="rcb", evaluate=True, with_diameter=True)
    print("\nRCB:")
    for kk, v in rcb.quality.items():
        print(f"  {kk:24s} {v}")

    dv = ours.quality["totalCommVol"] / max(rcb.quality["totalCommVol"], 1)
    print(f"\ntotal comm volume vs RCB: {dv:.3f}x "
          f"({'better' if dv < 1 else 'worse'})")
    assert ours.quality["imbalance"] <= 0.03 + 1e-6, \
        "balance constraint violated!"

    # the load drifts -> warm-restart from the previous result instead of
    # re-solving from scratch (see docs/api.md "repartition")
    workload = meshes.WORKLOADS["drifting_hotspot"]()
    res = partition(prob.replace(weights=np.asarray(
        workload.weights_at(mesh.points, 0))), method="geographer")
    print("\ndrifting hotspot, warm restarts:")
    steps = 3 if args.quick else 5
    for t in range(1, steps + 1):
        w_t = np.asarray(workload.weights_at(mesh.points, t))
        res = repartition(prob.replace(weights=w_t), res)
        mig = res.stats["migration"]
        print(f"  t={t}: iters={res.stats['iters']} "
              f"migrated={mig['fraction']:.3f} "
              f"imbalance={res.imbalance():.4f}")
        assert res.imbalance() <= 0.03 + 1e-6


if __name__ == "__main__":
    main()
