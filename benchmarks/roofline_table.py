"""Aggregate results/dryrun/*.json into the §Roofline / §Dry-run tables.

Reads every per-cell record the dry-run sweep wrote and emits the
EXPERIMENTS.md tables: three terms + bottleneck + useful-compute ratio per
(arch x shape) on the single-pod mesh, plus the multi-pod fit table.

Also emits the §Partition-kernel roofline section: the analytic
assign-kernel sweep (launch/kernel_roofline.py) across platforms at the
hot-loop gate shape, plus the measured utilization record from
``BENCH_scaling.json`` when present.
"""
from __future__ import annotations

import glob
import json
import os

from .common import md_table, save_json

DRYRUN_DIR = os.environ.get("REPRO_DRYRUN_DIR", "results/dryrun")
BENCH_SCALING = "BENCH_scaling.json"


def load(dryrun_dir: str = DRYRUN_DIR):
    recs = []
    for path in sorted(glob.glob(os.path.join(dryrun_dir, "*.json"))):
        with open(path) as f:
            recs.append(json.load(f))
    return recs


def roofline_rows(recs):
    rows = []
    for r in recs:
        if r.get("mesh") != "single" or r.get("skipped") or not r.get("ok"):
            continue
        rl = r.get("roofline")
        if not rl:
            continue
        rows.append({
            "arch": r["arch"], "shape": r["shape"],
            "compute_s": rl["compute_s"], "memory_s": rl["memory_s"],
            "collective_s": rl["collective_s"],
            "bottleneck": rl["bottleneck"],
            "model_GF": rl["model_flops"] / 1e9,
            "useful_ratio": rl["useful_ratio"],
            "roofline_frac": rl["roofline_frac"],
        })
    return rows


def fit_rows(recs):
    rows = []
    for r in recs:
        if not r.get("ok"):
            rows.append({"arch": r.get("arch"), "shape": r.get("shape"),
                         "mesh": r.get("mesh"), "status": "FAILED"})
            continue
        if r.get("skipped"):
            rows.append({"arch": r["arch"], "shape": r["shape"],
                         "mesh": r["mesh"], "status": "skipped (full attn)"})
            continue
        m = r["memory"]
        rows.append({"arch": r["arch"], "shape": r["shape"],
                     "mesh": r["mesh"],
                     "status": "ok" if m["fits_hbm_16g"] else "OOM>16G",
                     "args_GB": m["argument_size_in_bytes"] / 2 ** 30,
                     "temp_GB": m["temp_size_in_bytes"] / 2 ** 30,
                     "live_GB": m["live_bytes"] / 2 ** 30,
                     "compile_s": r.get("compile_s", {}).get("compile")})
    return rows


def partition_kernel_rows(n: int = 1 << 20, d: int = 2, k: int = 64):
    """Analytic assign-kernel roofline per platform at the gate shape,
    with the measured record (BENCH_scaling.json) appended when present.
    Useful-vs-wasted compute shows up through ``prune_frac``: rows are
    emitted at 0% and 50% tile pruning so the table brackets what
    ``stats["tiles_pruned_frac"]`` buys at this shape."""
    from repro.launch.kernel_roofline import PLATFORMS, predict
    rows = []
    for platform in PLATFORMS:
        backend = "jnp" if platform == "cpu" else "pallas"
        for prune in (0.0, 0.5):
            p = predict(n, d, k, platform=platform, backend=backend,
                        prune_frac=prune)
            rows.append({
                "platform": platform, "backend": backend,
                "prune_frac": prune, "ai": p["ai"],
                "compute_ms": p["compute_s"] * 1e3,
                "memory_ms": p["memory_s"] * 1e3,
                "bound_ms": p["bound_s"] * 1e3,
                "bottleneck": p["bottleneck"], "utilization": None,
            })
    if os.path.exists(BENCH_SCALING):
        with open(BENCH_SCALING) as f:
            rec = json.load(f).get("roofline")
        if rec:
            rows.append({
                "platform": rec["platform"] + " (measured)",
                "backend": rec["backend"],
                "prune_frac": rec["prune_frac"], "ai": rec["ai"],
                "compute_ms": rec["compute_s"] * 1e3,
                "memory_ms": rec["memory_s"] * 1e3,
                "bound_ms": rec["bound_s"] * 1e3,
                "bottleneck": rec["bottleneck"],
                "utilization": rec["utilization"],
            })
    return rows


def run(quick: bool = False):
    pk = partition_kernel_rows()
    print("\n### §Partition-kernel roofline — assign sweep at the "
          "hot-loop gate shape (n=2^20, d=2, k=64)\n")
    print(md_table(pk, ["platform", "backend", "prune_frac", "ai",
                        "compute_ms", "memory_ms", "bound_ms",
                        "bottleneck", "utilization"]))
    recs = load()
    if not recs:
        print("no dry-run records found; run repro.launch.dryrun first")
        save_json("roofline_table", {"partition_kernel": pk})
        return {"partition_kernel": pk}
    rl = roofline_rows(recs)
    ft = fit_rows(recs)
    print("\n### §Roofline — three terms per (arch x shape), single pod "
          "(16x16 = 256 chips)\n")
    print(md_table(rl, ["arch", "shape", "compute_s", "memory_s",
                        "collective_s", "bottleneck", "useful_ratio",
                        "roofline_frac"]))
    print("\n### §Dry-run — compile + HBM fit, both meshes\n")
    print(md_table(ft, ["arch", "shape", "mesh", "status", "args_GB",
                        "temp_GB", "live_GB", "compile_s"]))
    ok = sum(1 for r in ft if r["status"] == "ok")
    sk = sum(1 for r in ft if "skip" in r["status"])
    bad = [r for r in ft if r["status"] not in ("ok",)
           and "skip" not in r["status"]]
    print(f"\ncells ok={ok} skipped={sk} problems={len(bad)}")
    out = {"roofline": rl, "fit": ft, "partition_kernel": pk}
    save_json("roofline_table", out)
    return out


if __name__ == "__main__":
    run()
