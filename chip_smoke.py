"""Run the partitioner's main path once on a TPU and check what comes out.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the sharded solve on four chips

One chip: a 2-D problem of n = 2^22 points in the refined-mesh layout
(``core.meshes.refined_points``), k = 64, epsilon = 0.03 and no graph goes
through the front door (``repro.partition``):

* the flat solve, ``partition(prob, method="geographer")``, twice (the
  first call includes compilation, the second does not), plus the
  compiler's ``memory_analysis()`` of that solve;
* the hierarchical solve, ``partition(prob, hierarchy=(8, 8))``;
* one warm ``repartition`` on weights drifted by the drifting-hotspot
  workload (``core.meshes.DriftingHotspot``);
* one assign call with the flat solve's centers and influence through the
  ``pallas`` and the ``jnp`` backends.

``backend="auto"`` must resolve to the compiled Pallas kernel, every solve
must end with labels in [0, k) and imbalance <= epsilon, and the two
backends must agree on >= 99.99% of the labels (near-ties are the only
allowed difference, DESIGN.md §4c) and on the best distances to f32
rounding.

``--four-chips`` runs only the flat solve at ``devices=4`` and
``devices=(2, 2)``, each against ``devices=1`` with warm-up off (the
agreement contract of DESIGN.md §3b): >= 97% identical labels, both
balanced, ``(2, 2)`` bit-identical to ``4``, and every shard's points on
its own chip.

Each phase prints one JSON line. The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without a TPU, or when a phase raises or a check fails, the script exits
non-zero and prints no such line. Everything runs in this one process.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

N = 1 << 22
K = 64
EPS = 0.03
SEED = 0
HIERARCHY = (8, 8)
LABEL_AGREEMENT = 0.9999       # pallas vs jnp, fixed centers
SHARD_AGREEMENT = 0.97         # devices=P vs devices=1, DESIGN.md §3b


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def _timed(fn, *args, **kw):
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    return out, time.perf_counter() - t0


def make_problem(n: int = N, k: int = K, seed: int = SEED):
    """The smoke problem: refined-mesh point layout, unit weights."""
    from repro.core.meshes import refined_points
    from repro.partition import PartitionProblem
    return PartitionProblem(points=refined_points(n, seed=seed), k=k,
                            epsilon=EPS, seed=seed,
                            name=f"refined{n}_2d")


def check_compiled_pallas(*, n_local: int | None = None) -> str:
    """``auto`` resolves to the Pallas backend and the kernel compiles
    (not interpreted) in this process."""
    from repro.kernels.assign_kernel import default_interpret
    from repro.kernels.ops import resolve_assign_backend
    backend = resolve_assign_backend("auto", sharded=n_local is not None,
                                     n_local=n_local)
    _check(backend == "pallas",
           f"backend='auto' resolved to {backend!r}, not 'pallas'")
    _check(not default_interpret(), "the Pallas kernel would interpret")
    return "pallas (compiled)"


def check_result(res, prob, phase: str) -> dict:
    import numpy as np
    labels = np.asarray(res.labels)
    _check(labels.shape == (prob.n,), f"{phase}: labels shape "
           f"{labels.shape} != ({prob.n},)")
    _check(bool((labels >= 0).all() and (labels < prob.k).all()),
           f"{phase}: labels outside [0, {prob.k})")
    imb = float(res.imbalance())
    _check(imb <= prob.epsilon,
           f"{phase}: imbalance {imb} > epsilon {prob.epsilon}")
    return {"imbalance": imb,
            "blocks_used": int(np.unique(labels).size)}


def flat_memory_analysis(prob) -> dict:
    """Compile the flat solve's jitted core for this device and report
    ``memory_analysis()`` and the Pallas custom calls in the program."""
    import jax

    from repro.core.partitioner import _run_jit
    from repro.partition.algorithms import make_bkm_config
    cfg = make_bkm_config(prob)
    pts = jax.ShapeDtypeStruct((prob.n, prob.dim), cfg.dtype)
    c0 = jax.ShapeDtypeStruct((prob.k, prob.dim), cfg.dtype)
    compiled, sec = _timed(lambda: _run_jit.lower(pts, cfg, None, c0)
                           .compile())
    calls = compiled.as_text().count("tpu_custom_call")
    _check(calls >= 1, "no Pallas kernel (tpu_custom_call) in the "
           "compiled flat solve")
    m = compiled.memory_analysis()
    return {"compile_seconds": sec, "tpu_custom_calls": calls,
            "temp_bytes": int(m.temp_size_in_bytes),
            "argument_bytes": int(m.argument_size_in_bytes),
            "output_bytes": int(m.output_size_in_bytes),
            "temp_bytes_per_point": m.temp_size_in_bytes / prob.n}


def assign_agreement(prob, centers, influence) -> dict:
    """One assign call through the pallas and jnp backends with fixed
    centers and influence: labels agree up to near-ties, best distances
    agree to f32 rounding of ``|p|^2 + |c|^2 - 2 p.c``."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.ops import assign_backend
    pts = jnp.asarray(prob.points, jnp.float32)
    c = jnp.asarray(centers, jnp.float32)
    infl = jnp.asarray(influence, jnp.float32)
    (ip, bp, _), sec_p = _timed(
        lambda: [np.asarray(x) for x in assign_backend("pallas")(pts, c,
                                                                infl)])
    (ij, bj, _), sec_j = _timed(
        lambda: [np.asarray(x) for x in assign_backend("jnp")(pts, c,
                                                             infl)])
    agree = float(np.mean(ip == ij))
    # f32 rounding of the expanded squared distance, scaled by the largest
    # 1/influence^2: a few ulps of the operand norms, not of the result
    scale = (float(np.max(np.sum(np.asarray(pts) ** 2, axis=1)))
             + float(np.max(np.sum(np.asarray(c) ** 2, axis=1))))
    tol = 16 * float(np.finfo(np.float32).eps) * scale * float(
        np.max(1.0 / np.asarray(infl) ** 2))
    diff = float(np.max(np.abs(bp - bj)))
    _check(agree >= LABEL_AGREEMENT,
           f"pallas/jnp label agreement {agree} < {LABEL_AGREEMENT}")
    _check(diff <= tol, f"pallas/jnp best distances differ by {diff} > "
           f"f32 tolerance {tol}")
    return {"label_agreement": agree, "labels_differing": int(
                np.sum(ip != ij)),
            "best_max_abs_diff": diff, "best_tolerance": tol,
            "pallas_seconds_incl_compile": sec_p,
            "jnp_seconds_incl_compile": sec_j}


def one_chip(n: int = N) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.core.meshes import DriftingHotspot
    from repro.partition import partition, repartition

    prob, sec = _timed(make_problem, n)
    _emit("problem", n=prob.n, k=prob.k, dim=prob.dim,
          epsilon=prob.epsilon, layout="refined2d", seconds=sec)

    backend = check_compiled_pallas()
    res, sec = _timed(partition, prob, method="geographer")
    lvl = res.stats["levels"][0]
    _emit("flat", backend=backend, seconds_incl_compile=sec,
          iters=int(lvl["iters"]),
          final_balance_iters=int(lvl["final_balance_iters"]),
          **check_result(res, prob, "flat"))
    res2, sec = _timed(partition, prob, method="geographer")
    _check(np.array_equal(res2.labels, res.labels),
           "flat: a second identical solve changed its labels")
    _emit("flat_again", seconds=sec, points_per_second=prob.n / sec,
          **check_result(res2, prob, "flat_again"))
    _emit("flat_memory_analysis", kind="compile result, not a measurement",
          **flat_memory_analysis(prob))

    backend = check_compiled_pallas()
    hier, sec = _timed(partition, prob, hierarchy=HIERARCHY)
    _emit("hierarchical", backend=backend, hierarchy=list(HIERARCHY),
          seconds_incl_compile=sec,
          coarse_imbalance=float(hier.stats["levels"][0]["imbalance"]),
          **check_result(hier, prob, "hierarchical"))

    w2 = np.asarray(DriftingHotspot().weights_at(
        jnp.asarray(prob.points, jnp.float32), 1), np.float64)
    drifted = prob.replace(weights=w2)
    backend = check_compiled_pallas()
    rep, sec = _timed(repartition, drifted, res)
    _emit("repartition", backend=backend, seconds_incl_compile=sec,
          iters=int(rep.stats["iters"]),
          migration_fraction=float(rep.stats["migration"]["fraction"]),
          **check_result(rep, drifted, "repartition"))

    _emit("assign", **assign_agreement(prob, res.centers, res.influence))


def four_chips(n: int = N) -> None:
    import jax
    import numpy as np

    from repro.partition import partition
    from repro.partition.distributed import ShardedPartitionProblem

    _check(len(jax.devices()) >= 4,
           f"--four-chips needs 4 devices, found {len(jax.devices())}")
    prob = make_problem(n)
    cap = -(-prob.n // 4)
    backend = check_compiled_pallas(n_local=cap)
    sp = ShardedPartitionProblem.from_problem(prob, (2, 2), commit=True,
                                              dtype=np.float32)
    homes = [s.device for s in sp.points.addressable_shards]
    _check(len(set(homes)) == 4 and all(
        s.data.shape == (1, cap, prob.dim)
        for s in sp.points.addressable_shards),
        f"shards are not one per chip: {homes}")
    _emit("placement", shard_devices=[str(d) for d in homes], cap=cap)
    del sp

    runs = {}
    for devices in (1, 4, (2, 2)):
        res, sec = _timed(partition, prob, devices=devices, warmup=False)
        name = f"devices={devices}"
        runs[devices] = res
        _emit("sharded", devices=name, backend=backend,
              seconds_incl_compile=sec,
              iters=int(res.stats["levels"][0]["iters"]),
              **check_result(res, prob, name))
    base = runs[1].labels
    for devices in (4, (2, 2)):
        agree = float(np.mean(runs[devices].labels == base))
        _check(agree >= SHARD_AGREEMENT,
               f"devices={devices} agrees with devices=1 on {agree} of "
               f"labels < {SHARD_AGREEMENT}")
        _emit("agreement", devices=f"{devices}", vs="devices=1",
              label_agreement=agree)
    same = bool(np.array_equal(runs[(2, 2)].labels, runs[4].labels))
    _check(same, "devices=(2, 2) labels differ from devices=4")
    _emit("agreement", devices="(2, 2)", vs="devices=4", bit_identical=same)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded flat solve on four chips")
    args = ap.parse_args(argv)

    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}; run this from "
              "a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    from repro.envflags import use_compile_cache
    cache = use_compile_cache()        # before the first jax import

    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (jax platform {dev.platform!r}); "
              "this smoke run only counts on the chip", file=sys.stderr)
        return 1
    _emit("device", platform=dev.platform, kind=dev.device_kind,
          count=len(devs), jax=jax.__version__, compile_cache=cache)
    try:
        (four_chips if args.four_chips else one_chip)()
        stats = dev.memory_stats() or {}
        _emit("device_memory",
              peak_bytes_in_use=stats.get("peak_bytes_in_use"),
              bytes_limit=stats.get("bytes_limit"))
    except Exception as e:                     # noqa: BLE001
        import traceback
        traceback.print_exc()
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
