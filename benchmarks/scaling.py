"""Paper Figure 3 + 4 analogue: scaling of the partitioner, through the
unified ``repro.partition`` engine.

No MPI cluster exists in this container, so the paper's weak/strong axes
map to what is measurable here:

* SPMD scaling — the headline section: the sharded shard_map partitioner
  (``partition(problem, method=..., devices=d)``) over 1/2/4/8 virtual
  host devices (``XLA_FLAGS=--xla_force_host_platform_device_count=8``,
  set by benchmarks/run.py), flat geographer vs hierarchical k1 x k2 with
  a distributed coarse pass. Communication structure is identical to the
  paper's MPI version: psum'd global vector sums only. Per row we record
  wall time (steady-state, compile separated out), edge cut, total comm
  volume, imbalance and movement iterations — the regression-gate metric
  set of ``BENCH_scaling.json``.
* weak scaling — n grows with k at fixed n/k ("vertices per block"),
  wall-time per partition call (Fig. 3a analogue);
* strong scaling — fixed n, growing k (Fig. 3b analogue), flat vs
  hierarchical ``partition(hierarchy=(k1, k2))``.
* hot loop — one movement-iteration sweep (assignment + per-cluster
  moment reductions) at n=2^20: the fused assign+reduce backend mode vs
  the PR 4 fixed-chunk fused baseline, the unfused fallback (assignment,
  then a separate ``segment_moments`` sweep — bit-for-bit identical
  results) and the legacy pre-fusion hot loop (scatter-masked second-best
  + three global ``segment_sum`` passes, the shape this engine shipped
  with). Gated by ``tools/bench_compare.py``: fused must be >= 1.3x over
  legacy and >= 1.1x over the PR 4 fused baseline, must not lose to the
  fallback, and must stay bit-exact.
* roofline — analytic FLOPs/bytes/arithmetic-intensity of the hot-loop
  sweep (launch/kernel_roofline.py) against per-platform peaks, with the
  measured fused median folded in as achieved utilization; gated by
  ``compare_roofline`` (structure hard, utilization regression with
  ``--gate-time``).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

from repro.core import meshes as MESH
from repro.partition import PartitionProblem, factor_k, partition

from .common import md_table, save_bench_json, save_json, timer

SPMD_DEVICE_COUNTS = (1, 2, 4, 8)
HOTLOOP_N = 1 << 20
HOTLOOP_K = 64

# weak-scaling memory probe (the paper's §6 scale claim, DESIGN.md §13):
# full-size nightly runs n = 2^24 (measured ratio ~0.78); the quick CI
# gate runs 2^22 — the smallest size where XLA's ~95 MiB fixed
# compile/runtime arena amortizes below the ceiling (2^21 measures ~1.47
# on fixed overhead alone). The probe runs in a FRESH subprocess because
# ru_maxrss/VmHWM are process-lifetime high-water marks — any earlier
# benchmark section would pollute the measurement.
WEAK_MEM_N = 1 << 24
WEAK_MEM_N_QUICK = 1 << 22
WEAK_MEM_K = 16
WEAK_MEM_DEVICES = 8
WEAK_MEM_CHUNK = 1 << 16
# hard memory ceiling: incremental peak RSS (over the post-import
# interpreter baseline) must stay <= 1.25x the analytic sharded working
# set — the old float64 full-host deal alone would add ~3x the source
# points on top (f64 dealt copy + f64 weights), blowing this envelope
WEAK_MEM_RSS_CEILING = 1.25


def _rss_now_bytes() -> int:
    """Current RSS (Linux /proc; 0 where unavailable)."""
    try:
        with open("/proc/self/statm") as fh:
            return int(fh.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError, IndexError):
        return 0


def _rss_peak_bytes() -> int:
    """Lifetime peak RSS: VmHWM (Linux) with an ru_maxrss fallback."""
    try:
        with open("/proc/self/status") as fh:
            for line in fh:
                if line.startswith("VmHWM:"):
                    return int(line.split()[1]) * 1024
    except (OSError, ValueError, IndexError):
        pass
    import resource
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def weak_mem_working_set_bytes(n: int, d: int, devices: int,
                               chunk: int) -> int:
    """Analytic resident working set of ``from_problem``+solve, in bytes.

    Every term is an intended O(n) allocation of the streaming-deal
    sharded path (float32 problem, float32 solve dtype); the memory gate
    asserts the *measured* incremental peak stays within
    ``WEAK_MEM_RSS_CEILING`` of this sum — a reintroduced float64 host
    copy of the dealt points (+weights) adds ~12n bytes on top of the
    8n-byte f32 source at d=2 and breaks the envelope.
    """
    cap = -(-n // devices)
    pc = devices * cap                       # padded point count (~n)
    return (
        n * d * 4                # problem.points (f32 source)
        + 8 * n                  # seed permutation (int64, deal staging)
        + 8 * pc + pc            # gather (int64) + valid (bool)
        + devices * min(chunk, cap) * (d + 1) * 4   # per-slice staging
        + pc * (d + 1) * 4       # committed device points + weights (f32)
        + 4 * n                  # host unit weights staged during the deal
        + 4 * pc                 # prev-labels placeholder (int32)
        + 9 * 4 * pc             # solver live set (~9 n-sized f32/i32)
        + 8 * n + 4 * pc         # scattered labels (i64) + host label copy
    )


def memprobe(n: int, k: int, devices: int, chunk: int) -> dict:
    """Measure peak RSS of one out-of-core sharded partition call.

    Runs ``from_problem`` (streaming deal, placement-commit) + the solve
    with the in-graph device bootstrap — the path with no O(n) float64
    host allocation — and reports the incremental peak RSS over the
    post-import interpreter baseline against the analytic working set.
    Invoked in a fresh subprocess by ``weak_scaling_memory`` (the RSS
    high-water mark is only meaningful in a process that has run nothing
    else); prints the record as JSON on stdout with ``--memprobe``.
    """
    baseline = _rss_now_bytes()
    rng = np.random.default_rng(0)
    pts = rng.random((n, 2), dtype=np.float32)
    prob = PartitionProblem(points=pts, k=k, epsilon=0.05, seed=5)
    t0 = timer()
    res = partition(prob, method="geographer", devices=devices,
                    chunk=chunk, bootstrap="device", warmup=False,
                    max_iter=5)
    dt = timer() - t0
    peak = _rss_peak_bytes()
    ws = weak_mem_working_set_bytes(n, 2, devices, chunk)
    delta = max(peak - baseline, 0)
    return {
        "n": n, "k": k, "d": 2, "devices": devices, "chunk": chunk,
        "baseline_rss_bytes": baseline, "peak_rss_bytes": peak,
        "incremental_peak_bytes": delta, "working_set_bytes": ws,
        "rss_ratio": delta / ws, "rss_ceiling": WEAK_MEM_RSS_CEILING,
        "under_ceiling": bool(delta <= WEAK_MEM_RSS_CEILING * ws),
        "naive_f64_extra_bytes": 12 * n,     # the fixed up-cast would add
        "time_s": dt, "imbalance": float(res.imbalance()),
        "points_dtype": "float32",
    }


def _parity_checks() -> dict:
    """In-process bit-parity booleans riding on the weak_scaling record:
    chunked deal == one-shot deal, and 2-D mesh (2, 4) == flat 8 on both
    the flat and the hierarchical label path (modest n — the property is
    layout/trace identity, not scale)."""
    import jax
    rng = np.random.default_rng(3)
    n = 4099
    prob = PartitionProblem(points=rng.random((n, 2)).astype(np.float32),
                            weights=rng.uniform(0.5, 2.0, n)
                            .astype(np.float32),
                            k=8, epsilon=0.05, seed=11)
    one = prob.to_sharded(4)
    deal_ok = all(
        np.array_equal(one.points, sp.points)
        and np.array_equal(one.weights, sp.weights)
        and np.array_equal(one.gather, sp.gather)
        and np.array_equal(one.valid, sp.valid)
        for sp in (prob.to_sharded(4, chunk=c) for c in (1, 17, 1 << 30)))
    roundtrip = one.scatter_labels(
        np.asarray(one.deal(np.arange(n) % prob.k, chunk=13)), chunk=13)
    deal_ok = deal_ok and bool(np.array_equal(roundtrip, np.arange(n) % 8))
    if len(jax.devices()) < 8:
        return {"chunked_deal_bitexact": deal_ok,
                "mesh2d_labels_equal": None}
    flat = partition(prob, devices=8)
    flat2d = partition(prob, devices=(2, 4))
    hier = partition(prob, hierarchy=(4, 2), devices=8)
    hier2d = partition(prob, hierarchy=(4, 2), devices=(2, 4))
    return {
        "chunked_deal_bitexact": deal_ok,
        "mesh2d_labels_equal": bool(
            np.array_equal(flat.labels, flat2d.labels)
            and np.array_equal(hier.labels, hier2d.labels)),
    }


def weak_scaling_memory(quick: bool = False) -> dict:
    """The §6 scale-claim record: subprocess peak-RSS probe of the
    out-of-core sharded deal + solve, plus the bit-parity booleans.

    The probe result is gated hard by ``tools/bench_compare.py``
    (``compare_weak_scaling``): incremental peak RSS <= 1.25x the
    analytic sharded working set, chunked deal bit-identical to one-shot,
    and 2-D mesh labels bit-identical to the flat composition.
    """
    n = WEAK_MEM_N_QUICK if quick else WEAK_MEM_N
    # the probe measures host RSS on virtual CPU devices; pinning it to
    # the CPU keeps it off an accelerator this (parent) process holds
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count="
                         f"{WEAK_MEM_DEVICES}",
               JAX_PLATFORMS="cpu")
    env.setdefault("PYTHONPATH", "src")
    cmd = [sys.executable, "-m", "benchmarks.scaling", "--memprobe",
           str(n), str(WEAK_MEM_K), str(WEAK_MEM_DEVICES),
           str(WEAK_MEM_CHUNK)]
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(cmd, capture_output=True, text=True, env=env,
                          cwd=repo_root, check=False)
    if proc.returncode != 0:
        raise RuntimeError(
            f"memprobe subprocess failed ({proc.returncode}):\n"
            f"{proc.stdout}\n{proc.stderr}")
    rec = json.loads(proc.stdout.splitlines()[-1])
    rec.update(_parity_checks())
    print(f"  weak-mem n=2^{int(np.log2(rec['n']))} "
          f"devices={rec['devices']} chunk={rec['chunk']}: "
          f"peak={rec['incremental_peak_bytes'] / 2**20:.0f}MiB over "
          f"baseline vs working-set={rec['working_set_bytes'] / 2**20:.0f}"
          f"MiB -> ratio={rec['rss_ratio']:.2f} "
          f"(ceiling {rec['rss_ceiling']}), t={rec['time_s']:.2f}s, "
          f"deal_bitexact={rec['chunked_deal_bitexact']} "
          f"mesh2d_equal={rec['mesh2d_labels_equal']}")
    return rec


def _available_device_counts():
    import jax
    n = len(jax.devices())
    return tuple(d for d in SPMD_DEVICE_COUNTS if d <= n)


def _spmd_row(prob, method, d):
    """Timed sharded run: first call (compile + run), second call
    (steady state), then the paper metric set."""
    kw = (dict(method="geographer", devices=d) if method == "flat"
          else dict(hierarchy=factor_k(prob.k), devices=d))
    t0 = timer()
    partition(prob, **kw)
    t_first = timer() - t0
    t0 = timer()
    res = partition(prob, **kw)
    t_steady = timer() - t0
    ev = res.evaluate()
    # movement iterations: the flat path reports them at level 0, the
    # hierarchical path per refinement block at level 1 — take the max
    per_level = [lvl.get("iters") for lvl in res.stats["levels"]
                 if lvl.get("iters") is not None]
    iters = int(max(np.max(v) for v in per_level)) if per_level else None
    row = {"method": method, "devices": d, "n": prob.n, "k": prob.k,
           "time_s": t_steady, "compile_s": max(t_first - t_steady, 0.0),
           "cut": ev["cut"], "totalCommVol": ev["totalCommVol"],
           "imbalance": ev["imbalance"], "iters": iters,
           "balanced": bool(ev["imbalance"] <= prob.epsilon + 1e-6)}
    return row


def spmd_scaling(n: int = 60_000, k: int = 64, quick: bool = False):
    """Flat and hierarchical sharded runs over 1/2/4/8 virtual devices."""
    if quick:
        n, k = 8_000, 16
    mesh = MESH.REGISTRY["delaunay2d"](n, seed=3)
    prob = PartitionProblem.from_mesh(mesh, k, epsilon=0.03)
    rows = []
    for method in ("flat", "hierarchical"):
        for d in _available_device_counts():
            row = _spmd_row(prob, method, d)
            rows.append(row)
            print(f"  spmd {method:12s} devices={d} t={row['time_s']:.2f}s "
                  f"(compile {row['compile_s']:.1f}s) cut={row['cut']} "
                  f"imb={row['imbalance']:.3f}")
    return rows


def weak_scaling(per_block: int = 1500, ks=(4, 8, 16, 32, 64),
                 quick: bool = False):
    if quick:
        per_block, ks = 800, (4, 8, 16)
    rows = []
    for k in ks:
        n = per_block * k
        mesh = MESH.REGISTRY["delaunay2d"](n, seed=1)
        prob = PartitionProblem.from_mesh(mesh, k, epsilon=0.03)
        t0 = timer()
        res = partition(prob, method="geographer")
        dt = timer() - t0
        rows.append({"k": k, "n": n, "time_s": dt,
                     "us_per_point": dt / n * 1e6,
                     "blocks_used": int(len(np.unique(res.labels)))})
        print(f"  weak k={k:4d} n={n:8d} t={dt:.2f}s")
    return rows


def strong_scaling(n: int = 60_000, ks=(4, 8, 16, 32, 64, 128),
                   quick: bool = False):
    """Flat vs hierarchical wall time as k grows at fixed n."""
    if quick:
        n, ks = 12_000, (4, 16, 64)
    mesh = MESH.REGISTRY["delaunay2d"](n, seed=2)
    rows = []
    for k in ks:
        prob = PartitionProblem.from_mesh(mesh, k, epsilon=0.03)
        t0 = timer()
        flat = partition(prob, method="geographer")
        t_flat = timer() - t0
        k1, k2 = factor_k(k)
        if k2 > 1:
            t0 = timer()
            hier = partition(prob, hierarchy=(k1, k2))
            t_hier = timer() - t0
            imb_h = hier.imbalance()
        else:
            t_hier, imb_h = float("nan"), float("nan")
        rows.append({"k": k, "n": n, "time_flat_s": t_flat,
                     "time_hier_s": t_hier, "hier": f"{k1}x{k2}",
                     "imb_flat": flat.imbalance(), "imb_hier": imb_h})
        print(f"  strong k={k:4d} flat={t_flat:.2f}s "
              f"hier[{k1}x{k2}]={t_hier:.2f}s")
    return rows


def hotloop(n: int = HOTLOOP_N, k: int = HOTLOOP_K, d: int = 2,
            reps: int = 5, quick: bool = False):
    """The paper's hot loop (one movement-iteration sweep) four ways.

    * ``fused``     — backend ``return_moments=True``: assignment +
      moments in ONE pass over the points (the engine default: adaptive
      ``default_chunk`` keeps the [chunk, k] scratch cache-resident and
      the argmin-free epilogue keeps every reduction vectorized).
    * ``fused_pr4`` — the PR 4 fused hot loop exactly as it shipped
      (fixed ``chunk=65536``, argmin epilogue), inlined here so later
      optimizations to ``assign_argmin_jnp`` can't leak into the
      baseline the >= 1.1x gate measures against; labels stay
      bit-identical to ``fused`` (chunk-invariance + the exact
      first-occurrence index trick).
    * ``fallback``  — the shipped unfused path for backends without moment
      support: assignment, then a ``segment_moments`` sweep sharing the
      fused path's reduction structure (results bit-for-bit identical).
    * ``legacy``    — the pre-fusion hot loop exactly as the seed shipped
      it: scatter-masked second-best in the assignment plus three global
      ``segment_sum`` reductions (reads every point twice).

    Also emits the ``roofline`` record (launch/kernel_roofline.py):
    analytic FLOPs/bytes/AI of the sweep plus the measured ``fused``
    median -> achieved utilization, gated by ``compare_roofline``.

    ``quick`` does not shrink the problem — the gate's n=2^20 case runs
    in CI too, with the full rep count (the median feeds a hard gate).
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels.ops import (assign_argmin_jnp, default_chunk,
                                   resolve_assign_backend, segment_moments)
    from repro.launch.kernel_roofline import kernel_roofline_record

    del quick
    rng = np.random.default_rng(0)
    pts = jnp.asarray(rng.uniform(0, 1, (n, d)).astype(np.float32))
    w = jnp.asarray(rng.uniform(0.5, 2.0, n).astype(np.float32))
    ctr = jnp.asarray(rng.uniform(0, 1, (k, d)).astype(np.float32))
    infl = jnp.ones(k, jnp.float32)

    @jax.jit
    def fused(p, w_, c, i_):
        return assign_argmin_jnp(p, c, i_, weights=w_, return_moments=True)

    @jax.jit
    def fused_pr4(p, w_, c, i_):
        # the PR 4 fused hot loop exactly as it shipped: fixed
        # chunk=65536 and the argmin-based epilogue (self-contained so
        # later optimizations to assign_argmin_jnp can't leak in)
        inv2 = 1.0 / (i_ * i_)
        cn = jnp.sum(c * c, axis=1)

        def one_chunk(args):
            pc, wc = args
            pn = jnp.sum(pc * pc, axis=1, keepdims=True)
            eff = jnp.maximum(pn + cn[None, :] - 2.0 * pc @ c.T,
                              0.0) * inv2[None, :]
            idx = jnp.argmin(eff, axis=1).astype(jnp.int32)
            onehot = idx[:, None] == jnp.arange(k)[None, :]
            best = jnp.min(eff, axis=1)
            second = jnp.min(jnp.where(onehot, jnp.inf, eff), axis=1)
            ww = jnp.where(onehot, wc[:, None], 0.0)
            stacked = jnp.concatenate(
                [pc, jnp.ones((pc.shape[0], 1), pc.dtype),
                 best[:, None]], axis=1)
            return idx, best, second, ww.T @ stacked

        chunk = 65536
        pp = p.reshape(-1, chunk, d)
        wc = w_.reshape(-1, chunk)
        idx, b, s, m = jax.lax.map(one_chunk, (pp, wc))
        m = m.sum(axis=0)
        return (idx.reshape(-1), b.reshape(-1), s.reshape(-1),
                m[:, :d], m[:, d], m[:, d + 1])

    @jax.jit
    def fallback(p, w_, c, i_):
        idx, b, s = assign_argmin_jnp(p, c, i_)
        return (idx, b, s) + segment_moments(p, w_, idx, b, k)

    @jax.jit
    def legacy(p, w_, c, i_):
        inv2 = 1.0 / (i_ * i_)
        cn = jnp.sum(c * c, axis=1)

        def one_chunk(pc):
            pn = jnp.sum(pc * pc, axis=1, keepdims=True)
            eff = jnp.maximum(pn + cn[None, :] - 2.0 * pc @ c.T,
                              0.0) * inv2[None, :]
            idx = jnp.argmin(eff, axis=1).astype(jnp.int32)
            best = jnp.take_along_axis(eff, idx[:, None], axis=1)[:, 0]
            masked = eff.at[jnp.arange(pc.shape[0]), idx].set(jnp.inf)
            return idx, best, jnp.min(masked, axis=1)

        chunk = 65536
        pad = (-p.shape[0]) % chunk
        pp = jnp.pad(p, ((0, pad), (0, 0)))
        idx, b, s = jax.lax.map(one_chunk, pp.reshape(-1, chunk, d))
        idx = idx.reshape(-1)[:p.shape[0]]
        b = b.reshape(-1)[:p.shape[0]]
        s = s.reshape(-1)[:p.shape[0]]
        csum = jax.ops.segment_sum(w_[:, None] * p, idx, num_segments=k)
        cw = jax.ops.segment_sum(w_, idx, num_segments=k)
        rad2 = jax.ops.segment_sum(w_ * b, idx, num_segments=k)
        return idx, b, s, csum, cw, rad2

    fns = {"fused": fused, "fused_pr4": fused_pr4, "fallback": fallback,
           "legacy": legacy}
    outs, times = {}, {v: [] for v in fns}
    for name, f in fns.items():                       # compile
        outs[name] = jax.block_until_ready(f(pts, w, ctr, infl))
    for _ in range(reps):                             # interleave reps
        for name, f in fns.items():
            t0 = time.perf_counter()
            jax.block_until_ready(f(pts, w, ctr, infl))
            times[name].append(time.perf_counter() - t0)
    med = {name: float(np.median(ts)) for name, ts in times.items()}
    bitexact = all(bool(jnp.all(a == b))
                   for a, b in zip(outs["fused"], outs["fallback"]))
    labels_equal = all(bool(jnp.all(outs["fused"][0] == outs[v][0]))
                       for v in ("fused_pr4", "fallback", "legacy"))
    backend = resolve_assign_backend("auto")
    roofline = kernel_roofline_record(
        n, d, k, measured_s=med["fused"], backend=backend)
    roofline["chunk"] = default_chunk(k)
    out = {
        "n": n, "k": k, "d": d, "reps": reps,
        "rows": [{"variant": v, "time_s": med[v]} for v in fns],
        "speedup_vs_legacy": med["legacy"] / med["fused"],
        "speedup_vs_fallback": med["fallback"] / med["fused"],
        "speedup_vs_pr4_fused": med["fused_pr4"] / med["fused"],
        "bitexact": bitexact, "labels_equal": labels_equal,
        "roofline": roofline,
    }
    print(f"  hotloop n={n} k={k}: "
          f"fused={med['fused']:.3f}s pr4={med['fused_pr4']:.3f}s "
          f"fallback={med['fallback']:.3f}s "
          f"legacy={med['legacy']:.3f}s -> {out['speedup_vs_legacy']:.2f}x "
          f"vs legacy, {out['speedup_vs_pr4_fused']:.2f}x vs pr4 fused, "
          f"bitexact={bitexact}")
    print(f"  roofline [{roofline['platform']}/{backend}]: "
          f"AI={roofline['ai']:.2f} flop/byte, "
          f"bound={roofline['bound_s'] * 1e3:.1f}ms "
          f"({roofline['bottleneck']}), measured={med['fused'] * 1e3:.1f}ms "
          f"-> utilization={roofline['utilization']:.3f}")
    return out


def run(quick: bool = False, json_out: bool = False):
    print("\n### SPMD scaling — sharded shard_map partitioner, "
          "1/2/4/8 virtual devices (flat vs hierarchical)\n")
    spmd = spmd_scaling(quick=quick)
    print(md_table(spmd, ["method", "devices", "time_s", "compile_s",
                          "cut", "totalCommVol", "imbalance", "iters"]))
    print("\n### Fig 3a analogue — weak scaling (n/k fixed)\n")
    weak = weak_scaling(quick=quick)
    print(md_table(weak, ["k", "n", "time_s", "us_per_point"]))
    print("\n### Fig 3b analogue — strong scaling (n fixed, k grows; "
          "flat vs hierarchical k1xk2)\n")
    strong = strong_scaling(quick=quick)
    print(md_table(strong, ["k", "hier", "time_flat_s", "time_hier_s",
                            "imb_flat", "imb_hier"]))
    print("\n### Hot loop — fused assign+reduce vs unfused "
          "(one movement-iteration sweep, n=2^20)\n")
    hot = hotloop(quick=quick)
    print(md_table(hot["rows"], ["variant", "time_s"]))
    roofline = hot.pop("roofline")
    print("\n### Weak-scaling memory — out-of-core sharded deal, "
          "subprocess peak-RSS probe\n")
    weak_mem = weak_scaling_memory(quick=quick)
    out = {"spmd": spmd, "weak": weak, "strong": strong, "hotloop": hot,
           "roofline": roofline, "weak_scaling": weak_mem, "quick": quick}
    save_json("scaling", out)
    if json_out:
        save_bench_json("scaling", out)
    return out


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--memprobe":
        n_, k_, p_, c_ = (int(a) for a in sys.argv[2:6])
        print(json.dumps(memprobe(n_, k_, p_, c_)))
    else:
        run()
