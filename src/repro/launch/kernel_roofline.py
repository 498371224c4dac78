"""Analytical + measured roofline for the partition assign kernel.

``launch/roofline.py`` models the transformer stack from compiled dry-run
artifacts; this module models the *partition hot loop* — the fused
assign+reduce sweep (kernels/assign_kernel.py and friends) — analytically
from its shape, so predicted-vs-measured utilization can be tracked as a
gated benchmark record (``BENCH_scaling.json`` → ``roofline``, gate
``compare_roofline`` in tools/bench_compare.py).

Two cost terms per (n, d, k, block_p, block_c) sweep:

* **distance block** — the ``[BC, BP]`` effective-distance tile per
  (point-tile × center-tile) grid step: ``2*BP*BC*d`` cross-term FLOPs
  plus an O(BP*BC) epilogue (norm adds, influence scale, running
  argmin/min/second update, modeled at ``EPILOGUE_FLOPS_PER_CELL``).
  Pruned tiles (``prune_frac``, measured by ``stats["tiles_pruned_frac"]``)
  skip both.
* **moment block** — the fused ``[d+2, K]`` accumulator: one
  ``2*BP*(d+2)*K`` one-hot matmul per point tile.

HBM traffic model: the point array streams exactly once (``4*n*d``
bytes, lane-dense ``[d, n]``), the center block
(``4*(d+1)*K``) is re-fetched per point tile, outputs are
``12*n`` bytes (idx/best/second) plus the ``4*(d+2)*K`` moment block.
``precision="bf16"`` halves the *MXU time* of the distance matmul
(operands are cast in-VMEM; HBM traffic is unchanged).

The ``jnp`` backend (CPU hosts, the container benchmark) is the same
arithmetic but a different memory model: the dense ``[chunk, k]``
effective-distance scratch is materialized and re-traversed by the
min/mask/second epilogue (``JNP_SCRATCH_PASSES`` round trips), which is
why the adaptive ``default_chunk`` (keep ``chunk*k*4`` cache-resident)
wins on bandwidth-bound hosts; together with the argmin-free epilogue
(kernels/ops.py ``_chunk_assign``) that measured ~1.5x over the PR 4
fused hot loop at n=2^20 k=64.

Arithmetic intensity AI = FLOPs / HBM bytes; predicted time =
max(FLOPs/peak, bytes/bw); utilization = predicted / measured (1.0 =
running at the roofline). Peaks are per-chip table entries
(``PLATFORMS``, keyed by device kind), deliberately coarse — utilization
is tracked for *regressions*, not absolute truth.
"""
from __future__ import annotations

import math

EPILOGUE_FLOPS_PER_CELL = 6.0   # norms add, scale, compare/select chain
JNP_SCRATCH_PASSES = 4.0        # eff write + argmin + mask + second-min


# Per-chip peaks, keyed by ``jax.devices()[0].device_kind``. FLOP/s by
# distance-matmul precision; bytes/s of HBM (or DRAM). TPU v5e ("TPU v5
# lite"): 197 TFLOP/s bf16 and 16 GB HBM at 819 GB/s (Google Cloud
# documentation, "TPU v5e"); f32 is modeled at half the bf16 MXU rate.
# TPU v4: 275 TFLOP/s bf16, 1.2 TB/s (Google Cloud documentation, "TPU
# v4"). "cpu" is one container-class x86 core (AVX2 FMA ~1e11 f32
# FLOP/s, ~2e10 B/s DRAM; bf16 has no native support), a model figure and
# not a measurement.
PLATFORMS = {
    "TPU v5 lite": {"peak_flops": {"f32": 98.5e12, "bf16": 197e12},
                    "hbm_bw": 819e9},
    "TPU v4": {"peak_flops": {"f32": 137.5e12, "bf16": 275e12},
               "hbm_bw": 1.2e12},
    "cpu": {"peak_flops": {"f32": 1.0e11, "bf16": 1.0e11},
            "hbm_bw": 2.0e10},
}


def detect_platform() -> str:
    """The ``PLATFORMS`` key of the first jax device (its ``device_kind``).

    Raises:
        KeyError: the device kind has no entry in the peak table — an
            unknown chip is an error, never priced as another one.
    """
    import jax
    kind = jax.devices()[0].device_kind
    if kind not in PLATFORMS:
        raise KeyError(f"no peak-table entry for device_kind {kind!r}; "
                       f"known kinds: {sorted(PLATFORMS)}")
    return kind


def _pad(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def assign_intensity(n: int, d: int, k: int, *, block_p: int = 1024,
                     block_c: int = 128, fused: bool = True,
                     prune_frac: float = 0.0,
                     backend: str = "pallas") -> dict:
    """FLOPs, HBM bytes and arithmetic intensity of one assign(+reduce)
    sweep, split into the distance and moment blocks. ``backend``
    selects the memory model ("pallas"/"triton" tiled kernels vs the
    dense-scratch "jnp" path); FLOPs are backend-invariant."""
    n_pad = _pad(n, block_p)
    k_pad = _pad(k, block_c)
    n_pt = n_pad // block_p
    n_ct = k_pad // block_c
    live_tiles = n_pt * n_ct * max(1.0 - prune_frac, 0.0)

    dist_flops = live_tiles * block_p * block_c * (
        2.0 * d + EPILOGUE_FLOPS_PER_CELL)
    mom_flops = n_pt * 2.0 * block_p * (d + 2) * k_pad if fused else 0.0

    bytes_points = 4.0 * n_pad * d          # streamed exactly once
    bytes_outputs = 12.0 * n_pad            # idx + best + second
    if backend == "jnp":
        # chunked dense path: the [chunk, k] scratch is written and then
        # re-traversed by the epilogue; when it exceeds cache this is
        # real DRAM traffic (the term the adaptive default_chunk shrinks)
        bytes_centers = 4.0 * (d + 1) * k   # fetched once, cache-resident
        bytes_scratch = JNP_SCRATCH_PASSES * 4.0 * n_pad * k
    else:
        # tiled kernels: centers + inv2 re-fetched per point tile
        bytes_centers = n_pt * 4.0 * (d + 1) * k_pad
        bytes_scratch = 0.0
    bytes_moments = 4.0 * (d + 2) * k_pad if fused else 0.0

    dist_bytes = bytes_points + bytes_centers + bytes_outputs + bytes_scratch
    mom_bytes = bytes_moments

    def block(flops, hbm_bytes):
        return {"flops": flops, "hbm_bytes": hbm_bytes,
                "ai": flops / max(hbm_bytes, 1.0)}

    out = {"distance": block(dist_flops, dist_bytes),
           "moments": block(mom_flops, mom_bytes),
           "total": block(dist_flops + mom_flops, dist_bytes + mom_bytes)}
    return out


def predict(n: int, d: int, k: int, *, platform: str | None = None,
            precision: str = "f32", block_p: int = 1024,
            block_c: int = 128, fused: bool = True,
            prune_frac: float = 0.0, backend: str = "pallas") -> dict:
    """Roofline prediction for one sweep: per-block AI, compute/memory
    times against the platform peaks, and the binding term."""
    if platform is None:
        platform = detect_platform()
    peaks = PLATFORMS[platform]
    peak_flops = peaks["peak_flops"][precision]
    bw = peaks["hbm_bw"]
    intensity = assign_intensity(n, d, k, block_p=block_p, block_c=block_c,
                                 fused=fused, prune_frac=prune_frac,
                                 backend=backend)
    total = intensity["total"]
    # bf16 only accelerates the distance matmul; the moment accumulation
    # and epilogue stay f32 — model the compute term per block
    dist_peak = peak_flops
    other_peak = peaks["peak_flops"]["f32"]
    compute_s = (intensity["distance"]["flops"] / dist_peak
                 + intensity["moments"]["flops"] / other_peak)
    memory_s = total["hbm_bytes"] / bw
    bound_s = max(compute_s, memory_s)
    return {
        "platform": platform, "precision": precision, "backend": backend,
        "n": n, "d": d, "k": k, "block_p": block_p, "block_c": block_c,
        "fused": fused, "prune_frac": prune_frac,
        "distance": intensity["distance"], "moments": intensity["moments"],
        "total_flops": total["flops"], "total_hbm_bytes": total["hbm_bytes"],
        "ai": total["ai"],
        "compute_s": compute_s, "memory_s": memory_s, "bound_s": bound_s,
        "bottleneck": "compute" if compute_s >= memory_s else "memory",
    }


def utilization(predicted_bound_s: float, measured_s: float) -> float:
    """Fraction of the roofline achieved (1.0 = at the bound)."""
    if not (measured_s > 0.0) or not math.isfinite(measured_s):
        return 0.0
    return predicted_bound_s / measured_s


def kernel_roofline_record(n: int, d: int, k: int, *,
                           measured_s: float | None = None,
                           platform: str | None = None,
                           precision: str = "f32", block_p: int = 1024,
                           block_c: int = 128, fused: bool = True,
                           prune_frac: float = 0.0,
                           backend: str = "pallas") -> dict:
    """The ``roofline`` record for ``BENCH_scaling.json`` (schema in
    docs/benchmarks.md): the prediction plus measured wall time and
    achieved utilization, ready for ``compare_roofline`` gating."""
    rec = predict(n, d, k, platform=platform, precision=precision,
                  block_p=block_p, block_c=block_c, fused=fused,
                  prune_frac=prune_frac, backend=backend)
    rec["measured_s"] = measured_s
    rec["utilization"] = (None if measured_s is None
                          else utilization(rec["bound_s"], measured_s))
    return rec
