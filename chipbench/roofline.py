"""Peaks of the chip and the least time of one assign sweep.

Peaks are the published figures of one chip, keyed by the
``device_kind`` that JAX reports. A kind that is not in the table is an
error: no chip is priced as another.

TPU v5e ("TPU v5 lite"): 197 TFLOP/s (bf16 MXU) and 16 GB of HBM at
819 GB/s, from Google Cloud's "TPU v5e" documentation page
(cloud.google.com/tpu/docs/v5e). No f32 or vector-unit peak is
published, so the sweep's elementwise epilogue is left out of the bound
and the MXU figure is the compute peak: the share can only read low.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    """The peak entry of ``device_kind``; raises KeyError when unknown."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peak entry for device_kind {device_kind!r}; "
                       f"known kinds: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def sweep_work(n: int, k: int, d: int) -> tuple[float, float]:
    """(FLOPs, HBM bytes) that one assign sweep over ``n`` points and
    ``k`` centers in ``d`` dimensions needs: the ``2 n k d`` of the
    cross-term matmul, and reading the coordinates and the weight of
    every point once and writing its label (``n (4 d + 4 + 4)`` bytes).
    No padding and no pruning are counted, so the count is the same
    whatever implements the sweep."""
    return 2.0 * n * k * d, float(n) * (4 * d + 4 + 4)


def sweep_least_seconds(n: int, k: int, d: int,
                        device_kind: str) -> tuple[float, str]:
    """(least seconds of one sweep on one chip, the bound that binds:
    ``"compute"`` or ``"memory"``)."""
    p = peaks(device_kind)
    flops, nbytes = sweep_work(n, k, d)
    t_c = flops / p["flops_per_s"]
    t_m = nbytes / p["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
