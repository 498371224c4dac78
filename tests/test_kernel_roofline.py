"""Unit tests for the analytic assign-kernel roofline model
(repro.launch.kernel_roofline): platform table sanity, intensity math,
bottleneck selection, and the BENCH_scaling.json record schema."""
import math

import pytest

from repro.launch.kernel_roofline import (PLATFORMS, assign_intensity,
                                          detect_platform,
                                          kernel_roofline_record, predict,
                                          utilization)

# must stay in sync with tools/bench_compare.py::ROOFLINE_FIELDS
ROOFLINE_FIELDS = ("platform", "backend", "n", "d", "k", "ai", "compute_s",
                   "memory_s", "bound_s", "bottleneck", "measured_s",
                   "utilization")


def test_platform_table_sane():
    for name, p in PLATFORMS.items():
        assert p["hbm_bw"] > 0, name
        for prec in ("f32", "bf16"):
            assert p["peak_flops"][prec] > 0, (name, prec)
        # bf16 never slower than f32 on any modeled platform
        assert p["peak_flops"]["bf16"] >= p["peak_flops"]["f32"], name


def test_detect_platform_is_known():
    assert detect_platform() in PLATFORMS


def test_detect_platform_keys_by_device_kind(monkeypatch):
    """The peak table is keyed by ``device_kind``: v5e resolves to its own
    entry, and a kind the table does not hold raises instead of being
    priced as another chip."""
    import jax

    class _Dev:
        def __init__(self, kind):
            self.device_kind = kind

    monkeypatch.setattr(jax, "devices", lambda: [_Dev("TPU v5 lite")])
    assert detect_platform() == "TPU v5 lite"
    monkeypatch.setattr(jax, "devices", lambda: [_Dev("TPU v6e")])
    with pytest.raises(KeyError, match="TPU v6e"):
        detect_platform()


def test_intensity_positive_and_scales_with_d():
    lo = assign_intensity(1 << 16, 2, 64)
    hi = assign_intensity(1 << 16, 128, 64)
    for block in ("distance", "moments", "total"):
        assert lo[block]["flops"] > 0
        assert lo[block]["hbm_bytes"] > 0
        assert lo[block]["ai"] > 0
    # the 2*BP*BC*d matmul dominates: more dims, more FLOPs — and AI
    # rises because bytes grow ~d while epilogue FLOPs stay fixed
    assert hi["distance"]["flops"] > lo["distance"]["flops"]
    assert hi["total"]["ai"] > lo["total"]["ai"]


def test_intensity_prune_frac_cuts_distance_flops():
    base = assign_intensity(1 << 18, 2, 256)
    pruned = assign_intensity(1 << 18, 2, 256, prune_frac=0.5)
    assert pruned["distance"]["flops"] == pytest.approx(
        0.5 * base["distance"]["flops"])
    # moments are per point tile, untouched by center-tile pruning
    assert pruned["moments"]["flops"] == base["moments"]["flops"]


def test_intensity_unfused_drops_moment_block():
    unfused = assign_intensity(1 << 16, 2, 64, fused=False)
    assert unfused["moments"]["flops"] == 0.0
    assert unfused["moments"]["hbm_bytes"] == 0.0


def test_jnp_memory_model_has_scratch_traffic():
    """The dense [chunk, k] scratch is what makes the jnp path
    bandwidth-bound — its byte count must dominate the tiled model's."""
    jnp_b = assign_intensity(1 << 18, 2, 256, backend="jnp")
    pal_b = assign_intensity(1 << 18, 2, 256, backend="pallas")
    assert jnp_b["total"]["hbm_bytes"] > pal_b["total"]["hbm_bytes"]
    assert jnp_b["total"]["ai"] < pal_b["total"]["ai"]


def test_predict_bottleneck_selection():
    # low-d on a bandwidth-starved host: memory bound
    cpu = predict(1 << 18, 2, 64, platform="cpu", backend="jnp")
    assert cpu["bottleneck"] == "memory"
    assert cpu["bound_s"] == pytest.approx(
        max(cpu["compute_s"], cpu["memory_s"]))
    # predictions are finite and positive everywhere
    for plat in PLATFORMS:
        p = predict(1 << 20, 2, 64, platform=plat)
        assert math.isfinite(p["bound_s"]) and p["bound_s"] > 0


def test_predict_bf16_speeds_distance_only():
    f32 = predict(1 << 20, 128, 256, platform="TPU v5 lite", precision="f32")
    b16 = predict(1 << 20, 128, 256, platform="TPU v5 lite", precision="bf16")
    assert b16["compute_s"] < f32["compute_s"]
    # HBM traffic is modeled unchanged (operands cast in-VMEM)
    assert b16["memory_s"] == f32["memory_s"]


def test_utilization_edge_cases():
    assert utilization(1.0, 2.0) == pytest.approx(0.5)
    assert utilization(1.0, 0.0) == 0.0
    assert utilization(1.0, float("nan")) == 0.0
    assert utilization(1.0, float("inf")) == 0.0


def test_record_schema_complete():
    rec = kernel_roofline_record(1 << 20, 2, 64, measured_s=1.0,
                                 platform="cpu", backend="jnp")
    for field in ROOFLINE_FIELDS:
        assert field in rec and rec[field] is not None, field
    assert 0.0 < rec["utilization"]
    # without a measurement the record still carries the prediction
    rec2 = kernel_roofline_record(1 << 20, 2, 64, platform="cpu")
    assert rec2["measured_s"] is None and rec2["utilization"] is None
    assert rec2["bound_s"] > 0
