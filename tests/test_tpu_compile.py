"""Compile the assign kernels and the vmapped solve for a TPU v5e chip.

No chip is needed: the TPU compiler compiles for a *described* v5e:2x2
topology, and refuses what the chip would refuse (block shapes that break
the (8, 128) tiling rule, unaligned DMA slices, more fast memory than a
kernel may use) — none of which interpret mode can see. Each test asserts
that the compiled program holds the Pallas kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
so describing it at import would make test collection differ between
pytest-xdist workers. Keep these tests in this one file, which one worker
runs.
"""
import os
import re

import pytest

import jax
import jax.numpy as jnp

N = 1 << 20                       # the kernel gate size
BLOCK_P, BLOCK_C = 1024, 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:        # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Sharding on one described chip, with the persistent compilation
    cache off (a compile for a described chip cannot be read back)."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_args(sharding, d, k):
    """Shapes of one kernel call: lane-dense [d, N] points, centers padded
    to a block_c multiple, inverse influence, per-tile bounds, weights."""
    kpad = -(-k // BLOCK_C) * BLOCK_C
    return (_spec(sharding, (d, N)), _spec(sharding, (kpad, d)),
            _spec(sharding, (kpad,)),
            _spec(sharding, (N // BLOCK_P, kpad // BLOCK_C)),
            _spec(sharding, (N,)))


@pytest.mark.parametrize("fused", [False, True], ids=["argmin", "reduce"])
@pytest.mark.parametrize("d,k", [(2, 64), (3, 1024)])
def test_assign_kernel_compiles(one_chip, fused, d, k):
    from repro.kernels.assign_kernel import (assign_argmin_pallas,
                                             assign_reduce_pallas)
    pts, ctr, inv2, bounds, w = _kernel_args(one_chip, d, k)
    kw = dict(k_real=k, block_p=BLOCK_P, block_c=BLOCK_C, interpret=False)
    if fused:
        lowered = jax.jit(lambda *a: assign_reduce_pallas(*a, **kw)).lower(
            pts, ctr, inv2, bounds, w)
    else:
        lowered = jax.jit(lambda *a: assign_argmin_pallas(*a, **kw)).lower(
            pts, ctr, inv2, bounds)
    assert "tpu_custom_call" in lowered.compile().as_text()


def test_assign_kernel_bf16_compiles(one_chip):
    from repro.kernels.assign_kernel import assign_reduce_pallas
    pts, ctr, inv2, bounds, w = _kernel_args(one_chip, 2, 64)
    fn = jax.jit(lambda *a: assign_reduce_pallas(
        *a, k_real=64, block_p=BLOCK_P, block_c=BLOCK_C, interpret=False,
        precision="bf16"))
    assert "tpu_custom_call" in fn.lower(
        pts, ctr, inv2, bounds, w).compile().as_text()


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Make the solver's kernel calls compile rather than interpret, as
    they do on a TPU host: ``jax.default_backend()`` is the CPU here.
    Traces made under the patch are dropped afterwards so no later test in
    this process reuses them on the CPU."""
    import repro.kernels.assign_kernel as assign_kernel
    jax.clear_caches()
    monkeypatch.setattr(assign_kernel, "default_interpret", lambda: False)
    yield
    jax.clear_caches()


def test_batched_solve_compiles(one_chip, compiled_kernels):
    """The hierarchical refinement vmaps the whole solve, kernels
    included: every block spec must stay legal with the batch dim."""
    from repro.core.balanced_kmeans import BKMConfig
    from repro.partition.batched import _batched_jit
    B, cap, k2 = 8, N // 8, 8
    cfg = BKMConfig(k=k2, warmup=False, backend="pallas")
    lowered = _batched_jit.lower(
        _spec(one_chip, (B, cap, 2)), _spec(one_chip, (B, cap)),
        _spec(one_chip, (B, k2, 2)), _spec(one_chip, (B,)), cfg)
    assert "tpu_custom_call" in lowered.compile().as_text()


COLD_N, COLD_K = (1 << 16) + 42, 64


@pytest.fixture(scope="module")
def cold_solve_text(one_chip):
    """HLO of the cold solve compiled at an odd n, so that every sweep pads
    its points to a whole tile, with the kernel compiled as on a TPU host
    (traces made under the patch are dropped afterwards)."""
    import repro.kernels.assign_kernel as assign_kernel
    from repro.core.balanced_kmeans import BKMConfig
    from repro.core.partitioner import _run_jit
    jax.clear_caches()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(assign_kernel, "default_interpret", lambda: False)
        cfg = BKMConfig(k=COLD_K, backend="pallas")
        text = _run_jit.lower(_spec(one_chip, (COLD_N, 2)), cfg, None,
                              _spec(one_chip, (COLD_K, 2))
                              ).compile().as_text()
    jax.clear_caches()
    return text


def test_cold_solve_keeps_its_kernel_name_and_scopes(cold_solve_text):
    """The assign kernel keeps the short HLO name the benchmark's roofline
    reader keys on, and the op metadata carries the solve's named scopes,
    by which a profiler groups the chip's ops."""
    from chipbench.tracefile import short_name
    text = cold_solve_text
    names = {short_name(line.strip().removeprefix("ROOT "))
             for line in text.splitlines()
             if line.strip().removeprefix("ROOT ").startswith("%")}
    assert "assign_reduce_pallas" in names
    assert "pad" in names
    for scope in ("movement", "balance", "final_pass"):
        assert re.search(rf'op_name="[^"]*/{scope}/', text), scope


def test_cold_solve_kernel_takes_lane_dense_points(cold_solve_text):
    """Every kernel call of the compiled cold solve takes its points as
    one lane-dense ``f32[2, n_pad]`` operand (coordinates on sublanes,
    points on the 128 lanes), n padded to the kernel's point tile."""
    from repro.core.balanced_kmeans import BKMConfig
    from repro.kernels.ops import kernel_tiles
    cfg = BKMConfig(k=COLD_K)
    bp, _ = kernel_tiles(COLD_N, COLD_K, cfg.block_p, cfg.block_c)
    n_pad = -(-COLD_N // bp) * bp
    calls = re.findall(r"%assign_reduce_pallas[.\d]* = .*?"
                       r"operand_layout_constraints=\{(.*?\})\}",
                       cold_solve_text)
    assert calls
    for operands in calls:
        # operands: prune bounds, points, centers, inv2, weights
        points = re.findall(r"\w+\[[\d,]*\]", operands)[1]
        assert points == f"f32[2,{n_pad}]", points
