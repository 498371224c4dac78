"""Pallas assignment kernel vs pure-jnp oracle: shape/dtype sweeps +
hypothesis property tests (interpret mode)."""
import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.ops import (assign_argmin, assign_argmin_jnp,
                               assign_backend, segment_moments,
                               tile_prune_fraction)
from repro.kernels.ref import assign_argmin_ref

# moments-capable non-jnp backends, checked against the jnp oracle
KERNEL_BACKENDS = ("pallas", "triton")


def _rand(n, k, d, seed=0, spread=1.0):
    rng = np.random.default_rng(seed)
    pts = jnp.asarray(rng.uniform(0, spread, (n, d)), jnp.float32)
    ctr = jnp.asarray(rng.uniform(0, spread, (k, d)), jnp.float32)
    infl = jnp.asarray(rng.uniform(0.5, 2.0, (k,)), jnp.float32)
    return pts, ctr, infl


@pytest.mark.parametrize("n,k,d,bp,bc", [
    (1024, 64, 2, 256, 32),
    (2048, 128, 3, 512, 128),
    (777, 33, 2, 256, 32),      # padding on both axes
    (512, 16, 16, 128, 16),     # MoE-routing-like dims
    (256, 8, 128, 128, 8),      # high-dim (token-embedding routing)
    (4096, 512, 2, 1024, 128),  # production tile shape
    # non-default tile sizes x d sweep: lock the VMEM-block revisiting
    # logic for shapes the default-config paths never touch
    (1024, 200, 2, 256, 128),
    (1024, 200, 3, 256, 128),
    (512, 200, 128, 256, 128),
    (2048, 300, 2, 1024, 256),
    (2048, 300, 3, 1024, 256),
    (1024, 300, 128, 1024, 256),
    # lane-dense tiles: k not a multiple of 8 (padded center rows), k = 64
    # in one derived 64-row center tile, point tiles above 1024; d = 2, 3
    # take the VPU cross term, d = 16, 128 the MXU one
    (3000, 9, 2, 2048, 128),
    (3000, 9, 16, 2048, 128),
    (2500, 33, 3, 4096, 128),
    (2500, 33, 128, 2048, 128),
    (1500, 1, 2, 2048, 128),
    (1500, 1, 16, 2048, 128),
    (5000, 64, 2, 2048, 128),
    (5000, 64, 3, 8192, 128),
    (3000, 64, 16, 2048, 128),
    (2000, 64, 128, 2048, 128),
])
def test_kernel_matches_ref(n, k, d, bp, bc):
    pts, ctr, infl = _rand(n, k, d)
    i1, b1, s1 = assign_argmin(pts, ctr, infl, block_p=bp, block_c=bc)
    i0, b0, s0 = assign_argmin_ref(pts, ctr, infl)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i0))
    np.testing.assert_allclose(np.asarray(b1), np.asarray(b0),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s0),
                               rtol=1e-4, atol=1e-5)


def test_kernel_uniform_influence_is_plain_kmeans():
    """influence == 1 must reduce to vanilla nearest-center assignment."""
    pts, ctr, _ = _rand(512, 32, 2, seed=3)
    infl = jnp.ones(32, jnp.float32)
    i1, b1, _ = assign_argmin(pts, ctr, infl, block_p=256, block_c=32)
    d = jnp.sum((pts[:, None] - ctr[None]) ** 2, -1)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(jnp.argmin(d, 1)))


def test_kernel_influence_monotonicity():
    """Raising one cluster's influence can only gain it points (weighted
    Voronoi property the balancing loop relies on)."""
    pts, ctr, infl = _rand(2048, 16, 2, seed=4)
    i_before, _, _ = assign_argmin(pts, ctr, infl, block_p=512, block_c=16)
    infl2 = infl.at[3].mul(1.5)
    i_after, _, _ = assign_argmin(pts, ctr, infl2, block_p=512, block_c=16)
    before = set(np.where(np.asarray(i_before) == 3)[0].tolist())
    after = set(np.where(np.asarray(i_after) == 3)[0].tolist())
    assert before.issubset(after)


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10 ** 6),
       st.sampled_from([(130, 17, 2), (257, 9, 3), (96, 5, 4)]))
def test_kernel_property_random(seed, shape):
    n, k, d = shape
    pts, ctr, infl = _rand(n, k, d, seed=seed)
    i1, b1, s1 = assign_argmin(pts, ctr, infl, block_p=64, block_c=8)
    i0, b0, s0 = assign_argmin_ref(pts, ctr, infl)
    # argmin ties can differ; compare effective distances instead
    np.testing.assert_allclose(np.asarray(b1), np.asarray(b0),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s0),
                               rtol=1e-4, atol=1e-5)
    assert float(jnp.mean((i1 == i0).astype(jnp.float32))) > 0.99


def test_second_best_greater_equal_best():
    pts, ctr, infl = _rand(1024, 64, 2, seed=7)
    _, b, s = assign_argmin(pts, ctr, infl, block_p=256, block_c=32)
    assert bool(jnp.all(s >= b - 1e-7))


# ---------------------------------------------------------------------------
# padded (_FAR) center masking
# ---------------------------------------------------------------------------

def test_k1_second_is_exact_inf():
    """k == 1: every point's second-best would be a _FAR padding center.
    The kernel must mask those to exactly +inf (not a huge finite value,
    not NaN) so the Hamerly guard in assign_effective fires."""
    pts, _, _ = _rand(256, 1, 2, seed=11)
    ctr = jnp.asarray([[0.4, 0.6]], jnp.float32)
    infl = jnp.ones(1, jnp.float32)
    i1, b1, s1 = assign_argmin(pts, ctr, infl, block_p=256, block_c=8)
    i0, b0, s0 = assign_argmin_jnp(pts, ctr, infl)
    np.testing.assert_array_equal(np.asarray(i1), 0)
    assert bool(jnp.all(jnp.isinf(s1))) and bool(jnp.all(jnp.isinf(s0)))
    np.testing.assert_allclose(np.asarray(b1), np.asarray(b0),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("n,k,bc", [(256, 3, 8), (512, 9, 8), (300, 1, 128)])
def test_padded_centers_large_coordinates(n, k, bc):
    """Regression: with coordinates large enough that 2*p@c overflows
    against the _FAR padding rows, ``|p|^2 + |c|^2 - 2 p@c`` became
    ``inf - inf = NaN`` and corrupted argmin AND second-best (observed:
    ~51% wrong labels). The k_real mask must keep padded centers out of
    the distance math entirely."""
    rng = np.random.default_rng(5)
    pts = jnp.asarray(rng.uniform(0, 1, (n, 2)) * 1e9, jnp.float32)
    ctr = jnp.asarray(rng.uniform(0, 1, (k, 2)) * 1e9, jnp.float32)
    infl = jnp.ones(k, jnp.float32)
    i1, b1, s1 = assign_argmin(pts, ctr, infl, block_p=256, block_c=bc)
    i0, b0, s0 = assign_argmin_jnp(pts, ctr, infl)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i0))
    assert not bool(jnp.isnan(b1).any()) and not bool(jnp.isnan(s1).any())
    # |p|^2+|c|^2-2p.c cancels catastrophically at 1e9-scale coordinates,
    # so the two matmul orders only agree loosely; the test's subject is
    # the NaN/label corruption, not the conditioning
    np.testing.assert_allclose(np.asarray(b1), np.asarray(b0), rtol=1e-2)


# ---------------------------------------------------------------------------
# fused assign+reduce (return_moments=True)
# ---------------------------------------------------------------------------

def _moments_ref(pts, w, idx, best_sq, k):
    csum = np.zeros((k, pts.shape[1]))
    cw = np.zeros(k)
    rad2 = np.zeros(k)
    np.add.at(csum, idx, np.asarray(w)[:, None] * np.asarray(pts))
    np.add.at(cw, idx, np.asarray(w))
    np.add.at(rad2, idx, np.asarray(w) * np.asarray(best_sq))
    return csum, cw, rad2


@pytest.mark.parametrize("n,chunk", [(500, 65536), (5000, 1024)])
def test_jnp_fused_bitexact_vs_unfused(n, chunk):
    """The jnp backend's fused moments must equal the unfused
    assignment + segment_moments fallback BIT-FOR-BIT (they share the
    per-chunk one-hot reduction), single- and multi-chunk."""
    pts, ctr, infl = _rand(n, 7, 2, seed=13)
    w = jnp.asarray(np.random.default_rng(13).uniform(0.5, 2.0, n),
                    jnp.float32)
    iF, bF, sF, csum, cw, rad2 = assign_argmin_jnp(
        pts, ctr, infl, chunk=chunk, weights=w, return_moments=True)
    i0, b0, s0 = assign_argmin_jnp(pts, ctr, infl, chunk=chunk)
    m0 = segment_moments(pts, w, i0, b0, 7, chunk=chunk)
    for a, b in zip((iF, bF, sF, csum, cw, rad2), (i0, b0, s0) + m0):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the moments are the right quantities (float64 oracle)
    cs, cn_, r2 = _moments_ref(pts, w, np.asarray(i0), b0, 7)
    np.testing.assert_allclose(np.asarray(csum), cs, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(cw), cn_, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(rad2), r2, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend", KERNEL_BACKENDS)
@pytest.mark.parametrize("n,k,d,bp,bc", [
    (2000, 9, 2, 256, 8),       # multi point-tile, padded center tile
    (1024, 64, 2, 256, 32),     # multi center-tile
    (300, 1, 2, 128, 128),      # k == 1
    # non-default tile sizes x d sweep (VMEM revisiting / in-kernel loop)
    (1024, 200, 3, 256, 128),
    (512, 200, 128, 256, 128),
    (2048, 300, 2, 1024, 256),
    # lane-dense tiles (see test_kernel_matches_ref)
    (3000, 9, 3, 2048, 128),
    (2500, 33, 2, 4096, 128),
    (1500, 1, 16, 2048, 128),
    (5000, 64, 2, 2048, 128),
    (3000, 64, 128, 2048, 128),
])
def test_kernel_fused_moments_match_jnp(backend, n, k, d, bp, bc):
    """Fused==unfused parity per kernel backend: the VMEM-accumulated
    (pallas) / split-k (triton) moments agree with the jnp reference
    (f32 accumulation order differs, so tolerance not bitwise); the
    assignment triple must be bit-identical between the backend's fused
    and plain modes."""
    if backend == "triton" and bc == 8:
        bc = 128                  # triton tiles centers at lane multiples
    pts, ctr, infl = _rand(n, k, d, seed=17)
    w = jnp.asarray(np.random.default_rng(17).uniform(0.5, 2.0, n),
                    jnp.float32)
    fn = assign_backend(backend)
    pf = fn(pts, ctr, infl, block_p=bp, block_c=bc,
            weights=w, return_moments=True)
    jf = assign_argmin_jnp(pts, ctr, infl, weights=w, return_moments=True)
    np.testing.assert_array_equal(np.asarray(pf[0]), np.asarray(jf[0]))
    for a, b in zip(pf[3:], jf[3:]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
    # fused and plain agree on the assignment triple
    i1, b1, s1 = fn(pts, ctr, infl, block_p=bp, block_c=bc)
    np.testing.assert_array_equal(np.asarray(pf[0]), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(pf[1]), np.asarray(b1))
    np.testing.assert_array_equal(np.asarray(pf[2]), np.asarray(s1))


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 10 ** 6), st.sampled_from(KERNEL_BACKENDS),
       st.sampled_from([(130, 17, 2), (257, 9, 3)]))
def test_backend_fused_property(seed, backend, shape):
    """Property parity over backends: plain triple == fused triple, and
    fused moments match the jnp oracle."""
    n, k, d = shape
    pts, ctr, infl = _rand(n, k, d, seed=seed)
    w = jnp.asarray(np.random.default_rng(seed).uniform(0.5, 2.0, n),
                    jnp.float32)
    fn = assign_backend(backend)
    plain = fn(pts, ctr, infl, block_p=64, block_c=128)
    fused = fn(pts, ctr, infl, block_p=64, block_c=128,
               weights=w, return_moments=True)
    for a, b in zip(plain, fused[:3]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    jf = assign_argmin_jnp(pts, ctr, infl, weights=w, return_moments=True)
    for a, b in zip(fused[3:], jf[3:]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# kernel-entry padding contract (wrapper-side ValueError, not bare assert)
# ---------------------------------------------------------------------------

def test_nonmultiple_n_at_kernel_entry_raises():
    """Regression: a non-tile-multiple n reaching the kernel entry points
    directly must raise a ValueError naming the offending shape, not trip
    a bare assert (or worse, silently mis-tile)."""
    from repro.kernels.assign_kernel import (assign_argmin_pallas,
                                             assign_reduce_pallas)
    from repro.kernels.triton_assign import triton_assign_pallas
    pts, ctr, infl = _rand(1000, 8, 2, seed=23)   # 1000 % 256 != 0
    inv2 = 1.0 / (infl * infl)
    bounds = jnp.zeros((4, 1), jnp.float32)
    # the TPU kernel takes lane-dense [d, n] points, the triton one [n, d]
    with pytest.raises(ValueError, match=r"n=1000.*block_p=256"):
        assign_argmin_pallas(pts.T, ctr, inv2, bounds, k_real=8,
                             block_p=256, block_c=8)
    with pytest.raises(ValueError, match=r"n=1000.*block_p=256"):
        assign_reduce_pallas(pts.T, ctr, inv2, bounds, jnp.ones(1000),
                             k_real=8, block_p=256, block_c=8)
    with pytest.raises(ValueError, match=r"n=1000.*block_p=256"):
        triton_assign_pallas(pts, ctr, inv2, k_real=8,
                             block_p=256, block_c=8)
    with pytest.raises(ValueError, match=r"k=8.*block_c=128"):
        assign_argmin_pallas(pts[:768].T, ctr, inv2, bounds, k_real=8,
                             block_p=256, block_c=128)


# ---------------------------------------------------------------------------
# precision split (bf16 distance matmul, f32 accumulation)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ("jnp",) + KERNEL_BACKENDS)
def test_bf16_precision_within_tolerance(backend):
    """DESIGN.md §4c tolerance contract: bf16 effective distances within
    rtol ~2^-7 of f32, labels flip only where the f32 best/second gap is
    inside that band, and fused moments stay f32-accumulated (close to
    the f32 moments wherever labels agree)."""
    pts, ctr, infl = _rand(2048, 32, 3, seed=29)
    fn = assign_backend(backend)
    i32, b32, s32 = fn(pts, ctr, infl, block_p=256, block_c=32)
    i16, b16, s16 = fn(pts, ctr, infl, block_p=256, block_c=32,
                       precision="bf16")
    flipped = np.asarray(i16) != np.asarray(i32)
    # bf16 mantissa error (~2^-8 per operand) on the cross term is
    # *absolute* in the operand-norm scale (|p|^2 + |c|^2 ~ O(1) here);
    # small distances see it amplified by cancellation, hence atol
    np.testing.assert_allclose(np.asarray(b16)[~flipped],
                               np.asarray(b32)[~flipped],
                               rtol=1e-2, atol=2e-2)
    if flipped.any():
        # flips only on near-ties: the f32 second/best gap sits inside the
        # bf16 error band, which is absolute at the operand-norm scale
        # (~2^-8 per operand on |p|^2+|c|^2 ~ O(1), times inv2 <= 4)
        gap = np.asarray(s32)[flipped] - np.asarray(b32)[flipped]
        assert float(gap.max()) <= 2.0 ** -6
    assert float(np.mean(flipped)) < 0.05


# ---------------------------------------------------------------------------
# adaptive default chunk + tile-prune statistic + env override
# ---------------------------------------------------------------------------

def test_adaptive_chunk_is_label_bitexact():
    """chunk only tiles the point axis -> per-point results are identical
    for ANY chunk; the adaptive default must be label/best/second
    bit-exact vs the former fixed 65536."""
    pts, ctr, infl = _rand(5000, 37, 2, seed=37)
    a = assign_argmin_jnp(pts, ctr, infl)                  # adaptive
    b = assign_argmin_jnp(pts, ctr, infl, chunk=65536)     # PR 4 default
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    from repro.kernels.ops import default_chunk
    assert default_chunk(64) == (1 << 19) // 64
    assert default_chunk(1) == 65536                       # clamp high
    assert default_chunk(10 ** 6) == 2048                  # clamp low


def test_tile_prune_fraction_statistic():
    """Clustered data with converged (tight) second-best distances must
    show prunable tiles; the statistic is in [0, 1], never counts the
    j == 0 tile, and is 0 when second-best is infinite (k == 1)."""
    rng = np.random.default_rng(41)
    # four tight blobs: two near pairs far apart, point-sorted so tiles
    # are spatially coherent.  Each point's second-best is its pair
    # partner (~1 away); the far pair's center tiles (bound ~100) are
    # prunable.  k=2 alone can never prune (the second IS the other
    # center), hence 4 centers here.
    xs = [0.0, 1.0, 10.0, 11.0]
    pts = jnp.asarray(np.concatenate(
        [rng.normal([x, 0.0], 0.05, (512, 2)) for x in xs]), jnp.float32)
    ctr = jnp.asarray([[x, 0.0] for x in xs], jnp.float32)
    infl = jnp.ones(4, jnp.float32)
    _, _, s = assign_argmin_jnp(pts, ctr, infl)
    frac = tile_prune_fraction(pts, ctr, infl, s, block_p=256, block_c=1)
    assert 0.0 < float(frac) <= 1.0
    frac1 = tile_prune_fraction(pts, ctr[:1], infl[:1],
                                jnp.full(2048, jnp.inf), 256, 128)
    assert float(frac1) == 0.0


def test_stats_expose_tiles_pruned_frac():
    from repro.core.balanced_kmeans import BKMConfig, balanced_kmeans_jit
    rng = np.random.default_rng(43)
    pts = jnp.asarray(rng.uniform(0, 1, (3000, 2)), jnp.float32)
    _, _, _, st = balanced_kmeans_jit(pts, BKMConfig(k=4, block_p=256))
    frac = float(st["tiles_pruned_frac"])
    assert 0.0 <= frac <= 1.0


def test_env_override_resolves_auto(monkeypatch):
    from repro.kernels.ops import (backend_supports_moments,
                                   resolve_assign_backend)
    monkeypatch.setenv("REPRO_ASSIGN_BACKEND", "triton")
    assert resolve_assign_backend("auto") == "triton"
    assert backend_supports_moments("auto")
    # explicit names are NOT overridden
    assert resolve_assign_backend("jnp") == "jnp"
    monkeypatch.setenv("REPRO_ASSIGN_BACKEND", "nope")
    with pytest.raises(KeyError, match="REPRO_ASSIGN_BACKEND"):
        resolve_assign_backend("auto")


def test_auto_resolves_to_moments_capable_backend():
    """Acceptance: whatever auto resolves to (under any env combination
    CI runs) must be a registered, moments-capable backend."""
    from repro.kernels.ops import (_ASSIGN_BACKENDS,
                                   backend_supports_moments,
                                   resolve_assign_backend)
    name = resolve_assign_backend("auto")
    assert name in _ASSIGN_BACKENDS
    assert backend_supports_moments(name)
    assert backend_supports_moments("auto")


def test_fused_moments_ignore_zero_weight_padding():
    """Zero-weight (padded) points must contribute nothing to any moment
    — the sharded driver relies on this for its weight-0 slot padding."""
    pts, ctr, infl = _rand(400, 5, 2, seed=19)
    w = jnp.asarray(np.r_[np.ones(300), np.zeros(100)], jnp.float32)
    _, _, _, csum, cw, rad2 = assign_argmin_jnp(
        pts, ctr, infl, weights=w, return_moments=True)
    _, _, _, csum2, cw2, rad22 = assign_argmin_jnp(
        pts[:300], ctr, infl, weights=w[:300], return_moments=True)
    np.testing.assert_allclose(np.asarray(csum), np.asarray(csum2),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(cw), np.asarray(cw2),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(rad2), np.asarray(rad22),
                               rtol=1e-6, atol=1e-6)
