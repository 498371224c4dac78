"""Space-filling curve (Hilbert) indices, 2D and 3D.

The paper bootstraps Geographer by globally sorting points along a Hilbert
curve and placing the k initial centers at equal intervals along the curve
(Algorithm 2, lines 4-7).

Two implementations are provided:

* ``hilbert_index_np`` — host-side numpy, 64-bit keys (21 bits/dim in 3D,
  31 bits/dim in 2D). Used by the data pipeline and benchmarks.
* ``hilbert_index_jnp`` — in-graph jax version with 30-bit keys (15 bits/dim
  in 2D, 10 bits/dim in 3D) that fit int32. Used inside jitted partitioning
  steps and by the distributed partitioner.

Both use Skilling's transpose algorithm ("Programming the Hilbert curve",
AIP 2004), which is branch-free over the point axis and therefore
vectorizes cleanly on both numpy and the TPU VPU.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp


def _axes_to_transpose_np(X: np.ndarray, bits: int) -> np.ndarray:
    """Skilling inverse-undo + Gray encode. X: [n, d] uint64, returns [n, d]."""
    X = X.copy()
    n, d = X.shape
    M = np.uint64(1) << np.uint64(bits - 1)
    # Inverse undo excess work
    Q = M
    while Q > np.uint64(1):
        Pm = Q - np.uint64(1)
        for i in range(d):
            flag = (X[:, i] & Q) != 0
            # where flag: invert low bits of X[:,0]
            X[:, 0] = np.where(flag, X[:, 0] ^ Pm, X[:, 0])
            # else: exchange low bits of X[:,0] and X[:,i]
            t = np.where(~flag, (X[:, 0] ^ X[:, i]) & Pm, np.uint64(0))
            X[:, 0] ^= t
            X[:, i] ^= t
        Q >>= np.uint64(1)
    # Gray encode
    for i in range(1, d):
        X[:, i] ^= X[:, i - 1]
    t = np.zeros(n, dtype=np.uint64)
    Q = M
    while Q > np.uint64(1):
        flag = (X[:, d - 1] & Q) != 0
        t = np.where(flag, t ^ (Q - np.uint64(1)), t)
        Q >>= np.uint64(1)
    for i in range(d):
        X[:, i] ^= t
    return X


def _interleave_np(X: np.ndarray, bits: int) -> np.ndarray:
    """Bit-interleave the transposed form into a single key. X: [n, d]."""
    n, d = X.shape
    key = np.zeros(n, dtype=np.uint64)
    for b in range(bits - 1, -1, -1):
        for i in range(d):
            key = (key << np.uint64(1)) | ((X[:, i] >> np.uint64(b)) & np.uint64(1))
    return key


def quantize_np(points: np.ndarray, bits: int) -> np.ndarray:
    """Scale float coords in a bounding box to integer grid [0, 2^bits)."""
    lo = points.min(axis=0)
    hi = points.max(axis=0)
    span = np.maximum(hi - lo, 1e-30)
    scaled = (points - lo) / span
    q = np.minimum((scaled * (2 ** bits)).astype(np.uint64), np.uint64(2 ** bits - 1))
    return q


def hilbert_index_np(points: np.ndarray, bits: int | None = None) -> np.ndarray:
    """Hilbert key per point. points: [n, d] float, d in {2, 3}."""
    d = points.shape[1]
    if bits is None:
        bits = 31 if d == 2 else 21
    assert bits * d <= 63, "key must fit int64"
    with jax.profiler.TraceAnnotation("repro.bootstrap.keys"):
        q = quantize_np(np.asarray(points, dtype=np.float64), bits)
        t = _axes_to_transpose_np(q, bits)
        return _interleave_np(t, bits)


# --------------------------------------------------------------------------
# jax version (int32 keys; 15 bits/dim 2D, 10 bits/dim 3D)
# --------------------------------------------------------------------------

def _axes_to_transpose_jnp(X: jnp.ndarray, bits: int) -> jnp.ndarray:
    """X: [n, d] int32 -> transposed Hilbert form [n, d]. Unrolled over bits
    (bits <= 15) so the graph is straight-line; vectorized over points."""
    n, d = X.shape
    cols = [X[:, i] for i in range(d)]
    Q = 1 << (bits - 1)
    while Q > 1:
        Pm = Q - 1
        for i in range(d):
            flag = (cols[i] & Q) != 0
            inv = jnp.where(flag, cols[0] ^ Pm, cols[0])
            t = jnp.where(flag, 0, (cols[0] ^ cols[i]) & Pm)
            cols[0] = inv ^ t
            cols[i] = jnp.where(flag, cols[i], cols[i] ^ t)
        Q >>= 1
    for i in range(1, d):
        cols[i] = cols[i] ^ cols[i - 1]
    t = jnp.zeros(n, dtype=X.dtype)
    Q = 1 << (bits - 1)
    while Q > 1:
        flag = (cols[d - 1] & Q) != 0
        t = jnp.where(flag, t ^ (Q - 1), t)
        Q >>= 1
    return jnp.stack([c ^ t for c in cols], axis=1)


def hilbert_index_jnp(points: jnp.ndarray, bits: int | None = None,
                      lo: jnp.ndarray | None = None,
                      hi: jnp.ndarray | None = None) -> jnp.ndarray:
    """In-graph Hilbert key, int32. points: [n, d] float32.

    ``lo``/``hi`` allow passing a *global* bounding box (psum'd beforehand)
    so shards quantize consistently.
    """
    d = points.shape[1]
    if bits is None:
        bits = 15 if d == 2 else 10
    assert bits * d <= 31
    if lo is None:
        lo = jnp.min(points, axis=0)
    if hi is None:
        hi = jnp.max(points, axis=0)
    span = jnp.maximum(hi - lo, 1e-30)
    scaled = (points - lo) / span
    q = jnp.clip((scaled * (2 ** bits)).astype(jnp.int32), 0, 2 ** bits - 1)
    t = _axes_to_transpose_jnp(q, bits)
    key = jnp.zeros(points.shape[0], dtype=jnp.int32)
    for b in range(bits - 1, -1, -1):
        for i in range(d):
            key = (key << 1) | ((t[:, i] >> b) & 1)
    return key


def sfc_initial_centers_sharded(points: jnp.ndarray, weights: jnp.ndarray,
                                k: int, axis_name: str,
                                n_buckets: int = 1024) -> jnp.ndarray:
    """Distributed SFC bootstrap (paper Alg. 2 lines 4-7 under SPMD).

    Runs inside ``shard_map`` with ``points``/``weights`` holding one
    shard. Three steps, all O(1)-sized communication (independent of n):

    1. per-shard Hilbert keys against the *global* bounding box
       (pmin/pmax so every shard quantizes identically);
    2. a psum'd weighted key histogram whose prefix sums locate the k
       global weighted-quantile splitter keys — the static-shape analogue
       of the paper's distributed prefix sum over the sorted curve;
    3. for each splitter, the actual point with the globally nearest key
       (pmin over per-shard minima, lowest shard id breaking ties, winner
       coordinates broadcast with one psum).

    Returns [k, d] centers, replicated across shards. Zero-weight padded
    slots (which replicate real points) contribute nothing to the
    histogram and only valid coordinates to step 3.
    """
    d = points.shape[1]
    bits = 15 if d == 2 else 10
    total_bits = bits * d
    shift = max(total_bits - int(np.log2(n_buckets)), 0)
    lo = jax.lax.pmin(jnp.min(points, axis=0), axis_name)
    hi = jax.lax.pmax(jnp.max(points, axis=0), axis_name)
    keys = hilbert_index_jnp(points, bits=bits, lo=lo, hi=hi)

    bucket = (keys >> shift).astype(jnp.int32)
    hist = jax.ops.segment_sum(weights, bucket, num_segments=n_buckets)
    hist = jax.lax.psum(hist, axis_name)
    cum = jnp.cumsum(hist)
    total = jnp.maximum(cum[-1], 1e-12)
    targets = (jnp.arange(k, dtype=cum.dtype) + 0.5) * (total / k)
    b = jnp.clip(jnp.searchsorted(cum, targets), 0, n_buckets - 1)
    prev = jnp.where(b > 0, cum[jnp.maximum(b - 1, 0)], 0.0)
    frac = jnp.clip((targets - prev) / jnp.maximum(hist[b], 1e-12), 0.0, 1.0)
    splitters = (b.astype(jnp.float32) + frac) * float(2 ** shift)  # [k]

    # nearest real point to each splitter key (global argmin, ties -> the
    # lowest shard id, then the shard-local argmin)
    kd = jnp.abs(keys.astype(jnp.float32)[None, :] - splitters[:, None])
    loc = jnp.argmin(kd, axis=1)                          # [k] local best
    loc_d = jnp.take_along_axis(kd, loc[:, None], axis=1)[:, 0]
    best_d = jax.lax.pmin(loc_d, axis_name)
    me = jax.lax.axis_index(axis_name)
    n_shards = jax.lax.psum(1, axis_name)
    cand = jnp.where(loc_d <= best_d, me, n_shards)
    winner = jax.lax.pmin(cand, axis_name)
    mine = (winner == me)[:, None]
    contrib = jnp.where(mine, points[loc], 0.0)
    return jax.lax.psum(contrib, axis_name)


def sfc_order(points: np.ndarray) -> np.ndarray:
    """Stable Hilbert-curve sort order of ``points`` (host-side). Shared by
    the SFC baseline partitioner, initial-center placement, and the
    hierarchical engine's per-block center seeding."""
    keys = hilbert_index_np(points)
    with jax.profiler.TraceAnnotation("repro.bootstrap.sort"):
        return np.argsort(keys, kind="stable")


def sfc_initial_centers(points: np.ndarray, k: int,
                        weights: np.ndarray | None = None) -> np.ndarray:
    """Paper Alg. 2 line 7: centers at sorted positions i*n/k + n/2k.

    With node weights, strides are taken in cumulative-weight space so each
    center seeds a block of roughly equal weight.
    """
    order = sfc_order(points)
    n = points.shape[0]
    if weights is None:
        idx = (np.arange(k) * n) // k + n // (2 * k)
        return points[order[np.minimum(idx, n - 1)]]
    w = np.asarray(weights, dtype=np.float64)[order]
    cw = np.cumsum(w)
    total = cw[-1]
    targets = (np.arange(k) + 0.5) * (total / k)
    pos = np.searchsorted(cw, targets)
    return points[order[np.minimum(pos, n - 1)]]
