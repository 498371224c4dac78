"""Space-filling curve (Hilbert) indices, 2D and 3D.

The paper bootstraps Geographer by globally sorting points along a Hilbert
curve and placing the k initial centers at equal intervals along the curve
(Algorithm 2, lines 4-7).

Two implementations are provided:

* ``hilbert_index_np`` — host-side numpy, 64-bit keys (21 bits/dim in 3D,
  31 bits/dim in 2D). Used by the host SFC bootstrap (``sfc_order``), the
  data pipeline and benchmarks.
* ``hilbert_index_jnp`` — in-graph jax version with 30-bit keys (15 bits/dim
  in 2D, 10 bits/dim in 3D) that fit int32. Used inside jitted partitioning
  steps and by the distributed partitioner.

Both compute the curve of Skilling's transpose algorithm ("Programming the
Hilbert curve", AIP 2004). The jax version runs it one bit level at a time,
branch-free over the point axis, which vectorizes cleanly on the TPU VPU.
The numpy version runs it as a finite-state transducer: what the levels
above have done to a level is a state (an axis permutation, a flip per
axis, a Gray parity bit; 8 are reachable in 2D, 48 in 3D), so a table
derived from the transform (``_hilbert_table``) maps a state and the next c
bit levels of every axis to their d·c key bits and the next state in one
gather. Its keys are those of
the per-bit transform, bit for bit, from about a dozen array passes per c
levels instead of about thirty per level.
"""
from __future__ import annotations

import functools
import math

import numpy as np
import jax
import jax.numpy as jnp


# --------------------------------------------------------------------------
# numpy version (uint64 keys; 31 bits/dim 2D, 21 bits/dim 3D)
# --------------------------------------------------------------------------

_TABLE_BYTES = 1 << 17  # a transition table small enough to stay in cache
_BLOCK = 1 << 16        # points a block: the key loop's arrays stay in cache


def _chunk_levels(d: int) -> int:
    """Bit levels of every axis that one lookup consumes: the most for which
    a table of d!·2^d states × 2^(d·c) inputs of uint16 fits
    ``_TABLE_BYTES`` (6 in 2D, 3 in 3D)."""
    states = math.factorial(d) << d
    c = 1
    while states << (d * (c + 1) + 1) <= _TABLE_BYTES:
        c += 1
    return c


@functools.cache
def _hilbert_table(d: int, c: int) -> np.ndarray:
    """Skilling's transform as a transducer over c bit levels at a time.

    The transform walks the bit levels from the top. At each level it reads
    one bit per axis, then for i = 0..d-1 inverts X0 below the level if
    Xi's bit is set, else swaps X0 and Xi below it; the Gray code XORs the
    axes' bits in prefix order and flips every level below a set bit of the
    last axis. So what the levels above have done to a level is a state: an
    axis permutation, a flip per axis and one parity bit.

    Entry ``state << d·c | input`` holds the d·c key bits of the c levels
    (top level first, axis 0 first within a level) and, above them, the
    state they leave. ``input`` holds axis 0's c bits (top level first)
    above axis 1's, and so on. States are numbered breadth-first from the
    identity, 0; the walk reaches 8 of them in 2D and 48 in 3D.
    """
    m = d * c
    x = np.arange(1 << m)
    field = (d - 1 - np.arange(d)) * c       # each axis's bits in an input
    weight = 1 << (d - 1 - np.arange(d))     # each axis's key bit in a level
    states = [(tuple(range(d)), (0,) * d, 0)]  # (perm, flip, parity)
    number = {states[0]: 0}
    rows = []
    done = 0
    while done < len(states):
        frontier, done = states[done:], len(states)
        perm = np.repeat([s[0] for s in frontier], x.size, axis=0)
        flip = np.repeat([s[1] for s in frontier], x.size, axis=0)
        parity = np.repeat([s[2] for s in frontier], x.size)
        inputs = np.tile(x, len(frontier))
        key = np.zeros_like(inputs)
        for level in range(c - 1, -1, -1):
            raw = (inputs[:, None] >> (field + level)) & 1
            b = np.take_along_axis(raw, perm, axis=1) ^ flip  # bits the axes show
            for i in range(d):
                flip[:, 0] ^= b[:, i]                          # invert X0
                swap = (b[:, i] == 0)[:, None]                 # or swap X0, Xi
                for a in (perm, flip):
                    a[:, [0, i]] = np.where(swap, a[:, [i, 0]], a[:, [0, i]])
            gray = np.bitwise_xor.accumulate(b, axis=1)
            key = key << d | ((gray ^ parity[:, None]) * weight).sum(axis=1)
            parity = parity ^ gray[:, -1]
        after = np.column_stack([perm, flip, parity])
        code = after @ (max(d, 2) ** np.arange(2 * d + 1))
        _, first, inverse = np.unique(code, return_index=True,
                                      return_inverse=True)
        ids = []
        for row in after[first].tolist():
            s = (tuple(row[:d]), tuple(row[d:2 * d]), row[-1])
            if s not in number:
                number[s] = len(states)
                states.append(s)
            ids.append(number[s])
        rows.append(key | np.asarray(ids)[inverse.ravel()] << m)
    table = np.concatenate(rows)
    return table.astype(np.min_scalar_type(int(table.max())))


def _hilbert_keys(q: np.ndarray, bits: int) -> np.ndarray:
    """Hilbert keys of grid points q [n, d] (coordinates < 2^bits), one
    table lookup per c levels. A last chunk that reaches below level 0
    reads zeros there, and its key bits for those levels are dropped."""
    n, d = q.shape
    c = _chunk_levels(d)
    table = _hilbert_table(d, c)
    m = d * c
    key_mask = (1 << m) - 1
    state_mask = np.iinfo(table.dtype).max ^ key_mask
    field = [(d - 1 - a) * c for a in range(d)]
    keys = np.zeros(n, np.uint64)
    for lo in range(0, n, _BLOCK):
        key = keys[lo:lo + _BLOCK]
        cols = [q[lo:lo + _BLOCK, a].astype(np.uint32) for a in range(d)]
        entry = np.zeros(key.size, table.dtype)  # state 0, the identity
        idx = np.empty(key.size, np.intp)
        part = np.empty(key.size, np.uint32)
        for top in range(bits, 0, -c):  # levels [top - c, top)
            np.bitwise_and(entry, state_mask, out=idx)
            for a in range(d):
                shift = top - c - field[a]
                if shift >= 0:
                    np.right_shift(cols[a], shift, out=part)
                else:
                    np.left_shift(cols[a], -shift, out=part)
                np.bitwise_and(part, ((1 << c) - 1) << field[a], out=part)
                np.bitwise_or(idx, part, out=idx)
            np.take(table, idx, out=entry)
            w = d * min(c, top)  # key bits of levels >= 0
            np.left_shift(key, w, out=key)
            np.bitwise_and(entry, key_mask, out=part)
            if w < m:
                np.right_shift(part, m - w, out=part)
            np.bitwise_or(key, part, out=key)
    return keys


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
        return _hilbert_keys(q, bits)


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
