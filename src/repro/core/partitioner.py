"""Geographer: SFC bootstrap + balanced k-means (paper Algorithm 2).

Two entry points:

* ``geographer_partition`` — single-host orchestration (numpy SFC sort +
  jitted balanced k-means). Used by benchmarks and the quality experiments.
* ``geographer_partition_distributed`` — full SPMD version under
  ``shard_map``: global-bbox psum, in-graph Hilbert keys, sample-sort bucket
  redistribution over ``all_to_all`` (the static-shape analogue of the
  paper's distributed quicksort), strided initial centers from the global
  SFC order, then the replicated-center balanced k-means with psum
  reductions — the paper's exact communication structure.
"""
from __future__ import annotations

import functools
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np

from .balanced_kmeans import BKMConfig, balanced_kmeans
from .sfc import hilbert_index_jnp, sfc_initial_centers


def geographer_partition(points: np.ndarray, k: int,
                         weights: np.ndarray | None = None,
                         cfg: BKMConfig | None = None,
                         seed: int = 0,
                         return_stats: bool = False,
                         return_state: bool = False):
    """Partition ``points`` into k balanced blocks. Returns [n] block ids.

    ``return_stats=True`` returns ``(labels, stats)``; ``return_state=True``
    returns ``(labels, centers, influence, stats)`` — the (centers,
    influence) pair is the warm-start state consumed by
    ``geographer_repartition`` / ``repro.partition.repartition``.

    This remains the raw single-host implementation; prefer the unified
    front door ``repro.partition.partition(problem, method="geographer")``,
    which adds the registry, hierarchical (k1 x k2) mode, and quality
    evaluation on top of it.
    """
    with jax.profiler.TraceAnnotation("repro.bootstrap"):
        cfg = cfg or BKMConfig(k=k)
        if cfg.k != k:
            cfg = replace(cfg, k=k)
        n = points.shape[0]
        pts64 = np.asarray(points, dtype=np.float64)
        centers0 = sfc_initial_centers(pts64, k, weights)
    with jax.profiler.TraceAnnotation("repro.stage"):
        # random permutation for the sampled warm-up (paper §4.5)
        rng = np.random.default_rng(seed)
        perm = rng.permutation(n)
        pts, w, c0 = jax.block_until_ready((
            jnp.asarray(pts64[perm], dtype=cfg.dtype),
            None if weights is None
            else jnp.asarray(np.asarray(weights)[perm], dtype=cfg.dtype),
            jnp.asarray(centers0, cfg.dtype)))
    with jax.profiler.TraceAnnotation("repro.solve", slots=n):
        solved = jax.block_until_ready(_run_jit(pts, cfg, w, c0))
    with jax.profiler.TraceAnnotation("repro.fetch"):
        A, centers, infl, stats = jax.device_get(solved)
        out = np.empty(n, dtype=np.int64)
        out[perm] = A
    if return_state:
        return out, centers, infl, stats
    if return_stats:
        return out, stats
    return out


@functools.partial(jax.jit, static_argnames=("cfg",))
def _run_jit(points, cfg, weights, centers0):
    return balanced_kmeans(points, cfg, weights, centers0)


def warm_slots(n: int) -> int:
    """The padded point count of a warm solve over a point set that
    changes from call to call: ``n`` rounded up to a multiple of
    ``2 ** (floor(log2 n) - 3)``, eight buckets an octave.

    A jitted solve is compiled for one point count, so a mesh that
    refines or coarsens by ~1% a step would compile on every step; over
    the buckets it compiles once per bucket, and padding stays under
    12.5% of the slots. The sharded path rounds its per-shard ``cap`` by
    the same rule, so ``devices=1`` pads exactly as the flat path does.
    """
    n = int(n)
    step = 1 << max(n.bit_length() - 4, 0)
    return -(-n // step) * step


def geographer_repartition(points: np.ndarray, k: int,
                           centers0: np.ndarray,
                           influence0: np.ndarray | None = None,
                           weights: np.ndarray | None = None,
                           cfg: BKMConfig | None = None,
                           seed: int = 0,
                           prev_labels: np.ndarray | None = None,
                           *, attempt: int = 0, pad: bool = False):
    """Warm-started Geographer: balanced k-means resumed from a previous
    partition's ``(centers0, influence0)`` state, skipping the SFC
    bootstrap and the sampled warm-up entirely (DESIGN.md §8).

    Args:
        points:     [n, d] point coordinates (possibly moved since the
                    previous partition).
        k:          number of blocks; must match ``centers0.shape[0]``.
        centers0:   [k, d] centers of the previous partition.
        influence0: [k] influence of the previous partition (None = ones).
        weights:    [n] node weights (possibly re-weighted since the
                    previous partition), or None for unit weights.
        cfg:        BKMConfig; ``warmup`` is forced off (warm starts never
                    sample) and ``k`` is forced to match.
        seed:       permutation seed — pass the SAME seed as the previous
                    run so the sharded ``devices=1`` path stays bit-for-bit
                    identical (both permute with the problem seed).
        prev_labels: [n] previous block ids (original point order). When
                    given, an unchanged-and-still-balanced partition is
                    re-emitted verbatim (no-op detection — zero migration,
                    ``stats["iters"] == 0``).
        attempt:    which solve of the caller's balance-retry loop this
                    is; recorded on the ``repro.solve`` trace span.
        pad:        solve over ``warm_slots(n)`` slots, so that calls
                    whose point counts share a bucket share one compiled
                    solve (a point set that changes from step to step).
                    Pad slots replicate real points at weight zero, the
                    sharded path's convention; their labels are dropped.

    Returns:
        (labels [n] int64, centers [k, d], influence [k], stats dict).
        ``stats["iters"]`` is the movement-iteration count — 0 when the
        previous state is still a fixed point of the (unchanged) problem.
    """
    cfg = cfg or BKMConfig(k=k, warmup=False)
    if cfg.k != k or cfg.warmup:
        cfg = replace(cfg, k=k, warmup=False)
    if centers0.shape[0] != k:
        raise ValueError(f"centers0 has {centers0.shape[0]} rows, k={k}")
    with jax.profiler.TraceAnnotation("repro.stage"):
        n = points.shape[0]
        rng = np.random.default_rng(seed)
        perm = rng.permutation(n)
        slots = warm_slots(n) if pad else n
        idx = perm if slots == n else np.resize(perm, slots)
        if pad:
            w = (np.ones(n, cfg.dtype) if weights is None
                 else np.asarray(weights, cfg.dtype))[idx]
            w[n:] = 0
            n_valid = np.int32(n)
        else:
            w = None if weights is None else np.asarray(weights)[perm]
            n_valid = None
        pts, w, c0, infl0, prev = jax.block_until_ready((
            jnp.asarray(np.asarray(points, np.float64)[idx],
                        dtype=cfg.dtype),
            None if w is None else jnp.asarray(w, dtype=cfg.dtype),
            jnp.asarray(centers0, cfg.dtype),
            None if influence0 is None
            else jnp.asarray(influence0, cfg.dtype),
            None if prev_labels is None
            else jnp.asarray(np.asarray(prev_labels)[idx], jnp.int32)))
    with jax.profiler.TraceAnnotation("repro.solve", attempt=attempt,
                                      slots=slots):
        solved = jax.block_until_ready(
            _run_warm_jit(pts, cfg, w, c0, infl0, prev, n_valid))
    with jax.profiler.TraceAnnotation("repro.fetch"):
        A, centers, infl, stats = jax.device_get(solved)
        out = np.empty(n, dtype=np.int64)
        out[perm] = A[:n]
    return out, centers, infl, stats


@functools.partial(jax.jit, static_argnames=("cfg",))
def _run_warm_jit(points, cfg, weights, centers0, influence0,
                  prev_assignment, n_valid=None):
    return balanced_kmeans(points, cfg, weights, centers0,
                           influence0=influence0, warm_start=True,
                           prev_assignment=prev_assignment,
                           n_global=n_valid)


# ---------------------------------------------------------------------------
# Distributed (shard_map) version
# ---------------------------------------------------------------------------

def _sfc_redistribute(points, weights, axis_name, n_shards, oversample=32,
                      capacity_factor=2.0):
    """Sample-sort bucket redistribution by Hilbert key (static shapes).

    Each shard ends up with ``cap = capacity_factor * n_local`` slots holding
    points whose keys fall in its splitter range; a validity mask marks real
    points. Returns (points, weights, valid, my_count, my_offset).
    """
    n_local, d = points.shape
    lo = jax.lax.pmin(jnp.min(points, axis=0), axis_name)
    hi = jax.lax.pmax(jnp.max(points, axis=0), axis_name)
    keys = hilbert_index_jnp(points, lo=lo, hi=hi)
    order = jnp.argsort(keys)
    points, weights, keys = points[order], weights[order], keys[order]

    # splitters from a regular sample of each shard's sorted keys
    samp_idx = jnp.linspace(0, n_local - 1, oversample).astype(jnp.int32)
    sample = keys[samp_idx]
    all_samples = jnp.sort(jax.lax.all_gather(sample, axis_name).reshape(-1))
    s_idx = (jnp.arange(1, n_shards) * oversample * n_shards) // n_shards
    splitters = all_samples[s_idx]                       # [n_shards-1]

    dest = jnp.searchsorted(splitters, keys, side="right").astype(jnp.int32)
    cap = int(np.ceil(capacity_factor * n_local / n_shards))
    # slot points into [n_shards, cap] send buffers (by arrival order)
    slot_in_dest = jnp.cumsum(jax.nn.one_hot(dest, n_shards, dtype=jnp.int32),
                              axis=0)[jnp.arange(n_local), dest] - 1
    ok = slot_in_dest < cap                              # overflow dropped+counted
    flat = jnp.where(ok, dest * cap + slot_in_dest, n_shards * cap)
    buf_p = jnp.zeros((n_shards * cap + 1, d), points.dtype).at[flat].set(points)[:-1]
    buf_w = jnp.zeros((n_shards * cap + 1,), weights.dtype).at[flat].set(weights)[:-1]
    buf_k = jnp.full((n_shards * cap + 1,), -1, keys.dtype).at[flat].set(keys)[:-1]
    buf_v = jnp.zeros((n_shards * cap + 1,), jnp.bool_).at[flat].set(ok)[:-1]
    n_dropped = jax.lax.psum(jnp.sum(~ok), axis_name)

    def exch(x):
        x = x.reshape(n_shards, cap, *x.shape[1:])
        return jax.lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0,
                                  tiled=False).reshape(n_shards * cap, *x.shape[2:])

    rp, rw, rk, rv = exch(buf_p), exch(buf_w), exch(buf_k), exch(buf_v)
    # local sort received points by key, invalid (key -1 -> put last via where)
    rk_sort = jnp.where(rv, rk, jnp.iinfo(jnp.int32).max)
    o = jnp.argsort(rk_sort)
    rp, rw, rv = rp[o], rw[o], rv[o]
    my_count = jnp.sum(rv.astype(jnp.int32))
    counts = jax.lax.all_gather(my_count, axis_name)
    my_offset = jnp.cumsum(counts)[jax.lax.axis_index(axis_name)] - my_count
    return rp, rw, rv, my_count, my_offset, n_dropped


def _strided_centers(points, weights, valid, my_count, my_offset, k, axis_name):
    """Initial centers at global sorted positions i*N/k + N/2k (Alg. 2 l.7)."""
    n_total = jax.lax.psum(my_count, axis_name)
    gpos = (jnp.arange(k) * n_total) // k + n_total // (2 * k)   # [k] global
    local_pos = gpos - my_offset
    mine = (local_pos >= 0) & (local_pos < my_count)
    idx = jnp.clip(local_pos, 0, points.shape[0] - 1)
    contrib = jnp.where(mine[:, None], points[idx], 0.0)
    return jax.lax.psum(contrib, axis_name)


def make_distributed_partitioner(mesh, cfg: BKMConfig, axis_name="data"):
    """Builds a jitted shard_map partitioner over ``mesh[axis_name]``.

    Input: points [N, d], weights [N] sharded on axis 0. Output: block ids
    [N] (aligned with the *redistributed* order), plus diagnostics.
    """
    from jax.sharding import PartitionSpec as P

    from repro.dist.rules import shard_map

    n_shards = mesh.shape[axis_name]

    def local_fn(points, weights):
        points = points.reshape(-1, points.shape[-1])
        weights = weights.reshape(-1)
        rp, rw, rv, cnt, off, dropped = _sfc_redistribute(
            points, weights, axis_name, n_shards)
        centers0 = _strided_centers(rp, rw, rv, cnt, off, cfg.k, axis_name)
        w_eff = jnp.where(rv, rw, 0.0)
        A, centers, infl, stats = balanced_kmeans(
            rp, cfg, w_eff, centers0, axis_name=axis_name,
            n_global=points.shape[0] * n_shards)  # static (pre-redistribution)
        A = jnp.where(rv, A, -1)
        return (A[None], rp[None], rv[None], centers, infl,
                stats["final_imbalance"], dropped)

    inner = shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(axis_name, None), P(axis_name)),
        out_specs=(P(axis_name, None), P(axis_name, None, None),
                   P(axis_name, None), P(), P(), P(), P()))

    @jax.jit
    def run(points, weights):
        A, rp, rv, centers, infl, imb, dropped = inner(points, weights)
        s = A.shape
        return (A.reshape(s[0] * s[1]), rp.reshape(-1, points.shape[-1]),
                rv.reshape(-1), centers, infl, imb, dropped)

    return run
