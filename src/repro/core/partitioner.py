"""Geographer: SFC bootstrap + balanced k-means (paper Algorithm 2).

Two single-host entry points, both driven through one stage -> solve ->
fetch driver (``_stage_solve_fetch``):

* ``geographer_partition`` — a cold solve: numpy SFC bootstrap, then the
  jitted balanced k-means with its sampled warm-up.
* ``geographer_repartition`` — a warm solve resumed from a previous
  partition's (centers, influence) state (DESIGN.md §8).

The sharded multi-device layout of the same solve is
``repro.partition.distributed``; prefer the front doors
``repro.partition.partition`` / ``repartition`` over both.
"""
from __future__ import annotations

import functools
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np

from .balanced_kmeans import BKMConfig, balanced_kmeans
from .sfc import sfc_initial_centers


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
        pts64 = np.asarray(points, dtype=np.float64)
        centers0 = sfc_initial_centers(pts64, k, weights)
    # the driver stages from the bootstrap's float64 copy: no second one
    out, centers, infl, stats = _stage_solve_fetch(pts64, weights, cfg, seed,
                                                   centers0)
    if return_state:
        return out, centers, infl, stats
    if return_stats:
        return out, stats
    return out


def _stage_solve_fetch(points, weights, cfg: BKMConfig, seed: int,
                       centers0, influence0=None, prev_labels=None, *,
                       attempt: int | None = None, pad: bool = False):
    """The flat layout's one stage -> solve -> fetch driver, under the
    ``repro.stage`` / ``repro.solve`` / ``repro.fetch`` spans.

    Stages the points permuted with ``seed`` (the sampled warm-up's
    random order, paper §4.5; the sharded deal permutes with the same
    seed), solves, and scatters the labels back to the caller's order.
    ``attempt=None`` is a cold solve (``_run_jit``); an int is a warm
    solve (``_run_warm_jit``) resumed from ``centers0`` / ``influence0``,
    and the ``repro.solve`` span records it. ``pad`` stages
    ``warm_slots(n)`` slots: pad slots replicate real points at weight
    zero, and their labels are dropped.

    Returns:
        (labels [n] int64, centers [k, d], influence [k], stats dict).
    """
    with jax.profiler.TraceAnnotation("repro.stage"):
        n = points.shape[0]
        perm = np.random.default_rng(seed).permutation(n)
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
    span = {} if attempt is None else {"attempt": attempt}
    with jax.profiler.TraceAnnotation("repro.solve", **span, slots=slots):
        solved = jax.block_until_ready(
            _run_jit(pts, cfg, w, c0) if attempt is None
            else _run_warm_jit(pts, cfg, w, c0, infl0, prev, n_valid))
    with jax.profiler.TraceAnnotation("repro.fetch"):
        A, centers, infl, stats = jax.device_get(solved)
        out = np.empty(n, dtype=np.int64)
        out[perm] = A[:n]
    return out, centers, infl, stats


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
    return _stage_solve_fetch(points, weights, cfg, seed, centers0,
                              influence0, prev_labels, attempt=attempt,
                              pad=pad)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _run_warm_jit(points, cfg, weights, centers0, influence0,
                  prev_assignment, n_valid=None):
    return balanced_kmeans(points, cfg, weights, centers0,
                           influence0=influence0, warm_start=True,
                           prev_assignment=prev_assignment,
                           n_global=n_valid)
