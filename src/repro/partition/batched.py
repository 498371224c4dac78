"""Batched balanced k-means: many independent subproblems, one dispatch.

The paper's algorithm is a fixed-point loop over static-shape arrays, so a
batch of B subproblems (the k1 refinement blocks of a hierarchical
partition, or B independent meshes) vmaps cleanly: every subproblem is
padded to a common ``cap`` point count and carries a validity mask encoded
the same way as the warm-up sampling in ``core.balanced_kmeans`` — padded
slots *replicate real points with weight zero*, so they influence neither
the bounding box nor any weighted sum, and the nested while_loops batch
via jax's select-based rule (finished subproblems coast).

``batched_balanced_kmeans`` runs all B subproblems in ONE jitted device
dispatch and is bit-for-bit identical to calling ``balanced_kmeans`` per
subproblem (verified by tests/test_partition_engine.py);
``sequential_balanced_kmeans`` is that reference loop.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import metrics
from repro.core.balanced_kmeans import BKMConfig, balanced_kmeans


@functools.partial(jax.jit, static_argnames=("cfg",))
def _batched_jit(points, weights, centers0, target_weight, cfg: BKMConfig):
    def one(p, w, c0, tw):
        return balanced_kmeans(p, cfg, w, c0, target_weight=tw)
    return jax.vmap(one)(points, weights, centers0, target_weight)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _single_jit(points, weights, centers0, target_weight, cfg: BKMConfig):
    return balanced_kmeans(points, cfg, weights, centers0,
                           target_weight=target_weight)


def _prep(points, weights, centers0, cfg, target_weight):
    points = jnp.asarray(points, cfg.dtype)
    B, n, _ = points.shape
    weights = (jnp.ones((B, n), cfg.dtype) if weights is None
               else jnp.asarray(weights, cfg.dtype))
    centers0 = jnp.asarray(centers0, cfg.dtype)
    if target_weight is None:
        target_weight = jnp.sum(weights, axis=1) / cfg.k
    else:
        target_weight = jnp.broadcast_to(
            jnp.asarray(target_weight, cfg.dtype), (B,))
    return points, weights, centers0, target_weight


def batched_balanced_kmeans(points, weights, centers0, cfg: BKMConfig,
                            target_weight=None):
    """Solve B balanced-k-means subproblems in one jitted vmap dispatch.

    points [B, n, d]; weights [B, n] (0 marks padded slots — pad with
    *copies of real points* so bounding boxes stay tight); centers0
    [B, k, d]. ``target_weight``: scalar or [B] per-subproblem balance
    target (default: each subproblem's total weight / k).

    Returns (labels [B, n] int32, centers [B, k, d], influence [B, k],
    stats pytree with leading batch axis).
    """
    args = _prep(points, weights, centers0, cfg, target_weight)
    return _batched_jit(*args, cfg)


@functools.lru_cache(maxsize=64)
def _build_refine_runner(p1: int, p2: int, cfg: BKMConfig):
    """Compile-cached shard_map driver batching refinement blocks over the
    REFINE axis of the 2-D hierarchical mesh (dist.rules.partition_mesh2d).

    The blocks shard over ``REFINE_AXIS`` alone and are replicated over
    ``COARSE_AXIS`` (every coarse row computes the same block set — the
    blocks are tiny, 1/k1 of the data each, so the redundancy is cheap
    and keeps the body collective-free). Replication checking is off
    (``dist.rules.shard_map``) because the replication is by construction,
    not by collective.
    """
    from jax.sharding import PartitionSpec as P

    from repro.dist.rules import REFINE_AXIS, partition_mesh2d, shard_map

    mesh = partition_mesh2d(p1, p2)

    # every block solves locally on its refine-axis device — the
    # refinement phase of the 2-D mesh is communication-free by design
    # (the coarse pass owns the psum traffic), and the budget directive
    # pins that: a refactor that adds a collective here fails lint
    def local_blocks(p, w, c0, tw):   # spmdlint: psum-budget=0
        def one(pp, ww, cc, tt):
            return balanced_kmeans(pp, cfg, ww, cc, target_weight=tt)
        return jax.vmap(one)(p, w, c0, tw)

    spec = P(REFINE_AXIS)
    return jax.jit(shard_map(local_blocks, mesh=mesh,
                             in_specs=(spec, spec, spec, spec),
                             out_specs=(spec, spec, spec, spec)))


def sharded_batched_balanced_kmeans(points, weights, centers0,
                                    cfg: BKMConfig, *, devices,
                                    target_weight=None):
    """Solve B refinement subproblems sharded over the refine axis of the
    2-D ``(COARSE_AXIS, REFINE_AXIS)`` device mesh.

    Same contract as ``batched_balanced_kmeans`` plus ``devices=(P1, P2)``;
    the B blocks are padded to a multiple of P2 with copies of block 0
    (their outputs are dropped), dealt P(REFINE_AXIS)-sharded, and each
    device runs the plain local vmap. Every block still solves exactly
    the same trace as the host vmap, so the results are *bit-for-bit
    identical* to ``batched_balanced_kmeans`` (asserted by
    tests/test_hierarchical_2d.py).
    """
    p1, p2 = (int(d) for d in devices)
    pts, w, c0, tw = _prep(points, weights, centers0, cfg, target_weight)
    B = pts.shape[0]
    Bp = -(-B // p2) * p2                  # pad B to a multiple of P2
    if Bp != B:
        idx = jnp.concatenate([jnp.arange(B),
                               jnp.zeros(Bp - B, jnp.int32)])
        pts, w, c0, tw = (x[idx] for x in (pts, w, c0, tw))
    run = _build_refine_runner(p1, p2, cfg)
    A, C, infl, stats = run(pts, w, c0, tw)
    if Bp != B:
        A, C, infl = A[:B], C[:B], infl[:B]
        stats = jax.tree.map(lambda x: x[:B], stats)
    return A, C, infl, stats


def sequential_balanced_kmeans(points, weights, centers0, cfg: BKMConfig,
                               target_weight=None):
    """Reference loop: same subproblems, one dispatch each. Bit-for-bit
    equal to ``batched_balanced_kmeans`` — kept for parity testing and for
    hosts where one giant dispatch is undesirable."""
    pts, w, c0, tw = _prep(points, weights, centers0, cfg, target_weight)
    outs = [_single_jit(pts[b], w[b], c0[b], tw[b], cfg)
            for b in range(pts.shape[0])]
    A = jnp.stack([o[0] for o in outs])
    C = jnp.stack([o[1] for o in outs])
    infl = jnp.stack([o[2] for o in outs])
    stats = jax.tree.map(lambda *xs: jnp.stack(xs), *[o[3] for o in outs])
    return A, C, infl, stats


@functools.partial(jax.jit, static_argnames=("cfg", "warm"))
def _bucket_jit(points, weights, centers0, influence0, prev_assignment,
                target_weight, cfg: BKMConfig, warm: bool):
    if warm:
        def one(p, w, c0, i0, pa, tw):
            return balanced_kmeans(p, cfg, w, c0, target_weight=tw,
                                   influence0=i0, warm_start=True,
                                   prev_assignment=pa)
        A, C, infl, stats = jax.vmap(one)(points, weights, centers0,
                                          influence0, prev_assignment,
                                          target_weight)
    else:
        def one(p, w, c0, tw):
            return balanced_kmeans(p, cfg, w, c0, target_weight=tw)
        A, C, infl, stats = jax.vmap(one)(points, weights, centers0,
                                          target_weight)
    # per-slot request metrics ride in the same dispatch: imbalance on the
    # padded batch always, migration vs the warm-start assignment when warm
    stats = dict(stats)
    stats["imbalance"] = metrics.batch_imbalance(A, cfg.k, weights)
    if warm:
        stats["migration_fraction"] = metrics.batch_migration_fraction(
            prev_assignment, A, weights)
    return A, C, infl, stats


def bucket_balanced_kmeans(points, weights, centers0, cfg: BKMConfig, *,
                           counts=None, valid=None, target_weight=None,
                           influence0=None, prev_assignment=None,
                           warm: bool = False):
    """Solve one serving *bucket* — S fixed slots padded to a common point
    cap — in a single jitted vmap dispatch.

    This is the static-shape entry the multi-tenant ``PartitionServer``
    (repro.serve) drives: every slot is an independent subproblem padded
    with *copies of its own real points at weight zero* (the engine-wide
    padding discipline — bounding boxes stay tight, weighted sums are
    exact), and slots past the end of a request group are filler copies
    flagged invalid.

    Args:
        points:   [S, cap, d] padded per-slot coordinates.
        weights:  [S, cap] weights, 0 on padded entries (None = ones; only
            meaningful when every slot is full, i.e. counts == cap).
        centers0: [S, k, d] initial centers (SFC bootstrap for cold slots,
            cached warm centers for warm slots).
        cfg: shared ``BKMConfig`` (k/epsilon static across the bucket).
        counts:   optional [S] real point counts per slot (<= cap),
            recorded in ``stats["counts"]``.
        valid:    optional [S] bool slot-validity mask (False = filler
            slot whose outputs must be discarded), recorded in
            ``stats["valid"]``.
        target_weight: scalar or [S] balance target override.
        influence0: [S, k] warm influence (warm only; None = ones).
        prev_assignment: [S, cap] int32 previous labels in the padded
            order (warm only; enables no-op detection per slot).
        warm: resume every slot from (centers0, influence0) with
            ``warm_start=True`` instead of cold-starting.

    Returns:
        (labels [S, cap] int32, centers [S, k, d], influence [S, k],
        stats) — ``stats`` carries the solver pytree with a leading slot
        axis plus ``"imbalance"`` [S] (and ``"migration_fraction"`` [S]
        when warm) computed in-graph on the padded batch, and the
        host-side ``"counts"`` / ``"valid"`` passthroughs.

    Raises:
        ValueError: shape mismatches, counts exceeding the cap, or warm
            state missing/present on the wrong path.
    """
    pts, w, c0, tw = _prep(points, weights, centers0, cfg, target_weight)
    S, cap, _ = pts.shape
    if counts is not None:
        counts = np.asarray(counts)
        if counts.shape != (S,):
            raise ValueError(f"counts must be [{S}], got {counts.shape}")
        if counts.max() > cap or counts.min() < 1:
            raise ValueError(f"counts must lie in [1, cap={cap}], got "
                             f"range [{counts.min()}, {counts.max()}]")
    if valid is not None:
        valid = np.asarray(valid, bool)
        if valid.shape != (S,):
            raise ValueError(f"valid must be [{S}], got {valid.shape}")
    if warm:
        if influence0 is None:
            influence0 = jnp.ones((S, cfg.k), cfg.dtype)
        else:
            influence0 = jnp.asarray(influence0, cfg.dtype)
        if prev_assignment is None:
            raise ValueError("warm bucket solves need prev_assignment "
                             "(the [S, cap] warm-start labels)")
        prev_assignment = jnp.asarray(prev_assignment, jnp.int32)
        if influence0.shape != (S, cfg.k):
            raise ValueError(f"influence0 must be [{S}, {cfg.k}], got "
                             f"{influence0.shape}")
        if prev_assignment.shape != (S, cap):
            raise ValueError(f"prev_assignment must be [{S}, {cap}], got "
                             f"{prev_assignment.shape}")
    elif influence0 is not None or prev_assignment is not None:
        raise ValueError("influence0/prev_assignment are warm-start "
                         "state; pass warm=True")
    A, C, infl, stats = _bucket_jit(pts, w, c0, influence0,
                                    prev_assignment, tw, cfg, warm)
    stats = dict(stats)
    if counts is not None:
        stats["counts"] = counts
    if valid is not None:
        stats["valid"] = valid
    return A, C, infl, stats


def build_refinement_batch(points: np.ndarray, weights: np.ndarray | None,
                           labels: np.ndarray, k1: int):
    """Gather the k1 coarse blocks into static-shape refinement inputs.

    Every block is padded to ``cap = max block count`` by cycling its own
    point indices (real coordinates, zero weight), which keeps per-block
    bounding boxes exact and never introduces phantom geometry.

    Returns (bpts [k1, cap, d], bw [k1, cap], gather [k1, cap] int64,
    counts [k1]): ``gather[b, :counts[b]]`` are the original point ids of
    block b (so sub-labels scatter back losslessly), the rest is padding.
    """
    n = points.shape[0]
    labels = np.asarray(labels)
    counts = np.bincount(labels, minlength=k1)
    if counts.min() == 0:
        raise ValueError("empty coarse block; cannot refine")
    cap = int(counts.max())
    order = np.argsort(labels, kind="stable")
    starts = np.concatenate([[0], np.cumsum(counts)])
    gather = np.empty((k1, cap), np.int64)
    for b in range(k1):
        ids = order[starts[b]:starts[b + 1]]
        reps = -(-cap // len(ids))          # ceil
        gather[b] = np.tile(ids, reps)[:cap]
    w = np.ones(n) if weights is None else np.asarray(weights, np.float64)
    valid = np.arange(cap)[None, :] < counts[:, None]
    bpts = points[gather]                                 # [k1, cap, d]
    bw = np.where(valid, w[gather], 0.0)                  # [k1, cap]
    return bpts, bw, gather, counts
