"""Weighted balanced k-means (paper Section 4) — fully jittable core.

Faithful to Algorithms 1 + 2 with the following TPU/JAX adaptations
(recorded in DESIGN.md §4):

* Effective distances are computed in *squared* space:
  minimizing dist/influence is equivalent to minimizing sqdist/influence².
  Bounds (ub/lb) are kept in true effective-distance space (a sqrt of the
  per-point best/second values only, never of the full n×k matrix).
* The paper's per-point Hamerly skip (`if ub < lb`) is a scalar-CPU
  optimization; the vectorized path uses it for assignment semantics and to
  report the skip statistic, while the Pallas kernel path uses *tile-level*
  pruning for real savings (kernels/assign_kernel.py).
* Two sign typos in the paper are corrected (both confirmed against
  Hamerly 2010 and the paper's own derivations):
    - Eq. (1): ``influence /= gamma^(1/d)`` must be ``influence *=
      gamma^(1/d)`` so that oversized clusters (gamma < 1) *lose* influence
      and the derived new size equals gamma * size_old = target.
    - Eqs. (4)/(5): bound *relaxation* must widen the bounds:
      ``ub += delta/influence`` and ``lb -= max_c delta(c)/influence(c)``.
* Sampled warm-up (paper §4.5 "random initialization") is implemented with
  a traced sample length and weight masking so shapes stay static.
* The hot loop is a **fused assign+reduce**: each balance iteration's
  backend sweep also returns the per-cluster weighted moments (sizes,
  coordinate sums, radius sums), so the n×d point array is streamed
  exactly once per iteration — the movement phase's former three
  ``segment_sum`` passes collapsed into the assignment call
  (``assign_reduce``; DESIGN.md §4b). Backends without moment support
  fall back to a separate ``segment_moments`` sweep with the identical
  reduction structure, keeping fused and unfused results bit-for-bit
  equal on the ``jnp`` backend.

The same code runs single-device or under ``shard_map`` (pass ``axis_name``)
— cluster centers and influence are replicated, points are sharded, and the
only communication is global vector sums (paper §4.1), exactly the psums
emitted here. The multi-device driver is ``repro.partition.distributed``
(``partition(problem, method="geographer", devices=P)``), which pads each
shard to a static per-device shape and plumbs ``axis_name`` through this
module end-to-end; DESIGN.md §3b documents the layout.
"""
from __future__ import annotations

import functools
import warnings
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class BKMConfig:
    k: int
    epsilon: float = 0.03          # max imbalance (paper uses 0.03/0.05)
    max_iter: int = 30             # center-movement iterations (Alg. 2)
    max_balance_iter: int = 12     # balance iterations per movement (Alg. 1)
    influence_clip: float = 0.05   # max 5% influence change per step (paper)
    d_eff: int | None = None       # dimension in Eq. (1); default spatial d
    erosion: bool = True           # Eqs. (2)-(3)
    delta_tol: float = 5e-4        # movement threshold x bbox diagonal
    warmup: bool = True            # sampled warm-up rounds
    warmup_start: int = 100
    backend: str = "auto"          # kernels.ops assign backend
    use_kernel: bool = False       # deprecated: alias for backend="pallas"
    fused: bool | None = None      # fused assign+reduce; None = auto
    block_p: int = 8192            # kernel point-tile (lanes)
    block_c: int = 128             # kernel center-tile
    assign_chunk: int | None = None  # jnp path point chunk; None = adaptive
    assign_precision: str = "f32"  # distance matmul: "f32" | "bf16"
    dtype: Any = jnp.float32

    def __post_init__(self):
        if self.use_kernel:
            warnings.warn(
                "BKMConfig.use_kernel is deprecated; pass "
                "backend='pallas' instead", DeprecationWarning, stacklevel=3)
        if self.max_balance_iter < 1:
            # the movement moments ride out of the last balance iteration,
            # so the balance loop must run at least once
            raise ValueError("max_balance_iter must be >= 1")
        from repro.kernels.assign_kernel import PRECISIONS
        if self.assign_precision not in PRECISIONS:
            raise ValueError(
                f"assign_precision must be one of {PRECISIONS}, got "
                f"{self.assign_precision!r}")

    @property
    def assign_backend(self) -> str:
        """Effective backend name (folds the deprecated use_kernel flag)."""
        return "pallas" if self.use_kernel else self.backend


def _reduce(x, axis_name, op="sum"):
    # axis_name may be a single mesh axis or a tuple of axes (the 2-D
    # hierarchical mesh reduces over ("coarse", "refine") — jax sums over
    # the flattened product, bit-identical to the 1-D mesh of the same
    # device order)
    if axis_name is None:
        return x
    if op == "sum":
        return jax.lax.psum(x, axis_name)
    if op == "max":
        return jax.lax.pmax(x, axis_name)
    if op == "min":
        return jax.lax.pmin(x, axis_name)
    raise ValueError(op)


def assign_effective(points, centers, influence, chunk=None, backend="auto",
                     block_p=8192, block_c=128, precision="f32"):
    """Returns (assignment [n] int32, best_eff [n], second_eff [n]) where
    best/second are *true* effective distances dist/influence.

    ``backend`` selects the squared-distance argmin implementation from the
    ``kernels.ops`` registry ("jnp", "pallas", "triton", or "auto")."""
    from repro.kernels.ops import assign_backend
    fn = assign_backend(backend)
    idx, b, s = fn(points, centers, influence, chunk=chunk,
                   block_p=block_p, block_c=block_c, precision=precision)
    # second can be +inf when k == 1; keep bounds finite
    return idx, jnp.sqrt(b), jnp.sqrt(jnp.where(jnp.isfinite(s), s, b))


def assign_reduce(points, weights, centers, influence, cfg):
    """One hot-loop sweep: assignment + per-cluster weighted moments.

    When the resolved backend supports the fused contract (and
    ``cfg.fused`` is not False) the moments come out of the *same* pass
    over the points as the assignment; otherwise the backend call is
    followed by a ``kernels.ops.segment_moments`` sweep that shares the
    fused path's reduction structure, so both modes return bit-identical
    results for the ``jnp`` backend.

    Returns ``(idx, best_eff, second_eff, csum, cw, rad2raw)`` with
    best/second as *true* effective distances (sqrt'd, like
    ``assign_effective``) and the moments as LOCAL (not psum'd) sums:
    ``csum[c] = sum w*p``, ``cw[c] = sum w``, ``rad2raw[c] = sum
    w*best_eff_sq`` (multiply by ``influence[c]^2`` for true distances).
    """
    from repro.kernels.ops import (assign_backend, backend_supports_moments,
                                   segment_moments)
    fused = cfg.fused
    if fused is None:
        fused = backend_supports_moments(cfg.assign_backend)
    elif fused and not backend_supports_moments(cfg.assign_backend):
        raise ValueError(
            f"fused=True but assign backend {cfg.assign_backend!r} does "
            "not support return_moments; register it with "
            "supports_moments=True or pass fused=False/None")
    fn = assign_backend(cfg.assign_backend)
    if fused:
        idx, b, s, csum, cw, rad2 = fn(
            points, centers, influence, chunk=cfg.assign_chunk,
            block_p=cfg.block_p, block_c=cfg.block_c,
            weights=weights, return_moments=True,
            precision=cfg.assign_precision)
    else:
        idx, b, s = fn(points, centers, influence, chunk=cfg.assign_chunk,
                       block_p=cfg.block_p, block_c=cfg.block_c,
                       precision=cfg.assign_precision)
        csum, cw, rad2 = segment_moments(points, weights, idx, b, cfg.k,
                                         chunk=cfg.assign_chunk)
    return (idx, jnp.sqrt(b), jnp.sqrt(jnp.where(jnp.isfinite(s), s, b)),
            csum, cw, rad2)


def adapt_influence(influence, sizes, target, d_eff, clip):
    """Paper Eq. (1), sign-corrected; oversized clusters lose influence."""
    gamma = target / jnp.maximum(sizes, 1e-12)
    factor = jnp.clip(gamma ** (1.0 / d_eff), 1.0 - clip, 1.0 + clip)
    return influence * factor, factor


def erode_influence(influence, delta, beta):
    """Paper Eqs. (2)-(3): sigmoid regression of influence toward 1."""
    alpha = 2.0 / (1.0 + jnp.exp(-delta / jnp.maximum(beta, 1e-12))) - 1.0
    return jnp.exp((1.0 - alpha) * jnp.log(jnp.maximum(influence, 1e-12)))


def assign_and_balance(points, w_eff, centers, influence, A_old, ub, lb, cfg,
                       target_weight, axis_name=None, valid=None,
                       n_valid=None):
    """Algorithm 1. Returns (A, influence, ub, lb, sizes, csum, rad2sum,
    stats).

    ``w_eff`` already includes the warm-up sample mask. ``target_weight`` is
    the global per-cluster target (psum'd by the caller). ``valid`` marks
    real (non-padded) points and ``n_valid`` their global count — only for
    the skip statistic, so padding and shard count don't distort it.

    Every balance iteration is ONE fused assign+reduce sweep
    (``assign_reduce``): the per-cluster sizes come out of the same pass
    as the assignment, and the movement-phase moments (``csum`` weighted
    coordinate sums, ``rad2sum`` weighted true-distance² sums — both
    LOCAL, the caller psums them) ride out of the final iteration for
    free instead of costing three extra sweeps over the points. The
    Hamerly ``skip`` stays a statistic + bound-retention device: sound
    bounds make the argmin *unique* whenever ``ub < lb`` fires (strict
    inequality against every other center), so the freshly computed
    ``idx`` already equals the retained assignment and the fused moments
    over ``idx`` are exactly the moments of the returned labels.
    """
    d_eff = cfg.d_eff or points.shape[1]
    k, d = cfg.k, points.shape[1]

    @jax.named_scope("balance")
    def body(carry):
        i, A, ub_c, lb_c, infl, _, _, _, _, skips = carry
        idx, best, second, csum, cw, rad2raw = assign_reduce(
            points, w_eff, centers, infl, cfg)
        skip = ub_c < lb_c                       # Hamerly test (sound bounds)
        skip_stat = skip if valid is None else (skip & valid)
        A_new = idx
        ub_n = jnp.where(skip, ub_c, best)
        lb_n = jnp.where(skip, lb_c, second)
        sizes = _reduce(cw, axis_name)           # == segment_sum(w_eff, A)
        # true-distance² radius numerator: eff² scales back by infl[A]²,
        # which is invariant under the later influence rescaling
        rad2sum = rad2raw * (infl * infl)
        imb = jnp.max(sizes) / target_weight - 1.0
        done = imb <= cfg.epsilon
        infl_new, factor = adapt_influence(infl, sizes, target_weight,
                                           d_eff, cfg.influence_clip)
        infl_new = jnp.where(done, infl, infl_new)
        # Bound relaxation for the influence change: effdist scales exactly
        # by I_old/I_new per cluster (movement delta is zero inside Alg. 1).
        ratio = infl / infl_new                  # = 1/factor
        ub_n = ub_n * jnp.where(done, 1.0, ratio[A_new])
        lb_n = lb_n * jnp.where(done, 1.0, jnp.min(ratio))
        skips = skips + jnp.sum(skip_stat.astype(jnp.float32))
        return (i + 1, A_new, ub_n, lb_n, infl_new, sizes, csum, rad2sum,
                done, skips)

    def cond(carry):
        i, *_, done, _ = carry
        return (i < cfg.max_balance_iter) & (~done)

    init = (jnp.int32(0), A_old, ub, lb, influence,
            jnp.zeros(k, cfg.dtype), jnp.zeros((k, d), cfg.dtype),
            jnp.zeros(k, cfg.dtype), jnp.bool_(False), jnp.float32(0.0))
    (i, A, ub, lb, infl, sizes, csum, rad2sum, done,
     skips) = jax.lax.while_loop(cond, body, init)
    # under shard_map, report the *global* skip rate (psum'd numerator over
    # the true global point count) so the statistic is invariant to both
    # the shard count and the per-shard padding
    skips = _reduce(skips, axis_name)
    if n_valid is None:
        n_valid = points.shape[0] * (1 if axis_name is None
                                     else jax.lax.psum(1, axis_name))
    stats = {"balance_iters": i, "balanced": done,
             "skip_fraction": skips / (jnp.maximum(i, 1) * n_valid)}
    return A, infl, ub, lb, sizes, csum, rad2sum, stats


def balanced_kmeans(points, cfg: BKMConfig, weights=None, centers0=None,
                    axis_name=None, n_global=None, target_weight=None,
                    influence0=None, warm_start=False,
                    prev_assignment=None):
    """Algorithm 2 (minus the SFC sort, done by the caller/partitioner).

    ``points`` are the (local shard of) points, *already permuted randomly*
    if warm-up is enabled. ``centers0`` must be identical on all shards.
    ``axis_name`` is a mesh axis name or a tuple of axis names (the 2-D
    hierarchical mesh passes ``("coarse", "refine")``; every reduction
    then psums over the flattened axis product).
    ``target_weight`` overrides the per-cluster balance target (default
    total_weight / k); the hierarchical engine passes the *global* target
    here so every refinement subproblem balances against the same bar and
    the composed partition keeps global imbalance <= epsilon.

    ``warm_start=True`` resumes from a previous run's ``(centers0,
    influence0)`` state (dynamic repartitioning, DESIGN.md §8): the sampled
    warm-up is skipped, and a *convergence pre-pass* assigns every point
    under the previous state, seeds the Hamerly bounds with the exact
    best/second distances, and measures the candidate center movement
    ``delta0``. When the previous state is still a fixed point (``delta0``
    below the movement threshold) the movement loop never runs
    (``stats["iters"] == 0``) and the final balance pass re-emits the
    previous assignment unchanged — an unchanged problem migrates zero
    weight. ``influence0`` (default all-ones) must be replicated across
    shards exactly like ``centers0``.

    ``prev_assignment`` (warm only, [n] int32 in the same point order)
    enables *no-op detection*: when the pre-pass assignment equals the
    previous assignment AND the previous partition is still balanced under
    the new weights, the solve is skipped outright — labels, cut and comm
    volume are bit-identical to the previous step, so re-optimizing could
    only churn data for marginal objective gain. This is what makes
    ``repartition`` on an unchanged problem a strict fixed point even when
    the underlying k-means never reached its (rarely attainable) movement
    threshold.

    Returns (assignment, centers, influence, stats).
    """
    n, d = points.shape
    k = cfg.k
    dtype = cfg.dtype
    points = points.astype(dtype)
    w = jnp.ones(n, dtype) if weights is None else weights.astype(dtype)
    if centers0 is None:
        centers0 = points[jnp.linspace(0, n - 1, k).astype(jnp.int32)]
    if n_global is None:
        n_global = n * (1 if axis_name is None else
                        jax.lax.psum(1, axis_name))
    valid = w > 0                # padded shard slots carry weight zero

    total_w = jnp.maximum(_reduce(jnp.sum(w), axis_name), 1e-12)
    base_target = (total_w / k if target_weight is None
                   else jnp.asarray(target_weight, dtype))
    lo = _reduce(jnp.min(points, axis=0), axis_name, "min")
    hi = _reduce(jnp.max(points, axis=0), axis_name, "max")
    diag = jnp.sqrt(jnp.sum((hi - lo) ** 2))
    delta_threshold = cfg.delta_tol * diag

    if cfg.warmup and not warm_start:
        # the warm-up round count is a Python-level loop bound, so the
        # global point count must be static here. jax versions that
        # constant-fold psum-of-a-constant make n_global concrete even
        # under shard_map; where that folding is absent (or a caller
        # passes a traced value), fail with an actionable error instead
        # of an opaque tracer-conversion crash.
        try:
            ng = int(n_global)
        except (jax.errors.TracerIntegerConversionError,
                jax.errors.ConcretizationTypeError) as e:
            raise ValueError(
                "balanced_kmeans: warmup=True needs a *static* global "
                "point count to derive the number of warm-up rounds. "
                "Under shard_map/axis_name pass n_global=<int global n> "
                "(the distributed driver does), or disable warmup.") from e
        n_warm = int(np.ceil(np.log2(max(ng / cfg.warmup_start, 1))))
    else:
        n_warm = 0

    def sample_mask(it):
        # warm starts never sample: the movement loop must see the full
        # weight field even if the caller's cfg still has warmup=True
        if not cfg.warmup or warm_start:
            return jnp.ones(n, dtype)
        # sample size doubles per round; local prefix of the permutation
        frac = jnp.minimum((cfg.warmup_start * 2.0 ** it) / n_global, 1.0)
        s_local = jnp.ceil(frac * n).astype(jnp.int32)
        # explicit int32: the per-shard slot index is int32 by kernel
        # contract (the sharded front door enforces ceil(n/P) <= 2**31-1
        # via partition.distributed.check_index_capacity — global
        # position arithmetic stays int64 on the host side)
        return (jnp.arange(n, dtype=jnp.int32) < s_local).astype(dtype)

    hist_len = cfg.max_iter

    @jax.named_scope("movement")
    def body(carry):
        (it, centers, infl, A, ub, lb, _, hist) = carry
        mask = sample_mask(it)
        w_eff = w * mask
        # scale the target by the sampled-weight fraction so warm-up rounds
        # balance the sample against a proportionally reduced bar
        w_round = jnp.maximum(_reduce(jnp.sum(w_eff), axis_name), 1e-12)
        target = base_target * (w_round / total_w)
        A, infl, ub, lb, sizes, csum_l, rad2_l, st = assign_and_balance(
            points, w_eff, centers, infl, A, ub, lb, cfg, target, axis_name,
            valid=valid, n_valid=n_global)
        # --- movement phase (Alg. 2 lines 12-13): the moments rode out of
        # the balance loop's final assign+reduce sweep; only the paper's
        # global vector sums remain ([k, d] + [k] — `sizes` is already the
        # psum of the weighted counts)
        csum = _reduce(csum_l, axis_name)
        cw = sizes
        new_centers = jnp.where(cw[:, None] > 0, csum / jnp.maximum(cw, 1e-12)[:, None],
                                centers)
        delta = jnp.sqrt(jnp.sum((new_centers - centers) ** 2, axis=1))
        # --- influence erosion (Eqs. 2-3); beta = avg cluster diameter
        # proxy from the weighted true-distance² sums (exact best distances
        # from the final sweep, not the retained Hamerly bounds)
        rad2 = _reduce(rad2_l, axis_name) / jnp.maximum(cw, 1e-12)
        beta = 2.0 * jnp.mean(jnp.sqrt(jnp.maximum(rad2, 0.0)))
        infl_new = erode_influence(infl, delta, beta) if cfg.erosion else infl
        # --- bound relaxation for movement + erosion (Eqs. 4-5, corrected)
        ratio = infl / infl_new
        ub = ub * ratio[A] + delta[A] / infl_new[A]
        lb = jnp.maximum(lb * jnp.min(ratio) - jnp.max(delta / infl_new), 0.0)
        max_delta = jnp.max(delta)
        updates = {"skip_fraction": st["skip_fraction"],
                   "balance_iters": st["balance_iters"].astype(jnp.float32),
                   "max_delta": max_delta,
                   "imbalance": jnp.max(sizes) / target - 1.0}
        hist = {name: hist[name].at[it].set(updates[name]) for name in hist}
        return (it + 1, new_centers, infl_new, A, ub, lb, max_delta, hist)

    def cond(carry):
        it = carry[0]
        max_delta = carry[6]
        in_warm = it < n_warm
        keep_going = in_warm | (max_delta > delta_threshold)
        if warm_start:
            # never declare convergence while the last balance phase ended
            # above epsilon — each extra movement iteration buys another
            # full influence-adaptation budget (at it == 0 the pre-pass
            # already folded balance into delta0)
            last_imb = carry[7]["imbalance"][jnp.maximum(it - 1, 0)]
            keep_going = keep_going | ((it > 0) & (last_imb > cfg.epsilon))
        return (it < cfg.max_iter) & keep_going

    hist0 = {name: jnp.zeros(hist_len, jnp.float32)
             for name in ["skip_fraction", "balance_iters", "max_delta", "imbalance"]}
    centers0 = centers0.astype(dtype)
    infl0 = (jnp.ones(k, dtype) if influence0 is None
             else jnp.asarray(influence0, dtype))
    if warm_start:
        # Convergence pre-pass: assignment + exact Hamerly bounds under the
        # previous (centers, influence), and the movement the first
        # iteration WOULD make. If that movement is already below the
        # threshold, the while_loop body never runs and the final balance
        # pass re-emits the previous assignment bit-for-bit.
        A0, best0, second0, csum_l, cw_l, _ = assign_reduce(
            points, w, centers0, infl0, cfg)
        csum0 = _reduce(csum_l, axis_name)
        cw0 = _reduce(cw_l, axis_name)
        cand0 = jnp.where(cw0[:, None] > 0,
                          csum0 / jnp.maximum(cw0, 1e-12)[:, None], centers0)
        delta0 = jnp.max(jnp.sqrt(jnp.sum((cand0 - centers0) ** 2, axis=1)))
        # an imbalanced previous state is never "converged", no matter how
        # still its centers: force the movement loop to run so balance is
        # restored by repeated influence adaptation, not only by the single
        # final pass
        imb0 = jnp.max(cw0) / base_target - 1.0
        balanced0 = imb0 <= cfg.epsilon
        delta0 = jnp.where(balanced0, delta0, jnp.inf)
        if prev_assignment is not None:
            # no-op detection: unchanged assignment + still balanced means
            # the previous partition is re-emitted verbatim (zero
            # migration), even if the k-means objective could still improve
            mismatches = _reduce(
                jnp.sum((A0 != prev_assignment.astype(jnp.int32))
                        .astype(jnp.int32)), axis_name)
            delta0 = jnp.where((mismatches == 0) & balanced0, 0.0, delta0)
        init = (jnp.int32(0), centers0, infl0, A0, best0, second0,
                delta0.astype(dtype), hist0)
    else:
        init = (jnp.int32(0), centers0, infl0,
                jnp.zeros(n, jnp.int32), jnp.full(n, jnp.inf, dtype),
                jnp.zeros(n, dtype), jnp.array(jnp.inf, dtype), hist0)
    it, centers, infl, A, ub, lb, _, hist = jax.lax.while_loop(cond, body, init)

    # final full assignment + balance pass on ALL points (mask = 1) so the
    # returned assignment is exact and balanced even if warm-up dominated
    target = base_target
    with jax.named_scope("final_pass"):
        A, infl, ub, lb, sizes, _, _, st = assign_and_balance(
            points, w, centers, infl, A,
            jnp.full(n, jnp.inf, dtype), jnp.zeros(n, dtype), cfg, target,
            axis_name, valid=valid, n_valid=n_global)
    # tile-pruning effectiveness under the final state: fraction of the
    # kernel's (point-tile x center-tile) grid the bbox bound skips
    # (estimated from the converged second-best; ops.tile_prune_fraction).
    # lb after the final pass IS the second-best effective distance
    # (entered with lb=0/ub=inf, so the Hamerly skip never retains stale
    # bounds), squared back to the kernel's effective-sq space.
    from repro.kernels.ops import tile_prune_fraction
    frac = tile_prune_fraction(points, centers, infl, lb * lb,
                               cfg.block_p, cfg.block_c)
    n_shards = 1 if axis_name is None else jax.lax.psum(1, axis_name)
    stats = {"iters": it, "final_sizes": sizes,
             "final_imbalance": jnp.max(sizes) / target - 1.0,
             "final_balance_iters": st["balance_iters"],
             "skip_fraction_final": st["skip_fraction"],
             "tiles_pruned_frac": _reduce(frac, axis_name) / n_shards,
             "history": hist}
    return A, centers, infl, stats


@functools.partial(jax.jit, static_argnames=("cfg", "warm_start"))
def balanced_kmeans_jit(points, cfg: BKMConfig, weights=None, centers0=None,
                        influence0=None, warm_start=False):
    return balanced_kmeans(points, cfg, weights, centers0,
                           influence0=influence0, warm_start=warm_start)
