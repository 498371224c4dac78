"""``repartition()`` — dynamic repartitioning through the engine.

Parallel simulations change their load distribution every few timesteps
and must **re**partition cheaply while keeping data migration low. The
geometric formulation of balanced k-means is exactly where this shines:
warm-starting from the previous partition's (centers, influence) state
skips the SFC bootstrap and the sampled warm-up, converges in a handful of
movement iterations, and — because centers barely move — migrates a small
fraction of the weight a cold restart would (DESIGN.md §8)::

    from repro.partition import PartitionProblem, partition, repartition

    prob0 = PartitionProblem(points, k=16, weights=w0)
    prev  = partition(prob0, method="geographer")         # cold start once
    prob1 = prob0.replace(weights=w1)                     # load drifted
    res   = repartition(prob1, prev)                      # warm restart
    res.stats["migration"]["fraction"]                    # weight moved
    res.stats["iters"]                                    # ~0-5, not ~30

A mesh that is refined or coarsened between steps has another point
count: ``identity`` says, for each new point, its index in the previous
point set, or -1 for a point that refinement created::

    res2 = repartition(prob2, res, identity=idx)          # n changed
    res2.stats["migration"]["created"]                    # new weight

Methods without a warm-startable state (sfc/rcb/rib/multijagged — their
partitions are recomputed from scratch) fall back to a **cold start +
relabel matching**: new blocks are greedily matched to the previous blocks
by center correspondence, so block ids stay stable across steps and
migration is measured fairly for every method.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np

from repro.core import metrics
from repro.core.partitioner import geographer_repartition

from .algorithms import _geographer_result, make_bkm_config
from .distributed import repartition_sharded
from .engine import _CALLS, partition
from .problem import PartitionProblem, PartitionResult
from .registry import resolve_method, supports_warm_start

# Warm-start movement threshold (x bbox diagonal). Cold starts keep the
# tight default (5e-4) because their centers travel far from the SFC seed;
# a warm start resumes next to a converged state, where the productive
# signal is "centers stopped moving at the scale the workload drifted",
# not the cold threshold that even full runs rarely reach before max_iter.
WARM_DELTA_TOL = 5e-3

# A warm solve whose final balance pass ends above epsilon is re-warmed
# from its own output state (the pre-pass detects the imbalance and forces
# the movement loop to run again) at most this many times.
MAX_BALANCE_RETRIES = 2


@dataclass
class WarmState:
    """The portable warm-start state of a balanced-k-means partition.

    Everything ``balanced_kmeans(warm_start=True)`` resumes from, bundled
    so callers other than ``repartition()`` — the slot cache of
    ``repro.serve.PartitionServer`` in particular — can capture, hold and
    restore warm state without carrying a full ``PartitionResult``:

    Attributes:
        centers:   [k, d] final centers of the producing solve.
        influence: [k] final influence (paper Eq. 1 state), or None for
            all-ones.
        labels:    [n] block ids in the *original* point order (the
            ``prev_assignment`` fed to no-op detection).
    """
    centers: np.ndarray
    influence: np.ndarray | None
    labels: np.ndarray

    def __post_init__(self):
        self.centers = np.asarray(self.centers)
        self.labels = np.asarray(self.labels)
        if self.influence is not None:
            self.influence = np.asarray(self.influence)
        if self.centers.ndim != 2:
            raise ValueError(f"centers must be [k, d], "
                             f"got {self.centers.shape}")
        if (self.influence is not None
                and self.influence.shape != (self.centers.shape[0],)):
            raise ValueError(
                f"influence shape {self.influence.shape} does not match "
                f"k={self.centers.shape[0]}")

    @property
    def n(self) -> int:
        return self.labels.shape[0]

    @property
    def k(self) -> int:
        return self.centers.shape[0]

    @property
    def dim(self) -> int:
        return self.centers.shape[1]

    @classmethod
    def capture(cls, result: PartitionResult) -> "WarmState":
        """Extract the warm-start state from a ``PartitionResult``.

        Raises:
            ValueError: the result carries no centers (produced by a
                method without warm-start state, e.g. sfc/rcb).
        """
        if result.centers is None:
            raise ValueError(
                "result carries no centers to warm-start from (was it "
                "produced by a center-based method?)")
        infl = (None if result.influence is None
                else np.asarray(result.influence))
        return cls(centers=np.asarray(result.centers), influence=infl,
                   labels=np.asarray(result.labels))

    def compatible_with(self, n: int, k: int) -> bool:
        """True when this state can warm-start an (n, k) instance — the
        slot-cache invalidation predicate: a tenant that changed its
        point count or block count must cold-start."""
        return self.n == n and self.k == k

    def influence_or_ones(self) -> np.ndarray:
        """[k] influence, defaulting to all-ones (the solver's default)."""
        if self.influence is None:
            return np.ones(self.k)
        return self.influence


def weighted_centroids(points: np.ndarray, labels: np.ndarray, k: int,
                       weights: np.ndarray | None = None) -> np.ndarray:
    """[k, d] weighted centroid of every block (empty blocks get the
    global centroid so matching never sees NaNs).

    Args:
        points:  [n, d] coordinates.
        labels:  [n] block ids in [0, k).
        k:       number of blocks.
        weights: [n] node weights, or None for unit weights.

    Returns:
        [k, d] float64 centroids.
    """
    pts = np.asarray(points, np.float64)
    lab = np.asarray(labels)
    w = np.ones(len(lab)) if weights is None else np.asarray(weights,
                                                             np.float64)
    csum = np.zeros((k, pts.shape[1]))
    cw = np.zeros(k)
    np.add.at(csum, lab, pts * w[:, None])
    np.add.at(cw, lab, w)
    fallback = pts.mean(axis=0) if len(pts) else np.zeros(pts.shape[1])
    out = np.where(cw[:, None] > 0, csum / np.maximum(cw, 1e-12)[:, None],
                   fallback)
    return out


def greedy_center_match(new_centers: np.ndarray,
                        prev_centers: np.ndarray) -> np.ndarray:
    """Greedy center correspondence: a permutation ``m`` with
    ``m[new_block] = prev_block`` pairing the globally closest unmatched
    (new, prev) center pair first.

    Cold restarts return blocks in an arbitrary id order; relabeling
    through this matching keeps block ids stable across repartition steps
    so migration volume measures *data movement*, not id shuffling.

    Args:
        new_centers:  [k, d] centers/centroids of the new partition.
        prev_centers: [k, d] centers/centroids of the previous partition.

    Returns:
        [k] int64 permutation mapping new block ids to previous block ids.
    """
    new_c = np.asarray(new_centers, np.float64)
    prev_c = np.asarray(prev_centers, np.float64)
    if new_c.shape != prev_c.shape:
        raise ValueError(f"center shape mismatch: {new_c.shape} vs "
                         f"{prev_c.shape}")
    k = new_c.shape[0]
    D = ((new_c[:, None, :] - prev_c[None, :, :]) ** 2).sum(axis=-1)
    mapping = np.full(k, -1, np.int64)
    for _ in range(k):
        i, j = np.unravel_index(np.argmin(D), D.shape)
        mapping[i] = j
        D[i, :] = np.inf
        D[:, j] = np.inf
    return mapping


def _migration_stats(previous: PartitionResult, labels: np.ndarray,
                     weights: np.ndarray | None,
                     identity: np.ndarray | None) -> dict:
    """Migration against ``previous`` under the new weights. With an
    ``identity`` map it is counted over the persisting points (their
    previous labels carried through the map), and the weight of the
    created points is reported apart as ``created``."""
    with jax.profiler.TraceAnnotation("repro.migration"):
        prev, new, w, created = previous.labels, labels, weights, 0.0
        if identity is not None:
            kept = identity >= 0
            prev = np.asarray(previous.labels)[identity[kept]]
            new = np.asarray(labels)[kept]
            if weights is None:
                created = float(kept.size - np.count_nonzero(kept))
            else:
                w = np.asarray(weights)[kept]
                created = float(np.sum(np.asarray(weights)[~kept]))
        vol = float(metrics.migration_volume(prev, new, w))
        frac = float(metrics.migration_fraction(prev, new, w))
    return {"volume": vol, "fraction": frac,
            "retained_fraction": 1.0 - frac, "created": created}


def _check_previous(problem: PartitionProblem, previous: PartitionResult,
                    identity) -> np.ndarray | None:
    """Validates ``previous`` (and ``identity``) against ``problem``;
    returns the identity map as int64, or None."""
    if not isinstance(previous, PartitionResult):
        raise TypeError(f"previous must be a PartitionResult, got "
                        f"{type(previous)}")
    if previous.k != problem.k:
        raise ValueError(f"previous partition has k={previous.k}, "
                         f"problem has k={problem.k}")
    n_prev = len(previous.labels)
    if identity is None:
        if n_prev != problem.n:
            raise ValueError(
                f"previous partition labels {n_prev} points, problem has "
                f"n={problem.n} (repartition requires the same point set, "
                "possibly moved or re-weighted, unless identity= maps the "
                "new points to the previous ones)")
        return None
    identity = np.asarray(identity)
    if identity.shape != (problem.n,) or not np.issubdtype(identity.dtype,
                                                           np.integer):
        raise ValueError(
            f"identity must be [n={problem.n}] integers, got "
            f"{identity.dtype} {identity.shape}")
    if problem.n and (identity.min() < -1 or identity.max() >= n_prev):
        raise ValueError(
            f"identity holds indices outside [-1, {n_prev}): each new "
            "point's index in the previous point set, or -1")
    return identity.astype(np.int64, copy=False)


def _warm_geographer(problem: PartitionProblem, previous: PartitionResult,
                     devices: int | None, changed: bool,
                     **opts) -> PartitionResult:
    """Warm-started balanced k-means (+ balance-retry loop): the engine's
    one warm-start implementation, shared by every method whose registry
    entry declares ``supports_warm_start`` (currently the geographer
    family — a new warm-capable algorithm needs its own branch here).

    ``changed``: the point set is not the previous one. The solve then
    resumes from the previous centers and influence alone (they are per
    block and carry over), without no-op detection, over a point count
    padded to its ``warm_slots`` bucket."""
    opts.setdefault("delta_tol", WARM_DELTA_TOL)
    opts["warmup"] = False
    state = WarmState.capture(previous)
    centers, infl = state.centers, state.influence
    prev_labels = None if changed else state.labels
    if devices is None:
        cfg = make_bkm_config(problem, **opts)

        def solve(centers, infl, prev_labels, attempt):
            return _geographer_result(problem, geographer_repartition(
                problem.points, problem.k, centers, infl,
                weights=problem.weights, cfg=cfg, seed=problem.seed,
                prev_labels=prev_labels, attempt=attempt, pad=changed),
                warm=True)
    else:
        def solve(centers, infl, prev_labels, attempt):
            return repartition_sharded(problem, devices, centers, infl,
                                       prev_labels=prev_labels,
                                       attempt=attempt, pad=changed, **opts)
    # the solver balances against the caller's effective epsilon (an
    # opts override wins over the problem's), so the retry check must too
    eps_eff = opts.get("epsilon", problem.epsilon)
    total_iters = 0
    for attempt in range(MAX_BALANCE_RETRIES + 1):
        res = solve(centers, infl, prev_labels, attempt)
        total_iters += res.stats["iters"]
        centers, infl = res.centers, res.influence
        if res.stats["final_imbalance"] <= eps_eff + 1e-6:
            break
        if not changed:
            prev_labels = res.labels
    res.stats.update({"warm_start": True, "iters": total_iters,
                      "balance_retries": attempt})   # re-warm solves run
    return res


def _cold_relabel(problem: PartitionProblem, previous: PartitionResult,
                  method: str, devices: int | None,
                  identity: np.ndarray | None, **opts) -> PartitionResult:
    res = partition(problem, method=method, devices=devices, **opts)
    if previous.centers is not None:
        prev_centers = np.asarray(previous.centers)
    else:
        # the previous blocks' centroids over the persisting points
        pts, prev, w = problem.points, previous.labels, problem.weights
        if identity is not None:
            kept = identity >= 0
            pts = np.asarray(pts)[kept]
            prev = np.asarray(prev)[identity[kept]]
            w = None if w is None else np.asarray(w)[kept]
        prev_centers = weighted_centroids(pts, prev, problem.k, w)
    new_centers = (np.asarray(res.centers) if res.centers is not None else
                   weighted_centroids(problem.points, res.labels,
                                      problem.k, problem.weights))
    mapping = greedy_center_match(new_centers, prev_centers)
    res.labels = mapping[np.asarray(res.labels)]
    # carry centers/influence into the matched id space too
    if res.centers is not None:
        relabeled = np.empty_like(np.asarray(res.centers))
        relabeled[mapping] = np.asarray(res.centers)
        res.centers = relabeled
    if res.influence is not None:
        relabeled = np.empty_like(np.asarray(res.influence))
        relabeled[mapping] = np.asarray(res.influence)
        res.influence = relabeled
    res.stats.update({"warm_start": False, "relabel_matched": True})
    res.stats.setdefault("iters", _stats_iters(res))
    return res


def _stats_iters(res: PartitionResult):
    """Movement-iteration count of a result, or None for methods without
    an iteration loop (sfc/rcb/...)."""
    if "iters" in res.stats:
        return res.stats["iters"]
    for lvl in res.stats.get("levels", []):
        if lvl.get("iters") is not None:
            v = lvl["iters"]
            return int(np.max(v)) if np.ndim(v) else int(v)
    return None


def repartition(problem: PartitionProblem, previous: PartitionResult,
                method: str = "geographer", *,
                identity: np.ndarray | None = None,
                devices: int | None = None, warm: bool | None = None,
                refine=None, refine_eps: float | None = None,
                evaluate: bool = False, with_diameter: bool = False,
                **opts) -> PartitionResult:
    """Repartition ``problem`` starting from ``previous`` — the dynamic
    front door next to ``partition()``.

    Args:
        problem: the perturbed instance — same point count (and point
            identity) as ``previous``, typically with drifted weights
            and/or moved points; or, with ``identity``, a refined or
            coarsened point set of another count.
        previous: the ``PartitionResult`` of the last (re)partition call.
        identity: [problem.n] integers: for each point of ``problem``
            its index in ``previous``'s point set, or -1 for a point
            that refinement created (None = the same point set). With a
            map, the warm solve resumes from the previous centers and
            influence, never re-emits the previous labels (no-op
            detection is off), and pads its point count to a bucket
            (``core.partitioner.warm_slots``) so that steps of similar
            size share one compiled solve.
        method: registry name. Methods with ``supports_warm_start`` (see
            ``warm_start_methods()``) resume balanced k-means from
            ``previous.centers`` / ``previous.influence``; all others cold
            start and are relabel-matched to ``previous`` by greedy center
            correspondence.
        devices: run the solve on the sharded multi-device path (the
            previous centers/influence are replicated, communication stays
            psum-only; ``devices=1`` is bit-for-bit the single-device
            path).
        warm: force (True) or forbid (False) warm starting; None picks
            warm whenever the method supports it and ``previous`` carries
            centers. ``warm=False`` with a warm-capable method is the
            fair "cold restart" baseline: same algorithm, fresh SFC
            bootstrap, relabel-matched.
        refine: quality-recovery post-pass applied AFTER the warm (or
            cold-relabeled) solve and BEFORE migration accounting — True
            (= ``"label_prop"``) or a refiner registry name; runs over
            ``devices`` shards when set. Migration is then measured on
            the refined labels, since those are what the simulation
            actually redistributes to.
        refine_eps: balance slack for the refinement budgets (None =
            ``problem.epsilon``); only meaningful with ``refine``.
        evaluate: fill ``result.quality`` with the paper metric set.
        with_diameter: include block diameters in the evaluation.
        **opts: forwarded to the algorithm (BKMConfig fields for
            geographer; warm solves default ``delta_tol`` to
            ``WARM_DELTA_TOL`` and force ``warmup=False``).

    Returns:
        PartitionResult whose ``stats`` additionally carry
        ``stats["warm_start"]``, ``stats["iters"]`` (cumulative movement
        iterations; 0 when ``previous`` is still a fixed point) and
        ``stats["migration"]`` = {"volume", "fraction",
        "retained_fraction", "created"} measured against ``previous``
        under the NEW weights: with ``identity``, volume and fraction
        are over the persisting points (the fraction of their weight
        that changed blocks), and ``created`` is the weight of the
        created points (0.0 without a map).

    Raises:
        ValueError: k mismatch with ``previous``, an n mismatch without
            ``identity``, an ``identity`` of the wrong shape or with an
            index outside ``[-1, previous n)``, or ``warm=True`` for a
            method without warm-start support / a previous result
            without centers.
    """
    if not isinstance(problem, PartitionProblem):
        raise TypeError(
            f"repartition() takes a PartitionProblem, got {type(problem)}")
    with jax.profiler.TraceAnnotation("repro.repartition", method=method,
                                      n=problem.n, k=problem.k,
                                      call=next(_CALLS)):
        identity = _check_previous(problem, previous, identity)
        name = resolve_method(method)
        can_warm = supports_warm_start(name) and previous.centers is not None
        if warm is None:
            warm = can_warm
        elif warm and not supports_warm_start(name):
            raise ValueError(
                f"method {name!r} has no warm-start path; warm=True is "
                "supported by methods registered with supports_warm_start")
        elif warm and previous.centers is None:
            raise ValueError(
                "previous result carries no centers to warm-start from "
                "(was it produced by a center-based method?)")

        if refine is not None and refine is not False:
            from .refine import resolve_refiner
            refine = resolve_refiner(refine)   # fail fast, before the solve
        else:
            refine = None
        if warm:
            res = _warm_geographer(problem, previous, devices,
                                   identity is not None, **opts)
        else:
            res = _cold_relabel(problem, previous, name, devices, identity,
                                **opts)
        if refine is not None:
            from .refine import refine as _refine
            res = _refine(problem, res, refine, devices=devices,
                          eps=refine_eps)
        res.stats["migration"] = _migration_stats(previous, res.labels,
                                                  problem.weights, identity)
        if evaluate:
            res.evaluate(with_diameter=with_diameter)
        return res
