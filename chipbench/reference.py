"""The plain reference that decides ``correct``.

A partition is judged by what it says. ``partition()`` returns labels in
the original point order together with the final centers and
influences of balanced k-means, and claims:

* every label lies in ``[0, k)`` (``out_of_range``, limit 0);
* every block weighs at most ``(1 + epsilon) * W / k`` (``imbalance``,
  limit epsilon, the guarantee the configuration states);
* every point sits in the block whose center is nearest by effective
  distance ``|p - c|^2 / influence_c^2`` (the weighted Voronoi cells
  that make the partition geometric, paper Alg. 1). ``assign_gap`` is
  the widest amount by which a point's own block lies above its best
  block, in that squared effective distance over the unit cube;
* every center is where the movement phase put it: the weighted
  centroid of its block (paper Alg. 2). ``center_gap`` is the widest
  distance from a returned center to the centroid of its block, which
  the reference works out from the points and the labels alone, over
  that block's root-mean-square radius. A solve that moves its centers
  too few times, or not at all, leaves them off their centroids.

Everything here is float64 numpy on inputs the benchmark made itself
(points, weights) and on the answer (labels, centers, influence). It
imports nothing of the program.
"""
from __future__ import annotations

import math

import numpy as np

#: point-center pairs one full ``assign_gap`` check may cost; calls past
#: that budget are checked on a sample of ``SAMPLE_POINTS`` points
PAIR_BUDGET = 1 << 28
SAMPLE_POINTS = 4096
_BLOCK_PAIRS = 1 << 23


def imbalance(labels: np.ndarray, k: int,
              weights: np.ndarray | None = None) -> float:
    """``max block weight / (W / k) - 1``, unit weights when None."""
    if weights is None:
        sizes = np.bincount(labels, minlength=k).astype(np.float64)
        target = labels.shape[0] / k
    else:
        sizes = np.bincount(labels, weights=weights, minlength=k)
        target = float(np.sum(weights)) / k
    return float(sizes.max() / target - 1.0)


def out_of_range(labels: np.ndarray, n: int, k: int) -> int:
    """Labels that are not a block id, plus every missing or extra
    label."""
    labels = np.asarray(labels)
    if labels.ndim != 1:
        return n
    bad = int(np.sum((labels < 0) | (labels >= k)))
    return bad + abs(labels.shape[0] - n)


def assign_gap(points: np.ndarray, labels: np.ndarray,
               centers: np.ndarray, influence: np.ndarray,
               rows: np.ndarray | None = None) -> float:
    """Widest ``eff(own block) - eff(best block)`` over ``rows`` (all
    points when None), with ``eff(c) = |p - c|^2 / influence_c^2`` in
    float64.

    The best block comes from one matrix product per block of points:
    ``eff(c) = [p, |p|^2, 1] . [-2 c / I_c^2, 1 / I_c^2, |c|^2 / I_c^2]``,
    whose cancellation error in float64 is ~1e-16 over the unit cube; the
    own block's distance is taken directly. Blocks keep the ``[rows, k]``
    scratch bounded."""
    pts = np.asarray(points, np.float64)
    lab = np.asarray(labels).astype(np.int64)
    if rows is not None:
        pts, lab = pts[rows], lab[rows]
    c = np.asarray(centers, np.float64)
    inv2 = 1.0 / np.asarray(influence, np.float64) ** 2
    caug = np.concatenate([-2.0 * c.T * inv2, inv2[None, :],
                           (np.sum(c * c, axis=1) * inv2)[None, :]])
    step = max(1, _BLOCK_PAIRS // c.shape[0])
    worst = 0.0
    for s in range(0, pts.shape[0], step):
        p = pts[s:s + step]
        lb = lab[s:s + step]
        paug = np.concatenate([p, np.sum(p * p, axis=1, keepdims=True),
                               np.ones((p.shape[0], 1))], axis=1)
        best = np.min(paug @ caug, axis=1)
        own = np.sum((p - c[lb]) ** 2, axis=1) * inv2[lb]
        worst = max(worst, float(np.max(own - best)))
    return worst


def center_gap(points: np.ndarray, labels: np.ndarray,
               centers: np.ndarray,
               weights: np.ndarray | None = None) -> float:
    """Widest ``|c_j - g_j| / r_j`` over the blocks ``j``, where ``g_j`` is
    the weighted centroid of the points labelled ``j`` and ``r_j`` their
    root-mean-square distance from it, all in float64. An empty block, or
    one whose points all coincide, reads infinite."""
    pts = np.asarray(points, np.float64)
    lab = np.asarray(labels).astype(np.int64)
    c = np.asarray(centers, np.float64)
    k = c.shape[0]
    w = (np.ones(pts.shape[0]) if weights is None
         else np.asarray(weights, np.float64))
    mass = np.bincount(lab, weights=w, minlength=k)
    if np.any(mass <= 0):
        return math.inf
    g = np.stack([np.bincount(lab, weights=w * pts[:, j], minlength=k)
                  for j in range(pts.shape[1])], axis=1) / mass[:, None]
    r2 = np.bincount(lab, weights=w * np.sum((pts - g[lab]) ** 2, axis=1),
                     minlength=k) / mass
    if np.any(r2 <= 0):
        return math.inf
    return float(np.max(np.sqrt(np.sum((c - g) ** 2, axis=1) / r2)))


def check_calls(calls, k: int, seed, limits: dict) -> dict:
    """Judge every call of a run against ``limits`` (one per reading).

    ``calls`` holds, per call, ``points``, ``weights`` (or None),
    ``labels``, ``centers`` and ``influence``. Range, balance and
    ``center_gap`` are checked on every point of every call;
    ``assign_gap`` on every point of a sample of calls drawn from
    ``seed`` (as many as ``PAIR_BUDGET`` allows, at least one) and on
    ``SAMPLE_POINTS`` points drawn from ``seed`` of each other call.

    Returns the readings: ``out_of_range`` (count), ``imbalance`` (the
    largest over calls), ``assign_gap`` and ``center_gap`` (the widest),
    the calls that broke a limit, and how many calls were checked in
    full.
    """
    rng = np.random.default_rng(seed)
    n_calls = len(calls)
    if not n_calls:
        return {"out_of_range": None, "imbalance": None,
                "assign_gap": None, "center_gap": None, "failed_calls": 0,
                "calls_checked_in_full": 0}
    n = calls[0]["points"].shape[0]
    n_full = int(min(n_calls, max(1, PAIR_BUDGET // (n * k))))
    full = set(rng.choice(n_calls, size=n_full, replace=False).tolist())
    worst = {"out_of_range": 0, "imbalance": -1.0, "assign_gap": 0.0,
             "center_gap": 0.0}
    failed = 0
    for i, call in enumerate(calls):
        labels = np.asarray(call["labels"])
        got = {"out_of_range": out_of_range(labels, n, k)}
        if not got["out_of_range"]:
            got["imbalance"] = imbalance(labels, k, call["weights"])
            rows = (None if i in full else
                    rng.choice(n, size=min(SAMPLE_POINTS, n),
                               replace=False))
            got["assign_gap"] = assign_gap(
                call["points"], labels, call["centers"], call["influence"],
                rows)
            got["center_gap"] = center_gap(call["points"], labels,
                                           call["centers"], call["weights"])
        worst["out_of_range"] += got["out_of_range"]
        for name in ("imbalance", "assign_gap", "center_gap"):
            if name in got:
                worst[name] = max(worst[name], got[name])
        failed += any(got[name] > limits[name] for name in got)
    return {**worst, "failed_calls": failed,
            "calls_checked_in_full": n_full}
