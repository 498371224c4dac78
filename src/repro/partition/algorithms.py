"""Registry adapters for the five flat partitioners.

Each adapter maps the unified ``PartitionProblem`` onto the underlying
implementation's native signature and wraps the output in a
``PartitionResult``:

* ``geographer``        — SFC bootstrap + balanced k-means (the paper).
* ``sfc``  (alias hsfc) — Hilbert-curve chunking.
* ``rcb``               — recursive coordinate bisection.
* ``rib``               — recursive inertial bisection.
* ``multijagged`` (mj)  — one-shot multisection.

``**opts`` for ``geographer`` are forwarded into ``BKMConfig`` (epsilon is
taken from the problem unless overridden), so callers can tune
``max_iter`` / ``backend`` / ``warmup`` per call without touching the
problem object.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core import baselines
from repro.core.balanced_kmeans import BKMConfig
from repro.core.partitioner import geographer_partition

from .problem import PartitionProblem, PartitionResult
from .registry import register_algorithm

_BKM_FIELDS = {f.name for f in dataclasses.fields(BKMConfig)}


def make_bkm_config(problem: PartitionProblem, k: int | None = None,
                    **opts) -> BKMConfig:
    """BKMConfig for ``problem`` with per-call overrides (unknown keys are
    rejected so typos don't silently fall back to defaults)."""
    bad = set(opts) - _BKM_FIELDS
    if bad:
        raise TypeError(f"unknown BKMConfig options {sorted(bad)}")
    kw = {"k": k if k is not None else problem.k,
          "epsilon": problem.epsilon, **opts}
    return BKMConfig(**kw)


@register_algorithm("geographer", aliases=("balanced_kmeans", "bkm"),
                    supports_devices=True, supports_warm_start=True)
def _geographer(problem: PartitionProblem,
                devices: int | tuple[int, int] | None = None,
                bootstrap: str | None = None, chunk: int | None = None,
                **opts) -> PartitionResult:
    if devices is not None:
        from .distributed import partition_sharded
        return partition_sharded(problem, devices,
                                 bootstrap=bootstrap or "host",
                                 chunk=chunk, **opts)
    if bootstrap is not None:
        raise TypeError("bootstrap= only applies to the multi-device path "
                        "(pass devices=)")
    if chunk is not None:
        raise TypeError("chunk= streams the sharded deal and only applies "
                        "to the multi-device path (pass devices=)")
    cfg = make_bkm_config(problem, **opts)
    return _geographer_result(problem, geographer_partition(
        problem.points, problem.k, weights=problem.weights, cfg=cfg,
        seed=problem.seed, return_state=True))


def _geographer_result(problem: PartitionProblem, solved, *,
                       warm: bool = False, **extra) -> PartitionResult:
    """The ``PartitionResult`` of every balanced-k-means route. ``solved``
    is what a layout's ``_stage_solve_fetch`` returns (labels, centers,
    influence, stats); a ``warm`` solve adds its ``iters`` and
    ``warm_start``, and ``extra`` further stats keys (the sharded routes'
    ``devices`` and ``bootstrap``)."""
    labels, centers, infl, stats = solved
    summary = {"levels": [dict(stats)],
               "final_imbalance": float(stats["final_imbalance"])}
    if warm:
        summary.update(iters=int(stats["iters"]), warm_start=True)
    # centers/influence ride on the result so repartition() can warm-start
    # the next solve from this one (DESIGN.md §8)
    return PartitionResult(
        labels=np.asarray(labels, np.int64), k=problem.k,
        method="geographer", problem=problem, centers=centers,
        influence=infl, stats={**summary, **extra})


def _baseline_result(problem, labels, method) -> PartitionResult:
    labels = np.asarray(labels, np.int64)
    res = PartitionResult(labels=labels, k=problem.k, method=method,
                          problem=problem)
    res.stats = {"levels": [{}],
                 "final_imbalance": res.imbalance()}
    return res


@register_algorithm("sfc", aliases=("hsfc", "hilbert"),
                    supports_devices=False, supports_warm_start=False)
def _sfc(problem: PartitionProblem, **opts) -> PartitionResult:
    if opts:
        raise TypeError(f"sfc takes no options, got {sorted(opts)}")
    labels = baselines.sfc_partition(problem.points, problem.k,
                                     problem.weights)
    return _baseline_result(problem, labels, "sfc")


@register_algorithm("rcb", supports_devices=False,
                    supports_warm_start=False)
def _rcb(problem: PartitionProblem, **opts) -> PartitionResult:
    labels = baselines.rcb(problem.points, problem.k, problem.weights,
                           **opts)
    return _baseline_result(problem, labels, "rcb")


@register_algorithm("rib", supports_devices=False,
                    supports_warm_start=False)
def _rib(problem: PartitionProblem, **opts) -> PartitionResult:
    if opts:
        raise TypeError(f"rib takes no options, got {sorted(opts)}")
    labels = baselines.rib(problem.points, problem.k, problem.weights)
    return _baseline_result(problem, labels, "rib")


@register_algorithm("multijagged", aliases=("mj",),
                    supports_devices=False, supports_warm_start=False)
def _multijagged(problem: PartitionProblem, **opts) -> PartitionResult:
    if opts:
        raise TypeError(f"multijagged takes no options, got {sorted(opts)}")
    labels = baselines.multijagged(problem.points, problem.k,
                                   problem.weights)
    return _baseline_result(problem, labels, "multijagged")
