"""``partition()`` — the single front door for all partitioning.

    from repro.partition import PartitionProblem, partition

    prob = PartitionProblem.from_mesh(mesh, k=64, epsilon=0.03)
    res = partition(prob, method="geographer")          # flat
    res = partition(prob, method="rcb")                 # any registry name
    res = partition(prob, hierarchy=(8, 8))             # k = 8 x 8 blocks
    res = partition(prob, devices=8)                    # sharded SPMD run
    res.labels, res.imbalance(), res.evaluate()

``hierarchy`` accepts a (k1, k2) tuple or a "k1xk2" string; it routes
through ``hierarchical_partition`` with ``method`` as the coarse cut and
``refine_method`` (default geographer, batched vmap) as the per-block
refinement.

``devices=P`` runs the method's multi-device shard_map path over P
devices (points sharded, centers replicated, psum-only communication —
see partition/distributed.py). Only methods registered with
``supports_devices`` accept it; with ``hierarchy`` the coarse cut runs
distributed and the refinement stays a host-side batched vmap.

``devices=(P1, P2)`` lays out the 2-D hierarchical device mesh instead
(``dist.rules.partition_mesh2d``): the coarse cut shards its points over
the *product* of the ("coarse", "refine") axes — bit-identical to the
flat ``devices=P1*P2`` run — and with ``hierarchy`` the k1 refinements
batch over the refine axis. ``chunk=N`` (a ``**opts`` pass-through to
the geographer adapter) streams the sharded deal in bounded host slices
without changing any result bit.
"""
from __future__ import annotations

import itertools

import jax

from .hierarchical import hierarchical_partition
from .problem import PartitionProblem, PartitionResult
from .registry import (distributed_methods, get_algorithm, resolve_method,
                       supports_devices)


def _parse_hierarchy(hierarchy) -> tuple[int, int]:
    if isinstance(hierarchy, str):
        parts = hierarchy.lower().split("x")
        if len(parts) != 2:
            raise ValueError(f"hierarchy string must be 'k1xk2', "
                             f"got {hierarchy!r}")
        return int(parts[0]), int(parts[1])
    k1, k2 = hierarchy
    return int(k1), int(k2)


#: per-process index of front-door calls, the ``call`` of their trace span
_CALLS = itertools.count()


def partition(problem: PartitionProblem, method: str = "geographer", *,
              hierarchy=None, devices: int | tuple[int, int] | None = None,
              refine=None, refine_eps: float | None = None,
              evaluate: bool = False,
              with_diameter: bool = False, **opts) -> PartitionResult:
    """Partition ``problem`` with ``method`` (a registry name).

    Args:
        problem: the ``PartitionProblem`` to cut into ``problem.k``
            balanced blocks.
        method: registry name (``available_methods()``); aliases resolve,
            unknown names raise ``UnknownMethodError``.
        hierarchy: ``(k1, k2)`` tuple or ``"k1xk2"`` string — switches to
            two-level recursive partitioning with ``k1*k2 == problem.k``.
        devices: run the sharded multi-device path over P devices (method
            must be registered with ``supports_devices``; with
            ``hierarchy``, the coarse cut is the distributed pass). A
            ``(P1, P2)`` tuple uses the 2-D hierarchical mesh: the
            coarse/flat solve is bit-identical to ``devices=P1*P2`` and
            hierarchical refinement batches over the refine axis.
        refine: quality-recovery post-pass over the solver's labels —
            True (= ``"label_prop"``) or a refiner registry name (see
            ``repro.partition.refine``). Requires the problem to carry a
            CSR graph; runs sharded over ``devices`` when set (bit-for-
            bit equal to the host reference), and the returned result's
            ``method`` gains the refiner suffix (e.g. ``"sfc+lp"``).
        refine_eps: balance slack for the refinement budgets (None =
            ``problem.epsilon``); only meaningful with ``refine``.
        evaluate: fill ``result.quality`` with the paper's metric set
            (graph metrics require the problem to carry a CSR graph).
        with_diameter: include per-block diameters in the evaluation.
        **opts: forwarded to the algorithm — BKMConfig fields for
            geographer, or ``refine_method`` / ``batched`` /
            ``coarse_epsilon`` in hierarchical mode; unknown options
            raise ``TypeError``.

    Returns:
        A ``PartitionResult`` (labels in original point order, optional
        centers/influence warm-start state, per-level ``stats``).

    For incremental re-solves against a previous result, see
    ``repartition()``.
    """
    if not isinstance(problem, PartitionProblem):
        raise TypeError(
            f"partition() takes a PartitionProblem, got {type(problem)}; "
            "wrap raw arrays with PartitionProblem(points=..., k=...)")
    with jax.profiler.TraceAnnotation("repro.partition", method=method,
                                      n=problem.n, k=problem.k,
                                      call=next(_CALLS)):
        resolve_method(method)                 # fail fast on unknown names
        if devices is not None and not supports_devices(method):
            raise ValueError(
                f"method {method!r} has no multi-device path; devices= is "
                f"supported by: {distributed_methods()}")
        if refine is not None and refine is not False:
            from .refine import resolve_refiner
            refine = resolve_refiner(refine)   # fail fast, before the solve
        else:
            refine = None
        if hierarchy is not None:
            k1, k2 = _parse_hierarchy(hierarchy)
            result = hierarchical_partition(problem, k1, k2, method=method,
                                            devices=devices, **opts)
        else:
            if devices is not None:
                opts["devices"] = devices
            result = get_algorithm(method)(problem, **opts)
        if refine is not None:
            from .refine import refine as _refine
            result = _refine(problem, result, refine, devices=devices,
                             eps=refine_eps)
        if evaluate:
            result.evaluate(with_diameter=with_diameter)
        return result
