"""Sharded multi-device balanced k-means — `partition(..., devices=P)`.

The paper's scalability story (§4.1) is that every step of Algorithms 1+2
communicates only *global vector sums* over per-process partials: cluster
sizes [k], weighted coordinate sums [k, d], weighted counts [k], and the
bounding box [d]. This module is the actual SPMD driver for that claim:

* ``ShardedPartitionProblem`` — a static-shape sharded view of a
  ``PartitionProblem``: points/weights split round-robin over P devices
  and padded to a common per-device ``cap`` (padding replicates real
  points at weight zero, so it perturbs no weighted sum and no bbox).
  The deal preserves the source dtype (a float32 problem never takes a
  float64 host copy), streams in bounded slot chunks (``chunk=``), and
  can *placement-commit* each shard straight to its device
  (``commit=True``) so the host never holds a full dealt copy of the
  coordinates — peak host staging is O(n/P + chunk) beyond the index
  arrays.
* ``partition_sharded`` — lays the shards on a 1-D device mesh
  (``dist.rules.partition_mesh``), replicates centers/influence, and runs
  ``core.balanced_kmeans`` under ``shard_map`` with ``axis_name`` plumbed
  end-to-end, so every ``_reduce`` in the core becomes a ``psum`` /
  ``pmin`` / ``pmax`` — the paper's communication structure, nothing else.
* ``repartition_sharded`` — the warm-started twin of ``partition_sharded``
  (the ``devices=`` path of ``repartition()``): the previous centers and
  influence are replicated like any centers, so warm starting adds no
  collective.
* ``devices=(P1, P2)`` — the same solve on the 2-D hierarchical mesh
  (``dist.rules.partition_mesh2d``): points shard over the *product* of
  the ``("coarse", "refine")`` axes, every reduction psums over the axis
  tuple. The flattened device order equals the 1-D mesh's, so the run is
  bit-identical to ``devices=P1*P2`` — this is what lets the hierarchical
  engine (partition/hierarchical.py) keep its coarse cut exact while the
  k1 refinements batch over the refine axis alone.

SFC bootstrap (paper Alg. 2 lines 4-7) comes in two flavours:

* ``bootstrap="host"`` (default) — ``core.sfc.sfc_initial_centers`` on the
  gathered points, byte-identical to the single-device path. This is what
  makes the agreement guarantee below possible.
* ``bootstrap="device"`` — fully in-graph distributed bootstrap
  (``core.sfc.sfc_initial_centers_sharded``): per-shard Hilbert keys
  against the psum'd global bbox + global weighted-prefix-sum splitting
  over a psum'd key histogram. O(1)-sized communication, but 30-bit keys
  (vs 62-bit host keys), so centers may differ from the host bootstrap.
  This is also the *out-of-core* bootstrap: no O(n) float64 host copy.

Agreement with the single-device path (tested in
tests/test_sharded_partition.py, documented in DESIGN.md §3b):

* ``devices=1`` is *bit-for-bit identical* to
  ``partition(problem, method="geographer")``: the round-robin layout with
  P=1 is the identity on the permuted order and every psum over a 1-device
  axis is the identity.
* ``devices=P>1`` with ``warmup=False`` differs only by float reduction
  order (per-shard partial sums + psum vs one global ``segment_sum``):
  >= 97% identical labels (100% in most measured configs), asserted by
  the tests.
* ``devices=P>1`` with warm-up (the default) additionally samples a
  per-shard prefix that differs from the global prefix by up to P-1
  points per round; on small problems that can steer k-means to a
  *different but equally balanced* local optimum, so only the imbalance
  bound and block coverage are guaranteed, not label agreement.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.balanced_kmeans import BKMConfig, balanced_kmeans
from repro.core.partitioner import warm_slots
from repro.core.sfc import sfc_initial_centers, sfc_initial_centers_sharded
from repro.dist.rules import (COARSE_AXIS, PARTITION_AXIS, REFINE_AXIS,
                              partition_mesh, partition_mesh2d, shard_map)
from repro.kernels.ops import backend_supports_moments, resolve_assign_backend

from .algorithms import _geographer_result, make_bkm_config
from .problem import PartitionProblem, PartitionResult

BOOTSTRAPS = ("host", "device")

#: largest per-shard slot index the traced int32 index/label math can
#: address (core.balanced_kmeans iotas, the assign kernels' index math)
INT32_INDEX_CAP = np.iinfo(np.int32).max


def _device_shape(devices) -> tuple[int, ...]:
    """Normalize ``devices`` (int or (P1, P2) tuple) to a mesh shape."""
    if isinstance(devices, (tuple, list)):
        shape = tuple(int(d) for d in devices)
        if len(shape) != 2:
            raise ValueError(
                f"devices tuple must be (P1, P2), got {devices!r}")
        if min(shape) < 1:
            raise ValueError(f"devices must be >= 1, got {devices!r}")
        return shape
    P = int(devices)
    if P < 1:
        raise ValueError(f"devices must be >= 1, got {devices}")
    return (P,)


def _devices_stat(devices):
    """JSON-friendly devices value for stats dicts (tuple -> list)."""
    return list(devices) if isinstance(devices, (tuple, list)) \
        else int(devices)


def check_index_capacity(n: int, devices) -> int:
    """Validate that the per-shard slot count fits the traced index dtype.

    The round-robin layout gives every shard ``cap = ceil(n / P)`` slots.
    Host-side global-position arithmetic is explicit int64 throughout
    (``gather`` / ``scatter_labels`` address all n points), but the traced
    per-shard index math — the warm-up iota in ``core.balanced_kmeans``
    and the assign kernels' slot indexing — is int32 by kernel contract,
    so ``cap`` must stay <= 2**31 - 1. Spreading the points over more
    devices shrinks ``cap``, so the capacity grows with P (up to
    ~2.1 billion points *per shard*).

    Args:
        n: global point count.
        devices: shard count P, or a (P1, P2) mesh tuple.

    Returns:
        cap — the per-shard slot count ``ceil(n / P)``.

    Raises:
        ValueError: ``cap`` exceeds the int32 index capacity (names n,
            P, cap, and the limit).
    """
    P = int(np.prod(_device_shape(devices)))
    cap = -(-int(n) // P)                  # ceil(n / P)
    if cap > INT32_INDEX_CAP:
        raise ValueError(
            f"per-shard slot count cap=ceil(n/P)={cap} overflows the "
            f"int32 traced index capacity ({INT32_INDEX_CAP}) at "
            f"n={n}, devices={P}; shard over more devices so that "
            f"ceil(n/P) <= {INT32_INDEX_CAP}")
    return cap


def _mesh_for_shape(shape: tuple[int, ...]):
    """The device mesh matching a ``_device_shape`` result."""
    if len(shape) == 1:
        return partition_mesh(shape[0])
    return partition_mesh2d(*shape)


def _mesh_spec(mesh):
    """PartitionSpec sharding dim 0 over every axis of ``mesh``."""
    from jax.sharding import PartitionSpec as P
    names = mesh.axis_names
    return P(names[0] if len(names) == 1 else names)


@dataclass(frozen=True)
class ShardedPartitionProblem:
    """Static-shape sharded view of a ``PartitionProblem``.

    Layout: the points are first permuted with the problem's seed (the
    same permutation the single-device path uses for warm-up sampling),
    then dealt *round-robin* — permuted position g lives at shard g % P,
    slot g // P. A shard's slot prefix therefore tracks the global
    permutation prefix to within P-1 points, which keeps the warm-up
    sample semantics of ``core.balanced_kmeans`` (per-shard prefix masks)
    aligned with the single-device run.

    Slots past n (when P does not divide n) wrap around to real points at
    weight zero: they influence neither weighted sums nor the (psum'd)
    bounding box, and their labels are discarded on scatter-back.

    Attributes:
        problem: the source ``PartitionProblem``.
        devices: flat shard count P (the product, for a 2-D mesh — the
            layout depends only on P, never on the mesh factorization).
        points: [P, cap, d] — shard-major dealt coordinates in the
            *source* floating dtype (integer sources promote to float64;
            there is no silent float64 up-cast of float32 problems). A
            committed view (``commit=True``) holds a mesh-sharded
            ``jax.Array`` here instead of host numpy.
        weights: [P, cap] — dealt weights in the source floating dtype;
            exactly 0 marks a padded slot (the weight also carries the
            validity signal into the jitted core, which treats ``w > 0``
            as "real"). Committed views hold a ``jax.Array``.
        gather: [P, cap] int64 — original point id of every slot
            (``labels[gather[valid]]`` scatters shard labels home).
        valid: [P, cap] bool — False for padded slots.
    """
    problem: PartitionProblem
    devices: int
    points: np.ndarray
    weights: np.ndarray
    gather: np.ndarray
    valid: np.ndarray

    @property
    def cap(self) -> int:
        """Per-shard slot count, ``ceil(n / P)`` unless the deal was
        given a larger one."""
        return self.points.shape[1]

    @classmethod
    def from_problem(cls, problem: PartitionProblem, devices, *,
                     chunk: int | None = None, commit: bool = False,
                     dtype=None, mesh=None,
                     cap: int | None = None) -> "ShardedPartitionProblem":
        """Deal ``problem`` onto ``devices`` shards.

        The deal streams in bounded slot slices: each slice gathers
        ``P * min(chunk, cap)`` permuted points, so transient host
        staging is O(P * chunk) on top of the output arrays
        (``chunk=None`` = one-shot, a single full-cap slice — bit-
        identical to any chunked setting). With ``commit=True`` the
        dealt coordinates/weights go straight to their devices shard by
        shard and the host never holds the full [P, cap, d] copy: peak
        host staging drops to O(n/P + chunk) beyond the int64 ``gather``
        index (which stays on the host for ``scatter_labels``).

        Args:
            problem: the instance to shard; its seed fixes the
                permutation so re-sharding is deterministic.
            devices: shard count P with ``1 <= P <= problem.n``, or a
                (P1, P2) 2-D mesh shape (the layout only depends on the
                product).
            chunk: per-shard slots gathered per host slice (None = all).
            commit: placement-commit each shard's points/weights to its
                device (requires P <= visible jax devices); ``points`` /
                ``weights`` become mesh-sharded ``jax.Array``s.
            dtype: target dtype for committed arrays (None = the source
                dtype; commit respects jax's x64 setting).
            mesh: device mesh for ``commit`` (None = the 1-D or 2-D
                partition mesh implied by ``devices``).
            cap: per-shard slots (None = ``ceil(n / P)``); the slots
                past the points are padding.

        Returns:
            The static-shape sharded view.

        Raises:
            ValueError: P < 1, P > n, ``cap`` below ``ceil(n / P)``, or
                an int32 index-capacity overflow
                (``check_index_capacity``).
        """
        shape = _device_shape(devices)
        P = int(np.prod(shape))
        n = problem.n
        if P > n:
            raise ValueError(f"devices={P} exceeds n={n} points")
        least = check_index_capacity(n, P)
        if cap is None:
            cap = least
        elif not least <= cap <= INT32_INDEX_CAP:
            raise ValueError(f"cap={cap} must lie in [ceil(n/P)={least}, "
                             f"{INT32_INDEX_CAP}]")
        rng = np.random.default_rng(problem.seed)
        perm = rng.permutation(n)
        src = np.asarray(problem.points)
        pdtype = (src.dtype if np.issubdtype(src.dtype, np.floating)
                  else np.dtype(np.float64))
        if problem.weights is None:
            w = np.ones(n, pdtype)
        else:
            w = np.asarray(problem.weights)
            if not np.issubdtype(w.dtype, np.floating):
                w = np.asarray(w, np.float64)
        dim = src.shape[1]
        step = cap if chunk is None else max(1, min(int(chunk), cap))
        gather = np.empty((P, cap), np.int64)
        valid = np.empty((P, cap), bool)

        if not commit:
            pts = np.empty((P, cap, dim), pdtype)
            wts = np.empty((P, cap), w.dtype)
            for s0 in range(0, cap, step):
                s1 = min(s0 + step, cap)
                # global positions of slot columns [s0, s1): g[p, j] =
                # (s0+j)*P + p — explicit int64 so the position space
                # P*cap never overflows a platform-default int32 arange
                g = np.arange(s0 * P, s1 * P,
                              dtype=np.int64).reshape(s1 - s0, P).T
                v = g < n
                gth = perm[g % n]
                gather[:, s0:s1] = gth
                valid[:, s0:s1] = v
                pts[:, s0:s1] = src[gth]
                wts[:, s0:s1] = np.where(v, w[gth], 0)
            return cls(problem=problem, devices=P, points=pts,
                       weights=wts, gather=gather, valid=valid)

        # placement-commit path: build one shard at a time (O(cap) host
        # staging), convert to the target dtype slice by slice, and push
        # it to its device before touching the next shard
        from jax.sharding import NamedSharding
        mesh = mesh if mesh is not None else _mesh_for_shape(shape)
        odtype = np.dtype(dtype) if dtype is not None else pdtype
        sharding = NamedSharding(mesh, _mesh_spec(mesh))
        devs = mesh.devices.reshape(-1)
        ppieces, wpieces = [], []
        for p in range(P):
            pbuf = np.empty((1, cap, dim), odtype)
            wbuf = np.empty((1, cap), odtype)
            for s0 in range(0, cap, step):
                s1 = min(s0 + step, cap)
                g = np.arange(s0, s1, dtype=np.int64) * P + p
                v = g < n
                gth = perm[g % n]
                gather[p, s0:s1] = gth
                valid[p, s0:s1] = v
                pbuf[0, s0:s1] = src[gth]
                wbuf[0, s0:s1] = np.where(v, w[gth], 0)
            ppieces.append(jax.device_put(pbuf, devs[p]))
            wpieces.append(jax.device_put(wbuf, devs[p]))
        pts = jax.make_array_from_single_device_arrays(
            (P, cap, dim), sharding, ppieces)
        wts = jax.make_array_from_single_device_arrays(
            (P, cap), sharding, wpieces)
        return cls(problem=problem, devices=P, points=pts, weights=wts,
                   gather=gather, valid=valid)

    def deal(self, values: np.ndarray,
             chunk: int | None = None) -> np.ndarray:
        """Deal a per-point host array onto the shard layout.

        The inverse direction of ``scatter_labels``: original-point-order
        values land at their round-robin slot (padded slots replicate the
        aliased real point's value, consistent with the coordinate
        padding).

        Args:
            values: [n, ...] array in original point order.
            chunk: per-shard slots gathered per slice (None = one shot);
                bit-identical to the one-shot gather for every setting.

        Returns:
            [P, cap, ...] dealt array (source dtype preserved).
        """
        values = np.asarray(values)
        if chunk is None:
            return values[self.gather]
        out = np.empty(self.gather.shape + values.shape[1:], values.dtype)
        step = max(1, min(int(chunk), self.cap))
        for s0 in range(0, self.cap, step):
            s1 = min(s0 + step, self.cap)
            out[:, s0:s1] = values[self.gather[:, s0:s1]]
        return out

    def scatter_labels(self, A: np.ndarray,
                       chunk: int | None = None) -> np.ndarray:
        """Scatter shard labels back home.

        Args:
            A: [P, cap] per-shard labels.
            chunk: per-shard slots scattered per slice (None = one shot).
                Every valid slot addresses a distinct original id, so the
                chunked scatter is bit-identical to the one-shot write.

        Returns:
            [n] int64 labels in original point order (padded slots
            dropped).
        """
        A = np.asarray(A)
        labels = np.empty(self.problem.n, np.int64)
        step = self.cap if chunk is None else max(1, min(int(chunk),
                                                         self.cap))
        for s0 in range(0, self.cap, step):
            s1 = min(s0 + step, self.cap)
            v = self.valid[:, s0:s1]
            labels[self.gather[:, s0:s1][v]] = A[:, s0:s1][v]
        return labels


@functools.lru_cache(maxsize=64)
def _build_runner(devices, cap: int, dim: int, cfg: BKMConfig,
                  bootstrap: str, n_global: int | None):
    """Compile-cached shard_map driver for one (mesh, shapes, cfg) combo.

    ``devices`` is an int (1-D ``PARTITION_AXIS`` mesh) or a (P1, P2)
    tuple (2-D ``(COARSE_AXIS, REFINE_AXIS)`` mesh): the points shard
    over the axis *product* and every reduction inside the core psums
    over the axis tuple, so the 2-D run is bit-identical to the flat
    P1*P2 run (same flattened device order, same partial-sum placement).

    ``bootstrap`` selects center seeding: "host" (centers0 computed on the
    host, passed in replicated), "device" (in-graph distributed SFC
    bootstrap; centers0 input ignored), or "warm" (centers0 AND influence0
    are the replicated previous-partition state and the k-means core runs
    with ``warm_start=True`` — the sampled warm-up and the SFC bootstrap
    are both skipped). A warm runner reads the real point count from its
    ``n_valid`` input (``n_global`` is None), so one compiled runner
    serves every point count that fits its ``cap``; cold runners keep
    the static ``n_global`` their warm-up rounds need.
    """
    from jax.sharding import PartitionSpec as P

    if isinstance(devices, tuple):
        mesh = partition_mesh2d(*devices)
        axis = (COARSE_AXIS, REFINE_AXIS)
        spec = P(axis)
    else:
        mesh = partition_mesh(devices)
        axis = PARTITION_AXIS
        spec = P(axis)

    def local_fn(points, weights, centers0, influence0, prev_labels,
                 n_valid):
        points = points.reshape(cap, dim)
        weights = weights.reshape(cap)
        if bootstrap == "device":
            centers0 = sfc_initial_centers_sharded(
                points.astype(jnp.float32), weights.astype(jnp.float32),
                cfg.k, axis)
        A, centers, infl, stats = balanced_kmeans(
            points, cfg, weights, centers0.astype(cfg.dtype),
            axis_name=axis,
            n_global=n_valid if bootstrap == "warm" else n_global,
            influence0=influence0, warm_start=(bootstrap == "warm"),
            prev_assignment=(prev_labels.reshape(cap)
                             if bootstrap == "warm" else None))
        return A[None], centers, infl, stats

    inner = shard_map(
        local_fn, mesh=mesh,
        in_specs=(spec, spec, P(), P(), spec, P()),
        out_specs=(spec, P(), P(), P()))
    return jax.jit(inner)


def _runner_key(devices):
    """Hashable ``devices`` for the runner cache (tuple-or-int)."""
    shape = _device_shape(devices)
    return shape if len(shape) > 1 else shape[0]


def _prep_sharded_cfg(problem: PartitionProblem, devices,
                      cfg: BKMConfig, chunk: int | None = None,
                      cap: int | None = None):
    """Shard the problem (placement-committed in the solve dtype, so the
    drivers stage no further host copies) and pin cfg's "auto" backend AND
    its fused assign+reduce choice to concrete values *before* tracing the
    shard_map body (both depend on process-global state, not trace-local
    state). Returns (sharded, cfg). The fused sweep keeps the paper's
    psum-only communication contract: per balance iteration one [k] size
    sum, per movement iteration one [k, d] + one [k] moment sum. ``cap``
    is the per-shard slot count (None = ``ceil(n / P)``)."""
    shape = _device_shape(devices)
    sp = ShardedPartitionProblem.from_problem(
        problem, devices, chunk=chunk, commit=True, dtype=cfg.dtype,
        mesh=_mesh_for_shape(shape), cap=cap)
    backend = resolve_assign_backend(cfg.assign_backend, sharded=True,
                                     n_local=sp.cap)
    fused = (backend_supports_moments(backend) if cfg.fused is None
             else cfg.fused)
    cfg = dataclasses.replace(cfg, use_kernel=False, backend=backend,
                              fused=fused)
    return sp, cfg


def _stage_solve_fetch(problem: PartitionProblem, devices, cfg: BKMConfig,
                       mode: str, centers0, influence0=None,
                       prev_labels=None, chunk: int | None = None, *,
                       attempt: int | None = None, pad: bool = False):
    """The sharded layout's one stage -> solve -> fetch driver: the deal
    (``_prep_sharded_cfg``), one run of the ``mode`` runner ("host",
    "device" or "warm"), and ``scatter_labels``. A warm solve records
    ``attempt`` on its ``repro.solve`` span; ``pad`` rounds its
    per-shard ``cap`` up by ``core.partitioner.warm_slots`` so that
    point counts of one bucket share one compiled runner.

    Returns:
        (labels [n] int64, centers [k, d], influence [k], stats dict).
    """
    warm = mode == "warm"
    with jax.profiler.TraceAnnotation("repro.stage"):
        cap = (warm_slots(check_index_capacity(problem.n, devices)) if pad
               else None)
        sp, cfg = _prep_sharded_cfg(problem, devices, cfg, chunk=chunk,
                                    cap=cap)
        run = _build_runner(_runner_key(devices), sp.cap, problem.dim, cfg,
                            mode, None if warm else problem.n)
        # prev_labels=None deals a -1 sentinel: it never matches a real
        # assignment (block ids are >= 0), so the core's no-op shortcut
        # cannot fire on a partition that never existed (a cold runner
        # never reads it)
        prev = (jnp.full(sp.devices * sp.cap, -1, jnp.int32)
                if prev_labels is None else jnp.asarray(
                    sp.deal(np.asarray(prev_labels, np.int32),
                            chunk=chunk).reshape(-1), jnp.int32))
        args = jax.block_until_ready((
            sp.points, sp.weights, jnp.asarray(centers0, cfg.dtype),
            jnp.ones(cfg.k, cfg.dtype) if influence0 is None
            else jnp.asarray(influence0, cfg.dtype),
            prev, jnp.int32(problem.n)))
    span = {"attempt": attempt} if warm else {}
    with jax.profiler.TraceAnnotation("repro.solve", **span,
                                      slots=sp.devices * sp.cap):
        solved = jax.block_until_ready(run(*args))
    with jax.profiler.TraceAnnotation("repro.fetch"):
        A, centers, infl, stats = jax.device_get(solved)
        labels = sp.scatter_labels(A, chunk=chunk)
    return labels, centers, infl, stats


def partition_sharded(problem: PartitionProblem, devices, *,
                      bootstrap: str = "host", chunk: int | None = None,
                      **opts) -> PartitionResult:
    """Multi-device geographer partition of ``problem`` over ``devices``
    shards (the ``devices=`` path of the ``partition()`` front door).

    Args:
        problem: the partitioning instance (its seed fixes the shard
            layout permutation).
        devices: number of shards P, or a (P1, P2) 2-D hierarchical mesh
            shape (bit-identical to the flat P1*P2 run — the points shard
            over the axis product); must satisfy 1 <= P <= problem.n and
            P <= len(jax.devices()).
        bootstrap: SFC center seeding — "host" (identical to the
            single-device path, the agreement default) or "device" (fully
            in-graph distributed bootstrap, O(1)-sized communication, no
            O(n) float64 host copy).
        chunk: per-shard slots per deal slice — bounds transient host
            staging during the deal without changing any result bit.
        **opts: BKMConfig field overrides, exactly as in the single-device
            adapter (e.g. ``max_iter=50``, ``warmup=False``); unknown
            fields raise TypeError.

    Returns:
        PartitionResult with labels in original point order, the final
        (centers, influence) state — reusable as a ``repartition()`` warm
        start — and ``stats`` carrying the k-means iteration history plus
        ``devices`` / ``bootstrap``.
    """
    cfg = make_bkm_config(problem, **opts)
    if bootstrap not in BOOTSTRAPS:
        raise ValueError(f"bootstrap must be one of {BOOTSTRAPS}, "
                         f"got {bootstrap!r}")
    if bootstrap == "host":
        with jax.profiler.TraceAnnotation("repro.bootstrap"):
            centers0 = sfc_initial_centers(
                np.asarray(problem.points, np.float64), cfg.k,
                problem.weights)
    else:
        centers0 = np.zeros((cfg.k, problem.dim))      # ignored in-graph
    solved = _stage_solve_fetch(problem, devices, cfg, bootstrap, centers0,
                                chunk=chunk)
    return _geographer_result(problem, solved,
                              devices=_devices_stat(devices),
                              bootstrap=bootstrap)


def repartition_sharded(problem: PartitionProblem, devices,
                        centers0: np.ndarray,
                        influence0: np.ndarray | None = None,
                        prev_labels: np.ndarray | None = None,
                        chunk: int | None = None, *, attempt: int = 0,
                        pad: bool = False, **opts) -> PartitionResult:
    """Multi-device warm-started repartition (the ``devices=`` path of the
    ``repartition()`` front door).

    The previous centers and influence are replicated across shards like
    any cold run's centers, so warm starting adds no collective; the
    layout comes from the problem's seed, so ``devices=1`` is bit-for-bit
    ``core.partitioner.geographer_repartition`` with the same seed.

    Args:
        problem: the perturbed partitioning instance.
        devices: number of shards P, or a (P1, P2) 2-D mesh shape.
        centers0: [k, d] previous partition's centers.
        influence0: [k] previous partition's influence (None = ones).
        prev_labels: [n] previous block ids (enables no-op detection;
            ``repartition()`` always passes them — omitting them deals a
            -1 sentinel that can never masquerade as a real assignment).
        chunk: per-shard slots per deal slice (None = one shot).
        attempt: the balance-retry attempt, recorded on the
            ``repro.solve`` trace span.
        pad: round the per-shard ``cap`` up by
            ``core.partitioner.warm_slots`` (a point set that changes
            from step to step), as the flat path pads its point count.
        **opts: BKMConfig field overrides (``warmup`` is forced off).

    Returns:
        PartitionResult (labels, final centers/influence, stats with
        ``stats["warm_start"] = True`` and the movement iteration count at
        ``stats["iters"]``).
    """
    cfg = make_bkm_config(problem, **dict(opts, warmup=False))
    if centers0.shape[0] != cfg.k:
        raise ValueError(f"centers0 has {centers0.shape[0]} rows, "
                         f"k={cfg.k}")
    solved = _stage_solve_fetch(problem, devices, cfg, "warm", centers0,
                                influence0, prev_labels, chunk,
                                attempt=attempt, pad=pad)
    return _geographer_result(problem, solved, warm=True,
                              devices=_devices_stat(devices))
