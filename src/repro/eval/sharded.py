"""In-graph sharded quality metrics (paper §2 at §4.1 scale).

``core.metrics`` evaluates partitions with host numpy over a replicated
CSR graph, which caps the evaluation layer far below what the sharded
solver (``partition(problem, devices=P)``) can partition. This module is
the distributed counterpart: ``edge_cut`` / ``comm_volume`` /
``boundary_nodes`` computed under ``shard_map`` from a ``ShardedGraph`` —
the CSR companion of ``ShardedPartitionProblem``.

Layout. ``ShardedGraph`` deals the CSR rows onto the *same* seed-permuted
round-robin point layout the solver uses: the directed edges of the point
living at (shard p, slot s) become ``(src=s, dst=global neighbor id)``
entries of shard p's flat edge list, padded to a common per-shard cap
``ecap`` so shapes stay static. Padded slots (and padded edges) are
masked, exactly like the solver's weight-zero padding.

Communication. Every label a shard needs from its neighbors is resolved
by ONE global vector sum: each shard scatters its local labels into an
[n] zero vector at its own global positions and the psum of those
per-device partials IS the replicated label vector — no all_gather, no
point-to-point halo, the same "global sums over per-device partials"
discipline as the solver core (paper §4.1). The remaining collectives
are [k]-sized psums of per-device metric partials.

Exactness. All three metrics are integer counts, and integer additions
commute exactly — so the sharded metrics are **bit-for-bit equal** to the
numpy metrics at ``devices=1`` *and* at every device count (property
tested in tests/test_metrics_properties.py at P in {1, 2, 4, 8}).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import numpy as np

from repro.partition.distributed import ShardedPartitionProblem
from repro.partition.problem import PartitionProblem


@dataclass(frozen=True)
class ShardedGraph:
    """CSR adjacency dealt onto a ``ShardedPartitionProblem`` layout.

    Attributes:
        sharded: the point-layout companion (owns gather/valid and the
            source ``PartitionProblem``, which must carry a CSR graph).
        src: [P, ecap] int32 — local slot index of each directed edge's
            source (a valid slot of that shard).
        dst: [P, ecap] int64 — *global* point id of the edge's target
            (resolved against the psum'd label vector in-graph).
        edge_valid: [P, ecap] bool — False for padding entries.
    """
    sharded: ShardedPartitionProblem
    src: np.ndarray
    dst: np.ndarray
    edge_valid: np.ndarray

    @property
    def problem(self) -> PartitionProblem:
        return self.sharded.problem

    @property
    def devices(self) -> int:
        return self.sharded.devices

    @property
    def ecap(self) -> int:
        """Per-shard edge-slot count (max directed edges over shards)."""
        return self.src.shape[1]

    @classmethod
    def from_sharded(cls, sharded: ShardedPartitionProblem,
                     edge_cap: int | None = None) -> "ShardedGraph":
        """Deal the problem's CSR rows onto ``sharded``'s point layout.

        Args:
            sharded: an existing sharded view whose problem carries a CSR
                graph.
            edge_cap: per-shard edge-slot count ``ecap``. None sizes it
                to the max per-shard directed-edge count (the minimal
                valid cap). An explicit cap below that count is an
                error — a short slab would silently drop edges, which
                corrupts every metric downstream.

        Returns:
            The static-shape sharded graph.

        Raises:
            ValueError: the underlying problem has no CSR adjacency, or
                ``edge_cap`` is smaller than some shard's edge count.
        """
        prob = sharded.problem
        if not prob.has_graph:
            raise ValueError(
                "problem carries no CSR graph (indptr/indices); sharded "
                "graph metrics need one — build the PartitionProblem via "
                "from_mesh or pass indptr/indices")
        indptr = np.asarray(prob.indptr, np.int64)
        indices = np.asarray(prob.indices, np.int64)
        deg = np.diff(indptr)
        P = sharded.devices
        srcs, dsts, counts = [], [], []
        for p in range(P):
            slots = np.nonzero(sharded.valid[p])[0]
            g = sharded.gather[p][slots]               # global ids, this shard
            dg = deg[g]
            tot = int(dg.sum())
            counts.append(tot)
            row = np.repeat(np.arange(len(g)), dg)
            # within-row offsets: position minus the start of its row
            within = np.arange(tot) - np.repeat(
                np.concatenate([[0], np.cumsum(dg)[:-1]]), dg)
            dsts.append(indices[indptr[g][row] + within])
            srcs.append(slots[row].astype(np.int32))
        need = max(max(counts), 1)                     # >= 1: no 0-size slabs
        if edge_cap is None:
            ecap = need
        else:
            ecap = int(edge_cap)
            if ecap < need:
                raise ValueError(
                    f"edge_cap={ecap} is smaller than the largest "
                    f"per-shard directed-edge count {need}; a short edge "
                    "slab would silently truncate edges — pass "
                    f"edge_cap >= {need} (or None to size automatically)")
        src = np.zeros((P, ecap), np.int32)
        dst = np.zeros((P, ecap), np.int64)
        valid = np.zeros((P, ecap), bool)
        for p in range(P):
            src[p, :counts[p]] = srcs[p]
            dst[p, :counts[p]] = dsts[p]
            valid[p, :counts[p]] = True
        return cls(sharded=sharded, src=src, dst=dst, edge_valid=valid)

    @classmethod
    def from_problem(cls, problem: PartitionProblem, devices: int,
                     edge_cap: int | None = None) -> "ShardedGraph":
        """Shard ``problem``'s points *and* graph over ``devices`` shards
        (convenience for ``from_sharded(problem.to_sharded(devices))``)."""
        return cls.from_sharded(
            ShardedPartitionProblem.from_problem(problem, devices),
            edge_cap=edge_cap)


@functools.lru_cache(maxsize=64)
def _build_metrics_fn(devices: int, cap: int, ecap: int, n: int, k: int):
    """Compile-cached shard_map metric kernel for one shape combo.

    Returns a jitted fn(labels [P,cap] i32, gidx [P,cap] i64, lvalid
    [P,cap] bool, src [P,ecap] i32, dst [P,ecap] i64, evalid [P,ecap]
    bool) -> (cut2 scalar, comm_per_block [k], boundary_per_block [k])
    with every output replicated (already psum'd inside)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.dist.rules import PARTITION_AXIS, partition_mesh, shard_map

    mesh = partition_mesh(devices)
    axis = PARTITION_AXIS

    def local(labels, gidx, lvalid, src, dst, evalid):  # spmdlint: psum-budget=4
        labels = labels.reshape(cap)
        gidx = gidx.reshape(cap)
        lvalid = lvalid.reshape(cap)
        src = src.reshape(ecap)
        dst = dst.reshape(ecap)
        evalid = evalid.reshape(ecap)
        # halo resolution as ONE global vector sum: every global position
        # is owned by exactly one (shard, valid slot), all other shards
        # contribute zero — the psum of the partials is the full label
        # vector (label 0 works because non-owners add 0, owners add the
        # label itself)
        partial = jnp.zeros(n, jnp.int32).at[gidx].add(
            jnp.where(lvalid, labels, 0))
        glabels = jax.lax.psum(partial, axis)
        nb = glabels[dst]                       # [ecap] neighbor block
        mine = labels[src]                      # [ecap] own block
        is_cut = evalid & (nb != mine)
        cut2 = jax.lax.psum(jnp.sum(is_cut.astype(jnp.int32)), axis)
        # distinct (local slot, remote block) pairs via a [cap, k]
        # scatter-or table — the in-graph unique-per-row
        table = jnp.zeros((cap, k), bool).at[src, nb].max(is_cut)
        per_node = jnp.sum(table, axis=1)       # [cap] #remote blocks
        comm = jax.lax.psum(
            jnp.zeros(k, jnp.int32).at[labels].add(
                jnp.where(lvalid, per_node, 0)), axis)
        bnd = jax.lax.psum(
            jnp.zeros(k, jnp.int32).at[labels].add(
                (lvalid & (per_node > 0)).astype(jnp.int32)), axis)
        return cut2, comm, bnd

    inner = shard_map(
        local, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis), P(axis)),
        out_specs=(P(), P(), P()))
    return jax.jit(inner)


def _run_metrics(graph: ShardedGraph, labels: np.ndarray):
    """Run the shard_map kernel; returns host (cut, comm_pb, bnd_pb).

    The kernel computes all three metrics in one pass, and the last
    (labels, result) pair is memoized on the graph — so the natural
    pattern of calling ``edge_cut_sharded`` / ``comm_volume_sharded`` /
    ``boundary_nodes_sharded`` back to back on one labeling costs one
    device round trip, not three."""
    import jax
    import jax.numpy as jnp

    sp = graph.sharded
    labels = np.asarray(labels)
    if labels.shape != (sp.problem.n,):
        raise ValueError(f"labels must be [{sp.problem.n}], "
                         f"got {labels.shape}")
    key = labels.astype(np.int32, copy=False).tobytes()
    cached = getattr(graph, "_memo", None)
    if cached is not None and cached[0] == key:
        return cached[1]
    fn = _build_metrics_fn(sp.devices, sp.cap, graph.ecap, sp.problem.n,
                           sp.problem.k)
    cut2, comm, bnd = fn(jnp.asarray(sp.deal(labels.astype(np.int32))),
                         jnp.asarray(sp.gather.astype(np.int32)),
                         jnp.asarray(sp.valid),
                         jnp.asarray(graph.src),
                         jnp.asarray(graph.dst.astype(np.int32)),
                         jnp.asarray(graph.edge_valid))
    cut2, comm, bnd = jax.device_get((cut2, comm, bnd))
    result = (int(cut2) // 2, np.asarray(comm, np.int64),
              np.asarray(bnd, np.int64))
    object.__setattr__(graph, "_memo", (key, result))   # frozen dataclass
    return result


def edge_cut_sharded(graph: ShardedGraph, labels: np.ndarray) -> int:
    """Distributed edge cut — equals ``metrics.edge_cut`` exactly.

    Args:
        graph: the sharded CSR view.
        labels: [n] block ids in original point order.

    Returns:
        #undirected edges with endpoints in different blocks.
    """
    return _run_metrics(graph, labels)[0]


def comm_volume_sharded(graph: ShardedGraph,
                        labels: np.ndarray) -> tuple[int, int, np.ndarray]:
    """Distributed communication volume — equals ``metrics.comm_volume``
    exactly.

    Args:
        graph: the sharded CSR view.
        labels: [n] block ids in original point order.

    Returns:
        (max_comm, total_comm, per_block_comm [k]).
    """
    _, comm, _ = _run_metrics(graph, labels)
    return int(comm.max(initial=0)), int(comm.sum()), comm


def boundary_nodes_sharded(graph: ShardedGraph,
                           labels: np.ndarray) -> tuple[int, np.ndarray]:
    """Distributed boundary-node count — equals ``metrics.boundary_nodes``
    exactly.

    Args:
        graph: the sharded CSR view.
        labels: [n] block ids in original point order.

    Returns:
        (total, per_block [k]) boundary-vertex counts.
    """
    _, _, bnd = _run_metrics(graph, labels)
    return int(bnd.sum()), bnd


def evaluate_sharded(problem: PartitionProblem, labels: np.ndarray,
                     devices: int,
                     graph: ShardedGraph | None = None) -> dict:
    """The paper's §2 metric set, graph metrics computed in-graph over
    ``devices`` shards — drop-in for ``metrics.evaluate_problem`` when the
    problem carries a CSR graph (identical keys and values; balance
    metrics stay host-side numpy, they need no graph).

    Args:
        problem: the partitioning instance (must carry indptr/indices).
        labels: [n] block ids in original point order.
        devices: shard count P (1 <= P <= min(n, jax device count)).
        graph: optional pre-built ``ShardedGraph`` to reuse across calls
            (e.g. one mesh evaluated for many methods); must match
            ``problem`` and ``devices``.

    Returns:
        dict with ``imbalance`` / ``n_blocks_used`` / ``cut`` /
        ``maxCommVol`` / ``totalCommVol`` / ``boundaryNodes``.
    """
    from repro.core import metrics

    if graph is None:
        graph = ShardedGraph.from_problem(problem, devices)
    elif graph.problem is not problem or graph.devices != devices:
        raise ValueError("graph was built for a different problem/devices")
    labels = np.asarray(labels)
    cut, comm, bnd = _run_metrics(graph, labels)
    return {
        "imbalance": metrics.imbalance(labels, problem.k, problem.weights),
        "n_blocks_used": int(len(np.unique(labels))),
        "cut": cut,
        "maxCommVol": int(comm.max(initial=0)),
        "totalCommVol": int(comm.sum()),
        "boundaryNodes": int(bnd.sum()),
    }
