"""``refine()`` — sharded label-propagation refinement (DESIGN.md §11).

The paper concedes (§5-6) that graph-based partitioners beat geometric
ones on cut / communication volume. Size-constrained label propagation is
the standard cheap post-pass (Buluc et al., *Recent Advances in Graph
Partitioning*): iteratively move boundary nodes to their neighbor-majority
block as long as the balance constraint allows it. This module is that
pass, grown onto the engine::

    from repro.partition import PartitionProblem, partition, refine

    prob = PartitionProblem.from_mesh(mesh, k=32)
    res  = partition(prob, method="geographer")
    ref  = refine(prob, res)                       # host reference
    ref  = refine(prob, res, devices=8)            # sharded, bit-identical
    ref  = partition(prob, method="rcb", refine=True)   # composed

Algorithm (one synchronous round, identical on host and shards):

1.  Resolve the global label vector: each shard scatters its labels into
    an [n] zero vector at its own global positions; the psum of those
    partials IS the replicated vector (``repro.eval.sharded``'s one-[n]-
    psum neighbor-label discipline — no all_gather).
2.  Per-block weight budgets: quantized (fixed-point integer) block
    weights are psum'd as a [k] partial sum; ``budget_b = limit - W_b``
    where ``limit = floor((1+eps) * W / k) - margin`` is a static int.
3.  Every node builds its neighbor-label histogram H[v, :] (unit edge
    weights) and picks the best *admissible* target: the argmax of H over
    blocks whose budget fits the node's weight, ties broken by lowest
    block id (``argmax`` returns the first maximum on host numpy and
    under XLA alike). A node is a candidate when that target's gain
    ``H[v, t] - H[v, label(v)]`` is positive.
4.  Independent-set filter: a candidate moves only if no neighboring
    candidate has strictly higher priority ``(gain, then lower node
    key)``. Accepted moves therefore never touch two adjacent nodes in
    one round, so each frozen-label gain is exact and the edge cut
    decreases by the sum of accepted gains — refinement can NEVER
    increase the cut.
5.  Budget acceptance: surviving candidates are ordered globally by
    (target block, gain desc, node key asc) and accepted per block while
    the running quantized weight stays within the budget. All arithmetic
    is integer, so every device — and the host reference — computes the
    same accepted set bit for bit.
6.  Rounds repeat under ``lax.while_loop`` until a round accepts no move
    (or ``max_rounds``). Zero accepted moves <=> zero candidates (the
    first survivor of every target segment always fits its budget), so
    natural convergence certifies local optimality: no admissible single
    positive-gain move remains (property- and oracle-tested in
    tests/test_refinement_properties.py).

Determinism rules:

* All tie-breaks are total orders over integers: block id for target
  selection, the node key for move priority. Keys default to the original
  point order (``arange(n)``) and can be overridden via ``node_order`` —
  passing permutation-consistent keys makes refinement exactly
  equivariant under point permutations.
* Block ids are canonicalized on entry (rank of each block's minimum
  member key) and mapped back on exit, so refinement is exactly
  equivariant under block relabelings.
* Node weights go through ``core.metrics.quantize_weights`` fixed-point
  integers; the budget ``limit`` subtracts a margin of n quantization
  units (0 for unit weights), which over-covers the worst-case rounding
  drift so the *real*-weight imbalance never exceeds eps either.
* The sharded path is **bit-for-bit equal** to the host numpy reference
  at every device count: every decision is made from replicated vectors
  assembled by integer psums, and integer additions commute exactly.
"""
from __future__ import annotations

import functools
from typing import Callable

import numpy as np

from repro.core.metrics import edge_cut, imbalance, quantize_weights

from .problem import PartitionProblem, PartitionResult

#: rounds cap — cut strictly decreases every effective round, so this is
#: a static trace bound, not a tuning knob (convergence is usually O(10))
DEFAULT_MAX_ROUNDS = 128

_REFINERS: dict[str, Callable] = {}
_ALIASES: dict[str, str] = {}
_SHORT: dict[str, str] = {}


class UnknownRefinerError(KeyError):
    pass


def register_refiner(name: str, aliases: tuple[str, ...] = (),
                     short: str | None = None):
    """Decorator: register a refinement pass under ``name`` (+ aliases) —
    the refiner registry sits next to the solver registry so
    ``partition(..., refine=...)`` resolves through the same front-door
    discipline (typos fail loudly, aliases resolve).

    Args:
        name: canonical registry key.
        aliases: extra names resolving to ``name``.
        short: suffix used in composed method names / benchmark tool
            columns (default: the canonical name).
    """
    def deco(fn: Callable) -> Callable:
        if name in _REFINERS:
            raise ValueError(f"refiner {name!r} already registered")
        _REFINERS[name] = fn
        _SHORT[name] = short or name
        for a in aliases:
            _ALIASES[a] = name
        return fn
    return deco


def resolve_refiner(name) -> str:
    """Canonical refiner name (aliases resolve; True means the default)."""
    if name is True:
        name = "label_prop"
    name = _ALIASES.get(name, name)
    if name not in _REFINERS:
        raise UnknownRefinerError(
            f"unknown refinement method {name!r}; available: "
            f"{available_refiners()} (aliases: {sorted(_ALIASES)})")
    return name


def available_refiners() -> list[str]:
    """Sorted canonical names of every registered refinement pass."""
    return sorted(_REFINERS)


def refiner_short_name(name) -> str:
    """Suffix for composed method names, e.g. ``'lp'`` -> "geographer+lp"."""
    return _SHORT[resolve_refiner(name)]


# ---------------------------------------------------------------------------
# balance-budget protocol (shared by host, shards, and the test oracle)

def refinement_quantization(problem: PartitionProblem,
                            eps: float | None = None
                            ) -> tuple[np.ndarray, int]:
    """The fixed-point balance protocol of one refinement call.

    Args:
        problem: the partitioning instance.
        eps: balance slack (None = ``problem.epsilon``).

    Returns:
        (iw [n] int64 quantized node weights, limit int) — a block may
        never be filled past ``limit`` quantized units. ``limit`` shaves
        a margin of n units off ``floor((1+eps) * sum(iw) / k)`` for
        float weights (absorbing worst-case 0.5/node rounding drift so
        the real-weight imbalance stays <= eps too); unit weights
        quantize exactly, so their margin is 0.
    """
    eps = problem.epsilon if eps is None else float(eps)
    if eps < 0:
        raise ValueError(f"eps must be >= 0, got {eps}")
    iw = quantize_weights(problem.weights, problem.n)
    margin = 0 if problem.weights is None else problem.n
    W = int(iw.sum())
    limit = int(np.floor((1.0 + eps) * W / problem.k)) - margin
    # a block can never hold more than the total weight, so clamping the
    # limit at W is semantics-preserving and keeps every budget value
    # int32-safe on device (W <= 2^30 - 1 by the quantization scale)
    return iw, min(max(limit, 0), W)


def refinement_budgets(problem: PartitionProblem, labels: np.ndarray,
                       eps: float | None = None
                       ) -> tuple[np.ndarray, np.ndarray]:
    """Round-start admissibility budgets for ``labels`` — the quantity
    the in-graph rounds psum, exposed host-side for the oracle tests.

    Args:
        problem: the partitioning instance.
        labels: [n] block ids.
        eps: balance slack (None = ``problem.epsilon``).

    Returns:
        (iw [n] int64, budget [k] int64): a move of node v into block b
        is admissible iff ``iw[v] <= budget[b]``.
    """
    iw, limit = refinement_quantization(problem, eps)
    W = np.bincount(np.asarray(labels), weights=iw,
                    minlength=problem.k).astype(np.int64)
    return iw, np.maximum(limit - W, 0)


def _canonicalize(labels: np.ndarray, keys: np.ndarray,
                  k: int) -> tuple[np.ndarray, np.ndarray]:
    """Map block ids to their canonical order (rank of each block's
    minimum member key; empty blocks trail). Returns (canonical labels,
    order) with ``order[canonical_id] = original_id`` — the inverse map.

    Because the canonical space depends only on *which nodes share a
    block* (never on the id values), running the rounds in canonical
    space makes refinement exactly equivariant under block relabelings.
    Empty blocks are never move targets (their histogram column is all
    zero, so no positive gain exists), so their trailing placement never
    influences a decision.
    """
    first = np.full(k, np.iinfo(np.int64).max, np.int64)
    np.minimum.at(first, labels, keys.astype(np.int64))
    order = np.lexsort((np.arange(k), first))
    canon = np.empty(k, np.int64)
    canon[order] = np.arange(k)
    return canon[labels], order


# ---------------------------------------------------------------------------
# host reference (the bit-exactness anchor)

def _lp_rounds_host(labels: np.ndarray, indptr: np.ndarray,
                    indices: np.ndarray, iw: np.ndarray, keys: np.ndarray,
                    k: int, limit: int, max_rounds: int):
    """Pure-numpy synchronous rounds — the reference the sharded kernel
    must match bit for bit. Returns (labels, rounds, moves, last_moved).
    """
    n = labels.shape[0]
    labels = labels.astype(np.int64).copy()
    src = np.repeat(np.arange(n), np.diff(indptr))
    arange_n = np.arange(n)
    rounds, moves_total, moved = 0, 0, 1
    while rounds < max_rounds and moved > 0:
        W = np.bincount(labels, weights=iw, minlength=k).astype(np.int64)
        budget = np.maximum(limit - W, 0)
        nb = labels[indices]
        H = np.zeros((n, k), np.int64)
        np.add.at(H, (src, nb), 1)
        own = H[arange_n, labels]
        adm = budget[None, :] >= iw[:, None]
        Hm = np.where(adm, H, -1)
        tgt = np.argmax(Hm, axis=1)
        gain = np.where(Hm[arange_n, tgt] > own,
                        Hm[arange_n, tgt] - own, 0)
        # independent-set filter: a candidate yields to any neighboring
        # candidate of strictly higher (gain, lower-key) priority
        myg, nbg = gain[src], gain[indices]
        myk, nbk = keys[src], keys[indices]
        dom_e = (nbg > myg) | ((nbg == myg) & (nbk < myk))
        dom = np.zeros(n, bool)
        np.logical_or.at(dom, src, dom_e)
        acc0 = (gain > 0) & ~dom
        # per-target budget acceptance in (gain desc, key asc) order
        stgt = np.where(acc0, tgt, k)
        order = np.lexsort((keys, -gain, stgt))
        st = stgt[order]
        siw = np.where(acc0, iw, 0)[order]
        csum = np.cumsum(siw)
        is_start = np.ones(n, bool)
        is_start[1:] = st[1:] != st[:-1]
        base = np.maximum.accumulate(np.where(is_start, csum - siw, 0))
        ok = (st < k) & (csum - base <= budget[np.minimum(st, k - 1)])
        accept = np.zeros(n, bool)
        accept[order] = ok
        moved = int(accept.sum())
        labels = np.where(accept, tgt, labels)
        rounds += 1
        moves_total += moved
    return labels, rounds, moves_total, moved


# ---------------------------------------------------------------------------
# sharded path (shard_map + psum, bit-identical to the host rounds)

@functools.lru_cache(maxsize=64)
def _build_lp_runner(devices: int, cap: int, ecap: int, n: int, k: int,
                     limit: int, max_rounds: int):
    """Compile-cached shard_map refinement kernel for one shape combo.

    Returns a jitted fn(labels [P,cap] i32, gidx [P,cap] i32, lvalid
    [P,cap] bool, src [P,ecap] i32, dst [P,ecap] i32, evalid [P,ecap]
    bool, giw [n] i32 replicated, gkey [n] i32 replicated) ->
    (labels [P,cap] i32, rounds, moves, last_moved).

    Per round the kernel communicates: one [n] psum of label partials
    (the eval/sharded neighbor-label discipline), one [k] psum of
    quantized block-weight partials (the balance budgets), one [n] psum
    of candidate gains and one [n] psum of packed (accepted, target)
    flags. No all_gather, no point-to-point halo. Every decision is then
    made from replicated integer vectors, so all devices stay bitwise in
    lockstep with each other AND with the host reference.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.dist.rules import PARTITION_AXIS, partition_mesh, shard_map

    mesh = partition_mesh(devices)
    axis = PARTITION_AXIS
    i32 = jnp.int32

    def local(labels, gidx, lvalid, src, dst, evalid, giw, gkey):
        labels = labels.reshape(cap)
        gidx = gidx.reshape(cap)
        lvalid = lvalid.reshape(cap)
        src = src.reshape(ecap)
        dst = dst.reshape(ecap)
        evalid = evalid.reshape(ecap)
        liw = jnp.where(lvalid, giw[gidx], 0)
        lkey = gkey[gidx]
        evalid_i = evalid.astype(i32)
        arange_cap = jnp.arange(cap)

        def scatter_psum(vals):
            # non-owners (and padded slots) contribute 0; the owner adds
            # the value itself, so the psum IS the replicated [n] vector
            return jax.lax.psum(
                jnp.zeros(n, i32).at[gidx].add(jnp.where(lvalid, vals, 0)),
                axis)

        def cond(state):
            rounds, moved, _, _ = state
            return (rounds < max_rounds) & (moved > 0)

        def body(state):  # spmdlint: psum-budget=4
            rounds, _, moves_total, labels = state
            glabels = scatter_psum(labels)
            W = jax.lax.psum(jnp.zeros(k, i32).at[labels].add(liw), axis)
            budget = jnp.maximum(limit - W, 0)
            nb = glabels[dst]
            H = jnp.zeros((cap, k), i32).at[src, nb].add(evalid_i)
            own = H[arange_cap, labels]
            adm = budget[None, :] >= liw[:, None]
            Hm = jnp.where(adm, H, -1)
            tgt = jnp.argmax(Hm, axis=1).astype(i32)
            gain = jnp.where(Hm[arange_cap, tgt] > own,
                             Hm[arange_cap, tgt] - own, 0)
            ggain = scatter_psum(gain)
            myg, nbg = gain[src], ggain[dst]
            myk, nbk = lkey[src], gkey[dst]
            dom_e = evalid & ((nbg > myg) | ((nbg == myg) & (nbk < myk)))
            dom = jnp.zeros(cap, bool).at[src].max(dom_e)
            acc0 = (gain > 0) & ~dom
            gpack = scatter_psum(jnp.where(acc0, tgt + 1, 0))
            gtgt = gpack - 1
            gacc = gpack > 0
            stgt = jnp.where(gacc, gtgt, k)
            order = jnp.lexsort((gkey, -ggain, stgt))
            st = stgt[order]
            siw = jnp.where(gacc, giw, 0)[order]
            csum = jnp.cumsum(siw)
            is_start = jnp.concatenate(
                [jnp.ones(1, bool), st[1:] != st[:-1]])
            base = jax.lax.cummax(jnp.where(is_start, csum - siw, 0))
            ok = (st < k) & (csum - base <= budget[jnp.minimum(st, k - 1)])
            accept = jnp.zeros(n, bool).at[order].set(ok)
            moved = jnp.sum(accept.astype(i32))
            # padded slots follow their aliased real point (same
            # discipline as ShardedPartitionProblem.deal)
            labels = jnp.where(accept[gidx], gtgt[gidx], labels)
            return rounds + 1, moved, moves_total + moved, labels

        rounds, moved, moves_total, labels = jax.lax.while_loop(
            cond, body,
            (jnp.int32(0), jnp.int32(1), jnp.int32(0), labels.astype(i32)))
        return labels[None], rounds, moves_total, moved

    inner = shard_map(
        local, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis), P(axis),
                  P(), P()),
        out_specs=(P(axis), P(), P(), P()))
    return jax.jit(inner)


def _lp_rounds_sharded(graph, labels: np.ndarray, iw: np.ndarray,
                       keys: np.ndarray, limit: int, max_rounds: int):
    """Run the shard_map kernel over ``graph``'s layout. Same returns as
    ``_lp_rounds_host`` (labels come back in original point order)."""
    import jax
    import jax.numpy as jnp

    sp = graph.sharded
    run = _build_lp_runner(sp.devices, sp.cap, graph.ecap, sp.problem.n,
                           sp.problem.k, int(limit), int(max_rounds))
    A, rounds, moves, last = run(
        jnp.asarray(sp.deal(labels.astype(np.int32))),
        jnp.asarray(sp.gather.astype(np.int32)),
        jnp.asarray(sp.valid),
        jnp.asarray(graph.src),
        jnp.asarray(graph.dst.astype(np.int32)),
        jnp.asarray(graph.edge_valid),
        jnp.asarray(iw.astype(np.int32)),
        jnp.asarray(keys.astype(np.int32)))
    A, rounds, moves, last = jax.device_get((A, rounds, moves, last))
    return (sp.scatter_labels(np.asarray(A)), int(rounds), int(moves),
            int(last))


# ---------------------------------------------------------------------------
# front door

def _node_keys(problem: PartitionProblem, node_order) -> np.ndarray:
    if node_order is None:
        return np.arange(problem.n, dtype=np.int64)
    keys = np.asarray(node_order, np.int64)
    if keys.shape != (problem.n,):
        raise ValueError(f"node_order must be [{problem.n}] unique ints, "
                         f"got shape {keys.shape}")
    if np.unique(keys).size != problem.n:
        raise ValueError("node_order keys must be unique (they are the "
                         "deterministic move-priority tie-break)")
    lo, hi = np.iinfo(np.int32).min, np.iinfo(np.int32).max
    if keys.min() < lo or keys.max() > hi:
        raise ValueError("node_order keys must fit int32 (the sharded "
                         "path compares them as int32)")
    return keys


@register_refiner("label_prop", aliases=("lp", "labelprop"), short="lp")
def label_prop_refine(problem: PartitionProblem, labels: np.ndarray, *,
                      devices: int | None = None, eps: float | None = None,
                      max_rounds: int = DEFAULT_MAX_ROUNDS,
                      node_order=None, graph=None
                      ) -> tuple[np.ndarray, dict]:
    """Size-constrained label-propagation rounds over ``labels``.

    Args:
        problem: the instance (must carry a CSR graph).
        labels: [n] block ids in original point order.
        devices: None runs the host numpy reference; P >= 1 runs the
            shard_map kernel over P shards (bit-for-bit equal).
        eps: balance slack (None = ``problem.epsilon``).
        max_rounds: static round cap.
        node_order: [n] unique int priority keys (None = point order).
        graph: optional pre-built ``repro.eval.ShardedGraph`` to reuse
            (devices path only; must match ``problem`` and ``devices``).

    Returns:
        (labels [n] int64, info dict with ``rounds`` / ``moves`` /
        ``converged``).
    """
    if not problem.has_graph:
        raise ValueError(
            "problem carries no CSR graph (indptr/indices); label "
            "propagation moves boundary nodes along edges — build the "
            "PartitionProblem via from_mesh or pass indptr/indices")
    labels = np.asarray(labels)
    if labels.shape != (problem.n,):
        raise ValueError(f"labels must be [{problem.n}], "
                         f"got {labels.shape}")
    if max_rounds < 1:
        raise ValueError(f"max_rounds must be >= 1, got {max_rounds}")
    keys = _node_keys(problem, node_order)
    iw, limit = refinement_quantization(problem, eps)
    labels_c, order = _canonicalize(labels.astype(np.int64), keys,
                                    problem.k)
    if devices is None:
        out_c, rounds, moves, last = _lp_rounds_host(
            labels_c, np.asarray(problem.indptr, np.int64),
            np.asarray(problem.indices, np.int64), iw, keys,
            problem.k, limit, max_rounds)
    else:
        from repro.eval.sharded import ShardedGraph
        if graph is None:
            graph = ShardedGraph.from_problem(problem, devices)
        elif graph.problem is not problem or graph.devices != devices:
            raise ValueError(
                "graph was built for a different problem/devices")
        out_c, rounds, moves, last = _lp_rounds_sharded(
            graph, labels_c, iw, keys, limit, max_rounds)
    info = {"rounds": rounds, "moves": moves,
            "converged": bool(last == 0)}
    return order[out_c], info


def refine(problem: PartitionProblem, result, method="label_prop", *,
           devices: int | None = None, eps: float | None = None,
           evaluate: bool = False, **opts) -> PartitionResult:
    """Refine a partition — the quality-recovery front door next to
    ``partition()`` / ``repartition()``.

    Args:
        problem: the instance (must carry a CSR graph; the geometric
            solvers never read it, the refiner does).
        result: the ``PartitionResult`` to refine, or a raw [n] label
            array.
        method: refiner registry name (``available_refiners()``; aliases
            resolve, unknown names raise ``UnknownRefinerError``). True
            selects the default ``"label_prop"``.
        devices: None = host reference; P >= 1 = the shard_map path
            (bit-for-bit equal at every device count).
        eps: balance slack for the refinement budgets (None =
            ``problem.epsilon``). Refined block weights never exceed
            ``(1 + eps) * W / k``, so a balanced input stays balanced.
        evaluate: fill ``result.quality`` with the paper metric set.
        **opts: forwarded to the refiner (``max_rounds`` /
            ``node_order`` / ``graph`` for label_prop).

    Returns:
        A new ``PartitionResult``: refined labels, ``method`` suffixed
        with the refiner's short name (e.g. ``"geographer+lp"``), the
        base result's centers/influence carried over (still the warm
        state ``repartition()`` resumes from), and
        ``stats["refine"]`` = {method, rounds, moves, converged,
        cut_before, cut_after, devices, eps}.
    """
    if not isinstance(problem, PartitionProblem):
        raise TypeError(
            f"refine() takes a PartitionProblem, got {type(problem)}")
    name = resolve_refiner(method)
    if isinstance(result, PartitionResult):
        base = result
        labels_in = np.asarray(base.labels)
    else:
        base = None
        labels_in = np.asarray(result)
    labels_out, info = _REFINERS[name](problem, labels_in,
                                       devices=devices, eps=eps, **opts)
    cut_before = edge_cut(labels_in, problem.indptr, problem.indices)
    cut_after = edge_cut(labels_out, problem.indptr, problem.indices)
    stats = dict(base.stats) if base is not None else {}
    stats["refine"] = {
        "method": name, "rounds": info["rounds"], "moves": info["moves"],
        "converged": info["converged"], "cut_before": cut_before,
        "cut_after": cut_after,
        "devices": None if devices is None else int(devices),
        "eps": problem.epsilon if eps is None else float(eps)}
    stats["final_imbalance"] = imbalance(labels_out, problem.k,
                                         problem.weights)
    base_method = base.method if base is not None else "labels"
    out = PartitionResult(
        labels=labels_out, k=problem.k,
        method=f"{base_method}+{_SHORT[name]}", problem=problem,
        centers=None if base is None else base.centers,
        influence=None if base is None else base.influence,
        stats=stats)
    if evaluate:
        out.evaluate()
    return out
