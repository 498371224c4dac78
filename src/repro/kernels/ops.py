"""Jit'd wrappers around the Pallas kernels + the assignment-backend
registry.

``assign_backend(name)`` is the single dispatch point for the balanced
k-means hot loop (effective-distance argmin). Every backend has the same
contract::

    fn(points [n,d], centers [k,d], influence [k], *,
       chunk, block_p, block_c) -> (idx [n] int32,
                                    best_eff_sq [n], second_eff_sq [n])

Backends registered with ``supports_moments=True`` additionally accept the
fused assign+reduce mode (the paper's whole movement-iteration hot loop in
ONE pass over the points)::

    fn(..., weights=[n], return_moments=True)
        -> (idx, best_eff_sq, second_eff_sq,
            csum [k,d], cw [k], rad2 [k])

where ``csum[c] = sum_{idx==c} w*p`` (weighted coordinate sums),
``cw[c] = sum w`` (weighted counts == cluster sizes) and
``rad2[c] = sum w*best_eff_sq`` (weighted best effective-sq distances, the
erosion radius numerator before the ``influence^2`` rescale). The core
falls back to ``segment_moments`` for backends without moment support;
that helper shares the per-chunk one-hot reduction of the ``jnp`` fused
path, so for the ``jnp`` backend fused and unfused results are
**bit-for-bit identical** by construction. The Pallas kernel accumulates
its moments in an f32 VMEM block across point tiles (TPUs have no f64), so
its fused moments match the reference to float tolerance, not bitwise.

Registered backends:

* ``jnp``    — chunked dense matmul (|p|^2 + |c|^2 - 2 p.c^T) with the
               point axis tiled by ``chunk`` to bound the n*k scratch;
               fused moments fold into the same chunk loop.
* ``pallas`` — the fused TPU kernel (assign_kernel.py): tile-level
               Hamerly/bbox pruning, centers pre-sorted by bbox distance,
               moments accumulated in VMEM across point tiles.
* ``triton`` — the GPU-portable variant (triton_assign.py): 1-D grid over
               point tiles, in-kernel loop over center tiles, split-k
               moment partials — no TPU-only primitives, so the same body
               is Mosaic-GPU/Triton lowerable; interpret-verified on CPU.
* ``auto``   — per-platform resolution, in order: the
               ``REPRO_ASSIGN_BACKEND`` env override; ``pallas`` on real
               TPUs (``jnp`` for sub-tile shard_map shards); ``triton``
               on GPUs; ``jnp`` on CPU.

All backends accept ``precision`` ("f32" default, "bf16" = bf16 distance
matmul with f32 accumulation — DESIGN.md §4c documents the tolerance) and
``chunk=None`` meaning ``default_chunk(k)``: the point-axis tile sized so
the [chunk, k] effective-distance scratch stays cache/VMEM-resident
(the roofline analysis in launch/kernel_roofline.py showed the former
fixed 65536 default spilling the scratch at k>=16 on bandwidth-bound
hosts, costing ~1.35x at the gate shape n=2^20 k=64).

Third-party backends can be added with ``@register_assign_backend(name)``
(e.g. a CUDA Triton port); ``BKMConfig.backend`` then selects them by
name. Pallas kernels themselves auto-detect compiled-vs-interpret from the
jax backend (assign_kernel.default_interpret): compiled on a TPU, the
interpreter elsewhere. ``REPRO_ASSIGN_BACKEND=<name>`` pins what ``auto``
resolves to (``pallas`` on a CPU runs the kernel in interpret mode).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from .assign_kernel import (assign_argmin_pallas, assign_reduce_pallas,
                            default_interpret)

_FAR = 1e30   # padded-center coordinate; masked out by k_real in-kernel
_F32 = jax.lax.Precision.HIGHEST   # f32 matmuls stay f32 on a TPU


def default_chunk(k: int) -> int:
    """Point-axis chunk for the dense backends when the caller passes
    ``chunk=None``: size the [chunk, k] f32 effective-distance scratch to
    ~2 MB so it stays cache-resident on bandwidth-bound hosts (measured
    1.35x at n=2^20 k=64 vs the former fixed 65536 — see the roofline
    notes in launch/kernel_roofline.py), clamped to [2048, 65536].
    Chunking only tiles the point axis, so per-point results (labels,
    best/second) are bit-identical across chunk sizes; only the cross-
    chunk *moment* summation order changes."""
    return max(2048, min(65536, (1 << 19) // max(k, 1)))


# ---------------------------------------------------------------------------
# assignment-backend registry
# ---------------------------------------------------------------------------

_ASSIGN_BACKENDS: dict = {}
_ASSIGN_MOMENTS: set = set()   # backends accepting return_moments=True


def register_assign_backend(name: str, *, supports_moments: bool = False):
    """Decorator: register an effective-distance assignment backend.

    ``supports_moments=True`` declares that the backend implements the
    fused assign+reduce contract (``weights=``/``return_moments=`` keyword
    arguments, see the module docstring); backends without it fall back to
    a separate ``segment_moments`` sweep in the k-means core.
    """
    def deco(fn):
        _ASSIGN_BACKENDS[name] = fn
        if supports_moments:
            _ASSIGN_MOMENTS.add(name)
        return fn
    return deco


def available_assign_backends() -> list[str]:
    return sorted(_ASSIGN_BACKENDS) + ["auto"]


def resolve_assign_backend(name: str = "auto", *, sharded: bool = False,
                           n_local: int | None = None) -> str:
    """Map ``auto`` to a concrete backend for the current jax platform.

    Resolution order for ``auto`` (DESIGN.md §4c):

    1. ``REPRO_ASSIGN_BACKEND=<name>`` — env override, read per call so a
       test/CI leg can pin the resolution without re-importing. Only
       ``auto`` is overridden: an explicitly named backend always wins,
       so suites that exercise a specific backend stay meaningful under
       the override.
    2. real TPU → ``pallas`` (but ``jnp`` for sub-tile shard_map shards,
       see below).
    3. GPU → ``triton`` (the portable 1-D-grid kernel; no TPU-only
       primitives, Mosaic-GPU lowerable).
    4. otherwise (CPU) → ``jnp``.

    Keyed off ``default_interpret()`` so the backend choice and the
    kernel's compiled-vs-interpret decision share one predicate.

    ``sharded=True`` marks resolution for a ``shard_map`` body (the
    distributed partitioner): the choice is pinned *before* tracing —
    ``jax.default_backend()`` is process-global, not trace-local — and
    because the Pallas kernel's tile pruning only pays off once the local
    shard spans whole point tiles, shards of ``n_local < 1024`` points
    resolve to the chunked jnp path even on TPU hosts.
    """
    if name == "auto":
        env = os.environ.get("REPRO_ASSIGN_BACKEND")
        if env:
            if env not in _ASSIGN_BACKENDS:
                raise KeyError(
                    f"REPRO_ASSIGN_BACKEND={env!r} is not a registered "
                    f"assign backend; available: "
                    f"{available_assign_backends()}")
            return env
        if not default_interpret():    # real TPU
            if sharded and n_local is not None and n_local < 1024:
                return "jnp"
            return "pallas"
        if jax.default_backend() == "gpu":
            return "triton"
        return "jnp"
    if name not in _ASSIGN_BACKENDS:
        raise KeyError(f"unknown assign backend {name!r}; "
                       f"available: {available_assign_backends()}")
    return name


def assign_backend(name: str = "auto"):
    """Return the assignment callable for ``name`` (resolving ``auto``)."""
    return _ASSIGN_BACKENDS[resolve_assign_backend(name)]


def backend_supports_moments(name: str = "auto") -> bool:
    """True when ``name`` (resolved) implements fused assign+reduce."""
    return resolve_assign_backend(name) in _ASSIGN_MOMENTS


def _chunk_assign(p, cn, centers, inv2, precision: str = "f32"):
    """One dense chunk of the effective-distance argmin. Returns
    (idx, best, second, onehot) — ``onehot`` [C, k] bool marks each
    point's winning center and is reused by the fused moment reduction.
    ``precision="bf16"`` casts only the cross-term matmul operands to
    bfloat16 (f32 accumulation); norms and the epilogue stay f32. The f32
    matmuls here ask for ``Precision.HIGHEST``: a TPU's default f32
    matmul rounds its operands to bfloat16 (the CPU ignores the flag)."""
    pn = jnp.sum(p * p, axis=1, keepdims=True)
    if precision == "bf16":
        cross2 = 2.0 * jax.lax.dot_general(
            p.astype(jnp.bfloat16), centers.astype(jnp.bfloat16),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    else:
        cross2 = jnp.matmul(2.0 * p, centers.T, precision=_F32)
    sq = pn + cn[None, :] - cross2
    eff = jnp.maximum(sq, 0.0) * inv2[None, :]
    k = eff.shape[1]
    # argmin-free epilogue: XLA CPU lowers arg-reductions to a scalar
    # loop, while plain min/max vectorize. min + exact-equality + an
    # integer max over (k - j) recovers the *first* index attaining the
    # min — bit-identical to jnp.argmin (min returns an element of the
    # row exactly), measured ~1.5x on the n=2^20 hot loop.
    best = jnp.min(eff, axis=1)
    iseq = eff == best[:, None]
    rev = jnp.arange(k, 0, -1, dtype=jnp.int32)
    idx = (k - jnp.max(iseq * rev[None, :], axis=1)).astype(jnp.int32)
    onehot = idx[:, None] == jnp.arange(k)[None, :]
    second = jnp.min(jnp.where(onehot, jnp.inf, eff), axis=1)
    return idx, best, second, onehot


def _chunk_moments(onehot, p, w, best):
    """Per-chunk weighted moment partial as one [k, d+2] matmul:
    columns 0..d-1 = sum w*p, column d = sum w, column d+1 = sum w*best.
    Shared by the fused ``jnp`` backend and ``segment_moments`` so both
    accumulate in the identical order (bit-for-bit equal results)."""
    ww = jnp.where(onehot, w[:, None], 0.0)                  # [C, k]
    stacked = jnp.concatenate(
        [p, jnp.ones((p.shape[0], 1), p.dtype), best[:, None]], axis=1)
    # contracted in place (no explicit transpose): under vmap the result
    # then does not depend on the batch size on XLA:CPU, which keeps the
    # sharded batched refinement bit-identical to the host vmap
    return jnp.einsum("ck,cd->kd", ww, stacked, precision=_F32)  # [k, d+2]


def _split_moments(m, d):
    return m[:, :d], m[:, d], m[:, d + 1]


def segment_moments(points, weights, idx, best_sq, k: int, *,
                    chunk: int | None = None):
    """Per-cluster weighted moments of an existing assignment — the
    unfused fallback for assignment backends without moment support.

    Args:
        points: [n, d] point coordinates.
        weights: [n] nonneg weights (0 marks padded points).
        idx: [n] int32 cluster assignment.
        best_sq: [n] best effective *squared* distances (as returned by
            the assignment backends).
        k: number of clusters.
        chunk: point-axis tile (None = ``default_chunk(k)``); MUST match
            the assignment call's chunk for bit-exact agreement with the
            fused path (both resolve None identically, so leaving both
            unset is safe).

    Returns:
        (csum [k, d], cw [k], rad2 [k]) — weighted coordinate sums,
        weighted counts, and weighted best-eff-sq sums. Uses the same
        per-chunk one-hot matmul partials (and the same cross-chunk
        summation) as the fused ``jnp`` backend, so the results are
        bit-for-bit identical to ``return_moments=True``.
    """
    n, d = points.shape
    if chunk is None:
        chunk = default_chunk(k)
    arange_k = jnp.arange(k)[None, :]

    def one(p, w, ix, b):
        return _chunk_moments(ix[:, None] == arange_k, p, w, b)

    if n <= chunk:
        return _split_moments(one(points, weights, idx, best_sq), d)
    pad = (-n) % chunk
    p = jnp.pad(points, ((0, pad), (0, 0))).reshape(-1, chunk, d)
    w = jnp.pad(weights, (0, pad)).reshape(-1, chunk)
    ix = jnp.pad(idx, (0, pad)).reshape(-1, chunk)
    b = jnp.pad(best_sq, (0, pad)).reshape(-1, chunk)
    m = jax.lax.map(lambda a: one(*a), (p, w, ix, b)).sum(axis=0)
    return _split_moments(m, d)


@register_assign_backend("jnp", supports_moments=True)
def assign_argmin_jnp(points, centers, influence, *,
                      chunk: int | None = None,
                      block_p: int = 1024, block_c: int = 128,
                      weights=None, return_moments: bool = False,
                      precision: str = "f32"):
    """Chunked dense path (the paper's inner loop as one matmul per chunk).
    ``block_p``/``block_c`` are accepted for contract parity and ignored.
    ``chunk=None`` resolves to ``default_chunk(k)`` (cache-resident
    [chunk, k] scratch); per-point results are chunk-invariant, so the
    default change is label-bitexact vs any fixed chunk.

    With ``return_moments=True`` (requires ``weights``) the per-cluster
    moment partials are computed inside the same chunk loop while the
    chunk is hot, so the point array is streamed exactly once; the
    cross-chunk accumulation matches ``segment_moments`` bit-for-bit.
    """
    del block_p, block_c
    if return_moments and weights is None:
        raise ValueError("return_moments=True requires weights")
    if chunk is None:
        chunk = default_chunk(centers.shape[0])
    inv2 = 1.0 / (influence * influence)
    cn = jnp.sum(centers * centers, axis=1)
    n, d = points.shape

    def one_chunk(p):
        return _chunk_assign(p, cn, centers, inv2, precision)[:3]

    def one_chunk_fused(p, w):
        idx, best, second, onehot = _chunk_assign(p, cn, centers, inv2,
                                                  precision)
        return idx, best, second, _chunk_moments(onehot, p, w, best)

    if n <= chunk:
        if not return_moments:
            return one_chunk(points)
        idx, b, s, m = one_chunk_fused(points, weights)
        return (idx, b, s) + _split_moments(m, d)
    pad = (-n) % chunk
    pts = jnp.pad(points, ((0, pad), (0, 0))).reshape(-1, chunk, d)
    if not return_moments:
        idx, b, s = jax.lax.map(one_chunk, pts)
        return idx.reshape(-1)[:n], b.reshape(-1)[:n], s.reshape(-1)[:n]
    w = jnp.pad(weights, (0, pad)).reshape(-1, chunk)
    idx, b, s, m = jax.lax.map(lambda a: one_chunk_fused(*a), (pts, w))
    return ((idx.reshape(-1)[:n], b.reshape(-1)[:n], s.reshape(-1)[:n])
            + _split_moments(m.sum(axis=0), d))


def kernel_tiles(n: int, k: int, block_p: int, block_c: int):
    """The assign kernel's (point tile, center tile) for n points and k
    centers: ``block_p`` rounded up to whole 128-lane vregs and cut to the
    point count so rounded, ``block_c`` cut to k rounded up to 8 sublanes
    (k = 64 runs one 64-row tile, not a half-padded 128-row one)."""
    bp = min(-(-block_p // 128) * 128, -(-n // 128) * 128)
    bc = min(block_c, -(-k // 8) * 8)
    return bp, bc


def _tile_bounds(pts, centers, inv2, block_p, block_c):
    """Lower bound of effective sqdist between each point tile's bbox and
    each center tile: max(0, bbox-distance)^2 * max tile inv2. ``pts`` is
    the kernel's lane-dense ``[d, N]`` copy."""
    d, n = pts.shape
    k = centers.shape[0]
    pt = pts.reshape(d, n // block_p, block_p)
    lo = jnp.min(pt, axis=2).T                     # [nPT, d]
    hi = jnp.max(pt, axis=2).T
    ct = centers.reshape(k // block_c, block_c, d)  # [nCT, BC, d]
    # distance of each center to each tile bbox
    cexp = ct[None]                                 # [1, nCT, BC, d]
    gap = jnp.maximum(jnp.maximum(lo[:, None, None, :] - cexp,
                                  cexp - hi[:, None, None, :]), 0.0)
    d2 = jnp.sum(gap * gap, axis=-1)                # [nPT, nCT, BC]
    inv2_t = inv2.reshape(k // block_c, block_c)    # [nCT, BC]
    eff = d2 * inv2_t[None]                         # per-center bound
    return jnp.min(eff, axis=-1)                    # [nPT, nCT]


def _stage(points, centers, influence, block_p, block_c):
    """The kernel's operands, once a sweep: the points transposed to a
    lane-dense ``[d, N_pad]`` copy (edge-padded, so tile bboxes stay
    tight), centers and ``inv2`` padded to the center tile, and the
    per-tile prune bounds. With more than one center tile the centers are
    sorted by effective distance to the global point bbox so prunable
    tiles come late in the sequential grid dimension (``order`` maps a
    sorted column to its center); one tile has nothing to prune or
    order, so it gets neither (``order`` is None)."""
    n, d = points.shape
    k = centers.shape[0]
    bp, bc = kernel_tiles(n, k, block_p, block_c)
    inv2 = (1.0 / (influence * influence)).astype(jnp.float32)
    centers = centers.astype(jnp.float32)
    pts = jnp.pad(points.astype(jnp.float32).T, ((0, 0), (0, (-n) % bp)),
                  mode="edge")
    n_ct = -(-k // bc)
    order = None
    if n_ct > 1:
        lo = jnp.min(pts, axis=1)
        hi = jnp.max(pts, axis=1)
        gap = jnp.maximum(jnp.maximum(lo[None] - centers, centers - hi[None]),
                          0.0)
        order = jnp.argsort(jnp.sum(gap * gap, axis=1) * inv2)
        centers, inv2 = centers[order], inv2[order]
    pad_k = n_ct * bc - k
    cts = jnp.pad(centers, ((0, pad_k), (0, 0)), constant_values=_FAR)
    iv2 = jnp.pad(inv2, (0, pad_k), constant_values=1.0)
    if n_ct > 1:
        bounds = _tile_bounds(pts, cts, iv2, bp, bc)
    else:
        bounds = jnp.zeros((pts.shape[1] // bp, 1), jnp.float32)
    return pts, cts, iv2, bounds, order, bp, bc


@functools.partial(jax.jit, static_argnames=("block_p", "block_c",
                                             "return_moments", "precision"))
def assign_argmin(points, centers, influence, block_p: int = 8192,
                  block_c: int = 128, weights=None,
                  return_moments: bool = False, precision: str = "f32"):
    """Drop-in replacement for ref.assign_argmin_ref (same returns).

    ``return_moments=True`` (requires ``weights``) runs the fused
    assign+reduce kernel: the per-cluster weighted moments are accumulated
    in VMEM across point tiles and un-sorted back to original center ids
    here, so the point array is streamed exactly once. The tiles come
    from ``kernel_tiles``. ``precision`` passes through to the kernel
    (DESIGN.md §4c). The kernel runs compiled on a TPU and interpreted
    elsewhere (``assign_kernel.default_interpret``).
    """
    n, d = points.shape
    k = centers.shape[0]
    pts, cts, iv2, bounds, order, bp, bc = _stage(points, centers,
                                                  influence, block_p,
                                                  block_c)
    kw = dict(k_real=k, block_p=bp, block_c=bc, precision=precision)
    if return_moments:
        if weights is None:
            raise ValueError("return_moments=True requires weights")
        w = jnp.pad(weights, (0, pts.shape[1] - n)).astype(jnp.float32)
        idx_s, best, second, m = assign_reduce_pallas(pts, cts, iv2, bounds,
                                                      w, **kw)
        m = m[:, :k].T                                     # [k, d+2]
        if order is not None:
            # sorted column j belongs to original center order[j]
            m = jnp.zeros_like(m).at[order].set(m)
    else:
        idx_s, best, second = assign_argmin_pallas(pts, cts, iv2, bounds,
                                                   **kw)
    idx = jnp.clip(idx_s[:n], 0, k - 1)
    if order is not None:
        idx = order[idx]
    out = (idx.astype(jnp.int32), best[:n], second[:n])
    if return_moments:
        out += _split_moments(m, d)
    return out


@register_assign_backend("pallas", supports_moments=True)
def assign_argmin_pallas_backend(points, centers, influence, *,
                                 chunk: int | None = None,
                                 block_p: int = 8192,
                                 block_c: int = 128, weights=None,
                                 return_moments: bool = False,
                                 precision: str = "f32"):
    """Registry adapter for the Pallas kernel (``chunk`` is ignored: the
    kernel's own point tiling bounds VMEM)."""
    del chunk
    return assign_argmin(points, centers, influence,
                         block_p=block_p, block_c=block_c,
                         weights=weights, return_moments=return_moments,
                         precision=precision)


def tile_prune_fraction(points, centers, influence, second_sq,
                        block_p: int = 8192, block_c: int = 128):
    """Host-side estimate of the fraction of (point-tile × center-tile)
    grid steps the Pallas kernel's ``pl.when`` bbox bound prunes, for
    ``stats["tiles_pruned_frac"]`` (useful-vs-wasted compute in the
    roofline table).

    Stages the inputs as the kernel's wrapper does (``_stage``: the same
    tiles, sort and edge-padded points), then counts pairs whose bound
    cannot beat the point tile's worst *converged* second-best
    (``second_sq``, in effective-squared space, e.g. ``lb**2`` after a
    balance pass). The first center tile is never pruned (the kernel
    unconditionally computes j == 0 to initialize its accumulators), so
    one center tile prunes nothing. This is the steady-state bound —
    inside one sweep the kernel's running second-best starts at +inf, so
    the realized fraction converges to this value from below. Traceable;
    psum the numerator under shard_map (balanced_kmeans averages it over
    shards).
    """
    _, _, _, bounds, _, bp, _ = _stage(points, centers, influence, block_p,
                                       block_c)
    sec = jnp.pad(second_sq, (0, bounds.shape[0] * bp - second_sq.shape[0]),
                  mode="edge")
    # a tile prunes only when the bound beats its WORST second-best; an
    # infinite second (k == 1) makes the tile unprunable, as in-kernel
    worst = jnp.max(sec.reshape(-1, bp), axis=1)           # [nPT]
    prunable = bounds >= worst[:, None]
    prunable = prunable.at[:, 0].set(False)               # j == 0 runs
    return jnp.mean(prunable.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("bq", "bk", "softcap"))
def flash_attention(q, k, v, bq: int = 512, bk: int = 512,
                    softcap: float = 0.0):
    """Causal flash attention. q: [B, S, H, dh], k/v: [B, S, KV, dh]
    (H % KV == 0). Pads S to the tile size; padded keys sit above the
    causal diagonal of every real query, so no extra masking is needed.
    Returns [B, S, H, dh]."""
    from .flash_attention import flash_attention_pallas
    B, S, H, dh = q.shape
    KV = k.shape[2]
    bq = min(bq, max(128, 1 << (S - 1).bit_length()))
    bk = min(bk, bq)
    pad = (-S) % max(bq, bk)
    qt = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kt = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vt = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Sp = S + pad
    qh = qt.transpose(0, 2, 1, 3).reshape(B * H, Sp, dh)
    kh = kt.transpose(0, 2, 1, 3).reshape(B * KV, Sp, dh)
    vh = vt.transpose(0, 2, 1, 3).reshape(B * KV, Sp, dh)
    o = flash_attention_pallas(qh, kh, vh, bq=bq, bk=bk, softcap=softcap,
                               interpret=default_interpret())
    o = o.reshape(B, H, Sp, dh).transpose(0, 2, 1, 3)
    return o[:, :S]


@functools.partial(jax.jit, static_argnames=("top_k", "bt", "block_e"))
def router_topk(x, centroids, influence, top_k: int, bt: int = 256,
                block_e: int = 128):
    """Fused balanced-k-means MoE routing. x: [T, D], centroids: [E, D],
    influence: [E]. Returns (idx [T, top_k], eff [T, top_k]). E may exceed
    one VMEM tile: the kernel sweeps center tiles of ``block_e`` through
    the shared tiled path, merging a running top-k across tiles."""
    from .moe_router_kernel import router_topk_pallas
    T, D = x.shape
    E = centroids.shape[0]
    inv2 = 1.0 / (influence * influence)
    pad_t = (-T) % bt
    pad_e = (-E) % block_e
    xp = jnp.pad(x, ((0, pad_t), (0, 0))).astype(jnp.float32)
    cp = jnp.pad(centroids, ((0, pad_e), (0, 0)),
                 constant_values=_FAR).astype(jnp.float32)
    ip = jnp.pad(inv2, (0, pad_e), constant_values=1.0).astype(jnp.float32)
    idx, eff = router_topk_pallas(xp, cp, ip, top_k=top_k, bt=bt,
                                  block_e=block_e, e_real=E,
                                  interpret=default_interpret())
    return idx[:T], eff[:T]


# registering the triton-shaped backend imports this module back, so the
# import must sit after every name it needs is defined
from . import triton_assign as _triton_assign  # noqa: E402,F401
