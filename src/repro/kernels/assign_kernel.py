"""Pallas TPU kernel: fused effective-distance assignment (paper's hot loop).

Computes, for every point p, the cluster c minimizing
``sqdist(p, c) / influence(c)^2`` together with the best and second-best
effective squared distances (needed for the Hamerly bounds, Eqs. 4-5).
``assign_reduce_pallas`` also accumulates the per-cluster weighted moments
(Alg. 2's movement reductions) in a VMEM block revisited across point
tiles, so the point array is streamed exactly once.

The tile is lane-dense (DESIGN.md §4c): points lie on the 128-lane axis,
centers on sublanes.

* Points enter as ``[d, N]`` in ``(d, BP)`` blocks, centers as ``[K, d]``
  in ``(BC, d)`` blocks, ``1/influence^2`` as a ``[K, 1]`` column. Each
  grid step walks its point tile in lane chunks of ``L`` points and forms
  the ``[BC, L]`` tile of effective distances, one center per sublane.
* The reductions over centers run down sublanes: an elementwise ``min``
  across vregs, then one 8-way sublane fold. The index is the first row
  attaining the minimum (min, exact equality, max over the reversed row
  iota: ``jnp.argmin``'s tie rule, as ``ops._chunk_assign``), and the
  second-best masks that row. Results are ``[1, L]`` lane rows, stored
  into the ``[1, N]`` idx/best/second outputs with no relayout.
* The cross term ``p·c`` follows the static ``d`` and ``precision``.
  With ``precision="f32"`` and ``d <= _VPU_MAX_D`` (the meshes' 2 and 3
  coordinates) it is d broadcast multiply-adds on the VPU, f32
  throughout, in the oracle's expression ``|p|^2 + |c|^2 - 2 p·c``: a
  matmul would fill d of its 128 contraction rows and need 6 bf16 passes
  for f32. Otherwise (wider points, or ``precision="bf16"``) it is one
  MXU ``dot_general`` of the ``[BC, d]`` centers against the ``[d, L]``
  points, ``Precision.HIGHEST`` in f32 mode and bf16 operands with f32
  accumulation in bf16 mode; the norms, the running best/second and the
  moments stay f32 either way (tolerances in DESIGN.md §4c).
* The paper's per-point Hamerly branch and bounding-box center ordering
  become **tile-level pruning**: the wrapper (ops.py) precomputes a lower
  bound on the effective sqdist between each point tile's bounding box
  and each center tile; a whole center tile is skipped via ``pl.when``
  when its bound cannot beat the tile's current worst second-best. With
  more than one center tile the centers are pre-sorted by distance to the
  global bounding box (paper Alg. 1 line 6) so prunable tiles appear late
  in the ``arbitrary`` grid dimension.
* Padded centers (the ``_FAR`` rows the wrapper appends to reach a
  center-tile multiple) are masked to ``+inf`` effective distance by the
  static real-center count ``k_real``: the distance math is never trusted
  for them (``|FAR|^2`` overflows f32 and ``inf - inf`` is NaN).
* Running (best, second, argmin) accumulators live in the output VMEM
  rows, revisited across the center-tile grid dimension. In moments mode
  the ``[d+2, K]`` moment block (csum rows, weight row, radius row) is
  revisited across the point-tile dimension as well: after its last
  center tile each point tile adds ``[d+2, L] x [K, L]^T`` matmuls of the
  stacked coordinates against the weighted one-hot of its final labels,
  both operands contracting their lane axis, so both grid dimensions are
  ``arbitrary`` (sequential).
* The prune bounds are read as SMEM scalars. Every block's last two
  dimensions divide by (8, 128) or equal the array's, also under ``vmap``
  (the batched hierarchical refinement adds a leading batch dimension).

Grid: ``(N/BP, K/BC)``. VMEM per step: the point tile and the four
``[1, BP]`` rows, each padded to 8 sublanes and double-buffered
(2·8·BP·(1 + 4) floats), the center tile, and a few ``[BC, L]`` (moments:
``[K, L]``) f32 temporaries; e.g. BP = 8192, BC = 64, L = 2048:
2.5 MiB + 512 KiB per temporary, inside the 16 MiB scoped VMEM of a v5e.
Larger chunks amortise the loop: on a TPU v5e at n = 5,824,554, k = 64,
d = 2, one fused sweep (wrapper and kernel) took 7.4 ms with 32K-float
temporaries and 5.9 ms with 128K.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

PRECISIONS = ("f32", "bf16")

_VPU_MAX_D = 4          # f32 cross term on the VPU up to this many coords
_CHUNK_ELEMS = 1 << 17  # f32 elements of one [rows, L] temporary (512 KiB)


def _check_tiling(n: int, k: int, block_p: int, block_c: int,
                  entry: str) -> None:
    """Wrapper-side padding contract: the kernel entry points only accept
    tile-multiple shapes — ``ops.assign_argmin`` pads before calling. A
    non-multiple shape reaching this point is a caller bug; name it."""
    if n % block_p != 0:
        raise ValueError(
            f"{entry}: points axis n={n} is not a multiple of "
            f"block_p={block_p}; pad the point array (ops.assign_argmin "
            "does this) or pass a dividing block_p")
    if k % block_c != 0:
        raise ValueError(
            f"{entry}: centers axis k={k} is not a multiple of "
            f"block_c={block_c}; pad the center array with _FAR rows "
            "(ops.assign_argmin does this) or pass a dividing block_c")


def _cross_term(p, c, precision: str):
    """-2 p @ c^T cross term of the squared distance, [BP, BC] f32, for
    points on sublanes (the triton-shaped backend's ``[BP, d]`` tile).

    ``f32`` asks for full f32 contraction (``Precision.HIGHEST``): the
    TPU's default f32 matmul rounds its operands to bfloat16, which the
    f32 mode must not do. ``bf16`` casts both operands to bfloat16 before
    the MXU matmul (accumulation stays f32 via ``preferred_element_type``):
    half the operand bandwidth and double the MXU rate on TPU, at a
    relative distance error bounded by ~2^-8 per coordinate product."""
    return _mxu_dot(p, c, ((1,), (1,)), precision)


def _mxu_dot(a, b, contract, precision: str):
    """``dot_general`` over the ``contract`` axes with f32 accumulation:
    ``Precision.HIGHEST`` in f32 mode, bf16 operands in bf16 mode."""
    if precision == "bf16":
        a = a.astype(jnp.bfloat16)
        b = b.astype(jnp.bfloat16)
        exact = None
    else:
        exact = jax.lax.Precision.HIGHEST
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=exact,
                               preferred_element_type=jnp.float32)


def _lane_cross(c, p, precision: str):
    """p·c of every (center, point) pair of the lane-dense tile, [BC, L]
    f32, from centers [BC, d] and points [d, L]: VPU multiply-adds for f32
    at small d, else one MXU matmul (see the module docstring)."""
    d = p.shape[0]
    if precision == "f32" and d <= _VPU_MAX_D:
        acc = c[:, 0:1] * p[0:1, :]
        for a in range(1, d):
            acc = acc + c[:, a:a + 1] * p[a:a + 1, :]
        return acc
    return _mxu_dot(c, p, ((1,), (0,)), precision)


def _lanes(rows: int, block_p: int) -> int:
    """Lane chunk for ``[rows, L]`` temporaries of about ``_CHUNK_ELEMS``
    floats: a power of two from 128 up, dividing the point tile."""
    target = max(128, 1 << ((_CHUNK_ELEMS // rows).bit_length() - 1))
    return math.gcd(block_p, target)


def _assign_step(points_ref, bounds_ref, centers_ref, inv2_ref, idx_ref,
                 best_ref, second_ref, *, k_real: int, masked: bool,
                 precision: str, lanes: int):
    """One (point-tile × center-tile) grid step: init at the first center
    tile, tile-level bbox pruning, then per chunk of ``lanes`` points the
    ``[BC, L]`` effective distances and the running (best, second,
    argmin) update in the ``[1, BP]`` output rows."""
    j = pl.program_id(1)
    block_c = centers_ref.shape[0]
    block_p = points_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        best_ref[...] = jnp.full_like(best_ref, jnp.inf)
        second_ref[...] = jnp.full_like(second_ref, jnp.inf)
        idx_ref[...] = jnp.full_like(idx_ref, -1)

    # Tile-level Hamerly/bbox pruning: skip this center tile when its
    # lower bound cannot improve any point's second-best.
    bound = bounds_ref[0, j]
    worst_second = jnp.max(second_ref[...])

    @pl.when((j == 0) | (bound < worst_second))
    def _compute():
        c = centers_ref[...]                                 # [BC, D]
        cn = jnp.sum(c * c, axis=1, keepdims=True)           # [BC, 1]
        inv2 = inv2_ref[...]                                 # [BC, 1]
        rows = jax.lax.broadcasted_iota(jnp.int32, (block_c, 1), 0)
        # mask padded (_FAR) centers to +inf: their f32 distance overflows
        # (or NaNs via inf - inf) and must never reach argmin/second
        real = rows < k_real - j * block_c                   # [BC, 1]
        rev = block_c - rows

        def chunk(s, carry):
            cols = pl.ds(pl.multiple_of(s * lanes, lanes), lanes)
            p = points_ref[:, cols]                          # [D, L]
            pn = jnp.sum(p * p, axis=0, keepdims=True)       # [1, L]
            sq = (pn + cn) - 2.0 * _lane_cross(c, p, precision)
            eff = jnp.maximum(sq, 0.0) * inv2                # [BC, L]
            if masked:
                eff = jnp.where(real, eff, jnp.inf)
            best = jnp.min(eff, axis=0, keepdims=True)       # [1, L]
            first = jnp.max(jnp.where(eff == best, rev, 0), axis=0,
                            keepdims=True)
            idx = block_c - first                            # [1, L]
            second = jnp.min(jnp.where(rows == idx, jnp.inf, eff), axis=0,
                             keepdims=True)

            old_best = best_ref[:, cols]
            old_second = second_ref[:, cols]
            take_new = best < old_best
            best_ref[:, cols] = jnp.where(take_new, best, old_best)
            second_ref[:, cols] = jnp.minimum(
                jnp.minimum(old_second, second),
                jnp.maximum(old_best, best))
            idx_ref[:, cols] = jnp.where(take_new, j * block_c + idx,
                                         idx_ref[:, cols])
            return carry

        jax.lax.fori_loop(0, block_p // lanes, chunk, 0)


def _moments_step(points_ref, w_ref, idx_ref, best_ref, moments_ref, *,
                  lanes: int):
    """Moment accumulation into the grid-wide ``[d+2, K]`` VMEM block:
    rows ``0..d-1`` hold the weighted coordinate sums, row ``d`` the
    weighted counts, row ``d+1`` the weighted best effective-sq distances
    — all in *sorted-center* column space (the wrapper un-sorts). Each
    point tile contributes its one-hot matmul partials once, after its
    final center tile. Accumulation is always f32, independent of the
    distance precision."""
    i = pl.program_id(0)
    j = pl.program_id(1)
    d = points_ref.shape[0]
    block_p = points_ref.shape[1]
    kpad = moments_ref.shape[1]

    @pl.when((i == 0) & (j == 0))
    def _zero():
        moments_ref[...] = jnp.zeros_like(moments_ref)

    @pl.when(j == pl.num_programs(1) - 1)
    def _accumulate():
        centers = jax.lax.broadcasted_iota(jnp.int32, (kpad, 1), 0)

        def chunk(s, acc):
            cols = pl.ds(pl.multiple_of(s * lanes, lanes), lanes)
            ww = jnp.where(centers == idx_ref[:, cols], w_ref[:, cols],
                           0.0)                              # [K, L]
            stacked = jnp.concatenate(
                [points_ref[:, cols], jnp.ones((1, lanes), jnp.float32),
                 best_ref[:, cols]], axis=0)                 # [D+2, L]
            return acc + _mxu_dot(stacked, ww, ((1,), (1,)),
                                  "f32")                     # [D+2, K]

        moments_ref[...] += jax.lax.fori_loop(
            0, block_p // lanes, chunk,
            jnp.zeros((d + 2, kpad), jnp.float32))


def _assign_kernel(bounds_ref, points_ref, centers_ref, inv2_ref,
                   idx_ref, best_ref, second_ref, **step):
    _assign_step(points_ref, bounds_ref, centers_ref, inv2_ref, idx_ref,
                 best_ref, second_ref, **step)


def _assign_moments_kernel(bounds_ref, points_ref, centers_ref, inv2_ref,
                           w_ref, idx_ref, best_ref, second_ref,
                           moments_ref, *, moment_lanes: int, **step):
    _assign_step(points_ref, bounds_ref, centers_ref, inv2_ref, idx_ref,
                 best_ref, second_ref, **step)
    _moments_step(points_ref, w_ref, idx_ref, best_ref, moments_ref,
                  lanes=moment_lanes)


def default_interpret() -> bool:
    """Backend auto-detection: run the Mosaic-compiled kernel on real TPUs,
    the Pallas interpreter everywhere else (CPU CI containers, GPU hosts)."""
    return jax.default_backend() != "tpu"


def _specs(n: int, d: int, k: int, block_p: int, block_c: int):
    """Grid and the blocks shared by both kernels: the point tile's row of
    prune bounds in SMEM (``[N/BP, 1, K/BC]``, one ``(1, K/BC)`` block per
    point tile, so SMEM use grows with K only), the ``(d, BP)`` point tile,
    the ``(BC, d)`` center tile, the ``(BC, 1)`` inverse-influence column,
    and the ``[1, N]`` per-point rows (weights, idx, best, second)."""
    grid = (n // block_p, k // block_c)
    bounds = pl.BlockSpec((None, 1, grid[1]), lambda i, j: (i, 0, 0),
                          memory_space=pltpu.SMEM)
    points = pl.BlockSpec((d, block_p), lambda i, j: (0, i))
    centers = pl.BlockSpec((block_c, d), lambda i, j: (j, 0))
    inv2 = pl.BlockSpec((block_c, 1), lambda i, j: (j, 0))
    row = pl.BlockSpec((1, block_p), lambda i, j: (0, i))
    return grid, [bounds, points, centers, inv2], row


def _step_args(k: int, k_real: int, block_p: int, block_c: int,
               precision: str) -> dict:
    """Static arguments of ``_assign_step``."""
    return dict(k_real=k_real, masked=k_real < k, precision=precision,
                lanes=_lanes(block_c, block_p))


def _row_shapes(n: int):
    return [jax.ShapeDtypeStruct((1, n), jnp.int32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.float32)]


@functools.partial(jax.jit,
                   static_argnames=("k_real", "block_p", "block_c",
                                    "interpret", "precision"))
def assign_argmin_pallas(points, centers, inv2, tile_bounds, k_real: int,
                         block_p: int = 8192, block_c: int = 128,
                         interpret: bool | None = None,
                         precision: str = "f32"):
    """points [D, N] (lane-dense), centers [K, D] (pre-padded),
    inv2 [K] = 1/influence^2, tile_bounds [N/BP, K/BC], k_real = number of
    real (non-_FAR) centers. Returns (idx, best_eff_sq, second_eff_sq),
    each [N].

    ``interpret=None`` auto-detects: compiled on TPU, interpret elsewhere.
    Pass an explicit bool to override (e.g. interpret-mode debugging on
    TPU hosts). ``precision`` is the cross-term mode ("f32"/"bf16")."""
    if interpret is None:
        interpret = default_interpret()
    d, n = points.shape
    k = centers.shape[0]
    _check_tiling(n, k, block_p, block_c, "assign_argmin_pallas")
    grid, in_specs, row = _specs(n, d, k, block_p, block_c)
    kernel = functools.partial(
        _assign_kernel,
        **_step_args(k, k_real, block_p, block_c, precision))
    idx, best, second = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[row, row, row],
        out_shape=_row_shapes(n),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(tile_bounds[:, None, :], points, centers, inv2[:, None])
    return idx[0], best[0], second[0]


@functools.partial(jax.jit,
                   static_argnames=("k_real", "block_p", "block_c",
                                    "interpret", "precision"))
def assign_reduce_pallas(points, centers, inv2, tile_bounds, weights,
                         k_real: int, block_p: int = 8192,
                         block_c: int = 128,
                         interpret: bool | None = None,
                         precision: str = "f32"):
    """Fused assign+reduce: one pass over the point tiles returning
    (idx, best_eff_sq, second_eff_sq, moments [d+2, K]) with the moment
    block accumulated in VMEM across point tiles (sorted-center columns:
    rows 0..d-1 weighted coordinate sums, row d weighted counts, row d+1
    weighted best-eff-sq sums). Args as ``assign_argmin_pallas`` plus
    ``weights [N]`` (zero marks padded points)."""
    if interpret is None:
        interpret = default_interpret()
    d, n = points.shape
    k = centers.shape[0]
    _check_tiling(n, k, block_p, block_c, "assign_reduce_pallas")
    grid, in_specs, row = _specs(n, d, k, block_p, block_c)
    kernel = functools.partial(
        _assign_moments_kernel, moment_lanes=_lanes(k, block_p),
        **_step_args(k, k_real, block_p, block_c, precision))
    idx, best, second, moments = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs + [row],                                # weights
        out_specs=[row, row, row,
                   pl.BlockSpec((d + 2, k), lambda i, j: (0, 0))],  # moments
        out_shape=_row_shapes(n) + [
            jax.ShapeDtypeStruct((d + 2, k), jnp.float32)],
        # the moment block accumulates across BOTH grid dimensions, so the
        # point-tile dimension must be sequential too
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(tile_bounds[:, None, :], points, centers, inv2[:, None],
      weights[None, :])
    return idx[0], best[0], second[0], moments
