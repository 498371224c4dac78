"""Pallas TPU kernel: fused effective-distance assignment (paper's hot loop).

Computes, for every point p, the cluster c minimizing
``sqdist(p, c) / influence(c)^2`` together with the best and second-best
effective squared distances (needed for the Hamerly bounds, Eqs. 4-5).
With ``with_moments=True`` the same pass also accumulates the per-cluster
weighted moments (Alg. 2's movement reductions) in a VMEM block revisited
across point tiles, so the point array is streamed exactly once.

TPU adaptation of the paper's geometric optimizations (DESIGN.md §4):

* The pairwise-distance inner loop becomes an MXU matmul per
  (point-tile × center-tile): ``sq = |p|^2 + |c|^2 - 2 p @ c^T``.
* The paper's per-point Hamerly branch and bounding-box center ordering
  become **tile-level pruning**: the wrapper (ops.py) precomputes a lower
  bound on the effective sqdist between each point-tile's bounding box and
  each center-tile; inside the kernel a whole center-tile is skipped via
  ``pl.when`` when its bound cannot beat the tile's current worst
  second-best. Centers are pre-sorted by distance to the local bounding box
  (paper Alg. 1 line 6) so prunable tiles appear late in the ``arbitrary``
  grid dimension.
* Padded centers (the ``_FAR`` rows the wrapper appends to reach a
  ``block_c`` multiple) are masked to ``+inf`` effective distance by the
  static real-center count ``k_real`` — the distance math itself is never
  trusted for them (``|FAR|^2`` overflows f32 and can turn into NaN via
  ``inf - inf`` for large-coordinate inputs, which used to corrupt both
  the argmin and the second-best).
* Running (best, second, argmin) accumulators live in the output VMEM
  blocks, revisited across the center-tile grid dimension. In moments mode
  the ``[d+2, K]`` moment block (csum rows, weight row, radius row) is
  revisited across the *point*-tile dimension as well: each point tile
  adds its one-hot-matmul partial after its last center tile, so both grid
  dimensions become ``arbitrary`` (sequential) to keep the accumulation
  well-defined.
* Every per-point vector (weights in, idx/best/second out) is a ``[1, N]``
  row with ``(1, block_p)`` blocks, and the per-tile prune bounds are
  read as SMEM scalars. Both keep every block legal for Mosaic's
  (8, 128) tiling rule, also under ``vmap`` (the batched hierarchical
  refinement), where a 1-D ``(block_p,)`` block would become an illegal
  ``(1, block_p)`` slice of a ``[B, N]`` array.
* ``precision="bf16"`` computes the ``p @ c^T`` cross term on the MXU in
  bf16 (f32 accumulation); the norms ``|p|^2``/``|c|^2``, the Hamerly
  best/second accumulators and the moment block stay f32. Tolerance
  bounds documented in DESIGN.md §4c.

Grid: ``(n_point_tiles, n_center_tiles)``. VMEM per step: BP*D + BC*D +
BP*BC floats (+ 3 BP-sized accumulators, + BP + (d+2)*K + BP*K in moments
mode) — e.g. BP=1024, BC=128, D<=128, K=1024 → ~5.5 MB, under the
~16 MB v5e VMEM budget, with BP*BC = 1024x128 matching MXU tiling
(multiples of 128 on the lane dimension).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

PRECISIONS = ("f32", "bf16")


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
    """-2 p @ c^T cross term of the squared distance, [BP, BC] f32.

    ``f32`` asks for full f32 contraction (``Precision.HIGHEST``): the
    TPU's default f32 matmul rounds its operands to bfloat16, which the
    f32 mode must not do. ``bf16`` casts both operands to bfloat16 before
    the MXU matmul (accumulation stays f32 via ``preferred_element_type``):
    half the operand bandwidth and double the MXU rate on TPU, at a
    relative distance error bounded by ~2^-8 per coordinate product."""
    if precision == "bf16":
        p = p.astype(jnp.bfloat16)
        c = c.astype(jnp.bfloat16)
        exact = None
    else:
        exact = jax.lax.Precision.HIGHEST
    return jax.lax.dot_general(p, c, (((1,), (1,)), ((), ())),
                               precision=exact,
                               preferred_element_type=jnp.float32)


def _assign_step(p, bounds_ref, centers_ref, inv2_ref, idx_ref, best_ref,
                 second_ref, *, block_c: int, k_real: int, precision: str):
    """One (point-tile × center-tile) grid step: init at the first center
    tile, tile-level bbox pruning, distance matmul + running
    (best, second, argmin) update in the ``[1, BP]`` output rows."""
    j = pl.program_id(1)

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
        c = centers_ref[...]                   # [BC, D]
        inv2 = inv2_ref[...]                   # [1, BC]
        pn = jnp.sum(p * p, axis=1, keepdims=True)          # [BP, 1]
        cn = jnp.sum(c * c, axis=1)[None, :]                # [1, BC]
        sq = pn + cn - 2.0 * _cross_term(p, c, precision)   # [BP, BC]
        eff = jnp.maximum(sq, 0.0) * inv2                   # [BP, BC]
        # mask padded (_FAR) centers to +inf: their f32 distance overflows
        # (or NaNs via inf - inf) and must never reach argmin/second
        cols = j * block_c + jax.lax.broadcasted_iota(
            jnp.int32, eff.shape, 1)
        eff = jnp.where(cols < k_real, eff, jnp.inf)

        local_idx = jnp.argmin(eff, axis=1).astype(jnp.int32)
        local_best = jnp.min(eff, axis=1)
        bc = eff.shape[1]
        onehot = jax.nn.one_hot(local_idx, bc, dtype=jnp.bool_)
        local_second = jnp.min(jnp.where(onehot, jnp.inf, eff), axis=1)

        old_best = best_ref[0]
        old_second = second_ref[0]
        old_idx = idx_ref[0]
        take_new = local_best < old_best
        new_best = jnp.where(take_new, local_best, old_best)
        new_second = jnp.minimum(
            jnp.minimum(old_second, local_second),
            jnp.maximum(old_best, local_best))
        new_idx = jnp.where(take_new, j * block_c + local_idx, old_idx)
        best_ref[0] = new_best
        second_ref[0] = new_second
        idx_ref[0] = new_idx


def _moments_step(p, w_ref, idx_ref, best_ref, moments_ref):
    """Moment accumulation into the grid-wide ``[d+2, K]`` VMEM block:
    rows ``0..d-1`` hold the weighted coordinate sums, row ``d`` the
    weighted counts, row ``d+1`` the weighted best effective-sq distances
    — all in *sorted-center* column space (the wrapper un-sorts). Each
    point tile contributes its one-hot matmul partial once, after its
    final center tile. Accumulation is always f32, independent of the
    distance-matmul precision."""
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _zero():
        moments_ref[...] = jnp.zeros_like(moments_ref)

    @pl.when(j == pl.num_programs(1) - 1)
    def _accumulate():
        w = w_ref[0]                                         # [BP]
        idx = idx_ref[0]                                     # [BP]
        best = best_ref[0]                                   # [BP]
        kpad = moments_ref.shape[1]
        onehot = idx[:, None] == jax.lax.broadcasted_iota(
            jnp.int32, (p.shape[0], kpad), 1)                # [BP, K]
        ww = jnp.where(onehot, w[:, None], 0.0)              # [BP, K]
        stacked = jnp.concatenate(
            [p, jnp.ones((p.shape[0], 1), p.dtype), best[:, None]],
            axis=1)                                          # [BP, D+2]
        moments_ref[...] += jax.lax.dot_general(
            stacked, ww, (((0,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)              # [D+2, K]


def _assign_kernel(bounds_ref, points_ref, centers_ref, inv2_ref,
                   idx_ref, best_ref, second_ref, *, block_c: int,
                   k_real: int, precision: str):
    _assign_step(points_ref[...], bounds_ref, centers_ref, inv2_ref,
                 idx_ref, best_ref, second_ref, block_c=block_c,
                 k_real=k_real, precision=precision)


def _assign_moments_kernel(bounds_ref, points_ref, centers_ref, inv2_ref,
                           w_ref, idx_ref, best_ref, second_ref,
                           moments_ref, *, block_c: int, k_real: int,
                           precision: str):
    p = points_ref[...]
    _assign_step(p, bounds_ref, centers_ref, inv2_ref, idx_ref, best_ref,
                 second_ref, block_c=block_c, k_real=k_real,
                 precision=precision)
    _moments_step(p, w_ref, idx_ref, best_ref, moments_ref)


def default_interpret() -> bool:
    """Backend auto-detection: run the Mosaic-compiled kernel on real TPUs,
    the Pallas interpreter everywhere else (CPU CI containers, GPU hosts)."""
    return jax.default_backend() != "tpu"


def _specs(n: int, d: int, k: int, block_p: int, block_c: int):
    """Grid and the blocks shared by both kernels: the point tile's row of
    prune bounds in SMEM (``[N/BP, 1, K/BC]``, one ``(1, K/BC)`` block per
    point tile, so SMEM use grows with K only), point/center tiles, the
    ``[1, K]`` inverse-influence row, and the ``[1, N]`` per-point rows
    (weights, idx, best, second)."""
    grid = (n // block_p, k // block_c)
    bounds = pl.BlockSpec((None, 1, grid[1]), lambda i, j: (i, 0, 0),
                          memory_space=pltpu.SMEM)
    points = pl.BlockSpec((block_p, d), lambda i, j: (i, 0))
    centers = pl.BlockSpec((block_c, d), lambda i, j: (j, 0))
    inv2 = pl.BlockSpec((1, block_c), lambda i, j: (0, j))
    row = pl.BlockSpec((1, block_p), lambda i, j: (0, i))
    return grid, [bounds, points, centers, inv2], row


def _row_shapes(n: int):
    return [jax.ShapeDtypeStruct((1, n), jnp.int32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.float32)]


@functools.partial(jax.jit,
                   static_argnames=("k_real", "block_p", "block_c",
                                    "interpret", "precision"))
def assign_argmin_pallas(points, centers, inv2, tile_bounds, k_real: int,
                         block_p: int = 1024, block_c: int = 128,
                         interpret: bool | None = None,
                         precision: str = "f32"):
    """points [N, D], centers [K, D] (pre-padded), inv2 [K] = 1/influence^2,
    tile_bounds [N/BP, K/BC], k_real = number of real (non-_FAR) centers.
    Returns (idx, best_eff_sq, second_eff_sq), each [N].

    ``interpret=None`` auto-detects: compiled on TPU, interpret elsewhere.
    Pass an explicit bool to override (e.g. interpret-mode debugging on
    TPU hosts). ``precision`` is the distance-matmul mode ("f32"/"bf16")."""
    if interpret is None:
        interpret = default_interpret()
    n, d = points.shape
    k = centers.shape[0]
    _check_tiling(n, k, block_p, block_c, "assign_argmin_pallas")
    grid, in_specs, row = _specs(n, d, k, block_p, block_c)
    kernel = functools.partial(_assign_kernel, block_c=block_c,
                               k_real=k_real, precision=precision)
    idx, best, second = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[row, row, row],
        out_shape=_row_shapes(n),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(tile_bounds[:, None, :], points, centers, inv2[None, :])
    return idx[0], best[0], second[0]


@functools.partial(jax.jit,
                   static_argnames=("k_real", "block_p", "block_c",
                                    "interpret", "precision"))
def assign_reduce_pallas(points, centers, inv2, tile_bounds, weights,
                         k_real: int, block_p: int = 1024,
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
    n, d = points.shape
    k = centers.shape[0]
    _check_tiling(n, k, block_p, block_c, "assign_reduce_pallas")
    grid, in_specs, row = _specs(n, d, k, block_p, block_c)
    kernel = functools.partial(_assign_moments_kernel, block_c=block_c,
                               k_real=k_real, precision=precision)
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
    )(tile_bounds[:, None, :], points, centers, inv2[None, :],
      weights[None, :])
    return idx[0], best[0], second[0], moments
