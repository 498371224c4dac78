"""GPU-portable ("triton-shaped") assignment backend.

Same math as ``assign_kernel.py`` — effective-distance argmin with
best/second tracking and optional fused moments — but structured the way
a Triton / Mosaic-GPU kernel wants it rather than the way a TPU Mosaic
kernel does (DESIGN.md §4c):

* **1-D grid over point tiles only.** Each program owns one ``[block_p,
  d]`` point tile and loops over center tiles with an in-kernel
  ``fori_loop`` + dynamic slices of the full ``[K, d]`` center block
  (centers are small enough to sit in every program's fast memory; on a
  GPU this is the classic "B matrix in L2/SMEM, loop over K tiles" shape).
  No second grid dimension means no cross-program sequential semantics.
* **Split-k moment partials.** Fused moments are written as one
  ``[d+2, K]`` partial *per program* and summed by the wrapper outside
  the kernel — the TPU kernel's grid-revisited VMEM accumulator has no
  portable GPU equivalent (it relies on Mosaic's sequential-grid
  guarantee), whereas partials + an XLA reduction lower everywhere.
* **No tile pruning.** The bbox-bound ``pl.when`` skip needs the
  sequential center-tile dimension to pay off; here every center tile is
  visited. The jnp-side center *sort* is skipped too — indices come out
  in original center order, no un-sort needed.
* Nothing TPU-only in the body: no manual DMA, no semaphores, no
  ``dimension_semantics`` requirements beyond a parallel 1-D grid —
  interpret-verified on CPU in CI (``REPRO_ASSIGN_BACKEND=triton`` leg)
  and lowerable through Mosaic-GPU unchanged.

Registered as ``triton`` with ``supports_moments=True``; ``auto``
resolves to it on GPU hosts (ops.resolve_assign_backend).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .assign_kernel import _check_tiling, _cross_term, default_interpret


def _sweep_centers(p, centers_ref, inv2_ref, *, block_c: int, k_real: int,
                   precision: str):
    """In-kernel loop over center tiles; returns the final
    (idx [BP], best [BP], second [BP]) carry in original center order."""
    bp = p.shape[0]
    kpad = centers_ref.shape[0]
    pn = jnp.sum(p * p, axis=1, keepdims=True)              # [BP, 1]

    def tile(j, carry):
        best0, second0, idx0 = carry
        c = centers_ref[pl.ds(j * block_c, block_c), :]     # [BC, D]
        inv2 = inv2_ref[:, pl.ds(j * block_c, block_c)]     # [1, BC]
        cn = jnp.sum(c * c, axis=1)[None, :]
        sq = pn + cn - 2.0 * _cross_term(p, c, precision)
        eff = jnp.maximum(sq, 0.0) * inv2                   # [BP, BC]
        cols = j * block_c + jax.lax.broadcasted_iota(
            jnp.int32, eff.shape, 1)
        eff = jnp.where(cols < k_real, eff, jnp.inf)

        local_idx = jnp.argmin(eff, axis=1).astype(jnp.int32)
        local_best = jnp.min(eff, axis=1)
        onehot = jax.nn.one_hot(local_idx, block_c, dtype=jnp.bool_)
        local_second = jnp.min(jnp.where(onehot, jnp.inf, eff), axis=1)

        take_new = local_best < best0
        best = jnp.where(take_new, local_best, best0)
        second = jnp.minimum(jnp.minimum(second0, local_second),
                             jnp.maximum(best0, local_best))
        idx = jnp.where(take_new, j * block_c + local_idx, idx0)
        return best, second, idx

    init = (jnp.full((bp,), jnp.inf, jnp.float32),
            jnp.full((bp,), jnp.inf, jnp.float32),
            jnp.full((bp,), -1, jnp.int32))
    best, second, idx = jax.lax.fori_loop(0, kpad // block_c, tile, init)
    return idx, best, second


def _triton_kernel(points_ref, centers_ref, inv2_ref, idx_ref, best_ref,
                   second_ref, *, block_c: int, k_real: int,
                   precision: str):
    idx, best, second = _sweep_centers(
        points_ref[...], centers_ref, inv2_ref, block_c=block_c,
        k_real=k_real, precision=precision)
    idx_ref[...] = idx
    best_ref[...] = best
    second_ref[...] = second


def _triton_moments_kernel(points_ref, centers_ref, inv2_ref, w_ref,
                           idx_ref, best_ref, second_ref, partial_ref, *,
                           block_c: int, k_real: int, precision: str):
    p = points_ref[...]
    idx, best, second = _sweep_centers(
        p, centers_ref, inv2_ref, block_c=block_c, k_real=k_real,
        precision=precision)
    idx_ref[...] = idx
    best_ref[...] = best
    second_ref[...] = second
    # split-k moment partial for THIS program's point tile, [1, d+2, K];
    # accumulation stays f32 regardless of the distance-matmul precision
    kpad = centers_ref.shape[0]
    onehot = idx[:, None] == jax.lax.broadcasted_iota(
        jnp.int32, (p.shape[0], kpad), 1)
    ww = jnp.where(onehot, w_ref[...][:, None], 0.0)         # [BP, K]
    stacked = jnp.concatenate(
        [p, jnp.ones((p.shape[0], 1), p.dtype), best[:, None]], axis=1)
    partial_ref[...] = jax.lax.dot_general(
        stacked, ww, (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)[None]            # [1, D+2, K]


@functools.partial(jax.jit,
                   static_argnames=("k_real", "block_p", "block_c",
                                    "interpret", "precision"))
def triton_assign_pallas(points, centers, inv2, k_real: int,
                         block_p: int = 256, block_c: int = 128,
                         interpret: bool | None = None,
                         precision: str = "f32"):
    if interpret is None:
        interpret = default_interpret()
    n, d = points.shape
    k = centers.shape[0]
    _check_tiling(n, k, block_p, block_c, "triton_assign_pallas")
    kernel = functools.partial(_triton_kernel, block_c=block_c,
                               k_real=k_real, precision=precision)
    return pl.pallas_call(
        kernel,
        grid=(n // block_p,),
        in_specs=[
            pl.BlockSpec((block_p, d), lambda i: (i, 0)),
            pl.BlockSpec((k, d), lambda i: (0, 0)),
            pl.BlockSpec((1, k), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_p,), lambda i: (i,)),
            pl.BlockSpec((block_p,), lambda i: (i,)),
            pl.BlockSpec((block_p,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.float32),
            jax.ShapeDtypeStruct((n,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(points, centers, inv2[None, :])


@functools.partial(jax.jit,
                   static_argnames=("k_real", "block_p", "block_c",
                                    "interpret", "precision"))
def triton_assign_reduce_pallas(points, centers, inv2, weights,
                                k_real: int, block_p: int = 256,
                                block_c: int = 128,
                                interpret: bool | None = None,
                                precision: str = "f32"):
    if interpret is None:
        interpret = default_interpret()
    n, d = points.shape
    k = centers.shape[0]
    _check_tiling(n, k, block_p, block_c, "triton_assign_reduce_pallas")
    kernel = functools.partial(_triton_moments_kernel, block_c=block_c,
                               k_real=k_real, precision=precision)
    n_pt = n // block_p
    idx, best, second, partials = pl.pallas_call(
        kernel,
        grid=(n_pt,),
        in_specs=[
            pl.BlockSpec((block_p, d), lambda i: (i, 0)),
            pl.BlockSpec((k, d), lambda i: (0, 0)),
            pl.BlockSpec((1, k), lambda i: (0, 0)),
            pl.BlockSpec((block_p,), lambda i: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((block_p,), lambda i: (i,)),
            pl.BlockSpec((block_p,), lambda i: (i,)),
            pl.BlockSpec((block_p,), lambda i: (i,)),
            pl.BlockSpec((1, d + 2, k), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.float32),
            jax.ShapeDtypeStruct((n,), jnp.float32),
            jax.ShapeDtypeStruct((n_pt, d + 2, k), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(points, centers, inv2[None, :], weights)
    # split-k reduction of the per-program partials (deterministic XLA sum)
    return idx, best, second, partials.sum(axis=0)


def _pad_inputs(points, centers, influence, block_p, block_c):
    from .ops import _FAR
    n = points.shape[0]
    k = centers.shape[0]
    inv2 = 1.0 / (influence * influence)
    pad_n = (-n) % block_p
    pad_k = (-k) % block_c
    pts = jnp.pad(points, ((0, pad_n), (0, 0))).astype(jnp.float32)
    cts = jnp.pad(centers, ((0, pad_k), (0, 0)),
                  constant_values=_FAR).astype(jnp.float32)
    iv2 = jnp.pad(inv2, (0, pad_k), constant_values=1.0).astype(jnp.float32)
    return pts, cts, iv2


def triton_assign_backend(points, centers, influence, *,
                          chunk: int | None = None, block_p: int = 256,
                          block_c: int = 128, weights=None,
                          return_moments: bool = False,
                          precision: str = "f32"):
    """Registry adapter (``chunk`` ignored: the grid's point tiling bounds
    fast-memory use). Unlike the ``pallas`` backend there is no center
    sort, so indices and moments come out in original center order."""
    del chunk
    n = points.shape[0]
    k = centers.shape[0]
    pts, cts, iv2 = _pad_inputs(points, centers, influence, block_p,
                                block_c)
    if return_moments:
        if weights is None:
            raise ValueError("return_moments=True requires weights")
        w = jnp.pad(weights, (0, pts.shape[0] - n)).astype(jnp.float32)
        idx, best, second, m = triton_assign_reduce_pallas(
            pts, cts, iv2, w, k_real=k, block_p=block_p, block_c=block_c,
            interpret=default_interpret(), precision=precision)
        return (idx[:n], best[:n], second[:n],
                m.T[:k, :points.shape[1]], m[points.shape[1], :k],
                m[points.shape[1] + 1, :k])
    idx, best, second = triton_assign_pallas(
        pts, cts, iv2, k_real=k, block_p=block_p, block_c=block_c,
        interpret=default_interpret(), precision=precision)
    return idx[:n], best[:n], second[:n]


def _register():
    from .ops import register_assign_backend
    register_assign_backend("triton",
                            supports_moments=True)(triton_assign_backend)


_register()
