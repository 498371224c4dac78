"""Pallas TPU kernel: fused balanced-k-means MoE router (top-k).

The paper's assignment step specialized to expert routing: for each token
``t``, compute the effective squared distance to every expert centroid
``sqdist(x_t, c_e) / influence_e^2`` (MXU matmul per token-tile) and
extract the top-k closest experts in-register — one kernel instead of a
distance matmul + k passes of argmin over HBM.

E no longer has to fit one VMEM tile: the kernel is routed through the
same center-tiling scheme as the assignment kernel (DESIGN.md §4c) — a
second grid dimension sweeps ``block_e``-expert tiles sequentially while
the ``[bt, top_k]`` output blocks are revisited as running top-k
accumulators. Each tile's effective distances are concatenated with the
running top-k and the top-k re-extracted by a static unrolled (min, mask)
loop over the ``[bt, top_k + block_e]`` candidate row. Padded experts
(``e_real`` mask) are held at ``FAR`` *before* the merge, so they can
never displace a real expert and the large-coordinate ``inf - inf`` NaN
hazard of trusting FAR-row distance math is gone (same fix as the
assignment kernel's ``k_real`` mask).

Grid: ``(T/bt, E_pad/block_e)``, VMEM per step: bt*D + block_e*D +
bt*block_e + 2*bt*top_k floats (bt=256, D<=8192, block_e=128 -> ~10 MB at
the llama4 scale; drop bt to 128 for d_model=8192).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

FAR = 1e30


def _router_kernel(x_ref, c_ref, inv2_ref, idx_ref, eff_ref, *, top_k: int,
                   block_e: int, e_real: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        idx_ref[...] = jnp.full_like(idx_ref, -1)
        eff_ref[...] = jnp.full_like(eff_ref, FAR)

    x = x_ref[...].astype(jnp.float32)                  # [bt, D]
    c = c_ref[...].astype(jnp.float32)                  # [block_e, D]
    inv2 = inv2_ref[...]                                # [1, block_e]
    xn = jnp.sum(x * x, axis=1, keepdims=True)
    cn = jnp.sum(c * c, axis=1)[None, :]
    sq = xn + cn - 2.0 * jax.lax.dot_general(
        x, c, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    eff = jnp.maximum(sq, 0.0) * inv2                   # [bt, block_e]
    # mask padded experts BEFORE the merge (k_real-style NaN/FAR guard)
    cols = j * block_e + jax.lax.broadcasted_iota(jnp.int32, eff.shape, 1)
    eff = jnp.where(cols < e_real, eff, FAR)

    # merge this tile into the running top-k: candidates = running top-k
    # (positions 0..top_k-1, so earlier tiles win ties) + the tile row
    cand_eff = jnp.concatenate([eff_ref[...], eff], axis=1)
    cand_idx = jnp.concatenate([idx_ref[...], cols], axis=1)
    width = top_k + block_e
    for ki in range(top_k):
        best = jnp.argmin(cand_eff, axis=1).astype(jnp.int32)
        taken = jax.nn.one_hot(best, width, dtype=jnp.bool_)
        idx_ref[:, ki] = jnp.sum(
            jnp.where(taken, cand_idx, 0), axis=1).astype(jnp.int32)
        eff_ref[:, ki] = jnp.min(cand_eff, axis=1)
        cand_eff = jnp.where(taken, FAR, cand_eff)


@functools.partial(jax.jit,
                   static_argnames=("top_k", "bt", "block_e", "e_real",
                                    "interpret"))
def router_topk_pallas(x, centroids, inv2, top_k: int, bt: int = 256,
                       block_e: int = 128, e_real: int | None = None,
                       interpret: bool = True):
    """x: [T, D] (T % bt == 0), centroids: [E, D] (E % block_e == 0),
    inv2: [E]. ``e_real`` = number of real (non-padded) experts.
    Returns (idx [T, top_k] int32, eff [T, top_k] f32)."""
    T, D = x.shape
    E = centroids.shape[0]
    if e_real is None:
        e_real = E
    if T % bt != 0:
        raise ValueError(
            f"router_topk_pallas: token axis T={T} is not a multiple of "
            f"bt={bt}; pad the token array (ops.router_topk does this)")
    if E % block_e != 0:
        raise ValueError(
            f"router_topk_pallas: expert axis E={E} is not a multiple of "
            f"block_e={block_e}; pad the centroid array (ops.router_topk "
            "does this)")
    kernel = functools.partial(_router_kernel, top_k=top_k,
                               block_e=block_e, e_real=e_real)
    return pl.pallas_call(
        kernel,
        grid=(T // bt, E // block_e),
        in_specs=[
            pl.BlockSpec((bt, D), lambda i, j: (i, 0)),
            pl.BlockSpec((block_e, D), lambda i, j: (j, 0)),
            pl.BlockSpec((1, block_e), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bt, top_k), lambda i, j: (i, 0)),
            pl.BlockSpec((bt, top_k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, top_k), jnp.int32),
            jax.ShapeDtypeStruct((T, top_k), jnp.float32),
        ],
        # outputs are revisited running accumulators along the expert-tile
        # dimension -> it must be sequential; token tiles stay parallel
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, centroids, inv2[None, :])
