"""Pallas TPU kernel: causal flash attention (forward).

This is the TPU-native replacement for the pure-JAX chunked attention in
``models/layers.py`` (`_flash_full`): on real hardware the online-softmax
inner loop runs per (batch*head, q-tile, kv-tile) grid cell with running
(m, l, acc) accumulators in VMEM scratch, and **strictly-above-diagonal
kv-tiles are skipped** via ``pl.when`` — the triangular schedule that the
SPMD-level JAX path can only do when the sequence axis is unsharded.

Grid: ``(B*H, S/bq, S/bk)`` with semantics ("parallel","parallel",
"arbitrary"). VMEM per step: bq*dh (q) + 2*bk*dh (k,v) + bq*bk (scores)
+ bq*(dh+2) f32 scratch — bq=bk=512, dh=128: ~1.6 MB, MXU-aligned.

GQA is handled in the BlockSpec index maps: query head h reads kv head
``h // (H/KV)``; no head replication is materialized.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  bq: int, bk: int, softcap: float, scale: float):
    i = pl.program_id(1)      # q tile
    j = pl.program_id(2)      # kv tile
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal: skip tiles strictly above the diagonal
    @pl.when(j * bk <= i * bq + bq - 1)
    def _compute():
        q = q_ref[0].astype(jnp.float32)               # [bq, dh]
        k = k_ref[0].astype(jnp.float32)               # [bk, dh]
        v = v_ref[0].astype(jnp.float32)               # [bk, dh]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(kpos <= qpos, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == nj - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bq", "bk", "softcap", "interpret"))
def flash_attention_pallas(q, k, v, bq: int = 512, bk: int = 512,
                           softcap: float = 0.0, interpret: bool = True):
    """q: [BH, S, dh] (already GQA-expanded indexing via wrapper),
    k/v: [BKV, S, dh]; BH = B*H, BKV = B*KV with the head mapping done by
    the BlockSpec index maps. Returns o: [BH, S, dh]."""
    BH, S, dh = q.shape
    BKV = k.shape[0]
    G = BH // BKV
    if S % bq != 0 or S % bk != 0:
        raise ValueError(
            f"flash_attention_pallas: sequence length {S} must be a "
            f"multiple of the query tile bq={bq} and the key tile "
            f"bk={bk}; pad the sequence or pass matching tile sizes")
    grid = (BH, S // bq, S // bk)
    scale = dh ** -0.5
    kernel = functools.partial(_flash_kernel, bq=bq, bk=bk,
                               softcap=softcap, scale=scale)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, dh), lambda h, i, j: (h // G, j, 0)),
            pl.BlockSpec((1, bk, dh), lambda h, i, j: (h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
