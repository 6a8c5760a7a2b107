"""Pallas TPU flash-attention forward kernel (causal / bidirectional, GQA).

TPU-native adaptation of FlashAttention (arXiv:2205.14135): online-softmax
over KV blocks streamed HBM→VMEM via BlockSpec tiling, fp32 accumulators in
VMEM scratch, MXU-aligned (multiple-of-128) block shapes.  GQA is handled by
folding the query-group dimension into the grid and mapping G query rows
onto one KV head via the index map (no KV replication in HBM).

Grid: (batch·kv_heads·groups, q_blocks, kv_blocks) — kv innermost,
sequential ('arbitrary'), so the scratch accumulators carry across KV steps.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_kernel", "flash_attention_call"]

NEG_INF = -1e30


def flash_attention_kernel(
    q_ref, k_ref, v_ref,  # (1, Bq, d), (1, Bk, d), (1, Bk, d)
    o_ref,                # (1, Bq, d)
    m_scr, l_scr, acc_scr,  # VMEM scratch: (Bq, 1), (Bq, 1), (Bq, d)
    *,
    causal: bool,
    sm_scale: float,
    block_q: int,
    block_k: int,
    seq_k: int,
    q_offset: int,
):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # absolute positions of this block's rows/cols
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + q_offset
    k_pos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    # skip fully-masked blocks (upper triangle) entirely
    run = True
    if causal:
        run = (kj * block_k) <= (qi * block_q + q_offset + block_q - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # (Bq, Bk)
        mask = k_pos < seq_k
        if causal:
            mask = mask & (k_pos <= q_pos)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                       # (Bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                    # (Bq, Bk)
        l_new = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(kj == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-37)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention_call(
    q: jax.Array,  # (BH, Sq, d)  BH = batch*kv_heads*groups
    k: jax.Array,  # (BK, Sk, d)  BK = batch*kv_heads
    v: jax.Array,
    *,
    groups: int,
    causal: bool,
    q_offset: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    bh, sq, d = q.shape
    _, sk, _ = k.shape
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(sk, block_k)
    sm_scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(
        flash_attention_kernel,
        causal=causal,
        sm_scale=sm_scale,
        block_q=block_q,
        block_k=block_k,
        seq_k=sk,
        q_offset=q_offset,
    )
    return pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // groups, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // groups, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v)
