"""Pallas TPU kernel for the Mamba2 SSD chunked scan (arXiv:2405.21060).

TPU-native adaptation: one grid cell per (batch·head, chunk); the chunk
dimension is sequential ('arbitrary') and the inter-chunk SSM state
(head_dim × d_state, fp32) is carried in VMEM scratch — the analogue of the
CUDA implementation's split into BMM-heavy intra-chunk work (MXU-friendly
Q×Q and Q×N matmuls) plus a tiny carried recurrence, with no HBM round-trip
for the state.

Per chunk:
    y_intra = ((C Bᵀ) ⊙ decay_mask ⊙ dtⱼ) · x
    y_inter = exp(cum) ⊙ (C · stateᵀ)
    state   = exp(total)·state + Σⱼ exp(total-cumⱼ)·dtⱼ·xⱼ⊗Bⱼ
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssd_scan_kernel", "ssd_scan_call"]


def ssd_scan_kernel(
    x_ref,    # (1, Q, P)
    dt_ref,   # (1, Q)
    a_ref,    # (1, 1)   A for this head (negative)
    b_ref,    # (1, Q, N)
    c_ref,    # (1, Q, N)
    d_ref,    # (1, 1)   D skip for this head
    y_ref,    # (1, Q, P)
    state_scr,  # VMEM (P, N) fp32
    *,
    chunk: int,
):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0].astype(jnp.float32)      # (Q, P)
    dt = dt_ref[0].astype(jnp.float32)    # (Q,)
    a = a_ref[0, 0].astype(jnp.float32)   # scalar
    bb = b_ref[0].astype(jnp.float32)     # (Q, N)
    cc = c_ref[0].astype(jnp.float32)     # (Q, N)
    dskip = d_ref[0, 0].astype(jnp.float32)

    la = dt * a                            # (Q,) log decay
    cum = jnp.cumsum(la)                   # inclusive
    total = cum[-1]

    # intra-chunk: masked decay matrix (exponent masked BEFORE exp)
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    expnt = jnp.where(ii >= jj, cum[:, None] - cum[None, :], -jnp.inf)
    cb = jax.lax.dot_general(cc, bb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    scores = cb * jnp.exp(expnt) * dt[None, :]
    y = jax.lax.dot_general(scores, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    # inter-chunk: contribution of the carried state
    state = state_scr[...]                 # (P, N)
    y += jnp.exp(cum)[:, None] * jax.lax.dot_general(
        cc, state, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )

    # state update
    w = jnp.exp(total - cum) * dt          # (Q,)
    xw = x * w[:, None]                    # (Q, P)
    new_contrib = jax.lax.dot_general(
        xw, bb, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (P, N)
    state_scr[...] = jnp.exp(total) * state + new_contrib

    y_ref[0] = (y + dskip * x).astype(y_ref.dtype)


def ssd_scan_call(
    x: jax.Array,   # (BH, S, P)
    dt: jax.Array,  # (BH, S)
    A: jax.Array,   # (BH, 1)
    B_: jax.Array,  # (BG, S, N)  BG = batch (B/C shared across heads)
    C_: jax.Array,  # (BG, S, N)
    D_: jax.Array,  # (BH, 1)
    *,
    heads: int,
    chunk: int = 256,
    interpret: bool = False,
) -> jax.Array:
    bh, s, p = x.shape
    n = B_.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0
    nc = s // chunk

    kernel = functools.partial(ssd_scan_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk), lambda b, c: (b, c)),
            pl.BlockSpec((1, 1), lambda b, c: (b, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, c: (b // heads, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, c: (b // heads, c, 0)),
            pl.BlockSpec((1, 1), lambda b, c: (b, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, p), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="ssd_scan",
    )(x, dt, A, B_, C_, D_)
