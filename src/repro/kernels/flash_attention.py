"""Blocked (flash) attention Pallas TPU kernel.

TPU-native adaptation: q/k tiles sized for VMEM, MXU-aligned (multiples
of 128 on the contracted dims), online-softmax accumulation in fp32
scratch that persists across the sequential KV grid dimension.  Supports
causal masking, sliding windows (mixtral) and GQA head mapping directly
in the index maps (no KV replication in HBM).

Layout: q (B, H, Sq, D); k, v (B, HKV, Skv, D); out (B, H, Sq, D).
Grid: (B, H, Sq/bq, Skv/bk) with the KV dim sequential ("arbitrary").
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            sm_scale: float, causal: bool, block_q: int, block_k: int,
            sliding_window: Optional[int], n_kv_blocks: int):
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)            # (bq, D)
    k = k_ref[0, 0].astype(jnp.float32)            # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)            # (bk, D)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * sm_scale                                # (bq, bk)

    q_idx = pl.program_id(2) * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_idx = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), dtype=jnp.bool_)
    if causal:
        mask &= k_idx <= q_idx
    if sliding_window is not None:
        mask &= k_idx > (q_idx - sliding_window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                             # (bq,)
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    # fully-masked rows: p underflows to exp(NEG_INF - NEG_INF) = 1; kill
    p = jnp.where(mask, p, 0.0)

    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_cur

    @pl.when(j == n_kv_blocks - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True,
                    sliding_window: Optional[int] = None,
                    sm_scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None) -> jax.Array:
    """q: (B, H, Sq, D); k, v: (B, HKV, Skv, D) -> (B, H, Sq, D)."""
    B, H, Sq, D = q.shape
    _, HKV, Skv, _ = k.shape
    assert H % HKV == 0
    group = H // HKV
    if sm_scale is None:
        sm_scale = 1.0 / (D ** 0.5)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    assert Sq % block_q == 0 and Skv % block_k == 0, (
        "pad sequences to block multiples in ops.flash_attention")
    n_kv_blocks = Skv // block_k

    kernel = functools.partial(
        _kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_k=block_k, sliding_window=sliding_window,
        n_kv_blocks=n_kv_blocks)

    return pl.pallas_call(
        kernel,
        grid=(B, H, Sq // block_q, n_kv_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j: (b, h // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
