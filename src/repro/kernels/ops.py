"""jit'd public wrappers around the Pallas kernels, with model-layout
adapters ((B,S,H,D) <-> kernel layouts), padding to block multiples, and
automatic interpret-mode on non-TPU backends."""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import paged_attention as _pa
from repro.kernels import rmsnorm as _rn
from repro.kernels import ssd_scan as _ssd


@functools.partial(jax.jit, static_argnames=("causal", "sliding_window",
                                             "block_q", "block_k"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True,
                    sliding_window: Optional[int] = None,
                    block_q: int = 128, block_k: int = 128) -> jax.Array:
    """Model layout: q (B,Sq,H,D); k,v (B,Skv,HKV,D) -> (B,Sq,H,D)."""
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    qk = jnp.moveaxis(q, 1, 2)
    kk = jnp.moveaxis(k, 1, 2)
    vk = jnp.moveaxis(v, 1, 2)
    bq = min(block_q, max(16, Sq))
    bk = min(block_k, max(16, Skv))
    pad_q = (-Sq) % bq
    pad_k = (-Skv) % bk
    if pad_q:
        qk = jnp.pad(qk, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kk = jnp.pad(kk, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vk = jnp.pad(vk, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        # padded keys must never win the softmax: causal masking already
        # excludes them for q_idx < Skv; for padded q rows it's irrelevant.
        if not causal:
            raise NotImplementedError("non-causal padding needs kv_len mask")
    out = _fa.flash_attention(qk, kk, vk, causal=causal,
                              sliding_window=sliding_window,
                              sm_scale=1.0 / (D ** 0.5),
                              block_q=bq, block_k=bk)
    if pad_q:
        out = out[:, :, :Sq]
    return jnp.moveaxis(out, 1, 2)


@functools.partial(jax.jit, static_argnames=("sliding_window",))
def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    page_table: jax.Array, lengths: jax.Array,
                    layer: jax.Array, *,
                    sliding_window: Optional[int] = None) -> jax.Array:
    """Model layout: q (B,1,H,D) single decode token per sequence;
    k/v pages the stacked pool (L,P,ps,KV,D), read at int32 scalar
    ``layer``; page_table (B,PMAX); lengths (B,) valid KV tokens
    (including the just-written one) -> (B,1,H,D)."""
    B, S, H, D = q.shape
    assert S == 1, "paged attention is a decode (one-query) kernel"
    out = _pa.paged_decode_attention_layered(
        q[:, 0], k_pages, v_pages, page_table, lengths,
        jnp.reshape(layer, (1,)), sm_scale=1.0 / (D ** 0.5),
        sliding_window=sliding_window)
    return out[:, None]


rmsnorm = jax.jit(_rn.rmsnorm, static_argnames=("eps", "block_rows"))


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, dt, A, B_mat, C_mat, D, *, chunk: int = 128,
             init_state=None) -> Tuple[jax.Array, jax.Array]:
    return _ssd.ssd_scan(x, dt, A, B_mat, C_mat, D, chunk=chunk,
                         init_state=init_state)
