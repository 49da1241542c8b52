"""Paged decode-attention Pallas TPU kernel.

Decode-time attention where each sequence's K/V lives in fixed-size
pages scattered across a shared device-side page pool (the vLLM /
PagedAttention layout, realized on the paper's tier-1 HBM pool): a
per-sequence page table maps logical page ``i`` to a physical page id,
and the kernel gathers K/V pages *through the table* — no contiguity
and no per-sequence slab reservation.  This is the kernel that lets
``repro.serve`` drop the whole-sequence-resident requirement.

Layouts (kernel-native):
  q            (B, H, D)        one query token per sequence
  k/v pages    (P, ps, KV, D)   the shared pool; P physical pages of
                                ``ps`` tokens each (pool row P-1 may be
                                a scratch/trash page — the kernel never
                                reads positions >= lengths[b])
  page_table   (B, PMAX) int32  logical -> physical ids; entries
                                past a sequence's live pages must still
                                be *valid* pool indices (point them at
                                the trash page)
  lengths      (B,) int32       valid KV tokens per sequence (0 for an
                                idle row: output is all-zeros)
  out          (B, H, D)

Grid: (B, PMAX) with the page dimension sequential ("arbitrary") —
online-softmax state persists across pages in fp32 VMEM scratch exactly
like the flash kernel.  The page table and the lengths ride in as
scalar-prefetch operands so the K/V BlockSpec index maps can resolve
the physical page id before the body runs (one DMA per logical page,
skipped pages cost a no-op body via ``pl.when``).

Every block spans its array's full trailing two dims, which is what the
TPU's (8, 128) tiling rule accepts for any head count and head dim: q
and out move as ``(1, H, D)``, and one page of K/V moves as
``(1, ps*KV, D)`` — the pool viewed token-major / head-minor, a free
reshape in HBM.  All H query heads score against all ``ps*KV`` rows of
the page in one matmul; the ``tok`` operand maps each (query head, row)
pair to the row's token offset when the row belongs to that head's KV
group and to a huge offset otherwise, so one ``pos < length`` compare
masks both the GQA grouping and the sequence end.  Masked scores get
exactly zero weight, so the result is the grouped attention and K/V is
never replicated in HBM.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_OTHER_GROUP = 1 << 30      # token offset of a row outside the head's group
_F32 = jax.lax.Precision.HIGHEST   # fp32 matmul math, as in kernels/ref.py


def _kernel(pt_ref, len_ref, tok_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, sm_scale: float, page_size: int,
            n_pages_max: int, sliding_window: Optional[int]):
    b = pl.program_id(0)
    j = pl.program_id(1)                       # logical page (sequential)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]

    @pl.when(j * page_size < length)           # page holds live tokens
    def _update():
        q = q_ref[0].astype(jnp.float32)       # (H, D)
        k = k_ref[0].astype(jnp.float32)       # (ps*KV, D)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                precision=_F32,
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                       # (H, ps*KV)

        pos = j * page_size + tok_ref[...]
        mask = pos < length
        if sliding_window is not None:
            # the (single) query sits at absolute position length - 1
            mask &= pos > (length - 1 - sliding_window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                    # (H, 1)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        p = jnp.where(mask, p, 0.0)            # fully-masked cols stay dead

        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), precision=_F32,
            preferred_element_type=jnp.float32)
        m_ref[...] = m_cur

    @pl.when(j == n_pages_max - 1)
    def _finish():
        # length == 0 rows never update: l == 0 -> output exactly 0
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _token_offsets(H: int, KV: int, ps: int) -> jax.Array:
    """(H, ps*KV) int32: the token offset of page row ``c`` for query head
    ``r`` when row ``c`` holds that head's KV group, else a huge offset."""
    G = H // KV
    c = jnp.arange(ps * KV, dtype=jnp.int32)[None, :]
    r = jnp.arange(H, dtype=jnp.int32)[:, None]
    return jnp.where(c % KV == r // G, c // KV, _OTHER_GROUP)


def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, page_table: jax.Array,
                           lengths: jax.Array, *,
                           sm_scale: Optional[float] = None,
                           sliding_window: Optional[int] = None,
                           interpret: Optional[bool] = None) -> jax.Array:
    """q (B,H,D); k/v pages (P,ps,KV,D); page_table (B,PMAX) int32;
    lengths (B,) int32 -> (B,H,D)."""
    B, H, D = q.shape
    P, ps, KV, _ = k_pages.shape
    PMAX = page_table.shape[1]
    assert H % KV == 0, (H, KV)
    assert v_pages.shape == k_pages.shape
    assert page_table.shape[0] == B and lengths.shape == (B,)
    if sm_scale is None:
        sm_scale = 1.0 / (D ** 0.5)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    kernel = functools.partial(
        _kernel, sm_scale=sm_scale, page_size=ps, n_pages_max=PMAX,
        sliding_window=sliding_window)

    rows = ps * KV
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # page_table, lengths
        grid=(B, PMAX),
        in_specs=[
            pl.BlockSpec((H, rows), lambda b, j, pt, ln: (0, 0)),
            pl.BlockSpec((1, H, D), lambda b, j, pt, ln: (b, 0, 0)),
            pl.BlockSpec((1, rows, D), lambda b, j, pt, ln: (pt[b, j], 0, 0)),
            pl.BlockSpec((1, rows, D), lambda b, j, pt, ln: (pt[b, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, D), lambda b, j, pt, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_decode_attention",
    )(page_table, lengths, _token_offsets(H, KV, ps), q,
      k_pages.reshape(P, rows, D), v_pages.reshape(P, rows, D))
