"""Mamba2 SSD chunked-scan Pallas TPU kernel.

TPU adaptation of the paper's (GPU) SSD algorithm: instead of a warp-level
scan, the recurrence is blocked into chunks of Q tokens; each grid step
processes one (batch, head, chunk) cell entirely in VMEM:

  * intra-chunk quadratic term: (Q,Q) masked decay x (C·B^T) — MXU matmuls;
  * the (P,N) recurrent state lives in an fp32 VMEM scratch that persists
    across the sequential chunk dimension (dimension_semantics arbitrary);
  * per-chunk state update is a rank-Q matmul.

Grid: (B, H, nc); chunk dim sequential.  One head per program keeps the
working set at Q*P + Q*N + Q*Q + P*N fp32 ≈ 200 KB for Q=128, P=64,
N=128 — comfortably inside a v5e core's 128 MB VMEM budget with double
buffering.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, h0_ref,
            y_ref, hout_ref, state_ref, *, Q: int, n_chunks: int):
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        state_ref[...] = h0_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0].astype(jnp.float32)            # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)          # (Q,)
    A = a_ref[0]                                    # ()
    Bm = b_ref[0, 0].astype(jnp.float32)           # (Q, N)
    Cm = c_ref[0, 0].astype(jnp.float32)           # (Q, N)
    D = d_ref[0]                                    # ()

    a = dt * A                                      # (Q,)
    cum = jnp.cumsum(a)                             # (Q,)
    dtx = x * dt[:, None]                           # (Q, P)

    # intra-chunk: scores (Q,Q) on the MXU, masked exponential decay
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    diff = cum[:, None] - cum[None, :]
    qi = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    ki = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    decay = jnp.exp(jnp.where(ki <= qi, diff, -jnp.inf))
    y_diag = jax.lax.dot_general(scores * decay, dtx,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)

    # inter-chunk: read previous state, emit, then update
    h_prev = state_ref[...]                         # (P, N)
    y_off = jnp.exp(cum)[:, None] * jax.lax.dot_general(
        Cm, h_prev, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)         # (Q, P)

    w = jnp.exp(cum[-1] - cum)                      # (Q,)
    # state increment: (P, N) = dtx^T @ (w * B)
    incr = jax.lax.dot_general(dtx, w[:, None] * Bm,
                               (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    state_ref[...] = jnp.exp(jnp.sum(a)) * h_prev + incr

    y = y_diag + y_off + D * x
    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(c_idx == n_chunks - 1)
    def _emit_state():
        hout_ref[0, 0] = state_ref[...].astype(hout_ref.dtype)


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B_mat: jax.Array,
             C_mat: jax.Array, D: jax.Array, *, chunk: int = 128,
             init_state: Optional[jax.Array] = None,
             interpret: Optional[bool] = None
             ) -> Tuple[jax.Array, jax.Array]:
    """x: (B,S,H,P); dt: (B,S,H); A,D: (H,); B_mat/C_mat: (B,S,G,N).
    Returns (y: (B,S,H,P), final_state: (B,H,P,N))."""
    Bsz, S, H, P = x.shape
    G, N = B_mat.shape[2], B_mat.shape[3]
    HG = H // G
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    Q = min(chunk, S)
    pad = (-S) % Q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B_mat = jnp.pad(B_mat, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C_mat = jnp.pad(C_mat, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Sp = S + pad
    nc = Sp // Q

    if init_state is None:
        init_state = jnp.zeros((Bsz, H, P, N), jnp.float32)

    # kernel-friendly layouts
    xk = jnp.moveaxis(x, 2, 1)                      # (B,H,Sp,P)
    dtk = jnp.moveaxis(dt, 2, 1)                    # (B,H,Sp)
    bk = jnp.moveaxis(B_mat, 2, 1)                  # (B,G,Sp,N)
    ck = jnp.moveaxis(C_mat, 2, 1)

    kernel = functools.partial(_kernel, Q=Q, n_chunks=nc)
    y, hout = pl.pallas_call(
        kernel,
        grid=(Bsz, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, Q), lambda b, h, c: (b, h, c)),
            pl.BlockSpec((1,), lambda b, h, c: (h,)),
            pl.BlockSpec((1, 1, Q, N), lambda b, h, c: (b, h // HG, c, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda b, h, c: (b, h // HG, c, 0)),
            pl.BlockSpec((1,), lambda b, h, c: (h,)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, H, Sp, P), x.dtype),
            jax.ShapeDtypeStruct((Bsz, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xk, dtk, A.astype(jnp.float32), bk, ck, D.astype(jnp.float32),
      init_state)

    y = jnp.moveaxis(y, 1, 2)[:, :S]                # (B,S,H,P)
    return y, hout
