"""Hierarchical (fabric-aware) collectives — ScalePool's communication
schedule realized with shard_map + jax.lax collectives.

The paper's §4: bulk intra-cluster data movement stays on the fast XLink
fabric; only the reduced shard crosses the inter-cluster CXL fabric.  On
a TPU mesh this maps to:

    phase 1: reduce-scatter over the intra-pod axes  ("data")
    phase 2: all-reduce across pods                  ("pod")
    phase 3: all-gather over the intra-pod axes      ("data")

Compared to one flat all-reduce over (pod × data), the cross-pod fabric
carries 1/|data| of the bytes — the structural source of the paper's
inter-cluster communication win (§6: 3.79x).

Optionally, phase 2 compresses with error-feedback int8 (the gradient
traffic crossing the slow fabric tolerates quantization; residuals are
fed back next step).
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.compat import shard_map as _shard_map


# ---------------------------------------------------------------------------
# explicit collectives on flat buffers (benchmark + unit-test surface)
# ---------------------------------------------------------------------------

def flat_allreduce(x: jax.Array, mesh: Mesh, axes: Tuple[str, ...]) -> jax.Array:
    """Baseline: one psum spanning all given mesh axes (the 'RDMA-era'
    topology-oblivious collective)."""

    def f(xs):
        return jax.lax.psum(xs, axes)

    return _shard_map(f, mesh=mesh, in_specs=P(axes), out_specs=P(axes))(x)


def hierarchical_allreduce(x: jax.Array, mesh: Mesh, *,
                           intra_axis: str = "data",
                           inter_axis: str = "pod") -> jax.Array:
    """Two-level all-reduce: RS(intra) → AR(inter) → AG(intra).

    x is sharded over (inter, intra) on its leading dim; returns the same
    sharding with globally-reduced values.  Mathematically identical to
    ``flat_allreduce`` over both axes (tested), but the inter-axis fabric
    only carries 1/|intra| of the buffer.
    """

    def f(xs):
        # xs: local shard, shape (n, ...)
        n_intra = jax.lax.axis_size(intra_axis)
        # phase 1: reduce-scatter along intra axis over the leading dim
        shard = jax.lax.psum_scatter(xs, intra_axis, scatter_dimension=0,
                                     tiled=True)
        # phase 2: all-reduce the 1/n_intra shard across pods
        shard = jax.lax.psum(shard, inter_axis)
        # phase 3: all-gather back along intra
        return jax.lax.all_gather(shard, intra_axis, axis=0, tiled=True)

    return _shard_map(f, mesh=mesh, in_specs=P((inter_axis, intra_axis)),
                      out_specs=P((inter_axis, intra_axis)))(x)


# ---------------------------------------------------------------------------
# error-feedback int8 compression for the inter-pod phase
# ---------------------------------------------------------------------------

def quantize_int8(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-tensor int8 quantization."""
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def compressed_cross_pod_mean(x: jax.Array, axis_name: str,
                              residual: Optional[jax.Array] = None,
                              ) -> Tuple[jax.Array, jax.Array]:
    """Mean-reduce across pods with int8 error-feedback compression.

    Returns (reduced, new_residual).  Inside shard_map with ``axis_name``
    manual.  Error feedback: the quantization error is carried to the
    next step so the compression is unbiased over time.
    """
    xf = x.astype(jnp.float32)
    if residual is not None:
        xf = xf + residual
    # SHARED quantization scale across pods (a scalar pmax — negligible
    # traffic) so the int32 psum of codes is an exact sum of quantized
    # values: sum_i(q_i) * scale == sum_i(q_i * scale).
    local_max = jnp.max(jnp.abs(xf))
    gmax = jax.lax.pmax(local_max, axis_name)
    scale = gmax / 127.0 + 1e-12
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    new_residual = xf - q.astype(jnp.float32) * scale
    # int8 payload crosses the slow fabric; psum in int32 to avoid overflow
    n = jax.lax.psum(1, axis_name)
    summed = jax.lax.psum(q.astype(jnp.int32), axis_name)
    out = (summed.astype(jnp.float32) * scale / n).astype(x.dtype)
    return out, new_residual


def cross_pod_mean(x: jax.Array, axis_name: str) -> jax.Array:
    return jax.lax.pmean(x, axis_name)


# ---------------------------------------------------------------------------
# gradient-tree reduction for the training step
# ---------------------------------------------------------------------------

def reduce_gradients_hierarchically(grads: Any, *, inter_axis: str = "pod",
                                    compress: bool = False,
                                    residuals: Optional[Any] = None,
                                    ) -> Tuple[Any, Optional[Any]]:
    """Cross-pod gradient reduction, called INSIDE a shard_map whose manual
    axis is ``inter_axis`` (intra-pod reduction is handled by GSPMD on the
    auto axes — the XLink domain).

    With ``compress=True``, the inter-pod phase moves int8 + per-tensor
    scales (4x fewer bytes on the paper's CXL fabric), with error
    feedback carried in ``residuals``.
    """
    if not compress:
        return jax.tree.map(lambda g: cross_pod_mean(g, inter_axis), grads), None
    if residuals is None:
        residuals = jax.tree.map(lambda g: jnp.zeros_like(g, jnp.float32), grads)
    flat_g, tree = jax.tree.flatten(grads)
    flat_r = tree.flatten_up_to(residuals)
    outs, news = [], []
    for g, r in zip(flat_g, flat_r):
        o, nr = compressed_cross_pod_mean(g, inter_axis, r)
        outs.append(o)
        news.append(nr)
    return tree.unflatten(outs), tree.unflatten(news)
