"""Production mesh builders.

ScalePool mapping (DESIGN.md §2): the inner axes ("data", "model") are
one accelerator cluster's XLink domain (a 256-chip pod); the outer
"pod" axis is the inter-cluster CXL fabric.  Functions, not module
constants — importing this module never touches jax device state.

Every mesh in the repo is built by ``make_mesh``: its axes are
``AxisType.Auto``, so the logical-axis rules (``sharding.partition``)
steer GSPMD through ``with_sharding_constraint`` hints.  ``jax.make_mesh``
on its own gives ``Explicit`` axes, under which those hints become
assertions.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes), devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_smoke_mesh(n_devices: int | None = None):
    """Small mesh for in-process tests (requires forced host devices)."""
    n = n_devices or len(jax.devices())
    if n >= 8:
        return make_mesh((2, 2, 2), ("pod", "data", "model"))
    if n >= 4:
        return make_mesh((2, 2), ("data", "model"))
    return make_mesh((1, 1), ("data", "model"))
