"""Adapters over the jax API surfaces whose call shape this repo wraps.

The repo targets one jax (0.9); nothing here branches on its version.
``shard_map`` adds the repo's ``manual_axes`` spelling (axes handled by
the body; the rest stay auto / GSPMD), and ``cost_analysis`` pins the
return type the dry-run records are built from.  The ``compat-imports``
lint keeps callers on these two.
"""

from __future__ import annotations

from typing import Iterable, Optional

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check: bool = True,
              manual_axes: Optional[Iterable[str]] = None):
    """``jax.shard_map``.  ``manual_axes``: axes handled manually by ``f``
    (None = all mesh axes manual).  ``check`` maps to ``check_vma``."""
    kw = {}
    if manual_axes is not None:
        kw["axis_names"] = set(manual_axes)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check, **kw)


def cost_analysis(compiled) -> dict:
    """``Compiled.cost_analysis()`` as a plain dict."""
    return dict(compiled.cost_analysis())
