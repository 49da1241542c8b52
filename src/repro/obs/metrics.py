"""Hierarchical metrics registry — ONE schema for every ``stats()``.

Before ``repro.obs`` each subsystem grew its own ad-hoc stats dict
(``Engine.stats``, ``Transport.stats``, ``PoolArbiter.stats``) with
divergent key conventions and no way to merge them into one report.
The registry replaces them behind a single hierarchical namespace:

    serve/<engine>/clock_s            fabric/transfers
    serve/<engine>/kv/spills          fabric/link/<name>/busy_s
    arbiter/tenant/<t>/hot_used       pool/sched/...

Subsystems implement ``metrics(registry=None, prefix=...)`` which
fills (and returns) a registry; the legacy ``stats()`` dicts are kept
working as *thin adapters* over the registry snapshot, so nothing
downstream breaks while all new reporting (benchmark ``--json``,
``scripts/trace_report.py``, CI artifacts) reads the one schema.

Two metric kinds, deliberately minimal:

``Counter``
    Monotone count (events, bytes).  ``inc`` only.
``Gauge``
    Point-in-time value of any JSON-serializable type (numbers for
    dashboards, the odd string label for identity fields).

Values are stored exactly as given (no float coercion): the adapters
must reproduce the legacy dicts bit-identically.
"""

from __future__ import annotations

import json
from typing import Any, Dict, List, Optional


class Counter:
    __slots__ = ("value",)

    def __init__(self):
        self.value = 0

    def inc(self, n=1) -> None:
        self.value += n

    def get(self):
        return self.value


class Gauge:
    __slots__ = ("value",)

    def __init__(self):
        self.value = None

    def set(self, v) -> None:
        self.value = v

    def get(self):
        return self.value


class MetricsRegistry:
    """Get-or-create store of named metrics.  Names are ``/``-separated
    paths; ``snapshot()`` flattens to ``{path: value}`` and ``tree()``
    nests by path segment (the shape ``--json`` files serialize)."""

    def __init__(self):
        self._metrics: Dict[str, Any] = {}

    def _get(self, name: str, kind):
        m = self._metrics.get(name)
        if m is None:
            m = kind()
            self._metrics[name] = m
        elif not isinstance(m, kind):
            raise TypeError(f"metric {name!r} is {type(m).__name__}, "
                            f"not {kind.__name__}")
        return m

    def counter(self, name: str) -> Counter:
        return self._get(name, Counter)

    def gauge(self, name: str) -> Gauge:
        return self._get(name, Gauge)

    def set(self, name: str, value) -> None:
        """Shorthand: ``gauge(name).set(value)`` — the bulk of the
        ``metrics()`` implementations are point-in-time snapshots."""
        self.gauge(name).set(value)

    # ---- reading ---------------------------------------------------------
    def __contains__(self, name: str) -> bool:
        return name in self._metrics

    def names(self) -> List[str]:
        return sorted(self._metrics)

    def value(self, name: str):
        return self._metrics[name].get()

    def snapshot(self, prefix: str = "") -> Dict[str, Any]:
        """Flat ``{name: value}`` of every metric under ``prefix``."""
        return {n: m.get() for n, m in sorted(self._metrics.items())
                if n.startswith(prefix)}

    def tree(self) -> Dict[str, Any]:
        """Nested dict keyed by path segments."""
        out: Dict[str, Any] = {}
        for name, m in sorted(self._metrics.items()):
            parts = name.split("/")
            node = out
            for p in parts[:-1]:
                node = node.setdefault(p, {})
                if not isinstance(node, dict):
                    raise ValueError(f"metric {name!r} nests under the "
                                     f"leaf metric {p!r}")
            node[parts[-1]] = m.get()
        return out


def write_json(path: str, name: str, metrics: Dict[str, Any], *,
               extra: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Write one benchmark's headline metrics as a machine-readable
    JSON document (the ``--json PATH`` satellite): a stable envelope
    around the registry tree / summary dict so downstream tooling can
    diff runs without scraping stdout CSV."""
    doc = {"schema": "repro.obs/bench-v1", "bench": name,
           "metrics": metrics}
    if extra:
        doc.update(extra)
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True, default=str)
        f.write("\n")
    return doc
