"""Engine host spans in a profile, and the readers of the engine's
phases.

The serving engine marks its phases with ``engine.*`` host spans on the
profiler's clock, the clock of the device planes
(``repro.serve.engine.SPAN_NAMES``).  ``host_spans`` puts every idle
nanosecond of chip 0 inside ``bench.window`` down to the innermost
engine span over it, or to none.

``page_write_ms`` and ``step_idle_ms`` read only what a ``--trace 1``
record holds already (``bench/run.py`` adds ``trace.host_spans``).  The
other readers take a run record that also holds per step ``n`` (the
engine's ``steps`` before it), per request ``admit_step`` and
``setup_jit_s``; where those are absent they return None.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from bench import common, readers, trace

STEP = "engine.step"
# JAX's compile phases: tracing to a jaxpr, lowering, and compiling or
# loading from the persistent cache
JIT_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

Record = Dict[str, Any]


# ---- idle attribution -------------------------------------------------------

def _segments(spans: List[Tuple[str, int, int]], t0: int, t1: int):
    """``[t0, t1)`` cut wherever a span starts or ends, each piece with
    the innermost (shortest) span covering it, or None."""
    cuts = sorted({t0, t1} | {x for _, s, e in spans for x in (s, e)})
    by_start = sorted(spans, key=lambda sp: sp[1])
    active: List[Tuple[str, int, int]] = []
    j = 0
    for a, b in zip(cuts, cuts[1:]):
        while j < len(by_start) and by_start[j][1] <= a:
            active.append(by_start[j])
            j += 1
        active = [sp for sp in active if sp[2] > a]
        inner = min(active, key=lambda sp: sp[2] - sp[1], default=None)
        yield a, b, inner[0] if inner else None


def _idle(busy: List[Tuple[int, int]], t0: int, t1: int):
    edges = [t0] + [x for iv in busy for x in iv] + [t1]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges) - 1, 2)
            if edges[i + 1] > edges[i]]


def host_spans(ext: Dict[str, Any], chip: int = 0) -> Dict[str, Any]:
    """Per engine span name: ``count``, ``seconds`` (inside the window)
    and ``idle_s``, the idle time of ``chip`` for which it is the
    innermost engine span; ``idle_outside_s`` is idle time under no
    engine span.  The idle times sum to the chip's idle time."""
    # imported here, so that the module's readers load against a
    # program without engine spans
    from repro.serve.engine import SPAN_NAMES
    t0, t1 = trace.window(ext)
    dev = ext["devices"].get(chip, ext["devices"].get(str(chip)))
    busy = trace.union([(a, b) for _, a, b, *_ in
                        trace._clip(dev["ops"], t0, t1)])
    spans = [sp for sp in trace._clip(ext["host"], t0, t1)
             if sp[0] in SPAN_NAMES]
    out = {n: {"count": 0, "seconds": 0.0, "idle_s": 0.0}
           for n in SPAN_NAMES}
    for n, a, b in spans:
        out[n]["count"] += 1
        out[n]["seconds"] += (b - a) / 1e9
    outside = 0
    idle = _idle(busy, t0, t1)
    k = 0
    for a, b, name in _segments(spans, t0, t1):
        while k < len(idle) and idle[k][1] <= a:
            k += 1
        ns = 0
        i = k
        while i < len(idle) and idle[i][0] < b:
            ns += min(b, idle[i][1]) - max(a, idle[i][0])
            i += 1
        if name is None:
            outside += ns
        else:
            out[name]["idle_s"] += ns / 1e9
    return {"spans": {n: v for n, v in out.items() if v["count"]},
            "idle_outside_s": outside / 1e9}


# ---- readers ---------------------------------------------------------------

def step_idle_ms(rec: Record) -> Optional[float]:
    """Device idle under engine spans per engine step, in ms."""
    hs = (rec.get("trace") or {}).get("host_spans")
    if not hs or STEP not in hs["spans"]:
        return None
    idle = sum(v["idle_s"] for v in hs["spans"].values())
    return 1e3 * idle / hs["spans"][STEP]["count"]


def queue_wait_quantile(rec: Record, q: float) -> Optional[float]:
    """Over every request due in the window: host start of the step that
    admitted it minus its due time; one not admitted by the close enters
    with its wait so far."""
    w = rec["window_s"]
    start = {s["n"]: s["start"] for s in rec["steps"] if "n" in s}
    if not start:
        return None
    waits = [start.get(r.get("admit_step"), w) - r["due"]
             for r in rec["requests"] if r["due"] < w]
    return common.quantile(waits, q) if waits else None


def page_write_ms(rec: Record) -> Optional[float]:
    """Device ms of the page-write programs (those ``page_io_dev_share``
    counts) per page the traced steps wrote.  A prefill writes
    ceil(prompt length / page size) pages; a cell whose tier-1 pool holds
    every slot's pages never preempts, so no prompt is written twice."""
    share = readers.program_share(rec, "other")
    if share is None or "traced" not in rec:
        return None
    a, b = rec["traced"]["steps"]
    ps = rec["page_size"]
    pages = sum(-(-n // ps) for s in rec["steps"][a:b] for n in s["prefills"])
    if not pages:
        return None
    chips = rec["trace"]["chips"]
    secs = share / 100.0 * chips[min(chips)]["busy_s"]
    return 1e3 * secs / pages


def setup_jit_s(rec: Record) -> Optional[float]:
    """Host seconds of JAX's compile phases before the window
    (``jit_seconds``)."""
    return rec.get("setup_jit_s")


def jit_seconds(intervals: List[Tuple[float, float]]) -> float:
    """Seconds covered by the ``(start, end)`` intervals of JAX's
    ``JIT_EVENTS``: their union, since a jaxpr trace nests the traces of
    the jitted functions it calls and the durations cannot be summed."""
    return trace.length(trace.union(intervals))
