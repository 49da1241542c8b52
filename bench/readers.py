"""Reductions from a run record to one number, shared by the metric
files under ``bench/metrics`` (each file names one metric and calls one
of these).  A reduction that finds nothing to read returns None.

Serving records hold per request its due time and its token times, and
per step its decode rows, the key count of each decoded row and the
prompt length of each prefill, all in seconds from the window's start
on the host clock; the engine's counters over the window
(``counters``); the model configuration (``model``) and the work module
its configuration file names (``work``), which counts the flops and bytes
that the device-trace readers hold device time against.  A ``--trace 1``
run adds ``trace`` (from ``bench.trace.reduce``, with the engine's
``host_spans`` from ``bench.spans``) and ``traced``: the steps and host
seconds that the profiled span covered.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from bench.common import quantile

KERNEL = "paged_decode_attention"     # the Pallas kernel's name
DECODE = "paged_decode"               # the engine's jitted decode step
PREFILL = ("jit__lambda", "jit_<lambda>")   # the engine's jitted prefill

Record = Dict[str, Any]


# ---- end to end, host clock ------------------------------------------------

def setup_s(rec: Record) -> float:
    return rec["setup_s"]


def ttft_quantile(rec: Record, q: float) -> Optional[float]:
    """Over every request due in the window: first token minus due time;
    one still unanswered at the close enters with its wait so far."""
    w = rec["window_s"]
    waits = [(r["times"][0] if r["times"] else w) - r["due"]
             for r in rec["requests"] if r["due"] < w]
    return quantile(waits, q) if waits else None


def itl_quantile(rec: Record, q: float) -> Optional[float]:
    """Over every gap between consecutive output tokens in the window."""
    gaps = [b - a for r in rec["requests"]
            for a, b in zip(r["times"], r["times"][1:])]
    return quantile(gaps, q) if gaps else None


def serve_tok_s(rec: Record) -> float:
    return sum(len(r["times"]) for r in rec["requests"]) / rec["window_s"]


# ---- engine counters -------------------------------------------------------

def decode_rows_mean(rec: Record) -> Optional[float]:
    rows = [s["rows"] for s in rec["steps"] if s["rows"]]
    return sum(rows) / len(rows) if rows else None


# ---- device trace ----------------------------------------------------------

def _chip0(rec: Record) -> Optional[Dict[str, Any]]:
    tr = rec.get("trace")
    return tr["chips"][min(tr["chips"])] if tr else None


def _programs(chip, match) -> List[Dict[str, Any]]:
    return [v for k, v in chip["programs"].items() if match(k)]


def _is_decode(name: str) -> bool:
    return DECODE in name


def _is_prefill(name: str) -> bool:
    return name in PREFILL


def program_share(rec: Record, which: str) -> Optional[float]:
    """Percent of the busy device time spent in the prefill programs, the
    decode program, or every other program (the page I/O)."""
    chip = _chip0(rec)
    if chip is None or chip["busy_s"] <= 0:
        return None
    match = {"prefill": _is_prefill, "decode": _is_decode,
             "other": lambda n: not (_is_prefill(n) or _is_decode(n))}[which]
    secs = sum(p["seconds"] for p in _programs(chip, match))
    return 100.0 * secs / chip["busy_s"] if secs > 0 else None


def decode_step_ms(rec: Record) -> Optional[float]:
    chip = _chip0(rec)
    if chip is None:
        return None
    progs = _programs(chip, _is_decode)
    calls = sum(p["calls"] for p in progs)
    return 1e3 * sum(p["seconds"] for p in progs) / calls if calls else None


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak_flops: float, peak_bw: float):
    """(percent of the roofline reached, bound): the least time the chip
    could take over the time taken."""
    t_flops, t_bytes = flops / peak_flops, nbytes / peak_bw
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound


def _traced_steps(rec: Record) -> List[Dict[str, Any]]:
    a, b = rec["traced"]["steps"]
    return rec["steps"][a:b]


def paged_attn_roofline(rec: Record) -> Optional[float]:
    """Percent of its roofline the paged kernel reached over the traced
    steps: the work their decode rows need, over the kernel's device
    time."""
    chip = _chip0(rec)
    if chip is None:
        return None
    secs = sum(s for k, s in chip["ops_s"].items() if KERNEL in k)
    if secs <= 0:
        return None
    cfg, work = rec["model"], rec["work"]
    flops = nbytes = 0
    for s in _traced_steps(rec):
        f, b = work.paged_attention_work(cfg, s["lengths"], rec["page_size"],
                                         rec["kv_itemsize"])
        flops += f
        nbytes += b
    pk = rec["peaks"]
    share, _ = roofline_share(flops, nbytes, secs, pk["bf16_flops_per_s"],
                              pk["hbm_bytes_per_s"])
    return share


def idle_frac(rec: Record) -> Optional[float]:
    """Percent of the traced window with no operation on the device; the
    worst chip of a multi-chip cell."""
    tr = rec.get("trace")
    if not tr:
        return None
    busy = min(c["busy_s"] for c in tr["chips"].values())
    return 100.0 * (1.0 - busy / tr["window_s"])


def mfu(rec: Record) -> Optional[float]:
    """Model flops of the traced steps over window x chips x bf16 peak."""
    tr = rec.get("trace")
    if not tr:
        return None
    cfg, work = rec["model"], rec["work"]
    steps = _traced_steps(rec)
    flops = sum(sum(work.prefill_flops(cfg, n) for n in s["prefills"])
                + sum(work.decode_flops(cfg, n) for n in s["lengths"])
                for s in steps)
    if flops <= 0:
        return None
    peak = rec["peaks"]["bf16_flops_per_s"] * rec["chips"]
    return 100.0 * flops / (tr["window_s"] * peak)
