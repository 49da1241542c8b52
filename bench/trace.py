"""Capture of a profiler trace over part of a window, and its reduction
to device busy time, per-program device time, collective time and idle
gaps.

``Tracer`` turns the profiler on and off between two steps; ``extract``
reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
plain dict of events (the form the tests keep a recorded sample in);
``reduce`` turns that dict into the numbers the metric readers use.
Times in the dict are nanoseconds on the profiler's clock.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import time
from collections import defaultdict
from typing import Any, Dict, List, Tuple

import jax

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|allreduce|allgather|reducescatter|send|recv)", re.I)
_ID = re.compile(r"\(\d+\)$")
# control flow whose events span the ops of its body
CONTAINER = re.compile(r"^(while|conditional|call)(\.\d+)?$")


class Tracer:
    """The profiler on for one span of the window, between two steps,
    with a ``bench.window`` host span around it.  The Python tracer stays
    off: it would slow the host loop it is measuring."""

    def __init__(self, out_dir: str, start_s: float, stop_s: float):
        self.out_dir, self.start_s, self.stop_s = out_dir, start_s, stop_s
        self.span = None
        self.host = None          # [t_start, t_stop] host clock
        self.steps = None         # [first, end) indices into the steps

    def poll(self, now: float, t0: float, n_steps: int) -> None:
        if self.host is None and now >= t0 + self.start_s:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.out_dir, profiler_options=opts)
            self.span = jax.profiler.TraceAnnotation("bench.window")
            self.span.__enter__()
            self.host, self.steps = [time.perf_counter(), None], [n_steps, None]
        elif self.host and self.host[1] is None and now >= t0 + self.stop_s:
            self.stop(n_steps)

    def stop(self, n_steps: int) -> None:
        if self.host and self.host[1] is None:
            self.span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.host[1], self.steps[1] = time.perf_counter(), n_steps


def extract(trace_dir: str) -> Dict[str, Any]:
    """Events of the newest ``.xplane.pb`` under ``trace_dir``:
    ``{"devices": {id: {"ops": [[name, start, dur]], "modules": [...]}},
    "host": [[name, start, dur]]}``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    out: Dict[str, Any] = {"devices": {}, "host": []}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if key is None:
                    continue
                for ev in line.events:
                    name = op_name(ev.name) if key == "ops" else ev.name
                    row = [name, int(ev.start_ns), int(ev.duration_ns)]
                    if key == "ops":
                        row.append(dict(ev.stats).get("hlo_module", ""))
                    dev[key].append(row)
            out["devices"][int(m.group(1))] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    out["host"].append([ev.name, int(ev.start_ns),
                                        int(ev.duration_ns)])
    return out


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merged, sorted [start, end) intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals: List[Tuple[int, int]]) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a: List[Tuple[int, int]], b: List[Tuple[int, int]]) -> int:
    """Length of the merged intervals ``a`` not covered by merged ``b``."""
    total = 0
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def _clip(events, t0: int, t1: int):
    for ev in events:
        name, s, d = ev[:3]
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            yield (name, a, b) + tuple(ev[3:])


def op_name(text: str) -> str:
    """An op's name from the HLO text the TPU trace gives for it
    (``%copy.54 = bf16[...] copy(...)`` -> ``copy.54``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def program_name(module: str) -> str:
    """A program's name without the run's id suffix."""
    return _ID.sub("", module)


def window(ext: Dict[str, Any], mark: str = "bench.window") -> Tuple[int, int]:
    """[start, end) of the harness's ``bench.window`` host span."""
    spans = [(s, s + d) for name, s, d in ext["host"] if name == mark]
    if not spans:
        raise ValueError(f"no {mark!r} span in the trace")
    return spans[0]


def reduce(ext: Dict[str, Any], top: int = 10) -> Dict[str, Any]:
    """Per chip: busy seconds, device seconds and calls per program,
    collective seconds and the part of them with no other operation
    running; plus, for chip 0, the operations that took most time and
    the longest idle gaps named by the host span they fell in."""
    t0, t1 = window(ext)
    chips = {}
    for dev_id, dev in sorted(ext["devices"].items(), key=lambda kv: int(kv[0])):
        ops = list(_clip(dev["ops"], t0, t1))
        busy = union([(o[1], o[2]) for o in ops])
        coll = union([(o[1], o[2]) for o in ops if COLLECTIVE.match(o[0])])
        comp = union([(o[1], o[2]) for o in ops if not COLLECTIVE.match(o[0])])
        progs: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
        mods = sorted(_clip(dev["modules"], t0, t1), key=lambda x: x[1])
        for name, a, b in mods:
            p = progs[program_name(name)]
            p[0] += 1
            p[1] += (b - a) / 1e9
        chips[int(dev_id)] = {
            "busy_s": length(busy) / 1e9,
            "programs": {k: {"calls": v[0], "seconds": v[1]}
                         for k, v in progs.items()},
            "ops_s": _op_seconds(ops, mods),
            "collective_s": length(coll) / 1e9,
            "collective_exposed_s": subtract(coll, comp) / 1e9,
            "busy": busy,
        }
    if not chips:
        raise ValueError("no TPU device plane in the trace")
    first = chips[min(chips)]
    out = {
        "window_s": (t1 - t0) / 1e9,
        "chips": chips,
        "device_ops": sorted(first["ops_s"].items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": _idle_gaps(first["busy"], ext["host"], t0, t1, top),
    }
    for c in chips.values():
        del c["busy"]
    return out


def _op_seconds(ops, mods) -> Dict[str, float]:
    """Device seconds per ``program/op`` name, control-flow ops (which
    span their body's ops) left out; the program is the op's
    ``hlo_module`` where the trace gives it, else the program running
    around it."""
    starts = [m[1] for m in mods]
    out: Dict[str, float] = defaultdict(float)
    for op in ops:
        name, a, b = op[:3]
        if CONTAINER.match(name):
            continue
        prog = op[3] if len(op) > 3 and op[3] else ""
        if not prog:
            i = bisect.bisect_right(starts, a) - 1
            prog = (program_name(mods[i][0])
                    if i >= 0 and mods[i][2] >= b else "?")
        out[f"{prog}/{name}"] += (b - a) / 1e9
    return dict(out)


def _idle_gaps(busy, host, t0: int, t1: int, top: int):
    """The longest idle gaps of one chip, each named by the innermost
    host span that covers its middle."""
    edges = [t0] + [x for iv in busy for x in iv] + [t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges) - 1, 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:top]:
        mid = (a + b) // 2
        cover = [(d, n) for n, s, d in host if s <= mid < s + d]
        name = min(cover)[1] if cover else "no host span"
        out.append([name, (b - a) / 1e9])
    return out
