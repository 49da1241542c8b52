"""Serving cells: the ``repro.serve`` engine, driven by ``Engine.submit``
and ``Engine.step`` on the host clock.

The engine's own clocks are modeled seconds and are never read here; its
monotone counters (``Engine.metrics()``) are read once before and once
after the window, and the record carries their difference.
``Engine.step`` ends on host reads of the tokens it produced, so the host
time just after it returns is when those tokens exist: every token is
stamped with it.  An open loop submits each request when it is due; a
closed loop submits a client's next request when its last one is done.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import common, generators as gen
from bench.weights import make_params


class _Live:
    """One request as the harness sees it."""

    __slots__ = ("due", "submit", "handle", "times", "prompt", "client")

    def __init__(self, due, submit, handle, prompt, client=None):
        self.due, self.submit, self.handle = due, submit, handle
        self.prompt, self.client = prompt, client
        self.times: List[float] = []


def build(cell: common.Cell, seed: int):
    """The engine as ``launch/serve.py``'s engine mode builds it, over
    weights made on the device from the seed."""
    from repro.models.api import build_model
    from repro.serve import Engine, EngineConfig
    mcfg = common.model_config(cell.config)
    model = build_model(mcfg)
    params = make_params(model, seed, jnp.dtype(mcfg.param_dtype))
    ecfg = EngineConfig(**cell.config["serve"]["engine"])
    return Engine.local(model, ecfg, params=params), mcfg, params


def _buckets(engine, lo: int, hi: int) -> List[int]:
    return sorted({engine._bucket_len(n) for n in (lo, hi)}
                  | {b for b in engine._buckets if lo <= b <= hi})


def warm_up(engine, cell: common.Cell) -> Dict[str, Any]:
    """Run every prefill bucket and decode row bucket this cell's traffic
    can reach once, so that none compiles inside the window."""
    from repro.serve import Request
    lo, hi = gen.prompt_range(cell.traffic)
    prefill = _buckets(engine, lo, hi)
    if cell.traffic["kind"] == "closed_loop":
        rows = [engine._row_bucket(int(cell.traffic["clients"]))]
    else:
        rows = list(engine._row_buckets)
    for b in prefill:
        engine.submit(Request((1,) * min(b, hi), 2))
        engine.run_until_idle()
    for r in rows:
        for _ in range(r):
            engine.submit(Request((1,) * lo, 2))
        engine.run_until_idle()
    return {"prefill_buckets": prefill, "decode_row_buckets": rows}


# Engine.metrics() entries that only grow, besides those its registry
# keeps as counters; the modeled clocks (clock_s, busy_s and the rates
# made from them) and the gauges (queue_depth, running, ...) are not
COUNTERS = frozenset({"steps", "completed", "failed_oom", "tokens_decoded",
                      "pages_written", "preempts", "preempt_swaps",
                      "preempt_recomputes"})


def _counters(engine) -> Dict[str, float]:
    """The engine's monotone counters: those of ``Engine.metrics()``
    named in ``COUNTERS`` and those its registry keeps as ``Counter``s,
    named without the engine's ``serve/<tenant>/`` prefix."""
    from repro.obs.metrics import Counter
    reg = engine.metrics()
    out = {}
    for name, value in reg.snapshot().items():
        short = name.split("/", 2)[-1]
        if short in COUNTERS or isinstance(reg._metrics[name], Counter):
            out[short] = value
    return out


def _stamp(live: List[_Live], t: float):
    """Give new tokens the time ``t``.  Returns the requests now done, the
    prompt length of each prefill this step ran (its token 1) and the key
    count of each decode: token ``k >= 2`` of a request is decoded over
    ``prompt_len + k - 1`` keys."""
    done, prefills, lengths = [], [], []
    for r in live:
        n = len(r.handle.tokens)
        for k in range(len(r.times) + 1, n + 1):
            r.times.append(t)
            if k == 1:
                prefills.append(len(r.prompt))
            else:
                lengths.append(len(r.prompt) + k - 1)
        if r.handle.done:
            done.append(r)
    return done, prefills, lengths


def run_window(engine, cell: common.Cell, seed: int, seconds: float,
               tracer=None) -> Dict[str, Any]:
    """Drive the engine for ``seconds`` of host time; returns what
    happened, on the host clock."""
    from repro.serve import Request
    vocab = engine.model.cfg.vocab
    kind = cell.traffic["kind"]
    steps: List[Dict[str, Any]] = []
    requests: List[_Live] = []
    live: List[_Live] = []
    before = _counters(engine)
    t0 = time.perf_counter()
    end = t0 + seconds

    def submit(req, due, client=None):
        h = engine.submit(Request(tuple(int(x) for x in req.prompt),
                                  req.max_new))
        r = _Live(due, time.perf_counter(), h, req.prompt, client)
        requests.append(r)
        live.append(r)

    pending, clients = [], None
    if kind == "open_loop":
        pending = gen.open_loop(cell.traffic, seed, seconds, vocab)
    elif kind == "closed_loop":
        clients = gen.closed_loop(cell.traffic, seed, vocab)
        for c, stream in enumerate(clients):
            submit(next(stream), t0, c)
    else:
        raise common.BenchError(f"unknown serving traffic kind {kind!r}")
    i = 0
    while True:
        now = time.perf_counter()
        if tracer is not None:
            tracer.poll(now, t0, len(steps))
        if now >= end:
            break
        while i < len(pending) and t0 + pending[i].due_s <= now:
            submit(pending[i], t0 + pending[i].due_s)
            i += 1
        if engine.idle:
            nxt = t0 + pending[i].due_s if i < len(pending) else end
            with jax.profiler.TraceAnnotation("bench.wait_arrival"):
                time.sleep(max(0.0, min(nxt, end) - time.perf_counter()))
            continue
        decoded = engine._decoded_tokens
        s0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.step"):
            engine.step()
        t = time.perf_counter()
        done, prefills, lengths = _stamp(live, t)
        steps.append({"start": s0, "end": t,
                      "rows": engine._decoded_tokens - decoded,
                      "prefills": prefills, "lengths": lengths})
        for r in done:
            live.remove(r)
            if clients is not None and t < end:
                submit(next(clients[r.client]), t, r.client)
    if tracer is not None:
        tracer.stop(len(steps))
    after = _counters(engine)
    return {"t0": t0, "end": end, "window_s": seconds, "steps": steps,
            "requests": requests,
            "counters": {k: after[k] - before[k] for k in after
                         if k in before}}


def summarize(run: Dict[str, Any], mcfg, cell: common.Cell) -> Dict[str, Any]:
    """The run record: per request its due time, first-token time and
    token times; per step its rows; all on the host clock relative to
    the window's start.  Also the engine's counters over the window, the
    model configuration, and the work module that the configuration
    file names."""
    t0, end = run["t0"], run["end"]
    reqs = []
    for r in run["requests"]:
        reqs.append({"due": r.due - t0, "submit": r.submit - t0,
                     "prompt_len": len(r.prompt),
                     "times": [t - t0 for t in r.times if t <= end],
                     "done": bool(r.handle.done)})
    steps = [{"start": s["start"] - t0, "end": s["end"] - t0,
              "rows": s["rows"], "prefills": s["prefills"],
              "lengths": s["lengths"]}
             for s in run["steps"]]
    return {"window_s": run["window_s"], "requests": reqs,
            "steps": steps, "counters": run["counters"], "model": mcfg,
            "work": cell.module("work"),
            "page_size": int(cell.config["serve"]["engine"]["page_size"]),
            "kv_itemsize": jnp.dtype(cell.config["serve"]["engine"]["cache_dtype"]).itemsize}


def sample_finished(run: Dict[str, Any], seed: int, min_tokens: int):
    """The longest finished request and others drawn from the seed, until
    they hold ``min_tokens`` served tokens."""
    done = [r for r in run["requests"] if r.handle.done and r.handle.tokens]
    if not done:
        return []
    done.sort(key=lambda r: (len(r.prompt) + len(r.handle.tokens), r.handle.rid))
    picked = [done.pop()]
    rng = np.random.default_rng(np.random.SeedSequence([seed, 7]))
    order = rng.permutation(len(done))
    n = len(picked[0].handle.tokens)
    for j in order:
        if n >= min_tokens:
            break
        picked.append(done[j])
        n += len(done[j].handle.tokens)
    return [(np.asarray(r.prompt), np.asarray(r.handle.tokens, np.int32))
            for r in picked]


def check(params, mcfg, cell: common.Cell, served) -> List[Dict[str, Any]]:
    """Served tokens against the reference that the configuration file
    names: the widest gap by which a served token's reference logit lies
    below the best."""
    limit = float(cell.config["serve"]["check"]["max_logit_gap"])
    if not served:
        return [{"name": "finished_requests", "value": 0, "limit": 1,
                 "ok": False}]
    gaps = cell.module("reference").served_gaps(
        params, mcfg, served,
        pad_to=int(cell.config["serve"]["engine"]["max_seq"]))
    worst = max(gaps)
    tokens = sum(len(t) for _, t in served)
    return [{"name": "served_tokens_checked", "value": tokens, "limit": 1,
             "ok": tokens >= 1},
            {"name": "max_logit_gap", "value": worst, "limit": limit,
             "ok": worst <= limit}]
