"""repro.obs validation: flight-recorder ring semantics (bounded, O(1)
append, wrap without corrupting spans), trace determinism (same
trace/seed -> bit-identical event streams across runs and across
``Engine.local`` vs single-tenant-under-arbiter), zero-cost-when-
disabled (tracing never perturbs tokens or modeled clocks), Chrome
trace_event exporter schema conformance, the metrics-registry adapters
behind the legacy ``stats()`` dicts, and the per-link busy-seconds
conservation bound the fig10 attribution claims rest on."""

import json

import jax
import pytest

from repro.configs import SMOKE_ARCHS
from repro.core import fabric as fb
from repro.core.tiering import KVBudget
from repro.fabric import Topology, Transport
from repro.models.api import build_model
from repro.obs import (CAT_KV, CAT_REQUEST, NULL_TRACER, JsonlSink,
                       MetricsRegistry, NullTracer, Tracer,
                       events_from_jsonl, link_report,
                       link_report_from_trace, resolve,
                       rotated_jsonl_paths, tier_report,
                       to_chrome_trace, validate_trace_events,
                       write_chrome_trace)
from repro.serve import (Engine, EngineConfig, PoolArbiter, burst_trace,
                         run_trace)

GB = 1e9
VOCAB = SMOKE_ARCHS["qwen1.5-0.5b"].vocab
POOL_PAGES = 6          # tight: forces paging under the heavy trace


@pytest.fixture(scope="module")
def model():
    cfg = SMOKE_ARCHS["qwen1.5-0.5b"].__class__(**{
        **SMOKE_ARCHS["qwen1.5-0.5b"].__dict__, "compute_dtype": "float32"})
    return build_model(cfg)


@pytest.fixture(scope="module")
def params(model):
    return model.init(jax.random.PRNGKey(0))


def _cfg(**kw):
    base = dict(max_slots=3, max_seq=64, page_size=8)
    base.update(kw)
    return EngineConfig(**base)


def _heavy(n=5, seed=0):
    return burst_trace(n, prompt_len=12, max_new_tokens=10, vocab=VOCAB,
                       seed=seed)


def _traced_local_run(model, params):
    """One traced private-pool engine run under paging pressure."""
    tracer = Tracer()
    eng = Engine.local(model, _cfg(), params=params,
                       budget=KVBudget(tier1_pages=POOL_PAGES,
                                       tier2_bytes=1e9, page_size=8),
                       tenant="a", tracer=tracer)
    handles = run_trace(eng, _heavy())
    return eng, tracer, handles


@pytest.fixture(scope="module")
def traced_run(model, params):
    eng, tracer, handles = _traced_local_run(model, params)
    assert eng.stats()["preempt_swaps"] > 0, "pressure not exercised"
    return {"engine": eng, "tracer": tracer, "handles": handles}


# ---------------------------------------------------------------------------
# flight recorder: ring semantics
# ---------------------------------------------------------------------------

def test_ring_wraps_without_corrupting_events():
    tr = Tracer(capacity=8)
    for i in range(20):
        tr.span("t", f"s{i}", float(i), 0.5, x=i)
    assert len(tr) == 8
    assert tr.total_recorded == 20
    assert tr.dropped == 12
    evs = tr.events()
    # survivors are exactly the most recent 8, oldest first, intact
    assert [e.name for e in evs] == [f"s{i}" for i in range(12, 20)]
    for i, e in zip(range(12, 20), evs):
        assert (e.ph, e.track, e.ts, e.dur) == ("X", "t", float(i), 0.5)
        assert e.args == {"x": i}
        assert isinstance(e, tuple) and len(e) == 7


def test_ring_partial_fill_and_clear():
    tr = Tracer(capacity=8)
    tr.instant("t", "a", 1.0)
    tr.counter("t", "c", 2.0, 3.5)
    assert len(tr) == 2 and tr.dropped == 0
    a, c = tr.events()
    assert a.ph == "i" and a.dur == 0.0
    assert c.ph == "C" and c.args == {"value": 3.5}
    tr.clear()
    assert len(tr) == 0 and tr.total_recorded == 0 and tr.events() == []


def test_tracer_rejects_bad_capacity():
    with pytest.raises(ValueError):
        Tracer(capacity=0)


def test_null_tracer_and_resolve():
    assert NULL_TRACER.enabled is False
    NULL_TRACER.span("t", "s", 0.0, 1.0)
    NULL_TRACER.instant("t", "i", 0.0)
    NULL_TRACER.counter("t", "c", 0.0, 1.0)
    assert len(NULL_TRACER) == 0
    assert resolve(None) is NULL_TRACER
    tr = Tracer(capacity=4)
    assert resolve(tr) is tr
    assert isinstance(NullTracer(), Tracer)
    with pytest.raises(TypeError):
        resolve(42)


# ---------------------------------------------------------------------------
# metrics registry + legacy stats() adapters
# ---------------------------------------------------------------------------

def test_registry_kinds_snapshot_and_tree():
    reg = MetricsRegistry()
    reg.counter("a/events").inc()
    reg.counter("a/events").inc(2)
    reg.set("a/b/label", "x")
    assert reg.value("a/events") == 3
    snap = reg.snapshot("a/b/")
    assert snap == {"a/b/label": "x"}
    tree = reg.tree()
    assert tree["a"]["events"] == 3 and tree["a"]["b"]["label"] == "x"
    with pytest.raises(TypeError):
        reg.gauge("a/events")          # kind mismatch is an error


def test_stats_adapters_preserve_legacy_shapes(traced_run):
    eng = traced_run["engine"]
    st = eng.stats()
    for key in ("steps", "clock_s", "preempt_swaps", "preempt_recomputes",
                "kv", "transport"):
        assert key in st, key
    reg = eng.metrics()
    snap = reg.snapshot()
    p = f"serve/{eng.tenant}"
    assert snap[f"{p}/clock_s"] == st["clock_s"]
    assert snap[f"{p}/preempt_swaps"] == st["preempt_swaps"]
    tx = st["transport"]
    assert "links" in tx, "per-link stats missing (pre-obs regression)"
    for name, row in tx["links"].items():
        assert set(row) >= {"busy_s", "bytes", "peak_flows", "stretch_s"}


# ---------------------------------------------------------------------------
# determinism: bit-identical event streams
# ---------------------------------------------------------------------------

def test_same_trace_same_seed_bit_identical_events(model, params,
                                                   traced_run):
    _, tracer2, handles2 = _traced_local_run(model, params)
    assert traced_run["tracer"].events() == tracer2.events()
    assert ([h.tokens for h in traced_run["handles"]]
            == [h.tokens for h in handles2])


def test_local_vs_solo_arbiter_identical_engine_events(model, params,
                                                       traced_run):
    """A lone tenant under the arbiter replays the private-pool event
    stream bit-identically — the arbiter adds no modeled time and the
    tracer observes the same clocks."""
    tracer = Tracer()
    arb = PoolArbiter(POOL_PAGES, page_size=8)
    solo = Engine.local(model, _cfg(), params=params,
                        budget=KVBudget(tier2_bytes=1e9, page_size=8),
                        arbiter=arb, tenant="a", tracer=tracer)
    handles = run_trace(solo, _heavy())
    assert traced_run["tracer"].events() == tracer.events()
    assert ([h.tokens for h in traced_run["handles"]]
            == [h.tokens for h in handles])


def test_tracing_never_perturbs_tokens_or_clock(model, params, traced_run):
    """Zero-cost-when-disabled, observed from the other side: an
    untraced run is bit-identical to the traced one in every modeled
    quantity (tracing is passive observation, never a participant)."""
    eng = Engine.local(model, _cfg(), params=params,
                       budget=KVBudget(tier1_pages=POOL_PAGES,
                                       tier2_bytes=1e9, page_size=8),
                       tenant="a")
    assert eng.tracer is NULL_TRACER
    handles = run_trace(eng, _heavy())
    assert ([h.tokens for h in handles]
            == [h.tokens for h in traced_run["handles"]])
    for key in ("steps", "clock_s", "preempt_swaps", "preempt_recomputes"):
        assert eng.stats()[key] == traced_run["engine"].stats()[key], key


def test_request_lifecycle_spans_present(traced_run):
    tracer = traced_run["tracer"]
    tracks = tracer.tracks()
    assert "engine:a" in tracks and "engine:a/requests" in tracks
    reqs = [e for e in tracer.iter_track("engine:a/requests")
            if e.ph == "X" and e.cat == CAT_REQUEST]
    assert len(reqs) == len(traced_run["handles"])
    for e in reqs:
        assert e.dur > 0 and {"rid", "tokens", "ttft_s"} <= set(e.args)
    # paging pressure shows up as kv-category events on the engine row
    assert any(e.cat == CAT_KV for e in tracer.iter_track("engine:a"))


# ---------------------------------------------------------------------------
# exporter: trace_event schema
# ---------------------------------------------------------------------------

def test_chrome_export_validates_and_roundtrips(traced_run, tmp_path):
    path = tmp_path / "trace.json"
    doc = write_chrome_trace(traced_run["tracer"], str(path),
                             extra_metadata={"suite": "test_obs"})
    assert validate_trace_events(doc) == []
    with open(path) as f:
        loaded = json.load(f)
    assert validate_trace_events(loaded) == []
    assert loaded["otherData"]["suite"] == "test_obs"
    assert (loaded["otherData"]["events_recorded"]
            == traced_run["tracer"].total_recorded)
    evs = loaded["traceEvents"]
    names = {e["name"] for e in evs if e.get("ph") == "M"}
    assert {"process_name", "thread_name"} <= names
    rows = {(e["pid"], e["tid"]) for e in evs if e.get("ph") != "M"}
    labeled = {(e["pid"], e["tid"]) for e in evs
               if e.get("ph") == "M" and e["name"] == "thread_name"}
    assert rows <= labeled, "event row without thread_name metadata"


def test_validator_catches_malformed_events():
    bad = {"traceEvents": [
        {"ph": "X", "name": "no-dur", "pid": 1, "tid": 1, "ts": 0.0},
        {"ph": "?", "name": "bad-ph"},
        {"ph": "i", "pid": 1, "tid": 1, "ts": 0.0},        # no name
    ]}
    problems = validate_trace_events(bad)
    assert len(problems) == 3
    assert validate_trace_events({"nope": 1}) != []


# ---------------------------------------------------------------------------
# per-link conservation + report parity (the fig10 attribution base)
# ---------------------------------------------------------------------------

def _shared_trunk_transport(tracer=None, bw=10 * GB):
    """Two endpoints with private leaf links into one shared trunk."""
    topo = Topology("y")
    for n in ("a", "b", "sw", "mem"):
        topo.add_node(n, kind="memory" if n == "mem" else
                      ("switch" if n == "sw" else "endpoint"))
    topo.connect("a", "sw", fb.CXL3, capacity=bw, latency=1e-6)
    topo.connect("b", "sw", fb.CXL3, capacity=bw, latency=1e-6)
    topo.connect("sw", "mem", fb.CXL3, capacity=bw, latency=1e-6)
    return Transport(topo, tracer=tracer)


def test_per_link_busy_conservation_bound():
    """Every link's cumulative busy seconds must cover the bytes it
    carried at line rate (busy_s >= bytes/capacity) — the conservation
    bound that makes `sum(link busy) >= solo serialization seconds`
    checkable at all.  Before per-link accounting, ``Transport.stats()``
    had no ``links`` key and this test fails on the first assert."""
    tx = _shared_trunk_transport()
    nbytes = 1.0 * GB
    tx.begin_transfer(tx.route("a", "mem"), nbytes, 0.0)
    tx.begin_transfer(tx.route("b", "mem"), nbytes, 0.0)
    tx.quiesce()
    links = tx.stats()["links"]
    assert {"a->sw", "b->sw", "sw->mem"} <= set(links)
    # reverse directions exist in the topology but carried nothing
    assert links["mem->sw"]["bytes"] == 0.0
    assert links["mem->sw"]["busy_s"] == 0.0
    for name, row in links.items():
        cap = tx.topology.links[name].capacity
        assert row["busy_s"] >= row["bytes"] / cap - 1e-9, name
    # the shared trunk carried both flows: full serialization floor
    trunk = links["sw->mem"]
    assert trunk["bytes"] == pytest.approx(2 * nbytes)
    assert trunk["busy_s"] >= 2 * nbytes / (10 * GB) - 1e-9
    assert trunk["peak_flows"] == 2
    assert links["a->sw"]["peak_flows"] == 1
    # contention stretch: each flow ran at half rate through the trunk
    assert trunk["stretch_s"] > 0.0
    # sum over links covers any one flow's solo serialization time
    solo = nbytes / (10 * GB)
    assert sum(r["busy_s"] for r in links.values()) >= solo


def test_link_report_live_vs_from_trace_parity(tmp_path):
    tracer = Tracer()
    tx = _shared_trunk_transport(tracer=tracer)
    tx.begin_transfer(tx.route("a", "mem"), 0.5 * GB, 0.0)
    tx.begin_transfer(tx.route("b", "mem"), 0.25 * GB, 0.0)
    tx.begin_transfer(tx.route("a", "mem"), 0.125 * GB, 0.05)
    tx.quiesce()
    live = link_report(tx)
    doc = to_chrome_trace(tracer)
    assert validate_trace_events(doc) == []
    replay = link_report_from_trace(doc)
    # the replayed report covers exactly the links that saw traffic
    # (idle reverse-direction links never emitted occupancy spans)
    busy = {n for n, r in live.items() if r["bytes"] > 0}
    assert set(replay) == busy
    for name in busy:
        for key in ("busy_s", "bytes", "stretch_s"):
            assert replay[name][key] == pytest.approx(
                live[name][key], rel=1e-9, abs=1e-9), (name, key)
        assert replay[name]["peak_flows"] == live[name]["peak_flows"]
        assert replay[name]["tier"] == live[name]["tier"]
    tiers = tier_report(live)
    assert sum(r["links"] for r in tiers.values()) == len(live)


def test_transport_metrics_registry_schema():
    tx = _shared_trunk_transport()
    tx.begin_transfer(tx.route("a", "mem"), 0.5 * GB, 0.0)
    tx.quiesce()
    reg = tx.metrics()
    snap = reg.snapshot()
    assert snap["fabric/transfers"] == 1
    assert snap["fabric/link/a->sw/busy_s"] > 0
    assert snap["fabric/link/b->sw/busy_s"] == 0.0
    # the legacy dict is the adapter over this snapshot
    st = tx.stats()
    assert st["transfers"] == snap["fabric/transfers"]
    assert (st["links"]["a->sw"]["busy_s"]
            == snap["fabric/link/a->sw/busy_s"])


# ---------------------------------------------------------------------------
# JSONL streaming sink
# ---------------------------------------------------------------------------

def test_jsonl_sink_roundtrips_losslessly(tmp_path):
    path = str(tmp_path / "stream.jsonl")
    tracer = Tracer(capacity=4)         # deliberately tiny ring
    with JsonlSink(path, tracer) as sink:
        for i in range(16):             # overflows the ring 4x over
            tracer.span("engine:a", "decode", i * 0.1, 0.05,
                        tokens=i, exact=1.0 / 3.0)
        tracer.instant("pool:sched", "admit", 99.0, job="j", gang="")
    assert sink.written == 17
    assert tracer.dropped > 0           # the ring DID drop events...
    evs = events_from_jsonl(path)
    assert len(evs) == 17               # ...but the stream kept them all
    assert evs[0].args["exact"] == 1.0 / 3.0    # full float precision
    assert evs[-1].track == "pool:sched"
    # the surviving ring tail agrees with the stream tail
    assert tracer.events() == evs[-4:]


def test_jsonl_sink_attach_detach_contract(tmp_path):
    path = str(tmp_path / "s.jsonl")
    tracer = Tracer()
    sink = JsonlSink(path)
    sink.attach(tracer)
    with pytest.raises(RuntimeError):
        sink.attach(tracer)             # double-attach is a bug
    tracer.instant("t", "a", 0.0)
    sink.close()
    tracer.instant("t", "b", 1.0)       # after close: not streamed
    assert [e.name for e in events_from_jsonl(path)] == ["a"]
    sink.close()                        # idempotent


def test_events_from_jsonl_rejects_malformed_lines(tmp_path):
    p = tmp_path / "bad.jsonl"
    p.write_text('{"ph":"i","cat":"c","track":"t","name":"n",'
                 '"ts":0.0,"dur":0.0,"args":{}}\n'
                 '\n'                   # blank lines are skipped
                 'not json\n')
    with pytest.raises(ValueError, match="bad.jsonl:3"):
        events_from_jsonl(str(p))


def test_jsonl_sink_rotation_is_lossless(tmp_path):
    """max_bytes rotation: every event survives across the segment set,
    segments stay under the cap (except a single oversized line),
    suffixes are chronological, and events_from_jsonl stitches the set
    back together in emission order."""
    import os
    path = str(tmp_path / "rot.jsonl")
    tracer = Tracer()
    with JsonlSink(path, tracer, max_bytes=512) as sink:
        for i in range(64):
            tracer.instant("t", "tick", i * 0.1, i=i)
    assert len(sink.paths) > 1                   # it actually rotated
    assert sink.paths == rotated_jsonl_paths(path)
    assert sink.paths[0] == path
    assert [int(p.rsplit(".", 1)[-1]) for p in sink.paths[1:]] == \
        list(range(1, len(sink.paths)))          # never renamed
    for p in sink.paths:
        assert os.path.getsize(p) <= 512
    evs = events_from_jsonl(path)                # reads the whole set
    assert len(evs) == sink.written == 64
    assert [e.args["i"] for e in evs] == list(range(64))


def test_jsonl_sink_oversized_line_lands_alone(tmp_path):
    path = str(tmp_path / "big.jsonl")
    tracer = Tracer()
    with JsonlSink(path, tracer, max_bytes=64) as sink:
        tracer.instant("t", "small", 0.0)
        tracer.instant("t", "huge", 1.0, blob="x" * 300)
        tracer.instant("t", "after", 2.0)
    # the 300B line exceeds max_bytes but is never dropped: it opens a
    # segment of its own
    assert len(sink.paths) == 3
    assert [e.name for e in events_from_jsonl(path)] == \
        ["small", "huge", "after"]


def test_jsonl_sink_retention_keeps_the_tail(tmp_path):
    import os
    path = str(tmp_path / "ring.jsonl")
    tracer = Tracer()
    with JsonlSink(path, tracer, max_bytes=128, max_files=2) as sink:
        for i in range(40):
            tracer.instant("t", "tick", i * 0.1, i=i)
    assert len(sink.paths) == 2                  # disk-bounded ring
    assert not os.path.exists(path)              # oldest segments gone
    assert rotated_jsonl_paths(path) == sink.paths
    evs = events_from_jsonl(path)        # resolves the surviving set
    # the surviving set is the most recent tail, contiguous to the end
    idx = [e.args["i"] for e in evs]
    assert idx == list(range(idx[0], 40))
    assert sink.written == 40                    # writes were lossless;
                                                 # retention trimmed disk


def test_jsonl_sink_rotation_validates_args(tmp_path):
    with pytest.raises(ValueError, match="max_bytes"):
        JsonlSink(str(tmp_path / "a.jsonl"), max_bytes=0)
    with pytest.raises(ValueError, match="max_files"):
        JsonlSink(str(tmp_path / "b.jsonl"), max_files=0)
