"""Engine host spans in a profile: the idle attribution of
``bench.spans.host_spans`` on a hand-built trace, the readers over a
hand-built record, the pages a window's prefills imply against the
engine's own count at test size on the CPU, and the engine span names in
a profile recorded on the chip."""

from benchtools import REPO, cell_root  # noqa: F401  (fixture)

import json
import time

import jax
import jax._src.monitoring as monitoring
import pytest

from bench import common, readers, serve, spans, trace
from repro.serve.engine import SPAN_NAMES

MS = 1_000_000


def _hand():
    # window 0..100 ms; chip 0 busy 10..40 (decode) and 60..70 (a page
    # write), so idle 0..10, 40..60 and 70..100: 60 ms in all
    return {
        "devices": {
            "0": {"ops": [["fusion.1", 10 * MS, 30 * MS, "jit_paged_decode"],
                          ["copy.2", 60 * MS, 10 * MS, "jit_scatter"]],
                  "modules": [["jit_paged_decode(7)", 10 * MS, 30 * MS],
                              ["jit_scatter(9)", 60 * MS, 10 * MS]]}},
        "host": [["bench.window", 0, 100 * MS],
                 ["bench.step", 4 * MS, 47 * MS],
                 ["engine.step", 5 * MS, 45 * MS],
                 ["engine.decode", 8 * MS, 37 * MS],
                 ["np.asarray", 39 * MS, 5 * MS],      # not an engine span
                 ["engine.read", 38 * MS, 6 * MS],
                 ["engine.step", 55 * MS, 35 * MS],
                 ["engine.admit", 56 * MS, 19 * MS],
                 ["engine.prefill", 57 * MS, 8 * MS],
                 ["engine.read", 62 * MS, 3 * MS],
                 ["engine.page_write", 66 * MS, 8 * MS]],
    }


def test_idle_goes_to_the_innermost_engine_span():
    hs = spans.host_spans(_hand())
    idle = {k: v["idle_s"] * 1e3 for k, v in hs["spans"].items()}
    assert idle == pytest.approx({
        "engine.step": 3 + 5 + 1 + 15, "engine.decode": 2 + 1,
        "engine.read": 4, "engine.admit": 1 + 1, "engine.prefill": 3,
        "engine.page_write": 4})
    assert hs["idle_outside_s"] * 1e3 == pytest.approx(5 + 5 + 10)
    total = sum(idle.values()) / 1e3 + hs["idle_outside_s"]
    red = trace.reduce(_hand())
    assert total == pytest.approx(red["window_s"] - red["chips"][0]["busy_s"])
    assert hs["spans"]["engine.step"]["count"] == 2
    assert hs["spans"]["engine.read"]["count"] == 2
    assert hs["spans"]["engine.admit"]["seconds"] == pytest.approx(0.019)


def test_spans_are_clipped_to_the_window_and_may_be_absent():
    ext = _hand()
    ext["host"][0] = ["bench.window", 20 * MS, 40 * MS]     # 20..60 ms
    hs = spans.host_spans(ext)
    assert hs["spans"]["engine.step"]["seconds"] == pytest.approx(0.035)
    assert set(hs["spans"]) == {"engine.step", "engine.decode",
                                "engine.read", "engine.admit",
                                "engine.prefill"}
    assert (sum(v["idle_s"] for v in hs["spans"].values())
            + hs["idle_outside_s"]) == pytest.approx(0.020)
    ext["host"] = [h for h in ext["host"] if not h[0].startswith("engine.")]
    bare = spans.host_spans(ext)
    assert bare["spans"] == {}
    assert bare["idle_outside_s"] == pytest.approx(0.020)


def _record():
    rec = {"window_s": 10.0, "page_size": 32,
           "steps": [{"n": 5, "start": 1.0, "prefills": [33]},
                     {"n": 6, "start": 2.0, "prefills": []},
                     {"n": 7, "start": 3.5, "prefills": [32, 1]},
                     {"n": 8, "start": 4.0, "prefills": [64]}],  # untraced
           "requests": [{"due": 0.5, "admit_step": 5},
                        {"due": 1.5, "admit_step": 7},
                        {"due": 9.0, "admit_step": None},   # never admitted
                        {"due": 12.0, "admit_step": None}],  # not yet due
           "trace": trace.reduce(_hand()), "traced": {"steps": [0, 3]},
           "setup_jit_s": 4.5}
    rec["trace"]["host_spans"] = spans.host_spans(_hand())
    return rec


def test_readers_on_a_hand_record():
    rec = _record()
    assert spans.step_idle_ms(rec) == pytest.approx(40.0 / 2)
    # waits 0.5, 2.0 and, unadmitted, 10 - 9 = 1.0
    assert spans.queue_wait_quantile(rec, 0.5) == pytest.approx(1.0)
    assert spans.queue_wait_quantile(rec, 0.9) == pytest.approx(1.8)
    # 10 ms of jit_scatter over the 2 + 1 + 1 pages of the traced steps
    assert spans.page_write_ms(rec) == pytest.approx(2.5)
    assert spans.setup_jit_s(rec) == 4.5


def test_readers_find_nothing_without_the_engine_counters():
    rec = _record()
    for s in rec["steps"]:
        del s["n"]
    del rec["trace"]["host_spans"], rec["setup_jit_s"]
    assert spans.step_idle_ms(rec) is None
    assert spans.queue_wait_quantile(rec, 0.9) is None
    assert spans.setup_jit_s(rec) is None
    # the page count comes from the harness's own step records
    assert spans.page_write_ms(rec) == pytest.approx(2.5)


def test_page_write_metric_reads_nothing_without_a_trace_or_a_prefill():
    mod = common.load_module(REPO / "bench" / "metrics"
                             / "page_write_ms.chat.py")
    rec = _record()
    assert mod.read(rec) == pytest.approx(2.5)
    for s in rec["steps"][:3]:
        s["prefills"] = []
    assert mod.read(rec) is None
    del rec["trace"], rec["traced"]
    assert mod.read(rec) is None


def test_jit_seconds_take_nested_traces_once():
    intervals = []

    def on_duration(event, duration, **kw):
        if event in spans.JIT_EVENTS:
            t = time.perf_counter()
            intervals.append((t - duration, t))
    monitoring.register_event_duration_secs_listener(on_duration)
    try:
        f = jax.jit(lambda x: jax.jit(lambda y: y * 2)(x) + 1)
        f(1.0).block_until_ready()
    finally:
        monitoring.unregister_event_duration_listener(on_duration)
    total = sum(b - a for a, b in intervals)
    assert 0 < spans.jit_seconds(intervals) < total   # the inner trace nests


def test_prefills_count_the_pages_the_engine_wrote(cell_root):
    cell = common.load_cell("tiny-gqa.ol", cell_root)
    engine, mcfg, _ = serve.build(cell, 2**31 + 7)
    serve.warm_up(engine, cell)
    before = engine.pages_written
    rec = serve.summarize(serve.run_window(engine, cell, 2**31 + 7, 3.0),
                          mcfg, cell)
    ps = rec["page_size"]
    pages = sum(-(-n // ps) for s in rec["steps"] for n in s["prefills"])
    assert pages > 0
    assert pages == engine.pages_written - before


def test_recorded_chip_trace_has_the_engine_spans():
    # ~80 ms of a chat-cell profile from a TPU v5 lite with the engine's
    # host spans, in the form ``bench.trace.extract`` gives
    ext = json.loads((REPO / "tests" / "bench" / "data"
                      / "chip_trace_chat_spans_v5e.json").read_text())
    hs = spans.host_spans(ext)
    assert {"engine.step", "engine.page_write"} <= set(hs["spans"])
    # every engine span the chip recorded has a name the readers match
    recorded = {h[0] for h in ext["host"] if h[0].startswith("engine.")}
    assert recorded and recorded <= set(SPAN_NAMES)
    red = trace.reduce(ext)
    idle = red["window_s"] - red["chips"][0]["busy_s"]
    assert idle > 0
    assert (sum(v["idle_s"] for v in hs["spans"].values())
            + hs["idle_outside_s"]) == pytest.approx(idle)
    assert spans.step_idle_ms({"trace": {**red, "host_spans": hs}}) > 0
    # a gap inside a step is named by an engine span, not the harness's
    assert red["idle_gaps"] and not any(
        name == "bench.step" for name, _ in red["idle_gaps"])
    assert readers.program_share({"trace": red}, "other") > 0
