"""The serving engine's host spans and counters: spans land on the
profiler's host plane, nested as the engine's phases are, with their
arguments; ``pages_written`` counts the pages prefill writes; and
``RequestHandle.admit_step`` names the step whose prefill first ran the
request."""

import glob
import os

import jax
import pytest

from repro.configs import SMOKE_ARCHS
from repro.core.tiering import KVBudget
from repro.models.api import build_model
from repro.serve import Engine, EngineConfig, Request, burst_trace, run_trace
from repro.serve.engine import SPAN_NAMES

VOCAB = SMOKE_ARCHS["qwen1.5-0.5b"].vocab
PAGE = 8


@pytest.fixture(scope="module")
def model():
    cfg = SMOKE_ARCHS["qwen1.5-0.5b"].__class__(**{
        **SMOKE_ARCHS["qwen1.5-0.5b"].__dict__, "compute_dtype": "float32"})
    return build_model(cfg)


@pytest.fixture(scope="module")
def params(model):
    return model.init(jax.random.PRNGKey(0))


def _engine(model, params, budget=None):
    cfg = EngineConfig(max_slots=3, max_seq=64, page_size=PAGE)
    return Engine.local(model, cfg, params=params, budget=budget)


def _trace(n=5, prompt=12, new=6):
    return burst_trace(n, prompt_len=prompt, max_new_tokens=new,
                       vocab=VOCAB, seed=0)


def _profiled(tmp_path, run):
    """Host-plane engine spans of ``run()`` under the profiler:
    ``[(name, start_ns, end_ns, stats)]``."""
    from jax.profiler import ProfileData
    out = str(tmp_path / "prof")
    jax.profiler.start_trace(out)
    try:
        run()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in SPAN_NAMES:
                    s = int(ev.start_ns)
                    spans.append((ev.name, s, s + int(ev.duration_ns),
                                  dict(ev.stats)))
    return spans


def _inside(inner, spans, name):
    """The ``name`` spans that cover ``inner``."""
    return [sp for sp in spans if sp[0] == name
            and sp[1] <= inner[1] and inner[2] <= sp[2]]


def test_spans_nest_on_the_host_plane(model, params, tmp_path):
    eng = _engine(model, params)
    for r in _trace(n=2, prompt=12, new=3):
        eng.submit(r)
    spans = _profiled(tmp_path, eng.run_until_idle)
    by = {n: [sp for sp in spans if sp[0] == n] for n in SPAN_NAMES}
    assert [sp[3]["step_num"] for sp in by["engine.step"]] == list(
        range(eng.steps))
    assert len(by["engine.prefill"]) == 2
    for pf in by["engine.prefill"]:
        assert pf[3]["prompt_len"] == 12 and pf[3]["bucket"] == 16
        admit, = _inside(pf, spans, "engine.admit")
        assert _inside(admit, spans, "engine.step")
        assert len([r for r in by["engine.read"]
                    if pf[1] <= r[1] and r[2] <= pf[2]]) == 1
    assert sorted(sp[3]["admitted"] for sp in by["engine.admit"]) == [2]
    for pw in by["engine.page_write"]:
        assert pw[3]["pages"] == 2                       # ceil(12 / 8)
        assert _inside(pw, spans, "engine.admit")
        assert not _inside(pw, spans, "engine.prefill")
    assert sorted(pw[3]["rid"] for pw in by["engine.page_write"]) == [0, 1]
    assert by["engine.decode"]
    for dec in by["engine.decode"]:
        assert dec[3]["rows"] == 2 and dec[3]["bucket"] == 2
        assert _inside(dec, spans, "engine.step")
        for name in ("engine.read", "engine.emit"):
            assert len([sp for sp in by[name]
                        if dec[1] <= sp[1] and sp[2] <= dec[2]]) == 1


def test_tier2_page_moves_are_spanned(model, params, tmp_path):
    eng = _engine(model, params, KVBudget(tier1_pages=6, tier2_bytes=1e9,
                                          page_size=PAGE))
    spans = _profiled(tmp_path, lambda: run_trace(eng, _trace()))
    kv = eng.stats()["kv"]
    for name, moved in (("engine.spill", kv["spills"]),
                        ("engine.fetch", kv["fetches"])):
        found = [sp for sp in spans if sp[0] == name]
        assert found and sum(sp[3]["pages"] for sp in found) == moved
        assert all(_inside(sp, spans, "engine.step") for sp in found)
        assert all("rid" in sp[3] for sp in found)


def test_pages_written_and_admit_step(model, params):
    eng = _engine(model, params)
    prompts = [12, 5, 17, 8, 30]
    handles = [eng.submit(Request(tuple(range(1, n + 1)), 4))
               for n in prompts]
    first = {}
    while not eng.idle:
        n = eng.steps
        eng.step()
        for h in handles:
            if h.tokens and h.rid not in first:
                first[h.rid] = n
    assert eng.pages_written == sum(-(-n // PAGE) for n in prompts)
    assert eng.stats()["pages_written"] == eng.pages_written
    assert {h.rid: h.admit_step for h in handles} == first
    assert len(set(first.values())) > 1          # 3 slots: admitted apart


def test_admit_step_survives_a_recompute(model, params):
    eng = _engine(model, params, KVBudget(tier1_pages=6, tier2_bytes=0.0,
                                          page_size=PAGE))
    handles = []
    for r in _trace(n=5, prompt=12, new=8):
        handles.append(eng.submit(r))
    first = {}
    while not eng.idle:
        n = eng.steps
        eng.step()
        for h in handles:
            if h.tokens and h.rid not in first:
                first[h.rid] = n
    assert sum(h.recomputes for h in handles) > 0
    assert {h.rid: h.admit_step for h in handles} == first
    # re-prefills write their pages again
    assert eng.pages_written > sum(-(-12 // PAGE) for _ in handles)


def test_admit_step_unset_before_admission(model, params):
    eng = _engine(model, params)
    h = eng.submit(Request((1, 2, 3), 2))
    assert h.admit_step is None
    eng.step()
    assert h.admit_step == 0 and len(h.tokens) == 2   # prefill + decode
