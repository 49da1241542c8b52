"""repro.serve validation: engine determinism (same trace -> same tokens
under any arrival interleaving; lease-backed == local construction),
physical-page-pool accounting with page-granular, bit-exact evict/fetch
round trips, token fidelity over scattered (non-contiguous) page
layouts, modeled-clock attribution invariants, bucketed-prefill compile
bounds, and request-level failure semantics."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SMOKE_ARCHS
from repro.core.tiering import KVBudget, KVBudgetExceeded, PagedKV
from repro.models.api import build_model
from repro.serve import (Engine, EngineConfig, Request, RequestStatus,
                         burst_trace, latency_summary, load_trace,
                         run_trace, synthetic_trace)
from repro.serve.api import RequestHandle

VOCAB = SMOKE_ARCHS["qwen1.5-0.5b"].vocab


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


def _trace(n=5, prompt=12, new=6, seed=0):
    return burst_trace(n, prompt_len=prompt, max_new_tokens=new,
                       vocab=VOCAB, seed=seed)


def _check_clock_invariants(handles):
    """Every event clock sits at or after the previous event's."""
    for h in handles:
        if h.first_token_clock is not None:
            assert h.first_token_clock >= h.submit_clock
        if h.done_clock is not None and h.first_token_clock is not None:
            assert h.done_clock >= h.first_token_clock


# ---------------------------------------------------------------------------
# PagedKV: physical pool accounting + page-granular budget enforcement
# ---------------------------------------------------------------------------

def test_paged_kv_budget_enforced():
    kv = PagedKV(KVBudget(tier1_pages=4, tier2_bytes=100.0, page_size=8),
                 page_bytes=50.0)
    pa = kv.alloc("a", 2)
    pb = kv.alloc("b", 2)
    assert sorted(pa + pb) == [0, 1, 2, 3]       # distinct physical pages
    with pytest.raises(KVBudgetExceeded):
        kv.alloc("c", 1)                         # tier-1 pool full
    kv.evict("a", 0, payload={"x": 1})           # 1 page * 50B fits
    kv.evict("a", 1, payload={"x": 2})           # 2 * 50B = the whole budget
    assert kv.hot_free == 2 and kv.cold_bytes_used == 100.0
    assert kv.cold_logicals("a") == [0, 1] and not kv.is_fully_hot("a")
    with pytest.raises(KVBudgetExceeded):
        kv.evict("b", 0, payload={})             # tier-2 budget full
    phys, payload = kv.fetch("a", 0)
    assert payload == {"x": 1} and kv.page_table("a")[0] == phys
    kv.grow("a", 3)                              # 1 free page left: fits
    with pytest.raises(KVBudgetExceeded):
        kv.grow("a", 4)                          # pool exhausted again
    kv.free("a")
    kv.free("b")
    assert kv.hot_pages_used == 0 and kv.cold_pages_used == 0
    assert kv.hot_free == 4


def test_paged_kv_page_round_trip_bit_exact_and_relocated():
    rng = np.random.RandomState(0)
    page = {"k": rng.standard_normal((2, 8, 2, 4)).astype(np.float32),
            "v": np.asarray(jnp.asarray(
                rng.standard_normal((2, 8, 2, 4)), jnp.bfloat16))}
    kv = PagedKV(KVBudget(tier1_pages=4, tier2_bytes=1e9, page_size=8),
                 page_bytes=1024.0)
    kv.alloc("r", 2)
    old_phys = kv.page_table("r")[1]
    kv.evict("r", 1, page)
    kv.alloc("q", 1)                   # steals the freed physical page
    phys, back = kv.fetch("r", 1)      # must land somewhere else
    assert phys != old_phys
    np.testing.assert_array_equal(back["k"], page["k"])
    np.testing.assert_array_equal(back["v"], page["v"])
    assert kv.spills == 1 and kv.fetches == 1


def test_paged_kv_noncontiguous_reuse():
    kv = PagedKV(KVBudget(tier1_pages=4, tier2_bytes=0.0, page_size=8),
                 page_bytes=1.0)
    kv.alloc("a", 1)
    kv.alloc("b", 1)
    kv.free("a")
    phys = kv.alloc("c", 2)            # reuses a's page: non-contiguous
    assert len(set(phys)) == 2         # distinct pages; order unspecified


# ---------------------------------------------------------------------------
# engine: paging under pressure equals the unbudgeted run bit-exactly
# ---------------------------------------------------------------------------

def test_engine_budget_pressure_tokens_bit_exact(model, params):
    """A tier-1 pool tight enough to force page-granular evictions must
    reproduce the unbudgeted run token-for-token: evicted pages round-
    trip bit-exactly and the kernel's output is independent of the
    physical page layout."""
    trace = _trace()
    ref = Engine.local(model, _cfg(), params=params)
    ref_handles = run_trace(ref, trace)

    tight = Engine.local(model, _cfg(), params=params,
                         budget=KVBudget(tier1_pages=6, tier2_bytes=1e9,
                                         page_size=8))
    tight_handles = run_trace(tight, trace)
    stats = tight.stats()
    assert stats["preempt_swaps"] > 0, "budget pressure not exercised"
    assert stats["kv"]["spills"] > 0 and stats["kv"]["fetches"] > 0, \
        "no page actually rode the tier-2 fabric"
    assert [h.tokens for h in tight_handles] == \
        [h.tokens for h in ref_handles]
    _check_clock_invariants(tight_handles)


def test_engine_serves_scattered_pages(model, params):
    """After preemption scatters a request's KV across non-contiguous
    physical pages, its tokens still match the dense-cache greedy
    reference (model.prefill + model.decode, no engine)."""
    prompt = tuple(np.random.RandomState(7).randint(
        1, VOCAB, size=12).tolist())
    new = 8

    # dense reference: contiguous cache, one sequence, greedy argmax
    cache = model.init_cache(1, 64, dtype=jnp.float32)
    logits, cache = model.prefill(
        params, {"tokens": jnp.asarray([prompt], jnp.int32)}, cache)
    want = [int(np.argmax(np.asarray(logits)[0, -1]))]
    idx = len(prompt)
    while len(want) < new:
        logits, cache = model.decode(
            params, jnp.asarray([[want[-1]]], jnp.int32), cache,
            jnp.int32(idx))
        want.append(int(np.argmax(np.asarray(logits)[0, -1])))
        idx += 1

    # engine under pressure: competing requests force the probe request
    # to be paused, paged out, and resumed into different physical pages
    eng = Engine.local(model, _cfg(), params=params,
                       budget=KVBudget(tier1_pages=6, tier2_bytes=1e9,
                                       page_size=8))
    probe = eng.submit(Request(prompt, new))
    others = [eng.submit(r) for r in _trace(n=3, prompt=12, new=8, seed=1)]
    scattered = False
    for _ in range(10_000):
        if eng.idle:
            break
        eng.step()
        if eng.kv.holds(probe.rid):
            table = [p for p in eng.kv.page_table(probe.rid)
                     if p is not None]
            if table != sorted(table) or \
                    any(b - a != 1 for a, b in zip(table, table[1:])):
                scattered = True
    assert probe.status is RequestStatus.DONE
    assert eng.kv.fetches > 0, "probe never paged back in"
    assert scattered, "page table stayed contiguous — pressure too soft"
    assert probe.tokens == want
    assert all(o.status is RequestStatus.DONE for o in others)


def test_prefill_page_writes_match_batched_scatter(model, params):
    """Page-granular prefill writes (slice_page -> _write_page, the
    disaggregated-streaming seam) must compose to exactly the old
    batched ``.at[:, idx].set`` scatter: same tokens AND a bit-equal
    physical pool after the run, including partially-filled tail
    pages."""
    import types

    def old_scatter(self, cache, phys, plen):
        ps = self.cfg.page_size
        n_copy = -(-plen // ps)
        idx = jnp.asarray(np.asarray(phys[:n_copy], np.int32))

        def put(pool_leaf, cache_leaf):
            lay = cache_leaf.shape[0]
            tail = tuple(cache_leaf.shape[3:])
            pages = cache_leaf[:, 0].reshape(
                (lay, -1, ps) + tail)[:, :n_copy]
            return pool_leaf.at[:, idx].set(pages.astype(pool_leaf.dtype))

        self._pool = jax.tree.map(put, self._pool, cache)

    trace = _trace(n=3, prompt=12, new=4)     # 12 % 8 != 0: partial page
    paged = Engine.local(model, _cfg(), params=params)
    batched = Engine.local(model, _cfg(), params=params)
    batched._write_prefill_pages = types.MethodType(old_scatter, batched)
    hs_paged = run_trace(paged, trace)
    hs_batched = run_trace(batched, trace)
    assert [h.tokens for h in hs_paged] == [h.tokens for h in hs_batched]
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)),
        paged._pool, batched._pool)


def test_engine_deterministic_across_arrival_interleavings(model, params):
    """Same requests, different arrival interleavings (burst vs staggered
    vs reversed submission) -> identical per-request tokens."""
    prompts = [tuple(np.random.RandomState(i).randint(
        1, VOCAB, size=10 + 2 * i).tolist()) for i in range(4)]

    def run_with(arrivals, order):
        eng = Engine.local(model, _cfg(), params=params)
        reqs = [Request(prompts[i], 5, arrival_time=arrivals[i])
                for i in range(4)]
        handles = run_trace(eng, [reqs[i] for i in order])
        by_prompt = {h.request.prompt_tokens: h.tokens for h in handles}
        return [by_prompt[p] for p in prompts]

    burst = run_with([0.0] * 4, [0, 1, 2, 3])
    staggered = run_with([0.0, 0.004, 0.008, 0.02], [0, 1, 2, 3])
    shuffled = run_with([0.0] * 4, [2, 0, 3, 1])
    assert burst == staggered == shuffled


def test_engine_lease_and_local_identical(model):
    from repro.pool import smoke_pool
    pool = smoke_pool("scalepool")
    lease = pool.lease("serve-eng", 4, tier2_gb=64, kv_gb=1.0)
    trace = _trace(n=4)
    local = run_trace(Engine.local(model, _cfg()), trace)
    leased = run_trace(Engine.from_lease(model, lease, _cfg()), trace)
    assert [h.tokens for h in local] == [h.tokens for h in leased]


@pytest.mark.parametrize("build", ["local", "lease"])
def test_dropped_engine_freed_without_gc(model, params, build):
    """An engine that served requests is freed by reference counting
    alone: no cycle keeps its params and page pool alive until the next
    garbage collection (on a chip that is HBM the next engine needs)."""
    import gc
    import weakref
    if build == "lease":
        from repro.pool import smoke_pool
        lease = smoke_pool("scalepool").lease("serve-free", 4, tier2_gb=64,
                                              kv_gb=1.0)
    gc.collect()
    gc.disable()
    try:
        eng = (Engine.local(model, _cfg(), params=params) if build == "local"
               else Engine.from_lease(model, lease, _cfg(), params=params))
        handles = run_trace(eng, _trace(n=4))
        eng.lower_decode()
        assert all(h.status is RequestStatus.DONE for h in handles)
        pool_leaf = weakref.ref(jax.tree.leaves(eng._pool)[0])
        ref = weakref.ref(eng)
        del eng, handles
        assert ref() is None, gc.get_referrers(ref())
        assert pool_leaf() is None
    finally:
        gc.enable()


# ---------------------------------------------------------------------------
# engine semantics: recycling, recompute preemption, OOM, stats
# ---------------------------------------------------------------------------

def test_engine_slot_recycling_and_fifo(model, params):
    eng = Engine.local(model, _cfg(max_slots=2), params=params)
    handles = [eng.submit(Request((1 + i,) * 8, 4)) for i in range(5)]
    eng.run_until_idle()
    assert all(h.status is RequestStatus.DONE for h in handles)
    assert all(len(h.tokens) == 4 for h in handles)
    # FIFO: a request never starts before an earlier one with 2 slots
    firsts = [h.first_token_clock for h in handles]
    assert firsts == sorted(firsts)
    assert eng.stats()["completed"] == 5
    assert eng.kv.hot_pages_used == 0       # everything freed
    _check_clock_invariants(handles)


def test_engine_recompute_preemption_still_completes(model, params):
    """Tier-1-only pressure cannot spill pages: victims drop their KV
    and re-prefill; every request still completes with its full budget."""
    trace = _trace(n=5, prompt=12, new=8)
    eng = Engine.local(model, _cfg(), params=params,
                       budget=KVBudget(tier1_pages=6, tier2_bytes=0.0,
                                       page_size=8))
    handles = run_trace(eng, trace)
    stats = eng.stats()
    assert stats["preempt_recomputes"] > 0
    assert stats["kv"]["spills"] == 0       # nowhere to spill to
    assert stats["failed_oom"] == 0
    assert all(len(h.tokens) == 8 for h in handles)


def test_engine_oom_when_request_can_never_fit(model, params):
    eng = Engine.local(model, _cfg(), params=params,
                       budget=KVBudget(tier1_pages=2, tier2_bytes=1e9,
                                       page_size=8))
    ok = eng.submit(Request((1, 2, 3), 4))            # 1 page: fits
    too_big = eng.submit(Request((5,) * 30, 20))      # 7 pages > quota
    eng.run_until_idle()
    assert ok.status is RequestStatus.DONE
    assert too_big.status is RequestStatus.FAILED_OOM
    with pytest.raises(RuntimeError, match="quota"):
        too_big.result()


def test_engine_submit_validates_capacity_and_vocab(model, params):
    eng = Engine.local(model, _cfg(), params=params)
    with pytest.raises(ValueError, match="max_seq"):
        eng.submit(Request((1,) * 60, 10))
    # out-of-range ids would be clamped by JAX's OOB gather: reject loudly
    with pytest.raises(ValueError, match="vocab"):
        eng.submit(Request((1, VOCAB, 2), 4))
    with pytest.raises(ValueError, match="vocab"):
        eng.submit(Request((1, -3, 2), 4))


def test_load_trace_validates_vocab(tmp_path):
    p = tmp_path / "trace.jsonl"
    p.write_text('{"prompt_tokens": [1, 2], "max_new_tokens": 4}\n'
                 '{"prompt_tokens": [1, %d], "max_new_tokens": 4}\n' % VOCAB)
    assert len(load_trace(str(p))) == 2              # unvalidated: loads
    with pytest.raises(ValueError, match="trace.jsonl:2"):
        load_trace(str(p), vocab=VOCAB)


def test_engine_stats_and_latency_summary(model, params):
    eng = Engine.local(model, _cfg(), params=params)
    trace = synthetic_trace(4, mean_interarrival_s=0.001,
                            prompt_lens=(8, 16), max_new_tokens=4,
                            vocab=VOCAB, seed=1)
    handles = run_trace(eng, trace)
    s = eng.stats()
    assert s["completed"] == 4 and s["queue_depth"] == 0
    assert s["tokens_decoded"] == s["throughput_tok_s"] * s["clock_s"] \
        == pytest.approx(4 * 3)            # first token comes from prefill
    lat = latency_summary(handles)
    assert lat["n"] == 4 and lat["p95_s"] >= lat["p50_s"] > 0


def test_engine_static_reservation_serializes(model, params):
    """reserve_lifetime holds a request's full lifetime from admission:
    under a tight quota concurrency collapses but results are intact."""
    trace = _trace(n=4, prompt=12, new=8)
    static = Engine.local(model, _cfg(reserve_lifetime=True), params=params,
                          budget=KVBudget(tier1_pages=4, tier2_bytes=0.0,
                                          page_size=8))
    paged = Engine.local(model, _cfg(), params=params)
    hs_static = run_trace(static, trace)
    hs_paged = run_trace(paged, trace)
    assert static.stats()["preempt_recomputes"] == 0
    assert all(len(h.tokens) == 8 for h in hs_static)
    assert latency_summary(hs_static)["p95_s"] > \
        latency_summary(hs_paged)["p95_s"]


# ---------------------------------------------------------------------------
# modeled-clock attribution
# ---------------------------------------------------------------------------

def test_engine_clock_attribution_exact(model, params):
    """Event clocks land on the event's modeled completion time: for a
    lone request, TTFT is exactly the (bucketed) prefill cost and total
    latency adds one decode step per remaining token — no off-by-a-step
    under-reporting from stamping before the step's dt accrues."""
    eng = Engine.local(model, _cfg(), params=params)
    plen, new = 12, 5
    h = eng.submit(Request(tuple(range(1, 1 + plen)), new))
    eng.run_until_idle()
    bucket = eng._bucket_len(plen)
    assert h.ttft == pytest.approx(eng.cost.prefill_s(bucket))
    want_latency = (eng.cost.prefill_s(bucket)
                    + sum(eng.cost.decode_s(1) for _ in range(new - 1)))
    assert h.latency == pytest.approx(want_latency)
    assert h.done_clock == pytest.approx(eng.clock)


def test_engine_failed_oom_clock_consistent(model, params):
    eng = Engine.local(model, _cfg(), params=params,
                       budget=KVBudget(tier1_pages=2, tier2_bytes=0.0,
                                       page_size=8))
    big = eng.submit(Request((5,) * 30, 20))
    eng.run_until_idle()
    assert big.status is RequestStatus.FAILED_OOM
    assert big.done_clock is not None
    assert big.done_clock >= big.submit_clock
    assert big.done_clock <= eng.clock


def test_engine_future_arrival_never_decoded_early(model, params):
    """A request submitted directly (not via run_trace) with a future
    arrival_time must not be admitted before the modeled clock reaches
    it — pre-fix it was enqueued and decoded immediately, stamping
    first_token_clock BEFORE submit_clock and driving ttft/latency
    negative."""
    eng = Engine.local(model, _cfg(), params=params)
    h = eng.submit(Request((1, 2, 3, 4), 4, arrival_time=0.5))
    assert h.submit_clock == 0.5
    dt = eng.step()                     # gated: nothing to do but wait
    assert h.tokens == [] and h.status is RequestStatus.QUEUED
    assert dt == 0.0 and eng.clock == 0.5    # idle-advance to arrival
    eng.run_until_idle()
    assert h.status is RequestStatus.DONE
    assert h.first_token_clock >= 0.5
    assert h.ttft is not None and h.ttft > 0
    assert h.latency is not None and h.latency > h.ttft > 0
    _check_clock_invariants([h])


def test_engine_future_arrivals_keep_fifo_order(model, params):
    """Arrival gating is head-of-line: a later-submitted request with an
    earlier arrival still waits behind the FIFO head (determinism over
    opportunism), and both complete with non-negative clocks."""
    eng = Engine.local(model, _cfg(), params=params)
    first = eng.submit(Request((1, 2, 3), 3, arrival_time=1.0))
    second = eng.submit(Request((4, 5, 6), 3, arrival_time=0.25))
    eng.run_until_idle()
    assert first.first_token_clock <= second.first_token_clock
    for h in (first, second):
        assert h.ttft > 0 and h.latency > 0
    # the head was served at its arrival, not at the earlier one
    assert first.first_token_clock >= 1.0


def test_engine_busy_throughput_not_idle_diluted(model, params):
    """stats(): total-clock throughput is diluted by idle inter-arrival
    gaps (advance_clock), so a sparse trace reports an arbitrarily low
    rate; busy_s / throughput_busy_tok_s must reflect only worked time."""
    eng = Engine.local(model, _cfg(), params=params)
    trace = [Request((1, 2, 3), 4, arrival_time=0.0),
             Request((4, 5, 6), 4, arrival_time=100.0)]
    run_trace(eng, trace)
    s = eng.stats()
    assert s["clock_s"] > 100.0
    assert 0.0 < s["busy_s"] < 1.0
    assert s["throughput_busy_tok_s"] == pytest.approx(
        s["tokens_decoded"] / s["busy_s"])
    # the diluted number is >100x off on this trace; the busy number
    # is invariant to the gap
    assert s["throughput_busy_tok_s"] > 100 * s["throughput_tok_s"]


def test_latency_summary_nearest_rank():
    def h(lat):
        rh = RequestHandle(rid=0, request=Request((1,), 1),
                           status=RequestStatus.DONE,
                           submit_clock=0.0, done_clock=lat)
        return rh

    # n=2: the old int(p*n) indexing returned the MAX as "p50"
    two = latency_summary([h(1.0), h(2.0)])
    assert two["p50_s"] == 1.0 and two["p95_s"] == 2.0
    three = latency_summary([h(1.0), h(2.0), h(3.0)])
    assert three["p50_s"] == 2.0 and three["p95_s"] == 3.0
    hundred = latency_summary([h(float(i)) for i in range(1, 101)])
    assert hundred["p50_s"] == 50.0 and hundred["p95_s"] == 95.0


# ---------------------------------------------------------------------------
# scheduling policy details
# ---------------------------------------------------------------------------

def test_engine_paused_resume_in_pause_order(model, params):
    """The pause queue is insertion-ordered and resumes pop the FRONT:
    oldest paused re-enters first (ties impossible — pauses are
    sequential), matching the documented policy rather than rid order."""
    eng = Engine.local(model, _cfg(), params=params,
                       budget=KVBudget(tier1_pages=6, tier2_bytes=1e9,
                                       page_size=8))
    for r in _trace(n=5, prompt=12, new=10):
        eng.submit(r)
    prev = []
    saw_pause = False
    for _ in range(10_000):
        if eng.idle:
            break
        eng.step()
        cur = [s.rid for s in eng._paused]
        if cur:
            saw_pause = True
        # whatever left the pause queue this step left from the front
        # (drops can only happen with tier2 headroom exhausted — not here)
        survivors = [r for r in prev if r in cur]
        gone = [r for r in prev if r not in cur]
        assert prev[:len(gone)] == gone and prev[len(gone):] == survivors
        prev = cur
    assert saw_pause, "pressure never paused anything"


def test_engine_prefill_compile_count_bounded(model, params):
    """Bucketed prefill: many distinct prompt lengths, at most one
    compiled program per bucket (the CI compile-guard)."""
    eng = Engine.local(model, _cfg(max_slots=2), params=params)
    if not hasattr(eng._prefill_jit, "_cache_size"):
        pytest.skip("no jit cache introspection: the guard would only see "
                    "its own bucket bookkeeping and pass vacuously")
    lengths = [3, 5, 7, 9, 11, 14, 17, 21, 26, 31, 37, 45]
    rng = np.random.RandomState(0)
    handles = [eng.submit(Request(
        tuple(rng.randint(1, VOCAB, size=n).tolist()), 2))
        for n in lengths]
    eng.run_until_idle()
    assert all(h.status is RequestStatus.DONE for h in handles)
    n_buckets = len(eng.stats()["prefill_buckets"])
    assert eng.stats()["prefill_compiles"] <= n_buckets, (
        f"{eng.stats()['prefill_compiles']} prefill programs for "
        f"{len(set(lengths))} prompt lengths; bucket bound is {n_buckets}")


def test_engine_decode_compile_count_bounded(model, params):
    """Live-row bucketed decode: occupancy swings between 1 and
    max_slots rows across a trace, but the decode program count stays
    <= the pow2 row-bucket list (and tokens match the always-full-array
    reference engine bit-exactly)."""
    eng = Engine.local(model, _cfg(max_slots=6), params=params)
    if not hasattr(eng._decode_jit, "_cache_size"):
        pytest.skip("no jit cache introspection: the guard would only see "
                    "its own bucket bookkeeping and pass vacuously")
    # staggered arrivals + assorted budgets drive occupancy through
    # 1..6 live rows (every bucket), not just the burst peak
    trace = [Request(tuple(np.random.RandomState(i).randint(
                 1, VOCAB, size=8).tolist()),
                 max_new_tokens=3 + 5 * (i % 4),
                 arrival_time=2e-5 * i) for i in range(9)]
    handles = run_trace(eng, trace)
    assert all(h.status is RequestStatus.DONE for h in handles)
    s = eng.stats()
    assert s["decode_row_buckets"] == [1, 2, 4, 6]
    assert s["decode_compiles"] <= len(s["decode_row_buckets"]), (
        f"{s['decode_compiles']} decode programs; bucket bound is "
        f"{s['decode_row_buckets']}")
    assert len(eng._row_buckets_used) >= 3, "occupancy never varied"
    # bucketed decode must not change emitted tokens: per-row outputs
    # are independent of the batch they ride in
    ref = Engine.local(model, _cfg(max_slots=6), params=params)
    ref._row_buckets = [ref.cfg.max_slots]       # force full-array decode
    ref_handles = run_trace(ref, trace)
    assert [h.tokens for h in handles] == [h.tokens for h in ref_handles]
