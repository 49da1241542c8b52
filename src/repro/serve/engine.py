"""Request-level continuous-batching engine over a *physically paged*,
budgeted KV pool.

The serving counterpart of ``runtime.train``: one ``Engine`` owns a
shared device-side KV **page pool** (``KVBudget.tier1_pages`` physical
pages of ``page_size`` tokens, plus one trash page that absorbs idle
rows' writes), a slot array of decode rows, and a per-row page table
(``int32[max_slots, pages_per_slot]``) mapping each sequence's logical
pages onto arbitrary physical pages.  Decode is ONE batched call into
the model's paged path: the Pallas paged-attention kernel gathers K/V
through the page table, so a sequence needs neither contiguous pages
nor a reserved slab — the PR-2 contiguous-slot residency ceiling is
gone.

Scheduling per ``step()``:

* pressure relief: if the running rows' next-token page demand exceeds
  the pool, the newest-admitted rows are *paused* (descheduled — their
  pages stay hot until somebody needs them: lazy, page-granular
  eviction).  Growth allocations then evict the **coldest pages**
  (least-recently-scheduled paused sequence first; within it the
  oldest-written, lowest-logical pages first) to the tier-2 cold store
  over the capacity fabric — or, with no tier-2 byte headroom, drop the
  victim's KV entirely and requeue it for re-prefill (the recompute
  storm the paper's Fig. 7 tier-2 relief avoids);
* swap-in: paused sequences re-enter in pause order (oldest first —
  insertion-ordered, no re-sorting); only their *cold* pages ride the
  fabric back, into whatever physical pages are free — resuming a
  sequence whose pages were never evicted costs nothing;
* admission: FIFO prefill, padded to a power-of-two page-aligned
  *bucket* (one XLA program per bucket, not per prompt length) with the
  next-token logits read at the last real position;
* decode: every running row advances one token in a single jitted call.

Every event clock is attributed to the event's **modeled completion
time** (``engine.clock`` at step start + modeled seconds elapsed within
the step), so TTFT/latency are consistent across prefill, decode, swap
and OOM paths.

Each row is an independent batch entry of one fused program and the
page table fully determines what it attends to, so output is identical
for any arrival interleaving, any physical page layout, and for
lease-backed vs local construction (the engine's determinism contract,
enforced by tests).

Time is *modeled*: a ``ServeCostModel`` prices prefill/decode events
from the paper's fabric constants, and page-swap traffic is charged
through a ``repro.fabric.Transport`` (pass ``transport=``/``route=``
to put several engines on one shared routed fabric, where concurrent
transfers fair-share each link's bandwidth — the contention the
paper's shared CXL hierarchy implies).  Without an explicit transport
the engine owns a private degenerate 1-link one derived from the cost
model, reproducing the legacy ``swap_s`` scalars bit-exactly.

Multi-tenant: passing ``arbiter=``/``tenant=`` joins a shared
``repro.serve.PoolArbiter`` page pool instead of owning a private one —
``self.kv`` becomes the tenant's fair-share view (same interface), the
pool arrays live on the arbiter, and ``allowance()`` (the live max-min
share) replaces the fixed quota in the pressure/resume decisions.  A
lone tenant's allowance is the whole pool, so single-tenant behavior is
bit-identical to the private path.
"""

from __future__ import annotations

import contextlib
import dataclasses
from collections import deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.tiering import KVBudget, KVBudgetExceeded, PagedKV
from repro.models.api import Model
from repro.models.config import ShapeConfig
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import CAT_ENGINE, CAT_KV, CAT_REQUEST, resolve
from repro.serve.api import (EngineConfig, Request, RequestHandle,
                             RequestStatus, ServeCostModel)


def _dtype(d):
    return jnp.dtype(d) if not isinstance(d, str) else {
        "float32": jnp.float32, "bfloat16": jnp.bfloat16,
        "float16": jnp.float16}[d]


def _pow2_buckets(start: int, cap: int) -> List[int]:
    """Doubling sizes from ``start`` up to (and always including) ``cap``."""
    out: List[int] = []
    b = start
    while b < cap:
        out.append(b)
        b *= 2
    out.append(cap)
    return out


def evict_pages(pool, kv, st, logicals, engine, t) -> float:
    """Spill one batch of ``st``'s hot logical pages to ``kv``'s tier-2
    cold store: gather the physical pages from the device pool (one
    bulk copy), evict each, and record one swap episode on the handle.
    The bulk transfer is registered with ``engine``'s transport at
    modeled time ``t`` (so concurrent tenants on a shared fabric
    contend); returns the modeled swap seconds — the caller decides
    whose clock absorbs them (the engine's own step dt, or the victim
    tenant's revocation charge).  Shared by ``Engine._evict_or_drop``
    and ``PoolArbiter.reclaim`` so the two eviction paths cannot
    diverge."""
    table = kv.page_table(st.rid)
    idx = jnp.asarray(np.asarray([table[lp] for lp in logicals], np.int32))
    gathered = jax.tree.map(lambda l: np.asarray(l[:, idx]), pool)
    for i, lp in enumerate(logicals):
        kv.evict(st.rid, lp, jax.tree.map(lambda g, i=i: g[:, i], gathered))
    st.handle.swaps += 1        # one spill episode: len(logicals) pages,
                                # one bulk transfer over the capacity fabric
    cost = engine.charge_tier2(len(logicals) * kv.page_bytes, t)
    if engine.tracer.enabled:
        engine.tracer.span(engine._track, "spill", t, cost, cat=CAT_KV,
                           rid=st.rid, pages=len(logicals),
                           bytes=len(logicals) * kv.page_bytes)
    return cost


def slice_page(cache, i: int, page_size: int):
    """Payload of logical page ``i`` of a dense ``(layers, 1, seq, ...)``
    prefill cache: a tree of ``(layers, page_size, ...)`` leaves — the
    same per-page shape ``PagedKV.evict``/``fetch`` payloads use, so a
    page sliced here can be spilled to tier-2, streamed over the fabric
    (``repro.disagg``) or scattered with ``Engine._write_page``
    interchangeably."""
    def cut(cache_leaf):
        lay = cache_leaf.shape[0]
        tail = tuple(cache_leaf.shape[3:])
        return cache_leaf[:, 0].reshape((lay, -1, page_size) + tail)[:, i]
    return jax.tree.map(cut, cache)


@dataclasses.dataclass(eq=False)        # identity semantics: these live in
class _SlotState:                        # queues/sets and are never "equal"
    """Host-side bookkeeping for one in-flight request."""

    handle: RequestHandle
    index: int = 0                 # next KV write position (= current length)
    cur_tok: int = 0               # last emitted token (decode input)
    slot: Optional[int] = None     # row in the slot array, None when off
    admit_seq: int = -1            # admission order (pressure pauses
                                   # newest-admitted rows first)
    last_sched: int = -1           # step() count of the last decode — the
                                   # page-coldness signal for eviction
    ready_at: float = 0.0          # modeled completion time of the LAST
                                   # in-flight KV page (disaggregated
                                   # handoff); decode never schedules the
                                   # row before it.  0.0 == colocated.
    on_first_decode: Optional[Any] = None   # one-shot callback fired with
                                   # the modeled time of the row's first
                                   # decode (the disagg handoff_use event)

    @property
    def rid(self) -> int:
        return self.handle.rid

    @property
    def request(self) -> Request:
        return self.handle.request

    def effective_prompt(self) -> Tuple[int, ...]:
        """Prompt for (re-)prefill: original prompt plus everything
        already generated (the recompute-preemption continuation)."""
        return self.request.prompt_tokens + tuple(self.handle.tokens)

    @property
    def target_len(self) -> int:
        return self.request.prompt_len + self.request.max_new_tokens


@dataclasses.dataclass(eq=False)
class _Handoff:
    """One externally-prefilled sequence waiting for decode-side
    admission (``Engine.submit_prefilled``): the per-page payloads in
    flight over the fabric plus the modeled arrival gates."""

    state: _SlotState
    pages: List[Any]               # slice_page payloads, logical order
    page_ready: List[float]        # modeled transfer completion per page
    admit_at: float                # gate: first min_ready pages landed
    ready_at: float                # gate: ALL pages landed (decode start)


class Engine:
    """Continuous-batching serving engine.  Build with ``Engine.local``
    (explicit config) or ``Engine.from_lease`` (a ``repro.pool`` lease
    supplies the mesh, sharding rules, and the tier-2 KV byte budget)."""

    def __init__(self, model: Model, params, cfg: EngineConfig, *,
                 budget: Optional[KVBudget] = None,
                 cost_model: Optional[ServeCostModel] = None,
                 mesh=None, rules=None,
                 arbiter=None, tenant: Optional[str] = None,
                 transport=None, route=None, tracer=None):
        if model.cfg.family == "encdec":
            raise NotImplementedError(
                "Engine drives decoder-style models; encdec serving still "
                "goes through runtime.serve step factories")
        if not model.supports_paged_kv:
            raise NotImplementedError(
                f"Engine serves through the paged decode kernel, which "
                f"{model.cfg.family!r} does not implement yet (ssm keeps "
                f"an O(1) recurrent state with nothing to page; hybrid "
                f"interleaves recurrent state with its KV layers) — use "
                f"the runtime.serve step factories for this family")
        self.model = model
        self.params = params
        self.cfg = cfg
        self.mesh, self.rules = mesh, rules
        # tier-2 transfer routing: a shared repro.fabric Transport (+
        # this engine's route on it) makes concurrent tenants contend
        # on the actual links; without one, the engine owns a private
        # degenerate 1-link transport derived from its cost model —
        # pricing identical (bit-exact) to the legacy swap_s scalars
        if (transport is None) != (route is None):
            raise ValueError("pass transport= and route= together")
        self._transport = transport
        self._transport_owned = transport is None
        self.route = route
        # flight recorder: defaults to the shared transport's tracer
        # (one recorder per fabric domain), else the zero-cost null
        self.tracer = resolve(tracer if tracer is not None
                              else getattr(transport, "tracer", None))
        self.cost = cost_model or ServeCostModel.from_fabric(
            2.0 * model.cfg.param_count())

        dt = _dtype(cfg.cache_dtype)
        self._cache_dtype = dt
        slot_shapes = jax.eval_shape(
            lambda: model.init_cache(1, cfg.max_seq, dtype=dt))
        for leaf in jax.tree.leaves(slot_shapes):
            if len(leaf.shape) < 3 or leaf.shape[1] != 1 \
                    or leaf.shape[2] != cfg.max_seq:
                raise NotImplementedError(
                    f"paged serving expects (layers, batch=1, seq, ...) "
                    f"KV cache leaves, got {leaf.shape}")
        slot_bytes = sum(l.size * l.dtype.itemsize
                         for l in jax.tree.leaves(slot_shapes))
        page_bytes = slot_bytes * cfg.page_size / max(1, cfg.max_seq)
        self.slot_bytes = float(slot_bytes)

        full = budget or KVBudget(page_size=cfg.page_size)
        self.arbiter = arbiter
        self.tenant = tenant
        self._pool_store = None
        if arbiter is not None:
            # multi-tenant: the arbiter owns the physical pool; this
            # engine's tier-1 "quota" is the whole pool, but its live
            # allowance is a revocable max-min fair share.
            if self.tenant is None:
                self.tenant = f"tenant-{len(arbiter.tenants)}"
            self.budget = KVBudget(tier1_pages=arbiter.num_pages,
                                   tier2_bytes=full.tier2_bytes,
                                   page_size=cfg.page_size)
            self.kv = arbiter.register(self.tenant, self,
                                       slot_shapes=slot_shapes,
                                       page_bytes=page_bytes,
                                       tier2_bytes=full.tier2_bytes)
        else:
            tier1 = (full.tier1_pages if full.tier1_pages is not None
                     else cfg.max_slots * cfg.pages_per_slot)
            self.budget = KVBudget(tier1_pages=tier1,
                                   tier2_bytes=full.tier2_bytes,
                                   page_size=cfg.page_size)
            self.kv = PagedKV(self.budget, page_bytes)

        # shared physical page pool: leaf (layers, num_pages + 1, page,
        # ...).  The extra page (id == num_pages) is the TRASH page: idle
        # rows' page tables point at it, so their decode writes land
        # somewhere harmless and their gathers stay in bounds.  Under an
        # arbiter the arrays live on the arbiter (ONE pool, N tenants)
        # and ``self._pool`` is a view through the property below.
        self._trash = self.kv.num_pages
        if arbiter is None:
            self._pool = jax.tree.map(
                lambda l: jnp.zeros(
                    (l.shape[0], self.kv.num_pages + 1, cfg.page_size)
                    + l.shape[3:], l.dtype),
                slot_shapes)
        self._table = np.full((cfg.max_slots, cfg.pages_per_slot),
                              self._trash, np.int32)
        self._lengths = np.zeros(cfg.max_slots, np.int32)
        self._slot_tok = np.zeros(cfg.max_slots, np.int32)
        self._slots: List[Optional[_SlotState]] = [None] * cfg.max_slots

        self._queue: deque = deque()     # _SlotState, FIFO (+recompute front)
        self._paused: deque = deque()    # insertion-ordered: pause order IS
                                         # the resume order (oldest first)
        self._handoffs: deque = deque()  # _Handoff, FIFO: externally
                                         # prefilled sequences whose KV is
                                         # still riding the fabric
        self.handles: Dict[int, RequestHandle] = {}
        self._next_rid = 0
        self._admit_seq = 0

        self.clock = 0.0
        self.steps = 0
        self.busy_s = 0.0          # sum of nonzero step() durations: the
                                   # throughput denominator that idle
                                   # inter-arrival gaps cannot dilute
        self._decoded_tokens = 0

        # prefill buckets: page-aligned powers of two capped at the slot
        # capacity — the jit program count is bounded by len(buckets),
        # not by the number of distinct prompt lengths in the trace
        self._buckets = _pow2_buckets(cfg.page_size,
                                      cfg.pages_per_slot * cfg.page_size)
        self._buckets_used: set = set()

        # decode row buckets: live rows are gathered into the smallest
        # power-of-two row count (capped at max_slots) before the paged
        # decode, so a near-empty engine decodes a 1- or 2-row batch
        # instead of all max_slots rows — compiled-program count stays
        # bounded by len(row buckets), not by occupancy histories
        self._row_buckets = _pow2_buckets(1, cfg.max_slots)
        self._row_buckets_used: set = set()

        self._prefill_jit = jax.jit(
            lambda p, batch, cache, last: model.prefill_at(
                p, batch, cache, last))
        self._prefill_fn = self._scoped(self._prefill_jit)

        def paged_decode(params, toks, pool, table, lengths):
            logits, new_pool = model.decode_paged(params, toks, pool,
                                                  table, lengths)
            nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
            return nxt[:, None], new_pool

        self._decode_jit = jax.jit(paged_decode)
        self._decode_fn = self._scoped(self._decode_jit)

    @property
    def _track(self) -> str:
        """This engine's trace track (one timeline row per tenant)."""
        return f"engine:{self.tenant}" if self.tenant else "engine"

    # the physical page pool: private arrays for a solo engine, the
    # arbiter's shared arrays when multi-tenant (every tenant's prefill
    # scatter / decode write / swap round-trip hits the SAME pool)
    @property
    def _pool(self):
        return (self.arbiter.pool if self.arbiter is not None
                else self._pool_store)

    @_pool.setter
    def _pool(self, value):
        if self.arbiter is not None:
            self.arbiter.pool = value
        else:
            self._pool_store = value

    # ---- transfer pricing --------------------------------------------------
    @property
    def cost(self) -> ServeCostModel:
        return self._cost

    @cost.setter
    def cost(self, cm: ServeCostModel) -> None:
        self._cost = cm
        if self._transport_owned:
            # the private degenerate transport prices from the cost
            # model's tier-2 scalars: rebuild lazily so the benchmark
            # idiom ``eng.cost = replace(cm, tier2_bw=...)`` keeps swap
            # pricing in sync
            self._transport = None
            self.route = None

    @property
    def transport(self):
        """The ``repro.fabric.Transport`` tier-2 traffic is charged
        through.  Shared across engines it makes tenants contend on
        the fabric's links; the lazily-built private fallback is the
        cost model's degenerate 1-link facade."""
        if self._transport is None:
            self._transport = self._cost.transport()
            self.route = self._transport.topology.route("src", "dst")
        return self._transport

    def charge_tier2(self, nbytes: float, t: float) -> float:
        """Modeled seconds for one bulk tier-2 transfer beginning at
        modeled time ``t``, fair-sharing links with every transfer
        already in flight on this engine's transport.  Flows are
        labeled ``serve:<tenant>`` so link occupancy can be attributed
        to the tenant whose paging stalled a request."""
        tx = self.transport            # materializes self.route too
        return tx.transfer_s(self.route, nbytes, t,
                             label=f"serve:{self.tenant or 'engine'}")

    # ---- construction ----------------------------------------------------
    @classmethod
    def local(cls, model: Model, cfg: EngineConfig = EngineConfig(), *,
              params=None, rng=None,
              budget: Optional[KVBudget] = None,
              cost_model: Optional[ServeCostModel] = None,
              arbiter=None, tenant: Optional[str] = None,
              transport=None, route=None, tracer=None) -> "Engine":
        """Engine over local devices, no orchestrator: the KV budget is
        whatever the caller passes (default: unbudgeted tier-1, no
        tier-2).  Pass ``arbiter``/``tenant`` to join a shared
        multi-tenant page pool, and ``transport``/``route`` to charge
        tier-2 traffic on a shared routed fabric instead of a private
        degenerate link."""
        if params is None:
            params = model.init(rng if rng is not None
                                else jax.random.PRNGKey(0))
        return cls(model, params, cfg, budget=budget, cost_model=cost_model,
                   arbiter=arbiter, tenant=tenant,
                   transport=transport, route=route, tracer=tracer)

    @classmethod
    def from_lease(cls, model: Model, lease,
                   cfg: EngineConfig = EngineConfig(), *,
                   params=None, rng=None,
                   budget: Optional[KVBudget] = None,
                   cost_model: Optional[ServeCostModel] = None,
                   arbiter=None, tenant: Optional[str] = None,
                   transport=None, route=None, tracer=None) -> "Engine":
        """Bind a ``repro.pool.Lease``: the lease's mesh shapes the
        sharding rules and its tier-2 KV grant becomes the engine's
        ``KVBudget.tier2_bytes`` — serving capacity is composed by the
        orchestrator, not hard-coded per deployment."""
        from repro.sharding.profiles import make_rules

        mesh, policy = lease.materialize()
        shape = ShapeConfig("engine", "decode", cfg.max_seq, cfg.max_slots)
        rules = make_rules(model.cfg, shape, mesh, fsdp=False)
        if budget is None:
            if getattr(lease, "tenants", ()):
                # multi-tenant lease: this tenant's static slice of the
                # shared cold-store grant (tier-1 pages stay dynamic,
                # arbitrated max-min by the arbiter).  kv_share raises
                # on an unknown tenant — falling back to the FULL grant
                # here would let every mis-named tenant spill N x the
                # pool's cold bytes.
                budget = lease.kv_share(tenant, page_size=cfg.page_size)
            else:
                base = policy.kv_budget or KVBudget(page_size=cfg.page_size)
                budget = KVBudget(tier1_pages=base.tier1_pages,
                                  tier2_bytes=base.tier2_bytes,
                                  page_size=cfg.page_size)
        if params is None:
            params = model.init(rng if rng is not None
                                else jax.random.PRNGKey(0))
        return cls(model, params, cfg, budget=budget, cost_model=cost_model,
                   mesh=mesh, rules=rules, arbiter=arbiter, tenant=tenant,
                   transport=transport, route=route, tracer=tracer)

    def _scoped(self, jitted):
        # the closure captures the mesh and rules, never ``self``: stored
        # on the engine, a closure over ``self`` would be a reference
        # cycle that keeps its params and page pool on the device until
        # a garbage collection happens to run
        mesh, rules = self.mesh, self.rules

        def call(*args):
            with contextlib.ExitStack() as stack:
                if mesh is not None:
                    from repro.sharding.partition import use_rules
                    stack.enter_context(use_rules(rules, mesh))
                    stack.enter_context(jax.set_mesh(mesh))
                return jitted(*args)
        return call

    def lower_decode(self):
        """The paged-decode step as the device runs it for a full slot
        array (``jax.stages.Lowered``): ``.compile().as_text()`` shows
        whether the Pallas kernel is there (``tpu_custom_call``) or was
        interpreted."""
        rows = self.cfg.max_slots
        return self._scoped(self._decode_jit.lower)(
            self.params, jnp.zeros((rows, 1), jnp.int32), self._pool,
            jnp.asarray(self._table), jnp.asarray(self._lengths))

    # ---- client API ------------------------------------------------------
    def submit(self, request: Request) -> RequestHandle:
        """Enqueue a request (deterministic FIFO admission order).

        Token ids are validated against the model vocab here: JAX's
        out-of-bounds gather semantics would otherwise *clamp* a bad id
        to the last embedding row and serve a silently-wrong completion.
        """
        if request.prompt_len + request.max_new_tokens > self.cfg.max_seq:
            raise ValueError(
                f"prompt {request.prompt_len} + max_new "
                f"{request.max_new_tokens} exceeds max_seq {self.cfg.max_seq}")
        vocab = self.model.cfg.vocab
        bad = [t for t in request.prompt_tokens if not 0 <= t < vocab]
        if bad:
            raise ValueError(
                f"prompt token id {bad[0]} outside the model vocab "
                f"[0, {vocab}) — JAX would clamp it to a wrong embedding "
                f"instead of failing")
        rid = self._next_rid
        self._next_rid += 1
        handle = RequestHandle(rid=rid, request=request,
                               submit_clock=max(self.clock,
                                                request.arrival_time))
        self.handles[rid] = handle
        self._queue.append(_SlotState(handle))
        if self.tracer.enabled:
            self.tracer.instant(self._track, "submit", handle.submit_clock,
                                cat=CAT_REQUEST, rid=rid,
                                prompt_len=request.prompt_len,
                                max_new=request.max_new_tokens)
        return handle

    # ---- disaggregated prefill/decode seams (repro.disagg) -----------------
    def prefill_export(self, prompt: Sequence[int]) -> Tuple[int, List[Any],
                                                             float]:
        """Prefill-only mode: run ONE bucketed prefill exactly as
        ``_admit`` would (same jit program, same bucket, same modeled
        cost, same last-position argmax) but export the KV page-by-page
        (``slice_page`` payloads) instead of scattering it into this
        engine's pool — the prefill half of the disaggregated handoff.
        Returns ``(first_token, pages, modeled_seconds)``; the caller
        owns clock accounting, transfer pricing, and decode-side
        admission.  Because the compute path is shared with the
        colocated admit, the first token and every page payload are
        bit-identical to what a colocated prefill would have produced."""
        plen = len(prompt)
        bucket = self._bucket_len(plen)
        self._buckets_used.add(bucket)
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :plen] = prompt
        slot_cache = self.model.init_cache(1, bucket,
                                           dtype=self._cache_dtype)
        logits, cache = self._prefill_fn(self.params,
                                         {"tokens": jnp.asarray(tokens)},
                                         slot_cache, jnp.int32(plen - 1))
        cost = self.cost.prefill_s(bucket)
        tok = int(np.argmax(np.asarray(logits)[0, -1]))
        ps = self.cfg.page_size
        pages = [slice_page(cache, i, ps) for i in range(-(-plen // ps))]
        return tok, pages, cost

    def submit_prefilled(self, request: Request, *, first_tok: int,
                         prefill_done: float, pages: List[Any],
                         page_ready: Sequence[float],
                         min_ready_pages: Optional[int] = None,
                         kv_transit_s: float = 0.0,
                         submit_clock: Optional[float] = None,
                         on_first_decode=None) -> RequestHandle:
        """Decode-only mode: hand off a request whose prefill ran on
        another engine (``prefill_export``) and whose KV pages are in
        flight on the fabric.  ``page_ready[i]`` is the modeled
        completion time of page ``i``'s transfer; admission waits for
        the first ``min_ready_pages`` pages to land (default: all —
        partial-arrival admission reserves the slot early), and the row
        is never decoded before max(page_ready): transferred-before-use
        is the invariant the ``disagg-handoff`` sanitizer rule checks.
        The first token was already produced by the prefill tier at
        modeled time ``prefill_done``."""
        if request.prompt_len + request.max_new_tokens > self.cfg.max_seq:
            raise ValueError(
                f"prompt {request.prompt_len} + max_new "
                f"{request.max_new_tokens} exceeds max_seq {self.cfg.max_seq}")
        if len(pages) != len(page_ready):
            raise ValueError(f"{len(pages)} pages but {len(page_ready)} "
                             f"ready times")
        if not pages:
            raise ValueError("handoff with no KV pages")
        rid = self._next_rid
        self._next_rid += 1
        handle = RequestHandle(rid=rid, request=request,
                               submit_clock=(submit_clock
                                             if submit_clock is not None
                                             else request.arrival_time))
        handle.kv_transit_s = kv_transit_s
        self.handles[rid] = handle
        st = _SlotState(handle)
        st.index = request.prompt_len
        st.cur_tok = first_tok
        st.on_first_decode = on_first_decode
        # the prefill tier produced the first token at prefill_done;
        # trace events on THIS track must stay monotone, so the finish
        # path (max_new == 1) clamps forward to the local clock
        handle.first_token_clock = prefill_done
        self._emit(st, first_tok, max(self.clock, prefill_done))
        if handle.done:
            return handle
        ready = [float(t) for t in page_ready]
        n_gate = (len(ready) if min_ready_pages is None
                  else max(1, min(min_ready_pages, len(ready))))
        self._handoffs.append(_Handoff(
            state=st, pages=list(pages), page_ready=ready,
            admit_at=max(ready[:n_gate]), ready_at=max(ready)))
        return handle

    @property
    def idle(self) -> bool:
        return (not self._queue and not self._paused and not self._handoffs
                and all(s is None for s in self._slots))

    def advance_clock(self, t: float) -> None:
        """Idle-advance modeled time (trace drivers jump to next arrival)."""
        self.clock = max(self.clock, t)

    def run_until_idle(self, max_steps: int = 100_000) -> None:
        for _ in range(max_steps):
            if self.idle:
                return
            self.step()
        raise RuntimeError(f"engine not idle after {max_steps} steps")

    # ---- the engine loop -------------------------------------------------
    def step(self) -> float:
        """One scheduling round: relieve page pressure, swap in, admit,
        decode every running row one token.  Returns modeled seconds.
        Sub-phases receive the seconds already elapsed *within* this
        step so every event clock lands on the event's modeled time."""
        dt = 0.0
        if self.arbiter is not None:
            # swap seconds another tenant's revocation charged to us
            # since our last step: OUR pages rode the fabric, so OUR
            # subsequent event clocks absorb the time
            dt += self.arbiter.take_charge(self.tenant)
        dt += self._relieve_pressure(dt)
        dt += self._swap_in(dt)
        dt += self._admit_handoffs(dt)
        dt += self._admit(dt)
        dt += self._decode_once(dt)
        if (dt == 0.0 and self._queue and not self._paused  # repro: allow(no-float-equality) 0.0 is an exact no-work sentinel (no phase ran), never an accumulated time
                and all(s is None for s in self._slots)):
            # nothing runnable and the FIFO head has not arrived yet:
            # idle-advance to its arrival (the same jump run_trace makes)
            # so directly-submitted future-dated requests make progress
            nxt = self._queue[0].request.arrival_time
            if nxt > self.clock:
                self.advance_clock(nxt)
        if dt == 0.0:  # repro: allow(no-float-equality) same exact no-work sentinel as above
            # every runnable row (or the pending handoff) is still
            # waiting on KV in flight over the fabric: idle-advance to
            # the earliest modeled page arrival so progress is made
            gates = [s.ready_at for s in self._slots
                     if s is not None and s.ready_at > self.clock]
            if self._handoffs:
                gates.append(self._handoffs[0].admit_at)
            if gates:
                nxt = min(gates)
                if nxt > self.clock:
                    self.advance_clock(nxt)
        self.clock += dt
        if dt > 0.0:
            self.busy_s += dt
        self.steps += 1
        if self.tracer.enabled:
            # counter lanes (Perfetto renders these as area charts):
            # physical free stack, pause-queue depth, live allowance.
            # Values are identical between a private pool and a lone
            # tenant under the arbiter (the fig9 transparency contract),
            # so traced event streams stay bit-identical across both.
            if self.steps == 1:
                # pool geometry, once: the conservation baseline the
                # repro.analysis sanitizer checks page counters against
                self.tracer.instant(self._track, "kv_pool", self.clock,
                                    cat=CAT_KV, pages=self.kv.num_pages)
            self.tracer.counter(self._track, "free_pages", self.clock,
                                float(self.kv.free_count), cat=CAT_KV)
            self.tracer.counter(self._track, "paused", self.clock,
                                float(len(self._paused)))
            self.tracer.counter(self._track, "allowance", self.clock,
                                float(self.kv.allowance()), cat=CAT_KV)
            # hot_pages LAST in the step-end block: the sanitizer treats
            # it as the tenant's authoritative residency sample and
            # checks free + sum(hot) == pool against the same block's
            # free_pages value
            self.tracer.counter(self._track, "hot_pages", self.clock,
                                float(self.kv.hot_used()), cat=CAT_KV)
        return dt

    # ---- internals -------------------------------------------------------
    def _running(self) -> List[_SlotState]:
        return sorted((s for s in self._slots if s is not None),
                      key=lambda s: s.admit_seq)

    def _free_slot(self) -> Optional[int]:
        for i, s in enumerate(self._slots):
            if s is None:
                return i
        return None

    def _pages_next(self, st: _SlotState) -> int:
        # pages needed to write the next token at position st.index; under
        # static reservation the full lifetime is held from admission on
        if self.cfg.reserve_lifetime:
            return self.budget.pages_for(st.target_len)
        return self.budget.pages_for(st.index + 1)

    def _page_demand(self) -> int:
        """This engine's current want for hot pages (running + paused
        next-token demand, plus the queue head's admission need) — the
        demand signal the arbiter's max-min water-filling splits the
        shared pool over."""
        d = sum(self._pages_next(s) for s in self._slots if s is not None)
        d += sum(self._pages_next(s) for s in self._paused)
        if self._queue:
            st = self._queue[0]
            if self.cfg.reserve_lifetime:
                d += self.budget.pages_for(st.target_len)
            else:
                d += self.budget.pages_for(len(st.effective_prompt()) + 1)
        return d

    def _bucket_len(self, plen: int) -> int:
        for b in self._buckets:
            if b >= plen:
                return b
        raise ValueError(f"prompt of {plen} exceeds slot capacity "
                         f"{self._buckets[-1]}")

    def prefill_compiles(self) -> int:
        """Compiled prefill program count (the CI guard asserts this
        stays <= len(buckets) regardless of the trace's prompt lengths).
        Without jit cache introspection this is only a lower bound (the
        buckets actually requested) — the guard test skips rather than
        pass vacuously in that case."""
        if hasattr(self._prefill_jit, "_cache_size"):
            return self._prefill_jit._cache_size()
        return len(self._buckets_used)  # pragma: no cover

    def decode_compiles(self) -> int:
        """Compiled paged-decode program count — bounded by the pow2
        row-bucket list, not by the trace's occupancy history (same
        caveat as ``prefill_compiles`` without cache introspection)."""
        if hasattr(self._decode_jit, "_cache_size"):
            return self._decode_jit._cache_size()
        return len(self._row_buckets_used)  # pragma: no cover

    # ---- pressure relief / paging ----------------------------------------
    def _relieve_pressure(self, elapsed: float) -> float:
        """Deschedule newest-admitted rows until the remaining running
        rows' next-token demand fits the pool, then allocate this step's
        growth pages — evicting the coldest paused pages as needed."""
        dt = 0.0
        running = self._running()
        allow = self.kv.allowance()     # == num_pages for a private pool;
        while running:                  # the live fair share under an arbiter
            demand = sum(self._pages_next(s) for s in running)
            if demand <= allow and self._growth_deliverable(running):
                break
            self._pause(running.pop(),          # newest admission
                        self.clock + elapsed + dt)
        for st in running:
            want = self._pages_next(st)
            have = self.kv.pages_of(st.rid)
            if want > have:
                dt += self._make_room(want - have, t=elapsed + dt)
                new_phys = self.kv.grow(st.rid, want)
                for lp, phys in zip(range(have, want), new_phys):
                    self._table[st.slot, lp] = phys
        return dt

    def _growth_deliverable(self, running: List[_SlotState]) -> bool:
        """Can this step's growth pages actually be freed?  Sources:
        the free stack + revocation headroom (``hot_free``) plus our own
        paused sequences' hot pages (always evictable or droppable).
        For a private pool ``demand <= num_pages`` already implies this
        (growth = demand - held ≤ free + paused-hot), so the check only
        bites under an arbiter — another tenant may sit over its share
        with all rows *running* (nothing revocable until ITS next step
        pauses them), and growing into that gap must wait."""
        growth = sum(max(0, self._pages_next(s) - self.kv.pages_of(s.rid))
                     for s in running if self.kv.holds(s.rid))
        own_evictable = sum(self.kv.hot_count(s.rid) for s in self._paused
                            if self.kv.holds(s.rid))
        return growth <= self.kv.hot_free + own_evictable

    def _pause(self, st: _SlotState, t: Optional[float] = None) -> None:
        """Deschedule a running row at modeled time ``t`` (defaults to
        the clock).  Costless: its pages STAY hot until an allocation
        actually needs them (lazy eviction) — pausing and resuming
        without intervening pressure moves zero bytes."""
        if self.tracer.enabled:
            self.tracer.instant(self._track, "pause",
                                self.clock if t is None else t,
                                cat=CAT_KV, rid=st.rid,
                                hot_pages=self.kv.hot_count(st.rid)
                                if self.kv.holds(st.rid) else 0)
        slot = st.slot
        self._table[slot, :] = self._trash
        self._lengths[slot] = 0
        self._slots[slot] = None
        st.slot = None
        st.handle.status = RequestStatus.SWAPPED
        st.handle.preempts += 1     # swaps counts actual tier-2 traffic,
                                    # charged at eviction time
        self._paused.append(st)     # insertion order == pause order; the
                                    # resume policy pops from the front

    def _make_room(self, n_pages: int, protect: Sequence[_SlotState] = (),
                   t: float = 0.0) -> float:
        """Free physical pages by evicting the coldest paused pages to
        tier-2 (or dropping victims for recompute when the byte budget
        is exhausted).  Coldness: least-recently-scheduled sequence
        first (admission order breaking ties); within a victim, the
        oldest-written (lowest-logical) pages go first.  ``t`` is the
        seconds already elapsed within this step — spill transfers
        begin at ``clock + t`` on the transport."""
        dt = 0.0
        # snapshot the revocation headroom once: under an arbiter,
        # hot_free re-runs the max-min water-filling over every tenant,
        # and this loop would otherwise recompute it per evicted page.
        # Own evictions only grow the free stack, so the cached slack
        # stays a valid (conservative) lower bound.  Private pool: 0.
        slack = self.kv.hot_free - self.kv.free_count
        while self.kv.free_count + slack < n_pages:
            victims = [s for s in self._paused
                       if s not in protect and self.kv.hot_count(s.rid) > 0]
            if not victims:
                break               # nothing evictable; caller re-checks
            victim = min(victims, key=lambda s: (s.last_sched, s.admit_seq))
            dt += self._evict_or_drop(
                victim, n_pages - slack - self.kv.free_count, t + dt)
        return dt

    def _evict_or_drop(self, st: _SlotState, need: int, t: float) -> float:
        hot = self.kv.hot_logicals(st.rid)
        k = min(need, len(hot), self.kv.tier2_free_pages())
        if k <= 0:
            # no tier-2 headroom (or no tier-2 budget at all): page-
            # granular spill is impossible, and a partial prefix is
            # useless for recompute — drop the whole sequence's KV and
            # requeue it for re-prefill
            self._drop_for_recompute(st, self.clock + t)
            return 0.0
        return evict_pages(self._pool, self.kv, st, hot[:k], self,
                           self.clock + t)

    def _drop_for_recompute(self, st: _SlotState,
                            t: Optional[float] = None) -> None:
        if self.tracer.enabled:
            self.tracer.instant(self._track, "recompute_drop",
                                self.clock if t is None else t,
                                cat=CAT_KV, rid=st.rid,
                                generated=len(st.handle.tokens),
                                pages=self.kv.hot_count(st.rid)
                                if self.kv.holds(st.rid) else 0)
        self.kv.free(st.rid)
        st.index = 0
        st.handle.status = RequestStatus.QUEUED
        st.handle.recomputes += 1
        self._paused.remove(st)
        self._queue.appendleft(st)  # ahead of fresh arrivals (it already
                                    # held a slot once; FIFO fairness)

    def _swap_in(self, elapsed: float) -> float:
        """Paused sequences re-enter free rows in pause order (oldest
        paused first — they may hold tier-2 bytes the pool wants back).
        Only their COLD pages ride the fabric; still-hot pages never
        moved.  When nothing is running, liveness demands progress: the
        head of the pause queue may evict newer-paused pages to fit."""
        dt = 0.0
        allow = self.kv.allowance()
        run_demand = sum(self._pages_next(s) for s in self._slots
                         if s is not None)
        while self._paused:
            st = self._paused[0]
            slot = self._free_slot()
            if slot is None:
                break
            want = self._pages_next(st)
            if run_demand + want > allow:
                break       # resuming would overshoot the fair share the
                            # pressure phase just enforced (flap guard —
                            # paused pages must stay revocable)
            missing = (len(self.kv.cold_logicals(st.rid))
                       + max(0, want - self.kv.pages_of(st.rid)))
            if missing > self.kv.hot_free:
                if any(s is not None for s in self._slots):
                    break           # decode will free pages; wait
                dt += self._make_room(missing, protect=(st,),
                                      t=elapsed + dt)
                if missing > self.kv.hot_free:
                    break
            # resume BEFORE popping: mid-resume the sequence must stay
            # visible to the arbiter's demand accounting (its fetches/
            # growth are what the fair share is being claimed for)
            dt += self._resume_into(st, slot, want, elapsed + dt)
            self._paused.popleft()
            run_demand += want
        return dt

    def _resume_into(self, st: _SlotState, slot: int, want: int,
                     elapsed: float) -> float:
        dt = 0.0
        cold = self.kv.cold_logicals(st.rid)
        # reserve all physical pages this resume needs in one go: the
        # per-page fetch loop below would otherwise trigger one
        # revocation episode (and one setup latency on the victim's
        # clock) per cold page instead of one bulk transfer
        self.kv.prepare(len(cold) + max(0, want - self.kv.pages_of(st.rid)))
        if cold:
            fetched = [self.kv.fetch(st.rid, lp) for lp in cold]
            idx = jnp.asarray(np.asarray([p for p, _ in fetched], np.int32))

            def put(pool_leaf, *pages):     # one batched scatter, not one
                stacked = jnp.stack(         # whole-pool copy per page
                    [jnp.asarray(pg, pool_leaf.dtype) for pg in pages],
                    axis=1)
                return pool_leaf.at[:, idx].set(stacked)

            self._pool = jax.tree.map(put, self._pool,
                                      *[pl for _, pl in fetched])
            dt = self.charge_tier2(len(cold) * self.kv.page_bytes,
                                   self.clock + elapsed)
            if self.tracer.enabled:
                self.tracer.span(self._track, "fetch",
                                 self.clock + elapsed, dt, cat=CAT_KV,
                                 rid=st.rid, pages=len(cold),
                                 bytes=len(cold) * self.kv.page_bytes)
        self.kv.grow(st.rid, want)
        for lp, phys in enumerate(self.kv.page_table(st.rid)):
            self._table[slot, lp] = phys
        self._place(st, slot)
        return dt

    # ---- disaggregated handoff admission -----------------------------------
    def _admit_handoffs(self, elapsed: float) -> float:
        """Admit handed-off (externally prefilled) sequences whose
        leading KV pages have arrived: allocate physical pages, scatter
        every page payload (arrived pages now; the rest are gated by
        ``ready_at``, which decode scheduling honors), and place the
        row.  Runs after swap-in and before fresh admission — a handoff
        already spent prefill compute elsewhere, so it outranks a fresh
        arrival for free rows (the recompute-requeue fairness rule) —
        but never past a blocked pause queue, mirroring ``_admit``."""
        dt = 0.0
        while self._handoffs:
            if self._paused:
                break
            ho = self._handoffs[0]
            st = ho.state
            if ho.admit_at > self.clock + elapsed + dt:
                break       # leading pages still in flight on the fabric
            need = (self.budget.pages_for(st.target_len)
                    if self.cfg.reserve_lifetime
                    else self.budget.pages_for(st.index + 1))
            slot = self._free_slot()
            if slot is None or need > self.kv.hot_free:
                break
            phys = self.kv.alloc(st.rid, need)
            for i, payload in enumerate(ho.pages):
                self._write_page(int(phys[i]), payload)
            for lp, p in enumerate(phys):
                self._table[slot, lp] = p
            self._place(st, slot)
            st.ready_at = ho.ready_at
            self._handoffs.popleft()
        return dt

    # ---- admission / prefill ---------------------------------------------
    def _admit(self, elapsed: float) -> float:
        """FIFO prefill admission (head-of-line blocking keeps the order
        deterministic; a request that can never fit fails immediately).
        Admission never runs past a blocked pause queue: a fresh arrival
        must not eat the free rows/pages the oldest paused sequence is
        waiting for (it would starve behind a steady arrival stream) —
        and it never evicts a paused sequence's residency either."""
        dt = 0.0
        while self._queue:
            if self._paused:
                break
            st = self._queue[0]
            if st.request.arrival_time > self.clock + elapsed + dt:
                break   # not arrived yet on the modeled clock: admitting
                        # (and decoding) it now would emit tokens BEFORE
                        # its arrival and drive ttft/latency negative
            if self.budget.pages_for(st.target_len) > self.kv.num_pages:
                self._queue.popleft()
                st.handle.status = RequestStatus.FAILED_OOM
                st.handle.done_clock = self.clock + elapsed + dt
                if self.tracer.enabled:
                    self.tracer.instant(self._track, "failed_oom",
                                        st.handle.done_clock,
                                        cat=CAT_REQUEST, rid=st.rid)
                continue
            slot = self._free_slot()
            eff = st.effective_prompt()
            need = (self.budget.pages_for(st.target_len)
                    if self.cfg.reserve_lifetime
                    else self.budget.pages_for(len(eff) + 1))
            if slot is None or need > self.kv.hot_free:
                break
            # prefill BEFORE popping: while its pages are allocated the
            # request must stay visible (as queue head) to the arbiter's
            # demand accounting, or its fair share evaporates mid-admit
            dt += self._prefill_into(st, slot, eff, elapsed + dt)
            self._queue.popleft()
        return dt

    def _prefill_into(self, st: _SlotState, slot: int,
                      eff: Tuple[int, ...], elapsed: float) -> float:
        plen = len(eff)
        bucket = self._bucket_len(plen)
        self._buckets_used.add(bucket)
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :plen] = eff
        slot_cache = self.model.init_cache(1, bucket,
                                           dtype=self._cache_dtype)
        logits, cache = self._prefill_fn(self.params,
                                         {"tokens": jnp.asarray(tokens)},
                                         slot_cache, jnp.int32(plen - 1))
        # the padded tail is real (wasted) compute on hardware: charge it
        cost = self.cost.prefill_s(bucket)
        if self.tracer.enabled:
            self.tracer.span(self._track, "prefill",
                             self.clock + elapsed, cost, cat=CAT_ENGINE,
                             rid=st.rid, bucket=bucket, prompt_len=plen)
        tok = int(np.argmax(np.asarray(logits)[0, -1]))
        self._emit(st, tok, self.clock + elapsed + cost)
        if st.handle.done:
            return cost
        need = (self.budget.pages_for(st.target_len)
                if self.cfg.reserve_lifetime
                else self.budget.pages_for(plen + 1))
        phys = self.kv.alloc(st.rid, need)
        self._write_prefill_pages(cache, phys, plen)
        for lp, p in enumerate(phys):
            self._table[slot, lp] = p
        st.index = plen
        st.cur_tok = tok
        self._place(st, slot)
        return cost

    def _write_page(self, phys: int, payload) -> None:
        """Write ONE page payload (the ``slice_page`` / ``PagedKV``
        per-page format) into physical page ``phys`` of the pool — the
        import half of the page seam.  Prefill scatter, tier-2 fetch
        and the disaggregated handoff all land pages through the same
        dtype-converting ``.at[...].set``, so a page is bit-identical
        in the pool no matter which path carried it."""
        self._pool = jax.tree.map(
            lambda pool_leaf, page_leaf: pool_leaf.at[:, phys].set(
                jnp.asarray(page_leaf, pool_leaf.dtype)),
            self._pool, payload)

    def _write_prefill_pages(self, cache, phys: List[int],
                             plen: int) -> None:
        """Write the dense prefill cache into the allocated physical
        pages one page at a time (``slice_page`` -> ``_write_page``):
        page-granular at prefill time, so a disaggregated prefill tier
        can stream each page the moment it is sliced instead of
        scattering the whole bucket after prefill completes.  Only
        pages holding real tokens are copied: the padded bucket tail
        (and any growth/lifetime pages past the prompt) is garbage the
        kernel's length mask never reads.  The physical pages are
        distinct, so the per-page writes compose to exactly the old
        batched scatter (pinned by a regression test)."""
        ps = self.cfg.page_size
        for i in range(-(-plen // ps)):
            self._write_page(int(phys[i]), slice_page(cache, i, ps))

    def _place(self, st: _SlotState, slot: int) -> None:
        st.slot = slot
        st.admit_seq = self._admit_seq
        self._admit_seq += 1
        self._slots[slot] = st
        self._lengths[slot] = st.index
        self._slot_tok[slot] = st.cur_tok
        st.handle.status = RequestStatus.RUNNING

    # ---- decode ----------------------------------------------------------
    def _emit(self, st: _SlotState, tok: int, at: float) -> None:
        """Record a generated token at its modeled completion time."""
        st.handle.tokens.append(tok)
        if st.handle.first_token_clock is None:
            st.handle.first_token_clock = at
        eos_hit = (self.cfg.eos_token is not None
                   and tok == self.cfg.eos_token)
        if len(st.handle.tokens) >= st.request.max_new_tokens or eos_hit:
            st.handle.status = RequestStatus.DONE
            st.handle.done_clock = at
            if self.tracer.enabled:
                h = st.handle
                ttft = (h.first_token_clock - h.submit_clock
                        if h.first_token_clock is not None else 0.0)
                self.tracer.instant(self._track, "finish", at,
                                    cat=CAT_REQUEST, rid=h.rid,
                                    tokens=len(h.tokens))
                # one span per request lifetime on the tenant's request
                # row: submit -> done, with the latency decomposition
                # downstream reports read straight off the timeline
                extra = ({"kv_transit_s": h.kv_transit_s}
                         if h.kv_transit_s > 0.0 else {})
                self.tracer.span(f"{self._track}/requests", f"req{h.rid}",
                                 h.submit_clock, at - h.submit_clock,
                                 cat=CAT_REQUEST, rid=h.rid, ttft_s=ttft,
                                 tokens=len(h.tokens), swaps=h.swaps,
                                 preempts=h.preempts,
                                 recomputes=h.recomputes, **extra)
            if self.kv.holds(st.rid):
                self.kv.free(st.rid)
            if st.slot is not None:
                self._table[st.slot, :] = self._trash
                self._lengths[st.slot] = 0
                self._slots[st.slot] = None
                st.slot = None

    def _row_bucket(self, n_live: int) -> int:
        for b in self._row_buckets:
            if b >= n_live:
                return b
        raise AssertionError(f"{n_live} live rows > max_slots")

    def _decode_once(self, elapsed: float) -> float:
        # rows whose handed-off KV pages are still in flight on the
        # fabric are placed but not schedulable: decoding one would
        # read pages before their modeled transfer completion (the
        # disagg-handoff sanitizer violation).  Colocated rows have
        # ready_at == 0.0, so the filter is the identity for them.
        running = [st for st in self._running()
                   if st.ready_at <= self.clock + elapsed]
        if not running:
            return 0.0
        for st in running:
            self._lengths[st.slot] = st.index
            self._slot_tok[st.slot] = st.cur_tok
            st.last_sched = self.steps
            if st.on_first_decode is not None:
                # first decode of a handed-off row: report the modeled
                # use time (>= every page's transfer completion — the
                # transferred-before-use fact the sanitizer audits)
                st.on_first_decode(self.clock + elapsed)
                st.on_first_decode = None
        # gather live rows into a pow2 row bucket: pad with idle slots
        # (trash page table, length 0 — exactly what a full-array
        # decode feeds for them), so the decode batch shrinks with
        # occupancy while per-row outputs stay identical
        bucket = self._row_bucket(len(running))
        self._row_buckets_used.add(bucket)
        rows = [st.slot for st in running]
        if bucket < self.cfg.max_slots:
            idle = [i for i, s in enumerate(self._slots) if s is None]
            sel = np.asarray(rows + idle[:bucket - len(rows)], np.int32)
        else:
            sel = np.arange(self.cfg.max_slots, dtype=np.int32)
            rows = list(sel)                # full array: row == slot
        toks = jnp.asarray(self._slot_tok[sel][:, None])
        table = jnp.asarray(self._table[sel])
        lengths = jnp.asarray(self._lengths[sel])
        new_toks, self._pool = self._decode_fn(self.params, toks,
                                               self._pool, table, lengths)
        new_toks = np.asarray(new_toks)
        pos = {slot: i for i, slot in enumerate(rows)}
        cost = self.cost.decode_s(len(running))
        at = self.clock + elapsed + cost
        if self.tracer.enabled:
            self.tracer.span(self._track, "decode",
                             self.clock + elapsed, cost, cat=CAT_ENGINE,
                             rows=len(running), bucket=bucket)
        for st in running:
            tok = int(new_toks[pos[st.slot], 0])
            st.index += 1
            st.cur_tok = tok
            self._decoded_tokens += 1
            self._emit(st, tok, at)
        return cost

    # ---- observability ---------------------------------------------------
    # flat scalar keys of the legacy stats() dict; each maps 1:1 onto
    # the registry path  serve/<tenant>/<key>
    _STATS_KEYS = ("clock_s", "steps", "busy_s", "queue_depth", "running",
                   "swapped", "completed", "failed_oom", "tokens_decoded",
                   "throughput_tok_s", "throughput_busy_tok_s", "preempts",
                   "preempt_swaps", "preempt_recomputes", "prefill_buckets",
                   "prefill_compiles", "decode_row_buckets",
                   "decode_compiles")

    def _metrics_prefix(self) -> str:
        return f"serve/{self.tenant or 'engine'}"

    def metrics(self, registry: Optional[MetricsRegistry] = None,
                prefix: Optional[str] = None) -> MetricsRegistry:
        """Fill (and return) a ``repro.obs`` metrics registry with this
        engine's state under ``serve/<tenant>/...`` — the ONE schema
        downstream reporting reads; ``stats()`` is a thin adapter."""
        reg = registry if registry is not None else MetricsRegistry()
        p = prefix if prefix is not None else self._metrics_prefix()
        statuses = [h.status for h in self.handles.values()]
        pairs = (
            ("clock_s", self.clock),
            ("steps", self.steps),
            ("busy_s", self.busy_s),
            ("queue_depth", len(self._queue)),
            ("running", sum(s is not None for s in self._slots)),
            ("swapped", len(self._paused)),
            ("completed", sum(s is RequestStatus.DONE for s in statuses)),
            ("failed_oom",
             sum(s is RequestStatus.FAILED_OOM for s in statuses)),
            ("tokens_decoded", self._decoded_tokens),
            # clock_s includes idle inter-arrival gaps (advance_clock),
            # so this number is arbitrarily diluted on sparse traces —
            # it is the *offered-load* rate, kept for trace comparisons
            ("throughput_tok_s", (self._decoded_tokens / self.clock
                                  if self.clock > 0 else 0.0)),
            # decode rate while the engine is actually working: the
            # hardware-capability number benchmarks should quote
            ("throughput_busy_tok_s", (self._decoded_tokens / self.busy_s
                                       if self.busy_s > 0 else 0.0)),
            ("preempts",
             sum(h.preempts for h in self.handles.values())),
            ("preempt_swaps",
             sum(h.swaps for h in self.handles.values())),
            ("preempt_recomputes",
             sum(h.recomputes for h in self.handles.values())),
            ("prefill_buckets", list(self._buckets)),
            ("prefill_compiles", self.prefill_compiles()),
            ("decode_row_buckets", list(self._row_buckets)),
            ("decode_compiles", self.decode_compiles()),
        )
        for key, value in pairs:
            reg.set(f"{p}/{key}", value)
        for key, value in self.kv.residency().items():
            reg.set(f"{p}/kv/{key}", value)
        # the property materializes the lazy private transport so the
        # subtree is schema-stable whether or not a swap ever happened
        self.transport.metrics(reg, prefix=f"{p}/transport")
        if self.arbiter is not None:
            reg.set(f"{p}/tenant", self.tenant)
            reg.set(f"{p}/allowance", self.kv.allowance())
        return reg

    def stats(self) -> Dict[str, Any]:
        """Throughput, queue depth, page-pool residency, compile counts
        — the legacy dict, adapted off the ``metrics()`` registry."""
        p = self._metrics_prefix()
        snap = self.metrics().snapshot(p + "/")
        out: Dict[str, Any] = {k: snap[f"{p}/{k}"]
                               for k in self._STATS_KEYS}
        out["kv"] = self.kv.residency()
        out["transport"] = self.transport.stats()
        if self.arbiter is not None:
            out["tenant"] = snap[f"{p}/tenant"]
            out["allowance"] = snap[f"{p}/allowance"]
        return out
