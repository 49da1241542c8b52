"""repro.pool validation: allocator invariants (no double allocation,
capacity conservation, hop minimality), deterministic scheduler traces,
and the lease → JAX mesh + TieringPolicy runtime binding."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.core import simulator as sim
from repro.core.tiering import TieringPolicy
from repro.pool import (JobRequest, PoolJob, ResourcePool, Scheduler,
                        build_inventory, offload_bytes, smoke_pool)
from repro.pool.allocator import AllocationError, Allocator

GB = 1e9


def small_inventory(policy="scalepool", n_pods=4, pod_size=8):
    return build_inventory(
        n_pods=n_pods, pod_size=pod_size, hbm_per_accel_gb=192.0,
        n_memory_nodes=(2 if policy == "scalepool" else 0),
        memory_node_gb=1024.0, interconnect=policy)


# ---------------------------------------------------------------------------
# allocator invariants
# ---------------------------------------------------------------------------

def test_no_double_allocation():
    a = Allocator(small_inventory())
    allocs = [a.allocate(JobRequest(f"j{i}", 6)) for i in range(5)]
    assert all(x is not None for x in allocs)
    seen = set()
    for alloc in allocs:
        for pod, ids in alloc.accels.items():
            for i in ids:
                assert (pod, i) not in seen
                seen.add((pod, i))
    a.check_conservation()
    assert a.free_accels() == 32 - 30


def test_capacity_conservation_through_churn():
    a = Allocator(small_inventory())
    total = a.inv.total_accels
    t2_total = a.inv.total_tier2
    a.allocate(JobRequest("a", 8, 512 * GB))
    a.allocate(JobRequest("b", 12, 1024 * GB))
    a.check_conservation()
    assert a.free_accels() + 20 == total
    assert a.free_tier2() + 1536 * GB == pytest.approx(t2_total)
    a.release("a")
    a.allocate(JobRequest("c", 3, 256 * GB))
    a.check_conservation()
    a.release("b")
    a.release("c")
    a.check_conservation()
    assert a.free_accels() == total
    assert a.free_tier2() == pytest.approx(t2_total)


def test_release_unknown_job_raises():
    a = Allocator(small_inventory())
    with pytest.raises(AllocationError):
        a.release("ghost")
    a.allocate(JobRequest("x", 4))
    with pytest.raises(AllocationError):
        a.allocate(JobRequest("x", 4))


def test_overcommit_returns_none_and_leaves_state():
    a = Allocator(small_inventory())
    assert a.allocate(JobRequest("big", 33)) is None          # > 32 accels
    assert a.allocate(JobRequest("mem", 4, 3000 * GB)) is None  # > 2TB tier-2
    a.check_conservation()
    assert a.free_accels() == 32
    assert len(a.live) == 0


def test_hop_minimality_on_small_topology():
    """A job that fits one pod must land in one pod (0 inter-pod hops);
    a 1.5-pod job must span exactly ceil(n/pod) pods."""
    a = Allocator(small_inventory())
    one_pod = a.allocate(JobRequest("fits", 8))
    assert one_pod.n_pods == 1
    assert a.inv.span_hops(one_pod.pod_ids) == 0
    spanning = a.allocate(JobRequest("spans", 12))
    assert spanning.n_pods == 2        # minimal pod count, not 3
    # both pods on one leaf switch of the CXL fabric -> 1 hop
    assert a.inv.span_hops(spanning.pod_ids) == 1


def test_best_fit_prefers_tight_pod():
    """After a partial allocation, a job that exactly fits the remainder
    of a pod should take it rather than fragment a fresh pod."""
    a = Allocator(small_inventory())
    a.allocate(JobRequest("partial", 5))      # pod 0 now has 3 free
    tight = a.allocate(JobRequest("tight", 3))
    assert tight.pod_ids == (0,)
    a.check_conservation()


def test_baseline_whole_pod_granularity_and_hbm_scavenging():
    a = Allocator(small_inventory("baseline"))
    alloc = a.allocate(JobRequest("j", 5))
    assert alloc.whole_pods and alloc.n_granted == 8 and alloc.n_stranded == 3
    # 600GB of capacity demand: 3 idle accels (576GB) are not enough ->
    # a second pod is consumed purely for its HBM.
    mem = a.allocate(JobRequest("m", 5, 600 * GB))
    assert mem.n_granted == 16 and mem.n_stranded == 11
    # scalepool satisfies the same request with 5 accels + a reservation
    s = Allocator(small_inventory("scalepool"))
    sp = s.allocate(JobRequest("m", 5, 600 * GB))
    assert sp.n_granted == 5 and sp.tier2_bytes == 600 * GB


def test_fragmentation_metric():
    a = Allocator(small_inventory())
    assert a.metrics().fragmentation == 0.0
    for i, n in enumerate([6, 6, 6, 6]):     # 2 free in each pod
        a.allocate(JobRequest(f"j{i}", n))
    m = a.metrics()
    assert m.fragmentation == pytest.approx(1.0 - 2 / 8)
    assert m.utilization == pytest.approx(24 / 32)


# ---------------------------------------------------------------------------
# scheduler: determinism + end-to-end trace
# ---------------------------------------------------------------------------

def _jobs():
    par = lambda dp: sim.ParallelismConfig(tp=2, pp=1, dp=dp,
                                           global_batch_seqs=64)
    calib = dataclasses.replace(sim.Calibration(), cluster_size=8)
    t2 = offload_bytes(sim.MEGATRON, calib)
    return [
        PoolJob("a", sim.MEGATRON, par(4), n_steps=50, tier2_bytes=t2,
                submit_t=0.0),
        PoolJob("b", sim.MEGATRON, par(2), n_steps=50, submit_t=0.0),
        PoolJob("c", sim.MEGATRON, par(8), n_steps=80, tier2_bytes=t2,
                submit_t=1.0, elastic=True, min_dp=2),
        PoolJob("hi", sim.MEGATRON, par(4), n_steps=30, submit_t=2.0,
                priority=1),
    ]


def _run(policy):
    sched = Scheduler(small_inventory(policy), policy)
    for j in _jobs():
        sched.submit(j)
    return sched.run()


@pytest.mark.parametrize("policy", ["baseline", "scalepool"])
def test_scheduler_trace_deterministic(policy):
    r1, r2 = _run(policy), _run(policy)
    assert r1.trace == r2.trace
    assert r1.summary() == r2.summary()


def test_scheduler_end_to_end_semantics():
    res = _run("scalepool")
    recs = res.records
    # every job finished, and the schedule respects submission times
    for r in recs.values():
        assert r.finish_t is not None
        assert r.start_t >= r.submit_t
    # the high-priority job preempted someone and started on arrival
    assert recs["hi"].queue_delay == pytest.approx(0.0)
    assert any("preempt" in line for line in res.trace)
    # the elastic job was admitted shrunk, then grew back to full width
    assert any("grow c" in line for line in res.trace)
    assert recs["c"].dp_granted == 8
    assert recs["c"].resizes >= 1
    # accounting sanity
    assert 0.0 < res.utilization <= 1.0
    assert res.util_area <= res.granted_area + 1e-9
    s = res.summary()
    assert s["n_finished"] == 4


def test_scheduler_partial_horizon_accounts_tail_window():
    """run(until=...) with a job straddling the horizon must accrue
    util/granted areas and makespan up to ``until`` — pre-fix the
    accounting stopped at the last *processed* event (admission at t=0)
    and partial-horizon utilization was wildly overstated."""
    par = sim.ParallelismConfig(tp=2, pp=1, dp=2, global_batch_seqs=64)

    def fresh():
        s = Scheduler(small_inventory("scalepool"))
        s.submit(PoolJob("j", sim.MEGATRON, par, n_steps=50))
        return s

    full = fresh().run()
    T = full.records["j"].finish_t
    assert T > 0

    sched = fresh()
    half = sched.run(until=T / 2)
    assert half.records["j"].finish_t is None          # straddles ``until``
    assert half.makespan == pytest.approx(T / 2)
    assert half.util_area == pytest.approx(4 * T / 2)  # 4 accels, busy
    assert half.utilization == pytest.approx(full.utilization)
    # resuming past the horizon completes the job with no double counting
    rest = sched.run()
    assert rest.records["j"].finish_t == pytest.approx(T)
    assert rest.util_area == pytest.approx(full.util_area)
    # a drained schedule keeps its natural makespan even for finite until
    done = fresh().run(until=10 * T)
    assert done.makespan == pytest.approx(T)


def test_scalepool_beats_baseline_on_burst():
    """The tentpole claim at test scale: composable pooling admits a
    memory-heavy burst with less stranding and shorter completion."""

    def burst(policy):
        calib = dataclasses.replace(sim.Calibration(), cluster_size=8)
        sched = Scheduler(small_inventory(policy), policy, calib=calib)
        par = sim.ParallelismConfig(tp=2, pp=1, dp=3, global_batch_seqs=66)
        # 450GB per job: more than one pod's idle HBM (2 accels x 192GB)
        # under baseline -> 2 pods per job; comfortably within the 2TB
        # tier-2 pool for all four jobs under scalepool.
        t2 = 450 * GB
        for i in range(4):
            sched.submit(PoolJob(f"j{i}", sim.MEGATRON, par, n_steps=40,
                                 tier2_bytes=t2, submit_t=0.0))
        return sched.run()

    base, sp = burst("baseline"), burst("scalepool")
    assert sp.utilization > base.utilization
    assert sp.mean_jct < base.mean_jct
    assert sp.stranded_frac == pytest.approx(0.0)
    assert base.stranded_frac > 0.0


# ---------------------------------------------------------------------------
# lease → runtime binding
# ---------------------------------------------------------------------------

def test_lease_tiering_policy_follows_reservation():
    pool = smoke_pool()
    with_t2 = pool.lease("t2", 4, tier2_gb=128)
    without = pool.lease("no-t2", 4)
    assert with_t2.tiering_policy().offload_optimizer
    assert not without.tiering_policy().offload_optimizer


def test_lease_kv_grant_becomes_budget():
    """kv_gb earmarks a slice of the tier-2 reservation; the lease turns
    it into a KVBudget with the engine-side page quota left open."""
    pool = smoke_pool()
    lease = pool.lease("svc", 4, tier2_gb=64, kv_gb=16)
    assert lease.kv_bytes == pytest.approx(16 * GB)
    budget = lease.kv_budget(page_size=32)
    assert budget.tier2_bytes == pytest.approx(16 * GB)
    assert budget.tier1_pages is None and budget.page_size == 32
    policy = lease.tiering_policy()
    assert policy.kv_budget is not None and policy.kv_spill
    assert pool.metrics().tier2_kv_reserved == pytest.approx(16 * GB)
    # no grant -> no budget
    assert pool.lease("plain", 4, tier2_gb=8).kv_budget() is None
    with pytest.raises(ValueError, match="kv_bytes"):
        pool.lease("bad", 2, tier2_gb=4, kv_gb=8)   # kv > reservation


def test_tier2_bandwidth_is_schedulable():
    """Bandwidth is admission-controlled per memory node and conserved
    through churn (ROADMAP: concurrent offload-heavy leases contend)."""
    inv = build_inventory(n_pods=4, pod_size=8, n_memory_nodes=2,
                          memory_node_gb=1024.0, memory_node_gbps=50.0,
                          interconnect="scalepool")
    a = Allocator(inv)
    assert a.free_tier2_bw() == pytest.approx(100 * GB)
    big = a.allocate(JobRequest("bw-hog", 4, 64 * GB, tier2_bw=80 * GB))
    assert big is not None and big.tier2_bw_total == pytest.approx(80 * GB)
    # the fabric has only 20GB/s left: an offload-heavy peer is refused
    assert a.allocate(JobRequest("late", 4, 64 * GB, tier2_bw=40 * GB)) is None
    ok = a.allocate(JobRequest("light", 4, 64 * GB, tier2_bw=10 * GB))
    assert ok is not None
    m = a.metrics()
    assert m.tier2_bw_reserved == pytest.approx(90 * GB)
    assert 0.89 < m.tier2_bw_frac < 0.91
    a.check_conservation()
    a.release("bw-hog")
    a.release("light")
    assert a.free_tier2_bw() == pytest.approx(100 * GB)
    a.check_conservation()


def test_tier2_trunk_link_admission():
    """Bandwidth admission runs against the routed estate graph: an
    oversubscribed spine->t2sw trunk refuses an aggregate demand that
    per-node scalars alone would accept."""
    inv = build_inventory(n_pods=4, pod_size=8, n_memory_nodes=2,
                          memory_node_gb=1024.0, memory_node_gbps=40.0,
                          tier2_trunk_gbps=50.0, interconnect="scalepool")
    a = Allocator(inv)
    assert a.free_link_bw("spine->t2sw") == pytest.approx(50 * GB)
    # 60GB/s fits the nodes (40 + 20) but not the 50GB/s shared trunk
    assert a.allocate(JobRequest("wide", 4, 64 * GB, tier2_bw=60 * GB)) is None
    a.check_conservation()
    assert a.free_tier2_bw() == pytest.approx(80 * GB)   # nothing leaked
    ok = a.allocate(JobRequest("fits", 4, 64 * GB, tier2_bw=30 * GB))
    assert ok is not None
    assert a.free_link_bw("spine->t2sw") == pytest.approx(20 * GB)
    # a second job under the node caps still bounces off the trunk
    assert a.allocate(JobRequest("late", 4, 64 * GB, tier2_bw=30 * GB)) is None
    a.check_conservation()
    a.release("fits")
    assert a.free_link_bw("spine->t2sw") == pytest.approx(50 * GB)
    a.check_conservation()


def test_gang_members_submitted_at_different_times_admit_atomically():
    """ROADMAP PR 4 caveat (fails pre-fix): gang members submitted at
    different timestamps admitted independently — the first member
    started alone at t=0 while its peer was still in flight.  With the
    pending-gang buffer, a declared gang (gang_size) is held until
    complete and admitted all-or-nothing."""
    par = sim.ParallelismConfig(tp=2, pp=1, dp=3, global_batch_seqs=66)
    sched = Scheduler(small_inventory("scalepool"), queueing="drf")
    for i, t in enumerate([0.0, 1.0]):          # staggered submission
        sched.submit(PoolJob(f"g{i}", sim.MEGATRON, par, n_steps=10,
                             submit_t=t, user="u", gang="pair",
                             gang_size=2))
    res = sched.run()
    recs = res.records
    assert all(r.finish_t is not None for r in recs.values())
    # neither member may start before the gang is complete at t=1.0 —
    # pre-fix g0 admitted alone at t=0
    starts = [recs["g0"].start_t, recs["g1"].start_t]
    assert min(starts) == pytest.approx(1.0)
    assert starts[0] == pytest.approx(starts[1])
    assert any("hold g0" in line for line in res.trace)
    assert any("admit gang 'pair'" in line for line in res.trace)


def test_gang_without_explicit_user_still_assembles():
    """gang_key must use the RAW user: the drf fallback (user or name)
    would scatter a no-user gang's members across per-job pending
    buffers and hold each forever (run() returning with the jobs never
    started, silently)."""
    par = sim.ParallelismConfig(tp=2, pp=1, dp=3, global_batch_seqs=66)
    sched = Scheduler(small_inventory("scalepool"), queueing="drf")
    for i, t in enumerate([0.0, 1.0]):
        sched.submit(PoolJob(f"g{i}", sim.MEGATRON, par, n_steps=10,
                             submit_t=t, gang="pair", gang_size=2))
    res = sched.run()
    assert all(r.finish_t is not None for r in res.records.values())
    assert res.records["g0"].start_t == pytest.approx(1.0)
    assert not sched._pending_gangs
    # an incomplete gang is surfaced in the trace, not dropped silently
    sched2 = Scheduler(small_inventory("scalepool"), queueing="drf")
    sched2.submit(PoolJob("lone", sim.MEGATRON, par, n_steps=10,
                          gang="pair", gang_size=2))
    res2 = sched2.run()
    assert res2.records["lone"].start_t is None
    assert any("WARNING gang 'pair' incomplete" in l for l in res2.trace)
    # mixed gang_size declarations are an error, not a silent split/hold
    sched3 = Scheduler(small_inventory("scalepool"), queueing="drf")
    sched3.submit(PoolJob("m1", sim.MEGATRON, par, n_steps=10,
                          gang="pair", gang_size=2))
    sched3.submit(PoolJob("m2", sim.MEGATRON, par, n_steps=10,
                          gang="pair", gang_size=3))
    with pytest.raises(ValueError, match="gang_size"):
        sched3.run()


def test_priority_preemption_never_splits_a_declared_gang():
    """FIFO priority preemption must not yank one member of a declared
    gang while its peers keep running — gang members are not
    preemptable (all-or-nothing placement holds for their lifetime)."""
    par = lambda dp: sim.ParallelismConfig(tp=2, pp=1, dp=dp,
                                           global_batch_seqs=64)
    sched = Scheduler(small_inventory("scalepool"))
    for i in range(2):      # gang fills 24 of 32 accels
        sched.submit(PoolJob(f"g{i}", sim.MEGATRON, par(6), n_steps=30,
                             submit_t=0.0, user="u", gang="pair",
                             gang_size=2))
    # head-of-line high-priority job that cannot fit without preemption
    sched.submit(PoolJob("hi", sim.MEGATRON, par(8), n_steps=5,
                         submit_t=1.0, priority=1))
    res = sched.run()
    recs = res.records
    assert recs["g0"].preemptions == 0 and recs["g1"].preemptions == 0
    assert all(r.finish_t is not None for n, r in recs.items() if n != "hi")
    # the priority job waits for the gang instead of splitting it
    assert recs["hi"].start_t >= min(recs["g0"].finish_t,
                                     recs["g1"].finish_t)


def test_gang_buffer_applies_to_fifo_queueing_too():
    """A declared gang is one FIFO queue unit: held until complete,
    then placed atomically (or skipped whole)."""
    par = sim.ParallelismConfig(tp=2, pp=1, dp=3, global_batch_seqs=66)
    sched = Scheduler(small_inventory("scalepool"))     # fifo
    sched.submit(PoolJob("g0", sim.MEGATRON, par, n_steps=10, submit_t=0.0,
                         gang="pair", gang_size=2, user="u"))
    sched.submit(PoolJob("g1", sim.MEGATRON, par, n_steps=10, submit_t=2.0,
                         gang="pair", gang_size=2, user="u"))
    res = sched.run()
    recs = res.records
    assert all(r.finish_t is not None for r in recs.values())
    assert recs["g0"].start_t == pytest.approx(2.0)
    assert recs["g0"].start_t == pytest.approx(recs["g1"].start_t)


def test_scheduler_threads_tier2_bandwidth():
    """Two offload-heavy jobs that together oversubscribe the capacity
    fabric must run serially, not concurrently."""
    inv = build_inventory(n_pods=4, pod_size=8, n_memory_nodes=2,
                          memory_node_gb=4096.0, memory_node_gbps=40.0,
                          interconnect="scalepool")
    sched = Scheduler(inv)
    par = sim.ParallelismConfig(tp=2, pp=1, dp=2, global_batch_seqs=64)
    for i in range(2):
        sched.submit(PoolJob(f"offl-{i}", sim.MEGATRON, par, n_steps=5,
                             tier2_bytes=256 * GB, tier2_bw=60 * GB))
    res = sched.run()
    recs = list(res.records.values())
    assert all(r.finish_t is not None for r in recs)
    # second job cannot start until the first releases its bandwidth
    starts = sorted(r.start_t for r in recs)
    finishes = sorted(r.finish_t for r in recs)
    assert starts[1] >= finishes[0]


def test_freelist_heap_semantics():
    from repro.pool import FreeList
    fl = FreeList(range(8))
    assert fl.take(3) == (0, 1, 2)
    fl.put((1,))
    assert fl.take(2) == (1, 3)
    assert len(fl) == 4 and fl.ids() == [4, 5, 6, 7]
    with pytest.raises(AssertionError):
        fl.put((4,))                     # double free
    with pytest.raises(AssertionError):
        fl.take(99)                      # over-take
    clone = fl.clone()
    clone.take(4)
    assert fl.ids() == [4, 5, 6, 7]      # clone is independent


def test_lease_mesh_shape_mirrors_topology():
    pool = smoke_pool()
    wide = pool.lease("wide", 12, model_parallel=2)   # spans 2 pods
    assert wide.spans_pods
    shape, axes = wide.mesh_shape(8)
    assert axes == ("pod", "data", "model") and shape == (2, 2, 2)
    shape, axes = wide.mesh_shape(1)                  # 1 CPU device
    assert axes == ("data", "model") and shape == (1, 1)


def test_lease_resize_produces_consistent_plan():
    pool = smoke_pool()
    lease = pool.lease("job", 8, model_parallel=2)
    grown, plan = pool.resize("job", 16)
    assert grown.n_accels == 16
    assert plan["pods"] * plan["data"] * plan["model"] == 16
    assert plan["model"] == 2
    shrunk, plan2 = pool.resize("job", 4)
    assert shrunk.n_accels == 4
    assert plan2["pods"] * plan2["data"] * plan2["model"] == 4
    pool.alloc.check_conservation()


def test_lease_drives_real_train_step(rng):
    """Acceptance: a pool lease materializes as a concrete jax mesh +
    TieringPolicy and drives an actual sharded train step on CPU."""
    from repro.configs import SMOKE_ARCHS
    from repro.models.api import build_model
    from repro.models.config import ShapeConfig
    from repro.optim.adamw import AdamW
    from repro.runtime import train as train_rt
    from repro.sharding.partition import use_rules
    from repro.sharding.profiles import make_rules
    from repro.core.tiering import offload_state_shardings
    from conftest import make_batch

    pool = smoke_pool()
    lease = pool.lease("train", 8, tier2_gb=64, model_parallel=2)
    mesh, policy = lease.materialize()
    assert isinstance(policy, TieringPolicy) and policy.offload_optimizer

    cfg = SMOKE_ARCHS["qwen1.5-0.5b"]
    model = build_model(cfg)
    opt = AdamW(lr=1e-3)
    shape = ShapeConfig("pool_smoke", "train", 16, 2)
    rules = make_rules(cfg, shape, mesh, fsdp=False)
    state = train_rt.init_state(model, opt, rng)
    step, state_sh = train_rt.make_train_step(model, opt, shape, mesh=mesh,
                                              rules=rules)
    state_sh = offload_state_shardings(state_sh, policy)
    batch = make_batch(rng, cfg, B=2, S=16)
    with use_rules(rules, mesh), jax.set_mesh(mesh):
        new_state, metrics = jax.jit(step)(state, batch)
    assert jnp.isfinite(metrics["loss"])
    assert metrics["loss"].shape == ()


def test_lease_serve_session(rng):
    """The serving path: a lease with kv_spill binds to a decode session."""
    from repro.configs import SMOKE_ARCHS
    from repro.models.api import build_model
    from repro.models.config import ShapeConfig
    from repro.runtime import serve as serve_rt

    pool = smoke_pool()
    lease = pool.lease("serve", 4, tier2_gb=64, kv_gb=8)
    cfg = SMOKE_ARCHS["qwen1.5-0.5b"]
    model = build_model(cfg)
    shape = ShapeConfig("serve_smoke", "decode", 32, 2)
    sess = serve_rt.make_lease_session(model, shape, lease)
    assert sess.kv_spill
    params = model.init(rng)
    B, prompt = 2, 8
    tokens = jax.random.randint(rng, (B, prompt), 1, cfg.vocab)
    cache = model.init_cache(B, 32, dtype=jnp.float32)
    logits, cache = sess.prefill_step(params, {"tokens": tokens}, cache)
    carry = {"tokens": jnp.argmax(logits[:, -1:, :], -1).astype(jnp.int32),
             "cache": cache, "index": jnp.int32(prompt)}
    logits2, carry = sess.decode_step(params, carry)
    assert logits2.shape[0] == B
    assert jnp.isfinite(logits2).all()


def test_failed_resize_leaves_pool_intact():
    """An impossible re-sharding plan must not half-commit the resize."""
    pool = smoke_pool()
    pool.lease("j", 8, model_parallel=4)
    with pytest.raises(ValueError, match="model parallelism"):
        pool.resize("j", 6)       # 6 accels can't host mp=4
    assert pool.leases["j"].n_accels == 8
    assert pool.alloc.live["j"].n_requested == 8
    pool.alloc.check_conservation()


def test_pool_exhaustion_raises_informatively():
    pool = smoke_pool()
    pool.lease("hog", 30)
    with pytest.raises(RuntimeError, match="cannot satisfy"):
        pool.lease("late", 8)


# ---------------------------------------------------------------------------
# demand-weighted KV shares + gang placement (the repro.disagg estate)
# ---------------------------------------------------------------------------

def test_kv_shares_water_filling_sharing_incentive():
    pool = ResourcePool(small_inventory())
    lease = pool.lease("shared", 4, tier2_gb=64, kv_gb=3.0,
                       tenants=("a", "b", "c"))
    kv = lease.kv_bytes
    even = kv / 3
    # no demands: the legacy static split, bit-compatible
    assert lease.kv_shares() == pytest.approx(
        {"a": even, "b": even, "c": even})
    # a light demander saturates and donates; the surplus flows to the
    # heavy demander, and the leftover returns as an equal bonus
    shares = lease.kv_shares({"a": 0.2 * even, "b": 2.5 * even})
    assert sum(shares.values()) == pytest.approx(kv)
    assert shares["a"] >= 0.2 * even
    assert shares["b"] > even
    assert shares["c"] > 0.0           # quiet tenant keeps spill headroom
    # sharing incentive (pinned): a tenant demanding at least the even
    # split never receives less than the even split
    for demands in ({"a": even}, {"a": 5 * even},
                    {"a": even, "b": 9 * even, "c": 9 * even}):
        assert lease.kv_shares(demands)["a"] >= even * (1 - 1e-12)
    with pytest.raises(KeyError, match="intruder"):
        lease.kv_shares({"intruder": 1.0})


def test_gang_lease_roles_and_handoff_route():
    pool = ResourcePool(small_inventory(), policy="contention")
    gang = pool.lease_gang("serve", {
        "prefill": dict(n_accels=8),
        "decode": dict(n_accels=8, tier2_gb=8, kv_gb=1.0,
                       tenants=("d0",)),
    })
    assert set(gang) == {"prefill", "decode"}
    assert gang["prefill"].role == "prefill"
    assert gang["decode"].role == "decode"
    assert gang["prefill"].job == "serve/prefill"
    # pod_size=8: each tier fills one pod, so the tiers cannot share a
    # gateway and the KV handoff rides a real estate route
    route = pool.handoff_route(gang["prefill"], gang["decode"])
    assert route is not None and len(route.links) >= 1
    pool.release_gang("serve")
    assert pool.alloc.free_accels() == 32
    pool.alloc.check_conservation()
    with pytest.raises(AllocationError, match="no gang"):
        pool.release_gang("serve")


def test_gang_all_or_nothing_rollback():
    a = Allocator(small_inventory())
    a.allocate(JobRequest("hog", 28))
    free_before = a.free_accels()
    out = a.allocate_gang([JobRequest("g/p", 2, role="prefill"),
                           JobRequest("g/d", 6, role="decode")])
    assert out is None                 # the decode member cannot fit
    assert a.free_accels() == free_before
    assert "g/p" not in a.live and "g/d" not in a.live
    a.check_conservation()


def test_gang_colocated_tiers_degenerate_handoff():
    """Both tiers fitting one pod share a gateway: the handoff route is
    None — the signal DisaggCluster uses to run degenerate."""
    pool = ResourcePool(small_inventory())
    gang = pool.lease_gang("tiny", {"prefill": dict(n_accels=2),
                                    "decode": dict(n_accels=2)})
    assert pool.handoff_route(gang["prefill"], gang["decode"]) is None
    pool.release_gang("tiny")
