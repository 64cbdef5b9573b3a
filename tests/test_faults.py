"""Fault-injection harness + graceful degradation (DESIGN.md §15).

The acceptance criteria of the resilience PR: under every injected fault
the streaming pipeline recovers or degrades WITHOUT hanging, emitted
tokens stay bit-identical to an undisturbed run, the byte ledger stays
exact (retried transfers land exactly once), and the degradation level /
fault counters surface through ``Session.stats()`` and the gateway's
``/healthz`` + ``/metrics``. With no faults injected, every path is
byte-for-byte what it was before the harness existed.
"""
import asyncio
import json

import jax
import numpy as np
import pytest

from repro import Session
from repro.configs import get_smoke_config
from repro.core import (CLI2, InferenceSetting, PipelinedExecutor,
                        TimingEstimator, build_graph, build_schedule,
                        run_install)
from repro.core import Placement
from repro.core.faults import (DEGRADATION_RUNGS, AllocationFault,
                               DemandTimeout, FaultPlan, FaultSpec,
                               RecoveryPolicy, TransferFault, WorkerLost)
from repro.core.prefetch import PrefetchEngine
from repro.core.serving import ContinuousBatcher, Request
from repro.gateway import InprocClient
from repro.models import build_model
from repro.models.common import greedy_token

SETTING = InferenceSetting(batch=2, context=64)

# backoff without wall-clock cost in every injected-fault test
FAST = RecoveryPolicy(sleep=lambda s: None, backoff_base_s=0.0)


@pytest.fixture(scope="module")
def db():
    return run_install(CLI2, quick=True)


@pytest.fixture(scope="module")
def arches(db):
    """Per-arch (cfg, params, schedule, clean prefill/decode reference)."""
    out = {}
    for arch in ("yi-9b", "qwen30b-a3b"):
        cfg = get_smoke_config(arch)
        params = build_model(cfg).init(jax.random.PRNGKey(0))
        subs = build_graph(cfg, wdtype=2)
        sched = build_schedule(
            int(sum(s.weight_bytes for s in subs) * 0.2) + 1, subs,
            TimingEstimator(db, CLI2), SETTING)
        ex = PipelinedExecutor(cfg, params, sched, max_seq=64,
                               overlap=False)
        tokens = jax.random.randint(jax.random.PRNGKey(7), (2, 12), 0,
                                    cfg.vocab)
        last, kv, pos = ex.prefill(tokens)
        gen, _ = ex.decode(greedy_token(last), kv, pos, steps=5)
        out[arch] = dict(cfg=cfg, params=params, sched=sched,
                         tokens=tokens, ref_gen=np.asarray(gen),
                         ref_streamed=ex.stats.streamed_bytes)
    return out


def run_faulted(a, faults, recovery=FAST, overlap=True):
    ex = PipelinedExecutor(a["cfg"], a["params"], a["sched"], max_seq=64,
                           overlap=overlap, faults=faults,
                           recovery=recovery)
    last, kv, pos = ex.prefill(a["tokens"])
    gen, _ = ex.decode(greedy_token(last), kv, pos, steps=5)
    return ex, np.asarray(gen)


# ============================================================ plan basics
def test_fault_plan_is_deterministic():
    specs = [FaultSpec("prefetch.copy", "fail", after=2, count=2),
             FaultSpec("demand.timeout", "timeout", key="exp")]
    logs = []
    for _ in range(2):
        plan = FaultPlan(specs, seed=7, clock=lambda: 0.0)
        for i in range(6):
            try:
                plan.check("prefetch.copy", key=f"s{i}")
            except TransferFault:
                pass
        with pytest.raises(DemandTimeout):
            plan.check("demand.timeout", key="expert3")
        plan.check("demand.timeout", key="other")   # key filter: no match
        logs.append([(f["point"], f["key"], f["mode"], f["hit"])
                     for f in plan.fired])
    assert logs[0] == logs[1]
    assert logs[0] == [("prefetch.copy", "s2", "fail", 2),
                       ("prefetch.copy", "s3", "fail", 3),
                       ("demand.timeout", "expert3", "timeout", 0)]
    c = plan.counters()
    assert c["fired_total"] == 3 and c["hits"]["prefetch.copy"] == 6
    assert c["fired"] == {"prefetch.copy:fail": 2,
                          "demand.timeout:timeout": 1}


def test_fault_spec_validates_catalog():
    with pytest.raises(ValueError):
        FaultSpec("prefetch.cpoy")              # typo'd point fails loudly
    with pytest.raises(ValueError):
        FaultSpec("prefetch.copy", mode="explode")
    with pytest.raises(ValueError):
        FaultSpec("prefetch.copy", mode="delay")  # delay needs delay_s
    with pytest.raises(ValueError):
        FaultSpec("prefetch.copy", count=0)


def test_fault_delay_uses_injected_sleep():
    slept = []
    plan = FaultPlan([FaultSpec("prefetch.copy", "delay", delay_s=0.25)],
                     sleep=slept.append)
    plan.check("prefetch.copy")
    assert slept == [0.25]


def test_recovery_policy_backoff_and_retryable():
    pol = RecoveryPolicy(backoff_base_s=0.01, backoff_mult=2.0)
    assert pol.backoff_s(0) == pytest.approx(0.01)
    assert pol.backoff_s(2) == pytest.approx(0.04)
    assert pol.retryable(TransferFault("x"))
    assert not pol.retryable(AllocationFault("x"))
    assert not pol.retryable(KeyboardInterrupt())


# ============================================================ zero overhead
@pytest.mark.parametrize("arch", ["yi-9b", "qwen30b-a3b"])
def test_empty_plan_is_zero_overhead_bit_identical(arch, arches):
    """The default-path acceptance criterion: an executor with an empty
    FaultPlan produces byte-for-byte the clean run's tokens and ledger,
    and the plan records zero fired faults."""
    a = arches[arch]
    plan = FaultPlan([])
    ex, gen = run_faulted(a, plan)
    assert np.array_equal(gen, a["ref_gen"])
    assert ex.stats.streamed_bytes == a["ref_streamed"]
    assert plan.counters()["fired_total"] == 0
    st = ex.stats
    assert (st.fault_copy_retries, st.fault_copy_failures,
            st.fault_sync_fallbacks, st.fault_alloc_failures) == (0,) * 4
    assert not st.degraded_sync


def test_no_faults_session_plan_signature_unchanged(db):
    """Threading faults/recovery kwargs through Session must not perturb
    planning: the schedules are structurally identical."""
    cfg = get_smoke_config("yi-9b")
    total = sum(s.weight_bytes for s in build_graph(cfg, wdtype=2))
    s0 = Session.open(cfg, CLI2, int(total * 0.3) + 1, SETTING, db=db,
                      max_seq=64)
    s1 = Session.open(cfg, CLI2, int(total * 0.3) + 1, SETTING, db=db,
                      max_seq=64, faults=FaultPlan([]), recovery=FAST)
    d = s0.schedule.diff(s1.schedule)
    assert not d.to_pin and not d.to_evict
    assert not d.tier_plan_changes and not d.stream_bytes_changes
    assert s0.schedule.pinned_bytes == s1.schedule.pinned_bytes


# ============================================================ copy faults
@pytest.mark.parametrize("arch", ["yi-9b", "qwen30b-a3b"])
def test_copy_fail_retried_bit_identical_ledger_exact(arch, arches):
    """A failed stage copy retries with backoff and lands exactly once in
    the ledger: tokens AND streamed bytes match the undisturbed run."""
    a = arches[arch]
    plan = FaultPlan([FaultSpec("prefetch.copy", "fail", count=2)])
    ex, gen = run_faulted(a, plan)
    assert np.array_equal(gen, a["ref_gen"])
    assert ex.stats.streamed_bytes == a["ref_streamed"]
    assert ex.stats.fault_copy_retries >= 2
    assert ex.stats.fault_copy_failures == 0
    assert ex.stats.fault_sync_fallbacks == 0


@pytest.mark.parametrize("arch", ["yi-9b", "qwen30b-a3b"])
def test_copy_fail_exhausted_falls_back_to_sync_fetch(arch, arches):
    """Past the retry budget the acquire surfaces the error and the
    executor sync-fetches the shard itself — no hang, no double-count."""
    a = arches[arch]
    plan = FaultPlan([FaultSpec("prefetch.copy", "fail", count=20)])
    ex, gen = run_faulted(a, plan)
    assert np.array_equal(gen, a["ref_gen"])
    assert ex.stats.streamed_bytes == a["ref_streamed"]
    assert ex.stats.fault_copy_failures >= 1
    assert ex.stats.fault_sync_fallbacks >= 1


def test_copy_delay_only_slows_never_diverges(arches):
    a = arches["yi-9b"]
    plan = FaultPlan([FaultSpec("prefetch.copy", "delay", delay_s=0.01,
                                count=3)])
    ex, gen = run_faulted(a, plan)
    assert np.array_equal(gen, a["ref_gen"])
    assert ex.stats.streamed_bytes == a["ref_streamed"]
    assert plan.counters()["fired"]["prefetch.copy:delay"] == 3


# ============================================================ worker death
@pytest.mark.parametrize("arch", ["yi-9b", "qwen30b-a3b"])
def test_worker_crash_degrades_to_sync_mid_serve(arch, arches):
    """The watchdog satellite: a dead prefetch thread fails its pending
    slots (no blocked acquire), the pass completes on sync fetches, and
    the executor parks on the overlap=False path — bit-identically."""
    a = arches[arch]
    plan = FaultPlan([FaultSpec("prefetch.worker", "crash", after=1)])
    ex, gen = run_faulted(a, plan)
    assert np.array_equal(gen, a["ref_gen"])
    assert ex.stats.streamed_bytes == a["ref_streamed"]
    assert ex.stats.fault_worker_crashes == 1
    assert ex.stats.fault_sync_fallbacks >= 1
    assert ex.stats.degraded_sync       # watchdog tripped (tolerance=1)


def test_prefetch_worker_death_fails_pending_without_hanging():
    """Satellite regression: an exception in the staging thread must wake
    blocked ``acquire()`` callers with WorkerLost — the seed behaviour
    left them waiting on an event nobody would ever set."""
    cfg = get_smoke_config("yi-9b")
    subs = [s for s in build_graph(cfg, wdtype=2) if s.weight_bytes][:3]
    order = [Placement(s, "vram", "gpu", streamed=True) for s in subs]
    eng = PrefetchEngine(lambda sub: {"w": np.ones(4, np.float32)},
                         faults=FaultPlan([FaultSpec("prefetch.worker",
                                                     "crash")]),
                         recovery=FAST)
    eng.start(order, avail_bytes=None)
    with pytest.raises(WorkerLost):
        eng.acquire(order[0].sub.name, timeout=10.0)
    for pl in order[1:]:                # every pending slot failed too
        with pytest.raises(WorkerLost):
            eng.acquire(pl.sub.name, timeout=10.0)
        eng.discard(pl.sub.name)
    eng.discard(order[0].sub.name)
    eng.finish()                        # returns promptly, no deadlock
    assert eng.stats.worker_crashes == 1
    assert not eng.active


# ============================================================ demand faults
def moe_session(db, faults=None, frac=0.3):
    cfg = get_smoke_config("qwen30b-a3b")
    total = sum(s.weight_bytes for s in build_graph(cfg, wdtype=2))
    return Session.open(cfg, CLI2, int(total * frac) + 1, SETTING, db=db,
                        max_seq=64, faults=faults, recovery=FAST)


def wave(cfg, n=3, max_new=5):
    rng = np.random.RandomState(0)
    return [Request(rid=i, prompt=rng.randint(0, cfg.vocab, size=6 + 2 * i)
                    .astype(np.int32), max_new_tokens=max_new)
            for i in range(n)]


@pytest.fixture(scope="module")
def moe_clean(db):
    s = moe_session(db)
    reqs = wave(s.cfg)
    s.serve(reqs, max_batch=2)
    assert s.executor.stats.demanded_expert_bytes > 0, \
        "fixture bug: no demand streaming to fault"
    return {r.rid: list(r.generated) for r in reqs}, \
        s.executor.stats.streamed_bytes


@pytest.mark.parametrize("spec", [
    FaultSpec("demand.timeout", "timeout", count=1),
    FaultSpec("demand.copy", "fail", count=20),
    FaultSpec("demand.worker", "crash", count=1),
])
def test_demand_fault_never_deadlocks_moe_serve(spec, db, moe_clean):
    """The demand-deadline acceptance criterion: expert demands that time
    out, fail their copies, or lose their worker are sync-fetched — the
    serve completes with bit-identical tokens and an exact ledger
    (demanded bytes accounted exactly once, through either path)."""
    ref, ref_streamed = moe_clean
    s = moe_session(db, faults=FaultPlan([spec]))
    reqs = wave(s.cfg)
    s.serve(reqs, max_batch=2)
    assert {r.rid: list(r.generated) for r in reqs} == ref
    ex = s.executor.stats
    assert s.executor.stats.streamed_bytes == ref_streamed
    assert ex.fault_sync_fallbacks >= 1
    deg = s.stats()["degradation"]
    assert deg["sync_fallbacks"] == ex.fault_sync_fallbacks
    if spec.point == "demand.timeout":
        assert ex.fault_demand_timeouts >= 1
        assert s.executor.prefetch.stats.abandoned >= 1
    assert deg["injected"]["fired_total"] >= 1


# ============================================================ ladder
def dense_session(db, faults=None, frac=0.3, **kw):
    cfg = get_smoke_config("yi-9b")
    total = sum(s.weight_bytes for s in build_graph(cfg, wdtype=2))
    return Session.open(cfg, CLI2, int(total * frac) + 1, SETTING, db=db,
                        max_seq=64, faults=faults, recovery=FAST, **kw)


def test_degrade_walks_rungs_and_exhausts(db):
    """The ladder itself: inapplicable rungs are skipped (dense model,
    spec off), each applied rung reports its level, exhaustion returns
    None — and each replanning rung strictly shrinks the pinned set."""
    s = dense_session(db)
    pinned0 = s.schedule.pinned_bytes
    assert s.degradation_level == 0
    lvl = s.degrade(reason="test")
    assert DEGRADATION_RUNGS[lvl] == "tier_down"   # spec/expert rungs n/a
    assert s._emergency_reserve_bytes == s.budget_bytes // 4
    assert s.schedule.pinned_bytes < pinned0
    lvl = s.degrade(reason="test")
    assert DEGRADATION_RUNGS[lvl] == "sync" and s.overlap is False
    assert s.degrade(reason="test") is None        # exhausted
    assert [e["rung"] for e in s.degrade_log] == ["tier_down", "sync"]
    d = s.stats()["degradation"]
    assert d["level"] == len(DEGRADATION_RUNGS) - 1 and d["rung"] == "sync"


def test_degrade_moe_vetoes_cold_experts(db):
    s = moe_session(db)
    lvl = s.degrade(reason="test")
    assert DEGRADATION_RUNGS[lvl] == "expert_shrink"
    vetoed = [x for x in s.subs
              if x.kind == "moe_expert" and x.meta.get("pin_veto")]
    assert vetoed, "expert_shrink must veto the colder half"
    hot = [x for x in s.subs
           if x.kind == "moe_expert" and not x.meta.get("pin_veto")]
    # vetoed shards never pin; the surviving set is the hotter half
    assert max(v.meta.get("hot", 0.0) for v in vetoed) <= \
        max(h.meta.get("hot", 0.0) for h in hot)
    pinned = {p.sub.name for p in s.schedule.pinned_placements()}
    assert not pinned & {v.name for v in vetoed}, \
        "a vetoed expert survived in the post-shrink pin set"


def test_alloc_fault_degrades_and_serve_stays_bit_identical(db):
    """The emergency-rebudget acceptance criterion: an injected device
    allocation failure mid-serve steps the session down the ladder, the
    iteration re-runs, and every request's tokens match a fault-free
    serve."""
    clean = dense_session(db)
    ref = wave(clean.cfg, n=3, max_new=5)
    clean.serve(ref, max_batch=2)
    s = dense_session(db, faults=FaultPlan(
        [FaultSpec("alloc.device", "oom", after=2, count=1)]))
    reqs = wave(s.cfg, n=3, max_new=5)
    s.serve(reqs, max_batch=2)
    assert [list(r.generated) for r in reqs] == \
        [list(r.generated) for r in ref]
    assert s.degradation_level > 0
    b = s.batcher()
    assert len(b.degradations) == 1
    d = s.stats()["degradation"]
    assert d["alloc_failures"] >= 1 and d["log"]
    assert d["injected"]["fired"]["alloc.device:oom"] == 1


def test_alloc_fault_without_session_raises(arches):
    """No session, no ladder: a raw batcher propagates the allocation
    fault instead of silently retrying forever."""
    a = arches["yi-9b"]
    ex = PipelinedExecutor(
        a["cfg"], a["params"], a["sched"], max_seq=64,
        faults=FaultPlan([FaultSpec("alloc.device", "oom", count=1)]),
        recovery=FAST)
    b = ContinuousBatcher(a["cfg"], None, executor=ex, max_batch=2)
    b.submit(wave(a["cfg"], n=1))
    with pytest.raises(AllocationFault):
        b.serve([])


def test_alloc_host_fault_paged_recovers_and_pool_is_consistent(db):
    """Paged-KV half of the OOM matrix: a host/pool allocation fault in
    ``prepare`` aborts before any block mutates, the ladder steps down,
    the pass re-runs — tokens bit-identical, allocator invariants intact,
    no leaked blocks."""
    clean = dense_session(db, kv_layout="paged")
    ref = wave(clean.cfg, n=3, max_new=5)
    clean.serve(ref, max_batch=2)
    s = dense_session(db, kv_layout="paged", faults=FaultPlan(
        [FaultSpec("alloc.host", "oom", after=1, count=1)]))
    reqs = wave(s.cfg, n=3, max_new=5)
    s.serve(reqs, max_batch=2)
    assert [list(r.generated) for r in reqs] == \
        [list(r.generated) for r in ref]
    assert s.degradation_level > 0
    b = s.batcher()
    b.kv.alloc.check()                  # pool invariants after recovery
    assert all(sl is None for sl in b.slots)
    assert len(b.kv.alloc.blocks) == 0, "paged-KV blocks leaked"
    assert s.stats()["degradation"]["injected"]["fired"] \
        == {"alloc.host:oom": 1}


# ============================================================ per-request
def test_request_fault_fails_one_slot_only(db):
    """Satellite: an exception servicing ONE request fails that request
    alone — error event, freed slot — while the other slots' tokens stay
    bit-identical and the batcher keeps serving."""
    clean = dense_session(db)
    ref = wave(clean.cfg, n=3, max_new=5)
    clean.serve(ref, max_batch=2)
    s = dense_session(db, faults=FaultPlan(
        [FaultSpec("serving.request", "fail", key="1", after=1)]))
    reqs = wave(s.cfg, n=3, max_new=5)
    s.serve(reqs, max_batch=2)
    assert reqs[1].error is not None and not reqs[1].done
    assert 1 <= len(reqs[1].generated) < reqs[1].max_new_tokens
    for i in (0, 2):
        assert list(reqs[i].generated) == list(ref[i].generated), \
            f"rid {i} perturbed by rid 1's fault"
    b = s.batcher()
    st = b.stats()
    assert st["failed"] == 1 and st["completed"] == 2
    assert [r.rid for r in b.failed] == [1]
    assert all(sl is None for sl in b.slots)


def test_request_fault_emits_error_event(db):
    s = dense_session(db, faults=FaultPlan(
        [FaultSpec("serving.request", "fail", key="0", after=1)]))
    b = s.batcher(max_batch=2)
    b.submit(wave(s.cfg, n=1, max_new=5))
    errs = []
    while b.has_work:
        errs += [e for e in b.step() if e.error is not None]
    assert len(errs) == 1
    assert errs[0].rid == 0 and errs[0].done and errs[0].token == -1


# ============================================================ gateway
def body_for(cfg, token_ids, max_tokens=5, **kw):
    return json.dumps({"model": cfg.name, "token_ids": token_ids,
                       "max_tokens": max_tokens, **kw}).encode()


def test_gateway_pump_isolates_faulted_request(db):
    """Satellite: one ticket's injected fault answers 500 to exactly that
    client; the pump survives (a follow-up request completes), other
    tickets finish bit-identically, and the broker ledger reconciles with
    the new ``failed`` column."""
    clean = dense_session(db)
    ref = wave(clean.cfg, n=3, max_new=5)
    clean.serve(ref, max_batch=2)
    # broker rids are 1-based in submit order: rid "2" is ref[1]'s prompt
    s = dense_session(db, faults=FaultPlan(
        [FaultSpec("serving.request", "fail", key="2", after=1)]))

    async def main():
        gw = s.gateway(max_queue=8, max_batch=2).start()
        c = InprocClient(gw)
        out = {}

        async def go(i, r):
            st, _, body = await c.request(
                "POST", "/v1/chat/completions",
                body_for(s.cfg, [int(t) for t in r.prompt],
                         max_tokens=r.max_new_tokens))
            out[i] = (st, json.loads(body))

        tasks = []
        for i, r in enumerate(ref):
            tasks.append(asyncio.ensure_future(go(i, r)))
            await asyncio.sleep(0)     # pin broker rid order 1,2,3
        await asyncio.gather(*tasks)
        # pump is still alive: a follow-up request completes normally
        st, _, _ = await c.request(
            "POST", "/v1/chat/completions",
            body_for(s.cfg, [int(t) for t in ref[0].prompt]))
        assert st == 200
        m = await c.request("GET", "/metrics")
        await gw.close(drain=True)
        return out, json.loads(m[2])

    out, metrics = asyncio.run(main())
    assert out[1][0] == 500
    assert out[1][1]["error"]["code"] == "internal_error"
    for i in (0, 2):
        assert out[i][0] == 200
        assert out[i][1]["choices"][0]["token_ids"] \
            == list(ref[i].generated), f"survivor {i} diverged"
    led = metrics["broker"]["ledger"]
    assert led["failed"] == 1 and metrics["broker"]["reconciles"]
    assert metrics["serving"]["failed"] == 1
    assert metrics["degradation"]["injected"]["fired_total"] == 1


def test_gateway_pump_fault_point_survives(db):
    """An injected whole-turn pump fault fails the tickets of that turn
    but never kills the pump: later submissions serve normally."""
    s = dense_session(db, faults=FaultPlan(
        [FaultSpec("gateway.pump", "fail", count=1)]))

    async def main():
        gw = s.gateway(max_queue=8, max_batch=2).start()
        c = InprocClient(gw)
        st1, _, b1 = await c.request(
            "POST", "/v1/chat/completions",
            body_for(s.cfg, [1, 2, 3], max_tokens=4))
        st2, _, b2 = await c.request(
            "POST", "/v1/chat/completions",
            body_for(s.cfg, [1, 2, 3], max_tokens=4))
        st, _, h = await c.request("GET", "/healthz")
        m = await c.request("GET", "/metrics")
        await gw.close(drain=True)
        return (st1, b1), (st2, b2), json.loads(h), json.loads(m[2])

    (st1, b1), (st2, _), health, metrics = asyncio.run(main())
    assert st1 == 500
    assert json.loads(b1)["error"]["code"] == "internal_error"
    assert st2 == 200                  # pump survived the poisoned turn
    assert health["pump_errors"] == 1 and metrics["pump_errors"] == 1
    assert metrics["broker"]["ledger"]["failed"] == 1
    assert metrics["broker"]["reconciles"]


def test_gateway_drain_deadline_cancels_and_503s(db):
    """Satellite: ``close(drain=True)`` past the deadline cancels the
    stragglers, frees their slots, and answers 503 + Retry-After instead
    of hanging shutdown on one slow request."""
    s = dense_session(db)

    async def main():
        gw = s.gateway(max_queue=8, max_batch=2).start()
        c = InprocClient(gw)
        victim = asyncio.ensure_future(c.request(
            "POST", "/v1/chat/completions",
            body_for(s.cfg, [1, 2, 3], max_tokens=48)))
        # let the victim admit and decode a little
        for _ in range(40):
            await asyncio.sleep(0.005)
            if any(sl is not None for sl in gw.batcher.slots):
                break
        await gw.close(drain=True, drain_deadline_s=0.01)
        st, hdrs, body = await victim
        m = gw.metrics()
        return st, hdrs, json.loads(body), m

    st, hdrs, body, metrics = asyncio.run(main())
    assert st == 503 and body["error"]["code"] == "shutting_down"
    assert int(hdrs.get("retry-after", "0")) >= 1
    assert metrics["aborted_on_close"] == 1
    assert metrics["active_slots"] == 0          # slot actually freed
    assert metrics["broker"]["reconciles"]
    b = s.batcher()
    assert all(sl is None for sl in b.slots) and not b.pending


def test_healthz_reports_degradation(db):
    s = dense_session(db)
    s.degrade(reason="test")

    async def main():
        gw = s.gateway(max_queue=4, max_batch=2).start()
        c = InprocClient(gw)
        st, _, body = await c.request("GET", "/healthz")
        await gw.close(drain=False)
        return st, json.loads(body)

    st, health = asyncio.run(main())
    assert st == 200
    assert health["status"] == "degraded"
    assert health["degradation_level"] == \
        DEGRADATION_RUNGS.index("tier_down")
    assert health["degradation_rung"] == "tier_down"


# ============================================================ donation
def _emulate_donation(eng):
    """Make the engine's donating steps delete their KV inputs after use,
    as buffer donation does on an accelerator — the CPU backend ignores
    ``donate_argnums``, so a read of a donated buffer would go unseen."""
    for name, argnums in eng.DONATED_ARGS.items():
        def run(*args, _step=getattr(eng, name), _argnums=argnums):
            out = _step(*args)
            for i in _argnums:
                args[i].delete()
            return out
        setattr(eng, name, run)


@pytest.mark.parametrize("kv_layout", ["stacked", "paged"])
def test_failed_prefill_keeps_live_kv_under_donation(db, kv_layout):
    """A request whose prefill raises after its attention steps consumed
    (donated) the shared cache fails alone: the batcher keeps serving the
    other slots off the live buffers, bit-identical to a clean run, and
    no step ever reads a donated buffer."""
    cfg = get_smoke_config("yi-9b")
    clean = dense_session(db, kv_layout=kv_layout)
    ref = wave(cfg)
    clean.serve(ref, max_batch=4)

    sess = dense_session(db, kv_layout=kv_layout)
    ex = sess.executor
    _emulate_donation(ex.engine)
    prefill, ffn_step = ex.prefill, ex.engine.ffn_step
    state = {"calls": 0, "armed": False}

    def counting_prefill(*a, **kw):
        state["calls"] += 1
        state["armed"] = state["calls"] == 2      # the second admission
        return prefill(*a, **kw)

    def failing_ffn(w, x, streamed=False):
        if state["armed"]:
            state["armed"] = False
            raise RuntimeError("injected mid-prefill failure")
        return ffn_step(w, x, streamed=streamed)

    ex.prefill, ex.engine.ffn_step = counting_prefill, failing_ffn
    reqs = wave(cfg)
    sess.serve(reqs, max_batch=4)
    assert [r.rid for r in sess.batcher().failed] == [reqs[1].rid]
    for i in (0, 2):
        assert reqs[i].error is None
        assert reqs[i].generated == ref[i].generated
