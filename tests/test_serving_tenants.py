"""Multi-tenant SLO-aware serving (ISSUE 15): priority/quota/deadline
admission units over a deterministic clock, charged-preemption
accounting, degradation-ladder walk-up/walk-down hysteresis with
stage-transition trace events, weighted prefix eviction, no-tenant
token-identity vs the untenanted engine, structured router rejections,
and the adversarial heavy+light mix bar (light-tenant p99 e2e near
solo at near-FCFS aggregate throughput)."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.serving import (AdmissionRejected, DegradeLadder,
                                KVPagePool, Request, Scheduler,
                                ServingConfig, ServingEngine,
                                TenantTable, TokenBucket)


class FakeClock:
    """Deterministic monotonic clock: every read advances `tick`, and
    tests jump it explicitly (bucket refills, deadline aging)."""

    def __init__(self, tick=1e-6):
        self.now = 0.0
        self.tick = tick

    def __call__(self):
        self.now += self.tick
        return self.now


# ---------------------------------------------------------------------------
# token bucket + tenant table units
# ---------------------------------------------------------------------------
class TestTokenBucket:
    def test_refill_debit_and_defer(self):
        clk = FakeClock(tick=0.0)
        b = TokenBucket(rate=2.0, burst=10.0, clock=clk)
        assert b.level == 10.0                  # starts full
        assert b.try_debit(8)
        assert abs(b.level - 2.0) < 1e-9
        assert not b.try_debit(8)               # defer
        assert abs(b.seconds_until(8) - 3.0) < 1e-9
        clk.now += 3.0                          # refill 6 tokens
        assert b.try_debit(8)
        assert abs(b.level) < 1e-9

    def test_burst_cap_and_oversized_bill_debt(self):
        clk = FakeClock(tick=0.0)
        b = TokenBucket(rate=1.0, burst=4.0, clock=clk)
        clk.now += 100.0
        assert b.level == 4.0                   # capped at burst
        # a bill larger than the burst admits from a FULL bucket and
        # leaves debt — over-quota tenants defer, never starve
        assert b.try_debit(10)
        assert b.level == -6.0
        assert not b.try_debit(1)
        assert abs(b.seconds_until(1) - 7.0) < 1e-9

    def test_charge_is_unconditional(self):
        b = TokenBucket(rate=1.0, burst=2.0, clock=FakeClock(tick=0.0))
        b.charge(5)
        assert b.level == -3.0


class TestTenantTable:
    def test_policy_resolution_and_defaults(self):
        t = TenantTable({'a': {'priority': 3,
                               'quota_tokens_per_s': 5.0,
                               'burst_tokens': 7.0, 'weight': 0.5},
                         'b': {}}, clock=FakeClock())
        assert t.priority_of('a') == 3 and t.priority_of('b') == 0
        assert t.priority_of('unknown') == 0
        assert t.bucket('a').burst == 7.0
        assert t.bucket('b') is None and t.bucket(None) is None
        assert t.weight_of('a') == 0.5 and t.weight_of('zzz') == 1.0
        assert t.eviction_weights() == {'a': 0.5, 'b': 1.0}

    def test_unknown_policy_key_raises(self):
        with pytest.raises(ValueError, match='unknown policy keys'):
            TenantTable({'a': {'prio': 1}})


# ---------------------------------------------------------------------------
# scheduler: priority order + priority-aware victim
# ---------------------------------------------------------------------------
class TestPrioritySchedule:
    def test_admission_order_priority_then_fcfs(self):
        s = Scheduler(2, clock=FakeClock())
        lo1 = Request([1], priority=0)
        hi = Request([2], priority=2)
        lo2 = Request([3], priority=0)
        mid = Request([4], priority=1)
        for r in (lo1, hi, lo2, mid):
            s.submit(r)
        assert s.admission_order() == [hi, mid, lo1, lo2]
        # no priorities -> arrival order exactly (the FCFS identity)
        s2 = Scheduler(2, clock=FakeClock())
        rs = [Request([i + 1]) for i in range(4)]
        for r in rs:
            s2.submit(r)
        assert s2.admission_order() == rs

    def test_preempted_request_rejoins_front_of_class(self):
        s = Scheduler(2, clock=FakeClock())
        a, b = Request([1], priority=0), Request([2], priority=0)
        hi = Request([3], priority=1)
        s.submit(a)
        s.admit()
        s.submit(b)
        s.preempt(a)
        s.submit(hi)
        # hi outranks; a (preempted) precedes b within class 0
        assert s.admission_order() == [hi, a, b]

    def test_victim_is_youngest_of_lowest_class_below(self):
        s = Scheduler(3, clock=FakeClock())
        lo_old = Request([1], priority=0)
        lo_young = Request([2], priority=0)
        mid = Request([3], priority=1)
        for r in (lo_old, lo_young, mid):
            s.submit(r)
        s.admit()
        assert s.preempt_victim(below_priority=2) is lo_young
        assert s.preempt_victim(below_priority=1) is lo_young
        assert s.preempt_victim(below_priority=0) is None
        # untenanted rule: youngest overall
        assert s.preempt_victim() is mid
        # exclusion still applies
        assert s.preempt_victim(exclude=lo_young,
                                below_priority=2) is lo_old


# ---------------------------------------------------------------------------
# degradation ladder hysteresis (pure controller)
# ---------------------------------------------------------------------------
class TestDegradeLadder:
    def test_walks_up_in_order_and_down_hysteretically(self):
        clk = FakeClock()
        lad = DegradeLadder(window=2, up=(0.5, 0.7, 0.9),
                            down=(0.3, 0.5, 0.7), hold=3, clock=clk)
        stages = []
        for _ in range(6):
            ev = lad.observe(1.0, 8, 2)
            if ev:
                stages.append((ev['from'], ev['to']))
        assert stages == [(0, 1), (1, 2), (2, 3)]
        assert lad.stage == 3
        # calm signal: each step-down needs `hold` consecutive calm
        # observations — never more than one stage per dwell
        downs = []
        for _ in range(3 * 3 + 2):
            ev = lad.observe(0.0, 0, 2)
            if ev:
                downs.append((ev['from'], ev['to']))
        assert downs == [(3, 2), (2, 1), (1, 0)]
        assert lad.stage == 0
        assert lad.transitions == 6
        assert [h['to'] for h in lad.history] == [1, 2, 3, 2, 1, 0]

    def test_hysteresis_band_prevents_oscillation(self):
        # pressure sitting BETWEEN down[0] and up[0] must hold the
        # current stage forever — neither climbs nor drops
        lad = DegradeLadder(window=1, up=(0.8, 0.9, 0.95),
                            down=(0.4, 0.6, 0.8), hold=2,
                            clock=FakeClock())
        lad.observe(0.85, 0, 4)                 # 0 -> 1
        assert lad.stage == 1
        for _ in range(20):
            lad.observe(0.6, 0, 4)              # inside the band
        assert lad.stage == 1 and lad.transitions == 1

    def test_threshold_validation(self):
        with pytest.raises(ValueError, match='below its up-threshold'):
            DegradeLadder(up=(0.5, 0.6, 0.7), down=(0.5, 0.5, 0.6))
        with pytest.raises(ValueError, match='one threshold'):
            DegradeLadder(up=(0.5,), down=(0.4,))

    def test_pressure_signal_combines_pool_and_queue(self):
        assert DegradeLadder.pressure_of(0.9, 0, 4) == 0.9
        assert DegradeLadder.pressure_of(0.1, 8, 4) == 1.0
        assert DegradeLadder.pressure_of(0.2, 2, 4) == 0.25


# ---------------------------------------------------------------------------
# weighted prefix eviction (pool level)
# ---------------------------------------------------------------------------
class TestWeightedEviction:
    def _cache_chain(self, pool, seq, tokens, owner):
        pool.ensure_capacity(seq, len(tokens))
        pool.register_prefix(seq, tokens, len(tokens), owner=owner)
        pool.release(seq)                       # park in cached set

    def test_lightest_tenant_evicts_first(self):
        pool = KVPagePool(num_pages=4, page_size=2, prefix_cache=True)
        light_toks = [1, 2, 3, 4]
        heavy_toks = [9, 8, 7, 6]
        self._cache_chain(pool, 'L', light_toks, owner='light')
        self._cache_chain(pool, 'H', heavy_toks, owner='heavy')
        assert pool.cached_pages == 4
        pool.set_eviction_weights({'heavy': 0.1, 'light': 1.0})
        # pure LRU would evict LIGHT (older); weights pick heavy
        pool.ensure_capacity('new', 2)
        assert pool._match_pages(heavy_toks) == []
        assert len(pool._match_pages(light_toks)) == 2
        assert pool.stats()['weighted_eviction'] is True
        # disarmed -> back to LRU: the next squeeze (one page free,
        # two needed) evicts light's subtree, oldest cached root
        pool.set_eviction_weights(None)
        pool.ensure_capacity('new2', 4)
        assert pool._match_pages(light_toks) == []

    def test_lru_unchanged_without_weights(self):
        pool = KVPagePool(num_pages=4, page_size=2, prefix_cache=True)
        self._cache_chain(pool, 'A', [1, 2, 3, 4], owner='a')
        self._cache_chain(pool, 'B', [5, 6, 7, 8], owner='b')
        pool.ensure_capacity('new', 2)          # LRU: A evicts first
        assert pool._match_pages([1, 2, 3, 4]) == []
        assert len(pool._match_pages([5, 6, 7, 8])) == 2


# ---------------------------------------------------------------------------
# engine fixtures
# ---------------------------------------------------------------------------
@pytest.fixture(scope='module')
def tiny_lm():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(11)
    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                    num_heads=2, max_seq_len=128, hidden_dropout=0.0,
                    attn_dropout=0.0, use_flash_attention=False)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _drain(eng, cap=2000):
    steps = 0
    while eng.scheduler.has_work:
        eng.step()
        steps += 1
        assert steps < cap, "engine did not drain"
    return steps


def _events(eng, name, req_id=None):
    return [e for e in eng.tracer.events(req_id)
            if e['event'] == name]


# ---------------------------------------------------------------------------
# quota admission (engine)
# ---------------------------------------------------------------------------
class TestQuotaAdmission:
    def test_over_quota_defers_then_admits_on_refill(self, tiny_lm):
        clk = FakeClock()
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=2, prefill_chunk=8, clock=clk,
            tenants={'bulk': {'quota_tokens_per_s': 1.0,
                              'burst_tokens': 10.0}}))
        rng = np.random.RandomState(0)
        p = list(rng.randint(1, 128, 4))
        r1 = eng.submit(p, max_new_tokens=4, top_k=0,
                        tenant_id='bulk')       # bill 8 <= burst 10
        r2 = eng.submit(list(rng.randint(1, 128, 4)), max_new_tokens=4,
                        top_k=0, tenant_id='bulk')  # bill 8 > level 2
        for _ in range(6):
            eng.step()
        assert r1.state in ('running', 'finished', 'prefill')
        assert r2.state == 'waiting' and r2.quota_deferred
        assert r2.quota_defers == 1             # edge-counted, not
                                                # once per sweep
        assert eng.stats()['quota_deferrals_total'] == 1
        ev = _events(eng, 'quota_defer', r2.id)
        assert len(ev) == 1 and ev[0]['retry_after_s'] > 0, ev
        clk.now += 20.0                         # refill the bucket
        _drain(eng)
        assert r2.state == 'finished'
        st = eng.stats()['tenancy']['tenants']['bulk']
        assert st['quota_deferrals'] == 1
        assert st['tokens_billed'] == 16
        eng.shutdown()

    @pytest.mark.slow
    def test_resume_after_preempt_never_redebits(self, tiny_lm):
        clk = FakeClock()
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=2, prefill_chunk=8, clock=clk,
            tenants={'t': {'quota_tokens_per_s': 1.0,
                           'burst_tokens': 50.0}}))
        rng = np.random.RandomState(1)
        r = eng.submit(list(rng.randint(1, 128, 4)), max_new_tokens=4,
                       top_k=0, tenant_id='t')
        for _ in range(2):
            eng.step()
        billed = eng.stats()['tenancy']['tenants']['t']['tokens_billed']
        assert billed == 8 and r.quota_charged
        # simulate a preemption round-trip: release + requeue
        eng.pool.release(r.id)
        eng.scheduler.preempt(r)
        _drain(eng)
        assert r.state == 'finished'
        assert eng.stats()['tenancy']['tenants']['t']['tokens_billed'] \
            == 8                                # unchanged
        eng.shutdown()


# ---------------------------------------------------------------------------
# deadline-aware admission + deadline_miss
# ---------------------------------------------------------------------------
class TestDeadlineAdmission:
    def test_cold_engine_admits_then_warm_engine_rejects(self, tiny_lm):
        clk = FakeClock(tick=1e-3)
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=2, prefill_chunk=8, clock=clk,
            tenants={}))
        rng = np.random.RandomState(2)
        # cold: no decode rate observed -> a tight deadline still admits
        r = eng.submit(list(rng.randint(1, 128, 4)), max_new_tokens=4,
                       top_k=0, deadline_s=1e-9, tenant_id='t')
        _drain(eng)
        assert r.state == 'finished'
        # ... but it finished past its own deadline: deadline_miss
        assert eng.stats()['deadline_misses_total'] == 1
        assert _events(eng, 'deadline_miss', r.id)
        assert eng.tracer.request_table()[r.id]['deadline_miss'] is True
        # warm: decode rate known; queue a backlog, then an impossible
        # deadline rejects AT SUBMIT with a structured hint
        assert eng.decode_rate() > 0
        backlog = [eng.submit(list(rng.randint(1, 128, 8)),
                              max_new_tokens=16, top_k=0)
                   for _ in range(3)]
        with pytest.raises(AdmissionRejected) as ei:
            eng.submit(list(rng.randint(1, 128, 4)), max_new_tokens=4,
                       top_k=0, deadline_s=1e-9, tenant_id='t')
        e = ei.value
        assert e.reason == 'deadline_unmet'
        assert e.retry_after_s is not None and e.retry_after_s > 0
        assert e.estimated_s > e.deadline_s
        st = eng.stats()
        assert st['deadline_rejects_total'] == 1
        assert st['tenancy']['tenants']['t']['deadline_rejects'] == 1
        # a generous deadline admits against the same backlog
        ok = eng.submit(list(rng.randint(1, 128, 4)), max_new_tokens=4,
                        top_k=0, deadline_s=1e9)
        _drain(eng)
        assert ok.state == 'finished'
        assert all(b.state == 'finished' for b in backlog)
        eng.shutdown()


# ---------------------------------------------------------------------------
# charged priority preemption
# ---------------------------------------------------------------------------
class TestChargedPreemption:
    def test_high_priority_admit_preempts_below_and_pays(self, tiny_lm):
        clk = FakeClock()
        # pool sized so two running requests cannot BOTH grow: the
        # high-priority request's growth must preempt the low one
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=2, prefill_chunk=8,
            num_pages=3, max_pages_per_seq=3, clock=clk,
            degrade=False,
            tenants={'low': {'priority': 0},
                     'high': {'priority': 2,
                              'quota_tokens_per_s': 1000.0,
                              'burst_tokens': 1000.0}}))
        rng = np.random.RandomState(3)
        lo = eng.submit(list(rng.randint(1, 128, 8)),
                        max_new_tokens=12, top_k=0, tenant_id='low')
        for _ in range(3):
            eng.step()                          # lo occupies the pool
        hi = eng.submit(list(rng.randint(1, 128, 8)),
                        max_new_tokens=12, top_k=0, tenant_id='high')
        _drain(eng)
        assert lo.state == 'finished' and hi.state == 'finished'
        assert lo.preemptions >= 1              # lo was the victim
        assert hi.preemptions == 0              # never preempted upward
        st = eng.stats()
        assert st['preemptions_charged_total'] >= 1
        trow = st['tenancy']['tenants']['high']
        assert trow['preemptions_charged'] >= 1
        assert trow['charge_tokens'] >= 1
        # the charge debited high's bucket beyond its own bill
        assert trow['bucket_level'] < 1000.0 - trow['tokens_billed']
        ev = _events(eng, 'preempt', lo.id)
        assert ev and ev[0]['charged_to'] == 'high', ev
        assert ev[0]['charge_tokens'] >= 1
        eng.shutdown()


class TestYieldToHigherPriority:
    def test_low_priority_yields_instead_of_crashing(self, tiny_lm):
        # the pool cannot hold both requests; every other slot-holder
        # outranks the low request when ITS growth hits exhaustion —
        # the untenanted engine would preempt upward, the tenancy
        # rules forbid that, and raising PoolExhausted would kill the
        # serve loop. The low request must YIELD (re-queue) and finish
        # after the high one drains.
        # hi peaks at exactly 4 pages (32 tokens) and never shrinks;
        # lo's growth to its own 3rd/4th page hits exhaustion while hi
        # needs nothing — lo finds no victim at-or-below and must yield
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=2, prefill_chunk=8,
            num_pages=6, max_pages_per_seq=4, clock=FakeClock(),
            degrade=False,
            tenants={'hi': {'priority': 2}, 'lo': {'priority': 0}}))
        rng = np.random.RandomState(13)
        hi = eng.submit(list(rng.randint(1, 128, 24)),
                        max_new_tokens=8, top_k=0, tenant_id='hi')
        lo = eng.submit(list(rng.randint(1, 128, 8)),
                        max_new_tokens=17, top_k=0, tenant_id='lo')
        _drain(eng)                     # must not raise PoolExhausted
        assert hi.state == 'finished' and lo.state == 'finished'
        assert hi.preemptions == 0
        assert lo.preemptions >= 1
        ev = _events(eng, 'preempt', lo.id)
        assert any(e.get('reason') == 'yield_to_higher_priority'
                   for e in ev), ev
        eng.shutdown()


# ---------------------------------------------------------------------------
# degradation ladder in the engine (forced overload)
# ---------------------------------------------------------------------------
class TestEngineDegradation:
    def test_forced_overload_walks_all_stages_and_recovers(self, tiny_lm):
        clk = FakeClock()
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=2, prefill_chunk=16,
            num_pages=6, max_pages_per_seq=4, clock=clk,
            tenants={'a': {'weight': 0.2}, 'b': {'weight': 2.0}},
            degrade=True, degrade_window=2,
            degrade_up=(0.5, 0.7, 0.9), degrade_down=(0.2, 0.3, 0.4),
            degrade_hold=2))
        rng = np.random.RandomState(4)
        reqs = [eng.submit(list(rng.randint(1, 128, 8)),
                           max_new_tokens=8, top_k=0,
                           tenant_id='a' if i % 2 else 'b')
                for i in range(8)]              # deep queue, small pool
        _drain(eng)
        assert all(r.state == 'finished' for r in reqs)
        ups = [h for h in eng.ladder_history() if h['to'] > h['from']]
        assert [h['to'] for h in ups] == [1, 2, 3], \
            eng.ladder_history()                # all three, in order
        assert eng.pool._evict_weights is not None  # stage-3 lever on
        # stage-2 prefill shrink compiled a mixed program of the
        # halved chunk beside the configured one's
        assert {k[3] for k in eng._step_fns if k[0] == 'mixed'} == \
            {16, 8}, sorted(map(str, eng._step_fns))
        # every transition is a trace event with stage + pressure
        ev = _events(eng, 'degrade_stage')
        assert len(ev) == len(eng.ladder_history())
        assert [e['stage'] for e in ev[:3]] == [1, 2, 3]
        assert all('pressure' in e and 'stage_name' in e for e in ev)
        # pressure cleared: idle sweeps walk it back to 0 without
        # oscillation (monotone descent, hold-gated)
        for _ in range(20):
            eng.step()
        assert eng.degrade_stage() == 0
        assert eng.pool._evict_weights is None  # lever disarmed
        tos = [h['to'] for h in eng.ladder_history()]
        assert tos == sorted(tos[:3]) + sorted(tos[3:], reverse=True), \
            tos                                 # up 1,2,3 then down
        from paddle_tpu.serving.metrics import serve_snapshot
        eng.publish_metrics()
        assert serve_snapshot()['ptpu_serve_degrade_stage'] == 0
        eng.shutdown()

    def test_spec_shed_is_token_invariant(self, tiny_lm):
        # repetitive prompts so the n-gram proposer actually fires
        prompts = [[7, 8, 9] * 5, [3, 4] * 6]
        base = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=2, prefill_chunk=8, spec_k=4))
        ref = base.generate(prompts, max_new_tokens=8, top_k=0)
        assert base._spec_proposed > 0          # spec actually ran
        base.shutdown()
        # degrade_hold huge: the forced stage cannot walk back down
        # mid-run on the idle-looking pressure signal
        shed = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=2, prefill_chunk=8, spec_k=4,
            degrade=True, tenants={}, degrade_hold=10 ** 9))
        shed._ladder.stage = 1                  # force stage 1
        outs = shed.generate(prompts, max_new_tokens=8, top_k=0)
        assert shed._spec_proposed == 0         # drafts shed
        assert outs == ref                      # tokens identical
        shed.shutdown()

    def test_disagg_stage3_arms_both_pools(self, tiny_lm):
        # regression (ISSUE 16 satellite): the disaggregated pipeline
        # shares ONE ladder, but the observing engine used to arm the
        # stage-3 weighted-eviction lever only on its own pool — the
        # other side kept evicting pure-LRU under overload
        from paddle_tpu.serving.cluster.disagg import (
            DisaggregatedEngine)
        d = DisaggregatedEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=2, prefill_chunk=8,
            disaggregate=True, clock=FakeClock(),
            tenants={'a': {'weight': 0.2}, 'b': {'weight': 2.0}},
            degrade=True, degrade_window=1,
            degrade_up=(0.1, 0.2, 0.3),
            degrade_down=(0.01, 0.02, 0.03), degrade_hold=1))
        assert d.prefill._ladder is d.decode._ladder
        d.decode.pool.utilization = lambda: 0.95    # forced pressure
        for _ in range(3):
            d.decode._observe_pressure()
        assert d.decode.degrade_stage() == 3
        assert d.decode.pool._evict_weights is not None
        assert d.prefill.pool._evict_weights is not None
        # calm signal walks back down: BOTH levers disarm on 3 -> 2
        d.decode.pool.utilization = lambda: 0.0
        for _ in range(12):
            d.decode._observe_pressure()
        assert d.decode.degrade_stage() < 3
        assert d.decode.pool._evict_weights is None
        assert d.prefill.pool._evict_weights is None
        # symmetric: a PREFILL-side observation arms the decode pool
        d.prefill.pool.utilization = lambda: 0.95
        while d.prefill.degrade_stage() < 3:
            d.prefill._observe_pressure()
        assert d.prefill.pool._evict_weights is not None
        assert d.decode.pool._evict_weights is not None
        d.shutdown()


# ---------------------------------------------------------------------------
# no-tenant identity: default config is the PR-9 engine, bit for bit
# ---------------------------------------------------------------------------
class TestNoTenantIdentity:
    def test_outputs_and_compiled_shapes_unchanged(self, tiny_lm):
        rng = np.random.RandomState(5)
        prompts = [list(rng.randint(1, 128, n)) for n in (5, 11, 3)]
        seq = []
        for p in prompts:
            out = tiny_lm.generate(Tensor(np.asarray([p], 'int32')),
                                   max_new_tokens=6, top_k=0,
                                   use_cache=True)
            seq.append(np.asarray(out.data)[0].tolist())
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=3, prefill_chunk=8))
        assert eng._tenants is None and eng._ladder is None
        outs = eng.generate(prompts, max_new_tokens=6, top_k=0)
        assert outs == seq                      # greedy token identity
        # exactly the two untenanted compiled shapes: the mixed step
        # (B decode rows beside two chunk rows) and (B, 1) decode — no
        # ladder shapes, no extras
        assert set(eng._step_fns) == {('mixed', 3, 2, 8, False),
                                      (3, 1, False, False)}, \
            sorted(map(str, eng._step_fns))
        st = eng.stats()
        assert st['quota_deferrals_total'] == 0
        assert st['degrade_stage'] == 0
        assert st['tenancy']['enabled'] is False
        eng.shutdown()


# ---------------------------------------------------------------------------
# structured router rejection + tenancy forwarding (cluster)
# ---------------------------------------------------------------------------
class TestClusterTenancy:
    def _cluster(self, tiny_lm, max_queue=1, **router_kw):
        from paddle_tpu.serving.cluster import (ClusterRouter,
                                                LocalReplica)
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=2, prefill_chunk=8))
        rep = LocalReplica(eng, 'r0')
        router = ClusterRouter([rep], page_size=8, max_queue=max_queue,
                               **router_kw)
        return router, rep, eng

    @pytest.mark.slow
    def test_backpressure_reject_carries_retry_hint(self, tiny_lm):
        from paddle_tpu.serving.cluster import RouterRejected
        # refresh every submit so the hint sees the queued backlog
        router, rep, eng = self._cluster(tiny_lm, max_queue=1,
                                         refresh_interval_s=0.0)
        rng = np.random.RandomState(6)
        # warm the engine so a decode rate exists (the hint's input)
        eng.generate([list(rng.randint(1, 128, 4))], max_new_tokens=4,
                     top_k=0)
        router.submit(list(rng.randint(1, 128, 6)), max_new_tokens=8,
                      top_k=0)                  # fills the queue bound
        with pytest.raises(RouterRejected) as ei:
            router.submit(list(rng.randint(1, 128, 6)),
                          max_new_tokens=8, top_k=0)
        assert ei.value.reason == 'backpressure'
        assert ei.value.retry_after_s is not None
        assert ei.value.retry_after_s > 0
        assert router.snapshot()['rejects'] == 1
        router.run(timeout_s=120)
        router.shutdown()

    @pytest.mark.slow
    def test_engine_deadline_reject_passes_through_without_drain(
            self, tiny_lm):
        from paddle_tpu.serving.cluster import RouterRejected
        router, rep, eng = self._cluster(tiny_lm, max_queue=64)
        rng = np.random.RandomState(7)
        eng.generate([list(rng.randint(1, 128, 4))], max_new_tokens=4,
                     top_k=0)                   # decode rate observed
        router.submit(list(rng.randint(1, 128, 8)), max_new_tokens=16,
                      top_k=0)                  # backlog, unpumped
        with pytest.raises(RouterRejected) as ei:
            router.submit(list(rng.randint(1, 128, 4)),
                          max_new_tokens=4, top_k=0, deadline_s=1e-9)
        assert ei.value.reason == 'deadline_unmet'
        assert ei.value.retry_after_s > 0
        # a healthy replica refusing one deadline is NOT a hang
        assert router.healthy_replicas() == ['r0']
        assert not router.snapshot()['drain_events']
        router.run(timeout_s=120)
        router.shutdown()

    @pytest.mark.slow
    def test_tenant_opts_reach_engine_and_spills_account(self, tiny_lm):
        router, rep, eng = self._cluster(tiny_lm, max_queue=64)
        rng = np.random.RandomState(8)
        r = router.submit(list(rng.randint(1, 128, 4)),
                          max_new_tokens=4, top_k=0, tenant_id='gold',
                          priority=2)
        engine_req = rep._reqs[r.remote_rid]
        assert engine_req.tenant_id == 'gold'
        assert engine_req.priority == 2
        assert 'tenant_spills' in router.snapshot()
        router.run(timeout_s=120)
        assert r.done and len(r.tokens) == 4
        router.shutdown()

    @pytest.mark.slow
    def test_serve_backs_off_by_hint_and_completes(self, tiny_lm):
        router, rep, eng = self._cluster(tiny_lm, max_queue=2)
        rng = np.random.RandomState(9)
        prompts = [list(rng.randint(1, 128, 4)) for _ in range(6)]
        outs = router.serve(prompts, max_new_tokens=4, top_k=0,
                            timeout_s=300)
        assert [len(o) for o in outs] == [8] * 6
        assert router.snapshot()['requests_done'] == 6
        router.shutdown()


# ---------------------------------------------------------------------------
# the adversarial mix bar (ISSUE 15 acceptance)
# ---------------------------------------------------------------------------
class TestAdversarialMix:
    """One heavy tenant saturating the pool + N light tenants: light
    p99 e2e must hold within 1.5x of its solo baseline under the SLO
    scheduler, while aggregate decode throughput (tokens per engine
    sweep — the deterministic-clock stand-in for tokens/sec) stays
    within ~10% of FCFS on the same stream."""

    HEAVY_N, HEAVY_LEN, HEAVY_NEW = 6, 12, 16
    LIGHT_N, LIGHT_LEN, LIGHT_NEW = 6, 4, 4

    def _mk_prompts(self):
        rng = np.random.RandomState(10)
        heavy = [list(rng.randint(1, 128, self.HEAVY_LEN))
                 for _ in range(self.HEAVY_N)]
        light = [list(rng.randint(1, 128, self.LIGHT_LEN))
                 for _ in range(self.LIGHT_N)]
        return heavy, light

    def _run(self, tiny_lm, tenants, heavy, light):
        clk = FakeClock()
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=2, prefill_chunk=8, clock=clk,
            tenants=tenants))
        hreqs = [eng.submit(p, max_new_tokens=self.HEAVY_NEW, top_k=0,
                            tenant_id='heavy') for p in heavy]
        for _ in range(3):
            eng.step()          # heavy saturates the slots first
        lreqs = [eng.submit(p, max_new_tokens=self.LIGHT_NEW, top_k=0,
                            tenant_id=f'light{i % 3}')
                 for i, p in enumerate(light)]
        steps = _drain(eng)
        assert all(r.state == 'finished' for r in hreqs + lreqs)
        light_e2e = sorted(r.finish_time - r.submit_time
                           for r in lreqs)
        tokens = sum(len(r.generated) for r in hreqs + lreqs)
        eng.shutdown()
        # p99 over a small set = the max; steps+3 counts every sweep
        return light_e2e[-1], tokens / (steps + 3)

    def test_light_p99_holds_at_near_fcfs_throughput(self, tiny_lm):
        heavy, light = self._mk_prompts()
        # solo baseline: the light stream alone
        clk = FakeClock()
        solo = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=2, prefill_chunk=8, clock=clk))
        sreqs = [solo.submit(p, max_new_tokens=self.LIGHT_NEW, top_k=0)
                 for p in light]
        _drain(solo)
        solo_p99 = sorted(r.finish_time - r.submit_time
                          for r in sreqs)[-1]
        solo.shutdown()
        # FCFS: the untenanted scheduler on the adversarial stream
        fcfs_p99, fcfs_tps = self._run(tiny_lm, None, heavy, light)
        # SLO: lights outrank the heavy class
        ten = {'heavy': {'priority': 0},
               'light0': {'priority': 1}, 'light1': {'priority': 1},
               'light2': {'priority': 1}}
        slo_p99, slo_tps = self._run(tiny_lm, ten, heavy, light)
        # the bar: lights near solo, aggregate within ~10% of FCFS
        assert slo_p99 <= 1.5 * solo_p99, (slo_p99, solo_p99)
        assert slo_tps >= 0.9 * fcfs_tps, (slo_tps, fcfs_tps)
        # and the scheduler actually mattered: FCFS starved the lights
        assert fcfs_p99 > slo_p99, (fcfs_p99, slo_p99)


# ---------------------------------------------------------------------------
# schema v3 export round-trip from a tenanted engine
# ---------------------------------------------------------------------------
class TestTenantTraceExport:
    @pytest.mark.slow
    def test_v3_roundtrip_carries_tenant_columns(self, tiny_lm,
                                                 tmp_path):
        from paddle_tpu.serving.request_trace import (load_trace,
                                                      reconstruct)
        clk = FakeClock()
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=2, prefill_chunk=8, clock=clk,
            tenants={'bulk': {'priority': 0,
                              'quota_tokens_per_s': 1.0,
                              'burst_tokens': 10.0},
                     'gold': {'priority': 2}}))
        rng = np.random.RandomState(12)
        reqs = [eng.submit(list(rng.randint(1, 128, 4)),
                           max_new_tokens=4, top_k=0, tenant_id=tid)
                for tid in ('bulk', 'bulk', 'gold')]
        for _ in range(4):
            eng.step()
        clk.now += 30.0
        _drain(eng)
        path = str(tmp_path / 'tenants.jsonl')
        eng.export_trace(jsonl_path=path)
        header, events = load_trace(path)
        assert header['schema'] == 'paddle_tpu.serve_trace/6'
        table = reconstruct(events)
        assert table[reqs[2].id]['tenant_id'] == 'gold'
        assert table[reqs[2].id]['priority'] == 2
        assert table[reqs[1].id]['quota_defers'] == 1
        assert reqs[1].id not in [e['req'] for e in events
                                  if e['event'] == 'degrade_stage']
        # engine-scope rows never appear in the per-request table
        assert all(k >= 0 for k in table)
        eng.shutdown()
