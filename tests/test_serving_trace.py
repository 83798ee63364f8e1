"""Serving request observatory (ISSUE 6): per-request lifecycle
tracing with deterministic-clock event ordering across preempt/resume,
Histogram bucket-interpolated percentiles vs a numpy oracle, scheduler
timeline, stalled-request watchdog report schema, and the zero-extra-
host-syncs contract for the decode hot path."""
import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import monitor
from paddle_tpu.serving import (RequestState, ServingConfig,
                                ServingEngine, load_trace, reconstruct)
from paddle_tpu.serving import engine as engine_mod
from paddle_tpu.serving import metrics as serve_metrics
from paddle_tpu.serving.request_trace import RequestTracer


# ---------------------------------------------------------------------------
# Histogram percentiles (core.monitor) vs numpy oracle
# ---------------------------------------------------------------------------
class TestHistogramPercentiles:
    def test_vs_numpy_oracle(self):
        rng = np.random.RandomState(0)
        vals = rng.gamma(2.0, 0.05, 2000)        # skewed, latency-like
        edges = [float(b) for b in np.linspace(0.0, 1.0, 101)[1:]]
        h = monitor.Histogram('t_pct_oracle', buckets=edges)
        for v in vals:
            h.observe(float(v))
        width = edges[1] - edges[0]
        for q in (50, 90, 99):
            est = h.percentile(q)
            ref = np.percentile(vals, q)
            # bucket interpolation is exact to within one bucket width
            assert abs(est - ref) <= width + 1e-12, (q, est, ref)

    def test_uniform_interpolation_exact(self):
        # 10 observations at 0.5, 1.5, ..., 9.5 with unit buckets:
        # uniform-within-bucket interpolation is exact at every decile
        h = monitor.Histogram('t_pct_uniform',
                              buckets=[float(i) for i in range(1, 11)])
        for i in range(10):
            h.observe(i + 0.5)
        assert abs(h.percentile(50) - 5.0) < 1e-12
        assert abs(h.percentile(90) - 9.0) < 1e-12
        assert abs(h.percentile(10) - 1.0) < 1e-12

    def test_edges_and_inf_bucket(self):
        h = monitor.Histogram('t_pct_edges', buckets=[1.0, 2.0])
        assert h.percentile(50) is None          # empty
        h.observe(100.0)                         # lands in +Inf only
        # the estimator can't see past the last finite boundary
        assert h.percentile(99) == 2.0
        with pytest.raises(ValueError):
            h.percentile(101)
        p = h.percentiles((50, 90, 99))
        assert set(p) == {'p50', 'p90', 'p99'}

    def test_snapshot_carries_percentiles(self):
        monitor.metrics().reset()
        serve_metrics.publish({
            'pool': {}, '_new_ttfts_s': [0.02, 0.04, 0.2],
            '_new_slo': {'queue_wait_s': [0.001], 'tpot_s': [0.003],
                         'e2e_s': [0.5], 'preemptions': [2]},
            'timeline': {'iterations': 3, 'window': 3},
        })
        snap = serve_metrics.serve_snapshot()
        ttft = snap['ptpu_serve_ttft_seconds']
        assert ttft['count'] == 3
        assert ttft['p50_ms'] is not None and ttft['p99_ms'] is not None
        assert ttft['p50_ms'] <= ttft['p90_ms'] <= ttft['p99_ms']
        assert snap['ptpu_serve_tpot_seconds']['count'] == 1
        assert snap['ptpu_serve_preemptions_per_request']['p99'] >= 1.0
        assert snap['timeline']['iterations'] == 3
        # the deprecated ptpu_serve_ttft_ms mean gauge is GONE (its
        # one-release grace ended with ISSUE 7) — percentiles only
        assert 'ptpu_serve_ttft_ms' not in snap


# ---------------------------------------------------------------------------
# engine fixtures: tiny model + deterministic clock
# ---------------------------------------------------------------------------
@pytest.fixture(scope='module')
def tiny_lm():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(7)
    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                    num_heads=2, max_seq_len=128, hidden_dropout=0.0,
                    attn_dropout=0.0, use_flash_attention=False)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


@pytest.fixture(scope='module')
def mixed_prompts():
    rng = np.random.RandomState(3)
    return [list(rng.randint(1, 128, n)) for n in (5, 11, 3, 17, 8)]


def _fake_clock(step=0.001):
    """Deterministic strictly-increasing clock; returns (clock, state)
    — bump state['now'] to jump time (watchdog tests)."""
    state = {'now': 0.0}

    def clock():
        state['now'] += step
        return state['now']
    return clock, state


# ---------------------------------------------------------------------------
# lifecycle tracing
# ---------------------------------------------------------------------------
class TestRequestTracing:
    def test_event_ordering_across_preempt_resume(self, tiny_lm,
                                                  mixed_prompts):
        clock, _ = _fake_clock()
        # 4 pages of 8 can't hold the concurrent contexts: preemption
        # and resume must show up in the journals, in causal order
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=3, prefill_chunk=8, num_pages=4,
            clock=clock))
        eng.generate(mixed_prompts, max_new_tokens=6, top_k=0)
        assert eng.stats()['preemptions_total'] > 0
        preempted = [r for r in eng.scheduler.finished if r.preemptions]
        assert preempted
        for req in eng.scheduler.finished:
            evs = eng.tracer.events(req.id)
            names = [e['event'] for e in evs]
            times = [e['t'] for e in evs]
            assert times == sorted(times), names
            assert names[0] == 'submit' and names[-1] == 'retire'
            assert names[1] == 'admit'
            assert 'first_token' in names
            # a preempt is always followed by a resume (never a second
            # admit), and the request still retires
            for i, n in enumerate(names):
                if n == 'preempt':
                    later = names[i + 1:]
                    assert 'resume' in later, names
                    assert 'admit' not in later, names
            assert names.count('preempt') == req.preemptions
            assert names.count('resume') == req.preemptions
        eng.shutdown()

    def test_reconstruction_matches_engine_exactly(self, tiny_lm,
                                                   mixed_prompts):
        clock, _ = _fake_clock()
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=3, prefill_chunk=8, num_pages=4,
            clock=clock))
        outs = eng.generate(mixed_prompts, max_new_tokens=6, top_k=0)
        table = eng.request_table()
        assert len(table) == len(mixed_prompts)
        for req, out in zip(sorted(eng.scheduler.finished,
                                   key=lambda r: r.id), outs):
            r = table[req.id]
            assert r['prompt_tokens'] == len(req.prompt)
            assert r['tokens_generated'] == len(req.generated)
            assert r['preemptions'] == req.preemptions
            assert r['state'] == 'finished'
            # timestamps are the engine's own stamps — exact equality
            assert r['ttft_s'] == req.first_token_time - req.submit_time
            assert r['queue_wait_s'] == (req.admit_time
                                         - req.submit_time)
            assert r['e2e_s'] == req.finish_time - req.submit_time
            if len(req.generated) > 1:
                # same formula engine._observe_slo feeds the histogram
                assert r['tpot_s'] == (
                    (req.finish_time - req.first_token_time)
                    / (len(req.generated) - 1))
            assert r['pages_high_water'] >= 1
        eng.shutdown()

    def test_jsonl_roundtrip_and_chrome_export(self, tiny_lm,
                                               mixed_prompts, tmp_path):
        import paddle_tpu.profiler as prof
        clock, _ = _fake_clock()
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=3, prefill_chunk=8, clock=clock))
        # record the engine-phase spans so the chrome export carries
        # both requests (tracks) and serve::* steps
        p = prof.Profiler(scheduler=None, timer_only=True)
        p.start()
        eng.generate(mixed_prompts[:3], max_new_tokens=4, top_k=0)
        jsonl = str(tmp_path / 'serve.jsonl')
        chrome = str(tmp_path / 'serve.trace.json')
        paths = eng.export_trace(jsonl_path=jsonl, chrome_path=chrome)
        p.stop()

        header, events = load_trace(paths['jsonl'])
        assert header['schema'] == 'paddle_tpu.serve_trace/6'
        assert header['dropped_events'] == 0
        # JSON round trip preserves the reconstruction bit-for-bit
        assert reconstruct(events) == eng.request_table()

        with open(paths['chrome']) as f:
            doc = json.load(f)
        evs = doc['traceEvents']
        # structurally Perfetto-loadable: X events with ts/dur plus
        # process/thread metadata; one track (virtual tid) per request
        req_tids = {e['tid'] for e in evs
                    if e.get('cat') == 'serve_request'}
        assert len(req_tids) == 3
        assert all(('ts' in e and 'dur' in e) for e in evs
                   if e.get('ph') == 'X')
        tnames = [e for e in evs if e.get('name') == 'thread_name']
        assert any(e['args']['name'].startswith('req ')
                   for e in tnames)
        # request tracks group under their own named pseudo-process,
        # beside the host process carrying the engine spans
        pnames = {e['args']['name'] for e in evs
                  if e.get('name') == 'process_name'}
        assert 'serving requests' in pnames and len(pnames) == 2
        assert any(e.get('cat') == 'serve' for e in evs), \
            'engine serve::* phase spans missing from chrome export'
        eng.shutdown()

    def test_journal_caps_bound_memory(self, tiny_lm):
        clock, _ = _fake_clock()
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=2, prefill_chunk=8,
            trace_events_per_request=4, trace_requests=2, clock=clock))
        eng.generate([[1, 2, 3], [4, 5], [6, 7, 8]], max_new_tokens=5,
                     top_k=0)
        for tr in eng.tracer.traces():
            assert len(tr.events) <= 4
            # the terminal event survives the cap (an interior event
            # is evicted instead), so reconstruction keeps end state,
            # e2e and the authoritative token count
            assert tr.events[-1]['event'] == 'retire'
        assert sum(tr.dropped for tr in eng.tracer.traces()) > 0
        assert len(eng.tracer.traces()) == 2       # retired ring cap
        assert eng.tracer.dropped_requests == 1
        for r in eng.request_table().values():
            assert r['state'] == 'finished'
            assert r['tokens_generated'] == 5
            assert r['e2e_s'] is not None
        eng.shutdown()

    def test_trace_off_engine_still_serves(self, tiny_lm):
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=2, prefill_chunk=8,
            trace=False))
        outs = eng.generate([[1, 2, 3]], max_new_tokens=3, top_k=0)
        assert len(outs[0]) == 6
        assert eng.request_table() == {}
        with pytest.raises(RuntimeError, match='tracing is off'):
            eng.export_trace(jsonl_path='/tmp/nope.jsonl')
        eng.shutdown()


# ---------------------------------------------------------------------------
# scheduler timeline
# ---------------------------------------------------------------------------
class TestSchedulerTimeline:
    def test_timeline_records_batch_composition(self, tiny_lm,
                                                mixed_prompts):
        clock, _ = _fake_clock()
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=3, prefill_chunk=8, num_pages=4,
            clock=clock))
        eng.generate(mixed_prompts, max_new_tokens=6, top_k=0)
        rows = eng.timeline.snapshot()
        st = eng.stats()
        assert len(rows) == eng.timeline.iterations
        assert [r['iter'] for r in rows] == list(range(len(rows)))
        # the timeline's token/admission/preemption sums are the
        # engine's own totals, re-derived per iteration
        assert sum(r['decode_tokens'] for r in rows) == \
            st['decode_tokens_total']
        assert sum(r['prefill_tokens'] for r in rows) == \
            st['prefill_tokens_total']
        assert sum(r['preemptions'] for r in rows) == \
            st['preemptions_total']
        assert sum(r['admissions'] for r in rows) == \
            len(mixed_prompts) + st['preemptions_total']
        assert all(0 <= r['pool_pages_in_use'] <= r['pool_pages_total']
                   for r in rows)
        summ = eng.timeline.summary()
        assert summ['iterations'] == len(rows)
        assert 0 < summ['mean_occupancy'] <= 1
        assert summ['preemptions'] == st['preemptions_total']
        eng.shutdown()

    def test_ring_capacity(self, tiny_lm):
        clock, _ = _fake_clock()
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=2, prefill_chunk=8,
            timeline_capacity=4, clock=clock))
        eng.generate([[1, 2, 3], [4, 5]], max_new_tokens=6, top_k=0)
        assert eng.timeline.iterations > 4
        assert len(eng.timeline.snapshot()) == 4
        assert len(eng.timeline.tail(2)) == 2
        eng.shutdown()


# ---------------------------------------------------------------------------
# stalled-request watchdog
# ---------------------------------------------------------------------------
class TestStalledWatchdog:
    def test_report_schema_and_once_semantics(self, tiny_lm, tmp_path):
        clock, state = _fake_clock()
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=2, prefill_chunk=8,
            request_deadline_s=5.0, report_dir=str(tmp_path),
            clock=clock))
        req = eng.submit([1, 2, 3], max_new_tokens=4)
        state['now'] += 10.0              # age past the deadline
        eng.step()
        report = eng.last_serve_report
        assert report is not None
        assert report['kind'] == 'serve_report'
        assert report['schema'] == 'paddle_tpu.serve_trace/6'
        assert report['request']['req'] == req.id
        assert report['request']['age_s'] > 5.0
        assert report['request']['deadline_s'] == 5.0
        assert {'trace', 'timeline_tail', 'pool', 'pool_census',
                'engine'} <= set(report)
        assert any(e['event'] == 'submit' for e in report['trace'])
        assert report['pool']['num_pages'] == eng.pool.num_pages
        path = report['path']
        assert path and os.path.exists(path)
        with open(path) as f:
            assert json.load(f)['kind'] == 'serve_report'
        # one report per request: draining does not re-report
        eng.last_serve_report = None
        while eng.scheduler.has_work:
            eng.step()
        assert eng.last_serve_report is None
        assert req.state == RequestState.FINISHED
        eng.shutdown()

    def test_deadline_abort_action(self, tiny_lm, tmp_path):
        clock, state = _fake_clock()
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=2, prefill_chunk=8,
            request_deadline_s=5.0, deadline_action='abort',
            report_dir=str(tmp_path), clock=clock))
        reqs = [eng.submit(p, max_new_tokens=4)
                for p in ([1, 2, 3], [4, 5])]
        state['now'] += 10.0
        while eng.scheduler.has_work:
            eng.step()
        # both requests were older than the deadline: aborted, pages
        # released, journals closed with an abort event
        assert all(r.state == RequestState.ABORTED for r in reqs)
        assert eng.pool.pages_in_use == 0
        assert eng.stats()['requests_aborted_total'] == 2
        for r in reqs:
            evs = [e['event'] for e in eng.tracer.events(r.id)]
            assert evs[-1] == 'abort'
            assert eng.request_table()[r.id]['state'] == 'aborted'
        eng.shutdown()

    def test_abort_is_terminal_idempotent(self, tiny_lm):
        clock, _ = _fake_clock()
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=2, prefill_chunk=8,
            clock=clock))
        req = eng.submit([1, 2, 3], max_new_tokens=3)
        while eng.scheduler.has_work:
            eng.step()
        assert req.state == RequestState.FINISHED
        finish = req.finish_time
        n_slo = len(eng._new_slo['e2e_s']) + \
            sum(1 for _ in eng.scheduler.finished)
        # aborting a retired request is a no-op: no double count, no
        # restamped finish_time, no duplicate SLO samples
        assert eng.abort(req) is False
        assert eng.abort(req) is False
        assert req.state == RequestState.FINISHED
        assert req.finish_time == finish
        assert eng.stats()['requests_aborted_total'] == 0
        assert eng.scheduler.finished.count(req) == 1
        assert len(eng._new_slo['e2e_s']) + \
            sum(1 for _ in eng.scheduler.finished) == n_slo
        eng.shutdown()

    def test_no_deadline_no_reports(self, tiny_lm):
        clock, state = _fake_clock()
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=2, prefill_chunk=8,
            clock=clock))
        eng.submit([1, 2, 3], max_new_tokens=2)
        state['now'] += 1e6
        while eng.scheduler.has_work:
            eng.step()
        assert eng.last_serve_report is None
        eng.shutdown()


# ---------------------------------------------------------------------------
# the observability tax: zero extra host syncs in the decode hot path
# ---------------------------------------------------------------------------
class TestSyncBudget:
    def _count_fetches(self, tiny_lm, prompts, trace, monkeypatch):
        counts = [0]
        real = engine_mod._host_fetch

        def counting(x):
            counts[0] += 1
            return real(x)
        monkeypatch.setattr(engine_mod, '_host_fetch', counting)
        try:
            eng = ServingEngine(tiny_lm, ServingConfig(
                page_size=8, max_batch_size=3, prefill_chunk=8,
                num_pages=4, trace=trace))
            outs = eng.generate(prompts, max_new_tokens=6, top_k=0)
            st = eng.stats()
            eng.shutdown()
        finally:
            monkeypatch.setattr(engine_mod, '_host_fetch', real)
        return counts[0], outs, st

    def test_tracing_adds_no_host_syncs(self, tiny_lm, mixed_prompts,
                                        monkeypatch):
        """Every host sync the engine performs funnels through
        engine._host_fetch (the PR-3/4 convention); the full
        observatory — journals, timeline, SLO accounting, watchdog
        sweep — must not add a single one."""
        n_off, outs_off, st_off = self._count_fetches(
            tiny_lm, mixed_prompts, False, monkeypatch)
        n_on, outs_on, st_on = self._count_fetches(
            tiny_lm, mixed_prompts, True, monkeypatch)
        assert outs_on == outs_off          # identical serving results
        assert n_on == n_off, (n_on, n_off)
        # and the budget is one fetch per token-yielding dispatch:
        # each batched decode step fetches once (len(active) tokens,
        # and the first token of a prompt whose last chunk rode the
        # same program); a completed prefill fetches its first token
        # itself only where no decode row rode beside it — at most
        # every generated token NOT accounted to a decode step
        generated = sum(len(o) - len(p)
                        for o, p in zip(outs_on, mixed_prompts))
        prefill_fetches = generated - st_on['decode_tokens_total']
        assert st_on['decode_steps_total'] <= n_on <= min(
            st_on['decode_steps_total'] + prefill_fetches,
            st_on['dispatches_total']), (n_on, st_on)
