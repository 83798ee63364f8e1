"""Serving goodput ledger & decode roofline observatory (ISSUE 17):
ordered-clamp iteration-wall decomposition, the exact
delivered + wasted == emitted goodput identity across preemption /
speculative rejection / degrade shed / cluster drain-resubmit,
trace-v4 per-request pricing parity, the per-generation HBM peak
table (never faked on CPU), registry lifecycle, and the zero-extra-
host-syncs budget."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.serving import engine as engine_mod
from paddle_tpu.serving import ledger as ledger_mod
from paddle_tpu.serving.ledger import (HBM_GBPS, ServeLedger,
                                       render_serve_ledger,
                                       resolve_peak_hbm_gbps,
                                       serve_ledger_snapshot)


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
    return [list(rng.randint(1, 128, int(n)))
            for n in (11, 5, 17, 8, 23, 6)]


@pytest.fixture
def clean_registry(monkeypatch):
    """Isolate the module ledger registry so engines leaked by other
    test files can't bleed into snapshot assertions."""
    monkeypatch.setattr(ledger_mod, '_ledgers', {})


# ---------------------------------------------------------------------------
# ServeLedger units: ordered clamps, goodput counters, lifecycle
# ---------------------------------------------------------------------------
class TestServeLedgerUnits:
    def test_ordered_clamp_components_sum_to_wall(self, clean_registry):
        led = ServeLedger(engine='u0')
        led.observe_iteration(wall=0.010, compute=0.004,
                              host_fetch=0.002, schedule=0.001)
        a = led.account()
        c = a['components']
        assert c['compute'] == pytest.approx(0.004)
        assert c['host_fetch'] == pytest.approx(0.002)
        assert c['schedule'] == pytest.approx(0.001)
        assert c['page_stream'] == 0.0
        assert c['residue'] == pytest.approx(0.003)
        assert sum(c.values()) == pytest.approx(a['wall_seconds'])
        assert a['reconciled_fraction'] == pytest.approx(1.0)
        assert a['iterations'] == 1

    def test_overrun_clamps_in_order_and_flags(self, clean_registry):
        # measured compute alone exceeds the wall: later components
        # clamp to zero, residue stays zero (never negative), and
        # reconciled_fraction > 1 surfaces the overrun instead of
        # silently eating it
        led = ServeLedger(engine='u1')
        led.observe_iteration(wall=0.010, compute=0.020,
                              host_fetch=0.004, schedule=0.002)
        a = led.account()
        c = a['components']
        assert c['compute'] == pytest.approx(0.010)
        assert c['host_fetch'] == 0.0 and c['schedule'] == 0.0
        assert c['residue'] == 0.0
        assert a['reconciled_fraction'] == pytest.approx(2.6)
        # raw means stay visible so the clamp is diagnosable
        assert a['measured']['compute'] == pytest.approx(0.020)
        assert a['measured']['host_fetch'] == pytest.approx(0.004)

    def test_page_stream_folds_into_next_iteration(self,
                                                   clean_registry):
        led = ServeLedger(engine='u2')
        led.note_page_stream(0.5)
        led.note_page_stream(0.25)     # accumulates until observed
        led.observe_iteration(wall=2.0, compute=0.5)
        led.observe_iteration(wall=2.0, compute=0.5)  # nothing pending
        a = led.account()
        assert a['components']['page_stream'] == pytest.approx(0.375)
        assert led._pending_stream == 0.0

    def test_goodput_identity_and_per_tenant(self, clean_registry):
        led = ServeLedger(engine='u3')
        led.account_prefill(5, 2, tenant_id='a')
        led.account_decode(3, 1, tenant_id='b')
        led.account_spec_shed(4)
        g = led.goodput()
        assert g['emitted_tokens'] == 11
        assert g['delivered_tokens'] == 8
        assert g['wasted_tokens'] == 3
        assert g['delivered_tokens'] + g['wasted_tokens'] \
            == g['emitted_tokens']
        assert g['wasted_by_cause'] == {'preempt_recompute': 2,
                                        'spec_rejected': 1,
                                        'drain_recompute': 0}
        # shed capacity sits OUTSIDE the identity: never computed
        assert g['spec_shed_tokens'] == 4
        assert g['goodput_fraction'] == pytest.approx(8 / 11)
        assert g['per_tenant'] == {
            'a': {'delivered_tokens': 5, 'wasted_tokens': 2},
            'b': {'delivered_tokens': 3, 'wasted_tokens': 1}}

    def test_reset_zeroes_everything(self, clean_registry):
        led = ServeLedger(engine='u4')
        led.observe_iteration(wall=1.0, compute=0.5)
        led.account_prefill(5, 2)
        led.account_spec_shed(3)
        led.reset()
        assert led.account() is None
        g = led.goodput()
        assert g['emitted_tokens'] == 0 and g['spec_shed_tokens'] == 0
        assert g['goodput_fraction'] is None

    def test_registry_latest_wins_and_unregister(self, clean_registry):
        assert serve_ledger_snapshot() is None
        l1 = ServeLedger(engine='site_x')
        l2 = ServeLedger(engine='site_x')   # newer engine, same site
        l2.observe_iteration(wall=1.0, compute=0.25)
        l1.unregister()                     # stale: must NOT evict l2
        snap = serve_ledger_snapshot()
        assert snap is not None
        assert snap['ledger']['site_x']['wall_seconds'] \
            == pytest.approx(1.0)
        l2.unregister()
        assert serve_ledger_snapshot() is None
        l2.unregister()                     # idempotent

    def test_render(self, clean_registry):
        led = ServeLedger(engine='site_r')
        led.observe_iteration(wall=0.010, compute=0.006,
                              host_fetch=0.001)
        led.account_prefill(10, 4, tenant_id='t0')
        led.account_spec_shed(2)
        text = render_serve_ledger(serve_ledger_snapshot())
        assert 'engine: site_r' in text
        assert 'residue' in text and 'page_stream' in text
        assert 'goodput: 10 delivered / 4 wasted of 14 emitted' in text
        assert 'preempt_recompute=4' in text
        assert 'spec capacity shed' in text
        assert 'tenant t0' in text
        led.unregister()


# ---------------------------------------------------------------------------
# HBM peak table — never faked off-TPU
# ---------------------------------------------------------------------------
class TestPeakTable:
    @pytest.mark.parametrize('kind,peak', [
        ('TPU v6e', 1638.0), ('Trillium', 1638.0), ('TPU v5p', 2765.0),
        ('TPU v5 lite', 819.0), ('TPU v5e', 819.0), ('TPU v4', 1228.0),
        ('TPU v3', 900.0), ('TPU v2', 700.0)])
    def test_known_generations(self, kind, peak):
        assert resolve_peak_hbm_gbps(kind) == peak

    def test_non_tpu_and_unknown_are_none(self):
        assert resolve_peak_hbm_gbps('cpu') is None
        assert resolve_peak_hbm_gbps('Radeon') is None
        assert resolve_peak_hbm_gbps('TPU v99') is None
        # the local device in this suite is CPU: no peak, no MBU
        assert resolve_peak_hbm_gbps() is None

    def test_table_entries_positive(self):
        assert all(p > 0 for _s, p in HBM_GBPS)


# ---------------------------------------------------------------------------
# roofline: analytic bytes-moved model, MBU/MFU only against real peaks
# ---------------------------------------------------------------------------
class TestRoofline:
    def test_decode_bytes_model_and_mbu(self, clean_registry):
        led = ServeLedger(engine='rf0', param_bytes=1000,
                          kv_bytes_per_token=10, peak_hbm_gbps=100.0)
        led.observe_iteration(wall=0.01, compute=0.008,
                              decode_seconds=0.004, kv_read_tokens=50)
        led.observe_iteration(wall=0.01, compute=0.008,
                              decode_seconds=0.004, kv_read_tokens=150)
        r = led.roofline()
        # bytes/iter = params + mean(kv tokens read) * bytes/token
        assert r['decode_bytes_per_iteration'] == pytest.approx(
            1000 + 100 * 10)
        gbps = 2000 / 0.004 / 1e9
        assert r['hbm_gbps'] == pytest.approx(gbps)
        assert r['mbu'] == pytest.approx(gbps / 100.0)
        led.unregister()

    def test_mbu_none_without_peak(self, clean_registry):
        # CPU dryrun: resolve_peak_hbm_gbps() is None here, so the
        # ledger reports absolute GB/s with mbu None — never a faked %
        led = ServeLedger(engine='rf1', param_bytes=64,
                          kv_bytes_per_token=4)
        led.observe_iteration(wall=0.01, decode_seconds=0.002,
                              kv_read_tokens=16)
        r = led.roofline()
        assert r['hbm_gbps'] > 0.0
        assert r['peak_hbm_gbps'] is None and r['mbu'] is None
        led.unregister()

    def test_prefill_tflops_and_mfu(self, clean_registry):
        led = ServeLedger(engine='rf2', n_params=10 ** 6, layers=2,
                          hidden=64, peak_tflops=1.0)
        led.observe_iteration(wall=0.05, prefill_tokens=32,
                              prefill_seconds=0.01,
                              prefill_ctx_tokens=32 * 20)
        r = led.roofline()
        from paddle_tpu.core.ledger import model_flops_per_step
        total, _ = model_flops_per_step(10 ** 6, 32, layers=2,
                                        hidden=64, seq_len=20)
        assert r['prefill_model_flops'] == pytest.approx(total / 3.0)
        assert r['prefill_tflops'] == pytest.approx(
            total / 3.0 / 0.01 / 1e12)
        assert r['prefill_mfu'] == pytest.approx(r['prefill_tflops'])
        led.unregister()

    def test_paged_live_page_share_is_the_ratio_of_the_sums(
            self, clean_registry):
        led = ServeLedger(engine='rf4', kv_bytes_per_token=4)
        led.observe_iteration(wall=0.01, decode_seconds=0.002,
                              kv_read_tokens=16, paged_live_pages=3,
                              paged_page_slots=16)
        led.observe_iteration(wall=0.01, decode_seconds=0.002,
                              kv_read_tokens=16, paged_live_pages=5,
                              paged_page_slots=16)
        r = led.roofline()
        assert (r['paged_live_pages'], r['paged_page_slots']) == (8, 32)
        assert r['paged_live_page_share'] == 0.25
        led.reset()
        assert led.paged_page_slots == 0 and led.roofline() is None
        led.unregister()

    def test_none_before_any_dispatch(self, clean_registry):
        led = ServeLedger(engine='rf3')
        assert led.roofline() is None
        led.observe_iteration(wall=0.01, compute=0.005)  # sched-only
        assert led.roofline() is None
        led.unregister()


# ---------------------------------------------------------------------------
# the real engine: identity under preemption + spec, trace-v4 parity,
# ledger reconciliation, host-bound fraction, snapshot lifecycle
# ---------------------------------------------------------------------------
class TestPagedLivePageShare:
    @pytest.mark.parametrize('fused_k', [1, 4])
    def test_share_matches_the_requests_context_lengths(self, tiny_lm,
                                                        fused_k):
        # two requests admitted together, each prompt one chunk: the
        # mixed program (its prefill group's two rows, its decode group
        # idle) runs once over both prompts, then the [B, 1] decode
        # program (or the fused window's scan)
        # runs while either still owes tokens; request r's row holds
        # context L_r + t at its t-th decode iteration
        ps, B, P = 8, 4, 6
        prompts, new = [list(range(1, 12)), list(range(1, 20))], [9, 4]
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=ps, max_batch_size=B, prefill_chunk=32,
            max_pages_per_seq=P, prefix_cache=False, fused_k=fused_k))
        reqs = [eng.submit(p, max_new_tokens=n, top_k=0)
                for p, n in zip(prompts, new)]
        while not all(r.done for r in reqs):
            eng.step()
        live = sum(-(-len(p) // ps) for p in prompts)
        live += sum(-(-(len(p) + t) // ps)
                    for p, n in zip(prompts, new) for t in range(1, n))
        roof = eng.ledger.roofline()
        assert roof['paged_live_pages'] == live
        # every dispatched program carried whole tables: rows x P,
        # once per scan iteration of a fused window
        assert roof['paged_page_slots'] % P == 0
        assert eng.stats()['prefill_rows_per_dispatch'] == 2
        decode_programs = (roof['paged_page_slots']
                           - (B + 2) * P) // (B * P)
        if fused_k == 1:
            assert decode_programs == max(new) - 1
        assert max(new) - 1 <= decode_programs <= fused_k * max(new)
        assert 0 < roof['paged_live_page_share'] <= 1
        assert roof['paged_live_page_share'] == \
            live / roof['paged_page_slots']
        from paddle_tpu.serving.ledger import serve_ledger_snapshot
        snap = serve_ledger_snapshot()['roofline'][eng.ledger_site]
        assert snap['paged_live_page_share'] == \
            roof['paged_live_page_share']
        eng.shutdown()


class TestEngineGoodput:
    def test_baseline_matches_scheduler_ground_truth(self, tiny_lm,
                                                     mixed_prompts):
        # ample pool, no spec, no cache: every prompt position is
        # computed exactly once and every decode column lands — the
        # ledger must price delivered = sum(P_i + N_i - 1) (the first
        # token rides the final prefill column) and wasted = 0
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=3, prefill_chunk=16,
            prefix_cache=False))
        outs = eng.generate(mixed_prompts, max_new_tokens=6, top_k=0)
        assert eng.stats()['preemptions_total'] == 0
        g = eng.ledger.goodput()
        expect = sum(len(p) + (len(o) - len(p)) - 1
                     for p, o in zip(mixed_prompts, outs))
        assert g['delivered_tokens'] == expect, g
        assert g['wasted_tokens'] == 0 and g['spec_shed_tokens'] == 0
        assert g['emitted_tokens'] == expect
        eng.shutdown()

    def test_identity_under_preemption_and_spec_with_trace_parity(
            self, tiny_lm):
        # 4-page pool forces preempt/resume; repetitive prompts make
        # the n-gram proposer fire so drafts get rejected; the identity
        # must hold EXACTLY and the v4 trace must price every request
        # to the same delivered/wasted totals the engine charged
        from paddle_tpu.serving.request_trace import (load_trace,
                                                      reconstruct)
        import tempfile
        import os
        prompts = [[7, 8, 9] * 5, [3, 4] * 6, [5, 6, 7] * 6,
                   [9, 2] * 7]
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=3, prefill_chunk=8,
            num_pages=4, spec_k=4, trace=True))
        eng.generate(prompts, max_new_tokens=8, top_k=0)
        st = eng.stats()
        assert st['preemptions_total'] > 0       # pressure actually hit
        assert eng._spec_proposed > 0            # spec actually ran
        g = eng.ledger.goodput()
        assert g['delivered_tokens'] + g['wasted_tokens'] \
            == g['emitted_tokens']
        assert g['wasted_by_cause']['preempt_recompute'] > 0
        assert g['wasted_by_cause']['spec_rejected'] \
            >= eng._spec_proposed - eng._spec_accepted
        # trace ground truth: per-request v4 pricing sums to the
        # engine's lifetime account
        with tempfile.TemporaryDirectory() as td:
            p = os.path.join(td, 'serve.jsonl')
            eng.export_trace(jsonl_path=p)
            header, events = load_trace(p)
        assert header['schema'] == 'paddle_tpu.serve_trace/6'
        table = reconstruct(events)
        assert sum(r['delivered_tokens'] for r in table.values()) \
            == g['delivered_tokens']
        assert sum(r['wasted_tokens'] for r in table.values()) \
            == g['wasted_tokens']
        assert sum(r['recompute_tokens'] for r in table.values()) \
            == g['wasted_by_cause']['preempt_recompute']
        eng.shutdown()

    def test_degrade_shed_priced_outside_identity(self, tiny_lm):
        # forced stage 1 with spec configured on: drafts are shed, so
        # nothing spec-related is computed — shed capacity is reported
        # beside the identity, never inside wasted
        prompts = [[7, 8, 9] * 5, [3, 4] * 6]
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=2, prefill_chunk=8, spec_k=4,
            degrade=True, tenants={}, degrade_hold=10 ** 9))
        eng._ladder.stage = 1
        eng.generate(prompts, max_new_tokens=8, top_k=0)
        assert eng._spec_proposed == 0           # drafts actually shed
        g = eng.ledger.goodput()
        assert g['spec_shed_tokens'] > 0
        assert g['wasted_by_cause']['spec_rejected'] == 0
        assert g['delivered_tokens'] + g['wasted_tokens'] \
            == g['emitted_tokens']
        eng.shutdown()

    def test_ledger_reconciles_and_host_bound_real(self, tiny_lm,
                                                   mixed_prompts):
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=3, prefill_chunk=16))
        eng.generate(mixed_prompts, max_new_tokens=6, top_k=0)
        a = eng.ledger.account()
        assert a['iterations'] > 0
        wall = a['wall_seconds']
        assert wall > 0.0
        # clamped components reconcile by construction; the bench-leg
        # acceptance bound (10%) is asserted here on a live run too
        total = sum(a['components'].values())
        assert abs(total - wall) <= 0.10 * wall, a
        assert a['components']['compute'] > 0.0
        assert a['components']['host_fetch'] > 0.0
        # host_bound_fraction comes from the registered HostGapMonitor
        # fed by the real sampled-token fetches — present and sane
        hbf = a['host_bound_fraction']
        assert hbf is not None and 0.0 <= hbf <= 1.0
        roof = eng.ledger.roofline()
        assert roof['decode_bytes_per_iteration'] > 0
        assert roof['mbu'] is None               # CPU: never faked
        assert roof['prefill_tflops'] > 0.0
        eng.shutdown()

    def test_snapshot_merges_and_shutdown_unregisters(
            self, tiny_lm, mixed_prompts, clean_registry):
        from paddle_tpu.serving.metrics import serve_snapshot
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=3, prefill_chunk=16))
        eng.generate(mixed_prompts[:3], max_new_tokens=4, top_k=0)
        eng.publish_metrics()
        s = serve_snapshot()
        assert 'serve' in s['ledger'], s.keys()
        g = s['goodput']
        assert g['delivered_tokens'] + g['wasted_tokens'] \
            == g['emitted_tokens'] > 0
        assert 'serve' in s['roofline']
        assert s['ledger']['serve']['wall_seconds'] > 0
        assert s['ledger']['serve']['host_bound_fraction'] is not None
        # the published gauges land in the monitor registry
        from paddle_tpu.core import monitor as _m
        reg = _m.metrics()
        assert reg.get('ptpu_serve_ledger_wall_seconds').value(
            engine='serve') > 0
        assert reg.get('ptpu_serve_goodput_emitted_tokens').value(
            engine='serve') == g['emitted_tokens']
        # PR-13 discipline: shutdown unregisters ledger AND monitor,
        # so a dead engine stops reporting immediately
        eng.shutdown()
        assert serve_ledger_snapshot() is None
        from paddle_tpu.core.async_step import _monitors
        assert eng.ledger_site not in _monitors

    def test_zero_extra_host_syncs(self, tiny_lm, mixed_prompts,
                                   monkeypatch):
        # the PR-6 sync-count harness: the full goodput/ledger/roofline
        # observatory must not add a single host fetch — the budget
        # stays one per dispatch that yields a token: every decode
        # step's, and a first token's only where its chunk rode with no
        # decode row
        counts = [0]
        real = engine_mod._host_fetch

        def counting(x):
            counts[0] += 1
            return real(x)
        monkeypatch.setattr(engine_mod, '_host_fetch', counting)
        try:
            eng = ServingEngine(tiny_lm, ServingConfig(
                page_size=8, max_batch_size=3, prefill_chunk=8,
                num_pages=4))
            outs = eng.generate(mixed_prompts, max_new_tokens=6,
                                top_k=0)
            st = eng.stats()
            n_gen = counts[0]
            # reading every account + publishing adds zero syncs
            eng.ledger.account()
            eng.ledger.goodput()
            eng.ledger.roofline()
            eng.publish_metrics()
            assert counts[0] == n_gen
            eng.shutdown()
        finally:
            monkeypatch.setattr(engine_mod, '_host_fetch', real)
        generated = sum(len(o) - len(p)
                        for o, p in zip(outs, mixed_prompts))
        prefill_fetches = generated - st['decode_tokens_total']
        assert st['decode_steps_total'] <= n_gen <= min(
            st['decode_steps_total'] + prefill_fetches,
            st['dispatches_total']), (n_gen, st)


# ---------------------------------------------------------------------------
# cluster: drain-resubmit recompute priced wasted, identity preserved
# ---------------------------------------------------------------------------
class TestClusterDrainGoodput:
    def test_drain_resubmit_moves_delivered_to_wasted(self, tiny_lm,
                                                      mixed_prompts):
        from paddle_tpu.serving.cluster import (ClusterRouter,
                                                LocalReplica)
        reps = [LocalReplica(
            ServingEngine(tiny_lm, ServingConfig(
                page_size=8, max_batch_size=3, prefill_chunk=16)), rid)
            for rid in ('r0', 'r1')]
        router = ClusterRouter(reps, page_size=8, max_queue=32)
        reqs = [router.submit(p, max_new_tokens=12, top_k=0)
                for p in mixed_prompts]
        for _ in range(6):                       # partial progress
            router.pump()
        drained = reqs[0].replica_id
        router.drain(drained, reason='ledger test')
        router.run(timeout_s=120)
        assert all(r.done for r in reqs)
        router.refresh()
        snap = router.snapshot()
        g = snap['goodput']
        assert g is not None, snap
        # the resubmitted prefix a peer re-prefilled is priced wasted
        # (cause drain_recompute), NOT delivered — and the identity
        # stays exact at the cluster level
        assert g['drain_recompute_tokens'] > 0
        assert g['wasted_by_cause']['drain_recompute'] > 0
        assert g['delivered_tokens'] + g['wasted_tokens'] \
            == g['emitted_tokens']
        # move-not-add: cluster totals tie back to the replicas' own
        # accounts exactly
        rep_goodputs = [row['goodput']
                        for row in snap['replicas'].values()
                        if row.get('goodput')]
        rep_emitted = sum(r['emitted_tokens'] for r in rep_goodputs)
        rep_delivered = sum(r['delivered_tokens'] for r in rep_goodputs)
        rep_wasted = sum(r['wasted_tokens'] for r in rep_goodputs)
        moved = g['wasted_by_cause']['drain_recompute']
        assert g['emitted_tokens'] == rep_emitted
        assert g['delivered_tokens'] == rep_delivered - moved
        assert g['wasted_tokens'] == rep_wasted + moved
        assert moved == min(g['drain_recompute_tokens'], rep_delivered)
        # the lifetime counter reaches cluster_snapshot() for telemetry
        from paddle_tpu.serving.cluster.router import cluster_snapshot
        cs = cluster_snapshot()
        assert cs['ptpu_route_drain_recompute_tokens_total'] \
            >= g['drain_recompute_tokens']
        router.shutdown()
        assert all(rep.engine.ledger_site not in ledger_mod._ledgers
                   or ledger_mod._ledgers[rep.engine.ledger_site]
                   is not rep.engine.ledger for rep in reps)


# ---------------------------------------------------------------------------
# trace schema v6: old schemas still load
# ---------------------------------------------------------------------------
class TestSchemaCompat:
    @pytest.mark.parametrize('version', [1, 2, 3, 4, 5])
    def test_older_schemas_still_load(self, version, tmp_path):
        import json
        from paddle_tpu.serving.request_trace import (load_trace,
                                                      reconstruct)
        p = tmp_path / f'v{version}.jsonl'
        header = {'schema': f'paddle_tpu.serve_trace/{version}',
                  'dropped_events': 0}
        events = [
            {'event': 'submit', 'req': 0, 't': 1.0, 'prompt_tokens': 4},
            {'event': 'admit', 'req': 0, 't': 1.1},
            {'event': 'prefill_chunk', 'req': 0, 't': 1.2, 'tokens': 4},
            {'event': 'first_token', 'req': 0, 't': 1.3,
             'tokens_generated': 1},
            {'event': 'decode', 'req': 0, 't': 1.4,
             'tokens_generated': 2},
            {'event': 'retire', 'req': 0, 't': 1.5,
             'tokens_generated': 2},
        ]
        with open(p, 'w') as f:
            f.write(json.dumps(header) + '\n')
            for e in events:
                f.write(json.dumps(e) + '\n')
        hdr, evs = load_trace(str(p))
        assert hdr['schema'].endswith(f'/{version}')
        (r,) = reconstruct(evs).values()
        # pre-v4 journals reconstruct with zero waste — the delivered
        # column still prices what the journal does know
        assert r['recompute_tokens'] == 0 and r['spec_discarded'] == 0
        assert r['delivered_tokens'] == 4 + (2 - 1)
        assert r['wasted_tokens'] == 0
