"""Fused decode windows (ISSUE 19): k decode iterations inside ONE
compiled dispatch (lax.scan over the [B, 1] step) with ONE host fetch
per window. The bar is token identity — fused k must emit exactly what
k serial iterations emit, greedy AND sampled, with eos / budget cuts
truncating precisely where serial decode stops — plus exact ledger
accounting, per-iteration observability, and a zero-extra-host-sync
budget counted through the PR-3/PR-6 `engine._host_fetch` harness."""
import json

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.serving.engine as engine_mod
from paddle_tpu.core import monitor
from paddle_tpu.serving import (KVPagePool, PoolExhausted, RequestState,
                                ServingConfig, ServingEngine)
from paddle_tpu.serving.request_trace import load_trace, reconstruct
from paddle_tpu.serving.scheduler import DegradeLadder, Scheduler

MODEL_KW = dict(vocab_size=128, hidden_size=64, num_layers=2,
                num_heads=2, max_seq_len=160, hidden_dropout=0.0,
                attn_dropout=0.0, use_flash_attention=False)


@pytest.fixture(scope='module')
def tiny_lm():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(7)
    m = GPTForCausalLM(GPTConfig(**MODEL_KW))
    m.eval()
    return m


@pytest.fixture(scope='module')
def prompts():
    rng = np.random.RandomState(3)
    return [list(rng.randint(1, 128, n)) for n in (5, 11, 3, 8)]


def _engine(model, fused_k, **kw):
    base = dict(page_size=8, max_batch_size=4, prefill_chunk=8,
                fused_k=fused_k, seed=11)
    base.update(kw)
    return ServingEngine(model, ServingConfig(**base))


def _run(model, fused_k, prompts, max_new=12, top_k=0, eos=None, **kw):
    eng = _engine(model, fused_k, **kw)
    outs = eng.generate(prompts, max_new_tokens=max_new, top_k=top_k,
                        eos_token_id=eos)
    st = eng.stats()
    eng.shutdown()
    return outs, st


# ---------------------------------------------------------------------------
# token identity: fused k == k serial iterations
# ---------------------------------------------------------------------------
class TestFusedTokenIdentity:
    def test_greedy_k8_matches_serial(self, tiny_lm, prompts):
        ref, st1 = _run(tiny_lm, 1, prompts)
        out, st8 = _run(tiny_lm, 8, prompts)
        assert out == ref
        # the serial engine never fuses, the k=8 engine actually did
        assert st1['fused_windows_total'] == 0
        assert st8['fused_windows_total'] > 0
        assert st8['fused_k'] == 8
        # iteration accounting survives fusing: both engines ran the
        # same decode stream, so the iteration/token counters agree
        assert st8['decode_tokens_total'] == st1['decode_tokens_total']
        assert st8['decode_steps_total'] == st1['decode_steps_total']

    def test_sampled_same_seed_identical(self, tiny_lm, prompts):
        # the RNG folds per (request ordinal, absolute position), so a
        # fused window consumes exactly the randomness its serial
        # iterations would have — same seed -> same tokens
        ref, _ = _run(tiny_lm, 1, prompts, top_k=5)
        out, st = _run(tiny_lm, 8, prompts, top_k=5)
        assert out == ref
        assert st['fused_windows_total'] > 0
        # and sampling is actually doing something
        greedy, _ = _run(tiny_lm, 1, prompts)
        assert out != greedy

    def test_eos_mid_window_truncates_exactly(self, tiny_lm, prompts):
        # pick an eos id straight out of the reference stream so it
        # falls mid-window (not at a window edge) for at least one row
        base, _ = _run(tiny_lm, 1, prompts)
        eos = base[0][len(prompts[0]) + 2]      # 3rd generated token
        ref, _ = _run(tiny_lm, 1, prompts, eos=eos)
        out, st = _run(tiny_lm, 8, prompts, eos=eos)
        assert out == ref
        assert any(o[-1] == eos and len(o) - len(p) < 12
                   for o, p in zip(out, prompts)), \
            'eos never cut a row short — test lost its bite'
        assert st['fused_windows_total'] > 0

    def test_budget_cut_mid_window(self, tiny_lm, prompts):
        # max_new not a multiple of k: the last window must stop at
        # the budget, not round up to the window edge
        for k, max_new in ((8, 6), (4, 11)):
            ref, _ = _run(tiny_lm, 1, prompts, max_new=max_new)
            out, st = _run(tiny_lm, k, prompts, max_new=max_new)
            assert out == ref, (k, max_new)
            assert all(len(o) - len(p) == max_new
                       for o, p in zip(out, prompts))
            assert st['fused_windows_total'] > 0

    def test_page_boundary_crossing_inside_window(self, tiny_lm,
                                                  prompts):
        # page_size 2: one 8-iteration window crosses several page
        # boundaries, exercising the pre-reserved pages + on-device
        # scatter across the whole span
        kw = dict(page_size=2, num_pages=256, prefill_chunk=8)
        ref, _ = _run(tiny_lm, 1, prompts, **kw)
        out, st = _run(tiny_lm, 8, prompts, **kw)
        assert out == ref
        assert st['fused_windows_total'] > 0

    def test_preempt_resume_identity(self, tiny_lm, prompts):
        # a pool too small for the concurrent contexts: reservation
        # failures fall back to the serial step, which preempts and
        # resumes — outputs still match the unconstrained reference
        ref, _ = _run(tiny_lm, 1, prompts, max_new=6)
        out, st = _run(tiny_lm, 8, prompts, max_new=6,
                       max_batch_size=3, num_pages=4)
        assert out == ref
        assert st['preemptions_total'] > 0

    def test_trim_returns_window_tail(self, tiny_lm, prompts):
        # early eos inside a window: the reserved-but-unused tail is
        # trimmed back, and the drained pool holds zero pages
        base, _ = _run(tiny_lm, 1, prompts)
        eos = base[0][len(prompts[0]) + 2]
        eng = _engine(tiny_lm, 8)
        eng.generate(prompts, max_new_tokens=12, top_k=0,
                     eos_token_id=eos)
        assert eng.stats()['fused_windows_total'] > 0
        assert eng.pool.pages_in_use == 0
        eng.shutdown()


# ---------------------------------------------------------------------------
# ledger accounting + the host-sync budget
# ---------------------------------------------------------------------------
class TestFusedLedgerAndSyncs:
    def test_goodput_identity_exact(self, tiny_lm, prompts):
        # the delivered/wasted/emitted stream a fused run reports must
        # be EXACTLY what k serial iterations would have reported
        ref_eng = _engine(tiny_lm, 1)
        ref_eng.generate(prompts, max_new_tokens=12, top_k=0)
        ref = ref_eng.ledger.goodput()
        ref_eng.shutdown()
        eng = _engine(tiny_lm, 8)
        eng.generate(prompts, max_new_tokens=12, top_k=0)
        st = eng.stats()
        assert st['fused_windows_total'] > 0
        g = eng.ledger.goodput()
        assert (g['delivered_tokens'] + g['wasted_tokens']
                == g['emitted_tokens'])
        for k in ('emitted_tokens', 'delivered_tokens',
                  'wasted_tokens', 'goodput_fraction'):
            assert g[k] == ref[k], k
        assert g['wasted_tokens'] == 0          # preemption-free run
        # ledger window counters mirror the engine's
        acct = eng.ledger.account()
        assert acct['fused_windows'] == st['fused_windows_total']
        assert acct['fused_iterations'] == st['fused_iterations_total']
        assert acct['fused_tokens'] == st['fused_tokens_total']
        assert 0 < st['fused_tokens_total'] <= st['decode_tokens_total']
        eng.shutdown()

    def test_one_host_fetch_per_window(self, tiny_lm, prompts,
                                       monkeypatch):
        # the PR-3/PR-6 sync-count harness: serial decode pays one
        # fetch per iteration; a fused window pays ONE for all its
        # iterations. Nothing else in the engine may add a sync.
        counts = [0]
        real = engine_mod._host_fetch

        def counting(x):
            counts[0] += 1
            return real(x)
        monkeypatch.setattr(engine_mod, '_host_fetch', counting)
        try:
            eng = _engine(tiny_lm, 8)
            outs = eng.generate(prompts, max_new_tokens=12, top_k=0)
            st = eng.stats()
            n = counts[0]
            eng.ledger.account()
            eng.ledger.goodput()
            eng.publish_metrics()
            assert counts[0] == n       # observability adds zero
            eng.shutdown()
        finally:
            monkeypatch.setattr(engine_mod, '_host_fetch', real)
        generated = sum(len(o) - len(p) for o, p in zip(outs, prompts))
        prefill_fetches = generated - st['decode_tokens_total']
        serial_iters = (st['decode_steps_total']
                        - st['fused_iterations_total'])
        # a first token costs a fetch of its own only where its chunk
        # rode the mixed program with no decode row beside it
        decode_fetches = serial_iters + st['fused_windows_total']
        assert decode_fetches <= n <= min(
            decode_fetches + prefill_fetches, st['dispatches_total']), \
            (n, st)
        # and the budget actually shrank vs one-fetch-per-token
        assert n < prefill_fetches + st['decode_steps_total']


# ---------------------------------------------------------------------------
# per-iteration observability: timeline, metrics, trace
# ---------------------------------------------------------------------------
class TestFusedObservability:
    def test_timeline_records_per_iteration(self, tiny_lm, prompts):
        eng = _engine(tiny_lm, 8)
        eng.generate(prompts, max_new_tokens=12, top_k=0)
        st = eng.stats()
        assert st['fused_windows_total'] > 0
        rows = eng.timeline.snapshot()
        fused_rows = [r for r in rows if r.get('fused')]
        # one timeline entry per fused ITERATION, not per dispatch
        assert len(fused_rows) == st['fused_iterations_total']
        assert all(r['fused_k'] == 8 for r in fused_rows)
        assert (eng.timeline.summary()['fused_iterations']
                == st['fused_iterations_total'])
        # the per-iteration decode stream is complete: tokens across
        # all rows (fused or not) add up to the engine counter
        assert (sum(r.get('decode_tokens', 0) for r in rows)
                == st['decode_tokens_total'])
        eng.shutdown()

    def test_trace_v5_roundtrip_carries_fused_events(self, tiny_lm,
                                                     prompts,
                                                     tmp_path):
        eng = _engine(tiny_lm, 8)
        eng.generate(prompts, max_new_tokens=12, top_k=0)
        st = eng.stats()
        assert st['fused_windows_total'] > 0
        paths = eng.export_trace(jsonl_path=str(tmp_path / 'f.jsonl'))
        header, events = load_trace(paths['jsonl'])
        assert header['schema'] == 'paddle_tpu.serve_trace/6'
        fde = [e for e in events if e['event'] == 'fused_decode']
        assert fde and all('k' in e and 'accepted' in e for e in fde)
        assert sum(e['accepted'] for e in fde) \
            == st['fused_tokens_total']
        # reconstruction parity: fused events count as the decode
        # steps they ran, and the JSONL roundtrip is bit-exact
        table = reconstruct(events)
        assert table == eng.request_table()
        for rid, row in table.items():
            assert row['decode_steps'] + 1 == row['tokens_generated'] \
                or row['decode_steps'] == row['tokens_generated']
            assert row['fused_windows'] > 0 or row['fused_tokens'] == 0
        assert (sum(r['fused_tokens'] for r in table.values())
                == st['fused_tokens_total'])
        eng.shutdown()

    def test_stats_and_gauges_expose_fused_counters(self, tiny_lm,
                                                    prompts):
        from paddle_tpu.serving import metrics as serve_metrics
        eng = _engine(tiny_lm, 4)
        eng.generate(prompts, max_new_tokens=8, top_k=0)
        st = eng.stats()
        assert st['fused_k'] == 4
        assert st['fused_windows_total'] > 0
        series = serve_metrics.scalar_series(st)
        assert series['ptpu_serve_fused_k'] == 4
        assert (series['ptpu_serve_fused_windows_total']
                == st['fused_windows_total'])
        assert (series['ptpu_serve_fused_iterations_total']
                == st['fused_iterations_total'])
        eng.reset_stats()
        assert eng.stats()['fused_windows_total'] == 0
        eng.shutdown()


# ---------------------------------------------------------------------------
# quiescence predicate + degrade interaction (unit level)
# ---------------------------------------------------------------------------
class TestQuiescence:
    def _req(self, state):
        from paddle_tpu.serving.scheduler import Request
        r = Request([1, 2], max_new_tokens=4)
        r.state = state
        return r

    def test_scheduler_quiescent_predicate(self):
        s = Scheduler(num_slots=2)
        assert not s.quiescent()                # empty: nothing to fuse
        s.slots[0] = self._req(RequestState.RUNNING)
        assert s.quiescent()
        s.slots[1] = self._req(RequestState.PREFILL)
        assert not s.quiescent()                # prefill due mid-window
        s.slots[1] = self._req(RequestState.RUNNING)
        assert s.quiescent()
        s.waiting.append(self._req(RequestState.WAITING))
        assert not s.quiescent()                # admission due

    def test_ladder_would_transition_simulates_without_mutating(self):
        lad = DegradeLadder(window=4, hold=2)
        for _ in range(4):
            lad.observe(0.2, 0, 4)
        before = (lad.stage, list(lad._ring), lad._calm)
        assert not lad.would_transition(0.2, steps=8)
        # pressure that would cross up[0] within the window
        assert lad.would_transition(1.0, steps=8)
        assert (lad.stage, list(lad._ring), lad._calm) == before
        # a ladder sitting at stage 1 over a calming signal would
        # step DOWN mid-window — that is also a transition
        lad2 = DegradeLadder(window=2, hold=2)
        lad2.observe(1.0, 8, 2)
        assert lad2.stage == 1
        assert lad2.would_transition(0.1, steps=8)

    def test_effective_fused_k_sheds_at_stage_1(self, tiny_lm):
        eng = _engine(tiny_lm, 8, degrade=True)
        assert eng._effective_fused_k() == 8
        eng._ladder.stage = 1       # stage 1 sheds fused BEFORE spec
        assert eng._effective_fused_k() == 1
        eng._ladder.stage = 0
        assert eng._effective_fused_k() == 8
        eng.shutdown()

    def test_pool_try_reserve_all_or_nothing(self):
        pool = KVPagePool(num_pages=3, page_size=4)
        pool.ensure_capacity('a', 4)            # 1 page held
        assert pool.try_reserve('a', 12)        # grows to 3: fits
        assert pool.pages_in_use == 3
        assert not pool.try_reserve('b', 12)    # needs 3, 0 free
        # the failed reservation rolled back its own fresh pages
        assert pool.pages_in_use == 3 and pool.free_pages == 0
        pool.release('a')
        assert pool.try_reserve('b', 12)
        assert pool.pages_in_use == 3

    def test_config_knob_env_and_validation(self, monkeypatch):
        assert ServingConfig(fused_k=4).fused_k == 4
        with pytest.raises(ValueError, match='fused_k'):
            ServingConfig(fused_k=0)
        monkeypatch.setenv('PTPU_SERVE_FUSED_K', '16')
        assert ServingConfig().fused_k == 16
        assert ServingConfig(fused_k=2).fused_k == 2    # explicit wins


# ---------------------------------------------------------------------------
# publish cadence keys to the monitor wall clock (satellite 2)
# ---------------------------------------------------------------------------
class TestPublishCadence:
    def test_periodic_publish_uses_wall_clock(self, tiny_lm):
        # frozen config clock + controllable monitor time: mid-stream
        # steps must publish on WALL cadence, so gauge freshness can't
        # lapse into metrics_stale alerts on a healthy fused engine
        t = [100.0]
        prev = monitor.set_time_fn(lambda: t[0])
        try:
            eng = ServingEngine(tiny_lm, ServingConfig(
                page_size=8, max_batch_size=2, prefill_chunk=8,
                fused_k=4, clock=lambda: 0.0))
            pubs = [0]
            real = eng.publish_metrics

            def counting():
                pubs[0] += 1
                return real()
            eng.publish_metrics = counting
            eng.submit(list(range(1, 6)), max_new_tokens=64)
            eng.step()                  # prefill
            base = pubs[0]
            eng.step()                  # mid-stream, wall frozen
            eng.step()
            assert pubs[0] == base      # no retire, no cadence due
            t[0] += eng.PUBLISH_INTERVAL_S + 0.01
            eng.step()
            assert pubs[0] == base + 1  # wall cadence fired
            eng.step()
            assert pubs[0] == base + 1  # and re-armed, not every step
            eng.shutdown()
        finally:
            monitor.set_time_fn(prev)


# ---------------------------------------------------------------------------
# mp-sharded serving: the fused shape shards like the [B, 1] step
# ---------------------------------------------------------------------------
class TestFusedMpSharded:
    def test_mp2_fused_token_identical(self, prompts):
        import os
        import paddle_tpu.distributed.fleet as fleet_mod
        from paddle_tpu.distributed import topology_runtime
        from paddle_tpu.distributed.fleet.base.topology import (
            CommunicateTopology, HybridCommunicateGroup)
        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
        os.environ.setdefault('PADDLE_TRAINER_ID', '0')
        kw = dict(MODEL_KW, hidden_size=32, num_heads=2)
        paddle.seed(0)
        ref_model = GPTForCausalLM(GPTConfig(**kw))
        ref_model.eval()
        ref, _ = _run(ref_model, 1, prompts[:2], max_new=8)
        topo = CommunicateTopology(
            ["data", "pipe", "sharding", "model"], [1, 1, 1, 2])
        fleet_mod.fleet._topology = topo
        fleet_mod.fleet._hcg = HybridCommunicateGroup(topo)
        try:
            mesh = topology_runtime.build_mesh(['mp'], [2])
            paddle.seed(0)
            mp_model = GPTForCausalLM(GPTConfig(**kw))
            mp_model.eval()
            eng = ServingEngine(
                mp_model,
                ServingConfig(page_size=8, max_batch_size=4,
                              prefill_chunk=8, fused_k=4, seed=11),
                mesh=mesh)
            outs = eng.generate(prompts[:2], max_new_tokens=8, top_k=0)
            assert outs == ref
            assert eng.stats()['fused_windows_total'] > 0
            eng.shutdown()
        finally:
            fleet_mod.fleet._hcg = None
            fleet_mod.fleet._topology = None
