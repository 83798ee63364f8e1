"""One step ahead (ISSUE 33): the engine queues step n+1 on the device
before the ids of step n are fetched, a decode row's query token going
from one program to the next on the device. At toy sizes on the CPU,
for the dense GPT, the sparse-expert AFMoE and the state-space
Phi-4-flash models: the tokens are those of the SAME engine drained
after every step (the serial order), whatever rides — sampled rows,
prompts of many chunks, more chunks than the prefill group has rows, a
budget of one token, an EOS met mid-answer, a preemption or an abort of
a row in flight, the prefix cache, speculation, the fused window, a
host-tier fetch, an adoption; the compiled shapes stay two; and the
mechanism's counters read what each scenario makes them read."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.profiler as prof
import paddle_tpu.serving.engine as engine_mod
from paddle_tpu.core import monitor
from paddle_tpu.serving import RequestState, ServingConfig, ServingEngine
from paddle_tpu.serving.engine import DRAIN_REASONS, PREFILL_ROWS
from paddle_tpu.serving.metrics import serve_snapshot

VOCAB, SLOTS, CHUNK, PAGE = 96, 4, 4, 4
FAMILIES = ('gpt', 'afmoe', 'phi4flash')


@pytest.fixture(scope='module')
def gpt():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(7)
    m = GPTForCausalLM(GPTConfig(
        vocab_size=VOCAB, hidden_size=64, num_layers=2, num_heads=2,
        max_seq_len=96, hidden_dropout=0.0, attn_dropout=0.0,
        use_flash_attention=False))
    m.eval()
    return m


@pytest.fixture(scope='module')
def afmoe():
    from paddle_tpu.models.afmoe import AfmoeConfig, AfmoeForCausalLM
    paddle.seed(3)
    m = AfmoeForCausalLM(AfmoeConfig(
        vocab_size=VOCAB, hidden_size=64, num_layers=3, num_dense_layers=1,
        num_heads=4, num_kv_heads=2, head_dim=16, intermediate_size=96,
        moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
        sliding_window=24,
        layer_types=['sliding_attention'] * 2 + ['full_attention'],
        max_seq_len=96, dtype='float32'))
    m.eval()
    return m


@pytest.fixture(scope='module')
def phi4flash():
    from paddle_tpu.models.phi4flash import (Phi4FlashConfig,
                                             Phi4FlashForCausalLM)
    paddle.seed(3)
    m = Phi4FlashForCausalLM(Phi4FlashConfig(
        vocab_size=VOCAB, hidden_size=64, num_layers=8, num_heads=4,
        num_kv_heads=2, intermediate_size=128, sliding_window=8,
        max_seq_len=96, dtype='float32'))
    m.eval()
    return m


@pytest.fixture(params=FAMILIES)
def model(request):
    return request.getfixturevalue(request.param)


def engine(model, **kw):
    kw.setdefault('num_pages', 96)
    kw.setdefault('prefix_cache', False)
    return ServingEngine(model, ServingConfig(
        page_size=PAGE, max_batch_size=SLOTS, prefill_chunk=CHUNK,
        max_pages_per_seq=24, seed=5, **kw))


def prompts_of(seed, lengths):
    rng = np.random.RandomState(seed)
    return [[int(t) for t in rng.randint(1, VOCAB, n)] for n in lengths]


def serve(model, arrivals, drained, **knobs):
    """`arrivals` = [(the step() call before which it is submitted,
    prompt, submit keywords)] through one engine, which with `drained`
    is emptied after every step — the serial order, through the same
    `_drain` that whatever cannot be planned ahead takes. -> (requests,
    stats, compiled keys, step() calls)."""
    eng = engine(model, **knobs)
    if drained:
        # never a step launched behind another: each is fetched
        # (`_drain`) before the next is planned
        eng._holds_back = lambda: 'idle'
    try:
        reqs, calls = [], 0
        arrivals = sorted(arrivals, key=lambda a: a[0])
        while arrivals or eng.scheduler.has_work:
            while arrivals and arrivals[0][0] <= calls:
                _, prompt, kw = arrivals.pop(0)
                reqs.append(eng.submit(prompt, **kw))
            eng.step()
            calls += 1
            assert calls < 2000 and not (drained and eng._flight)
        assert eng._flight is None
        assert eng.pool.pages_in_use == 0
        assert eng.scheduler.slots == [None] * SLOTS
        return reqs, eng.stats(), set(eng._step_fns), calls
    finally:
        eng.shutdown()


def both(model, arrivals, **knobs):
    """The same arrivals one step ahead and drained after every step:
    the tokens must be the same, request for request."""
    ahead = serve(model, arrivals, False, **knobs)
    serial = serve(model, arrivals, True, **knobs)
    assert [r.generated for r in ahead[0]] == [
        r.generated for r in serial[0]]
    assert ahead[2] == serial[2]
    return ahead, serial


def greedy(new):
    return dict(max_new_tokens=new, top_k=0)


def sampled(new):
    """(a toy model's greedy answer soon repeats itself: a sampled one
    holds ids that are the first of their kind, to serve as an EOS)"""
    return dict(max_new_tokens=new, top_k=8, temperature=1.5)


def first_of_its_kind(ids, lo=2):
    """The first position from `lo` on whose id no earlier one holds."""
    return next(i for i in range(lo, len(ids)) if ids[i] not in ids[:i])


# what rides, by name: [(call, prompt length, submit keywords)]
SCENARIOS = {
    # three greedy rows of unlike lengths, all at once
    'greedy': [(0, 5, greedy(9)), (0, 11, greedy(6)), (0, 3, greedy(12))],
    # sampled rows beside a greedy one (the key is (ordinal, position))
    'top_k': [(0, 6, dict(max_new_tokens=8, top_k=5, temperature=1.3)),
              (0, 9, greedy(8)),
              (0, 4, dict(max_new_tokens=10, top_k=3, temperature=0.7))],
    # prompts of 5, 8 and 3 chunks
    'many_chunks': [(0, 19, greedy(5)), (0, 30, greedy(4)),
                    (0, 10, greedy(7))],
    # more prompts prefilling than the prefill group has rows: further
    # dispatches, and first tokens that no decode row's dispatch made
    'more_chunks_than_rows': [(0, n, greedy(6)) for n in (9, 13, 6, 11)][
        :PREFILL_ROWS + 2],
    # a budget of one token (and of none: a scoring request)
    'budget_of_one': [(0, 7, greedy(1)), (0, 5, greedy(6)),
                      (0, 9, greedy(0)), (1, 3, greedy(1))],
    # arrivals while steps are in flight, more requests than slots
    'late_arrivals': [(0, 6, greedy(7)), (2, 9, greedy(5)),
                      (3, 14, greedy(6)), (3, 4, greedy(9)),
                      (4, 7, greedy(4)), (9, 5, greedy(3))],
}


@pytest.mark.parametrize('scenario', list(SCENARIOS))
def test_tokens_are_those_of_the_engine_drained_after_every_step(
        model, scenario):
    script = SCENARIOS[scenario]
    prompts = prompts_of(len(scenario), [n for _, n, _ in script])
    arrivals = [(at, p, kw) for (at, _, kw), p in zip(script, prompts)]
    (reqs, st, keys, calls), (_, serial, _, _) = both(model, arrivals)
    assert [len(r.generated) for r in reqs] == [
        kw['max_new_tokens'] for _, _, kw in script]
    assert all(r.state == RequestState.FINISHED for r in reqs)
    assert st['overrun_tokens_total'] == 0      # no request had an EOS
    # (a late arrival rides the step after the one already queued, so
    # its chunks may share their dispatches otherwise than drained)
    for key in ('decode_tokens_total', 'prefill_tokens_total',
                'prefill_chunks_total', 'requests_completed_total') + (
                    () if any(at for at, _, _ in script)
                    else ('dispatches_total', 'decode_steps_total')):
        assert st[key] == serial[key], key
    # every step but those launched after idle rode behind another
    steps = round(st['dispatches_total'] / st['dispatches_per_step'])
    idle = st['pipeline_drains_total']['idle']
    assert st['pipelined_steps_total'] == steps - idle
    assert sum(st['pipeline_drains_total'].values()) == idle
    # the drained engine fetched every step with nothing behind it
    assert serial['pipelined_steps_total'] == 0
    assert sum(serial['pipeline_drains_total'].values()) == round(
        serial['dispatches_total'] / serial['dispatches_per_step'])


def test_an_eos_met_mid_answer_ends_the_request_where_it_stands(model):
    """A row is launched again before the ids that hold its EOS arrive:
    nothing after the EOS escapes, what the row computed past it is
    dropped and counted, its pages and its slot (and with it the
    recurrent state's) are free — the next request in that slot reads
    as if it ran alone."""
    prompts = prompts_of(11, (6, 9, 4))
    kinds = (greedy(10), sampled(10), greedy(10))
    free = serve(model, list(zip((0, 0, 0), prompts, kinds)), False)[0]
    g = free[1].generated
    e = first_of_its_kind(g[:9])
    then = prompts_of(12, (7,))[0]
    alone = serve(model, [(0, then, greedy(6))], False)[0][0].generated
    arrivals = [(0, prompts[0], greedy(10)),
                (0, prompts[1], dict(sampled(10), eos_token_id=g[e])),
                (0, prompts[2], greedy(10)),
                # rides the slot the EOS row leaves (three slots are
                # held: it waits for none, and takes the fourth — so
                # hold that one too)
                (0, prompts_of(13, (5,))[0], greedy(30)),
                (e + 4, then, greedy(6))]
    (reqs, st, _, _), (sreqs, serial, _, _) = both(model, arrivals)
    assert reqs[1].generated == g[:e + 1]
    assert reqs[0].generated == free[0].generated
    assert reqs[2].generated == free[2].generated
    assert reqs[4].generated == alone
    assert st['overrun_tokens_total'] == 1
    assert serial['overrun_tokens_total'] == 0
    # delivered: every token but each request's first, which its
    # prompt's last chunk made
    assert st['decode_tokens_total'] == serial['decode_tokens_total'] \
        == sum(len(r.generated) - 1 for r in reqs)


def test_an_eos_as_the_first_token_or_the_last_of_the_budget(gpt):
    """The EOS comes off a prompt's last chunk (the decode row launched
    behind it is dropped), and in the budget's last token (no row was
    launched behind that one: nothing to drop)."""
    prompts = prompts_of(21, (7, 5))
    free = serve(gpt, [(0, p, greedy(4)) for p in prompts], False)[0]
    first, last = free[0].generated[0], free[1].generated[3]
    arrivals = [(0, prompts[0], dict(greedy(4), eos_token_id=first)),
                (0, prompts[1], dict(greedy(4), eos_token_id=last))]
    (reqs, st, _, _), _ = both(gpt, arrivals)
    assert reqs[0].generated == [first]
    e = free[1].generated.index(last)
    assert reqs[1].generated == free[1].generated[:e + 1]
    assert st['overrun_tokens_total'] == 1 + (e < 3)


def test_a_pool_too_small_preempts_a_row_in_flight(model):
    """The pool runs out while a step is in flight: the preemption is
    decided on that step's ids (the pipe drains, reason `preempt`), the
    victim's pages go only then, and it resumes to the same tokens."""
    prompts = prompts_of(31, (9, 10, 11, 8))
    arrivals = [(0, p, greedy(14)) for p in prompts]
    roomy = serve(model, arrivals, False)[0]
    (reqs, st, _, _), (_, serial, _, _) = both(model, arrivals,
                                                num_pages=17)
    assert [r.generated for r in reqs] == [r.generated for r in roomy]
    assert st['preemptions_total'] >= 1 and serial['preemptions_total'] >= 1
    assert st['pipeline_drains_total']['preempt'] >= 1
    assert sum(r.preemptions for r in reqs) == st['preemptions_total']
    assert st['overrun_tokens_total'] == 0


def test_abort_of_a_request_in_flight(model):
    """abort() of a request whose row rides the step in flight lands
    that step first (reason `abort`): the request keeps what the step
    made, its pages go, and the others never notice."""
    prompts = prompts_of(41, (6, 9, 5))
    free = serve(model, [(0, p, greedy(12)) for p in prompts], False)[0]
    eng = engine(model)
    try:
        reqs = [eng.submit(p, **greedy(12)) for p in prompts]
        for _ in range(6):
            eng.step()
        victim, had = reqs[1], len(reqs[1].generated)
        assert eng._flight is not None and eng._flight.carries(victim)
        assert eng.abort(victim)
        assert eng._flight is None
        assert eng.stats()['pipeline_drains_total']['abort'] == 1
        assert victim.state == RequestState.ABORTED
        assert len(victim.generated) == had + 1
        assert victim.generated == free[1].generated[:had + 1]
        assert eng.pool.page_table(victim.id) == []
        assert not eng.abort(victim)        # once
        waiting = eng.submit(prompts_of(42, (4,))[0], **greedy(3))
        assert eng.abort(waiting)           # not in flight: no drain
        assert eng.stats()['pipeline_drains_total']['abort'] == 1
        while eng.scheduler.has_work:
            eng.step()
        assert reqs[0].generated == free[0].generated
        assert reqs[2].generated == free[2].generated
        assert eng.pool.pages_in_use == 0
        assert eng.stats()['requests_aborted_total'] == 2
    finally:
        eng.shutdown()


@pytest.mark.parametrize('family', ['gpt', 'afmoe'])
def test_the_prefix_cache_on(family, request):
    """Prompts that share a prefix: one whose sibling's chunk rides the
    step in flight waits for it as it waits for one of its own step,
    and maps its pages."""
    model = request.getfixturevalue(family)
    shared = prompts_of(51, (16,))[0]
    tails = prompts_of(52, (3, 6, 2, 5))
    arrivals = [(at, shared + t, greedy(6))
                for at, t in zip((0, 0, 1, 6), tails)]
    (reqs, st, _, _), (_, serial, _, _) = both(model, arrivals,
                                                prefix_cache=True)
    off = serve(model, arrivals, False)[0]
    assert [r.generated for r in reqs] == [r.generated for r in off]
    assert st['prefix_hit_tokens_total'] \
        == serial['prefix_hit_tokens_total'] == 3 * 16
    assert st['prefill_tokens_total'] == serial['prefill_tokens_total']


@pytest.mark.parametrize('knobs,reason', [
    (dict(spec_k=2), 'verify'), (dict(fused_k=4), 'fused')])
def test_speculation_and_the_fused_window_drain_and_still_match(
        gpt, knobs, reason):
    """The verify step's proposal reads the token, the fused window
    holds its own: neither is launched ahead — every step's ids are
    fetched with nothing behind them, under that reason — and the
    tokens are those of the plain engine, one step ahead."""
    prompts = prompts_of(61, (7, 12, 5))
    arrivals = [(at, p, greedy(11)) for at, p in zip((0, 0, 2), prompts)]
    plain = serve(gpt, arrivals, False)[0]
    (reqs, st, _, _), _ = both(gpt, arrivals, **knobs)
    assert [r.generated for r in reqs] == [r.generated for r in plain]
    drains = st['pipeline_drains_total']
    steps = round(st['dispatches_total'] / st['dispatches_per_step'])
    # a step that neither verified nor fused was held back all the same
    # (under speculation: wherever a greedy row decodes — while every
    # request still prefills there is no token for a proposal to read)
    ahead = st['pipelined_steps_total']
    assert ahead == 0 or reason == 'verify' and ahead < steps // 4
    assert drains[reason] >= steps - drains['idle'] - ahead > 0
    assert sum(drains.values()) == drains[reason] + drains['idle']
    assert st['spec_steps_total' if reason == 'verify'
              else 'fused_windows_total'] > 0


def test_sampled_rows_ride_the_pipe_under_speculation(gpt):
    """Speculation holds back a GREEDY row's step alone: with only
    sampled rows decoding there is no proposal to read a token."""
    arrivals = [(0, p, dict(max_new_tokens=8, top_k=4))
                for p in prompts_of(62, (6, 3))]
    (reqs, st, _, _), _ = both(gpt, arrivals, spec_k=2)
    assert st['pipeline_drains_total']['verify'] == 0
    assert st['pipelined_steps_total'] > 0


def test_a_host_tier_fetch_drains(gpt):
    """A prompt whose prefix sits in the host tier: the step waits on
    the fetch, so the step in flight lands first (reason `tier`)."""
    shared = prompts_of(71, (16,))[0]
    eng = engine(gpt, prefix_cache=True, host_tier_pages=16)
    try:
        eng.generate([shared + [7, 8]], **greedy(3))
        assert eng.pool.spill_lru(sync=True) >= 4
        assert eng.pool.host_resident_pages() >= 4
        busy = eng.submit(prompts_of(72, (5,))[0], **greedy(12))
        for _ in range(3):
            eng.step()
        assert eng._flight is not None
        late = eng.submit(shared + [9, 10, 11], **greedy(4))
        eng.step()
        st = eng.stats()
        assert st['pipeline_drains_total']['tier'] == 1
        assert st['pool']['tier_resurrected_pages_total'] >= 4
        while eng.scheduler.has_work:
            eng.step()
        want = serve(gpt, [(0, shared + [9, 10, 11], greedy(4))], True)[0]
        assert late.generated == want[0].generated
        assert len(busy.generated) == 12
    finally:
        eng.shutdown()


def test_an_adoption_and_a_hand_over_match_the_one_engine(gpt):
    """Disaggregated prefill -> decode: the decode engine lands what is
    in flight before it adopts (reason `adopt`); the prefill engine's
    row for a request it has handed over comes back to an empty slot
    and is dropped, uncounted (no EOS was met)."""
    from paddle_tpu.serving.cluster.disagg import DisaggregatedEngine
    prompts = prompts_of(81, (9, 5, 13, 6))
    arrivals = [(at, p, greedy(7)) for at, p in zip((0, 0, 3, 5), prompts)]
    want = serve(gpt, arrivals, True)[0]
    d = DisaggregatedEngine(gpt, ServingConfig(
        page_size=PAGE, max_batch_size=SLOTS, prefill_chunk=CHUNK,
        max_pages_per_seq=24, num_pages=96, prefix_cache=False, seed=5,
        disaggregate=True, prefill_slots=2))
    try:
        reqs, calls = [], 0
        pending = list(arrivals)
        while pending or d.has_work:
            while pending and pending[0][0] <= calls:
                _, p, kw = pending.pop(0)
                reqs.append(d.submit(p, **kw))
            d.step()
            calls += 1
            assert calls < 500
        assert [r.generated for r in reqs] == [r.generated for r in want]
        for eng in (d.prefill, d.decode):
            assert eng.stats()['overrun_tokens_total'] == 0
        assert d.decode.stats()['pipeline_drains_total']['adopt'] >= 1
        assert d.decode.stats()['pipelined_steps_total'] > 0
        assert d.pool.pages_in_use == d.prefill.pool.pages_in_use == 0
    finally:
        d.shutdown()


# -- shapes and counters ------------------------------------------------------
def test_two_step_shapes_and_a_second_workload_compiles_nothing(model):
    """The operands that carry the ids are always there: one program a
    shape, in flight or not. After a mixed workload the engine holds
    the two keys it held before this mechanism, and a second workload
    (other lengths, an EOS, an abort, a drain) compiles nothing."""
    from jax import monitoring
    compiles = []
    monitoring.register_event_duration_secs_listener(
        lambda event, duration, **kw: compiles.append(event)
        if event.endswith('backend_compile_duration') else None)
    eng = engine(model)
    try:
        eng.generate(prompts_of(91, (9, 3, 14, 6, 5)), **greedy(5))
        keys = {('mixed', SLOTS, min(PREFILL_ROWS, SLOTS), CHUNK, False),
                (SLOTS, 1, False, False)}
        assert set(eng._step_fns) == keys
        before = len(compiles)
        reqs = [eng.submit(p, **dict(greedy(8), eos_token_id=e))
                for p, e in zip(prompts_of(92, (4, 17, 7)), (None, 5, None))]
        for _ in range(4):
            eng.step()
        eng.abort(reqs[0])
        eng._drain()
        while eng.scheduler.has_work:
            eng.step()
        assert set(eng._step_fns) == keys
        assert len(compiles) == before
    finally:
        eng.shutdown()


def test_the_counters_in_stats_reset_and_the_published_metrics(gpt):
    """Steady decode: every step but the first rides behind another and
    only the last is fetched with nothing behind it. The three counters
    are in stats(), zero with reset_stats() and publish as
    ptpu_serve_*."""
    eng = engine(gpt)
    try:
        blank = eng.stats()
        assert blank['pipelined_steps_total'] == 0
        assert blank['overrun_tokens_total'] == 0
        assert blank['pipeline_drains_total'] == dict.fromkeys(
            DRAIN_REASONS, 0)
        g = eng.generate(prompts_of(93, (3, 4)), **sampled(20))
        st = eng.stats()
        steps = round(st['dispatches_total'] / st['dispatches_per_step'])
        assert steps == 1 + 19      # one chunk each, then 19 decode steps
        assert st['pipelined_steps_total'] == steps - 1
        assert st['pipeline_drains_total'] == dict(
            dict.fromkeys(DRAIN_REASONS, 0), idle=1)
        # a third request, ended by an EOS that a scout engine with the
        # same history (so the same sampling ordinals) read off it
        scout = engine(gpt)
        scout.generate(prompts_of(93, (3, 4)), **sampled(20))
        third = scout.generate([[9, 8, 7]], **sampled(20))[0][3:]
        scout.shutdown()
        e = first_of_its_kind(third[:12])
        out = eng.generate([[9, 8, 7]],
                           **dict(sampled(20), eos_token_id=third[e]))
        assert out[0][3:] == third[:e + 1]
        assert eng.stats()['overrun_tokens_total'] == 1
        monitor.metrics().reset()
        eng.publish_metrics()
        snap = serve_snapshot()
        st = eng.stats()
        assert snap['ptpu_serve_pipelined_steps_total'] \
            == st['pipelined_steps_total'] > steps
        assert snap['ptpu_serve_overrun_tokens_total'] == 1
        assert snap['ptpu_serve_pipeline_drains_total'] == {
            r: float(n) for r, n in st['pipeline_drains_total'].items()}
        assert snap['ptpu_serve_pipeline_drains_total']['idle'] == 2
        eng.reset_stats()
        st = eng.stats()
        assert st['pipelined_steps_total'] == st['overrun_tokens_total'] == 0
        assert not any(st['pipeline_drains_total'].values())
    finally:
        eng.shutdown()


# -- what a caller sees -------------------------------------------------------
def test_every_call_delivers_one_steps_tokens(model, monkeypatch):
    """The first call after idle launches two steps and lands one, the
    calls after it one and one, the last only lands: a row gains one
    token a call, each call costs one fetch, and a request submitted
    while a step is in flight rides the step after the one queued."""
    fetches = []
    real = engine_mod._host_fetch
    monkeypatch.setattr(engine_mod, '_host_fetch',
                        lambda x: fetches.append(1) or real(x))
    eng = engine(model)
    try:
        first = eng.submit(prompts_of(94, (3,))[0], **greedy(6))

        def call():
            mark, before = prof.mark(), len(fetches)
            eng.step()
            launched = [(s.args['shape'], s.args['batch'],
                         s.args.get('prefill_rows', 0), s.args['in_flight'])
                        for s in prof.spans(since_id=mark)
                        if s.name == 'serve::compiled_step']
            return launched, len(fetches) - before
        # its one chunk, and behind it its first decode row
        assert call() == ([('mixed', 0, 1, 0), ('decode', 1, 0, 1)], 1)
        assert len(first.generated) == 1
        late = eng.submit(prompts_of(95, (4,))[0], **greedy(2))
        # the step queued in the last call lands; the late prompt's
        # chunk is launched now, behind it, with the row's next token
        assert call() == ([('mixed', 1, 1, 1)], 1)
        assert len(first.generated) == 2 and late.generated == []
        assert late.state == RequestState.PREFILL
        assert call() == ([('decode', 2, 0, 1)], 1)
        assert len(first.generated) == 3 and len(late.generated) == 1
        # the late request's budget ends in the step in flight: it
        # rides nothing more
        assert call() == ([('decode', 1, 0, 1)], 1)
        assert late.state == RequestState.FINISHED
        assert len(first.generated) == 4
        assert call() == ([('decode', 1, 0, 1)], 1)
        # nothing left to launch: the call only lands
        assert call() == ([], 1)
        assert first.state == RequestState.FINISHED and not fetches[6:]
        assert not eng.scheduler.has_work and eng._flight is None
        assert call() == ([], 0)
    finally:
        monkeypatch.setattr(engine_mod, '_host_fetch', real)
        eng.shutdown()


def test_the_gap_monitor_tells_a_busy_wait_from_a_dry_one(gpt):
    """A fetch with the next step queued behind it waits on a busy
    device (blocked, depth 2); drained after every step it waits with
    the device's queue empty (gating, depth 1): the ledger's
    host_bound_fraction tells the two apart."""
    seen = {}
    for drained in (False, True):
        eng = engine(gpt)
        if drained:
            eng._holds_back = lambda: 'idle'
        try:
            eng.submit(prompts_of(96, (4,))[0], **greedy(24))
            while eng.scheduler.has_work:
                eng.step()
            snap = eng._gap.snapshot()
            seen[drained] = (snap['dispatch_depth_max'],
                             snap['host_gap_seconds'],
                             snap['blocked_wait_seconds'],
                             eng.ledger.account()['host_bound_fraction'])
        finally:
            eng.shutdown()
    depth, gating, blocked, bound = seen[False]
    assert depth == 2 and blocked > gating
    assert seen[True][0] == 1 and seen[True][1] > 0.0
    assert bound < seen[True][3]
