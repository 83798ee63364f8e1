"""`serve::device_step` (ISSUE 36): one record per landed step, made
where the step lands — what rode it and how long the device had it. At
toy sizes on the CPU (nothing timed here is a device number): the
records tile the time axis, their counts add up to the engine's own
counters, a drained step carries its reason, a dispatch that fetches
nothing is counted in the record the next fetch closes, and
`_decode_time` is the sum of the records it is defined on."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.profiler as prof
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.serving.engine import DRAIN_REASONS, PREFILL_ROWS

VOCAB, SLOTS, CHUNK, PAGE = 96, 4, 4, 4
ARGS = {'step', 'steps', 'dispatches', 'shape', 'decode_rows', 'chunks',
        'chunk_tokens', 'chunk_slots', 'emitted', 'behind', 'late'}


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


def engine(model, **kw):
    kw.setdefault('num_pages', 96)
    kw.setdefault('prefix_cache', False)
    return ServingEngine(model, ServingConfig(
        page_size=PAGE, max_batch_size=SLOTS, prefill_chunk=CHUNK,
        max_pages_per_seq=24, seed=5, **kw))


def prompts_of(seed, lengths):
    rng = np.random.RandomState(seed)
    return [list(rng.randint(1, VOCAB, n)) for n in lengths]


def run(eng, lengths, new_tokens=5, seed=11):
    """Submit, step until idle -> (requests, every span of the run)."""
    mark = prof.mark()
    reqs = [eng.submit(p, max_new_tokens=new_tokens, top_k=0)
            for p in prompts_of(seed, lengths)]
    while eng.scheduler.has_work:
        eng.step()
    return reqs, prof.spans(since_id=mark)


def records(spans):
    return [s for s in spans if s.name == 'serve::device_step']


@pytest.fixture(scope='module')
def served(gpt):
    """Prompts longer than two chunks (and more of them than the
    prefill group has rows, so steps dispatch twice and a trailing
    dispatch of inner chunks fetches nothing), run to the end."""
    eng = engine(gpt)
    reqs, spans = run(eng, (13, 3, 18, 9, 11, 6))
    stats = eng.stats()
    decode_time = eng._decode_time
    eng.shutdown()
    return reqs, spans, stats, decode_time


def test_one_record_per_landed_step_with_every_arg(served):
    reqs, spans, stats, _ = served
    recs = records(spans)
    assert recs and all(r.parent == 0 and r.cat == 'serve' for r in recs)
    for r in recs:
        assert ARGS <= set(r.args) <= ARGS | {'drain'}
        assert r.args['shape'] in ('decode', 'mixed')
        assert (r.args['shape'] == 'mixed') == (r.args['chunks'] > 0)
        assert r.args['steps'] >= 1 and r.args['dispatches'] >= 1
        assert r.dur_ns > 0
    # every launched step is counted once, in the record its fetch (or,
    # for one that fetched nothing, its successor's) closed
    steps = round(stats['dispatches_total'] / stats['dispatches_per_step'])
    assert sum(r.args['steps'] for r in recs) == steps
    assert len(recs) <= steps
    # the ordinal is the LAUNCHING serve::step's: never later than the
    # step that landed it, and in order
    ordinals = [r.args['step'] for r in recs]
    assert ordinals == sorted(ordinals)
    step_of = {s.id: s.args['step'] for s in spans if s.name == 'serve::step'}
    assert max(ordinals) <= max(step_of.values())


def test_the_counts_add_up_to_the_engines_own(served):
    reqs, spans, stats, _ = served
    recs = records(spans)
    assert sum(r.args['emitted'] for r in recs) == sum(
        len(r.generated) for r in reqs)
    assert sum(r.args['chunk_tokens'] for r in recs) \
        == stats['prefill_tokens_total'] == sum(len(r.prompt) for r in reqs)
    assert sum(r.args['chunks'] for r in recs) == stats[
        'prefill_chunks_total']
    compiled = [s for s in spans if s.name == 'serve::compiled_step']
    assert sum(r.args['dispatches'] for r in recs) == len(compiled) \
        == stats['dispatches_total']
    mixed = [s for s in compiled if s.args['shape'] == 'mixed']
    assert sum(r.args['chunk_slots'] for r in recs) \
        == len(mixed) * PREFILL_ROWS * CHUNK
    assert 1.0 - sum(r.args['chunk_tokens'] for r in recs) / sum(
        r.args['chunk_slots'] for r in recs) == pytest.approx(
        stats['padded_prefill_token_share'])
    assert sum(r.args['decode_rows'] for r in recs) == sum(
        s.args['batch'] for s in compiled)
    # the scenario has what the readers split by: steps of one decode
    # dispatch, of one dispatch with chunks, and of two dispatches
    kinds = {(r.args['dispatches'] >= 2, r.args['chunks'] > 0)
             for r in recs}
    assert {(False, False), (False, True), (True, True)} <= kinds


def test_the_records_tile_the_time_axis(served):
    _, spans, _, _ = served
    recs = records(spans)
    fetch_ends = {s.start_ns + s.dur_ns for s in spans
                  if s.name == 'serve::sample_fetch'}
    for before, r in zip(recs, recs[1:]):
        end = before.start_ns + before.dur_ns
        assert r.start_ns >= end
        if before.args['behind']:
            # the device went straight from one into the next
            assert r.start_ns == end
    for r in recs:
        # a record ends where a fetch returned (`_fetch`'s own clock
        # read, a few hundred ns after the span's)
        end = r.start_ns + r.dur_ns
        assert min(abs(end - t) for t in fetch_ends) < 2e6
    # all but the first step after idle and the drained last were
    # launched behind another
    assert sum(r.args['behind'] for r in recs) >= len(recs) - 2
    assert recs[-1].args['behind'] == 0 \
        and recs[-1].args['drain'] == 'idle'
    assert all(('drain' in r.args) == (not r.args['behind']) for r in recs)
    # `late`: the ids were there before the host asked for them (on the
    # CPU the step runs inside the call that queues it: mostly 1)
    assert {r.args['late'] for r in recs} <= {0, 1}


def test_decode_time_is_the_sum_of_the_records_with_decode_rows(served):
    _, spans, stats, decode_time = served
    recs = records(spans)
    with_rows = [r for r in recs if r.args['decode_rows']]
    assert with_rows and len(with_rows) < len(recs)
    assert decode_time == pytest.approx(
        sum(r.dur_ns for r in with_rows) * 1e-9, abs=1e-6)
    assert stats['decode_tokens_per_sec'] == pytest.approx(
        stats['decode_tokens_total'] / decode_time, rel=1e-6)


def test_a_dispatch_that_fetches_nothing_rides_the_next_record(gpt):
    """One prompt of four chunks: its first three steps carry inner
    chunks alone — no fetch, no record of their own; the record the
    first fetch closes counts all four steps and dispatches."""
    eng = engine(gpt)
    try:
        (req,), spans = run(eng, (4 * CHUNK,), new_tokens=3)
    finally:
        eng.shutdown()
    recs = records(spans)
    first = recs[0].args
    assert (first['steps'], first['dispatches'], first['chunks'],
            first['chunk_tokens'], first['decode_rows'],
            first['emitted']) == (4, 4, 4, 4 * CHUNK, 0, 1)
    # the first serve::step after idle launches two steps, so the
    # fourth was launched by the third
    assert first['step'] == 3 and first['chunk_slots'] \
        == 4 * PREFILL_ROWS * CHUNK
    # it began at the FIRST of the four launches
    launches = [s for s in spans if s.name == 'serve::compiled_step']
    assert abs(recs[0].start_ns - launches[0].start_ns) < 1e6
    assert [r.args['emitted'] for r in recs[1:]] == [1, 1]
    assert sum(r.args['steps'] for r in recs) == 6


@pytest.mark.parametrize('knob,shape', [({'spec_k': 2}, 'verify'),
                                        ({'fused_k': 4}, 'fused')])
def test_a_step_that_lands_at_once_has_its_record_and_its_reason(
        gpt, knob, shape):
    eng = engine(gpt, **knob)
    try:
        # a repeating prompt, so the n-gram proposer has drafts
        mark = prof.mark()
        reqs = [eng.submit([5, 6, 7, 8] * 3, max_new_tokens=8, top_k=0),
                eng.submit([9, 3, 9, 3, 9, 3, 9], max_new_tokens=8,
                           top_k=0)]
        while eng.scheduler.has_work:
            eng.step()
        spans = prof.spans(since_id=mark)
        stats = eng.stats()
    finally:
        eng.shutdown()
    recs = records(spans)
    at_once = [r for r in recs if r.args['shape'] == shape]
    assert at_once
    for r in at_once:
        assert r.args['drain'] == shape and r.args['behind'] == 0
        assert r.args['dispatches'] == 1 and r.args['decode_rows'] >= 1
    assert sum(r.args['emitted'] for r in recs) == sum(
        len(r.generated) for r in reqs)
    assert sum(r.args['dispatches'] for r in recs) == stats[
        'dispatches_total']
    assert sum(r.args['steps'] for r in recs) == round(
        stats['dispatches_total'] / stats['dispatches_per_step'])
    # the counter also counts the steps in flight that were drained
    # BEFORE such a step could be planned: their records say so too
    # (one that fetched nothing has no record of its own)
    assert len(at_once) <= len(
        [r for r in recs if r.args.get('drain') == shape]) \
        <= stats['pipeline_drains_total'][shape]


def test_an_abort_drains_the_step_in_flight_and_says_so(gpt):
    eng = engine(gpt)
    try:
        mark = prof.mark()
        keep, drop = [eng.submit(p, max_new_tokens=12, top_k=0)
                      for p in prompts_of(3, (5, 6))]
        for _ in range(4):
            eng.step()
        eng.abort(drop)
        while eng.scheduler.has_work:
            eng.step()
        spans = prof.spans(since_id=mark)
    finally:
        eng.shutdown()
    recs = records(spans)
    reasons = [r.args['drain'] for r in recs if 'drain' in r.args]
    assert 'abort' in reasons and set(reasons) <= set(DRAIN_REASONS)
    assert sum(r.args['emitted'] for r in recs) == len(keep.generated) \
        + len(drop.generated)
