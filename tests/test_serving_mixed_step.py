"""The mixed step (ISSUE 31): decode rows and prompt chunks in ONE
compiled program whose token-wise work runs once over every token of
the dispatch. At a small size on the CPU: the tokens are those of the
same requests served one at a time, the experts' rows are counted per
row group, the compiled shapes are two, the prefill group's rows, the
dispatches and fetches of a crowded step, and a preemption between a
chunk's reservation and its dispatch."""
import math

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.profiler as prof
import paddle_tpu.serving.engine as engine_mod
from paddle_tpu.models.afmoe import AfmoeConfig, AfmoeForCausalLM
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.serving import RequestState, ServingConfig, ServingEngine
from paddle_tpu.serving.engine import PREFILL_ROWS

VOCAB, SLOTS, CHUNK, PAGE = 96, 8, 8, 8


@pytest.fixture(scope='module')
def dense():
    paddle.seed(7)
    m = GPTForCausalLM(GPTConfig(
        vocab_size=VOCAB, hidden_size=64, num_layers=2, num_heads=2,
        max_seq_len=128, hidden_dropout=0.0, attn_dropout=0.0,
        use_flash_attention=False))
    m.eval()
    return m


@pytest.fixture(scope='module')
def sparse():
    """8 experts top-2 + 1 shared, window 24: 1 dense + 2 expert layers
    [sliding, sliding, full]."""
    paddle.seed(3)
    m = AfmoeForCausalLM(AfmoeConfig(
        vocab_size=VOCAB, hidden_size=64, num_layers=3, num_dense_layers=1,
        num_heads=4, num_kv_heads=2, head_dim=16, intermediate_size=96,
        moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
        sliding_window=24,
        layer_types=['sliding_attention'] * 2 + ['full_attention'],
        max_seq_len=128, dtype='float32'))
    m.eval()
    return m


@pytest.fixture(params=['dense', 'sparse'])
def model(request):
    return request.getfixturevalue(request.param)


def engine(model, **kw):
    kw.setdefault('num_pages', 160)
    return ServingEngine(model, ServingConfig(
        page_size=PAGE, max_batch_size=SLOTS, prefill_chunk=CHUNK,
        max_pages_per_seq=16, prefix_cache=False, **kw))


def drain(eng):
    while eng.scheduler.has_work:
        eng.step()


def prompts_of(seed, lengths):
    rng = np.random.RandomState(seed)
    return [[int(t) for t in rng.randint(1, VOCAB, n)] for n in lengths]


def alone(model, prompts, new, listen=None):
    """Each request through an engine of its own turn: one at a time."""
    outs = []
    eng = engine(model)
    eng.moe_rows_listener = listen
    for p in prompts:
        outs.append(eng.generate([p], max_new_tokens=new, top_k=0)[0])
    eng.shutdown()
    return outs


def crowd(eng, running, arriving, new, drained=False):
    """`running` decode until each has a token; then `arriving` are
    submitted together, so that they prefill beside the running rows.
    `drained`: step by step, with none in flight when they arrive."""
    reqs = [eng.submit(p, max_new_tokens=new, top_k=0) for p in running]
    while not all(r.state == RequestState.RUNNING for r in reqs):
        eng.step()
        if drained:
            eng._drain()
    reqs += [eng.submit(p, max_new_tokens=new, top_k=0) for p in arriving]
    return reqs


P = PREFILL_ROWS


@pytest.mark.parametrize('arriving', [1, P, P + 3])
def test_tokens_are_those_of_the_requests_served_one_at_a_time(
        model, arriving):
    running = prompts_of(1, (5, 11, 3))
    late = prompts_of(2, (19, 9, 26, 8, 13)[:arriving])
    want = alone(model, running + late, 9)
    eng = engine(model)
    reqs = crowd(eng, running, late, 9)
    mark = prof.mark()
    eng.step()
    # the step after the arrivals: every one of them prefills beside
    # the three decoding rows, the first P in the decode rows' program
    steps = [s.args for s in prof.spans(since_id=mark)
             if s.name == 'serve::compiled_step']
    assert [(a['shape'], a['batch'], a['prefill_rows']) for a in steps] == [
        ('mixed', 3 if i == 0 else 0, min(P, arriving - i * P))
        for i in range(math.ceil(arriving / P))]
    drain(eng)
    assert [r.output_ids() for r in reqs] == want
    st = eng.stats()
    assert st['prefill_tokens_total'] == sum(map(len, running + late))
    assert st['requests_completed_total'] == len(reqs)
    assert eng.pool.pages_in_use == 0
    eng.shutdown()


def test_a_requests_last_chunk_is_counted_alone(sparse, monkeypatch):
    """What `moe_rows_listener` hears for a prompt's last chunk — its
    own row group of a dispatch that carried decode rows and other
    chunks — is what that chunk routes when dispatched alone; the
    decode rows' load is counted from their group, and the counters
    count each call's union."""
    running = prompts_of(3, (4, 7))
    late = prompts_of(4, (21, 9, 14, 30))
    heard_alone, heard = {}, {}
    alone(sparse, running + late, 6,
          listen=lambda r, a, n, rows: heard_alone.__setitem__(
              tuple(r.prompt), (a, n, rows)))
    monkeypatch.setattr(engine_mod, 'PREFILL_ROWS', 3)
    eng = engine(sparse)
    eng.moe_rows_listener = lambda r, a, n, rows: heard.__setitem__(
        tuple(r.prompt), (a, n, rows))
    reqs = crowd(eng, running, late, 6)
    drain(eng)
    assert set(heard) == set(heard_alone) == {
        tuple(p) for p in running + late}
    for key, (start, n, rows) in heard_alone.items():
        assert heard[key][:2] == (start, n)
        assert rows.shape == (2, 8) and rows.sum() == 2 * 2 * n
        np.testing.assert_array_equal(heard[key][2], rows)
    st = eng.stats()
    # every live token's top-2 rows in both expert layers, none for
    # padding or idle rows, whatever rode together
    assert st['moe_rows_total'] == 2 * 2 * (
        st['decode_tokens_total'] + st['prefill_tokens_total'])
    assert st['moe_calls_total'] == 2 * st['dispatches_total']
    assert 0 < st['moe_experts_touched_total'] <= 8 * st['moe_calls_total']
    assert st['moe_load_steps'] == st['decode_steps_total']
    assert all(len(r.generated) == 6 for r in reqs)
    eng.shutdown()


def test_two_compiled_shapes_and_no_prefill_program(model):
    eng = engine(model)
    reqs = crowd(eng, prompts_of(5, (6, 12)), prompts_of(6, (17, 9, 25)), 7)
    drain(eng)
    assert all(len(r.generated) == 7 for r in reqs)
    assert sorted(map(str, eng._step_fns)) == sorted(map(str, [
        (SLOTS, 1, False, False), ('mixed', SLOTS, P, CHUNK, False)]))
    st = eng.stats()
    assert st['dispatches_per_step'] < 1.5
    assert 1.0 <= st['prefill_rows_per_dispatch'] <= P
    assert 0.0 < st['padded_prefill_token_share'] < 1.0
    eng.shutdown()


@pytest.mark.parametrize('slots', [1, 2, 3, 64])
def test_the_prefill_group_has_two_rows_and_never_more_than_slots(
        dense, slots):
    """P is one value for every model — the chip's sweep of both
    server cells chose it (PERF.md section 6, PR 31) — held to the
    slots there are."""
    assert PREFILL_ROWS == 2
    eng = ServingEngine(dense, ServingConfig(
        page_size=PAGE, max_batch_size=slots, prefill_chunk=CHUNK,
        max_pages_per_seq=16))
    eng.generate(prompts_of(7, (11, 4, 9)), max_new_tokens=3, top_k=0)
    assert {k for k in eng._step_fns if k[0] == 'mixed'} == {
        ('mixed', slots, min(2, slots), CHUNK, False)}
    eng.shutdown()


def test_a_crowded_step_dispatches_in_turns_and_fetches_what_is_due(
        dense, monkeypatch):
    """p requests prefilling and nothing decoding: ceil(p / P)
    dispatches of the one program, a fetch only after those with a row
    whose prompt ends."""
    fetches = []
    real = engine_mod._host_fetch
    monkeypatch.setattr(engine_mod, '_host_fetch',
                        lambda x: fetches.append(1) or real(x))
    eng = engine(dense)
    # slots 0..4: prompts of 2, 1, 2, 2, 1 chunks
    reqs = [eng.submit(p, max_new_tokens=3, top_k=0)
            for p in prompts_of(8, (12, 7, 16, 11, 5))]

    def step():
        mark, before = prof.mark(), len(fetches)
        eng.step()
        shapes = [(s.args['shape'], s.args['batch'],
                   s.args.get('prefill_rows'))
                  for s in prof.spans(since_id=mark)
                  if s.name == 'serve::compiled_step']
        return shapes, len(fetches) - before
    # step 1: rows (0, 1), (2, 3), (4): the prompts of slots 1 and 4
    # end, so the first and the third dispatch are fetched. The [B, 1]
    # program is compiled beside the mixed one, before any row decodes.
    # The call after idle launches the NEXT step too, before that
    # fetch, on what the first is known to do: the two rows whose
    # prompts end in it ride with the first two of the second chunks
    assert step() == ([('mixed', 0, 2), ('mixed', 0, 2),
                       ('mixed', 0, 1), ('mixed', 2, 2),
                       ('mixed', 0, 1)], 2)
    assert set(eng._step_fns) == {(SLOTS, 1, False, False),
                                  ('mixed', SLOTS, P, CHUNK, False)}
    assert [r.state for r in reqs] == [
        RequestState.PREFILL, RequestState.RUNNING, RequestState.PREFILL,
        RequestState.PREFILL, RequestState.RUNNING]
    # step 2: every row decodes in the step it launches; the second
    # step lands, both of its dispatches fetched (each ends a prompt)
    assert step() == ([('decode', 5, None)], 2)
    assert all(r.state == RequestState.RUNNING for r in reqs)
    drain(eng)
    assert all(len(r.generated) == 3 for r in reqs)
    monkeypatch.setattr(engine_mod, '_host_fetch', real)
    eng.shutdown()


def test_a_row_preempted_after_its_reservation_leaves_no_page(dense):
    """A pool with no page to spare: a decode row's growth preempts the
    youngest request, a prompt whose chunk was reserved in the same
    step. The chunk does not ride, its pages are back, and the request
    resumes to the tokens it would have had."""
    running = prompts_of(9, (16, 16))
    late = prompts_of(10, (13,))
    want = alone(dense, running + late, 6)
    # the running rows hold 2 pages each and the prompt's first chunk
    # takes a fifth; each row's 17th token needs a third page, and the
    # second row's is the seventh of six
    eng = engine(dense, num_pages=6)
    reqs = crowd(eng, running, late, 6, drained=True)
    victim = reqs[-1]
    mark = prof.mark()
    eng.step()
    steps = [s.args for s in prof.spans(since_id=mark)
             if s.name == 'serve::compiled_step']
    # (the step and, behind it, the next: the victim waits for pages)
    assert [(a['shape'], a['batch']) for a in steps] == [('decode', 2)] * 2
    assert victim.state == RequestState.WAITING
    assert victim.preemptions == 1 and victim.prefilled == 0
    assert eng.pool.page_table(victim.id) == []
    assert eng.pool.pages_in_use == sum(
        len(eng.pool.page_table(r.id)) for r in reqs[:2]) == 6
    assert eng.stats()['prefill_tokens_total'] == 32     # none of its
    drain(eng)
    assert [r.output_ids() for r in reqs] == want
    assert eng.pool.pages_in_use == 0
    eng.shutdown()
