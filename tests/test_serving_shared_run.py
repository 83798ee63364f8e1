"""The decode rows' shared run (the paged kernel's `shared=`): found by
the engine in the decode group's page tables (`shared_run`), handed to
the step programs as one more host operand, read once by the kernel
for its member rows. At toy sizes on the CPU, the kernel in interpret
mode: the run as the tables hold it and none without prefix hits; none
built for int8, latent or state-space programs or with the prefix cache
off; the counters and `serve::device_step`'s `shared_rows` agree; greedy
tokens with the run read once are the dense route's; the step programs
are traced once each."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.profiler as prof
from paddle_tpu.core import flags
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.serving.engine import shared_run
from paddle_tpu.serving.scheduler import RequestState

VOCAB, SLOTS, CHUNK, PAGE = 96, 4, 4, 4
SYSTEM = 12                     # a system prompt of three whole pages


def table(*rows, P=8):
    """Page tables [B, P] from lists of page ids (zeros past them)."""
    out = np.zeros((len(rows), P), np.int32)
    for b, r in enumerate(rows):
        out[b, :len(r)] = r
    return out


def find(rows, seq, q=None, ps=PAGE):
    seq = np.asarray(seq, np.int32)
    q = np.ones_like(seq) if q is None else np.asarray(q, np.int32)
    run, pages, members = shared_run(table(*rows), seq, q, ps)
    assert not run[pages:].any()
    return run[:pages].tolist(), members.tolist()


def test_no_run_without_a_shared_first_page():
    assert find([[1, 2], [3, 4], [5, 6]], [8, 8, 8]) == ([], [False] * 3)
    # one row alone is no run
    assert find([[1, 2, 3], [4, 5]], [12, 8]) == ([], [False, False])


def test_the_run_is_the_longest_leading_pages_the_rows_share():
    rows = [[1, 2, 3, 7], [1, 2, 3, 8, 9], [1, 2, 3, 10], [4, 5]]
    assert find(rows, [13, 20, 16, 8]) == (
        [1, 2, 3], [True, True, True, False])


def test_a_row_keeps_the_page_of_its_last_key():
    # row 0 is one token past three shared pages: all three are before
    # its last page; row 1 ends ON the third (12 tokens), so it keeps it
    # and reaches two — three pages for three rows beat two for four
    rows = [[1, 2, 3, 7], [1, 2, 3], [1, 2, 3, 8], [1, 2, 3, 9]]
    assert find(rows, [13, 12, 15, 14]) == (
        [1, 2, 3], [True, False, True, True])
    assert find(rows[:2], [13, 12]) == ([1, 2], [True, True])


def test_idle_rows_are_no_members():
    rows = [[1, 2, 3, 7], [1, 2, 3, 8], [1, 2, 3, 9]]
    assert find(rows, [13, 13, 13], q=[1, 0, 1]) == (
        [1, 2, 3], [True, False, True])
    assert find(rows, [13, 13, 13], q=[1, 0, 0]) == ([], [False] * 3)


def test_the_run_that_saves_the_most_keys_of_two_groups():
    rows = [[1, 2, 9], [1, 2, 8], [4, 5, 6, 7, 10], [4, 5, 6, 7, 11],
            [4, 5, 6, 7, 12]]
    assert find(rows, [9, 9, 17, 17, 17]) == (
        [4, 5, 6, 7], [False, False, True, True, True])


def test_a_member_that_leaves_early_does_not_cut_the_others_short():
    rows = [[1, 2, 3, 4, 9], [1, 2, 3, 4, 8], [1, 2, 3, 4, 7], [1, 6, 5]]
    assert find(rows, [17, 17, 17, 9]) == (
        [1, 2, 3, 4], [True, True, True, False])


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
    kw.setdefault('prefix_cache', True)
    return ServingEngine(model, ServingConfig(
        page_size=PAGE, max_batch_size=SLOTS, prefill_chunk=CHUNK,
        num_pages=96, max_pages_per_seq=24, seed=5, **kw))


def serve(eng, shared_prefix, kernel, new_tokens=6, n=4):
    """One request first (its prompt's pages registered), then the
    others: prompts of a common system prompt (or none) and a message
    of their own. -> (tokens of each, the run's spans)."""
    rng = np.random.RandomState(3)
    system = list(rng.randint(1, VOCAB, SYSTEM)) if shared_prefix else []
    prompts = [system + list(rng.randint(1, VOCAB, 3 + i))
               for i in range(n)]
    flags.set_flags({'FLAGS_paged_attention_kernel': kernel})
    mark = prof.mark()
    try:
        reqs = [eng.submit(prompts[0], max_new_tokens=new_tokens, top_k=0)]
        while reqs[0].state != RequestState.RUNNING:
            eng.step()
        reqs += [eng.submit(p, max_new_tokens=new_tokens, top_k=0)
                 for p in prompts[1:]]
        while eng.scheduler.has_work:
            eng.step()
    finally:
        flags.set_flags({'FLAGS_paged_attention_kernel': None})
    return [r.generated for r in reqs], prof.spans(since_id=mark)


def records(spans):
    return [s for s in spans if s.name == 'serve::device_step']


@pytest.fixture(scope='module')
def lfm2():
    from paddle_tpu.models.lfm2moe import (ATTN, CONV, Lfm2MoeConfig,
                                           Lfm2MoeForCausalLM)
    paddle.seed(5)
    m = Lfm2MoeForCausalLM(Lfm2MoeConfig(
        vocab_size=VOCAB, hidden_size=64,
        layer_types=[CONV, ATTN, CONV, CONV], num_dense_layers=1,
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=128,
        moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
        max_seq_len=96, dtype='float32'))
    m.eval()
    return m


@pytest.fixture(scope='module', params=['gpt', 'lfm2'])
def served(request):
    """The shared system prompt, the run read once (kernel) and the
    dense route; the engine of the kernel's run and its trace counts.
    The plain body (2 heads) and kv groups (4 query heads on 2)."""
    gpt = request.getfixturevalue(request.param)
    traced = []
    forward = type(gpt).forward_paged

    def counting(self, *a, **kw):
        traced.append(1)
        return forward(self, *a, **kw)
    eng = engine(gpt)
    type(gpt).forward_paged = counting
    try:
        tokens, spans = serve(eng, True, kernel=True)
        programs = set(eng._step_fns)
    finally:
        type(gpt).forward_paged = forward
        eng.shutdown()
    dense_eng = engine(gpt)
    try:
        dense, _ = serve(dense_eng, True, kernel=False)
    finally:
        dense_eng.shutdown()
    return dict(eng=eng, tokens=tokens, spans=spans, dense=dense,
                traced=len(traced), stats=eng.stats(), programs=programs)


def test_the_run_is_found_and_counted(served):
    st = served['stats']
    assert served['eng']._shared_runs
    assert st['prefix_hits_total'] >= 3
    assert st['shared_runs_total'] > 0
    assert st['shared_run_rows_total'] >= 2 * st['shared_runs_total']
    # the run is the system prompt's three pages in every attending
    # layer, for every member row: a part of the decode rows' keys
    assert st['attn_kv_tokens_shared_total'] == \
        st['kv_readers'] * SYSTEM * st['shared_run_rows_total']
    assert st['attn_kv_tokens_shared_total'] < \
        st['attn_kv_tokens_read_total'] \
        - st['attn_kv_tokens_read_chunks_total']


def test_the_records_carry_the_member_rows(served):
    recs = records(served['spans'])
    assert all('shared_rows' in r.args for r in recs)
    assert sum(r.args['shared_rows'] for r in recs) == \
        served['stats']['shared_run_rows_total']
    assert all(r.args['shared_rows'] <= r.args['decode_rows'] for r in recs)


def test_greedy_tokens_are_the_dense_routes(served):
    assert served['tokens'] == served['dense']
    assert all(len(t) == 6 for t in served['tokens'])


def test_the_two_programs_are_traced_once_each(served):
    assert served['programs'] == {
        ('mixed', SLOTS, served['eng']._prefill_rows, CHUNK, False),
        (SLOTS, 1, False, False)}
    assert served['traced'] == 2


def test_no_run_without_prefix_hits(gpt):
    eng = engine(gpt)
    try:
        _, spans = serve(eng, False, kernel=False)
        st = eng.stats()
    finally:
        eng.shutdown()
    assert st['prefix_hits_total'] == 0
    assert st['shared_runs_total'] == st['shared_run_rows_total'] == 0
    assert st['attn_kv_tokens_shared_total'] == 0
    assert {r.args['shared_rows'] for r in records(spans)} == {0}


def test_none_is_built_for_int8_or_without_the_prefix_cache(gpt):
    for kw in ({'kv_dtype': 'int8'}, {'prefix_cache': False}):
        eng = engine(gpt, **kw)
        try:
            _, spans = serve(eng, True, kernel=False, new_tokens=2, n=2)
            st = eng.stats()
        finally:
            eng.shutdown()
        assert not eng._shared_runs, kw
        assert st['shared_runs_total'] == 0
        assert all('shared_rows' not in r.args for r in records(spans))


def test_none_is_built_for_latent_or_state_space_programs():
    from test_axk1 import build
    eng = ServingEngine(build(), ServingConfig(
        page_size=PAGE, max_batch_size=SLOTS, prefill_chunk=CHUNK,
        num_pages=64, max_pages_per_seq=16, prefix_cache=True))
    assert not eng._shared_runs
    eng.shutdown()
    from paddle_tpu.models.phi4flash import (Phi4FlashConfig,
                                             Phi4FlashForCausalLM)
    paddle.seed(1)
    phi = Phi4FlashForCausalLM(Phi4FlashConfig(
        vocab_size=VOCAB, hidden_size=64, num_layers=8, num_heads=4,
        num_kv_heads=2, intermediate_size=128, sliding_window=8,
        max_seq_len=64, dtype='float32'))
    # its state refuses the prefix cache: no run is ever built
    eng = ServingEngine(phi, ServingConfig(
        page_size=PAGE, max_batch_size=SLOTS, prefill_chunk=CHUNK,
        num_pages=64, max_pages_per_seq=16, prefix_cache=False))
    assert not eng._shared_runs
    eng.shutdown()
