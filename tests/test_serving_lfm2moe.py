"""Lfm2MoeForCausalLM behind the ServingEngine at toy widths that keep
both operator kinds and both FF kinds (4 layers: conv with the dense FF,
attention, conv, conv with 8 experts top-2; hidden 64, pages of 4, chunks
of 8, float32): prefill in chunks then decode through pages, tails and
page snapshots against the reference's full forward; the prefix cache on
and off giving the same tokens and logits, a restore at a page end inside
a chunk, at a chunk's end and one a decode step wrote; preemption and
re-admission from the request's own pages; the page-state plane under
the allocator; the refusals that stand; the other models' programs."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.models.lfm2moe import (ATTN, CONV, Lfm2MoeConfig,
                                       Lfm2MoeForCausalLM)
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.serving.kv_pool import KVPagePool
from paddle_tpu.serving.protocol import RowGroups

from benchmarks.reference import lfm2moe as reference
from benchmarks.runners import serve_lfm2moe as runner

VOCAB, CHUNK, PAGE, H = 97, 8, 4, 64
ENGINE = dict(page_size=PAGE, max_batch_size=4, prefill_chunk=CHUNK,
              num_pages=96, max_pages_per_seq=24, prefix_cache=True)
# float32 throughout: the served path and the reference differ by the
# order of additions alone (the paged kernel's online softmax, the
# experts' sorted rows), 1e-6 of the logit scale; a wrong page, state or
# position moves a token's logit by a tenth of the scale or more
GAP = 1e-4


@pytest.fixture(scope='module')
def model():
    paddle.seed(5)
    m = Lfm2MoeForCausalLM(Lfm2MoeConfig(
        vocab_size=VOCAB, hidden_size=H,
        layer_types=[CONV, ATTN, CONV, CONV], num_dense_layers=1,
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=128,
        moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
        max_seq_len=96, dtype='float32'))
    m.eval()
    return m


def gaps(model, req):
    """Each emitted token's distance below the reference's argmax."""
    params, layer, cfg = runner.reference_view(model)
    out, n = req.output_ids(), len(req.prompt)
    ids = np.zeros(96, np.int32)
    ids[:len(out)] = out
    return reference.token_gaps(params, layer, cfg, ids,
                                np.arange(n - 1, len(out) - 1), out[n:],
                                vocab_block=40)


def serve(model, turns, **engine):
    """Sessions of turns, each turn's prompt = the turn before, its
    answer and a fresh message (`turns`: per session the message lengths
    and the answer length), submitted a session at a time, all sessions
    interleaved. -> (requests in order, stats)."""
    rng = np.random.default_rng(11)
    eng = ServingEngine(model, ServingConfig(**dict(ENGINE, **engine)))
    try:
        reqs, history = [], [[] for _ in turns]
        for t in range(max(len(m) for m, _ in turns)):
            live = []
            for s, (messages, want) in enumerate(turns):
                if t < len(messages):
                    history[s] = history[s] + rng.integers(
                        1, VOCAB, messages[t]).tolist()
                    live.append((s, eng.submit(history[s],
                                               max_new_tokens=want,
                                               top_k=0)))
            while eng.scheduler.has_work:
                eng.step()
            for s, req in live:
                history[s] = req.output_ids()
                reqs.append(req)
        return reqs, eng.stats()
    finally:
        eng.shutdown()


TURNS = [((13, 6, 9), 7), ((5, 11), 9), ((21, 3), 5)]


def test_the_toy_declares_its_state_by_slot_and_by_page(model):
    assert model.state_layers == 3
    assert model.state_spec() == [((2, H), jnp.dtype('float32'))] * 3
    assert model.page_state_spec() == [((3, 2, H), jnp.dtype('float32'))]
    assert [(s.num_kv_heads, s.head_dim, s.window)
            for s in model.kv_cache_spec()] == [(2, 16, None)]
    assert model.lm_head_weight() is model.embed        # tied


@pytest.mark.parametrize('prefix_cache', [False, True])
def test_sessions_match_the_references_full_forward(model, prefix_cache):
    """Three sessions of two and three turns: prompts over several
    chunks, decoded together; with the cache on a later turn maps its
    conversation's pages and restores the conv state at the page its hit
    ends on. Every emitted token is the reference's argmax up to float32
    noise of the logit scale."""
    reqs, stats = serve(model, TURNS, prefix_cache=prefix_cache)
    assert len(reqs) == 7
    for r in reqs:
        assert gaps(model, r).max() < GAP
    if prefix_cache:
        # the first turns share nothing; every later turn hits, and
        # every hit is one restore
        assert stats['prefix_hits_total'] == 4
        assert stats['state_restores_total'] == 4
        assert stats['state_restore_tokens_total'] == \
            sum(r.cached_tokens for r in reqs)
    else:
        assert stats['prefix_hits_total'] == stats['state_restores_total'] \
            == 0
    # 3 conv layers a live row; a snapshot each page end a token crossed
    assert stats['conv_tokens_total'] == 3 * (
        sum(len(r.prompt) - r.cached_tokens for r in reqs)
        + sum(len(r.generated) - 1 for r in reqs))
    assert stats['state_snapshots_written_total'] == sum(
        (len(r.output_ids()) - 1) // PAGE - r.cached_tokens // PAGE
        for r in reqs)
    assert stats['ssm_rows_total'] == stats['ssm_tokens_total'] == 0


def test_the_prefix_cache_on_and_off_gives_the_same_tokens(model):
    off, _ = serve(model, TURNS, prefix_cache=False)
    on, stats = serve(model, TURNS, prefix_cache=True)
    assert [r.generated for r in on] == [r.generated for r in off]
    assert sum(r.cached_tokens for r in on) > 0 and \
        stats['prefix_hit_tokens_total'] > 0


def run_prompts(model, prompts, want, **engine):
    eng = ServingEngine(model, ServingConfig(**dict(ENGINE, **engine)))
    try:
        reqs = [eng.submit(p, max_new_tokens=want, top_k=0)
                for p in prompts]
        while eng.scheduler.has_work:
            eng.step()
        return reqs, eng.stats()
    finally:
        eng.shutdown()


def test_a_preempted_request_resumes_from_the_pages_it_maps(model):
    """A pool too small for the batch preempts; with the cache on the
    victim maps what is still indexed of its tokens when it is admitted
    again — past the 12 its siblings share, pages it registered itself,
    some of them in decode — and restores the state at the page that
    ends there; it emits what an unpreempted run emits."""
    rng = np.random.default_rng(4)
    shared = rng.integers(1, VOCAB, 12).tolist()
    prompts = [shared + rng.integers(1, VOCAB, n).tolist()
               for n in (6, 5, 7, 4)]
    roomy, _ = run_prompts(model, prompts, 12)
    tight, stats = run_prompts(model, prompts, 12, num_pages=20)
    assert stats['preemptions_total'] > 0
    resumed = [r for r in tight if r.preemptions and r.cached_tokens > 12]
    assert resumed, [(r.preemptions, r.cached_tokens) for r in tight]
    assert resumed[0].cached_tokens > len(resumed[0].prompt)
    assert stats['state_restores_total'] >= stats['prefix_hits_total'] > 0
    assert [r.generated for r in tight] == [r.generated for r in roomy]
    for r in tight:
        assert gaps(model, r).max() < GAP


def forward(model, state, kv, layout, tokens, tables, seq, ql, slots,
            restore):
    rows = RowGroups(layout, jnp.asarray(tables), jnp.asarray(seq),
                     jnp.asarray(ql), jnp.asarray(slots),
                     jnp.asarray(restore))
    h, new_kv, _, new_state = model.forward_paged(
        Tensor(jnp.asarray(tokens)[None]), Tensor(rows.positions(95)),
        [tuple(Tensor(a) for a in c) for c in kv], rows,
        state=[Tensor(a) for a in state])
    logits = np.asarray(h.data[0] @ model.lm_head_weight().data.T)
    return logits, [t.data for t in new_state], \
        [tuple(t.data for t in c) for c in new_kv]


@pytest.mark.parametrize('at', [12, 16, 24])
def test_a_restored_row_computes_what_the_whole_sequence_does(model, at):
    """The protocol by hand, logits and not tokens. A request of 21
    prompt tokens prefills in chunks of 8 on slot 0 (page ends at 3, 7,
    11 inside chunks and 15 at a chunk's end), then decodes 6 tokens
    (the page ends at 23 in decode). A second request maps its first
    `at` / 4 pages and computes the rest on slot 1, flagged `restore`:
    at 12 from a snapshot written inside a chunk, at 16 at a chunk's
    end, at 24 by a decode step. Its logits are the reference's."""
    rng = np.random.default_rng(at)
    ids = rng.integers(1, VOCAB, 27)
    params, layer, cfg = runner.reference_view(model)
    want = np.asarray(reference.forward(params, layer, cfg,
                                        np.r_[ids, np.zeros(69, int)]))[:27]
    B, P, pages = 4, 2, 16
    state = [jnp.zeros((B + 1,) + s, d) for s, d in model.state_spec()] + \
        [jnp.zeros((pages,) + s, d) for s, d in model.page_state_spec()]
    kv = [(jnp.zeros((pages, PAGE, 32)), jnp.zeros((pages, PAGE, 32)))]
    first = np.arange(1, 9, dtype=np.int32)          # page 0 unused
    idle = np.zeros((B, 8), np.int32)
    got = np.zeros((27, VOCAB), np.float32)
    for start in range(0, 21, CHUNK):
        n = min(CHUNK, 21 - start)
        tokens = np.zeros(B + P * CHUNK, np.int32)
        tokens[B:B + n] = ids[start:start + n]
        lg, state, kv = forward(
            model, state, kv, ((B, 1), (P, CHUNK)), tokens,
            np.concatenate([idle, first[None], idle[:1]]),
            [1] * B + [start + n, 1], [0] * B + [n, 0], [B] * B + [0, B],
            [False] * (B + P))
        got[start:start + n] = lg[B:B + n]
    for pos in range(21, 27):
        tokens = np.zeros(B, np.int32)
        tokens[0] = ids[pos]
        lg, state, kv = forward(
            model, state, kv, ((B, 1),), tokens,
            np.concatenate([first[None], idle[:3]]),
            [pos + 1, 1, 1, 1], [1, 0, 0, 0], [0, B, B, B], [False] * B)
        got[pos] = lg[0]
    scale = want.max(-1) - want.mean(-1)
    assert (np.abs(got - want).max(-1) / scale).max() < GAP
    # the second request: the first `at` tokens mapped, the rest computed
    # in one chunk on slot 1, its own pages after the shared ones
    second = first.copy()
    second[at // PAGE:] = 9 + np.arange(8 - at // PAGE)
    n = 27 - at
    for restore in (True, False):
        tokens = np.zeros(B + P * CHUNK * 2, np.int32)
        tokens[B:B + n] = ids[at:]
        lg, _, _ = forward(
            model, state, kv, ((B, 1), (P, 2 * CHUNK)), tokens,
            np.concatenate([idle, second[None], idle[:1]]),
            [1] * B + [27, 1], [0] * B + [n, 0], [B] * B + [1, B],
            [False] * B + [restore, False])
        err = (np.abs(lg[B:B + n] - want[at:]).max(-1) / scale[at:]).max()
        # without the restore the row starts from slot 1's stale tail:
        # the first positions are wrong
        assert (err < GAP) == restore, (restore, err)


def test_the_page_state_rows_follow_their_pages():
    """The pool holds the plane [num_pages, ...] after the slots' arrays;
    a page's row is handed out, parked, resurrected and evicted with the
    page: nothing in the allocator names it."""
    pool = KVPagePool(8, PAGE, num_layers=1, num_heads=1, head_dim=8,
                      prefix_cache=True, state_spec=[((2, 8), 'float32')],
                      state_slots=2,
                      page_state_spec=[((3, 2, 8), 'float32')])
    state = pool.materialize_state()
    assert [a.shape for a in state] == [(3, 2, 8), (8, 3, 2, 8)]
    assert pool.page_state_bytes() == 3 * 2 * 8 * 4
    assert pool.state_bytes() == 3 * 2 * 8 * 4 + 8 * 3 * 2 * 8 * 4
    assert pool.stats()['page_state_bytes'] == 192
    toks = list(range(1, 10))
    pool.ensure_capacity('a', 9)
    pages = pool.page_table('a')
    # the dispatch that computed pages 0 and 1 wrote their rows
    plane = state[1].at[pages[0]].set(1.0).at[pages[1]].set(2.0)
    pool.register_prefix('a', toks, 8)
    pool.release('a')                       # parked, rows as they were
    assert pool.cached_pages == 2
    assert pool.match_and_map('b', toks, limit=len(toks) - 1) == 8
    assert pool.page_table('b') == pages[:2]      # the same rows
    assert float(plane[pool.page_table('b')[1]].max()) == 2.0
    pool.release('b')
    # pressure: the parked pages are evicted and handed out again, to be
    # written by whoever computes their last token next
    pool.ensure_capacity('c', 8 * PAGE)
    assert pool.cached_pages == 0 and set(pages[:2]) <= \
        set(pool.page_table('c'))
    assert pool.match_and_map('d', toks, limit=8) == 0


REFUSED = {
    'host tier': (dict(host_tier_pages=8), 'a resurrected prefix resumes'),
    'fused_k': (dict(fused_k=4), 'a window cut short resumes inside it'),
    'spec_k': (dict(spec_k=2), 'a rejected draft resumes before it'),
    'int8 pool': (dict(kv_dtype='int8'), 'no int8 form and no mp split'),
    'int8 weights': (dict(weight_dtype='int8'),
                     'no int8 form and no mp split'),
}


@pytest.mark.parametrize('what', sorted(REFUSED))
def test_what_still_resumes_inside_a_page_is_refused(model, what):
    knobs, why = REFUSED[what]
    with pytest.raises(NotImplementedError, match='holds recurrent state') \
            as err:
        ServingEngine(model, ServingConfig(**dict(ENGINE, **knobs)))
    assert why in str(err.value)
    assert 'prefix_cache' not in str(err.value)


def test_adopt_request_is_refused(model):
    from paddle_tpu.serving.scheduler import Request
    eng = ServingEngine(model, ServingConfig(**ENGINE))
    try:
        with pytest.raises(NotImplementedError, match='adopt_request'):
            eng.adopt_request(Request([1, 2, 3]))
    finally:
        eng.shutdown()


def test_the_hit_names_the_page_it_restores_from(model):
    eng = ServingEngine(model, ServingConfig(**dict(ENGINE, trace=True)))
    try:
        first = eng.submit(list(range(1, 14)), max_new_tokens=2, top_k=0)
        while eng.scheduler.has_work:
            eng.step()
        later = eng.submit(first.output_ids() + [5, 6], max_new_tokens=2,
                           top_k=0)
        eng.step()                      # admitted, its prefix mapped
        table = eng.pool.page_table(later.id)
        while eng.scheduler.has_work:
            eng.step()
        hit = [e for e in eng.tracer.events(later.id)
               if e['event'] == 'prefix_hit']
    finally:
        eng.shutdown()
    # 14 ids of the first turn were resident: 3 pages; the chunk starts
    # at 12 from the snapshot of the page that ends at 11
    assert later.cached_tokens == 12 and len(hit) == 1
    assert hit[0]['restored_page'] == table[2]


def test_two_step_programs_with_the_operands_they_had(model):
    """The two programs of every server; the model's state arrays (the
    tails and the plane), its slots and its restore flags are operands
    the other models' programs do not have (test_serving_phi4flash.py
    holds theirs)."""
    eng = ServingEngine(model, ServingConfig(**ENGINE))
    seen = {}
    build = eng._build_step

    def recording(key):
        fn = build(key)

        def run(params, kv, *rest):
            seen[key] = len(jax.tree_util.tree_leaves(rest))
            return fn(params, kv, *rest)
        return run
    eng._build_step = recording
    try:
        for p in ([5, 6, 7, 8, 9, 10], [11, 12]):
            eng.submit(p, max_new_tokens=3, top_k=0)
        while eng.scheduler.has_work:
            eng.step()
    finally:
        eng.shutdown()
    assert set(seen) == {('mixed', 4, 2, CHUNK, False), (4, 1, False, False)}
    # 10 host operands, the experts' counters, 3 tails + the plane, the
    # slots, the restore flags and the decode rows' shared run (its keys,
    # pages and member rows in one array: the prefix cache is on)
    assert set(seen.values()) == {10 + 1 + 4 + 1 + 1 + 1}
