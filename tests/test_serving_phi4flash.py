"""Phi4FlashForCausalLM behind the ServingEngine at toy widths that keep
all four layer kinds (8 layers: Mamba, window attention, the full layer,
a gated memory unit, a cross layer; hidden 64, window 8, chunk 4):
prefill then decode through pages AND recurrent state against the
reference's full forward, preemption, the refusals, and the other
models' step programs left as they were."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.models.phi4flash import (ATTN, CROSS, GMU, MAMBA,
                                         Phi4FlashConfig,
                                         Phi4FlashForCausalLM)
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.serving.protocol import RowGroups

from benchmarks.reference import phi4flash as reference
from benchmarks.runners import serve_phi4flash as runner

VOCAB, WINDOW, CHUNK, PAGE = 97, 8, 4, 4
ENGINE = dict(page_size=PAGE, max_batch_size=4, prefill_chunk=CHUNK,
              num_pages=64, max_pages_per_seq=16, prefix_cache=False)


@pytest.fixture(scope='module')
def model():
    paddle.seed(3)
    m = Phi4FlashForCausalLM(Phi4FlashConfig(
        vocab_size=VOCAB, hidden_size=64, num_layers=8, num_heads=4,
        num_kv_heads=2, intermediate_size=128, sliding_window=WINDOW,
        max_seq_len=64, dtype='float32'))
    m.eval()
    return m


def reference_logits(model, ids, rows=None):
    params, layer, cfg = runner.reference_view(model)
    return np.asarray(reference.forward(params, layer, cfg,
                                        np.asarray(ids, np.int32), rows))


def serve(model, prompts, new_tokens, **engine):
    eng = ServingEngine(model, ServingConfig(**dict(ENGINE, **engine)))
    try:
        reqs = [eng.submit(p, max_new_tokens=n, top_k=0)
                for p, n in zip(prompts, new_tokens)]
        while eng.scheduler.has_work:
            eng.step()
        return reqs, eng.stats(), sorted(map(str, eng._step_fns))
    finally:
        eng.shutdown()


def test_the_toy_keeps_all_four_kinds_and_shares_one_plane(model):
    cfg = model.config
    assert cfg.layer_kinds == [MAMBA, ATTN, MAMBA, ATTN, MAMBA, ATTN, GMU,
                               CROSS]
    spec = model.kv_cache_spec()
    assert [(s.window, s.reads) for s in spec] == [
        (WINDOW, None), (WINDOW, None), (None, None), (None, 2)]
    assert len(model.state_spec()) == 2 * 3 and model.state_layers == 3


def test_prefill_then_decode_matches_the_references_full_forward(model):
    """Prompts under one chunk, over several chunks and past the window,
    decoded together with idle rows (5 requests on 4 slots, so one waits
    and rows go idle as others finish): every emitted token is the
    reference's argmax up to float32 noise of the logit scale."""
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, VOCAB, n).tolist() for n in (3, 11, 18, 6, 9)]
    reqs, stats, shapes = serve(model, prompts, (6, 9, 5, 12, 4))
    assert shapes == ["('mixed', 4, 2, 4, False)", '(4, 1, False, False)']
    params, layer, cfg = runner.reference_view(model)
    for r in reqs:
        out, n = r.output_ids(), len(r.prompt)
        gaps = reference.token_gaps(
            params, layer, cfg, np.asarray(out, np.int32),
            np.arange(n - 1, len(out) - 1), out[n:], vocab_block=40)
        assert len(gaps) == len(r.generated) and gaps.max() < 1e-4
    # 3 Mamba layers a live row; the tokens are prompts + decode queries
    tokens = sum(len(p) for p in prompts) \
        + sum(len(r.generated) - 1 for r in reqs)
    assert stats['ssm_tokens_total'] == 3 * tokens
    assert stats['ssm_rows_total'] >= 3 * stats['decode_tokens_total']
    assert stats['kv_planes'] == 3 and stats['kv_readers'] == 4
    assert stats['state_bytes'] == stats['pool']['state_bytes'] == \
        5 * 3 * (16 * 128 * 4 + 3 * 128 * 4)
    # and the check has teeth: a wrong token is seen
    out = reqs[2].output_ids()
    out[-2] = (out[-2] + 1) % VOCAB
    n = len(reqs[2].prompt)
    assert reference.token_gaps(
        params, layer, cfg, np.asarray(out, np.int32),
        np.arange(n - 1, len(out) - 1), out[n:]).max() > 0.05


def test_forward_paged_logits_against_the_reference(model):
    """The protocol by hand, logits and not tokens: one request prefilled
    in chunks of 4 in the mixed layout beside an idle decode group, then
    decoded in the [B, 1] layout on slot 2 beside idle rows — 18 prompt
    tokens cross four chunk boundaries and the window of 8."""
    rng = np.random.default_rng(1)
    ids = rng.integers(1, VOCAB, 24)
    n_prompt, B, P = 18, 4, 2
    want = reference_logits(model, ids)
    pages = 8
    kv = [(jnp.zeros((pages, PAGE, 32)), jnp.zeros((pages, PAGE, 32)))
          for _ in range(3)]
    state = [jnp.zeros((B + 1,) + shape, dt)
             for shape, dt in model.state_spec()]
    table = np.arange(1, 8)[None, :].astype(np.int32)       # page 0 unused
    head = model.lm_head_weight().data

    def call(layout, tokens, tables, seq, ql, slots):
        nonlocal kv, state
        rows = RowGroups(layout, jnp.asarray(tables), jnp.asarray(seq),
                         jnp.asarray(ql), jnp.asarray(slots))
        h, new_kv, _, new_state = model.forward_paged(
            Tensor(jnp.asarray(tokens)[None]), Tensor(rows.positions(63)),
            [tuple(Tensor(a) for a in c) for c in kv], rows,
            state=[Tensor(a) for a in state])
        kv = [tuple(t.data for t in c) for c in new_kv]
        state = [t.data for t in new_state]
        return np.asarray(h.data[0] @ head.T)

    idle_tables = np.zeros((B, 7), np.int32)
    got = np.zeros((24, VOCAB), np.float32)
    for start in range(0, n_prompt, CHUNK):
        n = min(CHUNK, n_prompt - start)
        tokens = np.zeros(B + P * CHUNK, np.int32)
        tokens[B:B + n] = ids[start:start + n]
        lg = call(((B, 1), (P, CHUNK)), tokens,
                  np.concatenate([idle_tables, table, table * 0]),
                  [1] * B + [start + n, 1], [0] * B + [n, 0],
                  [B] * B + [2, B])
        got[start:start + n] = lg[B:B + n]
    for pos in range(n_prompt, 24):
        tokens = np.zeros(B, np.int32)
        tokens[2] = ids[pos]
        seq = np.ones(B, np.int32)
        seq[2] = pos + 1
        lg = call(((B, 1),), tokens,
                  np.concatenate([idle_tables[:2], table, idle_tables[:1]]),
                  seq, [0, 0, 1, 0], [B, B, 2, B])
        got[pos] = lg[2]
    scale = want.max(-1) - want.mean(-1)
    assert (np.abs(got - want).max(-1) / scale).max() < 1e-4


def test_a_preempted_request_re_prefills_to_the_same_tokens(model):
    """A pool too small for the batch preempts the youngest request; it
    starts again at position 0 from a zero state (nothing resets its
    slot from the host) and emits what an unpreempted run emits."""
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, VOCAB, n).tolist() for n in (14, 13, 15, 12)]
    roomy, _, _ = serve(model, prompts, (14,) * 4)
    tight, stats, _ = serve(model, prompts, (14,) * 4, num_pages=20)
    assert stats['preemptions_total'] > 0
    assert [r.generated for r in tight] == [r.generated for r in roomy]


REFUSED = {
    'prefix_cache': (dict(prefix_cache=True), 'a hit resumes past its prefix'),
    'host tier': (dict(host_tier_pages=8), 'a resurrected prefix resumes'),
    'fused_k': (dict(fused_k=4), 'a window cut short resumes inside it'),
    'spec_k': (dict(spec_k=2), 'a rejected draft resumes before it'),
    'int8 pool': (dict(kv_dtype='int8'), 'no int8 form and no mp split'),
    'int8 weights': (dict(weight_dtype='int8'),
                     'no int8 form and no mp split'),
}


@pytest.mark.parametrize('what', sorted(REFUSED))
def test_the_engine_refuses_what_would_resume_without_a_state(model, what):
    knobs, why = REFUSED[what]
    with pytest.raises(NotImplementedError, match='holds recurrent state') \
            as err:
        ServingEngine(model, ServingConfig(**dict(ENGINE, **knobs)))
    assert why in str(err.value)


def test_adopt_request_is_refused(model):
    from paddle_tpu.serving.scheduler import Request
    eng = ServingEngine(model, ServingConfig(**ENGINE))
    try:
        with pytest.raises(NotImplementedError, match='adopt_request'):
            eng.adopt_request(Request([1, 2, 3]))
    finally:
        eng.shutdown()


def _operands(eng, prompts):
    """Serve `prompts`; -> {step key: leaves of (state, moe, *host
    operands)} of every program call."""
    seen = {}
    build = eng._build_step

    def recording(key):
        fn = build(key)

        def run(params, kv, *rest):
            seen[key] = len(jax.tree_util.tree_leaves(rest))
            return fn(params, kv, *rest)
        return run
    eng._build_step = recording
    for p in prompts:
        eng.submit(p, max_new_tokens=3, top_k=0)
    while eng.scheduler.has_work:
        eng.step()
    return seen


@pytest.mark.parametrize('family', ['gpt', 'afmoe', 'phi4flash'])
def test_two_step_programs_with_the_operands_they_had(family, model):
    """GPT and AFMoE: a mixed and a [B, 1] program, each with the 8 host
    operands they had (tokens, tables, seq and q lens, key, ordinals,
    temperatures, top-ks) and the two that carry a decode row's token
    from one program to the next on the device (the ids handed on, and
    `src`: ISSUE 33), AFMoE's experts' counters besides — no state, no
    slots. Only the stateful model adds its arrays and the slots."""
    if family == 'gpt':
        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
        paddle.seed(0)
        m = GPTForCausalLM(GPTConfig(
            vocab_size=VOCAB, hidden_size=32, num_layers=2, num_heads=2,
            ffn_hidden_size=64, max_seq_len=64, hidden_dropout=0.0,
            attn_dropout=0.0))
        extra = 0
    elif family == 'afmoe':
        from paddle_tpu.models.afmoe import AfmoeConfig, AfmoeForCausalLM
        paddle.seed(0)
        m = AfmoeForCausalLM(AfmoeConfig(
            vocab_size=VOCAB, hidden_size=32, num_layers=2,
            num_dense_layers=1, num_heads=4, num_kv_heads=2, head_dim=8,
            intermediate_size=64, moe_intermediate_size=16, num_experts=4,
            num_experts_per_tok=2, sliding_window=8,
            layer_types=['sliding_attention', 'full_attention'],
            max_seq_len=64, dtype='float32'))
        extra = 1                       # the experts' counters
    else:
        m = model
        extra = len(model.state_spec()) + 1     # its arrays, the slots
    m.eval()
    eng = ServingEngine(m, ServingConfig(**ENGINE))
    try:
        assert (eng.pool.state is None) == (family != 'phi4flash')
        seen = _operands(eng, [[5, 6, 7, 8, 9, 10], [11, 12]])
    finally:
        eng.shutdown()
    assert set(seen) == {('mixed', 4, 2, CHUNK, False), (4, 1, False, False)}
    assert set(seen.values()) == {10 + extra}
