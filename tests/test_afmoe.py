"""AFMoE (sparse experts, grouped-query attention, window and full
layers) against its plain float32 reference, at a small size on the
CPU: the model's forward, the serving engine's chunked prefill and
decode through the paged cache, and the expert layer's share of a
layer (`experts_held`)."""
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu.core import flags  # noqa: E402
from paddle_tpu.core.tensor import Tensor  # noqa: E402
from paddle_tpu.models.afmoe import AfmoeConfig, AfmoeForCausalLM  # noqa: E402
from paddle_tpu.ops import moe  # noqa: E402
from paddle_tpu.serving import ServingConfig, ServingEngine  # noqa: E402
from paddle_tpu.serving.protocol import RowGroups  # noqa: E402
from benchmarks.common import Manifest  # noqa: E402
from benchmarks.reference import afmoe as reference  # noqa: E402

runner = Manifest().load_module('runners', 'serve_afmoe')
PAGE, WINDOW, VOCAB = 16, 24, 97


def tiny(**kw):
    """H 64, 4 query / 2 kv heads of 16, 8 experts top-2 + 1 shared,
    window 24 over pages of 16, 1 dense + 4 expert layers [s, s, s, f]."""
    kw.setdefault('dtype', 'float32')
    return AfmoeConfig(
        vocab_size=VOCAB, hidden_size=64, num_layers=5, num_dense_layers=1,
        num_heads=4, num_kv_heads=2, head_dim=16, intermediate_size=96,
        moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
        sliding_window=WINDOW,
        layer_types=['sliding_attention'] * 4 + ['full_attention'],
        max_seq_len=128, **kw)


@pytest.fixture(scope='module')
def model():
    paddle.seed(3)
    m = AfmoeForCausalLM(tiny())
    m.eval()
    # the balancing bias is seeded non-zero by the initialiser
    assert all(float(jnp.abs(b.data).max()) > 0
               for _, b in m.named_buffers())
    return m


@pytest.fixture(scope='module')
def ids():
    return np.random.default_rng(0).integers(1, VOCAB, (70,))


@pytest.fixture
def kernels():
    """The Pallas bodies (interpret mode) in place of the dense routes."""
    names = ('FLAGS_paged_attention_kernel',
             'FLAGS_moe_grouped_matmul_kernel')
    flags.set_flags({n: True for n in names})
    yield
    flags.set_flags({n: None for n in names})


def test_forward_matches_the_reference(model, ids):
    params, layer, cfg = runner.reference_view(model)
    want, chosen = reference.forward(params, layer, cfg, ids)
    got = model(jnp.asarray(ids[None])).data[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5)
    assert len(chosen) == 4 and chosen[0].shape == (70, 2)


@pytest.mark.parametrize('route', ['dense', 'kernels'])
def test_paged_prefill_then_decode_matches_the_full_forward(
        model, ids, route, request):
    """Prefill in chunks of 20, then decode token by token, through a
    hand-built page table: contexts cross the window (24) and page
    edges (16, 32, 48), and every position's logits are the reference's
    full forward's."""
    if route == 'kernels':
        request.getfixturevalue('kernels')
    params, layer, cfg = runner.reference_view(model)
    want = np.asarray(reference.forward(params, layer, cfg, ids[:60])[0])
    pages, width = 8, 2 * 16
    kv = [(Tensor(jnp.zeros((pages, PAGE, width), jnp.float32)),) * 2
          for _ in range(5)]
    table = jnp.asarray([[5, 2, 7, 0, 3, 1, 4, 6]], jnp.int32)
    head = model.lm_head_weight().data
    got, done = [], 0
    for n in (20, 20, 9) + (1,) * 11:
        tok = np.zeros((1, 20 if n > 1 else 1), np.int32)
        tok[0, :n] = ids[done:done + n]
        pos = np.clip(done + np.arange(tok.shape[1]), 0, 127)[None]
        h, kv, _ = model.forward_paged(
            Tensor(jnp.asarray(tok)), Tensor(jnp.asarray(pos, jnp.int32)),
            kv, RowGroups([tok.shape], table,
                          jnp.asarray([done + n], jnp.int32),
                          jnp.asarray([n], jnp.int32)))
        got.append(np.asarray(h.data[0, :n] @ head.T))
        done += n
    np.testing.assert_allclose(np.concatenate(got), want, atol=5e-5)


@pytest.mark.parametrize('route', ['dense', 'kernels'])
def test_the_engine_serves_it_as_the_reference_computes_it(
        model, ids, route, request):
    if route == 'kernels':
        request.getfixturevalue('kernels')
    eng = ServingEngine(model, ServingConfig(
        page_size=PAGE, max_batch_size=4, prefill_chunk=32, num_pages=64,
        max_pages_per_seq=8))
    last_chunks = {}
    eng.moe_rows_listener = lambda req, start, n, rows: \
        last_chunks.__setitem__(req.id, (start, n, rows))
    try:
        # prompts below, at and past the window; answers that cross it
        reqs = [eng.submit(list(map(int, ids[:n])), max_new_tokens=12,
                           top_k=0) for n in (5, 33, 50, 17, 24)]
        while not all(r.done for r in reqs):
            eng.step()
        check = runner.compare(model, [(r, 12) for r in reqs], 80,
                               last_chunks)
        stats, block = eng.stats(), eng.ledger.roofline()
    finally:
        eng.shutdown()
    assert check['tokens'] == 60 and check['logit_gap'] < 1e-4
    # the last prompt chunks (a few tokens each, after earlier chunks
    # or a cached prefix; the rest of each dispatch padding) routed
    # every row where the reference does
    spans = [last_chunks[r.id][:2] for r in reqs]
    assert [a + n for a, n in spans] == [5, 33, 50, 17, 24]
    assert spans[0] == (0, 5) and spans[2] == (32, 18)
    assert check['routed_rows'] == sum(n for _, n in spans) * 2 * 4
    assert check['routed_rows_moved'] == 0.0
    # the counters rode the fetches: every expert-layer call is there,
    # each routed top-k rows a LIVE token and none for padding
    assert stats['moe_calls_total'] % 4 == 0
    assert stats['moe_rows_total'] == 2 * 4 * (
        stats['decode_tokens_total'] + stats['prefill_tokens_total'])
    assert 0 < stats['moe_experts_touched_total'] \
        <= 8 * stats['moe_calls_total']
    assert stats['moe_load_steps'] == stats['decode_steps_total']
    assert block['moe_load_max_over_mean'] >= 1.0
    assert 0 < block['kv_read_tokens_window'] < block['kv_read_tokens_full']


def test_routes_the_engine_does_not_serve_raise(model):
    for kw in ({'fused_k': 2}, {'spec_k': 2}, {'kv_dtype': 'int8'},
               {'weight_dtype': 'int8'}):
        with pytest.raises(NotImplementedError, match='lacks the'):
            ServingEngine(model, ServingConfig(
                page_size=PAGE, max_batch_size=2, num_pages=16, **kw))


def test_the_shares_of_a_layer_add_up_to_the_layer(model):
    """Four chips of two experts each: every share routes over all
    eight experts and adds its own experts' part; with the shared
    expert counted once the parts are the uncut reference's layer."""
    sparse = model.layers[1].mlp
    _, layer, cfg = runner.reference_view(model)
    p = layer(1)
    m = jnp.asarray(np.random.default_rng(1).standard_normal((37, 64)),
                    jnp.float32)
    chosen, weights = reference._route(m, p['router'], p['expert_bias'],
                                       2, cfg['route_scale'], True)
    whole = reference._swiglu(m, p['shared_w1'], p['shared_w3'],
                              p['shared_w2']) \
        + reference._experts(m, chosen, weights, p, (0, 8))
    ex = sparse.experts
    parts, rows = [], []
    for first in range(0, 8, 2):
        got_chosen, got_w = moe.route(m, sparse.router.data,
                                      sparse.expert_bias.data, 2,
                                      cfg['route_scale'], True)
        out, n = moe.experts_swiglu(
            m, got_chosen, got_w, ex.w1.data[first:first + 2],
            ex.w3.data[first:first + 2], ex.w2.data[first:first + 2],
            experts_held=(first, 2))
        parts.append(out)
        rows.append(np.asarray(n))
    total = sum(parts) + sparse.shared(m)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=2e-5)
    assert np.concatenate(rows).sum() == 37 * 2     # no pair dropped
    # and the model told it holds a share computes that share alone
    paddle.seed(3)
    share = AfmoeForCausalLM(tiny(experts_held=(2, 2)))
    assert tuple(share.layers[1].mlp.experts.w1.shape) == (2, 64, 32)
    assert tuple(share.moe_counters().shape) == (4, 3)


def test_padding_rows_are_routed_to_no_expert(model):
    """A chunk's or a batch's padding (`live` false): its pairs reach no
    expert, are not counted, and its output is 0; the live rows' output
    is what it is without the mask. Counted by group, each group's live
    rows are counted apart."""
    sparse = model.layers[1].mlp
    m = jnp.asarray(np.random.default_rng(2).standard_normal((12, 64)),
                    jnp.float32)
    live = jnp.arange(12) < 7
    ex = sparse.experts
    chosen, weights = moe.route(m, sparse.router.data,
                                sparse.expert_bias.data, 2, 2.826, True)
    args = (m, chosen, weights, ex.w1.data, ex.w3.data, ex.w2.data)
    whole, rows_whole = moe.experts_swiglu(*args)
    part, rows = moe.experts_swiglu(*args, live=live)
    assert int(rows_whole.sum()) == 12 * 2 and int(rows.sum()) == 7 * 2
    np.testing.assert_array_equal(
        np.asarray(rows), np.bincount(np.asarray(chosen[:7]).ravel(),
                                      minlength=8))
    np.testing.assert_allclose(np.asarray(part[:7]), np.asarray(whole[:7]),
                               atol=1e-6)
    assert not np.asarray(part[7:]).any()
    group = np.asarray([0, 0, 0, 1, 1, 2, 2, 2, 2, 1, 0, 2], np.int32)
    same, by_group = moe.experts_swiglu(
        *args, live=live, counted=(jnp.asarray(group), 3))
    np.testing.assert_array_equal(np.asarray(same), np.asarray(part))
    np.testing.assert_array_equal(
        np.asarray(by_group),
        [np.bincount(np.asarray(chosen)[:7][group[:7] == g].ravel(),
                     minlength=8) for g in range(3)])


def test_a_model_declares_the_routes_it_is_written_for(model):
    from paddle_tpu.models.gpt import GPTForCausalLM
    assert model.paged_routes == ('plain',)
    assert set(GPTForCausalLM.paged_routes) == {
        'plain', 'fused', 'verify', 'int8_kv', 'int8_weights', 'mp'}
    with pytest.raises(NotImplementedError, match=r"lacks the \['fused'"):
        ServingEngine(model, ServingConfig(
            page_size=PAGE, max_batch_size=2, num_pages=16, fused_k=2))
