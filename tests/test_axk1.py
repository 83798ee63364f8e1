"""AxK1ForCausalLM (latent attention, group-limited experts, one chip's
share of the experts and of the vocabulary) at tiny widths that keep
every ratio — 4 heads, ranks 24 / 16, 16 experts in 4 groups of which 2
are kept, top-4 — against benchmarks/reference/axk1.py: logits, the
absorbed against the non-absorbed attention, chunks then decode through
latent pages, a prefix hit against a miss, the router under the group
limit, YaRN, and the shares of a layer adding up to the whole."""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.models import axk1
from paddle_tpu.models.axk1 import AxK1Config, AxK1ForCausalLM
from paddle_tpu.ops import moe
from paddle_tpu.serving.protocol import RowGroups

from benchmarks.reference import axk1 as reference
from benchmarks.runners import serve_axk1 as runner

VOCAB, PAGE, CHUNK = 96, 4, 8
YARN = {'beta_fast': 32, 'beta_slow': 1, 'factor': 32, 'mscale': 1,
        'mscale_all_dim': 1, 'original_max_position_embeddings': 16,
        'type': 'yarn'}
SIZES = dict(
    vocab_size=VOCAB, hidden_size=64, num_layers=3, num_heads=4,
    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, intermediate_size=160,
    moe_intermediate_size=32, n_routed_experts=16, num_experts_per_tok=4,
    n_group=4, topk_group=2, rope_scaling=YARN, max_seq_len=64,
    dtype='float32')


def build(seed=3, **over):
    paddle.seed(seed)
    m = AxK1ForCausalLM(AxK1Config(**dict(SIZES, **over)))
    m.eval()
    return m


@pytest.fixture(scope='module')
def model():
    return build()


def reference_logits(model, ids, rows=None):
    params, layer, cfg = runner.reference_view(model)
    return np.asarray(reference.forward(params, layer, cfg,
                                        np.asarray(ids, np.int32), rows)[0])


def test_logits_against_the_reference(model):
    """The whole model, absorbed, against the reference's non-absorbed
    float32 equations: 40 positions pass the 16 original ones, so the
    YaRN blend is exercised."""
    ids = np.random.default_rng(0).integers(1, VOCAB, (2, 40))
    got = np.asarray(model(ids).data)
    for b in range(2):
        want = reference_logits(model, ids[b])
        assert np.abs(got[b] - want).max() < 2e-5 * np.abs(want).max()


def test_absorbed_attention_is_the_non_absorbed(model):
    attn = model.layers[1].attn
    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.standard_normal((2, 24, 64)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(24), (2, 24))
    one = np.asarray(attn(a, pos, absorbed=True))
    two = np.asarray(attn(a, pos, absorbed=False))
    assert np.abs(one).max() > 1e-3
    np.testing.assert_allclose(one, two, rtol=1e-4, atol=1e-6)


def test_yarn_frequencies_and_scale_against_the_closed_form():
    """The published numbers: 64 rotary lanes, theta 10000, factor 32
    over 4096 positions, beta 32 / 1."""
    scaling = dict(YARN, original_max_position_embeddings=4096)
    got = np.asarray(axk1.yarn_inv_freq(64, 10000.0, scaling), np.float64)

    def d(r):
        return 64 * math.log(4096 / (2 * math.pi * r)) / (2 * math.log(1e4))
    lo, hi = math.floor(d(32)), math.ceil(d(1))
    assert (lo, hi) == (10, 23)
    for k in range(32):
        freq = 10000.0 ** (-2 * k / 64)
        mask = 1 - min(max((k - lo) / (hi - lo), 0), 1)
        want = freq / 32 * (1 - mask) + freq * mask
        assert got[k] == pytest.approx(want, rel=1e-5)
    assert got[5] == pytest.approx(10000.0 ** (-10 / 64), rel=1e-5)
    assert got[30] == pytest.approx(10000.0 ** (-60 / 64) / 32, rel=1e-5)
    np.testing.assert_allclose(reference.yarn(64, 10000.0, scaling)[0], got,
                               rtol=1e-5)
    m = 0.1 * math.log(32) + 1
    cfg = AxK1Config(rope_scaling=scaling)
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * m * m)
    assert cfg.softmax_scale == pytest.approx(0.0722 * 1.813, rel=1e-3)
    assert reference.yarn(64, 10000.0, scaling)[1:] == \
        (1.0, pytest.approx(m * m))
    assert AxK1Config(rope_scaling=None).softmax_scale == \
        pytest.approx(192 ** -0.5)
    assert cfg.latent_lanes == (512, 64)


def _route_loop(scores, top_k, n_group, topk_group):
    """The group-limited choice as a Python loop over tokens."""
    out = []
    for s in scores:
        groups = s.reshape(n_group, -1)
        score = [sum(sorted(g)[-2:]) for g in groups]
        kept = sorted(range(n_group), key=lambda g: -score[g])[:topk_group]
        per = len(s) // n_group
        allowed = [e for e in range(len(s)) if e // per in kept]
        out.append(sorted(sorted(allowed, key=lambda e: -s[e])[:top_k]))
    return out


def test_route_under_the_group_limit_against_a_loop():
    rng = np.random.default_rng(2)
    m = jnp.asarray(rng.standard_normal((50, 64)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((64, 16)) * 0.3, jnp.float32)
    experts, weights = moe.route(m, w, None, 4, 2.5, True, n_group=4,
                                 topk_group=2)
    scores = np.asarray(jax.nn.sigmoid(m @ w), np.float64)
    assert [sorted(e) for e in np.asarray(experts).tolist()] == \
        _route_loop(scores, 4, 4, 2)
    # at most two groups a token, the weights the chosen scores' shares
    assert all(len({e // 4 for e in row}) <= 2
               for row in np.asarray(experts))
    chosen = np.take_along_axis(scores, np.asarray(experts), -1)
    np.testing.assert_allclose(
        weights, chosen / chosen.sum(-1, keepdims=True) * 2.5, rtol=1e-5)
    # and the reference's own router picks the same
    ref_e, ref_w = reference.route(np.asarray(m), np.asarray(w), 4, 4, 2,
                                   2.5, True)
    assert np.array_equal(np.sort(ref_e, -1), np.sort(experts, -1))
    # the limit binds: without it some token picks from three groups
    free, _ = moe.route(m, w, None, 4, 2.5, True)
    assert any(len({e // 4 for e in row}) > 2 for row in np.asarray(free))


@pytest.mark.parametrize('bias', [True, False])
def test_one_group_is_the_choice_it_was(bias):
    """`n_group` 1 (the sparse cell's router) is bit-equal to the
    routing written before the limit existed."""
    rng = np.random.default_rng(3)
    m = jnp.asarray(rng.standard_normal((40, 64)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((64, 16)) * 0.3, jnp.bfloat16)
    b = jnp.asarray(rng.standard_normal(16) * 0.01, jnp.float32) \
        if bias else jnp.zeros((16,), jnp.float32)

    def was(m, router_w, bias, top_k, route_scale, route_norm):
        logits = jnp.dot(m, router_w.astype(m.dtype),
                         preferred_element_type=jnp.float32)
        scores = jax.nn.sigmoid(logits)
        _, experts = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
        if route_norm:
            weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
        return experts.astype(jnp.int32), weights * route_scale
    want = was(m, w, b, 4, 2.826, True)
    for got in (moe.route(m, w, b, 4, 2.826, True),
                moe.route(m, w, b, 4, 2.826, True, n_group=1, topk_group=1),
                moe.route(m, w, None if not bias else b, 4, 2.826, True)):
        assert np.array_equal(got[0], want[0])
        assert np.array_equal(np.asarray(got[1]), np.asarray(want[1]))


def test_the_shares_add_up(model):
    """Four chips that hold 4 of a layer's 16 experts each: their routed
    parts, and the shared expert counted ONCE, are the uncut
    reference's layer."""
    rng = np.random.default_rng(4)
    m = jnp.asarray(rng.standard_normal((30, 64)), jnp.float32)
    params, layer, cfg = runner.reference_view(model)
    p = layer(1)
    whole, _ = reference.sparse_mlp(m, p, cfg, held=(0, 16))
    with jax.default_matmul_precision('highest'):
        shared = np.asarray(reference._swiglu(
            m, p['shared_w1'], p['shared_w3'], p['shared_w2']))
    total = np.zeros_like(shared)
    pairs = 0
    for first in range(0, 16, 4):
        share = build(experts_held=(first, 4))
        mlp = share.layers[1].mlp
        mlp.router._data = p['router']
        for n in ('w1', 'w3', 'w2'):
            getattr(mlp.shared, n)._data = p['shared_' + n]
            getattr(mlp.experts, n)._data = \
                p['experts_' + n][first:first + 4]
        out, rows = mlp(m)
        total += np.asarray(out) - shared
        pairs += int(np.asarray(rows).sum())
    assert pairs == 30 * 4                  # every pair on exactly one chip
    np.testing.assert_allclose(total + shared, whole, rtol=2e-4, atol=2e-6)


def test_the_sliced_head_is_the_rows_of_the_whole(model):
    """A chip that holds the leading 32 rows of the embedding and of the
    head computes those rows' logits of the whole model."""
    part = build(vocab_held=32)
    for (n, p), (_, q) in zip(part.named_parameters(),
                              model.named_parameters()):
        p._data = q.data[:32] if n in ('embed', 'lm_head') else q.data
    assert part.lm_head_weight().data.shape == (32, 64)
    ids = np.random.default_rng(5).integers(1, 32, (1, 20))
    np.testing.assert_allclose(np.asarray(part(ids).data),
                               np.asarray(model(ids).data)[..., :32],
                               rtol=1e-5, atol=1e-6)


def _pages(model, pages):
    lanes = 128         # the 16 + 8 lane row as the pool holds it
    return [(jnp.zeros((pages, PAGE, lanes)),) for _ in model.layers]


def _call(model, kv, layout, tokens, tables, seq, ql):
    rows = RowGroups(layout, jnp.asarray(tables, jnp.int32),
                     jnp.asarray(seq, jnp.int32), jnp.asarray(ql, jnp.int32))
    h, new_kv, _ = model.forward_paged(
        Tensor(jnp.asarray(tokens, jnp.int32)[None]),
        Tensor(rows.positions(63)),
        [tuple(Tensor(a) for a in c) for c in kv], rows)
    return np.asarray(h.data[0] @ model.lm_head_weight().data.T), \
        [tuple(t.data for t in c) for c in new_kv]


def _prefill(model, kv, ids, table, start=0, B=3, P=2):
    """ids[start:] through the mixed layout's first chunk row in chunks
    of CHUNK beside idle decode rows -> (logits of those positions, kv)."""
    idle = np.zeros((B, table.shape[1]), np.int32)
    got = []
    for at in range(start, len(ids), CHUNK):
        n = min(CHUNK, len(ids) - at)
        tokens = np.zeros(B + P * CHUNK, np.int32)
        tokens[B:B + n] = ids[at:at + n]
        lg, kv = _call(model, kv, ((B, 1), (P, CHUNK)), tokens,
                       np.concatenate([idle, table, table * 0]),
                       [1] * B + [at + n, 1], [0] * B + [n, 0])
        got.append(lg[B:B + n])
    return np.concatenate(got), kv


def test_chunks_then_decode_through_latent_pages(model):
    """One request prefilled in chunks of 8 in the mixed layout beside
    an idle decode group, then decoded in the [B, 1] layout beside idle
    rows: every position's logits are the reference's full forward's."""
    ids = np.random.default_rng(6).integers(1, VOCAB, 30)
    n_prompt, B = 21, 3
    want = reference_logits(model, ids)
    kv = _pages(model, 10)
    assert kv[0][0].shape == (10, PAGE, 128) and len(kv[0]) == 1
    table = np.arange(1, 9)[None, :].astype(np.int32)       # page 0 unused
    got, kv = _prefill(model, kv, ids[:n_prompt], table)
    idle = np.zeros((1, 8), np.int32)
    for pos in range(n_prompt, 30):
        tokens = np.zeros(B, np.int32)
        tokens[1] = ids[pos]
        lg, kv = _call(model, kv, ((B, 1),), tokens,
                       np.concatenate([idle, table, idle]),
                       [1, pos + 1, 1], [0, 1, 0])
        got = np.concatenate([got, lg[1:2]])
    scale = want.max(-1) - want.mean(-1)
    assert (np.abs(got - want).max(-1) / scale).max() < 1e-4
    # the rows hold [c_kv | k_pe] and zeros in the padding; page 0 and
    # the slots past the context were never written
    plane = np.asarray(kv[1][0])
    assert np.abs(plane[1:8, :, :24]).min() > 0
    assert not plane[:, :, 24:].any() and not plane[0].any()
    assert not plane[8, 2:].any()


def test_a_prefix_hit_computes_what_a_miss_computes(model):
    """A second request maps the first's pages for the 16 tokens they
    share and prefills its own 9: its logits there are those of a
    request that prefilled all 25 itself, and of the reference."""
    rng = np.random.default_rng(7)
    shared = rng.integers(1, VOCAB, 16)
    first = np.concatenate([shared, rng.integers(1, VOCAB, 6)])
    second = np.concatenate([shared, rng.integers(1, VOCAB, 9)])
    kv = _pages(model, 20)
    _, kv = _prefill(model, kv, first, np.arange(1, 9)[None].astype(np.int32))
    # pages 1-4 hold the shared 16 tokens; the hit continues on 9, 10, ..
    hit_table = np.asarray([[1, 2, 3, 4, 9, 10, 11, 12]], np.int32)
    hit, kv_hit = _prefill(model, kv, second, hit_table, start=16)
    miss, _ = _prefill(model, _pages(model, 20), second,
                       np.arange(11, 19)[None].astype(np.int32))
    np.testing.assert_allclose(hit, miss[16:], rtol=1e-4, atol=1e-5)
    want = reference_logits(model, second)[16:]
    scale = want.max(-1) - want.mean(-1)
    assert (np.abs(hit - want).max(-1) / scale).max() < 1e-4
    # shared pages are read, never written
    for before, after in zip(kv, kv_hit):
        assert np.array_equal(np.asarray(before[0])[1:5],
                              np.asarray(after[0])[1:5])


def test_the_cache_spec_declares_latent_planes(model):
    spec = model.kv_cache_spec()
    assert len(spec) == 3 and all(
        (s.num_kv_heads, s.head_dim, s.window, s.reads, s.value_lanes)
        == (1, 24, None, None, 16) for s in spec)
    assert model.paged_routes == ('plain',) and model.mp_degree == 1
    assert model.moe_counters().shape == (2, 3)
    with pytest.raises(ValueError, match='groups'):
        AxK1Config(n_routed_experts=16, n_group=3)
    with pytest.raises(ValueError, match='YaRN'):
        AxK1Config(rope_scaling={'type': 'linear', 'factor': 2})


def test_the_published_sizes_are_the_issues():
    """Parameter counts at the published widths from the declared
    shapes, nothing built: 101.12 M of attention, 44.04 M an expert."""
    cfg = AxK1Config()
    shapes = {}

    class Declared(axk1.AxK1Attention):
        def _declare(self, **named):
            shapes.update(named)
    Declared(cfg)
    norms = 1536 + 512
    assert sum(math.prod(s) for s in shapes.values()) - norms == \
        101_122_048
    assert shapes['kv_a_proj'] == (7168, 576)
    assert 3 * 7168 * cfg.moe_intermediate_size == 44_040_192
    assert cfg.experts_held == (0, 192) and cfg.vocab_held == 163840


def test_the_reference_in_blocks_is_the_reference(model, monkeypatch):
    """The chip's check runs the reference over blocks: the token-wise
    pieces 2,048 rows at a time into the residual stream's own rows, a
    head's queries 512 at a time. 48 rows in blocks of 32 (the last one
    short) and 16 are what one block gives."""
    ids = np.random.default_rng(8).integers(1, VOCAB, 48)
    whole = reference_logits(model, ids)
    monkeypatch.setattr(reference, 'QUERY_BLOCK', 32)
    monkeypatch.setattr(reference, 'SCORE_ROWS', 16)
    blocks = reference_logits(model, ids)
    np.testing.assert_allclose(blocks, whole, rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match='whole blocks'):
        reference_logits(model, ids[:40])
