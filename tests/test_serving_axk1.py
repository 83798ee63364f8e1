"""AxK1ForCausalLM behind the ServingEngine at toy widths (tests/
test_axk1.py's): the pool's one-array latent planes — their bytes,
sharing, copy-on-write and eviction through the same allocator as the
paired planes —, the engine under the one-step-ahead pipeline with
prefix hits and misses in one step, the counters and the span argument
the documents cell reads, the three refusals, and the other models'
pools left as they were."""
import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.profiler import spans
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.serving.kv_pool import KVPagePool
from paddle_tpu.serving.protocol import KVLayerSpec

from benchmarks.reference import axk1 as reference
from benchmarks.runners import serve_axk1 as runner

from test_axk1 import VOCAB, build

PAGE, CHUNK = 4, 8
ENGINE = dict(page_size=PAGE, max_batch_size=4, prefill_chunk=CHUNK,
              num_pages=64, max_pages_per_seq=16, prefix_cache=True)


@pytest.fixture(scope='module')
def model():
    return build()


def latent_pool(**over):
    return KVPagePool(**dict(dict(
        num_pages=8, page_size=4, num_layers=6, num_heads=1, head_dim=576,
        dtype='bfloat16', prefix_cache=True, value_lanes=512), **over))


def test_a_latent_planes_bytes():
    """ONE array a plane, its row in whole 128-lane tiles: 576 lanes sit
    in 640, 1,280 B a token and plane in bf16 — against 2 x 1,152 for
    the same row held as a (k, v) pair."""
    pool = latent_pool()
    assert (pool.arrays_per_plane, pool.row_lanes) == (1, 640)
    assert pool.bytes_per_token() == 6 * 640 * 2 == 7680
    assert pool.pool_bytes() == 8 * 4 * 7680
    kv = pool.materialize()
    assert len(kv) == 6 and all(
        len(plane) == 1 and plane[0].shape == (8, 4, 640)
        and plane[0].dtype == jnp.bfloat16 for plane in kv)
    assert sum(a.nbytes for plane in kv for a in plane) == pool.pool_bytes()
    s = pool.stats()
    assert (s['arrays_per_plane'], s['row_lanes'], s['value_lanes']) == \
        (1, 640, 512)
    paired = KVPagePool(8, 4, num_layers=6, num_heads=1, head_dim=576,
                        dtype='bfloat16')
    assert (paired.arrays_per_plane, paired.row_lanes) == (2, 576)
    assert paired.bytes_per_token() == 6 * 2 * 1152
    assert [a.shape for a in paired.materialize()[0]] == [(8, 4, 576)] * 2
    with pytest.raises(ValueError, match='value_lanes'):
        latent_pool(value_lanes=600)


def test_latent_pages_are_shared_copied_on_write_and_evicted():
    """The allocator knows pages, not arrays: a second sequence maps the
    first's full pages (refcounts, no new page), diverges into a private
    page, released pages park and are evicted, oldest subtree first."""
    pool = latent_pool()
    doc = list(range(1, 9))                       # two full pages
    pool.ensure_capacity('a', 10)
    pool.register_prefix('a', doc + [20, 21], 10)
    assert pool.match_and_map('b', doc + [30, 31, 32], limit=10) == 8
    assert pool.page_table('b') == pool.page_table('a')[:2]
    assert pool.shared_pages == 2 and pool.pages_in_use == 3
    pool.ensure_capacity('b', 11)                 # its own third page
    assert pool.page_table('b')[2] != pool.page_table('a')[2]
    assert pool.prefix_hit_tokens == 8 and pool.pages_in_use == 4
    pool.release('a')
    assert pool.shared_pages == 0 and pool.cached_pages == 0 \
        and pool.pages_in_use == 3            # b still maps the document
    pool.release('b')
    assert pool.pages_in_use == 0 and pool.cached_pages == 2
    assert pool.match_and_map('c', doc + [40], limit=8) == 8
    pool.release('c')
    # a sequence that needs every page evicts the parked document
    pool.ensure_capacity('d', 8 * 4)
    assert pool.prefix_evictions == 2 and pool.cached_pages == 0
    assert pool.match_and_map('e', doc + [40], limit=8) == 0


REFUSED = {
    'int8 pool': (dict(kv_dtype='int8'), "kv_dtype='int8'"),
    'host tier': (dict(host_tier_pages=8), 'a host tier'),
}


@pytest.mark.parametrize('what', sorted(REFUSED))
def test_the_engine_refuses_what_has_no_latent_form(model, what):
    knobs, why = REFUSED[what]
    with pytest.raises(NotImplementedError, match='latent') as err:
        ServingEngine(model, ServingConfig(**dict(ENGINE, **knobs)))
    assert why in str(err.value)


def test_the_pool_refuses_int8_a_host_tier_and_an_mp_sharding():
    with pytest.raises(NotImplementedError, match='int8 pool of latent'):
        latent_pool(dtype='int8')
    with pytest.raises(NotImplementedError, match='host tier under latent'):
        latent_pool().attach_host_tier(object())
    with pytest.raises(NotImplementedError, match='mp sharding'):
        latent_pool().materialize(sharding=object())


def test_the_engine_refuses_an_mp_mesh_and_mixed_planes(model, monkeypatch):
    """An mp mesh of two is refused for the latent planes before the
    model's own mp degree is asked about; a spec that mixes latent and
    paired planes, or puts a window on a latent one, is refused too."""
    class Mesh:
        shape = {'mp': 2}
    with pytest.raises(NotImplementedError, match='an mp mesh'):
        ServingEngine(model, ServingConfig(**ENGINE), mesh=Mesh())
    spec = model.kv_cache_spec()
    monkeypatch.setattr(type(model), 'kv_cache_spec', lambda self: [
        spec[0], KVLayerSpec(1, 24, None), spec[2]])
    with pytest.raises(ValueError, match='planes of one kind'):
        ServingEngine(model, ServingConfig(**ENGINE))
    monkeypatch.setattr(type(model), 'kv_cache_spec', lambda self: [
        s._replace(window=8) for s in spec])
    with pytest.raises(NotImplementedError, match='window on a latent'):
        ServingEngine(model, ServingConfig(**ENGINE))


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


def test_hits_and_misses_ride_one_step_under_the_pipeline(model):
    """Six questions about two documents on four slots: the first ask
    of each document misses, later asks map its pages (a sibling waits
    for the first's chunks rather than computing them again), and every
    emitted token is the reference's argmax up to float32 noise."""
    rng = np.random.default_rng(0)
    docs = [rng.integers(1, VOCAB, n).tolist() for n in (16, 24)]
    prompts = [docs[i % 2] + rng.integers(1, VOCAB, 3 + i).tolist()
               for i in range(6)]
    reqs, stats, shapes = serve(model, prompts, (5, 4, 6, 3, 5, 4))
    assert shapes == ["('mixed', 4, 2, 8, False)", '(4, 1, False, False)']
    cached = [r.cached_tokens for r in reqs]
    assert cached[:2] == [0, 0] and cached[2:] == [16, 24, 16, 24]
    assert stats['prefix_hit_tokens_total'] == 80
    assert stats['prompt_tokens_total'] == sum(len(p) for p in prompts)
    assert stats['preemptions_total'] == 0
    assert stats['pipelined_steps_total'] > 0
    assert not any(v for k, v in stats['pipeline_drains_total'].items()
                   if k != 'idle')
    params, layer, cfg = runner.reference_view(model)
    for r in reqs:
        out, n = r.output_ids(), len(r.prompt)
        gaps = reference.token_gaps(
            params, layer, cfg, np.asarray(out, np.int32),
            np.arange(n - 1, len(out) - 1), out[n:], vocab_block=40)
        assert len(gaps) == len(r.generated) and gaps.max() < 1e-4
    # a hit and a miss emit the same tokens: the same six requests with
    # the cache off
    cold, cold_stats, _ = serve(model, prompts, (5, 4, 6, 3, 5, 4),
                                prefix_cache=False)
    assert [r.generated for r in cold] == [r.generated for r in reqs]
    assert cold_stats['prompt_tokens_total'] == 0


def test_the_counters_the_documents_cell_reads(model):
    """One request of 19 prompt tokens in chunks of 8 and 3 decode
    steps, three attending layers: keys read, the chunk rows' part and
    the (query, key) pairs, by hand."""
    reqs, stats, _ = serve(model, [list(range(1, 20))], (4,))
    layers = 3
    chunk_keys = 8 + 16 + 19                 # context at each chunk's end
    decode_keys = 20 + 21 + 22               # one query a step
    assert stats['attn_kv_tokens_read_chunks_total'] == layers * chunk_keys
    assert stats['attn_kv_tokens_read_total'] == \
        layers * (chunk_keys + decode_keys)
    triangle = sum(range(1, 20))             # query t reads t + 1 keys
    assert stats['attn_qk_pairs_total'] == layers * (triangle + decode_keys)
    assert stats['kv_plane_bytes_per_token'] == 128 * 4
    assert stats['kv_planes'] == stats['kv_readers'] == 3
    assert stats['pool']['bytes_per_token'] == 3 * 128 * 4
    (prefill,) = [s for s in spans() if s.name == 'serve::request.prefill'
                  and s.args['req'] == reqs[0].id]
    assert prefill.args['cached_tokens'] == 0 and prefill.args['chunks'] == 3


def test_the_pairs_a_latent_chunk_dispatches(model, monkeypatch):
    """A chunk of 40 of 64 slots in query tiles of 16 tokens (64 rows of
    4 heads): three live tiles multiply all their 16 tokens by the keys
    up to their last live query's — 16, 32 and 40 —, the fourth nothing;
    the decode rows' pairs are the real ones."""
    from paddle_tpu.ops.pallas import paged_attention as pa
    monkeypatch.setattr(pa, '_LATENT_TILE_ROWS', 64)
    _, stats, _ = serve(model, [list(range(1, 41))], (3,), prefill_chunk=64)
    layers, decode_keys = 3, 41 + 42
    assert stats['attn_qk_pairs_total'] == \
        layers * (sum(range(1, 41)) + decode_keys)
    assert stats['attn_qk_pairs_dispatched_total'] == \
        layers * (16 * (16 + 32 + 40) + decode_keys)
    # one tile holds the whole chunk at these widths as they are: the
    # 64 slots' worth of the 40 keys
    monkeypatch.undo()
    _, stats, _ = serve(model, [list(range(1, 41))], (3,), prefill_chunk=64)
    assert stats['attn_qk_pairs_dispatched_total'] == \
        layers * (64 * 40 + decode_keys)


def test_pairs_under_a_window_are_clipped(model):
    """The pair count of a window layer: a query reads no more keys
    than the window."""
    eng = ServingEngine(model, ServingConfig(**ENGINE))
    try:
        eng._kv_full, eng._windows = 1, {4: 2}
        # 6 queries ending at context 9: full 4+5+..+9 = 39; a window of
        # 4 clips every one of them to 4
        assert eng._pairs(9, 6) == 39 + 2 * 24
        # 3 queries from an empty context: 1 + 2 + 3 either way
        assert eng._pairs(3, 3) == 6 + 2 * 6
        # a decode row
        assert eng._pairs(9, 1) == 9 + 2 * 4
    finally:
        eng.shutdown()


@pytest.mark.parametrize('family', ['gpt', 'afmoe'])
def test_the_other_models_planes_are_pairs_as_they_were(family):
    import paddle_tpu as paddle
    paddle.seed(0)
    if family == 'gpt':
        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
        m = GPTForCausalLM(GPTConfig(
            vocab_size=VOCAB, hidden_size=32, num_layers=2, num_heads=2,
            ffn_hidden_size=64, max_seq_len=64, hidden_dropout=0.0,
            attn_dropout=0.0))
        heads, dim = 2, 16
    else:
        from paddle_tpu.models.afmoe import AfmoeConfig, AfmoeForCausalLM
        m = AfmoeForCausalLM(AfmoeConfig(
            vocab_size=VOCAB, hidden_size=32, num_layers=2,
            num_dense_layers=1, num_heads=4, num_kv_heads=2, head_dim=8,
            intermediate_size=64, moe_intermediate_size=16, num_experts=4,
            num_experts_per_tok=2, sliding_window=8,
            layer_types=['sliding_attention', 'full_attention'],
            max_seq_len=64, dtype='float32'))
        heads, dim = 2, 8
    m.eval()
    assert all(s.value_lanes is None for s in m.kv_cache_spec())
    eng = ServingEngine(m, ServingConfig(**ENGINE))
    try:
        assert all(len(plane) == 2 and plane[0].shape == (64, PAGE,
                                                          heads * dim)
                   for plane in eng.pool.kv)
        item = eng.pool.kv[0][0].dtype.itemsize
        assert eng.pool.bytes_per_token() == 2 * 2 * heads * dim * item
        assert eng.stats()['kv_plane_bytes_per_token'] == \
            2 * heads * dim * item
        # no latent plane, no query tiles: what is multiplied is counted
        # as what the masks allow
        eng.submit(list(range(1, 20)), max_new_tokens=3, top_k=0)
        while eng.scheduler.has_work:
            eng.step()
        stats = eng.stats()
        assert stats['attn_qk_pairs_dispatched_total'] == \
            stats['attn_qk_pairs_total'] > 0
    finally:
        eng.shutdown()
