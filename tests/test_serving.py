"""Serving engine: page-allocator invariants, ragged paged-attention
parity (Pallas interpret mode + dense fallback vs a per-sequence
oracle), continuous-batching equivalence with sequential generate,
preemption/resume correctness (ISSUE 5), copy-on-write prefix-cache
invariants and speculative-decode equivalence (ISSUE 9)."""
import math

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import flags
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.serving import (KVPagePool, PoolExhausted, RequestState,
                                ServingConfig, ServingEngine)


# ---------------------------------------------------------------------------
# page allocator
# ---------------------------------------------------------------------------
class TestPageAllocator:
    def test_alloc_free_reuse_and_occupancy(self):
        pool = KVPagePool(num_pages=8, page_size=4)
        assert pool.pages_for(1) == 1 and pool.pages_for(4) == 1
        assert pool.pages_for(5) == 2 and pool.pages_for(0) == 1
        pool.ensure_capacity('a', 9)           # 3 pages
        pool.ensure_capacity('b', 4)           # 1 page
        assert pool.pages_in_use == 4 and pool.free_pages == 4
        assert pool.utilization() == 0.5
        assert len(pool.page_table('a')) == 3
        # growth is incremental, already-held pages are kept
        pool.ensure_capacity('a', 10)
        assert len(pool.page_table('a')) == 3
        pool.ensure_capacity('a', 13)
        assert len(pool.page_table('a')) == 4
        # release returns every page exactly once
        freed = pool.release('a')
        assert freed == 4
        assert pool.pages_in_use == 1 and pool.free_pages == 7
        assert pool.release('a') == 0          # idempotent
        # freed pages are reused
        pool.ensure_capacity('c', 8 * 4 - 4)   # everything left
        assert pool.free_pages == 0
        st = pool.stats()
        assert st['high_water'] == 8 and st['pages_in_use'] == 8
        assert st['alloc_total'] == 4 + 1 + 7 and st['free_total'] == 4

    def test_no_double_mapping(self):
        pool = KVPagePool(num_pages=6, page_size=2)
        pool.ensure_capacity('a', 6)
        pool.ensure_capacity('b', 6)
        pages_a = set(pool.page_table('a'))
        pages_b = set(pool.page_table('b'))
        assert not pages_a & pages_b
        assert pages_a | pages_b == set(range(6)) & (pages_a | pages_b)
        assert pool.pages_in_use + pool.free_pages == pool.num_pages

    def test_exhaustion_raises_and_partial_growth_kept(self):
        pool = KVPagePool(num_pages=3, page_size=4)
        pool.ensure_capacity('a', 8)           # 2 pages
        with pytest.raises(PoolExhausted):
            pool.ensure_capacity('b', 12)      # needs 3, only 1 free
        # the partial page stays mapped (caller preempts + retries)
        assert pool.pages_in_use == 3
        assert pool.pages_in_use + pool.free_pages == pool.num_pages
        pool.release('a')
        pool.ensure_capacity('b', 12)
        assert len(pool.page_table('b')) == 3


# ---------------------------------------------------------------------------
# copy-on-write prefix cache: allocator-level invariants (ISSUE 9)
# ---------------------------------------------------------------------------
def _partition_ok(pool):
    """free + cached + mapped partitions the pool at all times."""
    return (len(pool._free) + len(pool._cached) + len(pool._ref)
            == pool.num_pages)


class TestPrefixCacheAllocator:
    def test_refcount_share_and_exact_once_release(self):
        pool = KVPagePool(num_pages=8, page_size=4, prefix_cache=True)
        toks = list(range(100, 112))           # 3 full blocks
        pool.ensure_capacity('a', 12)
        pool.register_prefix('a', toks, written=12)
        # b maps all 3 indexed pages — same physical pages, ref 2
        assert pool.match_and_map('b', toks + [7, 8]) == 12
        assert pool.page_table('b') == pool.page_table('a')
        assert pool.shared_pages == 3
        assert pool.pages_in_use == 3 and _partition_ok(pool)
        # a releases: pages stay mapped for b (nothing reclaimed)
        assert pool.release('a') == 0
        assert pool.shared_pages == 0 and pool.pages_in_use == 3
        # b releases: indexed pages park in the cached set, not free
        assert pool.release('b') == 3
        assert pool.pages_in_use == 0 and pool.cached_pages == 3
        assert pool.free_pages == 8 and _partition_ok(pool)
        # double release stays a no-op
        assert pool.release('a') == 0 and pool.release('b') == 0
        # a third request resurrects them from the cached set
        assert pool.match_and_map('c', toks) == 12
        assert pool.cached_pages == 0 and pool.pages_in_use == 3
        assert pool.prefix_hits == 2 and pool.prefix_hit_tokens == 24

    def test_fork_on_divergence_shares_only_common_blocks(self):
        pool = KVPagePool(num_pages=16, page_size=4, prefix_cache=True)
        common = [1, 2, 3, 4, 5, 6, 7, 8]      # 2 full blocks
        pool.ensure_capacity('a', 12)
        pool.register_prefix('a', common + [9, 10, 11, 12], written=12)
        # b shares the first 2 blocks then DIVERGES at token 9: the
        # divergent tail must land in private pages (fork-on-write =
        # recompute from the page boundary, never touch shared pages)
        b_toks = common + [99, 98, 97, 96]
        assert pool.match_and_map('b', b_toks) == 8
        pool.ensure_capacity('b', 12)
        ta, tb = pool.page_table('a'), pool.page_table('b')
        assert tb[:2] == ta[:2]                # shared prefix blocks
        assert tb[2] != ta[2]                  # private divergent page
        assert pool.shared_pages == 2
        # b's divergent block registers under its own chain and is
        # matchable by a third request; a's block 2 stays distinct
        pool.register_prefix('b', b_toks, written=12)
        assert pool._match_pages(b_toks) == tb[:3]
        assert pool._match_pages(common + [9, 10, 11, 12]) == ta[:3]

    def test_match_is_capped_and_block_granular(self):
        pool = KVPagePool(num_pages=8, page_size=4, prefix_cache=True)
        toks = list(range(50, 58))             # 2 full blocks
        pool.ensure_capacity('a', 8)
        pool.register_prefix('a', toks, written=8)
        # limit (engine passes len-1 so one token stays to compute):
        # 7 tokens -> only the first full block matches
        assert pool.peek_prefix(toks, limit=7) == (4, 1, 0, 0)
        assert pool.match_and_map('b', toks, limit=7) == 4
        # partial block never matches: 6 tokens -> 1 block
        assert pool.peek_prefix(toks[:6]) == (4, 1, 0, 0)
        # disabled pool: no matching, no counting
        off = KVPagePool(num_pages=4, page_size=4)
        off.ensure_capacity('x', 4)
        off.register_prefix('x', [1, 2, 3, 4], written=4)
        assert off.peek_prefix([1, 2, 3, 4]) == (0, 0, 0, 0)
        assert off.match_and_map('y', [1, 2, 3, 4]) == 0
        assert off.prefix_misses == 0

    def test_eviction_reclaims_cached_subtree_lru(self):
        pool = KVPagePool(num_pages=4, page_size=4, prefix_cache=True)
        chain = list(range(10, 22))            # 3 blocks
        pool.ensure_capacity('a', 12)
        pool.register_prefix('a', chain, written=12)
        pool.release('a')
        assert pool.cached_pages == 3 and pool.free_pages == 4
        # allocating 2 pages: 1 free + evicting the LRU root drops the
        # WHOLE chain (descendants keyed on a recycled parent id would
        # be a stale-chain hazard), so everything is allocatable
        pool.ensure_capacity('b', 8)
        assert pool.pages_in_use == 2
        assert pool.prefix_evictions == 3
        assert pool._match_pages(chain) == []  # index fully dropped
        assert _partition_ok(pool)
        # pool can still be filled to the brim
        pool.ensure_capacity('b', 16)
        assert pool.free_pages == 0
        with pytest.raises(PoolExhausted):
            pool.ensure_capacity('c', 4)

    def test_match_after_partial_allocation_is_noop(self):
        # review fix: a prefill retried after PoolExhausted kept its
        # partial pages; the lookup must degrade to a miss (shared
        # pages go at the FRONT of the table), not crash
        pool = KVPagePool(num_pages=8, page_size=4, prefix_cache=True)
        toks = list(range(40, 48))
        pool.ensure_capacity('a', 8)
        pool.register_prefix('a', toks, written=8)
        pool.ensure_capacity('b', 4)           # partial growth kept
        assert pool.match_and_map('b', toks) == 0
        assert len(pool.page_table('b')) == 1

    def test_deep_chain_eviction_is_iterative(self):
        # review fix: chains grow one node per page; at page_size=1
        # they get deeper than Python's recursion limit — eviction
        # must not blow the stack (or half-mutate the index)
        n = 1200
        pool = KVPagePool(num_pages=n, page_size=1, prefix_cache=True)
        toks = list(range(n))
        pool.ensure_capacity('a', n)
        pool.register_prefix('a', toks, written=n)
        pool.release('a')
        assert pool.cached_pages == n
        pool.ensure_capacity('b', 2)           # evicts the LRU chain
        assert pool.prefix_evictions == n
        assert pool._match_pages(toks) == []
        assert _partition_ok(pool)

    def test_trim_returns_private_tail_only(self):
        pool = KVPagePool(num_pages=8, page_size=4, prefix_cache=True)
        toks = list(range(60, 68))
        pool.ensure_capacity('a', 16)          # 4 pages
        pool.register_prefix('a', toks, written=8)
        # trim to 9 tokens: pages 3 and... keep=3, page 3 freed; the
        # indexed pages (0, 1) and page 2 stay
        assert pool.trim('a', 9) == 1
        assert len(pool.page_table('a')) == 3
        # shared page is never trimmed even when trailing
        pool2 = KVPagePool(num_pages=8, page_size=4, prefix_cache=True)
        pool2.ensure_capacity('x', 8)
        pool2.register_prefix('x', toks, written=8)
        pool2.match_and_map('y', toks, limit=None)
        assert pool2.trim('y', 1) == 0         # both pages indexed
        assert len(pool2.page_table('y')) == 2


# ---------------------------------------------------------------------------
# ragged paged attention: kernel + fallback vs a per-sequence oracle
# ---------------------------------------------------------------------------
def _oracle(q, k_pages, v_pages, page_tables, seq_lens, q_lens, H, D):
    """Host reference: gather each row's tokens from its pages, run
    plain per-head causal softmax attention over the valid prefix."""
    q = np.asarray(q, np.float64)
    kp = np.asarray(k_pages, np.float64)
    vp = np.asarray(v_pages, np.float64)
    B, T, HD = q.shape
    ps = kp.shape[1]
    out = np.zeros_like(q)
    for b in range(B):
        S, QL = int(seq_lens[b]), int(q_lens[b])
        keys = np.concatenate([kp[p] for p in page_tables[b]], 0)[:S]
        vals = np.concatenate([vp[p] for p in page_tables[b]], 0)[:S]
        for t in range(QL):
            pos = S - QL + t
            for h in range(H):
                qh = q[b, t, h * D:(h + 1) * D] / math.sqrt(D)
                s = keys[:pos + 1, h * D:(h + 1) * D] @ qh
                p_ = np.exp(s - s.max())
                p_ /= p_.sum()
                out[b, t, h * D:(h + 1) * D] = \
                    p_ @ vals[:pos + 1, h * D:(h + 1) * D]
    return out


def _mixed_case(dtype=np.float32, seed=0):
    """Mixed decode/prefill rows; row contexts span 1..4 pages; page
    tables deliberately shuffled so page order != pool order."""
    rng = np.random.RandomState(seed)
    B, T, H, D, ps, P = 3, 4, 2, 8, 8, 4
    HD = H * D
    num_pages = B * P + 3
    q = rng.randn(B, T, HD).astype(dtype)
    k_pages = rng.randn(num_pages, ps, HD).astype(dtype)
    v_pages = rng.randn(num_pages, ps, HD).astype(dtype)
    page_tables = rng.permutation(num_pages)[:B * P] \
        .reshape(B, P).astype(np.int32)
    # (seq_len, q_len): decode row, pure-prefill row, long multi-page
    # row with padding (q_len < T)
    lens = np.asarray([[13, 1], [4, 4], [29, 2]], np.int32)
    return (q, k_pages, v_pages, page_tables, lens[:, 0], lens[:, 1],
            H, D)


# rows of one batch, each a shape the kernel's page loop can get wrong;
# (name, seq_len in pages-and-slots terms, q_len policy). ps = page
# size, W = pages per DMA wave (2 here), P = page-table slots (5: not a
# multiple of W, so a full row ends on a partial wave)
_RAGGED_W, _RAGGED_P = 2, 5
_RAGGED_ROWS = {
    'page_boundary': lambda ps: ps,
    'page_boundary_plus_1': lambda ps: ps + 1,
    'wave_boundary': lambda ps: _RAGGED_W * ps,
    'wave_boundary_plus_1': lambda ps: _RAGGED_W * ps + 1,
    'one_token': lambda ps: 1,
    'idle': lambda ps: 1,                       # q_len 0, as the engine
    'all_slots': lambda ps: _RAGGED_P * ps,     # pads an empty row
    'odd_live_pages': lambda ps: 3 * ps - 3,    # 3 pages, W = 2
    'sentinel_slots': lambda ps: ps + 5,
    'shared_page': lambda ps: 3 * ps - 3,       # odd_live_pages' pages
    'stale_nan_pages': lambda ps: 3 * ps,       # NaN inside its last wave
}
_RAGGED_KINDS = {'fp32': (np.float32, 16), 'bf16': (jnp.bfloat16, 16),
                 'int8_ps16': (np.int8, 16), 'int8_ps32': (np.int8, 32)}
_ragged_cache = {}


def _ragged_case(T, kind):
    """(kernel out, dense out, oracle out, q_lens) of the one batch of
    _RAGGED_ROWS at query width T, computed once per (T, kind)."""
    if (T, kind) in _ragged_cache:
        return _ragged_cache[T, kind]
    from paddle_tpu.ops.pallas import scaffold
    dtype, ps = _RAGGED_KINDS[kind]
    int8 = dtype == np.int8
    H, D, P = 2, 8, _RAGGED_P
    HD = H * D
    names = list(_RAGGED_ROWS)
    B = len(names)
    rng = np.random.RandomState(T + ps)
    n_good = B * P
    # never inside any row's context, and not where a clamped sentinel
    # lands (the dense fallback clamps ids to the pool's two ends)
    nan_page = n_good
    kf = rng.randn(n_good + 2, ps, HD).astype(np.float32)
    vf = rng.randn(n_good + 2, ps, HD).astype(np.float32)
    seq = np.asarray([_RAGGED_ROWS[n](ps) for n in names], np.int32)
    ql = np.minimum(seq, T).astype(np.int32)
    ql[names.index('idle')] = 0
    ql[names.index('all_slots')] = min(T, 2)    # padded query columns
    pt = rng.permutation(n_good).reshape(B, P).astype(np.int32)
    live = -(-seq // ps)
    pt[names.index('shared_page')] = pt[names.index('odd_live_pages')]
    b = names.index('stale_nan_pages')
    pt[b, live[b]:] = nan_page
    b = names.index('sentinel_slots')
    pt[b, live[b]:] = [n_good + 100, -1, 2 ** 30][:P - live[b]]
    q = rng.randn(B, T, HD).astype(np.float32)
    if int8:
        kq, ks = pa.quantize_kv_rows(jnp.asarray(kf), H)
        vq, vs = pa.quantize_kv_rows(jnp.asarray(vf), H)
        # the reference sees the dequantized pages
        kf = np.asarray(pa._dequant_gathered(kq[None], ks[None], H))[0]
        vf = np.asarray(pa._dequant_gathered(vq[None], vs[None], H))[0]
        pages = (kq, vq)
        scales = dict(k_scales=ks, v_scales=vs)
        q_in = jnp.asarray(q)
    else:
        pages = (jnp.asarray(kf, dtype), jnp.asarray(vf, dtype))
        kf, vf = (np.asarray(x, np.float32) for x in pages)
        scales = {}
        q_in = jnp.asarray(q, dtype)
        q = np.asarray(q_in, np.float32)
    # garbage past the contexts: NaN in stored dtypes that have one
    nan = 127 if int8 else np.nan
    pages = tuple(x.at[nan_page].set(nan) for x in pages)
    if int8:
        scales = {n: x.at[nan_page].set(np.nan) for n, x in scales.items()}
    args = (q_in,) + pages + (jnp.asarray(pt), jnp.asarray(seq),
                              jnp.asarray(ql))
    wave_bytes = pa._WAVE_BYTES
    pa._WAVE_BYTES = _RAGGED_W * 2 * scaffold.block_bytes(
        (ps, HD), pages[0].dtype)
    try:
        kernel = pa.ragged_paged_attention_pallas(
            *args, num_heads=H, head_dim=D, **scales)
    finally:
        pa._WAVE_BYTES = wave_bytes
    dense = pa.ragged_paged_attention_dense(
        *args, num_heads=H, head_dim=D, **scales)
    # the oracle walks every table slot before it cuts at seq_len
    safe_pt = np.where((pt >= 0) & (pt < n_good), pt, 0)
    ref = _oracle(q, kf, vf, safe_pt, seq, ql, H, D)
    out = (np.asarray(kernel, np.float32), np.asarray(dense, np.float32),
           ref, ql)
    _ragged_cache[T, kind] = out
    return out


class TestRaggedPagedAttention:
    @pytest.mark.parametrize('row', list(_RAGGED_ROWS))
    @pytest.mark.parametrize('kind', list(_RAGGED_KINDS))
    @pytest.mark.parametrize('T', [1, 3, 128])
    def test_kernel_row_shapes_match_dense_and_oracle(self, T, kind, row):
        # T = 1 and 3 run the batched block-diagonal products, T = 128
        # the per-head ones; two pages a wave, five table slots
        kernel, dense, ref, ql = _ragged_case(T, kind)
        b = list(_RAGGED_ROWS).index(row)
        tol = dict(rtol=2e-2, atol=2e-2) if kind == 'bf16' \
            else dict(rtol=2e-4, atol=2e-5)
        # padded query columns and idle rows too: nothing the row did
        # not own (NaN pages, sentinel ids, the last wave's tail) leaks
        assert np.isfinite(kernel[b]).all()
        if row == 'idle':
            assert not kernel[b].any()
        np.testing.assert_allclose(kernel[b, :ql[b]], ref[b, :ql[b]],
                                   **tol)
        if row != 'stale_nan_pages':    # the dense gather multiplies
            np.testing.assert_allclose(  # 0 * NaN: not a reference there
                kernel[b, :ql[b]], dense[b, :ql[b]], **tol)

    def test_layers_share_one_trace_of_the_kernel_body(self, monkeypatch):
        # a model calls the kernel once a layer with the same shapes;
        # the call is one jitted function, so the body is traced (and
        # Mosaic-lowered) once per shape, not once per layer
        traced = []
        body = pa._ragged_paged_kernel
        monkeypatch.setattr(
            pa, '_ragged_paged_kernel',
            lambda *a, **kw: (traced.append(1), body(*a, **kw))[1])
        q, kp, vp, pt, sl, ql, H, D = _mixed_case()
        # seven rows, a batch no other test uses: this shape's first trace
        q, pt, sl, ql = (jnp.asarray(np.concatenate([x, x, x])[:7])
                         for x in (q, pt, sl, ql))

        def three_layers(q):
            for _ in range(3):
                q = pa.ragged_paged_attention_pallas(
                    q, jnp.asarray(kp), jnp.asarray(vp), pt, sl, ql,
                    num_heads=H, head_dim=D)
            return q
        jax.make_jaxpr(three_layers)(q)
        assert len(traced) == 1

    def test_kernel_matches_oracle_fp32(self):
        q, kp, vp, pt, sl, ql, H, D = _mixed_case()
        o = pa.ragged_paged_attention_pallas(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(pt), jnp.asarray(sl), jnp.asarray(ql),
            num_heads=H, head_dim=D)
        ref = _oracle(q, kp, vp, pt, sl, ql, H, D)
        for b in range(q.shape[0]):
            np.testing.assert_allclose(
                np.asarray(o)[b, :ql[b]], ref[b, :ql[b]],
                rtol=2e-4, atol=2e-5)

    def test_dense_fallback_matches_oracle_fp32(self):
        q, kp, vp, pt, sl, ql, H, D = _mixed_case(seed=1)
        o = pa.ragged_paged_attention_dense(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(pt), jnp.asarray(sl), jnp.asarray(ql),
            num_heads=H, head_dim=D)
        ref = _oracle(q, kp, vp, pt, sl, ql, H, D)
        for b in range(q.shape[0]):
            np.testing.assert_allclose(
                np.asarray(o)[b, :ql[b]], ref[b, :ql[b]],
                rtol=2e-4, atol=2e-5)

    def test_kernel_matches_dense_bf16(self):
        q, kp, vp, pt, sl, ql, H, D = _mixed_case()
        qb = jnp.asarray(q, jnp.bfloat16)
        kb = jnp.asarray(kp, jnp.bfloat16)
        vb = jnp.asarray(vp, jnp.bfloat16)
        o_k = pa.ragged_paged_attention_pallas(
            qb, kb, vb, jnp.asarray(pt), jnp.asarray(sl),
            jnp.asarray(ql), num_heads=H, head_dim=D)
        o_d = pa.ragged_paged_attention_dense(
            qb, kb, vb, jnp.asarray(pt), jnp.asarray(sl),
            jnp.asarray(ql), num_heads=H, head_dim=D)
        for b in range(q.shape[0]):
            np.testing.assert_allclose(
                np.asarray(o_k, np.float32)[b, :ql[b]],
                np.asarray(o_d, np.float32)[b, :ql[b]],
                rtol=5e-2, atol=5e-2)

    def test_route_selection(self):
        assert not pa.use_pallas_route()       # CPU test mesh -> dense
        flags.set_flags({'FLAGS_paged_attention_kernel': True})
        try:
            assert pa.use_pallas_route()
        finally:
            flags.set_flags({'FLAGS_paged_attention_kernel': None})
        assert not pa.use_pallas_route()

    def test_write_kv_pages_scatter(self):
        ps, HD, N = 4, 6, 5
        kp = jnp.zeros((N, ps, HD))
        vp = jnp.zeros((N, ps, HD))
        # row 0: 2 valid tokens at positions 5, 6 (page_table[1] slots
        # 1, 2); row 1: q_len=0 idle slot, nothing may be written
        k_new = jnp.arange(2 * 3 * HD, dtype=jnp.float32) \
            .reshape(2, 3, HD) + 1.0
        pt = jnp.asarray([[3, 1, 0, 0], [2, 2, 2, 2]], jnp.int32)
        sl = jnp.asarray([7, 1], jnp.int32)
        ql = jnp.asarray([2, 0], jnp.int32)
        kp2, vp2 = pa.write_kv_pages(kp, vp, k_new, 2 * k_new, pt, sl, ql)
        kp2 = np.asarray(kp2)
        np.testing.assert_allclose(kp2[1, 1], np.asarray(k_new)[0, 0])
        np.testing.assert_allclose(kp2[1, 2], np.asarray(k_new)[0, 1])
        # nothing else written: total nonzero rows == 2
        assert (np.abs(kp2).sum(-1) > 0).sum() == 2
        np.testing.assert_allclose(np.asarray(vp2)[1, 1],
                                   2 * np.asarray(k_new)[0, 0])


# ---------------------------------------------------------------------------
# continuous batching vs sequential generate
# ---------------------------------------------------------------------------
@pytest.fixture(scope='module')
def tiny_lm():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(7)
    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                    num_heads=2, max_seq_len=128, hidden_dropout=0.0,
                    attn_dropout=0.0, use_flash_attention=False)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


@pytest.fixture(scope='module')
def mixed_prompts():
    rng = np.random.RandomState(3)
    return [list(rng.randint(1, 128, n)) for n in (5, 11, 3, 17, 8)]


@pytest.fixture(scope='module')
def sequential_greedy(tiny_lm, mixed_prompts):
    outs = []
    for p in mixed_prompts:
        out = tiny_lm.generate(Tensor(np.asarray([p], 'int32')),
                               max_new_tokens=6, top_k=0, use_cache=True)
        outs.append(np.asarray(out.data)[0].tolist())
    return outs


class TestContinuousBatching:
    def test_equivalence_with_sequential_generate(
            self, tiny_lm, mixed_prompts, sequential_greedy):
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=3, prefill_chunk=8))
        outs = eng.generate(mixed_prompts, max_new_tokens=6, top_k=0)
        assert outs == sequential_greedy
        st = eng.stats()
        assert st['requests_completed_total'] == len(mixed_prompts)
        assert st['decode_tokens_per_sec'] > 0
        assert 0 < st['batch_occupancy'] <= 1
        # every page back in the free list after the stream drains
        assert eng.pool.pages_in_use == 0
        eng.shutdown()

    def test_preemption_resume_equivalence(
            self, tiny_lm, mixed_prompts, sequential_greedy):
        # 4 pages of 8 tokens can't hold the concurrent contexts this
        # stream grows into: the scheduler must preempt and resume, and
        # outputs must not change (greedy decode is deterministic)
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=3, prefill_chunk=8, num_pages=4))
        outs = eng.generate(mixed_prompts, max_new_tokens=6, top_k=0)
        assert outs == sequential_greedy
        assert eng.stats()['preemptions_total'] > 0
        assert eng.pool.pages_in_use == 0
        eng.shutdown()

    def test_pool_too_small_raises(self, tiny_lm):
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=2, prefill_chunk=8, num_pages=1))
        with pytest.raises(PoolExhausted, match='raise num_pages'):
            eng.generate([[1, 2, 3]], max_new_tokens=16, top_k=0)

    def test_request_validation(self, tiny_lm):
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=2, max_pages_per_seq=2))
        with pytest.raises(ValueError, match='page table holds'):
            eng.submit(list(range(1, 15)), max_new_tokens=8)
        with pytest.raises(ValueError, match='empty prompt'):
            eng.submit([], max_new_tokens=4)

    def test_admission_respects_page_budget(self, tiny_lm):
        # 3 free slots but pages for only ONE first chunk: admission
        # must stop at the budget, not fill every slot and churn
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=3, prefill_chunk=8, num_pages=1))
        for p in ([1] * 6, [2] * 6, [3] * 6):
            eng.submit(list(p), max_new_tokens=2)
        eng._admit()
        assert len(eng.scheduler.running()) == 1
        assert len(eng.scheduler.waiting) == 2

    def test_admit_oversized_head_does_not_starve_followers(
            self, tiny_lm):
        # ISSUE 11 satellite: the queue HEAD needs 2 pages but only 1
        # is free — the old sweep broke at the head and left an
        # admissible 1-page follower starving behind it. The head must
        # be skipped (keeping its queue position) and the follower
        # admitted in the SAME sweep.
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=3, prefill_chunk=16,
            num_pages=3, prefix_cache=False))
        blocker = eng.submit([9] * 6, max_new_tokens=10)
        eng.step()                      # holds 1 page, decodes on
        assert blocker.state == RequestState.RUNNING
        head = eng.submit(list(range(1, 18)), max_new_tokens=2)
        follower = eng.submit([7] * 6, max_new_tokens=2)
        assert eng.pool.free_pages == 2     # head's chunk needs 2,
        eng.pool.ensure_capacity('pin', 8)  # pin one -> budget 1
        assert eng._admit() == 1
        assert follower.state == RequestState.PREFILL
        assert eng.scheduler.waiting == [head]   # kept FCFS position
        eng.pool.release('pin')
        # next sweep's budget fits the head again
        assert eng._admit() == 1
        assert head.state == RequestState.PREFILL
        while eng.scheduler.has_work:
            eng.step()
        eng.shutdown()

    def test_admit_bypass_bound_prevents_head_starvation(
            self, tiny_lm):
        # the fairness scan is BOUNDED: once HOL_BYPASS_LIMIT
        # followers have been admitted past a budget-blocked head, the
        # sweep reverts to blocking at the head so freed pages can
        # accumulate for it instead of feeding a small-request stream
        # forever
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=2, prefill_chunk=16,
            num_pages=3, prefix_cache=False))
        eng.pool.ensure_capacity('pin', 16)     # 1 page budget left
        head = eng.submit(list(range(1, 18)), max_new_tokens=2)
        eng.submit([7] * 6, max_new_tokens=2)
        assert eng._admit() == 1                # follower bypasses
        assert eng.scheduler.waiting == [head]
        assert head.admit_bypasses == 1
        head.admit_bypasses = ServingEngine.HOL_BYPASS_LIMIT
        follower2 = eng.submit([8] * 6, max_new_tokens=2)
        assert eng._admit() == 0                # bound hit: sweep
        assert follower2.state == RequestState.WAITING  # blocks at head
        eng.pool.release('pin')
        # head fits now and takes the one remaining slot FIRST
        assert eng._admit() == 1
        assert head.state == RequestState.PREFILL
        assert eng.scheduler.waiting == [follower2]
        eng.shutdown()

    def test_generate_batch_config_change_replaces_engine(
            self, tiny_lm):
        tiny_lm.generate_batch([[1, 2, 3]], max_new_tokens=2, top_k=0,
                               serving_config=ServingConfig(
                                   page_size=8, max_batch_size=2))
        (old,) = tiny_lm._serving_engines.values()
        assert old.config.max_batch_size == 2
        tiny_lm.generate_batch([[1, 2, 3]], max_new_tokens=2, top_k=0,
                               serving_config=ServingConfig(
                                   page_size=16, max_batch_size=4))
        (new,) = tiny_lm._serving_engines.values()
        # no silent config collision, and the evicted engine released
        # its device KV pool (one live pool per model, not a leak)
        assert new.config.max_batch_size == 4
        assert new is not old and old.pool.kv is None

    def test_oversized_request_rejected_at_submit(self, tiny_lm):
        # a request the pool can NEVER hold must fail fast, not sit in
        # the queue forever while the admission budget skips it
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=2, prefill_chunk=16,
            num_pages=1))
        with pytest.raises(PoolExhausted, match='raise num_pages'):
            eng.submit(list(range(1, 11)), max_new_tokens=0)
        assert not eng.scheduler.has_work

    def test_max_new_tokens_zero_emits_nothing(self, tiny_lm):
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=2, prefill_chunk=8))
        outs = eng.generate([[1, 2, 3], [4, 5]], max_new_tokens=0)
        assert outs == [[1, 2, 3], [4, 5]]     # prefill-only, no token
        assert eng.pool.pages_in_use == 0
        eng.shutdown()

    def test_generate_batch_method_and_engine_reuse(
            self, tiny_lm, mixed_prompts, sequential_greedy):
        outs = tiny_lm.generate_batch(mixed_prompts, max_new_tokens=6,
                                      top_k=0, page_size=8,
                                      max_batch_size=3, prefill_chunk=8)
        assert outs == sequential_greedy
        eng = tiny_lm._serving_engines
        outs2 = tiny_lm.generate_batch(mixed_prompts[:2],
                                       max_new_tokens=6, top_k=0,
                                       page_size=8, max_batch_size=3,
                                       prefill_chunk=8)
        assert outs2 == sequential_greedy[:2]
        assert tiny_lm._serving_engines is eng      # cached, not rebuilt

    def test_pallas_route_equivalence_short(self, tiny_lm):
        # force the kernel body (interpret mode on CPU) through a short
        # end-to-end decode and compare with the dense route
        prompts = [[5, 9, 2], [7, 1, 1, 1, 4]]
        eng_d = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=2, prefill_chunk=4))
        ref = eng_d.generate(prompts, max_new_tokens=3, top_k=0)
        eng_d.shutdown()
        flags.set_flags({'FLAGS_paged_attention_kernel': True})
        try:
            eng_k = ServingEngine(tiny_lm, ServingConfig(
                page_size=8, max_batch_size=2, prefill_chunk=4))
            outs = eng_k.generate(prompts, max_new_tokens=3, top_k=0)
            eng_k.shutdown()
        finally:
            flags.set_flags({'FLAGS_paged_attention_kernel': None})
        assert outs == ref

    def test_top_k_sampling_runs_on_device(self, tiny_lm):
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=2, prefill_chunk=8, seed=11))
        outs = eng.generate([[3, 4, 5], [9, 8]], max_new_tokens=5,
                            top_k=4, temperature=0.8)
        assert all(len(o) in (len(p) + 1, len(p) + 5)
                   or len(p) < len(o) <= len(p) + 5
                   for o, p in zip(outs, [[3, 4, 5], [9, 8]]))
        eng.shutdown()


# ---------------------------------------------------------------------------
# prefix caching + speculative decoding through the engine (ISSUE 9)
# ---------------------------------------------------------------------------
@pytest.fixture(scope='module')
def shared_prefix_prompts(tiny_lm):
    """Requests sharing a 24-token system prompt + distinct tails."""
    rng = np.random.RandomState(11)
    system = list(rng.randint(1, 128, 24))
    return [system + list(rng.randint(1, 128, n)) for n in (4, 7, 5, 9)]


class TestPrefixCacheEngine:
    def _run(self, tiny_lm, prompts, max_new=5, **cfg):
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=2, prefill_chunk=8, **cfg))
        outs = eng.generate(prompts, max_new_tokens=max_new, top_k=0)
        st = eng.stats()
        return eng, outs, st

    def test_shared_prefix_identical_outputs_fewer_prefill_tokens(
            self, tiny_lm, shared_prefix_prompts):
        eng0, ref, st0 = self._run(tiny_lm, shared_prefix_prompts,
                                   prefix_cache=False)
        eng0.shutdown()
        eng, outs, st = self._run(tiny_lm, shared_prefix_prompts)
        # acceptance: token-identical to the PR-5 path, and cache hits
        # skipped whole prefill chunks (the TTFT win)
        assert outs == ref
        assert st['prefix_hits_total'] >= 3
        # a sibling admitted mid-prefill only matches the blocks
        # registered so far, so the floor is one block for the
        # concurrent hit plus full 3-block (24-token) hits after
        assert st['prefix_hit_tokens_total'] >= 8 + 2 * 24
        assert st['prefill_tokens_total'] < st0['prefill_tokens_total']
        # every page released exactly once even through sharing: the
        # drained pool has nothing mapped, only resurrectable cache
        assert eng.pool.pages_in_use == 0
        assert eng.pool.cached_pages > 0
        assert eng.pool.free_pages == eng.pool.num_pages
        eng.shutdown()

    def test_concurrent_sharing_maps_same_physical_pages(
            self, tiny_lm, shared_prefix_prompts):
        # submit two shared-prefix requests and step just past both
        # prefills: the live page tables must overlap physically
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=2, prefill_chunk=32))
        r1 = eng.submit(shared_prefix_prompts[0], max_new_tokens=8)
        r2 = eng.submit(shared_prefix_prompts[1], max_new_tokens=8)
        for _ in range(3):
            eng.step()
        t1, t2 = eng.pool.page_table(r1.id), eng.pool.page_table(r2.id)
        assert t1[:3] == t2[:3]            # 24-token system prompt
        assert eng.pool.shared_pages >= 3
        # and the serve gauges see it
        eng.publish_metrics()
        from paddle_tpu.serving import metrics as sm
        snap = sm.serve_snapshot()
        assert snap['ptpu_serve_prefix_shared_pages'] >= 3
        assert snap['prefix_hit_rate'] is not None
        while eng.scheduler.has_work:
            eng.step()
        eng.shutdown()

    def test_preempt_resume_with_sharing_keeps_outputs(
            self, tiny_lm, shared_prefix_prompts):
        # pool pressure on a shared-prefix stream: preempting the
        # youngest must not yank pages its sibling still references,
        # and resume (which may prefix-hit its own cached pages) must
        # not change outputs
        eng0, ref, _ = self._run(tiny_lm, shared_prefix_prompts,
                                 max_new=6, prefix_cache=False,
                                 num_pages=64)
        eng0.shutdown()
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=3, prefill_chunk=8,
            num_pages=7))
        outs = eng.generate(shared_prefix_prompts, max_new_tokens=6,
                            top_k=0)
        assert outs == ref
        assert eng.stats()['preemptions_total'] > 0
        assert eng.pool.pages_in_use == 0
        eng.shutdown()

    def test_admission_budget_counts_shared_pages_once(
            self, tiny_lm, shared_prefix_prompts):
        # the ISSUE 9 satellite fix: with most of the first chunk
        # covered by live shared pages, a second request must be
        # admitted even when the free budget alone could not hold its
        # whole first chunk (the PR-5 estimate charged every chunk
        # page and refused)
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=2, prefill_chunk=32,
            num_pages=6))
        first = eng.submit(shared_prefix_prompts[0], max_new_tokens=4)
        while first.state != RequestState.RUNNING:
            eng.step()
        # 4 pages mapped (25+ tokens); 2 free. A sibling's first chunk
        # is fully covered by the shared system prompt -> need 0 new
        eng.submit(shared_prefix_prompts[1], max_new_tokens=4)
        assert eng._admit() == 1
        while eng.scheduler.has_work:
            eng.step()
        eng.shutdown()

    def test_int8_kv_pages_share_scales(self, tiny_lm,
                                        shared_prefix_prompts):
        # quantized pools share pages AND their sibling scale buffers
        # (same page id addresses both); outputs stay identical to the
        # unshared int8 engine
        eng0, ref, _ = self._run(tiny_lm, shared_prefix_prompts,
                                 prefix_cache=False, kv_dtype='int8')
        eng0.shutdown()
        eng, outs, st = self._run(tiny_lm, shared_prefix_prompts,
                                  kv_dtype='int8')
        assert outs == ref
        assert st['prefix_hits_total'] >= 3
        assert eng.pool.quantized
        eng.shutdown()


class TestSpeculativeDecode:
    def test_ngram_proposer(self):
        from paddle_tpu.serving.engine import _ngram_propose
        # trailing bigram [3, 4] last recurs at position 2 -> proposes
        # the continuation that followed it
        t = [1, 2, 3, 4, 5, 6, 3, 4]
        assert _ngram_propose(t, 2, 3) == [5, 6, 3]
        # no recurrence of [5, 6] and no [6]: nothing to propose
        assert _ngram_propose([1, 2, 5, 6], 2, 3) == []
        # backoff to the unigram (most recent occurrence wins) when
        # the bigram never recurred
        assert _ngram_propose([7, 1, 2, 7, 3, 7], 2, 2) == [3, 7]
        # repetition loop proposes through the overlap
        assert _ngram_propose([9, 9, 9], 2, 4) == [9]
        assert _ngram_propose([5], 2, 4) == []
        assert _ngram_propose(t, 2, 0) == []

    def test_greedy_equivalence_with_spec_on(self, tiny_lm,
                                             mixed_prompts,
                                             sequential_greedy):
        # acceptance: speculation ON is token-identical to OFF, across
        # page boundaries (page_size 8, contexts grow past 16)
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=3, prefill_chunk=8, spec_k=4))
        outs = eng.generate(mixed_prompts, max_new_tokens=6, top_k=0)
        assert outs == sequential_greedy
        assert eng.pool.pages_in_use == 0
        eng.shutdown()

    def test_spec_accepts_drafts_and_advances_multitoken(
            self, tiny_lm, mixed_prompts):
        eng0 = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=3, prefill_chunk=8,
            prefix_cache=False))
        ref = eng0.generate(mixed_prompts, max_new_tokens=16, top_k=0)
        st0 = eng0.stats()
        eng0.shutdown()
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=3, prefill_chunk=8, spec_k=4))
        outs = eng.generate(mixed_prompts, max_new_tokens=16, top_k=0)
        st = eng.stats()
        assert outs == ref
        # the tiny model settles into repetition, so the n-gram
        # proposer fires and the verify step accepts drafts: more than
        # one token per decode dispatch (deterministic: fixed seeds)
        assert st['spec_proposed_tokens_total'] > 0
        assert st['spec_accepted_tokens_total'] > 0
        assert st['decode_steps_total'] < st0['decode_steps_total']
        assert st['decode_tokens_total'] == st0['decode_tokens_total']
        assert 0 < st['spec_acceptance_rate'] <= 1
        eng.shutdown()

    def test_spec_eos_early_exit_token_identical(self, tiny_lm,
                                                 mixed_prompts):
        # pick an eos that actually occurs mid-stream in the baseline
        # output, then require speculation to stop at exactly the same
        # token — nothing after eos may escape a multi-token burst
        eng0 = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=3, prefill_chunk=8,
            prefix_cache=False))
        base = eng0.generate(mixed_prompts, max_new_tokens=12, top_k=0)
        eng0.shutdown()
        gen0 = [o[len(p):] for o, p in zip(base, mixed_prompts)]
        eos = gen0[0][len(gen0[0]) // 2]       # fires mid-generation
        ref = []
        for g, p in zip(gen0, mixed_prompts):
            cut = g.index(eos) + 1 if eos in g else len(g)
            ref.append(p + g[:cut])
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=3, prefill_chunk=8, spec_k=4))
        outs = eng.generate(mixed_prompts, max_new_tokens=12,
                            eos_token_id=int(eos), top_k=0)
        assert outs == ref
        for o, p in zip(outs, mixed_prompts):
            gen = o[len(p):]
            assert eos not in gen[:-1]         # eos only terminal
        eng.shutdown()

    def test_spec_respects_max_new_tokens(self, tiny_lm):
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=2, prefill_chunk=8, spec_k=4))
        outs = eng.generate([[1, 2, 3, 1, 2, 3, 1, 2]],
                            max_new_tokens=3, top_k=0)
        assert len(outs[0]) == 8 + 3
        eng.shutdown()

    def test_spec_with_sampling_rows_mixed_batch(self, tiny_lm):
        # greedy rows speculate; a top-k row rides the same verify
        # dispatch through the sampled column — both must complete
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=2, prefill_chunk=8, spec_k=3,
            seed=5))
        greedy = eng.submit([1, 2, 3, 1, 2, 3, 1], max_new_tokens=8,
                            top_k=0)
        sampled = eng.submit([4, 5, 6, 7], max_new_tokens=8, top_k=4,
                             temperature=0.9)
        while eng.scheduler.has_work:
            eng.step()
        assert len(greedy.generated) == 8
        assert 1 <= len(sampled.generated) <= 8
        eng.shutdown()

    def test_spec_with_prefix_and_preemption_pressure(
            self, tiny_lm, shared_prefix_prompts):
        # everything on at once under pool pressure: outputs must
        # still match the plain PR-5 engine
        eng0 = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=3, prefill_chunk=8,
            prefix_cache=False))
        ref = eng0.generate(shared_prefix_prompts, max_new_tokens=8,
                            top_k=0)
        eng0.shutdown()
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=3, prefill_chunk=8, spec_k=4,
            num_pages=9))
        outs = eng.generate(shared_prefix_prompts, max_new_tokens=8,
                            top_k=0)
        assert outs == ref
        assert eng.pool.pages_in_use == 0
        eng.shutdown()

    def test_spec_trace_and_gauges(self, tiny_lm, mixed_prompts):
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=3, prefill_chunk=8, spec_k=4))
        eng.generate(mixed_prompts, max_new_tokens=16, top_k=0)
        eng.publish_metrics()
        from paddle_tpu.serving import metrics as sm
        snap = sm.serve_snapshot()
        assert snap['ptpu_serve_spec_proposed_tokens_total'] > 0
        assert snap['spec_acceptance_rate'] is not None
        # journals carry spec_verify events; reconstruct() aggregates
        # per-request proposed/accepted
        table = eng.request_table()
        assert sum(r['spec_proposed'] for r in table.values()) == \
            eng.stats()['spec_proposed_tokens_total']
        assert sum(r['spec_accepted'] for r in table.values()) == \
            eng.stats()['spec_accepted_tokens_total']
        eng.shutdown()


# ---------------------------------------------------------------------------
# int8 KV pages + weight-only-quantized decode (ISSUE 7)
# ---------------------------------------------------------------------------
class TestQuantizedKV:
    def test_quantized_kernel_and_fallback_match_oracle(self):
        # quantize fp32 pages per (slot, head); the dequantizing kernel
        # and dense fallback must agree with each other to fp32
        # precision and sit within the int8 rounding envelope of the
        # unquantized oracle — across page boundaries (rows span 1..4
        # pages, shuffled tables)
        q, kp, vp, pt, sl, ql, H, D = _mixed_case(seed=2)
        N, ps, HD = kp.shape
        kq, ks = pa.quantize_kv_rows(jnp.asarray(kp), H)
        vq, vs = pa.quantize_kv_rows(jnp.asarray(vp), H)
        kq, vq = kq.reshape(N, ps, HD), vq.reshape(N, ps, HD)
        ks, vs = ks.reshape(N, ps, H), vs.reshape(N, ps, H)
        o_k = pa.ragged_paged_attention_pallas(
            jnp.asarray(q), kq, vq, jnp.asarray(pt), jnp.asarray(sl),
            jnp.asarray(ql), num_heads=H, head_dim=D,
            k_scales=ks, v_scales=vs)
        o_d = pa.ragged_paged_attention_dense(
            jnp.asarray(q), kq, vq, jnp.asarray(pt), jnp.asarray(sl),
            jnp.asarray(ql), num_heads=H, head_dim=D,
            k_scales=ks, v_scales=vs)
        ref = _oracle(q, kp, vp, pt, sl, ql, H, D)
        for b in range(q.shape[0]):
            np.testing.assert_allclose(
                np.asarray(o_k)[b, :ql[b]], np.asarray(o_d)[b, :ql[b]],
                rtol=2e-4, atol=2e-5)
            np.testing.assert_allclose(
                np.asarray(o_d)[b, :ql[b]], ref[b, :ql[b]],
                rtol=5e-2, atol=5e-2)

    def test_write_kv_pages_quantized_scatter(self):
        ps, H, D, N = 4, 2, 3, 5
        HD = H * D
        kp = jnp.zeros((N, ps, HD), jnp.int8)
        vp = jnp.zeros((N, ps, HD), jnp.int8)
        ks = jnp.zeros((N, ps, H))
        vs = jnp.zeros((N, ps, H))
        k_new = jnp.asarray(
            np.arange(2 * 3 * HD, dtype=np.float32).reshape(2, 3, HD)
            + 1.0)
        pt = jnp.asarray([[3, 1, 0, 0], [2, 2, 2, 2]], jnp.int32)
        sl = jnp.asarray([7, 1], jnp.int32)
        ql = jnp.asarray([2, 0], jnp.int32)    # row 1 idle: no writes
        kp2, vp2, ks2, vs2 = pa.write_kv_pages_quantized(
            kp, vp, ks, vs, k_new, 2 * k_new, pt, sl, ql, num_heads=H)
        kp2, ks2 = np.asarray(kp2), np.asarray(ks2)
        # positions 5, 6 of row 0 -> page_table[1] slots 1, 2; the
        # dequantized rows must match the written values within half a
        # bin of the per-(slot, head) scale
        want = np.asarray(k_new)[0, :2].reshape(2, H, D)
        for slot, tok in ((1, 0), (2, 1)):
            deq = (kp2[1, slot].reshape(H, D).astype(np.float32)
                   * ks2[1, slot][:, None])
            bound = ks2[1, slot][:, None] / 2 + 1e-6
            assert (np.abs(deq - want[tok]) <= bound).all()
        # nothing else written (idle row dropped by the scatter)
        assert (np.abs(kp2).sum(-1) > 0).sum() == 2
        assert (ks2 > 0).sum() == 2 * H
        vdeq = (np.asarray(vp2)[1, 1].reshape(H, D).astype(np.float32)
                * np.asarray(vs2)[1, 1][:, None])
        assert (np.abs(vdeq - 2 * want[0])
                <= np.asarray(vs2)[1, 1][:, None] / 2 + 1e-6).all()

    def test_int8_kv_engine_matches_fp32_greedy(
            self, tiny_lm, mixed_prompts, sequential_greedy):
        # acceptance: int8-KV continuous batching == fp32-KV greedy
        # outputs across page boundaries, on BOTH routes
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=3, prefill_chunk=8,
            kv_dtype='int8'))
        outs = eng.generate(mixed_prompts, max_new_tokens=6, top_k=0)
        assert outs == sequential_greedy
        assert eng.pool.quantized
        assert eng.pool.stats()['kv_dtype'] == 'int8'
        eng.shutdown()
        flags.set_flags({'FLAGS_paged_attention_kernel': True})
        try:
            eng_k = ServingEngine(tiny_lm, ServingConfig(
                page_size=8, max_batch_size=3, prefill_chunk=8,
                kv_dtype='int8'))
            outs_k = eng_k.generate(mixed_prompts, max_new_tokens=6,
                                    top_k=0)
            eng_k.shutdown()
        finally:
            flags.set_flags({'FLAGS_paged_attention_kernel': None})
        assert outs_k == sequential_greedy

    def test_int8_kv_preemption_resume_equivalence(
            self, tiny_lm, mixed_prompts, sequential_greedy):
        # pool pressure exercises preempt/re-prefill on quantized
        # pages: slots re-quantize on resume, outputs must not change
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=3, prefill_chunk=8,
            num_pages=4, kv_dtype='int8'))
        outs = eng.generate(mixed_prompts, max_new_tokens=6, top_k=0)
        assert outs == sequential_greedy
        assert eng.stats()['preemptions_total'] > 0
        eng.shutdown()

    def test_int8_pool_capacity_at_least_2x(self, tiny_lm):
        # acceptance: the int8 pool fits >= 2x the in-flight tokens at
        # the same byte budget vs the default (fp32 on CPU) pool
        dense = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=2))
        quant = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=2, kv_dtype='int8'))
        d, qs = dense.pool.stats(), quant.pool.stats()
        assert d['num_pages'] == qs['num_pages']
        ratio = d['bytes_per_token'] / qs['bytes_per_token']
        assert ratio >= 2.0, ratio
        assert qs['pool_bytes'] * 2 <= d['pool_bytes']
        # byte math is exact: int8 pages + fp32 per-(slot, head) scales
        attn = tiny_lm.gpt.layers[0].attn
        hd = attn.local_heads * attn.head_dim
        per_tok = 2 * (hd + attn.local_heads * 4) * \
            tiny_lm.config.num_layers
        assert qs['bytes_per_token'] == per_tok
        dense.shutdown()
        quant.shutdown()


class TestWeightOnlyQuantizedDecode:
    def test_predictor_decode_top1_equivalent(
            self, tiny_lm, mixed_prompts, sequential_greedy):
        # acceptance: weight-only-quantized decode through the
        # inference.Predictor produces top-1-equivalent greedy output
        from paddle_tpu import inference
        cfg = inference.Config()
        cfg.enable_serving_engine(tiny_lm, max_new_tokens=6, top_k=0,
                                  page_size=8, max_batch_size=3,
                                  prefill_chunk=8, weight_dtype='int8')
        pred = inference.create_predictor(cfg)
        outs = pred.run([mixed_prompts])[0]
        for i, want in enumerate(sequential_greedy):
            assert outs[i, :len(want)].tolist() == want
        st = pred._engine.stats()
        assert st['weight_dtype'] == 'int8'
        # every 2-D non-embedding matmul weight quantized: qkv/out +
        # fc1/fc2 per layer = 4 * num_layers
        assert st['quantized_params'] == 4 * tiny_lm.config.num_layers
        pred._engine.shutdown()

    def test_weight_and_kv_quantized_together(self, tiny_lm,
                                              mixed_prompts,
                                              sequential_greedy):
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=3, prefill_chunk=8,
            kv_dtype='int8', weight_dtype='int8'))
        outs = eng.generate(mixed_prompts, max_new_tokens=6, top_k=0)
        assert outs == sequential_greedy
        eng.shutdown()

    def test_invalid_weight_dtype_rejected(self):
        with pytest.raises(ValueError, match='weight_dtype'):
            ServingConfig(weight_dtype='int4')


# ---------------------------------------------------------------------------
# metrics + predictor wiring
# ---------------------------------------------------------------------------
class TestServingSurface:
    def test_serve_gauges_in_step_telemetry(self, tiny_lm):
        from paddle_tpu.profiler import StepTelemetry
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=2, prefill_chunk=8))
        eng.generate([[2, 3, 4], [6, 7]], max_new_tokens=3, top_k=0)
        snap = StepTelemetry(publish=False).snapshot()
        serve = snap.get('serve')
        assert serve, 'snapshot has no serve section'
        assert serve['ptpu_serve_requests_completed_total'] >= 2
        assert serve['ptpu_serve_kv_pages_total'] == eng.pool.num_pages
        assert serve['ptpu_serve_ttft_seconds']['count'] >= 2
        eng.shutdown()

    def test_health_dump_serve_renders(self, tiny_lm):
        import importlib.util
        import os
        spec = importlib.util.spec_from_file_location(
            'health_dump', os.path.join(
                os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))),
                'tools', 'health_dump.py'))
        hd = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(hd)
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=2, prefill_chunk=8))
        eng.generate([[2, 3, 4]], max_new_tokens=2, top_k=0)
        eng.publish_metrics()
        from paddle_tpu.serving import metrics as sm
        doc = {'telemetry': {'serve': sm.serve_snapshot()}}
        serve = hd._find_serve(doc)
        assert serve is not None
        text = hd.render_serve(serve)
        assert 'decode throughput' in text
        assert 'KV pool' in text
        eng.shutdown()

    def test_predictor_runs_on_engine(self, tiny_lm, mixed_prompts,
                                      sequential_greedy):
        from paddle_tpu import inference
        cfg = inference.Config()
        cfg.enable_serving_engine(tiny_lm, max_new_tokens=6, top_k=0,
                                  page_size=8, max_batch_size=3,
                                  prefill_chunk=8)
        pred = inference.create_predictor(cfg)
        assert pred.get_input_names() == ['input_ids']
        outs = pred.run([mixed_prompts])
        assert len(outs) == 1
        padded = outs[0]
        for i, want in enumerate(sequential_greedy):
            got = padded[i, :len(want)].tolist()
            assert got == want
        # padded [B, L] array input round-trips too (rows pad-trimmed)
        n = max(len(p) for p in mixed_prompts[:2])
        arr = np.zeros((2, n), np.int32)
        for i, p in enumerate(mixed_prompts[:2]):
            arr[i, :len(p)] = p
        outs2 = pred.run([arr])
        for i, want in enumerate(sequential_greedy[:2]):
            assert outs2[0][i, :len(want)].tolist() == want
        # edge inputs fail loudly at the Predictor, not deep in the
        # engine: all-pad rows and empty batches
        with pytest.raises(ValueError, match='rows \\[1\\] are empty'):
            pred.run([np.asarray([[5, 0, 0], [0, 0, 0]], np.int32)])
        assert pred.run([[]])[0].shape == (0, 0)


# ---------------------------------------------------------------------------
# the seam: one step builder, one dispatch path (ISSUE 30), and since
# ISSUE 31 one mixed program where a [1, C] prefill program and the
# [B, 1] step took turns. Which programs compile, which spans open in
# which order with which args, when the host fetches, what the ledger
# is fed — pinned here.
# ---------------------------------------------------------------------------
def _drain(eng):
    while eng.scheduler.has_work:
        eng.step()


# knobs -> the `_step_fns` keys the mixed run below compiles
# the mixed program and, compiled beside it, the [B, 1] step of the same
# sampling mode
MIXED = {('mixed', 3, 2, 8, False), ('mixed', 3, 2, 8, True),
         (3, 1, False, False), (3, 1, True, False)}
SEAM_CASES = {
    'plain': ({}, MIXED),
    'spec_k=2': ({'spec_k': 2},
                 MIXED | {(3, 3, False, True), (3, 3, True, True)}),
    'fused_k=4': ({'fused_k': 4},
                  MIXED | {('fused', 3, 4, False), ('fused', 3, 4, True)}),
    'int8_weights': ({'weight_dtype': 'int8'}, MIXED),
}
DEVICE_SPANS = ('serve::prepare', 'serve::compiled_step',
                'serve::sample_fetch', 'serve::accept')


class TestOneDispatchPath:
    def _mixed_run(self, model, knobs, eos, monkeypatch):
        """Four greedy prompts (three longer than a chunk) through a
        pool too small for them — a preemption — then, on the quiet
        engine, a sampled request beside a greedy one: with `eos` it
        ends in the middle of a decode window."""
        import paddle_tpu.profiler as prof
        import paddle_tpu.serving.engine as engine_mod
        rng = np.random.RandomState(3)
        crowd = [list(rng.randint(1, 128, n)) for n in (19, 11, 21, 5)]
        pair = [list(rng.randint(1, 128, n)) for n in (13, 10)]
        fetches = []
        real = engine_mod._host_fetch
        monkeypatch.setattr(engine_mod, '_host_fetch',
                            lambda x: fetches.append(1) or real(x))
        eng = ServingEngine(model, ServingConfig(
            page_size=8, max_batch_size=3, prefill_chunk=8, num_pages=9,
            seed=11, **knobs))
        mark = prof.mark()
        reqs = [eng.submit(p, max_new_tokens=10, top_k=0) for p in crowd]
        _drain(eng)
        reqs.append(eng.submit(pair[0], max_new_tokens=10, top_k=8,
                               temperature=1.5, eos_token_id=eos))
        reqs.append(eng.submit(pair[1], max_new_tokens=10, top_k=0))
        _drain(eng)
        spans = sorted((s for s in prof.spans(since_id=mark)
                        if s.name in DEVICE_SPANS),
                       key=lambda s: s.start_ns)
        events = {r.id: eng.tracer.events(r.id) for r in reqs}
        out = (reqs, spans, events, set(eng._step_fns), eng.stats(),
               len(fetches))
        monkeypatch.setattr(engine_mod, '_host_fetch', real)
        eng.shutdown()
        return out

    @pytest.mark.parametrize('case', list(SEAM_CASES))
    def test_mixed_run_compiles_and_spans_as_pinned(
            self, tiny_lm, case, monkeypatch):
        knobs, keys = SEAM_CASES[case]
        reqs, *_ = self._mixed_run(tiny_lm, knobs, None, monkeypatch)
        g = reqs[4].generated
        # an id the sampled row first emits second or third in a window
        e = next(e for e in (2, 3) if g[e] not in g[:e])
        reqs, spans, events, compiled, st, fetches = self._mixed_run(
            tiny_lm, knobs, g[e], monkeypatch)
        assert reqs[4].generated == g[:e + 1]
        assert [len(r.generated) for r in reqs[:4] + reqs[5:]] == [10] * 5
        assert st['preemptions_total'] >= 1
        assert compiled == keys
        if 'fused_k' in knobs:
            last = [ev for ev in events[reqs[4].id]
                    if ev['event'] == 'fused_decode'][-1]
            assert 0 < last['accepted'] < last['k'] == 4

        # a verify / fused dispatch: prepare -> compiled_step ->
        # sample_fetch -> accept, with nothing in flight. A step's decode
        # and mixed dispatches are all queued first (prepare ->
        # compiled_step each) and, where nothing holds it back, the NEXT
        # step's behind them (`in_flight` 1); they land in their order,
        # one step later: the decode rows' accept, then the chunks';
        # inner chunks alone are not fetched
        names = [s.name.split('::')[1] for s in spans]
        shapes = []
        unfetched = 0
        queue = []          # the dispatches waiting for their turn
        for i, s in enumerate(spans):
            if s.name == 'serve::compiled_step':
                shape = s.args['shape']
                shapes.append(shape)
                assert names[i - 1] == 'prepare'
                assert 0 <= s.args['batch'] <= 3
                assert s.args['in_flight'] in (0, 1)
                if shape == 'mixed':
                    assert set(s.args) == {'shape', 'batch', 'in_flight',
                                           'prefill_rows'}
                    assert 1 <= s.args['prefill_rows'] <= 2
                    queue.append(s)
                    continue
                assert set(s.args) == {'shape', 'batch', 'in_flight'} | (
                    {'k'} if shape == 'fused' else set())
                assert s.args['batch'] >= 1
                if shape == 'decode':
                    queue.append(s)
                    continue
                assert not queue and not s.args['in_flight']
                assert names[i + 1:i + 3] == ['sample_fetch', 'accept']
                assert set(spans[i + 2].args) == {'emitted', 'retired'}
                assert shape != 'fused' or s.args['k'] == 4
            elif s.name == 'serve::accept' and 'chunks' in s.args:
                # the turn of the oldest queued dispatch: a mixed one
                d = queue.pop(0)
                assert s.args['chunks'] == d.args['prefill_rows']
                assert set(s.args) == {'chunks', 'emitted', 'retired'}
                before = names[i - 2:i] if d.args['batch'] else \
                    names[i - 1:i]
                if d.args['batch']:
                    assert before == ['sample_fetch', 'accept']
                    assert set(spans[i - 1].args) == {'emitted',
                                                      'retired'}
                elif before != ['sample_fetch']:
                    # inner chunks beside an idle decode group
                    unfetched += 1
                    assert s.args['emitted'] == 0
            elif s.name == 'serve::accept' and queue \
                    and queue[0].args['shape'] == 'decode':
                queue.pop(0)        # the [B, 1] step's turn
                assert names[i - 1] == 'sample_fetch'
                assert set(s.args) == {'emitted', 'retired'}
        launched_behind = {s.args['in_flight'] for s in spans
                           if s.name == 'serve::compiled_step'}
        pipe = st['pipeline_drains_total']
        if 'fused_k' in knobs:
            # every step waits for the ids of the one before it
            assert launched_behind == {0}
            assert st['pipelined_steps_total'] == 0 < pipe['fused']
        elif 'spec_k' in knobs:
            # ... wherever a greedy row decodes: its proposal reads them
            assert pipe['verify'] > st['pipelined_steps_total']
        else:
            assert launched_behind == {0, 1}
            assert st['pipelined_steps_total'] > 0
            assert pipe['preempt'] >= 1 and pipe['idle'] >= 2
            # the sampled row was launched a step past its EOS, and
            # what it computed there was dropped
            assert st['overrun_tokens_total'] == 1
        assert not queue
        assert set(shapes) == {'mixed', 'decode'} | (
            {'verify'} if 'spec_k' in knobs else set()) | (
            {'fused'} if 'fused_k' in knobs else set())
        chunks = [ev for evs in events.values() for ev in evs
                  if ev['event'] == 'prefill_chunk']
        assert sum(s.args['prefill_rows'] for s in spans
                   if s.name == 'serve::compiled_step'
                   and s.args['shape'] == 'mixed') == len(chunks)
        assert unfetched > 0
        assert names.count('sample_fetch') == fetches == (
            len(shapes) - unfetched)
        assert sum(s.args['emitted'] for s in spans
                   if s.name == 'serve::accept') == sum(
            len(r.generated) for r in reqs)
        assert st['dispatches_total'] == len(shapes)
        assert 1.0 <= st['prefill_rows_per_dispatch'] <= 2.0
        assert 1.0 <= st['dispatches_per_step'] < 2.0
        assert 0.0 < st['padded_prefill_token_share'] < 1.0

    def test_fused_window_feeds_the_ledger_what_serial_steps_do(
            self, tiny_lm):
        """Counts, not clocks: KV tokens read, live pages, page slots
        and prefill tokens. Three prompts of two chunks each and eight
        decode tokens a row are two whole windows of four."""
        rng = np.random.RandomState(5)
        prompts = [list(rng.randint(1, 128, n)) for n in (13, 10, 15)]
        fed = {}
        for k in (1, 4):
            eng = ServingEngine(tiny_lm, ServingConfig(
                page_size=8, max_batch_size=3, prefill_chunk=8,
                fused_k=k))
            rows = fed[k] = []
            observe = eng.ledger.observe_iteration
            eng.ledger.observe_iteration = (
                lambda _o=observe, _r=rows, **kw: _r.append(kw)
                or _o(**kw))
            eng.generate(prompts, max_new_tokens=9, top_k=0)
            assert eng.stats()['fused_windows_total'] == (2 if k > 1
                                                          else 0)
            eng.shutdown()

        def total(k, key):
            return sum(kw[key] for kw in fed[k])
        assert len(fed[1]) == len(fed[4])       # one record an iteration
        for key in ('paged_live_pages', 'paged_page_slots',
                    'prefill_tokens', 'prefill_ctx_tokens'):
            assert total(4, key) == total(1, key) > 0, key
        # a window's reads are spread evenly over its iterations, whole
        # tokens each: up to k - 1 a window are dropped
        assert 0 <= total(1, 'kv_read_tokens') - total(
            4, 'kv_read_tokens') < 2 * 4
        assert total(1, 'kv_read_tokens') > 0

    @pytest.mark.parametrize('rows', ['greedy', 'sampled'])
    def test_fused_body_is_the_decode_step(self, tiny_lm, rows):
        """One decode position through the [B, 1] step and through a
        fused window of one iteration, from one pool: the same ids and
        the same pages."""
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=3, prefill_chunk=8, fused_k=2,
            seed=5))
        rng = np.random.RandomState(9)
        for i, n in enumerate((13, 6, 10)):
            eng.submit(list(rng.randint(1, 128, n)), max_new_tokens=8,
                       top_k=(6 if rows == 'sampled' and i != 1 else 0),
                       temperature=1.3)
        while not all(r is not None and r.state == RequestState.RUNNING
                      for r in eng.scheduler.slots):
            eng.step()
        batch = []
        for i, req in enumerate(eng.scheduler.slots):
            eng.pool.ensure_capacity(req.id, req.context_len)
            batch.append((i, req, [req.generated[-1]], req.context_len))
        pool0 = eng.pool.kv
        def call(shape):    # a program called and fetched at once
            return eng._fetch(eng._enqueue(shape, batch, 3, 1),
                              behind=False)
        ids = call('decode')
        pages = eng.pool.kv
        eng.pool.kv = pool0
        ids_fused = call('fused')
        assert {k[0] for k in eng._step_fns} >= {3, 'fused'}
        assert ids_fused.shape == (3, 1)
        assert ids_fused[:, 0].tolist() == ids.tolist()
        for layer, layer_fused in zip(pages, eng.pool.kv):
            for a, b in zip(layer, layer_fused):
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(b))
        eng.shutdown()
