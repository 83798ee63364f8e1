"""Latent attention on the ONE paged kernel (`latent=(value lanes,
rotary lanes)`: every query head reads the one stored row whole and
multiplies its first value lanes; the pool is ONE array) against the
form written out head by head, in one tile (decode, verify) and in the
chunk's query tiles over TOKENS — live, partly live and dead tiles, each
held to the pages its own queries read —, kernel (interpret mode) and
dense fallback alike; the write into the one array; what a
latent call refuses; and the other calls left as they were."""
import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.ops.pallas import paged_attention as pa

HQ, VALUE, ROTARY, PS, P = 4, 512, 64, 4, 12
LANES = 640                              # 576 lanes in whole tiles


def _written_out(q, pages, pt, seq_lens, q_lens):
    """out[b, t, h] = softmax(q_h . row) row[:value], for the live
    queries; no scale (the caller's is in q)."""
    q, pg = np.asarray(q, np.float64), np.asarray(pages, np.float64)
    B, T, _ = q.shape
    out = np.zeros((B, T, HQ * VALUE))
    for b in range(B):
        rows = pg[pt[b]].reshape(-1, LANES)[:seq_lens[b]]
        for t in range(q_lens[b]):
            pos = seq_lens[b] - q_lens[b] + t
            for h in range(HQ):
                s = rows[:pos + 1] @ q[b, t, h * LANES:(h + 1) * LANES]
                p = np.exp(s - s.max())
                out[b, t, h * VALUE:(h + 1) * VALUE] = \
                    p / p.sum() @ rows[:pos + 1, :VALUE]
    return out


def _filled(rng, B, T, pages):
    """q [B, T, HQ * LANES] and a pool [pages, PS, LANES], zeros in the
    padding lanes of both."""
    q = np.zeros((B, T, HQ, LANES), np.float32)
    q[..., :VALUE + ROTARY] = 0.1 * rng.standard_normal(
        (B, T, HQ, VALUE + ROTARY))
    rows = np.zeros((pages, PS, LANES), np.float32)
    rows[..., :VALUE + ROTARY] = rng.standard_normal(
        (pages, PS, VALUE + ROTARY))
    return q.reshape(B, T, -1), rows


def _case(T, seed=0):
    rng = np.random.default_rng(seed + T)
    B, pages = 3, 40
    seq = np.array([max(T, 17), max(T, 9), 1])
    ql = np.array([T, min(T, 2), 0])        # full, partial, idle
    pt = np.stack([rng.permutation(pages)[:P] for _ in range(B)])
    return *_filled(rng, B, T, pages), pt, seq, ql


def _run(route, q, rows, pt, seq, ql, **kw):
    fn = pa.ragged_paged_attention_pallas if route == 'kernel' \
        else pa.ragged_paged_attention_dense
    if route == 'kernel':
        kw['interpret'] = True
    return np.asarray(fn(
        jnp.asarray(q), jnp.asarray(rows), None,
        jnp.asarray(pt, jnp.int32), jnp.asarray(seq, jnp.int32),
        jnp.asarray(ql, jnp.int32), num_heads=HQ, head_dim=LANES,
        latent=(VALUE, ROTARY), **kw))


@pytest.mark.parametrize('route', ['kernel', 'dense'])
@pytest.mark.parametrize('T', [1, 3, 40])   # 40 x 4 rows: the chunk product
def test_latent_paged_attention(T, route):
    q, rows, pt, seq, ql = _case(T)
    got = _run(route, q, rows, pt, seq, ql)
    assert got.shape == (3, T, HQ * VALUE)
    live = (np.arange(T)[None, :] < ql[:, None])[..., None]
    np.testing.assert_allclose(np.where(live, got, 0),
                               _written_out(q, rows, pt, seq, ql),
                               rtol=2e-4, atol=2e-4)


def _spy_tiles(monkeypatch, tile_rows):
    """Hold a tile to `tile_rows` rows; -> the q_tiles each call took."""
    monkeypatch.setattr(pa, '_LATENT_TILE_ROWS', tile_rows)
    seen = []
    call = pa._paged_call
    monkeypatch.setattr(pa, '_paged_call', lambda *a, **k: (
        seen.append(k['q_tiles']), call(*a, **k))[1])
    return seen


@pytest.mark.parametrize('tile_rows,tile,tiles', [
    (80, 20, 2), (40, 10, 4), (32, 8, 5), (50, 10, 4)])
def test_a_chunk_runs_in_query_tiles_of_whole_tokens(monkeypatch, tile_rows,
                                                     tile, tiles):
    """40 queries x 4 heads in tiles of 20, 10 and 8 tokens, every head
    of a token in its tile (50 rows hold 12 tokens, and 10 divide 40):
    a tile walks the pages its own queries read, and a batch row's
    first wave is started under the tile before it."""
    seen = _spy_tiles(monkeypatch, tile_rows)
    assert pa.latent_tile_tokens(40, HQ) == tile
    q, rows, pt, seq, ql = _case(40, seed=1)
    got = _run('kernel', q, rows, pt, seq, ql)
    assert seen == [tiles]
    live = (np.arange(40)[None, :] < ql[:, None])[..., None]
    np.testing.assert_allclose(np.where(live, got, 0),
                               _written_out(q, rows, pt, seq, ql),
                               rtol=2e-4, atol=2e-4)


def _ragged(T=40, seed=2):
    """Five rows of one batch: q_len a whole tile, none, a tile and one,
    one, and all T of 40 in tiles of 10 — live, partly live and dead
    tiles side by side, a live row behind every run of dead tiles."""
    rng = np.random.default_rng(seed)
    ql = np.array([10, 0, 11, 1, T])
    seq = np.array([10, 1, 30, 47, T + 8])      # no context .. 46 keys
    B, pages = len(ql), 80
    pt = rng.permutation(pages)[:B * P].reshape(B, P)
    return *_filled(rng, B, T, pages), pt, seq, ql


@pytest.mark.parametrize('wave_pages', [8, 2])
def test_live_partly_live_and_dead_tiles_in_one_batch(monkeypatch,
                                                      wave_pages):
    """q_len of tile, 0, tile + 1, 1 and T: every live query is right —
    the row after a run of dead tiles too, which opens on its own copy
    (`nxt`: nothing was started for it) — in one wave a tile and in
    several."""
    seen = _spy_tiles(monkeypatch, 40)
    monkeypatch.setattr(pa, '_WAVE_BYTES', wave_pages * PS * LANES * 4)
    q, rows, pt, seq, ql = _ragged()
    got = _run('kernel', q, rows, pt, seq, ql)
    assert seen == [4]
    live = (np.arange(40)[None, :] < ql[:, None])[..., None]
    np.testing.assert_allclose(np.where(live, got, 0),
                               _written_out(q, rows, pt, seq, ql),
                               rtol=2e-4, atol=2e-4)


def test_a_dead_tile_writes_zeros(monkeypatch):
    """A tile whose first token is no query folds no wave: its output
    rows are exactly zero, as an idle row's are."""
    _spy_tiles(monkeypatch, 40)
    q, rows, pt, seq, ql = _ragged()
    got = _run('kernel', q, rows, pt, seq, ql).reshape(5, 4, 10, -1)
    for b, n in enumerate(ql):
        first_dead = -(-n // 10)
        assert first_dead == 4 or (got[b, first_dead:] == 0).all()
        assert np.abs(got[b, :first_dead]).max(initial=1) > 0


@pytest.mark.parametrize('context', [0, 5, 8])
def test_a_tile_stops_at_its_last_querys_page(monkeypatch, context):
    """Held by poison: NaN in every page past the one tile 0's last
    query sits in. A page that is copied reaches the output (0 x NaN):
    tile 0 stays finite and right — it never copied them — and the
    tiles that must read them do not (so the poison does bite). A
    chunk behind a context, and a document's first chunk: a triangle."""
    _spy_tiles(monkeypatch, 40)
    monkeypatch.setattr(pa, '_WAVE_BYTES', 2 * PS * LANES * 4)
    T, tile = 40, 10
    rng = np.random.default_rng(3)
    pt = rng.permutation(40)[None, :P]
    seq, ql = np.array([context + T]), np.array([T])
    q, rows = _filled(rng, 1, T, 40)
    want = _written_out(q, rows, pt, seq, ql)
    def walked(n):
        """Pairs of the row's first n queries, by the counter's rule."""
        return pa.latent_pairs_dispatched(context + n, n, T, HQ)
    for t in range(T // tile - 1):          # the last tile reads them all
        # the keys tile t multiplies, as the engine counts them: the
        # kernel may copy the pages that hold them and no other
        keys = (walked((t + 1) * tile) - walked(t * tile)) // tile
        assert keys == context + (t + 1) * tile
        own = -(-keys // PS)
        poisoned = rows.copy()
        poisoned[pt[0, own:]] = np.nan
        got = _run('kernel', q, poisoned, pt, seq, ql)
        np.testing.assert_allclose(got[0, :(t + 1) * tile],
                                   want[0, :(t + 1) * tile],
                                   rtol=2e-4, atol=2e-4)
        assert np.isnan(got[0, (t + 1) * tile:]).any()


@pytest.mark.parametrize('context,queries,T,heads,want', [
    (500, 1, 1, 64, 500),                   # a decode row: its keys
    (500, 4, 4, 64, 4 * 500),               # a verify row: one tile
    (40, 40, 64, 64, 32 * 32 + 32 * 40),    # 2 of 2 tiles, a triangle
    (1040, 40, 64, 64, 32 * 1032 + 32 * 1040),
    (1033, 33, 256, 64, 32 * 1032 + 32 * 1033),     # 2 of 8 tiles
    (1000, 0, 256, 64, 0),
    (17_861, 161, 256, 64, 32 * sum(17_700 + 32 * i for i in (1, 2, 3, 4, 5))
     + 32 * 17_861),                        # 6 of 8 tiles
])
def test_the_pairs_a_row_dispatches(context, queries, T, heads, want):
    """The tile rule as arithmetic (what the engine counts): a live
    tile's tokens, live or not, times the keys up to its last live
    query's; never under the real pairs, and them for a single query."""
    got = pa.latent_pairs_dispatched(context, queries, T, heads)
    assert got == want
    first = context - queries + 1
    assert got >= queries * first + queries * (queries - 1) // 2


def test_values_are_the_first_lanes_of_the_same_row():
    """Rotary lanes and the padding take part in the scores and never
    in the output: a change there moves the weights, not the values."""
    q, rows, pt, seq, ql = _case(1)
    base = _run('dense', q, rows, pt, seq, ql)
    # keys of one position only: the softmax is 1 there whatever q is,
    # so the output IS that row's value lanes
    seq1 = np.ones_like(seq)
    one = _run('kernel', q, rows, pt, seq1, np.minimum(ql, 1))
    for b in (0, 1):
        np.testing.assert_allclose(
            one[b, 0].reshape(HQ, VALUE), np.tile(rows[pt[b, 0], 0, :VALUE],
                                                 (HQ, 1)), rtol=1e-5)
    moved = rows.copy()
    moved[..., VALUE:VALUE + ROTARY] *= -1.0
    assert np.abs(_run('dense', q, moved, pt, seq, ql) - base)[0].max() \
        > 1e-3


def test_the_write_fills_the_one_array_and_zeroes_its_padding():
    pages = jnp.ones((6, PS, LANES), jnp.float32)
    new = jnp.full((2, 3, VALUE + ROTARY), 2.0)
    pt = jnp.asarray([[4, 1], [2, 0]], jnp.int32)
    out = np.asarray(pa.write_latent_pages(
        pages, new, pt, jnp.asarray([6, 2], jnp.int32),
        jnp.asarray([3, 2], jnp.int32)))
    # row 0 writes positions 3, 4, 5: slot 3 of page 4, slots 0, 1 of
    # page 1; row 1 its two live tokens at positions 0, 1 of page 2
    written = [(4, 3), (1, 0), (1, 1), (2, 0), (2, 1)]
    for page in range(6):
        for slot in range(PS):
            row = out[page, slot]
            if (page, slot) in written:
                assert (row[:VALUE + ROTARY] == 2).all() \
                    and (row[VALUE + ROTARY:] == 0).all()
            else:
                assert (row == 1).all()


def test_what_a_latent_call_refuses():
    q = jnp.zeros((1, 1, HQ * LANES))
    pages = jnp.zeros((4, PS, LANES))
    args = (jnp.zeros((1, P), jnp.int32), jnp.ones((1,), jnp.int32),
            jnp.ones((1,), jnp.int32))
    kw = dict(num_heads=HQ, head_dim=LANES, latent=(VALUE, ROTARY))
    for route in (pa.ragged_paged_attention_dense,
                  pa.ragged_paged_attention_pallas):
        with pytest.raises(NotImplementedError, match='ONE array'):
            route(q, pages, pages, *args, **kw)
        with pytest.raises(NotImplementedError, match='ONE array'):
            route(q, pages, None, *args, window=8, **kw)
        with pytest.raises(NotImplementedError, match='ONE array'):
            route(q, pages, None, *args, num_kv_heads=2, **kw)
        with pytest.raises(ValueError, match='value'):
            route(q, pages, None, *args, num_heads=HQ, head_dim=LANES,
                  latent=(LANES, ROTARY))
        with pytest.raises(ValueError, match='stored row'):
            route(q, pages[..., :576], None, *args, **kw)


def test_the_mosaic_call_is_named_for_the_latent_body(monkeypatch):
    names = []
    real = pa.scaffold.pallas_call
    monkeypatch.setattr(pa.scaffold, 'pallas_call', lambda *a, **k: (
        names.append(k['name']), real(*a, **k))[1])
    q, rows, pt, seq, ql = _case(2, seed=5)
    _run('kernel', q, rows, pt, seq, ql)
    k = jnp.zeros((8, PS, 2 * 16))
    pa.ragged_paged_attention_pallas(
        jnp.zeros((1, 1, 2 * 16)), k, k, jnp.zeros((1, P), jnp.int32),
        jnp.ones((1,), jnp.int32), jnp.ones((1,), jnp.int32), num_heads=2,
        head_dim=16, interpret=True)
    # two queries a row: the chunk group's call; one: the decode rows'
    assert names == ['paged_attention_latent_chunk', 'paged_attention']
