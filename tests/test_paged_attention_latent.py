"""Latent attention on the ONE paged kernel (`latent=(value lanes,
rotary lanes)`: every query head reads the one stored row whole and
multiplies its first value lanes; the pool is ONE array) against the
form written out head by head, in the batched (decode) product, the
chunk product and the chunk product in query tiles, kernel (interpret
mode) and dense fallback alike; the write into the one array; what a
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


def _case(T, seed=0):
    rng = np.random.default_rng(seed + T)
    B, pages = 3, 40
    seq = np.array([max(T, 17), max(T, 9), 1])
    ql = np.array([T, min(T, 2), 0])        # full, partial, idle
    pt = np.stack([rng.permutation(pages)[:P] for _ in range(B)])
    q = np.zeros((B, T, HQ, LANES), np.float32)
    q[..., :VALUE + ROTARY] = 0.1 * rng.standard_normal(
        (B, T, HQ, VALUE + ROTARY))
    rows = np.zeros((pages, PS, LANES), np.float32)
    rows[..., :VALUE + ROTARY] = rng.standard_normal(
        (pages, PS, VALUE + ROTARY))
    return q.reshape(B, T, -1), rows, pt, seq, ql


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


@pytest.mark.parametrize('tile_rows,tiles', [(80, 2), (40, 4), (50, 4)])
def test_a_chunk_runs_in_query_tiles_of_whole_heads(monkeypatch, tile_rows,
                                                    tiles):
    """40 queries x 4 heads in tiles of 2 or 1 heads (50 rows hold one
    head of 40): every tile walks the row's pages again, and a batch
    row's first wave is started under the tile before it."""
    monkeypatch.setattr(pa, '_LATENT_TILE_ROWS', tile_rows)
    seen = []
    call = pa._paged_call
    monkeypatch.setattr(pa, '_paged_call', lambda *a, **k: (
        seen.append(k['q_tiles']), call(*a, **k))[1])
    q, rows, pt, seq, ql = _case(40, seed=1)
    got = _run('kernel', q, rows, pt, seq, ql)
    assert seen == [tiles]
    live = (np.arange(40)[None, :] < ql[:, None])[..., None]
    np.testing.assert_allclose(np.where(live, got, 0),
                               _written_out(q, rows, pt, seq, ql),
                               rtol=2e-4, atol=2e-4)


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
