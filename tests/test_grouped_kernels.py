"""The two kernels the sparse, grouped-query, windowed model leans on,
in Pallas interpret mode on the CPU: ragged paged attention with fewer
kv heads than query heads and a window against the dense route (and,
with neither, bit for bit what it gave before it learned them), and the
experts' grouped matmul against a loop over the experts."""
import hashlib

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import grouped_matmul as gmm
from paddle_tpu.ops.pallas import paged_attention as pa


def paged_inputs(B, T, Hq, Hk, D, ps, P, N, dtype, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, T, Hq * D)), dtype)
    k = jnp.asarray(rng.standard_normal((N, ps, Hk * D)), dtype)
    v = jnp.asarray(rng.standard_normal((N, ps, Hk * D)), dtype)
    pt = jnp.asarray(rng.permutation(N)[:B * P].reshape(B, P), jnp.int32)
    return q, k, v, pt


# (B, T, query heads, kv heads, seq_lens, q_lens, window): decode rows
# (the batched block-diagonal product) and chunks (kv groups stacked as
# rows), contexts below, at and past the window and a page edge, an
# idle row, a chunk that straddles the window
CASES = {
    'decode-groups-window': (4, 1, 4, 2, [1, 24, 25, 100], [1, 1, 1, 1], 24),
    'decode-groups': (4, 1, 4, 2, [1, 24, 25, 100], [1, 1, 0, 1], None),
    'decode-window': (3, 1, 4, 4, [17, 100, 128], [1, 1, 1], 40),
    'chunk-groups-window': (2, 40, 4, 2, [40, 100], [40, 33], 24),
    'chunk-groups': (2, 40, 4, 2, [40, 100], [40, 33], None),
    'chunk-window': (2, 40, 4, 4, [40, 100], [40, 33], 24),
    'verify-groups-window': (2, 3, 8, 2, [30, 77], [3, 2], 16),
}


@pytest.mark.parametrize('case', sorted(CASES))
def test_paged_attention_with_kv_groups_and_a_window(case):
    B, T, Hq, Hk, seqs, qlens, window = CASES[case]
    q, k, v, pt = paged_inputs(B, T, Hq, Hk, 16, 16, 8, 64, jnp.float32)
    args = (q, k, v, pt, jnp.asarray(seqs, jnp.int32),
            jnp.asarray(qlens, jnp.int32))
    kw = dict(num_heads=Hq, head_dim=16, num_kv_heads=Hk, window=window)
    got = pa.ragged_paged_attention_pallas(*args, **kw)
    want = pa.ragged_paged_attention_dense(*args, **kw)
    live = (np.arange(T)[None, :] < np.asarray(qlens)[:, None])[..., None]
    np.testing.assert_allclose(np.where(live, got, 0),
                               np.where(live, want, 0), atol=2e-6)


def test_a_window_reads_no_page_before_it():
    """Pages older than the window hold NaN: the loop must not copy
    them (a masked NaN would still poison p . v)."""
    q, k, v, pt = paged_inputs(1, 1, 4, 2, 16, 16, 8, 16, jnp.float32)
    old = pt[0, :4]                  # positions 0..63 of a 100-token row
    k, v = k.at[old].set(jnp.nan), v.at[old].set(jnp.nan)
    args = (q, k, v, pt, jnp.asarray([100], jnp.int32),
            jnp.asarray([1], jnp.int32))
    got = pa.ragged_paged_attention_pallas(
        *args, num_heads=4, head_dim=16, num_kv_heads=2, window=24)
    assert np.isfinite(np.asarray(got)).all()


# sha256 of the float32 bytes the kernel of the commit before kv groups
# and windows gave for these seeded inputs at the GPT server's head
# shape (16 heads of 128, pages of 16): a decode batch with an idle row
# and a 9-token chunk
BEFORE = {
    (3, 1): '9a1506fcbec1ad5083fe0e0dc9de1a8645dc302ad88ef7a73e98b8004a0a9b3c',
    (2, 9): '003d236f61aa5a6c5fa488b9f6e5e48a151a7859cdd4a35a29c189706de418d6',
}


@pytest.mark.parametrize('B,T,seqs,qlens', [
    (3, 1, [1, 40, 96], [1, 1, 0]), (2, 9, [9, 90], [9, 5])])
def test_without_groups_or_window_the_output_is_bit_for_bit_the_old(
        B, T, seqs, qlens):
    q, k, v, pt = paged_inputs(B, T, 16, 16, 128, 16, 6, 24, jnp.bfloat16,
                               seed=26)
    args = (q, k, v, pt, jnp.asarray(seqs, jnp.int32),
            jnp.asarray(qlens, jnp.int32))
    plain = pa.ragged_paged_attention_pallas(*args, num_heads=16,
                                             head_dim=128)
    told = pa.ragged_paged_attention_pallas(
        *args, num_heads=16, head_dim=128, num_kv_heads=16, window=None)
    digest = hashlib.sha256(
        np.asarray(plain.astype(jnp.float32)).tobytes()).hexdigest()
    assert digest == BEFORE[(B, T)]
    assert np.array_equal(np.asarray(plain.astype(jnp.float32)),
                          np.asarray(told.astype(jnp.float32)))


def test_int8_pages_refuse_groups_and_windows():
    q, k, v, pt = paged_inputs(1, 1, 4, 2, 16, 16, 4, 8, jnp.float32)
    k8, ks = pa.quantize_kv_rows(k, 2)
    v8, vs = pa.quantize_kv_rows(v, 2)
    with pytest.raises(NotImplementedError, match='int8 pages'):
        pa.ragged_paged_attention_pallas(
            q, k8, v8, pt, jnp.asarray([20], jnp.int32),
            jnp.asarray([1], jnp.int32), num_heads=4, head_dim=16,
            num_kv_heads=2, k_scales=ks, v_scales=vs)


# ---- grouped matmul -------------------------------------------------------
E, K, N = 8, 64, 32


def expert_loop(x_rows, ids, w, w_gate=None):
    """Row by row: each pair times its own expert's matrix."""
    out = np.zeros((len(ids), w.shape[2]), np.float32)
    for r, e in enumerate(ids):
        y = np.asarray(x_rows[r]) @ np.asarray(w[e])
        if w_gate is not None:
            g = np.asarray(x_rows[r]) @ np.asarray(w_gate[e])
            y = g / (1 + np.exp(-g)) * y
        out[r] = y
    return out


@pytest.mark.parametrize('ids', [
    np.random.default_rng(0).integers(0, E, 48),    # ragged
    np.full(48, 3),                                 # one expert takes all
    np.repeat([0, 7], 24),                          # six experts empty
    np.r_[np.random.default_rng(1).integers(0, E, 40), [E] * 8],  # 8 left out
], ids=['ragged', 'one-expert', 'two-experts', 'some-left-out'])
@pytest.mark.parametrize('gated', [False, True])
def test_grouped_matmul_against_a_loop_over_experts(ids, gated):
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((len(ids), K)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((E, K, N)) * 0.1, jnp.float32)
    wg = jnp.asarray(rng.standard_normal((E, K, N)) * 0.1, jnp.float32) \
        if gated else None
    p = gmm.plan(jnp.asarray(ids, jnp.int32), E, 16)
    tiles = (p['tile_expert'], p['tile_block'], p['n_live'])
    got = gmm.grouped_matmul_pallas(x[p['src']], w, *tiles, wg,
                                    interpret=True)
    dense = gmm.grouped_matmul_dense(x[p['src']], w, *tiles, wg)
    held = ids < E
    want = expert_loop(x[held], ids[held], w, wg)
    dest = np.asarray(p['dest'])
    assert (dest[~held] == got.shape[0]).all()      # left out: no row
    np.testing.assert_allclose(np.asarray(got)[dest[held]], want, atol=1e-5)
    np.testing.assert_allclose(np.asarray(dense)[dest[held]], want,
                               atol=1e-5)
    assert np.array_equal(np.asarray(p['counts']),
                          np.bincount(ids[held], minlength=E))
    # an expert without rows has no tile: its weights are never read
    live = int(p['n_live'][0])
    assert set(np.asarray(p['tile_expert'])[:live]) == set(ids[held])
    assert live == sum(-(-c // 16) for c in np.bincount(ids[held]))


def test_tile_rows_follow_the_rows_an_expert_gets():
    assert gmm.tile_rows_for(512, 128) == 16        # a decode step
    assert gmm.tile_rows_for(4096, 128) == 64       # a 512-token chunk
    assert gmm.tile_rows_for(10 ** 6, 8) == 128
