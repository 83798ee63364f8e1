"""Differential attention on the ONE paged kernel (`diff=2`: pairs of
key sub-heads on one value block of twice the width) against the dense
form written out head by head, with and without a window, in the
batched (decode) and the chunk product, kernel (interpret mode) and
dense fallback alike."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import paged_attention as pa

HQ, HK, D, PS, P = 8, 4, 16, 4, 6      # sub-heads of 16; pairs: 4 and 2


def _dense(q, k_pages, v_pages, pt, seq_lens, q_lens, window):
    """out[b, t, (p, s)] = softmax(q_{p,s} . k_{g,s}) [v_{g,1} | v_{g,2}],
    g = p // (query pairs / kv pairs), for the live queries."""
    q, kp, vp = (np.asarray(a, np.float64) for a in (q, k_pages, v_pages))
    B, T, _ = q.shape
    out = np.zeros((B, T, HQ * 2 * D))
    per = (HQ // 2) // (HK // 2)
    for b in range(B):
        k = kp[pt[b]].reshape(-1, HK * D)[:seq_lens[b]]
        v = vp[pt[b]].reshape(-1, HK * D)[:seq_lens[b]]
        for t in range(q_lens[b]):
            pos = seq_lens[b] - q_lens[b] + t
            lo = 0 if window is None else max(0, pos - window + 1)
            for p in range(HQ // 2):
                g = p // per
                vg = v[lo:pos + 1, 2 * g * D:(2 * g + 2) * D]
                for s in range(2):
                    h = 2 * p + s
                    sc = k[lo:pos + 1, (2 * g + s) * D:(2 * g + s + 1) * D] \
                        @ q[b, t, h * D:(h + 1) * D] / np.sqrt(D)
                    pr = np.exp(sc - sc.max())
                    out[b, t, h * 2 * D:(h + 1) * 2 * D] = pr / pr.sum() @ vg
    return out


@pytest.mark.parametrize('route', ['kernel', 'dense'])
@pytest.mark.parametrize('window', [None, 5])
@pytest.mark.parametrize('T', [1, 3, 24])   # 24 x 8 rows: the chunk product
def test_differential_paged_attention(T, window, route):
    rng = np.random.default_rng(T)
    B, pages = 3, 24
    seq = np.array([max(T, 17), max(T, 9), 1])
    ql = np.array([T, min(T, 2), 0])        # full, partial, idle
    pt = np.stack([rng.permutation(pages)[:P] for _ in range(B)])
    q = rng.standard_normal((B, T, HQ * D)).astype(np.float32)
    kp = rng.standard_normal((pages, PS, HK * D)).astype(np.float32)
    vp = rng.standard_normal((pages, PS, HK * D)).astype(np.float32)
    fn = pa.ragged_paged_attention_pallas if route == 'kernel' \
        else pa.ragged_paged_attention_dense
    kw = {'interpret': True} if route == 'kernel' else {}
    got = np.asarray(fn(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(pt, jnp.int32), jnp.asarray(seq, jnp.int32),
        jnp.asarray(ql, jnp.int32), num_heads=HQ, head_dim=D,
        num_kv_heads=HK, window=window, diff=2, **kw))
    assert got.shape == (B, T, HQ * 2 * D)
    want = _dense(q, kp, vp, pt, seq, ql, window)
    live = (np.arange(T)[None, :] < ql[:, None])[..., None]
    np.testing.assert_allclose(np.where(live, got, 0), want, rtol=2e-4,
                               atol=2e-4)


def test_sub_heads_must_pair_up_and_int8_pages_are_refused():
    q = jnp.zeros((1, 1, 6 * D))
    pages = jnp.zeros((4, PS, 3 * D))
    args = (jnp.zeros((1, P), jnp.int32), jnp.ones((1,), jnp.int32),
            jnp.ones((1,), jnp.int32))
    with pytest.raises(ValueError, match='pair up'):
        pa.ragged_paged_attention_pallas(
            q, pages, pages, *args, num_heads=6, head_dim=D,
            num_kv_heads=3, diff=2, interpret=True)
    scales = jnp.ones((4, PS, 4))
    with pytest.raises(NotImplementedError, match='value sharing'):
        pa.ragged_paged_attention_pallas(
            jnp.zeros((1, 1, HQ * D)), jnp.zeros((4, PS, HK * D), jnp.int8),
            jnp.zeros((4, PS, HK * D), jnp.int8), *args, num_heads=HQ,
            head_dim=D, num_kv_heads=HQ, k_scales=scales, v_scales=scales,
            diff=2, interpret=True)
