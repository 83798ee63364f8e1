"""The selective-scan kernel (ops/pallas/selective_scan.py, interpret
mode here) and the layer's XLA parts (ops/ssm.py) against plain forms:
T = 1 decode rows and T = C chunk rows with ragged q_lens, fresh rows,
idle rows on the spare slot, and a state carried from chunk to chunk."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import ssm
from paddle_tpu.ops.pallas import selective_scan as kernel

DN, N = 256, 16


def _inputs(R, T, slots_total, seed=0):
    ks = jax.random.split(jax.random.key(seed), 7)
    return dict(
        x=jax.random.normal(ks[0], (R, T, DN)),
        dt=jax.nn.softplus(jax.random.normal(ks[1], (R, T, DN)) - 3.0),
        B=jax.random.normal(ks[2], (R, T, N)),
        C=jax.random.normal(ks[3], (R, T, N)),
        A=-jnp.exp(jax.random.normal(ks[4], (N, DN))),
        D=jax.random.normal(ks[5], (DN,)),
        state=jax.random.normal(ks[6], (slots_total, N, DN)))


def _naive(a, slots, q_lens, fresh):
    """One row and one token at a time, in numpy."""
    a = {k: np.asarray(v, np.float64) for k, v in a.items()}
    R, T, _ = a['x'].shape
    y = np.zeros((R, T, DN))
    state = a['state'].copy()
    for r in range(R):
        s = np.zeros((N, DN)) if fresh[r] else a['state'][slots[r]].copy()
        for t in range(q_lens[r]):
            s = np.exp(a['dt'][r, t][None] * a['A']) * s \
                + (a['dt'][r, t] * a['x'][r, t])[None] * a['B'][r, t][:, None]
            y[r, t] = (s * a['C'][r, t][:, None]).sum(0) \
                + a['D'] * a['x'][r, t]
        if q_lens[r]:
            state[slots[r]] = s
    return y, state


CASES = {
    # R, T, slots (the last is the spare), q_lens, fresh
    'decode rows, one idle, one fresh': (4, 1, [0, 4, 2, 3], [1, 0, 1, 1],
                                         [0, 0, 1, 0]),
    'chunk rows, ragged, one idle': (3, 8, [2, 0, 4], [8, 3, 0], [1, 0, 0]),
    'one full chunk row': (1, 8, [1], [8], [0]),
}


@pytest.mark.parametrize('route', ['kernel', 'lax.scan'])
@pytest.mark.parametrize('case', sorted(CASES))
def test_scan_against_a_loop_over_rows_and_tokens(case, route):
    R, T, slots, q_lens, fresh = CASES[case]
    a = _inputs(R, T, 5)
    fn = (lambda *args: kernel.selective_scan_pallas(*args, interpret=True)) \
        if route == 'kernel' else ssm.selective_scan_ref
    y, state = fn(a['x'], a['dt'], a['B'], a['C'], a['A'], a['D'],
                  a['state'], jnp.asarray(slots, jnp.int32),
                  jnp.asarray(q_lens, jnp.int32), jnp.asarray(fresh, bool))
    want_y, want_state = _naive(a, slots, q_lens, fresh)
    np.testing.assert_allclose(np.asarray(y), want_y, rtol=2e-5, atol=2e-5)
    # every slot but the spare: a live row's is updated, an idle row's
    # and an unnamed one are as they were
    np.testing.assert_allclose(np.asarray(state)[:4], want_state[:4],
                               rtol=2e-5, atol=2e-5)


def test_the_kernel_carries_state_from_chunk_to_chunk():
    """Two chunks of 4 on one slot give what one chunk of 8 gives."""
    a = _inputs(1, 8, 3, seed=1)
    args = lambda lo, hi, state: (
        a['x'][:, lo:hi], a['dt'][:, lo:hi], a['B'][:, lo:hi],
        a['C'][:, lo:hi], a['A'], a['D'], state, jnp.asarray([1]),
        jnp.asarray([hi - lo]))
    whole, s_whole = kernel.selective_scan_pallas(
        *args(0, 8, a['state']), jnp.asarray([True]), interpret=True)
    first, s = kernel.selective_scan_pallas(
        *args(0, 4, a['state']), jnp.asarray([True]), interpret=True)
    second, s = kernel.selective_scan_pallas(
        *args(4, 8, s), jnp.asarray([False]), interpret=True)
    np.testing.assert_allclose(np.concatenate([first, second], 1), whole,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(s[1], s_whole[1], rtol=1e-5, atol=1e-5)


def test_channels_must_tile_the_lanes():
    a = _inputs(1, 1, 2)
    with pytest.raises(ValueError, match='lanes'):
        kernel.selective_scan_pallas(
            a['x'][..., :100], a['dt'][..., :100], a['B'], a['C'],
            a['A'][:, :100], a['D'][:100], a['state'][..., :100],
            jnp.asarray([0]), jnp.asarray([1]), jnp.asarray([True]))


def test_the_convolution_continues_from_each_rows_tail():
    """Chunks of a sequence, each continued from the tail the one
    before left, give the convolution of the whole sequence; a padded
    chunk leaves the tail at its last LIVE tokens; an idle row leaves
    its slot as it was; a fresh row ignores what its slot held."""
    K, L = 4, 11
    ks = jax.random.split(jax.random.key(2), 3)
    x = jax.random.normal(ks[0], (1, L, DN))
    w, b = jax.random.normal(ks[1], (K, DN)), jax.random.normal(ks[2], (DN,))
    pad = jnp.concatenate([jnp.zeros((1, K - 1, DN)), x], 1)
    conv = sum(pad[:, k:k + L] * w[k] for k in range(K)) + b
    want = np.asarray(conv * jax.nn.sigmoid(conv))
    tails = jnp.full((3, (K - 1) * DN), 7.0)        # stale content
    got = []
    for lo, hi, fresh in ((0, 4, True), (4, 8, False), (8, 11, False)):
        chunk = jnp.zeros((2, 4, DN)).at[0, :hi - lo].set(x[0, lo:hi])
        out, tails = ssm.causal_conv(
            chunk, tails, w, b, jnp.asarray([1, 2]),
            jnp.asarray([hi - lo, 0]), jnp.asarray([fresh, False]))
        got.append(np.asarray(out)[0, :hi - lo])
    np.testing.assert_allclose(np.concatenate(got), want[0], rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(tails)[1].reshape(K - 1, DN),
                               np.asarray(x)[0, -(K - 1):], rtol=1e-6)
    assert (np.asarray(tails)[0] == 7.0).all()      # nobody's slot
