"""A leading page run that several decode rows map, read ONCE for all of
them (`shared=` of the paged kernel), in Pallas interpret mode on the
CPU: the run's pass, each row's own pages after it and their merge
against the unsplit kernel and the dense route — plain body and kv
groups (32 query / 8 kv heads of 64, as the gated-conv server cell),
runs of 0, 1 and many pages, member and non-member rows in one call, a
member one token past the run, idle rows —; a member's own table is not
read over the run; and every call the shared pass does not take reads
as without it."""
import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.ops.pallas import paged_attention as pa

PS, P, N = 8, 14, 160


def shared_case(B, Hq, Hk, D, run, seed=0):
    """Rows 0 .. B-3 map the run's `run` pages first (row 0 one token
    past it, the last of them idle), row B-2 reaches no further than
    the run would (so it is no member), row B-1 maps pages of its own.
    -> (args, shared, member rows, live rows)."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((B, 1, Hq * D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((N, PS, Hk * D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((N, PS, Hk * D)), jnp.float32)
    free = list(rng.permutation(np.arange(P, N - 1)))   # N-1: no row's
    seq = rng.integers(run * PS + 1, P * PS, B).astype(np.int32)
    seq[0] = run * PS + 1
    seq[B - 2] = max(run * PS, 1)
    ql = np.ones((B,), np.int32)
    ql[B - 3] = 0
    members = np.arange(B) < B - 1
    pt = np.zeros((B, P), np.int32)
    for b in range(B):
        for i in range(-(-seq[b] // PS)):
            pt[b, i] = i if members[b] and i < run else free.pop()
    run_pt = np.zeros((P,), np.int32)
    run_pt[:run] = np.arange(run)
    args = (q, k, v, jnp.asarray(pt), jnp.asarray(seq), jnp.asarray(ql))
    shared = (jnp.asarray(run_pt), jnp.int32(run * PS), jnp.asarray(members))
    return args, shared, ql > 0


BODIES = {'plain': (4, 4, 16), 'groups': (32, 8, 64)}


@pytest.mark.parametrize('run', [0, 1, 11])     # 11 pages: two waves
@pytest.mark.parametrize('body', sorted(BODIES))
def test_the_run_read_once_is_the_unsplit_attention(body, run):
    Hq, Hk, D = BODIES[body]
    args, shared, live = shared_case(6, Hq, Hk, D, run, seed=run)
    kw = dict(num_heads=Hq, head_dim=D, num_kv_heads=Hk)
    got = np.asarray(pa.ragged_paged_attention_pallas(
        *args, shared=shared, interpret=True, **kw))
    unsplit = np.asarray(pa.ragged_paged_attention_pallas(
        *args, interpret=True, **kw))
    dense = np.asarray(pa.ragged_paged_attention_dense(*args, **kw))
    np.testing.assert_allclose(got[live], unsplit[live], atol=2e-6)
    np.testing.assert_allclose(got[live], dense[live], atol=2e-6)
    # an idle row writes zeros, as without
    assert not got[~live].any()


@pytest.mark.parametrize('body', sorted(BODIES))
def test_a_member_does_not_read_its_own_table_over_the_run(body):
    """The members' tables point at NaN pages where the run lies: only
    the run's own list may be read there (the answer is the dense
    route's over the real pages)."""
    Hq, Hk, D = BODIES[body]
    args, shared, live = shared_case(6, Hq, Hk, D, 5, seed=3)
    q, k, v, pt, seq, ql = args
    kw = dict(num_heads=Hq, head_dim=D, num_kv_heads=Hk)
    want = np.asarray(pa.ragged_paged_attention_dense(*args, **kw))
    poison = N - 1                      # a page no row maps
    k, v = k.at[poison].set(jnp.nan), v.at[poison].set(jnp.nan)
    # (row B-2 is flagged but its context ends at the run: no member,
    # it reads its own table, which stays as it is — a NaN page copied
    # for one row would linger in the slots the next row's partial
    # wave leaves)
    members = np.asarray(shared[2]) & (np.asarray(seq) > 5 * PS)
    bad = np.where(members[:, None] & (np.arange(P) < 5)[None, :],
                   poison, np.asarray(pt))
    got = np.asarray(pa.ragged_paged_attention_pallas(
        q, k, v, jnp.asarray(bad), seq, ql, shared=shared, interpret=True,
        **kw))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got[live], want[live], atol=2e-6)


@pytest.mark.parametrize('what', ['window', 'chunk', 'diff', 'int8'])
def test_calls_the_shared_pass_does_not_take_read_as_without(what):
    """A window call, a chunk, a differential call and an int8 pool
    ignore `shared`: the same output, bit for bit."""
    B, T = (6, 3) if what == 'chunk' else (6, 1)
    args, shared, _ = shared_case(B, 4, 4, 16, 4, seed=5)
    q, k, v, pt, seq, ql = args
    kw = dict(num_heads=4, head_dim=16, interpret=True)
    if what == 'window':
        kw['window'] = 20
    elif what == 'chunk':
        q = jnp.tile(q, (1, T, 1))
        ql = jnp.minimum(ql * T, seq)
    elif what == 'diff':
        kw.update(num_kv_heads=4, diff=2)
    else:
        k8, ks = pa.quantize_kv_rows(k.reshape(1, -1, 64), 4)
        v8, vs = pa.quantize_kv_rows(v.reshape(1, -1, 64), 4)
        k, v = k8.reshape(N, PS, 64), v8.reshape(N, PS, 64)
        kw.update(k_scales=ks.reshape(N, PS, 4), v_scales=vs.reshape(N, PS, 4))
    plain = np.asarray(pa.ragged_paged_attention_pallas(
        q, k, v, pt, seq, ql, **kw))
    got = np.asarray(pa.ragged_paged_attention_pallas(
        q, k, v, pt, seq, ql, shared=shared, **kw))
    assert np.array_equal(got, plain, equal_nan=True)
    assert not pa.shared_pass(T, 4, 4, kw.get('k_scales'), kw.get('window'),
                              kw.get('diff', 1))


def test_which_calls_take_a_run():
    # decode rows of the plain body and of kv groups that tile 8 rows
    assert pa.shared_pass(1, 16)
    assert pa.shared_pass(1, 32, 8) and pa.shared_pass(1, 32, 4)
    assert pa.shared_pass(1, 64, 4)                 # 16 a kv head
    assert not pa.shared_pass(1, 12, 4)             # 3 a kv head
    assert not pa.shared_pass(4, 16)                # a verify row
    assert not pa.shared_pass(1, 16, latent=(512, 64))
    assert not pa.shared_pass(1, 256)               # not the batched layout
