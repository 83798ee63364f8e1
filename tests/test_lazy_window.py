"""Lazy op-fusion window (VERDICT r3 weak #6: eager per-op dispatch pays
one launch per op; the window batches N eager ops into one XLA dispatch —
the core.ops.* fast-path analogue)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import lazy


def test_fuses_to_single_dispatch_and_matches_eager():
    x = paddle.to_tensor(np.arange(12, dtype='float32').reshape(3, 4))
    w = paddle.to_tensor(np.ones((4, 2), 'float32'))

    # eager reference
    ref = paddle.nn.functional.relu(
        paddle.matmul(x, w) + 1.0) * 2.0

    calls = {'n': 0}
    orig_jit = jax.jit

    def counting_jit(fn, *a, **k):
        wrapped = orig_jit(fn, *a, **k)

        def run(*args, **kw):
            calls['n'] += 1
            return wrapped(*args, **kw)
        return run

    lazy._COMPILE_CACHE.clear()
    jax.jit = counting_jit
    try:
        with paddle.lazy_guard():
            y = paddle.matmul(x, w)
            y = y + 1.0
            y = paddle.nn.functional.relu(y)
            y = y * 2.0
            # nothing executed yet: placeholder data
            assert getattr(y, '_lazy', False)
        out = np.asarray(y.data)
    finally:
        jax.jit = orig_jit
    np.testing.assert_allclose(out, np.asarray(ref.data), rtol=1e-6)
    assert calls['n'] == 1          # the whole window = ONE dispatch


def test_materialization_inside_window():
    with paddle.lazy_guard():
        a = paddle.to_tensor(np.ones((2, 2), 'float32'))
        b = a + 3.0
        v = float(b.sum())          # triggers a flush mid-window
        assert v == 16.0
        c = b * 2.0                 # window continues recording
    np.testing.assert_allclose(np.asarray(c.data), np.full((2, 2), 8.0))


def test_structural_cache_reuses_compile():
    lazy._COMPILE_CACHE.clear()

    def run(scale):
        with paddle.lazy_guard():
            t = paddle.to_tensor(np.full((2, 3), scale, 'float32'))
            u = (t * 2.0) + 1.0
        return np.asarray(u.data)

    np.testing.assert_allclose(run(1.0), np.full((2, 3), 3.0))
    n_after_first = len(lazy._COMPILE_CACHE)
    np.testing.assert_allclose(run(5.0), np.full((2, 3), 11.0))
    assert len(lazy._COMPILE_CACHE) == n_after_first   # same program


def test_window_is_no_grad():
    x = paddle.to_tensor(np.ones((2,), 'float32'))
    x.stop_gradient = False
    with paddle.lazy_guard():
        y = x * 2.0
    assert y.stop_gradient            # no tape inside the window


def test_defaults_distinguish_cache_entries():
    """Ops baking attributes as default args must NOT share a compiled
    program across different attribute values."""
    from paddle_tpu.ops import contrib as C
    lazy._COMPILE_CACHE.clear()
    ids = paddle.to_tensor(np.arange(8, dtype='int64'))
    with paddle.lazy_guard():
        a = C.hash_op(ids, num_hash=2, mod_by=97)
    a_np = np.asarray(a.data)
    with paddle.lazy_guard():
        b = C.hash_op(ids, num_hash=2, mod_by=13)
    b_np = np.asarray(b.data)
    assert (a_np < 97).all() and (b_np < 13).all()
    assert not np.array_equal(a_np, b_np)


def test_bool_inside_window_materializes():
    with paddle.lazy_guard():
        x = paddle.to_tensor(np.array([-1.0, -2.0], 'float32'))
        cond = (x.sum() > 0)
        assert bool(cond) is False     # flushes; no placeholder truthiness
