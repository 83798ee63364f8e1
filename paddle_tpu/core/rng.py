"""Global RNG state.

Reference parity: paddle/fluid/framework/generator.h (DefaultCPUGenerator /
GetDefaultCUDAGenerator:118-126) keeps per-device seeded generators fanned out by
`paddle.seed`. The TPU-native design keeps ONE functional `jax.random` key plus a
monotonically increasing fold counter: every draw folds the counter into the base
key, so draws are reproducible given the seed yet distinct per call. The counter
is a Python int, so it is static under `jax.jit` tracing — a traced function that
draws K times always folds 0..K-1 relative to the key active at trace time, which
is exactly the semantics needed for functional train steps.

`rng_guard` temporarily swaps the base key — used by the functional bridge
(paddle_tpu.jit) to thread an explicit per-step key, and by the fleet RNG-state
tracker (reference: fleet/meta_parallel/parallel_layers/random.py:24) for
TP-consistent dropout.
"""
import contextlib
import jax


class _GeneratorState:
    """Lazy: the jax key materializes on first draw, NOT at import —
    importing paddle_tpu must not initialize the device backend (a chip
    belongs to one process: launcher / utility processes share hosts
    with the trainer and must not take it)."""

    def __init__(self, seed=0):
        self._seed = seed
        self._key = None
        self.counter = 0

    @property
    def key(self):
        if self._key is None:
            self._key = jax.random.key(self._seed)
        return self._key

    @key.setter
    def key(self, k):
        self._key = k

    def next_key(self):
        k = jax.random.fold_in(self.key, self.counter)
        self.counter += 1
        return k


_state = _GeneratorState(seed=0)


def seed(s):
    """Set the global RNG seed (parity: paddle.seed)."""
    global _state
    _state = _GeneratorState(int(s))
    return _state


def get_rng_state():
    return (_state.key, _state.counter)


def set_rng_state(state):
    global _state
    key, counter = state
    _state = _GeneratorState(0)
    _state.key = key
    _state.counter = counter


def next_key():
    """Draw a fresh PRNG key from the global stream."""
    return _state.next_key()


@contextlib.contextmanager
def rng_guard(key):
    """Temporarily replace the global key (e.g. with a traced key under jit)."""
    global _state
    saved = _state
    _state = _GeneratorState(0)
    _state.key = key
    try:
        yield
    finally:
        _state = saved
