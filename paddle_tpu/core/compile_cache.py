"""Persistent XLA compilation cache: where it lives, and its traffic.

One directory, placed from OUTSIDE the program: where
`JAX_COMPILATION_CACHE_DIR` is set JAX has already read it, and nothing
here sets another. Where it is not, the cache goes to one fixed path
inside the checkout (`<repo>/.jax_cache`, git-ignored) — fixed because
a directory that moves between runs never hits. `install()` runs at
`import paddle_tpu`, so the trainer, the server, `chip_smoke.py`, the
`bench.py` leg children and replica workers all share the one cache;
it touches only `jax.config` and initializes no backend. What gets
cached follows JAX's own thresholds (`JAX_PERSISTENT_CACHE_*`).

Traffic is surfaced as `ptpu_compile_cache_*` metrics beside the
executor's in-process fingerprint-cache counters
(STAT_executor_cache_hit/miss): at GPT scale a warm cache turns the
minutes-long first dispatch into a disk read, and the gauges make the
saving visible in StepTelemetry / bench records / health_dump. JAX
emits monitoring events for the cache
(`/jax/compilation_cache/compile_requests_use_cache`, `.../cache_hits`,
and the `.../compile_time_saved_sec` duration); there is no miss event,
so misses are derived as requests - hits.
"""
import os
import threading

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), '.jax_cache')

_lock = threading.Lock()
_installed = False


def _install_listeners():
    global _installed
    with _lock:
        if _installed:
            return
        _installed = True
    from jax import monitoring
    from . import monitor as _m

    def on_event(event, **kwargs):
        if event == '/jax/compilation_cache/compile_requests_use_cache':
            _m.counter('ptpu_compile_cache_requests_total',
                       help='XLA compiles that consulted the persistent '
                            'cache').inc(1)
        elif event == '/jax/compilation_cache/cache_hits':
            _m.counter('ptpu_compile_cache_hits_total',
                       help='persistent compilation cache hits').inc(1)

    def on_duration(event, duration, **kwargs):
        if event == '/jax/compilation_cache/compile_time_saved_sec':
            _m.counter('ptpu_compile_cache_seconds_saved_total',
                       help='compile seconds avoided via the persistent '
                            'cache').inc(max(float(duration), 0.0))

    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)


def install():
    """Called at `import paddle_tpu`: place the cache (module
    docstring) and install the metric listeners."""
    import jax
    if not os.environ.get('JAX_COMPILATION_CACHE_DIR'):
        jax.config.update('jax_compilation_cache_dir', DEFAULT_DIR)
    _install_listeners()


def cache_dir():
    """The directory JAX caches into (None = caching off)."""
    import jax
    return jax.config.jax_compilation_cache_dir


def snapshot():
    """JSON-ready cache-traffic view (StepTelemetry / bench /
    health_dump)."""
    from . import monitor as _m

    def total(name):
        m = _m.metrics().get(name)
        if m is None:
            return 0.0
        return sum(c.value() for c in m._series().values())
    requests = int(total('ptpu_compile_cache_requests_total'))
    hits = int(total('ptpu_compile_cache_hits_total'))
    d = cache_dir()
    return {
        'enabled': d is not None,
        'dir': d,
        'requests': requests,
        'hits': hits,
        'misses': max(requests - hits, 0),
        'seconds_saved': round(
            total('ptpu_compile_cache_seconds_saved_total'), 3),
    }
