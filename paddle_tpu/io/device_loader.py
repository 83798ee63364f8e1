"""DeviceLoader — background-thread device-side input prefetch.

Wraps any DataLoader/iterable of host batches and double/triple-buffers
them onto the mesh from a producer thread: batch t+1's H2D transfer
(`jax.device_put` with the engine's input sharding spec) overlaps step
t's compute, so the training loop never pays the transfer in the host
gap between dispatches. The companion of the engines' windowed dispatch
(core/async_step.py; docs/performance.md#async-dispatch).

Sharding: pass `engine=` (any of the three compiled engines — they
expose `input_sharding(index, ndim)`) so batches land pre-sharded in
the spec the compiled step expects (dp-sharded batch dim under
hybrid/pipeline, replicated under mp-only); or pass explicit
`specs=[PartitionSpec, ...]` + `mesh=`; or neither, and batches go to
the default device whole (the jit.TrainStep shape).

Staging ring: host batches are copied into a reusable ring of
depth+1 staging buffers before the device_put (pinned-host analogue —
steady-state prefetch allocates nothing on the staging side). The
transfer never aliases the ring: on the CPU backend (where device_put
can zero-copy host memory) the loader copies out of the slot
explicitly, and on accelerator backends — where the H2D put is the
copy but PJRT doesn't guarantee it completes before returning — the
ring blocks on a slot's previous transfer before overwriting it (free
in steady state, depth+1 batches later). Ring reuse can therefore
never mutate a batch already handed to a (donating) compiled step.

Gauges: ptpu_host_prefetch_depth, ptpu_host_prefetch_stalls_total
(consumer arrived before a batch was staged), and
ptpu_host_prefetch_h2d_bytes_total; per-instance `stats()` carries the
same counters plus ring reuse counts.
"""
import queue as _queue
import threading

import numpy as np

from ..core import async_step as _async
from ..profiler import RecordEvent
from ..core.tensor import Tensor


class DeviceLoader:
    """Iterate device-resident batches prefetched from `loader`.

    Each yielded item is a tuple of jax arrays (a non-tuple upstream
    batch yields a 1-tuple), already placed with the resolved sharding.
    Re-iterable: every `__iter__` starts a fresh producer thread over
    `iter(loader)`. `close()` stops an in-flight producer.
    """

    def __init__(self, loader, engine=None, mesh=None, specs=None,
                 depth=None):
        self.loader = loader
        self.engine = engine
        self.mesh = mesh if mesh is not None else (
            getattr(engine, 'mesh', None))
        self.specs = list(specs) if specs is not None else None
        if self.specs is not None and self.mesh is None:
            raise ValueError("DeviceLoader(specs=...) needs mesh= (or an "
                             "engine that carries one)")
        self.depth = _async.resolve_prefetch_depth(depth)
        self._ring = [None] * (self.depth + 1)   # slot -> [np buffers]
        self._ring_pending = [None] * (self.depth + 1)
        self._ring_i = 0
        self._stop = threading.Event()   # the CURRENT iteration's event
        self._producer = None            # the CURRENT producer thread
        self._spec_cache = {}            # (index, ndim) -> (sharding,
                                         #                   aliases)
        self._stats = {'batches': 0, 'stalls': 0, 'h2d_bytes': 0,
                       'ring_reuses': 0}
        self._publish_depth()
        _async.note_prefetch(loaders=1, depth=self.depth)

    # -- sharding resolution --------------------------------------------------
    def _sharding(self, index, ndim):
        """Resolved (sharding, backend_aliases) for batch position
        `index` — cached per (index, ndim): both are loader constants,
        and the prefetch hot path must not re-probe device sets per
        batch."""
        key = (index, ndim)
        cached = self._spec_cache.get(key)
        if cached is not None:
            return cached
        from jax.sharding import NamedSharding, PartitionSpec
        sh = None
        if self.specs is not None:
            if index >= len(self.specs):
                sh = NamedSharding(self.mesh, PartitionSpec())
            else:
                spec = self.specs[index]
                sh = spec if (isinstance(spec, NamedSharding)
                              or hasattr(spec, 'mesh')) \
                    else NamedSharding(self.mesh, spec)
        elif self.engine is not None and hasattr(self.engine,
                                                 'input_sharding'):
            sh = self.engine.input_sharding(index, ndim)
        cached = (sh, self._backend_aliases(sh))
        self._spec_cache[key] = cached
        return cached

    # -- staging + transfer ---------------------------------------------------
    @staticmethod
    def _host_arrays(batch):
        items = batch if isinstance(batch, (tuple, list)) else (batch,)
        out = []
        for b in items:
            if isinstance(b, Tensor):
                b = b.data
            out.append(np.asarray(b))
        return out

    def _stage(self, arrays):
        """Copy the batch into this slot's reusable staging buffers
        (allocated on first use / shape change only). Before reuse, the
        slot's PREVIOUS device arrays are blocked on: PJRT does not
        guarantee device_put's host-side read completes before it
        returns on accelerator backends, so overwriting the buffer
        could race an in-flight H2D. In steady state (depth+1 batches
        later) the transfer is long done and the block is free — and it
        runs on the producer thread, never the dispatch hot loop."""
        i = self._ring_i
        pending = self._ring_pending[i]
        if pending is not None:
            self._ring_pending[i] = None
            for a in pending:
                try:
                    a.block_until_ready()
                except AttributeError:
                    pass
        slot = self._ring[i]
        if slot is None or len(slot) != len(arrays) or any(
                buf.shape != a.shape or buf.dtype != a.dtype
                for buf, a in zip(slot, arrays)):
            slot = [np.empty(a.shape, a.dtype) for a in arrays]
            self._ring[i] = slot
        else:
            self._stats['ring_reuses'] += 1
            _async.note_prefetch(ring_reuses=1)
        for buf, a in zip(slot, arrays):
            np.copyto(buf, a)
        self._ring_i = (i + 1) % len(self._ring)
        return slot, i

    @staticmethod
    def _backend_aliases(sharding):
        """True when device_put may ALIAS a host numpy buffer instead of
        copying (the CPU backend: device memory IS host memory — same
        hazard the engines' `_place` copies around). A real accelerator
        copies on the H2D transfer, so the ring is reusable as-is."""
        try:
            import jax
            if sharding is not None:
                dev = next(iter(sharding.device_set))
                return getattr(dev, 'platform', 'cpu') == 'cpu'
            return jax.default_backend() == 'cpu'
        except Exception:
            return True

    def _transfer(self, staged, slot_idx=None):
        import jax
        out = []
        nbytes = 0
        for j, buf in enumerate(staged):
            sh, aliases = self._sharding(j, buf.ndim)
            # on an aliasing backend the put must not capture the ring
            # slot, or the next wrap would mutate a batch already handed
            # to a (donating) compiled step — copy out of the ring; on
            # TPU the H2D transfer itself is that copy. The CPU dryrun
            # thus pays a second memcpy per batch; deliberate: bypassing
            # the ring there would leave the staging path dead code on
            # the only CI backend, losing its content-verified coverage.
            src = buf.copy() if aliases else buf
            out.append(jax.device_put(src, sh) if sh is not None
                       else jax.device_put(src))
            nbytes += buf.nbytes
        self._stats['h2d_bytes'] += nbytes
        self._stats['batches'] += 1
        if slot_idx is not None:
            # remember what was put from this slot so _stage can block
            # on the transfer before the ring wraps onto it
            self._ring_pending[slot_idx] = tuple(out)
        _async.note_prefetch(batches=1, h2d_bytes=nbytes)
        self._h2d_counter().inc(nbytes)
        return tuple(out)

    # -- iteration ------------------------------------------------------------
    def __iter__(self):
        # one stop event PER iteration: starting a new iteration (or
        # close()) signals the previous producer, which otherwise kept
        # running after an early consumer break and raced the next
        # iteration's producer on the shared staging ring — and JOIN it
        # (it notices the signal within one 0.1s put timeout), because
        # a signal alone leaves it mid-_stage on the shared ring
        self._stop.set()
        prev = getattr(self, '_producer', None)
        if prev is not None and prev.is_alive():
            prev.join(timeout=5)
        stop = self._stop = threading.Event()
        q = _queue.Queue(maxsize=self.depth)
        sentinel = object()
        err = []

        def put_stop_aware(item):
            """timeout-put so a producer blocked on a full queue still
            notices the stop signal (a plain put would pin the thread —
            and the ring — forever after the consumer walks away); the
            sentinel uses the same protocol so a full queue can't drop
            it (the consumer would block forever)."""
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return
                except _queue.Full:
                    continue

        def producer():
            try:
                for batch in self.loader:
                    if stop.is_set():
                        return
                    staged, slot_idx = self._stage(
                        self._host_arrays(batch))
                    with RecordEvent('loader::stage', event_type='loader',
                                     bytes=sum(b.nbytes for b in staged)):
                        item = self._transfer(staged, slot_idx)
                    put_stop_aware(item)
            except Exception as e:          # surfaced on the consumer side
                err.append(e)
            finally:
                put_stop_aware(sentinel)
        t = self._producer = threading.Thread(
            target=producer, daemon=True, name='ptpu-device-prefetch')
        t.start()
        import time as _time
        stall_counter = self._stall_counter()
        first_get = True
        try:
            while True:
                # the first get of an iteration always finds an empty
                # queue (the producer hasn't staged batch 0 yet) —
                # startup latency, not a prefetch stall
                stalled = q.empty() and t.is_alive() and not first_get
                first_get = False
                t0 = _time.perf_counter()
                # timeout-get: close() from another thread (or a dead
                # producer whose sentinel was suppressed by the stop
                # signal) must end the iteration, not deadlock a
                # consumer blocked in a plain get()
                with RecordEvent('loader::wait', event_type='loader'):
                    while True:
                        try:
                            item = q.get(timeout=0.2)
                            break
                        except _queue.Empty:
                            if stop.is_set() or not t.is_alive():
                                item = sentinel
                                break
                # queue wait = the transfer is in flight on the producer
                # thread, not idle host work: attribute it as blocked
                # time for the next dispatch's host-gap sample (the
                # stall counters below keep it visible on their own axis)
                _async.note_external_blocked(_time.perf_counter() - t0)
                if item is sentinel:
                    break
                if stalled:
                    # the consumer outran the prefetch of a REAL batch —
                    # the signal host_bound diagnosis needs (loader too
                    # slow or depth too small). Counted after the get so
                    # the end-of-stream sentinel wait isn't a phantom
                    # stall.
                    self._stats['stalls'] += 1
                    _async.note_prefetch(stalls=1)
                    stall_counter.inc(1)
                yield item
            if err:
                raise err[0]
        finally:
            # consumer done or walked away: stop the producer and let it
            # drain out of any pending put before the ring is reused
            stop.set()
            while not q.empty():
                try:
                    q.get_nowait()
                except _queue.Empty:
                    break
            t.join(timeout=5)

    def __len__(self):
        return len(self.loader)

    def close(self):
        self._stop.set()

    def stats(self):
        return dict(self._stats, depth=self.depth)

    # -- metrics --------------------------------------------------------------
    def _publish_depth(self):
        from ..core.monitor import gauge
        gauge('ptpu_host_prefetch_depth',
              help='DeviceLoader prefetch ring depth').set(self.depth)

    @staticmethod
    def _stall_counter():
        from ..core.monitor import counter
        return counter('ptpu_host_prefetch_stalls_total',
                       help='consumer waits on an empty prefetch queue')

    @staticmethod
    def _h2d_counter():
        from ..core.monitor import counter
        return counter('ptpu_host_prefetch_h2d_bytes_total',
                       help='bytes staged host-to-device by DeviceLoader')
