"""Async PS communicator — decouple trainer compute from PS RPCs.

Reference parity: fluid/distributed/service/communicator.h:197
(AsyncCommunicator: background send/recv threads + bounded queues so the
trainer never blocks on the wire) and communicator.cc's batch-merged
push. TPU-native shape: the overlap that matters is host<->device as
much as host<->PS, so the communicator pairs

  * a PULL prefetcher: `pull_ahead(feed)` walks the id stream in a
    worker thread and keeps up to `depth` pulled (and optionally
    device-put) embedding batches ready, and
  * a PUSH drainer: `push_async(ids, grads, lr)` enqueues the (possibly
    still in-flight jax array) gradient; the worker forces the readback
    and sends — so the device never waits for the push wire time, and
    the readback of step t overlaps the compute of step t+1.

Staleness contract matches the reference's async mode: a pull issued at
step t+depth may miss pushes still queued from steps < t; `flush()` is
the communicator's barrier (reference Communicator::Clean + the sync-
mode fences).
"""
import queue
import threading
import time

import numpy as np

__all__ = ['AsyncCommunicator']


class _Stop:
    pass


class AsyncCommunicator:
    def __init__(self, client, table_id, dim, depth=2, device_put=None):
        """client: PsClient (thread-safe). depth: max in-flight pulled
        batches / unsent pushes. device_put: optional fn(np_rows) ->
        device array run inside the prefetch thread, so H2D upload of
        batch t+1 overlaps compute of batch t."""
        self.client = client
        self.table_id = int(table_id)
        self.dim = int(dim)
        self.depth = int(depth)
        self._device_put = device_put
        self._push_q = queue.Queue(self.depth)
        self._push_err = None
        self._pushed = threading.Event()
        self._push_thread = threading.Thread(target=self._push_loop,
                                             daemon=True)
        self._push_thread.start()
        self._pull_thread = None
        self._cur_pull = None     # (stop_event, thread, queue) of the
                                  # ACTIVE pull — cancellation is
                                  # per-generation, so a stale abandoned
                                  # iterator can't kill a newer pull

    # -- pull side -----------------------------------------------------------
    def pull_ahead(self, id_batches):
        """Start prefetching: `id_batches` is an iterable of int64 id
        arrays. Returns an iterator of (ids, rows) in order, at most
        `depth` batches ahead of the consumer."""
        if self._pull_thread is not None:
            raise RuntimeError("pull_ahead already active; exhaust, "
                               "close() or cancel_pull() the previous "
                               "iterator first")
        out = queue.Queue(self.depth)     # per-pull: never shared across
        stop = threading.Event()          # generations

        def _put(item):
            """Bounded put that gives up when the consumer cancelled —
            an abandoned iterator must not wedge this thread forever on
            a full queue."""
            while not stop.is_set():
                try:
                    out.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def loop():
            try:
                for ids in id_batches:
                    if stop.is_set():
                        return
                    # shape is the client's contract (PsClient.pull
                    # flattens; a chunk adapter may keep [K, rows])
                    ids = np.ascontiguousarray(ids, np.int64)
                    rows = self.client.pull(self.table_id, ids, self.dim)
                    if self._device_put is not None:
                        rows = self._device_put(rows)
                    if not _put((ids, rows)):
                        return
            except Exception as e:           # surfaced at the consumer
                _put(e)
            finally:
                _put(_Stop)

        t = threading.Thread(target=loop, daemon=True)
        self._pull_thread = t
        self._cur_pull = (stop, t, out)
        t.start()

        def results():
            try:
                while True:
                    item = out.get()
                    if item is _Stop:
                        return
                    if isinstance(item, Exception):
                        raise item
                    yield item
            finally:
                # normal exhaustion, an error, or an abandoned iterator
                # (GeneratorExit lands here) all release THIS pull's
                # producer — a newer generation is untouched
                self._cancel_generation(stop, t, out)

        return results()

    def _cancel_generation(self, stop, t, out):
        """Stop one pull generation's producer and release its slot
        (only if it still owns the slot). Idempotent. Bounded wait: a
        producer stuck in an in-flight client.pull() RPC (dead server,
        partition) can't be interrupted — after the deadline the daemon
        thread is abandoned (it re-checks `stop` before any further
        put), matching the push side's join(timeout=10)."""
        stop.set()
        deadline = time.time() + 10.0
        while t.is_alive() and time.time() < deadline:
            try:                     # unblock a producer stuck on put()
                out.get_nowait()
            except queue.Empty:
                pass
            t.join(timeout=0.05)
        if self._pull_thread is t:
            self._pull_thread = None
            self._cur_pull = None

    def cancel_pull(self):
        """Cancel the ACTIVE in-flight pull_ahead (if any) so a new one
        can start. Idempotent."""
        cur = self._cur_pull
        if cur is not None:
            self._cancel_generation(*cur)

    # -- push side -----------------------------------------------------------
    def push_async(self, ids, grads, lr):
        """Queue a gradient push and return immediately. `grads` may be
        a live jax array — the worker thread forces it, so device->host
        readback overlaps the caller's next dispatch. Raises any error
        from a PREVIOUS push (at-most-depth delayed, never silent)."""
        if self._push_err is not None:
            err, self._push_err = self._push_err, None
            raise err
        self._push_q.put((ids, grads, float(lr)))

    def _push_loop(self):
        while True:
            item = self._push_q.get()
            if item is _Stop:
                return
            ids, grads, lr = item
            try:
                g = np.asarray(grads)        # forces device readback
                self.client.push(self.table_id, ids, g, lr)
            except Exception as e:           # noqa: BLE001
                self._push_err = e
                try:
                    from ..fleet.utils import log_util
                    log_util.log_json(
                        'ps_push_failed', level='error',
                        logger_name='ps', table=self.table_id,
                        rows=int(getattr(ids, 'size', 0)), error=repr(e))
                except Exception:
                    pass
            finally:
                self._push_q.task_done()

    def flush(self):
        """Barrier: wait until every queued push has landed on the
        servers (reference sync-mode fence). Re-raises a push error."""
        self._push_q.join()
        if self._push_err is not None:
            err, self._push_err = self._push_err, None
            raise err

    def stop(self):
        """Graceful close: cancel any in-flight prefetch, fence queued
        pushes (re-raising a queued push error AFTER the threads are
        released, so an error can't leave the communicator wedged)."""
        self.cancel_pull()
        err = None
        try:
            self.flush()
        except Exception as e:       # noqa: BLE001 — re-raised below
            err = e
        self._push_q.put(_Stop)
        self._push_thread.join(timeout=10)
        if err is not None:
            raise err

    close = stop

    def __enter__(self):
        return self

    def __exit__(self, *a):
        self.stop()
        return False
