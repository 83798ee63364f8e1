"""Async step pipeline — windowed dispatch + host-gap observability.

The training loops were host-synchronous: one `device_put` of the batch,
one host-computed LR scalar, and one blocking loss fetch per step, so the
accelerator idled in the host gap between dispatches. This module holds
the pieces every compiled engine shares to close that seam (the
host↔device twin of the ISSUE-10 comm/compute overlap):

  * `AsyncResult` — what `engine.train_step(...)` returns: the
    device-resident fp32 loss (and, when present, the found-inf flag and
    numerics taps) with NO host fetch. Deferred per-step work — taps
    processing, GradScaler found-inf accounting — runs at `wait()`, the
    window-drain point, never in the dispatch hot path.
  * `DispatchWindow` — a bounded in-flight queue (`PTPU_DISPATCH_WINDOW`,
    default 2): the host runs ahead by at most k dispatched steps; the
    (k+1)-th dispatch drains the oldest, which in steady state is
    already done on device. `flush()` drains everything — the engines
    call it from `state_dict`/`sync_model` so checkpoints always see
    every dispatched step applied.
  * `HostGapMonitor` — per-step dispatch/ready timestamps (surfaced as
    `step::dispatch` spans through the PR-1 profiler) yielding the
    `ptpu_host_gap_seconds` / `ptpu_host_dispatch_depth` gauges and a
    `host_bound_fraction` (mean host gap / mean step interval) so a
    bench round can tell compute-bound from host-bound.

fp32 invariant: the windowed loop dispatches the SAME executable with
the same key/lr/batch sequence as the synchronous loop, so the loss
sequence is bit-identical — the window changes when the host looks, not
what the device computes.

Knobs (docs/performance.md#async-dispatch):
  PTPU_DISPATCH_WINDOW  max in-flight dispatched steps (default 2)
  PTPU_DEVICE_PREFETCH  DeviceLoader prefetch depth (default 2)
  PTPU_DEVICE_LR        opt-in on-device LR schedules (default off)
"""
import collections
import os
import threading
import time

from ..profiler import RecordEvent


DEFAULT_DISPATCH_WINDOW = 2
DEFAULT_PREFETCH_DEPTH = 2


def _env_int(name, default):
    v = os.environ.get(name)
    if v is None or v == '':
        return default
    try:
        return int(v)
    except ValueError:
        return default


def resolve_dispatch_window(window=None):
    """In-flight dispatch window: kwarg -> PTPU_DISPATCH_WINDOW -> 2.
    Clamped to >= 1 (window 1 == drain every step == the synchronous
    discipline with the fetch still deferred to the drain point)."""
    if window is None:
        window = _env_int('PTPU_DISPATCH_WINDOW', DEFAULT_DISPATCH_WINDOW)
    return max(int(window), 1)


def resolve_prefetch_depth(depth=None):
    """DeviceLoader double/triple-buffer depth: kwarg ->
    PTPU_DEVICE_PREFETCH -> 2. Clamped to >= 1."""
    if depth is None:
        depth = _env_int('PTPU_DEVICE_PREFETCH', DEFAULT_PREFETCH_DEPTH)
    return max(int(depth), 1)


def resolve_device_lr(flag=None):
    """On-device LR schedule knob: kwarg -> PTPU_DEVICE_LR -> False.

    Opt-in: the device step counter advances once per compiled step, so
    it only mirrors the host scheduler when the training loop drives
    `scheduler.step()` once per train step (the standard GPT loop) —
    epoch-driven schedules (hapi's LRSchedulerCallback default) must
    keep the host feed."""
    if flag is not None:
        return bool(flag)
    v = os.environ.get('PTPU_DEVICE_LR')
    if v is None or v == '':
        return False
    return v.lower() in ('1', 'true', 'yes')


# ---------------------------------------------------------------------------
# host-gap observability
# ---------------------------------------------------------------------------
_monitors = {}          # site -> HostGapMonitor (latest per site wins)
_monitors_lock = threading.Lock()

# blocked-on-progress time reported by code that doesn't know which
# engine dispatches next on this thread (DeviceLoader's consumer-side
# queue wait: the batch transfer is in flight on the producer thread —
# surfaced separately as a prefetch stall, not as host gap). The next
# dispatch_begin on the same thread consumes it.
_tls = threading.local()


def note_external_blocked(seconds):
    _tls.blocked = getattr(_tls, 'blocked', 0.0) + max(float(seconds),
                                                       0.0)


def _take_external_blocked():
    v = getattr(_tls, 'blocked', 0.0)
    _tls.blocked = 0.0
    return v


class HostGapMonitor:
    """Rolling per-step dispatch timestamps for one engine site.

    The inter-dispatch span (dispatch_end(i) → dispatch_begin(i+1))
    decomposes into three attributed parts:

    * GATING time (`host_gap_seconds`): blocking waits on the NEWEST
      dispatched step — the synchronous discipline's fetch. Nothing is
      queued behind that step, so the device runs dry for the wait's
      tail plus all host work after it; this is exactly the
      serialization windowed dispatch eliminates, and it is measured
      from attributed call durations, so it stays deterministic even
      on a shared/1-core host where wall residue is scheduler noise.
    * BLOCKED time (`blocked_wait_seconds`): waits on OLDER steps (the
      windowed drain — newer steps remain enqueued as runway) and
      DeviceLoader queue waits (the transfer is in flight on the
      producer thread; surfaced separately as prefetch stalls). The
      device is busy throughout — not host gap.
    * RESIDUE (`host_residue_seconds`): the unattributed wall
      remainder — genuine per-step host work (batch feeds, python
      overhead) on a quiet multi-core host; on a shared single core it
      also absorbs OS starvation while XLA compute threads run, so
      hardware rounds read it and CPU dryruns lean on the gating term.

    step_i  = dispatch_begin(i+1) - dispatch_begin(i): the wall interval
              between submissions.
    host_bound_fraction = sum(gating) / sum(step intervals) over the
    rolling window — ~1.0 means every step serializes behind a host
    fetch (host-bound discipline), ~0.0 means the host always has the
    next step enqueued before the device needs it.
    """

    def __init__(self, site, window=64, clock=time.perf_counter):
        self.site = site
        self._clock = clock
        self._gaps = collections.deque(maxlen=window)       # gating
        self._residues = collections.deque(maxlen=window)
        self._intervals = collections.deque(maxlen=window)
        self._depths = collections.deque(maxlen=window)
        self._blocked = collections.deque(maxlen=window)
        self._blocked_since_end = 0.0
        self._gating_since_end = 0.0
        self._last_begin = None
        self._last_end = None
        self.steps = 0
        self.drained = 0
        self.dispatched_total = 0   # monotonic — AsyncResults key off it
        with _monitors_lock:
            _monitors[site] = self

    def reset(self):
        self._gaps.clear()
        self._residues.clear()
        self._intervals.clear()
        self._depths.clear()
        self._blocked.clear()
        self._blocked_since_end = 0.0
        self._gating_since_end = 0.0
        self._last_begin = None
        self._last_end = None
        self.steps = 0
        self.drained = 0

    def dispatch_begin(self):
        now = self._clock()
        blocked = self._blocked_since_end + _take_external_blocked()
        gating = self._gating_since_end
        if self._last_end is not None:
            raw = max(now - self._last_end, 0.0)
            self._gaps.append(gating)
            self._residues.append(max(raw - gating - blocked, 0.0))
            self._blocked.append(blocked)
        if self._last_begin is not None:
            self._intervals.append(max(now - self._last_begin, 0.0))
        self._last_begin = now
        return now

    def dispatch_end(self, depth=1):
        self._last_end = self._clock()
        self._blocked_since_end = 0.0
        self._gating_since_end = 0.0
        self._depths.append(int(depth))
        self.steps += 1
        self.dispatched_total += 1

    def note_blocked(self, seconds):
        """The host just spent `seconds` blocked on device progress the
        device had queued runway behind (windowed drain) — busy device,
        not host gap."""
        self._blocked_since_end += max(float(seconds), 0.0)

    def note_gating(self, seconds):
        """The host just spent `seconds` blocked on the NEWEST
        dispatched step (synchronous-discipline fetch): the device's
        queue is empty behind it — starvation exposure, counted as
        host gap."""
        self._gating_since_end += max(float(seconds), 0.0)

    def drain_point(self):
        """An explicit drain barrier (engine.flush / trial end): the
        waits it performed are deliberate, not inter-step host gap —
        consume the pending attributions so they can't leak into the
        NEXT dispatch's gap sample."""
        self._gating_since_end = 0.0
        self._blocked_since_end = 0.0
        _take_external_blocked()

    def step_ready(self):
        self.drained += 1

    # -- derived --------------------------------------------------------------
    def host_gap_seconds(self):
        return (sum(self._gaps) / len(self._gaps)) if self._gaps else 0.0

    def host_bound_fraction(self):
        total = sum(self._intervals)
        if not total:
            return None
        gaps = list(self._gaps)[-len(self._intervals):]
        return min(sum(gaps) / total, 1.0)

    def snapshot(self):
        depths = list(self._depths)
        return {
            'steps': self.steps,
            'drained': self.drained,
            'host_gap_seconds': self.host_gap_seconds(),
            'host_gap_seconds_max': max(self._gaps) if self._gaps else 0.0,
            'host_residue_seconds':
                (sum(self._residues) / len(self._residues))
                if self._residues else 0.0,
            'blocked_wait_seconds':
                (sum(self._blocked) / len(self._blocked))
                if self._blocked else 0.0,
            'step_interval_seconds':
                (sum(self._intervals) / len(self._intervals))
                if self._intervals else 0.0,
            'host_bound_fraction': self.host_bound_fraction(),
            'dispatch_depth_mean':
                (sum(depths) / len(depths)) if depths else 0.0,
            'dispatch_depth_max': max(depths) if depths else 0,
        }

    def publish(self):
        """Push the rolling view into core.monitor (the engines call
        this from flush(), never from the dispatch hot path)."""
        from . import monitor as _m
        snap = self.snapshot()
        _m.gauge('ptpu_host_gap_seconds',
                 help='rolling mean host gap between step dispatches',
                 labelnames=('site',)).set(snap['host_gap_seconds'],
                                           site=self.site)
        _m.gauge('ptpu_host_dispatch_depth',
                 help='rolling mean in-flight dispatched steps',
                 labelnames=('site',)).set(snap['dispatch_depth_mean'],
                                           site=self.site)
        if snap['host_bound_fraction'] is not None:
            _m.gauge('ptpu_host_bound_fraction',
                     help='host gap / step interval over the rolling '
                          'window (1.0 = host-bound)',
                     labelnames=('site',)).set(
                         snap['host_bound_fraction'], site=self.site)
        return snap


# ---------------------------------------------------------------------------
# prefetch totals (DeviceLoader reports here; StepTelemetry reads)
# ---------------------------------------------------------------------------
_prefetch = {'loaders': 0, 'batches': 0, 'stalls': 0, 'h2d_bytes': 0,
             'depth': None, 'ring_reuses': 0}
_prefetch_lock = threading.Lock()


def note_prefetch(loaders=0, batches=0, stalls=0, h2d_bytes=0,
                  depth=None, ring_reuses=0):
    with _prefetch_lock:
        _prefetch['loaders'] += loaders
        _prefetch['batches'] += batches
        _prefetch['stalls'] += stalls
        _prefetch['h2d_bytes'] += h2d_bytes
        _prefetch['ring_reuses'] += ring_reuses
        if depth is not None:
            _prefetch['depth'] = depth


def reset_prefetch_totals():
    with _prefetch_lock:
        _prefetch.update(loaders=0, batches=0, stalls=0, h2d_bytes=0,
                         depth=None, ring_reuses=0)


def unregister_monitor(monitor):
    """Drop a shut-down engine's monitor from the registry (only if it
    is still the registered one for its site) so telemetry stops
    reporting a dead engine's rolling stats."""
    with _monitors_lock:
        if _monitors.get(monitor.site) is monitor:
            del _monitors[monitor.site]


def host_snapshot():
    """The StepTelemetry.snapshot()['host'] payload: per-site dispatch
    gap/depth views + aggregated DeviceLoader prefetch totals. None-ish
    (empty sites, zero counters) when no async loop ran."""
    with _monitors_lock:
        sites = {site: mon.snapshot() for site, mon in _monitors.items()}
    with _prefetch_lock:
        prefetch = dict(_prefetch)
    return {'sites': sites, 'prefetch': prefetch}


# ---------------------------------------------------------------------------
# async step results + bounded window
# ---------------------------------------------------------------------------
class AsyncResult:
    """One dispatched train step: device-resident loss, no host fetch.

    `wait()` blocks until the device finished this step (NOT a
    transfer) and runs the deferred drain work (numerics taps /
    GradScaler accounting) exactly once, in drain order. `result()`
    performs the one host fetch — routed through the numerics
    observatory's `_host_fetch` hook so the sync-count harness sees it.
    """

    __slots__ = ('loss', 'found_inf', 'step', '_taps', '_on_drain',
                 '_monitor', '_drained', '_counted', '_host_loss',
                 '_seq')

    def __init__(self, loss, step, found_inf=None, taps=None,
                 on_drain=None, monitor=None):
        self.loss = loss
        self.found_inf = found_inf
        self.step = step
        self._taps = taps
        self._on_drain = on_drain
        self._monitor = monitor
        self._drained = False
        self._counted = False
        self._host_loss = None
        # dispatch sequence snapshot: while this is still the NEWEST
        # dispatched step, a blocking wait on it is the synchronous
        # discipline (no queued runway) and counts as host gap
        self._seq = monitor.dispatched_total if monitor is not None \
            else None

    @property
    def taps(self):
        return self._taps

    def done(self):
        return self._drained

    def wait(self):
        if self._drained:
            return self
        t0 = time.perf_counter()
        try:
            self.loss.block_until_ready()
        except AttributeError:
            pass
        if self._monitor is not None and not self._counted:
            self._counted = True
            dt = time.perf_counter() - t0
            if self._seq != self._monitor.dispatched_total:
                # waiting on an OLD step while newer ones sit queued
                # behind it: the device has runway — blocked, not gap
                self._monitor.note_blocked(dt)
            else:
                # the synchronous discipline: nothing queued behind —
                # this wait (and the host work after it) starves the
                # device, so it counts as host gap
                self._monitor.note_gating(dt)
            self._monitor.step_ready()
        # run the deferred drain work BEFORE latching: if it raises
        # (deferred NumericsError from the taps check), a later
        # wait()/flush() retries it instead of silently dropping the
        # rest of the step's accounting (e.g. the scaler update)
        cb = self._on_drain
        if cb is not None:
            cb(self)
            self._on_drain = None
        self._drained = True
        return self

    def result(self):
        """Host fp32 loss — ONE host sync (at the caller's chosen drain
        point, e.g. trial end)."""
        if self._host_loss is None:
            self.wait()
            from . import numerics as _num
            import numpy as _np
            self._host_loss = float(_np.asarray(_num._host_fetch(self.loss)))
        return self._host_loss

    def __float__(self):
        return self.result()

    def tensor(self):
        """The loss as a Tensor (still device-resident)."""
        from .tensor import Tensor
        return Tensor(self.loss)

    def __repr__(self):
        state = 'drained' if self._drained else 'in-flight'
        return f'AsyncResult(step={self.step}, {state})'


class AsyncDispatchMixin:
    """The window-drain surface shared by the three compiled engines
    (each owns a `_inflight` DispatchWindow and a `_gap`
    HostGapMonitor)."""

    def flush(self):
        """Drain the in-flight dispatch window: deferred per-step work
        (taps processing, GradScaler accounting) and gauge publication
        happen here, never in the dispatch hot loop. The flush waits
        are a deliberate barrier — excluded from the next dispatch's
        host-gap sample."""
        with RecordEvent('train::flush', event_type='train'):
            drained = self._inflight.flush()
        self._gap.drain_point()
        self._gap.publish()
        led = getattr(self, '_ledger', None)
        if led is not None:
            try:
                led.publish()   # ledger rides the same drain point
            except Exception:
                pass
        return drained

    def host_gap_snapshot(self):
        return self._gap.snapshot()


class DispatchWindow:
    """Bounded FIFO of in-flight AsyncResults. `push` drains the oldest
    past `size` (steady state: waits on step i-k, which the device
    already finished while the host dispatched i-k+1..i). Drain order is
    submission order — the GradScaler/taps deferred work replays exactly
    the per-step sequence."""

    def __init__(self, size):
        self.size = max(int(size), 1)
        self._q = collections.deque()

    def __len__(self):
        return len(self._q)

    def push(self, result):
        self._q.append(result)
        if len(self._q) > self.size:
            # the wait on step i-k: nested in the engine's
            # train::dispatch span, so dispatch minus this is the
            # host's own work
            with RecordEvent('train::window_wait', event_type='train'):
                while len(self._q) > self.size:
                    # peek-then-pop: if the deferred drain work raises
                    # (e.g. a deferred NumericsError), the step STAYS at
                    # the head so a later flush() retries its remaining
                    # accounting
                    self._q[0].wait()
                    self._q.popleft()
        return result

    def flush(self):
        drained = []
        while self._q:
            self._q[0].wait()
            drained.append(self._q.popleft())
        return drained

    def clear(self):
        self._q.clear()
