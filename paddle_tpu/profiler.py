"""Profiler v2 — unified host tracing + step telemetry.

Reference parity: python/paddle/profiler (Profiler:331, make_scheduler,
RecordEvent, export_chrome_tracing handlers) layered over the fluid-era
API (profiler:314 context manager) and platform/profiler.cc +
device_tracer.cc (N4).

One recorder. `RecordEvent` is the program's only span primitive and it
ALWAYS records into one process-wide ring of completed spans (id,
parent id, name, category, start and duration on
`time.perf_counter_ns`, thread, depth, args) — a tuple per span, one
lock acquisition, capacity fixed (`RING_CAPACITY`), overwritten spans
counted. While a `jax.profiler` session is live
(`jax.profiler.TraceAnnotation.is_enabled()`) the same span is also
entered as a `TraceAnnotation`, so it sits in the xplane's host plane on
the device trace's clock; with no session that costs one static call.

`spans(since_id)` reads the ring; `record_span` adds a span whose two
ends lie in different calls (a request's life). The v2 `Profiler`'s
RECORD windows and the fluid-era start/stop_profiler are VIEWS over the
ring: they mark the next span id where the window opens and take the
spans begun after it when it closes.

Device-side timing is jax.profiler's (XLA xplane), as the reference's
device_tracer correlates CUPTI with host events —
`Profiler(targets=[ProfilerTarget.TPU])` brackets the RECORD window
with jax.profiler.start_trace/stop_trace and stamps the logdir into the
exported trace metadata.

Step telemetry (`StepTelemetry`) aggregates examples/sec, tokens/sec,
compile seconds, compile-cache hit rates, live device memory and XLA
FLOP estimates into core.monitor gauges — consumed by the hapi
`StepTelemetry` callback and bench.py.
"""
import collections
import contextlib
import itertools
import json
import os
import threading
import time

from jax.profiler import TraceAnnotation as _Annotation

from .core import monitor as _monitor

_PID = os.getpid()
_now_ns = time.perf_counter_ns
_session_live = _Annotation.is_enabled      # a jax.profiler session is on

# One record is a 10-tuple of small ints, two interned strings and an
# optional args dict: ~250 bytes, ~500 with args. 32768 of them stay
# under about 16 MB and hold 800 serving steps of 40 spans.
RING_CAPACITY = 32768

Span = collections.namedtuple(
    'Span', 'id parent name cat start_ns dur_ns tid tname depth args')


# ---------------------------------------------------------------------------
# the ring
# ---------------------------------------------------------------------------
class _SpanRing:
    """Fixed-capacity ring of completed spans, in order of completion."""

    def __init__(self, capacity=RING_CAPACITY):
        self.capacity = int(capacity)
        self._slots = [None] * self.capacity
        self._n = 0                      # spans appended since clear()
        self._lock = threading.Lock()

    def append(self, record):
        with self._lock:
            n = self._n
            self._slots[n % self.capacity] = record
            self._n = n + 1

    def snapshot(self, since_id=0):
        with self._lock:
            n, cap = self._n, self.capacity
            if n <= cap:
                out = self._slots[:n]
            else:
                cut = n % cap
                out = self._slots[cut:] + self._slots[:cut]
        if since_id:
            out = [r for r in out if r[0] > since_id]
        return out

    def clear(self):
        with self._lock:
            self._slots = [None] * self.capacity
            self._n = 0

    def overwritten(self):
        return max(0, self._n - self.capacity)

    def __len__(self):
        return min(self._n, self.capacity)


_ring = _SpanRing()
_ids = itertools.count(1)                # next() is atomic in CPython
_tls = threading.local()         # per-thread (open-span stack, tid, name)
_legacy_mark = 0                         # fluid-era start_profiler's view


def _thread_state():
    try:
        return _tls.state
    except AttributeError:
        t = threading.current_thread()
        _tls.state = state = ([], t.ident, t.name)
        return state


def spans(since_id=0):
    """The ring's spans in order of completion, as `Span` records;
    `since_id` keeps those begun after that id (see `mark()`)."""
    return [Span._make(r) for r in _ring.snapshot(since_id)]


def mark():
    """An id that every span begun from now on exceeds: the start of a
    view (`spans(since_id=mark())` later)."""
    return next(_ids)


def overwritten_spans():
    """Spans the ring has dropped (oldest first) since the last
    reset_profiler()."""
    return _ring.overwritten()


def record_span(name, start_ns, end_ns, event_type=None, **args):
    """A span whose ends lie in different calls: stamp
    `time.perf_counter_ns()` at each and record it here at the second.
    It has no parent and is not mirrored into the device trace (an
    annotation cannot be backdated). Returns the span's id."""
    sid = next(_ids)
    _, tid, tname = _thread_state()
    _ring.append((sid, 0, name, event_type or 'python', int(start_ns),
                  int(end_ns) - int(start_ns), tid, tname, 0, args or None))
    return sid


def _as_dict(r):
    """The dict the exporters and ProfilerResult.spans have always
    carried (microseconds)."""
    return {'name': r[2], 'cat': r[3], 'ts': r[4] / 1000.0,
            'dur': r[5] / 1000.0, 'tid': r[6], 'tname': r[7], 'id': r[0],
            'parent': r[1], 'depth': r[8], 'args': r[9]}


def span_dicts(since_id=0):
    """`spans()` in the chrome-trace-ready dict form."""
    return [_as_dict(r) for r in _ring.snapshot(since_id)]


# ---------------------------------------------------------------------------
# RecordEvent — nested, thread-aware span marker
# ---------------------------------------------------------------------------
class RecordEvent:
    """Parity: paddle.profiler.RecordEvent / platform::RecordEvent RAII.

    Extra kwargs are recorded as the span's `args` (byte counts, cache
    keys, shapes...). Usable as a context manager or via explicit
    begin()/end(). Always recorded; mirrored into the device trace
    while a jax.profiler session is live.
    """

    __slots__ = ('name', 'event_type', 'args', '_start', '_id', '_ann')

    def __init__(self, name, event_type=None, **kwargs):
        self.name = name
        self.event_type = event_type
        self.args = kwargs or None
        self._start = None
        self._id = 0
        self._ann = None

    def begin(self):
        if _session_live():
            self._ann = _Annotation(self.name, **(self.args or {}))
            self._ann.__enter__()
        self._id = sid = next(_ids)
        _thread_state()[0].append(sid)
        self._start = _now_ns()

    def end(self):
        end_ns = _now_ns()
        start = self._start
        if start is None:
            return
        self._start = None
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        st, tid, tname = _thread_state()
        if st and st[-1] == self._id:
            st.pop()
        elif self._id in st:        # a child was begun and never ended
            del st[st.index(self._id):]
        _ring.append((self._id, st[-1] if st else 0, self.name,
                      self.event_type or 'python', start, end_ns - start,
                      tid, tname, len(st), self.args))

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *a):
        self.end()
        return False


record_function = RecordEvent       # torch-style alias


# ---------------------------------------------------------------------------
# legacy fluid-era API: a view over the ring since start_profiler()
# ---------------------------------------------------------------------------
def start_profiler(state='All', tracer_option='Default'):
    global _legacy_mark
    _legacy_mark = mark()


def stop_profiler(sorted_key=None, profile_path='/tmp/profile'):
    print(summary())
    if profile_path:
        export_chrome_tracing(profile_path + '.json')


def reset_profiler():
    _ring.clear()


def summary():
    """Aggregated name → calls/total/avg/min/max table of the spans
    since start_profiler() (the whole ring if it was never called)."""
    agg = {}
    for r in _ring.snapshot(_legacy_mark):
        dur = r[5] / 1000.0
        a = agg.setdefault(r[2], [0, 0.0, float('inf'), 0.0])
        a[0] += 1
        a[1] += dur
        a[2] = min(a[2], dur)
        a[3] = max(a[3], dur)
    lines = ['name\tcalls\ttotal_ms\tavg_us\tmin_us\tmax_us']
    for name in sorted(agg):
        c, tot, mn, mx = agg[name]
        lines.append(f'{name}\t{c}\t{tot / 1000.0:.3f}\t{tot / c:.1f}'
                     f'\t{mn:.1f}\t{mx:.1f}')
    return '\n'.join(lines) + '\n'


def export_chrome_tracing(path):
    """Legacy flat export of the same view as summary()."""
    _write_chrome_trace(path, span_dicts(_legacy_mark))
    return path


@contextlib.contextmanager
def profiler(state='All', sorted_key=None, profile_path='/tmp/profile',
             tracer_option='Default'):
    """Parity: fluid/profiler.py profiler:314 context manager."""
    start_profiler(state, tracer_option)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


# ---- device-side (XLA) trace ------------------------------------------------
def start_device_trace(logdir='/tmp/paddle_tpu_trace'):
    """XLA/PJRT profiler (parity role: device_tracer.cc CUPTI capture)."""
    import jax
    jax.profiler.start_trace(logdir)
    return logdir


def stop_device_trace():
    import jax
    jax.profiler.stop_trace()


# ---------------------------------------------------------------------------
# chrome-trace / JSON writers
# ---------------------------------------------------------------------------
def _chrome_events(spans):
    # spans may carry an explicit 'pid'/'pname' (synthetic track
    # groups — the serving request tracer puts each request on its own
    # virtual thread of a 'serving requests' pseudo-process so request
    # tracks render as a group beside the host's engine spans)
    events = []
    threads = {}
    procs = {_PID: 'paddle_tpu host'}
    for s in spans:
        pid = s.get('pid', _PID)
        if s.get('pname'):
            procs[pid] = s['pname']
        elif pid not in procs:
            procs[pid] = f'paddle_tpu pid {pid}'
        threads.setdefault((pid, s.get('tid', 0)), s.get('tname', ''))
        ev = {'name': s['name'], 'ph': 'X', 'pid': pid,
              'tid': s.get('tid', 0), 'ts': s['ts'], 'dur': s['dur'],
              'cat': s.get('cat') or 'python'}
        args = dict(s.get('args') or {})
        if s.get('parent'):
            args['parent_id'] = s['parent']
        if s.get('depth') is not None:
            args['depth'] = s['depth']
        if args:
            ev['args'] = {k: _jsonable(v) for k, v in args.items()}
        events.append(ev)
    for pid, pname in procs.items():
        events.append({'name': 'process_name', 'ph': 'M', 'pid': pid,
                       'args': {'name': pname}})
    for (pid, tid), tname in threads.items():
        events.append({'name': 'thread_name', 'ph': 'M', 'pid': pid,
                       'tid': tid, 'args': {'name': tname or str(tid)}})
    return events


def _jsonable(v):
    try:
        json.dumps(v)
        return v
    except TypeError:
        return repr(v)


def _device_chrome_events(trace_dir):
    """Chrome-format device events under a jax.profiler logdir, if the
    run produced any (older TF profiler versions write
    *.trace.json.gz beside the xplane protobuf)."""
    if not trace_dir or not os.path.isdir(trace_dir):
        return []
    import glob
    import gzip
    events = []
    pats = (os.path.join(trace_dir, '**', '*.trace.json.gz'),
            os.path.join(trace_dir, '**', '*.trace.json'))
    for pat in pats:
        for fp in glob.glob(pat, recursive=True):
            try:
                opener = gzip.open if fp.endswith('.gz') else open
                with opener(fp, 'rt') as f:
                    doc = json.load(f)
                for ev in doc.get('traceEvents', []):
                    if isinstance(ev, dict):
                        ev.setdefault('cat', 'device')
                        events.append(ev)
            except Exception:
                continue
    return events


def _write_chrome_trace(path, spans, metadata=None, device_events=()):
    doc = {'traceEvents': _chrome_events(spans) + list(device_events)}
    if metadata:
        doc['metadata'] = metadata
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    with open(path, 'w') as f:
        json.dump(doc, f)
    return path


# ---------------------------------------------------------------------------
# scheduler (paddle 2.x make_scheduler parity, torch aliases accepted)
# ---------------------------------------------------------------------------
class ProfilerState:
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3       # last RECORD step of a cycle


def make_scheduler(*, closed=None, ready=None, record=None, repeat=0,
                   skip_first=0, wait=None, warmup=None, active=None):
    """Parity: paddle.profiler.make_scheduler(closed, ready, record,
    repeat, skip_first); torch-style wait/warmup/active aliases map to
    closed/ready/record. Returns fn(step)->ProfilerState."""
    closed = wait if closed is None else closed
    ready = warmup if ready is None else ready
    record = active if record is None else record
    closed = int(closed or 0)
    ready = int(ready or 0)
    record = int(record)
    if record <= 0:
        raise ValueError("record (active) must be >= 1")
    if closed < 0 or ready < 0 or skip_first < 0 or repeat < 0:
        raise ValueError("scheduler windows must be non-negative")
    cycle = closed + ready + record

    def scheduler(step):
        if step < skip_first:
            return ProfilerState.CLOSED
        step -= skip_first
        if repeat and step >= repeat * cycle:
            return ProfilerState.CLOSED
        pos = step % cycle
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == cycle - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD
    scheduler._cycle = (skip_first, closed, ready, record, repeat)
    return scheduler


def _default_scheduler(_step):
    return ProfilerState.RECORD


class ProfilerTarget:
    CPU = 'cpu'
    GPU = 'gpu'
    TPU = 'tpu'
    CUSTOM_DEVICE = 'custom_device'


def export_chrome_tracing_handler(dir_name, worker_name=None):
    """Parity: paddle.profiler.export_chrome_tracing(dir_name) — an
    on_trace_ready handler writing one chrome-trace file per collected
    window into `dir_name`."""
    os.makedirs(dir_name, exist_ok=True)

    def handler(prof):
        worker = worker_name or f'host_{_PID}'
        lo, hi = prof.profiler_result.step_range
        path = os.path.join(dir_name,
                            f'{worker}_steps_{lo}_{hi}.paddle_trace.json')
        prof.profiler_result.export_chrome_tracing(path)
        return path
    return handler


class ProfilerResult:
    """Spans collected for one RECORD window, plus metadata."""

    def __init__(self, spans, step_range=(0, 0), device_trace_dir=None):
        self.spans = spans
        self.step_range = tuple(step_range)
        self.device_trace_dir = device_trace_dir

    def events(self):
        return list(self.spans)

    def _metadata(self):
        md = {'step_range': list(self.step_range),
              'schema': 'paddle_tpu.profiler/2'}
        if self.device_trace_dir:
            md['device_trace_dir'] = self.device_trace_dir
        return md

    def export_chrome_tracing(self, path):
        # best-effort merge of device-side events: TB/XLA profiler runs
        # that produced chrome-format dumps (*.trace.json[.gz]) fold in
        # under their own pids; xplane.pb-only runs stay referenced via
        # metadata.device_trace_dir (open with TB's profile plugin)
        return _write_chrome_trace(
            path, self.spans, self._metadata(),
            _device_chrome_events(self.device_trace_dir))

    def export_json(self, path):
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        with open(path, 'w') as f:
            json.dump({'metadata': self._metadata(),
                       'spans': [dict(s, args=_jsonable(s.get('args')))
                                 for s in self.spans]}, f)
        return path

    def summary(self, top=20):
        agg = {}
        for s in self.spans:
            a = agg.setdefault(s['name'], [0, 0])
            a[0] += 1
            a[1] += s['dur']
        rows = sorted(agg.items(), key=lambda kv: -kv[1][1])[:top]
        lines = ['name\tcalls\ttotal_ms\tavg_us']
        for name, (c, tot) in rows:
            lines.append(f'{name}\t{c}\t{tot / 1000.0:.3f}\t{tot / c:.1f}')
        return '\n'.join(lines) + '\n'


class Profiler:
    """Parity: paddle.profiler.Profiler (2.x) — scheduler-driven RECORD
    windows, on_trace_ready handlers, chrome/JSON export. A window is a
    view over the always-on span ring; `targets` containing TPU/GPU also
    brackets RECORD windows with jax.profiler device traces."""

    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 timer_only=False, record_shapes=False, profile_memory=False,
                 with_flops=False, device_trace_dir=None):
        self.timer_only = timer_only
        self.targets = list(targets) if targets else [ProfilerTarget.CPU]
        if scheduler is None:
            self._scheduler = _default_scheduler
        elif callable(scheduler):
            self._scheduler = scheduler
        elif isinstance(scheduler, (tuple, list)) and len(scheduler) == 2:
            start, end = scheduler
            if end <= start:
                raise ValueError("scheduler (start, end) needs end > start")
            self._scheduler = make_scheduler(closed=max(int(start), 0),
                                             record=int(end) - int(start),
                                             repeat=1)
        else:
            raise TypeError(f"bad scheduler {scheduler!r}")
        self.on_trace_ready = on_trace_ready
        self.profiler_result = None
        self._device_trace_dir = device_trace_dir
        self._device_tracing = False
        self.current_state = ProfilerState.CLOSED
        self._step_num = 0
        self._window_start = 0
        self._window_mark = 0
        self._running = False

    # -- device bracket ------------------------------------------------------
    def _wants_device(self):
        return any(t in (ProfilerTarget.TPU, ProfilerTarget.GPU)
                   for t in self.targets)

    def _device_begin(self):
        if not self._wants_device() or self._device_tracing:
            return
        try:
            import tempfile
            self._device_trace_dir = (self._device_trace_dir or
                                      tempfile.mkdtemp(
                                          prefix='paddle_tpu_xla_trace_'))
            start_device_trace(self._device_trace_dir)
            self._device_tracing = True
        except Exception:            # device tracer unavailable: host-only
            self._device_tracing = False

    def _device_end(self):
        if self._device_tracing:
            try:
                stop_device_trace()
            except Exception:
                pass
            self._device_tracing = False

    # -- state machine -------------------------------------------------------
    def _transition(self, new_state):
        old = self.current_state
        rec = (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN)
        if old not in rec and new_state in rec:
            self._open_window()
        if old == ProfilerState.RECORD_AND_RETURN or \
                (old in rec and new_state not in rec):
            self._device_end()
            self._collect()
            if new_state in rec:     # back-to-back windows (repeat)
                self._open_window()
        self.current_state = new_state

    def _open_window(self):
        """A RECORD window is a view over the ring: the spans begun
        after this mark, taken when the window closes."""
        self._window_start = self._step_num
        self._window_mark = mark()
        self._device_begin()

    def _collect(self):
        self.profiler_result = ProfilerResult(
            span_dicts(self._window_mark),
            step_range=(self._window_start, self._step_num),
            device_trace_dir=(self._device_trace_dir
                              if self._wants_device() else None))
        if self.on_trace_ready is not None and not self.timer_only:
            self.on_trace_ready(self)

    def start(self):
        if self._running:
            return
        self._running = True
        self._step_num = 0
        self._transition(self._scheduler(0))

    def step(self, num_samples=None):
        """Advance one iteration; drives the scheduler state machine."""
        if not self._running:
            raise RuntimeError("Profiler.step() before start()")
        self._step_num += 1
        new_state = self._scheduler(self._step_num)
        if new_state != self.current_state or \
                self.current_state == ProfilerState.RECORD_AND_RETURN:
            self._transition(new_state)

    def stop(self):
        if not self._running:
            return
        rec = (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN)
        if self.current_state in rec:
            self._device_end()
            self._collect()
        self.current_state = ProfilerState.CLOSED
        self._running = False

    # -- results -------------------------------------------------------------
    def export(self, path, format='json'):
        if self.profiler_result is None:
            raise RuntimeError("no collected window to export — run a "
                               "RECORD window (or call stop()) first")
        chrome = format in ('chrome', 'chrome_trace', 'chrometracing') \
            or path.endswith(('.trace.json', '.chrome.json'))
        if chrome:
            return self.profiler_result.export_chrome_tracing(path)
        return self.profiler_result.export_json(path)

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit='ms'):
        if self.profiler_result is not None:
            return self.profiler_result.summary()
        return summary()

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *a):
        self.stop()
        return False


# ---------------------------------------------------------------------------
# compile telemetry: instrumented AOT compile for jit call sites
# ---------------------------------------------------------------------------
def compile_with_telemetry(jitted, label, args, kwargs=None):
    """Split trace/lower vs XLA-compile for a `jax.jit`-wrapped fn and
    publish compile seconds + FLOP estimates. Returns (callable, ok):
    the AOT-compiled executable when lowering succeeds (ok=True), else
    the plain jitted fn (ok=False). Callers keep `jitted` as dispatch
    fallback for signature drift."""
    kwargs = kwargs or {}
    c_low = _monitor.counter('ptpu_lower_seconds_total',
                             help='cumulative trace + lower seconds '
                                  '(Python tracing to StableHLO)',
                             labelnames=('site',))
    c_sec = _monitor.counter('ptpu_compile_seconds_total',
                             help='cumulative XLA compile seconds '
                                  '(lowered.compile() alone)',
                             labelnames=('site',))
    c_num = _monitor.counter('ptpu_compiles_total',
                             help='XLA compilations', labelnames=('site',))
    try:
        t0 = time.perf_counter()
        with RecordEvent(f'{label}::lower', event_type='compile'):
            lowered = jitted.lower(*args, **kwargs)
        t1 = time.perf_counter()
        c_low.inc(t1 - t0, site=label)
        with RecordEvent(f'{label}::compile', event_type='compile'):
            compiled = lowered.compile()
        c_sec.inc(time.perf_counter() - t1, site=label)
        c_num.inc(1, site=label)
        # buffer-assignment census: the executable's temp (activation)
        # bytes — the resident set remat policies shrink (ISSUE 12;
        # core/memory.record_compiled_memory publishes the gauge)
        try:
            from .core import memory as _mem
            _mem.record_compiled_memory(label, compiled)
        except Exception:
            pass
        flops = _cost_flops(compiled)
        if flops is not None:
            _monitor.gauge('ptpu_xla_flops_per_run',
                           help='XLA cost-analysis FLOP estimate of the '
                                'latest compiled executable',
                           labelnames=('site',)).set(flops, site=label)
        return compiled, True
    except Exception as e:
        from .core.memory import is_oom_error
        if is_oom_error(e):
            # the program does not fit the device: the jitted fallback
            # would only compile it again to say the same — the
            # caller's to answer (the pipeline engine falls back to a
            # leaner remat policy)
            raise
        # lowering not supported for this callable/args — fall back to
        # the opaque jit path (compile time then hides in first call)
        c_num.inc(1, site=label)
        return jitted, False


def _cost_flops(compiled):
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        v = ca.get('flops')
        return float(v) if v is not None else None
    except Exception:
        return None


def device_memory_stats():
    """Live device memory via JAX (None entries when the backend does
    not expose memory_stats, e.g. CPU)."""
    try:
        import jax
        dev = jax.local_devices()[0]
        stats = dev.memory_stats() if hasattr(dev, 'memory_stats') else None
        if not stats:
            return None
        return {'bytes_in_use': stats.get('bytes_in_use'),
                'peak_bytes_in_use': stats.get('peak_bytes_in_use'),
                'bytes_limit': stats.get('bytes_limit')}
    except Exception:
        return None


# ---------------------------------------------------------------------------
# step telemetry reporter
# ---------------------------------------------------------------------------
class StepTelemetry:
    """Rolling-window step reporter: examples/sec, tokens/sec, step
    latency, compile totals, cache hit/miss, device memory, FLOP/s.
    Publishes gauges into core.monitor on every end_step; snapshot()
    returns the JSON-ready dict bench.py and the hapi callback read."""

    def __init__(self, window=20, publish=True):
        self.window = int(window)
        self.publish = publish
        self._durs = collections.deque(maxlen=self.window)
        self._examples = collections.deque(maxlen=self.window)
        self._tokens = collections.deque(maxlen=self.window)
        self._t0 = None
        self.steps = 0

    def begin_step(self):
        self._t0 = time.perf_counter()

    def end_step(self, examples=None, tokens=None):
        if self._t0 is None:
            return
        dt = time.perf_counter() - self._t0
        self._t0 = None
        self.steps += 1
        self._durs.append(dt)
        self._examples.append(0 if examples is None else int(examples))
        self._tokens.append(0 if tokens is None else int(tokens))
        if self.publish:
            self._publish()

    @contextlib.contextmanager
    def step(self, examples=None, tokens=None):
        self.begin_step()
        try:
            yield
        finally:
            self.end_step(examples=examples, tokens=tokens)

    # -- derived rates -------------------------------------------------------
    def _rate(self, counts):
        total_t = sum(self._durs)
        if not total_t:
            return 0.0
        return sum(counts) / total_t

    def examples_per_sec(self):
        return self._rate(self._examples)

    def tokens_per_sec(self):
        return self._rate(self._tokens)

    def avg_step_ms(self):
        return (sum(self._durs) / len(self._durs) * 1000.0) \
            if self._durs else 0.0

    def _publish(self):
        g = _monitor.gauge
        g('ptpu_examples_per_sec',
          help='rolling-window training throughput').set(
              self.examples_per_sec())
        if any(self._tokens):
            g('ptpu_tokens_per_sec',
              help='rolling-window token throughput').set(
                  self.tokens_per_sec())
        g('ptpu_step_ms', help='rolling mean step latency').set(
            self.avg_step_ms())
        g('ptpu_steps_total', help='telemetry steps observed').set(
            self.steps)
        mem = device_memory_stats()
        if mem and mem.get('bytes_in_use') is not None:
            g('ptpu_device_bytes_in_use',
              help='live device memory (JAX backend)').set(
                  mem['bytes_in_use'])
        # history sampling rides the publish cadence (ISSUE 18) —
        # no-op unless MetricsRegistry.enable_history() opted in
        _monitor.metrics().history_tick()

    def snapshot(self):
        reg = _monitor.metrics()

        def _counter_total(name):
            m = reg.get(name)
            if m is None:
                return 0.0
            return sum(c.value() for c in m._series().values())
        stats = _monitor.get_stats()
        snap = {
            'steps': self.steps,
            'avg_step_ms': self.avg_step_ms(),
            'examples_per_sec': self.examples_per_sec(),
            'tokens_per_sec': self.tokens_per_sec(),
            'compile_seconds_total':
                _counter_total('ptpu_compile_seconds_total'),
            'compiles_total': _counter_total('ptpu_compiles_total'),
            'compile_cache_hits':
                int(stats.get('STAT_executor_cache_hit', 0)),
            'compile_cache_misses':
                int(stats.get('STAT_executor_cache_miss', 0)),
            'device_memory': device_memory_stats(),
        }
        flops = reg.get('ptpu_xla_flops_per_run')
        if flops is not None:
            snap['xla_flops_per_run'] = {
                k[0]: c.value() for k, c in flops._series().items()}
        # numerics observatory (grad norms, nonfinite/divergence
        # counters, AMP loss scale) — zeros when it never ran
        try:
            from .core import numerics as _numerics
            snap['numerics'] = _numerics.snapshot()
        except Exception:
            snap['numerics'] = None
        # gradient-comm model (ptpu_comm_* gauges from the bucketed
        # engines) + persistent compile cache — docs/performance.md
        try:
            from .core import bucketing as _bucketing
            snap['comm'] = _bucketing.comm_snapshot() or None
        except Exception:
            snap['comm'] = None
        try:
            from .core import compile_cache as _cc
            snap['compile_cache'] = _cc.snapshot()
        except Exception:
            snap['compile_cache'] = None
        # serving engine (ptpu_serve_* gauges: decode tokens/sec, TTFT,
        # batch/page occupancy, preemptions) — docs/serving.md
        try:
            from .serving import metrics as _sm
            snap['serve'] = _sm.serve_snapshot() or None
        except Exception:
            snap['serve'] = None
        # Pallas primitive routing (ptpu_pallas_* counters): which fused
        # kernels vs reference fallbacks the traces picked — a silently
        # degraded route shows up here (docs/performance.md#fused-primitives)
        try:
            from .ops.pallas import scaffold as _scaffold
            snap['pallas'] = _scaffold.snapshot()
        except Exception:
            snap['pallas'] = None
        # async step pipeline (ptpu_host_* gauges): per-site dispatch
        # gap/depth + host_bound_fraction and DeviceLoader prefetch
        # totals — docs/performance.md#async-dispatch
        try:
            from .core import async_step as _async_step
            host = _async_step.host_snapshot()
            snap['host'] = host if (host.get('sites')
                                    or host['prefetch']['batches']) \
                else None
        except Exception:
            snap['host'] = None
        # tuned-remat view (ptpu_remat_* gauges/counters): active policy
        # per engine + checkpoint_name boundary counts, beside the
        # per-site activation-byte census — docs/performance.md#remat-policy
        try:
            from .distributed.fleet.utils.recompute import (
                snapshot as _remat_snapshot)
            from .core import memory as _mem
            remat = _remat_snapshot()
            acts = _mem.activation_bytes()
            if remat is not None or acts:
                remat = dict(remat or {})
                remat['activation_bytes'] = acts or None
            snap['remat'] = remat
        except Exception:
            snap['remat'] = None
        # pipeline schedule census (ptpu_pp_* gauges): active schedule,
        # virtual stages, tick counts and the modeled bubble fraction —
        # docs/performance.md#pipeline-schedules. Gauge presence is
        # checked first so sessions without a pipeline engine never pay
        # the fleet import.
        try:
            snap['pipeline'] = None
            if _monitor.metrics().get('ptpu_pp_ticks') is not None:
                from .distributed.fleet.meta_parallel.spmd_pipeline \
                    import pipeline_snapshot
                snap['pipeline'] = pipeline_snapshot()
        except Exception:
            snap['pipeline'] = None
        # step-time ledger (ISSUE 16): the reconciled wall decomposition
        # + MFU account, read back from the ptpu_ledger_* gauges
        try:
            from .core.ledger import ledger_snapshot
            snap['ledger'] = ledger_snapshot()
        except Exception:
            snap['ledger'] = None
        return snap


__all__ = [
    'RecordEvent', 'record_function', 'Profiler', 'ProfilerState',
    'ProfilerTarget', 'ProfilerResult', 'make_scheduler',
    'export_chrome_tracing_handler', 'start_profiler', 'stop_profiler',
    'reset_profiler', 'summary', 'export_chrome_tracing', 'profiler',
    'start_device_trace', 'stop_device_trace', 'compile_with_telemetry',
    'device_memory_stats', 'StepTelemetry', 'Span', 'spans', 'span_dicts',
    'mark', 'record_span', 'overwritten_spans', 'RING_CAPACITY',
]
