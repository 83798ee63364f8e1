"""Observability v2 tests: span tracer, scheduler state machine,
chrome-trace/JSON export, executor compile-cache counters, Prometheus exposition, and the
end-to-end acceptance run (training under Profiler produces nested
executor/compile/dataloader/collective spans + a metrics snapshot with
compile-cache hit/miss, step throughput and per-collective bytes)."""
import json
import os
import threading
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.profiler as prof
import paddle_tpu.static as static
from paddle_tpu.core import monitor
from paddle_tpu.core.tensor import Tensor

S = prof.ProfilerState


@pytest.fixture
def python_recorder():
    """There is one recorder (the span ring); kept so the tests that
    named the old Python fallback read as before."""
    yield


@pytest.fixture
def fresh_metrics():
    monitor.registry().reset()
    monitor.metrics().reset()
    yield


def _record_window(body):
    """Run `body` inside a one-window Profiler; return its result."""
    out = []
    p = prof.Profiler(scheduler=None, on_trace_ready=out.append)
    p.start()
    body()
    p.stop()
    assert len(out) == 1
    return out[0].profiler_result


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------
class TestSpans:
    def test_nesting_and_args(self, python_recorder):
        def body():
            with prof.RecordEvent('outer', batch=3):
                with prof.RecordEvent('mid', event_type='op'):
                    with prof.RecordEvent('leaf'):
                        pass
        res = _record_window(body)
        by_name = {s['name']: s for s in res.spans}
        assert set(by_name) == {'outer', 'mid', 'leaf'}
        assert by_name['outer']['depth'] == 0
        assert by_name['mid']['parent'] == by_name['outer']['id']
        assert by_name['leaf']['parent'] == by_name['mid']['id']
        assert by_name['leaf']['depth'] == 2
        assert by_name['outer']['args'] == {'batch': 3}
        assert by_name['mid']['cat'] == 'op'
        # spans close inside-out: child intervals nest in the parent
        assert by_name['outer']['ts'] <= by_name['leaf']['ts']
        assert (by_name['leaf']['ts'] + by_name['leaf']['dur']
                <= by_name['outer']['ts'] + by_name['outer']['dur'])

    def test_thread_awareness(self, python_recorder):
        def body():
            def worker():
                with prof.RecordEvent('in_thread'):
                    pass
            t = threading.Thread(target=worker, name='feeder')
            with prof.RecordEvent('in_main'):
                t.start()
                t.join()
        res = _record_window(body)
        by_name = {s['name']: s for s in res.spans}
        assert by_name['in_thread']['tid'] != by_name['in_main']['tid']
        assert by_name['in_thread']['tname'] == 'feeder'
        # a thread's spans don't parent into another thread's stack
        assert by_name['in_thread']['parent'] == 0

    def test_no_recording_when_closed(self, python_recorder):
        """The ring is always on; a Profiler's result is a view that
        holds no span from outside its windows — not one that ended
        before, not one that straddles the window's start, not one
        after its end."""
        with prof.RecordEvent('outside_any_window'):
            pass
        straddler = prof.RecordEvent('begun_before_the_window')
        straddler.begin()
        p = prof.Profiler()
        p.start()
        straddler.end()
        with prof.RecordEvent('inside'):
            pass
        p.stop()
        with prof.RecordEvent('after_the_window'):
            pass
        assert [s['name'] for s in p.profiler_result.spans] == ['inside']
        ring = [s.name for s in prof.spans()]
        assert {'outside_any_window', 'begun_before_the_window',
                'after_the_window'} <= set(ring)


# ---------------------------------------------------------------------------
# scheduler state machine
# ---------------------------------------------------------------------------
class TestScheduler:
    def test_full_cycle(self):
        sch = prof.make_scheduler(closed=1, ready=1, record=2, repeat=2,
                                  skip_first=1)
        got = [sch(i) for i in range(10)]
        assert got == [S.CLOSED, S.CLOSED, S.READY, S.RECORD,
                       S.RECORD_AND_RETURN, S.CLOSED, S.READY, S.RECORD,
                       S.RECORD_AND_RETURN, S.CLOSED]

    def test_torch_aliases_and_repeat_forever(self):
        sch = prof.make_scheduler(wait=1, warmup=0, active=1, repeat=0)
        assert [sch(i) for i in range(4)] == [
            S.CLOSED, S.RECORD_AND_RETURN, S.CLOSED, S.RECORD_AND_RETURN]

    def test_validation(self):
        with pytest.raises(ValueError):
            prof.make_scheduler(record=0)
        with pytest.raises(ValueError):
            prof.make_scheduler(closed=-1, record=1)

    def test_profiler_windows_and_handler(self, python_recorder):
        windows = []
        p = prof.Profiler(
            scheduler=prof.make_scheduler(closed=1, ready=0, record=2,
                                          repeat=2),
            on_trace_ready=lambda pr: windows.append(
                [s['name'] for s in pr.profiler_result.spans]))
        p.start()
        for i in range(8):
            with prof.RecordEvent(f'step{i}'):
                pass
            p.step()
        p.stop()
        assert len(windows) == 2
        assert windows[0] == ['step1', 'step2']
        assert windows[1] == ['step4', 'step5']

    def test_tuple_scheduler(self, python_recorder):
        windows = []
        p = prof.Profiler(scheduler=(2, 4),
                          on_trace_ready=lambda pr: windows.append(
                              len(pr.profiler_result.spans)))
        p.start()
        for i in range(6):
            with prof.RecordEvent('s'):
                pass
            p.step()
        p.stop()
        assert windows == [2]


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------
class TestExport:
    def _trace(self, tmp_path, fmt, fname):
        def body():
            with prof.RecordEvent('work', bytes=128):
                with prof.RecordEvent('sub'):
                    pass
        res = _record_window(body)
        path = str(tmp_path / fname)
        if fmt == 'chrome':
            res.export_chrome_tracing(path)
        else:
            res.export_json(path)
        with open(path) as f:
            return json.load(f)

    def test_chrome_trace_without_native(self, tmp_path, python_recorder):
        doc = self._trace(tmp_path, 'chrome', 't.trace.json')
        evs = [e for e in doc['traceEvents'] if e['ph'] == 'X']
        assert {e['name'] for e in evs} == {'work', 'sub'}
        work = next(e for e in evs if e['name'] == 'work')
        assert work['args']['bytes'] == 128
        metas = [e for e in doc['traceEvents'] if e['ph'] == 'M']
        assert any(m['name'] == 'process_name' for m in metas)
        assert doc['metadata']['schema'] == 'paddle_tpu.profiler/2'

    def test_json_export(self, tmp_path, python_recorder):
        doc = self._trace(tmp_path, 'json', 'raw.json')
        assert [s['name'] for s in doc['spans']] == ['sub', 'work']

    def test_export_handler_writes_file(self, tmp_path, python_recorder):
        handler = prof.export_chrome_tracing_handler(str(tmp_path / 'd'))
        p = prof.Profiler(on_trace_ready=handler)
        p.start()
        with prof.RecordEvent('x'):
            pass
        p.stop()
        files = os.listdir(tmp_path / 'd')
        assert len(files) == 1 and files[0].endswith('.paddle_trace.json')

    def test_legacy_fallback_summary_and_export(self, tmp_path,
                                                python_recorder):
        """fluid-era API: a view over the ring since start_profiler()."""
        prof.reset_profiler()
        prof.start_profiler()
        try:
            with prof.RecordEvent('legacy_op'):
                pass
            with prof.RecordEvent('legacy_op'):
                pass
            s = prof.summary()
            assert 'legacy_op' in s and '\t2\t' in s
            path = str(tmp_path / 'legacy.json')
            prof.export_chrome_tracing(path)
            doc = json.load(open(path))
            evs = [e for e in doc['traceEvents'] if e['ph'] == 'X']
            assert len(evs) == 2
        finally:
            prof.stop_profiler(profile_path=None)


# ---------------------------------------------------------------------------
# executor compile cache + metrics registry
# ---------------------------------------------------------------------------
class TestExecutorCounters:
    def test_compile_cache_hit_miss(self, fresh_metrics):
        paddle.enable_static()
        try:
            main = static.Program()
            with static.program_guard(main):
                x = static.data('x', [2, 4])
                y = static.nn.fc(x, 3)
            exe = static.Executor()
            with static.scope_guard(static.Scope()):
                feed = {'x': np.ones((2, 4), 'float32')}
                exe.run(main, feed=feed, fetch_list=[y])
                exe.run(main, feed=feed, fetch_list=[y])
                exe.run(main, feed=feed, fetch_list=[y])
            stats = monitor.get_int_stats()
            assert stats['STAT_executor_cache_miss'] == 1
            assert stats['STAT_executor_cache_hit'] == 2
            assert stats['STAT_executor_runs'] == 3
            # the XLA compile was counted and timed
            reg = monitor.metrics()
            assert reg.get('ptpu_compiles_total').value(
                site='executor') >= 1
            assert reg.get('ptpu_compile_seconds_total').value(
                site='executor') > 0
        finally:
            paddle.disable_static()


class TestPrometheus:
    def test_exposition_format(self, fresh_metrics):
        c = monitor.counter('ptpu_collective_bytes_total',
                            help='bytes', labelnames=('op',))
        c.inc(1024, op='all_reduce')
        monitor.gauge('ptpu_examples_per_sec').set(10.5)
        h = monitor.histogram('ptpu_step_seconds', buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(5.0)
        monitor.stat_add('STAT_executor_runs', 7)
        text = monitor.prometheus_text()
        assert '# TYPE ptpu_collective_bytes_total counter' in text
        assert 'ptpu_collective_bytes_total{op="all_reduce"} 1024' in text
        assert 'ptpu_examples_per_sec 10.5' in text
        assert 'ptpu_step_seconds_bucket{le="0.1"} 1' in text
        assert 'ptpu_step_seconds_bucket{le="+Inf"} 2' in text
        assert 'ptpu_step_seconds_count 2' in text
        assert 'STAT_executor_runs 7' in text

    def test_snapshot_and_http_endpoint(self, fresh_metrics):
        monitor.counter('ptpu_x_total').inc(3)
        snap = monitor.metrics_snapshot()
        assert snap['metrics']['ptpu_x_total']['series'][0]['value'] == 3
        srv = monitor.start_metrics_server(port=0)
        try:
            base = f'http://127.0.0.1:{srv.port}'
            text = urllib.request.urlopen(base + '/metrics').read().decode()
            assert 'ptpu_x_total 3' in text
            js = json.load(urllib.request.urlopen(base + '/metrics.json'))
            assert js['metrics']['ptpu_x_total']['series'][0]['value'] == 3
        finally:
            srv.close()

    def test_metric_type_conflicts(self, fresh_metrics):
        monitor.counter('ptpu_y_total')
        with pytest.raises(TypeError):
            monitor.gauge('ptpu_y_total')
        with pytest.raises(ValueError):
            monitor.counter('ptpu_y_total', labelnames=('op',))


# ---------------------------------------------------------------------------
# end-to-end acceptance: training under Profiler
# ---------------------------------------------------------------------------
class TestEndToEndTrace:
    def test_training_trace_and_metrics(self, tmp_path, python_recorder,
                                        fresh_metrics):
        import paddle_tpu.distributed as dist
        from paddle_tpu.io import DataLoader, Dataset

        rng = np.random.RandomState(0)
        xs = rng.rand(64, 4).astype('float32')
        ys = (xs @ np.array([[1.], [-2.], [3.], [.5]], 'float32')
              + 0.1).astype('float32')

        class _DS(Dataset):
            def __getitem__(self, i):
                return xs[i], ys[i]

            def __len__(self):
                return len(xs)

        paddle.enable_static()
        try:
            main = static.Program()
            with static.program_guard(main):
                x = static.data('x', [16, 4])
                label = static.data('label', [16, 1])
                pred = static.nn.fc(x, 1)
                loss = paddle.mean((pred - label) * (pred - label))
                opt = paddle.optimizer.SGD(learning_rate=0.1)
                opt.minimize(loss)
            exe = static.Executor()

            telem = prof.StepTelemetry(window=8)
            traces = []
            p = prof.Profiler(
                on_trace_ready=lambda pr: traces.append(
                    pr.profiler_result))
            loader = DataLoader(_DS(), batch_size=16, drop_last=True)
            losses = []
            with static.scope_guard(static.Scope()), p:
                for xb, yb in loader:
                    with telem.step(examples=16):
                        out = exe.run(main,
                                      feed={'x': xb.numpy(),
                                            'label': yb.numpy()},
                                      fetch_list=[loss])
                        # eager collective on the fetched loss
                        # (world_size 1: identity, still instrumented)
                        dist.all_reduce(Tensor(out[0]))
                        losses.append(float(out[0]))
                    p.step()
        finally:
            paddle.disable_static()

        assert losses[-1] < losses[0]          # it actually trained

        # -- trace assertions ------------------------------------------------
        res = traces[-1]
        path = res.export_chrome_tracing(str(tmp_path / 'e2e.trace.json'))
        doc = json.load(open(path))
        evs = [e for e in doc['traceEvents'] if e['ph'] == 'X']
        names = {e['name'] for e in evs}
        assert {'executor::build_program', 'executor::lower',
                'executor::compile', 'executor::run',
                'dataloader::next', 'dataloader::produce',
                'collective::all_reduce'} <= names
        # nesting: the XLA compile span sits under the program build
        spans = {s['id']: s for s in res.spans}
        xla = next(s for s in res.spans if s['name'] == 'executor::compile')
        assert spans[xla['parent']]['name'] == 'executor::build_program'
        produce = next(s for s in res.spans
                       if s['name'] == 'dataloader::produce')
        assert spans[produce['parent']]['name'] == 'dataloader::next'
        coll = next(s for s in res.spans
                    if s['name'] == 'collective::all_reduce')
        assert coll['args']['bytes'] == 4      # one f32 scalar

        # -- metrics snapshot ------------------------------------------------
        stats = monitor.get_int_stats()
        assert stats['STAT_executor_cache_miss'] == 1
        assert stats['STAT_executor_cache_hit'] == len(losses) - 1
        reg = monitor.metrics()
        assert reg.get('ptpu_collective_calls_total').value(
            op='all_reduce') == len(losses)
        assert reg.get('ptpu_collective_bytes_total').value(
            op='all_reduce') == 4 * len(losses)
        assert reg.get('ptpu_dataloader_batches_total').value() \
            == len(losses)

        snap = telem.snapshot()
        assert snap['steps'] == len(losses)
        assert snap['examples_per_sec'] > 0    # step throughput
        assert snap['compile_cache_misses'] == 1
        assert snap['compile_cache_hits'] == len(losses) - 1
        assert snap['compile_seconds_total'] > 0
        # gauges published for the /metrics endpoint
        assert reg.get('ptpu_examples_per_sec').value() > 0
        # and the whole registry renders
        text = monitor.prometheus_text()
        assert 'ptpu_collective_bytes_total{op="all_reduce"}' in text


class TestDeviceTrace:
    def test_device_trace_bracket_and_metadata(self, tmp_path,
                                               python_recorder):
        """targets=[TPU] brackets RECORD windows with the jax.profiler
        (xplane) and stamps the logdir into the export metadata."""
        import jax.numpy as jnp
        d = str(tmp_path / 'xla')
        results = []
        p = prof.Profiler(targets=[prof.ProfilerTarget.TPU],
                          device_trace_dir=d,
                          on_trace_ready=lambda pr: results.append(
                              pr.profiler_result))
        p.start()
        with prof.RecordEvent('devwork'):
            (jnp.ones((8, 8)) @ jnp.ones((8, 8))).block_until_ready()
        p.stop()
        res = results[0]
        if res.device_trace_dir is None:
            pytest.skip("device tracer unavailable in this environment")
        assert res.device_trace_dir == d
        path = res.export_chrome_tracing(str(tmp_path / 'dev.trace.json'))
        doc = json.load(open(path))
        assert doc['metadata']['device_trace_dir'] == d
        assert os.path.isdir(d)          # xplane dump landed
        assert any(e['name'] == 'devwork' for e in doc['traceEvents'])


class TestHapiTelemetryCallback:
    def test_fit_publishes_telemetry(self, fresh_metrics):
        from paddle_tpu import nn
        from paddle_tpu.hapi import Model, StepTelemetry
        from paddle_tpu.metric import Accuracy

        paddle.seed(0)
        net = nn.Sequential(nn.Flatten(), nn.Linear(16, 4))
        model = Model(net)
        model.prepare(
            optimizer=paddle.optimizer.Adam(learning_rate=1e-3,
                                            parameters=net.parameters()),
            loss=nn.CrossEntropyLoss(), metrics=Accuracy())
        xs = np.random.RandomState(0).rand(32, 16).astype('float32')
        ys = np.random.RandomState(1).randint(0, 4, (32, 1))
        from paddle_tpu.io import TensorDataset
        ds = TensorDataset([Tensor(xs), Tensor(ys.astype('int64'))])
        cb = StepTelemetry(window=8)
        model.fit(ds, epochs=1, batch_size=8, verbose=0, callbacks=[cb])
        snap = cb.snapshot()
        assert snap['steps'] == 4
        assert snap['examples_per_sec'] > 0
        assert monitor.metrics().get('ptpu_examples_per_sec') is not None
