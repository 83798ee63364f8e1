"""The one span recorder (PR 25): the always-on ring behind
`RecordEvent`, its mirror into a live jax.profiler session, the views
the v2 Profiler and the fluid-era API take of it, `record_span`, and
the spans the serving engine, the training engines and the DeviceLoader
open — names and nesting are the contract the benchmark's readers
(benchmarks/layer_metrics/_program_spans.py) and docs/serving.md#spans
rely on."""
import glob
import threading
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.profiler as prof
from paddle_tpu import nn
from paddle_tpu.core import monitor
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.io import DeviceLoader
from paddle_tpu.serving import ServingConfig, ServingEngine


def since(mark):
    return prof.spans(since_id=mark)


# ---------------------------------------------------------------------------
# the ring
# ---------------------------------------------------------------------------
class TestRing:
    def test_always_on_no_profiler_needed(self):
        m = prof.mark()
        with prof.RecordEvent('ring::outer', event_type='t', n=3):
            with prof.RecordEvent('ring::inner'):
                pass
        inner, outer = since(m)
        assert (inner.name, outer.name) == ('ring::inner', 'ring::outer')
        assert inner.parent == outer.id and outer.parent == 0
        assert (inner.depth, outer.depth) == (1, 0)
        assert outer.cat == 't' and outer.args == {'n': 3}
        assert inner.cat == 'python' and inner.args is None
        assert outer.tid == threading.get_ident()
        # perf_counter_ns, nested in time
        assert outer.start_ns <= inner.start_ns
        assert (inner.start_ns + inner.dur_ns
                <= outer.start_ns + outer.dur_ns)
        assert abs(time.perf_counter_ns() - outer.start_ns) < 60e9

    def test_an_unended_child_does_not_adopt_later_spans(self):
        m = prof.mark()
        outer = prof.RecordEvent('ring::outer2')
        outer.begin()
        prof.RecordEvent('ring::never_ended').begin()
        outer.end()
        with prof.RecordEvent('ring::next'):
            pass
        by_name = {s.name: s for s in since(m)}
        assert set(by_name) == {'ring::outer2', 'ring::next'}
        assert by_name['ring::next'].parent == 0

    def test_capacity_and_overwrite_count(self):
        ring = prof._SpanRing(capacity=8)
        for i in range(1, 21):
            ring.append((i, 0, f's{i}', 'python', i, 1, 0, 't', 0, None))
        assert len(ring) == 8 and ring.overwritten() == 12
        assert [r[0] for r in ring.snapshot()] == list(range(13, 21))
        assert [r[0] for r in ring.snapshot(since_id=17)] == [18, 19, 20]
        ring.clear()
        assert len(ring) == 0 and ring.overwritten() == 0
        assert ring.snapshot() == []

    def test_the_process_ring_is_bounded(self):
        # ~16 MB at ~500 bytes a span with args
        assert prof._ring.capacity == prof.RING_CAPACITY <= 32768
        before = prof.overwritten_spans()
        for _ in range(64):
            with prof.RecordEvent('ring::fill'):
                pass
        assert len(prof._ring) <= prof.RING_CAPACITY
        assert prof.overwritten_spans() >= before

    def test_record_span_spans_two_calls(self):
        m = prof.mark()
        t0 = time.perf_counter_ns()
        sid = prof.record_span('ring::life', t0, t0 + 5000,
                               event_type='serve', req=7)
        (s,) = since(m)
        assert s.id == sid and s.name == 'ring::life'
        assert (s.start_ns, s.dur_ns) == (t0, 5000)
        assert s.parent == 0 and s.cat == 'serve' and s.args == {'req': 7}

    def test_threads_share_the_ring_and_keep_their_own_stacks(self):
        m = prof.mark()

        def worker():
            for _ in range(200):
                with prof.RecordEvent('ring::thread_outer'):
                    with prof.RecordEvent('ring::thread_inner'):
                        pass
        threads = [threading.Thread(target=worker, name=f'w{i}')
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        got = [s for s in since(m) if s.name.startswith('ring::thread_')]
        assert len(got) == 4 * 200 * 2
        assert len({s.id for s in got}) == len(got)
        by_id = {s.id: s for s in got}
        for s in got:
            if s.name == 'ring::thread_inner':
                # the parent is this thread's outer span, never another's
                assert by_id[s.parent].tid == s.tid
                assert by_id[s.parent].name == 'ring::thread_outer'
        assert {s.tname for s in got} == {'w0', 'w1', 'w2', 'w3'}

    def test_a_span_costs_microseconds(self):
        n = 20000
        t0 = time.perf_counter()
        for _ in range(n):
            with prof.RecordEvent('ring::cost', event_type='serve'):
                pass
        per_span_us = (time.perf_counter() - t0) / n * 1e6
        # budget 3 us on the benchmark's host; 10x slack for a loaded CI
        assert per_span_us < 30, per_span_us


# ---------------------------------------------------------------------------
# the mirror into the device trace
# ---------------------------------------------------------------------------
class TestDeviceTraceMirror:
    def test_no_annotation_object_without_a_session(self, monkeypatch):
        made = []

        class Counting:
            is_enabled = staticmethod(lambda: False)

            def __init__(self, *a, **k):
                made.append(a)
        monkeypatch.setattr(prof, '_Annotation', Counting)
        monkeypatch.setattr(prof, '_session_live', Counting.is_enabled)
        m = prof.mark()
        with prof.RecordEvent('mirror::off', k=1):
            pass
        assert made == [] and [s.name for s in since(m)] == ['mirror::off']

    def test_a_live_session_gets_the_span_on_its_clock(self, tmp_path):
        from jax.profiler import ProfileData
        d = str(tmp_path / 'xla')
        m = prof.mark()
        jax.profiler.start_trace(d)
        try:
            with prof.RecordEvent('mirror::on', event_type='serve',
                                  req=5, shape='decode'):
                (jnp.ones((8, 8)) @ jnp.ones((8, 8))).block_until_ready()
        finally:
            jax.profiler.stop_trace()
        with prof.RecordEvent('mirror::after'):
            pass
        (path,) = glob.glob(d + '/**/*.xplane.pb', recursive=True)
        found = [(e, dict(e.stats))
                 for p in ProfileData.from_file(path).planes
                 if p.name == '/host:CPU' for line in p.lines
                 for e in line.events if e.name.startswith('mirror::')]
        assert [e.name for e, _ in found] == ['mirror::on']
        event, stats = found[0]
        assert stats['req'] == 5 and stats['shape'] == 'decode'
        ring = {s.name: s for s in since(m)}
        assert set(ring) >= {'mirror::on', 'mirror::after'}
        # same span, two clocks: the durations agree to the microsecond
        # scale of entering and leaving the annotation
        assert abs(event.duration_ns - ring['mirror::on'].dur_ns) < 2e6

    def test_trace_summary_reads_program_spans_from_the_xplane(self,
                                                               tmp_path):
        import sys
        import os
        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), 'tools'))
        import trace_summary
        d = str(tmp_path / 'xla')
        with jax.profiler.trace(d):
            with prof.RecordEvent('serve::step'):
                with prof.RecordEvent('serve::prepare'):
                    jnp.ones((4,)).block_until_ready()
        chips, spans = trace_summary.load_device_trace(d)
        assert chips == {}                      # a CPU trace: no device
        assert {'serve::step', 'serve::prepare'} <= {n for n, _, _ in spans}
        # nothing of the runtime's own (C++ `Foo::Bar` TraceMes)
        assert all(trace_summary.PROGRAM_SPAN.match(n) for n, _, _ in spans)


def test_idle_gaps_are_charged_to_the_innermost_program_span():
    import sys
    import os
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), 'tools'))
    import trace_summary
    call = ('%paged_attention.3 = bf16[8]{0} custom-call(), '
            'custom_call_target="tpu_custom_call"')
    fusion = '%fusion.7 = bf16[8]{0} fusion(%p), kind=kLoop'
    # device busy [0,100] [300,400] [1000,1100] [1200,1300] (ns)
    chips = {0: [[call, 0.0, 100.0], [fusion, 300.0, 100.0],
                 [call, 1000.0, 100.0], [fusion, 1200.0, 100.0]]}
    spans = [['serve::step', 0.0, 1050.0], ['serve::accept', 150.0, 100.0],
             ['serve::telemetry', 500.0, 450.0],
             ['bench::serve.engine_step', -10.0, 1100.0]]
    s = trace_summary.summarize_device_trace(chips, spans)
    chip = s['chips'][0]
    assert chip['window_s'] == pytest.approx(1300e-9)
    assert chip['busy_s'] == pytest.approx(400e-9)
    gaps = {name: (sec, n) for name, sec, n in chip['idle_gaps']}
    assert gaps['serve::accept'] == (pytest.approx(200e-9), 1)
    assert gaps['serve::telemetry'] == (pytest.approx(600e-9), 1)
    assert gaps['unattributed'] == (pytest.approx(100e-9), 1)
    ops = dict(chip['device_ops'])
    assert ops['pallas:paged_attention'] == pytest.approx(200e-9)
    assert ops['fusion:fusion'] == pytest.approx(200e-9)
    host = {name: (calls, sec) for name, calls, sec in s['host_spans']}
    assert host['serve::telemetry'] == (1, pytest.approx(450e-9))
    assert 'bench::serve.engine_step' not in host   # begins before op 0
    assert 'serve::accept' in trace_summary.render_device_trace(s)


# ---------------------------------------------------------------------------
# views: the v2 Profiler and the fluid-era API
# ---------------------------------------------------------------------------
class TestViews:
    def test_two_profilers_see_the_same_spans(self):
        a, b = prof.Profiler(), prof.Profiler()
        a.start()
        with prof.RecordEvent('view::first'):
            pass
        b.start()
        with prof.RecordEvent('view::second'):
            pass
        a.stop()
        with prof.RecordEvent('view::third'):
            pass
        b.stop()
        # a window takes, it does not drain: overlapping windows and the
        # ring itself all still hold the spans
        assert [s['name'] for s in a.profiler_result.spans] == [
            'view::first', 'view::second']
        assert [s['name'] for s in b.profiler_result.spans] == [
            'view::second', 'view::third']
        assert {'view::first', 'view::second', 'view::third'} <= {
            s.name for s in prof.spans()}

    def test_result_spans_keep_the_dict_shape(self):
        p = prof.Profiler()
        p.start()
        with prof.RecordEvent('view::shape', event_type='op', k='v'):
            pass
        p.stop()
        (s,) = p.profiler_result.spans
        assert set(s) == {'name', 'cat', 'ts', 'dur', 'tid', 'tname', 'id',
                          'parent', 'depth', 'args'}
        assert s['cat'] == 'op' and s['args'] == {'k': 'v'}
        assert s['tname'] == threading.current_thread().name

    def test_legacy_view_starts_at_start_profiler(self, capsys):
        with prof.RecordEvent('legacy::before'):
            pass
        prof.start_profiler()
        with prof.RecordEvent('legacy::inside'):
            pass
        table = prof.summary()
        prof.stop_profiler(profile_path=None)
        assert 'legacy::inside\t1\t' in table
        assert 'legacy::before' not in table
        assert 'legacy::inside' in capsys.readouterr().out


# ---------------------------------------------------------------------------
# lower / compile counters
# ---------------------------------------------------------------------------
def test_lowering_and_compiling_are_counted_apart():
    monitor.metrics().reset()
    m = prof.mark()
    fn = jax.jit(lambda x: (x @ x).sum())
    exe, ok = prof.compile_with_telemetry(fn, 'spantest.step',
                                          (jnp.ones((16, 16)),))
    assert ok and float(exe(jnp.ones((16, 16)))) == 16 * 16 * 16
    reg = monitor.metrics()
    lower = reg.get('ptpu_lower_seconds_total').value(site='spantest.step')
    comp = reg.get('ptpu_compile_seconds_total').value(site='spantest.step')
    spans = {s.name: s.dur_ns * 1e-9 for s in since(m)}
    assert lower > 0 and comp > 0
    # each counter holds its own span's extent, not both
    assert lower == pytest.approx(spans['spantest.step::lower'], rel=0.2,
                                  abs=2e-3)
    assert comp == pytest.approx(spans['spantest.step::compile'], rel=0.2,
                                 abs=2e-3)


# ---------------------------------------------------------------------------
# serving engine spans
# ---------------------------------------------------------------------------
# a step's launch (`serve::prefill_chunk`, `serve::dispatch`) and the
# landing of the step in flight (its fetch and accepts, which run one
# program later than they were queued) are children of the step alike
STEP_CHILDREN = {'serve::admit', 'serve::prefill_chunk',
                 'serve::dispatch', 'serve::sample_fetch',
                 'serve::accept', 'serve::telemetry'}


@pytest.fixture(scope='module')
def tiny_lm():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(7)
    m = GPTForCausalLM(GPTConfig(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=2,
        max_seq_len=128, hidden_dropout=0.0, attn_dropout=0.0,
        use_flash_attention=False))
    m.eval()
    return m


@pytest.fixture(scope='module', params=['serial', 'fused', 'spec'])
def served(request, tiny_lm):
    """A toy engine run at each of the three decode step shapes:
    (spans of the run, requests, steps taken)."""
    knobs = {'serial': {}, 'fused': {'fused_k': 4}, 'spec': {'spec_k': 2}}
    eng = ServingEngine(tiny_lm, ServingConfig(
        page_size=8, max_batch_size=3, prefill_chunk=8,
        **knobs[request.param]))
    rng = np.random.RandomState(3)
    m = prof.mark()
    reqs = [eng.submit(list(rng.randint(1, 128, n)), max_new_tokens=6,
                       top_k=0) for n in (5, 19, 3, 11)]
    steps = 0
    while eng.scheduler.has_work:
        eng.step()
        steps += 1
    spans = since(m)
    eng.shutdown()
    return request.param, spans, reqs, steps


class TestServingSpans:
    def test_one_step_span_per_step_with_the_tables_children(self, served):
        shape, spans, _, steps = served
        by_id = {s.id: s for s in spans}
        step_spans = [s for s in spans if s.name == 'serve::step']
        assert len(step_spans) == steps
        assert [s.args['step'] for s in step_spans] == list(
            range(1, steps + 1))
        assert all(s.parent == 0 for s in step_spans)
        for s in spans:
            if s.name in STEP_CHILDREN:
                assert by_id[s.parent].name in (
                    ('serve::step', 'serve::dispatch')
                    if s.name in ('serve::sample_fetch', 'serve::accept')
                    else ('serve::step',)), s
        for step in step_spans:
            kids = [s.name for s in spans if s.parent == step.id]
            assert kids.count('serve::admit') == 1
            assert kids.count('serve::telemetry') == 1
            assert set(kids) <= STEP_CHILDREN
            # children lie inside the step, in time
            for s in spans:
                if s.parent == step.id:
                    assert step.start_ns <= s.start_ns and (
                        s.start_ns + s.dur_ns
                        <= step.start_ns + step.dur_ns)

    def test_admit_counts_what_it_admitted_and_nothing_wraps_it(self,
                                                               served):
        """`serve::schedule` (a parent that timed nothing its children
        did not) and `serve::check_stalled` (opened every step with the
        watchdog off) had no reader and are gone (PR 36); so is the
        construction-time `serve::state_alloc`."""
        _, spans, reqs, _ = served
        by_id = {s.id: s for s in spans}
        got = [s for s in spans if s.name == 'serve::admit']
        assert got and all(
            by_id[s.parent].name == 'serve::step' for s in got)
        assert sum(s.args['admitted'] for s in got) == len(reqs)
        assert not {s.name for s in spans} & {
            'serve::schedule', 'serve::check_stalled', 'serve::state_alloc'}

    def test_device_spans_sit_under_their_phase(self, served):
        shape, spans, _, _ = served
        by_id = {s.id: s for s in spans}
        for name in ('serve::prepare', 'serve::compiled_step'):
            got = [s for s in spans if s.name == name]
            assert got, name
            assert {by_id[s.parent].name for s in got} == {
                'serve::dispatch'}, name
        # a launched step lands under the step itself; a verify step or
        # a fused window is fetched and accepted where it is called
        for name in ('serve::sample_fetch', 'serve::accept'):
            got = [s for s in spans if s.name == name]
            assert got, name
            parents = {by_id[s.parent].name for s in got}
            assert 'serve::step' in parents and parents <= {
                'serve::step', 'serve::dispatch'}, name
            assert ('serve::dispatch' in parents) == (shape != 'serial')
        flights = {s.args['in_flight'] for s in spans
                   if s.name == 'serve::compiled_step'}
        assert flights == ({0, 1} if shape == 'serial' else {0})
        shapes = {s.args['shape'] for s in spans
                  if s.name == 'serve::compiled_step'}
        assert 'mixed' in shapes
        assert {'serial': 'decode', 'fused': 'fused',
                'spec': 'verify'}[shape] in shapes or shape == 'spec'

    def test_accept_counts_what_it_emitted_and_retired(self, served):
        _, spans, reqs, _ = served
        accepts = [s for s in spans if s.name == 'serve::accept']
        assert sum(s.args['emitted'] for s in accepts) == sum(
            len(r.generated) for r in reqs)
        assert sum(s.args['retired'] for s in accepts) == len(reqs)
        # an accept begins where its dispatch's fetch returned (a mixed
        # dispatch's second, the chunks', where the decode rows' ended;
        # one after inner chunks alone where the program was queued)
        ends = sorted(s.start_ns + s.dur_ns for s in spans
                      if s.name in ('serve::sample_fetch', 'serve::accept',
                                    'serve::compiled_step'))
        for a in accepts:
            before = max(t for t in ends if t <= a.start_ns)
            assert a.start_ns - before < 5e6

    def test_every_request_span_carries_its_request(self, served):
        _, spans, reqs, _ = served
        ids = {r.id for r in reqs}
        for name in ('serve::prefill_chunk', 'serve::request.queue',
                     'serve::request.prefill'):
            assert {s.args['req'] for s in spans if s.name == name} == ids

    def test_queue_plus_prefill_is_the_engines_ttft(self, served):
        _, spans, reqs, _ = served
        for r in reqs:
            (q,) = [s for s in spans if s.name == 'serve::request.queue'
                    and s.args['req'] == r.id]
            (p,) = [s for s in spans if s.name == 'serve::request.prefill'
                    and s.args['req'] == r.id]
            assert q.args['prompt_tokens'] == len(r.prompt)
            assert p.args['chunks'] == -(-len(r.prompt) // 8)
            # prefill begins where queueing ended, on one clock
            assert p.start_ns == q.start_ns + q.dur_ns
            ttft_ms = (q.dur_ns + p.dur_ns) * 1e-6
            # the engine's stamp is config.clock (perf_counter) read a
            # few statements away from the ring's perf_counter_ns
            assert ttft_ms == pytest.approx(r.ttft_ms(), abs=0.5)

    def test_request_spans_ignore_an_injected_clock(self, tiny_lm):
        ticks = iter(range(10 ** 6))
        eng = ServingEngine(tiny_lm, ServingConfig(
            page_size=8, max_batch_size=2, prefill_chunk=8,
            clock=lambda: float(next(ticks)) * 1000.0))
        m = prof.mark()
        eng.generate([[1, 2, 3]], max_new_tokens=2, top_k=0)
        eng.shutdown()
        (q,) = [s for s in since(m) if s.name == 'serve::request.queue']
        assert q.dur_ns < 60e9      # not the fake clock's thousands of s


# ---------------------------------------------------------------------------
# training engines and the DeviceLoader
# ---------------------------------------------------------------------------
def _mlp():
    return nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))


def _mlp_loss(m, x, y):
    return nn.functional.cross_entropy(m(x), y)


def _batches(n, b=8):
    rng = np.random.RandomState(0)
    return [(rng.rand(b, 8).astype('float32'),
             rng.randint(0, 4, (b,)).astype('int64')) for _ in range(n)]


def _hybrid(window):
    import paddle_tpu.distributed.fleet as fm
    from paddle_tpu.distributed import topology_runtime
    from paddle_tpu.distributed.fleet.meta_parallel.hybrid_engine import (
        HybridParallelTrainStep)
    fm.fleet._hcg = None
    topology_runtime.build_mesh(['dp', 'sharding'], [2, 2])
    paddle.seed(0)
    m = _mlp()
    opt = paddle.optimizer.Adam(parameters=m.parameters(),
                                learning_rate=1e-2)
    return HybridParallelTrainStep(m, _mlp_loss, opt,
                                   dispatch_window=window), 'hybrid'


def _pipeline(window):
    import paddle_tpu.distributed.fleet as fm
    from paddle_tpu.distributed import topology_runtime
    from paddle_tpu.models.gpt import GPTConfig, build_gpt_pipeline
    from paddle_tpu.distributed.fleet.meta_parallel.spmd_pipeline import (
        SpmdPipelineEngine)
    fm.fleet._hcg = None
    topology_runtime.build_mesh(['dp', 'pp'], [1, 2])
    paddle.seed(5)
    cfg = GPTConfig(vocab_size=64, hidden_size=16, num_layers=2,
                    num_heads=2, max_seq_len=32, hidden_dropout=0.0,
                    attn_dropout=0.0, use_flash_attention=False)
    embed, blocks, head = build_gpt_pipeline(cfg)
    opt = paddle.optimizer.SGD(learning_rate=1e-2, parameters=[])
    return SpmdPipelineEngine(embed, blocks, head, opt, accumulate_steps=2,
                              use_remat=False, schedule='1F1B',
                              dispatch_window=window), 'pipeline'


def _jit(window):
    from paddle_tpu.jit import TrainStep
    paddle.seed(0)
    m = _mlp()
    opt = paddle.optimizer.Adam(parameters=m.parameters(),
                                learning_rate=1e-2)
    return TrainStep(m, _mlp_loss, opt, dispatch_window=window), 'jit'


@pytest.mark.parametrize('build', [_hybrid, _pipeline, _jit],
                         ids=['hybrid', 'pipeline', 'jit'])
def test_trainer_spans(build):
    """N windowed steps through the DeviceLoader: one train::dispatch a
    step under one name for every engine, a train::window_wait inside
    each dispatch past the window's depth, one loader::wait before each
    batch on the consumer, one loader::stage a batch on the producer,
    one train::flush."""
    n, window = 5, 2
    eng, label = build(window)
    if label == 'pipeline':
        rng = np.random.RandomState(0)
        ids = [rng.randint(0, 64, (2, 32)).astype('int32')
               for _ in range(n)]
        data = [(i, np.roll(i, -1, 1).astype('int32')) for i in ids]
    else:
        data = _batches(n)
    m = prof.mark()
    try:
        loader = DeviceLoader(data, engine=eng)
        for b in loader:
            eng.train_step(b) if label == 'pipeline' else eng.train_step(*b)
        eng.flush()
    finally:
        if hasattr(eng, 'shutdown'):
            eng.shutdown()
    spans = since(m)
    dispatch = [s for s in spans if s.name == 'train::dispatch']
    assert len(dispatch) == n
    assert all(s.args['engine'] == label for s in dispatch)
    steps = [s.args['step'] for s in dispatch]
    assert steps == list(range(steps[0], steps[0] + n))
    waits = [s for s in spans if s.name == 'train::window_wait']
    assert len(waits) == n - window
    assert {s.parent for s in waits} <= {s.id for s in dispatch}
    assert len([s for s in spans if s.name == 'train::flush']) == 1
    consumer = threading.get_ident()
    lwait = [s for s in spans if s.name == 'loader::wait']
    assert len(lwait) == n + 1          # the last one finds the end
    assert {s.tid for s in lwait} == {consumer}
    stage = [s for s in spans if s.name == 'loader::stage']
    assert len(stage) == n and consumer not in {s.tid for s in stage}
    assert all(s.args['bytes'] == sum(np.asarray(a).nbytes for a in b)
               for s, b in zip(stage, data))
    if label == 'pipeline':
        inner = [s for s in spans if s.name == 'pipeline::train_step']
        assert {s.parent for s in inner} == {s.id for s in dispatch}
