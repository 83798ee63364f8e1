"""The readers PR 25 adds (benchmarks/layer_metrics/): each on hand-made
span lists and op tables whose answer is known, on the recorded cut of a
chip trace, and on a program without the span ring (the parent commit the
driver lays these files over), where each returns None and does not
raise."""
import collections

import pytest

import benchtoy
from benchmarks import trace_reduce
from benchmarks.layer_metrics import _program_spans

MANIFEST = benchtoy.manifest()
Span = collections.namedtuple(
    'Span', 'id parent name cat start_ns dur_ns tid tname depth args')
MS = 1_000_000


def span(sid, parent, name, start_ms, dur_ms):
    return Span(sid, parent, name, 'serve', int(start_ms * MS),
                int(dur_ms * MS), 1, 'main', 0, None)


def reader(metric):
    return MANIFEST.load_module('layer_metrics', metric)


def serve_ring():
    """A warm-up step (must not count), then three window steps of
    10 / 20 / 30 ms of which 6 / 12 / 18 ms are device waits; telemetry
    1 / 2 / 3 ms; two requests queue 4 and 8 ms and prefill 40 and 60."""
    ring = [span(1, 0, 'serve::step', 0, 99),
            span(2, 1, 'serve::telemetry', 90, 9),
            span(3, 0, 'serve::request.queue', 0, 77)]
    sid = 10
    for k, start in ((1, 100), (2, 200), (3, 300)):
        step = sid
        ring += [
            span(step, 0, 'serve::step', start, 10 * k),
            span(sid + 1, step, 'serve::decode', start + 1, 8 * k),
            span(sid + 2, sid + 1, 'serve::compiled_step', start + 1, 2 * k),
            span(sid + 3, sid + 1, 'serve::sample_fetch', start + 4, 4 * k),
            span(sid + 4, step, 'serve::telemetry', start + 9 * k, k)]
        sid += 10
    ring += [span(sid, 0, 'serve::request.queue', 100, 4),
             span(sid + 1, 0, 'serve::request.queue', 200, 8),
             span(sid + 2, 0, 'serve::request.prefill', 104, 40),
             span(sid + 3, 0, 'serve::request.prefill', 208, 60)]
    return ring


def train_ring():
    """Four dispatches; the window's last three take 5 / 7 / 9 ms of
    which 3 / 4 / 5 ms wait on the window; the loader made the consumer
    wait 0.5 / 1.0 / 1.5 ms before them (and 50 ms before the first)."""
    ring = [span(1, 0, 'loader::wait', 0, 50),
            span(2, 0, 'train::dispatch', 50, 100)]
    sid = 10
    for k, start in ((1, 200), (2, 300), (3, 400)):
        ring += [
            span(sid, 0, 'loader::wait', start - 2, 0.5 * k),
            span(sid + 1, 0, 'train::dispatch', start, 3 + 2 * k),
            span(sid + 2, sid + 1, 'pipeline::train_step', start, 1),
            span(sid + 3, sid + 1, 'train::window_wait', start + 1, 2 + k)]
        sid += 10
    return ring


SERVE_FACTS = {'kind': 'serve', 'steps': 3, 'traced_steps': 2}
TRAIN_FACTS = {'kind': 'train', 'steps': 3, 'traced_steps': 2}
SPAN_READERS = [
    # metric, ring, facts, the value in ms
    ('host_ms_per_step.serve', serve_ring, SERVE_FACTS, 8.0),
    ('telemetry_ms_per_step.serve', serve_ring, SERVE_FACTS, 2.0),
    ('queue_wait_ms.serve', serve_ring, SERVE_FACTS, 6.0),
    ('prefill_ms_per_request.serve', serve_ring, SERVE_FACTS, 50.0),
    ('dispatch_host_ms_per_step.train', train_ring, TRAIN_FACTS, 3.0),
    ('input_wait_ms_per_step.train', train_ring, TRAIN_FACTS, 1.0),
]


@pytest.mark.parametrize('metric,ring,facts,want', SPAN_READERS,
                         ids=[r[0] for r in SPAN_READERS])
def test_a_span_reader_on_a_hand_made_ring(metric, ring, facts, want,
                                           monkeypatch):
    monkeypatch.setattr(_program_spans, 'ring', ring)
    mod = reader(metric)
    assert mod.read({}, facts) == pytest.approx(want)
    # fewer steps in the ring than the window had: what is there, no more
    assert mod.read({}, dict(facts, steps=1000)) is not None
    # an untraced run, and a run with no facts at all, read nothing
    assert mod.read({}, dict(facts, traced_steps=0)) is None
    assert mod.read({}, {}) is None


@pytest.mark.parametrize('metric,ring,facts,want', SPAN_READERS,
                         ids=[r[0] for r in SPAN_READERS])
def test_a_span_reader_on_a_program_without_the_ring(metric, ring, facts,
                                                     want, monkeypatch):
    """The parent commit: `paddle_tpu.profiler` has no `spans()`, or the
    ring holds none of the names."""
    import paddle_tpu.profiler as prof
    monkeypatch.delattr(prof, 'spans')
    assert _program_spans.ring() is None
    assert reader(metric).read({}, facts) is None
    monkeypatch.undo()
    monkeypatch.setattr(_program_spans, 'ring', lambda: [
        span(1, 0, 'executor::run', 0, 1)])
    assert reader(metric).read({}, facts) is None


def test_the_window_never_reaches_back_past_its_steps(monkeypatch):
    monkeypatch.setattr(_program_spans, 'ring', serve_ring)
    steps, spans = _program_spans.window(SERVE_FACTS, 'serve::step')
    assert [s.id for s in steps] == [10, 20, 30]
    assert min(s.id for s in spans) == 10
    taken = _program_spans.inside(
        spans, steps, {'serve::compiled_step', 'serve::sample_fetch'})
    assert taken == {10: 6 * MS, 20: 12 * MS, 30: 18 * MS}


def ops_trace(*chips):
    return {'chips': {i: {'ops': ops} for i, ops in enumerate(chips)}}


KERNEL_READERS = [
    ('paged_attention_ms_per_step.serve',
     {'pallas:paged_attention': 0.080, 'pallas:layer_norm_fwd': 0.010,
      'fusion:fusion': 0.5}, 40.0),
    ('flash_attention_ms_per_step.train',
     {'pallas:flash_attention_fwd': 0.010,
      'pallas:flash_attention_bwd_dq': 0.020,
      'pallas:flash_attention_bwd_dkv': 0.030,
      'pallas:bias_gelu_fwd': 0.4, 'fusion:fusion': 0.5}, 30.0),
]


@pytest.mark.parametrize('metric,ops,want', KERNEL_READERS,
                         ids=[r[0] for r in KERNEL_READERS])
def test_a_kernel_reader(metric, ops, want):
    mod = reader(metric)
    facts = {'traced_steps': 2}
    assert mod.read(ops_trace(ops), facts) == pytest.approx(want)
    # the mean over the chips
    halved = {k: v / 2 for k, v in ops.items()}
    assert mod.read(ops_trace(ops, halved), facts) == pytest.approx(
        0.75 * want)
    # a trace whose kernels have no name (the parent's): 0.0, finite
    recorded = trace_reduce.reduce(benchtoy.recorded_trace())
    assert mod.read(recorded, facts) == 0.0
    assert mod.read(recorded, {}) is None
    assert mod.read({'chips': {}}, facts) is None


def test_lower_s_reads_the_lowering_counter_alone():
    from paddle_tpu.core import monitor
    mod = reader('lower_s.train')
    monitor.metrics().reset()
    assert mod.read({}, {'traced_steps': 3}) is None    # no such counter
    c = monitor.counter('ptpu_lower_seconds_total', labelnames=('site',))
    c.inc(1.5, site='pipeline.step')
    c.inc(0.25, site='hybrid.step')
    monitor.counter('ptpu_compile_seconds_total',
                    labelnames=('site',)).inc(9.0, site='pipeline.step')
    assert mod.read({}, {'traced_steps': 3}) == pytest.approx(1.75)
    assert mod.read({}, {}) is None
    monitor.metrics().reset()


def test_every_new_metric_has_its_reader_and_its_cells():
    new = {m['name']: m for m in MANIFEST.data['per_layer']
           if m['name'] in {r[0] for r in SPAN_READERS + KERNEL_READERS}
           | {'lower_s.train'}}
    assert len(new) == 9
    for name, m in new.items():
        assert callable(reader(name).read)
        kind = name.rsplit('.', 1)[1]
        cells = {'serve': ['gpt3-1.3b.chat-closed64'],
                 'train': ['gpt3-1.3b.pretrain-2k',
                           'bert-large.pretrain-512']}[kind]
        assert m['workloads'] == cells and m['better'] == 'lower'
