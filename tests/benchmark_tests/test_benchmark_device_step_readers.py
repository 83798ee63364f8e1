"""The eight readers PR 36 adds (benchmarks/layer_metrics/): seven over
the serving engine's `serve::device_step` records, one over the paged
kernel's `_chunk` classes — each on hand-made rings and op tables whose
answer is known, on a program that records no such span or names no such
class (the parent commit the driver lays these files over: None, never
a raise), on a toy engine's own ring against the engine's counters, and
in `BENCHMARK.json`, looked up BY NAME."""
import collections

import numpy as np
import pytest

import benchtoy
from benchmarks import trace_reduce
from benchmarks.layer_metrics import _device_steps, _program_spans

MANIFEST = benchtoy.manifest()
Span = collections.namedtuple(
    'Span', 'id parent name cat start_ns dur_ns tid tname depth args')
MS = 1_000_000
SERVER_CELLS = ['gpt3-1.3b.chat-closed64', 'trinity-mini.mixed-closed64',
                'phi4-mini-flash.reason-closed64', 'axk1.docs-closed64']
# metric -> (unit, source, layer, moves): ISSUE 36's table
NEW = {
    'dispatches_per_step.serve':
        ('x', 'program_span', 'serving engine', 'serve_tokens_per_s'),
    'prefill_padding_share.serve':
        ('%', 'program_span', 'serving engine', 'serve_tokens_per_s'),
    'decode_step_ms.serve':
        ('ms', 'program_span', 'serving engine', 'itl_ms_p95'),
    'chunk_step_ms.serve':
        ('ms', 'program_span', 'serving engine', 'ttft_ms_p95'),
    'multi_dispatch_step_ms.serve':
        ('ms', 'program_span', 'serving engine', 'itl_ms_p95'),
    'multi_dispatch_step_share.serve':
        ('%', 'program_span', 'serving engine', 'itl_ms_p95'),
    'device_step_ms_p95.serve':
        ('ms', 'program_span', 'serving engine', 'itl_ms_p95'),
    'paged_attention_chunk_ms_per_step.serve':
        ('ms', 'device_trace', 'Pallas kernels', 'ttft_ms_p95'),
}
SPAN_METRICS = [m for m in NEW if NEW[m][1] == 'program_span']
# the state-space cell's window never holds a step of two dispatches
# (1,134 one-dispatch records of 1,134, chip, PR 36), and a cell on a
# metric's list has to report it in every traced run
ABSENT = {'multi_dispatch_step_ms.serve': {'phi4-mini-flash.reason-closed64'}}


def reader(metric):
    return MANIFEST.load_module('layer_metrics', metric)


def span(sid, name, start_ms, dur_ms, **args):
    return Span(sid, 0, name, 'serve', int(start_ms * MS),
                int(dur_ms * MS), 1, 'main', 0, args or None)


def record(sid, start_ms, dur_ms, dispatches=1, chunks=0, tokens=0,
           slots=0, steps=1, late=0, rows=4):
    return span(sid, 'serve::device_step', start_ms, dur_ms, step=sid,
                steps=steps, dispatches=dispatches,
                shape='mixed' if chunks else 'decode', decode_rows=rows,
                chunks=chunks, chunk_tokens=tokens, chunk_slots=slots,
                emitted=rows, behind=1, late=late)


def ring_of(kinds):
    """A warm-up step and its 500 ms record (must not count), then one
    `serve::step` a window record, each record landing inside its
    step."""
    ring = [span(1, 'serve::step', 0, 600, step=1),
            record(2, 0, 500, dispatches=3, chunks=6, tokens=1, slots=600)]
    sid = 10
    for start, rec in enumerate(kinds):
        ring += [span(sid, 'serve::step', 1000 + 100 * start, 90, step=sid),
                 record(sid + 1, 1000 + 100 * start, *rec)]
        sid += 10
    return ring


def mixed_ring():
    """Twelve window records: five decode-only of 10–14 ms, then one
    whose fetch came LATE (19 ms: the host's turn) and the one after it
    (5 ms: as much too short) — neither is the device's time —, three
    of one dispatch with chunks (30, 40, 50 ms; 150 tokens in 256 slots
    each, but the last: 84), two of two dispatches (70 and 90 ms; the
    second with a whole step of inner chunks folded in)."""
    return ring_of(
        [(10 + k,) for k in range(5)]
        + [(19, 1, 0, 0, 0, 1, 1), (5,)]
        + [(30, 1, 2, 150, 256), (40, 1, 2, 150, 256), (50, 1, 1, 84, 256),
           (70, 2, 4, 400, 512), (90, 2, 3, 240, 512, 2)])


def decode_ring():
    return ring_of([(10 + k,) for k in range(4)])


FACTS = {'kind': 'serve', 'steps': 12, 'traced_steps': 3}
ON_THE_MIXED_RING = {
    'dispatches_per_step.serve': 14 / 13,
    'prefill_padding_share.serve': 100 * (1 - 1024 / 1792),
    # over the device-true records: the window's first (nothing before
    # it in the window), the late one and its successor are left out
    'decode_step_ms.serve': 12.5,
    'chunk_step_ms.serve': 40.0,
    'multi_dispatch_step_ms.serve': 80.0,
    # over every record: what a request's tokens feel
    'multi_dispatch_step_share.serve': 100 * 2 / 12,
    'device_step_ms_p95.serve': 90.0,
}
ON_THE_DECODE_RING = {
    'dispatches_per_step.serve': 1.0,
    'prefill_padding_share.serve': None,     # nothing was prefilled
    'decode_step_ms.serve': 12.0,
    'chunk_step_ms.serve': None,
    'multi_dispatch_step_ms.serve': None,
    'multi_dispatch_step_share.serve': 0.0,
    'device_step_ms_p95.serve': 13.0,
}


@pytest.mark.parametrize('metric', SPAN_METRICS)
def test_a_device_step_reader_on_a_hand_made_ring(metric, monkeypatch):
    monkeypatch.setattr(_program_spans, 'ring', mixed_ring)
    mod = reader(metric)
    assert mod.read({}, FACTS) == pytest.approx(ON_THE_MIXED_RING[metric])
    # the warm-up's record lies before the window's first step
    recs = _device_steps.records(FACTS)
    assert len(recs) == 12 and len(_device_steps.device_true(recs)) == 9
    # fewer steps in the ring than the window had: what is there
    assert mod.read({}, dict(FACTS, steps=1000)) is not None
    # an untraced run, and a run with no facts at all, read nothing
    assert mod.read({}, dict(FACTS, traced_steps=0)) is None
    assert mod.read({}, {}) is None


@pytest.mark.parametrize('metric', SPAN_METRICS)
def test_a_kind_the_window_never_ran_is_left_out(metric, monkeypatch):
    monkeypatch.setattr(_program_spans, 'ring', decode_ring)
    want = ON_THE_DECODE_RING[metric]
    got = reader(metric).read({}, dict(FACTS, steps=4))
    assert got is None if want is None else got == pytest.approx(want)


@pytest.mark.parametrize('metric', SPAN_METRICS)
def test_a_device_step_reader_on_a_program_without_the_record(
        metric, monkeypatch):
    """The parent commit: a ring with `serve::step` spans and no
    `serve::device_step`, and a program with no ring at all."""
    monkeypatch.setattr(_program_spans, 'ring', lambda: [
        s for s in mixed_ring() if s.name != 'serve::device_step'])
    assert reader(metric).read({}, FACTS) is None
    monkeypatch.undo()
    import paddle_tpu.profiler as prof
    monkeypatch.delattr(prof, 'spans')
    assert reader(metric).read({}, FACTS) is None


def ops_trace(*chips):
    return {'chips': {i: {'ops': ops} for i, ops in enumerate(chips)}}


BODIES = [
    # the four cells' classes: a `_chunk` class beside a bare one
    ({'pallas:paged_attention': 0.060,
      'pallas:paged_attention_chunk': 0.020}, 10.0),
    ({'pallas:paged_attention': 0.010, 'pallas:paged_attention_window': 0.03,
      'pallas:paged_attention_chunk': 0.004,
      'pallas:paged_attention_window_chunk': 0.008}, 6.0),
    ({'pallas:paged_attention_diff': 0.010,
      'pallas:paged_attention_diff_window': 0.03,
      'pallas:paged_attention_diff_chunk': 0.001,
      'pallas:paged_attention_diff_window_chunk': 0.002}, 1.5),
    ({'pallas:paged_attention_latent': 0.7,
      'pallas:paged_attention_latent_chunk': 0.9}, 450.0),
]


@pytest.mark.parametrize('ops,want', BODIES,
                         ids=['plain', 'window', 'diff', 'latent'])
def test_the_chunk_calls_are_read_apart_and_still_summed(ops, want):
    ops = dict(ops, **{'pallas:layer_norm_fwd': 0.010, 'fusion:fusion': 0.5,
                       'pallas:moe_grouped_matmul_chunk': 9.0})
    mod = reader('paged_attention_chunk_ms_per_step.serve')
    facts = {'traced_steps': 2}
    assert mod.read(ops_trace(ops), facts) == pytest.approx(want)
    # the mean over the chips
    halved = {k: v / 2 for k, v in ops.items()}
    assert mod.read(ops_trace(ops, halved), facts) == pytest.approx(
        0.75 * want)
    # the accepted readers match by prefix and keep summing both calls
    whole = sum(v for k, v in ops.items()
                if k.startswith('pallas:paged_attention')) / 2 * 1e3
    assert reader('paged_attention_ms_per_step.serve').read(
        ops_trace(ops), facts) == pytest.approx(whole)
    assert reader('diff_attention_ms_per_step.serve').read(
        ops_trace(ops), dict(facts, attn={})) is None     # no counter
    assert reader('diff_attention_ms_per_step.serve').read(
        ops_trace(ops), dict(facts, attn={'x': 1})) == pytest.approx(whole)
    latent = sum(v for k, v in ops.items() if k.startswith(
        'pallas:paged_attention_latent')) / 2 * 1e3
    assert reader('mla_attention_ms_per_step.serve').read(
        ops_trace(ops), dict(facts, mla={'x': 1})) == pytest.approx(latent)


def test_a_trace_that_names_no_chunk_call_reads_zero():
    mod = reader('paged_attention_chunk_ms_per_step.serve')
    facts = {'traced_steps': 2}
    # the parent: both calls under the bare name — 0.0, finite, as the
    # other kernel readers give where the trace has no such class
    assert mod.read(ops_trace({'pallas:paged_attention_latent': 1.6,
                               'fusion:fusion': 0.5}), facts) == 0.0
    # a trace whose kernels have no name at all (the recorded cut)
    recorded = trace_reduce.reduce(benchtoy.recorded_trace())
    assert mod.read(recorded, facts) == 0.0
    assert mod.read(ops_trace({'pallas:paged_attention_chunk': 1.0}),
                    {}) is None                           # untraced
    assert mod.read({'chips': {}}, facts) is None


def test_the_manifests_entries_by_name():
    by_name = {m['name']: m for m in MANIFEST.data['per_layer']}
    assert len(by_name) == len(MANIFEST.data['per_layer'])
    e2e = {m['name'] for m in MANIFEST.data['end_to_end']}
    layers = {m['layer'] for n, m in by_name.items() if n not in NEW}
    for name, (unit, source, layer, moves) in NEW.items():
        m = by_name[name]
        assert callable(reader(name).read)
        assert (m['unit'], m['source'], m['layer'], m['moves'],
                m['better']) == (unit, source, layer, moves, 'lower')
        assert moves in e2e and layer in layers
        assert set(m['workloads']) == (set(SERVER_CELLS)
                                       - ABSENT.get(name, set()))
        assert set(m) == {'name', 'unit', 'better', 'source', 'layer',
                          'moves', 'workloads'}
    for cell in SERVER_CELLS:
        assert ({n for n in NEW if cell not in ABSENT.get(n, ())}
                <= {m['name'] for m in MANIFEST.metrics('per_layer', cell)})


def test_the_readers_on_a_toy_engines_own_ring():
    """A toy engine (CPU: nothing timed here is a device number) run
    with prompts of several chunks: the readers, over the real ring,
    give what the engine's own counters give."""
    import paddle_tpu as paddle
    import paddle_tpu.profiler as prof
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import ServingConfig, ServingEngine
    paddle.seed(7)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=96, hidden_size=64, num_layers=2, num_heads=2,
        max_seq_len=96, hidden_dropout=0.0, attn_dropout=0.0,
        use_flash_attention=False))
    model.eval()
    eng = ServingEngine(model, ServingConfig(
        page_size=4, max_batch_size=4, prefill_chunk=4, num_pages=96,
        max_pages_per_seq=24, prefix_cache=False, seed=5))
    rng = np.random.RandomState(11)
    mark = prof.mark()
    for n in (13, 3, 18, 9, 11, 6):
        eng.submit(list(rng.randint(1, 96, n)), max_new_tokens=5, top_k=0)
    steps = 0
    while eng.scheduler.has_work:
        eng.step()
        steps += 1
    stats = eng.stats()
    # (the shutdown's drain would land after the window's last step)
    facts = {'kind': 'serve', 'steps': steps, 'traced_steps': steps}
    values = {m: reader(m).read({}, facts) for m in SPAN_METRICS}
    eng.shutdown()
    assert min(s.id for s in _program_spans.window(
        facts, 'serve::step')[1]) > mark
    assert values['dispatches_per_step.serve'] == pytest.approx(
        stats['dispatches_per_step'])
    assert values['prefill_padding_share.serve'] == pytest.approx(
        100 * stats['padded_prefill_token_share'])
    assert 0 < values['multi_dispatch_step_share.serve'] < 100
    # on the CPU a step runs inside the call that queues it, so nearly
    # every fetch finds its ids there (`late`): the medians by kind
    # then take every record of the kind
    for m in ('decode_step_ms.serve', 'chunk_step_ms.serve',
              'multi_dispatch_step_ms.serve', 'device_step_ms_p95.serve'):
        assert values[m] > 0
