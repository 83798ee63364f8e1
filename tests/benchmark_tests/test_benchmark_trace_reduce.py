"""benchmarks/trace_reduce.py on the recorded cut of a chip trace
(trace_fixture.json: 600 events of a TPU v5e's `XLA Ops` line across one
engine-step boundary of gpt3-1.3b.chat-closed64) and on planes built by
hand to the same layout."""
import copy
import warnings

import pytest

import benchtoy
from benchmarks import trace_reduce as tr


def by_hand(events, spans=(), chip=0, asyncs=()):
    planes = [{'name': f'/device:TPU:{chip}', 'lines': [
        {'name': 'XLA Ops', 'events': [list(e) for e in events]},
        {'name': 'Async XLA Ops', 'events': [list(e) for e in asyncs]}]}]
    if spans:
        planes.append({'name': '/host:CPU', 'lines': [
            {'name': 'python3', 'events': [list(s) for s in spans]}]})
    return planes


def test_the_recorded_trace_reduces_to_what_the_chip_run_showed():
    s = tr.reduce(benchtoy.recorded_trace())
    assert list(s['chips']) == [0]
    c = s['chips'][0]
    # 600 events, the union of their intervals and its span
    assert c['window_s'] == pytest.approx(0.020769536, rel=1e-9)
    assert c['busy_s'] == pytest.approx(0.013276531, rel=1e-9)
    assert 0 < c['busy_s'] <= c['window_s']
    # the Mosaic calls are found by their custom-call target
    assert c['pallas_events'] == 40
    assert c['pallas_s'] == pytest.approx(0.011368395, rel=1e-9)
    assert s['device_ops'][0][0] == 'pallas:step'
    assert s['device_ops'][0][1] == pytest.approx(c['pallas_s'])
    assert len(s['device_ops']) <= 10
    # the idle time between the two engine steps lies inside the host's
    # bench::serve.engine_step span, and every gap is accounted for
    assert s['idle_gaps'][0][0] == 'bench::serve.engine_step'
    assert sum(g for _, g in s['idle_gaps']) == pytest.approx(
        c['window_s'] - c['busy_s'], rel=1e-9)


def test_async_ops_stay_out_of_the_busy_union():
    planes = benchtoy.recorded_trace()
    asyncs = [l for l in planes[0]['lines'] if l['name'] == 'Async XLA Ops']
    assert asyncs and asyncs[0]['events'], 'the fixture lost its async line'
    # that copy spans more than the whole window: counted, busy == window
    assert asyncs[0]['events'][0][2] > 0.0207e9
    with_async = tr.reduce(planes)
    for line in asyncs:
        line['events'] = []
    assert tr.reduce(planes)['busy_s'] == with_async['busy_s']


def test_busy_is_a_union_not_a_sum():
    s = tr.reduce(by_hand([('%a = f32[] add()', 0, 100),
                           ('%b = f32[] add()', 50, 100),
                           ('%c = f32[] add()', 300, 100)]))
    c = s['chips'][0]
    assert c['busy_s'] == pytest.approx(250e-9)
    assert c['window_s'] == pytest.approx(400e-9)
    assert s['idle_gaps'] == [['unattributed', pytest.approx(150e-9)]]


def test_a_gap_goes_to_the_innermost_enclosing_bench_span():
    events = [('%a = f32[] add()', 0, 100), ('%b = f32[] add()', 200, 100),
              ('%c = f32[] add()', 500, 100)]
    spans = [('bench::outer', 0, 1000), ('bench::inner', 90, 120),
             ('not ours', 0, 1000)]
    s = tr.reduce(by_hand(events, spans))
    assert dict(map(tuple, s['idle_gaps'])) == {
        'bench::inner': pytest.approx(100e-9),
        'bench::outer': pytest.approx(200e-9)}


def test_two_chips_planes_are_kept_apart_and_averaged():
    one = by_hand([('%a = f32[] add()', 0, 100),
                   ('%p = f32[] custom-call(), custom_call_target='
                    '"tpu_custom_call"', 100, 300)], chip=0)
    two = by_hand([('%a = f32[] add()', 0, 100),
                   ('%all-reduce.3 = f32[] all-reduce(%g)', 150, 50)], chip=1)
    s = tr.reduce(one + two)
    assert sorted(s['chips']) == [0, 1]
    assert s['chips'][0]['busy_s'] == pytest.approx(400e-9)
    assert s['chips'][1]['busy_s'] == pytest.approx(150e-9)
    assert s['chips'][0]['pallas_s'] == pytest.approx(300e-9)
    assert s['chips'][1]['pallas_s'] == 0
    assert s['chips'][1]['collective_s'] == pytest.approx(50e-9)
    assert s['busy_s'] == pytest.approx(275e-9)       # the mean
    assert s['pallas_s'] == pytest.approx(150e-9)


def test_a_trace_without_device_operations_reduces_to_no_chips():
    assert tr.reduce(by_hand([], [('bench::x', 0, 10)])) == {'chips': {}}


@pytest.mark.parametrize('text,cls', [
    ('%fusion.12 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop',
     'fusion:fusion'),
    ('%convolution_add_fusion.3 = bf16[8,8]{1,0} fusion(%a, %b), '
     'kind=kOutput', 'fusion:convolution_add_fusion'),
    ('%step.97 = (bf16[128,2048]{1,0}) custom-call(%x), '
     'custom_call_target="tpu_custom_call"', 'pallas:step'),
    ('%copy-done.4 = bf16[8]{0} copy-done(%copy-start.4)', 'copy-done'),
    ('%while.1 = (s32[]) while(%t), condition=%c, body=%b', 'while'),
])
def test_op_class(text, cls):
    assert tr.op_class(text) == cls


def test_a_loop_is_not_counted_beside_the_operations_it_encloses():
    s = tr.reduce(by_hand([('%while.1 = (s32[]) while(%t)', 0, 1000),
                           ('%cond.2 = (s32[]) conditional(%p, %t, %f), '
                            'branch_computations={%x, %y}', 0, 900),
                           ('%a = f32[] add()', 100, 200)]))
    assert s['device_ops'] == [['a', pytest.approx(200e-9)]]
    assert s['busy_s'] == pytest.approx(1000e-9)


def test_load_xplane_reads_a_profile_under_warnings_as_errors(tmp_path):
    """The loader on a real .xplane.pb — a CPU profile, which has the
    host plane and no device plane; nothing of it is a device number."""
    import jax
    import jax.numpy as jnp
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation('bench::test.span'):
            jnp.ones((8, 8)).sum().block_until_ready()
    with warnings.catch_warnings():
        warnings.simplefilter('error')
        planes = tr.load_xplane(tr.find_xplane(str(tmp_path)))
    spans = [e[0] for p in planes if p['name'] == tr.HOST_PLANE
             for line in p['lines'] for e in line['events']]
    assert spans == ['bench::test.span']
    assert tr.reduce(planes) == {'chips': {}}
    with pytest.raises(FileNotFoundError):
        tr.find_xplane(str(tmp_path / 'nothing-here'))
