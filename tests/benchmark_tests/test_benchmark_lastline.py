"""The last line of a run, in both trace modes, for every cell of
BENCHMARK.json: each runner is driven at toy size on the CPU through the
function run.py calls, and the object run.py would print is checked
against the contract (PR 22 was refused for a traced line that broke it).

On the CPU the traced run's profile is a null context and the trace
reduction is fed the recorded cut of a chip trace: a CPU profile is never
read under a device metric's name.
"""
import json
import math

import pytest

import benchtoy
from benchmarks import common, run as bench_run, trace_reduce

DEVICE = {'platform': 'tpu', 'kind': 'TPU v5 lite', 'count': 1,
          'memory_peak_bytes': 12345678}
MANIFEST = benchtoy.manifest()
CELLS = [w['name'] for w in MANIFEST.data['workloads']]


def check_line(line, manifest, cell_name, traced):
    """The contract's shape, as the driver reads it."""
    line = json.loads(json.dumps(line))        # what crosses the pipe
    keys = {'correct', 'attempted', 'failed', 'metrics', 'device'}
    assert set(line) == keys | ({'breakdown'} if traced else set())
    assert line['correct'] is True
    assert isinstance(line['attempted'], int) and line['attempted'] > 0
    assert line['failed'] == 0
    group = 'per_layer' if traced else 'end_to_end'
    wanted = {m['name']: m['unit'] for m in manifest.metrics(group,
                                                             cell_name)}
    assert wanted and set(line['metrics']) == set(wanted)
    for name, m in line['metrics'].items():
        assert set(m) == {'value', 'unit'} and m['unit'] == wanted[name]
        assert isinstance(m['value'], float) and math.isfinite(m['value'])
    device = line['device']
    base = {'platform', 'kind', 'count', 'memory_peak_bytes'}
    if not traced:
        assert set(device) == base
        assert line['metrics']['setup_s']['value'] > 0
        return
    assert set(device) == base | {'busy_s', 'window_s'}
    assert 0 < device['busy_s'] <= device['window_s']
    for key in ('device_ops', 'idle_gaps'):
        rows = line['breakdown'][key]
        assert 0 < len(rows) <= 10
        assert all(isinstance(n, str) and len(n) < 64 and s >= 0
                   for n, s in rows)


@pytest.mark.parametrize('traced', [0, 1])
@pytest.mark.parametrize('cell_name', CELLS)
def test_last_line_meets_the_contract(cell_name, traced):
    cell, config, traffic, runner = benchtoy.toy(MANIFEST, cell_name)
    ctx = common.Context(config, traffic, seed=2 ** 31 + 12345,
                         seconds=0.6, trace=traced, chips=cell['chips'])
    record = runner.run(ctx)
    assert record['facts']['compiles_in_window'] == 0
    assert record['facts']['traced_steps'] == (
        traffic['trace_steps'] if traced else 0)
    trace = trace_reduce.reduce(benchtoy.recorded_trace()) if traced \
        else None
    line = bench_run.result_line(MANIFEST, cell, record, trace, DEVICE)
    check_line(line, MANIFEST, cell_name, traced)


def test_a_reader_with_nothing_to_read_leaves_its_metric_out():
    cell = MANIFEST.cell(CELLS[0])
    record = {'correct': True, 'attempted': 1, 'failed': 0,
              'end_to_end': {}, 'facts': {'compile_s': 1.0}}
    trace = trace_reduce.reduce(benchtoy.recorded_trace())
    line = bench_run.result_line(MANIFEST, cell, record, trace, DEVICE)
    assert set(line['metrics']) == {'device_idle_share.train', 'compile_s'}


@pytest.mark.parametrize('busy,window', [(0.0, 1.0), (1.1, 1.0)])
def test_a_traced_line_with_a_wrong_window_is_refused(busy, window):
    cell = MANIFEST.cell(CELLS[0])
    record = {'correct': True, 'attempted': 1, 'failed': 0,
              'end_to_end': {}, 'facts': {}}
    trace = dict(trace_reduce.reduce(benchtoy.recorded_trace()),
                 busy_s=busy, window_s=window)
    with pytest.raises(ValueError):
        bench_run.result_line(MANIFEST, cell, record, trace, DEVICE)


def test_a_metric_that_is_not_finite_is_refused():
    cell = MANIFEST.cell(CELLS[0])
    record = {'correct': True, 'attempted': 1, 'failed': 0, 'facts': {},
              'end_to_end': {'train_tokens_per_s': float('nan'),
                             'setup_s': 1.0}}
    with pytest.raises(ValueError):
        bench_run.result_line(MANIFEST, cell, record, None, DEVICE)
