"""The four readers the sparse-expert cell adds, on op tables and
counters whose answer is known, and on a record without the experts'
facts (a program that lacks them), where each returns None and does
not raise."""
import pytest

import benchtoy
from benchmarks import moe_bytes, trace_reduce

MANIFEST = benchtoy.manifest()
CELL = 'trinity-mini.mixed-closed64'
WEIGHT = moe_bytes.expert_weight_bytes(2048, 1024)


def reader(metric):
    return MANIFEST.load_module('layer_metrics', metric)


def facts(**over):
    out = {'kind': 'serve', 'steps': 100, 'traced_steps': 4,
           'device_kind': 'TPU v5 lite',
           'counters': {'moe_load_sum': 30.0, 'moe_load_steps': 12},
           'moe': {'traced': {'moe_experts_touched_total': 2000,
                              'moe_rows_total': 40000,
                              'moe_calls_total': 16},
                   'expert_weight_bytes': WEIGHT},
           'kv_window': {'kv_read_tokens_window': 300,
                         'kv_read_tokens_full': 400}}
    out.update(over)
    return out


def trace(seconds):
    return {'chips': {0: {'ops': {'pallas:moe_grouped_matmul': seconds,
                                  'pallas:paged_attention_window': 0.5,
                                  'fusion:fusion': 1.0}}}}


def test_one_expert_is_three_matrices():
    assert WEIGHT == 3 * 2048 * 1024 * 2 == 12_582_912


def test_the_experts_bytes_bound_the_servers_shapes():
    # a decode step: 512 rows over ~125 experts, bytes-bound by far
    seconds, bound = moe_bytes.least_seconds(125, 512, WEIGHT,
                                             'TPU v5 lite')
    assert bound == 'hbm'
    assert seconds == pytest.approx(125 * WEIGHT / 819e9)
    # only with thousands of rows an expert would the MXU bound it
    assert moe_bytes.least_seconds(1, 10 ** 5, WEIGHT,
                                   'TPU v5 lite')[1] == 'mxu'


def test_moe_ms_per_step():
    assert reader('moe_ms_per_step.serve').read(
        trace(0.040), facts()) == pytest.approx(10.0)


def test_moe_roofline_is_least_time_over_kernel_time():
    least = 2000 * WEIGHT / 819e9
    got = reader('moe_grouped_matmul_roofline.serve').read(
        trace(2 * least), facts())
    assert got == pytest.approx(50.0)
    assert got <= 105
    # a trace without the kernel: the share reads 0, not nothing
    assert reader('moe_grouped_matmul_roofline.serve').read(
        trace(0.0), facts()) == 0.0


def test_load_and_window_share():
    assert reader('moe_load_max_over_mean.serve').read(
        None, facts()) == pytest.approx(2.5)
    assert reader('window_kv_read_share.serve').read(
        None, facts()) == pytest.approx(75.0)


@pytest.mark.parametrize('metric', [
    'moe_ms_per_step.serve', 'moe_grouped_matmul_roofline.serve',
    'moe_load_max_over_mean.serve', 'window_kv_read_share.serve'])
def test_a_record_without_the_experts_facts_reads_as_nothing(metric):
    """The GPT server's record (or a program before the counters): no
    `moe`, no `kv_window`, no load counters."""
    bare = {'kind': 'serve', 'steps': 100, 'traced_steps': 4,
            'counters': {'decode_steps_total': 5}}
    planes = trace_reduce.reduce(benchtoy.recorded_trace())
    assert reader(metric).read(planes, bare) is None


def test_the_recorded_cut_of_the_cells_trace():
    """A cut of the cell's own chip trace (two expert layers of a
    prefill-chunk program): both kernels' rows are there under the
    names the readers look for — the fusion that READS a grouped
    matmul's output is not one —, and the share of the two calls' HBM
    roofline is what the chip gave: 256 experts' weights in 4.19 ms."""
    import json
    import os
    with open(os.path.join(benchtoy.HERE, 'trace_fixture_afmoe.json')) as f:
        cut = json.load(f)
    reduced = trace_reduce.reduce(cut['planes'])
    ops = reduced['chips'][0]['ops']
    assert ops['pallas:moe_grouped_matmul'] == pytest.approx(4.1937e-3,
                                                             rel=1e-3)
    assert ops['pallas:paged_attention_window'] > 0
    assert not [k for k in ops if k.startswith('pallas:')
                and 'moe' not in k and 'paged' not in k]
    f = facts(traced_steps=1, moe=dict(facts()['moe'],
                                       traced=cut['moe_traced']))
    paged = sum(v for k, v in ops.items()
                if k.startswith('pallas:paged_attention'))
    assert reader('paged_attention_ms_per_step.serve').read(
        reduced, f) == pytest.approx(paged * 1e3)
    assert reader('moe_ms_per_step.serve').read(reduced, f) == \
        pytest.approx(ops['pallas:moe_grouped_matmul'] * 1e3)
    share = reader('moe_grouped_matmul_roofline.serve').read(reduced, f)
    assert share == pytest.approx(
        100 * 256 * WEIGHT / 819e9 / ops['pallas:moe_grouped_matmul'])
    assert 90 < share <= 105


def test_the_cell_reports_them():
    names = {m['name'] for m in MANIFEST.metrics('per_layer', CELL)}
    assert names == {
        'moe_ms_per_step.serve', 'moe_grouped_matmul_roofline.serve',
        'moe_load_max_over_mean.serve', 'window_kv_read_share.serve',
        'engine_step_ms.serve', 'batch_occupancy.serve',
        'pallas_ms_per_step.serve', 'device_idle_share.serve', 'compile_s'}
    assert {m['name'] for m in MANIFEST.metrics('end_to_end', CELL)} == {
        'serve_tokens_per_s', 'ttft_ms_p95', 'itl_ms_p95', 'setup_s'}
