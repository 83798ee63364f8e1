"""`tools/trace_cell.py` holds the program's `serve::device_step` records
to the device's own `XLA Modules` time (ISSUE 36, part 3): the pure
pieces on hand-made records and executions whose answer is known. The
numbers of a real trace come only from the chip."""
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'tools'))

import trace_cell  # noqa: E402

MS = 1_000_000


def rec(start_ms, dur_ms, dispatches=1, chunks=0, late=0, behind=1):
    return ({'dispatches': dispatches, 'chunks': chunks, 'late': late,
             'behind': behind}, start_ms * MS, dur_ms * MS)


def test_the_two_clocks_are_joined_on_the_spans_both_hold():
    ring = [1_000, 2_000, 3_000, 4_000]
    # the trace began after the ring's first span; one start is 7 ns off
    offset, spread = trace_cell.clock_offset_ns(
        ring, [2_500, 3_507, 4_500])
    assert (offset, spread) == (500, 7)
    assert trace_cell.clock_offset_ns([], [1]) == (None, None)


def test_records_beside_the_executions_they_cover():
    # the ring's clock is 1 s ahead of the trace's; executions end 1 ms
    # before the fetch that closes their record returns
    modules = [[0 * MS, 40 * MS],               # in flight at the start
               [49 * MS, 10 * MS], [59 * MS, 10 * MS],      # decode, decode
               [69 * MS, 30 * MS],                          # one + chunks
               [99 * MS, 30 * MS], [129 * MS, 20 * MS],     # two dispatches
               [149 * MS, 10.5 * MS]]
    records = [rec(990, 60),        # began before the trace's first event
               rec(1050, 10), rec(1060, 10), rec(1070, 30, chunks=2),
               rec(1100, 50, dispatches=2, chunks=3), rec(1150, 10.5),
               rec(1400, 10)]       # landed after the trace ended
    table = trace_cell.hold_to_device(records, -1000 * MS, modules)
    assert table['dispatch_mismatch'] == 0
    kinds = table['kinds']
    assert kinds['decode_only'] == {
        'records': 3, 'device_true': 3, 'program_ms': 10.0,
        'device_ms': 10.0, 'ratio': 1.0, 'ratio_all': 1.0}
    assert kinds['one_chunk_dispatch']['records'] == 1
    assert kinds['multi_dispatch']['program_ms'] == 50.0
    assert kinds['multi_dispatch']['device_ms'] == 50.0
    assert table['total']['records'] == 5 and table['total']['late'] == 0
    assert table['total']['program_ms'] == pytest.approx(110.5)
    assert table['total']['device_ms'] == pytest.approx(110.5)
    assert {r[-1] for r in table['rows']} == {1.0}          # lag_ms
    text = trace_cell.render_device_steps(table, 7_000)
    assert 'multi_dispatch' in text and 'window total' in text


def test_a_late_fetch_spoils_its_record_and_the_next_and_no_other():
    modules = [[k * 10 * MS, 10 * MS] for k in range(6)]
    # the third record's fetch returned 4 ms after its execution ended
    # (the host came late): it reads 14, the next 6, their sum is right
    records = [rec(0, 10), rec(10, 10), rec(20, 14, late=1), rec(34, 6),
               rec(40, 10), rec(50, 10, dispatches=2)]
    table = trace_cell.hold_to_device(records, 0, modules)
    decode = table['kinds']['decode_only']
    assert (decode['records'], decode['device_true']) == (5, 2)
    assert decode['ratio'] == 1.0 and decode['ratio_all'] == 1.0
    assert table['total']['late'] == 1
    assert table['total']['ratio'] == pytest.approx(1.0)
    assert [r[-1] for r in table['rows'] if r[3]] == [4.0]   # its lag
    # the last record says two dispatches and one ran; it follows a
    # fetch that waited, so it counts — and is counted
    assert table['dispatch_mismatch'] == 1
    assert table['kinds']['multi_dispatch']['device_true'] == 1
    assert trace_cell.hold_to_device([], 0, modules) is None
    assert trace_cell.hold_to_device(records, 0, []) is None
