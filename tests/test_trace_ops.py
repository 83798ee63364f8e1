"""tools/trace_ops.py: device time by instruction, on plain lists shaped
as `benchmarks.trace_reduce.load_xplane` returns them."""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'tools'))

import trace_ops


def test_a_class_is_split_by_instruction_and_control_flow_left_out():
    conv = ('%bitcast_dynamic-update-slice_fusion.{n} = bf16[24,{s}] '
            'fusion(%a, %b), kind=kOutput')
    events = [[conv.format(n=29, s='8192,2048'), 0.0, 800e3],
              [conv.format(n=29, s='8192,2048'), 1e6, 900e3],
              [conv.format(n=21, s='2,2048,8192'), 2e6, 700e3],
              ['%while.3 = (s32[]) while(%t), body=%b', 0.0, 5e6],
              ['copy.4 = f32[8]{0} copy(%x)', 3e6, 1e3]]
    planes = [{'name': '/device:TPU:0',
               'lines': [{'name': 'XLA Ops', 'events': events},
                         {'name': 'Async XLA Ops',
                          'events': [['%copy-start.1 = x', 0.0, 9e6]]}]},
              {'name': '/host:CPU',
               'lines': [{'name': 'python3',
                          'events': [['bench::train.flush', 0.0, 1e6]]}]}]
    ops = {k: (n, round(ms, 6))
           for k, (n, ms) in trace_ops.by_instruction(planes).items()}
    assert ops == {
        ('bitcast_dynamic-update-slice_fusion.29', 'bf16[24,8192,2048]'):
            (2, 1.7),
        ('bitcast_dynamic-update-slice_fusion.21', 'bf16[24,2,2048,8192]'):
            (1, 0.7),
        ('copy.4', 'f32[8]{0}'): (1, 0.001)}
