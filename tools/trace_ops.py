#!/usr/bin/env python
"""trace_ops — device time of a kept xplane by INSTRUCTION.

`benchmarks/trace_reduce.py` sums by class (`fusion:<name>` without its
`.N`), and one class can hold unlike things: since PR 35 the GPT
trainer's `bitcast_dynamic-update-slice_fusion` is four weight-gradient
matmuls AND two forward matmuls that write into stacked residuals. This
keys every `XLA Ops` event by the instruction's own `name.N` and result
shape, so a row can be matched to the compiled HLO text:

    python tools/trace_cell.py --workload gpt3-1.3b.pretrain-2k --out chiprun_out/t
    python tools/trace_ops.py chiprun_out/t/xplane [--top 60]
"""
import argparse
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import trace_reduce as tr

_INSTRUCTION = re.compile(r'^%?(\S+) = (\S+)')


def by_instruction(planes):
    """{(name.N, result shape): (calls, milliseconds)} over the device
    planes' `XLA Ops` lines, control flow left out as trace_reduce does."""
    ops = {}
    for plane in planes:
        if not tr.DEVICE_PLANE.match(plane['name']):
            continue
        for line in plane['lines']:
            if line['name'] != tr.OPS_LINE:
                continue
            for text, _, dur in line['events']:
                if any(mark in text for mark in tr.CONTAINERS):
                    continue
                m = _INSTRUCTION.match(text)
                key = (m.group(1), m.group(2)[:48]) if m else (text[:40], '')
                calls, ms = ops.get(key, (0, 0.0))
                ops[key] = (calls + 1, ms + dur * 1e-6)
    return ops


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('trace_dir', help='a directory holding an .xplane.pb')
    ap.add_argument('--top', type=int, default=60)
    args = ap.parse_args(argv)
    ops = by_instruction(tr.load_xplane(tr.find_xplane(args.trace_dir)))
    total = sum(ms for _, ms in ops.values())
    print(f'total {total:.1f} ms over {len(ops)} instructions')
    ranked = sorted(ops.items(), key=lambda kv: -kv[1][1])[:args.top]
    for (name, shape), (calls, ms) in ranked:
        print(f'{ms:10.2f} ms {calls:5d} x {ms / calls * 1e3:9.1f} us  '
              f'{name}  {shape}')


if __name__ == '__main__':
    main()
