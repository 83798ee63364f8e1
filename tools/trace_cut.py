#!/usr/bin/env python
"""trace_cut — cut a small recorded fixture out of a kept xplane (as
`tools/trace_cell.py` leaves it), in the JSON `benchmarks/trace_reduce
.reduce` takes: `--events` consecutive events of the `XLA Ops` line from
the first event whose text holds `--from`, times shifted to start at 0,
HLO text cut to 140 characters with its fusion / tpu_custom_call marker
kept.

    python tools/trace_cut.py --xplane chiprun_out/trace_cell/xplane \\
        --from moe_grouped_matmul --events 400 --out fixture.json
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def short(text):
    marks = [m for m in ('tpu_custom_call', ' fusion(') if m in text]
    cut = text[:140]
    return cut + ''.join(f' {m}' for m in marks if m not in cut)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--xplane', required=True)
    ap.add_argument('--from', dest='start', required=True)
    ap.add_argument('--events', type=int, default=400)
    ap.add_argument('--note', default='')
    ap.add_argument('--out', required=True)
    args = ap.parse_args(argv)
    from benchmarks import trace_reduce
    planes = trace_reduce.load_xplane(trace_reduce.find_xplane(args.xplane))
    device = next(p for p in planes
                  if trace_reduce.DEVICE_PLANE.match(p['name']))
    events = sorted(device['lines'][0]['events'], key=lambda e: e[1])
    first = next(i for i, e in enumerate(events) if args.start in e[0])
    cut = events[first:first + args.events]
    t0 = cut[0][1]
    out = {'note': args.note, 'planes': [{
        'name': device['name'],
        'lines': [{'name': trace_reduce.OPS_LINE,
                   'events': [[short(n), s - t0, d] for n, s, d in cut]}]}]}
    with open(args.out, 'w') as f:
        json.dump(out, f)
    classes = {}
    for n, _, d in cut:
        c = trace_reduce.op_class(short(n))
        classes[c] = classes.get(c, 0) + 1
    print(json.dumps(classes, indent=1))


if __name__ == '__main__':
    main()
