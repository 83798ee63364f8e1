"""From a profiler trace (.xplane.pb) to the numbers the per-layer
readers take: per chip the traced window, the busy union, time by class
of device operation, Pallas time, collective time, and each idle gap
charged to the benchmark's host span that enclosed it.

Layout on this libtpu (0.0.34), read off a real trace before this was
written: one plane per chip, `/device:TPU:<n>`, whose line `XLA Ops`
holds every operation as its whole HLO text; `Async XLA Ops` holds
copy-start/done pairs that overlap `XLA Ops` and so stay out of the
busy union; the plane `/host:CPU` has one line per thread, and the
`python3` lines carry the `bench::` TraceAnnotation spans. All lines
share one clock (nanoseconds from the start of the trace).

`load_xplane` turns the file into plain lists; `reduce` works on those,
so the tests feed it a small recorded cut of a chip trace as JSON.
"""
import glob
import os
import re
import warnings

DEVICE_PLANE = re.compile(r'^/device:TPU:(\d+)$')
OPS_LINE = 'XLA Ops'
HOST_PLANE = '/host:CPU'
SPAN_PREFIX = 'bench::'
PALLAS_MARK = 'tpu_custom_call'
# control flow, by opcode: its time is that of the operations it encloses,
# which the line lists too, so it stays in the busy union and out of the
# table of classes
CONTAINERS = (' while(', ' conditional(', ' call(')
COLLECTIVES = ('all-reduce', 'all-gather', 'reduce-scatter',
               'collective-permute', 'all-to-all')
_NAME = re.compile(r'^%?([^\s=]+?)(?:\.\d+)?\s*=')


def find_xplane(trace_dir):
    """The newest .xplane.pb under a jax.profiler trace directory."""
    files = glob.glob(os.path.join(trace_dir, '**', '*.xplane.pb'),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f'no .xplane.pb under {trace_dir}')
    return max(files, key=os.path.getmtime)


def load_xplane(path):
    """[{name, lines: [{name, events: [[name, start_ns, dur_ns]]}]}],
    keeping the device planes' `XLA Ops` lines and, of the host plane,
    the `bench::` spans (a 30 s trace holds 10^5 other host events)."""
    from jax.profiler import ProfileData
    with warnings.catch_warnings():
        # the bindings warn on deprecated accessors; a `-W error`
        # environment must not die in the reduction
        warnings.simplefilter('ignore')
        data = ProfileData.from_file(path)
        planes = []
        for plane in data.planes:
            device = DEVICE_PLANE.match(plane.name)
            if not device and plane.name != HOST_PLANE:
                continue
            lines = []
            for line in plane.lines:
                if device and line.name != OPS_LINE:
                    continue
                events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                          for e in line.events
                          if device or e.name.startswith(SPAN_PREFIX)]
                if events:
                    lines.append({'name': line.name, 'events': events})
            planes.append({'name': plane.name, 'lines': lines})
    return planes


def op_class(text):
    """A short class for one HLO line: `pallas:<name>` for a Mosaic
    call, `fusion:<name>` for an XLA fusion, else the instruction's own
    name — each without its `.N` suffix."""
    m = _NAME.match(text)
    base = m.group(1) if m else text.split('(')[0][:40]
    if PALLAS_MARK in text:
        return 'pallas:' + base
    if ' fusion(' in text:
        return 'fusion:' + base
    return base


def _union(intervals):
    """Sorted, merged [start, end] intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _span_at(spans, t):
    """The innermost `bench::` host span that holds instant t."""
    best = None
    for name, s, d in spans:
        if s <= t <= s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else 'unattributed'


def reduce(planes):
    """{'chips': {n: {...}}, and over the chips used the means
    `window_s`, `busy_s`, `pallas_s`, `collective_s` and the top ten
    `device_ops` and `idle_gaps`}. Seconds throughout."""
    spans = [ev for p in planes if p['name'] == HOST_PLANE
             for line in p['lines'] for ev in line['events']
             if ev[0].startswith(SPAN_PREFIX)]
    chips = {}
    for p in planes:
        m = DEVICE_PLANE.match(p['name'])
        events = [ev for line in p['lines'] if line['name'] == OPS_LINE
                  for ev in line['events']] if m else []
        if not events:
            continue
        ops, pallas_events = {}, 0
        for text, _, dur in events:
            if any(mark in text for mark in CONTAINERS):
                continue
            cls = op_class(text)
            ops[cls] = ops.get(cls, 0.0) + dur * 1e-9
            pallas_events += cls.startswith('pallas:')
        busy = _union([s, s + d] for _, s, d in events)
        gaps = {}
        for (_, end), (start, _) in zip(busy, busy[1:]):
            name = _span_at(spans, 0.5 * (end + start))
            gaps[name] = gaps.get(name, 0.0) + (start - end) * 1e-9
        chips[int(m.group(1))] = {
            'window_s': (busy[-1][1] - busy[0][0]) * 1e-9,
            'busy_s': sum(e - s for s, e in busy) * 1e-9,
            'pallas_s': sum(v for k, v in ops.items()
                            if k.startswith('pallas:')),
            'pallas_events': pallas_events,
            'collective_s': sum(v for k, v in ops.items()
                                if k.startswith(COLLECTIVES)),
            'ops': ops, 'gaps': gaps}
    if not chips:
        return {'chips': {}}
    n = len(chips)

    def mean(key):
        return sum(c[key] for c in chips.values()) / n

    def top(key):
        total = {}
        for c in chips.values():
            for k, v in c[key].items():
                total[k] = total.get(k, 0.0) + v / n
        return [[k, v] for k, v in sorted(total.items(),
                                          key=lambda kv: -kv[1])[:10]]
    return {'chips': chips, 'window_s': mean('window_s'),
            'busy_s': mean('busy_s'), 'pallas_s': mean('pallas_s'),
            'collective_s': mean('collective_s'),
            'device_ops': top('ops'), 'idle_gaps': top('gaps')}
