"""What the readers of `program_span` metrics share: the program's own
span ring (`paddle_tpu.profiler.spans()`, always on since PR 25), cut to
the run's measured window. The ring is read in-process after the runner
returns; the xplane's copy of the same spans is not kept by
`trace_reduce`, so nothing here needs a profiler session.

A program without the ring (a parent commit before PR 25) gives None
from every function here, and the reader leaves its metric out.
"""
import statistics

from benchmarks.common import log


def ring():
    """The ring's spans, oldest first; None where there is no ring."""
    try:
        from paddle_tpu import profiler
    except ImportError:
        return None
    read = getattr(profiler, 'spans', None)
    return read() if read is not None else None


def window(facts, step_name):
    """(steps, spans): the last `facts['steps']` spans called
    `step_name` (fewer if the ring holds fewer, never older ones) and
    every span recorded since the first of them began. None in an
    untraced run or where there is nothing to read."""
    if not facts.get('traced_steps') or not facts.get('steps'):
        return None
    spans = ring()
    if not spans:
        return None
    steps = [s for s in spans if s.name == step_name][-int(facts['steps']):]
    if not steps:
        return None
    log(f'program spans: {len(steps)} {step_name} of the window\'s '
        f'{facts["steps"]} steps are in the ring')
    return steps, [s for s in spans if s.id >= steps[0].id]


def inside(spans, roots, names):
    """{root id: summed ns} of the spans called one of `names` that sit,
    at any depth, under one of `roots` (by parent id)."""
    parent = {s.id: s.parent for s in spans}
    total = {r.id: 0 for r in roots}
    for s in spans:
        if s.name not in names:
            continue
        up = s.parent
        while up and up not in total:
            up = parent.get(up, 0)
        if up:
            total[up] += s.dur_ns
    return total


def median_ms(facts, step_name, name):
    """Median duration, in ms, of the spans called `name` in the
    window of `step_name` steps; None where there are none."""
    cut = window(facts, step_name)
    if cut is None:
        return None
    durs = [s.dur_ns for s in cut[1] if s.name == name]
    if not durs:
        return None
    log(f'program spans: {len(durs)} {name}')
    return statistics.median(durs) * 1e-6


def step_less_children_ms(facts, step_name, children):
    """Median over the window's steps of a step's duration less the
    `children` spans inside it, in ms."""
    cut = window(facts, step_name)
    if cut is None:
        return None
    steps, spans = cut
    taken = inside(spans, steps, set(children))
    return statistics.median(
        s.dur_ns - taken[s.id] for s in steps) * 1e-6


def pallas_class_ms_per_step(trace, facts, prefix):
    """Device time of the Mosaic calls whose class starts with
    `pallas:<prefix>`, mean over the chips, per traced step, in ms; 0.0
    where the trace has none (kernels without a `name=`)."""
    chips = list((trace.get('chips') or {}).values())
    if not chips or not facts.get('traced_steps'):
        return None
    seconds = sum(v for c in chips for k, v in c['ops'].items()
                  if k.startswith('pallas:' + prefix)) / len(chips)
    return seconds / facts['traced_steps'] * 1e3
