"""What the readers of the `serve::device_step` records share (PR 36):
the serving engine records one such span per landed step, where the step
lands — from the end of the record before it (or its own launch, if
later) to the return of its last fetch, which with a step queued behind
is the device's own time on it — with what rode it as args: `steps`,
`dispatches`, `decode_rows`, `chunks`, `chunk_tokens`, `chunk_slots`,
`emitted`, `behind` (docs/serving.md#spans). Read from the program's
span ring, cut to the window `_program_spans.window` cuts.

A step KIND is read off the args: one decode dispatch (`chunks` 0,
`dispatches` 1), one dispatch that carried prompt chunks, two or more
dispatches. A kind's TIME is read off the records whose two ends are the
device's (`device_true`): where the host came for a step's ids after the
device had them (`late` 1: its turn outlasted a short step) the record
ends with the host's arrival and the next begins as much too late — on
the chip such a pair reads up to a third off the executions it covers,
the records between two fetches that waited within 1 % (PERF §6, PR 36).
Counts, shares and the p95 — what a request's tokens feel — take every
record. A program that records no such span (a parent commit before
PR 36), an untraced run and a window without the kind all give None, and
the reader leaves its metric out.
"""
import statistics

from benchmarks.common import log
from benchmarks.layer_metrics import _program_spans

NAME = 'serve::device_step'


def decode_only(args):
    return args['chunks'] == 0 and args['dispatches'] == 1


def one_chunk_dispatch(args):
    return args['chunks'] >= 1 and args['dispatches'] == 1


def multi_dispatch(args):
    return args['dispatches'] >= 2


def records(facts):
    """[(args, milliseconds)] of the window's records, oldest first;
    None where there is nothing to read."""
    cut = _program_spans.window(facts, 'serve::step')
    if cut is None:
        return None
    recs = [(s.args, s.dur_ns * 1e-6) for s in cut[1]
            if s.name == NAME and s.args]
    return recs or None


def census(facts):
    """Log how many spans a step the ring holds over the window (what a
    span added to the engine costs the ring's reach: 32,768 spans)."""
    cut = _program_spans.window(facts, 'serve::step')
    if cut is not None:
        steps, spans = cut
        log(f'program spans: {len(spans)} spans since the first of the '
            f'{len(steps)} serve::step in the ring = '
            f'{len(spans) / len(steps):.2f} a step')


def waited(args):
    """The record's last fetch waited for the device, with a step
    queued behind it: its end is where the device went into the next."""
    return bool(args['behind']) and not args.get('late')


def device_true(recs):
    """The records that lie between two fetches that waited: both ends
    are where the device went from one step into the next."""
    return [(args, ms) for (before, _), (args, ms) in zip(recs, recs[1:])
            if waited(before) and waited(args)]


def durations_ms(facts):
    """The durations of all the window's records; None without any."""
    recs = records(facts)
    return None if recs is None else [ms for _, ms in recs]


def median_ms(facts, kind):
    """Median duration of the window's device-true records of one kind
    — of every record of the kind where none of them is (a CPU run,
    where a step runs inside the call that queues it and every fetch
    finds its ids there); None where the window has no such kind."""
    recs = records(facts)
    if recs is None:
        return None
    every = [ms for args, ms in recs if kind(args)]
    durs = [ms for args, ms in device_true(recs) if kind(args)] or every
    if not durs:
        return None
    log(f'device steps: the median of {len(durs)} of the {len(every)} '
        f'{kind.__name__} records (of {len(recs)})')
    return statistics.median(durs)


def ratio(facts, over, under):
    """Σ args[over] ÷ Σ args[under] over the window's records; None
    where the denominator is 0."""
    recs = records(facts)
    if recs is None:
        return None
    below = sum(args[under] for args, _ in recs)
    if not below:
        return None
    above = sum(args[over] for args, _ in recs)
    log(f'device steps: {above} {over} over {below} {under} in '
        f'{len(recs)} records')
    return above / below
