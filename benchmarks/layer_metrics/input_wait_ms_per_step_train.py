"""input_wait_ms_per_step.train — layer: training engines. How long the
consumer waited on the DeviceLoader per optimizer step: the sum of the
`loader::wait` spans of the window's steps (one precedes each dispatch)
over the steps."""
from benchmarks.common import log
from benchmarks.layer_metrics import _program_spans


def read(trace, facts):
    cut = _program_spans.window(facts, 'train::dispatch')
    if cut is None:
        return None
    n = len(cut[0])
    waits = [s for s in _program_spans.ring()
             if s.name == 'loader::wait'][-n:]
    if not waits:
        return None
    log(f'program spans: {len(waits)} loader::wait')
    return sum(s.dur_ns for s in waits) * 1e-6 / n
