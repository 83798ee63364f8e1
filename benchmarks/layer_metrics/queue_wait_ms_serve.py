"""queue_wait_ms.serve — layer: serving engine. The median
`serve::request.queue` span (submit to first admit) over the requests
admitted in the window's steps: the part of TTFT spent waiting for a
slot and pages."""
from benchmarks.layer_metrics import _program_spans


def read(trace, facts):
    return _program_spans.median_ms(facts, 'serve::step',
                                    'serve::request.queue')
