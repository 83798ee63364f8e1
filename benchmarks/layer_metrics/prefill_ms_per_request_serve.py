"""prefill_ms_per_request.serve — layer: serving engine. The median
`serve::request.prefill` span (first admit to first token) over the
requests whose first token came in the window's steps: the part of TTFT
spent in prefill chunks queued behind decode steps."""
from benchmarks.layer_metrics import _program_spans


def read(trace, facts):
    return _program_spans.median_ms(facts, 'serve::step',
                                    'serve::request.prefill')
