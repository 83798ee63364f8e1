"""dispatch_host_ms_per_step.train — layer: training engines. The
host's own work to dispatch one optimizer step: the median over the
window's steps of the `train::dispatch` span less the
`train::window_wait` span inside it (the wait on step i-k)."""
from benchmarks.layer_metrics import _program_spans


def read(trace, facts):
    return _program_spans.step_less_children_ms(
        facts, 'train::dispatch', ('train::window_wait',))
