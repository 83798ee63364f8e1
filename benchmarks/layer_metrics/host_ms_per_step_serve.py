"""host_ms_per_step.serve — layer: serving engine. What the host does in
one `engine.step()` beside waiting on the device: the median over the
window's steps of the `serve::step` span less the `serve::compiled_step`
(dispatch) and `serve::sample_fetch` (the wait for the sampled tokens)
spans inside it. On the program's clock (`time.perf_counter_ns`)."""
from benchmarks.layer_metrics import _program_spans


def read(trace, facts):
    return _program_spans.step_less_children_ms(
        facts, 'serve::step',
        ('serve::compiled_step', 'serve::sample_fetch'))
