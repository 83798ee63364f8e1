"""moe_ms_per_step.serve — layer: Pallas kernels. Device time of the
sparse-expert layers' grouped matmul (`pallas:moe_grouped_matmul` on the
`XLA Ops` line: the gated first half and the second half of every
expert layer of every dispatch) per traced engine step, mean over the
chips. None where the program has no expert counters to go with it."""
from benchmarks.layer_metrics import _program_spans


def read(trace, facts):
    if not facts.get('moe'):
        return None
    return _program_spans.pallas_class_ms_per_step(trace, facts,
                                                   'moe_grouped_matmul')
