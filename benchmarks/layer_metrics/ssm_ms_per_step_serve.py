"""ssm_ms_per_step.serve — layer: Pallas kernels. Device time of the
state-space layers' selective scan (`pallas:selective_scan` on the `XLA
Ops` line: the decode group's and the chunk group's call of every Mamba
layer of every dispatch) per traced engine step, mean over the chips.
None where the program has no scan counters to go with it."""
from benchmarks.layer_metrics import _program_spans


def read(trace, facts):
    if not facts.get('ssm'):
        return None
    return _program_spans.pallas_class_ms_per_step(trace, facts,
                                                   'selective_scan')
