"""diff_attention_ms_per_step.serve — layer: Pallas kernels. Device time
of the paged attention kernel under differential attention
(`pallas:paged_attention*` on the `XLA Ops` line: the window layers',
the full layer's and the cross layers' calls, decode and chunk group)
per traced engine step, mean over the chips. None where the program
has no attention counter to go with it."""
from benchmarks.layer_metrics import _program_spans


def read(trace, facts):
    if not facts.get('attn'):
        return None
    return _program_spans.pallas_class_ms_per_step(trace, facts,
                                                   'paged_attention')
