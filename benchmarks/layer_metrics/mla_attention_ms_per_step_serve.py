"""mla_attention_ms_per_step.serve — layer: Pallas kernels. Device time
of the paged attention kernel's latent body (`pallas:paged_attention_latent`
on the `XLA Ops` line: every layer's calls, decode and chunk group) per
traced engine step, mean over the chips. None where the program has no
latent-attention counters to go with it."""
from benchmarks.layer_metrics import _program_spans


def read(trace, facts):
    if not facts.get('mla'):
        return None
    return _program_spans.pallas_class_ms_per_step(
        trace, facts, 'paged_attention_latent')
