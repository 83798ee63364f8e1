"""paged_attention_ms_per_step.serve — layer: Pallas kernels. Device
time of the ragged paged-attention kernel (`pallas:paged_attention*` on
the `XLA Ops` line) per traced engine step, mean over the chips. Part of
`pallas_ms_per_step.serve`; the rest of that is the prefill chunks'
fused LayerNorm/GELU."""
from benchmarks.layer_metrics import _program_spans


def read(trace, facts):
    return _program_spans.pallas_class_ms_per_step(trace, facts,
                                                   'paged_attention')
