"""flash_attention_ms_per_step.train — layer: Pallas kernels. Device
time of the three flash-attention kernels (`pallas:flash_attention_fwd`,
`_bwd_dq`, `_bwd_dkv`; remat's recompute of the forward included) per
traced optimizer step, mean over the chips."""
from benchmarks.layer_metrics import _program_spans


def read(trace, facts):
    return _program_spans.pallas_class_ms_per_step(trace, facts,
                                                   'flash_attention')
