"""prefill_padding_share.serve — layer: serving engine. Of the token
slots the mixed dispatches' prefill groups held over the window (`P x T`
a dispatch: `chunk_slots`), the share that carried no prompt token: 100 x
(1 - Σ `chunk_tokens` ÷ Σ `chunk_slots`) over the `serve::device_step`
records with a mixed dispatch. None where the window prefilled nothing."""
from benchmarks.layer_metrics import _device_steps


def read(trace, facts):
    filled = _device_steps.ratio(facts, 'chunk_tokens', 'chunk_slots')
    return None if filled is None else 100.0 * (1.0 - filled)
