"""chunk_step_ms.serve — layer: serving engine. Median duration of the
`serve::device_step` records of ONE dispatch that carried prompt chunks
(`chunks` >= 1, `dispatches` 1) between two fetches that waited for the
device: the mixed program with up to `P` prompts beside the decode
rows."""
from benchmarks.layer_metrics import _device_steps


def read(trace, facts):
    return _device_steps.median_ms(facts, _device_steps.one_chunk_dispatch)
