"""decode_step_ms.serve — layer: serving engine. Median duration of the
`serve::device_step` records of ONE decode dispatch (`chunks` 0,
`dispatches` 1) between two fetches that waited for the device
(`_device_steps.device_true`): how long the device has a step that
carries no prompt. On the program's clock, between fetch returns."""
from benchmarks.layer_metrics import _device_steps


def read(trace, facts):
    return _device_steps.median_ms(facts, _device_steps.decode_only)
