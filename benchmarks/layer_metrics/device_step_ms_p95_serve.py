"""device_step_ms_p95.serve — layer: serving engine. Nearest-rank p95 of
the durations of all `serve::device_step` records of the window: what
`itl_ms_p95` is when the gap between a request's tokens is a device
step."""
from benchmarks.common import percentile
from benchmarks.layer_metrics import _device_steps


def read(trace, facts):
    durs = _device_steps.durations_ms(facts)
    return None if durs is None else percentile(durs, 95)
