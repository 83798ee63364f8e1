"""multi_dispatch_step_ms.serve — layer: serving engine. Median duration
of the `serve::device_step` records of two or more dispatches (between
two fetches that waited for the device): the gap
between tokens every decode row sees when more requests prefill than the
mixed program's prefill group has rows. None where no step of the window
dispatched twice."""
from benchmarks.layer_metrics import _device_steps


def read(trace, facts):
    return _device_steps.median_ms(facts, _device_steps.multi_dispatch)
