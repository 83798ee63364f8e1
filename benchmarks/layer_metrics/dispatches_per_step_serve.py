"""dispatches_per_step.serve — layer: serving engine. Compiled programs
called per engine step over the window: Σ `dispatches` ÷ Σ `steps` of the
`serve::device_step` records. Above 1: steps with more prefilling
requests than the mixed program's prefill group has rows dispatched
again — every weight read again (ROADMAP S2(a))."""
from benchmarks.layer_metrics import _device_steps


def read(trace, facts):
    _device_steps.census(facts)
    return _device_steps.ratio(facts, 'dispatches', 'steps')
