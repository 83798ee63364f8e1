"""engine_step_ms.serve — layer: serving engine. The median duration of
`engine.step()` on the benchmark's clock, over the traced run's whole
window."""
import statistics


def read(trace, facts):
    steps = facts.get('engine_step_ms')
    return statistics.median(steps) if steps else None
