"""step_ms.train — layer: training engines. The median time between
consecutive dispatch returns with the dispatch window full, over the
traced run's whole window: each return waits (block_until_ready) on the
step `window` back, so in steady state it is one optimizer step."""
import statistics


def read(trace, facts):
    gaps = facts.get('step_gaps_ms')
    return statistics.median(gaps) if gaps else None
