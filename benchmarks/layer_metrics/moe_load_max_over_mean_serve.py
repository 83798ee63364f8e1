"""moe_load_max_over_mean.serve — layer: serving engine. From the
engine's counters over the window: the mean over decode steps (and
expert layers) of the most rows one expert took over the mean rows an
expert took. 1 is a perfectly even router; the grouped matmul's tiles,
and on several chips the slowest chip, follow the maximum."""


def read(trace, facts):
    c = facts.get('counters') or {}
    if not c.get('moe_load_steps'):
        return None
    return c['moe_load_sum'] / c['moe_load_steps']
