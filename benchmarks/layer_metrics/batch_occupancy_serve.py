"""batch_occupancy.serve — layer: serving engine (scheduler). From the
engine's own counters over the window, which are exact: decode tokens /
(decode steps x decode slots), in percent."""


def read(trace, facts):
    c = facts.get('counters') or {}
    if not c.get('decode_steps_total'):
        return None
    return 100.0 * c['decode_tokens_total'] / (
        c['decode_steps_total'] * facts['max_batch_size'])
