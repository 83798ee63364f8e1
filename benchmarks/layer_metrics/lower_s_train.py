"""lower_s.train — layer: training engines. Seconds of Python tracing
and lowering to StableHLO (`ptpu_lower_seconds_total`, every site),
which no persistent compile cache holds. No step compiles in the window,
so all of it is set-up's."""


def read(trace, facts):
    if not facts.get('traced_steps'):
        return None
    try:
        from paddle_tpu.core import monitor
    except ImportError:
        return None
    metric = monitor.metrics_snapshot()['metrics'].get(
        'ptpu_lower_seconds_total')
    if metric is None:
        return None
    return sum(s['value'] for s in metric['series'])
