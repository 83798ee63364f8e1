"""multi_dispatch_step_share.serve — layer: serving engine. The share of
the window's `serve::device_step` records that hold two or more
dispatches, in percent (0 where none does)."""
from benchmarks.layer_metrics import _device_steps


def read(trace, facts):
    recs = _device_steps.records(facts)
    if recs is None:
        return None
    return 100.0 * sum(_device_steps.multi_dispatch(args)
                       for args, _ in recs) / len(recs)
