"""device_idle_share.train — layer: device. 100 x (1 - busy union of the
`XLA Ops` line / traced window), averaged over the chips."""


def read(trace, facts):
    if not trace.get('chips'):
        return None
    return 100.0 * (1.0 - trace['busy_s'] / trace['window_s'])
