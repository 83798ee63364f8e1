"""device_idle_share.serve — layer: device. 100 x (1 - busy union of the
`XLA Ops` line / traced window), averaged over the chips: the host's
scheduling, sampling and bookkeeping between engine steps."""


def read(trace, facts):
    if not trace.get('chips'):
        return None
    return 100.0 * (1.0 - trace['busy_s'] / trace['window_s'])
