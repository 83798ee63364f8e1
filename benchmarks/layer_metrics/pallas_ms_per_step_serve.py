"""pallas_ms_per_step.serve — layer: Pallas kernels. Device time of the
Mosaic calls (ragged paged attention) in the traced engine steps, per
engine step, averaged over the chips."""


def read(trace, facts):
    if not trace.get('chips') or not facts.get('traced_steps'):
        return None
    return trace['pallas_s'] / facts['traced_steps'] * 1e3
