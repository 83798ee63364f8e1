"""pallas_ms_per_step.train — layer: Pallas kernels. Device time of the
Mosaic calls (`tpu_custom_call` on the `XLA Ops` line) in the traced
steps, per optimizer step, averaged over the chips."""


def read(trace, facts):
    if not trace.get('chips') or not facts.get('traced_steps'):
        return None
    return trace['pallas_s'] / facts['traced_steps'] * 1e3
