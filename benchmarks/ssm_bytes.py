"""What the selective scan (Mosaic call `selective_scan`) has to move,
from counts, for its roofline share.

A (row, layer) update reads the row's state and writes it back: 2 x N x
Dn float32 (655,360 B at N 16, Dn 5120), whatever the row's tokens. A
token brings x, the step dt and y as float32 [Dn] each and B and C as
float32 [N] each. LEFT OUT, which can only lower the share: the 128-lane
broadcast the kernel reads B and C in (16 KB a token instead of 128 B),
the z gate (applied outside the kernel), A and D (read once a call),
and the spare slot's blocks that idle rows move. The operations (about
9 per state element and token) never bound a shape the server runs: the
least time is bytes over the HBM peak.
"""
from benchmarks import flops


def state_row_bytes(d_inner, d_state):
    """One (row, layer) update: the state in and out, float32."""
    return 2 * d_state * d_inner * 4


def token_bytes(d_inner, d_state):
    """One (token, layer): x, dt, y [Dn] and B, C [N], float32."""
    return (3 * d_inner + 2 * d_state) * 4


def least_seconds(rows, tokens, row_bytes, tok_bytes, device_kind):
    """The roofline of scan calls that made `rows` (row, layer) state
    updates over `tokens` (token, layer) positions."""
    return (rows * row_bytes + tokens * tok_bytes) \
        / (flops.peaks(device_kind)['hbm_gbps'] * 1e9)
