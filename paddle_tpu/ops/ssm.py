"""State-space (Mamba-1) layer parts for the serving path: what runs
once over a group's tokens in XLA — the causal depthwise convolution
with its per-request tail, the step's softplus — and the routing of the
recurrence between the Pallas kernel (`ops/pallas/selective_scan.py`,
TPU) and a plain `lax.scan` (CPU, and the numerics oracle of the
kernel's tests). `FLAGS_selective_scan_kernel` forces either way.

A group is the rows of one shape of a dispatch (serving/protocol.py):
x [R, T, Dn] right-padded to T per row, `q_lens` [R] the live tokens,
`slots` [R] each row's slot in the per-request state arrays (an idle
row names the spare slot), `fresh` [R] where the row's first query sits
at position 0 and the state starts from zeros.
"""
import jax
import jax.numpy as jnp

from .pallas import scaffold
from .pallas import selective_scan as _kernel

F32 = jnp.float32


def causal_conv(x, tails, weight, bias, slots, q_lens, fresh):
    """Depthwise causal convolution over each row's tokens, continued
    from the row's tail (its last K-1 inputs before this dispatch).
    x [R, T, Dn]; tails [S, (K-1) * Dn] the per-slot array; weight
    [K, Dn] (tap K-1 multiplies the token itself); bias [Dn].
    -> (silu(conv + bias) [R, T, Dn] float32, tails): the tail is taken
    at the row's last live tokens, so positions >= q_len leave it as it
    was."""
    R, T, dn = x.shape
    K = weight.shape[0]
    tail = tails[slots].reshape(R, K - 1, dn)
    tail = jnp.where(fresh[:, None, None], 0, tail)
    seq = jnp.concatenate([tail.astype(x.dtype), x], axis=1)   # [R, K-1+T]
    wf = weight.astype(F32)
    out = sum(seq[:, k:k + T].astype(F32) * wf[k] for k in range(K))
    out = out + bias.astype(F32)
    # the K-1 inputs that end at the row's last live token
    at = q_lens[:, None] + jnp.arange(K - 1, dtype=jnp.int32)[None, :]
    new_tail = jnp.take_along_axis(seq, at[:, :, None], axis=1)
    tails = tails.at[slots].set(
        new_tail.reshape(R, -1).astype(tails.dtype), mode='drop')
    return out * jax.nn.sigmoid(out), tails


def selective_scan_ref(x, dt, B, C, A, D, state, slots, q_lens, fresh):
    """The recurrence as a `lax.scan` over the group's T positions, all
    rows at once: the kernel's contract (module docstring there), plain.
    """
    R, T, dn = x.shape
    s0 = jnp.where(fresh[:, None, None], 0.0, state[slots])    # [R, N, Dn]

    def token(s, inp):
        x_t, dt_t, b_t, c_t, t = inp            # [R, Dn] x2, [R, N] x2
        new = jnp.exp(dt_t[:, None, :] * A[None]) * s \
            + (dt_t * x_t)[:, None, :] * b_t[:, :, None]
        live = (t < q_lens)[:, None, None]
        s = jnp.where(live, new, s)
        y = jnp.sum(s * c_t[:, :, None], axis=1) + D.reshape(1, -1) * x_t
        return s, jnp.where(live[:, 0], y, 0.0)
    sT, y = jax.lax.scan(token, s0, (
        jnp.moveaxis(x.astype(F32), 1, 0), jnp.moveaxis(dt.astype(F32), 1, 0),
        jnp.moveaxis(B.astype(F32), 1, 0), jnp.moveaxis(C.astype(F32), 1, 0),
        jnp.arange(T, dtype=jnp.int32)))
    return jnp.moveaxis(y, 0, 1), state.at[slots].set(sT, mode='drop')


def use_kernel():
    return scaffold.use_kernel('selective_scan',
                               'FLAGS_selective_scan_kernel')


def selective_scan(x, dt, B, C, A, D, state, slots, q_lens, fresh):
    """Auto-routed: -> (y [R, T, Dn] float32, state [S, N, Dn])."""
    fn = _kernel.selective_scan_pallas if use_kernel() \
        else selective_scan_ref
    return fn(x, dt, B, C, A, D, state, slots, q_lens, fresh)
