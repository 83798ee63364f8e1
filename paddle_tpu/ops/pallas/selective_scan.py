"""Selective scan (Mamba-1, arXiv:2312.00752) — Pallas TPU kernel for
the serving engine's recurrent layers.

    s_t = exp(dt_t * A) * s_{t-1} + (dt_t * x_t) (x) B_t
    y_t = s_t . C_t + D * x_t

for every row of one group of a dispatch, each row with its own number
of live tokens and its own slot in the per-request state array. ONE
body serves both groups of the mixed step: T = 1 decode rows (state in,
state out: bound by the 2 * N * Dn * 4 bytes a row it moves) and T = C
prompt-chunk rows (the recurrence runs inside VMEM, the state is read
and written once a chunk). Positions t >= q_len pass the state through
(their y is zero), so a padded chunk and an idle row leave it as it
was; a row whose first query sits at position 0 starts from zeros
whatever its slot held (`fresh`).

Layouts (Dn: channels, N: state size, both static):
  x, dt  float32 [R, T, Dn]   the conv'd input and softplus'd step
  B, C   float32 [R, T, N]    broadcast over 128 lanes by the wrapper:
                              the body multiplies [N, lanes] tiles, and
                              a [T, N] block would need a transpose a
                              token to put N on the sublanes
  A      float32 [N, Dn]      -exp(A_log), transposed: channels on the
                              lanes, the state's N rows on the sublanes
  D      float32 [1, Dn]
  state  float32 [S, N, Dn]   one slot a request, aliased in -> out; the
                              rows' slots are scalar-prefetched and pick
                              the block, so there is no gather, no
                              scatter and no copy of the other slots
  meta   int32 [R, 3]         (slot, q_len, fresh) a row
  -> y float32 [R, T, Dn], state

The grid is (rows, channel blocks); a channel block is the widest that
keeps the double-buffered blocks inside `_VMEM_BUDGET` (all Dn at the
cell's shapes: 64 programs of 655 KB each for the decode group). Inside
a program the channels go by `_LANES` at a time with that piece of the
state carried in registers over the row's live tokens. No two live rows
of a call share a slot (the engine's rows are requests); idle rows all
name the spare slot, whose content nobody reads.

On CPU the same body runs in interpret mode (tests); `ops/ssm.py`
routes between it and the plain `lax.scan`.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import scaffold

# channels one register-carried piece of the state spans: [N, 512]
# float32 is 8 vregs at N = 16
_LANES = 512
# the pipelined blocks (double-buffered) may take this much of VMEM
_VMEM_BUDGET = 40 * 2 ** 20


def _kernel(meta_ref, x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, si_ref,
            y_ref, so_ref, *, lanes):
    r = pl.program_id(0)
    q_len = meta_ref[r, 1]
    fresh = meta_ref[r, 2] > 0
    dn = x_ref.shape[-1]
    y_ref[...] = jnp.zeros_like(y_ref)
    for first in range(0, dn, lanes):
        sl = pl.ds(first, min(lanes, dn - first))
        reps = sl.size // scaffold.LANES
        a = a_ref[:, sl]
        d = d_ref[:, sl]

        def token(t, s, sl=sl, reps=reps, a=a, d=d):
            x = x_ref[pl.ds(t, 1), sl]
            dt = dt_ref[pl.ds(t, 1), sl]
            bt = jnp.concatenate([b_ref[t]] * reps, axis=-1)
            ct = jnp.concatenate([c_ref[t]] * reps, axis=-1)
            s = jnp.exp(dt * a) * s + (dt * x) * bt
            y_ref[pl.ds(t, 1), sl] = \
                jnp.sum(s * ct, axis=0, keepdims=True) + d * x
            return s
        s0 = jnp.where(fresh, 0.0, si_ref[:, sl])
        so_ref[:, sl] = jax.lax.fori_loop(0, q_len, token, s0)


def _channel_block(T, N, dn):
    """(channels a program, VMEM bytes): all of them, halved until the
    double-buffered blocks fit the budget (never below one piece)."""
    def need(blk):
        f32 = jnp.float32
        return 2 * (3 * scaffold.block_bytes((T, blk), f32)
                    + 2 * scaffold.block_bytes((T, N, scaffold.LANES), f32)
                    + 3 * scaffold.block_bytes((N, blk), f32)
                    + scaffold.block_bytes((1, blk), f32))
    blk = dn
    while need(blk) > _VMEM_BUDGET and blk % (2 * _LANES) == 0:
        blk //= 2
    return blk, need(blk)


@functools.partial(jax.jit, static_argnames=('interpret',))
def _scan_call(x, dt, B, C, A, D, state, meta, *, interpret):
    """The broadcast of B and C over the lanes and the Mosaic call, as
    one jitted function of the shapes: the model's layers share ONE
    trace of the body and ONE lowering a group shape."""
    R, T, dn = x.shape
    N = A.shape[0]
    lanes = scaffold.LANES
    blk, need = _channel_block(T, N, dn)
    bb = jnp.broadcast_to(B[..., None], (R, T, N, lanes))
    cb = jnp.broadcast_to(C[..., None], (R, T, N, lanes))
    tok = pl.BlockSpec((None, T, blk), lambda r, c, m: (r, 0, c))
    bc = pl.BlockSpec((None, T, N, lanes), lambda r, c, m: (r, 0, 0, 0))
    par = pl.BlockSpec((N, blk), lambda r, c, m: (0, c))
    row = pl.BlockSpec((1, blk), lambda r, c, m: (0, c))
    slot = pl.BlockSpec((None, N, blk), lambda r, c, m: (m[r, 0], 0, c))
    return scaffold.pallas_call(
        functools.partial(_kernel, lanes=min(_LANES, blk)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(R, dn // blk),
            in_specs=[tok, tok, bc, bc, par, row, slot],
            out_specs=[tok, slot]),
        out_shape=[jax.ShapeDtypeStruct((R, T, dn), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # meta, x, dt, B, C, A, D, state -> y, state
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary', 'arbitrary'),
            vmem_limit_bytes=int(min(need + 16 * 2 ** 20,
                                     scaffold.VMEM_CAP_BYTES))),
        interpret=interpret,
        name='selective_scan',
    )(meta, x, dt, bb, cb, A, D, state)


def selective_scan_pallas(x, dt, B, C, A, D, state, slots, q_lens, fresh,
                          interpret=None):
    """-> (y [R, T, Dn] float32, state). See the module docstring;
    `slots`, `q_lens` int32 [R], `fresh` bool [R]."""
    if x.shape[-1] % scaffold.LANES:
        raise ValueError(f'{x.shape[-1]} channels do not tile the '
                         f'{scaffold.LANES} lanes')
    meta = jnp.stack([slots.astype(jnp.int32), q_lens.astype(jnp.int32),
                      fresh.astype(jnp.int32)], axis=1)
    f32 = jnp.float32
    return _scan_call(
        x.astype(f32), dt.astype(f32), B.astype(f32), C.astype(f32),
        A.astype(f32), D.astype(f32).reshape(1, -1), state, meta,
        interpret=scaffold.interpret_mode() if interpret is None
        else interpret)
