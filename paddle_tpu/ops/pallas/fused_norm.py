"""Fused LayerNorm (forward + backward) — Pallas kernels on the shared
scaffolding (TPP, arXiv:2104.05755).

Forward: one pass per row block computing mean/rsqrt(var+eps) in fp32
and the affine epilogue in the input dtype — exactly the op order of
the `ops.nn_ops.layer_norm` reference (normalize in fp32, cast to the
input dtype, THEN scale/shift in the weight dtype), so fp32 outputs
agree to float tolerance and the bf16 cast points match. mean and rstd
are emitted as [rows, 1] residuals for the backward.

Backward (`jax.custom_vjp`): a second one-pass kernel produces dx per
row block from the saved mean/rstd (no recompute of the reductions):

    dx = rstd * (dxhat - mean(dxhat) - xhat * mean(dxhat * xhat))

while dweight/dbias accumulate across the sequential row grid in VMEM
scratch ([1, N] each) and are written once by the last program — the
whole backward is one read of x/dy and one write of dx/dw/db, where the
XLA autodiff of the reference materializes xhat twice and runs three
separate reductions.

Shape contract: normalization over the LAST axis only, with both weight
and bias present (the GPT/BERT LayerNorm shape); `ops.nn_ops.layer_norm`
routes here for that case and keeps the jnp path otherwise. Rows that
don't divide the block size are zero-padded (pad rows see dy = 0, so
they contribute nothing to dw/db and their dx is sliced off).

Routing: `FLAGS_fused_layer_norm` (None = auto: TPU kernel, CPU
reference), recorded as primitive 'layer_norm'.
"""
import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import scaffold

PRIMITIVE = 'layer_norm'
FLAG = 'FLAGS_fused_layer_norm'
# row block: LN rows are [*, hidden] slabs, keep blocks modest so the
# dw/db scratch + x/dy/dx blocks fit VMEM at hidden ~8k
ROW_BLOCK = 128


def use_fused(supported=True):
    return scaffold.use_kernel(PRIMITIVE, FLAG, supported=supported)


def _fwd_kernel(x_ref, w_ref, b_ref, o_ref, mean_ref, rstd_ref, *, eps):
    xf = x_ref[...].astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mean) ** 2, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    out = ((xf - mean) * rstd).astype(x_ref.dtype)
    o_ref[...] = out * w_ref[...] + b_ref[...]
    mean_ref[...] = mean
    rstd_ref[...] = rstd


def _bwd_kernel(x_ref, w_ref, dy_ref, mean_ref, rstd_ref,
                dx_ref, dw_ref, db_ref, dw_s, db_s):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        dw_s[...] = jnp.zeros_like(dw_s)
        db_s[...] = jnp.zeros_like(db_s)
    xf = x_ref[...].astype(jnp.float32)
    mean = mean_ref[...]
    rstd = rstd_ref[...]
    xhat = (xf - mean) * rstd
    dyf = dy_ref[...].astype(jnp.float32)
    dxhat = dyf * w_ref[...].astype(jnp.float32)
    m1 = jnp.mean(dxhat, axis=-1, keepdims=True)
    m2 = jnp.mean(dxhat * xhat, axis=-1, keepdims=True)
    dx_ref[...] = (rstd * (dxhat - m1 - xhat * m2)).astype(dx_ref.dtype)
    # the forward multiplies w by xhat CAST to the input dtype; route
    # dw through the same cast point so bf16 grads match the reference
    xhat_c = xhat.astype(x_ref.dtype).astype(jnp.float32)
    dw_s[...] += jnp.sum(dyf * xhat_c, axis=0, keepdims=True)
    db_s[...] += jnp.sum(dyf, axis=0, keepdims=True)

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        dw_ref[...] = dw_s[...]
        db_ref[...] = db_s[...]


def _fwd_pallas(x2, w, b, eps):
    R, N = x2.shape
    br = scaffold.pick_block_rows(N, ROW_BLOCK)
    xp = scaffold.pad_rows(x2, br)
    rows = xp.shape[0]
    o, mean, rstd = scaffold.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=(rows // br,),
        in_specs=[scaffold.row_spec(br, N), scaffold.bcast_spec(1, N),
                  scaffold.bcast_spec(1, N)],
        out_specs=(scaffold.row_spec(br, N), scaffold.row_spec(br, 1),
                   scaffold.row_spec(br, 1)),
        out_shape=(jax.ShapeDtypeStruct((rows, N), x2.dtype),
                   jax.ShapeDtypeStruct((rows, 1), jnp.float32),
                   jax.ShapeDtypeStruct((rows, 1), jnp.float32)),
        interpret=scaffold.interpret_mode(), name='layer_norm_fwd',
    )(xp, w.reshape(1, N), b.reshape(1, N))
    return o[:R], mean, rstd


def _bwd_pallas(x2, w, dy2, mean, rstd):
    R, N = x2.shape
    # same block choice as the forward: mean/rstd were saved at the
    # forward's padded length
    br = scaffold.pick_block_rows(N, ROW_BLOCK)
    xp = scaffold.pad_rows(x2, br)
    dyp = scaffold.pad_rows(dy2, br)
    rows = xp.shape[0]
    dx, dw, db = scaffold.pallas_call(
        _bwd_kernel,
        grid=(rows // br,),
        in_specs=[scaffold.row_spec(br, N), scaffold.bcast_spec(1, N),
                  scaffold.row_spec(br, N), scaffold.row_spec(br, 1),
                  scaffold.row_spec(br, 1)],
        out_specs=(scaffold.row_spec(br, N), scaffold.bcast_spec(1, N),
                   scaffold.bcast_spec(1, N)),
        out_shape=(jax.ShapeDtypeStruct((rows, N), x2.dtype),
                   jax.ShapeDtypeStruct((1, N), jnp.float32),
                   jax.ShapeDtypeStruct((1, N), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((1, N), jnp.float32),
                        pltpu.VMEM((1, N), jnp.float32)],
        interpret=scaffold.interpret_mode(), name='layer_norm_bwd',
    )(xp, w.reshape(1, N), dyp, mean, rstd)
    return dx[:R], dw.reshape(N), db.reshape(N)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_layer_norm(x, weight, bias, eps):
    """Array-level entry: x [..., N], weight/bias [N]; normalization
    over the last axis. Differentiable in x, weight, bias."""
    o, _, _ = _ln_fwd_impl(x, weight, bias, eps)
    return o


def _ln_fwd_impl(x, weight, bias, eps):
    shape = x.shape
    N = shape[-1]
    x2 = x.reshape(-1, N)
    o, mean, rstd = _fwd_pallas(x2, weight, bias, eps)
    return o.reshape(shape), mean, rstd


def _ln_fwd(x, weight, bias, eps):
    o, mean, rstd = _ln_fwd_impl(x, weight, bias, eps)
    return o, (x, weight, bias, mean, rstd)


def _ln_bwd(eps, res, g):
    x, weight, bias, mean, rstd = res
    shape = x.shape
    N = shape[-1]
    dx2, dw, db = _bwd_pallas(x.reshape(-1, N), weight,
                              g.reshape(-1, N), mean, rstd)
    return (dx2.reshape(shape), dw.astype(weight.dtype),
            db.astype(bias.dtype))


fused_layer_norm.defvjp(_ln_fwd, _ln_bwd)
