"""Fused elementwise transformer blocks — bias+GELU and
dropout+residual-add — on the shared Pallas scaffolding (TPP,
arXiv:2104.05755).

bias_gelu: y = gelu(x + bias). The forward kernel computes the add in
the INPUT dtype and the tanh-form activation via `jax.nn.gelu` traced
into the kernel body — the same expression the reference path runs, so
routes agree at the bf16 cast points; the exact (erf) form runs an fp32
rational-polynomial erf, which Pallas TPU cannot lower from lax.erf. The backward kernel recomputes u = x + b
once, applies the analytic gelu derivative in fp32, streams dx out per
row block, and accumulates dbias across the sequential grid in VMEM
scratch (one pass; XLA autodiff instead re-materializes tanh and runs a
separate reduction).

dropout_add: y = where(keep, x / (1-p), 0) + residual (paddle's
upscale_in_train). The keep mask is drawn OUTSIDE the kernel with the
same `jax.random.bernoulli(key, 1-p, shape)` the reference dropout
uses — stateless threefry keys give fused and reference routes the
SAME drop pattern for the same RNG stream (values agree to 1 ulp; XLA
contracts the divide/add chain differently inside one kernel body),
and the kernel fuses the select + scale + residual add into one pass
(backward: one masked scale, d(residual) = g). The mask travels as
fp32 0/1 so the custom VJP has a well-formed (zero) cotangent slot
for it.

Routing: `FLAGS_fused_elementwise` (None = auto), recorded as
primitives 'bias_gelu' and 'dropout_add'. `ops.nn_ops` owns the
functional entries (`bias_gelu`, `dropout_add`) that route here.
"""
import functools
import math

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import scaffold

FLAG = 'FLAGS_fused_elementwise'
ROW_BLOCK = 128


def use_fused(primitive, supported=True):
    return scaffold.use_kernel(primitive, FLAG, supported=supported)


def _gelu_grad(u, approximate):
    """d gelu(u) / du in fp32 (u fp32)."""
    if approximate:
        c = math.sqrt(2.0 / math.pi)
        inner = c * (u + 0.044715 * u ** 3)
        t = jnp.tanh(inner)
        return 0.5 * (1.0 + t) + 0.5 * u * (1.0 - t * t) * c * \
            (1.0 + 3 * 0.044715 * u ** 2)
    phi = jnp.exp(-0.5 * u * u) * (1.0 / math.sqrt(2.0 * math.pi))
    cdf = 0.5 * (1.0 + _erf(u * (1.0 / math.sqrt(2.0))))
    return cdf + u * phi


def _erf(x):
    """fp32 erf as a clamped rational polynomial x*P(x^2)/Q(x^2) (the
    Eigen/XLA single-precision form, max abs error 2.3e-7 — the same as
    lax.erf's own). Pallas TPU has no lowering for lax.erf / lax.erfc
    ("Unimplemented primitive ... erf"), so the exact-GELU kernels
    carry it as plain VPU mul/add/div."""
    x = jnp.clip(x, -4.0, 4.0)
    x2 = x * x
    p = jnp.float32(-2.72614225801306e-10)
    for c in (2.77068142495902e-08, -2.10102402082508e-06,
              -5.69250639462346e-05, -7.34990630326855e-04,
              -2.95459980854025e-03, -1.60960333262415e-02):
        p = p * x2 + jnp.float32(c)
    q = jnp.float32(-1.45660718464996e-05)
    for c in (-2.13374055278905e-04, -1.68282697438203e-03,
              -7.37332916720468e-03, -1.42647390514189e-02):
        q = q * x2 + jnp.float32(c)
    return x * p / q


def _gelu(u, approximate):
    """gelu(u) in u's dtype: the tanh form is jax.nn.gelu itself (the
    reference route's expression); the exact form goes through `_erf`
    in fp32."""
    if approximate:
        return jax.nn.gelu(u, approximate=True)
    u32 = u.astype(jnp.float32)
    return (0.5 * u32 * (1.0 + _erf(u32 * (1.0 / math.sqrt(2.0))))) \
        .astype(u.dtype)


# ---------------------------------------------------------------------------
# bias + gelu
# ---------------------------------------------------------------------------
def _bg_fwd_kernel(x_ref, b_ref, o_ref, *, approximate):
    o_ref[...] = _gelu(x_ref[...] + b_ref[...], approximate)


def _bg_bwd_kernel(x_ref, b_ref, dy_ref, dx_ref, db_ref, db_s, *,
                   approximate):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        db_s[...] = jnp.zeros_like(db_s)
    u = (x_ref[...] + b_ref[...]).astype(jnp.float32)
    du = dy_ref[...].astype(jnp.float32) * _gelu_grad(u, approximate)
    dx_ref[...] = du.astype(dx_ref.dtype)
    db_s[...] += jnp.sum(du, axis=0, keepdims=True)

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        db_ref[...] = db_s[...]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def bias_gelu(x, bias, approximate):
    """Array-level fused entry: x [..., N], bias [N]."""
    return _bg_fwd_impl(x, bias, approximate)


def _bg_fwd_impl(x, bias, approximate):
    shape = x.shape
    N = shape[-1]
    br = scaffold.pick_block_rows(N, ROW_BLOCK)
    x2 = scaffold.pad_rows(x.reshape(-1, N), br)
    rows = x2.shape[0]
    o = scaffold.pallas_call(
        functools.partial(_bg_fwd_kernel, approximate=approximate),
        grid=(rows // br,),
        in_specs=[scaffold.row_spec(br, N), scaffold.bcast_spec(1, N)],
        out_specs=scaffold.row_spec(br, N),
        out_shape=jax.ShapeDtypeStruct((rows, N), x.dtype),
        interpret=scaffold.interpret_mode(), name='bias_gelu_fwd',
    )(x2, bias.astype(x.dtype).reshape(1, N))
    R = x.reshape(-1, N).shape[0]
    return o[:R].reshape(shape)


def _bg_fwd(x, bias, approximate):
    return _bg_fwd_impl(x, bias, approximate), (x, bias)


def _bg_bwd(approximate, res, g):
    x, bias = res
    shape = x.shape
    N = shape[-1]
    br = scaffold.pick_block_rows(N, ROW_BLOCK)
    x2 = scaffold.pad_rows(x.reshape(-1, N), br)
    dy2 = scaffold.pad_rows(g.reshape(-1, N), br)
    rows = x2.shape[0]
    dx, db = scaffold.pallas_call(
        functools.partial(_bg_bwd_kernel, approximate=approximate),
        grid=(rows // br,),
        in_specs=[scaffold.row_spec(br, N), scaffold.bcast_spec(1, N),
                  scaffold.row_spec(br, N)],
        out_specs=(scaffold.row_spec(br, N), scaffold.bcast_spec(1, N)),
        out_shape=(jax.ShapeDtypeStruct((rows, N), x.dtype),
                   jax.ShapeDtypeStruct((1, N), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((1, N), jnp.float32)],
        interpret=scaffold.interpret_mode(), name='bias_gelu_bwd',
    )(x2, bias.astype(x.dtype).reshape(1, N), dy2)
    R = x.reshape(-1, N).shape[0]
    return dx[:R].reshape(shape), db.reshape(N).astype(bias.dtype)


bias_gelu.defvjp(_bg_fwd, _bg_bwd)


def bias_gelu_reference(x, bias, approximate):
    """The unfused jnp path — identical expression to nn.Linear's
    bias-add followed by ops.nn_ops.gelu."""
    return jax.nn.gelu(x + bias.astype(x.dtype), approximate=approximate)


# ---------------------------------------------------------------------------
# dropout + residual add
# ---------------------------------------------------------------------------
def _da_fwd_kernel(x_ref, r_ref, m_ref, o_ref, *, keep_prob):
    x = x_ref[...]
    dropped = jnp.where(m_ref[...] > 0.5, x / keep_prob,
                        jnp.zeros_like(x)).astype(x.dtype)
    o_ref[...] = dropped + r_ref[...]


def _da_bwd_kernel(m_ref, dy_ref, dx_ref, *, keep_prob):
    dy = dy_ref[...]
    dx_ref[...] = jnp.where(m_ref[...] > 0.5, dy / keep_prob,
                            jnp.zeros_like(dy)).astype(dx_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def dropout_add(x, residual, mask, p):
    """y = upscale-dropout(x) + residual; mask is the fp32 0/1 keep
    mask (drawn by the caller so fused and reference routes share the
    exact bernoulli draw)."""
    return _da_fwd_impl(x, residual, mask, p)


def _da_call(name, kernel, args, shape, dtype, n_in):
    N = shape[-1]
    rows = args[0].shape[0]
    br = scaffold.pick_block_rows(N, ROW_BLOCK)
    return scaffold.pallas_call(
        kernel,
        grid=(rows // br,),
        in_specs=[scaffold.row_spec(br, N)] * n_in,
        out_specs=scaffold.row_spec(br, N),
        out_shape=jax.ShapeDtypeStruct((rows, N), dtype),
        interpret=scaffold.interpret_mode(), name=name,
    )(*args)


def _da_fwd_impl(x, residual, mask, p):
    shape = x.shape
    N = shape[-1]
    br = scaffold.pick_block_rows(N, ROW_BLOCK)
    pad = lambda a: scaffold.pad_rows(a.reshape(-1, N), br)
    o = _da_call('dropout_add_fwd',
                 functools.partial(_da_fwd_kernel, keep_prob=1.0 - p),
                 [pad(x), pad(residual), pad(mask)], shape, x.dtype, 3)
    R = x.reshape(-1, N).shape[0]
    return o[:R].reshape(shape)


def _da_fwd(x, residual, mask, p):
    return _da_fwd_impl(x, residual, mask, p), mask


def _da_bwd(p, mask, g):
    shape = g.shape
    N = shape[-1]
    br = scaffold.pick_block_rows(N, ROW_BLOCK)
    pad = lambda a: scaffold.pad_rows(a.reshape(-1, N), br)
    dx = _da_call('dropout_add_bwd',
                  functools.partial(_da_bwd_kernel, keep_prob=1.0 - p),
                  [pad(mask), pad(g)], shape, g.dtype, 2)
    R = g.reshape(-1, N).shape[0]
    return dx[:R].reshape(shape), g, jnp.zeros_like(mask)


dropout_add.defvjp(_da_fwd, _da_bwd)


def dropout_add_reference(x, residual, mask, p):
    """The unfused jnp path — the exact expression ops.nn_ops.dropout
    (upscale_in_train) followed by the residual add runs."""
    return jnp.where(mask > 0.5, x / (1.0 - p),
                     jnp.zeros_like(x)).astype(x.dtype) + residual
