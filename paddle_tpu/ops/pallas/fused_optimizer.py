"""Fused one-pass optimizer step over flat buckets (TPP, arXiv:2104.05755).

The PR-4 bucketed engines already coalesced the optimizer phase into a
few flat, dtype-homogeneous 1-D buckets, but each bucket's update was
still a CHAIN of small XLA elementwise ops: unscale multiply, nonfinite
reduction, global-clip sum-of-squares, two moment updates, bias
corrections, the parameter step, the fp32-master cast-back — each a
separate HBM round-trip over the bucket. The two kernels here collapse
that chain into one read and one write per operand:

  * `grad_stats` — ONE pass over a gradient bucket producing the two
    scalars every step needs before it can touch the params: the
    global-clip sum-of-squares contribution and the nonfinite count
    (GradScaler found-inf). Accumulates across the sequential TPU grid
    into (1, 1) SMEM outputs.
  * `fused_shard_update` — ONE pass per bucket shard applying
    unscale/clip prefactor + decay-into-grad + the optimizer's own
    `update` rule + the found-inf no-op guard + the fp32-master
    cast-back, reading each state exactly once and writing each exactly
    twice (param dtype + master).

The update kernel is GENERIC over elementwise optimizers: the kernel
body calls `optimizer.update(p32, g32, state, lr)` directly — for an
elementwise rule that is pure jnp elementwise code, which Pallas traces
into the kernel like any other body. Vector states stream as row blocks
beside the params; scalar states (Adam beta powers) ride in a packed
(1, NS) fp32 SMEM array and their updated values are written through
(1, 1) SMEM outputs (every grid step writes the same value). Optimizers
opt in with `_pallas_fusible = True` (optimizer.py tags SGD, Momentum,
Adam/AdamW, Adamax, Adagrad, RMSProp, Adadelta, DecayedAdagrad);
anything untagged —
or non-elementwise — keeps the XLA chain and is counted as a fallback
route.

Numerics contract (tests/test_fused_primitives.py): in fp32 the fused
update is BIT-identical to `core.bucketing.shard_update` on the same
inputs — the kernel body runs the same ops in the same order, and
chunking a strictly-per-element rule cannot reorder anything. The one
place op order does change is `grad_stats`' sum-of-squares (blockwise
accumulation vs one whole-array reduction), so clip factors agree to
float tolerance, not bitwise.

Routing: `FLAGS_fused_optimizer` (None = auto: TPU kernel / CPU
reference), via scaffold.use_kernel — decisions are visible as
`ptpu_pallas_*_invocations_total{primitive='optimizer_step'|'grad_stats'}`.
"""
import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

from . import scaffold

STEP = 'optimizer_step'
STATS = 'grad_stats'
FLAG = 'FLAGS_fused_optimizer'


def fusible(optimizer):
    """Optimizers whose flat update may run inside the Pallas kernel:
    strictly elementwise AND tagged `_pallas_fusible` (the tag asserts
    the `update` body is pure jnp elementwise code with only scalar
    side states — verified by the parity tests)."""
    return bool(getattr(optimizer, '_elementwise', False)) and \
        bool(getattr(optimizer, '_pallas_fusible', False))


def use_fused_update(optimizer):
    return scaffold.use_kernel(STEP, FLAG, supported=fusible(optimizer))


def use_fused_stats():
    return scaffold.use_kernel(STATS, FLAG)


# ---------------------------------------------------------------------------
# grad_stats: one pass -> (sum of squares, nonfinite count)
# ---------------------------------------------------------------------------
def _stats_kernel(x_ref, sum_ref, cnt_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        sum_ref[0, 0] = 0.0
        cnt_ref[0, 0] = 0.0
    x = x_ref[...].astype(jnp.float32)
    # NOT masked: a nonfinite gradient must poison the sum exactly like
    # the unfused jnp.sum(g*g) does (the clip factor then trips the
    # numerics guards); the count reports it separately for found-inf
    sum_ref[0, 0] += jnp.sum(x * x)
    cnt_ref[0, 0] += jnp.sum((~jnp.isfinite(x)).astype(jnp.float32))


def grad_stats_pallas(flat):
    """(sum_sq fp32 scalar, nonfinite count fp32 scalar) of a flat
    array in one pass. Zero row-padding adds 0 to both."""
    x2 = scaffold.to_rows(flat.reshape(-1))
    rows = x2.shape[0]
    br = min(scaffold.ROW_BLOCK, rows)
    s, c = scaffold.pallas_call(
        _stats_kernel,
        grid=(rows // br,),
        in_specs=[scaffold.row_spec(br, scaffold.LANES)],
        out_specs=(scaffold.scalar_spec(), scaffold.scalar_spec()),
        out_shape=(jax.ShapeDtypeStruct((1, 1), jnp.float32),
                   jax.ShapeDtypeStruct((1, 1), jnp.float32)),
        interpret=scaffold.interpret_mode(), name='grad_stats',
    )(x2)
    return s[0, 0], c[0, 0]


# ---------------------------------------------------------------------------
# fused shard update
# ---------------------------------------------------------------------------
def _update_kernel(*refs, opt, vec_keys, scalar_keys, has_master,
                   use_pref, use_fi, wd):
    """One row block of the bucket shard: unscale/clip -> decay-into-grad
    -> optimizer.update -> found-inf guard -> param-dtype + master
    writes. Scalar layout in sc_ref: [lr, prefactor, found_inf,
    *scalar_states]."""
    n_vec = len(vec_keys)
    sc_ref, p_ref, g_ref = refs[0], refs[1], refs[2]
    k = 3
    master_ref = refs[k] if has_master else None
    k += 1 if has_master else 0
    vec_refs = refs[k:k + n_vec]
    outs = refs[k + n_vec:]

    lr = sc_ref[0, 0]
    g32 = g_ref[...].astype(jnp.float32)
    if use_pref:
        g32 = g32 * sc_ref[0, 1]
    p32 = master_ref[...] if has_master \
        else p_ref[...].astype(jnp.float32)
    if wd:
        g32 = g32 + wd * p32
    state = {key: r[...] for key, r in zip(vec_keys, vec_refs)}
    for j, key in enumerate(scalar_keys):
        state[key] = sc_ref[0, 3 + j]
    new32, ns = opt.update(p32, g32, state, lr)
    new_p = new32.astype(p_ref.dtype)
    if use_fi:
        skip = sc_ref[0, 2] > 0.5
        new_p = jnp.where(skip, p_ref[...], new_p)
        new32 = jnp.where(skip, p32, new32)
        ns = {key: jnp.where(skip, state[key], ns[key])
              for key in ns}
    o = 0
    outs[o][...] = new_p
    o += 1
    if has_master:
        outs[o][...] = new32
        o += 1
    for key in vec_keys:
        outs[o][...] = ns[key].astype(outs[o].dtype)
        o += 1
    for key in scalar_keys:
        outs[o][0, 0] = ns[key].astype(jnp.float32)
        o += 1


def fused_shard_update(optimizer, p_shard, g32_shard, st, lr,
                       prefactor=None, found_inf=None):
    """Drop-in fused twin of `core.bucketing.shard_update` (same
    signature and state contract), with the unscale/clip `prefactor`
    multiply and the GradScaler `found_inf` no-op guard folded into the
    same pass. Returns (new_p_shard, new_state)."""
    st = dict(st)
    master = st.pop('master', None)
    low = p_shard.dtype != jnp.float32
    has_master = master is not None or (
        low and getattr(optimizer, '_multi_precision', True))
    if master is None and has_master:
        master = p_shard.astype(jnp.float32)
    vec_keys = sorted(k for k in st if jnp.ndim(st[k]) >= 1)
    scalar_keys = sorted(k for k in st if jnp.ndim(st[k]) == 0)
    wd = getattr(optimizer, '_weight_decay', None)
    wd = float(wd) if (wd and optimizer._decay_into_grad()) else 0.0

    L = p_shard.shape[0]
    vecs = [p_shard, g32_shard] + ([master] if has_master else []) \
        + [st[k] for k in vec_keys]
    vecs2d = [scaffold.to_rows(v) for v in vecs]
    rows = vecs2d[0].shape[0]
    br = min(scaffold.ROW_BLOCK, rows)
    scalars = [jnp.asarray(lr, jnp.float32),
               jnp.asarray(1.0 if prefactor is None else prefactor,
                           jnp.float32),
               (jnp.asarray(found_inf).astype(jnp.float32)
                if found_inf is not None
                else jnp.asarray(0.0, jnp.float32))]
    scalars += [jnp.asarray(st[k], jnp.float32) for k in scalar_keys]
    sc = jnp.stack(scalars).reshape(1, -1)

    blk = scaffold.row_spec(br, scaffold.LANES)
    in_specs = [scaffold.scalar_spec()] + [blk] * len(vecs2d)
    out_specs = [blk] * (1 + (1 if has_master else 0) + len(vec_keys)) \
        + [scaffold.scalar_spec()] * len(scalar_keys)
    shp2d = vecs2d[0].shape
    out_shape = [jax.ShapeDtypeStruct(shp2d, p_shard.dtype)]
    if has_master:
        out_shape.append(jax.ShapeDtypeStruct(shp2d, jnp.float32))
    out_shape += [jax.ShapeDtypeStruct(shp2d, st[k].dtype)
                  for k in vec_keys]
    out_shape += [jax.ShapeDtypeStruct((1, 1), jnp.float32)
                  for _ in scalar_keys]

    kernel = functools.partial(
        _update_kernel, opt=optimizer, vec_keys=tuple(vec_keys),
        scalar_keys=tuple(scalar_keys), has_master=has_master,
        use_pref=prefactor is not None, use_fi=found_inf is not None,
        wd=wd)
    outs = scaffold.pallas_call(
        kernel,
        grid=(rows // br,),
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shape),
        interpret=scaffold.interpret_mode(), name='fused_shard_update',
    )(sc, *vecs2d)

    o = 0
    new_p = scaffold.from_rows(outs[o], L)
    o += 1
    ns = {}
    if has_master:
        ns['master'] = scaffold.from_rows(outs[o], L)
        o += 1
    for k in vec_keys:
        ns[k] = scaffold.from_rows(outs[o], L)
        o += 1
    for j, k in enumerate(scalar_keys):
        val = outs[o + j][0, 0]
        ns[k] = val.astype(jnp.asarray(st[k]).dtype)
    return new_p, ns
