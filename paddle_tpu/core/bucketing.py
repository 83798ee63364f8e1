"""Gradient bucketing + cross-replica sharded weight update.

Reference parity: the role of imperative/reducer.cc's gradient Group
fusion (fuse_grad_size_in_MB coalescing before FusedAllReduce) and
DygraphShardingOptimizer's reduce-scatter/broadcast vocabulary — rebuilt
TPU-native per Xu et al., "Automatic Cross-Replica Sharding of Weight
Update in Data-Parallel Training" (arXiv:2004.13336):

  * gradients are coalesced into a small number of dtype-homogeneous
    1-D **buckets** (size-capped, zero-padded, with a stable
    param -> (bucket, offset) layout map);
  * each bucket is communicated with ONE `reduce_scatter` over the
    data-parallel mesh axes instead of one `psum` per parameter;
  * every rank owns a 1/dp **shard** of each bucket's parameters and
    optimizer moments (ZeRO-1/2 semantics), applies the optimizer update
    on its shard only, and `all_gather`s the updated parameters;
  * an opt-in compressed-collective mode (EQuARX, arXiv:2506.17615)
    sends the reduce-scatter payload compressed but ACCUMULATES in
    fp32 (all_to_all + local fp32 sum — the paper's accuracy note: the
    wire is compressed, the reduction is not). `comm_dtype='bfloat16'`
    is a plain cast; `comm_dtype='int8'` is BLOCK-SCALED: per-block
    abs-max fp32 scales ride beside the int8 payload on the wire
    (`quantize_blocks`), the param refresh all-gathers int8 shards +
    scales the same way, and the `ptpu_comm_*` gauges count the real
    wire bytes — payload, scales and padding reported separately
    (docs/performance.md#int8-wire).

Everything here is either host-side layout bookkeeping or pure
traced-code helpers used inside the engines' `shard_map` bodies; the
only state is the monitor gauges (`ptpu_comm_*`).

Communication/compute overlap (ISSUE 10, arXiv:2004.13336 §overlap +
arXiv:2112.01075 chunked collectives):

  * **layer-grouped buckets** — `layer_group_fn` keys buckets on the
    model-layer index parsed from the parameter name, so a bucket's
    gradients are complete as soon as its layers' backward finishes
    and its reduce-scatter is schedulable under the remaining
    backward compute (one dtype-global blob serializes everything
    behind the full backward);
  * **chunked collectives** — `reduce_scatter`/`all_gather` accept a
    `chunk` element cap (`PTPU_COMM_CHUNK`) that decomposes an
    oversized bucket's collective into schedulable pieces along the
    shard dimension; piece results concatenate to the EXACT unchunked
    shard/gather layout (bit-identical for uncompressed wires);
  * **overlap telemetry** — `publish_overlap_gauges` models per-step
    exposed vs hidden comm seconds (`ptpu_comm_overlap_*`), emits one
    profiler span per group, and `comm_snapshot()['comm_overlap']`
    is the JSON view the bench/dryrun records carry.
"""
import math
import re

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

# int8 symmetric range: scale = blockmax / 127, values clipped to ±127
INT8_BIN = 127.0
# per-block scale granularity for the int8 wire (elements); must
# divide the per-rank shard length, so the effective block per bucket
# is the largest divisor of shard_len <= this (env PTPU_COMM_BLOCK)
DEFAULT_COMM_BLOCK = 256
# scales travel as fp32 beside the int8 payload
SCALE_ITEMSIZE = 4
# deferred-gather prefetch window: how many param groups may be
# in flight (gathered but not yet consumed) ahead of first use
DEFAULT_PREFETCH_DEPTH = 2
# modeled per-rank interconnect bandwidth for the exposed/hidden comm
# model (v5e ICI-class, one direction) — a MODEL constant like the
# byte gauges, not a measurement
MODELED_ICI_BYTES_PER_S = 4.5e10


def resolve_comm_config(comm_dtype=None, bucket_mb=None):
    """Gradient-communication knobs, resolved kwarg -> env -> fleet
    strategy -> default (strategy.comm_dtype / fuse_grad_size_in_MB)."""
    import os
    strategy = None
    try:
        from ..distributed.fleet import fleet as _fleet
        strategy = _fleet._user_defined_strategy
    except Exception:
        strategy = None
    if comm_dtype is None:
        comm_dtype = os.environ.get('PTPU_COMM_DTYPE') or None
    if comm_dtype is None and strategy is not None:
        comm_dtype = strategy.comm_dtype
    if comm_dtype is not None:
        comm_dtype = jnp.dtype(comm_dtype)
    if bucket_mb is None:
        bucket_mb = float(os.environ.get('PTPU_BUCKET_MB', 0) or 0) or None
    if bucket_mb is None and strategy is not None:
        bucket_mb = float(strategy.fuse_grad_size_in_MB)
    if bucket_mb is None:
        bucket_mb = 32.0
    return comm_dtype, int(bucket_mb * 1024 * 1024)


def resolve_comm_block(block=None):
    """Block-scale granularity for the int8 wire, kwarg -> env ->
    default."""
    import os
    if block is None:
        block = int(os.environ.get('PTPU_COMM_BLOCK', 0) or 0) or None
    if block is None:
        block = DEFAULT_COMM_BLOCK
    return max(int(block), 1)


def resolve_overlap_config(overlap=None, prefetch=None, chunk=None):
    """Communication-overlap knobs, resolved kwarg -> env -> fleet
    strategy -> default:

      overlap  : bool — layer-grouped buckets + eager reduce-scatter +
                 deferred/prefetched param all-gather
                 (`PTPU_COMM_OVERLAP` / sharding_configs['comm_overlap']
                 / engine kwarg `comm_overlap`);
      prefetch : int — deferred-gather prefetch depth, groups in
                 flight ahead of first use (`PTPU_COMM_PREFETCH` /
                 sharding_configs['comm_overlap_prefetch'] /
                 engine kwarg `prefetch_depth`);
      chunk    : int — max full-bucket elements per collective
                 (`PTPU_COMM_CHUNK` / sharding_configs['comm_chunk'] /
                 engine kwarg `comm_chunk`; 0 = unchunked).
    """
    import os
    sc = {}
    try:
        from ..distributed.fleet import fleet as _fleet
        strategy = _fleet._user_defined_strategy
        if strategy is not None:
            sc = strategy.sharding_configs or {}
    except Exception:
        sc = {}
    if overlap is None:
        v = os.environ.get('PTPU_COMM_OVERLAP')
        if v is not None and v != '':
            overlap = v.lower() in ('1', 'true', 'yes')
    if overlap is None:
        overlap = sc.get('comm_overlap', False)
    # a PRESENT env var wins over the strategy even when its value is
    # falsy — PTPU_COMM_CHUNK=0 must be able to switch chunking off
    if prefetch is None:
        v = os.environ.get('PTPU_COMM_PREFETCH')
        if v is not None and v != '':
            prefetch = int(v)
    if prefetch is None:
        prefetch = sc.get('comm_overlap_prefetch')
    if prefetch is None:
        prefetch = DEFAULT_PREFETCH_DEPTH
    if chunk is None:
        v = os.environ.get('PTPU_COMM_CHUNK')
        if v is not None and v != '':
            chunk = int(v)
    if chunk is None:
        chunk = sc.get('comm_chunk')
    if chunk is None:
        chunk = 0
    return bool(overlap), max(int(prefetch), 1), max(int(chunk), 0)


# model-layer index: first numeric dotted path component of the
# parameter name ('gpt.decoder.layers.3.linear1.weight' -> 3)
_LAYER_IDX_RE = re.compile(r'(?:^|\.)(\d+)(?:\.|$)')


def layer_group_fn(name, shape=None, dtype=None):
    """Bucket grouping key for layer-grouped buckets: the FIRST numeric
    path component of the dotted parameter name (model layer / block
    order), 'stem' when the name carries none (embeddings, final
    norms, heads). Zero-padded so group keys sort in layer order."""
    m = _LAYER_IDX_RE.search(name)
    return f'layer{int(m.group(1)):05d}' if m else 'stem'


def ensure_overlap_xla_flags():
    """Best-effort XLA scheduling flags for comm/compute overlap: the
    latency-hiding scheduler + async collective fusion. LIBTPU_INIT_ARGS
    is read once at backend initialization and engine builds run after
    it, so for the flags to reach THIS process's compiler the
    launcher must export PTPU_COMM_OVERLAP=1 — core/flags.py honors
    that at first import, before any backend exists. This call (from
    an engine build) records the intent in the flags registry and
    updates the env for CHILD processes; explicit user settings (True
    or False) are respected and never overridden."""
    from . import flags as _flags
    want = {}
    for k in ('FLAGS_xla_latency_hiding_scheduler',
              'FLAGS_xla_async_collectives'):
        if _flags.flag(k) is None:
            want[k] = True
    if want:
        _flags.set_flags(want)


def block_len(n, want):
    """Largest divisor of `n` that is <= `want` — the effective scale
    block for a flat array of length n (blocks must tile the array and
    must not cross shard boundaries, so callers pass the SHARD
    length)."""
    b = min(int(want), int(n))
    while b > 1 and n % b:
        b -= 1
    return max(b, 1)


# ---------------------------------------------------------------------------
# layout
# ---------------------------------------------------------------------------
class Slot:
    """One parameter's place inside a bucket."""
    __slots__ = ('name', 'shape', 'dtype', 'bucket', 'offset', 'size')

    def __init__(self, name, shape, dtype, bucket, offset, size):
        self.name = name
        self.shape = tuple(shape)
        self.dtype = jnp.dtype(dtype)
        self.bucket = bucket
        self.offset = offset
        self.size = size

    def to_dict(self):
        return {'name': self.name, 'shape': list(self.shape),
                'dtype': str(self.dtype), 'bucket': self.bucket,
                'offset': self.offset, 'size': self.size}


class Bucket:
    __slots__ = ('index', 'dtype', 'group', 'slots', 'used', 'size')

    def __init__(self, index, dtype, group):
        self.index = index
        self.dtype = jnp.dtype(dtype)
        self.group = group
        self.slots = []
        self.used = 0      # elements occupied by real parameters
        self.size = 0      # padded length (set at finalize)

    @property
    def pad(self):
        return self.size - self.used

    def nbytes(self, dtype=None):
        return self.size * jnp.dtype(dtype or self.dtype).itemsize


class BucketLayout:
    """Stable param -> (bucket, offset) map over dtype-homogeneous,
    size-capped, padded 1-D buckets.

    Built from an ORDERED {name: (shape, dtype)} description of the
    LOCAL (per-rank) parameter arrays; the greedy fill preserves
    insertion order, opens a new bucket when the byte cap would be
    exceeded (a single parameter larger than the cap gets its own
    bucket), and pads every bucket to a multiple of `pad_to` so a
    1/pad_to shard is always an integral slice.
    """

    def __init__(self, buckets, slots, pad_to):
        self.buckets = buckets
        self.slots = slots
        self.pad_to = pad_to

    @classmethod
    def build(cls, named_shapes, bucket_bytes=32 * 1024 * 1024, pad_to=1,
              group_fn=None):
        """named_shapes: ordered {name: (shape, dtype)}."""
        pad_to = max(int(pad_to), 1)
        buckets, slots = [], {}
        open_by_key = {}
        for name, (shape, dtype) in named_shapes.items():
            dtype = jnp.dtype(dtype)
            group = group_fn(name, shape, dtype) if group_fn else None
            size = int(np.prod(shape)) if len(shape) else 1
            key = (group, str(dtype))
            b = open_by_key.get(key)
            if b is not None and \
                    (b.used + size) * dtype.itemsize > bucket_bytes \
                    and b.used > 0:
                b = None   # cap exceeded: close it
            if b is None:
                b = Bucket(len(buckets), dtype, group)
                buckets.append(b)
                open_by_key[key] = b
            slot = Slot(name, shape, dtype, b.index, b.used, size)
            b.slots.append(slot)
            slots[name] = slot
            b.used += size
        for b in buckets:
            b.size = int(math.ceil(b.used / pad_to) * pad_to)
        return cls(buckets, slots, pad_to)

    # -- flatten / unflatten (pure; usable under jit and on host) -----------
    def flatten(self, tree, cast=None):
        """{name: array} -> [one 1-D padded array per bucket]."""
        out = []
        for b in self.buckets:
            parts = [jnp.reshape(tree[s.name], (-1,)).astype(cast or b.dtype)
                     for s in b.slots]
            if b.pad:
                parts.append(jnp.zeros((b.pad,), cast or b.dtype))
            out.append(parts[0] if len(parts) == 1
                       else jnp.concatenate(parts))
        return out

    def unflatten(self, flats, cast_slots=False):
        """[per-bucket 1-D arrays] -> {name: array of slot shape}."""
        tree = {}
        for b, flat in zip(self.buckets, flats):
            for s in b.slots:
                a = lax.slice_in_dim(flat, s.offset, s.offset + s.size)
                if cast_slots:
                    a = a.astype(s.dtype)
                tree[s.name] = jnp.reshape(a, s.shape)
        return tree

    def names(self):
        return list(self.slots)

    def total_elements(self):
        return sum(s.size for s in self.slots.values())

    def total_padded(self):
        return sum(b.size for b in self.buckets)

    def nbytes(self, dtype=None):
        return sum(b.nbytes(dtype) for b in self.buckets)

    def describe(self):
        """JSON-ready layout map (the stable param->(bucket,offset)
        contract, round-trippable by tests/tools)."""
        return {
            'pad_to': self.pad_to,
            'buckets': [{'index': b.index, 'dtype': str(b.dtype),
                         'group': b.group if b.group is None
                         else str(b.group),
                         'used': b.used, 'size': b.size,
                         'slots': [s.to_dict() for s in b.slots]}
                        for b in self.buckets],
        }


# ---------------------------------------------------------------------------
# block-scaled int8 quantization (pure; used inside shard_map bodies)
# ---------------------------------------------------------------------------
def quantize_blocks(flat, block):
    """Symmetric abs-max int8 quantization of a 1-D array in blocks of
    `block` elements (must divide len(flat)). Returns (int8 [L],
    fp32 scales [L // block]); dequantized value = q * scale."""
    blk = flat.astype(jnp.float32).reshape(-1, block)
    amax = jnp.max(jnp.abs(blk), axis=1, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / INT8_BIN
    q = jnp.clip(jnp.round(blk / scale), -INT8_BIN, INT8_BIN) \
        .astype(jnp.int8)
    return q.reshape(-1), scale.reshape(-1)


def dequantize_blocks(q, scales, block):
    """Inverse of quantize_blocks (fp32 result)."""
    blk = q.reshape(-1, block).astype(jnp.float32)
    return (blk * scales.reshape(-1, 1)).reshape(-1)


def _is_int8(comm_dtype):
    return comm_dtype is not None and jnp.dtype(comm_dtype) == jnp.int8


# ---------------------------------------------------------------------------
# collectives over buckets (call inside shard_map bodies)
# ---------------------------------------------------------------------------
def axes_size(mesh, axes):
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def shard_index(axes):
    """Combined shard index over `axes` (major-to-minor in the given
    order — matches `lax.psum_scatter` over the same axis tuple and a
    PartitionSpec placing `tuple(axes)` on dim 0)."""
    idx = jnp.asarray(0, jnp.int32)
    for a in axes:
        idx = idx * lax.psum(1, a) + lax.axis_index(a)
    return idx


def take_shard(flat, axes, n_shards):
    """Slice this rank's 1/n shard out of a (replicated) flat bucket."""
    shard_len = flat.shape[0] // n_shards
    return lax.dynamic_slice_in_dim(
        flat, shard_index(axes) * shard_len, shard_len, axis=0)


def _chunk_spans(shard_len, n_shards, chunk):
    """Split points for chunked collectives (arXiv:2112.01075): `chunk`
    caps the FULL-bucket elements per collective, so the piece width
    along the shard dimension is chunk // n_shards. Returns a list of
    (start, width) spans over [0, shard_len), or None when chunking is
    off / the bucket already fits one chunk."""
    if not chunk or n_shards < 1:
        return None
    w = max(int(chunk) // max(int(n_shards), 1), 1)
    if shard_len <= w:
        return None
    spans, s = [], 0
    while s < shard_len:
        spans.append((s, min(w, shard_len - s)))
        s += spans[-1][1]
    return spans


def reduce_scatter(flat, axes, n_shards, comm_dtype=None, mean=True,
                   block=None, chunk=None):
    """SUM-reduce a flat bucket over `axes` and keep this rank's 1/n
    shard. With `comm_dtype` narrower than fp32 the payload moves
    compressed but the reduction runs in fp32 (all_to_all + local fp32
    accumulate — EQuARX's compressed-wire / uncompressed-math split);
    otherwise a native `psum_scatter`. `comm_dtype='int8'` is
    block-scaled: per-block abs-max fp32 scales are computed on the
    flat bucket (block = largest divisor of the shard length <=
    `block`, default DEFAULT_COMM_BLOCK) and travel beside the int8
    payload in a second all_to_all. Returns an fp32 shard (the
    optimizer update math dtype) scaled to the mean when `mean`.

    `chunk` (elements, `PTPU_COMM_CHUNK`) decomposes an oversized
    bucket into multiple collectives over shard-dimension slices —
    schedulable pieces the latency-hiding scheduler can interleave
    with compute. Each element is still reduced across the same ranks
    in the same order, and pieces concatenate to the exact unchunked
    shard layout, so the uncompressed result is bit-identical."""
    axes = tuple(axes)
    spans = _chunk_spans(flat.shape[0] // n_shards, n_shards, chunk)
    if spans:
        view = flat.reshape(n_shards, -1)
        return jnp.concatenate([
            reduce_scatter(
                lax.slice_in_dim(view, s, s + w, axis=1).reshape(-1),
                axes, n_shards, comm_dtype=comm_dtype, mean=mean,
                block=block)
            for s, w in spans])
    if _is_int8(comm_dtype):
        shard_len = flat.shape[0] // n_shards
        b = block_len(shard_len, resolve_comm_block(block))
        q, scales = quantize_blocks(flat, b)
        q_ch = lax.all_to_all(q.reshape(n_shards, shard_len), axes,
                              split_axis=0, concat_axis=0)
        s_ch = lax.all_to_all(scales.reshape(n_shards, -1), axes,
                              split_axis=0, concat_axis=0)
        deq = q_ch.reshape(n_shards, -1, b).astype(jnp.float32) \
            * s_ch[:, :, None]
        shard = jnp.sum(deq.reshape(n_shards, shard_len), axis=0)
    elif comm_dtype is not None and \
            jnp.dtype(comm_dtype) != jnp.float32:
        if jnp.dtype(comm_dtype) != flat.dtype:
            flat = flat.astype(comm_dtype)
        # compress -> all_to_all (wire in comm_dtype) -> fp32 accumulate
        chunks = lax.all_to_all(flat.reshape(n_shards, -1), axes,
                                split_axis=0, concat_axis=0)
        shard = jnp.sum(chunks.astype(jnp.float32), axis=0)
    else:
        if comm_dtype is not None and jnp.dtype(comm_dtype) != flat.dtype:
            flat = flat.astype(comm_dtype)
        shard = lax.psum_scatter(flat, axes, scatter_dimension=0,
                                 tiled=True).astype(jnp.float32)
    if mean:
        shard = shard * (1.0 / n_shards)
    return shard


def all_gather(shard, axes, comm_dtype=None, block=None, chunk=None,
               n_shards=None):
    """Reassemble the full flat bucket from per-rank shards (reverse
    axis order of the matching reduce_scatter/take_shard). With
    `comm_dtype='int8'` the param refresh is scale-carrying: each rank
    quantizes its updated shard block-wise, int8 payload + fp32 scales
    all-gather together, and every rank dequantizes — all ranks see
    the SAME (quantized) params, and the sharded optimizer state keeps
    the fp32 master, so the rounding does not accumulate step over
    step. Result dtype follows the input shard.

    `chunk` + `n_shards` enable the chunked variant (mirror of
    reduce_scatter's): gather shard slices piecewise, then interleave
    the [n_shards, w] pieces back into the exact rank-major flat
    layout the unchunked gather produces."""
    axes = tuple(axes)
    if n_shards:
        spans = _chunk_spans(shard.shape[0], n_shards, chunk)
        if spans:
            pieces = [all_gather(
                lax.slice_in_dim(shard, s, s + w), axes,
                comm_dtype=comm_dtype, block=block)
                for s, w in spans]
            return jnp.concatenate(
                [p.reshape(n_shards, -1) for p in pieces],
                axis=1).reshape(-1)
    if not _is_int8(comm_dtype):
        for a in reversed(axes):
            shard = lax.all_gather(shard, a, axis=0, tiled=True)
        return shard
    dt = shard.dtype
    b = block_len(shard.shape[0], resolve_comm_block(block))
    q, scales = quantize_blocks(shard, b)
    for a in reversed(axes):
        q = lax.all_gather(q, a, axis=0, tiled=True)
        scales = lax.all_gather(scales, a, axis=0, tiled=True)
    return dequantize_blocks(q, scales, b).astype(dt)


def gather_groups(shards, axes, n_shards, comm_dtype=None, block=None,
                  chunk=None, prefetch=None):
    """Deferred/prefetched param all-gather over a list of 1-D bucket
    shards (call inside shard_map bodies): gathers groups IN ORDER,
    and with `prefetch` chains gather g behind gather g-prefetch via
    `optimization_barrier`, so at most `prefetch` full groups are in
    flight beyond the shards. The ONE home of the overlap gather
    contract — both engines' step-top materialization and their
    taps-mode re-gathers go through here."""
    out = []
    for gi, sh in enumerate(shards):
        if prefetch and gi >= prefetch:
            sh = lax.optimization_barrier((sh, out[gi - prefetch]))[0]
        out.append(all_gather(sh, axes, comm_dtype=comm_dtype,
                              block=block, chunk=chunk,
                              n_shards=n_shards))
    return out


# ---------------------------------------------------------------------------
# sharded weight update
# ---------------------------------------------------------------------------
def elementwise(optimizer):
    """True when the optimizer's update rule is strictly per-element, so
    applying it to a flattened shard is bit-equivalent to per-parameter
    application (Lamb/LARS/DGC use per-PARAMETER norms/quantiles and
    must keep the per-param path)."""
    return bool(getattr(optimizer, '_elementwise', False))


def init_bucket_state(optimizer, bucket, param_flat32, force_master=False):
    """Flat optimizer state for one bucket (host-side arrays).

    param_flat32: the bucket's initial parameter values, flattened to
    fp32 (numpy). Returns {state_key: np.ndarray}; adds the fp32
    'master' copy for low-precision buckets under multi_precision.
    `force_master` adds it for fp32 buckets too — required when the
    param all-gather wire is quantized (comm_dtype='int8'): the
    sharded master stays the exact trajectory and only the gathered
    working copy is rounded, so wire error never feeds back into the
    optimizer state. It therefore overrides multi_precision=False —
    without the master the int8-rounded params would BE the state and
    the invariant would silently break."""
    from .tensor import Tensor
    st = optimizer.init_state(Tensor(jnp.zeros((bucket.size,),
                                               jnp.float32)))
    st = {k: np.asarray(v) for k, v in st.items()}
    if force_master or (bucket.dtype != jnp.float32
                        and getattr(optimizer, '_multi_precision', True)):
        st['master'] = np.asarray(param_flat32, np.float32)
    return st


def grad_stats(flat):
    """One-pass (sum of squares, nonfinite count) of a flat gradient
    array — the two scalars the step needs before touching params
    (global-clip contribution + GradScaler found-inf). Routes to the
    fused Pallas kernel (ops/pallas/fused_optimizer.py) on TPU, one
    fused XLA reduction pair on the reference path. Both return fp32
    scalars; nonfinite gradients poison the sum exactly like
    jnp.sum(g*g) does."""
    from ..ops.pallas import fused_optimizer as FO
    if FO.use_fused_stats():
        return FO.grad_stats_pallas(flat)
    x = flat.astype(jnp.float32)
    return jnp.sum(x * x), jnp.sum((~jnp.isfinite(x))
                                   .astype(jnp.float32))


@jax.named_scope('optimizer')
def shard_update(optimizer, p_shard, g32_shard, st, lr, prefactor=None,
                 found_inf=None):
    """One bucket-shard optimizer update with fp32-master handling —
    the flat twin of the engines' `_update_one` (same rule order:
    prefactor multiply, decay-into-grad, update in fp32, master
    ride-along). `p_shard` is the shard in PARAMETER dtype; returns
    (new_p_shard, new_state).

    `prefactor` (optional scalar) is the combined unscale x global-clip
    multiplier applied to the gradient first; `found_inf` (optional
    bool scalar) makes the whole update a no-op (params and every state
    entry keep their old values — the GradScaler skip). Both fold into
    the SAME pass on the fused route (ops/pallas/fused_optimizer.py,
    one Pallas kernel per bucket shard: unscale + clip + moments +
    param step + master cast in one read/write per operand); the
    reference path below applies them as the familiar XLA op chain."""
    from ..ops.pallas import fused_optimizer as FO
    if FO.use_fused_update(optimizer):
        return FO.fused_shard_update(optimizer, p_shard, g32_shard, st,
                                     lr, prefactor=prefactor,
                                     found_inf=found_inf)
    low = p_shard.dtype != jnp.float32
    st = dict(st)
    master = st.pop('master', None)
    p32 = master if master is not None else (
        p_shard.astype(jnp.float32) if low else p_shard)
    if prefactor is not None:
        g32_shard = g32_shard * prefactor
    wd = getattr(optimizer, '_weight_decay', None)
    if wd and optimizer._decay_into_grad():
        g32_shard = g32_shard + wd * p32
    new32, ns = optimizer.update(p32, g32_shard, st, lr)
    ns = dict(ns)
    if master is not None or (low and getattr(optimizer,
                                              '_multi_precision', True)):
        ns['master'] = new32
    new_p = new32.astype(p_shard.dtype)
    if found_inf is not None:
        old = dict(st)
        if master is not None:
            old['master'] = master
        new_p = jnp.where(found_inf, p_shard, new_p)
        ns = {k: (jnp.where(found_inf, old[k], v) if k in old else v)
              for k, v in ns.items()}
        if 'master' in ns and master is None:
            ns['master'] = jnp.where(found_inf, p32, ns['master'])
    return new_p, ns


def flat_functional_apply(optimizer, layout, params, grads, flat_states,
                          lr):
    """Whole-model bucketed update for the single-program path
    (jit.TrainStep): semantics of Optimizer.functional_apply — global
    grad clip, weight decay, per-param rule — but applied to the
    flattened buckets so the optimizer phase is a handful of fused
    kernels instead of one chain per parameter.

    flat_states: [per-bucket state dict]. Returns (new_params,
    new_flat_states)."""
    from ..nn.clip import (ClipGradByGlobalNorm, ClipGradByNorm,
                           ClipGradByValue)
    clip = optimizer._grad_clip
    if isinstance(clip, ClipGradByNorm):
        # per-PARAM norms: clip before flattening
        cn = clip.clip_norm
        def _clip1(g):
            n = jnp.sqrt(jnp.sum(g.astype(jnp.float32) ** 2))
            return g * jnp.minimum(cn / jnp.maximum(n, 1e-12),
                                   1.0).astype(g.dtype)
        grads = {n: _clip1(g) for n, g in grads.items()}

    flat_grads = [g.astype(jnp.float32)
                  for g in layout.flatten(grads, cast=jnp.float32)]
    factor = None
    if isinstance(clip, ClipGradByGlobalNorm):
        # one fused stats pass per bucket (Pallas on TPU) feeds the
        # clip factor; the multiply itself fuses into the update pass
        sq = sum(grad_stats(g)[0] for g in flat_grads)
        gn = jnp.sqrt(sq)
        factor = clip.clip_norm / jnp.maximum(gn, clip.clip_norm)
    elif isinstance(clip, ClipGradByValue):
        flat_grads = [jnp.clip(g, clip.min, clip.max) for g in flat_grads]

    flat_params = layout.flatten(params)
    new_flats, new_states = [], []
    for b, pf, gf, st in zip(layout.buckets, flat_params, flat_grads,
                             flat_states):
        np_, ns = shard_update(optimizer, pf, gf, st, lr,
                               prefactor=factor)
        new_flats.append(np_)
        new_states.append(ns)
    new_params = {}
    for b, flat in zip(layout.buckets, new_flats):
        for s in b.slots:
            new_params[s.name] = jnp.reshape(
                lax.slice_in_dim(flat, s.offset, s.offset + s.size),
                s.shape)
    return new_params, new_states


# ---------------------------------------------------------------------------
# flat <-> per-param optimizer-state conversion (checkpoint contract)
# ---------------------------------------------------------------------------
def flat_states_to_named(layout, flat_states):
    """[per-bucket {key: host flat array}] -> {param: {key: array}} in
    the engines' per-parameter state_dict schema. Vector states slice
    per slot; scalar states (beta powers) replicate per param."""
    out = {}
    for b, st in zip(layout.buckets, flat_states):
        for s in b.slots:
            d = {}
            for k, v in st.items():
                v = np.asarray(v)
                if v.ndim >= 1 and v.shape[0] == b.size:
                    d[k] = v[s.offset:s.offset + s.size] \
                        .reshape(s.shape).copy()
                else:
                    d[k] = v.copy()
            out[s.name] = d
    return out


def named_states_to_flat(layout, named_states, template):
    """Inverse of flat_states_to_named. `template`: [per-bucket
    {key: host array}] giving each state's flat shape/dtype (used as
    the fallback for params missing from the checkpoint)."""
    out = []
    for b, tmpl in zip(layout.buckets, template):
        st = {k: np.array(v, copy=True) for k, v in tmpl.items()}
        for s in b.slots:
            src = named_states.get(s.name)
            if not src:
                continue
            for k, v in src.items():
                if k not in st:
                    continue
                v = np.asarray(v)
                if st[k].ndim >= 1 and st[k].shape[0] == b.size:
                    st[k][s.offset:s.offset + s.size] = \
                        v.reshape(-1).astype(st[k].dtype)
                else:
                    st[k] = v.astype(st[k].dtype)
        out.append(st)
    return out


# ---------------------------------------------------------------------------
# telemetry: ptpu_comm_* gauges
# ---------------------------------------------------------------------------
def _bucket_wire(b, n_shards, comm_dtype=None, block=None):
    """Per-bucket wire-byte split, the ONE home of the byte
    convention (wire_bytes totals and the overlap seconds model both
    read it): reduce_scatter moves gradients in `comm_dtype`
    (param/bucket dtype when None); all_gather moves updated params
    in their storage dtype; int8 mode moves int8 + fp32 block scales
    on both legs."""
    int8 = _is_int8(comm_dtype)
    rs_item = 1 if int8 else jnp.dtype(comm_dtype or b.dtype).itemsize
    ag_item = 1 if int8 else b.dtype.itemsize
    scale_bytes = 0
    if int8:
        eb = block_len(max(b.size // max(n_shards, 1), 1),
                       resolve_comm_block(block))
        scale_bytes = (b.size // eb) * SCALE_ITEMSIZE
    return {'reduce_scatter': {'payload': b.used * rs_item,
                               'scale': scale_bytes,
                               'pad': b.pad * rs_item},
            'all_gather': {'payload': b.used * ag_item,
                           'scale': scale_bytes,
                           'pad': b.pad * ag_item}}


def wire_bytes(layout, n_shards, comm_dtype=None, block=None):
    """Real per-rank wire bytes per step for a bucket layout, split
    into parameter payload vs overhead (the ISSUE-7 accounting audit):

      {'reduce_scatter'|'all_gather':
          {'payload': <real-parameter bytes on the wire>,
           'scale':   <block-scale sidecar bytes (int8 mode only)>,
           'pad':     <zero-padding bytes>,
           'total':   payload + scale + pad}}
    """
    out = {'reduce_scatter': {'payload': 0, 'scale': 0, 'pad': 0},
           'all_gather': {'payload': 0, 'scale': 0, 'pad': 0}}
    for b in layout.buckets:
        per = _bucket_wire(b, n_shards, comm_dtype, block)
        for op, parts in out.items():
            for k in parts:
                parts[k] += per[op][k]
    for op in out.values():
        op['total'] = op['payload'] + op['scale'] + op['pad']
    return out


def publish_comm_gauges(layout, engine, n_shards, comm_dtype=None,
                        enabled=True, block=None):
    """Publish the per-step communication model for a bucket layout.

    Byte convention (docs/performance.md): a ring allreduce moves
    2x the payload per rank (its reduce-scatter + all-gather
    decomposition); reduce_scatter and all_gather move 1x each. The
    baseline scheme is the per-parameter psum of fp32 gradients — the
    dtype the reduction math runs in, which is what the compressed mode
    preserves (EQuARX) — so `bucketed` vs `per_param_psum_fp32` is an
    equal-accuracy comparison. Wire bytes are REAL bytes: int8 mode
    counts the fp32 block-scale sidecars and the bucket zero-padding,
    reported separately from the parameter payload so the compression
    claim is auditable. Gauges are modeled at trace/build time (the
    compiled step replays the same collectives every step)."""
    from . import monitor as _m
    elems = layout.total_elements()
    padded = layout.total_padded()
    wires = wire_bytes(layout, n_shards, comm_dtype, block)
    rs_bytes = wires['reduce_scatter']['total']
    ag_bytes = wires['all_gather']['total']
    baseline = 2 * elems * 4    # per-param fp32 allreduce, 2x payload
    g = _m.gauge
    g('ptpu_comm_buckets', help='gradient buckets per step',
      labelnames=('engine',)).set(len(layout.buckets), engine=engine)
    g('ptpu_comm_bucket_pad_elements',
      help='zero-padding elements across buckets',
      labelnames=('engine',)).set(padded - elems, engine=engine)
    g('ptpu_comm_shards', help='weight-update shard count (dp degree)',
      labelnames=('engine',)).set(n_shards, engine=engine)
    g('ptpu_comm_bytes_per_step',
      help='modeled per-rank wire bytes per step, by collective '
           '(payload + block scales + padding)',
      labelnames=('engine', 'op')).set(rs_bytes, engine=engine,
                                       op='reduce_scatter')
    g('ptpu_comm_bytes_per_step',
      labelnames=('engine', 'op')).set(ag_bytes, engine=engine,
                                       op='all_gather')
    for op in ('reduce_scatter', 'all_gather'):
        g('ptpu_comm_payload_bytes_per_step',
          help='real-parameter bytes on the wire per rank per step '
               '(scales and padding excluded)',
          labelnames=('engine', 'op')).set(
              wires[op]['payload'], engine=engine, op=op)
        for kind in ('scale', 'pad'):
            g('ptpu_comm_overhead_bytes_per_step',
              help='non-payload wire bytes per rank per step: block '
                   'scales (int8 mode) and bucket zero-padding',
              labelnames=('engine', 'op', 'kind')).set(
                  wires[op][kind], engine=engine, op=op, kind=kind)
    # report the EFFECTIVE block (smallest across buckets), not the
    # requested one: block_len() shrinks to a divisor of the shard
    # length, and an honest gauge is what keeps the scale-overhead
    # numbers auditable (engine layouts pad to n_shards*8, so this
    # never collapses below 8)
    eff_block = 0
    if _is_int8(comm_dtype) and layout.buckets:
        want = resolve_comm_block(block)
        eff_block = min(
            block_len(max(b.size // max(n_shards, 1), 1), want)
            for b in layout.buckets)
    g('ptpu_comm_block_elements',
      help='int8 block-scale granularity in elements — smallest '
           'EFFECTIVE block across buckets (0 = not block-scaled)',
      labelnames=('engine',)).set(eff_block, engine=engine)
    g('ptpu_comm_modeled_bytes_per_step',
      help='modeled per-rank wire bytes per step, by scheme '
           '(allreduce counted 2x payload)',
      labelnames=('engine', 'scheme')).set(
          baseline, engine=engine, scheme='per_param_psum_fp32')
    g('ptpu_comm_modeled_bytes_per_step',
      labelnames=('engine', 'scheme')).set(
          rs_bytes + ag_bytes, engine=engine, scheme='bucketed')
    g('ptpu_comm_compressed_fraction',
      help='1 - reduce_scatter parameter payload / fp32 payload',
      labelnames=('engine',)).set(
          1.0 - wires['reduce_scatter']['payload'] / max(elems * 4, 1),
          engine=engine)
    g('ptpu_comm_enabled',
      help='1 when the bucketed rs/ag path is compiled into the step '
           '(0: modeled only — dp degree 1 or legacy path)',
      labelnames=('engine',)).set(1 if enabled else 0, engine=engine)
    _m.counter('ptpu_collective_calls_total',
               help='collective API invocations',
               labelnames=('op',)).inc(
                   2 * len(layout.buckets) if enabled else 0,
                   op='bucket_rs_ag')


def _bucket_wire_totals(b, n_shards, comm_dtype=None, block=None):
    """(reduce_scatter bytes, all_gather bytes) for ONE bucket —
    payload + block scales + padding, straight from _bucket_wire so
    the overlap seconds model can never drift from the byte gauges."""
    per = _bucket_wire(b, n_shards, comm_dtype, block)
    return (sum(per['reduce_scatter'].values()),
            sum(per['all_gather'].values()))


def overlap_seconds(layout, n_shards, comm_dtype=None, block=None,
                    enabled=True):
    """Trace-time exposed/hidden comm model for a bucket layout:
    (total_s, exposed_s, hidden_s) at MODELED_ICI_BYTES_PER_S.

    With overlap compiled in, a group's reduce-scatter hides under the
    backward of the layers still to come, and its next-step all-gather
    hides under the forward of the groups before it — EXCEPT group 0
    (layer order): its grads complete last (backward ends at layer 0),
    so its reduce-scatter has no compute left to hide under, and its
    params are the first the forward needs, so its gather is on the
    critical path. Exposed = group 0's rs+ag; hidden = the rest. With
    overlap off (or a single group) every byte is exposed."""
    per = [_bucket_wire_totals(b, n_shards, comm_dtype, block)
           for b in layout.buckets]
    total = sum(rs + ag for rs, ag in per) / MODELED_ICI_BYTES_PER_S
    if not enabled or len(per) <= 1:
        return total, total, 0.0
    exposed = sum(per[0]) / MODELED_ICI_BYTES_PER_S
    return total, exposed, total - exposed


def publish_overlap_gauges(layout, engine, n_shards, comm_dtype=None,
                           enabled=True, prefetch=None, chunk=0,
                           block=None):
    """Publish the ptpu_comm_overlap_* gauges for a bucket layout and
    emit one profiler span per group (modeled bytes/seconds ride as
    span args — the compiled step replays the same collectives every
    step, so the model is trace-time like the byte gauges)."""
    from . import monitor as _m
    from .. import profiler as _prof
    prefetch = int(prefetch or DEFAULT_PREFETCH_DEPTH)
    groups = len(layout.buckets)
    total_s, exposed_s, hidden_s = overlap_seconds(
        layout, n_shards, comm_dtype, block, enabled=enabled)
    g = _m.gauge
    g('ptpu_comm_overlap_enabled',
      help='1 when the overlapped (layer-grouped, deferred-gather) '
           'comm schedule is compiled into the step',
      labelnames=('engine',)).set(1 if enabled else 0, engine=engine)
    g('ptpu_comm_overlap_groups',
      help='layer-grouped gradient buckets per step',
      labelnames=('engine',)).set(groups, engine=engine)
    g('ptpu_comm_overlap_groups_in_flight',
      help='param groups gathered ahead of first use (prefetch window '
           'actually achievable with this layout)',
      labelnames=('engine',)).set(
          min(prefetch, groups) if enabled else 0, engine=engine)
    g('ptpu_comm_overlap_prefetch_depth',
      help='deferred-gather prefetch depth knob',
      labelnames=('engine',)).set(prefetch, engine=engine)
    g('ptpu_comm_overlap_chunk_elements',
      help='PTPU_COMM_CHUNK collective decomposition cap '
           '(0 = unchunked)',
      labelnames=('engine',)).set(int(chunk or 0), engine=engine)
    g('ptpu_comm_overlap_total_comm_seconds',
      help='modeled per-step collective seconds at the ICI model '
           'bandwidth',
      labelnames=('engine',)).set(total_s, engine=engine)
    g('ptpu_comm_overlap_exposed_comm_seconds',
      help='modeled comm seconds NOT hidden under compute (group 0 '
           'rs+ag when overlapped; everything when not)',
      labelnames=('engine',)).set(exposed_s, engine=engine)
    g('ptpu_comm_overlap_hidden_comm_seconds',
      help='modeled comm seconds hidden under backward/forward '
           'compute',
      labelnames=('engine',)).set(hidden_s, engine=engine)
    for b in layout.buckets:
        rs_b, ag_b = _bucket_wire_totals(b, n_shards, comm_dtype, block)
        with _prof.RecordEvent(
                f'comm::group{b.index}', event_type='comm',
                engine=engine, group=str(b.group), bucket=b.index,
                rs_bytes=rs_b, ag_bytes=ag_b,
                modeled_seconds=round(
                    (rs_b + ag_b) / MODELED_ICI_BYTES_PER_S, 9),
                hidden=bool(enabled and groups > 1 and b.index != 0)):
            pass


def comm_snapshot():
    """JSON-ready view of every ptpu_comm_* gauge (for
    StepTelemetry.snapshot / bench records / health_dump)."""
    from . import monitor as _m
    reg = _m.metrics()
    out = {}
    for name in ('ptpu_comm_buckets', 'ptpu_comm_bucket_pad_elements',
                 'ptpu_comm_shards', 'ptpu_comm_bytes_per_step',
                 'ptpu_comm_payload_bytes_per_step',
                 'ptpu_comm_overhead_bytes_per_step',
                 'ptpu_comm_block_elements',
                 'ptpu_comm_modeled_bytes_per_step',
                 'ptpu_comm_compressed_fraction', 'ptpu_comm_enabled',
                 'ptpu_comm_overlap_enabled', 'ptpu_comm_overlap_groups',
                 'ptpu_comm_overlap_groups_in_flight',
                 'ptpu_comm_overlap_prefetch_depth',
                 'ptpu_comm_overlap_chunk_elements',
                 'ptpu_comm_overlap_total_comm_seconds',
                 'ptpu_comm_overlap_exposed_comm_seconds',
                 'ptpu_comm_overlap_hidden_comm_seconds'):
        m = reg.get(name)
        if m is None:
            continue
        series = {}
        for key, child in m._series().items():
            label = ','.join(f'{ln}={lv}' for ln, lv
                             in zip(m.labelnames, key))
            series[label or '()'] = child.value()
        out[name] = series
    # derived headline: the acceptance number. This is a trace-time
    # MODEL either way; comm_bytes_drop_enabled says whether the rs/ag
    # path is actually compiled into the step (dp>1) or the engine only
    # modeled it (dp=1 / use_buckets=False) — consumers must not read a
    # modeled-only drop as realized wire savings.
    modeled = out.get('ptpu_comm_modeled_bytes_per_step') or {}
    enabled = out.get('ptpu_comm_enabled') or {}
    payload = out.get('ptpu_comm_payload_bytes_per_step') or {}
    overhead = out.get('ptpu_comm_overhead_bytes_per_step') or {}
    for eng in {k.split(',')[0].split('=', 1)[1]
                for k in modeled if k.startswith('engine=')}:
        base = modeled.get(f'engine={eng},scheme=per_param_psum_fp32')
        new = modeled.get(f'engine={eng},scheme=bucketed')
        if base and new is not None:
            out.setdefault('comm_bytes_drop_vs_per_param_psum', {})[
                eng] = round(1.0 - new / base, 4)
            out.setdefault('comm_bytes_drop_enabled', {})[eng] = bool(
                enabled.get(f'engine={eng}'))
        # wire-byte audit (ISSUE 7): real-parameter payload vs scale /
        # padding overhead, and the payload-vs-payload compression
        # factor — the "4x" claim measured on like bytes, with the
        # sidecar cost visible right beside it instead of hidden in it
        pay = sum(v for k, v in payload.items()
                  if k.startswith(f'engine={eng},'))
        ov_scale = sum(v for k, v in overhead.items()
                       if k.startswith(f'engine={eng},')
                       and k.endswith('kind=scale'))
        ov_pad = sum(v for k, v in overhead.items()
                     if k.startswith(f'engine={eng},')
                     and k.endswith('kind=pad'))
        if pay:
            out.setdefault('comm_wire_breakdown', {})[eng] = {
                'payload_bytes': pay, 'scale_bytes': ov_scale,
                'pad_bytes': ov_pad,
                'total_bytes': pay + ov_scale + ov_pad}
            if base:
                out.setdefault(
                    'comm_payload_factor_vs_per_param_psum', {})[
                    eng] = round(base / pay, 4)
    # overlap headline (ISSUE 10): per-engine exposed vs hidden comm
    # seconds + schedule shape — the dryrun/bench acceptance reads
    # exposed_comm_seconds < total_comm_seconds here. A trace-time
    # MODEL like the byte gauges; `enabled` says whether the overlapped
    # schedule is actually compiled into the step.
    ov_en = out.get('ptpu_comm_overlap_enabled') or {}
    for key in ov_en:
        eng = key.split('=', 1)[1]

        def _ov(name, default=0):
            return (out.get(f'ptpu_comm_overlap_{name}') or {}).get(
                key, default)

        out.setdefault('comm_overlap', {})[eng] = {
            'enabled': bool(_ov('enabled')),
            'groups': int(_ov('groups')),
            'groups_in_flight': int(_ov('groups_in_flight')),
            'prefetch_depth': int(_ov('prefetch_depth')),
            'chunk_elements': int(_ov('chunk_elements')),
            'total_comm_seconds': round(_ov('total_comm_seconds'), 9),
            'exposed_comm_seconds': round(
                _ov('exposed_comm_seconds'), 9),
            'hidden_comm_seconds': round(_ov('hidden_comm_seconds'), 9),
        }
    return out


def flatten_grad_list(grads):
    """Throwaway bucket view of an eager gradient list (GradScaler
    unscale / clip_grad_norm_): returns (layout keyed by list index as
    str, per-bucket flat arrays). One place owns the idiom so the
    fused-reduction / one-sync contract of both callers can't drift."""
    layout = BucketLayout.build(
        {str(i): (g.data.shape, g.data.dtype)
         for i, g in enumerate(grads)})
    flats = layout.flatten({str(i): g.data for i, g in enumerate(grads)})
    return layout, flats
