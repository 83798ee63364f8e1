"""Grouped matmul — Pallas TPU kernel for sparse-expert layers.

Role: the expert products of a token-choice mixture of experts. Every
token picks its own experts, so the (token, expert) pairs of one call
are ragged over the experts: some experts get many rows, some none.
The rows are sorted by expert and ONE kernel multiplies each expert's
rows by that expert's weights — products only over routed pairs, no
capacity, no dropped token, and no dense all-experts einsum (which
would do num_experts / top_k times the work).

TPU-native shape: each expert's rows are padded to whole ROW TILES
(`plan`), so a tile belongs to exactly one expert. The grid walks the
tiles; the tile's expert id is scalar-prefetched into SMEM and picks
the weight block through the BlockSpec's index map, so the pipeline
fetches an expert's weights when the walk reaches its first tile and
keeps them while the next tiles are the same expert's — an expert's
weight tiles are read once per call, and an expert without rows has no
tile and is never read. The tile count is static (rows / tile + the
experts held: every expert may waste at most one partial tile); tiles
past the live ones are mapped onto the last live tile's blocks (no
copy: the block index does not change) and skip their product.

With `w_gate` the body is the gated first half of a SwiGLU expert: one
pass over the rows computes silu(x . w_gate) * (x . w) — both weight
blocks ride the same index map.

Weights are blocked over their output columns only where a whole
[K, N] matrix passes `_WEIGHT_BLOCK_BYTES` (4 MiB: the published
expert of the benchmark's sparse configuration is exactly that, so its
weights arrive as one contiguous copy per expert); the column blocks
are the OUTER grid axis, so that inside one column block consecutive
tiles of one expert still share the fetched weights.

Routing mirrors paged_attention.py: the kernel on TPU, a dense `lax`
route on CPU (row tile after row tile against its expert's matrix: the
numerics oracle), overridable with
FLAGS_moe_grouped_matmul_kernel; on CPU the kernel body still runs
under Pallas interpret mode in the tests.

Layouts:
  x            [M_pad, K]   rows sorted by expert, each expert's rows
                            padded to whole tiles (plan()['src'] says
                            which pair each row holds)
  w, w_gate    [E, K, N]    the experts HELD here, stacked
  tile_expert  int32 [tiles]  expert of each row tile (dead: the last
                              live tile's)
  tile_block   int32 [tiles]  row block of each tile (dead: the last
                              live tile's)
  n_live       int32 [1]    tiles that hold rows
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import scaffold

# one weight block [K, tn] of one expert: the pipeline double-buffers
# it (twice with a gate), so 4 MiB is 16 MiB of VMEM at most
_WEIGHT_BLOCK_BYTES = 4 * 2 ** 20


def tile_rows_for(pairs, experts):
    """Rows of a tile: a power of two near twice the mean rows an
    expert gets, so most experts fill one tile — 16 (the bf16 sublane
    tile) for a decode step's 512 pairs over 128 experts, 64 for a
    512-token chunk's 4096; never over 128 (the MXU's rows)."""
    want = max(1, 2 * pairs // max(experts, 1))
    return int(min(128, max(16, 1 << (want - 1).bit_length())))


def plan(expert_ids, num_experts, tile_rows):
    """Sort `expert_ids` ([M] int32; ids >= num_experts are pairs of
    experts not held here, left out) into padded tiles.

    Returns a dict of int32 arrays: `dest` [M] the padded row of each
    pair (M_pad, out of range, for a pair left out), `src` [M_pad] the
    pair each padded row holds (padding rows: pair 0, harmless),
    `tile_expert` / `tile_block` [tiles], `n_live` [1], `counts`
    [num_experts] rows per expert. tiles = ceil(M / tile_rows) +
    num_experts, static."""
    M = expert_ids.shape[0]
    E, tm = int(num_experts), int(tile_rows)
    tiles = -(-M // tm) + E
    ids = jnp.minimum(expert_ids.astype(jnp.int32), E)
    counts = jnp.zeros((E + 1,), jnp.int32).at[ids].add(1)[:E]
    padded = -(-counts // tm) * tm
    ends = jnp.cumsum(padded)
    starts = ends - padded
    # a pair's rank among its expert's pairs, in the pairs' own order
    order = jnp.argsort(ids, stable=True)
    first = jnp.cumsum(counts) - counts              # unpadded starts
    sorted_ids = ids[order]
    held = sorted_ids < E
    safe = jnp.minimum(sorted_ids, E - 1)
    rank = jnp.arange(M, dtype=jnp.int32) - first[safe]
    dest_sorted = jnp.where(held, starts[safe] + rank, tiles * tm)
    dest = jnp.zeros((M,), jnp.int32).at[order].set(dest_sorted)
    src = jnp.zeros((tiles * tm,), jnp.int32).at[dest].set(
        jnp.arange(M, dtype=jnp.int32), mode='drop')
    n_live = ends[-1] // tm
    block = jnp.minimum(jnp.arange(tiles, dtype=jnp.int32),
                        jnp.maximum(n_live - 1, 0))
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends, block * tm, side='right'), E - 1) \
        .astype(jnp.int32)
    return {'dest': dest, 'src': src, 'tile_expert': tile_expert,
            'tile_block': block, 'n_live': n_live.reshape(1),
            'counts': counts}


def _grouped_kernel(te_ref, tb_ref, nl_ref, x_ref, w_ref, *rest, gated):
    """One row tile of one expert: [tm, K] x [K, tn] in the operands'
    stored dtype with fp32 accumulation; with `gated`,
    silu(x . w_gate) * (x . w). A tile past the live ones does
    nothing: its blocks are the last live tile's, still resident."""
    if gated:
        wg_ref, o_ref = rest
    else:
        o_ref, = rest
    t = pl.program_id(1)

    @pl.when(t < nl_ref[0])
    def _():
        x = x_ref[...]
        y = jnp.dot(x, w_ref[...], preferred_element_type=jnp.float32)
        if gated:
            g = jnp.dot(x, wg_ref[...],
                        preferred_element_type=jnp.float32)
            y = g * jax.nn.sigmoid(g) * y
        o_ref[...] = y.astype(o_ref.dtype)


def _column_block(K, N, dtype):
    """Output columns of one weight block: all of them where [K, N]
    fits _WEIGHT_BLOCK_BYTES, else the largest multiple of 128 that
    divides N and fits."""
    item = jnp.dtype(dtype).itemsize
    if K * N * item <= _WEIGHT_BLOCK_BYTES or N % 128:
        return N
    tn = N
    while tn % 256 == 0 and K * tn * item > _WEIGHT_BLOCK_BYTES:
        tn //= 2
    return tn


def grouped_matmul_pallas(x, w, tile_expert, tile_block, n_live,
                          w_gate=None, *, interpret=None):
    """Pallas route (interpret mode on CPU). x [M_pad, K], w (and
    w_gate) [E, K, N] -> [M_pad, N] in x's dtype; rows of dead tiles
    are left unwritten."""
    return _grouped_call(
        x, w, tile_expert, tile_block, n_live, w_gate,
        interpret=scaffold.interpret_mode() if interpret is None
        else interpret)


@functools.partial(jax.jit, static_argnames=('interpret',))
def _grouped_call(x, w, tile_expert, tile_block, n_live, w_gate, *,
                  interpret):
    """The Mosaic call, one jitted function of the shapes: a model's
    expert layers share one trace and one lowering of the body."""
    M_pad, K = x.shape
    E, _, N = w.shape
    tiles = tile_expert.shape[0]
    tm = M_pad // tiles
    tn = _column_block(K, N, w.dtype)
    gated = w_gate is not None
    x_spec = pl.BlockSpec((tm, K), lambda n, t, te, tb, nl: (tb[t], 0))
    w_spec = pl.BlockSpec((None, K, tn),
                          lambda n, t, te, tb, nl: (te[t], 0, n))
    o_spec = pl.BlockSpec((tm, tn), lambda n, t, te, tb, nl: (tb[t], n))
    weights = [w, w_gate] if gated else [w]
    need = 2 * len(weights) * scaffold.block_bytes((K, tn), w.dtype) \
        + 2 * scaffold.block_bytes((tm, K), x.dtype) \
        + 2 * scaffold.block_bytes((tm, tn), x.dtype) \
        + 4 * scaffold.block_bytes((tm, tn), jnp.float32)
    call = scaffold.pallas_call(
        functools.partial(_grouped_kernel, gated=gated),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(N // tn, tiles),
            in_specs=[x_spec] + [w_spec] * len(weights),
            out_specs=o_spec),
        out_shape=jax.ShapeDtypeStruct((M_pad, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            # the walk is in order: a dead tile leans on the tile
            # before it having left its blocks resident
            dimension_semantics=('arbitrary', 'arbitrary'),
            vmem_limit_bytes=int(min(
                max(need + 8 * 2 ** 20, 16 * 2 ** 20),
                scaffold.VMEM_CAP_BYTES))),
        interpret=interpret,
        name='moe_grouped_matmul')
    return call(tile_expert, tile_block, n_live, x, *weights)


def grouped_matmul_dense(x, w, tile_expert, tile_block, n_live,
                         w_gate=None):
    """Dense lax route: one live row tile after the other, each against
    its expert's matrix (gathered whole, fp32) — the CPU path and the
    kernel's oracle. Rows of dead tiles come out zero, uncomputed."""
    tiles = tile_expert.shape[0]
    tm, N = x.shape[0] // tiles, w.shape[2]

    def live_tile(rows, expert):
        rows = rows.astype(jnp.float32)
        y = rows @ w[expert].astype(jnp.float32)
        if w_gate is not None:
            g = rows @ w_gate[expert].astype(jnp.float32)
            y = g * jax.nn.sigmoid(g) * y
        return y.astype(x.dtype)

    def one(args):
        rows, expert, live = args
        return jax.lax.cond(live, live_tile,
                            lambda *_: jnp.zeros((tm, N), x.dtype),
                            rows, expert)
    out = jax.lax.map(one, (x.reshape(tiles, tm, -1), tile_expert,
                            jnp.arange(tiles) < n_live[0]))
    return out.reshape(x.shape[0], N)


def use_pallas_route():
    """The kernel on TPU, the dense route on CPU; force with
    FLAGS_moe_grouped_matmul_kernel=True/False."""
    return scaffold.use_kernel('moe_grouped_matmul',
                               'FLAGS_moe_grouped_matmul_kernel')


def grouped_matmul(x, w, tile_expert, tile_block, n_live, w_gate=None):
    """The one entry point of the expert products: x [M_pad, K] (rows
    sorted and padded by `plan`) times each row tile's expert's
    w [E, K, N]; with `w_gate`, silu(x . w_gate) * (x . w)."""
    if use_pallas_route():
        return grouped_matmul_pallas(x, w, tile_expert, tile_block, n_live,
                                     w_gate)
    return grouped_matmul_dense(x, w, tile_expert, tile_block, n_live,
                                w_gate)
