"""Shared scaffolding for the Pallas primitives library (TPP,
arXiv:2104.05755).

Every fused primitive in this package — flash/paged attention, the fused
optimizer step, LayerNorm, bias+GELU, dropout+residual — shares the same
skeleton:

  * an AUTO-ROUTE: the Pallas kernel on TPU, a pure-`jnp` reference path
    on CPU, force-overridable per primitive with a `FLAGS_*` flag (tests
    force the kernel on the CPU mesh, where it runs under Pallas
    interpret mode so CI exercises the body that lowers on TPU);
  * 1-D -> lane-tiled 2-D reshaping for flat-buffer kernels (the fused
    optimizer step streams [rows, 128] blocks of a bucket shard);
  * row-grid BlockSpec builders for "grid over row blocks, broadcast
    row for weights, (1, 1) accumulator" kernels;
  * routing OBSERVABILITY: every route decision bumps
    `ptpu_pallas_{kernel,fallback}_invocations_total{primitive=...}`
    through core.monitor, so a silently-degraded fallback (e.g. a flag
    typo sending the optimizer step back to the XLA op chain) is
    visible in StepTelemetry.snapshot()['pallas'] and
    `tools/health_dump.py pallas`. Routes are decided at TRACE time
    (the compiled step replays the chosen route every step), so the
    counters count routing decisions, not per-step executions — same
    convention as the trace-time ptpu_comm_* byte model. Primitives:
    flash_attention, flash_dropout (the dropout-fused causal kernels —
    ISSUE 12), paged_attention, optimizer_step, grad_stats,
    layer_norm, bias_gelu, dropout_add.

Adding a kernel on this scaffolding costs the kernel body plus a
~20-line wrapper: pick a primitive name, call `use_kernel(name, flag)`
to route, `to_rows`/`from_rows` or `row_spec`/`bcast_spec` for layout,
and call `scaffold.pallas_call(kernel, name=..., interpret=
interpret_mode(), ...)` — the package's one `pl.pallas_call` site, which
makes the kernel's name the device trace's row
(docs/performance.md#fused-primitives walks through one).
"""
import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# f32 VPU lane width; flat-buffer kernels reshape 1-D buckets to
# [rows, LANES] so blocks are tile-aligned on TPU
LANES = 128
# default rows per grid step for flat-buffer kernels: 256 x 128 f32
# blocks = 128 KB per operand ref — comfortably inside VMEM with the
# ~10 operand/output refs the fused optimizer step carries
ROW_BLOCK = 256

KERNEL = 'kernel'
FALLBACK = 'fallback'


def pallas_call(kernel, *, name, **kwargs):
    """`pl.pallas_call` whose Mosaic call is named `name` in the
    compiled program, whatever transform surrounds it. XLA names the
    instruction after the innermost component of the JAX name stack
    (pallas_call pushes `name` there) — and a scope pushed directly
    inside a transform renders as `transpose(jvp(name))`. The `pallas`
    scope takes that wrapping instead, so the instruction, and with it
    the profile's row, is `name` bare, under jax.grad and under
    jax.checkpoint (tests/test_kernel_names_aot.py)."""
    call = pl.pallas_call(kernel, name=name, **kwargs)

    def named(*args):
        with jax.named_scope('pallas'):
            return call(*args)
    return named


def interpret_mode():
    """Pallas TPU kernels only lower on TPU; under the CPU test mesh the
    same kernel bodies run in interpret mode so CI covers them."""
    return jax.default_backend() == 'cpu'


def fit_block(block, n):
    """Largest power-of-two shrink of `block` that divides `n` (shared by
    the flash kernels' tile fitting — a block that does not divide the
    sequence length would silently misalign in-kernel position iotas
    against pl.ds clamping)."""
    block = min(block, n)
    while block > 1 and n % block:
        block //= 2
    return block if block >= 1 and n % block == 0 else n


def record_route(primitive, used_kernel):
    """Count one routing decision for `primitive` (trace-time)."""
    from ...core import monitor as _m
    name = ('ptpu_pallas_kernel_invocations_total' if used_kernel
            else 'ptpu_pallas_fallback_invocations_total')
    _m.counter(
        name,
        help='Pallas-primitive routing decisions (trace-time), by '
             'primitive: kernel = fused Pallas body, fallback = '
             'reference jnp/XLA path',
        labelnames=('primitive',)).inc(1, primitive=primitive)


def use_kernel(primitive, flag=None, supported=True, record=True):
    """The flash/paged-style auto-route: Pallas kernel on TPU, reference
    path on CPU; `flag` (a FLAGS_* name, None = auto) forces either way;
    `supported=False` pins the fallback (unsupported shape/optimizer)
    regardless of the flag. Records the decision unless `record=False`.
    """
    use = False
    if supported:
        forced = None
        if flag is not None:
            from ...core import flags as _flags
            forced = _flags.flag(flag, None)
        use = bool(forced) if forced is not None \
            else jax.default_backend() == 'tpu'
    if record:
        record_route(primitive, use)
    return use


# ---------------------------------------------------------------------------
# VMEM budget
# ---------------------------------------------------------------------------
# Mosaic's default scoped-VMEM limit is 16 MiB (libtpu 0.0.34 on v5e);
# a kernel whose pipelined blocks pass half of it asks for its own
# limit, up to this cap — the chip has 128 MiB, and the rest stays with
# XLA's fusions around the custom call
VMEM_CAP_BYTES = 100 * 2 ** 20
# on top of the double-buffered blocks: room for the kernel body's own
# temporaries (fp32 score/prob tiles, upcast operands)
_VMEM_BODY_BYTES = 16 * 2 ** 20


def block_bytes(shape, dtype):
    """VMEM bytes of one pipelined block: the minor dim pads to the
    128-lane tile, the second-minor to the dtype's sublane tile."""
    item = jnp.dtype(dtype).itemsize
    dims = [d for d in shape if d is not None]
    lanes = -(-dims[-1] // LANES) * LANES
    sub = 32 // item
    rows = -(-dims[-2] // sub) * sub if len(dims) > 1 else 1
    lead = 1
    for d in dims[:-2]:
        lead *= d
    return lead * rows * lanes * item


def vmem_need(blocks):
    """Scoped-VMEM bytes a call with these (block_shape, dtype) blocks
    needs: the pipeline double-buffers every block."""
    return 2 * sum(block_bytes(s, d) for s, d in blocks) \
        + _VMEM_BODY_BYTES


def compiler_params(in_specs, inputs, out_specs, out_shape):
    """pltpu.CompilerParams sizing the scoped-VMEM limit from the
    call's own blocks (left at Mosaic's default when the need is under
    it). A need above VMEM_CAP_BYTES raises NotImplementedError naming
    the blocks on TPU — the kernel cannot run at that shape, and no
    reference path takes over quietly."""
    blocks = [(sp.block_shape, a.dtype)
              for sp, a in zip(list(in_specs) + list(out_specs),
                               list(inputs) + list(out_shape))
              if sp.block_shape is not None]
    need = vmem_need(blocks)
    if need <= _VMEM_BODY_BYTES + 8 * 2 ** 20:
        return pltpu.CompilerParams()
    if need > VMEM_CAP_BYTES and not interpret_mode():
        raise NotImplementedError(
            f'Pallas kernel needs {need / 2 ** 20:.0f} MiB of VMEM '
            f'(cap {VMEM_CAP_BYTES / 2 ** 20:.0f} MiB) for blocks '
            f'{[(tuple(s), jnp.dtype(d).name) for s, d in blocks]}')
    return pltpu.CompilerParams(
        vmem_limit_bytes=int(min(need, VMEM_CAP_BYTES)))


# ---------------------------------------------------------------------------
# layout helpers
# ---------------------------------------------------------------------------
def to_rows(flat, block_rows=ROW_BLOCK, lanes=LANES):
    """Zero-pad a 1-D array and reshape to [rows, lanes] with rows a
    multiple of `block_rows` — the flat-buffer kernel layout. Zero pad
    is safe for every current kernel: stats add 0, optimizer updates of
    (p=0, g=0, m=0) stay 0, and callers slice the pad off with
    `from_rows`."""
    n = flat.shape[0]
    rows = -(-n // lanes)
    # zero-size inputs still get one (all-pad) block so the grid is
    # never empty; callers slice the pad off, so the result is exact
    rows = max(-(-rows // block_rows) * block_rows, block_rows)
    pad = rows * lanes - n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(rows, lanes)


def from_rows(arr2d, n):
    """Inverse of `to_rows`: back to 1-D, pad dropped."""
    return arr2d.reshape(-1)[:n]


def pad_rows(x2d, block_rows):
    """Zero-pad a [R, N] array so R divides into `block_rows` blocks
    (R = 0 still yields one all-pad block — the grid is never empty;
    pad rows are inert in every kernel and sliced off by callers)."""
    r = x2d.shape[0]
    rows = max(-(-r // block_rows) * block_rows, block_rows)
    if rows != r:
        x2d = jnp.concatenate(
            [x2d, jnp.zeros((rows - r,) + x2d.shape[1:], x2d.dtype)])
    return x2d


def pick_block_rows(ncols, want):
    """Rows per grid block for a [R, ncols] kernel, shrunk so one block
    stays around `want` x LANES elements regardless of the feature dim
    (a fixed row count would grow VMEM use linearly with ncols — at
    ffn_hidden 32k a 128-row fp32 block is 16 MB per ref). Always a
    multiple of 8, floor 8: Mosaic refuses a block whose second-minor
    dim is neither a multiple of 8 nor the whole array (hidden 768 used
    to get 21 rows)."""
    return min(want, max(8, (want * LANES) // max(ncols, 1) // 8 * 8))


def row_spec(block_rows, ncols):
    """Grid-blocked rows: program i sees rows [i*block_rows, ...)."""
    return pl.BlockSpec((block_rows, ncols), lambda i: (i, 0))


def bcast_spec(nrows, ncols):
    """Same block for every program (weights, packed scalars)."""
    return pl.BlockSpec((nrows, ncols), lambda i: (0, 0))


def scalar_spec():
    """Whole small array in SMEM — packed scalar inputs and (1, 1)
    scalar accumulators/outputs. Mosaic loads and stores scalars only
    through SMEM ("Cannot store scalars to VMEM"); the array stays
    resident across the sequential grid and is written back once."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------
def routes_snapshot():
    """{primitive: {'kernel': n, 'fallback': n}} from the monitor
    counters (JSON-ready; bench legs and StepTelemetry embed it)."""
    from ...core import monitor as _m
    reg = _m.metrics()
    out = {}
    for name, key in (('ptpu_pallas_kernel_invocations_total', KERNEL),
                      ('ptpu_pallas_fallback_invocations_total',
                       FALLBACK)):
        m = reg.get(name)
        if m is None:
            continue
        for labels, child in m._series().items():
            prim = labels[0] if labels else ''
            out.setdefault(prim, {KERNEL: 0, FALLBACK: 0})[key] = \
                int(child.value())
    return out


def active_primitives():
    """Primitives whose Pallas kernel route was taken at least once —
    the bench record's `detail.fused_primitives` evidence list."""
    return sorted(p for p, c in routes_snapshot().items()
                  if c.get(KERNEL, 0) > 0)


def snapshot():
    """StepTelemetry.snapshot()['pallas'] payload."""
    routes = routes_snapshot()
    if not routes:
        return None
    return {'routes': routes, 'active': active_primitives()}
