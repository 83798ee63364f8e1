"""Ragged paged attention — Pallas TPU kernel for the serving engine.

Role (Ragged Paged Attention, arXiv:2604.15464): one kernel serves a
MIXED batch of in-flight requests — decode rows (one new token) and
chunked-prefill rows (a window of new tokens) — whose KV history lives
in a block-paged pool (`serving/kv_pool.py`) instead of a dense
[B, max_len] cache. Each batch row carries its own context length and a
page table; the kernel gathers that row's pages and applies causal
attention *within the sequence*, so the compiled step has one fixed
shape regardless of how ragged the batch is.

TPU-native shape: a `PrefetchScalarGridSpec` grid over (batch_row,
page). The page table and the per-row lengths are scalar-prefetched, so
the BlockSpec index map for K/V resolves `page_tables[b, p]` *before*
the kernel body runs — the pages stream HBM→VMEM exactly like the flash
kernel's K/V blocks, no host gather and no [B, max_len, H*D]
materialization (that is the dense fallback below). Online-softmax
state (running max / normalizer / fp32 accumulator) persists in VMEM
scratch across a row's page steps; heads run as static column slices of
the packed [T, H*D] slab (the flash_attention.py packed-layout idiom —
Tensor Processing Primitives, arXiv:2104.05755: one small reusable
kernel beside the existing ones, not a monolith).

Routing mirrors nn/layer/transformer.py's flash routing: the Pallas
kernel on TPU, a dense `lax` fallback on CPU / tiny shapes, overridable
with FLAGS_paged_attention_kernel. On CPU the kernel still runs under
Pallas interpret mode so CI covers the same body that lowers on TPU.

Layouts:
  q           [B, T, H*D]   new-token queries, right-padded to T per row
  k_pages     [N_pages, page_size, H*D]   the pool's device arrays
  v_pages     [N_pages, page_size, H*D]
  page_tables int32 [B, pages_per_seq]    pool page ids (unused slots
                                          must hold a valid id, e.g. 0)
  seq_lens    int32 [B]  context length INCLUDING this step's new tokens
  q_lens      int32 [B]  valid new tokens this step (<= T)

Query t of row b sits at global position seq_lens[b] - q_lens[b] + t and
attends keys at positions <= its own (causal) and < seq_lens[b].

Multi-query verify rows (ISSUE 9, speculative decoding): the serving
engine's [max_batch, spec_k+1] verify step feeds each greedy request's
last token plus its k draft tokens as one ragged row — q_len = 1+k,
seq_len = context+k. That is exactly the chunked-prefill shape this
kernel (and the dense fallback) already serves: the
causal-within-sequence mask scores every draft against the real
context plus the earlier drafts in ONE dispatch, so no verify-specific
kernel body exists. Rejected drafts leave stale K/V in their slots;
the seq_len mask keeps them invisible until the step that overwrites
them (engine._decode_step documents the rollback invariant).

Quantized pages (ISSUE 7, `kv_dtype='int8'`): k_pages/v_pages are int8
and carry sibling fp32 scale buffers `[N_pages, page_size, H]` — one
abs-max scale per (token slot, head). `write_kv_pages_quantized`
quantizes each new token's per-head K/V row at scatter time;
dequantization happens INSIDE the kernel (per-page VMEM block, one
multiply per head slice — free next to the MXU dot) and inside the
dense fallback, so attention math stays fp32 while the pool pays 1
byte/element + 4 bytes/head/slot. The int8 min tile is (32, 128), so
page_size >= 32 keeps the int8 page blocks tile-aligned; Mosaic (libtpu
0.0.34, v5e) also compiles 16- and 8-slot int8 pages, and 16-slot pages
match the dense reference on the chip (chip_smoke.py `kernels`).
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import scaffold

NEG_INF = -1e30

# interpret-mode forcing shared with every primitive in this package
_interpret = scaffold.interpret_mode


def _ragged_paged_kernel(pt_ref, ln_ref, q_ref, k_ref, v_ref, *rest,
                         page_size, num_heads, head_dim, pages_per_seq,
                         quantized=False):
    """One (batch_row, page) program.

    pt_ref/ln_ref are scalar-prefetched (page tables, [B, 2] lens); the
    K/V BlockSpecs already resolved this program's page id, so k_ref /
    v_ref hold one [page_size, H*D] page in VMEM. Scratch carries the
    online-softmax state across a row's page steps (the page grid
    iterates fastest, so p==0 re-arms and the last page finalizes).
    With `quantized` the K/V blocks are int8 and two extra refs hold
    this page's [page_size, H] fp32 scales; dequantization is one
    broadcast multiply per head slice, fused into the fp32 upcast the
    kernel already pays.
    """
    if quantized:
        ks_ref, vs_ref, o_ref, m_s, l_s, acc_s = rest
    else:
        ks_ref = vs_ref = None
        o_ref, m_s, l_s, acc_s = rest
    b = pl.program_id(0)
    p = pl.program_id(1)
    T = q_ref.shape[0]
    D = head_dim
    seq_len = ln_ref[b, 0]
    q_len = ln_ref[b, 1]
    page_start = p * page_size
    scale = 1.0 / math.sqrt(D)

    @pl.when(p == 0)
    def _():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    @pl.when(page_start < seq_len)
    def _():
        # global positions: rows = this step's queries, cols = this
        # page's keys; causal within the sequence + ragged length mask
        q_pos = (seq_len - q_len
                 + jax.lax.broadcasted_iota(jnp.int32, (T, page_size), 0))
        key_pos = page_start + jax.lax.broadcasted_iota(
            jnp.int32, (T, page_size), 1)
        valid = (key_pos < seq_len) & (key_pos <= q_pos)
        for h in range(num_heads):
            q = q_ref[:, h * D:(h + 1) * D].astype(jnp.float32) * scale
            k = k_ref[:, h * D:(h + 1) * D].astype(jnp.float32)
            v = v_ref[:, h * D:(h + 1) * D].astype(jnp.float32)
            if quantized:
                k = k * ks_ref[:, h:h + 1]
                v = v * vs_ref[:, h:h + 1]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_s[:, h:h + 1]
            l_prev = l_s[:, h:h + 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
            pexp = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            acc = acc_s[:, h * D:(h + 1) * D]
            acc_s[:, h * D:(h + 1) * D] = \
                acc * alpha + jax.lax.dot_general(
                    pexp, v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            m_s[:, h:h + 1] = m_new
            l_s[:, h:h + 1] = alpha * l_prev + jnp.sum(pexp, -1,
                                                       keepdims=True)

    @pl.when(p == pages_per_seq - 1)
    def _():
        l_safe = jnp.maximum(l_s[:], 1e-30)
        for h in range(num_heads):
            o_ref[:, h * D:(h + 1) * D] = (
                acc_s[:, h * D:(h + 1) * D] / l_safe[:, h:h + 1]
            ).astype(o_ref.dtype)


def ragged_paged_attention_pallas(q, k_pages, v_pages, page_tables,
                                  seq_lens, q_lens, *, num_heads,
                                  head_dim, k_scales=None,
                                  v_scales=None, interpret=None):
    """Pallas route (interpret-mode on CPU). See module docstring for
    layouts; k_scales/v_scales engage the int8 dequantizing body."""
    B, T, HD = q.shape
    ps = k_pages.shape[1]
    P = page_tables.shape[1]
    quantized = k_scales is not None
    lens = jnp.stack([seq_lens.astype(jnp.int32),
                      q_lens.astype(jnp.int32)], axis=1)       # [B, 2]
    # unused page-table slots may carry sentinels; the index map still
    # fetches them, so clamp to valid pool ids (compute is masked off)
    pt = jnp.clip(page_tables.astype(jnp.int32), 0,
                  k_pages.shape[0] - 1)
    page_spec = pl.BlockSpec((None, ps, HD),
                             lambda b, p, pt, ln: (pt[b, p], 0, 0))
    in_specs = [
        pl.BlockSpec((None, T, HD), lambda b, p, pt, ln: (b, 0, 0)),
        page_spec,
        page_spec,
    ]
    inputs = [pt, lens, q, k_pages, v_pages]
    if quantized:
        scale_spec = pl.BlockSpec(
            (None, ps, num_heads), lambda b, p, pt, ln: (pt[b, p], 0, 0))
        in_specs += [scale_spec, scale_spec]
        inputs += [k_scales, v_scales]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, P),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, T, HD),
                               lambda b, p, pt, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((T, num_heads), jnp.float32),   # running max
            pltpu.VMEM((T, num_heads), jnp.float32),   # normalizer
            pltpu.VMEM((T, HD), jnp.float32),          # accumulator
        ],
    )
    kernel = functools.partial(
        _ragged_paged_kernel, page_size=ps, num_heads=num_heads,
        head_dim=head_dim, pages_per_seq=P, quantized=quantized)
    out_dtype = q.dtype
    return scaffold.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, T, HD), out_dtype),
        interpret=_interpret() if interpret is None else interpret,
        name='paged_attention',
    )(*inputs)


def _dequant_gathered(pages, scales, H):
    """[B, P, ps, H*D] int8 + [B, P, ps, H] fp32 -> fp32 pages."""
    B, P, ps, HD = pages.shape
    D = HD // H
    return (pages.astype(jnp.float32).reshape(B, P, ps, H, D)
            * scales.astype(jnp.float32)[..., None]) \
        .reshape(B, P, ps, HD)


def ragged_paged_attention_dense(q, k_pages, v_pages, page_tables,
                                 seq_lens, q_lens, *, num_heads,
                                 head_dim, k_scales=None, v_scales=None):
    """Dense lax fallback: gather each row's pages into a [B, P*ps, H*D]
    context and run masked attention. O(B * pages_per_seq * page_size)
    memory — correct everywhere (the CPU serving path and the numerics
    oracle for the kernel), not the TPU hot path. Int8 pages are
    dequantized right after the gather (same per-(slot, head) scales
    the kernel applies in VMEM)."""
    B, T, HD = q.shape
    ps = k_pages.shape[1]
    P = page_tables.shape[1]
    D = head_dim
    pt = jnp.clip(page_tables.astype(jnp.int32), 0,
                  k_pages.shape[0] - 1)
    if k_scales is not None:
        k = _dequant_gathered(k_pages[pt], k_scales[pt], num_heads) \
            .reshape(B, P * ps, HD)
        v = _dequant_gathered(v_pages[pt], v_scales[pt], num_heads) \
            .reshape(B, P * ps, HD)
    else:
        k = k_pages[pt].reshape(B, P * ps, HD).astype(jnp.float32)
        v = v_pages[pt].reshape(B, P * ps, HD).astype(jnp.float32)
    scale = 1.0 / math.sqrt(D)
    q_pos = (seq_lens[:, None] - q_lens[:, None]
             + jnp.arange(T, dtype=jnp.int32)[None, :])        # [B, T]
    key_pos = jnp.arange(P * ps, dtype=jnp.int32)[None, None, :]
    valid = (key_pos < seq_lens[:, None, None]) & \
            (key_pos <= q_pos[:, :, None])                     # [B, T, K]
    outs = []
    for h in range(num_heads):
        qh = q[:, :, h * D:(h + 1) * D].astype(jnp.float32) * scale
        kh = k[:, :, h * D:(h + 1) * D]
        vh = v[:, :, h * D:(h + 1) * D]
        s = jnp.einsum('btd,bkd->btk', qh, kh,
                       preferred_element_type=jnp.float32)
        s = jnp.where(valid, s, NEG_INF)
        probs = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum('btk,bkd->btd', probs, vh))
    return jnp.concatenate(outs, axis=-1).astype(q.dtype)


def use_pallas_route():
    """Auto-selection through the shared scaffolding (scaffold.py):
    the Pallas kernel on TPU, the dense fallback on CPU (interpret-mode
    per-token decode is test machinery, not a serving path). Force with
    FLAGS_paged_attention_kernel=True/False; decisions are counted in
    ptpu_pallas_{kernel,fallback}_invocations_total."""
    return scaffold.use_kernel('paged_attention',
                               'FLAGS_paged_attention_kernel')


def ragged_paged_attention(q, k_pages, v_pages, page_tables, seq_lens,
                           q_lens=None, *, num_heads, head_dim,
                           k_scales=None, v_scales=None):
    """Auto-routed entry (array-level; used inside the serving engine's
    jitted steps). Pass k_scales/v_scales for int8 pages."""
    if q_lens is None:
        q_lens = jnp.full((q.shape[0],), q.shape[1], jnp.int32)
    fn = (ragged_paged_attention_pallas if use_pallas_route()
          else ragged_paged_attention_dense)
    return fn(q, k_pages, v_pages, page_tables, seq_lens, q_lens,
              num_heads=num_heads, head_dim=head_dim,
              k_scales=k_scales, v_scales=v_scales)


def _flat_slots(page_tables, seq_lens, q_lens, T, N, ps):
    """[B*T] flat pool slot per new token (OOB sentinel for padding —
    dropped by the scatter). Token t of row b lands at global position
    seq_lens[b] - q_lens[b] + t, i.e. flat slot
    page_tables[b, pos // ps] * ps + pos % ps."""
    pos = (seq_lens[:, None] - q_lens[:, None]
           + jnp.arange(T, dtype=jnp.int32)[None, :])          # [B, T]
    valid = jnp.arange(T, dtype=jnp.int32)[None, :] < q_lens[:, None]
    page_idx = jnp.take_along_axis(
        jnp.clip(page_tables, 0, N - 1), pos // ps, axis=1)    # [B, T]
    flat = page_idx * ps + pos % ps
    flat = jnp.where(valid, flat, N * ps)      # OOB -> dropped
    return flat.reshape(-1)


def write_kv_pages(k_pages, v_pages, k_new, v_new, page_tables,
                   seq_lens, q_lens):
    """Scatter this step's new K/V rows into the paged pool (pure array
    op, jit/donation-friendly).

    k_new/v_new: [B, T, H*D] right-padded like q; padded tokens are
    routed to an out-of-range index and dropped by the scatter.
    """
    N, ps, HD = k_pages.shape
    B, T, _ = k_new.shape
    flat = _flat_slots(page_tables, seq_lens, q_lens, T, N, ps)
    k2 = k_pages.reshape(N * ps, HD).at[flat].set(
        k_new.reshape(B * T, HD).astype(k_pages.dtype), mode='drop')
    v2 = v_pages.reshape(N * ps, HD).at[flat].set(
        v_new.reshape(B * T, HD).astype(v_pages.dtype), mode='drop')
    return k2.reshape(N, ps, HD), v2.reshape(N, ps, HD)


def quantize_kv_rows(x, num_heads):
    """[B, T, H*D] float -> (int8 [B, T, H*D], fp32 scales [B, T, H]):
    symmetric abs-max per (token, head) — the granularity the pool's
    scale buffers store, chosen so a token's scales are final the
    moment it is written (no rescaling of already-resident slots)."""
    B, T, HD = x.shape
    D = HD // num_heads
    xf = x.astype(jnp.float32).reshape(B, T, num_heads, D)
    amax = jnp.max(jnp.abs(xf), axis=-1)                       # [B,T,H]
    scale = jnp.maximum(amax, 1e-30) / 127.0
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127) \
        .astype(jnp.int8)
    return q.reshape(B, T, HD), scale


def write_kv_pages_quantized(k_pages, v_pages, k_scales, v_scales,
                             k_new, v_new, page_tables, seq_lens,
                             q_lens, *, num_heads):
    """Quantizing twin of write_kv_pages for int8 pools: each new
    token's K/V row is abs-max-quantized per head and scattered as int8
    + fp32 scales into the sibling scale buffers (same flat slots)."""
    N, ps, HD = k_pages.shape
    B, T, _ = k_new.shape
    H = num_heads
    flat = _flat_slots(page_tables, seq_lens, q_lens, T, N, ps)
    kq, ks = quantize_kv_rows(k_new, H)
    vq, vs = quantize_kv_rows(v_new, H)
    k2 = k_pages.reshape(N * ps, HD).at[flat].set(
        kq.reshape(B * T, HD), mode='drop')
    v2 = v_pages.reshape(N * ps, HD).at[flat].set(
        vq.reshape(B * T, HD), mode='drop')
    ks2 = k_scales.reshape(N * ps, H).at[flat].set(
        ks.reshape(B * T, H).astype(k_scales.dtype), mode='drop')
    vs2 = v_scales.reshape(N * ps, H).at[flat].set(
        vs.reshape(B * T, H).astype(v_scales.dtype), mode='drop')
    return (k2.reshape(N, ps, HD), v2.reshape(N, ps, HD),
            ks2.reshape(N, ps, H), vs2.reshape(N, ps, H))
