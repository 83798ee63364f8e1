"""Ragged paged attention — Pallas TPU kernel for the serving engine.

Role (Ragged Paged Attention, arXiv:2604.15464): one kernel serves a
MIXED batch of in-flight requests — decode rows (one new token) and
chunked-prefill rows (a window of new tokens) — whose KV history lives
in a block-paged pool (`serving/kv_pool.py`) instead of a dense
[B, max_len] cache. Each batch row carries its own context length and a
page table; the kernel gathers that row's pages and applies causal
attention *within the sequence*, so the compiled step has one fixed
shape regardless of how ragged the batch is.

TPU-native shape: work in proportion to each row's OWN context. A
`PrefetchScalarGridSpec` grid over batch rows only; the page table and
the per-row lengths are scalar-prefetched into SMEM and the K/V pools
stay in HBM (`memory_space=HBM`, no BlockSpec window). Inside a row's
program a `fori_loop` runs over ceil(live_pages / W) DMA WAVES, where
live_pages = ceil(seq_len / page_size): a wave starts one
`make_async_copy` per live page of K and of V (page ids read from the
SMEM table) into one of the two slots of a `[2, W, page_size, H*D]`
VMEM scratch, the next wave in flight while this one is folded into
the online softmax — and under a row's last wave the next row's first,
so a row does not open on a cold copy (0.40 -> 0.34 ms for the 1.3B
decode call at ragged 400-token contexts, PERF.md section 5). No page
past seq_len is copied or scheduled, table slots past
it are never read (sentinels there are never dereferenced), and a row
with q_len == 0 starts no copy and writes zeros. A partial last wave
leaves its other pages as the previous wave left them; `key_pos <
seq_len` masks them (the V slots are zeroed once, so 0 * v never meets
an uninitialised NaN).

W (pages of K, and as many of V, per wave) is derived in the wrapper,
`_wave_pages`: `_WAVE_BYTES` (1 MiB of K+V) over one page's bytes —
8 pages = 128 keys at 16 slots x 2048 x bf16 — shrunk until the two
slots fit `_VMEM_BUDGET` beside the double-buffered q / out blocks,
the fp32 accumulator [rows, H*D] and, for int8 pools, the row's scale
blocks; rounded down to a power of two; the call's `vmem_limit_bytes`
follows from the same sum. They are constants of this file, not flags.

Two score products, chosen by the static query width T:
  * T*H <= `_BATCHED_ROWS` (decode, speculative verify): the wrapper
    lays q out block-diagonally, row t*H+h = query t masked to head
    h's columns, so ONE [T*H, H*D] x [H*D, keys] product scores every
    head against the wave, the softmax state is a dense [T*H, 1]
    column and p.v is one [T*H, keys] x [keys, H*D] product whose
    diagonal blocks the wrapper keeps. With T = 1 a per-head product
    has one row; batched, the MXU sees 16.
  * otherwise (the prefill chunk): heads run as static column slices
    of the packed [T, H*D] slab, each an MXU-shaped [T, D] x [D, keys]
    product (the flash_attention.py packed-layout idiom — Tensor
    Processing Primitives, arXiv:2104.05755).
q.k takes its operands in their stored dtype with fp32 accumulation
(products of bf16 values are exact in fp32) and 1/sqrt(D) applied to
the fp32 scores; the softmax state, the probabilities, p.v and the
accumulator are fp32. On the v5e the decode call is bound by its page
copies, not its products (PERF.md section 5).

Routing mirrors nn/layer/transformer.py's flash routing: the Pallas
kernel on TPU, a dense `lax` fallback on CPU / tiny shapes, overridable
with FLAGS_paged_attention_kernel. On CPU the kernel still runs under
Pallas interpret mode so CI covers the same body that lowers on TPU.

Kv groups and windows (static arguments of the ONE body): with
`num_kv_heads` < `num_heads` the pool's width is num_kv_heads * D and
query head h reads kv head h // group. The block-diagonal rows of the
batched product then sit in their kv head's columns (32 query heads on
4 kv heads: [32, 512] x [512, keys]); the prefill chunk stacks the
`group` query heads of a kv head as ROWS, [group*T, D] x [D, keys] per
kv head, and `_SCORE_BYTES` holds one such score tile to 2 MiB (128
keys a wave at 4096 rows). With `window` a query at position p reads
keys p - window + 1 .. p: the row's loop opens at the page of its
first query's oldest key (older pages are neither copied nor
scheduled) and the mask drops older keys; the Mosaic call is then
named `paged_attention_window`. With num_kv_heads == num_heads and no
window the body is what it was. int8 pools take neither.

Differential attention (`diff`, static; arXiv:2410.05258 as SambaY
lays it out): the stored kv heads are SUB-heads of width D, and `diff`
neighbouring key sub-heads share ONE value block of width diff * D (v_g
= [v_g,1 | v_g,2]); query sub-head (p, s) scores against key sub-head
s of its kv block and multiplies the whole block, so the output is
diff * D wide a query sub-head (the caller takes the difference and
the norm). The kernel sees it as kv heads of width diff * D whose
queries are zero outside their own sub-head's D columns: the score
product contracts over the block and the zeros select the sub-head, the
p.v product is diff * D wide, the scale stays 1/sqrt(D). No second read
of K, no second body; the Mosaic call is named `paged_attention_diff`
(`..._diff_window` with a window). With `diff` absent the body is what
it was.

Latent attention (`latent=(value lanes, rotary lanes)`, static;
DeepSeek-V2, arXiv:2405.04434, in its absorbed form): the pool holds ONE
array a layer, a row `[c_kv | k_pe | 0]` of `value lanes + rotary
lanes` padded to whole 128-lane tiles (512 + 64 in 640), and every one
of the `num_heads` query heads reads that one stored head: scores
contract over the whole row (q is `[q' | q_pe | 0]` per head, the
caller's softmax scale already in it — the kernel applies none), values
are the first `value lanes` of the SAME page copy — no second pool, no
second DMA, no second body —, p.v takes the probabilities in the pool's
dtype (fp32 accumulation; the softmax state stays fp32) and the output
is `value lanes` wide a head. Rows are q's NATURAL layout, t*Hq+h —
every head a row of one score product ([64, 640] x [640, keys], then
[64, keys] x [keys, 512] for a decode row), no transpose round the call
—, and since 64 heads x 256 queries of 640 lanes fit no VMEM a prompt
chunk's grid gains QUERY TILES over TOKENS: each batch row runs as
`q_tiles` programs of ALL the heads x `latent_tile_tokens` tokens
(`_LATENT_TILE_ROWS` rows: 32 tokens at 64 heads). A tile walks only
the pages its OWN live queries can read: one whose first token lies at
or past q_len starts no copy, folds no wave and writes zeros (as a row
with q_len == 0 does), and a live tile's loop ends at the page of its
last live query's position, not at seq_len's — so a 162-token chunk of
a 256-token program costs 6 tiles of 8, a tile behind a long document
walks it once (a chunk's products outweigh its copies 30 to 1) and a
document's own first chunks walk a triangle. `latent_pairs_dispatched`
is that rule as arithmetic, for the engine's counter. The Mosaic call
is named `paged_attention_latent`. With `latent` absent the body, the
grid and the operands are what they were.

The name says the group of rows too: a call whose rows carry more than
one query (static `T` > 1: the mixed step's prompt chunks, and a verify
step's token with its drafts) appends `_chunk` LAST —
`paged_attention[_diff][_window][_latent]_chunk` —, the decode rows'
call (`T` == 1) nothing, so a mixed program's two calls a layer are two
rows of the device trace. The body is the same.

A shared run (`shared=(run pages, run keys, member rows)`; cascade or
shared-prefix attention — Hydragen, arXiv:2402.05099): where several
decode rows map the same leading pages (a system prompt the prefix
cache shares), the decode call reads them ONCE. Its grid gains one
program, first: it walks the run's pages with every member row's
queries stacked as the rows of one [B*group, D] x [D, keys] product a
kv head (the chunk layout, member rows in place of a chunk's tokens; no
mask — the run lies before every member's query) into a softmax state
of its own (m, l, acc: the run's output and log-sum-exp, unnormalised).
Each member row's program then opens its online softmax AS its rows of
that state — so folding its own pages after the run is the log-sum-exp
merge of the two passes — and its loop opens at the page after the run,
as a window's opens at its oldest key. Other rows read as without. A
member's rows of the state are loaded by whole 8-row tiles (Mosaic
takes a dynamic sublane offset only at a multiple of 8), so the query
heads a kv head must tile 8 rows or be whole tiles of them. It engages
on the decode rows of the plain body, with or without kv groups
(`shared_pass`), and nowhere else; the call keeps its name.

Layouts:
  q           [B, T, Hq*D]  new-token queries, right-padded to T per row
  k_pages     [N_pages, page_size, H*D]   the pool's device arrays (H:
  v_pages     [N_pages, page_size, H*D]   kv heads; Hq = H unless told)
  page_tables int32 [B, pages_per_seq]    pool page ids (slots past a
                                          row's live pages: anything)
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
dequantization happens INSIDE the kernel (the wave's pages upcast in
VMEM, one multiply per head slice) and inside the dense fallback, so
attention math stays fp32 while the pool pays 1 byte/element + 4
bytes/head/slot. The int8 pages ride the same DMA waves. The scales do
not: Mosaic (libtpu 0.0.34) refuses to slice a DMA source whose minor
dim (H) is under the 128-lane tile, so XLA gathers each row's
`[P*page_size, H]` scales (ids clamped; slots past seq_len are masked)
and the grid's pipeline brings the row's block — work over every table
slot, for the scales only. The int8 min tile is (32, 128), so page_size
>= 32 keeps the int8 pages tile-aligned; 16-slot int8 pages compile and
match the dense reference on the chip too (chip_smoke.py `kernels`).
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


# K + V bytes one DMA wave moves HBM->VMEM: 8 pages of K and 8 of V at
# the 1.3B server's shape (16 slots x 2048 x bf16 = 64 KB a page), so a
# wave is 128 keys — one lane-dense score tile
_WAVE_BYTES = 2 ** 20
# scoped VMEM the wave slots may take together with the q / out blocks
# and the fp32 accumulator (of scaffold.VMEM_CAP_BYTES; the rest is the
# body's fp32 score / prob / upcast tiles). No shape with as many kv
# heads as query heads comes near it: _WAVE_BYTES decides there.
_VMEM_BUDGET = 48 * 2 ** 20
# one fp32 [rows, keys] score tile may take this much: a prefill chunk
# whose kv groups stack 8 query heads a row block (4096 rows) folds 128
# keys a wave, a decode call is not held by it
_SCORE_BYTES = 2 * 2 ** 20
# up to this many (query, head) rows the heads are batched into one
# block-diagonal score product; above it (the prefill chunk) each head
# runs its own MXU-shaped [T, D] x [D, keys] product
_BATCHED_ROWS = 128
# (token, head) rows of one query tile of a latent chunk: all the heads
# of as many whole tokens as fit (32 tokens x 64 heads); q and out blocks
# (double-buffered) and the fp32 accumulator then take 18 MiB, and a
# [rows, 256 keys] score tile _SCORE_BYTES
_LATENT_TILE_ROWS = 2048


def latent_tile_tokens(T, num_heads):
    """Tokens of one query tile of a latent call whose rows carry `T`
    query slots: all of them where T x heads rows fit one tile (the
    decode rows, a verify row), else the most whole tokens of
    `_LATENT_TILE_ROWS` rows that divide T."""
    tile = max(1, min(T, _LATENT_TILE_ROWS // num_heads))
    while T % tile:
        tile -= 1
    return tile


def latent_pairs_dispatched(context, queries, T, num_heads):
    """The (query, key) pairs a latent call multiplies for ONE row of
    `queries` live tokens (of `T` slots) whose last sits at position
    context - 1: every live tile's `latent_tile_tokens` tokens, live or
    padding, times the keys up to its last live query's. A dead tile
    multiplies nothing. (A wave's keys past that position, under 256 a
    tile, are multiplied too and not counted.) This is the kernel's
    `live_pages` in tokens; the two are held together by the tests."""
    tile = latent_tile_tokens(T, num_heads)
    return sum(tile * (context - queries + min(queries, first + tile))
               for first in range(0, queries, tile))


def _wave_pages(page_size, HD, kv_dtype, rows, q_dtype, P, num_heads,
                quantized, planes=2, run_rows=0):
    """(W, vmem bytes): pages of K (and as many of V) per DMA wave —
    _WAVE_BYTES over one page's K+V bytes, shrunk until two wave slots
    fit _VMEM_BUDGET beside the double-buffered q / out blocks, the
    fp32 accumulator and (int8) the row's scale blocks, and until one
    [rows, keys] score tile fits _SCORE_BYTES; a power of two so
    `W * page_size` keys tile the lanes; never more than a row's
    page-table slots. `HD` is the pool's width (kv heads x head_dim),
    `num_heads` its kv heads, `planes` the arrays a wave copies from (2:
    K and V; 1: a latent plane). `run_rows`: the rows of a shared run's
    pass (its whole q block, its state and its per-kv-head score tile
    besides the row's)."""
    page = planes * scaffold.block_bytes((page_size, HD), kv_dtype)
    fixed = 4 * scaffold.block_bytes((rows, HD), q_dtype) \
        + scaffold.block_bytes((rows, HD), jnp.float32)
    if quantized:
        fixed += 4 * scaffold.block_bytes((P * page_size, num_heads),
                                          jnp.float32)
    if run_rows:
        fixed += 2 * scaffold.block_bytes((run_rows, HD), q_dtype) \
            + scaffold.block_bytes((run_rows, HD), jnp.float32) \
            + 2 * scaffold.block_bytes((run_rows, num_heads), jnp.float32)
    room = max(_VMEM_BUDGET - fixed, 2 * page)
    tile = max(1, _SCORE_BYTES // (4 * max(rows, run_rows) * page_size))
    want = max(1, min(_WAVE_BYTES // page, room // (2 * page), tile, P))
    W = 1 << (want.bit_length() - 1)
    # what the call holds: blocks, accumulator, softmax state, two
    # slots, and the body's fp32 copies of one wave of K and V plus
    # its score / prob / mask tiles
    need = fixed + 2 * W * page \
        + 2 * scaffold.block_bytes((rows, num_heads), jnp.float32) \
        + 3 * scaffold.block_bytes((W * page_size, HD), jnp.float32) \
        + 4 * scaffold.block_bytes((max(rows, run_rows), W * page_size),
                                   jnp.float32)
    return W, need


def _ragged_paged_kernel(pt_ref, ln_ref, *rest, page_size, num_heads,
                         head_dim, wave_pages, batched, quantized=False,
                         group=1, window=None, diff=1, latent=None,
                         q_tiles=1, shared=False):
    """One batch row: a loop over the row's OWN live pages.

    `num_heads` counts the pool's (kv) heads; `group` query heads
    share each of them (1: as many kv heads as query heads). `window`
    (static; None: every key) bounds a query at position p to the keys
    at p - window + 1 .. p: the row's loop then opens at the page of
    its first query's oldest key, and no older page is copied. `diff`
    (static): `head_dim` holds that many key sub-heads side by side,
    each query row zero outside its own, so only the scale differs.
    `latent` (static; (value lanes, rotary lanes)): there is no V pool —
    the values are the first `value lanes` of the wave's K copy, the
    accumulator and the output are that wide, and the scores come
    unscaled; `q_tiles` programs then share one batch row (program i is
    tile i % q_tiles of row i // q_tiles), each with all the heads of
    its own R // group TOKENS of q, and each walking only the pages its
    own live queries read: none where its first token lies at or past
    q_len, else up to the page of its last live query's position.

    pt_ref/ln_ref are scalar-prefetched (page tables, [B, 2] lens);
    k_hbm / v_hbm are the whole pools, left in HBM. Wave w copies pages
    [w*W, (w+1)*W) of the row's table — only those below
    cdiv(seq_len, page_size) — into one of the two VMEM slots while
    the wave before it is folded into the online softmax; under a
    row's last wave the NEXT row's first wave is started (`nxt`, SMEM,
    carries its slot to that row's program), so the copies form one
    stream over the batch (a latent chunk's: over the grid, dead
    tiles in it). A row with q_len == 0 starts no copy and writes
    zeros.

    `batched`: q_ref holds [T*Hq, H*D] block-diagonal rows (row
    t*Hq+h = query head h of token t, in the columns of ITS kv head),
    so ONE product scores every head against the wave and the softmax
    state is a dense [T*Hq, 1] column. Otherwise q_ref is [group*T,
    H*D] — row g*T+t holds, in kv head j's columns, query head
    j*group+g of token t — and the kv heads run as static column
    slices with [group*T, H] state, as flash_attention.py's packed
    layout: the `group` query heads of one kv head are one product's
    rows.
    With `quantized` the pools are int8 and two more refs hold the
    row's [P*page_size, H] fp32 scales in VMEM, applied per head slice
    to the wave's upcast pages.

    `shared` (static; decode rows of the batched layout only): a third
    scalar-prefetched ref holds a leading page run that several rows
    map (`run_ref`: its page count, then its page ids), `ln_ref` a
    third column (the page a row's own loop opens at: the run's length
    for a member row, else 0), and the grid one program more. Program 0
    walks the run's pages ONCE for every row, the members' queries
    stacked as the rows of one [B*group, D] x [D, keys] product a kv
    head (`qs_ref`, row b*group+g: query head j*group+g of row b in kv
    head j's columns), into its own softmax state (m, l, acc). Program
    i is row i-1: a member row's state opens as its rows of that state
    (the run's (o, lse) unnormalised, so the online softmax over its
    own pages IS the log-sum-exp merge of the two passes), and its loop
    opens at the page after the run; every other row reads as without.
    """
    if shared:
        run_ref, q_ref, qs_ref, k_hbm, *rest = rest
        (v_hbm, o_ref, kbuf, vbuf, sem, nxt, m_s, l_s, acc_s, m_sh, l_sh,
         acc_sh) = rest
        planes = ((k_hbm, kbuf), (v_hbm, vbuf))
    else:
        q_ref, k_hbm, *rest = rest
    if latent is not None:
        o_ref, kbuf, sem, nxt, m_s, l_s, acc_s = rest
        planes = ((k_hbm, kbuf),)
    elif quantized:
        (v_hbm, ks_ref, vs_ref, o_ref, kbuf, vbuf, sem, nxt, m_s, l_s,
         acc_s) = rest
        planes = ((k_hbm, kbuf), (v_hbm, vbuf))
    elif not shared:
        v_hbm, o_ref, kbuf, vbuf, sem, nxt, m_s, l_s, acc_s = rest
        planes = ((k_hbm, kbuf), (v_hbm, vbuf))
    # the grid's programs in order: (row, query tile); without query
    # tiles a program IS a row (and with `shared`, program 0 the run's)
    step = pl.program_id(0)
    steps = pl.num_programs(0)
    R = q_ref.shape[0]
    tile_tokens = R // group        # a latent tile's tokens
    W, ps, H, D = wave_pages, page_size, num_heads, head_dim
    keys = W * ps
    scale = None if latent is not None else 1.0 / math.sqrt(D // diff)

    def first_page(row):
        """The page the row's loop opens at: with a window that of the
        oldest key its first query reads, with `shared` the page after
        the run it shares (0 where it shares none); None otherwise
        (the table's slot 0)."""
        if shared:
            return ln_ref[row, 2]
        if window is None:
            return None
        oldest = ln_ref[row, 0] - ln_ref[row, 1] - (window - 1)
        return jnp.maximum(oldest, 0) // ps

    def program(i):
        """(batch row, first token of the query tile) of program i."""
        if shared:
            return jnp.maximum(i - 1, 0), 0
        if q_tiles == 1:
            return i, 0
        return i // q_tiles, i % q_tiles * tile_tokens

    def live_pages(row, first=0):
        """Pages the row's loop visits: none without a query. A latent
        tile (tokens first .. first + tile_tokens of the row) has none
        where its first token is no query, and ends at the page of its
        last live query's position (causal: no query of it reads past
        that)."""
        if latent is not None:
            seq, queries = ln_ref[row, 0], ln_ref[row, 1]
            ahead = jnp.maximum(queries - first - tile_tokens, 0)
            return jnp.where(queries > first, pl.cdiv(seq - ahead, ps), 0)
        pages = pl.cdiv(ln_ref[row, 0], ps)
        opens = first_page(row)
        if opens is not None:
            pages = pages - opens
        return jnp.where(ln_ref[row, 1] > 0, pages, 0)

    def program_pages(i):
        """Pages program i walks (with `shared`, program 0 the run's)."""
        pages = live_pages(*program(i))
        if shared:
            pages = jnp.where(i == 0, run_ref[0], pages)
        return pages

    b, token0 = program(step)
    seq_len = ln_ref[b, 0]
    q_len = ln_ref[b, 1]
    n_pages = program_pages(step)
    base = first_page(b)
    n_waves = pl.cdiv(n_pages, W)
    # the program after this one (its first wave is started under this
    # one's last, so a program does not open on a cold copy)
    after = jnp.minimum(step + 1, steps - 1)
    after_pages = jnp.where(step + 1 < steps, program_pages(after), 0)

    def wave_dma(i, wave, slot, pages, start, run=False):
        """Start (or wait for) the copies of wave `wave` of program `i`
        (`run`, static: of the shared run's walk, whose page ids are the
        run's list): its pages below `pages`, none when the wave lies
        past them. Table slots at or past a row's live pages are never
        read: what they hold (sentinels, another request's page) is
        never dereferenced."""
        at = wave * W       # the wave's first slot, in the row's table
        if start and not run:
            row, _ = program(i)
            opens = first_page(row)
            if opens is not None:
                at = opens + at

        def page_dma(j, carry):
            # a wait needs the copy's shape, not its source
            if not start:
                page_id = 0
            elif run:
                page_id = run_ref[1 + at + j]
            else:
                page_id = pt_ref[row, at + j]
            for n, (hbm, buf) in enumerate(planes):
                copy = pltpu.make_async_copy(
                    hbm.at[page_id], buf.at[slot, j], sem.at[slot, n])
                copy.start() if start else copy.wait()
            return carry
        jax.lax.fori_loop(0, jnp.clip(pages - wave * W, 0, W), page_dma, 0)

    @pl.when(step == 0)
    def _():
        # a partial wave leaves the slot's other pages as the last
        # wave left them: masked scores give p == 0 there, and 0 * v
        # must not meet the NaN an uninitialised VMEM word can hold
        values = planes[-1][1]
        values[...] = jnp.zeros_like(values)
        nxt[0] = 0          # the slot this row's wave 0 takes
        nxt[1] = 0          # 1: the row before has started it already

    slot0 = nxt[0]

    @pl.when((n_waves > 0) & (nxt[1] == 0))
    def _():
        if shared:
            pl.when(step == 0)(functools.partial(
                wave_dma, step, 0, slot0, n_pages, True, run=True))
            pl.when(step > 0)(functools.partial(
                wave_dma, step, 0, slot0, n_pages, True))
        else:
            wave_dma(step, 0, slot0, n_pages, start=True)

    def dequant(x, scales):
        """[keys, H*D] int8 x [keys, H] fp32 -> fp32 [keys, H*D]."""
        return jnp.concatenate(
            [x[:, h * D:(h + 1) * D].astype(jnp.float32)
             * scales[:, h:h + 1] for h in range(H)], axis=-1)

    # one group per score product: (q/k columns, v/out columns, state
    # column)
    per_head = [(slice(h * D, (h + 1) * D),) * 2 + (h,) for h in range(H)]
    if latent is not None:
        groups = [(slice(None), slice(0, latent[0]), 0)]
    elif batched:
        groups = [(slice(None), slice(None), 0)]
    else:
        groups = per_head

    def walk(fold, run=False):
        """The program's waves (`run`: the shared run's), each folded by
        `fold(w, k, v)` while the next one of the stream is in flight."""
        def wave_body(w, carry):
            slot = (slot0 + w) % 2
            # the next wave of the stream goes into the other slot: this
            # program's wave w+1 or, under its last wave, the next
            # program's first (after the run's: row 0's)
            last = w + 1 == n_waves
            if run:
                pl.when(last)(functools.partial(
                    wave_dma, after, 0, 1 - slot, after_pages, True))
                pl.when(jnp.logical_not(last))(functools.partial(
                    wave_dma, step, w + 1, 1 - slot, n_pages, True,
                    run=True))
            else:
                wave_dma(jnp.where(last, after, step),
                         jnp.where(last, 0, w + 1), 1 - slot,
                         jnp.where(last, after_pages, n_pages), start=True)
            wave_dma(step, w, slot, n_pages, start=False)
            k = kbuf[slot].reshape(keys, H * D)
            v = k if latent is not None else vbuf[slot].reshape(keys, H * D)
            if quantized:
                at = pl.ds(pl.multiple_of(w * keys, keys), keys)
                k = dequant(k, ks_ref[at, :])
                # past seq_len the gathered scales are some other page's:
                # p == 0 there, and 0 * v must stay 0 whatever they hold
                live = w * keys + jax.lax.broadcasted_iota(
                    jnp.int32, (keys, 1), 0) < seq_len
                v = dequant(v, jnp.where(live, vs_ref[at, :], 0.0))
            if latent is None:
                v = v.astype(jnp.float32)
            # (a latent call's values stay in the pool's dtype and take
            # the probabilities in it, accumulated in fp32: its chunk is
            # bound by its products, and an fp32 p.v costs the MXU
            # several passes)
            fold(w, k, v)
            return carry
        jax.lax.fori_loop(0, n_waves, wave_body, 0)

    def softmax_fold(q, k, v, valid, groups, m_ref, l_ref, acc_ref):
        """One wave into the online softmax state (m, l, acc) of the
        rows of `q`, a score product a group."""
        for cols, vcols, g in groups:
            # operands in their stored dtype (bf16 products are exact
            # in the fp32 accumulation); 1/sqrt(D) on the fp32 scores
            # (a latent call's q carries its scale)
            s = jax.lax.dot_general(
                q[:, cols].astype(k.dtype), k[:, cols],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if scale is not None:
                s = s * scale
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_ref[:, g:g + 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
            pexp = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            acc_ref[:, vcols] = acc_ref[:, vcols] * alpha \
                + jax.lax.dot_general(
                    pexp.astype(v.dtype), v[:, vcols],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            l_ref[:, g:g + 1] = alpha * l_ref[:, g:g + 1] \
                + jnp.sum(pexp, -1, keepdims=True)
            m_ref[:, g:g + 1] = m_new

    def row_fold(w, k, v):
        # global positions: rows = this step's queries (a batched row
        # is query row // H), cols = this wave's keys; causal within
        # the sequence + ragged length mask
        row = jax.lax.broadcasted_iota(jnp.int32, (R, keys), 0)
        if batched:
            token = row // (H * group)
            if latent is not None:
                token = token0 + token
        else:
            token = row if group == 1 else row % (R // group)
        q_pos = seq_len - q_len + token
        key_pos = w * keys + jax.lax.broadcasted_iota(
            jnp.int32, (R, keys), 1)
        if base is not None:
            key_pos = base * ps + key_pos
        valid = (key_pos < seq_len) & (key_pos <= q_pos)
        if window is not None:
            valid = valid & (key_pos > q_pos - window)
        softmax_fold(q_ref, k, v, valid, groups, m_s, l_s, acc_s)

    def run_fold(w, k, v):
        # every key of the run lies before every member's query: only
        # a partial last wave's stale slots are masked
        key_pos = w * keys + jax.lax.broadcasted_iota(
            jnp.int32, (qs_ref.shape[0], keys), 1)
        softmax_fold(qs_ref, k, v, key_pos < run_ref[0] * ps, per_head,
                     m_sh, l_sh, acc_sh)

    def take_run_state():
        """A member row's state opens as its rows of the run's: row h
        (query head h = j*group+g) takes row b*group+g of it — kv head
        j's column of (m, l), every column of acc (the wrapper keeps
        kv head j's)."""
        # the member's rows, in the 8-row tile(s) that hold them (a
        # load's first row must be a multiple of 8)
        span = max(group, 8)
        first = b * group // span * span
        mine = pl.ds(pl.multiple_of(first, 8), span)
        head = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0)
        at = b * group - first + head % group

        def spread(x):
            """[span, C] -> [R, C]: row h takes x[at[h]]."""
            out = jnp.broadcast_to(x[0:1], (R, x.shape[1]))
            for r in range(1, span):
                out = jnp.where(at == r, x[r:r + 1], out)
            return out
        own = jax.lax.broadcasted_iota(jnp.int32, (R, H), 1) \
            == head // group
        for src, dst in ((m_sh, m_s), (l_sh, l_s)):
            dst[...] = jnp.sum(jnp.where(own, spread(src[mine, :]), 0.0),
                               -1, keepdims=True)
        acc_s[...] = spread(acc_sh[mine, :])

    def row_program():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)
        if shared:
            pl.when(base > 0)(take_run_state)
        walk(row_fold)
        l_safe = jnp.maximum(l_s[...], 1e-30)
        for _, vcols, g in groups:
            o_ref[:, vcols] = (acc_s[:, vcols] / l_safe[:, g:g + 1]) \
                .astype(o_ref.dtype)

    if shared:
        @pl.when((step == 0) & (n_waves > 0))
        def _():
            m_sh[...] = jnp.full_like(m_sh, NEG_INF)
            l_sh[...] = jnp.zeros_like(l_sh)
            acc_sh[...] = jnp.zeros_like(acc_sh)
            walk(run_fold, run=True)
        # (program 0 writes no output: its block is row 0's, which
        # program 1 writes before the block moves on)
        pl.when(step > 0)(row_program)
    else:
        row_program()
    nxt[0] = (slot0 + n_waves) % 2
    nxt[1] = ((n_waves > 0) & (after_pages > 0)).astype(jnp.int32)


def ragged_paged_attention_pallas(q, k_pages, v_pages, page_tables,
                                  seq_lens, q_lens, *, num_heads,
                                  head_dim, k_scales=None,
                                  v_scales=None, interpret=None,
                                  num_kv_heads=None, window=None, diff=1,
                                  latent=None, shared=None):
    """Pallas route (interpret-mode on CPU). See module docstring for
    layouts; k_scales/v_scales engage the int8 dequantizing body;
    `num_kv_heads` (default: num_heads), `window` (default: every key),
    `diff` (key sub-heads that share one value block; then the
    output is [B, T, num_heads * diff * head_dim]) and `latent` ((value
    lanes, rotary lanes): `v_pages` None, `head_dim` the stored row's
    lanes, q [B, T, num_heads * head_dim] with its scale in it, the
    output [B, T, num_heads * value lanes]) are static. `shared` (see
    `ragged_paged_attention`) is read once for its member rows where
    `shared_pass` takes it, and ignored elsewhere.

    What the shapes decide is decided here; the call itself is one
    jitted function, so a model's layers — the same shapes 24 times in
    one step program — share ONE trace of the kernel body and ONE
    Mosaic lowering (jit caches both by shapes and static arguments)
    where each layer used to pay its own."""
    T = q.shape[1]
    if latent is not None:
        return _latent_call(q, k_pages, v_pages, page_tables, seq_lens,
                            q_lens, num_heads, head_dim, latent,
                            k_scales, num_kv_heads, window, diff,
                            interpret)
    kv_heads = num_kv_heads or num_heads
    if num_heads % kv_heads:
        raise ValueError(f'{num_heads} query heads do not divide over '
                         f'{kv_heads} kv heads')
    group = num_heads // kv_heads
    if k_scales is not None and (group > 1 or window is not None
                                 or diff > 1):
        raise NotImplementedError(
            'int8 pages with kv groups, a window or value sharing: the '
            'scale blocks are laid out for one kv head a query head and '
            'read from the table\'s slot 0')
    if diff > 1:
        # kv heads of width diff * D; a query sub-head keeps its own
        # sub-head's D columns of it and zeros elsewhere
        if kv_heads % diff:
            raise ValueError(f'{kv_heads} key sub-heads do not pair up '
                             f'by {diff}')
        B = q.shape[0]
        own = jnp.eye(diff, dtype=bool)[:, :, None]     # [s, s', 1]
        q = jnp.where(own, q.reshape(B, T, -1, diff, 1, head_dim), 0) \
            .reshape(B, T, num_heads * diff * head_dim)
        kv_heads, head_dim, group = kv_heads // diff, diff * head_dim, \
            group * diff
    batched = T * num_heads <= _BATCHED_ROWS
    if not shared_pass(T, num_heads, num_kv_heads, k_scales, window, diff,
                       latent):
        shared = None
    W, need = _wave_pages(
        k_pages.shape[1], k_pages.shape[2], k_pages.dtype,
        T * num_heads if batched else T * group, q.dtype,
        page_tables.shape[1], kv_heads, k_scales is not None,
        run_rows=0 if shared is None else -(-q.shape[0] * group // 8) * 8)
    return _paged_call(
        q, k_pages, v_pages, page_tables, seq_lens, q_lens, k_scales,
        v_scales, num_heads=kv_heads, head_dim=head_dim, wave_pages=W,
        batched=batched, vmem_bytes=need, group=group, window=window,
        diff=diff, shared=shared,
        interpret=_interpret() if interpret is None else interpret)


def shared_pass(T, num_heads, num_kv_heads=None, k_scales=None,
                window=None, diff=1, latent=None):
    """Whether a call of these static arguments reads a shared run once
    for its member rows: decode rows (T == 1) of the plain body, with
    or without kv groups (query heads a kv head that tile 8 rows or are
    whole tiles of them). A window call (a row reads only its last
    keys), a differential or latent call, an int8 pool and a chunk (its
    keys are read once for all its queries already) read every row's
    own table."""
    group = num_heads // (num_kv_heads or num_heads)
    return (T == 1 and num_heads <= _BATCHED_ROWS and k_scales is None
            and window is None and diff == 1 and latent is None
            and (8 % group == 0 or group % 8 == 0))


def _check_latent(latent, head_dim, row_lanes, v_pages, k_scales,
                  num_kv_heads, window, diff):
    """What a latent call may be: one stored head whose row holds the
    value and the rotary lanes, no second pool, no scales, window or
    shared value blocks."""
    if (v_pages is not None or k_scales is not None
            or num_kv_heads not in (None, 1) or window is not None
            or diff > 1):
        raise NotImplementedError(
            'a latent plane is ONE array read by every query head: no '
            'v_pages, int8 scales, kv groups, window or diff with it')
    value, rotary = latent
    if not 0 < value <= value + rotary <= head_dim == row_lanes:
        raise ValueError(
            f'latent {latent}: {value} value + {rotary} rotary lanes in '
            f'a query row of {head_dim} and a stored row of {row_lanes}')


def _latent_call(q, pages, v_pages, page_tables, seq_lens, q_lens,
                 num_heads, head_dim, latent, k_scales, num_kv_heads,
                 window, diff, interpret):
    """The latent call's static choices: every head of a token a row
    of the one score product (the batched layout, rows t*Hq+h), a
    batch row in query tiles of `latent_tile_tokens` tokens — one tile
    for the decode rows and a verify row, `_LATENT_TILE_ROWS` rows each
    for a prompt chunk."""
    _check_latent(latent, head_dim, pages.shape[2], v_pages, k_scales,
                  num_kv_heads, window, diff)
    T = q.shape[1]
    tile = latent_tile_tokens(T, num_heads)
    W, need = _wave_pages(
        pages.shape[1], head_dim, pages.dtype, tile * num_heads, q.dtype,
        page_tables.shape[1], 1, False, planes=1)
    return _paged_call(
        q, pages, None, page_tables, seq_lens, q_lens, None, None,
        num_heads=1, head_dim=head_dim, wave_pages=W, batched=True,
        vmem_bytes=need, group=num_heads, latent=tuple(latent),
        q_tiles=T // tile,
        interpret=_interpret() if interpret is None else interpret)


@functools.partial(jax.jit, static_argnames=(
    'num_heads', 'head_dim', 'wave_pages', 'batched', 'vmem_bytes',
    'interpret', 'group', 'window', 'diff', 'latent', 'q_tiles'))
def _paged_call(q, k_pages, v_pages, page_tables, seq_lens, q_lens,
                k_scales, v_scales, *, num_heads, head_dim, wave_pages,
                batched, vmem_bytes, interpret, group=1, window=None,
                diff=1, latent=None, q_tiles=1, shared=None):
    """The block-diagonal q (when `batched`; else, with kv groups, the
    query heads of one kv head stacked as rows), the Mosaic call and
    the diagonal blocks of its output, as one jitted function of the
    shapes and the wrapper's static choices. `num_heads` counts the kv
    heads, `group` the query heads on each; a latent call (`v_pages`
    None) takes q as it lies (rows t*Hq+h: its one stored head has
    every column), runs each batch row as `q_tiles` programs of `T //
    q_tiles` tokens and puts out `latent[0]` lanes a head. `shared`
    (decode rows of the batched layout): (run pages [P], run keys, member
    rows [B]) -> a program before the rows' that reads the run once."""
    B, T = q.shape[:2]
    N, ps, HD = k_pages.shape
    P = page_tables.shape[1]
    H, W, D = num_heads, wave_pages, head_dim
    quantized = k_scales is not None
    pt = page_tables.astype(jnp.int32)
    lens = [seq_lens.astype(jnp.int32), q_lens.astype(jnp.int32)]
    prefetch = [pt]
    if shared is not None:
        run_pt, run_keys, members = shared
        run_pages = jnp.asarray(run_keys, jnp.int32).reshape(()) // ps
        # a member's loop opens after the run; a row with no query, or
        # whose context does not reach past the run, is no member
        lens.append(jnp.where(
            members.astype(bool) & (lens[1] > 0)
            & (lens[0] > run_pages * ps), run_pages, 0))
        prefetch.append(jnp.stack(lens, axis=1))
        prefetch.append(jnp.concatenate(
            [run_pages[None], run_pt.astype(jnp.int32)]))       # [1 + P]
        # the members' queries as rows b*group+g, query head j*group+g
        # in kv head j's columns: one product a kv head
        # (whole 8-row tiles: a member's rows are loaded by the tile)
        run_rows = -(-B * group // 8) * 8
        q_run = q.reshape(B, H, group, D).transpose(0, 2, 1, 3) \
            .reshape(B * group, HD)
        q_run = jnp.pad(q_run, ((0, run_rows - B * group), (0, 0)))
    else:
        prefetch.append(jnp.stack(lens, axis=1))                # [B, 2]
    if latent is not None:
        q = q.reshape(B, T * group, HD)
    elif batched and group == 1:
        # row t*H+h = query t masked to head h's columns
        own = (jnp.arange(HD, dtype=jnp.int32)[None, :] // head_dim
               == jnp.arange(H, dtype=jnp.int32)[:, None])      # [H, HD]
        q = jnp.where(own, q[:, :, None, :], 0).reshape(B, T * H, HD)
    elif batched:
        # row t*Hq+h = query head h of token t in kv head h // group's
        # columns
        own = (jnp.arange(H * group, dtype=jnp.int32)[:, None] // group
               == jnp.arange(H, dtype=jnp.int32)[None, :])     # [Hq, H]
        q = jnp.where(own[:, :, None],
                      q.reshape(B, T, H * group, 1, D), 0) \
            .reshape(B, T * H * group, HD)
    elif group > 1:
        # row g*T+t = the g-th query head of every kv head, token t
        q = q.reshape(B, T, H, group, D).transpose(0, 3, 1, 2, 4) \
            .reshape(B, group * T, HD)
    R = q.shape[1]
    row_spec = out_spec = pl.BlockSpec((None, R, HD),
                                       lambda b, *_: (b, 0, 0))
    pool_spec = pl.BlockSpec(memory_space=pltpu.HBM)
    in_specs = [row_spec, pool_spec, pool_spec]
    inputs = [q, k_pages, v_pages]
    run_scratch = []
    if shared is not None:
        # program 0 is the run's, program i the row i-1's
        row_spec = out_spec = pl.BlockSpec(
            (None, R, HD), lambda i, *_: (jnp.maximum(i - 1, 0), 0, 0))
        in_specs = [row_spec, pl.BlockSpec((run_rows, HD),
                                           lambda i, *_: (0, 0)),
                    pool_spec, pool_spec]
        inputs.insert(1, q_run)
        run_scratch = [pltpu.VMEM((run_rows, H), jnp.float32),
                       pltpu.VMEM((run_rows, H), jnp.float32),
                       pltpu.VMEM((run_rows, HD), jnp.float32)]
    OD = HD                 # lanes of the accumulator and the output
    if latent is not None:
        # no V pool; rows t*Hq+h of a batch row in `q_tiles` tiles of
        # whole tokens; the output as wide as the values
        OD, R = latent[0], R // q_tiles

        def tile(i, *_):
            return (i // q_tiles, i % q_tiles, 0)
        row_spec = pl.BlockSpec((None, R, HD), tile)
        out_spec = pl.BlockSpec((None, R, OD), tile)
        in_specs, inputs = [row_spec, pool_spec], inputs[:-1]
    if quantized:
        # Mosaic cannot slice a DMA source whose minor dim (H) is under
        # the 128-lane tile, so the scales do not ride the page copies:
        # XLA gathers each row's [P*ps, H] (padded to whole waves; ids
        # clamped, every slot past seq_len is masked) and the pipeline
        # brings the row's block
        Pw = -(-P // W) * W
        ids = jnp.pad(jnp.clip(pt, 0, N - 1), ((0, 0), (0, Pw - P)))
        scale_spec = pl.BlockSpec((None, Pw * ps, H),
                                  lambda b, *_: (b, 0, 0))
        in_specs += [scale_spec, scale_spec]
        inputs += [k_scales[ids].reshape(B, Pw * ps, H),
                   v_scales[ids].reshape(B, Pw * ps, H)]
    G = 1 if batched else H
    wave = pltpu.VMEM((2, W, ps, HD), k_pages.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B * q_tiles + (shared is not None),),
        in_specs=in_specs,
        out_specs=out_spec,
        scratch_shapes=[
            wave,                                          # K wave slots
            *([wave] if latent is None else []),           # V wave slots
            pltpu.SemaphoreType.DMA((2, 2)),               # [slot, K|V]
            pltpu.SMEM((2,), jnp.int32),       # next wave-0 slot, started
            pltpu.VMEM((R, G), jnp.float32),               # running max
            pltpu.VMEM((R, G), jnp.float32),               # normalizer
            pltpu.VMEM((R, OD), jnp.float32),              # accumulator
            *run_scratch,               # the run's max, normalizer, acc
        ],
    )
    kernel = functools.partial(
        _ragged_paged_kernel, page_size=ps, num_heads=H,
        head_dim=head_dim, wave_pages=W, batched=batched,
        quantized=quantized, group=group, window=window,
        diff=diff, latent=latent, q_tiles=q_tiles,
        shared=shared is not None)
    out = scaffold.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, R * q_tiles, OD), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',),
            vmem_limit_bytes=int(min(max(vmem_bytes, 16 * 2 ** 20),
                                     scaffold.VMEM_CAP_BYTES))),
        interpret=interpret,
        # a call with a window, with shared value blocks or on a
        # latent plane has its own row in the profile, and so has the
        # call of a group whose rows carry more than one query (`_chunk`,
        # last: the mixed step's prompt chunks, and a verify step's
        # token + drafts) beside the decode rows' (the
        # `paged_attention*` readers sum them all)
        name='paged_attention' + ('_diff' if diff > 1 else '')
        + ('' if window is None else '_window')
        + ('' if latent is None else '_latent')
        + ('_chunk' if T > 1 else ''),
    )(*prefetch, *inputs)
    if latent is not None:
        return out.reshape(B, T, group * OD)
    if batched and group == 1:
        # each head's output is its own diagonal block of the rows
        out = jnp.where(own, out.reshape(B, T, H, HD), 0).sum(axis=2)
    elif batched:
        out = jnp.where(own[:, :, None],
                        out.reshape(B, T, H * group, H, D), 0) \
            .sum(axis=3).reshape(B, T, H * group * D)
    elif group > 1:
        out = out.reshape(B, group, T, H, D).transpose(0, 2, 3, 1, 4) \
            .reshape(B, T, H * group * D)
    return out


def _dequant_gathered(pages, scales, H):
    """[B, P, ps, H*D] int8 + [B, P, ps, H] fp32 -> fp32 pages."""
    B, P, ps, HD = pages.shape
    D = HD // H
    return (pages.astype(jnp.float32).reshape(B, P, ps, H, D)
            * scales.astype(jnp.float32)[..., None]) \
        .reshape(B, P, ps, HD)


def ragged_paged_attention_dense(q, k_pages, v_pages, page_tables,
                                 seq_lens, q_lens, *, num_heads,
                                 head_dim, k_scales=None, v_scales=None,
                                 num_kv_heads=None, window=None, diff=1,
                                 latent=None):
    """Dense lax fallback: gather each row's pages into a [B, P*ps, H*D]
    context and run masked attention. O(B * pages_per_seq * page_size)
    memory — correct everywhere (the CPU serving path and the numerics
    oracle for the kernel), not the TPU hot path. Int8 pages are
    dequantized right after the gather (same per-(slot, head) scales
    the kernel applies in VMEM). `latent`: every head reads the one
    stored row whole, unscaled, and multiplies its first value lanes."""
    B, T = q.shape[:2]
    ps, HD = k_pages.shape[1:]
    P = page_tables.shape[1]
    D = head_dim
    if latent is not None:
        _check_latent(latent, head_dim, HD, v_pages, k_scales,
                      num_kv_heads, window, diff)
        num_kv_heads, v_pages = 1, k_pages
    kv_heads = num_kv_heads or num_heads
    group = num_heads // kv_heads
    pt = jnp.clip(page_tables.astype(jnp.int32), 0,
                  k_pages.shape[0] - 1)
    if k_scales is not None:
        k = _dequant_gathered(k_pages[pt], k_scales[pt], kv_heads) \
            .reshape(B, P * ps, HD)
        v = _dequant_gathered(v_pages[pt], v_scales[pt], kv_heads) \
            .reshape(B, P * ps, HD)
    else:
        k = k_pages[pt].reshape(B, P * ps, HD).astype(jnp.float32)
        v = v_pages[pt].reshape(B, P * ps, HD).astype(jnp.float32)
    scale = 1.0 if latent is not None else 1.0 / math.sqrt(D)
    q_pos = (seq_lens[:, None] - q_lens[:, None]
             + jnp.arange(T, dtype=jnp.int32)[None, :])        # [B, T]
    key_pos = jnp.arange(P * ps, dtype=jnp.int32)[None, None, :]
    valid = (key_pos < seq_lens[:, None, None]) & \
            (key_pos <= q_pos[:, :, None])                     # [B, T, K]
    if window is not None:
        valid = valid & (key_pos > q_pos[:, :, None] - window)
    outs = []
    for h in range(num_heads):
        qh = q[:, :, h * D:(h + 1) * D].astype(jnp.float32) * scale
        j = h // group          # the kv head this query head reads
        if diff > 1:
            # query sub-head (p, s) reads key sub-head s of kv block
            # p // group and the block's whole diff * D of values
            blk = h // diff // group
            j = blk * diff + h % diff
            vh = v[:, :, blk * diff * D:(blk + 1) * diff * D]
        elif latent is not None:
            vh = v[:, :, :latent[0]]
        else:
            vh = v[:, :, j * D:(j + 1) * D]
        kh = k[:, :, j * D:(j + 1) * D]
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
                           k_scales=None, v_scales=None,
                           num_kv_heads=None, window=None, diff=1,
                           latent=None, shared=None):
    """Auto-routed entry (array-level; used inside the serving engine's
    jitted steps). Pass k_scales/v_scales for int8 pages; num_kv_heads
    where fewer kv heads than query heads are stored (the pool's width
    is num_kv_heads * head_dim), window where a query reads only its
    last `window` keys, diff where that many neighbouring key sub-heads
    share one value block (the output is then diff * head_dim wide a
    query sub-head), latent=(value lanes, rotary lanes) with v_pages
    None where the pool is ONE array of rows every head reads (head_dim
    the row's lanes, the scale in q, the output value lanes a head).

    `shared` = (run pages int32 [pages_per_seq], run keys int32, member
    rows bool [B]) or None: a leading page run that every member row's
    table maps to the same pool pages, whole pages, each before that
    row's last page. The kernel reads it ONCE for all the members and
    each member's own pages after it, merged in one softmax; rows that
    are not members read as without. Its answer is the same attention:
    the dense route, and every call `shared_pass` refuses, read each
    row's whole table."""
    if q_lens is None:
        q_lens = jnp.full((q.shape[0],), q.shape[1], jnp.int32)
    if not use_pallas_route():
        return ragged_paged_attention_dense(
            q, k_pages, v_pages, page_tables, seq_lens, q_lens,
            num_heads=num_heads, head_dim=head_dim, k_scales=k_scales,
            v_scales=v_scales, num_kv_heads=num_kv_heads, window=window,
            diff=diff, latent=latent)
    return ragged_paged_attention_pallas(
        q, k_pages, v_pages, page_tables, seq_lens, q_lens,
        num_heads=num_heads, head_dim=head_dim, k_scales=k_scales,
        v_scales=v_scales, num_kv_heads=num_kv_heads, window=window,
        diff=diff, latent=latent, shared=shared)


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


def write_latent_pages(pages, new, page_tables, seq_lens, q_lens):
    """write_kv_pages for a latent plane: `new` [B, T, lanes] rows into
    the ONE array; lanes past them (the plane's padding to whole tiles)
    are written as zeros."""
    N, ps, HD = pages.shape
    B, T, lanes = new.shape
    flat = _flat_slots(page_tables, seq_lens, q_lens, T, N, ps)
    new = jnp.pad(new.astype(pages.dtype), ((0, 0), (0, 0), (0, HD - lanes)))
    return pages.reshape(N * ps, HD).at[flat].set(
        new.reshape(B * T, HD), mode='drop').reshape(N, ps, HD)


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
