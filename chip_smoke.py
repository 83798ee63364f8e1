#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two main paths once, through the entry points a user calls, at
GPT-1.3B's published widths (hidden 2048, 16 heads of 128, vocab 50304,
sequence 2048, bf16; only DEPTH may be cut and is printed) with seeded
random weights:

  kernels    every `pl.pallas_call` site in paddle_tpu/ops/pallas/, compiled
             by Mosaic, against its own reference at the shapes the two
             phases below produce — so a refusal names the kernel and shape
  train      SpmdPipelineEngine (1F1B, remat, AdamW with bf16 moments) fed by
             DeviceLoader + train_step + flush: 1 warm-up + 3 steps on a
             repeated batch, then the same first step at a cut depth with
             every Pallas route forced off (kernel-vs-XLA agreement)
  serve      ServingEngine over the paged KV pool: 8 seeded prompts of
             32-384 tokens, 32 greedy tokens each, checked against the
             dense forward; then the fused_k and spec_k step shapes
  multichip  (>= 4 devices) pp2 x mp2 pipeline, sharding2 x mp2 ZeRO-hybrid
             and an mp=2 server, each against a one-chip golden run

One process runs everything (a chip belongs to one process) and shuts each
engine down before the next phase. Any failure in any phase raises: the
exit code is non-zero and no result line is printed. It claims nothing:
it prints compile seconds and pass/fail, never a throughput.

    python3 chip_smoke.py [phase ...]      # default: every phase

The last line of stdout is one JSON object with exactly these keys,
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`;
the line before it is the `summary` (phases, compile seconds, cache hits,
`"claim": null`).
"""
import gc
import json
import math
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# GPT-1.3B (the benchmark's `gpt3-1.3b` configuration). `depth`/`ab_depth`/
# `serve_depth`/`multi_depth` are the only fields a time or memory budget
# may cut; tests/test_chip_smoke.py passes a tiny dict of the same shape.
FULL = dict(
    vocab=50304, hidden=2048, heads=16, seq=2048,
    # train: A microbatches of mb sequences; 1 warm-up + `steps` steps
    depth=24, ab_depth=2, A=4, mb=2, steps=3, lr=1e-4,
    # serve: page and chunk of the benchmark's server cell, a batch of 8
    serve_depth=24, page_size=16, batch=8, chunk=128,
    prompt_lo=32, prompt_hi=384, new_tokens=32, requests=8,
    fused_k=4, spec_k=2,
    # kernels: row-blocked kernels see mb*seq rows of hidden / 4*hidden
    kernel_seq=2048, opt_elems=2048 * 4096 + 1000,
    # multichip: depth cut so the one-chip golden run (fp32 parameters,
    # grads and AdamW moments, 16 B/param) fits a 16 GB chip
    multi_depth=4, multi_seq=1024, multi_steps=2,
    # kernels of the sparse, grouped-query, windowed server cell
    # (benchmarks/configs/trinity-mini.json): 32 query heads on 4 kv
    # heads of 128, window 2048 over 528-page tables, contexts on both
    # sides of the window and at the table's end; 128 experts top-8 of
    # width 1024 at the decode step's and the prefill chunk's rows
    sparse=dict(heads=32, kv_heads=4, head_dim=128, window=2048,
                page_size=16, pages=528, batch=64, chunk=512,
                contexts=(16, 2047, 2049, 8448),
                experts=128, top_k=8, hidden=2048, width=1024),
    # kernels of the state-space, differential-attention server cell
    # (benchmarks/configs/phi4-mini-flash.json): 40 query sub-heads on
    # 20 key sub-heads of 64, pairs of them on one 128-wide value,
    # window 512 over 176-page tables, contexts on both sides of the
    # window and at the table's end; the selective scan over 5120
    # channels x 16 states at the decode rows' and the chunk's widths
    hybrid=dict(heads=40, kv_heads=20, head_dim=64, window=512,
                page_size=16, pages=176, batch=64, chunk=128,
                contexts=(16, 511, 513, 2816), prefill_rows=2,
                channels=5120, states=16),
    # the latent body of the paged kernel as the latent-attention server
    # cell calls it (benchmarks/configs/axk1.json): 64 query heads on
    # ONE stored row of 512 value + 64 rotary lanes in 640, pages of 64
    # over 528-page tables, ragged contexts from one page to the
    # table's end, at the decode rows' and the chunk's widths; the chunk
    # rows full, then PARTLY filled (`ragged`: live tokens a row, behind
    # the longest contexts — live, partly live and dead query tiles of
    # 32 tokens, the second pair a full row beside an idle one)
    latent=dict(heads=64, value=512, rotary=64, page_size=64, pages=528,
                batch=64, chunk=256, prefill_rows=2,
                contexts=(64, 700, 8448, 33792),
                ragged=((161, 33), (256, 0))),
    # the gated-conv server cell's kernels (benchmarks/configs/
    # lfm2-24b-a2b.json): 32 query heads on 8 kv heads of 64, pages of 64
    # over 448-page tables at contexts from one page to the table's end,
    # and the decode rows with a 64-page run most of them share;
    # the short convolution over 2048 channels, 3 taps, 7 conv layers of
    # page state, at the decode rows' and the chunk's widths
    conv=dict(heads=32, kv_heads=8, head_dim=64, page_size=64, pages=448,
              batch=128, chunk=256, prefill_rows=2,
              contexts=(64, 700, 7000, 28672), channels=2048, taps=3,
              layers=7),
)

# Tolerances, as max|got - ref| / max|ref| over a tensor.
# bf16 results: the kernels' own tests state rtol = atol = 2e-2 for bf16
# (tests/test_fused_primitives.py) — a bf16 value carries 8 mantissa bits
# (2^-8 = 3.9e-3 per rounding) and fwd+bwd chains a few roundings.
TOL_BF16 = 2e-2
# fp32 results (optimizer states, reductions): tests/test_flash_attention.py
# states rtol 5e-4 for fp32 kernel-vs-reference; blockwise accumulation
# reorders sums, nothing more.
TOL_F32 = 5e-4
# loss agreement between the kernel and the XLA routes of one bf16 forward:
# the loss is an fp32 mean over bf16 logits, so one bf16 rounding bounds it.
TOL_LOSS = 2 ** -8
# serving: the engine's greedy token must be the dense forward's argmax up
# to a logit gap bf16 noise explains. Logits are ~N(0, 1) over 50k entries,
# reached through ~100 bf16-rounded ops per layer stack; two routes of the
# same math differ by a few percent of the logit scale.
TOL_LOGIT_GAP = 0.05
# four chips vs the one-chip golden run: the tolerances
# __graft_entry__._dryrun_multichip_body already uses
TOL_PIPELINE, TOL_ZERO = 2e-3, 5e-3


def log(*a):
    print('[chip_smoke]', *a, flush=True)


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


class CompileMeter:
    """Counts XLA compilations (cache loads included) and their seconds
    from JAX's own monitoring event."""
    EVENT = '/jax/core/compile/backend_compile_duration'

    def __init__(self):
        self.count, self.seconds = 0, 0.0
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.count += 1
            self.seconds += float(duration)


def rel_err(got, ref):
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    check(got.shape == ref.shape, f'shape {got.shape} vs {ref.shape}')
    check(np.isfinite(got).all(), 'non-finite values in kernel output')
    return float(np.abs(got - ref).max() / (np.abs(ref).max() + 1e-30))


def routes_since(before):
    """Pallas routing decisions (kernel / fallback per primitive) made
    since the `before` snapshot."""
    from paddle_tpu.ops.pallas import scaffold
    out = {}
    for prim, c in scaffold.routes_snapshot().items():
        b = before.get(prim, {})
        d = {k: c[k] - b.get(k, 0) for k in c}
        if any(d.values()):
            out[prim] = d
    return out


def check_routes(routes, required, where):
    """fallback == 0 for every primitive routed, kernel > 0 for the
    `required` ones."""
    for prim, c in routes.items():
        check(c.get('fallback', 0) == 0,
              f'{where}: {prim} took the reference route {c}')
    for prim in required:
        check(routes.get(prim, {}).get('kernel', 0) > 0,
              f'{where}: {prim} never routed to its kernel ({routes})')


def set_pallas_routes(value):
    """Force every Pallas route on (True), off (False) or back to auto
    (None: kernel on TPU, reference on CPU)."""
    from paddle_tpu.core import flags
    flags.set_flags({k: value for k in (
        'FLAGS_fused_optimizer', 'FLAGS_fused_layer_norm',
        'FLAGS_fused_elementwise', 'FLAGS_paged_attention_kernel')})


def to_bf16(layers):
    import jax.numpy as jnp
    for layer in layers:
        for p in layer.parameters():
            if p.data.dtype == jnp.float32:
                p.data = p.data.astype(jnp.bfloat16)


def drop_eager(layers):
    """The engine owns device copies; free the eager duplicates."""
    import jax.numpy as jnp
    for layer in layers:
        for p in layer.parameters():
            p._data = jnp.zeros((1,), p.data.dtype)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------
def phase_kernels(size):
    """Each pallas_call site vs its reference; returns {check: error}."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.core import bucketing as B
    from paddle_tpu.core import flags
    from paddle_tpu.ops.pallas import (flash_attention as fa,
                                       fused_elementwise as fe,
                                       fused_norm as fnorm,
                                       fused_optimizer as fo,
                                       paged_attention as pa)

    bf = jnp.bfloat16
    H, D = size['heads'], size['hidden'] // size['heads']
    HD, L, mb = size['hidden'], size['kernel_seq'], size['mb']
    rng = np.random.RandomState(0)
    errs = {}

    def rand(shape, dtype=bf, scale=1.0):
        return jnp.asarray((rng.randn(*shape) * scale).astype(dtype))

    def ref_call(fn, *args, upcast=True):
        """The reference at the highest matmul precision, its bf16
        inputs upcast so the math runs in fp32."""
        def up(x):
            return x.astype(jnp.float32) if upcast and x.dtype == bf else x
        with jax.default_matmul_precision('highest'):
            return jax.jit(lambda *a: fn(*jax.tree_util.tree_map(up, a)))(
                *args)

    def record(name, got, ref, tol):
        got = got if isinstance(got, (tuple, list)) else (got,)
        ref = ref if isinstance(ref, (tuple, list)) else (ref,)
        e = max(rel_err(g, r) for g, r in zip(got, ref))
        errs[name] = e
        log(f'kernels  {name:<44s} err {e:.2e}  (tol {tol:.0e})')
        check(e <= tol, f'kernel {name}: error {e:.3e} > {tol:.0e}')

    def fwd_bwd(fn, n):
        """(cotangent, *args) -> (out, grads of the first n args). The
        other args ride along as plain arguments: closed over they would
        be baked into the program as constants (a 128 MB mask made
        minutes of compile and gigabytes of cache)."""
        def run(cot, *args):
            o, vjp = jax.vjp(lambda *d: fn(*d, *args[n:]), *args[:n])
            return (o,) + vjp(cot.astype(o.dtype))
        return run

    # -- flash attention: packed (the GPT default) and BHLD, fwd + bwd ----
    q, k, v = (rand((mb * H, L, D)) for _ in range(3))
    w = rand((mb * H, L, D), jnp.float32)

    def unpack(x):          # [BH, L, D] -> packed [B, L, H*D]
        return x.reshape(mb, H, L, D).transpose(0, 2, 1, 3) \
            .reshape(mb, L, HD)

    def repack(x):
        return x.reshape(mb, L, H, D).transpose(0, 2, 1, 3) \
            .reshape(mb * H, L, D)

    ref = ref_call(fwd_bwd(
        lambda q, k, v: fa._reference_attention(q, k, v, causal=True), 3),
        w, q, k, v)
    record(f'flash_bhld fwd+bwd L={L} d={D}',
           jax.jit(fwd_bwd(fa.flash_attention_bhld, 3))(w, q, k, v), ref,
           TOL_BF16)
    record(f'flash_packed fwd+bwd L={L} HD={HD}', jax.jit(fwd_bwd(
        lambda q, k, v, bias: repack(fa._flash_attn_packed(
            True, H, D, unpack(q), unpack(k), unpack(v), bias)), 3))(
        w, q, k, v, jnp.zeros((mb, L), jnp.float32)), ref, TOL_BF16)

    # -- dropout-fused flash at one rate ---------------------------------
    rate = 0.1
    mask8 = jnp.asarray(rng.rand(mb * H, L, L) >= rate, jnp.int8)

    def ref_dropout(q, k, v, keep):
        s = jnp.einsum('bqd,bkd->bqk', q, k) / math.sqrt(D)
        s = jnp.where(jnp.tril(jnp.ones((L, L), bool)), s, fa.NEG_INF)
        p = jax.nn.softmax(s, axis=-1) * keep / (1.0 - rate)
        return jnp.einsum('bqk,bkd->bqd', p, v)

    record(f'flash_dropout fwd+bwd L={L} rate={rate}',
           jax.jit(fwd_bwd(lambda q, k, v, keep: fa._flash_attn_dropout(
               rate, q, k, v, keep), 3))(w, q, k, v, mask8),
           ref_call(fwd_bwd(ref_dropout, 3), w, q, k, v, mask8), TOL_BF16)
    del q, k, v, w, ref, mask8

    # -- ragged paged attention at the serving step shapes -----------------
    P = -(-(size['prompt_hi'] + size['new_tokens']) // size['page_size'])
    Bs = size['batch']

    def paged_case(Bq, T, ps, int8, every_seq=None):
        n_pages = Bq * P + 3
        cap = P * ps
        if every_seq is None:
            seq = rng.randint(T, cap + 1, Bq).astype(np.int32)
            ql = rng.randint(1, T + 1, Bq).astype(np.int32)
            ql[0] = T
            if Bq > 1:      # an idle slot, as the engine pads them
                seq[-1], ql[-1] = 1, 0
        else:               # every row at one length, no idle slot
            seq = np.full(Bq, every_seq, np.int32)
            ql = np.full(Bq, min(T, every_seq), np.int32)
        pt = np.stack([rng.permutation(n_pages)[:P] for _ in range(Bq)]) \
            .astype(np.int32)
        qq = rand((Bq, T, HD))
        kf, vf = rand((n_pages, ps, HD)), rand((n_pages, ps, HD))
        scales = ()
        if int8:
            kf, ks = pa.quantize_kv_rows(kf, H)
            vf, vs = pa.quantize_kv_rows(vf, H)
            scales = (ks, vs)
        args = (qq, kf, vf, jnp.asarray(pt), jnp.asarray(seq),
                jnp.asarray(ql)) + scales

        def call(attention):
            return lambda *a: attention(
                *a[:6], num_heads=H, head_dim=D,
                **dict(zip(('k_scales', 'v_scales'), a[6:])))
        got = jax.jit(call(pa.ragged_paged_attention_pallas))(*args)
        ref = ref_call(call(pa.ragged_paged_attention_dense), *args)
        valid = (np.arange(T)[None, :] < ql[:, None])[..., None]
        record(f'paged_attention B={Bq} T={T} ps={ps} '
               f'{"int8" if int8 else "bf16"}'
               + ('' if every_seq is None else f' seq={every_seq}'),
               np.where(valid, np.asarray(got, np.float32), 0),
               np.where(valid, np.asarray(ref, np.float32), 0), TOL_BF16)

    for Bq, T in ((Bs, 1), (Bs, size['spec_k'] + 1), (1, size['chunk'])):
        paged_case(Bq, T, size['page_size'], False)
        # int8 pages: the int8 tile is (32, 128), so 32-slot pages are
        # the aligned shape; the engine's default 16-slot pages too
        paged_case(Bq, T, 2 * size['page_size'], True)
        paged_case(Bq, T, size['page_size'], True)
    # the page loop's two ends: every table slot live (the last DMA wave
    # whole), and one token (one page of a wave, the rest never copied)
    for int8 in (False, True):
        paged_case(Bs, 1, size['page_size'], int8,
                   every_seq=P * size['page_size'])
        paged_case(Bs, 1, size['page_size'], int8, every_seq=1)

    # -- kv groups + window, and the experts' grouped matmul ---------------
    sp = size.get('sparse')
    if sp:
        from paddle_tpu.ops.pallas import grouped_matmul as gmm
        Hq, Hk, Dk, ps = (sp[k] for k in ('heads', 'kv_heads', 'head_dim',
                                           'page_size'))
        pool = sp['batch'] * sp['pages'] // 8 + 3   # pages are shared out

        def grouped_paged(Bq, T, ctx, window):
            """Every row at context `ctx` (its last min(T, ctx) tokens
            new), tables drawn over one pool, against the dense route."""
            pt = rng.randint(0, pool, (Bq, sp['pages'])).astype(np.int32)
            args = (rand((Bq, T, Hq * Dk)), rand((pool, ps, Hk * Dk)),
                    rand((pool, ps, Hk * Dk)), jnp.asarray(pt),
                    jnp.full((Bq,), ctx, jnp.int32),
                    jnp.full((Bq,), min(T, ctx), jnp.int32))

            def call(attention):
                return lambda *a: attention(
                    *a, num_heads=Hq, head_dim=Dk, num_kv_heads=Hk,
                    window=window)
            got = jax.jit(call(pa.ragged_paged_attention_pallas))(*args)
            ref = ref_call(call(pa.ragged_paged_attention_dense), *args)
            live = (np.arange(T) < min(T, ctx))[None, :, None]
            record(f'paged_attention B={Bq} T={T} {Hq}q/{Hk}kv '
                   f'window={window} ctx={ctx}',
                   np.where(live, np.asarray(got, np.float32), 0),
                   np.where(live, np.asarray(ref, np.float32), 0), TOL_BF16)

        for ctx in sp['contexts']:
            for window in (sp['window'], None):
                grouped_paged(sp['batch'], 1, ctx, window)
                grouped_paged(1, sp['chunk'], ctx, window)

        def grouped(rows, label):
            """`rows` (token, expert) pairs over the experts, ragged as a
            router leaves them (a few experts empty), both halves of the
            experts' SwiGLU against the dense route."""
            Ex, Kx, Fx = sp['experts'], sp['hidden'], sp['width']
            ids = rng.randint(0, Ex - 3, rows).astype(np.int32)
            p = gmm.plan(jnp.asarray(ids), Ex,
                         gmm.tile_rows_for(rows, Ex))
            tiles = (p['tile_expert'], p['tile_block'], p['n_live'])
            x = rand((rows, Kx))[p['src']]
            w1, w3 = rand((Ex, Kx, Fx), scale=0.02), \
                rand((Ex, Kx, Fx), scale=0.02)
            w2 = rand((Ex, Fx, Kx), scale=0.02)
            keep = np.zeros(x.shape[0], bool)
            keep[np.asarray(p['dest'])] = True      # rows that hold a pair
            for name, args in (('gated', (x, w3, *tiles, w1)),
                               ('plain', (x[:, :Fx], w2, *tiles))):
                got = gmm.grouped_matmul_pallas(*args)
                ref = ref_call(gmm.grouped_matmul_dense, *args,
                               upcast=False)
                record(f'moe_grouped_matmul {label} rows={rows} {name}',
                       np.where(keep[:, None],
                                np.asarray(got, np.float32), 0),
                       np.where(keep[:, None],
                                np.asarray(ref, np.float32), 0), TOL_BF16)

        grouped(sp['batch'] * sp['top_k'], 'decode')
        grouped(sp['chunk'] * sp['top_k'], 'chunk')

    # -- differential paged attention, and the selective scan --------------
    hy = size.get('hybrid')
    if hy:
        from paddle_tpu.ops import ssm
        from paddle_tpu.ops.pallas import selective_scan as sscan
        Hq, Hk, Dk, ps = (hy[k] for k in ('heads', 'kv_heads', 'head_dim',
                                           'page_size'))
        pool = hy['batch'] * hy['pages'] // 8 + 3

        def diff_paged(Bq, T, ctx, window):
            """As grouped_paged, with pairs of key sub-heads on one
            value block: [Bq, T, Hq * 2 * Dk] against the dense route."""
            pt = rng.randint(0, pool, (Bq, hy['pages'])).astype(np.int32)
            args = (rand((Bq, T, Hq * Dk)), rand((pool, ps, Hk * Dk)),
                    rand((pool, ps, Hk * Dk)), jnp.asarray(pt),
                    jnp.full((Bq,), ctx, jnp.int32),
                    jnp.full((Bq,), min(T, ctx), jnp.int32))

            def call(attention):
                return lambda *a: attention(
                    *a, num_heads=Hq, head_dim=Dk, num_kv_heads=Hk,
                    window=window, diff=2)
            got = jax.jit(call(pa.ragged_paged_attention_pallas))(*args)
            ref = ref_call(call(pa.ragged_paged_attention_dense), *args)
            live = (np.arange(T) < min(T, ctx))[None, :, None]
            record(f'paged_attention_diff B={Bq} T={T} {Hq}q/{Hk}kv '
                   f'window={window} ctx={ctx}',
                   np.where(live, np.asarray(got, np.float32), 0),
                   np.where(live, np.asarray(ref, np.float32), 0), TOL_BF16)

        for ctx in hy['contexts']:
            for window in (hy['window'], None):
                diff_paged(hy['batch'], 1, ctx, window)
                diff_paged(hy['prefill_rows'], hy['chunk'], ctx, window)

        def scan_case(R, T):
            """R rows of T positions, ragged: a fresh row, an idle row on
            the spare slot, the rest on their own slots of R + 1."""
            dn, N = hy['channels'], hy['states']
            f32 = jnp.float32
            ql = rng.randint(1, T + 1, R).astype(np.int32)
            ql[0] = T
            slots = rng.permutation(R).astype(np.int32)
            fresh = np.zeros(R, bool)
            fresh[0] = True
            if R > 1:
                ql[-1], slots[-1] = 0, R
            args = (rand((R, T, dn), f32),
                    jax.nn.softplus(rand((R, T, dn), f32) - 3.0),
                    rand((R, T, N), f32), rand((R, T, N), f32),
                    -jnp.exp(rand((N, dn), f32)), rand((dn,), f32),
                    rand((R + 1, N, dn), f32), jnp.asarray(slots),
                    jnp.asarray(ql), jnp.asarray(fresh))
            ref = ref_call(ssm.selective_scan_ref, *args)
            got = jax.jit(sscan.selective_scan_pallas)(*args)
            # the spare slot holds what idle rows left there
            record(f'selective_scan R={R} T={T}',
                   (got[0], got[1][:R]), (ref[0], ref[1][:R]), TOL_F32)

        scan_case(hy['batch'], 1)
        scan_case(hy['prefill_rows'], hy['chunk'])

    # -- latent paged attention ---------------------------------------------
    la = size.get('latent')
    if la:
        Hq, ps = la['heads'], la['page_size']
        lanes = -(-(la['value'] + la['rotary']) // 128) * 128
        pool = la['batch'] * la['pages'] // 8 + 3

        def latent_paged(Bq, T, live=None):
            """[Bq, T] rows of ragged contexts against the ONE array of
            stored rows; the dense route a few rows at a time (it
            gathers a row's whole table). `live`: the rows' query
            tokens (default: every slot the context has tokens for),
            then behind the LONGEST contexts."""
            pt = rng.randint(0, pool, (Bq, la['pages'])).astype(np.int32)
            ctx = np.resize(la['contexts'] if live is None
                            else la['contexts'][::-1], Bq).astype(np.int32)
            q_lens = np.minimum(T if live is None else live, ctx) \
                .astype(np.int32)
            args = (rand((Bq, T, Hq * lanes), scale=0.05),
                    rand((pool, ps, lanes)), None, jnp.asarray(pt),
                    jnp.asarray(ctx), jnp.asarray(q_lens))

            def call(attention):
                return lambda q, pages, _, *a: attention(
                    q, pages, None, *a, num_heads=Hq, head_dim=lanes,
                    latent=(la['value'], la['rotary']))
            got = np.asarray(jax.jit(call(
                pa.ragged_paged_attention_pallas))(*args), np.float32)
            checked = sorted({*range(min(Bq, 4)), *range(max(Bq - 4, 0), Bq)})
            for at in range(0, len(checked), 4):
                rows = np.asarray(checked[at:at + 4])
                part = [a if a is None or a.shape[0] != Bq else a[rows]
                        for a in args]
                ref = ref_call(call(pa.ragged_paged_attention_dense), *part)
                live = (np.arange(T)[None, :]
                        < q_lens[rows][:, None])[..., None]
                record(f'paged_attention_latent B={Bq} T={T} rows '
                       f'{rows.tolist()} ctx={ctx[rows].tolist()} '
                       f'q_lens={q_lens[rows].tolist()}',
                       np.where(live, got[rows], 0),
                       np.where(live, np.asarray(ref, np.float32), 0),
                       TOL_BF16)

        latent_paged(la['batch'], 1)
        latent_paged(la['prefill_rows'], la['chunk'])
        for live in la['ragged']:
            latent_paged(la['prefill_rows'], la['chunk'], live)

    # -- kv groups of 4 at head_dim 64, and the gated short convolution ----
    cv = size.get('conv')
    if cv:
        from paddle_tpu.ops import short_conv as sconv
        from paddle_tpu.ops.pallas import short_conv as sconv_kernel
        Hq, Hk, Dk, ps = (cv[k] for k in ('heads', 'kv_heads', 'head_dim',
                                           'page_size'))
        pool = cv['batch'] * cv['pages'] // 8 + 3

        def conv_paged(Bq, T, ctx):
            pt = rng.randint(0, pool, (Bq, cv['pages'])).astype(np.int32)
            args = (rand((Bq, T, Hq * Dk)), rand((pool, ps, Hk * Dk)),
                    rand((pool, ps, Hk * Dk)), jnp.asarray(pt),
                    jnp.full((Bq,), ctx, jnp.int32),
                    jnp.full((Bq,), min(T, ctx), jnp.int32))

            def call(attention):
                return lambda *a: attention(
                    *a, num_heads=Hq, head_dim=Dk, num_kv_heads=Hk)
            got = jax.jit(call(pa.ragged_paged_attention_pallas))(*args)
            ref = ref_call(call(pa.ragged_paged_attention_dense), *args)
            live = (np.arange(T) < min(T, ctx))[None, :, None]
            record(f'paged_attention B={Bq} T={T} {Hq}q/{Hk}kv D={Dk} '
                   f'ctx={ctx}',
                   np.where(live, np.asarray(got, np.float32), 0),
                   np.where(live, np.asarray(ref, np.float32), 0), TOL_BF16)

        for ctx in cv['contexts']:
            conv_paged(cv['batch'], 1, ctx)
            conv_paged(cv['prefill_rows'], cv['chunk'], ctx)

        def conv_shared(run):
            """The decode rows with a leading run of `run` pages that
            most of them map (the sessions' system prompt), read once
            for them: one row a token past it, every 16th row on pages
            of its own, an idle row; the answer is the dense route's."""
            Bq = cv['batch']
            ctx = rng.randint(run * ps + 1, min(120, cv['pages']) * ps + 1,
                              Bq).astype(np.int32)
            ctx[0] = run * ps + 1
            ql = np.ones(Bq, np.int32)
            ql[-1] = 0
            members = np.arange(Bq) % 16 != 5
            pt = rng.randint(run, pool, (Bq, cv['pages'])).astype(np.int32)
            pt[members, :run] = np.arange(run)
            run_pt = np.zeros(cv['pages'], np.int32)
            run_pt[:run] = np.arange(run)
            args = (rand((Bq, 1, Hq * Dk)), rand((pool, ps, Hk * Dk)),
                    rand((pool, ps, Hk * Dk)), jnp.asarray(pt),
                    jnp.asarray(ctx), jnp.asarray(ql))
            got = jax.jit(lambda *a: pa.ragged_paged_attention_pallas(
                *a[:6], num_heads=Hq, head_dim=Dk, num_kv_heads=Hk,
                shared=a[6:]))(*args, jnp.asarray(run_pt),
                               jnp.int32(run * ps), jnp.asarray(members))
            ref = ref_call(lambda *a: pa.ragged_paged_attention_dense(
                *a, num_heads=Hq, head_dim=Dk, num_kv_heads=Hk), *args)
            live = (ql > 0)[:, None, None]
            record(f'paged_attention B={Bq} {Hq}q/{Hk}kv shared run={run}',
                   np.where(live, np.asarray(got, np.float32), 0),
                   np.where(live, np.asarray(ref, np.float32), 0), TOL_BF16)

        conv_shared(min(64, cv['pages'] // 2))

        def conv_case(R, T):
            """R rows of T positions: a fresh row, a row restored from
            its page before, an idle row on the spare slot, the rest on
            their own slots continuing at ragged positions."""
            D, K, L = cv['channels'], cv['taps'], cv['layers']
            span = -(-T // ps) + 1          # pages a row's tokens touch
            ql = rng.randint(1, T + 1, R).astype(np.int32)
            first = (rng.randint(1, cv['pages'] - span, R) * ps
                     + rng.randint(0, ps, R)).astype(np.int32)
            fresh, restore = np.zeros(R, bool), np.zeros(R, bool)
            ql[0], first[0], fresh[0] = T, 0, True
            slots = rng.permutation(R).astype(np.int32)
            if R > 1:
                first[1] = first[1] // ps * ps
                restore[1] = True
                ql[-1], slots[-1] = 0, R
            # the pages a row writes are its own; every other entry (the
            # page a restored row reads among them) lies apart from them
            cpool = R * span + cv['pages']
            pt = R * span + np.stack([rng.permutation(cv['pages'])
                                      for _ in range(R)])
            for r in range(R):
                at = first[r] // ps
                pt[r, at:at + span] = r * span + np.arange(span)
            pt = pt.astype(np.int32)
            args = (rand((R, T, 3 * D)), rand((K, D)),
                    rand((R + 1, K - 1, D)), rand((cpool, L, K - 1, D)),
                    L - 1, jnp.asarray(slots), jnp.asarray(ql),
                    jnp.asarray(first), jnp.asarray(pt), jnp.asarray(fresh),
                    jnp.asarray(restore), ps)
            ref = sconv.short_conv_ref(*args)
            got = sconv_kernel.short_conv_pallas(*args)
            # the spare slot holds what idle rows left there
            record(f'short_conv R={R} T={T}',
                   (got[0], got[1][:R], got[2]),
                   (ref[0], ref[1][:R], ref[2]), TOL_BF16)

        conv_case(cv['batch'], 1)
        conv_case(cv['prefill_rows'], cv['chunk'])

    # -- fused optimizer step + grad stats vs core.bucketing.shard_update --
    n = size['opt_elems']
    g32 = rand((n,), jnp.float32, 1e-2)

    def opt_case(name, pdt, **opt_kw):
        opt = paddle.optimizer.AdamW(learning_rate=1e-4, parameters=[],
                                     weight_decay=0.01, **opt_kw)
        p = rand((n,), pdt, 2e-2)
        st = opt.init_state(paddle.Tensor(jnp.zeros((n,), jnp.float32)))
        st['moment1'] = rand((n,), st['moment1'].dtype, 1e-2)
        st['moment2'] = jnp.abs(rand((n,), st['moment2'].dtype, 1e-2))
        st['beta1_pow'] = jnp.asarray(0.9 ** 3, jnp.float32)
        st['beta2_pow'] = jnp.asarray(0.999 ** 3, jnp.float32)
        if pdt != jnp.float32 and opt_kw.get('multi_precision', True):
            st['master'] = jnp.asarray(np.asarray(p, np.float32))
        keys = sorted(st)

        def flat(out):
            new_p, ns = out
            return (new_p,) + tuple(ns[k] for k in keys)
        for fi in (False, True):
            args = (p, g32, st, jnp.float32(1e-4), jnp.float32(0.5),
                    jnp.asarray(fi))
            got = jax.jit(lambda p, g, st, lr, pre, fi: flat(
                fo.fused_shard_update(opt, p, g, st, lr, prefactor=pre,
                                      found_inf=fi)))(*args)
            flags.set_flags({'FLAGS_fused_optimizer': False})
            try:
                ref = ref_call(lambda p, g, st, lr, pre, fi: flat(
                    B.shard_update(opt, p, g, st, lr, prefactor=pre,
                                   found_inf=fi)), *args, upcast=False)
            finally:
                flags.set_flags({'FLAGS_fused_optimizer': None})
            # one line per case: the worst state entry, each held to
            # the tolerance of its own dtype
            worst = max(
                (rel_err(g_, r_) / (TOL_BF16 if g_.dtype == bf
                                    else TOL_F32), key)
                for key, g_, r_ in zip(['param'] + keys, got, ref))
            errs[f'fused_shard_update {name} found_inf={fi}'] = worst[0]
            log(f'kernels  fused_shard_update {name} found_inf={fi}: '
                f'worst err/tol {worst[0]:.2e} ({worst[1]})')
            check(worst[0] <= 1.0, f'fused_shard_update {name} '
                  f'found_inf={fi}: {worst[1]} off by {worst[0]:.2f} tol')

    opt_case('adamw bf16 moments', bf, multi_precision=False,
             moment_dtype='bfloat16')
    opt_case('adamw fp32 moments + master', bf)

    def ref_stats(x):
        return jnp.sum(x * x), jnp.sum((~jnp.isfinite(x)).astype(
            jnp.float32))
    record('grad_stats sum_sq,count', jax.jit(fo.grad_stats_pallas)(g32),
           ref_call(ref_stats, g32), TOL_F32)
    bad = g32.at[jnp.asarray([5, n // 2, n - 1])].set(
        jnp.asarray([jnp.inf, jnp.nan, -jnp.inf]))
    cnt = float(jax.jit(fo.grad_stats_pallas)(bad)[1])
    check(cnt == 3.0, f'grad_stats nonfinite count {cnt} != 3')
    del g32, bad

    # -- fused LayerNorm, bias+GELU, dropout+add --------------------------
    R = mb * L
    for N in (size['hidden'], 4 * size['hidden']):
        x, dy = rand((R, N)), rand((R, N), jnp.float32)
        wt = jnp.asarray((1 + 0.5 * rng.randn(N)).astype(bf))
        bs = rand((N,), bf, 0.5)

        def ref_ln(a, w, b):
            mean = jnp.mean(a, -1, keepdims=True)
            var = jnp.var(a, -1, keepdims=True)
            return (a - mean) * jax.lax.rsqrt(var + 1e-5) * w + b
        record(f'layer_norm fwd+bwd N={N}', jax.jit(fwd_bwd(
            lambda a, w, b: fnorm.fused_layer_norm(a, w, b, 1e-5), 3))(
            dy, x, wt, bs), ref_call(fwd_bwd(ref_ln, 3), dy, x, wt, bs),
            TOL_BF16)
        for approx in (True, False):
            record(f'bias_gelu fwd+bwd N={N} approximate={approx}',
                   jax.jit(fwd_bwd(lambda a, b: fe.bias_gelu(
                       a, b, approx), 2))(dy, x, bs),
                   ref_call(fwd_bwd(lambda a, b: fe.bias_gelu_reference(
                       a, b, approx), 2), dy, x, bs), TOL_BF16)
        keep = jnp.asarray(rng.rand(R, N) >= 0.1, jnp.float32)
        res = rand((R, N))
        record(f'dropout_add fwd+bwd N={N}', jax.jit(fwd_bwd(
            lambda a, r, kp: fe.dropout_add(a, r, kp, 0.1), 2))(
            dy, x, res, keep),
            ref_call(fwd_bwd(lambda a, r, kp: fe.dropout_add_reference(
                a, r, kp, 0.1), 2), dy, x, res, keep), TOL_BF16)
    return errs


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------
def _gpt_config(size, depth, seq=None, flash=True):
    from paddle_tpu.models.gpt import GPTConfig
    return GPTConfig(vocab_size=size['vocab'], hidden_size=size['hidden'],
                     num_layers=depth, num_heads=size['heads'],
                     max_seq_len=seq or size['seq'], hidden_dropout=0.0,
                     attn_dropout=0.0, use_flash_attention=flash)


def _single_chip_mesh():
    import paddle_tpu.distributed.fleet as fm
    from paddle_tpu.distributed import topology_runtime
    fm.fleet._hcg = None
    topology_runtime.build_mesh(['dp', 'pp'], [1, 1])


def _pipeline_engine(size, depth, flash=True):
    """The benchmark's GPT trainer: GPT blocks through the 1F1B SPMD
    pipeline engine at pp=1, remat, param-dtype grad accumulation, AdamW
    with bf16-stored moments."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet.meta_parallel.spmd_pipeline import (
        SpmdPipelineEngine)
    from paddle_tpu.models.gpt import build_gpt_pipeline
    _single_chip_mesh()
    paddle.seed(0)
    embed, blocks, head = build_gpt_pipeline(_gpt_config(size, depth,
                                                         flash=flash))
    layers = [embed, head] + blocks
    to_bf16(layers)
    opt = paddle.optimizer.AdamW(
        learning_rate=size['lr'], parameters=[], weight_decay=0.01,
        multi_precision=False, moment_dtype='bfloat16')
    eng = SpmdPipelineEngine(embed, blocks, head, opt,
                             accumulate_steps=size['A'], use_remat=True,
                             schedule='1F1B', grad_accum_dtype='param')
    drop_eager(layers)
    return eng


def _train_batch(size):
    rng = np.random.RandomState(0)
    ids = rng.randint(0, size['vocab'],
                      (size['A'] * size['mb'], size['seq'])).astype('int32')
    return ids, np.roll(ids, -1, 1).astype('int32')


def phase_train(size, require_kernels=True):
    import jax
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.io import DeviceLoader
    from paddle_tpu.ops.pallas import scaffold

    from paddle_tpu.core import memory
    ids, labels = _train_batch(size)
    routes0 = scaffold.routes_snapshot()
    live0 = memory.sample(count_buffers=True)['live_bytes']
    eng = _pipeline_engine(size, size['depth'])
    losses = [float(eng.train_batch((Tensor(ids), Tensor(labels))))]
    # the windowed path: device prefetch + async dispatch, one flush
    results = [eng.train_step(b) for b in DeviceLoader(
        [(ids, labels)] * size['steps'], engine=eng)]
    eng.flush()
    losses += [float(r.result()) for r in results]
    routes = routes_since(routes0)
    peak = (jax.devices()[0].memory_stats() or {}).get('peak_bytes_in_use')
    log(f'train    depth {size["depth"]} losses '
        + ' '.join(f'{x:.4f}' for x in losses)
        + f'  peak_bytes_in_use {peak}  routes {routes}')
    check(all(np.isfinite(losses)), f'non-finite loss: {losses}')
    check(losses[-1] < losses[0], f'loss did not fall: {losses}')
    if require_kernels:
        check_routes(routes, ('flash_attention', 'layer_norm',
                              'bias_gelu'), 'train')
    del results
    released = eng.shutdown()
    del eng
    gc.collect()
    live = released['live_bytes'] - live0
    log(f'train    after shutdown: {live} live bytes above the start, '
        f'bytes_in_use {released.get("bytes_in_use")}')
    # a repeated int32 batch + scalars may stay; params/moments may not
    check(live < 64 * 2 ** 20,
          f'engine.shutdown() left {live} live bytes')

    # kernel-vs-XLA: the same first step at a cut depth, every Pallas
    # route forced off for the second run
    def first_step(flash):
        e = _pipeline_engine(size, size['ab_depth'], flash=flash)
        loss = float(e.train_batch((Tensor(ids), Tensor(labels))))
        params = jax.tree_util.tree_map(
            lambda a: np.asarray(a.astype('float32')), e._params)
        e.shutdown()
        return loss, params

    loss_k, params_k = first_step(True)
    set_pallas_routes(False)
    try:
        loss_x, params_x = first_step(False)
    finally:
        set_pallas_routes(None)
    gap = abs(loss_k - loss_x) / abs(loss_x)
    check(gap <= TOL_LOSS, f'kernel loss {loss_k} vs XLA loss {loss_x}')
    # AdamW's first step moves an element by ~lr*sign(g): where the two
    # routes agree on the sign the bf16 results are bit-equal, where
    # noise flips it (|g| ~ 0) they differ by at most 2*lr plus one
    # bf16 rounding of the parameter. Wrong gradients would agree on
    # about half the elements.
    same, total, worst = 0, 0, 0.0
    for a, b in zip(jax.tree_util.tree_leaves(params_k),
                    jax.tree_util.tree_leaves(params_x)):
        bound = 2 * size['lr'] * 1.01 + 2 ** -7 * np.abs(b)
        worst = max(worst, float((np.abs(a - b) / bound).max()))
        same += int((a == b).sum())
        total += a.size
    frac = same / total
    log(f'train    kernel-vs-XLA at depth {size["ab_depth"]}: loss '
        f'{loss_k:.5f} vs {loss_x:.5f} (rel {gap:.1e}), params equal '
        f'{frac:.4f}, worst diff/bound {worst:.2f}')
    check(worst <= 1.0, 'updated parameters differ beyond one AdamW step')
    check(frac >= 0.9, f'only {frac:.3f} of updated parameters agree')
    return {'depth': size['depth'], 'losses': losses,
            'ab_loss_rel': gap, 'ab_params_equal': frac}


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------
def _serve_model(size, depth):
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTForCausalLM
    paddle.seed(0)
    model = GPTForCausalLM(_gpt_config(size, depth))
    to_bf16([model])
    model.eval()
    return model


def _dense_checker(model, width):
    """Teacher-forced check of greedy outputs against the dense forward
    (compiled once, at `width` tokens): returns gaps(outs, prompts) ->
    (how far, at worst, an emitted token sits below the dense argmax as
    a share of the logit scale; the share of tokens that ARE the
    argmax)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.jit import functional_call
    params = {n: p.data for n, p in model.named_parameters()}

    @jax.jit
    def forward(params, ids):
        logits, _ = functional_call(model, params, (ids,))
        return logits.astype(jnp.float32)

    def gaps(outs, prompts):
        worst, exact, count = 0.0, 0, 0
        for out, prompt in zip(outs, prompts):
            ids = np.zeros((1, width), np.int32)
            ids[0, :len(out)] = out
            logits = np.asarray(forward(params, jnp.asarray(ids)))[0]
            check(np.isfinite(logits).all(), 'non-finite logits')
            for pos in range(len(prompt), len(out)):
                row = logits[pos - 1]
                gap = float(row.max() - row[out[pos]])
                worst = max(worst, gap / float(row.max() - row.mean()))
                exact += int(gap == 0.0)
                count += 1
        return worst, exact / count
    return gaps


def phase_serve(size, require_kernels=True, meter=None):
    import jax
    import paddle_tpu.distributed.fleet as fm
    from paddle_tpu.ops.pallas import scaffold
    from paddle_tpu.serving import ServingConfig, ServingEngine

    fm.fleet._hcg = None
    depth, new = size['serve_depth'], size['new_tokens']
    model = _serve_model(size, depth)
    rng = np.random.RandomState(0)
    lens = rng.randint(size['prompt_lo'], size['prompt_hi'] + 1,
                       size['requests'])
    prompts = [[int(t) for t in rng.randint(1, size['vocab'], int(n))]
               for n in lens]
    pages = -(-(size['prompt_hi'] + new) // size['page_size'])

    def engine(**kw):
        return ServingEngine(model, ServingConfig(
            page_size=size['page_size'], max_batch_size=size['batch'],
            prefill_chunk=size['chunk'], max_pages_per_seq=pages, **kw))

    routes0 = scaffold.routes_snapshot()
    eng = engine()
    eng.generate([prompts[0]], max_new_tokens=2, top_k=0)   # warm-up
    compiles0 = meter.count if meter else 0
    reqs = [eng.submit(p, max_new_tokens=new, top_k=0) for p in prompts]
    steps = 0
    while eng.scheduler.has_work:
        eng.step()
        steps += 1
        check(steps < 64 * new, 'serving loop did not drain')
    if meter:
        check(meter.count == compiles0,
              f'{meter.count - compiles0} compiles after warm-up')
    outs = [r.output_ids() for r in reqs]
    for r, p in zip(reqs, prompts):
        check(len(r.generated) == new and len(r.output_ids()) ==
              len(p) + new, f'request {r.id}: {len(r.generated)} tokens')
    B, C = size['batch'], size['chunk']
    check({k[0] for k in eng._step_fns} == {'mixed', B}
          and len(eng._step_fns) == 2,
          f'compiled step shapes {sorted(map(str, eng._step_fns))}')
    # donation is on off-CPU only: the pool's arrays are the step's
    # donated outputs and must still be alive and readable
    for layer in eng.pool.kv:
        for a in layer:
            check(not a.is_deleted(), 'a donated pool buffer was lost')
    jax.block_until_ready(eng.pool.kv)
    st = eng.stats()
    check(st['requests_completed_total'] == len(prompts) + 1, str(st))
    routes = routes_since(routes0)
    if require_kernels:
        check_routes(routes, ('paged_attention',), 'serve')
    eng.shutdown()
    gaps = _dense_checker(model, size['prompt_hi'] + new)
    worst, exact = gaps(outs[:2], prompts[:2])
    log(f'serve    depth {depth}: {len(outs)} requests x {new} tokens in '
        f'{steps} steps, routes {routes}; vs dense forward: worst logit '
        f'gap {worst:.3f} of scale, {exact:.2f} exact')
    check(worst <= TOL_LOGIT_GAP, f'greedy tokens off the dense argmax by '
          f'{worst:.3f} of the logit scale')

    # the two other compiled shapes later cells will want. Prompts that
    # repeat a short pattern give the n-gram proposer something to draft.
    pattern = [int(t) for t in rng.randint(1, size['vocab'], 7)]
    rep = [pattern * (size['prompt_lo'] // 7 + 1),
           pattern[::-1] * (size['prompt_lo'] // 7 + 2)]
    for name, kw, counter in (
            ('fused_k', {'fused_k': size['fused_k']},
             'fused_windows_total'),
            ('spec_k', {'spec_k': size['spec_k']}, 'spec_steps_total')):
        eng = engine(**kw)
        outs2 = eng.generate(rep, max_new_tokens=new, top_k=0)
        st = eng.stats()
        check(st[counter] > 0, f'{name}: no {counter} in {st}')
        check(all(len(o) == len(p) + new for o, p in zip(outs2, rep)),
              f'{name}: wrong output lengths')
        eng.shutdown()
        worst2, exact2 = gaps(outs2, rep)
        log(f'serve    {name}={kw[name]}: {counter} {st[counter]}, worst '
            f'logit gap {worst2:.3f} of scale, {exact2:.2f} exact')
        check(worst2 <= TOL_LOGIT_GAP, f'{name}: tokens off the dense '
              f'argmax by {worst2:.3f} of the logit scale')
    del model
    gc.collect()
    return {'depth': depth, 'logit_gap': worst, 'exact': exact}


# ---------------------------------------------------------------------------
# multichip
# ---------------------------------------------------------------------------
def _fleet(dp, pp, sharding, mp):
    import paddle_tpu.distributed.fleet as fm
    from paddle_tpu.distributed.fleet.base.topology import (
        CommunicateTopology, HybridCommunicateGroup)
    topo = CommunicateTopology(['data', 'pipe', 'sharding', 'model'],
                               [dp, pp, sharding, mp])
    fm.fleet._topology = topo
    fm.fleet._hcg = HybridCommunicateGroup(topo)


def _spread(trees, n_dev, where):
    """The work is really spread: every param/optimizer leaf lives on all
    `n_dev` devices, and one device holds well under the whole."""
    import jax
    total = per_dev = 0
    for a in jax.tree_util.tree_leaves(trees):
        if not hasattr(a, 'sharding') or a.ndim == 0:
            continue
        check(len(a.sharding.device_set) == n_dev,
              f'{where}: a {a.shape} leaf on {len(a.sharding.device_set)} '
              f'devices')
        total += a.nbytes
        per_dev += a.addressable_shards[0].data.nbytes
    log(f'multichip {where}: one device holds {per_dev / total:.2f} of '
        f'{total / 2 ** 30:.2f} GiB of params + optimizer state')
    check(per_dev <= 0.6 * total, f'{where}: state is not sharded')


def _memory_balance(n_dev, where):
    import jax
    used = [d.memory_stats()['bytes_in_use'] for d in jax.devices()[:n_dev]
            if d.memory_stats()]
    if not used:        # the CPU test mesh reports no memory_stats
        return
    log(f'multichip {where}: bytes_in_use per chip '
        f'{[round(u / 2 ** 30, 2) for u in used]} GiB')
    check(max(used) <= 2 * min(used), f'{where}: per-chip memory {used}')


def phase_multichip(size):
    import jax
    from jax.sharding import Mesh
    import paddle_tpu as paddle
    import paddle_tpu.distributed.fleet as fm
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.distributed import topology_runtime
    from paddle_tpu.distributed.fleet.meta_parallel.hybrid_engine import (
        HybridParallelTrainStep)
    from paddle_tpu.distributed.fleet.meta_parallel.spmd_pipeline import (
        SpmdPipelineEngine)
    from paddle_tpu.models.gpt import (GPTForCausalLM,
                                       GPTPretrainingCriterion,
                                       build_gpt_pipeline)
    from paddle_tpu.serving import ServingConfig, ServingEngine

    depth, L, steps = size['multi_depth'], size['multi_seq'], \
        size['multi_steps']
    cfg = _gpt_config(size, depth, seq=L)
    rng = np.random.RandomState(0)
    A, mb = 2, 2
    ids = rng.randint(0, size['vocab'], (A * mb, L)).astype('int32')
    labels = np.roll(ids, -1, 1).astype('int32')

    # The parity runs keep fp32 parameters (as the dry-run they scale up
    # does): the bf16 trainer rounds its per-microbatch loss to bf16 — a
    # quantum of 1/16 at a loss of 11, 5.7e-3 relative — which would
    # drown what sharding changes (the order of fp32 sums).
    def adamw():        # fp32 moments, now that state shards
        return paddle.optimizer.AdamW(learning_rate=size['lr'],
                                      parameters=[], weight_decay=0.01)

    def rel(a, b):
        return max(abs(x - y) / max(abs(x), abs(y), 1e-9)
                   for x, y in zip(a, b))

    # (a) pipeline pp2 x mp2 vs one chip
    def run_pipeline(pp, mp):
        _fleet(1, pp, 1, mp)
        topology_runtime.build_mesh(['dp', 'pp', 'mp'], [1, pp, mp])
        paddle.seed(0)
        embed, blocks, head = build_gpt_pipeline(cfg)
        layers = [embed, head] + blocks
        eng = SpmdPipelineEngine(embed, blocks, head, adamw(),
                                 accumulate_steps=A, use_remat=True)
        drop_eager(layers)
        out = [float(eng.train_batch((Tensor(ids), Tensor(labels))))
               for _ in range(steps)]
        if pp * mp > 1:
            _spread((eng._params, eng._states), pp * mp, 'pipeline')
            _memory_balance(pp * mp, 'pipeline')
        eng.shutdown()
        gc.collect()
        return out

    gold, multi = run_pipeline(1, 1), run_pipeline(2, 2)
    err_a = rel(multi, gold)
    log(f'multichip pipeline pp2 x mp2 depth {depth}: losses {multi} vs '
        f'one chip {gold}: max rel err {err_a:.2e}')
    check(all(np.isfinite(multi)) and err_a < TOL_PIPELINE,
          f'pipeline parity {err_a:.3e} (tol {TOL_PIPELINE})')

    # (b) ZeRO-hybrid sharding2 x mp2 with overlapped gathers vs one chip
    def run_hybrid(sharding, mp):
        _fleet(1, 1, sharding, mp)
        topology_runtime.build_mesh(['dp', 'sharding', 'mp'],
                                    [1, sharding, mp])
        paddle.seed(1)
        model = GPTForCausalLM(cfg)
        crit = GPTPretrainingCriterion(cfg)
        eng = HybridParallelTrainStep(
            model, lambda m, i, l: crit(m(i), l), adamw(),
            comm_overlap=True)
        drop_eager([model])
        out = [float(eng(Tensor(ids), Tensor(labels)))
               for _ in range(steps)]
        if sharding * mp > 1:
            _spread((eng._params, eng._param_shards, eng._states),
                    sharding * mp, 'hybrid')
            _memory_balance(sharding * mp, 'hybrid')
        eng.shutdown()
        gc.collect()
        return out

    gold, multi = run_hybrid(1, 1), run_hybrid(2, 2)
    err_b = rel(multi, gold)
    log(f'multichip hybrid sharding2 x mp2 depth {depth}: losses {multi} '
        f'vs one chip {gold}: max rel err {err_b:.2e}')
    check(all(np.isfinite(multi)) and err_b < TOL_ZERO,
          f'hybrid parity {err_b:.3e} (tol {TOL_ZERO})')

    # (c) an mp=2 server answering 4 requests, vs the dense forward of
    # the same (mp-built) model
    _fleet(1, 1, 1, 2)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ('mp',))
    model = _serve_model(size, depth)
    new = size['new_tokens']
    prompts = [[int(t) for t in rng.randint(1, size['vocab'], int(n))]
               for n in rng.randint(size['prompt_lo'],
                                    size['prompt_hi'] + 1, 4)]
    eng = ServingEngine(model, ServingConfig(
        page_size=size['page_size'], max_batch_size=4,
        prefill_chunk=size['chunk'], max_pages_per_seq=-(-(
            size['prompt_hi'] + new) // size['page_size'])), mesh=mesh)
    outs = eng.generate(prompts, max_new_tokens=new, top_k=0)
    check(all(len(o) == len(p) + new for o, p in zip(outs, prompts)),
          'mp=2 server: wrong output lengths')
    for layer in eng.pool.kv:
        check(len(layer[0].sharding.device_set) == 2,
              'mp=2 server: KV pages are not sharded over mp')
    eng.shutdown()
    fm.fleet._hcg = None
    worst, exact = _dense_checker(model, size['prompt_hi'] + new)(
        outs[:2], prompts[:2])
    log(f'multichip serve mp=2 depth {depth}: 4 requests x {new} tokens; '
        f'vs dense forward: worst logit gap {worst:.3f} of scale, '
        f'{exact:.2f} exact')
    check(worst <= TOL_LOGIT_GAP, f'mp=2 server: tokens off the dense '
          f'argmax by {worst:.3f} of the logit scale')
    return {'depth': depth, 'pipeline_rel': err_a, 'hybrid_rel': err_b,
            'serve_logit_gap': worst}


# ---------------------------------------------------------------------------
# main: the device gate, the phases, the result line
# ---------------------------------------------------------------------------
PHASES = ('kernels', 'train', 'serve', 'multichip')


def device_gate():
    """The first thing the chip-holding process does. JAX falls back to
    the CPU with only a warning when the TPU fails to initialize; this
    turns that into a failure."""
    import importlib.metadata as md
    import jax
    import jaxlib
    from paddle_tpu.core import ledger as train_ledger
    from paddle_tpu.serving import ledger as serve_ledger
    backend = jax.default_backend()
    dev = jax.devices()[0]
    device = {'platform': dev.platform, 'kind': dev.device_kind,
              'count': len(jax.devices())}
    log(f'device {device}; jax {jax.__version__} jaxlib '
        f'{jaxlib.__version__} libtpu {md.version("libtpu")}')
    if backend != 'tpu':
        sys.exit(f'chip_smoke: no accelerator — jax.default_backend() is '
                 f'{backend!r}')
    tflops = train_ledger.resolve_peak_tflops(dev.device_kind)
    gbps = serve_ledger.resolve_peak_hbm_gbps(dev.device_kind)
    if tflops is None or gbps is None:
        sys.exit(f'chip_smoke: device_kind {dev.device_kind!r} is in '
                 f'neither peak table (core/ledger.py: {tflops}, '
                 f'serving/ledger.py: {gbps})')
    return device


def main(argv=None):
    wanted = list(argv if argv is not None else sys.argv[1:]) \
        or list(PHASES)
    unknown = [p for p in wanted if p not in PHASES]
    if unknown:
        sys.exit(f'chip_smoke: unknown phase {unknown}; one of {PHASES}')
    t_start = time.time()
    device = device_gate()
    from paddle_tpu.core import compile_cache
    meter = CompileMeter()
    runners = {
        'kernels': lambda: {'checks': len(phase_kernels(FULL))},
        'train': lambda: phase_train(FULL),
        'serve': lambda: phase_serve(FULL, meter=meter),
        'multichip': lambda: phase_multichip(FULL),
    }
    summary = {}
    for name in PHASES:
        if name not in wanted:
            continue
        if name == 'multichip' and device['count'] < 4:
            log(f'multichip NOT RUN: {device["count"]} device(s), the '
                f'phase needs 4')
            summary[name] = 'not run: fewer than 4 devices'
            continue
        c0, s0 = meter.count, meter.seconds
        h0 = compile_cache.snapshot()
        t0 = time.time()
        out = runners[name]()
        h1 = compile_cache.snapshot()
        out.update(
            seconds=round(time.time() - t0, 1),
            compiles=meter.count - c0,
            compile_seconds=round(meter.seconds - s0, 1),
            cache_requests=h1['requests'] - h0['requests'],
            cache_hits=h1['hits'] - h0['hits'])
        summary[name] = out
        log(f'{name} PASSED {json.dumps(out)}')
    cache = compile_cache.snapshot()
    log(f'compile cache {cache}')
    log('summary ' + json.dumps({
        'phases': summary,
        'seconds': round(time.time() - t_start, 1),
        'compile_seconds': round(meter.seconds, 1),
        'compile_cache': {k: cache[k] for k in
                          ('dir', 'requests', 'hits', 'seconds_saved')},
        'claim': None}))
    # the result line: exactly these keys, nothing after it
    print(json.dumps({'ok': True, 'device': device}), flush=True)


if __name__ == '__main__':
    main()
