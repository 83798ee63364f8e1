#!/usr/bin/env python
"""axk1_breakages — show on the chip that the comparison which decides
`correct` in the latent-attention server cell is tight: the served path
as it is, then with one piece of the mathematics broken at a time, each
through the SAME engine route, reference and tolerances as
`benchmarks/runners/serve_axk1.py`.

    chiprun -- python tools/axk1_breakages.py --seed 11 [--only a,b] \\
        [--warm 24]

Each variant goes through the runner's own route at the cell's own load
(the traffic file's engine settings, step shapes and 64 clients, the
prefix cache on; the warm phase cut to `--warm` completions and to the
four requests the check reads — the first ask of the shortest document,
a later ask of it that hit, an ask of the second-shortest, an ask of the
longest that hit —, not held until every document has been asked about;
a window of one second) at the published widths and prints the check's
numbers beside their limits. Everything the check reads comes out of the engine's dispatches,
so a breakage shows only as far as the served path shows it. The served
path must pass; every breakage but those of NOT_HELD must fail at least
one limit:

    no_k_pe          the rotary part dropped from the scores (q_pe = 0)
    no_latent_norm   the RMS norm of the cached latent c_kv dropped
    no_yarn_scale    YaRN's m(mscale_all_dim)^2 left out of the softmax
                     scale (1.813 at the published numbers)
    values_all_lanes the values read from all 576 lanes of the row: the
                     64 rotary lanes' weighted sum folded onto the first
                     64 value lanes
    no_group_limit   the router's group limit dropped (top-8 of all 192)
    no_shared        the shared expert dropped
    scores_bf16      the scores rounded to bf16 before the softmax — the
                     nearest precision below the kernel's float32 scores
                     (forced with lax.reduce_precision)
    hit_other_doc    a prefix hit mapped onto another document's pages
    weights_f8       the nearest precision below the configuration's
                     bf16 WEIGHTS: every 2-D weight the step binds (all
                     but the routed experts' stacked ones) rounded to
                     float8's 3 mantissa bits (e4m3 under a scale that
                     keeps the exponent)
    latents_f8       the nearest precision below the configuration's
                     bf16 latent PAGES: every row [c_kv | k_pe] rounded
                     to float8 (e4m3) as it is written

Writes chiprun_out/axk1_breakages.<seed>.json.
"""
import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# what `correct` does not tell from the served path: its reading is
# reported, its verdict not demanded (PERF.md section 6, PR 34)
NOT_HELD = ('scores_bf16',)


@contextlib.contextmanager
def patched(obj, name, value):
    was = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, was)


def variants():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import axk1 as m
    from paddle_tpu.ops import moe
    from paddle_tpu.ops.pallas import paged_attention as pa
    from paddle_tpu.serving.kv_pool import KVPagePool
    absorb, norm, route = m.AxK1Attention._absorb_q, m.rms_norm, moe.route
    attend, sparse = pa.ragged_paged_attention, m.AxK1SparseMLP.forward
    dot_general, einsum = jax.lax.dot_general, jnp.einsum
    match = KVPagePool.match_and_map
    import paddle_tpu.jit as pjit
    bind, write = pjit.bind_arrays, pa.write_latent_pages

    def absorb_without_k_pe(self, q_nope, q_pe, lanes=None):
        return absorb(self, q_nope, jnp.zeros_like(q_pe), lanes)

    def norm_but_the_latents(x, g, eps):
        # the one norm whose weight is kv_lora_rank wide is c_kv's
        latent = g.shape[-1] in (16, 512) and x.shape[-1] == g.shape[-1]
        return x if latent else norm(x, g, eps)

    def scale_without_yarn(self):
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    def attend_all_lanes(q, pages, v, *a, latent=None, **k):
        value, rotary = latent
        out = attend(q, pages, v, *a, latent=(value + rotary, 0), **k)
        out = out.reshape(*out.shape[:2], -1, value + rotary)
        return out[..., :value].at[..., :rotary].add(out[..., value:]) \
            .reshape(*out.shape[:2], -1)

    def route_any_group(*a, n_group=1, topk_group=1, **k):
        return route(*a, **k)

    def sparse_without_shared(self, x, live=None, counted=None):
        with patched(self, 'shared', lambda x: jnp.zeros_like(x)):
            return sparse(self, x, live, counted)

    def rounded(x):
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    def attend_bf16_scores(*a, **k):
        # the kernel's score product (contracting the lanes of q and of
        # the keys) and the dense route's; neither is reached by any
        # other product traced inside this call
        # (Mosaic lowers no reduce_precision, and drops no convert)
        def dot(lhs, rhs, dims, *a2, **k2):
            out = dot_general(lhs, rhs, dims, *a2, **k2)
            if dims != (((1,), (1,)), ((), ())):
                return out
            return out.astype(jnp.bfloat16).astype(out.dtype)

        def ein(spec, *ops, **k2):
            out = einsum(spec, *ops, **k2)
            return rounded(out) if spec == 'btd,bkd->btk' else out
        with patched(jax.lax, 'dot_general', dot), \
                patched(jnp, 'einsum', ein):
            return attend(*a, **k)

    def match_another_document(self, seq_id, tokens, limit=None):
        """The prefix index's answer for another prompt's first pages,
        where one of at least this prompt's hit is indexed."""
        seen = self.__dict__.setdefault('_breakage_seen', {})
        ps = self.page_size
        seen.setdefault(tuple(tokens[:ps]), list(tokens))
        own = len(self._match_pages(tokens, limit))
        for first, other in seen.items():
            if own and first != tuple(tokens[:ps]) and \
                    len(self._match_pages(other, own * ps)) >= own:
                return match(self, seq_id, other, own * ps)
        return match(self, seq_id, tokens, limit)

    def bind_f8_weights(layer, arrays, *a, **k):
        # the engine's step binds its traced parameters here
        return bind(layer, {
            n: jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=3)
            if getattr(v, 'ndim', 0) == 2 else v
            for n, v in arrays.items()}, *a, **k)

    def write_f8_latents(pages, new, *a):
        return write(pages, jax.lax.reduce_precision(
            new, exponent_bits=4, mantissa_bits=3), *a)

    return {
        'served': [],
        'no_k_pe': [(m.AxK1Attention, '_absorb_q', absorb_without_k_pe)],
        'no_latent_norm': [(m, 'rms_norm', norm_but_the_latents)],
        'no_yarn_scale': [(m.AxK1Config, 'softmax_scale',
                           property(scale_without_yarn))],
        'values_all_lanes': [(pa, 'ragged_paged_attention',
                              attend_all_lanes)],
        'no_group_limit': [(moe, 'route', route_any_group)],
        'no_shared': [(m.AxK1SparseMLP, 'forward', sparse_without_shared)],
        'scores_bf16': [(pa, 'ragged_paged_attention', attend_bf16_scores)],
        'hit_other_doc': [(KVPagePool, 'match_and_map',
                           match_another_document)],
        'weights_f8': [(pjit, 'bind_arrays', bind_f8_weights)],
        'latents_f8': [(pa, 'write_latent_pages', write_f8_latents)],
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--seed', type=int, default=11)
    ap.add_argument('--warm', type=int, default=24,
                    help='completions of the warm phase (the cell: 128)')
    ap.add_argument('--only', default='',
                    help='comma-separated variants (default: all)')
    args = ap.parse_args(argv)
    import faulthandler
    faulthandler.dump_traceback_later(900, repeat=True)   # where, if stuck
    from benchmarks import common
    manifest = common.Manifest()
    cell = manifest.cell('axk1.docs-closed64')
    cfg, mix = manifest.config(cell), manifest.traffic(cell)
    mix = dict(mix, warm_completions=args.warm)
    runner = manifest.load_module('runners', cfg['runners'][mix['kind']])
    import jax
    if jax.default_backend() != 'tpu':
        sys.exit('axk1_breakages: no accelerator')
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)
    model = runner.build_model(cfg, args.seed % (2 ** 31 - 1),
                               cfg['max_seq_len'])
    out = {}
    os.makedirs(os.path.join(ROOT, 'chiprun_out'), exist_ok=True)
    t0 = time.time()
    for name, patches in variants().items():
        if args.only and name not in args.only.split(','):
            continue
        # a kernel call is one jitted function of its shapes and static
        # choices: a patch inside it is seen only by a fresh trace
        jax.clear_caches()
        with contextlib.ExitStack() as stack:
            for obj, attr, value in patches:
                stack.enter_context(patched(obj, attr, value))
            # the runner's own route: engine, closed loop, warm phase,
            # reference, one second of window; the model is built once
            stack.enter_context(patched(runner, 'build_model',
                                        lambda *a: model))
            stack.enter_context(patched(
                runner, 'warm_enough',
                lambda completed, answered, mix: completed >= args.warm))
            record = runner.run(common.Context(
                cfg, mix, args.seed, 1.0, 0,
                device_kind=jax.devices()[0].device_kind))
        check = record['facts']['check']
        check['correct'] = runner.passes(check)
        out[name] = check
        with open(os.path.join(ROOT, 'chiprun_out',
                               f'axk1_breakages.{args.seed}.json'),
                  'w') as f:
            json.dump(out, f, indent=1)     # as far as it got
        print(f'[breakages] {time.time() - t0:6.0f} s {name:<16s} '
              f'{runner.describe(check)} -> '
              f'{"correct" if check["correct"] else "NOT correct"}',
              flush=True)
    jax.clear_caches()
    bad = [n for n, c in out.items()
           if n not in NOT_HELD and c['correct'] != (n == 'served')]
    if bad:
        sys.exit(f'axk1_breakages: the comparison misjudged {bad}')
    print(f'[breakages] the served path passes and every breakage fails '
          f'(not held: {[n for n in NOT_HELD if n in out]})', flush=True)


if __name__ == '__main__':
    main()
