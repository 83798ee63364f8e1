#!/usr/bin/env python
"""afmoe_breakages — show on the chip that the comparison which decides
`correct` in the sparse-expert server cell is tight: the served path as
it is, then with one piece of the mathematics broken at a time, each
through the SAME engine route, reference and tolerances as
`benchmarks/runners/serve_afmoe.py`.

    chiprun -- python tools/afmoe_breakages.py --seed 11

Each variant goes through the runner's own route at the cell's own
load (the traffic file's engine settings, step shapes, 64 clients and
warm phase of 64 completions, the check of three requests, one of them
past the window; a window of one second) at the published widths and
prints the check's numbers beside their limits. Everything the check
reads comes out of the engine's dispatches, so a breakage shows only as
far as the served path shows it. The served path must pass; every
breakage but those of NOT_HELD must fail at least one limit:

    router_bf16      the router's product rounded to bf16 before the
                     sigmoid — NOT held. Written as a bf16 product
                     (preferred_element_type) the compiled step moves
                     no row of the first expert layer: the compiler
                     keeps the excess precision. So the rounding is
                     forced here (lax.reduce_precision), and still
                     moves fewer rows than the limit leaves room for
    no_expert_bias   the balancing bias left out of the choice
    no_shared        the shared expert dropped
    no_route_scale   route_scale dropped (weights sum to 1)
    no_window        the window bound dropped (window layers read every
                     key)
    rotary_in_full   rotary applied in the full-attention layers too
    fp8_activations  every RMS norm's output rounded to float8_e4m3 —
                     the nearest precision below the configuration's
                     bf16

Writes chiprun_out/afmoe_breakages.<seed>.json.
"""
import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


@contextlib.contextmanager
def patched(obj, name, value):
    was = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, was)


# what `correct` does not tell from the served path (the docstring says
# why): its reading is reported, its verdict not demanded
NOT_HELD = ('router_bf16',)


def variants():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import afmoe
    from paddle_tpu.ops import moe
    from paddle_tpu.ops.pallas import paged_attention as pa
    route, attend, norm = moe.route, pa.ragged_paged_attention, \
        afmoe.rms_norm
    qkv, sparse = afmoe.AfmoeAttention._qkv, afmoe.AfmoeSparseMLP.forward

    def route_bf16(m, w, bias, top_k, scale=1.0, norm_=True):
        logits = jax.lax.reduce_precision(
            jnp.dot(m, w.astype(m.dtype),
                    preferred_element_type=jnp.float32),
            exponent_bits=8, mantissa_bits=7)
        with patched(jnp, 'dot', lambda *a, **k: logits):
            return route(m, w, bias, top_k, scale, norm_)

    def route_unscaled(m, w, bias, top_k, scale=1.0, norm_=True):
        return route(m, w, bias, top_k, 1.0, norm_)

    def route_unbiased(m, w, bias, top_k, scale=1.0, norm_=True):
        return route(m, w, jnp.zeros_like(bias), top_k, scale, norm_)

    def attend_all(*a, window=None, **k):
        return attend(*a, window=None, **k)

    def qkv_rotary_everywhere(self, a, pos):
        with patched(self, 'window', self.window or 1):
            return qkv(self, a, pos)

    def sparse_without_shared(self, m, live=None):
        with patched(self, 'shared', lambda x: jnp.zeros_like(x)):
            return sparse(self, m, live)

    def norm_fp8(x, g, eps):
        return norm(x, g, eps).astype(jnp.float8_e4m3fn).astype(x.dtype)

    return {
        'served': [],
        'router_bf16': [(moe, 'route', route_bf16)],
        'no_expert_bias': [(moe, 'route', route_unbiased)],
        'no_shared': [(afmoe.AfmoeSparseMLP, 'forward',
                       sparse_without_shared)],
        'no_route_scale': [(moe, 'route', route_unscaled)],
        'no_window': [(pa, 'ragged_paged_attention', attend_all)],
        'rotary_in_full': [(afmoe.AfmoeAttention, '_qkv',
                            qkv_rotary_everywhere)],
        'fp8_activations': [(afmoe, 'rms_norm', norm_fp8)],
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--seed', type=int, default=11)
    ap.add_argument('--only', default='',
                    help='comma-separated variants (default: all)')
    args = ap.parse_args(argv)
    import faulthandler
    faulthandler.dump_traceback_later(240, repeat=True)   # where, if stuck
    from benchmarks import common
    manifest = common.Manifest()
    cell = manifest.cell('trinity-mini.mixed-closed64')
    cfg, mix = manifest.config(cell), manifest.traffic(cell)
    runner = manifest.load_module('runners', cfg['runners'][mix['kind']])
    import jax
    if jax.default_backend() != 'tpu':
        sys.exit('afmoe_breakages: no accelerator')
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)
    width = mix['prompt_tokens'][1] + mix['output_tokens'][1]
    model = runner.build_model(cfg, args.seed % (2 ** 31 - 1), width)
    out = {}
    os.makedirs(os.path.join(ROOT, 'chiprun_out'), exist_ok=True)
    t0 = time.time()
    for name, patches in variants().items():
        if args.only and name not in args.only.split(','):
            continue
        with contextlib.ExitStack() as stack:
            for obj, attr, value in patches:
                stack.enter_context(patched(obj, attr, value))
            # the runner's own route: engine, closed loop, warm phase,
            # reference, one second of window; the model is built once
            stack.enter_context(patched(runner, 'build_model',
                                        lambda *a: model))
            record = runner.run(common.Context(
                cfg, mix, args.seed, 1.0, 0,
                device_kind=jax.devices()[0].device_kind))
        check = record['facts']['check']
        check['correct'] = runner.passes(check)
        out[name] = check
        with open(os.path.join(ROOT, 'chiprun_out',
                               f'afmoe_breakages.{args.seed}.json'),
                  'w') as f:
            json.dump(out, f, indent=1)     # as far as it got
        print(f'[breakages] {time.time() - t0:6.0f} s {name:<16s} '
              f'{runner.describe(check)} -> '
              f'{"correct" if check["correct"] else "NOT correct"}',
              flush=True)
    bad = [n for n, c in out.items()
           if n not in NOT_HELD and c['correct'] != (n == 'served')]
    if bad:
        sys.exit(f'afmoe_breakages: the comparison misjudged {bad}')
    print(f'[breakages] the served path passes and every breakage fails '
          f'(not held: {[n for n in NOT_HELD if n in out]})', flush=True)


if __name__ == '__main__':
    main()
