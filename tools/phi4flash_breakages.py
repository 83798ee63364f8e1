#!/usr/bin/env python
"""phi4flash_breakages — show on the chip that the comparison which
decides `correct` in the state-space server cell is tight: the served
path as it is, then with one piece of the mathematics broken at a time,
each through the SAME engine route, reference and limits as
`benchmarks/runners/serve_phi4flash.py`.

    chiprun -- python tools/phi4flash_breakages.py --seed 11

Each variant goes through the runner's own route at the cell's own load
(the traffic file's engine settings, step shapes, 64 clients, the check
of three requests, one of them with a prompt past the window; a window
of one second) at the published widths and prints the check's numbers
beside their limits. The warm phase is cut to `--warm` completions (the
cell's 128 spread the clients over every phase for the WINDOW's sake;
the check needs its three requests): a variant then takes about a
minute and a half. Everything the check reads comes out of the engine's
dispatches, so a breakage shows only as far as the served path shows
it. The served path must pass; every breakage but those of NOT_HELD
must fail at least one limit:

    state_zeroed     a prompt chunk starts from a zero state and a zero
                     convolution tail: the recurrence forgets at every
                     chunk boundary (decode rows carry on from what the
                     last chunk left)
    state_bf16       the recurrent state rounded to bf16 whenever it is
                     written back — NOT held: 8 mantissa bits on a state
                     that decays by exp(dt A) a token move no more tokens
                     off the reference's argmax than bf16 activations
                     already do (0.865 / 0.00121 / 0.026 beside the
                     served path's 0.855-0.910 / 0.0008-0.0017 /
                     0.026-0.050)
    no_window        the window bound dropped (window layers read every
                     key)
    lambda_zero      lambda fixed to 0: plain attention on the first
                     sub-head of every pair
    cross_zero_plane the cross-decoder's layers read a plane of zeros
                     (uniform attention over zero values) instead of
                     the full layer's
    memory_gated     the gated memory units get layer 16's output AFTER
                     the z gate
    fp8_activations  every LayerNorm's output rounded to float8_e4m3 —
                     the nearest precision below the configuration's
                     bf16

Writes chiprun_out/phi4flash_breakages.<seed>.json.
"""
import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# what `correct` does not tell from the served path (the docstring says
# why): its reading is reported, its verdict not demanded
NOT_HELD = ('state_bf16',)


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
    from paddle_tpu.models import phi4flash as m
    from paddle_tpu.ops import ssm
    from paddle_tpu.ops.pallas import paged_attention as pa
    scan, conv, attend, norm = ssm.selective_scan, ssm.causal_conv, \
        pa.ragged_paged_attention, m.layer_norm
    mamba, lam = m.MambaMixer.forward_paged, m.DiffAttention._lambda
    attn = m.DiffAttention.forward_paged

    def scan_forgets(x, dt, B, C, A, D, state, slots, q_lens, fresh):
        return scan(x, dt, B, C, A, D, state, slots, q_lens,
                    jnp.ones_like(fresh) if x.shape[1] > 1 else fresh)

    def conv_forgets(x, tails, w, b, slots, q_lens, fresh):
        return conv(x, tails, w, b, slots, q_lens,
                    jnp.ones_like(fresh) if x.shape[1] > 1 else fresh)

    # a convert down and up again is excess precision the compiler may
    # drop (and does: both variants read as the served path to the last
    # digit); `reduce_precision` is the rounding it must keep
    def scan_bf16(*a):
        y, state = scan(*a)
        return y, jax.lax.reduce_precision(state, exponent_bits=8,
                                           mantissa_bits=7)

    def attend_all(*a, window=None, **k):
        return attend(*a, window=None, **k)

    def cross_reads_zeros(self, a, kv, rows):
        if not self.cross:
            return attn(self, a, kv, rows)
        out, _ = attn(self, a, tuple(jnp.zeros_like(p) for p in kv), rows)
        return out, kv

    def memory_after_gate(self, a, state, rows):
        out, mem, state = mamba(self, a, state, rows)
        if mem is not None:
            dn = self.cfg.d_inner
            z = m._dot(a, self.in_proj.data)[..., dn:]
            mem = (mem.astype(m.F32) * m._silu(z.astype(m.F32))) \
                .astype(mem.dtype)
        return out, mem, state

    def norm_fp8(x, g, b, eps):
        return jax.lax.reduce_precision(norm(x, g, b, eps),
                                        exponent_bits=4, mantissa_bits=3)

    return {
        'served': [],
        'state_zeroed': [(ssm, 'selective_scan', scan_forgets),
                         (ssm, 'causal_conv', conv_forgets)],
        'state_bf16': [(ssm, 'selective_scan', scan_bf16)],
        'no_window': [(pa, 'ragged_paged_attention', attend_all)],
        'lambda_zero': [(m.DiffAttention, '_lambda', lambda self: 0.0)],
        'cross_zero_plane': [(m.DiffAttention, 'forward_paged',
                              cross_reads_zeros)],
        'memory_gated': [(m.MambaMixer, 'forward_paged',
                          memory_after_gate)],
        'fp8_activations': [(m, 'layer_norm', norm_fp8)],
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
    faulthandler.dump_traceback_later(600, repeat=True)   # where, if stuck
    from benchmarks import common
    manifest = common.Manifest()
    cell = manifest.cell('phi4-mini-flash.reason-closed64')
    cfg, mix = manifest.config(cell), manifest.traffic(cell)
    mix = dict(mix, warm_completions=args.warm)
    runner = manifest.load_module('runners', cfg['runners'][mix['kind']])
    import jax
    if jax.default_backend() != 'tpu':
        sys.exit('phi4flash_breakages: no accelerator')
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
                               f'phi4flash_breakages.{args.seed}.json'),
                  'w') as f:
            json.dump(out, f, indent=1)     # as far as it got
        print(f'[breakages] {time.time() - t0:6.0f} s {name:<16s} '
              f'{runner.describe(check)} -> '
              f'{"correct" if check["correct"] else "NOT correct"}',
              flush=True)
    bad = [n for n, c in out.items()
           if n not in NOT_HELD and c['correct'] != (n == 'served')]
    if bad:
        sys.exit(f'phi4flash_breakages: the comparison misjudged {bad}')
    print(f'[breakages] the served path passes and every breakage fails '
          f'(not held: {[n for n in NOT_HELD if n in out]})', flush=True)


if __name__ == '__main__':
    main()
