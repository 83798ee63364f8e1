"""Pipeline schedule overhead measurement (VERDICT r2 #10 / r3 #1 evidence;
ISSUE 14 interleaved legs).

Runs the SAME model through the SPMD pipeline schedules at pp=4 on the
virtual 8-device CPU mesh and reports steady-state step times, per-tick
steady-state times, and the static schedule model's bubble fraction
(docs/performance.md#pipeline-schedules).

Schedules measured per scale:
  * 1F1B (activation-stashing; section_worker.cc:147-184 parity) — the
    v=1 baseline: T = A + 2*(pp-1) ticks, every masked warm-up/drain
    tick burns a FULL stage's fwd+bwd.
  * 1F1B recompute memory mode (stage-input buffer only, +1 fwd FLOPs).
  * F-then-B (scan transposition, O(A) boundary activations).
  * interleaved v=2 / v=... (arXiv:2104.04473): each stage holds v
    round-robin model chunks, so a masked tick burns 1/v of a stage —
    modeled bubble_fraction drops from (pp-1)/(A+pp-1) to
    (pp-1)/(A*v+pp-1) at iso (pp, A), at ~v x ppermute boundary
    crossings. The sweep records the model beside the measured
    ms_per_step/ms_per_tick so the shrink is a recorded number.

The A sweep (schedule x v x A) runs on the small scale where the extra
compiles are cheap; 'small' (hidden=128) is dispatch-bound on CPU,
'big' (hidden=512) is compute-bound — the regime a real TPU slice runs
in, where the FLOP accounting dominates.

Usage: python tools/pipeline_bench.py
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# a CPU dry-run by request: 8 virtual devices, set before jax is imported
os.environ['JAX_PLATFORMS'] = 'cpu'
if 'xla_force_host_platform_device_count' not in os.environ.get(
        'XLA_FLAGS', ''):
    os.environ['XLA_FLAGS'] = (os.environ.get('XLA_FLAGS', '') +
                               ' --xla_force_host_platform_device_count=8')

import numpy as np                                          # noqa: E402


def measure(schedule, memory_mode='stash', pp=4, A=8, steps=3, big=True,
            virtual_stages=None):
    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.distributed import topology_runtime
    from paddle_tpu.models.gpt import GPTConfig, build_gpt_pipeline
    from paddle_tpu.distributed.fleet.meta_parallel.spmd_pipeline import (
        SpmdPipelineEngine)
    import paddle_tpu.distributed.fleet as fleet_mod
    fleet_mod.fleet._hcg = None

    paddle.seed(0)
    topology_runtime.build_mesh(['dp', 'pp'], [1, pp])
    if big:
        cfg = GPTConfig(vocab_size=512, hidden_size=512, num_layers=8,
                        num_heads=8, max_seq_len=256, hidden_dropout=0.0,
                        attn_dropout=0.0, use_flash_attention=False)
        L, mb = 256, 2
    else:
        cfg = GPTConfig(vocab_size=256, hidden_size=128, num_layers=8,
                        num_heads=4, max_seq_len=128, hidden_dropout=0.0,
                        attn_dropout=0.0, use_flash_attention=False)
        L, mb = 128, 1
    embed, blocks, head = build_gpt_pipeline(cfg)
    opt = paddle.optimizer.SGD(learning_rate=1e-3, parameters=[])
    eng = SpmdPipelineEngine(embed, blocks, head, opt,
                             accumulate_steps=A, use_remat=True,
                             schedule=schedule, memory_mode=memory_mode,
                             virtual_stages=virtual_stages)
    model = eng._sched_model       # the engine's own schedule census
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (A * mb, L)).astype('int32')
    labels = np.roll(ids, -1, 1).astype('int32')
    data = (Tensor(ids), Tensor(labels))
    loss = eng.train_batch(data)       # compile
    float(loss)
    t0 = time.time()
    for _ in range(steps):
        loss = eng.train_batch(data)
    float(loss)
    ms = (time.time() - t0) / steps * 1000
    eng.shutdown()
    return {'ms_per_step': round(ms, 1),
            'ms_per_tick': round(ms / model['ticks'], 3),
            'loss': round(float(loss), 4),
            'pipeline': model}


def main():
    r = {}
    for scale, big in (('big', True), ('small', False)):
        sec = {}
        legs = [('1F1B', '1F1B', 'stash', None),
                ('1F1B_recompute', '1F1B', 'recompute', None),
                ('F-then-B', 'F-then-B', 'stash', None),
                ('interleaved_v2', 'interleaved', 'stash', 2)]
        if not big:
            legs.append(
                ('interleaved_v2_recompute', 'interleaved', 'recompute',
                 2))
        for name, sched, mode, v in legs:
            sec[name] = measure(sched, memory_mode=mode, big=big,
                                steps=3 if big else 5, virtual_stages=v)
        sec['ratio_1f1b_over_fthenb'] = round(
            sec['1F1B']['ms_per_step'] / sec['F-then-B']['ms_per_step'], 3)
        sec['ratio_recompute_over_fthenb'] = round(
            sec['1F1B_recompute']['ms_per_step']
            / sec['F-then-B']['ms_per_step'], 3)
        sec['ratio_interleaved_v2_over_1f1b'] = round(
            sec['interleaved_v2']['ms_per_step']
            / sec['1F1B']['ms_per_step'], 3)
        sec['bubble_drop_v2_vs_v1'] = round(
            sec['1F1B']['pipeline']['bubble_fraction']
            - sec['interleaved_v2']['pipeline']['bubble_fraction'], 4)
        r[scale] = sec
    # schedule x v x A sweep (model + steady per-tick time) on the
    # cheap scale: the modeled bubble must shrink monotonically in v at
    # iso (pp, A) and in A at iso v
    sweep = []
    for A in (8, 16):
        for sched, v in (('1F1B', None), ('interleaved', 2)):
            m = measure(sched, A=A, big=False, steps=3,
                        virtual_stages=v)
            sweep.append({'schedule': m['pipeline']['schedule'],
                          'virtual_stages': m['pipeline']
                          ['virtual_stages'],
                          'A': A,
                          'ms_per_step': m['ms_per_step'],
                          'ms_per_tick': m['ms_per_tick'],
                          'bubble_fraction': round(
                              m['pipeline']['bubble_fraction'], 4)})
    r['sweep'] = sweep
    r['note'] = ('stash-1F1B = SectionWorker store-activations schedule: '
                 'A+pp-1 fwd + A+pp-1 bwd (same totals as F-then-B, '
                 'save-dots backward), O(pp) in-flight window; '
                 'interleaved_v2 = Megatron virtual stages: masked ticks '
                 'cost 1/v stage, modeled bubble (pp-1)/(A*v+pp-1), '
                 '~v x ppermute crossings '
                 '(docs/performance.md#pipeline-schedules)')
    print(json.dumps(r))


if __name__ == '__main__':
    main()
