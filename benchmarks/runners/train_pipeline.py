"""Trainer cells of a GPT configuration: blocks through the 1F1B SPMD
pipeline engine at pp=1 — full per-block remat, gradient accumulation in
the parameters' dtype, AdamW with bf16-stored moments — fed by
DeviceLoader + train_step + flush. The engine recipe is
`chip_smoke._pipeline_engine` (proven on the chip in PR 21), copied so
that a later change to that script cannot move the yardstick.
"""
import numpy as np

from benchmarks import trainloop
from benchmarks.reference import gpt as reference

# The engine rounds each microbatch's loss to bf16 and averages them. At
# a loss of 11 bf16's spacing is 1/16, so rounding alone puts up to 1/32
# = 2.8e-3 relative between the engine and the float32 reference; the
# bf16 forward adds a few 1e-4 (PR 21 saw kernels and XLA agree to one
# rounding). 5e-3 holds both and would fail a wrong or fp8 forward,
# whose loss moves in the second digit.
LOSS_TOL = 5e-3
LOSS_WHY = 'bf16 rounding of each microbatch loss, 2.8e-3 at a loss of 11'


def build_engine(cfg, job, seed):
    import jax.numpy as jnp
    import paddle_tpu as paddle
    import paddle_tpu.distributed.fleet as fm
    from paddle_tpu.distributed import topology_runtime
    from paddle_tpu.distributed.fleet.meta_parallel.spmd_pipeline import (
        SpmdPipelineEngine)
    from paddle_tpu.models.gpt import GPTConfig, build_gpt_pipeline
    fm.fleet._hcg = None
    topology_runtime.build_mesh(['dp', 'pp'], [1, 1])
    paddle.seed(seed)
    embed, blocks, head = build_gpt_pipeline(GPTConfig(
        vocab_size=cfg['vocab_size'], hidden_size=cfg['hidden_size'],
        num_layers=cfg['num_layers'], num_heads=cfg['num_heads'],
        ffn_hidden_size=cfg['ffn_hidden_size'],
        max_seq_len=cfg['max_seq_len'], hidden_dropout=0.0,
        attn_dropout=0.0, use_flash_attention=True))
    layers = [embed, head] + blocks
    for layer in layers:
        for p in layer.parameters():
            if p.data.dtype == jnp.float32:
                p.data = p.data.astype(cfg['dtype'])
    opt = paddle.optimizer.AdamW(
        learning_rate=job['learning_rate'], parameters=[],
        weight_decay=job['weight_decay'], multi_precision=False,
        moment_dtype='bfloat16')
    eng = SpmdPipelineEngine(
        embed, blocks, head, opt, accumulate_steps=job['accumulate_steps'],
        use_remat=True, schedule='1F1B', grad_accum_dtype='param')
    for layer in layers:        # the engine owns device copies now
        for p in layer.parameters():
            p._data = jnp.zeros((1,), p.data.dtype)
    return eng


def reference_loss(eng, cfg, ids, labels, microbatch):
    """The float32 reference on the engine's seeded weights, one
    microbatch at a time, averaged as the engine averages."""
    e, b, h = (eng._params[g] for g in ('embed', 'blocks', 'head'))
    params = {'wte': e['word_embeddings.weight'],
              'wpe': e['position_embeddings.weight'],
              'lnf_w': h['norm.weight'], 'lnf_b': h['norm.bias'],
              'head': h['out.weight']}
    names = {'ln1_w': 'ln1.weight', 'ln1_b': 'ln1.bias',
             'qkv_w': 'attn.qkv_proj.weight', 'qkv_b': 'attn.qkv_proj.bias',
             'out_w': 'attn.out_proj.weight', 'out_b': 'attn.out_proj.bias',
             'ln2_w': 'ln2.weight', 'ln2_b': 'ln2.bias',
             'fc1_w': 'mlp.fc1.weight', 'fc1_b': 'mlp.fc1.bias',
             'fc2_w': 'mlp.fc2.weight', 'fc2_b': 'mlp.fc2.bias'}

    def layer(i):
        return {k: b[n][i] for k, n in names.items()}
    losses = [float(reference.loss(
        params, layer, cfg['num_layers'], ids[i:i + microbatch],
        labels[i:i + microbatch], cfg['num_heads']))
        for i in range(0, len(ids), microbatch)]
    return sum(losses) / len(losses)


def run(ctx):
    from paddle_tpu.core.tensor import Tensor
    cfg, job = ctx.config, ctx.traffic
    seq, mb, acc = job['seq_len'], job['microbatch'], job['accumulate_steps']
    eng = build_engine(cfg, job, ctx.weights_seed)
    ctx.mark('engine')
    rng = np.random.default_rng(ctx.seed)
    batches = []
    for _ in range(job['distinct_batches']):
        ids = rng.integers(0, cfg['vocab_size'], (acc * mb, seq),
                           dtype=np.int32)
        batches.append((ids, np.roll(ids, -1, 1)))
    ids, labels = batches[0]
    ref = reference_loss(eng, cfg, ids, labels, mb)
    ctx.mark('reference')
    first = float(eng.train_batch((Tensor(ids), Tensor(labels))))
    ctx.mark('first step')
    correct = trainloop.check_first_loss(first, ref, LOSS_TOL, LOSS_WHY)
    try:
        return trainloop.measure(ctx, eng, eng.train_step, batches,
                                 acc * mb * seq, correct)
    finally:
        eng.shutdown()
