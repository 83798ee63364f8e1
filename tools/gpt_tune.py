"""GPT-1.3B headline variants on one chip.

Usage: python tools/gpt_tune.py packed|bhld
(compare the packed transpose-free causal flash route vs the BHLD one
on the configuration of the benchmark's `gpt3-1.3b.pretrain-2k` cell).
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np

V5E_PEAK_TFLOPS = 197.0


def run(variant='packed'):
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.core import flags
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.distributed import topology_runtime
    from paddle_tpu.models.gpt import GPTConfig, build_gpt_pipeline
    from paddle_tpu.distributed.fleet.meta_parallel.spmd_pipeline import (
        SpmdPipelineEngine)
    import paddle_tpu.distributed.fleet as fm

    flags.set_flags({'FLAGS_flash_packed_causal': variant == 'packed'})
    fm.fleet._hcg = None
    topology_runtime.build_mesh(['dp', 'pp'], [1, 1])
    paddle.seed(0)
    L = 2048
    cfg = GPTConfig(vocab_size=50304, hidden_size=2048, num_layers=24,
                    num_heads=16, max_seq_len=L, hidden_dropout=0.0,
                    attn_dropout=0.0, use_flash_attention=True)
    embed, blocks, head = build_gpt_pipeline(cfg)
    layers = [embed, head] + blocks
    for layer in layers:
        for p in layer.parameters():
            if p.data.dtype == jnp.float32:
                p.data = p.data.astype(jnp.bfloat16)
    n_params = sum(int(np.prod(p.shape))
                   for layer in layers for p in layer.parameters())
    opt = paddle.optimizer.SGD(learning_rate=1e-4, parameters=[],
                               multi_precision=False)
    A, mb = 4, 2
    eng = SpmdPipelineEngine(embed, blocks, head, opt, accumulate_steps=A,
                             use_remat=True, schedule='1F1B',
                             grad_accum_dtype='param')
    for layer in layers:
        for p in layer.parameters():
            p._data = jnp.zeros((1,), jnp.bfloat16)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (A * mb, L)).astype('int32')
    data = (Tensor(ids), Tensor(np.roll(ids, -1, 1).astype('int32')))
    loss = eng.train_batch(data)
    assert np.isfinite(float(loss))
    n = 5
    dt = float('inf')
    for _ in range(3):
        t0 = time.time()
        for _ in range(n):
            loss = eng.train_batch(data)
        float(loss)
        dt = min(dt, (time.time() - t0) / n)
    tokens = A * mb * L
    flops = 6 * n_params * tokens + \
        12 * cfg.num_layers * cfg.hidden_size * L * tokens
    mfu = flops / dt / 1e12 / V5E_PEAK_TFLOPS
    print(f"{variant}: ms={dt*1000:.1f} mfu={mfu:.4f} "
          f"loss={float(loss):.4f}")
    return mfu


if __name__ == '__main__':
    run(sys.argv[1] if len(sys.argv) > 1 else 'packed')
