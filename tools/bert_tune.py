"""BERT config-3 MFU tuning experiments (VERDICT r3 #3: 41.4% -> >=50%).

Each variant runs in-process sequentially; run variants separately via
argv (one process per chip) for clean numbers:
  python tools/bert_tune.py dense|flash|b128|flash_b128|chunks8|chunks32
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np

V5E_PEAK_TFLOPS = 197.0


def run(variant):
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.core import flags
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.distributed import topology_runtime
    from paddle_tpu.models.bert import BertConfig, BertForPretraining
    from paddle_tpu.distributed.fleet.meta_parallel.hybrid_engine import (
        HybridParallelTrainStep)

    B, L, chunks = 64, 512, 16
    if 'b128' in variant:
        B = 128
    if 'chunks8' in variant:
        chunks = 8
    if 'chunks32' in variant:
        chunks = 32
    if 'flash' in variant:
        flags.set_flags({'FLAGS_flash_min_seq': 512})
    if 'bhld' in variant:
        flags.set_flags({'FLAGS_flash_packed_mha': False})

    topology_runtime.build_mesh(['dp', 'sharding'], [1, 1])
    paddle.seed(0)
    cfg = BertConfig(vocab_size=30522, hidden_size=768, num_layers=12,
                     num_heads=12, intermediate_size=3072, max_seq_len=L,
                     hidden_dropout=0.0, attn_dropout=0.0,
                     mlm_loss_chunks=chunks)
    model = BertForPretraining(cfg)
    for p in model.parameters():
        if p.data.dtype == jnp.float32:
            p.data = p.data.astype(jnp.bfloat16)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())

    def loss_fn(m, ids, mlm_labels, nsp_labels):
        return m(ids, masked_lm_labels=mlm_labels,
                 next_sentence_label=nsp_labels)

    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 weight_decay=0.01)
    if 'sgd' in variant:
        opt = paddle.optimizer.SGD(learning_rate=1e-4,
                                   parameters=model.parameters(),
                                   multi_precision=False)
    eng = HybridParallelTrainStep(model, loss_fn, opt)
    rng = np.random.RandomState(0)
    ids = Tensor(rng.randint(0, cfg.vocab_size, (B, L)).astype('int32'))
    mlm = Tensor(np.asarray(ids.data).astype('int64'))
    nsp = Tensor(rng.randint(0, 2, (B,)).astype('int64'))

    if 'fwdonly' in variant or 'fwdbwd' in variant:
        import jax
        from paddle_tpu.jit import get_params, functional_call
        params = {n_: p.data for n_, p in model.named_parameters()}

        def fwd(params, i, m, nl):
            out, _ = functional_call(
                model, params, (i,),
                dict(masked_lm_labels=m, next_sentence_label=nl))
            return out.astype(jnp.float32)

        if 'fwdonly' in variant:
            step = jax.jit(fwd)
        else:
            step = jax.jit(jax.grad(lambda p, i, m, nl:
                                    fwd(p, i, m, nl).sum()))
        r = step(params, ids.data, mlm.data, nsp.data)
        jax.block_until_ready(r)
        n = 5
        dt = float('inf')
        for _ in range(4):
            t0 = time.time()
            for _ in range(n):
                r = step(params, ids.data, mlm.data, nsp.data)
            jax.block_until_ready(r)
            dt = min(dt, (time.time() - t0) / n)
        tokens = B * L
        flops = 6 * n_params * tokens + \
            12 * cfg.num_layers * cfg.hidden_size * L * tokens
        if 'fwdonly' in variant:
            flops //= 3
        print(f"{variant}: B={B} ms={dt*1000:.1f} "
              f"mfu={flops/dt/1e12/V5E_PEAK_TFLOPS:.4f}")
        return

    loss = eng(ids, mlm, nsp)
    assert np.isfinite(float(loss))
    n = 5
    dt = float('inf')
    for _ in range(4):
        t0 = time.time()
        for _ in range(n):
            loss = eng(ids, mlm, nsp)
        float(loss)
        dt = min(dt, (time.time() - t0) / n)
    tokens = B * L
    flops = 6 * n_params * tokens + \
        12 * cfg.num_layers * cfg.hidden_size * L * tokens
    mfu = flops / dt / 1e12 / V5E_PEAK_TFLOPS
    print(f"{variant}: B={B} chunks={chunks} "
          f"ms={dt*1000:.1f} mfu={mfu:.4f}")
    return mfu


if __name__ == '__main__':
    run(sys.argv[1] if len(sys.argv) > 1 else 'dense')
