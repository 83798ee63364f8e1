"""Loss-parity harness: bf16 training must track the fp32 reference.

The BASELINE north star says "loss-curve-matching"; this harness trains the
same GPT config on the same data in fp32 and in bf16 (fp32 Adam masters)
and compares the curves. Run as a script for a JSON report (both legs on
the default backend);
tests/test_loss_parity.py runs both legs on CPU for CI determinism.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def run_curve(dtype='float32', steps=40, seed=0, lr=3e-3, batch=8,
              seq_len=128):
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.gpt import (GPTConfig, GPTForCausalLM,
                                       GPTPretrainingCriterion)

    paddle.seed(seed)
    cfg = GPTConfig(vocab_size=512, hidden_size=128, num_layers=4,
                    num_heads=4, max_seq_len=seq_len, hidden_dropout=0.0,
                    attn_dropout=0.0, use_flash_attention=False)
    model = GPTForCausalLM(cfg)
    if dtype == 'bfloat16':
        for p in model.parameters():
            if p.data.dtype == jnp.float32:
                p.data = p.data.astype(jnp.bfloat16)
    crit = GPTPretrainingCriterion(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=lr,
                                 parameters=model.parameters(),
                                 weight_decay=0.01, multi_precision=True)

    def loss_fn(m, ids, labels):
        return crit(m(ids), labels)

    step = TrainStep(model, loss_fn, opt)
    rng = np.random.RandomState(7)
    # one fixed batch: the curve measures optimization fidelity, and a
    # memorizable target gives a steep, comparison-friendly descent
    ids = rng.randint(0, cfg.vocab_size, (batch, seq_len)).astype('int32')
    labels = np.roll(ids, -1, 1).astype('int32')
    t_ids, t_labels = Tensor(ids), Tensor(labels)
    losses = []
    for _ in range(steps):
        losses.append(float(step(t_ids, t_labels)))
    return losses


def compare(steps=40, rel_tol=0.05):
    fp32 = np.array(run_curve('float32', steps))
    bf16 = np.array(run_curve('bfloat16', steps))
    rel = np.abs(bf16 - fp32) / np.maximum(np.abs(fp32), 1e-6)
    report = {
        'steps': steps,
        'fp32_first': round(float(fp32[0]), 4),
        'fp32_last': round(float(fp32[-1]), 4),
        'bf16_last': round(float(bf16[-1]), 4),
        'max_rel_gap': round(float(rel.max()), 5),
        'mean_rel_gap': round(float(rel.mean()), 5),
        'fp32_decreased': bool(fp32[-1] < fp32[0]),
        'bf16_decreased': bool(bf16[-1] < bf16[0]),
        'pass': bool(rel.max() < rel_tol
                     and fp32[-1] < fp32[0] and bf16[-1] < bf16[0]),
    }
    return report


if __name__ == '__main__':
    print(json.dumps(compare()))
