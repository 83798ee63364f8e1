"""The plain float32 references against the program's own models, at toy
size on the CPU, on seeded weights: the loss for the two trainers, and
for the server the logits through prefill + paged decode.

Everything here is float32 (the models are not cast to bf16 as the cells
cast them), so the two sides differ only in the order of float32 sums:
1e-4 relative holds that with room and would fail any missing term, a
wrong mask, epsilon or GELU variant (each moves a toy loss by > 1e-3).
The cells' own bf16 tolerances are in the runners, with their reasons;
test_benchmark_lastline.py runs those checks at toy size.
"""
import numpy as np
import pytest

import benchtoy

TOL_F32 = 1e-4
MANIFEST = benchtoy.manifest()


def toy_fp32(cell_name):
    """The cell at toy size with float32 parameters."""
    _, cfg, traffic, runner = benchtoy.toy(MANIFEST, cell_name)
    return dict(cfg, dtype='float32'), traffic, runner


def test_gpt_reference_loss_matches_the_pipeline_engine():
    from paddle_tpu.core.tensor import Tensor
    cfg, job, runner = toy_fp32('gpt3-1.3b.pretrain-2k')
    eng = runner.build_engine(cfg, job, seed=5)
    try:
        assert eng._params['blocks']['mlp.fc1.weight'].dtype == np.float32
        rng = np.random.default_rng(0)
        n = job['microbatch'] * job['accumulate_steps']
        ids = rng.integers(0, cfg['vocab_size'], (n, job['seq_len']),
                           dtype=np.int32)
        labels = np.roll(ids, -1, 1)
        ref = runner.reference_loss(eng, cfg, ids, labels,
                                    job['microbatch'])
        got = float(eng.train_batch((Tensor(ids), Tensor(labels))))
    finally:
        eng.shutdown()
    assert abs(got - ref) / ref < TOL_F32
    assert abs(ref - np.log(cfg['vocab_size'])) < 0.5   # untrained


def test_bert_reference_loss_matches_the_hybrid_engine():
    from paddle_tpu.core.tensor import Tensor
    cfg, job, runner = toy_fp32('bert-large.pretrain-512')
    eng, model = runner.build_engine(cfg, job, seed=5)
    try:
        rng = np.random.default_rng(0)
        ids = rng.integers(0, cfg['vocab_size'],
                           (job['batch'], job['seq_len']), dtype=np.int32)
        mlm = rng.integers(0, cfg['vocab_size'], ids.shape).astype(np.int64)
        nsp = rng.integers(0, 2, (job['batch'],)).astype(np.int64)
        ref = runner.reference_loss(model, cfg, ids, mlm, nsp)
        got = float(eng(Tensor(ids), Tensor(mlm), Tensor(nsp)))
    finally:
        eng.shutdown()
    assert abs(got - ref) / ref < TOL_F32
    assert ref > np.log(cfg['vocab_size'])      # MLM + NSP, untrained


def test_gpt_reference_logits_match_prefill_and_paged_decode():
    """Greedy tokens of the paged-KV engine, teacher-forced through the
    reference's full forward: each emitted token must be the reference's
    argmax up to float32 noise (as a share of the logit scale)."""
    from paddle_tpu.serving import ServingConfig, ServingEngine
    cfg, mix, runner = toy_fp32('gpt3-1.3b.chat-closed64')
    model = runner.build_model(cfg, seed=5)
    eng = ServingEngine(model, ServingConfig(**mix['engine']))
    try:
        rng = np.random.default_rng(0)
        # prompts over one prefill chunk and over a page: several chunks,
        # then decode steps that cross page boundaries
        reqs = [eng.submit(rng.integers(1, cfg['vocab_size'], n).tolist(),
                           max_new_tokens=12, top_k=0) for n in (5, 21, 40)]
        while eng.scheduler.has_work:
            eng.step()
    finally:
        eng.shutdown()
    worst, exact, count = runner.logit_gaps(
        model, cfg, [(r, 12) for r in reqs], width=64)
    assert count == 36 and worst < TOL_F32
    # and the check has teeth: a wrong token is seen
    reqs[0].generated[3] = (reqs[0].generated[3] + 1) % cfg['vocab_size']
    worst, _, _ = runner.logit_gaps(model, cfg, [(reqs[0], 12)], width=64)
    assert worst > 0.05


def test_the_reference_does_not_import_the_programs_models():
    import os
    ref_dir = os.path.join(MANIFEST.bench_dir, 'reference')
    for name in os.listdir(ref_dir):
        if name.endswith('.py'):
            with open(os.path.join(ref_dir, name)) as f:
                assert 'paddle_tpu' not in f.read().replace(
                    'paddle_tpu/models/', ''), name
