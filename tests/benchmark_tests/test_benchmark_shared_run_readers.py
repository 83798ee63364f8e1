"""The reader of the decode rows' shared run
(`decode_shared_key_share.serve`, found BY NAME beside the other readers;
not yet an entry of `BENCHMARK.json`, whose lfm2 cell's per-layer set a
benchmark test pins): on hand-made rings of `serve::device_step` records
whose answer is known, on a window without a run (0.0), on a program
whose records carry no such count (a parent commit: None, never a
raise), and on a toy engine's own ring against its counters."""
import collections

import numpy as np
import pytest

import benchtoy
from benchmarks.layer_metrics import _program_spans

MANIFEST = benchtoy.manifest()
METRIC = 'decode_shared_key_share.serve'
Span = collections.namedtuple(
    'Span', 'id parent name cat start_ns dur_ns tid tname depth args')
MS = 1_000_000


def reader():
    return MANIFEST.load_module('layer_metrics', METRIC)


def ring_of(shared_keys):
    """A warm-up step and its record (must not count: 10**6 keys), then
    one `serve::step` a window record carrying `shared_keys` (None: the
    record has no such arg)."""
    def record(sid, start, keys):
        args = dict(step=sid, steps=1, dispatches=1, shape='decode',
                    decode_rows=4, chunks=0, chunk_tokens=0, chunk_slots=0,
                    emitted=4, behind=1, late=0)
        if keys is not None:
            args.update(shared_rows=4 if keys else 0, shared_keys=keys)
        return Span(sid + 1, 0, 'serve::device_step', 'serve',
                    start * MS, 10 * MS, 1, 'main', 0, args)
    ring = [Span(1, 0, 'serve::step', 'serve', 0, 20 * MS, 1, 'main', 0,
                 {}), record(1, 0, 10 ** 6)]
    for i, keys in enumerate(shared_keys):
        sid = 10 * (i + 1)
        ring += [Span(sid, 0, 'serve::step', 'serve', (100 + 20 * i) * MS,
                      15 * MS, 1, 'main', 0, {}),
                 record(sid, 100 + 20 * i, keys)]
    return ring


def facts(steps, read=10_000, chunks=2_000):
    return {'kind': 'serve', 'steps': steps, 'traced_steps': 2,
            'counters': {'attn_kv_tokens_read_total': read,
                         'attn_kv_tokens_read_chunks_total': chunks}}


@pytest.mark.parametrize('keys,want', [
    ([1_000, 2_000, 3_000], 75.0), ([0, 0, 0], 0.0), ([8_000, 0, 0], 100.0)])
def test_the_share_on_a_hand_made_ring(keys, want, monkeypatch):
    monkeypatch.setattr(_program_spans, 'ring', lambda: ring_of(keys))
    assert reader().read(None, facts(len(keys))) == pytest.approx(want)


def test_a_program_whose_records_carry_no_count(monkeypatch):
    monkeypatch.setattr(_program_spans, 'ring',
                        lambda: ring_of([None, None]))
    assert reader().read(None, facts(2)) is None
    # an untraced run, no decode keys, no counters
    monkeypatch.setattr(_program_spans, 'ring', lambda: ring_of([5, 5]))
    assert reader().read(None, dict(facts(2), traced_steps=0)) is None
    assert reader().read(None, facts(2, read=100, chunks=100)) is None
    assert reader().read(None, {'kind': 'serve', 'steps': 2,
                                'traced_steps': 2}) is None


def test_a_toy_engine_reads_what_it_counted():
    """A toy GPT over the prefix cache with a shared system prompt: the
    reader over the engine's own ring and counters is the counters'
    share (the window is the whole run)."""
    import paddle_tpu as paddle
    import paddle_tpu.profiler as prof
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import ServingConfig, ServingEngine
    paddle.seed(7)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
        max_seq_len=64, hidden_dropout=0.0, attn_dropout=0.0,
        use_flash_attention=False))
    model.eval()
    eng = ServingEngine(model, ServingConfig(
        page_size=4, max_batch_size=4, prefill_chunk=4, num_pages=64,
        max_pages_per_seq=16, prefix_cache=True))
    rng = np.random.RandomState(1)
    system = list(rng.randint(1, 64, 8))
    try:
        first = eng.submit(system + [5, 6], max_new_tokens=6, top_k=0)
        while not first.generated:
            eng.step()
        for n in (3, 4, 5):
            eng.submit(system + list(rng.randint(1, 64, n)),
                       max_new_tokens=6, top_k=0)
        before, mark = eng.stats(), prof.mark()
        steps = 0
        while eng.scheduler.has_work:
            eng.step()
            steps += 1
        after = eng.stats()
    finally:
        eng.shutdown()
    c = {k: after[k] - before[k] for k in (
        'attn_kv_tokens_read_total', 'attn_kv_tokens_read_chunks_total',
        'attn_kv_tokens_shared_total')}
    assert c['attn_kv_tokens_shared_total'] > 0
    got = reader().read(None, {
        'kind': 'serve', 'steps': steps, 'traced_steps': 1,
        'counters': c})
    want = 100.0 * c['attn_kv_tokens_shared_total'] / (
        c['attn_kv_tokens_read_total']
        - c['attn_kv_tokens_read_chunks_total'])
    assert 0 < want < 100
    assert got == pytest.approx(want)
    assert prof.spans(since_id=mark)
