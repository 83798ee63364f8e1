"""What the latent-attention server cell adds to the benchmark: the
three readers and `mla_cost.py`'s arithmetic on tables whose answer is
known, on a record without the new facts (a program that lacks them),
where each returns None and does not raise; the document stream; the
configuration's bytes from its keys; the manifest's entries; and the
reference's router and YaRN on cases small enough to do by hand."""
import json
import os

import numpy as np
import pytest

import benchtoy
from benchmarks import docstream, mla_cost, trace_reduce
from benchmarks.reference import axk1 as reference

MANIFEST = benchtoy.manifest()
CELL = 'axk1.docs-closed64'
# the cell's toy traffic for the tests that walk EVERY cell of the
# manifest (test_benchmark_lastline.py): `benchtoy.toy` looks it up in
# `benchtoy.CELLS`, which no later PR may edit, so it is registered
# here, as this file is collected — before any test runs
benchtoy.CELLS.setdefault(CELL, dict(
    clients=4, documents=3, document_tokens=[16, 32],
    question_tokens=[4, 8], output_tokens=[2, 4], grid=16,
    warm_completions=4, trace_steps=5,
    engine=dict(page_size=8, max_batch_size=4, prefill_chunk=16,
                num_pages=64, max_pages_per_seq=8, fused_k=1, spec_k=0,
                prefix_cache=True)))
NEW = ['mla_attention_ms_per_step.serve', 'mla_attention_roofline.serve',
       'prefix_hit_share.serve']
CFG = MANIFEST.config(MANIFEST.cell(CELL))
MIX = MANIFEST.traffic(MANIFEST.cell(CELL))
ROW, PAIR = mla_cost.latent_row_bytes(CFG), mla_cost.pair_flops(CFG)
HBM, MXU = 819e9, 197e12


def reader(metric):
    return MANIFEST.load_module('layer_metrics', metric)


def facts(**over):
    out = {'kind': 'serve', 'steps': 100, 'traced_steps': 4,
           'device_kind': 'TPU v5 lite',
           'counters': {'prefix_hit_tokens_total': 988_000,
                        'prompt_tokens_total': 1_000_000},
           'mla': {'traced': {'attn_kv_tokens_read_total': 30_000_000,
                              'attn_kv_tokens_read_chunks_total': 2_000_000,
                              'attn_qk_pairs_total': 230_000_000},
                   'row_bytes': ROW, 'pair_flops': PAIR}}
    out.update(over)
    return out


def trace(latent):
    return {'chips': {0: {'ops': {
        'pallas:paged_attention_latent': latent,
        'pallas:paged_attention': 9.0,          # another model's: not read
        'pallas:moe_grouped_matmul': 0.25, 'fusion:fusion': 1.0}}}}


def test_the_costs_from_the_published_keys():
    # a token's row in one layer: 512 latent + 64 rotary lanes in bf16
    assert ROW == (512 + 64) * 2 == 1_152
    # a pair, absorbed: per head a score over 576 lanes and an output
    # over 512; up-projected: 192 + 128
    assert PAIR == 2 * 64 * (576 + 512) == 139_264
    assert mla_cost.pair_flops(CFG, absorbed=False) == 2 * 64 * 320 == 40_960
    assert mla_cost.cache_bytes_per_token(CFG) == 6 * 1_152 == 6_912
    parts = mla_cost.weight_params(CFG)
    # embedding + head + final norm; layer 0; five expert layers
    assert parts['embedding_and_head'] == 2 * 20480 * 7168 + 7168
    assert round(parts['dense_layers'] / 1e6, 1) == 497.5
    assert round(parts['expert_layers'] / 5e6, 1) == 675.0
    assert round(mla_cost.weight_bytes(CFG) / 1e9, 2) == 8.33
    # the pool as published, and as the padded rows hold it
    pages = MIX['engine']['num_pages'] * MIX['engine']['page_size']
    assert round(pages * 6_912 / 1e9, 2) == 3.98
    assert round(pages * 6 * 640 * 2 / 1e9, 2) == 4.42


def test_least_seconds_takes_each_groups_larger_bound():
    """Decode rows (pairs == keys) are bound by their bytes, chunk rows
    by their products; the switch between the bounds sits where a key is
    read by 1,152 B / 819 GB/s x 197 TFLOP/s / 139,264 = 1.99 queries."""
    least, bound = mla_cost.least_seconds(
        30_000_000, 2_000_000, 230_000_000, ROW, PAIR, 'TPU v5 lite')
    decode = 28_000_000
    assert bound['decode'] == ('hbm', pytest.approx(decode * ROW / HBM))
    assert bound['chunks'] == ('mxu', pytest.approx(
        (230_000_000 - decode) * PAIR / MXU))
    assert least == pytest.approx(decode * ROW / HBM
                                  + 202_000_000 * PAIR / MXU)
    # a chunk of one query a key is a decode row: bytes bind
    _, one = mla_cost.least_seconds(1_000, 1_000, 1_000, ROW, PAIR,
                                    'TPU v5 lite')
    assert one['chunks'][0] == 'hbm' and one['decode'] == ('hbm', 0.0)
    # two queries a key: the products take over
    _, two = mla_cost.least_seconds(1_000, 1_000, 2_000, ROW, PAIR,
                                    'TPU v5 lite')
    assert two['chunks'] == ('mxu', pytest.approx(2_000 * PAIR / MXU))
    assert ROW / HBM * MXU / PAIR == pytest.approx(1.99, abs=0.01)
    with pytest.raises(KeyError):
        mla_cost.least_seconds(1, 0, 1, ROW, PAIR, 'no such chip')


def test_the_three_readers():
    t = trace(0.100)
    assert reader('mla_attention_ms_per_step.serve').read(t, facts()) == \
        pytest.approx(25.0)
    least, _ = mla_cost.least_seconds(
        30_000_000, 2_000_000, 230_000_000, ROW, PAIR, 'TPU v5 lite')
    assert reader('mla_attention_roofline.serve').read(
        trace(4 * least), facts()) == pytest.approx(25.0)
    assert reader('prefix_hit_share.serve').read(t, facts()) == \
        pytest.approx(98.8)
    # a trace without the kernel: the share reads 0, not nothing
    assert reader('mla_attention_roofline.serve').read(
        trace(0.0), facts()) == 0.0
    assert reader('mla_attention_ms_per_step.serve').read(
        trace(0.0), facts()) == 0.0


@pytest.mark.parametrize('metric', NEW)
def test_a_record_without_the_new_facts_reads_as_nothing(metric):
    """Another server's record, or the parent's program: no `mla`, no
    `prompt_tokens_total`."""
    bare = {'kind': 'serve', 'steps': 100, 'traced_steps': 4,
            'counters': {'decode_steps_total': 5,
                         'prefix_hit_tokens_total': 0}}
    planes = trace_reduce.reduce(benchtoy.recorded_trace())
    assert reader(metric).read(planes, bare) is None
    if metric != 'prefix_hit_share.serve':
        assert reader(metric).read({'chips': {}}, facts()) is None


def test_the_recorded_cut_of_the_cells_trace():
    """A cut of the cell's own chip trace (trace_fixture_axk1.json: the
    latent calls of the first layers of one mixed program, decode group
    and chunk group): the kernel's row is there under the name the
    readers look for, and the share is what the chip gave for that
    stretch's counts."""
    with open(os.path.join(benchtoy.HERE, 'trace_fixture_axk1.json')) as f:
        cut = json.load(f)
    reduced = trace_reduce.reduce(cut['planes'])
    ops = reduced['chips'][0]['ops']
    latent = ops['pallas:paged_attention_latent']
    assert latent > 0 and not any(
        k.startswith('pallas:paged_attention') and not k.endswith('latent')
        for k in ops)
    f = facts(traced_steps=1, mla=dict(facts()['mla'],
                                       traced=cut['mla_traced']))
    assert reader('mla_attention_ms_per_step.serve').read(reduced, f) == \
        pytest.approx(latent * 1e3)
    share = reader('mla_attention_roofline.serve').read(reduced, f)
    lo, hi = cut['roofline_between']
    assert 0 < lo < share < hi <= 100


def test_the_document_stream():
    """The same lengths for every seed, whole pages, every document
    equally often; the seed draws the ids alone."""
    lengths = docstream.document_lengths(MIX)
    assert len(lengths) == 24 and lengths == sorted(lengths)
    assert (lengths[0], lengths[1], lengths[-1]) == (8448, 8960, 31808)
    assert sum(lengths) == 425_536 and all(n % 64 == 0 for n in lengths)
    docs = {seed: docstream.documents(MIX, 20480, seed) for seed in (1, 2)}
    assert [len(d) for d in docs[1]] == [len(d) for d in docs[2]] == lengths
    assert docs[1][0] != docs[2][0]
    assert all(1 <= t < 20480 for d in docs[1][:3] for t in d)
    short = [[7] * 64 for _ in range(24)]       # stand-ins: the order only
    streams = {seed: docstream.request_stream(MIX, short, 20480, seed)
               for seed in (1, 2)}
    drawn = {seed: [next(s) for _ in range(24 * 32)]
             for seed, s in streams.items()}
    for seed in (1, 2):
        which = [d for _, _, d in drawn[seed]]
        assert np.bincount(which).tolist() == [32] * 24
        # a round at a time: every document once before any comes again
        assert all(sorted(which[i:i + 24]) == list(range(24))
                   for i in range(0, len(which), 24))
        assert all(p[:64] == short[d] and 64 <= len(p) - 64 <= 512
                   and 64 <= want <= 256 for p, want, d in drawn[seed])
    same = lambda seed: [(len(p), want, d) for p, want, d in drawn[seed]]
    assert same(1) == same(2)
    assert drawn[1][0][0][64:] != drawn[2][0][0][64:]
    questions = [len(p) - 64 for p, _, _ in drawn[1][:256]]
    answers = [want for _, want, _ in drawn[1][:256]]
    assert round(np.mean(questions)) == 215 and round(np.mean(answers)) == 139
    # what a hit is worth: the documents' share of the prompts
    assert 100 * 17_731 / (17_731 + 215.4) == pytest.approx(98.8, abs=0.05)


def test_the_check_waits_for_its_four_requests():
    """The first ask of the shortest document that missed, a later ask
    of it that hit, an ask of the second-shortest, an ask of the LONGEST
    that hit — None while one is missing; the warm phase ends on the
    file's completions and an answer about every document."""
    import types
    runner = MANIFEST.load_module('runners', CFG['runners'][MIX['kind']])
    asks = [(0, 0), (1, 0), (2, 0), (0, 16), (2, 32), (1, 24)]
    reqs = [types.SimpleNamespace(id=i, cached_tokens=c)
            for i, (_, c) in enumerate(asks)]
    asked = {i: d for i, (d, _) in enumerate(asks)}
    done = lambda n: [(r, 4) for r in reqs[:n]]
    assert runner.pick_checked(done(3), asked, 3) is None  # no hit yet
    assert runner.pick_checked(done(4), asked, 3) is None  # none on the longest
    got = runner.pick_checked(done(6), asked, 3)
    assert [r.id for r in got] == [0, 3, 1, 4]
    assert len(got) == MIX['check_requests']
    assert runner.warm_enough(128, 24, MIX)
    assert not runner.warm_enough(128, 23, MIX)
    assert not runner.warm_enough(127, 24, MIX)


def test_the_manifests_new_entries():
    d = MANIFEST.data
    config = next(c for c in d['configs'] if c['name'] == 'axk1')
    assert config['reduced'] == ['num_layers', 'experts_held', 'vocab_held']
    # appended behind what the benchmark had (a later PR appends behind
    # these in turn: nothing here pins the END of a list)
    before = ['gpt3-1.3b', 'bert-large', 'trinity-mini', 'phi4-mini-flash']
    assert [c['name'] for c in d['configs']][:5] == before + ['axk1']
    assert config['source'] == CFG['source'] == \
        'https://huggingface.co/skt/A.X-K1/blob/main/config.json'
    assert d['workloads'][5] == MANIFEST.cell(CELL)
    assert MANIFEST.cell(CELL)['chips'] == 1
    new = d['per_layer'][25:28]
    assert [m['name'] for m in new] == NEW
    for m in new:
        assert m['workloads'][0] == CELL
    assert [(m['layer'], m['moves'], m['source']) for m in new] == [
        ('Pallas kernels', 'serve_tokens_per_s', 'device_trace'),
        ('Pallas kernels', 'serve_tokens_per_s', 'device_trace'),
        ('serving engine', 'ttft_ms_p95', 'program_counter')]
    assert {m['name'] for m in MANIFEST.metrics('per_layer', CELL)} >= \
        set(NEW) | {'engine_step_ms.serve', 'batch_occupancy.serve',
                    'pallas_ms_per_step.serve', 'device_idle_share.serve',
                    'compile_s', 'moe_ms_per_step.serve',
                    'moe_grouped_matmul_roofline.serve',
                    'moe_load_max_over_mean.serve'}
    assert {m['name'] for m in MANIFEST.metrics('end_to_end', CELL)} >= {
        'serve_tokens_per_s', 'ttft_ms_p95', 'itl_ms_p95', 'setup_s'}
    # the lists two older tests pin with `==` do not take the cell
    for pinned in ('paged_attention_ms_per_step.serve',
                   'host_ms_per_step.serve', 'telemetry_ms_per_step.serve',
                   'queue_wait_ms.serve', 'prefill_ms_per_request.serve'):
        entry = next(m for m in d['per_layer'] if m['name'] == pinned)
        assert CELL not in entry['workloads']


def test_the_configuration_file_carries_the_published_keys():
    with open('/opt/skills/guides/model-configs/architectures.jsonl') as f:
        rows = [json.loads(line) for line in f]
    published = next(r for r in rows if r['name'] == 'A.X-K1')['config'] \
        if os.path.exists('/opt/skills/guides/model-configs') else None
    assert published is None or \
        {k: CFG[k] for k in published} == published
    assert (CFG['num_hidden_layers'], CFG['n_routed_experts'],
            CFG['vocab_size'], CFG['kv_lora_rank'], CFG['q_lora_rank']) == \
        (61, 192, 163840, 512, 1536)
    assert CFG['rope_scaling'] == {
        'beta_fast': 32, 'beta_slow': 1, 'factor': 32, 'mscale': 1,
        'mscale_all_dim': 1, 'original_max_position_embeddings': 4096,
        'type': 'yarn'}
    assert (CFG['num_layers'], CFG['experts_held'], CFG['vocab_held']) == \
        (6, [0, 12], 20480)
    assert CFG['reduced'] == ['num_layers', 'experts_held', 'vocab_held']
    assert '16 chips share each layer' in CFG['deployment']
    assert all(isinstance(v, str) and v for v in CFG['assumed'].values())
    assert {'topk_method', 'group_score', 'rotary', 'norms'} <= \
        set(CFG['assumed'])
    assert MIX['engine'] == dict(
        page_size=64, max_batch_size=64, prefill_chunk=256, num_pages=9000,
        max_pages_per_seq=528, fused_k=1, spec_k=0, prefix_cache=True)
    assert (MIX['clients'], MIX['documents'], MIX['warm_completions']) == \
        (64, 24, 128)
    # the longest request fits a row's table
    assert 31808 + 512 + 256 <= 528 * 64 == CFG['max_seq_len']


# -- the reference, by hand --------------------------------------------------
def test_the_references_router_keeps_two_groups_of_four():
    """8 experts in 4 groups of 2, top-2 of the 2 best groups: a token
    whose two largest scores sit in groups whose SUM loses takes neither
    of them."""
    H = 8
    m = np.eye(H, dtype=np.float32)[:1] * 10          # token = e0 * 10
    logit = np.array([[2.0, -9, 1.5, 1.4, 1.9, -9, 0.5, 0.4]]) / 10
    wr = np.zeros((H, 8), np.float32)
    wr[0] = logit
    chosen, w = reference.route(m, wr, 2, 4, 2, 2.5, True)
    s = 1 / (1 + np.exp(-logit[0] * 10))
    # group sums: (e0+e1) < (e2+e3); (e4+e5) < (e2+e3); (e6+e7) next
    sums = s.reshape(4, 2).sum(-1)
    assert sums.argsort()[::-1][:2].tolist() == [1, 3]
    assert sorted(chosen[0].tolist()) == [2, 3]
    np.testing.assert_allclose(sorted(w[0]), sorted(
        s[[2, 3]] / s[[2, 3]].sum() * 2.5), rtol=1e-6)
    free, _ = reference.route(m, wr, 2, 1, 1, 2.5, True)
    assert sorted(free[0].tolist()) == [0, 4]


def test_the_references_yarn_blend():
    inv, factor, soft = reference.yarn(64, 10000.0, CFG['rope_scaling'])
    assert factor == 1.0 and soft == pytest.approx(
        (0.1 * np.log(32) + 1) ** 2)
    base = 10000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(inv[:11], base[:11])             # fast pairs
    np.testing.assert_allclose(inv[23:], base[23:] / 32)        # slow pairs
    assert all(base[k] / 32 < inv[k] < base[k] for k in range(11, 23))
    plain, one, two = reference.yarn(64, 10000.0, None)
    np.testing.assert_allclose(plain, base)
    assert (one, two) == (1.0, 1.0)
