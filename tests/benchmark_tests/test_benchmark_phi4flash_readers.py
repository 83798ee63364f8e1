"""What the state-space server cell adds to the benchmark: the four
readers and the two byte-count files on tables whose answer is known,
on a record without the new facts (a program that lacks them), where
each returns None and does not raise; the manifest's entries; the
reference on a case small enough to roll by hand; and the runner end to
end at toy widths."""
import json
import os

import numpy as np
import pytest

import benchtoy
from benchmarks import attn_bytes, common, ssm_bytes, trace_reduce
from benchmarks.reference import phi4flash as reference

MANIFEST = benchtoy.manifest()
CELL = 'phi4-mini-flash.reason-closed64'
# the cell's toy traffic for the tests that walk EVERY cell of the
# manifest (test_benchmark_lastline.py): `benchtoy.toy` looks it up in
# `benchtoy.CELLS`, which no later PR may edit, so it is registered
# here, as this file is collected — before any test runs
benchtoy.CELLS.setdefault(CELL, dict(
    clients=4, prompt_tokens=[8, 24], output_tokens=[2, 6], grid=16,
    warm_completions=4, trace_steps=5,
    engine=dict(page_size=8, max_batch_size=4, prefill_chunk=16,
                num_pages=64, max_pages_per_seq=8, fused_k=1, spec_k=0,
                prefix_cache=False)))
NEW = ['ssm_ms_per_step.serve', 'ssm_scan_roofline.serve',
       'diff_attention_ms_per_step.serve', 'diff_attention_roofline.serve']
ROW = ssm_bytes.state_row_bytes(5120, 16)
TOKEN = ssm_bytes.token_bytes(5120, 16)
KV = attn_bytes.kv_token_bytes(20, 64)


def reader(metric):
    return MANIFEST.load_module('layer_metrics', metric)


def facts(**over):
    out = {'kind': 'serve', 'steps': 100, 'traced_steps': 4,
           'device_kind': 'TPU v5 lite',
           'ssm': {'traced': {'ssm_rows_total': 2304,
                              'ssm_tokens_total': 4000},
                   'state_row_bytes': ROW, 'token_bytes': TOKEN},
           'attn': {'traced': {'attn_kv_tokens_read_total': 2_000_000},
                    'kv_token_bytes': KV}}
    out.update(over)
    return out


def trace(scan, attention):
    return {'chips': {0: {'ops': {
        'pallas:selective_scan': scan,
        'pallas:paged_attention_diff': attention / 4,
        'pallas:paged_attention_diff_window': 3 * attention / 4,
        'pallas:layer_norm_fwd': 0.25, 'fusion:fusion': 1.0}}}}


def test_the_byte_counts():
    # a (row, layer) update moves the [16, 5120] float32 state in and out
    assert ROW == 2 * 16 * 5120 * 4 == 655_360
    # a (token, layer): x, dt, y of 5120 and B, C of 16, float32
    assert TOKEN == (3 * 5120 + 2 * 16) * 4 == 61_568
    # K and V of 20 sub-heads of 64 in bf16: one token of one plane
    assert KV == 2 * 20 * 64 * 2 == 5_120
    assert ssm_bytes.least_seconds(9 * 64, 9 * 64, ROW, TOKEN,
                                   'TPU v5 lite') == pytest.approx(
        9 * 64 * (ROW + TOKEN) / 819e9)
    assert attn_bytes.least_seconds(10 ** 6, KV, 'TPU v5 lite') == \
        pytest.approx(5.12e9 / 819e9)
    with pytest.raises(KeyError):
        attn_bytes.least_seconds(1, KV, 'no such chip')


def test_ms_per_step_readers():
    t = trace(0.008, 0.024)
    assert reader('ssm_ms_per_step.serve').read(t, facts()) == \
        pytest.approx(2.0)
    # every `paged_attention*` class: the window layers' and the others'
    assert reader('diff_attention_ms_per_step.serve').read(t, facts()) == \
        pytest.approx(6.0)


def test_rooflines_are_least_time_over_kernel_time():
    least_scan = (2304 * ROW + 4000 * TOKEN) / 819e9
    least_attn = 2_000_000 * KV / 819e9
    t = trace(2 * least_scan, 4 * least_attn)
    assert reader('ssm_scan_roofline.serve').read(t, facts()) == \
        pytest.approx(50.0)
    assert reader('diff_attention_roofline.serve').read(t, facts()) == \
        pytest.approx(25.0)
    # a trace without the kernels: the shares read 0, not nothing
    for metric in ('ssm_scan_roofline.serve',
                   'diff_attention_roofline.serve'):
        assert reader(metric).read(trace(0.0, 0.0), facts()) == 0.0


@pytest.mark.parametrize('metric', NEW)
def test_a_record_without_the_new_facts_reads_as_nothing(metric):
    """The GPT server's record, or the parent's program: no `ssm`, no
    `attn`."""
    bare = {'kind': 'serve', 'steps': 100, 'traced_steps': 4,
            'counters': {'decode_steps_total': 5}}
    planes = trace_reduce.reduce(benchtoy.recorded_trace())
    assert reader(metric).read(planes, bare) is None
    assert reader(metric).read({'chips': {}}, facts()) is None


def test_the_recorded_cut_of_the_cells_trace():
    """A cut of the cell's own chip trace (trace_fixture_phi4flash.json:
    the first three Mamba / window-attention layer pairs of one mixed
    program): both kernels' rows are there under the names the readers
    look for, and the shares are what the chip gave — the [64, 1] scan
    call moves its 42 MB of state in 71 us, the window layers' decode
    call its 168 MB of keys and values in 290 us."""
    with open(os.path.join(benchtoy.HERE,
                           'trace_fixture_phi4flash.json')) as f:
        cut = json.load(f)
    reduced = trace_reduce.reduce(cut['planes'])
    ops = reduced['chips'][0]['ops']
    scan = ops['pallas:selective_scan']
    paged = sum(v for k, v in ops.items()
                if k.startswith('pallas:paged_attention_diff'))
    assert scan > 0 and paged > 0
    assert 'pallas:paged_attention_diff_window' in ops
    f = facts(traced_steps=1, ssm=dict(facts()['ssm'],
                                       traced=cut['ssm_traced']),
              attn=dict(facts()['attn'], traced=cut['attn_traced']))
    assert reader('ssm_ms_per_step.serve').read(reduced, f) == \
        pytest.approx(scan * 1e3)
    assert reader('diff_attention_ms_per_step.serve').read(reduced, f) == \
        pytest.approx(paged * 1e3)
    assert 50 < reader('ssm_scan_roofline.serve').read(reduced, f) < 60
    assert 65 < reader('diff_attention_roofline.serve').read(reduced, f) < 75


def test_the_manifests_new_entries():
    d = MANIFEST.data
    config = next(c for c in d['configs'] if c['name'] == 'phi4-mini-flash')
    assert config['reduced'] == [] and d['configs'][-1] is config
    assert d['workloads'][-1] == MANIFEST.cell(CELL)
    assert MANIFEST.cell(CELL)['chips'] == 1
    assert [m['name'] for m in d['per_layer'][-4:]] == NEW
    for m in d['per_layer'][-4:]:
        assert m['workloads'] == [CELL] and m['layer'] == 'Pallas kernels'
        assert m['moves'] == 'serve_tokens_per_s'
        assert m['source'] == 'device_trace'
    assert {m['name'] for m in MANIFEST.metrics('per_layer', CELL)} == \
        set(NEW) | {'engine_step_ms.serve', 'batch_occupancy.serve',
                    'pallas_ms_per_step.serve', 'device_idle_share.serve',
                    'compile_s', 'window_kv_read_share.serve'}
    assert {m['name'] for m in MANIFEST.metrics('end_to_end', CELL)} == {
        'serve_tokens_per_s', 'ttft_ms_p95', 'itl_ms_p95', 'setup_s'}
    # the lists two older tests pin with `==` do not take the cell
    for pinned in ('paged_attention_ms_per_step.serve',
                   'host_ms_per_step.serve', 'telemetry_ms_per_step.serve',
                   'queue_wait_ms.serve', 'prefill_ms_per_request.serve'):
        entry = next(m for m in d['per_layer'] if m['name'] == pinned)
        assert CELL not in entry['workloads']


def test_the_configuration_file_carries_the_published_widths():
    cfg = MANIFEST.config(MANIFEST.cell(CELL))
    published = {
        'embd_pdrop': 0, 'hidden_act': 'silu', 'hidden_size': 2560,
        'intermediate_size': 10240, 'layer_norm_eps': 1e-05,
        'max_position_embeddings': 262144, 'mb_per_layer': 2,
        'model_type': 'phi4flash', 'num_attention_heads': 40,
        'num_hidden_layers': 32, 'num_key_value_heads': 20,
        'resid_pdrop': 0, 'sliding_window': 512,
        'tie_word_embeddings': True, 'mlp_bias': False,
        'lm_head_bias': False, 'vocab_size': 200064}
    assert {k: cfg[k] for k in published} == published
    assert cfg['reduced'] == [] and 'one chip holds the whole model' in \
        cfg['deployment']
    assert cfg['assumed_sizes'] == {'d_state': 16, 'd_conv': 4, 'expand': 2,
                                    'dt_rank': 160}
    assert all(isinstance(v, str) and v for v in cfg['assumed'].values())
    mix = MANIFEST.traffic(MANIFEST.cell(CELL))
    assert mix['engine']['num_pages'] * 16 * 9 * KV == 5_284_823_040
    assert mix['prompt_tokens'] == [64, 768] and mix['clients'] == 64


# -- the reference, by hand --------------------------------------------------
def _tiny(seed=0):
    """2 tokens through 4 layers (Mamba, full attention, a gated memory
    unit, a cross layer): hidden 8, one pair of sub-heads of 4 on one kv
    pair, 4 channels x 2 states, 2 taps."""
    rng = np.random.default_rng(seed)
    H, D, dn, N, R, K, F, V = 8, 4, 4, 2, 1, 2, 6, 11
    w = lambda *s: rng.standard_normal(s).astype(np.float32) * 0.5
    base = lambda: {'norm1_w': 1 + w(H), 'norm1_b': w(H),
                    'norm2_w': 1 + w(H), 'norm2_b': w(H),
                    'gate_up': w(H, 2 * F), 'down': w(F, H)}
    lam = lambda: {'lambda_q1': w(D), 'lambda_k1': w(D), 'lambda_q2': w(D),
                   'lambda_k2': w(D), 'subln': 1 + w(2 * D),
                   'o_proj': w(2 * D, H), 'o_bias': w(H)}
    layers = [
        dict(base(), in_proj=w(H, 2 * dn), conv_w=w(K, dn), conv_b=w(dn),
             x_proj=w(dn, R + 2 * N), dt_proj=w(R, dn), dt_bias=w(dn),
             a_log=w(dn, N), d_skip=w(dn), out_proj=w(dn, H)),
        dict(base(), **lam(), qkv_proj=w(H, 6 * D), qkv_bias=w(6 * D)),
        dict(base(), in_proj=w(H, dn), out_proj=w(dn, H)),
        dict(base(), **lam(), qkv_proj=w(H, 2 * D), qkv_bias=w(2 * D))]
    cfg = dict(num_layers=4, num_heads=2, num_kv_heads=2, head_dim=D,
               hidden_size=H, sliding_window=None, layer_norm_eps=1e-5,
               d_state=N, d_conv=K, dt_rank=R, memory_layer=0,
               shared_kv_layer=1, lambda_init=[0.1, 0.2, 0.3, 0.4],
               layer_kinds=['mamba', 'attention', 'gmu', 'cross_attention'])
    params = {'embed': w(V, H), 'final_norm_w': 1 + w(H),
              'final_norm_b': w(H)}
    return params, layers, cfg


def _by_hand(params, layers, cfg, ids):
    """The same two tokens, scalar by scalar where it matters."""
    f8 = lambda a: np.asarray(a, np.float64)
    ln = lambda x, g, b: (x - x.mean(-1, keepdims=True)) / np.sqrt(
        x.var(-1, keepdims=True) + 1e-5) * f8(g) + f8(b)
    silu = lambda x: x / (1 + np.exp(-x))
    D, N, R = cfg['head_dim'], cfg['d_state'], cfg['dt_rank']
    h = f8(params['embed'])[ids]
    memory = shared = None
    for i, p in enumerate(layers):
        a = ln(h, p['norm1_w'], p['norm1_b'])
        kind = cfg['layer_kinds'][i]
        if kind == 'mamba':
            xz = a @ f8(p['in_proj'])
            dn = xz.shape[1] // 2
            x, z = xz[:, :dn], xz[:, dn:]
            cw = f8(p['conv_w'])
            # 2 taps: tap 1 on the token itself, tap 0 on the one before
            x = silu(np.stack([x[0] * cw[1], x[1] * cw[1] + x[0] * cw[0]])
                     + f8(p['conv_b']))
            dbc = x @ f8(p['x_proj'])
            dt = np.log1p(np.exp(dbc[:, :R] @ f8(p['dt_proj'])
                                 + f8(p['dt_bias'])))
            B, C, A = dbc[:, R:R + N], dbc[:, R + N:], -np.exp(f8(p['a_log']))
            s0 = (dt[0] * x[0])[:, None] * B[0][None, :]
            s1 = np.exp(dt[1][:, None] * A) * s0 \
                + (dt[1] * x[1])[:, None] * B[1][None, :]
            y = np.stack([s0 @ C[0], s1 @ C[1]]) + f8(p['d_skip']) * x
            memory, out = y, (y * silu(z)) @ f8(p['out_proj'])
        elif kind == 'gmu':
            out = (silu(a @ f8(p['in_proj'])) * memory) @ f8(p['out_proj'])
        else:
            qkv = a @ f8(p['qkv_proj']) + f8(p['qkv_bias'])
            q = qkv[:, :2 * D]
            if kind == 'attention':
                shared = (qkv[:, 2 * D:4 * D], qkv[:, 4 * D:])
            k, v = shared
            lam = np.exp(f8(p['lambda_q1']) @ f8(p['lambda_k1'])) \
                - np.exp(f8(p['lambda_q2']) @ f8(p['lambda_k2'])) \
                + cfg['lambda_init'][i]
            o = np.zeros((2, 2 * D))
            for t in range(2):
                both = []
                for s in range(2):
                    sc = k[:t + 1, s * D:(s + 1) * D] \
                        @ q[t, s * D:(s + 1) * D] / 2.0
                    pr = np.exp(sc) / np.exp(sc).sum()
                    both.append(pr @ v[:t + 1])
                d = both[0] - lam * both[1]
                o[t] = d / np.sqrt((d * d).mean() + 1e-5) * f8(p['subln']) \
                    * (1 - cfg['lambda_init'][i])
            out = o @ f8(p['o_proj']) + f8(p['o_bias'])
        h = h + out
        gu = ln(h, p['norm2_w'], p['norm2_b']) @ f8(p['gate_up'])
        F = gu.shape[1] // 2
        h = h + (silu(gu[:, :F]) * gu[:, F:]) @ f8(p['down'])
    return ln(h, params['final_norm_w'], params['final_norm_b']) \
        @ f8(params['embed']).T


def test_the_reference_against_two_tokens_by_hand():
    params, layers, cfg = _tiny()
    ids = np.array([3, 7])
    got = np.asarray(reference.forward(params, lambda i: layers[i], cfg, ids,
                                       vocab_block=4))
    np.testing.assert_allclose(got, _by_hand(params, layers, cfg, ids),
                               rtol=2e-4, atol=2e-4)
    # the blockwise reduction gives the same gaps as the whole rows
    tokens = np.array([5, 0])
    want = (got.max(-1) - got[[0, 1], tokens]) / (got.max(-1) - got.mean(-1))
    np.testing.assert_allclose(reference.token_gaps(
        params, lambda i: layers[i], cfg, ids, np.arange(2), tokens,
        vocab_block=4), want, rtol=1e-4, atol=1e-6)


def test_the_reference_imports_nothing_of_the_program():
    with open(reference.__file__) as f:
        assert 'paddle_tpu' not in f.read().split('"""', 2)[2]


# -- the runner, end to end at toy widths ---------------------------------------
def test_the_runner_at_toy_widths():
    """All four layer kinds at 8 layers and hidden 64, the committed
    files' every other key: `correct` with two step programs, the new
    facts, and each new reader's answer from them."""
    cell = MANIFEST.cell(CELL)
    cfg = dict(MANIFEST.config(cell), hidden_size=64, num_layers=8,
               num_heads=4, ffn_hidden_size=128, vocab_size=512,
               sliding_window=8, dtype='float32')
    mix = dict(MANIFEST.traffic(cell), clients=4, prompt_tokens=[4, 24],
               output_tokens=[4, 16], grid=16, warm_completions=6,
               trace_steps=5,
               engine=dict(page_size=4, max_batch_size=4, prefill_chunk=4,
                           num_pages=64, max_pages_per_seq=10, fused_k=1,
                           spec_k=0, prefix_cache=False))
    runner = MANIFEST.load_module('runners', cfg['runners'][mix['kind']])
    record = runner.run(common.Context(cfg, mix, 2 ** 31 + 5, 0.6, 1))
    assert record['correct'] and record['failed'] == 0
    f = record['facts']
    assert f['step_shapes'] == 2 and f['compiles_in_window'] == 0
    assert f['kv_planes'] == 3 and f['kv_readers'] == 4
    assert f['state_bytes'] == 5 * 3 * (16 * 128 * 4 + 3 * 128 * 4)
    assert f['traced_steps'] == 5
    assert f['ssm']['traced']['ssm_rows_total'] > 0
    assert f['attn']['traced']['attn_kv_tokens_read_total'] > 0
    f = dict(f, device_kind='TPU v5 lite')
    t = trace(0.5, 0.5)
    assert reader('ssm_ms_per_step.serve').read(t, f) == pytest.approx(100.0)
    assert reader('ssm_scan_roofline.serve').read(t, f) > 0
    assert reader('diff_attention_roofline.serve').read(t, f) > 0
    assert 0 < reader('window_kv_read_share.serve').read(t, f) < 100
