"""Server cells of an A.X-K1 configuration (latent attention on
one-array latent planes, sparse experts under group-limited routing,
one chip's share of the experts and of the vocabulary):
AxK1ForCausalLM behind the SAME paged-KV ServingEngine, scheduler, pool,
sampler and telemetry as the other server cells, driven in one thread by
the closed-loop pool of `benchmarks/loadgen.py` over the stream of
`benchmarks/docstream.py` (questions about shared long documents, the
prefix cache ON). Window, clocks, `facts` keys and the rules of
`correct` are `serve_afmoe.py`'s, so every `.serve` reader reads this
runner's record; what is added is `facts['mla']` (the engine's attention
counters over the traced steps and the sizes the roofline needs), the
prompt and hit tokens of the window, and which requests are checked.

The model module is imported before the device is touched: a checkout
whose program lacks it fails at once with an ImportError.
"""
from paddle_tpu.models.axk1 import AxK1Config, AxK1ForCausalLM

import gc
import statistics
import time

import numpy as np

from benchmarks import docstream, loadgen, mla_cost
from benchmarks.common import log, percentile, quartiles
from benchmarks.reference import axk1 as reference

# What `correct` compares, and the limits. Everything compared comes
# out of the engine's own dispatches in the warm phase, under the cell's
# load: the greedy tokens it emitted for four finished requests — the
# FIRST ask of the shortest document (a miss: its 8,448 tokens went
# through 33 chunks of 256, the question through one to three more), a
# LATER ask of the same document (a hit: its question's chunks and its
# decode rows read pages another request wrote), an ask of the
# second-shortest document, and an ask of the LONGEST document that hit
# (31,808 tokens mapped: a table of ~500 pages, ~63 waves a decode row,
# positions eight times past YaRN's original 4,096) — teacher-forced
# through the float32, NON-absorbed reference. Per emitted token: how
# far it sits below the reference's argmax at its position, as a share
# of the logit scale (max - mean of the row) — serve_engine.py's
# measure, reduced by vocabulary block. Each limit stands between two
# sets of chip readings (PERF.md section 6, PR 34), all at the cell's
# own load: the served path's over its seeds, and those of runs with one
# piece broken (`tools/axk1_breakages.py`), two of them the served path
# in the precision below the configuration's bf16.
#
# Served, the four requests' 410 tokens together: 9 runs of 9 seeds
# (argmax share 0.937-0.968, mean 0.00026-0.00081, worst 0.024-0.119;
# the long request's own numbers lie among the others'), after 18 runs
# of 18 seeds on the first three requests alone (0.932-0.975 /
# 0.00016-0.00078 / 0.013-0.093). Broken: the four-request check for
# the two precision controls and the group limit, the three-request
# check for the rest, the warm phase cut to the checked requests. NOT
# held by any limit: the scores rounded to bf16 before the softmax
# (0.957 / 0.00056 / 0.046: inside the served path's range).
#
# (1) The share of the tokens that ARE the reference's argmax. bf16
# noise and an expert flipped by a router near-tie move the argmax where
# the reference's top two logits are close: served 0.932-0.975 (its
# spread over 27 runs is what 410 tokens at 0.955 give: 0.01); the
# router's group limit dropped 0.830-0.871 on four seeds, the latent
# pages in float8 0.815 and 0.817, the weights in float8's mantissa
# 0.629, a hit mapped onto another document's pages 0.250, the shared
# expert dropped 0.160, the latent's norm dropped 0.077, YaRN's factor
# left out of the scale 0.065, k_pe dropped from the scores 0.009,
# values from all 576 lanes 0.000.
EXACT_TOKEN_TOL = 0.90
# (2) The mean distance. Served 0.00016-0.00081; the group limit dropped
# 0.00304-0.00479 (its tokens stay near the reference's: 4 of 8
# experts a token change in a layer that holds 12 of 192), the latent
# pages in float8 0.00381 and 0.00438, the weights in float8's mantissa 0.0175, the
# shared expert dropped 0.196, the latent's norm 0.244, YaRN's factor
# 0.326, k_pe 0.522, another document's pages 0.546, values from all
# lanes 0.838.
LOGIT_GAP_MEAN_TOL = 0.002
# (3) The worst distance. Served 0.013-0.119; the group limit dropped
# (0.083-0.171) and both precision controls (0.098-0.158) pass it and
# fail (1) and (2); the shared expert dropped 0.671, the latent's norm
# 0.687, YaRN's factor 0.931, k_pe 1.179, another document's pages
# 1.385 — a wrong page, position or mask puts the emitted token
# anywhere in the row, a gap near 1.
LOGIT_GAP_TOL = 0.25

_KEYS = ('num_layers', 'first_k_dense_replace', 'num_heads',
         'kv_lora_rank', 'qk_nope_head_dim', 'qk_rope_head_dim',
         'v_head_dim', 'rms_norm_eps', 'rope_theta', 'rope_scaling',
         'n_routed_experts', 'num_experts_per_tok', 'n_group', 'topk_group',
         'routed_scaling_factor', 'norm_topk_prob', 'experts_held')
# the program's name of a layer's array -> the reference's
_RENAME = (('attn.', ''), ('mlp.shared.', 'shared_'),
           ('mlp.experts.', 'experts_'), ('mlp.', ''))


def model_config(cfg, max_seq_len):
    """The configuration file's sizes. The tests' toy cut
    (tests/benchmark_tests/benchtoy.py TOY_WIDTHS) names the heads, the
    MLP width and the vocabulary in its own keys; where it gives them
    they hold, and the ranks, head sizes, experts and groups shrink
    with the hidden size so that every ratio stays."""
    toy = 'num_heads' in cfg
    h = cfg['hidden_size']
    heads = cfg.get('num_heads', cfg['num_attention_heads'])
    sizes = {k: cfg[k] for k in (
        'q_lora_rank', 'kv_lora_rank', 'qk_nope_head_dim',
        'qk_rope_head_dim', 'v_head_dim', 'moe_intermediate_size',
        'n_routed_experts', 'n_group', 'topk_group',
        'num_experts_per_tok')}
    held, vocab_held = cfg['experts_held'], cfg['vocab_held']
    scaling = cfg['rope_scaling']
    if toy:
        sizes.update(q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16,
                     qk_rope_head_dim=8, v_head_dim=16,
                     moe_intermediate_size=32, n_routed_experts=16,
                     n_group=4, topk_group=2, num_experts_per_tok=4)
        held, vocab_held = (0, 4), cfg['vocab_size']
        scaling = dict(scaling, original_max_position_embeddings=16)
    return AxK1Config(
        vocab_size=cfg['vocab_size'], hidden_size=h,
        num_layers=max(cfg['num_layers'], 2),
        first_k_dense_replace=cfg['first_k_dense_replace'],
        num_heads=heads,
        intermediate_size=cfg.get('ffn_hidden_size',
                                  cfg['intermediate_size']),
        n_shared_experts=cfg['n_shared_experts'],
        routed_scaling_factor=cfg['routed_scaling_factor'],
        norm_topk_prob=cfg['norm_topk_prob'],
        rms_norm_eps=cfg['rms_norm_eps'], rope_theta=cfg['rope_theta'],
        rope_scaling=scaling, max_seq_len=max_seq_len,
        experts_held=held, vocab_held=vocab_held, dtype=cfg['dtype'],
        **sizes)


def build_model(cfg, seed, max_seq_len):
    import paddle_tpu as paddle
    import paddle_tpu.distributed.fleet as fm
    fm.fleet._hcg = None
    paddle.seed(seed)
    model = AxK1ForCausalLM(model_config(cfg, max_seq_len))
    model.eval()
    return model


def reference_view(model):
    """(params, get_layer, cfg) as benchmarks/reference/axk1.py takes
    them: the program's seeded arrays by name, nothing computed."""
    p = {n: t.data for n, t in model.named_parameters()}
    cfg = {k: getattr(model.config, k) for k in _KEYS}
    params = {k: p[k] for k in ('embed', 'final_norm', 'lm_head')}

    def layer(i):
        pre, out = f'layers.{i}.', {}
        for n, a in p.items():
            if n.startswith(pre):
                n = n[len(pre):]
                for was, now in _RENAME:
                    if n.startswith(was):
                        n = now + n[len(was):]
                        break
                out[n] = a
        return out
    return params, layer, cfg


def compare(model, finished):
    """Teacher-forced: the reference's full forward over prompt + answer
    of each finished request (padded to whole blocks of the reference's
    rows, which a causal model makes harmless), and for every emitted
    token its distance below the reference's argmax, as a share of the
    logit scale. Logits are compared, not tokens: with random weights
    the largest logit changes hands on rounding. The limits read all
    requests' tokens together; `by_request` keeps each request's own
    (prompt tokens, cached tokens, share that are the argmax, mean,
    worst)."""
    params, layer, cfg = reference_view(model)
    block = reference.QUERY_BLOCK
    gaps, where, by_request = [], [], []
    for req in finished:
        out, n_prompt = req.output_ids(), len(req.prompt)
        width = min(-(-len(out) // block) * block, model.config.max_seq_len)
        ids = np.zeros((width,), np.int32)
        ids[:len(out)] = out
        got = reference.token_gaps(
            params, layer, cfg, ids, np.arange(n_prompt - 1, len(out) - 1),
            out[n_prompt:])
        if not np.isfinite(got).all():
            return {'logit_gap': float('inf'),
                    'logit_gap_mean': float('inf'), 'exact_tokens': 0.0,
                    'tokens': 0}
        gaps.extend(float(g) for g in got)
        where.extend((n_prompt, j) for j in range(len(got)))
        by_request.append((n_prompt, req.cached_tokens,
                           float((got == 0.0).mean()), float(got.mean()),
                           float(got.max())))
    worst = sorted(range(len(gaps)), key=lambda i: -gaps[i])[:4]
    log(f'the largest logit gaps (prompt tokens, answer position, gap): '
        f'{[where[i] + (round(gaps[i], 4),) for i in worst]}')
    log(f'each request (prompt tokens, cached, are the argmax, mean, worst): '
        f'{[r[:2] + tuple(round(x, 5) for x in r[2:]) for r in by_request]}')
    return {'logit_gap': max(gaps, default=0.0),
            'logit_gap_mean': sum(gaps) / max(len(gaps), 1),
            'exact_tokens': sum(g == 0.0 for g in gaps) / max(len(gaps), 1),
            'tokens': len(gaps), 'by_request': by_request}


def passes(check):
    return bool(check['tokens'] > 0
                and check['exact_tokens'] >= EXACT_TOKEN_TOL
                and check['logit_gap_mean'] <= LOGIT_GAP_MEAN_TOL
                and check['logit_gap'] <= LOGIT_GAP_TOL)


def describe(check):
    """The check's numbers, each beside its limit."""
    return (f'{check["exact_tokens"]:.3f} are its argmax (at least '
            f'{EXACT_TOKEN_TOL}); logit gap mean '
            f'{check["logit_gap_mean"]:.5f} (at most {LOGIT_GAP_MEAN_TOL}), '
            f'worst {check["logit_gap"]:.4f} of scale (at most '
            f'{LOGIT_GAP_TOL})')


def pick_checked(finished, asked, documents):
    """The four requests the check reads, of those finished, or None
    while one is missing: the first ask of document 0 (the shortest) if
    the cache missed it, a later ask of document 0 that hit, an ask of
    document 1, and an ask of the LONGEST document that hit (its
    question's chunks and decode rows read ~500 pages another request
    wrote, at positions eight times past YaRN's original 4,096).
    `asked`: request id -> document index."""
    last = documents - 1
    of = {d: [r for r, _ in finished if asked[r.id] == d]
          for d in (0, 1, last)}
    miss = [r for r in of[0] if r.cached_tokens == 0][:1]
    hit = [r for r in of[0] if r.cached_tokens > 0][:1]
    long_hit = [r for r in of[last] if r.cached_tokens > 0][:1]
    if miss and hit and of[1] and long_hit:
        return [miss[0], hit[0], of[1][0], long_hit[0]]
    return None


def warm_enough(completed, documents_answered, mix):
    """Whether the warm phase has run long enough, the checked requests
    apart: the traffic file's completions, and an answer about every
    document — so that in the window every request hits its document.
    (`tools/axk1_breakages.py` cuts this short: its window is not
    measured.)"""
    return completed >= mix['warm_completions'] \
        and documents_answered == mix['documents']


ATTN_KEYS = ('attn_kv_tokens_read_total', 'attn_kv_tokens_read_chunks_total',
             'attn_qk_pairs_total')
MOE_KEYS = ('moe_rows_total', 'moe_experts_touched_total',
            'moe_calls_total', 'moe_load_sum', 'moe_load_steps')


def peak_gb():
    """The device's peak bytes so far, in GB (0.0 where the backend
    keeps no such count: the CPU of the tests)."""
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get('peak_bytes_in_use', 0) / 1e9


def run(ctx):
    import jax
    from paddle_tpu.serving import ServingConfig, ServingEngine
    from paddle_tpu.serving.scheduler import RequestState
    span = jax.profiler.TraceAnnotation
    cfg, mix = ctx.config, ctx.traffic
    model = build_model(cfg, ctx.weights_seed, cfg['max_seq_len'])
    vocab = model.config.vocab_held
    ctx.mark('model')
    peaks = {'model': peak_gb()}
    eng = ServingEngine(model, ServingConfig(**mix['engine']))
    ctx.mark('engine')
    peaks['engine'] = peak_gb()
    docs = docstream.documents(mix, vocab, ctx.seed)
    asked, sent = {}, []

    def stream():
        for prompt, want, doc in docstream.request_stream(
                mix, docs, vocab, ctx.seed):
            sent.append(doc)
            yield prompt, want

    def submit(prompt, want):
        with span('bench::serve.submit'):
            req = eng.submit(prompt, max_new_tokens=want, top_k=0)
        asked[req.id] = sent[-1]
        return req

    def produced(req):
        return -1 if req.state == RequestState.ABORTED \
            else len(req.generated)
    pool = loadgen.ClosedLoop(mix['clients'], stream(), submit, produced,
                              time.perf_counter)
    step_ms, prefilling, gc_ms = [], [], []
    gc_began = [0.0]

    def on_gc(phase, info):
        if phase == 'start':
            gc_began[0] = time.perf_counter()
        else:
            gc_ms.append((info['generation'],
                          (time.perf_counter() - gc_began[0]) * 1e3))

    def step():
        prefilling.append(sum(c.seen == 0 for c in pool.in_flight))
        t = time.perf_counter()
        with span('bench::serve.engine_step'):
            eng.step()
        now = time.perf_counter()
        step_ms.append((now - t) * 1e3)
        pool.observe(now)

    try:
        # warm phase: compiles the two step shapes, prefills every
        # document once (so that in the window every request hits its
        # document), fills the batch and runs until the clients are
        # spread over every phase of a request and the four requests
        # the check reads have finished
        pool.fill()

        def warm():
            return warm_enough(
                pool.completed, len({asked[r.id] for r, _ in pool.finished}),
                mix) and \
                pick_checked(pool.finished, asked, len(docs)) is not None
        while not warm():
            step()
        ctx.mark('warm phase')
        peaks['warm phase'] = peak_gb()
        checked = pick_checked(pool.finished, asked, len(docs))
        if len(checked) != mix['check_requests']:
            raise ValueError(f'the traffic file checks '
                             f'{mix["check_requests"]} requests, the runner '
                             f'picks {len(checked)}')
        check = compare(model, checked)
        ctx.mark('reference')
        peaks['reference'] = peak_gb()
        log(f'device memory peak after each phase, GB: {peaks}')
        log(f'{len(checked)} requests (prompts, cached: '
            f'{[(len(r.prompt), r.cached_tokens) for r in checked]}), '
            f'{check["tokens"]} tokens vs the reference: ' + describe(check))
        shapes = sorted(map(str, eng._step_fns))
        log(f'warm phase completed {pool.completed} requests in '
            f'{len(step_ms)} steps; compiled step shapes {shapes}; pool '
            f'{eng.pool.stats()}')

        # what set-up left on the heap (the model's objects, the warm
        # phase's journals) is set aside, so that a full collection inside
        # the window walks the window's own objects only
        gc.collect()
        gc.freeze()
        ctx.setup_done()
        before = eng.stats()
        pool.open_window()
        del step_ms[:], prefilling[:]
        gc.callbacks.append(on_gc)
        traced, after_trace = 0, before
        t0 = time.perf_counter()
        if ctx.trace:
            with ctx.profile():
                for _ in range(mix['trace_steps']):
                    step()
            traced = len(step_ms)
            after_trace = eng.stats()
            log(f'traced {traced} engine steps in '
                f'{time.perf_counter() - t0:.3f} s (profiler start and '
                f'stop included)')
        while time.perf_counter() - t0 < ctx.seconds:
            step()
        elapsed = time.perf_counter() - t0
        after = eng.stats()
    finally:
        if on_gc in gc.callbacks:
            gc.callbacks.remove(on_gc)
        gc.unfreeze()
        eng.shutdown()
    in_window = ctx.compiles_in_window()
    counters = {k: after[k] - before[k] for k in (
        'decode_steps_total', 'decode_tokens_total', 'prefill_tokens_total',
        'prefill_chunks_total', 'preemptions_total',
        'requests_completed_total', 'prefix_hit_tokens_total',
        'prompt_tokens_total', 'prefix_evictions_total',
        'pipelined_steps_total') + ATTN_KEYS + MOE_KEYS}
    drains = {k: v - before['pipeline_drains_total'].get(k, 0)
              for k, v in after['pipeline_drains_total'].items()}
    wrong = sum(len(r.generated) != want for r, want in pool.finished)
    log(f'window {elapsed:.3f} s, {len(step_ms)} engine steps (median '
        f'{statistics.median(step_ms):.2f} ms): sent {pool.sent}, failed '
        f'{pool.failed}, completed {len(pool.finished)} ({wrong} of a wrong '
        f'length), first tokens {len(pool.ttft_ms)}, tokens {pool.tokens}, '
        f'gaps {len(pool.gap_ms)}, in flight at the end '
        f'{len(pool.in_flight)}; last refusal {pool.last_refusal}')
    med = statistics.median(step_ms)
    longest = sorted(range(len(step_ms)), key=lambda i: -step_ms[i])[:8]
    log(f'engine steps: sum {sum(step_ms) / 1e3:.3f} s, quartiles '
        f'{quartiles(step_ms)}, p99 {percentile(step_ms, 99):.1f} ms; time '
        f'over the median in steps of more than twice it: '
        f'{sum(x - med for x in step_ms if x > 2 * med) / 1e3:.3f} s; the '
        f'longest (index, ms, clients without a first token): '
        f'{[(i, round(step_ms[i], 1), prefilling[i]) for i in longest]}')
    log(f'garbage collections in the window (generation, ms): '
        f'{[(g, round(ms, 1)) for g, ms in gc_ms]}')
    log(f'engine counters over the window {counters}; pipeline drains '
        f'{drains}; compiles inside the window: {in_window}; one plane '
        f'holds {after["kv_plane_bytes_per_token"]} B a token')
    expert = model.layers[model._sparse[0]].mlp.experts
    shapes = len(shapes)        # compiled step programs, after the warm phase
    return {
        'correct': bool(passes(check) and wrong == 0 and in_window == 0
                        and shapes == 2),
        'attempted': pool.sent, 'failed': pool.failed,
        'end_to_end': {
            'serve_tokens_per_s': pool.tokens / elapsed,
            'ttft_ms_p95': percentile(pool.ttft_ms, 95),
            'itl_ms_p95': percentile(pool.gap_ms, 95),
            'setup_s': ctx.setup_s},
        'facts': {'kind': 'serve', 'steps': len(step_ms),
                  'traced_steps': traced, 'engine_step_ms': step_ms,
                  'counters': counters, 'pipeline_drains': drains,
                  'max_batch_size': mix['engine']['max_batch_size'],
                  'compile_s': ctx.compile_s,
                  'compiles_in_window': in_window,
                  'device_kind': ctx.device_kind, 'check': check,
                  'step_shapes': shapes,
                  'kv_plane_bytes_per_token':
                      after['kv_plane_bytes_per_token'],
                  # over the traced steps alone: what the traced latent
                  # calls had to read and to multiply, and the sizes of
                  # the PUBLISHED row and pair
                  'mla': {'traced': {k: after_trace[k] - before[k]
                                     for k in ATTN_KEYS},
                          'row_bytes': mla_cost.latent_row_bytes(cfg),
                          'pair_flops': mla_cost.pair_flops(cfg)},
                  'moe': {
                      'traced': {k: after_trace[k] - before[k]
                                 for k in MOE_KEYS},
                      'expert_weight_bytes': sum(
                          int(w.data.nbytes) // w.data.shape[0]
                          for w in (expert.w1, expert.w3, expert.w2))}},
    }
