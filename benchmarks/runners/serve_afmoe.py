"""Server cells of an AFMoE configuration (sparse experts, grouped-query
attention, window and full layers): AfmoeForCausalLM behind the SAME
paged-KV ServingEngine, scheduler, pool, sampler and telemetry as the
GPT server cell, driven in one thread by the closed-loop pool of
`benchmarks/loadgen.py`. Window, clocks, `facts` keys and the rules of
`correct` are `serve_engine.py`'s, so every `.serve` reader reads this
runner's record; what is added is the experts' counters (`facts['moe']`)
and the router's check.

The model module is imported before the device is touched: a checkout
whose program lacks it fails at once with an ImportError.
"""
from paddle_tpu.models.afmoe import AfmoeConfig, AfmoeForCausalLM

import gc
import statistics
import time

import numpy as np

from benchmarks import loadgen
from benchmarks.common import log, percentile, quartiles
from benchmarks.reference import afmoe as reference

# What `correct` compares, and the limits. Everything compared comes
# out of the engine's own dispatches in the warm phase, under the cell's
# load: the tokens it emitted and the rows per expert its [1, chunk]
# program counted. Each limit stands between two sets of chip readings
# (PERF.md section 6, PR 27), all at the cell's own load: the served
# path's over its seeds, and those of runs with one piece of the
# mathematics broken (`tools/afmoe_breakages.py`, one to three seeds):
# the balancing bias left out of the choice, the shared expert dropped,
# route_scale dropped, the window bound dropped, rotary in the full
# layers too, and every norm's output rounded to float8 — the precision
# below the configuration's. NOT held: the router's product in bf16 —
# the compiler keeps it in float32 where nothing forces the rounding,
# and forced it moves 4 rows in 7,800 of the first expert layer.
#
# Per emitted greedy token: how far it sits below the float32
# reference's argmax at its position, as a share of the logit scale
# (max - mean of the row) — serve_engine.py's measure.
#
# (1) The share of tokens that ARE the reference's argmax (99 tokens).
# bf16 noise and an expert flipped by a router near-tie move the argmax
# where the reference's top two logits are close: served 0.848-0.946;
# rotary in the full layers 0.768-0.798 (the one limit it fails, by one
# to four tokens), no bias 0.576-0.616, float8 norms 0.515-0.576, the
# window dropped 0.586, route_scale dropped 0.283, no shared expert 0.101.
EXACT_TOKEN_TOL = 0.80
# (2) The mean distance: nearly all of it the one or two tokens whose
# experts flipped. Served 0.0010-0.0061; float8 norms 0.0183-0.0246, no
# bias 0.021-0.030, route_scale dropped 0.062-0.067, the window dropped
# 0.160.
LOGIT_GAP_MEAN_TOL = 0.012
# (3) The worst distance. Its tail is heavy (served 0.041-0.173: a token
# whose experts flip in two layers), so this limit only catches what is
# grossly wrong — no shared expert 0.598, the window dropped 0.861; a
# wrong page, position or mask puts the emitted token anywhere in the
# row, a gap near 1.
LOGIT_GAP_TOL = 0.35
# (4) Routing, from the dispatch itself: a checked request's last prompt
# chunk is the one prefill dispatch whose counters the engine fetches —
# the rows each expert took in each expert layer, padding left out. The
# reference routes the same tokens (975 of them, 31,200 rows over the
# four expert layers); the share of (token, expert) rows that sit with
# another expert than the reference's is half the summed difference of
# the two counts over the rows. Near-ties of the eighth and ninth biased
# score flip on bf16 activations, more in later layers: served
# 0.0093-0.0130 (0.004-0.006 in the first expert layer, 0.014-0.019 in
# the last); float8 norms 0.031-0.034, route_scale dropped 0.052-0.055,
# no bias 0.057-0.065, no shared expert 0.098, the window dropped 0.160.
ROUTED_ROWS_MOVED_TOL = 0.02

# the reference's name of a layer's array -> the program's
_LAYER = dict({n: n for n in ('norm1', 'norm2', 'norm3', 'norm4')},
              **{n: 'attn.' + n for n in (
                  'q_proj', 'k_proj', 'v_proj', 'gate_proj', 'o_proj',
                  'q_norm', 'k_norm')})
_DENSE = {'w1': 'mlp.w1', 'w3': 'mlp.w3', 'w2': 'mlp.w2'}
_SPARSE = {'router': 'mlp.router', 'shared_w1': 'mlp.shared.w1',
           'shared_w3': 'mlp.shared.w3', 'shared_w2': 'mlp.shared.w2',
           'experts_w1': 'mlp.experts.w1', 'experts_w3': 'mlp.experts.w3',
           'experts_w2': 'mlp.experts.w2'}


def model_config(cfg, max_seq_len):
    return AfmoeConfig(
        vocab_size=cfg['vocab_size'], hidden_size=cfg['hidden_size'],
        num_layers=cfg['num_layers'],
        num_dense_layers=cfg['num_dense_layers'],
        num_heads=cfg['num_attention_heads'],
        num_kv_heads=cfg['num_key_value_heads'], head_dim=cfg['head_dim'],
        intermediate_size=cfg['intermediate_size'],
        moe_intermediate_size=cfg['moe_intermediate_size'],
        num_experts=cfg['num_experts'],
        num_experts_per_tok=cfg['num_experts_per_tok'],
        num_shared_experts=cfg['num_shared_experts'],
        sliding_window=cfg['sliding_window'],
        # a depth cut further (the tests' toy) keeps the pattern's END,
        # so that a full layer stays
        layer_types=cfg['layer_types_run'][-cfg['num_layers']:],
        rms_norm_eps=cfg['rms_norm_eps'],
        rope_theta=float(cfg['rope_theta']),
        route_scale=cfg['route_scale'], route_norm=cfg['route_norm'],
        mup_enabled=cfg['mup_enabled'], max_seq_len=max_seq_len,
        experts_held=cfg.get('experts_held'), dtype=cfg['dtype'])


def build_model(cfg, seed, max_seq_len):
    import paddle_tpu as paddle
    import paddle_tpu.distributed.fleet as fm
    fm.fleet._hcg = None
    paddle.seed(seed)
    model = AfmoeForCausalLM(model_config(cfg, max_seq_len))
    model.eval()
    return model


def reference_view(model):
    """(params, get_layer, cfg) as benchmarks/reference/afmoe.py takes
    them: the program's seeded arrays by name, nothing computed."""
    p = {n: t.data for n, t in model.named_parameters()}
    b = {n: t.data for n, t in model.named_buffers()}
    c = model.config
    cfg = {k: getattr(c, k) for k in (
        'num_layers', 'num_dense_layers', 'num_heads', 'num_kv_heads',
        'head_dim', 'hidden_size', 'sliding_window', 'layer_types',
        'rms_norm_eps', 'rope_theta', 'num_experts', 'num_experts_per_tok',
        'route_scale', 'route_norm', 'mup_enabled', 'experts_held')}
    params = {'embed': p['embed'], 'final_norm': p['final_norm'],
              'lm_head': p['lm_head']}

    def layer(i):
        names = dict(_LAYER, **(_DENSE if i < c.num_dense_layers
                                else _SPARSE))
        out = {k: p[f'layers.{i}.{n}'] for k, n in names.items()}
        if i >= c.num_dense_layers:
            out['expert_bias'] = b[f'layers.{i}.mlp.expert_bias']
        return out
    return params, layer, cfg


def compare(model, finished, width, last_chunks):
    """Teacher-forced: the reference's full forward over prompt + answer
    of each finished request (padded to `width`, which causal attention
    makes harmless), and (1) for every emitted token its distance below
    the reference's argmax, as a share of the logit scale; (2) the rows
    per expert that the engine's dispatch of the request's last prompt
    chunk counted (`last_chunks[request id] = (first position, tokens,
    rows [expert layers, experts held])`, from the engine's
    `moe_rows_listener`) against the reference's choice for the same
    tokens. Logits are compared, not tokens: with random weights the
    largest logit changes hands on rounding."""
    params, layer, cfg = reference_view(model)
    first, held = cfg['experts_held']
    gaps, where = [], []
    moved = np.zeros((cfg['num_layers'] - cfg['num_dense_layers'],))
    routed = 0
    for req, _ in finished:
        out, n_prompt = req.output_ids(), len(req.prompt)
        ids = np.zeros((width,), np.int32)
        ids[:len(out)] = out
        rows = np.arange(n_prompt - 1, len(out) - 1)
        logits, chosen = reference.forward(params, layer, cfg, ids,
                                           rows=rows)
        logits = np.asarray(logits)
        if not np.isfinite(logits).all() or req.id not in last_chunks:
            return {'logit_gap': float('inf'), 'logit_gap_mean': float('inf'),
                    'exact_tokens': 0.0, 'tokens': 0,
                    'routed_rows_moved': float('inf'),
                    'routed_rows_moved_by_layer': [], 'routed_rows': 0}
        for row, pos in zip(logits, range(n_prompt, len(out))):
            gaps.append(float(row.max() - row[out[pos]])
                        / float(row.max() - row.mean()))
            where.append((n_prompt, pos - n_prompt))
        start, n, served = last_chunks[req.id]
        for j, ref in enumerate(chosen):
            want = np.bincount(ref[start:start + n].ravel(),
                               minlength=cfg['num_experts'])
            moved[j] += np.abs(served[j] - want[first:first + held]).sum()
        routed += n * cfg['num_experts_per_tok']
    worst = sorted(range(len(gaps)), key=lambda i: -gaps[i])[:4]
    log(f'the largest logit gaps (prompt tokens, answer position, gap): '
        f'{[where[i] + (round(gaps[i], 4),) for i in worst]}')
    return {'logit_gap': max(gaps, default=0.0),
            'logit_gap_mean': sum(gaps) / max(len(gaps), 1),
            'exact_tokens': sum(g == 0.0 for g in gaps) / max(len(gaps), 1),
            'tokens': len(gaps),
            'routed_rows_moved': float(moved.sum()) / 2 / max(
                routed * len(moved), 1),
            'routed_rows_moved_by_layer': [
                float(m) / 2 / max(routed, 1) for m in moved],
            'routed_rows': routed * len(moved)}


def passes(check):
    return bool(check['tokens'] > 0 and check['routed_rows'] > 0
                and check['exact_tokens'] >= EXACT_TOKEN_TOL
                and check['logit_gap_mean'] <= LOGIT_GAP_MEAN_TOL
                and check['logit_gap'] <= LOGIT_GAP_TOL
                and check['routed_rows_moved'] <= ROUTED_ROWS_MOVED_TOL)


def describe(check):
    """The check's numbers, each beside its limit."""
    by_layer = [round(m, 4) for m in check['routed_rows_moved_by_layer']]
    return (f'{check["exact_tokens"]:.3f} are its argmax (at least '
            f'{EXACT_TOKEN_TOL}); logit gap mean '
            f'{check["logit_gap_mean"]:.5f} (at most {LOGIT_GAP_MEAN_TOL}), '
            f'worst {check["logit_gap"]:.4f} of scale (at most '
            f'{LOGIT_GAP_TOL}); of {check["routed_rows"]} rows the last '
            f'prompt chunks routed, {check["routed_rows_moved"]:.5f} sit '
            f'with another expert than the reference\'s (at most '
            f'{ROUTED_ROWS_MOVED_TOL}; by expert layer {by_layer})')


def pick_checked(finished, count, past):
    """`count` finished requests, one of them (if any has finished) with
    a prompt past `past` tokens, so that prefill then decode crosses
    the window."""
    long = [f for f in finished if len(f[0].prompt) > past][:1]
    rest = [f for f in finished if f not in long]
    return long + rest[:count - len(long)]


MOE_KEYS = ('moe_rows_total', 'moe_experts_touched_total',
            'moe_calls_total', 'moe_load_sum', 'moe_load_steps')


def run(ctx):
    import jax
    from paddle_tpu.serving import ServingConfig, ServingEngine
    from paddle_tpu.serving.scheduler import RequestState
    span = jax.profiler.TraceAnnotation
    cfg, mix = ctx.config, ctx.traffic
    width = mix['prompt_tokens'][1] + mix['output_tokens'][1]
    model = build_model(cfg, ctx.weights_seed, width)
    ctx.mark('model')
    eng = ServingEngine(model, ServingConfig(**mix['engine']))
    ctx.mark('engine')

    def submit(prompt, want):
        with span('bench::serve.submit'):
            return eng.submit(prompt, max_new_tokens=want, top_k=0)

    def produced(req):
        return -1 if req.state == RequestState.ABORTED \
            else len(req.generated)
    pool = loadgen.ClosedLoop(
        mix['clients'], loadgen.request_stream(mix, cfg['vocab_size'],
                                               ctx.seed),
        submit, produced, time.perf_counter)
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

    last_chunks = {}

    def heard(req, start, n, rows):
        last_chunks[req.id] = (start, n, rows)

    try:
        # warm phase: compiles the two step shapes, fills the batch and
        # runs until the clients are spread over every phase of a request;
        # the check listens to what the prompts' last chunks routed
        eng.moe_rows_listener = heard
        pool.fill()
        window = cfg['sliding_window']

        def crossed():
            """A finished request whose prompt passes the window (the
            check needs one), if the traffic sends any such."""
            return mix['prompt_tokens'][1] <= window or any(
                len(r.prompt) > window for r, _ in pool.finished)
        while pool.completed < mix['warm_completions'] or not crossed():
            step()
        eng.moe_rows_listener = None
        ctx.mark('warm phase')
        checked = pick_checked(pool.finished, mix['check_requests'], window)
        check = compare(model, checked, width, last_chunks)
        ctx.mark('reference')
        log(f'{len(checked)} requests (prompts '
            f'{[len(r.prompt) for r, _ in checked]}), {check["tokens"]} '
            f'tokens vs the reference: ' + describe(check))
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
        roofline = eng.ledger.roofline() or {}
    finally:
        if on_gc in gc.callbacks:
            gc.callbacks.remove(on_gc)
        gc.unfreeze()
        eng.shutdown()
    in_window = ctx.compiles_in_window()
    counters = {k: after[k] - before[k] for k in (
        'decode_steps_total', 'decode_tokens_total', 'prefill_tokens_total',
        'prefill_chunks_total', 'preemptions_total',
        'requests_completed_total', 'prefix_hit_tokens_total') + MOE_KEYS}
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
    log(f'engine counters over the window {counters}; compiles inside the '
        f'window: {in_window}; ledger roofline block '
        f'{ {k: v for k, v in roofline.items() if "kv_read" in k or "moe" in k} }')
    expert = model.layers[model._sparse[0]].mlp.experts
    return {
        'correct': bool(passes(check) and wrong == 0 and in_window == 0),
        'attempted': pool.sent, 'failed': pool.failed,
        'end_to_end': {
            'serve_tokens_per_s': pool.tokens / elapsed,
            'ttft_ms_p95': percentile(pool.ttft_ms, 95),
            'itl_ms_p95': percentile(pool.gap_ms, 95),
            'setup_s': ctx.setup_s},
        'facts': {'kind': 'serve', 'steps': len(step_ms),
                  'traced_steps': traced, 'engine_step_ms': step_ms,
                  'counters': counters,
                  'max_batch_size': mix['engine']['max_batch_size'],
                  'compile_s': ctx.compile_s,
                  'compiles_in_window': in_window,
                  'device_kind': ctx.device_kind, 'check': check,
                  'moe': {
                      # over the traced steps alone: what the traced
                      # grouped-matmul calls had to read
                      'traced': {k: after_trace[k] - before[k]
                                 for k in MOE_KEYS},
                      'expert_weight_bytes': sum(
                          int(w.data.nbytes) // w.data.shape[0]
                          for w in (expert.w1, expert.w3, expert.w2))},
                  'kv_window': {k: roofline.get(k, 0) for k in (
                      'kv_read_tokens_window', 'kv_read_tokens_full')}},
    }
