"""Server cells of a GPT configuration: GPTForCausalLM behind the
paged-KV ServingEngine, driven in one thread by the closed-loop pool of
`benchmarks/loadgen.py`. The model recipe is `chip_smoke._serve_model`.

Latencies are taken on the benchmark's clock, read when the `step()`
that produced a token returns; the engine's own request trace is not
read. Its counters, which are exact and repeat, are.
"""
import gc
import statistics
import time

import numpy as np

from benchmarks import loadgen
from benchmarks.common import log, percentile, quartiles
from benchmarks.reference import gpt as reference

# How far, at worst, an emitted greedy token sits below the float32
# reference's argmax at its position, as a share of the logit scale
# (max - mean of the row). Logits are ~N(0, 1) over 50k entries reached
# through ~100 bf16-rounded operations a layer stack; two routes of the
# same mathematics differ by a few percent of the scale (chip_smoke's
# TOL_LOGIT_GAP, held on the chip in PR 21; PR 23 saw 0.0046). A wrong
# page, position or mask puts the emitted token anywhere in the row: a
# gap near 1.
LOGIT_GAP_TOL = 0.05


def build_model(cfg, seed):
    import jax.numpy as jnp
    import paddle_tpu as paddle
    import paddle_tpu.distributed.fleet as fm
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    fm.fleet._hcg = None
    paddle.seed(seed)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=cfg['vocab_size'], hidden_size=cfg['hidden_size'],
        num_layers=cfg['num_layers'], num_heads=cfg['num_heads'],
        ffn_hidden_size=cfg['ffn_hidden_size'],
        max_seq_len=cfg['max_seq_len'], hidden_dropout=0.0,
        attn_dropout=0.0))
    for p in model.parameters():
        if p.data.dtype == jnp.float32:
            p.data = p.data.astype(cfg['dtype'])
    model.eval()
    return model


def logit_gaps(model, cfg, finished, width):
    """Teacher-forced: the reference's full forward over prompt + answer
    of each finished request (padded to `width`, which causal attention
    makes harmless), and for every emitted token its distance below the
    reference's argmax. Logits are compared, not tokens: with random
    weights the largest logit changes hands on rounding."""
    p = {n: t.data for n, t in model.named_parameters()}
    params = {'wte': p['gpt.embeddings.word_embeddings.weight'],
              'wpe': p['gpt.embeddings.position_embeddings.weight'],
              'lnf_w': p['gpt.final_norm.weight'],
              'lnf_b': p['gpt.final_norm.bias'], 'head': None}
    names = {'ln1_w': 'ln1.weight', 'ln1_b': 'ln1.bias',
             'qkv_w': 'attn.qkv_proj.weight', 'qkv_b': 'attn.qkv_proj.bias',
             'out_w': 'attn.out_proj.weight', 'out_b': 'attn.out_proj.bias',
             'ln2_w': 'ln2.weight', 'ln2_b': 'ln2.bias',
             'fc1_w': 'mlp.fc1.weight', 'fc1_b': 'mlp.fc1.bias',
             'fc2_w': 'mlp.fc2.weight', 'fc2_b': 'mlp.fc2.bias'}

    def layer(i):
        return {k: p[f'gpt.layers.{i}.{n}'] for k, n in names.items()}
    worst, exact, count = 0.0, 0, 0
    for req, _ in finished:
        out, n_prompt = req.output_ids(), len(req.prompt)
        ids = np.zeros((1, width), np.int32)
        ids[0, :len(out)] = out
        logits = np.asarray(reference.forward_logits(
            params, layer, cfg['num_layers'], ids, cfg['num_heads']))[0]
        if not np.isfinite(logits[:len(out)]).all():
            return float('inf'), 0.0, 0
        for pos in range(n_prompt, len(out)):
            row = logits[pos - 1]
            gap = float(row.max() - row[out[pos]])
            worst = max(worst, gap / float(row.max() - row.mean()))
            exact += gap == 0.0
            count += 1
    return worst, exact / max(count, 1), count


def run(ctx):
    import jax
    from paddle_tpu.serving import ServingConfig, ServingEngine
    from paddle_tpu.serving.scheduler import RequestState
    span = jax.profiler.TraceAnnotation
    cfg, mix = ctx.config, ctx.traffic
    model = build_model(cfg, ctx.weights_seed)
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

    try:
        # warm phase: compiles the two step shapes, fills the batch and
        # runs until the clients are spread over every phase of a request
        pool.fill()
        while pool.completed < mix['warm_completions']:
            step()
        ctx.mark('warm phase')
        checked = pool.finished[:mix['check_requests']]
        width = mix['prompt_tokens'][1] + mix['output_tokens'][1]
        worst, exact, count = logit_gaps(model, cfg, checked, width)
        ctx.mark('reference')
        log(f'{len(checked)} requests, {count} tokens vs the reference: '
            f'worst logit gap {worst:.4f} of scale (tolerance '
            f'{LOGIT_GAP_TOL}), {exact:.3f} exact')
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
        traced = 0
        t0 = time.perf_counter()
        if ctx.trace:
            with ctx.profile():
                for _ in range(mix['trace_steps']):
                    step()
            traced = len(step_ms)
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
        'requests_completed_total', 'prefix_hit_tokens_total')}
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
        f'window: {in_window}')
    return {
        'correct': bool(worst <= LOGIT_GAP_TOL and count > 0 and wrong == 0
                        and in_window == 0),
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
                  'compiles_in_window': in_window},
    }
