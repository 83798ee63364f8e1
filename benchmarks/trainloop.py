"""The measured window of a trainer cell, shared by the training
runners: warm the windowed path, open the window, count whole optimizer
steps, and report. A runner builds its engine and says how one batch is
dispatched; everything timed is here.

The loop is the one users run (`bench.py:_host_gap_record`): batches
prefetched to the device by DeviceLoader, `train_step` dispatched
without a fetch, the dispatch window draining step i-k, one `flush()` at
the end. The window opens after a flush (the device is idle, so the
first counted step starts at t0) and closes after the flush that waits,
with block_until_ready, on the last step's loss.
"""
import math
import statistics
import time

from benchmarks import flops
from benchmarks.common import log, quartiles


def check_first_loss(got, ref, tol, why):
    rel = abs(got - ref) / abs(ref)
    log(f'first-step loss {got:.5f} vs reference {ref:.5f}: rel {rel:.2e} '
        f'(tolerance {tol:g}: {why})')
    return bool(math.isfinite(got) and rel <= tol)


def measure(ctx, eng, dispatch, batches, tokens_per_step, correct):
    import jax
    from paddle_tpu.io import DeviceLoader
    span = jax.profiler.TraceAnnotation
    job = ctx.traffic

    def cycle():
        while True:
            yield from batches
    loader = DeviceLoader(cycle(), engine=eng)
    feed = iter(loader)
    depth = eng._inflight.size
    try:
        # warm-up on the windowed path: same shapes, same programs
        t = time.perf_counter()
        for _ in range(job['warm_steps']):
            dispatch(next(feed))
        eng.flush()
        step_est = (time.perf_counter() - t) / job['warm_steps']
        ctx.mark('warm steps')

        ctx.setup_done()
        results, returns = [], []
        traced = 0
        t0 = time.perf_counter()
        if ctx.trace:
            with ctx.profile():
                for _ in range(job['trace_steps']):
                    with span('bench::train.dispatch'):
                        results.append(dispatch(next(feed)))
                with span('bench::train.flush'):
                    eng.flush()
            traced = len(results)
            log(f'traced {traced} steps in {time.perf_counter() - t0:.3f} s '
                f'(profiler start and stop included)')
        # stop so that the steps in flight end at about --seconds
        while time.perf_counter() - t0 < ctx.seconds - depth * step_est:
            results.append(dispatch(next(feed)))
            returns.append(time.perf_counter())
        eng.flush()
        elapsed = time.perf_counter() - t0
    finally:
        loader.close()
    in_window = ctx.compiles_in_window()
    losses = [r.result() for r in results]
    steps = len(losses)
    bad = sum(not math.isfinite(x) for x in losses)
    # a dispatch returns when step i-depth has ended: with the window
    # full, the gaps between returns are the steps' own times
    gaps = [(b - a) * 1e3 for a, b in zip(returns, returns[1:])][depth:]
    rate = steps * tokens_per_step / elapsed
    log(f'{steps} steps, {steps * tokens_per_step} tokens in {elapsed:.3f} s'
        f' = {rate:.1f} tokens/s; between dispatch returns (window of '
        f'{depth} full, {len(gaps)} gaps): median '
        f'{statistics.median(gaps) if gaps else float("nan"):.2f} ms, '
        f'quartiles {quartiles(gaps)}')
    if ctx.device_kind is not None:
        per_token = flops.train_flops_per_token(ctx.config, job['seq_len'])
        log(f'MFU on required FLOPs '
            f'{flops.mfu(ctx.config, job["seq_len"], rate, ctx.device_kind, ctx.chips):.4f}'
            f' ({per_token / 1e9:.4f} GFLOP a token: causal attention '
            f'once, recompute excluded)')
    log(f'losses first {losses[0]:.4f} last {losses[-1]:.4f}; non-finite '
        f'{bad}; compiles inside the window: {in_window}; loader '
        f'{loader.stats()}')
    return {
        'correct': bool(correct and bad == 0 and in_window == 0),
        'attempted': steps, 'failed': bad,
        'end_to_end': {'train_tokens_per_s': rate, 'setup_s': ctx.setup_s},
        'facts': {'kind': 'train', 'steps': steps, 'traced_steps': traced,
                  'step_gaps_ms': gaps, 'tokens_per_step': tokens_per_step,
                  'compile_s': ctx.compile_s,
                  'compiles_in_window': in_window},
    }
