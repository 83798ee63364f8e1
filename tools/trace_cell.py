#!/usr/bin/env python
"""trace_cell — one traced run of a benchmark cell with the xplane KEPT,
and the table `tools/trace_summary.py --xplane` makes of it: idle gaps
by the program's own spans (`serve::admit`, `serve::prepare`, ...),
device time by kernel.

`benchmarks/run.py` deletes the xplane once `trace_reduce` has read it,
and `trace_reduce` keeps only `bench::` host spans, so the ledger's
`idle_gaps` stop at the benchmark's wrapper around `engine.step()`. This
drives the same runner through the same `Context`, with `profile`
pointed at a directory of its own; the benchmark's files are not
touched. It needs the chip, like run.py:

    chiprun -- python tools/trace_cell.py --workload gpt3-1.3b.chat-closed64 \\
        --seed 7 --seconds 30 --out chiprun_out/trace_cell

Writes <out>/<cell>.summary.json and .txt (and leaves the xplane under
<out>/xplane/ unless --drop-xplane), and cross-checks the two clocks:
the span ring's host time per step x traced steps against the device
trace's window - busy.
"""
import argparse
import json
import os
import shutil
import statistics
import sys
import time

T_START = time.time()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, 'tools'))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, default=1)
    ap.add_argument('--seconds', type=float, default=30.0)
    ap.add_argument('--out', default=os.path.join(ROOT, 'chiprun_out',
                                                  'trace_cell'))
    ap.add_argument('--top', type=int, default=16)
    ap.add_argument('--drop-xplane', action='store_true')
    args = ap.parse_args(argv)

    from benchmarks import common
    import trace_summary
    manifest = common.Manifest()
    cell = manifest.cell(args.workload)
    config, traffic = manifest.config(cell), manifest.traffic(cell)
    runner = manifest.load_module('runners',
                                  config['runners'][traffic['kind']])
    import jax
    if jax.default_backend() != 'tpu':
        sys.exit('trace_cell: no accelerator: a CPU profile has no '
                 'device plane')
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)
    import paddle_tpu.profiler as prof

    xplane_dir = os.path.join(args.out, 'xplane', cell['name'])
    shutil.rmtree(xplane_dir, ignore_errors=True)
    os.makedirs(xplane_dir)
    marks = {}

    class traced:
        """ctx.profile(): the jax.profiler session, with the span
        ring's ids at its two ends."""
        def __enter__(self):
            marks['lo'] = prof.mark()
            self.session = jax.profiler.trace(xplane_dir)
            return self.session.__enter__()

        def __exit__(self, *exc):
            out = self.session.__exit__(*exc)
            marks['hi'] = prof.mark()
            return out

    ctx = common.Context(
        config, traffic, args.seed, args.seconds, 1, chips=cell['chips'],
        t_start=T_START, device_kind=jax.devices()[0].device_kind,
        profile=traced)
    # a server runner shuts its engine down, and the serving ledger
    # with it: keep the ledger's roofline block (kv_read_tokens_mean,
    # paged_live_page_share) as it stood at shutdown
    from paddle_tpu.serving import engine as serving_engine
    rooflines, mixed = [], []
    shutdown = serving_engine.ServingEngine.shutdown

    def shutdown_keeping_roofline(self, *a, **kw):
        rooflines.append(self.ledger.roofline())
        st = self.stats()
        mixed.append(dict(
            {k: st[k] for k in ('dispatches_per_step',
                                'prefill_rows_per_dispatch',
                                'padded_prefill_token_share',
                                # one step ahead: of `steps`, those
                                # launched behind another, the fetches
                                # with nothing behind them, rows dropped
                                'pipelined_steps_total',
                                'pipeline_drains_total',
                                'overrun_tokens_total',
                                'preemptions_total')},
            steps=round(st['dispatches_total']
                        / max(st['dispatches_per_step'], 1e-9)),
            prefill_rows=self._prefill_rows))
        return shutdown(self, *a, **kw)
    serving_engine.ServingEngine.shutdown = shutdown_keeping_roofline
    record = runner.run(ctx)
    facts = record['facts']
    summary = trace_summary.summarize_device_trace(
        *trace_summary.load_device_trace(xplane_dir), top=args.top)
    text = trace_summary.render_device_trace(summary)

    # the two clocks: what the ring says the host did in the traced
    # steps, against what the device trace says the device did not do
    step_name = 'serve::step' if facts['kind'] == 'serve' \
        else 'train::dispatch'
    waits = {'serve::compiled_step', 'serve::sample_fetch',
             'train::window_wait'}
    ring = [s for s in prof.spans(since_id=marks['lo'])
            if s.id < marks['hi']]
    steps = [s for s in ring if s.name == step_name]
    if steps and summary['chips']:
        from benchmarks.layer_metrics import _program_spans
        waited = _program_spans.inside(ring, steps, waits)
        host_ms = [(s.dur_ns - waited[s.id]) * 1e-6 for s in steps]
        chip = summary['chips'][min(summary['chips'])]
        check = {
            'steps_in_ring': len(steps),
            'traced_steps': facts['traced_steps'],
            'host_ms_per_step_median': statistics.median(host_ms),
            'host_ms_sum': sum(host_ms),
            'step_span_ms_sum': sum(s.dur_ns for s in steps) * 1e-6,
            'device_window_ms': chip['window_s'] * 1e3,
            'device_idle_ms': chip['idle_s'] * 1e3,
            'ring_overwritten': prof.overwritten_spans()}
        summary['clock_cross_check'] = check
        text += ('\n\ntwo clocks: ring host time (step - device waits) '
                 f'sum {check["host_ms_sum"]:.1f} ms over '
                 f'{len(steps)} {step_name} spans (median '
                 f'{check["host_ms_per_step_median"]:.3f} ms); device '
                 f'trace idle {check["device_idle_ms"]:.1f} ms of a '
                 f'{check["device_window_ms"]:.1f} ms window; the step '
                 f'spans sum to {check["step_span_ms_sum"]:.1f} ms')
    summary['record'] = {k: record[k] for k in ('correct', 'attempted',
                                                'failed', 'end_to_end')}
    if rooflines and rooflines[-1]:
        summary['serve_ledger_roofline'] = rooflines[-1]
        text += '\n\nserving ledger at shutdown: ' + ', '.join(
            f'{k} {rooflines[-1][k]}' for k in (
                'kv_read_tokens_mean', 'kv_bytes_per_token',
                'paged_live_pages', 'paged_page_slots',
                'paged_live_page_share', 'kv_read_tokens_window',
                'kv_read_tokens_full', 'moe_load_max_over_mean')
            if k in rooflines[-1])
    if mixed:
        # how the mixed step and the pipe engaged, warm phase and window
        # together
        summary['mixed_step'] = mixed[-1]
        text += '\n\nmixed step and pipe at shutdown: ' + ', '.join(
            f'{k} {v}' for k, v in mixed[-1].items())
    print(text, flush=True)
    base = os.path.join(args.out, cell['name'])
    with open(base + '.summary.json', 'w') as f:
        json.dump(summary, f)
    with open(base + '.summary.txt', 'w') as f:
        f.write(text + '\n')
    if args.drop_xplane:
        shutil.rmtree(os.path.join(args.out, 'xplane'), ignore_errors=True)
    print(f'trace_cell: wrote {base}.summary.json', flush=True)


if __name__ == '__main__':
    main()
