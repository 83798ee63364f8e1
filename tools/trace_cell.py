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
trace's window - busy; and, for a server, holds the program's
`serve::device_step` records to the device: each record's duration
beside the summed `device_duration_ps` of the `XLA Modules` executions
(`jit_step(...)`, one a dispatch) it covers, by kind of step — the
records are not in the xplane (a span recorded at its end cannot be
mirrored), so this is the one place the two are laid side by side;
and says, over the traced steps and over the window, the (query, key)
pairs the attention masks allowed beside those the kernel multiplied
(`attn_qk_pairs_total`, `attn_qk_pairs_dispatched_total`: a latent
chunk's live query tiles whole) — the share of its products that were
not padding, and with the chunk class's device time its share of the
peak on what it multiplies.
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


from benchmarks.layer_metrics import _device_steps  # noqa: E402

MODULES_LINE = 'XLA Modules'
# a step's kind, and which records are the device's own time, as the
# benchmark's readers have them
KINDS = (_device_steps.decode_only, _device_steps.one_chunk_dispatch,
         _device_steps.multi_dispatch)
# engine counters read at the ends of the traced steps and of the window:
# real and multiplied pairs, and the keys that tell the decode rows'
# pairs (one query a row: its keys) from the chunk rows'
PAIRS = ('attn_qk_pairs_total', 'attn_qk_pairs_dispatched_total',
         'attn_kv_tokens_read_total', 'attn_kv_tokens_read_chunks_total')
# a fetch returns this long, at most, after its execution ended (read:
# 1.6-2.6 ms, up to 10 where the host came late)
FETCH_LAG_NS = 5e6


def load_modules(xplane_dir):
    """{chip: [[start_ns, device_ns]]} of the device planes' `XLA
    Modules` line: one event per program the device executed, its
    duration the device's own (`device_duration_ps`)."""
    import warnings
    from benchmarks import trace_reduce as tr
    from jax.profiler import ProfileData
    chips = {}
    with warnings.catch_warnings():
        warnings.simplefilter('ignore')
        data = ProfileData.from_file(tr.find_xplane(xplane_dir))
        for plane in data.planes:
            device = tr.DEVICE_PLANE.match(plane.name)
            for line in plane.lines if device else ():
                if line.name == MODULES_LINE:
                    chips[int(device.group(1))] = sorted(
                        [float(e.start_ns),
                         dict(e.stats)['device_duration_ps'] * 1e-3]
                        for e in line.events)
    return chips


def clock_offset_ns(ring_starts, trace_starts):
    """What to add to a time on the ring's clock (`perf_counter_ns`) to
    get the trace's: the median difference between the starts of the
    same spans on both (the newest of each, should one side hold more)
    -> (offset, the widest disagreement with it)."""
    n = min(len(ring_starts), len(trace_starts))
    if not n:
        return None, None
    diffs = [t - r for r, t in zip(sorted(ring_starts)[-n:],
                                   sorted(trace_starts)[-n:])]
    offset = statistics.median(diffs)
    return offset, max(abs(d - offset) for d in diffs)


def hold_to_device(records, offset_ns, modules):
    """The program's `serve::device_step` records beside the device's
    own time. `records` [(args, start_ns, dur_ns)] on the ring's clock,
    oldest first, `modules` [[start_ns, device_ns]] on the trace's. A
    record covers the executions that END inside it (a fetch returns
    after its execution ended, and every execution outlasts that lag);
    records that reach outside what the trace holds (the step in flight
    when the session began, one that ended after it) are left out. By
    kind the medians are taken over the DEVICE-TRUE records — between
    two fetches that waited for the device (`late` 0, `behind` 1: the
    benchmark's readers take the same) —, the total over all. ->
    {'kinds': {kind: {records, device_true, program_ms, device_ms:
    medians, ratio: of the two medians, ratio_all: the same over every
    record of the kind}}, 'total': {records, late, program_ms,
    device_ms, ratio}, 'dispatch_mismatch': records whose `dispatches`
    is not the number of executions they cover, 'rows': per record
    [kind, dispatches, chunks, late, program_ms, device_ms, lag_ms —
    from the end of its last execution to the fetch's return]}."""
    if not records or not modules:
        return None
    lo, hi = modules[0][0], max(s + d for s, d in modules)
    rows, mismatch, before = [], 0, None
    for args, start, dur in records:
        start += offset_ns
        covered = [(s, d) for s, d in modules
                   if start < s + d <= start + dur]
        waited = _device_steps.waited(args)
        if start >= lo and start + dur <= hi + FETCH_LAG_NS and covered:
            mismatch += len(covered) != args['dispatches']
            rows.append({
                'args': args, 'true': waited and bool(before),
                'program_ms': dur * 1e-6,
                'device_ms': sum(d for _, d in covered) * 1e-6,
                'lag_ms': (start + dur - sum(covered[-1])) * 1e-6})
        before = waited
    if not rows:
        return None

    def ratio(picked, middle):
        return middle([r['program_ms'] for r in picked]) / middle(
            [r['device_ms'] for r in picked])
    out = {'kinds': {}, 'dispatch_mismatch': mismatch, 'rows': [], 'total': {
        'records': len(rows),
        'late': sum(bool(r['args'].get('late')) for r in rows),
        'program_ms': sum(r['program_ms'] for r in rows),
        'device_ms': sum(r['device_ms'] for r in rows),
        'ratio': ratio(rows, sum)}}
    for test in KINDS:
        kind = test.__name__
        picked = [r for r in rows if test(r['args'])]
        true = [r for r in picked if r['true']]
        if true:
            out['kinds'][kind] = {
                'records': len(picked), 'device_true': len(true),
                'program_ms': statistics.median(
                    r['program_ms'] for r in true),
                'device_ms': statistics.median(r['device_ms'] for r in true),
                'ratio': ratio(true, statistics.median),
                'ratio_all': ratio(picked, statistics.median)}
        out['rows'] += [[kind, r['args']['dispatches'], r['args']['chunks'],
                         int(bool(r['args'].get('late'))),
                         round(r['program_ms'], 4), round(r['device_ms'], 4),
                         round(r['lag_ms'], 4)] for r in picked]
    return out


def render_device_steps(table, spread_ns):
    total = table['total']
    out = ['', 'serve::device_step against the device (XLA Modules, '
           f'device_duration_ps); the two clocks agree to '
           f'{spread_ns * 1e-3:.0f} us over the traced serve::step spans',
           f"{'step kind':<20} {'records':>7} {'dev-true':>8} "
           f"{'program_ms':>11} {'device_ms':>10} {'program/device':>15} "
           f"{'(all records)':>14}"]
    for kind, row in table['kinds'].items():
        out.append(f"{kind:<20} {row['records']:>7} {row['device_true']:>8} "
                   f"{row['program_ms']:>11.3f} {row['device_ms']:>10.3f} "
                   f"{row['ratio']:>15.4f} {row['ratio_all']:>14.4f}")
    out.append(f"{'window total':<20} {total['records']:>7} {'':>8} "
               f"{total['program_ms']:>11.3f} {total['device_ms']:>10.3f} "
               f"{total['ratio']:>15.4f}")
    out.append(f"(by kind: medians over the device-true records — between "
               f"two fetches that waited; {total['late']} of "
               f"{total['records']} fetches came late; records whose "
               f"`dispatches` is not the executions they cover: "
               f"{table['dispatch_mismatch']})")
    return '\n'.join(out)


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
            marks['pairs_lo'] = pairs()
            self.session = jax.profiler.trace(xplane_dir)
            return self.session.__enter__()

        def __exit__(self, *exc):
            out = self.session.__exit__(*exc)
            marks['hi'] = prof.mark()
            marks['pairs_hi'] = pairs()
            return out

    ctx = common.Context(
        config, traffic, args.seed, args.seconds, 1, chips=cell['chips'],
        t_start=T_START, device_kind=jax.devices()[0].device_kind,
        profile=traced)
    # a server runner shuts its engine down, and the serving ledger
    # with it: keep the ledger's roofline block (kv_read_tokens_mean,
    # paged_live_page_share) as it stood at shutdown
    from paddle_tpu.serving import engine as serving_engine
    rooflines, mixed, engines = [], [], []
    shutdown = serving_engine.ServingEngine.shutdown
    build = serving_engine.ServingEngine.__init__

    def build_noted(self, *a, **kw):
        engines.append(self)
        build(self, *a, **kw)
    serving_engine.ServingEngine.__init__ = build_noted

    def pairs():
        """The runner's engine's pair counters as they stand (None for
        a trainer, or a program without them)."""
        st = engines[-1].stats() if engines else {}
        return {k: st[k] for k in PAIRS} if all(k in st for k in PAIRS) \
            else None
    setup_done = ctx.setup_done

    def setup_done_noting_pairs():
        setup_done()
        marks['pairs_window'] = pairs()
    ctx.setup_done = setup_done_noting_pairs

    def shutdown_keeping_roofline(self, *a, **kw):
        rooflines.append(self.ledger.roofline())
        marks['pairs_end'] = pairs()
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
    chip_ops, trace_spans = trace_summary.load_device_trace(xplane_dir)
    summary = trace_summary.summarize_device_trace(chip_ops, trace_spans,
                                                   top=args.top)
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
    modules = load_modules(xplane_dir) if facts['kind'] == 'serve' else {}
    if steps and modules:
        offset, spread = clock_offset_ns(
            [s.start_ns for s in steps],
            [start for name, start, _ in trace_spans if name == step_name])
        table = offset is not None and hold_to_device(
            [(s.args, s.start_ns, s.dur_ns) for s in ring
             if s.name == 'serve::device_step'], offset,
            modules[min(modules)])
        if table:
            table['clock_spread_ns'] = spread
            summary['device_steps'] = table
            text += '\n' + render_device_steps(table, spread)
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
    for what, lo, hi in (('traced steps', 'pairs_lo', 'pairs_hi'),
                         ('window', 'pairs_window', 'pairs_end')):
        if marks.get(lo) and marks.get(hi):
            d = {k: marks[hi][k] - marks[lo][k] for k in PAIRS}
            decode = d['attn_kv_tokens_read_total'] \
                - d['attn_kv_tokens_read_chunks_total']
            d['chunk_pairs'] = d['attn_qk_pairs_total'] - decode
            d['chunk_pairs_dispatched'] = \
                d['attn_qk_pairs_dispatched_total'] - decode
            share = d['chunk_pairs'] / max(d['chunk_pairs_dispatched'], 1)
            summary.setdefault('pairs', {})[what] = d
            text += (f'\n(query, key) pairs over the {what}: real '
                     f'{d["attn_qk_pairs_total"]}, multiplied '
                     f'{d["attn_qk_pairs_dispatched_total"]}; of chunk '
                     f'rows {d["chunk_pairs"]} of '
                     f'{d["chunk_pairs_dispatched"]} = {share:.4f}')
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
