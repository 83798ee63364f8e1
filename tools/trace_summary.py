#!/usr/bin/env python
"""trace_summary — summarize a paddle_tpu.profiler exported trace.

Reads either exporter format (chrome-trace `traceEvents` or the raw
`spans` JSON) and prints:

  * the top-N spans by total duration (calls, total ms, avg us, share);
  * a compile-vs-execute breakdown from span categories (compile =
    trace/lower/XLA-compile spans; execute = executor/jit dispatches;
    plus dataloader / collective / serve / other buckets).

It also reads SERVING request traces (the JSON-lines files
`ServingEngine.export_trace` writes, schema paddle_tpu.serve_trace/1
through /4) and prints the per-request SLO table: queue-wait, TTFT,
TPOT, e2e, preemptions, pages high-water, delivered/wasted tokens —
plus cross-request percentiles and the goodput aggregate (ISSUE 17).
Serve traces are detected by their schema header (content sniff, not
file extension); `--serve` forces that mode.

Several serve-trace files MERGE into one cross-replica table (ISSUE
11): pass each replica's export and requests render prefixed with
their replica id (the v2 `route` events name it; older files fall
back to the file stem), with SLO percentiles over the whole cluster:

    python tools/trace_summary.py --serve r0.jsonl r1.jsonl

With `--xplane DIR` it reads a jax.profiler trace directory instead
(the `.xplane.pb` under it): the device's busy union on the `XLA Ops`
line, every idle gap charged to the innermost PROGRAM span (any
`prefix::name` the span ring mirrored into the host plane, PR 25) that
holds the gap's midpoint, the host spans' own totals, and device time by
kernel — the table `benchmarks/trace_reduce.py` cannot give while it
keeps only `bench::` spans.

Usage:
    python tools/trace_summary.py TRACE.json [--top 15] [--json]
    python tools/trace_summary.py SERVE_TRACE.jsonl [...] [--json]
    python tools/trace_summary.py --xplane TRACE_DIR [--top 15] [--json]
    python tools/trace_summary.py --selftest    # CI smoke: generate a
                                                # tiny trace, summarize it
"""
import argparse
import json
import os
import re
import sys


CATEGORY_BUCKETS = {
    'compile': 'compile',
    'executor': 'execute',
    'jit': 'execute',
    'train': 'execute',
    'optimizer': 'execute',
    'dataloader': 'dataloader',
    'collective': 'collective',
    'serve': 'serve',
    'serve_request': 'serve',
}


def load_spans(path):
    """Normalize either export format to [{name, cat, dur, ts}]."""
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, dict) and 'spans' in doc:
        return [s for s in doc['spans'] if 'dur' in s]
    events = doc.get('traceEvents', doc) if isinstance(doc, dict) else doc
    return [{'name': e.get('name', '?'), 'cat': e.get('cat', ''),
             'dur': e.get('dur', 0), 'ts': e.get('ts', 0)}
            for e in events if e.get('ph') == 'X']


def summarize(spans, top=15):
    agg, buckets = {}, {}
    total = 0
    for s in spans:
        dur = int(s.get('dur') or 0)
        total += dur
        a = agg.setdefault(s['name'], {'calls': 0, 'total_us': 0})
        a['calls'] += 1
        a['total_us'] += dur
        bucket = CATEGORY_BUCKETS.get(s.get('cat') or '', 'other')
        buckets[bucket] = buckets.get(bucket, 0) + dur
    rows = sorted(agg.items(), key=lambda kv: -kv[1]['total_us'])[:top]
    return {
        'span_count': len(spans),
        'total_us': total,
        'top_spans': [
            {'name': n, 'calls': a['calls'], 'total_us': a['total_us'],
             'avg_us': a['total_us'] / a['calls'],
             'share': (a['total_us'] / total) if total else 0.0}
            for n, a in rows],
        'buckets_us': dict(sorted(buckets.items(),
                                  key=lambda kv: -kv[1])),
    }


def render(summary):
    out = []
    total = summary['total_us']
    out.append(f"spans: {summary['span_count']}   "
               f"total: {total / 1000.0:.3f} ms")
    out.append('')
    out.append('-- compile vs execute ' + '-' * 38)
    for bucket, us in summary['buckets_us'].items():
        share = (us / total * 100) if total else 0.0
        out.append(f'{bucket:<12} {us / 1000.0:>12.3f} ms  {share:5.1f}%')
    out.append('')
    out.append('-- top spans ' + '-' * 47)
    out.append(f"{'name':<36} {'calls':>6} {'total_ms':>10} "
               f"{'avg_us':>9} {'share':>6}")
    for r in summary['top_spans']:
        out.append(f"{r['name'][:36]:<36} {r['calls']:>6} "
                   f"{r['total_us'] / 1000.0:>10.3f} "
                   f"{r['avg_us']:>9.1f} {r['share'] * 100:>5.1f}%")
    return '\n'.join(out)


# ---------------------------------------------------------------------------
# serving request traces (JSON-lines, paddle_tpu.serve_trace/1 – /6)
# ---------------------------------------------------------------------------
def summarize_serve(paths):
    """Per-request table + cross-request SLO percentiles from one or
    several serve-trace JSON-lines files. Multiple files are merged
    into one cross-replica table: request ids prefix with the replica
    (route-event replica_id, else the file stem — per-replica files
    restart ids at 0, so the prefix IS the disambiguator), and the
    percentiles aggregate the whole cluster's requests. Schema-v3
    traces (ISSUE 15) additionally group the percentile table BY
    TENANT (`percentiles_by_tenant`) — the per-tenant SLO view the
    multi-tenant scheduler is judged on. Schema-v4 traces (ISSUE 17)
    price each request's delivered vs wasted tokens (preempt-destroyed
    prefill recompute + rejected/discarded spec drafts); the `goodput`
    aggregate sums them across the table. v1-v3 merges are unchanged —
    their recompute/discard fields reconstruct as zeros."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from paddle_tpu.serving.request_trace import (load_trace,
                                                  percentile_of,
                                                  reconstruct)
    if isinstance(paths, str):
        paths = [paths]
    multi = len(paths) > 1
    rows, dropped, schema = [], 0, None
    for i, path in enumerate(paths):
        header, events = load_trace(path)
        schema = schema or header.get('schema')
        dropped += header.get('dropped_events', 0)
        fallback = os.path.splitext(os.path.basename(path))[0]
        for r in sorted(reconstruct(events).values(),
                        key=lambda r: r['req']):
            if multi and r.get('replica_id') is None:
                r['replica_id'] = fallback
            if multi:
                r['req'] = f"{r['replica_id']}:{r['req']}"
            rows.append(r)
    pct = {}
    for key in ('queue_wait_s', 'ttft_s', 'tpot_s', 'e2e_s'):
        vals = [r[key] for r in rows]
        pct[key] = {f'p{q}': percentile_of(vals, q) for q in (50, 90, 99)}
    by_tenant = {}
    if any(r.get('tenant_id') is not None for r in rows):
        tenants = sorted({r.get('tenant_id') or '-' for r in rows})
        for tid in tenants:
            trows = [r for r in rows
                     if (r.get('tenant_id') or '-') == tid]
            by_tenant[tid] = {
                'requests': len(trows),
                # cluster-wide tenant visibility (ISSUE 18): how many
                # replicas this tenant's requests landed on — each one
                # holds a SEPARATE quota bucket, so replicas > 1 means
                # the tenant's effective quota is multiplied until the
                # ROADMAP quota-sharing fix ships
                'replicas': len({r.get('replica_id') or '-'
                                 for r in trows}),
                'quota_defers': sum(r.get('quota_defers', 0)
                                    for r in trows),
                'deadline_misses': sum(1 for r in trows
                                       if r.get('deadline_miss')),
            }
            for key in ('queue_wait_s', 'e2e_s'):
                vals = [r[key] for r in trows]
                by_tenant[tid][key] = {
                    f'p{q}': percentile_of(vals, q)
                    for q in (50, 90, 99)}
    # cross-request goodput aggregate (schema v4, ISSUE 17): totals of
    # the per-request delivered/wasted pricing — emitted is their sum
    # by construction, mirroring the engine ledger identity
    delivered = sum(r.get('delivered_tokens', 0) for r in rows)
    wasted = sum(r.get('wasted_tokens', 0) for r in rows)
    goodput = {
        'delivered_tokens': delivered,
        'wasted_tokens': wasted,
        'emitted_tokens': delivered + wasted,
        'recompute_tokens': sum(r.get('recompute_tokens', 0)
                                for r in rows),
        'goodput_fraction': (delivered / (delivered + wasted)
                             if delivered + wasted else None),
    }
    return {'schema': schema, 'files': len(paths),
            'dropped_events': dropped,
            'requests': rows, 'percentiles': pct,
            'percentiles_by_tenant': by_tenant,
            'goodput': goodput}


def _fmt_ms(v):
    return f'{v * 1000.0:.2f}' if v is not None else '-'


def render_serve(s):
    rows = s['requests']
    out = [f"serve trace: {len(rows)} requests"
           + (f" across {s['files']} replica files"
              if s.get('files', 1) > 1 else '')
           + (f"   ({s['dropped_events']} events dropped at cap)"
              if s.get('dropped_events') else '')]
    out.append('')
    # cluster columns only when any request was router-placed
    # (schema v2 route events / merged per-replica files)
    routed = any(r.get('replica_id') is not None for r in rows)
    tenanted = any(r.get('tenant_id') is not None for r in rows)
    # host-tier resurrects (schema v6, ISSUE 20): the column renders
    # only when some request resurrected, so v1-v5 tables are
    # byte-identical to before
    tiered = any(r.get('resurrected_tokens', 0) for r in rows)
    extra_hdr = (f" {'resurr':>6}" if tiered else '') \
        + (f" {'tenant':>8} {'prio':>4}" if tenanted else '') \
        + (f" {'replica':>8} {'routed':>12}" if routed else '')
    out.append(f"{'req':>8} {'state':<9} {'prompt':>6} {'gen':>5} "
               f"{'queue_ms':>9} {'ttft_ms':>9} {'tpot_ms':>9} "
               f"{'e2e_ms':>9} {'preempt':>7} {'pages_hw':>8} "
               f"{'cached':>6} {'spec':>9} "
               f"{'deliv':>6} {'wasted':>6}" + extra_hdr)
    for r in rows:
        prop = r.get('spec_proposed', 0)
        spec = (f"{r.get('spec_accepted', 0)}/{prop}" if prop else '-')
        extra = (f" {r.get('resurrected_tokens', 0):>6}"
                 if tiered else '') \
            + (f" {str(r.get('tenant_id') or '-'):>8} "
               f"{r.get('priority', 0):>4}" if tenanted else '') \
            + (f" {str(r.get('replica_id') or '-'):>8} "
               f"{str(r.get('router_decision') or '-'):>12}"
               if routed else '')
        out.append(
            f"{r['req']:>8} {r['state'] or '?':<9} "
            f"{r['prompt_tokens'] if r['prompt_tokens'] is not None else '?':>6} "
            f"{r['tokens_generated']:>5} "
            f"{_fmt_ms(r['queue_wait_s']):>9} {_fmt_ms(r['ttft_s']):>9} "
            f"{_fmt_ms(r['tpot_s']):>9} {_fmt_ms(r['e2e_s']):>9} "
            f"{r['preemptions']:>7} {r['pages_high_water']:>8} "
            f"{r.get('prefix_cached_tokens', 0):>6} {spec:>9} "
            f"{r.get('delivered_tokens', 0):>6} "
            f"{r.get('wasted_tokens', 0):>6}" + extra)
    # cross-request prefix/spec aggregates (ISSUE 9): prompt tokens
    # served from cache, and draft-token acceptance over the stream
    cached = sum(r.get('prefix_cached_tokens', 0) for r in rows)
    prompt = sum(r['prompt_tokens'] or 0 for r in rows)
    prop = sum(r.get('spec_proposed', 0) for r in rows)
    acc = sum(r.get('spec_accepted', 0) for r in rows)
    if cached:
        out.append('')
        out.append(f"prefix cache: {cached}/{prompt} prompt tokens "
                   f"served from cache "
                   f"({100.0 * cached / max(prompt, 1):.1f}% hit-rate)")
    if prop:
        if not cached:
            out.append('')
        out.append(f"speculative decode: {acc}/{prop} draft tokens "
                   f"accepted ({100.0 * acc / prop:.1f}% acceptance)")
    # host-tier resurrect aggregate (schema v6, ISSUE 20): prompt
    # tokens restored from spilled host pages instead of re-prefilled
    res_tok = sum(r.get('resurrected_tokens', 0) for r in rows)
    res_pages = sum(r.get('resurrected_pages', 0) for r in rows)
    if res_tok:
        if not cached and not prop:
            out.append('')
        out.append(f"host tier: {res_tok}/{prompt} prompt tokens "
                   f"resurrected from spilled pages "
                   f"({res_pages} pages fetched)")
    # goodput aggregate (schema v4, ISSUE 17) — only rendered once any
    # request priced waste, so v1-v3 tables look exactly as before
    gp = s.get('goodput') or {}
    if gp.get('wasted_tokens'):
        out.append('')
        out.append(
            f"goodput: {gp['delivered_tokens']}/{gp['emitted_tokens']} "
            f"tokens delivered "
            f"({100.0 * gp['goodput_fraction']:.1f}%), "
            f"{gp['wasted_tokens']} wasted "
            f"({gp['recompute_tokens']} preempt-recompute)")
    out.append('')
    out.append('-- SLO percentiles (ms) ' + '-' * 36)
    for key, label in (('queue_wait_s', 'queue wait'),
                       ('ttft_s', 'ttft'), ('tpot_s', 'tpot'),
                       ('e2e_s', 'e2e')):
        p = s['percentiles'][key]
        out.append(f"{label:<12} p50 {_fmt_ms(p['p50']):>9}  "
                   f"p90 {_fmt_ms(p['p90']):>9}  "
                   f"p99 {_fmt_ms(p['p99']):>9}")
    # per-tenant SLO grouping (schema v3, ISSUE 15)
    by_tenant = s.get('percentiles_by_tenant') or {}
    if by_tenant:
        out.append('')
        out.append('-- SLO percentiles by tenant (ms) ' + '-' * 26)
        out.append(f"{'tenant':<12} {'n':>4} {'reps':>4} "
                   f"{'defer':>5} {'dl-miss':>7} "
                   f"{'qwait p50':>10} {'qwait p99':>10} "
                   f"{'e2e p50':>9} {'e2e p99':>9}")
        for tid, row in sorted(by_tenant.items()):
            qw, e2e = row['queue_wait_s'], row['e2e_s']
            out.append(
                f"{tid[:12]:<12} {row['requests']:>4} "
                f"{row.get('replicas', 1):>4} "
                f"{row['quota_defers']:>5} "
                f"{row['deadline_misses']:>7} "
                f"{_fmt_ms(qw['p50']):>10} {_fmt_ms(qw['p99']):>10} "
                f"{_fmt_ms(e2e['p50']):>9} {_fmt_ms(e2e['p99']):>9}")
        reps = {row.get('replicas', 1) for row in by_tenant.values()}
        if max(reps, default=1) > 1:
            out.append('note: reps > 1 — each replica holds a '
                       'separate quota bucket for that tenant '
                       '(effective quota multiplies until cluster '
                       'quota sharing ships)')
    return '\n'.join(out)


def _looks_like_serve_trace(path):
    # content sniff, NOT extension: fleet workerlogs are .jsonl too and
    # must not render as an empty "serve trace: 0 requests" table
    try:
        with open(path) as f:
            first = f.readline().strip()
        doc = json.loads(first)
        return isinstance(doc, dict) and (
            doc.get('schema', '').startswith('paddle_tpu.serve_trace')
            or ('event' in doc and 'req' in doc))
    except (OSError, ValueError):
        return False


def _serve_selftest():
    """Drive a deterministic-clock tracer through a preempt/resume
    lifecycle, export, summarize, assert the derived SLOs."""
    import tempfile
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from paddle_tpu.serving.request_trace import RequestTracer

    t = [0.0]

    def clock():
        t[0] += 0.001
        return t[0]

    tr = RequestTracer(clock=clock)
    tr.record(7, 'submit', t=1.0, prompt_tokens=5, max_new_tokens=4)
    tr.record(7, 'admit', t=1.5, slot=0)
    tr.record(7, 'prefix_hit', t=1.55, cached_tokens=4, pages=1)
    tr.record(7, 'prefill_chunk', t=1.6, tokens=5, prefilled=5, pages=1)
    tr.record(7, 'first_token', t=2.0, tokens_generated=1, pages=1)
    tr.record(7, 'preempt', t=2.1, pages_released=1,
              tokens_generated=1)
    tr.record(7, 'resume', t=2.5, slot=1)
    # v4 (ISSUE 17): the resume chunk re-derives the 5 positions the
    # preemption destroyed; the verify burst drops one accepted token
    # past eos — both priced as waste
    tr.record(7, 'prefill_chunk', t=2.6, tokens=6, prefilled=6, pages=2,
              recompute_tokens=5)
    for i, td in enumerate((2.8, 3.0)):
        tr.record(7, 'decode', t=td, tokens_generated=2 + i, pages=2)
    tr.record(7, 'spec_verify', t=3.1, proposed=3, accepted=1,
              discarded=1)
    tr.record(7, 'decode', t=3.2, tokens_generated=4, pages=2)
    tr.record(7, 'retire', t=3.2, tokens_generated=4, preemptions=1)
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, 'serve.jsonl')
        tr.export_jsonl(p)
        assert _looks_like_serve_trace(p)
        s = summarize_serve(p)
    (r,) = s['requests']
    assert r['queue_wait_s'] == 0.5 and r['ttft_s'] == 1.0, r
    assert r['preemptions'] == 1 and r['tokens_generated'] == 4, r
    assert abs(r['tpot_s'] - (3.2 - 2.0) / 3) < 1e-12, r
    assert r['e2e_s'] == 2.2 and r['pages_high_water'] == 2, r
    assert r['prefix_cached_tokens'] == 4, r
    assert r['spec_proposed'] == 3 and r['spec_accepted'] == 1, r
    # v4 goodput pricing: delivered = (11 computed - 5 recompute)
    # prefill + 3 decode (4 generated, first rides the prefill column);
    # wasted = 5 recompute + 2 rejected drafts + 1 discarded
    assert r['delivered_tokens'] == 9 and r['wasted_tokens'] == 8, r
    gp = s['goodput']
    assert gp['delivered_tokens'] + gp['wasted_tokens'] \
        == gp['emitted_tokens'] == 17, gp
    assert gp['recompute_tokens'] == 5, gp
    assert abs(s['percentiles']['ttft_s']['p50'] - 1.0) < 1e-12
    text = render_serve(s)
    assert 'prefix cache: 4/5' in text, text
    assert 'speculative decode: 1/3' in text, text
    assert 'goodput: 9/17 tokens delivered' in text, text
    assert 'deliv' in text and 'wasted' in text, text
    print(text)

    # cross-replica merge (ISSUE 11): two per-replica exports with v2
    # route events fold into one table, req ids replica-prefixed
    tr2 = RequestTracer(clock=clock)
    for rid, replica, decision in ((0, 'r0', 'affinity'),
                                   (0, 'r1', 'least_loaded')):
        t_ = tr2 if replica == 'r1' else RequestTracer(clock=clock)
        if replica == 'r0':
            tr0 = t_
        t_.record(rid, 'submit', t=1.0, prompt_tokens=3)
        t_.record(rid, 'route', t=1.01, replica_id=replica,
                  router_decision=decision)
        t_.record(rid, 'admit', t=1.2)
        t_.record(rid, 'first_token', t=1.5, tokens_generated=1)
        t_.record(rid, 'retire', t=1.8, tokens_generated=2)
    with tempfile.TemporaryDirectory() as d:
        p0 = os.path.join(d, 'r0.jsonl')
        p1 = os.path.join(d, 'r1.jsonl')
        tr0.export_jsonl(p0)
        tr2.export_jsonl(p1)
        m = summarize_serve([p0, p1])
    assert m['files'] == 2 and len(m['requests']) == 2, m
    assert {r['req'] for r in m['requests']} == {'r0:0', 'r1:0'}, m
    assert {r['router_decision'] for r in m['requests']} == \
        {'affinity', 'least_loaded'}, m
    mtext = render_serve(m)
    assert 'replica' in mtext and 'r0' in mtext and 'r1' in mtext, mtext
    print(mtext)

    # tenant grouping (schema v3, ISSUE 15): tenant columns on the
    # per-request table, percentile block grouped by tenant, engine-
    # scope degrade_stage events skipped by reconstruction
    tr3 = RequestTracer(clock=clock)
    for rid, tid, prio in ((0, 'heavy', 0), (1, 'light', 2)):
        tr3.record(rid, 'submit', t=1.0 + rid, prompt_tokens=3,
                   tenant_id=tid, priority=prio)
        if tid == 'heavy':
            tr3.record(rid, 'quota_defer', t=1.1, tenant_id=tid,
                       bill_tokens=8, retry_after_s=0.5)
        tr3.record(rid, 'admit', t=1.2 + rid)
        tr3.record(rid, 'first_token', t=1.5 + rid,
                   tokens_generated=1)
        tr3.record(rid, 'deadline_miss', t=1.7 + rid, e2e_s=0.8,
                   deadline_s=0.5)
        tr3.record(rid, 'retire', t=1.8 + rid, tokens_generated=2)
    tr3.record(-1, 'degrade_stage', t=1.05, from_stage=0, stage=1,
               stage_name='shed_spec', pressure=0.9)
    with tempfile.TemporaryDirectory() as d:
        p3 = os.path.join(d, 'tenants.jsonl')
        tr3.export_jsonl(p3)
        s3 = summarize_serve(p3)
    assert len(s3['requests']) == 2, s3      # engine event skipped
    byt = s3['percentiles_by_tenant']
    assert set(byt) == {'heavy', 'light'}, byt
    assert byt['heavy']['quota_defers'] == 1, byt
    assert byt['light']['deadline_misses'] == 1, byt
    assert abs(byt['light']['e2e_s']['p50'] - 0.8) < 1e-12, byt
    ttext = render_serve(s3)
    assert 'tenant' in ttext and 'by tenant' in ttext, ttext
    assert 'heavy' in ttext and 'light' in ttext, ttext
    print(ttext)
    print('trace_summary serve selftest: OK')


# ---------------------------------------------------------------------------
# device traces (.xplane.pb): idle gaps by program span, time by kernel
# ---------------------------------------------------------------------------
# a span of the program's ring or of the benchmark: lower-case
# `prefix::name`; the runtime's own C++ TraceMes (`Foo::Bar`) are not
PROGRAM_SPAN = re.compile(r'^[a-z0-9_.]+::[a-z0-9_.]+$')


def load_device_trace(trace_dir):
    """(device ops per chip {n: [[hlo text, start_ns, dur_ns]]}, program
    spans [[name, start_ns, dur_ns]]) of the newest xplane under
    `trace_dir`. Op classes and the planes' names are the benchmark's
    (benchmarks/trace_reduce.py), so the rows match the ledger's."""
    import warnings
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks import trace_reduce as tr
    from jax.profiler import ProfileData
    chips, spans = {}, []
    with warnings.catch_warnings():
        warnings.simplefilter('ignore')
        data = ProfileData.from_file(tr.find_xplane(trace_dir))
        for plane in data.planes:
            device = tr.DEVICE_PLANE.match(plane.name)
            for line in plane.lines:
                if device and line.name == tr.OPS_LINE:
                    chips[int(device.group(1))] = [
                        [e.name, float(e.start_ns), float(e.duration_ns)]
                        for e in line.events]
                elif plane.name == tr.HOST_PLANE:
                    spans += [[e.name, float(e.start_ns),
                               float(e.duration_ns)] for e in line.events
                              if PROGRAM_SPAN.match(e.name)]
    return chips, spans


def summarize_device_trace(chips, spans, top=15):
    """Seconds throughout. Per chip the window (first op start to last
    op end), the busy union, time by class of op, and the idle gaps
    charged by program span; `host_spans` are the spans' own totals
    inside the device window of chip 0."""
    import numpy as np
    from benchmarks import trace_reduce as tr
    starts = np.array([s for _, s, _ in spans])
    durs = np.array([d for _, _, d in spans])
    out = {'chips': {}}
    for n, events in sorted(chips.items()):
        if not events:
            continue
        ops = {}
        for text, _, dur in events:
            if not any(mark in text for mark in tr.CONTAINERS):
                cls = tr.op_class(text)
                ops[cls] = ops.get(cls, 0.0) + dur * 1e-9
        busy = tr._union([s, s + d] for _, s, d in events)
        gaps, counts = {}, {}
        for (_, end), (start, _) in zip(busy, busy[1:]):
            name = 'unattributed'
            if len(spans):
                t = 0.5 * (end + start)
                held = np.nonzero((starts <= t) & (t <= starts + durs))[0]
                if len(held):
                    name = spans[held[np.argmin(durs[held])]][0]
            gaps[name] = gaps.get(name, 0.0) + (start - end) * 1e-9
            counts[name] = counts.get(name, 0) + 1
        window = (busy[-1][1] - busy[0][0]) * 1e-9
        busy_s = sum(e - s for s, e in busy) * 1e-9

        def ranked(table):
            return sorted(table.items(), key=lambda kv: -kv[1])[:top]
        out['chips'][n] = {
            'window_s': window, 'busy_s': busy_s,
            'idle_s': window - busy_s,
            'idle_gaps': [[k, v, counts[k]] for k, v in ranked(gaps)],
            'device_ops': [[k, v] for k, v in ranked(ops)]}
    first = min(chips) if chips else None
    host = {}
    if first is not None and chips[first]:
        lo = min(s for _, s, _ in chips[first])
        hi = max(s + d for _, s, d in chips[first])
        for name, s, d in spans:
            if lo <= s and s + d <= hi:
                calls, total = host.get(name, (0, 0.0))
                host[name] = (calls + 1, total + d * 1e-9)
    out['host_spans'] = [[k, c, t] for k, (c, t) in sorted(
        host.items(), key=lambda kv: -kv[1][1])[:max(top, 24)]]
    return out


def render_device_trace(summary):
    out = []
    for n, c in summary['chips'].items():
        idle = c['idle_s']
        out.append(f'-- chip {n}: window {c["window_s"]:.4f} s, busy '
                   f'{c["busy_s"]:.4f} s, idle {idle:.4f} s '
                   f'({100 * idle / c["window_s"]:.2f} %) ' + '-' * 8)
        # mean_us tells the device's own gaps between the ops of one
        # program (microseconds, thousands of them) from the host
        # holding the device up (milliseconds, one a step)
        out.append(f"{'idle gaps by innermost program span':<40} "
                   f"{'gaps':>6} {'idle_ms':>10} {'of idle':>8} "
                   f"{'mean_us':>9}")
        for name, s, count in c['idle_gaps']:
            out.append(f'{name[:40]:<40} {count:>6} {s * 1e3:>10.3f} '
                       f'{100 * s / idle if idle else 0:>7.1f}% '
                       f'{s / count * 1e6:>9.1f}')
        out.append('')
        out.append(f"{'device time by op class':<48} {'ms':>10} "
                   f"{'of busy':>8}")
        for name, s in c['device_ops']:
            out.append(f'{name[:48]:<48} {s * 1e3:>10.3f} '
                       f'{100 * s / c["busy_s"]:>7.1f}%')
        out.append('')
    out.append(f"{'program spans inside the device window':<40} "
               f"{'calls':>6} {'total_ms':>10} {'avg_us':>10}")
    for name, calls, total in summary['host_spans']:
        out.append(f'{name[:40]:<40} {calls:>6} {total * 1e3:>10.3f} '
                   f'{total / calls * 1e6:>10.1f}')
    return '\n'.join(out)


def _selftest():
    """CI smoke: record a trace through the real tracer, export both
    formats, summarize, and assert the breakdown is sane."""
    import tempfile
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import paddle_tpu.profiler as prof

    results = []
    p = prof.Profiler(on_trace_ready=lambda pr: results.append(
        pr.profiler_result))
    p.start()
    with prof.RecordEvent('executor::build_program', event_type='compile'):
        with prof.RecordEvent('executor::compile', event_type='compile'):
            sum(range(20000))
    for _ in range(3):
        with prof.RecordEvent('executor::run', event_type='executor'):
            sum(range(5000))
        with prof.RecordEvent('dataloader::next', event_type='dataloader'):
            pass
    p.stop()

    with tempfile.TemporaryDirectory() as d:
        ok = True
        for fname, export in (
                ('t.trace.json', results[0].export_chrome_tracing),
                ('t.json', results[0].export_json)):
            path = os.path.join(d, fname)
            export(path)
            s = summarize(load_spans(path))
            assert s['span_count'] == 8, s
            assert s['buckets_us'].get('compile', 0) > 0, s
            assert s['buckets_us'].get('execute', 0) > 0, s
            assert s['buckets_us'].get('dataloader', 0) >= 0, s
            names = [r['name'] for r in s['top_spans']]
            assert 'executor::run' in names, names
            ok = ok and bool(render(s))
        print(render(s))
    _serve_selftest()
    print('trace_summary selftest: OK')
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('trace', nargs='*', help='exported trace JSON '
                    '(profiler spans/chrome, or serve-trace .jsonl '
                    'files — several serve traces merge into one '
                    'cross-replica table)')
    ap.add_argument('--top', type=int, default=15,
                    help='how many spans to list')
    ap.add_argument('--json', action='store_true',
                    help='machine-readable output')
    ap.add_argument('--serve', action='store_true',
                    help='force serve-trace (per-request SLO) mode')
    ap.add_argument('--xplane', metavar='DIR',
                    help='a jax.profiler trace directory: idle gaps by '
                         'program span and device time by kernel')
    ap.add_argument('--selftest', action='store_true',
                    help='generate a synthetic trace and summarize it')
    args = ap.parse_args(argv)
    if args.selftest:
        return _selftest()
    if args.xplane:
        s = summarize_device_trace(*load_device_trace(args.xplane),
                                   top=args.top)
        print(json.dumps(s) if args.json else render_device_trace(s))
        return 0
    if not args.trace:
        ap.error('trace path required (or --selftest / --xplane)')
    if args.serve or all(_looks_like_serve_trace(p)
                         for p in args.trace):
        s = summarize_serve(args.trace)
        print(json.dumps(s) if args.json else render_serve(s))
        return 0
    if len(args.trace) > 1:
        ap.error('multiple trace files only merge in --serve mode')
    summary = summarize(load_spans(args.trace[0]), top=args.top)
    print(json.dumps(summary) if args.json else render(summary))
    return 0


if __name__ == '__main__':
    sys.exit(main())
