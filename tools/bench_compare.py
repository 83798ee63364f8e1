#!/usr/bin/env python
"""Cross-round bench regression tracking (ISSUE 16).

Load any two BENCH_r*.json artifacts, line their legs up, and emit
per-leg metric deltas with regression/improvement verdicts against a
relative threshold — plus the step-time ledger breakdown side by side
when either round carries one — so a bench round produces attributable
numbers instead of a flat headline.

    python tools/bench_compare.py BENCH_old.json BENCH_new.json
    python tools/bench_compare.py A.json B.json --json --threshold 0.05
    python tools/bench_compare.py --selftest

Record shapes handled:
  * the driver wrapper {n, cmd, rc, tail, parsed} (parsed is the bench
    record) or a bare bench.py stdout record;
  * schema v2 (ISSUE 16): top-level `legs` dict + schema_version/round
    stamps + the headline `detail.ledger` record;
  * legacy r04/r05 records: no `legs` — satellite legs nest inside
    `detail` beside the headline scalars (the normalizer lifts both
    into one legs dict, headline under 'gpt1.3b_adamw').

Verdicts: rel = (new - old) / old per metric; |rel| <= threshold is
'flat', beyond it the metric's direction (higher-is-better tok/s vs
lower-is-better ms) decides 'improvement' or 'regression'. With
--strict the process exits 1 when any regression is found.
"""
import argparse
import json
import os
import sys

# metric -> direction ('higher'|'lower' is better). Anything numeric
# and shared but unlisted is reported as 'info' (delta, no verdict).
METRIC_DIRECTION = {
    'mfu': 'higher',
    'tflops': 'higher',
    'tokens_per_sec': 'higher',
    'samples_per_sec': 'higher',
    'images_per_sec': 'higher',
    'steps_per_sec': 'higher',
    'decode_tokens_per_sec': 'higher',
    'requests_per_sec': 'higher',
    'build_rows_per_sec': 'higher',
    'pull_rows_per_sec': 'higher',
    'push_rows_per_sec': 'higher',
    'ms_per_step': 'lower',
    'pull_ms': 'lower',
    'push_ms': 'lower',
    'dense_ms': 'lower',
    'ttft_p50_ms': 'lower',
    'ttft_p99_ms': 'lower',
    'tpot_p50_ms': 'lower',
    'e2e_p99_ms': 'lower',
    # serving goodput ledger & decode roofline (ISSUE 17)
    'goodput_fraction': 'higher',
    'host_bound_fraction': 'lower',
    'hbm_gbps': 'higher',
    'mbu': 'higher',
    # fused decode windows (ISSUE 19): small-batch decode headline
    'small_batch_decode_tokens_per_sec': 'higher',
    'small_batch_host_bound_fraction': 'lower',
    'fused_speedup_vs_per_token': 'higher',
    # tiered KV cache (ISSUE 20): oversubscribed serving headline
    'oversubscribed_decode_tokens_per_sec': 'higher',
    'resurrect_ttft_speedup': 'higher',
}
DEFAULT_THRESHOLD = 0.02
HEADLINE_LEG = 'gpt1.3b_adamw'
SERVE_LEG = 'gpt_serve_throughput'

# legacy detail keys that are records riding with the headline, not
# satellite legs of their own
_NON_LEG_DETAIL = frozenset((
    'host', 'remat', 'ledger', 'memory', 'telemetry', 'pipeline',
    'fused_primitives', 'comm', 'comm_overlap'))


def load_record(path):
    """The bench record out of a driver artifact (or bare stdout)."""
    with open(path) as f:
        doc = json.load(f)
    rec = doc.get('parsed') if isinstance(doc, dict) and 'parsed' in doc \
        else doc
    if not isinstance(rec, dict) or 'metric' not in rec:
        raise ValueError(f'{path}: not a bench record (no metric)')
    return rec


def normalize(rec):
    """-> {round, schema_version, metric, value, legs, ledger}."""
    detail = rec.get('detail') or {}
    legs = rec.get('legs')
    if not isinstance(legs, dict):
        # legacy shape: satellite legs nest inside detail; the headline
        # scalars ARE detail. Lift both.
        legs = {}
        headline = {}
        for k, v in detail.items():
            if isinstance(v, dict) and k not in _NON_LEG_DETAIL:
                legs[k] = v
            elif isinstance(v, (int, float)) or k == 'optimizer':
                headline[k] = v
        if isinstance(rec.get('value'), (int, float)):
            headline.setdefault('mfu', rec['value'])
        legs[HEADLINE_LEG] = headline
    ledger = None
    head = legs.get(HEADLINE_LEG)
    if isinstance(head, dict) and isinstance(head.get('ledger'), dict):
        ledger = head['ledger']
    elif isinstance(detail.get('ledger'), dict):
        ledger = detail['ledger']
    # serving twin (ISSUE 17): the throughput leg's serve-step ledger
    # + goodput + decode roofline, rendered side by side like the
    # training ledger above
    serve = legs.get(SERVE_LEG)
    serve_ledger = None
    if isinstance(serve, dict) and isinstance(serve.get('ledger'), dict):
        serve_ledger = {
            'ledger': serve['ledger'],
            'goodput': serve.get('goodput'),
            'roofline': serve.get('roofline'),
        }
    return {
        'round': rec.get('round'),
        'schema_version': rec.get('schema_version', 1),
        'metric': rec.get('metric'),
        'value': rec.get('value'),
        'legs': legs,
        'ledger': ledger,
        'serve_ledger': serve_ledger,
    }


def _verdict(direction, rel, threshold):
    if abs(rel) <= threshold:
        return 'flat'
    better = rel > 0 if direction == 'higher' else rel < 0
    return 'improvement' if better else 'regression'


def compare_legs(a, b, threshold=DEFAULT_THRESHOLD):
    """Per-leg metric deltas. Returns a list of leg dicts:
    {leg, status, metrics: [{name, old, new, rel, verdict}]}."""
    out = []
    for leg in sorted(set(a['legs']) | set(b['legs'])):
        la, lb = a['legs'].get(leg), b['legs'].get(leg)
        if la is None or lb is None:
            out.append({'leg': leg,
                        'status': 'added' if la is None else 'removed',
                        'metrics': []})
            continue
        if 'error' in la or 'error' in lb:
            which = ('both' if 'error' in la and 'error' in lb
                     else ('old' if 'error' in la else 'new'))
            out.append({'leg': leg, 'status': f'error({which})',
                        'metrics': []})
            continue
        rows = []
        for name in sorted(set(la) & set(lb)):
            va, vb = la[name], lb[name]
            if not (isinstance(va, (int, float))
                    and isinstance(vb, (int, float))):
                continue
            if not va:
                continue
            direction = METRIC_DIRECTION.get(name)
            rel = (vb - va) / abs(va)
            rows.append({
                'name': name, 'old': va, 'new': vb,
                'rel': round(rel, 4),
                'verdict': (_verdict(direction, rel, threshold)
                            if direction else 'info'),
            })
        out.append({'leg': leg, 'status': 'compared', 'metrics': rows})
    return out


def compare(a, b, threshold=DEFAULT_THRESHOLD):
    """The full comparison document for two normalized records."""
    legs = compare_legs(a, b, threshold)
    verdicts = [m['verdict'] for leg in legs for m in leg['metrics']]
    return {
        'old_round': a['round'], 'new_round': b['round'],
        'old_metric': {'name': a['metric'], 'value': a['value']},
        'new_metric': {'name': b['metric'], 'value': b['value']},
        'threshold': threshold,
        'legs': legs,
        'ledger': {'old': a['ledger'], 'new': b['ledger']},
        'serve_ledger': {'old': a.get('serve_ledger'),
                         'new': b.get('serve_ledger')},
        'regressions': verdicts.count('regression'),
        'improvements': verdicts.count('improvement'),
        'flat': verdicts.count('flat'),
    }


# ---------------------------------------------------------------------------
# rendering
# ---------------------------------------------------------------------------
_MARK = {'regression': '!! regression', 'improvement': '++ improvement',
         'flat': '   flat', 'info': '   info'}


def render(cmp_doc):
    old_r = cmp_doc.get('old_round') or 'old'
    new_r = cmp_doc.get('new_round') or 'new'
    out = [f'== bench compare {old_r} -> {new_r} ' + '=' * 30]
    om, nm = cmp_doc['old_metric'], cmp_doc['new_metric']
    out.append(f"headline: {om['name']} {om['value']} -> "
               f"{nm['name']} {nm['value']}   (threshold "
               f"{cmp_doc['threshold'] * 100:.1f}%)")
    for leg in cmp_doc['legs']:
        if leg['status'] != 'compared':
            out.append(f"  {leg['leg']:<24} [{leg['status']}]")
            continue
        out.append(f"  {leg['leg']}:")
        for m in leg['metrics']:
            out.append(
                f"    {m['name']:<22} {m['old']:>12.4g} -> "
                f"{m['new']:>12.4g}  {m['rel'] * 100:>+7.2f}%  "
                f"{_MARK.get(m['verdict'], m['verdict'])}")
    led = cmp_doc.get('ledger') or {}
    la, lb = led.get('old'), led.get('new')
    if la or lb:
        out.append('  step-time ledger (per-step seconds, '
                   f'{old_r} | {new_r}):')
        ca = (la or {}).get('components') or {}
        cb = (lb or {}).get('components') or {}

        def _f(v):
            return f'{v * 1e3:10.3f}ms' if isinstance(
                v, (int, float)) else '         --'

        out.append(f"    {'wall':<14} "
                   f"{_f((la or {}).get('wall_seconds'))} | "
                   f"{_f((lb or {}).get('wall_seconds'))}")
        for c in ('compute', 'exposed_comm', 'bubble', 'host_gap',
                  'residue'):
            out.append(f'    {c:<14} {_f(ca.get(c))} | {_f(cb.get(c))}')
        for key in ('model_tflops', 'hardware_tflops', 'mfu'):
            va = (la or {}).get(key)
            vb = (lb or {}).get(key)
            if va is not None or vb is not None:
                fa = f'{va:.4g}' if isinstance(va, (int, float)) else '--'
                fb = f'{vb:.4g}' if isinstance(vb, (int, float)) else '--'
                out.append(f'    {key:<14} {fa:>12} | {fb:>12}')
    sled = cmp_doc.get('serve_ledger') or {}
    sa, sb = sled.get('old'), sled.get('new')
    if sa or sb:
        out.append('  serve ledger (per-iteration seconds, '
                   f'{old_r} | {new_r}):')
        acct_a = (sa or {}).get('ledger') or {}
        acct_b = (sb or {}).get('ledger') or {}
        ca = acct_a.get('components') or {}
        cb = acct_b.get('components') or {}

        def _f(v):
            return f'{v * 1e3:10.3f}ms' if isinstance(
                v, (int, float)) else '         --'

        out.append(f"    {'wall':<14} "
                   f"{_f(acct_a.get('wall_seconds'))} | "
                   f"{_f(acct_b.get('wall_seconds'))}")
        for c in ('compute', 'host_fetch', 'schedule', 'page_stream',
                  'residue'):
            out.append(f'    {c:<14} {_f(ca.get(c))} | {_f(cb.get(c))}')

        def _g(v, fmt='{:.4g}'):
            return fmt.format(v) if isinstance(v, (int, float)) else '--'

        gp_a = (sa or {}).get('goodput') or {}
        gp_b = (sb or {}).get('goodput') or {}
        rf_a = (sa or {}).get('roofline') or {}
        rf_b = (sb or {}).get('roofline') or {}
        for label, va, vb in (
                ('goodput_frac', gp_a.get('goodput_fraction'),
                 gp_b.get('goodput_fraction')),
                ('wasted_tokens', gp_a.get('wasted_tokens'),
                 gp_b.get('wasted_tokens')),
                ('host_bound', acct_a.get('host_bound_fraction'),
                 acct_b.get('host_bound_fraction')),
                ('hbm_gbps', rf_a.get('hbm_gbps'), rf_b.get('hbm_gbps')),
                ('mbu', rf_a.get('mbu'), rf_b.get('mbu'))):
            if va is not None or vb is not None:
                out.append(f'    {label:<14} {_g(va):>12} | {_g(vb):>12}')
    out.append(f"verdicts: {cmp_doc['regressions']} regression(s), "
               f"{cmp_doc['improvements']} improvement(s), "
               f"{cmp_doc['flat']} flat")
    return '\n'.join(out)


# ---------------------------------------------------------------------------
# selftest
# ---------------------------------------------------------------------------
def legacy_fixture(mfu, ms_per_step):
    """A driver artifact in the pre-schema (v1) shape: the record under
    `parsed`, satellite legs — and their errors — nested inside the
    headline's detail. The values are made up: this is a SHAPE fixture
    for normalize()/compare(), not a measurement."""
    return {'n': 0, 'cmd': 'python bench.py', 'rc': 0, 'tail': '',
            'parsed': {
                'metric': 'gpt1.3b_adamw_trainstep_mfu', 'value': mfu,
                'unit': 'fraction', 'vs_baseline': round(mfu / 0.45, 4),
                'detail': {
                    'ms_per_step': ms_per_step,
                    'tokens_per_sec': round(16384e3 / ms_per_step, 1),
                    'params': 1315577856, 'seq_len': 2048,
                    'microbatches': 4,
                    'optimizer': 'adamw_bf16_moments',
                    'gpt1.3b_sgd': {'mfu': mfu + 0.01,
                                    'ms_per_step': ms_per_step - 20.0},
                    'lenet_mnist': {'error': 'RESOURCE_EXHAUSTED'}}}}


def selftest():
    # 1) synthetic v2 pair: delta math, verdict signs, ledger rendering
    def _rec(ms, toks, mfu, compute):
        return {'schema_version': 2, 'round': f'r{int(ms)}',
                'metric': 'm', 'value': mfu,
                'legs': {HEADLINE_LEG: {
                    'ms_per_step': ms, 'tokens_per_sec': toks,
                    'mfu': mfu,
                    'ledger': {'wall_seconds': ms / 1e3,
                               'components': {'compute': compute,
                                              'exposed_comm': 0.01,
                                              'bubble': 0.02,
                                              'host_gap': 0.005,
                                              'residue': 0.001},
                               'model_tflops': 100.0, 'mfu': mfu}}},
                'detail': {}}

    a = normalize(_rec(1000.0, 16000.0, 0.50, 0.9))
    b = normalize(_rec(800.0, 20000.0, 0.625, 0.7))
    doc = compare(a, b, threshold=0.02)
    head = {m['name']: m for leg in doc['legs'] for m in leg['metrics']
            if leg['leg'] == HEADLINE_LEG}
    assert head['ms_per_step']['verdict'] == 'improvement', head
    assert head['tokens_per_sec']['verdict'] == 'improvement', head
    assert abs(head['ms_per_step']['rel'] - (-0.2)) < 1e-9, head
    assert doc['ledger']['old'] and doc['ledger']['new']
    text = render(doc)
    assert 'step-time ledger' in text and 'compute' in text
    rev = compare(b, a, threshold=0.02)
    assert rev['regressions'] >= 2, 'reversed compare must regress'

    # 1b) synthetic serve-ledger pair (ISSUE 17): goodput_fraction is
    # higher-is-better, host_bound_fraction lower-is-better, and the
    # serve ledger/goodput/roofline render side by side
    def _srec(round_id, gf, hbf, mbu):
        return {'schema_version': 2, 'round': round_id,
                'metric': 'm', 'value': 0.5,
                'legs': {
                    HEADLINE_LEG: {'ms_per_step': 100.0},
                    SERVE_LEG: {
                        'decode_tokens_per_sec': 5000.0,
                        'goodput_fraction': gf,
                        'host_bound_fraction': hbf,
                        'hbm_gbps': 400.0 * (1.0 + mbu),
                        'mbu': mbu,
                        'ledger': {
                            'wall_seconds': 0.010,
                            'host_bound_fraction': hbf,
                            'components': {'compute': 0.006,
                                           'host_fetch': 0.002,
                                           'schedule': 0.001,
                                           'page_stream': 0.0005,
                                           'residue': 0.0005}},
                        'goodput': {'emitted_tokens': 1000,
                                    'delivered_tokens': int(gf * 1000),
                                    'wasted_tokens':
                                        1000 - int(gf * 1000),
                                    'goodput_fraction': gf},
                        'roofline': {'decode_bytes_per_iteration':
                                     1 << 20,
                                     'hbm_gbps': 400.0 * (1.0 + mbu),
                                     'mbu': mbu}}},
                'detail': {}}

    sa = normalize(_srec('sA', 0.80, 0.20, 0.30))
    sb = normalize(_srec('sB', 0.95, 0.10, 0.40))
    sdoc = compare(sa, sb, threshold=0.02)
    srows = {m['name']: m for leg in sdoc['legs']
             for m in leg['metrics'] if leg['leg'] == SERVE_LEG}
    assert srows['goodput_fraction']['verdict'] == 'improvement', srows
    assert srows['host_bound_fraction']['verdict'] == 'improvement', \
        srows
    assert srows['mbu']['verdict'] == 'improvement', srows
    srev = compare(sb, sa, threshold=0.02)
    srev_rows = {m['name']: m for leg in srev['legs']
                 for m in leg['metrics'] if leg['leg'] == SERVE_LEG}
    assert srev_rows['goodput_fraction']['verdict'] == 'regression'
    assert srev_rows['host_bound_fraction']['verdict'] == 'regression'
    stext = render(sdoc)
    assert 'serve ledger' in stext and 'page_stream' in stext, stext
    assert 'goodput_frac' in stext and 'host_bound' in stext, stext

    # 2) two legacy-shape driver artifacts: normalization and the
    # asserted regression verdict (headline MFU down 5%, past the 2%
    # default threshold)
    import tempfile
    with tempfile.TemporaryDirectory() as tmp:
        paths = []
        for name, doc in (('old.json', legacy_fixture(0.60, 1300.0)),
                          ('new.json', legacy_fixture(0.57, 1368.4))):
            paths.append(os.path.join(tmp, name))
            with open(paths[-1], 'w') as f:
                json.dump(doc, f)
        a, b = (normalize(load_record(p)) for p in paths)
    assert HEADLINE_LEG in a['legs'] and HEADLINE_LEG in b['legs']
    doc = compare(a, b)
    head = {m['name']: m for leg in doc['legs'] for m in leg['metrics']
            if leg['leg'] == HEADLINE_LEG}
    assert head['mfu']['verdict'] == 'regression', head.get('mfu')
    assert head['ms_per_step']['verdict'] == 'regression', \
        head.get('ms_per_step')
    assert doc['regressions'] >= 1
    text = render(doc)
    assert 'regression' in text
    print(text)
    print('bench_compare selftest OK')
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('old', nargs='?', help='older BENCH_r*.json')
    ap.add_argument('new', nargs='?', help='newer BENCH_r*.json')
    ap.add_argument('--threshold', type=float, default=DEFAULT_THRESHOLD,
                    help='relative delta past which a verdict is '
                         'rendered (default 0.02)')
    ap.add_argument('--json', action='store_true',
                    help='emit the comparison document as JSON')
    ap.add_argument('--strict', action='store_true',
                    help='exit 1 when any regression is found')
    ap.add_argument('--selftest', action='store_true')
    args = ap.parse_args(argv)
    if args.selftest:
        return selftest()
    if not args.old or not args.new:
        ap.error('need two BENCH_r*.json paths (or --selftest)')
    doc = compare(normalize(load_record(args.old)),
                  normalize(load_record(args.new)),
                  threshold=args.threshold)
    if args.json:
        print(json.dumps(doc, indent=2))
    else:
        print(render(doc))
    if args.strict and doc['regressions']:
        return 1
    return 0


if __name__ == '__main__':
    sys.exit(main())
