"""BENCHMARK.json against the contract it is written to, and the proof
that the harness is driven by data: a cell, a runner and a per-layer
reader are added as new files plus new entries, editing no file that is
there."""
import copy
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import benchtoy
from benchmarks import common, run as bench_run

NAME = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$')
UNIT = re.compile(r'^[A-Za-z0-9_/%.-]{1,16}$')
PATH = re.compile(r'^[A-Za-z0-9_.\-/]{1,200}$')
SOURCES = {'device_trace', 'program_span', 'program_counter', 'host_clock'}
KEYS = {
    'top': {'command', 'paths', 'run_seconds', 'configs', 'workloads',
            'end_to_end', 'per_layer'},
    'configs': {'name', 'source', 'file', 'reduced', 'why'},
    'workloads': {'name', 'config', 'traffic', 'chips', 'why'},
    'end_to_end': {'name', 'unit', 'better', 'bound', 'source'},
    'per_layer': {'name', 'unit', 'better', 'source', 'layer', 'moves'},
}


def one_line(text):
    return isinstance(text, str) and 1 <= len(text) <= 200 \
        and '\n' not in text and '\t' not in text


def lint(manifest):
    """Every way in which the manifest breaks its contract."""
    d, bad = manifest.data, []
    if set(d) != KEYS['top']:
        return [f'top-level keys {sorted(d)}']
    for group in ('configs', 'workloads', 'end_to_end', 'per_layer'):
        names = [e.get('name') for e in d[group]]
        if len(set(names)) != len(names):
            bad.append(f'{group}: a name appears twice')
        for e in d[group]:
            extra = set(e) - KEYS[group] - (
                {'workloads'} if group in ('end_to_end', 'per_layer')
                else set())
            if extra or KEYS[group] - set(e):
                bad.append(f'{group} {e.get("name")}: keys {sorted(e)}')
            if not NAME.match(str(e.get('name'))):
                bad.append(f'{group}: name {e.get("name")!r}')
    if not (isinstance(d['run_seconds'], int)
            and 1 <= d['run_seconds'] <= 51):
        bad.append(f'run_seconds {d["run_seconds"]!r}')
    if not (1 <= len(d['paths']) <= 16
            and all(PATH.match(p) and not p.startswith('/')
                    and '..' not in p for p in d['paths'])):
        bad.append(f'paths {d["paths"]}')
    for word in d['command']:
        if not one_line(word) or word.startswith('/') or '..' in word:
            bad.append(f'command word {word!r}')
        if os.path.exists(os.path.join(manifest.root, word)) and not any(
                word.startswith(p + '/') for p in d['paths']):
            bad.append(f'command names {word}, outside paths')
    files = [c['file'] for c in d['configs']]
    if len(set(files)) != len(files):
        bad.append('two configurations share a file')
    for c in d['configs']:
        if not any(c['file'].startswith(p + '/') for p in d['paths']) \
                or not os.path.isfile(os.path.join(manifest.root,
                                                   c['file'])):
            bad.append(f'config {c["name"]}: file {c["file"]}')
        if not (one_line(c['source']) and one_line(c['why'])):
            bad.append(f'config {c["name"]}: source or why')
        if len(c['reduced']) > 16 or not all(NAME.match(k) and not re.search(
                r'(_dim|_rank|hidden|intermediate|head_size)', k)
                for k in c['reduced']):
            bad.append(f'config {c["name"]}: reduced {c["reduced"]}')
        if not any(w['config'] == c['name'] for w in d['workloads']):
            bad.append(f'config {c["name"]}: used by no cell')
    cells = [w['name'] for w in d['workloads']]
    pairs = [(w['config'], w['traffic']) for w in d['workloads']]
    if len(set(pairs)) != len(pairs):
        bad.append('a pair of configuration and traffic appears twice')
    if not 1 <= len(cells) <= 24:
        bad.append(f'{len(cells)} cells')
    four = sum(w['chips'] == 4 for w in d['workloads'])
    if four > max(1, len(cells) // 4):
        bad.append(f'{four} of {len(cells)} cells ask for four chips')
    for w in d['workloads']:
        if w['chips'] not in (1, 4) or not one_line(w['why']) \
                or not NAME.match(w['traffic']):
            bad.append(f'cell {w["name"]}: chips, why or traffic')
        try:
            config = manifest.config(w)
            kind = manifest.traffic(w)['kind']
            runner = config['runners'][kind]
            if not os.path.isfile(os.path.join(
                    manifest.bench_dir, 'runners',
                    common.module_file(runner))):
                bad.append(f'cell {w["name"]}: no runner file {runner}')
        except (StopIteration, OSError, KeyError) as e:
            bad.append(f'cell {w["name"]}: {e!r}')
    for group in ('end_to_end', 'per_layer'):
        for m in d[group]:
            if not UNIT.match(m['unit']) or m['better'] not in (
                    'lower', 'higher') or m['source'] not in SOURCES:
                bad.append(f'{m["name"]}: unit, better or source')
            if set(m.get('workloads', [])) - set(cells):
                bad.append(f'{m["name"]}: lists a cell that is not there')
    e2e = {m['name']: m for m in d['end_to_end']}
    if 'setup_s' not in e2e or 'workloads' in e2e['setup_s']:
        bad.append('setup_s must be reported by every cell')
    for m in d['end_to_end']:
        if m['source'] not in ('host_clock', 'device_trace'):
            bad.append(f'{m["name"]}: an end-to-end metric is taken by the '
                       f'benchmark itself')
        if not (isinstance(m['bound'], float) and 0.01 <= m['bound'] <= 0.1):
            bad.append(f'{m["name"]}: bound {m["bound"]!r}')
    for m in d['per_layer']:
        if not one_line(m['layer']):
            bad.append(f'{m["name"]}: layer')
        if not os.path.isfile(os.path.join(
                manifest.bench_dir, 'layer_metrics',
                common.module_file(m['name']))):
            bad.append(f'{m["name"]}: no reader file')
        target = e2e.get(m['moves'])
        if target is None or set(m.get('workloads', cells)) - set(
                target.get('workloads', cells)):
            bad.append(f'{m["name"]}: moves {m["moves"]!r}, which not '
                       f'every one of its cells reports')
    for cell in cells:
        reported = [m['name'] for m in manifest.metrics('end_to_end', cell)]
        if len(reported) < 2 or not manifest.metrics('per_layer', cell):
            bad.append(f'cell {cell}: needs setup_s, one more end-to-end '
                       f'metric and a per-layer metric')
    readers = [common.module_file(m['name']) for m in d['per_layer']]
    if len(set(readers)) != len(readers):
        bad.append('two per-layer metrics share a reader file')
    for p in d['paths']:
        for base, _, names in os.walk(os.path.join(manifest.root, p)):
            if '__pycache__' in base:
                continue
            for n in names:
                rel = os.path.relpath(os.path.join(base, n), manifest.root)
                if not PATH.match(rel):
                    bad.append(f'file name {rel!r}')
    if os.path.getsize(os.path.join(manifest.root, 'BENCHMARK.json')) \
            > 64 * 1024:
        bad.append('BENCHMARK.json is over 64 KiB')
    return bad


def test_the_committed_manifest_meets_the_contract():
    assert lint(benchtoy.manifest()) == []


def test_the_issues_cells_and_metrics_are_there():
    d = benchtoy.manifest().data
    assert {c['name'] for c in d['configs']} == {'gpt3-1.3b', 'bert-large'}
    assert all(w['chips'] == 1 for w in d['workloads'])
    assert {m['name'] for m in d['end_to_end']} == {
        'train_tokens_per_s', 'serve_tokens_per_s', 'ttft_ms_p95',
        'itl_ms_p95', 'setup_s'}
    assert {m['name']: m['moves'] for m in d['per_layer']} == {
        'step_ms.train': 'train_tokens_per_s',
        'pallas_ms_per_step.train': 'train_tokens_per_s',
        'device_idle_share.train': 'train_tokens_per_s',
        'engine_step_ms.serve': 'itl_ms_p95',
        'batch_occupancy.serve': 'serve_tokens_per_s',
        'pallas_ms_per_step.serve': 'serve_tokens_per_s',
        'device_idle_share.serve': 'serve_tokens_per_s',
        'compile_s': 'setup_s'}


def broken(change):
    m = benchtoy.manifest()
    m.data = copy.deepcopy(m.data)
    change(m.data)
    return lint(m)


@pytest.mark.parametrize('change', [
    lambda d: d.update(run_seconds=52),
    lambda d: d.update(run_seconds=30.0),
    lambda d: d['workloads'][0].update(name='has space'),
    lambda d: d['end_to_end'][0].update(unit='tokens per second'),
    lambda d: d['end_to_end'][0].update(bound=0.2),
    lambda d: d['end_to_end'][0].update(why='a key the contract lacks'),
    lambda d: d['per_layer'][0].update(moves='serve_tokens_per_s'),
    lambda d: d['per_layer'][0].update(moves='no_such_metric'),
    lambda d: d['per_layer'][0].update(name='no_reader_for_this'),
    lambda d: d['workloads'][0].update(traffic='no-such-mix'),
    lambda d: [w.update(chips=4) for w in d['workloads'][:2]],
    lambda d: d['configs'][0].update(reduced=['hidden_size']),
    lambda d: d['workloads'].append(dict(d['workloads'][0], name='twin')),
    lambda d: d['end_to_end'][-1].update(workloads=[]),
], ids=lambda f: None)
def test_the_lint_sees_a_broken_manifest(change):
    assert broken(change) != []


def test_one_four_chip_cell_is_always_allowed():
    assert broken(lambda d: d['workloads'][0].update(chips=4)) == []


THROWAWAY_RUNNER = '''
def run(ctx):
    ctx.setup_done()
    return {'correct': True, 'attempted': ctx.traffic['steps'], 'failed': 0,
            'end_to_end': {'train_tokens_per_s': 1.0, 'setup_s': ctx.setup_s},
            'facts': {'echo': ctx.config['hidden_size']}}
'''
THROWAWAY_READER = '''
def read(trace, facts):
    return facts.get('echo')
'''


def test_a_cell_a_runner_and_a_reader_are_added_as_files_alone(tmp_path):
    root = str(tmp_path)
    src = benchtoy.manifest()
    shutil.copy(os.path.join(src.root, 'BENCHMARK.json'), root)
    shutil.copytree(src.bench_dir, os.path.join(root, 'benchmarks'),
                    ignore=shutil.ignore_patterns('__pycache__'))

    def digests():
        out = {}
        for base, _, names in os.walk(os.path.join(root, 'benchmarks')):
            for n in names:
                with open(os.path.join(base, n), 'rb') as f:
                    out[os.path.join(base, n)] = hashlib.sha256(
                        f.read()).hexdigest()
        return out
    before = digests()
    bench = os.path.join(root, 'benchmarks')
    with open(os.path.join(bench, 'configs', 'echo-1.json'), 'w') as f:
        json.dump({'hidden_size': 7, 'runners': {'train': 'train_echo'}}, f)
    with open(os.path.join(bench, 'traffic', 'echo-mix.json'), 'w') as f:
        json.dump({'kind': 'train', 'steps': 3}, f)
    with open(os.path.join(bench, 'runners', 'train_echo.py'), 'w') as f:
        f.write(THROWAWAY_RUNNER)
    with open(os.path.join(bench, 'layer_metrics', 'echo_width_train.py'),
              'w') as f:
        f.write(THROWAWAY_READER)
    with open(os.path.join(root, 'BENCHMARK.json')) as f:
        data = json.load(f)
    data['configs'].append({'name': 'echo-1', 'source': 'none: a test',
                            'file': 'benchmarks/configs/echo-1.json',
                            'reduced': [], 'why': 'throw-away'})
    data['workloads'].append({'name': 'echo-1.echo-mix', 'config': 'echo-1',
                              'traffic': 'echo-mix', 'chips': 1,
                              'why': 'throw-away'})
    data['per_layer'].append({
        'name': 'echo_width.train', 'unit': 'count', 'better': 'higher',
        'source': 'program_counter', 'layer': 'training engines',
        'moves': 'train_tokens_per_s', 'workloads': ['echo-1.echo-mix']})
    for m in data['end_to_end'] + data['per_layer']:
        if m['name'] in ('train_tokens_per_s',):
            m['workloads'].append('echo-1.echo-mix')
    with open(os.path.join(root, 'BENCHMARK.json'), 'w') as f:
        json.dump(data, f)

    manifest = common.Manifest(root=root)
    assert lint(manifest) == []
    cell = manifest.cell('echo-1.echo-mix')
    config, traffic = manifest.config(cell), manifest.traffic(cell)
    runner = manifest.load_module('runners',
                                  config['runners'][traffic['kind']])
    record = runner.run(common.Context(config, traffic, 1, 1.0, 1))
    device = {'platform': 'tpu', 'kind': 'TPU v5 lite', 'count': 1,
              'memory_peak_bytes': 1}
    from benchmarks import trace_reduce
    trace = trace_reduce.reduce(benchtoy.recorded_trace())
    traced = bench_run.result_line(manifest, cell, record, trace, device)
    assert traced['metrics']['echo_width.train'] == {'value': 7.0,
                                                     'unit': 'count'}
    plain = bench_run.result_line(manifest, cell, record, None, device)
    assert set(plain['metrics']) == {'train_tokens_per_s', 'setup_s'}
    after = digests()
    assert {p: h for p, h in after.items() if p in before} == before


def run_cli(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    return subprocess.run(
        [sys.executable, script, '--workload', 'gpt3-1.3b.pretrain-2k',
         '--seed', '3000000001', '--seconds', '1', '--trace', '0'],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_py_refuses_to_run_without_a_tpu():
    root = benchtoy.manifest().root
    done = run_cli(root, 'benchmarks/run.py')
    assert done.returncode != 0
    assert 'no accelerator' in done.stderr
    assert not [ln for ln in done.stdout.splitlines() if ln.startswith('{')]


def test_run_py_refuses_a_directory_without_the_program(tmp_path):
    root = benchtoy.manifest().root
    shutil.copy(os.path.join(root, 'BENCHMARK.json'), tmp_path)
    shutil.copytree(os.path.join(root, 'benchmarks'),
                    tmp_path / 'benchmarks',
                    ignore=shutil.ignore_patterns('__pycache__'))
    done = run_cli(str(tmp_path), 'benchmarks/run.py')
    assert done.returncode != 0 and done.stdout.strip() == ''
