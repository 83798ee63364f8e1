#!/usr/bin/env python3
"""The benchmark's one command: one run of one cell.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is found BY NAME from BENCHMARK.json:
the configuration's file (`configs[].file`), the traffic file
(`benchmarks/traffic/<traffic>.json`), the runner the configuration names
for that kind of traffic (`benchmarks/runners/<runner>.py`) and, in a
traced run, one reader per per-layer metric
(`benchmarks/layer_metrics/<metric>.py`, dots as underscores). Adding a
cell, a way of driving the program or a metric adds files and entries;
nothing here names a model, a cell or a metric.

It runs on the machine it is started on, refuses anything but a TPU with
the chips the cell asks for, logs on `[bench]` lines, and prints last
one JSON object: correct, attempted, failed, metrics, device and, traced,
breakdown. `--trace 0` reports the cell's end-to-end metrics, `--trace 1`
its per-layer metrics.
"""
import time
T_START = time.time()       # set-up is counted from here

import argparse
import json
import math
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import common, flops, trace_reduce
from benchmarks.common import log


def result_line(manifest, cell, record, trace, device):
    """The last line's object. `trace` is the trace's reduction in a
    traced run, None in an untraced one."""
    if trace is None:
        values = record['end_to_end']
        wanted = manifest.metrics('end_to_end', cell['name'])
    else:
        wanted = manifest.metrics('per_layer', cell['name'])
        values = {}
        for m in wanted:
            reader = manifest.load_module('layer_metrics', m['name'])
            value = reader.read(trace, record['facts'])
            if value is not None:       # nothing to read: left out
                values[m['name']] = value
    metrics = {}
    for m in wanted:
        if m['name'] in values:
            value = float(values[m['name']])
            if not math.isfinite(value):
                raise ValueError(f'metric {m["name"]} is {value}')
            metrics[m['name']] = {'value': value, 'unit': m['unit']}
    line = {'correct': bool(record['correct']),
            'attempted': int(record['attempted']),
            'failed': int(record['failed']),
            'metrics': metrics, 'device': dict(device)}
    if trace is not None:
        if not 0 < trace['busy_s'] <= trace['window_s']:
            raise ValueError(f'traced window {trace["window_s"]} s, busy '
                             f'{trace["busy_s"]} s')
        line['device'].update(busy_s=trace['busy_s'],
                              window_s=trace['window_s'])
        line['breakdown'] = {'device_ops': trace['device_ops'],
                             'idle_gaps': trace['idle_gaps']}
    return line


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(common.ROOT, 'paddle_tpu')):
        sys.exit('bench: no paddle_tpu/ beside benchmarks/: the system '
                 'under test is not in this checkout')
    manifest = common.Manifest()
    cell = manifest.cell(args.workload)
    config, traffic = manifest.config(cell), manifest.traffic(cell)
    runner = manifest.load_module('runners',
                                  config['runners'][traffic['kind']])

    import jax
    backend = jax.default_backend()
    if backend != 'tpu':
        sys.exit(f'bench: no accelerator: jax.default_backend() is '
                 f'{backend!r}; the benchmark has no CPU mode')
    if len(jax.devices()) < cell['chips']:
        sys.exit(f'bench: {cell["name"]} needs {cell["chips"]} chips, JAX '
                 f'sees {len(jax.devices())}')
    kind = jax.devices()[0].device_kind
    flops.peaks(kind)           # an unknown device is an error
    # every compile goes to the persistent cache, so that only a cell's
    # first run in a checkout compiles (JAX's default keeps only those
    # over a second). The directory is JAX_COMPILATION_CACHE_DIR or
    # <checkout>/.jax_cache, placed by `import paddle_tpu`.
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)
    import paddle_tpu  # noqa: F401  (places the cache)
    log(f'{cell["name"]} seed {args.seed} seconds {args.seconds} trace '
        f'{args.trace} on {len(jax.devices())} x {kind}; compile cache '
        f'{jax.config.jax_compilation_cache_dir}')

    trace_dir = os.path.join(common.ROOT, '.bench_trace', cell['name'])
    shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = common.Context(
        config, traffic, args.seed, args.seconds, args.trace,
        chips=cell['chips'], t_start=T_START, device_kind=kind,
        profile=lambda: jax.profiler.trace(trace_dir))
    record = runner.run(ctx)
    trace = None
    if args.trace:
        planes = trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        for p in planes:
            for line in p['lines']:
                log(f'trace: plane {p["name"]!r} line {line["name"]!r}: '
                    f'{len(line["events"])} events')
        trace = trace_reduce.reduce(planes)
        if len(trace['chips']) < cell['chips']:
            sys.exit(f'bench: the trace holds device operations of '
                     f'{len(trace["chips"])} chips, the cell uses '
                     f'{cell["chips"]}')
        log('trace per chip: ' + json.dumps({
            n: {k: v for k, v in c.items() if k not in ('ops', 'gaps')}
            for n, c in trace['chips'].items()}))
    line = result_line(manifest, cell, record, trace,
                       common.device_record(cell['chips']))
    print(json.dumps(line), flush=True)


if __name__ == '__main__':
    main()
