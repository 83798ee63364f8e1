"""What run.py and the runners share: the manifest, the run's context,
the compile meter and the device record. Nothing here names a model, a
cell or a metric."""
import contextlib
import json
import os
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def log(*a):
    print('[bench]', *a, flush=True)


def load_json(path):
    with open(path) as f:
        return json.load(f)


class Manifest:
    """BENCHMARK.json plus the files it names. `root` is the checkout;
    `bench_dir` holds traffic/, runners/ and layer_metrics/ (the tests
    point both at a temporary copy to show that a cell, a runner and a
    reader are added as files and entries alone)."""

    def __init__(self, root=ROOT, bench_dir=None):
        self.root = root
        self.bench_dir = bench_dir or os.path.join(root, 'benchmarks')
        self.data = load_json(os.path.join(root, 'BENCHMARK.json'))

    def cell(self, name):
        for w in self.data['workloads']:
            if w['name'] == name:
                return w
        raise SystemExit(f'bench: no workload {name!r} in BENCHMARK.json; '
                         f'one of {[w["name"] for w in self.data["workloads"]]}')

    def config(self, cell):
        entry = next(c for c in self.data['configs']
                     if c['name'] == cell['config'])
        return load_json(os.path.join(self.root, entry['file']))

    def traffic(self, cell):
        return load_json(os.path.join(self.bench_dir, 'traffic',
                                      cell['traffic'] + '.json'))

    def metrics(self, group, cell_name):
        """The `end_to_end` or `per_layer` metrics this cell reports."""
        return [m for m in self.data[group]
                if cell_name in m.get('workloads', [cell_name])]

    def load_module(self, kind, name):
        """benchmarks/<kind>/<name>.py by file, so a name may hold a dot
        and a later PR's file needs no entry in any package."""
        import importlib.util
        path = os.path.join(self.bench_dir, kind, module_file(name))
        spec = importlib.util.spec_from_file_location(
            f'benchmarks.{kind}.{module_file(name)[:-3]}', path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod


def module_file(name):
    return name.replace('.', '_').replace('-', '_') + '.py'


class CompileMeter:
    """Counts XLA compilations (loads from the persistent cache included)
    and their seconds from JAX's own monitoring event — as
    chip_smoke.CompileMeter does."""
    EVENT = '/jax/core/compile/backend_compile_duration'

    def __init__(self):
        self.count, self.seconds = 0, 0.0
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.count += 1
            self.seconds += float(duration)


class Context:
    """One run of one cell, as a runner sees it."""

    def __init__(self, config, traffic, seed, seconds, trace, chips=1,
                 meter=None, t_start=None, profile=None, device_kind=None):
        self.config, self.traffic = config, traffic
        self.seed, self.seconds = int(seed), float(seconds)
        self.trace, self.chips = bool(trace), int(chips)
        # the chip's `device_kind`; None off the chip (the tests), where
        # nothing is set against a peak
        self.device_kind = device_kind
        self.meter = meter or CompileMeter()
        self.t_start = time.time() if t_start is None else t_start
        # profile() wraps the traced part of the window; the tests pass
        # a null context (a CPU profile is no device trace)
        self.profile = profile or contextlib.nullcontext
        self._marks = [('start', self.t_start)]

    def mark(self, phase):
        """Set-up phases, printed by `setup_done` — where set-up goes."""
        self._marks.append((phase, time.time()))

    def setup_done(self):
        """Call at the first instant of the measured window."""
        now = time.time()
        self.setup_s = now - self.t_start
        self.compile_s = self.meter.seconds
        self.compiles_at_window = self.meter.count
        phases = ', '.join(f'{n} {t - t0:.1f}' for (_, t0), (n, t)
                           in zip(self._marks, self._marks[1:] +
                                  [('to window', now)]))
        log(f'set-up {self.setup_s:.1f} s ({phases}); '
            f'{self.meter.count} compiles {self.compile_s:.1f} s')

    def compiles_in_window(self):
        return self.meter.count - self.compiles_at_window

    @property
    def weights_seed(self):
        """The seed folded below 2**31 for the program's generators (the
        driver's seeds pass 2**31; jax and paddle seeds are int32)."""
        return self.seed % (2 ** 31 - 1)


def device_record(chips):
    """The `device` object of the last line, as JAX reports it; the
    peak is the fullest of the chips used."""
    import jax
    devs = jax.devices()
    peaks = [(d.memory_stats() or {}).get('peak_bytes_in_use', 0)
             for d in devs[:chips]]
    return {'platform': devs[0].platform, 'kind': devs[0].device_kind,
            'count': len(devs), 'memory_peak_bytes': int(max(peaks))}


def percentile(values, q):
    """The q-th percentile, nearest rank (no interpolation past the
    data: a p95 of 200 samples is the 190th smallest)."""
    import math
    s = sorted(values)
    return s[max(0, math.ceil(q / 100.0 * len(s)) - 1)]


def quartiles(values):
    import statistics
    if len(values) < 2:
        return list(values)
    return [round(q, 3) for q in statistics.quantiles(values, n=4)]
