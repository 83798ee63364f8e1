#!/usr/bin/env python
"""sweep_prefill_rows — one run of a server cell of the benchmark with
the mixed step's prefill group held at a given width, for the table of
PERF.md that the constant `serving.engine.PREFILL_ROWS` was chosen
from.

The program has no option for P; this tool replaces the constant in its
own process and hands everything else to `benchmarks/run.py` unchanged.
`--rows 0` leaves it as it is. It needs the chip, like run.py:

    chiprun -- python tools/sweep_prefill_rows.py --rows 4 \\
        --workload trinity-mini.mixed-closed64 --seed 11 --seconds 30

Prints run.py's log and result line, and before the line the engine's
own counts of how the mixed step engaged.
"""
import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def keep_stats_at_shutdown(engine_module, log):
    """A server runner shuts its engine down: log how the mixed step
    engaged (stats(), lifetime: warm phase and window) before it does."""
    shutdown = engine_module.ServingEngine.shutdown

    def logging_shutdown(self, *a, **kw):
        st = self.stats()
        log('mixed step: P %d; %s' % (
            self._prefill_rows,
            ', '.join(f'{k} {st[k]:.4f}' for k in (
                'dispatches_per_step', 'prefill_rows_per_dispatch',
                'padded_prefill_token_share'))))
        return shutdown(self, *a, **kw)
    engine_module.ServingEngine.shutdown = logging_shutdown


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--rows', type=int, required=True)
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, default=30.0)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from benchmarks import run
    from benchmarks.common import log
    from paddle_tpu.serving import engine
    if args.rows:
        engine.PREFILL_ROWS = args.rows
    keep_stats_at_shutdown(engine, log)
    run.main(['--workload', args.workload, '--seed', str(args.seed),
              '--seconds', str(args.seconds), '--trace', str(args.trace)])


if __name__ == '__main__':
    main()
