"""Step-time ledger & MFU observatory (ISSUE 16): decomposition
reconciliation, analytic FLOPs/recompute factors, peak resolution,
gauge round-trip through the three-engine wiring and the 2-rank
straggler subprocess leg."""
import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

os.environ.setdefault('JAX_PLATFORMS', 'cpu')

from paddle_tpu.core import ledger as L                    # noqa: E402


class _StubGap:
    """A HostGapMonitor stand-in with a fixed snapshot."""

    def __init__(self, wall=0.100, gap=0.010, residue=0.004,
                 blocked=0.0, steps=20):
        self.snap = {
            'steps': steps, 'drained': steps,
            'host_gap_seconds': gap, 'host_residue_seconds': residue,
            'blocked_wait_seconds': blocked,
            'step_interval_seconds': wall,
            'host_bound_fraction': gap / wall if wall else None,
            'dispatch_depth_mean': 1.0, 'dispatch_depth_max': 1,
        }

    def snapshot(self):
        return dict(self.snap)


# ---------------------------------------------------------------------------
# decomposition
# ---------------------------------------------------------------------------
class TestDecomposition:
    def test_components_sum_to_wall(self):
        led = L.StepLedger('unittest', gap=_StubGap())
        a = led.account()
        comps = a['components']
        assert set(comps) == {'compute', 'exposed_comm', 'bubble',
                              'host_gap', 'residue'}
        assert abs(sum(comps.values()) - a['wall_seconds']) < 1e-12
        assert abs(a['reconciled_fraction'] - 1.0) < 1e-9
        assert comps['host_gap'] == pytest.approx(0.010)
        assert comps['residue'] == pytest.approx(0.004)
        assert comps['compute'] == pytest.approx(0.086)

    def test_bubble_eats_device_busy_span_only(self):
        led = L.StepLedger('unittest', gap=_StubGap(),
                           bubble_fraction_fn=lambda: 0.25)
        a = led.account()
        comps = a['components']
        # bubble applies to wall - gap - residue - exposed, not wall
        busy = a['wall_seconds'] - comps['host_gap'] \
            - comps['residue'] - comps['exposed_comm']
        assert comps['bubble'] == pytest.approx(0.25 * busy)
        assert comps['compute'] == pytest.approx(0.75 * busy)
        assert abs(sum(comps.values()) - a['wall_seconds']) < 1e-12

    def test_no_interval_yet_returns_none(self):
        led = L.StepLedger('unittest', gap=_StubGap(wall=0.0))
        assert led.account() is None

    def test_gap_clamped_to_wall(self):
        led = L.StepLedger('unittest',
                           gap=_StubGap(wall=0.010, gap=0.050,
                                        residue=0.020))
        a = led.account()
        comps = a['components']
        assert comps['host_gap'] == pytest.approx(0.010)
        assert comps['residue'] == 0.0
        assert comps['compute'] == 0.0
        assert a['reconciled_fraction'] == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# analytic FLOPs / recompute / peaks
# ---------------------------------------------------------------------------
class TestFlops:
    def test_model_flops_formula_matches_bench(self):
        n, t, l, h, s = 1_418_842_112, 16384, 24, 2048, 2048
        total, attn = L.model_flops_per_step(n, t, layers=l, hidden=h,
                                             seq_len=s)
        assert total == 6.0 * n * t + 12.0 * l * h * s * t
        assert attn == 12.0 * l * h * s * t

    def test_recompute_factors(self):
        total, attn = 100.0, 20.0
        assert L.recompute_factor('none', total, attn) == 0.0
        assert L.recompute_factor(None, total, attn) == 0.0
        assert L.recompute_factor('dots', total, attn) == 0.0
        assert L.recompute_factor('full', total, attn) == 1.0
        assert L.recompute_factor('attn_mlp_boundaries', total, attn) \
            == pytest.approx(0.2)

    def test_recompute_factor_scales_hardware_tflops(self):
        L.configure('unittest', layers=2, hidden=64, seq_len=128,
                    n_params=1000, remat_policy='full',
                    tokens_per_step=256)
        led = L.StepLedger('unittest', gap=_StubGap())
        a = led.account()
        assert a['flops']['recompute_factor'] == 1.0
        assert a['hardware_tflops'] == pytest.approx(
            a['model_tflops'] * 4.0 / 3.0)
        L._arch_hints.pop('unittest', None)

    def test_peak_table(self):
        assert L.resolve_peak_tflops('TPU v5 lite') == 197.0
        assert L.resolve_peak_tflops('TPU v5p') == 459.0
        assert L.resolve_peak_tflops('TPU v4') == 275.0
        assert L.resolve_peak_tflops('TPU v3') == 123.0
        assert L.resolve_peak_tflops('TPU v6e') == 918.0
        # CPU dryrun: no peak, no MFU — absolute TFLOP/s only
        assert L.resolve_peak_tflops('cpu') is None
        assert L.resolve_peak_tflops() is None   # local device is CPU

    def test_mfu_against_peak_hint(self):
        L.configure('unittest2', n_params=10 ** 9, tokens_per_step=1000,
                    peak_tflops=197.0)
        led = L.StepLedger('unittest2', gap=_StubGap(wall=0.100))
        a = led.account()
        # 6e12 flops / 0.1 s = 60 TFLOP/s -> 30.46% of 197
        assert a['model_tflops'] == pytest.approx(60.0)
        assert a['mfu'] == pytest.approx(60.0 / 197.0)
        L._arch_hints.pop('unittest2', None)

    def test_cpu_account_has_no_mfu(self):
        L.configure('unittest3', n_params=10 ** 6, tokens_per_step=100)
        led = L.StepLedger('unittest3', gap=_StubGap())
        a = led.account()
        assert a['model_tflops'] > 0.0
        assert a['peak_tflops'] is None and a['mfu'] is None
        L._arch_hints.pop('unittest3', None)


# ---------------------------------------------------------------------------
# gauges + engine wiring + telemetry
# ---------------------------------------------------------------------------
class TestWiring:
    def test_publish_and_snapshot_roundtrip(self):
        L.configure('unittest4', n_params=500, tokens_per_step=64,
                    remat_policy='full')
        led = L.StepLedger('unittest4', gap=_StubGap())
        acct = led.publish()
        assert acct is not None
        snap = L.ledger_snapshot('unittest4')
        assert snap and 'unittest4' in snap
        got = snap['unittest4']
        assert got['wall_seconds'] == pytest.approx(acct['wall_seconds'])
        for c, v in acct['components'].items():
            assert got['components'][c] == pytest.approx(v)
        assert got['recompute_factor'] == 1.0
        assert got['tokens_per_step'] == 64
        L._arch_hints.pop('unittest4', None)

    def test_jit_trainstep_end_to_end(self):
        import paddle_tpu as paddle
        import paddle_tpu.nn as nn
        from paddle_tpu import jit as pjit

        class M(nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc = nn.Linear(8, 2)

            def forward(self, x):
                return self.fc(x)

        m = M()
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=m.parameters())
        ts = pjit.TrainStep(
            m, lambda mm, x, y: ((mm(x) - y) ** 2).mean(), opt)
        x = paddle.to_tensor(np.zeros((4, 8), 'float32'))
        y = paddle.to_tensor(np.zeros((4, 2), 'float32'))
        for _ in range(5):
            ts.train_step(x, y)
        ts.flush()
        a = ts._ledger.account()
        assert a is not None and a['engine'] == 'jit'
        comps = a['components']
        wall = a['wall_seconds']
        assert abs(sum(comps.values()) - wall) <= 0.10 * wall
        assert a['tokens_per_step'] == 4 * 8
        assert a['n_params'] == 8 * 2 + 2
        assert a['mfu'] is None          # CPU: absolute TFLOP/s only
        snap = L.ledger_snapshot()
        assert snap and 'jit' in snap
        # telemetry carries the account
        from paddle_tpu.profiler import StepTelemetry
        tel = StepTelemetry(publish=False).snapshot()
        assert tel.get('ledger') and 'jit' in tel['ledger']

    def test_render_ledger(self):
        led = L.StepLedger('unittest5', gap=_StubGap())
        led.publish()
        text = L.render_ledger(L.ledger_snapshot('unittest5'))
        assert 'engine: unittest5' in text
        for c in ('compute', 'exposed_comm', 'bubble', 'host_gap',
                  'residue'):
            assert c in text


# ---------------------------------------------------------------------------
# straggler detection
# ---------------------------------------------------------------------------
class TestStraggler:
    def test_noop_without_host_group(self):
        det = L.StragglerDetector(check_every=1)
        assert det.check(1, 0.5) is None
        assert det.maybe_check(1, _StubGap()) is None

    def test_two_rank_injected_slow_rank(self, tmp_path):
        """ISSUE 16 acceptance: a forced 2-rank slow-rank run triggers
        the straggler artifact naming the injected rank, on BOTH ranks,
        via the host-collective allgather."""
        s = socket.socket()
        s.bind(('127.0.0.1', 0))
        port = s.getsockname()[1] - 7     # host backend adds +7
        s.close()
        procs = []
        for rank in range(2):
            env = dict(os.environ)
            env.update({
                'PADDLE_TRAINER_ID': str(rank),
                'PADDLE_TRAINERS_NUM': '2',
                'PADDLE_MASTER': f'127.0.0.1:{port}',
                'JAX_PLATFORMS': 'cpu',
                'STRAGGLER_DUMP_DIR': str(tmp_path),
            })
            env.pop('XLA_FLAGS', None)
            procs.append(subprocess.Popen(
                [sys.executable, '-u',
                 os.path.join(HERE, 'dist_models', 'dist_straggler.py')],
                env=env, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True))
        outs = []
        for p in procs:
            out, _ = p.communicate(timeout=120)
            outs.append(out)
        assert all(p.returncode == 0 for p in procs), outs
        reports = [f for f in os.listdir(tmp_path)
                   if f.startswith('straggler_report.rank')]
        assert len(reports) == 2, (os.listdir(tmp_path), outs)
        with open(os.path.join(tmp_path, sorted(reports)[0])) as f:
            rep = json.load(f)
        assert rep['kind'] == 'straggler_report'
        assert rep['offending_ranks'] == [1]
        assert rep['world_size'] == 2
        assert rep['relative_wall']['1'] > rep['threshold']
        text = L.render_straggler_report(rep)
        assert 'STRAGGLER' in text and 'rank 1' in text
