"""Numerics observatory (ISSUE 3): fused tensor stats vs numpy, the
eager FLAGS_check_nan_inf guard (immediate + deferred with replay
localization), jit stat taps through the compiled engines, the
cross-rank divergence sentinel (incl. a true 2-rank forced desync),
artifact schema round-trips, and the clip/AMP satellites."""
import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.core import numerics as num
from paddle_tpu.core.tensor import Tensor

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(autouse=True)
def _numerics_flags_reset():
    yield
    paddle.set_flags({'FLAGS_check_nan_inf': False,
                      'FLAGS_check_nan_inf_deferred': False,
                      'FLAGS_tensor_stats': False})
    num.reset()


def _count_fetches(monkeypatch):
    """Route the observatory's single host-sync hook through a counter."""
    calls = []
    real = num._host_fetch
    monkeypatch.setattr(num, '_host_fetch',
                        lambda tree: calls.append(1) or real(tree))
    return calls


# ---------------------------------------------------------------------------
# fused tensor stats
# ---------------------------------------------------------------------------
class TestTensorStats:
    def test_matches_numpy(self):
        a = np.array([1.0, -2.0, 0.0, np.nan, np.inf, -np.inf, 3.5, 0.0],
                     np.float32)
        st = num.tensor_stats(a)
        assert st.nan_count == 1
        assert st.inf_count == 2
        assert st.zero_count == 2
        assert st.nonfinite_count == 3
        fin = a[np.isfinite(a)]
        assert np.isclose(st.min, fin.min())
        assert np.isclose(st.max, fin.max())
        assert np.isclose(st.mean, fin.mean(), rtol=1e-6)
        assert np.isclose(st.rms, np.sqrt((fin ** 2).mean()), rtol=1e-6)
        assert np.isclose(st.l2_norm, np.sqrt((fin ** 2).sum()), rtol=1e-6)
        assert st.numel == 8
        assert st.shape == (8,) and st.dtype == 'float32'

    def test_subnormal_and_zero_disjoint(self):
        # FTZ backends may compare a subnormal equal to 0 — the two
        # buckets must stay disjoint regardless
        a = np.array([0.0, 1e-40, 1.0], np.float32)
        st = num.tensor_stats(a)
        assert st.subnormal_count == 1
        assert st.zero_count == 1

    def test_bfloat16_and_int(self):
        import jax.numpy as jnp
        st = num.tensor_stats(jnp.asarray([1.0, jnp.nan], jnp.bfloat16))
        assert st.nan_count == 1 and st.numel == 2
        sti = num.tensor_stats(np.array([0, 3, 0], np.int32))
        assert sti.zero_count == 2 and sti.nonfinite_count == 0
        assert np.isclose(sti.l2_norm, 3.0)

    def test_empty(self):
        st = num.tensor_stats(np.zeros((0, 4), np.float32))
        assert st.numel == 0 and st.nonfinite_count == 0

    def test_collect_batches_one_sync(self, monkeypatch):
        calls = _count_fetches(monkeypatch)
        named = {f't{i}': np.full((4,), i, np.float32) for i in range(12)}
        out = num.collect(named)
        assert len(calls) == 1                   # 12 tensors, one sync
        assert out['t3'].mean == 3.0
        assert out['t0'].zero_count == 4

    def test_as_dict_json_ready(self):
        d = num.tensor_stats(np.ones((2, 2), np.float32)).as_dict()
        json.dumps(d)
        assert d['shape'] == [2, 2] and d['numel'] == 4


# ---------------------------------------------------------------------------
# eager guard
# ---------------------------------------------------------------------------
class TestEagerGuardImmediate:
    def test_trips_at_the_op_with_structured_report(self, tmp_path,
                                                    monkeypatch):
        monkeypatch.setenv('FLEET_LOG_DIR', str(tmp_path))
        paddle.set_flags({'FLAGS_check_nan_inf': True})
        with pytest.raises(FloatingPointError) as ei:
            paddle.log(paddle.to_tensor([-1.0]))
        err = ei.value
        assert isinstance(err, num.NumericsError)
        rep = err.report
        assert rep['kind'] == 'numerics_report'
        assert rep['op'] == 'log'
        assert rep['mode'] == 'eager-immediate'
        assert rep['output']['stats']['nan_count'] == 1
        assert rep['inputs'][0]['stats']['nan_count'] == 0
        assert err.report_path and os.path.exists(err.report_path)
        with open(err.report_path) as f:
            assert json.load(f)['op'] == 'log'

    def test_clean_ops_do_not_trip(self):
        paddle.set_flags({'FLAGS_check_nan_inf': True})
        out = paddle.log(paddle.to_tensor([1.0, 2.0]))
        assert np.isfinite(out.numpy()).all()


class TestEagerGuardDeferred:
    def _flags(self):
        paddle.set_flags({'FLAGS_check_nan_inf': True,
                          'FLAGS_check_nan_inf_deferred': True})

    def test_localizes_origin_not_consumer(self):
        self._flags()
        x = paddle.to_tensor([0.25, 0.5])
        y = paddle.log(x - 1.0)            # origin: log of negatives
        z = y * 2.0                        # consumer inherits the NaN
        w = z + 1.0                        # noqa: F841 — more consumers
        with pytest.raises(num.NumericsError) as ei:
            num.flush(site='test', step=3)
        rep = ei.value.report
        assert rep['op'] == 'log'
        assert rep['mode'] == 'eager-deferred'
        assert rep['step'] == 3
        # the replay proves the op CREATED the NaN: inputs were finite
        assert all(i['stats']['nan_count'] == 0 and
                   i['stats']['inf_count'] == 0 for i in rep['inputs'])

    def test_clean_step_costs_exactly_one_sync(self, monkeypatch):
        self._flags()
        x = paddle.to_tensor([1.0, 2.0])
        for _ in range(5):
            x = paddle.log(x * x + 1.0)
        calls = _count_fetches(monkeypatch)
        assert num.flush() is None
        assert len(calls) == 1
        assert num.guard().pending_ops() == 0

    def test_flush_without_ops_is_free(self, monkeypatch):
        self._flags()
        calls = _count_fetches(monkeypatch)
        assert num.flush() is None
        assert not calls

    def test_journal_cap_bounds_memory(self):
        paddle.set_flags({'FLAGS_check_nan_inf': True,
                          'FLAGS_check_nan_inf_deferred': True,
                          'FLAGS_check_nan_inf_max_journal': 8})
        y = paddle.log(paddle.to_tensor([-1.0]))       # origin
        for _ in range(12):
            y = y * 1.0
        assert num.guard().pending_ops() == 8
        with pytest.raises(num.NumericsError) as ei:
            num.flush()
        assert ei.value.report['journal_dropped'] > 0
        paddle.set_flags({'FLAGS_check_nan_inf_max_journal': 4096})

    def test_optimizer_step_is_the_boundary_and_guards_params(self):
        """The deferred sync runs at optimizer.step BEFORE the update:
        a poisoned backward raises and leaves params untouched."""
        self._flags()
        paddle.seed(0)
        net = nn.Linear(2, 2)
        opt = paddle.optimizer.SGD(learning_rate=0.5,
                                   parameters=net.parameters())
        w_before = np.asarray(net.weight.data).copy()
        x = paddle.to_tensor(np.ones((2, 2), np.float32))
        loss = paddle.log(net(x).sum() - 1e9)        # log(negative) -> nan
        loss.backward()
        with pytest.raises(num.NumericsError):
            opt.step()
        np.testing.assert_array_equal(np.asarray(net.weight.data),
                                      w_before)


# ---------------------------------------------------------------------------
# jit taps through the compiled engines
# ---------------------------------------------------------------------------
def _hybrid_engine(hidden=16):
    from paddle_tpu.distributed import topology_runtime
    from paddle_tpu.distributed.fleet.meta_parallel.hybrid_engine import (
        HybridParallelTrainStep)
    topology_runtime.build_mesh(['dp'], [1])
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, hidden), nn.ReLU(),
                        nn.Linear(hidden, 1))
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=net.parameters())

    def loss_fn(m, x, y):
        d = m(x) - y
        return (d * d).mean()

    eng = HybridParallelTrainStep(net, loss_fn, opt)
    rng = np.random.RandomState(0)
    x = Tensor(rng.rand(4, 8).astype('float32'))
    y = Tensor(rng.rand(4, 1).astype('float32'))
    return eng, x, y


class TestJitTaps:
    def test_hybrid_engine_publishes_stats_one_sync_per_step(
            self, monkeypatch):
        paddle.set_flags({'FLAGS_tensor_stats': True})
        eng, x, y = _hybrid_engine()
        try:
            float(eng(x, y))                       # compile + warm
            calls = _count_fetches(monkeypatch)
            for _ in range(3):
                eng(x, y)
            assert len(calls) == 3                 # ONE sync per step
            taps = eng.last_numerics
            assert taps['grad_norm'] > 0
            assert set(taps['grads']) == set(eng._params)
            assert all(s.nonfinite_count == 0
                       for s in taps['grads'].values())
            from paddle_tpu.core import monitor
            g = monitor.metrics().get('ptpu_num_grad_norm_global')
            assert g is not None and g.value() > 0
        finally:
            eng.shutdown()

    def test_hybrid_engine_planted_nan_raises_naming_layer(self):
        import jax.numpy as jnp
        paddle.set_flags({'FLAGS_check_nan_inf': True})
        eng, x, y = _hybrid_engine()
        float(eng(x, y))
        name = next(n for n in eng._params if n.endswith('weight'))
        eng._params[name] = eng._params[name] * jnp.nan
        with pytest.raises(num.NumericsError) as ei:
            eng(x, y)
        rep = ei.value.report
        assert rep['mode'] == 'jit' and rep['site'] == 'hybrid'
        assert rep['first_bad']
        assert any(t['name'] == name for t in rep['tensors'])
        assert ei.value.report_path and \
            os.path.exists(ei.value.report_path)
        eng._closed = True          # poisoned params; skip shutdown

    def test_trainstep_taps_and_trip(self):
        import jax.numpy as jnp
        from paddle_tpu.jit import TrainStep
        paddle.set_flags({'FLAGS_check_nan_inf': True})
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(),
                            nn.Linear(16, 1))
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=net.parameters())
        step = TrainStep(net, lambda m, a, b: ((m(a) - b) ** 2).mean(),
                         opt)
        rng = np.random.RandomState(0)
        x = Tensor(rng.rand(4, 8).astype('float32'))
        y = Tensor(rng.rand(4, 1).astype('float32'))
        float(step(x, y))
        assert step.last_numerics['grad_norm'] > 0
        k = next(iter(step._params))
        step._params[k] = step._params[k] * jnp.nan
        with pytest.raises(num.NumericsError) as ei:
            step(x, y)
        assert ei.value.report['site'] == 'jit'

    def test_pipeline_engine_taps(self):
        from paddle_tpu.distributed import topology_runtime
        from paddle_tpu.distributed.fleet.meta_parallel.spmd_pipeline \
            import SpmdPipelineEngine
        paddle.set_flags({'FLAGS_tensor_stats': True})
        topology_runtime.build_mesh(['dp', 'pp'], [1, 1])
        paddle.seed(0)
        H, V = 16, 11

        class Embed(nn.Layer):
            def __init__(self):
                super().__init__()
                self.emb = nn.Embedding(V, H)

            def forward(self, ids):
                return self.emb(ids)

        class Head(nn.Layer):
            def __init__(self):
                super().__init__()
                self.proj = nn.Linear(H, V)

            def forward(self, h, labels):
                logits = self.proj(h)
                return nn.functional.cross_entropy(
                    logits.reshape([-1, V]),
                    labels.reshape([-1])).mean()

        eng = SpmdPipelineEngine(
            Embed(), [nn.Linear(H, H) for _ in range(2)], Head(),
            paddle.optimizer.SGD(learning_rate=0.1, parameters=[]),
            accumulate_steps=2)
        try:
            rng = np.random.RandomState(0)
            ids = Tensor(rng.randint(0, V, (4, 6)).astype('int32'))
            labels = Tensor(rng.randint(0, V, (4, 6)).astype('int64'))
            float(eng.train_batch((ids, labels)).data)
            taps = eng.last_numerics
            assert taps['grad_norm'] > 0
            assert any(k.startswith('blocks/') for k in taps['grads'])
            assert any(k.startswith('embed/') for k in taps['grads'])
            # the fp16-scaling mode keeps working with taps threaded
            float(eng.train_batch((ids, labels), scale=8.0).data)
            assert not bool(np.asarray(eng.last_found_inf))
            assert eng.last_numerics['grad_norm'] > 0
            # a loss-scale OVERFLOW step the engine survives (update
            # skipped via found_inf) must NOT trip the taps, even with
            # the guard armed — the GradScaler owns that recovery
            import jax.numpy as jnp
            paddle.set_flags({'FLAGS_check_nan_inf': True})
            name = next(iter(eng._params['embed']))
            eng._params['embed'][name] = \
                eng._params['embed'][name] * jnp.nan
            eng.train_batch((ids, labels), scale=8.0)   # no raise
            assert bool(np.asarray(eng.last_found_inf))
            assert eng.last_numerics is None
            eng._closed = True          # poisoned params; skip shutdown
        finally:
            if not eng._closed:
                eng.shutdown()


class TestJitTapsShardEscape:
    def test_nonfinite_global_norm_trips_without_local_offender(self):
        """Per-tensor taps are shard-local under mp/pp; the mesh-reduced
        global norm is the check a sharded NaN cannot evade."""
        import jax.numpy as jnp
        paddle.set_flags({'FLAGS_check_nan_inf': True})
        taps = {'grads': {'w': num.stats_vec(jnp.ones((4,)))},
                'params': {},
                'grad_norm_sq': jnp.asarray(jnp.nan, jnp.float32)}
        with pytest.raises(num.NumericsError) as ei:
            num.process_jit_taps(taps, site='hybrid', step=5)
        rep = ei.value.report
        assert rep['first_bad'] == '<global grad norm>'
        assert 'model-parallel shard or pipeline stage' in rep['message']


class TestGuardLifecycle:
    def test_amp_skip_step_resets_guard(self):
        """A GradScaler overflow skip is a SURVIVED nonfinite step: the
        deferred guard's flag/journal must not leak into (and crash) the
        next clean step."""
        from paddle_tpu.amp import GradScaler
        paddle.set_flags({'FLAGS_check_nan_inf': True,
                          'FLAGS_check_nan_inf_deferred': True})
        paddle.seed(0)
        net = nn.Linear(2, 2)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=net.parameters())
        x = paddle.to_tensor(np.ones((2, 2), np.float32))
        # |sum|: the overflow must not hang on the sign the seeded init
        # happens to draw (jax 0.9's stream makes the sum negative)
        loss = paddle.exp(paddle.abs(net(x).sum()) * 1e9)   # -> inf
        loss.backward()
        scaler = GradScaler(init_loss_scaling=2.0,
                            decr_every_n_nan_or_inf=1)
        scaler.step(opt)                            # skipped, no raise
        assert scaler._found_inf
        assert num.guard().pending_ops() == 0       # state dropped
        opt.clear_grad()
        loss = (net(x) ** 2).mean()                 # clean step
        loss.backward()
        scaler.step(opt)                            # must NOT raise
        assert not scaler._found_inf

    def test_scaler_not_wedged_by_numerics_raise(self):
        """A NumericsError escaping optimizer.step() inside
        GradScaler.step must not leave _unscaled latched — a later step
        would silently apply still-scaled gradients."""
        from paddle_tpu.amp import GradScaler
        paddle.set_flags({'FLAGS_check_nan_inf': True,
                          'FLAGS_check_nan_inf_deferred': True})
        paddle.seed(0)
        net = nn.Linear(2, 2)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=net.parameters())
        # journal a nonfinite op OUTSIDE the grads (grads stay finite,
        # so unscale_ passes and the boundary flush raises)
        paddle.log(paddle.to_tensor([-1.0]))
        for p in net.parameters():
            p.grad = Tensor(np.ones(p.shape, np.float32))
        scaler = GradScaler(init_loss_scaling=4.0)
        with pytest.raises(num.NumericsError):
            scaler.step(opt)
        assert not scaler._unscaled          # re-armed, not wedged
        # recovery: a fresh clean step unscales normally
        for p in net.parameters():
            p.grad = Tensor(np.full(p.shape, 4.0, np.float32))
        scaler.step(opt)
        assert not scaler._found_inf

    def test_journal_cap_zero_disables_replay_not_detection(self):
        paddle.set_flags({'FLAGS_check_nan_inf': True,
                          'FLAGS_check_nan_inf_deferred': True,
                          'FLAGS_check_nan_inf_max_journal': 0})
        try:
            paddle.log(paddle.to_tensor([-1.0]))
            assert num.guard().pending_ops() == 0    # nothing pinned
            with pytest.raises(num.NumericsError):   # flag still trips
                num.flush()
        finally:
            paddle.set_flags({'FLAGS_check_nan_inf_max_journal': 4096})

    def test_journal_cap_zero_still_checked_at_optimizer_boundary(self):
        """With an empty journal (cap 0) the accumulated device flag
        must still be flushed at optimizer.step — detection cannot be
        silently disabled by the memory bound."""
        paddle.set_flags({'FLAGS_check_nan_inf': True,
                          'FLAGS_check_nan_inf_deferred': True,
                          'FLAGS_check_nan_inf_max_journal': 0})
        try:
            paddle.seed(0)
            net = nn.Linear(2, 2)
            opt = paddle.optimizer.SGD(learning_rate=0.1,
                                       parameters=net.parameters())
            x = paddle.to_tensor(np.ones((2, 2), np.float32))
            loss = paddle.log(net(x).sum() - 1e9)
            loss.backward()
            assert num.guard().pending_ops() == 0
            assert num.guard().has_pending()
            with pytest.raises(num.NumericsError) as ei:
                opt.step()
            assert ei.value.report['op'] is None    # no journal: origin
            assert 'journal window' in ei.value.report['message']
        finally:
            paddle.set_flags({'FLAGS_check_nan_inf_max_journal': 4096})

    def test_clip_inside_optimizer_step_adds_no_second_sync(self):
        """With FLAGS_tensor_stats the optimizer boundary publishes the
        pre-clip norm from its one batched sync; ClipGradByGlobalNorm
        must not publish (and sync) again inside optimizer.step."""
        from paddle_tpu.core import monitor
        paddle.set_flags({'FLAGS_tensor_stats': True})
        paddle.seed(0)
        net = nn.Linear(2, 2)
        opt = paddle.optimizer.SGD(
            learning_rate=0.1, parameters=net.parameters(),
            grad_clip=nn.ClipGradByGlobalNorm(1.0))
        for p in net.parameters():
            p.grad = Tensor(np.ones(p.shape, np.float32))
        before = monitor.metrics().get('ptpu_num_grad_norm_preclip')
        before_val = before.value(site='global_norm_clip') \
            if before is not None else None
        opt.step()
        g = monitor.metrics().get('ptpu_num_grad_norm_global')
        assert g is not None and g.value() > 0     # boundary published
        after = monitor.metrics().get('ptpu_num_grad_norm_preclip')
        after_val = after.value(site='global_norm_clip') \
            if after is not None else None
        assert after_val == before_val             # clip stayed silent

    def test_step_guard_exception_resets_instead_of_leaking(self):
        paddle.set_flags({'FLAGS_check_nan_inf': True,
                          'FLAGS_check_nan_inf_deferred': True})
        with pytest.raises(ValueError):
            with num.step_guard(step=1):
                paddle.log(paddle.to_tensor([-1.0]))   # journals a NaN
                raise ValueError('body failed')
        assert num.guard().pending_ops() == 0
        # the next clean step is not blamed for the failed one
        with num.step_guard(step=2):
            paddle.log(paddle.to_tensor([2.0]))


# ---------------------------------------------------------------------------
# divergence sentinel
# ---------------------------------------------------------------------------
class TestDivergenceSentinel:
    def test_vote_majority_and_tiebreak(self):
        s = num.DivergenceSentinel(group=object())
        fps = [np.array([1., 2., 3.]), np.array([1., 2., 3.]),
               np.array([1., 9., 3.])]
        consensus, offending = s._vote(fps)
        assert consensus == [0, 1] and offending == [2]
        # 2-rank tie breaks toward rank 0's value
        consensus, offending = s._vote([np.array([1., 2., 3.]),
                                        np.array([1., 2.5, 3.])])
        assert consensus == [0] and offending == [1]
        consensus, offending = s._vote([np.array([1., 2., 3.]),
                                        np.array([1., 2., 3.])])
        assert offending == []

    def test_shared_nan_is_agreement_not_divergence(self):
        """All ranks hitting the SAME nonfinite step is a numerics
        problem, not divergence — NaN fingerprints must vote together."""
        s = num.DivergenceSentinel(group=object())
        fp = np.array([np.nan, 2.0, 3.0])
        consensus, offending = s._vote([fp.copy() for _ in range(4)])
        assert offending == [] and consensus == [0, 1, 2, 3]

    def test_noop_without_group(self):
        s = num.DivergenceSentinel()
        assert s.check(0, grad_norm=1.0,
                       params={'w': np.ones(3, np.float32)}) is None

    def test_fingerprint_deterministic(self):
        s = num.DivergenceSentinel(group=object())
        p = {'w': np.arange(6, dtype=np.float32).reshape(2, 3),
             'b': Tensor(np.ones(2, np.float32))}
        f1 = s.fingerprint(grad_norm=0.5, params=p)
        f2 = s.fingerprint(grad_norm=0.5, params=p)
        np.testing.assert_array_equal(f1, f2)
        assert f1[0] == 0.5 and f1[1] == 17.0       # sum 0..5 + two 1s

    def test_two_rank_forced_desync(self, tmp_path):
        """ISSUE 3 acceptance: a forced 2-rank parameter desync produces
        a divergence report naming the first divergent step and the
        offending rank, on BOTH ranks, via the host-collective
        allgather."""
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
                'DIVERGENCE_DUMP_DIR': str(tmp_path),
            })
            env.pop('XLA_FLAGS', None)
            procs.append(subprocess.Popen(
                [sys.executable, '-u',
                 os.path.join(HERE, 'dist_models', 'dist_divergence.py')],
                env=env, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True))
        outs = []
        for p in procs:
            out, _ = p.communicate(timeout=120)
            outs.append(out)
        assert all(p.returncode == 0 for p in procs), outs
        reports = [f for f in os.listdir(tmp_path)
                   if f.startswith('divergence_report.rank')]
        assert len(reports) == 2, (os.listdir(tmp_path), outs)
        with open(os.path.join(tmp_path, sorted(reports)[0])) as f:
            rep = json.load(f)
        assert rep['kind'] == 'divergence_report'
        assert rep['first_divergent_step'] == 2
        assert rep['offending_ranks'] == [1]
        assert rep['world_size'] == 2
        text = num.render_divergence_report(rep)
        assert 'first divergent step: 2' in text
        assert '<-- divergent' in text


# ---------------------------------------------------------------------------
# artifact schema round trips through the CLI renderer
# ---------------------------------------------------------------------------
class TestArtifacts:
    def test_numerics_report_classify_and_render(self, tmp_path):
        sys.path.insert(0, os.path.join(os.path.dirname(HERE), 'tools'))
        import health_dump
        paddle.set_flags({'FLAGS_check_nan_inf': True})
        with pytest.raises(num.NumericsError) as ei:
            paddle.sqrt(paddle.to_tensor([-4.0]))
        rep = json.loads(json.dumps(ei.value.report))   # JSON round trip
        assert health_dump.classify(rep) == 'numerics_report'
        text = health_dump.render(rep)
        assert 'first nonfinite op: sqrt' in text
        assert 'nan=1' in text

    def test_divergence_report_via_cli_renderer(self):
        sys.path.insert(0, os.path.join(os.path.dirname(HERE), 'tools'))
        import health_dump
        rep = {'kind': 'divergence_report', 'step': 9,
               'first_divergent_step': 7, 'rank': 0, 'world_size': 4,
               'fingerprint_labels': list(num.FINGERPRINT_LABELS),
               'ranks': {str(r): [1.0, 2.0 + (r == 3), 3.0]
                         for r in range(4)},
               'offending_ranks': [3], 'consensus_ranks': [0, 1, 2]}
        rep = json.loads(json.dumps(rep))
        assert health_dump.classify(rep) == 'divergence_report'
        assert 'rank 3' in health_dump.render(rep)

    def test_step_telemetry_carries_numerics(self):
        from paddle_tpu.profiler import StepTelemetry
        snap = StepTelemetry(publish=False).snapshot()
        assert 'numerics' in snap
        assert 'grad_norm_global' in snap['numerics']
        json.dumps(snap['numerics'])


# ---------------------------------------------------------------------------
# satellites: clip + AMP
# ---------------------------------------------------------------------------
class TestClipGradNorm:
    def _param_with_grad(self, g):
        p = Tensor(np.ones_like(g), stop_gradient=False)
        p.grad = Tensor(np.asarray(g))
        return p

    def test_error_if_nonfinite_raises(self):
        p = self._param_with_grad(np.array([np.inf, 1.0], np.float32))
        with pytest.raises(RuntimeError, match='non-finite'):
            nn.clip_grad_norm_([p], max_norm=1.0, error_if_nonfinite=True)

    def test_nonfinite_tolerated_when_not_asked(self):
        p = self._param_with_grad(np.array([np.inf, 1.0], np.float32))
        total = nn.clip_grad_norm_([p], max_norm=1.0)
        assert not np.isfinite(float(total))

    def test_clip_still_scales_and_publishes_gauge(self):
        paddle.set_flags({'FLAGS_tensor_stats': True})
        p = self._param_with_grad(np.array([3.0, 4.0], np.float32))
        total = nn.clip_grad_norm_([p], max_norm=1.0,
                                   error_if_nonfinite=True)
        assert np.isclose(float(total), 5.0)
        assert np.isclose(
            float(np.linalg.norm(np.asarray(p.grad.data))), 1.0,
            rtol=1e-5)
        from paddle_tpu.core import monitor
        g = monitor.metrics().get('ptpu_num_grad_norm_preclip')
        assert g is not None
        assert np.isclose(g.value(site='clip_grad_norm_'), 5.0)

    def test_global_norm_clip_publishes_gauge(self):
        paddle.set_flags({'FLAGS_tensor_stats': True})
        clip = nn.ClipGradByGlobalNorm(clip_norm=1.0)
        p = self._param_with_grad(np.array([0.6, 0.8], np.float32))
        out = clip([(p, p.grad)])
        assert np.isclose(
            float(np.linalg.norm(np.asarray(out[0][1].data))), 1.0,
            rtol=1e-5)
        from paddle_tpu.core import monitor
        g = monitor.metrics().get('ptpu_num_grad_norm_preclip')
        assert np.isclose(g.value(site='global_norm_clip'), 1.0)


class TestGradScaler:
    def _setup(self, grads):
        paddle.seed(0)
        net = nn.Linear(2, len(grads))
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=net.parameters())
        for p, g in zip(net.parameters(), grads):
            p.grad = Tensor(np.full(p.shape, g, np.float32))
        return net, opt

    def test_unscale_single_fused_sync_and_found_inf(self):
        from paddle_tpu.amp import GradScaler
        net, opt = self._setup([1.0, np.inf])
        scaler = GradScaler(init_loss_scaling=4.0)
        scaler.unscale_(opt)
        assert scaler._found_inf
        # finite grads are unscaled by 1/scale
        finite = [p for p in net.parameters()
                  if np.isfinite(np.asarray(p.grad.data)).all()]
        assert finite and np.allclose(np.asarray(finite[0].grad.data),
                                      0.25)

    def test_skip_counts_and_scale_gauge(self):
        from paddle_tpu.amp import GradScaler
        from paddle_tpu.core import monitor
        net, opt = self._setup([np.nan, 1.0])
        scaler = GradScaler(init_loss_scaling=8.0,
                            decr_every_n_nan_or_inf=1)
        w_before = np.asarray(net.weight.data).copy()
        scaler.step(opt)
        np.testing.assert_array_equal(np.asarray(net.weight.data),
                                      w_before)       # update skipped
        assert scaler._scale == 4.0                   # backed off
        c = monitor.metrics().get('ptpu_amp_skipped_steps_total')
        assert c is not None and c.value() >= 1
        g = monitor.metrics().get('ptpu_amp_loss_scale')
        assert g.value() == 4.0

    def test_state_dict_round_trip(self):
        from paddle_tpu.amp import GradScaler
        a = GradScaler(init_loss_scaling=512.0, incr_ratio=3.0,
                       decr_ratio=0.25, incr_every_n_steps=7,
                       decr_every_n_nan_or_inf=3)
        a._good_steps, a._bad_steps = 5, 1
        a._scale = 128.0
        sd = json.loads(json.dumps(a.state_dict()))  # checkpoint-ready
        assert sd['incr_count'] == 5 and sd['decr_count'] == 1
        b = GradScaler()
        b.load_state_dict(sd)
        assert b._scale == 128.0
        assert b._incr_ratio == 3.0 and b._decr_ratio == 0.25
        assert b._incr_every_n == 7 and b._decr_every_n == 3
        assert b._good_steps == 5 and b._bad_steps == 1
        assert b.is_use_dynamic_loss_scaling()
        # the restored schedule continues where it left off
        b._found_inf = False
        for _ in range(2):
            b._update()
        assert b._good_steps == 0 and b._scale == 128.0 * 3.0

    def test_legacy_keys_still_accepted(self):
        from paddle_tpu.amp import GradScaler
        b = GradScaler()
        b.set_state_dict({'scale': 64.0, 'good_steps': 2,
                          'bad_steps': 1})
        assert b._scale == 64.0
        assert b._good_steps == 2 and b._bad_steps == 1
