"""Communication/compute overlap (ISSUE 10, docs/performance.md#comm-overlap).

Covers the overlap building blocks in core/bucketing.py (layer-grouped
buckets, knob resolution, chunked collectives, exposed/hidden comm
gauges), the engines' deferred/prefetched param all-gather (hybrid
in-process on the virtual mesh; true 2-rank bit-level + census memory
assertions via the dist_models subprocess), the dp=1 no-op invariant
(nothing to overlap => compiled program unchanged), and the XLA
latency-hiding flag plumbing in core/flags.py.
"""
import os
import subprocess
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.core import bucketing as B
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed import topology_runtime


def _mesh(axes, sizes):
    return topology_runtime.build_mesh(axes, sizes)


class TestOverlapConfig:
    def test_layer_group_fn(self):
        assert B.layer_group_fn('gpt.decoder.layers.3.w') == 'layer00003'
        assert B.layer_group_fn('blocks.11.attn.q.weight') == \
            'layer00011'
        assert B.layer_group_fn('embedding.weight') == 'stem'
        assert B.layer_group_fn('head.bias') == 'stem'
        # zero-padded keys sort in layer order
        assert B.layer_group_fn('l.2.w') < B.layer_group_fn('l.10.w')

    def test_grouped_layout_buckets_in_layer_order(self):
        layout = B.BucketLayout.build(
            {'emb.w': ((4, 4), 'float32'),
             'l.0.w': ((8, 4), 'float32'),
             'l.0.b': ((4,), 'float32'),
             'l.1.w': ((8, 4), 'float32'),
             'head.w': ((4,), 'float32')},
            group_fn=B.layer_group_fn, pad_to=8)
        groups = [b.group for b in layout.buckets]
        assert groups == ['stem', 'layer00000', 'layer00001']
        # stem bucket stays open and takes the head too
        stem = layout.buckets[0]
        assert {s.name for s in stem.slots} == {'emb.w', 'head.w'}
        # describe() carries the group key (layout contract)
        desc = layout.describe()
        assert [b['group'] for b in desc['buckets']] == groups

    def test_resolution_order(self, monkeypatch):
        monkeypatch.delenv('PTPU_COMM_OVERLAP', raising=False)
        monkeypatch.delenv('PTPU_COMM_PREFETCH', raising=False)
        monkeypatch.delenv('PTPU_COMM_CHUNK', raising=False)
        assert B.resolve_overlap_config() == (
            False, B.DEFAULT_PREFETCH_DEPTH, 0)
        monkeypatch.setenv('PTPU_COMM_OVERLAP', '1')
        monkeypatch.setenv('PTPU_COMM_PREFETCH', '3')
        monkeypatch.setenv('PTPU_COMM_CHUNK', '512')
        assert B.resolve_overlap_config() == (True, 3, 512)
        # kwargs beat env
        assert B.resolve_overlap_config(overlap=False, prefetch=1,
                                        chunk=64) == (False, 1, 64)

    def test_falsy_env_overrides_strategy(self, monkeypatch):
        """PTPU_COMM_CHUNK=0 must be able to switch OFF chunking a
        fleet strategy enabled — a present env var wins even when its
        value is falsy."""
        from paddle_tpu.distributed.fleet import fleet as fleet_mod
        from paddle_tpu.distributed.fleet.base.distributed_strategy \
            import DistributedStrategy
        strat = DistributedStrategy()
        strat.sharding_configs = {'comm_overlap': True,
                                  'comm_overlap_prefetch': 4,
                                  'comm_chunk': 4096}
        saved = fleet_mod._user_defined_strategy
        monkeypatch.setattr(fleet_mod, '_user_defined_strategy', strat)
        monkeypatch.delenv('PTPU_COMM_OVERLAP', raising=False)
        monkeypatch.delenv('PTPU_COMM_PREFETCH', raising=False)
        monkeypatch.delenv('PTPU_COMM_CHUNK', raising=False)
        assert B.resolve_overlap_config() == (True, 4, 4096)
        monkeypatch.setenv('PTPU_COMM_CHUNK', '0')
        monkeypatch.setenv('PTPU_COMM_OVERLAP', '0')
        overlap, _, chunk = B.resolve_overlap_config()
        assert overlap is False and chunk == 0
        assert fleet_mod._user_defined_strategy is strat
        monkeypatch.setattr(fleet_mod, '_user_defined_strategy', saved)


class TestChunkedCollectives:
    def test_chunk_spans(self):
        assert B._chunk_spans(64, 2, 0) is None
        assert B._chunk_spans(8, 2, 32) is None      # already fits
        spans = B._chunk_spans(64, 2, 32)            # width 16
        assert spans == [(0, 16), (16, 16), (32, 16), (48, 16)]
        # ragged tail
        assert B._chunk_spans(10, 2, 8)[-1] == (8, 2)

    def test_chunked_rs_ag_bit_exact(self):
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        mesh = _mesh(['dp'], [8])
        rng = np.random.RandomState(0)
        flat = jnp.asarray(rng.randn(8, 64), jnp.float32)

        def mk(chunk):
            def body(x):
                x = x[0]
                sh = B.reduce_scatter(x, ('dp',), 8, mean=True,
                                      chunk=chunk)
                full = B.all_gather(sh, ('dp',), chunk=chunk,
                                    n_shards=8)
                return sh[None], full[None]
            return shard_map(body, mesh=mesh, in_specs=P('dp'),
                             out_specs=(P('dp'), P('dp')),
                             check_vma=False)

        base_sh, base_full = mk(None)(flat)
        for chunk in (16, 24):
            sh, full = mk(chunk)(flat)
            assert np.array_equal(np.asarray(sh), np.asarray(base_sh))
            assert np.array_equal(np.asarray(full),
                                  np.asarray(base_full))


class TestOverlapGauges:
    def _layout(self):
        return B.BucketLayout.build(
            {'l.0.w': ((64, 4), 'float32'),
             'l.1.w': ((64, 4), 'float32'),
             'head.w': ((16,), 'float32')},
            group_fn=B.layer_group_fn, pad_to=8)

    def test_snapshot_exposed_lt_total_when_enabled(self):
        layout = self._layout()
        B.publish_overlap_gauges(layout, engine='ov_t', n_shards=2,
                                 enabled=True, prefetch=2, chunk=128)
        co = B.comm_snapshot()['comm_overlap']['ov_t']
        assert co['enabled'] and co['groups'] == 3
        assert co['groups_in_flight'] == 2
        assert co['chunk_elements'] == 128
        assert 0 < co['exposed_comm_seconds'] < co['total_comm_seconds']
        assert co['hidden_comm_seconds'] == pytest.approx(
            co['total_comm_seconds'] - co['exposed_comm_seconds'],
            abs=1e-12)

    def test_snapshot_disabled_everything_exposed(self):
        layout = self._layout()
        B.publish_overlap_gauges(layout, engine='ov_off', n_shards=2,
                                 enabled=False)
        co = B.comm_snapshot()['comm_overlap']['ov_off']
        assert not co['enabled'] and co['groups_in_flight'] == 0
        assert co['exposed_comm_seconds'] == co['total_comm_seconds']
        assert co['hidden_comm_seconds'] == 0


class TestHybridOverlap:
    def _data(self):
        rng = np.random.RandomState(0)
        return (Tensor(rng.rand(16, 8).astype('float32')),
                Tensor(rng.rand(16, 1).astype('float32')))

    def _run(self, steps=4, **kw):
        from paddle_tpu.distributed.fleet.meta_parallel.hybrid_engine \
            import HybridParallelTrainStep
        _mesh(['dp', 'sharding'], [2, 4])
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(8, 16), nn.Tanh(),
                            nn.Linear(16, 1))
        opt = paddle.optimizer.AdamW(learning_rate=0.01,
                                     weight_decay=0.01,
                                     parameters=net.parameters())
        eng = HybridParallelTrainStep(
            net, lambda m, x, y: nn.functional.mse_loss(m(x), y), opt,
            **kw)
        X, Y = self._data()
        losses = [float(eng(X, Y)) for _ in range(steps)]
        return losses, eng

    def test_overlap_bit_identical_and_sharded_resident_set(self):
        from paddle_tpu.core import memory as M
        ref, ref_eng = self._run(use_buckets=True)
        got, eng = self._run(use_buckets=True, comm_overlap=True)
        assert eng._overlap and not ref_eng._overlap
        assert got == ref
        sd, ref_sd = eng.state_dict(), ref_eng.state_dict()
        for n in ref_sd['params']:
            assert np.array_equal(sd['params'][n], ref_sd['params'][n])
        # deferred gather: bucketed params live as 1/n flat shards, so
        # the engine's resident param set occupies fewer device bytes
        # than the barrier engine's full replicas (census-measured)
        def pbytes(e):
            return (sum(M.device_nbytes(a) for a in e._params.values())
                    + sum(M.device_nbytes(a)
                          for a in getattr(e, '_param_shards', [])
                          or []))
        assert pbytes(eng) < pbytes(ref_eng)

    def test_overlap_chunked_bit_identical(self):
        ref, _ = self._run(use_buckets=True)
        got, eng = self._run(use_buckets=True, comm_overlap=True,
                             comm_chunk=32)
        assert eng._comm_chunk == 32 and got == ref

    def test_checkpoint_crosses_overlap_layouts(self):
        ref, ref_eng = self._run(use_buckets=True)
        sd = ref_eng.state_dict()
        _, eng = self._run(steps=1, use_buckets=True, comm_overlap=True)
        eng.set_state_dict(sd)
        X, Y = self._data()
        assert float(eng(X, Y)) == float(ref_eng(X, Y))

    def test_dp1_nothing_to_overlap_is_noop(self):
        from paddle_tpu.distributed.fleet.meta_parallel.hybrid_engine \
            import HybridParallelTrainStep
        _mesh(['dp'], [1])
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(8, 16), nn.Tanh(),
                            nn.Linear(16, 1))
        opt = paddle.optimizer.Adam(learning_rate=0.01,
                                    parameters=net.parameters())
        eng = HybridParallelTrainStep(
            net, lambda m, x, y: nn.functional.mse_loss(m(x), y), opt,
            comm_overlap=True)
        # no comm to overlap: knob must not change the engine shape
        assert not eng._overlap and not eng._param_shards
        X, Y = self._data()
        assert np.isfinite(float(eng(X, Y)))


class TestTrainStepOverlapNoop:
    def test_program_unchanged(self):
        """jit.TrainStep has no collectives (n_shards=1): comm_overlap
        on must leave losses bit-identical and buckets ungrouped."""
        from paddle_tpu.jit import TrainStep
        rng = np.random.RandomState(0)
        x = paddle.to_tensor(rng.rand(8, 8).astype('float32'))
        y = paddle.to_tensor(rng.randint(0, 2, (8,)).astype('int64'))

        def run(**kw):
            paddle.seed(0)
            net = nn.Sequential(nn.Linear(8, 16), nn.Tanh(),
                                nn.Linear(16, 2))
            opt = paddle.optimizer.Adam(learning_rate=0.01,
                                        parameters=net.parameters())
            step = TrainStep(net, lambda m, a, b: nn.functional
                             .cross_entropy(m(a), b), opt, **kw)
            return [float(step(x, y)) for _ in range(3)], step
        ref, _ = run()
        got, st = run(comm_overlap=True)
        assert got == ref
        assert all(b.group is None for b in st._layout.buckets)


class TestXlaFlagPlumbing:
    ENV = 'LIBTPU_INIT_ARGS'

    def test_set_flags_edits_libtpu_init_args(self, monkeypatch):
        """The xla_tpu_* tokens travel in LIBTPU_INIT_ARGS and never in
        XLA_FLAGS (jaxlib aborts on an unknown XLA_FLAGS token, in this
        process and in every child that inherits the env)."""
        from paddle_tpu.core import flags
        saved = flags.get_flags(['FLAGS_xla_latency_hiding_scheduler',
                                 'FLAGS_xla_async_collectives'])
        monkeypatch.setenv(self.ENV, '--keep_me=1')
        xla_before = os.environ.get('XLA_FLAGS')
        try:
            flags.set_flags({'FLAGS_xla_latency_hiding_scheduler': True})
            assert '--xla_tpu_enable_latency_hiding_scheduler=true' \
                in os.environ[self.ENV]
            flags.set_flags(
                {'FLAGS_xla_latency_hiding_scheduler': False})
            env = os.environ[self.ENV]
            assert '--xla_tpu_enable_latency_hiding_scheduler=false' \
                in env
            assert env.count('xla_tpu_enable_latency_hiding_scheduler')\
                == 1
            assert '--keep_me=1' in env
            assert os.environ.get('XLA_FLAGS') == xla_before
        finally:
            flags.set_flags(saved)

    def test_import_time_overlap_env_export(self, monkeypatch):
        """PTPU_COMM_OVERLAP=1 is honored at flags-module import —
        the only point early enough to reach libtpu's one-shot
        LIBTPU_INIT_ARGS read (engine builds always run after init)."""
        import importlib.util
        monkeypatch.setenv('PTPU_COMM_OVERLAP', '1')
        monkeypatch.setenv(self.ENV, '')

        def load(name):
            path = os.path.join(os.path.dirname(__file__), '..',
                                'paddle_tpu', 'core', 'flags.py')
            spec = importlib.util.spec_from_file_location(name, path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod

        mod = load('ptpu_flags_isolated')
        assert mod.flag('FLAGS_xla_latency_hiding_scheduler') is True
        assert mod.flag('FLAGS_xla_async_collectives') is True
        assert '--xla_tpu_enable_latency_hiding_scheduler=true' in \
            os.environ[self.ENV]
        # an explicit FLAGS_xla_* env pin beats the overlap default
        monkeypatch.setenv('FLAGS_xla_latency_hiding_scheduler', '0')
        monkeypatch.setenv(self.ENV, '')
        mod2 = load('ptpu_flags_isolated2')
        assert mod2.flag('FLAGS_xla_latency_hiding_scheduler') is False
        assert '--xla_tpu_enable_latency_hiding_scheduler=false' in \
            os.environ[self.ENV]

    def test_ensure_overlap_flags_respects_user_pin(self, monkeypatch):
        from paddle_tpu.core import flags
        monkeypatch.setenv(self.ENV, '')
        saved = flags.get_flags(['FLAGS_xla_latency_hiding_scheduler',
                                 'FLAGS_xla_async_collectives'])
        try:
            flags.set_flags(
                {'FLAGS_xla_latency_hiding_scheduler': False,
                 'FLAGS_xla_async_collectives': None})
            B.ensure_overlap_xla_flags()
            got = flags.get_flags(
                ['FLAGS_xla_latency_hiding_scheduler',
                 'FLAGS_xla_async_collectives'])
            # pinned False survives; unset flips on
            assert got['FLAGS_xla_latency_hiding_scheduler'] is False
            assert got['FLAGS_xla_async_collectives'] is True
        finally:
            flags.set_flags(saved)


class TestCensusDeviceBytes:
    def test_replicated_vs_sharded_device_bytes(self):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from paddle_tpu.core import memory as M
        mesh = _mesh(['dp'], [8])
        arr = jnp.zeros((64, 4), jnp.float32)
        repl = jax.device_put(arr, NamedSharding(mesh, P()))
        shrd = jax.device_put(arr, NamedSharding(mesh, P('dp')))
        assert M.device_nbytes(repl) == 8 * arr.nbytes
        assert M.device_nbytes(shrd) == arr.nbytes
        sample = M.accountant().sample(count_buffers=True)
        assert sample['live_device_bytes'] >= sample['live_bytes']


class TestTwoRankOverlapSubprocess:
    def test_overlap_equals_barrier_bit_level(self):
        """ISSUE 10 acceptance: true 2-rank overlap==barrier BIT-level
        fp32 (chunked too), bf16/int8 overlap wires within tolerance,
        deferred-gather resident param bytes below the barrier path's
        (census-measured), exposed-comm < total-comm in the model."""
        script = os.path.join(os.path.dirname(__file__), 'dist_models',
                              'dist_bucket_equiv.py')
        env = dict(os.environ)
        env.pop('XLA_FLAGS', None)   # script pins its own device count
        p = subprocess.run([sys.executable, '-u', script,
                            '--leg', 'overlap'], env=env,
                           capture_output=True, text=True, timeout=600)
        assert p.returncode == 0, (p.stdout or '') + (p.stderr or '')
        assert 'OK: overlap==barrier' in p.stdout


@pytest.mark.slow
class TestPipelineOverlapSlow:
    def test_pipeline_overlap_bit_identical(self):
        """dp2 x pp4 pipeline: overlap (deferred gather over 'dp') is
        bit-identical to the barrier bucketed path, including a
        loss-scaled (GradScaler) step."""
        from paddle_tpu.models.gpt import GPTConfig, build_gpt_pipeline
        from paddle_tpu.distributed.fleet.meta_parallel.spmd_pipeline \
            import SpmdPipelineEngine
        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=4,
                        num_heads=4, max_seq_len=32, hidden_dropout=0.0,
                        attn_dropout=0.0, use_flash_attention=False)
        rng = np.random.RandomState(0)
        A, mb, dp = 2, 2, 2
        ids = rng.randint(0, 64, (dp * A * mb, 32)).astype('int32')
        lab = np.roll(ids, -1, 1).astype('int32')

        def run(**kw):
            _mesh(['dp', 'pp'], [dp, 4])
            paddle.seed(0)
            embed, blocks, head = build_gpt_pipeline(cfg)
            opt = paddle.optimizer.AdamW(learning_rate=0.01,
                                         weight_decay=0.01,
                                         parameters=[])
            eng = SpmdPipelineEngine(embed, blocks, head, opt,
                                     accumulate_steps=A,
                                     use_remat=False, **kw)
            data = (Tensor(ids), Tensor(lab))
            out = [float(eng.train_batch(data)) for _ in range(2)]
            out.append(float(eng.train_batch(data, scale=1024.0)))
            eng.sync_model()
            params = {n: np.asarray(jax.device_get(p.data))
                      for layer in ([embed, head] + blocks)
                      for n, p in layer.named_parameters()}
            eng.shutdown()
            return out, params

        ref, ref_p = run(use_buckets=True)
        got, got_p = run(use_buckets=True, comm_overlap=True)
        assert got == ref
        for n in ref_p:
            assert np.array_equal(got_p[n], ref_p[n]), n
