"""Gradient bucketing + sharded weight update (ISSUE 4).

Covers: layout-map round-trip (param -> bucket/offset -> param), padding
correctness, mixed-dtype bucket separation, size-cap splitting, the
bucketed TrainStep / hybrid-engine / pipeline equivalence on the virtual
mesh, fp32 bit-level sharded-vs-replicated equivalence on a true 2-rank
mesh (subprocess), GradScaler.unscale_ / clip_grad_norm_ on flat buckets
with sync-count assertions, ptpu_comm_* gauges, and the persistent
compilation cache.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402

import paddle_tpu as paddle                                 # noqa: E402
from paddle_tpu import nn                                   # noqa: E402
from paddle_tpu.core import bucketing as B                  # noqa: E402
from paddle_tpu.core.tensor import Tensor                   # noqa: E402


class TestBucketLayout:
    def _shapes(self):
        return {
            'a': ((4, 3), jnp.float32),
            'b': ((7,), jnp.float32),
            'c': ((2, 2, 2), jnp.bfloat16),
            'd': ((5,), jnp.float32),
            'e': ((3,), jnp.bfloat16),
        }

    def test_roundtrip_param_bucket_param(self):
        layout = B.BucketLayout.build(self._shapes(), pad_to=4)
        rng = np.random.RandomState(0)
        tree = {n: jnp.asarray(rng.randn(*shp).astype('float32'),
                               dtype=dt)
                for n, (shp, dt) in self._shapes().items()}
        flats = layout.flatten(tree)
        back = layout.unflatten(flats)
        assert set(back) == set(tree)
        for n in tree:
            assert back[n].shape == tree[n].shape
            assert back[n].dtype == tree[n].dtype
            np.testing.assert_array_equal(np.asarray(back[n]),
                                          np.asarray(tree[n]))

    def test_layout_map_is_stable_and_explicit(self):
        layout = B.BucketLayout.build(self._shapes(), pad_to=4)
        desc = layout.describe()
        json.dumps(desc)   # JSON-ready
        # the map: every param knows (bucket, offset, size); offsets are
        # contiguous in insertion order within a bucket
        for b in desc['buckets']:
            off = 0
            for s in b['slots']:
                assert s['offset'] == off, s
                off += s['size']
            assert b['used'] == off
            assert b['size'] >= b['used'] and b['size'] % 4 == 0

    def test_padding_is_zero_and_dropped(self):
        layout = B.BucketLayout.build({'w': ((3,), jnp.float32)},
                                      pad_to=8)
        (flat,) = layout.flatten({'w': jnp.ones((3,), jnp.float32)})
        assert flat.shape == (8,)
        np.testing.assert_array_equal(np.asarray(flat[3:]), 0.0)
        back = layout.unflatten([flat])
        assert back['w'].shape == (3,)

    def test_mixed_dtype_buckets_separate(self):
        layout = B.BucketLayout.build(self._shapes(), pad_to=1)
        for b in layout.buckets:
            assert len({s.dtype for s in b.slots}) == 1
            assert all(s.dtype == b.dtype for s in b.slots)
        # fp32 params share one bucket, bf16 params another
        assert len(layout.buckets) == 2

    def test_size_cap_splits_buckets(self):
        shapes = {f'p{i}': ((256,), jnp.float32) for i in range(8)}
        layout = B.BucketLayout.build(shapes, bucket_bytes=1024, pad_to=1)
        # 256 fp32 = 1024 bytes: one param per bucket
        assert len(layout.buckets) == 8
        # a single param bigger than the cap still gets a bucket
        layout2 = B.BucketLayout.build({'big': ((4096,), jnp.float32)},
                                       bucket_bytes=1024)
        assert len(layout2.buckets) == 1

    def test_group_fn_separates(self):
        layout = B.BucketLayout.build(
            {'x/a': ((4,), jnp.float32), 'y/b': ((4,), jnp.float32)},
            group_fn=lambda n, s, d: n.split('/')[0])
        assert len(layout.buckets) == 2

    def test_flat_state_conversion_roundtrip(self):
        layout = B.BucketLayout.build(self._shapes(), pad_to=4)
        rng = np.random.RandomState(1)
        flat_states = []
        for b in layout.buckets:
            flat_states.append({
                'moment1': rng.randn(b.size).astype(np.float32),
                'beta1_pow': np.float32(0.9),
            })
        named = B.flat_states_to_named(layout, flat_states)
        assert set(named) == set(self._shapes())
        for n, (shp, _) in self._shapes().items():
            assert named[n]['moment1'].shape == shp
            assert named[n]['beta1_pow'] == np.float32(0.9)
        back = B.named_states_to_flat(layout, named, flat_states)
        for st, st0, b in zip(back, flat_states, layout.buckets):
            # real-slot region round-trips exactly; padding untouched
            np.testing.assert_array_equal(st['moment1'][:b.used],
                                          st0['moment1'][:b.used])

    def test_elementwise_classification(self):
        assert B.elementwise(paddle.optimizer.Adam(parameters=[]))
        assert B.elementwise(paddle.optimizer.SGD(parameters=[]))
        assert not B.elementwise(paddle.optimizer.Lamb(parameters=[]))
        assert not B.elementwise(paddle.optimizer.Lars(parameters=[]))


class TestCommGauges:
    def test_publish_and_snapshot(self):
        # the bf16-training shape the acceptance bar targets: bf16
        # params, bf16 wire, fp32-accuracy reduction
        layout = B.BucketLayout.build(
            {'w': ((1024,), jnp.bfloat16), 'v': ((1024,), jnp.bfloat16)},
            pad_to=8)
        B.publish_comm_gauges(layout, engine='testeng', n_shards=8,
                              comm_dtype=jnp.bfloat16, enabled=True)
        snap = B.comm_snapshot()
        assert snap['ptpu_comm_buckets']['engine=testeng'] == 1
        rs = snap['ptpu_comm_bytes_per_step'][
            'engine=testeng,op=reduce_scatter']
        ag = snap['ptpu_comm_bytes_per_step'][
            'engine=testeng,op=all_gather']
        assert rs == 2048 * 2              # bf16 wire
        assert ag == 2048 * 2              # params gather in their dtype
        base = snap['ptpu_comm_modeled_bytes_per_step'][
            'engine=testeng,scheme=per_param_psum_fp32']
        new = snap['ptpu_comm_modeled_bytes_per_step'][
            'engine=testeng,scheme=bucketed']
        assert base == 2 * 2048 * 4
        assert new == rs + ag
        drop = snap['comm_bytes_drop_vs_per_param_psum']['testeng']
        assert drop >= 0.40, drop          # the ISSUE 4 acceptance bar
        assert snap['ptpu_comm_enabled']['engine=testeng'] == 1
        assert snap['ptpu_comm_compressed_fraction'][
            'engine=testeng'] == 0.5


class TestInt8Wire:
    """ISSUE 7: block-scaled int8 quantization helpers and the real
    wire-byte accounting (payload vs scale vs pad)."""

    def test_block_len_divides(self):
        assert B.block_len(592, 256) == 148    # 592 = 4 * 148
        assert B.block_len(1024, 256) == 256
        assert B.block_len(296, 32) == 8
        assert B.block_len(7, 256) == 7
        for n, want in ((592, 256), (1024, 256), (296, 32), (11, 4)):
            b = B.block_len(n, want)
            assert n % b == 0 and b <= max(want, 1)

    def test_quantize_blocks_roundtrip_bound(self):
        rng = np.random.RandomState(0)
        flat = jnp.asarray((rng.randn(1024) * 3).astype('float32'))
        q, s = B.quantize_blocks(flat, 128)
        assert q.dtype == jnp.int8 and s.shape == (8,)
        back = np.asarray(B.dequantize_blocks(q, s, 128))
        # per-block bound: half a bin of that block's abs-max scale
        err = np.abs(back - np.asarray(flat)).reshape(8, 128).max(1)
        bound = np.asarray(s) / 2 + 1e-7
        assert (err <= bound).all(), (err, bound)

    def test_int8_gauges_payload_factor_and_breakdown(self):
        # deliberately pad-heavy layout so the pad accounting shows
        layout = B.BucketLayout.build(
            {'w': ((1000,), jnp.float32), 'v': ((500,), jnp.float32)},
            pad_to=64)
        B.publish_comm_gauges(layout, engine='int8eng', n_shards=8,
                              comm_dtype='int8', enabled=True,
                              block=256)
        snap = B.comm_snapshot()
        elems, padded = 1500, layout.total_padded()
        rs = snap['ptpu_comm_bytes_per_step'][
            'engine=int8eng,op=reduce_scatter']
        ag = snap['ptpu_comm_bytes_per_step'][
            'engine=int8eng,op=all_gather']
        wb = snap['comm_wire_breakdown']['int8eng']
        # payload: 1 byte/elem on BOTH legs; overhead carries the fp32
        # block scales and the zero-padding
        assert wb['payload_bytes'] == 2 * elems
        assert wb['pad_bytes'] == 2 * (padded - elems)
        assert wb['scale_bytes'] > 0
        assert wb['total_bytes'] == rs + ag
        # the ISSUE-7 acceptance bar: >= 4x payload drop vs the fp32
        # per-param psum (2x payload ring convention), overhead visible
        factor = snap['comm_payload_factor_vs_per_param_psum'][
            'int8eng']
        assert factor >= 4.0, factor
        assert snap['comm_bytes_drop_vs_per_param_psum'][
            'int8eng'] >= 0.70
        assert snap['ptpu_comm_block_elements']['engine=int8eng'] > 0
        assert snap['ptpu_comm_compressed_fraction'][
            'engine=int8eng'] == 0.75

    def test_wire_bytes_bf16_matches_legacy_model(self):
        layout = B.BucketLayout.build(
            {'w': ((2048,), jnp.bfloat16)}, pad_to=8)
        wires = B.wire_bytes(layout, 8, jnp.bfloat16)
        assert wires['reduce_scatter']['total'] == 2048 * 2
        assert wires['all_gather']['total'] == 2048 * 2
        assert wires['reduce_scatter']['scale'] == 0

    def test_force_master_overrides_multi_precision_off(self):
        # int8 comm NEEDS the sharded fp32 master even when the
        # optimizer opts out of multi_precision: without it the
        # int8-rounded gathered params would BE the optimizer state
        # and wire rounding would compound into the trajectory
        layout = B.BucketLayout.build({'w': ((64,), jnp.float32)},
                                      pad_to=8)
        opt = paddle.optimizer.Adam(learning_rate=0.01)
        opt._multi_precision = False
        st = B.init_bucket_state(opt, layout.buckets[0],
                                 np.zeros(layout.buckets[0].size,
                                          np.float32),
                                 force_master=True)
        assert 'master' in st
        # and fp32 buckets without the int8 wire still skip it
        st2 = B.init_bucket_state(opt, layout.buckets[0],
                                  np.zeros(layout.buckets[0].size,
                                           np.float32))
        assert 'master' not in st2

    def test_effective_block_gauge_honest(self):
        # shard_len 16 has no divisor of 256 above 16 — the gauge must
        # report the EFFECTIVE block (16), not the requested 256
        layout = B.BucketLayout.build({'w': ((120,), jnp.float32)},
                                      pad_to=16)   # size 128, 8 shards
        B.publish_comm_gauges(layout, engine='blkeng', n_shards=8,
                              comm_dtype='int8', enabled=True,
                              block=256)
        snap = B.comm_snapshot()
        assert snap['ptpu_comm_block_elements']['engine=blkeng'] == 16


def _mesh(axes, sizes):
    from paddle_tpu.distributed import topology_runtime
    return topology_runtime.build_mesh(axes, sizes)


class TestEngineEquivalence:
    """In-process equivalence on the 8-virtual-device mesh (the true
    2-rank bit-level check runs in the subprocess test below)."""

    def _data(self):
        rng = np.random.RandomState(0)
        return (Tensor(rng.rand(16, 8).astype('float32')),
                Tensor(rng.rand(16, 1).astype('float32')))

    def _run_hybrid(self, use_buckets, comm_dtype=None, opt_name='adamw',
                    steps=4):
        from paddle_tpu.distributed.fleet.meta_parallel.hybrid_engine \
            import HybridParallelTrainStep
        _mesh(['dp', 'sharding'], [2, 4])
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(8, 16), nn.Tanh(),
                            nn.Linear(16, 1))
        if opt_name == 'adamw':
            opt = paddle.optimizer.AdamW(learning_rate=0.01,
                                         weight_decay=0.01,
                                         parameters=net.parameters())
        else:
            opt = paddle.optimizer.Momentum(learning_rate=0.05,
                                            parameters=net.parameters())
        eng = HybridParallelTrainStep(net, lambda m, x, y: nn.functional
                                      .mse_loss(m(x), y), opt,
                                      use_buckets=use_buckets,
                                      comm_dtype=comm_dtype)
        X, Y = self._data()
        losses = [float(eng(X, Y)) for _ in range(steps)]
        return losses, eng

    def test_hybrid_bucketed_matches_legacy(self):
        for opt_name in ('adamw', 'momentum'):
            got, eng = self._run_hybrid(True, opt_name=opt_name)
            assert eng._bucketed
            ref, _ = self._run_hybrid(False, opt_name=opt_name)
            np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)

    def test_hybrid_bf16_comm_within_tolerance(self):
        got, eng = self._run_hybrid(True, comm_dtype='bfloat16')
        assert eng.comm_dtype == jnp.bfloat16
        ref, _ = self._run_hybrid(False)
        np.testing.assert_allclose(got, ref, rtol=5e-2, atol=1e-3)

    def test_hybrid_lamb_keeps_per_param_path(self):
        from paddle_tpu.distributed.fleet.meta_parallel.hybrid_engine \
            import HybridParallelTrainStep
        _mesh(['dp'], [8])
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(8, 4), nn.Tanh(), nn.Linear(4, 1))
        opt = paddle.optimizer.Lamb(learning_rate=0.01,
                                    parameters=net.parameters())
        eng = HybridParallelTrainStep(
            net, lambda m, x, y: nn.functional.mse_loss(m(x), y), opt)
        assert not eng._bucketed
        X, Y = self._data()
        assert np.isfinite(float(eng(X, Y)))

    def test_hybrid_checkpoint_crosses_layouts(self):
        """A bucketed engine's checkpoint restores into a legacy engine
        (and back): the state_dict schema stays per-parameter."""
        got, eng = self._run_hybrid(True)
        sd = eng.state_dict()
        ref, eng_legacy = self._run_hybrid(False)
        sd_legacy = eng_legacy.state_dict()
        assert set(sd['states']) == set(sd_legacy['states'])
        for n in sd['states']:
            assert set(sd['states'][n]) == set(sd_legacy['states'][n])
            np.testing.assert_allclose(
                sd['states'][n]['moment1'],
                sd_legacy['states'][n]['moment1'], rtol=1e-4, atol=1e-6)
        # legacy checkpoint -> bucketed engine reproduces the next loss
        _, eng2 = self._run_hybrid(True, steps=1)
        eng2.set_state_dict(sd_legacy)
        X, Y = self._data()
        l_next_legacy = float(eng_legacy(X, Y))
        l_next = float(eng2(X, Y))
        np.testing.assert_allclose(l_next, l_next_legacy, rtol=1e-5)

    def test_trainstep_bucketed_matches_legacy(self):
        from paddle_tpu.jit import TrainStep
        rng = np.random.RandomState(0)
        x = paddle.to_tensor(rng.rand(8, 8).astype('float32'))
        y = paddle.to_tensor(rng.randint(0, 2, (8,)).astype('int64'))

        def run(use_buckets):
            paddle.seed(0)
            net = nn.Sequential(nn.Linear(8, 16), nn.Tanh(),
                                nn.Linear(16, 2))
            opt = paddle.optimizer.Adam(learning_rate=0.01,
                                        parameters=net.parameters())
            step = TrainStep(net, lambda m, a, b: nn.functional
                             .cross_entropy(m(a), b), opt,
                             use_buckets=use_buckets)
            return [float(step(x, y)) for _ in range(4)], step
        got, st = run(True)
        assert st._use_buckets and st._layout is not None
        ref, st2 = run(False)
        assert not st2._use_buckets
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-7)

    def test_pipeline_bucketed_matches_legacy(self):
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

        def run(use_buckets):
            _mesh(['dp', 'pp'], [dp, 4])
            paddle.seed(0)
            embed, blocks, head = build_gpt_pipeline(cfg)
            opt = paddle.optimizer.AdamW(learning_rate=0.01,
                                         weight_decay=0.01,
                                         parameters=[])
            eng = SpmdPipelineEngine(embed, blocks, head, opt,
                                     accumulate_steps=A, use_remat=False,
                                     use_buckets=use_buckets)
            out = [float(eng.train_batch((Tensor(ids), Tensor(lab))))
                   for _ in range(3)]
            eng.shutdown()
            return out
        got = run(True)
        ref = run(False)
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=1e-5)


class TestTwoRankSubprocess:
    def test_sharded_vs_replicated_bit_level(self):
        """ISSUE 4 acceptance: on a true 2-rank mesh the bucketed
        sharded update is bit-identical (fp32) to the replicated one,
        and the bf16 compressed wire stays within tolerance."""
        script = os.path.join(os.path.dirname(__file__), 'dist_models',
                              'dist_bucket_equiv.py')
        env = dict(os.environ)
        env.pop('XLA_FLAGS', None)   # script pins its own device count
        # base leg only: the overlap leg runs from tests/test_overlap.py
        p = subprocess.run([sys.executable, '-u', script,
                            '--leg', 'base'], env=env,
                           capture_output=True, text=True, timeout=600)
        assert p.returncode == 0, (p.stdout or '') + (p.stderr or '')
        assert 'OK: sharded==replicated' in p.stdout


class TestBucketedAmpAndClip:
    def _net_with_grads(self, grads):
        paddle.seed(0)
        net = nn.Linear(2, len(grads))
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=net.parameters())
        for p, g in zip(net.parameters(), grads):
            p.grad = Tensor(np.full(p.shape, g, np.float32))
        return net, opt

    def test_unscale_one_fused_sync(self, monkeypatch):
        """unscale_ must flatten grads into buckets and read found_inf
        with ONE host sync (routed through the numerics fetch hook)."""
        from paddle_tpu.core import numerics as num
        from paddle_tpu.amp import GradScaler
        net, opt = self._net_with_grads([1.0, 2.0])
        scaler = GradScaler(init_loss_scaling=4.0)
        calls = []
        real = num._host_fetch
        monkeypatch.setattr(num, '_host_fetch',
                            lambda tree: (calls.append(1) or real(tree)))
        scaler.unscale_(opt)
        assert len(calls) == 1
        assert not scaler._found_inf
        for p, g in zip(net.parameters(), [1.0, 2.0]):
            np.testing.assert_allclose(np.asarray(p.grad.data),
                                       np.full(p.shape, g / 4.0),
                                       rtol=1e-6)

    def test_unscale_found_inf_on_buckets(self):
        from paddle_tpu.amp import GradScaler
        net, opt = self._net_with_grads([1.0, np.inf])
        scaler = GradScaler(init_loss_scaling=4.0)
        scaler.unscale_(opt)
        assert scaler._found_inf
        finite = [p for p in net.parameters()
                  if np.isfinite(np.asarray(p.grad.data)).all()]
        assert finite and np.allclose(np.asarray(finite[0].grad.data),
                                      0.25)

    def test_clip_grad_norm_bucketed_single_reduction(self, monkeypatch):
        """clip_grad_norm_ computes the global norm over flat buckets;
        with error_if_nonfinite its one host sync routes through the
        numerics fetch hook (and the PR-3 publish dedup still holds)."""
        from paddle_tpu.core import numerics as num
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 2))
        x = paddle.to_tensor(np.random.RandomState(0)
                             .rand(4, 4).astype('float32'))
        loss = net(x).sum()
        loss.backward()
        params = [p for p in net.parameters() if p.grad is not None]
        ref = np.sqrt(sum(
            float(jnp.sum(p.grad.data.astype(jnp.float32) ** 2))
            for p in params))
        calls = []
        real = num._host_fetch
        monkeypatch.setattr(num, '_host_fetch',
                            lambda tree: (calls.append(1) or real(tree)))
        total = nn.clip_grad_norm_(params, max_norm=0.5,
                                   error_if_nonfinite=True)
        assert len(calls) == 1
        np.testing.assert_allclose(float(total), ref, rtol=1e-5)
        got = np.sqrt(sum(
            float(jnp.sum(p.grad.data.astype(jnp.float32) ** 2))
            for p in params))
        np.testing.assert_allclose(got, min(ref, 0.5), rtol=1e-5)

    def test_clip_grad_norm_nonfinite_raises(self):
        net, _ = self._net_with_grads([np.nan, 1.0])
        with pytest.raises(RuntimeError, match='non-finite'):
            nn.clip_grad_norm_(list(net.parameters()), max_norm=1.0,
                               error_if_nonfinite=True)

    def test_clip_grad_norm_inf_norm(self):
        net, _ = self._net_with_grads([3.0, -7.0])
        total = nn.clip_grad_norm_(list(net.parameters()),
                                   max_norm=100.0,
                                   norm_type=float('inf'))
        np.testing.assert_allclose(float(total), 7.0, rtol=1e-6)


class TestCompileCache:
    _CODE = r'''
import json, os, sys
os.environ['JAX_PLATFORMS'] = 'cpu'
sys.path.insert(0, %(root)r)
import paddle_tpu
from paddle_tpu.core import compile_cache
import jax, jax.numpy as jnp
f = jax.jit(lambda x: (x * 3 + jnp.sin(x)).sum())
f(jnp.arange(1717, dtype=jnp.float32)).block_until_ready()
print('SNAP:' + json.dumps(compile_cache.snapshot()))
'''
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def _run(self, env):
        p = subprocess.run(
            [sys.executable, '-c', self._CODE % {'root': self._ROOT}],
            env=env, capture_output=True, text=True, timeout=300)
        assert p.returncode == 0, (p.stdout or '') + (p.stderr or '')
        line = [l for l in p.stdout.splitlines()
                if l.startswith('SNAP:')][-1]
        return json.loads(line[len('SNAP:'):])

    def test_persistent_cache_hits_and_gauges(self, tmp_path):
        """JAX_COMPILATION_CACHE_DIR places the cache: compile_cache
        leaves the directory as JAX read it, every file lands under it,
        and the second compile of the same program in a fresh process
        hits it and bumps the ptpu_compile_cache_* gauges."""
        env = dict(os.environ)
        env['JAX_COMPILATION_CACHE_DIR'] = str(tmp_path)
        env['JAX_ENABLE_COMPILATION_CACHE'] = 'true'
        env['JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS'] = '0'
        env['JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES'] = '-1'
        first = self._run(env)
        assert first['enabled'] and first['dir'] == str(tmp_path)
        assert first['requests'] >= 1
        assert any(tmp_path.iterdir())
        second = self._run(env)
        assert second['hits'] >= 1, second
        assert second['seconds_saved'] >= 0.0
        assert second['misses'] == second['requests'] - second['hits']

    def test_unset_env_uses_fixed_in_checkout_path(self, monkeypatch):
        """No JAX_COMPILATION_CACHE_DIR: one fixed path inside the
        checkout — never a temp name, pid or time."""
        import jax
        from paddle_tpu.core import compile_cache
        prior = jax.config.jax_compilation_cache_dir
        monkeypatch.delenv('JAX_COMPILATION_CACHE_DIR', raising=False)
        try:
            compile_cache.install()
            assert compile_cache.cache_dir() == os.path.join(
                self._ROOT, '.jax_cache')
        finally:
            jax.config.update('jax_compilation_cache_dir', prior)
