"""Distributed engine tests on the 8-device virtual CPU mesh (SURVEY.md §4:
multi-controller simulation replaces the reference's 2-process NCCL
subprocess tests, strictly stronger for CI)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed import topology_runtime
from paddle_tpu.distributed.fleet.meta_parallel.hybrid_engine import (
    HybridParallelTrainStep)


def make_mlp(seed=0, mp_layers=False):
    paddle.seed(seed)
    if mp_layers:
        from paddle_tpu.distributed.fleet.meta_parallel import (
            ColumnParallelLinear, RowParallelLinear)
        from paddle_tpu.distributed.collective import new_group

        class MLP(nn.Layer):
            def __init__(self):
                super().__init__()
                g = new_group(list(range(4)), axis_name='mp')
                self.fc1 = ColumnParallelLinear(8, 16, gather_output=False,
                                                mp_group=g)
                self.fc2 = RowParallelLinear(16, 8, input_is_parallel=True,
                                             mp_group=g)
                self.out = nn.Linear(8, 1)

            def forward(self, x):
                return self.out(paddle.tanh(self.fc2(self.fc1(x))))
        return MLP()
    return nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 8),
                         nn.Tanh(), nn.Linear(8, 1))


def mse_loss_fn(model, x, y):
    return nn.functional.mse_loss(model(x), y)


BATCH = 16
RNG = np.random.RandomState(0)
X = RNG.randn(BATCH, 8).astype('float32')
Y = RNG.randn(BATCH, 1).astype('float32')


def run_steps(engine, n=5):
    losses = []
    for _ in range(n):
        losses.append(float(engine(Tensor(X), Tensor(Y))))
    return losses


def baseline_losses(seed=0, n=5, lr=0.1):
    """Single-device eager reference."""
    net = make_mlp(seed)
    opt = paddle.optimizer.SGD(learning_rate=lr,
                               parameters=net.parameters())
    losses = []
    for _ in range(n):
        loss = mse_loss_fn(net, Tensor(X), Tensor(Y))
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss))
    return losses


class TestHybridEngine:
    def test_dp_matches_single_device(self):
        """dp=8 SPMD step == single-device training on the same global
        batch (allreduce-mean of shard grads == full-batch grad)."""
        topology_runtime.build_mesh(['dp'], [8])
        net = make_mlp(0)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=net.parameters())
        eng = HybridParallelTrainStep(net, mse_loss_fn, opt)
        got = run_steps(eng)
        ref = baseline_losses(0)
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)

    def test_zero_sharding_matches_dp(self):
        """dp=2 × sharding=4 (ZeRO-1 reduce-scatter/all-gather update) must
        produce identical training to plain dp."""
        topology_runtime.build_mesh(['dp', 'sharding'], [2, 4])
        net = make_mlp(0)
        opt = paddle.optimizer.Adam(learning_rate=0.01,
                                    parameters=net.parameters())
        eng = HybridParallelTrainStep(net, mse_loss_fn, opt)
        got = run_steps(eng)

        topology_runtime.build_mesh(['dp'], [8])
        net2 = make_mlp(0)
        opt2 = paddle.optimizer.Adam(learning_rate=0.01,
                                     parameters=net2.parameters())
        eng2 = HybridParallelTrainStep(net2, mse_loss_fn, opt2)
        ref = run_steps(eng2)
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)

    def test_sharding_axis_is_data_parallel(self):
        """ZeRO ranks ARE dp ranks (dygraph_sharding_optimizer.py:27): the
        batch must be sharded over ('dp','sharding') so sharding_degree=k
        scales per-step throughput — not replicate compute k times."""
        topology_runtime.build_mesh(['dp', 'sharding'], [2, 4])
        net = make_mlp(0)
        opt = paddle.optimizer.Adam(learning_rate=0.01,
                                    parameters=net.parameters())
        eng = HybridParallelTrainStep(net, mse_loss_fn, opt)
        eng(Tensor(X), Tensor(Y))
        for spec in eng._batch_specs:
            assert spec[0] == ('dp', 'sharding'), spec
        # each device sees BATCH/(dp*sharding) rows, not BATCH/dp
        from jax.sharding import NamedSharding
        ns = NamedSharding(eng.mesh, eng._batch_specs[0])
        assert ns.shard_shape(X.shape) == (BATCH // 8, 8)

    def test_tp_matches_dense(self):
        """mp=4 TP layers (column→row with explicit collectives) match the
        dense equivalent run on one device."""
        import paddle_tpu.distributed.fleet as fleet_mod
        topology_runtime.build_mesh(['dp', 'mp'], [2, 4])
        net = make_mlp(1, mp_layers=True)
        dense = make_mlp(1)
        # copy TP weights into dense equivalent
        dense[0].weight.set_value(net.fc1.weight)
        dense[0].bias.set_value(net.fc1.bias)
        dense[2].weight.set_value(net.fc2.weight)
        dense[2].bias.set_value(net.fc2.bias)
        dense[4].weight.set_value(net.out.weight)
        dense[4].bias.set_value(net.out.bias)

        class DenseNet(nn.Layer):
            def __init__(self):
                super().__init__()
                self.seq = dense

            def forward(self, x):
                return self.seq[4](paddle.tanh(
                    nn.functional.linear(
                        nn.functional.linear(x, self.seq[0].weight,
                                             self.seq[0].bias),
                        self.seq[2].weight, self.seq[2].bias)))

        opt = paddle.optimizer.SGD(learning_rate=0.05,
                                   parameters=net.parameters())
        eng = HybridParallelTrainStep(net, mse_loss_fn, opt)
        got = run_steps(eng)

        dn = DenseNet()
        opt2 = paddle.optimizer.SGD(learning_rate=0.05,
                                    parameters=dn.parameters())
        ref = []
        for _ in range(5):
            loss = mse_loss_fn(dn, Tensor(X), Tensor(Y))
            loss.backward()
            opt2.step()
            opt2.clear_grad()
            ref.append(float(loss))
        np.testing.assert_allclose(got, ref, rtol=1e-3, atol=1e-4)


class TestSpmdPipeline:
    def _data(self, config, dp, A, mb):
        rng = np.random.RandomState(7)
        n = dp * A * mb
        ids = rng.randint(0, config.vocab_size, (n, 32)).astype('int32')
        labels = np.roll(ids, -1, axis=1).astype('int32')
        return ids, labels

    def test_pp_dp_mp_gpt_trains(self):
        """GPT-tiny on dp=2 × pp=2 × mp=2: one compiled step, loss falls."""
        from paddle_tpu.models.gpt import GPTConfig, build_gpt_pipeline
        from paddle_tpu.distributed.fleet.meta_parallel.spmd_pipeline \
            import SpmdPipelineEngine
        import paddle_tpu.distributed.fleet as fleet_mod
        from paddle_tpu.distributed.fleet.base.topology import (
            CommunicateTopology, HybridCommunicateGroup)
        import os
        os.environ['PADDLE_TRAINER_ID'] = '0'

        topology_runtime.build_mesh(['dp', 'pp', 'mp'], [2, 2, 2])
        # minimal hcg so mp_layers see mp degree 2
        topo = CommunicateTopology(["data", "pipe", "sharding", "model"],
                                   [2, 2, 1, 2])
        fleet_mod.fleet._topology = topo
        fleet_mod.fleet._hcg = HybridCommunicateGroup(topo)
        topology_runtime.build_mesh(['dp', 'pp', 'mp'], [2, 2, 2])

        paddle.seed(3)
        config = GPTConfig(vocab_size=128, hidden_size=32, num_layers=4,
                           num_heads=4, max_seq_len=64, hidden_dropout=0.0,
                           attn_dropout=0.0, use_flash_attention=False)
        embed, blocks, head = build_gpt_pipeline(config)
        opt = paddle.optimizer.Adam(learning_rate=3e-3, parameters=[])
        eng = SpmdPipelineEngine(embed, blocks, head, opt,
                                 accumulate_steps=2, use_remat=True)
        ids, labels = self._data(config, dp=2, A=2, mb=2)
        losses = []
        for _ in range(8):
            losses.append(float(eng.train_batch((Tensor(ids),
                                                 Tensor(labels)))))
        assert losses[-1] < losses[0], losses
        fleet_mod.fleet._hcg = None

    def test_pp_matches_single_stage(self):
        """pp=2 pipelined schedule == pp=1 on identical weights/data."""
        from paddle_tpu.models.gpt import GPTConfig, build_gpt_pipeline
        from paddle_tpu.distributed.fleet.meta_parallel.spmd_pipeline \
            import SpmdPipelineEngine
        import paddle_tpu.distributed.fleet as fleet_mod
        fleet_mod.fleet._hcg = None  # no mp

        config = GPTConfig(vocab_size=64, hidden_size=16, num_layers=2,
                           num_heads=2, max_seq_len=64, hidden_dropout=0.0,
                           attn_dropout=0.0, use_flash_attention=False)
        ids, labels = self._data(config, dp=1, A=2, mb=2)

        def run(pp):
            paddle.seed(11)
            topology_runtime.build_mesh(['dp', 'pp'], [1, pp])
            embed, blocks, head = build_gpt_pipeline(config)
            opt = paddle.optimizer.SGD(learning_rate=0.05, parameters=[])
            eng = SpmdPipelineEngine(embed, blocks, head, opt,
                                     accumulate_steps=2, use_remat=False)
            return [float(eng.train_batch((Tensor(ids), Tensor(labels))))
                    for _ in range(4)]

        l1 = run(1)
        l2 = run(2)
        np.testing.assert_allclose(l1, l2, rtol=2e-4, atol=1e-5)

    def test_1f1b_matches_fthenb(self):
        """1F1B schedule is loss-identical to F-then-B (section_worker.cc
        schedule_mode 1 vs 0 compute the same gradients)."""
        from paddle_tpu.models.gpt import GPTConfig, build_gpt_pipeline
        from paddle_tpu.distributed.fleet.meta_parallel.spmd_pipeline \
            import SpmdPipelineEngine
        import paddle_tpu.distributed.fleet as fleet_mod
        fleet_mod.fleet._hcg = None

        config = GPTConfig(vocab_size=64, hidden_size=16, num_layers=4,
                           num_heads=2, max_seq_len=64, hidden_dropout=0.0,
                           attn_dropout=0.0, use_flash_attention=False)
        ids, labels = self._data(config, dp=2, A=4, mb=2)

        def run(schedule):
            paddle.seed(11)
            topology_runtime.build_mesh(['dp', 'pp'], [2, 4])
            embed, blocks, head = build_gpt_pipeline(config)
            opt = paddle.optimizer.Adam(learning_rate=3e-3, parameters=[])
            eng = SpmdPipelineEngine(embed, blocks, head, opt,
                                     accumulate_steps=4, use_remat=False,
                                     schedule=schedule)
            return [float(eng.train_batch((Tensor(ids), Tensor(labels))))
                    for _ in range(4)]

        np.testing.assert_allclose(run('1F1B'), run('F-then-B'),
                                   rtol=2e-4, atol=1e-5)

    def test_1f1b_memory_bounded_by_pp_not_A(self):
        """VERDICT r1 #3 'done' criterion: compiled temp memory is O(pp)
        under 1F1B (flat as accumulate_steps grows) but O(A) under
        F-then-B (the scan-transposition path stores every tick's
        boundary activation)."""
        import jax
        import jax.numpy as jnp
        from paddle_tpu.models.gpt import GPTConfig, build_gpt_pipeline
        from paddle_tpu.distributed.fleet.meta_parallel.spmd_pipeline \
            import SpmdPipelineEngine
        import paddle_tpu.distributed.fleet as fleet_mod
        fleet_mod.fleet._hcg = None

        config = GPTConfig(vocab_size=128, hidden_size=64, num_layers=8,
                           num_heads=4, max_seq_len=64, hidden_dropout=0.0,
                           attn_dropout=0.0, use_flash_attention=False)

        def temp_bytes(schedule, A, memory_mode='stash'):
            paddle.seed(5)
            topology_runtime.build_mesh(['dp', 'pp'], [2, 4])
            embed, blocks, head = build_gpt_pipeline(config)
            opt = paddle.optimizer.SGD(learning_rate=0.01, parameters=[])
            eng = SpmdPipelineEngine(embed, blocks, head, opt,
                                     accumulate_steps=A, use_remat=True,
                                     schedule=schedule,
                                     memory_mode=memory_mode)
            rng = np.random.RandomState(0)
            ids = jnp.asarray(rng.randint(0, 128, (2 * A * 2, 32)),
                              jnp.int32)
            comp = eng._build().lower(
                eng._params, eng._states, jnp.asarray(0.01, jnp.float32),
                jnp.asarray(1.0, jnp.float32), jax.random.PRNGKey(0),
                ids, ids).compile()
            return comp.memory_analysis().temp_size_in_bytes

        one_8, one_32 = temp_bytes('1F1B', 8), temp_bytes('1F1B', 32)
        rec_32 = temp_bytes('1F1B', 32, memory_mode='recompute')
        ftb_8, ftb_32 = temp_bytes('F-then-B', 8), temp_bytes('F-then-B', 32)
        # 1F1B: flat in A (buffer is min(A, 2pp-1) slots of residuals)
        assert one_32 < 1.2 * one_8, (one_8, one_32)
        # F-then-B: grows with A
        assert ftb_32 > 1.8 * ftb_8, (ftb_8, ftb_32)
        # at large A, stash-1F1B still uses less scratch than F-then-B
        # (it buffers save-dots residuals per in-flight microbatch)...
        assert one_32 < ftb_32, (one_32, ftb_32)
        # ...and the opt-in recompute mode (stage-input buffer only) uses
        # far less
        assert rec_32 < 0.5 * ftb_32, (rec_32, ftb_32)


class TestCollectiveAPI:
    """Parity: test_collective_base.py pattern — each collective vs numpy,
    inside a shard_map region."""

    def test_allreduce_allgather_inside_spmd(self):
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from paddle_tpu.distributed import collective as C
        mesh = topology_runtime.build_mesh(['x'], [8])
        data = np.arange(32, dtype='float32').reshape(8, 4)

        def f(a):
            with C.spmd_region(('x',)):
                t = Tensor(a[0])
                C.all_reduce(t, group=C.new_group(list(range(8)),
                                                  axis_name='x'))
                return t.data[None]
        out = jax.jit(shard_map(f, mesh=mesh, in_specs=P('x'),
                                out_specs=P('x'), check_vma=False))(data)
        ref = data.sum(0)
        for row in np.asarray(out):
            np.testing.assert_allclose(row, ref)

    def test_ppermute_ring(self):
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from paddle_tpu.distributed import collective as C
        mesh = topology_runtime.build_mesh(['x'], [8])
        data = np.arange(8, dtype='float32').reshape(8, 1)

        def f(a):
            with C.spmd_region(('x',)):
                t = C.shift(Tensor(a), offset=1)
                return t.data
        out = jax.jit(shard_map(f, mesh=mesh, in_specs=P('x'),
                                out_specs=P('x'), check_vma=False))(data)
        np.testing.assert_allclose(np.asarray(out).ravel(),
                                   np.roll(np.arange(8), 1))


class TestGPTTPParity:
    def test_gpt_mp_matches_dense(self):
        """GPT forward+CE under mp∈{1,2,4} matches the dense eager model
        bit-for-bit-ish (guards the Megatron (head,3,hd) qkv packing)."""
        import os
        import paddle_tpu.distributed.fleet as fm
        from paddle_tpu.distributed.fleet.base.topology import (
            CommunicateTopology, HybridCommunicateGroup)
        from paddle_tpu.models.gpt import (GPTConfig, GPTForCausalLM,
                                           GPTPretrainingCriterion)
        from paddle_tpu.distributed.fleet.meta_parallel.hybrid_engine \
            import HybridParallelTrainStep
        os.environ.setdefault('PADDLE_TRAINER_ID', '0')

        cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                        num_heads=4, max_seq_len=32, hidden_dropout=0.0,
                        attn_dropout=0.0, use_flash_attention=False)
        rng = np.random.RandomState(0)
        ids = rng.randint(0, 128, (4, 32)).astype('int32')
        lab = np.roll(ids, -1, 1).astype('int32')

        for mp in (2, 4):
            fm.fleet._hcg = None
            paddle.seed(5)
            topo = CommunicateTopology(
                ["data", "pipe", "sharding", "model"], [1, 1, 1, mp])
            fm.fleet._topology = topo
            fm.fleet._hcg = HybridCommunicateGroup(topo)
            topology_runtime.build_mesh(['dp', 'mp'], [1, mp])
            m = GPTForCausalLM(cfg)
            crit = GPTPretrainingCriterion(cfg)
            eng = HybridParallelTrainStep(
                m, lambda mm, i, l: crit(mm(i), l),
                paddle.optimizer.SGD(learning_rate=0.0, parameters=[]))
            l_mp = float(eng(Tensor(ids), Tensor(lab)))
            fm.fleet._hcg = None
            logits = m(Tensor(ids))
            l_dense = float(nn.functional.softmax_with_cross_entropy(
                logits, Tensor(lab)).mean())
            np.testing.assert_allclose(l_mp, l_dense, rtol=1e-5)
        fm.fleet._hcg = None


class TestSequenceParallel:
    """Ring attention / Ulysses — NET-NEW vs the reference (SURVEY.md §5.7)."""

    def test_ring_attention_matches_dense(self):
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from paddle_tpu.ops import ring_attention as ra
        from paddle_tpu.ops.pallas.flash_attention import (
            _reference_attention)
        mesh = topology_runtime.build_mesh(['sp'], [8])
        rng = np.random.RandomState(0)
        B, nh, L, hd = 2, 2, 64, 8
        q = rng.randn(B, nh, L, hd).astype('float32')
        k = rng.randn(B, nh, L, hd).astype('float32')
        v = rng.randn(B, nh, L, hd).astype('float32')

        def f(q_, k_, v_):
            return ra._ring_attention_arrays(q_, k_, v_, 'sp', causal=True,
                                             sp=8)
        out = jax.jit(shard_map(f, mesh=mesh,
                                in_specs=(P(None, None, 'sp'),) * 3,
                                out_specs=P(None, None, 'sp'),
                                check_vma=False))(q, k, v)
        ref = _reference_attention(
            jnp.asarray(q).reshape(B * nh, L, hd),
            jnp.asarray(k).reshape(B * nh, L, hd),
            jnp.asarray(v).reshape(B * nh, L, hd),
            causal=True).reshape(B, nh, L, hd)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    def test_ring_attention_grads_match(self):
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from paddle_tpu.ops import ring_attention as ra
        from paddle_tpu.ops.pallas.flash_attention import (
            _reference_attention)
        mesh = topology_runtime.build_mesh(['sp'], [4])
        rng = np.random.RandomState(1)
        B, nh, L, hd = 1, 2, 32, 8
        q = rng.randn(B, nh, L, hd).astype('float32')
        k = rng.randn(B, nh, L, hd).astype('float32')
        v = rng.randn(B, nh, L, hd).astype('float32')

        def loss_ring(q_, k_, v_):
            def inner(qq, kk, vv):
                o = ra._ring_attention_arrays(qq, kk, vv, 'sp', causal=True,
                                              sp=4)
                return jnp.sum(o * o)
            f = shard_map(lambda a, b, c: jnp.array([inner(a, b, c)]),
                          mesh=mesh, in_specs=(P(None, None, 'sp'),) * 3,
                          out_specs=P('sp'), check_vma=False)
            return jnp.sum(f(q_, k_, v_))

        g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)

        def loss_ref(q_, k_, v_):
            o = _reference_attention(q_.reshape(B * nh, L, hd),
                                     k_.reshape(B * nh, L, hd),
                                     v_.reshape(B * nh, L, hd), causal=True)
            return jnp.sum(o * o)
        g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
        for a, b in zip(g_ring, g_ref):
            np.testing.assert_allclose(np.asarray(a),
                                       np.asarray(b).reshape(a.shape),
                                       rtol=5e-4, atol=5e-5)

    def test_ulysses_matches_dense(self):
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from paddle_tpu.ops import ring_attention as ra
        from paddle_tpu.ops.pallas.flash_attention import (
            _reference_attention)
        mesh = topology_runtime.build_mesh(['sp'], [4])
        rng = np.random.RandomState(2)
        B, L, nh, hd = 2, 32, 4, 8
        # (head,3,hd) packed qkv
        qkv = rng.randn(B, L, nh * 3 * hd).astype('float32')

        def f(a):
            from paddle_tpu.distributed import collective as C
            with C.spmd_region(('sp',)):
                t = ra.ulysses_attention(Tensor(a), nh, hd, axis_name='sp',
                                         sp=4)
            return t.data
        out = jax.jit(shard_map(f, mesh=mesh, in_specs=P(None, 'sp'),
                                out_specs=P(None, 'sp'),
                                check_vma=False))(qkv)
        x5 = jnp.asarray(qkv).reshape(B, L, nh, 3, hd)
        q = x5[:, :, :, 0].transpose(0, 2, 1, 3).reshape(B * nh, L, hd)
        k = x5[:, :, :, 1].transpose(0, 2, 1, 3).reshape(B * nh, L, hd)
        v = x5[:, :, :, 2].transpose(0, 2, 1, 3).reshape(B * nh, L, hd)
        ref = _reference_attention(q, k, v, causal=True)
        ref = ref.reshape(B, nh, L, hd).transpose(0, 2, 1, 3).reshape(
            B, L, nh * hd)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    def test_gpt_sequence_parallel_trains(self):
        """GPT under dp=2 × sp=4: sequence dim sharded, ring attention,
        loss matches the dense run and decreases."""
        import os
        import paddle_tpu.distributed.fleet as fm
        from paddle_tpu.models.gpt import (GPTConfig, GPTForCausalLM,
                                           GPTPretrainingCriterion)
        os.environ.setdefault('PADDLE_TRAINER_ID', '0')
        fm.fleet._hcg = None

        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_heads=4, max_seq_len=64, hidden_dropout=0.0,
                        attn_dropout=0.0, use_flash_attention=False)
        rng = np.random.RandomState(0)
        ids = rng.randint(0, 64, (4, 64)).astype('int32')
        lab = np.roll(ids, -1, 1).astype('int32')

        def run(axes, sizes, lr=0.01, steps=3):
            paddle.seed(7)
            topology_runtime.build_mesh(axes, sizes)
            m = GPTForCausalLM(cfg)
            crit = GPTPretrainingCriterion(cfg)
            opt = paddle.optimizer.Adam(learning_rate=lr, parameters=[])
            eng = HybridParallelTrainStep(
                m, lambda mm, i, l: crit(mm(i), l), opt)
            return [float(eng(Tensor(ids), Tensor(lab)))
                    for _ in range(steps)]

        sp_losses = run(['dp', 'sp'], [2, 4])
        ref_losses = run(['dp'], [2])
        np.testing.assert_allclose(sp_losses, ref_losses, rtol=2e-4)
        assert sp_losses[-1] < sp_losses[0]


class TestPipelineLayerSpmd:
    def test_pipeline_layer_train_batch(self):
        """The dygraph parity path: PipelineLayer (LayerDesc/SharedLayerDesc)
        + fleet.distributed_model + train_batch drives the SPMD engine."""
        import os
        import paddle_tpu.distributed.fleet as fm
        from paddle_tpu.distributed.fleet.base.topology import (
            CommunicateTopology, HybridCommunicateGroup)
        from paddle_tpu.distributed.fleet.meta_parallel import (
            LayerDesc, SharedLayerDesc, PipelineLayer, PipelineParallel)
        from paddle_tpu.models.gpt import (GPTConfig, GPTEmbeddings,
                                           GPTDecoderLayer, GPTLMHead)
        os.environ.setdefault('PADDLE_TRAINER_ID', '0')
        fm.fleet._hcg = None
        topo = CommunicateTopology(["data", "pipe", "sharding", "model"],
                                   [2, 2, 1, 2])
        fm.fleet._topology = topo
        fm.fleet._hcg = HybridCommunicateGroup(topo)
        topology_runtime.build_mesh(['dp', 'pp', 'mp'], [2, 2, 2])

        paddle.seed(0)
        cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=4,
                        num_heads=4, max_seq_len=64, hidden_dropout=0.0,
                        attn_dropout=0.0, use_flash_attention=False)
        head = GPTLMHead(cfg)
        descs = ([LayerDesc(GPTEmbeddings, cfg)]
                 + [LayerDesc(GPTDecoderLayer, cfg) for _ in range(4)])

        # loss_fn is a Layer (GPTLMHead: final norm + vocab head + CE) so
        # the engine lifts its params into the trainable head tree
        pipe = PipelineLayer(descs, loss_fn=head)
        # make the tail's params visible to the engine: append head desc…
        # engine treats trailing non-uniform funcs as the head tail; here
        # the tail is inside loss_fn, so funcs = embed + 4 uniform blocks
        engine_model = PipelineParallel(pipe, fm.fleet._hcg,
                                        strategy=None)
        engine_model.accumulate_steps = 2
        engine_model.micro_batch_size = 2
        opt = paddle.optimizer.Adam(learning_rate=3e-3, parameters=[])
        rng = np.random.RandomState(0)
        ids = rng.randint(0, 128, (8, 32)).astype('int32')
        labels = np.roll(ids, -1, 1).astype('int32')
        losses = [float(engine_model.train_batch(
            (Tensor(ids), Tensor(labels)), opt)) for _ in range(4)]
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0]
        fm.fleet._hcg = None


    def test_pipeline_layer_state_dict_reflects_training(self):
        """state_dict after train_batch returns TRAINED weights (the engine
        syncs back), and SharedLayerDesc reuse across segments is refused."""
        import os
        import paddle_tpu.distributed.fleet as fm
        from paddle_tpu.distributed.fleet.base.topology import (
            CommunicateTopology, HybridCommunicateGroup)
        from paddle_tpu.distributed.fleet.meta_parallel import (
            LayerDesc, SharedLayerDesc, PipelineLayer, PipelineParallel)
        from paddle_tpu.models.gpt import (GPTConfig, GPTEmbeddings,
                                           GPTDecoderLayer, GPTLMHead)
        os.environ.setdefault('PADDLE_TRAINER_ID', '0')
        fm.fleet._hcg = None
        topo = CommunicateTopology(["data", "pipe", "sharding", "model"],
                                   [1, 2, 1, 1])
        fm.fleet._topology = topo
        fm.fleet._hcg = HybridCommunicateGroup(topo)
        topology_runtime.build_mesh(['dp', 'pp'], [1, 2])
        paddle.seed(0)
        cfg = GPTConfig(vocab_size=64, hidden_size=16, num_layers=2,
                        num_heads=2, max_seq_len=32, hidden_dropout=0.0,
                        attn_dropout=0.0, use_flash_attention=False)
        pipe = PipelineLayer(
            [LayerDesc(GPTEmbeddings, cfg)]
            + [LayerDesc(GPTDecoderLayer, cfg) for _ in range(2)],
            loss_fn=GPTLMHead(cfg))
        model = PipelineParallel(pipe, fm.fleet._hcg, strategy=None)
        model.accumulate_steps = 2
        model.micro_batch_size = 2
        opt = paddle.optimizer.SGD(learning_rate=0.1, parameters=[])
        rng = np.random.RandomState(0)
        ids = rng.randint(0, 64, (4, 32)).astype('int32')
        lab = np.roll(ids, -1, 1).astype('int32')
        model.train_batch((Tensor(ids), Tensor(lab)), opt)
        sd0 = {k: v.numpy().copy() for k, v in model.state_dict().items()}
        model.train_batch((Tensor(ids), Tensor(lab)), opt)
        sd1 = model.state_dict()
        changed = sum(not np.allclose(sd0[k], sd1[k].numpy())
                      for k in sd0)
        assert changed > 0, "state_dict did not reflect training"

        # batch-size contract enforced
        try:
            model.train_batch((Tensor(ids[:3]), Tensor(lab[:3])), opt)
            assert False, "expected batch-size mismatch error"
        except ValueError as e:
            assert 'micro_batch_size' in str(e)

        # tied weights across segments refused
        pipe2 = PipelineLayer(
            [SharedLayerDesc('emb', GPTEmbeddings, config=cfg),
             LayerDesc(GPTDecoderLayer, cfg),
             LayerDesc(GPTDecoderLayer, cfg),
             SharedLayerDesc('emb', GPTEmbeddings, config=cfg)],
            loss_fn=GPTLMHead(cfg))
        m2 = PipelineParallel(pipe2, fm.fleet._hcg, strategy=None)
        m2.accumulate_steps = 2
        m2.micro_batch_size = 2
        import pytest as _pt
        with _pt.raises(NotImplementedError):
            m2.train_batch((Tensor(ids), Tensor(lab)), opt)
        fm.fleet._hcg = None


class TestPipelineGradScaler:
    """fp16 GradScaler through the SPMD pipeline engine (VERDICT r2 #10;
    parity: hybrid_parallel_gradscaler.py — found_inf psum'd inside the
    step, update skipped, dynamic scale driven by the flag)."""

    def _setup(self, pp=2):
        from paddle_tpu.models.gpt import GPTConfig, build_gpt_pipeline
        from paddle_tpu.distributed.fleet.meta_parallel.spmd_pipeline \
            import SpmdPipelineEngine
        import paddle_tpu.distributed.fleet as fleet_mod
        fleet_mod.fleet._hcg = None
        paddle.seed(5)
        config = GPTConfig(vocab_size=64, hidden_size=16, num_layers=2,
                           num_heads=2, max_seq_len=32, hidden_dropout=0.0,
                           attn_dropout=0.0, use_flash_attention=False)
        topology_runtime.build_mesh(['dp', 'pp'], [1, pp])
        embed, blocks, head = build_gpt_pipeline(config)
        opt = paddle.optimizer.SGD(learning_rate=1e-2, parameters=[])
        eng = SpmdPipelineEngine(embed, blocks, head, opt,
                                 accumulate_steps=2, use_remat=False,
                                 schedule='1F1B')
        rng = np.random.RandomState(0)
        ids = rng.randint(0, 64, (2, 32)).astype('int32')
        labels = np.roll(ids, -1, 1).astype('int32')
        return eng, (Tensor(ids), Tensor(labels))

    def test_scaled_step_matches_unscaled(self):
        eng, data = self._setup()
        l0 = float(eng.train_batch(data, scale=1024.0))
        assert not bool(np.asarray(eng.last_found_inf))
        eng2, data2 = self._setup()
        l0u = float(eng2.train_batch(data2))
        np.testing.assert_allclose(l0, l0u, rtol=1e-4)
        # second scaled step: loss decreased (update actually applied,
        # grads correctly unscaled)
        l1 = float(eng.train_batch(data, scale=1024.0))
        l1u = float(eng2.train_batch(data2))
        np.testing.assert_allclose(l1, l1u, rtol=1e-3)
        assert l1 < l0

    def test_overflow_skips_update_and_scaler_backs_off(self):
        from paddle_tpu.amp import GradScaler
        import jax.numpy as jnp
        eng, data = self._setup()
        # poison one embed param with NaN: grads go non-finite, which is
        # exactly what found_inf must catch and the update must skip
        name = next(iter(eng._params['embed']))
        eng._params['embed'][name] = (eng._params['embed'][name]
                                      * jnp.nan)
        params_before = {n: np.asarray(v)
                         for n, v in eng._params['head'].items()}
        loss = eng.train_batch(data, scale=1024.0)
        assert bool(np.asarray(eng.last_found_inf))
        for n, v in eng._params['head'].items():
            np.testing.assert_array_equal(np.asarray(v),
                                          params_before[n])
        # the scaler's dynamic schedule consumes the flag
        scaler = GradScaler(init_loss_scaling=1024.0,
                            decr_every_n_nan_or_inf=1)
        scaler._found_inf = bool(np.asarray(eng.last_found_inf))
        scaler._update()
        assert scaler._scale < 1024.0

    def test_pipeline_layer_train_batch_with_scaler(self):
        """The PipelineParallel FRONT-END drives the scaler end-to-end
        through _train_batch_spmd (the r2 NotImplementedError is gone):
        train_batch(data, optimizer, scaler=...) scales/unscales inside
        the engine and feeds the scaler's dynamic schedule."""
        from paddle_tpu.amp import GradScaler
        from paddle_tpu.models.gpt import (GPTConfig, GPTEmbeddings,
                                           GPTDecoderLayer, GPTLMHead)
        import paddle_tpu.distributed.fleet as fm
        from paddle_tpu.distributed.fleet.meta_parallel import (
            LayerDesc, PipelineLayer, PipelineParallel)
        from paddle_tpu.distributed.fleet.base.topology import (
            CommunicateTopology, HybridCommunicateGroup)
        old_hcg = fm.fleet._hcg
        try:
            topo = CommunicateTopology(
                hybrid_group_names=['data', 'pipe', 'sharding', 'model'],
                dims=[1, 2, 1, 1])
            fm.fleet._hcg = HybridCommunicateGroup(topo)
            topology_runtime.build_mesh(['dp', 'pp'], [1, 2])
            paddle.seed(6)
            config = GPTConfig(vocab_size=64, hidden_size=16,
                               num_layers=2, num_heads=2, max_seq_len=32,
                               hidden_dropout=0.0, attn_dropout=0.0,
                               use_flash_attention=False)
            head = GPTLMHead(config)
            descs = ([LayerDesc(GPTEmbeddings, config)]
                     + [LayerDesc(GPTDecoderLayer, config)
                        for _ in range(2)])
            pipe = PipelineLayer(descs, loss_fn=head)
            model = PipelineParallel(pipe, fm.fleet._hcg, strategy=None)
            model.accumulate_steps = 2
            model.micro_batch_size = 1
            opt = paddle.optimizer.SGD(learning_rate=1e-2, parameters=[])
            scaler = GradScaler(init_loss_scaling=256.0,
                                incr_every_n_steps=2)
            rng = np.random.RandomState(1)
            ids = rng.randint(0, 64, (2, 32)).astype('int32')
            labels = np.roll(ids, -1, 1).astype('int32')
            losses = [
                float(model.train_batch((Tensor(ids), Tensor(labels)),
                                        opt, scaler=scaler))
                for _ in range(3)]
            assert losses[-1] < losses[0]
            assert scaler._scale >= 256.0       # grew (no infs)
            assert not scaler._found_inf
        finally:
            fm.fleet._hcg = old_hcg
