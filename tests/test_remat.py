"""Activation economy (ISSUE 12): tuned remat policies, sequence-
parallel activation sharding, dropout-fused flash attention, and the
activation-byte census.

Equivalence bars (docs/performance.md#remat-policy):
  * remat is a pure scheduling transform — per-step LOSS is
    bit-identical under every policy on all three engines; params/grads
    agree to fp32 ulp-level XLA-reassociation noise (strict grad
    bit-equality across different XLA fusions is not a backend
    guarantee).
  * sequence-parallel LayerNorm/dropout sharding == the replicated
    route within fp32 tolerance on the 8-dev mesh (SGD trajectory).
  * the dropout-fused flash route matches the dense reference fwd+VJP
    at the same mask/seed (interpret mode on the CPU mesh).
"""
import math
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed import topology_runtime
import paddle_tpu.distributed.fleet as fm
from paddle_tpu.distributed.fleet.utils.recompute import (
    resolve_policy, boundary_counts, snapshot as remat_snapshot,
    POLICY_NAMES, checkpoint_policy)
from paddle_tpu.models.gpt import (GPTConfig, GPTForCausalLM,
                                   GPTPretrainingCriterion,
                                   build_gpt_pipeline)

TINY = dict(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
            max_seq_len=64, hidden_dropout=0.0, attn_dropout=0.0,
            use_flash_attention=False)


def _data(B=4, L=64, vocab=64, seed=0):
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, vocab, (B, L)).astype('int32')
    return ids, np.roll(ids, -1, 1).astype('int32')


def _reset_topology():
    fm.fleet._hcg = None
    fm.fleet._user_defined_strategy = None


def _mp_topology(dp, mp):
    from paddle_tpu.distributed.fleet.base.topology import (
        CommunicateTopology, HybridCommunicateGroup)
    fm.fleet._hcg = None
    topo = CommunicateTopology(["data", "pipe", "sharding", "model"],
                               [dp, 1, 1, mp])
    fm.fleet._topology = topo
    fm.fleet._hcg = HybridCommunicateGroup(topo)
    return topology_runtime.build_mesh(['dp', 'mp'], [dp, mp])


# ---------------------------------------------------------------------------
# policy resolution units (kwarg -> env -> strategy)
# ---------------------------------------------------------------------------
class TestPolicyResolution:
    def teardown_method(self):
        os.environ.pop('PTPU_REMAT_POLICY', None)
        fm.fleet._user_defined_strategy = None

    def test_kwarg_wins(self):
        os.environ['PTPU_REMAT_POLICY'] = 'dots'
        assert resolve_policy('full') == 'full'

    def test_env_beats_strategy_and_default(self):
        os.environ['PTPU_REMAT_POLICY'] = 'attn_mlp_boundaries'
        strat = fm.DistributedStrategy()
        strat.recompute = True
        strat.recompute_configs = {'policy': 'dots'}
        fm.fleet._user_defined_strategy = strat
        assert resolve_policy(None) == 'attn_mlp_boundaries'

    def test_strategy_when_recompute_on(self):
        strat = fm.DistributedStrategy()
        strat.recompute = True
        strat.recompute_configs = {'policy': 'dots'}
        fm.fleet._user_defined_strategy = strat
        assert resolve_policy(None) == 'dots'
        # strategy.recompute off -> the policy key is ignored
        strat2 = fm.DistributedStrategy()
        strat2.recompute_configs = {'policy': 'dots'}
        fm.fleet._user_defined_strategy = strat2
        assert resolve_policy(None, default='none') == 'none'

    def test_default_and_sentinel(self):
        assert resolve_policy(None, default='full') == 'full'
        assert resolve_policy(None, default=None) is None
        assert resolve_policy(True) == 'full'
        assert resolve_policy(False) == 'none'

    def test_invalid_raises(self):
        with pytest.raises(ValueError):
            resolve_policy('no_such_policy')

    def test_policy_table(self):
        for name in POLICY_NAMES:
            on, pol = checkpoint_policy(name)
            assert on == (name != 'none')


class TestFlashResiduals:
    """The flash forward rules name their own `o` and `lse` (ISSUE 35):
    outputs of a pallas_call have no name, so `save_only_these_names`
    would run the whole kernel again in the backward for them."""

    @staticmethod
    def _entries():
        from paddle_tpu.ops.pallas import flash_attention as fa
        import jax.numpy as jnp
        bias = jnp.zeros((2, 128), jnp.float32)
        return {
            'bhld': lambda q, k, v: fa.flash_attention_bhld(q, k, v),
            'biased': lambda q, k, v: fa.flash_attention(
                q, k, v, bias=bias, num_heads=1, causal=False),
            'packed': lambda q, k, v: fa.flash_attention_packed(
                q, k, v, 1, 64, causal=True),
        }

    @pytest.mark.parametrize('entry', ['bhld', 'biased', 'packed'])
    @pytest.mark.parametrize('policy,kept', [
        ('attn_mlp_boundaries', 2), ('attn_mlp_lean', 2), ('full', 0)])
    def test_saved_by_name(self, entry, policy, kept):
        import jax
        import jax.numpy as jnp
        from jax._src.ad_checkpoint import saved_residuals
        fn = self._entries()[entry]
        q = jnp.ones((2, 128, 64), jnp.float32)
        f = jax.checkpoint(lambda *a: fn(*a).sum(),
                           policy=checkpoint_policy(policy)[1])
        saved = [(aval.shape, why) for aval, why in saved_residuals(
            f, q, q, q) if not why.startswith(('from the argument',
                                               'from a constant'))]
        # o [2, 128, 64] and the logsumexp, one value a row (and head)
        assert len(saved) == kept, saved
        if kept:
            assert sorted(s for s, _ in saved) in (
                [(2, 128, 1), (2, 128, 64)], [(2, 128, 64), (2, 128, 1)])

    def test_lean_is_the_boundaries_without_attn_out(self):
        from paddle_tpu.distributed.fleet.utils.recompute import (
            BOUNDARY_NAMES, FIT_ORDER)
        assert FIT_ORDER == ('attn_mlp_boundaries', 'attn_mlp_lean', 'full')
        assert {'flash_o', 'flash_lse', 'attn_out'} <= set(BOUNDARY_NAMES)
        import jax
        import jax.numpy as jnp
        from jax._src.ad_checkpoint import saved_residuals
        from jax.ad_checkpoint import checkpoint_name

        def f(x):
            y = checkpoint_name(jnp.sin(x), 'attn_out')
            return jnp.sin(checkpoint_name(jnp.cos(y), 'mlp_fc1')).sum()
        kept = {p: len([w for _, w in saved_residuals(
            jax.checkpoint(f, policy=checkpoint_policy(p)[1]),
            jnp.ones((4,))) if not w.startswith('from the argument')])
            for p in ('attn_mlp_boundaries', 'attn_mlp_lean')}
        assert kept == {'attn_mlp_boundaries': 2, 'attn_mlp_lean': 1}


# ---------------------------------------------------------------------------
# remat ON == OFF equivalence on the three engines
# ---------------------------------------------------------------------------
POLICIES = ('none', 'full', 'attn_mlp_boundaries')


def _close_params(a, b):
    # Adam's rsqrt amplifies ulp-level grad reassociation noise where
    # second moments are near zero, so params get a slightly looser
    # bound than raw grads; the hard bar is the bit-identical loss
    for n in a:
        np.testing.assert_allclose(
            a[n], b[n], rtol=5e-4, atol=1e-5,
            err_msg=f'param {n} drifted beyond fp32 remat noise')



def _check_traj(base, got, pol):
    """Step-1 loss is computed from IDENTICAL params, so it must be
    bit-identical under remat (the pure scheduling-transform bar);
    later steps feed Adam-amplified ulp noise back through the params,
    so the trajectory gets an fp32-noise bound."""
    assert got[0][0] == base[0][0], (pol, got[0][0], base[0][0])
    np.testing.assert_allclose(base[0], got[0], rtol=1e-6,
                               err_msg=str(pol))
    _close_params(base[1], got[1])


class TestRematEquivalence:
    def _hybrid(self, policy, steps=3):
        from paddle_tpu.distributed.fleet.meta_parallel.hybrid_engine \
            import HybridParallelTrainStep
        _reset_topology()
        topology_runtime.build_mesh(['dp'], [2])
        paddle.seed(7)
        cfg = GPTConfig(**TINY)
        m = GPTForCausalLM(cfg)
        crit = GPTPretrainingCriterion(cfg)
        opt = paddle.optimizer.Adam(learning_rate=0.01, parameters=[])
        eng = HybridParallelTrainStep(
            m, lambda mm, i, l: crit(mm(i), l), opt, remat_policy=policy)
        ids, lab = _data()
        losses = [float(eng(Tensor(ids), Tensor(lab)))
                  for _ in range(steps)]
        params = {n: np.asarray(v) for n, v in eng.params.items()}
        eng.shutdown()
        return losses, params

    def test_hybrid_loss_bit_identity(self):
        base = self._hybrid('none')
        for pol in ('full', 'attn_mlp_boundaries'):
            got = self._hybrid(pol)
            _check_traj(base, got, pol)

    def test_trainstep_loss_bit_identity(self):
        from paddle_tpu.jit import TrainStep

        def run(policy):
            _reset_topology()
            topology_runtime.build_mesh(['dp'], [1])
            paddle.seed(7)
            cfg = GPTConfig(**TINY)
            m = GPTForCausalLM(cfg)
            crit = GPTPretrainingCriterion(cfg)
            opt = paddle.optimizer.Adam(learning_rate=0.01,
                                        parameters=m.parameters())
            ts = TrainStep(m, lambda mm, i, l: crit(mm(i), l), opt,
                           remat_policy=policy)
            ids, lab = _data()
            losses = [float(ts(Tensor(ids), Tensor(lab)))
                      for _ in range(3)]
            return losses, {n: np.asarray(v)
                            for n, v in ts._params.items()}

        base = run('none')
        for pol in ('full', 'attn_mlp_boundaries'):
            got = run(pol)
            _check_traj(base, got, pol)

    def test_pipeline_loss_bit_identity(self):
        from paddle_tpu.distributed.fleet.meta_parallel.spmd_pipeline \
            import SpmdPipelineEngine

        def run(policy):
            _reset_topology()
            topology_runtime.build_mesh(['dp', 'pp'], [1, 1])
            paddle.seed(7)
            cfg = GPTConfig(**TINY)
            embed, blocks, head = build_gpt_pipeline(cfg)
            opt = paddle.optimizer.Adam(learning_rate=0.01, parameters=[])
            eng = SpmdPipelineEngine(embed, blocks, head, opt,
                                     accumulate_steps=2,
                                     use_remat=policy != 'none',
                                     remat_policy=policy)
            ids, lab = _data()
            losses = [float(eng.train_batch((Tensor(ids), Tensor(lab))))
                      for _ in range(3)]
            params = {f'{g}/{n}': np.asarray(v)
                      for g in ('embed', 'blocks', 'head')
                      for n, v in eng._params[g].items()}
            eng.shutdown()
            return losses, params

        base = run('none')
        for pol in ('full', 'attn_mlp_boundaries'):
            got = run(pol)
            _check_traj(base, got, pol)

    def test_boundary_tags_counted(self):
        before = dict(boundary_counts())
        self._hybrid('attn_mlp_boundaries', steps=1)
        after = boundary_counts()
        for tag in ('attn_qkv', 'attn_ctx', 'attn_out', 'mlp_fc1',
                    'mlp_out', 'embed_out'):
            assert after.get(tag, 0) > before.get(tag, 0), (tag, after)
        snap = remat_snapshot()
        assert snap and snap['policies'].get('hybrid') == \
            'attn_mlp_boundaries'
        assert snap['boundary_total'] >= sum(before.values())


# ---------------------------------------------------------------------------
# taps invariant: the PR-3 per-param stat boundaries survive remat
# ---------------------------------------------------------------------------
class TestTapsUnderRemat:
    def test_same_tap_tree_and_values(self):
        from paddle_tpu.core import flags
        from paddle_tpu.distributed.fleet.meta_parallel.hybrid_engine \
            import HybridParallelTrainStep
        flags.set_flags({'FLAGS_tensor_stats': True})
        try:
            def run(policy):
                _reset_topology()
                topology_runtime.build_mesh(['dp'], [2])
                paddle.seed(7)
                cfg = GPTConfig(**TINY)
                m = GPTForCausalLM(cfg)
                crit = GPTPretrainingCriterion(cfg)
                opt = paddle.optimizer.Adam(learning_rate=0.01,
                                            parameters=[])
                eng = HybridParallelTrainStep(
                    m, lambda mm, i, l: crit(mm(i), l), opt,
                    remat_policy=policy)
                ids, lab = _data()
                eng(Tensor(ids), Tensor(lab))
                num = eng.last_numerics
                eng.shutdown()
                return num
            base = run('none')
            remat = run('attn_mlp_boundaries')
            assert base is not None and remat is not None
            # same per-param boundaries ...
            assert set(base['grads']) == set(remat['grads'])
            assert set(base['params']) == set(remat['params'])
            # ... and the same statistics up to remat fp32 noise
            np.testing.assert_allclose(
                base['grad_norm'], remat['grad_norm'], rtol=1e-5)
            for n in base['grads']:
                np.testing.assert_allclose(
                    base['grads'][n].rms, remat['grads'][n].rms,
                    rtol=1e-4, atol=1e-9, err_msg=n)
        finally:
            flags.set_flags({'FLAGS_tensor_stats': None})


# ---------------------------------------------------------------------------
# activation-byte census: attn_mlp_boundaries shrinks the compiled
# step's resident temp bytes (CPU dryrun acceptance)
# ---------------------------------------------------------------------------
class TestActivationCensus:
    def _temp_bytes(self, policy):
        from paddle_tpu.core import memory as mem
        from paddle_tpu.distributed.fleet.meta_parallel.hybrid_engine \
            import HybridParallelTrainStep
        mem.reset()
        _reset_topology()
        topology_runtime.build_mesh(['dp'], [1])
        paddle.seed(7)
        cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=4,
                        num_heads=4, max_seq_len=128, hidden_dropout=0.0,
                        attn_dropout=0.0, use_flash_attention=False)
        m = GPTForCausalLM(cfg)
        crit = GPTPretrainingCriterion(cfg)
        opt = paddle.optimizer.Adam(learning_rate=0.01, parameters=[])
        eng = HybridParallelTrainStep(
            m, lambda mm, i, l: crit(mm(i), l), opt, remat_policy=policy)
        ids, lab = _data(B=8, L=128, vocab=128)
        loss = float(eng(Tensor(ids), Tensor(lab)))
        acts = mem.activation_bytes()
        sample = mem.sample()
        eng.shutdown()
        assert np.isfinite(loss)
        assert sample['activation_bytes'] == acts
        return acts['hybrid.step']

    def test_census_drop_under_boundary_policy(self):
        dense = self._temp_bytes('none')
        tuned = self._temp_bytes('attn_mlp_boundaries')
        assert tuned < dense, (tuned, dense)

    def test_gauge_published(self):
        from paddle_tpu.core import monitor
        self._temp_bytes('none')
        g = monitor.metrics().get('ptpu_mem_activation_bytes')
        assert g is not None
        sites = {labels[0] for labels in g._series()}
        assert 'hybrid.step' in sites


# ---------------------------------------------------------------------------
# sequence-parallel activation sharding == replicated (8-dev mesh)
# ---------------------------------------------------------------------------
class TestSequenceParallel:
    def _run(self, seqp, opt_name='sgd', dropout=0.0, steps=3, seed=7):
        from paddle_tpu.distributed.fleet.meta_parallel.hybrid_engine \
            import HybridParallelTrainStep
        _mp_topology(2, 4)
        paddle.seed(seed)
        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_heads=4, max_seq_len=64,
                        hidden_dropout=dropout, attn_dropout=0.0,
                        use_flash_attention=False)
        m = GPTForCausalLM(cfg)
        crit = GPTPretrainingCriterion(cfg)
        opt = (paddle.optimizer.SGD(learning_rate=0.5, parameters=[])
               if opt_name == 'sgd'
               else paddle.optimizer.Adam(learning_rate=0.01,
                                          parameters=[]))
        eng = HybridParallelTrainStep(
            m, lambda mm, i, l: crit(mm(i), l), opt,
            sequence_parallel=seqp)
        assert eng._seq_parallel == bool(seqp)
        ids, lab = _data()
        losses = [float(eng(Tensor(ids), Tensor(lab)))
                  for _ in range(steps)]
        params = {n: np.asarray(v) for n, v in eng.params.items()}
        eng.shutdown()
        return losses, params

    def test_sharded_equals_replicated_sgd(self):
        """The headline acceptance bar: SGD (scale-sensitive — no Adam
        normalization masking) trajectory with the LayerNorm/dropout/
        residual segments sequence-scattered over mp matches the
        replicated route to fp32 noise."""
        base = self._run(False)
        got = self._run(True)
        np.testing.assert_allclose(base[0], got[0], rtol=1e-6)
        for n in base[1]:
            np.testing.assert_allclose(
                base[1][n], got[1][n], rtol=1e-4, atol=1e-6,
                err_msg=f'param {n}')

    def test_sharded_equals_replicated_adam(self):
        base = self._run(False, opt_name='adam')
        got = self._run(True, opt_name='adam')
        np.testing.assert_allclose(base[0], got[0], rtol=1e-5)

    def test_dropout_deterministic_and_trains(self):
        """With dropout on, each token's mask is drawn by its owner
        rank (same stream, local shapes) — not mask-identical to the
        replicated route, but deterministic across runs and a valid
        dropout trajectory."""
        a = self._run(True, dropout=0.1)
        b = self._run(True, dropout=0.1)
        assert a[0] == b[0]
        assert np.isfinite(a[0]).all()

    def test_resolution_and_gating(self):
        from paddle_tpu.distributed import collective as C
        os.environ['PTPU_SEQUENCE_PARALLEL'] = '1'
        try:
            assert C.resolve_sequence_parallel(None) is True
            assert C.resolve_sequence_parallel(False) is False
        finally:
            del os.environ['PTPU_SEQUENCE_PARALLEL']
        strat = fm.DistributedStrategy()
        strat.tensor_parallel_configs = {'sequence_parallel': True}
        fm.fleet._user_defined_strategy = strat
        try:
            assert C.resolve_sequence_parallel(None) is True
        finally:
            fm.fleet._user_defined_strategy = None
        # no mp axis -> the knob is inert (engine gates on mp > 1)
        from paddle_tpu.distributed.fleet.meta_parallel.hybrid_engine \
            import HybridParallelTrainStep
        _reset_topology()
        topology_runtime.build_mesh(['dp'], [2])
        paddle.seed(0)
        cfg = GPTConfig(**TINY)
        m = GPTForCausalLM(cfg)
        crit = GPTPretrainingCriterion(cfg)
        opt = paddle.optimizer.SGD(learning_rate=0.1, parameters=[])
        eng = HybridParallelTrainStep(
            m, lambda mm, i, l: crit(mm(i), l), opt,
            sequence_parallel=True)
        assert eng._seq_parallel is False
        eng.shutdown()


# ---------------------------------------------------------------------------
# dropout-fused flash attention (interpret mode)
# ---------------------------------------------------------------------------
class TestFlashDropout:
    B, nh, L, hd = 2, 2, 128, 64
    rate = 0.1

    def _qkv_mask(self, seed=3):
        rs = np.random.RandomState(0)
        q = jnp.asarray(rs.randn(self.B * self.nh, self.L, self.hd),
                        jnp.float32)
        k = jnp.asarray(rs.randn(self.B * self.nh, self.L, self.hd),
                        jnp.float32)
        v = jnp.asarray(rs.randn(self.B * self.nh, self.L, self.hd),
                        jnp.float32)
        keep = jax.random.bernoulli(
            jax.random.key(seed), 1.0 - self.rate,
            (self.B, self.nh, self.L, self.L))
        return q, k, v, keep

    def _dense(self, q, k, v, keep):
        s = jnp.einsum('bqd,bkd->bqk', q, k,
                       preferred_element_type=jnp.float32) \
            / math.sqrt(self.hd)
        causal = jnp.tril(jnp.ones((self.L, self.L), bool))
        s = jnp.where(causal, s, -1e9)
        p = jax.nn.softmax(s, axis=-1)
        kp = keep.reshape(self.B * self.nh, self.L, self.L)
        p = jnp.where(kp, p / (1.0 - self.rate), 0.0)
        return jnp.einsum('bqk,bkd->bqd', p, v)

    def test_fwd_and_vjp_match_dense_same_mask(self):
        from paddle_tpu.ops.pallas import flash_attention as fa
        q, k, v, keep = self._qkv_mask()
        mask8 = keep.reshape(self.B * self.nh, self.L,
                             self.L).astype(jnp.int8)
        o_ref = self._dense(q, k, v, keep)
        o_fl = jax.jit(lambda q, k, v: fa._flash_attn_dropout(
            self.rate, q, k, v, mask8))(q, k, v)
        np.testing.assert_allclose(np.asarray(o_ref), np.asarray(o_fl),
                                   rtol=1e-5, atol=1e-5)

        g_ref = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(self._dense(q, k, v, keep) ** 2),
            argnums=(0, 1, 2)))(q, k, v)
        g_fl = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(fa._flash_attn_dropout(
                self.rate, q, k, v, mask8) ** 2),
            argnums=(0, 1, 2)))(q, k, v)
        for name, a, b in zip('qkv', g_ref, g_fl):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4,
                err_msg=f'd{name}')

    def test_route_counters_and_errors(self):
        from paddle_tpu.ops.pallas import flash_attention as fa
        from paddle_tpu.ops.pallas import scaffold
        before = scaffold.routes_snapshot().get(
            'flash_dropout', {'kernel': 0})['kernel']
        qkv = Tensor(jnp.zeros((1, 64, 4 * 3 * 16), jnp.float32))
        fa.causal_attention(qkv, 4, 16, dropout=0.1,
                            dropout_key=jax.random.key(0))
        after = scaffold.routes_snapshot()['flash_dropout']['kernel']
        assert after == before + 1
        # clear errors only when no route exists
        with pytest.raises(ValueError, match='dropout_key'):
            fa.causal_attention(qkv, 4, 16, dropout=0.1)
        with pytest.raises(ValueError, match='rate'):
            fa.causal_attention(qkv, 4, 16, dropout=1.5,
                                dropout_key=jax.random.key(0))

    def test_gpt_attention_same_seed_matches_dense(self):
        """End to end: the model-level flash-dropout route (the dense
        fallback for attention_dropout > 0 is GONE) vs the dense
        reference config at the same RNG-stream point."""
        from paddle_tpu.models.gpt import GPTAttention
        _reset_topology()
        rs = np.random.RandomState(0)
        x = rs.randn(1, 512, 64).astype(np.float32)

        def run(use_flash):
            cfg = GPTConfig(vocab_size=64, hidden_size=64, num_layers=2,
                            num_heads=1, max_seq_len=512,
                            attn_dropout=0.2,
                            use_flash_attention=use_flash)
            paddle.seed(11)
            att = GPTAttention(cfg)
            att.train()
            paddle.seed(42)
            return np.asarray(att(Tensor(jnp.asarray(x))).data)

        np.testing.assert_allclose(run(True), run(False),
                                   rtol=1e-5, atol=1e-6)

    def test_eval_and_zero_dropout_keep_packed_route(self):
        from paddle_tpu.ops.pallas import flash_attention as fa
        from paddle_tpu.ops.pallas import scaffold
        qkv = Tensor(jnp.zeros((1, 64, 4 * 3 * 16), jnp.float32))
        before = scaffold.routes_snapshot().get(
            'flash_attention', {'kernel': 0})['kernel']
        fa.causal_attention(qkv, 4, 16, dropout=0.0)
        after = scaffold.routes_snapshot()['flash_attention']['kernel']
        assert after == before + 1
