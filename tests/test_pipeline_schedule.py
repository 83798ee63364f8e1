"""Interleaved virtual-stage pipeline schedule (ISSUE 14;
arXiv:2104.04473, docs/performance.md#pipeline-schedules).

Covers: round-robin chunk partitioning + uneven-layer/accumulate-step
rejection, virtual-stage knob resolution (kwarg / PTPU_PP_VIRTUAL /
PipelineLayer(num_virtual_pipeline_stages=)), v=2 == v=1 equivalence on
the 8-device mesh (pp2 and dp2xpp2, stash + recompute memory modes,
GradScaler found-inf path, remat-policy composition, sync_model
cross-restore v2<->v1), the static bubble model + ptpu_pp_* census, the
named batch-validation errors, and a true 2-rank subprocess leg.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed import topology_runtime
from paddle_tpu.distributed.fleet.meta_parallel.spmd_pipeline import (
    SpmdPipelineEngine, PipelineScheduleError, PipelineBatchError,
    chunk_layer_order, schedule_model, publish_schedule_gauges,
    pipeline_snapshot, resolve_virtual_stages, engine_from_pipeline_layer)
from paddle_tpu.models.gpt import GPTConfig, build_gpt_pipeline

TINY = dict(vocab_size=64, hidden_size=16, num_layers=4, num_heads=2,
            max_seq_len=32, hidden_dropout=0.0, attn_dropout=0.0,
            use_flash_attention=False)


def _reset():
    import paddle_tpu.distributed.fleet as fleet_mod
    fleet_mod.fleet._hcg = None


def _data(n, vocab=64, seq=32, seed=7):
    ids = np.random.RandomState(seed).randint(
        0, vocab, (n, seq)).astype('int32')
    return ids, np.roll(ids, -1, 1).astype('int32')


def _build(schedule='1F1B', v=None, memory_mode='stash', dp=1, pp=2,
           A=4, opt_name='adam', num_layers=4, use_remat=False,
           remat_policy=None, seed=11):
    _reset()
    paddle.seed(seed)
    topology_runtime.build_mesh(['dp', 'pp'], [dp, pp])
    cfg = GPTConfig(**{**TINY, 'num_layers': num_layers})
    embed, blocks, head = build_gpt_pipeline(cfg)
    opt = (paddle.optimizer.Adam(learning_rate=3e-3, parameters=[])
           if opt_name == 'adam'
           else paddle.optimizer.SGD(learning_rate=0.05, parameters=[]))
    eng = SpmdPipelineEngine(embed, blocks, head, opt,
                             accumulate_steps=A, use_remat=use_remat,
                             schedule=schedule, virtual_stages=v,
                             memory_mode=memory_mode,
                             remat_policy=remat_policy)
    return eng, blocks


def _run(steps=3, scale=None, **kw):
    """Train, sync back, return (losses, per-LAYER param dict) — the
    layer-indexed view is stacking-order independent, so it compares
    across schedules."""
    eng, blocks = _build(**kw)
    dp, A = kw.get('dp', 1), kw.get('A', 4)
    ids, labels = _data(dp * A * 2)
    losses = [float(eng.train_batch((Tensor(ids), Tensor(labels)),
                                    scale=scale))
              for _ in range(steps)]
    eng.sync_model()
    params = {f'{i}/{n}': np.asarray(p.data)
              for i, b in enumerate(blocks)
              for n, p in b.named_parameters()}
    for n, p in eng.embed.named_parameters():
        params[f'embed/{n}'] = np.asarray(p.data)
    for n, p in eng.head.named_parameters():
        params[f'head/{n}'] = np.asarray(p.data)
    eng.shutdown()
    return losses, params


def _assert_bit_identical(a, b, what=''):
    la, pa = a
    lb, pb = b
    assert la == lb, f'{what} losses differ: {la} vs {lb}'
    for k in pa:
        np.testing.assert_array_equal(pa[k], pb[k],
                                      err_msg=f'{what} param {k}')


class TestChunkPartition:
    def test_round_robin_assignment(self):
        # L=8, pp=2, v=2: chunks g=c*pp+s -> stage 0 holds layers
        # [0,1] (chunk 0) + [4,5] (chunk 2), stage 1 holds [2,3]+[6,7]
        assert chunk_layer_order(8, 2, 2) == [0, 1, 4, 5, 2, 3, 6, 7]
        assert chunk_layer_order(8, 4, 2) == [0, 4, 1, 5, 2, 6, 3, 7]
        # v=1 is the identity (existing schedules unchanged)
        assert chunk_layer_order(8, 4, 1) == list(range(8))
        # a permutation: every layer exactly once
        assert sorted(chunk_layer_order(12, 2, 3)) == list(range(12))

    def test_uneven_layers_rejected(self):
        with pytest.raises(PipelineScheduleError, match='round-robin'):
            chunk_layer_order(6, 2, 2)
        with pytest.raises(PipelineScheduleError, match='non-empty'):
            _build(schedule='interleaved', v=2, num_layers=2, pp=2)

    def test_accumulate_steps_must_divide_pp(self):
        # microbatches advance in groups of pp per chunk
        with pytest.raises(PipelineScheduleError,
                           match='accumulate_steps'):
            _build(schedule='interleaved', v=2, A=3, pp=2)

    def test_fthenb_refuses_virtual_stages(self):
        with pytest.raises(PipelineScheduleError, match='F-then-B'):
            _build(schedule='F-then-B', v=2)

    def test_1f1b_auto_upgrades_to_interleaved(self):
        eng, _ = _build(schedule='1F1B', v=2)
        assert eng.schedule == 'interleaved' and eng.vp == 2
        eng.shutdown()

    def test_env_resolution(self, monkeypatch):
        monkeypatch.setenv('PTPU_PP_VIRTUAL', '2')
        assert resolve_virtual_stages() == 2
        # kwarg wins over env
        assert resolve_virtual_stages(1) == 1
        eng, _ = _build(schedule='1F1B')
        assert eng.schedule == 'interleaved' and eng.vp == 2
        eng.shutdown()
        monkeypatch.setenv('PTPU_PP_VIRTUAL', 'nope')
        with pytest.raises(PipelineScheduleError, match='PTPU_PP_VIRTUAL'):
            resolve_virtual_stages()

    def test_pipeline_layer_wiring(self):
        """PipelineLayer(num_virtual_pipeline_stages=) reaches the
        engine (it was accepted-and-dropped before ISSUE 14)."""
        from paddle_tpu.distributed.fleet.meta_parallel import (
            LayerDesc, PipelineLayer)
        from paddle_tpu.models.gpt import (GPTEmbeddings, GPTDecoderLayer,
                                           GPTLMHead)
        _reset()
        topology_runtime.build_mesh(['dp', 'pp'], [1, 2])
        paddle.seed(0)
        cfg = GPTConfig(**TINY)
        pipe = PipelineLayer(
            [LayerDesc(GPTEmbeddings, cfg)]
            + [LayerDesc(GPTDecoderLayer, cfg) for _ in range(4)],
            loss_fn=GPTLMHead(cfg), num_virtual_pipeline_stages=2)
        assert pipe._num_virtual_pipeline_stages == 2
        opt = paddle.optimizer.SGD(learning_rate=0.05, parameters=[])
        eng = engine_from_pipeline_layer(pipe, opt, accumulate_steps=4)
        assert eng.schedule == 'interleaved' and eng.vp == 2
        eng.shutdown()
        # a value the block run cannot honor names the knob loudly
        pipe3 = PipelineLayer(
            [LayerDesc(GPTEmbeddings, cfg)]
            + [LayerDesc(GPTDecoderLayer, cfg) for _ in range(4)],
            loss_fn=GPTLMHead(cfg), num_virtual_pipeline_stages=3)
        with pytest.raises(PipelineScheduleError, match='chunks'):
            engine_from_pipeline_layer(pipe3, opt, accumulate_steps=4)
        with pytest.raises(ValueError, match='>= 1'):
            PipelineLayer([LayerDesc(GPTDecoderLayer, cfg)],
                          loss_fn=GPTLMHead(cfg),
                          num_virtual_pipeline_stages=0)


class TestBatchValidation:
    def test_batch_not_divisible_named_error(self):
        eng, _ = _build(A=4)
        ids, labels = _data(7)
        with pytest.raises(PipelineBatchError, match='accumulate_steps'):
            eng.train_batch((Tensor(ids), Tensor(labels)))
        eng.shutdown()

    def test_label_mismatch_named_error(self):
        eng, _ = _build(A=4)
        ids, labels = _data(8)
        with pytest.raises(PipelineBatchError, match='disagree'):
            eng.train_batch((Tensor(ids), Tensor(labels[:4])))
        eng.shutdown()


class TestBubbleModel:
    def test_1f1b_closed_forms(self):
        m = schedule_model('1F1B', 4, 8)
        assert m['ticks'] == 8 + 2 * 3
        assert m['slots_per_chunk'] == 7          # min(A, 2pp-1)
        assert m['inflight_peak'] == 7
        assert abs(m['bubble_fraction'] - 3 / 11) < 1e-12
        # slot census matches the engine's circular window for a spread
        # of shapes
        for pp, A in ((2, 4), (4, 8), (4, 32), (8, 8)):
            assert schedule_model('1F1B', pp, A)['slots_per_chunk'] \
                == min(A, 2 * pp - 1), (pp, A)

    def test_interleaved_closed_forms(self):
        m = schedule_model('interleaved', 4, 8, 2)
        D = 2 * 3 + 1 * 4
        assert m['ticks'] == 8 * 2 + D
        assert abs(m['bubble_fraction'] - 3 / 19) < 1e-12
        # v=1 degenerates to the 1F1B table
        m1 = schedule_model('interleaved', 4, 8, 1)
        ref = schedule_model('1F1B', 4, 8)
        assert {k: v for k, v in m1.items() if k != 'schedule'} \
            == {k: v for k, v in ref.items() if k != 'schedule'}

    def test_bubble_monotone_in_v(self):
        for pp, A in ((2, 4), (4, 8)):
            fracs = [schedule_model('interleaved', pp, A, v)
                     ['bubble_fraction'] for v in (1, 2, 4)]
            assert fracs[0] > fracs[1] > fracs[2], (pp, A, fracs)

    def test_gauge_round_trip(self):
        m = schedule_model('interleaved', 2, 4, 2)
        publish_schedule_gauges(m, engine='pipeline')
        snap = pipeline_snapshot()
        assert snap['schedule'] == 'interleaved'
        assert snap['virtual_stages'] == 2
        assert snap['ticks'] == m['ticks']
        assert abs(snap['bubble_fraction'] - m['bubble_fraction']) < 1e-9


class TestInterleavedEquivalence:
    """fp32 bit-identity bars for the v=2 interleaved schedule vs the
    v=1 1F1B baseline: the tick table only reorders WHEN each (chunk,
    microbatch) job runs; per-parameter contributions accumulate in the
    same ascending-microbatch order, so stash-mode results are
    BIT-identical. The recompute mode re-runs each chunk's forward
    inside the backward: XLA fuses that per-chunk subgraph differently
    from the per-stage one (different dot tilings), so params carry
    ~1-ulp fp32 reassociation noise — the PR-12 finding; losses stay
    bit-identical."""

    def test_pp2_stash_bit_identical(self):
        base = _run(schedule='1F1B')
        got = _run(schedule='interleaved', v=2)
        _assert_bit_identical(base, got, 'pp2 stash')
        assert base[0][-1] < base[0][0]       # it actually trains

    def test_dp2_pp2_stash_bit_identical(self):
        base = _run(schedule='1F1B', dp=2)
        got = _run(schedule='interleaved', v=2, dp=2)
        _assert_bit_identical(base, got, 'dp2xpp2 stash')

    def test_pp2_recompute_loss_bit_identical(self):
        base = _run(schedule='1F1B', memory_mode='recompute')
        got = _run(schedule='interleaved', v=2, memory_mode='recompute')
        assert base[0] == got[0], (base[0], got[0])
        for k in base[1]:
            np.testing.assert_allclose(
                base[1][k], got[1][k], rtol=5e-5, atol=1e-8,
                err_msg=f'recompute param {k}')

    @pytest.mark.slow
    def test_sgd_recompute_step_bit_identical(self):
        # one SGD step has no rsqrt amplification: fully bit-identical
        base = _run(schedule='1F1B', memory_mode='recompute',
                    opt_name='sgd', steps=1)
        got = _run(schedule='interleaved', v=2, memory_mode='recompute',
                   opt_name='sgd', steps=1)
        _assert_bit_identical(base, got, 'pp2 sgd recompute')

    @pytest.mark.slow
    def test_scaler_path_bit_identical(self):
        base = _run(schedule='1F1B', scale=1024.0)
        got = _run(schedule='interleaved', v=2, scale=1024.0)
        _assert_bit_identical(base, got, 'pp2 scaled')

    def test_scaler_found_inf_skips_update(self):
        # a loss scale that overflows the fp32 grads must trip
        # found_inf and skip the update on BOTH schedules (an inf scale
        # makes the overflow deterministic on this tiny model)
        for sched, v in (('1F1B', None), ('interleaved', 2)):
            eng, blocks = _build(schedule=sched, v=v)
            ids, labels = _data(8)
            before = {n: np.asarray(p.data).copy()
                      for n, p in blocks[0].named_parameters()}
            eng.train_batch((Tensor(ids), Tensor(labels)),
                            scale=float('inf'))
            assert bool(np.asarray(eng.last_found_inf)), sched
            eng.sync_model()
            for n, p in blocks[0].named_parameters():
                np.testing.assert_array_equal(
                    before[n], np.asarray(p.data),
                    err_msg=f'{sched}: update not skipped for {n}')
            eng.shutdown()

    @pytest.mark.slow
    def test_remat_policy_composes(self):
        base = _run(schedule='1F1B', use_remat=True,
                    remat_policy='attn_mlp_boundaries')
        got = _run(schedule='interleaved', v=2, use_remat=True,
                   remat_policy='attn_mlp_boundaries')
        _assert_bit_identical(base, got, 'pp2 attn_mlp_boundaries')

    @pytest.mark.slow
    def test_sync_model_cross_restore_v2_v1(self):
        """Train under one schedule, sync_model, rebuild the engine
        under the other and continue: the round-robin stacking maps
        back to the same per-layer weights, so both continuation
        orders land on identical losses and params."""
        def train_then_continue(first, second):
            eng, blocks = _build(**first)
            ids, labels = _data(8)
            data = (Tensor(ids), Tensor(labels))
            l0 = [float(eng.train_batch(data)) for _ in range(2)]
            eng.sync_model()
            eng.shutdown()
            # rebuild on the SAME trained layers (no reseed)
            opt = paddle.optimizer.Adam(learning_rate=3e-3,
                                        parameters=[])
            eng2 = SpmdPipelineEngine(
                eng.embed, blocks, eng.head, opt, accumulate_steps=4,
                use_remat=False, **second)
            l1 = [float(eng2.train_batch(data))]
            eng2.sync_model()
            params = {f'{i}/{n}': np.asarray(p.data)
                      for i, b in enumerate(blocks)
                      for n, p in b.named_parameters()}
            eng2.shutdown()
            return l0 + l1, params

        v2_to_v1 = train_then_continue(
            dict(schedule='interleaved', v=2),
            dict(schedule='1F1B'))
        v1_to_v2 = train_then_continue(
            dict(schedule='1F1B'),
            dict(schedule='interleaved', virtual_stages=2))
        v1_to_v1 = train_then_continue(
            dict(schedule='1F1B'), dict(schedule='1F1B'))
        _assert_bit_identical(v2_to_v1, v1_to_v1, 'v2->v1')
        _assert_bit_identical(v1_to_v2, v1_to_v1, 'v1->v2')

    def test_engine_publishes_schedule_census(self):
        eng, _ = _build(schedule='interleaved', v=2)
        snap = pipeline_snapshot()
        assert snap['schedule'] == 'interleaved' \
            and snap['virtual_stages'] == 2
        m = eng._sched_model
        assert snap['ticks'] == m['ticks']
        assert snap['bubble_fraction'] < \
            schedule_model('1F1B', 2, 4)['bubble_fraction']
        # telemetry surfaces the same census
        from paddle_tpu.profiler import StepTelemetry
        tel = StepTelemetry(publish=False).snapshot()
        assert tel['pipeline'] and \
            tel['pipeline']['schedule'] == 'interleaved'
        eng.shutdown()


def _one_step_grads(bf16=False, **kw):
    """One SGD step at lr 1 from the same seeded weights: the update IS
    the gradient. Returns (loss, {param: gradient})."""
    import jax.numpy as jnp
    eng, blocks = _build(pp=1, opt_name='sgd', **kw)
    eng.optimizer.set_lr(1.0)
    eng.grad_accum_dtype = 'param' if bf16 else 'float32'
    if bf16:
        eng._params = {g: {n: a.astype(jnp.bfloat16) for n, a in t.items()}
                       for g, t in eng._params.items()}
    before = {f'{g}/{n}': np.asarray(a, np.float32)
              for g, t in eng._params.items() for n, a in t.items()}
    ids, labels = _data(8)
    loss = float(eng.train_batch((Tensor(ids), Tensor(labels))))
    grads = {k: before[k] - np.asarray(eng._params[k.split('/', 1)[0]]
                                       [k.split('/', 1)[1]], np.float32)
             for k in before}
    eng.shutdown()
    return loss, grads


class TestOneStage:
    """pp=1 under 'stash' (ISSUE 35): the engine's own reverse scan adds
    each layer's weight gradient into the accumulation buffer; the
    'recompute' memory mode still takes `jax.vjp` through the layer scan
    and adds the fresh tree — the path every pp=1 engine took before —
    and is the reference here, both under remat 'full'."""

    REF = dict(memory_mode='recompute', remat_policy='full')

    @pytest.mark.parametrize('new', [
        dict(remat_policy='full'), dict(use_remat=True),
        dict(remat_policy='attn_mlp_lean'), dict(use_remat=False)],
        ids=['full', 'fitted', 'lean', 'no-remat'])
    def test_three_steps_of_losses_bit_identical(self, new):
        ref = _run(pp=1, **self.REF)
        got = _run(pp=1, **new)
        assert got[0] == ref[0]
        for k in ref[1]:
            np.testing.assert_allclose(got[1][k], ref[1][k], rtol=0,
                                       atol=2e-7, err_msg=k)

    def test_block_gradients_bit_identical_in_float32(self):
        """Accumulated in float32 every block gradient is the SAME
        number (the same pullback, the same adds in the same order).
        The word embedding's differs in its last bits: without the
        select, XLA folds `acc + scatter-add(0, ids, dx)` into a
        scatter-add onto `acc`, another order of the same sums."""
        l_ref, ref = _one_step_grads(**self.REF)
        l_new, new = _one_step_grads(remat_policy='full')
        assert l_new == l_ref
        for k in ref:
            if k.startswith('blocks/') or k.startswith('head/'):
                np.testing.assert_array_equal(new[k], ref[k], err_msg=k)
            else:
                np.testing.assert_allclose(new[k], ref[k], rtol=1e-5,
                                           atol=1e-8, err_msg=k)

    def test_gradients_one_rounding_close_in_param_dtype(self):
        """bf16 parameters, gradients accumulated in bf16: the reference
        rounds a layer's gradient to bf16 and then adds; fused, the
        product may be added before the one rounding. Either way one
        bf16 rounding of the sum apart."""
        l_ref, ref = _one_step_grads(bf16=True, **self.REF)
        l_new, new = _one_step_grads(bf16=True, remat_policy='full')
        assert abs(l_new - l_ref) <= 2 ** -7 * abs(l_ref)
        for k in ref:
            # the update is read off bf16 parameters: two of their
            # roundings, and one of the sum's
            room = 2 ** -7 * (np.abs(ref[k]).max() + 2 * 0.02 + 1e-6)
            np.testing.assert_allclose(new[k], ref[k], rtol=0, atol=room,
                                       err_msg=k)

    def test_fits_the_richest_policy_the_limit_allows(self, monkeypatch):
        """`use_remat=True` alone: the richest of FIT_ORDER whose
        reckoned bytes fit the device's limit; no limit named (the
        CPU) fits everything; the gauge says which was compiled."""
        from paddle_tpu.distributed.fleet.meta_parallel import (
            spmd_pipeline as sp)
        from paddle_tpu.distributed.fleet.utils.recompute import (
            FIT_ORDER, snapshot)
        ids, labels = _data(8)

        def fitted(limit):
            monkeypatch.setattr(sp, '_device_bytes_limit',
                                lambda dev: limit)
            eng, _ = _build(pp=1, use_remat=True)
            eng.train_batch((Tensor(ids), Tensor(labels)))
            r = dict(eng._remat_reckoned)
            assert snapshot()['policies']['pipeline'] == r['policy']
            assert pipeline_snapshot()['saved_boundary_bytes'] == \
                r['held_bytes'][r['policy']]
            eng.shutdown()
            return r

        r = fitted(None)
        assert r['policy'] == FIT_ORDER[0] == 'attn_mlp_boundaries'
        base = r['fixed_bytes'] + r['working_bytes']
        rich = base + r['held_bytes'][FIT_ORDER[0]]
        assert fitted(rich)['policy'] == FIT_ORDER[0]
        r = fitted(rich - 1)
        assert r['policy'] == 'attn_mlp_lean'
        lean = base + r['held_bytes']['attn_mlp_lean']
        assert lean < rich
        r = fitted(lean - 1)
        assert r['policy'] == 'full' and list(r['held_bytes']) == \
            list(FIT_ORDER)
        assert r['held_bytes']['full'] < r['held_bytes']['attn_mlp_lean']
        assert fitted(1)['policy'] == 'full'     # the last is taken anyway

    def test_falls_back_when_the_compiler_refuses(self, monkeypatch):
        """RESOURCE_EXHAUSTED from the AOT compile: the next policy,
        traced and compiled again, said in the gauge; any other error,
        and a named policy's, is raised."""
        from paddle_tpu import profiler
        from paddle_tpu.distributed.fleet.utils.recompute import snapshot
        real = profiler.compile_with_telemetry
        refused = []

        def compile_(jitted, label, args, kwargs=None):
            if len(refused) < 2:
                jitted.lower(*args)         # the trace sets the policy
                refused.append(snapshot()['policies']['pipeline'])
                raise RuntimeError('RESOURCE_EXHAUSTED: Used 17.77G of '
                                   '15.75G hbm')
            return real(jitted, label, args, kwargs)
        monkeypatch.setattr(profiler, 'compile_with_telemetry', compile_)
        ids, labels = _data(8)
        eng, _ = _build(pp=1, use_remat=True)
        loss = float(eng.train_batch((Tensor(ids), Tensor(labels))))
        assert refused == ['attn_mlp_boundaries', 'attn_mlp_lean']
        assert snapshot()['policies']['pipeline'] == 'full'
        assert eng._remat_reckoned['refused_by_compiler'] == 2
        eng.shutdown()
        assert loss == _run(steps=1, pp=1, **self.REF)[0][0]

        refused.clear()
        eng, _ = _build(pp=1, remat_policy='attn_mlp_boundaries')
        with pytest.raises(RuntimeError, match='RESOURCE_EXHAUSTED'):
            eng.train_batch((Tensor(ids), Tensor(labels)))
        eng.shutdown()

    def test_snapshot_says_what_the_step_holds(self):
        """`grad_tree_bytes` is 0 on the one-stage path and the size of
        the fresh gradient tree where `jax.vjp` returns one;
        `saved_boundary_bytes` is what crosses to the backward."""
        from paddle_tpu.profiler import StepTelemetry
        ids, labels = _data(8)
        held = {}
        for name, kw in (('one', dict(pp=1, remat_policy='full')),
                         ('rich', dict(pp=1, use_remat=True)),
                         ('vjp', dict(pp=1, **self.REF)),
                         ('pp2', dict(pp=2))):
            eng, _ = _build(**kw)
            eng.train_batch((Tensor(ids), Tensor(labels)))
            held[name] = snap = pipeline_snapshot()
            tel = StepTelemetry(publish=False).snapshot()
            for key in ('saved_boundary_bytes', 'grad_tree_bytes'):
                assert tel['remat'][key] == tel['pipeline'][key] == \
                    snap[key]
            n_params = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                           for t in eng._params.values()
                           for a in t.values())
            eng.shutdown()
            if name != 'pp2':       # pp2's is one stage's share
                assert snap['grad_tree_bytes'] == \
                    (n_params if name == 'vjp' else 0)
        # 4 layers' inputs of [2, 32, 16] float32; the boundaries more
        assert held['one']['saved_boundary_bytes'] == 4 * 2 * 32 * 16 * 4
        assert held['rich']['saved_boundary_bytes'] > \
            held['one']['saved_boundary_bytes']
        assert 0 < held['pp2']['grad_tree_bytes'] < \
            held['vjp']['grad_tree_bytes']
        assert held['pp2']['saved_boundary_bytes'] > 0


@pytest.mark.slow
class TestTwoRank:
    def test_two_rank_subprocess_equivalence(self):
        """True 2-rank pp mesh in a fresh process: interleaved v=2 ==
        1F1B bit-identical + bubble census (dist_pipeline_sched.py)."""
        script = os.path.join(os.path.dirname(__file__), 'dist_models',
                              'dist_pipeline_sched.py')
        env = dict(os.environ)
        env.pop('XLA_FLAGS', None)
        p = subprocess.run([sys.executable, '-u', script],
                           capture_output=True, text=True, timeout=600,
                           env=env)
        assert p.returncode == 0, \
            f'STDOUT:\n{p.stdout}\nSTDERR:\n{p.stderr}'
        assert 'BIT-IDENTICAL' in p.stdout
