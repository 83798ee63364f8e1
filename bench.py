"""Benchmark: GPT-1.3B (north-star model) train-step MFU on one TPU chip.

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline"}.

The reference publishes no numbers (BASELINE.md); vs_baseline is measured
MFU against the BASELINE.json north-star target fraction of 45% MFU
(value > 1.0 beats the target).

Headline: GPT-1.3B (hidden 2048, 24 layers, seq 2048), bf16, through the
1F1B SPMD pipeline engine at pp=1 — per-block rematerialization, microbatch
accumulation, param-dtype grad accumulator, single fused XLA program per
step. The optimizer is the north star's real one — AdamW — with bf16-stored
moments (5.7G beside 2.8G bf16 params; fp32 moments +10.4G don't fit a 16G
v5e) and fp32 update math in-register; at scale the hybrid engine instead
shards fp32 Adam state over the 'sharding' axis (ZeRO, tested on the
virtual mesh). detail carries the SGD leg (r1-r4 comparability) and the
BERT-base config-3 measurement (bf16 + ZeRO-2 via the hybrid engine).
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

TARGET_MFU = 0.45


def _peak_tflops():
    """bf16 peak of the device this leg runs on, from the ledger's
    table. A device the table does not know (the CPU included) is an
    error: an MFU is never computed against another chip's peak."""
    import jax
    from paddle_tpu.core import ledger
    kind = jax.devices()[0].device_kind
    peak = ledger.resolve_peak_tflops(kind)
    if peak is None:
        raise RuntimeError(
            f'no peak TFLOP/s known for device_kind {kind!r} '
            f'(platform {jax.default_backend()!r}): add it to '
            'paddle_tpu.core.ledger.PEAK_TFLOPS_BF16 with its source')
    return peak

# record schema (ISSUE 16): v2 = top-level legs + schema_version/round
# stamps + the headline ledger record (r04/r05 artifacts predate this
# and nest legs inside detail — bench_compare normalizes both shapes)
BENCH_SCHEMA_VERSION = 2


def _next_round_id():
    """rNN one past the newest BENCH_r*.json beside this script (the
    artifact naming the driver uses); BENCH_ROUND env overrides."""
    import re
    here = os.path.dirname(os.path.abspath(__file__))
    rounds = []
    try:
        for f in os.listdir(here):
            m = re.match(r'BENCH_r(\d+)\.json$', f)
            if m:
                rounds.append(int(m.group(1)))
    except OSError:
        pass
    return f'r{(max(rounds) + 1 if rounds else 6):02d}'



def _host_gap_record(eng, sync_step, make_batches, dispatch,
                     n_sync=3, sync_trials=2, n=5, trials=3):
    """Shared ISSUE-13 harness for the training legs: measure the
    sync_loop sub-record (host-synchronous discipline — `sync_step()`
    does one per-step feed + blocking fetch) and then the windowed
    timed region (DeviceLoader + `dispatch(batch)`, loss fetched only
    at trial end) on the SAME engine. Returns (detail.host record,
    windowed best dt seconds)."""
    from paddle_tpu.io import DeviceLoader
    eng._gap.reset()
    sync_dt = float('inf')
    for _ in range(sync_trials):
        t0 = time.time()
        for _ in range(n_sync):
            sync_step()
        sync_dt = min(sync_dt, (time.time() - t0) / n_sync)
    sync_gap = eng.host_gap_snapshot()

    eng._gap.reset()
    dt = float('inf')                      # best-of-trials
    loader_stats = None
    for _ in range(trials):
        loader = DeviceLoader(make_batches(n), engine=eng)
        t0 = time.time()
        last = None
        for b in loader:
            last = dispatch(b)
        eng.flush()
        last.result()                      # ONE fetch, at trial end
        dt = min(dt, (time.time() - t0) / n)
        loader_stats = loader.stats()
    win_gap = eng.host_gap_snapshot()
    host = {
        'dispatch_window': eng._inflight.size,
        'prefetch': loader_stats,
        'device_lr': eng._lr.fn is not None,
        'windowed': {k: win_gap.get(k) for k in
                     ('steps', 'host_gap_seconds', 'host_residue_seconds',
                      'host_bound_fraction', 'dispatch_depth_mean',
                      'dispatch_depth_max')},
        'sync_loop': dict(
            {k: sync_gap.get(k) for k in
             ('steps', 'host_gap_seconds', 'host_residue_seconds',
              'host_bound_fraction')},
            ms_per_step=sync_dt * 1000),
        # the ISSUE-13 CPU-dryrun acceptance signal: the windowed loop's
        # host gap must be strictly below the synchronous loop's
        'host_gap_reduced':
            win_gap['host_gap_seconds'] < sync_gap['host_gap_seconds'],
    }
    return host, dt


def bench_gpt_1p3b(optimizer='adamw'):
    """optimizer='adamw' is the headline: the north star is Fleet hybrid
    training, and nobody trains GPT with SGD. fp32 Adam moments for 1.3B
    params (+10.4G) don't fit a 16G v5e chip, so moments are stored bf16
    (5.7G beside 2.8G bf16 params) and the update math runs fp32
    in-register (optimizer.py Adam.moment_dtype). 'sgd' is kept as a
    detail leg for cross-round comparability with r1-r4."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.distributed import topology_runtime
    from paddle_tpu.models.gpt import GPTConfig, build_gpt_pipeline
    from paddle_tpu.distributed.fleet.meta_parallel.spmd_pipeline import (
        SpmdPipelineEngine)
    import paddle_tpu.distributed.fleet as fm

    peak = _peak_tflops()       # refuse an unknown device before the work
    fm.fleet._hcg = None
    topology_runtime.build_mesh(['dp', 'pp'], [1, 1])
    paddle.seed(0)
    L = 2048
    cfg = GPTConfig(vocab_size=50304, hidden_size=2048, num_layers=24,
                    num_heads=16, max_seq_len=L, hidden_dropout=0.0,
                    attn_dropout=0.0, use_flash_attention=True)
    embed, blocks, head = build_gpt_pipeline(cfg)
    layers = [embed, head] + blocks
    for layer in layers:
        for p in layer.parameters():
            if p.data.dtype == jnp.float32:
                p.data = p.data.astype(jnp.bfloat16)
    n_params = sum(int(np.prod(p.shape))
                   for layer in layers for p in layer.parameters())
    if optimizer == 'adamw':
        opt = paddle.optimizer.AdamW(learning_rate=1e-4, parameters=[],
                                     weight_decay=0.01,
                                     multi_precision=False,
                                     moment_dtype='bfloat16')
    else:
        opt = paddle.optimizer.SGD(learning_rate=1e-4, parameters=[],
                                   multi_precision=False)
    A, mb = 4, 2
    eng = SpmdPipelineEngine(embed, blocks, head, opt, accumulate_steps=A,
                             use_remat=True, schedule='1F1B',
                             grad_accum_dtype='param')
    # A=4 x mb=2 measured best on one v5e chip (58.8% vs 53.9% at mb=1:
    # bigger per-microbatch matmuls amortize layernorm/transpose overhead)
    # the engine owns device copies; free the eager duplicates (2.6G)
    for layer in layers:
        for p in layer.parameters():
            p._data = jnp.zeros((1,), jnp.bfloat16)

    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (A * mb, L)).astype('int32')
    labels = np.roll(ids, -1, 1).astype('int32')
    data = (Tensor(ids), Tensor(labels))
    from paddle_tpu.core import memory as _mem
    census_before = _mem.sample(count_buffers=True)
    loss = eng.train_batch(data)          # compile + warmup
    assert np.isfinite(float(loss))
    census_after = _mem.sample(count_buffers=True)

    # sync_loop sub-record + windowed timed region (ISSUE 13): the
    # headline ms_per_step now comes from the DeviceLoader + windowed
    # dispatch loop, with the host-synchronous discipline measured on
    # the same engine for the host-gap comparison
    # step-time ledger (ISSUE 16): name the arch facts the engine can't
    # infer so the ledger's analytic FLOPs match the bench formula below
    from paddle_tpu.core import ledger as _ledger_mod
    _ledger_mod.configure('pipeline', layers=cfg.num_layers,
                          hidden=cfg.hidden_size, seq_len=L,
                          n_params=n_params, arch='gpt')
    # telemetry time axis (ISSUE 18): history rings sample on the
    # telemetry publishes inside the timed loop, and the engine alert
    # pack rides along — a clean leg must not fire a critical rule
    # (_check_legs asserts on the recorded summary)
    from paddle_tpu.core import monitor as _monitor
    from paddle_tpu.core.alerts import AlertManager, default_rules
    hist = _monitor.metrics().enable_history(capacity=240)
    alerts = AlertManager(hist, rules=default_rules(), source='bench')
    host, dt = _host_gap_record(
        eng,
        sync_step=lambda: float(
            eng.train_batch((Tensor(ids), Tensor(labels)))),
        make_batches=lambda k: [(ids, labels)] * k,
        dispatch=eng.train_step,
        n_sync=3, sync_trials=2, n=5, trials=3)
    # the reconciled where-did-the-step-go account, published by the
    # flush inside the windowed loop (health_dump ledger renders this)
    ledger_rec = eng._ledger.account()
    _monitor.metrics().history_tick()   # final sample + rule pass
    series_rec = hist.export(max_points=24)
    alerts_rec = alerts.summary()
    alerts.detach()

    tokens = A * mb * L
    flops = 6 * n_params * tokens + \
        12 * cfg.num_layers * cfg.hidden_size * L * tokens
    tflops = flops / dt / 1e12
    # teardown proof (r5 regression): shutdown must actually release the
    # ~8.5G of params+moments+executables; the post-shutdown census from
    # the memory accountant goes into the round record
    before = len(jax.live_arrays())
    released = eng.shutdown()
    # which fused Pallas primitives the compiled step actually routed to
    # (ISSUE 8): BENCH_r06+ attributes ms_per_step deltas to these. On a
    # CPU-only bench run the optimizer/norm kernels auto-fall back, so
    # the routes dict is the honest evidence either way (interpret-mode
    # parity lives in tests/test_fused_primitives.py).
    from paddle_tpu.ops.pallas import scaffold as _scaffold
    from paddle_tpu.distributed.fleet.utils.recompute import (
        boundary_counts as _remat_boundaries)
    return {
        'mfu': tflops / peak,
        'ms_per_step': dt * 1000,
        'tokens_per_sec': tokens / dt,
        'tflops': tflops,
        'params': n_params,
        'seq_len': L,
        'microbatches': A,
        'optimizer': optimizer,
        'fused_primitives': {'active': _scaffold.active_primitives(),
                             'routes': _scaffold.routes_snapshot()},
        # tuned-remat evidence (ISSUE 12): the resolved policy, the
        # checkpoint_name boundaries the trace carried, and the
        # activation census around the compile (the compiled-program
        # temp bytes ride in telemetry.remat.activation_bytes +
        # memory.sample.activation_bytes)
        'remat': {
            'policy': eng._remat_policy or (
                'full' if eng.use_remat else 'none'),
            'boundaries': _remat_boundaries(),
            'census_before': {k: census_before.get(k) for k in
                              ('bytes_in_use', 'live_bytes',
                               'live_buffers')},
            'census_after': {k: census_after.get(k) for k in
                             ('bytes_in_use', 'live_bytes',
                              'live_buffers')},
            'activation_bytes': census_after.get('activation_bytes'),
        },
        # async step pipeline (ISSUE 13): dispatch window + prefetch
        # depth + host-gap before/after — BENCH_r06's instrument for
        # telling compute-bound from host-bound
        'host': host,
        # step-time ledger (ISSUE 16): compute/exposed-comm/bubble/
        # host-gap/residue decomposition + model TFLOP/s with the remat
        # recompute factor reflected (MFU only on real TPU peaks)
        'ledger': ledger_rec,
        # telemetry time axis (ISSUE 18): the downsampled history-ring
        # block + the alert summary for the leg (health_dump alerts
        # renders both; _check_legs fails the leg on a critical fire)
        'series': series_rec,
        'alerts': alerts_rec,
        'live_buffers_before_shutdown': before,
        'live_buffers_after_shutdown': released.get('live_buffers'),
        'live_bytes_after_shutdown': released.get('live_bytes'),
    }


def bench_bert_config3():
    """BASELINE config 3: BERT-base pretraining, bf16 + the ZeRO-2 hybrid
    engine path (sharding machinery engaged; degree 1 on one chip).
    Flash at L=512 measured 46.0% MFU vs 40.7% dense after the 512x512
    tile tuning, so the crossover flag is lowered here (tools/
    bert_tune.py holds the variant sweep)."""
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.core import flags
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.distributed import topology_runtime
    from paddle_tpu.models.bert import (BertConfig, BertForPretraining,
                                        bert_pretrain_loss)
    from paddle_tpu.distributed.fleet.meta_parallel.hybrid_engine import (
        HybridParallelTrainStep)

    peak = _peak_tflops()
    flags.set_flags({'FLAGS_flash_min_seq': 512})
    topology_runtime.build_mesh(['dp', 'sharding'], [1, 1])
    paddle.seed(0)
    B, L = 64, 512
    cfg = BertConfig(vocab_size=30522, hidden_size=768, num_layers=12,
                     num_heads=12, intermediate_size=3072, max_seq_len=L,
                     hidden_dropout=0.0, attn_dropout=0.0)
    model = BertForPretraining(cfg)
    for p in model.parameters():
        if p.data.dtype == jnp.float32:
            p.data = p.data.astype(jnp.bfloat16)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())

    def loss_fn(m, ids, mlm_labels, nsp_labels):
        # fused MLM path: chunked projection-xent, no [B*L, vocab] logits
        return m(ids, masked_lm_labels=mlm_labels,
                 next_sentence_label=nsp_labels)

    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 weight_decay=0.01)
    eng = HybridParallelTrainStep(model, loss_fn, opt)
    rng = np.random.RandomState(0)
    ids_np = rng.randint(0, cfg.vocab_size, (B, L)).astype('int32')
    mlm_np = ids_np.astype('int64')
    nsp_np = rng.randint(0, 2, (B,)).astype('int64')
    ids, mlm, nsp = Tensor(ids_np), Tensor(mlm_np), Tensor(nsp_np)
    loss = eng(ids, mlm, nsp)              # compile + warmup
    assert np.isfinite(float(loss))

    # sync_loop sub-record + windowed timed region (ISSUE 13), same
    # harness as the headline leg
    host, dt = _host_gap_record(
        eng,
        sync_step=lambda: float(
            eng(Tensor(ids_np), Tensor(mlm_np), Tensor(nsp_np))),
        make_batches=lambda k: [(ids_np, mlm_np, nsp_np)] * k,
        dispatch=lambda b: eng.train_step(*b),
        n_sync=3, sync_trials=2, n=10, trials=4)
    tokens = B * L
    flops = 6 * n_params * tokens + \
        12 * cfg.num_layers * cfg.hidden_size * L * tokens
    eng.shutdown()
    return {
        'samples_per_sec': B / dt,
        'ms_per_step': dt * 1000,
        'mfu': flops / dt / 1e12 / peak,
        'params': n_params,
        'batch': B, 'seq_len': L,
        'host': host,
    }


def bench_lenet_config1():
    """BASELINE config 1: MNIST LeNet, dygraph + jitted train step."""
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.vision.models import LeNet
    from paddle_tpu.jit import TrainStep

    paddle.seed(0)
    model = LeNet(10)
    opt = paddle.optimizer.Adam(parameters=model.parameters())
    step = TrainStep(model, lambda m, img, lb: nn.functional.cross_entropy(
        m(img), lb), opt)
    B = 256
    rng = np.random.RandomState(0)
    imgs = paddle.to_tensor(rng.rand(B, 1, 28, 28).astype('float32'))
    labels = paddle.to_tensor(rng.randint(0, 10, (B,)).astype('int64'))
    float(step(imgs, labels))              # compile
    n = 20
    dt = float('inf')
    for _ in range(3):
        t0 = time.time()
        for _ in range(n):
            loss = step(imgs, labels)
        float(loss)
        dt = min(dt, (time.time() - t0) / n)
    return {'images_per_sec': B / dt, 'ms_per_step': dt * 1000,
            'batch': B}


def bench_resnet50_config2(B=128, steps=20, trials=3):
    """BASELINE config 2: ResNet-50 ImageNet shape, bf16, dp machinery
    (degree 1 on one chip — the dp grad sync is the hybrid engine's
    pmean, exercised multi-device in the dryrun/tests)."""
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.distributed import topology_runtime
    from paddle_tpu.vision.models import resnet50
    from paddle_tpu import nn
    from paddle_tpu.distributed.fleet.meta_parallel.hybrid_engine import (
        HybridParallelTrainStep)
    import paddle_tpu.distributed.fleet as fm

    peak = _peak_tflops()
    fm.fleet._hcg = None
    topology_runtime.build_mesh(['dp'], [1])
    paddle.seed(0)
    model = resnet50(num_classes=1000)
    for p in model.parameters():
        if p.data.dtype == jnp.float32:
            p.data = p.data.astype(jnp.bfloat16)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                    parameters=model.parameters())

    def loss_fn(m, x, y):
        return nn.functional.cross_entropy(m(x), y)

    eng = HybridParallelTrainStep(model, loss_fn, opt)
    rng = np.random.RandomState(0)
    x = Tensor(jnp.asarray(rng.rand(B, 3, 224, 224), jnp.bfloat16))
    y = Tensor(rng.randint(0, 1000, (B,)).astype('int64'))
    loss = eng(x, y)                        # compile
    assert np.isfinite(float(loss))
    n = steps
    dt = float('inf')
    for _ in range(trials):
        t0 = time.time()
        for _ in range(n):
            loss = eng(x, y)
        float(loss)
        dt = min(dt, (time.time() - t0) / n)
    # ResNet-50 @224: ~4.1 GFLOPs forward per image; train ~3x forward
    flops = 3 * 4.1e9 * B
    eng.shutdown()
    return {'images_per_sec': B / dt, 'ms_per_step': dt * 1000,
            'mfu': flops / dt / 1e12 / peak,
            'params': n_params, 'batch': B}


def bench_deepfm_ps_config5():
    """BASELINE config 5: DeepFM over the REAL PS wire (PsServer +
    PsClient over localhost TCP against csrc/sparse_table), OVERLAPPED
    via the AsyncCommunicator (reference communicator.h:197 role): the
    prefetch thread pulls batch t+1 and uploads it to the device while
    the chip computes step t, and the push drainer forces step t's
    gradient readback + wire push in the background. Steady state
    ms_per_step ~= max(device step, host wire work), not their sum
    (VERDICT r4 weak #2: the un-overlapped loop measured 165 ms of
    which 97% was serial transfer). Reports the un-overlapped
    components too so the overlap is visible in the record."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from paddle_tpu.distributed.ps.service import PsServer, PsClient
    from paddle_tpu.distributed.ps.communicator import AsyncCommunicator

    fields, dim, B, K = 26, 8, 512, 16      # K = merged steps per PS round trip
    srv = PsServer().start()
    srv.add_table(0, dim=dim, optimizer='adagrad', seed=3)
    client = PsClient([f'127.0.0.1:{srv.port}'])
    rng = np.random.RandomState(0)
    # criteo-ish power-law ids over a large space; the steady-state
    # loop cycles over warmed distinct chunks (resident rows — the r4
    # bench's regime, so the overlap number isolates pipelining from
    # first-touch row inserts; the scale leg covers cold/spilled rows)
    n_chunks = 12
    distinct = [(rng.pareto(1.2, (K, B, fields)) * 1000)
                .astype(np.int64).reshape(K, -1) % (10**7)
                for _ in range(3)]
    id_stream = [distinct[i % 3] for i in range(n_chunks + 1)]

    w1 = jnp.asarray(rng.randn(fields * dim, 32) * 0.05, jnp.float32)
    b1 = jnp.zeros((32,), jnp.float32)
    w2 = jnp.asarray(rng.randn(32, 1) * 0.05, jnp.float32)
    labels = jnp.asarray(rng.randint(0, 2, (B, 1)), jnp.float32)

    def one_step(emb, w1, b1, w2):
        def loss_of(emb, w1, b1, w2):
            e = emb.reshape(B, fields, dim)
            s = e.sum(1)
            fm = 0.5 * (s * s - (e * e).sum(1)).sum(-1, keepdims=True)
            h = jax.nn.relu(e.reshape(B, -1) @ w1 + b1)
            logit = h @ w2 + fm
            return jnp.mean(jnp.clip(logit, 0) - logit * labels
                            + jnp.log1p(jnp.exp(-jnp.abs(logit))))
        loss, grads = jax.value_and_grad(loss_of, argnums=(0, 1, 2, 3))(
            emb, w1, b1, w2)
        ge, gw1, gb1, gw2 = grads
        lr = 0.05
        return loss, ge, w1 - lr * gw1, b1 - lr * gb1, w2 - lr * gw2

    @jax.jit
    def dense_chunk(embs, w1, b1, w2):
        """K merged train steps in ONE dispatch (the reference
        Communicator's batch-merge, TPU-shaped): scan carries the dense
        params through K batches; the K row-grad sets come back in one
        device->host readback. Embedding rows within the chunk are
        one-chunk stale — the async-PS contract."""
        def body(carry, emb):
            w1, b1, w2 = carry
            loss, ge, w1, b1, w2 = one_step(emb, w1, b1, w2)
            return (w1, b1, w2), (loss, ge)
        (w1, b1, w2), (losses, ges) = lax.scan(body, (w1, b1, w2), embs)
        return losses.mean(), ges, w1, b1, w2

    # warm every distinct chunk's rows + compile, then measure the
    # UN-overlapped per-step parts on the same warm-row state
    for ch in distinct:
        for f in ch:
            client.pull(0, f, dim)
    flat0 = id_stream[-1]
    embs = jnp.asarray(np.stack([client.pull(0, f, dim)
                                 for f in flat0]))
    loss, ges, w1, b1, w2 = dense_chunk(embs, w1, b1, w2)
    np.asarray(ges)
    pull_ms = push_ms = dense_ms = float('inf')
    for _ in range(2):                       # best of 2 (shared chip)
        tp = time.time()
        pulled = [client.pull(0, f, dim) for f in flat0]
        pull_ms = min(pull_ms, (time.time() - tp) * 1000 / K)
        td = time.time()
        loss, ges, w1, b1, w2 = dense_chunk(
            jnp.asarray(np.stack(pulled)), w1, b1, w2)
        ges_np = np.asarray(ges)
        dense_ms = min(dense_ms, (time.time() - td) * 1000 / K)
        tu = time.time()
        for f, g in zip(flat0, ges_np):
            client.push(0, f, g, lr=0.05)
        push_ms = min(push_ms, (time.time() - tu) * 1000 / K)

    # chunk adapter: the communicator moves whole K-chunks per queue
    # item. Only the MAIN thread touches the device; the prefetch
    # thread overlaps the K pulls and the drainer overlaps the K pushes
    # with compute.
    import types as _types
    chunk_client = _types.SimpleNamespace(
        pull=lambda tid, ids, d: np.stack(
            [client.pull(tid, f, d) for f in ids]),
        push=lambda tid, ids, grads, lr: [
            client.push(tid, f, g, lr) for f, g in zip(ids, grads)])
    dt = float('inf')
    for _ in range(2):                       # best of 2 (shared chip)
        comm = AsyncCommunicator(chunk_client, 0, dim, depth=2)
        batches = comm.pull_ahead(id_stream[:n_chunks])
        ids0, emb0 = next(batches)           # prime the pipeline
        t0 = time.time()
        done = 0
        for ids_t, emb_t in batches:
            loss, ges, w1, b1, w2 = dense_chunk(jnp.asarray(emb0),
                                                w1, b1, w2)
            comm.push_async(ids0, np.asarray(ges), lr=0.05)
            done += K
            ids0, emb0 = ids_t, emb_t
        loss, ges, w1, b1, w2 = dense_chunk(jnp.asarray(emb0),
                                            w1, b1, w2)
        comm.push_async(ids0, np.asarray(ges), lr=0.05)
        done += K
        comm.flush()
        float(loss)
        dt = min(dt, (time.time() - t0) / done)
        comm.stop()

    rows = B * fields
    out = {'steps_per_sec': 1.0 / dt, 'ms_per_step': dt * 1000,
           'pull_ms': pull_ms, 'push_ms': push_ms,
           'dense_ms': dense_ms, 'merged_steps': K,
           'overlap_speedup': (pull_ms + push_ms + dense_ms) / (dt * 1000),
           'rows_per_pull': rows,
           'pull_rows_per_sec': rows / (pull_ms / 1000),
           'push_rows_per_sec': rows / (push_ms / 1000),
           'table_rows': int(client.table_size(0))}
    client.shutdown()
    client.close()
    return out


def bench_ps_scale(total_rows=2_000_000, mem_budget_rows=1 << 18,
                   dim=8, batch_rows=13312):
    """PS-at-scale leg (VERDICT r5 #4): the SSD spill tier engaged for
    real over the TCP wire — ~2M distinct rows against a 256k-row RAM
    budget (>85% of the table lives in the spill logs), then pull/push
    latency measured on uniform batches over the WHOLE id space, so
    most touches hit cold spilled rows (reference scale claim:
    README.md:49-50 10^11-feature PS; same tier, laptop-sized corpus)."""
    import tempfile
    from paddle_tpu.distributed.ps.service import PsServer, PsClient

    tmp = tempfile.TemporaryDirectory(prefix='ps_scale_')
    srv = PsServer().start()
    srv.add_table(0, dim=dim, optimizer='adagrad', seed=3,
                  ssd_path=tmp.name, mem_budget_rows=mem_budget_rows)
    client = PsClient([f'127.0.0.1:{srv.port}'])
    rng = np.random.RandomState(0)

    # populate: first-touch pulls insert rows; the budget forces spill
    t0 = time.time()
    seen = 0
    chunk = 1 << 17
    while seen < total_rows:
        ids = np.arange(seen, min(seen + chunk, total_rows),
                        dtype=np.int64)
        client.pull(0, ids, dim)
        seen += len(ids)
    build_s = time.time() - t0
    tbl = srv.tables[0]
    resident = int(tbl.mem_rows())
    total = int(tbl.total_rows())

    # steady state: uniform random batches over the full space — cold
    # (spilled) rows dominate each pull/push
    n = 15
    t_pull = t_push = 0.0
    for _ in range(n):
        ids = rng.randint(0, total_rows, batch_rows).astype(np.int64)
        tp = time.time()
        rows = client.pull(0, ids, dim)
        t_pull += time.time() - tp
        g = rng.rand(batch_rows, dim).astype(np.float32) * 0.01
        tu = time.time()
        client.push(0, ids, g, lr=0.05)
        t_push += time.time() - tu
    out = {'table_rows': total,
           'resident_rows': resident,
           'spilled_rows': total - resident,
           'spilled_frac': round(1 - resident / max(total, 1), 4),
           'mem_budget_rows': mem_budget_rows,
           'build_rows_per_sec': total_rows / build_s,
           'pull_ms': t_pull / n * 1000,
           'push_ms': t_push / n * 1000,
           'rows_per_batch': batch_rows,
           'pull_rows_per_sec': batch_rows / (t_pull / n),
           'push_rows_per_sec': batch_rows / (t_push / n)}
    client.shutdown()
    client.close()
    tmp.cleanup()
    return out


def bench_gpt_serve():
    """gpt_serve_throughput: the serving engine (paged KV pool +
    continuous batching + ragged paged attention, docs/serving.md) vs
    sequential per-request `generate` on the SAME mixed-length request
    stream. The acceptance number is `speedup_vs_sequential` — batched
    continuous decode must beat one-request-at-a-time decode by roughly
    the achievable batch occupancy; the dense per-request cache's
    O(B * max_len) memory also drops to O(pages in use)
    (kv_pages_high_water * page_size tokens)."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import ServingEngine, ServingConfig

    paddle.seed(0)
    on_tpu = jax.default_backend() == 'tpu'
    if on_tpu:
        # GPT-2 124M-ish decode workload, bf16 weights/KV
        cfg = GPTConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                        num_heads=12, max_seq_len=1024, hidden_dropout=0.0,
                        attn_dropout=0.0, use_flash_attention=True)
        n_req, max_new, batch, page_size, chunk = 16, 64, 8, 16, 128
        lo, hi = 32, 384
    else:
        # CPU CI shape: the leg must still run end to end on the test mesh
        cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                        num_heads=2, max_seq_len=128, hidden_dropout=0.0,
                        attn_dropout=0.0, use_flash_attention=False)
        n_req, max_new, batch, page_size, chunk = 6, 8, 3, 8, 16
        lo, hi = 4, 24
    model = GPTForCausalLM(cfg)
    if on_tpu:
        for p in model.parameters():
            if p.data.dtype == jnp.float32:
                p.data = p.data.astype(jnp.bfloat16)
    model.eval()
    rng = np.random.RandomState(0)
    lens = rng.randint(lo, hi + 1, n_req)
    prompts = [list(rng.randint(1, cfg.vocab_size, int(n))) for n in lens]

    # -- sequential per-request baseline (dense cache, greedy). First
    # pass warms every (1, L0+max_new) compiled-step shape — the dense
    # path recompiles per prompt length, and charging those compiles to
    # the baseline would flatter the engine; the measured pass is
    # steady-state decode on both sides --------------------------------
    for p in prompts:
        model.generate(Tensor(np.asarray([p], 'int32')),
                       max_new_tokens=max_new, top_k=0)
    t0 = time.time()
    gen_tokens = 0
    for p in prompts:
        out = model.generate(Tensor(np.asarray([p], 'int32')),
                             max_new_tokens=max_new, top_k=0)
        gen_tokens += out.shape[-1] - len(p)
    seq_dt = time.time() - t0
    seq_tps = gen_tokens / seq_dt

    # -- continuous batching over the paged pool ----------------------------
    # page-table width sized to the WORKLOAD, not max_seq_len: attention
    # cost (and the fallback's gather) scales with table width, and the
    # stream's contexts are known to fit hi+max_new tokens
    pages_per_seq = -(-(hi + max_new) // page_size)
    # telemetry time axis (ISSUE 18): the serve publish cadence
    # (telemetry_serve's publish -> history_tick) samples the rings
    # while the stream runs; the engine alert pack must stay quiet
    from paddle_tpu.core import monitor as _monitor
    from paddle_tpu.core.alerts import AlertManager, default_rules
    hist = _monitor.metrics().enable_history(capacity=240)
    alerts = AlertManager(hist, rules=default_rules(), source='bench')
    eng = ServingEngine(model, ServingConfig(
        page_size=page_size, max_batch_size=batch, prefill_chunk=chunk,
        max_pages_per_seq=pages_per_seq))
    eng.generate([prompts[0]], max_new_tokens=2, top_k=0)  # compile warmup
    eng.reset_stats()       # also clears the request journals/timeline
    t0 = time.time()
    outs = eng.generate(prompts, max_new_tokens=max_new, top_k=0)
    serve_dt = time.time() - t0
    serve_tokens = sum(len(o) - len(p) for o, p in zip(outs, prompts))
    st = eng.stats()

    # per-request SLO percentiles from the lifecycle journals (EXACT
    # per-request values for the measured stream — the monitor
    # histograms in telemetry_serve are bucket-interpolated and include
    # warmup; these are the headline numbers)
    from paddle_tpu.serving.request_trace import percentile_of
    table = eng.request_table()
    slo = {}
    for key, label in (('ttft_s', 'ttft_ms'), ('tpot_s', 'tpot_ms'),
                       ('queue_wait_s', 'queue_wait_ms'),
                       ('e2e_s', 'e2e_ms')):
        vals = [r[key] for r in table.values()]
        slo[label] = {
            f'p{q}': (round(p * 1000.0, 3)
                      if (p := percentile_of(vals, q)) is not None
                      else None)
            for q in (50, 90, 99)}
    timeline = eng.timeline.summary()

    dense_cache_tokens = n_req * cfg.max_seq_len
    paged_tokens = st['pool']['high_water'] * page_size
    # serving ledger (ISSUE 17), captured BEFORE shutdown (which
    # unregisters the ledger): reconciled wall decomposition, the
    # goodput identity and the decode roofline for the measured
    # stream (warmup excluded by reset_stats)
    serve_ledger = eng.ledger.account()
    serve_goodput = eng.ledger.goodput()
    serve_roofline = eng.ledger.roofline()
    _monitor.metrics().history_tick()   # final sample + rule pass
    series_rec = hist.export(max_points=24)
    alerts_rec = alerts.summary()
    alerts.detach()
    eng.shutdown()

    # -- shared-prefix stream (ISSUE 9): N requests with a common
    # system prompt, served by the PR-5 config (no prefix cache, no
    # speculation) and by the prefix+spec engine. TTFT should drop by
    # the cached prefill chunks, decode tokens/sec should rise by the
    # accepted drafts per verify dispatch — greedy outputs identical.
    sys_len = 256 if on_tpu else 16
    spec_k = 4
    n_shared = 8 if on_tpu else 4
    system = list(rng.randint(1, cfg.vocab_size, sys_len))
    shared_prompts = [system + list(rng.randint(
        1, cfg.vocab_size, int(n)))
        for n in rng.randint(lo, hi + 1, n_shared)]
    pages_shared = -(-(sys_len + hi + max_new) // page_size)

    def _run_shared(**knobs):
        e = ServingEngine(model, ServingConfig(
            page_size=page_size, max_batch_size=batch,
            prefill_chunk=chunk, max_pages_per_seq=pages_shared,
            **knobs))
        # warm every compiled shape this engine will hit: prefill +
        # decode via the stream head, the verify shape via a
        # repetitive prompt the n-gram proposer fires on
        e.generate([shared_prompts[0]], max_new_tokens=2, top_k=0)
        if knobs.get('spec_k'):
            e.generate([[7, 8, 9] * 4], max_new_tokens=4, top_k=0)
        e.reset_stats()
        t0 = time.time()
        outs = e.generate(shared_prompts, max_new_tokens=max_new,
                          top_k=0)
        dt = time.time() - t0
        toks = sum(len(o) - len(p)
                   for o, p in zip(outs, shared_prompts))
        stl = e.stats()
        ttft = percentile_of(
            [r['ttft_s'] for r in e.request_table().values()], 50)
        e.shutdown()
        return {
            'tokens_per_sec': toks / dt,
            'decode_tokens_per_sec': stl['decode_tokens_per_sec'],
            'ttft_p50_ms': (round(ttft * 1000.0, 3)
                            if ttft is not None else None),
            'prefill_tokens': stl['prefill_tokens_total'],
            'decode_steps': stl['decode_steps_total'],
            'decode_tokens': stl['decode_tokens_total'],
            'prefix_hits': stl['prefix_hits_total'],
            'prefix_hit_tokens': stl['prefix_hit_tokens_total'],
            'spec_proposed': stl['spec_proposed_tokens_total'],
            'spec_accepted': stl['spec_accepted_tokens_total'],
            'spec_acceptance_rate': stl['spec_acceptance_rate'],
        }, outs

    base_rec, base_outs = _run_shared(prefix_cache=False, spec_k=0)
    opt_rec, opt_outs = _run_shared(prefix_cache=True, spec_k=spec_k)
    shared_prefix = {
        'requests': n_shared,
        'system_prompt_tokens': sys_len,
        'spec_k': spec_k,
        'baseline_pr5': base_rec,
        'prefix_spec': opt_rec,
        'outputs_identical': base_outs == opt_outs,
        'ttft_speedup_vs_pr5':
            (base_rec['ttft_p50_ms'] / opt_rec['ttft_p50_ms']
             if base_rec['ttft_p50_ms'] and opt_rec['ttft_p50_ms']
             else None),
        'decode_speedup_vs_pr5':
            (opt_rec['decode_tokens_per_sec']
             / base_rec['decode_tokens_per_sec']
             if base_rec['decode_tokens_per_sec'] else None),
    }

    # -- fused decode windows (ISSUE 19): small-batch decode is where
    # per-token serving goes host-bound (one dispatch + one fetch per
    # token, device done long before Python). The same stream at fused
    # k in {1, 4, 8}: decode tok/s and the ledger's measured
    # host_bound_fraction side by side, outputs identical across k.
    sb_batch = min(4, batch)
    sb_prompts = prompts[:sb_batch]
    # long enough for several windows at k=8 — a stream one window
    # swallows whole leaves no inter-step interval for the gap monitor
    # to price, and host_bound_fraction would read None
    sb_max_new = max(max_new, 24)
    sb_pages = -(-(hi + sb_max_new) // page_size)

    def _run_fused(k):
        e = ServingEngine(model, ServingConfig(
            page_size=page_size, max_batch_size=sb_batch,
            prefill_chunk=chunk, max_pages_per_seq=sb_pages,
            fused_k=k))
        # warm every compiled shape this engine will hit — prefill,
        # the [B, 1] step (mixed prefill/decode sweeps) and the fused
        # (B,) scan — on a short pass over the same stream
        e.generate(sb_prompts, max_new_tokens=2, top_k=0)
        e.reset_stats()
        t0 = time.time()
        outs = e.generate(sb_prompts, max_new_tokens=sb_max_new,
                          top_k=0)
        dt = time.time() - t0
        stf = e.stats()
        led = e.ledger.account() or {}
        e.shutdown()
        toks = sum(len(o) - len(p) for o, p in zip(outs, sb_prompts))
        return {
            'fused_k': k,
            'tokens_per_sec': toks / dt,
            'decode_tokens_per_sec': stf['decode_tokens_per_sec'],
            'host_bound_fraction': led.get('host_bound_fraction'),
            'fused_windows': stf['fused_windows_total'],
            'fused_iterations': stf['fused_iterations_total'],
            'fused_tokens': stf['fused_tokens_total'],
            'decode_steps': stf['decode_steps_total'],
        }, outs

    sb_recs, sb_outs = {}, {}
    for k in (1, 4, 8):
        sb_recs[k], sb_outs[k] = _run_fused(k)
    small_batch = {
        'requests': sb_batch,
        'decode_slots': sb_batch,
        'max_new_tokens': sb_max_new,
        'per_k': {str(k): r for k, r in sb_recs.items()},
        'outputs_identical':
            sb_outs[1] == sb_outs[4] == sb_outs[8],
    }

    # -- tiered KV cache (ISSUE 20): the SAME mixed stream through a
    # device pool sized BELOW its concurrent contexts, with the host
    # tier absorbing the overflow. The bars: token identity with a
    # sized-to-fit run (spill/resurrect must be invisible in the
    # tokens), sustained throughput + SLO percentiles under
    # oversubscription, and resurrect-from-host TTFT strictly beating
    # recompute-from-scratch on a long cold prompt.
    fit_pages = batch * pages_per_seq          # sized-to-fit capacity
    over_pages = max(pages_per_seq + 1, int(fit_pages * 0.5))

    def _run_tiered(num_pages, host_pages):
        e = ServingEngine(model, ServingConfig(
            page_size=page_size, max_batch_size=batch,
            prefill_chunk=chunk, max_pages_per_seq=pages_per_seq,
            num_pages=num_pages, host_tier_pages=host_pages,
            spill_watermark=0.7))
        e.generate([prompts[0]], max_new_tokens=2, top_k=0)
        e.reset_stats()
        t0 = time.time()
        o = e.generate(prompts, max_new_tokens=max_new, top_k=0)
        dt = time.time() - t0
        stt = e.stats()
        pst = stt['pool']
        tab = e.request_table()
        pct = {
            label: {f'p{q}': (round(v * 1000.0, 3)
                              if (v := percentile_of(
                                  [r[key] for r in tab.values()], q))
                              is not None else None)
                    for q in (50, 90, 99)}
            for key, label in (('ttft_s', 'ttft_ms'),
                               ('e2e_s', 'e2e_ms'))}
        toks = sum(len(x) - len(p) for x, p in zip(o, prompts))
        rec = {
            'device_pages': num_pages,
            'host_pages': host_pages,
            'tokens_per_sec': toks / dt,
            'decode_tokens_per_sec': stt['decode_tokens_per_sec'],
            'preemptions': stt['preemptions_total'],
            'slo': pct,
            'spilled_pages': pst.get('tier_spilled_pages_total', 0),
            'spilled_bytes': pst.get('tier_spilled_bytes_total', 0),
            'fetched_pages': pst.get('tier_fetched_pages_total', 0),
            'fetched_bytes': pst.get('tier_fetched_bytes_total', 0),
            'resurrected_pages':
                pst.get('tier_resurrected_pages_total', 0),
        }
        e.shutdown()
        return rec, o

    fit_rec, fit_outs = _run_tiered(fit_pages, 0)
    over_rec, over_outs = _run_tiered(over_pages, fit_pages * 2)

    # resurrect-vs-recompute TTFT: one long prompt whose prefix pages
    # sit on the host tier vs the same prompt with a cold cache —
    # best-of-3 each, the fetch must beat re-running the prefill.
    # 16 pages of prompt (14 on the CPU CI shape — max_seq_len caps
    # it): long enough that prefill compute dominates the
    # (near-constant) fetch dispatch overhead
    long_pages = 16 if on_tpu else 14
    long_prompt = list(rng.randint(
        1, cfg.vocab_size, long_pages * page_size + 1))
    e = ServingEngine(model, ServingConfig(
        page_size=page_size, max_batch_size=2, prefill_chunk=chunk,
        max_pages_per_seq=long_pages + 4,
        host_tier_pages=2 * long_pages + 4))
    e.generate([long_prompt], max_new_tokens=2, top_k=0)  # warm shapes
    recompute_ttft, resurrect_ttft = [], []
    for _ in range(3):
        e.pool.reset()                        # cold: nothing cached
        e.reset_stats()
        e.generate([long_prompt], max_new_tokens=2, top_k=0)
        (r,) = e.request_table().values()
        recompute_ttft.append(r['ttft_s'])
        # prefix now registered: push it to the host tier, measure
        # the resurrect path
        spilled = e.pool.spill_lru(sync=True)
        assert spilled >= long_pages, spilled
        e.reset_stats()
        outs_r = e.generate([long_prompt], max_new_tokens=2, top_k=0)
        (r,) = e.request_table().values()
        resurrect_ttft.append(r['ttft_s'])
    resurrect_identical = outs_r[0][:len(long_prompt) + 2] \
        == e.generate([long_prompt], max_new_tokens=2,
                      top_k=0)[0][:len(long_prompt) + 2]
    e.shutdown()
    oversubscribed = {
        'requests': n_req,
        'oversubscription':
            round(fit_pages / float(over_pages), 3),
        'outputs_identical': over_outs == fit_outs,
        'sized_to_fit': fit_rec,
        'tiered': over_rec,
        'recompute_ttft_ms':
            round(min(recompute_ttft) * 1000.0, 3),
        'resurrect_ttft_ms':
            round(min(resurrect_ttft) * 1000.0, 3),
        'resurrect_ttft_speedup':
            (min(recompute_ttft) / min(resurrect_ttft)
             if min(resurrect_ttft) else None),
        'resurrect_outputs_identical': resurrect_identical,
    }
    return {
        'serve_tokens_per_sec': serve_tokens / serve_dt,
        'sequential_tokens_per_sec': seq_tps,
        'speedup_vs_sequential': (serve_tokens / serve_dt) / seq_tps,
        'decode_tokens_per_sec': st['decode_tokens_per_sec'],
        'ttft_ms_mean': st['ttft_ms_mean'],
        'slo': slo,
        'timeline': timeline,
        'batch_occupancy': st['batch_occupancy'],
        'kv_page_utilization': st['kv_page_utilization'],
        'kv_pages_high_water': st['pool']['high_water'],
        'preemptions': st['preemptions_total'],
        'requests': n_req,
        'max_new_tokens': max_new,
        'decode_slots': batch,
        'page_size': page_size,
        # quantized-KV capacity accounting (ISSUE 7): the pool's dtype
        # and real byte footprint, so the round record shows the
        # tokens-per-byte win when kv_dtype='int8' legs land
        'kv_dtype': st['pool']['kv_dtype'],
        'kv_pool_bytes': st['pool']['pool_bytes'],
        'kv_bytes_per_token': st['pool']['bytes_per_token'],
        'prompt_lens': [int(n) for n in lens],
        'kv_tokens_dense_vs_paged': [dense_cache_tokens, paged_tokens],
        'shared_prefix': shared_prefix,
        # fused decode windows (ISSUE 19): the small-batch record plus
        # flat headline keys bench_compare tracks across rounds (k=8
        # leg vs the k=1 per-token path on the identical stream)
        'small_batch': small_batch,
        'small_batch_decode_tokens_per_sec':
            sb_recs[8]['decode_tokens_per_sec'],
        'small_batch_host_bound_fraction':
            sb_recs[8]['host_bound_fraction'],
        'fused_speedup_vs_per_token':
            (sb_recs[8]['decode_tokens_per_sec']
             / sb_recs[1]['decode_tokens_per_sec']
             if sb_recs[1]['decode_tokens_per_sec'] else None),
        # tiered KV cache (ISSUE 20): the oversubscribed record plus
        # flat headline keys bench_compare tracks across rounds
        'oversubscribed': oversubscribed,
        'oversubscribed_decode_tokens_per_sec':
            over_rec['decode_tokens_per_sec'],
        'resurrect_ttft_speedup':
            oversubscribed['resurrect_ttft_speedup'],
        # serving ledger & roofline (ISSUE 17): the wall decomposition
        # (components reconcile to wall_seconds, residue surfaced),
        # the delivered/wasted goodput account, and the decode
        # bytes-moved roofline (MBU only on TPU, absolute GB/s always)
        'ledger': serve_ledger,
        'goodput': serve_goodput,
        'roofline': serve_roofline,
        'goodput_fraction': serve_goodput.get('goodput_fraction'),
        'host_bound_fraction':
            (serve_ledger or {}).get('host_bound_fraction'),
        'hbm_gbps': (serve_roofline or {}).get('hbm_gbps'),
        'mbu': (serve_roofline or {}).get('mbu'),
        # telemetry time axis (ISSUE 18): downsampled rings + alert
        # summary for the measured stream (no critical may fire on a
        # clean leg — _check_legs asserts it)
        'series': series_rec,
        'alerts': alerts_rec,
        'backend': jax.default_backend(),
    }


def bench_gpt_serve_cluster():
    """gpt_serve_cluster (ISSUE 11): a 2-replica dp serving cluster
    behind the prefix-affinity router vs the single PR-9 engine on the
    SAME sustained mixed-length stream (two system-prompt families +
    random tails). Records per-replica AND aggregate SLO percentiles
    from the lifecycle journals, router placement stats (affinity /
    least-loaded / spills / rejects), and the aggregate decode
    throughput. On the CPU dryrun the replicas interleave on one core,
    so the wall clock can't show the dp speedup — the aggregate of
    per-replica decode rates (each measured over its OWN decode time,
    the same clock the 1-chip leg uses) is the scaling signal, and the
    wall numbers ride along for hardware rounds."""
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import ServingEngine, ServingConfig
    from paddle_tpu.serving.cluster import ClusterRouter, LocalReplica
    from paddle_tpu.serving.request_trace import percentile_of

    paddle.seed(0)
    on_tpu = jax.default_backend() == 'tpu'
    if on_tpu:
        cfg = GPTConfig(vocab_size=50304, hidden_size=768,
                        num_layers=12, num_heads=12, max_seq_len=1024,
                        hidden_dropout=0.0, attn_dropout=0.0,
                        use_flash_attention=True)
        n_req, max_new, batch, page_size, chunk = 24, 48, 8, 16, 128
        sys_len, lo, hi = 128, 16, 256
    else:
        cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                        num_heads=2, max_seq_len=128,
                        hidden_dropout=0.0, attn_dropout=0.0,
                        use_flash_attention=False)
        n_req, max_new, batch, page_size, chunk = 10, 8, 3, 8, 16
        sys_len, lo, hi = 16, 2, 24
    model = GPTForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(0)
    families = [list(rng.randint(1, cfg.vocab_size, sys_len))
                for _ in range(2)]
    prompts = [families[i % 2]
               + list(rng.randint(1, cfg.vocab_size,
                                  int(rng.randint(lo, hi + 1))))
               for i in range(n_req)]
    pages_per_seq = -(-(sys_len + hi + max_new) // page_size)

    def _mk_config():
        return ServingConfig(page_size=page_size,
                             max_batch_size=batch,
                             prefill_chunk=chunk,
                             max_pages_per_seq=pages_per_seq)

    def _slo(table):
        out = {}
        for key, label in (('ttft_s', 'ttft_ms'),
                           ('tpot_s', 'tpot_ms'),
                           ('queue_wait_s', 'queue_wait_ms'),
                           ('e2e_s', 'e2e_ms')):
            vals = [r[key] for r in table.values()]
            out[label] = {
                f'p{q}': (round(p * 1000.0, 3)
                          if (p := percentile_of(vals, q)) is not None
                          else None)
                for q in (50, 90, 99)}
        return out

    # -- 1-chip baseline: the PR-9 engine on the whole stream --------------
    single = ServingEngine(model, _mk_config())
    single.generate([prompts[0]], max_new_tokens=2, top_k=0)  # warmup
    single.reset_stats()
    t0 = time.time()
    ref_outs = single.generate(prompts, max_new_tokens=max_new,
                               top_k=0)
    single_dt = time.time() - t0
    sstats = single.stats()
    single_rec = {
        'tokens_per_sec': sum(len(o) - len(p) for o, p in
                              zip(ref_outs, prompts)) / single_dt,
        'decode_tokens_per_sec': sstats['decode_tokens_per_sec'],
        'slo': _slo(single.request_table()),
        'prefill_tokens': sstats['prefill_tokens_total'],
        'prefix_hits': sstats['prefix_hits_total'],
    }
    single.shutdown()

    # -- 2-replica cluster on the SAME stream ------------------------------
    replicas = [LocalReplica(ServingEngine(model, _mk_config()), rid)
                for rid in ('r0', 'r1')]
    for r in replicas:      # same warmup the single engine got
        r.engine.generate([prompts[0]], max_new_tokens=2, top_k=0)
        r.engine.reset_stats()
    router = ClusterRouter(replicas, page_size=page_size,
                           max_queue=2 * n_req)
    t0 = time.time()
    outs = router.serve(prompts, max_new_tokens=max_new, top_k=0,
                        timeout_s=600)
    cluster_dt = time.time() - t0
    gen_tokens = sum(len(o) - len(p) for o, p in zip(outs, prompts))
    per_replica = {}
    agg_decode_tps = 0.0
    all_tables = {}
    for r in replicas:
        st = r.engine.stats()
        table = r.engine.request_table()
        all_tables.update({f'{r.replica_id}:{k}': v
                           for k, v in table.items()})
        agg_decode_tps += st['decode_tokens_per_sec']
        per_replica[r.replica_id] = {
            'requests': len(table),
            'decode_tokens_per_sec': st['decode_tokens_per_sec'],
            'prefill_tokens': st['prefill_tokens_total'],
            'prefix_hits': st['prefix_hits_total'],
            'batch_occupancy': st['batch_occupancy'],
            'slo': _slo(table),
            # per-replica goodput (ISSUE 17), read off the live ledger
            'goodput': r.engine.ledger.goodput(),
        }
    router.refresh()        # fresh statuses -> snapshot goodput sees
                            # every replica's final token counts
    snap = router.snapshot()

    # -- structured-rejection retry-hint accuracy (ISSUE 15): overload
    # a tiny-bound router over the SAME (warm) replicas, record the
    # RouterRejected retry_after_s hint, then measure how long the
    # cluster actually took to accept a retry — the hint's quality is
    # part of the round record because serve()'s throttle loop backs
    # off by it
    from paddle_tpu.serving.cluster import RouterRejected
    hint_router = ClusterRouter(replicas, page_size=page_size,
                                max_queue=2, refresh_interval_s=0.0)
    hinted = actual = None
    for p in prompts * 4:
        try:
            hint_router.submit(p, max_new_tokens=max_new, top_k=0)
        except RouterRejected as rej:
            hinted = rej.retry_after_s
            t_rej = time.time()
            break
    if hinted is not None:
        t_dead = time.time() + 300
        while time.time() < t_dead:
            hint_router.pump()
            try:
                hint_router.submit(prompts[0],
                                   max_new_tokens=max_new, top_k=0)
                actual = time.time() - t_rej
                break
            except RouterRejected:
                continue
    hint_router.run(timeout_s=600)
    retry_hint = {
        'hinted_s': hinted,
        'actual_s': actual,
        # `is not None`: a legitimate 0.0 hint is exactly the case the
        # accuracy record must not silently drop
        'hint_over_actual': (hinted / actual
                             if hinted is not None and actual
                             else None),
    }
    router.shutdown()
    return {
        'requests': n_req,
        'replicas': len(replicas),
        'max_new_tokens': max_new,
        'decode_slots_per_replica': batch,
        'page_size': page_size,
        'retry_hint': retry_hint,
        'single_engine': single_rec,
        'cluster': {
            'wall_tokens_per_sec': gen_tokens / cluster_dt,
            'aggregate_decode_tokens_per_sec': agg_decode_tps,
            'slo': _slo(all_tables),
            'per_replica': per_replica,
            'router': snap,
        },
        'aggregate_decode_speedup_vs_single':
            (agg_decode_tps / single_rec['decode_tokens_per_sec']
             if single_rec['decode_tokens_per_sec'] else None),
        # cluster-aggregated goodput (ISSUE 17): replica accounts
        # summed, with any drain-resubmit recompute repriced wasted
        'cluster_goodput': snap.get('goodput'),
        'goodput_fraction':
            (snap.get('goodput') or {}).get('goodput_fraction'),
        'affinity_hit_rate': snap['affinity_hit_rate'],
        'outputs_identical_to_single': outs == ref_outs,
        'backend': jax.default_backend(),
    }


def bench_gpt_serve_tenants():
    """gpt_serve_tenants (ISSUE 15): the adversarial multi-tenant
    stream — ONE heavy tenant flooding long requests + three light
    tenants submitting short ones mid-stream — served by the FCFS
    scheduler (no tenants configured) and by the SLO scheduler
    (priority classes + a quota on the heavy tenant) on the SAME
    stream. The acceptance numbers: light-tenant p99 e2e under the SLO
    scheduler vs its SOLO baseline (bar: <= 1.5x), and aggregate
    decode throughput vs FCFS (bar: >= ~0.9x — priority scheduling
    must not burn the pool's work-conservation). On the shared 1-core
    CPU dryrun both ratios carry wall-clock noise — the deterministic
    tokens-per-engine-sweep version of the same bars is asserted in
    tests/test_serving_tenants.py; the hardware round reads these as
    measured. The record also carries per-tenant SLO percentiles,
    quota/charged-preemption counters, and the degradation-ladder
    stage timeline."""
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import ServingEngine, ServingConfig
    from paddle_tpu.serving.request_trace import percentile_of

    paddle.seed(0)
    on_tpu = jax.default_backend() == 'tpu'
    if on_tpu:
        cfg = GPTConfig(vocab_size=50304, hidden_size=768,
                        num_layers=12, num_heads=12, max_seq_len=1024,
                        hidden_dropout=0.0, attn_dropout=0.0,
                        use_flash_attention=True)
        batch, page_size, chunk = 8, 16, 128
        heavy_n, heavy_len, heavy_new = 12, 256, 128
        light_n, light_len, light_new = 12, 24, 16
    else:
        cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                        num_heads=2, max_seq_len=128,
                        hidden_dropout=0.0, attn_dropout=0.0,
                        use_flash_attention=False)
        batch, page_size, chunk = 2, 8, 16
        heavy_n, heavy_len, heavy_new = 5, 12, 12
        light_n, light_len, light_new = 6, 4, 4
    model = GPTForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(0)
    heavy = [list(rng.randint(1, cfg.vocab_size, heavy_len))
             for _ in range(heavy_n)]
    light = [list(rng.randint(1, cfg.vocab_size, light_len))
             for _ in range(light_n)]
    pages_per_seq = -(-(heavy_len + heavy_new) // page_size)

    def _mk_engine(tenants):
        e = ServingEngine(model, ServingConfig(
            page_size=page_size, max_batch_size=batch,
            prefill_chunk=chunk, max_pages_per_seq=pages_per_seq,
            tenants=tenants))
        e.generate([heavy[0][:4]], max_new_tokens=2, top_k=0)  # warm
        if e._ladder is not None:
            # warm the stage-2 halved-chunk prefill shape too — a
            # ladder transition mid-overload must not pay a compile
            # (the measured stream would charge it to one tenant's e2e)
            e._ladder.stage = 2
            e.generate([heavy[0][:4]], max_new_tokens=2, top_k=0)
            e._ladder.stage = 0
            e._ladder._ring.clear()
        e.reset_stats()
        return e

    def _slo_pcts(table, tenant_prefix=None):
        rows = [r for r in table.values()
                if tenant_prefix is None
                or (r.get('tenant_id') or '').startswith(tenant_prefix)]
        out = {}
        for key, label in (('queue_wait_s', 'queue_wait_ms'),
                           ('e2e_s', 'e2e_ms')):
            vals = [r[key] for r in rows]
            out[label] = {
                f'p{q}': (round(p * 1000.0, 3)
                          if (p := percentile_of(vals, q)) is not None
                          else None)
                for q in (50, 90, 99)}
        return out

    def _run(tenants):
        eng = _mk_engine(tenants)
        t0 = time.time()
        hreqs = [eng.submit(p, max_new_tokens=heavy_new, top_k=0,
                            tenant_id='heavy') for p in heavy]
        for _ in range(3):
            eng.step()              # heavy saturates the slots first
        lreqs = [eng.submit(p, max_new_tokens=light_new, top_k=0,
                            tenant_id=f'light{i % 3}')
                 for i, p in enumerate(light)]
        while eng.scheduler.has_work:
            eng.step()
        dt = time.time() - t0
        st = eng.stats()
        table = eng.request_table()
        gen = sum(len(r.generated) for r in hreqs + lreqs)
        rec = {
            'wall_s': round(dt, 3),
            'tokens_per_sec': gen / dt,
            'decode_tokens_per_sec': st['decode_tokens_per_sec'],
            'preemptions': st['preemptions_total'],
            'quota_deferrals': st['quota_deferrals_total'],
            'preemptions_charged': st['preemptions_charged_total'],
            'light': _slo_pcts(table, 'light'),
            'heavy': _slo_pcts(table, 'heavy'),
            'per_tenant': {
                tid: {k: row.get(k) for k in
                      ('priority', 'submitted', 'completed',
                       'quota_deferrals', 'preemptions_charged',
                       'charge_tokens', 'tokens_billed')}
                for tid, row in
                st['tenancy'].get('tenants', {}).items()},
            'ladder': {
                'stage_transitions':
                    st['tenancy'].get('stage_transitions', 0),
                'final_stage': st['degrade_stage'],
                'timeline': [
                    {'to': h['to'], 'from': h['from'],
                     'pressure': h['pressure']}
                    for h in eng.ladder_history()],
                'max_stage': max(
                    [h['to'] for h in eng.ladder_history()] or [0]),
            },
            # goodput account (ISSUE 17): delivered/wasted identity +
            # the per-tenant split (who paid for the preempt churn)
            'goodput': eng.ledger.goodput(),
        }
        outs = [r.output_ids() for r in hreqs + lreqs]
        eng.shutdown()
        return rec, outs

    # SOLO baseline for the light tenants: their stream alone
    solo = _mk_engine(None)
    t0 = time.time()
    sreqs = [solo.submit(p, max_new_tokens=light_new, top_k=0,
                         tenant_id=f'light{i % 3}')
             for i, p in enumerate(light)]
    while solo.scheduler.has_work:
        solo.step()
    solo_p99 = percentile_of(
        [r.finish_time - r.submit_time for r in sreqs], 99)
    solo.shutdown()

    fcfs_rec, fcfs_outs = _run(None)
    # the heavy quota BILLS every admit (tokens_billed lands in the
    # record) but is sized not to bind on this stream: a binding quota
    # deliberately idles decode slots (rate limiting), which would
    # measure the quota policy, not the scheduler's work conservation
    # — the aggregate-throughput bar compares schedulers. Binding-
    # quota deferral behavior is covered in tests/test_serving_tenants.
    heavy_bill = heavy_n * (heavy_len + heavy_new)
    tenants = {'heavy': {'priority': 0,
                         'quota_tokens_per_s': float(heavy_bill),
                         'burst_tokens': float(heavy_bill),
                         'weight': 0.2},
               'light0': {'priority': 1, 'weight': 1.0},
               'light1': {'priority': 1, 'weight': 1.0},
               'light2': {'priority': 1, 'weight': 1.0}}
    slo_rec, slo_outs = _run(tenants)
    slo_light_p99 = (slo_rec['light']['e2e_ms']['p99'] or 0.0) / 1000.0
    return {
        'scheduler_comparison': {'fcfs': fcfs_rec, 'slo': slo_rec},
        'heavy_requests': heavy_n,
        'light_requests': light_n,
        'decode_slots': batch,
        'page_size': page_size,
        'solo_light_p99_e2e_ms': (round(solo_p99 * 1000.0, 3)
                                  if solo_p99 is not None else None),
        'light_p99_vs_solo':
            (slo_light_p99 / solo_p99 if solo_p99 else None),
        'aggregate_decode_vs_fcfs':
            (slo_rec['decode_tokens_per_sec']
             / fcfs_rec['decode_tokens_per_sec']
             if fcfs_rec['decode_tokens_per_sec'] else None),
        'light_p99_fcfs_over_slo':
            ((fcfs_rec['light']['e2e_ms']['p99'] or 0)
             / (slo_rec['light']['e2e_ms']['p99'] or 1)),
        # greedy tokens are scheduler-invariant: same stream, same
        # outputs per request, under FCFS and the SLO scheduler
        'outputs_identical_fcfs_vs_slo': fcfs_outs == slo_outs,
        'backend': jax.default_backend(),
    }


# ---------------------------------------------------------------------------
# leg orchestration — each leg runs in a FRESH subprocess (r5 regression:
# one process accumulated every leg's device state until RESOURCE_EXHAUSTED
# blanked 4 of 5 BASELINE configs; a leg now gets a clean XLA client and
# its engines are shut down before it reports)
# ---------------------------------------------------------------------------
LEGS = {
    'gpt_adamw': lambda: bench_gpt_1p3b('adamw'),
    'gpt_sgd': lambda: bench_gpt_1p3b('sgd'),
    'bert_base_zero2_bf16': bench_bert_config3,
    'lenet_mnist': bench_lenet_config1,
    'resnet50_dp_bf16': bench_resnet50_config2,
    'deepfm_ps': bench_deepfm_ps_config5,
    'ps_scale_ssd': bench_ps_scale,
    'gpt_serve_throughput': bench_gpt_serve,
    'gpt_serve_cluster': bench_gpt_serve_cluster,
    'gpt_serve_tenants': bench_gpt_serve_tenants,
}

_LEG_SENTINEL = 'LEG_RESULT:'


def _attach_telemetry(r):
    """Per-leg compile/device-memory telemetry (each leg is its own
    process now, so the numbers are leg-scoped, not accumulated).
    With BENCH_NUMERICS=1 the numerics sub-dict carries real grad-norm
    and nonfinite-count numbers (stat taps add one host sync per step,
    so the flag is off for headline measurements)."""
    from paddle_tpu.profiler import StepTelemetry
    snap = StepTelemetry(publish=False).snapshot()
    numerics = snap.get('numerics') or {}
    r['telemetry'] = {
        'compile_seconds_total': round(snap['compile_seconds_total'],
                                       2),
        'compiles_total': int(snap['compiles_total']),
        'device_memory': snap['device_memory'],
        'numerics': {
            'grad_norm_global': numerics.get('grad_norm_global'),
            'nonfinite_total': numerics.get('nonfinite_total'),
            'nonfinite_steps': numerics.get('nonfinite_steps'),
            'amp_skipped_steps': numerics.get('amp_skipped_steps'),
        },
        # gradient-comm model from the bucketed engines + persistent
        # compile cache (docs/performance.md) — the ISSUE 4
        # comm-bytes-drop acceptance number lives under
        # comm.comm_bytes_drop_vs_per_param_psum
        'comm': snap.get('comm'),
        # overlap schedule view (ISSUE 10): exposed vs hidden comm
        # seconds, groups/prefetch/chunk — also inside comm, but
        # surfaced top-level so the legs contract can assert it
        'comm_overlap': (snap.get('comm') or {}).get(
            'comm_overlap'),
        'compile_cache': snap.get('compile_cache'),
        # ptpu_serve_* view — only the serving leg publishes these
        'serve': snap.get('serve'),
        # fused-primitive routing counters (ISSUE 8)
        'pallas': snap.get('pallas'),
        # tuned-remat view (ISSUE 12): active policy per engine,
        # boundary-tag counts, per-site activation bytes
        'remat': snap.get('remat'),
        # async-dispatch view (ISSUE 13): per-site host gap/depth +
        # DeviceLoader prefetch totals
        'host': snap.get('host'),
        # pipeline schedule census (ISSUE 14): active schedule /
        # virtual stages / modeled bubble fraction
        'pipeline': snap.get('pipeline'),
        # step-time ledger (ISSUE 16): reconciled wall decomposition
        # + model/hardware TFLOP/s + MFU per engine
        'ledger': snap.get('ledger'),
    }
    # per-leg memory census: per-phase high-water marks + live-buffer
    # walk — the optimizer-state-sharding savings show up here
    from paddle_tpu.core import memory as _mem
    acct = _mem.accountant()
    r['memory'] = {
        'sample': acct.sample(count_buffers=True),
        'phases': {k: {f: v.get(f) for f in
                       ('high_water', 'max_delta', 'calls')}
                   for k, v in acct.phases().items()},
    }
    return r


def run_leg(name):
    """Child entry: run one leg, print its JSON on a sentinel line."""
    if os.environ.get('BENCH_NUMERICS') == '1':
        # opt-in: thread numerics taps through the leg's compiled steps
        # so the record carries per-leg grad-norm / nonfinite telemetry
        from paddle_tpu.core import flags as _flags
        _flags.set_flags({'FLAGS_tensor_stats': True})
    r = _attach_telemetry(LEGS[name]())
    print(_LEG_SENTINEL + json.dumps(r), flush=True)


def _leg_in_subprocess(name, timeout=5400):
    """Run one leg in a fresh subprocess so it gets a clean XLA client
    and the chip to itself (this parent never touches jax)."""
    import subprocess
    p = subprocess.run(
        [sys.executable, '-u', os.path.abspath(__file__), '--leg', name],
        capture_output=True, text=True, timeout=timeout)
    for line in reversed((p.stdout or '').splitlines()):
        if line.startswith(_LEG_SENTINEL):
            return json.loads(line[len(_LEG_SENTINEL):])
    raise RuntimeError(
        f"bench leg {name} produced no result (rc={p.returncode}): "
        f"{((p.stdout or '') + (p.stderr or ''))[-400:]}")


# the top-level legs every round record must carry (r5 regression +
# the ISSUE 10 self-check: the r05 record buried satellite results —
# and their errors — inside the headline leg's detail dict)
EXPECTED_LEGS = ('gpt1.3b_adamw', 'gpt1.3b_sgd', 'bert_base_zero2_bf16',
                 'lenet_mnist', 'resnet50_dp_bf16', 'deepfm_ps',
                 'ps_scale_ssd', 'gpt_serve_throughput',
                 'gpt_serve_cluster', 'gpt_serve_tenants')


def _check_legs(result):
    """Leg self-check (ISSUE 10): every result lands TOP-level under
    result.legs — never nested under another leg's detail — and the
    headline leg carries telemetry.comm_overlap. Raises on violation
    so a regressed record shape fails the round loudly instead of
    silently burying legs again."""
    legs = result.get('legs')
    assert isinstance(legs, dict), 'result.legs missing'
    missing = [k for k in EXPECTED_LEGS if k not in legs]
    assert not missing, f'legs missing from result.legs: {missing}'

    def _no_nested_legs(d, path):
        for k, v in d.items():
            assert k != 'legs', \
                f'leg buried under {"/".join(path)}/legs'
            if isinstance(v, dict):
                _no_nested_legs(v, path + (k,))

    for name, leg in legs.items():
        assert isinstance(leg, dict), f'leg {name} is not a dict'
        _no_nested_legs(leg, (name,))
    detail = result.get('detail')
    if isinstance(detail, dict):
        _no_nested_legs(detail, ('detail',))
    # headline telemetry carries the overlap view (dryrun twin asserts
    # exposed < total; at dp=1 the gauges report the modeled schedule
    # with enabled=false — presence is the contract here). A telemetry
    # collection error is its own visible record, not a shape bug.
    tel = legs['gpt1.3b_adamw'].get('telemetry') or {}
    assert 'comm_overlap' in tel or 'error' in tel, \
        'headline leg telemetry lacks comm_overlap'
    # the activation-economy view (ISSUE 12): the headline leg must
    # carry the remat record (policy + boundary counts + census) both
    # in detail and in telemetry
    assert 'remat' in tel or 'error' in tel, \
        'headline leg telemetry lacks remat'
    assert 'remat' in legs['gpt1.3b_adamw'] or 'error' in \
        legs['gpt1.3b_adamw'], 'headline leg lacks the remat record'
    # the pipeline-schedule record shape (ISSUE 14): any leg or detail
    # carrying a `pipeline` record — the schedule census bench legs and
    # telemetry attach — must look like schedule_model()/
    # pipeline_snapshot() output, so a future pipeline leg is validated
    # like the host/remat records
    def _check_pipeline_record(rec, where):
        assert isinstance(rec, dict), \
            f'{where}: pipeline record is not a dict'
        for key in ('schedule', 'virtual_stages', 'accumulate_steps',
                    'ticks', 'chunk_ticks', 'bubble_fraction'):
            assert key in rec, f'{where}: pipeline record lacks {key}'
        assert rec['schedule'] in ('1F1B', 'F-then-B', 'interleaved'), \
            f"{where}: unknown schedule {rec['schedule']!r}"
        assert 0.0 <= rec['bubble_fraction'] < 1.0, \
            f"{where}: bubble_fraction out of range"
        assert int(rec['virtual_stages']) >= 1, where

    for name, leg in legs.items():
        for holder, where in ((leg, f'legs.{name}'),
                              (leg.get('telemetry') or {},
                               f'legs.{name}.telemetry'),
                              (leg.get('detail') or {},
                               f'legs.{name}.detail')):
            rec = holder.get('pipeline') if isinstance(holder, dict) \
                else None
            if rec is not None:
                _check_pipeline_record(rec, where)
    if isinstance(detail, dict) and detail.get('pipeline') is not None:
        _check_pipeline_record(detail['pipeline'], 'detail')
    # the multi-tenant serving view (ISSUE 15): the tenants leg must
    # carry both scheduler runs, the acceptance ratios, and the
    # ladder timeline; the cluster leg must carry the retry-hint
    # accuracy record the structured RouterRejected satellite added
    tleg = legs.get('gpt_serve_tenants') or {}
    if 'error' not in tleg:
        cmp_ = tleg.get('scheduler_comparison')
        assert isinstance(cmp_, dict) and 'fcfs' in cmp_ \
            and 'slo' in cmp_, 'tenants leg lacks scheduler_comparison'
        for side in ('fcfs', 'slo'):
            for key in ('decode_tokens_per_sec', 'light', 'heavy',
                        'ladder', 'per_tenant'):
                assert key in cmp_[side], \
                    f'tenants leg {side} record lacks {key}'
        assert 'light_p99_vs_solo' in tleg \
            and 'aggregate_decode_vs_fcfs' in tleg, \
            'tenants leg lacks the acceptance ratios'
        assert 'timeline' in cmp_['slo']['ladder'], \
            'tenants leg lacks the ladder timeline'
        assert tleg.get('outputs_identical_fcfs_vs_slo') is True, \
            'SLO scheduler changed greedy outputs'
    cleg = legs.get('gpt_serve_cluster') or {}
    if 'error' not in cleg:
        assert 'retry_hint' in cleg, \
            'cluster leg lacks the retry-hint accuracy record'
    # the async-dispatch view (ISSUE 13): the headline leg must carry
    # detail.host with the dispatch window, prefetch depth, and the
    # sync-vs-windowed host-gap comparison incl. host_bound_fraction
    headline = legs['gpt1.3b_adamw']
    if 'error' not in headline:
        hostrec = headline.get('host')
        assert isinstance(hostrec, dict), 'headline leg lacks detail.host'
        assert 'dispatch_window' in hostrec and 'prefetch' in hostrec, \
            'detail.host lacks window/prefetch knobs'
        assert 'host_bound_fraction' in (hostrec.get('windowed') or {}), \
            'detail.host.windowed lacks host_bound_fraction'
        assert 'sync_loop' in hostrec, \
            'detail.host lacks the sync_loop comparison record'
    # the step-time ledger (ISSUE 16): the headline leg must carry the
    # reconciled decomposition — components sum to within 10% of the
    # measured wall (residue is one of them, surfaced separately) —
    # and the model-TFLOP/s account with the remat recompute factor
    if 'error' not in headline:
        led = headline.get('ledger')
        assert isinstance(led, dict), 'headline leg lacks detail.ledger'
        comps = led.get('components')
        assert isinstance(comps, dict), 'detail.ledger lacks components'
        for key in ('compute', 'exposed_comm', 'bubble', 'host_gap',
                    'residue'):
            assert key in comps, f'detail.ledger.components lacks {key}'
        wall = led.get('wall_seconds') or 0.0
        assert wall > 0.0, 'detail.ledger lacks wall_seconds'
        total = sum(comps.values())
        assert abs(total - wall) <= 0.10 * wall, \
            f'ledger components sum {total:.6f}s vs wall {wall:.6f}s ' \
            f'(off by more than 10%)'
        assert 'model_tflops' in led, 'detail.ledger lacks model_tflops'
        assert 'recompute_factor' in (led.get('flops') or {}), \
            'detail.ledger lacks the remat recompute factor'
        assert 'ledger' in (headline.get('telemetry') or {}) \
            or 'error' in (headline.get('telemetry') or {}), \
            'headline leg telemetry lacks ledger'
    # the serving goodput ledger (ISSUE 17): the throughput leg must
    # carry the reconciled serve-step decomposition — five components
    # summing to within 10% of the measured iteration wall (residue
    # surfaced, never hidden) — a real host_bound_fraction, and the
    # goodput account whose identity holds exactly
    sleg = legs.get('gpt_serve_throughput') or {}
    if 'error' not in sleg:
        sled = sleg.get('ledger')
        assert isinstance(sled, dict), 'serve leg lacks ledger'
        scomps = sled.get('components')
        assert isinstance(scomps, dict), 'serve ledger lacks components'
        for key in ('compute', 'host_fetch', 'schedule', 'page_stream',
                    'residue'):
            assert key in scomps, f'serve ledger components lack {key}'
        swall = sled.get('wall_seconds') or 0.0
        assert swall > 0.0, 'serve ledger lacks wall_seconds'
        stotal = sum(scomps.values())
        assert abs(stotal - swall) <= 0.10 * swall, \
            f'serve ledger components sum {stotal:.6f}s vs wall ' \
            f'{swall:.6f}s (off by more than 10%)'
        assert sled.get('host_bound_fraction') is not None, \
            'serve ledger lacks host_bound_fraction'
        sgp = sleg.get('goodput')
        assert isinstance(sgp, dict), 'serve leg lacks goodput'
        assert sgp['delivered_tokens'] + sgp['wasted_tokens'] \
            == sgp['emitted_tokens'], \
            'serve goodput identity broken (delivered + wasted != emitted)'
        sroof = sleg.get('roofline')
        assert isinstance(sroof, dict), 'serve leg lacks roofline'
        assert 'decode_bytes_per_iteration' in sroof, \
            'serve roofline lacks decode_bytes_per_iteration'
        # fused decode windows (ISSUE 19): the small-batch record —
        # the same stream at fused k in {1, 4, 8}, token-identical,
        # with decode tok/s and host_bound_fraction side by side, and
        # the k>1 legs actually fusing
        sb = sleg.get('small_batch')
        assert isinstance(sb, dict), 'serve leg lacks small_batch'
        assert sb.get('outputs_identical') is True, \
            'small_batch outputs differ across fused k'
        per_k = sb.get('per_k')
        assert isinstance(per_k, dict) and set(per_k) == {'1', '4',
                                                          '8'}, \
            'small_batch.per_k must carry k in {1, 4, 8}'
        for k, r in per_k.items():
            for key in ('decode_tokens_per_sec', 'host_bound_fraction',
                        'fused_windows', 'fused_iterations',
                        'fused_tokens', 'decode_steps'):
                assert key in r, f'small_batch.per_k[{k}] lacks {key}'
            if k == '1':
                assert r['fused_windows'] == 0, \
                    'per-token leg reported fused windows'
            else:
                assert r['fused_windows'] > 0, \
                    f'fused k={k} leg never fused'
                assert r['fused_tokens'] <= r['fused_iterations'] \
                    * sb['decode_slots'], \
                    f'small_batch k={k} token overcount'
        assert isinstance(
            sleg.get('small_batch_decode_tokens_per_sec'),
            (int, float)), 'serve leg lacks flat small-batch tok/s'
        assert isinstance(sleg.get('fused_speedup_vs_per_token'),
                          (int, float)), \
            'serve leg lacks fused_speedup_vs_per_token'
        # tiered KV cache (ISSUE 20): the oversubscribed record — a
        # device pool below its concurrent contexts with the host tier
        # underneath, token-identical to the sized-to-fit run, with
        # real spill traffic and resurrect TTFT beating recompute
        ov = sleg.get('oversubscribed')
        assert isinstance(ov, dict), 'serve leg lacks oversubscribed'
        assert ov.get('outputs_identical') is True, \
            'oversubscribed outputs differ from sized-to-fit'
        assert ov.get('resurrect_outputs_identical') is True, \
            'resurrected stream outputs differ'
        assert ov.get('oversubscription', 0) > 1.0, \
            'oversubscribed leg did not oversubscribe the pool'
        tr = ov.get('tiered')
        assert isinstance(tr, dict), 'oversubscribed lacks tiered rec'
        for key in ('device_pages', 'host_pages', 'tokens_per_sec',
                    'decode_tokens_per_sec', 'slo', 'spilled_pages',
                    'spilled_bytes', 'fetched_pages', 'fetched_bytes',
                    'resurrected_pages'):
            assert key in tr, f'oversubscribed.tiered lacks {key}'
        assert tr['spilled_pages'] > 0, \
            'oversubscribed leg never spilled to the host tier'
        assert isinstance(ov.get('resurrect_ttft_ms'), (int, float)) \
            and isinstance(ov.get('recompute_ttft_ms'), (int, float)), \
            'oversubscribed lacks the TTFT pair'
        assert ov['resurrect_ttft_ms'] < ov['recompute_ttft_ms'], \
            'resurrect-from-host TTFT did not beat recompute ' \
            f"({ov['resurrect_ttft_ms']}ms vs " \
            f"{ov['recompute_ttft_ms']}ms)"
        assert isinstance(
            sleg.get('oversubscribed_decode_tokens_per_sec'),
            (int, float)), 'serve leg lacks flat oversubscribed tok/s'
    # the telemetry time axis (ISSUE 18): the headline and serve legs
    # carry the downsampled history-ring block + the alert summary, and
    # a clean leg must not have fired a critical rule — an alert there
    # is a real regression (pool saturation, degrade ladder, dead
    # publish cadence), not record noise
    for name in ('gpt1.3b_adamw', 'gpt_serve_throughput'):
        leg = legs.get(name) or {}
        if 'error' in leg:
            continue
        arec = leg.get('alerts')
        assert isinstance(arec, dict), f'{name} leg lacks alerts summary'
        for key in ('rules', 'evals', 'fired_total', 'fired_critical',
                    'active'):
            assert key in arec, f'{name} leg alerts summary lacks {key}'
        assert arec['fired_critical'] == 0, \
            f"{name}: critical alert fired on a clean leg " \
            f"({arec['fired_by_severity']}, active={arec['active']})"
        srec = leg.get('series')
        assert isinstance(srec, dict) and srec, \
            f'{name} leg lacks the history-ring series block'
        for sk, sv in srec.items():
            assert 't' in sv and 'v' in sv and len(sv['t']) == \
                len(sv['v']), f'{name}.series.{sk} torn'

    def _check_goodput_identity(gp, where):
        if not isinstance(gp, dict):
            return
        assert gp['delivered_tokens'] + gp['wasted_tokens'] \
            == gp['emitted_tokens'], \
            f'{where}: goodput identity broken'

    if 'error' not in cleg:
        _check_goodput_identity(cleg.get('cluster_goodput'),
                                'cluster leg')
    if 'error' not in tleg:
        for side in ('fcfs', 'slo'):
            _check_goodput_identity(
                (tleg.get('scheduler_comparison') or {})
                .get(side, {}).get('goodput'), f'tenants leg {side}')
    # record stamps (ISSUE 16): schema version + round id at top level
    assert result.get('schema_version'), 'result lacks schema_version'
    assert result.get('round'), 'result lacks round id'
    return True


def _round_floats(r, ndigits=2):
    if isinstance(r, float):
        return round(r, ndigits)
    if isinstance(r, dict):
        return {k: _round_floats(v, ndigits) for k, v in r.items()}
    if isinstance(r, list):
        return [_round_floats(v, ndigits) for v in r]
    return r


def main():
    g = _leg_in_subprocess('gpt_adamw')
    detail = {
        'ms_per_step': round(g['ms_per_step'], 1),
        'tokens_per_sec': round(g['tokens_per_sec'], 1),
        'tflops': round(g['tflops'], 2),
        'params': g['params'],
        'seq_len': g['seq_len'],
        'microbatches': g['microbatches'],
        'optimizer': 'adamw_bf16_moments',
        # ISSUE 13: async step pipeline — dispatch window/prefetch depth
        # + host-gap before (sync_loop) vs after (windowed) + the
        # host_bound_fraction BENCH_r06 reads (health_dump host)
        'host': g.get('host'),
        # ISSUE 16: the reconciled step-wall ledger + MFU account
        # (bench_compare renders two rounds of these side by side)
        'ledger': g.get('ledger'),
        # ISSUE 8: which fused Pallas primitives were active in the
        # headline step (health_dump pallas renders this)
        'fused_primitives': g.get('fused_primitives'),
        'live_buffers_after_shutdown':
            g.get('live_buffers_after_shutdown'),
        'live_bytes_after_shutdown': g.get('live_bytes_after_shutdown'),
        'memory': g.get('memory'),
    }
    # every leg reports at TOP level (result.legs.<name>), errors
    # included — the r5 record buried the satellite legs (and their
    # RESOURCE_EXHAUSTED errors) inside the headline leg's detail dict
    legs = {'gpt1.3b_adamw': dict(detail)}
    for key, src in (
            ('gpt1.3b_sgd', 'gpt_sgd'),
            ('bert_base_zero2_bf16', 'bert_base_zero2_bf16'),
            ('lenet_mnist', 'lenet_mnist'),
            ('resnet50_dp_bf16', 'resnet50_dp_bf16'),
            ('deepfm_ps', 'deepfm_ps'),
            ('ps_scale_ssd', 'ps_scale_ssd'),
            ('gpt_serve_throughput', 'gpt_serve_throughput'),
            ('gpt_serve_cluster', 'gpt_serve_cluster'),
            ('gpt_serve_tenants', 'gpt_serve_tenants'),
    ):
        try:
            r = _leg_in_subprocess(src)
            if src == 'gpt_sgd':
                r = {k: r[k] for k in ('mfu', 'ms_per_step',
                                       'tokens_per_sec', 'memory')
                     if k in r}
            elif src == 'bert_base_zero2_bf16':
                r = {k: r[k] for k in ('samples_per_sec', 'ms_per_step',
                                       'mfu', 'memory', 'host')
                     if k in r}
            elif src == 'gpt_serve_throughput':
                # serving telemetry rides with its own leg's child
                r.setdefault('telemetry_serve',
                             (r.pop('telemetry', None) or {}).get(
                                 'serve'))
                r.pop('memory', None)
            legs[key] = _round_floats(
                r, 4 if src in ('gpt_sgd', 'bert_base_zero2_bf16',
                                'gpt_serve_throughput',
                                'gpt_serve_cluster',
                                'gpt_serve_tenants') else 2)
        except Exception as e:       # the record still lists the leg;
            legs[key] = {'error': repr(e)[:200]}    # the exit code fails
    # per-leg compile/memory telemetry comes from the headline child
    # (each leg is its own process — no cross-leg accumulation)
    detail['telemetry'] = g.get('telemetry', {})
    # the legs snapshot was taken before telemetry landed in detail —
    # the top-level contract says every leg carries its own
    legs['gpt1.3b_adamw']['telemetry'] = detail['telemetry']
    result = {
        # record contract (ISSUE 16): schema_version gates what
        # bench_compare may assume about the shape; round identifies
        # the bench round without relying on the artifact filename
        'schema_version': BENCH_SCHEMA_VERSION,
        'round': os.environ.get('BENCH_ROUND') or _next_round_id(),
        'metric': 'gpt1.3b_adamw_trainstep_mfu',
        'value': round(g['mfu'], 4),
        'unit': 'fraction_of_device_bf16_peak',
        'vs_baseline': round(g['mfu'] / TARGET_MFU, 4),
        'legs': legs,
        'detail': detail,
    }
    failed = sorted(k for k, v in legs.items() if 'error' in v)
    if failed:
        print(json.dumps(result))
        sys.exit(f'bench.py: legs failed: {", ".join(failed)}')
    _check_legs(result)
    print(json.dumps(result))


if __name__ == '__main__':
    if len(sys.argv) >= 3 and sys.argv[1] == '--leg':
        run_leg(sys.argv[2])
    else:
        main()
