"""The legs that no runner of `benchmarks/` has yet (ROADMAP W10), each
a configuration BASELINE.json names: config 1 `lenet_mnist`, config 2
`resnet50_dp_bf16`, config 5 `deepfm_ps` and its scale twin
`ps_scale_ssd`. The repo's benchmark is `benchmarks/run.py`
(`BENCHMARK.json`); nothing here is a yardstick, and a number from here
is a chip number only when it was run on one.

`python bench.py` runs each leg in a fresh subprocess (a clean XLA
client and the chip to itself; this parent never touches jax) and
prints ONE JSON line {"legs": {<name>: record}}; a leg that fails is
listed with its error and the exit code is non-zero.
`python bench.py --leg <name>` runs one leg in this process.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np


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


LEGS = {
    'lenet_mnist': bench_lenet_config1,
    'resnet50_dp_bf16': bench_resnet50_config2,
    'deepfm_ps': bench_deepfm_ps_config5,
    'ps_scale_ssd': bench_ps_scale,
}

_LEG_SENTINEL = 'LEG_RESULT:'


def _attach_telemetry(r):
    """Per-leg compile/device-memory telemetry (each leg is its own
    process now, so the numbers are leg-scoped, not accumulated).
    With BENCH_NUMERICS=1 the numerics sub-dict carries real grad-norm
    and nonfinite-count numbers (stat taps add one host sync per step,
    so the flag is off by default)."""
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


def _check_legs(result):
    """Record self-check: every leg lands TOP-level under result.legs,
    never nested under another leg, and a `pipeline` record any leg or
    its telemetry carries looks like schedule_model()/
    pipeline_snapshot() output. Raises on violation."""
    legs = result.get('legs')
    assert isinstance(legs, dict), 'result.legs missing'
    missing = [k for k in LEGS if k not in legs]
    assert not missing, f'legs missing from result.legs: {missing}'

    def _no_nested_legs(d, path):
        for k, v in d.items():
            assert k != 'legs', \
                f'leg buried under {"/".join(path)}/legs'
            if isinstance(v, dict):
                _no_nested_legs(v, path + (k,))

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
        assert isinstance(leg, dict), f'leg {name} is not a dict'
        _no_nested_legs(leg, (name,))
        for holder, where in ((leg, f'legs.{name}'),
                              (leg.get('telemetry') or {},
                               f'legs.{name}.telemetry')):
            rec = holder.get('pipeline')
            if rec is not None:
                _check_pipeline_record(rec, where)
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
    # every leg reports at TOP level (result.legs.<name>), errors
    # included: the record still lists a failed leg, the exit code fails
    legs = {}
    for name in LEGS:
        try:
            legs[name] = _round_floats(_leg_in_subprocess(name))
        except Exception as e:
            legs[name] = {'error': repr(e)[:200]}
    result = {'legs': legs}
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
