"""chip_smoke.py's phases at a tiny size on the CPU mesh (the device gate
lives in the script's main, not in the phases), plus the properties the
chip run depends on: importing the package initializes no backend, and a
spawned replica worker is never defaulted onto the CPU."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402

# the shape of chip_smoke.FULL at toy widths: 2 heads of 16, 2-layer
# models, 64-token sequences, 8-slot pages
TINY = dict(
    vocab=128, hidden=32, heads=2, seq=64,
    depth=2, ab_depth=2, A=2, mb=1, steps=3, lr=1e-3,
    serve_depth=2, page_size=8, batch=2, chunk=16,
    prompt_lo=8, prompt_hi=24, new_tokens=4, requests=3,
    fused_k=2, spec_k=2,
    kernel_seq=32, opt_elems=700,
    multi_depth=2, multi_seq=32, multi_steps=2,
    sparse=dict(heads=4, kv_heads=2, head_dim=16, window=24,
                page_size=8, pages=8, batch=2, chunk=20,
                contexts=(3, 23, 25, 64),
                experts=8, top_k=2, hidden=32, width=16),
    hybrid=dict(heads=4, kv_heads=2, head_dim=16, window=24,
                page_size=8, pages=8, batch=2, chunk=20,
                contexts=(3, 23, 25, 64), prefill_rows=2,
                channels=128, states=16),
    latent=dict(heads=4, value=16, rotary=8, page_size=8, pages=8,
                batch=2, chunk=40, prefill_rows=2, contexts=(3, 64),
                ragged=((25, 3), (40, 0))),
)


def test_kernels_phase():
    errs = chip_smoke.phase_kernels(TINY)
    assert len(errs) >= 25 + 16 + 4 + 16 + 2 + 2 + 2


def test_train_phase():
    out = chip_smoke.phase_train(TINY, require_kernels=False)
    assert out['losses'][-1] < out['losses'][0]


def test_serve_phase():
    meter = chip_smoke.CompileMeter()
    out = chip_smoke.phase_serve(TINY, require_kernels=False, meter=meter)
    assert out['logit_gap'] <= chip_smoke.TOL_LOGIT_GAP


@pytest.mark.slow      # ~20 s: four engine builds on the virtual mesh
def test_multichip_phase():
    out = chip_smoke.phase_multichip(TINY)
    assert out['pipeline_rel'] < chip_smoke.TOL_PIPELINE


def test_a_failing_phase_raises():
    """No phase failure is caught into a record: a non-finite loss
    propagates out of the phase (and so out of main, exit != 0)."""
    with pytest.raises(AssertionError, match='non-finite loss'):
        chip_smoke.phase_train(dict(TINY, lr=float('nan')),
                               require_kernels=False)


def test_main_refuses_the_cpu():
    """Run as the driver runs it, on a machine without a chip: a
    non-zero exit and no result line."""
    p = subprocess.run([sys.executable, os.path.join(ROOT, 'chip_smoke.py')],
                       capture_output=True, text=True, timeout=120,
                       env=dict(os.environ, JAX_PLATFORMS='cpu'))
    assert p.returncode != 0
    assert 'no accelerator' in p.stderr
    assert '"ok"' not in p.stdout


def test_result_line_has_exactly_the_contract_keys(monkeypatch, capsys):
    """The driver parses the last stdout line and refuses any key beyond
    {ok, device{platform, kind, count}}; the summary goes on the line
    before it."""
    import json
    device = {'platform': 'tpu', 'kind': 'TPU v5 lite', 'count': 1}
    monkeypatch.setattr(chip_smoke, 'device_gate', lambda: dict(device))
    monkeypatch.setattr(chip_smoke, 'phase_kernels', lambda size: [0.0])
    monkeypatch.setattr(chip_smoke, 'phase_train', lambda size: {})
    monkeypatch.setattr(chip_smoke, 'phase_serve', lambda size, meter: {})
    chip_smoke.main([])
    lines = capsys.readouterr().out.splitlines()
    assert json.loads(lines[-1]) == {'ok': True, 'device': device}
    assert lines[-2].startswith('[chip_smoke] summary ')
    summary = json.loads(lines[-2].split('summary ', 1)[1])
    assert summary['claim'] is None
    assert summary['phases']['multichip'].startswith('not run')


def test_import_initializes_no_backend():
    code = (
        'import sys; sys.path.insert(0, %r)\n'
        'import paddle_tpu, paddle_tpu.distributed.launch\n'
        'import paddle_tpu.serving.cluster\n'
        'from jax._src import xla_bridge\n'
        'print("BACKENDS", list(xla_bridge._backends))\n' % ROOT)
    p = subprocess.run([sys.executable, '-c', code], capture_output=True,
                       text=True, timeout=120)
    assert p.returncode == 0, p.stderr
    assert 'BACKENDS []' in p.stdout


def test_spawned_replica_env_gains_no_platform(monkeypatch):
    """RemoteReplica.spawn passes the parent's environment through: a
    worker spawned from a TPU host must not be defaulted onto the CPU."""
    from paddle_tpu.serving.cluster import replica
    seen = {}

    class Stop(Exception):
        pass

    def fake_popen(cmd, env=None, **kw):
        seen['env'] = env
        raise Stop

    monkeypatch.delenv('JAX_PLATFORMS')
    monkeypatch.setattr(subprocess, 'Popen', fake_popen)
    with pytest.raises(Stop):
        replica.RemoteReplica.spawn('r0', {}, env={'EXTRA': '1'})
    assert 'JAX_PLATFORMS' not in seen['env']
    assert seen['env']['EXTRA'] == '1'
