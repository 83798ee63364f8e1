"""Launcher + elastic tests (reference pattern: test_fleet_launch_*.sh,
test_fleet_elastic_manager.py — CLI-level, single host)."""
import os
import subprocess
import sys
import tempfile
import time

import numpy as np
import pytest

from paddle_tpu.core.native import load_native, TCPStore

pytestmark = pytest.mark.skipif(load_native() is None,
                                reason="native lib unavailable")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_launch_single_node_env():
    """fleetrun single-node: trainer sees the PADDLE_* env."""
    with tempfile.TemporaryDirectory() as tmp:
        script = os.path.join(tmp, 'train.py')
        with open(script, 'w') as f:
            f.write(
                "import os\n"
                "assert os.environ['PADDLE_TRAINER_ID'] == '0'\n"
                "assert os.environ['PADDLE_TRAINERS_NUM'] == '1'\n"
                "print('TRAINER_OK')\n")
        out = subprocess.run(
            [sys.executable, '-m', 'paddle_tpu.distributed.launch', script],
            capture_output=True, text=True, cwd=REPO,
            env={**os.environ, 'PYTHONPATH': REPO})
        assert 'TRAINER_OK' in out.stdout, out.stderr


def test_launch_two_node_rendezvous():
    """Two fleetrun pods on localhost rendezvous via the TCP store and each
    trainer learns the full endpoint list (reference: 2-proc dist tests)."""
    with tempfile.TemporaryDirectory() as tmp:
        script = os.path.join(tmp, 'train.py')
        with open(script, 'w') as f:
            f.write(
                "import os\n"
                "eps = os.environ['PADDLE_TRAINER_ENDPOINTS'].split(',')\n"
                "assert len(eps) == 2, eps\n"
                "print('RANK', os.environ['PADDLE_TRAINER_ID'], 'OK')\n")
        port = 17170 + np.random.RandomState().randint(500)
        env = {**os.environ, 'PYTHONPATH': REPO}
        p0 = subprocess.Popen(
            [sys.executable, '-m', 'paddle_tpu.distributed.launch',
             '--nnodes', '2', '--node_rank', '0',
             '--master', f'127.0.0.1:{port}', script],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            cwd=REPO, env=env)
        p1 = subprocess.Popen(
            [sys.executable, '-m', 'paddle_tpu.distributed.launch',
             '--nnodes', '2', '--node_rank', '1',
             '--master', f'127.0.0.1:{port}', script],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            cwd=REPO, env=env)
        out0, _ = p0.communicate(timeout=60)
        out1, _ = p1.communicate(timeout=60)
        assert 'RANK 0 OK' in out0, out0
        assert 'RANK 1 OK' in out1, out1
        assert p0.returncode == 0 and p1.returncode == 0


def test_launch_elastic_restart():
    """--elastic restarts a crashing trainer up to max_restarts."""
    with tempfile.TemporaryDirectory() as tmp:
        marker = os.path.join(tmp, 'count')
        script = os.path.join(tmp, 'train.py')
        with open(script, 'w') as f:
            f.write(
                f"import os, sys\n"
                f"p = {marker!r}\n"
                f"n = int(open(p).read()) if os.path.exists(p) else 0\n"
                f"open(p, 'w').write(str(n + 1))\n"
                f"sys.exit(1 if n < 2 else 0)\n")
        out = subprocess.run(
            [sys.executable, '-m', 'paddle_tpu.distributed.launch',
             '--nnodes', '1', '--elastic', '--max_restarts', '5', script],
            capture_output=True, text=True, cwd=REPO, timeout=90,
            env={**os.environ, 'PYTHONPATH': REPO})
        assert out.returncode == 0, out.stdout + out.stderr
        assert open(marker).read() == '3'  # crashed twice, then succeeded


def test_elastic_manager_membership():
    from paddle_tpu.distributed.fleet.elastic import (ElasticManager,
                                                      ElasticStatus)
    master = TCPStore(is_master=True)
    os.environ['PADDLE_CURRENT_ENDPOINT'] = 'hostA:1'
    m1 = ElasticManager(store=master, job_id='j1', np_min=1,
                        heartbeat_interval=0.2, dead_after=1.5)
    m1.register()
    c2 = TCPStore(port=master.port)
    os.environ['PADDLE_CURRENT_ENDPOINT'] = 'hostB:1'
    m2 = ElasticManager(store=c2, job_id='j1', np_min=1,
                        heartbeat_interval=0.2, dead_after=1.5)
    m2.register()
    time.sleep(0.5)
    known = ['hostA:1', 'hostB:1']
    assert m1.watch(known) == ElasticStatus.HOLD
    # hostB dies: stop its heartbeat, wait past dead_after
    m2.exit()
    time.sleep(2.0)
    assert m1.watch(known) == ElasticStatus.RESTART
    assert m1.hosts(known) == ['hostA:1']
    m1.exit()
    c2.close()
    master.close()


def test_multihost_world_via_fleetrun():
    """The full DCN deployment shape: two fleetrun pods rendezvous over the
    TCP store, form ONE jax.distributed world (2 procs x 4 virtual chips),
    and run a cross-process psum (reference: multi-node NCCL world; here
    PJRT multi-controller)."""
    with tempfile.TemporaryDirectory() as tmp:
        script = os.path.join(tmp, 'train.py')
        with open(script, 'w') as f:
            f.write(f'''
import sys, os
sys.path.insert(0, {REPO!r})
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'
os.environ['JAX_PLATFORMS'] = 'cpu'
import jax
jax.config.update('jax_platforms', 'cpu')
import paddle_tpu as paddle
paddle.distributed.init_parallel_env()
assert jax.process_count() == 2
assert jax.device_count() == 8
import numpy as np_, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map
from jax.experimental import multihost_utils
mesh = Mesh(np_.array(jax.devices()).reshape(8), ('dp',))
arr = multihost_utils.host_local_array_to_global_array(
    np_.full((4, 1), float(os.environ['PADDLE_TRAINER_ID']) + 1.0,
             np_.float32), mesh, P('dp'))
out = jax.jit(shard_map(lambda x: jax.lax.psum(x, 'dp'), mesh=mesh,
                        in_specs=P('dp'), out_specs=P('dp')))(arr)
local = multihost_utils.global_array_to_host_local_array(out, mesh,
                                                         P('dp'))
assert float(np_.asarray(local.addressable_data(0))[0, 0]) == 12.0
print('MULTIHOST_OK', flush=True)
''')
        port = 18400 + np.random.RandomState().randint(400)
        env = {**os.environ, 'PYTHONPATH': REPO}
        procs = []
        for rank in (1, 0):
            procs.append(subprocess.Popen(
                [sys.executable, '-m', 'paddle_tpu.distributed.launch',
                 '--nnodes', '2', '--node_rank', str(rank),
                 '--master', f'127.0.0.1:{port}', script],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True, cwd=REPO, env=env))
        outs = [p.communicate(timeout=120)[0] for p in procs]
        if any('Multiprocess computations aren\'t implemented on the CPU '
               'backend' in o for o in outs):
            # this image's jaxlib has no cross-process CPU collective
            # backend (gloo plugin absent) — the launch/rendezvous path
            # itself worked up to the psum, which is all we can check
            pytest.skip("jaxlib CPU backend lacks multiprocess "
                        "collectives in this image")
        for p, o in zip(procs, outs):
            assert 'MULTIHOST_OK' in o, o[-800:]
            assert p.returncode == 0
