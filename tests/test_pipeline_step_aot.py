"""The GPT trainer cell's step, compiled for a described v5e (ISSUE 35):
at pp=1 the pipeline engine's own reverse scan adds each layer's weight
gradient into the accumulation buffer where the matmul makes it, and
what `use_remat=True` saves is fitted to the chip — so the flash forward
kernel has ONE call site, the block's matmuls are not run again, no
`[L, ...]` tree of fresh gradients leaves the layer scan, and at 24
layers the reckoning and the compiler agree on which policy fits.

AOT compiles by the installed TPU compiler, as test_kernel_names_aot.py
(its fixtures; skipped where no v5e can be described): nothing runs. The
engine is the cell's (`benchmarks/runners/train_pipeline.build_engine`)
at the cell's widths and 2 layers, its mesh swapped for a 1x1 mesh of
the described chip; the step is lowered on abstract shapes, at 24 layers
too (the layers are a scan: 8 s of compile either way).
"""
import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from test_kernel_names_aot import topo, as_on_tpu   # noqa: F401 fixtures

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# `memory_stats()['bytes_limit']` of the chip (my chip run, PR 35); the
# compiler's own limit reads "Used ... of 15.75G hbm"
CHIP_BYTES = 16_909_336_064
BUILT = 2


@pytest.fixture(scope='module')
def cell():
    with open(os.path.join(ROOT, 'benchmarks/configs/gpt3-1.3b.json')) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, 'benchmarks/traffic/pretrain-2k.json')) as f:
        return cfg, json.load(f)


@pytest.fixture
def engine(cell, topo, as_on_tpu, monkeypatch):
    from benchmarks.runners import train_pipeline
    from paddle_tpu.distributed.fleet.meta_parallel import spmd_pipeline
    cfg, job = cell
    eng = train_pipeline.build_engine(dict(cfg, num_layers=BUILT), job, 0)
    eng.mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ('dp', 'pp'))
    # a described device has no allocator to ask
    monkeypatch.setattr(spmd_pipeline, '_device_bytes_limit',
                        lambda device: CHIP_BYTES)
    yield eng
    eng.shutdown()


def lower(eng, job, layers):
    """The engine's step lowered for the described chip with `layers`
    stacked layers of the shapes it was built with."""
    def like(a, spec, stacked=False):
        shape = tuple(a.shape)
        if stacked and shape[:1] == (BUILT,):
            shape = (layers,) + shape[1:]
        return jax.ShapeDtypeStruct(
            shape, a.dtype, sharding=NamedSharding(eng.mesh, spec))
    groups = ('embed', 'blocks', 'head')
    params = {g: {n: like(a, eng._specs[g][n], g == 'blocks')
                  for n, a in eng._params[g].items()} for g in groups}
    states = {g: {n: {k: like(v, eng._state_specs[g][n][k], g == 'blocks')
                      for k, v in st.items()}
                  for n, st in eng._states[g].items()} for g in groups}
    states['_buckets'] = []
    rep = NamedSharding(eng.mesh, P())
    batch = jax.ShapeDtypeStruct(
        (job['accumulate_steps'] * job['microbatch'], job['seq_len']),
        jnp.int32, sharding=rep)
    scalar = jax.ShapeDtypeStruct((), jnp.float32, sharding=rep)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)
    return eng._build().lower(params, states, scalar, scalar, key, batch,
                              batch)


def scans(jaxpr):
    """Every scan equation of a jaxpr, nested ones too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == 'scan':
            yield eqn
        for v in eqn.params.values():
            inner = getattr(v, 'jaxpr', v)
            if hasattr(inner, 'eqns'):
                yield from scans(inner)


def test_the_small_step_runs_no_matmul_and_no_flash_forward_twice(
        engine, cell):
    # 3 layers, so that a stacked weight's shape is no activation's
    lowered = lower(engine, cell[1], 3)
    assert engine._remat_reckoned['policy'] == 'attn_mlp_boundaries'
    text = lowered.compile().as_text()
    # the backward reads the saved `flash_o` / `flash_lse`: no second site
    assert len(re.findall(r'%flash_attention_fwd(?:\.\d+)? = ', text)) == 1
    # 4 forward + 8 backward in the block, 3 in the head: qkv, out_proj
    # and fc1 are not run again in the backward
    assert len(re.findall(r' convolution\(', text)) <= 15
    # the four weight gradients are still matmuls whose epilogue writes
    # row l of the stacked buffer, in place, now the ACCUMULATION buffer
    written = re.findall(
        r'= bf16\[3,(\d+),(\d+)\]\S* fusion\([^\n]*kind=kOutput[^\n]*'
        r'"aliasing_operands"', text)
    assert sorted(written) == sorted(
        [('2048', '2048'), ('2048', '6144'), ('2048', '8192'),
         ('8192', '2048')])


def test_no_gradient_tree_leaves_the_layer_scan(engine, cell):
    """The backward over the layers is the engine's reverse scan, and
    everything it returns is its carry (dx and the accumulation
    buffer): nothing is stacked over the layers."""
    batch = cell[1]['accumulate_steps'] * cell[1]['microbatch']
    ids = jnp.zeros((batch, cell[1]['seq_len']), jnp.int32)
    jaxpr = jax.make_jaxpr(engine._build())(
        engine._params, engine._states, jnp.float32(0), jnp.float32(1),
        jnp.zeros((2,), jnp.uint32), ids, ids).jaxpr
    backward = [e for e in scans(jaxpr) if e.params['reverse']]
    assert len(backward) == 1
    assert len(backward[0].outvars) == backward[0].params['num_carry']
    stacked = [v.aval.shape for e in scans(jaxpr) if not e.params['reverse']
               for v in e.outvars[e.params['num_carry']:]]
    # the forward scan stacks residuals, never a weight's shape
    weights = {tuple(a.shape) for a in engine._params['blocks'].values()}
    assert stacked and not weights & set(stacked)


def test_at_24_layers_the_reckoning_and_the_compiler_agree(engine, cell):
    lowered = lower(engine, cell[1], 24)
    r = engine._remat_reckoned
    assert r['policy'] == 'attn_mlp_boundaries' and r['bytes_limit'] == \
        CHIP_BYTES
    need = r['fixed_bytes'] + r['working_bytes'] + \
        r['held_bytes'][r['policy']]
    assert need <= CHIP_BYTES
    # 168 MB a layer: the input, qkv, the kernel's output and logsumexp,
    # out_proj's and fc1's outputs, of [2, 2048] tokens
    assert r['held_bytes'][r['policy']] == 24 * 2 * 2048 * (
        2 * (2048 + 6144 + 2048 + 2048 + 8192) + 4 * 16)
    compiled = lowered.compile()          # RESOURCE_EXHAUSTED if not
    peak = compiled.memory_analysis().peak_memory_in_bytes
    assert abs(need - peak) <= 0.02 * peak, (need, peak)
    # the second gradient tree (2.84 GB) would not have fitted beside it
    assert need + r['fixed_bytes'] // 4 > CHIP_BYTES
