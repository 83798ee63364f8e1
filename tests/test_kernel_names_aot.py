"""Every Mosaic call of the two main paths compiles, for a described
v5e, to a `tpu_custom_call` instruction that carries the KERNEL's name —
bare, under jax.grad and under jax.checkpoint — so the device trace's
rows (`benchmarks/trace_reduce.py:op_class` reads the instruction name)
are `pallas:flash_attention_fwd`, ... and not the name of the transform
around the call (`pallas:checkpoint`, `pallas:jvp__`; PERF.md §5, PR 24).

AOT compiles by the installed TPU compiler (on-chip-measurement guide
§2, third rehearsal): nothing runs, no chip is needed. The topology is
described inside a module-scoped fixture, never at import; this is the
one test file that loads libtpu that way.
"""
import os
import re

import pytest

os.environ.setdefault('TPU_LOG_DIR', 'disabled')

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

BF16 = jnp.bfloat16
_CALL = re.compile(r'%([A-Za-z0-9_]+?)(?:\.\d+)? = [^\n]*'
                   r'custom_call_target="tpu_custom_call"')


@pytest.fixture(scope='module')
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:
        pytest.skip(f'no v5e:2x2 topology can be described here: {e}')


@pytest.fixture(scope='module')
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_on_tpu(monkeypatch):
    """The scaffold asks jax.default_backend() whether to interpret;
    the compile below is for the TPU, so it must not. The persistent
    compile cache cannot read an entry written without a chip: off."""
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    yield
    jax.config.update('jax_enable_compilation_cache', was)
    compilation_cache.reset_cache()


def mosaic_calls(fn, shapes, sharding):
    """The names (without `.N`) of the tpu_custom_call instructions in
    `fn` compiled for one described chip."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    return set(_CALL.findall(text))


def grad_of(fn, n_args):
    """The value and every argument's gradient, so that neither the
    forward nor any backward kernel is dead code."""
    return jax.value_and_grad(
        lambda *a: fn(*a).astype(jnp.float32).sum(),
        argnums=tuple(range(n_args)))


def flash_gpt():
    """GPT-3 1.3B's attention: [B*H, L, D] = [16, 2048, 128], causal."""
    from paddle_tpu.ops.pallas import flash_attention as fa
    qkv = ((16, 2048, 128), BF16)
    return fa.flash_attention_bhld, [qkv, qkv, qkv]


def flash_bert():
    """BERT-large's: packed [B, L, H*D] = [2, 512, 16*64], key bias."""
    from paddle_tpu.ops.pallas import flash_attention as fa
    qkv = ((2, 512, 1024), BF16)
    return (lambda q, k, v: fa.flash_attention_packed(q, k, v, 16, 64),
            [qkv, qkv, qkv])


def layer_norm():
    from paddle_tpu.ops.pallas import fused_norm as fn
    return (lambda x, w, b: fn.fused_layer_norm(x, w, b, 1e-5),
            [((4096, 2048), BF16), ((2048,), BF16), ((2048,), BF16)])


def bias_gelu():
    from paddle_tpu.ops.pallas import fused_elementwise as fe
    return (lambda x, b: fe.bias_gelu(x, b, True),
            [((4096, 8192), BF16), ((8192,), BF16)])


def dropout_add():
    from paddle_tpu.ops.pallas import fused_elementwise as fe
    x = ((4096, 2048), BF16)
    return (lambda a, r, m: fe.dropout_add(a, r, m, 0.1),
            [x, x, ((4096, 2048), jnp.float32)])


FLASH = {'flash_attention_fwd', 'flash_attention_bwd_dq',
         'flash_attention_bwd_dkv'}
# case -> (builder, the forward's kernels, forward + backward)
DIFFERENTIATED = {
    'flash-gpt': (flash_gpt, {'flash_attention_fwd'}, FLASH),
    'flash-bert-packed': (flash_bert, {'flash_attention_fwd'}, FLASH),
    'layer_norm': (layer_norm, {'layer_norm_fwd'},
                   {'layer_norm_fwd', 'layer_norm_bwd'}),
    'bias_gelu': (bias_gelu, {'bias_gelu_fwd'},
                  {'bias_gelu_fwd', 'bias_gelu_bwd'}),
    'dropout_add': (dropout_add, {'dropout_add_fwd'},
                    {'dropout_add_fwd', 'dropout_add_bwd'}),
}


@pytest.mark.parametrize('case', sorted(DIFFERENTIATED))
@pytest.mark.parametrize('wrap', ['bare', 'grad', 'checkpoint+grad',
                                  'scope+grad'])
def test_a_kernel_keeps_its_name_under_every_transform(case, wrap, one_chip,
                                                       as_on_tpu):
    build, forward, both = DIFFERENTIATED[case]
    fn, shapes = build()
    want = forward if wrap == 'bare' else both
    if wrap == 'grad':
        fn = grad_of(fn, len(shapes))
    elif wrap == 'checkpoint+grad':
        fn = grad_of(jax.checkpoint(fn), len(shapes))
    elif wrap == 'scope+grad':
        inner = fn

        def scoped(*a):
            with jax.named_scope('attn'):
                return inner(*a)
        fn = grad_of(scoped, len(shapes))
    # exactly the kernels' names: none named after a transform
    # (`transpose_jvp_..__`, `checkpoint`) or after the jitted function
    assert mosaic_calls(fn, shapes, one_chip) == want, (case, wrap)


@pytest.mark.parametrize('rows,T', [(64, 1), (2, 128)])
def test_paged_attention_is_named(rows, T, one_chip, as_on_tpu):
    """The GPT server's two row groups: 64 decode rows of one query
    token and the mixed step's 2 prompt chunks of 128, 16 heads of 128,
    pages of 16, 80 pages a sequence."""
    from paddle_tpu.ops.pallas import paged_attention as pa
    pages = ((3072, 16, 2048), BF16)

    def fn(q, k, v, pt, sl, ql):
        return pa.ragged_paged_attention_pallas(
            q, k, v, pt, sl, ql, num_heads=16, head_dim=128)
    got = mosaic_calls(fn, [((rows, T, 2048), BF16), pages, pages,
                            ((rows, 80), jnp.int32), ((rows,), jnp.int32),
                            ((rows,), jnp.int32)], one_chip)
    # a group whose rows carry more than one query says so, last
    assert got == {'paged_attention' + ('_chunk' if T > 1 else '')}


@pytest.mark.parametrize('rows,window,name', [
    (64, None, 'paged_attention'), (64, 2048, 'paged_attention_window'),
    (1, None, 'paged_attention_chunk'),
    (1, 2048, 'paged_attention_window_chunk'),
    (2, None, 'paged_attention_chunk'),
    (2, 2048, 'paged_attention_window_chunk')])
def test_paged_attention_with_kv_groups_compiles_and_is_named(
        rows, window, name, one_chip, as_on_tpu):
    """The sparse server cell's row groups at its published widths: 32
    query heads on 4 kv heads of 128, 528-page tables over 28,000
    pages; [64, 1] decode and chunks of 512 (one, and the mixed step's
    2 rows), whose 8 query heads a kv head stack as 4096 rows. A call
    with a window has its own name, and `_chunk` comes after
    `_window`."""
    from paddle_tpu.ops.pallas import paged_attention as pa
    T = 1 if rows == 64 else 512
    pages = ((28000, 16, 512), BF16)

    def fn(q, k, v, pt, sl, ql):
        return pa.ragged_paged_attention_pallas(
            q, k, v, pt, sl, ql, num_heads=32, head_dim=128,
            num_kv_heads=4, window=window)
    got = mosaic_calls(fn, [((rows, T, 4096), BF16), pages, pages,
                            ((rows, 528), jnp.int32), ((rows,), jnp.int32),
                            ((rows,), jnp.int32)], one_chip)
    assert got == {name}


@pytest.mark.parametrize('rows,window,name', [
    (64, None, 'paged_attention_diff'),
    (64, 512, 'paged_attention_diff_window'),
    (2, None, 'paged_attention_diff_chunk'),
    (2, 512, 'paged_attention_diff_window_chunk')])
def test_differential_paged_attention_compiles_and_is_named(
        rows, window, name, one_chip, as_on_tpu):
    """The state-space server cell's row groups at its published
    widths: 40 query sub-heads on 20 key sub-heads of 64, pairs of them
    on one 128-wide value, 176-page tables over 7,168 pages; [64, 1]
    decode and the mixed step's 2 chunks of 128. The names start
    `paged_attention` (the readers sum the class) and say `diff`."""
    from paddle_tpu.ops.pallas import paged_attention as pa
    T = 1 if rows == 64 else 128
    pages = ((7168, 16, 1280), BF16)

    def fn(q, k, v, pt, sl, ql):
        return pa.ragged_paged_attention_pallas(
            q, k, v, pt, sl, ql, num_heads=40, head_dim=64,
            num_kv_heads=20, window=window, diff=2)
    got = mosaic_calls(fn, [((rows, T, 2560), BF16), pages, pages,
                            ((rows, 176), jnp.int32), ((rows,), jnp.int32),
                            ((rows,), jnp.int32)], one_chip)
    assert got == {name}


@pytest.mark.parametrize('rows,T', [(64, 1), (2, 256), (2, 128), (64, 4)])
def test_latent_paged_attention_compiles_and_is_named(rows, T, one_chip,
                                                      as_on_tpu):
    """The latent-attention server cell's row groups at its published
    widths: 64 query heads on ONE stored row of 512 value + 64 rotary
    lanes in 640, 528-page tables over 9,000 pages of 64; [64, 1] decode
    (one tile a row), the mixed step's 2 chunks of 256 (8 query tiles of
    32 tokens x 64 heads; chunk 128: 4) and a verify step's token with 3
    drafts (one tile of 256 rows). The name starts `paged_attention` and
    says `latent`; there is no V pool among the operands."""
    from paddle_tpu.ops.pallas import paged_attention as pa

    def fn(q, pages, pt, sl, ql):
        return pa.ragged_paged_attention_pallas(
            q, pages, None, pt, sl, ql, num_heads=64, head_dim=640,
            latent=(512, 64))
    got = mosaic_calls(fn, [((rows, T, 64 * 640), BF16),
                            ((9000, 64, 640), BF16),
                            ((rows, 528), jnp.int32), ((rows,), jnp.int32),
                            ((rows,), jnp.int32)], one_chip)
    # `_chunk` after `_latent`: the readers' `paged_attention_latent`
    # prefix still matches both calls
    assert got == {'paged_attention_latent' + ('_chunk' if T > 1 else '')}


@pytest.mark.parametrize('rows,T', [(64, 1), (2, 128)])
def test_the_selective_scan_compiles_and_is_named(rows, T, one_chip,
                                                  as_on_tpu):
    """The same cell's recurrence: 5120 channels x 16 states, 65 slots
    (64 and the spare), the decode group and the chunk group."""
    from paddle_tpu.ops.pallas import selective_scan as ss
    f32, i32 = jnp.float32, jnp.int32
    tok, bc = ((rows, T, 5120), f32), ((rows, T, 16), f32)
    got = mosaic_calls(ss.selective_scan_pallas, [
        tok, tok, bc, bc, ((16, 5120), f32), ((5120,), f32),
        ((65, 16, 5120), f32), ((rows,), i32), ((rows,), i32),
        ((rows,), jnp.bool_)], one_chip)
    assert got == {'selective_scan'}


@pytest.mark.parametrize('rows,T', [(128, 1), (2, 256)])
def test_paged_attention_at_head_dim_64_compiles_and_is_named(
        rows, T, one_chip, as_on_tpu):
    """The gated-conv server cell's two attention layers at their
    published widths: 32 query heads on 8 kv heads of 64 (groups of 4,
    a 512-lane pool), 448-page tables over 10,240 pages of 64; [128, 1]
    decode and the mixed step's 2 chunks of 256."""
    from paddle_tpu.ops.pallas import paged_attention as pa
    pages = ((10240, 64, 512), BF16)

    def fn(q, k, v, pt, sl, ql):
        return pa.ragged_paged_attention_pallas(
            q, k, v, pt, sl, ql, num_heads=32, head_dim=64, num_kv_heads=8)
    got = mosaic_calls(fn, [((rows, T, 2048), BF16), pages, pages,
                            ((rows, 448), jnp.int32), ((rows,), jnp.int32),
                            ((rows,), jnp.int32)], one_chip)
    assert got == {'paged_attention' + ('_chunk' if T > 1 else '')}


@pytest.mark.parametrize('rows,heads,kv_heads,head_dim,ps,pages,pool', [
    (128, 32, 8, 64, 64, 448, 10240), (64, 16, 16, 128, 16, 80, 3072),
    (64, 32, 4, 128, 16, 528, 28000)])
def test_the_shared_run_compiles_into_the_decode_call(
        rows, heads, kv_heads, head_dim, ps, pages, pool, one_chip,
        as_on_tpu):
    """The decode rows' call with a shared run at the gated-conv, GPT and
    sparse server cells' widths: the run's program joins the rows' in
    ONE Mosaic call, named as the decode call is (the member rows' state
    is loaded by whole 8-row tiles: a load that is not compiles nowhere)."""
    from paddle_tpu.ops.pallas import paged_attention as pa
    pool_shape = ((pool, ps, kv_heads * head_dim), BF16)

    def fn(q, k, v, pt, sl, ql, run, keys, members):
        return pa.ragged_paged_attention_pallas(
            q, k, v, pt, sl, ql, num_heads=heads, head_dim=head_dim,
            num_kv_heads=kv_heads, shared=(run, keys, members))
    got = mosaic_calls(fn, [
        ((rows, 1, heads * head_dim), BF16), pool_shape, pool_shape,
        ((rows, pages), jnp.int32), ((rows,), jnp.int32),
        ((rows,), jnp.int32), ((pages,), jnp.int32), ((), jnp.int32),
        ((rows,), jnp.bool_)], one_chip)
    assert got == {'paged_attention'}


@pytest.mark.parametrize('rows,T', [(128, 1), (2, 256)])
def test_the_short_conv_compiles_and_is_named(rows, T, one_chip, as_on_tpu):
    """The same cell's gated short convolution: [B | C | u] of 3 x 2048
    channels, 3 taps, 129 slots (128 and the spare), the page-state
    plane of 10,240 pages x 7 conv layers, the decode group and the chunk
    group — one name for both."""
    from paddle_tpu.ops.pallas import short_conv as sc
    i32 = jnp.int32

    def fn(bcu, w, tails, plane, slots, q_lens, first, pt, fresh, restore):
        return sc.short_conv_pallas(bcu, w, tails, plane, 3, slots, q_lens,
                                    first, pt, fresh, restore, 64)
    got = mosaic_calls(fn, [
        ((rows, T, 3 * 2048), BF16), ((3, 2048), BF16),
        ((129, 2, 2048), BF16), ((10240, 7, 2, 2048), BF16),
        ((rows,), i32), ((rows,), i32), ((rows,), i32), ((rows, 448), i32),
        ((rows,), jnp.bool_), ((rows,), jnp.bool_)], one_chip)
    assert got == {'short_conv'}


@pytest.mark.parametrize('pairs', [512, 4096, (64 + 2 * 512) * 8])
def test_the_experts_grouped_matmul_compiles_and_is_named(
        pairs, one_chip, as_on_tpu):
    """128 experts of [2048, 1024] x 3 at a decode step's 512 (token,
    expert) pairs, a 512-token chunk's 4096 and the mixed step's 8,704
    (64 decode rows beside 2 chunks, tiles of 128 rows): the gated half
    and the plain half are the same Mosaic call by name."""
    from paddle_tpu.ops.pallas import grouped_matmul as gmm
    tm = gmm.tile_rows_for(pairs, 128)
    tiles = -(-pairs // tm) + 128
    idx = ((tiles,), jnp.int32)

    def fn(x, w1, w3, w2, te, tb, nl):
        h = gmm.grouped_matmul_pallas(x, w3, te, tb, nl, w1)
        return gmm.grouped_matmul_pallas(h, w2, te, tb, nl)
    up, down = ((128, 2048, 1024), BF16), ((128, 1024, 2048), BF16)
    got = mosaic_calls(fn, [((tiles * tm, 2048), BF16), up, up, down, idx,
                            idx, ((1,), jnp.int32)], one_chip)
    assert got == {'moe_grouped_matmul'}


def test_the_optimizer_kernels_are_named(one_chip, as_on_tpu):
    import paddle_tpu as paddle
    from paddle_tpu.ops.pallas import fused_optimizer as fo
    n = 1 << 20
    opt = paddle.optimizer.AdamW(learning_rate=1e-4, parameters=[],
                                 weight_decay=0.01)
    st = opt.init_state(paddle.Tensor(jnp.zeros((8,), jnp.float32)))
    keys = sorted(st)

    def update(p, g, m1, m2, b1, b2):
        state = dict(zip(keys, [b1, b2, m1, m2]))
        new_p, ns = fo.fused_shard_update(opt, p, g, state,
                                          jnp.float32(1e-4))
        s, c = fo.grad_stats_pallas(g)
        return new_p, [ns[k] for k in keys], s, c
    assert keys == ['beta1_pow', 'beta2_pow', 'moment1', 'moment2']
    vec, scalar = ((n,), jnp.float32), ((), jnp.float32)
    got = mosaic_calls(update, [((n,), BF16), vec, vec, vec, scalar,
                                scalar], one_chip)
    assert got == {'fused_shard_update', 'grad_stats'}
