"""GPT-3 (Brown et al. 2020, after GPT-2): a pre-LayerNorm decoder with
learned positions, causal softmax attention and a tanh-GELU MLP, in
plain float32 jax.numpy — no kernel, no cache, no remat, no batching
tricks. It is independent of paddle_tpu/models/: a runner copies the
seeded values out of the program by name into the dict below.

    params = {'wte': [V, H], 'wpe': [P, H], 'lnf_w': [H], 'lnf_b': [H],
              'head': [H, V] or None (None: tied to wte)}
    layer  = {'ln1_w', 'ln1_b', 'qkv_w': [H, 3H], 'qkv_b': [3H],
              'out_w': [H, H], 'out_b', 'ln2_w', 'ln2_b',
              'fc1_w': [H, F], 'fc1_b', 'fc2_w': [F, H], 'fc2_b'}

The qkv projection's output is laid out (head, {q, k, v}, head_dim), the
Megatron packing the program uses; a departure from the paper only in
how one matrix is stored. Layers arrive one at a time (`get_layer(i)`)
and are upcast inside the jitted block, so a 1.3B model's float32 copy
never exists beside the engine's own state on a 16 GB chip.
"""
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _ln(x, w, b, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(F32), tree)


@jax.jit
def _embed(wte, wpe, ids):
    return wte.astype(F32)[ids] + wpe.astype(F32)[:ids.shape[-1]]


def _block(layer, x, heads, eps):
    p = _f32(layer)
    B, L, H = x.shape
    d = H // heads
    h = _ln(x, p['ln1_w'], p['ln1_b'], eps)
    qkv = (h @ p['qkv_w'] + p['qkv_b']).reshape(B, L, heads, 3, d)
    q, k, v = (qkv[:, :, :, i].transpose(0, 2, 1, 3) for i in range(3))
    s = jnp.einsum('bhqd,bhkd->bhqk', q, k) / math.sqrt(d)
    s = jnp.where(jnp.tril(jnp.ones((L, L), bool)), s, -jnp.inf)
    a = jnp.einsum('bhqk,bhkd->bhqd', jax.nn.softmax(s, -1), v)
    a = a.transpose(0, 2, 1, 3).reshape(B, L, H)
    x = x + a @ p['out_w'] + p['out_b']
    h = _ln(x, p['ln2_w'], p['ln2_b'], eps)
    h = jax.nn.gelu(h @ p['fc1_w'] + p['fc1_b'], approximate=True)
    return x + h @ p['fc2_w'] + p['fc2_b']


_block_jit = jax.jit(_block, static_argnums=(2, 3))


@jax.jit
def _logits(x, lnf_w, lnf_b, head, eps):
    h = _ln(x, lnf_w.astype(F32), lnf_b.astype(F32), eps)
    return h @ head.astype(F32)


@jax.jit
def _tied_logits(x, lnf_w, lnf_b, wte, eps):
    h = _ln(x, lnf_w.astype(F32), lnf_b.astype(F32), eps)
    return h @ wte.astype(F32).T


def forward_logits(params, get_layer, num_layers, ids, heads, eps=1e-5):
    """[B, L] token ids -> [B, L, V] float32 logits."""
    with jax.default_matmul_precision('highest'):
        x = _embed(params['wte'], params['wpe'], ids)
        for i in range(num_layers):
            x = _block_jit(get_layer(i), x, heads, eps)
        if params.get('head') is None:
            return _tied_logits(x, params['lnf_w'], params['lnf_b'],
                                params['wte'], eps)
        return _logits(x, params['lnf_w'], params['lnf_b'],
                       params['head'], eps)


@jax.jit
def _xent(logits, labels):
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))


def loss(params, get_layer, num_layers, ids, labels, heads, eps=1e-5):
    """Mean next-token cross entropy over every position of [B, L]."""
    return _xent(forward_logits(params, get_layer, num_layers, ids, heads,
                                eps), labels)
