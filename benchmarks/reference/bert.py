"""BERT (Devlin et al. 2018): a post-LayerNorm encoder over token +
position + segment embeddings, full (unmasked) softmax attention, an
exact-GELU MLP, and the pre-training loss: masked-LM cross entropy
through the transform + LayerNorm + tied decoder, plus next-sentence
cross entropy on the tanh-pooled first token. Plain float32 jax.numpy,
independent of paddle_tpu/models/.

    params = {'wte': [V, H], 'wpe': [P, H], 'wtt': [2, H],
              'emb_ln_w', 'emb_ln_b', 'pool_w': [H, H], 'pool_b',
              'mlm_w': [H, H], 'mlm_b', 'mlm_ln_w', 'mlm_ln_b',
              'nsp_w': [H, 2], 'nsp_b'}
    layer  = {'q_w', 'q_b', 'k_w', 'k_b', 'v_w', 'v_b', 'out_w', 'out_b',
              'ln1_w', 'ln1_b', 'fc1_w', 'fc1_b', 'fc2_w', 'fc2_b',
              'ln2_w', 'ln2_b'}          (weights are [in, out])

Departures from the paper, all the program's: no decoder bias on the
MLM head; LayerNorm epsilon 1e-12 in the embeddings and the MLM head
(the paper's) but 1e-5 inside the encoder layers (`eps_layer`), where
the variance is of order one and the two agree to 1e-5.
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


def _layer(layer, x, heads, eps):
    p = _f32(layer)
    B, L, H = x.shape
    d = H // heads

    def split(a):
        return a.reshape(B, L, heads, d).transpose(0, 2, 1, 3)
    q = split(x @ p['q_w'] + p['q_b'])
    k = split(x @ p['k_w'] + p['k_b'])
    v = split(x @ p['v_w'] + p['v_b'])
    s = jnp.einsum('bhqd,bhkd->bhqk', q, k) / math.sqrt(d)
    a = jnp.einsum('bhqk,bhkd->bhqd', jax.nn.softmax(s, -1), v)
    a = a.transpose(0, 2, 1, 3).reshape(B, L, H)
    x = _ln(x + a @ p['out_w'] + p['out_b'], p['ln1_w'], p['ln1_b'], eps)
    h = jax.nn.gelu(x @ p['fc1_w'] + p['fc1_b'], approximate=False)
    return _ln(x + h @ p['fc2_w'] + p['fc2_b'], p['ln2_w'], p['ln2_b'],
               eps)


_layer_jit = jax.jit(_layer, static_argnums=(2, 3))


@jax.jit
def _embed(params, ids, eps):
    p = _f32({k: params[k] for k in ('wte', 'wpe', 'wtt', 'emb_ln_w',
                                     'emb_ln_b')})
    x = p['wte'][ids] + p['wpe'][:ids.shape[-1]] + p['wtt'][0]
    return _ln(x, p['emb_ln_w'], p['emb_ln_b'], eps)


@jax.jit
def _heads_loss(params, x, mlm_labels, nsp_labels, eps):
    p = _f32({k: v for k, v in params.items() if k != 'wpe'})
    h = jax.nn.gelu(x @ p['mlm_w'] + p['mlm_b'], approximate=False)
    h = _ln(h, p['mlm_ln_w'], p['mlm_ln_b'], eps)
    logp = jax.nn.log_softmax(h @ p['wte'].T, -1)
    mlm = -jnp.mean(jnp.take_along_axis(logp, mlm_labels[..., None], -1))
    pooled = jnp.tanh(x[:, 0] @ p['pool_w'] + p['pool_b'])
    nlogp = jax.nn.log_softmax(pooled @ p['nsp_w'] + p['nsp_b'], -1)
    nsp = -jnp.mean(jnp.take_along_axis(nlogp, nsp_labels[..., None], -1))
    return mlm + nsp


def loss(params, get_layer, num_layers, ids, mlm_labels, nsp_labels, heads,
         eps=1e-12, eps_layer=1e-5):
    """MLM (every position labelled) + NSP loss of [B, L] token ids, all
    of segment 0, no padding."""
    with jax.default_matmul_precision('highest'):
        x = _embed(params, ids, eps)
        for i in range(num_layers):
            x = _layer_jit(get_layer(i), x, heads, eps_layer)
        return _heads_loss(params, x, mlm_labels, nsp_labels, eps)
