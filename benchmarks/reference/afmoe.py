"""AFMoE (`model_type` `afmoe`, arcee-ai Trinity): a decoder of sparse
experts with grouped-query attention and window and full layers, in
plain float32 jax.numpy — no kernel, no cache, no batching, no sorting
of rows by expert. It is independent of paddle_tpu/models/: a runner
copies the seeded values out of the program by name into the dicts
below.

    rms(x; g) = x / sqrt(mean(x^2) + eps) * g
    h0 = E[ids] * sqrt(H)                                (mup_enabled) +
    a = rms(h; g1);  h <- h + rms(Attn(a); g2)           sandwich norms +
    m = rms(h; g3);  h <- h + rms(F(m); g4)
    Attn: q = a.Wq, k = a.Wk, v = a.Wv; q, k <- rms over each head's D +;
          rotary (half-split rotate_half, theta, position = index) on
          q, k in `sliding_attention` layers only +; query head j reads
          kv head j // group; scale D^-1/2; causal, and in
          `sliding_attention` layers only keys with 0 <= p_q - p_k <
          window; o = softmax(q k^T) v * sigmoid(a.Wg) +; Attn = o.Wo
    F (dense layers) = (silu(m.W1) * (m.W3)).W2
    F (expert layers): s = sigmoid(m.Wr); S = top-k of s + b (b in the
          choice only +); w_e = s_e / (sum_S s + 1e-20) * route_scale;
          F = SwiGLU_shared(m) + sum_{e in S, e held} w_e SwiGLU_e(m)
    logits = rms(h_L; gf) . W_head^T

(+: not in the public config.json; stated from the public
`transformers` implementation from memory — the configuration file's
`assumed` lists each.) `experts_held = (first, count)`: only those
experts' terms enter the sum, as on a chip that holds that share; the
router still ranks all of them.

    params = {'embed': [V, H], 'final_norm': [H], 'lm_head': [V, H]}
    layer  = {'norm1'..'norm4': [H], 'q_proj': [H, Hq*D], 'k_proj',
              'v_proj': [H, Hk*D], 'gate_proj': [H, Hq*D],
              'o_proj': [Hq*D, H], 'q_norm', 'k_norm': [D], and
              dense:  'w1', 'w3': [H, I], 'w2': [I, H]
              expert: 'router': [H, E], 'expert_bias': [E],
                      'shared_w1', 'shared_w3', 'shared_w2',
                      'experts_w1', 'experts_w3': [C, H, F],
                      'experts_w2': [C, F, H]}

One sequence at a time, computed in blocks so that it fits beside a
serving pool on a 16 GB chip: attention one query head at a time (the
[L, L] scores of one head), the experts one at a time over the rows
that chose them (gathered on the host, padded to a power of two so few
shapes compile), the head over blocks of the vocabulary and only at
the rows asked for. Layers arrive one at a time (`get_layer(i)`) and
are upcast inside the jitted pieces.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
SLIDING = 'sliding_attention'


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(F32)


def _rotary(x, theta):
    """x [L, D], position = row index."""
    L, D = x.shape
    inv = theta ** (-jnp.arange(0, D, 2, dtype=F32) / D)
    ang = jnp.arange(L, dtype=F32)[:, None] * inv
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    rot = jnp.concatenate([-x[:, D // 2:], x[:, :D // 2]], -1)
    return x * cos + rot * sin


@functools.partial(jax.jit, static_argnums=(2,))
def _embed(embed, ids, scale):
    return embed[ids].astype(F32) * scale


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10))
def _head_attn(a, wq, wk, wv, norms, j, D, group, theta, window, eps):
    """Query head j (a traced index: one program serves every head)
    against its kv head j // group: a [L, H], wq [H, Hq*D], wk, wv
    [H, Hk*D] -> [L, D]. `theta` None: no rotary; `window` None: every
    earlier key."""
    gq, gk = norms
    H = a.shape[1]

    def head(w, i):
        return jax.lax.dynamic_slice(w, (0, i * D), (H, D)).astype(F32)
    q = _rms(a @ head(wq, j), gq, eps)
    k = _rms(a @ head(wk, j // group), gk, eps)
    v = a @ head(wv, j // group)
    if theta is not None:
        q, k = _rotary(q, theta), _rotary(k, theta)
    s = (q @ k.T) / math.sqrt(D)
    L = a.shape[0]
    dist = jnp.arange(L)[:, None] - jnp.arange(L)[None, :]
    ok = dist >= 0
    if window is not None:
        ok = ok & (dist < window)
    return jax.nn.softmax(jnp.where(ok, s, -jnp.inf), -1) @ v


@jax.jit
def _gate_out(a, ctx, wg, wo):
    return (ctx * jax.nn.sigmoid(a @ wg.astype(F32))) @ wo.astype(F32)


@functools.partial(jax.jit, static_argnums=(3,))
def _pre(h, g_in, g_mid, eps, attn):
    """h + rms(attn; g_in) and its normed copy for the MLP."""
    h = h + _rms(attn, g_in, eps)
    return h, _rms(h, g_mid, eps)


@functools.partial(jax.jit, static_argnums=(2,))
def _norm(h, g, eps):
    return _rms(h, g, eps)


@functools.partial(jax.jit, static_argnums=(3,))
def _post(h, f, g, eps):
    return h + _rms(f, g, eps)


@jax.jit
def _swiglu(m, w1, w3, w2):
    g = m @ w1.astype(F32)
    return (g * jax.nn.sigmoid(g) * (m @ w3.astype(F32))) @ w2.astype(F32)


@jax.jit
def _expert_rows(m, at, weight, w1, w3, w2, i):
    """Rows `at` of m through expert i (a traced index) of the stacked
    weights, each times its routing weight."""
    return _swiglu(m[at], w1[i], w3[i], w2[i]) * weight[:, None]


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _route(m, wr, bias, top_k, route_scale, route_norm):
    s = jax.nn.sigmoid(m @ wr.astype(F32))
    _, chosen = jax.lax.top_k(s + bias.astype(F32), top_k)
    w = jnp.take_along_axis(s, chosen, -1)
    if route_norm:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return chosen, w * route_scale


def _experts(m, chosen, weights, layer, held):
    """sum over each token's chosen, held experts of w_e SwiGLU_e(m):
    one expert at a time over the rows that chose it."""
    first, count = held
    chosen, weights = np.asarray(chosen), np.asarray(weights)
    out = jnp.zeros_like(m)
    for e in range(first, first + count):
        rows, slot = np.nonzero(chosen == e)
        if not len(rows):
            continue
        pad = 1 << max(int(len(rows) - 1).bit_length(), 3)
        at = np.zeros(pad, np.int32)
        at[:len(rows)] = rows
        w = np.zeros(pad, np.float32)
        w[:len(rows)] = weights[rows, slot]
        # padding rows add 0 * y to row 0
        out = out.at[at].add(_expert_rows(
            m, at, w, layer['experts_w1'], layer['experts_w3'],
            layer['experts_w2'], e - first))
    return out


@functools.partial(jax.jit, static_argnums=(4, 5))
def _logit_block(h, gf, head, v, block, eps):
    """The logits of vocabulary rows v .. v + block (v traced)."""
    rows = jax.lax.dynamic_slice(head, (v, 0), (block, head.shape[1]))
    return _rms(h, gf, eps) @ rows.astype(F32).T


def forward(params, get_layer, cfg, ids, rows=None, vocab_block=32768):
    """ids: int [L] -> (logits float32 [len(rows), V] — every row where
    `rows` is None —, chosen: per expert layer int [L, k])."""
    eps, D = cfg['rms_norm_eps'], cfg['head_dim']
    Hq, Hk = cfg['num_heads'], cfg['num_kv_heads']
    group = Hq // Hk
    held = tuple(cfg.get('experts_held') or (0, cfg['num_experts']))
    ids = jnp.asarray(ids, jnp.int32)
    chosen_all = []
    with jax.default_matmul_precision('highest'):
        scale = math.sqrt(cfg['hidden_size']) if cfg['mup_enabled'] else 1.0
        h = _embed(params['embed'], ids, scale)
        for i in range(cfg['num_layers']):
            p = get_layer(i)
            sliding = cfg['layer_types'][i] == SLIDING
            a = _norm(h, p['norm1'], eps)
            heads = [_head_attn(
                a, p['q_proj'], p['k_proj'], p['v_proj'],
                (p['q_norm'], p['k_norm']), j, D, group,
                cfg['rope_theta'] if sliding else None,
                cfg['sliding_window'] if sliding else None, eps)
                for j in range(Hq)]
            attn = _gate_out(a, jnp.concatenate(heads, -1), p['gate_proj'],
                             p['o_proj'])
            h, m = _pre(h, p['norm2'], p['norm3'], eps, attn)
            if i < cfg['num_dense_layers']:
                f = _swiglu(m, p['w1'], p['w3'], p['w2'])
            else:
                chosen, weights = _route(
                    m, p['router'], p['expert_bias'],
                    cfg['num_experts_per_tok'], cfg['route_scale'],
                    cfg['route_norm'])
                chosen_all.append(np.asarray(chosen))
                f = _swiglu(m, p['shared_w1'], p['shared_w3'],
                            p['shared_w2']) \
                    + _experts(m, chosen, weights, p, held)
            h = _post(h, f, p['norm4'], eps)
        if rows is not None:
            h = h[jnp.asarray(rows, jnp.int32)]
        V = params['lm_head'].shape[0]
        block = min(vocab_block, V)
        starts = list(range(0, V - block + 1, block))
        if starts[-1] + block < V:
            starts.append(V - block)        # the tail, overlapping
        parts = [_logit_block(h, params['final_norm'], params['lm_head'],
                              v, block, eps) for v in starts]
        if len(starts) > 1:
            parts[-1] = parts[-1][:, starts[-2] + block - starts[-1]:]
        logits = jnp.concatenate(parts, -1)
    return logits, chosen_all
