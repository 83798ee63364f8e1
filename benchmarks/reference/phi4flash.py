"""Phi-4-mini-flash (`model_type` `phi4flash`; SambaY, arXiv:2507.06607):
a decoder-hybrid-decoder of Mamba, differential window / full attention,
gated memory units and cross attention on ONE shared set of keys and
values, in plain float32 jax.numpy at `highest` matmul precision — no
kernel, no cache, no chunk, no batch, no recurrent-state array. It is
independent of paddle_tpu/models/: a runner copies the seeded values out
of the program by name into the dicts below.

    LN(x; g, b) = (x - mean) / sqrt(var + eps) * g + b
    h0 = E[ids]                                    no scale, no positions
    a = LN1(h);  h <- h + Mix(a)
    [g, u] = LN2(h) . W_gate_up;  h <- h + (silu(g) * u) . W_down
    logits = LN_f(h_L) . E^T                                        tied
    Mix = mamba:  [x, z] = a . W_in; x = silu(conv_causal(x) + b_conv);
                  [d, B, C] = x . W_x; D = softplus(d . W_dt + b_dt);
                  s_t = exp(D_t A) s_{t-1} + (D_t x_t) (x) B_t, A = -exp(A_log)
                  y_t = s_t . C_t + D_skip x_t;  (y * silu(z)) . W_out;
                  layer `memory_layer` hands m = y on
          attention: [q, k, v] = a . W_qkv + b; query sub-head (p, s)
                  scores key sub-head (p // group, s), scale D^-1/2,
                  causal (window layers: 0 <= p_q - p_k < window), on
                  v_g = [v_g,1 | v_g,2]: o_p = RMS_2D(A_1 v_g - lam A_2
                  v_g) * g_sub * (1 - lam_init); lam = exp(lq1 . lk1) -
                  exp(lq2 . lk2) + lam_init; concat_p(o_p) . W_o + b_o
          gmu:    (silu(a . W_1) * m) . W_2
          cross:  q = a . W_q + b; k, v of layer `shared_kv_layer`; as
                  attention, full causal, with its own lam and g_sub

Departures from the published description (the configuration file's
`assumed` lists each with its origin): none of the widths; stated from
memory, with no network — the Mamba sizes (N 16, 4 taps, expand 2,
dt_rank H/16), which layers are of which kind and that the one full
layer's cache is the shared one, differential attention's form and its
lam_init, biases on the attention projections alone, m taken before the
gate, the window counted with the query's own key, no positions.

    params = {'embed': [V, H], 'final_norm_w', 'final_norm_b': [H]}
    layer  = {'norm1_w', 'norm1_b', 'norm2_w', 'norm2_b': [H],
              'gate_up': [H, 2F], 'down': [F, H], and by kind
      mamba:     'in_proj' [H, 2Dn], 'conv_w' [K, Dn], 'conv_b' [Dn],
                 'x_proj' [Dn, R+2N], 'dt_proj' [R, Dn], 'dt_bias' [Dn],
                 'a_log' [Dn, N], 'd_skip' [Dn], 'out_proj' [Dn, H]
      attention: 'qkv_proj' [H, Hq*D + 2*Hk*D], 'qkv_bias', 'o_proj'
                 [Hq*D, H], 'o_bias' [H], 'lambda_q1', 'lambda_k1',
                 'lambda_q2', 'lambda_k2' [D], 'subln' [2D]
      cross:     the same with 'qkv_proj' [H, Hq*D]
      gmu:       'in_proj' [H, Dn], 'out_proj' [Dn, H]}

One sequence at a time, in pieces that fit beside a serving pool on a
16 GB chip: one layer's weights upcast at a time (`get_layer(i)`),
attention one query pair at a time (two [L, L] score tiles), the scan a
`lax.scan` over the tokens, the head over blocks of the vocabulary and
only at the rows asked for.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
MAMBA, ATTN, GMU, CROSS = 'mamba', 'attention', 'gmu', 'cross_attention'


def _ln(x, g, b, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * g.astype(F32) \
        + b.astype(F32)


def _silu(x):
    return x * jax.nn.sigmoid(x)


@jax.jit
def _embed(table, ids):
    return table[ids].astype(F32)


_norm = jax.jit(_ln, static_argnums=3)


@functools.partial(jax.jit, static_argnums=5)
def _mlp(h, g, b, gate_up, down, eps):
    gu = _ln(h, g, b, eps) @ gate_up.astype(F32)
    F = gu.shape[-1] // 2
    return h + (_silu(gu[:, :F]) * gu[:, F:]) @ down.astype(F32)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _mamba(a, p, N, R, K):
    """a [L, H] -> (out [L, H], y [L, Dn])."""
    f = lambda n: p[n].astype(F32)
    xz = a @ f('in_proj')
    dn = xz.shape[-1] // 2
    x, z = xz[:, :dn], xz[:, dn:]
    pad = jnp.concatenate([jnp.zeros((K - 1, dn), F32), x])
    L = x.shape[0]
    x = _silu(sum(pad[k:k + L] * f('conv_w')[k] for k in range(K))
              + f('conv_b'))
    dbc = x @ f('x_proj')
    dt = jax.nn.softplus(dbc[:, :R] @ f('dt_proj') + f('dt_bias'))
    B, C = dbc[:, R:R + N], dbc[:, R + N:]
    A = -jnp.exp(f('a_log'))                                    # [Dn, N]

    def token(s, inp):
        x_t, dt_t, b_t, c_t = inp
        s = jnp.exp(dt_t[:, None] * A) * s \
            + (dt_t * x_t)[:, None] * b_t[None, :]
        return s, s @ c_t + f('d_skip') * x_t
    _, y = jax.lax.scan(token, jnp.zeros((dn, N), F32), (x, dt, B, C))
    return (y * _silu(z)) @ f('out_proj'), y


@functools.partial(jax.jit, static_argnums=(2, 3))
def _project(a, p, q_width, kv_width):
    """-> (q [L, Hq*D], k, v [L, Hk*D] or None for a cross layer)."""
    qkv = a @ p['qkv_proj'].astype(F32) + p['qkv_bias'].astype(F32)
    if qkv.shape[-1] == q_width:
        return qkv, None, None
    return (qkv[:, :q_width], qkv[:, q_width:q_width + kv_width],
            qkv[:, q_width + kv_width:])


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 9))
def _pair_attn(q, k, v, p, pair, group, D, window, lam_init, eps):
    """Query pair `pair` (traced): its two sub-heads against the two
    key sub-heads of kv pair `pair // group`, each on the pair's whole
    2D of values; the difference, its norm, the scale. -> [L, 2D]."""
    L = q.shape[0]
    cols = jax.lax.dynamic_slice_in_dim
    g = pair // group
    dist = jnp.arange(L)[:, None] - jnp.arange(L)[None, :]      # p_q - p_k
    ok = dist >= 0
    if window is not None:
        ok = ok & (dist < window)
    vg = cols(v, 2 * g * D, 2 * D, axis=1)
    outs = []
    for s in range(2):
        qs = cols(q, (2 * pair + s) * D, D, axis=1)
        ks = cols(k, (2 * g + s) * D, D, axis=1)
        sc = jnp.where(ok, qs @ ks.T / math.sqrt(D), -jnp.inf)
        outs.append(jax.nn.softmax(sc, -1) @ vg)
    f = lambda n: p[n].astype(F32)
    lam = jnp.exp(jnp.sum(f('lambda_q1') * f('lambda_k1'))) \
        - jnp.exp(jnp.sum(f('lambda_q2') * f('lambda_k2'))) + lam_init
    o = outs[0] - lam * outs[1]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
    return o * f('subln') * (1.0 - lam_init)


@jax.jit
def _attn_out(ctx, p):
    return ctx @ p['o_proj'].astype(F32) + p['o_bias'].astype(F32)


@jax.jit
def _gmu(a, memory, p):
    return (_silu(a @ p['in_proj'].astype(F32)) * memory) \
        @ p['out_proj'].astype(F32)


@functools.partial(jax.jit, static_argnums=2)
def _logit_block(h, table, block, v):
    """The logits of vocabulary rows v .. v + block (v traced)."""
    rows = jax.lax.dynamic_slice(table, (v, 0), (block, table.shape[1]))
    return h @ rows.astype(F32).T


def hidden(params, get_layer, cfg, ids, rows=None):
    """ids: int [L] -> the final-normed hidden state float32 [len(rows),
    H] (every row where `rows` is None)."""
    eps, D = cfg['layer_norm_eps'], cfg['head_dim']
    Hq, Hk = cfg['num_heads'], cfg['num_kv_heads']
    group = Hq // Hk
    ids = jnp.asarray(ids, jnp.int32)
    memory = shared = None
    with jax.default_matmul_precision('highest'):
        h = _embed(params['embed'], ids)
        for i in range(cfg['num_layers']):
            p, kind = get_layer(i), cfg['layer_kinds'][i]
            a = _norm(h, p['norm1_w'], p['norm1_b'], eps)
            if kind == MAMBA:
                mix = {n: p[n] for n in (
                    'in_proj', 'conv_w', 'conv_b', 'x_proj', 'dt_proj',
                    'dt_bias', 'a_log', 'd_skip', 'out_proj')}
                out, y = _mamba(a, mix, cfg['d_state'], cfg['dt_rank'],
                                cfg['d_conv'])
                if i == cfg['memory_layer']:
                    memory = y
            elif kind == GMU:
                out = _gmu(a, memory, {n: p[n] for n in ('in_proj',
                                                         'out_proj')})
            else:
                q, k, v = _project(
                    a, {n: p[n] for n in ('qkv_proj', 'qkv_bias')},
                    Hq * D, Hk * D)
                if kind == CROSS:
                    k, v = shared
                elif i == cfg['shared_kv_layer']:
                    shared = (k, v)
                window = cfg['sliding_window'] if kind == ATTN \
                    and i < cfg['shared_kv_layer'] else None
                lam = {n: p[n] for n in ('lambda_q1', 'lambda_k1',
                                         'lambda_q2', 'lambda_k2', 'subln')}
                ctx = jnp.concatenate([
                    _pair_attn(q, k, v, lam, jnp.int32(pair), group, D,
                               window, jnp.float32(cfg['lambda_init'][i]),
                               eps)
                    for pair in range(Hq // 2)], -1)
                out = _attn_out(ctx, {n: p[n] for n in ('o_proj',
                                                        'o_bias')})
            h = _mlp(h + out, p['norm2_w'], p['norm2_b'], p['gate_up'],
                     p['down'], eps)
        if rows is not None:
            h = h[jnp.asarray(rows, jnp.int32)]
        return _norm(h, params['final_norm_w'], params['final_norm_b'], eps)


def _vocab_blocks(V, block):
    """(start, columns to keep) of blocks of one size that cover V; the
    last overlaps the one before it."""
    block = min(block, V)
    starts = list(range(0, V - block + 1, block))
    keep = [block] * len(starts)
    if starts[-1] + block < V:
        keep.append(V - starts[-1] - block)
        starts.append(V - block)
    return block, list(zip(starts, keep))


def forward(params, get_layer, cfg, ids, rows=None, vocab_block=32768):
    """ids: int [L] -> logits float32 [len(rows), V] (every row where
    `rows` is None)."""
    h = hidden(params, get_layer, cfg, ids, rows)
    block, blocks = _vocab_blocks(params['embed'].shape[0], vocab_block)
    with jax.default_matmul_precision('highest'):
        return jnp.concatenate([
            _logit_block(h, params['embed'], block, v)[:, block - keep:]
            for v, keep in blocks], -1)


def token_gaps(params, get_layer, cfg, ids, rows, tokens,
               vocab_block=32768):
    """For each of `rows`, how far `tokens[i]`'s logit sits below the
    row's largest, as a share of the logit scale (max - mean): the
    forward above, with the vocabulary reduced block by block so that
    [rows, V] never exists (2,048 answer tokens x 200k logits are 1.6
    GB). -> numpy float [len(rows)] (nan where a logit is not finite)."""
    h = hidden(params, get_layer, cfg, ids, rows)
    V = params['embed'].shape[0]
    block, blocks = _vocab_blocks(V, vocab_block)
    tokens = np.asarray(tokens)
    top = np.full(len(tokens), -np.inf)
    total = np.zeros(len(tokens))
    at = np.zeros(len(tokens))
    with jax.default_matmul_precision('highest'):
        for v, keep in blocks:
            lg = np.asarray(_logit_block(h, params['embed'], block, v),
                            np.float64)[:, block - keep:]
            first = v + block - keep
            top = np.maximum(top, lg.max(-1))
            total += lg.sum(-1)
            here = (tokens >= first) & (tokens < first + keep)
            at[here] = lg[here, tokens[here] - first]
    return (top - at) / (top - total / V)
