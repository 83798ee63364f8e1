"""A.X-K1 (`model_type` `axk1`): latent attention (MLA) in its
NON-absorbed form and sparse experts under group-limited routing, in
plain float32 jax.numpy — no kernel, no cache, no batching, no absorbed
products, no sorting of rows by expert. It is independent of
paddle_tpu/models/: a runner copies the seeded values out of the program
by name into the dicts below.

    rms(x; g) = x / sqrt(mean(x^2) + eps) * g
    h0 = E[ids];  h <- h + Attn(rms(h; g1));  h <- h + F(rms(h; g2))
    Attn(a) (DeepSeek-V2, arXiv:2405.04434 section 2.1):
        c_q = rms(a.W_DQ; gq);  [q_nope | q_pe]_i = c_q.W_UQ  per head i
        [c_kv | k_pe] = a.W_DKV;  c_kv <- rms(c_kv; gkv)
        q_pe_i, k_pe <- rotary on interleaved pairs (2k, 2k+1), position
            = index, YaRN frequencies (below); k_pe shared by all heads
        [k_nope | v]_i = c_kv.W_UKV  per head i
        s_ij = (q_nope_i.k_nope_ij + q_pe_i.k_pe_j) * scale, causal
        o_i = sum_j softmax(s)_ij v_ij;  Attn = [o_1 .. o_Hq].W_O
    YaRN, pair k of dim/2, theta, factor f over `orig` positions:
        inv_k = theta^(-2k/dim) / f * (1 - mask_k) + theta^(-2k/dim) * mask_k
        mask_k = 1 - clip((k - lo) / (hi - lo), 0, 1)
        lo = floor(d(beta_fast)), hi = ceil(d(beta_slow)) in [0, dim-1],
        d(r) = dim * ln(orig / (2 pi r)) / (2 ln theta)
        cos, sin times m(mscale) / m(mscale_all_dim), m(x) = 0.1 x ln f + 1
        scale = (nope + rope)^-1/2 * m(mscale_all_dim)^2
    F (layers < first_k_dense_replace) = (silu(m.W1) * (m.W3)).W2
    F (expert layers): s = sigmoid(m.Wr); a group's score = the sum of
        its two largest s; keep the `topk_group` best of `n_group`
        groups of neighbours; S = top-k of s inside them;
        w_e = s_e / (sum_S s + 1e-20) * routed_scaling_factor;
        F = SwiGLU_shared(m) + sum_{e in S, e held} w_e SwiGLU_e(m)
    logits = rms(h_L; gf) . W_head^T  over the rows of the head held

`experts_held = (first, count)`: only those experts' terms enter the
sum, as on a chip that holds that share; the router still ranks all of
them. The embedding and the head are the slices the caller holds.

    params = {'embed': [V', H], 'final_norm': [H], 'lm_head': [V', H]}
    layer  = {'norm1', 'norm2': [H], 'q_a_proj': [H, rq], 'q_a_norm':
              [rq], 'q_b_proj': [rq, Hq*(nope+rope)], 'kv_a_proj':
              [H, rkv+rope], 'kv_a_norm': [rkv], 'kv_b_proj':
              [rkv, Hq*(nope+v)], 'o_proj': [Hq*v, H], and
              dense:  'w1', 'w3': [H, I], 'w2': [I, H]
              expert: 'router': [H, E], 'shared_w1', 'shared_w3',
                      'shared_w2', 'experts_w1', 'experts_w3':
                      [C, H, F], 'experts_w2': [C, F, H]}

One sequence at a time, computed in blocks so that a document of 32k
tokens fits beside a serving pool on a 16 GB chip: the residual stream
h [L, H] is the ONE array of that size and every piece adds to it in
place (donated). Attention takes one query head at a time and inside it
SCORE_ROWS queries after another, each head's output through its own
rows of W_O (so neither [Hq, L, L] nor [L, Hq * v] exists); the
token-wise pieces (the norms with what follows them) see QUERY_BLOCK
rows at a time, the dense MLP over blocks of its width besides, the
experts one at a time over the rows of the block that chose them; the
head over blocks of the vocabulary and only at the rows asked for.
Layers arrive one at a time (`get_layer(i)`) and are upcast inside the
jitted pieces.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# what every reference of this directory writes the same way: the RMS
# norm, SwiGLU, the experts one at a time over the rows that chose them,
# the head by blocks of the vocabulary
from benchmarks.reference.afmoe import (_experts as experts, _logit_block,
                                        _norm, _rms, _swiglu)
from benchmarks.reference.phi4flash import _vocab_blocks

F32 = jnp.float32
QUERY_BLOCK = 2048      # rows a token-wise piece sees at a time
SCORE_ROWS = 512        # queries of one head against every key at a time


def yarn(dim, theta, scaling):
    """(inv_freq numpy float64 [dim / 2], cos/sin factor, softmax
    factor m(mscale_all_dim)^2): the closed form of the module
    docstring; `scaling` None: plain rotary, factors 1."""
    k = np.arange(dim // 2)
    freq = theta ** (-2.0 * k / dim)
    if not scaling:
        return freq, 1.0, 1.0
    f, orig = scaling['factor'], scaling['original_max_position_embeddings']

    def d(r):
        return dim * math.log(orig / (2 * math.pi * r)) \
            / (2 * math.log(theta))

    def m(x):
        return 0.1 * x * math.log(f) + 1.0 if f > 1 else 1.0
    lo = max(math.floor(d(scaling['beta_fast'])), 0)
    hi = min(math.ceil(d(scaling['beta_slow'])), dim - 1)
    mask = 1.0 - np.clip((k - lo) / max(hi - lo, 1e-3), 0, 1)
    return (freq / f * (1 - mask) + freq * mask,
            m(scaling['mscale']) / m(scaling['mscale_all_dim']),
            m(scaling['mscale_all_dim']) ** 2)


def _rotary(x, inv, factor):
    """x [L, D], position = row index; lanes (2k, 2k+1) turn together."""
    L, D = x.shape
    ang = jnp.arange(L, dtype=F32)[:, None] * inv
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    even, odd = x[:, 0::2], x[:, 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     -1).reshape(L, D)


@jax.jit
def _embed(embed, ids):
    return embed[ids].astype(F32)


@functools.partial(jax.jit, static_argnums=(6, 7))
def _down(h, g, wdq, gq, wdkv, gkv, rank, eps):
    """a = rms(h; g) -> (c_q [L, rq], c_kv [L, rank] normed, k_pe
    [L, rope] before its rotation)."""
    a = _rms(h, g, eps)
    c_q = _rms(a @ wdq.astype(F32), gq, eps)
    ckv = a @ wdkv.astype(F32)
    return c_q, _rms(ckv[:, :rank], gkv, eps), ckv[:, rank:]


@functools.partial(jax.jit, static_argnums=(1, 2))
def _rotary_rows(x, factor, inv):
    return _rotary(x, jnp.asarray(inv, F32), factor)


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10, 11, 12),
                   donate_argnums=(0,))
def _add_head(h, c_q, c_kv, k_pe, weights, j, nope, rope, vd, scale,
              factor, inv, block):
    """h + o_j.W_O[j]: query head j (a traced index) against every key,
    `block` queries at a time (one after another: `lax.map`), keys and
    values up-projected for this head from the latents (non-absorbed),
    its output through its own rows of W_O — so neither [Hq, L, L] nor
    [L, Hq * v] ever exists."""
    wuq, wukv, wo = weights
    L = c_kv.shape[0]
    wq = jax.lax.dynamic_slice(
        wuq, (0, j * (nope + rope)), (wuq.shape[0], nope + rope)) \
        .astype(F32)
    wkv = jax.lax.dynamic_slice(
        wukv, (0, j * (nope + vd)), (wukv.shape[0], nope + vd)).astype(F32)
    q = c_q @ wq
    q_nope = q[:, :nope].reshape(L // block, block, nope)
    q_pe = _rotary(q[:, nope:], jnp.asarray(inv, F32), factor) \
        .reshape(L // block, block, rope)
    kv = c_kv @ wkv
    k_nope, v = kv[:, :nope], kv[:, nope:]

    def one(args):
        i, qn, qp = args
        s = (qn @ k_nope.T + qp @ k_pe.T) * scale
        ok = (i * block + jnp.arange(block))[:, None] \
            >= jnp.arange(L)[None, :]
        return jax.nn.softmax(jnp.where(ok, s, -jnp.inf), -1) @ v
    o = jax.lax.map(one, (jnp.arange(L // block), q_nope, q_pe)) \
        .reshape(L, vd)
    return h + o @ jax.lax.dynamic_slice(
        wo, (j * vd, 0), (vd, wo.shape[1])).astype(F32)


@functools.partial(jax.jit, static_argnums=(5,))
def _swiglu_block(m, w1, w3, w2, c, block):
    """Columns c .. c + block (c traced) of the hidden width: their
    part of the sum over the width."""
    H = m.shape[1]
    a = jax.lax.dynamic_slice(w1, (0, c), (H, block))
    b = jax.lax.dynamic_slice(w3, (0, c), (H, block))
    d = jax.lax.dynamic_slice(w2, (c, 0), (block, H))
    return _swiglu(m, a, b, d)


def _dense(m, w1, w3, w2, block=4608):
    width = w1.shape[1]
    if width % block:
        return _swiglu(m, w1, w3, w2)
    return sum(_swiglu_block(m, w1, w3, w2, c, block)
               for c in range(0, width, block))


@functools.partial(jax.jit, static_argnums=(2,))
def _rows(x, r, block):
    """Rows r .. r + block of x (r traced)."""
    return jax.lax.dynamic_slice(x, (r, 0), (block, x.shape[1]))


@functools.partial(jax.jit, donate_argnums=(0,))
def _add_rows(h, f, r):
    """h with f added to its rows r .. r + len(f) (r traced), in place."""
    at = jax.lax.dynamic_slice(h, (r, 0), f.shape)
    return jax.lax.dynamic_update_slice(h, at + f, (r, 0))


def _blocks(L):
    """(first row, rows) of the blocks of QUERY_BLOCK rows that cover
    L; the last may be short."""
    return [(r, min(QUERY_BLOCK, L - r)) for r in range(0, L, QUERY_BLOCK)]


def route(m, wr, top_k, n_group, topk_group, scale, norm):
    """numpy in, numpy out: (chosen int [L, k], weights [L, k]). A
    group's score is the sum of its two largest scores; only experts of
    the `topk_group` best groups can be chosen."""
    with jax.default_matmul_precision('highest'):
        s = np.asarray(jax.nn.sigmoid(
            jnp.asarray(m, F32) @ jnp.asarray(wr).astype(F32)), np.float64)
    L, E = s.shape
    choice = s.copy()
    if n_group > 1:
        grouped = s.reshape(L, n_group, E // n_group)
        score = np.sort(grouped, -1)[..., -2:].sum(-1)
        kept = np.argsort(-score, -1, kind='stable')[:, :topk_group]
        keep = np.zeros((L, n_group), bool)
        np.put_along_axis(keep, kept, True, -1)
        choice = np.where(keep[:, :, None], grouped, -np.inf).reshape(L, E)
    chosen = np.argsort(-choice, -1, kind='stable')[:, :top_k]
    w = np.take_along_axis(s, chosen, -1)
    if norm:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return chosen, (w * scale).astype(np.float32)


def sparse_mlp(m, p, cfg, held=None):
    """The expert layer's F(m) -> (F [L, H], chosen [L, k])."""
    held = held or tuple(cfg.get('experts_held')
                         or (0, cfg['n_routed_experts']))
    chosen, weights = route(
        m, p['router'], cfg['num_experts_per_tok'], cfg['n_group'],
        cfg['topk_group'], cfg['routed_scaling_factor'],
        cfg['norm_topk_prob'])
    with jax.default_matmul_precision('highest'):
        return _swiglu(m, p['shared_w1'], p['shared_w3'], p['shared_w2']) \
            + experts(m, chosen, weights, p, held), chosen


def attention(h, p, cfg):
    """h + Attn(rms(h; norm1)): h [L, H] the residual stream (given up
    to the sum)."""
    nope, rope = cfg['qk_nope_head_dim'], cfg['qk_rope_head_dim']
    inv, factor, soft = yarn(rope, cfg['rope_theta'], cfg['rope_scaling'])
    inv = tuple(float(x) for x in inv)
    scale = (nope + rope) ** -0.5 * soft
    L = h.shape[0]
    block = min(SCORE_ROWS, L)
    if L % block:
        raise ValueError(f'{L} rows are not whole blocks of {block}')
    down = [_down(_rows(h, r, n), p['norm1'], p['q_a_proj'], p['q_a_norm'],
                  p['kv_a_proj'], p['kv_a_norm'], cfg['kv_lora_rank'],
                  cfg['rms_norm_eps']) for r, n in _blocks(L)]
    c_q, c_kv, k_pe = (jnp.concatenate(x) for x in zip(*down))
    del down
    k_pe = _rotary_rows(k_pe, factor, inv)
    for j in range(cfg['num_heads']):
        h = _add_head(h, c_q, c_kv, k_pe,
                      (p['q_b_proj'], p['kv_b_proj'], p['o_proj']), j, nope,
                      rope, cfg['v_head_dim'], scale, factor, inv, block)
    return h


def mlp(h, p, cfg, dense):
    """h + F(rms(h; norm2)), QUERY_BLOCK rows at a time into h's own
    rows -> (h, chosen int [L, k] of an expert layer, else None)."""
    chosen = []
    for r, n in _blocks(h.shape[0]):
        m = _norm(_rows(h, r, n), p['norm2'], cfg['rms_norm_eps'])
        if dense:
            f = _dense(m, p['w1'], p['w3'], p['w2'])
        else:
            f, rows = sparse_mlp(m, p, cfg)
            chosen.append(rows)
        h = _add_rows(h, f, r)
    return h, None if dense else np.concatenate(chosen)


def hidden(params, get_layer, cfg, ids, rows=None):
    """ids: int [L] -> (h_L float32 [len(rows), H] before the final
    norm — every row where `rows` is None —, chosen: per expert layer
    int [L, k])."""
    ids = jnp.asarray(ids, jnp.int32)
    chosen_all = []
    with jax.default_matmul_precision('highest'):
        h = _embed(params['embed'], ids)
        for i in range(cfg['num_layers']):
            p = get_layer(i)
            h = attention(h, p, cfg)
            h, chosen = mlp(h, p, cfg, i < cfg['first_k_dense_replace'])
            if chosen is not None:
                chosen_all.append(chosen)
        if rows is not None:
            h = h[jnp.asarray(rows, jnp.int32)]
    return h, chosen_all


def forward(params, get_layer, cfg, ids, rows=None, vocab_block=8192):
    """-> (logits float32 [len(rows), V'], chosen per expert layer)."""
    h, chosen = hidden(params, get_layer, cfg, ids, rows)
    V = params['lm_head'].shape[0]
    block, blocks = _vocab_blocks(V, vocab_block)
    with jax.default_matmul_precision('highest'):
        parts = [_logit_block(h, params['final_norm'], params['lm_head'],
                              v, block, cfg['rms_norm_eps'])[:, block - keep:]
                 for v, keep in blocks]
    return jnp.concatenate(parts, -1), chosen


def token_gaps(params, get_layer, cfg, ids, rows, tokens,
               vocab_block=8192):
    """For each of `rows`, how far `tokens[i]`'s logit sits below the
    row's largest, as a share of the logit scale (max - mean): the
    forward above, with the vocabulary reduced block by block so that
    [rows, V'] never exists. -> numpy float [len(rows)] (nan where a
    logit is not finite)."""
    h, _ = hidden(params, get_layer, cfg, ids, rows)
    V = params['lm_head'].shape[0]
    block, blocks = _vocab_blocks(V, vocab_block)
    tokens = np.asarray(tokens)
    top = np.full(len(tokens), -np.inf)
    total = np.zeros(len(tokens))
    at = np.zeros(len(tokens))
    with jax.default_matmul_precision('highest'):
        for v, keep in blocks:
            lg = np.asarray(_logit_block(
                h, params['final_norm'], params['lm_head'], v, block,
                cfg['rms_norm_eps']), np.float64)[:, block - keep:]
            first = v + block - keep
            top = np.maximum(top, lg.max(-1))
            total += lg.sum(-1)
            here = (tokens >= first) & (tokens < first + keep)
            at[here] = lg[here, tokens[here] - first]
    return (top - at) / (top - total / V)
