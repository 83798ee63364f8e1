"""A.X-K1 (`model_type` `axk1`) — latent attention (MLA) and sparse
experts under group-limited routing, for the paged-KV serving engine.

The layer, as equations (benchmarks/reference/axk1.py computes the same
in plain float32, NON-absorbed; what the public config.json does not
carry is listed under `assumed` in the benchmark's configuration file).
rms, SwiGLU, the experts' products, the embedding, the head and the one
seeded initialiser are models/afmoe.py's and ops/moe.py's:

    h0 = E[ids];  h <- h + Attn(rms(h; g1));  h <- h + F(rms(h; g2))
    Attn(a), latent attention (DeepSeek-V2, arXiv:2405.04434 s. 2.1):
        c_q = rms(a.W_DQ; gq)                          [q_lora_rank]
        [q_nope | q_pe]_i = c_q.W_UQ      per head i   [nope + rope]
        [c_kv | k_pe] = a.W_DKV;  c_kv <- rms(c_kv; gkv)
        q_pe_i, k_pe <- rotary (interleaved pairs, YaRN frequencies;
                        k_pe is shared by every head)
        [k_nope | v]_i = c_kv.W_UKV       per head i   [nope + v]
        s_ij = (q_nope_i.k_nope_ij + q_pe_i.k_pe_j) * scale, causal
        o_i = sum_j softmax(s)_ij v_ij;  Attn = [o_1 .. o_Hq].W_O
      served ABSORBED: q'_i = q_nope_i.W_UK_i^T [kv_lora_rank],
        s_ij = (q'_i.c_kv_j + q_pe_i.k_pe_j) * scale,
        o'_i = sum_j p_ij c_kv_j,  o_i = o'_i.W_UV_i
      so a token's cache is the one row [c_kv | k_pe] (512 + 64 lanes)
      whatever the number of heads: a LATENT plane of the pool
      (serving/protocol.py `value_lanes`), read by the paged kernel's
      `latent` body (ops/pallas/paged_attention.py).
    YaRN (rope_scaling): `yarn_inv_freq`, `yarn_mscale` below; scale =
        (nope + rope)^-1/2 * mscale(factor, mscale_all_dim)^2.
    F, layers < first_k_dense_replace: SwiGLU at `intermediate_size`
    F, else: SwiGLU_shared + sum over the token's top-k experts of
        w_e * SwiGLU_e at `moe_intermediate_size`; sigmoid scores, no
        bias, the choice limited to `topk_group` of `n_group` groups
        (ops/moe.py), w_e = s_e / (sum + 1e-20) * routed_scaling_factor
    logits = rms(h_L; gf) . W_head^T                   untied

What one chip holds of a layer is said by the configuration:
`experts_held = (first, count)` of the router's experts (ops/moe.py
computes those experts' terms; the shared expert and everything else is
whole) and `vocab_held`, the leading rows of the embedding and the head
(ids and logits of that slice alone).

The serving-model protocol (serving/protocol.py) as AfmoeForCausalLM
implements it; `kv_cache_spec()` declares latent planes.
"""
import math

import jax
import jax.numpy as jnp

from ..ops import moe
from ..ops.pallas import paged_attention as pa
from ..serving.protocol import KVLayerSpec
from .afmoe import (F32, AfmoeExperts, AfmoeForCausalLM, AfmoeMLP, _dot,
                    _Params, rms_norm)


def yarn_mscale(factor, mscale=1.0):
    """m(x) = 0.1 x ln(factor) + 1 (1 for a factor <= 1)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim, theta, scaling):
    """float32 [dim / 2]: the rotary frequencies of pair k, plain
    theta^(-2k/dim) where `scaling` is None, else YaRN's blend of that
    (fast pairs) with it over `factor` (slow pairs): the ramp runs from
    the pair that turns `beta_fast` times over the original context to
    the one that turns `beta_slow` times."""
    freq = theta ** (-jnp.arange(0, dim, 2, dtype=F32) / dim)
    if not scaling:
        return freq
    orig = scaling['original_max_position_embeddings']

    def pair_of(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))
    lo = max(math.floor(pair_of(scaling['beta_fast'])), 0)
    hi = min(math.ceil(pair_of(scaling['beta_slow'])), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - lo)
                    / max(hi - lo, 1e-3), 0, 1)
    keep = 1.0 - ramp                   # 1: a fast pair, left as it is
    return freq / scaling['factor'] * (1 - keep) + freq * keep


class AxK1Config:
    def __init__(self, vocab_size=163840, hidden_size=7168, num_layers=61,
                 first_k_dense_replace=1, num_heads=64, q_lora_rank=1536,
                 kv_lora_rank=512, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128,
                 intermediate_size=18432, moe_intermediate_size=2048,
                 n_routed_experts=192, num_experts_per_tok=8,
                 n_shared_experts=1, n_group=8, topk_group=4,
                 routed_scaling_factor=2.5, norm_topk_prob=True,
                 rms_norm_eps=1e-6, rope_theta=10000.0, rope_scaling=None,
                 max_seq_len=131072, experts_held=None, vocab_held=None,
                 dtype='bfloat16', initializer_range=0.02):
        if n_shared_experts != 1:
            raise ValueError('one shared expert is what is written')
        if n_routed_experts % n_group or not 0 < topk_group <= n_group \
                or (n_group > 1 and n_routed_experts // n_group < 2):
            raise ValueError(f'{n_routed_experts} experts in {n_group} '
                             f'groups of which {topk_group} are kept')
        if rope_scaling and rope_scaling.get('type') != 'yarn':
            raise ValueError(f'rope_scaling {rope_scaling}: YaRN is what '
                             f'is written')
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.first_k_dense_replace = first_k_dense_replace
        self.num_heads = num_heads
        self.q_lora_rank = q_lora_rank
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.n_routed_experts = n_routed_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.n_group = n_group
        self.topk_group = topk_group
        self.routed_scaling_factor = routed_scaling_factor
        self.norm_topk_prob = norm_topk_prob
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = float(rope_theta)
        self.rope_scaling = dict(rope_scaling) if rope_scaling else None
        self.max_seq_len = max_seq_len
        # (first, count) of the router's experts whose weights live
        # here; None: all of them
        self.experts_held = tuple(experts_held) if experts_held \
            else (0, n_routed_experts)
        # leading rows of the embedding and the head held here
        self.vocab_held = int(vocab_held or vocab_size)
        self.dtype = dtype
        self.initializer_range = initializer_range
        # the embedding is E[ids] as it is (AfmoeForCausalLM._embed)
        self.mup_enabled = False

    @property
    def latent_lanes(self):
        """(value lanes, rotary lanes) of a token's cached row."""
        return self.kv_lora_rank, self.qk_rope_head_dim

    @property
    def softmax_scale(self):
        s = self.rope_scaling
        m = yarn_mscale(s['factor'], s['mscale_all_dim']) if s else 1.0
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 \
            * m * m


def rotary_interleaved(x, pos, inv_freq, mscale=1.0):
    """x [..., T, heads, D], pos int [..., T]: lanes (2k, 2k+1) turn
    together by pos * inv_freq[k]; cos and sin times `mscale`."""
    ang = pos.astype(F32)[..., None] * inv_freq            # [.., T, D/2]
    cos = (jnp.cos(ang) * mscale)[..., None, :]
    sin = (jnp.sin(ang) * mscale)[..., None, :]
    xf = x.astype(F32)
    even, odd = xf[..., 0::2], xf[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     -1).reshape(x.shape).astype(x.dtype)


class AxK1Attention(_Params):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        H, Hq = cfg.hidden_size, cfg.num_heads
        nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        self._declare(
            q_a_proj=(H, cfg.q_lora_rank), q_a_norm=(cfg.q_lora_rank,),
            q_b_proj=(cfg.q_lora_rank, Hq * (nope + rope)),
            kv_a_proj=(H, cfg.kv_lora_rank + rope),
            kv_a_norm=(cfg.kv_lora_rank,),
            kv_b_proj=(cfg.kv_lora_rank, Hq * (nope + cfg.v_head_dim)),
            o_proj=(Hq * cfg.v_head_dim, H))

    def _down(self, a, pos):
        """a [B, T, H] -> q_nope [B, T, Hq, nope], q_pe [B, T, Hq, rope]
        (rotated), the token's row [B, T, rank + rope] = [c_kv | k_pe]
        (normed, rotated)."""
        cfg = self.cfg
        B, T, _ = a.shape
        nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        inv = yarn_inv_freq(rope, cfg.rope_theta, cfg.rope_scaling)
        s = cfg.rope_scaling
        m = yarn_mscale(s['factor'], s['mscale']) \
            / yarn_mscale(s['factor'], s['mscale_all_dim']) if s else 1.0
        c_q = rms_norm(_dot(a, self.q_a_proj.data), self.q_a_norm.data,
                       cfg.rms_norm_eps)
        q = _dot(c_q, self.q_b_proj.data) \
            .reshape(B, T, cfg.num_heads, nope + rope)
        ckv = _dot(a, self.kv_a_proj.data)
        c_kv = rms_norm(ckv[..., :cfg.kv_lora_rank], self.kv_a_norm.data,
                        cfg.rms_norm_eps)
        k_pe = rotary_interleaved(ckv[..., None, cfg.kv_lora_rank:], pos,
                                  inv, m)[..., 0, :]
        return q[..., :nope], rotary_interleaved(q[..., nope:], pos, inv,
                                                 m), \
            jnp.concatenate([c_kv, k_pe], -1)

    def _up(self):
        """kv_b_proj as (W_UK [rank, Hq, nope], W_UV [rank, Hq, v])."""
        cfg = self.cfg
        w = self.kv_b_proj.data.reshape(
            cfg.kv_lora_rank, cfg.num_heads,
            cfg.qk_nope_head_dim + cfg.v_head_dim)
        return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]

    def _absorb_q(self, q_nope, q_pe, lanes=None):
        """-> [B, T, Hq, rank + rope] = [q' | q_pe] * scale (zeros
        behind it up to `lanes`, the pool's row): the query against a
        cached row, q' = q_nope.W_UK^T a batched matmul over heads (bf16
        operands, float32 accumulation); the softmax scale goes in
        before the one rounding to the activations' dtype."""
        w_uk, _ = self._up()
        q_lat = jnp.einsum('bthd,chd->bthc', q_nope, w_uk,
                           preferred_element_type=F32)
        q = (jnp.concatenate([q_lat, q_pe.astype(F32)], -1)
             * self.cfg.softmax_scale).astype(q_nope.dtype)
        if lanes is None:
            return q
        return jnp.pad(q, ((0, 0),) * 3 + ((0, lanes - q.shape[-1]),))

    def _out(self, o_lat):
        """o' [B, T, Hq, rank] (sum_j p_ij c_kv_j) -> o'.W_UV -> .W_O."""
        _, w_uv = self._up()
        o = jnp.einsum('bthc,chd->bthd', o_lat, w_uv,
                       preferred_element_type=F32).astype(o_lat.dtype)
        return _dot(o.reshape(*o.shape[:2], -1), self.o_proj.data)

    def forward(self, a, pos, absorbed=True):
        """Whole sequences, no cache: a [B, L, H]. `absorbed` False:
        keys and values up-projected per head, as the equations are
        first written (the tests hold the two forms together)."""
        cfg = self.cfg
        rank = cfg.kv_lora_rank
        q_nope, q_pe, row = self._down(a, pos)
        ok = (pos[:, :, None] >= pos[:, None, :])[:, None]  # [B,1,q,k]
        if absorbed:
            q = self._absorb_q(q_nope, q_pe)
            s = jnp.einsum('bqhc,bkc->bhqk', q.astype(F32),
                           row.astype(F32))
            p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), -1)
            o_lat = jnp.einsum('bhqk,bkc->bqhc', p,
                               row[..., :rank].astype(F32))
            return self._out(o_lat.astype(a.dtype))
        w_uk, w_uv = self._up()
        c_kv, k_pe = row[..., :rank].astype(F32), row[..., rank:]
        k_nope = jnp.einsum('bkc,chd->bkhd', c_kv, w_uk.astype(F32))
        v = jnp.einsum('bkc,chd->bkhd', c_kv, w_uv.astype(F32))
        s = (jnp.einsum('bqhd,bkhd->bhqk', q_nope.astype(F32), k_nope)
             + jnp.einsum('bqhd,bkd->bhqk', q_pe.astype(F32),
                          k_pe.astype(F32))) * cfg.softmax_scale
        p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), -1)
        o = jnp.einsum('bhqk,bkhd->bqhd', p, v).astype(a.dtype)
        return _dot(o.reshape(*o.shape[:2], -1), self.o_proj.data)

    def forward_paged(self, a, pos, kv, rows):
        """a [1, N, H] over the dispatch's tokens; `rows` (a
        serving/protocol.py RowGroups) takes the attention group by
        group, everything else runs once. `kv` is the layer's latent
        plane, (rows,)."""
        cfg = self.cfg
        if len(kv) != 1:
            raise NotImplementedError(
                f'a latent plane is one array, got {len(kv)}')
        lanes = kv[0].shape[-1]             # the pool's row: whole tiles

        def write(pool, new, page_tables, seq_lens, q_lens):
            return (pa.write_latent_pages(pool[0], new, page_tables,
                                          seq_lens, q_lens),)

        def read(pool, q, page_tables, seq_lens, q_lens):
            return pa.ragged_paged_attention(
                q, pool[0], None, page_tables, seq_lens, q_lens,
                num_heads=cfg.num_heads, head_dim=lanes,
                latent=cfg.latent_lanes)
        q_nope, q_pe, row = self._down(a, pos)
        q = self._absorb_q(q_nope, q_pe, lanes)
        o_lat, kv = rows.attend(write, read, kv,
                                q.reshape(*q.shape[:2], -1), row)
        return self._out(o_lat.reshape(*o_lat.shape[:2], cfg.num_heads,
                                       cfg.kv_lora_rank)), kv


class AxK1SparseMLP(_Params):
    """Router, the experts held here and the shared expert."""

    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        H, F = cfg.hidden_size, cfg.moe_intermediate_size
        self._declare(router=(H, cfg.n_routed_experts))
        self.shared = AfmoeMLP(H, F)
        self.experts = AfmoeExperts(cfg.experts_held[1], H, F)

    def forward(self, m, live=None, counted=None):
        """As AfmoeSparseMLP.forward: m [N, H] -> (out [N, H], rows
        int32 [count, experts held])."""
        cfg = self.cfg
        with jax.named_scope('router'):
            chosen, weights = moe.route(
                m, self.router.data, None, cfg.num_experts_per_tok,
                cfg.routed_scaling_factor, cfg.norm_topk_prob,
                n_group=cfg.n_group, topk_group=cfg.topk_group)
        with jax.named_scope('experts'):
            ex = self.experts
            out, rows = moe.experts_swiglu(
                m, chosen, weights, ex.w1.data, ex.w3.data, ex.w2.data,
                experts_held=cfg.experts_held, live=live, counted=counted)
        with jax.named_scope('shared_expert'):
            out = out + self.shared(m)
        return out, rows


class AxK1DecoderLayer(_Params):
    def __init__(self, cfg, index):
        super().__init__()
        self.eps = cfg.rms_norm_eps
        H = cfg.hidden_size
        self._declare(norm1=(H,), norm2=(H,))
        self.attn = AxK1Attention(cfg)
        self.sparse = index >= cfg.first_k_dense_replace
        self.mlp = AxK1SparseMLP(cfg) if self.sparse \
            else AfmoeMLP(H, cfg.intermediate_size)

    def _join(self, h, attn_out, live=None, counted=None):
        """h + attention, then the whole MLP half: -> (h, rows of the
        expert layer or None); `live`, `counted` as
        AfmoeDecoderLayer._join."""
        h = h + attn_out
        m = rms_norm(h, self.norm2.data, self.eps)
        rows = None
        with jax.named_scope('mlp'):
            if self.sparse:
                f, rows = self.mlp(
                    m.reshape(-1, m.shape[-1]),
                    None if live is None else live.reshape(-1), counted)
                f = f.reshape(m.shape)
            else:
                f = self.mlp(m)
        return h + f, rows

    def forward(self, h, pos, absorbed=True):
        with jax.named_scope('attn'):
            a = self.attn(rms_norm(h, self.norm1.data, self.eps), pos,
                          absorbed)
        return self._join(h, a)

    def forward_paged(self, h, pos, kv, rows):
        with jax.named_scope('attn'):
            a, new_kv = self.attn.forward_paged(
                rms_norm(h, self.norm1.data, self.eps), pos, kv, rows)
        h, counts = self._join(h, a, rows.live(), rows.counted())
        return h, new_kv, counts


class AxK1ForCausalLM(AfmoeForCausalLM):
    """Embedding, the decoder layers, final norm and the untied head:
    AfmoeForCausalLM's outline, initialiser and protocol methods over
    this model's layers; the cache is one latent plane a layer."""
    decoder_layer = AxK1DecoderLayer

    def kv_cache_spec(self):
        cfg = self.config
        value, rotary = cfg.latent_lanes
        return [KVLayerSpec(1, value + rotary, None, None, value)
                for _ in self.layers]
