"""GPT — the flagship model (BASELINE config 4: GPT-3 1.3B hybrid parallel).

Reference parity: the GPT used by sandyhouse/Paddle's fleet hybrid-parallel
stack (the pipeline/sharding meta-optimizers were built to train it;
test models: fluid/tests/unittests/hybrid_parallel_pp_transformer.py,
hybrid_parallel_mp_layers.py patterns).

TPU-native: decoder blocks are built from the tensor-parallel layers
(VocabParallelEmbedding / ColumnParallelLinear / RowParallelLinear), so under
the hybrid engine's shard_map the qkv/ffn matmuls run on mp-local shards with
XLA collectives between them — Megatron semantics on ICI. Attention uses one
fused softmax(QK^T)V with a causal mask in-kernel (MXU-shaped batched
matmuls); the Pallas flash-attention kernel swaps in for long sequences.
All shapes static; dropout keys via the global RNG stream.
"""
import math

import numpy as np
import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..core.autograd import run_op
from ..ops import math as M
from ..ops import manip
from ..ops import nn_ops as F
from ..nn import initializer as I
from ..distributed.fleet.meta_parallel.parallel_layers.mp_layers import (
    VocabParallelEmbedding, ColumnParallelLinear, RowParallelLinear,
    ParallelCrossEntropy, _mp_info)
from ..distributed.fleet.utils.recompute import tag_tensor as _remat_tag


class GPTConfig:
    def __init__(self, vocab_size=50304, hidden_size=768, num_layers=12,
                 num_heads=12, ffn_hidden_size=None, max_seq_len=1024,
                 hidden_dropout=0.1, attn_dropout=0.1,
                 initializer_range=0.02, layer_norm_eps=1e-5,
                 use_flash_attention=True):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.ffn_hidden_size = ffn_hidden_size or 4 * hidden_size
        self.max_seq_len = max_seq_len
        self.hidden_dropout = hidden_dropout
        self.attn_dropout = attn_dropout
        self.initializer_range = initializer_range
        self.layer_norm_eps = layer_norm_eps
        self.use_flash_attention = use_flash_attention


def gpt_tiny(**kw):
    return GPTConfig(vocab_size=1024, hidden_size=128, num_layers=4,
                     num_heads=4, max_seq_len=256, **kw)


def gpt_small(**kw):  # GPT-2 124M
    return GPTConfig(hidden_size=768, num_layers=12, num_heads=12, **kw)


def gpt_medium(**kw):  # 350M
    return GPTConfig(hidden_size=1024, num_layers=24, num_heads=16, **kw)


def gpt_1p3b(**kw):  # GPT-3 1.3B (BASELINE config 4)
    return GPTConfig(hidden_size=2048, num_layers=24, num_heads=16,
                     max_seq_len=2048, **kw)


def _sp_active():
    """True only when the engine declared the batch sequence-sharded over a
    live 'sp' axis (mere axis presence is not enough — e.g. the pipeline
    engine runs with the full mesh in scope but dp-only batch sharding)."""
    from ..distributed import collective as C
    from ..distributed import topology_runtime
    return (C.in_spmd_region() and C.sp_data_sharded()
            and 'sp' in C.current_spmd_axes()
            and topology_runtime.axis_size('sp') > 1)


def _mp_seq_active():
    """True when the engine declared Megatron-style sequence-parallel
    activation sharding: the residual stream between mp regions runs on
    token slices scattered over the mp group
    (docs/performance.md#sequence-parallel-activations)."""
    from ..distributed import collective as C
    return C.in_spmd_region() and C.mp_seq_sharded()


class GPTEmbeddings(nn.Layer):
    """Token (vocab-parallel) + learned position embeddings. Under sequence
    parallelism the local chunk's positions are offset by the sp rank."""

    def __init__(self, config):
        super().__init__()
        init = I.Normal(0.0, config.initializer_range)
        self.word_embeddings = VocabParallelEmbedding(
            config.vocab_size, config.hidden_size,
            weight_attr=nn.ParamAttr(initializer=init))
        self.position_embeddings = nn.Embedding(
            config.max_seq_len, config.hidden_size,
            weight_attr=nn.ParamAttr(initializer=init))
        self.dropout = nn.Dropout(config.hidden_dropout)

    @jax.named_scope('embed')
    def forward(self, input_ids, position_ids=None):
        if position_ids is None:
            L = input_ids.shape[-1]
            pos = jnp.arange(L, dtype=jnp.int32)
            if _sp_active():
                from jax import lax
                pos = pos + lax.axis_index('sp') * L
            position_ids = Tensor(pos)
        tok = self.word_embeddings(input_ids)
        pos = self.position_embeddings(position_ids)
        return self.dropout(
            _remat_tag(M.add(tok, pos), 'embed_out'))


class GPTAttention(nn.Layer):
    """Causal self-attention, heads sharded over mp.

    qkv = ColumnParallel (gather_output=False) so each mp rank holds
    nh/mp heads; out proj = RowParallel(input_is_parallel) — one allreduce
    per attention block, Megatron-style.
    """

    def __init__(self, config):
        super().__init__()
        self.world_size, _, _ = _mp_info()
        self.num_heads = config.num_heads
        self.head_dim = config.hidden_size // config.num_heads
        self.local_heads = config.num_heads // self.world_size
        self.attn_dropout_p = config.attn_dropout
        self.use_flash = config.use_flash_attention
        init = I.Normal(0.0, config.initializer_range)
        out_init = I.Normal(
            0.0, config.initializer_range / math.sqrt(2 * config.num_layers))
        self.qkv_proj = ColumnParallelLinear(
            config.hidden_size, 3 * config.hidden_size,
            weight_attr=nn.ParamAttr(initializer=init), gather_output=False)
        self.out_proj = RowParallelLinear(
            config.hidden_size, config.hidden_size,
            weight_attr=nn.ParamAttr(initializer=out_init),
            input_is_parallel=True)
        # NOTE: the hidden dropout that used to follow out_proj now
        # lives in GPTDecoderLayer's residual join (F.dropout_add), so
        # it fuses with the add; the RNG draw order is unchanged.

    def forward(self, x, cache=None, cache_len=None):
        """cache: optional (k, v) Tensors [B, nh, max_len, hd] (fixed-size,
        position-indexed by cache_len) enabling O(1)-per-token decode."""
        if cache is not None:
            return self._forward_cached(x, cache, cache_len)
        # remat boundary tags (docs/performance.md#remat-policy): the
        # attn_mlp_boundaries policy saves these contraction outputs and
        # recomputes the cheap elementwise chains between them
        qkv = _remat_tag(self.qkv_proj(x), 'attn_qkv')
        # under sequence-parallel activation sharding the input x is a
        # token SLICE and qkv_proj gathered it back to the full token
        # dim — take B/L from qkv, not x
        B, L = qkv.shape[0], qkv.shape[1]
        hd, nh = self.head_dim, qkv.shape[-1] // (3 * self.head_dim)

        # out-dim layout is (head, 3, hd): column-sharding then hands each
        # mp rank whole heads (Megatron qkv packing), so TP == dense.
        attn_key = None
        if self.attn_dropout_p > 0.0 and self.training:
            from ..core import rng as _rng
            attn_key = _rng.next_key()

        def attn(a):
            x5 = a.reshape(B, L, nh, 3, hd)
            q, k, v = x5[:, :, :, 0], x5[:, :, :, 1], x5[:, :, :, 2]
            q = q.transpose(0, 2, 1, 3)  # B, nh, L, hd
            k = k.transpose(0, 2, 1, 3)
            v = v.transpose(0, 2, 1, 3)
            scores = jnp.einsum('bhqd,bhkd->bhqk', q, k,
                                preferred_element_type=jnp.float32)
            scores = scores * (1.0 / math.sqrt(hd))
            causal = jnp.tril(jnp.ones((L, L), bool))
            scores = jnp.where(causal, scores, jnp.asarray(-1e9, scores.dtype))
            probs = jax.nn.softmax(scores, axis=-1).astype(a.dtype)
            if attn_key is not None:
                keep = jax.random.bernoulli(
                    attn_key, 1.0 - self.attn_dropout_p, probs.shape)
                probs = jnp.where(keep,
                                  probs / (1.0 - self.attn_dropout_p), 0.0)
            out = jnp.einsum('bhqk,bhkd->bhqd', probs, v)
            return out.transpose(0, 2, 1, 3).reshape(B, L, nh * hd)

        flash = self.use_flash and L >= 512 and not _sp_active()
        if _sp_active():
            # sequence-parallel: K/V ring over the 'sp' axis (net-new vs the
            # reference — SURVEY.md §5.7)
            from ..ops import ring_attention as ra
            from ..distributed import topology_runtime
            ctx = ra.ring_causal_qkv(qkv, nh, hd, axis_name='sp',
                                     sp=topology_runtime.axis_size('sp'),
                                     dropout=self.attn_dropout_p
                                     if self.training else 0.0)
        elif flash:
            # active attention dropout no longer forces the dense path:
            # the keep mask is drawn OUTSIDE the kernel at the exact
            # RNG-stream point the dense path draws (attn_key above), so
            # the dropout-fused flash route is same-seed/same-mask
            # comparable with the dense reference (ISSUE 12)
            from ..ops.pallas import flash_attention as fa
            ctx = fa.causal_attention(
                qkv, nh, hd,
                dropout=self.attn_dropout_p if attn_key is not None
                else 0.0,
                dropout_key=attn_key)
        else:
            from ..ops.pallas import scaffold as _scaffold
            _scaffold.record_route('flash_dropout' if attn_key is not None
                                   else 'flash_attention', False)
            ctx = run_op('fused_attention', attn, [qkv])
        if not flash:
            # the kernel's forward rule names its own output (`flash_o`):
            # the context, re-laid, would be the same values saved twice
            ctx = _remat_tag(ctx, 'attn_ctx')
        out = _remat_tag(self.out_proj(ctx), 'attn_out')
        return out

    def _forward_cached(self, x, cache, cache_len):
        """Single-step decode: x [B, 1, H]; write this token's k/v at
        position cache_len, attend over cache[:cache_len+1]."""
        B, L, _ = x.shape
        qkv = self.qkv_proj(x)
        hd = self.head_dim
        nh = qkv.shape[-1] // (3 * hd)
        k_cache, v_cache = cache
        pos = cache_len.data if isinstance(cache_len, Tensor) else cache_len

        def fn(a, kc, vc):
            x5 = a.reshape(B, L, nh, 3, hd)
            q = x5[:, :, :, 0].transpose(0, 2, 1, 3)  # B,nh,1,hd
            k = x5[:, :, :, 1].transpose(0, 2, 1, 3)
            v = x5[:, :, :, 2].transpose(0, 2, 1, 3)
            kc2 = jax.lax.dynamic_update_slice(
                kc, k.astype(kc.dtype), (0, 0, pos, 0))
            vc2 = jax.lax.dynamic_update_slice(
                vc, v.astype(vc.dtype), (0, 0, pos, 0))
            scores = jnp.einsum('bhqd,bhkd->bhqk', q,
                                kc2.astype(q.dtype),
                                preferred_element_type=jnp.float32)
            scores = scores * (1.0 / math.sqrt(hd))
            idx = jnp.arange(kc.shape[2])
            mask = idx[None, None, None, :] <= pos
            scores = jnp.where(mask, scores, -1e9)
            probs = jax.nn.softmax(scores, axis=-1).astype(a.dtype)
            o = jnp.einsum('bhqk,bhkd->bhqd', probs, vc2.astype(a.dtype))
            o = o.transpose(0, 2, 1, 3).reshape(B, L, nh * hd)
            return o, kc2, vc2
        ctx, kc2, vc2 = run_op('cached_attention', fn,
                               [qkv, k_cache, v_cache])
        out = self.out_proj(ctx)
        return out, (kc2, vc2)

    def forward_paged(self, x, kv, rows):
        """Serving-engine path: x [1, N, H], the query tokens of every
        row of the dispatch end to end (`rows`, a serving/protocol.py
        RowGroups, says which rows: right-padded to their q_lens); kv =
        (k_pages, v_pages) Tensors [num_pages, page_size,
        local_heads*hd] from the shared pool — or the int8 pool's
        4-tuple (k_pages, v_pages, k_scales, v_scales), in which case
        new K/V quantize at scatter time and attention dequantizes
        inside the kernel (kv_dtype='int8',
        docs/serving.md#quantized-kv). The projections run once over
        the N tokens; `rows.attend` writes each group's new k/v into
        its sequences' pages and runs ragged paged attention over each
        row's page table (causal within the sequence)."""
        qkv = self.qkv_proj(x)
        hd = self.head_dim
        nh = qkv.shape[-1] // (3 * hd)
        from ..ops.pallas import paged_attention as pa

        def write(pool, k, v, page_tables, seq_lens, q_lens):
            if len(pool) == 4:
                return pa.write_kv_pages_quantized(
                    *pool, k, v, page_tables, seq_lens, q_lens,
                    num_heads=nh)
            return pa.write_kv_pages(*pool, k, v, page_tables, seq_lens,
                                     q_lens)

        def read(pool, q, page_tables, seq_lens, q_lens):
            scales = {'k_scales': pool[2], 'v_scales': pool[3]} \
                if len(pool) == 4 else {}
            return pa.ragged_paged_attention(
                q, pool[0], pool[1], page_tables, seq_lens, q_lens,
                num_heads=nh, head_dim=hd, **scales)

        def fn(a, *pool):
            x5 = a.reshape(1, -1, nh, 3, hd)
            q, k, v = (x5[:, :, :, i].reshape(1, -1, nh * hd)
                       for i in range(3))
            ctx, pool = rows.attend(write, read, pool, q, k, v)
            return (ctx, *pool)
        ctx, *new_kv = run_op('paged_attention', fn, [qkv, *kv])
        return self.out_proj(ctx), tuple(new_kv)


class GPTMLP(nn.Layer):
    """FFN. The fc1 bias-add fuses into the GELU (F.bias_gelu — the
    Pallas bias+GELU kernel on TPU, the identical jnp expression on
    CPU), and the trailing hidden dropout moved UP into the decoder
    layer's residual join (F.dropout_add) so it fuses with the add —
    same ops, same RNG draw order, kernel-fusable boundaries."""

    def __init__(self, config):
        super().__init__()
        init = I.Normal(0.0, config.initializer_range)
        out_init = I.Normal(
            0.0, config.initializer_range / math.sqrt(2 * config.num_layers))
        self.fc1 = ColumnParallelLinear(
            config.hidden_size, config.ffn_hidden_size,
            weight_attr=nn.ParamAttr(initializer=init), gather_output=False)
        self.fc2 = RowParallelLinear(
            config.ffn_hidden_size, config.hidden_size,
            weight_attr=nn.ParamAttr(initializer=out_init),
            input_is_parallel=True)

    def forward(self, x):
        if self.fc1.bias is not None:
            h = F.bias_gelu(
                _remat_tag(self.fc1(x, with_bias=False),
                                  'mlp_fc1'),
                self.fc1.bias, approximate=True)
        else:
            h = F.gelu(_remat_tag(self.fc1(x), 'mlp_fc1'),
                       approximate=True)
        return _remat_tag(self.fc2(h), 'mlp_out')


class GPTDecoderLayer(nn.Layer):
    """Pre-LN transformer block. Both residual joins run through
    F.dropout_add (the sublayers' trailing hidden dropout fused with
    the residual add — one Pallas pass on TPU, the identical dropout →
    add expression and RNG stream on the reference route; eval and
    dropout=0 degrade to the plain add)."""

    def __init__(self, config):
        super().__init__()
        self.ln1 = nn.LayerNorm(config.hidden_size,
                                epsilon=config.layer_norm_eps)
        self.attn = GPTAttention(config)
        self.ln2 = nn.LayerNorm(config.hidden_size,
                                epsilon=config.layer_norm_eps)
        self.mlp = GPTMLP(config)
        self.hidden_dropout = config.hidden_dropout
        # params consumed while the residual stream is sequence-
        # scattered (docs/performance.md#sequence-parallel-activations):
        # their per-rank grads cover only the local token slice, so the
        # engine psums them over 'mp' when sequence_parallel is on
        # (Megatron marks its LN params the same way). Inert otherwise.
        for p in (list(self.ln1.parameters()) + list(self.ln2.parameters())
                  + ([self.attn.out_proj.bias]
                     if self.attn.out_proj.bias is not None else [])
                  + ([self.mlp.fc2.bias]
                     if self.mlp.fc2.bias is not None else [])):
            p.sequence_parallel_grad = True

    def _join(self, sub_out, residual):
        return F.dropout_add(sub_out, residual, p=self.hidden_dropout,
                             training=self.training)

    # jax.named_scope puts `ln1/attn/ln2/mlp` on the op names of the
    # profile (metadata.op_name); a Pallas call keeps its own `name=`
    def _norm_attn(self, x, attn, *args, **kwargs):
        with jax.named_scope('ln1'):
            h = self.ln1(x)
        with jax.named_scope('attn'):
            return attn(h, *args, **kwargs)

    def _norm_mlp(self, x):
        with jax.named_scope('ln2'):
            h = self.ln2(x)
        with jax.named_scope('mlp'):
            return self.mlp(h)

    def forward(self, x, cache=None, cache_len=None):
        if cache is not None:
            a, new_cache = self._norm_attn(x, self.attn, cache=cache,
                                           cache_len=cache_len)
            x = self._join(a, x)
            x = self._join(self._norm_mlp(x), x)
            return x, new_cache
        x = self._join(self._norm_attn(x, self.attn), x)
        x = self._join(self._norm_mlp(x), x)
        return x

    def forward_paged(self, x, kv, rows):
        a, new_kv = self._norm_attn(x, self.attn.forward_paged, kv, rows)
        x = self._join(a, x)
        x = self._join(self._norm_mlp(x), x)
        return x, new_kv


class GPTModel(nn.Layer):
    _supports_sequence_parallel = True

    def __init__(self, config):
        super().__init__()
        self.config = config
        self.embeddings = GPTEmbeddings(config)
        self.layers = nn.LayerList(
            [GPTDecoderLayer(config) for _ in range(config.num_layers)])
        self.final_norm = nn.LayerNorm(config.hidden_size,
                                       epsilon=config.layer_norm_eps)
        for p in self.final_norm.parameters():
            # the final norm also runs on the scattered stream
            p.sequence_parallel_grad = True

    def forward(self, input_ids, position_ids=None, caches=None,
                cache_len=None):
        x = self.embeddings(input_ids, position_ids)
        if caches is not None:
            new_caches = []
            for layer, c in zip(self.layers, caches):
                x, nc = layer(x, cache=c, cache_len=cache_len)
                new_caches.append(nc)
            with jax.named_scope('final_norm'):
                return self.final_norm(x), new_caches
        qkv = self.layers[0].attn.qkv_proj if self.layers else None
        seqp = (_mp_seq_active() and qkv is not None
                and qkv.world_size > 1)
        if seqp:
            # sequence-parallel activation sharding: the residual
            # stream drops to this rank's token slice here (a static
            # slice — the embed output is replicated over mp) and stays
            # scattered through every LayerNorm/dropout/residual
            # segment; the qkv/fc1 entries gather, the out-proj/fc2
            # exits re-scatter (mp_layers), and the stream is gathered
            # back to full ONLY after the final norm below.
            from ..distributed import collective as C
            x = C._c_slice_seq(x, group=qkv.group)
        for layer in self.layers:
            x = layer(x)
        with jax.named_scope('final_norm'):
            x = self.final_norm(x)
        if seqp:
            from ..distributed import collective as C
            x = C._c_gather_seq_replicated(x, group=qkv.group)
        return x

    def forward_paged(self, input_ids, position_ids, kv_list, rows):
        """Serving-engine forward over the paged KV pool: kv_list is the
        per-layer [(k_pages, v_pages)] Tensors; returns (hidden,
        new_kv_list). See serving/engine.py for the step around it."""
        x = self.embeddings(input_ids, position_ids)
        new_kv = []
        for layer, c in zip(self.layers, kv_list):
            x, nc = layer.forward_paged(x, c, rows)
            new_kv.append(nc)
        with jax.named_scope('final_norm'):
            return self.final_norm(x), new_kv

    def init_caches(self, batch, max_len, dtype=None):
        import jax.numpy as _jnp
        cfg = self.config
        hd = cfg.hidden_size // cfg.num_heads
        nh_local = self.layers[0].attn.local_heads
        dt = dtype or self.embeddings.word_embeddings.weight.dtype
        return [(Tensor(_jnp.zeros((batch, nh_local, max_len, hd), dt)),
                 Tensor(_jnp.zeros((batch, nh_local, max_len, hd), dt)))
                for _ in range(cfg.num_layers)]


class GPTForCausalLM(nn.Layer):
    """LM head tied to the (vocab-parallel) input embedding — parity with
    the SharedLayerDesc tying in the reference's pipeline GPT (A.4)."""

    _supports_sequence_parallel = True

    def __init__(self, config):
        super().__init__()
        self.gpt = GPTModel(config)
        self.config = config

    # -- the serving-model protocol (serving/protocol.py) -------------------
    @property
    def mp_degree(self):
        return self.gpt.layers[0].attn.world_size

    def kv_cache_spec(self):
        from ..serving.protocol import KVLayerSpec
        return [KVLayerSpec(l.attn.local_heads * l.attn.world_size,
                            l.attn.head_dim, None)
                for l in self.gpt.layers]

    def lm_head_weight(self):
        return self.gpt.embeddings.word_embeddings.weight

    # every route of the engine's: gpt.forward_paged takes them all
    paged_routes = ('plain', 'fused', 'verify', 'int8_kv', 'int8_weights',
                    'mp')

    def moe_counters(self):
        return None

    def forward_paged(self, input_ids, position_ids, kv_list, rows,
                      moe_counters=None):
        h, new_kv = self.gpt.forward_paged(input_ids, position_ids,
                                           kv_list, rows)
        return h, new_kv, moe_counters

    def forward(self, input_ids, position_ids=None):
        hidden = self.gpt(input_ids, position_ids)
        # Megatron "copy to tensor-parallel region" (f op) in front of
        # the vocab-parallel head matmul: identity forward, psum('mp')
        # backward. Without it each mp rank's backward carries only its
        # own vocab shard's PARTIAL cotangent into final_norm and the
        # last decoder segment, so replicated-param grads there diverge
        # per rank (ColumnParallelLinear heads get this via their own
        # _c_identity; the tied-matmul path was missing it).
        from ..distributed import collective as C
        if self.gpt.embeddings.word_embeddings.world_size > 1 \
                and C.in_spmd_region():
            hidden = C._c_identity(
                hidden, group=self.gpt.embeddings.word_embeddings.group)
        w = self.gpt.embeddings.word_embeddings.weight  # [V(/mp local), H]
        with jax.named_scope('head'):
            logits = M.matmul(hidden, w, transpose_y=True)
        return logits  # class dim vocab-parallel under mp

    @staticmethod
    def _sample_next(step_logits, temperature, top_k):
        import numpy as np_
        step = step_logits / max(temperature, 1e-6)
        if top_k and top_k > 0:
            kth = np_.sort(step, axis=-1)[:, -top_k][:, None]
            z = np_.where(step < kth, -1e30, step)
            z = z - z.max(-1, keepdims=True)
            p = np_.exp(z) / np_.exp(z).sum(-1, keepdims=True)
            return np_.asarray(
                [np_.random.choice(p.shape[-1], p=row) for row in p])
        return step.argmax(-1)

    def generate(self, input_ids, max_new_tokens=32, temperature=1.0,
                 top_k=0, eos_token_id=None, use_cache=True):
        """Greedy / top-k sampling decode (parity role: the beam_search/
        sampling ops tier). use_cache=True runs the O(1)-per-token KV-cached
        path with a jitted fixed-shape decode step; False re-forwards the
        full window per token."""
        ids_probe = input_ids.data if isinstance(input_ids, Tensor) \
            else input_ids
        fits = (ids_probe.shape[-1] + max_new_tokens
                <= self.config.max_seq_len)
        if use_cache and fits:
            return self._generate_cached(input_ids, max_new_tokens,
                                         temperature, top_k, eos_token_id)
        # beyond max_seq_len the cached path would truncate; the sliding-
        # window re-forward below matches the uncached semantics exactly
        import numpy as np_
        from ..core import rng as rng_mod
        from ..core.autograd import no_grad
        ids = np_.asarray(input_ids.data if isinstance(input_ids, Tensor)
                          else input_ids)
        # early-exit once EVERY row has emitted EOS at least once (rows
        # that finish early keep emitting until the laggards catch up,
        # so the tokens that ARE emitted are step-for-step identical to
        # the run-to-max_new_tokens output)
        done = np_.zeros(ids.shape[0], bool)
        with no_grad():
            for _ in range(max_new_tokens):
                window = ids[:, -self.config.max_seq_len:]
                logits = self(Tensor(window.astype('int32')))
                nxt = self._sample_next(np_.asarray(logits.data)[:, -1, :],
                                        temperature, top_k)
                ids = np_.concatenate([ids, nxt[:, None]], axis=1)
                if eos_token_id is not None:
                    done |= (nxt == eos_token_id)
                    if done.all():
                        break
        return Tensor(ids)

    def generate_batch(self, prompts, max_new_tokens=32, temperature=1.0,
                       top_k=0, eos_token_id=None, serving_config=None,
                       engine=None, **engine_kw):
        """Continuous-batching decode over the serving engine: `prompts`
        is a LIST of ragged token-id sequences (mixed lengths welcome —
        that is the point). Returns a list of full token lists (prompt +
        generated) in submission order. The engine (paged KV pool +
        batched one-token decode, serving/engine.py) is cached on the
        model and reused across same-config calls; a different config
        replaces it (the old engine is shut down — each pins a device
        KV pool). Pass `engine=` to share one across models of the
        same weights, `serving_config=`/knobs to size it."""
        from ..serving import ServingEngine, ServingConfig
        eng = engine
        if eng is None:
            cfg = serving_config or ServingConfig(**engine_kw)
            # key on the resolved config's CONTENTS — two calls with
            # different knobs must not share an engine
            key = tuple(sorted((k, repr(v))
                               for k, v in vars(cfg).items()))
            eng = getattr(self, '_serving_engines', {}).get(key)
            if eng is None:
                # ONE live engine per model: each pins a full device KV
                # pool, so a config change evicts (and shuts down) the
                # old engine rather than growing an unbounded cache
                for old in getattr(self, '_serving_engines',
                                   {}).values():
                    old.shutdown()
                eng = ServingEngine(self, cfg)
                self._serving_engines = {key: eng}
        return eng.generate(prompts, max_new_tokens=max_new_tokens,
                            eos_token_id=eos_token_id,
                            temperature=temperature, top_k=top_k)

    def generate_scan(self, input_ids, max_new_tokens=32, temperature=1.0,
                      top_k=0, seed=0):
        """Whole-generation-in-one-dispatch decode: prefill + the full
        token loop run as ONE jitted lax.scan (amortizes host→device
        launch latency). Sampling runs on device via
        jax.random; greedy when top_k == 0."""
        import numpy as np_
        from ..core.autograd import no_grad
        from ..jit import bind_arrays
        from jax import lax
        ids = np_.asarray(input_ids.data if isinstance(input_ids, Tensor)
                          else input_ids).astype('int32')
        B, L0 = ids.shape
        max_len = L0 + max_new_tokens
        if max_len > self.config.max_seq_len:
            raise ValueError(
                f"prompt({L0}) + max_new_tokens({max_new_tokens}) exceeds "
                f"max_seq_len({self.config.max_seq_len})")
        model = self
        params = {n: p.data for n, p in self.named_parameters()}
        was_training = self.training
        self.eval()

        def run(ps, prompt, key):
            caches = model.gpt.init_caches(B, max_len)
            kv0 = [(c[0].data, c[1].data) for c in caches]

            def one(tok, pos, kv):
                cts = [(Tensor(k), Tensor(v)) for k, v in kv]
                with bind_arrays(model, ps):
                    pos_ids = Tensor(pos[None].astype(jnp.int32))
                    h, ncs = model.gpt(Tensor(tok), pos_ids, caches=cts,
                                       cache_len=pos)
                    w = model.gpt.embeddings.word_embeddings.weight
                    logits = M.matmul(h, w, transpose_y=True)
                return logits.data[:, -1, :], [(c[0].data, c[1].data)
                                               for c in ncs]

            def prefill_step(kv, t):
                logits, kv = one(lax.dynamic_slice_in_dim(prompt, t, 1, 1),
                                 t, kv)
                return kv, logits

            kv, all_logits = lax.scan(prefill_step, kv0, jnp.arange(L0))
            last = all_logits[-1]

            def decode_step(carry, i):
                kv, last, k = carry
                scaled = last / jnp.maximum(temperature, 1e-6)
                if top_k and top_k > 0:
                    kth = jnp.sort(scaled, axis=-1)[:, -top_k][:, None]
                    scaled = jnp.where(scaled < kth, -1e30, scaled)
                    k, sub = jax.random.split(k)
                    nxt = jax.random.categorical(sub, scaled, axis=-1)
                else:
                    nxt = jnp.argmax(scaled, axis=-1)
                nxt = nxt.astype(jnp.int32)
                last, kv = one(nxt[:, None], L0 + i, kv)
                return (kv, last, k), nxt

            (_, _, _), toks = lax.scan(
                decode_step, (kv, last, key), jnp.arange(max_new_tokens))
            return toks.T  # [B, max_new_tokens]

        with no_grad():
            key = jax.random.key(seed)
            cache_key = (B, L0, max_new_tokens, float(temperature),
                         int(top_k))
            if not hasattr(self, '_gen_cache'):
                self._gen_cache = {}
            jfn = self._gen_cache.get(cache_key)
            if jfn is None:
                jfn = jax.jit(run)
                self._gen_cache[cache_key] = jfn
            new = jfn(params, jnp.asarray(ids), key)
        if was_training:
            self.train()
        return Tensor(np_.concatenate([ids, np_.asarray(new)], axis=1))

    def _generate_cached(self, input_ids, max_new_tokens, temperature,
                         top_k, eos_token_id):
        import numpy as np_
        from ..core.autograd import no_grad
        from ..jit import bind_arrays
        ids = np_.asarray(input_ids.data if isinstance(input_ids, Tensor)
                          else input_ids).astype('int32')
        B, L0 = ids.shape
        max_len = min(self.config.max_seq_len, L0 + max_new_tokens)
        model = self
        params = {n: p.data for n, p in self.named_parameters()}
        was_training = self.training
        self.eval()  # generation is deterministic-forward; dropout keys
        # would otherwise bake into the trace as constants

        with no_grad():
            caches = self.gpt.init_caches(B, max_len)
            cache_arrays = [(c[0].data, c[1].data) for c in caches]

            def step(ps, token, pos, kv):
                cts = [(Tensor(k), Tensor(v)) for k, v in kv]
                with bind_arrays(model, ps):
                    pos_ids = Tensor(pos[None].astype(jnp.int32))
                    h, new_caches = model.gpt(Tensor(token), pos_ids,
                                              caches=cts, cache_len=pos)
                    w = model.gpt.embeddings.word_embeddings.weight
                    logits = M.matmul(h, w, transpose_y=True)
                new_kv = [(c[0].data, c[1].data) for c in new_caches]
                return logits.data[:, -1, :], new_kv

            # donate the cache so XLA updates it in place (no per-token
            # full-cache copy); cache the compiled step across calls
            if not hasattr(self, '_step_cache'):
                self._step_cache = {}
            ck = (B, max_len)
            jit_step = self._step_cache.get(ck)
            if jit_step is None:
                jit_step = jax.jit(step, donate_argnums=(3,))
                self._step_cache[ck] = jit_step

            # prefill: feed prompt tokens sequentially through the cache
            last_logits = None
            for t in range(L0):
                last_logits, cache_arrays = jit_step(
                    params, ids[:, t:t + 1], jnp.asarray(t, jnp.int32),
                    cache_arrays)

            out = ids
            # per-row EOS bookkeeping: stop as soon as every row has
            # emitted its EOS (not only when all rows emit it on the
            # SAME step) — emitted tokens stay identical, the loop just
            # skips the steps where everyone was already finished
            done = np_.zeros(B, bool)
            for i in range(max_new_tokens):
                pos = L0 + i
                if pos >= max_len:
                    break
                nxt = self._sample_next(np_.asarray(last_logits),
                                        temperature, top_k)
                out = np_.concatenate([out, nxt[:, None].astype('int32')],
                                      axis=1)
                if eos_token_id is not None:
                    done |= (nxt == eos_token_id)
                    if done.all():
                        break
                last_logits, cache_arrays = jit_step(
                    params, out[:, -1:], jnp.asarray(pos, jnp.int32),
                    cache_arrays)
        if was_training:
            self.train()
        return Tensor(out)


class GPTPretrainingCriterion(nn.Layer):
    """Parity: vocab-parallel softmax CE loss with mean over tokens."""

    def __init__(self, config=None):
        super().__init__()
        self.ce = ParallelCrossEntropy()

    def forward(self, logits, labels, loss_mask=None):
        loss = self.ce(logits, labels)
        if loss_mask is not None:
            masked = M.multiply(manip.reshape(loss, labels.shape), loss_mask)
            return M.divide(M.sum(masked), M.sum(loss_mask))
        return M.mean(loss)


class GPTLMHead(nn.Layer):
    """Final norm + (vocab-parallel) LM head + criterion — the last pipeline
    stage's tail. Untied head weight (the tied variant runs under the
    non-pipelined hybrid engine; tying across stages costs a pp-psum the
    engine applies to the embed tree — A.4)."""

    def __init__(self, config):
        super().__init__()
        self.norm = nn.LayerNorm(config.hidden_size,
                                 epsilon=config.layer_norm_eps)
        init = I.Normal(0.0, config.initializer_range)
        self.out = ColumnParallelLinear(
            config.hidden_size, config.vocab_size,
            weight_attr=nn.ParamAttr(initializer=init),
            has_bias=False, gather_output=False)
        self.ce = ParallelCrossEntropy()

    @jax.named_scope('head_loss')
    def forward(self, hidden, labels):
        h = self.norm(hidden)
        from ..distributed import collective as C
        vocab_parallel = self.out.world_size > 1 and C.in_spmd_region()
        n_tokens = int(np.prod(h.shape[:-1]))
        if not vocab_parallel and n_tokens * self.out.out_features > 2 ** 28:
            # big-logits regime: chunked fused projection+xent — the
            # [tokens, vocab] logits never hit HBM (recompute backward, see
            # ops/nn_ops.fused_linear_cross_entropy). Below the threshold
            # the single matmul + fused hard-xent (bf16-only residual) is
            # faster: recompute would spend ~2% extra FLOPs to save memory
            # that isn't scarce.
            return F.fused_linear_cross_entropy(
                h, self.out.weight, labels, ignore_index=-100,
                transpose_y=False)
        logits = self.out(h)
        loss = self.ce(logits, labels)
        return M.mean(loss)


def build_gpt_pipeline(config):
    """(embed, blocks, head) triple for SpmdPipelineEngine."""
    embed = GPTEmbeddings(config)
    blocks = [GPTDecoderLayer(config) for _ in range(config.num_layers)]
    head = GPTLMHead(config)
    return embed, blocks, head


def gpt_pipeline_descs(config):
    """LayerDesc list for PipelineLayer partitioning (parity: pp GPT built
    from LayerDesc/SharedLayerDesc, pp_layers.py)."""
    from ..distributed.fleet.meta_parallel import LayerDesc, SharedLayerDesc
    descs = [SharedLayerDesc('embed', GPTEmbeddings, config=config)]
    for _ in range(config.num_layers):
        descs.append(LayerDesc(GPTDecoderLayer, config))
    descs.append(LayerDesc(nn.LayerNorm, config.hidden_size))
    return descs
