"""Transformer stack.

Reference parity: python/paddle/nn/layer/transformer.py — MultiHeadAttention
(:109, with Cache/StaticCache for decoding), TransformerEncoderLayer(:437),
TransformerEncoder(:622), TransformerDecoderLayer(:731), TransformerDecoder
(:969), Transformer(:1112). Attention math stays as large batched matmuls so
XLA tiles it onto the MXU; the Pallas flash-attention kernel
(paddle_tpu/ops/pallas/flash_attention.py) is used automatically for long
sequences when no additive mask is provided.
"""
import collections

import jax
import jax.numpy as jnp

from ...core.tensor import Tensor
from ...core.autograd import run_op
from ...ops import nn_ops as F
from ...ops import math as M
from ...ops import manip
from .base import Layer
from .common import Linear, Dropout
from .norm import LayerNorm
from .container import LayerList


def _convert_attention_mask(attn_mask, dtype):
    if attn_mask is None:
        return None
    if attn_mask.dtype == jnp.bool_:
        return Tensor(jnp.where(attn_mask.data, 0.0, -1e9).astype(dtype))
    return attn_mask


def _as_key_bias(attn_mask):
    """Reduce an additive attention mask to a [B, L_k] key-padding bias if
    it has that structure, else None (caller falls back to the dense path).

    Only the [B|1, 1, 1, L_k] form qualifies: per paddle broadcast
    semantics a 2-D mask is [L_q, L_k] (e.g. the causal mask from
    Transformer.generate_square_subsequent_mask) and a 3-D mask's leading
    dim broadcasts against heads — neither is expressible as a per-key
    bias."""
    a = attn_mask.data if isinstance(attn_mask, Tensor) else attn_mask
    if a.ndim == 4 and a.shape[1] == 1 and a.shape[2] == 1:
        return a[:, 0, 0, :]              # [B|1, L_k]
    return None


class MultiHeadAttention(Layer):
    """Parity: nn/layer/transformer.py:109."""

    Cache = collections.namedtuple("Cache", ["k", "v"])
    StaticCache = collections.namedtuple("StaticCache", ["k", "v"])

    def __init__(self, embed_dim, num_heads, dropout=0.0, kdim=None,
                 vdim=None, need_weights=False, weight_attr=None,
                 bias_attr=None):
        super().__init__()
        self.embed_dim = embed_dim
        self.kdim = kdim or embed_dim
        self.vdim = vdim or embed_dim
        self.num_heads = num_heads
        self.dropout = dropout
        self.need_weights = need_weights
        self.head_dim = embed_dim // num_heads
        assert self.head_dim * num_heads == embed_dim

        self.q_proj = Linear(embed_dim, embed_dim, weight_attr, bias_attr)
        self.k_proj = Linear(self.kdim, embed_dim, weight_attr, bias_attr)
        self.v_proj = Linear(self.vdim, embed_dim, weight_attr, bias_attr)
        self.out_proj = Linear(embed_dim, embed_dim, weight_attr, bias_attr)

    def _prepare_qkv(self, query, key, value, cache=None):
        q = self.q_proj(query)
        q = manip.reshape(q, [0, 0, self.num_heads, self.head_dim])
        q = manip.transpose(q, [0, 2, 1, 3])
        if isinstance(cache, self.StaticCache):
            k, v = cache.k, cache.v
        else:
            k = self.k_proj(key)
            v = self.v_proj(value)
            k = manip.reshape(k, [0, 0, self.num_heads, self.head_dim])
            k = manip.transpose(k, [0, 2, 1, 3])
            v = manip.reshape(v, [0, 0, self.num_heads, self.head_dim])
            v = manip.transpose(v, [0, 2, 1, 3])
        if isinstance(cache, self.Cache):
            k = manip.concat([cache.k, k], axis=2)
            v = manip.concat([cache.v, v], axis=2)
            cache = self.Cache(k, v)
        return (q, k, v) if cache is None else (q, k, v, cache)

    def gen_cache(self, key, value=None, type=Cache):
        if type == MultiHeadAttention.StaticCache:
            k = self.k_proj(key)
            v = self.v_proj(value if value is not None else key)
            k = manip.transpose(
                manip.reshape(k, [0, 0, self.num_heads, self.head_dim]),
                [0, 2, 1, 3])
            v = manip.transpose(
                manip.reshape(v, [0, 0, self.num_heads, self.head_dim]),
                [0, 2, 1, 3])
            return self.StaticCache(k, v)
        if value is None:
            batch = key.shape[0]
            k = Tensor(jnp.zeros([batch, self.num_heads, 0, self.head_dim],
                                 key.dtype))
            v = Tensor(jnp.zeros([batch, self.num_heads, 0, self.head_dim],
                                 key.dtype))
            return self.Cache(k, v)
        return self.Cache(key, value)

    def core_attention(self, q, k, v, attn_mask=None):
        flash = self._try_flash(q, k, v, attn_mask)
        if flash is not None:
            return flash, None
        from ...ops.pallas import scaffold as _scaffold
        _scaffold.record_route('flash_attention', False)
        scale = self.head_dim ** -0.5
        product = M.matmul(M.scale(q, scale), k, transpose_y=True)
        if attn_mask is not None:
            attn_mask = _convert_attention_mask(attn_mask, product.dtype)
            product = M.add(product, attn_mask)
        weights = F.softmax(product)
        if self.dropout:
            weights = F.dropout(weights, self.dropout, training=self.training)
        out = M.matmul(weights, v)
        return out, weights

    def _flash_eligible(self, B, Lq, Lk, attn_mask):
        """Shared eligibility + mask reduction for both flash routes:
        self-attention-shaped (L_q == L_k, tile-aligned, above the
        tunable FLAGS_flash_min_seq crossover vs XLA's fused dense
        attention), no attention-weight output, no active attention
        dropout, MXU-lane-shaped head_dim, and a mask that is None or
        reduces to a key-padding bias. Returns (ok, bias)."""
        from ...core import flags
        if not flags.flag('FLAGS_use_flash_attention', True):
            return False, None
        if self.need_weights or (self.dropout and self.training):
            return False, None
        min_seq = flags.flag('FLAGS_flash_min_seq', 1024)
        min_seq = 1024 if min_seq is None else int(min_seq)
        if Lq != Lk or Lq < min_seq or Lq % 256 != 0:
            return False, None
        if self.head_dim not in (64, 128, 256):
            return False, None
        bias = None
        if attn_mask is not None:
            attn_mask = _convert_attention_mask(attn_mask, jnp.float32)
            bias = _as_key_bias(attn_mask)
            if bias is None:
                return False, None
            if bias.shape[0] == 1 and B > 1:
                bias = jnp.broadcast_to(bias, (B, bias.shape[1]))
            if bias.shape[-1] != Lk:
                return False, None
        return True, bias

    def _try_flash(self, q, k, v, attn_mask):
        """[B, nh, L, hd] flash route (dense-path layout). Returns the
        context or None to fall back."""
        ok, bias = self._flash_eligible(q.shape[0], q.shape[2],
                                        k.shape[2], attn_mask)
        if not ok:
            return None
        from ...ops.pallas.flash_attention import mha_flash_attention
        return mha_flash_attention(q, k, v, key_bias=bias, causal=False)

    def _try_flash_blhd(self, q4, k4, v4, attn_mask):
        """Transpose-free flash route: q4/k4/v4 in the natural projection
        layout [B, L, nh, hd] (the [B, nh, L, hd] physical transpose XLA
        would materialize costs ~14% of a BERT step); the packed kernel
        runs every head over static column slices. Returns the
        [B, L, nh, hd] context or None to fall back."""
        from ...core import flags
        if not flags.flag('FLAGS_flash_packed_mha', True):
            return None                 # A/B: fall to the BHLD route
        ok, bias = self._flash_eligible(q4.shape[0], q4.shape[1],
                                        k4.shape[1], attn_mask)
        if not ok:
            return None
        from ...ops.pallas.flash_attention import mha_flash_attention_blhd
        return mha_flash_attention_blhd(q4, k4, v4, key_bias=bias,
                                        causal=False)

    def forward(self, query, key=None, value=None, attn_mask=None, cache=None):
        key = query if key is None else key
        value = key if value is None else value
        if cache is None:
            # project + split heads WITHOUT transposing; the flash route
            # consumes this layout directly, the dense path transposes
            q4 = manip.reshape(self.q_proj(query),
                               [0, 0, self.num_heads, self.head_dim])
            k4 = manip.reshape(self.k_proj(key),
                               [0, 0, self.num_heads, self.head_dim])
            v4 = manip.reshape(self.v_proj(value),
                               [0, 0, self.num_heads, self.head_dim])
            ctx = self._try_flash_blhd(q4, k4, v4, attn_mask)
            if ctx is not None:
                out = manip.reshape(ctx, [0, 0, self.embed_dim])
                return self.out_proj(out)
            q = manip.transpose(q4, [0, 2, 1, 3])
            k = manip.transpose(k4, [0, 2, 1, 3])
            v = manip.transpose(v4, [0, 2, 1, 3])
        else:
            q, k, v, cache = self._prepare_qkv(query, key, value, cache)

        out, weights = self.core_attention(q, k, v, attn_mask)
        out = manip.transpose(out, [0, 2, 1, 3])
        out = manip.reshape(out, [0, 0, self.embed_dim])
        out = self.out_proj(out)

        outs = [out]
        if self.need_weights:
            outs.append(weights)
        if cache is not None:
            outs.append(cache)
        return out if len(outs) == 1 else tuple(outs)


class TransformerEncoderLayer(Layer):
    """Parity: nn/layer/transformer.py:437."""

    def __init__(self, d_model, nhead, dim_feedforward, dropout=0.1,
                 activation="relu", attn_dropout=None, act_dropout=None,
                 normalize_before=False, weight_attr=None, bias_attr=None):
        super().__init__()
        attn_dropout = dropout if attn_dropout is None else attn_dropout
        act_dropout = dropout if act_dropout is None else act_dropout
        self.normalize_before = normalize_before
        self.self_attn = MultiHeadAttention(d_model, nhead,
                                            dropout=attn_dropout,
                                            weight_attr=weight_attr,
                                            bias_attr=bias_attr)
        self.linear1 = Linear(d_model, dim_feedforward, weight_attr, bias_attr)
        self.dropout = Dropout(act_dropout, mode="upscale_in_train")
        self.linear2 = Linear(dim_feedforward, d_model, weight_attr, bias_attr)
        self.norm1 = LayerNorm(d_model)
        self.norm2 = LayerNorm(d_model)
        self.dropout1 = Dropout(dropout, mode="upscale_in_train")
        self.dropout2 = Dropout(dropout, mode="upscale_in_train")
        self.activation = getattr(F, activation)

    def _norm(self, which, x):
        # jax.named_scope: `norm1/attn/norm2/mlp` on the profile's op
        # names (metadata.op_name); a Pallas call keeps its own `name=`
        with jax.named_scope(which):
            return getattr(self, which)(x)

    def forward(self, src, src_mask=None, cache=None):
        residual = src
        if self.normalize_before:
            src = self._norm('norm1', src)
        with jax.named_scope('attn'):
            if cache is None:
                src = self.self_attn(src, src, src, src_mask)
            else:
                src, incremental_cache = self.self_attn(
                    src, src, src, src_mask, cache)
        # remat boundary tag (docs/performance.md#remat-policy): the
        # attention output is a contraction boundary — saved under the
        # attn_mlp_boundaries policy, the joins/norms recompute
        from ...distributed.fleet.utils.recompute import (
            tag_tensor as _remat_tag)
        src = _remat_tag(src, 'attn_out')
        # residual joins and the FFN bias+GELU route through the fused
        # Pallas primitives (ops/pallas/fused_elementwise.py): same ops
        # and RNG stream as dropout-then-add / linear-then-gelu on the
        # reference route, one kernel pass each on TPU
        src = F.dropout_add(src, residual, p=self.dropout1.p,
                            training=self.training,
                            mode=self.dropout1.mode)
        if not self.normalize_before:
            src = self._norm('norm1', src)

        residual = src
        if self.normalize_before:
            src = self._norm('norm2', src)
        with jax.named_scope('mlp'):
            if self.activation is F.gelu and self.linear1.bias is not None:
                h = F.bias_gelu(
                    _remat_tag(F.linear(src, self.linear1.weight),
                               'mlp_fc1'),
                    self.linear1.bias)
            else:
                h = self.activation(
                    _remat_tag(self.linear1(src), 'mlp_fc1'))
            src = _remat_tag(self.linear2(self.dropout(h)), 'mlp_out')
        src = F.dropout_add(src, residual, p=self.dropout2.p,
                            training=self.training,
                            mode=self.dropout2.mode)
        if not self.normalize_before:
            src = self._norm('norm2', src)
        return src if cache is None else (src, incremental_cache)

    def gen_cache(self, src):
        return self.self_attn.gen_cache(src)


class TransformerEncoder(Layer):
    """Parity: nn/layer/transformer.py:622."""

    def __init__(self, encoder_layer, num_layers, norm=None):
        super().__init__()
        import copy
        self.layers = LayerList([encoder_layer] + [
            type(encoder_layer)(**_layer_config(encoder_layer))
            for _ in range(num_layers - 1)])
        self.num_layers = num_layers
        self.norm = norm

    def forward(self, src, src_mask=None, cache=None):
        output = src
        new_caches = []
        for i, mod in enumerate(self.layers):
            if cache is None:
                output = mod(output, src_mask=src_mask)
            else:
                output, new_cache = mod(output, src_mask=src_mask,
                                        cache=cache[i])
                new_caches.append(new_cache)
        if self.norm is not None:
            output = self.norm(output)
        return output if cache is None else (output, new_caches)

    def gen_cache(self, src):
        return [layer.gen_cache(src) for layer in self.layers]


def _layer_config(layer):
    if isinstance(layer, TransformerEncoderLayer):
        return dict(d_model=layer.self_attn.embed_dim,
                    nhead=layer.self_attn.num_heads,
                    dim_feedforward=layer.linear1.out_features,
                    dropout=layer.dropout1.p,
                    activation=layer.activation.__name__,
                    attn_dropout=layer.self_attn.dropout,
                    act_dropout=layer.dropout.p,
                    normalize_before=layer.normalize_before)
    if isinstance(layer, TransformerDecoderLayer):
        return dict(d_model=layer.self_attn.embed_dim,
                    nhead=layer.self_attn.num_heads,
                    dim_feedforward=layer.linear1.out_features,
                    dropout=layer.dropout1.p,
                    activation=layer.activation.__name__,
                    normalize_before=layer.normalize_before)
    raise TypeError(type(layer))


class TransformerDecoderLayer(Layer):
    """Parity: nn/layer/transformer.py:731."""

    def __init__(self, d_model, nhead, dim_feedforward, dropout=0.1,
                 activation="relu", attn_dropout=None, act_dropout=None,
                 normalize_before=False, weight_attr=None, bias_attr=None):
        super().__init__()
        attn_dropout = dropout if attn_dropout is None else attn_dropout
        act_dropout = dropout if act_dropout is None else act_dropout
        self.normalize_before = normalize_before
        self.self_attn = MultiHeadAttention(d_model, nhead, attn_dropout,
                                            weight_attr=weight_attr,
                                            bias_attr=bias_attr)
        self.cross_attn = MultiHeadAttention(d_model, nhead, attn_dropout,
                                             weight_attr=weight_attr,
                                             bias_attr=bias_attr)
        self.linear1 = Linear(d_model, dim_feedforward, weight_attr, bias_attr)
        self.dropout = Dropout(act_dropout, mode="upscale_in_train")
        self.linear2 = Linear(dim_feedforward, d_model, weight_attr, bias_attr)
        self.norm1 = LayerNorm(d_model)
        self.norm2 = LayerNorm(d_model)
        self.norm3 = LayerNorm(d_model)
        self.dropout1 = Dropout(dropout, mode="upscale_in_train")
        self.dropout2 = Dropout(dropout, mode="upscale_in_train")
        self.dropout3 = Dropout(dropout, mode="upscale_in_train")
        self.activation = getattr(F, activation)

    def forward(self, tgt, memory, tgt_mask=None, memory_mask=None,
                cache=None):
        residual = tgt
        if self.normalize_before:
            tgt = self.norm1(tgt)
        if cache is None:
            tgt = self.self_attn(tgt, tgt, tgt, tgt_mask)
        else:
            tgt, incremental_cache = self.self_attn(tgt, tgt, tgt, tgt_mask,
                                                    cache[0])
        tgt = M.add(residual, self.dropout1(tgt))
        if not self.normalize_before:
            tgt = self.norm1(tgt)

        residual = tgt
        if self.normalize_before:
            tgt = self.norm2(tgt)
        if cache is None:
            tgt = self.cross_attn(tgt, memory, memory, memory_mask)
        else:
            tgt = self.cross_attn(tgt, memory, memory, memory_mask, cache[1])
            tgt, static_cache = tgt if isinstance(tgt, tuple) else (tgt, cache[1])
        tgt = M.add(residual, self.dropout2(tgt))
        if not self.normalize_before:
            tgt = self.norm2(tgt)

        residual = tgt
        if self.normalize_before:
            tgt = self.norm3(tgt)
        tgt = self.linear2(self.dropout(self.activation(self.linear1(tgt))))
        tgt = M.add(residual, self.dropout3(tgt))
        if not self.normalize_before:
            tgt = self.norm3(tgt)
        return tgt if cache is None else (tgt, (incremental_cache,
                                                static_cache))

    def gen_cache(self, memory):
        incremental_cache = self.self_attn.gen_cache(memory)
        static_cache = self.cross_attn.gen_cache(
            memory, memory, type=MultiHeadAttention.StaticCache)
        return incremental_cache, static_cache


class TransformerDecoder(Layer):
    """Parity: nn/layer/transformer.py:969."""

    def __init__(self, decoder_layer, num_layers, norm=None):
        super().__init__()
        self.layers = LayerList([decoder_layer] + [
            type(decoder_layer)(**_layer_config(decoder_layer))
            for _ in range(num_layers - 1)])
        self.num_layers = num_layers
        self.norm = norm

    def forward(self, tgt, memory, tgt_mask=None, memory_mask=None,
                cache=None):
        output = tgt
        new_caches = []
        for i, mod in enumerate(self.layers):
            if cache is None:
                output = mod(output, memory, tgt_mask=tgt_mask,
                             memory_mask=memory_mask)
            else:
                output, new_cache = mod(output, memory, tgt_mask=tgt_mask,
                                        memory_mask=memory_mask,
                                        cache=cache[i])
                new_caches.append(new_cache)
        if self.norm is not None:
            output = self.norm(output)
        return output if cache is None else (output, new_caches)

    def gen_cache(self, memory, do_zip=False):
        cache = [layer.gen_cache(memory) for layer in self.layers]
        if do_zip:
            cache = list(zip(*cache))
        return cache


class Transformer(Layer):
    """Parity: nn/layer/transformer.py:1112."""

    def __init__(self, d_model=512, nhead=8, num_encoder_layers=6,
                 num_decoder_layers=6, dim_feedforward=2048, dropout=0.1,
                 activation="relu", attn_dropout=None, act_dropout=None,
                 normalize_before=False, weight_attr=None, bias_attr=None,
                 custom_encoder=None, custom_decoder=None):
        super().__init__()
        if custom_encoder is not None:
            self.encoder = custom_encoder
        else:
            encoder_layer = TransformerEncoderLayer(
                d_model, nhead, dim_feedforward, dropout, activation,
                attn_dropout, act_dropout, normalize_before, weight_attr,
                bias_attr)
            encoder_norm = LayerNorm(d_model) if normalize_before else None
            self.encoder = TransformerEncoder(encoder_layer,
                                              num_encoder_layers, encoder_norm)
        if custom_decoder is not None:
            self.decoder = custom_decoder
        else:
            decoder_layer = TransformerDecoderLayer(
                d_model, nhead, dim_feedforward, dropout, activation,
                attn_dropout, act_dropout, normalize_before, weight_attr,
                bias_attr)
            decoder_norm = LayerNorm(d_model) if normalize_before else None
            self.decoder = TransformerDecoder(decoder_layer,
                                              num_decoder_layers, decoder_norm)
        self.d_model = d_model
        self.nhead = nhead

    def forward(self, src, tgt, src_mask=None, tgt_mask=None,
                memory_mask=None):
        memory = self.encoder(src, src_mask=src_mask)
        output = self.decoder(tgt, memory, tgt_mask=tgt_mask,
                              memory_mask=memory_mask)
        return output

    def generate_square_subsequent_mask(self, length):
        return Tensor(jnp.tril(jnp.ones([length, length])) * 0
                      + jnp.where(jnp.tril(jnp.ones([length, length],
                                                    bool)), 0.0, -jnp.inf))
