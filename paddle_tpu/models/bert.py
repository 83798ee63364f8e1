"""BERT (BASELINE config 3: BERT-base pretraining with bf16 + ZeRO-2).

Reference parity: the transformer encoder stack the reference builds from
nn/layer/transformer.py (TransformerEncoder:622) with MLM+NSP pretraining
heads, trained via fleet sharding (dist_sharding tests pattern).
"""
import math

import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..ops import math as M
from ..ops import manip
from ..ops import nn_ops as F
from ..nn import initializer as I


class BertConfig:
    def __init__(self, vocab_size=30522, hidden_size=768, num_layers=12,
                 num_heads=12, intermediate_size=3072, max_seq_len=512,
                 type_vocab_size=2, hidden_dropout=0.1, attn_dropout=0.1,
                 initializer_range=0.02, layer_norm_eps=1e-12,
                 mlm_loss_chunks=16):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.intermediate_size = intermediate_size
        self.max_seq_len = max_seq_len
        self.type_vocab_size = type_vocab_size
        self.hidden_dropout = hidden_dropout
        self.attn_dropout = attn_dropout
        self.initializer_range = initializer_range
        self.layer_norm_eps = layer_norm_eps
        # fused-xent chunk count (16 measured fastest at B=64,L=512 on v5e)
        self.mlm_loss_chunks = mlm_loss_chunks


class BertEmbeddings(nn.Layer):
    def __init__(self, config):
        super().__init__()
        init = nn.ParamAttr(
            initializer=I.Normal(0.0, config.initializer_range))
        self.word_embeddings = nn.Embedding(config.vocab_size,
                                            config.hidden_size,
                                            weight_attr=init)
        self.position_embeddings = nn.Embedding(config.max_seq_len,
                                                config.hidden_size,
                                                weight_attr=init)
        self.token_type_embeddings = nn.Embedding(config.type_vocab_size,
                                                  config.hidden_size,
                                                  weight_attr=init)
        self.layer_norm = nn.LayerNorm(config.hidden_size,
                                       epsilon=config.layer_norm_eps)
        self.dropout = nn.Dropout(config.hidden_dropout)

    @jax.named_scope('embed')
    def forward(self, input_ids, token_type_ids=None, position_ids=None):
        L = input_ids.shape[-1]
        if position_ids is None:
            position_ids = Tensor(jnp.arange(L, dtype=jnp.int32))
        if token_type_ids is None:
            token_type_ids = Tensor(jnp.zeros(input_ids.shape, jnp.int32))
        x = M.add(M.add(self.word_embeddings(input_ids),
                        self.position_embeddings(position_ids)),
                  self.token_type_embeddings(token_type_ids))
        # remat boundary (docs/performance.md#remat-policy): saved under
        # attn_mlp_boundaries so the backward never replays the three
        # embedding gathers; the LN/dropout tail recomputes
        from ..distributed.fleet.utils.recompute import tag_tensor
        return self.dropout(self.layer_norm(
            tag_tensor(x, 'embed_out')))


class BertModel(nn.Layer):
    def __init__(self, config):
        super().__init__()
        self.config = config
        self.embeddings = BertEmbeddings(config)
        encoder_layer = nn.TransformerEncoderLayer(
            config.hidden_size, config.num_heads, config.intermediate_size,
            dropout=config.hidden_dropout, activation='gelu',
            attn_dropout=config.attn_dropout)
        self.encoder = nn.TransformerEncoder(encoder_layer,
                                             config.num_layers)
        self.pooler = nn.Linear(config.hidden_size, config.hidden_size)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        x = self.embeddings(input_ids, token_type_ids)
        if attention_mask is not None and attention_mask.ndim == 2:
            mask = manip.unsqueeze(attention_mask, [1, 2])
            attention_mask = M.scale(M.subtract(
                Tensor(jnp.asarray(1.0)), mask.astype('float32')), -1e9)
        x = self.encoder(x, attention_mask)
        pooled = M.tanh(self.pooler(x[:, 0]))
        return x, pooled


class BertForPretraining(nn.Layer):
    """MLM + NSP heads."""

    def __init__(self, config):
        super().__init__()
        self.bert = BertModel(config)
        self.config = config
        self.mlm_transform = nn.Linear(config.hidden_size,
                                       config.hidden_size)
        self.mlm_norm = nn.LayerNorm(config.hidden_size,
                                     epsilon=config.layer_norm_eps)
        self.nsp = nn.Linear(config.hidden_size, 2)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None,
                masked_lm_labels=None, next_sentence_label=None):
        """Without labels: returns (mlm_logits, nsp_logits). With labels:
        returns the pretraining loss, computed through the chunked fused
        projection-xent so the [B*L, vocab] logits never materialize."""
        seq, pooled = self.bert(input_ids, token_type_ids, attention_mask)
        with jax.named_scope('head_loss'):
            return self._heads(seq, pooled, masked_lm_labels,
                               next_sentence_label)

    def _heads(self, seq, pooled, masked_lm_labels, next_sentence_label):
        h = self.mlm_norm(F.gelu(self.mlm_transform(seq)))
        w = self.bert.embeddings.word_embeddings.weight
        nsp_logits = self.nsp(pooled)
        if masked_lm_labels is not None:
            mlm = F.fused_linear_cross_entropy(
                h, w, masked_lm_labels, ignore_index=-100,
                chunks=self.config.mlm_loss_chunks)
            if next_sentence_label is None:
                return mlm
            nsp = F.cross_entropy(nsp_logits, next_sentence_label)
            return M.add(mlm, nsp)
        mlm_logits = M.matmul(h, w, transpose_y=True)
        return mlm_logits, nsp_logits


def bert_pretrain_loss(mlm_logits, nsp_logits, mlm_labels, nsp_labels,
                       ignore_index=-100):
    mlm = F.cross_entropy(mlm_logits, mlm_labels,
                          ignore_index=ignore_index)
    nsp = F.cross_entropy(nsp_logits, nsp_labels)
    return M.add(mlm, nsp)
