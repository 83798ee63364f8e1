"""LFM2-MoE (`model_type` `lfm2_moe`, LiquidAI's LFM2 family with
sparse experts) — a decoder whose layers are of two operator kinds
(gated short convolutions and grouped-query attention) and two FF kinds
(a dense SwiGLU, then sparse experts), for the paged-KV serving engine.

The layer, as equations (benchmarks/reference/lfm2moe.py computes the
same in plain float32; what the public config.json does not carry is
listed under `assumed` in the benchmark's configuration file):

    rms(x; g) = x / sqrt(mean(x^2) + eps) * g                in float32
    h0 = E[ids];  h <- h + Op_l(rms(h; g_op));  h <- h + F_l(rms(h; g_ff))
    Op_l, `layer_types[l]` == 'conv' (gated short convolution):
        [B | C | u] = a . W_in                   W_in [H, 3H], no bias
        v_t = B_t * u_t;  w_t = sum_k c_k * v_{t-K+1+k}   K taps, causal
        Op = (C * w) . W_out
      a request's state at position t is v_{t-K+2} .. v_t (K-1 rows)
    Op_l, 'full_attention': q = a.Wq -> [Hq, D], k = a.Wk, v = a.Wv ->
        [Hk, D]; q <- rms(q; gq), k <- rms(k; gk) over D; rotary
        (rotate-half, theta) on q and k; query head j reads kv head
        j // (Hq / Hk), scale D^-1/2, causal over the whole context;
        Op = o . Wo
    F_l, l < num_dense_layers: SwiGLU at `intermediate_size`
    F_l, else: sum over the token's top-k experts of w_e * SwiGLU_e at
        `moe_intermediate_size` (ops/moe.py: sigmoid scores, the expert
        bias in the choice only, norm_topk_prob, routed_scaling_factor;
        no shared expert)
    logits = rms(h_L; gf) . E^T                                     tied

rms, rotary and the experts' products are models/afmoe.py's and
ops/moe.py's; the one seeded initialiser is models/phi4flash.py's (its
`conv` initialiser for the taps).

On the serving-model protocol (serving/protocol.py): `kv_cache_spec()`
has one entry per attention layer; `state_spec()` declares per conv
layer the tail [K-1, H] in the model's dtype, and `page_state_spec()`
the same per page for every conv layer at once ([conv layers, K-1, H]):
the short convolution (ops/short_conv.py) writes a page's snapshot when
it computes the page's last token and a row flagged `restore` starts
from the page before it, so the prefix cache serves this model.
`forward_paged` runs everything token-wise ONCE over the dispatch's
tokens, and attention and the convolution group by group.
`paged_routes` is the plain route alone.
"""
import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..ops import moe, short_conv
from ..ops.pallas import paged_attention as pa
from ..serving.protocol import KVLayerSpec
from .afmoe import _dot, moe_result, rms_norm, rotary
from .phi4flash import _Params, fill_parameters

CONV, ATTN = 'conv', 'full_attention'


class Lfm2MoeConfig:
    """The published keys by their names, and what one deployment runs
    of them: `layer_types` the layers RUN, in order (the benchmark's cut
    runs published layers 0 and 2-9), `num_dense_layers` how many of
    them lead with the dense FF."""

    def __init__(self, vocab_size=65536, hidden_size=2048,
                 num_hidden_layers=40, layer_types=None, num_dense_layers=2,
                 num_attention_heads=32, num_key_value_heads=8,
                 intermediate_size=11776, moe_intermediate_size=1536,
                 num_experts=64, num_experts_per_tok=4, conv_L_cache=3,
                 norm_eps=1e-5, rope_theta=1e6, routed_scaling_factor=1.0,
                 norm_topk_prob=True, use_expert_bias=True,
                 max_seq_len=128000, dtype='bfloat16',
                 initializer_range=0.02):
        if layer_types is None:
            layer_types = [ATTN if i % 4 == 2 else CONV
                           for i in range(num_hidden_layers)]
        if set(layer_types) - {CONV, ATTN}:
            raise ValueError(f'layer_types {layer_types}')
        if num_attention_heads % num_key_value_heads:
            raise ValueError('query heads must divide over kv heads')
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.layer_types = list(layer_types)
        self.num_layers = len(self.layer_types)
        self.num_dense_layers = num_dense_layers
        self.num_heads = num_attention_heads
        self.num_kv_heads = num_key_value_heads
        self.head_dim = hidden_size // num_attention_heads
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.conv_L_cache = conv_L_cache
        self.norm_eps = norm_eps
        self.rope_theta = rope_theta
        self.routed_scaling_factor = routed_scaling_factor
        self.norm_topk_prob = norm_topk_prob
        self.use_expert_bias = use_expert_bias
        self.max_seq_len = max_seq_len
        self.dtype = dtype
        self.initializer_range = initializer_range


class Lfm2ShortConv(_Params):
    """The gated short convolution. `index` is its place among the conv
    layers: its tail is `state[index]`, its snapshots the plane's row
    `index`."""

    def __init__(self, cfg, index):
        super().__init__()
        self.cfg, self.index = cfg, index
        H, std = cfg.hidden_size, cfg.initializer_range
        self._declare(in_proj=((H, 3 * H), std),
                      conv=((cfg.conv_L_cache, H), 'conv'),
                      out_proj=((H, H), std))

    def forward_paged(self, a, state, rows, page_size):
        """a [1, N, H] -> (out [1, N, H], state): the projections once
        over the tokens, the convolution group by group."""
        bcu = _dot(a, self.in_proj.data)
        tails, plane = state[self.index], state[-1]
        fresh = rows.fresh()
        outs = []
        for at, _, (g,) in rows.groups(bcu):
            q_lens = rows.q_lens[at]
            y, tails, plane = short_conv.short_conv(
                g, self.conv.data, tails, plane, self.index, rows.slots[at],
                q_lens, rows.seq_lens[at] - q_lens, rows.page_tables[at],
                fresh[at], rows.restore[at], page_size)
            outs.append(y)
        state = list(state)
        state[self.index], state[-1] = tails, plane
        return _dot(rows.join(outs), self.out_proj.data), state


class Lfm2Attention(_Params):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        H, D, std = cfg.hidden_size, cfg.head_dim, cfg.initializer_range
        self._declare(q_proj=((H, cfg.num_heads * D), std),
                      k_proj=((H, cfg.num_kv_heads * D), std),
                      v_proj=((H, cfg.num_kv_heads * D), std),
                      out_proj=((cfg.num_heads * D, H), std),
                      q_layernorm=((D,), 'ones'), k_layernorm=((D,), 'ones'))

    def forward_paged(self, a, pos, kv, rows):
        """a [1, N, H], pos [1, N] -> (out [1, N, H], kv)."""
        cfg = self.cfg
        N, D = a.shape[1], cfg.head_dim

        def heads(w, n, norm):
            x = _dot(a, w.data).reshape(1, N, n, D)
            x = rms_norm(x, norm.data, cfg.norm_eps)
            return rotary(x, pos, cfg.rope_theta).reshape(1, N, n * D)

        def write(pool, k, v, page_tables, seq_lens, q_lens):
            return pa.write_kv_pages(*pool, k, v, page_tables, seq_lens,
                                     q_lens)

        def read(pool, q, page_tables, seq_lens, q_lens, shared=None):
            return pa.ragged_paged_attention(
                q, *pool, page_tables, seq_lens, q_lens,
                num_heads=cfg.num_heads, head_dim=D,
                num_kv_heads=cfg.num_kv_heads, shared=shared)
        ctx, kv = rows.attend(
            write, read, kv, heads(self.q_proj, cfg.num_heads,
                                   self.q_layernorm),
            heads(self.k_proj, cfg.num_kv_heads, self.k_layernorm),
            _dot(a, self.v_proj.data))
        return _dot(ctx, self.out_proj.data), kv


class Lfm2MLP(_Params):
    def __init__(self, cfg, width):
        super().__init__()
        H, std = cfg.hidden_size, cfg.initializer_range
        self._declare(w1=((H, width), std), w3=((H, width), std),
                      w2=((width, H), std))

    def forward(self, m):
        return moe.swiglu(m, self.w1.data, self.w3.data, self.w2.data)


class Lfm2SparseMLP(_Params):
    """Router, the expert bias (in the choice only) and the experts."""

    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        H, F, E = cfg.hidden_size, cfg.moe_intermediate_size, \
            cfg.num_experts
        std = cfg.initializer_range
        self._declare(gate=((H, E), std), w1=((E, H, F), std),
                      w3=((E, H, F), std), w2=((E, F, H), std))
        if cfg.use_expert_bias:
            # a trained checkpoint's is not zero, and a zero one would
            # hide a bias that leaks into the weights
            self._declare(expert_bias=((E,), 0.01))

    def forward(self, m, live, counted):
        """m [N, H] -> (out [N, H], rows int32 [count, experts])."""
        cfg = self.cfg
        with jax.named_scope('router'):
            chosen, weights = moe.route(
                m, self.gate.data, self.expert_bias.data
                if cfg.use_expert_bias else None, cfg.num_experts_per_tok,
                cfg.routed_scaling_factor, cfg.norm_topk_prob)
        with jax.named_scope('experts'):
            return moe.experts_swiglu(
                m, chosen, weights, self.w1.data, self.w3.data,
                self.w2.data, live=live, counted=counted)


class Lfm2DecoderLayer(_Params):
    def __init__(self, cfg, index, conv_index):
        super().__init__()
        self.eps = cfg.norm_eps
        self.kind = cfg.layer_types[index]
        H = cfg.hidden_size
        self._declare(operator_norm=((H,), 'ones'), ffn_norm=((H,), 'ones'))
        self.op = Lfm2ShortConv(cfg, conv_index) if self.kind == CONV \
            else Lfm2Attention(cfg)
        self.sparse = index >= cfg.num_dense_layers
        self.ff = Lfm2SparseMLP(cfg) if self.sparse \
            else Lfm2MLP(cfg, cfg.intermediate_size)

    def ff_half(self, h, rows):
        """h + F(rms(h)) -> (h, rows of the expert layer or None)."""
        m = rms_norm(h, self.ffn_norm.data, self.eps)
        with jax.named_scope('mlp'):
            if not self.sparse:
                return h + self.ff(m), None
            f, counts = self.ff(m.reshape(-1, m.shape[-1]),
                                rows.live().reshape(-1), rows.counted())
            return h + f.reshape(m.shape), counts


class Lfm2MoeForCausalLM(_Params):
    """Embedding, the decoder layers, the final norm; the head is the
    embedding."""

    def __init__(self, config):
        super().__init__()
        self.config = cfg = config
        V, H = cfg.vocab_size, cfg.hidden_size
        self._declare(embed=((V, H), cfg.initializer_range),
                      embedding_norm=((H,), 'ones'))
        kinds = cfg.layer_types
        self.layers = nn.LayerList([
            Lfm2DecoderLayer(cfg, l, kinds[:l].count(CONV))
            for l in range(cfg.num_layers)])
        self.state_layers = kinds.count(CONV)
        self._sparse = [l for l, layer in enumerate(self.layers)
                        if layer.sparse]
        self.reset_parameters()

    def reset_parameters(self):
        """Matrices N(0, initializer_range), the taps U(+-K^-1/2), norm
        weights 1, the expert bias N(0, 0.01) in float32."""
        fill_parameters(self, ('expert_bias',))

    # -- the serving-model protocol (serving/protocol.py) -------------------
    mp_degree = 1
    # forward_paged is written for the plain route alone: the engine
    # refuses the fused window, the verify step, the host tier, int8 and
    # mp for a model with recurrent state anyway
    paged_routes = ('plain',)

    def kv_cache_spec(self):
        cfg = self.config
        return [KVLayerSpec(cfg.num_kv_heads, cfg.head_dim, None)
                for layer in self.layers if layer.kind == ATTN]

    def _tail(self):
        cfg = self.config
        return (cfg.conv_L_cache - 1, cfg.hidden_size), \
            jnp.dtype(cfg.dtype)

    def state_spec(self):
        """Per conv layer the tail: its last K-1 inputs v, in the dtype
        the served path computes them in."""
        return [self._tail()] * self.state_layers

    def page_state_spec(self):
        """The same tails a page, every conv layer in one plane."""
        shape, dt = self._tail()
        return [((self.state_layers,) + shape, dt)]

    def lm_head_weight(self):
        return self.embed

    def moe_counters(self):
        return jnp.zeros((len(self._sparse), 3), jnp.int32)

    def forward_paged(self, input_ids, position_ids, kv_list, rows,
                      moe_counters=None, state=None):
        """The engine's forward over the paged pool, the tails and the
        page-state plane: -> (final-normed hidden Tensor [1, N, H], new
        kv list (one entry an attention layer), None or the experts'
        (rows [expert layers, counted groups, experts], counters), new
        state list)."""
        cfg = self.config
        pos = position_ids.data
        with jax.named_scope('embed'):
            h = self.embed.data[input_ids.data]
        planes = [tuple(t.data for t in kv) for kv in kv_list]
        page_size = planes[0][0].shape[1]
        state = [t.data for t in state]
        new_kv, counted = [], []
        for layer in self.layers:
            a = rms_norm(h, layer.operator_norm.data, cfg.norm_eps)
            with jax.named_scope(layer.kind):
                if layer.kind == CONV:
                    out, state = layer.op.forward_paged(a, state, rows,
                                                        page_size)
                else:
                    out, kv = layer.op.forward_paged(
                        a, pos, planes[len(new_kv)], rows)
                    new_kv.append(tuple(Tensor(x) for x in kv))
            h, counts = layer.ff_half(h + out, rows)
            if counts is not None:
                counted.append(counts)
        with jax.named_scope('final_norm'):
            h = rms_norm(h, self.embedding_norm.data, cfg.norm_eps)
        return Tensor(h), new_kv, moe_result(counted, moe_counters), \
            [Tensor(x) for x in state]
