"""AFMoE — a decoder of sparse experts with grouped-query attention and
window and full layers in one model (`model_type` `afmoe`, the block of
arcee-ai's Trinity family), for the paged-KV serving engine.

The layer, as equations (benchmarks/reference/afmoe.py computes the
same in plain float32; what the public config.json does not carry is
marked + and listed under `assumed` in the benchmark's configuration
file):

    rms(x; g)  = x / sqrt(mean(x^2) + eps) * g              in float32
    h0         = E[ids] * sqrt(H)                            (mup_enabled) +
    a = rms(h; g1);  h <- h + rms(Attn_l(a); g2)             sandwich +
    m = rms(h; g3);  h <- h + rms(F_l(m); g4)
    Attn_l(a): q = a.Wq -> [Hq, D], k = a.Wk, v = a.Wv -> [Hk, D];
               q <- rms(q; gq), k <- rms(k; gk) over D +;
               rotary (half-split, theta, position = token index) on
               q, k in `sliding_attention` layers ONLY +; query head j
               reads kv head j // (Hq / Hk), scale D^-1/2, causal, and
               in `sliding_attention` layers only keys with
               0 <= p_q - p_k < window;
               o = softmax(q k^T) v * sigmoid(a.Wg) +;  Attn = o.Wo
    F_l, l <  num_dense_layers: SwiGLU at `intermediate_size`
    F_l, else: shared SwiGLU + sum over the token's top-k experts of
               w_e * SwiGLU_e, all at `moe_intermediate_size`
               (ops/moe.py: sigmoid scores, the balancing bias in the
               choice only +, route_norm, route_scale; no capacity)
    logits     = rms(h_L; gf) . W_head^T                     untied

No biases anywhere. Parameters are created on the device by ONE jitted
seeded initialiser (the experts alone are 3 stacked [E, H, F] arrays a
layer), in `dtype`.

The model implements the serving-model protocol of
paddle_tpu/serving/protocol.py: `kv_cache_spec()` (kv heads, head_dim
and window per layer), `forward_paged` (the plain route alone:
`paged_routes`), `lm_head_weight()`, `moe_counters()` — an int32
[expert layers, experts held + 3] array that rides the step's one host
fetch: rows per expert of the last call (live rows only: a chunk's or a
batch's padding is routed nowhere), then three counts that only ever
grow (experts touched, rows, calls; they wrap, the engine takes
differences).
"""
import math

import jax
import jax.numpy as jnp

from .. import nn
from ..core import rng
from ..core.tensor import Tensor
from ..ops import moe
from ..ops.pallas import paged_attention as pa
from ..serving.protocol import KVLayerSpec

SLIDING, FULL = 'sliding_attention', 'full_attention'
F32 = jnp.float32


class AfmoeConfig:
    def __init__(self, vocab_size=200192, hidden_size=2048, num_layers=32,
                 num_dense_layers=2, num_heads=32, num_kv_heads=4,
                 head_dim=128, intermediate_size=6144,
                 moe_intermediate_size=1024, num_experts=128,
                 num_experts_per_tok=8, num_shared_experts=1,
                 sliding_window=2048, layer_types=None,
                 global_attn_every_n_layers=4, rms_norm_eps=1e-5,
                 rope_theta=10000.0, route_scale=2.826, route_norm=True,
                 mup_enabled=True, max_seq_len=131072, experts_held=None,
                 dtype='bfloat16', initializer_range=0.02):
        if layer_types is None:
            n = global_attn_every_n_layers
            layer_types = [FULL if (i + 1) % n == 0 else SLIDING
                           for i in range(num_layers)]
        if len(layer_types) != num_layers or \
                set(layer_types) - {SLIDING, FULL}:
            raise ValueError(f'layer_types {layer_types} for '
                             f'{num_layers} layers')
        if num_heads % num_kv_heads or num_shared_experts != 1:
            raise ValueError('query heads must divide over kv heads, and '
                             'one shared expert is what is written')
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_dense_layers = num_dense_layers
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.num_shared_experts = num_shared_experts
        self.sliding_window = sliding_window
        self.layer_types = list(layer_types)
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.route_scale = route_scale
        self.route_norm = route_norm
        self.mup_enabled = mup_enabled
        self.max_seq_len = max_seq_len
        # (first, count) of the router's experts whose weights live
        # here; None: all of them
        self.experts_held = tuple(experts_held) if experts_held \
            else (0, num_experts)
        self.dtype = dtype
        self.initializer_range = initializer_range


def rms_norm(x, g, eps):
    xf = x.astype(F32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * g.astype(F32)).astype(x.dtype)


def rotary(x, pos, theta):
    """x [B, T, heads, D], pos int [B, T]: half-split rotate_half."""
    D = x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=F32) / D)
    ang = pos.astype(F32)[..., None] * inv                  # [B, T, D/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, :, None, :]
    xf = x.astype(F32)
    x1, x2 = xf[..., :D // 2], xf[..., D // 2:]
    return (xf * cos + jnp.concatenate([-x2, x1], -1) * sin) \
        .astype(x.dtype)


def _dot(x, w):
    return jnp.dot(x, w, preferred_element_type=F32).astype(x.dtype)


class _Params(nn.Layer):
    """A layer whose parameters are named shapes, filled by the
    model's one initialiser."""

    def _declare(self, **shapes):
        for name, shape in shapes.items():
            p = Tensor(jnp.zeros((), F32), stop_gradient=False)
            p.persistable = True
            p.init_shape = tuple(shape)
            setattr(self, name, p)


class AfmoeAttention(_Params):
    def __init__(self, cfg, layer_type):
        super().__init__()
        H, D = cfg.hidden_size, cfg.head_dim
        self.cfg = cfg
        self.window = cfg.sliding_window if layer_type == SLIDING else None
        self._declare(q_proj=(H, cfg.num_heads * D),
                      k_proj=(H, cfg.num_kv_heads * D),
                      v_proj=(H, cfg.num_kv_heads * D),
                      gate_proj=(H, cfg.num_heads * D),
                      o_proj=(cfg.num_heads * D, H),
                      q_norm=(D,), k_norm=(D,))

    def _qkv(self, a, pos):
        """a [B, T, H] -> q [B, T, Hq*D], k, v [B, T, Hk*D], the q/k
        norms and (window layers) rotary applied."""
        cfg = self.cfg
        B, T, _ = a.shape
        D = cfg.head_dim
        q = _dot(a, self.q_proj.data).reshape(B, T, cfg.num_heads, D)
        k = _dot(a, self.k_proj.data).reshape(B, T, cfg.num_kv_heads, D)
        v = _dot(a, self.v_proj.data)
        q = rms_norm(q, self.q_norm.data, cfg.rms_norm_eps)
        k = rms_norm(k, self.k_norm.data, cfg.rms_norm_eps)
        if self.window is not None:     # full layers carry no positions
            q = rotary(q, pos, cfg.rope_theta)
            k = rotary(k, pos, cfg.rope_theta)
        return q.reshape(B, T, -1), k.reshape(B, T, -1), v

    def _out(self, a, ctx):
        gate = jax.nn.sigmoid(_dot(a, self.gate_proj.data).astype(F32))
        return _dot((ctx.astype(F32) * gate).astype(a.dtype),
                    self.o_proj.data)

    def forward(self, a, pos):
        """Whole sequences, no cache: a [B, L, H]."""
        cfg = self.cfg
        B, L, _ = a.shape
        D, G = cfg.head_dim, cfg.num_heads // cfg.num_kv_heads
        q, k, v = self._qkv(a, pos)
        q = q.reshape(B, L, cfg.num_kv_heads, G, D).astype(F32)
        k = k.reshape(B, L, cfg.num_kv_heads, D).astype(F32)
        v = v.reshape(B, L, cfg.num_kv_heads, D).astype(F32)
        s = jnp.einsum('bqhgd,bkhd->bhgqk', q, k) / math.sqrt(D)
        dist = pos[:, :, None] - pos[:, None, :]            # p_q - p_k
        ok = dist >= 0
        if self.window is not None:
            ok = ok & (dist < self.window)
        s = jnp.where(ok[:, None, None], s, -jnp.inf)
        ctx = jnp.einsum('bhgqk,bkhd->bqhgd', jax.nn.softmax(s, -1), v)
        return self._out(a, ctx.reshape(B, L, -1).astype(a.dtype))

    def forward_paged(self, a, pos, kv, rows):
        """a [1, N, H] over the dispatch's tokens; `rows` (a
        serving/protocol.py RowGroups) takes the attention group by
        group, everything else runs once."""
        cfg = self.cfg
        if len(kv) != 2:
            raise NotImplementedError(
                'an int8 KV pool under kv groups and windows: the '
                'paged kernel\'s scale blocks do not take them')

        def write(pool, k, v, page_tables, seq_lens, q_lens):
            return pa.write_kv_pages(*pool, k, v, page_tables, seq_lens,
                                     q_lens)

        def read(pool, q, page_tables, seq_lens, q_lens):
            return pa.ragged_paged_attention(
                q, *pool, page_tables, seq_lens, q_lens,
                num_heads=cfg.num_heads, head_dim=cfg.head_dim,
                num_kv_heads=cfg.num_kv_heads, window=self.window)
        ctx, kv = rows.attend(write, read, kv, *self._qkv(a, pos))
        return self._out(a, ctx), kv


class AfmoeMLP(_Params):
    def __init__(self, hidden, width):
        super().__init__()
        self._declare(w1=(hidden, width), w3=(hidden, width),
                      w2=(width, hidden))

    def forward(self, m):
        return moe.swiglu(m, self.w1.data, self.w3.data, self.w2.data)


class AfmoeExperts(_Params):
    def __init__(self, count, hidden, width):
        super().__init__()
        self._declare(w1=(count, hidden, width), w3=(count, hidden, width),
                      w2=(count, width, hidden))


class AfmoeSparseMLP(_Params):
    """Router, the experts held here and the shared expert."""

    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        H, F = cfg.hidden_size, cfg.moe_intermediate_size
        self._declare(router=(H, cfg.num_experts))
        # the balancing bias: a buffer the training loop moves, not a
        # weight; it enters the choice of experts only
        self.register_buffer('expert_bias', Tensor(
            jnp.zeros((cfg.num_experts,), F32)))
        self.shared = AfmoeMLP(H, F)
        self.experts = AfmoeExperts(cfg.experts_held[1], H, F)

    def forward(self, m, live=None, counted=None):
        """m [N, H], live bool [N] or None (every row), counted (ids
        int32 [N], count) or None (all rows together) -> (out [N, H],
        rows int32 [count, experts held]: the live rows' pairs by the
        group they are counted with)."""
        cfg = self.cfg
        with jax.named_scope('router'):
            chosen, weights = moe.route(
                m, self.router.data, self.expert_bias.data,
                cfg.num_experts_per_tok, cfg.route_scale, cfg.route_norm)
        with jax.named_scope('experts'):
            ex = self.experts
            out, rows = moe.experts_swiglu(
                m, chosen, weights, ex.w1.data, ex.w3.data, ex.w2.data,
                experts_held=cfg.experts_held, live=live, counted=counted)
        with jax.named_scope('shared_expert'):
            out = out + self.shared(m)
        return out, rows


class AfmoeDecoderLayer(_Params):
    def __init__(self, cfg, index):
        super().__init__()
        self.eps = cfg.rms_norm_eps
        H = cfg.hidden_size
        self._declare(norm1=(H,), norm2=(H,), norm3=(H,), norm4=(H,))
        self.attn = AfmoeAttention(cfg, cfg.layer_types[index])
        self.sparse = index >= cfg.num_dense_layers
        self.mlp = AfmoeSparseMLP(cfg) if self.sparse \
            else AfmoeMLP(H, cfg.intermediate_size)

    def _join(self, h, attn_out, live=None, counted=None):
        """The attention's sandwich half, then the whole MLP half:
        -> (h, rows of the expert layer or None). `live` [B, T]: the
        positions that hold a token (None: all); `counted`: the group
        each position's routed rows count with (None: one)."""
        h = h + rms_norm(attn_out, self.norm2.data, self.eps)
        m = rms_norm(h, self.norm3.data, self.eps)
        rows = None
        with jax.named_scope('mlp'):
            if self.sparse:
                f, rows = self.mlp(
                    m.reshape(-1, m.shape[-1]),
                    None if live is None else live.reshape(-1), counted)
                f = f.reshape(m.shape)
            else:
                f = self.mlp(m)
        return h + rms_norm(f, self.norm4.data, self.eps), rows

    def forward(self, h, pos):
        with jax.named_scope('attn'):
            a = self.attn(rms_norm(h, self.norm1.data, self.eps), pos)
        return self._join(h, a)

    def forward_paged(self, h, pos, kv, rows):
        with jax.named_scope('attn'):
            a, new_kv = self.attn.forward_paged(
                rms_norm(h, self.norm1.data, self.eps), pos, kv, rows)
        h, counts = self._join(h, a, rows.live(), rows.counted())
        return h, new_kv, counts


def _fill(key, shapes, stds, dtype_of):
    """Every parameter of the model from one key: N(0, std), or ones
    where std is None. The draws use the `rbg` generator (the chip's
    own bit generator): 8 GB of normals take seconds, where threefry's
    take most of a minute."""
    key = jax.random.wrap_key_data(
        jnp.tile(jax.random.key_data(key), 2), impl='rbg')
    out = {}
    for i, name in enumerate(sorted(shapes)):
        std, dt = stds[name], dtype_of[name]
        if std is None:
            out[name] = jnp.ones(shapes[name], dt)
        else:
            out[name] = (jax.random.normal(
                jax.random.fold_in(key, i), shapes[name], F32)
                * std).astype(dt)
    return out


class AfmoeForCausalLM(_Params):
    """Embedding, the decoder layers, final norm and the untied head.
    A model of the same outline (models/axk1.py) names its own layer
    class and the vocabulary rows it holds."""
    decoder_layer = AfmoeDecoderLayer

    def __init__(self, config):
        super().__init__()
        self.config = cfg = config
        V, H = getattr(cfg, 'vocab_held', cfg.vocab_size), cfg.hidden_size
        self._declare(embed=(V, H), final_norm=(H,), lm_head=(V, H))
        self.layers = nn.LayerList(
            [self.decoder_layer(cfg, i) for i in range(cfg.num_layers)])
        self._sparse = [i for i, l in enumerate(self.layers) if l.sparse]
        self.reset_parameters()

    def reset_parameters(self):
        """One jitted initialiser from the global generator's next key:
        matrices N(0, initializer_range), norm weights 1, and the
        balancing bias N(0, 0.01) — a trained checkpoint's is not zero,
        and a zero one would hide a bias that leaks into the weights."""
        cfg = self.config
        named = dict(self.named_parameters())
        shapes = {n: p.init_shape for n, p in named.items()}
        stds = {n: None if len(s) == 1 else cfg.initializer_range
                for n, s in shapes.items()}
        bias = {n: b for n, b in self.named_buffers()}
        shapes.update({n: tuple(b.shape) for n, b in bias.items()})
        stds.update({n: 0.01 for n in bias})
        dtype_of = {n: F32 if n in bias else cfg.dtype for n in shapes}
        filled = jax.jit(lambda key: _fill(key, shapes, stds, dtype_of))(
            rng.next_key())
        for n, p in named.items():
            p._data = filled[n]
        for n, b in bias.items():
            b._data = filled[n]

    # -- the serving-model protocol (serving/protocol.py) -------------------
    mp_degree = 1
    # its forward_paged is written for the plain route alone: no fused
    # window, no verify step, no int8 pages or weights, no mp shards
    paged_routes = ('plain',)

    def kv_cache_spec(self):
        cfg = self.config
        return [KVLayerSpec(cfg.num_kv_heads, cfg.head_dim,
                            layer.attn.window) for layer in self.layers]

    def lm_head_weight(self):
        return self.lm_head

    def moe_counters(self):
        return jnp.zeros((len(self._sparse), 3), jnp.int32)

    def _embed(self, ids):
        h = self.embed.data[ids]
        if self.config.mup_enabled:
            h = (h.astype(F32) * math.sqrt(self.config.hidden_size)) \
                .astype(h.dtype)
        return h

    def forward_paged(self, input_ids, position_ids, kv_list, rows,
                      moe_counters=None):
        """The engine's forward over the paged pool: -> (final-normed
        hidden Tensor [1, N, H], new kv list, None or the experts'
        (rows [layers, counted groups, experts held], counters))."""
        pos = position_ids.data
        with jax.named_scope('embed'):
            h = self._embed(input_ids.data)
        new_kv, counted = [], []
        for layer, kv in zip(self.layers, kv_list):
            h, nkv, counts = layer.forward_paged(
                h, pos, tuple(t.data for t in kv), rows)
            new_kv.append(tuple(Tensor(a) for a in nkv))
            if counts is not None:
                counted.append(counts)
        with jax.named_scope('final_norm'):
            h = rms_norm(h, self.final_norm.data, self.config.rms_norm_eps)
        moe = None
        if moe_counters is not None:
            counts = jnp.stack(counted)             # [layers, groups, C]
            call = jnp.sum(counts, axis=1)          # what the kernel saw
            moe = (counts, moe_counters + jnp.stack(
                [jnp.sum(call > 0, -1), jnp.sum(call, -1),
                 jnp.ones_like(call[:, 0])], axis=-1))
        return Tensor(h), new_kv, moe

    def forward(self, input_ids):
        """[B, L] ids -> float32 logits [B, L, V], no cache (the tests'
        route against the reference)."""
        ids = input_ids.data if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        pos = jnp.broadcast_to(jnp.arange(ids.shape[1], dtype=jnp.int32),
                               ids.shape)
        h = self._embed(ids)
        for layer in self.layers:
            h, _ = layer(h, pos)
        h = rms_norm(h, self.final_norm.data, self.config.rms_norm_eps)
        return Tensor(jnp.einsum('blh,vh->blv', h, self.lm_head.data,
                                 preferred_element_type=F32))
