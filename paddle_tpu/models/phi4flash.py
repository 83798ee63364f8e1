"""Phi-4-mini-flash (`model_type` `phi4flash`; the architecture is
SambaY, Ren et al. 2025, arXiv:2507.06607) — a decoder-hybrid-decoder
of four kinds of layer, for the paged-KV serving engine.

The layers, as equations (benchmarks/reference/phi4flash.py computes
the same in plain float32; what the public config.json does not carry
is listed under `assumed` in the benchmark's configuration file):

    LN(x; g, b) = (x - mean) / sqrt(var + eps) * g + b
    h0 = E[ids]                       no scale, no positions anywhere
    a = LN1_l(h);  h <- h + Mix_l(a)
    [g, u] = LN2_l(h) . W_gate_up;  h <- h + (silu(g) * u) . W_down
    logits = LN_f(h_L) . E^T                                       tied

  Mix_l, l even, l <= L/2 (the self-decoder's MAMBA layers):
    [x, z] = a . W_in;  x = silu(conv1d_causal(x) + b_conv)   4 taps
    [d, B, C] = x . W_x;  D = softplus(d . W_dt + b_dt);  A = -exp(A_log)
    s_t = exp(D_t * A) * s_{t-1} + (D_t * x_t) (x) B_t     [Dn, N] float32
    y_t = s_t . C_t + D_skip * x_t;  out = (y * silu(z)) . W_out
    the LAST of them (l = L/2) also hands m = y (before the z gate) to
    the cross-decoder
  Mix_l, l odd, l <= L/2 + 1 (DIFFERENTIAL attention; window for
      l < L/2 + 1, full for l = L/2 + 1, whose K/V the cross-decoder
      shares):
    [q, k, v] = a . W_qkv + b: q is P pairs x 2 sub-heads x D, k and v
    P_kv pairs x 2 x D; query pair p reads kv pair p // (P / P_kv).
    A_s = softmax(q_{p,s} . k_{g,s}^T / sqrt(D)), causal (and window),
    v_g = [v_{g,1} | v_{g,2}];
    o_p = RMSNorm_2D(A_1 v_g - lam A_2 v_g) * (1 - lam_init),
    lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init,
    lam_init = 0.8 - 0.6 exp(-0.3 l);  out = concat_p(o_p) . W_o + b_o
  Mix_l, l even, l > L/2 + 1 (GATED MEMORY UNIT):
    out = (silu(a . W_1) * m) . W_2, m layer L/2's memory of the token
  Mix_l, l odd, l > L/2 + 1 (CROSS attention): q = a . W_q + b only;
    keys and values are layer L/2 + 1's, read from ITS pages; the same
    differential form with this layer's own lam vectors and norm.

On the serving-model protocol (serving/protocol.py): `kv_cache_spec()`
has one entry per ATTENDING layer — the self-decoder's attention layers
own a plane each, the cross layers read the full layer's and never
write —, `state_spec()` declares per Mamba layer the recurrence's state
[N, Dn] float32 (channels on the lanes) and the convolution's tail
[(K-1) * Dn] in the model's dtype, `forward_paged` runs everything
token-wise ONCE over the dispatch's tokens and attention and the scan
group by group. `paged_routes` is the plain route alone.
"""
import math

import jax
import jax.numpy as jnp

from .. import nn
from ..core import rng
from ..core.tensor import Tensor
from ..ops import ssm
from ..ops.pallas import fused_norm as _fln
from ..ops.pallas import paged_attention as pa
from ..serving.protocol import KVLayerSpec

MAMBA, ATTN, GMU, CROSS = 'mamba', 'attention', 'gmu', 'cross_attention'
F32 = jnp.float32
# parameters kept in float32 whatever the model's dtype: the
# recurrence's own and the differential attention's lambda vectors
FLOAT32 = ('dt_bias', 'a_log', 'd_skip', 'lambda_q1', 'lambda_k1',
           'lambda_q2', 'lambda_k2')


class Phi4FlashConfig:
    def __init__(self, vocab_size=200064, hidden_size=2560, num_layers=32,
                 num_heads=40, num_kv_heads=20, intermediate_size=10240,
                 sliding_window=512, mb_per_layer=2, layer_norm_eps=1e-5,
                 d_state=16, d_conv=4, expand=2, dt_rank=None,
                 max_seq_len=262144, dtype='bfloat16',
                 initializer_range=0.02):
        if num_layers % 4 or mb_per_layer != 2:
            raise ValueError(
                'the layer pattern is written for mb_per_layer 2 and a '
                f'depth that splits into two even halves, got '
                f'{num_layers} layers, mb_per_layer {mb_per_layer}')
        if num_heads % num_kv_heads or num_kv_heads % 2:
            raise ValueError('query sub-heads divide over kv sub-heads, '
                             'which come in pairs')
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = hidden_size // num_heads
        self.intermediate_size = intermediate_size
        self.sliding_window = sliding_window
        self.layer_norm_eps = layer_norm_eps
        self.d_state = d_state
        self.d_conv = d_conv
        self.d_inner = expand * hidden_size
        self.dt_rank = dt_rank or math.ceil(hidden_size / 16)
        self.max_seq_len = max_seq_len
        self.dtype = dtype
        self.initializer_range = initializer_range
        # the self-decoder is layers 0 .. L/2 + 1: Mamba and attention
        # in turn, the last attention (L/2 + 1) full; the cross-decoder
        # the rest: gated memory units and cross attention in turn
        self.memory_layer = num_layers // 2
        self.shared_kv_layer = self.memory_layer + 1
        self.layer_kinds = [
            (MAMBA if l % 2 == 0 else ATTN) if l <= self.shared_kv_layer
            else (GMU if l % 2 == 0 else CROSS)
            for l in range(num_layers)]

    def lambda_init(self, layer):
        return 0.8 - 0.6 * math.exp(-0.3 * layer)


def layer_norm(x, g, b, eps):
    """LayerNorm over the last axis: the fused kernel (GPT's, at this
    width) on the TPU, its reference's op order elsewhere."""
    if _fln.use_fused(supported=g.dtype == x.dtype == b.dtype):
        return _fln.fused_layer_norm(x, g, b, eps)
    xf = x.astype(F32)
    mean = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean((xf - mean) ** 2, -1, keepdims=True)
    return ((xf - mean) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * g + b


def _dot(x, w):
    return jnp.dot(x, w, preferred_element_type=F32).astype(x.dtype)


def _silu(x):
    return x * jax.nn.sigmoid(x)


class _Params(nn.Layer):
    """A layer whose parameters are named (shape, initialiser), filled
    by the model's one initialiser. An initialiser is a std (N(0,
    std)), or one of 'ones', 'zeros', 'a_log', 'dt_bias'."""

    def _declare(self, **specs):
        for name, (shape, init) in specs.items():
            p = Tensor(jnp.zeros((), F32), stop_gradient=False)
            p.persistable = True
            p.init_shape, p.init = tuple(shape), init
            setattr(self, name, p)


class MambaMixer(_Params):
    """Selective state-space layer (Mamba-1). `index` names its pair of
    arrays in the model's state list."""

    def __init__(self, cfg, index, hands_memory):
        super().__init__()
        self.cfg, self.index, self.hands_memory = cfg, index, hands_memory
        H, dn, N, R = cfg.hidden_size, cfg.d_inner, cfg.d_state, cfg.dt_rank
        std = cfg.initializer_range
        self._declare(
            in_proj=((H, 2 * dn), std), conv_w=((cfg.d_conv, dn), 'conv'),
            conv_b=((dn,), 'zeros'), x_proj=((dn, R + 2 * N), std),
            dt_proj=((R, dn), R ** -0.5), dt_bias=((dn,), 'dt_bias'),
            a_log=((dn, N), 'a_log'), d_skip=((dn,), 'ones'),
            out_proj=((dn, H), std))

    def forward_paged(self, a, state, rows):
        """a [1, N, H] -> (out [1, N, H], memory [1, N, Dn] or None,
        state): the projections once over the tokens, the convolution
        and the scan group by group on each row's own slot."""
        cfg = self.cfg
        dn, N, R = cfg.d_inner, cfg.d_state, cfg.dt_rank
        xz = _dot(a, self.in_proj.data)
        x, z = xz[..., :dn], xz[..., dn:]
        s, tails = state[2 * self.index], state[2 * self.index + 1]
        A = -jnp.exp(self.a_log.data.astype(F32)).T             # [N, Dn]
        fresh = rows.fresh()
        ys = []
        for at, _, (xg,) in rows.groups(x):
            slots, q_lens = rows.slots[at], rows.q_lens[at]
            with jax.named_scope('conv'):
                xc, tails = ssm.causal_conv(
                    xg, tails, self.conv_w.data, self.conv_b.data, slots,
                    q_lens, fresh[at])
            dbc = jnp.dot(xc.astype(a.dtype), self.x_proj.data,
                          preferred_element_type=F32)
            dt = jax.nn.softplus(
                jnp.dot(dbc[..., :R].astype(a.dtype), self.dt_proj.data,
                        preferred_element_type=F32)
                + self.dt_bias.data.astype(F32))
            with jax.named_scope('scan'):
                y, s = ssm.selective_scan(
                    xc, dt, dbc[..., R:R + N], dbc[..., R + N:], A,
                    self.d_skip.data, s, slots, q_lens, fresh[at])
            ys.append(y)
        y = rows.join(ys)                                       # float32
        out = _dot((y * _silu(z.astype(F32))).astype(a.dtype),
                   self.out_proj.data)
        state = list(state)
        state[2 * self.index], state[2 * self.index + 1] = s, tails
        return out, (y.astype(a.dtype) if self.hands_memory else None), state


class DiffAttention(_Params):
    """Differential attention over the paged pool: a layer that owns
    its plane (`cross` False: q, k and v) or reads the shared one
    (`cross` True: q alone)."""

    def __init__(self, cfg, layer, cross):
        super().__init__()
        self.cfg, self.cross = cfg, cross
        self.window = cfg.sliding_window \
            if not cross and layer < cfg.shared_kv_layer else None
        self.lambda_init = cfg.lambda_init(layer)
        H, D = cfg.hidden_size, cfg.head_dim
        q, kv = cfg.num_heads * D, cfg.num_kv_heads * D
        std = cfg.initializer_range
        width = q if cross else q + 2 * kv
        self._declare(
            qkv_proj=((H, width), std), qkv_bias=((width,), std),
            o_proj=((q, H), std), o_bias=((H,), std),
            lambda_q1=((D,), 0.1), lambda_k1=((D,), 0.1),
            lambda_q2=((D,), 0.1), lambda_k2=((D,), 0.1),
            subln=((2 * D,), 'ones'))

    def _lambda(self):
        dot = lambda a, b: jnp.sum(a.data.astype(F32) * b.data.astype(F32))
        return jnp.exp(dot(self.lambda_q1, self.lambda_k1)) \
            - jnp.exp(dot(self.lambda_q2, self.lambda_k2)) + self.lambda_init

    def _combine(self, ctx):
        """ctx [1, N, pairs * 2 * 2D] (A_1 v_g, then A_2 v_g, a pair)
        -> [1, N, pairs * 2D]: the difference, its norm, the scale."""
        cfg = self.cfg
        D2 = 2 * cfg.head_dim
        c = ctx.astype(F32).reshape(ctx.shape[:2] + (-1, 2, D2))
        o = c[..., 0, :] - self._lambda() * c[..., 1, :]
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                              + cfg.layer_norm_eps)
        o = o * self.subln.data.astype(F32) * (1.0 - self.lambda_init)
        return o.reshape(ctx.shape[:2] + (-1,)).astype(ctx.dtype)

    def forward_paged(self, a, kv, rows):
        """a [1, N, H]; kv the plane this layer writes (own) or only
        reads (cross) -> (out [1, N, H], kv)."""
        cfg = self.cfg
        q_w = cfg.num_heads * cfg.head_dim
        kv_w = cfg.num_kv_heads * cfg.head_dim
        qkv = _dot(a, self.qkv_proj.data) + self.qkv_bias.data

        def write(pool, k, v, page_tables, seq_lens, q_lens):
            return pa.write_kv_pages(*pool, k, v, page_tables, seq_lens,
                                     q_lens)

        def read(pool, q, page_tables, seq_lens, q_lens):
            return pa.ragged_paged_attention(
                q, *pool, page_tables, seq_lens, q_lens,
                num_heads=cfg.num_heads, head_dim=cfg.head_dim,
                num_kv_heads=cfg.num_kv_heads, window=self.window, diff=2)
        if self.cross:
            ctx, kv = rows.attend(None, read, kv, qkv)
        else:
            ctx, kv = rows.attend(
                write, read, kv, qkv[..., :q_w],
                qkv[..., q_w:q_w + kv_w], qkv[..., q_w + kv_w:])
        return _dot(self._combine(ctx), self.o_proj.data) \
            + self.o_bias.data, kv


class GatedMemoryUnit(_Params):
    def __init__(self, cfg):
        super().__init__()
        H, dn, std = cfg.hidden_size, cfg.d_inner, cfg.initializer_range
        self._declare(in_proj=((H, dn), std), out_proj=((dn, H), std))

    def forward(self, a, memory):
        gate = _silu(jnp.dot(a, self.in_proj.data,
                             preferred_element_type=F32))
        return _dot((gate * memory.astype(F32)).astype(a.dtype),
                    self.out_proj.data)


class Phi4FlashMLP(_Params):
    def __init__(self, cfg):
        super().__init__()
        H, F, std = cfg.hidden_size, cfg.intermediate_size, \
            cfg.initializer_range
        self._declare(gate_up=((H, 2 * F), std), down=((F, H), std))

    def forward(self, m):
        gu = jnp.dot(m, self.gate_up.data, preferred_element_type=F32)
        F = gu.shape[-1] // 2
        return _dot((_silu(gu[..., :F]) * gu[..., F:]).astype(m.dtype),
                    self.down.data)


class Phi4FlashDecoderLayer(_Params):
    def __init__(self, cfg, layer, mamba_index):
        super().__init__()
        self.eps = cfg.layer_norm_eps
        self.kind = kind = cfg.layer_kinds[layer]
        H = cfg.hidden_size
        self._declare(norm1_w=((H,), 'ones'), norm1_b=((H,), 'zeros'),
                      norm2_w=((H,), 'ones'), norm2_b=((H,), 'zeros'))
        if kind == MAMBA:
            self.mixer = MambaMixer(cfg, mamba_index,
                                    layer == cfg.memory_layer)
        elif kind == GMU:
            self.mixer = GatedMemoryUnit(cfg)
        else:
            self.mixer = DiffAttention(cfg, layer, kind == CROSS)
        self.mlp = Phi4FlashMLP(cfg)

    def norm1(self, h):
        return layer_norm(h, self.norm1_w.data, self.norm1_b.data, self.eps)

    def mlp_half(self, h):
        with jax.named_scope('mlp'):
            return h + self.mlp(layer_norm(
                h, self.norm2_w.data, self.norm2_b.data, self.eps))


def _fill(key, specs, dtype_of):
    """Every parameter of the model from one key, by its initialiser
    (`_Params._declare`). The draws use the `rbg` generator (the chip's
    own bit generator): gigabytes of normals take seconds."""
    key = jax.random.wrap_key_data(
        jnp.tile(jax.random.key_data(key), 2), impl='rbg')
    out = {}
    for i, name in enumerate(sorted(specs)):
        shape, init = specs[name]
        k, dt = jax.random.fold_in(key, i), dtype_of[name]
        if init == 'ones':
            out[name] = jnp.ones(shape, dt)
        elif init == 'zeros':
            out[name] = jnp.zeros(shape, dt)
        elif init == 'conv':        # U(-1/sqrt(K), 1/sqrt(K)), K taps
            bound = shape[0] ** -0.5
            out[name] = jax.random.uniform(k, shape, F32, -bound, bound) \
                .astype(dt)
        elif init == 'a_log':       # A = -(1 .. N) in every channel
            out[name] = jnp.broadcast_to(jnp.log(jnp.arange(
                1, shape[1] + 1, dtype=F32)), shape).astype(dt)
        elif init == 'dt_bias':     # softplus^-1 of a step in [1e-3, 1e-1]
            step = jnp.exp(jax.random.uniform(k, shape, F32)
                           * (math.log(1e-1) - math.log(1e-3))
                           + math.log(1e-3))
            out[name] = (step + jnp.log(-jnp.expm1(-step))).astype(dt)
        else:
            out[name] = (jax.random.normal(k, shape, F32) * init).astype(dt)
    return out


class Phi4FlashForCausalLM(_Params):
    """Embedding, the 32 layers, the final norm; the head is the
    embedding."""

    def __init__(self, config):
        super().__init__()
        self.config = cfg = config
        V, H = cfg.vocab_size, cfg.hidden_size
        self._declare(embed=((V, H), cfg.initializer_range),
                      final_norm_w=((H,), 'ones'),
                      final_norm_b=((H,), 'zeros'))
        kinds = cfg.layer_kinds
        self.layers = nn.LayerList([
            Phi4FlashDecoderLayer(cfg, l, kinds[:l].count(MAMBA))
            for l in range(cfg.num_layers)])
        # the attending layers in order, and of them the plane owners
        self._attending = [l for l, k in enumerate(kinds)
                           if k in (ATTN, CROSS)]
        self._owners = [l for l in self._attending if kinds[l] == ATTN]
        self.state_layers = kinds.count(MAMBA)
        self.reset_parameters()

    def reset_parameters(self):
        """One jitted initialiser from the global generator's next key."""
        cfg = self.config
        named = dict(self.named_parameters())
        specs = {n: (p.init_shape, p.init) for n, p in named.items()}
        dtype_of = {n: F32 if n.rsplit('.', 1)[-1] in FLOAT32
                    else cfg.dtype for n in specs}
        filled = jax.jit(lambda key: _fill(key, specs, dtype_of))(
            rng.next_key())
        for n, p in named.items():
            p._data = filled[n]

    # -- the serving-model protocol (serving/protocol.py) -------------------
    mp_degree = 1
    # forward_paged is written for the plain route alone; the engine
    # refuses the others for a model with recurrent state anyway
    paged_routes = ('plain',)

    def kv_cache_spec(self):
        cfg = self.config
        shared = self._attending.index(cfg.shared_kv_layer)
        return [KVLayerSpec(
            cfg.num_kv_heads, cfg.head_dim, self.layers[l].mixer.window,
            shared if cfg.layer_kinds[l] == CROSS else None)
            for l in self._attending]

    def state_spec(self):
        """Per Mamba layer: the recurrence's state [N, Dn] float32
        (channels on the lanes) and the convolution's last K-1 inputs,
        flat, in the model's dtype."""
        cfg = self.config
        return [spec for _ in range(self.state_layers) for spec in (
            ((cfg.d_state, cfg.d_inner), F32),
            (((cfg.d_conv - 1) * cfg.d_inner,), cfg.dtype))]

    def lm_head_weight(self):
        return self.embed

    def moe_counters(self):
        return None

    def forward_paged(self, input_ids, position_ids, kv_list, rows,
                      moe_counters=None, state=None):
        """The engine's forward over the paged pool and the recurrent
        state: -> (final-normed hidden Tensor [1, N, H], new kv list
        (one entry a plane owner), None, new state list)."""
        cfg = self.config
        del position_ids                    # no positions anywhere
        with jax.named_scope('embed'):
            h = self.embed.data[input_ids.data]
        planes = {l: tuple(t.data for t in kv)
                  for l, kv in zip(self._owners, kv_list)}
        state = [t.data for t in state]
        memory = None
        for l, layer in enumerate(self.layers):
            a = layer.norm1(h)
            with jax.named_scope(layer.kind):
                if layer.kind == MAMBA:
                    out, m, state = layer.mixer.forward_paged(a, state, rows)
                    memory = m if m is not None else memory
                elif layer.kind == GMU:
                    out = layer.mixer(a, memory)
                else:
                    owner = cfg.shared_kv_layer \
                        if layer.kind == CROSS else l
                    out, planes[owner] = layer.mixer.forward_paged(
                        a, planes[owner], rows)
            h = layer.mlp_half(h + out)
        with jax.named_scope('final_norm'):
            h = layer_norm(h, self.final_norm_w.data,
                           self.final_norm_b.data, cfg.layer_norm_eps)
        new_kv = [tuple(Tensor(a) for a in planes[l]) for l in self._owners]
        return Tensor(h), new_kv, None, [Tensor(a) for a in state]
