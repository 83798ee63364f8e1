"""fluid.layers remainder: legacy-signature wrappers over the modern op
surface (reference: python/paddle/fluid/layers/{nn,tensor,control_flow,
learning_rate_scheduler,detection}.py __all__ sheet).

Every name here is a THIN adapter: the compute lives in the shared op
layer (ops/, nn/functional), so these record into static programs and
run eagerly alike. LoD/SelectedRows-specific names are deliberately
absent (SURVEY N11 disposition: dense padded tensors + lengths).
"""
import numpy as np

from ..core.tensor import Tensor
from ..ops.common import as_tensor
from .. import nn as _nn
from ..nn import functional as F
from ..ops import math as M
from ..ops import manip as _manip
from ..ops import creation as _cr
from ..ops import contrib as _contrib
from ..ops import sequence as _seq


def rank(input):
    """fluid.layers.rank — the tensor's number of dimensions as a
    0-D int32 tensor."""
    import jax.numpy as jnp
    return Tensor(jnp.asarray(len(as_tensor(input).shape), jnp.int32))


def is_empty(x, name=None):
    """fluid.layers.is_empty (operators/is_empty_op.cc)."""
    import jax.numpy as jnp
    return Tensor(jnp.asarray(int(np.prod(as_tensor(x).shape)) == 0))


def reverse(x, axis):
    """fluid.layers.reverse (operators/reverse_op.cc) → flip."""
    if isinstance(axis, int):
        axis = [axis]
    return _manip.flip(x, axis)


def crop_tensor(x, shape=None, offsets=None, name=None):
    """fluid.layers.crop_tensor (operators/crop_tensor_op.cc)."""
    return M.crop(x, shape=shape, offsets=offsets)


def pad2d(input, paddings=(0, 0, 0, 0), mode='constant', pad_value=0.0,
          data_format='NCHW', name=None):
    """fluid.layers.pad2d (operators/pad2d_op.cc): paddings
    [top, bottom, left, right] on the spatial dims."""
    t, b, l, r = [int(p) for p in paddings]
    if data_format == 'NCHW':
        pad = [0, 0, 0, 0, t, b, l, r]
    else:
        pad = [0, 0, t, b, l, r, 0, 0]
    mode_map = {'constant': 'constant', 'reflect': 'reflect',
                'edge': 'replicate'}
    return F.pad(input, pad, mode=mode_map[mode], value=pad_value)


def pad_constant_like(x, y, pad_value=0.0, name=None):
    """fluid.layers.pad_constant_like (operators/pad_constant_like_op.cc):
    pad y at the tail of every dim up to x's shape."""
    xs, ys = as_tensor(x).shape, as_tensor(y).shape
    pad = []
    for dx, dy in zip(xs, ys):
        pad += [0, int(dx) - int(dy)]
    return F.pad(y, pad, mode='constant', value=pad_value)


def adaptive_pool2d(input, pool_size, pool_type='max', require_index=False,
                    name=None):
    """fluid.layers.adaptive_pool2d."""
    if pool_type == 'max':
        if require_index:
            return F.adaptive_max_pool2d(input, pool_size,
                                         return_mask=True)
        return F.adaptive_max_pool2d(input, pool_size)
    return F.adaptive_avg_pool2d(input, pool_size)


def adaptive_pool3d(input, pool_size, pool_type='max', require_index=False,
                    name=None):
    """fluid.layers.adaptive_pool3d — [N, C, D, H, W]: fold depth into
    the batch, reuse the 2-D kernel per depth slice, then pool depth."""
    x = as_tensor(input)
    if isinstance(pool_size, int):
        pool_size = [pool_size] * 3
    N, C, D, H, W = [int(d) for d in x.shape]
    od, oh, ow = [int(p) for p in pool_size]
    xf = _manip.reshape(x, [N * C, D, H, W])
    # adaptive over (H, W) per depth slice
    xf = _manip.reshape(xf, [N * C * D, 1, H, W])
    hw = (F.adaptive_max_pool2d(xf, [oh, ow]) if pool_type == 'max'
          else F.adaptive_avg_pool2d(xf, [oh, ow]))
    hw = _manip.reshape(hw, [N * C, D, oh * ow])
    hw = _manip.transpose(hw, [0, 2, 1])
    hw = _manip.reshape(hw, [N * C * oh * ow, 1, D, 1])
    d = (F.adaptive_max_pool2d(hw, [od, 1]) if pool_type == 'max'
         else F.adaptive_avg_pool2d(hw, [od, 1]))
    d = _manip.reshape(d, [N * C, oh, ow, od])
    d = _manip.transpose(d, [0, 3, 1, 2])
    return _manip.reshape(d, [N, C, od, oh, ow])


def pool3d(input, pool_size=-1, pool_type='max', pool_stride=1,
           pool_padding=0, global_pooling=False, ceil_mode=False,
           exclusive=True, data_format='NCDHW', name=None):
    """fluid.layers.pool3d (operators/pool_op.cc 3-D path)."""
    x = as_tensor(input)
    if global_pooling:
        axes = [2, 3, 4] if data_format == 'NCDHW' else [1, 2, 3]
        return (M.max(x, axis=axes, keepdim=True) if pool_type == 'max'
                else M.mean(x, axis=axes, keepdim=True))
    if pool_type == 'max':
        return F.max_pool3d(x, pool_size, stride=pool_stride,
                            padding=pool_padding, ceil_mode=ceil_mode) \
            if hasattr(F, 'max_pool3d') else _pool3d_generic(
                x, pool_size, pool_stride, pool_padding, 'max',
                ceil_mode, exclusive)
    return _pool3d_generic(x, pool_size, pool_stride, pool_padding,
                           'avg', ceil_mode, exclusive)


def _pool3d_generic(x, ksize, stride, padding, kind, ceil_mode,
                    exclusive):
    """Delegates to the shared reduce_window pooling helper
    (ops/nn_ops.py _pool_nd) — one implementation for every N-D pool."""
    from ..ops.nn_ops import _pool_nd
    return _pool_nd(x, 3, ksize, stride, padding, kind, ceil_mode,
                    exclusive)


def lrn(input, n=5, k=1.0, alpha=1e-4, beta=0.75, name=None,
        data_format='NCHW'):
    """fluid.layers.lrn (operators/lrn_op.cc) → local_response_norm
    (this backend's impl already uses the fluid alpha*sum convention —
    no /n — so alpha passes straight through)."""
    return F.local_response_norm(input, size=n, alpha=alpha,
                                 beta=beta, k=k,
                                 data_format=data_format)


def grid_sampler(x, grid, name=None):
    """fluid.layers.grid_sampler → F.grid_sample."""
    return F.grid_sample(x, grid)


def warpctc(input, label, blank=0, norm_by_times=False,
            input_length=None, label_length=None):
    """fluid.layers.warpctc (operators/warpctc_op.cc) → F.ctc_loss.
    input [T, B, C] logits (or [B, T, C] with lengths, per the modern
    contract)."""
    return F.ctc_loss(input, label, input_length, label_length,
                      blank=blank, reduction='none')


def ctc_greedy_decoder(input, blank, input_length=None, padding_value=0,
                       name=None):
    """fluid.layers.ctc_greedy_decoder: argmax per step, collapse
    repeats, drop blanks (ctc_align)."""
    probs = as_tensor(input)
    ids = M.argmax(probs, axis=-1)
    out, lens = _contrib.ctc_align(ids, blank=blank,
                                   lengths=input_length,
                                   padding_value=padding_value)
    return out, lens


def unique_with_counts(x, dtype='int32'):
    """fluid.layers.unique_with_counts (operators/unique_with_counts_op
    .cc): returns (unique values, index map, counts)."""
    out, inverse, counts = _manip.unique(
        x, return_inverse=True, return_counts=True)
    return out, _manip.cast(inverse, dtype), _manip.cast(counts, dtype)


def uniform_random_batch_size_like(input, shape, dtype='float32',
                                   input_dim_idx=0, output_dim_idx=0,
                                   min=-1.0, max=1.0, seed=0):
    """fluid.layers.uniform_random_batch_size_like."""
    shape = list(shape)
    shape[output_dim_idx] = int(
        as_tensor(input).shape[input_dim_idx])
    return _cr.uniform(shape, dtype=dtype, min=min, max=max, seed=seed)


def gaussian_random_batch_size_like(input, shape, input_dim_idx=0,
                                    output_dim_idx=0, mean=0.0, std=1.0,
                                    seed=0, dtype='float32'):
    """fluid.layers.gaussian_random_batch_size_like."""
    shape = list(shape)
    shape[output_dim_idx] = int(
        as_tensor(input).shape[input_dim_idx])
    if seed:
        import jax
        import jax.numpy as jnp
        key = jax.random.key(int(seed))
        return Tensor(jax.random.normal(
            key, tuple(shape), jnp.dtype(dtype)) * std + mean)
    return _cr.gaussian(shape, mean=mean, std=std, dtype=dtype)


def inplace_abn(input, act=None, **bn_kwargs):
    """fluid.layers.inplace_abn (operators/inplace_abn_op.cc): fused
    BN + activation. XLA fuses these anyway and buffers are immutable,
    so this is batch_norm + act — same math, no aliasing."""
    out = F.batch_norm(input, **bn_kwargs) if bn_kwargs else \
        _nn.BatchNorm2D(int(as_tensor(input).shape[1]))(input)
    if act:
        out = getattr(F, act)(out)
    return out


def similarity_focus(input, axis, indexes, name=None):
    """similarity_focus_op.cc: build a focus mask — select slices along
    `axis` (1, 2, or 3 of the 4-D input) at `indexes`; in each selected
    slice mark the max position per row and per column; broadcast the
    union mask back over the selected axis."""
    import jax.numpy as jnp
    from ..core.autograd import run_op
    x = as_tensor(input)
    if axis not in (1, 2, 3):
        raise ValueError(f"similarity_focus axis must be 1, 2 or 3, "
                         f"got {axis}")
    dim = int(x.shape[axis])
    bad = [i for i in indexes if not 0 <= int(i) < dim]
    if bad:
        raise ValueError(f"similarity_focus indexes {bad} out of range "
                         f"for axis {axis} (size {dim})")

    def fn(a):
        # move the selected axis to position 1; rows/cols are the two
        # remaining trailing dims
        perm = {1: (0, 1, 2, 3), 2: (0, 2, 1, 3), 3: (0, 3, 1, 2)}[axis]
        at = a.transpose(perm)
        N = at.shape[0]
        H, W = at.shape[2], at.shape[3]
        sel = at[:, jnp.asarray(indexes)]

        def one_image(img_sel):
            m = jnp.zeros((H, W), a.dtype)
            for k in range(len(indexes)):
                fm = img_sel[k]
                row_best = jnp.argmax(fm, axis=1)      # per row
                col_best = jnp.argmax(fm, axis=0)      # per col
                m = m.at[jnp.arange(H), row_best].set(1.0)
                m = m.at[col_best, jnp.arange(W)].set(1.0)
            return m
        masks = jnp.stack([one_image(sel[i]) for i in range(N)])
        full = jnp.broadcast_to(masks[:, None], at.shape)
        inv = tuple(np.argsort(perm))
        return full.transpose(inv)
    return run_op('similarity_focus', fn, [x])


# -- learning-rate decay bridge (fluid.layers.learning_rate_scheduler) --
# The fluid decay fns appended lr-computation ops to the startup
# program; under the one-jit Executor the schedule lives host-side in
# the optimizer, so each returns the MODERN scheduler object preloaded
# with the same formula (optimizer.set_lr_scheduler consumes it).

def noam_decay(d_model, warmup_steps, learning_rate=1.0):
    from ..optimizer import lr as _lr
    return _lr.NoamDecay(d_model, warmup_steps,
                         learning_rate=learning_rate)


def exponential_decay(learning_rate, decay_steps, decay_rate,
                      staircase=False):
    from ..optimizer import lr as _lr

    def fn(epoch):
        e = (epoch // decay_steps) if staircase else (epoch
                                                     / decay_steps)
        return decay_rate ** e
    return _lr.LambdaDecay(learning_rate, fn)


def natural_exp_decay(learning_rate, decay_steps, decay_rate,
                      staircase=False):
    from ..optimizer import lr as _lr

    def fn(epoch):
        e = (epoch // decay_steps) if staircase else (epoch
                                                     / decay_steps)
        return float(np.exp(-decay_rate * e))
    return _lr.LambdaDecay(learning_rate, fn)


def inverse_time_decay(learning_rate, decay_steps, decay_rate,
                       staircase=False):
    from ..optimizer import lr as _lr

    def fn(epoch):
        e = (epoch // decay_steps) if staircase else (epoch
                                                     / decay_steps)
        return 1.0 / (1.0 + decay_rate * e)
    return _lr.LambdaDecay(learning_rate, fn)


def polynomial_decay(learning_rate, decay_steps, end_learning_rate=1e-4,
                     power=1.0, cycle=False):
    from ..optimizer import lr as _lr
    return _lr.PolynomialDecay(learning_rate, decay_steps,
                               end_lr=end_learning_rate, power=power,
                               cycle=cycle)


def piecewise_decay(boundaries, values):
    from ..optimizer import lr as _lr
    return _lr.PiecewiseDecay(boundaries, values)


def cosine_decay(learning_rate, step_each_epoch, epochs):
    from ..optimizer import lr as _lr
    return _lr.CosineAnnealingDecay(learning_rate,
                                    T_max=step_each_epoch * epochs)


def linear_lr_warmup(learning_rate, warmup_steps, start_lr, end_lr):
    from ..optimizer import lr as _lr
    return _lr.LinearWarmup(learning_rate, warmup_steps, start_lr,
                            end_lr)


def rnn(cell, inputs, initial_states=None, sequence_length=None,
        time_major=False, is_reverse=False, **kwargs):
    """fluid.layers.rnn — functional driver over a cell (rnn.py:~440)."""
    runner = _nn.RNN(cell, is_reverse=is_reverse, time_major=time_major)
    return runner(inputs, initial_states, sequence_length)


def birnn(cell_fw, cell_bw, inputs, initial_states=None,
          sequence_length=None, time_major=False, **kwargs):
    """fluid.layers.birnn — bidirectional functional driver."""
    runner = _nn.BiRNN(cell_fw, cell_bw, time_major=time_major)
    return runner(inputs, initial_states, sequence_length)


# ---------------------------------------------------------------------------
# remaining fluid.layers tail (wave 3)
# ---------------------------------------------------------------------------

def _mode_param(shape, dtype='float32'):
    """Create a parameter in whichever mode is active: a Program
    parameter under enable_static, an eagerly-initialized Tensor
    otherwise (Xavier-uniform like _make_param's default)."""
    from ..core.autograd import STATIC_RECORD_HOOK
    if STATIC_RECORD_HOOK is not None:
        from .nn import _make_param
        return _make_param(list(shape), dtype)
    import jax
    from ..core import rng as rng_mod
    fan_in = int(np.prod(shape[:-1])) or 1
    fan_out = int(shape[-1])
    limit = float(np.sqrt(6.0 / (fan_in + fan_out)))
    key = rng_mod.next_key()
    import jax.numpy as jnp
    t = Tensor(jax.random.uniform(key, tuple(int(d) for d in shape),
                                  jnp.dtype(dtype), -limit, limit))
    t.stop_gradient = False
    return t


def conv3d_transpose(input, num_filters, output_size=None, filter_size=None,
                     padding=0, stride=1, dilation=1, groups=1,
                     param_attr=None, bias_attr=None, use_cudnn=True,
                     act=None, name=None, data_format='NCDHW'):
    """fluid.layers.conv3d_transpose — creates the IODHW weight/bias
    params and delegates to the shared functional kernel
    (ops/nn_ops.py conv3d_transpose, the single transpose-conv
    implementation)."""
    from ..ops.nn_ops import conv3d_transpose as _f_conv3dt
    x = as_tensor(input)
    cin = int(x.shape[1])
    if isinstance(filter_size, int):
        filter_size = [filter_size] * 3
    dt = str(x.dtype)
    w = _mode_param([cin, num_filters // groups] + list(filter_size), dt)
    b = None
    if bias_attr is not False:
        b = _mode_param([num_filters], dt)
    out = _f_conv3dt(x, w, b, stride=stride, padding=padding,
                     groups=groups, dilation=dilation,
                     output_size=output_size)
    if act:
        out = getattr(F, act)(out)
    return out


def _resize_nd(input, out_shape, scale, mode, align_corners,
               data_format):
    """1-D / 3-D separable linear interpolation with BOTH coordinate
    conventions: align_corners=True maps output i to i*(in-1)/(out-1)
    (the fluid default); False uses the half-pixel convention. Each
    spatial axis is one gather+lerp — XLA fuses the chain."""
    import jax.numpy as jnp
    from ..core.autograd import run_op
    x = as_tensor(input)
    nd = len(x.shape) - 2
    if out_shape is None:
        sf = scale if isinstance(scale, (list, tuple)) else [scale] * nd
        out_shape = [int(int(d) * s)
                     for d, s in zip(x.shape[2:], sf)]
    out_shape = [int(v) for v in out_shape]

    def fn(a):
        out = a
        for ax in range(nd):
            axis = 2 + ax
            n_in = out.shape[axis]
            n_out = out_shape[ax]
            if n_in == n_out:
                continue
            i = jnp.arange(n_out, dtype=a.dtype)
            if align_corners and n_out > 1:
                t = i * (n_in - 1) / (n_out - 1)
            else:
                t = jnp.clip((i + 0.5) * n_in / n_out - 0.5, 0,
                             n_in - 1)
            lo = jnp.floor(t).astype(jnp.int32)
            hi = jnp.clip(lo + 1, 0, n_in - 1)
            w = (t - lo).reshape((-1,) + (1,) * (out.ndim - axis - 1))
            lo_v = jnp.take(out, lo, axis=axis)
            hi_v = jnp.take(out, hi, axis=axis)
            out = lo_v * (1 - w) + hi_v * w
        return out
    return run_op('resize_nd', fn, [x])


def resize_linear(input, out_shape=None, scale=None, name=None,
                  align_corners=True, align_mode=1,
                  data_format='NCW'):
    """fluid.layers.resize_linear — 1-D linear interpolation
    [N, C, W]."""
    return _resize_nd(input, out_shape, scale, 'linear', align_corners,
                      data_format)


def resize_trilinear(input, out_shape=None, scale=None, name=None,
                     actual_shape=None, align_corners=True,
                     align_mode=1, data_format='NCDHW'):
    """fluid.layers.resize_trilinear — 3-D interpolation
    [N, C, D, H, W]."""
    return _resize_nd(input, out_shape, scale, 'trilinear',
                      align_corners, data_format)


def image_resize_short(input, out_short_len, resample='BILINEAR'):
    """fluid.layers.image_resize_short: scale so the SHORT side equals
    out_short_len, keeping aspect ratio."""
    x = as_tensor(input)
    h, w = int(x.shape[2]), int(x.shape[3])
    short, long_ = (h, w) if h < w else (w, h)
    ratio = out_short_len / float(short)
    oh, ow = int(round(h * ratio)), int(round(w * ratio))
    return F.interpolate(x, size=[oh, ow],
                         mode='bilinear' if resample == 'BILINEAR'
                         else 'nearest')


# -- fluid RNN-op wrappers (param-creating, over the modern cells) ----------

def gru_unit(input, hidden, size, param_attr=None, bias_attr=None,
             activation='tanh', gate_activation='sigmoid',
             origin_mode=False):
    """fluid.layers.gru_unit (operators/gru_unit_op.cc): one GRU step
    exposing the fluid op's full output triple —
    (updated_hidden [B, D], reset_hidden_pre [B, D] = r * h_prev,
    gate [B, 3D] = [u, r, c-hat] after activations). origin_mode picks
    h = u*h_prev + (1-u)*c vs h = (1-u)*h_prev + u*c."""
    import jax
    import jax.numpy as jnp
    from ..core.autograd import run_op
    hidden_dim = size // 3
    x = as_tensor(input)
    h = as_tensor(hidden, ref=x)
    dt = str(x.dtype)
    wi = _mode_param([int(x.shape[-1]), size], dt)
    wh = _mode_param([hidden_dim, size], dt)
    bias = _mode_param([size], dt)
    act = {'tanh': jnp.tanh, 'sigmoid': jax.nn.sigmoid,
           'relu': jax.nn.relu, 'identity': (lambda v: v)}[activation]
    gact = {'sigmoid': jax.nn.sigmoid, 'tanh': jnp.tanh,
            'relu': jax.nn.relu,
            'identity': (lambda v: v)}[gate_activation]

    def fn(xa, ha, wia, wha, ba):
        g = xa @ wia + ba
        hg = ha @ wha
        gu = gact(g[:, :hidden_dim] + hg[:, :hidden_dim])
        gr = gact(g[:, hidden_dim:2 * hidden_dim]
                  + hg[:, hidden_dim:2 * hidden_dim])
        rhp = gr * ha
        c = act(g[:, 2 * hidden_dim:]
                + rhp @ wha[:, 2 * hidden_dim:])
        if origin_mode:
            nh = gu * ha + (1 - gu) * c
        else:
            nh = (1 - gu) * ha + gu * c
        gate = jnp.concatenate([gu, gr, c], axis=-1)
        return nh, rhp, gate
    return run_op('gru_unit', fn, [x, h, wi, wh, bias])


def lstm_unit(x_t, hidden_t_prev, cell_t_prev, forget_bias=0.0,
              param_attr=None, bias_attr=None, name=None):
    """fluid.layers.lstm_unit (operators/lstm_unit_op.cc): one LSTM
    step. Returns (hidden_t, cell_t)."""
    from ..nn import LSTMCell
    cell = LSTMCell(int(as_tensor(x_t).shape[-1]),
                    int(as_tensor(hidden_t_prev).shape[-1]))
    _, (h, c) = cell(x_t, (hidden_t_prev, cell_t_prev))
    return h, c


def dynamic_lstm(input, size, h_0=None, c_0=None, param_attr=None,
                 bias_attr=None, use_peepholes=True, is_reverse=False,
                 gate_activation='sigmoid', cell_activation='tanh',
                 candidate_activation='tanh', dtype='float32',
                 name=None):
    """fluid.layers.dynamic_lstm (operators/lstm_op.cc): full-sequence
    LSTM over dense padded input [B, T, D]; `size` = 4 * hidden_dim.
    Returns (hidden_seq [B, T, H], cell_seq [B, T, H]) — BOTH sequences
    like the reference. Peepholes are not modeled (documented
    deviation: XLA fuses the plain gates)."""
    from ..nn import LSTMCell
    hidden = size // 4
    x = as_tensor(input)
    cell = LSTMCell(int(x.shape[-1]), hidden)
    B, T = int(x.shape[0]), int(x.shape[1])
    h = as_tensor(h_0) if h_0 is not None else \
        Tensor(np.zeros((B, hidden), np.float32))
    c = as_tensor(c_0) if c_0 is not None else \
        Tensor(np.zeros((B, hidden), np.float32))
    hs, cs = [], []
    order = range(T - 1, -1, -1) if is_reverse else range(T)
    from ..ops import manip as _mp
    for t in order:
        step = _mp.slice(x, [1], [t], [t + 1])
        step = _mp.reshape(step, [B, int(x.shape[-1])])
        _, (h, c) = cell(step, (h, c))
        hs.append(h)
        cs.append(c)
    if is_reverse:
        hs, cs = hs[::-1], cs[::-1]
    out_h = _mp.stack(hs, axis=1)
    out_c = _mp.stack(cs, axis=1)
    return out_h, out_c


def dynamic_lstmp(input, size, proj_size, **kwargs):
    """fluid.layers.dynamic_lstmp (operators/lstmp_op.cc): LSTM with a
    learned projection of the hidden state. Returns (projected_seq,
    cell_seq)."""
    out, cell_seq = dynamic_lstm(input, size, **kwargs)
    w = _mode_param([size // 4, proj_size], str(as_tensor(input).dtype))
    proj = M.matmul(out, w)
    return proj, cell_seq


def dynamic_gru(input, size, param_attr=None, bias_attr=None,
                is_reverse=False, gate_activation='sigmoid',
                candidate_activation='tanh', h_0=None,
                origin_mode=False):
    """fluid.layers.dynamic_gru (operators/gru_op.cc): full-sequence GRU
    over dense padded input. `size` is the hidden dim; fluid feeds
    pre-multiplied input [B, T, 3*size]; here the raw features work
    directly (the cell owns its input projection)."""
    from ..nn import GRU
    x = as_tensor(input)
    m = GRU(int(x.shape[-1]), size,
            direction='backward' if is_reverse else 'forward')
    init = None
    if h_0 is not None:
        init = Tensor(as_tensor(h_0).data[None])
    out, _ = m(input, init)
    return out


def lstm(input, init_h, init_c, max_len, hidden_size, num_layers,
         dropout_prob=0.0, is_bidirec=False, is_test=False, name=None,
         default_initializer=None, seed=-1):
    """fluid.layers.lstm (operators/cudnn_lstm_op.cc): multi-layer
    (optionally bidirectional) LSTM. Returns (out, last_h, last_c)."""
    from ..nn import LSTM
    x = as_tensor(input)
    m = LSTM(int(x.shape[-1]), hidden_size, num_layers=num_layers,
             direction='bidirect' if is_bidirec else 'forward',
             dropout=dropout_prob)
    out, (h, c) = m(input, (init_h, init_c))
    return out, h, c


def beam_search_decode(ids, parents, beam_size=None, end_id=None,
                       scores=None, name=None):
    """fluid.layers.beam_search_decode (beam_search_decode_op.cc):
    backtrace per-step beam selections into full sequences. Dense
    LoD-free contract: ids AND parent beam indices [T, B, W] (the
    reference packs parents into the ids LoD; here they are an explicit
    tensor — `nn.BeamSearchDecoder` and `ops.sequence.beam_search`
    already emit them). Returns (sequences [T, B, W], scores
    passthrough)."""
    from ..ops.contrib import gather_tree
    seqs = gather_tree(ids, parents)
    return seqs, scores


def chunk_eval(input, label, chunk_scheme, num_chunk_types,
               excluded_chunk_types=None, seq_length=None):
    """fluid.layers.chunk_eval (operators/chunk_eval_op.cc): chunk-level
    precision/recall/F1 for sequence labeling under IOB/IOE/IOBES/plain
    schemes. Host-side metric (python chunk extraction, like the
    reference's CPU-only kernel). Returns (precision, recall, f1,
    num_infer_chunks, num_label_chunks, num_correct_chunks)."""
    import jax.numpy as jnp
    inf = np.asarray(as_tensor(input).data).reshape(
        np.asarray(as_tensor(input).data).shape[0], -1)
    lab = np.asarray(as_tensor(label).data).reshape(inf.shape)
    excluded = set(excluded_chunk_types or [])
    if seq_length is not None:
        lens = np.asarray(as_tensor(seq_length).data).reshape(-1)
    else:
        lens = np.full(inf.shape[0], inf.shape[1])

    def extract(row, n):
        """tag id -> (type, pos); chunks per scheme."""
        chunks = []
        cur_type, cur_start = None, None
        for i in range(int(n)):
            t = int(row[i])
            if chunk_scheme == 'plain':
                typ = t
                if typ in excluded or typ < 0:
                    if cur_type is not None:
                        chunks.append((cur_type, cur_start, i - 1))
                        cur_type = None
                    continue
                if cur_type != typ:
                    if cur_type is not None:
                        chunks.append((cur_type, cur_start, i - 1))
                    cur_type, cur_start = typ, i
                continue
            n_pos = {'IOB': 2, 'IOE': 2, 'IOBES': 4}[chunk_scheme]
            if t == num_chunk_types * n_pos:      # the O tag
                if cur_type is not None:
                    chunks.append((cur_type, cur_start, i - 1))
                    cur_type = None
                continue
            typ, pos = t // n_pos, t % n_pos
            if typ in excluded:
                continue
            if chunk_scheme == 'IOB':
                begin = pos == 0
            elif chunk_scheme == 'IOE':
                begin = cur_type != typ
            else:                                  # IOBES
                begin = pos in (0, 3)              # B or S
            if begin or cur_type != typ:
                if cur_type is not None:
                    chunks.append((cur_type, cur_start, i - 1))
                cur_type, cur_start = typ, i
            if chunk_scheme == 'IOE' and pos == 1:  # E closes
                chunks.append((cur_type, cur_start, i))
                cur_type = None
            if chunk_scheme == 'IOBES' and pos in (2, 3):  # E/S close
                chunks.append((cur_type, cur_start, i))
                cur_type = None
        if cur_type is not None:
            chunks.append((cur_type, cur_start, int(n) - 1))
        return set(chunks)

    n_inf = n_lab = n_cor = 0
    for b in range(inf.shape[0]):
        ci = extract(inf[b], lens[b])
        cl = extract(lab[b], lens[b])
        n_inf += len(ci)
        n_lab += len(cl)
        n_cor += len(ci & cl)
    prec = n_cor / n_inf if n_inf else 0.0
    rec = n_cor / n_lab if n_lab else 0.0
    f1 = 2 * prec * rec / (prec + rec) if prec + rec else 0.0
    mk = lambda v, dt=jnp.float32: Tensor(jnp.asarray(v, dt))
    return (mk(prec), mk(rec), mk(f1), mk(n_inf, jnp.int32),
            mk(n_lab, jnp.int32), mk(n_cor, jnp.int32))


# -- legacy tiers that do not map to this backend (loud, documented) --------

def _lod_legacy(name_, hint):
    def raiser(*a, **k):
        raise NotImplementedError(
            f"fluid.layers.{name_} operates on LoD metadata, which this "
            f"framework drops by design (SURVEY N11: dense padded "
            f"tensors + lengths vectors). {hint}")
    raiser.__name__ = name_
    raiser.__doc__ = (f"fluid.layers.{name_} — LoD-era API, "
                      f"unsupported by design. {hint}")
    return raiser


lod_append = _lod_legacy('lod_append', "Carry a lengths tensor instead.")
lod_reset = _lod_legacy('lod_reset', "Carry a lengths tensor instead.")
reorder_lod_tensor_by_rank = _lod_legacy(
    'reorder_lod_tensor_by_rank',
    "Sort dense rows with paddle.argsort + gather.")
get_tensor_from_selected_rows = _lod_legacy(
    'get_tensor_from_selected_rows',
    "SelectedRows does not exist here; gradients are dense or handled "
    "by the PS sparse tables.")
merge_selected_rows = _lod_legacy(
    'merge_selected_rows',
    "SelectedRows does not exist here; use segment_sum over ids.")


def _reader_legacy(name_):
    def raiser(*a, **k):
        raise NotImplementedError(
            f"fluid.layers.{name_} belongs to the fluid reader stack, "
            "superseded by paddle.io.DataLoader (multiprocess workers, "
            "see io/__init__.py) — feed arrays through "
            "Executor.run(feed=...) or DataLoader instead.")
    raiser.__name__ = name_
    raiser.__doc__ = (f"fluid.layers.{name_} — legacy reader API, "
                      "superseded by paddle.io.DataLoader.")
    return raiser


py_reader = _reader_legacy('py_reader')
read_file = _reader_legacy('read_file')
double_buffer = _reader_legacy('double_buffer')
create_py_reader_by_data = _reader_legacy('create_py_reader_by_data')


# -- TensorArray tier (dygraph-functional; LoDTensorArray analogue) ---------

class TensorArray(list):
    """Dense TensorArray (the LoDTensorArray analogue — a python list of
    Tensors). Works eagerly and inside dy2static-traced code via
    convert_call; a RECORDED static while loop should carry a stacked
    tensor instead (lax.scan discipline), so array ops raise there."""


def _no_static_array(name_):
    from ..core.autograd import STATIC_RECORD_HOOK
    if STATIC_RECORD_HOOK is not None:
        raise NotImplementedError(
            f"fluid.layers.{name_} inside a recorded static program: "
            "dynamic-length arrays don't trace — carry a pre-allocated "
            "stacked tensor through static.nn.while_loop instead")


def create_array(dtype='float32', initialized_list=None):
    """fluid.layers.create_array."""
    _no_static_array('create_array')
    arr = TensorArray()
    for v in (initialized_list or []):
        arr.append(as_tensor(v))
    return arr


def array_write(x, i, array=None):
    """fluid.layers.array_write — write x at index i (extends like the
    reference when i == len)."""
    _no_static_array('array_write')
    if array is None:
        array = TensorArray()
    idx = int(np.asarray(as_tensor(i).data).reshape(()))
    x = as_tensor(x)
    if idx == len(array):
        array.append(x)
    elif idx < len(array):
        array[idx] = x
    else:
        raise IndexError(
            f"array_write index {idx} beyond array length {len(array)}")
    return array


def array_read(array, i):
    """fluid.layers.array_read."""
    _no_static_array('array_read')
    idx = int(np.asarray(as_tensor(i).data).reshape(()))
    return array[idx]


def array_length(array):
    """fluid.layers.array_length."""
    import jax.numpy as jnp
    return Tensor(jnp.asarray(len(array), jnp.int64))


def tensor_array_to_tensor(input, axis=1, use_stack=False):
    """fluid.layers.tensor_array_to_tensor — concat (or stack) the
    array's tensors along `axis`; also returns each entry's size along
    that axis (the LoD-free replacement for the packed index)."""
    import jax.numpy as jnp
    _no_static_array('tensor_array_to_tensor')
    arrs = [as_tensor(t).data for t in input]
    if use_stack:
        out = jnp.stack(arrs, axis=axis)
        sizes = np.ones(len(arrs), np.int32)
    else:
        out = jnp.concatenate(arrs, axis=axis)
        sizes = np.asarray([a.shape[axis] for a in arrs], np.int32)
    return Tensor(out), Tensor(jnp.asarray(sizes))


# -- debug ops --------------------------------------------------------------

def Print(input, first_n=-1, message=None, summarize=20,
          print_tensor_name=True, print_tensor_type=True,
          print_tensor_shape=True, print_tensor_layout=True,
          print_tensor_lod=False, print_phase='both'):
    """fluid.layers.Print (operators/print_op.cc) — pass-through that
    prints the tensor. Eager: host print. Recorded static: jax.debug
    .print (a host callback)."""
    from ..core.autograd import STATIC_RECORD_HOOK, run_op
    msg = message or ''
    if STATIC_RECORD_HOOK is None:
        a = np.asarray(as_tensor(input).data)
        flat = a.reshape(-1)[:summarize]
        print(f"{msg} shape={a.shape} dtype={a.dtype} "
              f"values={flat.tolist()}")
        return as_tensor(input)

    import jax

    def fn(a):
        jax.debug.print(msg + " {x}", x=a)
        return a
    return run_op('print', fn, [as_tensor(input)])


def Assert(cond, data=None, summarize=20, name=None):
    """fluid.layers.Assert (operators/assert_op.cc). Eager: raises
    ValueError when the condition is false. Recorded static programs:
    raises NotImplementedError — in-graph assertions need host
    callbacks; gate input data eagerly or use FLAGS_check_nan_inf for
    numeric guards."""
    from ..core.autograd import STATIC_RECORD_HOOK
    if STATIC_RECORD_HOOK is not None:
        raise NotImplementedError(
            "fluid.layers.Assert inside a recorded static program is "
            "not supported (XLA programs cannot raise) — check the "
            "condition eagerly before feeding, or use "
            "FLAGS_check_nan_inf for numeric guards")
    ok = bool(np.asarray(as_tensor(cond).data).all())
    if not ok:
        extra = ''
        if data is not None:
            vals = [np.asarray(as_tensor(d).data).reshape(-1)[:summarize]
                    for d in (data if isinstance(data, (list, tuple))
                              else [data])]
            extra = f' data={[v.tolist() for v in vals]}'
        raise ValueError(f"Assert failed{extra}")
    return True


# -- imperative control-flow classes (functional forms are the path) --------

def _imperative_cf(name_, modern, example):
    class _Raiser:
        def __init__(self, *a, **k):
            raise NotImplementedError(
                f"fluid.layers.{name_} builds blocks by mutating "
                f"variables in place, which an XLA-traced program "
                f"cannot express — use the functional form "
                f"{modern} (e.g. {example}); dy2static converts "
                f"python `while`/`if` to it automatically")
    _Raiser.__name__ = name_
    _Raiser.__doc__ = (f"fluid.layers.{name_} — imperative block API "
                       f"superseded by {modern}.")
    return _Raiser


While = _imperative_cf(
    'While', 'static.nn.while_loop',
    "while_loop(lambda i: i < n, lambda i: i + 1, [i0])")
Switch = _imperative_cf(
    'Switch', 'static.nn.case/switch_case',
    "case([(cond1, fn1), (cond2, fn2)], default=fn3)")
IfElse = _imperative_cf(
    'IfElse', 'static.nn.cond',
    "cond(pred, true_fn, false_fn)")
StaticRNN = _imperative_cf(
    'StaticRNN', 'paddle.nn.RNN / fluid_layers.rnn',
    "rnn(cell, inputs, initial_states)")
DynamicRNN = _imperative_cf(
    'DynamicRNN', 'paddle.nn.RNN + sequence lengths',
    "rnn(cell, inputs, sequence_length=lens)")
