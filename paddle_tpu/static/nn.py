"""Static-graph layer API.

Reference parity: python/paddle/static/nn (fluid/layers/nn.py subset): fc,
conv2d, embedding, batch_norm, etc. These build Parameters in the current
Program and record ops through the shared op layer.
"""
import numpy as np
import jax.numpy as jnp

from ..core import dtypes
from ..ops import nn_ops as F
from ..ops import math as M
from ..ops import manip
from ..nn import initializer as I
from .program import default_main_program, Parameter

__all__ = ['fc', 'embedding', 'conv2d', 'batch_norm', 'cross_entropy',
           'softmax_with_cross_entropy', 'mean', 'dropout']


def _make_param(shape, dtype='float32', initializer=None, attr=None):
    prog = default_main_program()
    block = prog.global_block()
    init = initializer
    if attr is not None and getattr(attr, 'initializer', None) is not None:
        init = attr.initializer
    name = None
    if attr is not None and getattr(attr, 'name', None):
        name = attr.name
    return block.create_parameter(name=name, shape=shape, dtype=dtype,
                                  initializer=init or I.XavierUniform())


def fc(x, size, num_flatten_dims=1, weight_attr=None, bias_attr=None,
       activation=None, name=None):
    """Parity: fluid/layers/nn.py fc → mul + elementwise_add (+act)."""
    in_dim = int(np.prod(x.shape[num_flatten_dims:]))
    w = _make_param([in_dim, size], x.dtype, attr=weight_attr)
    if len(x.shape) > num_flatten_dims + 1:
        x = manip.reshape(x, list(x.shape[:num_flatten_dims]) + [in_dim])
    out = M.matmul(x, w)
    if bias_attr is not False:
        b = _make_param([size], x.dtype, initializer=I.Constant(0.0),
                        attr=bias_attr)
        out = M.add(out, b)
    if activation:
        out = getattr(F, activation)(out)
    return out


def embedding(input, size, is_sparse=False, padding_idx=None,
              param_attr=None, dtype='float32'):
    w = _make_param(list(size), dtype, attr=param_attr)
    return F.embedding(input, w, padding_idx=padding_idx)


def conv2d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=1, param_attr=None, bias_attr=None, act=None,
           data_format="NCHW", name=None):
    k = filter_size if isinstance(filter_size, (list, tuple)) \
        else (filter_size, filter_size)
    cin = input.shape[1]
    w = _make_param([num_filters, cin // groups, k[0], k[1]], input.dtype,
                    attr=param_attr)
    b = None
    if bias_attr is not False:
        b = _make_param([num_filters], input.dtype,
                        initializer=I.Constant(0.0), attr=bias_attr)
    out = F.conv2d(input, w, b, stride=stride, padding=padding,
                   dilation=dilation, groups=groups)
    if act:
        out = getattr(F, act)(out)
    return out


def batch_norm(input, act=None, is_test=False, momentum=0.9, epsilon=1e-05,
               param_attr=None, bias_attr=None, data_layout='NCHW',
               **kwargs):
    c = input.shape[1] if data_layout == 'NCHW' else input.shape[-1]
    scale = _make_param([c], input.dtype, initializer=I.Constant(1.0),
                        attr=param_attr)
    bias = _make_param([c], input.dtype, initializer=I.Constant(0.0),
                       attr=bias_attr)

    # Static BN uses in-graph batch statistics (global-stat tracking needs
    # state vars; the dygraph path owns that).
    from ..core.autograd import run_op
    ch_axis = 1 if data_layout == 'NCHW' else input.ndim - 1
    axes = tuple(i for i in range(input.ndim) if i != ch_axis)

    def fn(a, w, b):
        shape = [1] * a.ndim
        shape[ch_axis] = a.shape[ch_axis]
        m = jnp.mean(a, axis=axes, keepdims=True)
        v = jnp.var(a, axis=axes, keepdims=True)
        out = (a - m) / jnp.sqrt(v + epsilon)
        return out * w.reshape(shape) + b.reshape(shape)
    out = run_op('batch_norm', fn, [input, scale, bias])
    if act:
        out = getattr(F, act)(out)
    return out


def cross_entropy(input, label, soft_label=False, ignore_index=-100):
    return F.cross_entropy(input, label, soft_label=soft_label,
                           ignore_index=ignore_index, reduction='none',
                           use_softmax=False)


def softmax_with_cross_entropy(logits, label, **kwargs):
    return F.softmax_with_cross_entropy(logits, label, **kwargs)


def mean(x):
    return M.mean(x)


def dropout(x, dropout_prob=0.5, is_test=False, **kwargs):
    return F.dropout(x, p=dropout_prob, training=not is_test)


# ---------------------------------------------------------------------------
# fluid.layers breadth (P23): the wider static surface — parameterized
# wrappers where fluid created parameters, re-exports where the shared op
# layer already records (fluid/layers/nn.py + sequence_lod.py +
# detection.py + control_flow.py surfaces)
# ---------------------------------------------------------------------------

def conv2d_transpose(input, num_filters, filter_size, stride=1, padding=0,
                     dilation=1, groups=1, param_attr=None, bias_attr=None,
                     act=None, name=None):
    """Parity: fluid/layers/nn.py conv2d_transpose."""
    k = filter_size if isinstance(filter_size, (list, tuple)) \
        else (filter_size, filter_size)
    cin = input.shape[1]
    w = _make_param([cin, num_filters // groups, k[0], k[1]], input.dtype,
                    attr=param_attr)
    b = None
    if bias_attr is not False:
        b = _make_param([num_filters], input.dtype,
                        initializer=I.Constant(0.0), attr=bias_attr)
    out = F.conv2d_transpose(input, w, b, stride=stride, padding=padding,
                             dilation=dilation, groups=groups)
    if act:
        out = getattr(F, act)(out)
    return out


def layer_norm(input, scale=True, shift=True, begin_norm_axis=1,
               epsilon=1e-05, param_attr=None, bias_attr=None, act=None,
               name=None):
    """Parity: fluid/layers/nn.py layer_norm."""
    import numpy as _np
    norm_shape = [int(_np.prod(input.shape[begin_norm_axis:]))]
    w = _make_param(norm_shape, input.dtype,
                    initializer=I.Constant(1.0),
                    attr=param_attr) if scale else None
    b = _make_param(norm_shape, input.dtype,
                    initializer=I.Constant(0.0),
                    attr=bias_attr) if shift else None
    # dynamic (-1) leading dims: flatten against the single CONCRETE
    # trailing size so only one unknown axis remains in the reshape
    lead = list(input.shape[:begin_norm_axis])
    if any(d is None or d < 0 for d in lead):
        lead = [-1]
    flat = manip.reshape(input, lead + [norm_shape[0]])
    out = F.layer_norm(flat, norm_shape, w, b, epsilon=epsilon)
    out = manip.reshape(out, [d if d is not None else -1
                              for d in input.shape])
    if act:
        out = getattr(F, act)(out)
    return out


def group_norm(input, groups, epsilon=1e-05, param_attr=None,
               bias_attr=None, act=None, data_layout='NCHW', name=None):
    """Parity: fluid/layers/nn.py group_norm."""
    c = input.shape[1] if data_layout == 'NCHW' else input.shape[-1]
    w = _make_param([c], input.dtype, initializer=I.Constant(1.0),
                    attr=param_attr)
    b = _make_param([c], input.dtype, initializer=I.Constant(0.0),
                    attr=bias_attr)
    out = F.group_norm(input, groups, epsilon=epsilon, weight=w, bias=b,
                       data_format=data_layout)
    if act:
        out = getattr(F, act)(out)
    return out


def prelu(x, mode='all', param_attr=None, name=None):
    """Parity: fluid/layers/nn.py prelu (modes all/channel/element)."""
    if mode == 'all':
        shape = [1]
    elif mode == 'channel':
        shape = [x.shape[1]]
    else:
        shape = list(x.shape[1:])
    a = _make_param(shape, x.dtype, initializer=I.Constant(0.25),
                    attr=param_attr)
    return F.prelu(x, a)


def nce(input, label, num_total_classes, num_neg_samples=5,
        param_attr=None, bias_attr=None, sampler='uniform', name=None):
    """Parity: fluid/layers/nn.py nce (parameterized wrapper over the op
    — operators/nce_op.cc)."""
    from ..ops import contrib
    d = input.shape[-1]
    w = _make_param([num_total_classes, d], input.dtype, attr=param_attr)
    b = None
    if bias_attr is not False:
        b = _make_param([num_total_classes], input.dtype,
                        initializer=I.Constant(0.0), attr=bias_attr)
    return contrib.nce(input, label, num_total_classes, w, b,
                       num_neg_samples=num_neg_samples, sampler=sampler)


def hsigmoid(input, label, num_classes, param_attr=None, bias_attr=None,
             name=None):
    """Parity: fluid/layers/nn.py hsigmoid
    (operators/hierarchical_sigmoid_op.cc, default complete tree)."""
    from ..ops import contrib
    d = input.shape[-1]
    w = _make_param([num_classes - 1, d], input.dtype, attr=param_attr)
    b = None
    if bias_attr is not False:
        b = _make_param([num_classes - 1], input.dtype,
                        initializer=I.Constant(0.0), attr=bias_attr)
    return contrib.hsigmoid_loss(input, label, num_classes, w, b)


def row_conv(input, future_context_size, param_attr=None, act=None,
             name=None):
    """Parity: fluid/layers/nn.py row_conv (operators/row_conv_op.cc)."""
    from ..ops import contrib
    d = input.shape[-1]
    w = _make_param([future_context_size + 1, d], input.dtype,
                    attr=param_attr)
    out = contrib.row_conv(input, w)
    if act:
        out = getattr(F, act)(out)
    return out


def deformable_conv(input, offset, mask, num_filters, filter_size,
                    stride=1, padding=0, dilation=1, groups=1,
                    deformable_groups=1, im2col_step=1, param_attr=None,
                    bias_attr=None, modulated=True, name=None):
    """Parity: fluid/layers/nn.py deformable_conv
    (operators/deformable_conv_op.cc v1/v2)."""
    from ..vision.detection import deform_conv2d
    k = filter_size if isinstance(filter_size, (list, tuple)) \
        else (filter_size, filter_size)
    cin = input.shape[1]
    w = _make_param([num_filters, cin // groups, k[0], k[1]], input.dtype,
                    attr=param_attr)
    b = None
    if bias_attr is not False:
        b = _make_param([num_filters], input.dtype,
                        initializer=I.Constant(0.0), attr=bias_attr)
    return deform_conv2d(input, offset, w, b, stride=stride,
                         padding=padding, dilation=dilation,
                         deformable_groups=deformable_groups,
                         groups=groups,
                         mask=mask if modulated else None)


def bilinear_tensor_product(x, y, size, act=None, param_attr=None,
                            bias_attr=None, name=None):
    """Parity: fluid/layers/nn.py bilinear_tensor_product."""
    from ..ops import linalg
    w = _make_param([size, x.shape[-1], y.shape[-1]], x.dtype,
                    attr=param_attr)
    b = None
    if bias_attr is not False:
        b = _make_param([size], x.dtype, initializer=I.Constant(0.0),
                        attr=bias_attr)
    out = linalg.bilinear_tensor_product(x, y, w, b)
    if act:
        out = getattr(F, act)(out)
    return out


def spectral_norm(weight, dim=0, power_iters=1, eps=1e-12, name=None):
    from ..ops import contrib
    return contrib.spectral_norm(weight, dim=dim, power_iters=power_iters,
                                 eps=eps)


def bilateral_slice(x, guide, grid, has_offset, name=None):
    """Parity: fluid/contrib/layers/nn.py:1499 bilateral_slice
    (operators/bilateral_slice_op.cc)."""
    from ..ops import contrib
    return contrib.bilateral_slice(x, guide, grid, has_offset=has_offset)


def correlation(x, y, pad_size, kernel_size, max_displacement, stride1,
                stride2, corr_type_multiply=1):
    """Parity: fluid/contrib/layers/nn.py:1562 correlation
    (operators/correlation_op.cc)."""
    from ..ops import contrib
    return contrib.correlation(x, y, pad_size, kernel_size,
                               max_displacement, stride1, stride2,
                               corr_type_multiply)


# ---------------------------------------------------------------------------
# fluid.layers legacy surface (VERDICT r3 #10 — fluid/layers/nn.py et al.)
# Legacy NAMES + legacy SIGNATURES adapted onto the shared op layer; every
# call records through the same ops the modern API uses.
# ---------------------------------------------------------------------------

def _legacy_binop(op, x, y, axis=-1, act=None, name=None):
    """fluid elementwise_* broadcast: align y's dims starting at `axis`."""
    if axis != -1 and len(getattr(y, 'shape', [])) < len(x.shape):
        yr = y
        trail = len(x.shape) - axis - len(y.shape)
        if trail > 0:
            yr = manip.reshape(y, list(y.shape) + [1] * trail)
        out = op(x, yr)
    else:
        out = op(x, y)
    if act:
        out = getattr(F, act)(out)
    return out


def elementwise_add(x, y, axis=-1, act=None, name=None):
    return _legacy_binop(M.add, x, y, axis, act)


def elementwise_sub(x, y, axis=-1, act=None, name=None):
    return _legacy_binop(M.subtract, x, y, axis, act)


def elementwise_mul(x, y, axis=-1, act=None, name=None):
    return _legacy_binop(M.multiply, x, y, axis, act)


def elementwise_div(x, y, axis=-1, act=None, name=None):
    return _legacy_binop(M.divide, x, y, axis, act)


def elementwise_pow(x, y, axis=-1, act=None, name=None):
    return _legacy_binop(M.pow, x, y, axis, act)


def elementwise_max(x, y, axis=-1, act=None, name=None):
    return _legacy_binop(M.maximum, x, y, axis, act)


def elementwise_min(x, y, axis=-1, act=None, name=None):
    return _legacy_binop(M.minimum, x, y, axis, act)


def elementwise_mod(x, y, axis=-1, act=None, name=None):
    return _legacy_binop(M.mod, x, y, axis, act)


def elementwise_floordiv(x, y, axis=-1, act=None, name=None):
    return _legacy_binop(M.floor_divide, x, y, axis, act)


def _legacy_reduce(fn, input, dim=None, keep_dim=False, name=None):
    axis = dim if dim is None or isinstance(dim, (list, tuple)) else [dim]
    return fn(input, axis=axis, keepdim=keep_dim)


def reduce_sum(input, dim=None, keep_dim=False, name=None):
    return _legacy_reduce(M.sum, input, dim, keep_dim)


def reduce_mean(input, dim=None, keep_dim=False, name=None):
    return _legacy_reduce(M.mean, input, dim, keep_dim)


def reduce_max(input, dim=None, keep_dim=False, name=None):
    return _legacy_reduce(M.max, input, dim, keep_dim)


def reduce_min(input, dim=None, keep_dim=False, name=None):
    return _legacy_reduce(M.min, input, dim, keep_dim)


def reduce_prod(input, dim=None, keep_dim=False, name=None):
    return _legacy_reduce(M.prod, input, dim, keep_dim)


def reduce_all(input, dim=None, keep_dim=False, name=None):
    return _legacy_reduce(M.all, input, dim, keep_dim)


def reduce_any(input, dim=None, keep_dim=False, name=None):
    return _legacy_reduce(M.any, input, dim, keep_dim)


def fill_constant(shape, dtype, value, force_cpu=False, out=None, name=None):
    from ..ops import creation
    return creation.full(shape, value, dtype=dtype)


def fill_constant_batch_size_like(input, shape, dtype, value,
                                  input_dim_idx=0, output_dim_idx=0,
                                  name=None):
    from ..ops import creation
    shape = list(shape)
    shape[output_dim_idx] = input.shape[input_dim_idx]
    return creation.full(shape, value, dtype=dtype)


def create_tensor(dtype, name=None, persistable=False):
    from ..ops import creation
    return creation.zeros([1], dtype=dtype)


def create_global_var(shape, value, dtype, persistable=False,
                      force_cpu=False, name=None):
    prog = default_main_program()
    block = prog.global_block()
    from .program import Variable
    vname = name or prog._unique_name('global_var')
    v = Variable(block, vname, list(shape), dtype,
                 persistable=persistable)
    v.initializer = I.Constant(float(value))
    block.vars[vname] = v
    if persistable:
        prog.startup_ops.append(v)
    return v


def create_parameter(shape, dtype, name=None, attr=None, is_bias=False,
                     default_initializer=None):
    return _make_param(list(shape), dtype,
                       initializer=default_initializer, attr=attr)


def mul(x, y, x_num_col_dims=1, y_num_col_dims=1, name=None):
    xs = x
    if len(x.shape) > x_num_col_dims + 1:
        xs = manip.reshape(x, [int(np.prod(x.shape[:x_num_col_dims]))
                               if x_num_col_dims else 1, -1])
    return M.matmul(xs, y)


def matmul(x, y, transpose_x=False, transpose_y=False, alpha=1.0,
           name=None):
    out = M.matmul(x, y, transpose_x=transpose_x, transpose_y=transpose_y)
    if alpha != 1.0:
        out = M.scale(out, scale=alpha)
    return out


def l2_normalize(x, axis, epsilon=1e-12, name=None):
    return F.normalize(x, p=2, axis=axis, epsilon=epsilon)


def pool2d(input, pool_size=-1, pool_type="max", pool_stride=1,
           pool_padding=0, global_pooling=False, ceil_mode=False,
           exclusive=True, data_format="NCHW", name=None):
    if global_pooling:
        return (F.adaptive_max_pool2d if pool_type == 'max'
                else F.adaptive_avg_pool2d)(input, 1)
    fn = F.max_pool2d if pool_type == 'max' else F.avg_pool2d
    return fn(input, kernel_size=pool_size, stride=pool_stride,
              padding=pool_padding, ceil_mode=ceil_mode)


def image_resize(input, out_shape=None, scale=None, resample='BILINEAR',
                 align_corners=True, align_mode=1, name=None,
                 data_format='NCHW'):
    mode = {'BILINEAR': 'bilinear', 'NEAREST': 'nearest',
            'TRILINEAR': 'trilinear', 'LINEAR': 'linear'}[resample]
    return F.interpolate(input, size=out_shape, scale_factor=scale,
                         mode=mode)


def resize_bilinear(input, out_shape=None, scale=None, name=None,
                    align_corners=True, align_mode=1, data_format='NCHW'):
    return F.interpolate(input, size=out_shape, scale_factor=scale,
                         mode='bilinear')


def resize_nearest(input, out_shape=None, scale=None, name=None,
                   align_corners=True, data_format='NCHW'):
    return F.interpolate(input, size=out_shape, scale_factor=scale,
                         mode='nearest')


def cos_sim(X, Y):
    return F.cosine_similarity(X, Y, axis=-1)


def log_loss(input, label, epsilon=1e-4, name=None):
    return F.log_loss(input, label, epsilon=epsilon)


def huber_loss(input, label, delta):
    return F.smooth_l1_loss(input, label, reduction='none', delta=delta)


def smooth_l1(x, y, inside_weight=None, outside_weight=None, sigma=None):
    diff = M.subtract(x, y)
    if inside_weight is not None:
        diff = M.multiply(diff, inside_weight)
    s2 = (sigma or 1.0) ** 2
    from ..core.tensor import Tensor
    import jax.numpy as _jnp
    d = diff.data if isinstance(diff, Tensor) else _jnp.asarray(diff)
    a = _jnp.abs(d)
    out = _jnp.where(a < 1.0 / s2, 0.5 * s2 * d * d, a - 0.5 / s2)
    if outside_weight is not None:
        ow = outside_weight.data if isinstance(outside_weight, Tensor) \
            else _jnp.asarray(outside_weight)
        out = out * ow
    return Tensor(out.sum(axis=-1, keepdims=True))


def bpr_loss(input, label, name=None):
    """Bayesian personalized ranking (fluid/layers/nn.py bpr_loss)."""
    from ..core.tensor import Tensor
    import jax.numpy as _jnp
    x = input.data
    lb = label.data.reshape(-1)
    pos = _jnp.take_along_axis(x, lb[:, None].astype(_jnp.int32), axis=1)
    loss = -_jnp.log(jnn_sigmoid(pos - x) + 1e-8)
    n = x.shape[1]
    loss = (loss.sum(axis=1, keepdims=True) - (-_jnp.log(
        jnn_sigmoid(_jnp.zeros_like(pos)) + 1e-8))) / (n - 1)
    return Tensor(loss)


def rank_loss(label, left, right, name=None):
    from ..core.tensor import Tensor
    import jax.numpy as _jnp
    d = left.data - right.data
    lb = label.data
    return Tensor(_jnp.log1p(_jnp.exp(d)) - lb * d)


def margin_rank_loss(label, left, right, margin=0.1, name=None):
    return F.margin_ranking_loss(left, right, label, margin=margin,
                                 reduction='none')


def dice_loss(input, label, epsilon=1e-5):
    from ..core.tensor import Tensor
    import jax.numpy as _jnp
    x = input.data
    lb = F.one_hot(label, x.shape[-1]).data.reshape(x.shape) \
        if label.data.shape != x.shape else label.data
    red = tuple(range(1, x.ndim))
    inter = (x * lb).sum(axis=red)
    union = x.sum(axis=red) + lb.sum(axis=red)
    return Tensor((1 - (2 * inter + epsilon) / (union + epsilon)).mean())


def sigmoid_cross_entropy_with_logits(x, label, ignore_index=-100,
                                      name=None, normalize=False):
    """fluid sigmoid_cross_entropy_with_logits: positions whose label ==
    ignore_index contribute 0; normalize divides by the valid count."""
    from ..core.tensor import Tensor
    import jax.numpy as _jnp
    out = F.binary_cross_entropy_with_logits(x, label, reduction='none')
    lb = label.data if isinstance(label, Tensor) else _jnp.asarray(label)
    valid = lb != ignore_index
    o = _jnp.where(valid, out.data, 0.0)
    if normalize:
        o = o / _jnp.maximum(valid.sum().astype(o.dtype), 1.0)
    return Tensor(o)


def teacher_student_sigmoid_loss(input, label,
                                 soft_max_up_bound=15.0,
                                 soft_max_lower_bound=-15.0):
    from ..core.tensor import Tensor
    import jax.numpy as _jnp
    x = _jnp.clip(input.data.reshape(-1), soft_max_lower_bound,
                  soft_max_up_bound)
    z = label.data.reshape(-1)
    loss = _jnp.log(1 + _jnp.exp(-_jnp.abs(x))) + _jnp.maximum(x, 0.0) \
        - x * z
    return Tensor(loss[:, None])


def kldiv_loss(x, target, reduction='mean', name=None):
    return F.kl_div(x, target, reduction=reduction)


def hard_sigmoid(x, slope=0.2, offset=0.5, name=None):
    from ..core.tensor import Tensor
    import jax.numpy as _jnp
    return Tensor(_jnp.clip(slope * x.data + offset, 0.0, 1.0))


def hard_swish(x, threshold=6.0, scale=6.0, offset=3.0, name=None):
    from ..core.tensor import Tensor
    import jax.numpy as _jnp
    return Tensor(x.data * _jnp.clip(x.data + offset, 0, threshold)
                  / scale)


def swish(x, beta=1.0, name=None):
    from ..core.tensor import Tensor
    return Tensor(x.data * jnn_sigmoid(beta * x.data))


def mish(x, name=None):
    from ..core.tensor import Tensor
    import jax.numpy as _jnp
    return Tensor(x.data * _jnp.tanh(_jnp.log1p(_jnp.exp(x.data))))


def brelu(x, t_min=0.0, t_max=24.0, name=None):
    from ..core.tensor import Tensor
    import jax.numpy as _jnp
    return Tensor(_jnp.clip(x.data, t_min, t_max))


def soft_relu(x, threshold=40.0, name=None):
    from ..core.tensor import Tensor
    import jax.numpy as _jnp
    return Tensor(_jnp.log1p(_jnp.exp(_jnp.clip(x.data, -threshold,
                                                threshold))))


def jnn_sigmoid(v):
    import jax
    return jax.nn.sigmoid(v)


def sums(input, out=None):
    out_t = input[0]
    for t in input[1:]:
        out_t = M.add(out_t, t)
    return out_t


def autoincreased_step_counter(counter_name=None, begin=1, step=1):
    v = create_global_var([1], begin - step, 'int64', persistable=True,
                          name=counter_name or '@STEP_COUNTER')
    return v


def has_inf(x):
    from ..core.tensor import Tensor
    import jax.numpy as _jnp
    return Tensor(_jnp.isinf(x.data).any())


def has_nan(x):
    from ..core.tensor import Tensor
    import jax.numpy as _jnp
    return Tensor(_jnp.isnan(x.data).any())


def shuffle_channel(x, group, name=None):
    from ..core.tensor import Tensor
    n, c, h, w = x.shape
    r = manip.reshape(x, [n, group, c // group, h, w])
    t = manip.transpose(r, [0, 2, 1, 3, 4])
    return manip.reshape(t, [n, c, h, w])


def add_position_encoding(input, alpha, beta, name=None):
    from ..core.tensor import Tensor
    import jax.numpy as _jnp
    x = input.data
    B, L, D = x.shape
    pos = _jnp.arange(L)[:, None]
    half = D // 2
    div = _jnp.power(10000.0, _jnp.arange(half) / float(half))
    enc = _jnp.concatenate([_jnp.sin(pos / div), _jnp.cos(pos / div)],
                           axis=1)
    return Tensor(alpha * x + beta * enc[None, :, :D])


def fsp_matrix(x, y):
    from ..core.tensor import Tensor
    import jax.numpy as _jnp
    a, b = x.data, y.data
    n, c1 = a.shape[:2]
    c2 = b.shape[1]
    h = a.shape[2] * a.shape[3]
    return Tensor(_jnp.einsum('nch,ndh->ncd', a.reshape(n, c1, h),
                              b.reshape(n, c2, h)) / h)


def sampling_id(x, min=0.0, max=1.0, seed=0, dtype='int64'):
    from ..core.tensor import Tensor
    from ..core import rng as _rng
    import jax
    key = _rng.next_key()
    return Tensor(jax.random.categorical(key, jax.numpy.log(
        x.data + 1e-9), axis=-1))


def filter_by_instag(ins, ins_tag, filter_tag, is_lod=True,
                     out_val_if_empty=0):
    """Parity: fluid.layers.filter_by_instag (host data-prep)."""
    from ..ops import recsys as _rec
    return _rec.filter_by_instag(ins, ins_tag, filter_tag, is_lod,
                                 out_val_if_empty)


# -- recsys / PS tier (fluid.contrib.layers parity) --------------------------

def continuous_value_model(input, cvm, use_cvm=True):
    from ..ops import recsys as _R
    return _R.continuous_value_model(input, cvm, use_cvm=use_cvm)


def data_norm(input, act=None, epsilon=1e-05, param_attr=None,
              data_layout='NCHW', in_place=False, name=None,
              moving_mean_name=None, moving_variance_name=None,
              do_model_average_for_mean_and_var=True, slot_dim=-1,
              summary_decay=0.9999999):
    """fluid/layers/nn.py data_norm — creates the three persistable
    summary stats and normalizes by them (stat UPDATE happens in the
    training loop via ops.recsys.data_norm_update)."""
    from ..ops import recsys as _R
    d = input.shape[-1]
    bsize = _make_param([d], 'float32', initializer=I.Constant(1e4))
    bsum = _make_param([d], 'float32', initializer=I.Constant(0.0))
    bsq = _make_param([d], 'float32', initializer=I.Constant(1e4))
    y, _, _ = _R.data_norm(input, bsize, bsum, bsq, epsilon=epsilon)
    if act:
        y = getattr(F, act)(y)
    return y


def shuffle_batch(x, seed=None):
    from ..ops import recsys as _R
    out, _idx = _R.shuffle_batch(x, seed=seed or 0)
    return out


def batch_fc(input, param_size, param_attr=None, bias_size=None,
             bias_attr=None, act=None):
    from ..ops import recsys as _R
    w = _make_param(list(param_size), input.dtype, attr=param_attr)
    b = _make_param(list(bias_size), input.dtype, attr=bias_attr,
                    initializer=I.Constant(0.0)) \
        if bias_size is not None else None
    out = _R.batch_fc(input, w, b)
    if act:
        out = getattr(F, act)(out)
    return out


def rank_attention(input, rank_offset, rank_param_shape, rank_param_attr=None,
                   max_rank=3, max_size=0):
    from ..ops import recsys as _R
    w = _make_param(list(rank_param_shape), input.dtype,
                    attr=rank_param_attr)
    return _R.rank_attention(input, rank_offset, w, max_rank=max_rank)


def tdm_child(x, node_nums, child_nums, param_attr=None, dtype='int32'):
    """fluid.contrib.layers.tdm_child — the tree-info table is a
    (non-trainable) parameter of shape [node_nums, 3 + child_nums]."""
    from ..ops import recsys as _R
    info = _make_param([node_nums, 3 + child_nums], 'float32',
                       attr=param_attr, initializer=I.Constant(0.0))
    return _R.tdm_child(x, info, child_nums)


def tdm_sampler(x, neg_samples_num_list, layer_node_num_list, leaf_node_num,
                tree_travel_attr=None, tree_layer_attr=None,
                output_positive=True, output_list=False, seed=0,
                tree_dtype='int32', dtype='int32'):
    from ..ops import recsys as _R
    layer_nums = len(neg_samples_num_list)
    travel = _make_param([leaf_node_num, layer_nums], 'float32',
                         attr=tree_travel_attr, initializer=I.Constant(0.0))
    total = int(sum(layer_node_num_list))
    layer = _make_param([total], 'float32', attr=tree_layer_attr,
                        initializer=I.Constant(0.0))
    offs = [0]
    for n in layer_node_num_list:
        offs.append(offs[-1] + int(n))
    return _R.tdm_sampler(x, travel, layer, neg_samples_num_list, offs,
                          output_positive=output_positive, seed=seed)


def match_matrix_tensor(x, y, channel_num, act=None, param_attr=None,
                        dtype='float32', name=None):
    from ..ops import recsys as _R
    d = x.shape[-1]
    w = _make_param([d, channel_num, d], dtype, attr=param_attr)
    out = _R.match_matrix_tensor(x, y, w)
    if act:
        out = getattr(F, act)(out)
    return out


def var_conv_2d(input, row, col, input_channel, output_channel, filter_size,
                stride=1, param_attr=None, act=None, dtype='float32',
                name=None):
    from ..ops import recsys as _R
    w = _make_param([output_channel,
                     input_channel * filter_size * filter_size], dtype,
                    attr=param_attr)
    out = _R.var_conv_2d(input, w, input_channel, output_channel,
                         filter_size, stride=stride, row_lens=row,
                         col_lens=col)
    if act:
        out = getattr(F, act)(out)
    return out


def tree_conv(nodes_vector, edge_set, output_size, num_filters=1,
              max_depth=2, act='tanh', param_attr=None, bias_attr=None,
              name=None):
    from ..ops import recsys as _R
    fdim = nodes_vector.shape[-1]
    w = _make_param([fdim, 3, output_size, num_filters],
                    nodes_vector.dtype, attr=param_attr)
    out = _R.tree_conv(nodes_vector, edge_set, w, max_depth=max_depth)
    if bias_attr is not False and bias_attr is not None:
        b = _make_param([output_size, num_filters], nodes_vector.dtype,
                        attr=bias_attr, initializer=I.Constant(0.0))
        out = M.add(out, b)
    if act:
        out = getattr(F, act)(out)
    return out


def search_pyramid_hash(input, num_emb, space_len, pyramid_layer, rand_len,
                        drop_out_percent=0.0, is_training=True,
                        use_filter=False, white_list_len=0, black_list_len=0,
                        seed=0, lr=1.0, param_attr=None, param_attr_wl=None,
                        param_attr_bl=None, name=None,
                        distribute_update_vars=None, dtype='float32',
                        seq_lens=None):
    from ..ops import recsys as _R
    w = _make_param([space_len + rand_len, 1], dtype, attr=param_attr)
    return _R.pyramid_hash(input, w, num_emb=num_emb, space_len=space_len,
                           pyramid_layer=pyramid_layer, rand_len=rand_len,
                           seq_lens=seq_lens, seed=seed)


def py_func(func, x, out, backward_func=None,
            skip_vars_in_backward_input=None):
    """Parity: fluid/layers/nn.py py_func (operators/py_func_op.cc) —
    embed a host python callable as an op in the static program. The
    recorded op runs `func` through `jax.pure_callback` (the XLA host
    callback — the TPU analogue of the reference's interpreter
    re-entry), so it executes inside the one-jit Executor replay.

    `out` declares the result spec: a Variable created via
    `block.create_var(shape=..., dtype=...)`, or a (shape, dtype)
    tuple, or a list of either. With `backward_func(x..., out...,
    dout...) -> dx...` the op is differentiable (also via callback);
    without it, gradients stop."""
    import jax
    xs = list(x) if isinstance(x, (list, tuple)) else [x]

    def _is_spec(o):
        # a single (shape, dtype) pair, e.g. ([3, 4], 'float32')
        return (isinstance(o, tuple) and len(o) == 2
                and isinstance(o[0], (list, tuple))
                and isinstance(o[1], (str, np.dtype, type)))
    if _is_spec(out) or not isinstance(out, (list, tuple)):
        outs = [out]
        multi_out = False
    else:
        outs = list(out)
        multi_out = True

    def spec_of(o):
        if _is_spec(o):
            shape, dt = o
        else:
            shape, dt = o.shape, o.dtype
        import jax.numpy as _jnp
        if any(d is None or int(d) < 1 for d in shape):
            raise ValueError(
                f"py_func out shape {tuple(shape)} has dynamic dims; "
                "XLA host callbacks need static shapes — declare the "
                "concrete batch size (the reference's -1 dims rely on "
                "interpreter-side shape inference this backend "
                "deliberately does not do)")
        shape = tuple(int(d) for d in shape)
        return jax.ShapeDtypeStruct(shape, _jnp.dtype(dt))

    out_specs = [spec_of(o) for o in outs]

    def host_fwd(*arrays):
        res = func(*[np.asarray(a) for a in arrays])
        res = res if isinstance(res, (list, tuple)) else [res]
        return tuple(np.asarray(r, dtype=sp.dtype).reshape(sp.shape)
                     for r, sp in zip(res, out_specs))

    def fwd_fn(*arrays):
        res = jax.pure_callback(host_fwd, tuple(out_specs), *arrays)
        return tuple(res) if multi_out else res[0]

    if backward_func is not None:
        skip = skip_vars_in_backward_input or []
        skip = skip if isinstance(skip, (list, tuple)) else [skip]
        # positions of forward inputs the reference drops from
        # backward_func's argument list (matched by object identity)
        skip_idx = {i for i, v in enumerate(xs)
                    if any(v is sv for sv in skip)}

        @jax.custom_vjp
        def op(*arrays):
            return fwd_fn(*arrays)

        def op_fwd(*arrays):
            o = fwd_fn(*arrays)
            return o, (arrays, o if multi_out else (o,))

        def op_bwd(res, cts):
            arrays, os_ = res
            cts = cts if isinstance(cts, tuple) else (cts,)
            in_specs = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype)
                             for a in arrays)
            passed = tuple(a for i, a in enumerate(arrays)
                           if i not in skip_idx)

            def host_bwd(*all_args):
                grads = backward_func(*[np.asarray(a)
                                        for a in all_args])
                grads = grads if isinstance(grads, (list, tuple)) \
                    else [grads]
                grads = list(grads)
                # zeros for skipped inputs, in position
                full = []
                gi = 0
                for i, sp in enumerate(in_specs):
                    if i in skip_idx:
                        full.append(np.zeros(sp.shape, sp.dtype))
                    else:
                        full.append(np.asarray(
                            grads[gi], dtype=sp.dtype).reshape(sp.shape))
                        gi += 1
                return tuple(full)
            return jax.pure_callback(host_bwd, in_specs,
                                     *passed, *os_, *cts)

        op.defvjp(op_fwd, op_bwd)
        run_fn = op
    else:
        run_fn = fwd_fn

    from ..core.autograd import run_op as _run_op
    return _run_op('py_func', run_fn, xs,
                   n_nondiff=0 if backward_func is not None else len(xs))


def multi_box_head(inputs, image, base_size, num_classes,
                   aspect_ratios, min_ratio=None, max_ratio=None,
                   min_sizes=None, max_sizes=None, steps=None,
                   step_w=None, step_h=None, offset=0.5,
                   variance=(0.1, 0.1, 0.2, 0.2), flip=True, clip=False,
                   kernel_size=1, pad=0, stride=1, name=None,
                   min_max_aspect_ratios_order=False):
    """Parity: fluid/layers/detection.py multi_box_head — the SSD
    detection head: per feature map, a prior_box ladder plus 1x1/3x3
    conv loc & conf predictors, flattened and concatenated across maps.
    Returns (mbox_locs [N, P, 4], mbox_confs [N, P, C], boxes [P, 4],
    variances [P, 4])."""
    from ..vision import detection as _det
    from ..ops import manip as _m
    n_in = len(inputs)
    if min_sizes is None:
        # the reference's min/max ratio ladder: first map fixed at
        # 10%/20% of base_size, the rest stepping min_ratio..max_ratio
        step = int(np.floor((max_ratio - min_ratio) / (n_in - 2))) \
            if n_in > 2 else 0
        min_sizes = [base_size * 0.1]
        max_sizes = [base_size * 0.2]
        r = min_ratio
        for _ in range(1, n_in):
            min_sizes.append(base_size * r / 100.0)
            max_sizes.append(base_size * (r + step) / 100.0)
            r += step
    if not isinstance(min_sizes[0], (list, tuple)):
        min_sizes = [[m] for m in min_sizes]
    if max_sizes is not None and not isinstance(max_sizes[0],
                                                (list, tuple)):
        max_sizes = [[m] for m in max_sizes]
    if not isinstance(aspect_ratios[0], (list, tuple)):
        aspect_ratios = [aspect_ratios] * n_in

    locs, confs, boxes_l, vars_l = [], [], [], []
    for i, x in enumerate(inputs):
        mins = [float(v) for v in min_sizes[i]]
        maxs = [float(v) for v in max_sizes[i]] if max_sizes else None
        st = (0.0, 0.0)
        if steps is not None:
            st = steps[i] if isinstance(steps[i], (list, tuple)) \
                else [steps[i], steps[i]]
        elif step_w is not None:
            st = [step_w[i], step_h[i] if step_h is not None else 0.0]
        box, var = _det.prior_box(
            x, image, mins, maxs, aspect_ratios[i], variance=variance,
            flip=flip, clip=clip, steps=st, offset=offset,
            min_max_aspect_ratios_order=min_max_aspect_ratios_order)
        P_i = int(np.prod(box.shape[:-1]))
        boxes_l.append(_m.reshape(box, [P_i, 4]))
        vars_l.append(_m.reshape(var, [P_i, 4]))
        num_priors = P_i // (int(x.shape[2]) * int(x.shape[3]))
        cin = int(x.shape[1])
        wl = _make_param([num_priors * 4, cin, kernel_size, kernel_size],
                         x.dtype)
        bl = _make_param([num_priors * 4], x.dtype,
                         initializer=I.Constant(0.0))
        loc = F.conv2d(x, wl, bl, stride=stride, padding=pad)
        loc = _m.transpose(loc, [0, 2, 3, 1])
        locs.append(_m.reshape(loc, [int(x.shape[0]), P_i, 4]))
        wc = _make_param(
            [num_priors * num_classes, cin, kernel_size, kernel_size],
            x.dtype)
        bc = _make_param([num_priors * num_classes], x.dtype,
                         initializer=I.Constant(0.0))
        conf = F.conv2d(x, wc, bc, stride=stride, padding=pad)
        conf = _m.transpose(conf, [0, 2, 3, 1])
        confs.append(_m.reshape(conf,
                                [int(x.shape[0]), P_i, num_classes]))
    mbox_locs = _m.concat(locs, axis=1)
    mbox_confs = _m.concat(confs, axis=1)
    boxes = _m.concat(boxes_l, axis=0)
    variances = _m.concat(vars_l, axis=0)
    return mbox_locs, mbox_confs, boxes, variances


def _reexport():
    """The rest of the fluid.layers vocabulary records through the shared
    op layer — re-export so `static.nn.<name>` resolves (fluid/layers
    nn.py / sequence_lod.py / detection.py / control_flow.py names)."""
    from ..ops import contrib as _contrib
    from ..ops import sequence as _seq
    from . import fluid_layers as _fl
    from ..ops import creation as _cr
    from ..vision import detection as _det
    from ..vision import ops as _vops
    from . import control_flow as _cf
    g = globals()
    for mod, names in (
        (F, ['relu', 'softmax', 'log_softmax', 'sigmoid', 'tanh', 'gelu',
             'max_pool2d', 'avg_pool2d', 'adaptive_avg_pool2d',
             'adaptive_max_pool2d', 'one_hot', 'maxout', 'instance_norm',
             'pad', 'interpolate', 'grid_sample', 'pixel_shuffle',
             'label_smooth', 'kl_div', 'mse_loss', 'l1_loss',
             'smooth_l1_loss', 'margin_ranking_loss', 'nll_loss',
             'binary_cross_entropy', 'binary_cross_entropy_with_logits',
             'square_error_cost', 'elu', 'selu', 'leaky_relu', 'conv3d',
             'conv2d_transpose', 'unfold', 'affine_grid', 'temporal_shift',
             'npair_loss', 'sequence_mask', 'grid_sample']),
        (M, ['scale', 'clip', 'clip_by_norm', 'assign', 'increment',
             'stanh', 'sign', 'log', 'pow', 'topk', 'argmax', 'argmin',
             'argsort', 'where', 'multiplex', 'diag', 'isfinite',
             'equal', 'not_equal', 'less_than', 'less_equal',
             'greater_than', 'greater_equal', 'logical_and', 'logical_or',
             'logical_xor', 'logical_not', 'cumsum', 'crop']),
        (manip, ['cast', 'concat', 'reshape', 'squeeze', 'unsqueeze',
                 'transpose', 'split', 'stack', 'unstack', 'unbind',
                 'slice', 'strided_slice', 'gather', 'gather_nd',
                 'scatter', 'scatter_nd', 'scatter_nd_add', 'expand',
                 'expand_as', 'flatten', 'flip', 'shard_index', 'shape',
                 'space_to_depth', 'tile', 'triu', 'unique',
                 'index_sample']),
        (_cr, ['zeros', 'ones', 'zeros_like', 'ones_like', 'eye',
               'linspace', 'arange', 'uniform', 'full', 'full_like',
               'randperm']),
        (_contrib, ['unpool', 'im2sequence', 'spp', 'mean_iou',
                    'precision_recall', 'positive_negative_pair',
                    'affine_channel', 'sample_logits', 'random_crop',
                    'polygon_box_transform']),
        (_seq, ['sequence_pad', 'sequence_unpad', 'sequence_expand',
                'sequence_reverse', 'linear_chain_crf', 'crf_decoding',
                'beam_search', 'sequence_concat', 'sequence_conv',
                'sequence_enumerate', 'sequence_expand_as',
                'sequence_first_step', 'sequence_last_step',
                'sequence_pool', 'sequence_reshape', 'sequence_softmax',
                'sequence_slice', 'sequence_scatter', 'sequence_unpad',
                'edit_distance', 'ctc_greedy_decoder', 'warpctc',
                'gather_tree']),
        (_det, ['retinanet_target_assign',
                'roi_perspective_transform',
                'multiclass_nms', 'bipartite_match', 'iou_similarity',
                'yolo_box', 'prior_box', 'box_coder', 'box_clip',
                'anchor_generator', 'generate_proposals', 'matrix_nms',
                'density_prior_box', 'distribute_fpn_proposals',
                'collect_fpn_proposals', 'roi_align', 'roi_pool',
                'ssd_loss', 'target_assign', 'detection_output',
                'rpn_target_assign', 'sigmoid_focal_loss',
                'yolov3_loss', 'prroi_pool', 'psroi_pool',
                'locality_aware_nms', 'polygon_box_transform',
                'retinanet_detection_output', 'box_decoder_and_assign',
                'generate_proposal_labels', 'generate_mask_labels',
                'multi_box_head', 'deformable_roi_pooling']),
        (_cf, ['while_loop', 'cond', 'switch_case', 'case']),
        (_fl, ['rank', 'is_empty', 'reverse', 'crop_tensor', 'pad2d',
               'pad_constant_like', 'adaptive_pool2d', 'adaptive_pool3d',
               'pool3d', 'lrn', 'grid_sampler', 'warpctc',
               'ctc_greedy_decoder', 'unique_with_counts',
               'uniform_random_batch_size_like',
               'gaussian_random_batch_size_like', 'inplace_abn',
               'similarity_focus', 'noam_decay', 'exponential_decay',
               'natural_exp_decay', 'inverse_time_decay',
               'polynomial_decay', 'piecewise_decay', 'cosine_decay',
               'linear_lr_warmup', 'rnn', 'birnn',
               'conv3d_transpose', 'resize_linear', 'resize_trilinear',
               'image_resize_short', 'gru_unit', 'lstm_unit',
               'dynamic_lstm', 'dynamic_lstmp', 'dynamic_gru', 'lstm',
               'beam_search_decode', 'chunk_eval', 'create_array',
               'array_write', 'array_read', 'array_length',
               'tensor_array_to_tensor', 'Print', 'Assert', 'While',
               'Switch', 'IfElse', 'StaticRNN', 'DynamicRNN',
               'lod_append', 'lod_reset', 'reorder_lod_tensor_by_rank',
               'get_tensor_from_selected_rows', 'merge_selected_rows',
               'py_reader', 'double_buffer', 'read_file',
               'create_py_reader_by_data']),
        (_contrib, ['center_loss', 'sampled_softmax_with_cross_entropy',
                    'ctc_align']),
        (_vops, ['roi_align', 'roi_pool']),
    ):
        for n in names:
            if hasattr(mod, n) and n not in g:
                g[n] = getattr(mod, n)
    # legacy spellings of names the modern API renamed
    for legacy, mod, modern in (
        ('range', _cr, 'arange'), ('gaussian_random', _cr, 'gaussian'),
        ('uniform_random', _cr, 'uniform'), ('size', manip, 'numel'),
        ('hash', _contrib, 'row_hash'),
    ):
        if hasattr(mod, modern) and legacy not in g:
            g[legacy] = getattr(mod, modern)


def _nn_aliases():
    from .. import nn as _nnmod
    g = globals()
    for fluid_name, modern in (
        ('RNNCell', 'RNNCellBase'), ('GRUCell', 'GRUCell'),
        ('LSTMCell', 'LSTMCell'), ('BeamSearchDecoder',
                                   'BeamSearchDecoder'),
        ('Decoder', 'Decoder'), ('dynamic_decode', 'dynamic_decode'),
    ):
        if hasattr(_nnmod, modern):
            g.setdefault(fluid_name, getattr(_nnmod, modern))


_nn_aliases()
del _nn_aliases


def accuracy(input, label, k=1, correct=None, total=None, name=None):
    """fluid.layers.accuracy (operators/metrics/accuracy_op.cc) —
    top-k accuracy as a recordable op (works on symbolic Variables,
    unlike the eager paddle.metric.accuracy helper)."""
    import jax.numpy as _jnp
    from ..core.autograd import run_op as _run_op
    from ..ops.common import as_tensor as _as_t
    inp = _as_t(input)
    lab = _as_t(label, ref=inp)

    def fn(p, l):
        kk = min(int(k), p.shape[-1])
        _, topi = jax.lax.top_k(p, kk)
        hit = (topi == l.reshape(-1, 1)).any(axis=-1)
        return hit.mean(dtype=_jnp.float32)
    import jax
    return _run_op('accuracy', fn, [inp, lab], n_nondiff=2)


def auc(input, label, curve='ROC', num_thresholds=4095, topk=1,
        slide_steps=1, name=None):
    """fluid.layers.auc (operators/metrics/auc_op.cc) — batch ROC-AUC
    via thresholded TP/FP histograms, recordable (the reference's
    stateful accumulators live in the metric class for streaming use;
    this op returns the current batch's AUC like auc_op's BatchAuc)."""
    import jax
    import jax.numpy as _jnp
    from ..core.autograd import run_op as _run_op
    from ..ops.common import as_tensor as _as_t
    inp = _as_t(input)
    lab = _as_t(label, ref=inp)
    T = int(num_thresholds)

    def fn(p, l):
        pos_score = p[:, -1] if p.ndim > 1 else p
        y = l.reshape(-1).astype(_jnp.int32)
        bins = _jnp.clip((pos_score * T).astype(_jnp.int32), 0, T)
        tp_h = _jnp.zeros((T + 1,), _jnp.float32).at[bins].add(
            (y == 1).astype(_jnp.float32))
        fp_h = _jnp.zeros((T + 1,), _jnp.float32).at[bins].add(
            (y == 0).astype(_jnp.float32))
        # cumulate from the top threshold down
        tp = _jnp.cumsum(tp_h[::-1])
        fp = _jnp.cumsum(fp_h[::-1])
        tot_p = _jnp.maximum(tp[-1], 1.0)
        tot_n = _jnp.maximum(fp[-1], 1.0)
        if curve == 'PR':
            # precision-recall AUC over the same threshold sweep
            rec = tp / tot_p
            prec = tp / _jnp.maximum(tp + fp, 1.0)
            rec = _jnp.concatenate([_jnp.zeros((1,)), rec])
            prec = _jnp.concatenate([_jnp.ones((1,)), prec])
            return _jnp.trapezoid(prec, rec).astype(_jnp.float32)
        tpr = _jnp.concatenate([_jnp.zeros((1,)), tp]) / tot_p
        fpr = _jnp.concatenate([_jnp.zeros((1,)), fp]) / tot_n
        return _jnp.trapezoid(tpr, fpr).astype(_jnp.float32)
    return _run_op('auc', fn, [inp, lab], n_nondiff=2)


def _data_alias():
    g = globals()
    from .program import data as _data_fn
    g.setdefault('data', _data_fn)


_data_alias()
del _data_alias
_reexport()
del _reexport
