"""paddle_tpu — a TPU-native deep-learning framework with PaddlePaddle's API.

Top-level namespace parity: python/paddle/__init__.py of the reference
(sandyhouse/Paddle ~v2.1). Eager tensors + autograd tape over jax.vjp; jitted
functional train steps for performance; XLA collectives for distribution.
"""
__version__ = '0.1.0'

# persistent XLA compilation cache (docs/performance.md): under
# JAX_COMPILATION_CACHE_DIR where set, else <repo>/.jax_cache; must run
# before the first jit compile
from .core import compile_cache as _compile_cache
_compile_cache.install()

from .core import dtypes as _dtypes_mod
from .core.dtypes import (bool_ as bool, uint8, int8, int16, int32, int64,  # noqa
                          float16, bfloat16, float32, float64, complex64,
                          complex128)
from .core.tensor import Tensor, to_tensor, _install_operators
from .core import autograd as _autograd
from .core.autograd import no_grad, enable_grad
from .core.lazy import lazy_guard
from .core.rng import seed, get_rng_state, set_rng_state

from . import ops
_install_operators()

# ---- re-export op surface at paddle.* level --------------------------------
from .ops.math import (  # noqa
    add, subtract, multiply, divide, floor_divide, remainder, mod, pow,
    maximum, minimum, fmax, fmin, exp, expm1, log, log2, log10, log1p, sqrt,
    rsqrt, square, abs, sign, floor, ceil, round, trunc, reciprocal, neg, sin,
    cos, tan, asin, acos, atan, sinh, cosh, tanh, asinh, acosh, atanh, atan2,
    erf, lgamma, digamma, scale, clip, increment, stanh, matmul, bmm, mm, dot,
    inner, outer, kron, cross, mv, addmm, sum, mean, max, min, prod, amax,
    amin, nansum, nanmean, logsumexp, all, any, std, var, median, mode,
    quantile, cumsum, cumprod, argmax, argmin, argsort, sort, topk, nonzero,
    equal, not_equal, less_than, less_equal, greater_than, greater_equal,
    equal_all, allclose, isclose, logical_and, logical_or, logical_xor,
    logical_not, bitwise_and, bitwise_or, bitwise_xor, bitwise_not, isnan,
    isinf, isfinite, nan_to_num, norm, dist, where, multiplex, trace, diag,
    diag_embed, lerp, frac, rad2deg, deg2rad, gcd, lcm, count_nonzero,
    heaviside, histogram, broadcast_shape, clip_by_norm, sigmoid,
)
from .ops.manip import (  # noqa
    cast, reshape, transpose, moveaxis, swapaxes, squeeze, unsqueeze, flatten,
    concat, stack, split, chunk, unstack, unbind, tile, expand, expand_as,
    broadcast_to, broadcast_tensors, flip, roll, rot90, gather, gather_nd,
    take_along_axis, put_along_axis, scatter, scatter_nd, scatter_nd_add,
    index_select, index_sample, masked_select, slice, strided_slice, tril,
    triu, diagonal, unique, unique_consecutive, one_hot, shard_index,
    meshgrid, repeat_interleave, as_complex, as_real, real, imag, numel,
    shape, masked_fill,
)
from .ops.creation import (  # noqa
    zeros, ones, full, empty, zeros_like, ones_like, full_like, empty_like,
    arange, linspace, logspace, eye, assign, clone, diagflat, complex,
    uniform, rand, randn, normal, standard_normal, randint, randint_like,
    randperm, bernoulli, poisson, multinomial, gaussian,
)
from .ops import linalg  # noqa
from .ops.linalg import einsum  # noqa

from . import nn
from . import optimizer
from . import amp
from . import io
from . import metric
from . import vision
from . import autograd
from . import jit
from . import static
from . import distributed
from .distributed import DataParallel   # parity: paddle.DataParallel
from . import device
from . import framework
from . import utils
from . import incubate
from . import hapi
from .hapi import Model
from .framework import (save, load, get_default_dtype, set_default_dtype,
                        set_grad_enabled, is_grad_enabled, grad, in_dynamic_mode,
                        CPUPlace, CUDAPlace, TPUPlace, set_device, get_device)
from .nn.layer.common import ParamAttr
from .jit import to_static

# paddle.disable_static / enable_static no-ops (dygraph is the default mode)
from .static import enable_static, disable_static, in_static_mode  # noqa

flops = lambda *a, **k: 0


def is_compiled_with_cuda():
    return False


def is_compiled_with_xpu():
    return False


def is_compiled_with_npu():
    return False


def is_compiled_with_rocm():
    return False


def is_compiled_with_tpu():
    return True


def summary(net, input_size=None, dtypes=None):
    from .hapi.summary import summary as _summary
    return _summary(net, input_size, dtypes)
from . import text  # noqa: E402
from . import profiler  # noqa: E402
from . import models  # noqa: E402
from .ops import fft  # noqa: E402
from .ops.math import (  # noqa: E402
    bincount, bucketize, searchsorted, take, tensordot, logcumsumexp,
    renorm, diff, trapezoid, vander, angle, conj, polar, crop)
from .core.flags import set_flags, get_flags  # noqa: E402
from . import distribution  # noqa: E402
from . import regularizer  # noqa: E402
from . import version  # noqa: E402


def get_cudnn_version():
    return None

from .api_tail import (add_n, floor_mod, inverse, t, is_tensor,  # noqa
                       is_empty, rank, reverse, scatter_,
                       set_printoptions, batch, get_cuda_rng_state,
                       set_cuda_rng_state, CUDAPinnedPlace, NPUPlace,
                       cholesky, create_parameter, check_shape,
                       tanh_, reshape_, squeeze_, unsqueeze_)
from .core import dtypes as dtype  # noqa — paddle.dtype namespace
from . import inference  # noqa
from . import sysconfig  # noqa
from . import onnx  # noqa
