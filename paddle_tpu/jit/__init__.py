"""paddle_tpu.jit — dygraph→static bridge and jitted train steps.

Reference parity: python/paddle/jit (to_static / TranslatedLayer) and
dygraph_to_static/program_translator.py. TPU-native design: instead of
AST-rewriting Python into a ProgramDesc, the eager Layer IS the trace — we run
it under `jax.jit` with its parameters/buffers lifted to function inputs
(functional_call), so the whole step compiles to ONE XLA executable. That is
the idiomatic XLA replacement for the reference's per-op executor hot loop
(operator.cc:1075 RunImpl) and delivers the fusion/latency win the op-function
codegen (pybind/op_function_generator.cc) chases on GPU.

`TrainStep` compiles forward+backward+optimizer into a single program with
donated buffers (grads via jax.grad at trace level — the tape is bypassed).
"""
import contextlib
import functools

import numpy as np
import jax
import jax.numpy as jnp

from ..core import rng as rng_mod
from ..core import autograd
from ..core.tensor import Tensor


def _named_params(layer):
    return list(layer.named_parameters())


def _named_buffers(layer):
    return [(n, b) for n, b in layer.named_buffers() if b is not None]


@contextlib.contextmanager
def bind_arrays(layer, param_arrays, buffer_arrays=None):
    """Temporarily swap layer parameter/buffer .data with given arrays
    (tracers under jit). Yields a dict to collect mutated buffer values."""
    params = _named_params(layer)
    buffers = _named_buffers(layer)
    saved_p = [(p, p._data) for _, p in params]
    saved_b = [(b, b._data) for _, b in buffers]
    try:
        for (n, p) in params:
            p._data = param_arrays[n]
        if buffer_arrays is not None:
            for (n, b) in buffers:
                if n in buffer_arrays:
                    b._data = buffer_arrays[n]
        out_buffers = {}
        yield out_buffers
        for (n, b) in buffers:
            out_buffers[n] = b._data
    finally:
        for p, d in saved_p:
            p._data = d
        for b, d in saved_b:
            b._data = d


def functional_call(layer, param_arrays, args, buffer_arrays=None,
                    rng_key=None):
    """Run `layer(*args)` with parameters bound from `param_arrays`.

    Returns (output arrays pytree, new_buffer_arrays). Pure if the layer is —
    the substrate for jit/pjit'd steps.
    """
    with bind_arrays(layer, param_arrays, buffer_arrays) as out_buffers:
        ctx = rng_mod.rng_guard(rng_key) if rng_key is not None \
            else contextlib.nullcontext()
        with ctx, autograd.no_grad():
            out = layer(*[Tensor(a) if not isinstance(a, Tensor) else a
                          for a in args])
        out_arrays = jax.tree_util.tree_map(
            lambda t: t.data if isinstance(t, Tensor) else t, out,
            is_leaf=lambda t: isinstance(t, Tensor))
    return out_arrays, dict(out_buffers)


def get_params(layer):
    """Extract {name: array} of trainable parameters."""
    return {n: p.data for n, p in _named_params(layer)
            if not p.stop_gradient}


def get_buffers(layer):
    return {n: b.data for n, b in _named_buffers(layer)}


def write_back(layer, param_arrays=None, buffer_arrays=None):
    if param_arrays:
        lookup = dict(_named_params(layer))
        for n, arr in param_arrays.items():
            lookup[n]._data = arr
    if buffer_arrays:
        lookup = dict(_named_buffers(layer))
        for n, arr in buffer_arrays.items():
            if n in lookup:
                lookup[n]._data = arr


from ..core.async_step import AsyncDispatchMixin as _AsyncDispatchMixin


class TrainStep(_AsyncDispatchMixin):
    """One fully-jitted train step: forward, backward, clip, optimizer.

    loss_fn(model, *batch_tensors) -> scalar loss Tensor.
    """

    def __init__(self, model, loss_fn, optimizer, donate=True,
                 use_buckets=None, comm_overlap=None, prefetch_depth=None,
                 comm_chunk=None, remat_policy=None, dispatch_window=None,
                 device_lr=None):
        from ..core import async_step as A_
        from ..core import bucketing as B
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        # tuned remat (docs/performance.md#remat-policy): kwarg ->
        # PTPU_REMAT_POLICY -> strategy.recompute_configs['policy'];
        # the single-program step historically ran without remat, so the
        # default stays 'none'
        from ..distributed.fleet.utils.recompute import (
            resolve_policy as _resolve_remat)
        self._remat_policy = _resolve_remat(remat_policy,
                                                       default='none')
        self._param_names = [n for n, p in _named_params(model)
                             if not p.stop_gradient]
        # copies, not views: the compiled step DONATES these buffers and the
        # eager layer must keep its own arrays alive for eval/save
        self._params = {n: jnp.array(a, copy=True)
                        for n, a in get_params(model).items()}
        self._buffers = {n: jnp.array(a, copy=True)
                         for n, a in get_buffers(model).items()}
        lookup = dict(_named_params(model))
        # bucketed optimizer phase (core/bucketing.py): elementwise
        # optimizers update a handful of flat dtype-homogeneous buckets
        # instead of one kernel chain per parameter — same math (the
        # update is per-element), fewer/larger fused kernels
        self._use_buckets = (use_buckets is not False
                             and B.elementwise(optimizer)
                             and bool(self._param_names))
        # comm-overlap knobs are accepted for engine-API uniformity and
        # recorded in the gauges, but the single-program path has NO
        # collectives to overlap (n_shards=1) — grouping stays off so
        # the compiled program is unchanged with the knob on (the
        # ISSUE-10 dp=1 acceptance invariant)
        self._comm_overlap, self._prefetch_depth, self._comm_chunk = \
            B.resolve_overlap_config(comm_overlap, prefetch_depth,
                                     comm_chunk)
        if self._use_buckets:
            _, bucket_bytes = B.resolve_comm_config()
            self._layout = B.BucketLayout.build(
                {n: (lookup[n].data.shape, lookup[n].data.dtype)
                 for n in self._param_names},
                bucket_bytes=bucket_bytes, pad_to=8)
            self._opt_states = []
            for b in self._layout.buckets:
                flat32 = np.zeros((b.size,), np.float32)
                for s in b.slots:
                    flat32[s.offset:s.offset + s.size] = np.asarray(
                        jax.device_get(lookup[s.name].data),
                        np.float32).reshape(-1)
                st = B.init_bucket_state(optimizer, b, flat32)
                self._opt_states.append(
                    {k: jnp.asarray(v) for k, v in st.items()})
            B.publish_comm_gauges(self._layout, engine='jit', n_shards=1,
                                  enabled=False)
            B.publish_overlap_gauges(self._layout, engine='jit',
                                     n_shards=1, enabled=False,
                                     prefetch=self._prefetch_depth,
                                     chunk=self._comm_chunk)
        else:
            self._layout = None
            self._opt_states = {}
            for n in self._param_names:
                st = optimizer.init_state(lookup[n])
                if lookup[n].data.dtype != jnp.float32 and \
                        getattr(optimizer, '_multi_precision', True):
                    # pre-seed the fp32 master so the state pytree
                    # structure is stable across steps (lax.scan carry
                    # requirement)
                    st['master'] = lookup[n].data.astype(jnp.float32)
                self._opt_states[n] = st
        # numerics taps (core/numerics.py): latched here — they change
        # the compiled step's output tree, so set FLAGS before building
        from ..core import numerics as _num
        self._taps_on = _num.taps_enabled()
        # -- async step pipeline (ISSUE 13,
        # docs/performance.md#async-dispatch): bounded in-flight window,
        # host-gap instrumentation, on-device LR schedule ----------------
        self._inflight = A_.DispatchWindow(
            A_.resolve_dispatch_window(dispatch_window))
        self._gap = A_.HostGapMonitor('jit')
        # step-time ledger (ISSUE 16): wall decomposition + model-FLOPs
        # accounting, published from flush()
        from ..core import ledger as _led
        self._ledger = _led.StepLedger(
            'jit', gap=self._gap,
            params_fn=lambda: _led.count_params(self._params),
            remat_policy=self._remat_policy)
        from ..optimizer import device_lr as _dlr
        self._lr = _dlr.LrFeed(optimizer, device_lr)
        self._compiled = jax.jit(
            self._step,
            donate_argnums=(0, 1, 2) if donate else ())
        self._exec_cache = {}    # batch signature -> AOT executable
        self._step_i = 0

    def _step(self, params, buffers, opt_states, lr, key, batch):
        model, loss_fn, opt = self.model, self.loss_fn, self.optimizer
        # on-device LR schedule: `lr` carries the device int32 step
        # counter; the traced schedule derives this step's lr and the
        # incremented counter rides out as an extra output
        step_c = None
        if self._lr.fn is not None:
            step_c = lr
            lr = self._lr.fn(step_c).astype(jnp.float32)

        def loss_of(ps, bufs):
            with bind_arrays(model, ps, bufs) as out_bufs:
                with rng_mod.rng_guard(key), autograd.no_grad():
                    loss = loss_fn(model, *[Tensor(b) for b in batch])
            return loss.data.astype(jnp.float32), dict(out_bufs)

        from ..distributed.fleet.utils.recompute import (
            apply_policy as _apply_remat)
        loss_of = _apply_remat(loss_of, self._remat_policy,
                                          engine='jit')
        (loss, new_buffers), grads = jax.value_and_grad(
            loss_of, has_aux=True)(params, buffers)
        if self._use_buckets:
            from ..core import bucketing as B
            new_params, new_states = B.flat_functional_apply(
                opt, self._layout, params, grads, opt_states, lr)
        else:
            new_params, new_states = opt.functional_apply(params, grads,
                                                          opt_states, lr)
        out = (loss, new_params, new_buffers, new_states)
        if step_c is not None:
            out = out + (step_c + 1,)
        if self._taps_on:
            from ..core import numerics as _num
            taps = _num.jit_taps(grads, new_params)
            return out + (taps,)
        return out

    def _dispatch(self, batch):
        from .. import profiler as _prof
        from ..core import async_step as A_
        from ..core.monitor import stat_add
        # gap bracket opens BEFORE any jax client call (asarray/key
        # fold-in can serialize behind in-flight compute — dispatch
        # time, not inter-dispatch host gap)
        self._gap.dispatch_begin()
        arrays = tuple(b.data if isinstance(b, Tensor) else jnp.asarray(b)
                       for b in batch)
        if arrays:
            self._ledger.observe_batch(arrays[0].shape)
        key = rng_mod.next_key()
        args = (self._params, self._buffers, self._opt_states,
                self._lr.arg(), key, arrays)
        sig = tuple((tuple(a.shape), str(a.dtype)) for a in arrays)
        exe = self._exec_cache.get(sig)
        if exe is None:
            # compile split out from the steady-state step (observability
            # v2): lower/compile spans + compile-seconds/FLOP metrics
            stat_add('STAT_trainstep_compiles')
            with _prof.RecordEvent('jit::train_step_compile',
                                   event_type='compile'):
                exe, _ = _prof.compile_with_telemetry(
                    self._compiled, 'train_step', args)
            self._exec_cache[sig] = exe
        with _prof.RecordEvent('jit::train_step', event_type='jit'):
            try:
                out = exe(*args)
            except TypeError:
                # AOT signature drift (e.g. dtype-only change): retrace
                if exe is self._compiled:
                    raise
                self._exec_cache[sig] = self._compiled
                out = self._compiled(*args)
        self._gap.dispatch_end(depth=len(self._inflight) + 1)
        loss, self._params, self._buffers, self._opt_states = out[:4]
        i = 4
        if self._lr.fn is not None:
            self._lr.carry = out[i]
            i += 1
        taps = out[i] if self._taps_on else None
        step_no = self._step_i
        self._step_i += 1
        on_drain = None
        if taps is not None:
            def on_drain(res, _t=taps, _s=step_no):
                from ..core import numerics as _num
                meta = {k: {n: (a.shape, a.dtype)
                            for n, a in self._params.items()}
                        for k in ('grads', 'params')}
                self.last_numerics = _num.process_jit_taps(
                    _t, site='jit', step=_s, meta=meta)
        return A_.AsyncResult(loss, step_no, taps=taps,
                              on_drain=on_drain, monitor=self._gap)

    def __call__(self, *batch):
        if len(self._inflight):
            # mixed APIs: drain queued async steps FIRST so deferred
            # work (taps processing) keeps submission order
            self.flush()
        res = self._dispatch(batch)
        res.wait()     # legacy per-step semantics: taps processed now
        return Tensor(res.loss)

    def train_step(self, *batch):
        """Async dispatch (docs/performance.md#async-dispatch): returns
        an AsyncResult; the bounded in-flight window
        (PTPU_DISPATCH_WINDOW) drains the oldest step as it fills."""
        from .. import profiler as _prof
        with _prof.RecordEvent('train::dispatch', event_type='train',
                               engine='jit', step=self._step_i):
            return self._inflight.push(self._dispatch(batch))

    def input_sharding(self, index, ndim):
        """DeviceLoader contract: single-program step — batches go to
        the default device whole."""
        return None

    def sync_model(self):
        """Write jitted state back into the eager Layer (for save/eval).
        Drains the async dispatch window first."""
        self.flush()
        write_back(self.model, self._params, self._buffers)

    # -- multi-step: k steps per dispatch (amortizes host→device launch) ----
    def compile_multi_step(self, k=None):
        if getattr(self, '_multi', None) is not None:
            return  # jax.jit caches per input shape — one jit covers all k
        step = self._step
        device_lr = self._lr.fn is not None

        def many(params, buffers, opt_states, lr, keys, batch_stack):
            def body(carry, xs):
                p, b, s, c = carry
                key = xs[0]
                batch = xs[1]
                # trailing outputs (numerics taps) don't escape a
                # scanned multi-step; XLA DCEs them. Under on-device LR
                # the step counter advances through the scan carry.
                out = step(p, b, s, c, key, batch)
                c2 = out[4] if device_lr else c
                return (out[1], out[2], out[3], c2), out[0]
            (p, b, s, c), losses = jax.lax.scan(
                body, (params, buffers, opt_states, lr),
                (keys, batch_stack))
            return losses, p, b, s, c

        self._multi = jax.jit(many, donate_argnums=(0, 1, 2))

    def run_steps(self, *batch_stacks):
        """Each arg: array with leading dim k (one slice per step). Returns
        the k per-step losses as one Tensor."""
        if len(self._inflight):
            # mixed APIs: drain queued async steps FIRST so deferred
            # work keeps submission order (same rule as __call__)
            self.flush()
        arrays = tuple(b.data if isinstance(b, Tensor) else jnp.asarray(b)
                       for b in batch_stacks)
        k = arrays[0].shape[0]
        self.compile_multi_step()
        lr = self._lr.arg()
        keys = jax.random.split(rng_mod.next_key(), k)
        (losses, self._params, self._buffers, self._opt_states,
         lr_out) = self._multi(
            self._params, self._buffers, self._opt_states, lr, keys,
            arrays)
        if self._lr.fn is not None:
            self._lr.carry = lr_out
        self._step_i += k
        return Tensor(losses)


class EvalStep:
    """Jitted forward pass for inference."""

    def __init__(self, model):
        self.model = model
        self._compiled = jax.jit(self._fwd)

    def _fwd(self, params, buffers, batch):
        out, _ = functional_call(self.model, params, batch, buffers)
        return out

    def __call__(self, *batch):
        arrays = tuple(b.data if isinstance(b, Tensor) else jnp.asarray(b)
                       for b in batch)
        params = {n: p.data for n, p in _named_params(self.model)}
        out = self._compiled(params, get_buffers(self.model), arrays)
        return jax.tree_util.tree_map(Tensor, out)


class StaticFunction:
    """Parity: dygraph_to_static StaticFunction:232 — wraps a function or a
    Layer method; each distinct input signature compiles once into a cached
    XLA executable (the ProgramCache:692 analogue is jax.jit's cache)."""

    def __init__(self, function, input_spec=None):
        # dy2static: rewrite data-dependent if/while/for-range into
        # lax.cond/while_loop dispatchers before tracing (parity:
        # program_translator's AST conversion)
        from . import dy2static
        self._function = dy2static.convert_function(function)
        self._dygraph_function = function
        self._layer = getattr(function, '__self__', None)
        self.input_spec = input_spec
        self._jit_cache = {}   # static-kwargs snapshot -> jitted trace
        self._exec_cache = {}  # (skey, shape sig) -> AOT executable
        self._compiled_sigs = set()

    def __call__(self, *args, **kwargs):
        if not ProgramTranslator.get_instance().enable_to_static:
            return self._dygraph_function(*args, **kwargs)
        # tensor kwargs trace as inputs; other kwargs are compile-time
        # constants keyed into the cache (a new value recompiles instead
        # of silently reusing the first call's)
        t_kwargs = {k: v for k, v in kwargs.items()
                    if isinstance(v, Tensor)}
        s_kwargs = {k: v for k, v in kwargs.items()
                    if not isinstance(v, Tensor)}
        # positional args: tensors/numerics trace; anything else is a
        # compile-time constant keyed into the cache
        spec, arrays, static_pos = [], [], {}
        for i, a in enumerate(args):
            if isinstance(a, Tensor):
                spec.append('t')
                arrays.append(a.data)
            elif isinstance(a, (np.ndarray, jnp.ndarray)):
                spec.append('t')
                arrays.append(jnp.asarray(a))
            else:   # python scalars/objects are compile-time constants
                spec.append('s')
                static_pos[i] = a

        def _hkey(items):
            try:
                k = tuple(items)
                hash(k)
                return k
            except TypeError:
                return tuple((a, repr(b)) for a, b in items)
        from .. import profiler as _prof
        from ..core.monitor import counter
        skey = (tuple(spec), _hkey(sorted(static_pos.items())),
                _hkey(sorted(s_kwargs.items())))
        jitted = self._jit_cache.get(skey)
        counter('ptpu_jit_cache_total',
                help='StaticFunction program-cache lookups',
                labelnames=('result',)).inc(
                    1, result='hit' if jitted is not None else 'miss')
        if jitted is None:
            fn = self._function
            layer = self._layer

            def traced(params, buffers, key, arrs, t_arrays,
                       _sk=dict(s_kwargs), _sp=dict(static_pos),
                       _spec=tuple(spec)):
                it = iter(arrs)
                full = [Tensor(next(it)) if s == 't' else _sp[i]
                        for i, s in enumerate(_spec)]
                with bind_arrays(layer, params, buffers) if layer is not None \
                        else contextlib.nullcontext() as _:
                    with rng_mod.rng_guard(key), autograd.no_grad():
                        kw = dict(_sk)
                        kw.update({k: Tensor(a)
                                   for k, a in t_arrays.items()})
                        out = fn(*full, **kw)
                return jax.tree_util.tree_map(
                    lambda t: t.data if isinstance(t, Tensor) else t, out,
                    is_leaf=lambda t: isinstance(t, Tensor))
            jitted = self._jit_cache[skey] = jax.jit(traced)
        if self._layer is not None:
            params = {n: p.data for n, p in _named_params(self._layer)}
            buffers = get_buffers(self._layer)
        else:
            params, buffers = {}, {}
        call_args = (params, buffers, rng_mod.next_key(), tuple(arrays),
                     {k: v.data for k, v in t_kwargs.items()})
        # per-shape executable cache: jax.jit retraces internally on new
        # shapes; tracking it here splits trace/lower/compile into spans
        # and compile-seconds metrics (jax caches per aval signature)
        shape_sig = (skey, tuple(
            (tuple(getattr(l, 'shape', ())), str(getattr(l, 'dtype', '')))
            for l in jax.tree_util.tree_leaves(
                (params, buffers, call_args[3], call_args[4]))))
        if shape_sig not in self._compiled_sigs:
            self._compiled_sigs.add(shape_sig)
            with _prof.RecordEvent('dy2static::trace_compile',
                                   event_type='compile'):
                exe, ok = _prof.compile_with_telemetry(
                    jitted, 'dy2static', call_args)
            if ok:
                self._exec_cache[shape_sig] = exe
        exe = self._exec_cache.get(shape_sig, jitted)
        with _prof.RecordEvent('dy2static::call', event_type='jit'):
            try:
                out = exe(*call_args)
            except TypeError:
                if exe is jitted:
                    raise
                self._exec_cache.pop(shape_sig, None)
                out = jitted(*call_args)
        return jax.tree_util.tree_map(Tensor, out)


def to_static(function=None, input_spec=None, build_strategy=None,
              property=False):
    """Parity: paddle.jit.to_static decorator."""
    def decorate(fn):
        if isinstance(fn, type):
            raise TypeError("to_static expects a function or Layer instance")
        from ..nn.layer.base import Layer
        if isinstance(fn, Layer):
            sf = StaticFunction(fn.forward, input_spec)
            fn.forward = sf
            return fn
        return functools.wraps(fn)(StaticFunction(fn, input_spec))
    if function is not None:
        return decorate(function)
    return decorate


def save(layer, path, input_spec=None, **configs):
    """Parity: paddle.jit.save — persists state dict (program export lands
    with paddle_tpu.static serialization)."""
    from .. import framework
    framework.save(layer.state_dict(), path + '.pdparams')


def load(path, **configs):
    from .. import framework
    return framework.load(path + '.pdparams')


def not_to_static(fn):
    return fn


class ProgramTranslator:
    _instance = None

    @classmethod
    def get_instance(cls):
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def __init__(self):
        self.enable_to_static = True

    def enable(self, enable_to_static):
        self.enable_to_static = enable_to_static


def enable_to_static(flag=True):
    ProgramTranslator.get_instance().enable(flag)


# -- dy2static logging + traced-layer sheet ---------------------------------

_verbosity = 0
_code_level = 0


def set_verbosity(level=0, also_to_stdout=False):
    """paddle.jit.set_verbosity — dy2static transform logging level."""
    global _verbosity
    _verbosity = int(level)


def set_code_level(level=100, also_to_stdout=False):
    """paddle.jit.set_code_level — print transformed code at/below the
    given level."""
    global _code_level
    _code_level = int(level)


class TranslatedLayer:
    """paddle.jit.TranslatedLayer — the callable a jit.load returns
    (wraps a loaded inference Program + params; parity:
    fluid/dygraph/io.py TranslatedLayer)."""

    def __init__(self, program, feed_names, fetch_vars, scope=None):
        self._program = program
        self._feed_names = feed_names
        self._fetch = fetch_vars
        self._scope = scope

    def __call__(self, *args):
        from ..static.executor import Executor
        exe = Executor()
        feed = {n: (a.data if isinstance(a, Tensor) else a)
                for n, a in zip(self._feed_names, args)}
        outs = exe.run(self._program, feed=feed, fetch_list=self._fetch)
        outs = [Tensor(jnp.asarray(o)) for o in outs]
        return outs[0] if len(outs) == 1 else outs

    def eval(self):
        return self

    def train(self):
        raise NotImplementedError(
            "TranslatedLayer wraps an inference program; rebuild the "
            "dygraph Layer for training")


class TracedLayer:
    """paddle.jit.TracedLayer — trace a dygraph layer into a static
    program via to_static machinery (fluid/dygraph/jit.py). `trace`
    returns (outputs, traced) where traced(input...) replays the
    compiled function."""

    def __init__(self, fn, example_args):
        self._fn = fn
        self._compiled = jax.jit(fn)
        self._example = example_args

    @staticmethod
    def trace(layer, inputs):
        inputs = list(inputs)

        def fn(*arrs):
            outs = layer(*[Tensor(a) for a in arrs])
            if isinstance(outs, (list, tuple)):
                return [o.data for o in outs]
            return outs.data
        arrs = [i.data if isinstance(i, Tensor) else jnp.asarray(i)
                for i in inputs]
        traced = TracedLayer(fn, arrs)
        out = traced(*inputs)
        return out, traced

    def __call__(self, *args):
        arrs = [a.data if isinstance(a, Tensor) else jnp.asarray(a)
                for a in args]
        out = self._compiled(*arrs)
        if isinstance(out, (list, tuple)):
            outs = [Tensor(o) for o in out]
            return outs[0] if len(outs) == 1 else outs
        return Tensor(out)

    def save_inference_model(self, path, feed=None, fetch=None):
        raise NotImplementedError(
            "TracedLayer.save_inference_model: use paddle.jit.save / "
            "static.save_inference_model (StableHLO export) instead")
