"""Lazy op-fusion window — batch eager ops into ONE XLA dispatch.

Reference parity: the role of the generated `core.ops.*` fast paths
(pybind/op_function_generator.cc:519) — cutting per-op Python/dispatch
overhead on the eager path. Each eager op costs its own dispatch;
inside a

    with paddle.lazy_guard():
        ...   # N eager ops
    y.numpy()

window the ops record symbolically (shapes via jax.eval_shape) and
execute as one jitted program at the first materialization (window
exit, `.numpy()`, `float()`, printing) — N dispatches become 1.
Windows with the same op structure + shapes reuse the compiled program
(structural cache), so a repeated ad-hoc loop pays one compile.

Scope: a fusion window is a NO-GRAD region (the tape needs concrete
residuals); entering it disables grad recording for the window.
"""
import contextlib

import jax
import jax.numpy as jnp


class _LazyState:
    __slots__ = ('nodes', 'tensors', 'avals', 'consts', 'const_order')

    def __init__(self):
        self.nodes = []        # (name, fn, in_refs, kwargs, out_ids)
        self.tensors = {}      # out_id -> Tensor (lazy, awaiting data)
        self.avals = {}        # out_id -> ShapeDtypeStruct
        self.consts = {}       # const_id -> concrete array
        self.const_order = []


_STATE = None
_COMPILE_CACHE = {}
_CACHE_MAX = 256        # bound: value-bearing closures key by identity
                        # (can't share safely) and would otherwise grow
                        # one permanent entry per window


def active():
    return _STATE is not None


def record(name, fn, tensor_args, kwargs):
    """The run_op lazy hook: record the op symbolically, return lazy
    output Tensors carrying only shape/dtype."""
    from .tensor import Tensor
    st = _STATE
    in_refs = []
    in_avals = []
    for t in tensor_args:
        tid = id(t)
        if tid in st.tensors:                  # produced in this window
            in_refs.append(('v', tid))
            in_avals.append(st.avals[tid])
        else:                                  # concrete window input
            arr = t.data
            cid = id(arr)
            if cid not in st.consts:
                st.consts[cid] = arr
                st.const_order.append(cid)
            in_refs.append(('c', cid))
            in_avals.append(jax.ShapeDtypeStruct(arr.shape, arr.dtype))

    out_aval = jax.eval_shape(lambda *xs: fn(*xs, **kwargs), *in_avals)
    multi = isinstance(out_aval, (tuple, list))
    out_avals = list(out_aval) if multi else [out_aval]
    outs = []
    out_ids = []
    for av in out_avals:
        t = Tensor.__new__(Tensor)
        t._data = av                       # placeholder (shape/dtype ok)
        t.stop_gradient = True
        t.grad = None
        t._node = None
        t.name = None
        t.persistable = False
        t.is_distributed = False
        t._lazy = True
        outs.append(t)
        out_ids.append(id(t))
        st.tensors[id(t)] = t
        st.avals[id(t)] = av
    st.nodes.append((name, fn, tuple(in_refs), kwargs, tuple(out_ids)))
    return tuple(outs) if multi else outs[0]


def _val_fp(v):
    """Fingerprint one closed-over/default value; None = value-bearing
    (array) — the whole fn must fall back to identity keying."""
    if hasattr(v, 'shape') and hasattr(v, 'dtype'):
        return None
    if isinstance(v, (int, float, str, bool, bytes, type(None))):
        return ('lit', v)
    if isinstance(v, tuple):
        subs = tuple(_val_fp(x) for x in v)
        return None if any(s is None for s in subs) else ('tup', subs)
    if callable(v):
        return ('fn', _fn_key(v))
    return ('obj', id(v))


def _fn_key(fn):
    """Structural identity of an op fn. Many ops build a fresh closure
    per call over the same code object; keying on the code + a
    fingerprint of the closed-over cells AND default args (ops bake
    attributes as defaults) lets identical windows share the compiled
    program. Values holding arrays fall back to id(fn) — a cache hit
    would otherwise replay the OLD fn's baked-in array."""
    code = getattr(fn, '__code__', None)
    if code is None:
        return ('id', id(fn))
    parts = []
    for c in fn.__closure__ or ():
        try:
            v = c.cell_contents
        except ValueError:                      # empty cell
            parts.append(('empty',))
            continue
        fp = _val_fp(v)
        if fp is None:
            return ('id', id(fn))               # value-bearing closure
        parts.append(fp)
    for v in (fn.__defaults__ or ()):
        fp = _val_fp(v)
        if fp is None:
            return ('id', id(fn))
        parts.append(('def', fp))
    for k, v in sorted((fn.__kwdefaults__ or {}).items()):
        fp = _val_fp(v)
        if fp is None:
            return ('id', id(fn))
        parts.append(('kwdef', k, fp))
    return ('code', id(code), tuple(parts))


def _structural_key(st):
    """Cache key: op sequence + input shapes (NOT values)."""
    parts = []
    # canonical slot per const/value id
    slot = {cid: i for i, cid in enumerate(st.const_order)}
    vslot = {}
    for name, fn, in_refs, kwargs, out_ids in st.nodes:
        for oid in out_ids:
            vslot[oid] = len(vslot)
        ins = tuple((k, slot[r] if k == 'c' else vslot[r])
                    for k, r in in_refs)
        parts.append((name, _fn_key(fn), ins,
                      tuple(sorted((k, repr(v))
                                   for k, v in kwargs.items())),
                      len(out_ids)))
    shapes = tuple((tuple(st.consts[c].shape), str(st.consts[c].dtype))
                   for c in st.const_order)
    return (tuple(parts), shapes)


def flush():
    """Execute every recorded op as ONE jitted program and backfill the
    lazy tensors. The window (if still open) continues with fresh
    state."""
    global _STATE
    st = _STATE
    if st is None or not st.nodes:
        return
    out_ids_all = [oid for node in st.nodes for oid in node[4]]
    const_order = list(st.const_order)

    key = _structural_key(st)
    compiled = _COMPILE_CACHE.get(key)
    if compiled is None:
        # freeze the structure; a cache hit replays a DIFFERENT window
        # with the same structure, and results align positionally
        frozen = [(fn, in_refs, kwargs, out_ids)
                  for _, fn, in_refs, kwargs, out_ids in st.nodes]
        corder = tuple(const_order)

        def replay(consts):
            env = dict(zip(corder, consts))
            for fn, in_refs, kwargs, out_ids in frozen:
                args = [env[r] for _, r in in_refs]
                out = fn(*args, **kwargs)
                outs = list(out) if isinstance(out, (tuple, list)) \
                    else [out]
                for oid, o in zip(out_ids, outs):
                    env[oid] = o
            return [env[oid] for f in frozen for oid in f[3]]

        compiled = jax.jit(replay)
        if len(_COMPILE_CACHE) >= _CACHE_MAX:
            _COMPILE_CACHE.pop(next(iter(_COMPILE_CACHE)))
        _COMPILE_CACHE[key] = compiled

    # reset BEFORE backfilling so .data access does not re-enter
    _STATE = _LazyState()
    try:
        results = compiled([st.consts[c] for c in const_order])
    except Exception as e:
        # poison the window's tensors: reading them must error loudly,
        # not hand back a ShapeDtypeStruct placeholder
        for oid in out_ids_all:
            t = st.tensors[oid]
            t.__dict__.pop('_lazy', None)
            t._lazy_error = e
        raise
    for oid, arr in zip(out_ids_all, results):
        t = st.tensors[oid]
        t._data = arr
        if hasattr(t, '_lazy'):
            del t._lazy


@contextlib.contextmanager
def lazy_guard():
    """Fuse the eager ops issued inside this block into one XLA dispatch
    per materialization (no-grad region)."""
    from . import autograd
    global _STATE
    if _STATE is not None:
        yield                                  # nested: inert
        return
    _STATE = _LazyState()
    try:
        with autograd.no_grad():
            yield
            flush()
    finally:
        # materialize anything still pending even if the body raised
        try:
            flush()
        finally:
            _STATE = None
