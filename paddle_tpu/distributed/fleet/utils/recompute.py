"""Activation recompute (gradient checkpointing) + tuned remat policies.

Reference parity: fleet/utils/recompute.py RecomputeFunction(PyLayer):63 —
drop activations in forward, re-forward inside backward with saved RNG
state. TPU-native: `jax.checkpoint` (remat) IS this transform, applied at
trace level so XLA rematerializes inside the fused backward; the eager tape
path uses the PyLayer re-forward for parity semantics.

Policy layer (ISSUE 12, docs/performance.md#remat-policy): models tag
contraction outputs with `checkpoint_name` (`tag_tensor` below) and the
engines wrap their traced loss/block functions in `apply_policy`, so the
save/recompute split is TUNED instead of all-or-nothing (TPP
arXiv:2104.05755: contractions are worth saving, elementwise chains are
cheap to recompute). Named policies:

  * 'none'                — no remat; XLA keeps every residual live;
  * 'full'                — `jax.checkpoint` with the default policy:
                            save nothing, recompute everything in the
                            backward (the pre-ISSUE-12 use_remat=True);
  * 'attn_mlp_boundaries' — save ONLY the tagged contraction outputs
                            (qkv/attention-context/out-proj, fc1/fc2,
                            the attn/MLP boundary set, and the flash
                            kernel's own output and logsumexp so the
                            kernel never runs again); layernorm, GELU,
                            dropout joins, softmax internals and the
                            embedding gather recompute in the backward;
  * 'attn_mlp_lean'       — the same without `attn_out`: out_proj is
                            recomputed too (one [tokens, H] array a
                            layer cheaper; the pipeline engine's first
                            fall-back at pp=1);
  * 'dots'                — `jax.checkpoint_policies.dots_saveable`
                            (save every matmul output, tagged or not —
                            the stashing-1F1B engine default).

Resolution order (resolve_policy): explicit engine kwarg → the
`PTPU_REMAT_POLICY` env var → fleet strategy
`recompute_configs['policy']` (when `strategy.recompute` is enabled) →
the engine's own default. Remat is a pure scheduling transform: loss and
gradients are BIT-identical with any policy (tests/test_remat.py pins
this for all three engines).
"""
import os

import jax

from ....core import rng as rng_mod
from ....core.tensor import Tensor
from ....core.autograd import no_grad, grad_enabled
from ....autograd import PyLayer


class RecomputeFunction(PyLayer):
    """Parity: recompute.py:63."""

    @staticmethod
    def forward(ctx, run_function, preserve_rng_state, *args):
        ctx.run_function = run_function
        ctx.preserve_rng_state = preserve_rng_state
        if preserve_rng_state:
            ctx.fw_rng_state = rng_mod.get_rng_state()
        ctx.inputs = []
        ctx.tensor_indices = []
        tensor_inputs = []
        for i, arg in enumerate(args):
            if isinstance(arg, Tensor):
                tensor_inputs.append(arg)
                ctx.tensor_indices.append(i)
                ctx.inputs.append(None)
            else:
                ctx.inputs.append(arg)
        ctx.save_for_backward(*tensor_inputs)
        with no_grad():
            outputs = run_function(*args)
        return outputs

    @staticmethod
    def backward(ctx, *grads):
        from ....core import autograd as ag
        tensors = ctx.saved_tensor()
        inputs = list(ctx.inputs)
        detached = []
        for idx, t in zip(ctx.tensor_indices, tensors):
            d = Tensor(t.data, stop_gradient=t.stop_gradient)
            inputs[idx] = d
            detached.append(d)

        saved_rng = None
        if ctx.preserve_rng_state:
            saved_rng = rng_mod.get_rng_state()
            rng_mod.set_rng_state(ctx.fw_rng_state)
        try:
            # PyLayer.apply calls backward under no_grad; the re-forward
            # must build a tape, and parameter grads must accumulate into
            # .grad (accumulate_leaves) — the whole point of recompute.
            with ag.enable_grad():
                outputs = ctx.run_function(*inputs)
        finally:
            if saved_rng is not None:
                rng_mod.set_rng_state(saved_rng)

        outs = outputs if isinstance(outputs, (tuple, list)) else [outputs]
        outs = [o for o in outs if isinstance(o, Tensor)]
        gts = list(grads)[:len(outs)]
        cap = {id(d): None for d in detached if not d.stop_gradient}
        ag.backward(list(outs), gts, retain_graph=False, capture=cap,
                    accumulate_leaves=True)
        return tuple(Tensor(cap[id(d)]) if cap.get(id(d)) is not None
                     else None for d in detached)


def recompute(function, *args, **kwargs):
    """Parity: paddle.distributed.fleet.utils.recompute."""
    preserve = kwargs.pop('preserve_rng_state', True)
    use_reentrant = kwargs.pop('use_reentrant', True)
    if not grad_enabled():
        return function(*args, **kwargs)
    return _recompute_eager(function, preserve, *args)


def _recompute_eager(function, preserve, *args):
    from ....core import autograd as ag

    ctx = {}
    tensor_args = [a for a in args if isinstance(a, Tensor)]
    needs = [not t.stop_gradient for t in tensor_args]
    fw_rng = rng_mod.get_rng_state() if preserve else None
    with no_grad():
        outputs = function(*args)
    multi = isinstance(outputs, (tuple, list))
    outs = list(outputs) if multi else [outputs]

    if not any(needs):
        return outputs

    def vjp_fn(cts):
        cts_list = list(cts) if isinstance(cts, tuple) else [cts]
        detached = []
        new_args = []
        for a in args:
            if isinstance(a, Tensor):
                d = Tensor(a.data, stop_gradient=a.stop_gradient)
                detached.append(d)
                new_args.append(d)
            else:
                new_args.append(a)
        saved = rng_mod.get_rng_state()
        if fw_rng is not None:
            rng_mod.set_rng_state(fw_rng)
        try:
            with ag.enable_grad():
                re_out = function(*new_args)
        finally:
            rng_mod.set_rng_state(saved)
        re_outs = list(re_out) if isinstance(re_out, (tuple, list)) \
            else [re_out]
        cap = {id(d): None for d in detached if not d.stop_gradient}
        ag.backward(re_outs, [Tensor(c) for c in cts_list], capture=cap,
                    accumulate_leaves=True)
        result = []
        for d in detached:
            g = cap.get(id(d))
            result.append(g)
        return result

    detached_outs = [Tensor(o.data, stop_gradient=False) for o in outs]
    ag.record('recompute', vjp_fn, tensor_args, needs, detached_outs)
    return tuple(detached_outs) if multi else detached_outs[0]


def recompute_jax(function):
    """The trace-level transform: jax.checkpoint / remat for jitted steps —
    the preferred TPU path (XLA rematerializes inside the fused backward)."""
    return jax.checkpoint(function)


# ---------------------------------------------------------------------------
# remat policy layer (ISSUE 12)
# ---------------------------------------------------------------------------

# checkpoint_name tags the models emit at contraction boundaries. The
# attn_mlp_boundaries policy saves exactly these; anything else is
# recomputed in the backward (TPP: cheap elementwise loops re-fuse).
# `flash_o` / `flash_lse` are the flash-attention forward rule's own
# residuals (ops/pallas/flash_attention.py): outputs of a pallas_call,
# so unnamed they would be recomputed — the whole kernel — although the
# context they hold is saved. Where the kernel runs, the model leaves
# `attn_ctx` (the same values, re-laid) untagged: saved once.
BOUNDARY_NAMES = ('attn_qkv', 'attn_ctx', 'attn_out',
                  'mlp_fc1', 'mlp_out', 'embed_out',
                  'flash_o', 'flash_lse')

POLICY_NAMES = ('none', 'full', 'attn_mlp_boundaries', 'attn_mlp_lean',
                'dots')

# richest first: what `use_remat=True` alone tries at pp=1 in the
# pipeline engine, each only if the one before does not fit
FIT_ORDER = ('attn_mlp_boundaries', 'attn_mlp_lean', 'full')


def checkpoint_policy(name):
    """(remat_on, jax_policy_or_None) for a named policy."""
    if name in (None, 'none', False):
        return False, None
    if name in ('full', True):
        return True, None
    if name == 'attn_mlp_boundaries':
        return True, jax.checkpoint_policies.save_only_these_names(
            *BOUNDARY_NAMES)
    if name == 'attn_mlp_lean':
        return True, jax.checkpoint_policies.save_only_these_names(
            *(n for n in BOUNDARY_NAMES if n != 'attn_out'))
    if name == 'dots':
        pol = getattr(jax.checkpoint_policies, 'dots_saveable', None) \
            or jax.checkpoint_policies.checkpoint_dots
        return True, pol
    raise ValueError(
        f"unknown remat policy {name!r}; expected one of {POLICY_NAMES}")


def resolve_policy(policy=None, default='none'):
    """Resolve the remat policy: engine kwarg -> PTPU_REMAT_POLICY env ->
    fleet strategy recompute_configs['policy'] (when strategy.recompute
    is on) -> `default`. Returns the policy NAME (validated) — or None
    when `default` is None and nothing was specified anywhere (the
    engine keeps its own legacy behavior, e.g. the stashing 1F1B's
    save-dots split)."""
    if policy is None:
        v = os.environ.get('PTPU_REMAT_POLICY')
        if v:
            policy = v
    if policy is None:
        try:
            from .. import fleet as _fleet_mod
            strategy = _fleet_mod._user_defined_strategy
            if strategy is not None and strategy.recompute:
                policy = (strategy.recompute_configs or {}).get('policy')
        except Exception:
            policy = None
    if policy is None:
        policy = default
    if policy is None:
        return None
    if policy is True:
        policy = 'full'
    if policy is False:
        policy = 'none'
    checkpoint_policy(policy)   # validate early, not at first dispatch
    return policy


def apply_policy(fn, policy, engine=None):
    """Wrap a traced function in `jax.checkpoint` per the named policy
    ('none' returns fn unchanged) and publish the decision gauge."""
    name = policy if isinstance(policy, str) else (
        'full' if policy else 'none')
    on, jax_policy = checkpoint_policy(name)
    if engine is not None:
        _publish_policy(engine, name)
    if not on:
        return fn
    if jax_policy is None:
        return jax.checkpoint(fn)
    return jax.checkpoint(fn, policy=jax_policy)


def tag(x, name):
    """`checkpoint_name` on a raw array (trace-time identity; counted so
    the bench can report how many boundaries a trace carries)."""
    from jax.ad_checkpoint import checkpoint_name
    _count_boundary(name)
    return checkpoint_name(x, name)


def tag_tensor(t, name):
    """`checkpoint_name` on a Tensor through the op tape (the transform
    is an identity with a trivial vjp, so the eager path is a no-op
    passthrough and the traced path carries the name)."""
    from ....core.autograd import run_op
    from jax.ad_checkpoint import checkpoint_name
    _count_boundary(name)
    return run_op('checkpoint_name',
                  lambda a: checkpoint_name(a, name), [t])


def _count_boundary(name):
    try:
        from ....core.monitor import counter
        counter('ptpu_remat_boundaries_total',
                help='checkpoint_name boundary tags applied (trace-time), '
                     'by tag name',
                labelnames=('name',)).inc(1, name=name)
    except Exception:
        pass


def _publish_policy(engine, policy):
    try:
        from ....core.monitor import gauge
        g = gauge('ptpu_remat_policy_info',
                  help='active remat policy per engine (value 1; the '
                       'policy rides in the label)',
                  labelnames=('engine', 'policy'))
        # zero the engine's OTHER policy series so a rebuilt engine
        # (e.g. an in-process policy sweep) never leaves a stale series
        # that snapshot() could misreport as active
        for other in POLICY_NAMES:
            if other != policy:
                g.set(0, engine=engine, policy=other)
        g.set(1, engine=engine, policy=policy)
    except Exception:
        pass


_HELD = (('saved_boundary_bytes', 'ptpu_remat_saved_boundary_bytes',
          'bytes of pullback residuals the compiled step holds for the '
          'backward under its remat policy (trace-time reckoning)'),
         ('grad_tree_bytes', 'ptpu_remat_grad_tree_bytes',
          'bytes of the fresh gradient tree a backward returns beside '
          'the accumulation buffer (0: added where they are made)'))


def publish_held(engine, **held):
    """The two byte counts of an engine's trace-time reckoning
    (saved_boundary_bytes=, grad_tree_bytes=) as gauges."""
    try:
        from ....core.monitor import gauge
        for key, name, help_ in _HELD:
            gauge(name, help=help_, labelnames=('engine',)).set(
                float(held[key]), engine=engine)
    except Exception:
        pass


def held_snapshot(engine):
    """{saved_boundary_bytes, grad_tree_bytes} of `engine` as published
    (None each where it has not)."""
    from ....core import monitor as _m
    out = {}
    for key, name, _ in _HELD:
        m = _m.metrics().get(name)
        vals = [child.value() for labels, child in m._series().items()
                if labels and labels[0] == engine] if m is not None else []
        out[key] = int(vals[0]) if vals else None
    return out


def boundary_counts():
    """{tag name: trace-time count} from the monitor counter."""
    try:
        from ....core import monitor as _m
        m = _m.metrics().get('ptpu_remat_boundaries_total')
        if m is None:
            return {}
        return {labels[0] if labels else '': int(child.value())
                for labels, child in m._series().items()}
    except Exception:
        return {}


def snapshot():
    """StepTelemetry.snapshot()['remat'] payload: active policies per
    engine + the boundary-tag counts (None when nothing recorded)."""
    try:
        from ....core import monitor as _m
        reg = _m.metrics()
        policies = {}
        g = reg.get('ptpu_remat_policy_info')
        if g is not None:
            for labels, child in g._series().items():
                if child.value():
                    policies[labels[0]] = labels[1]
        bounds = boundary_counts()
        if not policies and not bounds:
            return None
        return {'policies': policies, 'boundaries': bounds,
                'boundary_total': int(sum(bounds.values())),
                **held_snapshot('pipeline')}
    except Exception:
        return None
