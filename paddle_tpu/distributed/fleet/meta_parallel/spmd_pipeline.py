"""SPMD pipeline-parallel engine (dp × pp × mp in ONE compiled program).

Reference parity: the semantics of PipelineTrainer/SectionWorker
(section_worker.cc:104-185 — microbatch schedules), PipelineParallel
.train_batch (pipeline_parallel.py:114 — F-then-B over microbatches with p2p
sends), 1F1B's steady-state utilization, gradient accumulation over
microbatches (optimizer.py _accumulate_gradients:4974), and tied-weight grad
sync (A.4 allreduce_shared_weight_gradients).

TPU-native design (no host round-trips per microbatch — SURVEY.md §7 hard
part (a)):
  * every stage's transformer blocks are ONE stacked parameter pytree
    [num_layers, ...] sharded over the 'pp' mesh axis → each device holds its
    stage's [layers_per_stage, ...] slice and runs them with a local
    `lax.scan` (weight-stationary);
  * the microbatch clock is a `lax.scan` over A + P - 1 ticks; activations
    move between neighbor stages with `lax.ppermute` over ICI — the
    CollectivePermute replacement for send_v2/recv_v2 NCCL pairs;
  * stage-dependent behavior (ingest on stage 0, loss on last stage) is
    `jnp.where` masking — SPMD-uniform code, XLA-friendly;
  * three schedules: '1F1B' (default) and 'F-then-B' match
    section_worker.cc:134-185's schedule_mode pair — '1F1B'
    hand-interleaves one forward + one backward sub-step per tick with a
    circular O(pp) stage-input buffer and per-tick local `jax.vjp` (see
    _build_1f1b); 'F-then-B' takes `jax.grad` through the whole tick
    scan — scan transposition yields the reverse pipeline automatically,
    at O(A) boundary-activation cost — with `jax.checkpoint` on the
    block fn for activation recompute; 'interleaved' is the Megatron
    virtual-stage schedule (arXiv:2104.04473): each physical stage holds
    `virtual_stages` model chunks split round-robin, so every masked
    warm-up/drain tick burns 1/v of a stage and the bubble shrinks
    ~1/v (see _build_interleaved + schedule_model);
  * embedding/head weights are replicated over 'pp'; their grads get
    psum('pp') — exactly allreduce_shared_weight_gradients;
  * dp grad sync = pmean over 'dp'; mp collectives run inside blocks.
"""
import functools
import os

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P, NamedSharding
from jax import shard_map

from ....core import rng as rng_mod
from ....core import autograd
from ....core import async_step as A_
from ....core import bucketing as B
from ....core.tensor import Tensor
from ....jit import bind_arrays
from ... import collective as C
from ... import topology_runtime


def _spec_for(p, axes, extra_leading_pp=False):
    nd = len(p.data.shape) + (1 if extra_leading_pp else 0)
    spec = [None] * nd
    if extra_leading_pp:
        spec[0] = 'pp'
    if getattr(p, 'is_distributed', False) and 'mp' in axes:
        spec[p.split_axis + (1 if extra_leading_pp else 0)] = 'mp'
    return P(*spec)


def _tree_bytes(tree):
    return sum(int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize
               for a in jax.tree_util.tree_leaves(tree))


def _device_bytes_limit(device):
    """The bytes the device's allocator may hand out (None where the
    backend does not say, as the CPU's)."""
    try:
        return int(device.memory_stats()['bytes_limit'])
    except Exception:
        return None


class PipelineScheduleError(ValueError):
    """A pipeline-schedule configuration the engine cannot honor
    (layer/chunk divisibility, virtual stages on a schedule without
    them, accumulate_steps not forming whole microbatch groups)."""


class PipelineBatchError(ValueError):
    """A batch whose shape cannot be microbatched by the engine
    (size not divisible by dp x accumulate_steps, or an input/label
    leading-dimension mismatch)."""


def resolve_virtual_stages(virtual_stages=None, from_layer=None):
    """Virtual-stage count resolution (docs/performance.md
    #pipeline-schedules): explicit kwarg -> PTPU_PP_VIRTUAL env ->
    PipelineLayer(num_virtual_pipeline_stages=) -> None (unset)."""
    if virtual_stages is not None:
        return int(virtual_stages)
    env = os.environ.get('PTPU_PP_VIRTUAL')
    if env:
        try:
            return int(env)
        except ValueError:
            raise PipelineScheduleError(
                f"PTPU_PP_VIRTUAL={env!r} is not an integer")
    if from_layer is not None:
        return int(from_layer)
    return None


def chunk_layer_order(num_layers, pp, virtual_stages):
    """Round-robin layer -> (stage, chunk) assignment (arXiv:2104.04473
    interleaved schedule): global model chunk g = c*pp + s holds layers
    [g*per, (g+1)*per) with per = num_layers/(pp*v). Returns the
    STACKING order: row i of the [num_layers, ...] stacked block tree
    holds original layer order[i], so the P('pp') shard of device s is
    exactly its v chunks, chunk-major. Identity when v == 1."""
    pp = max(int(pp), 1)
    v = max(int(virtual_stages or 1), 1)
    if num_layers % (pp * v) or num_layers < pp * v:
        raise PipelineScheduleError(
            f"{num_layers} layers cannot split round-robin into "
            f"pp({pp}) x virtual_stages({v}) = {pp * v} non-empty "
            f"chunks; pick num_layers divisible by pp*virtual_stages "
            f"(PipelineLayer(num_virtual_pipeline_stages=) / "
            f"virtual_stages= / PTPU_PP_VIRTUAL)")
    per = num_layers // (pp * v)
    return [(c * pp + s) * per + i
            for s in range(pp) for c in range(v) for i in range(per)]


def _sim_inflight(pp, A, v):
    """Walk the interleaved-1F1B tick table: per-chunk residual slots
    needed (closed write..read interval, the same-tick write-then-read
    counts as live) and the peak in-flight microbatch count per device.
    v=1 reproduces the classic 1F1B window min(A, 2*pp-1). Each
    chunk's live set is a contiguous ascending-m window (both job
    streams are monotone in m), so a two-pointer per chunk plus one
    event sweep per stage does it in O(pp * (A*v + T))."""
    ppv = pp * v
    D = 2 * (pp - 1) + (v - 1) * pp
    T = A * v + D

    slots = 1
    peak = 0
    for s in range(pp):
        def t_fwd(c, m):
            r, q = divmod(m, pp)
            return s + r * ppv + c * pp + q

        def t_bwd(c, m):
            r, q = divmod(m, pp)
            return (D - s) + r * ppv + (v - 1 - c) * pp + q

        delta = [0] * (T + 2)
        for c in range(v):
            m0 = 0
            for m in range(A):
                delta[t_fwd(c, m)] += 1
                delta[t_bwd(c, m) + 1] -= 1
                while t_bwd(c, m0) < t_fwd(c, m):
                    m0 += 1
                slots = max(slots, m - m0 + 1)
        live = 0
        for d in delta:
            live += d
            peak = max(peak, live)
    return slots, peak


def schedule_model(schedule, pp, accumulate_steps, virtual_stages=1,
                   memory_mode=None):
    """Static schedule model of ONE compiled pipeline step: tick count,
    executed chunk sub-steps per device, and the modeled bubble
    fraction (masked warm-up/drain work as a fraction of executed
    work). One tick = one chunk forward + one chunk backward sub-step
    per stage in lockstep; a chunk is 1/v of a stage, so interleaving
    shrinks the (pp-1)-tick ramp cost by ~1/v (arXiv:2104.04473):

        bubble_fraction = (pp - 1) / (A*v + pp - 1)

    The forward/backward cond windows in the compiled scan match
    fwd_window/bwd_window exactly; ticks is the lax.scan length."""
    if schedule in ('FThenB', 'F-then-B'):
        schedule = 'F-then-B'
    pp = max(int(pp), 1)
    A = int(accumulate_steps)
    v = max(int(virtual_stages or 1), 1) if schedule == 'interleaved' \
        else 1
    if schedule == 'F-then-B':
        ticks = A + pp - 1          # fwd scan; bwd is its transposition
        warmup = pp - 1
        fwd_w = bwd_w = A + pp - 1
        slots, peak = A, A          # O(A) boundary activations stored
    else:                           # '1F1B' / 'interleaved'
        D = 2 * (pp - 1) + (v - 1) * pp
        ticks = A * v + D
        warmup = D - (pp - 1)       # ticks before the first bwd anywhere
        fwd_w = bwd_w = A * v + pp - 1
        slots, peak = _sim_inflight(pp, A, v)
        slots = min(slots, A)
    useful = 2 * A * v
    chunk_ticks = fwd_w + bwd_w
    model = {
        'schedule': schedule,
        'pp': pp,
        'virtual_stages': v,
        'accumulate_steps': A,
        'ticks': ticks,
        'warmup_ticks': warmup,
        'fwd_window': fwd_w,
        'bwd_window': bwd_w,
        'chunk_ticks': chunk_ticks,
        'useful_chunk_ticks': useful,
        'bubble_fraction': 1.0 - useful / chunk_ticks,
        'inflight_peak': peak,
        'slots_per_chunk': slots,
        # wire-traffic model: two lax.ppermute ring hops per tick (act
        # +1, cotangent -1) — interleaving trades ~v x more boundary
        # crossings for the 1/v ramp (docs/performance.md
        # #pipeline-schedules)
        'ppermute_steps': 2 * ticks if pp > 1 else 0,
    }
    if memory_mode is not None:
        model['memory_mode'] = memory_mode
    return model


def publish_schedule_gauges(model, engine='pipeline'):
    """ptpu_pp_* gauges from a schedule_model() dict through
    core.monitor — StepTelemetry.snapshot()['pipeline'] and
    `tools/health_dump.py pp` read these back."""
    try:
        from ....core.monitor import gauge
    except Exception:
        return
    lbl = {'engine': engine}
    for name, key, help_ in (
            ('ptpu_pp_ticks', 'ticks', 'pipeline scan ticks per step'),
            ('ptpu_pp_chunk_ticks', 'chunk_ticks',
             'executed chunk fwd+bwd sub-steps per device per step'),
            ('ptpu_pp_useful_chunk_ticks', 'useful_chunk_ticks',
             'unmasked chunk sub-steps per device per step'),
            ('ptpu_pp_bubble_fraction', 'bubble_fraction',
             'modeled masked-work fraction of the schedule'),
            ('ptpu_pp_inflight_peak', 'inflight_peak',
             'peak in-flight microbatches per device'),
            ('ptpu_pp_virtual_stages', 'virtual_stages',
             'model chunks per physical stage (v)'),
            ('ptpu_pp_stages', 'pp', 'pipeline-parallel degree'),
            ('ptpu_pp_accumulate_steps', 'accumulate_steps',
             'microbatches per step (A)')):
        gauge(name, help=help_, labelnames=('engine',)).set(
            float(model[key]), **lbl)
    g = gauge('ptpu_pp_schedule_info',
              help='active pipeline schedule (value 1; the schedule '
                   'rides in the label)',
              labelnames=('engine', 'schedule'))
    for other in ('1F1B', 'F-then-B', 'interleaved'):
        g.set(1 if other == model['schedule'] else 0,
              engine=engine, schedule=other)


def pipeline_snapshot(engine='pipeline'):
    """StepTelemetry.snapshot()['pipeline'] payload: the published
    schedule census read back from the ptpu_pp_* gauges (None when no
    pipeline engine has been built)."""
    try:
        from ....core import monitor as _m
        reg = _m.metrics()
        if reg.get('ptpu_pp_ticks') is None:
            return None

        def val(name):
            m = reg.get(name)
            if m is None:
                return None
            for labels, child in m._series().items():
                if labels and labels[0] == engine:
                    return child.value()
            return None

        snap = {
            'ticks': int(val('ptpu_pp_ticks') or 0),
            'chunk_ticks': int(val('ptpu_pp_chunk_ticks') or 0),
            'useful_chunk_ticks':
                int(val('ptpu_pp_useful_chunk_ticks') or 0),
            'bubble_fraction': val('ptpu_pp_bubble_fraction'),
            'inflight_peak': int(val('ptpu_pp_inflight_peak') or 0),
            'virtual_stages': int(val('ptpu_pp_virtual_stages') or 1),
            'pp': int(val('ptpu_pp_stages') or 1),
            'accumulate_steps':
                int(val('ptpu_pp_accumulate_steps') or 0),
        }
        info = reg.get('ptpu_pp_schedule_info')
        if info is not None:
            for labels, child in info._series().items():
                if labels and labels[0] == engine and child.value():
                    snap['schedule'] = labels[1]
        # what the compiled step holds for its backward (the remat
        # reckoning: docs/performance.md#remat-policy)
        from ..utils.recompute import held_snapshot
        snap.update(held_snapshot(engine))
        return snap
    except Exception:
        return None


from ....nn.layer.base import Layer as _Layer
from ....nn.layer.container import LayerList as _LayerList


class _FnLayer(_Layer):
    """Parameterless adapter for plain-callable pipeline descs."""

    def __init__(self, fn):
        super().__init__()
        self._fn = fn

    def forward(self, *args):
        return self._fn(*args)


class _HeadWrapper(_Layer):
    """Adapts (tail layers + loss_fn) into the engine's head(hidden, labels)
    contract. A loss_fn that owns trainable parameters must itself be an
    nn.Layer (so the engine can lift them); a plain closure capturing
    parameters would silently bake them as compile-time constants."""

    def __init__(self, tail_layers, loss_fn):
        super().__init__()
        self.tail = _LayerList([
            t if isinstance(t, _Layer) else _FnLayer(t)
            for t in tail_layers])
        if isinstance(loss_fn, _Layer):
            self.loss_layer = loss_fn
            self._loss_call = loss_fn
        else:
            self._loss_call = loss_fn

    def forward(self, hidden, labels):
        x = hidden
        for layer in self.tail:
            x = layer(x)
        return self._loss_call(x, labels)


def engine_from_pipeline_layer(pipeline_layer, optimizer, accumulate_steps,
                               mesh=None, use_remat=True, schedule='1F1B',
                               remat_policy=None, virtual_stages=None):
    """Build a SpmdPipelineEngine from a PipelineLayer's descs (parity: the
    dygraph PipelineParallel engine construction from pp_layers).

    Convention: desc[0] is the embedding/input stage, the trailing
    non-uniform descs (e.g. final norm) plus the PipelineLayer's loss_fn
    form the head, and the uniform middle run becomes the stacked blocks.

    `PipelineLayer(num_virtual_pipeline_stages=)` is honored here: a
    value > 1 (or virtual_stages=/PTPU_PP_VIRTUAL) selects the
    interleaved schedule; values the uniform block run cannot split
    into pp*v non-empty chunks raise PipelineScheduleError.
    """
    funcs, shared = pipeline_layer.build_full_model()
    if pipeline_layer._loss_fn is None:
        raise ValueError("PipelineLayer needs loss_fn for SPMD training")
    if len(funcs) < 2:
        raise ValueError("pipeline model too small to split: need "
                         "embed + blocks")
    # Tied weights across segments would silently untie here (embed and head
    # trees get independent arrays) — refuse rather than train a wrong
    # parameterization. Untied heads (GPTLMHead pattern) are the supported
    # shape; single-segment sharing is fine.
    uses = {}
    for f in funcs:
        for key, layer in shared.items():
            if f is layer or getattr(f, 'func', None) is layer \
                    or getattr(f, '__self__', None) is layer:
                uses[key] = uses.get(key, 0) + 1
    multi = [k for k, c in uses.items() if c > 1]
    if multi:
        raise NotImplementedError(
            f"SharedLayerDesc keys {multi} are used by multiple pipeline "
            "segments; cross-stage weight tying is not supported by the "
            "SPMD pipeline engine yet — use an untied head "
            "(e.g. models.gpt.GPTLMHead / build_gpt_pipeline)")

    embed = funcs[0]

    def sig(layer):
        if not hasattr(layer, 'named_parameters'):
            return None
        return tuple(sorted((n, tuple(p.shape))
                            for n, p in layer.named_parameters())) or None

    # find the maximal uniform run starting at funcs[1]
    base = sig(funcs[1]) if len(funcs) > 1 else None
    if base is None:
        raise ValueError("desc[1] must be the first transformer block (a "
                         "Layer with parameters); got "
                         f"{type(funcs[1]).__name__}")
    end = 1
    while end < len(funcs) and sig(funcs[end]) == base:
        end += 1
    blocks = funcs[1:end]
    tail = funcs[end:]
    head = _HeadWrapper(tail, pipeline_layer._loss_fn)
    # honor the PipelineLayer's recompute_interval: a nonzero interval is
    # the dygraph-parity opt-in for activation recompute, so it forces
    # remat ON for the compiled engine (the trace-level twin of wrapping
    # every k-th layer in fleet.utils.recompute) — the resolved policy
    # then decides what is saved vs recomputed
    if getattr(pipeline_layer, '_recompute_interval', 0):
        use_remat = True
    # wire the long-silently-ignored num_virtual_pipeline_stages
    # (kwarg -> PTPU_PP_VIRTUAL -> the PipelineLayer's own value); the
    # engine validates divisibility and schedule compatibility
    v = resolve_virtual_stages(
        virtual_stages,
        from_layer=getattr(pipeline_layer,
                           '_num_virtual_pipeline_stages', None))
    return SpmdPipelineEngine(embed, blocks, head, optimizer,
                              accumulate_steps, mesh=mesh,
                              use_remat=use_remat, schedule=schedule,
                              remat_policy=remat_policy,
                              virtual_stages=v)


from .meta_parallel_base import EngineTeardown


class SpmdPipelineEngine(A_.AsyncDispatchMixin, EngineTeardown):
    """Pipelined hybrid train step.

    Args:
      embed: Layer mapping (input_ids) -> activations [mb, L, H]; params
        replicated over pp (tied-weight psum applies).
      blocks: list of num_layers structurally-identical Layers.
      head: Layer mapping (activations, labels) -> per-microbatch scalar
        loss (final norm + LM head + criterion).
      optimizer: paddle_tpu Optimizer (functional update rules reused).
      accumulate_steps: number of microbatches A.
    """

    def __init__(self, embed, blocks, head, optimizer, accumulate_steps,
                 mesh=None, use_remat=True, schedule='1F1B',
                 grad_accum_dtype='float32', memory_mode='stash',
                 use_buckets=None, comm_dtype=None, bucket_mb=None,
                 comm_block=None, comm_overlap=None, prefetch_depth=None,
                 comm_chunk=None, remat_policy=None,
                 dispatch_window=None, device_lr=None,
                 virtual_stages=None):
        self.embed = embed
        self.blocks = blocks
        self.head = head
        self.optimizer = optimizer
        self.A = accumulate_steps
        # tuned remat (docs/performance.md#remat-policy): a resolved
        # policy (kwarg -> PTPU_REMAT_POLICY -> strategy) overrides what
        # `use_remat=True` alone picks: the schedule's split (full remat
        # / save-dots) in _make_stage_forward, and at pp=1 under 1F1B
        # the richest policy that fits the device (_fit_remat)
        from ..utils.recompute import resolve_policy as _resolve_remat
        self._remat_policy = _resolve_remat(remat_policy,
                                                       default=None)
        if self._remat_policy is not None:
            use_remat = self._remat_policy != 'none'
        self.use_remat = use_remat
        # 1F1B backward source: 'stash' (default) keeps each in-flight
        # microbatch's vjp residuals — the reference SectionWorker's
        # store-activations schedule (section_worker.cc:147-184) — so
        # backward never re-runs the stage forward; 'recompute' keeps only
        # the stage INPUT per in-flight microbatch and re-derives the
        # residuals inside the backward tick (lower memory, +1 fwd FLOPs).
        if memory_mode not in ('stash', 'recompute'):
            raise ValueError(f"memory_mode must be 'stash' or 'recompute', "
                             f"got {memory_mode!r}")
        self.memory_mode = memory_mode
        # 1F1B microbatch-grad accumulator dtype: float32 (default) or
        # 'param' to accumulate in the parameter dtype — halves the
        # accumulator HBM for bf16 models when memory-bound (single-chip
        # 1.3B); fine for small accumulate_steps
        self.grad_accum_dtype = grad_accum_dtype
        if schedule in ('FThenB', 'F-then-B'):
            schedule = 'F-then-B'
        elif schedule not in ('1F1B', 'interleaved'):
            raise ValueError(f"unknown pipeline schedule {schedule!r}; "
                             "expected '1F1B', 'F-then-B' or "
                             "'interleaved'")
        # virtual stages (arXiv:2104.04473 interleaved schedule):
        # kwarg -> PTPU_PP_VIRTUAL -> PipelineLayer wiring (via
        # engine_from_pipeline_layer). v > 1 upgrades the default 1F1B
        # to 'interleaved'; F-then-B has no virtual-stage formulation.
        vv = resolve_virtual_stages(virtual_stages)
        if vv is not None and vv < 1:
            raise PipelineScheduleError(
                f"virtual_stages must be >= 1, got {vv}")
        if schedule == 'interleaved':
            self.vp = vv if vv is not None else 2
        elif vv is not None and vv > 1:
            if schedule == 'F-then-B':
                raise PipelineScheduleError(
                    f"schedule 'F-then-B' cannot honor virtual_stages="
                    f"{vv} (num_virtual_pipeline_stages/PTPU_PP_VIRTUAL"
                    "); use schedule='interleaved' or '1F1B'")
            schedule = 'interleaved'
            self.vp = vv
        else:
            self.vp = 1
        self.schedule = schedule
        self._use_scaling = False     # fp16 GradScaler path (compile-time)
        self.mesh = mesh if mesh is not None else topology_runtime.get_mesh()
        if self.mesh is None:
            raise ValueError("no mesh registered")
        self.axes = tuple(self.mesh.axis_names)
        self.pp = self.mesh.shape.get('pp', 1)
        self.dp = self.mesh.shape.get('dp', 1)
        # stacking order: row i of the stacked [L, ...] block trees
        # holds blocks[self._layer_order[i]] — identity for 1F1B /
        # F-then-B, round-robin chunk-major for interleaved so each
        # P('pp') shard is its stage's v chunks back to back. Raises
        # PipelineScheduleError (naming the knobs) when the layers
        # cannot split into pp*v non-empty chunks.
        self._layer_order = chunk_layer_order(
            len(blocks), self.pp, self.vp)
        if self.vp > 1 and accumulate_steps % max(self.pp, 1):
            raise PipelineScheduleError(
                f"interleaved schedule needs accumulate_steps("
                f"{accumulate_steps}) divisible by pp("
                f"{max(self.pp, 1)}): microbatches advance in groups "
                "of pp per model chunk (arXiv:2104.04473)")
        # static schedule model + census (ptpu_pp_* gauges ->
        # StepTelemetry.snapshot()['pipeline'], health_dump pp): the
        # compiled scan's tick count and cond windows follow this model
        # exactly, so the bubble shrink is a measured number
        self._sched_model = schedule_model(
            self.schedule, self.pp, self.A, self.vp,
            memory_mode=memory_mode)
        publish_schedule_gauges(self._sched_model, engine='pipeline')

        # -- parameter pytrees ------------------------------------------------
        self._embed_named = [(n, p) for n, p in embed.named_parameters()
                             if not p.stop_gradient]
        self._head_named = [(n, p) for n, p in head.named_parameters()
                            if not p.stop_gradient]
        self._block_named = [(n, p) for n, p in blocks[0].named_parameters()
                             if not p.stop_gradient]

        embed_specs = {n: _spec_for(p, self.axes)
                       for n, p in self._embed_named}
        head_specs = {n: _spec_for(p, self.axes)
                      for n, p in self._head_named}
        block_specs = {n: _spec_for(p, self.axes, extra_leading_pp=True)
                       for n, p in self._block_named}

        from ....core import memory as _mem
        with _mem.phase('engine.init'):
            stacked = {}
            for n, p0 in self._block_named:
                per_layer = []
                for j in self._layer_order:
                    per_layer.append(
                        dict(blocks[j].named_parameters())[n].data)
                stacked[n] = jnp.stack(per_layer, axis=0)  # [L, ...]

            self._specs = {'embed': embed_specs, 'blocks': block_specs,
                           'head': head_specs}
            self._params = {
                'embed': {n: self._place(p.data, embed_specs[n])
                          for n, p in self._embed_named},
                'blocks': {n: self._place(stacked[n], block_specs[n])
                           for n, p0 in self._block_named},
                'head': {n: self._place(p.data, head_specs[n])
                         for n, p in self._head_named},
            }
            # shapes snapshot for taps meta (overlap mode later moves
            # bucketed slots out of the group trees)
            self._tap_shapes = {
                f'{grp}/{n}': (tuple(a.shape), a.dtype)
                for grp in ('embed', 'blocks', 'head')
                for n, a in self._params[grp].items()}

            # -- bucketed rs/ag weight-update sharding over 'dp'
            # (arXiv:2004.13336): grads coalesce into flat buckets, each
            # dp rank owns a 1/dp shard of params+moments. Blocks are
            # stage-LOCAL (their buckets key separately and their flat
            # states carry a leading pp dim); mp-sharded params keep the
            # per-param path.
            self.comm_dtype, self._bucket_bytes = B.resolve_comm_config(
                comm_dtype, bucket_mb)
            self._comm_block = B.resolve_comm_block(comm_block)
            # comm/compute overlap (ISSUE 10): deferred/prefetched param
            # all-gather + chunked collectives over 'dp' (the pipeline's
            # grads only complete at scan end, so the eager-rs leg of
            # the overlap story is the hybrid engine's; here the win is
            # the gather moved under the next step's forward + the
            # sharded resident param set)
            overlap_req, self._prefetch_depth, self._comm_chunk = \
                B.resolve_overlap_config(comm_overlap, prefetch_depth,
                                         comm_chunk)
            dp_on_init = 'dp' in self.axes and self.mesh.shape['dp'] > 1
            self._pp_layout = None
            mp_on = 'mp' in self.axes and self.mesh.shape['mp'] > 1
            if B.elementwise(optimizer):
                local_shapes = {}
                for grp, named_list in (('embed', self._embed_named),
                                        ('blocks', self._block_named),
                                        ('head', self._head_named)):
                    for n, p in named_list:
                        if getattr(p, 'is_distributed', False) and mp_on:
                            continue
                        shp = tuple(p.data.shape)
                        if grp == 'blocks':
                            shp = (len(blocks) // max(self.pp, 1),) + shp
                        local_shapes[f'{grp}/{n}'] = (shp, p.data.dtype)
                if local_shapes:
                    self._pp_layout = B.BucketLayout.build(
                        local_shapes, bucket_bytes=self._bucket_bytes,
                        pad_to=max(self.dp, 1) * 8,
                        group_fn=lambda name, shape, dtype:
                            'blocks' if name.startswith('blocks/')
                            else 'repl')
            self._pp_bucketed = bool(
                self._pp_layout is not None and dp_on_init
                and use_buckets is not False)
            self._pp_overlap = bool(overlap_req and self._pp_bucketed)
            if self._pp_overlap:
                B.ensure_overlap_xla_flags()
            if self._pp_layout is not None:
                accum_fp32 = self.grad_accum_dtype != 'param'
                B.publish_comm_gauges(
                    self._pp_layout, engine='pipeline',
                    n_shards=max(self.dp, 1),
                    comm_dtype=self.comm_dtype or (
                        jnp.float32 if accum_fp32 else None),
                    enabled=self._pp_bucketed,
                    block=self._comm_block)
                B.publish_overlap_gauges(
                    self._pp_layout, engine='pipeline',
                    n_shards=max(self.dp, 1),
                    comm_dtype=self.comm_dtype or (
                        jnp.float32 if accum_fp32 else None),
                    enabled=self._pp_overlap,
                    prefetch=self._prefetch_depth,
                    chunk=self._comm_chunk,
                    block=self._comm_block)
            if not self._pp_bucketed:
                self._pp_layout = None
            if self._pp_overlap:
                # deferred gather: bucketed params live as [pp, size/dp]
                # shards between steps; the full trees only exist inside
                # the step (materialized group-by-group before use)
                self._build_param_shards(stacked)

            # optimizer state mirrors the param tree (per-param states
            # only for params outside the bucket layout)
            self._states = {}
            self._state_specs = {}
            in_layout = set(self._pp_layout.slots) if self._pp_bucketed \
                else set()
            for grp in ('embed', 'blocks', 'head'):
                self._states[grp] = {}
                self._state_specs[grp] = {}
                for n, arr in self._params[grp].items():
                    if f'{grp}/{n}' in in_layout:
                        continue
                    st = {}
                    sspec = {}
                    tmpl = optimizer.init_state(Tensor(
                        jnp.zeros(arr.shape, jnp.float32)))
                    if arr.dtype != jnp.float32 and getattr(
                            optimizer, '_multi_precision', True):
                        tmpl['master'] = arr.astype(jnp.float32)
                    for k, v in tmpl.items():
                        spec = self._specs[grp][n] if (
                            np.ndim(v) >= 1 and v.shape == arr.shape) else (
                            P('pp') if grp == 'blocks' and np.ndim(v) >= 1
                            else P())
                        if grp == 'blocks' and np.ndim(v) == 0:
                            # scalars (beta powers) per stacked tree stay
                            # scalar
                            spec = P()
                        st[k] = self._place(v, spec)
                        sspec[k] = spec
                    self._states[grp][n] = st
                    self._state_specs[grp][n] = sspec
            self._states['_buckets'] = []
            self._state_specs['_buckets'] = []
            if self._pp_bucketed:
                self._init_flat_states(stacked)

        self._compiled = None
        self._closed = False
        self._grad_clip = optimizer._grad_clip

        # -- async step pipeline (ISSUE 13,
        # docs/performance.md#async-dispatch) --------------------------------
        self._inflight = A_.DispatchWindow(
            A_.resolve_dispatch_window(dispatch_window))
        self._gap = A_.HostGapMonitor('pipeline')
        # step-time ledger (ISSUE 16): wall decomposition (incl. the
        # modeled schedule bubble) + model-FLOPs accounting. The FLOPs
        # remat factor: a resolved policy wins; else the legacy split —
        # 'recompute' memory mode re-runs stage forwards ('full'),
        # stash-1F1B keeps residuals with a save-dots backward ('dots')
        from ....core import ledger as _led
        self._ledger = _led.StepLedger(
            'pipeline', gap=self._gap,
            params_fn=lambda: _led.count_params(self._params),
            remat_policy=self._remat_policy or (
                'full' if self.memory_mode == 'recompute'
                else ('dots' if self.use_remat else 'none')),
            bubble_fraction_fn=lambda: self._sched_model.get(
                'bubble_fraction', 0.0))
        from ....optimizer import device_lr as _dlr
        self._lr = _dlr.LrFeed(optimizer, device_lr,
                               place=lambda a: self._place(a, P()))

    def _init_flat_states(self, stacked):
        """Flat sharded optimizer state per bucket. Every vector state is
        a GLOBAL [pp, bucket_size] array sharded P('pp' on dim 0, 'dp'
        on dim 1): each device holds the [1, size/dp] shard it updates.
        Stage-local (blocks) buckets genuinely differ along pp;
        replicated (embed/head) buckets carry identical rows — same
        per-device bytes either way, and one uniform spec."""
        opt = self.optimizer
        pp = max(self.pp, 1)
        pp_ax = 'pp' if 'pp' in self.axes else None
        vec_spec = P(pp_ax, 'dp')
        for b in self._pp_layout.buckets:
            # host-side initial fp32 values, per stage row
            flat32 = np.zeros((pp, b.size), np.float32)
            for s in b.slots:
                grp, n = s.name.split('/', 1)
                if grp == 'blocks':
                    arr = np.asarray(jax.device_get(stacked[n]), np.float32)
                    per = arr.shape[0] // pp
                    for k in range(pp):
                        flat32[k, s.offset:s.offset + s.size] = \
                            arr[k * per:(k + 1) * per].reshape(-1)
                else:
                    named = dict(self._embed_named if grp == 'embed'
                                 else self._head_named)
                    row = np.asarray(jax.device_get(named[n].data),
                                     np.float32).reshape(-1)
                    flat32[:, s.offset:s.offset + s.size] = row
            st = B.init_bucket_state(
                opt, b, flat32[0],
                force_master=B._is_int8(self.comm_dtype))
            placed, sspec = {}, {}
            for k, v in st.items():
                if np.ndim(v) >= 1:
                    host = flat32 if k == 'master' else np.broadcast_to(
                        np.asarray(v), (pp, b.size))
                    sharding = NamedSharding(self.mesh, vec_spec)
                    placed[k] = jax.make_array_from_callback(
                        host.shape, sharding,
                        lambda idx, _h=host: _h[idx])
                    sspec[k] = vec_spec
                else:
                    placed[k] = self._place(v, P())
                    sspec[k] = P()
            self._states['_buckets'].append(placed)
            self._state_specs['_buckets'].append(sspec)

    def _build_param_shards(self, stacked):
        """Overlap mode: move every bucketed param out of the group
        trees into flat [pp, bucket_size] arrays sharded P('pp','dp')
        — each device keeps only the [1, size/dp] slice it updates.
        Blocks rows are stage-local; embed/head rows replicate (same
        per-device bytes, one uniform spec — the flat-state layout)."""
        pp = max(self.pp, 1)
        pp_ax = 'pp' if 'pp' in self.axes else None
        spec = P(pp_ax, 'dp')
        layout = self._pp_layout
        shards = []
        for b in layout.buckets:
            host = np.zeros((pp, b.size), b.dtype)
            for s in b.slots:
                grp, n = s.name.split('/', 1)
                if grp == 'blocks':
                    arr = np.asarray(jax.device_get(stacked[n]))
                    per = arr.shape[0] // pp
                    for k in range(pp):
                        host[k, s.offset:s.offset + s.size] = \
                            arr[k * per:(k + 1) * per].reshape(-1) \
                            .astype(b.dtype)
                else:
                    named = dict(self._embed_named if grp == 'embed'
                                 else self._head_named)
                    row = np.asarray(
                        jax.device_get(named[n].data)).reshape(-1) \
                        .astype(b.dtype)
                    host[:, s.offset:s.offset + s.size] = row
            sharding = NamedSharding(self.mesh, spec)
            shards.append(jax.make_array_from_callback(
                host.shape, sharding, lambda idx, _h=host: _h[idx]))
        for s in layout.slots.values():
            grp, n = s.name.split('/', 1)
            self._params[grp].pop(n, None)
            self._specs[grp].pop(n, None)
        self._params['_shards'] = shards
        self._specs['_shards'] = [spec] * len(shards)

    def _materialize_params(self, params):
        """Deferred/prefetched param all-gather (overlap): rebuild the
        full embed/blocks/head trees from the [1, size/dp] local shard
        views at the top of the step, group by group, chaining gather g
        behind gather g-prefetch_depth via optimization_barrier so at
        most `prefetch_depth` full groups are in flight beyond the
        shards. Passthrough when overlap is off."""
        if not getattr(self, '_pp_overlap', False):
            return params
        layout = self._pp_layout
        gathered = B.gather_groups(
            [sh[0] for sh in params['_shards']], ('dp',), self.dp,
            comm_dtype=self.comm_dtype, block=self._comm_block,
            chunk=self._comm_chunk, prefetch=self._prefetch_depth)
        out = {grp: dict(params[grp])
               for grp in ('embed', 'blocks', 'head')}
        for k, v in layout.unflatten(gathered).items():
            grp, n = k.split('/', 1)
            out[grp][n] = v
        out['_shards'] = params['_shards']
        return out

    def _place(self, arr, spec):
        # copy before placing: device_put to a (partially) replicated
        # sharding can alias the source buffer, and the jitted step DONATES
        # these arrays — aliasing would free the model's eager params.
        return jax.device_put(jnp.array(arr, copy=True),
                              NamedSharding(self.mesh, spec))

    # ------------------------------------------------------------------------
    def _block_apply(self, template, param_slice, x, key):
        """Run one decoder block with bound params."""
        with bind_arrays(template, param_slice):
            with rng_mod.rng_guard(key), autograd.no_grad():
                out = template(Tensor(x))
        return out.data

    def _embed_apply(self, pe_, ids_m, k):
        """One microbatch's ids through the embedding with bound params."""
        with bind_arrays(self.embed, pe_):
            with rng_mod.rng_guard(k), autograd.no_grad():
                return self.embed(Tensor(ids_m)).data

    def _head_apply(self, ph_, out, lab, k):
        """Final activations and labels -> the microbatch's float32 loss."""
        with bind_arrays(self.head, ph_):
            with rng_mod.rng_guard(k), autograd.no_grad():
                return self.head(Tensor(out), Tensor(lab)).data \
                    .astype(jnp.float32)

    def _build(self):
        if self.schedule == '1F1B':
            return self._build_1f1b()
        if self.schedule == 'interleaved':
            return self._build_interleaved()
        return self._build_fthenb()

    # -- shared tail of both schedules ---------------------------------------
    def _remat_block(self, default):
        """The block function under the remat policy that applies: a
        resolved one (kwarg / PTPU_REMAT_POLICY / strategy:
        docs/performance.md#remat-policy) wins; `use_remat=True` alone
        takes the schedule's `default`; else the bare block."""
        block_apply = functools.partial(self._block_apply, self.blocks[0])
        from ..utils.recompute import apply_policy as _apply_remat
        if self._remat_policy is not None:
            default = self._remat_policy
        elif not self.use_remat:
            return block_apply
        return _apply_remat(block_apply, default, engine='pipeline')

    def _make_stage_forward(self, save_dots=False):
        """(block_params_local, x, key) -> x: scan this stage's blocks.

        save_dots: instead of full per-block rematerialization, checkpoint
        with a save-MXU-outputs policy — the backward recomputes only the
        cheap elementwise tail (layernorm/gelu/softmax), not the matmuls.
        Used by the activation-stashing 1F1B, whose O(pp) in-flight window
        makes the bigger residual set affordable (the reference
        SectionWorker likewise stores, not recomputes)."""
        block_apply = self._remat_block('dots' if save_dots else 'full')

        def stage_forward(block_params_local, x, key):
            def body(carry, xs):
                pslice, k = xs
                return block_apply(pslice, carry, k), None
            n_local = jax.tree_util.tree_leaves(
                block_params_local)[0].shape[0]
            keys = jax.random.split(key, n_local)
            out, _ = lax.scan(body, x, (block_params_local, keys))
            return out
        return stage_forward

    def _reduce_and_update(self, params, states, loss, grads, lr, dp_on,
                           scale=None):
        """Cross-axis loss/grad reductions + optimizer update (both
        schedules): tied/replicated trees (embed, head) psum over pp;
        everything pmeans over dp. With loss scaling, grads unscale here
        and a non-finite gradient anywhere skips the whole update
        (parity: check_finite_and_unscale + update_loss_scaling driven by
        hybrid_parallel_gradscaler.py — found_inf is global after the
        psum/pmean sync, since an inf on any rank infects the reduced
        value)."""
        if getattr(self, '_pp_bucketed', False):
            return self._bucketed_reduce_and_update(
                params, states, loss, grads, lr, dp_on, scale=scale)
        pp = self.pp
        if pp > 1:
            loss = lax.psum(loss, 'pp')  # only last stage ≠ 0
        if dp_on:
            loss = lax.pmean(loss, 'dp')

        def sync(tree, over_pp):
            def one(g):
                if over_pp and pp > 1:
                    g = lax.psum(g, 'pp')
                if dp_on:
                    g = lax.pmean(g, 'dp')
                return g
            return jax.tree_util.tree_map(one, tree)

        grads = {'embed': sync(grads['embed'], True),
                 'blocks': sync(grads['blocks'], False),
                 'head': sync(grads['head'], True)}

        # trace-time telemetry: grad-sync payload per compiled step (the
        # executable replays these psums/pmeans every step)
        if pp > 1 or dp_on:
            from ....core.monitor import counter
            nbytes = _tree_bytes(grads)
            counter('ptpu_collective_bytes_total',
                    help='payload bytes through collective APIs',
                    labelnames=('op',)).inc(nbytes, op='pipeline_grad_sync')
            counter('ptpu_collective_calls_total',
                    help='collective API invocations',
                    labelnames=('op',)).inc(1, op='pipeline_grad_sync')

        found_inf = jnp.asarray(False)
        if scale is not None:
            leaves = jax.tree_util.tree_leaves(grads)
            found_inf = jnp.any(jnp.stack(
                [jnp.any(~jnp.isfinite(g)) for g in leaves]))
            # block grads are stage-LOCAL (never psum'd over pp): an
            # overflow on one stage must skip the update on ALL stages or
            # the replicated embed/head trees desync — reduce the flag
            # over pp (dp grads are already pmean'd, so dp ranks agree)
            if pp > 1:
                found_inf = lax.pmax(found_inf.astype(jnp.int32),
                                     'pp') > 0
            inv = (1.0 / scale).astype(jnp.float32)
            grads = jax.tree_util.tree_map(
                lambda g: (g.astype(jnp.float32) * inv).astype(g.dtype),
                grads)

        # numerics taps: post-unscale, pre-update grad stats + the
        # global grad-norm^2. Block grads are stage-LOCAL (never psum'd
        # over pp) so their sum-of-squares reduces over 'pp'; embed/head
        # are already fully reduced. Per-tensor stats for blocks cover
        # the local stage's slice under pp>1 (the global norm is exact).
        taps_on = getattr(self, '_taps_on', False)
        flat_grads = gn_sq = None
        if taps_on:
            sq_eh = jnp.asarray(0.0, jnp.float32)
            for grp in ('embed', 'head'):
                for g in grads[grp].values():
                    sq_eh = sq_eh + jnp.sum(g.astype(jnp.float32) ** 2)
            sq_b = jnp.asarray(0.0, jnp.float32)
            for g in grads['blocks'].values():
                sq_b = sq_b + jnp.sum(g.astype(jnp.float32) ** 2)
            if pp > 1:
                sq_b = lax.psum(sq_b, 'pp')
            gn_sq = sq_eh + sq_b
            flat_grads = {f'{grp}/{n}': g
                          for grp in ('embed', 'blocks', 'head')
                          for n, g in grads[grp].items()}

        new_params, new_states = {}, {'_buckets': []}
        for grp in ('embed', 'blocks', 'head'):
            new_params[grp], new_states[grp] = {}, {}
            for n, p in params[grp].items():
                np_, ns = self._update_one(
                    p, grads[grp][n], dict(states[grp][n]), lr)
                if scale is not None:
                    np_ = jnp.where(found_inf, p, np_)
                    ns = jax.tree_util.tree_map(
                        lambda new, old: jnp.where(found_inf, old, new),
                        ns, dict(states[grp][n]))
                new_params[grp][n] = np_
                new_states[grp][n] = ns
        if taps_on:
            from ....core import numerics as _num
            flat_params = {f'{grp}/{n}': p
                           for grp in ('embed', 'blocks', 'head')
                           for n, p in new_params[grp].items()}
            taps = _num.jit_taps(flat_grads, flat_params,
                                 extra_norm_sq=gn_sq)
            return loss, new_params, new_states, found_inf, taps
        return loss, new_params, new_states, found_inf

    def _bucketed_reduce_and_update(self, params, states, loss, grads, lr,
                                    dp_on, scale=None):
        """Bucketed twin of `_reduce_and_update` (arXiv:2004.13336):
        embed/head grads still psum over 'pp' (tied-weight sync), then
        every eligible grad coalesces into flat buckets, each bucket
        moves through ONE reduce_scatter over 'dp' (compressed wire
        under `comm_dtype`), this rank updates its 1/dp shard of params
        + optimizer moments, and ONE all_gather per bucket rebuilds the
        updated params. mp-sharded params fall back to the per-param
        path; a nonfinite gradient anywhere still skips the whole
        update (found_inf pmax over dp and pp — shards differ per dp
        rank, so the dp reduction is load-bearing here)."""
        pp = self.pp
        layout = self._pp_layout
        if pp > 1:
            loss = lax.psum(loss, 'pp')  # only last stage ≠ 0
        if dp_on:
            loss = lax.pmean(loss, 'dp')

        def pp_sync(tree):
            if pp > 1:
                return jax.tree_util.tree_map(
                    lambda g: lax.psum(g, 'pp'), tree)
            return tree

        grads = {'embed': pp_sync(grads['embed']),
                 'blocks': grads['blocks'],
                 'head': pp_sync(grads['head'])}
        flat_named = {f'{grp}/{n}': g
                      for grp in ('embed', 'blocks', 'head')
                      for n, g in grads[grp].items()}
        accum_fp32 = self.grad_accum_dtype != 'param'
        legacy = {k: v for k, v in flat_named.items()
                  if k not in layout.slots}
        if dp_on:
            legacy = {k: lax.pmean(v, 'dp') for k, v in legacy.items()}
        flat_grads = layout.flatten(
            {k: flat_named[k] for k in layout.slots},
            cast=jnp.float32 if accum_fp32 else None)
        shards32 = [B.reduce_scatter(f, ('dp',), self.dp,
                                     comm_dtype=self.comm_dtype,
                                     mean=True,
                                     block=self._comm_block,
                                     chunk=self._comm_chunk)
                    for f in flat_grads]

        # trace-time telemetry: rs+ag wire bytes (scales + padding
        # included) replayed every step
        from ....core.monitor import counter
        wires = B.wire_bytes(layout, max(self.dp, 1),
                             self.comm_dtype or (
                                 jnp.float32 if accum_fp32 else None),
                             self._comm_block)
        nbytes = (wires['reduce_scatter']['total']
                  + wires['all_gather']['total'])
        counter('ptpu_collective_bytes_total',
                help='payload bytes through collective APIs',
                labelnames=('op',)).inc(nbytes, op='pipeline_bucket_rs_ag')
        counter('ptpu_collective_calls_total',
                help='collective API invocations',
                labelnames=('op',)).inc(2 * len(layout.buckets),
                                        op='pipeline_bucket_rs_ag')

        found_inf = jnp.asarray(False)
        inv = None
        fi_guard = None
        if scale is not None:
            # per-bucket found-inf from the same one-pass stats kernel
            # the fused optimizer step uses (nonfinite COUNT > 0 ==
            # any(~isfinite)); legacy params keep the per-param check
            flags = [B.grad_stats(g)[1] > 0 for g in shards32]
            flags += [jnp.any(~jnp.isfinite(v)) for v in legacy.values()]
            f = (jnp.any(jnp.stack(flags)) if flags
                 else jnp.asarray(False)).astype(jnp.int32)
            if dp_on:
                f = lax.pmax(f, 'dp')
            if pp > 1:
                f = lax.pmax(f, 'pp')
            found_inf = f > 0
            fi_guard = found_inf
            inv = (1.0 / scale).astype(jnp.float32)
            legacy = {k: (v.astype(jnp.float32) * inv).astype(v.dtype)
                      for k, v in legacy.items()}

        # numerics taps (diagnostics mode): the hot path never
        # materializes fully-reduced per-param grads, so pay one extra
        # pmean per param to surface them — observation only, the
        # update below still consumes the bucket shards
        taps_on = getattr(self, '_taps_on', False)
        tap_grads = gn_sq = None
        if taps_on:
            tap_grads = {}
            for k in layout.slots:
                g = flat_named[k]
                g = lax.pmean(g, 'dp') if dp_on else g
                if inv is not None:
                    g = (g.astype(jnp.float32) * inv).astype(g.dtype)
                tap_grads[k] = g
            tap_grads.update(legacy)
            sq_eh = jnp.asarray(0.0, jnp.float32)
            sq_b = jnp.asarray(0.0, jnp.float32)
            for k, g in tap_grads.items():
                v = jnp.sum(g.astype(jnp.float32) ** 2)
                if k.startswith('blocks/'):
                    sq_b = sq_b + v
                else:
                    sq_eh = sq_eh + v
            if pp > 1:
                sq_b = lax.psum(sq_b, 'pp')
            gn_sq = sq_eh + sq_b

        overlap = getattr(self, '_pp_overlap', False)
        if not overlap:
            slot_params = {k: params[k.split('/', 1)[0]]
                           [k.split('/', 1)[1]]
                           for k in layout.slots}
            flat_params = layout.flatten(slot_params)
        new_flat, new_shards, new_buckets = [], [], []
        for gi, (b, g32, st_in) in enumerate(
                zip(layout.buckets, shards32, states['_buckets'])):
            # local vector-state view is [1, shard]: drop/restore the
            # leading pp dim around the flat update
            st = {k: (v[0] if getattr(v, 'ndim', 0) >= 2 else v)
                  for k, v in st_in.items()}
            # overlap: this rank's stored param shard IS the slice
            # take_shard would cut out of the materialized replica
            p_shard = params['_shards'][gi][0] if overlap else \
                B.take_shard(flat_params[gi], ('dp',), self.dp)
            # unscale multiply + found-inf no-op guard fold into the
            # one-pass fused update (prefactor/found_inf); the
            # reference route applies the same ops in the same order
            np_, ns = B.shard_update(self.optimizer, p_shard, g32, st,
                                     lr, prefactor=inv,
                                     found_inf=fi_guard)
            new_buckets.append(
                {k: (v[None] if getattr(v, 'ndim', 0) >= 1 else v)
                 for k, v in ns.items()})
            if overlap:
                # deferred gather: the updated shard is the engine
                # state; its all-gather runs at the NEXT step's top,
                # under that step's early forward compute
                new_shards.append(np_[None])
            else:
                new_flat.append(B.all_gather(np_, ('dp',),
                                             comm_dtype=self.comm_dtype,
                                             block=self._comm_block,
                                             chunk=self._comm_chunk,
                                             n_shards=self.dp))

        new_params = {'embed': {}, 'blocks': {}, 'head': {}}
        new_states = {'embed': {}, 'blocks': {}, 'head': {},
                      '_buckets': new_buckets}
        if overlap:
            new_params['_shards'] = new_shards
        else:
            for k, v in layout.unflatten(new_flat).items():
                grp, n = k.split('/', 1)
                new_params[grp][n] = v
        for k, g in legacy.items():
            grp, n = k.split('/', 1)
            p = params[grp][n]
            old = dict(states[grp][n])
            np_, ns = self._update_one(p, g, dict(old), lr)
            if scale is not None:
                np_ = jnp.where(found_inf, p, np_)
                ns = jax.tree_util.tree_map(
                    lambda new, old_: jnp.where(found_inf, old_, new),
                    ns, old)
            new_params[grp][n] = np_
            new_states[grp][n] = ns

        if taps_on:
            from ....core import numerics as _num
            flat_params_tap = {f'{grp}/{n}': p
                               for grp in ('embed', 'blocks', 'head')
                               for n, p in new_params[grp].items()}
            if overlap:
                # diagnostics mode pays the gather the hot path
                # deferred, so per-param stats see full params
                flat_params_tap.update(layout.unflatten(
                    B.gather_groups([s2[0] for s2 in new_shards],
                                    ('dp',), self.dp,
                                    comm_dtype=self.comm_dtype,
                                    block=self._comm_block,
                                    chunk=self._comm_chunk)))
            taps = _num.jit_taps(tap_grads, flat_params_tap,
                                 extra_norm_sq=gn_sq)
            return loss, new_params, new_states, found_inf, taps
        return loss, new_params, new_states, found_inf

    def _finalize(self, step, dp_on):
        # on-device LR schedule: the lr slot carries a device int32
        # step counter; the compiled step derives lr = fn(counter) and
        # returns counter+1 (no per-step host LR compute or H2D feed)
        lr_fn = self._lr.fn
        if lr_fn is not None:
            base_step = step

            def step(params, states, step_c, scale, key, ii, ll):
                out = base_step(params, states,
                                lr_fn(step_c).astype(jnp.float32),
                                scale, key, ii, ll)
                return out[:4] + (step_c + 1,) + out[4:]

        dp_sp = P('dp') if dp_on else P()
        in_specs = (self._specs, self._state_specs, P(), P(), P(), dp_sp,
                    dp_sp)
        out_specs = (P(), self._specs, self._state_specs, P())
        if lr_fn is not None:
            out_specs = out_specs + (P(),)
        if getattr(self, '_taps_on', False):
            from ....core import numerics as _num
            # ALL trainable params (overlap mode keeps bucketed slots
            # out of the group trees, but taps still cover them)
            keys = [f'embed/{n}' for n, _ in self._embed_named] \
                + [f'blocks/{n}' for n, _ in self._block_named] \
                + [f'head/{n}' for n, _ in self._head_named]
            out_specs = out_specs + (_num.taps_spec(
                {'grads': dict.fromkeys(keys, 0),
                 'params': dict.fromkeys(keys, 0),
                 'grad_norm_sq': 0}),)
        mapped = shard_map(step, mesh=self.mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
        return jax.jit(mapped, donate_argnums=(0, 1))

    @staticmethod
    def _split_residuals(fn, args, variant_argnums, evaluate=True):
        """Taint-split the flattened outputs of ``fn(*args)`` into
        tick-VARIANT ones (those depending on the arguments named in
        ``variant_argnums``) and tick-INVARIANT ones, and evaluate the
        invariant ones once by running only their pruned sub-graph (weight
        casts/transposes — never the stage forward).

        The taint walk is a conservative jaxpr pass: any eqn with a
        tainted operand taints all its outputs (higher-order primitives
        are treated atomically — sound because scan/cond/pjit consts are
        hoisted to explicit invars in final-style jaxprs). Used to split
        per-microbatch vjp residuals into activation residuals (buffered
        per in-flight microbatch) and weight-derived residuals (computed
        once per step, shared by every tick). An output misclassified as
        variant merely wastes buffer space; it can never produce a wrong
        gradient.

        Returns ``(variant_flags, values, avals)``: ``values[i]`` holds
        the invariant output value (None at variant positions); ``avals``
        are every flattened output's abstract values, so callers need no
        second abstract trace for shapes. ``evaluate=False`` classifies
        only (``values`` is None): ``args`` may then be abstract."""
        closed = jax.make_jaxpr(fn)(*args)
        jaxpr = closed.jaxpr
        variant_flat = []
        for i, a in enumerate(args):
            n = len(jax.tree_util.tree_leaves(a))
            variant_flat += [i in variant_argnums] * n
        tainted = set()
        for var, isv in zip(jaxpr.invars, variant_flat):
            if isv:
                tainted.add(var)

        def _is_tainted(v):
            return not hasattr(v, 'val') and v in tainted  # Literal: .val

        for eqn in jaxpr.eqns:
            if any(_is_tainted(v) for v in eqn.invars):
                tainted.update(eqn.outvars)
        flags = [_is_tainted(v) for v in jaxpr.outvars]
        avals = [v.aval if not hasattr(v, 'val')
                 else jax.core.get_aval(v.val)
                 for v in jaxpr.outvars]
        if not evaluate:
            return flags, None, avals

        # dead-code-eliminate from the invariant outputs, then evaluate
        # just that sub-graph (it never touches a variant input, so this
        # runs no microbatch compute)
        want = [v for v, f in zip(jaxpr.outvars, flags) if not f]
        needed = {v for v in want if not hasattr(v, 'val')}
        keep = []
        for eqn in reversed(jaxpr.eqns):
            if any(o in needed for o in eqn.outvars):
                keep.append(eqn)
                needed.update(v for v in eqn.invars
                              if not hasattr(v, 'val'))
        keep.reverse()
        # the traced debug_info names one result path per ORIGINAL
        # outvar; the pruned jaxpr has fewer, so drop the paths
        pruned = jaxpr.replace(
            eqns=keep, outvars=want,
            debug_info=jaxpr.debug_info._replace(result_paths=None))
        flat_args = jax.tree_util.tree_leaves(args)
        inv_vals = jax.core.eval_jaxpr(pruned, closed.consts, *flat_args)
        values = [None] * len(flags)
        it = iter(inv_vals)
        for i, f in enumerate(flags):
            if not f:
                values[i] = next(it)
        return flags, values, avals

    # `_remat_reckoned`: what `_fit_remat` reckoned and chose for the
    # step being built (None: a named policy, no remat, or another
    # schedule); `_remat_fell`: how many of recompute.FIT_ORDER the
    # compiler has refused (RESOURCE_EXHAUSTED in _dispatch)
    _remat_reckoned = None
    _remat_fell = 0

    def _fit_remat(self, held_bytes, fixed, working):
        """What `use_remat=True` saves at pp=1 when no policy is named:
        the richest of recompute.FIT_ORDER whose stacked residuals
        (`held_bytes(name)`, one microbatch in flight) fit the device
        beside `fixed` (parameters, optimizer state, the accumulation
        buffer) and `working` (one layer's / the head's transient
        values) — `use_remat=True` has always meant "make it fit". A
        backend that names no limit (the CPU) fits everything. Returns
        the policy; says what it reckoned in the log."""
        from ..utils.recompute import FIT_ORDER
        from ..utils.log_util import log_json
        limit = _device_bytes_limit(self.mesh.devices.flat[0])
        order = FIT_ORDER[self._remat_fell:]
        held = {}
        for name in order:
            held[name] = held_bytes(name)
            if limit is None or name == order[-1] \
                    or fixed + working + held[name] <= limit:
                break
        self._remat_reckoned = {
            'policy': name, 'held_bytes': held, 'fixed_bytes': fixed,
            'working_bytes': working, 'bytes_limit': limit,
            'refused_by_compiler': self._remat_fell}
        log_json('remat_fit', logger_name='pipeline',
                 **self._remat_reckoned)
        return name

    def _remat_fall_back(self, err):
        """The compiler's answer to a policy `_fit_remat` chose: on
        RESOURCE_EXHAUSTED move to the next candidate (True: build and
        compile again); anything else, or nothing left, is the caller's
        to raise."""
        from ....core.memory import is_oom_error
        from ..utils.recompute import FIT_ORDER
        fitted = (self._remat_reckoned or {}).get('policy')
        if fitted in (None, FIT_ORDER[-1]) or not is_oom_error(err):
            return False
        self._remat_fell = FIT_ORDER.index(fitted) + 1
        from ..utils.log_util import log_json
        log_json('remat_fall_back', level='warning', logger_name='pipeline',
                 refused=fitted, next=FIT_ORDER[self._remat_fell],
                 error=str(err)[:400])
        return True

    def _build_one_stage(self):
        """1F1B on ONE stage (pp=1, 'stash'): a tick's backward consumes
        the same tick's forward, so nothing crosses ticks, and the tick
        is written out: embed, a forward scan over the layers that
        stacks each layer's pullback residuals, head, and the engine's
        own REVERSE scan over the layers whose carry holds (dx, the
        blocks' accumulation buffer). Layer l's pullback is rebuilt from
        its row of residuals — the stash schedule's `_split_residuals` /
        `tree_unflatten` way — and its weight gradient is added into
        row l of the buffer where the matmul makes it. `jax.vjp` through
        the layer scan would return a whole [L, ...] tree of fresh
        gradients every tick, a second copy that lives just long enough
        to be added; here none exists, and the memory it held keeps the
        block's contraction outputs (`_fit_remat`), so the backward
        recomputes LayerNorm and GELU and not the matmuls. The embedding
        and the head keep `jax.vjp` and an add: they are small."""
        A = self.A
        axes = self.axes
        embed_apply, head_apply = self._embed_apply, self._head_apply
        dp_on = 'dp' in axes and self.mesh.shape['dp'] > 1
        use_scaling = self._use_scaling
        acc_param = self.grad_accum_dtype == 'param'
        from ..utils.recompute import publish_held

        def step(params, states, lr, scale, key, input_ids, labels):
            with C.spmd_region(axes):
                params = self._materialize_params(params)
                mb = input_ids.shape[0] // A
                pe, pb, ph = params['embed'], params['blocks'], params['head']
                k0 = key
                if dp_on:
                    k0 = jax.random.fold_in(k0, lax.axis_index('dp'))
                ids_mb = input_ids.reshape(A, mb, *input_ids.shape[1:])
                labels_mb = labels.reshape(A, mb, *labels.shape[1:])
                n_layers = jax.tree_util.tree_leaves(pb)[0].shape[0]

                x_aval = jax.eval_shape(embed_apply, pe, ids_mb[0], k0)
                probe_args = (jax.tree_util.tree_map(lambda a: a[0], pb),
                              x_aval, k0)

                def follows_input(probe, args):
                    """Of probe's pullback leaves (its outputs after
                    the first), those that follow args[1]: their
                    indices and bytes."""
                    flags, _, avals = self._split_residuals(
                        probe, args, {1}, evaluate=False)
                    idx = [i for i, v in enumerate(flags[1:]) if v]
                    return idx, _tree_bytes([avals[1 + i] for i in idx])

                def probed(block_fn):
                    """(layer -> (y, its pullback's leaves), the leaves
                    the forward scan stacks, their bytes over the
                    layers); the probe's newest trace leaves the
                    pullback's treedef in `probe.box`."""
                    box = {}

                    def probe(pslice, x, k):
                        y, vjp_fn = jax.vjp(
                            lambda p, xx: block_fn(p, xx, k), pslice, x)
                        leaves, box['treedef'] = \
                            jax.tree_util.tree_flatten(vjp_fn)
                        return y, leaves
                    probe.box = box
                    idx, nbytes = follows_input(probe, probe_args)
                    return probe, idx, n_layers * nbytes

                gacc0 = jax.tree_util.tree_map(
                    lambda a: jnp.zeros(
                        a.shape, a.dtype if acc_param else jnp.float32),
                    (pe, pb, ph))
                if self._remat_policy is None and self.use_remat:
                    def head_probe(ph_, o):
                        loss, vjp_fn = jax.vjp(
                            lambda p, oo: head_apply(p, oo, labels_mb[0],
                                                     k0), ph_, o)
                        return loss, jax.tree_util.tree_leaves(vjp_fn)

                    # transient values: the largest single piece (the
                    # head, or one layer with nothing recomputed) holds
                    # its pullback's residuals, their cotangents, and
                    # as much again in the float32 the compiler makes
                    # them in — at 1.3B the compiler's peak is within
                    # 1 % of this (tests/test_pipeline_step_aot.py)
                    working = 3 * max(
                        follows_input(head_probe, (ph, x_aval))[1],
                        probed(functools.partial(
                            self._block_apply, self.blocks[0]))[2]
                        // n_layers)
                    tried = {}

                    def held_bytes(name):
                        # the gauge follows: the last tried is the taken
                        tried[name] = probed(self._remat_block(name))
                        return tried[name][2]
                    policy = self._fit_remat(
                        held_bytes, _tree_bytes((params, states, gacc0)),
                        working)
                    self._ledger.remat_policy = policy
                    layer_probe, var_idx, held = tried[policy]
                else:
                    self._remat_reckoned = None
                    layer_probe, var_idx, held = probed(
                        self._remat_block(None))
                publish_held('pipeline', saved_boundary_bytes=held,
                             grad_tree_bytes=0)

                def grad_cot():
                    return (scale / A).astype(jnp.float32) \
                        if use_scaling else jnp.asarray(1.0 / A,
                                                        jnp.float32)

                def accum(acc, d):
                    return jax.tree_util.tree_map(
                        lambda a, g: a + g.astype(a.dtype), acc, d)

                def tick(carry, m):
                    (g_e, g_b, g_h), loss_acc = carry
                    # keys as the pp > 1 schedule derives them at stage 0
                    k_mb = jax.random.fold_in(k0, m)
                    ke = jax.random.fold_in(k_mb, 17)
                    ks = jax.random.fold_in(
                        jax.random.fold_in(k_mb, 31), 0)
                    kh = jax.random.fold_in(k_mb, 7919)
                    keys = jax.random.split(ks, n_layers)

                    x, embed_vjp = jax.vjp(
                        lambda pe_: embed_apply(pe_, ids_mb[m], ke), pe)

                    def fwd_layer(x, xs):
                        y, leaves = layer_probe(xs[0], x, xs[1])
                        return y, [leaves[i] for i in var_idx]

                    out, rows = lax.scan(fwd_layer, x, (pb, keys))
                    loss, head_vjp = jax.vjp(
                        lambda ph_, o: head_apply(ph_, o, labels_mb[m],
                                                  kh), ph, out)
                    d_h, dx = head_vjp(grad_cot())

                    def bwd_layer(c, xs):
                        dx, g_b = c
                        l, pslice, k, row = xs
                        # the leaves that follow the weights alone are
                        # re-derived from this layer's slice (casts,
                        # transposes: never the block's forward)
                        _, leaves, _ = self._split_residuals(
                            layer_probe,
                            (pslice, jnp.zeros(x_aval.shape, x_aval.dtype),
                             k), {1})
                        leaves = leaves[1:]
                        for i, r in zip(var_idx, row):
                            leaves[i] = r
                        d_p, dx = jax.tree_util.tree_unflatten(
                            layer_probe.box['treedef'], leaves)(dx)

                        def add_row(g, d):
                            old = lax.dynamic_index_in_dim(
                                g, l, 0, keepdims=False)
                            return lax.dynamic_update_index_in_dim(
                                g, old + d.astype(g.dtype), l, 0)
                        return (dx, jax.tree_util.tree_map(
                            add_row, g_b, d_p)), None

                    (dx, g_b), _ = lax.scan(
                        bwd_layer, (dx, g_b),
                        (jnp.arange(n_layers), pb, keys, rows),
                        reverse=True)
                    (d_e,) = embed_vjp(dx)
                    return ((accum(g_e, d_e), g_b, accum(g_h, d_h)),
                            loss_acc + loss), None

                (gacc, loss_sum), _ = lax.scan(
                    tick, (gacc0, jnp.asarray(0.0, jnp.float32)),
                    jnp.arange(A))
                grads = {'embed': gacc[0], 'blocks': gacc[1],
                         'head': gacc[2]}
                return self._reduce_and_update(
                    params, states, loss_sum / A, grads, lr, dp_on,
                    scale=scale if use_scaling else None)

        return self._finalize(step, dp_on)

    def _build_1f1b(self):
        """1F1B steady-state schedule (section_worker.cc:147-184 parity).

        TPU-native formulation: ONE `lax.scan` over T = A + 2*(pp-1) ticks.
        Every tick, every stage runs one forward sub-step (microbatch
        m_f = t - stage) and one backward sub-step (microbatch
        m_b = t - (2*(pp-1) - stage)), lockstep-SPMD with `jnp.where`
        masking outside the active windows. Activations flow +1 over the
        'pp' ring and cotangents flow -1, one `lax.ppermute` each per tick.

        Memory/compute, per ``memory_mode``:
          * 'stash' (default — the reference SectionWorker's
            store-activations 1F1B): the forward sub-step runs under
            `jax.vjp`, and the pullback — a `jax.tree_util.Partial`, i.e.
            a real pytree of residual arrays — is flattened; the
            tick-VARIANT residual leaves (activations; identified by
            `_split_residuals`) go into a circular buffer of
            B = min(A, 2*pp-1) slots, while weight-derived leaves are
            taken from the current tick's forward call (tick-invariant,
            so bit-identical). The backward sub-step unflattens the
            pullback from the buffered slot and applies it — the stage
            forward is never re-run. Stage FLOPs: fwd + bwd.
          * 'recompute': only the stage-INPUT activation of each
            in-flight microbatch is buffered; backward re-runs the stage
            from the saved input via a local `jax.vjp` consumed in the
            same tick (full-remat cost). Lower memory, +1 fwd FLOPs.
        Either way live state is O(pp), not O(A) — the reference 1F1B's
        memory property (in-flight <= 2*(pp-1)+1 here vs Megatron's pp:
        the constant-factor price of every stage doing fwd+bwd each tick
        in lockstep). Stage 0 embeds each microbatch on its tick — no
        [A, mb, L, H] up-front buffer.
        """
        A, pp = self.A, self.pp
        axes = self.axes
        embed_apply, head_apply = self._embed_apply, self._head_apply
        opt = self.optimizer
        dp_on = 'dp' in axes and self.mesh.shape['dp'] > 1
        use_scaling = self._use_scaling
        stash = self.memory_mode == 'stash'
        if stash and pp == 1:
            # backward always consumes the SAME tick's forward (m_b ==
            # m_f): nothing crosses ticks, nothing is buffered
            return self._build_one_stage()
        from ..utils.recompute import publish_held
        B = min(A, 2 * pp - 1)
        T = A + 2 * (pp - 1)
        stage_forward = self._make_stage_forward(save_dots=stash)

        def step(params, states, lr, scale, key, input_ids, labels):
            with C.spmd_region(axes):
                params = self._materialize_params(params)
                stage = lax.axis_index('pp') if pp > 1 else 0
                is_last = stage == pp - 1
                mb = input_ids.shape[0] // A
                pe, pb, ph = params['embed'], params['blocks'], params['head']
                k0 = key
                if dp_on:
                    k0 = jax.random.fold_in(k0, lax.axis_index('dp'))

                ids_mb = input_ids.reshape(A, mb, *input_ids.shape[1:])
                labels_mb = labels.reshape(A, mb, *labels.shape[1:])

                emb_shape = jax.eval_shape(
                    embed_apply, pe, ids_mb[0], k0)
                act_shape, act_dtype = emb_shape.shape, emb_shape.dtype

                def fwd_only(pe_, pb_, x_in, m, k_mb):
                    """Forward sub-step: embed (stage 0) + local blocks.
                    Keys derive from (microbatch, stage) so the backward
                    recompute replays identical dropout."""
                    ke = jax.random.fold_in(k_mb, 17)
                    ks = jax.random.fold_in(
                        jax.random.fold_in(k_mb, 31), stage)
                    if pp > 1:
                        x = lax.cond(
                            stage == 0,
                            lambda: embed_apply(pe_, ids_mb[m], ke),
                            lambda: x_in)
                    else:
                        x = embed_apply(pe_, ids_mb[m], ke)
                    return stage_forward(pb_, x, ks)

                def full_fn(p3, x_in, m, k_mb):
                    """fwd_only + head loss (last stage) — the function the
                    backward sub-step differentiates."""
                    pe_, pb_, ph_ = p3
                    out = fwd_only(pe_, pb_, x_in, m, k_mb)
                    kh = jax.random.fold_in(k_mb, 7919)
                    if pp > 1:
                        loss = lax.cond(
                            is_last,
                            lambda: head_apply(ph_, out, labels_mb[m], kh),
                            lambda: jnp.asarray(0.0, jnp.float32))
                    else:
                        loss = head_apply(ph_, out, labels_mb[m], kh)
                    return out, loss

                acc_param = self.grad_accum_dtype == 'param'
                gacc0 = jax.tree_util.tree_map(
                    lambda a: jnp.zeros(
                        a.shape, a.dtype if acc_param else jnp.float32),
                    (pe, pb, ph))

                def grad_cot():
                    return (scale / A).astype(jnp.float32) \
                        if use_scaling else jnp.asarray(1.0 / A,
                                                        jnp.float32)

                def accum(gacc, d_p3, b_active):
                    return jax.tree_util.tree_map(
                        lambda a, g: a + jnp.where(
                            b_active, g.astype(a.dtype),
                            jnp.zeros((), a.dtype)),
                        gacc, d_p3)

                if stash:
                    # -- activation-stashing 1F1B ------------------------
                    box = {}

                    def fwd_probe(p3, x_in, m, k_mb):
                        (out, loss), vjp_fn = jax.vjp(
                            lambda p, xx: full_fn(p, xx, m, k_mb),
                            p3, x_in)
                        leaves, treedef = jax.tree_util.tree_flatten(vjp_fn)
                        box['treedef'] = treedef
                        return out, loss, leaves

                    probe_args = ((pe, pb, ph),
                                  jnp.zeros(act_shape, act_dtype),
                                  jnp.asarray(0, jnp.int32), k0)
                    flags, inv_vals, avals = self._split_residuals(
                        fwd_probe, probe_args, {1, 2, 3})
                    leaf_shapes = avals[2:]
                    leaf_var = flags[2:]
                    inv_leaves = inv_vals[2:]
                    var_idx = [i for i, v in enumerate(leaf_var) if v]
                    # B real slots + 1 scratch slot: inactive forward ticks
                    # write to the scratch slot, so the hot path is a pure
                    # dynamic-update (no read-old + select per leaf, which
                    # would force XLA to materialize a buffer copy per tick
                    # instead of updating the loop carry in place).
                    bufs0 = tuple(
                        jnp.zeros((B + 1,) + tuple(leaf_shapes[i].shape),
                                  leaf_shapes[i].dtype)
                        for i in var_idx)
                    carry0 = (jnp.zeros(act_shape, act_dtype),  # fwd act
                              jnp.zeros(act_shape, act_dtype),  # cotangent
                              bufs0,                            # residuals
                              gacc0,
                              jnp.asarray(0.0, jnp.float32))    # loss acc

                    def tick(carry, t):
                        fwd_act, grad_in, bufs, gacc, loss_acc = carry

                        m_f = t - stage
                        f_active = (m_f >= 0) & (m_f < A)
                        m_fc = jnp.clip(m_f, 0, A - 1)
                        m_b = t - (2 * (pp - 1) - stage)
                        b_active = (m_b >= 0) & (m_b < A)
                        m_bc = jnp.clip(m_b, 0, A - 1)
                        slot_b = jnp.mod(m_bc, B)

                        # -- forward sub-step: microbatch m_f = t - stage;
                        # runs under vjp so its pullback's residuals
                        # exist. Gated on the tick range in which ANY
                        # stage still forwards — the predicate is uniform
                        # across the mesh, so the cond's mp collectives
                        # see uniform control flow and the bwd-only drain
                        # ticks pay no forward at all (total work = A+pp-1
                        # forwards + A+pp-1 backwards, same as F-then-B).
                        def do_fwd():
                            out, l_f, leaves = fwd_probe(
                                (pe, pb, ph), fwd_act, m_fc,
                                jax.random.fold_in(k0, m_fc))
                            return (out, l_f,
                                    [leaves[i] for i in var_idx])

                        def skip_fwd():
                            return (jnp.zeros(act_shape, act_dtype),
                                    jnp.asarray(0.0, jnp.float32),
                                    [jnp.zeros(tuple(leaf_shapes[i].shape),
                                               leaf_shapes[i].dtype)
                                     for i in var_idx])

                        out_f, loss_f, vleaves = lax.cond(
                            t < A + pp - 1, do_fwd, skip_fwd)
                        slot_f = jnp.where(f_active, jnp.mod(m_fc, B), B)
                        bufs = tuple(
                            lax.dynamic_update_index_in_dim(
                                buf, vl, slot_f, 0)
                            for buf, vl in zip(bufs, vleaves))
                        loss_acc = loss_acc + jnp.where(f_active, loss_f,
                                                        0.0)

                        # Reading after the write is correct: the only
                        # same-tick producer-consumer is the last stage
                        # (m_b == m_f), where the just-written slot is
                        # exactly the wanted fresh data; inactive
                        # forwards write the scratch slot so they can
                        # never clobber a pending slot.
                        gathered = [
                            lax.dynamic_index_in_dim(
                                buf, slot_b, 0, keepdims=False)
                            for buf in bufs]

                        # -- backward sub-step: m_b = t-(2(pp-1)-stage);
                        # pullback rebuilt from the stashed residuals —
                        # the stage forward is NOT re-run. Gated on the
                        # warm-up ticks where no stage has a backward yet.
                        def do_bwd():
                            leaves_b = list(inv_leaves)
                            for g, i in zip(gathered, var_idx):
                                leaves_b[i] = g
                            vjp_b = jax.tree_util.tree_unflatten(
                                box['treedef'], leaves_b)
                            g_out = jnp.where(
                                is_last,
                                jnp.zeros(act_shape, act_dtype),
                                grad_in.astype(act_dtype))
                            return vjp_b((g_out, grad_cot()))

                        def skip_bwd():
                            return (jax.tree_util.tree_map(
                                jnp.zeros_like, (pe, pb, ph)),
                                jnp.zeros(act_shape, act_dtype))

                        d_p3, dx = lax.cond(t >= pp - 1, do_bwd, skip_bwd)
                        gacc = accum(gacc, d_p3, b_active)
                        dx = jnp.where(b_active, dx, jnp.zeros_like(dx))

                        nxt_act = lax.ppermute(
                            out_f, 'pp',
                            [(i, (i + 1) % pp) for i in range(pp)])
                        nxt_grad = lax.ppermute(
                            dx, 'pp',
                            [(i, (i - 1) % pp) for i in range(pp)])
                        return (nxt_act, nxt_grad, bufs, gacc,
                                loss_acc), None
                else:
                    # -- recompute 1F1B (stage-input buffer only) --------
                    carry0 = (jnp.zeros(act_shape, act_dtype),  # fwd act
                              jnp.zeros(act_shape, act_dtype),  # cotangent
                              jnp.zeros((B + 1,) + act_shape,
                                        act_dtype),             # inputs buf
                              gacc0,
                              jnp.asarray(0.0, jnp.float32))    # loss acc

                    def tick(carry, t):
                        fwd_act, grad_in, buf, gacc, loss_acc = carry

                        m_f = t - stage
                        f_active = (m_f >= 0) & (m_f < A)
                        m_fc = jnp.clip(m_f, 0, A - 1)
                        m_b = t - (2 * (pp - 1) - stage)
                        b_active = (m_b >= 0) & (m_b < A)
                        m_bc = jnp.clip(m_b, 0, A - 1)
                        # read-before-write (see stash tick) + same-tick
                        # select for the last stage
                        x_read = lax.dynamic_index_in_dim(
                            buf, jnp.mod(m_bc, B), 0, keepdims=False)
                        p_same = jnp.logical_and(m_fc == m_bc, f_active)
                        x_saved = jnp.where(p_same, fwd_act, x_read)

                        # -- forward sub-step: microbatch m_f = t - stage
                        out_f = fwd_only(pe, pb, fwd_act, m_fc,
                                         jax.random.fold_in(k0, m_fc))
                        # stash this microbatch's stage input (scratch slot
                        # B absorbs inactive ticks — pure in-place update)
                        slot_f = jnp.where(f_active, jnp.mod(m_fc, B), B)
                        buf = lax.dynamic_update_index_in_dim(
                            buf, fwd_act, slot_f, 0)

                        # -- backward sub-step: m_b = t-(2(pp-1)-stage) --
                        k_b = jax.random.fold_in(k0, m_bc)
                        (_out_p, loss_p), vjp_fn = jax.vjp(
                            lambda p3, x: full_fn(p3, x, m_bc, k_b),
                            (pe, pb, ph), x_saved)
                        g_out = jnp.where(is_last, jnp.zeros_like(_out_p),
                                          grad_in.astype(_out_p.dtype))
                        d_p3, dx = vjp_fn((g_out, grad_cot()))
                        gacc = accum(gacc, d_p3, b_active)
                        loss_acc = loss_acc + jnp.where(b_active, loss_p,
                                                        0.0)
                        dx = jnp.where(b_active, dx, jnp.zeros_like(dx))

                        if pp > 1:
                            nxt_act = lax.ppermute(
                                out_f, 'pp',
                                [(i, (i + 1) % pp) for i in range(pp)])
                            nxt_grad = lax.ppermute(
                                dx, 'pp',
                                [(i, (i - 1) % pp) for i in range(pp)])
                        else:
                            nxt_act, nxt_grad = out_f, dx
                        return (nxt_act, nxt_grad, buf, gacc,
                                loss_acc), None

                # what crosses ticks, and the fresh gradient tree every
                # tick's vjp returns beside the accumulation buffer
                publish_held(
                    'pipeline', saved_boundary_bytes=_tree_bytes(carry0[2]),
                    grad_tree_bytes=_tree_bytes((pe, pb, ph)))
                (_, _, _, gacc, loss_sum), _ = lax.scan(
                    tick, carry0, jnp.arange(T))
                grads = {'embed': gacc[0], 'blocks': gacc[1],
                         'head': gacc[2]}
                return self._reduce_and_update(
                    params, states, loss_sum / A, grads, lr, dp_on,
                    scale=scale if use_scaling else None)

        return self._finalize(step, dp_on)

    def _build_interleaved(self):
        """Interleaved virtual-stage 1F1B (arXiv:2104.04473; Megatron's
        num_model_chunks schedule).

        Each physical stage holds v model chunks; global virtual stage
        g = c*pp + s runs chunk c on device s. ONE `lax.scan` over
        T = A*v + D ticks, D = 2*(pp-1) + (v-1)*pp: every tick each
        device runs ONE chunk-forward (its job stream index
        j_f = t - stage; job j -> chunk c = (j mod pp*v) // pp,
        microbatch m = (j // (pp*v))*pp + j mod pp — microbatches
        advance in groups of pp per chunk, hence A % pp == 0) and ONE
        chunk-backward (j_b = t - (D - stage); reversed chunk order
        within each group). Activations still move +1 and cotangents
        -1 over the SAME 'pp' ring, one `lax.ppermute` each per tick:
        the ring wrap pp-1 -> 0 carries a microbatch from chunk c-1
        into chunk c, so boundary crossings scale ~v x while every
        masked warm-up/drain tick now burns 1/v of a stage — the
        modeled bubble shrinks from (pp-1)/(A+pp-1) to
        (pp-1)/(A*v+pp-1) (see schedule_model).

        The O(pp) residual machinery generalizes to per-(chunk,
        in-flight-microbatch) slots: `memory_mode='stash'` buffers the
        tick-variant vjp residual leaves in slots_per_chunk slots per
        chunk (weight-derived leaves are evaluated once per chunk and
        selected by c_b inside the scan); 'recompute' buffers only the
        chunk-input activation per slot. Tied/replicated grads keep
        their pp-psum semantics unchanged (_reduce_and_update).
        v == 1 degenerates to the classic 1F1B tick table."""
        A, pp, v = self.A, self.pp, self.vp
        axes = self.axes
        embed_apply, head_apply = self._embed_apply, self._head_apply
        dp_on = 'dp' in axes and self.mesh.shape['dp'] > 1
        use_scaling = self._use_scaling
        stash = self.memory_mode == 'stash'
        ppv = pp * v
        D = 2 * (pp - 1) + (v - 1) * pp
        T = A * v + D
        K = min(self._sched_model['slots_per_chunk'], A)
        nslots = v * K
        per = len(self.blocks) // ppv       # layers per chunk
        # pp*v == 1: every backward consumes the same tick's forward —
        # full per-block remat stays the memory-safe single-chip choice
        # (the v=1 1F1B rationale)
        save_dots = stash and ppv > 1
        stage_forward = self._make_stage_forward(save_dots=save_dots)

        def step(params, states, lr, scale, key, input_ids, labels):
            with C.spmd_region(axes):
                params = self._materialize_params(params)
                stage = lax.axis_index('pp') if pp > 1 else 0
                mb = input_ids.shape[0] // A
                pe, pb, ph = params['embed'], params['blocks'], params['head']
                k0 = key
                if dp_on:
                    k0 = jax.random.fold_in(k0, lax.axis_index('dp'))

                ids_mb = input_ids.reshape(A, mb, *input_ids.shape[1:])
                labels_mb = labels.reshape(A, mb, *labels.shape[1:])

                emb_shape = jax.eval_shape(
                    embed_apply, pe, ids_mb[0], k0)
                act_shape, act_dtype = emb_shape.shape, emb_shape.dtype

                def chunk_slice(tree, c):
                    """This device's rows for chunk c: local leaves are
                    [v*per, ...] chunk-major (chunk_layer_order)."""
                    return jax.tree_util.tree_map(
                        lambda l: lax.dynamic_slice_in_dim(
                            l, c * per, per, 0), tree)

                def fwd_only(pe_, pbc_, x_in, m, c, k_mb):
                    """One chunk-forward: embed feeds virtual stage 0
                    (device 0, chunk 0); everyone else consumes the
                    ring. Keys derive from (microbatch, GLOBAL virtual
                    stage) — identical to the v=1 keys when v == 1."""
                    ke = jax.random.fold_in(k_mb, 17)
                    ks = jax.random.fold_in(
                        jax.random.fold_in(k_mb, 31), c * pp + stage)
                    if ppv > 1:
                        x = lax.cond(
                            jnp.logical_and(stage == 0, c == 0),
                            lambda: embed_apply(pe_, ids_mb[m], ke),
                            lambda: x_in)
                    else:
                        x = embed_apply(pe_, ids_mb[m], ke)
                    return stage_forward(pbc_, x, ks)

                def full_fn(p3, x_in, m, c, k_mb):
                    """fwd_only + head loss on the LAST virtual stage
                    (device pp-1, chunk v-1) — what backward
                    differentiates. p3 carries the CHUNK's block rows
                    so the pullback yields chunk-shaped cotangents."""
                    pe_, pbc_, ph_ = p3
                    out = fwd_only(pe_, pbc_, x_in, m, c, k_mb)
                    kh = jax.random.fold_in(k_mb, 7919)
                    if ppv > 1:
                        loss = lax.cond(
                            jnp.logical_and(stage == pp - 1, c == v - 1),
                            lambda: head_apply(ph_, out, labels_mb[m],
                                               kh),
                            lambda: jnp.asarray(0.0, jnp.float32))
                    else:
                        loss = head_apply(ph_, out, labels_mb[m], kh)
                    return out, loss

                acc_param = self.grad_accum_dtype == 'param'
                gacc0 = jax.tree_util.tree_map(
                    lambda a: jnp.zeros(
                        a.shape, a.dtype if acc_param else jnp.float32),
                    (pe, pb, ph))

                def grad_cot():
                    return (scale / A).astype(jnp.float32) \
                        if use_scaling else jnp.asarray(1.0 / A,
                                                        jnp.float32)

                def accum_full(acc, d, active):
                    return jax.tree_util.tree_map(
                        lambda a, g: a + jnp.where(
                            active, g.astype(a.dtype),
                            jnp.zeros((), a.dtype)),
                        acc, d)

                def accum_chunk(acc, d_chunk, c, active):
                    """Add a chunk-shaped block cotangent into rows
                    [c*per, (c+1)*per) of the local accumulator."""
                    def one(a, g):
                        cur = lax.dynamic_slice_in_dim(a, c * per, per, 0)
                        upd = cur + jnp.where(
                            active, g.astype(a.dtype),
                            jnp.zeros((), a.dtype))
                        return lax.dynamic_update_slice_in_dim(
                            a, upd, c * per, 0)
                    return jax.tree_util.tree_map(one, acc, d_chunk)

                def fwd_job(t):
                    """tick -> (active, chunk, microbatch) of this
                    device's forward job stream."""
                    j = t - stage
                    active = (j >= 0) & (j < A * v)
                    jc = jnp.clip(j, 0, A * v - 1)
                    q = jnp.mod(jc, ppv)
                    c = q // pp
                    m = (jc // ppv) * pp + jnp.mod(q, pp)
                    return active, c, m

                def bwd_job(t):
                    """Backward stream: reversed chunk order within
                    each pp-microbatch group."""
                    j = t - (D - stage)
                    active = (j >= 0) & (j < A * v)
                    jc = jnp.clip(j, 0, A * v - 1)
                    q = jnp.mod(jc, ppv)
                    c = (v - 1) - q // pp
                    m = (jc // ppv) * pp + jnp.mod(q, pp)
                    return active, c, m

                if stash:
                    # -- activation-stashing interleaved 1F1B ------------
                    box = {}

                    def fwd_probe(p3, x_in, m, c, k_mb):
                        (out, loss), vjp_fn = jax.vjp(
                            lambda p, xx: full_fn(p, xx, m, c, k_mb),
                            p3, x_in)
                        leaves, treedef = jax.tree_util.tree_flatten(vjp_fn)
                        box['treedef'] = treedef
                        return out, loss, leaves

                    # taint split per chunk: x_in/m/k are tick-variant
                    # (buffered per slot); the chunk id + its weight
                    # rows are per-chunk constants, so the pruned
                    # weight-derived residual graph is evaluated ONCE
                    # per chunk and stacked for in-scan selection
                    flags = avals = None
                    inv_per_c = []
                    for c in range(v):
                        pbc = jax.tree_util.tree_map(
                            lambda l: lax.slice_in_dim(
                                l, c * per, (c + 1) * per, axis=0), pb)
                        probe_args = ((pe, pbc, ph),
                                      jnp.zeros(act_shape, act_dtype),
                                      jnp.asarray(0, jnp.int32),
                                      jnp.asarray(c, jnp.int32), k0)
                        fl, inv_vals, avs = self._split_residuals(
                            fwd_probe, probe_args, {1, 2, 4})
                        if flags is None:
                            flags, avals = fl, avs
                        else:
                            assert fl == flags, \
                                "chunk residual split diverged"
                        inv_per_c.append(inv_vals)
                    leaf_shapes = avals[2:]
                    leaf_var = flags[2:]
                    var_idx = [i for i, f in enumerate(leaf_var) if f]
                    inv_idx = [i for i, f in enumerate(leaf_var)
                               if not f]
                    inv_stacks = [
                        jnp.stack([inv_per_c[c][2 + i]
                                   for c in range(v)])
                        for i in inv_idx]
                    # v*K real slots (chunk-major) + 1 scratch slot for
                    # inactive forwards — the same pure
                    # dynamic-update-in-place trick as v=1
                    bufs0 = tuple(
                        jnp.zeros(
                            (nslots + 1,) + tuple(leaf_shapes[i].shape),
                            leaf_shapes[i].dtype)
                        for i in var_idx)
                    carry0 = (jnp.zeros(act_shape, act_dtype),  # fwd act
                              jnp.zeros(act_shape, act_dtype),  # cotangent
                              bufs0,                            # residuals
                              gacc0,
                              jnp.asarray(0.0, jnp.float32))    # loss acc

                    def tick(carry, t):
                        fwd_act, grad_in, bufs, gacc, loss_acc = carry
                        f_active, c_f, m_f = fwd_job(t)
                        b_active, c_b, m_b = bwd_job(t)
                        slot_b = c_b * K + jnp.mod(m_b, K)

                        # -- forward sub-step: ONE chunk (1/v stage) —
                        # cond-gated on the global window so drain
                        # ticks pay nothing
                        def do_fwd():
                            out, l_f, leaves = fwd_probe(
                                (pe, chunk_slice(pb, c_f), ph),
                                fwd_act, m_f, c_f,
                                jax.random.fold_in(k0, m_f))
                            return (out, l_f,
                                    [leaves[i] for i in var_idx])

                        def skip_fwd():
                            return (jnp.zeros(act_shape, act_dtype),
                                    jnp.asarray(0.0, jnp.float32),
                                    [jnp.zeros(
                                        tuple(leaf_shapes[i].shape),
                                        leaf_shapes[i].dtype)
                                     for i in var_idx])

                        out_f, loss_f, vleaves = lax.cond(
                            t < A * v + pp - 1, do_fwd, skip_fwd)
                        slot_f = jnp.where(
                            f_active, c_f * K + jnp.mod(m_f, K), nslots)
                        bufs = tuple(
                            lax.dynamic_update_index_in_dim(
                                buf, vl, slot_f, 0)
                            for buf, vl in zip(bufs, vleaves))
                        loss_acc = loss_acc + jnp.where(f_active, loss_f,
                                                        0.0)

                        # read AFTER the write: the only same-tick
                        # producer-consumer is the last virtual stage
                        # (same job), whose just-written slot holds
                        # exactly the wanted fresh residuals
                        gathered = [
                            lax.dynamic_index_in_dim(
                                buf, slot_b, 0, keepdims=False)
                            for buf in bufs]

                        # -- backward sub-step: pullback rebuilt from
                        # the slot + the chunk's weight-derived stack
                        def do_bwd():
                            leaves_b = [None] * len(leaf_var)
                            for stk, i in zip(inv_stacks, inv_idx):
                                leaves_b[i] = lax.dynamic_index_in_dim(
                                    stk, c_b, 0, keepdims=False)
                            for g, i in zip(gathered, var_idx):
                                leaves_b[i] = g
                            vjp_b = jax.tree_util.tree_unflatten(
                                box['treedef'], leaves_b)
                            g_out = jnp.where(
                                jnp.logical_and(stage == pp - 1,
                                                c_b == v - 1),
                                jnp.zeros(act_shape, act_dtype),
                                grad_in.astype(act_dtype))
                            return vjp_b((g_out, grad_cot()))

                        def skip_bwd():
                            return ((jax.tree_util.tree_map(
                                jnp.zeros_like, pe),
                                jax.tree_util.tree_map(
                                    lambda l: jnp.zeros(
                                        (per,) + l.shape[1:], l.dtype),
                                    pb),
                                jax.tree_util.tree_map(
                                    jnp.zeros_like, ph)),
                                jnp.zeros(act_shape, act_dtype))

                        (d_pe, d_pbc, d_ph), dx = lax.cond(
                            t >= D - (pp - 1), do_bwd, skip_bwd)
                        gacc = (accum_full(gacc[0], d_pe, b_active),
                                accum_chunk(gacc[1], d_pbc, c_b,
                                            b_active),
                                accum_full(gacc[2], d_ph, b_active))
                        dx = jnp.where(b_active, dx, jnp.zeros_like(dx))

                        if pp > 1:
                            nxt_act = lax.ppermute(
                                out_f, 'pp',
                                [(i, (i + 1) % pp) for i in range(pp)])
                            nxt_grad = lax.ppermute(
                                dx, 'pp',
                                [(i, (i - 1) % pp) for i in range(pp)])
                        else:
                            nxt_act, nxt_grad = out_f, dx
                        return (nxt_act, nxt_grad, bufs, gacc,
                                loss_acc), None
                else:
                    # -- recompute interleaved (chunk-input buffer) ------
                    carry0 = (jnp.zeros(act_shape, act_dtype),  # fwd act
                              jnp.zeros(act_shape, act_dtype),  # cotangent
                              jnp.zeros((nslots + 1,) + act_shape,
                                        act_dtype),             # inputs buf
                              gacc0,
                              jnp.asarray(0.0, jnp.float32))    # loss acc

                    def tick(carry, t):
                        fwd_act, grad_in, buf, gacc, loss_acc = carry
                        f_active, c_f, m_f = fwd_job(t)
                        b_active, c_b, m_b = bwd_job(t)
                        # read-before-write + same-JOB same-tick select
                        x_read = lax.dynamic_index_in_dim(
                            buf, c_b * K + jnp.mod(m_b, K), 0,
                            keepdims=False)
                        p_same = jnp.logical_and(
                            jnp.logical_and(m_f == m_b, c_f == c_b),
                            f_active)
                        x_saved = jnp.where(p_same, fwd_act, x_read)

                        def do_fwd():
                            return fwd_only(
                                pe, chunk_slice(pb, c_f), fwd_act,
                                m_f, c_f, jax.random.fold_in(k0, m_f))

                        out_f = lax.cond(
                            t < A * v + pp - 1, do_fwd,
                            lambda: jnp.zeros(act_shape, act_dtype))
                        slot_f = jnp.where(
                            f_active, c_f * K + jnp.mod(m_f, K), nslots)
                        buf = lax.dynamic_update_index_in_dim(
                            buf, fwd_act, slot_f, 0)

                        # -- backward: re-run the chunk from its saved
                        # input via a local vjp consumed this tick
                        def do_bwd():
                            k_b = jax.random.fold_in(k0, m_b)
                            (_out_p, loss_p), vjp_fn = jax.vjp(
                                lambda p3, x: full_fn(p3, x, m_b, c_b,
                                                      k_b),
                                (pe, chunk_slice(pb, c_b), ph), x_saved)
                            g_out = jnp.where(
                                jnp.logical_and(stage == pp - 1,
                                                c_b == v - 1),
                                jnp.zeros_like(_out_p),
                                grad_in.astype(_out_p.dtype))
                            d_p3, dx = vjp_fn((g_out, grad_cot()))
                            return d_p3, dx, loss_p

                        def skip_bwd():
                            return ((jax.tree_util.tree_map(
                                jnp.zeros_like, pe),
                                jax.tree_util.tree_map(
                                    lambda l: jnp.zeros(
                                        (per,) + l.shape[1:], l.dtype),
                                    pb),
                                jax.tree_util.tree_map(
                                    jnp.zeros_like, ph)),
                                jnp.zeros(act_shape, act_dtype),
                                jnp.asarray(0.0, jnp.float32))

                        (d_pe, d_pbc, d_ph), dx, loss_p = lax.cond(
                            t >= D - (pp - 1), do_bwd, skip_bwd)
                        gacc = (accum_full(gacc[0], d_pe, b_active),
                                accum_chunk(gacc[1], d_pbc, c_b,
                                            b_active),
                                accum_full(gacc[2], d_ph, b_active))
                        loss_acc = loss_acc + jnp.where(b_active, loss_p,
                                                        0.0)
                        dx = jnp.where(b_active, dx, jnp.zeros_like(dx))

                        if pp > 1:
                            nxt_act = lax.ppermute(
                                out_f, 'pp',
                                [(i, (i + 1) % pp) for i in range(pp)])
                            nxt_grad = lax.ppermute(
                                dx, 'pp',
                                [(i, (i - 1) % pp) for i in range(pp)])
                        else:
                            nxt_act, nxt_grad = out_f, dx
                        return (nxt_act, nxt_grad, buf, gacc,
                                loss_acc), None

                (_, _, _, gacc, loss_sum), _ = lax.scan(
                    tick, carry0, jnp.arange(T))
                grads = {'embed': gacc[0], 'blocks': gacc[1],
                         'head': gacc[2]}
                return self._reduce_and_update(
                    params, states, loss_sum / A, grads, lr, dp_on,
                    scale=scale if use_scaling else None)

        return self._finalize(step, dp_on)

    def _build_fthenb(self):
        A, pp = self.A, self.pp
        axes = self.axes
        embed, head = self.embed, self.head
        dp_on = 'dp' in axes and self.mesh.shape['dp'] > 1
        use_scaling = self._use_scaling
        stage_forward = self._make_stage_forward()

        def step(params, states, lr, scale, key, input_ids, labels):
            with C.spmd_region(axes):
                params = self._materialize_params(params)
                stage = lax.axis_index('pp') if pp > 1 else 0
                mb = input_ids.shape[0] // A

                def loss_of(ps):
                    pe, pb, ph = ps['embed'], ps['blocks'], ps['head']
                    k0 = key
                    if dp_on:
                        k0 = jax.random.fold_in(k0, lax.axis_index('dp'))

                    # Embed all microbatches — only stage 0 pays for it
                    # (stage==0 is uniform across each mp group, so the
                    # vocab-parallel psum inside the cond is deadlock-free).
                    def do_embed(_):
                        with bind_arrays(embed, pe):
                            with rng_mod.rng_guard(
                                    jax.random.fold_in(k0, 17)), \
                                    autograd.no_grad():
                                return embed(Tensor(input_ids)).data
                    H = None  # resolved below via eval_shape
                    emb_shape = jax.eval_shape(do_embed, 0)
                    if pp > 1:
                        emb_all = lax.cond(
                            stage == 0, do_embed,
                            lambda _: jnp.zeros(emb_shape.shape,
                                                emb_shape.dtype), 0)
                    else:
                        emb_all = do_embed(0)
                    emb_all = emb_all.reshape(A, mb, *emb_all.shape[1:])
                    labels_mb = labels.reshape(A, mb, *labels.shape[1:])

                    Lseq = emb_all.shape[2]
                    act0 = jnp.zeros((mb, Lseq, emb_all.shape[-1]),
                                     emb_all.dtype)
                    loss0 = jnp.asarray(0.0, jnp.float32)

                    def tick(carry, t):
                        act, loss_acc = carry
                        # stage 0 ingests microbatch t (clamped)
                        t_in = jnp.clip(t, 0, A - 1)
                        my_in = jnp.where(stage == 0,
                                          emb_all[t_in], act)
                        tick_key = jax.random.fold_in(k0, t)
                        out = stage_forward(pb, my_in, tick_key)
                        # last stage consumes microbatch t-(pp-1)
                        t_out = jnp.clip(t - (pp - 1), 0, A - 1)

                        def do_head(o):
                            with bind_arrays(head, ph):
                                with rng_mod.rng_guard(
                                        jax.random.fold_in(k0, 7919)), \
                                        autograd.no_grad():
                                    return head(
                                        Tensor(o),
                                        Tensor(labels_mb[t_out])).data \
                                        .astype(jnp.float32)
                        valid = ((stage == pp - 1) &
                                 (t >= pp - 1) & (t - (pp - 1) < A))
                        if pp > 1:
                            mb_loss = lax.cond(
                                valid, do_head,
                                lambda o: jnp.asarray(0.0, jnp.float32),
                                out)
                        else:
                            mb_loss = jnp.where(valid, do_head(out), 0.0)
                        loss_acc = loss_acc + mb_loss
                        # rotate activations to the next stage
                        if pp > 1:
                            nxt = lax.ppermute(
                                out, 'pp',
                                [(i, (i + 1) % pp) for i in range(pp)])
                        else:
                            nxt = out
                        return (nxt, loss_acc), None

                    (act, loss_sum), _ = lax.scan(
                        tick, (act0, loss0), jnp.arange(A + pp - 1))
                    # Return the LOCAL loss (nonzero only on the last
                    # stage). Reducing it here would run the psum transpose
                    # under every device's cotangent seed and scale grads by
                    # the stage count; value-level reductions happen after
                    # value_and_grad.
                    return loss_sum / A

                if use_scaling:
                    loss, grads = jax.value_and_grad(
                        lambda ps: loss_of(ps)
                        * scale.astype(jnp.float32))(params)
                    loss = loss / scale.astype(jnp.float32)
                else:
                    loss, grads = jax.value_and_grad(loss_of)(params)
                return self._reduce_and_update(
                    params, states, loss, grads, lr, dp_on,
                    scale=scale if use_scaling else None)

        return self._finalize(step, dp_on)

    @jax.named_scope('optimizer')
    def _update_one(self, p, g, st, lr):
        opt = self.optimizer
        low = p.dtype != jnp.float32
        master = st.pop('master', None)
        p32 = master if master is not None else (
            p.astype(jnp.float32) if low else p)
        g32 = g.astype(jnp.float32)
        wd = getattr(opt, '_weight_decay', None)
        if wd and opt._decay_into_grad():
            g32 = g32 + wd * p32
        np_, ns = opt.update(p32, g32, st, lr)
        ns = dict(ns)
        if master is not None:
            ns['master'] = np_
        return np_.astype(p.dtype), ns

    # ------------------------------------------------------------------------
    def _dispatch(self, data, scale=None, scaler=None):
        """Dispatch one pipelined step; returns an AsyncResult holding
        the device-resident loss + found-inf flag (+ taps). Deferred
        drain work: taps processing and — when a GradScaler rides along
        — its found-inf accounting, applied in submission order."""
        self._ensure_open()
        # gap bracket opens BEFORE any jax client call (batch asarray,
        # key fold-in, scale placement can serialize behind in-flight
        # compute — dispatch time, not inter-dispatch host gap)
        self._gap.dispatch_begin()
        if scaler is not None and scale is None \
                and scaler.is_enable():
            scale = scaler._scale
        input_ids, labels = data
        ii = input_ids.data if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        ll = labels.data if isinstance(labels, Tensor) \
            else jnp.asarray(labels)
        self._ledger.observe_batch(ii.shape)
        # microbatching contract, checked up front: the step reshapes
        # each dp rank's slice to [A, mb, ...] — a bad batch size used
        # to surface as an opaque reshape traceback from inside the
        # compiled step trace
        n = int(ii.shape[0]) if ii.ndim else 0
        if ll.ndim == 0 or int(ll.shape[0]) != n:
            raise PipelineBatchError(
                f"inputs and labels disagree on the batch dimension: "
                f"{tuple(ii.shape)} vs {tuple(ll.shape)}")
        dp = max(self.dp, 1)
        if n == 0 or n % (dp * self.A):
            raise PipelineBatchError(
                f"batch size {n} is not divisible by dp({dp}) x "
                f"accumulate_steps({self.A}); feed dp * A * "
                "micro_batch_size rows per step (adjust "
                "accumulate_steps or pipeline_configs)")
        want_scaling = scale is not None
        if not hasattr(self, '_compiled_by_mode'):
            self._compiled_by_mode = {}
        from ....core import memory as _mem
        if not hasattr(self, '_taps_on'):
            # latched at first build (taps change the compiled output
            # signature — set FLAGS before the first train_batch)
            from ....core import numerics as _num
            self._taps_on = _num.taps_enabled()
        if want_scaling != self._use_scaling or self._compiled is None:
            self._use_scaling = want_scaling
            # two-slot cache: alternating scaled/unscaled steps must not
            # recompile the pipeline each switch
            self._compiled = self._compiled_by_mode.get(want_scaling)
            if self._compiled is None:
                from .... import profiler as _prof
                with _prof.RecordEvent('pipeline::build',
                                       event_type='compile',
                                       pp=self.pp,
                                       scaling=want_scaling), \
                        _mem.phase('pipeline.build'):
                    self._compiled = self._build()
                self._compiled_by_mode[want_scaling] = self._compiled
        lr = self._lr.arg()
        sc = jnp.asarray(1.0 if scale is None else float(scale),
                         jnp.float32)
        key = rng_mod.next_key()
        from .... import profiler as _prof
        # each MODE's executable compiles on its first dispatch (minutes
        # at GPT scale; a later scaled/unscaled switch compiles again) —
        # _step_guard journals/heartbeats only warm dispatches
        if not hasattr(self, '_warm_modes'):
            self._warm_modes = set()
        first = want_scaling not in self._warm_modes
        args = (self._params, self._states, lr, sc, key, ii, ll)
        if not hasattr(self, '_exec_by_mode'):
            self._exec_by_mode = {}
        exe = self._exec_by_mode.get(want_scaling)
        if exe is None:
            # explicit AOT compile: lower/compile telemetry + the
            # buffer-assignment activation census
            # (ptpu_mem_activation_bytes; docs/performance.md
            # #remat-policy) for the pipeline step program
            while True:
                try:
                    exe, _ = _prof.compile_with_telemetry(
                        self._compiled, 'pipeline.step', args)
                    break
                except Exception as e:
                    # the fitted remat policy did not fit after all:
                    # the next one, traced and compiled again
                    if not self._remat_fall_back(e):
                        raise
                    self._compiled = self._compiled_by_mode[
                        want_scaling] = self._build()
            self._exec_by_mode[want_scaling] = exe
        with _prof.RecordEvent('pipeline::train_step', event_type='jit'), \
                self._step_guard(first, 'pipeline.train_step',
                                 'pipeline.step'):
            try:
                out = exe(*args)
            except TypeError:
                # AOT signature drift: fall back to the jitted fn
                if exe is self._compiled:
                    raise
                self._exec_by_mode[want_scaling] = self._compiled
                out = self._compiled(*args)
        self._gap.dispatch_end(depth=len(self._inflight) + 1)
        step_no = self._pp_step = getattr(self, '_pp_step', 0) + 1
        loss, self._params, self._states, found = out[:4]
        i = 4
        if self._lr.fn is not None:
            self._lr.carry = out[i]
            i += 1
        taps = out[i] if self._taps_on else None
        self._warm_modes.add(want_scaling)
        self.last_found_inf = found
        on_drain = None
        if taps is not None or scaler is not None:
            def on_drain(res, _t=taps, _s=step_no, _scaler=scaler):
                found_host = None
                if _t is not None:
                    found_host = self._process_taps(res.found_inf, _t,
                                                    step=_s)
                    self.last_found_inf = found_host
                if _scaler is not None:
                    if found_host is None:
                        from ....core import numerics as _num
                        found_host = bool(np.asarray(
                            _num._host_fetch(res.found_inf)))
                    # deferred found-inf accounting (ISSUE 13): same
                    # sequence the per-step path applies, at drain
                    _scaler.update_from_found(bool(found_host))
        return A_.AsyncResult(loss, step_no, found_inf=found, taps=taps,
                              on_drain=on_drain, monitor=self._gap)

    def train_batch(self, data, scale=None):
        """data = (input_ids, labels) covering dp_degree × A × micro_bs.
        `scale`: optional loss-scaling factor (fp16 GradScaler path); the
        step unscales grads, skips the update on non-finite gradients,
        and records `self.last_found_inf` for the scaler's dynamic
        update."""
        if len(self._inflight):
            # mixed APIs: drain queued async steps FIRST so deferred
            # work (taps/scaler accounting) keeps submission order
            self.flush()
        res = self._dispatch(data, scale=scale)
        res.wait()     # legacy per-step semantics (taps processed now)
        return Tensor(res.loss)

    def train_step(self, data, scaler=None):
        """Async dispatch (docs/performance.md#async-dispatch): returns
        an AsyncResult with the device-resident loss and found-inf flag
        — no host fetch. A GradScaler passed here has its found-inf read
        and dynamic-scale update deferred to the window-drain point, in
        submission order: the skip accounting is exact for the scales
        actually dispatched, but a scale CHANGE only reaches steps
        dispatched after its drain (up to `window` steps later than the
        per-step path — scale-induced overflows can therefore resolve
        one window later; docs/performance.md#async-dispatch).
        `flush()` drains everything."""
        from .... import profiler as _prof
        with _prof.RecordEvent('train::dispatch', event_type='train',
                               engine='pipeline',
                               step=getattr(self, '_pp_step', 0) + 1):
            return self._inflight.push(
                self._dispatch(data, scaler=scaler))

    def input_sharding(self, index, ndim):
        """DeviceLoader contract: batch tensors are dp-sharded on axis 0
        (replicated when dp=1)."""
        dp_on = 'dp' in self.axes and self.mesh.shape['dp'] > 1
        return NamedSharding(self.mesh, P('dp') if dp_on else P())

    def _process_taps(self, found, taps, step=None):
        """Fetch found_inf + the taps pytree in ONE host sync; returns
        the host-side found flag for last_found_inf."""
        from ....core import numerics as _num
        found_host, taps_host = _num._host_fetch((found, taps))
        if bool(found_host):
            # loss-scale overflow the compiled step already survived
            # (update skipped via found_inf): the post-unscale grads are
            # nonfinite BY DESIGN — raising NumericsError here, or
            # folding inf into the grad-norm gauges/histogram, would
            # punish the GradScaler's routine scale probe (the eager AMP
            # skip path drops the guard state for the same reason)
            self.last_numerics = None
            return found_host
        taps = taps_host    # already on host: the fetch inside
                            # process_jit_taps is a free no-op
        meta = {kind: dict(self._tap_shapes)
                for kind in ('grads', 'params')}
        self.last_numerics = _num.process_jit_taps(
            taps, site='pipeline',
            step=getattr(self, '_pp_step', None) if step is None
            else step,
            meta=meta)
        return found_host

    def sync_model(self):
        self._ensure_open()
        self.flush()    # every dispatched step lands before the copy-out
        for n, p in self._embed_named:
            if n in self._params['embed']:
                p._data = self._params['embed'][n]
        for n, p in self._head_named:
            if n in self._params['head']:
                p._data = self._params['head'][n]
        # stacked row i holds blocks[self._layer_order[i]] (chunk-major
        # under the interleaved schedule; identity otherwise)
        for row, j in enumerate(self._layer_order):
            lookup = dict(self.blocks[j].named_parameters())
            for n, _ in self._block_named:
                if n in self._params['blocks']:
                    lookup[n]._data = self._params['blocks'][n][row]
        if getattr(self, '_pp_overlap', False):
            # reconstruct bucketed params from the [pp, size] flat
            # shards: blocks rows are stage-local slices in stage
            # order; embed/head rows replicate (row 0 is the value).
            # These are the EXACT updated values — under an int8 wire
            # the compiled forward sees the block-rounded gathered
            # copy, but the shards are the trajectory
            # (docs/performance.md#comm-overlap).
            pp = max(self.pp, 1)
            blk_lookup = [dict(b.named_parameters())
                          for b in self.blocks]
            for b, sh in zip(self._pp_layout.buckets,
                             self._params['_shards']):
                host = np.asarray(jax.device_get(sh))  # [pp, size]
                for s in b.slots:
                    grp, n = s.name.split('/', 1)
                    if grp == 'blocks':
                        per = s.shape[0]
                        for k in range(pp):
                            rows = host[k, s.offset:s.offset + s.size] \
                                .reshape(s.shape)
                            for j in range(per):
                                blk_lookup[self._layer_order[
                                    k * per + j]][n]._data = \
                                    jnp.asarray(rows[j])
                    else:
                        named = dict(self._embed_named if grp == 'embed'
                                     else self._head_named)
                        named[n]._data = jnp.asarray(
                            host[0, s.offset:s.offset + s.size]
                            .reshape(s.shape))

    # shutdown()/close() from EngineTeardown
