"""Hybrid-parallel SPMD train step (DP × TP × ZeRO sharding).

Reference parity: the execution semantics of fleet's hybrid dygraph engines —
DataParallel grad allreduce (imperative/reducer.cc), TensorParallel
(mp_layers + mp ring collectives), DygraphShardingOptimizer ZeRO-1
(dygraph_sharding_optimizer.py:27) — composed per the topology's axis layout
(SURVEY.md A.1).

TPU-native design: ONE `jax.jit(shard_map(step))` over the registered Mesh.
  * batch sharded over ('dp','sharding') on axis 0 — ZeRO ranks ARE
    data-parallel ranks; params replicated over both;
  * TP params sharded over 'mp' at their `split_axis` (mp_layers emit the
    explicit collectives inside the traced forward);
  * ZeRO-1: optimizer states (incl. fp32 master weights) sharded over
    'sharding'; grads reduce-scattered, the local param shard updated, and
    params all-gathered — the reduce-scatter/all-gather placement matches
    the automatic cross-replica weight-update sharding technique
    (arXiv:2004.13336) and ShardingOptimizer's broadcast/reduce vocabulary;
  * dp grad sync is a single fused pmean per param (XLA coalesces —
    the FusedAllReduce equivalent).
All of forward, backward (jax.grad at trace level), collectives, and the
optimizer fuse into one XLA executable with donated buffers.
"""
import functools

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




def _param_spec(p, mesh_axes, zero_axis=None):
    """PartitionSpec for a parameter array."""
    ndim = len(p.data.shape)
    spec = [None] * ndim
    if getattr(p, 'is_distributed', False) and 'mp' in mesh_axes:
        spec[p.split_axis] = 'mp'
    return P(*spec)


from .meta_parallel_base import EngineTeardown


class HybridParallelTrainStep(A_.AsyncDispatchMixin, EngineTeardown):
    """Compile a full train step over the registered mesh.

    loss_fn(model, *batch) -> scalar loss Tensor. Batch tensors are sharded
    on axis 0 over ('dp','sharding') — leading batch dims must divide
    dp*sharding_degree; when the mesh has sp>1 (and the model declares
    _supports_sequence_parallel), every batch tensor of rank >= 2 is ALSO
    sharded on axis 1 over 'sp' — pass `sp_shard_args` (a set of positional
    batch indices) to restrict sequence sharding to the token-aligned
    tensors if the loss takes non-sequence rank-2 inputs.
    """

    def __init__(self, model, loss_fn, optimizer, mesh=None,
                 accumulate_steps=1, use_remat=False, sp_shard_args=None,
                 use_buckets=None, comm_dtype=None, bucket_mb=None,
                 comm_block=None, comm_overlap=None, prefetch_depth=None,
                 comm_chunk=None, remat_policy=None,
                 sequence_parallel=None, dispatch_window=None,
                 device_lr=None):
        self.sp_shard_args = sp_shard_args
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.mesh = mesh if mesh is not None else topology_runtime.get_mesh()
        if self.mesh is None:
            raise ValueError("no mesh registered; fleet.init with "
                             "hybrid_configs first or build_mesh()")
        self.axes = tuple(self.mesh.axis_names)
        if 'pp' in self.axes and self.mesh.shape['pp'] > 1:
            raise ValueError("pp>1: use SpmdPipelineEngine")
        self.accumulate_steps = accumulate_steps
        # tuned remat (docs/performance.md#remat-policy): kwarg -> env ->
        # strategy; the legacy `use_remat` bool only sets the default
        from ..utils.recompute import resolve_policy as _resolve_remat
        self._remat_policy = _resolve_remat(
            remat_policy, default='full' if use_remat else 'none')
        self.use_remat = self._remat_policy != 'none'
        self.dp = self.mesh.shape.get('dp', 1)
        self.sharding_deg = self.mesh.shape.get('sharding', 1)
        self.mp = self.mesh.shape.get('mp', 1)
        self.sp = self.mesh.shape.get('sp', 1)
        # Megatron-style sequence-parallel activation sharding
        # (docs/performance.md#sequence-parallel-activations): the
        # LayerNorm/dropout/residual segments between mp regions run on
        # token slices scattered over the mp group — only meaningful
        # with a live mp axis and a model that declares support
        self._seq_parallel = bool(
            C.resolve_sequence_parallel(sequence_parallel)
            and 'mp' in self.axes and self.mp > 1
            and getattr(model, '_supports_sequence_parallel', False))
        # params the model consumes on the SCATTERED token stream
        # (LayerNorms, row-parallel biases): their per-rank grads cover
        # only the local token slice, so the step psums them over 'mp'
        # to restore the full-token gradient the replicated route gets
        self._seq_grad_names = frozenset(
            n for n, p in model.named_parameters()
            if getattr(p, 'sequence_parallel_grad', False)
        ) if self._seq_parallel else frozenset()

        named = [(n, p) for n, p in model.named_parameters()
                 if not p.stop_gradient]
        self._names = [n for n, _ in named]
        self._params_by_name = dict(named)
        self._param_specs = {n: _param_spec(p, self.axes)
                             for n, p in named}
        # ZeRO eligibility: shard optimizer state over 'sharding' on axis 0
        self._zero_ok = {}
        for n, p in named:
            shp = p.data.shape
            ok = (self.sharding_deg > 1 and len(shp) >= 1
                  and shp[0] % self.sharding_deg == 0
                  and not (getattr(p, 'is_distributed', False)
                           and p.split_axis == 0))
            self._zero_ok[n] = ok

        # -- bucketed rs/ag weight-update sharding (arXiv:2004.13336) ------
        # data-parallel replication axes: every rank along them holds the
        # same params and a different batch shard — grads mean-reduce over
        # them and the weight update can shard 1/n per rank.
        self._rs_axes = tuple(a for a in ('dp', 'sharding', 'sp')
                              if a in self.axes and self.mesh.shape[a] > 1)
        self._n_shards = int(np.prod([self.mesh.shape[a]
                                      for a in self._rs_axes] or [1]))
        self.comm_dtype, self._bucket_bytes = B.resolve_comm_config(
            comm_dtype, bucket_mb)
        self._comm_block = B.resolve_comm_block(comm_block)
        # comm/compute overlap (ISSUE 10): layer-grouped buckets +
        # eager reduce-scatter + deferred/prefetched param all-gather.
        # Grouping only engages when there is real comm to overlap
        # (n_shards > 1) so the dp=1 compiled program stays unchanged.
        overlap_req, self._prefetch_depth, self._comm_chunk = \
            B.resolve_overlap_config(comm_overlap, prefetch_depth,
                                     comm_chunk)
        # mp-sharded params are already distributed (their state shards
        # with them); they keep the per-param path
        bucketable = [n for n, p in named
                      if not (getattr(p, 'is_distributed', False)
                              and 'mp' in self.axes and self.mp > 1)]
        self._layout = None
        if bucketable and B.elementwise(optimizer):
            self._layout = B.BucketLayout.build(
                {n: (self._params_by_name[n].data.shape,
                     self._params_by_name[n].data.dtype)
                 for n in bucketable},
                bucket_bytes=self._bucket_bytes,
                pad_to=max(self._n_shards, 1) * 8,
                group_fn=(B.layer_group_fn
                          if overlap_req and self._n_shards > 1
                          else None))
        self._bucketed = bool(
            self._layout is not None and self._n_shards > 1
            and use_buckets is not False)
        self._overlap = bool(overlap_req and self._bucketed)
        if self._overlap:
            B.ensure_overlap_xla_flags()
        if self._layout is not None:
            B.publish_comm_gauges(self._layout, engine='hybrid',
                                  n_shards=max(self._n_shards, 1),
                                  comm_dtype=self.comm_dtype,
                                  enabled=self._bucketed,
                                  block=self._comm_block)
            B.publish_overlap_gauges(self._layout, engine='hybrid',
                                     n_shards=max(self._n_shards, 1),
                                     comm_dtype=self.comm_dtype,
                                     enabled=self._overlap,
                                     prefetch=self._prefetch_depth,
                                     chunk=self._comm_chunk,
                                     block=self._comm_block)
        if not self._bucketed:
            self._layout = None

        from ....core import memory as _mem
        with _mem.phase('engine.init'):
            # deferred gather: bucketed params live as flat 1/n SHARDS
            # between steps (ZeRO-3-style resident set); the full
            # replica only exists transiently inside the step, gathered
            # group-by-group just before first use
            slot_names = set(self._layout.slots) if self._overlap \
                else set()
            self._params = {n: self._place(p.data, self._param_specs[n])
                            for n, p in named if n not in slot_names}
            self._param_shards = []
            if self._overlap:
                shard_spec = P(self._rs_axes)
                for b in self._layout.buckets:
                    host = np.zeros((b.size,), b.dtype)
                    for s in b.slots:
                        host[s.offset:s.offset + s.size] = np.asarray(
                            jax.device_get(
                                self._params_by_name[s.name].data)
                        ).reshape(-1).astype(b.dtype)
                    self._param_shards.append(
                        self._place_flat(host, shard_spec))
            self._states = {'named': {}, 'buckets': []}
            self._state_specs = {'named': {}, 'buckets': []}
            legacy_names = set(self._names) if not self._bucketed else \
                set(self._names) - set(self._layout.slots)
            for n, p in named:
                if n not in legacy_names:
                    continue
                st = optimizer.init_state(p)
                if p.data.dtype != jnp.float32 and \
                        getattr(optimizer, '_multi_precision', True):
                    st['master'] = p.data.astype(jnp.float32)
                sspec = {}
                for k, v in st.items():
                    if self._zero_ok[n] and np.ndim(v) >= 1 \
                            and v.shape == p.data.shape:
                        # slice the state to this sharding rank
                        axes0 = list(self._param_specs[n])
                        axes0[0] = 'sharding'
                        sspec[k] = P(*axes0)
                    else:
                        sspec[k] = self._param_specs[n] if (
                            np.ndim(v) >= 1 and v.shape == p.data.shape) \
                            else P()
                    st[k] = self._place(v, sspec[k])
                self._states['named'][n] = st
                self._state_specs['named'][n] = sspec
            if self._bucketed:
                self._init_flat_states()

        self._grad_clip = optimizer._grad_clip
        self._compiled = None
        self._exec = None
        self._closed = False
        self._step_count = 0

        # -- async step pipeline (ISSUE 13,
        # docs/performance.md#async-dispatch): bounded in-flight dispatch
        # window + host-gap instrumentation + on-device LR schedule ------
        self._inflight = A_.DispatchWindow(
            A_.resolve_dispatch_window(dispatch_window))
        self._gap = A_.HostGapMonitor('hybrid')
        # step-time ledger (ISSUE 16): reconciled wall decomposition +
        # model-FLOPs accounting, published from flush()
        from ....core import ledger as _led
        self._ledger = _led.StepLedger(
            'hybrid', gap=self._gap,
            params_fn=lambda: _led.count_params(
                list(self._params_by_name.values())),
            remat_policy=self._remat_policy)
        # batch input specs are init-time facts (DeviceLoader asks for
        # them before the first dispatch)
        self._sp_on = ('sp' in self.axes and self.sp > 1
                       and getattr(model, '_supports_sequence_parallel',
                                   False))
        self._batch_axes = tuple(a for a in ('dp', 'sharding')
                                 if a in self.axes
                                 and self.mesh.shape[a] > 1)
        from ....optimizer import device_lr as _dlr
        self._lr = _dlr.LrFeed(optimizer, device_lr,
                               place=lambda a: self._place(a, P()))

    def _init_flat_states(self):
        """Sharded flat optimizer state, one entry per bucket: vector
        states (moments, fp32 master) are GLOBAL 1-D arrays of the
        bucket's padded length sharded over the dp axes — each rank
        materializes only its 1/n shard (ZeRO-1); scalars (beta powers)
        replicate. Built via make_array_from_callback so no device ever
        holds a full fp32 replica."""
        opt = self.optimizer
        shard_spec = P(self._rs_axes)
        for b in self._layout.buckets:
            flat32 = np.zeros((b.size,), np.float32)
            for s in b.slots:
                flat32[s.offset:s.offset + s.size] = np.asarray(
                    jax.device_get(self._params_by_name[s.name].data),
                    np.float32).reshape(-1)
            st = B.init_bucket_state(
                opt, b, flat32,
                force_master=B._is_int8(self.comm_dtype))
            placed, sspec = {}, {}
            for k, v in st.items():
                if np.ndim(v) >= 1:
                    placed[k] = self._place_flat(v, shard_spec)
                    sspec[k] = shard_spec
                else:
                    placed[k] = self._place(v, P())
                    sspec[k] = P()
            self._states['buckets'].append(placed)
            self._state_specs['buckets'].append(sspec)

    def _place_flat(self, host_arr, spec):
        host_arr = np.asarray(host_arr)
        sh = NamedSharding(self.mesh, spec)
        return jax.make_array_from_callback(
            host_arr.shape, sh, lambda idx: host_arr[idx])

    def _place(self, arr, spec):
        # copy before placing: device_put to a (partially) replicated
        # sharding can alias the source buffer, and the jitted step DONATES
        # these arrays — aliasing would free the model's eager params.
        return jax.device_put(jnp.array(arr, copy=True),
                              NamedSharding(self.mesh, spec))

    # -- the SPMD step --------------------------------------------------------
    def _build(self):
        model, loss_fn, opt = self.model, self.loss_fn, self.optimizer
        axes = self.axes
        # numerics taps (core/numerics.py): latched at build — the taps
        # change the compiled step's output signature, so flip the flag
        # BEFORE the first dispatch (a later flip needs a new engine)
        from ....core import numerics as _num
        taps_on = self._taps_on = _num.taps_enabled()
        # axes whose shards see different data → loss/grad pmean + distinct
        # dropout keys ('sp' chunks are different tokens, like dp shards).
        # Must stay the SAME axis set the bucket reduce_scatter and the
        # P(_rs_axes) flat-state sharding use, or grads and params desync.
        dp_axes = self._rs_axes
        zero_ok = self._zero_ok
        s = self.sharding_deg
        from ..utils.recompute import apply_policy as _apply_remat
        remat_policy = self._remat_policy
        seq_parallel = self._seq_parallel

        def global_norm_sq(grads):
            """Mesh-wide global grad-norm^2: mp-sharded params psum
            their local sum of squares (shared by taps + clip)."""
            sq_d = jnp.asarray(0.0, jnp.float32)
            sq_r = jnp.asarray(0.0, jnp.float32)
            for n, g in grads.items():
                p = self._params_by_name[n]
                v = jnp.sum(g.astype(jnp.float32) ** 2)
                if getattr(p, 'is_distributed', False) and 'mp' in axes:
                    sq_d = sq_d + v
                else:
                    sq_r = sq_r + v
            if 'mp' in axes and self.mp > 1:
                sq_d = lax.psum(sq_d, 'mp')
            return sq_d + sq_r

        bucketed = self._bucketed
        layout = self._layout
        rs_axes = self._rs_axes
        n_shards = self._n_shards
        comm_dtype = self.comm_dtype
        comm_block = self._comm_block
        overlap = self._overlap
        prefetch_depth = self._prefetch_depth
        comm_chunk = self._comm_chunk

        def clip_factor(gn_sq_val):
            from ....nn.clip import ClipGradByGlobalNorm
            if self._grad_clip is None:
                return None
            if not (isinstance(self._grad_clip, ClipGradByGlobalNorm)
                    or hasattr(self._grad_clip, '_clip')):
                return None
            clip_norm = getattr(self._grad_clip, 'clip_norm',
                                None) or getattr(
                    getattr(self._grad_clip, '_clip', None),
                    'clip_norm', 1.0)
            gn = jnp.sqrt(gn_sq_val)
            return factor_from(gn, clip_norm)

        def factor_from(gn, clip_norm):
            return clip_norm / jnp.maximum(gn, clip_norm)

        def step(params, states, lr, key, *batch):
            with C.spmd_region(axes, sp_data_sharded=sp_on,
                               mp_seq_parallel=seq_parallel):
                # -- deferred/prefetched param all-gather (overlap
                # mode): bucketed params arrive as 1/n shards; rebuild
                # the working replica group-by-group IN LAYER ORDER at
                # the top of the step, where the latency-hiding
                # scheduler can run group g's gather under the forward
                # compute of groups < g. `prefetch_depth` bounds the
                # in-flight window: an optimization_barrier makes
                # gather g data-depend on gather g-depth, so at most
                # `depth` full groups are live beyond the shards.
                shards_in = None
                if overlap:
                    shards_in = params['shards']
                    gathered_p = B.gather_groups(
                        shards_in, rs_axes, n_shards,
                        comm_dtype=comm_dtype, block=comm_block,
                        chunk=comm_chunk, prefetch=prefetch_depth)
                    params = dict(params['named'])
                    params.update(layout.unflatten(gathered_p))

                def loss_of(ps):
                    with bind_arrays(model, ps):
                        # fold data-parallel position into the key so dp
                        # shards draw different dropout masks; mp ranks share
                        # the key (TP-consistent dropout — A.5; per-rank
                        # divergence goes through the RNGStatesTracker)
                        k = key
                        for a in dp_axes:
                            k = jax.random.fold_in(k, lax.axis_index(a))
                        with rng_mod.rng_guard(k), autograd.no_grad():
                            loss = loss_fn(model, *[Tensor(b)
                                                    for b in batch])
                    return loss.data.astype(jnp.float32)

                lf = _apply_remat(loss_of, remat_policy,
                                             engine='hybrid')
                loss, raw_grads = jax.value_and_grad(lf)(params)
                if seq_parallel and self._seq_grad_names:
                    # scattered-segment params: sum the per-token-slice
                    # grads over the mp group (full-token gradient)
                    raw_grads = {
                        n: (lax.psum(g, 'mp')
                            if n in self._seq_grad_names else g)
                        for n, g in raw_grads.items()}
                if dp_axes:
                    loss = lax.pmean(loss, dp_axes)

                named_states = states['named']
                if not bucketed:
                    grads = raw_grads
                    if dp_axes:
                        grads = {n: lax.pmean(g, dp_axes)
                                 for n, g in grads.items()}

                    # numerics taps: PRE-CLIP grads (the clip below rebinds
                    # `grads` to a new dict) + the mesh-wide global
                    # grad-norm^2 (same reduction the clip uses)
                    gn_sq = None
                    preclip_grads = grads
                    if taps_on:
                        gn_sq = global_norm_sq(grads)

                    # mesh-aware global-norm clip (parity:
                    # HybridParallelClipGrad,
                    # hybrid_parallel_optimizer.py:32)
                    factor = clip_factor(
                        gn_sq if gn_sq is not None
                        else global_norm_sq(grads)) \
                        if self._grad_clip is not None else None
                    if factor is not None:
                        grads = {n: (g.astype(jnp.float32) * factor)
                                 .astype(g.dtype)
                                 for n, g in grads.items()}

                    new_params, new_named = {}, {}
                    for n, p in params.items():
                        g = grads[n]
                        st = dict(named_states[n])
                        if zero_ok[n] and 'sharding' in axes and s > 1:
                            # ZeRO-1: reduce-scatter grad, update local
                            # shard, all-gather updated param.
                            rows = p.shape[0] // s
                            idx = lax.axis_index('sharding')
                            g_shard = lax.dynamic_slice_in_dim(
                                g, idx * rows, rows, axis=0)
                            p_shard = lax.dynamic_slice_in_dim(
                                p, idx * rows, rows, axis=0)
                            np_, ns = self._update_one(p_shard, g_shard,
                                                       st, lr)
                            p_new = lax.all_gather(np_, 'sharding', axis=0,
                                                   tiled=True)
                        else:
                            p_new, ns = self._update_one(p, g, st, lr)
                        new_params[n] = p_new
                        new_named[n] = ns
                    new_states = {'named': new_named, 'buckets': []}
                    if taps_on:
                        taps = _num.jit_taps(preclip_grads, new_params,
                                             extra_norm_sq=gn_sq)
                        return loss, new_params, new_states, taps
                    return loss, new_params, new_states

                # -- bucketed path (arXiv:2004.13336): flatten grads into
                # dtype-homogeneous buckets, ONE reduce_scatter per bucket
                # over the dp axes (compressed wire under comm_dtype),
                # sharded optimizer update on this rank's 1/n slice, ONE
                # all_gather per bucket for the updated params -----------
                legacy = {n: g for n, g in raw_grads.items()
                          if n not in layout.slots}
                if dp_axes:
                    legacy = {n: lax.pmean(g, dp_axes)
                              for n, g in legacy.items()}
                # layer-grouped buckets: each flat bucket depends only
                # on ITS layers' grads, so its reduce-scatter is
                # emitted as soon as those grads exist instead of
                # serializing behind the full backward; `chunk` splits
                # oversized buckets into schedulable pieces
                flat_grads = layout.flatten(
                    {n: raw_grads[n] for n in layout.slots})
                shards32 = [B.reduce_scatter(f, rs_axes, n_shards,
                                             comm_dtype=comm_dtype,
                                             mean=True,
                                             block=comm_block,
                                             chunk=comm_chunk)
                            for f in flat_grads]

                # taps diagnostics mode pays an extra pmean to surface
                # fully-reduced per-param grads (the bucketed hot path
                # never materializes them)
                gn_sq = None
                preclip_grads = None
                if taps_on:
                    preclip_grads = dict(legacy)
                    preclip_grads.update(
                        {n: (lax.pmean(raw_grads[n], dp_axes)
                             if dp_axes else raw_grads[n])
                         for n in layout.slots})
                    gn_sq = global_norm_sq(preclip_grads)

                factor = None
                if self._grad_clip is not None:
                    # global grad-norm^2 from the bucket shards: shards
                    # are disjoint over the dp axes, so one psum restores
                    # the full sum; legacy (mp-sharded) params add their
                    # psum('mp') contribution exactly as the per-param
                    # path does. Each shard's contribution is ONE fused
                    # stats pass (Pallas kernel on TPU — the first leg
                    # of the fused optimizer step).
                    sq_local = sum(B.grad_stats(g)[0] for g in shards32) \
                        if shards32 else jnp.asarray(0.0, jnp.float32)
                    sq_b = lax.psum(sq_local, rs_axes) if rs_axes \
                        else sq_local
                    sq_b = sq_b + (global_norm_sq(legacy) if legacy
                                   else jnp.asarray(0.0, jnp.float32))
                    factor = clip_factor(sq_b)
                if factor is not None:
                    legacy = {n: (g.astype(jnp.float32) * factor)
                              .astype(g.dtype)
                              for n, g in legacy.items()}

                flat_params = None if overlap else layout.flatten(params)
                new_params, new_named = {}, {}
                new_buckets = []
                new_shards, gathered = [], []
                for gi, (b, g32, st) in enumerate(
                        zip(layout.buckets, shards32,
                            states['buckets'])):
                    # overlap: this rank's param shard IS the engine
                    # state (same values take_shard would slice out of
                    # the gathered replica — fp32/bf16 wires gather
                    # exactly, and under int8 the forced master makes
                    # the update independent of the working copy)
                    p_shard = shards_in[gi] if overlap else \
                        B.take_shard(flat_params[gi], rs_axes, n_shards)
                    # the clip multiply rides into the one-pass fused
                    # update as `prefactor` instead of a separate
                    # bucket-sized elementwise op
                    np_, ns = B.shard_update(self.optimizer, p_shard,
                                             g32, st, lr,
                                             prefactor=factor)
                    if overlap:
                        # deferred gather: the updated shard goes back
                        # out as engine state; its all-gather moves to
                        # the NEXT step's forward, just before first use
                        new_shards.append(np_)
                    else:
                        gathered.append(B.all_gather(
                            np_, rs_axes, comm_dtype=comm_dtype,
                            block=comm_block, chunk=comm_chunk,
                            n_shards=n_shards))
                    new_buckets.append(ns)
                if not overlap:
                    new_params.update(layout.unflatten(gathered))
                for n, g in legacy.items():
                    p = params[n]
                    st = dict(named_states[n])
                    if zero_ok[n] and 'sharding' in axes and s > 1:
                        # mp-sharded params keep the per-param ZeRO-1
                        # slice over 'sharding' (their states were
                        # created with that spec)
                        rows = p.shape[0] // s
                        idx = lax.axis_index('sharding')
                        g_shard = lax.dynamic_slice_in_dim(
                            g, idx * rows, rows, axis=0)
                        p_shard = lax.dynamic_slice_in_dim(
                            p, idx * rows, rows, axis=0)
                        np_, ns = self._update_one(p_shard, g_shard,
                                                   st, lr)
                        np_ = lax.all_gather(np_, 'sharding', axis=0,
                                             tiled=True)
                    else:
                        np_, ns = self._update_one(p, g, st, lr)
                    new_params[n] = np_
                    new_named[n] = ns
                new_states = {'named': new_named, 'buckets': new_buckets}
                out_params = {'named': new_params,
                              'shards': new_shards} if overlap \
                    else new_params
                if taps_on:
                    tap_params = new_params
                    if overlap:
                        # diagnostics mode pays the gather the hot path
                        # deferred, so per-param stats see full params
                        tap_params = dict(new_params)
                        tap_params.update(layout.unflatten(
                            B.gather_groups(new_shards, rs_axes,
                                            n_shards,
                                            comm_dtype=comm_dtype,
                                            block=comm_block,
                                            chunk=comm_chunk)))
                    taps = _num.jit_taps(preclip_grads, tap_params,
                                         extra_norm_sq=gn_sq)
                    return loss, out_params, new_states, taps
                return loss, out_params, new_states

        # sequence sharding only for models that declare support (GPT sets
        # _supports_sequence_parallel; others would silently attend within
        # chunks) — the mesh may still carry an sp axis for other tensors.
        sp_on = self._sp_on
        if 'sp' in axes and self.sp > 1 and not sp_on:
            raise ValueError(
                "mesh has sp>1 but the model does not declare "
                "_supports_sequence_parallel; sequence-sharding it would "
                "silently train wrong")
        batch_specs = tuple(self._input_spec(i, nd)
                            for i, nd in enumerate(self._batch_ndims))
        self._batch_specs = batch_specs
        if self._overlap:
            pspecs = {'named': {n: self._param_specs[n]
                                for n in self._params},
                      'shards': [P(self._rs_axes)
                                 for _ in self._layout.buckets]}
        else:
            pspecs = self._param_specs
        # on-device LR schedule: the lr argument becomes a device int32
        # step counter; the compiled step derives lr = fn(counter) and
        # returns counter+1 — no per-step host LR compute or H2D feed
        lr_fn = self._lr.fn
        if lr_fn is not None:
            base_step = step

            def step(params, states, step_c, key, *batch):
                out = base_step(params, states,
                                lr_fn(step_c).astype(jnp.float32),
                                key, *batch)
                return out[:3] + (step_c + 1,) + out[3:]

        in_specs = (pspecs, self._state_specs, P(), P(),
                    *batch_specs)
        out_specs = (P(), pspecs, self._state_specs)
        if lr_fn is not None:
            out_specs = out_specs + (P(),)
        if taps_on:
            names = list(self._names)
            out_specs = out_specs + (_num.taps_spec(
                {'grads': dict.fromkeys(names, 0),
                 'params': dict.fromkeys(names, 0),
                 'grad_norm_sq': 0}),)
        mapped = shard_map(step, mesh=self.mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
        return jax.jit(mapped, donate_argnums=(0, 1))

    @jax.named_scope('optimizer')
    def _update_one(self, p, g, st, lr):
        """Per-shard optimizer update with fp32 master handling (the same
        rule functional_apply uses, inlined for shard-level application)."""
        opt = self.optimizer
        low = p.dtype != jnp.float32
        master = st.pop('master', None)
        p32 = master if master is not None else (
            p.astype(jnp.float32) if low else p)
        g32 = g.astype(jnp.float32)
        wd = getattr(opt, '_weight_decay', None)
        if wd and opt._decay_into_grad():
            g32 = g32 + wd * p32
        if not st:
            st = opt.init_state(Tensor(p32))
        np_, ns = opt.update(p32, g32, st, lr)
        ns = dict(ns)
        if master is not None or (low and getattr(opt, '_multi_precision',
                                                  True)):
            ns['master'] = np_
        return np_.astype(p.dtype), ns

    # -- public ---------------------------------------------------------------
    def _dispatch(self, batch):
        """Dispatch one compiled step; returns an AsyncResult holding
        the device-resident loss (+ taps) — no host fetch."""
        arrays = tuple(b.data if isinstance(b, Tensor) else jnp.asarray(b)
                       for b in batch)
        ddeg = self.dp * self.sharding_deg
        for i, a in enumerate(arrays):
            if a.ndim >= 1 and a.shape[0] % ddeg != 0:
                raise ValueError(
                    f"batch arg {i} has leading dim {a.shape[0]}, not "
                    f"divisible by dp*sharding = {self.dp}*"
                    f"{self.sharding_deg} = {ddeg} (ZeRO 'sharding' "
                    f"ranks are data-parallel ranks)")
        self._ensure_open()
        if arrays:
            self._ledger.observe_batch(arrays[0].shape)
        # gap bracket opens BEFORE any jax client call (key fold-in, lr
        # placement can serialize behind in-flight compute — that time
        # belongs to the dispatch, not the inter-dispatch host gap)
        self._gap.dispatch_begin()
        from ....core import memory as _mem
        first = self._compiled is None   # this dispatch will XLA-compile
        if self._compiled is None:
            self._batch_ndims = tuple(a.ndim for a in arrays)
            with _mem.phase('pipeline.build'):
                self._compiled = self._build()
        lr = self._lr.arg()
        key = rng_mod.next_key()
        p_arg = {'named': self._params, 'shards': self._param_shards} \
            if self._overlap else self._params
        args = (p_arg, self._states, lr, key) + arrays
        if first:
            # explicit AOT compile: lower/compile spans + compile
            # seconds AND the buffer-assignment activation census
            # (ptpu_mem_activation_bytes — the resident bytes the remat
            # policy shrinks; docs/performance.md#remat-policy)
            from .... import profiler as _prof
            self._exec, _ = _prof.compile_with_telemetry(
                self._compiled, 'hybrid.step', args)
        with self._step_guard(first, 'hybrid.train_step', 'hybrid.step'):
            try:
                out = self._exec(*args)
            except TypeError:
                # AOT signature drift (e.g. a new batch shape): fall
                # back to the jitted fn, which retraces per signature
                if self._exec is self._compiled:
                    raise
                self._exec = self._compiled
                out = self._exec(*args)
        self._gap.dispatch_end(depth=len(self._inflight) + 1)
        loss, p_out, self._states = out[:3]
        i = 3
        if self._lr.fn is not None:
            self._lr.carry = out[i]
            i += 1
        taps = out[i] if getattr(self, '_taps_on', False) else None
        if self._overlap:
            self._params = p_out['named']
            self._param_shards = p_out['shards']
        else:
            self._params = p_out
        step_no = self._step_count
        self._step_count += 1
        on_drain = None
        if taps is not None:
            def on_drain(res, _t=taps, _s=step_no):
                self._process_taps(_t, 'hybrid', step=_s)
        return A_.AsyncResult(loss, step_no, taps=taps,
                              on_drain=on_drain, monitor=self._gap)

    def __call__(self, *batch):
        if len(self._inflight):
            # mixed APIs: drain queued async steps FIRST so deferred
            # work (taps/scaler accounting) keeps submission order
            self.flush()
        res = self._dispatch(batch)
        res.wait()     # legacy per-step semantics: taps processed now
        return Tensor(res.loss)

    def train_step(self, *batch):
        """Async dispatch (docs/performance.md#async-dispatch): returns
        an AsyncResult (device-resident loss, no host fetch); a bounded
        in-flight window (PTPU_DISPATCH_WINDOW) lets the host run ahead,
        draining the oldest step — and its deferred taps work — as the
        window fills. `flush()` drains everything."""
        from .... import profiler as _prof
        with _prof.RecordEvent('train::dispatch', event_type='train',
                               engine='hybrid', step=self._step_count):
            return self._inflight.push(self._dispatch(batch))

    # -- DeviceLoader contract ------------------------------------------------
    def _input_spec(self, idx, nd):
        dp_name = self._batch_axes if self._batch_axes else None
        shard_seq = self._sp_on and nd >= 2 and (
            self.sp_shard_args is None or idx in self.sp_shard_args)
        if shard_seq:
            return P(dp_name, 'sp')
        return P(dp_name) if dp_name else P()

    def input_sharding(self, index, ndim):
        """NamedSharding for batch argument `index` — the spec the
        compiled step expects, so DeviceLoader's background H2D lands
        batches pre-sharded."""
        return NamedSharding(self.mesh, self._input_spec(index, ndim))

    def _process_taps(self, taps, site, step=None):
        """One host sync for the step's stats pytree; publishes
        ptpu_num_* gauges and raises NumericsError on nonfinite grads
        (FLAGS_check_nan_inf) naming the offending parameter."""
        from ....core import numerics as _num
        meta = {'grads': {n: (p.data.shape, p.data.dtype)
                          for n, p in self._params_by_name.items()},
                'params': {n: (p.data.shape, p.data.dtype)
                           for n, p in self._params_by_name.items()}}
        self.last_numerics = _num.process_jit_taps(
            taps, site=site,
            step=self._step_count if step is None else step, meta=meta)

    def _host_bucket_params(self):
        """{name: host array} for bucketed slots, reconstructed from
        the flat param shards (overlap mode). These are the EXACT
        updated values — under an int8 wire the compiled forward sees
        the block-rounded gathered copy, but the shards (backed by the
        sharded fp32 master) are the trajectory, so checkpoints and
        sync_model round-trip without wire rounding
        (docs/performance.md#comm-overlap)."""
        out = {}
        for b, sh in zip(self._layout.buckets, self._param_shards):
            host = np.asarray(jax.device_get(sh))
            for s in b.slots:
                out[s.name] = host[s.offset:s.offset + s.size] \
                    .reshape(s.shape)
        return out

    def sync_model(self):
        """Write updated params back into the eager Layer. Drains the
        async dispatch window first so every dispatched step is
        reflected (docs/performance.md#async-dispatch drain semantics)."""
        self._ensure_open()
        self.flush()
        for n, arr in self._params.items():
            self._params_by_name[n]._data = arr
        if self._overlap:
            for n, arr in self._host_bucket_params().items():
                self._params_by_name[n]._data = jnp.asarray(arr)

    # shutdown()/close() from EngineTeardown

    @property
    def params(self):
        return self._params

    # -- checkpoint (parity: fleet.save/set_state_dict re-broadcast flow,
    # SURVEY.md §5.4) --------------------------------------------------------
    def state_dict(self):
        """Checkpoint in the stable PER-PARAMETER schema regardless of
        the runtime state layout: flat sharded bucket states are
        converted back through the layout map, so a checkpoint written
        by a bucketed engine restores into a legacy one and vice
        versa."""
        import numpy as _np
        import jax as _jax
        self.flush()        # checkpoints see every dispatched step
        out = {'params': {}, 'states': {}}
        for n, a in self._params.items():
            out['params'][n] = _np.asarray(_jax.device_get(a))
        if self._overlap:
            for n, a in self._host_bucket_params().items():
                out['params'][n] = _np.asarray(a)
        for n, st in self._states['named'].items():
            out['states'][n] = {k: _np.asarray(_jax.device_get(v))
                                for k, v in st.items()}
        if self._bucketed:
            host_flat = [{k: _np.asarray(_jax.device_get(v))
                          for k, v in st.items()}
                         for st in self._states['buckets']]
            out['states'].update(
                B.flat_states_to_named(self._layout, host_flat))
        out['step'] = self._step_count
        return out

    def set_state_dict(self, sd):
        import numpy as _np
        import jax as _jax
        for n, a in sd['params'].items():
            if n in self._params:
                self._params[n] = self._place(a, self._param_specs[n])
        if self._overlap:
            # rebuild the flat param shards from the per-param schema
            # (missing params keep their current shard values)
            shard_spec = P(self._rs_axes)
            for i, b in enumerate(self._layout.buckets):
                host = _np.array(
                    _jax.device_get(self._param_shards[i]), copy=True)
                touched = False
                for s in b.slots:
                    if s.name in sd['params']:
                        host[s.offset:s.offset + s.size] = _np.asarray(
                            sd['params'][s.name]).reshape(-1) \
                            .astype(host.dtype)
                        touched = True
                if touched:
                    self._param_shards[i] = self._place_flat(
                        host, shard_spec)
        named_sd = dict(sd.get('states', {}))
        if self._bucketed:
            template = [{k: _np.asarray(_jax.device_get(v))
                         for k, v in st.items()}
                        for st in self._states['buckets']]
            flat = B.named_states_to_flat(
                self._layout,
                {n: named_sd.pop(n) for n in list(named_sd)
                 if n in self._layout.slots},
                template)
            for i, st in enumerate(flat):
                for k, v in st.items():
                    spec = self._state_specs['buckets'][i][k]
                    self._states['buckets'][i][k] = (
                        self._place_flat(v, spec) if _np.ndim(v) >= 1
                        else self._place(v, spec))
        for n, st in named_sd.items():
            if n in self._states['named']:
                for k, v in st.items():
                    if k in self._state_specs['named'][n]:
                        self._states['named'][n][k] = self._place(
                            v, self._state_specs['named'][n][k])
        self._step_count = sd.get('step', 0)
        if self._lr.fn is not None:
            # re-sync the device LR counter to the (restored) host
            # scheduler's epoch — resume mid-schedule lands on the same
            # lr the host path would feed next
            self._lr.reset_carry()
