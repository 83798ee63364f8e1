"""Global flags registry.

Reference parity: platform/flags.cc (35 gflags DEFINEs) +
pybind/global_value_getter_setter.cc — `paddle.set_flags/get_flags` and
`FLAGS_*` env seeding. Flags that map to XLA/jax knobs apply them on set.
"""
import os

_FLAGS = {
    'FLAGS_check_nan_inf': False,
    # numerics observatory (core/numerics.py): defer the NaN/Inf sync to
    # the step boundary (optimizer.step / numerics.flush) — device-side
    # flag accumulation + ONE host sync per step, with replay-based op
    # localization on a trip. Off = legacy raise-at-the-op semantics.
    'FLAGS_check_nan_inf_deferred': False,
    # ops kept in the eager replay journal per step (memory bound of the
    # deferred mode; the oldest ops drop first)
    'FLAGS_check_nan_inf_max_journal': 4096,
    # always-on tensor stats: compiled train steps thread grad/param
    # stat taps as extra outputs and publish ptpu_num_* gauges; the
    # eager optimizer publishes the same from .grad (one extra host
    # sync per step either way)
    'FLAGS_tensor_stats': False,
    'FLAGS_cudnn_deterministic': True,   # XLA is deterministic by default
    'FLAGS_allocator_strategy': 'pjrt',
    'FLAGS_fraction_of_gpu_memory_to_use': 0.92,
    'FLAGS_eager_delete_tensor_gb': 0.0,
    'FLAGS_use_pinned_memory': True,
    'FLAGS_benchmark': False,
    'FLAGS_selected_gpus': '',
    'FLAGS_selected_tpus': '',
    'FLAGS_sync_nccl_allreduce': True,
    'FLAGS_max_inplace_grad_add': 0,
    'FLAGS_conv_workspace_size_limit': 512,
    'FLAGS_paddle_num_threads': 1,
    'FLAGS_profile_start_step': -1,
    'FLAGS_profile_stop_step': -1,
    # route eligible nn.MultiHeadAttention through the Pallas flash kernel
    # (parity: the reference's fused_attention op swap-in)
    'FLAGS_use_flash_attention': True,
    # min sequence length for the flash route; below it XLA's fused dense
    # attention usually wins on TPU (tunable per model/shape)
    'FLAGS_flash_min_seq': 1024,
    # causal_attention (GPT path) through the packed transpose-free
    # kernel. Off by default: the packed kernel keeps FULL [L, H*D] K/V
    # slabs in VMEM — ~16 MB at GPT-1.3B shapes (L=2048, H*D=2048),
    # over the v5e VMEM budget; enable per-model after measuring (BERT
    # shapes are fine: 0.75 MB slabs)
    'FLAGS_flash_packed_causal': False,
    # MHA encoder flash via the packed transpose-free kernel (True) or
    # the BHLD-transposing kernel (False) — A/B knob for tuning
    'FLAGS_flash_packed_mha': True,
    # serving: ragged paged-attention route. None = auto (Pallas kernel
    # on TPU, dense lax fallback on CPU — transformer.py's flash-routing
    # pattern); True/False force a route (tests force True to run the
    # kernel body under interpret mode on the CPU mesh)
    'FLAGS_paged_attention_kernel': None,
    # fused Pallas primitives (ops/pallas/, TPP arXiv:2104.05755) —
    # same route convention as the paged kernel: None = auto (fused
    # Pallas kernel on TPU, reference jnp path on CPU), True/False
    # force (tests force True: the kernels run under interpret mode on
    # the CPU mesh). Route decisions are counted in
    # ptpu_pallas_{kernel,fallback}_invocations_total.
    # one-pass optimizer step + grad stats over flat buckets
    'FLAGS_fused_optimizer': None,
    # fused LayerNorm fwd+bwd (last-axis, affine)
    'FLAGS_fused_layer_norm': None,
    # fused bias+GELU and dropout+residual-add blocks
    'FLAGS_fused_elementwise': None,
    # wrap op-kernel exceptions with [operator < name > error] context
    # (enforce.h framing; off by default to keep exception types exact)
    'FLAGS_op_error_context': False,
    # XLA scheduling knobs for communication/compute overlap (ISSUE 10,
    # docs/performance.md#comm-overlap). None = leave the compiler
    # default; True/False edit LIBTPU_INIT_ARGS in the environment on set —
    # effective only BEFORE backend initialization, so launchers export
    # PTPU_COMM_OVERLAP=1 (honored at this module's import, below) or
    # set FLAGS_xla_*/the env tokens directly. Engine builds also call
    # bucketing.ensure_overlap_xla_flags(), which records intent and
    # updates the env for child processes; user pins are respected.
    'FLAGS_xla_latency_hiding_scheduler': None,
    'FLAGS_xla_async_collectives': None,
}

# FLAGS_* -> the libtpu option tokens they drive in LIBTPU_INIT_ARGS
_XLA_FLAG_TOKENS = {
    'FLAGS_xla_latency_hiding_scheduler': (
        'xla_tpu_enable_latency_hiding_scheduler',),
    'FLAGS_xla_async_collectives': (
        'xla_tpu_enable_async_collective_fusion',),
}


def _apply_xla_flag(name, value):
    """Reflect a True/False scheduling flag into the LIBTPU_INIT_ARGS
    environment (replacing any prior token for the same option).
    xla_tpu_* options belong to libtpu, which reads LIBTPU_INIT_ARGS
    once when it initializes; they must NOT go into XLA_FLAGS — that is
    parsed by jaxlib, which knows no xla_tpu_* option and aborts the
    process on an unknown flag (jaxlib 0.9.0: "Unknown flag in
    XLA_FLAGS"), in this process and in every child that inherits the
    env. A set after backend init is recorded in the registry but
    cannot reach the already-built client; a process that never loads
    libtpu ignores the variable."""
    if value is None:
        return
    val = 'true' if value else 'false'
    toks = [t for t in os.environ.get('LIBTPU_INIT_ARGS', '').split()
            if not any(t.startswith(f'--{opt}=')
                       for opt in _XLA_FLAG_TOKENS[name])]
    toks += [f'--{opt}={val}' for opt in _XLA_FLAG_TOKENS[name]]
    os.environ['LIBTPU_INIT_ARGS'] = ' '.join(toks)


def _seed_from_env():
    for k in list(_FLAGS):
        if k in os.environ:
            v = os.environ[k]
            cur = _FLAGS[k]
            if isinstance(cur, bool):
                _FLAGS[k] = v.lower() in ('1', 'true', 'yes')
            elif isinstance(cur, int):
                _FLAGS[k] = int(v)
            elif isinstance(cur, float):
                _FLAGS[k] = float(v)
            elif cur is None and v.lower() in ('1', 'true', 'yes',
                                               '0', 'false', 'no'):
                # tri-state flags (None = auto): env seeds a real bool
                _FLAGS[k] = v.lower() in ('1', 'true', 'yes')
            else:
                _FLAGS[k] = v
            if k in _XLA_FLAG_TOKENS:
                _apply_xla_flag(k, _FLAGS[k])


_seed_from_env()

# comm/compute overlap (ISSUE 10): the XLA scheduling flags only reach
# the compiler when exported BEFORE backend initialization, and engine
# builds necessarily run after it — so the launcher contract
# `PTPU_COMM_OVERLAP=1` is honored HERE, at first import of this
# module, flipping any still-unset scheduling flag. Explicit
# FLAGS_xla_* env settings were seeded above and take precedence.
if os.environ.get('PTPU_COMM_OVERLAP', '').lower() in ('1', 'true',
                                                       'yes'):
    for _k in _XLA_FLAG_TOKENS:
        if _FLAGS.get(_k) is None:
            _FLAGS[_k] = True
            _apply_xla_flag(_k, True)


def set_flags(flags):
    """Parity: paddle.set_flags({'FLAGS_x': v})."""
    for k, v in flags.items():
        _FLAGS[k] = v
        if k in _XLA_FLAG_TOKENS:
            _apply_xla_flag(k, v)


def get_flags(keys):
    """Parity: paddle.get_flags — str or list → dict."""
    if isinstance(keys, str):
        keys = [keys]
    return {k: _FLAGS.get(k) for k in keys}


def flag(name, default=None):
    return _FLAGS.get(name, default)
