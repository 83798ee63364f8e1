"""Serving engine: fixed-shape jitted steps over the paged KV pool.

Four compiled step shapes serve every request mix (the continuous-
batching contract — the device never recompiles as traffic changes):

  * the mixed step   — ONE program for a step with prompts to prefill:
    the B=max_batch_size decode rows of one token AND P prompt chunks
    of T=prefill_chunk tokens ride it together. Everything token-wise
    (embedding, norms, projections, MLP, a sparse layer's router and
    experts) runs once over the B + P*T tokens laid end to end, so
    every weight and every expert is read once a step; attention alone
    goes group by group ([B, 1] and [P, T], each over its own page
    tables), and the head runs over the B + P last-query rows. P is
    `PREFILL_ROWS` (not configured: a padded row is not free, the chip
    chose). More than P prompts prefilling ride further dispatches of
    the same program with an idle decode group;
  * batched decode   — B=max_batch_size, T=1: every RUNNING request
    advances one token in ONE dispatch (a step with no prompt to
    prefill);
  * batched verify   — B=max_batch_size, T=spec_k+1 (only with
    speculative decoding, spec_k > 0): each greedy request carries its
    n-gram-proposed draft tokens as extra ragged query rows — the same
    causal-within-sequence masking chunked prefill uses — and the
    accept-longest-agreeing-prefix rule plus a bonus token advances a
    request up to spec_k+1 tokens per dispatch, token-identical to the
    one-token path;
  * fused decode     — B=max_batch_size, k=fused_k iterations of the
    decode step rolled into ONE dispatch via lax.scan (only with
    fused_k > 1): the carry holds the sampled token, per-row seq_len,
    eos/budget done-mask and the paged KV pool, so the host fetches
    sampled ids once per k tokens instead of once per token. Engaged
    per dispatch only when the scheduler is quiescent for the window
    (no waiting work, no mid-window admit/retire hazard, no degrade
    transition due) and every row's k-token page reservation fits;
    otherwise the engine falls back to the [B, 1] step. Tokens are
    IDENTICAL to serial decode for greedy and sampled rows alike: the
    sampling key is folded per (request ordinal, absolute position),
    never per dispatch.

Prefix caching (ISSUE 9) rides in the pool: prompts sharing a prefix
map the same physical pages (kv_pool.py refcounts + hash-chained
index), so cache hits skip whole prefill chunks and TTFT drops to the
uncached tail's cost.

All four come from ONE builder (`_build_step`) and are called in ONE
place (`_enqueue`; `_fetch` is the one host sync): they run the model's
`forward_paged` (ragged paged attention + `write_kv_pages` scatter)
under `jit` with the KV pool donated, pick the next token ON DEVICE (greedy argmax or
temperature/top-k via jax.random), and fetch only the sampled token
ids — the single per-token host round-trip. The dispatch sites keep
what is theirs (prefix match and page growth; draft proposal, capacity
and verify; window reservation and roll-back) and hand over their rows.
A request whose last prompt chunk rode a dispatch takes its first token
from it and joins the decode rows in the NEXT step.

The engine runs ONE STEP AHEAD of the device (ISSUE 33): step n+1 is
planned and queued (`_launch`) before the ids of step n are fetched and
accepted (`_land`), so the host's turn between two programs runs under
the device's work. The ids never need the host to reach the next
program — every program hands on each slot's newest id ON the device
(`prev_ids` -> `handoff`) and a decode row whose token has not come to
the host reads it there (`src`) —, and the plan of n+1 is made on what
n is KNOWN to do (`_Flight`, `_known`): a row emits one token, a chunk
prefills its tokens. An EOS alone cannot be known: a row launched past
it is dropped when its ids arrive. What cannot be planned so (a verify
step, a fused window, a preemption or an abort of a request in flight)
drains the pipe first (`_drain`), read off the engine's own state — no
knob. Every `step()` still delivers one step's tokens.
Idle decode slots ride along with q_len=0: their K/V writes are dropped
by the scatter and their outputs ignored, so occupancy is a pure
scheduling concern.

Scheduling (admit / chunk order / preempt-youngest) lives in
scheduler.py; page accounting in kv_pool.py; ptpu_serve_* metrics in
metrics.py; per-request lifecycle tracing in request_trace.py — every
host-side scheduling decision the engine makes lands in the request's
journal and the scheduler timeline, with zero extra device syncs.
docs/serving.md covers tuning the knobs.
"""
import collections
import contextlib
import functools
import math
import os
import time

import numpy as np

from .kv_pool import KVPagePool, PoolExhausted, _np_dtype
from .scheduler import (AdmissionRejected, DegradeLadder, Request,
                        RequestState, Scheduler, SchedulerTimeline,
                        TenantTable)
from .request_trace import (ENGINE_REQ, RequestTracer,
                            build_serve_report, write_serve_report)
from . import metrics as _metrics
from .ledger import ServeLedger
from .protocol import RowGroups
from ..core import monitor as _monitor
from ..core.async_step import HostGapMonitor, unregister_monitor
from ..profiler import RecordEvent, record_span


def _host_fetch(x):
    """Every host sync the engine performs (the per-step sampled-token
    fetch) funnels through this hook so tests can count them — the
    PR-3 numerics._host_fetch convention. Tracing must not add calls
    here (asserted in tests/test_serving_trace.py)."""
    return np.asarray(x)


# the growing counts a sparse-expert model carries (serving/protocol.py
# moe_counters), in their order, and the counters they feed
_MOE_COUNTERS = (('experts_touched', 'ptpu_moe_experts_touched_total'),
                 ('rows', 'ptpu_moe_rows_total'),
                 ('calls', 'ptpu_moe_calls_total'))

# P: the prompt chunks that ride the mixed step beside its decode rows
# (never more than there are slots). Every row is computed in full,
# real or padding, and past a few hundred tokens a dispatch the matmuls
# hide behind no weight read: on the v5e a row costs the dense 1.3B
# model 3.0 ms of a 24 ms step (chunk 128) and the sparse Trinity cut
# 5.7 of 39 (chunk 512), against one more dispatch — every weight and
# expert read again — for each P prompts beyond the first P. Swept on
# both benchmark server cells (PERF.md section 6, PR 31: P 1, 2, 3, 4,
# 5, 8): the dense model reads alike at 1 and 2 (2 the shorter gaps
# between tokens), the sparse one at 2 and 3; a rule from the device's
# peak FLOP/s over its HBM bandwidth said 2 and 5, and 5 read 9 % under
# the best. One value is within 3 % of it on both, so it is a constant
PREFILL_ROWS = 2

# why a step's ids were fetched with nothing queued behind them: the
# next step is a verify step (its n-gram proposal reads the token) or a
# fused window, a preemption or an abort took a request the step in
# flight carries, another engine's request was adopted, a host-tier
# fetch, nothing left to launch
DRAIN_REASONS = ('verify', 'fused', 'preempt', 'abort', 'adopt', 'tier',
                 'idle')


class _Flight:
    """A launched step whose sampled ids have not come to the host:
    its dispatches as `_fetch` and the accept loops take them, and what
    it is KNOWN to do before they arrive — a decode row emits one
    token, a chunk prefills its tokens and, a prompt's last, emits the
    first. (An EOS is the one thing not known: `_accept_decode`.)"""
    __slots__ = ('dispatches', 'emits', 'prefills', 'step', 'steps')

    def __init__(self, step, steps=1):
        self.dispatches = []    # (decode rows, chunks riding, queued)
        self.emits = set()      # ids of the requests that take a token
        self.prefills = {}      # request id -> (request, first, tokens)
        self.step = step        # ordinal of the serve::step that launched
        self.steps = steps      # 0: a verify step of it landed already

    def carries(self, req):
        return req.id in self.emits or req.id in self.prefills


class _DeviceStep:
    """What the device ran between two fetches: the `serve::device_step`
    record being built (`ServingEngine._ran`, `_close_device_step`).
    `since` is where the record before it ended, `launched` when the
    first of its dispatches was queued; the counts are the record's
    args."""
    __slots__ = ('since', 'launched', 'steps', 'dispatches', 'decode_rows',
                 'chunks', 'chunk_tokens', 'chunk_slots', 'emitted',
                 'snapshots', 'shared_rows', 'shared_keys')

    def __init__(self, since):
        self.since = since
        self.launched = None
        self.steps = self.dispatches = self.decode_rows = 0
        self.chunks = self.chunk_tokens = self.chunk_slots = 0
        self.emitted = self.snapshots = 0
        self.shared_rows = self.shared_keys = 0


class ServingConfig:
    """Knobs (docs/serving.md#tuning):

    page_size        tokens per KV page (TPU lane-friendly: >= 8)
    max_batch_size   decode slots = max concurrent requests
    num_pages        pool capacity; default fits every slot at
                     max_pages_per_seq (no preemption pressure)
    max_pages_per_seq  page-table width; default covers max_seq_len
    prefill_chunk    prompt tokens a request prefills per step (one
                     row of the mixed step's prefill group)
    kv_dtype         pool dtype (default: model param dtype).
                     'int8' stores block-paged K/V as int8 with one
                     abs-max fp32 scale per (token slot, head) in
                     sibling scale buffers; attention dequantizes
                     inside the paged-attention kernel, so the pool
                     holds ~4x (vs fp32) / ~2x (vs bf16) more tokens
                     per byte (docs/serving.md#quantized-kv)
    weight_dtype     None (default) or 'int8': weight-only-quantized
                     decode — matmul weights (ndim >= 2, embeddings
                     excluded) are stored int8 with per-out-channel
                     abs-max scales and dequantized inside the jitted
                     step (XLA fuses the scale multiply into the
                     matmul's operand upcast), reusing
                     quantization.quantize_to_int8. NOTE: the engine
                     does not own the model, so the model's full-
                     precision weights stay resident beside the int8
                     copies — the win is the step's weight-read
                     bandwidth, not total HBM; drop the model's params
                     yourself (or load via load_quantized_predictor)
                     to reclaim the memory
    prefix_cache     copy-on-write prefix sharing over the paged pool
                     (default on): requests whose prompts share a
                     prefix map the same physical pages and skip the
                     prefill compute for them; granularity is one page
                     (page_size tokens) — docs/serving.md#prefix-cache
    spec_k           speculative decoding draft length (default 0 =
                     off): an n-gram proposer drafts up to k tokens
                     per greedy request and a third compiled step
                     shape [max_batch, spec_k+1] verifies them all in
                     ONE dispatch (accept-longest-agreeing-prefix +
                     bonus token; greedy output is token-identical to
                     spec_k=0 — docs/serving.md#speculative-decode)
    spec_ngram       proposer match length: the trailing n-gram looked
                     up in the request's own token history (prompt +
                     generated) to source draft continuations
    fused_k          decode iterations fused into one dispatch
                     (default: $PTPU_SERVE_FUSED_K, else 1 = off): a
                     fourth compiled shape scans k decode steps on
                     device and fetches sampled ids once per window,
                     cutting the per-token host round-trip k-fold at
                     small batch. Token-identical to fused_k=1; falls
                     back to the [B, 1] step whenever the scheduler
                     is not quiescent for a full window, draft
                     proposals exist this dispatch (spec verify wins),
                     or a row's k-token page reservation doesn't fit.
                     Ladder stage 1+ sheds it before spec_k
                     (docs/serving.md#fused-decode)
    seed             device sampling stream seed
    trace            per-request lifecycle journal on/off (host-only
                     bookkeeping; default on — docs/serving.md)
    trace_events_per_request / trace_requests   journal caps
    timeline_capacity  scheduler-timeline ring size (iterations)
    request_deadline_s stalled-request watchdog deadline (None = off):
                       a request older than this produces a
                       serve_report artifact
    deadline_action  'report' (default) or 'abort' (also drop it)
    report_dir       serve_report directory (default:
                     $PTPU_SERVE_REPORT_DIR, then $FLEET_LOG_DIR)
    clock            monotonic clock for ALL request timing
                     (tests inject a deterministic one)
    disaggregate     prefill/decode disaggregation (ISSUE 11, default
                     off): chunked prefill runs on a dedicated prefill
                     engine whose finished KV pages STREAM into the
                     decode engine's pool, where the request is
                     adopted into a decode slot
                     (serving/cluster/disagg.py,
                     docs/serving.md#disaggregated-serving)
    prefill_slots    prefill-engine slot count under disaggregation
    stream_chunk_pages  pages per streamed copy op (0 = one shot) —
                     bounds the handoff's staging footprint like the
                     PR-10 chunked collectives
    tenants          multi-tenant policy map (ISSUE 15, default None):
                     {tenant_id: {priority, quota_tokens_per_s,
                     burst_tokens, weight}}. priority (int, larger =
                     more important) orders admission and bounds
                     preemption; quota_tokens_per_s feeds a refillable
                     token bucket debited at admit (over-quota tenants
                     DEFER, never drop); weight drives stage-3
                     prefix-cache eviction. Unknown/anonymous tenants
                     get priority 0, no quota, weight 1.0. With no
                     tenants declared scheduling is IDENTICAL to the
                     untenanted engine (docs/serving.md#multi-tenant)
    degrade          graceful-degradation ladder: None (default) =
                     auto (on exactly when `tenants` is set), or an
                     explicit bool. Stages under sustained pressure:
                     1 sheds speculative decoding, 2 halves the
                     prefill chunk, 3 evicts prefix-cache subtrees by
                     tenant weight; walks back down hysteretically
    degrade_window   pressure-signal window (iterations)
    degrade_up       stage up-thresholds (windowed mean pressure)
    degrade_down     stage down-thresholds (must sit below their
                     up-threshold — the hysteresis band)
    degrade_hold     consecutive calm iterations before stepping down
    """

    def __init__(self, page_size=16, max_batch_size=4, num_pages=None,
                 max_pages_per_seq=None, prefill_chunk=32,
                 kv_dtype=None, weight_dtype=None, prefix_cache=True,
                 spec_k=0, spec_ngram=2, fused_k=None, seed=0,
                 trace=True,
                 trace_events_per_request=512, trace_requests=512,
                 timeline_capacity=2048, request_deadline_s=None,
                 deadline_action='report', report_dir=None, clock=None,
                 disaggregate=False, prefill_slots=2,
                 stream_chunk_pages=0, tenants=None, degrade=None,
                 degrade_window=8, degrade_up=(0.85, 0.92, 0.97),
                 degrade_down=(0.60, 0.70, 0.80), degrade_hold=4,
                 host_tier_pages=0, spill_watermark=0.92,
                 spill_chunk_pages=0, spill_window=2):
        if page_size <= 0 or max_batch_size <= 0 or prefill_chunk <= 0:
            raise ValueError("page_size, max_batch_size and "
                             "prefill_chunk must be positive")
        if spec_k < 0 or spec_ngram < 1:
            raise ValueError("spec_k must be >= 0 and spec_ngram >= 1")
        if fused_k is None:
            fused_k = int(os.environ.get('PTPU_SERVE_FUSED_K', '1'))
        if int(fused_k) < 1:
            raise ValueError("fused_k must be >= 1 (1 = per-token "
                             "decode, k > 1 = fused k-step windows)")
        if deadline_action not in ('report', 'abort'):
            raise ValueError("deadline_action must be 'report' or "
                             "'abort'")
        self.page_size = int(page_size)
        self.max_batch_size = int(max_batch_size)
        self.num_pages = num_pages
        self.max_pages_per_seq = max_pages_per_seq
        self.prefill_chunk = int(prefill_chunk)
        self.kv_dtype = kv_dtype
        if weight_dtype is not None and \
                _np_dtype(weight_dtype) != np.int8:
            raise ValueError("weight_dtype must be None or 'int8', got "
                             f"{weight_dtype!r}")
        self.weight_dtype = weight_dtype
        self.prefix_cache = bool(prefix_cache)
        self.spec_k = int(spec_k)
        self.spec_ngram = int(spec_ngram)
        self.fused_k = int(fused_k)
        self.seed = int(seed)
        self.trace = bool(trace)
        self.trace_events_per_request = int(trace_events_per_request)
        self.trace_requests = int(trace_requests)
        self.timeline_capacity = int(timeline_capacity)
        self.request_deadline_s = request_deadline_s
        self.deadline_action = deadline_action
        self.report_dir = report_dir
        self.clock = clock
        self.disaggregate = bool(disaggregate)
        self.prefill_slots = int(prefill_slots)
        self.stream_chunk_pages = int(stream_chunk_pages)
        if tenants is not None and not isinstance(tenants, dict):
            raise ValueError("tenants must be a {tenant_id: policy} "
                             "dict or None")
        self.tenants = dict(tenants) if tenants is not None else None
        if degrade not in (None, True, False):
            raise ValueError("degrade must be None (auto), True or "
                             "False")
        self.degrade = degrade
        self.degrade_window = int(degrade_window)
        self.degrade_up = tuple(degrade_up)
        self.degrade_down = tuple(degrade_down)
        self.degrade_hold = int(degrade_hold)
        # host-RAM KV tier (ISSUE 20): 0 host pages = no tier — the
        # engine then keeps PR-19's compiled shapes, host-sync count
        # and gauge set exactly (asserted in test_serving_kvtier.py)
        if int(host_tier_pages) < 0:
            raise ValueError("host_tier_pages must be >= 0 (0 = no "
                             "host tier)")
        if not (0.0 < float(spill_watermark) <= 1.0):
            raise ValueError("spill_watermark must be in (0, 1]")
        self.host_tier_pages = int(host_tier_pages)
        self.spill_watermark = float(spill_watermark)
        self.spill_chunk_pages = int(spill_chunk_pages)
        self.spill_window = int(spill_window)

    @property
    def degrade_enabled(self):
        """The ladder's effective switch: explicit bool wins, None
        means on exactly when tenants are declared — the untenanted
        default must keep today's behavior (and compiled step shapes)
        bit-for-bit."""
        if self.degrade is None:
            return self.tenants is not None
        return self.degrade


class ServingEngine:
    """Continuous-batching inference over a model that implements the
    serving-model protocol (serving/protocol.py: the cache spec per
    layer, `forward_paged`, the head's weight) — GPTForCausalLM,
    AfmoeForCausalLM.

    `mesh`: an optional replica-local jax Mesh with an 'mp' axis — the
    mp-sharded serving route (ISSUE 11): attention heads (and the KV
    pool's pages) split over 'mp' exactly like the training flash
    route, so one replica can span several chips when the model's KV
    doesn't fit one. The model must have been built under a fleet hcg
    whose mp degree equals the mesh's 'mp' size (mp_layers then mark
    qkv/out/vocab params with their split axes and emit the Megatron
    collectives inside the traced step). docs/serving.md#mp-sharding.
    """

    def __init__(self, model, config=None, mesh=None, ledger_site=None,
                 **cfg_kw):
        import jax
        import jax.numpy as jnp
        if config is None:
            config = ServingConfig(**cfg_kw)
        elif cfg_kw:
            raise ValueError("pass either config or knobs, not both")
        if config.disaggregate:
            # the flag selects a DIFFERENT engine class — silently
            # serving unified under a disaggregate config would lie
            raise ValueError(
                "config.disaggregate=True needs the disaggregated "
                "engine: build via serving.cluster.build_engine(...) "
                "or serving.cluster.DisaggregatedEngine(...) "
                "(docs/serving.md#disaggregated-serving)")
        self.model = model
        self.config = config
        mcfg = model.config
        ps = config.page_size
        self.max_pages_per_seq = int(
            config.max_pages_per_seq
            or math.ceil(mcfg.max_seq_len / ps))
        num_pages = int(config.num_pages
                        or config.max_batch_size * self.max_pages_per_seq)
        # the model's side of the protocol: what a token's K/V takes
        # in each layer's pages. One page table serves every layer, so
        # the layers must agree on the pages' width; a layer's window
        # bounds what its attention READS, not what the pool keeps
        spec = list(model.kv_cache_spec())
        if len({(s.num_kv_heads, s.head_dim, s.value_lanes)
                for s in spec}) != 1:
            raise ValueError(
                "every layer must store the same kv heads and head_dim, "
                "and planes of one kind — (k, v) pairs, or latent rows "
                "of the same value_lanes — (one page table serves them "
                f"all), got {spec}")
        latent = spec[0].value_lanes is not None
        if latent and any(s.window is not None for s in spec):
            raise NotImplementedError(
                f"a window on a latent plane (the paged kernel's latent "
                f"body has none), got {spec}")
        if any(s.reads is not None and spec[s.reads].reads is not None
               for s in spec):
            raise ValueError(
                f"a layer that reads another's plane must name its "
                f"OWNER, got {spec}")
        # {window: attending layers that have it}; a layer without one
        # reads a row's whole context. Readers are the attending
        # layers, planes those of them that own their pages
        self._windows = collections.Counter(
            s.window for s in spec if s.window is not None)
        self._kv_readers = len(spec)
        self._kv_full = len(spec) - sum(self._windows.values())
        self._kv_planes = sum(s.reads is None for s in spec)
        self._attn_kv_tokens = self._attn_kv_chunks = 0
        self._attn_qk_pairs = self._attn_qk_dispatched = 0
        # the query slots a latent row's pairs are multiplied in: the
        # paged kernel's own tile rule, by the model's query heads (the
        # other bodies have no query tiles: dispatched == real)
        self._latent_pairs = None
        if latent:
            from ..ops.pallas import paged_attention
            self._latent_pairs = functools.partial(
                paged_attention.latent_pairs_dispatched,
                num_heads=int(mcfg.num_heads))
        self._prompt_tokens = 0
        dtype = config.kv_dtype or model.lm_head_weight().dtype
        self.mesh = mesh
        self._mp = int(mesh.shape['mp']) if (
            mesh is not None and 'mp' in mesh.shape) else 1
        # the routes this configuration takes through forward_paged
        # against those the model's is written for
        needs = {'plain'} | {route for route, on in (
            ('fused', config.fused_k > 1), ('verify', config.spec_k > 0),
            ('int8_kv', _np_dtype(dtype) == np.int8),
            ('int8_weights', config.weight_dtype is not None),
            ('mp', self._mp > 1)) if on}
        # recurrent state (protocol.py state_spec): per-request arrays
        # of a fixed size beside the pages. Unless the model keeps it a
        # page too (page_state_spec: then a request resumes at a page
        # boundary, from the snapshot of the page before), nothing keeps
        # the state of an earlier position, so whatever resumes a request
        # anywhere but at its start or its end is refused here, by name
        state_spec = list(model.state_spec()) \
            if hasattr(model, 'state_spec') else []
        page_state_spec = list(model.page_state_spec()) \
            if hasattr(model, 'page_state_spec') else []
        self._stateful = bool(state_spec)
        self._page_state = bool(page_state_spec)
        self._state_layers = int(getattr(model, 'state_layers', 0))
        self._state_rows = self._state_tokens = 0
        # page-state snapshots written (a page end a live row covered,
        # all layers at once), rows restored from one, and the tokens
        # the restored rows resumed after
        self._snapshots = self._restores = self._restore_tokens = 0
        if self._stateful:
            resumes = [what for what, on in (
                ('prefix_cache=True (a hit resumes past its prefix)',
                 config.prefix_cache and not self._page_state),
                ('a host tier (a resurrected prefix resumes past it)',
                 config.host_tier_pages > 0),
                ('fused_k > 1 (a window cut short resumes inside it)',
                 config.fused_k > 1),
                ('spec_k > 0 (a rejected draft resumes before it)',
                 config.spec_k > 0)) if on]
            if resumes:
                raise NotImplementedError(
                    f"{type(model).__name__} holds recurrent state, and "
                    f"no state is kept at the position a request would "
                    f"resume from: {'; '.join(resumes)}")
            unsplit = needs & {'int8_kv', 'int8_weights', 'mp'}
            if unsplit:
                raise NotImplementedError(
                    f"{type(model).__name__} holds recurrent state, "
                    f"which has no int8 form and no mp split: "
                    f"{sorted(unsplit)}")
        if latent:
            # a latent plane is ONE array read whole by every head: the
            # int8 scales, the host tier's transfers and the mp split
            # of the heads axis are written for (k, v) pairs (the pool
            # refuses the same, kv_pool.py). The prefix cache is not
            # among them: pages are shared whatever they hold
            unpaired = [what for what, on in (
                ("kv_dtype='int8'", 'int8_kv' in needs),
                ('a host tier', config.host_tier_pages > 0),
                ('an mp mesh', self._mp > 1)) if on]
            if unpaired:
                raise NotImplementedError(
                    f"{type(model).__name__} caches latent (one-array) "
                    f"planes, which have no int8 form, no host-tier "
                    f"transfer and no heads axis to split: "
                    f"{'; '.join(unpaired)}")
        # decode rows that map one leading page run read it once (the
        # paged kernel's `shared`, found in their tables: shared_run):
        # built where the prefix cache can share pages and the kernel's
        # decode call takes a run — (k, v) planes in the model's dtype,
        # no mp split, full layers to read it in
        from ..ops.pallas import paged_attention
        self._shared_runs = bool(
            config.prefix_cache and not latent and self._mp == 1
            and _np_dtype(dtype) != np.int8 and self._kv_full > 0
            and paged_attention.shared_pass(1, int(mcfg.num_heads),
                                            spec[0].num_kv_heads))
        # the decode rows' keys the run served (a member row's run keys
        # in each full layer: the unit of _attn_kv_tokens), the
        # dispatches that carried a run and the member rows they had
        self._shared_keys = self._shared_run_count = self._shared_rows = 0
        self._no_runs = {}
        lacking = needs - set(model.paged_routes)
        if lacking:
            raise NotImplementedError(
                f"{type(model).__name__}.forward_paged lacks the "
                f"{sorted(lacking)} route this configuration needs: it "
                f"is written for {sorted(model.paged_routes)}")
        # a sparse-expert model's counters (protocol.py moe_counters)
        # ride the plain route's one fetch; a configuration that takes
        # another route too carries none
        self._moe_dev = model.moe_counters() if needs == {'plain'} \
            else None
        if self._moe_dev is not None:
            self._moe_seen = np.zeros(self._moe_dev.shape, np.int64)
        self._moe = {'rows': 0, 'experts_touched': 0, 'calls': 0}
        # rows per expert of the last fetched dispatch, by counted
        # group ([expert layers, groups, experts held]: group 0 the
        # decode rows, then one per prompt chunk), or None
        self._moe_rows = None
        # who wants the rows per expert and layer of a prompt's last
        # chunk ALONE (its own group of the dispatch it rode): a
        # callable (request, first position, tokens, int array [expert
        # layers, experts held]), or None. The benchmark's check
        # listens.
        self.moe_rows_listener = None
        if self._mp > 1:
            if model.mp_degree != self._mp:
                raise ValueError(
                    f"mesh mp={self._mp} but the model was built with "
                    f"mp degree {model.mp_degree} — fleet.init (or a "
                    f"minimal hcg) with model-parallel degree "
                    f"{self._mp} BEFORE constructing the model")
            if config.weight_dtype is not None:
                raise ValueError(
                    "weight_dtype='int8' is not supported on the "
                    "mp-sharded serving route yet (per-out-channel "
                    "scales would need their own split specs)")
        # the pool holds GLOBAL heads; under mp the arrays are sharded
        # on the trailing heads*hd axis so each shard owns its local
        # heads' pages — the same layout the column-sharded qkv writes
        self.pool = KVPagePool(
            num_pages, ps, num_layers=self._kv_planes,
            num_heads=spec[0].num_kv_heads, head_dim=spec[0].head_dim,
            dtype=dtype, prefix_cache=config.prefix_cache,
            state_spec=state_spec, state_slots=config.max_batch_size,
            value_lanes=spec[0].value_lanes,
            page_state_spec=page_state_spec)
        self._kv_sharding = None
        if self._mp > 1:
            from jax.sharding import NamedSharding, PartitionSpec as P
            self._kv_sharding = NamedSharding(mesh, P(None, None, 'mp'))
        self.pool.materialize(sharding=self._kv_sharding)
        if self._stateful:
            self.pool.materialize_state()
        # host-RAM KV tier (ISSUE 20): pinned host buffers + one
        # background transfer thread under the pool. Spills are
        # proactive (watermark in _observe_spill_pressure) or the
        # pool's own synchronous exhaustion fallback; resurrection
        # happens inside match_and_map on the prefill path. Disabled
        # (the default) the attribute stays None and every tier hook
        # below is a single falsy check.
        self._host_tier = None
        self._tier_spilled_seen = 0
        if config.host_tier_pages > 0:
            from .host_tier import HostTier
            self._host_tier = self.pool.attach_host_tier(HostTier(
                config.host_tier_pages,
                chunk_pages=config.spill_chunk_pages,
                window=config.spill_window))
        self._clock = config.clock or time.perf_counter
        self.scheduler = Scheduler(config.max_batch_size,
                                   clock=self._clock)
        # request observatory: lifecycle journals + iteration timeline
        # (host-only bookkeeping on data the scheduler already holds)
        self.tracer = RequestTracer(
            capacity_requests=config.trace_requests,
            events_per_request=config.trace_events_per_request,
            clock=self._clock) if config.trace else None
        self.timeline = SchedulerTimeline(config.timeline_capacity)
        self.last_serve_report = None
        self._stall_reported = set()        # req ids already reported
        self._params = {n: p.data for n, p in model.named_parameters()}
        # weight-only-quantized decode (ISSUE 7): matmul weights live
        # on device as int8 + per-out-channel abs-max scales; the
        # jitted step dequantizes at trace time so XLA fuses the scale
        # multiply into the matmul operand upcast. Embeddings (and the
        # tied LM head) stay full precision — logit ordering is the
        # product, don't round it.
        self._qparam_dtypes = {}
        if config.weight_dtype is not None:
            from ..quantization import quantize_to_int8
            for n, a in list(self._params.items()):
                # 2-D matmul weights only (per-out-channel scales);
                # GPT serving has no convs — higher-rank params keep
                # full precision rather than guessing a channel axis
                if a.ndim != 2 or 'embed' in n or \
                        not jnp.issubdtype(a.dtype, jnp.floating):
                    continue
                q, s = quantize_to_int8(
                    np.asarray(jax.device_get(a), np.float32),
                    quant_axis=a.ndim - 1)
                self._params[n] = {'q': jnp.asarray(q),
                                   's': jnp.asarray(s)}
                self._qparam_dtypes[n] = a.dtype
        # mp-sharded params: split specs from the mp_layers marks
        # (split_axis over 'mp', everything else replicated); placed
        # once here so the jitted step never reshards weights
        self._param_specs = None
        if self._mp > 1:
            from jax.sharding import NamedSharding, PartitionSpec as P
            specs = {}
            for n, p in model.named_parameters():
                spec = [None] * len(p.data.shape)
                if getattr(p, 'is_distributed', False):
                    spec[p.split_axis] = 'mp'
                specs[n] = P(*spec)
            self._param_specs = specs
            self._params = {
                n: jax.device_put(a, NamedSharding(mesh, specs[n]))
                for n, a in self._params.items()}
        self._step_fns = {}
        # CONSTANT base sampling key: per-row keys are derived inside
        # the step as fold_in(fold_in(base, request_ordinal),
        # absolute_position), so the token sampled at position p of
        # request o is a pure function of (seed, o, p) — the invariant
        # that makes fused-k, serial decode, spec verify and
        # preempt/resume re-prefill all emit IDENTICAL sampled tokens
        self._key = jax.random.key(config.seed)
        # engine-local submission ordinal feeding that fold (NOT the
        # process-global Request.id, which would couple sampled output
        # to unrelated engines constructed earlier in the process)
        self._next_sample_ord = 0
        self._jnp = jnp
        self._jax = jax
        self._step_ordinal = 0          # the serve::step span's `step`
        # lifetime accounting for stats()/metrics
        self._decode_time = 0.0
        self._decode_tokens = 0
        self._decode_steps = 0
        self._occupancy_sum = 0.0
        self._util_sum = 0.0
        self._prefill_tokens = 0
        self._prefill_chunks = 0
        # the mixed step's prefill group, and how often it engages:
        # steps that dispatched, the programs they called, and of the
        # mixed ones the token slots their prefill groups held
        self._prefill_rows = min(PREFILL_ROWS, config.max_batch_size)
        self._steps = 0
        self._dispatches = 0
        self._mixed_dispatches = 0
        self._mixed_slots = 0
        # one step ahead: the launched step whose ids have not come to
        # the host (`_Flight`, or None), the newest id of every slot as
        # the last dispatch left it ON the device (what a decode row
        # reads when its token never came to the host), when the last
        # fetch returned (where the last `serve::device_step` record
        # ended) and whether its ids were there before it was asked for
        # them, the record being built (the dispatches landed since:
        # those that fetched nothing wait in it for the next fetch),
        # and the mechanism's counters — steps launched behind another, fetches with
        # nothing queued behind them by what held the next step back,
        # rows dropped after an EOS
        self._flight = None
        self._prev_ids = jnp.zeros((config.max_batch_size,), jnp.int32)
        if self._mp > 1:    # replicated, as every program hands it back
            from jax.sharding import NamedSharding, PartitionSpec as P
            self._prev_ids = jax.device_put(
                self._prev_ids, NamedSharding(mesh, P()))
        self._fetched_at, self._fetched_late = 0.0, False
        self._device_step = _DeviceStep(0.0)
        self._pipelined = 0
        self._drains = dict.fromkeys(DRAIN_REASONS, 0)
        self._overrun = 0
        # speculative decoding accounting (draft tokens proposed by
        # the n-gram proposer vs accepted by the verify step)
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._spec_steps = 0
        # fused-decode accounting (ISSUE 19): windows dispatched, the
        # device iterations they ran, and the tokens they delivered
        self._fused_windows = 0
        self._fused_iterations = 0
        self._fused_tokens = 0
        # per-step handoff from _fused_decode_window to step() so the
        # timeline/ledger record one entry per fused ITERATION (the
        # router occupancy tiebreak and staleness alerting consume
        # per-iteration signals, not per-dispatch ones)
        self._fused_last = None
        self._submitted = 0
        self._completed = 0
        self._aborted = 0
        self._ttfts_s = []
        self._new_ttfts_s = []
        # per-retire SLO samples pending the next histogram publish
        self._new_slo = {'queue_wait_s': [], 'tpot_s': [], 'e2e_s': [],
                         'preemptions': []}
        self._last_publish = 0.0
        # WALL-clock twin of _last_publish: the periodic publish path
        # keys staleness-relevant cadence to the monitor's time source
        # (the same one gauge last_update stamps and `metrics_stale`
        # alert rules read), so a deterministic injected config.clock —
        # or a fused window that retires k tokens between steps — can
        # never starve gauge freshness (ISSUE 19 satellite)
        self._last_publish_wall = 0.0
        # multi-tenant SLO layer (ISSUE 15): policy table (priority /
        # quota buckets / eviction weights), the degradation ladder,
        # and per-tenant lifetime accounting. All None/zero when no
        # tenants are configured — the default engine pays one
        # attribute check per sweep and nothing else.
        self._tenants = (TenantTable(config.tenants, clock=self._clock)
                         if config.tenants is not None else None)
        self._ladder = (DegradeLadder(
            window=config.degrade_window, up=config.degrade_up,
            down=config.degrade_down, hold=config.degrade_hold,
            clock=self._clock) if config.degrade_enabled else None)
        self._quota_deferrals = 0
        self._preemptions_charged = 0
        self._deadline_rejects = 0
        # pools co-armed with self.pool on stage-3 transitions: under a
        # SHARED ladder (disaggregated prefill+decode) whichever engine
        # observes the transition must arm/disarm weighted eviction on
        # BOTH pools, not just its own (ISSUE 16 satellite)
        self._stage3_pools = ()
        self._deadline_misses = 0
        self._tenant_stats = {}
        # per-tenant SLO samples pending the next histogram publish
        # (tenant-labeled ptpu_serve_tenant_* histograms)
        self._new_tenant_slo = {}
        # deadline-aware admission switch: the disaggregated facade
        # turns it OFF on its prefill engine (whose local backlog and
        # decode rate misrepresent the pipeline) and checks the
        # combined estimate itself before forwarding the submit
        self.deadline_admission = True
        # serving ledger + host-gap observatory (ISSUE 17): the
        # sampled-token fetch is this engine's only host sync, so a
        # registered HostGapMonitor over the step loop turns its wait
        # into a real host_bound_fraction; the ServeLedger carries the
        # wall decomposition, the goodput account and the decode
        # bytes-moved roofline. Both unregister at shutdown().
        self.ledger_site = ledger_site or 'serve'
        self._gap = HostGapMonitor(site=self.ledger_site)
        param_bytes = 0
        for a in self._params.values():
            if isinstance(a, dict):     # int8 weight: q + scales
                param_bytes += int(a['q'].nbytes) + int(a['s'].nbytes)
            else:
                param_bytes += int(getattr(a, 'nbytes', 0) or 0)
        n_params = sum(int(getattr(p.data, 'size', 0) or 0)
                       for _n, p in model.named_parameters())
        self.ledger = ServeLedger(
            engine=self.ledger_site, gap=self._gap,
            n_params=n_params, layers=mcfg.num_layers,
            hidden=mcfg.hidden_size, param_bytes=param_bytes,
            kv_bytes_per_token=self.pool.bytes_per_token())
        self._reset_iteration()

    # followers a budget-blocked queue head tolerates being admitted
    # past it before the admission sweep reverts to blocking at the
    # head (head-of-line fairness with a starvation bound)
    HOL_BYPASS_LIMIT = 8

    # seconds between periodic gauge publishes on a busy engine —
    # publishing rebuilds stats and touches ~20 monitor gauges, which
    # is host work the per-token decode path shouldn't pay every step
    # (retire and drain always publish immediately)
    PUBLISH_INTERVAL_S = 0.5

    # -- tenancy helpers -----------------------------------------------------
    @staticmethod
    def _blank_tstat():
        return {'submitted': 0, 'completed': 0, 'aborted': 0,
                'quota_deferrals': 0, 'preemptions_charged': 0,
                'charge_tokens': 0, 'deadline_rejects': 0,
                'deadline_misses': 0, 'tokens_billed': 0}

    def _tstat(self, tenant_id):
        """Per-tenant lifetime accounting row (created on first use —
        WRITE paths only; read paths use _tenant_stats.get so a stats
        call never materializes rows for traffic that never came)."""
        tid = str(tenant_id)
        st = self._tenant_stats.get(tid)
        if st is None:
            st = self._tenant_stats[tid] = self._blank_tstat()
        return st

    def decode_rate(self):
        """Observed decode throughput (generated tokens/sec), 0.0 until
        the first measured decode step."""
        return (self._decode_tokens / self._decode_time
                if self._decode_time else 0.0)

    def pending_tokens(self):
        """Tokens of work already accepted but not yet computed:
        un-prefilled prompt + remaining generation budget across the
        queue and the slots — the backlog a new request queues behind
        (the replica status() math, shared with deadline admission)."""
        reqs = ([r for r in self.scheduler.slots if r is not None]
                + list(self.scheduler.waiting))
        return sum(max(r.max_new_tokens - len(r.generated), 0)
                   + max(len(r.tokens) - r.prefilled, 0)
                   for r in reqs)

    def _estimate_completion_s(self, extra_tokens):
        """Estimated seconds until a request of `extra_tokens` total
        work would complete behind the current backlog — the PR-11
        router deadline_bound_s math moved down into the engine. None
        while no decode rate has been observed (a cold engine admits;
        rejecting on zero data would refuse the first request)."""
        rate = self.decode_rate()
        if rate <= 0.0:
            return None
        return (self.pending_tokens() + extra_tokens) / rate

    def degrade_stage(self):
        return self._ladder.stage if self._ladder is not None else 0

    def _effective_spec_k(self):
        """Ladder stage 1+ sheds speculative decoding — a pure-
        throughput optimization whose draft verify columns cost pool
        pages and step FLOPs the overloaded engine needs elsewhere
        (outputs are spec-invariant by the PR-9 bar, so shedding is
        invisible in tokens)."""
        if self._ladder is not None and self._ladder.stage >= 1:
            return 0
        return self.config.spec_k

    def _effective_prefill_chunk(self):
        """Ladder stage 2+ halves the prefill chunk (floor: one page):
        new requests trade TTFT for the running set's TPOT — each
        sweep spends less of the step on prefill FLOPs. A second chunk
        is a second mixed program (P rows of chunk//2), warmed on first
        use and gauged via the stage transition."""
        C = self.config.prefill_chunk
        if self._ladder is not None and self._ladder.stage >= 2:
            # never LARGER than the configured chunk: with page_size >
            # prefill_chunk the floor would otherwise grow the chunk
            # (and compile a never-warmed bigger shape) mid-overload
            return min(C, max(self.pool.page_size, C // 2))
        return C

    def _effective_fused_k(self):
        """Ladder stage 1+ sheds the fused window FIRST, ahead of
        spec_k in the same stage's use-site ordering: the window is a
        pure latency-amortization whose k-token page reservations and
        held retire slots are exactly the flexibility an overloaded
        scheduler needs back. Outputs are fused-invariant by the ISSUE
        19 bar, so shedding is invisible in tokens."""
        if self._ladder is not None and self._ladder.stage >= 1:
            return 1
        return self.config.fused_k

    def _fused_ok(self, k):
        """Quiescence gate for a k-iteration fused window: the
        scheduler must have no decision due (Scheduler.quiescent) and
        the degrade ladder no stage transition reachable within k
        observations of the CURRENT pressure (DegradeLadder.
        would_transition) — a window the ladder would interrupt
        mid-flight must not be dispatched at all."""
        if not self.scheduler.quiescent():
            return False
        if self._ladder is not None:
            p = DegradeLadder.pressure_of(
                self.pool.utilization(), len(self.scheduler.waiting),
                self.config.max_batch_size,
                spill=self._spill_pressure())
            if self._ladder.would_transition(p, k):
                return False
        return True

    def ladder_history(self):
        """Stage-transition events [{t, from, to, pressure}]: the
        ladder's timeline."""
        return list(self._ladder.history) if self._ladder else []

    # -- request intake ------------------------------------------------------
    def submit(self, prompt_ids, max_new_tokens=32, eos_token_id=None,
               temperature=1.0, top_k=0, tenant_id=None, priority=None,
               deadline_s=None):
        """Queue one request. `tenant_id`/`priority`/`deadline_s` are
        the multi-tenant knobs (ISSUE 15): priority defaults to the
        tenant's policy class (explicit values override), and a
        deadline the backlog already makes unmeetable REJECTS here with
        a structured AdmissionRejected (retry_after_s hint) instead of
        queueing to certain failure."""
        if priority is None:
            priority = (self._tenants.priority_of(tenant_id)
                        if self._tenants is not None else 0)
        req = Request(prompt_ids, max_new_tokens=max_new_tokens,
                      eos_token_id=eos_token_id, temperature=temperature,
                      top_k=top_k, tenant_id=tenant_id,
                      priority=priority, deadline_s=deadline_s)
        total = len(req.prompt) + req.max_new_tokens
        if total > self.max_pages_per_seq * self.pool.page_size:
            raise ValueError(
                f"request needs {total} tokens; page table holds "
                f"{self.max_pages_per_seq} pages of {self.pool.page_size}")
        if self.pool.pages_for(total) > self.pool.num_pages:
            # reject NOW: admission's page budget would skip it forever
            # (no amount of preemption frees pages the pool doesn't have)
            raise PoolExhausted(
                f"KV pool ({self.pool.num_pages} pages x "
                f"{self.pool.page_size}) cannot hold one request of "
                f"{total} tokens — raise num_pages")
        if total > self.model.config.max_seq_len:
            raise ValueError(
                f"prompt({len(req.prompt)}) + max_new_tokens"
                f"({req.max_new_tokens}) exceeds max_seq_len"
                f"({self.model.config.max_seq_len})")
        if req.deadline_s is not None and self.deadline_admission:
            est = self._estimate_completion_s(total)
            if est is not None and est > req.deadline_s:
                self._deadline_rejects += 1
                if req.tenant_id is not None:
                    self._tstat(req.tenant_id)['deadline_rejects'] += 1
                raise AdmissionRejected(
                    'deadline_unmet',
                    retry_after_s=est - req.deadline_s,
                    estimated_s=est, deadline_s=req.deadline_s)
        # sampling ordinal: engine-local, assigned in submission order
        # so identically-seeded engines fed the same prompts derive
        # identical per-position sampling keys (the fused-vs-serial
        # and disaggregated-vs-unified token-identity bar). Adopted
        # requests (disaggregation) carry the ordinal their submitting
        # engine assigned.
        if req.sample_ord is None:
            req.sample_ord = self._next_sample_ord
            self._next_sample_ord += 1
        self.scheduler.submit(req)
        req.submit_ns = time.perf_counter_ns()
        self._submitted += 1
        if req.tenant_id is not None:
            self._tstat(req.tenant_id)['submitted'] += 1
        fields = {}
        if req.tenant_id is not None:
            fields['tenant_id'] = req.tenant_id
        if req.priority:
            fields['priority'] = req.priority
        if req.deadline_s is not None:
            fields['deadline_s'] = req.deadline_s
        self._trace(req, 'submit', t=req.submit_time,
                    prompt_tokens=len(req.prompt),
                    max_new_tokens=req.max_new_tokens, **fields)
        return req

    def _trace(self, req, event, t=None, **fields):
        if self.tracer is not None:
            self.tracer.record(req.id, event, t=t, **fields)

    def generate(self, prompts, max_new_tokens=32, eos_token_id=None,
                 temperature=1.0, top_k=0, max_steps=None):
        """Batch convenience: submit every prompt, drive step() until
        drained, return per-prompt token lists (prompt + generated) in
        submission order."""
        reqs = [self.submit(p, max_new_tokens=max_new_tokens,
                            eos_token_id=eos_token_id,
                            temperature=temperature, top_k=top_k)
                for p in prompts]
        guard = max_steps or 16 * (max_new_tokens + 4) * max(
            1, math.ceil(len(reqs) / self.config.max_batch_size))
        steps = 0
        while self.scheduler.has_work:
            self.step()
            steps += 1
            if steps > guard:
                raise RuntimeError(
                    f"serving loop did not drain in {guard} steps")
        return [r.output_ids() for r in reqs]

    # -- engine iteration ----------------------------------------------------
    def step(self):
        """One scheduler iteration, ONE STEP AHEAD of the device: admit
        waiting requests, then plan the next step — the next prompt
        chunk of every prefilling request, the running set's decode
        rows — and queue its programs (`_launch`) BEFORE the ids of the
        step in flight are fetched and accepted (`_land`), so the
        host's turn runs under the device's work. The plan is made on
        the host's state plus what the step in flight is KNOWN to do
        (`_known`); a decode row's query token goes from one program to
        the next on the device. What cannot be planned so drains the
        pipe first (`_drain`). Every call delivers one step's tokens:
        the first after idle launches two steps and lands one, a call
        with nothing left to launch only lands. Records a timeline
        entry, runs the stalled-request watchdog, publishes metrics.
        The whole of it is one `serve::step` span; its children are the
        span table of docs/serving.md#spans."""
        self._step_ordinal += 1
        with RecordEvent('serve::step', event_type='serve',
                         step=self._step_ordinal):
            self._step()

    def _step(self):
        completed_before = self._completed
        preempt_before = self.scheduler.preemptions
        t_begin = self._gap.dispatch_begin()
        t_sched = time.perf_counter()
        self._check_stalled()
        with RecordEvent('serve::admit', event_type='serve') as ev:
            admitted = self._admit()
            ev.args = {'admitted': admitted}
        sched_dt = time.perf_counter() - t_sched
        if self._flight is None:
            # nothing in flight: the serial order, on the host's own
            # state (a verify step or a fused window lands in here)
            self._flight = self._launch()
        depth = 1
        if self._flight is not None:
            why = self._holds_back()
            ahead = None if why else self._launch()
            # (a preemption inside that launch has drained already)
            if self._flight is not None:
                if ahead is not None:
                    depth = 2
                    self._land(behind=True)
                else:
                    self._drain(why or 'idle')
            self._flight = ahead
            if ahead is not None and not self.scheduler.has_work:
                # every request ended in the step that landed (an EOS):
                # what was launched behind it carries nothing to wait
                # for, and a caller that steps while there is work
                # would never land it
                self._drain()
        fused = self._fused_last
        self._fused_last = None
        wall = time.perf_counter() - t_begin
        with RecordEvent('serve::telemetry', event_type='serve'):
            self._step_telemetry(
                fused, wall, sched_dt, admitted, depth,
                self.scheduler.preemptions - preempt_before,
                self._completed != completed_before)

    def _reset_iteration(self):
        """The iteration's phase clocks, roofline counts and landed
        rows: _enqueue, _fetch and _land alone feed them (host
        perf_counter segments — never a device sync), _step_telemetry
        hands them to the ledger and resets them, so that what lands
        between two steps (a drain for an abort) is in the next
        record."""
        self._it_compute = 0.0
        self._it_fetch = 0.0
        self._it_gating = 0.0
        self._it_decode_s = 0.0
        self._it_kv_read_tokens = 0
        self._it_kv_window = [0, 0]
        self._it_moe_load = None
        self._it_live_pages = 0
        self._it_page_slots = 0
        self._it_prefill_tokens = 0
        self._it_prefill_s = 0.0
        self._it_prefill_ctx = 0
        # what the landed step carried: decode rows, the tokens they
        # emitted (> rows when speculative decoding accepts drafts),
        # prompt tokens prefilled
        self._it_rows = 0
        self._it_tokens = 0
        self._it_chunk_tokens = 0

    def _step_telemetry(self, fused, wall, sched_dt, admitted, depth,
                        preempted, retired):
        """Everything in a step that observes and serves nothing (the
        `serve::telemetry` span; ROADMAP D5): the timeline and ledger
        records, the degrade ladder's pressure reading, host-tier
        close-out, the gap monitor's close and the metrics publish.
        `depth` is how many steps were queued on the device when this
        one's ids were fetched."""
        prefill_tokens = self._it_chunk_tokens
        decode_slots, decode_tokens = self._it_rows, self._it_tokens
        # one observability record per decode ITERATION: a fused
        # window runs n_iter device iterations inside one dispatch,
        # and the timeline / ladder / ledger must see the same per-
        # iteration stream serial decode produces (k entries, each
        # with that iteration's row occupancy; wall and phase segments
        # amortized across the window) — otherwise every downstream
        # consumer of these signals (router occupancy tiebreaks, alert
        # rules, ledger decode throughput) would read a kx-slower
        # engine. Admissions/preemptions/prefill attribute to the
        # first entry only: they happened once, before the window.
        n_iter = fused['iters'] if fused else 1
        for j in range(n_iter):
            first = (j == 0)
            self._observe_pressure()
            entry = dict(
                t=self._clock(),
                decode_slots_occupied=(fused['rows'][j] if fused
                                       else decode_slots),
                decode_slots=self.config.max_batch_size,
                prefill_tokens=prefill_tokens if first else 0,
                decode_tokens=(fused['rows'][j] if fused
                               else decode_tokens),
                admissions=admitted if first else 0,
                preemptions=preempted if first else 0,
                waiting=len(self.scheduler.waiting),
                pool_pages_in_use=self.pool.pages_in_use,
                pool_pages_total=self.pool.num_pages,
                degrade_stage=self.degrade_stage())
            if fused:
                entry['fused'] = True
                entry['fused_k'] = fused['k']
            self.timeline.record(**entry)
            # ledger close-out: the iteration wall and its measured
            # phase segments. Under a fused window the one host fetch
            # amortizes over the window's iterations — the per-window
            # host-fetch attribution that makes host_bound_fraction
            # drop k-fold instead of misreading the window as one
            # giant iteration.
            self.ledger.observe_iteration(
                wall=wall / n_iter,
                compute=self._it_compute / n_iter,
                host_fetch=self._it_fetch / n_iter,
                schedule=sched_dt / n_iter,
                decode_seconds=self._it_decode_s / n_iter,
                kv_read_tokens=self._it_kv_read_tokens // n_iter,
                kv_window_tokens=self._it_kv_window if first else None,
                moe_load=self._it_moe_load if first else None,
                paged_live_pages=self._it_live_pages if first else 0,
                paged_page_slots=self._it_page_slots if first else 0,
                prefill_tokens=self._it_prefill_tokens if first else 0,
                prefill_seconds=self._it_prefill_s if first else 0.0,
                prefill_ctx_tokens=self._it_prefill_ctx if first else 0)
        # host-tier close-out (ISSUE 20): transfer wall accumulated by
        # spill/fetch since the last step folds into the ledger's
        # page_stream component (the disagg-handoff attribution point),
        # and newly spilled pages since the last step emit one engine-
        # scope `spill` trace event. One falsy check when tierless; no
        # host sync either way (the tier counts on the transfer thread).
        if self._host_tier is not None:
            tier_wall = self._host_tier.take_wall()
            if tier_wall > 0.0:
                self.ledger.note_page_stream(tier_wall)
            spilled = self._host_tier.spilled_pages
            if spilled > self._tier_spilled_seen:
                if self.tracer is not None:
                    self.tracer.record(
                        ENGINE_REQ, 'spill',
                        pages=spilled - self._tier_spilled_seen,
                        host_used_pages=self._host_tier.used_slots)
                self._tier_spilled_seen = spilled
        # gap-monitor span close: dispatch_end BEFORE note_gating —
        # dispatch_end zeroes the pending gating attribution, and the
        # fetch wait belongs to the span that just closed (it is
        # consumed by the NEXT dispatch_begin).
        # A fetch with the next step queued behind it waits on a busy
        # device (blocked); one with nothing behind it lets the device
        # run dry (gating).
        self._gap.dispatch_end(depth=depth)
        if self._it_gating > 0.0:
            self._gap.note_gating(self._it_gating)
        if self._it_fetch > self._it_gating:
            self._gap.note_blocked(self._it_fetch - self._it_gating)
        self._reset_iteration()
        # publish cadence: retire and drain publish immediately; the
        # periodic path keys to the MONITOR's wall clock (the same
        # source gauge last_update stamps and staleness alert rules
        # read), never to config.clock — an injected deterministic
        # clock, or fused windows retiring k tokens per step, must not
        # let gauge freshness lapse into `metrics_stale` alerts.
        if (retired
                or not self.scheduler.has_work
                or (_monitor._time_fn() - self._last_publish_wall
                    >= self.PUBLISH_INTERVAL_S)):
            self.publish_metrics()

    def _observe_pressure(self):
        """Feed the degradation ladder this iteration's pressure and
        apply any stage transition: gauge set immediately, an engine-
        scope `degrade_stage` trace event, and the stage-3 weighted-
        eviction lever armed/disarmed on the pool. Stage 1 (spec shed)
        and 2 (prefill shrink) act through _effective_spec_k /
        _effective_prefill_chunk at their use sites."""
        self._observe_spill_pressure()
        if self._ladder is None:
            return
        ev = self._ladder.observe(self.pool.utilization(),
                                  len(self.scheduler.waiting),
                                  self.config.max_batch_size,
                                  spill=self._spill_pressure())
        if ev is None:
            return
        _metrics.publish_degrade_stage(self._ladder.stage,
                                       self._ladder.pressure())
        if self.tracer is not None:
            self.tracer.record(
                ENGINE_REQ, 'degrade_stage', t=ev['t'],
                from_stage=ev['from'], stage=ev['to'],
                stage_name=DegradeLadder.STAGE_NAMES[ev['to']],
                pressure=ev['pressure'])
        if ev['to'] >= 3 and self._tenants is not None:
            weights = self._tenants.eviction_weights()
            for pool in (self.pool, *self._stage3_pools):
                pool.set_eviction_weights(weights)
        elif ev['from'] >= 3 > ev['to']:
            for pool in (self.pool, *self._stage3_pools):
                pool.set_eviction_weights(None)

    def _spill_pressure(self):
        """Host-tier occupancy in [0, 1] — the ladder's spill input
        (ISSUE 20): while the tier has room, spilling absorbs pool
        pressure and the ladder need not escalate to stage-3 weighted
        eviction; a saturating tier pushes pressure back up so the
        eviction lever arms only once the second tier is spent. 0.0
        without a tier — the ladder then sees exactly PR-19's signal."""
        t = self._host_tier
        return t.used_slots / t.host_pages if t is not None else 0.0

    def _observe_spill_pressure(self):
        """The proactive spiller: pool utilization past the spill
        watermark kicks an ASYNC spill of LRU-parked cached subtrees
        (bounded by the transfer window) so the free list restocks off
        the critical path — allocation's synchronous spill fallback is
        for when this didn't keep up. A falsy check without a tier."""
        if self._host_tier is None:
            return
        if self.pool.utilization() >= self.config.spill_watermark \
                and self.pool.cached_pages > 0:
            self.pool.spill_lru(
                max_pages=max(self.pool.num_pages // 8, 1))

    def _admit(self):
        """Admit waiting requests one at a time against a free-page
        budget: each admission reserves its FIRST chunk's pages (the
        pool doesn't allocate until the prefill step runs, so the
        budget, not pool.free_pages, is what shrinks here) — admitting
        more than the pool can first-chunk just manufactures
        preemption churn.

        Prefix-cache hits shrink the bill (ISSUE 9 satellite: the
        PR-5 estimate over-counted and refused admissible requests):
        pages a live sibling already maps cost the budget NOTHING,
        and cached-resurrect pages cost a page but no prefill compute
        — so the need is the first chunk's page-table size minus the
        live-shared pages.

        Head-of-line fairness (ISSUE 11 satellite): a head whose first
        chunk exceeds this sweep's budget no longer blocks the sweep —
        the scan continues down the queue and admits any follower that
        DOES fit (FCFS order among the admissible). The skipped head
        keeps its queue position; and so that a stream of small
        requests can't starve it forever (every retire's freed pages
        going straight to a new follower), each follower admitted past
        it counts against HOL_BYPASS_LIMIT — once spent, the sweep
        reverts to blocking at the head, freed pages accumulate across
        sweeps, and the head admits as soon as they cover its chunk.

        Tenancy (ISSUE 15): the sweep runs in priority-then-FCFS
        order (scheduler.admission_order — arrival order when no
        tenants are configured), and a quota'd tenant's request debits
        its whole token bill from the tenant bucket at FIRST admit.
        Insufficient quota DEFERS the request (skipped this sweep, a
        `quota_defer` trace event on the defer edge) — it admits once
        the bucket refills; the defer does not spend the HOL bypass
        bound (quota is the tenant's own backpressure, not page
        starvation). Resume after preemption never re-debits."""
        sched = self.scheduler
        budget = self.pool.free_pages
        n_admitted = 0
        n_bypassed = 0          # admissions AFTER the head blocked —
                                # only those are bypasses (a request
                                # admitted while it was itself the
                                # head passed nobody)
        blocked_head = None
        skipped_before = False  # "req is the live queue head" ⟺ every
                                # earlier entry of the sweep admitted —
                                # the order-list twin of the old
                                # `req is waiting[0]` check
        for req in sched.admission_order():
            victim = None
            if None not in sched.slots:
                # slot-pressure preemption (tenancy only): a waiting
                # request strictly ABOVE some running tenant's class
                # displaces the youngest of the lowest class below it
                # — the admitting request's victim rule — instead of
                # waiting out the victim's whole decode. Charged like
                # any preemption; the victim re-queues at the front of
                # its class and, being lower-priority, cannot churn
                # back in. Untenanted engines break here exactly as
                # before (FCFS never preempts for admission).
                if self._tenants is None:
                    break
                victim = sched.preempt_victim(
                    below_priority=req.priority)
                if victim is None:
                    break       # order is priority-sorted: nobody
                                # later outranks the running set either
                if self._drain('preempt'):
                    # decided again on what the step in flight returned:
                    # it may have retired the victim, or freed a slot
                    victim = None if None in sched.slots else \
                        sched.preempt_victim(below_priority=req.priority)
                    if victim is None and None not in sched.slots:
                        break
            # host-resurrect pages (ISSUE 20) bill the page budget one
            # allocatable page each, same as device-resurrect — but
            # their cost is a host→device TRANSFER, not prefill
            # compute: the cached span still skips the prefill chunks,
            # and the fetch wall lands in the ledger's page_stream
            # component instead of compute
            cached, live, _resv, _host = self.pool.peek_prefix(
                req.tokens, limit=len(req.tokens) - 1)
            need = max(self.pool.pages_for(
                min(len(req.tokens),
                    cached + self._effective_prefill_chunk())) - live,
                0)
            # feasibility BEFORE any side effect: nothing is billed
            # and no victim's work is destroyed for an admit the page
            # budget still wouldn't cover (a victim whose pages are
            # all shared reclaims nothing — count only what its
            # release would actually free)
            avail = budget + (self.pool.reclaimable_pages(victim.id)
                              if victim is not None else 0)
            if avail < need:
                if not skipped_before:
                    if req.admit_bypasses >= self.HOL_BYPASS_LIMIT:
                        break       # starvation bound reached: stop
                                    # bypassing, let pages accumulate
                    blocked_head = req
                skipped_before = True
                continue        # oversized for THIS sweep's budget:
                                # skip, keep scanning for a fit
            if not self._try_debit_quota(req):
                skipped_before = True
                continue        # over quota: deferred, not dropped
            if victim is not None:
                budget += self._charge_and_preempt(req, victim)
            if sched.admit_request(req) is None:
                skipped_before = True
                continue
            req.quota_deferred = False
            budget -= need
            n_admitted += 1
            if req.admit_ns is None and req.submit_ns is not None:
                # first admit: the request's queueing ends here, on the
                # span ring's clock (config.clock may be injected)
                req.admit_ns = time.perf_counter_ns()
                record_span('serve::request.queue', req.submit_ns,
                            req.admit_ns, event_type='serve', req=req.id,
                            prompt_tokens=len(req.prompt))
            if blocked_head is not None:
                n_bypassed += 1
            self._trace(req,
                        'resume' if req.preemptions else 'admit',
                        t=(req.admit_time
                           if not req.preemptions else None),
                        slot=sched.slot_of(req),
                        waiting=len(sched.waiting))
        if blocked_head is not None:
            blocked_head.admit_bypasses += n_bypassed
        return n_admitted

    def _try_debit_quota(self, req):
        """Debit req's token bill (prompt + generation budget) from
        its tenant's bucket at first admit. True = admit may proceed
        (no tenancy / no quota / already charged / debit succeeded);
        False = defer this sweep. The defer EDGE (not every deferred
        sweep) counts in the quota_deferrals gauges and emits one
        quota_defer trace event carrying the bucket's own retry
        estimate."""
        if self._tenants is None or req.quota_charged:
            return True
        bucket = self._tenants.bucket(req.tenant_id)
        if bucket is None:
            return True
        bill = len(req.prompt) + req.max_new_tokens
        if bucket.try_debit(bill):
            req.quota_charged = True
            if req.tenant_id is not None:
                self._tstat(req.tenant_id)['tokens_billed'] += bill
            return True
        if not req.quota_deferred:
            req.quota_deferred = True
            req.quota_defers += 1
            self._quota_deferrals += 1
            self._tstat(req.tenant_id)['quota_deferrals'] += 1
            self._trace(req, 'quota_defer', tenant_id=req.tenant_id,
                        bill_tokens=bill,
                        retry_after_s=bucket.seconds_until(bill))
        return False

    def adopt_request(self, req):
        """Adopt a request prefilled ELSEWHERE (prefill→decode
        disaggregation, serving/cluster/disagg.py): its KV pages were
        already allocated in this engine's pool under req.id and their
        contents streamed in, its first token is already in
        req.generated — it goes straight to a RUNNING decode slot.
        Returns False when no slot is free (caller keeps it pending).
        The streamed pages join this pool's prefix index so decode-side
        siblings share them like locally-prefilled ones."""
        if self._stateful:
            raise NotImplementedError(
                f"{type(self.model).__name__} holds recurrent state, and "
                f"no state is kept at the position a request would "
                f"resume from: adopt_request (pages stream between "
                f"engines, the state of the prompt's end does not)")
        self._drain('adopt')
        if self.scheduler.adopt(req) is None:
            return False
        req.prefilled = len(req.tokens)
        self._submitted += 1
        # everything but the newest token has K/V resident (the next
        # decode step writes that one) — same invariant _decode_step
        # maintains
        self.pool.register_prefix(req.id, req.tokens,
                                  req.context_len - 1,
                                  owner=req.tenant_id)
        self._trace(req, 'admit', slot=self.scheduler.slot_of(req),
                    handoff=True,
                    pages=len(self.pool.page_table(req.id)))
        if req.done:
            self._retire(req)
        return True

    def _ensure_or_preempt(self, req, n_tokens):
        """Grow req's pages, preempting other in-flight requests until
        the allocation fits. Refcount-aware: a victim's release only
        reclaims pages no live sibling still maps — a victim whose
        pages are all shared frees nothing, so the loop keeps
        preempting (older victims) rather than spinning on one, and a
        sharer's prefix is never yanked out from under it.

        Victim choice (ISSUE 15): with tenants configured the victim
        is the youngest request of the lowest priority class STRICTLY
        below req's — falling back to req's own class (youngest peer,
        the untenanted rule restricted to <= req.priority) only when
        nobody below holds a slot, so the engine never deadlocks on a
        same-priority pool squeeze but also never preempts upward.
        When every OTHER slot-holder outranks req, req YIELDS instead
        (its own pages release and it re-queues at the front of its
        class, returning False) — the untenanted engine would have
        preempted upward here; raising would crash the serve loop on
        a recoverable pressure condition. Every tenancy-mode
        preemption is CHARGED to the preemptor's quota bucket (the
        victim's prefilled tokens — the work the preemption destroys
        and the pool must recompute), so a high-priority tenant can't
        churn the pool for free. Returns True when capacity was
        ensured, False when req itself was preempted (the caller must
        not touch its pages this sweep)."""
        sched = self.scheduler
        while True:
            try:
                self.pool.ensure_capacity(req.id, n_tokens)
                return True
            except PoolExhausted:
                if self._drain('preempt'):
                    # a preemption is decided on what the step in flight
                    # returned: it may hand pages back (a retire), and
                    # its rows' pages may not go while it writes them.
                    # It may also have ended `req` itself (an EOS)
                    if req.state == RequestState.FINISHED:
                        return False    # (its retire released the pages)
                    continue
                if self._tenants is not None:
                    victim = sched.preempt_victim(
                        exclude=req, below_priority=req.priority)
                    if victim is None:
                        victim = sched.preempt_victim(
                            exclude=req,
                            below_priority=req.priority + 1)
                else:
                    victim = sched.preempt_victim(exclude=req)
                if victim is None:
                    if (self._tenants is not None
                            and req in sched.slots
                            and any(r is not None and r is not req
                                    for r in sched.slots)):
                        released = self.pool.release(req.id)
                        sched.preempt(req)
                        self._trace(
                            req, 'preempt', pages_released=released,
                            tokens_generated=len(req.generated),
                            reason='yield_to_higher_priority')
                        return False
                    raise PoolExhausted(
                        f"KV pool ({self.pool.num_pages} pages x "
                        f"{self.pool.page_size}) cannot hold one request "
                        f"of {n_tokens} tokens — raise num_pages")
                self._charge_and_preempt(req, victim)

    def _charge_and_preempt(self, req, victim):
        """Preempt `victim` on behalf of `req`: charge the victim's
        destroyed prefill work to req's tenant bucket (tenancy mode),
        release the victim's pages and re-queue it at the front of its
        class. Returns the pages released (the admission sweep's
        budget gain). One body for both preemption sites — pool
        exhaustion (_ensure_or_preempt) and slot pressure (_admit) —
        so the charging rule can't drift between them."""
        charge = 0
        if self._tenants is not None:
            charge = max(victim.prefilled, 1)
            bucket = self._tenants.bucket(req.tenant_id)
            if bucket is not None:
                bucket.charge(charge)
            self._preemptions_charged += 1
            if req.tenant_id is not None:
                st = self._tstat(req.tenant_id)
                st['preemptions_charged'] += 1
                st['charge_tokens'] += charge
        released = self.pool.release(victim.id)
        self.scheduler.preempt(victim)
        self._trace(victim, 'preempt', pages_released=released,
                    for_req=req.id,
                    tokens_generated=len(victim.generated),
                    **({'charged_to': req.tenant_id,
                        'charge_tokens': charge}
                       if self._tenants is not None else {}))
        return released

    # -- jitted steps --------------------------------------------------------
    def _step_fn(self, key):
        """The compiled program under `key`, built on first use.
        (B, T, sample, verify) is the [B, T] step: sample=False
        compiles a greedy argmax — the common serving mode must not pay
        _device_sample's full-vocab sort on every dispatch (top_ks is
        traced, XLA can't elide it) — and verify=True the speculative-
        decode shape [max_batch, spec_k+1], the greedy argmax at EVERY
        query position (the per-draft verdicts) instead of just the
        last. ('mixed', B, P, C, sample) is the [B, 1] decode rows and
        P prompt chunks of C in one program. ('fused', B, K, sample) is
        K iterations of the [B, 1] step under one lax.scan (ISSUE
        19)."""
        fn = self._step_fns.get(key)
        if fn is None:
            fn = self._step_fns[key] = self._build_step(key)
        return fn

    def _build_step(self, key):
        """The one builder of compiled steps. What every shape shares
        is written once: the parameters bound for the trace (int8
        weights de-quantised, the mp region entered), the forward over
        the paged pool and the pick of each row's next id, donation of
        the pool, the shard_map specs, and the eval()/no_grad call.
        Every program is `step(params, kv, state, moe, prev_ids, *host
        operands) -> (ids, handoff, kv, state, moe)`; `moe` (the
        experts' counters) is None wherever the model or the route
        carries none, `state` (the recurrent-state arrays, with each
        row's `slots` the last host operand) wherever the model
        declares none — and then no operand, and nothing of it in the
        traced program. `prev_ids` -> `handoff` ([B] int32, by batch
        slot: the newest id of the slot's request) goes from each
        dispatch to the next ON the device, so that a step can be
        queued before the ids of the one before it have come to the
        host: `src` [R] names, for a decode row, the slot of `prev_ids`
        that holds its query token (-1: the host's, in `tokens`) and,
        for a chunk row, the slot its sampled id is handed on in (-1:
        an idle row). Both operands are always there — one program a
        shape, whether a step is in flight or not."""
        jax, jnp = self._jax, self._jnp
        model = self.model
        from ..core.tensor import Tensor
        from ..core.autograd import no_grad
        from ..jit import bind_arrays
        max_pos = model.config.max_seq_len - 1
        qdtypes = dict(self._qparam_dtypes)
        mp = self._mp
        fused = key[0] == 'fused'
        verify = False
        # the rows a dispatch carries, in groups of (rows, width)
        if fused:
            _, B, K, sample = key
            layout = ((B, 1),)
        elif key[0] == 'mixed':
            _, B, P, C, sample = key
            layout = ((B, 1), (P, C))
        else:
            B, T, sample, verify = key
            layout = ((B, T),)

        @contextlib.contextmanager
        def bound(params):
            # fused dequant of weight-only-quantized params:
            # q * (scale / 127) per out-channel, cast to storage dtype
            arrs = {}
            for n, v in params.items():
                if isinstance(v, dict):
                    s = v['s'] * (1.0 / 127.0)
                    shape = [1] * (v['q'].ndim - 1) + [-1]
                    arrs[n] = (v['q'].astype(jnp.float32)
                               * s.reshape(shape)).astype(qdtypes[n])
                else:
                    arrs[n] = v
            # mp_layers key their collectives off the spmd region —
            # without it a >1-degree model would silently run the
            # degenerate single-rank math on sharded weights
            region = contextlib.nullcontext()
            if mp > 1:
                from ..distributed import collective as C
                region = C.spmd_region(('mp',))
            with bind_arrays(model, arrs), region:
                yield

        def full_logits(lg):
            """Vocab-parallel logits -> full vocab: the tied LM head is
            the VocabParallelEmbedding weight, so under mp each shard
            computes [., V/mp] logits for its vocab rows; argmax /
            sampling need the whole vocab, so gather over 'mp' (shard
            i's rows are vocab block i — concat order is the identity)."""
            if mp <= 1:
                return lg
            g = jax.lax.all_gather(lg, 'mp')        # [mp, ..., V/mp]
            g = jnp.moveaxis(g, 0, -2)              # [..., mp, V/mp]
            return g.reshape(lg.shape[:-1] + (lg.shape[-1] * mp,))

        def forward_pick(kv, state, moe, tokens, page_tables, seq_lens,
                         q_lens, key, ords, temps, top_ks, slots=None,
                         restore=None, shared=None):
            """The dispatch's query tokens ([N], `layout`'s rows end to
            end; the per-row operands [R]) through the model over the
            paged pool, then each row's next id: the hidden state of its
            last query -> logits -> sampled or greedy. -> (ids, kv,
            state, moe)."""
            if shared is not None:
                # int32 [1 + pages + B]: the run's keys, its page ids,
                # its member rows
                P = page_tables.shape[1]
                shared = (shared[1:1 + P], shared[0], shared[1 + P:] > 0)
            rows = RowGroups(layout, page_tables, seq_lens, q_lens, slots,
                             restore, shared)
            # int8 pools carry (k, v, k_scales, v_scales) per layer;
            # dense pools (k, v) — forward_paged keys off the arity
            cts = [tuple(Tensor(a) for a in c) for c in kv]
            # a model with recurrent state takes it and hands it back
            # as a fourth result; the others are called as they were
            stateful = {} if state is None else {
                'state': [Tensor(a) for a in state]}
            h, new_kv, moe, *new_state = model.forward_paged(
                Tensor(tokens[None, :]), Tensor(rows.positions(max_pos)),
                cts, rows, moe_counters=moe, **stateful)
            if new_state:
                state = [t.data for t in new_state[0]]
            new_kv = [tuple(t.data for t in c) for c in new_kv]
            w = model.lm_head_weight()
            if verify:
                # multi-query verify: greedy next-token at every
                # draft position in one dispatch; padding positions
                # (t >= q_len) produce garbage the host ignores.
                # Rows that sample ride along via an extra column
                # so the step still costs ONE host fetch.
                logits_all = full_logits(jnp.einsum(
                    'nh,vh->nv', h.data[0], w.data,
                    preferred_element_type=jnp.float32))
                nxt = jnp.argmax(logits_all, axis=-1) \
                    .astype(jnp.int32).reshape(layout[0])   # [B, T]
                if sample:
                    samp = _device_sample(
                        rows.last(logits_all[None]).astype(jnp.float32),
                        key, ords, seq_lens, temps, top_ks)
                    nxt = jnp.concatenate([nxt, samp[:, None]], 1)
                return nxt, new_kv, state, moe
            logits = full_logits(jnp.einsum(
                'bh,vh->bv', rows.last(h.data), w.data,
                preferred_element_type=jnp.float32))
            if sample:
                nxt = _device_sample(logits.astype(jnp.float32), key,
                                     ords, seq_lens, temps, top_ks)
            else:
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return nxt, new_kv, state, moe

        if not fused:
            def step(params, kv, state, moe, prev_ids, tokens,
                     page_tables, seq_lens, q_lens, src, key, ords,
                     temps, top_ks, slots=None, restore=None, shared=None):
                with bound(params):
                    if not verify:
                        # a decode row whose newest token never came to
                        # the host takes it where the last dispatch
                        # left it
                        fed = src[:B]
                        tokens = tokens.at[:B].set(jnp.where(
                            fed >= 0, prev_ids[jnp.maximum(fed, 0)],
                            tokens[:B]))
                    nxt, kv, state, moe = forward_pick(
                        kv, state, moe, tokens, page_tables, seq_lens,
                        q_lens, key, ords, temps, top_ks, slots, restore,
                        shared)
                    handoff = prev_ids
                    if not verify:
                        # handed on by slot: a live decode row's id in
                        # its own, a chunk row's in its request's (only
                        # a prompt's last chunk's is ever read there)
                        handoff = jnp.where(q_lens[:B] > 0, nxt[:B],
                                            prev_ids)
                        if len(layout) > 1:
                            to = src[B:]
                            handoff = handoff.at[
                                jnp.where(to >= 0, to, B)].set(
                                    nxt[B:], mode='drop')
                    if moe is not None:
                        # the experts' rows of this call and their
                        # counters ride behind the sampled ids: still
                        # ONE host fetch a step (_take_moe)
                        counts, moe = moe
                        nxt = jnp.concatenate(
                            [nxt, counts.reshape(-1), moe.reshape(-1)])
                return nxt, handoff, kv, state, moe
        else:
            def step(params, kv, state, moe, prev_ids, tokens,
                     page_tables, seq_lens, ords, rems, eos_ids, live,
                     key, temps, top_ks):
                # The carry is (kv pool, last token, seq_len, done-mask,
                # emitted count) per row; each scan body is the [B, 1]
                # decode step by call — same positions, same sampling
                # key folded per (ordinal, absolute position) — so the K
                # stacked outputs are token-identical to K serial
                # dispatches. Rows that hit eos or their budget mid-
                # window flip `done` and ride the remaining iterations
                # with q_len=0 (the idle-slot mechanism: KV writes
                # dropped by the scatter, outputs ignored by the host).
                # The window carries no experts' counters and no
                # recurrent state (refused at construction).
                with bound(params):
                    def body(carry, _):
                        kv_c, tok, seq, done, emitted = carry
                        alive = ~done
                        q = jnp.where(alive, 1, 0).astype(jnp.int32)
                        nxt, new_kv, _, _ = forward_pick(
                            kv_c, None, None, tok, page_tables, seq, q,
                            key, ords, temps, top_ks)
                        # serial-order accounting: the emitted token
                        # counts BEFORE the eos/budget check (append-
                        # then-check), so eos-in-window truncates
                        # precisely where the one-token path stops
                        emitted2 = emitted + q
                        hit_eos = (eos_ids >= 0) & (nxt == eos_ids)
                        done2 = done | hit_eos | (emitted2 >= rems)
                        tok2 = jnp.where(alive, nxt, tok)
                        return (new_kv, tok2, seq + q, done2,
                                emitted2), nxt

                    carry0 = (kv, tokens, seq_lens, ~live,
                              jnp.zeros((B,), jnp.int32))
                    (kv, _t, _s, _d, _e), ys = jax.lax.scan(
                        body, carry0, xs=None, length=K)
                # (a window is never launched ahead: its ids are on
                # the host before the next step is planned)
                return (jnp.moveaxis(ys, 0, 1), prev_ids, kv, state,
                        moe)                                    # [B, K]

        # donation updates the pool pages (and the recurrent state) in
        # place; CPU jax has no donation support and would warn every
        # call
        donate = (1, 2) if jax.default_backend() != 'cpu' else ()
        if mp > 1:
            # one jit(shard_map(step)) over the replica-local mesh —
            # the hybrid train step's layout applied to serving: params
            # at their split axes, KV pages on the heads axis, all the
            # tiny host-built operands (tokens/tables/lens/key)
            # replicated; the sampled tokens come back replicated
            # (every shard gathers the full vocab)
            from jax import shard_map
            from jax.sharding import PartitionSpec as P
            kv_specs = [tuple(P(None, None, 'mp') for _ in layer)
                        for layer in self.pool.kv]
            # params, kv, state, moe, then the replicated operands:
            # the ids handed on and the host's (the mp route carries no
            # `slots` and no `restore`: no state; and no `shared` run)
            host_operands = step.__code__.co_argcount - 4 - 3 * (not fused)
            step = shard_map(
                step, mesh=self.mesh,
                in_specs=(dict(self._param_specs), kv_specs, None, None)
                + (P(),) * host_operands,
                out_specs=(P(), P(), kv_specs, None, None),
                check_vma=False)
        jitted = jax.jit(step, donate_argnums=donate)

        def run(*args):
            was = model.training
            model.eval()
            try:
                with no_grad():
                    return jitted(*args)
            finally:
                if was:
                    model.train()
        return run

    def _dispatch(self, shape, rows, B, T, accept, *args):
        """One compiled step called, fetched and accepted at once
        (`_enqueue`, `_fetch`, then `accept(ids, rows, *args)` under
        its span), nothing queued behind it: a verify step or a fused
        window, what the host must read before it can plan on — its
        own `serve::device_step` record, drained for `shape`. Returns
        the tokens emitted."""
        self._drains[shape] += 1
        queued = self._enqueue(shape, rows, B, T)
        work = self._ran(queued, rows, ())
        work.steps += 1
        nxt = self._fetch(queued, behind=False)
        work.emitted = self._accepted(accept, nxt, rows, *args)
        self._close_device_step(self._step_ordinal, shape, behind=False,
                                drain=shape)
        return work.emitted

    def _enqueue(self, shape, rows, B, T, chunks=()):
        """The one place a compiled step is called. `rows` are the
        decode rows that carry a query, each (slot, request, query
        tokens, context length after them); `shape` says which program
        they ride: 'decode' ([B, 1]), 'verify' ([B, T], a token and its
        drafts), 'fused' (T iterations of [B, 1] in one window) or
        'mixed' — the [B, 1] decode rows AND `chunks`, prompt chunks of
        up to T tokens in the rows of the program's prefill group, each
        (row, request, query tokens, context length after them).
        Builds the host operands (idle rows of either group ride along
        with q_len 0; a row whose request takes a token from the step
        in flight is fed on the device: `src`), calls the program —
        which returns when it is queued on the device —, takes the new
        pool and the ids handed on, and feeds the iteration's clocks
        and counts (`_it_*`). Returns what `_fetch` needs to bring the
        sampled ids to the host, behind it when the call began and the
        token slots of its prefill group (`_ran`)."""
        jnp = self._jnp
        mixed, fused = shape == 'mixed', shape == 'fused'
        width = T if shape == 'verify' else 1   # a decode row's queries
        P = self._prefill_rows if mixed else 0
        with RecordEvent('serve::prepare', event_type='serve'):
            tokens = np.zeros((B * width + P * T,), np.int32)
            page_tables = np.zeros((B + P, self.max_pages_per_seq),
                                   np.int32)
            seq_lens = np.ones((B + P,), np.int32)
            q_lens = np.zeros((B + P,), np.int32)
            ords = np.zeros((B + P,), np.int32)
            temps = np.zeros((B + P,), np.float32)
            top_ks = np.zeros((B + P,), np.int32)
            # where the ids handed on from dispatch to dispatch hold a
            # decode row's query token, and where a chunk row's id goes
            src = np.full((B + P,), -1, np.int32)
            flight = self._flight
            # each row's slot in the recurrent-state arrays: a decode
            # row's is its own, a chunk's its request's; an idle row
            # names the spare slot
            slots = np.full((B + P,), self.config.max_batch_size,
                            np.int32) if self._stateful else None
            # the rows that resume after a mapped prefix: a chunk that
            # starts where its request's hit ended
            restore = np.zeros((B + P,), bool) if self._page_state \
                else None
            snapshots = 0
            ps = self.pool.page_size
            if fused:
                rems = np.zeros((B,), np.int32)
                eos_ids = np.full((B,), -1, np.int32)

            def place(row, first, req, query, context):
                tokens[first:first + len(query)] = query
                page_tables[row, :] = self._page_row(req)
                seq_lens[row] = context
                q_lens[row] = len(query)
                ords[row] = _ord_of(req)
                temps[row] = req.temperature
                top_ks[row] = req.top_k
            for i, req, query, context in rows:
                place(i, i * width, req, query, context)
                if flight is not None and req.id in flight.emits:
                    src[i] = i      # its newest token is not on the host
                if slots is not None:
                    slots[i] = i
                if restore is not None:
                    snapshots += (context // ps
                                  - (context - len(query)) // ps)
                iterations = 1
                if fused:
                    rems[i] = iterations = min(
                        T, req.max_new_tokens - len(req.generated))
                    if req.eos_token_id is not None:
                        eos_ids[i] = req.eos_token_id
                # the rooflines' counts: iteration j of a row reads
                # context + j KV tokens, in the live pages that hold
                # them, out of the slots the program's tables have
                for j in range(iterations):
                    self._it_live_pages += self.pool.pages_for(context + j)
                    self._count_kv_read(context + j, len(query), width)
            for row, req, query, context in chunks:
                place(B + row, B * width + row * T, req, query, context)
                src[B + row] = self.scheduler.slot_of(req)
                if slots is not None:
                    slots[B + row] = src[B + row]
                if restore is not None:
                    start = context - len(query)
                    snapshots += context // ps - start // ps
                    if 0 < start == req.cached_tokens:
                        restore[B + row] = True
                        self._restores += 1
                        self._restore_tokens += start
                keys = self._keys_read(context, len(query))
                self._attn_kv_tokens += keys
                self._attn_kv_chunks += keys
                self._count_pairs(context, len(query), T)
                self._it_live_pages += self.pool.pages_for(context)
                self._it_prefill_tokens += len(query)
                self._it_prefill_ctx += len(query) * context
            self._it_page_slots += (T if fused else 1) * page_tables.size
            sample = any(req.top_k > 0 for _, req, _, _ in (*rows, *chunks))
            if fused:
                key = ('fused', B, T, sample)
                head = (tokens, page_tables, seq_lens, ords, rems,
                        eos_ids, q_lens > 0)
                tail = (temps, top_ks)
            else:
                key = ('mixed', B, P, T, sample) if mixed \
                    else (B, T, sample, shape == 'verify')
                head = (tokens, page_tables, seq_lens, q_lens, src)
                tail = (ords, temps, top_ks)
            if slots is not None:
                tail += (slots,)
                live = int((q_lens > 0).sum())
                self._state_rows += self._state_layers * live
                self._state_tokens += self._state_layers * int(q_lens.sum())
            if restore is not None:
                tail += (restore,)
                self._snapshots += snapshots
            shared_rows = shared_keys = 0
            if self._shared_runs and shape in ('decode', 'mixed'):
                # the decode rows' shared run, packed, after the state
                # operands (None where the model has none); no run: the
                # zeros already on the device
                run, pages, members = shared_run(
                    page_tables[:B], seq_lens[:B], q_lens[:B], ps)
                shared_rows = int(members.sum())
                shared_keys = self._kv_full * pages * ps * shared_rows
                packed = self._no_run(B)
                if shared_rows:
                    self._shared_run_count += 1
                    self._shared_rows += shared_rows
                    self._shared_keys += shared_keys
                    packed = np.concatenate([[pages * ps], run, members]) \
                        .astype(np.int32)
                tail = tail[:3] + (slots, restore, packed)
        if mixed and (B, 1, sample, False) not in self._step_fns:
            self._warm_decode(B, sample)
        fn = self._step_fn(key)
        span_args = {'shape': shape, 'batch': len(rows),
                     'in_flight': int(flight is not None)}
        if fused:
            span_args['k'] = T
        if mixed:
            span_args['prefill_rows'] = len(chunks)
            self._mixed_dispatches += 1
            self._mixed_slots += P * T
        self._dispatches += 1
        t0 = time.perf_counter()
        with RecordEvent('serve::compiled_step', event_type='serve',
                         **span_args):
            (ids, self._prev_ids, self.pool.kv, self.pool.state,
             self._moe_dev) = fn(
                self._params, self.pool.kv, self.pool.state,
                self._moe_dev, self._prev_ids, *map(jnp.asarray, head),
                self._key, *(None if x is None else jnp.asarray(x)
                             for x in tail))
        t1 = time.perf_counter()
        self._it_compute += t1 - t0
        # one program's time, shared between the phases by the query
        # tokens each brought to it
        queries = int(q_lens.sum()) or 1
        prefill = int(q_lens[B:].sum()) / queries
        self._it_prefill_s += (t1 - t0) * prefill
        self._it_decode_s += (t1 - t0) * (1.0 - prefill)
        return (ids, B + P, 1 + P, bool(rows), shared_rows, shared_keys,
                snapshots, t0, P * T)

    def _no_run(self, B):
        """The packed shared-run operand of a dispatch without a run
        (all zero), put on the device once a batch size."""
        zeros = self._no_runs.get(B)
        if zeros is None:
            zeros = self._no_runs[B] = self._jnp.asarray(
                np.zeros((1 + self.max_pages_per_seq + B,), np.int32))
        return zeros

    def _fetch(self, queued, behind):
        """The one host sync of a dispatch: the ids `_enqueue` left on
        the device come to the host, the experts' counters behind them
        are split off, and the fetch's clocks are fed. `behind`: the
        next step is queued behind this one, so the wait is the
        device's work and not its idling. -> ids."""
        ids, n, groups, decode = queued[:4]
        # the device was done before the host asked: the wait below is
        # none, and what ends at its return is the host's turn
        late = ids.is_ready()
        t1 = time.perf_counter()
        with RecordEvent('serve::sample_fetch', event_type='serve'):
            ids = _host_fetch(ids)      # the sampled-token fetch
        if self._moe_dev is not None:
            ids = self._take_moe(ids, n, groups, decode)
        t2 = time.perf_counter()
        self._it_fetch += t2 - t1
        if not behind:
            self._it_gating += t2 - t1
        # the one clock of the `serve::device_step` records: where the
        # record that holds this dispatch ends, if no later fetch of
        # its step moves it, and where the next one begins
        self._fetched_at, self._fetched_late = t2, late
        return ids

    def _ran(self, queued, rows, riding):
        """Count one landed dispatch into the `serve::device_step`
        record being built — beside what earlier landings left there
        that fetched nothing (inner chunks alone), which the device
        ran, or still runs, since the last fetch. -> the record."""
        *_, shared_rows, shared_keys, snapshots, launched, slots = queued
        work = self._device_step
        work.snapshots += snapshots
        work.shared_rows += shared_rows
        work.shared_keys += shared_keys
        if work.launched is None:
            work.launched = launched
        work.dispatches += 1
        work.decode_rows += len(rows)
        work.chunks += len(riding)
        work.chunk_tokens += sum(n for _, _, n in riding)
        work.chunk_slots += slots
        return work

    def _close_device_step(self, step, shape, behind, drain):
        """One `serve::device_step` record, made where a step lands:
        from the later of its first dispatch's launch and the end of
        the record before it to the return of its last fetch (`_fetch`
        read both ends: no clock is read here), and a new record begun. With a step
        queued behind it (`behind`) the device went from the record
        before straight into this one and on into the next, so the
        interval is the device's own time on these dispatches. It is
        NOT where nothing was queued behind (a drain: the next record
        begins at its launch, and the host's turn lies between); where
        the launch came after the last fetch (the first step after
        idle: the interval begins with the launch's own latency); and
        where the host came for the ids after the device had them
        (`late` 1: the host's turn outlasted a short step — the record
        ends with the host's arrival, the next one begins as much too
        late, and only the sum of the two is the device's; a reader of
        durations takes the records between two fetches that waited).
        Dispatches that fetch nothing — a step's trailing ones, or a
        whole step of inner chunks — are counted (`steps`,
        `dispatches`, `chunks`, ...) in the record the NEXT fetch
        closes, which is where the device ran them. `step` is the
        ordinal of the `serve::step` that launched the step whose fetch
        closed the record. `_decode_time` is the sum of the records
        that carried decode rows. Not mirrored into a device trace (an
        annotation cannot be backdated): `tools/trace_cell.py` holds it
        to the device's own `XLA Modules` line."""
        work, end = self._device_step, self._fetched_at
        began = max(work.launched, work.since)
        self._device_step = _DeviceStep(end)
        if work.decode_rows:
            self._decode_time += end - began
        args = {'drain': drain} if drain else {}
        if self._page_state:
            args['snapshots'] = work.snapshots
        if self._shared_runs:
            # the decode rows that read a shared run, and the keys it
            # served them (the unit of attn_kv_tokens_shared_total)
            args['shared_rows'] = work.shared_rows
            args['shared_keys'] = work.shared_keys
        record_span('serve::device_step', int(began * 1e9), int(end * 1e9),
                    event_type='serve', step=step, steps=work.steps,
                    dispatches=work.dispatches, shape=shape,
                    decode_rows=work.decode_rows, chunks=work.chunks,
                    chunk_tokens=work.chunk_tokens,
                    chunk_slots=work.chunk_slots, emitted=work.emitted,
                    behind=int(behind), late=int(self._fetched_late),
                    **args)

    def _warm_decode(self, B, sample):
        """A server that prefills will decode: compile the [B, 1] step
        beside the mixed program's own compile, by one call with every
        row idle (q_len 0: nothing is written, read, fetched or
        counted) — else the first step with no prompt to prefill stalls
        every running request for the seconds its compile takes."""
        def zeros(*shape, dtype=np.int32):
            return self._jnp.asarray(np.zeros(shape, dtype))
        # the state operands and the shared run, where the program has
        # them (None before one it has)
        spare = [
            self._jnp.asarray(np.full((B,), B, np.int32))
            if self._stateful else None,
            zeros(B, dtype=bool) if self._page_state else None,
            self._no_run(B) if self._shared_runs else None]
        while spare and spare[-1] is None:
            spare.pop()
        _, self._prev_ids, self.pool.kv, self.pool.state, _ = \
            self._step_fn((B, 1, sample, False))(
                self._params, self.pool.kv, self.pool.state,
                self._moe_dev, self._prev_ids, zeros(B),
                zeros(B, self.max_pages_per_seq),
                self._jnp.asarray(np.ones((B,), np.int32)), zeros(B),
                self._jnp.asarray(np.full((B,), -1, np.int32)),
                self._key, zeros(B), zeros(B, dtype=np.float32), zeros(B),
                *spare)

    def _fused_decode_window(self, K):
        """Up to K decode iterations in ONE dispatch + ONE host fetch.
        The caller holds scheduler/ladder quiescence; this method owns
        the page budget: every row's full window is reserved up front
        (pool.try_reserve — all-or-nothing per row) and the unused
        tail handed back with the spec-style trim after the fetch.
        Returns (rows, tokens emitted), or None when a reservation
        fails and the caller should fall back to the [B, 1] step."""
        rows = []
        for i, req in enumerate(self.scheduler.slots):
            if req is None or req.state != RequestState.RUNNING:
                continue
            w = min(K, req.max_new_tokens - len(req.generated))
            if not self.pool.try_reserve(req.id, req.context_len + w):
                # roll the earlier rows' fresh reservations back so the
                # serial fallback sees the pool it would have seen
                for _i, r, _q, _c in rows:
                    self.pool.trim(r.id, r.context_len)
                return None
            rows.append((i, req, [_last_token(req)], req.context_len))
        if not rows:
            return 0, 0
        return len(rows), self._dispatch(
            'fused', rows, self.config.max_batch_size, K,
            self._accept_fused, K)


    def _keys_read(self, context, queries):
        """The keys one row's queries may read, summed over the
        ATTENDING layers: a full layer's the row's whole context, a
        window layer's the window of its first query through its last
        (what the masks let the row read: the least the paged kernel
        must copy for it)."""
        total = self._kv_full * context
        for w, layers in self._windows.items():
            total += layers * min(context, w + queries - 1)
        return total

    def _pairs(self, context, queries):
        """The (query, key) pairs of one row, summed over the ATTENDING
        layers: query t of `queries` sits at position context - queries
        + t and may read the keys up to its own — in a window layer no
        more than the window."""
        first = context - queries + 1       # keys the first query reads

        def upto(cap):
            """sum over the queries of min(keys it may read, cap)."""
            ramp = max(0, min(queries, cap - first + 1))    # below cap
            return ramp * first + ramp * (ramp - 1) // 2 \
                + (queries - ramp) * cap
        total = self._kv_full * upto(context)
        for w, layers in self._windows.items():
            total += layers * upto(w)
        return total

    def _count_pairs(self, context, queries, slots):
        """One row's (query, key) pairs: those its masks allow, and
        those the kernel multiplies for them — a latent row's `slots`
        query slots run in tiles of whole tokens, and a live tile
        multiplies all its tokens by the keys up to its last live
        query's (paged_attention.latent_pairs_dispatched); any other
        row's are counted as the real ones."""
        real = self._pairs(context, queries)
        self._attn_qk_pairs += real
        self._attn_qk_dispatched += real if self._latent_pairs is None \
            else self._kv_full * self._latent_pairs(context, queries, slots)

    def _count_kv_read(self, context, queries=1, slots=1):
        """One decode row's KV reads this iteration, in tokens a PLANE
        (the pool's bytes per token count the planes; the readers of a
        shared plane each read it): what the attending layers read (a
        window layer no more than its window) over the planes, so
        tokens x kv_bytes_per_token stays the bytes; and, for the
        window layers alone, what they read against what they would
        without the bound."""
        total = self._keys_read(context, queries)
        self._attn_kv_tokens += total
        self._count_pairs(context, queries, slots)
        for w, layers in self._windows.items():
            self._it_kv_window[0] += layers * min(context, w)
            self._it_kv_window[1] += layers * context
        self._it_kv_read_tokens += total // self._kv_planes

    def _take_moe(self, packed, n, groups, decode):
        """Split one fetch into its `n` sampled ids and what the
        experts counted behind them (protocol.py: the rows per expert
        of this call by counted group — group 0 the decode rows, then
        one per prompt chunk, `groups` in all — then experts touched,
        rows and calls, only ever growing and wrapping — differences
        are taken), and account them: `ptpu_moe_*` counters, and for a
        call with decode rows their load, the most rows an expert took
        from them over the mean, averaged over the expert layers. The
        rows stay in `_moe_rows` for the chunks' accept. -> ids."""
        layers = self._moe_dev.shape[0]
        grown = packed[-3 * layers:].reshape(layers, 3).astype(np.int64)
        self._moe_rows = packed[n:-3 * layers].reshape(layers, groups, -1)
        delta = ((grown - self._moe_seen) % (1 << 32)).sum(axis=0)
        self._moe_seen = grown
        for (key, name), d in zip(_MOE_COUNTERS, delta):
            self._moe[key] += int(d)
            _monitor.counter(
                name,
                help='sparse-expert layers on the served path: experts '
                     'a call touched, (token, expert) rows routed, '
                     'expert-layer calls').inc(int(d))
        if decode:
            rows = self._moe_rows[:, 0].astype(np.float64)
            mean = rows.mean(axis=1)
            if (mean > 0).all():
                self._it_moe_load = float((rows.max(axis=1) / mean).mean())
        return packed[:n]

    def _accepted(self, accept, *args, **span_args):
        """Run one of the host accept loops under its `serve::accept`
        span (to the end of the decode or prefill body); returns the
        tokens it emitted."""
        with RecordEvent('serve::accept', event_type='serve',
                         **span_args) as ev:
            done_before = self._completed
            emitted = accept(*args)
            ev.args = {**span_args, 'emitted': emitted,
                       'retired': self._completed - done_before}
        return emitted

    def _accept_fused(self, nxt, rows, K):
        """Host accept of one fused window's [B, K] fetch. It replays
        the serial append-then-check loop per row, so eos / max_new
        cuts truncate exactly where K serial iterations would have
        stopped (the device done-mask already idled the row past that
        point). Returns the tokens emitted."""
        B = self.config.max_batch_size
        emitted_total = 0
        per_iter_rows = [0] * K
        accepted = {}
        for i, req, _query, _context in rows:
            a = 0
            for j in range(K):
                if req.done:
                    break
                req.generated.append(int(nxt[i, j]))
                emitted_total += 1
                per_iter_rows[j] += 1
                a += 1
            accepted[i] = a
        iters_run = max(accepted.values())
        util = self.pool.utilization()
        for j in range(iters_run):
            self._occupancy_sum += per_iter_rows[j] / B
            self._util_sum += util
        self._decode_steps += iters_run
        self._decode_tokens += emitted_total
        self._fused_windows += 1
        self._fused_iterations += iters_run
        self._fused_tokens += emitted_total
        self.ledger.account_fused_window(K, iters_run, emitted_total)
        for i, req, _query, _context in rows:
            a = accepted[i]
            # every emitted token reached its request: delivered work,
            # nothing rejected (no draft columns in a fused window) —
            # the ledger's delivered+wasted == emitted identity holds
            # exactly as K serial account_decode(1, 0) calls would
            self.ledger.account_decode(a, 0, tenant_id=req.tenant_id)
            prev_high = getattr(req, '_computed_high', 0)
            req._computed_high = max(prev_high, req.context_len - 1)
            # hand back the reserved-but-unused window tail (early eos
            # or budget cut) — the speculative-decode trim discipline
            self.pool.trim(req.id, req.context_len)
            self.pool.register_prefix(req.id, req.tokens,
                                      req.context_len - 1,
                                      owner=req.tenant_id)
            self._trace(req, 'fused_decode', k=K, accepted=a,
                        tokens_generated=len(req.generated),
                        seq_len=req.context_len,
                        pages=len(self.pool.page_table(req.id)))
            if req.done:
                self._retire(req)
        self._fused_last = {'k': K, 'iters': iters_run,
                            'rows': per_iter_rows[:iters_run]}
        return emitted_total

    def _page_row(self, req):
        row = self.pool.page_table(req.id)
        return row + [0] * (self.max_pages_per_seq - len(row))

    def _reserve_chunk(self, req, earlier):
        """A prefilling request's next prompt chunk, reserved: the
        prefix match on its first, then page growth for its tokens.
        `earlier` are the chunks already reserved in this step. ->
        (request, first position, tokens), or None when the request
        rides nothing this step."""
        C = self._effective_prefill_chunk()
        if req.state != RequestState.PREFILL:
            return None     # preempted by an earlier request in this
                            # same step() sweep: it re-queued slotless,
                            # allocating pages to it now would bleed the
                            # pool (and preempt live work) for a request
                            # that isn't scheduled
        toks = req.tokens
        _, _, start = self._known(req)
        if start == 0 and self.pool.prefix_cache:
            # a sibling's chunk reserved earlier in this step — or
            # riding the step in flight — is about to compute the very
            # block this prompt needs next (the same tokens from
            # position 0 on): nothing of it is indexed before its ids
            # are accepted — wait for it, and map its pages then
            # instead of computing them again beside it
            if self._flight is not None:
                earlier = [*self._flight.prefills.values(), *earlier]
            ps = self.pool.page_size
            have = self.pool.peek_prefix(toks, limit=len(toks) - 1)[0] \
                if earlier else 0
            if earlier and have + ps < len(toks) and any(
                    start <= have and have + ps <= start + n
                    and other.tokens[have:have + ps] == toks[have:have + ps]
                    and other.tokens[:have] == toks[:have]
                    for other, start, n in earlier):
                return None
            if self._host_tier is not None and self.pool.peek_prefix(
                    toks, limit=len(toks) - 1)[3]:
                self._drain('tier')     # the step waits on a host fetch
            # first chunk of a fresh admit (or a resume): map the
            # longest indexed prefix — full pages only, capped one
            # short of the context so the step still computes the
            # logits the first sampled token needs
            cached = self.pool.match_and_map(req.id, toks,
                                             limit=len(toks) - 1)
            # what a hit is a share of: the tokens of every prompt that
            # was looked up (a preempted request's resume counts again,
            # as its hit does)
            self._prompt_tokens += len(toks)
            req.cached_tokens = cached
            if cached:
                req.prefilled = start = cached
                table = self.pool.page_table(req.id)
                # a model that keeps its state a page resumes from the
                # snapshot of the hit's last page (the chunk that starts
                # at `cached` is flagged `restore` in _enqueue)
                self._trace(req, 'prefix_hit', cached_tokens=cached,
                            pages=len(table), **(
                                {'restored_page': table[
                                    cached // self.pool.page_size - 1]}
                                if self._page_state else {}))
                # host-tier resurrection rode the hit (ISSUE 20): the
                # pages came back by prefetch, not re-prefill — the
                # trace event is what reconstruct() prices as
                # resurrected (transfer-cost) tokens
                rz = (self.pool.pop_resurrect_stats()
                      if self._host_tier is not None else None)
                if rz:
                    self._trace(req, 'resurrect', pages=rz['pages'],
                                tokens=rz['tokens'])
        n = min(C, len(toks) - start)
        if not self._ensure_or_preempt(req, start + n):
            return None     # yielded to higher-priority pool pressure:
                            # re-queued, resumes when pressure clears
        return req, start, n

    def _accept_chunks(self, nxt, B, riding):
        """Host accept of the prompt chunks that rode one mixed
        dispatch, `riding` = [(request, first position, tokens)] in the
        rows of its prefill group; `nxt` the dispatch's fetch (the
        chunks' ids behind the B decode rows'), or None when nothing
        was due. Returns the tokens emitted: the first token of every
        request whose prompt a chunk completed."""
        emitted = 0
        for row, (req, start, n) in enumerate(riding):
            toks = req.tokens
            last = start + n == len(toks)
            due = _samples(req, start, n)
            req.prefilled = start + n
            self._prefill_tokens += n
            self._prefill_chunks += 1
            req.prefill_chunks += 1
            # goodput: positions below the request's computed high-
            # water mark were forward-passed before (then destroyed by
            # a preemption release) — this chunk re-derives them,
            # priced as preempt_recompute waste. Prefix-cache
            # resurrection advanced `start` past the cached span, so
            # resurrected pages never bill. First-time positions are
            # delivered prompt work.
            prev_high = getattr(req, '_computed_high', 0)
            recompute = max(0, min(prev_high, start + n) - start)
            req._computed_high = max(prev_high, start + n)
            self.ledger.account_prefill(n - recompute, recompute,
                                        tenant_id=req.tenant_id)
            # every prefilled token's K/V is resident: index the newly
            # completed full pages so siblings (and our own resume)
            # share
            self.pool.register_prefix(req.id, toks, req.prefilled,
                                      owner=req.tenant_id)
            extra = {'recompute_tokens': recompute} if recompute else {}
            if due:
                # this chunk completes (re-)prefill and sampled a token
                # off its final column — marked so reconstruct() can
                # tell prefill-sampled tokens (initial AND every
                # resume) from decode-step tokens when pricing
                # delivered work (v4)
                extra['sampled'] = 1
            self._trace(req, 'prefill_chunk', tokens=n,
                        prefilled=start + n,
                        pages=len(self.pool.page_table(req.id)), **extra)
            if due:
                if self._moe_rows is not None \
                        and self.moe_rows_listener is not None:
                    # what this chunk alone routed: its own group
                    self.moe_rows_listener(
                        req, start, n, self._moe_rows[:, 1 + row].copy())
                emitted += self._accept_first(req, int(nxt[B + row]))
            elif last:
                self._retire(req)   # prefill-only request (scoring):
                                    # the budget says emit nothing
        return emitted

    def _accept_first(self, req, tok):
        """Host accept of the token a request's last prefill chunk
        sampled: its first token (or, after a preemption, its next)."""
        req.generated.append(tok)
        if req.first_token_time is None:
            req.first_token_time = self._clock()
            if req.admit_ns is not None:
                record_span('serve::request.prefill', req.admit_ns,
                            time.perf_counter_ns(), event_type='serve',
                            req=req.id, prompt_tokens=len(req.prompt),
                            chunks=req.prefill_chunks,
                            cached_tokens=req.cached_tokens)
            ttft = req.first_token_time - req.submit_time
            self._ttfts_s.append(ttft)
            self._new_ttfts_s.append(ttft)
            self._trace(req, 'first_token',
                        t=req.first_token_time, tokens_generated=1,
                        pages=len(self.pool.page_table(req.id)))
        if req.done:
            self._retire(req)
        else:
            req.state = RequestState.RUNNING
        return 1

    def _known(self, req):
        """(state, context length, prompt tokens prefilled) of a
        request once the step in flight is accepted, on what that step
        is KNOWN to do (`_Flight`) — the host's own with nothing in
        flight. Whether a token is the EOS cannot be known: a row that
        may meet one is taken to go on, and `_accept_decode` drops what
        it computed past it."""
        state, made, filled = req.state, len(req.generated), req.prefilled
        flight = self._flight
        if flight is not None:
            chunk = flight.prefills.get(req.id)
            if chunk is not None:
                filled = chunk[1] + chunk[2]
                if filled == len(req.tokens):
                    state = RequestState.RUNNING
            made += req.id in flight.emits
            if state == RequestState.RUNNING \
                    and made >= req.max_new_tokens:
                state = RequestState.FINISHED
        return state, len(req.prompt) + made, filled

    def _slots_in(self, state):
        """[(slot, request, context length)] of the requests that will
        be in `state` once the step in flight is accepted (`_known`)."""
        out = []
        for i, req in enumerate(self.scheduler.slots):
            if req is not None:
                known, context, _ = self._known(req)
                if known == state:
                    out.append((i, req, context))
        return out

    def _holds_back(self):
        """Why the next step cannot be planned before the ids in flight
        arrive, read off the engine's own state: a fused window is
        configured ('fused'), or a greedy row will decode under
        speculation — the n-gram proposal reads its token ('verify').
        None: it can."""
        if self._effective_fused_k() > 1:
            return 'fused'
        if self._effective_spec_k() > 0 and any(
                req.top_k <= 0
                for _, req, _ in self._slots_in(RequestState.RUNNING)):
            return 'verify'
        return None

    def _launch(self):
        """Plan one step and queue its programs: the next prompt chunk
        of every prefilling request, reserved, then the dispatches
        (`_advance`). -> the `_Flight` to land, or None: nothing to
        run, or a verify step or fused window that landed at once."""
        chunks = []
        for _, req, _ in self._slots_in(RequestState.PREFILL):
            with RecordEvent('serve::prefill_chunk', event_type='serve',
                             req=req.id):
                chunk = self._reserve_chunk(req, chunks)
            if chunk is not None:
                chunks.append(chunk)
        if not chunks and not self._slots_in(RequestState.RUNNING):
            return None
        called = self._dispatches
        with RecordEvent('serve::dispatch', event_type='serve'):
            flight = self._advance(chunks)
        self._steps += self._dispatches > called
        return flight

    def _land(self, behind, drain=None):
        """Bring the step in flight home: every dispatch's ids fetched
        (inner chunks alone sample nothing anyone reads: no fetch) and
        accepted, in the order they were queued — the device runs the
        next dispatch, and with `behind` the next STEP, while the host
        accepts this one's tokens. The step's last fetch closes its
        `serve::device_step` record (`_close_device_step`); `drain` is
        why nothing is queued behind it."""
        flight, self._flight = self._flight, None
        B = self.config.max_batch_size
        due = [bool(rows) or any(_samples(*c) for c in riding)
               for rows, riding, _ in flight.dispatches]
        last = max((i for i, d in enumerate(due) if d), default=-1)
        for i, (rows, riding, queued) in enumerate(flight.dispatches):
            work = self._ran(queued, rows, riding)
            if i == 0:
                work.steps += flight.steps
            nxt = self._fetch(queued, behind) if due[i] else None
            if rows:
                # POST-preemption counts: the rows that rode
                tokens = self._accepted(self._accept_decode, nxt, rows,
                                        False, 1)
                self._decode_tokens += tokens
                self._it_rows += len(rows)
                self._it_tokens += tokens
                work.emitted += tokens
            if riding:
                work.emitted += self._accepted(
                    self._accept_chunks, nxt, B, riding, chunks=len(riding))
                self._it_chunk_tokens += sum(n for _, _, n in riding)
            if i == last:
                # (what follows fetches nothing: the next record's)
                self._close_device_step(
                    flight.step, 'mixed' if work.chunks else 'decode',
                    behind, drain)

    def _drain(self, reason='idle'):
        """Empty the pipe: fetch and accept the step in flight with
        nothing queued behind it, for `reason` (DRAIN_REASONS) — what
        cannot be planned before its ids, or must not touch what it
        still writes, waits here; after it the engine stands where the
        serial order would. -> whether a step was in flight."""
        if self._flight is None:
            return False
        self._drains[reason] += 1
        self._land(behind=False, drain=reason)
        return True

    def _advance(self, chunks):
        """The step's dispatches, queued. The running set's decode rows
        and the reserved prompt `chunks` ([(request, first position,
        tokens)]) ride ONE mixed program; more chunks than its prefill
        group has rows ride further dispatches of the same program with
        an idle decode group; no chunk at all is the [B, 1] step. Every
        dispatch of the step is queued here and fetched in `_land` (no
        chunk depends on another's result: the pages of each are
        reserved first), a step later when the next can be launched
        before; a request whose last chunk rides here decodes from the
        NEXT step on, its first token handed on by its slot whichever
        dispatch made it. With spec_k > 0, greedy requests whose
        history yields an n-gram proposal carry up to k draft tokens
        into the [B, spec_k+1] verify step: every draft position's
        greedy argmax comes back in the one fetch, the longest agreeing
        draft prefix is accepted plus the bonus token, and pages grown
        for rejected drafts are handed back (their slots are
        overwritten in place by later writes — the ragged kernel's
        seq_len mask never exposes a stale slot before the step that
        rewrites it); the verify step and the fused window are fetched
        and accepted at once, with nothing in flight (`_holds_back`),
        and the chunks ride the mixed program beside an idle decode
        group. Everything is planned on `_known`. Returns the `_Flight`
        queued, or None."""
        running = self._slots_in(RequestState.RUNNING)
        K = self._effective_spec_k()
        if self.config.spec_k > 0 and K == 0:
            # degrade stage >= 1 shed the configured draft capacity this
            # step: price the foregone draft columns (min(spec_k,
            # remaining budget) per greedy running row) as shed
            # capacity — never computed, so outside the emitted-token
            # identity
            for _, req, context in running:
                budget = req.max_new_tokens - 1 - (
                    context - len(req.prompt))
                if req.top_k <= 0 and budget > 0:
                    self.ledger.account_spec_shed(
                        min(self.config.spec_k, budget),
                        tenant_id=req.tenant_id)
        proposals = {}
        if K > 0 and self._flight is None:
            for _, req, _ in running:
                if req.top_k > 0:
                    continue        # spec verify is greedy-only
                budget = req.max_new_tokens - len(req.generated) - 1
                drafts = _ngram_propose(req.tokens,
                                        self.config.spec_ngram,
                                        min(K, budget))
                if drafts:
                    proposals[req.id] = drafts
        B = self.config.max_batch_size
        # fused window (ISSUE 19): when no verify columns ride this
        # dispatch (spec takes precedence — its drafts already amortize
        # the host fetch) and the scheduler is quiescent for a full
        # window (so no request is prefilling: there are no chunks),
        # scan k decode iterations on device and fetch once. A failed
        # page reservation falls through to the serial step below
        # rather than preempting — the window is an optimization, never
        # a capacity decision.
        FK = self._effective_fused_k()
        if FK > 1 and not proposals and self._fused_ok(FK):
            res = self._fused_decode_window(FK)
            if res is not None:
                self._it_rows, self._it_tokens = res
                return None
        # capacity first (may preempt, or yield the request itself: a
        # row an earlier row's growth preempted grows nothing); then
        # snapshot the running set again — a yielded request left its
        # slot, so the batch build below skips it naturally
        for _, req, _ in running:
            state, context, _ = self._known(req)
            if state == RequestState.RUNNING \
                    and not self._ensure_or_preempt(
                        req, context + len(proposals.get(req.id, ()))):
                proposals.pop(req.id, None)
        rows = []
        for i, req, context in self._slots_in(RequestState.RUNNING):
            drafts = proposals.get(req.id, [])
            rows.append((i, req, [_last_token(req)] + drafts,
                         context + len(drafts)))
        # a reservation above (a later chunk's, a decode row's) may have
        # preempted a prefilling request: its pages went with it, and
        # it rides nothing
        chunks = [c for c in chunks if c[0].state == RequestState.PREFILL]
        if not rows and not chunks:
            return None
        flight = _Flight(self._step_ordinal)
        self._pipelined += self._flight is not None
        if rows:
            self._decode_steps += 1
            self._occupancy_sum += len(rows) / B
            self._util_sum += self.pool.utilization()
            # without a surviving proposal the verify columns would all
            # be padding: the [B, 1] rows serve
            if any(len(query) > 1 for _, _, query, _ in rows):
                self._it_rows = len(rows)
                self._it_tokens = self._dispatch(
                    'verify', rows, B, K + 1, self._accept_decode, True,
                    K + 1)
                self._decode_tokens += self._it_tokens
                flight.steps = 0    # counted in the verify step's record
                rows = []
            elif not chunks:
                flight.dispatches.append(
                    (rows, [], self._enqueue('decode', rows, B, 1)))
        C, P = self._effective_prefill_chunk(), self._prefill_rows
        for first in range(0, len(chunks), P):
            riding = chunks[first:first + P]
            flight.dispatches.append((rows, riding, self._enqueue(
                'mixed', rows, B, C,
                chunks=[(row, req, req.tokens[start:start + n], start + n)
                        for row, (req, start, n) in enumerate(riding)])))
            rows = []
        if not flight.dispatches:
            return None
        for rows, riding, _ in flight.dispatches:
            flight.emits.update(req.id for _, req, _, _ in rows)
            for chunk in riding:
                flight.prefills[chunk[0].id] = chunk
                if _samples(*chunk):
                    flight.emits.add(chunk[0].id)
        return flight

    def _accept_decode(self, nxt, rows, verify, T):
        """Host accept of one [B, T] decode/verify fetch: token append,
        done/EOS checks, draft rollback, prefix registration, retire.
        Returns the tokens emitted."""
        emitted_total = 0
        for i, req, query, _context in rows:
            if self.scheduler.slots[i] is not req:
                # the row was launched before the ids arrived that took
                # its request from the slot — an EOS (or a hand-over to
                # another engine): what it computed is dropped, never
                # delivered; the pages it grew went with the retire,
                # and what it wrote lies past every indexed page, where
                # a later owner's writes come before any read
                self._overrun += req.state == RequestState.FINISHED
                continue
            drafts = query[1:]
            spec_m = None
            if verify:
                if req.top_k > 0:
                    appended = [int(nxt[i, T])]     # sampled column
                else:
                    g = nxt[i]
                    m = 0
                    while m < len(drafts) and int(g[m]) == drafts[m]:
                        m += 1
                    appended = drafts[:m] + [int(g[m])]
                    if drafts:
                        self._spec_proposed += len(drafts)
                        self._spec_accepted += m
                        self._spec_steps += 1
                        spec_m = m
            else:
                appended = [int(nxt[i])]
            # emit in order, honoring eos mid-burst exactly like the
            # one-token path would have (nothing after eos escapes)
            delivered_row = 0
            for tok in appended:
                req.generated.append(tok)
                emitted_total += 1
                delivered_row += 1
                if req.done:
                    break
            # goodput: this row computed 1 + len(drafts) query columns;
            # columns that never reached the request (rejected drafts,
            # post-eos overdraft) are spec_rejected waste
            self.ledger.account_decode(
                delivered_row, 1 + len(drafts) - delivered_row,
                tenant_id=req.tenant_id)
            if spec_m is not None:
                # emitted after the append sweep so `discarded` prices
                # the accepted-but-dropped tail (eos / budget cut the
                # burst short) — trace v4 waste matches the ledger's
                # spec_rejected charge per request exactly
                self._trace(req, 'spec_verify', proposed=len(drafts),
                            accepted=spec_m,
                            discarded=len(appended) - delivered_row)
            prev_high = getattr(req, '_computed_high', 0)
            req._computed_high = max(prev_high, req.context_len - 1)
            if drafts:
                # speculative rollback: hand back pages grown for
                # rejected drafts beyond the accepted context
                self.pool.trim(req.id, req.context_len)
            # K/V is resident for everything but the newest token
            self.pool.register_prefix(req.id, req.tokens,
                                      req.context_len - 1,
                                      owner=req.tenant_id)
            self._trace(req, 'decode',
                        tokens_generated=len(req.generated),
                        seq_len=req.context_len,
                        pages=len(self.pool.page_table(req.id)))
            if req.done:
                self._retire(req)
        return emitted_total

    def _retire(self, req):
        self.pool.release(req.id)
        self.scheduler.retire(req)
        self._completed += 1
        if req.tenant_id is not None:
            self._tstat(req.tenant_id)['completed'] += 1
        self._observe_slo(req)
        self._trace(req, 'retire', t=req.finish_time,
                    tokens_generated=len(req.generated),
                    preemptions=req.preemptions)

    def abort(self, req, reason='aborted'):
        """Drop a request wherever it sits: pages released, slot/queue
        entry cleared, journal closed with an `abort` event. The
        watchdog's deadline_action='abort' path and operator kill.
        No-op (returns False) on an already-retired/aborted request —
        double accounting would poison the SLO histograms."""
        if self._flight is not None and self._flight.carries(req):
            self._drain('abort')    # it may finish in the step in flight
        if not self.scheduler.abort(req):
            return False
        self.pool.release(req.id)
        self._aborted += 1
        if req.tenant_id is not None:
            self._tstat(req.tenant_id)['aborted'] += 1
        self._observe_slo(req)
        self._trace(req, 'abort', t=req.finish_time, reason=reason,
                    tokens_generated=len(req.generated),
                    preemptions=req.preemptions)
        return True

    def _observe_slo(self, req):
        """Queue the per-request SLO samples (queue-wait, TPOT, e2e,
        preemption count) for the next histogram publish — host floats
        the scheduler already stamped, no device work. Requests with a
        tenant also queue tenant-labeled queue-wait/e2e samples, and a
        finish past the request's own deadline records a deadline_miss
        (counter + trace event) — the admission estimate was wrong or
        pressure grew after admit; either way the SLO view must say
        so."""
        slo = self._new_slo
        qw = e2e = None
        if req.submit_time is not None and req.admit_time is not None:
            qw = req.admit_time - req.submit_time
            slo['queue_wait_s'].append(qw)
        if (req.first_token_time is not None
                and req.finish_time is not None
                and len(req.generated) > 1):
            slo['tpot_s'].append(
                (req.finish_time - req.first_token_time)
                / (len(req.generated) - 1))
        if req.submit_time is not None and req.finish_time is not None:
            e2e = req.finish_time - req.submit_time
            slo['e2e_s'].append(e2e)
        slo['preemptions'].append(req.preemptions)
        if req.tenant_id is not None:
            ts = self._new_tenant_slo.setdefault(
                req.tenant_id, {'queue_wait_s': [], 'e2e_s': []})
            if qw is not None:
                ts['queue_wait_s'].append(qw)
            if e2e is not None:
                ts['e2e_s'].append(e2e)
        if (req.deadline_s is not None and e2e is not None
                and e2e > req.deadline_s):
            self._deadline_misses += 1
            if req.tenant_id is not None:
                self._tstat(req.tenant_id)['deadline_misses'] += 1
            self._trace(req, 'deadline_miss', t=req.finish_time,
                        e2e_s=e2e, deadline_s=req.deadline_s)

    # -- stalled-request watchdog --------------------------------------------
    def _check_stalled(self):
        """Requests older than config.request_deadline_s produce a
        structured serve_report artifact (trace + timeline tail + pool
        census) once, instead of silently sitting in the queue."""
        deadline = self.config.request_deadline_s
        if not deadline:
            return
        now = self._clock()
        stalled = [r for r in (list(self.scheduler.waiting)
                               + [s for s in self.scheduler.slots
                                  if s is not None])
                   if r.submit_time is not None
                   and now - r.submit_time > deadline
                   and r.id not in self._stall_reported]
        for req in stalled:
            self._stall_reported.add(req.id)
            self.last_serve_report = self._build_report(
                req, age_s=now - req.submit_time)
            self.last_serve_report['path'] = write_serve_report(
                self.last_serve_report, self.config.report_dir)
            if self.config.deadline_action == 'abort':
                self.abort(req, reason='deadline_exceeded')

    def _build_report(self, req, age_s):
        events = (self.tracer.events(req.id)
                  if self.tracer is not None else [])
        return build_serve_report(
            reason=f'request exceeded deadline '
                   f'({self.config.request_deadline_s}s)',
            request_summary={
                'req': req.id, 'state': req.state, 'age_s': age_s,
                'deadline_s': self.config.request_deadline_s,
                'prompt_tokens': len(req.prompt),
                'tokens_generated': len(req.generated),
                'preemptions': req.preemptions,
            },
            trace_events=events,
            timeline_tail=self.timeline.tail(32),
            pool_stats=self.pool.stats(),
            pool_census=self.pool.census(),
            engine_stats={
                'in_flight': len(self.scheduler.running()),
                'waiting': len(self.scheduler.waiting),
                'submitted': self._submitted,
                'completed': self._completed,
                'aborted': self._aborted,
            })

    # -- stats / metrics -----------------------------------------------------
    def stats(self):
        steps = max(self._decode_steps, 1)
        s = {
            'decode_tokens_per_sec':
                (self._decode_tokens / self._decode_time
                 if self._decode_time else 0.0),
            'ttft_ms_mean':
                (1000.0 * sum(self._ttfts_s) / len(self._ttfts_s)
                 if self._ttfts_s else None),
            'batch_occupancy': self._occupancy_sum / steps,
            'kv_page_utilization': self._util_sum / steps,
            'slots': self.config.max_batch_size,
            'in_flight': len(self.scheduler.running()),
            'waiting': len(self.scheduler.waiting),
            'pool': self.pool.stats(),
            'requests_submitted_total': self._submitted,
            'requests_completed_total': self._completed,
            'requests_aborted_total': self._aborted,
            'preemptions_total': self.scheduler.preemptions,
            'decode_steps_total': self._decode_steps,
            'decode_tokens_total': self._decode_tokens,
            'prefill_tokens_total': self._prefill_tokens,
            'prefill_chunks_total': self._prefill_chunks,
            # the mixed step: programs called a step that dispatched,
            # prompt chunks riding a mixed dispatch (of its P rows), and
            # the share of its prefill group's token slots left padding
            'dispatches_total': self._dispatches,
            'dispatches_per_step': self._dispatches / max(self._steps, 1),
            'prefill_rows_per_dispatch':
                self._prefill_chunks / max(self._mixed_dispatches, 1),
            'padded_prefill_token_share':
                (1.0 - self._prefill_tokens / self._mixed_slots
                 if self._mixed_slots else 0.0),
            # one step ahead: steps launched while another was in
            # flight (of the steps that dispatched: dispatches_total /
            # dispatches_per_step), fetches with nothing queued behind
            # them by what held the next step back, and rows dropped
            # because their request had met its EOS a step before
            'pipelined_steps_total': self._pipelined,
            'pipeline_drains_total': dict(self._drains),
            'overrun_tokens_total': self._overrun,
            # sparse-expert layers (zeros for a model without them)
            'moe_rows_total': self._moe['rows'],
            'moe_experts_touched_total': self._moe['experts_touched'],
            'moe_calls_total': self._moe['calls'],
            'moe_load_sum': self.ledger.moe_load_sum,
            'moe_load_steps': self.ledger.moe_load_steps,
            # attention over the pages: layers that attend, planes they
            # own, and the keys their masks let the dispatched rows
            # read (decode and chunk rows, summed over the layers)
            'kv_readers': self._kv_readers,
            'kv_planes': self._kv_planes,
            'attn_kv_tokens_read_total': self._attn_kv_tokens,
            # the chunk rows' part of it (a chunk's keys are read once
            # for all its queries: its call is bound by its products),
            # and the (query, key) pairs all rows' masks allow: a
            # decode row's keys once, a chunk row's under its causal
            # triangle
            'attn_kv_tokens_read_chunks_total': self._attn_kv_chunks,
            'attn_qk_pairs_total': self._attn_qk_pairs,
            # the pairs the kernel MULTIPLIES for them: a latent chunk
            # row's live query tiles whole, padding tokens included
            # (real / dispatched: the share of its products that are
            # not padding); equal to the real ones for any other model
            'attn_qk_pairs_dispatched_total': self._attn_qk_dispatched,
            # the decode rows' keys a shared run served (read ONCE for
            # its member rows by the paged kernel; a part of
            # attn_kv_tokens_read_total, same unit), the dispatches
            # that carried a run and their member rows
            'attn_kv_tokens_shared_total': self._shared_keys,
            'shared_runs_total': self._shared_run_count,
            'shared_run_rows_total': self._shared_rows,
            # one token's device bytes in one plane (a (k, v) pair, or
            # a latent plane's one padded row)
            'kv_plane_bytes_per_token':
                self.pool.bytes_per_token() // max(self._kv_planes, 1),
            # recurrent state (zeros for a model without it): its
            # bytes, the (row, layer) updates and the tokens they took —
            # under the scan (a model without page state) or the short
            # convolution (a model with it)
            'state_bytes': self.pool.state_bytes(),
            'ssm_rows_total': 0 if self._page_state else self._state_rows,
            'ssm_tokens_total':
                0 if self._page_state else self._state_tokens,
            'conv_rows_total': self._state_rows if self._page_state else 0,
            'conv_tokens_total':
                self._state_tokens if self._page_state else 0,
            # state kept a page (zeros for a model that keeps none):
            # snapshots written, rows restored from one, and the tokens
            # those rows resumed after
            'state_snapshots_written_total': self._snapshots,
            'state_restores_total': self._restores,
            'state_restore_tokens_total': self._restore_tokens,
            'weight_dtype': (str(self.config.weight_dtype)
                             if self.config.weight_dtype else None),
            'quantized_params': len(self._qparam_dtypes),
            # prefix cache (pool-owned counters) + speculative decode
            'prefix_cache': self.pool.prefix_cache,
            'prefix_hits_total': self.pool.prefix_hits,
            'prefix_misses_total': self.pool.prefix_misses,
            'prefix_hit_tokens_total': self.pool.prefix_hit_tokens,
            # the tokens of every prompt looked up in the prefix index
            # (0 with the cache off): what the hit tokens are a share of
            'prompt_tokens_total': self._prompt_tokens,
            'prefix_shared_pages': self.pool.shared_pages,
            'prefix_cached_pages': self.pool.cached_pages,
            'prefix_evictions_total': self.pool.prefix_evictions,
            'spec_k': self.config.spec_k,
            'spec_proposed_tokens_total': self._spec_proposed,
            'spec_accepted_tokens_total': self._spec_accepted,
            'spec_steps_total': self._spec_steps,
            'spec_acceptance_rate':
                (self._spec_accepted / self._spec_proposed
                 if self._spec_proposed else None),
            # fused multi-token decode (ISSUE 19)
            'fused_k': self.config.fused_k,
            'fused_windows_total': self._fused_windows,
            'fused_iterations_total': self._fused_iterations,
            'fused_tokens_total': self._fused_tokens,
            # multi-tenant SLO layer (ISSUE 15): always present so the
            # snapshot shape is stable — zeros/empty when untenanted
            'quota_deferrals_total': self._quota_deferrals,
            'preemptions_charged_total': self._preemptions_charged,
            'deadline_rejects_total': self._deadline_rejects,
            'deadline_misses_total': self._deadline_misses,
            'degrade_stage': self.degrade_stage(),
            'tenancy': self._tenancy_stats(),
        }
        return s

    def _tenancy_stats(self):
        """Per-tenant lifetime view for stats()/serve_snapshot() and
        health_dump tenants: policy (priority/quota/weight), live
        bucket level, and the accounting rows."""
        out = {
            'enabled': self._tenants is not None,
            'degrade_enabled': self._ladder is not None,
            'degrade_stage': self.degrade_stage(),
            'pressure': (round(self._ladder.pressure(), 4)
                         if self._ladder is not None else 0.0),
            'stage_transitions': (self._ladder.transitions
                                  if self._ladder is not None else 0),
            'tenants': {},
        }
        tids = set(self._tenant_stats)
        if self._tenants is not None:
            tids.update(self._tenants.tenants())
        for tid in sorted(tids):
            row = dict(self._tenant_stats.get(tid)
                       or self._blank_tstat())
            if self._tenants is not None:
                pol = self._tenants.policy(tid)
                if pol is not None:
                    row['priority'] = pol['priority']
                    row['quota_tokens_per_s'] = pol['quota_tokens_per_s']
                    row['weight'] = pol['weight']
                bucket = self._tenants.bucket(tid)
                if bucket is not None:
                    row['bucket_level'] = round(bucket.level, 3)
            out['tenants'][tid] = row
        return out

    def reset_stats(self):
        """Zero the rate/occupancy accounting AND the trace/timeline
        observatory (NOT the pool or queue) — who measures calls this
        after compile warmup so steady-state numbers aren't polluted by
        the first-dispatch compiles."""
        self._decode_time = 0.0
        self._decode_tokens = 0
        self._decode_steps = 0
        self._occupancy_sum = 0.0
        self._util_sum = 0.0
        self._prefill_tokens = 0
        self._prefill_chunks = 0
        self._steps = 0
        self._dispatches = 0
        self._mixed_dispatches = 0
        self._mixed_slots = 0
        self._pipelined = 0
        self._drains = dict.fromkeys(DRAIN_REASONS, 0)
        self._overrun = 0
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._spec_steps = 0
        self._fused_windows = 0
        self._fused_iterations = 0
        self._fused_tokens = 0
        self._ttfts_s = []
        self._new_ttfts_s = []
        for v in self._new_slo.values():
            v.clear()
        for d in self._new_tenant_slo.values():
            for v in d.values():
                v.clear()
        if self.tracer is not None:
            self.tracer.reset()
        self.timeline.reset()
        self.ledger.reset()
        self._gap.reset()

    def publish_metrics(self):
        s = self.stats()
        s['_new_ttfts_s'] = list(self._new_ttfts_s)
        self._new_ttfts_s.clear()
        s['_new_slo'] = {k: list(v) for k, v in self._new_slo.items()}
        for v in self._new_slo.values():
            v.clear()
        s['_new_tenant_slo'] = {t: {k: list(v) for k, v in d.items()}
                                for t, d in self._new_tenant_slo.items()}
        for d in self._new_tenant_slo.values():
            for v in d.values():
                v.clear()
        s['timeline'] = self.timeline.summary()
        self._last_publish = self._clock()
        self._last_publish_wall = _monitor._time_fn()
        _metrics.publish(s)
        self.ledger.publish()
        self._gap.publish()

    def request_table(self):
        """Per-request SLO reconstruction from the lifecycle journals
        (request_trace.reconstruct) — empty when tracing is off."""
        return self.tracer.request_table() if self.tracer else {}

    def export_trace(self, jsonl_path=None, chrome_path=None):
        """Export the request journals: JSON-lines (schema header +
        one event per line) and/or chrome-trace. The chrome export
        folds in any serve::* engine-phase spans sitting in the
        profiler's span buffer, so requests render as tracks next to
        the engine steps that served them (Perfetto-loadable)."""
        if self.tracer is None:
            raise RuntimeError("tracing is off — build the engine with "
                               "ServingConfig(trace=True)")
        out = {}
        if jsonl_path:
            out['jsonl'] = self.tracer.export_jsonl(jsonl_path)
        if chrome_path:
            from .. import profiler as _prof
            spans = [s for s in _prof.span_dicts()
                     if s['cat'] == 'serve']
            out['chrome'] = self.tracer.export_chrome_tracing(
                chrome_path, extra_spans=spans)
        return out

    def shutdown(self):
        """Drop the pool's device pages and the compiled steps, and
        unregister the gap monitor + serve ledger so a dead engine
        stops reporting (the PR-13 training-engine discipline —
        serve_ledger_snapshot() and the host-gap registry read live
        objects, not stale gauges)."""
        self._drain()
        if self._host_tier is not None:
            self._host_tier.shutdown()
        self.pool.drop_arrays()
        self._step_fns.clear()
        self._params = {}
        self._prev_ids = None
        unregister_monitor(self._gap)
        self.ledger.unregister()
        return {'released': True}


def _ngram_propose(tokens, ngram, k):
    """Prompt-lookup draft proposer (the model-free speculator): find
    the most recent earlier occurrence of the context's trailing
    n-gram and propose the up-to-k tokens that followed it. Backs off
    to shorter n-grams; returns [] when nothing matches — the request
    then just decodes one token this step. Pure host work on the token
    list the scheduler already holds."""
    L = len(tokens)
    if k <= 0 or L < 2:
        return []
    for n in range(min(int(ngram), L - 1), 0, -1):
        # rightmost candidate ends one short of the trailing gram, so
        # the continuation (which may overlap the suffix — that is how
        # repetition loops propose) is never empty. Compared in place:
        # this runs per greedy row per decode step, so no per-position
        # slice allocations on the miss path.
        first = tokens[L - n]
        for j in range(L - n - 1, -1, -1):
            if tokens[j] != first:
                continue
            if all(tokens[j + t] == tokens[L - n + t]
                   for t in range(1, n)):
                return [int(t) for t in tokens[j + n:j + n + k]]
    return []


def _samples(req, start, n):
    """Whether the chunk [start, start + n) of a request's prompt
    samples a token anyone reads: only the chunk that completes the
    prompt does, and for a scoring request (no budget) not even that."""
    return start + n == len(req.tokens) and req.max_new_tokens > 0


def _last_token(req):
    """The token a decode row feeds: the newest of its context, whose
    K/V the step writes."""
    return req.generated[-1] if req.generated else req.prompt[-1]


def shared_run(page_tables, seq_lens, q_lens, page_size):
    """The leading page run the paged kernel reads ONCE for several
    decode rows (ops/pallas/paged_attention.py `shared`), found in
    their page tables [B, P]: rows are grouped by their first page; in
    a group, a row reaches as many whole leading pages as it maps to
    the same pool pages as the group's first row and that lie strictly
    before its own last page (so it keeps the page holding its query's
    key); the run is the length, among the rows' reaches, that saves
    the most keys — its rows x its pages — over the rows that reach it,
    two rows at least. Vectorised: the rows sorted by first page, a
    [B, P] compare against each group's first row (its first mismatch
    ends a row's reach), then by reach within a group (the k-th longest
    is reached by k + 1 rows). -> (run page ids int32 [P], zeros past
    the run; its pages; member rows bool [B]), all zero where no run
    saves a key."""
    B, P = page_tables.shape
    run, members = np.zeros((P,), np.int32), np.zeros((B,), bool)
    room = np.where((q_lens > 0) & (seq_lens > 0),
                    (seq_lens - 1) // page_size, 0)
    rows = np.flatnonzero(room)     # a whole page before their last
    by_first = np.argsort(page_tables[rows, 0], kind='stable')
    first = page_tables[rows[by_first], 0]
    opens = np.ones(rows.size, bool)        # a group's first row, sorted
    opens[1:] = first[1:] != first[:-1]
    if opens.all():
        return run, 0, members
    group = np.empty(rows.size, np.intp)
    group[by_first] = np.cumsum(opens) - 1
    lead = by_first[opens]                  # each group's first row
    cols = page_tables[rows, :room.max()]
    same = cols == cols[lead[group]]
    reach = np.minimum(room[rows], np.where(
        same.all(axis=1), cols.shape[1], np.argmin(same, axis=1)))
    order = np.lexsort((-reach, group))
    by_group = group[order]
    k = np.arange(rows.size) - np.searchsorted(by_group, by_group)
    saved = np.where(k >= 1, reach[order] * (k + 1), 0)
    best = int(np.argmax(saved))
    if saved[best] == 0:
        return run, 0, members
    pages, g = int(reach[order][best]), by_group[best]
    run[:pages] = page_tables[rows[lead[g]], :pages]
    members[rows[(group == g) & (reach >= pages)]] = True
    return run, pages, members


def _ord_of(req):
    """The request's sampling ordinal for the per-position key fold.
    engine.submit assigns engine-local ordinals in submission order
    (and adopted requests carry their submitter's); requests injected
    past submit — scheduler-level tests driving engine internals —
    fall back to the global request id, still a stable per-request
    fold."""
    o = getattr(req, 'sample_ord', None)
    return int(o if o is not None else req.id)


def _device_sample(logits, key, ords, positions, temps, top_ks):
    """On-device next-token choice, [B, V] fp32 logits -> [B] int32.

    Matches GPTForCausalLM._sample_next semantics: top_k <= 0 means
    GREEDY argmax (temperature ignored); top_k > 0 samples from the
    temperature-scaled top-k renormalized distribution.

    The per-row key is fold_in(fold_in(key, ords[b]), positions[b]) —
    a pure function of (seed, request ordinal, absolute token
    position), never of dispatch count or batch composition. That
    invariance is what makes fused-k windows, serial decode, the spec
    verify column and preempt/resume re-prefill all sample IDENTICAL
    tokens (ISSUE 19); `positions` is the absolute index of the token
    being sampled (== seq_lens in every step shape)."""
    import jax
    import jax.numpy as jnp
    V = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temps[:, None], 1e-6)
    k = jnp.clip(top_ks, 1, V)
    srt = jnp.sort(scaled, axis=-1)             # ascending
    kth = jnp.take_along_axis(srt, (V - k)[:, None], axis=-1)
    masked = jnp.where(scaled < kth, -1e30, scaled)
    keys = jax.vmap(
        lambda o, p: jax.random.fold_in(jax.random.fold_in(key, o), p)
    )(ords, positions)
    sampled = jax.vmap(jax.random.categorical)(keys, masked).astype(
        jnp.int32)
    return jnp.where(top_ks > 0, sampled, greedy)
