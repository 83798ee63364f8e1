"""Block-paged KV-cache pool (vLLM-style paging, TPU-shaped).

One fixed device PLANE per owning decoder layer, shared by every
in-flight request: a pair of arrays (k, v) `[num_pages, page_size,
kv_heads * head_dim]` or, for a model that declares `value_lanes` (its
cache spec, serving/protocol.py), ONE array `[num_pages, page_size,
row_lanes]` — the latent row the scores contract over, whose first
`value_lanes` lanes are the values too (`row_lanes`: the row padded to
whole 128-lane tiles, zeros in the padding — 576 lanes sit in 640).
The width is the model's KV heads: 4 kv heads of 128 are 2 KB a token a
layer and array however many query heads read them. Page table, free
list, refcounts and prefix index know pages, not arrays: both kinds of
plane are allocated, shared, parked and evicted by the same code.
Sequences own pages through per-sequence page tables; a
host-side free-list allocator hands pages out and takes them back, so
KV memory is O(pages actually in use) instead of the dense cache's
O(batch * max_seq_len). The ragged paged-attention kernel gathers a
row's pages straight from this layout (`ops/pallas/paged_attention.py`
module docstring has the exact shapes).

Quantized pages (`kv_dtype='int8'`, ISSUE 7): each layer's entry
becomes a 4-tuple `(k_pages int8, v_pages int8, k_scales fp32,
v_scales fp32)` with scales of shape `[num_pages, page_size,
local_heads]` — one abs-max scale per (token slot, head), computed
when the token's K/V row is scattered in (`write_kv_pages_quantized`)
so already-written slots never rescale. Attention dequantizes inside
the kernel (or the dense fallback), so the math stays fp32 while the
pool holds ~4x (vs fp32) / ~2x (vs bf16) more tokens per byte; the
exact per-token byte math is `bytes_per_token()` below and
docs/serving.md#quantized-kv.

Copy-on-write prefix caching (ISSUE 9, `prefix_cache=True`): physical
pages are REFCOUNTED and a hash-chained prefix index maps token blocks
(granularity = page_size tokens) to the physical page that already
holds their K/V, so requests whose prompts share a prefix map their
page tables onto the same pages and skip the prefill compute for them.
The index key is `(parent_page, tuple(block_tokens))` — a radix chain
keyed by the previous block's *index* page, so a key identifies the
entire token prefix exactly (no hash-collision risk, and a block's K/V
is a pure function of the whole prefix, so dedup across requests is
sound). Only FULL pages are ever shared; a request diverging from a
cached prefix mid-page simply recomputes from the last shared page
boundary into a private page — that recompute IS the fork-on-write
(shared pages are append-only-immutable and never written: a request
always has >= 1 privately-prefilled token, so every page it scatters
into is private). Released pages whose content is still indexed park
in an LRU "cached" set: allocatable like free pages (eviction drops
the index subtree under them so a recycled page id can never satisfy a
stale chain), but a later matching prompt — including a preempted
request resuming — resurrects them for free. Int8 pools share scale
buffers automatically: scales are addressed by the same page id.

Host-RAM tier (ISSUE 20, `attach_host_tier`): under pool pressure,
cached (ref-0 parked) subtrees SPILL to a pinned host buffer pool
(`host_tier.HostTier`) instead of evicting — each spilled page's index
entry re-keys onto a negative HOST marker (`marker = -2 - host_slot`;
device pages are >= 0 and the chain root sentinel is -1, so markers
never collide), its children re-parent onto the marker, and the device
page unpins into the free list when the background transfer lands.
A matching prompt — or a preempted request resuming — walks the same
radix chain, finds the markers, and RESURRECTS the pages by host→device
prefetch instead of re-prefilling them; spill-in-flight device pages
sit in `_spilling`, outside free AND cached, so `try_reserve` and
`_take_page` see them as unavailable until landed.

The allocator is deliberately host-side and dumb-simple: serving
decisions (admit / grow / preempt) happen between jitted steps, where
Python cost is amortized over a whole batch step. Invariants it
enforces (tested in tests/test_serving.py):

  * a page's refcount equals the number of sequences mapping it
    (exactly one owner unless prefix sharing maps it again);
  * free + cached + mapped partitions the pool at all times;
  * release drops every page of a sequence exactly once — a page
    returns to the free/cached set only when its LAST mapper lets go.
"""
import collections
import hashlib
import math
import struct
import threading

import numpy as _np


def chain_hash(parent_hash, block_tokens):
    """64-bit hash of one radix-chain link: the parent chain hash plus
    this block's tokens. Stable across processes (blake2b, fixed
    little-endian packing) — the disaggregated router hashes a prompt's
    block chain with exactly this function and compares against the
    digests each replica publishes from its own prefix index
    (cluster/router.py prefix-affinity placement)."""
    h = hashlib.blake2b(digest_size=8)
    h.update(struct.pack('<Q',
                         int(parent_hash) & 0xFFFFFFFFFFFFFFFF))
    h.update(_np.asarray(list(block_tokens), '<i4').tobytes())
    return int.from_bytes(h.digest(), 'little')


def chain_hashes(tokens, page_size, limit=None):
    """Chain hashes of every FULL page_size-token block of `tokens`
    (capped at `limit` tokens), in chain order — h[i] identifies the
    whole prefix up to block i, matching the pool's radix-index
    identity (kv_pool docstring)."""
    n = len(tokens) if limit is None else min(len(tokens),
                                              max(int(limit), 0))
    out, h = [], -1
    for i in range(n // int(page_size)):
        h = chain_hash(h, tokens[i * page_size:(i + 1) * page_size])
        out.append(h)
    return out


def _np_dtype(dt):
    """np.dtype of a string / numpy / jnp dtype spec without importing
    jax for the common cases (pure-allocator tests stay jax-free)."""
    try:
        return _np.dtype(dt)
    except TypeError:
        import jax.numpy as jnp
        return _np.dtype(jnp.dtype(dt))


class PoolExhausted(RuntimeError):
    """No free pages — the scheduler's cue to stop admitting or to
    preempt a victim (engine.py)."""


class KVPagePool:
    """Free-list page allocator + the paged device arrays.

    Device arrays are created lazily (`materialize()`) so pure
    allocator tests never touch jax; the engine materializes once at
    build. `kv[l]` is plane l — the (k_pages, v_pages) pair, or the
    one-array (rows,) of a latent plane (`value_lanes`) — and
    `num_layers` counts the PLANES: the layers that own one (a layer
    that reads another's, or keeps no K/V at all, has none).
    `num_heads` counts the heads STORED: the model's kv heads.

    Beside the pages, which grow with a request's context, the pool
    holds a model's RECURRENT state (protocol.py `state_spec`): per
    entry of `state_spec` one array [state_slots + 1, *shape] — a slot
    a batch slot and a spare that idle rows name — of a fixed size a
    request. It has no allocator: a request's slot is its batch slot,
    and a slot is never cleared (a row that starts at position 0 starts
    from zeros on the device).
    """

    def __init__(self, num_pages, page_size, num_layers=0, num_heads=0,
                 head_dim=0, dtype=None, prefix_cache=False,
                 state_spec=(), state_slots=0, value_lanes=None):
        if num_pages <= 0 or page_size <= 0:
            raise ValueError("num_pages and page_size must be positive")
        # None: planes are (k, v) pairs; an int: one array a plane,
        # whose first `value_lanes` lanes are the values
        self.value_lanes = None if value_lanes is None else int(value_lanes)
        if self.value_lanes is not None:
            if not 0 < self.value_lanes <= int(num_heads) * int(head_dim):
                raise ValueError(
                    f"value_lanes {value_lanes} of a row of "
                    f"{int(num_heads) * int(head_dim)} lanes")
            if dtype is not None and _np_dtype(dtype) == _np.int8:
                raise NotImplementedError(
                    "an int8 pool of latent (one-array) planes: the "
                    "per-(slot, head) scales are laid out for a (k, v) "
                    "pair, and one scale over a row that is key and "
                    "value at once is not written")
        self.state_spec = [(tuple(int(d) for d in shape), dt)
                           for shape, dt in state_spec]
        self.state_slots = int(state_slots)
        self.state = None                   # [array] per state_spec entry
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.head_dim = int(head_dim)
        self.dtype = dtype
        self.prefix_cache = bool(prefix_cache)
        self.kv = None                      # [(k_pages, v_pages)] per layer
        self._free = list(range(self.num_pages - 1, -1, -1))
        self._ref = {}                      # page id -> mapper count
        self._owners = {}                   # page id -> set of seq ids
        self._seq_pages = {}                # seq id -> [page ids]
        # prefix index: (parent index page | -1, block token tuple) ->
        # physical page; _cached is the LRU set of ref-0-but-indexed
        # pages (allocatable, resurrectable)
        self._index = {}
        self._page_key = {}                 # page id -> its index key
        self._children = {}                 # page id -> child page ids
        self._cached = collections.OrderedDict()
        self._registered_upto = {}          # seq id -> tokens indexed
        # weighted eviction (ISSUE 15): indexed pages remember the
        # tenant whose request first registered them; when the
        # degradation ladder reaches stage 3 the engine installs
        # per-tenant weights and cached-subtree eviction picks the
        # LIGHTEST tenant's LRU root instead of the global LRU —
        # a heavy tenant under overload loses its own cache first
        self._page_tenant = {}              # page id -> tenant id|None
        self._evict_weights = None          # tenant id -> weight|None
        self._digest_cache = None           # (limit, hashes) memo —
                                            # invalidated on any index
                                            # mutation; status() polls
                                            # this several times a
                                            # second per replica
        self._lock = threading.Lock()
        # host-RAM tier (ISSUE 20): markers (<= -2) live in _index /
        # _page_key / _children like device pages; _spilling pins
        # device pages whose spill is in flight (outside free AND
        # cached — no allocation path can hand them out)
        self.host_tier = None
        self._spilling = set()
        self.host_resurrect_pages = 0
        self.host_resurrect_tokens = 0
        self._pending_resurrect = None      # engine pops for trace/ledger
        self.alloc_total = 0
        self.free_total = 0
        self.high_water = 0
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.prefix_hit_tokens = 0
        self.prefix_evictions = 0

    def attach_host_tier(self, tier):
        """Install the host-RAM tier (host_tier.HostTier). Must happen
        before any spill; the pool never constructs one itself so pure
        allocator tests stay tier-free."""
        if self.value_lanes is not None:
            raise NotImplementedError(
                "a host tier under latent (one-array) planes: spill and "
                "fetch move (k, v) pairs")
        self.host_tier = tier
        return tier

    # -- device arrays -------------------------------------------------------
    @property
    def quantized(self):
        """True when pages store int8 + per-(slot, head) fp32 scales."""
        if self.dtype is None:
            return False
        return _np_dtype(self.dtype) == _np.int8

    @property
    def row_lanes(self):
        """Lanes of one stored row of one array: kv heads x head_dim,
        and for a latent plane that padded to whole 128-lane tiles (576
        -> 640: the kernel's page copies, its scratch and its products
        then see whole tiles, and the padding is zeros, which a score
        can contract over; 11 % more bytes a token is the price)."""
        hd = self.num_heads * self.head_dim
        return hd if self.value_lanes is None else -(-hd // 128) * 128

    def materialize(self, sharding=None):
        """Create the device arrays. `sharding` (a NamedSharding whose
        spec splits the trailing heads*hd axis, e.g. P(None, None,
        'mp')) places the pool sharded over a replica-local mesh for
        the mp-sharded serving route — each mp shard then holds its
        local heads' pages, exactly the layout forward_paged's
        column-sharded qkv writes (docs/serving.md#mp-sharding)."""
        if self.kv is not None:
            return self.kv
        if sharding is not None and self.value_lanes is not None:
            raise NotImplementedError(
                "latent (one-array) planes under an mp sharding: the "
                "one stored head has no heads axis to split")
        import jax.numpy as jnp

        def _z(shape, dt):
            arr = jnp.zeros(shape, dt)
            if sharding is not None:
                import jax
                arr = jax.device_put(arr, sharding)
            return arr

        hd = self.num_heads * self.head_dim
        if self.quantized:
            shape = (self.num_pages, self.page_size, hd)
            sshape = (self.num_pages, self.page_size, self.num_heads)
            self.kv = [
                (_z(shape, jnp.int8), _z(shape, jnp.int8),
                 _z(sshape, jnp.float32), _z(sshape, jnp.float32))
                for _ in range(self.num_layers)]
            return self.kv
        dt = self.dtype or jnp.float32
        shape = (self.num_pages, self.page_size, self.row_lanes)
        self.kv = [
            tuple(_z(shape, dt) for _ in range(self.arrays_per_plane))
            for _ in range(self.num_layers)]
        return self.kv

    @property
    def arrays_per_plane(self):
        """2: a plane is the pair (k, v); 1: a latent plane."""
        return 2 if self.value_lanes is None else 1

    def bytes_per_token(self):
        """Device bytes one token occupies across all planes — the
        capacity math of docs/serving.md#quantized-kv. A paired plane
        holds K and V: int8 pages cost heads*head_dim*1 + heads*4
        (scale) for each, dense pages heads*head_dim*itemsize for each.
        A latent plane holds ONE row of `row_lanes` (the padding is
        held, and copied, like the rest)."""
        if self.quantized:
            per = self.row_lanes * 1 + self.num_heads * 4
        else:
            item = _np_dtype(self.dtype).itemsize if self.dtype else 4
            per = self.row_lanes * item
        return self.arrays_per_plane * per * self.num_layers

    def pool_bytes(self):
        """Total device bytes of the materialized (or to-be-
        materialized) pool arrays."""
        return self.num_pages * self.page_size * self.bytes_per_token()

    def materialize_state(self):
        """Create the recurrent-state arrays, zeroed (None: the model
        declares none)."""
        if self.state is None and self.state_spec:
            import jax.numpy as jnp
            self.state = [
                jnp.zeros((self.state_slots + 1,) + shape, dt)
                for shape, dt in self.state_spec]
        return self.state

    def state_bytes(self):
        """Device bytes of the recurrent-state arrays (0: none)."""
        return sum(
            (self.state_slots + 1) * math.prod(shape)
            * _np_dtype(dt).itemsize for shape, dt in self.state_spec)

    def drop_arrays(self):
        """Release the device buffers (engine shutdown)."""
        self.kv = None
        self.state = None

    # -- allocator -----------------------------------------------------------
    def pages_for(self, n_tokens):
        return max(1, math.ceil(n_tokens / self.page_size))

    @property
    def pages_in_use(self):
        """Pages mapped by at least one sequence. Cached (indexed but
        unmapped) pages are reclaimable and count as free."""
        return self.num_pages - len(self._free) - len(self._cached)

    @property
    def free_pages(self):
        """Allocatable pages: truly free + cached-evictable."""
        return len(self._free) + len(self._cached)

    @property
    def cached_pages(self):
        return len(self._cached)

    @property
    def shared_pages(self):
        """Physical pages currently mapped by more than one sequence."""
        return sum(1 for r in self._ref.values() if r > 1)

    def utilization(self):
        return self.pages_in_use / self.num_pages

    def capacity_tokens(self, seq_id):
        """Tokens the sequence can hold without another allocation."""
        return len(self._seq_pages.get(seq_id, ())) * self.page_size

    def reclaimable_pages(self, seq_id):
        """Pages release(seq_id) would actually free right now (the
        seq is their only mapper) — the admission sweep's preemption-
        feasibility estimate: preempting a victim whose pages are all
        shared reclaims nothing, so the sweep must not destroy its
        work for a budget that still won't cover the admit."""
        with self._lock:
            return sum(1 for p in self._seq_pages.get(seq_id, ())
                       if self._ref.get(p) == 1)

    def page_table(self, seq_id):
        return list(self._seq_pages.get(seq_id, ()))

    def owned_sequences(self):
        return list(self._seq_pages)

    def _evict_subtree(self, page):
        """Drop `page` and every index descendant from the prefix
        index, returning the cached (ref-0) ones to the free list.
        Dropping descendants with the parent is a correctness
        requirement, not just hygiene: the freed page id will be
        recycled, and a surviving child keyed on it could satisfy a
        stale chain. A descendant a live sequence still maps (possible
        when registration dedup chained it through a canonical page
        its owner never mapped) is only DE-indexed — it lives on as a
        plain private page and frees normally at release. Iterative:
        chains grow one node per page of a sequence, which at small
        page sizes is deeper than Python's recursion limit."""
        self._digest_cache = None
        stack = [page]
        while stack:
            p = stack.pop()
            stack.extend(self._children.pop(p, ()))
            key = self._page_key.pop(p)
            del self._index[key]
            parent = key[0]
            if parent != -1 and parent in self._children:
                self._children[parent].discard(p)
            self._page_tenant.pop(p, None)
            if p <= -2:
                # host-tier node: the index entry IS the page — drop it
                # and hand the host slot back (device side owes nothing)
                if self.host_tier is not None:
                    self.host_tier.free_slot(-2 - p)
                self.prefix_evictions += 1
            elif p in self._cached:
                del self._cached[p]
                self._free.append(p)
                self.prefix_evictions += 1

    def set_eviction_weights(self, weights):
        """Install (or clear, with None) per-tenant eviction weights.
        While set, cached-subtree eviction under allocation pressure
        picks the root whose owning tenant has the LOWEST weight
        (LRU order within a weight class; unowned pages weigh 1.0)
        instead of pure LRU — the degradation ladder's stage-3 lever
        (docs/serving.md#multi-tenant)."""
        self._evict_weights = (None if weights is None
                               else {str(k): float(v)
                                     for k, v in weights.items()})

    def _pick_eviction_root(self):
        """The cached page eviction starts from: global LRU normally;
        under weighted eviction, the LRU cached page of the lightest-
        weight owning tenant."""
        if self._evict_weights is None:
            return next(iter(self._cached))
        w = self._evict_weights
        return min(self._cached,
                   key=lambda p: w.get(self._page_tenant.get(p), 1.0))

    # -- host-RAM tier (ISSUE 20) --------------------------------------------
    def _rekey_node(self, old, new):
        """Move a radix node's identity from ref `old` to ref `new`:
        its index entry, its children's keys (they chain through the
        parent REF), the parent's child set, and the tenant tag. The
        spill (page -> marker) and resurrect (marker -> page)
        directions are the same bookkeeping."""
        key = self._page_key.pop(old)
        del self._index[key]
        self._index[key] = new
        self._page_key[new] = key
        parent = key[0]
        if parent != -1 and parent in self._children:
            self._children[parent].discard(old)
            self._children[parent].add(new)
        kids = self._children.pop(old, None)
        if kids:
            self._children[new] = kids
            for c in list(kids):
                ckey = self._page_key.pop(c)
                nkey = (new, ckey[1])
                del self._index[ckey]
                self._index[nkey] = c
                self._page_key[c] = nkey
        tn = self._page_tenant.pop(old, None)
        if tn is not None:
            self._page_tenant[new] = tn
        self._digest_cache = None

    def _spill_landed_locked(self, pages):
        for p in pages:
            if p in self._spilling:
                self._spilling.discard(p)
                self._free.append(p)

    def _spill_landed(self, pages):
        with self._lock:
            self._spill_landed_locked(pages)

    def _spill_prepare(self, root):
        """Re-key `root`'s cached (ref-0) subtree pages onto HOST
        markers and pin them in `_spilling` — the index mutation half
        of a spill, lock held by caller. A matching prompt still
        chain-walks to the markers; live descendants (mapped by a
        sequence) stay device-resident — only their chain link
        re-parents. Slot allocation is all-or-nothing per subtree (a
        half-spilled subtree would split its chain); a full tier
        prepares nothing and the caller falls back to plain eviction.
        Returns (device_pages, host_slots) or None. The TRANSFER is
        the caller's job: synchronous inline, or queued to the
        background thread OUTSIDE the pool lock (the bounded window
        semaphore must never be waited on while holding the lock the
        landed-callback needs)."""
        tier = self.host_tier
        if tier is None or self.kv is None:
            return None
        cached = []
        stack = [root]
        while stack:                # parents visit before children, so
            p = stack.pop()         # a child re-keys under its parent's
            stack.extend(self._children.get(p, ()))     # marker
            if p in self._cached:
                cached.append(p)
        if not cached:
            return None
        slots = tier.alloc_slots(len(cached))
        if slots is None:
            return None
        for p, slot in zip(cached, slots):
            self._rekey_node(p, -2 - slot)
            del self._cached[p]
            self._spilling.add(p)
        return cached, slots

    def _spill_subtree(self, root, sync=True):
        """Synchronous spill of `root`'s cached subtree (the
        `_take_page` exhaustion path — the page is needed NOW). Lock
        held by caller. Returns the device pages spilled."""
        assert sync, "async spills go through spill_lru"
        prep = self._spill_prepare(root)
        if prep is None:
            return []
        pages, slots = prep
        self.host_tier.spill_sync(self.kv, pages, slots)
        self._spill_landed_locked(pages)
        return pages

    def spill_lru(self, max_pages=None, sync=False):
        """Spill LRU-parked cached subtrees (preempted requests'
        released pages land there too) until `max_pages` device pages
        are spilling (None = the whole parked set). The engine's
        proactive spiller calls this when utilization crosses the
        spill watermark, keeping the free list stocked so allocation
        never has to spill synchronously. Returns pages spilled.

        Async jobs are submitted AFTER the lock is released: the
        tier's bounded in-flight window can block the producer, and
        the landed callback that unblocks it needs this lock — queueing
        under the lock would deadlock the pair. The pinned pages'
        contents are immutable until landed and `self.kv` only swaps
        on the engine thread (the thread running this), so staging
        outside the lock reads exactly the rows that were pinned."""
        if self.host_tier is None:
            return 0
        n = 0
        jobs = []
        with self._lock:
            while self._cached and (max_pages is None or n < max_pages):
                prep = self._spill_prepare(self._pick_eviction_root())
                if prep is None:
                    break
                pages, slots = prep
                if sync:
                    self.host_tier.spill_sync(self.kv, pages, slots)
                    self._spill_landed_locked(pages)
                else:
                    jobs.append((pages, slots))
                n += len(pages)
        for pages, slots in jobs:
            self.host_tier.submit_spill(
                self.kv, pages, slots,
                on_landed=lambda pages=list(pages):
                    self._spill_landed(pages))
        return n

    def host_resident_pages(self):
        """Pages currently host-resident (markers in the index)."""
        with self._lock:
            return sum(1 for p in self._page_key if p <= -2)

    def pop_resurrect_stats(self):
        """Pop the pending resurrect accounting (pages/tokens fetched
        since the last pop) — the engine turns it into a `resurrect`
        trace event and ledger page_stream attribution."""
        with self._lock:
            r, self._pending_resurrect = self._pending_resurrect, None
        return r

    def _resurrect_locked(self, markers, seq_id=None):
        """Fetch host-resident `markers` back into device pages: parked
        (cached, ref-0) pages when seq_id is None (the router's warm
        hint), mapped into seq_id's table otherwise. Lock held by
        caller; allocation uses only the free list on the warm path
        (a hint never evicts). Returns the device pages, aligned with
        `markers` (shorter when the pool ran out mid-chain)."""
        tier = self.host_tier
        devs, slots = [], []
        for m in markers:
            if m not in self._page_key:
                break               # destroyed under us by an eviction
            if seq_id is not None:
                try:
                    page = self._take_page(seq_id)
                except PoolExhausted:
                    break
            else:
                if not self._free:
                    break
                page = self._free.pop()
            devs.append(page)
            slots.append(-2 - m)
            self._rekey_node(m, page)
            if seq_id is None:
                self._cached[page] = None       # parked, LRU newest
        if devs:
            self.kv = tier.fetch(self.kv, slots, devs)
            for s in slots:
                tier.free_slot(s)
            self.host_resurrect_pages += len(devs)
            self.host_resurrect_tokens += len(devs) * self.page_size
            pend = self._pending_resurrect or {'pages': 0, 'tokens': 0}
            pend['pages'] += len(devs)
            pend['tokens'] += len(devs) * self.page_size
            self._pending_resurrect = pend
        return devs

    def warm_prefix(self, tokens, limit=None):
        """Advisory host→device prefetch (the router's prefix-affinity
        hint): resurrect the host-resident pages of the longest
        indexed chain for `tokens` into PARKED (cached, ref-0) device
        pages, so the request that follows prefix-hits device pages
        with zero transfer on its own critical path. Uses only truly
        free pages — a hint never evicts or preempts — and stops at
        the first unavailable page. Returns pages warmed."""
        if self.host_tier is None or not self.prefix_cache:
            return 0
        with self._lock:
            refs = self._match_pages(tokens, limit)
            markers = [m for m in refs if m <= -2]
            return len(self._resurrect_locked(markers, seq_id=None))

    def _take_page(self, seq_id):
        if not self._free and self._cached:
            # host tier first (ISSUE 20): spill the LRU cached subtree
            # synchronously — the page is needed NOW and the proactive
            # spiller didn't keep up — so its prefix survives as
            # host-resident markers instead of evaporating
            if self.host_tier is not None and self.kv is not None:
                self._spill_subtree(self._pick_eviction_root(),
                                    sync=True)
            if not self._free and self._cached:
                # evict the least-recently-used cached prefix subtree
                # (weight-ordered when eviction weights are installed)
                self._evict_subtree(self._pick_eviction_root())
        if not self._free:
            raise PoolExhausted(
                f"KV pool exhausted: {self.num_pages} pages of "
                f"{self.page_size} tokens all in use")
        page = self._free.pop()
        assert page not in self._ref, f"page {page} double-mapped"
        self._ref[page] = 1
        self._owners[page] = {seq_id}
        self._seq_pages.setdefault(seq_id, []).append(page)
        self.alloc_total += 1
        self.high_water = max(self.high_water, self.pages_in_use)
        return page

    def _map_existing(self, page, seq_id):
        """Map an already-resident page into seq_id's table: incref a
        live page, or resurrect a cached one (ref 0 -> 1)."""
        if page in self._cached:
            del self._cached[page]
            self._ref[page] = 1
            self._owners[page] = {seq_id}
        else:
            self._ref[page] += 1
            self._owners[page].add(seq_id)
        self._seq_pages.setdefault(seq_id, []).append(page)
        self.high_water = max(self.high_water, self.pages_in_use)

    def ensure_capacity(self, seq_id, n_tokens):
        """Grow seq_id's page list to hold n_tokens. Raises
        PoolExhausted (after rolling back nothing — partial growth is
        kept, the caller preempts and retries)."""
        need = self.pages_for(n_tokens)
        with self._lock:
            while len(self._seq_pages.get(seq_id, ())) < need:
                self._take_page(seq_id)
        return self._seq_pages[seq_id]

    def try_reserve(self, seq_id, n_tokens):
        """Grow seq_id's page list to hold n_tokens, or change NOTHING
        — the fused decode window's all-or-nothing reservation (ISSUE
        19). Unlike ensure_capacity (partial growth kept because its
        caller preempts and retries), a failed reservation rolls its
        own fresh pages straight back: the engine falls back to the
        [B, 1] step for this dispatch instead of preempting, so the
        pool must come out untouched. Returns True when the pages are
        held. Fresh pages are private and unindexed by construction,
        so the rollback mirrors trim's bookkeeping."""
        need = self.pages_for(n_tokens)
        with self._lock:
            grown = 0
            try:
                while len(self._seq_pages.get(seq_id, ())) < need:
                    self._take_page(seq_id)
                    grown += 1
            except PoolExhausted:
                pages = self._seq_pages.get(seq_id, [])
                for _ in range(grown):
                    page = pages.pop()
                    del self._ref[page]
                    del self._owners[page]
                    self._free.append(page)
                    self.free_total += 1
                return False
        return True

    def release(self, seq_id):
        """Drop seq_id's mapping of every page it holds, exactly once
        per page. A page whose refcount reaches zero becomes
        reclaimable: indexed pages park in the cached (LRU,
        resurrectable) set, unindexed ones return to the free list.
        Pages a sibling still references stay mapped — preemption can
        never evict a live sharer's prefix. Returns the number of
        pages made reclaimable."""
        with self._lock:
            pages = self._seq_pages.pop(seq_id, [])
            self._registered_upto.pop(seq_id, None)
            reclaimed = 0
            for page in pages:
                owners = self._owners.get(page)
                assert owners is not None and seq_id in owners, \
                    f"page {page} owned by {owners}, freed by {seq_id}"
                owners.discard(seq_id)
                self._ref[page] -= 1
                if self._ref[page] > 0:
                    continue
                del self._ref[page]
                del self._owners[page]
                if page in self._page_key:
                    self._cached[page] = None       # LRU newest
                else:
                    self._free.append(page)
                reclaimed += 1
                self.free_total += 1
        return reclaimed

    def trim(self, seq_id, n_tokens):
        """Give back trailing pages beyond what n_tokens needs — the
        speculative-decode rollback: the verify step grows the table
        for k drafts, rejected ones hand their pages straight back.
        Only private unindexed tail pages are trimmed (shared or
        indexed pages stay; their slots are overwritten in place by
        later writes). Returns the number of pages freed."""
        keep = self.pages_for(n_tokens)
        with self._lock:
            pages = self._seq_pages.get(seq_id, [])
            freed = 0
            while len(pages) > keep:
                page = pages[-1]
                if self._ref.get(page) != 1 or page in self._page_key:
                    break
                pages.pop()
                del self._ref[page]
                del self._owners[page]
                self._free.append(page)
                freed += 1
                self.free_total += 1
        return freed

    def reset(self):
        with self._lock:
            if self.host_tier is not None:
                for p in self._page_key:
                    if p <= -2:
                        self.host_tier.free_slot(-2 - p)
            self._spilling.clear()
            self._pending_resurrect = None
            self._free = list(range(self.num_pages - 1, -1, -1))
            self._ref.clear()
            self._owners.clear()
            self._seq_pages.clear()
            self._index.clear()
            self._page_key.clear()
            self._children.clear()
            self._cached.clear()
            self._registered_upto.clear()
            self._page_tenant.clear()
            self._digest_cache = None

    # -- prefix index --------------------------------------------------------
    def _match_pages(self, tokens, limit=None):
        """Walk the index chain over full token blocks; returns the
        matched physical pages (longest indexed prefix, in order)."""
        ps = self.page_size
        n = len(tokens) if limit is None else min(len(tokens),
                                                  max(int(limit), 0))
        pages, parent = [], -1
        for i in range(n // ps):
            page = self._index.get(
                (parent, tuple(tokens[i * ps:(i + 1) * ps])))
            if page is None:
                break
            pages.append(page)
            parent = page
        return pages

    def peek_prefix(self, tokens, limit=None):
        """Non-mutating admission probe: (cached_tokens, live_pages,
        resurrect_pages, host_pages). Live pages are mapped by a
        sibling and cost the page budget nothing; resurrect pages sit
        in the device cached set and cost one allocatable page each
        (they just skip the prefill compute); host pages (ISSUE 20)
        also cost one allocatable page each PLUS a host→device
        transfer — the engine budgets them as transfer cost, not
        compute."""
        if not self.prefix_cache:
            return 0, 0, 0, 0
        with self._lock:
            pages = self._match_pages(tokens, limit)
            live = sum(1 for p in pages if self._ref.get(p, 0) > 0)
            host = sum(1 for p in pages if p <= -2)
        return (len(pages) * self.page_size, live,
                len(pages) - live - host, host)

    def match_and_map(self, seq_id, tokens, limit=None):
        """Map the longest indexed prefix of `tokens` (full blocks,
        capped at `limit` tokens) into seq_id's page table, increffing
        live pages and resurrecting cached ones. Returns the number of
        prefix tokens now covered — the caller skips prefilling them.
        Counted as one hit (or miss) per lookup."""
        if not self.prefix_cache:
            return 0
        with self._lock:
            if self._seq_pages.get(seq_id):
                # the seq already allocated (e.g. a prior prefill
                # attempt grew partial pages before PoolExhausted and
                # the caller retried): shared pages must sit at the
                # FRONT of the table, so just prefill privately
                return 0
            pages = self._match_pages(tokens, limit)
            if self.host_tier is not None and any(p <= -2
                                                 for p in pages):
                mapped = self._match_and_map_tiered(seq_id, tokens,
                                                    limit)
            else:
                for page in pages:
                    self._map_existing(page, seq_id)
                mapped = len(pages)
            if not mapped:
                self.prefix_misses += 1
                return 0
            cached = mapped * self.page_size
            self.prefix_hits += 1
            self.prefix_hit_tokens += cached
            self._registered_upto[seq_id] = cached
        return cached

    def _match_and_map_tiered(self, seq_id, tokens, limit=None):
        """match_and_map's slow path when the matched chain crosses
        host-resident markers: walk the index LIVE block by block
        (resurrection re-keys nodes and allocation pressure may evict
        or spill under us, so a pre-computed match would go stale),
        mapping device pages and fetching each contiguous marker run
        back in one chunked transfer. Lock held by caller. Returns
        full blocks mapped."""
        ps = self.page_size
        n = len(tokens) if limit is None else min(len(tokens),
                                                  max(int(limit), 0))
        blocks = n // ps

        def _block(j):
            return tuple(tokens[j * ps:(j + 1) * ps])

        parent, mapped, i = -1, 0, 0
        while i < blocks:
            ref = self._index.get((parent, _block(i)))
            if ref is None:
                break
            if ref <= -2:
                run, cur, j = [ref], ref, i + 1
                while j < blocks:
                    nxt = self._index.get((cur, _block(j)))
                    if nxt is None or nxt > -2:
                        break
                    run.append(nxt)
                    cur = nxt
                    j += 1
                devs = self._resurrect_locked(run, seq_id)
                mapped += len(devs)
                i += len(devs)
                if len(devs) < len(run):
                    return mapped       # pool ran out mid-chain: the
                                        # prefix covered so far stands
                parent = devs[-1] if devs else parent
            else:
                self._map_existing(ref, seq_id)
                mapped += 1
                i += 1
                parent = ref
        return mapped

    def register_prefix(self, seq_id, tokens, written, owner=None):
        """Index seq_id's newly completed full pages (first `written`
        tokens of `tokens` have K/V resident) so later requests can
        share them. A block already indexed elsewhere is NOT
        re-registered — the chain advances through the canonical page
        (dedup), and this sequence's private twin stays unindexed.
        The walk starts from the chain root every call (cheap: a few
        dict hits per resident block) so a chain broken by eviction
        self-heals from this sequence's own pages instead of chaining
        onto a stale — possibly recycled — parent id.

        `owner` (a tenant id) tags newly indexed pages for weighted
        eviction — the tenant whose request FIRST registered a page
        owns it for eviction purposes (shared pages keep their
        original owner; re-registration never re-tags)."""
        if not self.prefix_cache:
            return
        ps = self.page_size
        with self._lock:
            blocks = min(int(written), len(tokens)) // ps
            if blocks * ps <= self._registered_upto.get(seq_id, 0):
                return
            seq_pages = self._seq_pages.get(seq_id, [])
            parent = -1
            for i in range(min(blocks, len(seq_pages))):
                key = (parent, tuple(tokens[i * ps:(i + 1) * ps]))
                page = self._index.get(key)
                if page is None:
                    page = seq_pages[i]
                    if page in self._page_key:      # already chained
                        break                       # under another key
                    self._index[key] = page
                    self._page_key[page] = key
                    if owner is not None:
                        self._page_tenant[page] = str(owner)
                    self._digest_cache = None
                    if parent != -1:
                        self._children.setdefault(parent,
                                                  set()).add(page)
                parent = page
            self._registered_upto[seq_id] = blocks * ps

    def prefix_chain_hashes(self, limit=4096):
        """Chain hashes (chain_hash above) of every chain indexed in
        the prefix index, capped at `limit` entries — the affinity
        digest a serving replica publishes so the cluster router can
        route a prompt to the replica that already holds its prefix
        pages. A hash is present exactly when the corresponding token
        chain would prefix-hit here (match_and_map walks the same
        radix links). Memoized: the replica status loop reads this
        several times a second, and re-hashing thousands of chains
        under the pool lock would stall the allocator — the memo
        invalidates whenever the index gains or loses a chain."""
        if not self.prefix_cache:
            return []
        out = []
        with self._lock:
            memo = self._digest_cache
            if memo is not None and memo[0] == limit:
                return list(memo[1])
            roots = [(key, page) for key, page in self._index.items()
                     if key[0] == -1]
            stack = [(-1, key, page) for key, page in roots]
            while stack and len(out) < int(limit):
                parent_hash, key, page = stack.pop()
                h = chain_hash(parent_hash, key[1])
                out.append(h)
                for child in self._children.get(page, ()):
                    ckey = self._page_key.get(child)
                    if ckey is not None:
                        stack.append((h, ckey, child))
            self._digest_cache = (limit, list(out))
        return out

    def census(self):
        """{seq_id: pages held} — who is sitting on the pool right now
        (the serve_report watchdog artifact embeds this so a stalled
        request's report names the page hogs)."""
        with self._lock:
            return {seq: len(pages)
                    for seq, pages in self._seq_pages.items()}

    def stats(self):
        s = {
            'num_pages': self.num_pages,
            'page_size': self.page_size,
            'kv_dtype': ('int8' if self.quantized
                         else str(_np_dtype(self.dtype))
                         if self.dtype is not None else 'float32'),
            'bytes_per_token': self.bytes_per_token(),
            'pool_bytes': self.pool_bytes(),
            'kv_planes': self.num_layers,
            'arrays_per_plane': self.arrays_per_plane,
            'row_lanes': self.row_lanes,
            'value_lanes': self.value_lanes,
            'state_bytes': self.state_bytes(),
            'pages_in_use': self.pages_in_use,
            'free_pages': self.free_pages,
            'utilization': self.utilization(),
            'high_water': self.high_water,
            'alloc_total': self.alloc_total,
            'free_total': self.free_total,
            'sequences': len(self._seq_pages),
            'prefix_cache': self.prefix_cache,
            'cached_pages': self.cached_pages,
            'shared_pages': self.shared_pages,
            'prefix_hits_total': self.prefix_hits,
            'prefix_misses_total': self.prefix_misses,
            'prefix_hit_tokens_total': self.prefix_hit_tokens,
            'prefix_evictions_total': self.prefix_evictions,
            'weighted_eviction': self._evict_weights is not None,
        }
        if self.host_tier is not None:
            s.update(self.host_tier.stats())
            s['tier_resurrected_pages_total'] = self.host_resurrect_pages
            s['tier_resurrected_tokens_total'] = \
                self.host_resurrect_tokens
            s['tier_spill_inflight_pages'] = len(self._spilling)
        return s
