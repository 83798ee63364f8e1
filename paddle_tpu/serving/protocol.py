"""The serving-model protocol: what `ServingEngine` asks of a model.

The engine never reaches into a model's layers. A model it can serve
has:

    config.max_seq_len, config.num_layers, config.hidden_size
        the longest context a request may reach, and the ledger's
        sizes
    kv_cache_spec() -> [KVLayerSpec] one per ATTENDING layer
        what a token's K/V takes in that layer's pages: kv heads (the
        GLOBAL count under mp), head_dim, `window` — None where a
        query reads every earlier key, else the number of most recent
        keys it reads — and `reads`: None where the layer OWNS a plane
        of the pool (it writes its tokens' K/V there and reads them
        back), else the index in this list of the owning entry whose
        plane it reads and never writes (a cross-decoder layer on a
        shared cache). A layer that neither attends nor caches (a
        state-space layer, a gated unit) has no entry. The pool holds
        one plane an OWNER, sized by kv heads; one page table serves
        every plane, so every entry must agree on kv heads, head_dim
        and `value_lanes` (window layers keep pages they no longer
        read: a window-aware allocator is ROADMAP Queue 2).
        `value_lanes` None: a plane is the PAIR (k, v) of equal width.
        An int: a LATENT plane, ONE array a layer whose row is what
        the scores contract over (num_kv_heads * head_dim lanes:
        latent attention's [c_kv | k_pe], one stored head) and whose
        first `value_lanes` lanes are also the values — every entry of
        such a model must be latent, agree on the row's width and on
        `value_lanes`, and carry no window (the kernel's latent body
        has none). The pool pads such a row to whole 128-lane tiles
        (`KVPagePool.row_lanes`), zeros in the padding
    forward_paged(tokens, positions, kv, rows, moe_counters=None)
            -> (hidden, new_kv, moe)
        tokens / positions: int Tensors [1, N], the query tokens of
        every row of the dispatch laid end to end; `rows` a `RowGroups`
        (below) that says which rows they are; kv: per OWNING entry of
        the cache spec, in its order, a tuple of pool Tensors ((k, v),
        the int8 pool's (k, v, k_scales, v_scales), or a latent
        plane's (rows,)). Everything
        token-wise (embedding, norms,
        projections, MLP, experts) runs ONCE over the N tokens, so a
        weight is read once a dispatch; attention alone goes group by
        group (`rows.attend`). hidden is the final-normed Tensor
        [1, N, H] the head's weight multiplies; moe is None, or the
        pair of `moe_counters` below
    paged_routes
        the routes of the engine's that forward_paged is written for,
        of 'plain', 'fused' (fused_k > 1), 'verify' (spec_k > 0),
        'int8_kv', 'int8_weights', 'mp'. The engine refuses at
        construction a configuration that needs one the model lacks
    lm_head_weight() -> Tensor [V, H]
        the head (tied or not); its dtype is the pool's default dtype
    mp_degree
        the tensor-parallel degree the model was built under (1: none)
    moe_counters() -> None, or int32 [expert layers, 3]
        a model with sparse-expert layers counts what they route: per
        layer the experts touched (per call of the experts' kernel: the
        union over the dispatch's rows), the (token, expert) rows and
        the calls, each only ever growing (int32, wrapping; the engine
        takes differences). The engine passes the last value into the
        plain route's step, and forward_paged returns (rows, counters):
        the new value, and before it int32 [expert layers, counted
        groups, experts held], the rows each expert took in THIS call
        from each of `rows.counted()`'s groups (padding rows take
        none). The engine packs both behind the sampled tokens so the
        step still costs ONE host fetch. On the other routes it passes
        None and nothing is counted.
    state_spec() -> [(shape, dtype)]    (optional: a model without the
                                         method holds no such state)
        recurrent state: per-request arrays of a FIXED size, beside the
        pages that grow with the context. The engine allocates each as
        [max_batch_size + 1, *shape] (kv_pool.py: one slot a batch
        slot, and a spare that idle rows name), passes the list into
        `forward_paged(..., state=[Tensor])`, takes the new list back
        as a fourth result and donates it as it donates `kv`.
        `rows.slots` [R] names each row's slot. The slot rule is the
        model's to keep, on the device: a row with q_len 0 writes
        nothing (it names the spare slot); a row whose first query
        sits at position 0 (`rows.fresh()`) starts from zeros whatever
        its slot held — so admission, preemption and re-prefill need
        no reset from the host and no dispatch of their own. Nothing
        keeps the state of an earlier position: the engine refuses, for
        such a model, whatever would resume a request anywhere but at
        its start or its end (prefix cache, host tier, adoption, the
        fused window, the verify step; docs/serving.md) — unless:
    page_state_spec() -> [(shape, dtype)]   (optional: a model without
                                             the method keeps none)
        the SAME state kept a page: the engine allocates each entry as
        [num_pages, *shape] (kv_pool.py: the page-state plane, whose row
        p is page p's and follows it through the allocator, the prefix
        index and eviction) and passes it at the END of the `state`
        list, after the slot arrays. The model's rule: the dispatch that
        computes the LAST token of a page writes the state as of that
        token into the page's row (a shared page is never written: its
        last token is never computed again), and a row flagged
        `rows.restore` starts from the row of the page BEFORE its first
        query instead of from its slot. A model that declares it keeps
        ALL its state so; the engine then lifts the prefix cache's
        refusal — a hit, or a preempted request's own registered pages,
        resumes at the page boundary it ends on — and keeps every other
        refusal above (the host tier moves K/V pages alone; adoption,
        the fused window and the verify step resume inside a page).

A model whose `read` (`RowGroups.attend`) can be handed a shared run
takes it as `shared=` and passes it to `ragged_paged_attention`: the
engine builds one (`engine.shared_run`) wherever the prefix cache is on
over (k, v) planes in the model's dtype without an mp split — so every
model served so must — and never for latent planes or a model whose
state refuses the prefix cache.

`GPTForCausalLM` and `AfmoeForCausalLM` implement it;
`Phi4FlashForCausalLM` with shared planes and recurrent state,
`AxK1ForCausalLM` with latent planes, `Lfm2MoeForCausalLM` with
recurrent state kept a page.
"""
import collections

import jax.numpy as jnp

KVLayerSpec = collections.namedtuple(
    'KVLayerSpec', ['num_kv_heads', 'head_dim', 'window', 'reads',
                    'value_lanes'],
    defaults=(None, None))


class RowGroups:
    """The rows of one dispatch, in groups of one shape each.

    `layout` is ((rows, width), ...): a [B, T] step is one group, the
    mixed step two — B decode rows of one token, then P prompt chunks
    of C. `page_tables` [R, pages], `seq_lens` [R] and `q_lens` [R]
    cover the R = sum(rows) rows in that order; the dispatch's N =
    sum(rows * width) tokens lie end to end the same way, row-major,
    and position t of a row holds a token iff t < its q_len (an idle
    row rides with q_len 0). `slots` [R] (None for a model without
    recurrent state) names each row's slot in the per-request state
    arrays; an idle row names the spare one. `restore` [R] bool (None
    for a model that keeps no state a page) marks the rows that resume
    after a mapped prefix: their state comes from the page before their
    first query. `shared` (None: no run) is the first group's leading
    page run that several of its rows map — (run pages int32 [pages],
    run keys int32, member rows bool [b]), the engine's `shared_run` —
    handed to that group's `read` to pass on to the paged kernel
    (`ragged_paged_attention(..., shared=)`, which reads the run once
    for its members). The split back into groups and the join are
    written here once, for every model.
    """

    def __init__(self, layout, page_tables, seq_lens, q_lens, slots=None,
                 restore=None, shared=None):
        self.layout = tuple((int(b), int(t)) for b, t in layout)
        self.page_tables = page_tables
        self.seq_lens = seq_lens
        self.q_lens = q_lens
        self.slots = slots
        self.restore = restore
        self.shared = shared

    def _groups(self):
        """(first row, first token, rows, width) of each group."""
        row = tok = 0
        for b, t in self.layout:
            yield row, tok, b, t
            row, tok = row + b, tok + b * t

    def _per_token(self, fn):
        """[N] from fn(group's seq_lens / q_lens [b, 1], arange [1, t])
        -> [b, t], group after group."""
        out = [fn(self.seq_lens[r:r + b, None], self.q_lens[r:r + b, None],
                  jnp.arange(t, dtype=jnp.int32)[None, :]).reshape(-1)
               for r, _, b, t in self._groups()]
        return out[0] if len(out) == 1 else jnp.concatenate(out)

    def positions(self, max_pos):
        """int32 [1, N]: each token's position in its sequence (query t
        of a row sits at seq_len - q_len + t), clipped to the model's
        last."""
        return jnp.clip(self._per_token(lambda s, q, t: s - q + t),
                        0, max_pos)[None, :]

    def live(self):
        """bool [1, N]: the positions that hold a token."""
        return self._per_token(lambda s, q, t: t < q)[None, :]

    def counted(self):
        """(ids int32 [N], count): the group a token's routed rows are
        counted with. The first group's rows count together (a decode
        batch: the engine reads its load), each row of a later group
        for itself (a prompt's chunk: the benchmark's check listens to
        one request's)."""
        ids, count = [], 0
        for i, (_, _, b, t) in enumerate(self._groups()):
            if i == 0:
                ids.append(jnp.zeros((b * t,), jnp.int32))
                count = 1
            else:
                ids.append(jnp.repeat(
                    count + jnp.arange(b, dtype=jnp.int32), t))
                count += b
        return (ids[0] if len(ids) == 1 else jnp.concatenate(ids)), count

    def fresh(self):
        """bool [R]: the rows whose first query sits at position 0 —
        a request's first chunk, or its first again after a preemption:
        recurrent state starts from zeros there."""
        return (self.q_lens > 0) & (self.seq_lens == self.q_lens)

    def groups(self, *arrays):
        """Per group: (rows slice, width, each of `arrays` [1, N, .]
        as the group's [b, t, .]): for what runs group by group —
        attention below, a model's recurrence."""
        for r, tok, b, t in self._groups():
            yield slice(r, r + b), t, tuple(
                a[0, tok:tok + b * t].reshape(b, t, a.shape[-1])
                for a in arrays)

    @staticmethod
    def join(parts):
        """Groups' [b, t, .] back to [1, N, .] in the dispatch's order."""
        out = [p.reshape(1, -1, p.shape[-1]) for p in parts]
        return out[0] if len(out) == 1 else jnp.concatenate(out, axis=1)

    def attend(self, write, read, pool, q, k=None, v=None):
        """Attention over the paged pool, group by group. q / k / v are
        [1, N, .] over the dispatch's tokens (v None: a latent plane,
        whose one row `k` is; `write` then takes no v); `write(pool, k,
        v, page_tables, seq_lens, q_lens) -> pool` puts a group's new
        K/V ([b, t, .]) into its rows' pages and `read(pool, q,
        page_tables, seq_lens, q_lens) -> [b, t, .]` attends — called
        with `shared=` the run as well for the group that has one (a
        model that can be handed one passes it to the kernel). Every
        group writes before any reads: no request rides two groups of
        one dispatch, so the writes never meet, and the pool is updated
        in one chain. `write` None: a reader of another layer's plane,
        which writes nothing (the pool comes back as it went in).
        -> (context [1, N, .], pool)."""
        def rows_of(at):
            return self.page_tables[at], self.seq_lens[at], self.q_lens[at]
        if write is not None:
            new = (k,) if v is None else (k, v)
            for at, _, new_g in self.groups(*new):
                pool = write(pool, *new_g, *rows_of(at))
        out = []
        for i, (at, _, (qg,)) in enumerate(self.groups(q)):
            run = {} if i or self.shared is None else {'shared': self.shared}
            out.append(read(pool, qg, *rows_of(at), **run))
        return self.join(out), pool

    def last(self, x):
        """x [1, N, .] -> [R, .] at each row's last query."""
        out = []
        for r, tok, b, t in self._groups():
            idx = jnp.clip(self.q_lens[r:r + b] - 1, 0, t - 1) \
                .astype(jnp.int32)
            out.append(jnp.take_along_axis(
                x[0, tok:tok + b * t].reshape(b, t, x.shape[-1]),
                idx[:, None, None], axis=1)[:, 0, :])
        return out[0] if len(out) == 1 else jnp.concatenate(out)
