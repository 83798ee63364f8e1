"""The serving-model protocol: what `ServingEngine` asks of a model.

The engine never reaches into a model's layers. A model it can serve
has:

    config.max_seq_len, config.num_layers, config.hidden_size
        the longest context a request may reach, and the ledger's
        sizes
    kv_cache_spec() -> [KVLayerSpec] one per layer
        what a token's K/V takes in that layer's pages: kv heads (the
        GLOBAL count under mp), head_dim, and `window` — None where a
        query reads every earlier key, else the number of most recent
        keys it reads. The pool is sized by kv heads; one page table
        serves every layer, so every layer must agree on kv heads and
        head_dim (window layers keep pages they no longer read: a
        window-aware allocator is ROADMAP Queue 2)
    forward_paged(tokens, positions, kv, rows, moe_counters=None)
            -> (hidden, new_kv, moe)
        tokens / positions: int Tensors [1, N], the query tokens of
        every row of the dispatch laid end to end; `rows` a `RowGroups`
        (below) that says which rows they are; kv: per layer a tuple
        of pool Tensors ((k, v), or the int8 pool's (k, v, k_scales,
        v_scales)). Everything token-wise (embedding, norms,
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

`GPTForCausalLM` and `AfmoeForCausalLM` implement it.
"""
import collections

import jax.numpy as jnp

KVLayerSpec = collections.namedtuple(
    'KVLayerSpec', ['num_kv_heads', 'head_dim', 'window'])


class RowGroups:
    """The rows of one dispatch, in groups of one shape each.

    `layout` is ((rows, width), ...): a [B, T] step is one group, the
    mixed step two — B decode rows of one token, then P prompt chunks
    of C. `page_tables` [R, pages], `seq_lens` [R] and `q_lens` [R]
    cover the R = sum(rows) rows in that order; the dispatch's N =
    sum(rows * width) tokens lie end to end the same way, row-major,
    and position t of a row holds a token iff t < its q_len (an idle
    row rides with q_len 0). The split back into groups and the join
    are written here once, for every model.
    """

    def __init__(self, layout, page_tables, seq_lens, q_lens):
        self.layout = tuple((int(b), int(t)) for b, t in layout)
        self.page_tables = page_tables
        self.seq_lens = seq_lens
        self.q_lens = q_lens

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

    def attend(self, write, read, pool, q, k, v):
        """Attention over the paged pool, group by group. q / k / v are
        [1, N, .] over the dispatch's tokens; `write(pool, k, v,
        page_tables, seq_lens, q_lens) -> pool` puts a group's new K/V
        ([b, t, .]) into its rows' pages and `read(pool, q,
        page_tables, seq_lens, q_lens) -> [b, t, .]` attends. Every
        group writes before any reads: no request rides two groups of
        one dispatch, so the writes never meet, and the pool is updated
        in one chain. -> (context [1, N, .], pool)."""
        def view(a, tok, b, t):
            return a[0, tok:tok + b * t].reshape(b, t, a.shape[-1])

        def rows_of(r, b):
            return (self.page_tables[r:r + b], self.seq_lens[r:r + b],
                    self.q_lens[r:r + b])
        for r, tok, b, t in self._groups():
            pool = write(pool, view(k, tok, b, t), view(v, tok, b, t),
                         *rows_of(r, b))
        out = []
        for r, tok, b, t in self._groups():
            ctx = read(pool, view(q, tok, b, t), *rows_of(r, b))
            out.append(ctx.reshape(1, b * t, ctx.shape[-1]))
        return (out[0] if len(out) == 1
                else jnp.concatenate(out, axis=1)), pool

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
