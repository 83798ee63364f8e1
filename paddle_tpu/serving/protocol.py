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
    forward_paged(tokens, positions, kv, page_tables, seq_lens, q_lens,
                  moe_counters=None) -> (hidden, new_kv, moe_counters)
        tokens / positions: int Tensors [B, T]; kv: per layer a tuple
        of pool Tensors ((k, v), or the int8 pool's (k, v, k_scales,
        v_scales)); the rest plain int32 arrays. Position t of row b
        holds a token iff t < q_lens[b]; the rest is padding. hidden is
        the final-normed Tensor [B, T, H] the head's weight multiplies
    paged_routes
        the routes of the engine's that forward_paged is written for,
        of 'plain', 'fused' (fused_k > 1), 'verify' (spec_k > 0),
        'int8_kv', 'int8_weights', 'mp'. The engine refuses at
        construction a configuration that needs one the model lacks
    lm_head_weight() -> Tensor [V, H]
        the head (tied or not); its dtype is the pool's default dtype
    mp_degree
        the tensor-parallel degree the model was built under (1: none)
    moe_counters() -> None, or int32 [expert layers, experts held + 3]
        a model with sparse-expert layers counts what they route: per
        layer, the (token, expert) rows each expert took in the LAST
        call (padding rows take none), then experts touched, rows and
        calls, each only ever growing (int32, wrapping; the engine
        takes differences). The engine passes the last value into the
        plain route's step, takes the new one out, and packs it behind
        the sampled tokens so the step still costs ONE host fetch. On
        the other routes it passes None and nothing is counted.

`GPTForCausalLM` and `AfmoeForCausalLM` implement it.
"""
import collections

KVLayerSpec = collections.namedtuple(
    'KVLayerSpec', ['num_kv_heads', 'head_dim', 'window'])
