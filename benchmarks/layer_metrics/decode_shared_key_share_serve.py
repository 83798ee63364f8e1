"""decode_shared_key_share.serve — layer: Pallas kernels. The decode
rows' keys that a shared run served — a leading page run that several
decode rows map, which the paged kernel reads ONCE for all of them —
over the keys the decode rows' masks let them read, over the window, in
percent. The numerator is the engine's count as each
`serve::device_step` record carries it (`shared_keys`, the unit of
`attn_kv_tokens_shared_total`: a member row's run keys in each full
layer), summed over the window's records; the denominator is
`attn_kv_tokens_read_total` less `attn_kv_tokens_read_chunks_total` over
the window. 0.0 where no run was found; None where the program's
records carry no such count (a parent before the shared run) or no
decode row read a key."""
from benchmarks.layer_metrics import _device_steps


def read(trace, facts):
    recs = _device_steps.records(facts)
    c = facts.get('counters') or {}
    decode = c.get('attn_kv_tokens_read_total', 0) \
        - c.get('attn_kv_tokens_read_chunks_total', 0)
    if not recs or decode <= 0 \
            or not all('shared_keys' in args for args, _ in recs):
        return None
    return 100.0 * sum(args['shared_keys'] for args, _ in recs) / decode
