"""mla_attention_roofline.serve — layer: Pallas kernels. The least time
the chip could take for the traced latent-attention calls
(`benchmarks/mla_cost.py`: per group of rows the LARGER of the keys its
masks let it read x the published row's 1,152 B over the HBM peak and
its (query, key) pairs x 139,264 operations over the bf16 peak — the
engine's `attn_kv_tokens_read_total`, `attn_kv_tokens_read_chunks_total`
and `attn_qk_pairs_total` over the traced steps) over the device time of
`pallas:paged_attention_latent*` in the trace, in percent; 0 where the
trace holds no such call. It prices the same work whatever implements
it, so it cannot pass 100. The counters and the trace cover the same
engine steps."""
from benchmarks import mla_cost
from benchmarks.common import log


def read(trace, facts):
    mla = facts.get('mla')
    chips = list((trace.get('chips') or {}).values())
    if not mla or not chips:
        return None
    seconds = sum(v for c in chips for k, v in c['ops'].items()
                  if k.startswith('pallas:paged_attention_latent')) \
        / len(chips)
    t = mla['traced']
    if not seconds or not t['attn_kv_tokens_read_total']:
        return 0.0          # the kernel did not run in the traced steps
    least, bound = mla_cost.least_seconds(
        t['attn_kv_tokens_read_total'],
        t['attn_kv_tokens_read_chunks_total'], t['attn_qk_pairs_total'],
        mla['row_bytes'], mla['pair_flops'], facts['device_kind'])
    log(f'latent attention: {t["attn_kv_tokens_read_total"]} (key, layer) '
        f'reads and {t["attn_qk_pairs_total"]} pairs, least '
        f'{least * 1e3:.2f} ms ('
        + ', '.join(f'{g} {b}-bound {s * 1e3:.2f}'
                    for g, (b, s) in bound.items())
        + f') of {seconds * 1e3:.2f} ms')
    return 100.0 * least / seconds
