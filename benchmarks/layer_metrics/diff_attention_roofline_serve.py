"""diff_attention_roofline.serve — layer: Pallas kernels. The least time
the chip could take for the traced paged-attention calls
(`benchmarks/attn_bytes.py`: the keys and values every attending
layer's mask lets every dispatched row read — the engine's
`attn_kv_tokens_read_total` over the traced steps x 5,120 B a token and
plane — over the HBM peak) over the device time of
`pallas:paged_attention*` in the trace, in percent; 0 where the trace
holds no such call. The counter and the trace cover the same engine
steps."""
from benchmarks import attn_bytes
from benchmarks.common import log


def read(trace, facts):
    attn = facts.get('attn')
    chips = list((trace.get('chips') or {}).values())
    if not attn or not chips:
        return None
    seconds = sum(v for c in chips for k, v in c['ops'].items()
                  if k.startswith('pallas:paged_attention')) / len(chips)
    tokens = attn['traced']['attn_kv_tokens_read_total']
    if not seconds or not tokens:
        return 0.0          # the kernel did not run in the traced steps
    least = attn_bytes.least_seconds(tokens, attn['kv_token_bytes'],
                                     facts['device_kind'])
    log(f'paged attention: {tokens} (key, layer) reads, least '
        f'{least * 1e3:.2f} ms (hbm-bound) of {seconds * 1e3:.2f} ms')
    return 100.0 * least / seconds
