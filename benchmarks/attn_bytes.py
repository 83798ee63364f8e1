"""What the paged attention kernel (Mosaic calls `paged_attention*`) has
to move, from counts, for its roofline share.

For every attending layer and every row of a dispatch the kernel must
copy the keys and values the layer's mask lets the row read: the
engine's `attn_kv_tokens_read_total` counts those (a full layer the
row's context, a window layer its window through the row's queries), and
a token of one plane is K and V of kv heads x head_dim in the pool's
dtype. LEFT OUT, which can only lower the share: the rest of the first
and last 16-token page of a row, q and the output, and the products
(the decode call is bound by its page copies, PERF.md section 5).
"""
from benchmarks import flops


def kv_token_bytes(num_kv_heads, head_dim, itemsize=2):
    """K and V of one token in one plane."""
    return 2 * num_kv_heads * head_dim * itemsize


def least_seconds(kv_tokens, token_bytes, device_kind):
    return kv_tokens * token_bytes \
        / (flops.peaks(device_kind)['hbm_gbps'] * 1e9)
