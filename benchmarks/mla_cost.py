"""What latent attention (MLA; the Mosaic call `paged_attention_latent`)
has to move and to multiply, from counts, for its roofline share — and
the sizes of a latent-attention configuration from its published keys.

A token's cache is ONE row a layer, `[c_kv | k_pe]`: kv_lora_rank +
qk_rope_head_dim values in the pool's dtype (512 + 64 in bf16: 1,152 B).
The PUBLISHED row is what is priced, whatever the plane's layout pads it
to: a layout that copies more than that pays for it in the share.

In the absorbed form every (query, key) pair costs, per layer, one
multiply-add per head over the row (the score) and one over its value
lanes (the output): 2 x heads x (rank + rope + rank) operations (64 x
1,088 x 2 = 139,264). The up-projected form, which a chunk of many
queries could use instead, costs 2 x heads x (nope + rope + v) = 40,960
a pair, plus the up-projection of every key once a chunk.

A call's least time is the larger of its bytes over the HBM peak and its
operations over the bf16 peak. Decode rows (one query a row: as many
pairs as keys) and chunk rows (every key read once for all the chunk's
queries) are different calls of the kernel, so each group's least time
is taken apart and the two are added: the engine counts the keys read by
all rows and by chunk rows, and the pairs of all rows. LEFT OUT, which
can only lower the share: q, the output, the rest of a row's last page,
the softmax's own operations.
"""
from benchmarks import flops


def latent_row_bytes(cfg, itemsize=2):
    """One token's cached row in one layer, as published."""
    return (cfg['kv_lora_rank'] + cfg['qk_rope_head_dim']) * itemsize


def pair_flops(cfg, absorbed=True):
    """Operations of one (query, key) pair in one layer."""
    rank, rope = cfg['kv_lora_rank'], cfg['qk_rope_head_dim']
    if absorbed:
        per_head = rank + rope + rank
    else:
        per_head = cfg['qk_nope_head_dim'] + rope + cfg['v_head_dim']
    return 2 * cfg['num_attention_heads'] * per_head


def least_seconds(kv_tokens, kv_tokens_chunks, qk_pairs, row_bytes,
                  flops_a_pair, device_kind):
    """(seconds, {'decode': ('hbm'|'mxu', s), 'chunks': (...)}): the
    roofline of the traced calls. `kv_tokens`: keys read by all rows,
    summed over layers; `kv_tokens_chunks`: the chunk rows' part;
    `qk_pairs`: pairs of all rows (a decode row's pairs are its keys)."""
    peak = flops.peaks(device_kind)
    hbm, mxu = peak['hbm_gbps'] * 1e9, peak['bf16_tflops'] * 1e12
    decode_keys = kv_tokens - kv_tokens_chunks
    groups = {'decode': (decode_keys, decode_keys),
              'chunks': (kv_tokens_chunks, qk_pairs - decode_keys)}
    total, bound = 0.0, {}
    for name, (keys, pairs) in groups.items():
        by_bytes, by_ops = keys * row_bytes / hbm, pairs * flops_a_pair / mxu
        bound[name] = ('hbm', by_bytes) if by_bytes >= by_ops \
            else ('mxu', by_ops)
        total += bound[name][1]
    return total, bound


def weight_params(cfg):
    """Parameters ONE chip holds of the configuration as it is run
    (`num_layers`, `experts_held`, `vocab_held`), by part."""
    h = cfg['hidden_size']
    heads = cfg['num_attention_heads']
    nope, rope, v = (cfg['qk_nope_head_dim'], cfg['qk_rope_head_dim'],
                     cfg['v_head_dim'])
    rq, rkv = cfg['q_lora_rank'], cfg['kv_lora_rank']
    attention = h * rq + rq * heads * (nope + rope) + h * (rkv + rope) \
        + rkv * heads * (nope + v) + heads * v * h + rq + rkv
    expert = 3 * h * cfg['moe_intermediate_size']
    dense = cfg['first_k_dense_replace']
    sparse = cfg['num_layers'] - dense
    return {
        'embedding_and_head': 2 * cfg['vocab_held'] * h + h,
        'dense_layers': dense * (attention + 3 * h * cfg['intermediate_size']
                                 + 2 * h),
        'expert_layers': sparse * (
            attention + h * cfg['n_routed_experts'] + 2 * h
            + (cfg['n_shared_experts'] + cfg['experts_held'][1]) * expert)}


def weight_bytes(cfg, itemsize=2):
    return itemsize * sum(weight_params(cfg).values())


def cache_bytes_per_token(cfg, itemsize=2):
    """A token's published rows over the layers that are run."""
    return cfg['num_layers'] * latent_row_bytes(cfg, itemsize)
