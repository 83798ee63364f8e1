"""What the grouped matmul of a sparse-expert layer (Mosaic call
`moe_grouped_matmul`) has to do, from counts: the bytes it must read
and the operations it must perform, for its roofline share.

The kernel reads an expert's weights once per call if any row chose
the expert and not at all otherwise, so the bytes that MUST move are
(experts touched, summed over the calls) x (one expert's three
matrices); the rows' own traffic (each row in and out twice, a few KB)
is left out, which can only lower the share. The operations are 2 per
multiply-add over the three matrices for every routed row. The least
time is the larger of bytes / HBM peak and operations / bf16 peak; on a
v5e the bytes bound every shape the server runs (a decode step's 512
rows touch ~125 experts: 1.57 GB against 6.4 GFLOP a layer).
"""
from benchmarks import flops


def expert_weight_bytes(hidden, width, itemsize=2):
    """One expert's W1, W3 [hidden, width] and W2 [width, hidden]."""
    return 3 * hidden * width * itemsize


def least_seconds(experts_touched, rows, weight_bytes, device_kind,
                  itemsize=2):
    """(seconds, 'hbm' or 'mxu'): the roofline of the calls that
    touched `experts_touched` experts (summed over calls) with `rows`
    routed rows, each expert's weights `weight_bytes` bytes."""
    peak = flops.peaks(device_kind)
    by_bytes = experts_touched * weight_bytes / (peak['hbm_gbps'] * 1e9)
    by_ops = rows * 2 * (weight_bytes // itemsize) \
        / (peak['bf16_tflops'] * 1e12)
    return (by_bytes, 'hbm') if by_bytes >= by_ops else (by_ops, 'mxu')
