"""Operations a configuration REQUIRES per trained token, and the chip's
peaks. Required means the forward and backward pass of the published
mathematics: recomputation (remat) is not counted, and causal attention
counts the unmasked half once — the kernel skips the rest, so counting
it (as `bench.py` and `core/ledger.py` do, `12*L*H*S`) flatters the MFU.

A matmul of an [n, k] weight costs 2*k*n per token forward and twice
that backward: 6 per parameter that meets a matmul. Embedding lookups,
LayerNorm and biases are not matmuls and are left out (under 0.1 %).
"""
import json
import os


def peaks(device_kind):
    """The row of peaks.json for exactly this `device_kind`. A device
    that is not in the table is an error, never a default."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           'peaks.json')) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f'no peaks known for device_kind {device_kind!r}: '
                       f'add it to benchmarks/peaks.json with its source '
                       f'(known: {sorted(table)})')
    return table[device_kind]


def matmul_params(config):
    """Parameters that meet a matmul once per token."""
    h, layers = config['hidden_size'], config['num_layers']
    ffn, vocab = config['ffn_hidden_size'], config['vocab_size']
    block = 4 * h * h + 2 * h * ffn          # qkv + out, fc1 + fc2
    n = layers * block + vocab * h           # + the (tied or not) head
    if config['architecture'] == 'bert':
        n += h * h                           # the MLM transform
    return n


def train_flops_per_token(config, seq_len):
    """6*N + attention. Attention per layer and token, forward: QK^T and
    PV are 2*S*H each over S keys; backward twice that: 12*S*H in full,
    6*S*H where a causal mask leaves half."""
    h, layers = config['hidden_size'], config['num_layers']
    attention = {'gpt': 6, 'bert': 12}[config['architecture']]
    return 6 * matmul_params(config) + attention * layers * h * seq_len


def mfu(config, seq_len, tokens_per_s, device_kind, chips=1):
    """Required FLOP/s over the bf16 peak of the chips used."""
    return (train_flops_per_token(config, seq_len) * tokens_per_s
            / (peaks(device_kind)['bf16_tflops'] * 1e12 * chips))
