"""benchmarks/flops.py and peaks.json: required operations per token and
the chip's peaks."""
import pytest

import benchtoy
from benchmarks import flops

MANIFEST = benchtoy.manifest()
GPT = MANIFEST.config(MANIFEST.cell('gpt3-1.3b.pretrain-2k'))
BERT = MANIFEST.config(MANIFEST.cell('bert-large.pretrain-512'))


def test_gpt_counts_causal_attention_once():
    L, H, S, V = 24, 2048, 2048, 50304
    n = L * 12 * H * H + V * H          # parameters that meet a matmul
    assert flops.matmul_params(GPT) == n
    assert flops.train_flops_per_token(GPT, S) == 6 * n + 6 * L * H * S
    # not bench.py's 12*L*H*S, which counts the masked half too
    assert flops.train_flops_per_token(GPT, S) < 6 * n + 12 * L * H * S
    assert flops.train_flops_per_token(GPT, S) / 1e9 == pytest.approx(
        8.47, abs=0.01)


def test_bert_counts_full_attention():
    L, H, F, S, V = 24, 1024, 4096, 512, 30522
    n = L * (4 * H * H + 2 * H * F) + V * H + H * H
    assert flops.matmul_params(BERT) == n
    assert flops.train_flops_per_token(BERT, S) == 6 * n + 12 * L * H * S


def test_recompute_is_not_counted():
    # the GPT cell runs full remat; the count is the same without it: it
    # is 3 passes (forward, two backward) of 2 FLOP per parameter
    per_param = (flops.train_flops_per_token(GPT, 0)
                 / flops.matmul_params(GPT))
    assert per_param == 6


def test_peaks_are_keyed_by_the_exact_device_kind():
    p = flops.peaks('TPU v5 lite')
    assert (p['bf16_tflops'], p['hbm_gbps'], p['hbm_gb']) == (197.0, 819.0,
                                                              16.0)
    assert 'Google Cloud' in p['source']
    for kind in ('TPU v5', 'tpu v5 lite', 'TPU v5 lite ', 'cpu', ''):
        with pytest.raises(KeyError):
            flops.peaks(kind)


def test_mfu_is_required_flops_over_the_peak_of_the_chips_used():
    rate = 11600.0
    one = flops.mfu(GPT, 2048, rate, 'TPU v5 lite')
    assert one == pytest.approx(
        flops.train_flops_per_token(GPT, 2048) * rate / 197e12)
    assert 0.49 < one < 0.51
    assert flops.mfu(GPT, 2048, rate, 'TPU v5 lite', chips=4) == \
        pytest.approx(one / 4)
    with pytest.raises(KeyError):
        flops.mfu(GPT, 2048, rate, 'cpu')
