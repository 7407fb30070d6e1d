"""shapes.py against numbers worked by hand."""

import pytest

from benchmark.reduce import shapes


def test_gpt2_medium_parameter_count_by_hand():
    # block: qkv 3*1024*1024 + 3*1024 = 3,148,800; out 1024*1024 + 1024 = 1,049,600;
    # up 1024*4096 + 4096 = 4,198,400; down 4096*1024 + 1024 = 4,195,328;
    # two layer norms 4*1024 = 4,096  -> 12,596,224 a block, x24 = 302,309,376
    # wte 50257*1024 = 51,463,168; wpe 1024*1024 = 1,048,576; ln_f 2,048
    assert shapes.gpt2_param_count(24, 1024, 4096, 50257, 1024) == 354_823_168


def test_train_flops_per_token_by_hand():
    n = 354_823_168
    # 6N = 2,128,939,008; attention 12 * 24 * 1024 * 1024 = 301,989,888
    assert shapes.train_flops_per_token(n, 24, 1024, 1024) == 2_430_928_896


def test_flash_causal_flops_one_call_by_hand():
    # one head, T=1024, D=64: forward 2*T*T*D = 134,217,728 (two matmuls of
    # 2*T*T*D, halved by the causal mask); backward twice that.
    assert shapes.flash_causal_flops(1, 1, 1024, 64, backward=False) == 134_217_728
    assert shapes.flash_causal_flops(1, 1, 1024, 64) == 3 * 134_217_728
    assert shapes.flash_causal_flops(16, 16, 1024, 64) == 256 * 3 * 134_217_728


def test_paged_decode_bytes_one_step_by_hand():
    # 36 slots at 400 tokens of context: 14,400 tokens x 16 heads x 64 x (K,V) x 2 B x 24 layers
    assert shapes.paged_decode_kv_bytes(14_400, 16, 64, 24) == 14_400 * 16 * 64 * 2 * 2 * 24
    assert shapes.paged_decode_kv_bytes(14_400, 16, 64, 24) == 1_415_577_600   # ~1.4 GB a step


def test_unknown_device_is_an_error():
    assert shapes.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        shapes.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        shapes.peaks("_source")
