"""perf/flops.py against hand-worked numbers."""

import pytest

from _bench import load

flops = load("flops.py")


def test_gpt2_345m_matmul_params():
    # 24 layers x 12 h^2 (QKV 3h^2, projection h^2, MLP 8h^2) + 50304 x 1024
    assert flops.gpt_matmul_params(24, 1024, 50304) == (
        24 * 12 * 1024 * 1024 + 50304 * 1024)
    assert flops.gpt_matmul_params(24, 1024, 50304) == pytest.approx(
        353.5e6, rel=1e-3)


@pytest.mark.parametrize("causal,expect", [(True, 2.272e9), (False, 2.423e9)])
def test_gpt2_345m_train_flops_per_token(causal, expect):
    # 6 x 353.5 M = 2.121 G, plus attention: forward 4 * keys * h a layer,
    # three times that with the backward. Dense: 3*24*4*1024*1024 = 0.302 G;
    # causal counts (1024+1)/2 keys: 0.151 G
    got = flops.gpt_train_flops_per_token(24, 1024, 50304, 1024,
                                          causal=causal)
    assert got == pytest.approx(expect, rel=1e-3)
    by_hand = 6 * 353501184 + 3 * 24 * 4 * 1024 * (
        512.5 if causal else 1024)
    assert got == pytest.approx(by_hand, rel=1e-12)


def test_attention_train_cost():
    ops, nbytes = flops.attention_train_cost(4, 16, 1024, 64, layers=1)
    # six matmuls of 2*b*h*s*s*d, half of each under the causal mask
    assert ops == 6 * 2 * 4 * 16 * 1024 * 1024 * 64 / 2
    # twelve (b, h, s, d) bf16 tensors cross HBM
    assert nbytes == 12 * 4 * 16 * 1024 * 64 * 2
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = flops.roofline_seconds(ops, nbytes, peaks)
    assert bound == "compute" and t == pytest.approx(ops / 197e12)
    t, bound = flops.roofline_seconds(1.0, 819e9, peaks)
    assert bound == "memory" and t == pytest.approx(1.0)


def test_unknown_device_kind_raises():
    assert flops.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert flops.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="not in perf/peaks.json"):
        flops.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        flops.peaks_for("cpu")
