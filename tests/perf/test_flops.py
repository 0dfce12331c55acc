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


PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.mark.parametrize("d_qk,d_v", [(64, 64), (192, 128)])
def test_the_three_flash_kernels_share_the_six_matmuls(d_qk, d_v):
    costs = flops.flash_kernel_costs(4, 16, 1024, d_qk, d_v, layers=3)
    assert set(costs) == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
    assert flops.FLASH_KERNELS == "flash_*"
    # each kernel two of the six matmuls, one d_qk and one d_v wide, halved
    # under the causal mask
    for ops, _ in costs.values():
        assert ops == 2 * 4 * 16 * 1024 * 1024 * (d_qk + d_v) / 2 * 3
    lane = 4 * 16 * 1024 * 2 * 3  # one bf16 lane of every row, in bytes
    assert costs["flash_fwd"][1] == lane * (2 * d_qk + 2 * d_v)
    assert costs["flash_bwd_dq"][1] == lane * (3 * d_qk + 2 * d_v)
    assert costs["flash_bwd_dkv"][1] == lane * (3 * d_qk + 3 * d_v)
    if d_qk == d_v:
        whole, _ = flops.attention_train_cost(4, 16, 1024, d_qk, layers=3)
    else:
        joyai = load("joyai_flops.py")
        whole, _ = joyai.attention_train_cost(4, 1024, 3, {
            "num_attention_heads": 16, "qk_nope_head_dim": 128,
            "qk_rope_head_dim": 64, "v_head_dim": 128})
    assert sum(ops for ops, _ in costs.values()) == whole


def test_which_bound_sets_a_flash_kernels_least_time():
    # GPT-2's heads at sequence 1024: the forward has 256 operations a byte
    # against the chip's 240, dq 205 and dk/dv 171
    costs = flops.flash_kernel_costs(8, 16, 1024, 64, 64, layers=24)
    bounds = {k: flops.roofline_seconds(*c, PEAKS)[1]
              for k, c in costs.items()}
    assert bounds == {"flash_fwd": "compute", "flash_bwd_dq": "memory",
                      "flash_bwd_dkv": "memory"}
    # latent attention at sequence 4096: compute, all three
    costs = flops.flash_kernel_costs(2, 32, 4096, 192, 128, layers=6)
    assert {flops.roofline_seconds(*c, PEAKS)[1]
            for c in costs.values()} == {"compute"}


def test_attention_shape_reads_either_familys_keys():
    cell = {"global_batch": 8, "seq_len": 1024}
    assert flops.attention_shape(
        {"n_layer": 24, "n_head": 16, "n_embd": 1024}, cell) == (
            8, 16, 1024, 64, 64, 24)
    assert flops.attention_shape(
        {"num_attention_heads": 32, "qk_nope_head_dim": 128,
         "qk_rope_head_dim": 64, "v_head_dim": 128, "layers_kept": 5,
         "num_nextn_predict_layers": 1}, cell) == (8, 32, 1024, 192, 128, 6)
    assert flops.attention_shape({"hidden_size": 64}, cell) is None


def test_roofline_share_is_least_time_over_booked_time():
    r = {"kernel_seconds": {"flash_fwd": 2.0, "flash_bwd": 6.0,
                            "mla_rope": 9.0, "gmm": 1.0, "tgmm": 3.0},
         "steps": 4.0}
    # 1 s of operations a step, 4 steps, 8 s booked to the flash kernels
    assert flops.roofline_share(r, "flash_*", 197e12, 1.0, PEAKS) == (
        pytest.approx(50.0))
    assert flops.roofline_share(r, "flash_fwd", 197e12, 1.0, PEAKS) == (
        pytest.approx(200.0))
    assert flops.roofline_share(r, "*gmm", 197e12, 1.0, PEAKS) == (
        pytest.approx(100.0))
    assert flops.roofline_share(r, "rms_fwd", 197e12, 1.0, PEAKS) is None
    assert flops.roofline_share(dict(r, steps=0.0), "gmm", 197e12, 1.0,
                                PEAKS) is None
    assert flops.roofline_share(r, "gmm", 197e12, 1.0, None) is None
    assert flops.roofline_share(None, "gmm", 197e12, 1.0, PEAKS) is None
