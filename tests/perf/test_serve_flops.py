"""perf/serve_flops.py against counts worked by hand."""

import pytest

from _bench import load

flops = load("flops.py")
sf = load("serve_flops.py")

L, H, V = 24, 1024, 50304
BLOCKS = L * 12 * H * H      # matmul parameters of the blocks
HEAD = V * H


def test_prefill_counts_the_blocks_for_every_token_and_the_head_once():
    want = 2 * BLOCKS * 128 + 2 * HEAD + L * 4 * H * (128 * 129 / 2)
    assert sf.gpt_prefill_flops(L, H, V, 128) == pytest.approx(want)


def test_decode_counts_one_token_against_its_cache():
    want = 2 * (BLOCKS + HEAD) + L * 4 * H * 300
    assert sf.gpt_decode_flops(L, H, V, 300) == pytest.approx(want)


@pytest.mark.parametrize("prompt,out", [(16, 1), (128, 64), (768, 256)])
def test_a_request_is_its_prefill_and_a_decode_step_a_further_token(
        prompt, out):
    want = sf.gpt_prefill_flops(L, H, V, prompt) + sum(
        sf.gpt_decode_flops(L, H, V, prompt + k - 1)
        for k in range(2, out + 1))
    assert sf.gpt_request_flops(L, H, V, prompt, out) == pytest.approx(want)


def test_no_token_no_operations():
    assert sf.gpt_request_flops(L, H, V, 128, 0) == 0.0


def test_a_mean_request_of_the_chat_mix_is_about_156_gflop():
    """PERF.md quotes it: 2 x 355M parameters x (162 + 76) tokens and a few
    percent of attention."""
    got = sf.gpt_request_flops(L, H, V, 165, 77)
    assert got == pytest.approx(156.4e9, rel=0.01)
    assert got < 2.0 * flops.gpt_matmul_params(L, H, V) * (165 + 77) * 1.05
