"""``perf/joyai_flops.py`` by hand-counted cases, the configuration file
against the catalog's published keys, and the new readers on a program that
lacks what they read (the parent): nothing, and no error."""

import json
import os

from _bench import PERF, REPO, load

jf = load("joyai_flops.py")

TINY = {"hidden_size": 4, "num_attention_heads": 2, "q_lora_rank": 3,
        "kv_lora_rank": 2, "qk_nope_head_dim": 2, "qk_rope_head_dim": 1,
        "v_head_dim": 2, "intermediate_size": 8, "moe_intermediate_size": 2,
        "n_routed_experts": 4, "n_shared_experts": 1, "layers_kept": 3,
        "first_k_dense_replace": 1, "num_nextn_predict_layers": 1,
        "published": {"n_routed_experts": 16}}


def test_attention_and_expert_parameters_by_hand():
    # q: 4*3 + 3*2*(2+1) = 30; kv down 4*(2+1) = 12; kv up 2*2*(2+2) = 16;
    # output 2*2*4 = 16
    assert jf.attention_params(TINY) == 30 + 12 + 16 + 16 == 74
    assert jf.expert_params(TINY) == 3 * 4 * 2 == 24


def test_fixed_parameters_count_the_head_twice_and_the_router_whole():
    # 4 blocks of attention (3 layers + MTP), one dense MLP 3*4*8, three
    # expert layers (2 + MTP) each a 4x16 router and one shared expert,
    # the MTP joining projection 2*4*4, the head twice over 10 rows
    want = 4 * 74 + 96 + 3 * (4 * 16 + 24) + 32 + 2 * 10 * 4
    assert jf.fixed_matmul_params(TINY, vocab_rows=10) == want == 768


def test_attention_cost_halves_the_square_and_counts_both_widths():
    ops, nbytes = jf.attention_train_cost(batch=2, seq=8, layers=4, c=TINY)
    # six matmuls: three 3 wide (QK^T, dQ, dK), three 2 wide (PV, dV, dP)
    assert ops == 2 * 2 * 2 * 8 * 8 * 3 * (3 + 2) * 0.5 * 4 == 15360
    # q, k twice and dq, dk (3 wide); v, o twice, do, dv (2 wide); bf16
    assert nbytes == 2 * 2 * 8 * 6 * (3 + 2) * 2 * 4 == 7680


def test_step_flops_follow_the_counted_rows():
    base = jf.train_step_flops(TINY, 10, batch=2, seq=8,
                               rows_on_held_experts=0)
    attn, _ = jf.attention_train_cost(2, 8, 4, TINY)
    assert base == 6 * 768 * 16 + attn
    more = jf.train_step_flops(TINY, 10, 2, 8, rows_on_held_experts=5)
    assert more - base == 6 * 24 * 5
    ops, nbytes = jf.experts_train_cost(TINY, 5, expert_layers=3)
    assert ops == 6 * 24 * 5
    assert nbytes == 4 * 24 * 2 * 3 * 3 + (4 + 4 + 2 + 4) * 2 * 3 * 5


def test_the_cells_count_is_the_issues():
    with open(os.path.join(PERF, "configs", "joyai_llm_flash.json")) as f:
        cfg = json.load(f)
    assert jf.attention_params(cfg) == 26_345_472
    # 8192 tokens, half an assignment a token a layer over five expert
    # layers: 21.7 TFLOP a step (ISSUE 27's count)
    flops = jf.train_step_flops(cfg, 16256, 2, 4096, 5 * 4096)
    assert 21.5e12 < flops < 21.9e12


def test_the_file_keeps_every_published_key():
    with open(os.path.join(PERF, "configs", "joyai_llm_flash.json")) as f:
        cfg = json.load(f)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "JoyAI-LLM-Flash")
        assert cfg["source"] == row["source_url"]
        changed = {k for k, v in row["config"].items() if cfg.get(k) != v}
        assert changed == {"n_routed_experts", "vocab_size"}
        assert all(cfg["published"][k] == row["config"][k] for k in changed)
    assert cfg["reduced"] == ["n_routed_experts", "vocab_size",
                              "layers_kept"]
    assert cfg["num_hidden_layers"] == cfg["published"][
        "num_hidden_layers"] == 40 and cfg["layers_kept"] == 5
    for key in ("mtp_loss_coeff", "router_bias", "init",
                "padded_vocab_size"):
        assert key in cfg["assumed"]
    assert "16 chips" in cfg["deployment"]


class _Ctx:
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    counters = {}
    cell = {"global_batch": 2, "seq_len": 8}

    def __init__(self, config, reduction):
        self.config, self.reduction = config, reduction


def test_readers_find_nothing_on_a_program_without_their_kernels():
    r = {"kernel_seconds": {"rms_fwd": 1.0}, "kernel_counts": {"rms_fwd": 3},
         "steps": 2.0, "chips": 1}
    for name in ("mla_attn_roofline", "moe_experts_roofline",
                 "moe_load_max_over_mean.train"):
        reader = load(f"layer_metrics/{name}.py")
        assert reader.read(_Ctx(TINY, r)) is None
        assert reader.read(_Ctx({"n_layer": 2}, None)) is None


def test_readers_read_the_kernels_by_the_programs_names():
    """The flash kernels by the ``flash_...`` name in their
    ``kernel_metadata``, the grouped matmuls by their instruction names;
    steps are the runs of the step's program."""
    r = {"kernel_seconds": {"flash_fwd": 0.5e-6, "flash_bwd_dq": 0.5e-6,
                            "flash_bwd_dkv": 1e-6, "mla_rope": 7.0,
                            "gmm": 1e-6, "tgmm": 1e-6},
         "steps": 2.0, "chips": 1}
    ctx = _Ctx(TINY, r)
    ops, nbytes = jf.attention_train_cost(2, 8, 4, TINY)
    least = max(ops / 1e12, nbytes / 1e11)
    got = load("layer_metrics/mla_attn_roofline.py").read(ctx)
    assert abs(got - 100 * least * 2 / 2e-6) < 1e-9 * got
    ctx.counters = {"moe_rows_here_per_step": 5,
                    "moe_load_max_over_mean": 1.25}
    ops, nbytes = jf.experts_train_cost(TINY, 5, expert_layers=3)
    least = max(ops / 1e12, nbytes / 1e11)
    got = load("layer_metrics/moe_experts_roofline.py").read(ctx)
    assert abs(got - 100 * least * 2 / 2e-6) < 1e-9 * got
    assert load("layer_metrics/moe_load_max_over_mean.train.py").read(
        ctx) == 1.25
