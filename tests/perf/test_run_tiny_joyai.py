"""The JoyAI-LLM-Flash driver through ``perf/run.py``'s own code at a tiny
fixture configuration on the CPU (the model's every mechanism at width 64),
and the proofs that its output check can fail: the fp8 control, and the two
faults this model adds planted in the program itself - the routed experts'
part left out, the multi-token-prediction term left out.

Nothing here is a device number: the result line says ``cpu``.
"""

import io
import json
import time

import pytest

from _bench import e2e, fixture_root, load

run = load("run.py", name="perf_test_run_joyai")
LIMIT_S = 120.0

JOYAI_TINY = {
    "name": "joyai_tiny", "model_type": "joyai_llm_flash",
    "attention_bias": False, "first_k_dense_replace": 1, "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 96, "kv_lora_rank": 32,
    "moe_intermediate_size": 32, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 4, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts_per_tok": 4,
    "num_hidden_layers": 12, "num_nextn_predict_layers": 1,
    "q_lora_rank": 48, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "rms_norm_eps": 1e-6, "rope_interleave": True, "rope_scaling": None,
    "rope_theta": 32000000, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 16, "vocab_size": 120,
    "layers_kept": 3,
    "published": {"n_routed_experts": 16, "vocab_size": 960,
                  "num_hidden_layers": 12},
    "assumed": {"padded_vocab_size": 128, "first_expert": 4,
                "mtp_loss_coeff": 0.3, "router_bias_update_speed": 0.001},
}

PRETRAIN_TINY = {
    "config": "joyai_tiny", "driver": "joyai_pretrain", "chips": 1,
    "seq_len": 32, "micro_batch": 2, "global_batch": 2,
    "corpus_samples": 12, "spans": ["fetch_batch", "step", "fetch_loss"],
    "trace_seconds": 0.3,
    # set as the cell's are (PERF.md 2), from this fixture's own readings on
    # the CPU over four seeds: the bf16 program reads at most 0.020 / 0.017 /
    # 0.00047 / 0.0066, the fp8 control at least 0.026 / 0.0065 / 0.0019 /
    # 0.012 (seed 3, the study's: 0.034 / 0.0065 / 0.0019 / 0.012); a
    # left-out part reads 1
    "limits": {"grad_norm_gap": 0.03, "update_norm_gap": 0.1,
               "grad_norm_gap_median": 0.001,
               "routing_mismatch_share": 0.01},
}
TRAIN_METRICS = [e2e("setup_s", "s"), e2e("train_step_ms", "ms")]


def _run(root, seed=2**31 + 7):
    t0 = time.perf_counter()
    out = io.StringIO()
    line = run.run_cell(root, "joyai_tiny.pretrain", seed, 0.3, 0,
                        allow_cpu=True, out=out)
    assert time.perf_counter() - t0 < LIMIT_S
    last = out.getvalue().strip().splitlines()[-1]
    assert json.loads(last) == json.loads(json.dumps(line))
    return line


@pytest.fixture
def root(tmp_path):
    return fixture_root(tmp_path, {"joyai_tiny.pretrain": PRETRAIN_TINY},
                        {"joyai_tiny": JOYAI_TINY}, TRAIN_METRICS)


def test_the_cell_runs_and_is_correct(root):
    line = _run(root)
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    names = [c["name"] for c in line["compared"]]
    assert names == ["grad_norm_gap", "update_norm_gap",
                     "grad_norm_gap_median", "routing_mismatch_share",
                     "moe_dropped_assignments",
                     "skipped_updates", "compiles_in_window"]
    assert set(line["metrics"]) == {"setup_s", "train_step_ms"}


def _break_model(monkeypatch, how):
    """Plant a fault in the program's own model, under the driver."""
    if how == "no_routed_experts":
        from apex_tpu.transformer import moe

        real = moe._expert_rows

        def shared_only(*a, **k):
            y, *rest = real(*a, **k)
            return (0.0 * y, *rest)

        monkeypatch.setattr(moe, "_expert_rows", shared_only)
    else:
        from apex_tpu.models import gpt

        real = gpt.gpt_mtp_loss_fn
        monkeypatch.setattr(
            gpt, "gpt_mtp_loss_fn",
            lambda losses, mtp, coeff: real(losses, mtp, 0.0))
        from apex_tpu import models

        monkeypatch.setattr(models, "gpt_mtp_loss_fn", gpt.gpt_mtp_loss_fn)


@pytest.mark.parametrize("how", ["no_routed_experts", "no_mtp_term"])
def test_a_left_out_part_reads_incorrect(root, monkeypatch, how):
    _break_model(monkeypatch, how)
    line = _run(root)
    assert line["correct"] is False
    over = {c["name"] for c in line["compared"] if c["value"] > c["limit"]}
    assert "grad_norm_gap" in over, line["compared"]


def test_study_fails_the_control_and_every_fault(root):
    """perf/study.py's readings through the comparison a run uses: the
    program reads correct; the fp8 control and the four planted faults (the
    reference in the program's place) do not."""
    t0 = time.perf_counter()
    compare = load("compare.py")
    drv = run.load_module(root, "drivers", "joyai_pretrain")
    ctx = run.Context(root, PRETRAIN_TINY, JOYAI_TINY, 0, 0.0, 0,
                      {"platform": "cpu"}, None)
    verdicts = {kind: compare.correct(compared) for kind, _, compared, _
                in drv.study(PRETRAIN_TINY, JOYAI_TINY, [3], ctx, controls=1)}
    assert verdicts == {
        "program": True, "control_fp8": False, "fault_half_batch": False,
        "fault_no_routed_experts": False, "fault_no_mtp_term": False,
        "fault_state_unchanged": False}
    assert time.perf_counter() - t0 < 2 * LIMIT_S
