"""Test configuration: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's test topology (DistributedTestBase spawns
world_size<=4 single-node processes; apex/transformer/testing/
distributed_test_base.py:36-38) — here a single JAX process with 8 virtual
CPU devices exercises every mesh/collective path, and Pallas kernels run in
interpret mode. The compiled-HLO analysis passes (donation, the hlo-comms
differ, hlo-sharding) compile against this same virtual topology — their
``replica_groups``/sharding assertions hold digit-for-digit with no TPU
attached, which is what keeps the analysis self-check tier-1.
"""

import os

# Must be set before jax initializes its backends. Force-override: the outer
# environment may point JAX_PLATFORMS at a real TPU — tests always run on
# the virtual CPU mesh (the chip is reached through chip_smoke.py only).
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", False)

import pytest  # noqa: E402

# Slow tier (measured >=8 s each on the CPU mesh, ~430 s of the ~750 s
# suite): excluded from the smoke run. Central list instead of per-file
# decorators so the tier stays auditable in one place.
#   smoke: python -m pytest tests/ -q -m "not slow"   (~5 min serial)
#   fast:  python -m pytest tests/ -q -m "not slow" -n 4
#   full:  python -m pytest tests/ -q
_SLOW_TESTS = {
    "test_fwd_bwd_pre_post_checked_matches_unchecked",
    "test_gpt_pp_tp_sp_full_step_checked",
    "test_amp_mlp_example",
    "test_imagenet_example",
    "test_long_context_ring_cp_example",
    "test_gpt_cp_tp_sp_matches_tp_only",
    "test_pp_cp_tp_loss_matches_cp_disabled",
    "test_zero_dp_inside_pp_mesh_trains",
    "test_gpt_pretrain_example",
    "test_gpt_pretrain_resume",
    "test_gpt_pretrain_chaos",
    "test_gpt_compression_parity",
    "test_gpt_compression_resume_migration",
    "test_elastic_selftest_gate",
    "test_replay_selftest_gate",
    "test_serving_selftest_gate",
    "test_remediation_selftest_gate",
    "test_remediation_campaign",
    "test_gpt_remediation_acceptance_drill",
    "test_serving_wedged_decode_bundle",
    "test_serving_overload_drill",
    "test_serving_cancel_and_drain_hardening",
    "test_fleet_selftest_gate",
    "test_fleet_chaos_drill",
    "test_cross_process_determinism",
    "test_gpt_replay_bitflip_drill",
    "test_gpt_elastic_chaos_drill",
    "test_gpt_preemption_skip_budget",
    "test_gpt_hang_incident_drill",
    "test_gpt_slow_host_stall_drill",
    "test_crash_mid_fingerprint_leaves_unverified_dir",
    # subprocess pins: each child pays a fresh jax import (~10 s)
    "test_sigterm_mid_finalize_still_commits",
    "test_kill_mid_async_save_leaves_clean_torn_dir",
    "test_gpt_pretrain_xray",
    "test_gpt_pretrain_profile_analyze",
    "test_analysis_cli_subprocess",
    "test_gpt_pp_target_zero_comms_suppressions",
    "test_sparsity_example",
    "test_llama_finetune_example",
    "test_post_params_stay_replicated_under_sp",
    "test_matches_sequential_composition",
    "test_zero_bubble_matches_fused_pre_post",
    "test_bert_sp_loss_and_grads_match_non_sp",
    "test_tp8_loss_decreases",
    "test_selective_remat_matches_plain",
    "test_tp8_sequence_parallel_loss_decreases",
    "test_loss_decreases",
    "test_gradients_flow_through_halo",
    "test_layer_with_moe_mlp",
    "test_sp_matches_non_sp",
    "test_forward_shapes",
    "test_forward_shape_and_dtype",
    "test_train_updates_batch_stats_and_loss_decreases",
    "test_ep_matches_local",
    "test_pp_tp_sp_training_converges",
    "test_llama_style_pp_tp_sp_training_converges",
    "test_syncbn_dp_matches_single_device_global_batch",
    "test_matches_unsharded",
    "test_gpt_ring_cp_matches_single_device",
    "test_inner_blocking_matches",
    "test_grad_flows",
    "test_remat_matches_plain",
}


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: >=8s on the CPU mesh; excluded by -m 'not slow'"
    )
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection / recovery-path tests (tier-1 unless also slow)",
    )


_COLLECT_ERRORS = False


def pytest_collectreport(report):
    # a module that fails to import must not nuke the whole run through the
    # stale-_SLOW_TESTS guard below: its slow tests are legitimately absent
    global _COLLECT_ERRORS
    if report.failed:
        _COLLECT_ERRORS = True


def pytest_collection_modifyitems(config, items):
    seen = set()
    for item in items:
        if item.originalname in _SLOW_TESTS or item.name in _SLOW_TESTS:
            item.add_marker(pytest.mark.slow)
            seen.add(item.originalname if item.originalname in _SLOW_TESTS
                     else item.name)
    # name-keyed tiers rot silently: a renamed slow test would drop back
    # into the smoke run with no signal. Fail on stale entries, but only
    # when the FULL suite was collected — any subsetting (node ids, file
    # paths, --ignore, --deselect, -k) legitimately hides entries.
    inv = [str(a) for a in config.invocation_params.args]
    subsetting = any(
        "::" in a or a.endswith(".py") or a.startswith(("-k", "--ignore", "--deselect"))
        for a in inv
    )
    if not subsetting and not _COLLECT_ERRORS:
        stale = _SLOW_TESTS - seen
        if stale:
            raise pytest.UsageError(
                f"_SLOW_TESTS entries matched no collected test (renamed or "
                f"removed?): {sorted(stale)}"
            )


@pytest.fixture
def rng():
    return jax.random.PRNGKey(0)


@pytest.fixture(autouse=True)
def _reset_parallel_state():
    yield
    try:
        from apex_tpu.parallel import parallel_state

        parallel_state.destroy_model_parallel()
    except Exception:
        pass
