"""Registered in-graph metric taps (``sow("intermediates", name, ...)``).

Every tap name sown anywhere under ``apex_tpu/`` MUST have a row here —
a tier-1 lint test (tests/test_monitor.py) greps the source for sow
calls and fails on unregistered names. The point is drift protection:
metric taps die silently (a refactor renames a module, the sow vanishes,
dashboards flatline weeks later); a registry the lint enforces turns
that into a test failure at the PR that caused it.

Reading taps: ``model.apply(..., mutable=["intermediates"])`` then
``monitor.taps_from_intermediates(...)`` to flatten the collection into
``{name: scalar}`` ready for a :class:`~apex_tpu.monitor.MetricBag`.
"""

#: tap name -> (where it is sown, what the value means)
REGISTERED_TAPS = {
    "moe_aux_loss": (
        "transformer/layer.py ParallelTransformerLayer (MoE branch): the "
        "load-balancing auxiliary loss of each MoE layer, BEFORE the "
        "moe_aux_loss_coeff weighting"
    ),
    "layer_out_rms": (
        "transformer/layer.py ParallelTransformerLayer (when "
        "TransformerConfig.collect_layer_metrics): fp32 RMS of the "
        "layer's output hidden states — the per-layer activation-scale "
        "series that makes divergence onsets attributable to a depth. "
        "Consumed per-step by the replay flight recorder "
        "(training/gpt_step.py stacks the sows into a (layers,) "
        "vector, cross-rank-aggregated) so the divergence bisector can "
        "localize a corruption to the first divergent layer"
    ),
    "moe_chosen": (
        "transformer/moe.py MoEMLP: the experts each token chose, (tokens, "
        "top_k) int32 over ALL the model's experts, held here or not — what "
        "the benchmark's output check compares with the reference's choices "
        "(perf/drivers/joyai_pretrain.py)"
    ),
    "moe_load": (
        "transformer/moe.py MoEMLP: rows each HELD expert took this call, "
        "(experts held,) int32. training/gpt_step.py folds the "
        "layers' loads into the step's MetricBag (moe_rows_here, "
        "moe_load_max, moe_load_mean, moe_load_max_over_mean)"
    ),
    "moe_dropped": (
        "transformer/moe.py MoEMLP: assignments the capacity rule cut this "
        "call (0 whenever moe_capacity_factor is None). The MetricBag's "
        "moe_dropped; the benchmark holds it to 0"
    ),
    "moe_compact": (
        "transformer/moe.py MoEMLP: whether this call's rows went through "
        "the short sorted buffer (bool; False on the worst-case fallback and "
        "in a layer that holds all its experts). The MetricBag's "
        "moe_compact_share is its mean over the expert layers and microbatches"
    ),
}

__all__ = ["REGISTERED_TAPS"]
