"""Deterministic replay & divergence forensics.

The observe→diagnose half of the auto-repair loop (ROADMAP item 3):
when the sentinel or the fleet detector says "something corrupted",
this package answers *which step* and *which leaf* — mechanically,
from the journal and the checkpoints, with no human staring at metrics
jsonl. Three pieces (docs/resilience.md "Replay & forensics"):

- ``journal``  — the flight recorder: per-step nondeterminism inputs
  (batch ids + content crc, chaos arms, lr_scale) and output
  fingerprints (loss, verdict, per-layer layer_out_rms),
  ``kind="journal"`` records through the MetricRouter plus a
  checkpoint-anchored sidecar jsonl; anchors at every verified
  checkpoint reuse the integrity manifest's per-leaf crc32 as the
  state fingerprint. jax-free.
- ``replayer`` — checkpoint-anchored re-execution: rebuild the EXACT
  step from the journal header's target config
  (``apex_tpu.training.build_gpt_training`` — the same builder the GPT example
  trains through), restore a verified anchor, re-run the journaled
  segment, compare fingerprints bitwise on a matching platform
  (tolerance-banded otherwise); ``determinism_guard`` is the one home
  of the numerics flags that claim rests on. Replay time books as
  goodput spans.
- ``bisect``   — the corruption bisector: binary-search the first
  divergent step across checkpoint anchors (replay-from-a-corrupted-
  checkpoint faithfully reproduces the corruption, so consistency is
  monotone in the anchor), then localize the leaf (per-leaf crc vs the
  dirty anchor's manifest) and the layer (first divergent
  layer_out_rms depth) — one ``kind="divergence"`` forensic record.

CLI: ``python -m apex_tpu.resilience.replay`` (verify / ``--bisect`` /
``--diff`` / the exit-nonzero ``--selftest`` gate wired into the
verify skill next to the elastic selftest).
"""

from apex_tpu.resilience.replay.journal import (
    JOURNAL_FILENAME,
    FlightRecorder,
    Journal,
    batch_crc,
    journal_path,
    load_journal,
)

__all__ = [
    "JOURNAL_FILENAME",
    "FlightRecorder",
    "Journal",
    "batch_crc",
    "journal_path",
    "load_journal",
    # jax-needing pieces import lazily via PEP 562 below
    "determinism_guard",
    "replay_segment",
    "build_context",
    "compare_journals",
    "verified_anchor_steps",
    "ReplayError",
    "ReplayReport",
    "bisect_divergence",
    "format_divergence",
    "GPTTargetConfig",
    "build_gpt_training",
    "synthetic_corpus",
]

_LAZY = {
    "determinism_guard": "apex_tpu.resilience.replay.replayer",
    "replay_segment": "apex_tpu.resilience.replay.replayer",
    "build_context": "apex_tpu.resilience.replay.replayer",
    "compare_journals": "apex_tpu.resilience.replay.replayer",
    "verified_anchor_steps": "apex_tpu.resilience.replay.replayer",
    "ReplayError": "apex_tpu.resilience.replay.replayer",
    "ReplayReport": "apex_tpu.resilience.replay.replayer",
    "bisect_divergence": "apex_tpu.resilience.replay.bisect",
    "format_divergence": "apex_tpu.resilience.replay.bisect",
    "GPTTargetConfig": "apex_tpu.training",
    "build_gpt_training": "apex_tpu.training",
    "synthetic_corpus": "apex_tpu.resilience.replay.targets",
}


def __getattr__(name):
    # PEP-562 lazy exports (the analysis/__init__ contract): journal
    # reading/diffing must stay importable on a jax-free box, and the
    # CLI must be able to pin the CPU mesh env BEFORE anything imports
    # jax transitively
    target = _LAZY.get(name)
    if target is None:
        raise AttributeError(name)
    import importlib

    return getattr(importlib.import_module(target), name)
