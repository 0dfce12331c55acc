"""``apex_tpu.training`` — the training step, built one way.

Sits above ``models``, ``transformer``, ``amp``, ``optimizers``, ``parallel``,
``monitor`` and ``resilience``'s sentinel and chaos arms; below the replayer,
the remediation campaign, the examples and the benchmark's cells.
"""

from apex_tpu.training.gpt_step import (
    GPTTargetConfig,
    GPTTraining,
    build_gpt_training,
)

__all__ = ["GPTTargetConfig", "GPTTraining", "build_gpt_training"]
