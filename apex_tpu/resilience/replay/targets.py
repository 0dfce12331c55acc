"""The replayer's data target: the synthetic corpus a recording regenerates.

The step a recording replays is built by ``apex_tpu.training``
(:func:`~apex_tpu.training.build_gpt_training` from the journal header's
:class:`~apex_tpu.training.GPTTargetConfig`); what is the replayer's own is
the data that step was fed.
"""

import os
import tempfile

import numpy as np

# read here by perf/drivers/*.py, and patched here by tests/perf/: a
# `benchmark` PR points them at apex_tpu.training and removes this line
from apex_tpu.training import (  # noqa: F401
    GPTTargetConfig, GPTTraining, build_gpt_training)

__all__ = ["synthetic_corpus"]


def synthetic_corpus(vocab: int, n_tokens: int = 200_000) -> str:
    """Deterministic synthetic token corpus (seeded markov-ish stream).

    The replayer REGENERATES the recording run's data when the journal
    header says the corpus was synthetic: same seed, same stream, verified
    per step by the journaled ``batch_crc``.
    """
    from apex_tpu.data import write_token_file

    tmp = tempfile.mkdtemp(prefix="apex_tpu_corpus_")
    prefix = os.path.join(tmp, "synthetic")
    rng = np.random.RandomState(0)
    # markov-ish stream so the LM has structure to learn
    toks = np.cumsum(rng.randint(1, 5, size=(n_tokens,)), dtype=np.int64) % vocab
    write_token_file(prefix, toks.astype(np.int32))
    return prefix
