"""Tensor-parallel layers: Column/Row-parallel linear, vocab-parallel embedding.

Reference parity: apex/transformer/tensor_parallel/layers.py —
``ColumnParallelLinear`` (:460), ``RowParallelLinear`` (:645),
``VocabParallelEmbedding`` (:174), and the fused
``LinearWithGradAccumulationAndAsyncCommunication`` autograd Function (:279).

TPU design: flax.linen modules meant to run inside ``shard_map`` over the
'tp' mesh axis. Parameters hold the *local shard* (features // tp); the
matching global arrays come out of shard_map with the right PartitionSpec.
All of the reference's manual overlap machinery (async all-gather before
wgrad, dgrad reduce-scatter overlapped with the wgrad GEMM, fused
accumulation into main_grad via fused_weight_gradient_mlp_cuda) is exactly
what XLA's latency-hiding scheduler does with the collectives emitted by the
mappings' custom_vjps — hard part #3 in SURVEY.md §7 verified by profile,
not hand scheduling.

Per-rank init matches Megatron semantics (random.py:204): initializers are
wrapped so each TP rank draws from fold_in(key, 2718 + rank).
"""

from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.parallel import parallel_state
from apex_tpu.parallel.mappings import (
    copy_to_tensor_model_parallel_region,
    gather_from_sequence_parallel_region,
    gather_from_tensor_model_parallel_region,
    reduce_from_tensor_model_parallel_region,
    reduce_scatter_to_sequence_parallel_region,
    scatter_to_tensor_model_parallel_region,
)


def _tp_size(axis_name: str) -> int:
    if parallel_state.model_parallel_is_initialized():
        return int(parallel_state.get_mesh().shape[axis_name])
    return 1


def tp_rank_init(init_fn: Callable, axis_name: str = "tp") -> Callable:
    """Wrap an initializer so each TP rank draws a distinct stream
    (ref seed offset semantics, tensor_parallel/random.py:204-236)."""

    def wrapped(key, shape, dtype=jnp.float32):
        key = jax.random.fold_in(key, 2718)
        try:
            key = jax.random.fold_in(key, jax.lax.axis_index(axis_name))
        except NameError:
            # not inside shard_map over axis_name: fine for tp==1, but with
            # tp>1 every rank would draw the SAME shard init — a caller bug
            # that must surface, not silently degrade
            if _tp_size(axis_name) > 1:
                raise RuntimeError(
                    f"tp_rank_init: initializer ran outside shard_map while "
                    f"the mesh has {_tp_size(axis_name)} {axis_name!r} shards;"
                    f" every rank would get identical params. Initialize "
                    f"inside shard_map over {axis_name!r}."
                ) from None
        return init_fn(key, shape, dtype)

    return wrapped


class ColumnParallelLinear(nn.Module):
    """Y = X A + b with A partitioned along its output (column) dim.

    Ref: layers.py:460. ``sequence_parallel_enabled`` all-gathers the
    sequence-sharded input in forward and reduce-scatters its grad in
    backward (layers.py:311-326, 345-361) — here that is the custom_vjp of
    ``gather_from_sequence_parallel_region``.
    """

    output_size: int
    use_bias: bool = True
    gather_output: bool = False
    sequence_parallel_enabled: bool = False
    axis_name: str = "tp"
    params_dtype: jnp.dtype = jnp.float32
    kernel_init: Callable = nn.initializers.lecun_normal()
    bias_init: Callable = nn.initializers.zeros_init()
    # keep the fp32 MXU accumulator instead of rounding back to x.dtype —
    # for heads whose consumer (e.g. vocab CE) wants full-precision logits
    output_dtype: Optional[Any] = None

    @nn.compact
    def __call__(self, x, sequence_parallel_override: Optional[bool] = None):
        # call-time SP override for setup-built instances whose input layout
        # changes per call — KV-cache decode feeds a replicated single token
        # through a layer constructed for sequence-sharded training inputs
        # (params are identical either way; only the gather moves)
        sp = (self.sequence_parallel_enabled
              if sequence_parallel_override is None
              else sequence_parallel_override)
        tp = _tp_size(self.axis_name)
        assert self.output_size % tp == 0, (
            f"output_size {self.output_size} not divisible by tp {tp}"
        )
        out_local = self.output_size // tp
        kernel = self.param(
            "kernel",
            tp_rank_init(self.kernel_init, self.axis_name),
            (x.shape[-1], out_local),
            self.params_dtype,
        )
        if tp > 1:
            if sp:
                x = gather_from_sequence_parallel_region(x, self.axis_name)
            else:
                x = copy_to_tensor_model_parallel_region(x, self.axis_name)
        y = jax.lax.dot_general(
            x,
            kernel.astype(x.dtype),
            (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(self.output_dtype or x.dtype)
        if self.use_bias:
            bias = self.param(
                "bias",
                tp_rank_init(self.bias_init, self.axis_name),
                (out_local,),
                self.params_dtype,
            )
            y = y + bias.astype(y.dtype)
        if self.gather_output and tp > 1:
            assert not sp
            y = gather_from_tensor_model_parallel_region(y, self.axis_name)
        return y


class RowParallelLinear(nn.Module):
    """Y = X A + b with A partitioned along its input (row) dim.

    Ref: layers.py:645. Output is psum'ed over TP (or reduce-scattered to
    the sequence-parallel region); bias is added *after* the reduction so it
    is applied exactly once.
    """

    output_size: int
    use_bias: bool = True
    input_is_parallel: bool = True
    sequence_parallel_enabled: bool = False
    axis_name: str = "tp"
    params_dtype: jnp.dtype = jnp.float32
    kernel_init: Callable = nn.initializers.lecun_normal()
    bias_init: Callable = nn.initializers.zeros_init()

    @nn.compact
    def __call__(self, x):
        tp = _tp_size(self.axis_name)
        if tp > 1 and not self.input_is_parallel:
            x = scatter_to_tensor_model_parallel_region(x, self.axis_name)
        kernel = self.param(
            "kernel",
            tp_rank_init(self.kernel_init, self.axis_name),
            (x.shape[-1], self.output_size),
            self.params_dtype,
        )
        y = jax.lax.dot_general(
            x,
            kernel.astype(x.dtype),
            (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(x.dtype)
        if tp > 1:
            if self.sequence_parallel_enabled:
                y = reduce_scatter_to_sequence_parallel_region(y, self.axis_name)
            else:
                y = reduce_from_tensor_model_parallel_region(y, self.axis_name)
        if self.use_bias:
            bias = self.param("bias", self.bias_init, (self.output_size,), self.params_dtype)
            if tp > 1 and self.sequence_parallel_enabled:
                # bias grad under SP is a partial sum over the local sequence
                # shard — identity-fwd/psum-bwd restores the full gradient
                # (ref: sequence_parallel_enabled grad allreduce semantics)
                bias = copy_to_tensor_model_parallel_region(bias, self.axis_name)
            y = y + bias.astype(y.dtype)
        return y


class VocabParallelEmbedding(nn.Module):
    """Embedding table partitioned along the vocab dim.

    Ref: layers.py:174 — each rank owns rows [rank*V/tp, (rank+1)*V/tp),
    out-of-range token ids produce zeros locally, and the partial lookups
    are summed over TP (:250-277).
    """

    num_embeddings: int
    embedding_dim: int
    axis_name: str = "tp"
    params_dtype: jnp.dtype = jnp.float32
    embedding_init: Callable = nn.initializers.normal(stddev=1.0)

    def setup(self):
        tp = _tp_size(self.axis_name)
        assert self.num_embeddings % tp == 0
        self.vocab_local = self.num_embeddings // tp
        self.embedding = self.param(
            "embedding",
            tp_rank_init(self.embedding_init, self.axis_name),
            (self.vocab_local, self.embedding_dim),
            self.params_dtype,
        )

    def __call__(self, ids):
        table = self.embedding
        tp = _tp_size(self.axis_name)
        if tp == 1:
            return jnp.take(table, ids, axis=0)
        rank = jax.lax.axis_index(self.axis_name)
        start = rank * self.vocab_local
        in_range = (ids >= start) & (ids < start + self.vocab_local)
        local_ids = jnp.clip(ids - start, 0, self.vocab_local - 1)
        out = jnp.take(table, local_ids, axis=0)
        out = jnp.where(in_range[..., None], out, 0.0)
        return reduce_from_tensor_model_parallel_region(out, self.axis_name)

    def attend(self, x, parallel_input: bool = False):
        """Vocab-parallel logits against the (tied) embedding table.

        Ref: parallel_lm_logits in testing/standalone_transformer_lm.py —
        copy-to-TP-region (identity fwd / psum bwd) then X @ E^T, leaving
        logits sharded along vocab for vocab_parallel_cross_entropy.
        ``parallel_input=True`` skips the copy when the caller's gather
        already carries the TP grad reduction (the reference's
        ``tensor_parallel_output_grad=True`` path) — avoids a redundant
        full psum of the hidden-grad in backward.
        """
        tp = _tp_size(self.axis_name)
        if tp > 1 and not parallel_input:
            x = copy_to_tensor_model_parallel_region(x, self.axis_name)
        table = self.embedding.astype(x.dtype)
        return jax.lax.dot_general(
            x,
            table,
            (((x.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
