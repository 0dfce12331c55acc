"""Mixture-of-Experts layer with expert parallelism.

No reference counterpart (the reference has no MoE/EP — SURVEY.md §2.5
lists EP as absent); this is the expert-parallelism extension the TPU
framework makes first-class. One layer, ``MoEMLP``, whose settings cover
both families in use:

- **Switch / GShard** (``router="softmax"``, top-1/top-2, a
  ``capacity_factor``): gates are the chosen softmax probabilities, each
  expert takes ``capacity`` assignments a top-k pass and the rest are
  dropped (they pass through the residual path), with the load-balancing
  auxiliary loss ``E * Σ_e fraction_e * prob_e``;
- **DeepSeek-V3** (``router="sigmoid"``, ``capacity_factor=None``): experts
  are chosen by ``sigmoid score + bias`` (the bias takes no gradient), the
  gates are the chosen scores, normalised and scaled; SwiGLU experts, a
  shared expert beside them, and no assignment is ever dropped.

One dispatch serves both (``_expert_rows``): the kept assignments are
sorted by expert once, the rows are gathered in that order, the experts
run as ONE grouped matmul whose work follows the rows present (the Pallas
``megablox`` kernels of ``jax.experimental``; an einsum over a one-hot on
``impl="xla"``), and each token takes its rows back weighted by its gates.
The sorted buffer holds ``_ROWS_OVER_EVEN`` times the rows an even router
would send the experts held here; a call that counts more rows than that
takes the worst-case buffer instead (every assignment lands here), chosen
on the device by ``lax.cond``, so nothing is dropped that the capacity rule
did not drop. A layer that holds all its experts has the worst-case buffer
alone, and no ``cond``. What stays worst-case either way: the sort over
every assignment (run once a call, before the choice, and kept for the
backward pass), the two gathers shaped (tokens, top_k), and the expert
axis's send buffers.

Which experts a program holds: with ``expert_axis`` each rank of the mesh
axis holds ``num_experts / ep`` and the layer exchanges rows once each way
(one ``all_to_all`` pair a layer); without one, ``experts_held`` /
``first_expert`` name the share held here, the router still scores all
``num_experts``, and what the absent experts would add is left out —
one chip's part of an expert-parallel job, with nothing standing in for
the other chips.
"""

import functools
from typing import Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.monitor.goodput.scopes import model_scope
from apex_tpu.monitor.xray import ledger as xlax
from apex_tpu.ops._dispatch import resolve_impl
from apex_tpu.transformer.config import TransformerConfig

#: rows of a grouped-matmul tile; the buffer of rows is a multiple of it
_GMM_ROWS = 512
#: the sorted buffer's rows over what an even router sends the experts held
#: (in the JoyAI cell 16,384 rows of 65,536: one expert that every token
#: chooses, 8,192, and the other fifteen at twice their even share)
_ROWS_OVER_EVEN = 4


def _axis_size_or_1(axis_name: Optional[str]) -> int:
    if axis_name is None:
        return 1
    try:
        return xlax.axis_size(axis_name)
    except NameError:
        return 1


def router_probs(logits, num_experts: int, top_k: int):
    """Softmax gate probabilities + top-k expert assignment."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, top_k)
    return probs, gate_vals, expert_idx


def router_sigmoid(logits, bias, top_k: int, norm_topk_prob: bool,
                   scaling_factor: float):
    """DeepSeek-V3's ``noaux_tc`` router without group limits: scores
    ``s = sigmoid(logits)`` in fp32, experts chosen as the top-k of
    ``s + bias`` (``bias`` takes no gradient), gates ``s`` of the chosen,
    divided by their sum under ``norm_topk_prob``, times
    ``scaling_factor``. Returns (scores, gates, expert_idx)."""
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, expert_idx = jax.lax.top_k(
        s + jax.lax.stop_gradient(bias.astype(jnp.float32)), top_k)
    gates = jnp.take_along_axis(s, expert_idx, axis=-1)
    if norm_topk_prob:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    return s, gates * scaling_factor, expert_idx


def total_moe_aux_loss(intermediates, config) -> jnp.ndarray:
    """Sum every sown ``moe_aux_loss`` scaled by
    ``config.moe_aux_loss_coeff`` — add this to the training loss:

        out, inter = model.apply(vars, x, mutable=["intermediates"])
        loss = task_loss + total_moe_aux_loss(inter, cfg)
    """
    total = jnp.asarray(0.0, jnp.float32)

    def visit(node):
        nonlocal total
        if isinstance(node, dict):
            for k, v in node.items():
                if k == "moe_aux_loss":
                    for leaf in jax.tree_util.tree_leaves(v):
                        total = total + leaf
                else:
                    visit(v)

    visit(intermediates)
    return config.moe_aux_loss_coeff * total


def load_balancing_loss(probs, expert_idx, num_experts: int):
    """Switch aux loss: E * Σ_e (token fraction to e) * (mean prob of e)."""
    f = jnp.mean(
        jax.nn.one_hot(expert_idx[..., 0], num_experts, dtype=jnp.float32),
        axis=0,
    )
    p = jnp.mean(probs, axis=0)
    return num_experts * jnp.sum(f * p)


def _dispatch_indices(expert_idx, num_experts: int, capacity: int):
    """Position of each token inside its expert's capacity buffer (cumsum
    trick); tokens beyond capacity get position -1 (dropped). The capacity
    RULE of the Switch/GShard settings: which assignments are kept."""
    onehot = jax.nn.one_hot(expert_idx, num_experts, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) * onehot  # 1-based within expert
    pos_in_expert = jnp.sum(pos, axis=-1) - 1
    keep = pos_in_expert < capacity
    return jnp.where(keep, pos_in_expert, -1)


@jax.custom_vjp
def _take_rows(src, idx, back):
    """``src[idx]`` with rows of zeros where ``idx < 0``; ``idx`` of any
    shape. Its transpose is a gather too: ``back`` (one row per row of
    ``src``, any number of columns) lists the flat positions in the output
    that read that row (-1 = none), so no scatter-add runs on the device."""
    rows = jnp.take(src, jnp.maximum(idx, 0), axis=0)
    return jnp.where((idx >= 0)[..., None], rows, jnp.zeros((), src.dtype))


def _take_rows_fwd(src, idx, back):
    return _take_rows(src, idx, back), (idx, back)


def _take_rows_bwd(res, g):
    idx, back = res
    flat = g.reshape((-1, g.shape[-1]))
    picked = _take_rows(flat, back, idx.reshape(-1, 1))
    return jnp.sum(picked.astype(jnp.float32), axis=1).astype(g.dtype), \
        None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def _grouped_matmul(x, w, group_sizes, interpret):
    """Rows of ``x`` (sorted by group, ``group_sizes`` rows each) times
    their group's matrix of ``w`` (groups, k, n): the megablox kernels,
    which visit the tiles that hold rows and no others. Rows past the
    groups' total come back undefined."""
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    r, (k, n) = x.shape[0], w.shape[1:]
    rows = min(_GMM_ROWS, -(-r // 128) * 128)
    pad = -r % rows
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    out = megablox.gmm(x, w, group_sizes, x.dtype,
                       (rows, min(512, k), min(512, n)), None, None, False,
                       interpret)
    return out[:r] if pad else out


def _sort_by_expert(row_expert, held):
    """Rows sorted by their expert: ``row_expert`` (r,) names each row's,
    0-based among the ``held`` here (-1 = no expert here). Returns
    ``order`` (r,: the row at sorted position i; rows with no expert
    last), ``place`` (r,: its inverse, -1 for rows with no expert) and the
    rows each held expert took."""
    r = row_expert.shape[0]
    here = row_expert >= 0
    with model_scope("moe_dispatch"):
        key = jnp.where(here, row_expert, held)
        order = jnp.argsort(key, stable=True)          # rows sorted by expert
        place = jnp.zeros((r,), jnp.int32).at[order].set(
            jnp.arange(r, dtype=jnp.int32))            # where each row went
        load = jnp.sum(
            key[:, None] == jnp.arange(held, dtype=key.dtype)[None, :],
            axis=0, dtype=jnp.int32)
        return order, jnp.where(here, place, -1), load


def _expert_rows(rows, order, load, w_in, w_out, activate, impl, capacity):
    """The held experts' FFN over the rows ``_sort_by_expert`` sorted, in a
    buffer of ``capacity`` rows, which the caller knows to hold every row
    with an expert here. ``rows(order)`` gathers the rows in the sorted
    order (``order[i]`` = the caller's row at sorted position i < capacity,
    -1 past the last row with an expert), so they are read once, already
    sorted. Returns the results in sorted order, and ``order`` so masked."""
    held = w_in.shape[0]
    with model_scope("moe_dispatch"):
        filled = jnp.arange(capacity) < jnp.sum(load)
        order = jnp.where(filled, order[:capacity], -1)
        x = rows(order)
    use_pallas, interpret = resolve_impl(impl)
    with model_scope("moe_experts"):
        if use_pallas:
            hdn = activate(_grouped_matmul(x, w_in.astype(x.dtype), load,
                                           interpret))
            # rows past the last group are undefined: keep them finite
            hdn = jnp.where(filled[:, None], hdn, jnp.zeros((), hdn.dtype))
            y = _grouped_matmul(hdn, w_out.astype(x.dtype), load, interpret)
            y = jnp.where(filled[:, None], y, jnp.zeros((), y.dtype))
        else:
            # plain XLA: every row through every held expert, one kept (a
            # sorted position is the expert's whose rows end past it)
            expert = jnp.searchsorted(
                jnp.cumsum(load), jnp.arange(capacity), side="right")
            onehot = (expert[:, None] == jnp.arange(held)[None, :])
            onehot = onehot & filled[:, None]
            hdn = activate(jnp.einsum(
                "rh,ehf->erf", x, w_in.astype(x.dtype),
                preferred_element_type=jnp.float32).astype(x.dtype))
            y = jnp.einsum("erf,efh->erh", hdn, w_out.astype(x.dtype),
                           preferred_element_type=jnp.float32)
            y = jnp.einsum("erh,re->rh", y, onehot.astype(y.dtype)
                           ).astype(x.dtype)
    return y, order


def _on_counted_rows(part, floats, row_expert, held, expected, remat):
    """The held experts' part for the rows ``row_expert`` (r,) names (-1 =
    no expert here): ``part(*floats, order, place, load, capacity)`` ->
    a float array, through a sorted buffer of ``capacity`` rows
    (``_sort_by_expert``, ``_expert_rows``). Runs it with the buffer the
    shapes promise (``_ROWS_OVER_EVEN`` times ``expected``, the rows an
    even router sends here, in whole tiles) when the rows counted fit it,
    and with the worst case ``r`` when they do not, so no row is dropped
    either way. Returns (the array, the rows each held expert took,
    whether the short buffer ran). The sort is the same for both buffers:
    it runs once, before the choice, and is kept for the backward pass
    (two int32 a row) rather than run again.

    ``jax.grad`` through a plain ``cond`` keeps BOTH branches' residuals,
    the untaken one's as zeros: the worst-case buffers again, allocated and
    filled. So the pair is one ``custom_vjp`` that keeps its inputs alone,
    as ``jax.checkpoint`` does, and whose backward is a ``cond`` too: the
    branch that ran recomputes its own forward and transposes it. Under
    ``vmap`` a batched count turns both ``cond``s into selects that run
    both branches: right, and slower than the worst case alone
    (``training/gpt_step.py`` runs a model with expert layers one
    microbatch after another, not vmapped).

    Where the promised buffer IS the worst case (all experts held) there
    is one path and no ``cond``: under ``jax.checkpoint`` when ``remat``,
    bare when the caller recomputes it already."""
    r = row_expert.shape[0]
    capacity = min(r, -(-_ROWS_OVER_EVEN * expected // _GMM_ROWS) * _GMM_ROWS)
    ints = _sort_by_expert(row_expert, held)
    load = ints[2]
    full = functools.partial(part, capacity=r)
    if capacity == r:
        out = (jax.checkpoint(full) if remat else full)(*floats, *ints)
        return out, load, jnp.zeros((), bool)
    compact = functools.partial(part, capacity=capacity)

    @jax.custom_vjp
    def either(fits, floats, ints):
        return jax.lax.cond(fits, compact, full, *floats, *ints)

    def forward(fits, floats, ints):
        return either(fits, floats, ints), (fits, floats, ints)

    def pull(branch, floats, ints, g):
        return jax.vjp(lambda *f: branch(*f, *ints), *floats)[1](g)

    def backward(res, g):
        fits, floats, ints = res
        return None, jax.lax.cond(
            fits, functools.partial(pull, compact),
            functools.partial(pull, full), floats, ints, g), None

    either.defvjp(forward, backward)
    fits = jnp.sum(load) <= capacity
    return either(fits, floats, ints), load, fits


def _routed_experts(x, w_in, w_out, gate_vals, chosen, first, *, e, k, ep,
                    local_e, expert_axis, activate, impl):
    """What the experts held here add for ``x`` (tokens, h): ``chosen``
    (tokens, k) names each token's experts among all ``e`` (-1 = dropped by
    the capacity rule), ``gate_vals`` their gates. Without an expert axis
    (``ep`` 1) the rows of experts ``[first, first + local_e)`` are
    computed and the rest left out; with one, rows are exchanged once each
    way. Returns the sum (tokens, h) in fp32, the rows each held expert
    took and whether they went through the short buffer
    (``_on_counted_rows``)."""
    tokens, h = x.shape
    expected = -(-tokens * k * local_e * ep // e)
    # one row an assignment, token-major: row a = (token a // k, pass a % k)
    a_expert = chosen.reshape(-1)
    if ep == 1:
        local = a_expert - first
        row_expert = jnp.where(
            (a_expert >= 0) & (local >= 0) & (local < local_e), local, -1)

        # recomputed in the backward pass with its gates: the (tokens, k,
        # h) rows the tokens take back would not fit five layers of them
        # beside the optimizer's state
        def part(x, w_in, w_out, gate_vals, order, place, load, capacity):
            def rows(order):
                # row i of the sorted buffer is token order[i] // k; token
                # t's rows sit at place[t*k : (t+1)*k]
                return _take_rows(
                    x, jnp.where(order >= 0, order // k, -1),
                    place.reshape(tokens, k))

            y, order = _expert_rows(
                rows, order, load, w_in, w_out, activate, impl, capacity)
            with model_scope("moe_combine"):
                mine = _take_rows(y, place.reshape(tokens, k), order[:, None])
                return jnp.sum(mine.astype(jnp.float32)
                               * gate_vals[..., None], axis=1)

        out, load, compact = _on_counted_rows(
            part, (x, w_in, w_out, gate_vals), row_expert, local_e, expected,
            remat=True)
    else:
        # exchange once each way: every rank sends each peer the rows its
        # experts take, in a buffer sized for the worst case (every token
        # sends a peer min(k, local_e) rows)
        a_token = jnp.repeat(jnp.arange(tokens, dtype=jnp.int32), k)
        cap = tokens * min(k, local_e)
        with model_scope("moe_dispatch"):
            dest = jnp.where(a_expert >= 0, a_expert // local_e, ep)
            by_dest = jnp.argsort(dest, stable=True)
            start = jnp.searchsorted(dest[by_dest], jnp.arange(ep + 1))
            count = start[1:] - start[:-1]
            col = jnp.arange(cap, dtype=jnp.int32)
            # send[p, c] = the c-th assignment bound for peer p, or -1
            send = jnp.where(
                col[None, :] < count[:, None],
                by_dest[jnp.minimum(start[:-1, None] + col[None, :],
                                    tokens * k - 1)], -1)
            # where each assignment sits in the send buffer (flat)
            rank_of = jnp.zeros((tokens * k,), jnp.int32).at[by_dest].set(
                jnp.arange(tokens * k, dtype=jnp.int32))
            peer = jnp.minimum(dest, ep - 1)
            sent_at = jnp.where(
                dest < ep, peer * cap + rank_of - start[peer], -1)
            send_x = _take_rows(
                x, jnp.where(send >= 0, a_token[jnp.maximum(send, 0)], -1),
                sent_at.reshape(tokens, k))
            send_e = jnp.where(
                send >= 0, a_expert[jnp.maximum(send, 0)] % local_e, -1)
            recv_x = xlax.all_to_all(
                send_x, expert_axis, split_axis=0, concat_axis=0,
                tiled=False).reshape(ep * cap, h)
            recv_e = xlax.all_to_all(
                send_e, expert_axis, split_axis=0, concat_axis=0,
                tiled=False).reshape(ep * cap)

        # no collective in here: the ranks may take different buffers
        def part(recv_x, w_in, w_out, order, place, load, capacity):
            def rows(order):
                return _take_rows(recv_x, order, place[:, None])

            y, order = _expert_rows(
                rows, order, load, w_in, w_out, activate, impl, capacity)
            with model_scope("moe_combine"):
                return _take_rows(y, place, order[:, None])

        back, load, compact = _on_counted_rows(
            part, (recv_x, w_in, w_out), recv_e, local_e, expected,
            remat=False)
        with model_scope("moe_combine"):
            back = xlax.all_to_all(
                back.reshape(ep, cap, h), expert_axis, split_axis=0,
                concat_axis=0, tiled=False).reshape(ep * cap, h)
            # assignment a's row sits at sent_at[a]; the transpose reads
            # flat slot send[p, c] for buffer row (p, c)
            mine = _take_rows(back, sent_at.reshape(tokens, k),
                              send.reshape(-1, 1))
            out = jnp.sum(mine.astype(jnp.float32) * gate_vals[..., None],
                          axis=1)
    return out, load, compact


class MoEMLP(nn.Module):
    """Expert-parallel MoE FFN block (module docstring).

    Input (tokens, hidden) — callers flatten (s, b). ``num_experts`` is the
    GLOBAL expert count. With ``expert_axis`` it must divide by the axis
    size and each rank owns ``num_experts / ep`` experts; without one the
    layer holds ``experts_held`` experts from ``first_expert`` (default:
    all). Returns (output, aux_loss), and sows ``moe_chosen`` (the experts each
    token chose), ``moe_load`` (rows each held expert took), ``moe_dropped``
    and ``moe_compact`` (1 when the rows went through the short buffer of
    ``_on_counted_rows``) for whoever collects ``intermediates``.
    """

    config: TransformerConfig
    num_experts: int = 8
    top_k: int = 1
    capacity_factor: Optional[float] = 1.25
    expert_axis: Optional[str] = "dp"
    activation: Callable = jax.nn.gelu
    router: str = "softmax"
    norm_topk_prob: bool = False
    routed_scaling_factor: float = 1.0
    gated: bool = False
    ffn_hidden_size: Optional[int] = None
    shared_experts: int = 0
    experts_held: Optional[int] = None
    first_expert: int = 0
    impl: str = "auto"  # the grouped matmul: "auto" | "pallas" | "xla"

    @nn.compact
    def __call__(self, x) -> Tuple[jnp.ndarray, jnp.ndarray]:
        cfg = self.config
        tokens, h = x.shape
        e, k = self.num_experts, self.top_k
        ep = _axis_size_or_1(self.expert_axis)
        if ep > 1:
            assert e % ep == 0, f"num_experts ({e}) not divisible by ep ({ep})"
            local_e = e // ep
            first = jax.lax.axis_index(self.expert_axis) * local_e
        else:
            local_e = e if self.experts_held is None else self.experts_held
            first = self.first_expert
            assert 0 <= first and first + local_e <= e, (first, local_e, e)
        ffn = self.ffn_hidden_size or cfg.ffn_hidden_size
        width = ffn * (2 if self.gated else 1)

        def activate(hdn):
            if not self.gated:
                return self.activation(hdn)
            gate, up = jnp.split(hdn, 2, axis=-1)
            return self.activation(gate) * up

        with model_scope("moe_route"):
            gate_w = self.param(
                "router", nn.initializers.normal(stddev=0.02), (h, e),
                cfg.params_dtype,
            )
            # router math in fp32 (standard MoE stability practice)
            logits = x.astype(jnp.float32) @ gate_w.astype(jnp.float32)
            if self.router == "sigmoid":
                bias = self.param("router_bias", nn.initializers.zeros_init(),
                                  (e,), jnp.float32)
                _, gate_vals, expert_idx = router_sigmoid(
                    logits, bias, k, self.norm_topk_prob,
                    self.routed_scaling_factor)
                aux = jnp.zeros((), jnp.float32)
            else:
                probs, gate_vals, expert_idx = router_probs(logits, e, k)
                gate_vals = gate_vals * self.routed_scaling_factor
                aux = load_balancing_loss(probs, expert_idx, e)
            keep = jnp.ones((tokens, k), bool)
            if self.capacity_factor is not None:
                # per-assignment-pass capacity: each of the top_k passes
                # dispatches one assignment per token, so per-pass slots are
                # cf*tokens/e and TOTAL slots per expert are cf*tokens*top_k/e
                # — the GShard convention for the capacity_factor knob
                capacity = int(self.capacity_factor * tokens / e)
                if capacity < 1:
                    raise ValueError(
                        f"capacity_factor {self.capacity_factor} leaves no "
                        f"slot an expert at {tokens} tokens over {e} experts")
                keep = jnp.stack([
                    _dispatch_indices(expert_idx[:, j], e, capacity) >= 0
                    for j in range(k)], axis=1)
            dropped = jnp.sum(~keep)

        # per-rank experts: (local_e, h, width) / (local_e, ffn, h)
        w_in = self.param(
            "w_in", nn.initializers.lecun_normal(batch_axis=(0,)),
            (local_e, h, width), cfg.params_dtype,
        )
        w_out = self.param(
            "w_out", nn.initializers.lecun_normal(batch_axis=(0,)),
            (local_e, ffn, h), cfg.params_dtype,
        )

        routed = functools.partial(
            _routed_experts, e=e, k=k, ep=ep, local_e=local_e,
            expert_axis=self.expert_axis, activate=activate, impl=self.impl)
        if ep > 1:
            # the exchange is recomputed in the backward pass with the
            # experts' part (which _on_counted_rows recomputes by itself)
            routed = jax.checkpoint(routed)
        out, load, compact = routed(x, w_in, w_out, gate_vals,
                                    jnp.where(keep, expert_idx, -1), first)

        if self.shared_experts:
            with model_scope("moe_shared"):
                s_ffn = ffn * self.shared_experts
                s_in = self.param(
                    "shared_w_in", nn.initializers.lecun_normal(),
                    (h, s_ffn * (2 if self.gated else 1)), cfg.params_dtype)
                s_out = self.param(
                    "shared_w_out", nn.initializers.lecun_normal(),
                    (s_ffn, h), cfg.params_dtype)
                hdn = activate(jnp.dot(
                    x, s_in.astype(x.dtype),
                    preferred_element_type=jnp.float32).astype(x.dtype))
                out = out + jnp.dot(hdn, s_out.astype(x.dtype),
                                    preferred_element_type=jnp.float32)
        self.sow("intermediates", "moe_chosen", expert_idx)
        self.sow("intermediates", "moe_load", load)
        self.sow("intermediates", "moe_dropped", dropped)
        self.sow("intermediates", "moe_compact", compact)
        return out.astype(x.dtype), aux
