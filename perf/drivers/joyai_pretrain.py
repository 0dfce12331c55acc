"""Driver: one chip's share of JoyAI-LLM-Flash pretraining through the
library's step builder.

Built like ``gpt_pretrain``: the window drives what ``examples/gpt/
pretrain_gpt.py:main`` builds its hot path from (``apex_tpu.training.
build_gpt_training`` from ``pretrain_gpt.target_config(parse_args(
argv))``, the model named by ``--arch-file`` and the share by ``--experts-
held --first-expert --vocab-rows --layers-kept``), with the loop ``main``
runs there: host batch -> device, one ``train_step``, fetch loss and
verdict. The weights come from the cell's ``weights_seed`` where its file
gives one, else from the benchmark's seed (``perf/reference/
joyai_llm_flash.py``), so that the reference can follow the same run; the
corpus always comes from the benchmark's seed.

Set-up builds ONE compiled step with its state and drives it through the
first three steps (the window's call and feed) while keeping what the output
check needs - the experts the first step chose (the step hands them out
beside its loss: ``collect_expert_choices``), each loss with its two terms,
Adam's first moment after step one, the parameters' change after step
three - and hands that same object to the window.
"""

import dataclasses
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.realpath(__file__)))))
# what a GPT-shaped run shares whatever the model: the example's loader, the
# state, the corpus, one step of main()'s loop
from perf.drivers.gpt_pretrain import (  # noqa: E402,F401
    CHECK_STEPS, State, _corpus, _load_example, _one_step, hlo_text,
    scope_names)


def _published(config):
    """The architecture file the program is given: the published keys, the
    share's counts put back to the published ones (the share goes in the
    program's own arguments)."""
    skip = ("name", "source", "also", "reduced", "published", "deployment",
            "assumed", "layers_kept")
    arch = {k: v for k, v in config.items() if k not in skip}
    arch.update(config["published"])
    return arch


def _ref_kw(st, **kw):
    c = st.config
    return dict(
        heads=c["num_attention_heads"], nope=c["qk_nope_head_dim"],
        rope=c["qk_rope_head_dim"], v_dim=c["v_head_dim"],
        first=c["assumed"]["first_expert"], top_k=c["num_experts_per_tok"],
        theta=float(c["rope_theta"]), eps=float(c["rms_norm_eps"]),
        scale=float(c["routed_scaling_factor"]), **kw)


def build(cell, config):
    """What does not depend on the seed: the training object, its compiled
    step (lowered and compiled once, then called as the compiled object)."""
    import jax
    import jax.numpy as jnp

    from apex_tpu import training
    from perf.reference import joyai_llm_flash as ref

    st = State()
    st.cell, st.config = cell, config
    st.dims = ref.dims_of(config)
    st.layers = st.dims["layers"]
    gpt = _load_example("examples/gpt/pretrain_gpt.py")
    with tempfile.NamedTemporaryFile(
            "w", suffix=".json", prefix="joyai_arch_", delete=False) as f:
        json.dump(_published(config), f)
    try:
        args = gpt.parse_args([
            "--arch-file", f.name,
            "--layers-kept", str(config["layers_kept"]),
            "--experts-held", str(config["n_routed_experts"]),
            "--first-expert", str(config["assumed"]["first_expert"]),
            "--vocab-rows", str(st.dims["vocab"]),
            "--mtp-loss-coeff", str(config["assumed"]["mtp_loss_coeff"]),
            "--router-bias-update-speed",
            str(config["assumed"]["router_bias_update_speed"]),
            "--seq-len", str(cell["seq_len"]),
            "--micro-batch", str(cell["micro_batch"]),
            "--global-batch", str(cell["global_batch"]),
        ])
        tcfg = dataclasses.replace(
            gpt.target_config(args, journal_on=False),
            max_devices=int(cell.get("chips", 1)),
            collect_expert_choices=True)
    finally:
        os.unlink(f.name)
    st.lr, st.weight_decay = tcfg.lr, tcfg.weight_decay
    st.mtp_coeff = config["assumed"]["mtp_loss_coeff"]
    st.bias_speed = config["assumed"]["router_bias_update_speed"]
    st.training = training.build_gpt_training(tcfg)
    st.batch = cell["global_batch"]
    st.n_batches = cell["corpus_samples"] // st.batch

    tr = st.training
    state = jax.eval_shape(tr.init_state)
    bag = jax.eval_shape(tr.init_bag)
    scalar = jax.ShapeDtypeStruct((), jnp.float32)
    bs = tr.batch_struct()
    st.step = tr.train_step.lower(*state, bag, bs, bs, scalar,
                                  scalar).compile()
    st.zero = jnp.asarray(0.0, jnp.float32)
    st.one = jnp.asarray(1.0, jnp.float32)
    return st


def _make_w0(st, seed):
    """The initial weights: one draw for every seed where the cell fixes it
    (``weights_seed``: how soon the MTP block's router overflows the short
    buffer follows the weights, and with it the work of a step, PERF.md 7
    row 21), else the run's own."""
    from perf.reference import joyai_llm_flash as ref

    return ref.init_weights(
        ref.seed_key(st.cell.get("weights_seed", seed)), **st.dims)


def _bag(st):
    """The MetricBag's running values on the host (sums, for mean and sum
    modes)."""
    import jax

    return {k: float(v) for k, v in jax.device_get(
        st.carry[4].values).items()}


def _step_keeping_choices(st, ctx):
    """``_one_step``, and the experts that step chose as the compiled step
    hands them out: (rows, expert layers, seq, top_k), rows in the batch's
    order."""
    compiled, outs = st.step, []
    st.step = lambda *args: outs.append(compiled(*args)) or outs[0]
    try:
        loss, _ = _one_step(st, ctx)
    finally:
        st.step = compiled
    c = np.asarray(outs[0][-1])  # (dp, microbatches, layers, tokens, k)
    dp, micro, layers, _, k = c.shape
    seq, rows = st.cell["seq_len"], st.cell["micro_batch"]
    # tokens are (seq, micro batch) flattened; a global batch's rows lie
    # microbatch-major, then chip, then the micro batch's own
    c = c.reshape(dp, micro, layers, seq, rows, k).transpose(1, 0, 4, 2, 3, 5)
    return loss, c.reshape(micro * dp * rows, layers, seq, k)


def start_run(st, seed, ctx):
    """Corpus, weights and state from the seed (the weights from the cell's
    ``weights_seed`` where it gives one), then the first three steps
    through the window's own call, keeping what the output check reads."""
    import jax

    from perf import joyai_tree
    from perf.reference import joyai_llm_flash as ref

    tr, layers = st.training, st.layers
    st.seed = seed
    st.corpus = _corpus(st, seed)
    params = jax.device_put(
        jax.jit(joyai_tree.to_program)(_make_w0(st, seed)), tr.replicated)
    opt_state = jax.jit(tr.opt.init, out_shardings=tr.replicated)(params)
    st.carry = (params, opt_state,
                jax.device_put(tr.scaler.init(), tr.replicated),
                jax.device_put(tr.sentinel.init(), tr.replicated),
                tr.init_bag())
    st.steps_done = 0

    norms = jax.jit(lambda t: ref.leaf_norms(joyai_tree.stacked(t, layers)))
    delta = jax.jit(lambda t, w: ref.leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a - b, joyai_tree.stacked(t, layers), w)))
    losses, before = [], _bag(st)
    for i in range(CHECK_STEPS):
        if i == 0:
            loss, chosen = _step_keeping_choices(st, ctx)
        else:
            loss, _ = _one_step(st, ctx)
        after = _bag(st)
        losses.append([loss] + [after[k] - before[k]
                                for k in ("loss_main", "loss_mtp")])
        before = after
        if i == 0:
            # m1 = (1 - beta1) * g1: the gradient as fused_adam got it
            g1 = {k: np.asarray(v) / 0.1 for k, v in jax.device_get(
                norms(st.carry[1].exp_avg)).items()}
    st.got = {"losses": losses, "g1": g1,
              "delta": jax.device_get(delta(st.carry[0], _make_w0(st, seed))),
              "skipped": float(jax.device_get(st.carry[2].skipped)),
              "chosen": chosen}


def setup(cell, config, seed, ctx):
    st = build(cell, config)
    start_run(st, seed, ctx)
    return st


def window(st, seconds, ctx):
    from perf import joyai_flops

    steps, bad, log = 0, 0, []
    start = _bag(st)
    t0 = time.perf_counter()
    while True:
        loss, verdict = _one_step(st, ctx)
        steps += 1
        log.append((loss, verdict))
        # a step whose update the program suppressed (sentinel verdict
        # skip/rollback/halt, or a non-finite loss) did no training
        bad += int(verdict != 0 or not np.isfinite(loss))
        ctx.poll()
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds + ctx.trace_stall_s:
            break
    if bad:
        print("[joyai_pretrain] steps the sentinel flagged (step, loss, "
              "verdict): " + "; ".join(
                  f"{i} {l:.4f} v{v}" for i, (l, v) in enumerate(log)
                  if v != 0), file=sys.stderr, flush=True)
    print("[joyai_pretrain] loss every 8th step: "
          + " ".join(f"{l:.3f}" for l, _ in log[::8]),
          file=sys.stderr, flush=True)
    end = _bag(st)
    st.dropped = end["moe_dropped"]
    rows_here = end["moe_rows_here"] - start["moe_rows_here"]  # all steps
    print(f"[joyai_pretrain] rows on held experts a step "
          f"{rows_here / steps:.0f} (a layer: "
          f"{(end['moe_load_mean'] - start['moe_load_mean']) / steps:.1f} "
          f"an expert), largest load {end['moe_load_max']:.0f}, max / mean "
          f"{(end['moe_load_max_over_mean'] - start['moe_load_max_over_mean']) / steps:.2f}"
          f", dropped {end['moe_dropped']:.0f}", file=sys.stderr, flush=True)
    seq = st.cell["seq_len"]
    model_flops = joyai_flops.train_step_flops(
        st.config, st.dims["vocab"], st.batch * steps, seq, rows_here)
    return {
        "attempted": steps, "failed": bad, "window_s": elapsed,
        "end_to_end": {"train_step_ms": 1e3 * elapsed / steps},
        "counters": {
            "steps": steps, "tokens_per_step": st.batch * seq,
            "model_flops": model_flops,
            "micro_batch": st.cell["micro_batch"], "last_loss": loss,
            "moe_rows_here_per_step": rows_here / steps,
            "moe_load_max": end["moe_load_max"],
            "moe_load_mean": (end["moe_load_mean"] - start["moe_load_mean"])
            / steps,
            "moe_load_max_over_mean": (
                end["moe_load_max_over_mean"]
                - start["moe_load_max_over_mean"]) / steps,
            "moe_dropped_assignments": end["moe_dropped"],
            "moe_compact_share": (
                end["moe_compact_share"] - start["moe_compact_share"])
            / steps,
        },
    }


def release(st):
    st.carry = st.step = st.training = None


def reference(st, seed, precision="f32", keep_rows=None, routed=True,
              mtp=True):
    """The first three steps as the plain reference takes them (or, for the
    control and the planted faults, as a lower precision, half a batch, no
    routed experts or no second loss term would)."""
    import jax
    import jax.numpy as jnp

    from perf.reference import joyai_llm_flash as ref

    corpus = _corpus(st, seed)
    rows = corpus[: CHECK_STEPS * st.batch].reshape(CHECK_STEPS, st.batch, -1)
    losses, g1, delta, chosen = jax.device_get(ref.train_steps(
        lambda: _make_w0(st, seed), jnp.asarray(rows[:, :, :-1]),
        jnp.asarray(rows[:, :, 1:]), steps=CHECK_STEPS, lr=st.lr,
        weight_decay=st.weight_decay, bias_speed=st.bias_speed,
        keep_rows=keep_rows, mtp_coeff=st.mtp_coeff,
        **_ref_kw(st, precision=precision, routed=routed, mtp=mtp)))
    return {"losses": [[float(x) for x in step] for step in losses],
            "g1": g1, "delta": delta, "skipped": 0.0,
            "chosen": np.asarray(chosen)}


def compare_run(got, want, limits, dropped=0.0):
    """``compare.training`` on the total loss, the gradient and the change,
    then what this model adds: the median leaf's gradient gap, the two loss
    terms read, the share of the step's expert choices that are not the
    reference's, the assignments dropped."""
    from perf import compare

    inf = float("inf")
    out = compare.training(
        dict(got, losses=[l[0] for l in got["losses"]]),
        dict(want, losses=[l[0] for l in want["losses"]]), limits)
    verdicts = out[-1:]          # skipped_updates stays last but these
    out = out[:-1]
    # the worst leaf above is as a rule a router's, which a routing flip
    # moves whatever the precision; the median leaf's gap is what every
    # leaf of the timed step's backward pass shares
    limit = inf if limits is None else limits.get("grad_norm_gap_median")
    if limit is not None:
        out.append(compare._entry(
            "grad_norm_gap_median",
            np.median(leaf_gaps(got["g1"], want["g1"])), limit))
    for i, (a, b) in enumerate(zip(got["losses"], want["losses"])):
        for term, j in (("main", 1), ("mtp", 2)):
            name = f"{term}_loss_gap_step{i + 1}"
            limit = inf if limits is None else limits.get(name)
            if limit is not None:
                out.append(compare._entry(
                    name, abs(a[j] - b[j]) / max(abs(b[j]), 1e-30), limit))
    limit = inf if limits is None else limits.get("routing_mismatch_share")
    if limit is not None:
        out.append(compare._entry(
            "routing_mismatch_share", routing_mismatch(
                got["chosen"], want["chosen"]), limit))
    out.append(compare._entry("moe_dropped_assignments", dropped, 0.0))
    return out + verdicts


def leaf_gaps(got, want):
    """Every leaf's gap as ``compare.worst_leaf_gap`` measures the worst:
    between the two norms of a leaf, against the reference's norm of that
    leaf or of the median leaf, whichever is larger."""
    from perf import compare

    _, w = compare._flat(want)
    _, g = compare._flat(got)
    return np.abs(g - w) / np.maximum(w, np.median(w))


def routing_mismatch(got, want):
    """Share of the (row, expert layer, position) choices of ``want`` that
    ``got`` did not make; both (rows, expert layers, seq, top_k), -1 where a
    position has none (the MTP block's last)."""
    rows = min(got.shape[0], want.shape[0])
    got, want = got[:rows], want[:rows]
    same = (got[..., :, None] == want[..., None, :]).any(-2)
    counted = want >= 0
    return float(1.0 - (same & counted).sum() / counted.sum())


def check(st, ctx):
    """The first three steps against the float32 reference (PERF.md 2)."""
    return compare_run(st.got, reference(st, st.seed), st.cell["limits"],
                       getattr(st, "dropped", 0.0))


def study(cell, config, seeds, ctx, controls=3):
    """Readings for the limits, and the proof that the comparison fails what
    it has to: the program against the reference on every seed, and on the
    first ``controls`` seeds the fp8 control and the planted faults (the
    reference put in the program's place) against the reference. One
    process; yields (kind, seed, compared, readings)."""
    st = build(cell, config)
    gots = {}
    for seed in seeds:
        start_run(st, seed, ctx)
        gots[seed] = st.got
    release(st)
    for n, seed in enumerate(seeds):
        want = reference(st, seed)
        kinds = [("program", gots[seed])]
        if n < controls:
            still = lambda norms: {k: 0.0 * v for k, v in norms.items()}
            kinds += [
                ("control_fp8", reference(st, seed, precision="fp8")),
                ("fault_half_batch",
                 reference(st, seed, keep_rows=st.batch // 2)),
                ("fault_no_routed_experts",
                 reference(st, seed, routed=False)),
                ("fault_no_mtp_term", reference(st, seed, mtp=False)),
                ("fault_state_unchanged", dict(
                    want, g1=still(want["g1"]), delta=still(want["delta"])))]
        for kind, got in kinds:
            yield (kind, seed, compare_run(got, want, cell["limits"]),
                   compare_run(got, want, None))
