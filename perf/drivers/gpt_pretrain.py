"""Driver: GPT pretraining through the library's step builder.

The window drives what ``examples/gpt/pretrain_gpt.py:main`` builds its hot
path from (``apex_tpu.training.build_gpt_training`` from
``pretrain_gpt.target_config(parse_args(argv))``), with the loop ``main`` runs
there: host batch -> device, one ``train_step``, fetch loss and verdict. The
weights come from the benchmark's seed (``perf/reference/gpt.py``), not from
the program's initialiser, so that the reference can follow the same run.

Set-up builds ONE compiled step with its state, drives it through the first
three steps (the same call and feed as the window's) while keeping what the
output check needs - each loss, Adam's first moment after step one (the first
gradient as the optimizer got it), the parameters' change after step three -
and hands that same object to the window.
"""

import dataclasses
import importlib.util
import os
import sys
import time

import numpy as np

CHECK_STEPS = 3

#: the checkout that holds the system under test (this file's own, whatever
#: directory the harness found the cell in)
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.realpath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def _load_example(relpath):
    path = os.path.join(REPO, relpath)
    spec = importlib.util.spec_from_file_location("perf_example_gpt", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class State:
    pass


def build(cell, config):
    """What does not depend on the seed: the training object, its compiled
    step (lowered and compiled once, then called as the compiled object)."""
    import jax
    import jax.numpy as jnp

    from apex_tpu import training

    st = State()
    st.cell, st.config = cell, config
    st.heads = config["n_head"]
    st.dims = dict(layers=config["n_layer"], hidden=config["n_embd"],
                   vocab=config["assumed"]["padded_vocab_size"],
                   max_positions=cell["seq_len"])
    gpt = _load_example("examples/gpt/pretrain_gpt.py")
    args = gpt.parse_args([
        "--layers", str(config["n_layer"]), "--hidden", str(config["n_embd"]),
        "--heads", str(config["n_head"]), "--vocab", str(st.dims["vocab"]),
        "--seq-len", str(cell["seq_len"]),
        "--micro-batch", str(cell["micro_batch"]),
        "--global-batch", str(cell["global_batch"]),
    ])
    tcfg = dataclasses.replace(
        gpt.target_config(args, journal_on=False),
        max_devices=int(cell.get("chips", 1)))
    st.lr, st.weight_decay = tcfg.lr, tcfg.weight_decay
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


def start_run(st, seed, ctx):
    """Corpus, weights and state from the seed, then the first three steps
    through the window's own call, keeping what the output check reads."""
    import jax

    from perf import gpt_tree
    from perf.reference import gpt as ref

    tr, layers, heads = st.training, st.dims["layers"], st.heads
    st.seed = seed
    # the seeded corpus, on the host as main()'s dataset is
    st.corpus = _corpus(st, seed)
    # weights on the device in one jitted call; the rest of the carried
    # state as GPTTraining.init_state builds it
    w0 = ref.init_weights(ref.seed_key(seed), **st.dims)
    params = jax.device_put(jax.jit(gpt_tree.to_program)(w0), tr.replicated)
    del w0  # made again after the steps: they need the memory
    opt_state = jax.jit(tr.opt.init, out_shardings=tr.replicated)(params)
    st.carry = (params, opt_state,
                jax.device_put(tr.scaler.init(), tr.replicated),
                jax.device_put(tr.sentinel.init(), tr.replicated),
                tr.init_bag())
    st.steps_done = 0

    norms = jax.jit(lambda t: ref.leaf_norms(
        gpt_tree.stacked(t, layers), heads))
    delta = jax.jit(lambda t, w: ref.leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a - b, gpt_tree.stacked(t, layers), w), heads))
    losses = []
    for i in range(CHECK_STEPS):
        loss, _ = _one_step(st, ctx)
        losses.append(loss)
        if i == 0:
            # m1 = (1 - beta1) * g1: the gradient as fused_adam got it
            g1 = {k: np.asarray(v) / 0.1 for k, v in jax.device_get(
                norms(st.carry[1].exp_avg)).items()}
    w0 = ref.init_weights(ref.seed_key(seed), **st.dims)
    st.got = {"losses": losses, "g1": g1,
              "delta": jax.device_get(delta(st.carry[0], w0)),
              "skipped": float(jax.device_get(st.carry[2].skipped))}


def _corpus(st, seed):
    from perf import loadgen

    return loadgen.token_corpus(
        seed, st.config["vocab_size"], st.cell["corpus_samples"],
        st.cell["seq_len"])


def setup(cell, config, seed, ctx):
    st = build(cell, config)
    start_run(st, seed, ctx)
    return st


def _one_step(st, ctx):
    """Host batch -> device, one train_step, fetch loss and verdict: the
    body of main()'s loop, for set-up's three steps and the window alike."""
    import jax.numpy as jnp

    with ctx.span("fetch_batch"):
        i = (st.steps_done % st.n_batches) * st.batch
        rows = st.corpus[i:i + st.batch]
        x, y = st.training.reshape_batch(rows[:, :-1], rows[:, 1:])
        x, y = jnp.asarray(x), jnp.asarray(y)
    with ctx.span("step"):
        out = st.step(*st.carry, x, y, st.zero, st.one)
        st.carry = out[:5]
    with ctx.span("fetch_loss"):
        loss, verdict = float(out[5]), int(out[6])
    st.steps_done += 1
    return loss, verdict


def window(st, seconds, ctx):
    from perf import flops

    steps, bad, log = 0, 0, []
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
        print("[gpt_pretrain] steps the sentinel flagged (step, loss, "
              "verdict, the three losses before): " + "; ".join(
                  f"{i} {l:.4f} v{v} after "
                  + "/".join(f"{x:.4f}" for x, _ in log[max(0, i - 3):i])
                  for i, (l, v) in enumerate(log) if v != 0),
              file=sys.stderr, flush=True)
    print("[gpt_pretrain] loss every 8th step: "
          + " ".join(f"{l:.3f}" for l, _ in log[::8]),
          file=sys.stderr, flush=True)
    d = st.dims
    tokens = st.batch * st.cell["seq_len"]
    per_token = flops.gpt_train_flops_per_token(
        d["layers"], d["hidden"], d["vocab"], st.cell["seq_len"])
    return {
        "attempted": steps, "failed": bad, "window_s": elapsed,
        "end_to_end": {"train_step_ms": 1e3 * elapsed / steps},
        "counters": {"steps": steps, "tokens_per_step": tokens,
                     "model_flops": per_token * tokens * steps,
                     "micro_batch": st.cell["micro_batch"],
                     "last_loss": loss},
    }


def hlo_text(st):
    """The compiled step's text, for a traced run's reduction (``perf/
    hlo_scopes.py``): each instruction's ``op_name`` path and a kernel's
    ``kernel_metadata``. Asked for before ``release`` drops the step."""
    from apex_tpu.analysis.hlo.parser import module_text

    return module_text(st.step)


def scope_names():
    """The program's registries of names, which tell ``hlo_scopes.
    scope_map`` what in an ``op_name`` path is a phase of the step and what
    a scope of the model."""
    from apex_tpu.monitor.goodput import scopes

    return {"phases": scopes.STEP_PHASES, "scopes": scopes.MODEL_SCOPES,
            "kernel_key": scopes.KERNEL_KEY}


def release(st):
    st.carry = st.step = st.training = None


def reference(st, seed, precision="f32", keep_rows=None):
    """The first three steps as the plain reference takes them (or, for the
    control and the planted fault, as a lower precision or half a batch
    would)."""
    import jax
    import jax.numpy as jnp

    from perf.reference import gpt as ref

    corpus = _corpus(st, seed)
    rows = corpus[: CHECK_STEPS * st.batch].reshape(CHECK_STEPS, st.batch, -1)
    if keep_rows is None:
        rpb = st.cell["reference_rows_per_block"]
    else:
        rpb = min(st.cell["reference_rows_per_block"], keep_rows)
    w0 = ref.init_weights(ref.seed_key(seed), **st.dims)
    losses, g1, delta = jax.device_get(ref.train_steps(
        w0, jnp.asarray(rows[:, :, :-1]), jnp.asarray(rows[:, :, 1:]),
        heads=st.heads, precision=precision, rows_per_block=rpb, lr=st.lr, weight_decay=st.weight_decay,
        steps=CHECK_STEPS, keep_rows=keep_rows))
    return {"losses": [float(x) for x in losses], "g1": g1, "delta": delta,
            "skipped": 0.0}


def check(st, ctx):
    """The first three steps against the float32 reference (PERF.md 2)."""
    from perf import compare

    return compare.training(st.got, reference(st, st.seed),
                            st.cell["limits"])


def study(cell, config, seeds, ctx, controls=3):
    """Readings for the limits, and the proof that the comparison fails what
    it has to: the program against the reference on every seed, and on the
    first ``controls`` seeds the fp8 control and the planted faults (the
    reference put in the program's place) against the reference. One
    process; yields (kind, seed, compared, readings): ``compared`` through
    the cell's limits, exactly as a run's ``check`` gives it, ``readings``
    every number against no limit."""
    from perf import compare

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
            # a step that hands back the state it was given leaves Adam's
            # moment and every parameter where they were: norms of nought,
            # which need no run (tests/perf plants it in the program)
            still = lambda norms: {k: 0.0 * v for k, v in norms.items()}
            kinds += [
                ("control_fp8", reference(st, seed, precision="fp8")),
                ("fault_half_batch",
                 reference(st, seed, keep_rows=st.batch // 2)),
                ("fault_state_unchanged", dict(
                    want, g1=still(want["g1"]), delta=still(want["delta"])))]
        for kind, got in kinds:
            yield (kind, seed, compare.training(got, want, cell["limits"]),
                   compare.training(got, want, None))
