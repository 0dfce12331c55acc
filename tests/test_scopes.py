"""The program's names on the device: step phases, kernel names, the scope
join, one clock for host spans.

What the chip trace speaks (``monitor/goodput/scopes.py``) is closed like
the goodput phases and held to its registry here, lint-style: every
``pl.pallas_call`` of the tree names a registered kernel, every
``step_phase`` a registered phase. Then the names are followed to where
a capture finds them: the kernels' ``kernel_metadata`` in a TPU lowering
(made on this host: nothing runs), the phases in a compiled step's
``op_name`` paths, ``scope_map`` on a cut of a real v5e module and real
v5e op texts, the timeline reader on a TPU-layout capture whose event
names are whole HLO texts, and a goodput span in a real CPU capture.
"""

import ast
import collections
import glob
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

from apex_tpu.analysis.hlo.parser import parse_hlo_module, parse_instruction
from apex_tpu.monitor import goodput
from apex_tpu.monitor.goodput import scopes
from apex_tpu.monitor.xray import timeline
from apex_tpu.monitor.xray.timeline import analyzer
from apex_tpu.monitor.xray.timeline import hlo_scopes as sm
from apex_tpu.ops import _dispatch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures")


def _sources():
    for path in sorted(glob.glob(
            os.path.join(REPO, "apex_tpu", "**", "*.py"), recursive=True)):
        with open(path) as f:
            yield os.path.relpath(path, REPO), ast.parse(f.read())


def _calls(tree, name):
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            func = node.func
            callee = (func.id if isinstance(func, ast.Name)
                      else func.attr if isinstance(func, ast.Attribute)
                      else None)
            if callee == name:
                yield node


# ---------------------------------------------------------------------------
# the registries are closed, and the tree keeps to them


class TestRegistries:
    def test_unknown_names_are_refused(self):
        with pytest.raises(ValueError, match="closed"):
            scopes.step_phase("warmup")
        with pytest.raises(ValueError, match="closed"):
            scopes.kernel_metadata("my_kernel")
        assert scopes.kernel_metadata("ln_fwd") == {"kernel": "ln_fwd"}
        assert len(set(scopes.KERNELS)) == len(scopes.KERNELS)
        assert len(set(scopes.STEP_PHASES)) == len(scopes.STEP_PHASES)
        # a step phase is not a run phase: one word never means both a
        # device scope and a host span ("step" is the host's)
        assert not set(scopes.STEP_PHASES) & set(goodput.PHASES)

    def test_every_pallas_call_names_a_registered_kernel(self):
        seen = collections.Counter()
        for rel, tree in _sources():
            for call in _calls(tree, "pallas_call"):
                kw = {k.arg: k.value for k in call.keywords}
                where = f"{rel}:{call.lineno}"
                assert "metadata" in kw, f"{where}: no metadata="
                meta = kw["metadata"]
                assert (isinstance(meta, ast.Call)
                        and meta.func.id == "kernel_metadata"
                        and isinstance(meta.args[0], ast.Constant)), (
                    f"{where}: metadata= is not kernel_metadata(<literal>)")
                kernel = meta.args[0].value
                assert kernel in scopes.KERNELS, f"{where}: {kernel!r}"
                if "name" in kw:  # a kernel has ONE name
                    assert kw["name"].value == kernel, where
                seen[kernel] += 1
        assert seen == collections.Counter(scopes.KERNELS)

    def test_every_step_phase_is_registered_and_used(self):
        used = set()
        for rel, tree in _sources():
            for call in _calls(tree, "step_phase"):
                arg = call.args[0]
                assert isinstance(arg, ast.Constant), (
                    f"{rel}:{call.lineno}: a variable phase cannot be "
                    f"checked")
                assert arg.value in scopes.STEP_PHASES, (
                    f"{rel}:{call.lineno}: {arg.value!r}")
                used.add(arg.value)
        assert used == set(scopes.STEP_PHASES)


# ---------------------------------------------------------------------------
# (a) the kernels' names reach a TPU lowering; the phases a compiled step


@pytest.fixture
def as_tpu(monkeypatch):
    monkeypatch.setattr(_dispatch, "on_tpu", lambda: True)


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _metadata_lowered_for_tpu(f, *args):
    """The ``kernel_metadata`` (a dict) of every ``tpu_custom_call`` in
    ``f`` lowered for the TPU platform ({} where a call carries none)."""
    text = jax.export.export(jax.jit(f), platforms=["tpu"])(
        *args).mlir_module()
    found = []
    for line in text.splitlines():
        if "@tpu_custom_call" in line:
            m = re.search(r'kernel_metadata = "(\{[^"]*\})"', line)
            found.append(dict(re.findall(
                r'\\22(\w+)\\22:\\22(\w+)\\22', m.group(1))) if m else {})
    return found


def _kernels_lowered_for_tpu(f, *args):
    """The ``kernel`` of every ``tpu_custom_call`` in ``f`` lowered for the
    TPU platform (None where a call carries none)."""
    return [m.get("kernel") for m in _metadata_lowered_for_tpu(f, *args)]


def _norm(op):
    from apex_tpu.ops import layer_norm, rms_norm

    def f(x, w, b):
        y = (layer_norm(x, w, b, impl="pallas") if op == "layer_norm"
             else rms_norm(x, w, impl="pallas"))
        return y.astype(jnp.float32).sum()

    args = (_sds((512, 1024), jnp.bfloat16), _sds((1024,), jnp.bfloat16),
            _sds((1024,), jnp.bfloat16))
    return jax.value_and_grad(f, (0, 1)), args


def _flash():
    from apex_tpu.ops import flash_attention

    def f(q, k, v):
        return flash_attention(q, k, v, causal=True, impl="pallas").astype(
            jnp.float32).sum()

    qkv = _sds((2, 4, 256, 64), jnp.bfloat16)
    return jax.value_and_grad(f, (0, 1, 2)), (qkv, qkv, qkv)


def _latent_flash():
    from apex_tpu.ops import rope_frequencies
    from apex_tpu.ops.attention import latent_flash_attention

    freqs = rope_frequencies(64, 256, interleaved=True)

    def f(q_nope, q_rope, kv, k_rope):
        return latent_flash_attention(
            q_nope, q_rope, kv, k_rope, freqs, heads=2, interleaved=True,
            impl="pallas").astype(jnp.float32).sum()

    bf = jnp.bfloat16
    return jax.value_and_grad(f, (0, 1, 2, 3)), (
        _sds((2, 256, 256), bf), _sds((2, 256, 128), bf),
        _sds((2, 256, 512), bf), _sds((2, 256, 64), bf))


def _paged_decode():
    from apex_tpu.ops.attention import paged_decode_attention

    def f(q, k_pool, v_pool, tables, lengths):
        return paged_decode_attention(q, k_pool, v_pool, tables, lengths,
                                      scale=0.125, impl="pallas")

    bf, i32 = jnp.bfloat16, jnp.int32
    return f, (_sds((4, 16, 64), bf), _sds((32, 16, 1024), bf),
               _sds((32, 16, 1024), bf), _sds((4, 8), i32), _sds((4,), i32))


def _flat(which):
    from apex_tpu.ops.multi_tensor import CHUNK_SIZE
    from apex_tpu.optimizers._fused_kernels import adam_flat, l2norm_flat

    flat, scalar = _sds((3 * CHUNK_SIZE,), jnp.float32), _sds((), jnp.float32)
    if which == "l2norm":
        return (lambda x: l2norm_flat(x, impl="pallas")), (flat,)

    def adam(g, p, m, v, bc1, bc2):
        return adam_flat(g, p, m, v, bc1, bc2, lr=1e-3, beta1=0.9,
                         beta2=0.999, eps=1e-8, weight_decay=0.01,
                         adam_w_mode=True, impl="pallas")

    return adam, (flat, flat, flat, flat, scalar, scalar)


@pytest.mark.usefixtures("as_tpu")
@pytest.mark.parametrize("build,expected", [
    (lambda: _norm("layer_norm"), {"ln_fwd", "ln_bwd"}),
    (lambda: _norm("rms_norm"), {"rms_fwd", "rms_bwd"}),
    (_flash, {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}),
    (_latent_flash, {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
                     "mla_rope"}),
    (_paged_decode, {"paged_decode"}),
    (lambda: _flat("adam"), {"adam_flat"}),
    (lambda: _flat("l2norm"), {"sumsq_flat"}),
], ids=["layer_norm", "rms_norm", "flash", "latent_flash", "paged_decode",
        "adam_flat", "sumsq_flat"])
def test_every_tpu_custom_call_carries_a_registered_kernel(build, expected):
    f, args = build()
    found = _kernels_lowered_for_tpu(f, *args)
    assert found and all(k in scopes.KERNELS for k in found), found
    assert set(found) == expected


@pytest.mark.usefixtures("as_tpu")
def test_the_flash_kernels_carry_their_tiles():
    """The tiles are chosen at trace time, so the compiled step is what
    says which ones ran: beside each flash kernel's name."""
    f, args = _flash()
    for meta in _metadata_lowered_for_tpu(f, *args):
        assert meta["kernel"].startswith("flash_")
        assert (meta["block_q"], meta["block_k"]) == ("256", "256")
        assert (meta["d_qk"], meta["d_v"]) == ("64", "64")
    # latent attention's calls name the score's parts instead
    f, args = _latent_flash()
    flash = [m for m in _metadata_lowered_for_tpu(f, *args)
             if m["kernel"].startswith("flash_")]
    assert len(flash) == 3
    for meta in flash:
        assert (meta["block_q"], meta["d_nope"], meta["d_rope"],
                meta["d_v"]) == ("256", "128", "64", "128")
        assert "d_qk" not in meta
    assert scopes.kernel_metadata("flash_fwd", block_q=512, block_k=128) == {
        "kernel": "flash_fwd", "block_q": "512", "block_k": "128"}


def test_the_lowerings_above_cover_the_registry():
    marks = test_every_tpu_custom_call_carries_a_registered_kernel.pytestmark
    cases = next(m for m in marks if m.name == "parametrize").args[1]
    covered = set().union(*(expected for _, expected in cases))
    assert covered == set(scopes.KERNELS)


@pytest.fixture(scope="module")
def tiny_step_scopes():
    """``scope_map`` of the tiny GPT step compiled for this host's CPU
    mesh (dp over the virtual devices, so the gradient all-reduce is
    there)."""
    from apex_tpu.parallel import parallel_state
    from apex_tpu.training import GPTTargetConfig, build_gpt_training

    cfg = GPTTargetConfig(vocab=128, layers=2, hidden=64, heads=4,
                          seq_len=32, micro_batch=1, global_batch=16)
    try:
        tr = build_gpt_training(cfg)
        state, bag = jax.eval_shape(tr.init_state), jax.eval_shape(tr.init_bag)
        scalar = _sds((), jnp.float32)
        bs = tr.batch_struct()
        compiled = tr.train_step.lower(
            *state, bag, bs, bs, scalar, scalar).compile()
    finally:
        parallel_state.destroy_model_parallel()
    module = parse_hlo_module(compiled)
    return module, sm.scope_map(module)


def test_every_instruction_of_the_tiny_step_lies_under_a_phase(
        tiny_step_scopes):
    module, table = tiny_step_scopes
    stray = [
        (i.name, i.opcode, i.op_name) for i in module.instructions()
        if i.opcode not in sm.STRUCTURAL
        and table[i.name].phase not in scopes.STEP_PHASES
    ]
    assert not stray, stray[:10]
    assert len(table) > 500


def test_the_tiny_step_shows_every_phase_and_both_directions(
        tiny_step_scopes):
    _, table = tiny_step_scopes
    parts = {s.part for s in table.values() if s.how == "own"}
    assert parts >= {"forward", "backward", "grad_sync", "unscale",
                     "optimizer", "guard"}
    modules = {s.module for s in table.values()}
    assert "transformer/layer_*/self_attention/query_key_value" in modules
    # what the optimizer's cond runs is the optimizer's
    assert any(s.how == "caller" and s.phase == "optimizer"
               for s in table.values()) or all(
        s.phase != sm.UNATTRIBUTED for s in table.values()
        if s.how != "none")


# ---------------------------------------------------------------------------
# (b) scope_map on a cut of the real v5e module and real v5e op texts


@pytest.fixture(scope="module")
def snippet():
    with open(os.path.join(FIXTURES, "v5e_step_snippet.hlo.txt")) as f:
        return f.read()


@pytest.fixture(scope="module")
def op_texts():
    with open(os.path.join(FIXTURES, "v5e_op_texts.json")) as f:
        return json.load(f)["op_texts"]


class TestScopeMap:
    @pytest.mark.parametrize("path,want", [
        ("jit(train_step)/forward_backward/jvp(vmap(GPTModel))/transformer/"
         "layer_7/mlp/dense_h_to_4h/dot_general",
         ("forward_backward", "forward",
          "transformer/layer_*/mlp/dense_h_to_4h")),
        ("jit(train_step)/forward_backward/transpose(jvp(vmap(GPTModel)))/"
         "transformer/layer_0/self_attention/query_key_value/dot_general",
         ("forward_backward", "backward",
          "transformer/layer_*/self_attention/query_key_value")),
        # a custom_vjp's backward rule: transpose(<the scope>)
        ("jit(train_step)/forward_backward/transpose(forward_backward)/"
         "jvp(vmap(GPTModel))/transformer/layer_3/input_layernorm/ln_bwd/"
         "pallas_call",
         ("forward_backward", "backward",
          "transformer/layer_*/input_layernorm")),
        ("jit(train_step)/optimizer/cond/branch_1_fun/mul",
         ("optimizer", None, "")),
        ("jit(train_step)/guard/jit(_where)/select_n", ("guard", None, "")),
        # XLA joins merged ops' paths with ';': the first speaks
        ("jit(train_step)/unscale/mul;jit(train_step)/guard/add",
         ("unscale", None, "")),
        ("jit(train_step)/forward_backward/jvp(vmap(GPTModel))/embedding/"
         "word_embeddings/jit(_take)/gather",
         ("forward_backward", "forward", "embedding/word_embeddings")),
        ("params['params']['embedding']", (sm.UNATTRIBUTED, None, "")),
        ("", (sm.UNATTRIBUTED, None, "")),
    ])
    def test_classify_path(self, path, want):
        assert sm.classify_path(path) == want

    def test_multi_line_kernel_metadata_does_not_end_the_computation(
            self, snippet):
        """A filled ``kernel_metadata`` prints as multi-line JSON whose
        last line STARTS with ``}}``: the parser must neither close the
        computation there nor lose the ``metadata=`` that follows."""
        module = parse_hlo_module(snippet)
        by_name = {i.name: i for i in module.instructions()}
        ln = by_name["ln_fwd.49"]
        assert ln.computation == "main.1616"
        assert ln.custom_call_target == "tpu_custom_call"
        assert dict(ln.kernel_metadata) == {"kernel": "ln_fwd"}
        assert ln.op_name.endswith("input_layernorm/ln_fwd/pallas_call")
        assert ln.operands == ("reshape.852", "bitcast.3", "bitcast.3")
        # everything after it is still in the entry computation
        assert by_name["cond.882"].computation == "main.1616"
        assert by_name["cond.882"].calls == (
            "region_1009.1021.clone", "region_1010.1022.clone")
        assert module.entry_name == "main.1616"
        assert [p.index for p in module.entry_params] == [0, 1, 2]

    def test_phase_direction_module_kernel(self, snippet):
        table = sm.scope_map(snippet)

        def got(name):
            s = table[name]
            return s.part, s.module, s.kernel, s.how

        ln = "transformer/layer_*/input_layernorm"
        attn = "transformer/layer_*/self_attention"
        assert got("ln_fwd.49") == ("forward", ln, "ln_fwd", "own")
        assert got("self_attention.117") == (
            "backward", attn, "flash_bwd_dq", "own")
        # a fusion XLA left without metadata speaks for what it fused
        assert got("fusion.895") == (
            "backward", "word_embeddings.attend", None, "fused")
        # a metadata-less copy belongs to the op it feeds
        assert got("copy.2098") == ("backward", attn, None, "flow")
        assert table["copy-done.333"].how == "flow"
        # a custom-call nobody registered is no kernel of ours
        assert got("foreign.1")[2] is None
        assert got("is-finite_reduce_fusion.1")[0] == "unscale"
        assert table["orphan.1"].phase == sm.UNATTRIBUTED
        assert table["params.1"].how == "none"

    def test_cond_bodies_belong_to_the_caller(self, snippet):
        table = sm.scope_map(snippet)
        for copy in ("copy.4016", "copy.4017", "copy.5001"):
            assert (table[copy].phase, table[copy].how) == (
                "optimizer", "caller")
        assert table["multiply_add_fusion.4"].phase == "optimizer"

    def test_a_fusion_belongs_where_most_of_it_was_traced(self, snippet):
        """On the v5e XLA fuses the Adam update and the non-finite check
        of the new parameters into one pass and names it after the check
        (``guard/reduce_and``): booked by its own name, 14 ms a step of
        optimizer would read as guard."""
        table = sm.scope_map(snippet)
        fused = table["is-finite_reduce_fusion.291"]
        assert (fused.phase, fused.how) == ("optimizer", "fused")
        assert fused.mix == (("optimizer", 3), ("guard", 1))
        # one phase inside: nothing mixed, and the own name stands
        assert table["is-finite_reduce_fusion.1"].mix == ()
        assert table["is-finite_reduce_fusion.1"].how == "own"
        assert table["multiply_add_fusion.4"].how == "own"

    def test_real_v5e_op_texts_parse_to_their_instruction(self, op_texts):
        by_name = {}
        for text in op_texts:
            ins = parse_instruction(text)
            by_name[ins.name] = ins
        assert set(by_name) == {
            "fusion.895", "exponential_reduce_fusion", "self_attention.117"}
        assert by_name["fusion.895"].opcode == "fusion"
        assert by_name["fusion.895"].calls == ("fused_computation.2868",)
        attn = by_name["self_attention.117"]
        assert attn.opcode == "custom-call"
        assert attn.custom_call_target == "tpu_custom_call"
        assert attn.kernel_metadata == ()  # the parent's: empty
        assert "bitcast.2550" in attn.operands
        assert parse_instruction("jit_train_step(1519)") is None


# ---------------------------------------------------------------------------
# (c) the timeline reader on a TPU-layout capture


def _tpu_capture(op_texts):
    """A v5e-layout trace: device ops named by whole HLO texts on the
    ``XLA Ops`` lane (a ``cond`` with its body nested inside), async
    copies on their own lane, two program runs, host annotations."""
    attn_old = next(t for t in op_texts if t.startswith("%self_attention"))
    ln = ('%ln_fwd.49 = bf16[2,4096,1024]{2,1,0:T(8,128)(2,1)S(1)} '
          'custom-call(bf16[2,4096,1024]{2,1,0:T(8,128)(2,1)} %reshape.852, '
          'f32[1,1024]{1,0:T(1,128)} %bitcast.3, f32[1,1024]{1,0:T(1,128)} '
          '%bitcast.3), custom_call_target="tpu_custom_call", '
          'frontend_attributes={kernel_metadata={\n"kernel":"ln_fwd"\n}}')
    attn = attn_old.replace("kernel_metadata={}",
                            'kernel_metadata={\n"block_k":"512",\n'
                            '"block_q":"1024",\n"kernel":"flash_bwd_dq"\n}')
    cond = ('%cond.882 = (f32[1024,1024]{1,0:T(8,128)}, f32[1024]{0:T(1024)})'
            ' conditional(pred[]{:T(512)} %gate.1, (f32[1024,1024], '
            'f32[1024]) %tuple.30, (f32[1024,1024], f32[1024]) %tuple.30), '
            'true_computation=%region_1009.1021.clone, '
            'false_computation=%region_1010.1022.clone')
    body = ('%multiply_add_fusion.4 = f32[1024,1024]{1,0:T(8,128)} fusion('
            'f32[1024,1024]{1,0:T(8,128)} %get-tuple-element.11), kind=kLoop,'
            ' calls=%fused_computation.9')
    copy = '%copy.5001 = f32[1024]{0:T(1024)} copy(f32[1024]{0:T(1024)} %gte)'
    stray = '%mystery.7 = f32[8]{0} add(f32[8]{0} %a, f32[8]{0} %b)'

    def meta(kind, pid, name, tid=None):
        e = {"ph": "M", "name": kind, "pid": pid, "args": {"name": name}}
        if tid is not None:
            e["tid"] = tid
        return e

    def ev(name, ts, dur, pid=3, tid=1, **args):
        return {"ph": "X", "name": name, "pid": pid, "tid": tid, "ts": ts,
                "dur": dur, "args": args}

    events = [
        meta("process_name", 3, "/device:TPU:0"),
        meta("thread_name", 3, "XLA Ops", 1),
        meta("thread_name", 3, "Async XLA Ops", 2),
        meta("thread_name", 3, "XLA Modules", 3),
        meta("thread_name", 3, "Steps", 4),
        meta("process_name", 7, "/host:CPU"),
        meta("thread_name", 7, "python3", 1),
    ]
    for run, t0 in enumerate((0.0, 1000.0)):
        events += [
            ev("jit_train_step(1519)", t0, 600.0, tid=3),
            ev(str(run), t0, 600.0, tid=4),
            ev(ln, t0, 100.0),
            ev(attn, t0 + 100.0, 200.0),
            ev(cond, t0 + 300.0, 300.0),         # 300 = 200 + 50 + own 50
            ev(body, t0 + 310.0, 200.0),
            ev(copy, t0 + 520.0, 50.0),
            # overlaps the ops; occupies no core
            ev("%copy-start.333 = (bf16[8]) copy-start(bf16[8] %x)",
               t0 + 50.0, 400.0, tid=2),
            ev("data_wait", t0 + 600.0, 380.0, pid=7),
        ]
    events.append(ev(stray, 1600.0, 20.0))
    events.append(ev("snapshot", 1620.0, 500.0, pid=7))
    return {"traceEvents": events}


class TestTpuLayout:
    def test_names_are_cut_from_whole_hlo_texts(self, op_texts):
        tl = timeline.parse_trace(_tpu_capture(op_texts))
        ops = tl.device_op_events()
        names = collections.Counter(e.name for e in ops)
        assert names == {
            "ln_fwd.49": 2, "self_attention.117": 2, "cond.882": 2,
            "multiply_add_fusion.4": 2, "copy.5001": 2, "mystery.7": 1}
        ln = next(e for e in ops if e.name == "ln_fwd.49")
        assert ln.args["custom_call_target"] == "tpu_custom_call"
        assert ln.args["kernel_metadata"] == {"kernel": "ln_fwd"}
        assert ln.args["opcode"] == "custom-call"
        # no v5e event carries hlo_op; the async lane is not an op lane
        assert all(e.hlo_op is None for e in ops)
        assert tl.program_runs() == 2
        assert timeline.classify_op(ops[0].name) == "compute"

    def test_self_time_counts_a_cond_body_once(self, op_texts):
        tl = timeline.parse_trace(_tpu_capture(op_texts))
        selfs = collections.Counter()
        for e, t in timeline.self_times(tl.device_op_events()):
            selfs[e.name] += t
        assert selfs["cond.882"] == pytest.approx(2 * 50.0)
        assert selfs["multiply_add_fusion.4"] == pytest.approx(400.0)
        assert sum(selfs.values()) == pytest.approx(2 * 600.0 + 20.0)

    def test_scope_join_hand_counted(self, snippet, op_texts):
        tl = timeline.parse_trace(_tpu_capture(op_texts))
        rep = timeline.analyze(tl, module=parse_hlo_module(snippet))
        sc = rep.scopes
        assert sc.n_steps == 2 and sc.n_devices == 1
        assert sc.busy_us == pytest.approx(1220.0)
        assert sc.self_us == pytest.approx(sc.busy_us)  # the identity
        assert sc.by_part == pytest.approx({
            "forward": 200.0, "backward": 400.0, "optimizer": 600.0,
            sm.UNATTRIBUTED: 20.0})
        assert sc.by_kernel == pytest.approx(
            {"ln_fwd": 200.0, "flash_bwd_dq": 400.0})
        assert sc.kernel_calls == {"ln_fwd": 2, "flash_bwd_dq": 2}
        # block_q x block_k as the compiled module's kernel_metadata has them
        assert sc.kernel_tiles == {"flash_bwd_dq": {"1024x512": 2}}
        assert sc.by_module[
            ("backward", "transformer/layer_*/self_attention")
        ] == pytest.approx(400.0)
        assert sc.by_op[("optimizer", "copy")] == pytest.approx(100.0)
        assert sc.by_how["caller"] == pytest.approx(100.0)
        assert sc.spanning == {}  # multiply_add_fusion.4 is all optimizer
        assert sc.by_how["no instruction"] == pytest.approx(20.0)
        assert sc.attributed_fraction == pytest.approx(1200.0 / 1220.0)
        # the window runs to the end of the snapshot annotation: the gaps
        # between and after the runs go to the spans that cover them,
        # instant by instant (the first gap's last 20 us lie under none;
        # snapshot, begun later, is innermost where data_wait overlaps it)
        assert sc.idle_by_annotation == pytest.approx(
            {"data_wait": 380.0, "snapshot": 500.0,
             analyzer.NO_ANNOTATION: 20.0})
        text = rep.summary()
        assert "by Pallas kernel" in text and "flash_bwd_dq" in text
        assert "1 calls a step  tiles 1024x512" in text
        kinds = [r for r in rep.to_records() if "part" in r or "kernel" in r]
        assert {r.get("part") for r in kinds} >= {"forward", "optimizer"}

    def test_idle_goes_to_the_innermost_annotation(self):
        """A serving tick's parts nest in the goodput ``decode`` span: an
        idle gap across them is split between the parts, the span keeping
        only what no part covers, and what no annotation covers is
        (no annotation)."""
        def host(name, ts, dur):
            return timeline.TraceEvent(name, 7, 1, ts, dur)

        spans = [host("decode", 100.0, 100.0),
                 host("engine.book", 110.0, 80.0),
                 host("engine.dispatch", 120.0, 30.0),
                 host("engine.wait", 160.0, 20.0),
                 host("engine.book", 200.0, 10.0)]
        got = analyzer.idle_by_innermost([(90.0, 170.0), (185.0, 230.0)],
                                         spans)
        assert got == pytest.approx({
            analyzer.NO_ANNOTATION: 10.0 + 20.0, "decode": 10.0 + 10.0,
            "engine.book": 10.0 + 10.0 + 5.0 + 10.0,
            "engine.dispatch": 30.0, "engine.wait": 10.0})

    def test_trace_event_export_form_long_name_and_tf_op(self, snippet,
                                                         op_texts):
        """What ``*.trace.json.gz`` of a v5e capture holds (PR 25's look at
        one): the short instruction name, the whole text as
        ``args.long_name``, the ``op_name`` path as ``args.tf_op`` where XLA
        kept one. The raw xplane has the text as the NAME: both read the
        same."""
        raw = _tpu_capture(op_texts)
        tf_op = {
            "ln_fwd.49": "jit(train_step)/forward_backward/jvp(vmap(GPTModel))"
                         "/transformer/layer_0/input_layernorm/ln_fwd/"
                         "pallas_call:",
            "multiply_add_fusion.4": "jit(train_step)/optimizer/cond/"
                                     "branch_1_fun/add:",
        }
        for e in raw["traceEvents"]:
            if e.get("ph") == "X" and e["name"].startswith("%"):
                short = e["name"].split(" = ")[0].lstrip("%")
                e["args"] = {"long_name": e["name"], "hlo_category": "x"}
                if short in tf_op:
                    e["args"]["tf_op"] = tf_op[short]
                e["name"] = short
        exported = timeline.parse_trace(raw)
        whole = timeline.parse_trace(_tpu_capture(op_texts))
        assert [e.name for e in exported.device_op_events()] == [
            e.name for e in whole.device_op_events()]
        ln = next(e for e in exported.device_op_events()
                  if e.name == "ln_fwd.49")
        assert ln.args["kernel_metadata"] == {"kernel": "ln_fwd"}
        assert ln.args["custom_call_target"] == "tpu_custom_call"
        # with the compiled module: the same table either way
        module = parse_hlo_module(snippet)
        assert timeline.analyze(exported, module=module).scopes.by_part == (
            pytest.approx(
                timeline.analyze(whole, module=module).scopes.by_part))
        # with no module the events' own tf_op still place what they can,
        # and their own text still names the kernels
        bare = timeline.analyze(exported).scopes
        assert bare.by_part == pytest.approx({
            "forward": 200.0, "optimizer": 400.0, sm.UNATTRIBUTED: 620.0})
        assert bare.by_how["event"] == pytest.approx(600.0)
        assert bare.by_kernel == pytest.approx(
            {"ln_fwd": 200.0, "flash_bwd_dq": 400.0})
        assert bare.kernel_tiles == {"flash_bwd_dq": {"1024x512": 2}}
        # a capture that says nothing of scopes gets no table
        assert timeline.analyze(whole).scopes is None

    def test_an_unregistered_kernel_is_named_as_such(self, snippet,
                                                     op_texts):
        data = _tpu_capture(op_texts)
        data["traceEvents"].append({
            "ph": "X", "pid": 3, "tid": 1, "ts": 1700.0, "dur": 10.0,
            "name": '%foreign.1 = bf16[8]{0} custom-call(bf16[8]{0} %x), '
                    'custom_call_target="tpu_custom_call", '
                    'frontend_attributes={kernel_metadata={}}', "args": {}})
        rep = timeline.analyze(timeline.parse_trace(data),
                               module=parse_hlo_module(snippet))
        assert rep.scopes.by_kernel["(unregistered)"] == pytest.approx(10.0)

    def test_time_in_fusions_that_span_phases_is_reported(self, snippet,
                                                          op_texts):
        data = _tpu_capture(op_texts)
        data["traceEvents"].append({
            "ph": "X", "pid": 3, "tid": 1, "ts": 1700.0, "dur": 30.0,
            "name": "%is-finite_reduce_fusion.291 = pred[]{:T(512)} fusion("
                    "f32[1024,1024]{1,0:T(8,128)} %multiply_add_fusion.4), "
                    "kind=kInput, calls=%fused_computation.1757", "args": {}})
        rep = timeline.analyze(timeline.parse_trace(data),
                               module=parse_hlo_module(snippet))
        assert rep.scopes.spanning == pytest.approx({
            ("is-finite_reduce_fusion", "optimizer 3 + guard 1"): 30.0})
        assert rep.scopes.by_part["optimizer"] == pytest.approx(630.0)
        assert "guard" not in rep.scopes.by_part
        assert "fusions that span phases" in rep.summary()

    def test_cli_reads_capture_and_hlo(self, tmp_path, op_texts, capsys):
        import gzip

        from apex_tpu.monitor.xray.timeline.__main__ import main

        d = tmp_path / "plugins" / "profile" / "run1"
        d.mkdir(parents=True)
        with gzip.open(d / "h.trace.json.gz", "wt") as f:
            json.dump(_tpu_capture(op_texts), f)
        hlo = os.path.join(FIXTURES, "v5e_step_snippet.hlo.txt")
        assert main([str(tmp_path), "--hlo", hlo]) == 0
        out = capsys.readouterr().out
        assert "98.36% under a registered phase" in out
        assert "snapshot 0.500 ms" in out
        assert main([str(tmp_path), "--hlo", str(tmp_path / "nope")]) == 1


# ---------------------------------------------------------------------------
# (d) one clock: a goodput span is in the capture


def test_a_goodput_span_is_on_the_profilers_clock(tmp_path):
    from apex_tpu.utils.timers import trace

    x = jnp.ones((64, 64))
    (x @ x).block_until_ready()
    with trace(str(tmp_path)):
        with goodput.span("data_wait"):
            (x @ x).block_until_ready()
        held = goodput.begin_span("snapshot")
        (x @ x).block_until_ready()
        held.close()
        held.close()  # idempotent: the annotation is exited once
    tl, _ = timeline.parse_logdir(str(tmp_path))
    ops = {(e.pid, e.tid) for e in tl.device_op_events()}
    for phase in ("data_wait", "snapshot"):
        found = [e for e in tl.events if e.name == phase]
        assert len(found) == 1, phase
        assert (found[0].pid, found[0].tid) not in ops
        assert found[0].dur > 0


def test_the_snapshot_ring_books_its_copy(tmp_path):
    """``RollbackBuffer.snapshot`` opens the ``snapshot`` phase: in the
    run's ledger (through the process-global router) and thereby, in a
    capture, on the profiler's clock."""
    from apex_tpu.monitor.router import MemorySink, MetricRouter
    from apex_tpu.resilience.rollback import RollbackBuffer

    mem = MemorySink()
    router = MetricRouter([mem])
    goodput.set_router(router)
    try:
        ring = RollbackBuffer(2, interval=10)
        assert ring.maybe_snapshot(10, {"w": jnp.ones((4,))})
        assert not ring.maybe_snapshot(11, {"w": jnp.ones((4,))})
    finally:
        goodput.set_router(None)
    spans = [r for r in mem.records if r["kind"] == "span"]
    assert [(r["phase"], r["step"]) for r in spans] == [("snapshot", 10)]
    assert "snapshot" in goodput.PHASES
    assert (goodput.PHASE_PRIORITY.index("rollback")
            < goodput.PHASE_PRIORITY.index("snapshot")
            < goodput.PHASE_PRIORITY.index("compile"))
