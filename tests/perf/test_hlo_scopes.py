"""perf/hlo_scopes.py on a small hand-written module (fixtures/step.hlo.txt),
rule by rule, and against the program's own reader on a step this tree
compiles. The module: a forward matmul fusion under ``mla_project``, the
forward and dq flash kernels (one still named ``self_attention.3``, the
other ``name=``d), a grouped matmul under a scope JAX wrapped in transforms,
the gradient all-reduce, and the optimizer's gated ``cond`` whose apply
branch holds a fusion of two optimizer ops and one guard op, named after the
guard's, and a copy with no metadata at all."""

import os

import pytest

from _bench import FIXTURES, GPT_TINY, PRETRAIN_TINY, load

hs = load("hlo_scopes.py")
PHASES = ("forward_backward", "grad_sync", "unscale", "optimizer", "guard")
MODEL = ("mla_project", "moe_experts", "mtp")


@pytest.fixture(scope="module")
def text():
    with open(os.path.join(FIXTURES, "step.hlo.txt")) as f:
        return f.read()


@pytest.fixture(scope="module")
def scopes(text):
    return hs.scope_map(text, PHASES, MODEL)


def test_instructions_are_read_whole(text):
    by_name = {i.name: i for i in hs.instructions(text)}
    fwd = by_name["self_attention.3"]  # spans five lines of the text
    assert fwd.opcode == "custom-call" and fwd.operands == ("fusion.1",)
    assert fwd.kernel_metadata == {"kernel": "flash_fwd", "block_q": "1024",
                                   "block_k": "512"}
    assert fwd.op_name.endswith("self_attention/pallas_call")
    assert fwd.computation == "main.9"
    cond = by_name["conditional.1"]
    assert cond.calls == ("keep_branch", "apply_branch")
    assert by_name["is-finite_reduce_fusion"].calls == ("fused_adam",)
    assert by_name["multiply.1"].computation == "fused_adam"
    assert by_name["gmm.4"].kernel_metadata == {}
    assert by_name["copy.1"].op_name == ""


@pytest.mark.parametrize("name,part,scope,kernel,how", [
    # its own op_name: phase, direction by JAX's transpose mark, last scope
    ("self_attention.3", "forward", "", "flash_fwd", "own"),
    ("flash_bwd_dq.7", "backward", "", "flash_bwd_dq", "own"),
    # the innermost scope, though JAX wrapped it in transforms
    ("gmm.4", "backward", "moe_experts", None, "own"),
    ("all-reduce.1", "grad_sync", "", None, "own"),
    ("conditional.1", "optimizer", "", None, "own"),
    # a fusion named after the guard's op holds two optimizer ops to one
    ("is-finite_reduce_fusion", "optimizer", "", None, "fused"),
    # the same fused computation, called from a matmul's fusion whose own
    # name says forward: the majority of what it fused still wins
    ("fusion.1", "optimizer", "", None, "fused"),
    # no metadata, in a branch of the cond: the caller's
    ("copy.7", "optimizer", "", None, "caller"),
    # no metadata, in the entry: the nearest user's
    ("copy.1", "optimizer", "", None, "flow"),
    # a get-tuple-element repeats the call's attributes and runs nothing
    ("gte.9", "(unattributed)", "", None, "none"),
])
def test_every_rule_places_its_instruction(scopes, name, part, scope, kernel,
                                           how):
    s = scopes[name]
    assert (s.part, s.scope, s.kernel, s.how) == (part, scope, kernel, how)


def test_paths_are_cut_outside_parentheses_and_the_last_scope_wins():
    path = ("jit(train_step)/forward_backward/transpose(jvp(GPTModel))/mtp/"
            "mtp/layer/self_attention/mla_project/q_b_proj/dot_general")
    assert hs.split_path(path)[2] == "transpose(jvp(GPTModel))"
    assert hs.classify_path(path, PHASES, MODEL) == ("backward",
                                                     "mla_project")
    assert hs.classify_path(path.replace("/mla_project", ""), PHASES,
                            MODEL) == ("backward", "mtp")
    assert hs.classify_path("jit(f)/unscale/mul;jit(f)/guard/x", PHASES,
                            MODEL) == ("unscale", "")
    assert hs.classify_path("jit(convert)/convert_element_type", PHASES,
                            MODEL) == ("(unattributed)", "")


def test_an_events_cut_text_still_gives_its_kernel():
    assert hs.kernel_metadata(
        'frontend_attributes={kernel_metadata={\n"kernel":"rms_fwd"\n}}'
    ) == {"kernel": "rms_fwd"}
    assert hs.kernel_metadata('kernel_metadata={"kernel":"rms_f') == {}
    assert hs.kernel_metadata("metadata={op_name=\"x\"}") == {}
    with pytest.raises(ValueError):
        hs.balanced("{never closed", 0)


def test_the_copy_agrees_with_the_programs_reader_on_a_compiled_step():
    """The tiny fixture cell's step as this tree compiles it (CPU text: no
    Mosaic kernel in it): every instruction gets the phase and the kernel
    the program's own ``scope_map`` gives it."""
    from apex_tpu.monitor.xray.timeline import scope_map

    drv = load("drivers/gpt_pretrain.py")
    st = drv.build(PRETRAIN_TINY, GPT_TINY)
    step_text = drv.hlo_text(st)
    drv.release(st)
    mine = hs.scope_map(step_text, **drv.scope_names())
    theirs = scope_map(step_text)
    assert set(mine) == set(theirs) and len(mine) > 500
    assert {n: s.part for n, s in mine.items()} == {
        n: s.part for n, s in theirs.items()}
    assert {n: s.how for n, s in mine.items()} == {
        n: s.how for n, s in theirs.items()}
    parts = {s.part for s in mine.values()}
    assert {"forward", "backward", "unscale", "optimizer", "guard"} <= parts
