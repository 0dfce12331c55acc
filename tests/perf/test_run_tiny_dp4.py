"""The GPT driver over four (virtual CPU) devices, as ``gpt2_345m.
pretrain_dp4`` runs it over four chips: data parallel, one micro batch a
device, gradients averaged between them every step. And the proof that the
output check fails the one fault this cell adds to the one-chip cell's: the
exchange between the devices left out, every device stepping by its own
rows' gradient.

Nothing here is a device number: the result line says ``cpu``.
"""

import io

import pytest

from _bench import GPT_TINY, PRETRAIN_TINY, e2e, fixture_root, load

run = load("run.py", name="perf_test_run_dp4")

DP4_TINY = dict(PRETRAIN_TINY, chips=4, micro_batch=1, global_batch=4)


@pytest.fixture
def root(tmp_path):
    return fixture_root(
        tmp_path, {"gpt_tiny.pretrain_dp4": DP4_TINY}, {"gpt_tiny": GPT_TINY},
        [e2e("setup_s", "s"), e2e("train_step_ms", "ms")])


def _run(root):
    return run.run_cell(root, "gpt_tiny.pretrain_dp4", 2**31 + 11, 0.3, 0,
                        allow_cpu=True, out=io.StringIO())


def test_four_devices_read_correct(root):
    line = _run(root)
    assert line["correct"] is True and line["failed"] == 0


def test_the_exchange_left_out_reads_incorrect(root, monkeypatch):
    from apex_tpu.parallel import ddp

    calls = []

    def no_exchange(grads, axis_name, **kw):
        calls.append(axis_name)
        return grads

    # build_gpt_training takes the name from the module when it is called
    monkeypatch.setattr(ddp, "all_reduce_gradients", no_exchange)
    line = _run(root)
    assert calls == ["dp"]
    assert line["correct"] is False
    over = {c["name"] for c in line["compared"] if c["value"] > c["limit"]}
    assert "grad_norm_gap" in over, line["compared"]


def test_the_exchange_is_booked_after_the_backward_pass():
    """What ``step_grad_sync_ms.train`` reads: the compiled four-device
    step's text has ops under the phase ``grad_sync``, none of them the
    forward or backward pass's, and places every collective in a phase that
    follows the backward pass. (The CPU's compiler merges the gradients'
    all-reduce with the overflow flag's, under the latter's name; on the
    chip they stand apart: PERF.md 5.)"""
    hs = load("hlo_scopes.py")
    drv = load("drivers/gpt_pretrain.py")
    st = drv.build(DP4_TINY, GPT_TINY)
    text = drv.hlo_text(st)
    drv.release(st)
    scopes = hs.scope_map(text, **drv.scope_names())
    synced = [s for s in scopes.values() if s.part == "grad_sync"]
    assert synced and not any("/forward_backward/" in s.op_name
                              for s in synced)
    exchanged = {i.name: scopes[i.name].part for i in hs.instructions(text)
                 if i.opcode.startswith("all-reduce")}
    assert exchanged and set(exchanged.values()) <= {
        "grad_sync", "unscale", "guard"}, exchanged
