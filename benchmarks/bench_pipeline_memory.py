"""Pipeline-schedule memory measurement.

Question: differentiating the pipeline forward scan stashes one boundary
activation per tick — O(M + P) for 1F1B, O(V*M + P) for the interleaved
scan — versus the reference 1F1B's O(P) in-flight bound
(/root/reference/apex/transformer/pipeline_parallel/schedules/
fwd_bwd_pipelining_without_interleaving.py:345-348).  How much does that
cost at real microbatch counts, and does ``tick_block_remat`` (nested-scan
rematerialization, schedules._scan_ticks) restore the bound?

Method: compile the full fwd+bwd step on a P-rank mesh (virtual CPU
devices) and read XLA's live-temporary high-water mark via
``apex_tpu.monitor.xray.memory_report`` (the one home of the
lower/compile/memory_analysis dance) — the same quantity a TPU HBM OOM
is about.  Sweep M with tick_block_remat in {0 (off), 8, sqrt-ish} for
both schedules.  The result is a compiled byte count on the CPU mesh, not
a device measurement.

Usage: python benchmarks/bench_pipeline_memory.py  (forces the CPU).
"""

import functools
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()

import jax

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp
import numpy as np
from apex_tpu.compat import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.monitor.xray import memory_report
from apex_tpu.parallel.pipeline import (
    forward_backward_pipelining_with_interleaving,
    forward_backward_pipelining_without_interleaving,
)

PP = 4
HID = 256
MICRO_B = 4


def stage_fn(params, x):
    h = jnp.tanh(x @ params["w1"])
    return jnp.tanh(h @ params["w2"])


def loss_fn(y, t):
    return jnp.mean((y - t) ** 2)


def temp_bytes(num_micro, block, vpp=1):
    mesh = Mesh(np.array(jax.devices()[:PP]), ("pp",))
    key = jax.random.PRNGKey(0)
    if vpp == 1:
        params = {
            "w1": jax.random.normal(key, (PP, HID, HID)) * 0.05,
            "w2": jax.random.normal(key, (PP, HID, HID)) * 0.05,
        }
        pspec = {"w1": P("pp", None, None), "w2": P("pp", None, None)}
    else:
        params = {
            "w1": jax.random.normal(key, (vpp, HID, HID)) * 0.05,
            "w2": jax.random.normal(key, (vpp, HID, HID)) * 0.05,
        }
        pspec = P()
    mbs = jnp.zeros((num_micro, MICRO_B, HID))
    targets = jnp.zeros((num_micro, MICRO_B, HID))

    @functools.partial(
        shard_map, mesh=mesh, in_specs=(pspec, P(), P()),
        out_specs=(P(), pspec), check_vma=False,
    )
    def run(stacked, mbs, targets):
        if vpp == 1:
            local = jax.tree_util.tree_map(lambda a: a[0], stacked)
            loss, _, grads = forward_backward_pipelining_without_interleaving(
                stage_fn, loss_fn, local, mbs, targets,
                axis_name="pp", tick_block_remat=block,
            )
            grads = jax.tree_util.tree_map(lambda g: g[None], grads)
        else:
            loss, _, grads = forward_backward_pipelining_with_interleaving(
                stage_fn, loss_fn, stacked, mbs, targets,
                num_model_chunks=vpp, axis_name="pp", tick_block_remat=block,
            )
        return loss, grads

    return memory_report(run, params, mbs, targets).temp_bytes


def main():
    act_bytes = MICRO_B * HID * 4
    print(f"P={PP} hid={HID} micro_batch={MICRO_B} "
          f"(one boundary activation = {act_bytes} B)")
    print(f"{'schedule':12s} {'M':>4s} {'block':>6s} {'temp MiB':>9s}")
    for vpp, name in ((1, "1f1b"), (2, "interleaved")):
        for m in (8, 32, 128):
            for block in (0, 8, 32):
                t = temp_bytes(m, block, vpp=vpp)
                print(f"{name:12s} {m:4d} {block:6d} {t / 2**20:9.2f}")


if __name__ == "__main__":
    main()
