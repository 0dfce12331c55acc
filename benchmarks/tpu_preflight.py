"""Compile-only pre-flight: everything ``chip_smoke.py`` runs, compiled for the
v5e with no chip attached.

The installed libtpu can describe a TPU topology it is not connected to
(``jax.experimental.topologies``), and XLA + Mosaic compile against it on the
CPU. That catches, before any chip time is spent, what a CPU test cannot:
a Pallas kernel Mosaic refuses, a program over the HBM or VMEM budget. It
says nothing about execution, run-time HBM, numerics or speed — nothing here
is a measurement.

    python benchmarks/tpu_preflight.py              # one chip: trainer + server
    python benchmarks/tpu_preflight.py --chips 4    # + dp2xtp2(+SP), dp4 ZeRO
    python benchmarks/tpu_preflight.py --attention  # the K/V residency edge
    python benchmarks/tpu_preflight.py --cell joyai_llm_flash.pretrain
                                                    # a benchmark cell's step
    python benchmarks/tpu_preflight.py --cell gpt2_345m.chat
                                # a serving cell's prefill buckets + decode

Exit code 0 only when every program compiled. One at a time: libtpu holds a
machine-wide lock, so two of these cannot run side by side.
"""

import argparse
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"  # nothing here may look for a real chip
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

import chip_smoke  # noqa: E402

GiB = 2.0 ** 30


def pretend_tpu(devices):
    """Make the program build what it would build on these devices: the
    mesh comes from ``jax.devices()`` and the op dispatch from ``on_tpu()``,
    so both are pointed at the topology for the life of this process."""
    from apex_tpu.ops import _dispatch

    jax.devices = lambda *a, **k: list(devices)
    _dispatch.on_tpu = lambda: True


def report(name, compiled, seconds):
    mem = compiled.memory_analysis()
    calls = compiled.as_text().count("tpu_custom_call")
    print(f"ok   {name}: {seconds:.0f} s, {calls} Mosaic kernels, "
          f"arguments {mem.argument_size_in_bytes / GiB:.2f} GiB "
          f"(aliased {mem.alias_size_in_bytes / GiB:.2f}), temporaries "
          f"{mem.temp_size_in_bytes / GiB:.3f} GiB", flush=True)


def attempt(name, build):
    t0 = time.time()
    try:
        compiled = build()
    except Exception as e:  # the verdict of this program, not of the run
        print(f"FAIL {name}: {str(e)[:400]}", flush=True)
        return False
    report(name, compiled, time.time() - t0)
    return True


def trainer_program(n_devices, extra_args=(), global_batch=None):
    """The training step ``chip_smoke.trainer_phase`` runs on a machine with
    ``n_devices`` chips (plus ``extra_args``), compiled."""
    from apex_tpu.parallel import parallel_state

    gpt = chip_smoke.load_example("examples/gpt/pretrain_gpt.py")
    argv = chip_smoke.trainer_argv(
        "unused.jsonl", n_devices, global_batch=global_batch,
        extra_args=extra_args)
    try:
        return chip_smoke.lower_train_step(gpt, argv).compile()
    finally:
        parallel_state.destroy_model_parallel()  # the next layout's turn


def server_programs(device):
    """Every program of the serving phase: the load run's five prefill
    buckets and decode step, then the logits check's two."""
    from apex_tpu.serving import ServingEngine

    serve = chip_smoke.load_example("examples/serving/serve_gpt.py")
    args = serve.parse_args(chip_smoke.server_argv("unused.jsonl"))
    model, variables = serve.build_model(args)  # initialised on the CPU
    # shapes only: the weights themselves never leave this host
    variables = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), variables)
    sharding = SingleDeviceSharding(device)
    ok = True
    for label, cfg in (("serve", serve.serving_config(args)),
                       ("logits", chip_smoke.logits_config(args))):
        eng = ServingEngine(model, variables, cfg)
        for key, lowered in eng.lower_programs(sharding).items():
            ok &= attempt(f"{label} {key}", lowered.compile)
    placed = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        variables)
    tokens = jax.ShapeDtypeStruct((1, 32), jnp.int32, sharding=sharding)
    ok &= attempt(
        "logits reference forward",
        chip_smoke.reference_forward(model).lower(placed, tokens).compile)
    return ok


def attention_edge(device):
    """The benchmark cell's call with its derived tiles, then every
    flash-attention variant AT the longest sequence the dispatch still
    sends to the Pallas kernels (``_KV_RESIDENT_BYTES``), and one block
    past it, which must reach no kernel."""
    from apex_tpu.ops import attention as A

    sharding = SingleDeviceSharding(device)

    def compile_attn(b, h, h_kv, sq, sk, d, dtype, causal, bwd,
                     kpm=False, window=None, d_v=None):
        def sds(shape, dt):
            return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

        def fwd(q, k, v, m):
            return A.flash_attention(
                q, k, v, causal=causal, window=window,
                key_padding_mask=m if kpm else None)

        def loss(q, k, v, m):
            return fwd(q, k, v, m).astype(jnp.float32).sum()

        f = jax.grad(loss, argnums=(0, 1, 2)) if bwd else fwd
        k = sds((b, h_kv, sk, d), dtype)
        v = k if d_v is None else sds((b, h_kv, sk, d_v), dtype)
        return jax.jit(f).lower(
            sds((b, h, sq, d), dtype), k, v, sds((b, sk), jnp.bool_)
        ).compile()

    # the benchmark cell's call: far from the edge, so the tiles are the
    # widest the rule derives (ops/attention.py:_flash_tiles)
    ok = attempt(
        "attention gpt2_345m.pretrain (8, 16, 1024, 64) bf16 causal fwd+bwd, "
        f"tiles {A._flash_tiles(1024, 1024, None, A._kv_vmem_bytes(1024, 64, 2))}",
        lambda: compile_attn(8, 16, 16, 1024, 1024, 64, jnp.bfloat16,
                             True, True))
    # q and k wider than v: what latent attention's entry assembles where
    # its own kernels do not apply (head sizes that are no whole lane tiles)
    ok &= attempt(
        "attention (2, 32, 4096, 192/128) bf16 causal fwd+bwd, tiles "
        f"{A._flash_tiles(4096, 4096, None, A._kv_vmem_bytes(4096, 192, 2, 128))}",
        lambda: compile_attn(2, 32, 32, 4096, 4096, 192, jnp.bfloat16,
                             True, True, d_v=128))
    # joyai_llm_flash.pretrain's call: the projections' own outputs

    def compile_latent():
        def sds(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=sharding)

        def loss(*a):
            return A.latent_flash_attention(
                *a, heads=32, interleaved=True).astype(jnp.float32).sum()

        return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
            sds(2, 4096, 32 * 128), sds(2, 4096, 32 * 64),
            sds(2, 4096, 32 * 256), sds(2, 4096, 64),
            jax.ShapeDtypeStruct((4096, 1, 1, 64), jnp.float32,
                                 sharding=sharding)).compile()

    ok &= attempt(
        "latent attention joyai_llm_flash.pretrain (2, 4096, 32 x 128+64/128) "
        "bf16 fwd+bwd, heads in lanes, tiles "
        f"{A._flash_tiles(4096, 4096, None, A._kv_vmem_bytes(4096, 256, 2, 128))}",
        compile_latent)
    for dtype in (jnp.bfloat16, jnp.float32):
        for d in (64, 128, 256):
            per_key = A._kv_vmem_bytes(1, d, jnp.dtype(dtype).itemsize)
            s = A._KV_RESIDENT_BYTES // per_key // 128 * 128
            tag = f"{jnp.dtype(dtype).name} d={d} s={s}"
            cases = [
                ("causal fwd+bwd bh=4", (1, 4, 4, s, s, d, dtype, True, True)),
                ("causal fwd+bwd bh=64",
                 (4, 16, 16, s, s, d, dtype, True, True)),
                ("kpm fwd+bwd", (2, 8, 8, s, s, d, dtype, False, True, True)),
                ("gqa+window+kpm fwd+bwd",
                 (2, 8, 2, s, s, d, dtype, True, True, True, 1024)),
                ("decode kpm", (8, 16, 16, 1, s, d, dtype, False, False, True)),
                ("cross sq>sk fwd+bwd",
                 (2, 8, 8, s, 1024, d, dtype, False, True)),
            ]
            for name, case in cases:
                ok &= attempt(f"attention {tag} {name}",
                              lambda c=case: compile_attn(*c))
            past = compile_attn(1, 4, 4, s + 128, s + 128, d, dtype,
                                True, False)
            beyond = past.as_text().count("tpu_custom_call")
            print(f"{'ok  ' if beyond == 0 else 'FAIL'} attention {tag} one "
                  f"block past the edge reaches {beyond} kernels", flush=True)
            ok &= beyond == 0
    return ok


def cell_files(name):
    """(cell, config) of a benchmark cell: its ``perf/workloads/`` file and
    its configuration's."""
    import json

    def load(*path):
        with open(os.path.join(ROOT, *path)) as f:
            return json.load(f)

    bench = load("BENCHMARK.json")
    entry = next(w for w in bench["workloads"] if w["name"] == name)
    cfg = next(c for c in bench["configs"] if c["name"] == entry["config"])
    return load("perf", "workloads", name + ".json"), load(cfg["file"])


def cell_program(cell, config, hlo_out=None):
    """A benchmark cell's compiled step, built by the cell's own driver
    (``perf/drivers/<driver>.py:build``) for the chips the cell asks for;
    ``hlo_out`` keeps the compiled module's text."""
    from perf import run as perf_run

    driver = perf_run.load_module(ROOT, "drivers", cell["driver"])
    step = driver.build(cell, config).step
    if hlo_out:
        with open(hlo_out, "w") as f:
            f.write(step.as_text())
    return step


def serving_cell_programs(cell, config, device):
    """Every program of a serving cell's engine (a prefill a bucket, the
    decode step), compiled: the model and the engine as
    ``perf/drivers/gpt_serve.py:build`` makes them, the weights as shapes."""
    from apex_tpu.models import GPTModel
    from apex_tpu.serving import ServingConfig, ServingEngine
    from apex_tpu.transformer import TransformerConfig

    model = GPTModel(config=TransformerConfig(
        num_layers=config["n_layer"], hidden_size=config["n_embd"],
        num_attention_heads=config["n_head"],
        vocab_size=config["assumed"]["padded_vocab_size"],
        max_position_embeddings=config["n_positions"], hidden_dropout=0.0,
        attention_dropout=0.0, position_embedding_type="learned"))
    variables = jax.eval_shape(
        model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    eng = ServingEngine(model, {"params": variables["params"]},
                        ServingConfig(**cell["engine"]))
    ok = True
    for key, lowered in eng.lower_programs(
            SingleDeviceSharding(device)).items():
        ok &= attempt(f"{cell['driver']} {key}", lowered.compile)
    return ok


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--attention", action="store_true",
                        help="sweep the flash-attention K/V residency edge "
                             "instead of chip_smoke's programs")
    parser.add_argument("--cell", default=None,
                        help="compile this benchmark cell's training step, "
                             "or a serving cell's engine programs (for the "
                             "chips it asks for) instead")
    parser.add_argument("--hlo-out", default=None,
                        help="with --cell: write the compiled step's text")
    args = parser.parse_args()
    if args.cell:
        cell, config = cell_files(args.cell)
        args.chips = cell["chips"]

    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2")
    devices = list(topo.devices)[: args.chips]
    print(f"compile-only topology v5e:2x2 — {devices[0].device_kind}, "
          f"compiling for {len(devices)} chip(s)", flush=True)
    pretend_tpu(devices)

    if args.cell and "engine" in cell:
        ok = serving_cell_programs(cell, config, devices[0])
    elif args.cell:
        ok = attempt(f"cell {args.cell}, {args.chips} chip(s)",
                     lambda: cell_program(cell, config, args.hlo_out))
    elif args.attention:
        ok = attention_edge(devices[0])
    else:
        ok = attempt(f"trainer {len(devices)} chip(s)",
                     lambda: trainer_program(len(devices)))
        if args.chips == 4:
            ok &= attempt("trainer dp2 x tp2 + SP, global batch 16",
                          lambda: trainer_program(4, ("--tp", "2"), 16))
            ok &= attempt("trainer dp4 ZeRO, global batch 16",
                          lambda: trainer_program(4, ("--zero",), 16))
        ok &= server_programs(devices[0])
    print("ALL COMPILED" if ok else "FAILURES", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
