"""What the chip would refuse, caught without one.

The chip is reached through ``chip_smoke.py`` only; these are the parts of
that path a CPU can still pin:

- every Pallas kernel variant CROSS-LOWERED for the TPU
  (``jax.export`` with ``platforms=["tpu"]``, ``interpret=False``): the
  block-shape rules of the TPU lowering fire here, long before Mosaic;
- the flash-attention dispatch at its K/V residency edge;
- no fallback that hides the device: ``on_tpu()`` raises when the backend
  does, the kernel smoke fails without a chip;
- the compile cache is placed from outside;
- ``chip_smoke.py``'s device gate fails here, and its trainer and server
  phases pass at toy width.
"""

import hashlib
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import pytest

from apex_tpu.ops import _dispatch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(relpath, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, relpath))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture
def as_tpu(monkeypatch):
    """Dispatch as on a TPU: ``impl="pallas"`` resolves to compiled
    (``interpret=False``) kernels, which only a TPU lowering accepts."""
    monkeypatch.setattr(_dispatch, "on_tpu", lambda: True)


@pytest.fixture
def no_disk_cache(monkeypatch):
    """Entry points enable the persistent compile cache; inside the test
    process that would write every later compile to disk."""
    from apex_tpu.utils import compile_cache

    monkeypatch.setattr(
        compile_cache, "enable_compile_cache", lambda: "<disabled in tests>")


def _kernels_for_tpu(f, *args):
    """``tpu_custom_call`` sites in ``f`` lowered for the TPU platform."""
    exported = jax.export.export(jax.jit(f), platforms=["tpu"])(*args)
    return exported.mlir_module().count("tpu_custom_call")


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


# ---------------------------------------------------------------------------
# every Pallas kernel variant lowers for the TPU


@pytest.mark.usefixtures("as_tpu")
class TestCrossLowering:
    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
    @pytest.mark.parametrize(
        "rows,hidden", [(1, 1024), (8, 4096), (24, 8192), (512, 1024)])
    @pytest.mark.parametrize("op", ["layer_norm", "rms_norm"])
    def test_norm_fwd_bwd(self, op, rows, hidden, dtype):
        from apex_tpu.ops import layer_norm, rms_norm

        def f(x, w, b):
            if op == "layer_norm":
                return layer_norm(x, w, b, impl="pallas")
            return rms_norm(x, w, impl="pallas")

        def loss(x, w, b):
            return f(x, w, b).astype(jnp.float32).sum()

        args = (_sds((rows, hidden), dtype), _sds((hidden,), dtype),
                _sds((hidden,), dtype))
        assert _kernels_for_tpu(f, *args) == 1
        argnums = (0, 1, 2) if op == "layer_norm" else (0, 1)
        # the backward kernel (the forward's output is dead under a sum)
        assert _kernels_for_tpu(jax.grad(loss, argnums), *args) >= 1

    @staticmethod
    def _attention(b, h, h_kv, sq, sk, d, dtype, kpm=False, **kw):
        from apex_tpu.ops import flash_attention

        def fwd(q, k, v, m):
            return flash_attention(
                q, k, v, key_padding_mask=m if kpm else None,
                impl="pallas", **kw)

        def loss(q, k, v, m):
            return fwd(q, k, v, m).astype(jnp.float32).sum()

        kv = _sds((b, h_kv, sk, d), dtype)
        args = (_sds((b, h, sq, d), dtype), kv, kv, _sds((b, sk), jnp.bool_))
        return fwd, jax.grad(loss, (0, 1, 2)), args

    @pytest.mark.parametrize("name,case,kw", [
        ("noncausal", (2, 4, 4, 256, 256, 64, jnp.float32), {}),
        ("causal", (2, 4, 4, 256, 256, 64, jnp.float32),
         dict(causal=True)),
        ("gpt2 bf16 causal", (4, 16, 16, 1024, 1024, 64, jnp.bfloat16),
         dict(causal=True)),
        # the benchmark cell's call (global batch 8), tiles derived
        ("gpt2 345m cell", (8, 16, 16, 1024, 1024, 64, jnp.bfloat16),
         dict(causal=True)),
        ("gqa+window", (1, 8, 2, 4096, 4096, 128, jnp.bfloat16),
         dict(causal=True, window=1024)),
        ("long causal", (1, 2, 2, 8192, 8192, 128, jnp.bfloat16),
         dict(causal=True)),
    ])
    def test_flash_fwd_bwd(self, name, case, kw):
        fwd, bwd, args = self._attention(*case, **kw)
        assert _kernels_for_tpu(fwd, *args) == 1, name
        assert _kernels_for_tpu(bwd, *args) == 3, name

    @pytest.mark.parametrize("kpm", [False, True])
    def test_latent_flash_fwd_bwd(self, kpm):
        """Latent attention's call as the JoyAI cell makes it (the
        projections' outputs, a head a 128-lane column range): one flash
        kernel and the rotation forward; three and two with the backward."""
        from apex_tpu.ops.attention import latent_flash_attention

        def fwd(qn, qr, kv, kr, freqs, m):
            return latent_flash_attention(
                qn, qr, kv, kr, freqs, heads=32, interleaved=True,
                key_padding_mask=m if kpm else None, impl="pallas")

        def loss(*a):
            return fwd(*a).astype(jnp.float32).sum()

        bf = jnp.bfloat16
        args = (_sds((2, 4096, 32 * 128), bf), _sds((2, 4096, 32 * 64), bf),
                _sds((2, 4096, 32 * 256), bf), _sds((2, 4096, 64), bf),
                _sds((4096, 1, 1, 64), jnp.float32),
                _sds((2, 4096), jnp.bool_))
        assert _kernels_for_tpu(fwd, *args) == 2
        assert _kernels_for_tpu(jax.grad(loss, (0, 1, 2, 3)), *args) == 5

    @pytest.mark.parametrize("batch", [1, 4, 8])
    def test_flash_key_padding_any_batch(self, batch):
        """A (b, sk) key-padding mask blocked as (1, sk) is refused by the
        TPU lowering for every b > 1 — ``models.generate`` at batch > 1
        and BERT with padding (fails at the parent of this change)."""
        fwd, bwd, args = self._attention(
            batch, 4, 4, 256, 256, 64, jnp.bfloat16, kpm=True)
        assert _kernels_for_tpu(fwd, *args) == 1
        assert _kernels_for_tpu(bwd, *args) == 3

    @pytest.mark.parametrize("batch", [1, 4, 8])
    def test_flash_decode_shape_key_padding(self, batch):
        """One query token against a padded cache: the per-token call of
        the KV-cache decode path (sq=1, causal=False, key padding)."""
        fwd, _, args = self._attention(
            batch, 4, 4, 1, 256, 64, jnp.bfloat16, kpm=True)
        assert _kernels_for_tpu(fwd, *args) == 1

    def test_flat_optimizer_kernels(self):
        from apex_tpu.ops.multi_tensor import CHUNK_SIZE
        from apex_tpu.optimizers._fused_kernels import adam_flat, l2norm_flat

        flat = _sds((3 * CHUNK_SIZE,), jnp.float32)
        scalar = _sds((), jnp.float32)

        def adam(g, p, m, v, bc1, bc2):
            return adam_flat(
                g, p, m, v, bc1, bc2, lr=1e-3, beta1=0.9, beta2=0.999,
                eps=1e-8, weight_decay=0.01, adam_w_mode=True, impl="pallas")

        assert _kernels_for_tpu(
            adam, flat, flat, flat, flat, scalar, scalar) == 1
        assert _kernels_for_tpu(
            lambda x: l2norm_flat(x, impl="pallas"), flat) == 1


# ---------------------------------------------------------------------------
# the K/V residency edge of the flash dispatch


@pytest.mark.usefixtures("as_tpu")
class TestKvResidencyEdge:
    """``_KV_RESIDENT_BYTES`` is the edge of a compile sweep against the v5e
    (the constant's comment; ``benchmarks/tpu_preflight.py --attention``
    re-runs it): up to it the Pallas kernels, one block past it none."""

    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
    @pytest.mark.parametrize("d", [64, 128])
    def test_dispatch_at_and_past_the_edge(self, d, dtype):
        from apex_tpu.ops import attention as A
        from apex_tpu.ops import flash_attention

        # VMEM pads the head dim to 128 lanes: d=64 costs what d=128 does
        per_key = 2 * 128 * jnp.dtype(dtype).itemsize
        edge = A._KV_RESIDENT_BYTES // per_key // 128 * 128
        assert A._kv_vmem_bytes(edge, d, jnp.dtype(dtype).itemsize) \
            <= A._KV_RESIDENT_BYTES

        def f(q, k, v):
            return flash_attention(q, k, v, causal=True)

        def kernels(s):
            x = _sds((1, 2, s, d), dtype)
            return _kernels_for_tpu(f, x, x, x)

        assert kernels(edge) == 1
        assert kernels(edge + 128) == 0

    def test_longer_of_the_two_sequences_must_fit(self):
        """The backward's dk/dv kernel holds Q/dO resident the way the
        others hold K/V: a long query side sends the call to the blockwise
        path even when the keys are short."""
        from apex_tpu.ops import flash_attention

        q = _sds((1, 2, 16384, 128), jnp.bfloat16)
        kv = _sds((1, 2, 1024, 128), jnp.bfloat16)
        assert _kernels_for_tpu(
            lambda q, k, v: flash_attention(q, k, v), q, kv, kv) == 0


# ---------------------------------------------------------------------------
# no fallback that hides the device


class TestNoHiddenFallback:
    def test_on_tpu_raises_when_the_backend_does(self, monkeypatch):
        def broken(*_a, **_k):
            raise RuntimeError("Unable to initialize backend 'tpu'")

        monkeypatch.setattr(jax, "devices", broken)
        _dispatch.on_tpu.cache_clear()
        try:
            with pytest.raises(RuntimeError, match="Unable to initialize"):
                _dispatch.on_tpu()
            with pytest.raises(RuntimeError, match="Unable to initialize"):
                _dispatch.resolve_impl("auto")
        finally:
            _dispatch.on_tpu.cache_clear()

    def test_interpret_only_when_asked_for_off_chip(self, as_tpu):
        # on a TPU nothing resolves to interpret=True ...
        assert _dispatch.resolve_impl("auto") == (True, False)
        assert _dispatch.resolve_impl("pallas") == (True, False)
        assert _dispatch.resolve_impl("xla") == (False, False)

    def test_off_chip_auto_is_xla_and_pallas_is_interpreted(self):
        # ... and off one, only an explicit impl="pallas" interprets
        assert _dispatch.resolve_impl("auto") == (False, False)
        assert _dispatch.resolve_impl("pallas") == (True, True)

    def test_kernel_smoke_fails_without_a_chip(self, capsys):
        smoke = _load("benchmarks/tpu_kernel_smoke.py", "_kernel_smoke")
        assert smoke.main() == 1
        out = capsys.readouterr().out
        assert "FAILURES" in out and "ALL OK" not in out

    def test_native_build_is_keyed_on_its_source(self, tmp_path, monkeypatch):
        """A build directory copied from elsewhere cannot supply a library
        built from other code: the cached name carries the source hash."""
        from apex_tpu import _native

        with open(_native._SRC, "rb") as f:
            source = f.read()
        build = tmp_path / "build"
        build.mkdir()
        (build / "libapex_tpu_C.so").write_bytes(b"built from other source")
        monkeypatch.setattr(_native, "_BUILD_DIR", str(build))
        so = _native._compile()
        assert so is not None, "g++ is part of this installation"
        assert hashlib.sha256(source).hexdigest()[:16] in os.path.basename(so)

        edited = tmp_path / "apex_tpu_C.cpp"
        edited.write_bytes(source + b"\n// edited\n")
        monkeypatch.setattr(_native, "_SRC", str(edited))
        assert _native._compile() != so


# ---------------------------------------------------------------------------
# the compile cache is placed from outside


class TestCompileCache:
    @pytest.fixture
    def updates(self, monkeypatch):
        calls = {}
        monkeypatch.setattr(
            jax.config, "update", lambda key, value: calls.update({key: value}))
        return calls

    def test_unset_uses_the_fixed_in_checkout_path(self, monkeypatch, updates):
        from apex_tpu.utils.compile_cache import enable_compile_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert enable_compile_cache() == os.path.join(REPO, ".jax_cache")
        assert updates["jax_compilation_cache_dir"] == os.path.join(
            REPO, ".jax_cache")

    def test_set_configures_no_directory_in_code(
            self, monkeypatch, updates, tmp_path):
        from apex_tpu.utils.compile_cache import enable_compile_cache

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert "jax_compilation_cache_dir" not in updates


# ---------------------------------------------------------------------------
# the serving engine's programs take the weights as an argument


def test_serving_programs_take_weights_as_arguments():
    """Closed over, the weights become a constant in every bucket program
    and the decode step — 1.4 GB apiece at GPT-2 345M."""
    import numpy as np

    from apex_tpu.models import GPTModel
    from apex_tpu.serving import ServingConfig, ServingEngine
    from apex_tpu.transformer import TransformerConfig

    model = GPTModel(config=TransformerConfig(
        num_layers=1, hidden_size=32, num_attention_heads=2, vocab_size=64,
        max_position_embeddings=32, hidden_dropout=0.0,
        attention_dropout=0.0, position_embedding_type="rope"))
    variables = jax.jit(model.init)(
        jax.random.PRNGKey(0), np.zeros((1, 4), np.int32))
    n_weights = len(jax.tree_util.tree_leaves(variables))
    eng = ServingEngine(model, variables, ServingConfig(
        lanes=2, block_size=8, num_blocks=8, max_seq_len=32))
    lowered = eng.lower_programs()
    assert set(lowered) == {8, 16, 32, "decode"}
    n_pool = len(eng._spec.pool_shapes(8, 8))
    for key, program in lowered.items():
        n_rest = 6 if key == "decode" else 5
        assert program.in_tree.num_leaves == n_pool + n_weights + n_rest, key


# ---------------------------------------------------------------------------
# chip_smoke.py


@pytest.fixture
def chip_smoke():
    return _load("chip_smoke.py", "_chip_smoke_under_test")


class TestChipSmoke:
    TOY = dict(layers=2, hidden=64, heads=4, vocab=512)

    def test_device_gate_fails_off_the_chip(self, chip_smoke):
        with pytest.raises(chip_smoke.PhaseFailed, match="no accelerator"):
            chip_smoke.device_gate()

    def test_main_fails_at_the_device_phase(
            self, chip_smoke, no_disk_cache, monkeypatch, capsys):
        for phase in ("clock_phase", "trainer_phase", "server_phase"):
            monkeypatch.setattr(
                chip_smoke, phase,
                lambda *a, **k: pytest.fail("a phase ran off the chip"))
        assert chip_smoke.main() == 1
        out = capsys.readouterr().out
        assert "FAILED" in out and '"ok"' not in out

    def test_trainer_phase_at_toy_width(
            self, chip_smoke, no_disk_cache, monkeypatch, tmp_path):
        # a CPU has no peak in the table; the pin only makes the mfu field
        # a number here — nothing reads it as a utilization
        monkeypatch.setenv("APEX_TPU_PEAK_FLOPS", "1e12")
        losses = chip_smoke.trainer_phase(
            str(tmp_path), model=self.TOY, seq_len=32, micro_batch=1)
        assert len(losses) == chip_smoke.TRAIN_SAMPLES // 8  # 8 devices
        records = [json.loads(line)
                   for line in open(tmp_path / "trainer.jsonl")]
        assert sum(r["kind"] == "metrics" for r in records) == len(losses)

    def test_server_phase_at_toy_width(
            self, chip_smoke, no_disk_cache, tmp_path):
        chip_smoke.server_phase(
            str(tmp_path), model=self.TOY, lanes=2, max_seq_len=64,
            requests=4, prompt_len=(4, 40), max_new=(2, 6))
        assert os.path.exists(tmp_path / "server.jsonl")
