"""``ops.paged_decode_attention``: one decode token a lane against a paged
KV pool, read where it lies by block table and length.

Both forms of the entry, the Pallas kernel in interpret mode and the plain
XLA one, are held to a plain softmax(QK^T)V over each lane's GATHERED keys
(numpy, float64), for what a model can ask of decode attention: the chat
cell's 16 heads of 64, grouped heads at a rope model's head size, a sliding
window, lengths that end anywhere in a block, an idle lane, a bad table.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops import _dispatch
from apex_tpu.ops.attention import paged_decode_attention

IMPLS = ("xla", "pallas")


def _reference(q, k_pool, v_pool, tables, lengths, scale, window):
    """Per lane: gather its blocks (entries clipped into the pool, as the
    entry promises), keep keys [0, length) or the last ``window`` of them,
    softmax in float64."""
    lanes, heads, hd = q.shape
    nb, bs, width = k_pool.shape
    h_kv = width // hd
    group = heads // h_kv
    q, k_pool, v_pool = (np.asarray(x, np.float64)
                         for x in (q, k_pool, v_pool))
    out = np.zeros(q.shape)
    for lane in range(lanes):
        n = int(lengths[lane])
        if n == 0:
            continue
        own = np.clip(np.asarray(tables[lane]), 0, nb - 1)
        k = k_pool[own].reshape(-1, h_kv, hd)[:n]
        v = v_pool[own].reshape(-1, h_kv, hd)[:n]
        lo = 0 if window is None else max(0, n - window)
        for head in range(heads):
            s = k[lo:, head // group] @ q[lane, head] * scale
            p = np.exp(s - s.max())
            out[lane, head] = (p / p.sum()) @ v[lo:, head // group]
    return out


def _case(heads, h_kv, hd, lengths, dtype=jnp.float32, bs=16, max_blocks=8,
          seed=0):
    """Pools of random keys and values, every lane's blocks drawn without
    order from the pool, the rest of its table the engine's sentinel."""
    rng = np.random.RandomState(seed)
    lanes = len(lengths)
    nb = lanes * max_blocks + 3
    pools = [jnp.asarray(rng.randn(nb, bs, h_kv * hd), dtype)
             for _ in range(2)]
    q = jnp.asarray(rng.randn(lanes, heads, hd), dtype)
    tables = np.full((lanes, max_blocks), nb, np.int32)
    order, used = rng.permutation(nb), 0
    for lane, n in enumerate(lengths):
        need = -(-int(n) // bs)
        tables[lane, :need] = order[used:used + need]
        used += need
    return q, pools, tables, np.asarray(lengths, np.int32)


def _check(q, pools, tables, lengths, impl, window=None, tol=2e-5):
    scale = q.shape[-1] ** -0.5
    got = paged_decode_attention(
        q, *pools, jnp.asarray(tables), jnp.asarray(lengths), scale=scale,
        window=window, impl=impl)
    assert got.shape == q.shape and got.dtype == q.dtype
    want = _reference(q, *pools, tables, lengths, scale, window)
    np.testing.assert_allclose(np.asarray(got, np.float64), want, atol=tol,
                               rtol=tol)
    return got


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
def test_the_chat_cells_heads(impl, dtype, tol):
    """16 heads of 64, block 16: GPT-2 345M's decode, ragged lanes."""
    q, pools, tables, lengths = _case(16, 16, 64, [100, 37, 128, 5],
                                      dtype=dtype)
    _check(q, pools, tables, lengths, impl, tol=tol)


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("heads,h_kv", [(8, 2), (4, 1), (6, 2)])
def test_grouped_heads_at_a_rope_models_head_size(impl, heads, h_kv):
    """Query head i attends through kv head i // group (consecutive
    grouping, as ``flash_attention``), head size 128."""
    q, pools, tables, lengths = _case(heads, h_kv, 128, [77, 128, 16],
                                      seed=1)
    _check(q, pools, tables, lengths, impl)


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("window", [1, 16, 24, 300])
def test_sliding_window(impl, window):
    """Keys at or behind ``length - 1 - window`` are masked, as the
    contiguous decode branch masks them (mistral decode); a window past the
    first chunk of keys makes the kernel's loop start late."""
    q, pools, tables, lengths = _case(
        8, 2, 128, [1, 33, 100, 512, 290], max_blocks=32, seed=2)
    _check(q, pools, tables, lengths, impl, window=window)


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("length", [1, 15, 16, 17, 128])
def test_lengths_that_end_anywhere_in_a_block(impl, length):
    """1, bs - 1, bs, bs + 1 and max_seq_len, each beside a full lane."""
    q, pools, tables, lengths = _case(16, 16, 64, [length, 128], seed=3)
    _check(q, pools, tables, lengths, impl)


@pytest.mark.parametrize("impl", IMPLS)
def test_more_keys_than_one_chunk(impl):
    """A lane past ``_PAGED_CHUNK_KEYS`` keys runs the loop several times
    (both buffers, the prefetch of the next chunk)."""
    q, pools, tables, lengths = _case(
        16, 16, 64, [1000, 257, 256, 511], max_blocks=64, seed=4)
    _check(q, pools, tables, lengths, impl)


@pytest.mark.parametrize("impl", IMPLS)
def test_an_idle_lane_whose_table_is_all_sentinel(impl):
    """An inactive lane as the engine hands it over: position 0, so length
    1, and no block of its own. It reads a clipped block's bytes: finite,
    and the lanes beside it are untouched."""
    q, pools, tables, lengths = _case(16, 16, 64, [50, 1, 90], seed=5)
    tables[1, :] = pools[0].shape[0]  # the sentinel, num_blocks
    got = _check(q, pools, tables, lengths, impl)
    assert np.isfinite(np.asarray(got, np.float32)).all()


@pytest.mark.parametrize("impl", IMPLS)
def test_a_sentinel_entry_inside_the_length(impl):
    """The benchmark's planted ``block_left_out`` fault: every lane's first
    table entry is ``num_blocks``. Wrong bytes (the clipped block's), never
    a fault: the output is finite and differs from the sound table's."""
    q, pools, tables, lengths = _case(16, 16, 64, [100, 37, 128], seed=6)
    sound = _check(q, pools, tables, lengths, impl)
    bad = tables.copy()
    bad[:, 0] = pools[0].shape[0]
    got = _check(q, pools, bad, lengths, impl)
    assert np.isfinite(np.asarray(got)).all()
    assert not np.allclose(np.asarray(got), np.asarray(sound), atol=1e-3)


@pytest.mark.parametrize("impl", IMPLS)
def test_entries_far_out_of_range_never_address_outside_the_pool(impl):
    q, pools, tables, lengths = _case(16, 16, 64, [40, 70], seed=7)
    tables[0, 1] = -5
    tables[1, 2] = 10 ** 6
    got = _check(q, pools, tables, lengths, impl)
    assert np.isfinite(np.asarray(got)).all()


@pytest.mark.parametrize("impl", IMPLS)
def test_a_lane_of_length_zero_attends_nothing(impl):
    """What the attention layer hands over for a lane with no block under
    its position: zeros, and no work."""
    q, pools, tables, lengths = _case(16, 16, 64, [0, 60, 0], seed=8)
    got = _check(q, pools, tables, lengths, impl)
    assert not np.asarray(got[0]).any() and not np.asarray(got[2]).any()


def test_the_two_forms_agree_to_rounding():
    """float32: the kernel's chunked online softmax against the XLA form's
    one softmax over the whole pool."""
    q, pools, tables, lengths = _case(
        16, 4, 64, [1000, 3, 640, 64], max_blocks=64, seed=9)
    run = lambda impl: np.asarray(paged_decode_attention(
        q, *pools, jnp.asarray(tables), jnp.asarray(lengths), scale=0.125,
        window=700, impl=impl))
    np.testing.assert_allclose(run("pallas"), run("xla"), atol=2e-6)


@pytest.mark.parametrize("heads,h_kv,hd,bs,dtype", [
    (4, 4, 8, 8, jnp.float32),      # 32 lanes wide: no whole lane tile
    (16, 16, 64, 8, jnp.bfloat16),  # half a bf16 sublane tile a block
], ids=["narrow-rows", "short-blocks"])
def test_shapes_the_kernel_cannot_tile_take_the_xla_form(
        heads, h_kv, hd, bs, dtype, monkeypatch):
    """``impl="pallas"`` on such a call still answers, and reaches no
    kernel (lowered for the TPU: no custom call)."""
    q, pools, tables, lengths = _case(heads, h_kv, hd, [9, 30], dtype=dtype,
                                      bs=bs, seed=10)
    _check(q, pools, tables, lengths, "pallas",
           tol=2e-5 if dtype == jnp.float32 else 2e-2)
    monkeypatch.setattr(_dispatch, "on_tpu", lambda: True)
    f = lambda *a: paged_decode_attention(*a, scale=1.0, impl="pallas")
    exported = jax.export.export(jax.jit(f), platforms=["tpu"])(
        q, *pools, jnp.asarray(tables), jnp.asarray(lengths))
    assert exported.mlir_module().count("tpu_custom_call") == 0


def test_heads_that_do_not_fit_the_pool_are_refused():
    q, pools, tables, lengths = _case(16, 16, 64, [9])
    with pytest.raises(ValueError, match="kv heads"):
        paged_decode_attention(q[:, :5], *pools, jnp.asarray(tables),
                               jnp.asarray(lengths), scale=1.0)
    with pytest.raises(ValueError, match="kv heads"):
        paged_decode_attention(q[:, :, :48], *pools, jnp.asarray(tables),
                               jnp.asarray(lengths), scale=1.0)


@pytest.mark.parametrize("name,case,window", [
    ("gpt2 345m chat cell", (24, 16, 16, 64, 1536, 64, jnp.bfloat16), None),
    ("gqa+window", (8, 32, 8, 128, 512, 64, jnp.bfloat16), 256),
    ("float32", (8, 16, 16, 64, 512, 64, jnp.float32), None),
])
def test_the_kernel_lowers_for_the_tpu(name, case, window, monkeypatch):
    """One Mosaic call, named: ``breakdown.device_ops`` of a traced run of
    the chat cell shows the kernel as ``paged_decode``."""
    lanes, heads, h_kv, hd, nb, max_blocks, dtype = case
    monkeypatch.setattr(_dispatch, "on_tpu", lambda: True)
    sds = jax.ShapeDtypeStruct
    pool = sds((nb, 16, h_kv * hd), dtype)
    f = lambda *a: paged_decode_attention(*a, scale=hd ** -0.5,
                                          window=window)
    module = jax.export.export(jax.jit(f), platforms=["tpu"])(
        sds((lanes, heads, hd), dtype), pool, pool,
        sds((lanes, max_blocks), jnp.int32), sds((lanes,), jnp.int32)
    ).mlir_module()
    assert module.count("tpu_custom_call") == 1, name
    assert "paged_decode" in module, name
