"""ctypes loader + numpy fallback for the C++ host runtime (csrc/).

Reference parity: the import layer for the reference's native extensions
(apex imports amp_C/apex_C and degrades gracefully when extensions were
not built — README.md:141-170). Same contract here: ``available()``
reports whether the shared library loaded; every wrapper silently falls
back to a numpy implementation with identical semantics, so the framework
never hard-requires a compiler at runtime.

The library is compiled on demand with g++ (baked into the image) into
``csrc/build/`` and cached under a name that carries a hash of its source,
so a build directory copied from another checkout can never supply a
library built from other code; pybind11 is unavailable so the ABI is plain
C consumed via ctypes.
"""

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import List, Optional

import numpy as np

_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_TRIED = False

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SRC = os.path.join(_ROOT, "csrc", "apex_tpu_C.cpp")
_BUILD_DIR = os.path.join(_ROOT, "csrc", "build")


def _installed_ext() -> Optional[str]:
    """A wheel/editable install may have built the extension as
    ``apex_tpu/_C.*.so`` (setup.py, optional) — prefer it over an on-demand
    compile, which needs the repo-layout ``csrc/`` next to the package."""
    import glob

    hits = sorted(glob.glob(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "_C*.so")))
    return hits[0] if hits else None


def _compile() -> Optional[str]:
    try:
        with open(_SRC, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:16]
    except OSError:
        return None
    so = os.path.join(_BUILD_DIR, f"libapex_tpu_C.{digest}.so")
    if os.path.exists(so):
        return so
    os.makedirs(_BUILD_DIR, exist_ok=True)
    # compile to a per-pid temp and rename atomically: an interrupted or
    # concurrent build must never leave a half-written .so under the name
    # the cache trusts
    tmp = f"{so}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", _SRC, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)
    except (OSError, subprocess.SubprocessError):
        # no compiler / failed build: callers fall back to the numpy twin,
        # and available() says so
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return None
    return so


def _load() -> Optional[ctypes.CDLL]:
    global _LIB, _TRIED
    with _LOCK:
        if _TRIED:
            return _LIB
        _TRIED = True
        so = _installed_ext() or _compile()
        if so is None:
            return None
        try:
            lib = ctypes.CDLL(so)
        except OSError:
            return None
        i64 = ctypes.c_int64
        u64 = ctypes.c_uint64
        p = ctypes.POINTER
        # version gate FIRST: a stale .so from an older ABI may lack the
        # newer symbols, and a ctypes attribute lookup on a missing symbol
        # raises — the numpy fallback must win instead
        try:
            lib.apex_tpu_native_abi_version.restype = i64
            if lib.apex_tpu_native_abi_version() != 2:
                return None
        except AttributeError:
            return None
        lib.gather_rows_i32.argtypes = [
            p(ctypes.c_int32), p(i64), i64, i64, p(ctypes.c_int32)
        ]
        lib.gather_rows_u16.argtypes = [
            p(ctypes.c_uint16), p(i64), i64, i64, p(ctypes.c_uint16)
        ]
        lib.gather_rows_i32_mt.argtypes = [
            p(ctypes.c_int32), p(i64), i64, i64, p(ctypes.c_int32), i64
        ]
        lib.gather_rows_u16_mt.argtypes = [
            p(ctypes.c_uint16), p(i64), i64, i64, p(ctypes.c_uint16), i64
        ]
        lib.flatten_f32.argtypes = [
            p(p(ctypes.c_float)), p(i64), i64, p(ctypes.c_float)
        ]
        lib.unflatten_f32.argtypes = [
            p(ctypes.c_float), p(i64), i64, p(p(ctypes.c_float))
        ]
        lib.permutation_i64.argtypes = [i64, u64, p(i64)]
        lib.build_lm_sample_offsets.argtypes = [i64, i64, p(i64), i64]
        lib.build_lm_sample_offsets.restype = i64
        _LIB = lib
        return _LIB


def available() -> bool:
    return _load() is not None


def _i64ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))


# staging batches past ~8 MB get striped over threads (host DRAM bandwidth
# spans cores); below that the spawn cost exceeds the copy
_MT_BYTES_THRESHOLD = 8 << 20
_MT_THREADS = min(8, os.cpu_count() or 1)


def gather_rows(data: np.ndarray, offsets: np.ndarray, row_len: int) -> np.ndarray:
    """out[i] = data[offsets[i] : offsets[i]+row_len]; data 1-D int32/uint16.

    The data-loader hot path: one native memcpy per sample out of the
    token memmap (threaded across cores for large batches)."""
    offsets = np.ascontiguousarray(offsets, np.int64)
    n = offsets.shape[0]
    if np.any(offsets < 0) or np.any(offsets + row_len > data.shape[0]):
        raise IndexError("gather_rows: offsets out of bounds")
    lib = _load()
    if lib is None or data.dtype not in (np.int32, np.uint16):
        return np.stack([data[o : o + row_len] for o in offsets]) if n else (
            np.empty((0, row_len), data.dtype)
        )
    data = np.ascontiguousarray(data)
    out = np.empty((n, row_len), data.dtype)
    threads = (
        _MT_THREADS if out.nbytes >= _MT_BYTES_THRESHOLD and _MT_THREADS > 1
        else 1
    )
    if data.dtype == np.int32:
        lib.gather_rows_i32_mt(
            data.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            _i64ptr(offsets), n, row_len,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), threads,
        )
    else:
        lib.gather_rows_u16_mt(
            data.ctypes.data_as(ctypes.POINTER(ctypes.c_uint16)),
            _i64ptr(offsets), n, row_len,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint16)), threads,
        )
    return out


def flatten(buffers: List[np.ndarray]) -> np.ndarray:
    """apex_C.flatten analogue over host fp32 buffers."""
    bufs = [np.ascontiguousarray(b, np.float32) for b in buffers]
    sizes = np.asarray([b.size for b in bufs], np.int64)
    total = int(sizes.sum())
    lib = _load()
    if lib is None:
        return (
            np.concatenate([b.ravel() for b in bufs])
            if bufs
            else np.empty((0,), np.float32)
        )
    out = np.empty((total,), np.float32)
    ptrs = (ctypes.POINTER(ctypes.c_float) * len(bufs))(
        *[b.ctypes.data_as(ctypes.POINTER(ctypes.c_float)) for b in bufs]
    )
    lib.flatten_f32(ptrs, _i64ptr(sizes), len(bufs), out.ctypes.data_as(
        ctypes.POINTER(ctypes.c_float)
    ))
    return out


def unflatten(flat: np.ndarray, shapes: List[tuple]) -> List[np.ndarray]:
    """apex_C.unflatten analogue."""
    flat = np.ascontiguousarray(flat, np.float32)
    sizes = np.asarray([int(np.prod(s)) if s else 1 for s in shapes], np.int64)
    if int(sizes.sum()) > flat.size:
        raise ValueError("unflatten: shapes exceed flat buffer")
    lib = _load()
    outs = [np.empty(s, np.float32) for s in shapes]
    if lib is None:
        off = 0
        for o, n in zip(outs, sizes):
            o[...] = flat[off : off + n].reshape(o.shape)
            off += int(n)
        return outs
    ptrs = (ctypes.POINTER(ctypes.c_float) * len(outs))(
        *[o.ctypes.data_as(ctypes.POINTER(ctypes.c_float)) for o in outs]
    )
    lib.unflatten_f32(
        flat.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        _i64ptr(sizes), len(outs), ptrs,
    )
    return outs


def _splitmix64(state: int) -> tuple:
    state = (state + 0x9E3779B97F4A7C15) & 0xFFFFFFFFFFFFFFFF
    z = state
    z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & 0xFFFFFFFFFFFFFFFF
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & 0xFFFFFFFFFFFFFFFF
    return state, z ^ (z >> 31)


def permutation(n: int, seed: int) -> np.ndarray:
    """Deterministic Fisher-Yates shuffle (epoch shuffles for
    billion-sample datasets). The fallback runs the SAME splitmix64
    algorithm in Python, so the shuffle — and therefore the data order of
    a resumed run — is identical whether or not the native library loaded
    (slower, but bit-equal)."""
    lib = _load()
    if lib is None:
        out = np.arange(n, dtype=np.int64)
        state = (seed ^ 0xD6E8FEB86659FD93) & 0xFFFFFFFFFFFFFFFF
        for i in range(n - 1, 0, -1):
            state, r = _splitmix64(state)
            j = r % (i + 1)
            out[i], out[j] = out[j], out[i]
        return out
    out = np.empty((n,), np.int64)
    lib.permutation_i64(n, seed, _i64ptr(out))
    return out


def lm_sample_offsets(n_tokens: int, seq_len: int) -> np.ndarray:
    """Start offsets of fixed-length LM samples over a token stream."""
    max_out = max((n_tokens - 1) // seq_len, 0)
    lib = _load()
    if lib is None:
        return (np.arange(max_out, dtype=np.int64) * seq_len)
    out = np.empty((max_out,), np.int64)
    n = lib.build_lm_sample_offsets(n_tokens, seq_len, _i64ptr(out), max_out)
    return out[:n]
