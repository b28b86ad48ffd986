"""ctypes bindings for the native runtime (native/dl4j_tpu_native.cpp).

Builds the .so on first use if g++ is available; every caller has a pure-
Python fallback, so the framework works without the native lib (slower
pipeline, same results).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from pathlib import Path
from typing import Optional

import numpy as np

_NATIVE_DIR = Path(__file__).resolve().parents[2] / "native"
_LIB_PATH = _NATIVE_DIR / "libdl4j_tpu_native.so"
_lib = None
_tried = False


def load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    _tried = True
    if not _LIB_PATH.exists():
        try:
            subprocess.run(["make", "-C", str(_NATIVE_DIR)], check=True,
                           capture_output=True, timeout=120)
        except Exception:  # noqa: BLE001 — fall back to pure python
            return None
    try:
        lib = ctypes.CDLL(str(_LIB_PATH))
    except OSError:
        return None
    lib.ring_create.restype = ctypes.c_void_p
    lib.ring_create.argtypes = [ctypes.c_uint64, ctypes.c_uint64]
    lib.ring_destroy.argtypes = [ctypes.c_void_p]
    lib.ring_push.restype = ctypes.c_int
    lib.ring_push.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint64]
    lib.ring_pop.restype = ctypes.c_int64
    lib.ring_pop.argtypes = [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_uint64]
    lib.ring_size.restype = ctypes.c_uint64
    lib.ring_size.argtypes = [ctypes.c_void_p]
    lib.threshold_encode.restype = ctypes.c_int64
    lib.threshold_encode.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_float,
        ctypes.c_void_p, ctypes.c_int64]
    lib.threshold_decode.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_float,
        ctypes.c_void_p, ctypes.c_int64]
    lib.parse_csv_floats.restype = ctypes.c_int64
    lib.parse_csv_floats.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_void_p, ctypes.c_int64]
    lib.f32_to_bf16.argtypes = [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64]
    lib.arena_create.restype = ctypes.c_void_p
    lib.arena_create.argtypes = [ctypes.c_uint64, ctypes.c_uint64]
    lib.arena_destroy.argtypes = [ctypes.c_void_p]
    lib.arena_alloc.restype = ctypes.c_void_p
    lib.arena_alloc.argtypes = [ctypes.c_void_p]
    lib.arena_free.restype = ctypes.c_int
    lib.arena_free.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    for fn in ("arena_block_size", "arena_in_use", "arena_peak"):
        getattr(lib, fn).restype = ctypes.c_uint64
        getattr(lib, fn).argtypes = [ctypes.c_void_p]
    lib.npy_parse_header.restype = ctypes.c_int
    lib.npy_parse_header.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p]
    lib.parse_csv_matrix.restype = ctypes.c_int64
    lib.parse_csv_matrix.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_void_p,
        ctypes.c_int64]
    _lib = lib
    return _lib


def has_native() -> bool:
    return load() is not None


class NativeRing:
    """SPSC ring of byte slots (AsyncDataSetIterator backing store). ONE
    consumer: every pop receives into the one buffer the ring keeps."""

    def __init__(self, slot_size: int, n_slots: int):
        lib = load()
        if lib is None:
            raise RuntimeError("native lib unavailable")
        self._lib = lib
        self._ptr = lib.ring_create(slot_size, n_slots)
        if not self._ptr:
            raise MemoryError("ring_create failed")
        self.slot_size = slot_size
        # over memory that is not zeroed: only the pages a payload
        # reaches are ever touched
        self._buf = (ctypes.c_char * slot_size).from_buffer(
            np.empty(slot_size, np.uint8))

    def push(self, payload: bytes) -> bool:
        rc = self._lib.ring_push(self._ptr, payload, len(payload))
        if rc == -1:
            raise ValueError(f"payload {len(payload)} > slot {self.slot_size}")
        return rc == 1

    def pop(self) -> Optional[bytes]:
        """The oldest payload, or None from an empty ring; neither
        allocates a slot, so a consumer may poll with this."""
        n = self._lib.ring_pop(self._ptr, self._buf, self.slot_size)
        if n <= 0:
            return None
        return ctypes.string_at(self._buf, n)

    def __len__(self):
        return int(self._lib.ring_size(self._ptr))

    def close(self):
        if self._ptr:
            self._lib.ring_destroy(self._ptr)
            self._ptr = None

    def __del__(self):
        try:
            self.close()
        except Exception:  # noqa: BLE001
            pass


def threshold_encode(grad: np.ndarray, residual: np.ndarray, threshold: float,
                     max_out: Optional[int] = None):
    """Returns int64 token array; residual updated IN PLACE (error feedback)."""
    g = np.ascontiguousarray(grad, np.float32).ravel()
    assert residual.dtype == np.float32 and residual.size == g.size
    cap = max_out or g.size
    lib = load()
    if lib is not None:
        out = np.empty(cap, np.int64)
        n = lib.threshold_encode(
            g.ctypes.data, residual.ctypes.data, g.size,
            ctypes.c_float(threshold), out.ctypes.data, cap)
        return out[:n]
    # pure python fallback
    acc = g + residual
    pos = acc >= threshold
    neg = acc <= -threshold
    idx = np.nonzero(pos | neg)[0][:cap]
    sel_pos = pos[idx]
    residual[:] = acc
    residual[idx[sel_pos]] -= threshold
    residual[idx[~sel_pos]] += threshold
    return ((idx.astype(np.int64) << 1) | (~sel_pos).astype(np.int64))


def threshold_decode(tokens: np.ndarray, threshold: float, n: int) -> np.ndarray:
    out = np.zeros(n, np.float32)
    lib = load()
    if lib is not None and tokens.size:
        t = np.ascontiguousarray(tokens, np.int64)
        lib.threshold_decode(t.ctypes.data, t.size,
                             ctypes.c_float(threshold), out.ctypes.data, n)
        return out
    if tokens.size:
        idx = tokens >> 1
        sign = np.where((tokens & 1) == 1, -1.0, 1.0).astype(np.float32)
        np.add.at(out, idx, sign * threshold)
    return out


def parse_csv_floats(text: bytes, max_out: int) -> np.ndarray:
    lib = load()
    if lib is not None:
        out = np.empty(max_out, np.float32)
        n = lib.parse_csv_floats(text, len(text), out.ctypes.data, max_out)
        return out[:n]
    import re
    vals = re.split(rb"[,\s;]+", text.strip())
    return np.asarray([float(v) for v in vals if v], np.float32)[:max_out]


class _ArenaBlock(np.ndarray):
    """ndarray view over an arena block; holds a reference to its arena so
    the slab can never be freed (GC or close) while a view is reachable."""
    _arena = None


class StagingArena:
    """Pinned-host-style staging allocator (reference: libnd4j workspaces +
    cudaHostAlloc staging). Page-aligned fixed-size blocks, LIFO freelist,
    first-touch NUMA placement at creation; zero malloc churn in the
    steady-state input pipeline. `borrow()` yields a numpy view over a
    block; `release()` returns it (double-release and foreign blocks are
    rejected). Falls back to plain numpy allocation when the native lib is
    absent (same API, no reuse guarantee)."""

    def __init__(self, block_size: int, n_blocks: int):
        self._lib = load()
        self._ptr = None
        self._fallback: list = []
        self._fallback_peak = 0
        self.n_blocks = n_blocks
        if self._lib is not None:
            self._ptr = self._lib.arena_create(block_size, n_blocks)
            if not self._ptr:
                raise MemoryError("arena_create failed")
            self.block_size = int(self._lib.arena_block_size(self._ptr))
        else:
            self.block_size = block_size

    def borrow(self) -> Optional[np.ndarray]:
        """A uint8 view over one block, or None if the arena is exhausted.
        Pass the SAME array (not a slice) back to release()."""
        if self._ptr:
            p = self._lib.arena_alloc(self._ptr)
            if not p:
                return None
            raw = np.ctypeslib.as_array(
                ctypes.cast(p, ctypes.POINTER(ctypes.c_uint8)),
                shape=(self.block_size,))
            block = raw.view(_ArenaBlock)
            block._arena = self  # slab outlives every reachable view
            return block
        if len(self._fallback) >= self.n_blocks:
            return None
        buf = np.zeros(self.block_size, np.uint8)
        self._fallback.append(buf)
        self._fallback_peak = max(self._fallback_peak, len(self._fallback))
        return buf

    def release(self, block: np.ndarray) -> None:
        if self._ptr:
            if not self._lib.arena_free(self._ptr, block.ctypes.data):
                raise ValueError(
                    "block does not belong to this arena (or was already "
                    "released, or is a slice rather than the borrowed array)")
            # _arena stays set: even a released view keeps the slab alive so
            # a stray late write can never hit freed memory
        else:
            kept = [b for b in self._fallback if b is not block]
            if len(kept) == len(self._fallback):
                raise ValueError(
                    "block does not belong to this arena (or was already "
                    "released)")
            self._fallback = kept

    @property
    def in_use(self) -> int:
        return int(self._lib.arena_in_use(self._ptr)) if self._ptr else len(self._fallback)

    @property
    def peak(self) -> int:
        return int(self._lib.arena_peak(self._ptr)) if self._ptr else self._fallback_peak

    def close(self, force: bool = False):
        """Free the slab. Refuses while blocks are outstanding unless
        `force=True` (outstanding views would become dangling pointers)."""
        if self._ptr:
            if not force and int(self._lib.arena_in_use(self._ptr)):
                raise RuntimeError(
                    f"{self.in_use} block(s) still borrowed; release them "
                    f"first or close(force=True)")
            self._lib.arena_destroy(self._ptr)
            self._ptr = None

    def __del__(self):
        try:
            # no outstanding views can exist here: each holds a reference to
            # this arena, so reachable views keep __del__ from running
            self.close(force=True)
        except Exception:  # noqa: BLE001
            pass


def npy_header(buf: bytes):
    """Parse a .npy v1/v2 header natively: (shape, dtype, data_offset,
    fortran). Pure-numpy fallback uses numpy's own parser."""
    lib = load()
    if lib is not None:
        shape = np.zeros(8, np.int64)
        ndim = ctypes.c_int32()
        dch = ctypes.c_char()
        isz = ctypes.c_int32()
        off = ctypes.c_int64()
        fortran = ctypes.c_int32()
        rc = lib.npy_parse_header(
            buf, len(buf), shape.ctypes.data, ctypes.byref(ndim),
            ctypes.byref(dch), ctypes.byref(isz), ctypes.byref(off),
            ctypes.byref(fortran))
        if rc == 0:
            dtype = np.dtype(f"{dch.value.decode()}{isz.value}")
            return (tuple(int(s) for s in shape[:ndim.value]), dtype,
                    int(off.value), bool(fortran.value))
        # fall through to numpy on unsupported (e.g. big-endian) headers
    import io
    from numpy.lib import format as npf
    f = io.BytesIO(buf)
    version = npf.read_magic(f)
    shape, fortran, dtype = npf._read_array_header(f, version)
    return shape, dtype, f.tell(), fortran


def load_npy(buf: bytes) -> np.ndarray:
    """bytes of a .npy file → ndarray (zero-copy view onto `buf`)."""
    shape, dtype, off, fortran = npy_header(buf)
    n = int(np.prod(shape)) if shape else 1
    arr = np.frombuffer(buf, dtype=dtype, count=n, offset=off)
    return arr.reshape(shape, order="F" if fortran else "C")


def parse_csv_matrix(text: bytes, n_cols: int,
                     max_rows: Optional[int] = None) -> np.ndarray:
    """CSV text → (rows, n_cols) f32; rows with a different column count
    (headers, blanks) are skipped. Native fast path, numpy fallback."""
    cap = max_rows if max_rows is not None else text.count(b"\n") + 1
    lib = load()
    if lib is not None:
        out = np.empty((cap, n_cols), np.float32)
        n = lib.parse_csv_matrix(text, len(text), n_cols,
                                 out.ctypes.data, cap)
        return out[:n].copy()
    import re
    rows = []
    for line in text.splitlines():
        # same delimiter set as the native parser: , ; tab space
        parts = [p for p in re.split(rb"[,;\t ]+", line.strip()) if p]
        if len(parts) != n_cols:
            continue
        try:
            rows.append([float(p) for p in parts])
        except ValueError:
            continue
        if len(rows) >= cap:
            break
    return np.asarray(rows, np.float32).reshape(-1, n_cols)


def f32_to_bf16(arr: np.ndarray) -> np.ndarray:
    a = np.ascontiguousarray(arr, np.float32)
    lib = load()
    out = np.empty(a.size, np.uint16)
    if lib is not None:
        lib.f32_to_bf16(a.ctypes.data, out.ctypes.data, a.size)
    else:
        bits = a.view(np.uint32).ravel()
        lsb = (bits >> 16) & 1
        out = ((bits + 0x7FFF + lsb) >> 16).astype(np.uint16)
    import jax.numpy as jnp
    return out.reshape(arr.shape).view(jnp.bfloat16)
