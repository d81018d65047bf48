"""ctypes binding for the native C++ data backend (``native/``).

The reference leans on torch's native DataLoader workers for its host-side
data path (``/root/reference/multi_proc_single_gpu.py:156``); this module is
the TPU framework's first-party equivalent: IDX parsing (raw + gzip),
normalize, and epoch gather run in multithreaded C++ when
``libtpumnist_native.so`` is built (``make -C native``), with the worker
count coming from the CLI's ``-j/--workers`` flag. Every entry point has a
pure-NumPy fallback in ``data/mnist.py`` / ``data/loader.py``; the native
path is an optimization, never a requirement.

Serving note (DESIGN.md §7k): on a FUSED serve plane the per-request
``tm_cast_f32``/``tm_normalize``/``tm_quant_i8`` calls disappear — raw
uint8 requests stage as bytes and that math runs inside the fused XLA
program. These kernels remain the training input path and the split
(``--no-fuse`` / float-input) serve plane, which is the bitwise
reference the fused programs are pinned against.
"""

from __future__ import annotations

import ctypes
import os
from typing import Optional, Tuple

import numpy as np

_LIB_NAME = "libtpumnist_native.so"


def _find_library() -> Optional[str]:
    if os.environ.get("TPUMNIST_NATIVE", "") == "0":
        # Explicit fallback switch: equivalence tests run the pure-NumPy
        # path in a process that HAS the library.
        return None
    # TPUMNIST_ is the house env prefix (compile cache, faults,
    # timeouts); the historical TPU_MNIST_ spelling keeps working.
    override = (os.environ.get("TPUMNIST_NATIVE_LIB")
                or os.environ.get("TPU_MNIST_NATIVE_LIB"))
    candidates = [override] if override else []
    here = os.path.dirname(os.path.abspath(__file__))
    repo_root = os.path.dirname(os.path.dirname(here))
    candidates += [
        os.path.join(repo_root, "native", _LIB_NAME),
        os.path.join(here, _LIB_NAME),
    ]
    for c in candidates:
        if c and os.path.isfile(c):
            return c
    return None


_lib = None
#: Negative-cache sentinel: pad_into/cast_f32 run PER DISPATCHED BATCH
#: on the serve hot path, so a fallback environment must not re-walk
#: the filesystem probe (env reads + two stat()s) on every batch.
#: ``_lib = None`` stays the one reset switch (tests rely on it) — it
#: clears this cache too.
_MISSING = object()


def _load():
    global _lib
    if _lib is not None:
        return None if _lib is _MISSING else _lib
    path = _find_library()
    if path is None:
        _lib = _MISSING
        return None
    try:
        lib = ctypes.CDLL(path)
    except OSError:
        _lib = _MISSING
        return None
    lib.tm_idx_load.restype = ctypes.POINTER(ctypes.c_uint8)
    lib.tm_idx_load.argtypes = [
        ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int), ctypes.c_int,
        ctypes.POINTER(ctypes.c_int64),
    ]
    lib.tm_free.restype = None
    lib.tm_free.argtypes = [ctypes.c_void_p]
    lib.tm_normalize.restype = ctypes.c_int
    lib.tm_normalize.argtypes = [
        ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_float),
        ctypes.c_int64, ctypes.c_float, ctypes.c_float, ctypes.c_int,
    ]
    lib.tm_gather.restype = ctypes.c_int
    lib.tm_gather.argtypes = [
        ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int64, ctypes.POINTER(ctypes.c_float),
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int,
    ]
    lib.tm_version.restype = ctypes.c_int
    if lib.tm_version() < 4:
        # A stale library is rejected WHOLE, not just its missing
        # symbols: v3 rewrote tm_normalize to the fallback's exact f32 op
        # sequence (the old fused kernel is ~1ulp off the bits every
        # trajectory/equivalence pin asserts), and v4 added the
        # quant/dequant entry points the int8 serving plane stages
        # through — a partial surface would silently mix native and
        # fallback behavior per call site. Stale (pre-v4) -> fallback,
        # per DESIGN.md 4b's matrix.
        _lib = _MISSING
        return None
    # v3 entry points (serve dispatch path) — guaranteed present past
    # the version gate above. void-pointer argtypes on purpose: these
    # two run PER DISPATCHED BATCH on the serve hot path, and
    # ``ndarray.ctypes.data_as`` costs ~5us per cast while the raw
    # ``.ctypes.data`` integer is sub-microsecond — at bucket sizes the
    # cast overhead alone exceeded the copy.
    lib.tm_pad_copy.restype = ctypes.c_int
    lib.tm_pad_copy.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int,
    ]
    lib.tm_cast_f32.restype = ctypes.c_int
    lib.tm_cast_f32.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_int,
    ]
    # v4 entry points (int8 serving plane): activation quantization runs
    # PER DISPATCHED BATCH on the serve hot path — same raw-pointer
    # argtypes rationale as pad_copy/cast_f32 above.
    lib.tm_quant_i8.restype = ctypes.c_int
    lib.tm_quant_i8.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_float,
        ctypes.c_int,
    ]
    lib.tm_dequant_f32.restype = ctypes.c_int
    lib.tm_dequant_f32.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_float,
        ctypes.c_int,
    ]
    _lib = lib
    return lib


def available() -> bool:
    return _load() is not None


def parse_idx(path: str) -> Optional[np.ndarray]:
    """Native IDX parse (uint8 only), one read+inflate pass; None if
    unavailable or unsupported (the NumPy path then produces the real error)."""
    lib = _load()
    if lib is None:
        return None
    dims = (ctypes.c_int64 * 8)()
    ndim = ctypes.c_int(0)
    count = ctypes.c_int64(0)
    buf = lib.tm_idx_load(path.encode(), dims, ctypes.byref(ndim), 8,
                          ctypes.byref(count))
    if not buf:
        return None
    try:
        shape = tuple(int(dims[i]) for i in range(ndim.value))
        arr = np.ctypeslib.as_array(buf, shape=(int(count.value),)).copy()
    finally:
        lib.tm_free(buf)
    return arr.reshape(shape)


def normalize_images(images: np.ndarray, mean: float, std: float,
                     workers: int = 4) -> Optional[np.ndarray]:
    """Native (x/255 - mean)/std; returns (N,28,28,1) f32 or None."""
    lib = _load()
    if lib is None:
        return None
    flat = np.ascontiguousarray(images, np.uint8).reshape(-1)
    out = np.empty(flat.size, np.float32)
    lib.tm_normalize(
        flat.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        flat.size, mean, std, workers,
    )
    return out.reshape(images.shape + (1,))


def gather_epoch(
    images: np.ndarray, labels: np.ndarray, index_matrix: np.ndarray,
    workers: int = 4,
) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Native stacked-epoch gather: images (N, ...) f32, labels (N,) i32,
    index_matrix (S, B) -> ((S, B, ...) images, (S, B) labels), or None."""
    lib = _load()
    if lib is None:
        return None
    images = np.ascontiguousarray(images, np.float32)
    labels = np.ascontiguousarray(labels, np.int32)
    idx = np.ascontiguousarray(index_matrix, np.int64).reshape(-1)
    row = int(np.prod(images.shape[1:]))
    out_images = np.empty((idx.size, row), np.float32)
    out_labels = np.empty(idx.size, np.int32)
    rc = lib.tm_gather(
        images.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        labels.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        idx.size, row, images.shape[0],
        out_images.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        out_labels.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        workers,
    )
    if rc != 0:
        return None
    shape = index_matrix.shape + images.shape[1:]
    return out_images.reshape(shape), out_labels.reshape(index_matrix.shape)


def pad_into(dst: np.ndarray, src: np.ndarray, workers: int = 4) -> bool:
    """Native serve-dispatch staging fill: ``dst[:len(src)] = src;
    dst[len(src):] = 0`` in multithreaded C++. Returns False (caller runs
    the bitwise-identical NumPy fallback) when the library is absent/old
    or either array is not float32 C-contiguous with matching rows."""
    lib = _load()
    if lib is None:  # absent, unloadable, or pre-v3 (rejected whole)
        return False
    if dst.dtype != np.float32 or src.dtype != np.float32:
        return False
    if not (dst.flags["C_CONTIGUOUS"] and src.flags["C_CONTIGUOUS"]):
        return False
    if not dst.flags["WRITEABLE"]:
        # The C kernel writes through the raw pointer; a frozen dst must
        # fall back so NumPy's slice-assign raises like it always did.
        return False
    if dst.ndim < 1 or src.shape[1:] != dst.shape[1:] \
            or src.shape[0] > dst.shape[0]:
        return False
    row = 1
    for d in dst.shape[1:]:
        row *= d
    rc = lib.tm_pad_copy(src.ctypes.data, src.shape[0], row,
                         dst.ctypes.data, dst.shape[0], workers)
    return rc == 0


def quant_i8(arr: np.ndarray, scale: float,
             workers: int = 4) -> Optional[np.ndarray]:
    """Native float32 -> int8 symmetric quantization:
    ``clip(rint(x * (1/scale)), -127, 127)`` with round-to-nearest-even —
    BITWISE-identical to the NumPy fallback (which must multiply by the
    same precomputed f32 reciprocal, not divide; ``serve/programs.py``
    does). None when the library is absent/old, the dtype/layout is
    wrong, or the scale is not positive."""
    lib = _load()
    if lib is None:  # absent, unloadable, or pre-v4 (rejected whole)
        return None
    if arr.dtype != np.float32 or not arr.flags["C_CONTIGUOUS"]:
        return None
    if not (scale > 0.0):
        return None
    out = np.empty(arr.shape, np.int8)
    rc = lib.tm_quant_i8(arr.ctypes.data, out.ctypes.data, arr.size,
                         scale, workers)
    return out if rc == 0 else None


def dequant_f32(arr: np.ndarray, scale: float,
                workers: int = 4) -> Optional[np.ndarray]:
    """Native int8 -> float32 dequantization (``float(q) * scale``, the
    NumPy fallback's exact op — bitwise-identical); None when the
    library is absent/old or the dtype/layout is wrong."""
    lib = _load()
    if lib is None:  # absent, unloadable, or pre-v4 (rejected whole)
        return None
    if arr.dtype != np.int8 or not arr.flags["C_CONTIGUOUS"]:
        return None
    out = np.empty(arr.shape, np.float32)
    rc = lib.tm_dequant_f32(arr.ctypes.data, out.ctypes.data, arr.size,
                            scale, workers)
    return out if rc == 0 else None


def cast_f32(arr: np.ndarray, workers: int = 4) -> Optional[np.ndarray]:
    """Native float64 -> float32 (round-to-nearest-even, the same C
    conversion NumPy's ``astype`` performs — bitwise-identical); None for
    any other dtype/layout or when the library is absent/old."""
    lib = _load()
    if lib is None:  # absent, unloadable, or pre-v3 (rejected whole)
        return None
    if arr.dtype != np.float64 or not arr.flags["C_CONTIGUOUS"]:
        return None
    out = np.empty(arr.shape, np.float32)
    rc = lib.tm_cast_f32(arr.ctypes.data, out.ctypes.data,
                         arr.size, workers)
    return out if rc == 0 else None
