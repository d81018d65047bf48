"""Native C++ data backend: builds from source, then must agree bit-for-bit
with the NumPy fallback path (same contract, different engine)."""

import shutil
import subprocess

import numpy as np
import pytest

from pytorch_distributed_mnist_tpu.data import native
from pytorch_distributed_mnist_tpu.data.mnist import (
    MNIST_MEAN,
    MNIST_STD,
    synthetic_dataset,
    write_idx,
)


@pytest.fixture(scope="module", autouse=True)
def built_library():
    import os

    # This module TESTS the native engine: the fallback switch must not
    # turn the whole suite into fixture errors (or trigger a pointless
    # rebuild of a .so that exists). Lift it for the module and re-probe.
    switched_off = os.environ.get("TPUMNIST_NATIVE", "") == "0"
    if switched_off:
        del os.environ["TPUMNIST_NATIVE"]
        native._lib = None  # force re-probe without the switch
    try:
        if not native.available():
            if shutil.which("make") is None or shutil.which("g++") is None:
                pytest.skip("no native toolchain")
            import pytorch_distributed_mnist_tpu as pkg

            root = os.path.dirname(
                os.path.dirname(os.path.abspath(pkg.__file__)))
            subprocess.run(["make", "-C", os.path.join(root, "native")],
                           check=True)
            native._lib = None  # force re-probe
        assert native.available()
        yield
    finally:
        if switched_off:
            os.environ["TPUMNIST_NATIVE"] = "0"
            native._lib = None


def test_version():
    # v3 added the serve-dispatch entry points (tm_pad_copy,
    # tm_cast_f32); v4 the int8 serving plane's quant/dequant
    # (tm_quant_i8, tm_dequant_f32).
    assert native._load().tm_version() == 4


@pytest.mark.parametrize("stale_version", [2, 3])
def test_stale_library_rejected_whole(monkeypatch, stale_version):
    """A stale .so (TPU_MNIST_NATIVE_LIB override, or a never-re-made
    build) must be rejected WHOLE: a pre-v3 fused tm_normalize is ~1ulp
    off the bits every equivalence/trajectory pin asserts, and a pre-v4
    library lacks the quant/dequant entry points the int8 serving plane
    stages through — a partial surface would silently mix native and
    fallback per call site. Stale -> fallback, per DESIGN.md 4b."""
    class _Sym:
        def __init__(self, ret=None):
            self._ret = ret

        def __call__(self, *args):
            return self._ret

    class _StubLib:
        def __init__(self):
            for name in ("tm_idx_load", "tm_free", "tm_normalize",
                         "tm_gather", "tm_pad_copy", "tm_cast_f32"):
                setattr(self, name, _Sym())
            self.tm_version = _Sym(stale_version)

    monkeypatch.setattr(native, "_find_library", lambda: "stub.so")
    monkeypatch.setattr(native.ctypes, "CDLL", lambda path: _StubLib())
    native._lib = None
    try:
        assert native._load() is None
        assert not native.available()
    finally:
        native._lib = None  # re-probe the real library for later tests


def test_parse_idx_zero_length_dim(tmp_path):
    # (0, 28, 28): empty file must parse to an empty array, not crash.
    arr = np.zeros((0, 28, 28), np.uint8)
    p = str(tmp_path / "empty-idx3-ubyte")
    write_idx(p, arr)
    got = native.parse_idx(p)
    assert got is not None and got.shape == (0, 28, 28)


def test_parse_idx_truncated_payload(tmp_path):
    # Header promises more bytes than the file holds -> clean None.
    import struct

    p = str(tmp_path / "trunc-idx3-ubyte")
    with open(p, "wb") as f:
        f.write(struct.pack(">HBB", 0, 8, 3))
        f.write(struct.pack(">III", 100, 28, 28))
        f.write(b"\x00" * 10)  # far short of 100*28*28
    assert native.parse_idx(p) is None


def test_parse_idx_huge_ndim_byte(tmp_path):
    # data[3]=0xFF on a short file: must return None, not read out of bounds.
    p = str(tmp_path / "badndim")
    with open(p, "wb") as f:
        f.write(b"\x00\x00\x08\xff\x01")
    assert native.parse_idx(p) is None


def test_parse_idx_matches_numpy(tmp_path):
    arr = np.random.default_rng(0).integers(0, 256, (7, 28, 28)).astype(np.uint8)
    p = str(tmp_path / "imgs-idx3-ubyte")
    write_idx(p, arr)
    got = native.parse_idx(p)
    np.testing.assert_array_equal(got, arr)


def test_parse_idx_gzip(tmp_path):
    import gzip

    arr = np.arange(256, dtype=np.uint8)
    raw = str(tmp_path / "x-idx1-ubyte")
    write_idx(raw, arr)
    with open(raw, "rb") as f, gzip.open(raw + ".gz", "wb") as g:
        g.write(f.read())
    np.testing.assert_array_equal(native.parse_idx(raw + ".gz"), arr)


def test_parse_idx_bad_file_returns_none(tmp_path):
    p = str(tmp_path / "bad")
    with open(p, "wb") as f:
        f.write(b"\x01\x02garbage")
    assert native.parse_idx(p) is None


def test_normalize_matches_numpy_bitwise():
    """The C kernel runs the fallback's exact float32 op sequence
    (div/sub/div, not a fused scale*x+offset), so the two engines agree
    to the BIT on every representable input — which engine normalized a
    batch can never show up in a trajectory. Exhaustive over all 256
    uint8 values."""
    images = np.arange(256, dtype=np.uint8).repeat(16).reshape(-1, 16, 4)
    got = native.normalize_images(images, MNIST_MEAN, MNIST_STD, workers=4)
    want = ((images.astype(np.float32) / 255.0 - MNIST_MEAN)
            / MNIST_STD)[..., None]
    np.testing.assert_array_equal(
        got.view(np.uint32), want.view(np.uint32))


def test_gather_matches_numpy_fancy_indexing():
    rng = np.random.default_rng(1)
    images = rng.normal(size=(50, 28, 28, 1)).astype(np.float32)
    labels = rng.integers(0, 10, 50).astype(np.int32)
    idx = rng.integers(0, 50, (4, 8))
    got_imgs, got_lbls = native.gather_epoch(images, labels, idx, workers=3)
    np.testing.assert_array_equal(got_imgs, images[idx.reshape(-1)].reshape(4, 8, 28, 28, 1))
    np.testing.assert_array_equal(got_lbls, labels[idx.reshape(-1)].reshape(4, 8))


def test_gather_out_of_bounds_returns_none():
    images = np.zeros((5, 2), np.float32)
    labels = np.zeros(5, np.int32)
    idx = np.array([[0, 99]])
    assert native.gather_epoch(images, labels, idx) is None


def test_gather_matches_numpy_bitwise():
    """The epoch gather is a row copy: bitwise by construction, pinned
    so a future 'optimization' can't quietly change that."""
    rng = np.random.default_rng(8)
    images = rng.normal(size=(64, 28, 28, 1)).astype(np.float32)
    labels = rng.integers(0, 10, 64).astype(np.int32)
    idx = rng.integers(0, 64, (3, 16))
    got_imgs, got_lbls = native.gather_epoch(images, labels, idx, workers=4)
    want = images[idx.reshape(-1)].reshape(3, 16, 28, 28, 1)
    np.testing.assert_array_equal(
        got_imgs.view(np.uint32), want.view(np.uint32))
    np.testing.assert_array_equal(got_lbls, labels[idx.reshape(-1)].reshape(3, 16))


# -- v3 serve-dispatch entry points (ISSUE 6) --------------------------------


def _numpy_pad(dst, src):
    dst[:len(src)] = src
    dst[len(src):] = 0.0


@pytest.mark.parametrize("rows", [0, 1, 100, 128])
def test_pad_into_matches_numpy_bitwise(rows):
    """The staging fill (copy + zero tail) the serve dispatch runs per
    batch: native and the engine's NumPy fallback write identical
    bytes, including the degenerate empty and exact-fit cases."""
    rng = np.random.default_rng(rows)
    src = rng.normal(size=(rows, 28, 28, 1)).astype(np.float32)
    got = np.full((128, 28, 28, 1), np.nan, np.float32)
    want = np.full((128, 28, 28, 1), np.nan, np.float32)
    assert native.pad_into(got, src, workers=4)
    _numpy_pad(want, src)
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))


def test_pad_into_rejects_bad_layouts():
    """Anything the C kernel can't handle safely returns False — the
    caller runs the NumPy fallback, never a corrupted copy."""
    dst = np.zeros((8, 4), np.float32)
    assert not native.pad_into(dst, np.zeros((9, 4), np.float32))  # src > dst
    assert not native.pad_into(dst, np.zeros((2, 5), np.float32))  # row shape
    assert not native.pad_into(dst, np.zeros((2, 4), np.float64))  # dtype
    assert not native.pad_into(
        dst, np.zeros((2, 8), np.float32)[:, ::2])  # non-contiguous src
    assert not native.pad_into(np.zeros((8, 4), np.float64),
                               np.zeros((2, 4), np.float32))  # dst dtype
    frozen = np.zeros((8, 4), np.float32)
    frozen.flags.writeable = False
    # A frozen dst must fall back (where NumPy's slice-assign raises),
    # never be scribbled through the raw pointer.
    assert not native.pad_into(frozen, np.zeros((2, 4), np.float32))


def test_cast_f32_matches_numpy_bitwise():
    """float64 -> float32 rounds to nearest even in both engines; the
    serve preprocess path may take either without a bit of drift."""
    rng = np.random.default_rng(11)
    arr = rng.normal(size=(129, 28, 28, 1)) * 1e3
    got = native.cast_f32(arr, workers=4)
    want = arr.astype(np.float32)
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))


def test_cast_f32_rejects_other_dtypes():
    assert native.cast_f32(np.zeros((2, 2), np.float32)) is None
    assert native.cast_f32(np.zeros((2, 2), np.int64)) is None
    assert native.cast_f32(
        np.zeros((2, 8), np.float64)[:, ::2]) is None  # non-contiguous


def test_quant_i8_matches_numpy_bitwise():
    """float32 -> int8 symmetric quantization: the native kernel's
    round-to-nearest-even via the precomputed f32 reciprocal is
    BITWISE-identical to the NumPy fallback expression the serving
    plane uses (serve/programs.py) — which engine quantized a batch can
    never show up in the logits."""
    rng = np.random.default_rng(12)
    arr = (rng.normal(size=(65, 28, 28, 1)) * 2.5).astype(np.float32)
    scale = np.float32(np.abs(arr).max() / np.float32(127.0))
    got = native.quant_i8(arr, float(scale), workers=4)
    inv = np.float32(1.0) / scale
    want = np.clip(np.rint(arr * inv), -127, 127).astype(np.int8)
    np.testing.assert_array_equal(got, want)
    # Ties (x/scale exactly .5) round to even in both engines.
    half = (np.arange(-8, 8, dtype=np.float32) + np.float32(0.5))
    got_half = native.quant_i8(half, 1.0, workers=1)
    want_half = np.clip(np.rint(half), -127, 127).astype(np.int8)
    np.testing.assert_array_equal(got_half, want_half)


def test_quant_i8_non_finite_pinned():
    """NaN quantizes to 0 and ±inf clips to ±127 in BOTH engines
    (static_cast of NaN is UB in C; NaN.astype(int8) is platform-
    defined in NumPy — both paths pin the same explicit values, so a
    client-supplied non-finite pixel can't make the engines diverge)."""
    x = np.array([np.nan, np.inf, -np.inf, 1.0], np.float32)
    got = native.quant_i8(x, 1.0, workers=1)
    np.testing.assert_array_equal(got, np.array([0, 127, -127, 1],
                                                np.int8))


def test_quant_i8_rejects_bad_inputs():
    assert native.quant_i8(np.zeros((2, 2), np.float64), 1.0) is None
    assert native.quant_i8(np.zeros((2, 2), np.float32), 0.0) is None
    assert native.quant_i8(np.zeros((2, 2), np.float32), -1.0) is None
    assert native.quant_i8(
        np.zeros((2, 8), np.float32)[:, ::2], 1.0) is None  # non-contiguous


def test_dequant_f32_matches_numpy_bitwise():
    """int8 -> float32 dequantization (q * scale) is one f32 multiply
    per element in both engines — bitwise-identical."""
    q = np.arange(-127, 128, dtype=np.int8).reshape(5, 51)
    scale = np.float32(0.0123)
    got = native.dequant_f32(q, float(scale), workers=2)
    want = q.astype(np.float32) * scale
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))


def test_dequant_f32_rejects_other_dtypes():
    assert native.dequant_f32(np.zeros((2, 2), np.uint8), 1.0) is None
    assert native.dequant_f32(np.zeros((2, 2), np.float32), 1.0) is None


def test_tpumnist_native_zero_disables_library(monkeypatch):
    """TPUMNIST_NATIVE=0 is the explicit in-process fallback switch: the
    NumPy path runs with the library present."""
    monkeypatch.setenv("TPUMNIST_NATIVE", "0")
    monkeypatch.setattr(native, "_lib", None)
    assert not native.available()
    assert native.cast_f32(np.zeros((2, 2), np.float64)) is None
    assert not native.pad_into(np.zeros((4, 2), np.float32),
                               np.zeros((2, 2), np.float32))
    assert native.quant_i8(np.zeros((2, 2), np.float32), 1.0) is None
    assert native.dequant_f32(np.zeros((2, 2), np.int8), 1.0) is None
    monkeypatch.delenv("TPUMNIST_NATIVE")
    monkeypatch.setattr(native, "_lib", None)
    assert native.available()


def _numpy_mode(monkeypatch):
    monkeypatch.setenv("TPUMNIST_NATIVE", "0")
    monkeypatch.setattr(native, "_lib", None)


def test_engine_preprocess_native_equals_numpy_bitwise(monkeypatch):
    """THE dispatch-path equivalence pin: InferenceEngine.preprocess on
    raw uint8 and on float64 inputs returns bit-identical stacks
    whether the native library or the NumPy fallback runs."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_mnist_tpu.models import get_model
    from pytorch_distributed_mnist_tpu.serve.engine import InferenceEngine
    from pytorch_distributed_mnist_tpu.train.state import create_train_state

    model = get_model("linear", compute_dtype=jnp.float32)
    state = create_train_state(model, jax.random.key(0))
    engine = InferenceEngine(model.apply, state.params)
    raw, _ = synthetic_dataset(33, seed=6)
    f64 = np.random.default_rng(6).normal(size=(33, 28, 28, 1))

    nat_raw = engine.preprocess(raw)
    nat_f64 = engine.preprocess(f64)
    _numpy_mode(monkeypatch)
    np_raw = engine.preprocess(raw)
    np_f64 = engine.preprocess(f64)
    np.testing.assert_array_equal(nat_raw.view(np.uint32),
                                  np_raw.view(np.uint32))
    np.testing.assert_array_equal(nat_f64.view(np.uint32),
                                  np_f64.view(np.uint32))


def test_engine_predict_native_equals_numpy_bitwise(monkeypatch):
    """End-to-end dispatch: a padded (non-exact-bucket) predict returns
    bit-identical logits with the native staging fill on or off."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_mnist_tpu.models import get_model
    from pytorch_distributed_mnist_tpu.serve.engine import InferenceEngine
    from pytorch_distributed_mnist_tpu.train.state import create_train_state

    model = get_model("linear", compute_dtype=jnp.float32)
    state = create_train_state(model, jax.random.key(0))
    engine = InferenceEngine(model.apply, state.params)
    raw, _ = synthetic_dataset(13, seed=7)  # pads 13 -> bucket 32
    stack = engine.preprocess(raw)
    nat_logits = engine.logits(stack)
    _numpy_mode(monkeypatch)
    np_logits = engine.logits(stack)
    np.testing.assert_array_equal(
        np.asarray(nat_logits).view(np.uint32),
        np.asarray(np_logits).view(np.uint32))


@pytest.mark.slow
def test_library_builds_from_source(tmp_path):
    """The committed source must actually compile (make -C native) and
    export the v3 surface — otherwise the .so in the tree can silently
    rot while every test runs against the stale binary. Builds in a
    copy so the checked-in library is never raced."""
    if shutil.which("make") is None or shutil.which("g++") is None:
        pytest.skip("no native toolchain")
    import ctypes
    import os

    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "native")
    build = tmp_path / "native"
    shutil.copytree(src, build)
    os.remove(build / "libtpumnist_native.so")
    subprocess.run(["make", "-C", str(build)], check=True,
                   capture_output=True)
    lib = ctypes.CDLL(str(build / "libtpumnist_native.so"))
    lib.tm_version.restype = ctypes.c_int
    assert lib.tm_version() == 4
    for sym in ("tm_pad_copy", "tm_cast_f32", "tm_normalize", "tm_gather",
                "tm_quant_i8", "tm_dequant_f32"):
        assert hasattr(lib, sym)


def test_loader_native_and_numpy_stacked_epoch_agree():
    from pytorch_distributed_mnist_tpu.data.loader import MNISTDataLoader
    from pytorch_distributed_mnist_tpu.data.mnist import normalize_images

    images, labels = synthetic_dataset(120, seed=5)
    x = normalize_images(images)
    loader = MNISTDataLoader(x, labels.astype(np.int32), batch_size=32, train=True, seed=9)
    loader.set_sample_epoch(2)
    ep_native = loader.stacked_epoch()

    lib, native._lib = native._lib, None  # simulate missing library
    try:
        import unittest.mock as mock

        with mock.patch.object(native, "_find_library", return_value=None):
            ep_numpy = loader.stacked_epoch()
    finally:
        native._lib = lib
    for k in ("image", "label", "mask"):
        np.testing.assert_array_equal(ep_native[k], ep_numpy[k])
