"""Native runtime components (C++ via ctypes).

The reference's native runtime surface — dmlc RecordIO reader, threaded IO
parser/prefetcher (src/io/) — re-implemented TPU-host-side in C++
(recordio.cc). Built on demand with g++ (no pybind11 in this image; plain
C ABI + ctypes). `lib()` compiles lazily and caches the .so next to the
source; all Python-level classes degrade gracefully to the pure-Python
implementations when a toolchain is unavailable.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as onp

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRCS = [os.path.join(_HERE, "recordio.cc"),
         os.path.join(_HERE, "image_pipeline.cc")]
_SRC = _SRCS[0]  # kept for external references
_SO = os.path.join(_HERE, "libmxtpu_native.so")

_lock = threading.Lock()
_lib = None
_build_error = None


def _build_if_stale(so: str, srcs, cmd_for, force: bool) -> str:
    """Build ``so`` unless it was built from exactly these sources by
    exactly this command. The key is a hash of the source bytes and the
    command, kept beside the library: ``*.so`` is git-ignored, so a
    binary left in a checkout from older sources (file times say
    nothing after a copy) must never be loaded."""
    import hashlib
    tmp = f"{so}.tmp{os.getpid()}"
    h = hashlib.sha256("\0".join(cmd_for("@OUT@")).encode())
    for src in srcs:
        with open(src, "rb") as f:
            h.update(f.read())
    key, key_path = h.hexdigest(), so + ".srchash"
    if not force and os.path.exists(so):
        try:
            with open(key_path) as f:
                if f.read().strip() == key:
                    return so
        except OSError:
            pass
    try:
        subprocess.run(cmd_for(tmp), check=True, capture_output=True)
        os.replace(tmp, so)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    with open(key_path, "w") as f:
        f.write(key + "\n")
    return so


def build(force: bool = False) -> str:
    """Compile the native library (cached by source hash)."""
    return _build_if_stale(
        _SO, _SRCS,
        lambda out: ["g++", "-O3", "-std=c++17", "-shared", "-fPIC",
                     "-pthread", *_SRCS, "-o", out, "-ljpeg"], force)


def lib():
    """Load (building if needed) the native library, or None."""
    global _lib, _build_error
    with _lock:
        if _lib is not None or _build_error is not None:
            return _lib
        try:
            path = build()
            L = ctypes.CDLL(path)
            L.rio_open.restype = ctypes.c_void_p
            L.rio_open.argtypes = [ctypes.c_char_p]
            L.rio_error.restype = ctypes.c_char_p
            L.rio_error.argtypes = [ctypes.c_void_p]
            L.rio_count.restype = ctypes.c_int64
            L.rio_count.argtypes = [ctypes.c_void_p]
            L.rio_get.restype = ctypes.c_int64
            L.rio_get.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                  ctypes.POINTER(ctypes.POINTER(
                                      ctypes.c_uint8))]
            L.rio_close.argtypes = [ctypes.c_void_p]
            L.rio_writer_open.restype = ctypes.c_void_p
            L.rio_writer_open.argtypes = [ctypes.c_char_p]
            L.rio_writer_write.restype = ctypes.c_int
            L.rio_writer_write.argtypes = [ctypes.c_void_p,
                                           ctypes.c_char_p, ctypes.c_int64]
            L.rio_writer_close.argtypes = [ctypes.c_void_p]
            L.rio_batch_server_create.restype = ctypes.c_void_p
            L.rio_batch_server_create.argtypes = [
                ctypes.c_void_p, ctypes.c_int, ctypes.c_int,
                ctypes.c_uint64, ctypes.c_int]
            L.rio_batch_next.restype = ctypes.c_void_p
            L.rio_batch_next.argtypes = [ctypes.c_void_p]
            L.rio_batch_total_bytes.restype = ctypes.c_int64
            L.rio_batch_total_bytes.argtypes = [ctypes.c_void_p]
            L.rio_batch_data.restype = ctypes.POINTER(ctypes.c_uint8)
            L.rio_batch_data.argtypes = [ctypes.c_void_p]
            L.rio_batch_offsets.restype = ctypes.POINTER(ctypes.c_int64)
            L.rio_batch_offsets.argtypes = [ctypes.c_void_p]
            L.rio_batch_lengths.restype = ctypes.POINTER(ctypes.c_int64)
            L.rio_batch_lengths.argtypes = [ctypes.c_void_p]
            L.rio_batch_size.restype = ctypes.c_int64
            L.rio_batch_size.argtypes = [ctypes.c_void_p]
            L.rio_batch_free.argtypes = [ctypes.c_void_p]
            L.rio_batch_server_reset.argtypes = [ctypes.c_void_p]
            L.rio_batch_server_destroy.argtypes = [ctypes.c_void_p]
            L.imgpipe_create.restype = ctypes.c_void_p
            L.imgpipe_create.argtypes = [
                ctypes.c_void_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
                ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
                ctypes.c_int, ctypes.c_int, ctypes.c_int,
                ctypes.POINTER(ctypes.c_float),
                ctypes.POINTER(ctypes.c_float), ctypes.c_uint64,
                ctypes.c_int, ctypes.c_float, ctypes.c_int]
            L.imgpipe_next.restype = ctypes.c_void_p
            L.imgpipe_next.argtypes = [ctypes.c_void_p]
            L.imgpipe_batch_data.restype = ctypes.POINTER(ctypes.c_float)
            L.imgpipe_batch_data.argtypes = [ctypes.c_void_p]
            L.imgpipe_batch_labels.restype = ctypes.POINTER(ctypes.c_float)
            L.imgpipe_batch_labels.argtypes = [ctypes.c_void_p]
            L.imgpipe_batch_n.restype = ctypes.c_int64
            L.imgpipe_batch_n.argtypes = [ctypes.c_void_p]
            L.imgpipe_batch_pad.restype = ctypes.c_int64
            L.imgpipe_batch_pad.argtypes = [ctypes.c_void_p]
            L.imgpipe_batch_free.argtypes = [ctypes.c_void_p]
            L.imgpipe_reset.argtypes = [ctypes.c_void_p]
            L.imgpipe_decode_failures.restype = ctypes.c_int64
            L.imgpipe_decode_failures.argtypes = [ctypes.c_void_p]
            L.imgpipe_destroy.argtypes = [ctypes.c_void_p]
            _lib = L
        except Exception as e:  # toolchain missing → python fallback
            _build_error = e
            _lib = None
        return _lib


def available() -> bool:
    return lib() is not None


def status() -> dict:
    """Whether the native library built and loaded in this process, and
    the build/load error when it did not (callers then run the
    pure-Python implementations)."""
    loaded = available()
    return {"loaded": loaded, "path": _SO,
            "error": None if loaded else
            f"{type(_build_error).__name__}: {_build_error}"}


class NativeRecordIO:
    """mmap'd zero-copy indexed reader (drop-in fast path for
    recordio.MXRecordIO read access)."""

    def __init__(self, path: str):
        L = lib()
        if L is None:
            raise RuntimeError(f"native lib unavailable: {_build_error}")
        self._L = L
        self._h = L.rio_open(path.encode())
        if not self._h:
            raise IOError(f"cannot open {path}")
        err = L.rio_error(self._h)
        if err:
            raise IOError(err.decode())

    def __len__(self):
        return int(self._L.rio_count(self._h))

    def read_idx(self, i: int) -> bytes:
        ptr = ctypes.POINTER(ctypes.c_uint8)()
        n = self._L.rio_get(self._h, i, ctypes.byref(ptr))
        if n < 0:
            raise IndexError(i)
        return ctypes.string_at(ptr, n)

    def close(self):
        if self._h:
            self._L.rio_close(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class NativeRecordIOWriter:
    def __init__(self, path: str):
        L = lib()
        if L is None:
            raise RuntimeError(f"native lib unavailable: {_build_error}")
        self._L = L
        self._h = L.rio_writer_open(path.encode())
        if not self._h:
            raise IOError(f"cannot open {path}")

    def write(self, buf: bytes):
        if self._L.rio_writer_write(self._h, buf, len(buf)) != 0:
            raise IOError("write failed")

    def close(self):
        if self._h:
            self._L.rio_writer_close(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class NativeBatchServer:
    """Threaded shuffled batch prefetcher (the iter_prefetcher.h /
    parser-thread role of the reference's C++ IO pipeline)."""

    def __init__(self, path: str, batch_size: int, shuffle: bool = False,
                 seed: int = 0, num_workers: int = 0):
        if num_workers <= 0:
            # MXNET_CPU_WORKER_NTHREADS sizes the native IO thread pool
            # (ref: env_var.md:25 — the CPU engine worker count)
            from ..base import get_env
            num_workers = max(2, int(get_env("MXNET_CPU_WORKER_NTHREADS",
                                             1)))
        self._reader = NativeRecordIO(path)
        self._L = self._reader._L
        self._h = self._L.rio_batch_server_create(
            self._reader._h, batch_size, int(shuffle), seed, num_workers)
        self.batch_size = batch_size

    def __iter__(self):
        while True:
            b = self._L.rio_batch_next(self._h)
            if not b:
                return
            n = int(self._L.rio_batch_size(b))
            total = int(self._L.rio_batch_total_bytes(b))
            data = onp.ctypeslib.as_array(self._L.rio_batch_data(b),
                                          shape=(total,)).copy()
            offs = onp.ctypeslib.as_array(self._L.rio_batch_offsets(b),
                                          shape=(n,)).copy()
            lens = onp.ctypeslib.as_array(self._L.rio_batch_lengths(b),
                                          shape=(n,)).copy()
            self._L.rio_batch_free(b)
            yield [data[o:o + l].tobytes()
                   for o, l in zip(offs.tolist(), lens.tolist())]

    def reset(self):
        self._L.rio_batch_server_reset(self._h)

    def close(self):
        if getattr(self, "_h", None):
            self._L.rio_batch_server_destroy(self._h)
            self._h = None
            self._reader.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


# ---------------------------------------------------------------------------
# C predict API library (ref: src/c_api/c_predict_api.cc — the standalone
# inference ABI). Separate .so because it links libpython (the RecordIO
# library stays interpreter-free).
# ---------------------------------------------------------------------------

_CAPI_SRC = os.path.join(_HERE, "c_predict_api.cc")
_CAPI_SO = os.path.join(_HERE, "libmxtpu_capi.so")


_CAPI_HDR = os.path.join(_HERE, "mxtpu_predict.h")


def build_capi(force: bool = False) -> str:
    """Compile libmxtpu_capi.so (cached by source+header hash)."""
    import sysconfig
    inc = sysconfig.get_path("include")
    libdir = sysconfig.get_config_var("LIBDIR")
    ldver = sysconfig.get_config_var("LDVERSION")
    return _build_if_stale(
        _CAPI_SO, [_CAPI_SRC, _CAPI_HDR],
        lambda out: ["g++", "-O2", "-std=c++17", "-shared", "-fPIC",
                     _CAPI_SRC, f"-I{inc}", f"-L{libdir}",
                     f"-lpython{ldver}", f"-Wl,-rpath,{libdir}",
                     "-o", out], force)


class NativeImagePipeline:
    """Threaded JPEG decode + augment + batch pipeline (image_pipeline.cc;
    ref: src/io/iter_image_recordio_2.cc parser threads +
    image_aug_default.cc). Yields (data, label) float32 numpy batches,
    NCHW by default."""

    def __init__(self, path: str, batch_size: int, data_shape=(3, 224, 224),
                 label_width: int = 1, shuffle: bool = False, resize: int = 0,
                 rand_crop: bool = False, rand_mirror: bool = False,
                 mean=None, std=None, seed: int = 0, num_workers: int = 0,
                 layout: str = "NCHW", label_pad_value: float = 0.0,
                 force_resize: bool = False):
        L = lib()
        if L is None:
            raise RuntimeError(f"native lib unavailable: {_build_error}")
        if num_workers <= 0:
            # MXNET_CPU_WORKER_NTHREADS sizes the native IO thread pool
            from ..base import get_env
            num_workers = max(2, int(get_env("MXNET_CPU_WORKER_NTHREADS",
                                             1)))
        self._L = L
        self._reader = NativeRecordIO(path)
        c, h, w = data_shape
        m = (ctypes.c_float * 3)(*(mean if mean is not None else (0, 0, 0)))
        s = (ctypes.c_float * 3)(*(std if std is not None else (1, 1, 1)))
        self._nhwc = layout == "NHWC"
        self._h = L.imgpipe_create(
            self._reader._h, batch_size, c, h, w, int(resize),
            int(label_width), int(rand_crop), int(rand_mirror),
            int(shuffle), int(self._nhwc), m, s, seed, num_workers,
            float(label_pad_value), int(force_resize))
        if not self._h:
            self._reader.close()
            raise ValueError(
                f"imgpipe_create rejected batch_size={batch_size}")
        self.batch_size = batch_size
        self.data_shape = tuple(data_shape)
        self.label_width = label_width

    def __iter__(self):
        c, h, w = self.data_shape
        shape = (self.batch_size, h, w, c) if self._nhwc \
            else (self.batch_size, c, h, w)
        n_img = self.batch_size * c * h * w
        n_lbl = self.batch_size * self.label_width
        while True:
            b = self._L.imgpipe_next(self._h)
            if not b:
                return
            data = onp.ctypeslib.as_array(
                self._L.imgpipe_batch_data(b), shape=(n_img,)).copy()
            labels = onp.ctypeslib.as_array(
                self._L.imgpipe_batch_labels(b), shape=(n_lbl,)).copy()
            self.last_pad = int(self._L.imgpipe_batch_pad(b))
            self._L.imgpipe_batch_free(b)
            yield (data.reshape(shape),
                   labels.reshape(self.batch_size, self.label_width))

    def reset(self):
        self._L.imgpipe_reset(self._h)

    @property
    def decode_failures(self) -> int:
        return int(self._L.imgpipe_decode_failures(self._h))

    def close(self):
        if getattr(self, "_h", None):
            self._L.imgpipe_destroy(self._h)
            self._h = None
        if getattr(self, "_reader", None) is not None:
            self._reader.close()
            self._reader = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
