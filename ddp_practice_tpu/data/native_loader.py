"""ctypes binding for the native (C++) batch-assembly backend.

Builds `native/libddp_loader.<source hash>.so` on first use if a compiler
is available (no pybind11 in this environment; the C ABI + ctypes keeps
the binding dependency-free). A library is loaded only when it was built
from the `dataloader.cpp` and `Makefile` now in `native/` — the hash of
both is in its filename, so a binary left on disk by another checkout or
another machine's build is never picked up. Falls back to numpy when it
cannot build — callers treat None from `make_gather` as "use the numpy
path", which is bit-identical; `available()` says which one a run got.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import threading
from typing import Callable, Optional

import numpy as np

from ddp_practice_tpu.data.datasets import Dataset

_NATIVE_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "native")
)

_lib = None
_lib_lock = threading.Lock()


_ABI_VERSION = 3  # keep in sync with dl_version() in native/dataloader.cpp


def _so_name() -> Optional[str]:
    """`libddp_loader.<hash>.so` for the sources on disk (None: no
    sources, nothing to build). A hash in the filename, not a stamp
    beside it: dlopen caches handles by pathname, so a rebuilt library
    under an old name would never be re-loaded in-process either."""
    h = hashlib.sha256()
    try:
        for name in ("dataloader.cpp", "Makefile"):
            with open(os.path.join(_NATIVE_DIR, name), "rb") as f:
                h.update(f.read())
    except OSError:
        return None
    return f"libddp_loader.{h.hexdigest()[:12]}.so"


def _load_library() -> Optional[ctypes.CDLL]:
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib if _lib is not _UNAVAILABLE else None
        _lib = _UNAVAILABLE  # the negative result is cached too
        name = _so_name()
        if name is None:
            return None
        so_path = os.path.join(_NATIVE_DIR, name)
        if not os.path.exists(so_path):
            _build(name)
        if not os.path.exists(so_path):
            return None
        lib = ctypes.CDLL(so_path)
        lib.dl_create.restype = ctypes.c_void_p
        lib.dl_create.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int32,
        ]
        lib.dl_destroy.argtypes = [ctypes.c_void_p]
        lib.dl_gather.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int32,
        ]
        lib.dl_gather.restype = ctypes.c_int32
        lib.dl_version.restype = ctypes.c_int32
        if lib.dl_version() != _ABI_VERSION:  # binding/source drift guard
            return None
        _lib = lib
        return _lib


_UNAVAILABLE = object()  # sentinel: library looked for and not usable


def _build(name: str) -> None:
    """make TARGET=<name>, after removing libraries of other sources.
    A failed build (no compiler, read-only tree) leaves nothing behind
    and the caller uses numpy."""
    for stale in glob.glob(os.path.join(_NATIVE_DIR, "libddp_loader*.so")):
        if os.path.basename(stale) != name:  # a concurrent build's result
            try:
                os.unlink(stale)
            except OSError:
                pass
    try:
        subprocess.run(
            ["make", "-C", _NATIVE_DIR, f"TARGET={name}"],
            check=True,
            capture_output=True,
            timeout=120,
        )
    except (subprocess.SubprocessError, OSError):
        pass


class _NativeGather:
    """Callable gather backed by the C++ library.

    Wraps the dataset's own storage zero-copy in its own dtype: fp32
    arrays stay fp32, uint8 stays uint8 (4x less memory traffic), and a
    memmapped corpus is wrapped at its mapped address — the C++ memcpy
    then streams pages from disk through the OS page cache. References
    are held for the handle's lifetime.
    """

    def __init__(self, lib: ctypes.CDLL, dataset: Dataset):
        self._lib = lib
        # already-contiguous arrays (incl. .npy memmaps) pass through as
        # views — no copy, no fp32 materialization
        self._images = np.ascontiguousarray(dataset.images)
        self._labels = np.ascontiguousarray(dataset.labels, dtype=np.int32)
        self._dtype = self._images.dtype
        self._sample_shape = self._images.shape[1:]
        self._sample_elems = int(np.prod(self._sample_shape))
        self._handle = lib.dl_create(
            self._images.ctypes.data_as(ctypes.c_void_p),
            self._labels.ctypes.data_as(ctypes.c_void_p),
            len(self._images),
            self._sample_elems,
            self._dtype.itemsize,
        )

    def __call__(self, indices: np.ndarray):
        idx = np.ascontiguousarray(indices, dtype=np.int64)
        n = len(idx)
        out_images = np.empty((n,) + self._sample_shape, self._dtype)
        out_labels = np.empty((n,), np.int32)
        status = self._lib.dl_gather(
            self._handle,
            idx.ctypes.data_as(ctypes.c_void_p),
            n,
            out_images.ctypes.data_as(ctypes.c_void_p),
            out_labels.ctypes.data_as(ctypes.c_void_p),
            0,
        )
        if status != 0:  # same error class as the numpy fancy-index path
            raise IndexError(
                f"native gather: index out of range for dataset of "
                f"{len(self._images)} samples"
            )
        return out_images, out_labels

    def __del__(self):
        try:
            if self._handle:
                self._lib.dl_destroy(self._handle)
        except Exception:
            pass


def make_gather(dataset: Dataset) -> Optional[Callable]:
    """Return a native gather callable, or None if the backend is
    unavailable (caller falls back to numpy)."""
    lib = _load_library()
    if lib is None:
        return None
    return _NativeGather(lib, dataset)


def available() -> bool:
    return _load_library() is not None
