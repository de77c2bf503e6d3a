"""ctypes bindings for the C++ native data pipeline (native/tpu_ddp_data.cpp).

The reference's data path is native too — torchvision's C transforms plus
the DataLoader worker pool (reference part1/main.py:19-50,36-41; SURVEY.md
§2 row N4). This module exposes that C++ replacement to Python:

- :func:`transform_batch` — one-shot augment+normalize of a batch (the
  transforms alone, used by tests and small jobs);
- :class:`NativeDataLoader` — drop-in for
  :class:`tpu_ddp.data.loader.DataLoader`: same ``set_epoch`` /
  ``__len__`` / ``__iter__`` contract, but batches are produced by C++
  worker threads into a bounded prefetch queue, so augmentation and
  normalization overlap with the device step (the reference gets this from
  ``num_workers=2``).

The shared library builds lazily on first use (``make -C native``); when no
toolchain is available, callers fall back to the numpy pipeline
(:func:`available` tells them).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

from tpu_ddp.data.cifar10 import CIFAR10_MEAN, CIFAR10_STD
from tpu_ddp.utils.config import SEED

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")


_i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
_u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
_i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
_f32p = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")


class NativeLib:
    """Shared lazy build-and-load machinery for the ctypes-bound C++
    libraries under ``native/`` (the image pipeline here, text packing
    in tpu_ddp/data/text.py): mtime-checked `make` on first use,
    negative-cached build errors, thread-safe single load."""

    def __init__(self, lib_name: str, src_name: str, bind):
        self._lib_path = os.path.join(_NATIVE_DIR, lib_name)
        self._src_path = os.path.join(_NATIVE_DIR, src_name)
        self._bind = bind
        self._lib = None
        self._lock = threading.Lock()
        self.build_error: str | None = None

    def _build(self) -> bool:
        # The .so is never committed (.gitignore): it is always built
        # here from the committed source, so one older than its source
        # is rebuilt.
        if os.path.exists(self._lib_path) and \
                os.path.getmtime(self._lib_path) >= \
                os.path.getmtime(self._src_path):
            return True
        try:
            # Build ONLY this library's target: a compile failure in a
            # sibling library must not poison this one, and per-target
            # builds can't race each other onto the same .so.
            subprocess.run(["make", "-C", _NATIVE_DIR,
                            os.path.basename(self._lib_path)],
                           check=True, capture_output=True, text=True,
                           timeout=300)
            return True
        except (subprocess.SubprocessError, OSError) as e:
            out = getattr(e, "stderr", "") or str(e)
            self.build_error = f"native build failed: {out[-500:]}"
            return False

    def get(self):
        """The loaded library, building if needed; None on failure."""
        with self._lock:
            if self._lib is not None:
                return self._lib
            if self.build_error is not None:
                return None  # negative-cached: don't re-spawn make
            if not self._build():
                return None
            try:
                self._lib = self._bind(ctypes.CDLL(self._lib_path))
            except OSError as e:  # pragma: no cover - exotic
                self.build_error = str(e)
                return None
            return self._lib


def _bind(lib):
    lib.tpu_ddp_transform_batch.argtypes = [
        _u8p, _i32p, ctypes.c_int64, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_void_p, ctypes.c_int64, _f32p, _f32p,
        ctypes.c_int, ctypes.c_uint64, ctypes.c_uint64, _f32p, _i32p]
    lib.tpu_ddp_transform_batch.restype = None
    lib.tpu_ddp_loader_create.argtypes = [
        _u8p, _i32p, ctypes.c_int64, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, _i64p, ctypes.c_int64, ctypes.c_int, _f32p, _f32p,
        ctypes.c_int, ctypes.c_uint64, ctypes.c_uint64, ctypes.c_int,
        ctypes.c_int]
    lib.tpu_ddp_loader_create.restype = ctypes.c_void_p
    lib.tpu_ddp_loader_next.argtypes = [ctypes.c_void_p, _f32p, _i32p]
    lib.tpu_ddp_loader_next.restype = ctypes.c_int
    lib.tpu_ddp_loader_destroy.argtypes = [ctypes.c_void_p]
    lib.tpu_ddp_loader_destroy.restype = None
    lib.tpu_ddp_version.restype = ctypes.c_int
    return lib


_data_lib = NativeLib("libtpu_ddp_data.so", "tpu_ddp_data.cpp", _bind)


def get_lib():
    """The loaded shared library, building it if needed; None on failure."""
    return _data_lib.get()


def available() -> bool:
    return get_lib() is not None


def build_error() -> str | None:
    return _data_lib.build_error


def transform_batch(images_u8, labels, indices=None, *, augment=False,
                    seed: int = SEED, epoch: int = 0,
                    mean=CIFAR10_MEAN, std=CIFAR10_STD):
    """Augment+normalize ``images_u8[indices]`` in C++; returns (f32, i32).

    With ``augment=False`` this is numerically identical to
    :func:`tpu_ddp.data.cifar10.normalize` (tested); with ``augment=True``
    it applies RandomCrop(pad 4)+RandomHorizontalFlip with counter-based,
    schedule-independent randomness.
    """
    lib = get_lib()
    if lib is None:
        raise RuntimeError(f"native library unavailable: {_data_lib.build_error}")
    images_u8 = np.ascontiguousarray(images_u8, dtype=np.uint8)
    labels = np.ascontiguousarray(labels, dtype=np.int32)
    n, h, w, c = images_u8.shape
    if indices is None:
        idx_ptr, n_out = None, n
    else:
        indices = np.ascontiguousarray(indices, dtype=np.int64)
        idx_ptr = indices.ctypes.data_as(ctypes.c_void_p)
        n_out = len(indices)
    out_x = np.empty((n_out, h, w, c), np.float32)
    out_y = np.empty((n_out,), np.int32)
    lib.tpu_ddp_transform_batch(
        images_u8, labels, n, h, w, c, idx_ptr, n_out,
        np.ascontiguousarray(mean, np.float32),
        np.ascontiguousarray(std, np.float32),
        int(augment), seed, epoch, out_x, out_y)
    return out_x, out_y


class NativeDataLoader:
    """C++-prefetched drop-in for :class:`tpu_ddp.data.loader.DataLoader`.

    Same constructor surface and iteration contract (normalized f32 NHWC
    images, i32 labels; ``drop_last=False`` keeps the short final batch).
    ``num_threads``/``prefetch_depth`` mirror the reference DataLoader's
    ``num_workers=2`` + its 2-batch-per-worker prefetch.
    """

    def __init__(self, images_u8, labels, batch_size, sampler=None,
                 augment=False, seed: int = SEED, num_threads: int = 2,
                 prefetch_depth: int = 4,
                 mean=CIFAR10_MEAN, std=CIFAR10_STD):
        self.images_u8 = np.ascontiguousarray(images_u8, dtype=np.uint8)
        self.labels = np.ascontiguousarray(labels, dtype=np.int32)
        self.batch_size = batch_size
        self.sampler = sampler
        self.augment = augment
        self.seed = seed
        self.epoch = 0
        self.num_threads = num_threads
        self.prefetch_depth = prefetch_depth
        self.mean = np.ascontiguousarray(mean, np.float32)
        self.std = np.ascontiguousarray(std, np.float32)
        if get_lib() is None:
            raise RuntimeError(f"native library unavailable: {_data_lib.build_error}")

    def set_epoch(self, epoch: int) -> None:
        self.epoch = epoch
        if self.sampler is not None:
            self.sampler.set_epoch(epoch)

    def _order(self):
        if self.sampler is not None:
            return np.ascontiguousarray(self.sampler.indices(), np.int64)
        return np.arange(len(self.labels), dtype=np.int64)

    def __len__(self) -> int:
        n = len(self.sampler) if self.sampler is not None \
            else len(self.labels)
        return (n + self.batch_size - 1) // self.batch_size

    def __iter__(self):
        lib = get_lib()
        order = self._order()
        n, h, w, c = self.images_u8.shape
        handle = lib.tpu_ddp_loader_create(
            self.images_u8, self.labels, n, h, w, c, order, len(order),
            self.batch_size, self.mean, self.std, int(self.augment),
            self.seed, self.epoch, self.num_threads, self.prefetch_depth)
        if not handle:
            raise RuntimeError("tpu_ddp_loader_create failed")
        out_x = np.empty((self.batch_size, h, w, c), np.float32)
        out_y = np.empty((self.batch_size,), np.int32)
        try:
            while True:
                got = lib.tpu_ddp_loader_next(handle, out_x, out_y)
                if got < 0:
                    break
                # Copy out: the queue buffer is reused next iteration.
                yield out_x[:got].copy(), out_y[:got].copy()
        finally:
            lib.tpu_ddp_loader_destroy(handle)
