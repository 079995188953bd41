"""ctypes bindings for the native data-plane library (native/recordio.cc).

The flat-C-ABI + ctypes boundary mirrors the reference's C API discipline
(include/mxnet/c_api.h ↔ python/mxnet/base.py ctypes loading). The library
is built on demand with `make -C native`; all callers degrade to the pure-
Python path when the toolchain or libjpeg is unavailable.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Optional

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_SO = os.path.join(_REPO, "native", "libmxtpu_io.so")

_lib = None
_tried = False


def build(target: str = "all", force: bool = False) -> None:
    """``make -C native <target>`` from ``native/*.cc`` (default: both
    libraries). ``force`` rebuilds whatever lies on disk (the ``.so``
    files are not tracked by git, so a copied tree may carry stale ones).
    Raises ``subprocess.CalledProcessError`` with the compiler's output
    when the build fails."""
    cmd = ["make", "-C", os.path.dirname(_SO), target]
    subprocess.run(cmd + (["-B"] if force else []), check=True,
                   capture_output=True, timeout=300)


def load() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native library; None if unavailable."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    _tried = True
    src = os.path.join(os.path.dirname(_SO), "recordio.cc")
    stale = (os.path.exists(_SO) and os.path.exists(src)
             and os.path.getmtime(src) > os.path.getmtime(_SO))
    if not os.path.exists(_SO) or stale:
        try:
            build()
        except (OSError, subprocess.SubprocessError):
            if stale:  # keep using the older (but loadable) build
                pass
            else:
                return None
    try:
        lib = ctypes.CDLL(_SO)
        _bind(lib)
    except (OSError, AttributeError):
        # missing file OR a prebuilt .so lacking even the core symbols:
        # degrade to the pure-Python path rather than crash
        return None
    _lib = lib
    return _lib


def _bind(lib: ctypes.CDLL) -> None:
    lib.mxio_reader_open.restype = ctypes.c_void_p
    lib.mxio_reader_open.argtypes = [ctypes.c_char_p, ctypes.c_int, ctypes.c_int]
    lib.mxio_reader_next.restype = ctypes.c_int
    lib.mxio_reader_next.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.POINTER(ctypes.c_uint64)]
    lib.mxio_reader_reset.argtypes = [ctypes.c_void_p]
    lib.mxio_reader_close.argtypes = [ctypes.c_void_p]
    lib.mxio_writer_open.restype = ctypes.c_void_p
    lib.mxio_writer_open.argtypes = [ctypes.c_char_p]
    lib.mxio_writer_write.argtypes = [ctypes.c_void_p,
                                      ctypes.POINTER(ctypes.c_uint8),
                                      ctypes.c_uint64]
    lib.mxio_writer_close.argtypes = [ctypes.c_void_p]
    lib.mxio_imgloader_create.restype = ctypes.c_void_p
    lib.mxio_imgloader_create.argtypes = [
        ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float),
        ctypes.c_int, ctypes.c_int, ctypes.c_uint64, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.POINTER(ctypes.c_int)]
    # aug transforms are newer symbols: bind optionally so a stale prebuilt
    # .so (no toolchain to rebuild) keeps its reader/writer/loader usable
    try:
        lib.mxio_aug_rotate.argtypes = [
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int, ctypes.c_int,
            ctypes.c_float, ctypes.c_int, ctypes.POINTER(ctypes.c_uint8)]
        lib.mxio_aug_hsl.argtypes = [
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int, ctypes.c_int]
        lib._mxtpu_has_aug = True
    except AttributeError:
        lib._mxtpu_has_aug = False
    try:
        lib.mxio_imgloader_create2.restype = ctypes.c_void_p
        lib.mxio_imgloader_create2.argtypes = \
            list(lib.mxio_imgloader_create.argtypes) + [ctypes.c_int]
        lib._mxtpu_has_label_width = True
    except AttributeError:
        lib._mxtpu_has_label_width = False
    try:
        lib.mxio_im2rec.restype = ctypes.c_int64
        lib.mxio_im2rec.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_int]
        lib._mxtpu_has_im2rec = True
    except AttributeError:
        lib._mxtpu_has_im2rec = False
    lib.mxio_imgloader_next.restype = ctypes.c_int
    lib.mxio_imgloader_next.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_float),
        ctypes.POINTER(ctypes.c_float)]
    lib.mxio_imgloader_reset.argtypes = [ctypes.c_void_p]
    lib.mxio_imgloader_destroy.argtypes = [ctypes.c_void_p]


def available() -> bool:
    return load() is not None


def aug_rotate(img: np.ndarray, angle: float, fill: int = 255) -> np.ndarray:
    """Native rotation transform on an (H, W, 3) uint8 RGB array (exported
    for golden tests vs image.rotate_image)."""
    lib = load()
    if lib is None or not getattr(lib, "_mxtpu_has_aug", False):
        raise RuntimeError("native io library unavailable (or too old "
                           "for aug transforms)")
    img = np.ascontiguousarray(img, np.uint8)
    h, w = img.shape[:2]
    out = np.empty_like(img)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    lib.mxio_aug_rotate(img.ctypes.data_as(u8p), w, h,
                        ctypes.c_float(angle), fill,
                        out.ctypes.data_as(u8p))
    return out


def aug_hsl(img: np.ndarray, dh: int, ds: int, dl: int) -> np.ndarray:
    """Native HLS-space jitter on an (H, W, 3) uint8 RGB array (exported
    for golden tests vs image.hsl_shift)."""
    lib = load()
    if lib is None or not getattr(lib, "_mxtpu_has_aug", False):
        raise RuntimeError("native io library unavailable (or too old "
                           "for aug transforms)")
    out = np.ascontiguousarray(img, np.uint8).copy()
    h, w = out.shape[:2]
    lib.mxio_aug_hsl(out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
                     w, h, dh, ds, dl)
    return out


def im2rec_pack(lst_path, root, rec_path, idx_path, resize=0, quality=95,
                nthreads=4):
    """Multithreaded .lst -> .rec/.idx packer (the reference's C++
    tools/im2rec.cc). Returns the number of records written. Ordered
    output: byte-identical regardless of thread count."""
    lib = load()
    if lib is None or not getattr(lib, "_mxtpu_has_im2rec", False):
        raise RuntimeError("native io library unavailable (or too old "
                           "for im2rec)")
    n = lib.mxio_im2rec(str(lst_path).encode(), str(root).encode(),
                        str(rec_path).encode(), str(idx_path).encode(),
                        int(resize), int(quality), int(nthreads))
    if n < 0:
        raise IOError("mxio_im2rec failed (unreadable .lst or unwritable "
                      "output paths)")
    return int(n)


class NativeRecordReader:
    """Sharded sequential reader over a .rec file (native)."""

    def __init__(self, path, part_index=0, num_parts=1):
        lib = load()
        if lib is None:
            raise RuntimeError("native io library unavailable")
        self._lib = lib
        self._h = lib.mxio_reader_open(path.encode(), part_index, num_parts)
        if not self._h:
            raise IOError("cannot open %s" % path)

    def read(self):
        data = ctypes.POINTER(ctypes.c_uint8)()
        length = ctypes.c_uint64()
        if not self._lib.mxio_reader_next(self._h, ctypes.byref(data),
                                          ctypes.byref(length)):
            return None
        return ctypes.string_at(data, length.value)

    def reset(self):
        self._lib.mxio_reader_reset(self._h)

    def close(self):
        if self._h:
            self._lib.mxio_reader_close(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class NativeImageLoader:
    """Threaded JPEG-decoding batch loader (native ImageRecordIOParser2
    analogue). Yields (data (N,C,H,W) float32, labels (N,), n_valid)."""

    def __init__(self, path, batch_size, data_shape, nthreads=4,
                 rand_crop=False, rand_mirror=False, mean_rgb=None,
                 std_rgb=None, part_index=0, num_parts=1, seed=0,
                 resize_shorter=0, queue_depth=2, shuffle_buffer=0,
                 max_rotate_angle=0, rotate=-1, fill_value=255,
                 random_h=0, random_s=0, random_l=0, label_width=1):
        lib = load()
        if lib is None:
            raise RuntimeError("native io library unavailable")
        self._lib = lib
        c, h, w = data_shape
        mean = (ctypes.c_float * 3)(*(mean_rgb or (0.0, 0.0, 0.0)))
        std = (ctypes.c_float * 3)(*(std_rgb or (1.0, 1.0, 1.0)))
        wants_aug = (max_rotate_angle > 0 or rotate > 0 or random_h
                     or random_s or random_l)
        if wants_aug and not getattr(lib, "_mxtpu_has_aug", False):
            # old prebuilt .so: it would silently drop these params — fall
            # back to the Python reader, which honors them
            raise RuntimeError("native io library too old for aug params")
        aug = (ctypes.c_int * 6)(int(max_rotate_angle), int(rotate),
                                 int(fill_value), int(random_h),
                                 int(random_s), int(random_l))
        self.batch_size = batch_size
        self.data_shape = data_shape
        self.label_width = int(label_width)
        self._data = np.empty((batch_size, c, h, w), np.float32)
        if self.label_width > 1:
            if not getattr(lib, "_mxtpu_has_label_width", False):
                # old prebuilt .so would silently read zeros for packed
                # labels — fall back to the Python reader, which honors it
                raise RuntimeError("native io library too old for "
                                   "label_width")
            self._labels = np.empty((batch_size, self.label_width),
                                    np.float32)
            self._h = lib.mxio_imgloader_create2(
                path.encode(), batch_size, h, w, c, nthreads,
                int(rand_crop), int(rand_mirror), mean, std,
                part_index, num_parts, seed, resize_shorter, queue_depth,
                shuffle_buffer, aug, self.label_width)
        else:
            self._labels = np.empty((batch_size,), np.float32)
            self._h = lib.mxio_imgloader_create(
                path.encode(), batch_size, h, w, c, nthreads,
                int(rand_crop), int(rand_mirror), mean, std,
                part_index, num_parts, seed, resize_shorter, queue_depth,
                shuffle_buffer, aug)
        if not self._h:
            raise IOError("cannot open %s" % path)

    def next_batch(self):
        n = self._lib.mxio_imgloader_next(
            self._h,
            self._data.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            self._labels.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
        if n == 0:
            return None
        return self._data, self._labels, n

    def reset(self):
        self._lib.mxio_imgloader_reset(self._h)

    def close(self):
        if self._h:
            self._lib.mxio_imgloader_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
