"""Native data-path library: build + ctypes binding.

The C++ sources in this directory (recordio.cc, image.cc, pipeline.cc)
implement the host-side IO hot loop — the TPU-native counterpart of the
reference's C++ data layer (dmlc-core RecordIO, src/io/iter_image_recordio_2.cc).
They are compiled once into ``libmxnative.so`` next to the sources (g++,
linked against the system libjpeg/libpng) and loaded via ctypes; everything
degrades gracefully to the pure-Python/cv2 path when the toolchain or the
image libraries are unavailable (``lib() is None``).

Set ``MXNET_USE_NATIVE_IO=0`` to force the Python path (config.py knob).
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading

_DIR = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_DIR, "libmxnative.so")
_SOURCES = ["recordio.cc", "image.cc", "pipeline.cc"]
_DEPS = _SOURCES + ["mxnative.h"]  # staleness check includes the header
# What this binding was written against: MXNATIVE_ABI of mxnative.h. A
# library that answers another number (or none) is rebuilt, never bound:
# the struct and the signatures below would not be its own.
_ABI = 2

_lock = threading.Lock()
_lib = None
_tried = False


class MXPipeConfig(ctypes.Structure):
    _fields_ = [
        ("batch_size", ctypes.c_int),
        ("target_h", ctypes.c_int),
        ("target_w", ctypes.c_int),
        ("target_c", ctypes.c_int),
        ("label_width", ctypes.c_int),
        ("resize", ctypes.c_int),
        ("rand_crop", ctypes.c_int),
        ("rand_mirror", ctypes.c_int),
        ("seed", ctypes.c_uint64),
        ("num_threads", ctypes.c_int),
        ("queue_depth", ctypes.c_int),
        ("round_batch", ctypes.c_int),
    ]


def _build(dirpath: str = _DIR, force: bool = False) -> bool:
    """Compile libmxnative.so if missing or older than sources/header
    (or whatever its age, with ``force``).

    Compiles to a process-unique temp path and renames into place so
    concurrent importers (multi-process data parallel, pytest workers)
    never observe a half-written .so.
    """
    so = os.path.join(dirpath, "libmxnative.so")
    deps = [os.path.join(dirpath, s) for s in _DEPS]
    if not force and os.path.exists(so) and all(
            os.path.getmtime(so) >= os.path.getmtime(s) for s in deps):
        return True
    tmp = "%s.%d.tmp" % (so, os.getpid())
    srcs = [os.path.join(dirpath, s) for s in _SOURCES]
    cmd = ["g++", "-std=c++17", "-O2", "-fPIC", "-shared", "-pthread",
           "-o", tmp] + srcs + ["-ljpeg", "-lpng"]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=180)
        os.replace(tmp, so)   # atomic on POSIX
        return True
    except Exception:
        try:
            os.remove(tmp)
        except OSError:
            pass
        return False


def _abi_of(handle: ctypes.CDLL):
    """The library's own ABI number; None from one built before it had
    any."""
    try:
        fn = handle.mxnative_abi
    except AttributeError:
        return None
    fn.restype = ctypes.c_int
    fn.argtypes = []
    return fn()


def _load(dirpath: str = _DIR):
    """Build if stale, open, and bind — or None. A library that is new
    enough by its mtime and yet speaks another ABI (a checkout moved
    under a built tree, a copied working tree) is unloaded, rebuilt from
    the sources beside it and opened again."""
    so = os.path.join(dirpath, "libmxnative.so")
    for force in (False, True):
        if not _build(dirpath, force=force):
            return None
        try:
            handle = ctypes.CDLL(so)
        except OSError:
            return None
        if _abi_of(handle) == _ABI:
            return _bind(handle)
        # dlopen finds an open library by its path: close this one, or
        # the rebuilt file would never be mapped
        import _ctypes
        _ctypes.dlclose(handle._handle)
    return None


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    u8p = ctypes.POINTER(ctypes.c_uint8)
    lib.mxrio_open.restype = ctypes.c_void_p
    lib.mxrio_open.argtypes = [ctypes.c_char_p]
    lib.mxrio_count.restype = ctypes.c_int64
    lib.mxrio_count.argtypes = [ctypes.c_void_p]
    lib.mxrio_offset.restype = ctypes.c_int64
    lib.mxrio_offset.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.mxrio_index_of.restype = ctypes.c_int64
    lib.mxrio_index_of.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.mxrio_get.restype = ctypes.c_int64
    lib.mxrio_get.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                              ctypes.POINTER(u8p)]
    lib.mxrio_close.argtypes = [ctypes.c_void_p]
    lib.mxrio_writer_open.restype = ctypes.c_void_p
    lib.mxrio_writer_open.argtypes = [ctypes.c_char_p]
    lib.mxrio_writer_write.restype = ctypes.c_int64
    lib.mxrio_writer_write.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                       ctypes.c_int64]
    lib.mxrio_writer_close.restype = ctypes.c_int
    lib.mxrio_writer_close.argtypes = [ctypes.c_void_p]

    lib.mximg_decode.restype = ctypes.c_int
    lib.mximg_decode.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                                 ctypes.c_int, ctypes.POINTER(u8p),
                                 ctypes.POINTER(ctypes.c_int),
                                 ctypes.POINTER(ctypes.c_int),
                                 ctypes.POINTER(ctypes.c_int)]
    lib.mximg_free.argtypes = [u8p]
    lib.mximg_resize.argtypes = [u8p, ctypes.c_int, ctypes.c_int,
                                 ctypes.c_int, u8p, ctypes.c_int,
                                 ctypes.c_int]

    lib.mxpipe_create.restype = ctypes.c_void_p
    lib.mxpipe_create.argtypes = [ctypes.c_void_p,
                                  ctypes.POINTER(MXPipeConfig)]
    lib.mxpipe_start_epoch.argtypes = [ctypes.c_void_p,
                                       ctypes.POINTER(ctypes.c_int64),
                                       ctypes.c_int64]
    lib.mxpipe_next.restype = ctypes.c_int
    lib.mxpipe_next.argtypes = [ctypes.c_void_p, u8p,
                                ctypes.POINTER(ctypes.c_float),
                                ctypes.POINTER(ctypes.c_int)]
    lib.mxpipe_error.restype = ctypes.c_char_p
    lib.mxpipe_error.argtypes = [ctypes.c_void_p]
    lib.mxpipe_close.argtypes = [ctypes.c_void_p]
    return lib


def lib():
    """The loaded native library, or None when unavailable/disabled."""
    global _lib, _tried
    if _tried:
        return _lib
    with _lock:
        if _tried:
            return _lib
        from .. import config as _config
        enabled = True
        try:
            enabled = bool(int(_config.get("MXNET_USE_NATIVE_IO")))
        except Exception:
            pass
        if enabled:
            _lib = _load()
        _tried = True
        return _lib
