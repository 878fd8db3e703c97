"""Native (C++) runtime components, bound via ctypes.

The reference implements its IO/runtime layer in C++ (recordio at
``paddle/fluid/recordio/``, threaded readers under
``paddle/fluid/operators/reader/``); this package keeps that split: the
compute path is XLA, the data path is native code.  The shared library is
built on first use with g++ (no pybind11 in the image — flat C ABI +
ctypes) into this directory, under a name that carries a hash of its
source and compile command (git-ignored; never committed).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "recordio.cpp")

_lock = threading.Lock()
_lib = None
_build_error = None


def _built(src, stem, incs=(), libs=()):
    """Path of the shared library for ``src``, compiled on first use.
    The name carries a hash of the source and the compile command, so a
    binary left behind by an older source or another checkout is never
    loaded — once a tree has been copied a file's mtime proves nothing."""
    cmd = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", *incs, src]
    with open(src, "rb") as f:
        tag = hashlib.sha256(
            f.read() + " ".join(cmd + list(libs)).encode()).hexdigest()[:12]
    lib = os.path.join(_DIR, f"{stem}.{tag}.so")
    if not os.path.exists(lib):
        tmp = f"{lib[:-3]}.{os.getpid()}.tmp.so"  # atomic under xdist
        subprocess.run(cmd + ["-o", tmp, *libs], check=True,
                       capture_output=True)
        os.replace(tmp, lib)
    return lib


def load():
    """Build (if needed) and load the native library; returns None when a
    toolchain is unavailable (callers fall back to pure Python)."""
    global _lib, _build_error
    with _lock:
        if _lib is not None or _build_error is not None:
            return _lib
        try:
            lib = ctypes.CDLL(_built(_SRC, "libpaddletpu_native",
                                     libs=("-lz", "-lpthread")))
        except Exception as e:  # pragma: no cover - toolchain missing
            _build_error = e
            return None
        lib.recio_writer_open.restype = ctypes.c_void_p
        lib.recio_writer_open.argtypes = [ctypes.c_char_p, ctypes.c_uint32,
                                          ctypes.c_uint32]
        lib.recio_writer_write.restype = ctypes.c_int
        lib.recio_writer_write.argtypes = [ctypes.c_void_p,
                                           ctypes.c_char_p,
                                           ctypes.c_uint32]
        lib.recio_writer_close.restype = ctypes.c_int
        lib.recio_writer_close.argtypes = [ctypes.c_void_p]
        lib.recio_scanner_open.restype = ctypes.c_void_p
        lib.recio_scanner_open.argtypes = [ctypes.c_char_p]
        lib.recio_scanner_next.restype = ctypes.c_int
        lib.recio_scanner_next.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
            ctypes.POINTER(ctypes.c_uint32)]
        lib.recio_scanner_close.argtypes = [ctypes.c_void_p]
        lib.recio_loader_open.restype = ctypes.c_void_p
        lib.recio_loader_open.argtypes = [
            ctypes.POINTER(ctypes.c_char_p), ctypes.c_uint32,
            ctypes.c_uint32, ctypes.c_uint32]
        lib.recio_loader_next.restype = ctypes.c_int
        lib.recio_loader_next.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
            ctypes.POINTER(ctypes.c_uint32)]
        lib.recio_loader_close.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


# ---------------------------------------------------------------------------
# capi: the embeddable C inference ABI (capi.cpp) — built separately since
# it links against libpython.
# ---------------------------------------------------------------------------

_CAPI_SRC = os.path.join(_DIR, "capi.cpp")
_capi_lib = None
_capi_error = None


def _python_flags():
    import sysconfig
    inc = sysconfig.get_path("include")
    libdir = sysconfig.get_config_var("LIBDIR")
    ver = sysconfig.get_config_var("LDVERSION") or \
        sysconfig.get_config_var("VERSION")
    return [f"-I{inc}"], [f"-L{libdir}", f"-lpython{ver}"]


def load_capi():
    """Build (if needed) and load the C inference ABI; None if no
    toolchain."""
    global _capi_lib, _capi_error
    with _lock:
        if _capi_lib is not None or _capi_error is not None:
            return _capi_lib
        try:
            incs, libs = _python_flags()
            lib = ctypes.CDLL(_built(_CAPI_SRC, "libpaddletpu_capi",
                                     incs, libs),
                              mode=ctypes.RTLD_GLOBAL)
        except Exception as e:  # pragma: no cover - toolchain missing
            _capi_error = e
            return None
        lib.pd_tpu_init.restype = ctypes.c_int
        lib.pd_tpu_last_error.restype = ctypes.c_char_p
        lib.pd_tpu_create.restype = ctypes.c_void_p
        lib.pd_tpu_create.argtypes = [ctypes.c_char_p]
        lib.pd_tpu_num_feeds.restype = ctypes.c_int
        lib.pd_tpu_num_feeds.argtypes = [ctypes.c_void_p]
        lib.pd_tpu_feed_name.restype = ctypes.c_char_p
        lib.pd_tpu_feed_name.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.pd_tpu_run.restype = ctypes.c_void_p
        lib.pd_tpu_run.argtypes = [
            ctypes.c_void_p, ctypes.c_int,
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_longlong),
            ctypes.POINTER(ctypes.POINTER(ctypes.c_longlong)),
            ctypes.POINTER(ctypes.c_int),
            ctypes.POINTER(ctypes.c_char_p)]
        lib.pd_tpu_result_count.restype = ctypes.c_int
        lib.pd_tpu_result_count.argtypes = [ctypes.c_void_p]
        lib.pd_tpu_result_data.restype = ctypes.c_void_p
        lib.pd_tpu_result_data.argtypes = [
            ctypes.c_void_p, ctypes.c_int,
            ctypes.POINTER(ctypes.c_longlong)]
        lib.pd_tpu_result_rank.restype = ctypes.c_int
        lib.pd_tpu_result_rank.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.pd_tpu_result_dim.restype = ctypes.c_longlong
        lib.pd_tpu_result_dim.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                          ctypes.c_int]
        lib.pd_tpu_result_dtype.restype = ctypes.c_char_p
        lib.pd_tpu_result_dtype.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.pd_tpu_free_result.argtypes = [ctypes.c_void_p]
        lib.pd_tpu_destroy.argtypes = [ctypes.c_void_p]
        _capi_lib = lib
        return _capi_lib
