"""Lazy builder/loader for the native (C++) components.

The reference ships its native core as libmxnet.so built ahead of time
(SURVEY.md §1); here the native pieces are small and build on demand with
g++ (seconds), with pure-Python fallbacks when a toolchain is absent:

- ``io_lib()``  → ctypes handle to libmxtpu_io.so (RecordIO+JPEG batch
  decode pipeline — C++ counterpart of src/io/iter_image_recordio_2.cc).
- ``ps_server_binary()`` → path to mxtpu_ps_server (ps-lite analog).
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading

from .base import checkout_path

__all__ = ["io_lib", "ps_server_binary", "native_dir", "build"]

_lock = threading.Lock()
_cache: dict = {}


def native_dir() -> str:
    return checkout_path("native")


def _build_target(target: str) -> str | None:
    nd = native_dir()
    out = os.path.join(nd, "build", target)
    if os.environ.get("MXNET_NO_NATIVE_BUILD"):
        return out if os.path.exists(out) else None
    # make decides, every time: an existing binary may predate the source
    # beside it (a copied checkout carries build/ along), and an
    # up-to-date one costs make a stat
    try:
        subprocess.run(["make", "-C", nd, os.path.join("build", target)],
                       check=True, capture_output=True, timeout=120)
    except (subprocess.CalledProcessError, FileNotFoundError,
            subprocess.TimeoutExpired):
        return None
    return out if os.path.exists(out) else None


def build() -> bool:
    """Build everything; returns True if all targets exist."""
    return all(_build_target(t) for t in ("libmxtpu_io.so", "mxtpu_ps_server"))


def io_lib():
    """ctypes CDLL of the IO pipeline, or None if unavailable."""
    with _lock:
        if "io" not in _cache:
            path = _build_target("libmxtpu_io.so")
            lib = None
            if path:
                try:
                    lib = ctypes.CDLL(path)
                    lib.mxtpu_decode_batch.restype = ctypes.c_int
                    lib.mxtpu_decode_batch.argtypes = [
                        ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
                        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
                        ctypes.c_int, ctypes.c_int, ctypes.c_uint64,
                        ctypes.POINTER(ctypes.c_float),
                        ctypes.POINTER(ctypes.c_float),
                        ctypes.POINTER(ctypes.c_float),
                        ctypes.POINTER(ctypes.c_float), ctypes.c_int,
                        ctypes.c_int]
                    lib.mxtpu_decode_batch_u8.restype = ctypes.c_int
                    lib.mxtpu_decode_batch_u8.argtypes = [
                        ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
                        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
                        ctypes.c_int, ctypes.c_int, ctypes.c_uint64,
                        ctypes.POINTER(ctypes.c_uint8),
                        ctypes.POINTER(ctypes.c_float), ctypes.c_int,
                        ctypes.c_int]
                    lib.mxtpu_scan_offsets.restype = ctypes.c_int64
                    lib.mxtpu_scan_offsets.argtypes = [
                        ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
                        ctypes.c_int64]
                except (OSError, AttributeError):
                    # OSError: unloadable .so; AttributeError: stale build
                    # missing a newer symbol — fall back to the PIL path
                    lib = None
            _cache["io"] = lib
        return _cache["io"]


def ps_server_binary() -> str | None:
    with _lock:
        if "ps" not in _cache:
            _cache["ps"] = _build_target("mxtpu_ps_server")
        return _cache["ps"]
