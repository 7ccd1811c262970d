"""Native runtime components, loaded via ctypes (no pybind11 in this stack).

The compute path is JAX/XLA/Pallas; these are the host-runtime pieces the
reference implements in C++ (data_feed.cc parsing threads). The shared
object is never committed: it is built from ``fast_parser.cpp`` with g++ on
first use, and again whenever the source's hash differs from the one
recorded beside the ``.so`` (a copy of the tree does not keep mtimes, a hash
survives it). With no g++ on the host the pure-Python parser is used
(``available()`` is False); a build that was attempted and failed raises
with the compiler's stderr -- it is never swallowed into the slow path.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import shutil
import subprocess
import threading

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_LOCK = threading.Lock()
_LIB = None
_LIB_TRIED = False


class NativeBuildError(RuntimeError):
    """g++ was found and failed to build the native parser."""


def _build(src: str, so: str, stamp: str, digest: str) -> None:
    tmp = f"{so}.tmp.{os.getpid()}"
    try:
        proc = subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread",
             "-o", tmp, src],
            capture_output=True, text=True, timeout=300)
        if proc.returncode != 0:
            raise NativeBuildError(
                f"g++ failed to build {src} (exit {proc.returncode}):\n"
                f"{proc.stderr[-4000:]}")
        os.replace(tmp, so)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    with open(stamp, "w") as f:
        f.write(digest)


def _load():
    global _LIB, _LIB_TRIED
    with _LOCK:
        if _LIB_TRIED:
            return _LIB
        so = os.path.join(_DIR, "libfast_parser.so")
        src = os.path.join(_DIR, "fast_parser.cpp")
        stamp = so + ".srchash"
        with open(src, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()
        try:
            with open(stamp) as f:
                built_from = f.read().strip()
        except OSError:
            built_from = None
        if not os.path.exists(so) or built_from != digest:
            if shutil.which("g++") is None:
                _LIB_TRIED = True
                return None     # no toolchain: the Python parser serves
            _build(src, so, stamp, digest)
        lib = ctypes.CDLL(so)
        lib.parse_slot_file.restype = ctypes.c_int64
        lib.parse_slot_file.argtypes = [
            ctypes.c_char_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_float), ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int32]
        _LIB = lib
        _LIB_TRIED = True
        return _LIB


def available() -> bool:
    return _load() is not None


def parse_slot_file(path: str, n_slots: int, n_threads: int = 0):
    """Parse a rectangular slot-text file natively.

    Returns (rows: int, columns: list of float32 arrays [rows, width_s]) or
    None when the host has no g++ to build the library with (the caller
    then uses the Python parser).
    """
    lib = _load()
    if lib is None:
        return None
    fsize = os.path.getsize(path)
    # every float needs >=2 bytes of text ("0 "), so fsize/2 bounds the count
    cap = max(fsize // 2 + n_slots, 64)
    out = np.empty(cap, np.float32)
    widths = np.zeros(n_slots, np.int64)
    rows = lib.parse_slot_file(
        path.encode(), n_slots,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), cap,
        widths.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), n_threads)
    if rows < 0:
        raise ValueError(
            {-1: f"cannot open {path!r}",
             -2: f"{path!r}: ragged line (slots must be fixed-width, "
                 f"{n_slots} ';'-separated slots per line)",
             -3: f"{path!r}: parser buffer overflow",
             -4: f"{path!r}: malformed float"}.get(int(rows),
                                                   f"error {rows}"))
    stride = int(widths.sum())
    mat = out[:rows * stride].reshape(int(rows), stride)
    cols, off = [], 0
    for w in widths:
        cols.append(np.ascontiguousarray(mat[:, off:off + int(w)]))
        off += int(w)
    return int(rows), cols
