"""ctypes bindings for the native C++ data-plane (dataplane.cpp).

Compiles the shared library on first use with g++ (cached next to the
source; rebuilt when it is absent or when the source's content hash differs
from the one recorded at build time — a copy of the tree does not preserve
mtimes, and the library itself is not committed). Every entry point
degrades to the pure-Python path when the toolchain is unavailable —
callers check ``available()`` or just get ``None`` from ``byte_pack_docs``;
``status()`` says which of the three happened.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import List, Optional

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "dataplane.cpp")
_LIB_PATH = os.path.join(_HERE, "_dataplane.so")
# Hash of the source the library was built from, written next to it.
_HASH_PATH = _LIB_PATH + ".src-sha256"

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False
_status = "not loaded"


def _src_hash() -> str:
    with open(_SRC, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def _built_from() -> Optional[str]:
    try:
        with open(_HASH_PATH) as f:
            return f.read().strip()
    except OSError:
        return None


def _build() -> bool:
    try:
        subprocess.run(
            ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-o", _LIB_PATH, _SRC],
            check=True, capture_output=True, timeout=120,
        )
    except (OSError, subprocess.SubprocessError):
        return False
    with open(_HASH_PATH, "w") as f:
        f.write(_src_hash())
    return True


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried, _status
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        _status = "unavailable (python packer)"
        stale = (not os.path.exists(_LIB_PATH)
                 or _built_from() != _src_hash())
        if stale and not _build():
            return None
        try:
            lib = ctypes.CDLL(_LIB_PATH)
        except OSError:
            return None
        _status = "built" if stale else "reused"
        u8p = ctypes.POINTER(ctypes.c_uint8)
        i64p = ctypes.POINTER(ctypes.c_int64)
        i32p = ctypes.POINTER(ctypes.c_int32)
        i64 = ctypes.c_int64
        i32 = ctypes.c_int32
        lib.byte_pack_count.argtypes = [u8p, i64p, i64, i32, i64, i64, i64]
        lib.byte_pack_count.restype = i64
        lib.byte_pack_fill.argtypes = [u8p, i64p, i64, i32, i64, i64, i64,
                                       i32, i32, i32, i32p, i64]
        lib.byte_pack_fill.restype = i64
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def status() -> str:
    """``built`` (compiled from dataplane.cpp by this process), ``reused``
    (a library built from this exact source was already there) or
    ``unavailable (python packer)`` (no toolchain: callers pack in Python)."""
    _load()
    return _status


def byte_pack_docs(
    texts: List[str],
    normal_vocab: int,
    bos: int,
    eos: int,
    pad: int,
    row_len: int,
    overlap: int = 0,
    max_doc_tokens: int = 10**9,
) -> Optional[np.ndarray]:
    """Byte-tokenize + chunk + pack documents into ``[N, row_len]`` int32
    rows. Returns None when the native library is unavailable (callers fall
    back to the Python path in data/memory.py)."""
    lib = _load()
    if lib is None:
        return None
    blobs = [t.encode("utf-8") for t in texts]
    data = b"".join(blobs)
    offsets = np.zeros(len(blobs) + 1, np.int64)
    np.cumsum([len(b) for b in blobs], out=offsets[1:])
    buf = np.frombuffer(data, np.uint8) if data else np.zeros(0, np.uint8)
    buf = np.ascontiguousarray(buf)

    u8p = buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
    offp = offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))
    n_tokens = lib.byte_pack_count(
        u8p, offp, len(blobs), normal_vocab, max_doc_tokens, row_len, overlap)
    n_rows = (n_tokens + row_len - 1) // row_len
    out = np.empty(max(n_rows, 0) * row_len, np.int32)
    written = lib.byte_pack_fill(
        u8p, offp, len(blobs), normal_vocab, max_doc_tokens, row_len, overlap,
        bos, eos, pad,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), out.size)
    if written < 0:
        return None  # capacity mismatch — should not happen; fall back
    return out[:written].reshape(-1, row_len)
