"""ctypes binding to the native analysis library (native/analysis.cpp).

The build environment has no pybind11; the C ABI + ctypes keeps the
Python↔C++ boundary dependency-free. `make -C native` runs at first use —
a no-op when the library is newer than analysis.cpp, a rebuild when it is
not — so a checkout that never built it gets it and a stale binary is never
trusted. With no compiler (or a failed build or load) the pure-Python
tokenizer serves: the native path is a strict accelerator, never a
requirement, and `native_available()` (on `_nodes/stats` as
`analysis.native_tokenizer`) says which one is live.

ASCII-only fast path: the C++ tokenizer matches the Python regex exactly
for ASCII text; any input with a byte >= 0x80 routes to Python so behavior
never diverges (see native/analysis.cpp header).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import List, Optional, Tuple

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "build", "libosttpu.so")

_lib = None
_lib_lock = threading.Lock()
_load_attempted = False


def _build_and_load() -> Optional[ctypes.CDLL]:
    try:
        subprocess.run(["make", "-C", _NATIVE_DIR], check=True,
                       capture_output=True, timeout=120)
    except (subprocess.SubprocessError, OSError):
        return None
    try:
        lib = ctypes.CDLL(_LIB_PATH)
    except OSError:
        return None
    lib.ost_tokenize_standard.restype = ctypes.c_void_p
    lib.ost_tokenize_standard.argtypes = [
        ctypes.c_char_p, ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
        ctypes.POINTER(ctypes.c_int32)]
    lib.ost_tokenize_batch.restype = ctypes.c_void_p
    lib.ost_tokenize_batch.argtypes = [
        ctypes.c_char_p, ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
        ctypes.POINTER(ctypes.c_int32)]
    lib.ost_free.restype = None
    lib.ost_free.argtypes = [ctypes.c_void_p]
    return lib


def get_lib() -> Optional[ctypes.CDLL]:
    global _lib, _load_attempted
    if _lib is not None or _load_attempted:
        return _lib
    with _lib_lock:
        if not _load_attempted:
            _lib = _build_and_load()
            _load_attempted = True
    return _lib


def native_available() -> bool:
    return get_lib() is not None


def tokenize_standard_ascii(text: str, max_token_length: int = 255,
                            lowercase: bool = False
                            ) -> Optional[List[Tuple[str, int]]]:
    """Native tokenize for ASCII text; None = use the Python fallback."""
    lib = get_lib()
    if lib is None:
        return None
    try:
        raw = text.encode("ascii")
    except UnicodeEncodeError:
        return None  # non-ASCII: Python regex keeps exact Unicode semantics
    n = ctypes.c_int32(0)
    ptr = lib.ost_tokenize_standard(raw, len(raw), max_token_length,
                                    1 if lowercase else 0,
                                    ctypes.byref(n))
    if not ptr:
        return None
    try:
        buf = ctypes.string_at(ptr)
    finally:
        lib.ost_free(ptr)
    if n.value == 0:
        return []
    out = []
    for line in buf.decode("ascii").split("\n"):
        tok, _, pos = line.rpartition("\t")
        out.append((tok, int(pos)))
    return out
