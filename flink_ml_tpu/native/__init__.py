"""ctypes loader for the native runtime library (native/src/*.cc).

Compiles the C++ sources with g++ on first use, cached as a .so under
native/build/ whose NAME carries a hash of the source bytes and compiler
flags — the environment bakes the toolchain but no prebuilt artifacts,
and a build left behind by another commit (native/build/ is ignored by
git, so it travels with a copied tree) can never be loaded for these
sources. Falls back to `available() == False` when no compiler is
present so pure-Python paths keep working.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
from typing import Optional

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_SRC_DIR = os.path.join(_REPO_ROOT, "native", "src")
_SOURCES = sorted(glob.glob(os.path.join(_SRC_DIR, "*.cc")))
_BUILD_DIR = os.path.join(_REPO_ROOT, "native", "build")
# -ffp-contract=off: the agglomerative kernel must reproduce the numpy
# merge log bit for bit; FMA contraction shifts distances by 1 ulp and
# reorders ties
_FLAGS = ["-O2", "-std=c++17", "-ffp-contract=off", "-shared", "-fPIC"]

_lib: Optional[ctypes.CDLL] = None
_load_error: Optional[str] = None


def _lib_path() -> str:
    """The .so for exactly these sources and flags."""
    digest = hashlib.sha256(" ".join(_FLAGS).encode())
    for src in _SOURCES:
        digest.update(os.path.basename(src).encode())
        with open(src, "rb") as f:
            digest.update(f.read())
    return os.path.join(
        _BUILD_DIR, f"libflinkmlnative-{digest.hexdigest()[:16]}.so"
    )


def _compile(lib_path: str) -> None:
    os.makedirs(_BUILD_DIR, exist_ok=True)
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", *_FLAGS, "-o", tmp, *_SOURCES], check=True, capture_output=True
        )
        os.replace(tmp, lib_path)  # atomic: a concurrent loader never sees a torn .so
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


def _declare(lib: ctypes.CDLL) -> None:
    """Declare every exported function's signature."""
    u64, p = ctypes.c_uint64, ctypes.c_void_p
    i32, long_ = ctypes.c_int32, ctypes.c_long
    lib.dc_create.restype = p
    lib.dc_create.argtypes = [u64, ctypes.c_char_p]
    lib.dc_destroy.argtypes = [p]
    lib.dc_append.restype = ctypes.c_long
    lib.dc_append.argtypes = [p, ctypes.c_void_p, u64]
    lib.dc_num_segments.restype = ctypes.c_long
    lib.dc_num_segments.argtypes = [p]
    lib.dc_segment_size.restype = u64
    lib.dc_segment_size.argtypes = [p, ctypes.c_long]
    lib.dc_read.restype = ctypes.c_int
    lib.dc_read.argtypes = [p, ctypes.c_long, ctypes.c_void_p]
    lib.dc_memory_used.restype = u64
    lib.dc_memory_used.argtypes = [p]
    lib.dc_spilled_segments.restype = ctypes.c_long
    lib.dc_spilled_segments.argtypes = [p]
    lib.dc_spilled_bytes.restype = u64
    lib.dc_spilled_bytes.argtypes = [p]
    lib.dc_parse_csv_doubles.restype = ctypes.c_long
    lib.dc_parse_csv_doubles.argtypes = [ctypes.c_char_p, u64, ctypes.c_void_p, u64]
    lib.fh_hash_categorical_doubles.restype = None
    lib.fh_hash_categorical_doubles.argtypes = [p, long_, p, long_, i32, p]
    lib.fh_hash_categorical_utf32.restype = None
    lib.fh_hash_categorical_utf32.argtypes = [p, long_, long_, p, long_, i32, p]
    lib.fh_combine.restype = None
    lib.fh_combine.argtypes = [p, p, long_, long_, p, p]
    lib.agg_cluster.restype = long_
    lib.agg_cluster.argtypes = [
        p, long_, ctypes.c_int, ctypes.c_double, ctypes.c_int, long_,
        ctypes.c_int, p, p,
    ]


def load() -> Optional[ctypes.CDLL]:
    global _lib, _load_error
    if _lib is not None or _load_error is not None:
        return _lib
    try:
        if not _SOURCES:
            raise OSError(f"no native sources under {_SRC_DIR}")
        lib_path = _lib_path()
        if not os.path.exists(lib_path):
            _compile(lib_path)
        lib = ctypes.CDLL(lib_path)
        _declare(lib)
        _lib = lib
    except (OSError, subprocess.CalledProcessError) as e:
        _load_error = str(e)
    return _lib


def available() -> bool:
    return load() is not None
