"""Build the native IO engine shared library with g++.

No pybind11/setuptools machinery needed for a C-ABI .so; one compiler
invocation, cached next to the source under a name that carries a hash
of the source — so a binary built from other source (a stale one, or
one that travelled with a copy of the tree, where mtimes mean nothing)
is never loaded. Import-time use goes through ``load()`` which returns
None (pure-Python fallback) whenever a toolchain or binary is
unavailable — the framework never hard-requires the native engine.
"""

from __future__ import annotations

import hashlib
import os
import pathlib
import subprocess

_SRC = pathlib.Path(__file__).with_name("io_engine.cpp")
_LIB_STEM = "libtorrent_tpu_io"


def lib_path() -> pathlib.Path:
    """Where the binary built from the committed source lives."""
    digest = hashlib.sha256(_SRC.read_bytes()).hexdigest()[:16]
    return _SRC.with_name(f"{_LIB_STEM}.{digest}.so")


def build(force: bool = False) -> pathlib.Path | None:
    """Compile the engine if needed; returns the .so path or None."""
    if not _SRC.exists():
        return None
    lib = lib_path()
    if not force and lib.exists():
        return lib
    # compile beside the target and rename: a concurrent process never
    # loads a half-written binary
    tmp = lib.with_name(f"{lib.name}.{os.getpid()}.tmp")
    cmd = [
        os.environ.get("CXX", "g++"),
        "-O2",
        "-std=c++17",
        "-shared",
        "-fPIC",
        "-pthread",
        str(_SRC),
        "-o",
        str(tmp),
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, lib)
    except (OSError, subprocess.SubprocessError):
        tmp.unlink(missing_ok=True)
        return None
    for old in _SRC.parent.glob(f"{_LIB_STEM}*.so"):
        if old != lib:  # binaries of other source versions
            old.unlink(missing_ok=True)
    return lib


def load():
    """ctypes handle to the built engine, or None if unavailable."""
    import ctypes

    path = build()
    if path is None:
        return None
    try:
        lib = ctypes.CDLL(str(path))
    except OSError:
        # Foreign binary (other arch, older glibc): rebuild from
        # source once before giving up on the native engine.
        path = build(force=True)
        if path is None:
            return None
        try:
            lib = ctypes.CDLL(str(path))
        except OSError:
            return None
    lib.tt_io_create.restype = ctypes.c_void_p
    lib.tt_io_create.argtypes = [ctypes.c_int]
    lib.tt_io_destroy.restype = None
    lib.tt_io_destroy.argtypes = [ctypes.c_void_p]
    lib.tt_io_read_batch.restype = ctypes.c_int
    lib.tt_io_read_batch.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_char_p),
        ctypes.c_int32,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64,
        ctypes.POINTER(ctypes.c_uint8),
        ctypes.POINTER(ctypes.c_int32),
    ]
    lib.tt_rc4_init.restype = None
    lib.tt_rc4_init.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.c_int32,
    ]
    lib.tt_rc4_crypt.restype = None
    # buf is mutated in place (keystream xor), hence void* not char*
    lib.tt_rc4_crypt.argtypes = [
        ctypes.c_void_p,
        ctypes.c_void_p,
        ctypes.c_int64,
    ]
    return lib


if __name__ == "__main__":
    out = build(force=True)
    print(f"built: {out}" if out else "build failed")
