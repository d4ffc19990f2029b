"""ctypes loader for the native host library (``psnative.cc``).

The library is always built on the machine that runs it: its file name
carries a digest of the source, the build flags and this host's CPU, so
a checkout copied from another machine (or an edited source) never
loads a stale or foreign ``.so`` — it builds its own with ``make``, once,
on first use. A build that fails raises :class:`NativeBuildError` with
the compiler's output; nothing quietly falls back to NumPy. (The
``native() is None`` branches at the call sites are the NumPy references
the parity tests reach by swapping :func:`native` out.) This mirrors the
reference's split: C++ for the host data plane, accelerator code
elsewhere.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess
import threading
from typing import Optional

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "psnative.cc")
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None  # guarded-by: _lock


class NativeBuildError(RuntimeError):
    """``make`` could not produce the native library; carries its output."""


def _host_tag() -> str:
    """What ``-march=native`` resolves against: the machine type and the
    CPU's feature flags (Linux; empty elsewhere, where the machine type
    alone keys the build)."""
    flags = ""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    flags = line.split(":", 1)[1].strip()
                    break
    except OSError:
        pass
    return f"{platform.machine()} {flags}"


def lib_path() -> str:
    """Where this host's build of the current source lives."""
    h = hashlib.sha256()
    for path in (_SRC, os.path.join(_DIR, "Makefile")):
        with open(path, "rb") as f:
            h.update(f.read())
    h.update(_host_tag().encode())
    return os.path.join(_DIR, f"libpsnative-{h.hexdigest()[:16]}.so")


def _configure(lib: ctypes.CDLL) -> ctypes.CDLL:
    u8p = ctypes.POINTER(ctypes.c_uint8)
    u64p = ctypes.POINTER(ctypes.c_uint64)
    i64p = ctypes.POINTER(ctypes.c_int64)
    f32p = ctypes.POINTER(ctypes.c_float)
    lib.ps_crc32c.argtypes = [u8p, ctypes.c_uint64]
    lib.ps_crc32c.restype = ctypes.c_uint32
    lib.ps_mix64.argtypes = [ctypes.c_uint64, ctypes.c_uint64]
    lib.ps_mix64.restype = ctypes.c_uint64
    lib.ps_mix64_array.argtypes = [u64p, ctypes.c_uint64, ctypes.c_uint64, u64p]
    lib.ps_mix64_array.restype = None
    i32p = ctypes.POINTER(ctypes.c_int32)
    lib.ps_hash_slots.argtypes = [
        u64p, ctypes.c_uint64, ctypes.c_uint64, ctypes.c_uint64, i32p,
    ]
    lib.ps_hash_slots.restype = None
    lib.ps_pack_bits.argtypes = [i32p, ctypes.c_uint64, ctypes.c_uint32, u8p]
    lib.ps_pack_bits.restype = None
    lib.ps_hash_slots_packbits.argtypes = [
        u64p, ctypes.c_uint64, ctypes.c_uint64, ctypes.c_uint64,
        ctypes.c_uint32, u8p,
    ]
    lib.ps_hash_slots_packbits.restype = None
    lib.ps_stream_encode.argtypes = [
        u64p, ctypes.c_int64, ctypes.c_int32,      # keys, nsub, lanes
        ctypes.c_uint64, ctypes.c_uint64,          # seed, num_slots
        u8p, ctypes.c_uint32, ctypes.c_uint32,     # dict_mask, raw/code bits
        ctypes.c_int32,                            # dict_pad
        i32p, u8p, u8p, u8p,                       # lane_starts + 3 streams
    ]
    lib.ps_stream_encode.restype = ctypes.c_int64
    lib.ps_lz_max_compressed.argtypes = [ctypes.c_uint64]
    lib.ps_lz_max_compressed.restype = ctypes.c_uint64
    lib.ps_lz_compress.argtypes = [u8p, ctypes.c_uint64, u8p, ctypes.c_uint64]
    lib.ps_lz_compress.restype = ctypes.c_int64
    lib.ps_lz_decompress.argtypes = [u8p, ctypes.c_uint64, u8p, ctypes.c_uint64]
    lib.ps_lz_decompress.restype = ctypes.c_int64
    lib.ps_murmur3_x64_128.argtypes = [
        ctypes.c_char_p, ctypes.c_uint64, ctypes.c_uint32, u64p,
    ]
    lib.ps_murmur3_x64_128.restype = None
    for name in ("ps_parse_libsvm", "ps_parse_criteo"):
        fn = getattr(lib, name)
        fn.argtypes = [
            ctypes.c_char_p, ctypes.c_int64,
            f32p, i64p, u64p, f32p, i32p,
            ctypes.c_int64, ctypes.c_int64, i64p,
        ]
        fn.restype = ctypes.c_int64
    return lib


def native() -> ctypes.CDLL:
    """The loaded native library, built from ``psnative.cc`` first if
    this host has no build of the current source yet."""
    global _lib
    with _lock:
        if _lib is None:
            path = lib_path()
            if not os.path.exists(path):
                _build(path)
            _lib = _configure(ctypes.CDLL(path))
        return _lib


def _build(path: str) -> None:
    # built under a temporary name and renamed, so a concurrent process
    # never loads a half-written library
    tmp = f"{path}.{os.getpid()}.tmp"
    cmd = ["make", "-C", _DIR, f"LIB={os.path.basename(tmp)}"]
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=300
        )
    except (OSError, subprocess.TimeoutExpired) as e:
        raise NativeBuildError(f"could not run {' '.join(cmd)}: {e}") from e
    if proc.returncode != 0:
        raise NativeBuildError(
            f"{' '.join(cmd)} exited {proc.returncode}:\n"
            f"{proc.stdout}{proc.stderr}"
        )
    os.replace(tmp, path)
