"""Native (C++) components, loaded via ctypes.

Parity: the reference's C++ core (SURVEY.md §2.1). The library is built from
the sources in this directory on first use and named after their hash, so a
process only ever loads a binary made from the ``object_store.cc`` it sits
beside — git holds the source, never the binary. Every component has a
pure-Python fallback; ``status()`` says which one a run got and why.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import sys

_LIB = None
_STATUS = ""

_DIR = os.path.dirname(os.path.abspath(__file__))
_SOURCES = ("object_store.cc", "rt_store.h")


def _target() -> str:
    h = hashlib.sha256()
    for name in _SOURCES:
        with open(os.path.join(_DIR, name), "rb") as f:
            h.update(f.read())
    return f"libray_tpu_native-{h.hexdigest()[:12]}.so"


def status() -> str:
    """One line: which object store this process runs on, and why."""
    load_native()
    return _STATUS


def load_native():
    """Returns the loaded CDLL, or None when it cannot be built here (the
    reason goes to stderr once and into ``status()``)."""
    global _LIB, _STATUS
    if _STATUS:
        return _LIB
    target = _target()
    so = os.path.join(_DIR, target)
    built = ""
    if not os.path.exists(so):
        # make builds to a temp file and renames: concurrent first uses (one
        # per spawning worker) never dlopen a half-written library
        r = subprocess.run(
            ["make", "-s", f"TARGET={target}"],
            cwd=_DIR,
            capture_output=True,
            text=True,
            timeout=120,
        )
        if r.returncode != 0 or not os.path.exists(so):
            _STATUS = (
                "python store (native build failed: "
                f"{(r.stderr or r.stdout).strip()[-200:]!r})"
            )
            print(f"ray_tpu.native: {_STATUS}", file=sys.stderr)
            return None
        built = ", built here"
        for old in glob.glob(os.path.join(_DIR, "libray_tpu_native*.so")):
            if old != so:
                os.unlink(old)  # binaries of sources that are gone
    try:
        lib = ctypes.CDLL(so)
    except OSError as e:
        _STATUS = f"python store (native load failed: {e})"
        print(f"ray_tpu.native: {_STATUS}", file=sys.stderr)
        return None
    _STATUS = f"native store ({target} from {'+'.join(_SOURCES)}{built})"
    lib.rt_store_open.restype = ctypes.c_void_p
    lib.rt_store_open.argtypes = [
        ctypes.c_char_p,
        ctypes.c_uint64,
        ctypes.c_uint64,
        ctypes.c_int,
    ]
    lib.rt_store_close.argtypes = [ctypes.c_void_p]
    lib.rt_store_create.restype = ctypes.c_uint64
    lib.rt_store_create.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.c_uint64,
        ctypes.POINTER(ctypes.c_int),
    ]
    lib.rt_store_seal.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.rt_store_get.restype = ctypes.c_uint64
    lib.rt_store_get.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_uint64),
    ]
    lib.rt_store_contains.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.rt_store_release.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.rt_store_abort.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.rt_store_delete.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.rt_store_used_bytes.restype = ctypes.c_uint64
    lib.rt_store_used_bytes.argtypes = [ctypes.c_void_p]
    lib.rt_store_num_objects.restype = ctypes.c_uint64
    lib.rt_store_num_objects.argtypes = [ctypes.c_void_p]
    lib.rt_store_base.restype = ctypes.c_void_p
    lib.rt_store_base.argtypes = [ctypes.c_void_p]
    lib.rt_store_capacity.restype = ctypes.c_uint64
    lib.rt_store_capacity.argtypes = [ctypes.c_void_p]
    lib.rt_store_lru_victim.restype = ctypes.c_int
    lib.rt_store_lru_victim.argtypes = [ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint8)]
    lib.rt_store_prefault.restype = ctypes.c_uint64
    lib.rt_store_prefault.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    _LIB = lib
    return _LIB
