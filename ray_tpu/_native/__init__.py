"""Native (C++) runtime components, loaded via ctypes.

The hot object-plane path (capacity-managed shared-memory store with LRU
eviction, spilling, restore, and cross-process pinning) is C++
(cc/store.cc), mirroring the reference's native surface
(/root/reference/src/ray/object_manager/plasma/). The library is never
committed: it is compiled from cc/store.cc on first use with the system
toolchain into lib/ (ignored by git), under a name keyed by a hash of
the source, so a copied tree or an edited source rebuilds. A build that
fails raises — there is no silent pure-Python store.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

_CC_DIR = os.path.join(os.path.dirname(__file__), "cc")
_LIB_DIR = os.path.join(os.path.dirname(__file__), "lib")
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None


def _build(src: str, out: str) -> None:
    os.makedirs(_LIB_DIR, exist_ok=True)
    tmp = out + f".tmp.{os.getpid()}"
    cmd = ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-o", tmp, src]
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
    if r.returncode != 0:
        raise RuntimeError(f"ray_tpu native build failed:\n{r.stderr}")
    os.replace(tmp, out)    # atomic: concurrent builders race harmlessly


def store_lib() -> ctypes.CDLL:
    """The store library, built from cc/store.cc on first use. Raises if
    it cannot be built or loaded."""
    global _lib
    with _lock:
        if _lib is not None:
            return _lib
        src = os.path.join(_CC_DIR, "store.cc")
        with open(src, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:16]
        out = os.path.join(_LIB_DIR, f"libray_tpu_store-{digest}.so")
        if not os.path.exists(out):
            _build(src, out)
        lib = ctypes.CDLL(out)
        # signatures
        lib.rt_store_open.restype = ctypes.c_void_p
        lib.rt_store_open.argtypes = [ctypes.c_char_p, ctypes.c_uint64,
                                      ctypes.c_char_p]
        lib.rt_store_close.argtypes = [ctypes.c_void_p]
        lib.rt_store_put.restype = ctypes.c_int
        lib.rt_store_put.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                     ctypes.c_char_p, ctypes.c_uint64]
        lib.rt_store_create.restype = ctypes.c_int
        lib.rt_store_create.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                        ctypes.c_uint64]
        lib.rt_store_seal.restype = ctypes.c_int
        lib.rt_store_seal.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.rt_store_abort.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.rt_store_get.restype = ctypes.c_int
        lib.rt_store_get.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                     ctypes.POINTER(ctypes.c_uint64)]
        lib.rt_store_contains.restype = ctypes.c_int
        lib.rt_store_contains.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.rt_store_delete.restype = ctypes.c_int
        lib.rt_store_delete.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.rt_store_pin.restype = ctypes.c_int
        lib.rt_store_pin.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.rt_store_unpin.restype = ctypes.c_int
        lib.rt_store_unpin.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.rt_store_used_bytes.restype = ctypes.c_uint64
        lib.rt_store_used_bytes.argtypes = [ctypes.c_void_p]
        lib.rt_store_evict.restype = ctypes.c_uint64
        lib.rt_store_evict.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.rt_store_stats.argtypes = [ctypes.c_void_p] + \
            [ctypes.POINTER(ctypes.c_uint64)] * 4
        lib.rt_store_reserve.restype = ctypes.c_int
        lib.rt_store_reserve.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        _lib = lib
        return _lib
