"""Native (C++) runtime components, loaded via ctypes.

The reference's hot runtime paths are C++ (plasma allocator, raylet);
here the allocator core is C++ too, compiled on demand with the
system toolchain into the checkout's cache directory
(_private/cache_dir.py). A binary is named after the CONTENT of the
source it was built from, so one built from another ``.cc`` is never
loaded. Everything has a pure Python fallback, so a missing compiler
degrades gracefully (first-fit semantics are identical and
parity-tested); ``build_status()`` says which one is in use.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from typing import Dict, Optional

from ray_tpu._private.cache_dir import checkout_cache_dir

logger = logging.getLogger(__name__)

_DIR = os.path.dirname(os.path.abspath(__file__))

_libs: dict = {}
_lib_lock = threading.Lock()
_load_failed: set = set()
# name -> "built" (compiled by this process) | "found" (a binary of this
# exact source was already in the cache) | "unavailable: <why>"
_status: Dict[str, str] = {}


def build_status() -> Dict[str, str]:
    """How each native library asked for so far was obtained."""
    with _lib_lock:
        return dict(_status)


def _build(name: str) -> Optional[str]:
    """g++ <name>.cc into the cache, unless a binary built from exactly
    this source is there already. Returns the .so path, or None."""
    src = os.path.join(_DIR, f"{name}.cc")
    try:
        with open(src, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:16]
        so = os.path.join(checkout_cache_dir("native"),
                          f"_{name}.{digest}.so")
        if os.path.exists(so):
            _status[name] = "found"
            return so
        # per-pid temp: concurrent builders (two drivers, parallel
        # pytest) must not install each other's half-written output
        tmp = f"{so}.{os.getpid()}.tmp"
        try:
            cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", src,
                   "-o", tmp]
            subprocess.run(cmd, check=True, capture_output=True,
                           timeout=120)
            os.replace(tmp, so)
            _status[name] = "built"
            return so
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
    except (OSError, subprocess.SubprocessError) as e:
        detail = ""
        stderr = getattr(e, "stderr", None)
        if stderr:
            detail = ": " + stderr.decode(errors="replace").strip()[:500]
        _status[name] = f"unavailable: {e}{detail}"
        logger.warning("native %s build failed (%s%s); using the "
                       "Python fallback", name, e, detail)
        return None


def load_native_lib(name: str) -> Optional[ctypes.CDLL]:
    """Build-and-load a _native component by name, or None (fallback)."""
    with _lib_lock:
        if name in _libs:
            return _libs[name]
        if name in _load_failed:
            return None
        so = _build(name)
        if so is None:
            _load_failed.add(name)
            return None
        try:
            lib = ctypes.CDLL(so)
        except OSError as e:
            _status[name] = f"unavailable: {e}"
            logger.warning("native %s load failed (%s)", name, e)
            _load_failed.add(name)
            return None
        _libs[name] = lib
        return lib


def load_exchange_lib() -> Optional[ctypes.CDLL]:
    """PRP shuffle kernels (exchange.cc), or None (numpy fallback)."""
    lib = load_native_lib("exchange")
    if lib is not None and not getattr(lib, "_sigs_set", False):
        u64, u32 = ctypes.c_uint64, ctypes.c_uint32
        vp = ctypes.c_void_p
        lib.prp_gather.argtypes = [vp, vp, u32, u64, u64, u64, vp]
        lib.prp_indices.argtypes = [vp, u64, u64, u64, vp]
        lib._sigs_set = True  # AFTER signatures: other threads race here
    return lib


def load_allocator_lib() -> Optional[ctypes.CDLL]:
    """The compiled allocator library, or None (fallback)."""
    lib = load_native_lib("allocator")
    if lib is None or getattr(lib, "_sigs_set", False):
        return lib
    lib.arena_create.restype = ctypes.c_void_p
    lib.arena_create.argtypes = [ctypes.c_uint64, ctypes.c_uint64]
    lib.arena_destroy.argtypes = [ctypes.c_void_p]
    lib.arena_alloc.restype = ctypes.c_int64
    lib.arena_alloc.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
    lib.arena_free.restype = ctypes.c_int
    lib.arena_free.argtypes = [ctypes.c_void_p, ctypes.c_uint64,
                               ctypes.c_uint64]
    lib.arena_free_bytes.restype = ctypes.c_uint64
    lib.arena_free_bytes.argtypes = [ctypes.c_void_p]
    lib.arena_num_holes.restype = ctypes.c_uint64
    lib.arena_num_holes.argtypes = [ctypes.c_void_p]
    lib._sigs_set = True  # AFTER signatures: other threads race here
    return lib


class NativeFreeList:
    """ctypes wrapper over the C++ arena allocator. Raises ImportError
    at construction if the native library is unavailable."""

    def __init__(self, size: int, align: int = 64):
        lib = load_allocator_lib()
        if lib is None:
            raise ImportError("native allocator unavailable")
        self._lib = lib
        self._handle = lib.arena_create(size, align)

    def allocate(self, nbytes: int) -> int:
        """Offset, or -1 when no hole fits."""
        return self._lib.arena_alloc(self._handle, nbytes)

    def free(self, offset: int, nbytes: int) -> None:
        rc = self._lib.arena_free(self._handle, offset, nbytes)
        if rc != 0:
            raise ValueError(
                f"invalid free: [{offset}, {offset + nbytes}) overlaps "
                "an existing hole (double free?)")

    def free_bytes(self) -> int:
        return self._lib.arena_free_bytes(self._handle)

    def num_holes(self) -> int:
        return self._lib.arena_num_holes(self._handle)

    def __del__(self):
        try:
            if getattr(self, "_handle", None):
                self._lib.arena_destroy(self._handle)
                self._handle = None
        except Exception:
            pass
