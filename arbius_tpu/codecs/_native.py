"""Loader for the native codec core (native/codecs.cc).

The library is built with g++ on first use into
`native/build/libarbius_codecs.<hash of codecs.cc>.so`, so a binary built
from another source (a stale build dir, a copied tree) can never load.
When the build or the load fails the reason is logged once and callers
fall back to the pure-Python reference implementation. Both paths
implement the same byte-exact spec, so the fallback changes speed, never
output — `deflate_impl()` says which one this process runs.
"""
from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading

log = logging.getLogger("arbius.codecs")

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_SRC = os.path.join(_REPO_ROOT, "native", "codecs.cc")
_BUILD_DIR = os.path.join(_REPO_ROOT, "native", "build")

_lock = threading.Lock()
_lib = None
_tried = False


def _build_and_load() -> ctypes.CDLL:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    so = os.path.join(_BUILD_DIR, f"libarbius_codecs.{digest}.so")
    if not os.path.exists(so):
        os.makedirs(_BUILD_DIR, exist_ok=True)
        tmp = so + f".tmp{os.getpid()}"
        # detlint: allow[CONC403] the lock EXISTS to serialize this
        # one-time native build — concurrent callers must block until
        # the .so is compiled, and the 120 s timeout bounds the stall
        subprocess.run(
            ["g++", "-O2", "-shared", "-fPIC", "-o", tmp, _SRC],
            check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)
    lib = ctypes.CDLL(so)
    lib.arbius_deflate_fixed.restype = ctypes.c_size_t
    lib.arbius_deflate_fixed.argtypes = [
        ctypes.c_char_p, ctypes.c_size_t,
        ctypes.POINTER(ctypes.c_uint8), ctypes.c_size_t]
    return lib


def _load() -> ctypes.CDLL | None:
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        try:
            _lib = _build_and_load()
        except subprocess.CalledProcessError as e:
            log.warning("native codec build failed (g++ rc=%s), using the "
                        "pure-Python deflate: %s", e.returncode,
                        e.stderr.decode(errors="replace").strip())
        except (OSError, subprocess.TimeoutExpired, AttributeError) as e:
            # no source / no g++ / unloadable or symbol-less library
            log.warning("native codec unavailable, using the pure-Python "
                        "deflate: %s: %s", type(e).__name__, e)
        return _lib


def deflate_impl() -> str:
    """"native" or "python": the deflate this process runs."""
    return "native" if _load() is not None else "python"


def deflate_fixed():
    """Return a bytes->bytes compressor backed by the .so, or None."""
    lib = _load()
    if lib is None:
        return None

    def fn(data: bytes) -> bytes:
        # worst case fixed-Huffman: 9 bits/literal + 3-bit header + EOB
        cap = len(data) + len(data) // 4 + 64
        out = (ctypes.c_uint8 * cap)()
        written = lib.arbius_deflate_fixed(data, len(data), out, cap)
        if written == 0 and data:
            raise RuntimeError("native deflate overflow (bug: cap too small)")
        return bytes(out[:written])

    return fn
