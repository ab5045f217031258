"""Loader for the native layer (libagentainer_native.so).

Builds on first use via ``make -C native`` (g++ is part of the baked
toolchain) and caches the result. A failed build degrades gracefully: callers
check ``available()`` and fall back to the pure-Python store / aiohttp proxy
when the library can't be built (e.g. no compiler on a user machine).

Processes that start together on a fresh check-out (a daemon beside its
engine hosts, the workers of a test run) all come through ``ensure_built``:
one of them builds under an exclusive lock and the others wait for it, and
the Makefile links to a temporary name and renames, so nobody ever opens a
library that is still being written.
"""

from __future__ import annotations

import ctypes
import fcntl
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

_REPO_ROOT = Path(__file__).resolve().parent.parent
_NATIVE_DIR = _REPO_ROOT / "native"

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_load_error: str | None = None
_load_s: float | None = None  # what the one real load() took, build included


def lib_path() -> Path:
    return _NATIVE_DIR / "build" / "libagentainer_native.so"


def loadgen_path() -> Path:
    return _NATIVE_DIR / "build" / "loadgen"


def _build() -> bool:
    try:
        proc = subprocess.run(
            ["make", "-C", str(_NATIVE_DIR)],
            capture_output=True,
            text=True,
            timeout=300,
        )
        if proc.returncode != 0:
            # a silent build failure used to downgrade every daemon to the
            # memory store with no trace — say WHY the native layer is gone
            tail = (proc.stderr or proc.stdout or "").strip().splitlines()[-8:]
            print(
                "[atpu-native] build failed (falling back to the Python "
                "store/data plane):\n  " + "\n  ".join(tail),
                file=sys.stderr,
            )
        return proc.returncode == 0 and lib_path().exists()
    except (OSError, subprocess.SubprocessError) as e:
        print(f"[atpu-native] build not attempted: {e}", file=sys.stderr)
        return False


def _bind(lib: ctypes.CDLL) -> None:
    c = ctypes
    lib.atpu_store_new.restype = c.c_void_p
    lib.atpu_store_new.argtypes = [c.c_char_p]
    lib.atpu_store_free.argtypes = [c.c_void_p]
    lib.atpu_free.argtypes = [c.c_void_p]
    lib.atpu_cmd.restype = c.c_int
    lib.atpu_cmd.argtypes = [
        c.c_void_p,
        c.c_char_p,
        c.c_size_t,
        c.POINTER(c.POINTER(c.c_uint8)),
        c.POINTER(c.c_size_t),
    ]
    lib.atpu_subscribe.restype = c.c_uint64
    lib.atpu_subscribe.argtypes = [c.c_void_p, c.c_char_p, c.c_size_t]
    lib.atpu_sub_poll.restype = c.c_int
    lib.atpu_sub_poll.argtypes = [
        c.c_void_p,
        c.c_uint64,
        c.c_int,
        c.POINTER(c.POINTER(c.c_uint8)),
        c.POINTER(c.c_size_t),
    ]
    lib.atpu_sub_close.argtypes = [c.c_void_p, c.c_uint64]
    lib.atpu_publish.restype = c.c_int
    lib.atpu_publish.argtypes = [c.c_void_p, c.c_char_p, c.c_char_p, c.c_size_t]
    lib.atpu_aof_flush.argtypes = [c.c_void_p]
    lib.atpu_dp_start.restype = c.c_void_p
    lib.atpu_dp_start.argtypes = [
        c.c_void_p,
        c.c_char_p,
        c.c_int,
        c.c_char_p,
        c.c_int,
        c.c_char_p,
    ]
    lib.atpu_dp_port.restype = c.c_int
    lib.atpu_dp_port.argtypes = [c.c_void_p]
    lib.atpu_dp_stop.argtypes = [c.c_void_p]
    lib.atpu_dp_route_set.argtypes = [
        c.c_void_p,
        c.c_char_p,
        c.c_char_p,
        c.c_int,
        c.c_char_p,
        c.c_int,
    ]
    lib.atpu_dp_route_del.argtypes = [c.c_void_p, c.c_char_p]
    lib.atpu_dp_counters_drain.argtypes = [
        c.c_void_p,
        c.c_char_p,
        c.POINTER(c.c_uint64),
        c.POINTER(c.c_double),
        c.POINTER(c.c_double),
    ]


def ensure_built() -> str | None:
    """Bring ``native/build/`` up to date with the sources: the library and
    ``loadgen``. Returns why it could not, or None. Between processes one
    caller builds and the rest wait on the lock, then find the work done."""
    build_dir = _NATIVE_DIR / "build"
    try:
        build_dir.mkdir(exist_ok=True)
        lock = open(build_dir / ".build.lock", "w")
    except OSError as e:
        # a check-out this process cannot write to: nothing is built here,
        # and a library someone else put there is loaded as it is
        return f"native build not attempted: {e}" if _stale() else None
    with lock:
        fcntl.flock(lock, fcntl.LOCK_EX)  # released when the file closes
        if _stale() and not _build():
            return "native build failed (make -C native)"
    return None


def _load_once() -> ctypes.CDLL | None:
    global _load_error
    _load_error = ensure_built()
    if _load_error is not None:
        return None
    try:
        lib = ctypes.CDLL(str(lib_path()))
        _bind(lib)
        return lib
    except OSError as e:
        _load_error = f"dlopen failed: {e}"
        return None


def load() -> ctypes.CDLL | None:
    """Load (building if needed) the native library; None if unavailable."""
    global _lib, _load_s
    with _lock:
        if _lib is None and _load_error is None:
            t0 = time.monotonic()
            _lib = _load_once()
            _load_s = time.monotonic() - t0
        return _lib


def _stale() -> bool:
    """Rebuild when a target is missing or any source is newer than the
    library."""
    try:
        if not loadgen_path().exists():
            return True
        lib_mtime = lib_path().stat().st_mtime
        return any(
            src.stat().st_mtime > lib_mtime
            for pattern in ("*.cc", "*.h")
            for src in _NATIVE_DIR.glob(pattern)
        )
    except OSError:
        return True


def available() -> bool:
    if os.environ.get("ATPU_DISABLE_NATIVE", "") == "1":
        return False
    return load() is not None


def load_error() -> str | None:
    return _load_error


def load_seconds() -> float | None:
    """Seconds this process spent building or loading the library (its
    first ``load()``, whatever came of it); None if it never tried."""
    return _load_s
