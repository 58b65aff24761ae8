"""ctypes bindings for the native host runtime (libmxtpu_core.so).

The C++ core (src/mxtpu/) re-provides the reference's native runtime
pieces — dependency engine (reference src/engine/threaded_engine.cc),
pooled storage (src/storage/pooled_storage_manager.h), recordio
(dmlc-core recordio + python/mxnet/recordio.py), threaded prefetch
(src/io/iter_prefetcher.h) — behind a plain C ABI.  This module loads the
shared object (building it from ``src/`` on first use — ``mxnet_tpu/lib``
is not tracked by git, so every fresh checkout builds its own) and
exposes typed wrappers.  Every consumer has a pure-Python fallback so the
framework still works without a C++ toolchain; `lib() is None` is the
feature probe (surfaced via mx.runtime.Features 'NATIVE_RUNTIME'), and
`build_error` then holds what `make` said.
"""
from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading

_LIB = None
_TRIED = False
_LOCK = threading.Lock()
#: stderr of a failed `make -C src` (None: no build ran, or it succeeded)
build_error = None

# MXNET_TPU_CORE_SO points the loader at an alternate build (TSAN/ASAN);
# when set, the override is authoritative: no rebuild-on-stale either
_LIB_OVERRIDE = os.environ.get("MXNET_TPU_CORE_SO") or None
_LIB_PATH = os.path.abspath(_LIB_OVERRIDE) if _LIB_OVERRIDE else \
    os.path.join(os.path.dirname(__file__), "lib", "libmxtpu_core.so")
_SRC_DIR = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")

# callback: int fn(void* ctx, char* err_buf, int err_len, int skipped).
# err_buf is declared void* — with c_char_p ctypes would hand the callback an
# immutable bytes copy instead of the writable native buffer.  skipped=1 is a
# notify-only call (poisoned inputs): release resources, don't run the body.
ASYNC_FN = ctypes.CFUNCTYPE(ctypes.c_int, ctypes.c_void_p,
                            ctypes.c_void_p, ctypes.c_int, ctypes.c_int)


def _declare(lib):
    u64 = ctypes.c_uint64
    i64 = ctypes.c_int64
    p = ctypes.c_void_p
    lib.MXTEngineCreate.restype = p
    lib.MXTEngineCreate.argtypes = [ctypes.c_int, ctypes.c_int]
    lib.MXTEngineDestroy.argtypes = [p]
    lib.MXTEngineNewVar.restype = u64
    lib.MXTEngineNewVar.argtypes = [p]
    lib.MXTEngineDeleteVar.restype = ctypes.c_int
    lib.MXTEngineDeleteVar.argtypes = [p, u64]
    lib.MXTEnginePushAsync.restype = ctypes.c_int
    lib.MXTEnginePushAsync.argtypes = [p, ASYNC_FN, p,
                                       ctypes.POINTER(u64), ctypes.c_int,
                                       ctypes.POINTER(u64), ctypes.c_int,
                                       ctypes.c_int]
    lib.MXTEngineWaitForVar.restype = ctypes.c_int
    lib.MXTEngineWaitForVar.argtypes = [p, u64, ctypes.c_char_p, ctypes.c_int]
    lib.MXTEngineWaitForAll.argtypes = [p]
    lib.MXTEnginePendingCount.restype = ctypes.c_int
    lib.MXTEnginePendingCount.argtypes = [p]

    lib.MXTStorageCreate.restype = p
    lib.MXTStorageCreate.argtypes = [ctypes.c_int, u64, u64]
    lib.MXTStorageDestroy.argtypes = [p]
    lib.MXTStorageAlloc.restype = p
    lib.MXTStorageAlloc.argtypes = [p, u64]
    lib.MXTStorageFree.argtypes = [p, p]
    lib.MXTStorageDirectFree.argtypes = [p, p]
    lib.MXTStorageReleaseAll.argtypes = [p]
    lib.MXTStorageStats.argtypes = [p, ctypes.POINTER(u64)]

    lib.MXTRecordIOWriterCreate.restype = p
    lib.MXTRecordIOWriterCreate.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
    lib.MXTRecordIOWriterWrite.restype = ctypes.c_int
    lib.MXTRecordIOWriterWrite.argtypes = [p, ctypes.c_char_p, u64]
    lib.MXTRecordIOWriterTell.restype = i64
    lib.MXTRecordIOWriterTell.argtypes = [p]
    lib.MXTRecordIOWriterDestroy.argtypes = [p]
    lib.MXTRecordIOReaderCreate.restype = p
    lib.MXTRecordIOReaderCreate.argtypes = [ctypes.c_char_p]
    lib.MXTRecordIOReaderNext.restype = ctypes.c_int
    lib.MXTRecordIOReaderNext.argtypes = [p, ctypes.POINTER(ctypes.c_void_p),
                                          ctypes.POINTER(u64)]
    lib.MXTRecordIOReaderSeek.restype = ctypes.c_int
    lib.MXTRecordIOReaderSeek.argtypes = [p, i64]
    lib.MXTRecordIOReaderTell.restype = i64
    lib.MXTRecordIOReaderTell.argtypes = [p]
    lib.MXTRecordIOReaderDestroy.argtypes = [p]
    lib.MXTRecordIOFreeBuffer.argtypes = [ctypes.c_void_p]

    lib.MXTQueueCreate.restype = p
    lib.MXTQueueCreate.argtypes = [u64]
    lib.MXTQueueDestroy.argtypes = [p]
    lib.MXTQueuePush.restype = ctypes.c_int
    lib.MXTQueuePush.argtypes = [p, ctypes.c_char_p, u64]
    lib.MXTQueuePop.restype = ctypes.c_int
    lib.MXTQueuePop.argtypes = [p, ctypes.POINTER(ctypes.c_void_p),
                                ctypes.POINTER(u64)]
    lib.MXTQueueClose.argtypes = [p]
    lib.MXTQueueSize.restype = u64
    lib.MXTQueueSize.argtypes = [p]

    lib.MXTPrefetcherCreate.restype = p
    lib.MXTPrefetcherCreate.argtypes = [ctypes.c_char_p, u64,
                                        ctypes.POINTER(i64), u64]
    lib.MXTPrefetcherPop.restype = ctypes.c_int
    lib.MXTPrefetcherPop.argtypes = [p, ctypes.POINTER(ctypes.c_void_p),
                                     ctypes.POINTER(u64)]
    lib.MXTPrefetcherDestroy.argtypes = [p]

    i32 = ctypes.c_int
    lib.MXTImdecode.restype = i32
    lib.MXTImdecode.argtypes = [ctypes.c_char_p, u64, i32, i32,
                                ctypes.POINTER(i32), ctypes.POINTER(i32),
                                ctypes.POINTER(i32),
                                ctypes.POINTER(ctypes.c_void_p)]
    lib.MXTImresize.restype = i32
    lib.MXTImresize.argtypes = [ctypes.c_char_p, i32, i32, i32, i32, i32,
                                ctypes.c_char_p]
    lib.MXTImFreeBuffer.argtypes = [ctypes.c_void_p]
    return lib


def native_imdecode(payload, resize_short=0):
    """Decode a JPEG via the native decoder (GIL released during the C
    call).  Returns an HWC uint8 array, or None when the payload isn't a
    JPEG / the native lib is unavailable / decode failed."""
    L = lib()
    if L is None:
        return None
    import numpy as onp
    h = ctypes.c_int()
    w = ctypes.c_int()
    c = ctypes.c_int()
    out = ctypes.c_void_p()
    rc = L.MXTImdecode(payload, len(payload), 1, int(resize_short),
                       ctypes.byref(h), ctypes.byref(w), ctypes.byref(c),
                       ctypes.byref(out))
    if rc != 1:
        return None
    try:
        buf = ctypes.string_at(out, h.value * w.value * c.value)
    finally:
        L.MXTImFreeBuffer(out)
    arr = onp.frombuffer(buf, dtype=onp.uint8)
    return arr.reshape(h.value, w.value, c.value)


def _src_digest():
    """sha256 over the sources the .so is built from.  Staleness compares
    this with the digest recorded at build time: file mtimes say nothing
    in a fresh copy of the tree."""
    h = hashlib.sha256()
    mx_dir = os.path.join(_SRC_DIR, "mxtpu")
    names = [os.path.join(_SRC_DIR, "Makefile")] + sorted(
        os.path.join(mx_dir, n) for n in os.listdir(mx_dir)
        if n.endswith((".cc", ".h")))
    for path in names:
        h.update(os.path.basename(path).encode() + b"\0")
        with open(path, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _stale():
    """True when the built .so does not come from the current sources."""
    if not os.path.isdir(os.path.join(_SRC_DIR, "mxtpu")):
        return False          # no sources shipped: use what is there
    if not os.path.exists(_LIB_PATH):
        return True
    try:
        with open(_LIB_PATH + ".src") as f:
            return f.read().strip() != _src_digest()
    except OSError:
        return True


def _try_build():
    """`make -B -C src` under a file lock (replicas and test children of
    a fresh checkout all get here at once); on failure keep stderr in
    `build_error`, log it and leave the pure-Python fallbacks in charge."""
    global build_error
    import fcntl
    os.makedirs(os.path.dirname(_LIB_PATH), exist_ok=True)
    with open(_LIB_PATH + ".lock", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if not _stale():
            return True       # another process built it while we waited
        try:
            proc = subprocess.run(["make", "-B", "-C", _SRC_DIR],
                                  stdout=subprocess.DEVNULL,
                                  stderr=subprocess.PIPE, text=True,
                                  timeout=300)
            err = proc.stderr if proc.returncode else None
        except (OSError, subprocess.TimeoutExpired) as e:
            err = "%s: %s" % (type(e).__name__, e)
        if err is None and os.path.exists(_LIB_PATH):
            with open(_LIB_PATH + ".src", "w") as f:
                f.write(_src_digest())
            return True
    build_error = err or "make succeeded but wrote no %s" % _LIB_PATH
    logging.getLogger(__name__).warning(
        "native runtime build failed; using the pure-Python paths:\n%s",
        build_error[-2000:])
    return False


def lib():
    """The loaded native library, or None when unavailable."""
    global _LIB, _TRIED
    if _LIB is not None or _TRIED:
        return _LIB
    with _LOCK:
        if _LIB is not None or _TRIED:
            return _LIB
        _TRIED = True
        if os.environ.get("MXNET_TPU_DISABLE_NATIVE", "") == "1":
            return None
        if _LIB_OVERRIDE is None and _stale() and not _try_build():
            return None       # never load a .so of other sources
        if os.path.exists(_LIB_PATH):
            _LIB = _declare(ctypes.CDLL(_LIB_PATH))
        return _LIB


def read_buffer(ptr, size):
    """Copy a malloc'd native buffer into bytes and free it."""
    L = lib()
    data = ctypes.string_at(ptr, size)
    L.MXTRecordIOFreeBuffer(ctypes.cast(ptr, ctypes.c_void_p))
    return data
