"""Runtime feature detection (parity: python/mxnet/runtime.py, src/libinfo.cc)."""
from __future__ import annotations

import os
import threading

import jax


class Feature:
    def __init__(self, name, enabled):
        self.name = name
        self.enabled = enabled

    def __repr__(self):
        return "✔ %s" % self.name if self.enabled else "✖ %s" % self.name


class Features(dict):
    """mx.runtime.Features() — build/runtime feature flags."""

    def __init__(self):
        from ._native import lib as _native_lib
        platforms = {d.platform for d in jax.devices()}
        feats = {
            "TPU": bool(platforms - {"cpu"}),
            "CPU": True,
            "NATIVE_RUNTIME": _native_lib() is not None,
            "XLA": True,
            "PALLAS": True,
            "BF16": True,
            "INT64_TENSOR_SIZE": True,
            "SIGNAL_HANDLER": False,
            "CUDA": False,
            "CUDNN": False,
            "ONEDNN": False,
            "TENSORRT": False,
            "OPENMP": False,
            "DIST_KVSTORE": True,
        }
        super().__init__({k: Feature(k, v) for k, v in feats.items()})

    def is_enabled(self, name):
        return self[name].enabled


def feature_list():
    return list(Features().values())


# ---------------------------------------------------------------------------
# persistent compile cache
# ---------------------------------------------------------------------------
#: the one in-checkout cache location (git-ignored; the path is part of
#: the cache key's environment, so it never moves)
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")

_CACHE = {"lock": threading.Lock(), "dir": None, "hits": 0, "misses": 0}


def _count_cache_event(event, **_kw):
    if event == "/jax/compilation_cache/cache_hits":
        _CACHE["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        _CACHE["misses"] += 1


def enable_compile_cache():
    """Turn on JAX's persistent compilation cache for this process and
    return its directory.  Process entry points call this (the replica
    main, ``chip_smoke.py``) before their first compile; importing the
    package never does.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set in code: JAX's own variables govern what is kept
    (by default only programs that took a second or more to compile;
    ``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS=0`` keeps them all).
    Where it is not, JAX is pointed at :data:`COMPILE_CACHE_DIR` and
    keeps every program, so a restarted replica's small per-bucket
    programs are reads too.  Either way hits and misses are counted for
    :func:`compile_cache_stats`."""
    with _CACHE["lock"]:
        if _CACHE["dir"] is None:
            path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
            if not path:
                path = COMPILE_CACHE_DIR
                jax.config.update("jax_compilation_cache_dir", path)
                jax.config.update(
                    "jax_persistent_cache_min_compile_time_secs", 0)
                jax.config.update(
                    "jax_persistent_cache_min_entry_size_bytes", -1)
            jax.monitoring.register_event_listener(_count_cache_event)
            _CACHE["dir"] = path
        return _CACHE["dir"]


def compile_cache_stats():
    """{dir, hits, misses} — dir is None until :func:`enable_compile_cache`
    ran; a miss is a compile that was written to the cache."""
    return {"dir": _CACHE["dir"], "hits": _CACHE["hits"],
            "misses": _CACHE["misses"]}
