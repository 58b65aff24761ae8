"""Model registry: named, versioned, bucket-precompiled served models.

A served model is a batch function ``fn(batch_np) -> batch_np`` plus the
metadata the batcher needs (item shape/dtype, batch buckets).  Sources:

- a hybridized ``gluon.HybridBlock`` (the thread-safe CachedOp path —
  one XLA executable per signature, safe to drive from worker threads,
  see ``tests/test_threadsafe_inference.py``),
- an exported checkpoint pair (``SymbolBlock.imports``), or
- any plain callable (tests / custom pre-post-processing).

Batch bucketing: XLA compiles one program per input signature, so a
serving layer that dispatched every distinct batch size would compile
continuously under real traffic.  Instead each model declares a sorted
tuple of batch buckets (default: powers of two up to ``max_batch_size``);
the batcher pads a coalesced batch up to the smallest bucket that fits
and slices the padding back off the outputs.  ``warmup=True`` (default)
runs every bucket once at load time so no client request ever pays a
compile.

Hot swap: ``load()`` warms the new version BEFORE publishing it, then
flips the model's latest pointer atomically — in-flight and queued
requests resolve their version at dispatch time, so a swap never
interrupts traffic.
"""
from __future__ import annotations

import threading
import time

import numpy as onp

from .errors import BadRequestError, ModelNotFoundError

__all__ = ["ServedModel", "ModelRegistry", "default_buckets",
           "resolve_builder", "load_model_spec"]

def resolve_builder(path):
    """``"package.module:callable"`` → the callable.

    The fleet's model specs (replica boot, admin hot-load, rollout) name
    models by importable builder instead of shipping code: only a
    callable reachable on the server's own PYTHONPATH can ever run —
    the restricted-unpickler stance applied to model loading."""
    mod, _, fn = str(path).partition(":")
    if not mod or not fn:
        raise BadRequestError(
            "builder must be 'package.module:callable', got %r" % (path,))
    import importlib
    try:
        target = importlib.import_module(mod)
    except ImportError as e:
        raise BadRequestError("cannot import builder module %r: %s"
                              % (mod, e))
    for attr in fn.split("."):
        target = getattr(target, attr, None)
        if target is None:
            raise BadRequestError("builder %r has no attribute %r"
                                  % (path, attr))
    if not callable(target):
        raise BadRequestError("builder %r is not callable" % (path,))
    return target


def load_model_spec(registry, spec):
    """Load ONE model-spec dict into ``registry`` and return the
    ``ServedModel``.  Spec keys: ``name``, ``builder``
    ("module:callable"), optional ``kwargs`` (builder arguments),
    ``version``, ``item_shape``, ``dtype``, ``max_batch_size``,
    ``buckets``, ``warmup``.  Shared by the replica boot path, the admin
    hot-load endpoint, and ``fleet.rollout``."""
    builder = resolve_builder(spec["builder"])
    model = builder(**(spec.get("kwargs") or {}))
    return registry.load(
        spec["name"], model, version=spec.get("version"),
        item_shape=spec.get("item_shape"),
        dtype=spec.get("dtype", "float32"),
        max_batch_size=spec.get("max_batch_size", 32),
        buckets=spec.get("buckets"),
        warmup=spec.get("warmup", True))


def default_buckets(max_batch_size):
    """Powers of two up to (and always including) max_batch_size."""
    buckets = []
    b = 1
    while b < max_batch_size:
        buckets.append(b)
        b *= 2
    buckets.append(max_batch_size)
    return tuple(buckets)


def _block_batch_fn(block):
    """HybridBlock -> batch function over host arrays.

    The block's per-signature cached graphs make this thread-safe and
    recompile-free: each bucket shape traces once, every later call is a
    cache hit (reference: cached_op_threadsafe.cc semantics)."""
    def fn(batch_np):
        from .. import np as mxnp
        out = block(mxnp.array(batch_np))
        if isinstance(out, (list, tuple)):
            out = out[0]
        return out.asnumpy() if hasattr(out, "asnumpy") else onp.asarray(out)
    return fn


class ServedModel:
    """One (name, version) entry: batch fn + signature + buckets."""

    def __init__(self, name, fn, version=1, item_shape=None,
                 dtype="float32", max_batch_size=32, buckets=None):
        self.name = name
        self.version = int(version)
        self.fn = fn
        self.item_shape = tuple(item_shape) if item_shape is not None else None
        self.dtype = str(dtype)
        if buckets:
            self.buckets = tuple(sorted(set(int(b) for b in buckets)))
        else:
            self.buckets = default_buckets(int(max_batch_size))
        self.max_batch_size = self.buckets[-1]
        self.loaded_at = time.time()
        self.warmed = False

    # -- admission-side validation ---------------------------------------
    def check_item(self, item):
        """Validate/coerce ONE request item to (item_shape, dtype)."""
        arr = onp.asarray(item)
        try:
            arr = arr.astype(self.dtype, copy=False)
        except (TypeError, ValueError) as e:
            raise BadRequestError(
                "model %r expects dtype %s: %s" % (self.name, self.dtype, e))
        if self.item_shape is not None and tuple(arr.shape) != self.item_shape:
            raise BadRequestError(
                "model %r expects item shape %s, got %s"
                % (self.name, self.item_shape, tuple(arr.shape)))
        return arr

    # -- bucketing / execution -------------------------------------------
    def bucket_for(self, n):
        for b in self.buckets:
            if b >= n:
                return b
        return self.buckets[-1]

    def run_batch(self, batch_np):
        """Pad to the enclosing bucket, execute, slice padding back off.

        Returns ``(outputs, bucket, device_seconds)`` where outputs has
        the REAL batch size.  Padding rows are zeros — per-item
        independence is the serving contract (inference mode: no
        batch-coupled statistics)."""
        n = batch_np.shape[0]
        bucket = self.bucket_for(n)
        if bucket > n:
            pad = onp.zeros((bucket - n,) + batch_np.shape[1:],
                            dtype=batch_np.dtype)
            padded = onp.concatenate([batch_np, pad], axis=0)
        else:
            padded = batch_np
        t0 = time.perf_counter()
        out = self.fn(padded)
        dt = time.perf_counter() - t0
        return onp.asarray(out)[:n], bucket, dt

    def warmup(self):
        """Pre-compile every bucket (zeros input) so serving never pays a
        first-call trace/compile.  Requires item_shape."""
        if self.item_shape is None:
            return 0
        for b in self.buckets:
            self.fn(onp.zeros((b,) + self.item_shape, dtype=self.dtype))
        self.warmed = True
        return len(self.buckets)

    def describe(self):
        return {"name": self.name, "version": self.version,
                "item_shape": (list(self.item_shape)
                               if self.item_shape is not None else None),
                "dtype": self.dtype, "buckets": list(self.buckets),
                "max_batch_size": self.max_batch_size,
                "warmed": self.warmed, "loaded_at": self.loaded_at}


class ModelRegistry:
    """Thread-safe multi-model, multi-version registry."""

    def __init__(self):
        self._lock = threading.RLock()
        self._models = {}   # name -> {version: ServedModel}
        self._latest = {}   # name -> version

    def load(self, name, model, version=None, *, item_shape=None,
             dtype="float32", max_batch_size=32, buckets=None, warmup=True):
        """Register ``model`` (HybridBlock or ``fn(batch)->batch``) as
        ``name``/``version`` (default: current latest + 1) and return the
        ``ServedModel``.  With ``warmup`` the per-bucket compile happens
        here, before the version becomes routable (hot-swap safety)."""
        fn = model
        if not callable(model):
            raise TypeError("model must be a HybridBlock or callable, got %r"
                            % (type(model).__name__,))
        if hasattr(model, "collect_params"):  # gluon block
            if hasattr(model, "hybridize") and not getattr(
                    model, "_active", False):
                model.hybridize(active=True)
            fn = _block_batch_fn(model)
        with self._lock:
            if version is None:
                version = self._latest.get(name, 0) + 1
        served = ServedModel(name, fn, version=version, item_shape=item_shape,
                             dtype=dtype, max_batch_size=max_batch_size,
                             buckets=buckets)
        if warmup:
            served.warmup()  # compile outside the lock, before publishing
        with self._lock:
            self._models.setdefault(name, {})[served.version] = served
            if served.version >= self._latest.get(name, 0):
                self._latest[name] = served.version  # atomic traffic flip
        return served

    def load_checkpoint(self, name, symbol_file, param_file=None, **kwargs):
        """Register an exported artifact pair (``HybridBlock.export`` /
        ``Symbol.save`` output) via ``SymbolBlock.imports``."""
        from ..gluon.block import SymbolBlock
        blk = SymbolBlock.imports(symbol_file, param_file=param_file)
        return self.load(name, blk, **kwargs)

    def get(self, name, version=None):
        with self._lock:
            versions = self._models.get(name)
            if not versions:
                raise ModelNotFoundError("no model %r (have: %s)"
                                         % (name, sorted(self._models)))
            if version is None:
                version = self._latest[name]
            served = versions.get(int(version))
            if served is None:
                raise ModelNotFoundError(
                    "model %r has no version %s (have: %s)"
                    % (name, version, sorted(versions)))
            return served

    def latest_version(self, name):
        with self._lock:
            if name not in self._latest:
                raise ModelNotFoundError("no model %r" % (name,))
            return self._latest[name]

    def unload(self, name, version=None):
        """Remove one version (or the whole model when version=None)."""
        with self._lock:
            versions = self._models.get(name)
            if not versions:
                raise ModelNotFoundError("no model %r" % (name,))
            if version is None:
                del self._models[name]
                del self._latest[name]
                return
            if int(version) not in versions:
                raise ModelNotFoundError("model %r has no version %s"
                                         % (name, version))
            del versions[int(version)]
            if not versions:
                del self._models[name]
                del self._latest[name]
            elif self._latest[name] == int(version):
                self._latest[name] = max(versions)

    def models(self):
        """{name: {"latest": v, "versions": {v: describe()}}}"""
        with self._lock:
            return {
                name: {"latest": self._latest[name],
                       "versions": {v: m.describe()
                                    for v, m in versions.items()}}
                for name, versions in self._models.items()
            }

    def __contains__(self, name):
        with self._lock:
            return name in self._models
