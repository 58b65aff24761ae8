"""Bucketed, backward-overlapped gradient communication.

Design (PyTorch DDP gradient bucketing + Horovod tensor fusion, PAPERS.md;
reference analog: the engine's priority-ordered grad pushes overlapping
backward, python/mxnet/gluon/trainer.py:395-407 + kvstore/dist.py:620):
instead of one pushpull per parameter key (~160 for ResNet-50, ~200 for
BERT) issued serially AFTER backward, parameter gradients are packed —
grouped by dtype, in REVERSE registration order (the order backward
produces them) — into flat ~``MXNET_KV_BUCKET_KB`` buckets, and each
bucket's ONE fused pushpull launches the moment its last gradient is
final (autograd grad-ready completion hooks, autograd.py), overlapping
the remainder of the backward walk.  Before the optimizer reads
``p.grad()``, every gradient is transparently a view-unpack of its
bucket's reduced flat buffer.

Per-store lowering:

- ``device``/``tpu_ici`` (in-process): the pack → pushpull → unpack chain
  is recorded into the pending bulk segment (see the lazy-alias fast path
  in ``KVStore._write_out``), so the whole step keeps its single compiled
  program and the bucket reduce lowers to one fused XLA add/psum per
  bucket — hundreds of per-key collectives become ~a dozen.
- ``dist_*`` (parameter-server sockets): the bucket launch materializes
  the pack (a deliberate bulk-segment boundary) and hands ONE flat tensor
  per bucket to the engine-async push machinery — fewer, larger messages
  through the retry/seq transport; big buckets still slice across server
  shards under ``p3``.  Pulls drain in launch order at ``finish()``.
  Gradient compression, when configured, operates on the flat bucket
  (one residual per bucket) instead of per key.

Observability: every launch records ``comm.bucket.<dtype>`` into the
profiler's comm table (count, bytes, queue→launch latency), and
``GradBucketer.stats()`` reports buckets / launches / bytes / segment
boundaries per step (asserted by tests/test_bucketing.py).
"""
from __future__ import annotations

import time

import numpy as onp

import jax.numpy as jnp

from .. import config as _config
from .. import profiler
from .. import _bulk
from ..ndarray import apply_op, _wrap_value

__all__ = ["GradBucketer"]

_KEY_PREFIX = "__gbkt"


def _pack_flat(*gs):
    """Concatenate raveled gradients into one flat buffer (recorded as a
    single bulk op; XLA fuses it with the producing backward segment)."""
    if len(gs) == 1:
        return gs[0].reshape(-1)
    return jnp.concatenate([g.reshape(-1) for g in gs])


def _slice_view(flat, bounds, shape):
    """View one parameter's gradient back out of the reduced flat bucket.
    ``bounds``/``shape`` ride as constant args (tuples are never lifted to
    runtime inputs, so each (offset, size) gets its own cached segment
    slot — see _bulk._fn_key, which does not key defaults)."""
    return flat[bounds[0]:bounds[1]].reshape(shape)


class _Bucket:
    __slots__ = ("index", "key", "dtype", "entries", "size", "nbytes",
                 "ready", "launched", "flat_out", "first_ready_t",
                 "launch_t", "out_wrapper", "flat_sent")

    def __init__(self, index, dtype):
        self.index = index
        self.key = "%s%d" % (_KEY_PREFIX, index)
        self.dtype = dtype
        self.entries = []    # (param_idx, Parameter, offset, size, shape)
        self.size = 0        # total elements
        self.nbytes = 0
        self.ready = set()
        self.launched = False
        self.flat_out = None
        self.first_ready_t = None
        self.launch_t = None
        self.out_wrapper = None  # reused destination ndarray across steps
        self.flat_sent = None    # dist: the flat pack as pushed, kept for
        # the step so a MembershipChanged replay re-sends the SAME local
        # gradients (p.grad() may already view a stale reduced buffer)


class GradBucketer:
    """Packs gradients into fused-communication buckets for one Trainer.

    ``params``: list of ``(trainer_index, Parameter)`` in registration
    order; every parameter must be dense with ``grad_req != 'null'``.
    """

    def __init__(self, store, params, bucket_bytes=None):
        self._store = store
        if bucket_bytes is None:
            bucket_bytes = int(_config.get("MXNET_KV_BUCKET_KB")) * 1024
        self.bucket_bytes = max(1, int(bucket_bytes))
        self._dist = store.type.startswith("dist") or store.type == "p3"
        self.buckets = []
        self._bucket_of = {}  # param_idx -> _Bucket
        self._build_plan(params)
        self._finished = True  # first mark_ready() of a step resets
        self._retry = False    # replaying the step after MembershipChanged
        self._launch_order = []
        self._stats = {"steps": 0, "launches": 0, "bytes": 0,
                       "overlapped_launches": 0, "segment_boundaries": 0,
                       "relaunched_steps": 0}
        self._flush_listener = None

    # -- planning ---------------------------------------------------------
    def _build_plan(self, params):
        """Reverse registration order, grouped by dtype: backward finalizes
        gradients roughly from the last-registered (closest to the loss)
        parameters backwards, so bucket 0 fills — and launches — first."""
        open_buckets = {}  # dtype -> _Bucket
        for idx, p in reversed(list(params)):
            dt = onp.dtype(p.dtype)
            b = open_buckets.get(dt)
            if b is None:
                b = _Bucket(len(self.buckets), dt)
                self.buckets.append(b)
                open_buckets[dt] = b
            size = int(onp.prod(p.shape)) if p.shape else 1
            b.entries.append((idx, p, b.size, size, tuple(p.shape)))
            b.size += size
            b.nbytes += size * dt.itemsize
            self._bucket_of[idx] = b
            if b.nbytes >= self.bucket_bytes:
                del open_buckets[dt]  # bucket full; next grad opens a new one

    @property
    def num_buckets(self):
        return len(self.buckets)

    def collective_bound(self):
        """Upper bound on fused collectives per step the plan may issue:
        ceil(total_grad_bytes / bucket_bytes) + one partial tail per dtype
        (the bench assertion that catches a silent per-key fallback)."""
        total = sum(b.nbytes for b in self.buckets)
        ndtypes = len({b.dtype for b in self.buckets})
        return -(-total // self.bucket_bytes) + ndtypes

    # -- step lifecycle ---------------------------------------------------
    def _reset_step(self):
        for b in self.buckets:
            b.ready.clear()
            b.launched = False
            b.flat_out = None
            b.first_ready_t = None
            b.launch_t = None
            b.flat_sent = None
        self._launch_order = []
        self._finished = False
        self._retry = False
        self._stats["steps"] += 1
        if self._flush_listener is None:
            def _on_flush(_n_ops):
                self._stats["segment_boundaries"] += 1
            self._flush_listener = _bulk.add_flush_listener(_on_flush)

    def hook_for(self, idx):
        """Grad-ready callback for trainer parameter ``idx`` (registered
        by the Trainer via autograd.register_grad_ready_hook)."""
        def _ready(_arr):
            self.mark_ready(idx, overlapped=True)
        return _ready

    def mark_ready(self, idx, overlapped=False):
        """Note that param ``idx``'s gradient for this step is final;
        launches the bucket's fused pushpull once all members are ready."""
        if self._finished:
            self._reset_step()  # first grad of a new backward
        b = self._bucket_of.get(idx)
        if b is None or b.launched:
            return
        b.ready.add(idx)
        if b.first_ready_t is None:
            b.first_ready_t = time.perf_counter()
        if len(b.ready) == len(b.entries):
            self._launch(b, overlapped=overlapped)

    def finish(self):
        """Complete the step: launch any bucket whose members never all
        fired (partial backward, hooks not yet installed), drain dist
        pulls in launch order, and leave every ``p.grad()`` holding its
        unpacked view of the reduced bucket."""
        if self._finished:
            # no hook fired this step (first step before hook install, or
            # grads produced outside backward): treat finish() as the
            # whole step
            self._reset_step()
        for b in self.buckets:
            if not b.launched:
                self._launch(b, overlapped=False)
        if self._dist:
            for b in self._launch_order:
                self._pull_and_unpack(b)
        self._finished = True
        self._retry = False

    def abandon_step(self):
        """Reset launch state after a ``MembershipChanged`` so the next
        ``finish()`` replays this step under the new generation: buckets
        that already launched re-send their saved flat pack (their
        members' ``p.grad()`` may already view a reduced buffer from the
        rolled-back round), never-launched buckets pack fresh."""
        for b in self.buckets:
            b.launched = False
            b.ready.clear()
            b.flat_out = None
            b.launch_t = None
        self._launch_order = []
        self._finished = False
        self._retry = True
        self._stats["relaunched_steps"] += 1

    # -- launch / unpack --------------------------------------------------
    def _launch(self, b, overlapped=False):
        if self._retry and b.flat_sent is not None:
            flat = b.flat_sent  # replay the step's exact local gradients
        else:
            grads = [p.grad() for (_i, p, _o, _s, _sh) in b.entries]
            flat = apply_op(_pack_flat, *grads)
        now = time.perf_counter()
        b.launch_t = now
        queue_s = (now - b.first_ready_t) if b.first_ready_t else 0.0
        b.launched = True
        self._launch_order.append(b)
        self._stats["launches"] += 1
        self._stats["bytes"] += b.nbytes
        if overlapped:
            self._stats["overlapped_launches"] += 1
        profiler.record_comm_stat("comm.bucket.%s" % b.dtype.name,
                                  nbytes=b.nbytes, queue_s=queue_s)
        # bucket 0 holds the gradients that finish first — highest urgency
        priority = -b.index
        if self._dist:
            # engine-async: socket work overlaps the rest of backward.
            # Accessing the flat value inside push materializes the pending
            # segment — the intended bulk-segment boundary per bucket.
            b.flat_sent = flat  # kept for a MembershipChanged replay
            self._store.push(b.key, flat, priority=priority)
            b.flat_out = None  # pulled at finish(), in launch order
        else:
            out = _empty_like_flat(b)
            self._store.pushpull(b.key, flat, out=out, priority=priority)
            b.flat_out = out
            self._unpack(b)

    def _pull_and_unpack(self, b):
        out = _empty_like_flat(b)
        self._store.pull(b.key, out=out, priority=-b.index)
        b.flat_out = out
        self._unpack(b)

    def _unpack(self, b):
        """Repoint each param's existing grad ndarray at its slice of the
        reduced flat bucket.  Recorded lazily: for in-process stores the
        slices fuse into the same program as the optimizer update that
        consumes them."""
        flat_out = b.flat_out
        for (_i, p, off, size, shape) in b.entries:
            g = p.grad()
            piece = apply_op(_slice_view, flat_out, (off, off + size), shape)
            g._set_data(piece._buf)

    # -- observability ----------------------------------------------------
    def stats(self):
        s = dict(self._stats)
        s["num_buckets"] = self.num_buckets
        s["bucket_bytes"] = self.bucket_bytes
        s["collective_bound"] = self.collective_bound()
        if self._stats["steps"]:
            s["launches_per_step"] = (self._stats["launches"]
                                      / self._stats["steps"])
        return s

    def close(self):
        if self._flush_listener is not None:
            _bulk.remove_flush_listener(self._flush_listener)
            self._flush_listener = None


def _empty_like_flat(b):
    """Destination wrapper for a bucket's reduced flat buffer (allocated
    once per bucket and reused: the store replaces its buffer each step,
    so a fresh zeros allocation per step would be pure overhead)."""
    if b.out_wrapper is None:
        b.out_wrapper = _wrap_value(jnp.zeros((b.size,), b.dtype))
    return b.out_wrapper
