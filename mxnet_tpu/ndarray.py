"""ndarray: the imperative array type over XLA/PJRT buffers.

Parity: reference `include/mxnet/ndarray.h:82` (NDArray = Chunk{storage,
engine-var} + shape/dtype) and `python/mxnet/numpy/multiarray.py` (ndarray).

TPU-native design: an ndarray owns a `jax.Array` (a PJRT buffer future).
JAX/PJRT already provides the async-dispatch contract the reference builds
with its threaded engine (`src/engine/threaded_engine.cc`): every op returns
immediately with a buffer future, ordering is per-device program order, and
`wait_to_read()`/`asnumpy()` are the sync points.  The host-side "engine" is
therefore thin (see engine.py); `MXNET_ENGINE_TYPE=NaiveEngine` degrades to
synchronous execution for debugging, matching `src/engine/naive_engine.cc`.

Every operator goes through `apply_op`, the equivalent of
`Imperative::Invoke` (src/imperative/imperative.cc:98): it unwraps inputs,
runs the jnp/lax computation (XLA-compiled + cached per shape/dtype by JAX),
and — when autograd is recording — captures a VJP closure on the tape
(RecordOp analog).
"""
from __future__ import annotations

import math
import os
import threading
import time

import numpy as onp

import jax
import jax.numpy as jnp

from . import autograd
from . import _bulk
from .autograd import TapeNode
from .context import Context, current_context

__all__ = ["ndarray", "NDArray", "apply_op", "from_numpy", "waitall"]

# --------------------------------------------------------------------------
# engine shims: NaiveEngine mode + waitall tracking
# --------------------------------------------------------------------------
from .config import get as _cfg_get  # typed MXNET_* registry
from .profiler import _AGG as _profiler_agg  # per-op aggregate stats flag

_NAIVE = _cfg_get("MXNET_ENGINE_TYPE") == "NaiveEngine"
_PENDING = []  # ALL in-flight buffers, for waitall() completeness
_PENDING_LOCK = threading.Lock()
_PENDING_PRUNE_AT = 256  # amortized prune threshold (keeps memory bounded)
_DRAINING = []  # retired batches being drained outside the lock
_DEFERRED_ERRORS = []  # async failures observed during pruning


def _drain_retired(old):
    """Observe a retired batch of buffers complete (their references would
    otherwise pin memory); completed-with-error buffers stash their
    exception for the next waitall().

    One batched block_until_ready instead of per-buffer is_ready() probes:
    every per-buffer probe is a runtime call, which made tracking O(n)
    calls per append past the threshold.
    Runs on the dedicated drainer THREAD, never the dispatching thread: an
    imperative ResNet-50 step tracks ~300 buffers, so the prune threshold
    trips mid-step and a synchronous block here would serialize the host
    pipeline against device compute (measured 3.7s of a 4.9s 5-step window
    before the drain moved off-thread).  The batch stays visible in
    _DRAINING while being drained, so a concurrent waitall() still
    observes (and blocks on) it — no in-flight failure slips past."""
    errors = []
    try:
        jax.block_until_ready(old)
    except Exception:
        # collect EVERY failed buffer's error individually (rare path)
        for buf in old:
            try:
                jax.block_until_ready(buf)
            except Exception as e:
                errors.append(e)
    with _PENDING_LOCK:
        # remove by IDENTITY: list.remove compares with ==, and two
        # same-length batches of jax arrays elementwise-compare into
        # an ambiguous-truth array (TypeError) while holding the lock
        still_ours = False
        for i, b in enumerate(_DRAINING):
            if b is old:
                del _DRAINING[i]
                still_ours = True
                break
        # stash failures ONLY if the batch was still ours: a concurrent
        # waitall() that already claimed it has raised (or will raise)
        # these same errors to the user — double-stashing would make a
        # later unrelated waitall() re-raise a stale error
        if still_ours:
            _DEFERRED_ERRORS.extend(errors)


_DRAIN_QUEUE = None  # lazily-created SimpleQueue feeding the drainer thread
_DRAIN_THREAD = None
_DRAIN_OUTSTANDING = 0  # queued + in-flight batches, guarded by _PENDING_LOCK
_DRAIN_SHUTDOWN = False  # barrier ran: never spawn another worker


def _drain_worker():
    global _DRAIN_OUTSTANDING
    while True:
        old = _DRAIN_QUEUE.get()
        if old is None:  # shutdown sentinel from the atexit barrier
            return
        try:
            _drain_retired(old)
        finally:
            with _PENDING_LOCK:
                _DRAIN_OUTSTANDING -= 1


def _enqueue_drain(old):
    global _DRAIN_QUEUE, _DRAIN_THREAD, _DRAIN_OUTSTANDING
    with _PENDING_LOCK:
        if _DRAIN_SHUTDOWN:
            # post-barrier (late atexit handlers doing array work): never
            # respawn a worker that would be parked in a C-level wait at
            # teardown; dropping the batch is fine — the process is exiting
            return
        # create queue+thread under the lock: two dispatch threads racing
        # here could otherwise mint two queues, stranding batches put on
        # the overwritten one
        if _DRAIN_THREAD is None or not _DRAIN_THREAD.is_alive():
            import queue
            if _DRAIN_QUEUE is None:
                _DRAIN_QUEUE = queue.SimpleQueue()
            t = threading.Thread(target=_drain_worker, daemon=True,
                                 name="mxtpu-drainer")
            t.start()
            _DRAIN_THREAD = t
        _DRAIN_OUTSTANDING += 1
    _DRAIN_QUEUE.put(old)


def _drain_shutdown_barrier():
    """Interpreter-exit barrier: the drainer daemon must be GONE when the
    runtime tears down — a daemon thread still blocked at exit (in a PJRT
    RPC, or even just a C-level queue wait) aborts the whole process on
    some PJRT plugins ('FATAL: exception not rethrown' from C++ static
    destructors cancelling lingering pthreads).  Observing every tracked
    buffer ready from THIS thread makes the worker's own blocks return
    ~immediately; then stop the worker via sentinel and join it."""
    global _DRAIN_SHUTDOWN
    with _PENDING_LOCK:
        _DRAIN_SHUTDOWN = True
    if _DRAIN_THREAD is None:
        return
    import time as _time
    deadline = _time.monotonic() + 15.0

    def _bounded_waitall():
        try:
            waitall()
        except Exception:
            pass

    # waitall() itself has no deadline, so run it on a (daemon) helper and
    # join bounded — a wedged device must not turn exit into a hang; if the
    # deadline passes with buffers unfinished we exit anyway and accept the
    # (pre-existing, wedged-device-only) abort risk
    w = threading.Thread(target=_bounded_waitall, daemon=True)
    w.start()
    w.join(15.0)
    while _time.monotonic() < deadline:
        with _PENDING_LOCK:
            busy = _DRAIN_OUTSTANDING > 0
        if not busy:
            break
        _time.sleep(0.02)
    _DRAIN_QUEUE.put(None)  # stop the worker
    _DRAIN_THREAD.join(max(0.1, deadline - _time.monotonic()))


import atexit as _atexit

_atexit.register(_drain_shutdown_barrier)


def _track(data):
    if isinstance(data, jax.Array) and not isinstance(data, jax.core.Tracer):
        if _NAIVE:
            jax.block_until_ready(data)
            return
        old = None
        with _PENDING_LOCK:
            _PENDING.append(data)
            if len(_PENDING) >= _PENDING_PRUNE_AT:
                half = len(_PENDING) // 2
                old = _PENDING[:half]
                del _PENDING[:half]
                _DRAINING.append(old)
        if old:
            _enqueue_drain(old)


def waitall():
    """Block until ALL pending async work completes.

    Parity: mx.nd.waitall → Engine::WaitForAll
    (src/engine/threaded_engine.cc:416). Every produced buffer is tracked
    until observed ready (not a bounded recent-window), so no in-flight
    computation — or async failure — can slip past a waitall().
    """
    try:
        _bulk.flush()  # pending bulked segment counts as in-flight work
    except Exception as e:
        with _PENDING_LOCK:
            _DEFERRED_ERRORS.append(e)
    with _PENDING_LOCK:
        pending = list(_PENDING)
        _PENDING.clear()
        for batch in _DRAINING:  # batches mid-drain in another thread
            pending.extend(batch)
        del _DRAINING[:]
        errors = list(_DEFERRED_ERRORS)
        _DEFERRED_ERRORS.clear()
    # ONE batched block for the whole set: per-buffer blocking pays one
    # runtime round-trip each; the per-buffer walk only runs to attribute
    # errors
    try:
        jax.block_until_ready(pending)
    except Exception:
        for buf in pending:
            try:
                jax.block_until_ready(buf)
            except Exception as e:
                errors.append(e)
    if errors:
        raise errors[0]


# --------------------------------------------------------------------------
# wrapping helpers
# --------------------------------------------------------------------------
def _unwrap(x):
    return x._data if isinstance(x, ndarray) else x


def _unwrap_deep(x):
    if isinstance(x, ndarray):
        return x._data
    if isinstance(x, (list, tuple)):
        return type(x)(_unwrap_deep(v) for v in x)
    if isinstance(x, slice):
        return slice(_unwrap_deep(x.start), _unwrap_deep(x.stop), _unwrap_deep(x.step))
    return x


def _wrap_value(data, node=None, index=0):
    arr = ndarray.__new__(ndarray)
    arr._buf = data
    arr._node = node
    arr._out_index = index
    arr._marked = False
    arr._grad = None
    arr._grad_req = "write"
    if not isinstance(data, _bulk.LazyArray) and node is None:
        _track(data)
    return arr


_scalar_lift_cache = {}


def _lift_scalar(a):
    """Device buffer for a lifted python scalar, cached on (type, value).

    jnp.asarray(0.05) is an EAGER dispatch (one device round-trip); an
    optimizer step passes the same lr/wd/rescale/clip scalars for every
    parameter every step, which cost ~40 eager transfers per LeNet
    step.  Caching also pins the buffer id, so
    the bulk flush's leaf-slot dedup sees one stable leaf per scalar."""
    # copysign disambiguates -0.0 from 0.0 (== and hash conflate them,
    # and 1/x, atan2, copysign are sign-of-zero sensitive)
    k = (type(a), a, math.copysign(1.0, a) if type(a) is float else 1.0)
    v = _scalar_lift_cache.get(k)
    if v is None:
        if len(_scalar_lift_cache) > 4096:   # unbounded-loop safety valve
            _scalar_lift_cache.clear()
        v = jnp.asarray(a)
        _scalar_lift_cache[k] = v
    return v


def apply_op(fn, *args, **kwargs):
    """Invoke op `fn(*vals, **kwargs)`; record VJP on the tape if needed.

    `args` may mix ndarray and constants — only ndarray positions are
    differentiable (the rest are closed over, like non-tensor NodeAttrs in
    the reference op registry).

    Dispatch is BULKED by default: the op is recorded into the pending
    micro-trace segment (_bulk.py) and executes — together with every other
    pending op — as one compiled XLA program at the next sync point.  Ops
    the bulker cannot key or shape-infer, and any call made while tracing
    (hybridize/jit), fall back to immediate eager dispatch.
    """
    if _profiler_agg["enabled"]:
        # per-op aggregate stats (reference AggregateStats,
        # src/profiler/aggregate_stats.cc): time the host dispatch
        t0 = time.perf_counter()
        try:
            return _apply_op_dispatch(fn, args, kwargs)
        finally:
            from . import profiler
            profiler.record_op_stat(getattr(fn, "__name__", "op"),
                                    time.perf_counter() - t0)
    return _apply_op_dispatch(fn, args, kwargs)


def _apply_op_dispatch(fn, args, kwargs):
    nd_idx = [i for i, a in enumerate(args) if isinstance(a, ndarray)]
    nd_args = [args[i] for i in nd_idx]

    recording = autograd.is_recording() and any(
        a._node is not None or a._marked for a in nd_args
    )

    if _bulk.enabled() and not any(
            isinstance(a._buf, jax.core.Tracer) for a in nd_args):
        # first attempt lifts python-scalar positionals as (weak-typed)
        # runtime inputs — `x + i` in a loop then reuses ONE executable
        # instead of compiling per distinct i; ops that need the scalar
        # statically (axis, shape args) fail shape inference and retry
        # with scalars as baked constants
        try:
            return _apply_op_bulked(fn, args, kwargs, nd_idx, nd_args,
                                    recording, lift_scalars=True)
        except _bulk.Unbulkable:
            pass
        try:
            return _apply_op_bulked(fn, args, kwargs, nd_idx, nd_args,
                                    recording, lift_scalars=False)
        except _bulk.Unbulkable:
            _bulk.note_eager_fallback()

    return _apply_op_eager(fn, args, kwargs, nd_idx, nd_args, recording)


def _apply_op_bulked(fn, args, kwargs, nd_idx, nd_args, recording,
                     lift_scalars=False):
    # lift every array-valued positional (ndarray buffers, raw jax/onp
    # arrays) into the segment; scalars/tuples stay constants
    seg_args = []
    arr_idx = []   # positions traced as segment inputs
    for i, a in enumerate(args):
        if isinstance(a, ndarray):
            seg_args.append(a._buf)
            arr_idx.append(i)
        elif isinstance(a, jax.Array) or (
                isinstance(a, onp.ndarray) and a.dtype != object):
            seg_args.append(a)
            arr_idx.append(i)
        elif lift_scalars and type(a) in (int, float, bool):
            seg_args.append(_lift_scalar(a))  # stays weak-typed: same
            arr_idx.append(i)                 # promotion as the raw scalar
        else:
            seg_args.append(a)
    outs, multi = _bulk.record_op(fn, tuple(seg_args), kwargs)

    node = None
    if recording:
        template = list(args)
        for i in arr_idx:
            template[i] = None
        n_tape = len(arr_idx)

        def closed(*vs):
            full = list(template)
            for i, v in zip(arr_idx, vs):
                full[i] = v
            return fn(*full, **kwargs)

        # tape inputs: the ndarrays, plus wrappers for raw-array positions
        # (their grads are computed and dropped — they are not leaves)
        tape_inputs = []
        for i in arr_idx:
            a = args[i]
            if isinstance(a, ndarray):
                tape_inputs.append(a)
            elif isinstance(a, onp.ndarray):
                tape_inputs.append(_wrap_value(jnp.asarray(a)))
            else:
                # seg_args[i] already holds the device buffer (incl. the
                # cached _lift_scalar buffer for python scalars — a fresh
                # jnp.asarray here would re-pay an eager transfer per op)
                tape_inputs.append(_wrap_value(seg_args[i]))
        node = TapeNode(
            None,                      # VJP deferred: backward replays fn
            tape_inputs,
            len(outs),
            [o.shape for o in outs],
            [o.dtype for o in outs],
            out_is_tuple=multi,
            fn=closed,
            in_bufs=tuple(seg_args[i] for i in arr_idx),
        )
        assert n_tape == len(tape_inputs)
    wrapped = [_wrap_value(o, node, i) for i, o in enumerate(outs)]
    if multi:
        return tuple(wrapped)
    return wrapped[0]


def _apply_op_eager(fn, args, kwargs, nd_idx, nd_args, recording):
    vals = [a._data for a in nd_args]

    # raw LazyArray args (deferred-VJP replay passes record-time buffers,
    # which are lazy for chained ops in one segment) must materialize
    # before jax.vjp sees them
    if any(type(a) is _bulk.LazyArray for a in args):
        args = tuple(_bulk.materialize(a) if type(a) is _bulk.LazyArray
                     else a for a in args)

    if recording:
        template = list(args)

        def closed(*vs):
            full = list(template)
            for i, v in zip(nd_idx, vs):
                full[i] = v
            return fn(*full, **kwargs)

        out_vals, vjp_fn = jax.vjp(closed, *vals)
    else:
        full = list(args)
        for i, v in zip(nd_idx, vals):
            full[i] = v
        out_vals = fn(*full, **kwargs)
        vjp_fn = None

    multi = isinstance(out_vals, (tuple, list))
    outs = list(out_vals) if multi else [out_vals]

    node = None
    if recording:
        node = TapeNode(
            vjp_fn,
            nd_args,
            len(outs),
            [o.shape for o in outs],
            [o.dtype for o in outs],
            out_is_tuple=multi,
            fn=closed,
        )
    wrapped = [_wrap_value(o, node, i) for i, o in enumerate(outs)]
    if multi:
        return type(out_vals)(wrapped) if isinstance(out_vals, tuple) else wrapped
    return wrapped[0]


def _guard_int64_narrowing(obj, dtype):
    """With x64 disabled, jnp.asarray silently narrows int64->int32 —
    an embedding/take index over 2^31 rows would CORRUPT, not fail
    (reference builds guard this with USE_INT64_TENSOR_SIZE,
    /root/reference/tests/nightly/test_large_array.py).  Policy: loud or
    correct, never silent — in-range values narrow safely; out-of-range
    values raise with a pointer to MXNET_INT64_TENSOR_SIZE=1."""
    if jax.config.jax_enable_x64:
        return  # true int64 mode: no narrowing happens
    try:
        src = onp.asarray(obj)
    except Exception:
        return
    if src.dtype not in (onp.int64, onp.uint64) or src.size == 0:
        return
    if dtype is not None and onp.dtype(dtype).itemsize <= 4:
        return  # explicit narrow request: user asked for it
    lo, hi = int(src.min()), int(src.max())
    # narrowing targets: int64->int32 (signed bound), uint64->uint32
    bound_lo, bound_hi = ((0, 2**32) if src.dtype == onp.uint64
                          else (-2**31, 2**31))
    if lo < bound_lo or hi >= bound_hi:
        raise OverflowError(
            "%s value %d does not fit %s and would be silently "
            "truncated; set MXNET_INT64_TENSOR_SIZE=1 to enable true "
            "int64 tensors"
            % (src.dtype.name, hi if hi >= bound_hi else lo,
               "uint32" if src.dtype == onp.uint64 else "int32"))


def _to_jax(obj, dtype=None, ctx=None):
    if isinstance(obj, ndarray):
        data = obj._data
        if dtype is not None:
            data = data.astype(dtype)
    else:
        if not isinstance(obj, (int, float, bool, jax.Array)):
            _guard_int64_narrowing(obj, dtype)
        data = jnp.asarray(obj, dtype=dtype)
    if ctx is not None and isinstance(data, jax.Array):
        dev = ctx.jax_device if isinstance(ctx, Context) else ctx
        try:
            if jax.core.is_concrete(data):
                data = jax.device_put(data, dev)
        except Exception:
            pass
    return data


def array(obj, dtype=None, ctx=None, device=None):
    """Create an ndarray (parity: mx.np.array)."""
    ctx = ctx or device
    if dtype is None and not hasattr(obj, "dtype"):
        # match reference default_dtype: python floats -> float32
        pass
    return _wrap_value(_to_jax(obj, dtype=dtype, ctx=ctx))


def from_numpy(a, zero_copy=False):
    return array(a)


# --------------------------------------------------------------------------
# the ndarray class
# --------------------------------------------------------------------------
class ndarray:
    """NumPy-compatible imperative array on TPU (mx.np.ndarray parity).

    `_buf` holds either a concrete jax.Array or a `_bulk.LazyArray` — a
    pending output of the op-bulking micro-trace (the reference engine's
    bulk execution reborn, see _bulk.py).  Reading `._data` materializes;
    shape/dtype metadata never forces materialization."""

    __slots__ = ("_buf", "_node", "_out_index", "_marked", "_grad",
                 "_grad_req", "__weakref__")

    def __init__(self, data=None, dtype=None, ctx=None):
        self._buf = _to_jax(data if data is not None else (), dtype, ctx)
        self._node = None
        self._out_index = 0
        self._marked = False
        self._grad = None
        self._grad_req = "write"

    # -- lazy buffer ------------------------------------------------------
    @property
    def _data(self):
        buf = self._buf
        if type(buf) is _bulk.LazyArray:
            buf = _bulk.materialize(buf)
            self._buf = buf
        return buf

    @_data.setter
    def _data(self, v):
        self._buf = v

    # -- properties -------------------------------------------------------
    @property
    def shape(self):
        return tuple(self._buf.shape)

    @property
    def dtype(self):
        return onp.dtype(self._buf.dtype)

    @property
    def size(self):
        return int(onp.prod(self.shape)) if self.shape else 1

    @property
    def ndim(self):
        return len(self._buf.shape)

    @property
    def itemsize(self):
        return self.dtype.itemsize

    @property
    def T(self):
        return apply_op(jnp.transpose, self)

    @property
    def ctx(self):
        try:
            dev = self._data.devices().pop()
            dt = "tpu" if dev.platform != "cpu" else dev.platform
            return Context(dt, dev.id)
        except Exception:
            return current_context()

    context = ctx
    device = ctx

    @property
    def grad(self):
        return self._grad

    @property
    def stype(self):
        return "default"

    # -- autograd ---------------------------------------------------------
    def attach_grad(self, grad_req="write"):
        """Allocate gradient buffer & mark as autograd leaf
        (parity: NDArray.attach_grad → MXAutogradMarkVariables)."""
        self._marked = True
        self._grad_req = grad_req
        self._grad = _wrap_value(jnp.zeros(self.shape, self.dtype))

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        autograd.backward([self], [out_grad], retain_graph, train_mode)

    def detach(self):
        return _wrap_value(self._data)

    def zero_grad(self):
        if self._grad is not None:
            self._grad._data = jnp.zeros_like(self._grad._data)

    # -- sync points ------------------------------------------------------
    def wait_to_read(self):
        try:
            jax.block_until_ready(self._data)  # materializes pending bulk
        except jax.errors.ConcretizationTypeError:
            pass

    wait_to_write = wait_to_read

    def asnumpy(self):
        self.wait_to_read()
        return onp.asarray(self._data)

    def item(self, *args):
        return self.asnumpy().item(*args)

    def tolist(self):
        return self.asnumpy().tolist()

    def __array__(self, dtype=None):
        a = self.asnumpy()
        return a.astype(dtype) if dtype is not None else a

    # NumPy interop protocol (reference numpy_dispatch_protocol.py:37 +
    # numpy/fallback.py:25): numpy.mean(mx_array) etc. dispatch to mx ops
    # instead of coercing through __array__; see numpy_dispatch.py
    def __array_function__(self, func, types, args, kwargs):
        from .numpy_dispatch import array_function
        return array_function(self, func, types, args, kwargs)

    def __array_ufunc__(self, ufunc, method, *inputs, **kwargs):
        from .numpy_dispatch import array_ufunc
        return array_ufunc(self, ufunc, method, *inputs, **kwargs)

    def __dlpack__(self, **kw):
        return self._data.__dlpack__(**kw)

    def __dlpack_device__(self):
        return self._data.__dlpack_device__()

    # -- conversion / movement -------------------------------------------
    def astype(self, dtype, copy=True):
        if onp.dtype(dtype) == self.dtype and not copy:
            return self
        return apply_op(lambda x: x.astype(onp.dtype(dtype)), self)

    def copy(self):
        return apply_op(jnp.copy, self)

    def copyto(self, other):
        if isinstance(other, ndarray):
            other._set_data(jnp.broadcast_to(self._data, other.shape).astype(other.dtype))
            return other
        if isinstance(other, Context):
            return self.as_in_ctx(other)
        raise TypeError("copyto: unsupported target %r" % (other,))

    def as_in_ctx(self, ctx):
        if not isinstance(ctx, Context):
            raise TypeError("expected Context")
        data = jax.device_put(self._data, ctx.jax_device)
        return _wrap_value(data)

    as_in_context = as_in_ctx
    to_device = as_in_ctx
    as_np_ndarray = lambda self: self
    as_nd_ndarray = lambda self: self

    # -- mutation ---------------------------------------------------------
    def _set_data(self, data):
        if autograd.is_recording() and (self._node is not None):
            raise RuntimeError(
                "in-place mutation of an array produced inside a record() "
                "scope is not allowed (reference: kWriteInplace hazard)"
            )
        self._buf = data
        if type(data) is not _bulk.LazyArray:
            _track(data)

    def __setitem__(self, key, value):
        key = _unwrap_deep(key)
        v = _unwrap(value)
        if isinstance(key, tuple) and len(key) == 0:
            key = Ellipsis
        bkey = key
        if isinstance(bkey, jax.Array) and bkey.dtype == jnp.bool_:
            self._set_data(jnp.where(bkey, jnp.asarray(v, self._data.dtype), self._data)
                           if onp.ndim(v) == 0 else self._data.at[bkey].set(v))
            return
        self._set_data(self._data.at[bkey].set(v))

    def __getitem__(self, key):
        key = _unwrap_deep(key)
        return apply_op(lambda x: x[key], self)

    # -- dunder scalars ----------------------------------------------------
    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __bool__(self):
        if self.size != 1:
            raise ValueError(
                "The truth value of an ndarray with %d elements is "
                "ambiguous. Use a.any() or a.all()." % self.size)
        return bool(self.asnumpy().item())

    def __float__(self):
        return float(self.asnumpy().item())

    def __int__(self):
        return int(self.asnumpy().item())

    def __index__(self):
        return int(self.asnumpy().item())

    def __repr__(self):
        try:
            s = str(self.asnumpy())
        except Exception as e:  # tracers
            s = "<abstract %s %s>" % (self._data.aval.str_short(), type(self._data).__name__)
        return "array(%s, ctx=%s)" % (s.replace("\n", "\n      "), self.ctx)

    __hash__ = None

    # -- arithmetic -------------------------------------------------------
    def _binary(self, other, fn, reverse=False):
        if isinstance(other, (list, tuple, onp.ndarray)):
            other = array(other)
        if reverse:
            return apply_op(lambda b, a: fn(a, b), self, other) if not isinstance(
                other, ndarray) else apply_op(fn, other, self)
        return apply_op(fn, self, other)

    def __add__(self, o):
        return self._binary(o, jnp.add)

    def __radd__(self, o):
        return self._binary(o, jnp.add, True)

    def __sub__(self, o):
        return self._binary(o, jnp.subtract)

    def __rsub__(self, o):
        return self._binary(o, jnp.subtract, True)

    def __mul__(self, o):
        return self._binary(o, jnp.multiply)

    def __rmul__(self, o):
        return self._binary(o, jnp.multiply, True)

    def __truediv__(self, o):
        return self._binary(o, jnp.true_divide)

    def __rtruediv__(self, o):
        return self._binary(o, jnp.true_divide, True)

    def __floordiv__(self, o):
        return self._binary(o, jnp.floor_divide)

    def __rfloordiv__(self, o):
        return self._binary(o, jnp.floor_divide, True)

    def __mod__(self, o):
        return self._binary(o, jnp.mod)

    def __rmod__(self, o):
        return self._binary(o, jnp.mod, True)

    def __divmod__(self, o):
        return self // o, self % o

    def __pow__(self, o):
        return self._binary(o, jnp.power)

    def __rpow__(self, o):
        return self._binary(o, jnp.power, True)

    def __matmul__(self, o):
        return self._binary(o, jnp.matmul)

    def __rmatmul__(self, o):
        return self._binary(o, jnp.matmul, True)

    def __neg__(self):
        return apply_op(jnp.negative, self)

    def __pos__(self):
        return self

    def __abs__(self):
        return apply_op(jnp.abs, self)

    def __invert__(self):
        return apply_op(jnp.invert, self)

    def __and__(self, o):
        return self._binary(o, jnp.bitwise_and)

    def __or__(self, o):
        return self._binary(o, jnp.bitwise_or)

    def __xor__(self, o):
        return self._binary(o, jnp.bitwise_xor)

    def __rand__(self, o):
        return self._binary(o, jnp.bitwise_and, True)

    def __ror__(self, o):
        return self._binary(o, jnp.bitwise_or, True)

    def __rxor__(self, o):
        return self._binary(o, jnp.bitwise_xor, True)

    def __lshift__(self, o):
        return self._binary(o, jnp.left_shift)

    def __rshift__(self, o):
        return self._binary(o, jnp.right_shift)

    # comparisons
    def __eq__(self, o):
        return self._binary(o, jnp.equal)

    def __ne__(self, o):
        return self._binary(o, jnp.not_equal)

    def __lt__(self, o):
        return self._binary(o, jnp.less)

    def __le__(self, o):
        return self._binary(o, jnp.less_equal)

    def __gt__(self, o):
        return self._binary(o, jnp.greater)

    def __ge__(self, o):
        return self._binary(o, jnp.greater_equal)

    # in-place (real mutation, version-bump semantics)
    def __iadd__(self, o):
        self._set_data(self._data + _unwrap(o))
        return self

    def __isub__(self, o):
        self._set_data(self._data - _unwrap(o))
        return self

    def __imul__(self, o):
        self._set_data(self._data * _unwrap(o))
        return self

    def __itruediv__(self, o):
        self._set_data(self._data / _unwrap(o))
        return self

    def __ifloordiv__(self, o):
        self._set_data(self._data // _unwrap(o))
        return self

    def __imod__(self, o):
        self._set_data(self._data % _unwrap(o))
        return self

    def __ipow__(self, o):
        self._set_data(self._data ** _unwrap(o))
        return self

    # -- ndarray methods mirroring mx.np.ndarray --------------------------
    def reshape(self, *shape, **kwargs):
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        shape = tuple(int(s) for s in shape)
        return apply_op(lambda x: jnp.reshape(x, shape), self)

    def reshape_like(self, other):
        return self.reshape(other.shape)

    def transpose(self, *axes):
        if len(axes) == 1 and isinstance(axes[0], (tuple, list)):
            axes = tuple(axes[0])
        axes = axes if axes else None
        return apply_op(lambda x: jnp.transpose(x, axes), self)

    def swapaxes(self, a, b):
        return apply_op(lambda x: jnp.swapaxes(x, a, b), self)

    def flatten(self):
        return self.reshape(-1)

    def ravel(self):
        return self.reshape(-1)

    def squeeze(self, axis=None):
        return apply_op(lambda x: jnp.squeeze(x, axis), self)

    def expand_dims(self, axis):
        return apply_op(lambda x: jnp.expand_dims(x, axis), self)

    def broadcast_to(self, shape):
        return apply_op(lambda x: jnp.broadcast_to(x, shape), self)

    def broadcast_like(self, other):
        return self.broadcast_to(other.shape)

    def repeat(self, repeats, axis=None):
        return apply_op(lambda x: jnp.repeat(x, repeats, axis), self)

    def tile(self, reps):
        return apply_op(lambda x: jnp.tile(x, reps), self)

    def take(self, indices, axis=None, mode="clip"):
        idx = _unwrap(indices)
        if isinstance(idx, (list, tuple)):
            idx = onp.asarray(idx)
        return apply_op(lambda x: jnp.take(x, idx, axis=axis, mode=mode), self)

    def pick(self, index, axis=-1, keepdims=False, mode="clip"):
        idx = _unwrap(index)
        return apply_op(
            lambda x: jnp.take_along_axis(
                x, jnp.expand_dims(idx.astype(jnp.int32), axis), axis
            ).squeeze(axis) if not keepdims else jnp.take_along_axis(
                x, jnp.expand_dims(idx.astype(jnp.int32), axis), axis),
            self)

    def clip(self, a_min=None, a_max=None):
        return apply_op(lambda x: jnp.clip(x, a_min, a_max), self)

    def round(self, decimals=0):
        return apply_op(lambda x: jnp.round(x, decimals), self)

    def _reduce(self, fn, axis=None, dtype=None, keepdims=False):
        def f(x):
            r = fn(x, axis=axis, keepdims=keepdims)
            return r.astype(dtype) if dtype is not None else r
        return apply_op(f, self)

    def sum(self, axis=None, dtype=None, keepdims=False, **kw):
        return self._reduce(jnp.sum, axis, dtype, keepdims)

    def mean(self, axis=None, dtype=None, keepdims=False, **kw):
        return self._reduce(jnp.mean, axis, dtype, keepdims)

    def prod(self, axis=None, dtype=None, keepdims=False, **kw):
        return self._reduce(jnp.prod, axis, dtype, keepdims)

    def max(self, axis=None, keepdims=False, **kw):
        return self._reduce(jnp.max, axis, None, keepdims)

    def min(self, axis=None, keepdims=False, **kw):
        return self._reduce(jnp.min, axis, None, keepdims)

    def std(self, axis=None, dtype=None, ddof=0, keepdims=False, **kw):
        return apply_op(lambda x: jnp.std(x, axis=axis, ddof=ddof, keepdims=keepdims), self)

    def var(self, axis=None, dtype=None, ddof=0, keepdims=False, **kw):
        return apply_op(lambda x: jnp.var(x, axis=axis, ddof=ddof, keepdims=keepdims), self)

    def argmax(self, axis=None, **kw):
        return apply_op(lambda x: jnp.argmax(x, axis=axis), self)

    def argmin(self, axis=None, **kw):
        return apply_op(lambda x: jnp.argmin(x, axis=axis), self)

    def argsort(self, axis=-1, is_ascend=True, **kw):
        def f(x):
            r = jnp.argsort(x, axis=axis)
            return r if is_ascend else jnp.flip(r, axis=axis)
        return apply_op(f, self)

    def sort(self, axis=-1, **kw):
        return apply_op(lambda x: jnp.sort(x, axis=axis), self)

    def cumsum(self, axis=None, dtype=None):
        return apply_op(lambda x: jnp.cumsum(x, axis=axis, dtype=dtype), self)

    def dot(self, other):
        return self._binary(other, jnp.dot)

    def all(self, axis=None, keepdims=False):
        return self._reduce(jnp.all, axis, None, keepdims)

    def any(self, axis=None, keepdims=False):
        return self._reduce(jnp.any, axis, None, keepdims)

    def nonzero(self):
        return apply_op(jnp.nonzero, self)

    def abs(self):
        return apply_op(jnp.abs, self)

    def sqrt(self):
        return apply_op(jnp.sqrt, self)

    def square(self):
        return apply_op(jnp.square, self)

    def log(self):
        return apply_op(jnp.log, self)

    def exp(self):
        return apply_op(jnp.exp, self)

    def sigmoid(self):
        return apply_op(jax.nn.sigmoid, self)

    def tanh(self):
        return apply_op(jnp.tanh, self)

    def relu(self):
        return apply_op(jax.nn.relu, self)

    def slice_axis(self, axis, begin, end):
        sl = [slice(None)] * self.ndim
        sl[axis] = slice(begin, end)
        return self[tuple(sl)]

    def tostype(self, stype):
        if stype == "default":
            return self
        from . import sparse
        return sparse.cast_storage(self, stype)


NDArray = ndarray
