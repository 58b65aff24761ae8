"""Deferred-eager op bulking: batch consecutive imperative ops into one
compiled XLA segment.

Parity: the reference engine's bulk execution (`MXNET_EXEC_BULK_EXEC_TRAIN`,
`src/engine/threaded_engine.h:432` BulkStatus/BulkAppend — consecutive
engine ops coalesced into one scheduled function).  TPU-native design:
instead of coalescing engine *tasks*, imperative ops are recorded into a
pending micro-trace ("segment"); a host sync point (`.asnumpy()`,
`wait_to_read()`, `waitall()`, direct `._data` access) traces the segment
into ONE jitted XLA executable (cached by segment structure) and runs it.
A steady-state training loop therefore costs a handful of device dispatches
per step instead of one per op — a dispatch costs host time on any
backend, and per-op dispatch is the dominant cost of an eager loop.

The segment executable is cached on a structural key: per op, the function
identity (code object + closure-cell fingerprint), constant args, and the
dataflow wiring; plus the avals of all concrete leaf inputs.  Closure cells
holding device arrays (e.g. PRNG keys) are lifted to leaf inputs — the op
function is rebuilt with fresh cells at trace time — so the same executable
serves every iteration of a loop while values flow as runtime inputs.

Anything the tracer cannot key or shape-infer (data-dependent output
shapes, exotic constants) raises `Unbulkable` and the caller falls back to
plain eager dispatch.  `MXNET_EXEC_BULK_EXEC=0` disables the whole
machinery; the NaiveEngine setting implies it.
"""
from __future__ import annotations

import logging
import os
import threading
import types

import numpy as onp

import jax
import jax.numpy as jnp

log = logging.getLogger(__name__)

_MAX_DEFAULT = 512


class Unbulkable(Exception):
    """Op cannot join a bulk segment; execute it eagerly instead."""


class LazyArray:
    """Placeholder for an op output that has not been materialized yet."""

    __slots__ = ("aval", "op", "idx", "value", "error", "__weakref__")

    def __init__(self, aval, op, idx):
        self.aval = aval
        self.op = op          # BulkOp producing it
        self.idx = idx        # output position within the op
        self.value = None     # concrete jax.Array once flushed
        self.error = None     # poison: exception from a failed flush

    @property
    def shape(self):
        return self.aval.shape

    @property
    def dtype(self):
        return self.aval.dtype

    @property
    def ndim(self):
        return len(self.aval.shape)


class BulkOp:
    __slots__ = ("fn", "arg_spec", "kwarg_spec", "cell_spec", "outs",
                 "out_is_tuple", "key", "ambients")

    def __init__(self, fn, arg_spec, kwarg_spec, cell_spec, outs,
                 out_is_tuple, key):
        self.fn = fn
        self.arg_spec = arg_spec      # tuple of ('lazy',x)|('leaf',x)|('const',v)
        self.kwarg_spec = kwarg_spec  # tuple of (name, spec)
        self.cell_spec = cell_spec    # None, or tuple of specs for closure cells
        self.outs = outs              # list of LazyArray
        self.out_is_tuple = out_is_tuple
        self.key = key                # structural cache-key fragment


class _SegState(threading.local):
    def __init__(self):
        self.ops = []
        self.limit = _MAX_DEFAULT
        self.flushing = False


_seg = _SegState()
_cache = {}
_aval_cache = {}  # (fn_key, arg sig, ambients) -> (out_avals, out_is_tuple)
_UNBULKABLE = object()  # negative-cache tag: (_UNBULKABLE, reason)


def _aval_cache_put(key, value):
    """Single insertion point so the growth cap covers negative entries
    too (a stream of distinct failing signatures must not grow the dict
    without bound)."""
    if len(_aval_cache) > 16384:
        _aval_cache.clear()
    _aval_cache[key] = value
_stats = {"flushes": 0, "compiles": 0, "ops_bulked": 0, "eager_fallbacks": 0}

# Ambient thread-local state that op functions read at EXECUTION time (e.g.
# the AMP scope dtype).  Deferred execution would otherwise observe the
# state at flush time instead of call time, so record_op snapshots every
# registered ambient and the flush runner re-enters it around each op.
# Each entry: name -> (getter, setter); the snapshot must be hashable (it
# joins the cache key).
_ambients = {}


def register_ambient(name, getter, setter):
    _ambients[name] = (getter, setter)


def _snapshot_ambients():
    return tuple((name, g()) for name, (g, _) in _ambients.items())


class _AmbientScope:
    def __init__(self, snap):
        self.snap = snap
        self.saved = None

    def __enter__(self):
        self.saved = [(name, _ambients[name][0]()) for name, _ in self.snap]
        for name, v in self.snap:
            _ambients[name][1](v)

    def __exit__(self, *exc):
        for name, v in self.saved:
            _ambients[name][1](v)
        return False


def enabled():
    if os.environ.get("MXNET_EXEC_BULK_EXEC", "1") in ("0", "false", "False"):
        return False
    if os.environ.get("MXNET_ENGINE_TYPE") == "NaiveEngine":
        return False
    return not _seg.flushing


def stats():
    return dict(_stats)


def set_bulk_size(n):
    prev = _seg.limit
    _seg.limit = max(1, int(n))
    return prev


# ---------------------------------------------------------------------------
# cache-key construction
# ---------------------------------------------------------------------------
_SCALARS = (int, float, bool, str, bytes, complex, type(None), type(Ellipsis))


def _const_key(v, depth=0):
    if depth > 10:
        raise Unbulkable("constant nesting too deep")
    if isinstance(v, _SCALARS):
        return (type(v).__name__, v)
    if isinstance(v, (onp.generic,)):
        return ("npscalar", v.dtype.str, v.item())
    if isinstance(v, onp.dtype):
        return ("dtype", v.str)
    if isinstance(v, type):
        return ("type", v.__module__, v.__qualname__)
    if isinstance(v, (tuple, list)):
        return (type(v).__name__,
                tuple(_const_key(x, depth + 1) for x in v))
    if isinstance(v, (frozenset, set)):
        return ("set", tuple(sorted(repr(x) for x in v)))
    if isinstance(v, dict):
        return ("dict", tuple(sorted((k, _const_key(x, depth + 1))
                                     for k, x in v.items())))
    if isinstance(v, slice):
        return ("slice", _const_key(v.start, depth + 1),
                _const_key(v.stop, depth + 1), _const_key(v.step, depth + 1))
    if callable(v):
        return _fn_key(v, depth + 1)[0]
    raise Unbulkable("unkeyable constant %r" % type(v).__name__)


def _fn_key(fn, depth=0):
    """(key, cell_spec) for a callable.  cell_spec is None when the function
    can be called as-is, else a tuple describing how to rebuild its closure
    cells (lifting device-array cells to leaf inputs)."""
    if depth > 10:
        raise Unbulkable("function nesting too deep")
    if getattr(fn, "_mx_no_bulk", False):
        # per-call state (host callbacks, fresh custom-op instances): every
        # call would be a cache miss, so run it eagerly instead
        raise Unbulkable("fn marked no-bulk")
    if isinstance(fn, types.BuiltinFunctionType):
        return ("builtin", fn.__module__, fn.__qualname__), None
    if isinstance(fn, types.MethodType):
        k, _ = _fn_key(fn.__func__, depth + 1)
        # pin the bound object itself (identity-hashed): id()/repr() would
        # collide when addresses are reused after GC
        try:
            hash(fn.__self__)
        except TypeError:
            raise Unbulkable("unhashable bound-method receiver")
        return ("method", k, fn.__self__), None
    part = getattr(fn, "func", None)
    if part is not None and hasattr(fn, "args"):  # functools.partial
        k, _ = _fn_key(fn.func, depth + 1)
        return ("partial", k, _const_key(fn.args, depth + 1),
                _const_key(fn.keywords or {}, depth + 1)), None
    code = getattr(fn, "__code__", None)
    if code is None:
        # arbitrary callable object (jnp ufunc wrappers, custom-op
        # instances): key by the object itself — identity-hashed AND kept
        # alive by the cache key, so the key can never alias a new object
        # at a recycled address
        try:
            hash(fn)
        except TypeError:
            raise Unbulkable("unhashable callable %r" % (fn,))
        return ("obj", fn), None
    if getattr(fn, "__defaults__", None):
        for d in fn.__defaults__:
            if isinstance(d, (jax.Array, onp.ndarray)):
                raise Unbulkable("array default argument")
    cells = fn.__closure__ or ()
    cell_keys = []
    cell_spec = []
    lifted = False
    for c in cells:
        v = c.cell_contents
        buf = getattr(v, "_buf", None)  # ndarray wrapper in a closure cell
        if buf is not None and not callable(v):
            v = buf
        if isinstance(v, LazyArray):
            if v.value is not None:
                v = v.value
            else:
                cell_keys.append(("cellleaf", jax.ShapeDtypeStruct(
                    v.aval.shape, v.aval.dtype)))
                cell_spec.append(("lazycell", v))
                lifted = True
                continue
        if isinstance(v, jax.Array):
            cell_keys.append(("cellleaf", jax.ShapeDtypeStruct(
                v.shape, v.dtype)))
            cell_spec.append(("leaf", v))
            lifted = True
        elif isinstance(v, onp.ndarray):
            av = jnp.asarray(v)
            cell_keys.append(("cellleaf", jax.ShapeDtypeStruct(
                av.shape, av.dtype)))
            cell_spec.append(("leaf", av))
            lifted = True
        elif isinstance(v, types.FunctionType):
            # recurse: a nested closure may hold array cells of its own
            # (hybridized blocks close over aux/param arrays) — those lift
            # through the whole chain
            k, inner_spec = _fn_key(v, depth + 1)
            cell_keys.append(k)
            if inner_spec is not None:
                cell_spec.append(("fn", v, inner_spec))
                lifted = True
            else:
                cell_spec.append(("const", v))
        elif callable(v) and not isinstance(v, type):
            k, _ = _fn_key(v, depth + 1)
            cell_keys.append(k)
            cell_spec.append(("const", v))
        else:
            cell_keys.append(_const_key(v, depth + 1))
            cell_spec.append(("const", v))
    key = ("fn", code, tuple(cell_keys))
    return key, (tuple(cell_spec) if lifted else None)


def _rebuild_fn(fn, cell_values):
    cells = tuple(types.CellType(v) for v in cell_values)
    g = types.FunctionType(fn.__code__, fn.__globals__, fn.__name__,
                           fn.__defaults__, cells)
    g.__kwdefaults__ = fn.__kwdefaults__
    return g


def _resolve_cell_spec(fn, spec, resolve_entry):
    """Rebuild `fn` with its cell_spec resolved: array-bearing cells via
    `resolve_entry(entry)`, ('fn', f, inner) cells recursively, constants
    as-is."""
    values = []
    for entry in spec:
        tag = entry[0]
        if tag == "fn":
            values.append(_resolve_cell_spec(entry[1], entry[2],
                                             resolve_entry))
        elif tag == "const":
            values.append(entry[1])
        else:  # leaf / lazycell / lazy — plan- or record-level array refs
            values.append(resolve_entry(entry))
    return _rebuild_fn(fn, values)


# ---------------------------------------------------------------------------
# recording
# ---------------------------------------------------------------------------
def _spec_of(v):
    """Classify one op argument."""
    if isinstance(v, LazyArray):
        if v.value is not None:
            return ("leaf", v.value)
        if v.error is not None:
            raise v.error
        return ("lazy", v)
    if isinstance(v, jax.Array):
        return ("leaf", v)
    if isinstance(v, onp.ndarray) and v.dtype != object:
        return ("leaf", jnp.asarray(v))
    return ("const", v)


def record_op(fn, args, kwargs):
    """Record `fn(*args, **kwargs)` into the current segment.  Array-valued
    args may be jax.Array, onp.ndarray or LazyArray; everything else is a
    constant.  Returns (list of LazyArray outputs, out_is_tuple)."""
    fn_key, cell_spec = _fn_key(fn)
    arg_spec = tuple(_spec_of(a) for a in args)
    kwarg_spec = tuple(sorted(
        (k, _spec_of(v)) for k, v in kwargs.items()))

    def avalize(spec):
        tag, v = spec
        if tag == "const":
            return v
        if tag == "lazy":
            return jax.ShapeDtypeStruct(v.aval.shape, v.aval.dtype)
        return jax.ShapeDtypeStruct(v.shape, v.dtype)

    def spec_sig(spec):
        # hot path: runs once per array per recorded op (the fused
        # optimizer op alone carries ~500 arrays).  Key on the raw
        # (shape, dtype) objects — no ShapeDtypeStruct construction, no
        # str(dtype) (numpy dtypes hash/compare fine)
        tag, v = spec
        if tag == "const":
            return ("const", _const_key(v))
        if tag == "lazy":
            a = v.aval
            return ("arr", a.shape, a.dtype)
        return ("arr", v.shape, v.dtype)

    ambients = _snapshot_ambients()
    try:
        amb_key = tuple((n, _const_key(v)) for n, v in ambients)
    except Unbulkable:
        amb_key = tuple((n, repr(v)) for n, v in ambients)

    # shape inference without executing (and bulkability check); eval_shape
    # is pure Python tracing at ~ms per conv-sized op, so it is cached on
    # the same structural identity the executable cache uses
    aval_key = (fn_key, tuple(spec_sig(s) for s in arg_spec),
                tuple((k, spec_sig(s)) for k, s in kwarg_spec), amb_key)
    cached = _aval_cache.get(aval_key)
    if cached is not None:
        if cached[0] is _UNBULKABLE:
            # negative cache: a failed shape inference is value-independent
            # for this structural signature (lifted scalars are abstract),
            # so re-tracing it per call would pay ~ms of eval_shape on
            # EVERY op that needs the baked-const retry (e.g. sgd_update's
            # `clip_gradient > 0` branch, once per parameter per step)
            raise Unbulkable(cached[1])
        avals, out_is_tuple = cached
    else:
        call_fn = fn
        if cell_spec is not None:
            # for shape inference, rebuild with the current cell values; a
            # still-pending lazy cell stands in as zeros of its aval (the
            # inference result is cached on structure, not values)
            def _record_cell(entry):
                if entry[0] == "lazycell":
                    a = entry[1].aval
                    return jnp.zeros(a.shape, a.dtype)
                return entry[1]
            call_fn = _resolve_cell_spec(fn, cell_spec, _record_cell)

        # only array args go through eval_shape (it abstracts EVERY leaf,
        # so a constant like axis=1 or clip=-1.0 would become a tracer and
        # break ops that branch on it); constants are closed over
        arr_arg_idx = [i for i, s in enumerate(arg_spec) if s[0] != "const"]
        arr_kw_keys = [k for k, s in kwarg_spec if s[0] != "const"]

        def shell(*arrs):
            it = iter(arrs)
            full_args = [next(it) if s[0] != "const" else s[1]
                         for s in arg_spec]
            full_kw = {k: (next(it) if s[0] != "const" else s[1])
                       for k, s in kwarg_spec}
            return call_fn(*full_args, **full_kw)

        try:
            out_avals = jax.eval_shape(
                shell,
                *[avalize(arg_spec[i]) for i in arr_arg_idx],
                *[avalize(dict(kwarg_spec)[k]) for k in arr_kw_keys])
        except Unbulkable as e:
            _aval_cache_put(aval_key, (_UNBULKABLE, str(e)))
            raise
        except Exception as e:
            msg = "eval_shape failed: %s" % e
            _aval_cache_put(aval_key, (_UNBULKABLE, msg))
            raise Unbulkable(msg)

        out_is_tuple = isinstance(out_avals, (tuple, list))
        avals = list(out_avals) if out_is_tuple else [out_avals]
        for a in avals:
            # negative-cache these too: they are as structural as an
            # eval_shape failure, and an uncached raise re-pays the full
            # trace on every call of the same signature
            if not isinstance(a, jax.ShapeDtypeStruct) or any(
                    not isinstance(d, int) for d in a.shape):
                msg = "non-array or dynamic-shape output"
                _aval_cache_put(aval_key, (_UNBULKABLE, msg))
                raise Unbulkable(msg)
            if a.dtype == jax.dtypes.float0:
                msg = "float0 output (int-input VJP); run eagerly"
                _aval_cache_put(aval_key, (_UNBULKABLE, msg))
                raise Unbulkable(msg)
        _aval_cache_put(aval_key, (avals, out_is_tuple))

    op = BulkOp(fn, arg_spec, kwarg_spec, cell_spec, [], out_is_tuple, None)
    op.ambients = ambients
    op.outs = [LazyArray(a, op, i) for i, a in enumerate(avals)]
    op.key = (fn_key,
              tuple(("kw", k) for k, _ in kwarg_spec),
              len(avals), out_is_tuple, amb_key)
    _seg.ops.append(op)
    _stats["ops_bulked"] += 1
    outs = list(op.outs)  # before a limit-flush clears op.outs
    if len(_seg.ops) >= _seg.limit:
        flush()
    return outs, out_is_tuple


def note_eager_fallback():
    _stats["eager_fallbacks"] += 1


# ---------------------------------------------------------------------------
# flush listeners: segment-boundary observability
#
# A listener is called (with the number of ops the segment held) after each
# successful flush.  Consumers: kvstore/bucketing.py counts the segment
# boundaries a bucketed step produces (the bucket launches ARE the intended
# boundaries on dist stores — a per-param fallback would show up as many
# more), and tests assert the single-program property of the in-process
# bucket path.  Listeners must be cheap and must not record ops.
# ---------------------------------------------------------------------------
_flush_listeners = []


def add_flush_listener(fn):
    _flush_listeners.append(fn)
    return fn


def remove_flush_listener(fn):
    try:
        _flush_listeners.remove(fn)
    except ValueError:
        pass


# ---------------------------------------------------------------------------
# flush: compile + run the pending segment
# ---------------------------------------------------------------------------
def flush():
    """Materialize every pending op in the current segment with one compiled
    executable (structure-cached)."""
    ops = _seg.ops
    if not ops:
        return
    _seg.ops = []
    _seg.flushing = True
    try:
        _flush_ops(ops)
    except Exception as e:
        for op in ops:
            for o in op.outs:
                if o.value is None:
                    o.error = e
        raise
    finally:
        _seg.flushing = False


def _flush_ops(ops):
    _stats["flushes"] += 1
    op_index_of = {id(op): i for i, op in enumerate(ops)}

    # leaves: dedup concrete inputs by buffer identity
    leaves = []
    leaf_slot = {}

    def slot_of(arr):
        s = leaf_slot.get(id(arr))
        if s is None:
            s = len(leaves)
            leaf_slot[id(arr)] = s
            leaves.append(arr)
        return s

    key_parts = []
    op_plans = []   # static plan per op: (fn, argplan, kwplan, cellplan, nout)
    for op in ops:
        argplan = []
        for spec in op.arg_spec:
            tag, v = spec
            if tag == "lazy":
                if v.value is not None:
                    argplan.append(("leaf", slot_of(v.value)))
                else:
                    argplan.append(("lazy", op_index_of[id(v.op)], v.idx))
            elif tag == "leaf":
                argplan.append(("leaf", slot_of(v)))
            else:
                argplan.append(("const", v))
        kwplan = []
        for k, spec in op.kwarg_spec:
            tag, v = spec
            if tag == "lazy":
                if v.value is not None:
                    kwplan.append((k, ("leaf", slot_of(v.value))))
                else:
                    kwplan.append((k, ("lazy", op_index_of[id(v.op)], v.idx)))
            elif tag == "leaf":
                kwplan.append((k, ("leaf", slot_of(v))))
            else:
                kwplan.append((k, ("const", v)))
        def plan_cells(spec):
            plan = []
            for entry in spec:
                if entry[0] == "leaf":
                    plan.append(("leaf", slot_of(entry[1])))
                elif entry[0] == "lazycell":
                    lz = entry[1]
                    if lz.value is not None:
                        plan.append(("leaf", slot_of(lz.value)))
                    else:
                        plan.append(("lazy", op_index_of[id(lz.op)], lz.idx))
                elif entry[0] == "fn":
                    plan.append(("fn", entry[1], plan_cells(entry[2])))
                else:
                    plan.append(("const", entry[1]))
            return tuple(plan)

        cellplan = None
        if op.cell_spec is not None:
            cellplan = plan_cells(op.cell_spec)
        # NOTE: output liveness (is any ndarray still holding this lazy?)
        # deliberately does NOT join the plan or the key — it depends on GC
        # timing, and a nondeterministic key would recompile the same
        # segment over and over.  Every op output is returned; dead ones
        # are freed as soon as their LazyArray goes out of scope.
        op_plans.append((op.fn, tuple(argplan), tuple(kwplan),
                         cellplan,
                         len(op.outs), op.out_is_tuple,
                         op.ambients))
        def plan_key(p):
            if p[0] == "leaf":
                return ("leaf",)
            if p[0] == "const":
                return ("const", _const_key(p[1]))  # raw value may be a list
            return p
        key_parts.append((
            op.key,
            tuple(plan_key(p) for p in argplan),
            tuple((k, plan_key(p)) for k, p in kwplan)))

    leaf_avals = tuple((a.shape, str(a.dtype)) for a in leaves)

    def cell_slots(plan):
        out = []
        for c in plan:
            if c[0] == "leaf":
                out.append(c[1])
            elif c[0] == "lazy":
                out.append(("lz", c[1], c[2]))
            elif c[0] == "fn":
                out.extend(cell_slots(c[2]))
        return out

    # leaf slots appear positionally inside argplans, so the structural key
    # must record WHICH slot each leaf reference uses
    slot_sig = tuple(
        tuple((p[1] if p[0] == "leaf" else -1) for p in plan[1]) +
        tuple((p[1][1] if p[1][0] == "leaf" else -1) for p in plan[2]) +
        (tuple(cell_slots(plan[3])) if plan[3] is not None else ())
        for plan in op_plans)
    cache_key = (tuple(key_parts), slot_sig, leaf_avals)

    entry = _cache.get(cache_key)
    if entry is None:
        _stats["compiles"] += 1

        def run(leaf_vals):
            results = []
            out_list = []
            for (fn, argplan, kwplan, cellplan, nout, is_tup,
                 ambients) in op_plans:
                def resolve(p):
                    if p[0] == "leaf":
                        return leaf_vals[p[1]]
                    if p[0] == "lazy":
                        r = results[p[1]]
                        return r[p[2]]
                    return p[1]
                f = fn
                if cellplan is not None:
                    f = _resolve_cell_spec(fn, cellplan, resolve)
                with _AmbientScope(ambients):
                    out = f(*[resolve(p) for p in argplan],
                            **{k: resolve(p) for k, p in kwplan})
                outs = list(out) if is_tup else [out]
                results.append(outs)
                out_list.extend(outs)
            return out_list

        entry = jax.jit(run)
        if len(_cache) > 2048:
            # safety valve: cache keys hold callables (incl. bound-method
            # receivers), so unbounded growth would pin every model a
            # long-lived process ever created; a rare full clear only costs
            # recompiles
            _cache.clear()
        _cache[cache_key] = entry

    out_vals = entry(leaves)
    it = iter(out_vals)
    from .ndarray import _track
    for op in ops:
        for o in op.outs:
            o.value = next(it)
            o.op = None   # break the ref chain: a live LazyArray must not
            o.idx = -1    # pin its op's input buffers after materialization
        op.arg_spec = op.kwarg_spec = op.cell_spec = None
        op.outs = ()
    # one tracked buffer per flush suffices for waitall() completeness:
    # all outputs ride the same executable, so observing the last output
    # ready implies the whole segment ran (single-program semantics)
    if out_vals:
        _track(out_vals[-1])
    for fn in list(_flush_listeners):
        fn(len(ops))


def materialize(lazy):
    if lazy.value is None:
        if lazy.error is not None:
            raise lazy.error
        flush()
        if lazy.value is None:
            if lazy.error is not None:
                raise lazy.error
            raise RuntimeError("lazy array did not materialize in flush")
    return lazy.value
