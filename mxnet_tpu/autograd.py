"""Imperative autograd: record scopes + gradient tape + backward.

Parity: reference `python/mxnet/autograd.py` (record :121 / pause :145 /
backward :245) and the C++ tape in `src/imperative/imperative.cc`
(`Imperative::RecordOp` :204, `Imperative::Backward` :387).

TPU-native design: instead of replaying an nnvm gradient graph through an
engine interpreter, every recorded op captures a JAX VJP closure at execution
time (`jax.vjp` linearises the op while XLA runs the forward).  `backward()`
walks the tape in reverse topological order calling those closures — the
whole thing stays on-device and async (PJRT futures), which is the moral
equivalent of the reference pushing backward kernels to the threaded engine.
"""
from __future__ import annotations

import threading
from collections import deque

import numpy as onp

import jax
import jax.numpy as jnp

_STATE = threading.local()


def _float_kind(dt):
    """True for dtypes that carry gradients.  numpy's `kind` alone misses
    the ml_dtypes extension floats (bfloat16/float8 report kind 'V'), so
    bf16 tape nodes would be fed float0 cotangents and crash the vjp."""
    dt = onp.dtype(dt)
    return dt.kind in "fc" or jnp.issubdtype(dt, jnp.inexact)


def _st():
    if not hasattr(_STATE, "recording"):
        _STATE.recording = False
        _STATE.training = False
    return _STATE


def is_recording():
    return _st().recording


def is_training():
    return _st().training


def set_recording(is_record):
    st = _st()
    prev = st.recording
    st.recording = bool(is_record)
    return prev


def set_training(train_mode):
    st = _st()
    prev = st.training
    st.training = bool(train_mode)
    return prev


# ---------------------------------------------------------------------------
# grad-ready completion hooks
#
# The bucketed-communication layer (kvstore/bucketing.py) needs to know the
# moment a leaf's gradient is FINAL — its last tape contribution accumulated
# — while the rest of the backward walk is still running, so a gradient
# bucket can launch its fused pushpull overlapping the remaining backward
# (the reference engine's priority-ordered push pipeline,
# python/mxnet/gluon/trainer.py:395-407; PyTorch DDP's autograd hooks).
# backward() counts, per marked leaf, how many reachable tape nodes still
# reference it; when the count drains to zero the leaf's grad is written
# immediately (instead of at the end of the walk) and its hooks fire.
# ---------------------------------------------------------------------------
_GRAD_READY_HOOKS = {}  # id(arr) -> (weakref(arr), [callbacks])


def register_grad_ready_hook(arr, fn):
    """Call ``fn(arr)`` each time a backward pass finalizes ``arr``'s
    gradient (written to ``arr.grad`` per its grad_req).  Fires at most
    once per backward per leaf, as early as the tape walk allows.  Returns
    a handle for :func:`remove_grad_ready_hook`.  Exceptions raised by a
    hook propagate out of ``backward()``."""
    import weakref
    key = id(arr)
    entry = _GRAD_READY_HOOKS.get(key)
    if entry is None or entry[0]() is not arr:
        # weakref cleanup: a dead leaf must not pin its slot (and a
        # recycled id() must not inherit a stale hook list)
        ref = weakref.ref(
            arr, lambda _r, k=key: _GRAD_READY_HOOKS.pop(k, None))
        entry = (ref, [])
        _GRAD_READY_HOOKS[key] = entry
    entry[1].append(fn)
    return (key, fn)


def remove_grad_ready_hook(handle):
    key, fn = handle
    entry = _GRAD_READY_HOOKS.get(key)
    if entry is not None:
        try:
            entry[1].remove(fn)
        except ValueError:
            pass
        if not entry[1]:
            _GRAD_READY_HOOKS.pop(key, None)


def _fire_grad_ready(arr):
    entry = _GRAD_READY_HOOKS.get(id(arr))
    if entry is not None and entry[0]() is arr:
        for fn in list(entry[1]):
            fn(arr)


class _RecordingStateScope:
    """Scope manager flipping (recording, training) like the reference's
    `_RecordingStateScope` (python/mxnet/autograd.py:33)."""

    def __init__(self, is_record, train_mode):
        self._rec = is_record
        self._train = train_mode
        self._prev = None

    def __enter__(self):
        st = _st()
        self._prev = (st.recording, st.training)
        if self._rec is not None:
            st.recording = self._rec
        if self._train is not None:
            st.training = self._train
        return self

    def __exit__(self, *exc):
        st = _st()
        st.recording, st.training = self._prev
        return False


def record(train_mode=True):
    """autograd.record(): enter recording + training scope."""
    return _RecordingStateScope(True, train_mode)


def pause(train_mode=False):
    return _RecordingStateScope(False, train_mode)


def train_mode():
    return _RecordingStateScope(None, True)


def predict_mode():
    return _RecordingStateScope(None, False)


class TapeNode:
    """One recorded op: a VJP closure + its input arrays.

    Reference analog: an nnvm node appended by Imperative::RecordOp with its
    FGradient; here the "gradient function" is the jax.vjp closure which
    already holds the linearisation residuals on device.
    """

    __slots__ = ("vjp_fn", "inputs", "n_outputs", "out_shapes", "out_dtypes",
                 "out_is_tuple", "fn", "in_bufs")

    def __init__(self, vjp_fn, inputs, n_outputs, out_shapes, out_dtypes,
                 out_is_tuple=None, fn=None, in_bufs=None):
        self.vjp_fn = vjp_fn
        self.inputs = inputs  # list of ndarray (kept alive while tape lives)
        # record-time input buffers for deferred-VJP replay: the replay must
        # recompute the forward from the values the op actually SAW, not
        # whatever the ndarray wrapper holds at backward time (an in-place
        # x[:]= mutation between forward and backward would otherwise
        # silently poison the gradient — reference kWriteInplace semantics)
        self.in_bufs = in_bufs
        self.n_outputs = n_outputs
        self.out_shapes = out_shapes
        self.out_dtypes = out_dtypes
        # the differentiated fn's output pytree was a tuple (even if len 1)
        self.out_is_tuple = (n_outputs > 1 if out_is_tuple is None
                             else out_is_tuple)
        # primal closure kept for create_graph replay (higher-order grad:
        # reference test_higher_order_grad.py; MXGradient on the grad graph)
        self.fn = fn


def _make_replay(node_fn, out_shapes, out_dtypes, out_is_tuple, n_in,
                 in_float):
    """Build the VJP-replay closure for one tape node: recomputes the
    forward under jax.vjp and applies the cotangents (float outputs get the
    provided cts, integer outputs float0 zeros).  Returns only the grads of
    float-dtype inputs (`in_float` mask): integer-input grads are float0,
    which cannot ride through a bulked segment — the caller re-slots the
    outputs by the same static mask."""
    def replay(*vals):
        prim = vals[:n_in]
        cts_in = list(vals[n_in:])
        cts = []
        for shape, dt in zip(out_shapes, out_dtypes):
            if _float_kind(dt):
                cts.append(cts_in.pop(0))
            else:
                cts.append(onp.zeros(shape, jax.dtypes.float0))
        ct = tuple(cts) if out_is_tuple else cts[0]
        grads = jax.vjp(node_fn, *prim)[1](ct)
        return tuple(g for g, f in zip(grads, in_float) if f)
    return replay


_filled_cache = {}  # (shape, dtype, fill) -> device buffer
_filled_cache_bytes = 0
_FILLED_BUDGET = 64 << 20  # HBM pinned by cached constants, not entry count


def _filled(shape, dtype, fill):
    """Cached constant buffer (zero cotangents, ones seeds).

    jnp.zeros is an EAGER dispatch; a hybridized ResNet-50's forward node
    has ~106 BatchNorm-aux outputs, each needing a zero cotangent every
    backward — uncached that is ~106 eager dispatches per step.
    jax.Arrays are immutable, so sharing one
    buffer per (shape, dtype) is safe, and the stable buffer id also
    dedups into one bulk-segment leaf slot.  The eviction valve is
    byte-budgeted: counting entries would let a few activation-sized
    cotangents pin GBs of HBM."""
    global _filled_cache_bytes
    dt = onp.dtype(dtype)
    k = (tuple(shape), dt.str, fill)
    v = _filled_cache.get(k)
    if v is None:
        nbytes = int(onp.prod(shape)) * dt.itemsize if shape else dt.itemsize
        if _filled_cache_bytes + nbytes > _FILLED_BUDGET:
            _filled_cache.clear()
            _filled_cache_bytes = 0
        v = jnp.full(shape, fill, dt)
        _filled_cache[k] = v
        _filled_cache_bytes += nbytes
    return v


def _zero_cotangent(shape, dtype):
    dt = onp.dtype(dtype)
    if _float_kind(dt):
        return _filled(shape, dt, 0)
    # integer/bool outputs take float0 cotangents in JAX
    return onp.zeros(shape, jax.dtypes.float0)


def _is_float0(x):
    d = getattr(x, "_buf", x)  # _buf: metadata peek, never materializes
    return getattr(d, "dtype", None) == jax.dtypes.float0


def backward(heads, head_grads=None, retain_graph=False, train_mode=True,
             create_graph=False):
    """Compute gradients of `heads` w.r.t. all attach_grad()-ed leaves.

    Parity: python/mxnet/autograd.py:245 `backward` →
    src/imperative/imperative.cc:387 `Imperative::Backward`.

    With create_graph=True (inside a record() scope), backward replays each
    node's primal closure through `apply_op` so the produced gradients are
    themselves recorded — enabling higher-order differentiation (reference:
    MXGradient pass applied to the gradient graph).
    """
    from .ndarray import ndarray  # local import to avoid cycle

    if isinstance(heads, ndarray):
        heads = [heads]
    if head_grads is None:
        head_grads = [None] * len(heads)
    elif isinstance(head_grads, ndarray):
        head_grads = [head_grads]

    # ---- collect reachable tape nodes (reverse graph walk) -------------
    nodes = []  # postorder
    seen = set()

    def visit(node):
        stack = [(node, False)]
        while stack:
            n, processed = stack.pop()
            if processed:
                nodes.append(n)
                continue
            if id(n) in seen:
                continue
            seen.add(id(n))
            stack.append((n, True))
            for inp in n.inputs:
                if inp._node is not None and id(inp._node) not in seen:
                    stack.append((inp._node, False))

    for h in heads:
        if h._node is not None:
            visit(h._node)

    # cotangent accumulators keyed by node id
    cots = {id(n): [None] * n.n_outputs for n in nodes}
    leaf_grads = {}  # id(arr) -> grad (jnp value, or ndarray in replay mode)

    def _add_grads(a, b):
        from .ndarray import _wrap_value as _w
        if isinstance(a, ndarray) or isinstance(b, ndarray):
            aw = a if isinstance(a, ndarray) else _w(a)
            bw = b if isinstance(b, ndarray) else _w(b)
            return aw + bw
        return a + b

    def _accum_leaf(arr, g):
        if _is_float0(g):
            return
        prev = leaf_grads.get(id(arr))
        leaf_grads[id(arr)] = g if prev is None else _add_grads(prev, g)
        leaf_grads.setdefault(("arr", id(arr)), arr)

    # seed heads
    any_node = False
    for h, hg in zip(heads, head_grads):
        seed = (
            _filled(h.shape, h.dtype, 1)
            if hg is None
            else (hg._data if isinstance(hg, ndarray) else jnp.asarray(hg))
        )
        if h._node is None:
            if h._marked:
                _accum_leaf(h, seed)
            continue
        any_node = True
        slot = cots[id(h._node)]
        g = slot[h._out_index]
        slot[h._out_index] = seed if g is None else g + seed

    if not any_node and not leaf_grads:
        raise ValueError(
            "cannot differentiate: outputs are not connected to any "
            "recorded computation (did you forget autograd.record()?)"
        )

    # ---- reverse topological execution ---------------------------------
    from .ndarray import apply_op, _wrap_value as _wrap

    replay_mode = create_graph and is_recording()

    # ---- per-leaf completion tracking (grad-ready hooks) ----------------
    # remaining reachable-node references per marked leaf: when a leaf's
    # count drains to zero mid-walk, its gradient is final — write it and
    # fire hooks NOW so bucketed comm can launch overlapping the rest of
    # the backward.  Only paid when hooks are registered.
    hooks_live = bool(_GRAD_READY_HOOKS)
    finalized = set()
    pending_refs = {}
    if hooks_live:
        for n in nodes:
            for inp in n.inputs:
                if inp._node is None and inp._marked:
                    pending_refs[id(inp)] = pending_refs.get(id(inp), 0) + 1

    def _write_leaf_grad(arr, g):
        """Write one finalized leaf gradient per its grad_req (the logic
        previously inline in the tail loop).  Returns True if written."""
        req = arr._grad_req
        if req == "null":
            return False
        if isinstance(g, ndarray):
            if req == "add" and arr._grad is not None:
                g = _add_grads(arr._grad, g)
            if arr._grad is None:
                arr._grad = g
            else:
                # x.grad must remain the SAME ndarray attach_grad created
                # (reference writes grads INTO the attached buffer, so user
                # aliases stay live); transplant the value and the tape
                # node (the node carries the replay closure higher-order
                # differentiation needs)
                arr._grad._buf = g._buf
                arr._grad._node = g._node
                arr._grad._out_index = g._out_index
        elif req == "add" and arr._grad is not None:
            arr._grad._data = arr._grad._data + g
        else:
            if arr._grad is None:
                arr._grad = _wrap(g)
            else:
                arr._grad._data = g
        return True

    def _finalize_leaf(arr):
        if id(arr) in finalized:
            return
        g = leaf_grads.get(id(arr))
        if g is None:
            return  # leaf never received a gradient this backward
        finalized.add(id(arr))
        if _write_leaf_grad(arr, g):
            _fire_grad_ready(arr)

    for n in reversed(nodes):
        slot = cots[id(n)]
        if all(g is None for g in slot):
            if hooks_live:
                # a dead node still releases its references: its inputs'
                # grads cannot change any more through this node
                for inp in n.inputs:
                    if inp._node is None and inp._marked:
                        c = pending_refs.get(id(inp), 1) - 1
                        pending_refs[id(inp)] = c
                        if c <= 0:
                            _finalize_leaf(inp)
            continue
        full = []
        for i, g in enumerate(slot):
            if g is None:
                g = _zero_cotangent(n.out_shapes[i], n.out_dtypes[i])
            full.append(g)
        # replay is used when recording higher-order grads (create_graph)
        # AND for bulk-recorded nodes whose VJP was deferred (vjp_fn=None):
        # the backward computation then records into the bulk segment too,
        # so one compiled program covers the whole fwd+bwd step
        if n.fn is not None and (replay_mode or n.vjp_fn is None):
            # recorded replay: grads connect to the tape through n.inputs
            float_cts = []
            for g, dt in zip(full, n.out_dtypes):
                if _float_kind(dt):
                    float_cts.append(g if isinstance(g, ndarray) else _wrap(g))
            # factory, NOT an inline def: execution is deferred to the bulk
            # flush, so the closure must own its per-node cells (an inline
            # def would share `backward`'s loop-rebound locals)
            in_float = tuple(_float_kind(i.dtype)
                             for i in n.inputs)
            replay = _make_replay(n.fn, n.out_shapes, n.out_dtypes,
                                  n.out_is_tuple, len(n.inputs), in_float)

            if replay_mode:
                # higher-order: inputs must stay ndarrays so the replay's
                # grads connect back through the tape
                flt_grads = apply_op(replay, *(list(n.inputs) + float_cts))
            else:
                # deferred VJP: replay from the RECORD-TIME buffers, not
                # the live wrappers (see TapeNode.in_bufs)
                ins = (list(n.in_bufs) if n.in_bufs is not None
                       else [i._buf for i in n.inputs])
                with pause():
                    flt_grads = apply_op(replay, *(ins + float_cts))
            if not isinstance(flt_grads, (list, tuple)):
                flt_grads = [flt_grads]
            # re-slot by the static mask: int/bool inputs take no gradient
            flt_iter = iter(flt_grads)
            in_grads = [next(flt_iter) if f else None for f in in_float]
        else:
            raw = [g._data if isinstance(g, ndarray) else g for g in full]
            ct = tuple(raw) if n.out_is_tuple else raw[0]
            in_grads = n.vjp_fn(ct)
        for inp, g in zip(n.inputs, in_grads):
            if g is None or _is_float0(g):
                continue
            if inp._node is not None:
                islot = cots.get(id(inp._node))
                if islot is not None:
                    prev = islot[inp._out_index]
                    islot[inp._out_index] = (g if prev is None
                                             else _add_grads(prev, g))
            elif inp._marked:
                _accum_leaf(inp, g)
        if not retain_graph and not replay_mode:
            n.vjp_fn = None  # free residuals eagerly
            n.fn = None      # deferred-VJP nodes: drop the replay closure too
        if hooks_live:
            # this node's contributions (if any) are accumulated above, so
            # releasing its references AFTER the accumulation is what makes
            # a zero count mean "final"
            for inp in n.inputs:
                if inp._node is None and inp._marked:
                    c = pending_refs.get(id(inp), 1) - 1
                    pending_refs[id(inp)] = c
                    if c <= 0:
                        _finalize_leaf(inp)

    # ---- write results into .grad per grad_req --------------------------
    # (leaves already finalized mid-walk by the hook machinery are skipped;
    # head-seeded leaves with no tape references land here)
    for key, g in list(leaf_grads.items()):
        if isinstance(key, tuple):
            continue
        arr = leaf_grads[("arr", key)]
        if id(arr) in finalized:
            continue
        finalized.add(id(arr))
        if _write_leaf_grad(arr, g):
            _fire_grad_ready(arr)

    if not retain_graph:
        for h in heads:
            h._node = None

    # bulk boundary policy: by default the backward segment stays OPEN so
    # the optimizer update that typically follows records into the SAME
    # program — one dispatch for bwd+update instead of two (a dispatch
    # costs host time on any backend; trainer.step flushes at its end,
    # and any host fetch flushes too, so correctness never depends on
    # this boundary).  MXNET_EXEC_BULK_FUSE_BACKWARD_UPDATE=0 restores
    # the eager flush — use it if the merged program's live set (fwd
    # residuals + both param copies) presses HBM on very large models.
    import os as _os
    if _os.environ.get("MXNET_EXEC_BULK_FUSE_BACKWARD_UPDATE",
                       "1") == "0":
        from . import _bulk
        _bulk.flush()


def grad(heads, variables, head_grads=None, retain_graph=None,
         create_graph=False, train_mode=True):
    """Return gradients of heads w.r.t. variables (python/mxnet/autograd.py:grad).

    create_graph=True (inside a record() scope) records the backward replay
    so returned grads support further differentiation (Hessian-vector
    products etc. — reference test_higher_order_grad.py).
    """
    from .ndarray import ndarray, _wrap_value

    single = isinstance(variables, ndarray)
    if single:
        variables = [variables]
    saved = [(v._grad, v._grad_req, v._marked) for v in variables]
    for v in variables:
        v._marked = True
        v._grad = None
        v._grad_req = "write"
    try:
        backward(heads, head_grads, retain_graph=bool(retain_graph) or create_graph,
                 train_mode=train_mode, create_graph=create_graph)
        out = []
        for v in variables:
            if v._grad is None:
                out.append(_wrap_value(jnp.zeros(v.shape, v.dtype)))
            else:
                out.append(v._grad)
    finally:
        for v, (g, req, m) in zip(variables, saved):
            v._grad, v._grad_req, v._marked = g, req, m
    return out[0] if single else out


def mark_variables(variables, gradients, grad_reqs="write"):
    """Parity: MXAutogradMarkVariables."""
    if isinstance(grad_reqs, str):
        grad_reqs = [grad_reqs] * len(variables)
    for v, g, req in zip(variables, gradients, grad_reqs):
        v._marked = True
        v._grad = g
        v._grad_req = req


class Function:
    """Custom differentiable function (python/mxnet/autograd.py:369).

    Subclass and implement forward(self, *inputs) and backward(self, *ograds).
    """

    def __init__(self):
        self._inputs = None

    def __call__(self, *inputs):
        from .ndarray import ndarray, _wrap_value
        self._inputs = inputs
        with pause():
            outputs = self.forward(*inputs)
        single = not isinstance(outputs, (list, tuple))
        outs = [outputs] if single else list(outputs)
        if is_recording():
            fn = self

            def vjp_fn(cts):
                if single:
                    cts = (cts,)
                with pause():
                    igrads = fn.backward(*[_wrap_value(c) for c in cts])
                if not isinstance(igrads, (list, tuple)):
                    igrads = (igrads,)
                return tuple(g._data for g in igrads)

            node = TapeNode(
                vjp_fn,
                [x for x in inputs if isinstance(x, ndarray)],
                len(outs),
                [o.shape for o in outs],
                [o.dtype for o in outs],
            )
            for i, o in enumerate(outs):
                o._node = node
                o._out_index = i
        return outs[0] if single else outs

    def forward(self, *inputs):
        raise NotImplementedError

    def backward(self, *output_grads):
        raise NotImplementedError


def get_symbol(x):  # reference API parity; tracing introspection not supported
    return None
