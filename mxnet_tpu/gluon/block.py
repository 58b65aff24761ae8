"""gluon.Block / HybridBlock (parity: python/mxnet/gluon/block.py).

Block (:203) is the eager container; HybridBlock (:998) adds `hybridize()`:
the reference traces `forward` via deferred-compute into an nnvm Symbol and
executes it with CachedOp (static/dynamic executors, memory planning,
fusion).

TPU-native: `hybridize()` traces the same Python `forward` with jax.jit —
the whole graph becomes ONE XLA executable (layout assignment, fusion,
rematerialization subsume CachedOp's MXPlanMemory/CSE/pointwise-fusion
passes).  Parameters enter as traced arguments; mutable aux state
(BatchNorm running stats) is captured as extra outputs and written back
after each call, preserving the reference's side-effecting op semantics.
Autograd through a hybridized call records a single tape node whose VJP is
the compiled backward program (pjit transpose), matching CachedOp::Backward.
"""
from __future__ import annotations

import re
from collections import OrderedDict

import numpy as onp

import jax
import jax.numpy as jnp

from .. import autograd
from .._rng import next_key, trace_keys
from ..context import Context, current_context
from ..ndarray import ndarray, _wrap_value, apply_op
from .parameter import Parameter, DeferredInitializationError

_KEYLESS = {}


def _keyless_dummy():
    """Constant key fed to cached graphs that consume no randomness: the
    jitted fn still takes the key argument, but a stable unused constant
    costs nothing, while next_key()'s fold_in is an eager device
    dispatch."""
    k = _KEYLESS.get("k")
    if k is None:
        # must be CONCRETE even when first requested under an ambient
        # trace (nested hybridized block): a traced key cached here would
        # leak the tracer into later calls
        with jax.ensure_compile_time_eval():
            k = jax.random.key(0)
        _KEYLESS["k"] = k
    return k

__all__ = ["Block", "HybridBlock", "SymbolBlock"]


def _sharding_token():
    """Trace-cache token for the ACTIVE ShardingConfig (None when the
    parallel package was never imported or no config scope is open).
    sys.modules guard: layers pay nothing in unsharded processes."""
    import sys
    sc = sys.modules.get("mxnet_tpu.parallel.shardcfg")
    return sc.active_token() if sc is not None else None


def _maybe_constrain(x, kind):
    """Sharding constraint at a named activation point under the ACTIVE
    ShardingConfig; identity otherwise.  Layers call this at their
    constraint points (Dense output, BERT q/k/v, FFN/token streams)."""
    import sys
    sc = sys.modules.get("mxnet_tpu.parallel.shardcfg")
    if sc is None:
        return x
    return sc.maybe_constrain_nd(x, kind)


def _flatten_arrays(obj, out):
    if isinstance(obj, ndarray):
        out.append(obj)
    elif isinstance(obj, (list, tuple)):
        for o in obj:
            _flatten_arrays(o, out)
    elif isinstance(obj, dict):
        for o in obj.values():
            _flatten_arrays(o, out)


class _BlockScope:
    pass


class _OpHookHandle:
    """Detaches a register_op_hook group in one call."""

    def __init__(self, handles, blocks):
        self._handles = handles
        self._blocks = blocks

    def detach(self):
        for h in self._handles:
            h.detach()
        self._handles = []
        for b in self._blocks:
            b._op_hooks_active = max(
                getattr(b, "_op_hooks_active", 1) - 1, 0)
        self._blocks = []

    def __iter__(self):  # back-compat with list-returning callers
        return iter(self._handles)


class Block:
    """Base container (reference block.py:203)."""

    def __init__(self):
        self._children = OrderedDict()
        self._reg_params = OrderedDict()
        self._forward_hooks = OrderedDict()
        self._forward_pre_hooks = OrderedDict()
        self._hook_id = 0

    # -- attribute registration ------------------------------------------
    def __setattr__(self, name, value):
        if isinstance(value, Block):
            existing = self.__dict__.get("_children")
            if existing is not None:
                existing[name] = value
        elif isinstance(value, Parameter):
            existing = self.__dict__.get("_reg_params")
            if existing is not None:
                existing[name] = value
        super().__setattr__(name, value)

    def register_child(self, block, name=None):
        if name is None:
            name = str(len(self._children))
        self._children[name] = block

    # -- parameter collection --------------------------------------------
    def collect_params(self, select=None):
        """Return {structural_name: Parameter} (reference collect_params).

        Names are attribute paths like 'features.0.weight'."""
        out = OrderedDict()

        def walk(block, prefix):
            for pname, p in block._reg_params.items():
                full = prefix + pname if not prefix else prefix + "." + pname
                p._structure_name = full if prefix else pname
                out[p._structure_name] = p
            for cname, child in block._children.items():
                walk(child, (prefix + "." + cname) if prefix else cname)

        walk(self, "")
        if select is not None:
            pat = re.compile(select)
            out = OrderedDict((k, v) for k, v in out.items() if pat.match(k))
        return out

    @property
    def params(self):
        return self.collect_params()

    # -- initialization ---------------------------------------------------
    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False, device=None):
        from .. import initializer as _initmod
        init = init or _initmod.Uniform()
        for name, p in self.collect_params().items():
            p.initialize(init=p.init, ctx=ctx or device, default_init=init,
                         force_reinit=force_reinit)

    def setattr(self, name, value):
        for p in self.collect_params().values():
            setattr(p, name, value)

    def cast(self, dtype):
        for p in self.collect_params().values():
            p.cast(dtype)
        for child in self._children.values():
            pass  # params already collected recursively
        self._on_cast(dtype)
        return self

    def _on_cast(self, dtype):
        for c in self._children.values():
            c._on_cast(dtype)

    def reset_ctx(self, ctx):
        for p in self.collect_params().values():
            p.reset_ctx(ctx)

    reset_device = reset_ctx

    def zero_grad(self):
        for p in self.collect_params().values():
            p.zero_grad()

    # -- hooks -------------------------------------------------------------
    def register_forward_hook(self, hook):
        self._hook_id += 1
        self._forward_hooks[self._hook_id] = hook
        return _HookHandle(self._forward_hooks, self._hook_id)

    def register_forward_pre_hook(self, hook):
        self._hook_id += 1
        self._forward_pre_hooks[self._hook_id] = hook
        return _HookHandle(self._forward_pre_hooks, self._hook_id)

    def register_op_hook(self, callback, monitor_all=False):
        """Monitor child-block outputs (and inputs with monitor_all)
        during forward (parity: block.py:869 register_op_hook → CachedOp
        _register_op_hook; here the monitored unit is the child block —
        the graph node granularity of this framework).

        callback(name, opr_name, array) is called eagerly per forward.
        While hooks are attached, hybridized blocks run the eager path so
        every call reaches the callbacks with concrete arrays (the
        reference's CachedOp monitors compiled-graph tensors via engine
        callbacks; here the compiled graph has no per-op host callbacks,
        so monitoring implies eager).  Attach the hook on the OUTERMOST
        block you call — hooking only an inner child of a compiled parent
        cannot bypass the parent's cached graph.  Returns one handle;
        detach() it to restore compiled execution.
        """
        handles = []
        blocks = []

        def attach(blk, path):
            def fwd_hook(b, inputs, output, _path=path):
                outs = output if isinstance(output, (list, tuple)) \
                    else [output]
                for i, o in enumerate(outs):
                    if o is not None and hasattr(o, "shape"):
                        callback("%s_output%d" % (_path, i),
                                 type(b).__name__, o)
                if monitor_all:
                    for i, a in enumerate(inputs):
                        if hasattr(a, "shape"):
                            callback("%s_input%d" % (_path, i),
                                     type(b).__name__, a)
            handles.append(blk.register_forward_hook(fwd_hook))
            blk._op_hooks_active = getattr(blk, "_op_hooks_active", 0) + 1
            blocks.append(blk)
            for cname, child in blk._children.items():
                attach(child, "%s.%s" % (path, cname) if path else cname)

        attach(self, "")
        return _OpHookHandle(handles, blocks)

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    # -- call --------------------------------------------------------------
    def __call__(self, *args, **kwargs):
        for hook in self._forward_pre_hooks.values():
            hook(self, args)
        out = self.forward(*args, **kwargs)
        for hook in self._forward_hooks.values():
            hook(self, args, out)
        return out

    def forward(self, *args, **kwargs):
        raise NotImplementedError

    # -- serialization -----------------------------------------------------
    def save_parameters(self, filename, deduplicate=False):
        """Save params as .npz (reference block.py:341 → npx.savez/cnpy)."""
        params = self.collect_params()
        arrays = {}
        for name, p in params.items():
            if p._data is not None:
                arrays[name] = p.data().asnumpy()
        # write to the exact filename (reference uses .params; bare
        # onp.savez would append .npz)
        with open(filename, "wb") as f:
            onp.savez(f, **arrays)

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False,
                        dtype_source="current", device=None):
        loaded = dict(onp.load(filename))
        params = self.collect_params()
        for name, p in params.items():
            key = name if name in loaded else name + ":0"
            if key not in loaded:
                if not allow_missing:
                    raise ValueError("Parameter %s missing in file %s"
                                     % (name, filename))
                continue
            arr = loaded[key]
            p.set_data(_wrap_value(jnp.asarray(arr)))
        if not ignore_extra:
            extra = set(loaded) - set(params)
            if extra:
                raise ValueError("file %s has extra parameters %s"
                                 % (filename, sorted(extra)))

    def save(self, prefix):
        self.save_parameters(prefix + "-model.params.npz")

    def load(self, prefix):
        self.load_parameters(prefix + "-model.params.npz")

    def hybridize(self, active=True, **kwargs):
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    def summary(self, *inputs):
        """Print per-layer summary (reference block.summary)."""
        rows = []

        def hook(block, _, out):
            outs = []
            _flatten_arrays(out, outs)
            rows.append((type(block).__name__,
                         [o.shape for o in outs],
                         sum(int(onp.prod(p.shape)) for p in
                             block._reg_params.values() if p.shape)))

        handles = []

        def attach(b):
            handles.append(b.register_forward_hook(hook))

        self.apply(attach)
        try:
            self(*inputs)
        finally:
            for h in handles:
                h.detach()
        total = sum(int(onp.prod(p.shape)) for p in
                    self.collect_params().values() if p.shape)
        print("%-30s %-30s %s" % ("Layer", "Output shapes", "Params"))
        for name, shapes, n in rows:
            print("%-30s %-30s %d" % (name, shapes, n))
        print("Total params: %d" % total)

    def __repr__(self):
        lines = [self.__class__.__name__ + "("]
        for name, child in self._children.items():
            c = repr(child).replace("\n", "\n  ")
            lines.append("  (%s): %s" % (name, c))
        lines.append(")")
        return "\n".join(lines)


class _HookHandle:
    def __init__(self, hooks, hid):
        self._hooks = hooks
        self._id = hid

    def detach(self):
        self._hooks.pop(self._id, None)


class HybridBlock(Block):
    """Block with hybridize(): forward traces into one XLA executable
    (reference block.py:998, CachedOp execution path)."""

    def __init__(self):
        super().__init__()
        self._active = False
        self._cached_graphs = {}
        self._flags = {}

    def hybridize(self, active=True, static_alloc=False, static_shape=False,
                  **kwargs):
        self._active = active
        self._flags = dict(static_alloc=static_alloc,
                           static_shape=static_shape, **kwargs)
        self._cached_graphs = {}
        super().hybridize(active, static_alloc=static_alloc,
                          static_shape=static_shape, **kwargs)

    def optimize_for(self, x, *args, backend=None, clear=True, **kwargs):
        """Parity: block.py:1312 optimize_for — backend partitioning via
        the subgraph-backend registry (mxnet_tpu.subgraph).  Default
        backend is XLA whole-graph compilation; backends like INT8 may
        rewrite children (the BuildSubgraph analog)."""
        from ..subgraph import get_backend
        be = get_backend(backend if backend is not None else "XLA")
        if clear:
            # clear BEFORE the backend runs so its warm-up compile is the
            # one that's kept
            self._cached_graphs = {}
        ret = be.optimize(self, x, *args, **kwargs)
        if ret is not None and ret is not self:
            raise ValueError(
                "subgraph backend %r returned a new block; backends must "
                "rewrite the block in place (the MXOptimizeForBackend "
                "contract)" % (backend,))
        if not self._active:
            self.hybridize(True)
        self(x, *args)  # cache hit if the backend already warmed

    def infer_shape(self, *args):
        """Layers override to finalize deferred parameter shapes."""
        pass

    def _has_uninitialized_params(self):
        return any(p._data is None for p in self.collect_params().values())

    # -- the cached-graph machinery ---------------------------------------
    def _signature(self, flat_inputs):
        training = autograd.is_training()
        from ..ops import nn as _ops_nn
        from ..ops.pallas.epilogue import fuse_epilogue_enabled
        from ..ops.pallas.fused_cell import rnn_mode
        amp = _ops_nn._amp_state()  # amp scope traces its own graph
        amp_key = (str(amp[0]), amp[1]) if amp is not None else None
        # the epilogue-fusion and fused-cell gates change the traced
        # graph (Dense/BERT fused fast paths; the LSTM persistent
        # kernel): flipping MXNET_FUSE_EPILOGUE / MXNET_RNN_FUSED_CELL
        # must retrace, not reuse a stale cache; likewise an ACTIVE
        # ShardingConfig inserts sharding constraints into the graph
        return (tuple((a.shape, str(a.dtype)) for a in flat_inputs),
                training, amp_key, fuse_epilogue_enabled(), rnn_mode(),
                _sharding_token())

    def _build_cache(self, args, kwargs, flat_inputs):
        """Trace forward into a jitted pure function.

        pure(param_vals, input_vals, key) -> (flat_outputs..., aux_updates...)
        Reference analog: _build_cache (block.py:1135) deferred-compute
        trace → Symbol → CachedOp.
        """
        params = self.collect_params()
        live = OrderedDict((name, p) for name, p in params.items()
                           if p._data is not None)
        pnames = list(live)
        outer_training = autograd.is_training()

        tree_template = {}

        def pure(pvals, ivals, key):
            saved = [(p, p._data) for p in live.values()]
            try:
                wrappers = []
                for name, v in zip(pnames, pvals):
                    w = _wrap_value(v)
                    live[name]._data = w
                    wrappers.append((name, w, v))
                # rebuild the input pytree with traced values
                idx = [0]

                def rebuild(obj):
                    if isinstance(obj, ndarray):
                        v = _wrap_value(ivals[idx[0]])
                        idx[0] += 1
                        return v
                    if isinstance(obj, (list, tuple)):
                        return type(obj)(rebuild(o) for o in obj)
                    return obj

                targs = [rebuild(a) for a in args]
                tkwargs = {k: rebuild(v) for k, v in kwargs.items()}
                with trace_keys(key) as holder:
                    with autograd._RecordingStateScope(False, outer_training):
                        out = self.forward(*targs, **tkwargs)
                # how many keys the graph consumed: a keyless graph (all
                # inference nets) lets every later call skip the eager
                # next_key() fold_in — a full device round-trip per call
                tree_template["n_keys"] = holder["count"]
                flat_out = []
                _flatten_arrays(out, flat_out)
                tree_template["out"] = out
                # aux updates: params mutated during trace (BatchNorm
                # running stats) become extra graph outputs
                aux = []
                aux_names = []
                for name, w, v in wrappers:
                    if w._data is not v:
                        aux.append(w._data)
                        aux_names.append(name)
                tree_template["aux_names"] = aux_names
                tree_template["n_out"] = len(flat_out)
                return tuple(o._data for o in flat_out) + tuple(aux)
            finally:
                for p, old in saved:
                    p._data = old

        jitted = jax.jit(pure)
        return {"fn": jitted, "live": live, "pnames": pnames,
                "template": tree_template}

    def _call_cached(self, args, kwargs):
        flat_inputs = []
        _flatten_arrays(list(args) + list(kwargs.values()), flat_inputs)
        sig = self._signature(flat_inputs)
        cache = self._cached_graphs.get(sig)
        if cache is None:
            cache = self._build_cache(args, kwargs, flat_inputs)
            self._cached_graphs[sig] = cache
        live, pnames = cache["live"], cache["pnames"]
        fn = cache["fn"]
        pvals = [live[n]._data._data for n in pnames]
        ivals = [a._data for a in flat_inputs]
        # the key argument is only materialized when the traced graph
        # consumes randomness (n_keys unknown until the first call traces)
        if cache["template"].get("n_keys", 1):
            key = next_key()
        else:
            key = _keyless_dummy()

        diff_params = [live[n]._data for n in pnames]

        def run(*vals):
            np_ = len(pnames)
            return fn(list(vals[:np_]), list(vals[np_:]), key)

        results = apply_op(run, *(diff_params + flat_inputs))
        template = cache["template"]
        n_out = template["n_out"]
        flat_out = list(results[:n_out])
        aux_vals = results[n_out:]
        for name, v in zip(template["aux_names"], aux_vals):
            # write back through the RAW buffer: the `_data` property
            # materializes LazyArrays, which flushed the freshly-recorded
            # forward out of the bulk segment — paying one extra program
            # dispatch per hybridized call (BatchNorm nets: every call)
            live[name]._data._set_data(v._buf)

        # rebuild output structure
        idx = [0]

        def rebuild(obj):
            if isinstance(obj, ndarray):
                v = flat_out[idx[0]]
                idx[0] += 1
                return v
            if isinstance(obj, (list, tuple)):
                return type(obj)(rebuild(o) for o in obj)
            return obj

        return rebuild(template["out"])

    def __call__(self, *args, **kwargs):
        # remember the call signature so export() can re-trace without the
        # user passing example inputs (reference: export requires a prior
        # forward to have fixed the graph)
        flat = []
        _flatten_arrays(list(args) + list(kwargs.values()), flat)
        if flat:
            self._last_input_avals = [
                {"shape": list(a.shape), "dtype": str(a.dtype)} for a in flat]
        # first call with deferred params runs eagerly so each layer infers
        # its shapes (reference: deferred init at first forward); subsequent
        # calls hit the compiled cache.  Active op hooks force eager so
        # monitors see concrete arrays every call.
        if self._active and not self._has_uninitialized_params() \
                and not getattr(self, "_op_hooks_active", 0):
            for hook in self._forward_pre_hooks.values():
                hook(self, args)
            out = self._call_cached(args, kwargs)
            for hook in self._forward_hooks.values():
                hook(self, args, out)
            return out
        return super().__call__(*args, **kwargs)

    def export(self, path, epoch=0, remove_amp_cast=True):
        """Deployment export (reference block.py:1514): writes the
        `-symbol.json` (StableHLO program + signature, see symbol.py) and
        `-NNNN.params.npz` artifact pair.  The block must have been called
        at least once so the input signature is known."""
        if not getattr(self, "_last_input_avals", None):
            raise ValueError(
                "export requires the block to have been run at least once "
                "(reference: HybridBlock.export after a forward)")
        from ..symbol import trace_block
        sym = trace_block(self, self._last_input_avals, train=False)
        sym.save(path + "-symbol.json")
        params_file = "%s-%04d.params.npz" % (path, epoch)
        self.save_parameters(params_file)
        return path + "-symbol.json", params_file

    def to_sym(self, input_shapes=None, input_dtypes=None):
        """Symbolically trace this block into a composable mx.sym DAG +
        params dict — the (sym, params) pair the ONNX exporter and the
        reference's Gluon→Symbol conversion consume.

        The forward runs ONCE with mx.sym Variables in place of inputs
        and parameters (same rebinding trick as _build_cache); every
        np/npx call dispatches symbolically on them, so a block written
        against the eager array API traces unchanged.  Runs in predict
        mode: dropout is identity, BatchNorm uses running stats (what an
        exported inference graph means).  Returns (sym, params) with
        params: name -> ndarray (BatchNorm running stats marked aux)."""
        from .. import sym_api

        if input_shapes is None:
            if not getattr(self, "_last_input_avals", None):
                raise ValueError(
                    "to_sym needs input_shapes= or a prior forward call")
            input_shapes = [tuple(a["shape"])
                            for a in self._last_input_avals]
            input_dtypes = [a["dtype"] for a in self._last_input_avals]
        if input_shapes and not isinstance(input_shapes[0], (tuple, list)):
            input_shapes = [tuple(input_shapes)]
        if input_dtypes is None:
            input_dtypes = ["float32"] * len(input_shapes)

        params = OrderedDict(
            (name, p) for name, p in self.collect_params().items()
            if p._data is not None)
        saved = [(p, p._data) for p in params.values()]
        try:
            pvals = {}
            for name, p in params.items():
                v = p._data
                aux = p.grad_req == "null"  # running stats etc.
                p._data = sym_api.var(name, shape=tuple(v.shape),
                                      dtype=str(v.dtype), aux=aux)
                pvals[name] = v
            data_vars = [
                sym_api.var("data" if len(input_shapes) == 1
                            else "data%d" % i,
                            shape=tuple(s), dtype=str(d))
                for i, (s, d) in enumerate(zip(input_shapes, input_dtypes))]
            with autograd._RecordingStateScope(False, False):
                out = self.forward(*data_vars)
            if isinstance(out, (list, tuple)):
                out = sym_api.Group([o for o in out])
            return out, pvals
        finally:
            for p, old in saved:
                p._data = old


class SymbolBlock(HybridBlock):
    """Run an imported serialized graph (reference block.py:1716).

    forward() executes the deserialized StableHLO program — inference
    deployment path; gradients flow when the artifact was produced in
    this process, while a cold-loaded artifact is inference-only."""

    def __init__(self, symbol, params=None):
        super().__init__()
        self._symbol = symbol
        self._param_vals = params or {}

    @staticmethod
    def imports(symbol_file, input_names=None, param_file=None, ctx=None,
                device=None, allow_missing_params=False):
        """Load -symbol.json (+ params npz) into a runnable block
        (parity: SymbolBlock.imports).  Accepts BOTH serialized formats:
        the StableHLO deployment artifact (HybridBlock.export) and the
        composable mx.sym DAG json (Symbol.save)."""
        from ..sym_api import load as sym_load, Symbol as GraphSymbol
        sym = sym_load(symbol_file)
        params = {}
        if param_file:
            loaded = onp.load(param_file)
            params = {k: jnp.asarray(loaded[k]) for k in loaded.files}
        if isinstance(sym, GraphSymbol):
            if input_names is None:
                input_names = [n for n in sym.list_arguments()
                               if n not in params]
            missing = (set(sym.list_arguments())
                       - set(params) - set(input_names))
            if missing and not allow_missing_params:
                raise ValueError("missing parameters: %s" % sorted(missing))
            blk = SymbolBlock(sym, params)
            blk._input_names = list(input_names)
            return blk
        missing = set(sym.param_avals) - set(params)
        if missing and not allow_missing_params:
            raise ValueError("missing parameters: %s" % sorted(missing))
        return SymbolBlock(sym, params)

    def forward(self, *args):
        from ..sym_api import Symbol as GraphSymbol
        if isinstance(self._symbol, GraphSymbol):
            names = getattr(self, "_input_names", None) or \
                [n for n in self._symbol.list_arguments()
                 if n not in self._param_vals]

            def run(*iv):
                env = {k: _wrap_value(v)
                       for k, v in self._param_vals.items()}
                env.update(dict(zip(names, (_wrap_value(v._data
                                            if hasattr(v, "_data") else v)
                                            for v in iv))))
                out = self._symbol._eval(env)
                if isinstance(out, (list, tuple)):
                    return type(out)(o._data if hasattr(o, "_data") else o
                                     for o in out)
                return out._data if hasattr(out, "_data") else out

            return apply_op(lambda *iv: run(*iv), *args)
        return apply_op(lambda *iv: self._symbol(self._param_vals, *iv),
                        *args)

    def collect_params(self, select=None):
        # imported params are plain buffers, not trainable Parameters
        return OrderedDict()
