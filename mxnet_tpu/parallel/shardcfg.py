"""Mesh-native composed sharding: ONE config object for every axis.

ROADMAP item 2 names the unlock for every later scale item: a single
mesh/sharding config threaded through gluon + ops instead of per-module
ad-hoc specs.  `ShardingConfig` is that object:

- the named mesh (axes drawn from dp/tp/sp/pp/ep; any subset, any order),
  built once and cached, or bound to an existing `jax.sharding.Mesh`;
- per-param-family `PartitionSpec` rules (ordered regex -> spec template,
  Megatron dp×tp BERT rules shipped as `ShardingConfig.for_transformer`);
- activation constraint points (`constrain(x, kind)` inserts GSPMD
  `with_sharding_constraint`s at the named points: "data", "act",
  "tokens", "attention" — the SNIPPETS [1] pattern);
- serialization (`to_dict`/`from_dict`) so checkpoints can record the
  layout they were written under (resharding on membership change,
  ROADMAP item 3, starts from exactly this metadata).

Consumers: `DataParallelTrainer(sharding=cfg)` lays out params and
optimizer slots by `param_sharding`; `PipelineRunner`/`PipelineTrainer`/
`MoELayer`/`ring_attention` take `sharding=cfg` and pick their axis off
the one mesh; `ops.attention.flash_attention` consults the ACTIVE config
(`cfg.scope()` / `current()`) and reroutes through a `shard_map` entry
over the named mesh (batch over dp, heads over tp, sequence over sp —
see `ops.attention.flash_attention_sharded`).

Spec templates are resolved against the mesh AND the concrete shape:
axis names the mesh does not carry are dropped, and an axis whose size
does not divide the dimension falls back to replicated for that dim —
one config object therefore works unchanged across mesh shapes
(dp-only, dp×tp, dp×tp×sp, a single device).

This module imports nothing from mxnet_tpu at import time: gluon blocks
and ops consult it through ``sys.modules`` guards, so a process that
never builds a config pays nothing.
"""
from __future__ import annotations

import os
import re
import threading

import numpy as onp

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["ShardingConfig", "make_mesh", "current", "active_token",
           "maybe_constrain_nd", "collective_census", "MESH_AXES",
           "MeshShrinkError", "reshard_plan", "shard_slabs",
           "manual_mode", "manual_lowering", "REMAT_POLICIES",
           "ZERO_SLOT_PREFIXES"]

#: canonical axis vocabulary (any subset, any order, may appear size-1)
MESH_AXES = ("dp", "tp", "sp", "pp", "ep")

#: remat policy name -> constraint-point names SAVED across backward
#: (everything else is recomputed).  "tokens" keeps only the layer-
#: boundary token streams (classic sublinear per-layer checkpointing);
#: "attention" additionally keeps the q/k/v heads so the attention entry
#: itself is not recomputed (more residual memory, less recompute).
REMAT_POLICIES = {
    "tokens": ("tokens",),
    "attention": ("tokens", "attention"),
}

#: optimizer-slot name prefixes understood by `ShardingConfig.param_spec`
#: ("slot0::<param>" / "slot1::<param>"): the spec resolves through
#: `slot_spec` of the underlying parameter, so format-2 checkpoints and
#: `reshard_plan` lay out / classify ZeRO slot shards with no extra code.
ZERO_SLOT_PREFIXES = ("slot0::", "slot1::")


def make_mesh(shape=None, axis_names=("dp",), devices=None):
    """Create a Mesh over local devices.

    - ``shape=None`` puts all devices on the first axis (trailing axes
      size 1).
    - ``axis_names`` longer than ``shape`` pads the shape with size-1
      axes (a (4, 2) shape under ("dp", "tp", "sp") means sp=1).
    - A shape whose product exceeds the available device count raises a
      clear error (instead of propagating numpy's reshape failure); a
      product smaller than the device count uses the first
      ``prod(shape)`` devices.
    """
    devices = list(devices) if devices is not None else jax.devices()
    axis_names = tuple(axis_names)
    if shape is None:
        shape = (len(devices),) + (1,) * (len(axis_names) - 1)
    shape = tuple(int(s) for s in shape)
    if any(s < 1 for s in shape):
        raise ValueError("make_mesh: mesh shape %r has a non-positive "
                         "axis size" % (shape,))
    if len(axis_names) > len(shape):
        shape = shape + (1,) * (len(axis_names) - len(shape))
    if len(shape) > len(axis_names):
        raise ValueError(
            "make_mesh: shape %r has %d axes but only %d axis names %r; "
            "name every mesh axis" % (shape, len(shape), len(axis_names),
                                      axis_names))
    need = 1
    for s in shape:
        need *= s
    if need > len(devices):
        raise ValueError(
            "make_mesh: mesh shape %r (=%s) needs %d devices but only %d "
            "are available; pick a shape that factors the device count "
            "(e.g. XLA_FLAGS=--xla_force_host_platform_device_count=%d "
            "for a virtual CPU mesh)"
            % (shape, "x".join(str(s) for s in shape), need, len(devices),
               need))
    arr = onp.array(devices[:need]).reshape(shape)
    return Mesh(arr, axis_names)


class MeshShrinkError(ValueError):
    """No valid mesh factoring exists for the surviving device count.

    Extends the PR-9 non-factoring ValueError contract: the message names
    BOTH geometries (the old mesh and the surviving device count) so an
    operator can see at a glance why the shrink ladder bottomed out.
    Carries ``old_shape``/``axis_names``/``n_devices`` for programmatic
    handling (the elastic trainer surfaces it unrecovered)."""

    def __init__(self, msg, old_shape=None, axis_names=None,
                 n_devices=None):
        super().__init__(msg)
        self.old_shape = tuple(old_shape) if old_shape else None
        self.axis_names = tuple(axis_names) if axis_names else None
        self.n_devices = n_devices


# ---------------------------------------------------------------------------
# param-family rules
# ---------------------------------------------------------------------------
class ShardingRule:
    """One per-param-family rule: a name regex and a spec template.

    ``spec`` is a tuple with one entry per leading dimension: an axis
    name (str), a tuple of axis names, or None (replicated).  Trailing
    dims not covered by the template stay replicated.
    """

    __slots__ = ("pattern", "spec", "_re")

    def __init__(self, pattern, spec):
        self.pattern = str(pattern)
        self.spec = tuple(tuple(a) if isinstance(a, (list, tuple)) else a
                          for a in spec)
        self._re = re.compile(self.pattern)

    def matches(self, name):
        return self._re.search(name) is not None

    def to_dict(self):
        return {"pattern": self.pattern,
                "spec": [list(a) if isinstance(a, tuple) else a
                         for a in self.spec]}

    @classmethod
    def from_dict(cls, d):
        return cls(d["pattern"], d["spec"])

    def __repr__(self):
        return "ShardingRule(%r -> %r)" % (self.pattern, self.spec)

    def __eq__(self, other):
        return (isinstance(other, ShardingRule)
                and self.pattern == other.pattern and self.spec == other.spec)


# default activation constraint points: dim templates aligned to the
# LEADING dims of whatever value is constrained (extra dims replicated)
_DEFAULT_CONSTRAINTS = {
    # any batch-major value: batch over dp
    "data": ("dp",),
    # generic layer activation (B, ..., C): batch over dp only — GSPMD
    # propagates tp through the matmuls from the param shardings
    "act": ("dp",),
    # token stream (B, L, C): batch over dp, sequence over sp
    "tokens": ("dp", "sp", None),
    # attention heads layout (B, H, L, D): batch over dp, heads over tp,
    # sequence over sp (SNIPPETS [1]'s q/k/v constraint in this repo's
    # B,H,L,D layout)
    "attention": ("dp", "tp", "sp", None),
}

_TLS = threading.local()


def _stack():
    st = getattr(_TLS, "stack", None)
    if st is None:
        st = _TLS.stack = []
    return st


def current():
    """The innermost active ShardingConfig (``with cfg.scope():``), or
    None.  Consulted by gluon layers and ops.attention at trace time."""
    st = _stack()
    return st[-1] if st else None


def active_token():
    """Hashable token describing the active config for trace-cache keys
    (HybridBlock._signature): flipping the active config retraces.  The
    manual-lowering flag is part of the token — the same config traces
    WITHOUT GSPMD constraints inside a manual region (the ZeRO step's
    shard_map body), and those traces must not cache-share."""
    cfg = current()
    if cfg is None:
        return None
    return (cfg.signature(), manual_mode())


def _manual_depth():
    return getattr(_TLS, "manual", 0)


def manual_mode():
    """True inside a manual-collective lowering region (`manual_lowering`):
    the enclosing code is a shard_map body where mesh axes are manual, so
    GSPMD `with_sharding_constraint`s would be rejected and sharded op
    dispatch (the flash shard_map entry) must stay local."""
    return _manual_depth() > 0


def manual_lowering():
    """Context manager marking a manual-collective region (the ZeRO
    trainer's shard_map body): constraint points skip GSPMD constraints
    (data is already per-shard local) but still apply remat
    checkpoint-name tags; `ops.attention` keeps dispatch local."""

    class _Manual:
        def __enter__(self):
            _TLS.manual = _manual_depth() + 1
            return self

        def __exit__(self, *exc):
            _TLS.manual = max(0, _manual_depth() - 1)
            return False

    return _Manual()


def maybe_constrain_nd(x, kind):
    """Constrain a gluon ndarray at a named point under the ACTIVE config
    (no-op without one).  Recorded through apply_op so the autograd tape
    sees it (the VJP of a sharding constraint is the same constraint).

    When the active config carries a `remat` policy, the value is ALSO
    tagged with `jax.ad_checkpoint.checkpoint_name(x, kind)` — the
    `save_only_these_names` policy then keeps exactly these boundary
    tensors as residuals and recomputes everything between them.  Tagging
    applies even on a 1-device mesh (remat is a memory knob, not a
    sharding one) and inside manual-lowering regions (where the GSPMD
    constraint itself is skipped)."""
    cfg = current()
    if cfg is None:
        return x
    tag = kind in cfg.remat_saved_names()
    constrain = cfg.active and not manual_mode()
    if not (tag or constrain):
        return x

    def op(v):
        if constrain:
            v = cfg.constrain(v, kind)
        if tag:
            from jax.ad_checkpoint import checkpoint_name
            v = checkpoint_name(v, kind)
        return v

    from mxnet_tpu.ndarray import apply_op, ndarray
    if not isinstance(x, ndarray):
        return op(x)
    return apply_op(op, x)


class ShardingConfig:
    """One config object for mesh axes, param layouts and activation
    constraint points.

    Args:
      mesh: bind an existing jax.sharding.Mesh (axis_names/shape derived)
      mesh_shape / axis_names: build the mesh lazily over local devices
        (`make_mesh` semantics: names may outnumber shape entries)
      rules: ordered ShardingRule list (or dicts) — first match wins
      param_fn: escape hatch callable (name, shape) -> PartitionSpec
        checked BEFORE rules (not serializable; to_dict refuses)
      constraints: override/extend the named activation constraint points
      data_axis: batch axis for input sharding (default: first mesh axis
        named "dp", else the first axis)
      devices: explicit device list for lazy mesh construction
      zero: ZeRO state-sharding stage over the dp axis (Rajbhandari et
        al. 2020).  0 = fully replicated state (today); 1 = fp32
        optimizer slots shard over dp (`slot_spec`); 2 = grads shard too
        (in the fused one-program step gradients are already transient —
        the reduce-scatter lowering never materializes a persistent full
        gradient, so 2 lowers like 1); 3 = params at rest ALSO shard over
        dp (`param_spec` gains the dp dim; the step all-gathers them on
        entry instead of on exit)
      remat: activation rematerialization policy — None/"off" (save
        everything, today), or a key of REMAT_POLICIES ("tokens",
        "attention"): backward keeps only the tensors tagged at those
        named constraint points and recomputes the rest
    """

    def __init__(self, mesh=None, mesh_shape=None, axis_names=None,
                 rules=(), param_fn=None, constraints=None, data_axis=None,
                 devices=None, zero=0, remat=None):
        if mesh is not None:
            self._mesh = mesh
            self.axis_names = tuple(mesh.axis_names)
            self.mesh_shape = tuple(mesh.devices.shape)
        else:
            self._mesh = None
            self.axis_names = tuple(axis_names) if axis_names else ("dp",)
            if mesh_shape is not None:
                mesh_shape = tuple(int(s) for s in mesh_shape)
                if len(self.axis_names) > len(mesh_shape):
                    mesh_shape = mesh_shape + (1,) * (
                        len(self.axis_names) - len(mesh_shape))
            self.mesh_shape = mesh_shape
        self._devices = list(devices) if devices is not None else None
        self.rules = [r if isinstance(r, ShardingRule)
                      else ShardingRule.from_dict(r) for r in rules]
        self.param_fn = param_fn
        self.constraints = dict(_DEFAULT_CONSTRAINTS)
        if constraints:
            self.constraints.update(
                {k: tuple(v) for k, v in constraints.items()})
        if data_axis is None:
            data_axis = "dp" if "dp" in self.axis_names else self.axis_names[0]
        self.data_axis = data_axis
        self.zero = int(zero)
        if self.zero not in (0, 1, 2, 3):
            raise ValueError("ShardingConfig: zero stage must be 0..3, "
                             "got %r" % (zero,))
        if isinstance(remat, str):
            remat = remat.strip().lower() or None
            if remat in ("off", "none", "0"):
                remat = None
        if remat is not None and remat not in REMAT_POLICIES:
            raise ValueError(
                "ShardingConfig: unknown remat policy %r (known: off, %s)"
                % (remat, ", ".join(sorted(REMAT_POLICIES))))
        self.remat = remat

    # -- mesh ---------------------------------------------------------------
    @property
    def mesh(self):
        if self._mesh is None:
            self._mesh = make_mesh(self.mesh_shape, self.axis_names,
                                   self._devices)
            self.mesh_shape = tuple(self._mesh.devices.shape)
        return self._mesh

    def axis_size(self, name):
        """Size of a mesh axis, 1 when the mesh does not carry it.

        Resolved from the declared ``mesh_shape`` when the mesh itself was
        never built — a config deserialized from checkpoint metadata must
        answer spec-resolution questions on hosts that can't materialize
        the writer's mesh (slice-on-read under a shrunken device set)."""
        if name not in self.axis_names:
            return 1
        if self._mesh is None and self.mesh_shape is not None:
            return int(self.mesh_shape[self.axis_names.index(name)])
        return int(self.mesh.shape[name])

    @property
    def n_devices(self):
        return int(self.mesh.devices.size)

    @property
    def active(self):
        """Whether this config shards anything at all (>1 device)."""
        return self.n_devices > 1

    def describe(self):
        return "x".join("%s=%d" % (a, self.axis_size(a))
                        for a in self.axis_names)

    # -- spec resolution ----------------------------------------------------
    def _axis_factor(self, entry):
        """Mesh size product of a spec entry (str | tuple | None), only
        counting axes the mesh carries; returns (kept_entry, size)."""
        if entry is None:
            return None, 1
        names = entry if isinstance(entry, tuple) else (entry,)
        kept = tuple(n for n in names if n in self.axis_names)
        size = 1
        for n in kept:
            size *= self.axis_size(n)
        if not kept or size == 1:
            return None, 1
        return (kept if len(kept) > 1 else kept[0]), size

    def resolve_spec(self, template, shape=None, ndim=None):
        """Resolve a spec template against this mesh (and a shape, when
        given): unknown axes drop, non-dividing axes fall back to
        replicated for that dim, trailing dims are replicated."""
        template = tuple(template)
        if ndim is None:
            ndim = len(shape) if shape is not None else len(template)
        out = []
        for i in range(min(ndim, len(template))):
            entry, size = self._axis_factor(template[i])
            if entry is not None and shape is not None \
                    and shape[i] % size != 0:
                entry = None
            out.append(entry)
        while out and out[-1] is None:
            out.pop()
        return P(*out)

    def param_spec(self, name, shape):
        """PartitionSpec for a parameter: param_fn, then first matching
        rule, else replicated.

        Optimizer-slot names ("slot0::<param>"/"slot1::<param>", the
        DataParallelTrainer/checkpoint flattening) resolve through
        `slot_spec` of the underlying parameter — ZeRO slot shards get
        format-2 checkpoint slabs and `reshard_plan` classification with
        no slot-specific code anywhere else.  At zero >= 3 parameters
        themselves gain the dp dim (params-at-rest shard)."""
        for pre in ZERO_SLOT_PREFIXES:
            if name.startswith(pre):
                return self.slot_spec(name[len(pre):], shape)
        spec = self._base_param_spec(name, shape)
        if self.zero >= 3:
            spec = self._with_dp(spec, shape)
        return spec

    def _base_param_spec(self, name, shape):
        if self.param_fn is not None:
            spec = self.param_fn(name, shape)
            if spec is not None:
                return self.resolve_spec(tuple(spec), shape)
        for rule in self.rules:
            if rule.matches(name):
                return self.resolve_spec(rule.spec, shape)
        return P()

    def param_sharding(self, name, shape):
        return NamedSharding(self.mesh, self.param_spec(name, shape))

    # -- ZeRO state sharding -------------------------------------------------
    def zero_dim(self, name, shape, spec=None):
        """The dim of `name` the dp axis subdivides for ZeRO state
        sharding: the FIRST dim the remaining dp factor divides (on top
        of whatever the param spec already shards there), or None when no
        dim is divisible, dp is absent/size-1, or the spec already
        carries dp somewhere."""
        dp = self.axis_size("dp")
        if self.zero < 1 or dp <= 1:
            return None
        if spec is None:
            spec = self._base_param_spec(name, tuple(shape))
        for entry in spec:
            names = (entry,) if isinstance(entry, str) else tuple(entry or ())
            if "dp" in names:
                return None
        for d, size in enumerate(shape):
            entry = spec[d] if d < len(spec) else None
            names = (entry,) if isinstance(entry, str) else tuple(entry or ())
            factor = 1
            for n in names:
                factor *= self.axis_size(n)
            if size and size % (factor * dp) == 0:
                return d
        return None

    def _with_dp(self, spec, shape):
        """Insert dp into `spec` at `zero_dim` (identity when None)."""
        d = self.zero_dim("", shape, spec=spec)
        if d is None:
            return spec
        entries = list(spec) + [None] * (len(shape) - len(spec))
        e = entries[d]
        if e is None:
            entries[d] = "dp"
        else:
            entries[d] = ((e,) if isinstance(e, str) else tuple(e)) + ("dp",)
        while entries and entries[-1] is None:
            entries.pop()
        return P(*entries)

    def slot_spec(self, name, shape):
        """PartitionSpec for `name`'s fp32 optimizer slots: the param's
        own spec, plus — at zero >= 1 — the dp axis on the first
        divisible dim (`P("dp", ...)` for a replicated param).  Equal to
        the param spec at zero 0 (slots co-sharded with their param)."""
        shape = tuple(shape)
        spec = self._base_param_spec(name, shape)
        if self.zero < 1:
            return spec
        return self._with_dp(spec, shape)

    def slot_sharding(self, name, shape):
        return NamedSharding(self.mesh, self.slot_spec(name, shape))

    # -- activation rematerialization ----------------------------------------
    def remat_saved_names(self):
        """Constraint-point names SAVED across backward under the remat
        policy (empty tuple = no policy = save everything)."""
        return REMAT_POLICIES.get(self.remat, ())

    def remat_policy(self):
        """The `jax.checkpoint` policy for this config's remat knob
        (None without one): save ONLY the tensors tagged at the policy's
        constraint points, recompute the rest in backward."""
        if not self.remat:
            return None
        return jax.checkpoint_policies.save_only_these_names(
            *self.remat_saved_names())

    def data_spec(self):
        return self.resolve_spec((self.data_axis,))

    def data_sharding(self):
        return NamedSharding(self.mesh, self.data_spec())

    def replicated(self):
        return NamedSharding(self.mesh, P())

    # -- activation constraint points ---------------------------------------
    def spec_for(self, kind, shape=None, ndim=None):
        tmpl = self.constraints.get(kind)
        if tmpl is None:
            raise KeyError("unknown constraint point %r (known: %s)"
                           % (kind, sorted(self.constraints)))
        return self.resolve_spec(tmpl, shape=shape, ndim=ndim)

    def constrain(self, x, kind):
        """GSPMD sharding constraint at a named point (identity on a
        1-device mesh).  Safe under jit/grad: with_sharding_constraint
        is differentiable and its transpose is itself."""
        if not self.active:
            return x
        shape = tuple(getattr(x, "shape", ()) or ())
        spec = self.spec_for(kind, shape=shape if shape else None,
                             ndim=len(shape) if shape else 0)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec))

    # -- scope / identity ---------------------------------------------------
    def scope(self):
        """Context manager activating this config for gluon layers and
        ops dispatched inside (see `current()`)."""
        cfg = self

        class _Scope:
            def __enter__(self):
                _stack().append(cfg)
                return cfg

            def __exit__(self, *exc):
                st = _stack()
                if st and st[-1] is cfg:
                    st.pop()
                elif cfg in st:  # defensive: unbalanced exit
                    st.remove(cfg)
                return False

        return _Scope()

    def signature(self):
        """Content-hashable identity: two configs with the same axes,
        shape, rules and constraint points trace-cache-share."""
        return (self.axis_names, self.mesh_shape,
                tuple((r.pattern, r.spec) for r in self.rules),
                id(self.param_fn) if self.param_fn is not None else None,
                tuple(sorted((k, tuple(v))
                             for k, v in self.constraints.items())),
                self.data_axis, self.zero, self.remat)

    def __repr__(self):
        return "ShardingConfig(%s, rules=%d%s%s%s)" % (
            self.describe() if self._mesh is not None or self.mesh_shape
            else ",".join(self.axis_names),
            len(self.rules), ", param_fn" if self.param_fn else "",
            ", zero=%d" % self.zero if self.zero else "",
            ", remat=%s" % self.remat if self.remat else "")

    # -- serialization (checkpoint metadata) --------------------------------
    def to_dict(self):
        if self.param_fn is not None:
            raise ValueError(
                "ShardingConfig with a param_fn callable is not "
                "serializable; express the layout as ShardingRule "
                "patterns instead")
        # mesh_shape may still be unresolved (lazy mesh): resolve via the
        # property only when a mesh was ever needed; None serializes fine
        return {
            "axis_names": list(self.axis_names),
            "mesh_shape": list(self.mesh_shape) if self.mesh_shape else None,
            "rules": [r.to_dict() for r in self.rules],
            "constraints": {k: list(v) for k, v in self.constraints.items()},
            "data_axis": self.data_axis,
            "zero": self.zero,
            "remat": self.remat,
        }

    @classmethod
    def from_dict(cls, d, devices=None):
        return cls(mesh_shape=d.get("mesh_shape"),
                   axis_names=d.get("axis_names") or ("dp",),
                   rules=[ShardingRule.from_dict(r)
                          for r in d.get("rules", [])],
                   constraints=d.get("constraints"),
                   data_axis=d.get("data_axis"),
                   devices=devices,
                   zero=d.get("zero", 0),
                   remat=d.get("remat"))

    # -- elastic resharding (membership change) -----------------------------
    def shrink_to(self, devices):
        """Re-factor this config's mesh onto a smaller device set.

        ``devices`` is the surviving device list (or a bare count; a list
        also pins the new mesh to exactly those devices).  The shrink
        ladder, in order:

        1. **dp-first**: every non-dp axis keeps its size and dp absorbs
           the loss (dp' = n // prod(other axes)) — a lost dp row costs
           throughput, never layout.
        2. **tp refactor**: when dp can't absorb it, tp shrinks to the
           largest divisor of the old tp size that still factors the
           surviving count (each new tp shard is a whole union of old
           shards) — loud warning.
        3. **replicated fallback**: tp'=1 (every tp rule resolves away) —
           louder warning.  Gated by MXNET_MESH_TP_FALLBACK; disabled, the
           ladder stops at step 1.

        Raises :class:`MeshShrinkError` naming both geometries when no
        rung fits (e.g. a prime survivor count under sp>1).  The returned
        config shares rules/constraints/data_axis — specs re-resolve
        against the new mesh through the existing drop/replicate rules, so
        the SAME rule list lays out params under any rung of the ladder.
        """
        from .. import config as _config
        if isinstance(devices, int):
            dev_list, n = None, int(devices)
        else:
            dev_list = list(devices)
            n = len(dev_list)
        old_shape = tuple(self.mesh_shape or ())
        if not old_shape:  # lazy config never materialized: force it
            old_shape = tuple(self.mesh.devices.shape)
        names = self.axis_names
        if n < 1:
            raise MeshShrinkError(
                "shrink_to: no surviving devices (old mesh %s)"
                % self.describe(), old_shape, names, n)
        sizes = dict(zip(names, old_shape))
        dp_ax = "dp" if "dp" in sizes else names[0]
        non_dp = 1
        for a, s in sizes.items():
            if a != dp_ax:
                non_dp *= s
        new_sizes = None
        if n % non_dp == 0:
            new_sizes = dict(sizes)
            new_sizes[dp_ax] = n // non_dp  # rung 1: dp absorbs the loss
        elif "tp" in sizes and sizes["tp"] > 1 \
                and bool(_config.get("MXNET_MESH_TP_FALLBACK")):
            rest = non_dp // sizes["tp"]  # sp/pp/ep must survive intact
            if n % rest == 0:
                budget = n // rest
                old_tp = sizes["tp"]
                tp2 = 1
                for cand in range(old_tp, 0, -1):
                    if old_tp % cand == 0 and budget % cand == 0:
                        tp2 = cand
                        break
                new_sizes = dict(sizes)
                new_sizes["tp"] = tp2
                new_sizes[dp_ax] = budget // tp2
                import warnings
                if tp2 == 1:
                    warnings.warn(
                        "shrink_to: %d surviving device(s) admit no tp>1 "
                        "factoring of mesh %s — tensor-parallel params "
                        "fall back to REPLICATED (tp rules resolve away); "
                        "expect higher per-device memory"
                        % (n, self.describe()))
                else:
                    warnings.warn(
                        "shrink_to: mesh %s re-factored to tp=%d over %d "
                        "surviving device(s) (dp-first shrink did not "
                        "divide)" % (self.describe(), tp2, n))
        if new_sizes is None:
            raise MeshShrinkError(
                "shrink_to: cannot factor %d surviving device(s) into "
                "mesh %s (axes %s): the non-dp extent %d does not divide "
                "%d%s" % (n, self.describe(), ",".join(names), non_dp, n,
                          "" if bool(_config.get("MXNET_MESH_TP_FALLBACK"))
                          else " and MXNET_MESH_TP_FALLBACK=0 forbids the "
                               "tp refactor/replicated rungs"),
                old_shape, names, n)
        new_shape = tuple(new_sizes[a] for a in names)
        return ShardingConfig(
            mesh_shape=new_shape, axis_names=names, rules=list(self.rules),
            param_fn=self.param_fn,
            constraints={k: tuple(v) for k, v in self.constraints.items()},
            data_axis=self.data_axis, devices=dev_list,
            zero=self.zero, remat=self.remat)

    # -- constructors -------------------------------------------------------
    @classmethod
    def from_env(cls, devices=None, **kw):
        """Build from MXNET_MESH_SHAPE ("4,2") + MXNET_MESH_AXES
        ("dp,tp"); unset -> all devices on dp.  MXNET_ZERO_STAGE and
        MXNET_REMAT_POLICY seed the zero/remat knobs (explicit kwargs
        win)."""
        zero_s = os.environ.get("MXNET_ZERO_STAGE", "").strip()
        if zero_s and "zero" not in kw:
            try:
                kw["zero"] = int(zero_s)
            except ValueError:
                raise ValueError("MXNET_ZERO_STAGE=%r is not an int (0..3)"
                                 % zero_s)
        remat_s = os.environ.get("MXNET_REMAT_POLICY", "").strip()
        if remat_s and "remat" not in kw:
            kw["remat"] = remat_s
        shape_s = os.environ.get("MXNET_MESH_SHAPE", "").strip()
        axes_s = os.environ.get("MXNET_MESH_AXES", "").strip()
        axes = tuple(a.strip() for a in axes_s.split(",") if a.strip()) \
            if axes_s else None
        shape = None
        if shape_s:
            try:
                shape = tuple(int(s) for s in shape_s.split(",") if s.strip())
            except ValueError:
                raise ValueError(
                    "MXNET_MESH_SHAPE=%r is not a comma-separated int "
                    "list (e.g. '4,2')" % shape_s)
            if axes is None:
                axes = MESH_AXES[:len(shape)]
        return cls(mesh_shape=shape, axis_names=axes or ("dp",),
                   devices=devices, **kw)

    @classmethod
    def for_transformer(cls, mesh=None, mesh_shape=None, axis_names=None,
                        devices=None, **kw):
        """Megatron-style dp×tp rules for this repo's transformer blocks
        (BERT MHA/FFN Dense names): qkv/ffn1 column-parallel (units dim),
        proj/ffn2 row-parallel (in_units dim), their biases follow the
        column split, everything else replicated.  Works on ANY mesh —
        axes the mesh lacks resolve away."""
        rules = [
            # column-parallel GEMMs: out-features dim 0 over tp
            ShardingRule(r"(qkv|ffn1)\.weight$", ("tp", None)),
            ShardingRule(r"(qkv|ffn1)\.bias$", ("tp",)),
            # row-parallel GEMMs: in-features dim 1 over tp
            ShardingRule(r"(attention\.proj|ffn2)\.weight$", (None, "tp")),
            # row-parallel bias is a full-size add after the tp-reduce:
            # replicated (no rule needed; default)
        ]
        return cls(mesh=mesh, mesh_shape=mesh_shape, axis_names=axis_names,
                   rules=rules, devices=devices, **kw)


# ---------------------------------------------------------------------------
# elastic resharding: slab geometry + recovery plan
# ---------------------------------------------------------------------------
def shard_slabs(sharding, shape):
    """Distinct shard slabs of an array under a NamedSharding.

    Returns ``{slab_key: (slices, [devices])}`` where ``slab_key`` is a
    hashable ``((start, stop), ...)`` per dim (None bounds resolved to the
    full extent) and the device list holds every replica of that slab.
    GSPMD shards form a regular grid, so the slabs partition the array.
    """
    out = {}
    for dev, idx in sharding.devices_indices_map(tuple(shape)).items():
        key = tuple(
            (0 if s.start is None else int(s.start),
             int(shape[d]) if s.stop is None else int(s.stop))
            for d, s in enumerate(idx))
        if key in out:
            out[key][1].append(dev)
        else:
            out[key] = (idx, [dev])
    return out


def reshard_plan(old_cfg, new_cfg, shapes, lost_devices=()):
    """Per-array recovery plan for a mesh membership change.

    ``old_cfg`` is the layout state was written/held under (typically
    ``ShardingConfig.from_dict`` of checkpoint metadata), ``new_cfg`` the
    survivors' shrunken config, ``shapes`` a ``{name: shape}`` dict and
    ``lost_devices`` the devices (or device ids) that left the mesh.

    Each entry records the old/new resolved specs and a recovery
    ``source``:

    - ``"memory"``: every distinct slab of the old placement still has at
      least one replica on a surviving device — survivors re-place the
      live array (peer copy; on a multi-host mesh this is a gather from
      surviving peers).
    - ``"checkpoint"``: some slab lived ONLY on lost devices — the slices
      must come from the newest crash-safe sharded checkpoint.

    When the old mesh can no longer be constructed over the surviving
    process (fewer local devices than the old mesh needs), every array
    conservatively plans ``"checkpoint"`` — correctness never depends on
    reading a shard that might be gone.
    """
    lost = {getattr(d, "id", d) for d in lost_devices}
    old_shardings = None
    try:
        mesh = old_cfg.mesh  # may raise: old geometry needs gone devices
        old_shardings = lambda name, shape: NamedSharding(  # noqa: E731
            mesh, old_cfg.param_spec(name, shape))
    except ValueError:
        pass
    plan = {}
    n_mem = n_ckpt = 0
    for name, shape in shapes.items():
        shape = tuple(int(s) for s in shape)
        old_spec = old_cfg.param_spec(name, shape)
        new_spec = new_cfg.param_spec(name, shape)
        source = "checkpoint"
        if old_shardings is not None:
            source = "memory"
            slabs = shard_slabs(old_shardings(name, shape), shape)
            for _key, (_idx, devs) in slabs.items():
                if all(getattr(d, "id", d) in lost for d in devs):
                    source = "checkpoint"  # slab only lost replicas held
                    break
        plan[name] = {"old_spec": old_spec, "new_spec": new_spec,
                      "source": source, "moved": old_spec != new_spec}
        if source == "memory":
            n_mem += 1
        else:
            n_ckpt += 1
    plan["__summary__"] = {"memory": n_mem, "checkpoint": n_ckpt,
                           "old": old_cfg.describe(),
                           "new": new_cfg.describe()}
    return plan


# ---------------------------------------------------------------------------
# collective census (CI gates)
# ---------------------------------------------------------------------------
#: HLO collective classes counted by `collective_census`
COLLECTIVE_CLASSES = ("all-reduce", "all-gather", "reduce-scatter",
                      "collective-permute", "all-to-all")

_OPCODE_RE = re.compile(r"\s*([a-z][a-z0-9\-]*)\(")


def _hlo_opcode(line):
    """Opcode of one HLO instruction line (``%name = <type> opcode(...)``),
    or None.  The result type is skipped structurally, not by pattern: a
    combined collective has a TUPLE type with spaces in it
    (``(f32[32]{0}, f32[]) all-reduce(...)``)."""
    _, sep, rhs = line.partition(" = ")
    if not sep:
        return None
    if rhs.startswith("("):
        depth = 0
        for i, ch in enumerate(rhs):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rhs = rhs[i + 1:]
                break
    else:
        rhs = rhs.partition(" ")[2]
    m = _OPCODE_RE.match(rhs)
    return m.group(1) if m else None


def collective_census(compiled):
    """Count collectives per class in optimized HLO.

    `compiled` is a jax Compiled (``jit(f).lower(...).compile()``), a
    Lowered, or raw HLO text.  Async pairs (``-start``/``-done``) count
    once.  Deterministic and load-independent — safe to gate CI on,
    exactly like the decode-launch census (fused_cell.count_launches):
    the counts depend only on the program and partitioner, never on
    machine load.  Text in which no instruction parses at all is an
    error, not a census of zeros.
    """
    if hasattr(compiled, "compile"):        # Lowered -> Compiled
        compiled = compiled.compile()
    if hasattr(compiled, "as_text"):
        text = compiled.as_text()
    else:
        text = str(compiled)
    counts = {c: 0 for c in COLLECTIVE_CLASSES}
    parsed = 0
    for line in text.splitlines():
        op = _hlo_opcode(line)
        if op is None:
            continue
        parsed += 1
        if op.endswith("-start"):
            op = op[:-len("-start")]
        if op in counts:
            counts[op] += 1
    if not parsed:
        raise ValueError("collective_census: no HLO instruction found in "
                         "the program text (format changed?)")
    counts["total"] = sum(counts[c] for c in COLLECTIVE_CLASSES)
    return counts


def census_fn(fn, *args, **kwargs):
    """Convenience: lower+compile ``fn`` on the given args and census its
    collectives.  ``fn`` may already be jitted."""
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    return collective_census(jitted.lower(*args, **kwargs))
