"""mxnet_tpu.parallel — SPMD distributed training over device meshes.

This is where the TPU build goes *beyond* the reference: the reference has
data parallelism only (SURVEY.md §2.4 — kvstore + ps-lite/NCCL/Horovod).
Here, parallelism is expressed as shardings over a `jax.sharding.Mesh`
(dp/tp/pp/sp axes) and GSPMD/XLA inserts the collectives (all-reduce over
ICI for dp gradients, all-gather/reduce-scatter for tp, ppermute rings for
sequence parallelism — see ring_attention.py).

Components:
- make_mesh / MeshConfig: mesh construction helpers
- functionalize(net): HybridBlock → pure (params, x) -> out function
- DataParallelTrainer: whole-training-step compilation with dp sharding
"""
from __future__ import annotations

from collections import OrderedDict

import numpy as onp

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import autograd
from .._rng import trace_keys
from ..ndarray import ndarray, _wrap_value
from .shardcfg import (ShardingConfig, ShardingRule, make_mesh,
                       collective_census, census_fn, MeshShrinkError,
                       reshard_plan, shard_slabs, manual_lowering)

__all__ = ["Mesh", "NamedSharding", "P", "make_mesh", "functionalize",
           "DataParallelTrainer", "replicate", "shard_batch",
           "ShardingConfig", "ShardingRule", "collective_census",
           "census_fn", "MeshShrinkError", "reshard_plan", "shard_slabs",
           "manual_lowering"]


def functionalize(net, train=False):
    """Extract a pure function from a Gluon block.

    Returns (fn, params) with fn(param_vals: dict, *input_vals, key=None)
    -> (out_vals_pytree, aux_updates: dict).  The same rebinding trick as
    HybridBlock._build_cache — usable under jit/shard_map/grad.
    """
    params = OrderedDict((name, p) for name, p in net.collect_params().items()
                         if p._data is not None)

    def fn(param_vals, *input_vals, key=None):
        saved = [(p, p._data) for p in params.values()]
        wrappers = []
        try:
            for name, p in params.items():
                w = _wrap_value(param_vals[name])
                p._data = w
                wrappers.append((name, w, param_vals[name]))
            args = [_wrap_value(v) if isinstance(v, jax.Array) or hasattr(v, "shape")
                    else v for v in input_vals]
            ctx = trace_keys(key) if key is not None else None
            if ctx is not None:
                ctx.__enter__()
            try:
                with autograd._RecordingStateScope(False, train):
                    out = net.forward(*args)
            finally:
                if ctx is not None:
                    ctx.__exit__(None, None, None)
            aux = {}
            for name, w, v in wrappers:
                if w._data is not v:
                    aux[name] = w._data
            if isinstance(out, (list, tuple)):
                out_vals = type(out)(o._data for o in out)
            else:
                out_vals = out._data
            return out_vals, aux
        finally:
            for p, old in saved:
                p._data = old

    return fn, params


def replicate(x, mesh):
    """Place an array replicated over the whole mesh."""
    sharding = NamedSharding(mesh, P())
    return jax.device_put(x, sharding)


def shard_batch(x, mesh, axis_name="dp"):
    """Shard a batch along its leading axis over the named mesh axis."""
    spec = P(axis_name)
    return jax.device_put(x, NamedSharding(mesh, spec))


class DataParallelTrainer:
    """Compiled data-parallel training step over a mesh.

    TPU-native replacement for the reference's Trainer+kvstore loop: the
    forward, backward, gradient all-reduce (GSPMD-inserted over ICI) and
    optimizer update compile into ONE XLA executable with donated
    param/state buffers.

    loss_fn(out, *labels) must return a scalar ndarray expression built
    from mx ops (it is traced).
    """

    def __init__(self, net, loss_fn, optimizer="sgd", optimizer_params=None,
                 mesh=None, train=True, param_pspec=None, data_axis=None,
                 sharding=None):
        from .. import optimizer as opt_mod
        self.net = net
        self.loss_fn = loss_fn
        # ONE source of truth for layout: a ShardingConfig.  The legacy
        # (mesh=, param_pspec=) surface wraps into a config so old callers
        # keep their exact shardings (param_pspec becomes param_fn).
        if sharding is not None:
            if mesh is not None and mesh is not sharding.mesh:
                raise ValueError("DataParallelTrainer: pass either mesh= or "
                                 "sharding=, not conflicting both")
            if param_pspec is not None:
                raise ValueError("DataParallelTrainer: param_pspec= is the "
                                 "legacy surface; put rules/param_fn on the "
                                 "ShardingConfig instead")
            self.sharding = sharding
        else:
            mesh = mesh if mesh is not None else make_mesh()
            self.sharding = ShardingConfig(
                mesh=mesh, param_fn=param_pspec,
                data_axis=data_axis or mesh.axis_names[0])
        self.mesh = self.sharding.mesh
        opt = (optimizer if isinstance(optimizer, opt_mod.Optimizer)
               else opt_mod.create(optimizer, **(optimizer_params or {})))
        self.optimizer = opt
        self.train = train
        self._step = None
        self._fn, self._params = functionalize(net, train=train)
        self.data_axis = data_axis or self.sharding.data_axis
        # optimizer state as pure pytree (fp32 slots like the reference's
        # create_state)
        self._opt_kind, self._hp = self._opt_signature(opt)

    def _opt_signature(self, opt):
        from .. import optimizer as opt_mod
        common = dict(wd=opt.wd,
                      clip_gradient=opt.clip_gradient or 0.0,
                      rescale_grad=opt.rescale_grad)
        if isinstance(opt, opt_mod.SGD):
            return ("sgd_mom" if opt.momentum else "sgd",
                    dict(momentum=getattr(opt, "momentum", 0.0), **common))
        if type(opt) is opt_mod.AdamW:
            return ("adamw", dict(beta1=opt.beta1, beta2=opt.beta2,
                                  epsilon=opt.epsilon, **common))
        if type(opt) is opt_mod.Adam:
            return ("adam", dict(beta1=opt.beta1, beta2=opt.beta2,
                                 epsilon=opt.epsilon, **common))
        raise NotImplementedError(
            "DataParallelTrainer supports sgd/sgd_mom/adam/adamw fused "
            "steps; got %r (use gluon.Trainer for the others)"
            % type(opt).__name__)

    def init_state(self):
        """Build the (sharded) training state: params placed per the
        ShardingConfig's rules/param_fn (GSPMD lays out TP shards; at
        zero >= 3 params also shard over dp), fp32 optimizer slots per
        `slot_sharding` — co-sharded with their parameter at zero 0,
        dp-sharded on the first divisible dim at zero >= 1."""
        shard_of = self.sharding.param_sharding
        slot_of = self.sharding.slot_sharding
        pvals = {}
        for k, p in self._params.items():
            v = p._data._data
            pvals[k] = jax.device_put(v, shard_of(k, v.shape))
        trainable = [k for k, p in self._params.items()
                     if p.grad_req != "null"]
        if self._opt_kind == "sgd":
            slots = {}
        elif self._opt_kind == "sgd_mom":
            slots = {k: jax.device_put(jnp.zeros(pvals[k].shape, jnp.float32),
                                       slot_of(k, pvals[k].shape))
                     for k in trainable}
        else:  # adam/adamw
            slots = {k: (jax.device_put(jnp.zeros(pvals[k].shape, jnp.float32),
                                        slot_of(k, pvals[k].shape)),
                         jax.device_put(jnp.zeros(pvals[k].shape, jnp.float32),
                                        slot_of(k, pvals[k].shape)))
                     for k in trainable}
        return {"params": pvals, "slots": slots, "t": jnp.zeros((), jnp.int32)}

    def _zero_explicit_ok(self):
        """Whether the explicit reduce-scatter/all-gather ZeRO lowering
        applies: zero >= 1 on an effectively dp-only mesh (every other
        axis size 1) whose base param rules don't already shard over dp.
        Other meshes keep the GSPMD lowering — state is still sharded
        (same memory win) but the partitioner picks the collectives."""
        s = self.sharding
        if getattr(s, "zero", 0) < 1 or s.axis_size("dp") <= 1:
            return False
        if any(s.axis_size(a) > 1 for a in s.axis_names if a != "dp"):
            return False
        if self.data_axis != "dp":
            return False
        for k, p in self._params.items():
            spec = s._base_param_spec(k, tuple(p._data._data.shape))
            for entry in spec:
                names = (entry,) if isinstance(entry, str) \
                    else tuple(entry or ())
                if "dp" in names:
                    return False
        return True

    def build_step(self, donate=True):
        if self._zero_explicit_ok():
            return self._build_step_zero(donate=donate)
        fn = self._fn
        loss_fn = self.loss_fn
        kind, hp = self._opt_kind, self._hp
        sharding = self.sharding
        remat_policy = sharding.remat_policy() \
            if hasattr(sharding, "remat_policy") else None

        grad_names = [k for k, p in self._params.items()
                      if p.grad_req != "null"]

        def step(state, batch, labels, key, lr):
            pvals = state["params"]

            def loss_of(diff_pvals):
                full = dict(pvals)
                full.update(diff_pvals)
                # activate the config so gluon-level constraint points
                # (Dense/attention/FFN) and the sharded flash entry see
                # it at trace time
                with sharding.scope():
                    out, aux = fn(full, batch, key=key)
                out_nd = (_wrap_value(out) if not isinstance(out, tuple)
                          else tuple(_wrap_value(o) for o in out))
                lbl_nd = tuple(_wrap_value(l) for l in labels) \
                    if isinstance(labels, tuple) else (_wrap_value(labels),)
                with autograd._RecordingStateScope(False, True):
                    loss = loss_fn(out_nd, *lbl_nd)
                loss_val = loss._data if isinstance(loss, ndarray) else loss
                return jnp.mean(loss_val), aux

            if remat_policy is not None:
                # drop all forward residuals except the tagged constraint
                # points; backward recomputes the segments between them
                loss_of = jax.checkpoint(loss_of, policy=remat_policy)
            diff = {k: pvals[k] for k in grad_names}
            (loss_val, aux), grads = jax.value_and_grad(loss_of, has_aux=True)(diff)
            t = state["t"] + 1
            new_params = dict(pvals)
            new_slots = dict(state["slots"])
            clip = hp.get("clip_gradient", 0.0)
            rescale = hp.get("rescale_grad", 1.0)
            wd = hp.get("wd", 0.0)
            for k in grad_names:
                g = grads[k].astype(jnp.float32) * rescale
                if clip and clip > 0:
                    g = jnp.clip(g, -clip, clip)
                w = pvals[k].astype(jnp.float32)
                if kind != "adamw":
                    g = g + wd * w
                if kind == "sgd":
                    new_w = w - lr * g
                elif kind == "sgd_mom":
                    m = hp["momentum"] * new_slots[k] - lr * g
                    new_slots[k] = m
                    new_w = w + m
                else:  # adam/adamw w/ bias correction in lr
                    b1, b2, eps = hp["beta1"], hp["beta2"], hp["epsilon"]
                    m, v = new_slots[k]
                    m = b1 * m + (1 - b1) * g
                    v = b2 * v + (1 - b2) * jnp.square(g)
                    tf = t.astype(jnp.float32)
                    lr_t = lr * jnp.sqrt(1 - b2 ** tf) / (1 - b1 ** tf)
                    new_slots[k] = (m, v)
                    new_w = w - lr_t * m / (jnp.sqrt(v) + eps)
                    if kind == "adamw":
                        new_w = new_w - lr * wd * w
                new_params[k] = new_w.astype(pvals[k].dtype)
            for k, v in aux.items():
                new_params[k] = v
            return {"params": new_params, "slots": new_slots, "t": t}, loss_val

        mesh = self.mesh
        repl = NamedSharding(mesh, P())
        data_sh = NamedSharding(mesh, P(self.data_axis))

        pvals = {k: p._data._data for k, p in self._params.items()}
        param_sh = {k: self.sharding.param_sharding(k, v.shape)
                    for k, v in pvals.items()}
        slot_of = self.sharding.slot_sharding
        trainable = [k for k, p in self._params.items()
                     if p.grad_req != "null"]
        if self._opt_kind == "sgd":
            slot_sh = {}
        elif self._opt_kind == "sgd_mom":
            slot_sh = {k: slot_of(k, pvals[k].shape) for k in trainable}
        else:
            slot_sh = {k: (slot_of(k, pvals[k].shape),) * 2
                       for k in trainable}
        state_sh = {"params": param_sh, "slots": slot_sh, "t": repl}

        self._step = jax.jit(
            step,
            in_shardings=(state_sh, data_sh, data_sh, repl, repl),
            out_shardings=(state_sh, repl),
            donate_argnums=(0,) if donate else (),
        )
        return self._step

    def _build_step_zero(self, donate=True):
        """Explicit ZeRO train step: a shard_map over dp whose collectives
        are hand-placed so the static `collective_census` proves the
        layout —

          per-device partial grads (no implicit collectives inside the
          manual region) → `psum_scatter` (ONE reduce-scatter per sharded
          param: each device receives only its slot shard of the summed
          gradient) → local optimizer math on the dp slot shard →
          `all_gather` of the updated param shards (zero <= 2; at zero 3
          params stay sharded at rest and the gather moves to step ENTRY).

        One small all-reduce reports the global mean loss; a param with
        no dp-divisible dim keeps the replicated update (its gradient is
        psum'd — counted, never silent).  Gradient math is ordered
        exactly as the replicated step (reduce, then rescale/clip/wd on
        the reduced shard), so zero-1 training is bit-identical to
        zero-0 on the same mesh.  Dropout keys are shard-decorrelated by
        `fold_in(key, axis_index(dp))` — with dropout > 0 the trajectory
        intentionally differs from the replicated run (same rule as the
        sharded flash kernel's in-kernel dropout)."""
        fn = self._fn
        loss_fn = self.loss_fn
        kind, hp = self._opt_kind, self._hp
        sharding = self.sharding
        mesh = self.mesh
        dp_ax = "dp"
        ndev = sharding.axis_size(dp_ax)
        zero = sharding.zero
        remat_policy = sharding.remat_policy()

        pvals0 = {k: p._data._data for k, p in self._params.items()}
        grad_names = [k for k, p in self._params.items()
                      if p.grad_req != "null"]
        # static ZeRO geometry: the dp dim of every param's slot shard
        # (None = no divisible dim -> replicated update), and whether the
        # param itself rests sharded (zero 3)
        zdim = {k: sharding.zero_dim(k, tuple(v.shape))
                for k, v in pvals0.items()}
        sspec = {k: sharding.slot_spec(k, tuple(v.shape))
                 for k, v in pvals0.items()}
        rest_sharded = {k: (zero >= 3 and zdim[k] is not None)
                        for k in pvals0}
        pspec = {k: (sspec[k] if rest_sharded[k] else P())
                 for k in pvals0}
        nglob_box = {}

        def body(state, batch, labels, key, lr):
            pvals, slots = state["params"], state["slots"]
            if key is not None:
                # shard-decorrelated dropout (same key on every shard
                # would repeat masks batch-slice to batch-slice)
                key = jax.random.fold_in(key,
                                         jax.lax.axis_index(dp_ax))
            full = {}
            for k, v in pvals.items():
                if rest_sharded[k]:
                    full[k] = jax.lax.all_gather(v, dp_ax, axis=zdim[k],
                                                 tiled=True)
                else:
                    full[k] = v

            def loss_of(diff_pvals):
                p = dict(full)
                p.update(diff_pvals)
                from .shardcfg import manual_lowering as _manual
                with sharding.scope(), _manual():
                    out, aux = fn(p, batch, key=key)
                if aux:
                    raise NotImplementedError(
                        "zero >= 1: blocks that update parameters in "
                        "forward (e.g. BatchNorm running stats) are not "
                        "supported under the manual reduce-scatter "
                        "lowering; train them with zero=0")
                out_nd = (_wrap_value(out) if not isinstance(out, tuple)
                          else tuple(_wrap_value(o) for o in out))
                lbl_nd = tuple(_wrap_value(l) for l in labels) \
                    if isinstance(labels, tuple) else (_wrap_value(labels),)
                with autograd._RecordingStateScope(False, True):
                    loss = loss_fn(out_nd, *lbl_nd)
                loss_val = loss._data if isinstance(loss, ndarray) else loss
                # objective = local_sum / GLOBAL count: the cotangent
                # seeded into backward is exactly the replicated step's
                # 1/N per element (bit-identical partial grads)
                nglob = int(onp.prod(loss_val.shape or (1,))) * ndev
                nglob_box["n"] = nglob
                return jnp.sum(loss_val) / nglob, jnp.sum(loss_val)

            if remat_policy is not None:
                loss_of = jax.checkpoint(loss_of, policy=remat_policy)
            diff = {k: full[k] for k in grad_names}
            (_, lsum), grads = jax.value_and_grad(
                loss_of, has_aux=True)(diff)
            loss_out = jax.lax.psum(lsum, dp_ax) / nglob_box["n"]

            t = state["t"] + 1
            clip = hp.get("clip_gradient", 0.0)
            rescale = hp.get("rescale_grad", 1.0)
            wd = hp.get("wd", 0.0)
            new_params = dict(pvals)
            new_slots = dict(slots)

            def opt_math(g, w, slot, k):
                # identical op order to the replicated step's update
                if clip and clip > 0:
                    g = jnp.clip(g, -clip, clip)
                if kind != "adamw":
                    g = g + wd * w
                if kind == "sgd":
                    return w - lr * g, slot
                if kind == "sgd_mom":
                    m = hp["momentum"] * slot - lr * g
                    return w + m, m
                b1, b2, eps = hp["beta1"], hp["beta2"], hp["epsilon"]
                m, v = slot
                m = b1 * m + (1 - b1) * g
                v = b2 * v + (1 - b2) * jnp.square(g)
                tf = t.astype(jnp.float32)
                lr_t = lr * jnp.sqrt(1 - b2 ** tf) / (1 - b1 ** tf)
                new_w = w - lr_t * m / (jnp.sqrt(v) + eps)
                if kind == "adamw":
                    new_w = new_w - lr * wd * w
                return new_w, (m, v)

            for k in grad_names:
                d = zdim[k]
                slot = new_slots.get(k)
                if d is None:
                    # no dp-divisible dim: replicated update, grads psum'd
                    g = jax.lax.psum(grads[k], dp_ax)
                    g = g.astype(jnp.float32) * rescale
                    w = full[k].astype(jnp.float32)
                    new_w, slot = opt_math(g, w, slot, k)
                    new_params[k] = new_w.astype(pvals[k].dtype)
                else:
                    # reduce-scatter the partial grads: each device holds
                    # only its slot shard of the summed gradient
                    gs = jax.lax.psum_scatter(grads[k], dp_ax,
                                              scatter_dimension=d,
                                              tiled=True)
                    gs = gs.astype(jnp.float32) * rescale
                    shard = full[k].shape[d] // ndev
                    off = jax.lax.axis_index(dp_ax) * shard
                    wsh = jax.lax.dynamic_slice_in_dim(full[k], off, shard,
                                                       axis=d)
                    w = wsh.astype(jnp.float32)
                    new_w, slot = opt_math(gs, w, slot, k)
                    new_shard = new_w.astype(pvals[k].dtype)
                    if rest_sharded[k]:
                        new_params[k] = new_shard
                    else:
                        new_params[k] = jax.lax.all_gather(
                            new_shard, dp_ax, axis=d, tiled=True)
                if k in new_slots:
                    new_slots[k] = slot
            return ({"params": new_params, "slots": new_slots, "t": t},
                    loss_out)

        if self._opt_kind == "sgd":
            slot_spec_tree = {}
        elif self._opt_kind == "sgd_mom":
            slot_spec_tree = {k: sspec[k] for k in grad_names}
        else:
            slot_spec_tree = {k: (sspec[k],) * 2 for k in grad_names}
        state_spec = {"params": pspec, "slots": slot_spec_tree, "t": P()}
        smapped = jax.shard_map(
            body, mesh=mesh,
            in_specs=(state_spec, P(dp_ax), P(dp_ax), P(), P()),
            out_specs=(state_spec, P()),
            check_vma=False)

        repl = NamedSharding(mesh, P())
        data_sh = NamedSharding(mesh, P(dp_ax))
        param_sh = {k: NamedSharding(mesh, pspec[k]) for k in pvals0}
        slot_sh = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), slot_spec_tree,
            is_leaf=lambda s: isinstance(s, P))
        state_sh = {"params": param_sh, "slots": slot_sh, "t": repl}
        self._step = jax.jit(
            smapped,
            in_shardings=(state_sh, data_sh, data_sh, repl, repl),
            out_shardings=(state_sh, repl),
            donate_argnums=(0,) if donate else (),
        )
        return self._step

    def state_arrays(self, state):
        """Flatten a training state into ``{name: jax.Array}`` with ZeRO
        slot naming ("slot0::<param>"/"slot1::<param>") — the layout
        `ShardingConfig.param_spec` routes through `slot_spec`, so
        `save_checkpoint(..., sharding=cfg)` writes dp-sharded slot
        slabs and `load_resharded` places them back under any mesh."""
        flat = dict(state["params"])
        for k, s in state["slots"].items():
            if isinstance(s, tuple):
                flat["slot0::" + k] = s[0]
                flat["slot1::" + k] = s[1]
            else:
                flat["slot0::" + k] = s
        return flat

    def save_state(self, path, state, step=0, extra=None, keep=None):
        """Format-2 sharded checkpoint of the full training state
        (params + ZeRO slot shards + step counter)."""
        from .checkpoint import save_checkpoint
        extra = dict(extra or {})
        extra["t"] = int(state["t"])
        extra["opt_kind"] = self._opt_kind
        return save_checkpoint(path, self.state_arrays(state), step=step,
                               extra=extra, keep=keep,
                               sharding=self.sharding)

    def load_state(self, path, step=None):
        """Restore a `save_state` checkpoint under THIS trainer's (possibly
        different/shrunken) ShardingConfig: params and slot shards come
        back placed per the current mesh (slice-on-read)."""
        from .checkpoint import load_resharded
        shapes = {}
        slot_names = {}
        for k, p in self._params.items():
            shape = tuple(p._data._data.shape)
            shapes[k] = shape
            if p.grad_req != "null" and self._opt_kind != "sgd":
                names = ["slot0::" + k] if self._opt_kind == "sgd_mom" \
                    else ["slot0::" + k, "slot1::" + k]
                slot_names[k] = names
                for n in names:
                    shapes[n] = shape
        arrs, meta = load_resharded(path, shapes, self.sharding, step=step)
        slots = {}
        for k, names in slot_names.items():
            if self._opt_kind == "sgd_mom":
                slots[k] = arrs[names[0]]
            else:
                slots[k] = (arrs[names[0]], arrs[names[1]])
        t = jnp.asarray(int(meta.get("extra", {}).get("t", 0)), jnp.int32)
        state = {"params": {k: arrs[k] for k in self._params},
                 "slots": slots, "t": t}
        return state, meta

    def step(self, state, batch, labels, key, lr):
        if self._step is None:
            self.build_step()
        batch = batch._data if isinstance(batch, ndarray) else batch
        if isinstance(labels, ndarray):
            labels = labels._data
        elif isinstance(labels, tuple):
            labels = tuple(l._data if isinstance(l, ndarray) else l for l in labels)
        return self._step(state, batch, labels, key, lr)

    def write_back(self, state):
        """Copy compiled-state params back into the Gluon Parameters."""
        for k, p in self._params.items():
            p._data._set_data(state["params"][k])

    def reshard(self, sharding, state):
        """Adopt a new (typically shrunk-after-chip-loss) ShardingConfig:
        re-place every state leaf onto the new mesh and drop the compiled
        step so the next call rebuilds against the new config — the fresh
        program traces under the new sharding token, so a stale program
        with the old mesh's collectives can never run (the
        collective_census gate on the resharded step checks exactly
        this).  Returns the re-placed state."""
        shard_of = sharding.param_sharding
        slot_of = sharding.slot_sharding
        pvals = {k: jax.device_put(v, shard_of(k, v.shape))
                 for k, v in state["params"].items()}
        slots = {}
        for k, s in state["slots"].items():
            if isinstance(s, tuple):
                slots[k] = tuple(jax.device_put(x, slot_of(k, x.shape))
                                 for x in s)
            else:
                slots[k] = jax.device_put(s, slot_of(k, s.shape))
        t = jax.device_put(state["t"], NamedSharding(sharding.mesh, P()))
        self.sharding = sharding
        self.mesh = sharding.mesh
        self._step = None
        return {"params": pvals, "slots": slots, "t": t}

from .checkpoint import (  # noqa: F401,E402
    save_checkpoint, load_checkpoint, wait_for_saves, list_steps,
    latest_step, verify_checkpoint, resume_training, load_resharded,
    restore_trainer_states)
from .pipeline import PipelineRunner, pipeline_apply  # noqa: F401,E402
from .moe import MoELayer  # noqa: F401,E402
