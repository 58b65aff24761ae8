"""Pipeline parallelism: GPipe-style stage execution over a mesh axis.

Parity-plus (SURVEY.md §2.4: the reference has data parallelism ONLY —
this axis is where the TPU build goes beyond it, per the §7 design
stance).  Stages live on a `pp` mesh axis; microbatches stream through
with `jax.lax.ppermute` passing activations between neighbor stages, the
standard TPU pipelining recipe (scaling-book: pipelining = shifting
buffers over ICI while the MXU stays busy).

API:
  stages = [fn_0, ..., fn_{S-1}]      # per-stage (params, x) -> y
  runner = PipelineRunner(stages, mesh, axis="pp")
  y = runner.apply(stage_params, x, n_microbatches=M)

Each fn must map equal input/output shapes across stage boundaries
(classic GPipe layering).  The whole loop compiles to one XLA program
under shard_map; collectives ride ICI.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = ["PipelineRunner", "pipeline_apply"]


class PipelineRunner:
    def __init__(self, stage_fns, mesh=None, axis="pp", sharding=None):
        if sharding is not None:
            mesh = sharding.mesh
        if mesh is None:
            raise ValueError("PipelineRunner needs mesh= or sharding=")
        self.stage_fns = list(stage_fns)
        self.mesh = mesh
        self.axis = axis
        self.n_stages = mesh.shape[axis]
        assert len(self.stage_fns) == self.n_stages, \
            "need one stage fn per device on the %r axis" % axis

    def apply(self, stage_params, x, n_microbatches=None):
        """Run x (batch-major) through the pipeline.

        stage_params: list (len S) of per-stage param pytrees; x is split
        into microbatches along axis 0; output matches x's leading shape.
        """
        S = self.n_stages
        M = S if n_microbatches is None else int(n_microbatches)
        B = x.shape[0]
        assert M >= 1, "n_microbatches must be >= 1"
        assert B % M == 0, "batch %d not divisible into %d microbatches" \
            % (B, M)
        axis = self.axis
        fns = self.stage_fns

        # stack per-stage params on a leading axis sharded over pp; stage
        # fns may differ (lax.switch dispatch) but their param pytrees
        # must share structure AND leaf shapes so they stack
        structs = [jax.tree.structure(p) for p in stage_params]
        if any(s != structs[0] for s in structs[1:]):
            raise ValueError(
                "pipeline stages must share one param pytree structure "
                "(got %s); pad heterogeneous stages to a common structure"
                % ([str(s) for s in structs]))
        stacked = jax.tree.map(lambda *ps: jnp.stack(ps), *stage_params)
        mb = x.reshape(M, B // M, *x.shape[1:])

        def stage_apply(params, h, idx):
            """Dispatch to this stage's fn (all stages traced via switch —
            stage code is usually identical layers, branch is cheap)."""
            return lax.switch(idx, [lambda p, a, f=f: f(p, a)
                                    for f in fns], params, h)

        def per_stage(params_stk, mb_all):
            # params_stk: [1, ...] this stage's params; mb_all: all
            # microbatches replicated
            sidx = lax.axis_index(axis)
            params = jax.tree.map(lambda a: a[0], params_stk)
            nsteps = M + S - 1
            zero = jnp.zeros_like(mb_all[0])

            def body(carry, t):
                outputs, recv = carry
                # stage 0 feeds from the microbatch stream; others from
                # the neighbor's activation
                feed = jnp.where(
                    (sidx == 0),
                    mb_all[jnp.clip(t, 0, M - 1)], recv)
                h = stage_apply(params, feed, sidx)
                # active iff this stage has work at step t
                active = (t >= sidx) & (t < M + sidx)
                h = jnp.where(active, h, zero)
                # pass activations down the ring (stage i → i+1)
                nxt = lax.ppermute(
                    h, axis, [(i, (i + 1) % S) for i in range(S)])
                # last stage emits output for microbatch t - (S-1)
                out_idx = t - (S - 1)
                emit = (sidx == S - 1) & (out_idx >= 0)
                outputs = jnp.where(
                    emit,
                    outputs.at[jnp.clip(out_idx, 0, M - 1)].set(h),
                    outputs)
                return (outputs, nxt), None

            outputs0 = jnp.zeros((M,) + mb_all.shape[1:], mb_all.dtype)
            (outputs, _), _ = lax.scan(body, (outputs0, zero),
                                       jnp.arange(nsteps))
            # only the last stage holds real outputs (zeros elsewhere):
            # psum broadcasts them without materializing S copies
            if S > 1:
                outputs = lax.psum(outputs, axis)
            return outputs

        out = shard_map(
            per_stage, mesh=self.mesh,
            in_specs=(P(axis), P()),  # params sharded by stage
            out_specs=P(),
            check_vma=False,
        )(stacked, mb)
        return out.reshape(B, *out.shape[2:])


def pipeline_apply(stage_fns, stage_params, x, mesh=None, axis="pp",
                   n_microbatches=None, sharding=None):
    """Functional one-shot wrapper around PipelineRunner."""
    return PipelineRunner(stage_fns, mesh, axis, sharding=sharding).apply(
        stage_params, x, n_microbatches)


# ---------------------------------------------------------------------------
# Trainer-grade pipeline training (VERDICT r4 #10: a real model trains
# through pp, not just a toy forward)
# ---------------------------------------------------------------------------
class PipelineTrainer:
    """GPipe training over a ``pp`` mesh axis with the praxis pattern:
    a replicated prologue (input stem), S homogeneous pipelined body
    stages (one per device on the axis), and a replicated epilogue
    (head + loss).  Forward microbatches stream through ``ppermute``;
    the backward pipeline is the AD transpose of the same program
    (reverse ppermute), so fwd+bwd+update compile into ONE XLA
    executable — mirroring DataParallelTrainer's contract.

    Stages must be structurally identical Gluon blocks (the standard
    pipelined-transformer shape: repeated layers); the prologue/epilogue
    absorb the heterogeneous edges.

    API (mirrors DataParallelTrainer):
      t = PipelineTrainer(prologue, stages, epilogue, loss_fn,
                          "sgd", {"learning_rate": .1}, mesh)
      state = t.init_state(); t.build_step()
      state, loss = t.step(state, x, y, lr)
    """

    def __init__(self, prologue, stages, epilogue, loss_fn, optimizer,
                 hp, mesh=None, axis="pp", n_microbatches=None,
                 sharding=None):
        from . import functionalize  # late: parallel/__init__ imports us

        if sharding is not None:
            mesh = sharding.mesh
        if mesh is None:
            raise ValueError("PipelineTrainer needs mesh= or sharding=")
        self.mesh = mesh
        self.axis = axis
        self.loss_fn = loss_fn
        self._hp = dict(hp or {})
        self._opt = optimizer
        if optimizer == "sgd" and self._hp.get("momentum"):
            self._opt = "sgd_mom"
        S = mesh.shape[axis]
        assert len(stages) == S, \
            "need one stage block per device on %r (%d != %d)" % (
                axis, len(stages), S)
        self.n_stages = S
        self.n_microbatches = n_microbatches or S

        self._pro_fn, self._pro_params = functionalize(prologue,
                                                       train=True) \
            if prologue is not None else (None, {})
        self._epi_fn, self._epi_params = functionalize(epilogue,
                                                       train=True) \
            if epilogue is not None else (None, {})
        self._stage_fns = []
        self._stage_params = []
        for st in stages:
            f, p = functionalize(st, train=True)
            self._stage_fns.append(f)
            self._stage_params.append(p)
        structs = [sorted(p.keys()) for p in self._stage_params]
        if any(s != structs[0] for s in structs[1:]):
            raise ValueError("pipeline stages must be structurally "
                             "identical blocks")
        self._step = None

    def _vals(self, params):
        return {k: p._data._data for k, p in params.items()}

    def init_state(self):
        stacked = {}
        keys = sorted(self._stage_params[0].keys())
        sh = NamedSharding(self.mesh, P(self.axis))
        repl = NamedSharding(self.mesh, P())
        stage_vals = [self._vals(p) for p in self._stage_params]
        for k in keys:
            leaves = [v[k] for v in stage_vals]
            stacked[k] = jax.device_put(jnp.stack(leaves), sh)
        pro = {k: jax.device_put(v, repl)
               for k, v in self._vals(self._pro_params).items()}
        epi = {k: jax.device_put(v, repl)
               for k, v in self._vals(self._epi_params).items()}
        params = {"stages": stacked, "pro": pro, "epi": epi}
        slots = (jax.tree.map(
            lambda v: jnp.zeros(v.shape, jnp.float32), params)
            if self._opt == "sgd_mom" else {})
        return {"params": params, "slots": slots}

    def _forward(self, params, x, key=None, want_aux=False):
        """Full forward: prologue → pipelined stages → epilogue.

        Runs every part in TRAINING mode (batch stats, dropout given a
        key).  With want_aux=True also returns the aux updates — BN
        running stats etc. — for the prologue/epilogue and per-stage
        params (stage aux from each stage's LAST active microbatch, the
        standard GPipe convention)."""
        axis, S, M = self.axis, self.n_stages, self.n_microbatches
        stage_fn = self._stage_fns[0]  # homogeneous

        keys = (list(jax.random.split(key, 3)) if key is not None
                else [None, None, None])
        h = x
        pro_aux = {}
        if self._pro_fn is not None:
            h, pro_aux = self._pro_fn(params["pro"], h, key=keys[0])
        B = h.shape[0]
        if B % M != 0:
            raise ValueError("batch %d not divisible into %d microbatches"
                             % (B, M))
        mb = h.reshape(M, B // M, *h.shape[1:])
        stage_key = keys[1]

        def per_stage(params_stk, mb_all):
            sidx = lax.axis_index(axis)
            sparams = jax.tree.map(lambda a: a[0], params_stk)
            nsteps = M + S - 1
            zero = jnp.zeros_like(mb_all[0])

            # learn which params the stage actually MUTATES (BN running
            # stats) with one abstract trace — the aux carry must hold
            # ONLY those: seeding it with all of sparams would make the
            # write-back in step() overwrite freshly gradient-stepped
            # weights with their forward-time values
            try:
                aux_shapes = jax.eval_shape(
                    lambda p, h: stage_fn(p, h, key=None)[1],
                    sparams, mb_all[0])
            except Exception:  # dropout stages demand a key at trace
                aux_shapes = jax.eval_shape(
                    lambda p, h: stage_fn(p, h,
                                          key=jax.random.key(0))[1],
                    sparams, mb_all[0])
            aux_keys = sorted(aux_shapes.keys())
            aux0 = {k: sparams[k] for k in aux_keys}

            def body(carry, t):
                outputs, recv, aux_carry = carry
                feed = jnp.where(sidx == 0,
                                 mb_all[jnp.clip(t, 0, M - 1)], recv)
                skey = (jax.random.fold_in(stage_key, t)
                        if stage_key is not None else None)
                hh, st_aux = stage_fn(sparams, feed, key=skey)
                active = (t >= sidx) & (t < M + sidx)
                hh = jnp.where(active, hh, zero)
                # aux (running stats): keep the last ACTIVE microbatch's
                # update per stage; inactive steps must not clobber
                new_aux = {k: jnp.where(active, st_aux[k], aux_carry[k])
                           for k in aux_keys}
                nxt = lax.ppermute(
                    hh, axis, [(i, (i + 1) % S) for i in range(S)])
                out_idx = t - (S - 1)
                emit = (sidx == S - 1) & (out_idx >= 0)
                outputs = jnp.where(
                    emit, outputs.at[jnp.clip(out_idx, 0, M - 1)].set(hh),
                    outputs)
                return (outputs, nxt, new_aux), None

            outputs0 = jnp.zeros((M,) + mb_all.shape[1:], mb_all.dtype)
            (outputs, _, aux_final), _ = lax.scan(
                body, (outputs0, zero, aux0), jnp.arange(nsteps))
            if S > 1:
                outputs = lax.psum(outputs, axis)
            # re-add the stage axis so out_specs=P(axis) reassembles the
            # (S, ...) stacked layout of params["stages"]
            aux_final = jax.tree.map(lambda a: a[None], aux_final)
            return outputs, aux_final

        out, stage_aux = shard_map(
            per_stage, mesh=self.mesh,
            in_specs=(P(axis), P()), out_specs=(P(), P(axis)),
            check_vma=False)(params["stages"], mb)
        out = out.reshape(B, *out.shape[2:])
        epi_aux = {}
        if self._epi_fn is not None:
            out, epi_aux = self._epi_fn(params["epi"], out, key=keys[2])
        if want_aux:
            return out, {"pro": pro_aux, "stages": stage_aux,
                         "epi": epi_aux}
        return out

    def build_step(self, donate=True):
        hp = self._hp
        kind = self._opt
        loss_fn = self.loss_fn

        def step(state, x, y, lr, key):
            from mxnet_tpu import autograd as ag
            from mxnet_tpu.ndarray import _wrap_value, ndarray as ndcls

            def loss_of(params):
                out, aux = self._forward(params, x, key=key,
                                         want_aux=True)
                with ag._RecordingStateScope(False, True):
                    l = loss_fn(_wrap_value(out), _wrap_value(y))
                l = jnp.mean(l._data if isinstance(l, ndcls) else l)
                return l, aux

            (loss_val, aux), grads = jax.value_and_grad(
                loss_of, has_aux=True)(state["params"])
            lr_ = lr
            if kind == "sgd_mom":
                mom = hp.get("momentum", 0.9)
                new_slots = jax.tree.map(
                    lambda s, g: mom * s - lr_ * g.astype(jnp.float32),
                    state["slots"], grads)
                new_params = jax.tree.map(
                    lambda p, m: (p.astype(jnp.float32) + m).astype(p.dtype),
                    state["params"], new_slots)
            else:
                new_params = jax.tree.map(
                    lambda p, g: (p.astype(jnp.float32)
                                  - lr_ * g.astype(jnp.float32)
                                  ).astype(p.dtype),
                    state["params"], grads)
                new_slots = state["slots"]
            # aux updates (BN running stats, non-trainable) overwrite the
            # gradient-stepped values — their grads are zero in training
            # mode, so this is the only real update they get
            for group, upd in aux.items():
                for k, v in upd.items():
                    new_params[group][k] = v.astype(
                        new_params[group][k].dtype)
            return {"params": new_params, "slots": new_slots}, loss_val

        self._step = jax.jit(step,
                             donate_argnums=(0,) if donate else ())
        return self._step

    def step(self, state, x, y, lr=None, key=None):
        from mxnet_tpu.ndarray import ndarray as ndcls
        if self._step is None:
            self.build_step()
        x = x._data if isinstance(x, ndcls) else x
        y = y._data if isinstance(y, ndcls) else y
        if lr is None:
            lr = self._hp.get("learning_rate", 0.01)
        if key is None:
            # advance an internal counter: a FIXED default key would
            # replay identical dropout masks on every training step
            self._auto_step = getattr(self, "_auto_step", 0) + 1
            key = jax.random.fold_in(jax.random.key(0), self._auto_step)
        return self._step(state, x, y, lr, key)


__all__ += ["PipelineTrainer"]
