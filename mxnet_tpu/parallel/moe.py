"""Expert parallelism: mixture-of-experts with all_to_all dispatch.

Parity-plus (SURVEY.md §2.4: the reference has data parallelism only;
expert parallelism is part of this build's mesh-native scaling story).
The classic TPU MoE recipe (GShard/Switch): tokens compute router
gates locally, get packed into per-expert capacity buckets, exchange
over the `ep` mesh axis with `lax.all_to_all` (ICI), run their expert's
FFN where its weights live, and ride the inverse all_to_all home.

API:
  moe = MoELayer(num_experts, d_model, d_hidden, mesh, axis="ep")
  y = moe.apply(params, x)            # x: [tokens, d_model] per device
  params = moe.init(jax.random.key(0))
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

__all__ = ["MoELayer"]


class MoELayer:
    """Top-1 (Switch) MoE FFN with experts sharded over the `ep` axis."""

    def __init__(self, num_experts, d_model, d_hidden, mesh=None, axis="ep",
                 capacity_factor=2.0, sharding=None):
        if sharding is not None:
            mesh = sharding.mesh
        if mesh is None:
            raise ValueError("MoELayer needs mesh= or sharding=")
        self.E = num_experts
        self.d_model = d_model
        self.d_hidden = d_hidden
        self.mesh = mesh
        self.axis = axis
        self.capacity_factor = capacity_factor
        self.n_shards = mesh.shape[axis]
        assert self.E % self.n_shards == 0, \
            "num_experts must divide over the ep axis"

    def init(self, key, scale=0.02):
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "router": jax.random.normal(k1, (self.d_model, self.E),
                                        jnp.float32) * scale,
            "w_in": jax.random.normal(
                k2, (self.E, self.d_model, self.d_hidden),
                jnp.float32) * scale,
            "w_out": jax.random.normal(
                k3, (self.E, self.d_hidden, self.d_model),
                jnp.float32) * scale,
        }

    def apply(self, params, x):
        """x: [T_total, d_model] global token batch, sharded over the ep
        axis on dim 0 (each device works on T_total/shards tokens)."""
        E, shards, axis = self.E, self.n_shards, self.axis
        e_local = E // shards

        def local(router, w_in, w_out, xs):
            # xs: [T_local, D] this device's tokens; w_* arrive with a
            # leading sharded dim of size 1 (this shard's experts).
            # Capacity follows the GShard/Switch recipe from PER-DEVICE
            # tokens, so the [E, C, D] dispatch buffers stay constant as
            # the ep axis grows (per-expert total capacity = shards * C).
            C = max(1, int(self.capacity_factor * xs.shape[0] / E))
            w_in = w_in[0]                            # [e_local, D, H]
            w_out = w_out[0]                          # [e_local, H, D]
            logits = xs @ router                      # [T, E]
            gates = jax.nn.softmax(logits, -1)
            expert = jnp.argmax(gates, -1)            # [T] top-1
            gate = jnp.take_along_axis(gates, expert[:, None], -1)[:, 0]

            # position of each token within its expert's bucket
            onehot = jax.nn.one_hot(expert, E, dtype=jnp.int32)  # [T, E]
            pos = jnp.cumsum(onehot, 0) * onehot      # 1-based positions
            slot = jnp.sum(pos, -1) - 1               # [T] 0-based
            keep = slot < C                           # capacity drop mask

            # pack tokens into [E, C, D] dispatch buckets
            buckets = jnp.zeros((E, C, xs.shape[-1]), xs.dtype)
            idx_e = jnp.where(keep, expert, 0)
            idx_c = jnp.where(keep, slot, 0)
            contrib = jnp.where(keep[:, None], xs, 0.0)
            buckets = buckets.at[idx_e, idx_c].add(contrib)

            # all_to_all: [E, C, D] → [shards, e_local, C, D] exchanged so
            # each device ends with ITS experts' buckets from every peer
            b = buckets.reshape(shards, e_local, C, -1)
            recv = lax.all_to_all(b, axis, split_axis=0, concat_axis=0,
                                  tiled=False)
            # recv: [shards, e_local, C, D] (peer-major)

            # expert FFN where the weights live
            def ffn(tok, wi, wo):
                return jax.nn.relu(tok @ wi) @ wo
            out = jax.vmap(
                lambda blk, wi, wo: ffn(blk.reshape(-1, blk.shape[-1]),
                                        wi, wo).reshape(blk.shape),
                in_axes=(1, 0, 0),
            )(recv, w_in, w_out)                      # [e_local, shards, C, D]
            out = jnp.swapaxes(out, 0, 1)             # [shards, e_local, C, D]

            # inverse all_to_all: results return to the token's device
            back = lax.all_to_all(out, axis, split_axis=0, concat_axis=0,
                                  tiled=False)
            back = back.reshape(E, C, -1)

            # unpack: each kept token reads its bucket slot, scaled by gate
            y = back[idx_e, idx_c] * gate[:, None]
            return jnp.where(keep[:, None], y, 0.0)

        return shard_map(
            local, mesh=self.mesh,
            in_specs=(P(), P(axis), P(axis), P(axis)),
            out_specs=P(axis),
            check_vma=False,
        )(params["router"],
          params["w_in"].reshape(self.n_shards, e_local, self.d_model,
                                 self.d_hidden),
          params["w_out"].reshape(self.n_shards, e_local, self.d_hidden,
                                  self.d_model),
          x)

    def dense_reference(self, params, x):
        """Every-expert-on-every-token reference (no EP, no capacity
        drops with big enough capacity) for correctness checks."""
        logits = x @ params["router"]
        gates = jax.nn.softmax(logits, -1)
        expert = jnp.argmax(gates, -1)
        gate = jnp.take_along_axis(gates, expert[:, None], -1)[:, 0]
        outs = jnp.einsum("td,edh->teh", x, params["w_in"])
        outs = jax.nn.relu(outs)
        outs = jnp.einsum("teh,ehd->ted", outs, params["w_out"])
        sel = jnp.take_along_axis(
            outs, expert[:, None, None].repeat(outs.shape[-1], -1),
            1)[:, 0]
        return sel * gate[:, None]
