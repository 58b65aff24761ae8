"""Ring attention: sequence/context parallelism over the device mesh.

The reference has NO sequence parallelism (SURVEY.md §5.7 — its only
long-context mechanism is the O(L·w) sliding-window kernel,
src/operator/contrib/transformer.cc:847).  This module goes beyond
capability parity: sequence length shards across a mesh axis, K/V blocks
rotate around the ICI ring via `lax.ppermute` while every device keeps a
flash-attention running (max, sum, acc) triple — O(L/n) memory per chip and
compute/communication overlap, the standard TPU ring-attention recipe.

Composable with dp/tp axes: q/k/v enter sharded (B over dp, L over sp) and
the kernel is a shard_map over the same mesh.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P


def _flash_block(q, k_blk, v_blk, o, m, l, scale, q_start, k_start,
                 causal, window):
    """One blockwise-attention accumulation step (fp32 accumulators)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k_blk,
                   preferred_element_type=jnp.float32) * scale
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 3)
    mask = jnp.ones(s.shape, jnp.bool_)
    if causal:
        mask = mask & (k_pos <= q_pos)
    if window is not None:
        mask = mask & (jnp.abs(q_pos - k_pos) <= window)
    s = jnp.where(mask, s, -jnp.inf)

    m_blk = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m, m_blk)
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - m_safe)
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
    l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    o_new = o * alpha + jnp.einsum("bhqk,bhkd->bhqd", p,
                                   v_blk.astype(jnp.float32))
    return o_new, m_new, l_new


def ring_attention(q, k, v, mesh=None, seq_axis="sp", causal=False,
                   window=None, scale=None, sharding=None, spec=None):
    """Attention over sequence-sharded q/k/v: (B, H, L, D) with L split
    across `seq_axis`.  Returns (B, H, L, D) with the same sharding.

    `spec` overrides the default P(None, None, seq_axis, None) so batch/
    head dims can ride dp/tp at the same time (the body only indexes the
    `seq_axis`, so any extra sharded dims compose transparently)."""
    if sharding is not None:
        mesh = sharding.mesh
    if mesh is None:
        raise ValueError("ring_attention needs mesh= or sharding=")
    D = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    n = mesh.shape[seq_axis]

    def local(qs, ks, vs):
        idx = jax.lax.axis_index(seq_axis)
        Lc = qs.shape[-2]
        qf = qs.astype(jnp.float32)
        o = jnp.zeros(qs.shape[:-1] + (D,), jnp.float32)
        m = jnp.full(qs.shape[:-1] + (1,), -jnp.inf, jnp.float32)
        l = jnp.zeros(qs.shape[:-1] + (1,), jnp.float32)
        q_start = idx * Lc

        k_rot, v_rot = ks, vs
        src = idx
        perm = [(i, (i + 1) % n) for i in range(n)]
        for step in range(n):
            k_start = src * Lc
            o, m, l = _flash_block(qf, k_rot.astype(jnp.float32),
                                   v_rot, o, m, l, scale,
                                   q_start, k_start, causal, window)
            if step + 1 < n:
                # rotate K/V to the next device over the ICI ring; the
                # matmul for the current block overlaps the transfer
                k_rot = jax.lax.ppermute(k_rot, seq_axis, perm)
                v_rot = jax.lax.ppermute(v_rot, seq_axis, perm)
                src = (src - 1) % n
        l = jnp.where(l == 0.0, 1.0, l)
        return (o / l).astype(qs.dtype)

    if spec is None:
        spec = P(None, None, seq_axis, None)
    return shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec)(q, k, v)


def ring_attention_sharded(q, k, v, mesh=None, seq_axis="sp", sharding=None,
                           **kw):
    """Convenience: device_put inputs with the sequence sharding first."""
    if sharding is not None:
        mesh = sharding.mesh
    sh = NamedSharding(mesh, P(None, None, seq_axis, None))
    return ring_attention(jax.device_put(q, sh), jax.device_put(k, sh),
                          jax.device_put(v, sh), mesh, seq_axis, **kw)
