"""Attention kernels: fused multi-head projections + flash attention.

Parity: reference `src/operator/contrib/transformer.cc`:
- `_contrib_interleaved_matmul_selfatt_qk` (:650), `_selfatt_valatt` (:693),
  `_encdec_qk` (:740), `_encdec_valatt` — fused MHA matmuls on interleaved
  QKV projections (the BERT fast path);
- `_contrib_sldwin_atten_*` (:847-1038) — sliding-window (Longformer)
  attention;
- `div_sqrt_dim` (:600).

TPU-native: the interleaved matmuls are einsums (XLA maps them straight to
the MXU and fuses the scale); the full softmax(QK^T)V chain is provided as
`flash_attention` — a Pallas blockwise kernel with O(L) memory on TPU
(see ops/pallas/flash_attention.py), replacing both the O(L^2) fused matmul
path and the sliding-window kernels; sliding-window masking is a flag of the
same kernel.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax


def div_sqrt_dim(x):
    return x / math.sqrt(x.shape[-1])


# --------------------------------------------------------------------------
# interleaved fused MHA projections (transformer.cc:650-826)
# qkv layout: (L, B, num_heads * 3 * head_dim) with per-head [q; k; v]
# --------------------------------------------------------------------------
def interleaved_matmul_selfatt_qk(queries_keys_values, heads):
    L, B, E = queries_keys_values.shape
    head_dim = E // heads // 3
    x = queries_keys_values.reshape(L, B, heads, 3, head_dim)
    q = x[:, :, :, 0]  # (L, B, H, D)
    k = x[:, :, :, 1]
    scale = 1.0 / math.sqrt(head_dim)
    # output (B*H, L, L) like the reference
    att = jnp.einsum("lbhd,mbhd->bhlm", q * scale, k)
    return att.reshape(B * heads, L, L)


def interleaved_matmul_selfatt_valatt(queries_keys_values, attention, heads):
    L, B, E = queries_keys_values.shape
    head_dim = E // heads // 3
    x = queries_keys_values.reshape(L, B, heads, 3, head_dim)
    v = x[:, :, :, 2]  # (L, B, H, D)
    att = attention.reshape(B, heads, L, L)
    out = jnp.einsum("bhlm,mbhd->lbhd", att, v)
    return out.reshape(L, B, heads * head_dim)


def interleaved_matmul_encdec_qk(queries, keys_values, heads):
    Lq, B, E = queries.shape
    Lk = keys_values.shape[0]
    head_dim = E // heads
    q = queries.reshape(Lq, B, heads, head_dim)
    kv = keys_values.reshape(Lk, B, heads, 2, head_dim)
    k = kv[:, :, :, 0]
    scale = 1.0 / math.sqrt(head_dim)
    att = jnp.einsum("lbhd,mbhd->bhlm", q * scale, k)
    return att.reshape(B * heads, Lq, Lk)


def interleaved_matmul_encdec_valatt(keys_values, attention, heads):
    Lk, B, E2 = keys_values.shape
    head_dim = E2 // heads // 2
    kv = keys_values.reshape(Lk, B, heads, 2, head_dim)
    v = kv[:, :, :, 1]
    Lq = attention.shape[1]
    att = attention.reshape(B, heads, Lq, Lk)
    out = jnp.einsum("bhlm,mbhd->lbhd", att, v)
    return out.reshape(Lq, B, heads * head_dim)


# --------------------------------------------------------------------------
# reference (XLA, non-Pallas) attention — correctness oracle & CPU path
# --------------------------------------------------------------------------
def attention_reference(q, k, v, mask=None, causal=False, window=None,
                        scale=None, dropout=0.0, dropout_key=None,
                        kv_length=None):
    """q,k,v: (B, H, L, D). Returns (B, H, L, D).  `kv_length` is a (B,)
    valid key count (padding); `dropout` drops normalized attention
    probabilities using `dropout_key` (a jax PRNG key)."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32) * scale,
                        k.astype(jnp.float32))
    Lq, Lk = logits.shape[-2], logits.shape[-1]
    if causal:
        cm = jnp.tril(jnp.ones((Lq, Lk), bool), k=Lk - Lq)
        logits = jnp.where(cm, logits, -jnp.inf)
    if window is not None:
        qi = jnp.arange(Lq)[:, None] + (Lk - Lq)
        ki = jnp.arange(Lk)[None, :]
        wm = jnp.abs(qi - ki) <= window
        logits = jnp.where(wm, logits, -jnp.inf)
    if kv_length is not None:
        km = jnp.arange(Lk)[None, None, None, :] < jnp.asarray(
            kv_length).reshape(-1)[:, None, None, None]
        logits = jnp.where(km, logits, -jnp.inf)
    if mask is not None:
        logits = jnp.where(mask, logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)
    if dropout and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout, p.shape)
        p = p * keep / (1.0 - dropout)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


# Which path the last flash_attention call took: "pallas" | "pallas-interpret"
# | "xla".  Tests assert on this to guarantee the kernel is actually used.
last_path = None


def _pallas_mode():
    """'compiled' on a TPU backend, 'interpret' when forced via
    MXNET_FLASH_ATTENTION=interpret (CPU test lane), None when disabled
    or on any other backend."""
    from .pallas import kernel_mode
    return kernel_mode("MXNET_FLASH_ATTENTION")


def _flash_local(q, k, v, mask=None, causal=False, window=None, scale=None,
                 dropout=0.0, dropout_key=None, kv_length=None):
    """Single-device flash attention dispatch: Pallas kernel (compiled or
    interpret) when eligible, XLA reference otherwise.  This is the
    per-shard body of the sharded entry too."""
    global last_path
    if not 0.0 <= dropout < 1.0:
        # matches the eager Dropout op's validation; rate >= 1 would put
        # a 1/(1-rate) = inf scale through the kernel (NaN outputs)
        raise ValueError("flash_attention: dropout must be in [0, 1), got %r"
                         % (dropout,))
    if dropout and dropout_key is None:
        raise ValueError("flash_attention: dropout > 0 requires dropout_key")
    mode = _pallas_mode()
    if mask is None and mode is not None and q.shape[-2] == k.shape[-2]:
        from .pallas.flash_attention import flash_attention_tpu
        seed = None
        if dropout:
            seed = jax.random.bits(dropout_key, (1,), jnp.uint32)
        out = flash_attention_tpu(q, k, v, causal=causal, window=window,
                                  scale=scale, dropout=float(dropout),
                                  seed=seed, kv_length=kv_length,
                                  interpret=(mode == "interpret"))
        last_path = "pallas" if mode == "compiled" else "pallas-interpret"
        return out
    last_path = "xla"
    return attention_reference(q, k, v, mask=mask, causal=causal,
                               window=window, scale=scale, dropout=dropout,
                               dropout_key=dropout_key, kv_length=kv_length)


# --------------------------------------------------------------------------
# mesh-sharded flash attention (shard_map entry over the named mesh)
# --------------------------------------------------------------------------
# Which sharded route the last flash_attention call took: "shard_map"
# (dp×tp shard_map around the local kernel), "ring" (sequence-sharded sp
# route), or None (unsharded dispatch).  Tests assert on this.
last_sharded = None


def _active_sharding():
    """The ACTIVE ShardingConfig the sharded flash entry should serve, if
    any (``pallas.gspmd_config``; MXNET_SHARDED_FLASH=0 switches the
    entry off).  Inside a manual-collective region operands are already
    per-shard local, and a nested shard_map over the same mesh axes would
    be rejected."""
    import os
    from .pallas import gspmd_config
    flag = os.environ.get("MXNET_SHARDED_FLASH", "").lower()
    if flag in ("0", "off", "false"):
        return None
    return gspmd_config()


def _sharded_eligible(cfg, q, k, mask, dropout, kv_length):
    """Whether the sharded entry can serve this call: self-attention
    (Lq == Lk, no dense mask), 4-D heads layout, and every sharded dim
    divisible by its mesh axis.  The sp (ring) route additionally has no
    dropout/kv_length support — those fall back to the local dispatch."""
    if mask is not None or getattr(q, "ndim", 0) != 4:
        return False
    if q.shape[-2] != k.shape[-2]:
        return False
    B, H, L, _ = q.shape
    dp, tp, sp = (cfg.axis_size("dp"), cfg.axis_size("tp"),
                  cfg.axis_size("sp"))
    if dp * tp * sp == 1:
        return False
    if B % dp or H % tp or L % sp:
        return False
    if sp > 1 and (dropout or kv_length is not None):
        return False
    return True


def _splash_ok(q):
    """Whether a causal per-shard call routes to jax's TPU splash-attention
    kernel (SNIPPETS [2] pattern): the compiled Pallas lane, not switched
    off by MXNET_SPLASH_ATTENTION, and a shape the kernel tiles — sequence
    and head_dim both multiples of the 128-lane width."""
    import os
    flag = os.environ.get("MXNET_SPLASH_ATTENTION", "").lower()
    if flag in ("0", "off", "false"):
        return False
    return (_pallas_mode() == "compiled"
            and q.shape[-2] % 128 == 0 and q.shape[-1] % 128 == 0)


def _splash_causal(qb, kb, vb, scale):
    """Per-shard splash-attention call: qb (Bl, Hl, L, D) -> same.  The
    splash kernel takes (H, L, D) with scale folded into q."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as _sk, splash_attention_mask as _sm)
    Hl, L = qb.shape[1], qb.shape[2]
    mhm = _sm.MultiHeadMask([_sm.CausalMask((L, L)) for _ in range(Hl)])
    kern = _sk.make_splash_mha(mhm, head_shards=1, q_seq_shards=1)
    s = scale if scale is not None else 1.0 / math.sqrt(qb.shape[-1])
    out = jax.vmap(kern)((qb * s).astype(qb.dtype), kb, vb)
    return out.astype(qb.dtype)


def flash_attention_sharded(q, k, v, cfg=None, causal=False, window=None,
                            scale=None, dropout=0.0, dropout_key=None,
                            kv_length=None):
    """Mesh-sharded flash attention over the active (or given)
    ShardingConfig: q/k/v constrained to the config's "attention" point
    (batch over dp, heads over tp, sequence over sp in this repo's
    (B, H, L, D) layout), then

    - sp > 1: the ring route (`parallel.ring_attention`) — K/V rotate
      over the ICI ring so every query shard sees every key shard;
    - else: a `shard_map` over (dp, tp) whose per-shard body is the
      ordinary local dispatch (Pallas flash with the existing block-size
      autotune + custom VJP, or the splash causal kernel on TPU), so the
      sharded entry composes with everything the local one has.
    """
    global last_sharded, last_path
    if cfg is None:
        cfg = _active_sharding()
        if cfg is None:
            raise ValueError("flash_attention_sharded: no ShardingConfig "
                             "active (use `with cfg.scope():`) and none "
                             "passed")
    mesh = cfg.mesh
    q = cfg.constrain(q, "attention")
    k = cfg.constrain(k, "attention")
    v = cfg.constrain(v, "attention")

    if cfg.axis_size("sp") > 1:
        from mxnet_tpu.parallel.ring_attention import ring_attention
        spec = cfg.spec_for("attention", shape=q.shape)
        out = ring_attention(q, k, v, mesh=mesh, seq_axis="sp",
                             causal=causal, window=window, scale=scale,
                             spec=spec)
        last_sharded = "ring"
        last_path = "ring"
        return out

    spec = cfg.spec_for("attention", shape=q.shape, ndim=4)
    shard_axes = [a for a in ("dp", "tp") if cfg.axis_size(a) > 1]
    use_kl = kv_length is not None
    use_drop = bool(dropout) and dropout_key is not None

    args = [q, k, v]
    in_specs = [spec, spec, spec]
    if use_kl:
        args.append(jnp.asarray(kv_length).reshape(-1))
        in_specs.append(cfg.resolve_spec(("dp",), ndim=1))
    if use_drop:
        args.append(dropout_key)
        in_specs.append(jax.sharding.PartitionSpec())

    def body(*ops):
        qb, kb, vb = ops[:3]
        i = 3
        klb = None
        keyb = None
        if use_kl:
            klb = ops[i]
            i += 1
        if use_drop:
            # decorrelate the in-kernel dropout mask across shards: fold
            # the linear shard index into the key (same key on every
            # shard would repeat masks batch-slice to batch-slice)
            idx = jnp.int32(0)
            for a in shard_axes:
                idx = idx * cfg.axis_size(a) + lax.axis_index(a)
            keyb = jax.random.fold_in(ops[i], idx)
        if causal and not (window or use_drop or use_kl) \
                and _splash_ok(qb):
            global last_path
            out = _splash_causal(qb, kb, vb, scale)
            last_path = "splash"
            return out
        return _flash_local(qb, kb, vb, causal=causal, window=window,
                            scale=scale, dropout=dropout, dropout_key=keyb,
                            kv_length=klb)

    out = jax.shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                        out_specs=spec, check_vma=False)(*args)
    last_sharded = "shard_map"
    return out


def flash_attention(q, k, v, mask=None, causal=False, window=None, scale=None,
                    dropout=0.0, dropout_key=None, kv_length=None):
    """Blockwise O(L)-memory attention with a Pallas-kernel custom VJP.
    Uses the Pallas TPU kernel (fwd + bwd) on any accelerator backend;
    falls back to the XLA reference path on CPU or for features the kernel
    does not cover (dense masks, cross-attention with Lq != Lk).

    `dropout` (with `dropout_key`, a jax PRNG key) applies attention-
    probability dropout IN KERNEL (hash-based mask, regenerated by the
    backward kernels); `kv_length` (B,) is a padding mask as a per-row
    valid key count.  Both keep the call on the Pallas fast path.

    Under an ACTIVE ShardingConfig (``with cfg.scope():`` on a >1-device
    mesh, e.g. inside DataParallelTrainer's step) eligible calls reroute
    through `flash_attention_sharded` — a shard_map over the named mesh
    (gate: MXNET_SHARDED_FLASH)."""
    global last_sharded
    cfg = _active_sharding()
    if cfg is not None and _sharded_eligible(cfg, q, k, mask, dropout,
                                             kv_length):
        return flash_attention_sharded(
            q, k, v, cfg=cfg, causal=causal, window=window, scale=scale,
            dropout=dropout, dropout_key=dropout_key, kv_length=kv_length)
    last_sharded = None
    return _flash_local(q, k, v, mask=mask, causal=causal, window=window,
                        scale=scale, dropout=dropout, dropout_key=dropout_key,
                        kv_length=kv_length)


# --------------------------------------------------------------------------
# sliding-window attention (transformer.cc:847-1038, Longformer style)
# --------------------------------------------------------------------------
def sldwin_atten(q, k, v, window, symmetric=True):
    """q,k,v: (B, H, L, D); banded attention with width `window`."""
    w = window if symmetric else None
    if symmetric:
        return flash_attention(q, k, v, window=window)
    # asymmetric: only look back `window`
    L = q.shape[-2]
    qi = jnp.arange(L)[:, None]
    ki = jnp.arange(L)[None, :]
    m = (ki <= qi) & (qi - ki <= window)
    return attention_reference(q, k, v, mask=m)
