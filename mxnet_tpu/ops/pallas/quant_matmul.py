"""Fused dequant-matmul for weight-only quantized LLM decode.

Decode GEMMs are memory-bandwidth-bound: at batch ~slots the MXU is idle
waiting on weight bytes, so shrinking the weights IS the speedup
(LLM.int8, Dettmers et al. 2022; AWQ, Lin et al. 2023 — the weight-only
line: activations stay fp32/bf16, integer weights are dequantized on the
fly inside the kernel, never materialized in HBM at full width).

Two integer formats, both plain NamedTuples (automatic JAX pytrees, so
they flow through ``jit`` / ``shard_map`` / ``device_put`` like any
weight leaf):

- :class:`QuantW8` — per-output-channel symmetric int8: ``q (O, I)
  int8``, ``s (O,) f32``; ``w = q * s[:, None]``.  Same scheme as the
  CNN tier's ``contrib.quantization._quantize_weight`` (oneDNN per-oc
  scales).
- :class:`QuantW4` — per-group symmetric int4, two values packed per
  byte along the input dim: ``q (O, I/2) uint8``, ``s (O, G) f32`` with
  ``group = I / G`` (default 128, the AWQ/GPTQ convention).  Values are
  clipped to [-7, 7] so the codebook is symmetric (no -8 asymmetry).
  The group size is derivable from the shapes: ``group = 2 * q.shape[1]
  // s.shape[1]``.

The Pallas kernel (whole-array VMEM) fuses unpack + dequant + matmul
into one launch; the XLA reference (:func:`quant_matmul_reference`)
computes the identical formula op-for-op, which makes
``MXNET_QUANT_MATMUL=interpret`` a bit-exactness oracle for the kernel
on CPU.  Dispatch is the repo's gate grammar: ``''`` auto (Pallas on a
TPU backend), ``0``/``off`` forces the XLA reference, ``interpret``
forces the kernel in interpreter mode.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kernel_mode

__all__ = ["QuantW8", "QuantW4", "quantize_w8", "quantize_w4",
           "dequantize_weight", "quant_matmul", "quant_matmul_reference",
           "pack_int4", "unpack_int4", "is_quantized", "group_for",
           "quant_mode", "trace_counts", "last_path"]

_INT8_MAX = 127.0
_INT4_MAX = 7.0

# trace-time counter (tests assert the fused path is actually in the
# compiled program — the epilogue/fused_cell convention)
trace_counts = {"quant_matmul": 0}
# "pallas" | "pallas-interpret" | "xla" — which backend last latched
last_path = None


class QuantW8(NamedTuple):
    """Per-output-channel int8 weight: ``w ≈ q * s[:, None]``."""
    q: jax.Array  # (O, I) int8
    s: jax.Array  # (O,)   f32


class QuantW4(NamedTuple):
    """Per-group int4 weight, nibble-packed along the input dim:
    ``w ≈ unpack(q).reshape(O, G, group) * s[:, :, None]``."""
    q: jax.Array  # (O, I // 2) uint8 — byte i holds values 2i (low
    #               nibble) and 2i+1 (high nibble)
    s: jax.Array  # (O, G) f32, G = I // group


def is_quantized(w):
    return isinstance(w, (QuantW8, QuantW4))


def quant_mode():
    """'compiled' | 'interpret' | None — the fused dequant-matmul gate
    (``MXNET_QUANT_MATMUL``)."""
    return kernel_mode("MXNET_QUANT_MATMUL")


# ---------------------------------------------------------------------------
# quantize / pack
# ---------------------------------------------------------------------------
def group_for(in_dim, group):
    """Largest divisor of ``in_dim`` that is ≤ ``group`` and divides it
    evenly — the effective group size.  Under tensor parallelism the
    row-parallel shards see ``I_local = I / tp``, so the global group
    must shrink to stay shard-local (scales can't straddle shards)."""
    return math.gcd(min(int(group), int(in_dim)), int(in_dim))


def quantize_w8(w):
    """fp32 (O, I) → :class:`QuantW8` (symmetric per-oc, amax/127)."""
    w = jnp.asarray(w, jnp.float32)
    amax = jnp.abs(w).max(axis=1)
    s = jnp.where(amax > 0, amax / _INT8_MAX, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(w / s[:, None]), -127, 127).astype(jnp.int8)
    return QuantW8(q=q, s=s)


def quantize_w4(w, group=128):
    """fp32 (O, I) → :class:`QuantW4` (symmetric per-group, amax/7).

    ``group`` is clamped to a divisor of the input dim via
    :func:`group_for`; I must be even (nibble packing)."""
    w = jnp.asarray(w, jnp.float32)
    o, i = w.shape
    if i % 2:
        raise ValueError("int4 packing needs an even input dim, got %d" % i)
    group = group_for(i, group)
    if group % 2:
        # a group must cover whole packed bytes
        group = group_for(i, group * 2) if group > 1 else 2
    g = i // group
    wg = w.reshape(o, g, group)
    amax = jnp.abs(wg).max(axis=2)
    s = jnp.where(amax > 0, amax / _INT4_MAX, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(wg / s[:, :, None]), -7, 7)
    return QuantW4(q=pack_int4(q.reshape(o, i).astype(jnp.int8)), s=s)


def pack_int4(v):
    """(O, I) int8 in [-8, 7] → (O, I/2) uint8, value ``2i`` in the low
    nibble of byte ``i`` and ``2i+1`` in the high nibble."""
    v32 = v.astype(jnp.int32)
    packed = ((v32[:, 1::2] & 0xF) << 4) | (v32[:, 0::2] & 0xF)
    return packed.astype(jnp.uint8)


def unpack_int4(q):
    """(O, I/2) uint8 → (O, I) int32, sign-extended nibbles (arithmetic
    shifts — ``(b << 28) >> 28`` low, ``(b << 24) >> 28`` high)."""
    b = q.astype(jnp.int32)
    lo = (b << 28) >> 28
    hi = (b << 24) >> 28
    return jnp.stack([lo, hi], axis=-1).reshape(q.shape[0], -1)


def dequantize_weight(qw):
    """Integer weight → fp32 (O, I).  This exact formula is what the
    Pallas kernel computes inline; tests pin kernel == reference."""
    if isinstance(qw, QuantW8):
        return qw.q.astype(jnp.float32) * qw.s[:, None]
    o = qw.q.shape[0]
    i = 2 * qw.q.shape[1]
    g = qw.s.shape[1]
    vals = unpack_int4(qw.q)
    w = (vals.astype(jnp.float32).reshape(o, g, i // g)
         * qw.s[:, :, None])
    return w.reshape(o, i)


# ---------------------------------------------------------------------------
# the fused kernel + reference
# ---------------------------------------------------------------------------
def quant_matmul_reference(x, qw):
    """XLA reference: dequantize then ``x @ w.T`` in fp32 — the
    bit-exactness oracle for the fused kernel."""
    return jnp.dot(x, dequantize_weight(qw).T,
                   preferred_element_type=jnp.float32)


def _qmm8_kernel(x_ref, q_ref, s_ref, o_ref):
    w = q_ref[...].astype(jnp.float32) * s_ref[...]  # s fed as (O, 1)
    o_ref[...] = jnp.dot(x_ref[...], w.T,
                         preferred_element_type=jnp.float32)


def _qmm4_kernel(x_ref, q_ref, s_ref, o_ref):
    b = q_ref[...].astype(jnp.int32)
    lo = (b << 28) >> 28
    hi = (b << 24) >> 28
    o, half = b.shape
    vals = jnp.stack([lo, hi], axis=-1).reshape(o, 2 * half)
    w = (vals.astype(jnp.float32).reshape(o, s_ref.shape[1], -1)
         * s_ref[...][:, :, None]).reshape(o, 2 * half)
    o_ref[...] = jnp.dot(x_ref[...], w.T,
                         preferred_element_type=jnp.float32)


def _pallas_qmm(xf, qw, interpret):
    n = xf.shape[0]
    o = qw.q.shape[0]
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    if isinstance(qw, QuantW8):
        return pl.pallas_call(
            _qmm8_kernel,
            in_specs=[vmem, vmem, vmem],
            out_specs=vmem,
            out_shape=jax.ShapeDtypeStruct((n, o), jnp.float32),
            interpret=interpret,
        )(xf, qw.q, qw.s.reshape(o, 1))
    return pl.pallas_call(
        _qmm4_kernel,
        in_specs=[vmem, vmem, vmem],
        out_specs=vmem,
        out_shape=jax.ShapeDtypeStruct((n, o), jnp.float32),
        interpret=interpret,
    )(xf, qw.q, qw.s)


def quant_matmul(x, qw):
    """``x @ dequant(qw).T`` with the integer weight dequantized inside
    the kernel.  ``x``: (..., I) any float dtype; returns (..., O) f32.

    Dispatch: Pallas (compiled or interpret per ``MXNET_QUANT_MATMUL``),
    else the XLA reference.  A selected kernel that fails to compile
    fails the call."""
    global last_path
    i = (qw.q.shape[1] if isinstance(qw, QuantW8) else 2 * qw.q.shape[1])
    o = qw.q.shape[0]
    lead = x.shape[:-1]
    xf = x.reshape(-1, i).astype(jnp.float32)
    mode = quant_mode()
    if mode == "compiled" and isinstance(qw, QuantW4):
        # the int4 kernel's nibble interleave (stack + reshape on the
        # lane axis) did not come back from the v5e's compiler in 35
        # minutes at (3072, 768) and cost the run its chip (PR 21):
        # int4 weights dequantize in XLA until the unpack is rewritten
        mode = None
    if mode is not None:
        y = _pallas_qmm(xf, qw, interpret=(mode == "interpret"))
        trace_counts["quant_matmul"] += 1
        last_path = "pallas" if mode == "compiled" else "pallas-interpret"
        return y.reshape(lead + (o,))
    last_path = "xla"
    return quant_matmul_reference(xf, qw).reshape(lead + (o,))
