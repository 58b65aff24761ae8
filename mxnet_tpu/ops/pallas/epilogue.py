"""Fused epilogue kernels for the transformer hot path.

Parity: the reference's BERT fast path fuses the matmul epilogues by hand
(`src/operator/contrib/transformer.cc` — bias+GELU after the FFN matmul,
bias+dropout+residual after the projection matmuls); MXNet's pointwise
RTC fusion pass stitched the same chains on CUDA.  Unfused, each step of
`matmul → add(bias) → gelu` / `add(bias) → dropout → add(residual)` is a
full HBM round-trip of the activation tensor — at BERT-base shapes the
FFN epilogue alone re-reads ~25 MB per layer per step.

Two fused ops, each a `jax.custom_vjp`:

- ``bias_gelu(x, b)``     = gelu(x + b)               (exact erf GELU)
- ``bias_dropout_residual(x, b, r)`` = r + dropout(x + b)

Forward AND backward are single fused kernels.  The dropout mask is the
same counter-based hash as the flash kernel's in-kernel dropout
(`hash_keep_bits`): seeded by GLOBAL element positions, the backward
regenerates the identical mask from (seed, position) instead of storing
it — the op carries **zero** dropout residuals, where the unfused chain
stores a full-size mask for backward.  ``bias_gelu`` saves only (x, b)
and recomputes u = x + b in backward (one add versus an activation-sized
residual).

Dispatch mirrors ops/attention.flash_attention: a Pallas kernel on a TPU
backend, the identical jnp composition (which XLA provably fuses into one
loop — it is a pure elementwise chain) elsewhere, under GSPMD, or when
``MXNET_EPILOGUE_KERNEL=0``; ``MXNET_EPILOGUE_KERNEL=interpret`` forces
Pallas interpret mode (CPU test lane).  Both paths share the hash mask, so they are
gradient-consistent and testable against each other.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import gspmd_config, kernel_mode
from .flash_attention import hash_keep_bits

_SQRT_HALF = math.sqrt(0.5)
_INV_SQRT_2PI = 1.0 / math.sqrt(2.0 * math.pi)

# per-op call counters, bumped once per (re)trace of the public entry
# points.  tests/test_fused_epilogue.py asserts on these to guarantee the
# fused path is actually in the compiled program, not assumed.
trace_counts = {"bias_gelu": 0, "bias_dropout_residual": 0}
# which backend the last call dispatched to: "pallas"|"pallas-interpret"|"xla"
last_path = None


def fuse_epilogue_enabled():
    """The layer/graph-level gate: MXNET_FUSE_EPILOGUE (default ON).
    Controls whether Dense/FFN/BERT and the fuse-epilogue graph pass
    rewrite to the fused ops; the ops themselves stay callable either
    way."""
    return os.environ.get("MXNET_FUSE_EPILOGUE", "1") not in (
        "0", "false", "False", "off")


def _mode():
    """'compiled' | 'interpret' | None (jnp path).

    Under GSPMD the compiled lane takes the jnp chain, which XLA
    partitions and fuses: "Mosaic kernels cannot be automatically
    partitioned. Please wrap the call in a shard_map" (BERT-base at dp=4
    on the four-chip v5e host, PR 21)."""
    mode = kernel_mode("MXNET_EPILOGUE_KERNEL")
    if mode == "compiled" and gspmd_config() is not None:
        return None
    return mode


def _pick_rows(R, C, dtype):
    """Row-block size: biggest power-of-two divisor of R whose f32 tile
    fits comfortably in VMEM (~2 MB per operand block)."""
    budget = max(1, (2 << 20) // max(C * 4, 1))
    br = 1
    while br * 2 <= min(R, budget) and R % (br * 2) == 0:
        br *= 2
    return br


_ERF_ALPHA = (-2.72614225801306e-10, 2.77068142495902e-08,
              -2.10102402082508e-06, -5.69250639462346e-05,
              -7.34990630326855e-04, -2.95459980854025e-03,
              -1.60960333262415e-02)
_ERF_BETA = (-1.45660718464996e-05, -2.13374055278905e-04,
             -1.68282697438203e-03, -7.37332916720468e-03,
             -1.42647390514189e-02)


def _erf_kernel(x):
    """float32 erf from multiplies, adds and one divide, for use INSIDE
    the Pallas kernels: jax 0.9.0's Mosaic lowering has no rule for
    ``lax.erf`` ("Unimplemented primitive in Pallas TPU lowering for
    KernelType.TC: erf", v5e, PR 21).  The clamped rational approximation
    x*P(x^2)/Q(x^2) that Eigen and XLA use for float32; within 5e-7 of
    ``lax.erf`` everywhere (3.2e-7 of the true value)."""
    x = jnp.clip(x, -4.0, 4.0)
    x2 = x * x
    p = jnp.float32(_ERF_ALPHA[0])
    for c in _ERF_ALPHA[1:]:
        p = p * x2 + jnp.float32(c)
    q = jnp.float32(_ERF_BETA[0])
    for c in _ERF_BETA[1:]:
        q = q * x2 + jnp.float32(c)
    return x * p / q


def _gelu_f32(u, erf=jax.lax.erf):
    return 0.5 * u * (1.0 + erf(u * _SQRT_HALF))


def _dgelu_f32(u, erf=jax.lax.erf):
    # d/du [u * Phi(u)] = Phi(u) + u * phi(u)
    phi = jnp.exp(-0.5 * u * u) * _INV_SQRT_2PI
    return 0.5 * (1.0 + erf(u * _SQRT_HALF)) + u * phi


def _keep_scale_rows(seed, i0, shape, rate):
    """Dropout multiplier tile for rows [i0, i0+shape[0]) of the 2-D view:
    0 where dropped, 1/(1-rate) kept.  Global (row, col) counters make the
    mask independent of the block tiling, so fwd/bwd and Pallas/XLA all
    draw the identical mask."""
    gi = i0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    gj = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    h = hash_keep_bits(seed, 0, gi, gj)
    thr = jnp.uint32(min(int(round(rate * 4294967296.0)), 4294967295))
    return (h >= thr).astype(jnp.float32) * (1.0 / (1.0 - rate))


# ---------------------------------------------------------------------------
# bias_gelu
# ---------------------------------------------------------------------------
def _bg_fwd_kernel(x_ref, b_ref, o_ref):
    u = x_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    o_ref[...] = _gelu_f32(u, _erf_kernel).astype(o_ref.dtype)


def _bg_bwd_kernel(x_ref, g_ref, b_ref, dx_ref):
    u = x_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    dx_ref[...] = (g_ref[...].astype(jnp.float32)
                   * _dgelu_f32(u, _erf_kernel)).astype(dx_ref.dtype)


def _rowblock_call(kernel, arrays, bias, out_dtype, interpret):
    """Shared pallas_call harness: grid over row blocks of the (R, C)
    activations; the bias rides along whole."""
    R, C = arrays[0].shape
    br = _pick_rows(R, C, out_dtype)
    row_spec = pl.BlockSpec((br, C), lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        grid=(R // br,),
        in_specs=[row_spec for _ in arrays] + [pl.BlockSpec((C,),
                                                            lambda i: (0,))],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((R, C), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*arrays, bias)


def _bias_gelu_fwd_pallas(x, b, interpret):
    return _rowblock_call(_bg_fwd_kernel, [x], b, x.dtype, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _bias_gelu(x, b, mode):
    if mode is not None:
        return _bias_gelu_fwd_pallas(x, b, mode == "interpret")
    u = x.astype(jnp.float32) + b.astype(jnp.float32)
    return _gelu_f32(u).astype(x.dtype)


def _bias_gelu_fwd(x, b, mode):
    return _bias_gelu(x, b, mode), (x, b)


def _bias_gelu_bwd(mode, res, g):
    x, b = res
    if mode is not None:
        dx = _rowblock_call(_bg_bwd_kernel, [x, g], b, x.dtype,
                            mode == "interpret")
    else:
        u = x.astype(jnp.float32) + b.astype(jnp.float32)
        dx = (g.astype(jnp.float32) * _dgelu_f32(u)).astype(x.dtype)
    # db: one cheap reduction XLA fuses into the dx consumer; accumulate
    # in f32 (bf16 row sums at BERT batch sizes lose ~2 decimal digits)
    db = jnp.sum(dx.astype(jnp.float32), axis=0).astype(b.dtype)
    return dx, db


_bias_gelu.defvjp(_bias_gelu_fwd, _bias_gelu_bwd)


def bias_gelu(x, b):
    """gelu(x + b) fused fwd+bwd.  x: (..., C), b: (C,)."""
    trace_counts["bias_gelu"] += 1
    global last_path
    mode = _mode()
    last_path = {"compiled": "pallas", "interpret": "pallas-interpret",
                 None: "xla"}[mode]
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    out = _bias_gelu(x2, b, mode)
    return out.reshape(shape)


# ---------------------------------------------------------------------------
# bias_dropout_residual
# ---------------------------------------------------------------------------
def _bdr_fwd_kernel(x_ref, r_ref, b_ref, seed_ref, o_ref, *, rate, block_r):
    i = pl.program_id(0)
    u = x_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    if rate:
        u = u * _keep_scale_rows(seed_ref[0], i * block_r, u.shape, rate)
    o_ref[...] = (r_ref[...].astype(jnp.float32) + u).astype(o_ref.dtype)


def _bdr_bwd_kernel(g_ref, seed_ref, dx_ref, *, rate, block_r):
    i = pl.program_id(0)
    g = g_ref[...].astype(jnp.float32)
    if rate:
        g = g * _keep_scale_rows(seed_ref[0], i * block_r, g.shape, rate)
    dx_ref[...] = g.astype(dx_ref.dtype)


def _bdr_call(kernel, arrays, bias_like, seed, out_dtype, rate, interpret):
    R, C = arrays[0].shape
    br = _pick_rows(R, C, out_dtype)
    row_spec = pl.BlockSpec((br, C), lambda i: (i, 0))
    in_specs = [row_spec for _ in arrays]
    if bias_like is not None:
        in_specs.append(pl.BlockSpec((C,), lambda i: (0,)))
    in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    ops = list(arrays) + ([bias_like] if bias_like is not None else [])
    return pl.pallas_call(
        functools.partial(kernel, rate=rate, block_r=br),
        grid=(R // br,),
        in_specs=in_specs,
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((R, C), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*ops, seed)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _bias_dropout_residual(x, b, r, seed, rate, mode):
    if mode is not None:
        return _bdr_call(_bdr_fwd_kernel, [x, r], b, seed, x.dtype, rate,
                         mode == "interpret")
    u = x.astype(jnp.float32) + b.astype(jnp.float32)
    if rate:
        u = u * _keep_scale_rows(seed[0], 0, u.shape, rate)
    return (r.astype(jnp.float32) + u).astype(x.dtype)


def _bdr_fwd(x, b, r, seed, rate, mode):
    # no activation-sized residuals: backward regenerates the mask from
    # (seed, position) — only the scalar seed (and the (C,) bias, for its
    # dtype) is saved
    return _bias_dropout_residual(x, b, r, seed, rate, mode), (seed, b)


def _bdr_bwd(rate, mode, res, g):
    seed, b = res
    b_dtype = b.dtype
    if rate:
        if mode is not None:
            dx = _bdr_call(_bdr_bwd_kernel, [g], None, seed, g.dtype, rate,
                           mode == "interpret")
        else:
            dx = (g.astype(jnp.float32)
                  * _keep_scale_rows(seed[0], 0, g.shape, rate)).astype(
                      g.dtype)
    else:
        dx = g
    db = jnp.sum(dx.astype(jnp.float32), axis=0).astype(b_dtype)
    return dx, db, g, None


_bias_dropout_residual.defvjp(_bdr_fwd, _bdr_bwd)


def bias_dropout_residual(x, b, r, rate=0.0, key=None):
    """r + dropout(x + b) fused fwd+bwd, rate already resolved for the
    current train/predict mode (0.0 = no dropout).  x, r: (..., C),
    b: (C,); `key` is a jax PRNG key that seeds the in-kernel hash mask
    (required when rate > 0)."""
    trace_counts["bias_dropout_residual"] += 1
    global last_path
    if not 0.0 <= rate < 1.0:
        raise ValueError(
            "bias_dropout_residual: rate must be in [0, 1), got %r"
            % (rate,))
    if rate and key is None:
        raise ValueError("bias_dropout_residual: rate > 0 requires key")
    mode = _mode()
    last_path = {"compiled": "pallas", "interpret": "pallas-interpret",
                 None: "xla"}[mode]
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    r2 = r.reshape(-1, shape[-1])
    if rate:
        seed = jax.random.bits(key, (1,), jnp.uint32)
    else:
        seed = jnp.zeros((1,), jnp.uint32)
    out = _bias_dropout_residual(x2, b, r2, seed, float(rate), mode)
    return out.reshape(shape)
