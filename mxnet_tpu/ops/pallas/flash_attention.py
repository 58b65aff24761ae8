"""Flash attention as a Pallas TPU kernel — forward AND backward.

Replaces the reference's O(L^2)-memory fused attention matmuls
(`src/operator/contrib/transformer.cc:650` interleaved_matmul_selfatt_qk →
softmax → valatt chain) and the sliding-window kernels
(`transformer.cc:847` sldwin_atten_*) with a blockwise online-softmax
kernel: per q-block the kernel streams k/v blocks through VMEM, keeping a
running (max, sum, acc) carry, and never materializes an (L, L) score
matrix in HBM.  VMEM footprint per program is
O(block_q·D + block_k·D + block_q·block_k); HBM is O(L·D) for the tensors
plus O(L) for the saved log-sum-exp.  Causal and banded (sliding-window)
masking are flags on the same kernel, and blocks that a mask rules out
entirely are skipped, so causal attention does ~half the work.

Training is first-class: `flash_attention_tpu` carries a `jax.custom_vjp`
whose backward is two more Pallas kernels (dq, and dk/dv), using the
standard recomputation trick — softmax probabilities are rebuilt per block
from q, k and the saved row-wise log-sum-exp, so no O(L^2) residual is
stored.

Layout: q, k, v are (B, H, L, D); D should be a multiple of 128 (MXU lane
width) and blocks multiples of the sublane tile for best tiling.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Large-negative sentinel instead of -inf: masked scores underflow to exactly
# 0 after the softmax shift (every row of a causal / banded self-attention has
# at least one unmasked key, so running (max, sum) state self-corrects), which
# lets the kernels skip all isfinite() guards on the hot path.
_MASKED = -1e30
_NEG_INF = float("-inf")
_LANES = 128  # lane width: (m, l) carries are kept lane-broadcast


def _block_mask(s_shape, qi, ki, block_q, block_k, causal, window,
                kvlen=None):
    """Boolean mask for one (block_q, block_k) score tile, or None.
    `kvlen` is a dynamic per-batch valid key count (padding mask)."""
    if not causal and window is None and kvlen is None:
        return None
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s_shape, 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s_shape, 1)
    mask = None
    if causal:
        mask = k_pos <= q_pos
    if window is not None:
        wm = jnp.abs(q_pos - k_pos) <= window
        mask = wm if mask is None else (mask & wm)
    if kvlen is not None:
        km = k_pos < kvlen
        mask = km if mask is None else (mask & km)
    return mask


def _block_needed(qi, ki, block_q, block_k, causal, window, kvlen=None):
    """Whether any element of score tile (qi, ki) survives the mask."""
    need = True
    q_first = qi * block_q
    q_last = q_first + block_q - 1
    k_first = ki * block_k
    k_last = k_first + block_k - 1
    if causal:
        need = jnp.logical_and(need, k_first <= q_last)
    if window is not None:
        need = jnp.logical_and(need, k_first <= q_last + window)
        need = jnp.logical_and(need, k_last >= q_first - window)
    if kvlen is not None:
        need = jnp.logical_and(need, k_first < kvlen)
    return need


def _block_boundary(qi, ki, block_q, block_k, causal, window, kvlen=None):
    """Whether tile (qi, ki) intersects a mask edge (needs per-element
    masking).  Interior tiles skip the iota/where work entirely."""
    if not causal and window is None and kvlen is None:
        return False
    q_first = qi * block_q
    q_last = q_first + block_q - 1
    k_first = ki * block_k
    k_last = k_first + block_k - 1
    interior = True
    if causal:
        interior = jnp.logical_and(interior, k_last <= q_first)
    if window is not None:
        interior = jnp.logical_and(interior, q_last - k_first <= window)
        interior = jnp.logical_and(interior, k_last - q_first <= window)
    if kvlen is not None:
        interior = jnp.logical_and(interior, k_last < kvlen)
    return jnp.logical_not(interior)


def _masked_dispatch(qi, ki, block_q, block_k, causal, window, kvlen, step):
    """Run `step(use_mask)` for tile (qi, ki): skipped when fully masked,
    without per-element masking on interior tiles, with it on tiles that
    intersect a mask edge.  Shared by the forward and both backward
    kernels."""
    needed = _block_needed(qi, ki, block_q, block_k, causal, window, kvlen)
    if causal or window is not None or kvlen is not None:
        boundary = _block_boundary(qi, ki, block_q, block_k, causal, window,
                                   kvlen)
        pl.when(jnp.logical_and(needed, boundary))(lambda: step(True))
        pl.when(jnp.logical_and(needed, jnp.logical_not(boundary)))(
            lambda: step(False))
    else:
        pl.when(needed)(lambda: step(False))


# ---------------------------------------------------------------------------
# in-kernel dropout: counter-based hash, no PRNG primitive
# ---------------------------------------------------------------------------
def hash_keep_bits(seed, b, gi, gj):
    """Deterministic pseudo-random uint32 per (seed, batch-head, q-pos,
    k-pos), built from pure uint32 vector arithmetic (multiply/xor/shift):
    runs identically on the TPU vector unit, in Pallas interpret mode, and
    in plain XLA (the oracle in tests) — unlike pltpu.prng_*, which has no
    CPU lowering.  Position-based counters make the mask independent of
    the block tiling, so the forward and both backward kernels regenerate
    the exact same mask from their own grids.  Murmur3's finalizer gives
    the avalanche; the linear pre-mix only needs to separate coordinates."""
    u = jnp.uint32
    h = (gi.astype(u) * u(0x9E3779B1)) ^ (gj.astype(u) * u(0x85EBCA77))
    h = h ^ (jnp.asarray(seed, u) + jnp.asarray(b, jnp.int32).astype(u)
             * u(0xC2B2AE3D))
    h = h ^ (h >> u(16))
    h = h * u(0x85EBCA6B)
    h = h ^ (h >> u(13))
    h = h * u(0xC2B2AE35)
    h = h ^ (h >> u(16))
    return h


def _keep_scale(seed, b, qi, ki, shape, block_q, block_k, rate):
    """Float32 dropout multiplier tile: 0 where dropped, 1/(1-rate) kept."""
    gi = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    gj = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    h = hash_keep_bits(seed, b, gi, gj)
    thr = jnp.uint32(min(int(round(rate * 4294967296.0)), 4294967295))
    return (h >= thr).astype(jnp.float32) * (1.0 / (1.0 - rate))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, seed_ref, kvlen_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr,
                *, scale, causal, window, block_q, block_k, num_k, dropout,
                has_kvlen):
    b = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    kvlen = kvlen_ref[b] if has_kvlen else None

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _MASKED)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _step(use_mask):
        # matmuls keep the input dtype (bf16 runs the MXU at full rate);
        # accumulation and the softmax state are always f32
        q = q_ref[0]                                   # (bq, D)
        k = k_ref[0]                                   # (bk, D)
        v = v_ref[0]                                   # (bk, D)
        s = jax.lax.dot_general(                       # (bq, bk) = q @ k.T
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if use_mask:
            mask = _block_mask(s.shape, qi, ki, block_q, block_k, causal,
                               window, kvlen)
            s = jnp.where(mask, s, _MASKED)

        m_prev = jnp.max(m_scr[:], axis=-1, keepdims=True)   # (bq, 1)
        l_prev = jnp.max(l_scr[:], axis=-1, keepdims=True)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_next = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next)                        # (bq, bk)
        # the softmax normalizer accumulates the UNdropped p — dropout
        # applies to normalized probabilities, and scaling commutes
        l_next = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if dropout:
            p = p * _keep_scale(seed_ref[0], b, qi, ki, p.shape,
                                block_q, block_k, dropout)
        acc_scr[:] = acc_scr[:] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_next, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_next, l_scr.shape)

    _masked_dispatch(qi, ki, block_q, block_k, causal, window, kvlen, _step)

    @pl.when(ki == num_k - 1)
    def _finalize():
        m = jnp.max(m_scr[:], axis=-1, keepdims=True)    # (bq, 1)
        l = jnp.max(l_scr[:], axis=-1, keepdims=True)
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = jnp.where(l == 0.0, _NEG_INF, m + jnp.log(l_safe))


def _smem_spec():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _fwd_call(q, k, v, seed, kvlen, causal, window, scale, dropout,
              has_kvlen, block_q, block_k, interpret):
    BH, L, D = q.shape
    num_q = L // block_q
    num_k = L // block_k
    grid = (BH, num_q, num_k)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, num_k=num_k, dropout=dropout,
        has_kvlen=has_kvlen)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            _smem_spec(),
            _smem_spec(),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, L, D), q.dtype),
            jax.ShapeDtypeStruct((BH, L, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, seed, kvlen)
    return out, lse


# ---------------------------------------------------------------------------
# backward: dq kernel (grid over q blocks, streams k blocks)
# ---------------------------------------------------------------------------
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, seed_ref,
                   kvlen_ref, dq_ref, dq_scr,
                   *, scale, causal, window, block_q, block_k, num_k, dropout,
                   has_kvlen):
    b = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    kvlen = kvlen_ref[b] if has_kvlen else None

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _step(use_mask):
        q = q_ref[0]                                   # (bq, D)
        k = k_ref[0]                                   # (bk, D)
        v = v_ref[0]                                   # (bk, D)
        do = do_ref[0]                                 # (bq, D)
        lse = lse_ref[0]                               # (bq, 1)
        delta = delta_ref[0]                           # (bq, 1)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if use_mask:
            mask = _block_mask(s.shape, qi, ki, block_q, block_k, causal,
                               window, kvlen)
            s = jnp.where(mask, s, _MASKED)
        p = jnp.exp(s - lse)                           # masked -> exp(-1e30)=0
        if has_kvlen:
            # a fully-padded row has lse = -inf; exp(s + inf) would poison
            p = jnp.where(lse == _NEG_INF, 0.0, p)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout:
            # chain rule through the dropout mask applied to normalized
            # probabilities (delta = sum(do*o) already equals
            # sum_k p*dp_dropped — see _flash_bwd docstring)
            dp = dp * _keep_scale(seed_ref[0], b, qi, ki, dp.shape,
                                  block_q, block_k, dropout)
        ds = (p * (dp - delta)).astype(k.dtype)        # (bq, bk)
        dq_scr[:] = dq_scr[:] + jnp.dot(
            ds, k, preferred_element_type=jnp.float32) * scale

    _masked_dispatch(qi, ki, block_q, block_k, causal, window, kvlen, _step)

    @pl.when(ki == num_k - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# backward: dk/dv kernel (grid over k blocks, streams q blocks)
# ---------------------------------------------------------------------------
def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    seed_ref, kvlen_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                    *, scale, causal, window, block_q, block_k, num_q,
                    dropout, has_kvlen):
    b = pl.program_id(0)
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    kvlen = kvlen_ref[b] if has_kvlen else None

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _step(use_mask):
        q = q_ref[0]                                   # (bq, D)
        k = k_ref[0]                                   # (bk, D)
        v = v_ref[0]                                   # (bk, D)
        do = do_ref[0]                                 # (bq, D)
        lse = lse_ref[0]                               # (bq, 1)
        delta = delta_ref[0]                           # (bq, 1)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if use_mask:
            mask = _block_mask(s.shape, qi, ki, block_q, block_k, causal,
                               window, kvlen)
            s = jnp.where(mask, s, _MASKED)
        p = jnp.exp(s - lse)                           # masked -> exp(-1e30)=0
        if has_kvlen:
            p = jnp.where(lse == _NEG_INF, 0.0, p)
        if dropout:
            # seeded by GLOBAL positions, so this grid (b, ki, qi) rebuilds
            # the identical mask the forward's (b, qi, ki) grid drew
            keep = _keep_scale(seed_ref[0], b, qi, ki, p.shape,
                               block_q, block_k, dropout)
            pd = p * keep
        else:
            keep = None
            pd = p
        # dv += dropped(p).T @ do : contract the q dimension
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            pd.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if keep is not None:
            dp = dp * keep
        ds = (p * (dp - delta)).astype(q.dtype)        # (bq, bk)
        # dk += ds.T @ q, scaled to match s = (q @ k.T) * scale
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    _masked_dispatch(qi, ki, block_q, block_k, causal, window, kvlen, _step)

    @pl.when(qi == num_q - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_call(q, k, v, do, lse, delta, seed, kvlen, causal, window, scale,
              dropout, has_kvlen, block_q, block_k, interpret):
    BH, L, D = q.shape
    num_q = L // block_q
    num_k = L // block_k

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, causal=causal, window=window,
            block_q=block_q, block_k=block_k, num_k=num_k, dropout=dropout,
            has_kvlen=has_kvlen),
        grid=(BH, num_q, num_k),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            _smem_spec(),
            _smem_spec(),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, L, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, delta, seed, kvlen)

    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, scale=scale, causal=causal, window=window,
            block_q=block_q, block_k=block_k, num_q=num_q, dropout=dropout,
            has_kvlen=has_kvlen),
        grid=(BH, num_k, num_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),
            _smem_spec(),
            _smem_spec(),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, L, D), q.dtype),
            jax.ShapeDtypeStruct((BH, L, D), q.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, delta, seed, kvlen)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom-VJP core on (BH, L, D) tensors
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(5, 6, 7, 8, 9, 10, 11, 12))
def _flash(q, k, v, seed, kvlen, causal, window, scale, dropout, has_kvlen,
           block_q, block_k, interpret):
    out, _ = _fwd_call(q, k, v, seed, kvlen, causal, window, scale, dropout,
                       has_kvlen, block_q, block_k, interpret)
    return out


def _flash_fwd(q, k, v, seed, kvlen, causal, window, scale, dropout,
               has_kvlen, block_q, block_k, interpret):
    out, lse = _fwd_call(q, k, v, seed, kvlen, causal, window, scale,
                         dropout, has_kvlen, block_q, block_k, interpret)
    return out, (q, k, v, seed, kvlen, out, lse)


def _flash_bwd(causal, window, scale, dropout, has_kvlen, block_q, block_k,
               interpret, residuals, g):
    """With dropout, O = (P ⊙ M/(1-r)) V where P = softmax(S).  The usual
    delta = Σ_d dO·O still equals Σ_k P·dP (dP = chain through the mask),
    because Σ_k P_ik dP_ik = Σ_k (P ⊙ M/(1-r))_ik (dO V^T)_ik = dO_i·O_i —
    so the standard recomputation trick survives dropout unchanged."""
    q, k, v, seed, kvlen, out, lse = residuals
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1,
                    keepdims=True)
    dq, dk, dv = _bwd_call(q, k, v, g, lse, delta, seed, kvlen, causal,
                           window, scale, dropout, has_kvlen, block_q,
                           block_k, interpret)
    return dq, dk, dv, None, None


_flash.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# block-size selection: env override > per-process autotune sweep > table
# ---------------------------------------------------------------------------
# one entry per (seq-len bucket, dtype width): tuned at the BERT shapes the
# bench drives (L=128 and L=2048, D∈{64,128}).  Small L wants one block per
# grid row (no online-softmax rescale traffic); long L wants the biggest
# k-block VMEM tolerates so each q-block streams fewer carry updates, and
# bf16 halves the score-tile footprint so block_q can double.
_AUTOTUNE_CACHE = {}  # (L, D, dtype, causal, banded) -> (block_q, block_k)


def _table_blocks(L, D, dtype):
    narrow = jnp.dtype(dtype).itemsize <= 2
    if L <= 256:
        return (L, L)
    if L <= 1024:
        return (256, 512)
    return (512, 1024) if narrow else (256, 1024)


def _env_block(name):
    raw = os.environ.get(name, "")
    try:
        return int(raw) if raw else 0
    except ValueError:
        return 0


def _sweep_candidates(L):
    out = []
    for bq in (128, 256, 512):
        for bk in (128, 256, 512, 1024):
            if bq <= L and bk <= L and L % bq == 0 and L % bk == 0:
                out.append((bq, bk))
    return out or [(min(L, 128), min(L, 128))]


def _autotune_sweep(L, D, dtype, causal, window):
    """One-time on-device sweep: time the forward kernel per candidate on
    synthetic (8, L, D) tensors, best wall-clock wins (min-of-2 after a
    compile warmup — interference can only slow a sample down)."""
    import time
    BH = 8
    q = jnp.zeros((BH, L, D), dtype)
    seed = jnp.zeros((1,), jnp.uint32)
    kvlen = jnp.zeros((1,), jnp.int32)
    best, best_t = None, float("inf")
    for bq, bk in _sweep_candidates(L):
        try:
            run = jax.jit(functools.partial(
                _fwd_call, causal=causal, window=window,
                scale=1.0 / math.sqrt(D), dropout=0.0, has_kvlen=False,
                block_q=bq, block_k=bk, interpret=False))
            jax.block_until_ready(run(q, q, q, seed, kvlen))  # compile
            t = float("inf")
            for _ in range(2):
                t0 = time.perf_counter()
                jax.block_until_ready(run(q, q, q, seed, kvlen))
                t = min(t, time.perf_counter() - t0)
        except Exception:  # candidate doesn't fit/compile on this chip
            continue
        if t < best_t:
            best, best_t = (bq, bk), t
    return best or _table_blocks(L, D, dtype)


def pick_block_sizes(L, D, dtype, causal=False, window=None,
                     interpret=False):
    """(block_q, block_k) for a flash call: MXNET_FLASH_BLOCK_Q/K env
    overrides win outright; with MXNET_FLASH_AUTOTUNE=1 on a compiled
    (non-interpret, non-CPU) backend a one-time on-device sweep picks per
    (L, D, dtype, mask-kind) and caches for the process; otherwise the
    static table."""
    eq, ek = _env_block("MXNET_FLASH_BLOCK_Q"), _env_block(
        "MXNET_FLASH_BLOCK_K")
    if eq and ek:
        return eq, ek
    key = (L, D, str(jnp.dtype(dtype)), bool(causal), window is not None)
    got = _AUTOTUNE_CACHE.get(key)
    if got is None:
        autotune = os.environ.get("MXNET_FLASH_AUTOTUNE", "") not in (
            "", "0", "false", "False", "off")
        if autotune and not interpret and jax.default_backend() != "cpu":
            got = _autotune_sweep(L, D, jnp.dtype(dtype), causal, window)
        else:
            got = _table_blocks(L, D, dtype)
        _AUTOTUNE_CACHE[key] = got
    bq, bk = got
    return (eq or bq), (ek or bk)


@functools.partial(jax.jit, static_argnames=("causal", "window", "scale",
                                             "dropout", "block_q", "block_k",
                                             "interpret"))
def _flash_attention_blocks(q, k, v, causal=False, window=None, scale=None,
                            dropout=0.0, seed=None, kv_length=None,
                            block_q=512, block_k=1024, interpret=False):
    B, H, L, D = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    block_q = min(block_q, L)
    while L % block_q:
        block_q //= 2
    block_k = min(block_k, L)
    while L % block_k:
        block_k //= 2
    qr = q.reshape(B * H, L, D)
    kr = k.reshape(B * H, L, D)
    vr = v.reshape(B * H, L, D)
    if seed is None:
        seed = jnp.zeros((1,), jnp.uint32)
    else:
        seed = jnp.asarray(seed, jnp.uint32).reshape(-1)[:1]
    has_kvlen = kv_length is not None
    if has_kvlen:
        # one entry per (batch, head) program: bh = b * H + h
        kvlen = jnp.repeat(jnp.asarray(kv_length, jnp.int32).reshape(B), H)
    else:
        kvlen = jnp.zeros((1,), jnp.int32)
    out = _flash(qr, kr, vr, seed, kvlen, causal, window, scale,
                 float(dropout), has_kvlen, block_q, block_k, interpret)
    return out.reshape(B, H, L, D)


def flash_attention_tpu(q, k, v, causal=False, window=None, scale=None,
                        dropout=0.0, seed=None, kv_length=None,
                        block_q=None, block_k=None, interpret=False):
    """q,k,v: (B, H, L, D) → (B, H, L, D).  Differentiable (custom VJP with
    Pallas backward kernels).  `window` is a symmetric band half-width.

    `dropout` applies in-kernel dropout to the normalized attention
    probabilities (reference semantics: transformer.cc:650-826 attention
    dropout), regenerated in the backward kernels from the same hash —
    `seed` (uint32 scalar/array) picks the mask.  `kv_length` is a (B,)
    per-sequence valid key count (padding mask as a per-row k-limit).

    ``block_q``/``block_k`` default to ``pick_block_sizes`` — the env
    overrides (MXNET_FLASH_BLOCK_Q/K), the per-process autotune cache
    (MXNET_FLASH_AUTOTUNE=1), or the static table, in that order.  The
    jitted core (`_flash_attention_blocks`) still clamps/halves them to
    divide L, so any override is safe."""
    L, D = q.shape[-2], q.shape[-1]
    if block_q is None or block_k is None:
        tq, tk = pick_block_sizes(L, D, q.dtype, causal=causal,
                                  window=window, interpret=interpret)
        block_q = block_q or tq
        block_k = block_k or tk
    return _flash_attention_blocks(q, k, v, causal=causal, window=window,
                                   scale=scale, dropout=dropout, seed=seed,
                                   kv_length=kv_length,
                                   block_q=int(block_q),
                                   block_k=int(block_k),
                                   interpret=interpret)
