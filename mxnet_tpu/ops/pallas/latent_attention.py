"""The decode step's attention over a paged latent cache (multi-head latent
attention, :mod:`mxnet_tpu.models.latent`), one query token a lane.

The pools hold one row a token and layer, ``[c_kv, k_r, 0]`` padded to whole
lane tiles, a page of ``S`` positions split over the two pools: the K pool
holds positions ``[0, S/2)`` of every page and the V pool ``[S/2, S)``,
both ``(layers, pages, S/2, width)``.  The query of each head is a whole row
too (the absorbed ``[q~, q_rope, 0]``, scaled), so the scores contract
whole rows and the values come back as whole rows: every head reads every
lane of the row, no head axis is split off.

:func:`paged_latent_rows` (trace name ``paged_latent_rows``) walks each
lane's page table up to its length and copies those pages out of the two
pools once, a block of pages at a time, into the scores, flash-decoding's
running maximum and sum, and the weighted sum of the rows; pages past a
length get no copy and no product, and an inactive lane (length 0) reads
nothing.  :func:`latent_rows` is the same mathematics over rows gathered
whole: the reference the kernel is tested against and what the CPU runs.

Selected by :func:`kernel_mode` from what the pool is (a float pool whose
half-page is whole ``(8, 128)`` tiles) under ``MXNET_PAGED_ATTENTION``,
the switch of :mod:`.paged_attention`'s kernels (``interpret`` runs the
Pallas interpreter).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kernel_mode as _kernel_mode

__all__ = ["kernel_mode", "latent_rows", "paged_latent_rows", "last_path"]

#: Which path the last step built took: "pallas" | "pallas-interpret" |
#: "xla" (None before one was built).
last_path = None

_MASKED = -1e30         # a score past the length; finite, so no inf - inf
_HI = jax.lax.Precision.HIGHEST
_BLOCK_TOKENS = 512     # positions a block of pages holds (2 pages of 256)


def kernel_mode(pool):
    """"compiled" | "interpret" | None for a latent pool ``(L, P, S/2,
    width)``: a float array whose half-page is whole tiles of its dtype
    (width a multiple of 128 lanes, ``S/2`` of the sublane tile)."""
    if not jnp.issubdtype(pool.dtype, jnp.floating):
        return None
    half, width = pool.shape[2:]
    if width % 128 or half % (32 // pool.dtype.itemsize):
        return None
    return _kernel_mode("MXNET_PAGED_ATTENTION")


def latent_rows(q, rows, lengths):
    """q: (B, H, width); rows: (B, C, width), each lane's context in
    position order; lengths: (B,).  -> (B, H, width) float32: the softmax
    over each lane's first ``lengths`` rows of ``q . row``, times the rows;
    zeros for a length of 0.  Both products at the highest precision, as
    the kernel's."""
    s = jnp.einsum("bhe,bce->bhc", q.astype(jnp.float32),
                   rows.astype(jnp.float32), precision=_HI)
    live = jnp.arange(rows.shape[1])[None, None, :] < lengths[:, None, None]
    p = jax.nn.softmax(jnp.where(live, s, -jnp.inf), axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)
    return jnp.einsum("bhc,bce->bhe", p, rows.astype(jnp.float32),
                      precision=_HI)


def _kernel(li_ref, len_ref, tab_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf,
            v_buf, sems, slot_ref, *, half, block_pages, pages_per_seq):
    """One lane a grid step: the recurrence over the lane's live pages, a
    block of ``block_pages`` at a time, each page's two halves copied out
    of the pools as they lie.  Two buffers a pool: while a block is
    attended the next is in flight, and a lane's last block is attended
    while the next lane's first comes in (its buffer handed over in
    ``slot_ref``)."""
    lane, lanes = pl.program_id(0), pl.num_programs(0)
    page = 2 * half
    tokens = block_pages * page
    li = li_ref[0]

    def each_copy(of, block, slot, do):
        live = (len_ref[of] + page - 1) // page
        first = block * block_pages

        def one(j, carry):
            pid = tab_ref[of * pages_per_seq + first + j]
            do(pltpu.make_async_copy(
                k_hbm.at[li, pid], k_buf.at[slot, j], sems.at[0, slot]))
            do(pltpu.make_async_copy(
                v_hbm.at[li, pid], v_buf.at[slot, j], sems.at[1, slot]))
            return carry
        jax.lax.fori_loop(0, jnp.clip(live - first, 0, block_pages), one, 0)

    def start(of, block, slot):
        each_copy(of, block, slot, lambda copy: copy.start())

    @pl.when(lane == 0)
    def _():
        slot_ref[0] = 0
        start(0, 0, 0)

    n = len_ref[lane]
    blocks = (n + tokens - 1) // tokens
    first = slot_ref[0]
    q = q_ref[...]                                      # (H, width)
    rows = block_pages * half
    # the position in the block of each buffered row: page j's first half
    # from the K pool, its second half from the V pool
    col = jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
    col = col // half * page + col % half
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    row = row // half * page + row % half

    def attend(i, carry):
        m, l, acc = carry
        slot = (first + i) % 2

        @pl.when(i + 1 < blocks)
        def _():
            start(lane, i + 1, 1 - slot)

        @pl.when((i + 1 == blocks) & (lane + 1 < lanes))
        def _():
            start(lane + 1, 0, 1 - slot)

        each_copy(lane, i, slot, lambda copy: copy.wait())
        left = n - i * tokens                           # live positions here
        parts = []
        for buf, at in ((k_buf, 0), (v_buf, half)):
            # what lies past the length in the buffer was never copied:
            # zeroed, since 0 * NaN is no zero
            r = jnp.where(row + at < left, buf[slot].reshape(rows, -1)
                          .astype(jnp.float32), 0.0)
            s = jax.lax.dot_general(q, r, (((1,), (1,)), ((), ())),
                                    precision=_HI,
                                    preferred_element_type=jnp.float32)
            parts.append((jnp.where(col + at < left, s, _MASKED), r))
        m_new = jnp.maximum(m, jnp.maximum(
            parts[0][0].max(axis=-1, keepdims=True),
            parts[1][0].max(axis=-1, keepdims=True)))
        alpha = jnp.exp(m - m_new)
        l, acc = alpha * l, alpha * acc
        for s, r in parts:
            p = jnp.exp(s - m_new)
            l = l + p.sum(axis=-1, keepdims=True)
            acc = acc + jnp.dot(p, r, precision=_HI,
                                preferred_element_type=jnp.float32)
        return m_new, l, acc

    h, width = q_ref.shape
    m, l, acc = jax.lax.fori_loop(0, blocks, attend, (
        jnp.full((h, 1), _MASKED, jnp.float32),
        jnp.zeros((h, 1), jnp.float32), jnp.zeros((h, width), jnp.float32)))
    # a length of 0 attended nothing: its zeros over 1, not 0 / 0
    o_ref[...] = acc / jnp.where(l > 0.0, l, 1.0)

    @pl.when((blocks == 0) & (lane + 1 < lanes))
    def _():
        start(lane + 1, 0, first)
    slot_ref[0] = (first + blocks) % 2


@functools.partial(jax.jit, static_argnames="interpret")
def _call(li, lengths, tables, q, k_pool, v_pool, *, interpret):
    """The kernel over (1,) layer index, (B,) lengths, (B * pps,) page
    table, (B, H, width) queries and the two pools; one jitted function
    that the layers of a step share (one trace and one lowering to
    Mosaic)."""
    b, hp, width = q.shape
    half = k_pool.shape[2]
    pps = tables.shape[0] // b
    block_pages = max(1, min(pps, _BLOCK_TOKENS // (2 * half)))
    lane = pl.BlockSpec((None, hp, width), lambda i, *_: (i, 0, 0))
    buf = pltpu.VMEM((2, block_pages, half, width), k_pool.dtype)
    return pl.pallas_call(
        functools.partial(_kernel, half=half, block_pages=block_pages,
                          pages_per_seq=pps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b,),
            in_specs=[lane, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=lane,
            scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((b, hp, width), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_latent_rows",
    )(li, lengths, tables, q, k_pool, v_pool)


def paged_latent_rows(q, k_pool, v_pool, li, lengths, tables):
    """:func:`latent_rows` over layer ``li`` of the pools without the
    gather.  q: (B, H, width) scaled; k_pool/v_pool: ``(L, P, S/2,
    width)`` whole, left where they are (the layer goes in as a scalar);
    lengths: (B,), 0 for an inactive lane; tables: (B, pages_per_seq).
    The two products at the highest precision (at the default one, a
    bfloat16 pass, the check's rows erred by 1.2 % of a logit's spread at
    the median, on one v5e chip), the softmax's maximum and sum in float32.
    Only where :func:`kernel_mode` selects it."""
    global last_path
    mode = kernel_mode(k_pool)
    b, h, width = q.shape
    hp = -(-h // 8) * 8                 # whole sublane tiles of heads
    out = _call(jnp.asarray(li, jnp.int32).reshape(1),
                lengths.astype(jnp.int32), tables.astype(jnp.int32)
                .reshape(-1), jnp.pad(q.astype(jnp.float32),
                                      ((0, 0), (0, hp - h), (0, 0))),
                k_pool, v_pool, interpret=mode == "interpret")
    last_path = "pallas" if mode == "compiled" else "pallas-interpret"
    return out[:, :h]
