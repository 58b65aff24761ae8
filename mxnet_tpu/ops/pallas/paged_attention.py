"""Paged attention for autoregressive decode over a page-granular KV cache.

The decode-serving memory problem (vLLM, Kwon et al. SOSP'23): a dense
per-sequence KV cache must reserve `max_ctx` slots per sequence up
front, so real fleets run at 20-40% cache utilization.  Paging fixes it
the way virtual memory does — the cache is a pool of fixed-size pages
(``k_pages``/``v_pages``: ``(num_kv_heads, total_pages, page_size,
head_dim)``), each sequence owns a *page table* (``page_indices`` row),
and attention gathers through the table.  Allocation/eviction become
O(1) free-list ops (``serving/kvcache.py``) and admission control is
exact page accounting instead of worst-case reservation.

Two backends behind :func:`paged_attention` (the ops/attention.py,
ops/pallas/epilogue.py dispatch shape), over the pages form
``(KVH, P, S, D)``:

- **TPU**: ``jax.experimental.pallas.ops.tpu.paged_attention`` — the
  Pallas GQA kernel (SNIPPETS [3] shards this very kernel along KV
  heads for the multi-chip tier).  The kernel applies no softmax scale,
  so queries are pre-scaled here.
- **CPU**: an XLA gather-based reference — pages are gathered
  back into a contiguous ``(B, KVH, pages_per_seq * page_size, D)``
  view and attention runs as masked f32 softmax.  The whole decode
  engine is therefore tier-1 testable on CPU, and the reference IS the
  bit-exactness oracle: gathering a sequence's pages yields exactly the
  contiguous cache a non-paged decoder would hold, so a program that
  reads through this view matches a full-cache decode bit for bit.

**Who reads through which path** (the step programs of
``models.decoder`` hold the pools as token rows ``(L, P, S, KVH * D)``,
never in the pages form):

- the head-major view ``(B, KVH, C, D)`` (``models.decoder._gather_kv``
  and :func:`attend_ctx`'s mathematics): this op's reference and int8
  paths, the prefill-chunk and verify programs, the hybrid models'
  attention layers; a sequence's own pages, gathered whole.
- the decode step of the classic block, on a TPU, over a float pool
  whose page is whole lane tiles: :func:`paged_attend_rows`, this
  module's own kernel over the pool as it lies.  One query token a
  lane; each lane's page table is walked up to its length and those
  pages are copied out of the pool once, a block at a time, keys and
  values, into :func:`attend_rows`' two products and flash-decoding's
  running maximum and sum.  Pages past a length are never touched (in
  the chat cell some four fifths of the table: PERF.md, PR 36), and an
  inactive lane reads nothing.  Selected by :func:`rows_kernel_mode`
  from what the pool is, not by a model's name.
- the same decode step everywhere else (int8 :class:`QPages`, a ``tp``
  shard's row under 128 lanes, a page that is no whole tile, the CPU):
  every page of every table gathered as token rows
  (``models.decoder._gather_rows``) into :func:`attend_rows`, which
  never splits the rows' lane axis (at head_dim 64 that split cost more
  than the attention, PERF.md PR 30).  This is the reference the kernel
  is tested against.

The three are the same mathematics with the float additions in another
order, so the decode step agrees with the head-major view to float32
rounding (``tests/test_decode_attention_rows.py``: 1e-5 of the outputs'
std, the kernel in the interpreter included), and with the other
programs in its greedy tokens, not in the bits of its logits.

``MXNET_PAGED_ATTENTION`` — ``0``/``off`` forces the references,
``interpret`` runs the kernels through the Pallas interpreter (the CPU
test lane: ``pltpu.force_tpu_interpret_mode`` around jax's kernel,
``interpret=True`` for this module's own, whose jitted call is run again
from jax's cache, which the TPU interpreter's buffer ids do not
survive), default selects a kernel on
a TPU backend where the compiler takes it: jax's for heads whose
``head_dim`` is a multiple of the 128 lanes (:func:`kernel_mode_for`),
this module's for a pool whose page is whole tiles
(:func:`rows_kernel_mode`).  A kernel selected here that fails to
compile fails the call.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kernel_mode

__all__ = ["paged_attention", "paged_attention_reference",
           "paged_attend_rows", "copy_page", "QPages", "gather_pages_deq",
           "last_path"]


class QPages(NamedTuple):
    """int8 KV page pool + parallel per-(page, head) scales pool.

    ``q``: int8 codes, the fp page layout with the same axes —
    ``(KVH, P, S, D)`` per layer, ``(L, KVH, P, S, D)`` stacked, or the
    token rows ``(L, P, S, KVH * D)`` the step programs hold
    (``models.decoder``).
    ``s``: f32 scales, one per (page, kv-head) — ``(KVH, P)`` /
    ``(L, KVH, P)``; ``token ≈ q * s`` for every token in the page.

    A page's scale is LATCHED by the write landing at page slot 0
    (``amax(token)/127``); later writes into the page reuse it with
    codes clipped to [-127, 127].  That makes each page's scale a
    deterministic function of the token that opened it — speculative
    rollback (``PageAllocator.trim``) frees whole pages past the
    accepted prefix, and the boundary page's scale was latched by an
    already-confirmed token, so spec-vs-plain and migrated-vs-unmigrated
    decode stay bit-identical under int8 KV exactly as in fp.  (A
    running-max-with-rescale scheme would rewrite history on every
    append and break both batteries.)

    A NamedTuple is an automatic JAX pytree: QPages flows through
    ``jit`` donation, ``device_put``, and ``shard_map`` in_specs like
    the fp page array it replaces."""
    q: jax.Array
    s: jax.Array

# Which path the last call took: "pallas" | "pallas-interpret" | "xla".
# Tests assert on this to guarantee the kernel is actually exercised.
last_path = None



def _mode():
    """'compiled' | 'interpret' | None (XLA reference)."""
    return kernel_mode("MXNET_PAGED_ATTENTION")


def kernel_mode_for(head_dim):
    """:func:`_mode` for jax's kernel (:func:`paged_attention`, the pages
    form) over heads of ``head_dim``: None where the compiler refuses
    it.  The decode step does not ask here: it reads the pool as rows
    through :func:`paged_attend_rows`, at any head_dim
    (:func:`rows_kernel_mode`)."""
    mode = _mode()
    if mode == "compiled" and head_dim % 128:
        # jax's kernel blocks its (.., 1) softmax carries by head_dim, and
        # Mosaic refuses a 64-wide block of a 1-wide array: "the last two
        # dimensions of your block shape [must be] divisible by 8 and 128
        # respectively, or be equal to the respective dimensions of the
        # overall array" (v5e, PR 21).  Such heads read through the gather
        # reference of this op.
        mode = None
    return mode


def _pages_per_block(pages_per_seq):
    """Largest power-of-two divisor of pages_per_seq, capped at 8 — the
    kernel requires the compute block to tile the sequence's pages."""
    b = 1
    while b * 2 <= min(pages_per_seq, 8) and pages_per_seq % (b * 2) == 0:
        b *= 2
    return b


def gather_pages(pages, page_indices):
    """Gather per-sequence pages into contiguous per-sequence caches.

    pages: (KVH, P, S, D); page_indices: (B, pages_per_seq) int32
    -> (B, KVH, pages_per_seq * S, D), token-major per sequence — exactly
    the contiguous cache layout a non-paged decoder would hold.

    Page tables may alias: with copy-on-write prefix caching
    (``serving/kvcache.PrefixCache``) the same physical page id appears
    in several rows (and the scratch page in many), and a gather reads
    each reference independently — shared pages need no special casing
    here, only the write path must never scatter into a page whose
    refcount exceeds one (the engine forks first).
    """
    kvh, _, s, d = pages.shape
    b, pps = page_indices.shape
    # (KVH, B, pps, S, D) -> (B, KVH, pps*S, D)
    g = jnp.swapaxes(pages[:, page_indices], 0, 1)
    return g.reshape(b, kvh, pps * s, d)


def copy_page(pages, src, dst):
    """Duplicate one physical page: ``pages[..., dst, :, :] <-
    pages[..., src, :, :]``.  Works on any layout whose page axis is
    third-from-last — the kernel layout ``(KVH, P, S, D)``, the stacked
    ``(L, KVH, P, S, D)`` and the engine's token rows ``(L, P, S,
    KVH * D)``.  This is the device half of
    a copy-on-write fork (``PageAllocator.fork`` is the bookkeeping
    half): the writer copies the shared page into its fresh private one
    before the first divergent write.

    :class:`QPages` copies both pools — the codes page AND its scale
    entry (page axis is LAST in the scales pool), so a CoW fork of an
    int8 page carries the latched scale with it."""
    if isinstance(pages, QPages):
        return QPages(
            q=pages.q.at[..., dst, :, :].set(pages.q[..., src, :, :]),
            s=pages.s.at[..., dst].set(pages.s[..., src]))
    return pages.at[..., dst, :, :].set(pages[..., src, :, :])


def gather_pages_deq(codes, scales, page_indices):
    """Gather + dequantize int8 pages into contiguous fp32 caches.

    codes: (KVH, P, S, D) int8; scales: (KVH, P) f32;
    page_indices: (B, pages_per_seq) int32
    -> (B, KVH, pages_per_seq * S, D) f32 — the same contiguous layout
    :func:`gather_pages` produces, with each page's tokens scaled by its
    latched per-head scale.  This dequant-at-read is the int8-KV
    counterpart of the fp gather reference and shares its bit-exactness
    role: every consumer (decode read, prefill re-read, verify re-read)
    sees identical fp values for identical pages."""
    kvh, _, s, d = codes.shape
    b, pps = page_indices.shape
    g = jnp.swapaxes(codes[:, page_indices], 0, 1)     # (B,KVH,pps,S,D)
    sg = jnp.swapaxes(scales[:, page_indices], 0, 1)   # (B,KVH,pps)
    ctx = g.astype(jnp.float32) * sg[..., None, None]
    return ctx.reshape(b, kvh, pps * s, d)


def _masked_softmax(logits, lengths):
    """f32 softmax over the last axis, the keys at and past ``lengths[b]``
    (leading axis) masked off; a length of 0 (an inactive slot) gives
    zeros, not NaN."""
    at = jnp.arange(logits.shape[-1])
    live = at < lengths.reshape((-1,) + (1,) * (logits.ndim - 1))
    p = jax.nn.softmax(jnp.where(live, logits, -jnp.inf), axis=-1)
    return jnp.where(jnp.isnan(p), 0.0, p)


def attend_ctx(q, k_ctx, v_ctx, lengths, scale):
    """Masked decode attention over contiguous per-sequence caches.

    q: (B, H, D); k_ctx/v_ctx: (B, KVH, C, D); lengths: (B,) valid keys.
    f32 softmax, GQA by head grouping.  This inner math is shared by the
    paged reference (after gather) and by full-cache reference decoders,
    which is what makes "paged == full-cache" a bit-exact statement
    between them.  The decode step of ``models.decoder`` reads
    :func:`attend_rows` instead and agrees with this to float32
    rounding, not bit for bit.
    """
    b, h, d = q.shape
    kvh = k_ctx.shape[1]
    g = h // kvh
    qf = (q.astype(jnp.float32) * scale).reshape(b, kvh, g, d)
    logits = jnp.einsum("bkgd,bkcd->bkgc", qf, k_ctx.astype(jnp.float32))
    p = _masked_softmax(logits, lengths)
    out = jnp.einsum("bkgc,bkcd->bkgd", p, v_ctx.astype(jnp.float32))
    return out.reshape(b, h, d).astype(q.dtype)


def _own_lanes(h, num_kv_heads, head_dim):
    """own[h, e]: lane ``e`` of a token row belongs to the KV head that
    head ``h`` reads (head ``h`` reads KV head ``h // g``)."""
    g = h // num_kv_heads
    return (jnp.arange(num_kv_heads * head_dim, dtype=jnp.int32)[None, :]
            // head_dim == jnp.arange(h, dtype=jnp.int32)[:, None] // g)


def _q_rows(q, scale, own):
    """The scaled query of head ``h`` laid into a whole token row that is
    zero outside the lanes of its KV head: (B, H, D) -> (B, H, KVH * D)."""
    kvh = own.shape[1] // q.shape[-1]
    qf = q.astype(jnp.float32) * scale
    return jnp.where(own[None], jnp.tile(qf, (1, 1, kvh)), 0.0)


def _pick_own(out_rows, own, head_dim):
    """The block-diagonal pick, on (B, H, KVH * D): each head keeps its
    own lanes of the weighted rows; exact zeros added."""
    b, h, width = out_rows.shape
    return jnp.where(own[None], out_rows, 0.0).reshape(
        b, h, width // head_dim, head_dim).sum(2)


def attend_rows(q, k_rows, v_rows, lengths, scale, num_kv_heads):
    """:func:`attend_ctx`'s attention over the context as token rows.

    q: (B, H, D); k_rows/v_rows: (B, C, KVH * D), one row of all KV
    heads per token, which is how the step programs' pool holds them
    (``models.decoder``); lengths: (B,) valid keys.  The context's lane
    axis is never split or permuted: setting ``KVH * D`` lanes head-major
    at a head_dim of 64, half a lane tile, cost the decode step three
    passes over the gathered context (PERF.md, PR 30).  The head structure
    sits on the small side of each product instead: the query of head
    ``h`` is laid into a row that is zero outside the lanes of its KV
    head ``h // g``, the scores contract whole rows, the values come back
    as whole rows, and each head keeps its own lanes of them.  The added
    terms are exact zeros, so this is :func:`attend_ctx`'s mathematics
    with the float additions in another order: the two agree to float32
    rounding, not bit for bit.

    This reads a context that was gathered whole, every page of every
    table; it is the reference :func:`paged_attend_rows` is tested
    against and what int8 pools, rows under 128 lanes and the CPU read."""
    b, h, d = q.shape
    own = _own_lanes(h, int(num_kv_heads), d)
    logits = jnp.einsum("bhe,bce->bhc", _q_rows(q, scale, own),
                        k_rows.astype(jnp.float32))
    p = _masked_softmax(logits, lengths)
    out_rows = jnp.einsum("bhc,bce->bhe", p, v_rows.astype(jnp.float32))
    return _pick_own(out_rows, own, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# the decode step's kernel: the walk over each lane's live pages
# ---------------------------------------------------------------------------
_MASKED = -1e30         # a score past the length; finite, so no inf - inf
_BLOCK_TOKENS = 128     # tokens a block of pages holds (8 pages of 16)


def rows_kernel_mode(pool):
    """:func:`_mode` for :func:`paged_attend_rows` over ``pool``, by what
    the code sees: a float array in rows form ``(L, P, S, KVH * D)`` whose
    page is whole ``(8, 128)`` tiles of its dtype (the row a multiple of
    128 lanes, the page a multiple of the dtype's sublane tile), so that a
    page is one contiguous slab a copy can take as it lies.  None for
    everything else: int8 :class:`QPages`, a ``tp`` shard's row under 128
    lanes, a page of 4 tokens."""
    if isinstance(pool, QPages) or not jnp.issubdtype(pool.dtype,
                                                      jnp.floating):
        return None
    S, width = pool.shape[2:]
    if width % 128 or S % (32 // pool.dtype.itemsize):
        return None
    return _mode()


def _rows_kernel(li_ref, len_ref, tab_ref, q_ref, k_hbm, v_hbm, o_ref,
                 k_buf, v_buf, sems, slot_ref, *, page, block_pages,
                 pages_per_seq):
    """One lane a grid step: flash-decoding's recurrence over the lane's
    live pages, a block of ``block_pages`` of them at a time, each page
    copied out of the pools as it lies.  Two buffers a pool: while a
    block is attended the next one is in flight, and the last block of a
    lane is attended while the first of the next lane comes in (the
    buffer the next grid step starts in is handed over in ``slot_ref``).
    Pages past a length get no copy and no product."""
    lane, lanes = pl.program_id(0), pl.num_programs(0)
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
    q = q_ref[...]                                      # (H, KVH * D)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, tokens), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (tokens, 1), 0)

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
        left = n - i * tokens                           # live tokens here
        s = jax.lax.dot_general(
            q, k_buf[slot].reshape(tokens, -1).astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)         # (H, tokens)
        s = jnp.where(col < left, s, _MASKED)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        # what lies past the length in the buffer was never copied:
        # zeroed, since 0 * NaN is no zero
        v = jnp.where(row < left, v_buf[slot].reshape(tokens, -1).astype(
            jnp.float32), 0.0)
        return (m_new, alpha * l + p.sum(axis=-1, keepdims=True),
                alpha * acc + jnp.dot(p, v,
                                      preferred_element_type=jnp.float32))

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
def _rows_call(li, lengths, tables, q_rows, k_pool, v_pool, *, interpret):
    """The kernel over (1,) layer index, (B,) lengths, (B * pps,) page
    table, (B, H, KVH * D) query rows and the two pools.  A jitted
    function of its own so that the layers of a step program, which
    differ in ``li``'s value alone, share one trace of the kernel and one
    lowering of it to Mosaic: traced and lowered a layer at a time they
    took 8 s of every process's warm-up at 12 layers, whatever the
    compile cache holds (PERF.md, PR 36)."""
    b, hp, width = q_rows.shape
    S = k_pool.shape[2]
    pps = tables.shape[0] // b
    block_pages = max(1, min(pps, _BLOCK_TOKENS // S))
    lane = pl.BlockSpec((None, hp, width), lambda i, *_: (i, 0, 0))
    buf = pltpu.VMEM((2, block_pages, S, width), k_pool.dtype)
    return pl.pallas_call(
        functools.partial(_rows_kernel, page=S, block_pages=block_pages,
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
        name="paged_attend_rows",
    )(li, lengths, tables, q_rows, k_pool, v_pool)


def paged_attend_rows(q, k_pool, v_pool, li, lengths, tables, scale,
                      num_kv_heads):
    """:func:`attend_rows` over layer ``li`` of the pools without the
    gather: one query token a lane, each lane's page table walked up to
    its length and those pages read out of the pools once.

    q: (B, H, D); k_pool/v_pool: the pools whole, rows form
    ``(L, P, S, KVH * D)`` float, left where they are (the kernel copies
    pages out of them; the layer goes in as a scalar: slicing it out
    would make XLA copy a layer's slab before the call); lengths: (B,)
    valid keys, 0 for an inactive lane, which reads nothing and gives
    zeros; tables: (B, pages_per_seq).  The products are
    :func:`attend_rows`' two, over a block of pages as they lie, at the
    default precision (on the TPU one bfloat16 pass for float32
    operands, in Mosaic as in XLA: measured, PERF.md PR 36; float32 in
    the interpreter, as on the CPU), the softmax's maximum and sum
    carried in float32 from block to block.  Only where
    :func:`rows_kernel_mode` selects it."""
    global last_path
    mode = rows_kernel_mode(k_pool)
    b, h, d = q.shape
    own = _own_lanes(h, int(num_kv_heads), d)
    hp = -(-h // 8) * 8                 # whole sublane tiles of heads
    q_rows = jnp.pad(_q_rows(q, scale, own), ((0, 0), (0, hp - h), (0, 0)))
    out_rows = _rows_call(
        jnp.asarray(li, jnp.int32).reshape(1), lengths.astype(jnp.int32),
        tables.astype(jnp.int32).reshape(-1), q_rows, k_pool, v_pool,
        interpret=mode == "interpret")
    last_path = "pallas" if mode == "compiled" else "pallas-interpret"
    return _pick_own(out_rows[:, :h], own, d).astype(q.dtype)


def paged_attention_reference(q, k_pages, v_pages, lengths, page_indices,
                              scale=None):
    """XLA gather-based reference: pages -> contiguous view -> masked
    f32 softmax.  Correct for any (GQA) head grouping and inactive
    (length-0) rows."""
    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    k_ctx = gather_pages(k_pages, page_indices)
    v_ctx = gather_pages(v_pages, page_indices)
    return attend_ctx(q, k_ctx, v_ctx, lengths, scale)


def paged_attention(q, k_pages, v_pages, lengths, page_indices, scale=None):
    """Decode-phase paged attention (one query token per sequence).

    q:            (B, num_heads, head_dim) — this step's query rows
    k_pages/v_pages: (num_kv_heads, total_pages, page_size, head_dim)
    lengths:      (B,) int32 — valid context length per sequence
                  (inactive batch slots pass 0: their output is garbage
                  by contract and masked off by the caller)
    page_indices: (B, pages_per_seq) int32 page table rows

    Returns (B, num_heads, head_dim) in q.dtype.

    Shard-oblivious by design: under a tensor-parallel decode step
    (``models.decoder.tp_plan``) this runs INSIDE ``shard_map``, so
    ``num_heads``/``num_kv_heads`` here are the per-shard counts
    (global // tp) and the page axis is full on every shard.  Heads
    shard contiguously, so each shard's local GQA group structure —
    head ``h`` reads KV head ``h // (num_heads // num_kv_heads)`` —
    is exactly the global one and the kernel needs no sharding
    awareness at all; attention is embarrassingly parallel over heads.
    """
    global last_path
    if isinstance(k_pages, QPages):
        # int8 KV pages: dequant-at-read through the gather reference —
        # the contiguous fp view is exactly what a full-cache decoder
        # holding the dequantized tokens would attend over, so the
        # paged==full-cache bit statement survives quantization
        d = q.shape[-1]
        s = scale if scale is not None else 1.0 / (d ** 0.5)
        k_ctx = gather_pages_deq(k_pages.q, k_pages.s, page_indices)
        v_ctx = gather_pages_deq(v_pages.q, v_pages.s, page_indices)
        last_path = "xla"
        return attend_ctx(q, k_ctx, v_ctx, lengths, s)
    mode = kernel_mode_for(q.shape[-1])
    if mode is not None:
        import contextlib
        from jax.experimental.pallas import tpu as pltpu
        from jax.experimental.pallas.ops.tpu.paged_attention import (
            paged_attention as kernel)
        d = q.shape[-1]
        s = scale if scale is not None else 1.0 / (d ** 0.5)
        # the TPU kernel masks length-0 rows itself but divides by a
        # zero denominator; clamp to 1 (reads the scratch page, the
        # caller discards inactive rows either way)
        safe_len = jnp.maximum(lengths.astype(jnp.int32), 1)
        # jax's kernel takes no interpret argument: the TPU interpreter
        # is switched on around the call instead
        interp = (pltpu.force_tpu_interpret_mode() if mode == "interpret"
                  else contextlib.nullcontext())
        with interp:
            out = kernel(
                (q * jnp.asarray(s, q.dtype)), k_pages, v_pages,
                safe_len, page_indices.astype(jnp.int32),
                pages_per_compute_block=_pages_per_block(
                    page_indices.shape[1]))
        last_path = "pallas" if mode == "compiled" else "pallas-interpret"
        return out
    last_path = "xla"
    return paged_attention_reference(q, k_pages, v_pages, lengths,
                                     page_indices, scale=scale)
