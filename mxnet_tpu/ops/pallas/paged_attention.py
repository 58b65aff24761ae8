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

Two backends behind one call (the ops/attention.py, ops/pallas/epilogue.py
dispatch shape):

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

Who reads through that head-major view: this op's reference and int8
paths (:func:`attend_ctx`) and, with their own causal products over the
same view (``models.decoder._gather_kv``), the prefill-chunk and verify
programs and the hybrid model's attention layers.  The decode step of
``models.decoder`` does not, where the kernel is not selected: it reads
the same gathered pages as token rows
``(B, C, KVH * D)`` through :func:`attend_rows`, which never splits the
rows' lane axis (at head_dim 64 that split cost more than the attention,
PERF.md PR 30).  The two are the same mathematics with the float
additions in another order, so the decode step agrees with the
head-major view to float32 rounding (``tests/test_decode_attention_rows
.py``: 1e-5 of the outputs' std), and with the other programs in its
greedy tokens, not in the bits of its logits.

``MXNET_PAGED_ATTENTION`` — ``0``/``off`` forces the reference,
``interpret`` runs the Pallas kernel through the TPU interpreter
(``pltpu.force_tpu_interpret_mode`` — the CPU test lane for the kernel
and its wrapper), default selects the kernel on a TPU backend for heads
whose ``head_dim`` is a multiple of the 128 lanes (the compiler refuses
the rest).  A kernel selected here that fails to compile fails the call.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import kernel_mode

__all__ = ["paged_attention", "paged_attention_reference", "copy_page",
           "QPages", "gather_pages_deq", "last_path"]


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
    """:func:`_mode` for heads of ``head_dim``: None where the compiler
    refuses the kernel."""
    mode = _mode()
    if mode == "compiled" and head_dim % 128:
        # jax's kernel blocks its (.., 1) softmax carries by head_dim, and
        # Mosaic refuses a 64-wide block of a 1-wide array: "the last two
        # dimensions of your block shape [must be] divisible by 8 and 128
        # respectively, or be equal to the respective dimensions of the
        # overall array" (v5e, PR 21).  Such heads read through the gather.
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
    rounding, not bit for bit."""
    b, h, d = q.shape
    width = k_rows.shape[-1]
    kvh = int(num_kv_heads)
    g = h // kvh
    # own[h, e]: lane e belongs to the KV head that head h reads
    own = (jnp.arange(width, dtype=jnp.int32)[None, :] // d
           == jnp.arange(h, dtype=jnp.int32)[:, None] // g)
    qf = q.astype(jnp.float32) * scale
    q_rows = jnp.where(own[None], jnp.tile(qf, (1, 1, kvh)), 0.0)
    logits = jnp.einsum("bhe,bce->bhc", q_rows, k_rows.astype(jnp.float32))
    p = _masked_softmax(logits, lengths)
    out_rows = jnp.einsum("bhc,bce->bhe", p, v_rows.astype(jnp.float32))
    # the block-diagonal pick, on (B, H, KVH * D): exact zeros added
    out = jnp.where(own[None], out_rows, 0.0).reshape(b, h, kvh, d).sum(2)
    return out.astype(q.dtype)


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
