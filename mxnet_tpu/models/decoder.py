"""Causal decoder LM for autoregressive decode serving.

The model half of ``serving/generate.py``'s continuous-batching engine:
a small GPT-style decoder built from the SAME blocks the BERT encoder
uses (``nn.Dense``/``nn.LayerNorm`` parameter containers, the reused
``PositionwiseFFN``, the PR-2 fused ``bias_gelu`` epilogue kernel, the
flash-attention kernel for the full-sequence path) — plus the pieces an
LLM server needs that an encoder never does:

- ``full_forward``      — whole-sequence causal forward (training /
  one-shot scoring / the greedy-parity oracle).  Flash attention with
  ``causal=True`` (Pallas on TPU, XLA reference on CPU).
- ``make_prefill_chunk`` — jitted fixed-shape chunk prefill: process
  ``chunk`` prompt tokens of ONE sequence, scatter their KV into cache
  pages, attend causally against the sequence's own pages.  Long
  prompts run as a series of these, interleaved with decode steps.
- ``make_decode_step``  — jitted one-token-per-sequence decode over the
  whole slot batch: scatter this step's KV into pages, paged attention
  (``ops/pallas/paged_attention``), greedy next token.  KV page arrays
  are donated, so the cache is updated in place on accelerators.

GQA layout: ``num_heads`` query heads grouped onto ``num_kv_heads`` KV
heads (head ``h`` reads KV head ``h // (H // KVH)``) — the grouping the
TPU paged-attention kernel expects, consistent across all three paths.

A second block, state-space layers with attention layers among them
(:mod:`.hybrid`, builder :func:`hybrid_lm`), is served by the same
factories: ``make_decode_step``, ``make_prefill_chunk``, ``fresh_pool``
and ``full_forward`` dispatch on the config (:func:`is_hybrid`), and its
pools carry a recurrent state paged beside the KV rows.

Weights are read once through :meth:`CausalLM.jax_params` (raw
``jax.Array`` pytree) and treated as frozen for serving — the registry
hot-swap path replaces the whole model, never mutates weights in place.
"""
from __future__ import annotations

import collections
import functools
import threading
import warnings
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .. import config as _config
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..gluon.parameter import Parameter
from ..ops import attention as _attention
from ..ops.pallas import epilogue as _epilogue
from ..ops.pallas import fused_cell as _fused
from ..ops.pallas import paged_attention as _paged
from ..ops.pallas import quant_matmul as _qmm
from .bert import PositionwiseFFN

# jax warns when buffer donation is requested on backends that ignore it
# (CPU); donation is a no-op there and the hint is correct for TPU
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")

__all__ = ["DecoderConfig", "CausalLM", "full_forward", "make_decode_step",
           "make_prefill_chunk",
           "make_verify_step", "make_token_combine",
           "pool_shape", "fresh_pool", "rows_from_pages", "pages_from_rows",
           "fork_page", "put_pages",
           "fn_cache_stats", "decode_launch_stats",
           "verify_launch_stats", "decode_collective_stats", "tp_plan",
           "TPPlan", "causal_lm", "decoder_tiny", "decoder_tiny_lm",
           "decoder_draft", "hybrid_lm", "routed_delta_lm", "is_hybrid"]


# ---------------------------------------------------------------------------
# bounded per-geometry program cache
# ---------------------------------------------------------------------------
class _FnCache:
    """LRU cache for the jitted decode/prefill builders.

    Each (cfg, page_size, …) geometry compiles its own fixed-shape XLA
    program; an unbounded cache lets admit/evict churn across many
    (batch, pages) geometries grow compiled-program memory without
    limit.  Capacity comes from ``MXNET_GEN_FN_CACHE`` (read per miss so
    tests/ops can retune live); compile/evict counts are exported via
    :func:`fn_cache_stats` and surface in ServingMetrics.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._od = collections.OrderedDict()
        self.compiles = 0
        self.evictions = 0

    def _cap(self):
        try:
            return max(1, int(_config.get("MXNET_GEN_FN_CACHE")))
        except (TypeError, ValueError):
            return 16

    def get(self, key, builder):
        with self._lock:
            fn = self._od.get(key)
            if fn is not None:
                self._od.move_to_end(key)
                return fn
        fn = builder()  # build outside the lock (tracing can be slow)
        with self._lock:
            if key not in self._od:
                self._od[key] = fn
                self.compiles += 1
                cap = self._cap()
                while len(self._od) > cap:
                    self._od.popitem(last=False)
                    self.evictions += 1
            else:
                self._od.move_to_end(key)
            return self._od[key]

    def stats(self):
        with self._lock:
            return {"size": len(self._od), "cap": self._cap(),
                    "compiles": self.compiles,
                    "evictions": self.evictions}

    def clear(self):
        with self._lock:
            self._od.clear()
            self.compiles = 0
            self.evictions = 0


_fn_cache = _FnCache()


def fn_cache_stats():
    """{size, cap, compiles, evictions} of the decode/prefill program
    cache (shared across decode, prefill and verify builders)."""
    return _fn_cache.stats()


class DecoderConfig(NamedTuple):
    """Static (hashable) model geometry — the jit-cache key for the
    decode/prefill programs."""
    vocab_size: int
    num_layers: int
    units: int
    hidden_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    max_length: int


def _ln(x, gamma, beta, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * gamma + beta).astype(
        x.dtype)


def _dot_t(x, w):
    """``x @ w.T`` with the gluon (out, in) weight convention —
    dispatching integer weight leaves (``quant_matmul.QuantW8/W4``,
    produced by ``serving.quantize.quantize_lm``) through the fused
    dequant-matmul.  Every GEMM of every decode path funnels through
    here, so a quantized param pytree quantizes ALL of prefill, decode,
    verify, and the full-forward oracle at once."""
    if _qmm.is_quantized(w):
        return _qmm.quant_matmul(x, w)
    return jnp.dot(x, w.T)


def _proj(x, w, b=None):
    """Dense with the gluon (out, in) weight convention."""
    y = _dot_t(x, w)
    return y if b is None else y + b


def _ffn(x, lp):
    """PositionwiseFFN math via the fused bias_gelu epilogue (the PR-2
    kernel: Pallas on accelerators, the XLA-fused chain on CPU)."""
    h = _epilogue.bias_gelu(_proj(x, lp["w1"]), lp["b1"])
    return _proj(h, lp["w2"], lp["b2"])


def _qkv(x, lp, cfg):
    """x: (..., C) -> q (..., H, D), k/v (..., KVH, D)."""
    lead = x.shape[:-1]
    q = _proj(x, lp["wq"], lp["bq"]).reshape(
        lead + (cfg.num_heads, cfg.head_dim))
    k = _proj(x, lp["wk"], lp["bk"]).reshape(
        lead + (cfg.num_kv_heads, cfg.head_dim))
    v = _proj(x, lp["wv"], lp["bv"]).reshape(
        lead + (cfg.num_kv_heads, cfg.head_dim))
    return q, k, v


def _layer_tail(x, att_merged, lp, axis=None):
    """Shared post-attention epilogue: proj + residual LN + FFN + LN
    (post-LN, the TransformerLayer convention).

    With ``axis`` set this is the row-parallel tail of a Megatron layer:
    ``wo``/``w2`` are in-feature shards, so their dots produce PARTIAL
    sums that all-reduce over the named mesh axis; the replicated biases
    are added after the reduce.  These two psums are the ONLY cross-chip
    traffic of a tensor-parallel decode layer."""
    if axis is None:
        o = _proj(att_merged, lp["wo"], lp["bo"])
    else:
        o = jax.lax.psum(_dot_t(att_merged, lp["wo"]), axis) + lp["bo"]
    x = _ln(x + o, lp["ln1g"], lp["ln1b"])
    if axis is None:
        f = _ffn(x, lp)
    else:
        h = _epilogue.bias_gelu(_proj(x, lp["w1"]), lp["b1"])
        f = jax.lax.psum(_dot_t(h, lp["w2"]), axis) + lp["b2"]
    return _ln(x + f, lp["ln2g"], lp["ln2b"])


# ---------------------------------------------------------------------------
# the KV pool: one row per token, written in place
# ---------------------------------------------------------------------------
# The step programs hold K and V as ``(L, P, S, KVH * D)``: layer, page,
# slot in the page, then ONE row of all KV heads per token.  A row is a
# whole number of 128-lane tiles wherever ``KVH * D`` is a multiple of
# 128, so the device's own default layout of that shape is the plain
# row-major one with no padding: the pool enters and leaves a program
# as it lies, a token's K is one contiguous row to write, and a page one
# contiguous slab to gather.  The pages form ``(L, KVH, P, S, D)`` is
# what the paged-attention op and the wire format of a migrated session
# speak; at head_dim 64 a TPU lays THAT shape out with the page axis on
# the lanes, which no scatter or gather can use,
# and XLA then relays the whole pool out on the way into and out of
# every launch (PERF.md, PR 26: two thirds of the device's time).  A
# layout pinned on the program (``jax.experimental.layout.Format``)
# would keep the pages form, but jax 0.9.0's persistent compilation
# cache hands back an executable that has lost the pin (same finding).
def pool_shape(cfg, total_pages, page_size):
    """Shape of one pool in rows form: (L, P, S, KVH * D)."""
    return (cfg.num_layers, int(total_pages), int(page_size),
            cfg.num_kv_heads * cfg.head_dim)


def is_hybrid(cfg):
    """True for a model with recurrent layers, state-space or delta-rule
    (:class:`~.hybrid.HybridConfig`): its pools carry a recurrent state
    beside the KV rows and its programs come from :mod:`.hybrid`."""
    return hasattr(cfg, "layer_kinds")


def _kv_dtype(cfg, kv_dtype):
    """The cache dtype of a program or pool: the one asked for, else the
    model's own (``cfg.kv_dtype`` where the config has one), else
    float32.  A caller that names none (the benchmark's logits check) and
    the engine, which names its own, then meet in the same cache entry."""
    return str(kv_dtype if kv_dtype is not None
               else getattr(cfg, "kv_dtype", "float32"))


def fresh_pool(cfg, total_pages, page_size, kv_dtype=None):
    """A zeroed pool, one of the two (K, V) a step program takes.  What a
    pool is is the program's business; callers hold it as an opaque
    pytree and hand it back.

    For the classic block: rows form, a float32 or bfloat16 array, or an
    int8 :class:`~..ops.pallas.paged_attention.QPages` (codes in rows
    form, per-(layer, head, page) scales).  Scales start at ONE so
    untouched pages (the scratch page, inactive slots) dequantize to exact
    zeros, like the float pool.

    For a model with state-space layers: a :class:`~.hybrid.HybridPool`,
    the attention layers' token rows and, beside them, **one state entry
    per page and state-space layer** (the recurrent state after the last
    token written into that page, float32).  The K pool carries the first
    half of the channels and the V pool the second, so the two calls
    return the same structure and neither part stays unread."""
    kv_dtype = _kv_dtype(cfg, kv_dtype)
    if is_hybrid(cfg):
        return _hybrid.fresh_pool(cfg, total_pages, page_size, kv_dtype)
    shape = pool_shape(cfg, total_pages, page_size)
    if kv_dtype == "int8":
        return _paged.QPages(
            q=jnp.zeros(shape, jnp.int8),
            s=jnp.ones((cfg.num_layers, cfg.num_kv_heads, shape[1]),
                       jnp.float32))
    return jnp.zeros(shape, jnp.dtype(kv_dtype))


def _codes(pool):
    """The (L, ..) array of a pool that holds the tokens."""
    return pool.q if isinstance(pool, _paged.QPages) else pool


def _with_codes(pool, codes):
    if isinstance(pool, _paged.QPages):
        return _paged.QPages(q=codes, s=pool.s)
    return codes


@jax.jit
def rows_from_pages(pool):
    """Pages form (L, KVH, P, S, D) -> rows form (L, P, S, KVH * D);
    int8 scales pass through."""
    a = _codes(pool)
    L, kvh, P, S, d = a.shape
    return _with_codes(
        pool, a.transpose(0, 2, 3, 1, 4).reshape(L, P, S, kvh * d))


@functools.partial(jax.jit, static_argnums=1)
def pages_from_rows(pool, num_kv_heads):
    """Rows form -> pages form (the inverse of :func:`rows_from_pages`)."""
    a = _codes(pool)
    L, P, S, width = a.shape
    a = a.reshape(L, P, S, num_kv_heads, width // num_kv_heads)
    return _with_codes(pool, a.transpose(0, 3, 1, 2, 4))


@functools.partial(jax.jit, donate_argnums=0)
def fork_page(pool, src, dst):
    """Copy page ``src`` over page ``dst`` in every layer of a rows-form
    pool, in place: the device half of a copy-on-write fork.  A hybrid
    pool's page takes its state entries along."""
    if isinstance(pool, _hybrid.HybridPool):
        rows, ssm, conv = jax.tree.map(
            lambda a: a.at[:, dst].set(a[:, src]), tuple(pool[:3]))
        return pool._replace(rows=rows, ssm=ssm, conv=conv)
    return _paged.copy_page(pool, src, dst)


@functools.partial(jax.jit, donate_argnums=0)
def put_pages(pool, idx, blob):
    """Write ``blob`` (a rows-form pool of ``len(idx)`` pages) over the
    pages ``idx`` of ``pool``, in place: a migrated session's import."""
    def write(i, codes):
        page = jax.lax.dynamic_slice_in_dim(_codes(blob), i, 1, axis=1)
        return jax.lax.dynamic_update_slice(codes, page, (0, idx[i], 0, 0))
    codes = jax.lax.fori_loop(0, idx.shape[0], write, _codes(pool))
    if isinstance(pool, _paged.QPages):
        return _paged.QPages(q=codes, s=pool.s.at[:, :, idx].set(blob.s))
    return codes


def _write_rows(pool, li, wp, ws, rows):
    """``pool[li, wp[t], ws[t], :] = rows[t]`` for every token ``t`` in
    order (a later duplicate wins).  Each token's page is read, its row
    laid over it, and the page written back whole with one
    ``dynamic_update_slice`` of (1, 1, S, KVH * D): whole tiles for
    float rows and int8 codes alike, in place in a donated pool.
    wp/ws: any shape; rows: ``wp.shape + (KVH, D)``."""
    S, width = pool.shape[2:]
    wp, ws = wp.reshape(-1), ws.reshape(-1)
    rows = rows.reshape(-1, 1, 1, 1, width)
    slot = jnp.arange(S, dtype=jnp.int32)[None, None, :, None]

    def write(t, pool):
        at = (li, wp[t], 0, 0)
        old = jax.lax.dynamic_slice(pool, at, (1, 1, S, width))
        return jax.lax.dynamic_update_slice(
            pool, jnp.where(slot == ws[t], rows[t], old), at)
    return jax.lax.fori_loop(0, wp.shape[0], write, pool)


def _write_chunk(pool, li, page_row, pos0, n_valid, rows):
    """The same write for a prefill chunk, a page at a time: ``rows``
    (T, KVH, D) are consecutive positions ``pos0 ..`` of the sequence
    whose page table is ``page_row``, the first ``n_valid`` of them
    real.  Each page the chunk touches is read, the chunk's valid rows
    laid over it, and written back whole: T/S + 1 updates of a page
    instead of T of a row.  A page slot with no valid row targets the
    scratch page, which gets its own content back: padded tokens change
    nothing anywhere."""
    S, width = pool.shape[2:]
    T = rows.shape[0]
    n_pages = -(-T // S) + 1            # a chunk may start mid-page
    off = pos0 % S
    grid = jax.lax.dynamic_update_slice(
        jnp.zeros((n_pages * S, width), rows.dtype),
        rows.reshape(T, width), (off, 0)).reshape(n_pages, 1, 1, S, width)
    t = jnp.arange(n_pages * S, dtype=jnp.int32) - off
    live = ((t >= 0) & (t < n_valid)).reshape(n_pages, S)
    slot = pos0 // S + jnp.arange(n_pages, dtype=jnp.int32)
    pid = jnp.where(live.any(axis=1),
                    page_row[jnp.clip(slot, 0, page_row.shape[0] - 1)], 0)

    def write(k, pool):
        at = (li, pid[k], 0, 0)
        old = jax.lax.dynamic_slice(pool, at, (1, 1, S, width))
        return jax.lax.dynamic_update_slice(
            pool, jnp.where(live[k][None, None, :, None], grid[k], old), at)
    return jax.lax.fori_loop(0, n_pages, write, pool)


def _kv_append(pages, li, wp, ws, val, chunk=None):
    """Write new tokens into layer ``li`` of a rows-form pool.

    ``wp``/``ws``: (..., T) int write page/slot per token; the LAST axis
    indexes CONSECUTIVE positions of one sequence (decode passes T=1 by
    expanding a singleton axis; prefill passes the chunk; verify the
    spec window).  ``val``: ``ws.shape + (KVH, D)``.  ``chunk``:
    prefill's ``(page_row, pos0, n_valid)``, which lets the write go a
    page at a time (:func:`_write_chunk`); wp/ws then describe the same
    targets token by token.

    fp pools write the rows as they are.  int8 :class:`~..ops.pallas.
    paged_attention.QPages` quantize with the page-start scale latch: a
    token landing at page slot 0 sets its page's per-head scale to
    ``amax/127``; every other token reuses the scale its page start
    latched — looked up within this call's window when the start is in
    it (``src = t - ws``), from the scales pool otherwise.  Duplicate
    scale writes within a window all carry the same value, so the
    scatter is order-independent."""
    if chunk is None:
        def write(pool, rows):
            return _write_rows(pool, li, wp, ws, rows)
    else:
        def write(pool, rows):
            return _write_chunk(pool, li, *chunk, rows)
    if not isinstance(pages, _paged.QPages):
        return write(pages, val.astype(pages.dtype))
    amax = jnp.abs(val.astype(jnp.float32)).max(axis=-1)   # ws.shape+(KVH,)
    fresh = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
    old = pages.s[li, :, wp]                               # ws.shape+(KVH,)
    t = ws.shape[-1]
    src = jnp.arange(t, dtype=jnp.int32) - ws              # page-start idx
    start_fresh = jnp.take_along_axis(
        fresh, jnp.clip(src, 0, t - 1)[..., None], axis=-2)
    snew = jnp.where((src >= 0)[..., None], start_fresh, old)
    codes = jnp.clip(jnp.round(val.astype(jnp.float32) / snew[..., None]),
                     -127, 127).astype(jnp.int8)
    return _paged.QPages(q=write(pages.q, codes),
                         s=pages.s.at[li, :, wp].set(snew))


def _gather_kv(pages, li, tables, num_kv_heads):
    """Contiguous fp32 per-sequence context from layer ``li`` of a
    rows-form pool: the pages of ``tables`` (B, pages_per_seq) gathered
    as whole slabs straight out of the pool (slicing the layer out first
    makes XLA copy, and convert, the whole slab to read a few pages of
    it), then set head-major -> (B, KVH, pages_per_seq * S, D), the view
    :func:`~..ops.pallas.paged_attention.attend_ctx` and a non-paged
    decoder share.  int8 codes are dequantized by their page's latched
    per-head scale, value for value what ``gather_pages_deq`` gives for
    the pages form.

    The prefill-chunk and verify programs and the hybrid model's
    attention layers still read through this view (a sequence's own
    pages; their products run over (.., C, D) per head as a full-cache
    decoder's do).  The decode step does not: setting the
    context head-major costs three passes over it where ``D`` is half a
    lane tile, so it reads token rows, through the kernel that walks each
    lane's live pages or through :func:`_gather_rows`, and agrees with
    this view to float32 rounding (:func:`_decode_attention`)."""
    rows = _codes(pages)
    b, pps = tables.shape
    S = rows.shape[2]
    ctx = rows[li, tables].reshape(b, pps, S, num_kv_heads, -1)
    ctx = ctx.transpose(0, 3, 1, 2, 4)                 # (B,KVH,pps,S,D)
    if isinstance(pages, _paged.QPages):
        sg = jnp.swapaxes(pages.s[li][:, tables], 0, 1)  # (B,KVH,pps)
        ctx = ctx.astype(jnp.float32) * sg[..., None, None]
    return ctx.reshape(b, num_kv_heads, pps * S, -1)


def _gather_rows(pages, li, tables):
    """The context of :func:`_gather_kv` as it lies in the pool: the
    pages of ``tables`` (B, pages_per_seq) of layer ``li`` as token rows
    (B, pages_per_seq * S, KVH * D), no head axis split off.  int8 codes
    are dequantized in the row form, the page's per-head scale spread
    over its head's lanes: value for value what :func:`_gather_kv`
    gives.  Every page of every table, whatever the lengths: what the
    decode step reads where the kernel is not selected, and the kernel's
    reference."""
    ctx = _codes(pages)[li, tables]                    # (B,pps,S,KVH*D)
    b, pps, S, width = ctx.shape
    if isinstance(pages, _paged.QPages):
        sg = jnp.moveaxis(pages.s[li][:, tables], 0, -1)    # (B,pps,KVH)
        sg = jnp.repeat(sg, width // sg.shape[-1], axis=-1)
        ctx = ctx.astype(jnp.float32) * sg[:, :, None, :]
    return ctx.reshape(b, pps * S, width)


def _decode_attention(q, k_pages, v_pages, li, lengths, tables,
                      num_kv_heads):
    """One query token per sequence against layer ``li`` of the pools,
    as token rows either way (the mathematics of
    ``attend_ctx(_gather_kv(..))`` without the head-major relayout of the
    context that prefill, verify and the hybrid programs still read
    through; the two agree to float32 rounding).

    Where :func:`~..ops.pallas.paged_attention.rows_kernel_mode` selects
    the kernel (a float pool whose page is whole lane tiles, on a TPU;
    the interpreter under ``MXNET_PAGED_ATTENTION=interpret``) each
    lane's page table is walked up to its length and those pages are read
    out of the pool once (:func:`~..ops.pallas.paged_attention.
    paged_attend_rows`).  Else (int8 pools, a ``tp`` shard's row under
    128 lanes, the CPU) every page of every table is gathered and the
    masked f32 softmax runs over the gathered rows
    (:func:`~..ops.pallas.paged_attention.attend_rows`), which is the
    reference the kernel is tested against."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    if _paged.rows_kernel_mode(k_pages) is not None:
        return _paged.paged_attend_rows(q, k_pages, v_pages, li, lengths,
                                        tables, scale, num_kv_heads)
    _paged.last_path = "xla"
    return _paged.attend_rows(
        q, _gather_rows(k_pages, li, tables),
        _gather_rows(v_pages, li, tables), lengths, scale, num_kv_heads)


# ---------------------------------------------------------------------------
# tensor-parallel plan (ShardingConfig -> per-shard decode geometry)
# ---------------------------------------------------------------------------
# The raw jax_params pytree has no gluon path names, but the layout rules
# (ShardingConfig.for_transformer) are written against them — synthesize
# the paths the gluon blocks would carry so ONE rule set covers training
# and serving.  LN/embeddings have no entry: they resolve replicated.
_TP_PARAM_PATHS = {
    "wq": "attention.qkv.weight", "bq": "attention.qkv.bias",
    "wk": "attention.qkv.weight", "bk": "attention.qkv.bias",
    "wv": "attention.qkv.weight", "bv": "attention.qkv.bias",
    "wo": "attention.proj.weight", "bo": "attention.proj.bias",
    "w1": "ffn.ffn1.weight", "b1": "ffn.ffn1.bias",
    "w2": "ffn.ffn2.weight", "b2": "ffn.ffn2.bias",
}

#: the GEMM leaves quantize_lm replaces with QuantW8/QuantW4 structures
#: (biases, LN params and embeddings stay fp)
_QUANT_KINDS = ("wq", "wk", "wv", "wo", "w1", "w2")


def _shard_token(sharding):
    """Hashable cache-key component for the active sharding: config
    signature + mesh device identity (same signature on a different
    device set must NOT share a compiled program).  With no explicit
    config the ambient scope's token keys the entry, so flipping the
    active config cannot serve a stale program."""
    if sharding is None:
        from ..parallel import shardcfg as _shardcfg
        return _shardcfg.active_token()
    return (sharding.signature(),
            tuple(int(d.id) for d in sharding.mesh.devices.flat))


class TPPlan:
    """Resolved tensor-parallel serving layout for one (cfg, sharding).

    Holds the local (per-shard) decode geometry — heads, KV heads and
    FFN width divided by tp; ``units``/``head_dim`` stay FULL because
    activations are replicated — plus the PartitionSpecs for the param
    pytree and the KV pools (each shard holds its own KV heads of every
    token row).  Built via :func:`tp_plan`.
    """

    def __init__(self, sharding, cfg, quant=None, kv_int8=False):
        from jax.sharding import NamedSharding, PartitionSpec as P
        self.sharding = sharding
        self.cfg = cfg
        self.axis = "tp"
        self.tp = int(sharding.axis_size("tp"))
        self.mesh = sharding.mesh
        self.local_cfg = cfg._replace(
            num_heads=cfg.num_heads // self.tp,
            num_kv_heads=cfg.num_kv_heads // self.tp,
            hidden_size=cfg.hidden_size // self.tp)
        #: quant token (None | ("int8",) | ("int4", group)) — switches
        #: the GEMM param leaves to QuantW8/QuantW4 spec structures
        self.quant = quant
        self.kv_int8 = bool(kv_int8)
        # the pool in rows form (L, P, S, KVH * D): heads lie contiguous
        # in a row, so splitting the row over tp gives each shard its
        # own KV heads (the Pope et al. layout SNIPPETS.md [3] uses)
        self.kv_rows_spec = P(None, None, None, "tp")
        if self.kv_int8:
            # int8: the codes shard like fp rows; the parallel scales
            # pool (L, KVH, P) shards along its KV-head axis
            self.kv_in_spec = _paged.QPages(q=self.kv_rows_spec,
                                            s=P(None, "tp", None))
            self.kv_sharding = _paged.QPages(
                q=NamedSharding(self.mesh, self.kv_rows_spec),
                s=NamedSharding(self.mesh, P(None, "tp", None)))
        else:
            self.kv_in_spec = self.kv_rows_spec
            self.kv_sharding = NamedSharding(self.mesh, self.kv_rows_spec)

    def leaf_spec(self, kind, shape):
        """PartitionSpec for one layer-param leaf (``wq``/``b2``/…),
        resolved through the config's rules against the synthesized
        gluon path — unmatched leaves (LN, embeddings) replicate."""
        from jax.sharding import PartitionSpec as P
        path = _TP_PARAM_PATHS.get(kind)
        if path is None:
            return P()
        return self.sharding.param_spec("layers.0." + path, shape)

    def _layer_shapes(self):
        c = self.cfg
        kvu = c.num_kv_heads * c.head_dim
        return {"wq": (c.units, c.units), "bq": (c.units,),
                "wk": (kvu, c.units), "bk": (kvu,),
                "wv": (kvu, c.units), "bv": (kvu,),
                "wo": (c.units, c.units), "bo": (c.units,),
                "w1": (c.hidden_size, c.units), "b1": (c.hidden_size,),
                "w2": (c.units, c.hidden_size), "b2": (c.units,),
                "ln1g": (c.units,), "ln1b": (c.units,),
                "ln2g": (c.units,), "ln2b": (c.units,)}

    def param_specs(self):
        """Spec pytree matching the jax_params structure (shapes are a
        function of cfg alone, so builders need no live params).

        With a quant token the six GEMM leaves become QuantW8/QuantW4
        spec structures: the integer codes inherit the fp weight's
        column/row axes; int8 per-oc scales follow the output axis only
        (replicated for row-parallel — the global per-oc amax is
        shard-consistent); int4 per-group scales follow both axes
        (groups are shard-local by construction — the serving quantizer
        re-derives the group size against the LOCAL input dim)."""
        from jax.sharding import PartitionSpec as P
        lp = {k: self.leaf_spec(k, s)
              for k, s in self._layer_shapes().items()}
        if self.quant is not None:
            mode = self.quant[0]
            for k in _QUANT_KINDS:
                base = tuple(lp[k]) + (None,) * (2 - len(tuple(lp[k])))
                o_ax, i_ax = base[0], base[1]
                if mode == "int8":
                    lp[k] = _qmm.QuantW8(q=P(o_ax, i_ax), s=P(o_ax))
                else:
                    lp[k] = _qmm.QuantW4(q=P(o_ax, i_ax), s=P(o_ax, i_ax))
        return {"embed": P(), "pos": P(),
                "layers": [dict(lp) for _ in range(self.cfg.num_layers)]}

    def place_params(self, params):
        """device_put the param pytree onto the mesh per the plan (the
        one-time layout move at engine init).  Flatten-and-zip rather
        than a shape-specific walk so QuantW8/QuantW4 leaves place
        through the same code path as raw arrays."""
        from jax.sharding import NamedSharding, PartitionSpec

        specs = self.param_specs()
        leaves, treedef = jax.tree.flatten(params)
        spec_leaves = jax.tree.flatten(
            specs, is_leaf=lambda x: isinstance(x, PartitionSpec))[0]
        placed = [jax.device_put(a, NamedSharding(self.mesh, s))
                  for a, s in zip(leaves, spec_leaves)]
        return jax.tree.unflatten(treedef, placed)

    def place_kv(self, pages):
        """(Re)pin a rows-form pool to the KV-head sharding — used at
        init and after host-side page mutations (install/import) that
        may have produced a differently-placed result."""
        return jax.device_put(pages, self.kv_sharding)

    def wrap(self, fn, n_rest, n_out_rest):
        """jit(shard_map(fn)) with the plan's layout: params + KV pools
        (rows form) sharded, every other operand/result replicated;
        pools donated so the cache stays in place across steps."""
        from jax.sharding import PartitionSpec as P
        rep = P()
        kv = self.kv_in_spec
        in_specs = (self.param_specs(), kv, kv) + (rep,) * n_rest
        out_specs = (kv, kv) + (rep,) * n_out_rest
        smapped = jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                                out_specs=out_specs, check_vma=False)
        return jax.jit(smapped, donate_argnums=(1, 2))


def tp_plan(cfg, sharding, quant=None, kv_int8=False):
    """Resolve (cfg, ShardingConfig) to a :class:`TPPlan`, or None when
    no tensor parallelism was asked for (no config, tp absent or 1).

    A config that asks for tp > 1 and cannot have it is an error, never
    a single-chip engine under a TP label: a mesh that does not fit this
    host (``axis_size`` raises), geometry tp does not divide (the GQA
    ``kv_heads % tp`` constraint and friends), or rules that do not
    resolve to the Megatron column/row layout all raise ValueError."""
    if sharding is None:
        return None
    tp = int(sharding.axis_size("tp"))
    if tp <= 1:
        return None
    bad = [s for s, n in (("num_heads=%d" % cfg.num_heads, cfg.num_heads),
                          ("num_kv_heads=%d" % cfg.num_kv_heads,
                           cfg.num_kv_heads),
                          ("hidden_size=%d" % cfg.hidden_size,
                           cfg.hidden_size)) if n % tp != 0]
    if bad:
        raise ValueError(
            "decoder: tp=%d does not divide %s (pick tp dividing the "
            "head/FFN geometry)" % (tp, ", ".join(bad)))
    plan = TPPlan(sharding, cfg, quant=quant, kv_int8=kv_int8)
    shapes = plan._layer_shapes()
    want = {"wq": ("tp",), "wk": ("tp",), "wv": ("tp",), "bq": ("tp",),
            "w1": ("tp",), "b1": ("tp",),
            "wo": (None, "tp"), "w2": (None, "tp")}
    off = [k for k, w in want.items()
           if tuple(plan.leaf_spec(k, shapes[k])) != w]
    if off:
        raise ValueError(
            "decoder: sharding rules do not resolve the Megatron "
            "column/row layout for %s (use ShardingConfig."
            "for_transformer)" % ", ".join(sorted(off)))
    return plan


# ---------------------------------------------------------------------------
# full-sequence causal forward (training / scoring / parity oracle)
# ---------------------------------------------------------------------------
def full_forward(params, cfg, tokens):
    """tokens: (B, L) int32 -> logits (B, L, vocab) float32.

    Whole-sequence causal attention through the flash kernel; the greedy
    parity oracle for the incremental paged decode path."""
    if is_hybrid(cfg):
        return _hybrid.full_forward(params, cfg, tokens)
    B, L = tokens.shape
    g = cfg.num_heads // cfg.num_kv_heads
    x = params["embed"][tokens] + params["pos"][:L]
    for lp in params["layers"]:
        q, k, v = _qkv(x, lp, cfg)                      # (B, L, H/KVH, D)
        q4 = jnp.transpose(q, (0, 2, 1, 3))             # (B, H, L, D)
        k4 = jnp.repeat(jnp.transpose(k, (0, 2, 1, 3)), g, axis=1)
        v4 = jnp.repeat(jnp.transpose(v, (0, 2, 1, 3)), g, axis=1)
        att = _attention.flash_attention(q4, k4, v4, causal=True)
        merged = jnp.transpose(att, (0, 2, 1, 3)).reshape(B, L, cfg.units)
        x = _layer_tail(x, merged, lp)
    return jnp.dot(x.astype(jnp.float32),
                   params["embed"].astype(jnp.float32).T)


# ---------------------------------------------------------------------------
# incremental decode over the paged KV cache
# ---------------------------------------------------------------------------
def recurrent_name(cfg):
    """What a refusal calls the layers that keep a paged state."""
    return ("delta-rule layers" if _hybrid.DELTA in cfg.layer_kinds
            else "state-space layers")


def _refuse_hybrid(cfg, what):
    """The programs of a model with recurrent layers (state-space or
    delta-rule) exist for one chip, unquantized: anything else is refused
    by name, never served by a program that does something else."""
    if is_hybrid(cfg):
        raise ValueError(
            "decoder: %s is not supported for a model with %s"
            % (what, recurrent_name(cfg)))


def hybrid_program(cfg, sharding, quant, kv_dtype):
    """True where the programs to build are :mod:`.hybrid`'s; what those
    programs cannot do is refused here (the engine asks before it builds
    anything, the factories again for whoever calls them directly)."""
    if not is_hybrid(cfg):
        return False
    if sharding is not None and int(sharding.axis_size("tp")) > 1:
        _refuse_hybrid(cfg, "a tp sharding")
    if quant is not None:
        _refuse_hybrid(cfg, "weight quantisation (%r)" % (quant,))
    if kv_dtype == "int8":
        _refuse_hybrid(cfg, "an int8 KV pool")
    return True


def make_decode_step(cfg, page_size, sharding=None, quant=None,
                     kv_dtype=None):
    """Build (or fetch) the jitted batched decode step for
    (cfg, page_size) — cached in the bounded per-geometry LRU.

    With ``sharding`` carrying an active tp axis the step runs per-shard
    under ``shard_map`` (params column/row-split, KV pages split along
    KV heads); otherwise the 1-chip program.  The sharding token is part
    of the cache key, so toggling the config never serves a stale
    program; the quant token (None | ("int8",) | ("int4", group)) and
    the KV dtype key the same way — a quantized engine never shares a
    program with an fp one even at identical geometry.

    fn(params, k_pages, v_pages, tokens, positions, page_tables, active)
      k_pages/v_pages: the pools (:func:`fresh_pool`), rows form
                       (layers, total_pages, page_size, KVH * head_dim),
                       donated and updated in place; with
                       kv_dtype="int8" a QPages (codes, scales) pytree.
                       For a model with state-space layers a
                       :class:`~.hybrid.HybridPool` each: a lane at
                       position p reads the state entry of the page
                       that holds p - 1 (zeros at p = 0) and writes the
                       entry of the page that holds p; an inactive lane
                       writes the scratch page's.  ``kv_dtype`` left out
                       is the model's own (``cfg.kv_dtype``), else
                       float32
      tokens:     (B,) int32 — this step's input token per slot
      positions:  (B,) int32 — cache index the token lands at
      page_tables:(B, pages_per_seq) int32
      active:     (B,) bool — inactive slots write the scratch page and
                  read garbage; the engine discards their outputs
    -> (k_pages, v_pages, next_tokens (B,) int32, logits (B, vocab) f32)
    """
    kv_dtype = _kv_dtype(cfg, kv_dtype)
    key = ("decode", cfg, int(page_size), _shard_token(sharding),
           quant, kv_dtype)
    if hybrid_program(cfg, sharding, quant, kv_dtype):
        return _fn_cache.get(key, lambda: _hybrid.build_decode_step(
            cfg, int(page_size)))
    return _fn_cache.get(key, lambda: _build_decode_step(
        cfg, int(page_size), tp_plan(cfg, sharding, quant=quant,
                                     kv_int8=(kv_dtype == "int8"))))


def _build_decode_step(cfg, page_size, plan=None):
    S = int(page_size)
    # per-shard geometry: local head counts, FULL activation width (the
    # all-reduce at the layer tail re-replicates x before the next qkv)
    qcfg = plan.local_cfg if plan is not None else cfg
    Cl = qcfg.num_heads * cfg.head_dim
    axis = plan.axis if plan is not None else None

    def step(params, k_pages, v_pages, tokens, positions, page_tables,
             active):
        B = tokens.shape[0]
        x = (params["embed"][tokens]
             + params["pos"][jnp.clip(positions, 0, cfg.max_length - 1)])
        page_of = jnp.take_along_axis(
            page_tables, (positions // S)[:, None], axis=1)[:, 0]
        # inactive slots scatter to page 0 — the allocator's reserved
        # scratch page (serving/kvcache.py) — and read length 0
        wp = jnp.where(active, page_of, 0)
        ws = jnp.where(active, positions % S, 0)
        lengths = jnp.where(active, positions + 1, 0).astype(jnp.int32)
        for li, lp in enumerate(params["layers"]):
            q, k, v = _qkv(x, lp, qcfg)                 # (B, H/KVH, D)
            # a singleton token axis: each slot is its own sequence,
            # so the scale-latch window is one token wide
            k_pages = _kv_append(k_pages, li, wp[:, None], ws[:, None],
                                 k[:, None])
            v_pages = _kv_append(v_pages, li, wp[:, None], ws[:, None],
                                 v[:, None])
            att = _decode_attention(q, k_pages, v_pages, li, lengths,
                                    page_tables, qcfg.num_kv_heads)
            x = _layer_tail(x, att.reshape(B, Cl), lp, axis=axis)
        logits = jnp.dot(x.astype(jnp.float32),
                         params["embed"].astype(jnp.float32).T)
        return (k_pages, v_pages,
                jnp.argmax(logits, axis=-1).astype(jnp.int32), logits)

    return _pool_program(step, plan, n_rest=4, n_out_rest=2)


def _pool_program(fn, plan, n_rest, n_out_rest):
    """Step function ``fn(params, k_pool, v_pool, *rest) -> (k_pool,
    v_pool, *out)`` jitted with the pools donated (input aliased to
    output, rows form in and out), per shard under a TP plan."""
    if plan is None:
        return jax.jit(fn, donate_argnums=(1, 2))
    return plan.wrap(fn, n_rest, n_out_rest)


def make_token_combine(slots):
    """Build (or fetch) the async engine's lane-merge program: the next
    step's input tokens without a host read.

    Continuing lanes chain on the in-flight step's on-device
    ``next_tokens`` (``carry`` true); lanes that joined the batch since
    (fresh prefills) feed their host-staged pending token.  Keeping the
    merge on-device is what lets the launch half of a pipelined step go
    out before anyone has forced the previous step's result — the decode
    program itself is untouched, so the static launch census is too.

    fn(chained (B,) int32, staged (B,) int32, carry (B,) bool)
      -> (B,) int32
    """
    key = ("combine", int(slots))
    return _fn_cache.get(key, lambda: jax.jit(
        lambda chained, staged, carry: jnp.where(carry, chained, staged)))


def _kv_structs(cfg, page_size, total_pages, kv_dtype="float32"):
    """ShapeDtypeStruct of one pool (:func:`fresh_pool`'s, unallocated)."""
    return jax.eval_shape(lambda: fresh_pool(cfg, total_pages, page_size,
                                             kv_dtype))


def _decode_step_structs(params, cfg, page_size, slots, pages_per_seq,
                         total_pages, kv_dtype="float32"):
    """ShapeDtypeStruct argument tuple of one decode step (census
    tracing/lowering without touching real buffers).  Quantized param
    leaves (QuantW8/QuantW4 pytrees) map leaf-wise like raw arrays."""
    kp = _kv_structs(cfg, page_size, total_pages, kv_dtype)
    return (jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params),
            kp, kp,
            jax.ShapeDtypeStruct((slots,), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.int32),
            jax.ShapeDtypeStruct((slots, pages_per_seq), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.bool_))


def decode_launch_stats(params, cfg, page_size, slots, pages_per_seq,
                        total_pages, sharding=None, quant=None,
                        kv_dtype="float32"):
    """Static launch census of one decode step (the dispatch-count
    audit): traces the step program and counts launch-class primitives
    with ``fused_cell.count_launches`` — deterministic and
    load-independent, safe to gate CI on.  With ``sharding`` the census
    covers the PER-SHARD program (collectives are not launch-class; see
    :func:`decode_collective_stats`).

    Returns {launches_per_step, pallas_per_step}.
    """
    S = int(page_size)
    fn = make_decode_step(cfg, S, sharding=sharding, quant=quant,
                          kv_dtype=kv_dtype)
    args = _decode_step_structs(params, cfg, S, slots, pages_per_seq,
                                total_pages, kv_dtype=kv_dtype)
    jaxpr = jax.make_jaxpr(fn)(*args)
    return {"launches_per_step": int(_fused.count_launches(jaxpr)),
            "pallas_per_step": int(_fused.count_pallas_calls(jaxpr))}


def decode_collective_stats(params, cfg, page_size, slots, pages_per_seq,
                            total_pages, sharding, quant=None,
                            kv_dtype="float32"):
    """Static COLLECTIVE census of one sharded decode step: lowers the
    shard_map program through the partitioner and counts HLO collectives
    per class (``parallel.shardcfg.collective_census``).  Like the
    launch census this is a property of the program alone — the tier-1
    gate asserts all-reduce-only (2 row-parallel reduces per layer) with
    counts invariant to batch size.

    Returns {mesh, tp, collectives: {class: n, ..., total}}.
    """
    from ..parallel import shardcfg as _shardcfg
    plan = tp_plan(cfg, sharding)
    if plan is None:
        raise ValueError("decode_collective_stats needs a sharding with "
                         "an active tp axis that divides the geometry")
    S = int(page_size)
    fn = make_decode_step(cfg, S, sharding=sharding, quant=quant,
                          kv_dtype=kv_dtype)
    args = _decode_step_structs(params, cfg, S, slots, pages_per_seq,
                                total_pages, kv_dtype=kv_dtype)
    census = _shardcfg.collective_census(fn.lower(*args))
    return {"mesh": sharding.describe(), "tp": plan.tp,
            "collectives": census}


def make_prefill_chunk(cfg, page_size, chunk, sharding=None, quant=None,
                       kv_dtype=None):
    """Build (or fetch) the jitted single-sequence chunk prefill for
    (cfg, page_size, chunk) — cached in the bounded per-geometry LRU.

    fn(params, k_pages, v_pages, tokens, pos0, n_valid, page_row)
      tokens:  (chunk,) int32 — prompt slice, padded past n_valid
      pos0:    () int32 — absolute cache position of tokens[0]
      n_valid: () int32 — valid tokens in this chunk
      page_row:(pages_per_seq,) int32 — THIS sequence's page table
    -> (k_pages, v_pages, next_token () int32, last_logits (vocab,) f32)

    The chunk's KV is scattered into the sequence's pages first, then
    the chunk queries attend over the gathered pages (prefix + chunk)
    under a causal + validity mask — so arbitrarily long prompts cost a
    bounded slice of each engine step instead of stalling the decode
    batch (Sarathi-style chunked prefill).

    ``sharding`` with an active tp axis runs the chunk per-shard under
    ``shard_map`` (local heads, row-parallel all-reduce at the tail),
    bit-compatible with the sharded decode step's pages.

    For a model with state-space layers the pools are
    :class:`~.hybrid.HybridPool`: the chunk reads the state entry of the
    page that holds ``pos0 - 1`` (zeros at ``pos0 = 0``), scans, and
    writes an entry for every page it touches (the state after the page's
    last token, or after the chunk's last valid one); where a sequence's
    state lives follows from ``page_row`` alone.  ``kv_dtype`` left out is
    the model's own (``cfg.kv_dtype``), else float32.
    """
    kv_dtype = _kv_dtype(cfg, kv_dtype)
    key = ("prefill", cfg, int(page_size), int(chunk),
           _shard_token(sharding), quant, kv_dtype)
    if hybrid_program(cfg, sharding, quant, kv_dtype):
        return _fn_cache.get(key, lambda: _hybrid.build_prefill_chunk(
            cfg, int(page_size), int(chunk)))
    return _fn_cache.get(key, lambda: _build_prefill_chunk(
        cfg, int(page_size), int(chunk),
        tp_plan(cfg, sharding, quant=quant,
                kv_int8=(kv_dtype == "int8"))))


def _build_prefill_chunk(cfg, page_size, chunk, plan=None):
    S = int(page_size)
    P = int(chunk)
    qcfg = plan.local_cfg if plan is not None else cfg
    Cl = qcfg.num_heads * cfg.head_dim
    axis = plan.axis if plan is not None else None
    g = qcfg.num_heads // qcfg.num_kv_heads
    scale = 1.0 / (cfg.head_dim ** 0.5)

    def prefill(params, k_pages, v_pages, tokens, pos0, n_valid, page_row):
        idx = pos0 + jnp.arange(P, dtype=jnp.int32)
        valid = jnp.arange(P) < n_valid
        x = (params["embed"][tokens]
             + params["pos"][jnp.clip(idx, 0, cfg.max_length - 1)])
        wp = jnp.where(valid, page_row[idx // S], 0)
        ws = jnp.where(valid, idx % S, 0)
        chunk = (page_row, pos0, n_valid)
        kvh = qcfg.num_kv_heads
        for li, lp in enumerate(params["layers"]):
            q, k, v = _qkv(x, lp, qcfg)                 # (P, H/KVH, D)
            k_pages = _kv_append(k_pages, li, wp, ws, k, chunk)
            v_pages = _kv_append(v_pages, li, wp, ws, v, chunk)
            # gather THIS sequence's pages (prefix + the chunk just
            # written) back to a contiguous (KVH, C, D) view
            kc = _gather_kv(k_pages, li, page_row[None], kvh)[0]
            vc = _gather_kv(v_pages, li, page_row[None], kvh)[0]
            kr = jnp.repeat(kc, g, axis=0)              # (H, C, D)
            vr = jnp.repeat(vc, g, axis=0)
            qf = q.astype(jnp.float32).swapaxes(0, 1) * scale  # (H, P, D)
            logits = jnp.einsum("hpd,hcd->hpc", qf,
                                kr.astype(jnp.float32))
            causal = (jnp.arange(kr.shape[1])[None, :]
                      <= idx[:, None])                  # key <= query pos
            logits = jnp.where(causal[None], logits, -jnp.inf)
            p = jax.nn.softmax(logits, axis=-1)
            p = jnp.where(jnp.isnan(p), 0.0, p)
            att = jnp.einsum("hpc,hcd->hpd", p, vr.astype(jnp.float32))
            merged = att.swapaxes(0, 1).reshape(P, Cl).astype(x.dtype)
            x = _layer_tail(x, merged, lp, axis=axis)
        last = x[jnp.clip(n_valid - 1, 0, P - 1)]
        last_logits = jnp.dot(last.astype(jnp.float32),
                              params["embed"].astype(jnp.float32).T)
        return (k_pages, v_pages,
                jnp.argmax(last_logits).astype(jnp.int32), last_logits)

    return _pool_program(prefill, plan, n_rest=4, n_out_rest=2)


def make_verify_step(cfg, page_size, width, sharding=None, quant=None,
                     kv_dtype="float32"):
    """Build (or fetch) the jitted wide VERIFY step for speculative
    decoding — cached per (cfg, page_size, width) in the same bounded
    per-geometry LRU as the decode/prefill programs.

    One launch scores ``width`` candidate tokens per slot against the
    target model (the slot's pending token plus up to ``width - 1``
    drafted ones): their KV is scattered into the slot's pages exactly
    like a prefill chunk, the queries attend causally over the slot's
    own gathered pages, and the argmax at EVERY position comes back —
    position ``i``'s output is the greedy successor of the prefix ending
    at token ``i``, which is what longest-prefix acceptance compares the
    draft against.  Rejected positions leave garbage KV behind; the
    engine rolls those pages back (``PageAllocator.trim``) and masked
    reads never see them.

    fn(params, k_pages, v_pages, tokens, positions, n_valid,
       page_tables, active)
      tokens:     (B, width) int32 — [pending, draft...] per slot,
                  zero-padded past n_valid
      positions:  (B,) int32 — cache index tokens[:, 0] lands at
      n_valid:    (B,) int32 — real tokens this step per slot (1 =
                  plain decode riding the wide program)
      page_tables:(B, pages_per_seq) int32
      active:     (B,) bool — inactive slots write the scratch page
    -> (k_pages, v_pages, out_tokens (B, width) int32)

    ``sharding`` with an active tp axis runs verification per-shard
    under ``shard_map`` — speculative decoding rides the TP engine
    unmodified (the acceptance logic only sees replicated out_tokens).
    """
    _refuse_hybrid(cfg, "the verify program of speculative decoding (a "
                   "rejected draft would need the state rolled back inside "
                   "a page)")
    key = ("verify", cfg, int(page_size), int(width),
           _shard_token(sharding), quant, str(kv_dtype))
    return _fn_cache.get(key, lambda: _build_verify_step(
        cfg, int(page_size), int(width),
        tp_plan(cfg, sharding, quant=quant,
                kv_int8=(kv_dtype == "int8"))))


def _build_verify_step(cfg, page_size, width, plan=None):
    S = int(page_size)
    W = int(width)
    qcfg = plan.local_cfg if plan is not None else cfg
    Cl = qcfg.num_heads * cfg.head_dim
    axis = plan.axis if plan is not None else None
    g = qcfg.num_heads // qcfg.num_kv_heads
    scale = 1.0 / (cfg.head_dim ** 0.5)

    def verify(params, k_pages, v_pages, tokens, positions, n_valid,
               page_tables, active):
        B = tokens.shape[0]
        pps = page_tables.shape[1]
        idx = positions[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]
        valid = ((jnp.arange(W)[None, :] < n_valid[:, None])
                 & active[:, None])
        x = (params["embed"][tokens]
             + params["pos"][jnp.clip(idx, 0, cfg.max_length - 1)])
        page_of = jnp.take_along_axis(
            page_tables, jnp.clip(idx // S, 0, pps - 1), axis=1)
        # invalid/padded positions scatter to the reserved scratch page
        wp = jnp.where(valid, page_of, 0)
        ws = jnp.where(valid, idx % S, 0)
        for li, lp in enumerate(params["layers"]):
            q, k, v = _qkv(x, lp, qcfg)                 # (B, W, H/KVH, D)
            k_pages = _kv_append(k_pages, li, wp, ws, k)
            v_pages = _kv_append(v_pages, li, wp, ws, v)
            kc = _gather_kv(k_pages, li, page_tables, qcfg.num_kv_heads)
            vc = _gather_kv(v_pages, li, page_tables, qcfg.num_kv_heads)
            kr = jnp.repeat(kc, g, axis=1)              # (B, H, C, D)
            vr = jnp.repeat(vc, g, axis=1)
            qf = q.astype(jnp.float32).transpose(0, 2, 1, 3) * scale
            logits = jnp.einsum("bhwd,bhcd->bhwc", qf,
                                kr.astype(jnp.float32))
            causal = (jnp.arange(kr.shape[2])[None, None, :]
                      <= idx[:, :, None])               # key <= query pos
            logits = jnp.where(causal[:, None], logits, -jnp.inf)
            p = jax.nn.softmax(logits, axis=-1)
            p = jnp.where(jnp.isnan(p), 0.0, p)
            att = jnp.einsum("bhwc,bhcd->bhwd", p, vr.astype(jnp.float32))
            merged = att.transpose(0, 2, 1, 3).reshape(
                B, W, Cl).astype(x.dtype)
            x = _layer_tail(x, merged, lp, axis=axis)
        logits = jnp.dot(x.astype(jnp.float32),
                         params["embed"].astype(jnp.float32).T)
        return (k_pages, v_pages,
                jnp.argmax(logits, axis=-1).astype(jnp.int32))

    return _pool_program(verify, plan, n_rest=5, n_out_rest=1)


def verify_launch_stats(params, cfg, page_size, width, slots,
                        pages_per_seq, total_pages, quant=None,
                        kv_dtype="float32"):
    """Static launch census of one wide verify step (the speculative
    analog of :func:`decode_launch_stats`): traced, deterministic, and
    independent of acceptance — the launch count is a property of
    (cfg, page_size, width) alone, never of which drafts land.

    Returns {width, launches_per_step, pallas_per_step,
    launches_per_emitted_token} where the per-emitted figure assumes
    full acceptance (``width`` tokens emitted by the one launch)."""
    S = int(page_size)
    W = int(width)
    fn = make_verify_step(cfg, S, W, quant=quant, kv_dtype=kv_dtype)
    kp = _kv_structs(cfg, S, total_pages, kv_dtype)
    args = (jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params),
            kp, kp,
            jax.ShapeDtypeStruct((slots, W), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.int32),
            jax.ShapeDtypeStruct((slots, pages_per_seq), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.bool_))
    jaxpr = jax.make_jaxpr(fn)(*args)
    launches = _fused.count_launches(jaxpr)
    return {"width": W,
            "launches_per_step": int(launches),
            "pallas_per_step": int(_fused.count_pallas_calls(jaxpr)),
            "launches_per_emitted_token": launches / float(W)}


# ---------------------------------------------------------------------------
# gluon parameter container
# ---------------------------------------------------------------------------
class DecoderLayer(HybridBlock):
    """Parameter container mirroring TransformerLayer's shape (post-LN,
    reused PositionwiseFFN); compute lives in the pure functions above."""

    def __init__(self, units, hidden_size, num_heads, num_kv_heads):
        super().__init__()
        head_dim = units // num_heads
        kv_units = num_kv_heads * head_dim
        self.wq = nn.Dense(units, flatten=False, in_units=units)
        self.wk = nn.Dense(kv_units, flatten=False, in_units=units)
        self.wv = nn.Dense(kv_units, flatten=False, in_units=units)
        self.wo = nn.Dense(units, flatten=False, in_units=units)
        self.ffn = PositionwiseFFN(units, hidden_size, dropout=0.0)
        self.ln1 = nn.LayerNorm(in_channels=units)
        self.ln2 = nn.LayerNorm(in_channels=units)


class CausalLM(HybridBlock):
    """GPT-style causal decoder LM (tied input/output embedding).

    ``forward(tokens)`` is the full-sequence path (scoring, the serving
    registry's predict route); incremental generation runs through
    ``serving.DecodeEngine``, which drives the jitted prefill/decode
    programs against this block's parameters."""

    def __init__(self, vocab_size=512, num_layers=2, units=128,
                 hidden_size=256, num_heads=4, num_kv_heads=None,
                 max_length=512, eos_id=None):
        super().__init__()
        num_kv_heads = num_kv_heads or num_heads
        assert units % num_heads == 0
        assert num_heads % num_kv_heads == 0
        self._cfg = DecoderConfig(
            vocab_size=int(vocab_size), num_layers=int(num_layers),
            units=int(units), hidden_size=int(hidden_size),
            num_heads=int(num_heads), num_kv_heads=int(num_kv_heads),
            head_dim=units // num_heads, max_length=int(max_length))
        self.eos_id = eos_id
        self.word_embed = nn.Embedding(vocab_size, units)
        self.position_embed = Parameter("position_embed",
                                        shape=(max_length, units))
        self.layers = nn.HybridSequential()
        for _ in range(num_layers):
            self.layers.add(DecoderLayer(units, hidden_size, num_heads,
                                         num_kv_heads))
        self._jax_params = None

    @property
    def config(self):
        return self._cfg

    def jax_params(self):
        """Raw jax.Array pytree of the weights (cached: serving treats
        weights as frozen — hot swap replaces the model object)."""
        if self._jax_params is not None:
            return self._jax_params

        def raw(p):
            return p.data()._data

        layers = []
        for layer in self.layers:
            layers.append({
                "wq": raw(layer.wq.weight), "bq": raw(layer.wq.bias),
                "wk": raw(layer.wk.weight), "bk": raw(layer.wk.bias),
                "wv": raw(layer.wv.weight), "bv": raw(layer.wv.bias),
                "wo": raw(layer.wo.weight), "bo": raw(layer.wo.bias),
                "w1": raw(layer.ffn.ffn1.weight),
                "b1": raw(layer.ffn.ffn1.bias),
                "w2": raw(layer.ffn.ffn2.weight),
                "b2": raw(layer.ffn.ffn2.bias),
                "ln1g": raw(layer.ln1.gamma), "ln1b": raw(layer.ln1.beta),
                "ln2g": raw(layer.ln2.gamma), "ln2b": raw(layer.ln2.beta),
            })
        self._jax_params = {
            "embed": raw(self.word_embed.weight),
            "pos": raw(self.position_embed),
            "layers": layers,
        }
        return self._jax_params

    def forward(self, tokens):
        raw = tokens._data if hasattr(tokens, "_data") else jnp.asarray(
            tokens)
        logits = full_forward(self.jax_params(), self._cfg,
                              raw.astype(jnp.int32))
        from .. import np as mxnp
        return mxnp.array(logits)


# ---------------------------------------------------------------------------
# builders (tests, bench, replica model specs)
# ---------------------------------------------------------------------------
def decoder_tiny(vocab_size=128, **kw):
    kw.setdefault("num_layers", 2)
    kw.setdefault("units", 64)
    kw.setdefault("hidden_size", 128)
    kw.setdefault("num_heads", 4)
    kw.setdefault("num_kv_heads", 2)
    kw.setdefault("max_length", 128)
    return CausalLM(vocab_size, **kw)


def causal_lm(seed=0, **kw):
    """Initialized, deterministic CausalLM of any size (``kw`` are
    :class:`CausalLM`'s) — the importable builder a replica spec names
    to serve a full-width model with random weights
    (``mxnet_tpu.models.decoder:causal_lm``; ``chip_smoke.py``)."""
    import mxnet_tpu as mx
    mx.random.seed(int(seed))
    net = CausalLM(**kw)
    net.initialize(mx.init.Xavier())
    return net


def decoder_tiny_lm(seed=0, vocab_size=128, **kw):
    """Initialized, deterministic tiny LM — the importable builder the
    replica spec / chaos drills serve
    (``mxnet_tpu.models.decoder:decoder_tiny_lm``)."""
    import mxnet_tpu as mx
    mx.random.seed(int(seed))
    net = decoder_tiny(vocab_size, **kw)
    net.initialize(mx.init.Xavier())
    return net


def decoder_draft(target, seed=0, num_layers=1, units=32, hidden_size=64,
                  num_heads=2, num_kv_heads=1):
    """Reduced-depth/width draft LM for speculative decoding: shares the
    target's tokenizer (vocab) and context length but runs a fraction of
    its compute per token.  ``target`` is the CausalLM (or its
    DecoderConfig) the drafts will be verified against — a vocab
    mismatch would make the draft tokens meaningless, so geometry is
    copied rather than trusted to the caller."""
    import mxnet_tpu as mx
    cfg = target.config if hasattr(target, "config") else target
    mx.random.seed(int(seed))
    net = CausalLM(cfg.vocab_size, num_layers=int(num_layers),
                   units=int(units), hidden_size=int(hidden_size),
                   num_heads=int(num_heads),
                   num_kv_heads=int(num_kv_heads),
                   max_length=cfg.max_length,
                   eos_id=getattr(target, "eos_id", None))
    net.initialize(mx.init.Xavier())
    return net


def hybrid_lm(seed=0, **kw):
    """Initialized, deterministic :class:`~.hybrid.HybridLM` (state-space
    layers with attention layers among them) of any size, the weights
    drawn on the device in the model's dtype: :func:`.hybrid.hybrid_lm`
    under the name a replica spec or a benchmark configuration gives
    (``mxnet_tpu.models.decoder:hybrid_lm``)."""
    return _hybrid.hybrid_lm(seed, **kw)


def routed_delta_lm(seed=0, *, vocab_size, num_layers, units, num_heads,
                    num_kv_heads, head_dim, attention_layers, linear_attn,
                    experts_held, expert_shares, experts_per_token,
                    expert_hidden, shared_experts=1, expert_share=0,
                    hidden_size=0, attn_gate=True, neg_eigval=True,
                    full_proj=False, rope=False, dense_layers=0,
                    norm_topk=True, routed_scale=1.0, tied_head=False,
                    rms_eps=1e-5, max_length=1024, kv_dtype=None,
                    dtype="bfloat16"):
    """A :class:`~.hybrid.HybridLM` of the ``solar_open2`` model type, as
    **one chip of an expert-parallel group** holds it: gated delta-rule
    layers (``linear_attn``: the published ``linear_attn_config``) with
    gated position-free attention at ``attention_layers``, every layer's
    feed-forward part routed over ``experts_held * expert_shares`` experts
    of which this model holds the ``experts_held`` of share
    ``expert_share``, beside the shared expert.  The arguments are the
    published keys under this repo's names (a benchmark configuration's
    ``builder_kwargs`` maps them); what the block has no code for is
    refused, not ignored."""
    for what, asked in (("rotary positions (use_rope)", rope),
                        ("leading dense layers (first_k_dense_replace)",
                         dense_layers),
                        ("kda_use_full_proj", full_proj),
                        ("kda_allow_neg_eigval = false", not neg_eigval)):
        if asked:
            raise ValueError("decoder.routed_delta_lm: %s is not supported"
                             % what)
    return _hybrid.hybrid_lm(
        seed, vocab_size=vocab_size, num_layers=num_layers, units=units,
        hidden_size=hidden_size, num_heads=num_heads,
        num_kv_heads=num_kv_heads, head_dim=head_dim,
        attention_layers=[i for i in attention_layers if i < num_layers],
        recurrent=_hybrid.DELTA, attn_gate=attn_gate, tied_head=tied_head,
        delta_heads=linear_attn["num_heads"],
        delta_head_dim=linear_attn["head_dim"],
        delta_rank=linear_attn["head_dim"],
        d_conv=linear_attn["short_conv_kernel_size"],
        n_experts=experts_held * expert_shares, experts_held=experts_held,
        expert_share=expert_share, experts_per_token=experts_per_token,
        expert_hidden=expert_hidden,
        shared_hidden=expert_hidden * shared_experts, norm_topk=norm_topk,
        routed_scale=routed_scale, rms_eps=rms_eps, max_length=max_length,
        kv_dtype=kv_dtype, dtype=dtype)


# at the end: hybrid.py builds on the helpers above
from . import hybrid as _hybrid  # noqa: E402
