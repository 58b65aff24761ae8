"""A hybrid decoder: selective state-space layers with a few attention layers
among them (the Jamba family's block), on the step programs of
:mod:`.decoder`.

The block is pre-norm with RMSNorm, no biases and **no positions of any
kind**; every layer is ``h = x + mixer(rms(x));  x' = h + mlp(rms(h))`` with
the SiLU-gated ``mlp(u) = W_down (silu(W_gate u) * (W_up u))``.  The mixer
of a layer is named by ``HybridConfig.layer_kinds``:

- ``"attention"``: causal softmax attention, ``num_heads`` query heads on
  ``num_kv_heads`` KV heads, no rotary embedding.  Its keys and values live
  in the paged pool as token rows, like the classic block's.
- ``"state_space"``: ``[a, z] = W_in u``; a causal depthwise convolution
  ``c_t = silu(b + sum_j w[:, j] a_{t-(K-1)+j})``; ``[d, B, C] = W_x c_t``,
  each RMS-normalised; ``delta = softplus(W_dt d + b_dt)``;
  ``h_t = exp(delta A) h_{t-1} + (delta c_t) B``, ``A = -exp(A_log)``;
  ``y_t = h_t C + D c_t``; ``out = W_out (y_t silu(z))``.  What it keeps
  between tokens is ``h`` (d_inner x d_state) and the last ``K - 1``
  convolution inputs: the **state entry**, float32.

**The state is paged** (:class:`HybridPool`).  Every page of a sequence has
one state entry per state-space layer: the state after the last token
written into that page.  A step at position ``p`` reads the entry of the
page that holds ``p - 1`` (zeros at ``p = 0``, so a reused page or slot
starts clean) and writes the entry of the page that holds ``p``; a prefill
chunk reads the entry before ``pos0``, scans, and writes an entry for every
page it touches.  A page is then self-contained: preemption by recompute, a
freed page handed to another sequence and a prefix hit on whole pages need
no second bookkeeping, at the price of one entry a page instead of one a
sequence.

Precision: the residual stream, every norm, the convolution, ``delta``,
``exp``, the recurrence and the logits are float32.  A matrix product
multiplies the weights as stored (bfloat16 as published: exact on the MXU)
by the float32 activations split into two bfloat16 terms (16 bits of
mantissa), both in one product, and accumulates in float32 (:func:`_mm`);
attention's own two products and the two small projections that feed
``softplus`` and ``exp`` (``W_x``, ``W_dt``: 1 % of the operations) run in
float32 at the highest precision.  One bfloat16 pass over rounded
activations, which is what the classic block's programs make, reads 0.13 to
0.23 of a logit's standard deviation here against the float32 reference.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..gluon.block import HybridBlock
from ..gluon.parameter import Parameter
from ..ops import attention as _attention
from . import decoder as _dec

__all__ = ["HybridConfig", "HybridPool", "HybridLM", "hybrid_lm",
           "layer_runs", "fresh_pool", "state_entry_bytes",
           "full_forward", "build_decode_step", "build_prefill_chunk"]

ATTENTION, STATE_SPACE = "attention", "state_space"


class HybridConfig(NamedTuple):
    """Static (hashable) geometry of a hybrid decoder: the first eight
    fields are :class:`~.decoder.DecoderConfig`'s, so the engine reads
    them alike; ``layer_kinds`` names every layer's mixer and keys the
    program cache with the rest."""
    vocab_size: int
    num_layers: int
    units: int
    hidden_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    max_length: int
    layer_kinds: tuple
    d_inner: int
    d_state: int
    d_conv: int
    dt_rank: int
    rms_eps: float
    kv_dtype: str


class HybridPool(NamedTuple):
    """One of the two pools of a model with state-space layers
    (:func:`fresh_pool`).  ``rows``: the attention layers' keys (or values)
    as token rows ``(attention layers, P, S, KVH * D)`` in the cache dtype.
    ``ssm`` ``(state-space layers, P, d_state, d_inner / 2)`` and ``conv``
    ``(state-space layers, P, (d_conv - 1) * d_inner / 2)``, float32: one
    state entry a page, channels on the lanes (the convolution's few
    inputs flat in one row: as ``(.., d_conv - 1, d_inner / 2)`` the
    device's default layout moves the page axis inside, and every launch
    would relay the array out on its way in and out).  The recurrence is
    independent per channel, so the K pool carries channels
    ``[0, d_inner / 2)`` and the V pool the rest: both pools have the same
    structure and nothing in either stays unread."""
    rows: jax.Array
    ssm: jax.Array
    conv: jax.Array


def layer_runs(cfg):
    """[(kind, lo, hi)]: the runs of consecutive layers of one kind, in
    order.  The parameters hold each run stacked on a leading axis and the
    programs scan over it, so a run compiles once however long it is."""
    runs, lo = [], 0
    for i in range(1, cfg.num_layers + 1):
        if i == cfg.num_layers or cfg.layer_kinds[i] != cfg.layer_kinds[lo]:
            runs.append((cfg.layer_kinds[lo], lo, i))
            lo = i
    return runs


def _count(cfg, kind):
    return sum(k == kind for k in cfg.layer_kinds)


def state_entry_bytes(cfg):
    """Bytes of one page's state entries over all state-space layers and
    both pools."""
    return (_count(cfg, STATE_SPACE) * cfg.d_inner
            * (cfg.d_state + cfg.d_conv - 1) * 4)


def fresh_pool(cfg, total_pages, page_size, kv_dtype):
    """A zeroed :class:`HybridPool`, its token rows in ``kv_dtype``."""
    P, half = int(total_pages), cfg.d_inner // 2
    n_ssm = _count(cfg, STATE_SPACE)
    return HybridPool(
        rows=jnp.zeros((_count(cfg, ATTENTION), P, int(page_size),
                        cfg.num_kv_heads * cfg.head_dim),
                       jnp.dtype(kv_dtype)),
        ssm=jnp.zeros((n_ssm, P, cfg.d_state, half), jnp.float32),
        conv=jnp.zeros((n_ssm, P, (cfg.d_conv - 1) * half), jnp.float32))


# ---------------------------------------------------------------------------
# the layer's parts
# ---------------------------------------------------------------------------
def _mm(x, w):
    """``x @ w.T`` with float32 accumulation.  Float32 weights: a float32
    product.  Weights in bfloat16 are exact on the MXU, the float32
    activations are not: rounded to bfloat16 they lose 16 bits, and through
    28 pre-norm layers, whose residual stream nothing damps, that error
    reaches a fifth of a logit's standard deviation (PERF.md, PR 29).  So
    the activations go in as two bfloat16 terms, ``hi + lo`` (16 bits of
    mantissa), stacked into ONE product that reads the weights once and
    has twice the rows."""
    if w.dtype != jnp.bfloat16:
        return jnp.dot(x.astype(w.dtype), w.T,
                       preferred_element_type=jnp.float32)
    x = x.astype(jnp.float32)
    # reduce_precision, not a cast there and back: XLA may drop such a
    # pair of converts (xla_allow_excess_precision), and on the TPU does,
    # which leaves lo = 0 and one rounded pass at twice the price
    hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    both = jnp.dot(jnp.stack([hi, x - hi]).astype(jnp.bfloat16), w.T,
                   preferred_element_type=jnp.float32)
    return both[0] + both[1]


def _mm_hi(x, w):
    """``x @ w.T`` in float32 at the highest precision: the projections
    whose results go through ``softplus`` and ``exp``."""
    return jnp.dot(x, w.astype(jnp.float32).T,
                   precision=jax.lax.Precision.HIGHEST)


def _rms(x, g, eps):
    x = x.astype(jnp.float32)
    return (x * jax.lax.rsqrt(jnp.square(x).mean(-1, keepdims=True) + eps)
            * g.astype(jnp.float32))


def _mlp(x, lp, eps):
    u = _rms(x, lp["norm2"], eps)
    return x + _mm(jax.nn.silu(_mm(u, lp["w_gate"])) * _mm(u, lp["w_up"]),
                   lp["w_down"])


def _ssm_inputs(c, lp, cfg):
    """From the convolved input ``c`` (..., d_inner): ``delta`` (...,
    d_inner), ``B`` and ``C`` (..., d_state), and ``A`` transposed to
    (d_state, d_inner) so that the channels lie on the lanes."""
    R, N = cfg.dt_rank, cfg.d_state
    dbc = _mm_hi(c, lp["w_x"])
    d = _rms(dbc[..., :R], lp["norm_dt"], cfg.rms_eps)
    Bm = _rms(dbc[..., R:R + N], lp["norm_b"], cfg.rms_eps)
    Cm = _rms(dbc[..., R + N:], lp["norm_c"], cfg.rms_eps)
    delta = jax.nn.softplus(_mm_hi(d, lp["w_dt"])
                            + lp["b_dt"].astype(jnp.float32))
    return delta, Bm, Cm, -jnp.exp(lp["a_log"].astype(jnp.float32)).T


def _conv(ext, lp, T):
    """The causal depthwise convolution over ``ext`` (T + K - 1, d_inner):
    the K - 1 inputs before the chunk, then the chunk's."""
    w = lp["conv_w"].astype(jnp.float32)
    return jax.nn.silu(lp["conv_b"].astype(jnp.float32) + sum(
        w[:, j] * ext[..., j:j + T, :] for j in range(w.shape[1])))


def _scan_block(T):
    """Tokens a block of the blocked scan holds: the largest power of two
    up to 32 that divides ``T``."""
    bs = 1
    while bs < 32 and T % (2 * bs) == 0:
        bs *= 2
    return bs


def selective_scan(delta, dc, Bm, Cm, A_T, h0, marks):
    """``h_t = exp(delta_t A) h_{t-1} + dc_t B_t`` over a chunk, from
    ``h0``; returns ``y_t = h_t C_t`` (T, C) and ``h`` at the chunk indices
    ``marks`` (K,) as (K, N, C).  delta, dc: (T, C); Bm, Cm: (T, N); A_T,
    h0: (N, C).  A token with ``delta = 0`` leaves the state as it is.

    Blocked: the chunk is cut into ``T / bs`` blocks.  The recurrence runs
    over the ``bs`` tokens of every block at once, first from zero (which
    gives each block's own contribution to its final state), then the
    blocks' final states are chained (``T / bs`` steps on one state), then
    the recurrence runs again from each block's true initial state, which
    yields ``y``.  72 steps of work on (T / bs, N, C) for a 256-token chunk
    instead of 256 on (N, C): on the v5e 0.40 ms a layer at T = 256, C =
    5120 against 0.91 for the token-by-token scan and 2.6 for
    ``jax.lax.associative_scan`` (PERF.md, PR 29)."""
    T, C = delta.shape
    bs = _scan_block(T)
    nb = T // bs
    xs = tuple(a.reshape(nb, bs, -1).swapaxes(0, 1)
               for a in (delta, dc, Bm, Cm))

    def update(h, dl, dcj, b):
        return (jnp.exp(dl[:, None, :] * A_T) * h
                + dcj[:, None, :] * b[:, :, None])

    own, _ = jax.lax.scan(
        lambda h, x: (update(h, *x[:3]), None),
        jnp.zeros((nb,) + A_T.shape, jnp.float32), xs)
    decay = jnp.exp(delta.reshape(nb, bs, C).sum(1)[:, None, :] * A_T)
    _, before = jax.lax.scan(lambda H, x: (x[0] * H + x[1], H), h0,
                             (decay, own))

    def token(carry, x):
        h, marked = carry
        j, dl, dcj, b, cm = x
        h = update(h, dl, dcj, b)
        marked = jnp.where((marks % bs == j)[:, None, None],
                           h[marks // bs], marked)
        return (h, marked), (h * cm[:, :, None]).sum(1)

    (_, marked), y = jax.lax.scan(
        token, (before, jnp.zeros(marks.shape + A_T.shape, jnp.float32)),
        (jnp.arange(bs),) + xs)
    return y.swapaxes(0, 1).reshape(T, C), marked


def _ssm_chunk(u, lp, cfg, h_in, conv_in, valid, marks):
    """The state-space mixer over one sequence's chunk ``u`` (T, units)
    from the state ``(h_in, conv_in)``; tokens beyond ``valid`` change no
    state.  Returns the mixer's output and the state entries after the
    chunk indices ``marks``: (K, N, d_inner) and (K, d_conv - 1, d_inner)."""
    T, di = u.shape[0], cfg.d_inner
    az = _mm(u, lp["w_in"])
    a, z = az[:, :di], az[:, di:]
    ext = jnp.concatenate([conv_in, a])
    c = _conv(ext, lp, T)
    delta, Bm, Cm, A_T = _ssm_inputs(c, lp, cfg)
    delta = jnp.where(valid[:, None], delta, 0.0)
    y, h_marks = selective_scan(delta, delta * c, Bm, Cm, A_T, h_in, marks)
    y = y + lp["d"].astype(jnp.float32) * c
    # the inputs a_{m-K+2} .. a_m lie at ext[m + 1 : m + K]
    conv_marks = jax.vmap(lambda m: jax.lax.dynamic_slice_in_dim(
        ext, m + 1, cfg.d_conv - 1))(marks)
    return _mm(y * jax.nn.silu(z), lp["w_out"]), h_marks, conv_marks


def _read_entry(kp, vp, li, page, fresh):
    """The state entry of ``page`` (any shape of page ids) in state-space
    layer ``li``, both halves joined on the channels; zeros where
    ``fresh``."""
    h = jnp.concatenate([kp.ssm[li, page], vp.ssm[li, page]], axis=-1)
    half = h.shape[-1] // 2
    conv = jnp.concatenate(
        [p.conv[li, page].reshape(h.shape[:-2] + (-1, half))
         for p in (kp, vp)], axis=-1)
    fresh = jnp.asarray(fresh)[..., None, None]
    return jnp.where(fresh, 0.0, h), jnp.where(fresh, 0.0, conv)


def _write_entry(kp, vp, li, pages, h, conv):
    """Entries ``h`` (K, N, d_inner), ``conv`` (K, d_conv - 1, d_inner)
    over the pages ``pages`` (K,) of layer ``li``, in place in donated
    pools.  Several writers of the scratch page may race: nobody reads it
    as anything."""
    half = h.shape[-1] // 2

    def put(pool, part):
        return pool._replace(
            ssm=pool.ssm.at[li, pages].set(h[..., part]),
            conv=pool.conv.at[li, pages].set(
                conv[..., part].reshape(pages.shape + (-1,))))
    return put(kp, slice(None, half)), put(vp, slice(half, None))


def _over_layers(params, cfg, x, kp, vp, attention, state_space):
    """The layers in order: a ``lax.scan`` over each run's stacked
    parameters, the residual stream and both pools carried.  ``attention``
    and ``state_space`` are ``(u, lp, kp, vp, li) -> (out, kp, vp)`` with
    ``li`` the layer's index among its own kind (its row in the pool)."""
    seen = {ATTENTION: 0, STATE_SPACE: 0}
    for (kind, lo, hi), run in zip(layer_runs(cfg), params["runs"]):
        mixer = attention if kind == ATTENTION else state_space

        def layer(carry, xs, mixer=mixer):
            x, kp, vp = carry
            lp, li = xs
            out, kp, vp = mixer(_rms(x, lp["norm1"], cfg.rms_eps), lp, kp,
                                vp, li)
            return (_mlp(x + out, lp, cfg.rms_eps), kp, vp), None

        if hi - lo == 1:        # a static layer index: the page-wise writes
            (x, kp, vp), _ = layer(
                (x, kp, vp), (jax.tree.map(lambda a: a[0], run), seen[kind]))
        else:
            (x, kp, vp), _ = jax.lax.scan(
                layer, (x, kp, vp),
                (run, seen[kind] + jnp.arange(hi - lo, dtype=jnp.int32)))
        seen[kind] += hi - lo
    return x, kp, vp


def _logits(x, params, cfg):
    return _mm(_rms(x, params["norm_f"], cfg.rms_eps), params["embed"])


def _attend(q, kc, vc, seen, cfg):
    """Softmax attention of the queries ``q`` (B, T, H, D) over gathered
    contexts ``kc``, ``vc`` (B, KVH, ctx, D), each query head on its
    group's KV head; ``seen`` (B, T, ctx) says which keys a query may read
    (a row that may read none gives zeros).  Both products in float32 at
    the highest precision: they are a thousandth of a step's operations.
    -> (B, T, H * D)"""
    B, T = q.shape[:2]
    hi = jax.lax.Precision.HIGHEST
    qf = (q.astype(jnp.float32) / (cfg.head_dim ** 0.5)).reshape(
        B, T, cfg.num_kv_heads, -1, cfg.head_dim)
    s = jnp.einsum("btkgd,bkcd->bkgtc", qf, kc.astype(jnp.float32),
                   precision=hi)
    p = jax.nn.softmax(jnp.where(seen[:, None, None], s, -jnp.inf), axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)
    out = jnp.einsum("bkgtc,bkcd->btkgd", p, vc.astype(jnp.float32),
                     precision=hi)
    return out.reshape(B, T, -1)


def _qkv(u, lp, cfg):
    lead = u.shape[:-1]
    return (_mm(u, lp["wq"]).reshape(lead + (cfg.num_heads, cfg.head_dim)),
            _mm(u, lp["wk"]).reshape(lead + (cfg.num_kv_heads, cfg.head_dim)),
            _mm(u, lp["wv"]).reshape(lead + (cfg.num_kv_heads, cfg.head_dim)))


# ---------------------------------------------------------------------------
# the whole sequence at once (scoring, the model's forward)
# ---------------------------------------------------------------------------
def full_forward(params, cfg, tokens):
    """tokens: (B, L) int32 -> logits (B, L, vocab) float32, no cache."""
    B, L = tokens.shape
    g = cfg.num_heads // cfg.num_kv_heads
    everything = jnp.ones(L, bool)
    last = jnp.full((1,), L - 1, jnp.int32)

    def attention(u, lp, kp, vp, li):
        q, k, v = (a.transpose(0, 2, 1, 3) for a in _qkv(u, lp, cfg))
        att = _attention.flash_attention(
            q, jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1),
            causal=True)
        return _mm(att.transpose(0, 2, 1, 3).reshape(B, L, -1),
                   lp["wo"]), kp, vp

    def state_space(u, lp, kp, vp, li):
        h0 = jnp.zeros((cfg.d_state, cfg.d_inner), jnp.float32)
        c0 = jnp.zeros((cfg.d_conv - 1, cfg.d_inner), jnp.float32)
        out = jax.vmap(lambda ub: _ssm_chunk(ub, lp, cfg, h0, c0, everything,
                                             last)[0])(u)
        return out, kp, vp

    x = params["embed"][tokens].astype(jnp.float32)
    x, _, _ = _over_layers(params, cfg, x, None, None, attention,
                           state_space)
    return _logits(x, params, cfg)


# ---------------------------------------------------------------------------
# the step programs (decoder.make_decode_step / make_prefill_chunk)
# ---------------------------------------------------------------------------
def build_decode_step(cfg, page_size):
    """``step(params, k_pool, v_pool, tokens, positions, page_tables,
    active)`` of :func:`~.decoder.make_decode_step` for a hybrid model."""
    S = int(page_size)

    def step(params, kp, vp, tokens, positions, page_tables, active):
        B = tokens.shape[0]
        page_of = jnp.take_along_axis(
            page_tables, (positions // S)[:, None], axis=1)[:, 0]
        before = jnp.take_along_axis(
            page_tables, (jnp.maximum(positions - 1, 0) // S)[:, None],
            axis=1)[:, 0]
        # inactive lanes write the scratch page (kvcache.SCRATCH_PAGE)
        wp = jnp.where(active, page_of, 0)
        ws = jnp.where(active, positions % S, 0)
        fresh = positions == 0
        lengths = jnp.where(active, positions + 1, 0).astype(jnp.int32)

        def attention(u, lp, kp, vp, li):
            q, k, v = _qkv(u, lp, cfg)                  # (B, H/KVH, D)
            kp = kp._replace(rows=_dec._kv_append(
                kp.rows, li, wp[:, None], ws[:, None], k[:, None]))
            vp = vp._replace(rows=_dec._kv_append(
                vp.rows, li, wp[:, None], ws[:, None], v[:, None]))
            kc = _dec._gather_kv(kp.rows, li, page_tables, cfg.num_kv_heads)
            vc = _dec._gather_kv(vp.rows, li, page_tables, cfg.num_kv_heads)
            seen = jnp.arange(kc.shape[2])[None, :] < lengths[:, None]
            att = _attend(q[:, None], kc, vc, seen[:, None], cfg)
            return _mm(att[:, 0], lp["wo"]), kp, vp

        def state_space(u, lp, kp, vp, li):
            di = cfg.d_inner
            az = _mm(u, lp["w_in"])
            a, z = az[:, :di], az[:, di:]
            h, conv = _read_entry(kp, vp, li, before, fresh)
            ext = jnp.concatenate([conv, a[:, None, :]], axis=1)
            c = _conv(ext, lp, 1)[:, 0]
            delta, Bm, Cm, A_T = _ssm_inputs(c, lp, cfg)
            h = (jnp.exp(delta[:, None, :] * A_T) * h
                 + (delta * c)[:, None, :] * Bm[:, :, None])
            y = (h * Cm[:, :, None]).sum(1) + lp["d"].astype(jnp.float32) * c
            kp, vp = _write_entry(kp, vp, li, wp, h, ext[:, 1:])
            return _mm(y * jax.nn.silu(z), lp["w_out"]), kp, vp

        x = params["embed"][tokens].astype(jnp.float32)
        x, kp, vp = _over_layers(params, cfg, x, kp, vp, attention,
                                 state_space)
        logits = _logits(x, params, cfg)
        return kp, vp, jnp.argmax(logits, axis=-1).astype(jnp.int32), logits

    return jax.jit(step, donate_argnums=(1, 2))


def build_prefill_chunk(cfg, page_size, chunk):
    """``prefill(params, k_pool, v_pool, tokens, pos0, n_valid, page_row)``
    of :func:`~.decoder.make_prefill_chunk` for a hybrid model."""
    S, T = int(page_size), int(chunk)
    n_pages = -(-T // S) + 1            # a chunk may start inside a page

    def prefill(params, kp, vp, tokens, pos0, n_valid, page_row):
        idx = pos0 + jnp.arange(T, dtype=jnp.int32)
        valid = jnp.arange(T) < n_valid
        wp = jnp.where(valid, page_row[idx // S], 0)
        ws = jnp.where(valid, idx % S, 0)
        span = (page_row, pos0, n_valid)
        # the pages the chunk touches, the chunk index of the last valid
        # token in each, and the page that holds the token before the chunk
        slot = pos0 // S + jnp.arange(n_pages, dtype=jnp.int32)
        first = slot * S - pos0
        live = jnp.maximum(first, 0) < n_valid
        marks = jnp.clip(jnp.minimum(first + S, n_valid) - 1, 0, T - 1)
        pid = jnp.where(live, page_row[jnp.clip(slot, 0,
                                                page_row.shape[0] - 1)], 0)
        before = page_row[jnp.maximum(pos0 - 1, 0) // S]

        def attention(u, lp, kp, vp, li):
            q, k, v = _qkv(u, lp, cfg)                  # (T, H/KVH, D)
            kp = kp._replace(rows=_dec._kv_append(kp.rows, li, wp, ws, k,
                                                  span))
            vp = vp._replace(rows=_dec._kv_append(vp.rows, li, wp, ws, v,
                                                  span))
            kc = _dec._gather_kv(kp.rows, li, page_row[None],
                                 cfg.num_kv_heads)      # (1, KVH, ctx, D)
            vc = _dec._gather_kv(vp.rows, li, page_row[None],
                                 cfg.num_kv_heads)
            seen = jnp.arange(kc.shape[2])[None, :] <= idx[:, None]
            att = _attend(q[None], kc, vc, seen[None], cfg)
            return _mm(att[0], lp["wo"]), kp, vp

        def state_space(u, lp, kp, vp, li):
            h_in, conv_in = _read_entry(kp, vp, li, before, pos0 == 0)
            out, h_marks, conv_marks = _ssm_chunk(u, lp, cfg, h_in, conv_in,
                                                  valid, marks)
            kp, vp = _write_entry(kp, vp, li, pid, h_marks, conv_marks)
            return out, kp, vp

        x = params["embed"][tokens].astype(jnp.float32)
        x, kp, vp = _over_layers(params, cfg, x, kp, vp, attention,
                                 state_space)
        last = jax.lax.dynamic_slice_in_dim(
            x, jnp.clip(n_valid - 1, 0, T - 1), 1)     # a row: a matmul
        last_logits = _logits(last, params, cfg)[0]
        return (kp, vp, jnp.argmax(last_logits).astype(jnp.int32),
                last_logits)

    return jax.jit(prefill, donate_argnums=(1, 2))


# ---------------------------------------------------------------------------
# the gluon block
# ---------------------------------------------------------------------------
def _run_shapes(cfg, kind):
    """{leaf: shape of one layer} of a layer of ``kind``, gluon's (out, in)
    convention for the matrices."""
    C, F, di = cfg.units, cfg.hidden_size, cfg.d_inner
    N, R = cfg.d_state, cfg.dt_rank
    kvu = cfg.num_kv_heads * cfg.head_dim
    if kind == ATTENTION:
        mixer = {"wq": (cfg.num_heads * cfg.head_dim, C), "wk": (kvu, C),
                 "wv": (kvu, C), "wo": (C, cfg.num_heads * cfg.head_dim)}
    else:
        mixer = {"w_in": (2 * di, C), "conv_w": (di, cfg.d_conv),
                 "conv_b": (di,), "w_x": (R + 2 * N, di), "norm_dt": (R,),
                 "norm_b": (N,), "norm_c": (N,), "w_dt": (di, R),
                 "b_dt": (di,), "a_log": (di, N), "d": (di,),
                 "w_out": (C, di)}
    return dict(mixer, norm1=(C,), norm2=(C,), w_gate=(F, C), w_up=(F, C),
                w_down=(C, F))


class _Run(HybridBlock):
    """Parameter container of one run of layers of one kind, every leaf
    stacked on a leading axis of the run's length."""

    def __init__(self, cfg, kind, n, dtype):
        super().__init__()
        self.leaves = sorted(_run_shapes(cfg, kind))
        for name, shape in _run_shapes(cfg, kind).items():
            setattr(self, name, Parameter(name, shape=(n,) + shape,
                                          dtype=dtype, grad_req="null"))


class HybridLM(HybridBlock):
    """A hybrid state-space / attention causal LM with a tied output
    embedding, served by ``serving.DecodeEngine`` through the step programs
    of :mod:`.decoder` and scored whole by ``forward(tokens)``.  The
    weights are frozen (``grad_req="null"``: the block exists to be served)
    and held in ``dtype``, bfloat16 as the family publishes them;
    :func:`hybrid_lm` draws them on the device."""

    def __init__(self, vocab_size=128, num_layers=6, units=64,
                 hidden_size=128, num_heads=4, num_kv_heads=1,
                 attn_layer_period=3, attn_layer_offset=1, d_state=16,
                 d_conv=4, expand=2, dt_rank=4, max_length=512,
                 rms_eps=1e-6, dtype="bfloat16", eos_id=None):
        super().__init__()
        assert units % num_heads == 0 and num_heads % num_kv_heads == 0
        assert (expand * units) % 2 == 0
        kinds = tuple(ATTENTION if i % attn_layer_period == attn_layer_offset
                      else STATE_SPACE for i in range(int(num_layers)))
        self._cfg = HybridConfig(
            vocab_size=int(vocab_size), num_layers=int(num_layers),
            units=int(units), hidden_size=int(hidden_size),
            num_heads=int(num_heads), num_kv_heads=int(num_kv_heads),
            head_dim=units // num_heads, max_length=int(max_length),
            layer_kinds=kinds, d_inner=int(expand * units),
            d_state=int(d_state), d_conv=int(d_conv), dt_rank=int(dt_rank),
            rms_eps=float(rms_eps), kv_dtype=str(dtype))
        self.eos_id = eos_id
        self.dtype = jnp.dtype(dtype)
        self.embed = Parameter("embed", shape=(vocab_size, units),
                               dtype=self.dtype, grad_req="null")
        self.norm_f = Parameter("norm_f", shape=(units,), dtype=self.dtype,
                                grad_req="null")
        self.runs = [_Run(self._cfg, kind, hi - lo, self.dtype)
                     for kind, lo, hi in layer_runs(self._cfg)]
        for i, run in enumerate(self.runs):
            setattr(self, "run%d" % i, run)     # registers the children
        self._jax_params = None

    @property
    def config(self):
        return self._cfg

    def jax_params(self):
        """{"embed", "norm_f", "runs": [{leaf: (run length, ...)}]}: the
        raw ``jax.Array`` tree the programs take (cached: serving treats
        weights as frozen)."""
        if self._jax_params is None:
            self._jax_params = {
                "embed": self.embed.data()._data,
                "norm_f": self.norm_f.data()._data,
                "runs": [{k: getattr(run, k).data()._data
                          for k in run.leaves} for run in self.runs]}
        return self._jax_params

    def forward(self, tokens):
        raw = tokens._data if hasattr(tokens, "_data") else jnp.asarray(
            tokens)
        from .. import np as mxnp
        return mxnp.array(full_forward(self.jax_params(), self._cfg,
                                       raw.astype(jnp.int32)))


@functools.partial(jax.jit, static_argnums=(1, 2))
def _normal(key, shape, dtype):
    """One leaf, drawn and cast in one program: a compile per shape, not
    one per operation and shape (a cold start drew for three minutes)."""
    return (0.02 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def hybrid_lm(seed=0, **kw):
    """An initialised :class:`HybridLM` of any size (``kw`` are its
    arguments), the weights drawn from ``seed`` **on the device, leaf by
    leaf, in the model's dtype**: matrices normal(0, 0.02); ``a_log =
    log(1 .. d_state)`` on every channel, ``d`` and the norm gains 1, the
    convolution's and ``delta``'s biases 0, as the family's published code
    sets them before training.  The importable builder of a replica spec
    or a benchmark configuration (``mxnet_tpu.models.decoder:hybrid_lm``)."""
    net = HybridLM(**kw)
    cfg, dtype = net.config, net.dtype
    keys = iter(jax.random.split(jax.random.PRNGKey(int(seed) % (2 ** 32)),
                                 1 + 16 * len(net.runs)))

    def normal(shape):
        return _normal(next(keys), tuple(shape), dtype)

    net.embed.set_data(normal(net.embed.shape))
    net.norm_f.set_data(jnp.ones(net.norm_f.shape, dtype))
    for run in net.runs:
        for name in run.leaves:
            p = getattr(run, name)
            if name == "a_log":
                value = jnp.broadcast_to(jnp.log(jnp.arange(
                    1, cfg.d_state + 1, dtype=jnp.float32)),
                    p.shape).astype(dtype)
            elif len(p.shape) == 3:             # a run of matrices
                value = normal(p.shape)
            else:
                value = jnp.full(p.shape, 0.0 if name in ("conv_b", "b_dt")
                                 else 1.0, dtype)
            p.set_data(value)
    return net
