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
  With ``attn_gate`` the attention's output is gated elementwise before
  its output projection: ``W_o (sigmoid(W_g u) * att)``.
- ``"delta_rule"``: a gated delta-rule linear attention with per-channel
  decay (the ``kda`` layer of the Kimi-Linear family), per head with
  ``d_k = d_v = delta_head_dim``: ``q~, k~, v~ = silu(conv(W_q u)),
  silu(conv(W_k u)), silu(conv(W_v u))`` (causal depthwise, no bias);
  ``q = l2norm(q~) / sqrt(d_k)``, ``k = l2norm(k~)``; the log decay
  ``g = -exp(A_log_h) softplus(W_f2 W_f1 u + dt_bias)``, ``beta = 2
  sigmoid(W_b u)``; the state ``S`` (d_k x d_v): ``S <- diag(exp(g)) S``,
  ``S <- S + beta k (v - S^T k)^T``, ``o = S^T q``; ``out = W_o
  (rms_head(o) * sigmoid(W_g2 W_g1 u))``.  A decode step is that
  recurrence; a prefill chunk solves blocks of tokens together and hands
  the state from block to block (:func:`delta_rule_chunk`).  Its **state
  entry** is ``S`` of every head and the last ``K - 1`` inputs of the
  three convolutions, float32, paged as the state-space mixer's
  (:class:`HybridPool` says how it lies).  A model has state-space layers
  or delta-rule layers, not both.
- ``"state_space"``: ``[a, z] = W_in u``; a causal depthwise convolution
  ``c_t = silu(b + sum_j w[:, j] a_{t-(K-1)+j})``; ``[d, B, C] = W_x c_t``,
  each RMS-normalised; ``delta = softplus(W_dt d + b_dt)``;
  ``h_t = exp(delta A) h_{t-1} + (delta c_t) B``, ``A = -exp(A_log)``;
  ``y_t = h_t C + D c_t``; ``out = W_out (y_t silu(z))``.  What it keeps
  between tokens is ``h`` (d_inner x d_state) and the last ``K - 1``
  convolution inputs: the **state entry**, float32.

The feed-forward part is ``mlp`` above or, where the config names routed
experts (``n_experts``), the routed layer of :mod:`.routed`: top
``experts_per_token`` of all ``n_experts``, of which this chip holds
``experts_held``, and one shared expert.

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
from . import routed as _routed

__all__ = ["HybridConfig", "HybridPool", "HybridLM", "hybrid_lm",
           "layer_runs", "fresh_pool", "state_entry_bytes",
           "recurrent_layers", "delta_rule_chunk",
           "full_forward", "build_decode_step", "build_prefill_chunk"]

ATTENTION, STATE_SPACE, DELTA = "attention", "state_space", "delta_rule"


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
    # what the state-space block does not have; the defaults are that block
    attn_gate: bool = False         # W_o (sigmoid(W_g u) * att)
    tied_head: bool = True          # logits through the embedding
    delta_heads: int = 0            # the delta-rule mixer: heads,
    delta_head_dim: int = 0         # d_k = d_v of a head,
    delta_rank: int = 0             # width of the decay's and gate's W_1
    n_experts: int = 0              # the router's width; 0: the dense mlp
    experts_held: tuple = (0, 0)    # (first, count) of the experts held here
    experts_per_token: int = 0
    expert_hidden: int = 0          # width of a routed expert
    shared_hidden: int = 0          # width of the shared expert
    norm_topk: bool = True
    routed_scale: float = 1.0


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
    structure and nothing in either stays unread.  For delta-rule layers
    ``ssm`` holds ``S`` as ``(layers, P, heads / 2 * d_k, d_v)``, a head's
    rows together as the rule multiplies them (the K pool the first half of
    the heads; with the heads on the lanes a prefill chunk's transpose of
    its one entry became a relayout of the whole pool, 1.2 GB a launch),
    and ``conv`` the three convolutions' inputs, ``(K - 1) * 3 * heads *
    d_v / 2`` floats a page and layer, as a tuple of arrays ``(layers, P,
    n)`` where one row would be longer than 32 768 (:func:`_conv_parts`).

    ``counts``, only of a model that routes (else None, no leaf): what the
    routed layers counted, summed over layers and launches, uint32
    (:data:`.routed.COUNTS`).  The prefill chunk adds to the K pool's and
    the decode step to the V pool's, so the programs keep their signatures
    and nothing is read back in a step's path; the engine reads both when
    its ``stats()`` are asked."""
    rows: jax.Array
    ssm: jax.Array
    conv: jax.Array
    counts: jax.Array | None = None


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


def recurrent_layers(cfg):
    """The layers that keep a state entry: state-space or delta-rule."""
    return cfg.num_layers - _count(cfg, ATTENTION)


def _entry_dims(cfg):
    """One layer's state entry: the state's shape, the axis of it that the
    two pools halve, and the convolution inputs' (rows, channels): ``h``
    (d_state, d_inner) halved on the channels and K - 1 inputs, or ``S``
    (heads * d_k, d_v), a head's rows together, halved on the heads, and
    K - 1 inputs of each of three convolutions."""
    if DELTA in cfg.layer_kinds:
        rows = cfg.delta_heads * cfg.delta_head_dim
        return (rows, cfg.delta_head_dim), 0, (3 * (cfg.d_conv - 1), rows)
    return (cfg.d_state, cfg.d_inner), 1, (cfg.d_conv - 1, cfg.d_inner)


def state_entry_bytes(cfg):
    """Bytes of one page's state entries over all recurrent layers and both
    pools."""
    state, _, conv = _entry_dims(cfg)
    return (recurrent_layers(cfg)
            * (state[0] * state[1] + conv[0] * conv[1]) * 4)


def fresh_pool(cfg, total_pages, page_size, kv_dtype):
    """A zeroed :class:`HybridPool`, its token rows in ``kv_dtype``."""
    P, layers = int(total_pages), recurrent_layers(cfg)
    state, axis, conv = _entry_dims(cfg)
    half = tuple(n // 2 if i == axis else n for i, n in enumerate(state))
    width = conv[0] * (conv[1] // 2)
    parts = _conv_parts(width)
    rows = [jnp.zeros((layers, P, width // parts), jnp.float32)
            for _ in range(parts)]
    return HybridPool(
        rows=jnp.zeros((_count(cfg, ATTENTION), P, int(page_size),
                        cfg.num_kv_heads * cfg.head_dim),
                       jnp.dtype(kv_dtype)),
        ssm=jnp.zeros((layers, P) + half, jnp.float32),
        conv=rows[0] if parts == 1 else tuple(rows),
        counts=(jnp.zeros(len(_routed.COUNTS), jnp.uint32)
                if cfg.n_experts else None))


# ---------------------------------------------------------------------------
# the layer's parts
# ---------------------------------------------------------------------------
def _split(x):
    """float32 ``x`` as two bfloat16 terms, ``hi + lo`` (16 bits of
    mantissa), stacked on a new leading axis."""
    # reduce_precision, not a cast there and back: XLA may drop such a
    # pair of converts (xla_allow_excess_precision), and on the TPU does,
    # which leaves lo = 0 and one rounded pass at twice the price
    hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return jnp.stack([hi, x - hi]).astype(jnp.bfloat16)


def _mm(x, w):
    """``x @ w.T`` with float32 accumulation.  Float32 weights: a float32
    product.  Weights in bfloat16 are exact on the MXU, the float32
    activations are not: rounded to bfloat16 they lose 16 bits, and through
    28 pre-norm layers, whose residual stream nothing damps, that error
    reaches a fifth of a logit's standard deviation (PERF.md, PR 29).  So
    the activations go in as two bfloat16 terms, ``hi + lo`` (16 bits of
    mantissa), stacked into ONE product that reads the weights once and
    has twice the rows.  (A third term changes nothing that the check of
    a model that routes reads: PERF.md, PR 35.)"""
    if w.dtype != jnp.bfloat16:
        return jnp.dot(x.astype(w.dtype), w.T,
                       preferred_element_type=jnp.float32)
    both = jnp.dot(_split(x.astype(jnp.float32)), w.T,
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


def _feed_forward(x, lp, cfg, experts, j, live):
    """The layer's second half, ``x + ffn(rms(x))``, and what a routed layer
    counted (None for the dense mlp).  ``experts``: the run's routed
    experts, all its layers', of which this is layer ``j``; ``live``: the
    tokens that are somebody's."""
    if not cfg.n_experts:
        return _mlp(x, lp, cfg.rms_eps), None
    u = _rms(x, lp["norm2"], cfg.rms_eps)
    out, counts = _routed.routed_feed_forward(u, lp, experts, j, cfg, live)
    return x + out, counts


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


# ---------------------------------------------------------------------------
# the delta-rule mixer
# ---------------------------------------------------------------------------
_HI = jax.lax.Precision.HIGHEST


#: Tokens that the chunked delta rule solves together.
_DELTA_BLOCK = 16


def delta_rule_chunk(q, k, v, g, beta, S0, marks):
    """The gated delta rule with per-channel decay over a chunk, per head:
    ``S <- diag(exp(g_t)) S;  S <- S + beta_t k_t (v_t - S^T k_t)^T;
    o_t = S^T q_t``, from ``S0``.  q, k, g: (T, H, dk); v: (T, H, dv); beta:
    (T, H); S0: (H, dk, dv); g <= 0; T a multiple of 16.  Returns ``o``
    (T, H, dv) and ``S`` after the chunk indices ``marks`` (K,) as (K, H,
    dk, dv).  A token with ``g = 0`` and ``beta = 0`` leaves the state as
    it is.

    Chunk-parallel: blocks of ``C`` = 16 tokens are solved together.  With
    ``G_t`` the decay summed from the block's start, a block's updates
    ``u_t = beta_t (v_t - S'_t^T k_t)`` obey the unit lower-triangular
    system ``(I + diag(beta) A) U = diag(beta) (V - K+ S_in)``, ``A[t, s] =
    sum_d k_t[d] k_s[d] exp(G_t[d] - G_s[d])`` for ``s < t``, ``K+_t = k_t
    exp(G_t)``.  ``A``, the same form for ``q`` against ``k``, and the
    system's solution for both right-hand sides need no state and are
    taken for all blocks at once; a scan over the blocks then hands the
    state on with four small products a block.  Every decay is the
    exponential of a difference that is never positive, so nothing
    overflows however strong the decay."""
    T, H, dk = q.shape
    C = _DELTA_BLOCK
    nb = T // C

    def blocks(a):              # (T, H, ..) -> (nb, H, C, ..)
        return a.reshape((nb, C) + a.shape[1:]).swapaxes(1, 2)
    q, k, v, g = map(blocks, (q, k, v, g))
    beta = blocks(beta[..., None])                      # (nb, H, C, 1)
    G = jnp.cumsum(g, axis=2)                           # (nb, H, C, dk)
    later = jnp.arange(C)[:, None] >= jnp.arange(C)[None, :]    # s <= t
    # exp(G_t - G_s) on every channel, for s <= t: (nb, H, C, C, dk)
    decay = jnp.where(later[..., None], jnp.exp(jnp.minimum(
        G[:, :, :, None] - G[:, :, None, :], 0.0)), 0.0)
    ks = k[:, :, None, :, :] * decay
    A = (k[:, :, :, None, :] * ks).sum(-1)              # inclusive of s = t
    P = (q[:, :, :, None, :] * ks).sum(-1)
    k_in = k * jnp.exp(G)                               # K+
    q_in = q * jnp.exp(G)
    k_out = k * jnp.exp(G[:, :, -1:] - G)               # to the block's end
    # forward substitution, row by row: x_t = rhs_t - beta_t sum_{s<t} A x_s
    rhs = beta * jnp.concatenate([k_in, v], axis=-1)
    rows = []
    for t in range(C):
        x = rhs[:, :, t]
        if t:
            x = x - beta[:, :, t] * jnp.einsum(
                "bhs,bhsd->bhd", A[:, :, t, :t], jnp.stack(rows, 2),
                precision=_HI)
        rows.append(x)
    solved = jnp.stack(rows, 2)
    W, U0 = solved[..., :dk], solved[..., dk:]

    def block(S, x):
        W, U0, q_in, P, k_out, last = x
        U = U0 - jnp.einsum("hcd,hdv->hcv", W, S, precision=_HI)
        o = (jnp.einsum("hcd,hdv->hcv", q_in, S, precision=_HI)
             + jnp.einsum("hcs,hsv->hcv", P, U, precision=_HI))
        after = (jnp.exp(last)[..., None] * S
                 + jnp.einsum("hcd,hcv->hdv", k_out, U, precision=_HI))
        return after, (S, U, o)

    _, (before, U, o) = jax.lax.scan(
        block, S0, (W, U0, q_in, P, k_out, G[:, :, -1]))
    # the state after a marked token: the block's state decayed to it and
    # the updates of the block's tokens up to it
    b, j = marks // C, marks % C
    Gb = G[b]                                           # (K, H, C, dk)
    Gm = jnp.take_along_axis(Gb, j[:, None, None, None], axis=2)
    upto = (jnp.arange(C)[None, :] <= j[:, None])[:, None, :, None]
    k_m = jnp.where(upto, k[b] * jnp.exp(jnp.minimum(Gm - Gb, 0.0)), 0.0)
    marked = (jnp.exp(Gm[:, :, 0])[..., None] * before[b]
              + jnp.einsum("khcd,khcv->khdv", k_m, U[b], precision=_HI))
    return o.swapaxes(1, 2).reshape(T, H, -1), marked


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.square(x).sum(-1, keepdims=True) + 1e-6)


def _delta_inputs(u, lp, cfg, conv_in):
    """From the normed input ``u`` (.., T, units) and the convolutions'
    earlier inputs ``conv_in`` (.., 3 (K - 1), H d): ``q, k, v, g`` (.., T,
    H, d), ``beta`` (.., T, H), and the convolutions' inputs with the
    chunk's own behind them, (.., T + K - 1, 3, H d)."""
    H, d, K = cfg.delta_heads, cfg.delta_head_dim, cfg.d_conv
    lead, T = u.shape[:-2], u.shape[-2]
    a = _mm(u, lp["w_qkv"]).reshape(lead + (T, 3, H * d))
    ext = jnp.concatenate(
        [conv_in.reshape(lead + (K - 1, 3, H * d)), a], axis=-3)
    w = lp["conv_w"].astype(jnp.float32).reshape(3, H * d, K)
    c = jax.nn.silu(sum(w[:, :, i] * ext[..., i:i + T, :, :]
                        for i in range(K)))
    q, k, v = (c[..., i, :].reshape(lead + (T, H, d)) for i in range(3))
    g = (-jnp.exp(lp["a_log"].astype(jnp.float32))[:, None]
         * jax.nn.softplus(_mm_hi(_mm_hi(u, lp["w_f1"]), lp["w_f2"])
                           + lp["dt_bias"].astype(jnp.float32)).reshape(
                               lead + (T, H, d)))
    beta = 2.0 * jax.nn.sigmoid(_mm(u, lp["w_b"]))
    return _l2norm(q) / (d ** 0.5), _l2norm(k), v, g, beta, ext


def _delta_output(o, u, lp, cfg):
    """``W_o (rms_head(o) * sigmoid(W_g2 W_g1 u))`` of ``o`` (.., H, d)."""
    gate = jax.nn.sigmoid(_mm(_mm(u, lp["w_g1"]), lp["w_g2"]))
    o = _rms(o, lp["norm_o"], cfg.rms_eps)
    return _mm(o.reshape(gate.shape) * gate, lp["wo"])


def _delta_chunk(u, lp, cfg, h_in, conv_in, valid, marks):
    """The delta-rule mixer over one sequence's chunk ``u`` (T, units) from
    the state entry ``(h_in, conv_in)``; tokens beyond ``valid`` change no
    state.  Returns the mixer's output and the entries after the chunk
    indices ``marks``: (K, H d_k, d_v) and (K, 3 (K_conv - 1), H d)."""
    T = u.shape[0]
    q, k, v, g, beta, ext = _delta_inputs(u, lp, cfg, conv_in)
    g = jnp.where(valid[:, None, None], g, 0.0)
    beta = jnp.where(valid[:, None], beta, 0.0)
    pad = -T % _DELTA_BLOCK     # whole blocks: the tail changes no state
    if pad:
        q, k, v, g, beta = (jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
                            for a in (q, k, v, g, beta))
    H, d = cfg.delta_heads, cfg.delta_head_dim
    o, S_marks = delta_rule_chunk(q, k, v, g, beta, h_in.reshape(H, d, d),
                                  marks)
    conv_marks = jax.vmap(lambda m: jax.lax.dynamic_slice_in_dim(
        ext, m + 1, cfg.d_conv - 1))(marks)
    return (_delta_output(o[:T], u, lp, cfg),
            S_marks.reshape(marks.shape + h_in.shape),
            conv_marks.reshape(marks.shape + (-1, ext.shape[-1])))


def _delta_step(u, lp, cfg, halves, conv):
    """One token of every lane: ``u`` (B, units), the lanes' state entries
    as the two pools hold them, ``halves`` of (B, H / 2 d_k, d_v), and
    ``conv`` (B, 3 (K - 1), H d).  Returns the mixer's output and the new
    entries.  The rule runs on each pool's heads by themselves: joined in
    a layer scan, the two halves made XLA join the two whole pools before
    the loop and part them after it, 2.6 GB copied a step."""
    B, H, d = u.shape[0], cfg.delta_heads, cfg.delta_head_dim
    q, k, v, g, beta, ext = _delta_inputs(u[:, None], lp, cfg, conv)
    # (B, 1, H, d_k) -> (B, H, d_k, 1): a head's channels beside its rows
    q, k, g = (a[:, 0, :, :, None] for a in (q, k, g))
    out, new = [], []
    for part, h in enumerate(halves):
        of = slice(part * H // 2, (part + 1) * H // 2)
        S = jnp.exp(g[:, of]) * h.reshape(B, H // 2, d, d)
        upd = beta[:, 0, of, None] * (v[:, 0, of] - (S * k[:, of]).sum(2))
        S = S + k[:, of] * upd[:, :, None]                  # (B, H / 2, d, d)
        out.append((S * q[:, of]).sum(2))
        new.append(S.reshape(h.shape))
    return (_delta_output(jnp.concatenate(out, 1), u, lp, cfg), tuple(new),
            ext[:, 1:].reshape(conv.shape))


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


def _pieces(a, page):
    """Into how many row blocks a page's slice of the state array ``a``
    (layers, P, rows, d) is cut when many pages are read or written at
    once: blocks of at most 512 KB.  The TPU's gather cuts a larger slice
    in two by first copying out both halves of its whole operand, the
    pool (1.2 GB a pool and decode step for the delta rule's 2 MB
    slices); the state-space block's 164 KB slices are not cut.  (rows / n,
    d) blocks of a (rows, d) slice lie as they lay, so the view is free;
    the convolution inputs' flat rows are cut where the pool is made,
    :func:`_conv_parts`, because a view of those is not.)"""
    if jnp.ndim(page) == 0:         # one page: a dynamic slice
        return 1
    rows, nbytes = a.shape[2], a.shape[2] * a.shape[3] * a.dtype.itemsize
    pieces = 1
    while nbytes // pieces > 2 ** 19 and rows % (2 * pieces) == 0:
        pieces *= 2
    return pieces


def _conv_parts(width):
    """Into how many arrays a pool's convolution inputs (``width`` floats a
    page and layer, flat) are cut: rows of at most 32 768 elements, which
    is where the TPU's gather starts to cut a slice by copying its operand.
    1 for the state-space block (7 680), 2 for the delta rule (36 864)."""
    parts = 1
    while width // parts > 2 ** 15 and width % (2 * parts) == 0:
        parts *= 2
    return parts


def _take_pages(a, li, page):
    """``a[li, page]`` (:func:`_pieces`)."""
    n = _pieces(a, page)
    if n == 1:
        return a[li, page]
    L, P, rows, d = a.shape
    got = a.reshape(L, P * n, rows // n, d)[
        li, page[..., None] * n + jnp.arange(n)]
    return got.reshape(page.shape + (rows, d))


def _put_pages(a, li, pages, value):
    """``a.at[li, pages].set(value)`` (:func:`_pieces`)."""
    n = _pieces(a, pages)
    if n == 1:
        return a.at[li, pages].set(value)
    L, P, rows, d = a.shape
    return a.reshape(L, P * n, rows // n, d).at[
        li, pages[..., None] * n + jnp.arange(n)].set(
            value.reshape(pages.shape + (n, rows // n, d))).reshape(a.shape)


def _read_entry(kp, vp, li, page, fresh, cfg, join=True):
    """The state entry of ``page`` (any shape of page ids) in recurrent
    layer ``li``, the pools' halves joined (:func:`_entry_dims`), or with
    ``join=False`` the state's two halves as they lie; zeros where
    ``fresh``."""
    _, axis, (conv_rows, _) = _entry_dims(cfg)
    h = tuple(_take_pages(p.ssm, li, page) for p in (kp, vp))
    if join:
        h = (jnp.concatenate(h, axis=axis - 2),)
    def flat(conv):             # a pool's row, whole or from its parts
        if not isinstance(conv, tuple):
            return conv[li, page]
        return jnp.concatenate([part[li, page] for part in conv], axis=-1)
    conv = jnp.concatenate(
        [flat(p.conv).reshape(h[0].shape[:-2] + (conv_rows, -1))
         for p in (kp, vp)], axis=-1)
    fresh = jnp.asarray(fresh)[..., None, None]
    h = tuple(jnp.where(fresh, 0.0, part) for part in h)
    return h[0] if join else h, jnp.where(fresh, 0.0, conv)


def _write_entry(kp, vp, li, pages, h, conv, cfg):
    """Entries ``h`` (K,) + the state's shape (or its two halves),
    ``conv`` (K, rows, channels) over the pages ``pages`` (K,) of layer
    ``li``, in place in donated pools.  Several writers of the scratch page
    may race: nobody reads it as anything."""
    _, axis, _ = _entry_dims(cfg)

    def half(a, i, axis):
        n = a.shape[axis] // 2
        return jax.lax.slice_in_dim(a, i * n, (i + 1) * n, axis=axis)

    def put(pool, i):
        ssm = _put_pages(pool.ssm, li, pages, h[i] if isinstance(h, tuple)
                         else half(h, i, axis - 2))
        flat = half(conv, i, -1).reshape(pages.shape + (-1,))
        if not isinstance(pool.conv, tuple):
            return pool._replace(ssm=ssm,
                                 conv=pool.conv.at[li, pages].set(flat))
        return pool._replace(ssm=ssm, conv=tuple(
            part.at[li, pages].set(piece) for part, piece in zip(
                pool.conv, jnp.split(flat, len(pool.conv), -1))))
    return put(kp, 0), put(vp, 1)


def _over_layers(params, cfg, x, kp, vp, attention, recurrent, live=None):
    """The layers in order: a ``lax.scan`` over each run's stacked
    parameters, the residual stream and both pools carried.  ``attention``
    and ``recurrent`` (the state-space or the delta-rule mixer) are ``(u,
    lp, kp, vp, li) -> (out, kp, vp)`` with ``li`` the layer's index among
    its own kind (its row in the pool).  Returns the stream, the pools and
    what the routed layers counted (None where the model has none); a
    run's routed experts stay out of the scan and go to the grouped
    product whole (:func:`.routed.routed_feed_forward`)."""
    seen = {ATTENTION: 0, "recurrent": 0}
    counts = (jnp.zeros(len(_routed.COUNTS), jnp.uint32)
              if cfg.n_experts else None)
    for (kind, lo, hi), run in zip(layer_runs(cfg), params["runs"]):
        mixer = attention if kind == ATTENTION else recurrent
        kind = ATTENTION if kind == ATTENTION else "recurrent"
        experts = {k: run[k] for k in _routed.EXPERT_LEAVES if k in run}
        run = {k: v for k, v in run.items() if k not in experts}

        def layer(carry, xs, mixer=mixer, experts=experts):
            x, kp, vp, counts = carry
            lp, li, j = xs
            out, kp, vp = mixer(_rms(x, lp["norm1"], cfg.rms_eps), lp, kp,
                                vp, li)
            x, counted = _feed_forward(x + out, lp, cfg, experts, j, live)
            if counted is not None:
                counts = counts + counted
            return (x, kp, vp, counts), None

        if hi - lo == 1:        # a static layer index: the page-wise writes
            (x, kp, vp, counts), _ = layer(
                (x, kp, vp, counts),
                (jax.tree.map(lambda a: a[0], run), seen[kind], 0))
        else:
            steps = jnp.arange(hi - lo, dtype=jnp.int32)
            (x, kp, vp, counts), _ = jax.lax.scan(
                layer, (x, kp, vp, counts), (run, seen[kind] + steps, steps))
        seen[kind] += hi - lo
    return x, kp, vp, counts


def _logits(x, params, cfg):
    return _mm(_rms(x, params["norm_f"], cfg.rms_eps),
               params["embed" if cfg.tied_head else "head"])


def _attention_out(att, u, lp, cfg):
    """The attention mixer's output projection, of the gated result where
    the model gates it: ``W_o (sigmoid(W_g u) * att)``."""
    if cfg.attn_gate:
        att = att * jax.nn.sigmoid(_mm(u, lp["wg"]))
    return _mm(att, lp["wo"])


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
        return _attention_out(att.transpose(0, 2, 1, 3).reshape(B, L, -1),
                              u, lp, cfg), kp, vp

    state, _, conv = _entry_dims(cfg)
    chunk = _delta_chunk if DELTA in cfg.layer_kinds else _ssm_chunk

    def recurrent(u, lp, kp, vp, li):
        h0 = jnp.zeros(state, jnp.float32)
        c0 = jnp.zeros(conv, jnp.float32)
        out = jax.vmap(lambda ub: chunk(ub, lp, cfg, h0, c0, everything,
                                        last)[0])(u)
        return out, kp, vp

    x = params["embed"][tokens].astype(jnp.float32)
    x, _, _, _ = _over_layers(params, cfg, x, None, None, attention,
                              recurrent, live=jnp.ones((B, L), bool))
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
            return _attention_out(att[:, 0], u, lp, cfg), kp, vp

        def state_space(u, lp, kp, vp, li):
            di = cfg.d_inner
            az = _mm(u, lp["w_in"])
            a, z = az[:, :di], az[:, di:]
            h, conv = _read_entry(kp, vp, li, before, fresh, cfg)
            ext = jnp.concatenate([conv, a[:, None, :]], axis=1)
            c = _conv(ext, lp, 1)[:, 0]
            delta, Bm, Cm, A_T = _ssm_inputs(c, lp, cfg)
            h = (jnp.exp(delta[:, None, :] * A_T) * h
                 + (delta * c)[:, None, :] * Bm[:, :, None])
            y = (h * Cm[:, :, None]).sum(1) + lp["d"].astype(jnp.float32) * c
            kp, vp = _write_entry(kp, vp, li, wp, h, ext[:, 1:], cfg)
            return _mm(y * jax.nn.silu(z), lp["w_out"]), kp, vp

        def delta(u, lp, kp, vp, li):
            h, conv = _read_entry(kp, vp, li, before, fresh, cfg, join=False)
            out, h, conv = _delta_step(u, lp, cfg, h, conv)
            kp, vp = _write_entry(kp, vp, li, wp, h, conv, cfg)
            return out, kp, vp

        x = params["embed"][tokens].astype(jnp.float32)
        x, kp, vp, counts = _over_layers(
            params, cfg, x, kp, vp, attention,
            delta if DELTA in cfg.layer_kinds else state_space, live=active)
        if counts is not None:      # the decode steps' counts: the V pool's
            vp = vp._replace(counts=vp.counts + counts)
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
            return _attention_out(att[0], u, lp, cfg), kp, vp

        chunk_of = _delta_chunk if DELTA in cfg.layer_kinds else _ssm_chunk

        def recurrent(u, lp, kp, vp, li):
            h_in, conv_in = _read_entry(kp, vp, li, before, pos0 == 0,
                                            cfg)
            out, h_marks, conv_marks = chunk_of(u, lp, cfg, h_in, conv_in,
                                                valid, marks)
            kp, vp = _write_entry(kp, vp, li, pid, h_marks, conv_marks,
                                  cfg)
            return out, kp, vp

        x = params["embed"][tokens].astype(jnp.float32)
        x, kp, vp, counts = _over_layers(params, cfg, x, kp, vp, attention,
                                         recurrent, live=valid)
        if counts is not None:      # the prefill chunks' counts: the K pool's
            kp = kp._replace(counts=kp.counts + counts)
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
    convention for the matrices (the routed experts' (in, out), as the
    grouped product takes them: :data:`.routed.EXPERT_LEAVES`)."""
    C, F, di = cfg.units, cfg.hidden_size, cfg.d_inner
    N, R = cfg.d_state, cfg.dt_rank
    kvu = cfg.num_kv_heads * cfg.head_dim
    if kind == ATTENTION:
        mixer = {"wq": (cfg.num_heads * cfg.head_dim, C), "wk": (kvu, C),
                 "wv": (kvu, C), "wo": (C, cfg.num_heads * cfg.head_dim)}
        if cfg.attn_gate:
            mixer["wg"] = (cfg.num_heads * cfg.head_dim, C)
    elif kind == DELTA:
        H, d, r = cfg.delta_heads, cfg.delta_head_dim, cfg.delta_rank
        mixer = {"w_qkv": (3 * H * d, C), "conv_w": (3 * H * d, cfg.d_conv),
                 "w_f1": (r, C), "w_f2": (H * d, r), "a_log": (H,),
                 "dt_bias": (H * d,), "w_b": (H, C), "w_g1": (r, C),
                 "w_g2": (H * d, r), "norm_o": (d,), "wo": (C, H * d)}
    else:
        mixer = {"w_in": (2 * di, C), "conv_w": (di, cfg.d_conv),
                 "conv_b": (di,), "w_x": (R + 2 * N, di), "norm_dt": (R,),
                 "norm_b": (N,), "norm_c": (N,), "w_dt": (di, R),
                 "b_dt": (di,), "a_log": (di, N), "d": (di,),
                 "w_out": (C, di)}
    if not cfg.n_experts:
        return dict(mixer, norm1=(C,), norm2=(C,), w_gate=(F, C),
                    w_up=(F, C), w_down=(C, F))
    E, Fs, held = cfg.expert_hidden, cfg.shared_hidden, cfg.experts_held[1]
    return dict(mixer, norm1=(C,), norm2=(C,),
                w_router=(cfg.n_experts, C), router_bias=(cfg.n_experts,),
                we_in=(held, C, 2 * E), we_out=(held, E, C),
                ws_gate=(Fs, C), ws_up=(Fs, C), ws_down=(C, Fs))


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
    """A hybrid causal LM: recurrent layers (state-space, or the gated
    delta rule) with attention layers among them, a dense or a routed
    feed-forward part, served by ``serving.DecodeEngine`` through the step
    programs of :mod:`.decoder` and scored whole by ``forward(tokens)``.
    The weights are frozen (``grad_req="null"``: the block exists to be
    served) and held in ``dtype``, bfloat16 as the families publish them;
    :func:`hybrid_lm` draws them on the device.

    The defaults are the state-space block with a tied output embedding.
    ``attention_layers`` names the attention layers where a period and an
    offset do not; ``recurrent="delta_rule"`` takes ``delta_heads``,
    ``delta_head_dim`` and ``delta_rank``; ``n_experts`` > 0 makes every
    layer's feed-forward part the routed layer of :mod:`.routed`, of which
    this model holds the ``experts_held`` experts of share ``expert_share``
    (``[experts_held * expert_share, experts_held * (expert_share + 1))``).
    ``kv_dtype`` is the dtype the model's keys and values are cached in
    where that is not the weights' (``config.kv_dtype``)."""

    def __init__(self, vocab_size=128, num_layers=6, units=64,
                 hidden_size=128, num_heads=4, num_kv_heads=1,
                 attn_layer_period=3, attn_layer_offset=1, d_state=16,
                 d_conv=4, expand=2, dt_rank=4, max_length=512,
                 rms_eps=1e-6, dtype="bfloat16", eos_id=None, head_dim=None,
                 attention_layers=None, recurrent=STATE_SPACE,
                 attn_gate=False, tied_head=True, delta_heads=0,
                 delta_head_dim=0, delta_rank=0, n_experts=0,
                 experts_held=None, expert_share=0, experts_per_token=0,
                 expert_hidden=0, shared_hidden=0, norm_topk=True,
                 routed_scale=1.0, kv_dtype=None):
        super().__init__()
        assert num_heads % num_kv_heads == 0
        assert head_dim is not None or units % num_heads == 0
        assert (expand * units) % 2 == 0
        assert recurrent in (STATE_SPACE, DELTA)
        if attention_layers is None:
            attention_layers = [i for i in range(int(num_layers))
                                if i % attn_layer_period == attn_layer_offset]
        kinds = tuple(ATTENTION if i in set(attention_layers) else recurrent
                      for i in range(int(num_layers)))
        held = int(n_experts if experts_held is None else experts_held)
        if n_experts:
            assert 0 < held and held * (int(expert_share) + 1) <= n_experts
            assert experts_per_token <= n_experts
        self._cfg = HybridConfig(
            vocab_size=int(vocab_size), num_layers=int(num_layers),
            units=int(units), hidden_size=int(hidden_size),
            num_heads=int(num_heads), num_kv_heads=int(num_kv_heads),
            head_dim=int(head_dim or units // num_heads),
            max_length=int(max_length),
            layer_kinds=kinds, d_inner=int(expand * units),
            d_state=int(d_state), d_conv=int(d_conv), dt_rank=int(dt_rank),
            rms_eps=float(rms_eps), kv_dtype=str(kv_dtype or dtype),
            attn_gate=bool(attn_gate), tied_head=bool(tied_head),
            delta_heads=int(delta_heads),
            delta_head_dim=int(delta_head_dim), delta_rank=int(delta_rank),
            n_experts=int(n_experts),
            experts_held=(held * int(expert_share), held) if n_experts
            else (0, 0),
            experts_per_token=int(experts_per_token),
            expert_hidden=int(expert_hidden),
            shared_hidden=int(shared_hidden), norm_topk=bool(norm_topk),
            routed_scale=float(routed_scale))
        self.eos_id = eos_id
        self.dtype = jnp.dtype(dtype)
        self.embed = Parameter("embed", shape=(vocab_size, units),
                               dtype=self.dtype, grad_req="null")
        if not tied_head:
            self.head = Parameter("head", shape=(vocab_size, units),
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
        """{"embed", "norm_f", "runs": [{leaf: (run length, ...)}]} and,
        where the output embedding is its own, "head": the raw
        ``jax.Array`` tree the programs take (cached: serving treats
        weights as frozen)."""
        if self._jax_params is None:
            self._jax_params = {
                "embed": self.embed.data()._data,
                "norm_f": self.norm_f.data()._data,
                "runs": [{k: getattr(run, k).data()._data
                          for k in run.leaves} for run in self.runs]}
            if not self._cfg.tied_head:
                self._jax_params["head"] = self.head.data()._data
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


def _initial(name, p, cfg, normal):
    """The value of a run's leaf ``p`` before training, as the families'
    published code sets it."""
    f32 = jnp.float32
    if name == "a_log" and len(p.shape) == 3:   # state-space: every channel
        value = jnp.log(jnp.arange(1, cfg.d_state + 1, dtype=f32))
    elif name == "a_log":                       # delta rule: 1 .. 16 by head
        value = jnp.log(jnp.linspace(1.0, 16.0, cfg.delta_heads, dtype=f32))
    elif name == "dt_bias":     # softplus^-1 of 0.001 .. 0.1 over a head
        dt = jnp.exp(jnp.linspace(jnp.log(1e-3), jnp.log(1e-1),
                                  cfg.delta_head_dim, dtype=f32))
        value = jnp.tile(dt + jnp.log(-jnp.expm1(-dt)), cfg.delta_heads)
    elif len(p.shape) >= 3:                     # a run of matrices
        return normal(p.shape)
    else:
        value = jnp.full(p.shape, 0.0 if name in (
            "conv_b", "b_dt", "router_bias") else 1.0, f32)
    return jnp.broadcast_to(value, p.shape).astype(p.dtype)


def hybrid_lm(seed=0, **kw):
    """An initialised :class:`HybridLM` of any size (``kw`` are its
    arguments), the weights drawn from ``seed`` **on the device, leaf by
    leaf, in the model's dtype**: matrices normal(0, 0.02); ``a_log =
    log(1 .. d_state)`` on every channel, ``d`` and the norm gains 1, the
    convolution's and ``delta``'s biases 0, as the family's published code
    sets them before training (the delta rule's ``a_log`` the logarithm of
    1 .. 16 over the heads, its ``dt_bias`` the inverse softplus of 0.001
    .. 0.1 over a head's channels, the router's selection bias 0).  The
    importable builder of a replica spec or a benchmark configuration
    (``mxnet_tpu.models.decoder:hybrid_lm``)."""
    net = HybridLM(**kw)
    cfg, dtype = net.config, net.dtype
    root = jax.random.PRNGKey(int(seed) % (2 ** 32))
    keys = iter(jax.random.split(root, 1 + 16 * len(net.runs)))

    def normal(shape):
        key, shape = next(keys), tuple(shape)
        if len(shape) < 4:
            return _normal(key, shape, dtype)
        # a run of stacks of matrices (the routed experts): a layer at a
        # time, so that the float32 draw of the largest is a layer's
        return jnp.stack([_normal(k, shape[1:], dtype)
                          for k in jax.random.split(key, shape[0])])

    net.embed.set_data(normal(net.embed.shape))
    net.norm_f.set_data(jnp.ones(net.norm_f.shape, dtype))
    for run in net.runs:
        for name in run.leaves:
            p = getattr(run, name)
            p.set_data(_initial(name, p, cfg, normal))
    if not cfg.tied_head:
        net.head.set_data(_normal(jax.random.fold_in(root, 1),
                                  tuple(net.head.shape), dtype))
    return net
