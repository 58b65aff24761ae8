"""Latent attention (multi-head latent attention, MLA): the mixer of
:mod:`.hybrid`'s ``"latent_attention"`` layers, over a paged cache of one
compressed row a token and layer.

Per token, with ``u`` the mixer's normed input and ``H`` heads::

    c_q = rms(W_DQ u)                                    (q_rank)
    [q_nope_i, q_rope_i] = (W_UQ c_q)_i                  (nope + rope a head)
    [c_kv, k_r] = W_DKV u;  c_kv = rms(c_kv)             (kv_rank; rope)
    q_rope_i, k_r rotated at the token's position (YaRN), k_r shared by all
    score_i(t, s) = (q_nope_i . W_UK_i c_kv_s + q_rope_i . k_r_s) * scale
    o_i = sum_s softmax(score_i) W_UV_i c_kv_s;   out = W_O [o_1 .. o_H]

with ``scale = m^2 / sqrt(nope + rope)`` and ``m = 0.1 mscale_all_dim
ln(factor) + 1`` (DeepSeek's YaRN rule).  ``W_UK_i`` and ``W_UV_i`` are the
rows of head ``i`` in ``W_UKV`` (``wkv_b``: a head's ``nope`` rows of keys,
then its ``v_dim`` rows of values).

**The cache** holds ``[c_kv, k_r]`` of every token and layer, after the norm
and the rotation, padded with zeros to a whole number of 128 lanes
(:func:`row_width`).  A page of ``S`` positions lies half in each pool: the
K pool holds positions ``[0, S/2)`` of every page and the V pool ``[S/2,
S)`` (the two pools are made by the same call and have one shape, and
nothing in either stays unread).

**Decode** takes the absorbed form: ``q~_i = W_UK_i^T q_nope_i``, attention
of the ``H`` heads over the one latent row (the whole row for the scores
against ``[q~_i, q_rope_i, 0]``, its first ``kv_rank`` lanes for the
values), then ``W_UV_i`` after the sum.  On a TPU the walk over each lane's
live pages is a Pallas kernel (:mod:`..ops.pallas.latent_attention`).

**A prefill chunk** up-projects the context's rows instead (``W_UK c_kv``,
``W_UV c_kv``: a chunk of 512 queries over ``N`` cached rows costs ``N *
(2 H (nope + v) kv_rank + 2 H T (nope + rope + v))`` operations that way
against ``N * 2 H T (2 kv_rank + rope)`` absorbed, half), a block of
context rows at a time with a running maximum and sum: no ``(heads, chunk,
context)`` score tensor whole, and the blocks past the chunk's end are
never read.

Precision: the projections as :func:`.hybrid._mm` (bfloat16 weights exact,
the float32 activations as two bfloat16 terms); the chunk's attention
products at ``Precision.HIGH``; the decode kernel's two products at the
highest precision.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import hybrid as _hybrid

__all__ = ["Latent", "row_width", "score_scale", "yarn_inv_freq",
           "latent_inputs", "write_rows", "page_rows", "decode_attention",
           "absorbed_step", "chunk_attention", "full_attention"]

_MASKED = -1e30         # a score past the causal edge; finite: no inf - inf
#: context positions a block of the prefill chunk's attention reads
CHUNK_BLOCK = 1024


class Latent(NamedTuple):
    """Static geometry of the latent mixer (the published keys)."""
    q_rank: int             # q_lora_rank
    kv_rank: int            # kv_lora_rank
    nope: int               # qk_nope_head_dim
    rope: int               # qk_rope_head_dim
    v_dim: int              # v_head_dim
    theta: float            # rope_theta
    factor: float = 1.0     # rope_scaling (YaRN): factor,
    original: int = 0       # original_max_position_embeddings,
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


def row_width(spec):
    """Lanes of one cached row: ``kv_rank + rope`` padded to 128."""
    return -(-(spec.kv_rank + spec.rope) // 128) * 128


def _yarn_mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 and m else 1.0


def score_scale(spec):
    """``m^2 / sqrt(nope + rope)``: the softmax scale under YaRN's
    ``mscale_all_dim`` rule."""
    m = _yarn_mscale(spec.factor, spec.mscale_all_dim)
    return m * m / math.sqrt(spec.nope + spec.rope)


def yarn_inv_freq(spec):
    """The rotation's frequencies (rope / 2,), float32: YaRN's blend of the
    original ``theta^(-2i/d)`` and the interpolated ``/ factor`` by a linear
    ramp between the dimensions that turn ``beta_fast`` and ``beta_slow``
    times over ``original`` positions (DeepSeek's
    ``yarn_find_correction_range``).  ``factor`` 1: the plain rotation."""
    d = spec.rope
    extra = 1.0 / spec.theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    if spec.factor <= 1:
        return extra.astype(np.float32)
    inter = extra / spec.factor

    def dim(rotations):
        return (d * math.log(spec.original / (rotations * 2 * math.pi))
                / (2 * math.log(spec.theta)))
    lo = max(math.floor(dim(spec.beta_fast)), 0)
    hi = min(math.ceil(dim(spec.beta_slow)), d - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - lo) / (hi - lo),
                   0.0, 1.0)
    keep = 1.0 - ramp                   # 1: the original frequency
    return (inter * (1.0 - keep) + extra * keep).astype(np.float32)


def _cos_sin_scale(spec):
    return (_yarn_mscale(spec.factor, spec.mscale)
            / _yarn_mscale(spec.factor, spec.mscale_all_dim))


def _rotate(x, positions, spec):
    """``x`` (..., heads, rope) rotated at ``positions`` (x.shape[:-2]): the
    halves ``[x1 cos - x2 sin, x2 cos + x1 sin]``, cos and sin times YaRN's
    ``mscale / mscale_all_dim``."""
    return _hybrid._rotate(x, positions, jnp.asarray(yarn_inv_freq(spec)),
                           _cos_sin_scale(spec))


def _heads(x, w):
    """``x_h @ w_h.T`` for every head: x (..., H, a), w (H, b, a) ->
    (..., H, b), float32 accumulation; bfloat16 weights take the float32
    activations as two bfloat16 terms (:func:`.hybrid._mm`).  Both sides
    go in as float32 holding bfloat16 values, which one pass of the MXU
    multiplies exactly (the CPU has no batched product of bfloat16
    operands)."""
    if w.dtype != jnp.bfloat16:
        return jnp.einsum("...ha,hba->...hb", x.astype(w.dtype), w,
                          preferred_element_type=jnp.float32)
    both = jnp.einsum("s...ha,hba->s...hb", _hybrid._split(
        x.astype(jnp.float32)).astype(jnp.float32), w.astype(jnp.float32),
        preferred_element_type=jnp.float32)
    return both[0] + both[1]


def _up(lp, cfg):
    """``W_UK`` (H, nope, kv_rank) and ``W_UV`` (H, v_dim, kv_rank) as views
    of ``wkv_b``."""
    sp = cfg.latent
    w = lp["wkv_b"].reshape(cfg.num_heads, sp.nope + sp.v_dim, sp.kv_rank)
    return w[:, :sp.nope], w[:, sp.nope:]


def latent_inputs(u, lp, cfg, positions):
    """From the normed input ``u`` (.., units) at ``positions`` (..):
    ``q_nope`` (.., H, nope), ``q_rope`` (.., H, rope) rotated, and the
    row the cache keeps, ``[c_kv, k_r, 0]`` (.., row_width)."""
    sp, mm = cfg.latent, _hybrid._mm
    lead = u.shape[:-1]
    c_q = _hybrid._rms(mm(u, lp["wq_a"]), lp["q_norm"], cfg.rms_eps)
    q = mm(c_q, lp["wq_b"]).reshape(lead + (cfg.num_heads, -1))
    q_rope = _rotate(q[..., sp.nope:], positions, sp)
    kv = mm(u, lp["wkv_a"])
    c_kv = _hybrid._rms(kv[..., :sp.kv_rank], lp["kv_norm"], cfg.rms_eps)
    k_r = _rotate(kv[..., None, sp.kv_rank:], positions, sp)[..., 0, :]
    pad = jnp.zeros(lead + (row_width(sp) - sp.kv_rank - sp.rope,),
                    jnp.float32)
    return q[..., :sp.nope], q_rope, jnp.concatenate([c_kv, k_r, pad], -1)


def write_rows(kp, vp, li, pages, slots, rows):
    """``rows`` (n, width) at page ``pages`` (n,), position ``slots`` (n,)
    in the page, into layer ``li`` of the pools, in place: a position in
    the first half of its page lies in the K pool, one in the second half
    in the V pool, and the other pool's write goes to the scratch page
    (page 0, which nobody reads)."""
    half = kp.rows.shape[2]
    first = slots < half
    at = slots % half
    rows = rows.astype(kp.rows.dtype)
    return (kp._replace(rows=kp.rows.at[li, jnp.where(first, pages, 0),
                                        at].set(rows)),
            vp._replace(rows=vp.rows.at[li, jnp.where(first, 0, pages),
                                        at].set(rows)))


def page_rows(k_rows, v_rows, li, pages):
    """The rows of ``pages`` (.., n) of layer ``li`` in position order, the
    two halves of each page joined: (.., n * S, width)."""
    both = jnp.stack([k_rows[li, pages], v_rows[li, pages]], axis=-3)
    return both.reshape(pages.shape[:-1] + (-1, k_rows.shape[-1]))


def decode_attention(q_row, kp, vp, li, lengths, tables):
    """The absorbed decode attention of ``q_row`` (B, H, width), already
    scaled, over each lane's first ``lengths`` cached rows: (B, H, width),
    softmax-weighted sums of whole rows.  The Pallas walk over live pages
    where :func:`~..ops.pallas.latent_attention.kernel_mode` selects it,
    else every page of every table gathered (the kernel's reference)."""
    from ..ops.pallas import latent_attention as _la
    if _la.kernel_mode(kp.rows) is not None:
        return _la.paged_latent_rows(q_row, kp.rows, vp.rows, li, lengths,
                                     tables)
    _la.last_path = "xla"
    return _la.latent_rows(q_row, page_rows(kp.rows, vp.rows, li, tables),
                           lengths)


def absorbed_step(q_nope, q_rope, kp, vp, li, lengths, tables, lp, cfg):
    """A decode step's attention output (B, H * v_dim): the query absorbed
    into the latent space, attention over the latent rows, ``W_UV`` after
    the sum."""
    sp = cfg.latent
    w_uk, w_uv = _up(lp, cfg)
    q_lat = _heads(q_nope, jnp.swapaxes(w_uk, 1, 2))     # (B, H, kv_rank)
    pad = jnp.zeros(q_rope.shape[:-1] + (row_width(sp) - sp.kv_rank
                                         - sp.rope,), jnp.float32)
    q_row = jnp.concatenate([q_lat, q_rope, pad], -1) * score_scale(sp)
    o = decode_attention(q_row, kp, vp, li, lengths, tables)
    out = _heads(o[..., :sp.kv_rank], w_uv)              # (B, H, v_dim)
    return out.reshape(out.shape[0], -1)


def chunk_attention(q_nope, q_rope, kp, vp, li, page_row, idx, end, lp,
                    cfg):
    """One sequence's chunk of queries (T, H, ..) at positions ``idx`` over
    its cached rows ``[0, end)`` (the chunk's own among them), up-projected
    a block of :data:`CHUNK_BLOCK` positions at a time, with a running
    maximum and sum: (T, H * v_dim).  Blocks at or past ``end`` are not
    read."""
    sp = cfg.latent
    T, H = q_nope.shape[:2]
    S = 2 * kp.rows.shape[2]
    per = max(1, CHUNK_BLOCK // S)                  # pages a block
    n = page_row.shape[0]
    table = jnp.pad(page_row, (0, -n % per))
    hi = jax.lax.Precision.HIGH
    scale = score_scale(sp)
    qn = q_nope * scale
    qr = q_rope * scale

    def block(b, carry):
        m, l, acc = carry
        pages = jax.lax.dynamic_slice_in_dim(table, b * per, per)
        ctx = page_rows(kp.rows, vp.rows, li, pages)     # (per * S, width)
        kv = _hybrid._mm(ctx[:, :sp.kv_rank], lp["wkv_b"]).reshape(
            ctx.shape[0], H, -1)
        s = (jnp.einsum("thn,chn->htc", qn, kv[..., :sp.nope], precision=hi)
             + jnp.einsum("thr,cr->htc", qr,
                          ctx[:, sp.kv_rank:sp.kv_rank + sp.rope],
                          precision=hi))
        pos = b * per * S + jnp.arange(per * S, dtype=jnp.int32)
        s = jnp.where(pos[None, None, :] <= idx[None, :, None], s, _MASKED)
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        acc = alpha[..., None] * acc + jnp.einsum(
            "htc,chv->htv", p, kv[..., sp.nope:], precision=hi)
        return m_new, alpha * l + p.sum(-1), acc

    blocks = (end + per * S - 1) // (per * S)
    m, l, acc = jax.lax.fori_loop(0, blocks, block, (
        jnp.full((H, T), _MASKED, jnp.float32), jnp.zeros((H, T), jnp.float32),
        jnp.zeros((H, T, sp.v_dim), jnp.float32)))
    out = acc / jnp.where(l > 0, l, 1.0)[..., None]
    return out.transpose(1, 0, 2).reshape(T, -1)


def full_attention(q_nope, q_rope, rows, lp, cfg):
    """The causal attention of a whole sequence (B, L, ..) over its own
    rows (B, L, width), expanded: (B, L, H * v_dim).  Scoring only
    (``HybridLM.forward``)."""
    sp = cfg.latent
    B, L, H = q_nope.shape[:3]
    kv = _hybrid._mm(rows[..., :sp.kv_rank], lp["wkv_b"]).reshape(
        B, L, H, -1)
    hi = jax.lax.Precision.HIGHEST
    s = (jnp.einsum("bthn,bchn->bhtc", q_nope, kv[..., :sp.nope],
                    precision=hi)
         + jnp.einsum("bthr,bcr->bhtc", q_rope,
                      rows[..., sp.kv_rank:sp.kv_rank + sp.rope],
                      precision=hi)) * score_scale(sp)
    causal = jnp.tril(jnp.ones((L, L), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    out = jnp.einsum("bhtc,bchv->bthv", p, kv[..., sp.nope:], precision=hi)
    return out.reshape(B, L, -1)
