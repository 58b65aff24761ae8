"""The plain reference of the ``xing4_0`` block (Xing4.0-29B-A4B;
``configs/xing4-29b-l5-serve.json``): the layer equations in straightforward
``jax.numpy``, float32 at the highest matmul precision, the latent attention
in its expanded form (every head's keys and values up-projected from the
latent rows) over blocks of keys, the router and the experts one expert at a
time, no cache, no pages, no kernel, nothing imported from the program.

The residual is ``x`` (n, C), n = ``hc_mult`` streams, the embedding entering
as n copies.  Each layer has two sub-layers, ``F_1`` the attention on
``rms(.; g1)`` and ``F_2`` the feed-forward part on ``rms(.; g2)``, and each
sub-layer mixes the streams (mHC, arXiv:2512.24880, sections 3-4):

    x~ = vec(x) * rsqrt(mean(vec(x)^2) + eps)              (no gain)
    [t_pre, t_post, t_res] = x~ phi^T
    H_pre = sigmoid(alpha_0 t_pre + b_pre)                 (n,)
    H_post = 2 sigmoid(alpha_1 t_post + b_post)            (n,)
    H_res = Sinkhorn_K(exp(clamp(alpha_2 t_res + b_res, -c, c)))   (n, n)
        K times: rows normalised, then columns, each sum plus eps
    x <- H_res x + H_post^T F(H_pre x)

Before the final norm the streams are mixed into one by ``sigmoid(alpha x~
phi_head^T + b_head) x``.

Latent attention, per token with ``u`` the normed input, H heads:

    c_q = rms(W_DQ u; g_q);  [q_nope_i, q_rope_i] = (W_UQ c_q)_i
    [c_kv, k_r] = W_DKV u;  c_kv = rms(c_kv; g_kv)
    k_nope_i = W_UK_i c_kv;  v_i = W_UV_i c_kv      (wkv_b: a head's keys
                                                     then its values)
    q_rope_i, k_r rotated (YaRN frequencies, the halves rotated)
    o_i = softmax_s((q_nope_i . k_nope_i,s + q_rope_i . k_r,s) m^2 /
                    sqrt(nope + rope)) v_i;   out = W_O [o_1 .. o_H]
    m = 0.1 mscale_all_dim ln(factor) + 1

The feed-forward part is the dense ``W_down (silu(W_gate u) * W_up u)`` in
a run that has ``w_gate``, else routed over all experts (all held here):

    s = sigmoid(W_r u);  chosen = the k largest of s + b
    w_e = s_e / sum of the chosen s, times routed_scale
    moe(u) = sum over chosen e of w_e E_e(u) + E_shared(u)

``logits = rms(x; g_f) head^T``.

Departures from the published description: the weights are random (from the
seed) and the mHC gains and biases with them (``assumed`` of the
configuration says how they are drawn); the rotation takes the halves of
``q_rope`` and ``k_r`` where DeepSeek's code takes interleaved pairs (a
fixed permutation of ``W_UQ``'s and ``W_DKV``'s rope rows, the same in both,
which random weights do not tell apart); the next-n (MTP) layer is left out
(it only drafts).

The weights are data: the tree the program's model hands out
(``jax_params()``).  A matrix is raised to float32 where it is multiplied,
an expert at a time, and the head a block of the vocabulary at a time, so
the reference takes a few hundred MB of weights at a time beside the
engine's 8 GB; an expert is computed over the tokens that chose it, in
blocks of rows, so at 32 768 positions the experts cost what the routing
asks for and not every token through every expert.
"""
import functools
import math

import numpy as np

#: positions a block of queries and a block of keys hold in the attention
BLOCK = 1024
#: rows an expert takes at a time
EXPERT_ROWS = 512
#: vocabulary rows the head takes at a time
HEAD_ROWS = 16384


def _yarn(rope, theta, factor, original, beta_fast, beta_slow):
    """The rotation's frequencies (rope / 2,), float64."""
    extra = 1.0 / theta ** (np.arange(0, rope, 2) / rope)
    if factor <= 1:
        return extra
    inter = extra / factor

    def dim(rotations):
        return (rope * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    lo = max(math.floor(dim(beta_fast)), 0)
    hi = min(math.ceil(dim(beta_slow)), rope - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((np.arange(rope // 2) - lo) / (hi - lo), 0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


@functools.lru_cache(maxsize=None)
def _plain(geometry, n_rows, dtype, fault, block):
    """The jitted plain forward: (params, tokens, start) -> logits of the
    ``n_rows`` positions from ``start`` on, one program for a given length
    of ``tokens`` (a whole number of ``block`` positions).  ``fault``
    plants one (``FAULTS``) or is None."""
    import jax
    import jax.numpy as jnp
    (H, nope, rope, v_dim, kv_rank, theta, factor, original, beta_fast,
     beta_slow, mscale, mscale_all, eps, k_top, norm_topk, scale, n_hc,
     iters, hc_eps, clamp) = geometry
    f32 = jnp.float32
    m = 0.1 * mscale_all * math.log(factor) + 1.0 if factor > 1 else 1.0
    m_cs = ((0.1 * mscale * math.log(factor) + 1.0) / m
            if factor > 1 else 1.0)
    softmax_scale = (1.0 if fault == "no_mscale" else m * m) / math.sqrt(
        nope + rope)
    inv = jnp.asarray(_yarn(rope, theta, factor, original, beta_fast,
                            beta_slow), f32)

    def on_grid(a):
        """``a`` at the control's precision: bfloat16 by rounding (not a
        cast there and back: XLA may drop it); int8 with each row scaled to
        its range first; float32 as it is."""
        if dtype == "bfloat16":
            return jax.lax.reduce_precision(a, exponent_bits=8,
                                            mantissa_bits=7)
        if dtype == "int8":
            top = jnp.abs(a).max(-1, keepdims=True)
            top = jnp.where(top > 0, top, 1.0)
            return jnp.round(a / top * 127.0) / 127.0 * top
        return a

    def mm(x, w):
        """x @ w.T; under a control both sides on its grid first."""
        return on_grid(x) @ on_grid(w.astype(f32)).T

    def rms(x, g=None):
        y = x * jax.lax.rsqrt(jnp.square(x).mean(-1, keepdims=True) + eps)
        return y if g is None else y * g.astype(f32)

    def rotate(x, pos):                     # x (L, .., rope), pos (L,)
        if fault == "k_r_not_rotated" and x.ndim == 2:
            return x
        ang = pos.astype(f32)[:, None] * inv                # (L, rope / 2)
        cos, sin = jnp.cos(ang) * m_cs, jnp.sin(ang) * m_cs
        if x.ndim == 3:
            cos, sin = cos[:, None], sin[:, None]
        x1, x2 = x[..., :rope // 2], x[..., rope // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               -1)

    def attention(u, lp):
        L = u.shape[0]
        pos = jnp.arange(L)
        c_q = rms(mm(u, lp["wq_a"]), lp["q_norm"])
        q = mm(c_q, lp["wq_b"]).reshape(L, H, nope + rope)
        q_nope, q_rope = q[..., :nope], rotate(q[..., nope:], pos)
        kv_a = mm(u, lp["wkv_a"])
        c_kv = kv_a[:, :kv_rank]
        if fault != "latent_cached_before_norm":
            c_kv = rms(c_kv, lp["kv_norm"])
        k_r = rotate(kv_a[:, kv_rank:], pos)                  # (L, rope)
        kv = mm(c_kv, lp["wkv_b"]).reshape(L, H, nope + v_dim)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        nb = L // block

        def queries(_, b):
            qn = jax.lax.dynamic_slice_in_dim(q_nope, b * block, block)
            qr = jax.lax.dynamic_slice_in_dim(q_rope, b * block, block)
            at = b * block + jnp.arange(block)

            def keys(c, carry):
                mx, sm, acc = carry
                kn = jax.lax.dynamic_slice_in_dim(k_nope, c * block, block)
                kr = jax.lax.dynamic_slice_in_dim(k_r, c * block, block)
                vv = jax.lax.dynamic_slice_in_dim(v, c * block, block)
                s = (jnp.einsum("thd,shd->hts", qn, kn)
                     + jnp.einsum("thr,sr->hts", qr, kr)) * softmax_scale
                seen = (c * block + jnp.arange(block))[None, :] <= at[:, None]
                s = jnp.where(seen[None], s, -jnp.inf)
                new = jnp.maximum(mx, s.max(-1))
                p = jnp.exp(s - new[..., None])
                keep = jnp.exp(mx - new)
                return (new, keep * sm + p.sum(-1),
                        keep[..., None] * acc
                        + jnp.einsum("hts,shd->htd", p, vv))
            # the key blocks up to this query block's own: later ones would
            # be masked whole (block 0 holds every query's first key)
            mx, sm, acc = jax.lax.fori_loop(0, b + 1, keys, (
                jnp.full((H, block), -jnp.inf), jnp.zeros((H, block)),
                jnp.zeros((H, block, v_dim))))
            return None, (acc / sm[..., None]).transpose(1, 0, 2)
        _, o = jax.lax.scan(queries, None, jnp.arange(nb))
        return mm(o.reshape(L, H * v_dim), lp["wo"])

    def expert(u, w_gate, w_up, w_down):
        return mm(jax.nn.silu(mm(u, w_gate)) * mm(u, w_up), w_down)

    def ffn(u, lp):
        if "w_gate" in lp:
            return expert(u, lp["w_gate"], lp["w_up"], lp["w_down"])
        s = jax.nn.sigmoid(mm(u, lp["w_router"]))
        _, idx = jax.lax.top_k(s + lp["router_bias"].astype(f32), k_top)
        w = jnp.take_along_axis(s, idx, axis=-1)
        if norm_topk:
            w = w / w.sum(-1, keepdims=True)
        w = w * scale
        width = lp["we_out"].shape[1]

        def one_expert(total, e):
            w_in, w_out, number = e
            mine = (w * (idx == number)).sum(-1)            # 0: not chosen
            # the tokens that chose it first, a block of rows at a time
            # (L is a whole number of row blocks: the last holds tokens)
            order = jnp.argsort(mine == 0)
            rows = min(EXPERT_ROWS, block)

            def some(i, total):
                r = jax.lax.dynamic_slice_in_dim(order, i * rows, rows)
                y = expert(u[r], w_in[:, :width].T, w_in[:, width:].T,
                           w_out.T)
                return total.at[r].add(mine[r][:, None] * y)
            n = (mine > 0).sum()
            return jax.lax.fori_loop(0, (n + rows - 1) // rows, some,
                                     total), None
        routed, _ = jax.lax.scan(
            one_expert, jnp.zeros(u.shape),
            (lp["we_in"], lp["we_out"], jnp.arange(lp["we_in"].shape[0])))
        return routed + expert(u, lp["ws_gate"], lp["ws_up"], lp["ws_down"])

    def sinkhorn(mat):
        if fault == "no_sinkhorn":          # a softmax over each row
            return mat / mat.sum(-1, keepdims=True)
        for _ in range(iters):
            mat = mat / (mat.sum(-1, keepdims=True) + hc_eps)
            mat = mat / (mat.sum(-2, keepdims=True) + hc_eps)
        return mat

    def hc(x, phi, alpha, b):
        """``alpha * (x~ phi^T) + b`` for the streams x (L, n, C)."""
        t = rms(x.reshape(x.shape[0], -1)) @ phi.astype(f32).T
        a = alpha.astype(f32)
        if a.shape[0] == 3:
            a = jnp.concatenate([jnp.full(n_hc, a[0]), jnp.full(n_hc, a[1]),
                                 jnp.full(n_hc * n_hc, a[2])])
        return a * t + b.astype(f32)

    def sub_layer(x, lp, name, f):
        t = hc(x, lp[name + "_phi"], lp[name + "_alpha"], lp[name + "_bias"])
        pre = jax.nn.sigmoid(t[:, :n_hc])
        post = jax.nn.sigmoid(t[:, n_hc:2 * n_hc])
        post = post if fault == "h_post_not_doubled" else 2.0 * post
        res = sinkhorn(jnp.exp(jnp.clip(
            t[:, 2 * n_hc:].reshape(-1, n_hc, n_hc), -clamp, clamp)))
        out = f(jnp.einsum("lj,ljc->lc", pre, x))
        return jnp.einsum("lij,ljc->lic", res, x) + post[..., None] * out[
            :, None]

    def layer(x, lp):
        x = sub_layer(x, lp, "hc_attn",
                      lambda h: attention(rms(h, lp["norm1"]), lp))
        return sub_layer(x, lp, "hc_ffn",
                         lambda h: ffn(rms(h, lp["norm2"]), lp)), None

    def forward(p, tokens, start):
        e = p["embed"].astype(f32)[tokens]
        x = jnp.broadcast_to(e[:, None], (e.shape[0], n_hc, e.shape[1]))
        for run in p["runs"]:
            x, _ = jax.lax.scan(layer, x, run)
        rows = jax.lax.dynamic_slice_in_dim(x, start, n_rows, axis=0)
        t = hc(rows, p["hc_head_phi"], p["hc_head_alpha"],
               p["hc_head_bias"])
        h = rms(jnp.einsum("lj,ljc->lc", jax.nn.sigmoid(t), rows),
                p["norm_f"])
        head = p["head"]
        blocks = head.reshape(-1, HEAD_ROWS, head.shape[-1]) \
            if head.shape[0] % HEAD_ROWS == 0 else head[None]
        _, logits = jax.lax.scan(lambda _, w: (None, mm(h, w)), None, blocks)
        return logits.transpose(1, 0, 2).reshape(n_rows, -1)

    return jax.jit(forward)


#: the faults this reference can plant (``reference_logits(fault=...)``)
FAULTS = ("no_mscale", "k_r_not_rotated", "no_sinkhorn",
          "h_post_not_doubled", "latent_cached_before_norm")


def _geometry(cfg):
    sp = cfg.latent
    return (cfg.num_heads, sp.nope, sp.rope, sp.v_dim, sp.kv_rank,
            float(sp.theta), float(sp.factor), int(sp.original),
            float(sp.beta_fast), float(sp.beta_slow), float(sp.mscale),
            float(sp.mscale_all_dim), float(cfg.rms_eps),
            int(cfg.experts_per_token), bool(cfg.norm_topk),
            float(cfg.routed_scale), int(cfg.streams),
            int(cfg.sinkhorn_iters), float(cfg.hc_eps), float(cfg.hc_clamp))


def reference_logits(params, cfg, fed, n_rows, pad_to=None, dtype="float32",
                     fault=None):
    """Logits (on the device) of the last ``n_rows`` positions of ``fed``;
    where ``fed`` has fewer, of its first ``n_rows`` positions.  ``fed`` is
    padded behind its end to ``pad_to`` (the causal mask keeps the padding
    out of every row before it; a routed token is its own) and to a whole
    number of :data:`BLOCK` positions (of 128 below that), so requests of
    any length share one program.  ``cfg`` names the geometry: the latent widths and rotation
    (``cfg.latent``), the heads, the router's ``experts_per_token``,
    ``norm_topk``, ``routed_scale``, the streams and their Sinkhorn.
    ``dtype`` other than float32 is a control: the same forward with both
    sides of every projection on that grid (``"bfloat16"``: rounded;
    ``"int8"``: each row scaled to its range first).  ``fault`` plants one
    of :data:`FAULTS`."""
    import gc
    import jax
    import jax.numpy as jnp
    # what a stopped engine left in reference cycles (its pools, 2.5 GB
    # here) goes before a forward that needs the room
    gc.collect()
    n = max(pad_to or 0, len(fed), n_rows)
    block = min(BLOCK, -(-n // 128) * 128)
    tokens = np.zeros(-(-n // block) * block, np.int32)
    tokens[:len(fed)] = fed
    with jax.default_matmul_precision("highest"):
        return _plain(_geometry(cfg), int(n_rows), dtype, fault, block)(
            params, jnp.asarray(tokens), jnp.int32(max(0, len(fed) - n_rows)))
