"""The plain reference of the ``solar_open2`` block (Solar-Open2-250B;
``configs/solar-open2-ep8-serve.json``) as one chip of an expert-parallel
group holds it: the layer equations in straightforward ``jax.numpy``, float32
at the highest matmul precision, the delta rule token by token, O(L^2)
attention, every held expert over every token, no cache, no batching, no
kernel, nothing imported from the program.

For layer ``i`` on the residual stream ``x`` (L, C), no bias and no
positions anywhere:

    h = x + mixer_i(rms(x; g1_i));  x' = h + moe_i(rms(h; g2_i))
    rms(x; g) = x * rsqrt(mean(x^2) + eps) * g

``mixer_i`` is gated attention where the run has a ``wq`` (every query head
on its group's KV head, causal):

    out = W_o (sigmoid(W_g u) * softmax(q k^T / sqrt(D)) v)

or the gated delta rule with per-channel decay (``kda``), per head ``h`` with
``d = d_k = d_v``; ``W_qkv`` holds ``W_q``, ``W_k``, ``W_v`` one below the
other and ``conv_w`` their taps alike:

    q~, k~, v~ = silu(conv(W_q u)), silu(conv(W_k u)), silu(conv(W_v u))
        conv(a)_t = sum_j w[:, j] * a_{t-(K-1)+j}           zeros before t=0
    q = l2norm(q~) / sqrt(d);  k = l2norm(k~)
        l2norm(x) = x rsqrt(|x|^2 + 1e-6)
    g = -exp(A_log_h) * softplus(W_f2 W_f1 u + dt_bias)      (d per head)
    beta = 2 sigmoid(W_b u)                          (kda_allow_neg_eigval)
    S <- diag(exp(g)) S;  S <- S + beta k (v - S^T k)^T;  o = S^T q
    out = W_o (rms(o; g_o) * sigmoid(W_g2 W_g1 u))           rms over a head

``moe_i`` routes every token over all ``n`` experts and adds what the experts
**held here** (``cfg.experts_held``: first, count) give, and the shared one:

    s = sigmoid(W_r u);  chosen = the k largest of s + b
    w_e = s_e / sum of the chosen s, times routed_scale
    moe(u) = sum over chosen e held here of w_e E_e(u) + E_shared(u)
    E(u) = W_down (silu(W_gate u) * (W_up u))

(``we_in[e]`` is ``[W_gate^T, W_up^T]`` side by side, ``we_out[e]`` is
``W_down^T``.)  What the experts of the other chips would add is left out,
as the program leaves it out; ``logits = rms(x; g_f) head^T`` over the
vocabulary's slice.

The weights are data: the tree the program's model hands out
(``jax_params()``), whose ``runs`` hold each run of consecutive layers of one
kind stacked on a leading axis.  They stay in the dtype they come in; a
matrix is raised to float32 where it is multiplied and the held experts one
at a time, so the reference of 6.6 GB of weights takes 0.4 GB of them at a
time beside the engine; a bfloat16 weight raised to float32 is exact.
"""
import functools
import math

import numpy as np


@functools.lru_cache(maxsize=None)
def _plain(num_heads, num_kv_heads, head_dim, eps, held, per_token,
           norm_topk, scale, n_rows, dtype):
    """The jitted plain forward: (params, tokens, start) -> logits of the
    ``n_rows`` positions from ``start`` on (one program for a given length
    of ``tokens``, whatever ``start`` is), and two of its parts by
    themselves: ``moe(u, lp)`` and ``stream(params, tokens)``, the residual
    stream behind the last layer."""
    import jax
    import jax.numpy as jnp
    H, KVH, D = num_heads, num_kv_heads, head_dim
    first, count = held
    f32 = jnp.float32

    # the grids of the controls: a row scaled to [-1, 1] for the 8-bit ones
    grids = {
        "int8": lambda a: jnp.round(a * 127.0) / 127.0,
        "float8_e4m3fn": lambda a: (a * 448.0).astype(
            jnp.float8_e4m3fn).astype(f32) / 448.0,
    }

    def on_grid(a):
        """``a`` at the control's precision: bfloat16 by rounding, the 8-bit
        grids with each row scaled to its range first; float32 as it is."""
        if dtype == "bfloat16":    # not a cast there and back: XLA may drop it
            return jax.lax.reduce_precision(a, exponent_bits=8,
                                            mantissa_bits=7)
        if dtype not in grids:
            return a
        top = jnp.abs(a).max(-1, keepdims=True)
        top = jnp.where(top > 0, top, 1.0)
        return grids[dtype](a / top) * top

    def mm(x, w):
        """x @ w.T; under a control both sides on its grid first (each
        token's activations, each output channel's weights)."""
        return on_grid(x) @ on_grid(w.astype(f32)).T

    def rms(x, g):
        return x * jax.lax.rsqrt(jnp.square(x).mean(-1, keepdims=True)
                                 + eps) * g.astype(f32)

    def attention(u, lp):
        L = u.shape[0]
        q = mm(u, lp["wq"]).reshape(L, H, D)
        k = jnp.repeat(mm(u, lp["wk"]).reshape(L, KVH, D), H // KVH, axis=1)
        v = jnp.repeat(mm(u, lp["wv"]).reshape(L, KVH, D), H // KVH, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(D)
        causal = jnp.tril(jnp.ones((L, L), bool))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        att = jnp.einsum("hqk,khd->qhd", p, v).reshape(L, H * D)
        return mm(jax.nn.sigmoid(mm(u, lp["wg"])) * att, lp["wo"])

    def delta_rule(u, lp):
        L = u.shape[0]
        heads = lp["a_log"].shape[0]
        width, K = lp["conv_w"].shape
        d = width // (3 * heads)
        a = mm(u, lp["w_qkv"])
        padded = jnp.concatenate([jnp.zeros((K - 1, width)), a])
        conv_w = lp["conv_w"].astype(f32)
        c = jax.nn.silu(sum(conv_w[:, j] * padded[j:j + L]
                            for j in range(K)))
        q, k, v = (x.reshape(L, heads, d) for x in jnp.split(c, 3, axis=-1))

        def l2norm(x):
            return x * jax.lax.rsqrt(jnp.square(x).sum(-1, keepdims=True)
                                     + 1e-6)
        q, k = l2norm(q) / math.sqrt(d), l2norm(k)
        g = (-jnp.exp(lp["a_log"].astype(f32))[:, None]
             * jax.nn.softplus(mm(mm(u, lp["w_f1"]), lp["w_f2"])
                               + lp["dt_bias"].astype(f32)).reshape(
                                   L, heads, d))
        beta = 2.0 * jax.nn.sigmoid(mm(u, lp["w_b"]))       # (L, heads)

        def token(S, at):                                   # S (heads, d, d)
            q_t, k_t, v_t, g_t, beta_t = at
            S = jnp.exp(g_t)[:, :, None] * S
            seen = jnp.einsum("hkv,hk->hv", S, k_t)
            S = on_grid(S + beta_t[:, None, None] * k_t[:, :, None]
                        * (v_t - seen)[:, None, :])
            return S, jnp.einsum("hkv,hk->hv", S, q_t)
        _, o = jax.lax.scan(token, jnp.zeros((heads, d, d)),
                            (q, k, v, g, beta))
        gate = jax.nn.sigmoid(mm(mm(u, lp["w_g1"]), lp["w_g2"]))
        return mm(rms(o, lp["norm_o"]).reshape(L, heads * d) * gate,
                  lp["wo"])

    def expert(u, w_gate, w_up, w_down):
        return mm(jax.nn.silu(mm(u, w_gate)) * mm(u, w_up), w_down)

    def moe(u, lp):
        s = jax.nn.sigmoid(mm(u, lp["w_router"]))
        _, idx = jax.lax.top_k(s + lp["router_bias"].astype(f32), per_token)
        w = jnp.take_along_axis(s, idx, axis=-1)
        if norm_topk:
            w = w / w.sum(-1, keepdims=True)
        w = w * scale
        width = lp["we_out"].shape[1]

        def held_expert(total, e):
            w_in, w_out, number = e
            mine = (w * (idx == number)).sum(-1)            # 0: not chosen
            y = expert(u, w_in[:, :width].T, w_in[:, width:].T, w_out.T)
            return total + mine[:, None] * y, None
        routed, _ = jax.lax.scan(
            held_expert, jnp.zeros_like(u),
            (lp["we_in"], lp["we_out"], first + jnp.arange(count)))
        return routed + expert(u, lp["ws_gate"], lp["ws_up"], lp["ws_down"])

    def layer(x, lp):
        mixer = attention if "wq" in lp else delta_rule
        h = x + mixer(rms(x, lp["norm1"]), lp)
        return h + moe(rms(h, lp["norm2"]), lp), None

    def stream(p, tokens):
        x = p["embed"].astype(f32)[tokens]
        for run in p["runs"]:
            x, _ = jax.lax.scan(layer, x, run)
        return x

    def forward(p, tokens, start):
        rows = jax.lax.dynamic_slice_in_dim(stream(p, tokens), start, n_rows,
                                            axis=0)
        return mm(rms(rows, p["norm_f"]), p["head"])

    return jax.jit(forward), jax.jit(moe), jax.jit(stream)


def _of(cfg, n_rows=1, dtype="float32"):
    return _plain(cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                  float(cfg.rms_eps), tuple(cfg.experts_held),
                  int(cfg.experts_per_token), bool(cfg.norm_topk),
                  float(cfg.routed_scale), int(n_rows), dtype)


def reference_moe(lp, cfg, u):
    """``moe(u)`` of one layer's leaves ``lp`` (not stacked) by itself, for
    the experts ``cfg.experts_held``: what the test that adds the shares up
    compares."""
    import jax
    with jax.default_matmul_precision("highest"):
        return _of(cfg)[1](u, lp)


def reference_stream(params, cfg, fed):
    """The residual stream behind the last layer, (len(fed), units)."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        return _of(cfg)[2](params, jnp.asarray(np.asarray(fed, np.int32)))


def reference_logits(params, cfg, fed, n_rows, pad_to=None, dtype="float32"):
    """Logits (on the device) of the last ``n_rows`` positions of ``fed``;
    where ``fed`` has fewer, of its first ``n_rows`` positions.  ``pad_to``
    pads ``fed`` behind its end (attention's causal mask and the recurrence's
    direction keep the padding out of every row before it; a routed token
    is its own), so requests of any length share one program.  ``cfg`` names
    the share: ``experts_held`` (first, count), ``experts_per_token``,
    ``norm_topk``, ``routed_scale``, beside the heads.  ``dtype`` other than
    float32 is a control: the same forward with both sides of every
    projection, the router's among them, and the delta rule's state after
    every token, on that grid (``"bfloat16"``: rounded; ``"int8"``,
    ``"float8_e4m3fn"``: each row scaled to its range first).  The products
    themselves are float32 at the highest precision throughout."""
    import jax
    import jax.numpy as jnp
    tokens = np.zeros(max(pad_to or 0, len(fed), n_rows), np.int32)
    tokens[:len(fed)] = fed
    with jax.default_matmul_precision("highest"):
        return _of(cfg, n_rows, dtype)[0](
            params, jnp.asarray(tokens), jnp.int32(max(0, len(fed) - n_rows)))
