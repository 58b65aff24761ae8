"""The plain reference of the ``brumby`` block (Brumby-14B-Base;
``configs/brumby-14b-l4-serve.json``): the layer equations in
straightforward ``jax.numpy``, float32 at the highest matmul precision,
power retention in its attention form (O(L^2), no state), no cache, no
batching, no kernel, nothing imported from the program.

For layer ``i`` on the residual stream ``x`` (L, C), every product without
bias, ``H`` query heads on ``KVH`` KV heads of ``D`` (query head ``h`` on KV
head ``h // (H / KVH)``):

    h = x + W_o [y_1 .. y_H](rms(x; g1_i));  x' = h + mlp_i(rms(h; g2_i))
    rms(x; g) = x * rsqrt(mean(x^2) + eps) * g
    mlp(u) = W_down (silu(W_gate u) * (W_up u))

and with ``u = rms(x; g1_i)`` at position ``t``:

    q_{t,h} = RoPE(rms((W_q u_t)_h; q_norm), t) / sqrt(D)
    k_{t,c} = RoPE(rms((W_k u_t)_c; k_norm), t);  v_{t,c} = (W_v u_t)_c
    log g_{t,c} = logsigmoid(W_decay u_t + b_decay)_c
    c_{t,c} = sum_{r <= t} log g_{r,c}
    w_{t,s} = (q_{t,h} . k_{s,c})^2 exp(c_{t,c} - c_{s,c})      s <= t
    y_{t,h} = sum_{s <= t} w_{t,s} v_{s,c} / sum_{s <= t} w_{t,s}

``RoPE(x, t)`` rotates the halves of ``x``: ``[x1 cos - x2 sin, x2 cos + x1
sin]`` at angles ``t / rope_theta^(2i / D)``, i < D / 2.  The logits are
``rms(x; g_f) head^T``.  The program computes the same function through
its state (``S_t = g_t S_{t-1} + phi(k_t) v_t^T``, ``phi(q).phi(k) =
(q.k)^2``); nothing of that form is here.

The weights are data: the tree the program's model hands out
(``jax_params()``), whose ``runs`` hold the layers stacked on a leading
axis.  They stay in the dtype they come in; a matrix is raised to float32
where it is multiplied (a bfloat16 weight raised to float32 is exact), one
layer at a time, and the head in blocks of the vocabulary: raised whole it
would be 3.1 GB beside the engine during the check.
"""
import functools
import math

import numpy as np

#: Bytes of one block of the head raised to float32, at most.
HEAD_BLOCK_BYTES = 2 ** 28


def head_blocks(vocab, units):
    """Into how many equal blocks of rows the head is cut: the fewest that
    divide the vocabulary and hold at most HEAD_BLOCK_BYTES in float32."""
    n = max(1, -(-vocab * units * 4 // HEAD_BLOCK_BYTES))
    while vocab % n:
        n += 1
    return n


@functools.lru_cache(maxsize=None)
def _plain(num_heads, num_kv_heads, head_dim, eps, theta, n_rows, dtype):
    """The jitted plain forward: (params, tokens, start) -> logits of the
    ``n_rows`` positions from ``start`` on (one program for a given length
    of ``tokens``, whatever ``start`` is)."""
    import jax
    import jax.numpy as jnp
    H, KVH, D = num_heads, num_kv_heads, head_dim
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

    def rope(x):                        # x (L, heads, D)
        L, half = x.shape[0], D // 2
        inv = 1.0 / theta ** (jnp.arange(half, dtype=f32) * 2 / D)
        ang = jnp.arange(L, dtype=f32)[:, None, None] * inv
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                                x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)

    def retention(u, lp):
        L = u.shape[0]
        q = mm(u, lp["wq"]).reshape(L, H, D)
        k = mm(u, lp["wk"]).reshape(L, KVH, D)
        v = mm(u, lp["wv"]).reshape(L, KVH, D)
        q = rope(rms(q, lp["q_norm"])) / math.sqrt(D)
        k = rope(rms(k, lp["k_norm"]))
        log_g = jax.nn.log_sigmoid(mm(u, lp["w_decay"])
                                   + lp["b_decay"].astype(f32))
        c = jnp.cumsum(log_g, axis=0)                       # (L, KVH)
        causal = jnp.tril(jnp.ones((L, L), bool))
        gap = jnp.where(causal[None], c.T[:, :, None] - c.T[:, None, :],
                        -jnp.inf)                           # (KVH, t, s)
        k, v = (jnp.repeat(a, H // KVH, axis=1) for a in (k, v))
        decay = jnp.repeat(jnp.exp(gap), H // KVH, axis=0)  # (H, t, s)
        w = jnp.square(jnp.einsum("thd,shd->hts", q, k)) * decay
        y = jnp.einsum("hts,shd->thd", w, v) / w.sum(-1).T[..., None]
        return mm(y.reshape(L, H * D), lp["wo"])

    def mlp(u, lp):
        return mm(jax.nn.silu(mm(u, lp["w_gate"])) * mm(u, lp["w_up"]),
                  lp["w_down"])

    def layer(x, lp):
        h = x + retention(rms(x, lp["norm1"]), lp)
        return h + mlp(rms(h, lp["norm2"]), lp), None

    def logits(rows, head):
        """The head raised to float32 a block of the vocabulary at a
        time."""
        V, C = head.shape
        n = head_blocks(V, C)
        out = jax.lax.map(lambda w: mm(rows, w), head.reshape(n, V // n, C))
        return out.transpose(1, 0, 2).reshape(rows.shape[0], V)

    def forward(p, tokens, start):
        x = p["embed"].astype(f32)[tokens]
        for run in p["runs"]:
            x, _ = jax.lax.scan(layer, x, run)
        rows = jax.lax.dynamic_slice_in_dim(x, start, n_rows, axis=0)
        return logits(rms(rows, p["norm_f"]), p["head"])

    return jax.jit(forward)


def reference_logits(params, cfg, fed, n_rows, pad_to=None, dtype="float32"):
    """Logits (on the device) of the last ``n_rows`` positions of ``fed``;
    where ``fed`` has fewer, of its first ``n_rows`` positions.  ``pad_to``
    pads ``fed`` behind its end (the causal weights keep the padding out of
    every row before it), so requests of any length share one program.
    ``cfg`` gives the heads, the norm's epsilon and the rotary base.  ``dtype`` other than float32 is a control:
    the same forward with both sides of every projection on that grid
    (``"bfloat16"``: rounded; ``"int8"``, ``"float8_e4m3fn"``: each row
    scaled to its range first).  The products themselves are float32 at
    the highest precision throughout."""
    import jax
    import jax.numpy as jnp
    tokens = np.zeros(max(pad_to or 0, len(fed), n_rows), np.int32)
    tokens[:len(fed)] = fed
    fn = _plain(cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                float(cfg.rms_eps), float(cfg.rope_theta), int(n_rows), dtype)
    with jax.default_matmul_precision("highest"):
        return fn(params, jnp.asarray(tokens),
                  jnp.int32(max(0, len(fed) - n_rows)))
