"""The plain reference of the hybrid state-space / attention decoder
(AI21-Jamba2-3B's block; ``configs/jamba2-3b-serve.json``): the layer
equations in straightforward ``jax.numpy``, float32 at the highest matmul
precision, the recurrence token by token, O(L^2) attention, no cache, no
kernel, nothing imported from the program.

For layer ``i`` of the model, on the residual stream ``x`` (L, C):

    h = x + mixer_i(rms(x; g1_i));  x' = h + mlp(rms(h; g2_i))
    rms(x; g) = x * rsqrt(mean(x^2) + eps) * g
    mlp(u) = W_down (silu(W_gate u) * (W_up u))

``mixer_i`` is causal attention (no bias, no positions of any kind, every
query head on its group's KV head) or the selective state-space mixer:

    [a, z] = W_in u
    c_t = silu(b_conv + sum_j w_conv[:, j] * a_{t-(K-1)+j})     zeros before t=0
    [d, B, C] = W_x c_t;  d, B, C = rms(d; g_dt), rms(B; g_B), rms(C; g_C)
    delta = softplus(W_dt d + b_dt);  A = -exp(A_log)
    h_t = exp(delta[:, None] * A) * h_{t-1} + (delta * c_t)[:, None] * B[None, :]
    y_t = h_t C + D * c_t;  out = W_out (y_t * silu(z))

and ``logits = rms(x; g_f) embed^T``.  The weights are data: the tree the
program's model hands out (``jax_params()``), whose ``runs`` hold each run of
consecutive layers of one kind stacked on a leading axis; a run with a
``wq`` is attention.  They stay in the dtype they come in and are raised to
float32 one layer at a time inside the program (a ``lax.scan`` over the
run), so the reference of a 3 B model takes 0.4 GB of weights at a time and
not 12 GB; a bfloat16 weight raised to float32 is exact.
"""
import functools
import math

import numpy as np


@functools.lru_cache(maxsize=None)
def _plain_hybrid(num_heads, num_kv_heads, head_dim, eps, n_rows, dtype):
    """The jitted plain forward: (params, tokens, start) -> logits of the
    ``n_rows`` positions from ``start`` on.  One program for a given length
    of ``tokens``, whatever ``start`` is."""
    import jax
    import jax.numpy as jnp
    H, KVH, D = num_heads, num_kv_heads, head_dim

    # the grids of the controls: a row scaled to [-1, 1] for the 8-bit ones
    grids = {
        "int8": lambda a: jnp.round(a * 127.0) / 127.0,
        "float8_e4m3fn": lambda a: (a * 448.0).astype(
            jnp.float8_e4m3fn).astype(jnp.float32) / 448.0,
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
        return on_grid(x) @ on_grid(w).T

    def rms(x, g):
        return x * jax.lax.rsqrt(jnp.square(x).mean(-1, keepdims=True)
                                 + eps) * g

    def mlp(x, lp):
        u = rms(x, lp["norm2"])
        return x + mm(jax.nn.silu(mm(u, lp["w_gate"])) * mm(u, lp["w_up"]),
                      lp["w_down"])

    def attention(u, lp):
        L = u.shape[0]
        q = mm(u, lp["wq"]).reshape(L, H, D)
        k = jnp.repeat(mm(u, lp["wk"]).reshape(L, KVH, D), H // KVH, axis=1)
        v = jnp.repeat(mm(u, lp["wv"]).reshape(L, KVH, D), H // KVH, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(D)
        causal = jnp.tril(jnp.ones((L, L), bool))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return mm(jnp.einsum("hqk,khd->qhd", p, v).reshape(L, H * D),
                  lp["wo"])

    def state_space(u, lp):
        L = u.shape[0]
        d_inner, K = lp["conv_w"].shape
        n_state, dt_rank = lp["a_log"].shape[1], lp["w_dt"].shape[1]
        a, z = jnp.split(mm(u, lp["w_in"]), 2, axis=-1)
        padded = jnp.concatenate([jnp.zeros((K - 1, d_inner)), a])
        c = jax.nn.silu(lp["conv_b"] + sum(
            lp["conv_w"][:, j] * padded[j:j + L] for j in range(K)))
        d, B, C = jnp.split(mm(c, lp["w_x"]), [dt_rank, dt_rank + n_state],
                            axis=-1)
        d, B, C = (rms(d, lp["norm_dt"]), rms(B, lp["norm_b"]),
                   rms(C, lp["norm_c"]))
        delta = jax.nn.softplus(mm(d, lp["w_dt"]) + lp["b_dt"])
        A = -jnp.exp(lp["a_log"])                       # (d_inner, n_state)

        def token(h, at):
            delta_t, c_t, B_t, C_t = at
            h = on_grid(jnp.exp(delta_t[:, None] * A) * h
                        + (delta_t * c_t)[:, None] * B_t[None, :])
            return h, h @ C_t + lp["d"] * c_t
        _, y = jax.lax.scan(token, jnp.zeros((d_inner, n_state)),
                            (delta, c, B, C))
        return mm(y * jax.nn.silu(z), lp["w_out"])

    def layer(x, lp):
        lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
        mixer = attention if "wq" in lp else state_space
        return mlp(x + mixer(rms(x, lp["norm1"]), lp), lp), None

    def forward(p, tokens, start):
        embed = p["embed"].astype(jnp.float32)
        x = embed[tokens]
        for run in p["runs"]:
            x, _ = jax.lax.scan(layer, x, run)
        rows = jax.lax.dynamic_slice_in_dim(x, start, n_rows, axis=0)
        return mm(rms(rows, p["norm_f"].astype(jnp.float32)), embed)

    return jax.jit(forward)


def reference_logits(params, cfg, fed, n_rows, pad_to=None, dtype="float32"):
    """Logits (on the device) of the last ``n_rows`` positions of ``fed``;
    where ``fed`` has fewer, of its first ``n_rows`` positions.  ``pad_to``
    pads ``fed`` behind its end (attention's causal mask and the recurrence's
    direction keep the padding out of every row before it), so requests of
    any length share one program.  ``dtype`` other than float32 is a control:
    the same forward with both sides of every projection, and the recurrent
    state after every token, on that grid (``"bfloat16"``: rounded; ``"int8"``,
    ``"float8_e4m3fn"``: each row scaled to its range first).  The products
    themselves are float32 at the highest precision throughout."""
    import jax
    import jax.numpy as jnp
    tokens = np.zeros(max(pad_to or 0, len(fed), n_rows), np.int32)
    tokens[:len(fed)] = fed
    fn = _plain_hybrid(cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                       float(cfg.rms_eps), int(n_rows), dtype)
    with jax.default_matmul_precision("highest"):
        return fn(params, jnp.asarray(tokens),
                  jnp.int32(max(0, len(fed) - n_rows)))
