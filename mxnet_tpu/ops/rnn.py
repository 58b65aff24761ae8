"""Fused stacked RNN (LSTM/GRU/vanilla) kernels.

Parity: reference `src/operator/rnn.cc` + `rnn-inl.h` + `rnn_impl.h`: one
stateful op runs the whole stacked/bidirectional sequence (cuDNN RNN on GPU,
oneDNN on CPU).  TPU-native: the time loop is a `lax.scan` (compiled once,
unrolled by XLA onto the MXU per step); stacking/bidirectionality are
composed functionally.  Weight layout matches the reference's flattened
parameter vector (i2h_weight, h2h_weight, i2h_bias, h2h_bias per layer per
direction, gates in MXNet order: LSTM [i, f, c, o], GRU [r, z, n]).
"""
from __future__ import annotations

import numpy as onp

import jax
import jax.numpy as jnp
from jax import lax


def _gates(mode):
    return {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}[mode]


def param_size(mode, input_size, state_size, num_layers=1, bidirectional=False,
               projection_size=None):
    """Total flattened parameter count (parity: rnn-inl.h GetParamSize)."""
    ng = _gates(mode)
    d = 2 if bidirectional else 1
    size = 0
    for layer in range(num_layers):
        in_sz = input_size if layer == 0 else state_size * d
        for _ in range(d):
            size += ng * state_size * in_sz      # i2h_weight
            size += ng * state_size * state_size  # h2h_weight
            size += 2 * ng * state_size           # i2h_bias + h2h_bias
    return size


def unpack_params(params, mode, input_size, state_size, num_layers=1,
                  bidirectional=False):
    """Slice the flat parameter vector into per-layer weight dicts.

    Layout matches reference rnn-inl.h: all weights (layer-major,
    direction-minor), then all biases.
    """
    ng = _gates(mode)
    d = 2 if bidirectional else 1
    layers = []
    off = 0
    for layer in range(num_layers):
        in_sz = input_size if layer == 0 else state_size * d
        dirs = []
        for _ in range(d):
            w_i2h = lax.dynamic_slice(params, (off,), (ng * state_size * in_sz,)).reshape(
                (ng * state_size, in_sz))
            off += ng * state_size * in_sz
            w_h2h = lax.dynamic_slice(params, (off,), (ng * state_size * state_size,)).reshape(
                (ng * state_size, state_size))
            off += ng * state_size * state_size
            dirs.append({"w_i2h": w_i2h, "w_h2h": w_h2h})
        layers.append(dirs)
    for layer in range(num_layers):
        for dd in range(d):
            b_i2h = lax.dynamic_slice(params, (off,), (ng * state_size,))
            off += ng * state_size
            b_h2h = lax.dynamic_slice(params, (off,), (ng * state_size,))
            off += ng * state_size
            layers[layer][dd]["b_i2h"] = b_i2h
            layers[layer][dd]["b_h2h"] = b_h2h
    return layers


def _cell_step(mode, state_size):
    """Step fns take the PRE-TRANSPOSED recurrent weight (H, G): the
    transpose is hoisted out of the scan so the per-step program is one
    (B,H)x(H,G) matmul + fused elementwise (the cuDNN-RNN fusion,
    reference rnn-inl.h, re-based on the MXU)."""
    if mode == "lstm":
        def step(carry, gates_x, w_h2h_t, b_h2h):
            h, c = carry
            g = gates_x + jnp.matmul(h, w_h2h_t) + b_h2h
            i, f, u, o = jnp.split(g, 4, axis=-1)
            i = jax.nn.sigmoid(i)
            f = jax.nn.sigmoid(f)
            u = jnp.tanh(u)
            o = jax.nn.sigmoid(o)
            c2 = f * c + i * u
            h2 = o * jnp.tanh(c2)
            return (h2, c2), h2
    elif mode == "gru":
        def step(carry, gates_x, w_h2h_t, b_h2h):
            (h,) = carry
            gh = jnp.matmul(h, w_h2h_t) + b_h2h
            xr, xz, xn = jnp.split(gates_x, 3, axis=-1)
            hr, hz, hn = jnp.split(gh, 3, axis=-1)
            r = jax.nn.sigmoid(xr + hr)
            z = jax.nn.sigmoid(xz + hz)
            n = jnp.tanh(xn + r * hn)
            h2 = (1 - z) * n + z * h
            return (h2,), h2
    else:
        act = jax.nn.relu if mode == "rnn_relu" else jnp.tanh

        def step(carry, gates_x, w_h2h_t, b_h2h):
            (h,) = carry
            h2 = act(gates_x + jnp.matmul(h, w_h2h_t) + b_h2h)
            return (h2,), h2
    return step


# scan unroll factor: amortizes per-step loop overhead and lets XLA
# software-pipeline consecutive cells' matmul + elementwise phases
# (MXNET_RNN_SCAN_UNROLL overrides; 5 won the 1/5/7/35 sweep on v5e).
# Read per call, not at import — the knob is an A/B lever and jax.scan
# handles any remainder when seq_len is not divisible by it.
import os as _os


def _scan_unroll():
    try:
        return max(1, int(_os.environ.get("MXNET_RNN_SCAN_UNROLL", "5")))
    except ValueError:
        return 5


def _single_layer(x, h0, c0, p, mode, reverse=False, fused=None):
    """x: (T, B, I). Returns (out (T, B, H), hT, cT).

    ``fused`` ('compiled'|'interpret'|None) routes the LSTM forward
    direction through the persistent fused-cell Pallas kernel
    (ops/pallas/fused_cell): the i2h GEMM stays hoisted here, the whole
    time loop runs as ONE kernel launch.  GRU/vanilla and the reverse
    direction fall back to the scan."""
    gates_x = jnp.einsum("tbi,gi->tbg", x, p["w_i2h"]) + p["b_i2h"]
    w_h2h_t = p["w_h2h"].T  # hoisted: one transpose per call, not per step
    if fused is not None and mode == "lstm" and not reverse:
        from .pallas import fused_cell as _fc
        c0v = c0 if c0 is not None else jnp.zeros_like(h0)
        return _fc.lstm_sequence(gates_x, h0, c0v, w_h2h_t, p["b_h2h"],
                                 mode=fused)
    step = _cell_step(mode, p["w_h2h"].shape[1])
    carry = (h0, c0) if mode == "lstm" else (h0,)

    def scan_fn(carry, gx):
        new_carry, out = step(carry, gx, w_h2h_t, p["b_h2h"])
        return new_carry, out

    carry, outs = lax.scan(scan_fn, carry, gates_x, reverse=reverse,
                           unroll=_scan_unroll())
    hT = carry[0]
    cT = carry[1] if mode == "lstm" else None
    return outs, hT, cT


def _stacked_wavefront(x, layers, h0, c0, mode, state_size):
    """Layer-diagonal (wavefront) schedule for a unidirectional stacked
    RNN: iteration k advances layer l at time k-l, so ALL layers' cell
    matmuls batch into ONE (2L-1, B, H) x (2L-1, H, G) batched matmul
    per iteration and the serial chain is T+L-1 iterations instead of
    T*L — the cuDNN persistent-RNN schedule, re-based on the MXU.
    Numerically identical to the layer-by-layer scan."""
    T, B = x.shape[0], x.shape[1]
    L = len(layers)
    H = state_size
    ng = _gates(mode)
    step = _cell_step(mode, H)

    # precompute layer-0 input projections for all T (biases folded)
    p0 = layers[0][0]
    gates_x0 = jnp.einsum("tbi,gi->tbg", x, p0["w_i2h"]) + p0["b_i2h"]

    w_h2h = jnp.stack([p[0]["w_h2h"].T for p in layers])        # (L,H,G)
    b_h2h = jnp.stack([p[0]["b_h2h"] for p in layers])          # (L,G)
    if L > 1:
        w_i2h_rest = jnp.stack([p[0]["w_i2h"].T for p in layers[1:]])
        b_i2h_rest = jnp.stack([p[0]["b_i2h"] for p in layers[1:]])

    lidx = jnp.arange(L)
    is_lstm = mode == "lstm"

    def body(carry, k):
        h, c, pend = carry            # h,c: (L,B,H); pend: (L-1,B,H) or None
        # one batched matmul: recurrent for all L + input-proj for l>=1
        if L > 1:
            A = jnp.concatenate([h, pend], axis=0)          # (2L-1,B,H)
            W = jnp.concatenate([w_h2h, w_i2h_rest], axis=0)
            prod = jnp.matmul(A, W)                          # (2L-1,B,G)
            hh = prod[:L] + b_h2h[:, None, :]
            i2h_rest = prod[L:] + b_i2h_rest[:, None, :]
        else:
            hh = jnp.matmul(h, w_h2h) + b_h2h[:, None, :]
            i2h_rest = None
        gx0 = gates_x0[jnp.clip(k, 0, T - 1)]                # (B,G)
        if L > 1:
            gx = jnp.concatenate([gx0[None], i2h_rest], axis=0)
        else:
            gx = gx0[None]
        g = gx + hh                                          # (L,B,G)

        if is_lstm:
            i, f, u, o = jnp.split(g, 4, axis=-1)
            i = jax.nn.sigmoid(i)
            f = jax.nn.sigmoid(f)
            u = jnp.tanh(u)
            o = jax.nn.sigmoid(o)
            c2 = f * c + i * u
            h2 = o * jnp.tanh(c2)
        elif mode == "gru":
            # gru gates mix differently: xr/xz/xn from gx, hr/hz/hn from hh
            xr, xz, xn = jnp.split(gx, 3, axis=-1)
            hr, hz, hn = jnp.split(hh, 3, axis=-1)
            r = jax.nn.sigmoid(xr + hr)
            z = jax.nn.sigmoid(xz + hz)
            n = jnp.tanh(xn + r * hn)
            h2 = (1 - z) * n + z * h
            c2 = c
        else:
            act = jax.nn.relu if mode == "rnn_relu" else jnp.tanh
            h2 = act(g)
            c2 = c

        active = ((k >= lidx) & (k < T + lidx))[:, None, None]  # (L,1,1)
        h_new = jnp.where(active, h2, h)
        c_new = jnp.where(active, c2, c) if is_lstm else c
        pend_new = h_new[:-1] if L > 1 else pend
        return (h_new, c_new, pend_new), h_new[-1]

    # run the whole cell in the compute dtype (x ⊗ weights promotion):
    # a float32 h0 against bf16 weights would silently promote every
    # recurrent matmul back to fp32
    cdt = gates_x0.dtype
    h0 = h0.astype(cdt)
    pend0 = jnp.zeros((L - 1, B, H), cdt) if L > 1 else \
        jnp.zeros((0, B, H), cdt)
    c_init = (c0.astype(cdt) if c0 is not None
              else jnp.zeros_like(h0))
    (hT, cT, _), outs = lax.scan(
        body, (h0, c_init, pend0), jnp.arange(T + L - 1),
        unroll=min(_scan_unroll(), T + L - 1))
    out_seq = outs[L - 1:]                                   # (T,B,H)
    return out_seq, hT, (cT if is_lstm else None)


def rnn_forward(x, params, h0, c0, mode, state_size, num_layers=1,
                bidirectional=False, dropout_rate=0.0, dropout_key=None,
                fused="auto"):
    """Full stacked RNN. x: (T, B, I); h0/c0: (L*D, B, H).

    Returns (out (T, B, H*D), hT (L*D, B, H), cT or None).

    ``fused``: the persistent fused-cell kernel gate for the LSTM time
    loop — "auto" resolves MXNET_RNN_FUSED_CELL (Pallas on a TPU
    backend, off elsewhere), None/False disables,
    'compiled'/'interpret' force.  Callers that jit-trace this function
    (npx.rnn, bench A/B arms) resolve the gate OUTSIDE and pass the
    value through so their trace caches key on it.
    """
    d = 2 if bidirectional else 1
    layers = unpack_params(params, mode, x.shape[-1], state_size, num_layers,
                           bidirectional)

    if fused == "auto":
        from .pallas import fused_cell as _fc
        fused = _fc.rnn_mode()
    elif not fused:
        fused = None
    fused = fused if mode == "lstm" else None

    # fused wavefront path: unidirectional stacks without inter-layer
    # dropout.  (Layer-0's input projection is precomputed for all T, so
    # any input width works; layers 1..L-1 have in_size == state_size by
    # construction when d == 1.)  MXNET_RNN_WAVEFRONT=0 forces the
    # layer-by-layer scan (A/B lever).  The persistent fused-cell kernel
    # outranks the wavefront for LSTM: the wavefront shrank the serial
    # chain to T+L-1 dispatches, the fused kernel collapses it to one
    # launch per layer.
    no_drop = (dropout_rate == 0.0 or dropout_key is None
               or num_layers == 1)
    if d == 1 and no_drop and fused is None and \
            _os.environ.get("MXNET_RNN_WAVEFRONT", "1") != "0":
        return _stacked_wavefront(
            x, layers, h0, c0 if mode == "lstm" else None, mode,
            state_size)
    hTs, cTs = [], []
    inp = x
    for li, dirs in enumerate(layers):
        outs = []
        for di, p in enumerate(dirs):
            s = li * d + di
            out, hT, cT = _single_layer(
                inp, h0[s], c0[s] if c0 is not None else None, p, mode,
                reverse=(di == 1), fused=fused)
            outs.append(out)
            hTs.append(hT)
            if cT is not None:
                cTs.append(cT)
        inp = outs[0] if d == 1 else jnp.concatenate(outs, axis=-1)
        if dropout_rate > 0.0 and dropout_key is not None and li < num_layers - 1:
            sub = jax.random.fold_in(dropout_key, li)
            keep = 1.0 - dropout_rate
            mask = jax.random.bernoulli(sub, keep, inp.shape).astype(inp.dtype) / keep
            inp = inp * mask
    hT = jnp.stack(hTs)
    cT = jnp.stack(cTs) if cTs else None
    return inp, hT, cT
