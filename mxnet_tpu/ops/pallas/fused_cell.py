"""Persistent fused-cell Pallas kernel for a latency-bound serial loop.

The LSTM word-LM step is latency-bound: ~70 serial small-cell iterations
whose per-iteration dispatch/launch overhead, not flops or bytes, sets
the throughput band.  The scan/wavefront paths in ``ops/rnn.py`` already
minimized the per-iteration *program*; what is left is the per-iteration
*launch*.  This module removes it: one kernel invocation owns the whole
serial loop.

:func:`lstm_sequence` — RNN training.  ONE ``pallas_call`` iterates
the time dimension in its grid (``dimension_semantics=("arbitrary",)``
— a sequential grid): the recurrent weight ``w_h2h_t`` and bias are
latched in VMEM once (constant index map — fetched on step 0, resident
for the whole sequence), the carries (h, c) live in VMEM scratch, and
each grid step fuses the ``(B,H)x(H,4H)`` recurrent matmul + all four
gate nonlinearities + the elementwise state update.  The ``i2h``
batched GEMM stays hoisted outside, exactly as the scan path does.
A ``jax.custom_vjp`` in the style of ``ops/pallas/epilogue.py`` makes
it trainable: the backward is a second persistent kernel running the
grid time-REVERSED, recomputing the gate activations from the saved
carries (h/c sequences — h is the primal output, so the only extra
residual is the c sequence) instead of storing per-gate activations;
the weight/bias gradients contract OUTSIDE the kernel as one batched
GEMM over the emitted per-step gate gradients (the transpose of the
hoisted-i2h trick).

Dispatch is the repo's gate grammar (flash/epilogue/paged):
``MXNET_RNN_FUSED_CELL`` — ``''`` auto (on a TPU backend), ``0``/``off``
forces the scan path, ``interpret`` forces the Pallas kernel in
interpreter mode (the CPU test lane).  LSTM is covered; GRU/vanilla RNN
and the reverse direction of bidirectional stacks take the scan path.

:func:`count_launches` is the audit tool for the dispatch-count claims:
a deterministic, load-independent jaxpr walk counting the primitives
that lower to device kernel launches (matmuls, gathers/scatters,
reductions, pallas calls; elementwise chains fuse and are excluded).
The decoder's launch censuses (``models/decoder.py``) and
``tests/test_fused_cell.py`` assert on it — counts, not timings.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kernel_mode

__all__ = ["lstm_sequence", "rnn_mode", "count_launches", "trace_counts",
           "last_path"]

# per-op trace counter (tests assert the fused path is actually in the
# compiled program, the PR-2 epilogue convention)
trace_counts = {"lstm_sequence": 0}
# "pallas" | "pallas-interpret" — which backend the last call latched
last_path = None


# ---------------------------------------------------------------------------
# dispatch gate
# ---------------------------------------------------------------------------
def rnn_mode():
    """'compiled' | 'interpret' | None — the fused LSTM cell gate
    (``MXNET_RNN_FUSED_CELL``)."""
    return kernel_mode("MXNET_RNN_FUSED_CELL")


# ---------------------------------------------------------------------------
# persistent LSTM cell kernel
# ---------------------------------------------------------------------------
def _lstm_fwd_kernel(gx_ref, h0_ref, c0_ref, w_ref, b_ref,
                     out_ref, cseq_ref, h_scr, c_scr):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        h_scr[...] = h0_ref[...].astype(jnp.float32)
        c_scr[...] = c0_ref[...].astype(jnp.float32)

    h = h_scr[...]
    c = c_scr[...]
    g = (gx_ref[0].astype(jnp.float32)
         + jnp.dot(h, w_ref[...].astype(jnp.float32),
                   preferred_element_type=jnp.float32)
         + b_ref[...].astype(jnp.float32))
    i, f, u, o = jnp.split(g, 4, axis=-1)
    i = jax.nn.sigmoid(i)
    f = jax.nn.sigmoid(f)
    u = jnp.tanh(u)
    o = jax.nn.sigmoid(o)
    c2 = f * c + i * u
    h2 = o * jnp.tanh(c2)
    h_scr[...] = h2
    c_scr[...] = c2
    out_ref[0] = h2.astype(out_ref.dtype)
    cseq_ref[0] = c2.astype(cseq_ref.dtype)


def _lstm_seq_fwd_pallas(gates_x, h0, c0, w_h2h_t, b_h2h, interpret):
    T, B, G = gates_x.shape
    H = h0.shape[-1]
    dt = gates_x.dtype
    step_spec = pl.BlockSpec((1, B, G), lambda t: (t, 0, 0))
    out_spec = pl.BlockSpec((1, B, H), lambda t: (t, 0, 0))
    whole2 = pl.BlockSpec((B, H), lambda t: (0, 0))
    out, cseq = pl.pallas_call(
        _lstm_fwd_kernel,
        grid=(T,),
        in_specs=[step_spec, whole2, whole2,
                  pl.BlockSpec((H, G), lambda t: (0, 0)),
                  pl.BlockSpec((G,), lambda t: (0,))],
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((T, B, H), dt),
                   jax.ShapeDtypeStruct((T, B, H), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((B, H), jnp.float32),
                        pltpu.VMEM((B, H), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(gates_x, h0, c0, w_h2h_t, b_h2h)
    return out, cseq, None


def _lstm_bwd_kernel(gx_ref, hp_ref, cp_ref, ct_ref, do_ref, dcs_ref,
                     w_ref, b_ref, dgx_ref, dh0_ref, dc0_ref,
                     dh_scr, dc_scr):
    t = pl.program_id(0)          # grid step t processes time T-1-t

    @pl.when(t == 0)
    def _():
        dh_scr[...] = jnp.zeros_like(dh_scr)
        dc_scr[...] = jnp.zeros_like(dc_scr)

    w = w_ref[...].astype(jnp.float32)
    hp = hp_ref[0].astype(jnp.float32)
    cp = cp_ref[0].astype(jnp.float32)
    ct = ct_ref[0].astype(jnp.float32)
    # recompute the gate activations from the saved carries — zero
    # per-gate residuals, one extra (B,H)x(H,4H) matmul on the MXU
    g = (gx_ref[0].astype(jnp.float32)
         + jnp.dot(hp, w, preferred_element_type=jnp.float32)
         + b_ref[...].astype(jnp.float32))
    i, f, u, o = jnp.split(g, 4, axis=-1)
    i = jax.nn.sigmoid(i)
    f = jax.nn.sigmoid(f)
    u = jnp.tanh(u)
    o = jax.nn.sigmoid(o)

    dh = dh_scr[...] + do_ref[0].astype(jnp.float32)
    tc = jnp.tanh(ct)
    d_o = dh * tc
    dc = dc_scr[...] + dcs_ref[0].astype(jnp.float32) + dh * o * (1 - tc * tc)
    dgi = (dc * u) * i * (1 - i)
    dgf = (dc * cp) * f * (1 - f)
    dgu = (dc * i) * (1 - u * u)
    dgo = d_o * o * (1 - o)
    dg = jnp.concatenate([dgi, dgf, dgu, dgo], axis=-1)   # (B, 4H)
    dgx_ref[0] = dg.astype(dgx_ref.dtype)
    # dh_{t-1} = dg @ w_h2h_t.T : contract the gate dim
    dh_prev = jax.lax.dot_general(
        dg, w, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    dc_prev = dc * f
    dh_scr[...] = dh_prev
    dc_scr[...] = dc_prev

    @pl.when(t == pl.num_programs(0) - 1)
    def _():
        dh0_ref[...] = dh_prev.astype(dh0_ref.dtype)
        dc0_ref[...] = dc_prev.astype(dc0_ref.dtype)


def _lstm_seq_bwd_pallas(gates_x, h_prev, c_prev, cseq, dout, dcseq,
                         w_h2h_t, b_h2h, interpret):
    T, B, G = gates_x.shape
    H = h_prev.shape[-1]
    rev_g = pl.BlockSpec((1, B, G), lambda t: (T - 1 - t, 0, 0))
    rev_h = pl.BlockSpec((1, B, H), lambda t: (T - 1 - t, 0, 0))
    whole2 = pl.BlockSpec((B, H), lambda t: (0, 0))
    return pl.pallas_call(
        _lstm_bwd_kernel,
        grid=(T,),
        in_specs=[rev_g, rev_h, rev_h, rev_h, rev_h, rev_h,
                  pl.BlockSpec((H, G), lambda t: (0, 0)),
                  pl.BlockSpec((G,), lambda t: (0,))],
        out_specs=[rev_g, whole2, whole2],
        out_shape=[jax.ShapeDtypeStruct((T, B, G), gates_x.dtype),
                   jax.ShapeDtypeStruct((B, H), gates_x.dtype),
                   jax.ShapeDtypeStruct((B, H), gates_x.dtype)],
        scratch_shapes=[pltpu.VMEM((B, H), jnp.float32),
                        pltpu.VMEM((B, H), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(gates_x, h_prev, c_prev, cseq, dout, dcseq, w_h2h_t, b_h2h)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _lstm_seq(gates_x, h0, c0, w_h2h_t, b_h2h, mode):
    out, cseq, _ = _lstm_seq_fwd_pallas(gates_x, h0, c0, w_h2h_t, b_h2h,
                                        mode == "interpret")
    return out, cseq


def _lstm_seq_fwd(gates_x, h0, c0, w_h2h_t, b_h2h, mode):
    out, cseq = _lstm_seq(gates_x, h0, c0, w_h2h_t, b_h2h, mode)
    # residuals: inputs + the primal carries.  `out` IS the h sequence,
    # so the only extra activation-sized save is the c sequence
    return (out, cseq), (gates_x, h0, c0, w_h2h_t, b_h2h, out, cseq)


def _lstm_seq_bwd(mode, res, cts):
    gates_x, h0, c0, w_h2h_t, b_h2h, out, cseq = res
    dout, dcseq = cts
    cdt = gates_x.dtype
    h_prev = jnp.concatenate([h0[None].astype(cdt), out[:-1]], axis=0)
    c_prev = jnp.concatenate([c0[None].astype(jnp.float32),
                              cseq[:-1]], axis=0)
    dgx, dh0, dc0 = _lstm_seq_bwd_pallas(
        gates_x, h_prev, c_prev, cseq, dout, dcseq, w_h2h_t, b_h2h,
        mode == "interpret")
    # weight/bias grads contract OUTSIDE the kernel as one batched GEMM
    # over the per-step gate grads (the bwd analog of the hoisted i2h)
    dw = jnp.einsum("tbh,tbg->hg", h_prev.astype(jnp.float32),
                    dgx.astype(jnp.float32)).astype(w_h2h_t.dtype)
    db = jnp.sum(dgx.astype(jnp.float32), axis=(0, 1)).astype(b_h2h.dtype)
    return (dgx, dh0.astype(h0.dtype), dc0.astype(c0.dtype), dw, db)


_lstm_seq.defvjp(_lstm_seq_fwd, _lstm_seq_bwd)


def lstm_sequence(gates_x, h0, c0, w_h2h_t, b_h2h, mode=None):
    """Whole-sequence fused LSTM cell loop: one persistent kernel.

    gates_x:  (T, B, 4H) — precomputed input projections (+ i2h bias)
    h0, c0:   (B, H) initial carries
    w_h2h_t:  (H, 4H) pre-transposed recurrent weight (latched in VMEM)
    b_h2h:    (4H,)

    Returns (out (T, B, H), hT (B, H), cT (B, H)); differentiable via
    the persistent backward kernel.  ``mode`` defaults to
    :func:`rnn_mode` and must not be None (callers gate first).
    """
    if mode is None:
        mode = rnn_mode()
    assert mode in ("compiled", "interpret"), mode
    trace_counts["lstm_sequence"] += 1
    global last_path
    last_path = "pallas" if mode == "compiled" else "pallas-interpret"
    cdt = gates_x.dtype
    out, cseq = _lstm_seq(gates_x, h0.astype(cdt), c0.astype(cdt),
                          w_h2h_t, b_h2h, mode)
    return out, out[-1], cseq[-1].astype(cdt)


# ---------------------------------------------------------------------------
# launch counting (the dispatch-tower audit)
# ---------------------------------------------------------------------------
#: primitives that lower to (at least) one device kernel launch each.
#: Elementwise chains fuse into their consumers under XLA and are
#: deliberately NOT counted — this is a deterministic proxy for the
#: number of serially-issued kernels, not an exact executable census.
_LAUNCH_PRIMS = {
    "dot_general", "conv_general_dilated",
    "gather", "scatter", "scatter-add", "scatter_add", "scatter-update",
    "dynamic_slice", "dynamic_update_slice",
    "argmax", "argmin", "reduce_sum", "reduce_max", "reduce_min",
    "reduce_prod", "sort", "cumsum", "cumlogsumexp",
    "pallas_call",
}


def count_launches(jaxpr):
    """Count launch-class primitives in a (Closed)Jaxpr, recursively.

    ``scan`` multiplies its body count by the trip count (the serial
    tower a scan unrolls to at run time); ``pallas_call`` counts as ONE
    launch regardless of its inner grid — that is the whole point of a
    persistent kernel.  Deterministic and load-independent: safe to gate
    CI on.
    """
    jx = getattr(jaxpr, "jaxpr", jaxpr)
    n = 0
    for eqn in jx.eqns:
        name = eqn.primitive.name
        if name == "pallas_call":
            n += 1
            continue
        if name == "scan":
            body = eqn.params["jaxpr"]
            n += int(eqn.params.get("length", 1)) * count_launches(body)
            continue
        if name in ("while", "cond"):
            for key in ("body_jaxpr", "cond_jaxpr", "branches"):
                sub = eqn.params.get(key)
                if sub is None:
                    continue
                subs = sub if isinstance(sub, (tuple, list)) else [sub]
                n += max(count_launches(s) for s in subs)
            continue
        if name in _LAUNCH_PRIMS:
            n += 1
            continue
        # recurse through call-like primitives (pjit, custom_vjp, remat…)
        for sub in eqn.params.values():
            if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                n += count_launches(sub)
    return n


def count_pallas_calls(jaxpr):
    """Count only pallas_call launches (the per-layer-group assert)."""
    jx = getattr(jaxpr, "jaxpr", jaxpr)
    n = 0
    for eqn in jx.eqns:
        if eqn.primitive.name == "pallas_call":
            n += 1
            continue
        for sub in eqn.params.values():
            if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                n += count_pallas_calls(sub)
            elif isinstance(sub, (tuple, list)):
                for s in sub:
                    if hasattr(s, "eqns") or hasattr(s, "jaxpr"):
                        n += count_pallas_calls(s)
    return n
