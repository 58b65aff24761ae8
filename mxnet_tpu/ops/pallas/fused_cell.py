"""Persistent fused-cell Pallas kernels for latency-bound serial loops.

benchmark/PHASES.json adjudication: the LSTM word-LM step is
LATENCY-bound at 4% of the compute roofline — ~70 serial small-cell
iterations whose per-iteration dispatch/launch overhead, not flops or
bytes, sets the throughput band.  The scan/wavefront paths in
``ops/rnn.py`` already minimized the per-iteration *program*; what is
left is the per-iteration *launch*.  This module removes it: one kernel
invocation owns the whole serial loop.

Two persistent kernels, one pattern:

- :func:`lstm_sequence` — RNN training.  ONE ``pallas_call`` iterates
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

- :func:`decode_layer_group` — LLM decode-step inference.  One
  ``pallas_call`` per *layer group* executes, for every layer in the
  group: the qkv projections, the KV append into the paged cache
  (in-place via ``input_output_aliases`` — the pages stay donated across
  ``DecodeEngine`` steps), the paged-attention read (page tables in
  SMEM; valid-key masks built from the table like
  ``ops/pallas/paged_attention.py``'s reference builds its gather), and
  the whole attention→FFN epilogue chain (out-proj, residual LN,
  FFN with the erf-GELU the fused epilogue uses, residual LN).  The
  activations carry across layers in VMEM scratch; per-layer weights
  stream through blocked specs.  One decode step becomes one launch per
  layer group instead of a tower of per-op XLA dispatches.

Dispatch is the repo's gate grammar (flash/epilogue/paged):
``MXNET_RNN_FUSED_CELL`` / ``MXNET_DECODE_FUSED`` — ``''`` auto (the
LSTM cell on a TPU backend; the decode cell nowhere yet, see
:func:`decode_mode`), ``0``/``off`` forces the scan / per-op XLA paths,
``interpret`` forces the Pallas kernel in interpreter mode (the CPU test
lane).  LSTM is covered first; GRU/vanilla RNN and the reverse
direction of bidirectional stacks take the scan path.

:func:`count_launches` is the audit tool for the dispatch-count claims:
a deterministic, load-independent jaxpr walk counting the primitives
that lower to device kernel launches (matmuls, gathers/scatters,
reductions, pallas calls; elementwise chains fuse and are excluded).
``benchmark/steplat.py`` and the engine metrics assert on it — counts,
not timings, so no opperf-style flake risk.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kernel_mode
from .epilogue import _erf_kernel, _gelu_f32

__all__ = ["lstm_sequence", "decode_layer_group", "decode_attn_phase",
           "decode_ffn_phase", "rnn_mode", "decode_mode",
           "count_launches", "trace_counts", "last_path"]

# per-op trace counters (bench/tests assert the fused path is actually in
# the compiled program, the PR-2 epilogue convention)
trace_counts = {"lstm_sequence": 0, "decode_layer_group": 0,
                "decode_attn_phase": 0, "decode_ffn_phase": 0}
# "pallas" | "pallas-interpret" — which backend the last call latched
last_path = None


# ---------------------------------------------------------------------------
# dispatch gates (one per consumer)
# ---------------------------------------------------------------------------
def rnn_mode():
    """'compiled' | 'interpret' | None — the fused LSTM cell gate
    (``MXNET_RNN_FUSED_CELL``)."""
    return kernel_mode("MXNET_RNN_FUSED_CELL")


def decode_mode():
    """'interpret' | None — the fused decode-step gate
    (``MXNET_DECODE_FUSED``).

    The cell is not selected on a TPU backend: the v5e's compiler
    refuses it at every geometry tried (128 units with head_dim 32, and
    GPT-2-small's 768 units with head_dim 64; PR 21).  Three refusals
    were met in turn.  Two were repaired: jax 0.9.0's Mosaic lowering
    has no rule for ``lax.erf`` (the compiled cell now uses
    ``epilogue._erf_kernel``), and it rejects a ``(1, C)`` block of a
    stacked ``(Lg, C)`` vector (they ride as ``(Lg, 1, C)``).  The third
    is in the kernel's body and is what stands: "Mosaic failed to
    compile TPU kernel: infer-vector-layout: unsupported shape cast" on
    the head split, ``tpu.reshape (vector<8x768xf32>) ->
    vector<8x12x1x64xf32>``.  What lies behind it was never reached; by
    arithmetic alone (not the compiler's word) the kernel's VMEM plan at
    GPT-2-small width with 8 slots x 1024 context, one layer's whole K
    and V pool in and out beside that layer's fp32 weights,
    double-buffered, is about 246 MiB against a 16 MiB scoped limit.
    The engine runs the per-op step there; ``interpret`` keeps the cell
    as the CPU oracle until ROADMAP S1/D4 decide its future."""
    mode = kernel_mode("MXNET_DECODE_FUSED")
    return mode if mode == "interpret" else None


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
# persistent decode-step kernel (one launch per layer group)
# ---------------------------------------------------------------------------
def _erf_for(mode):
    """The erf the per-op step's bias_gelu uses on the same lane, so the
    cell stays bit-comparable with it: ``lax.erf`` under the interpreter
    (the CPU oracle), the in-kernel rational where Mosaic compiles the
    cell, which has no rule for ``lax.erf`` (``epilogue._erf_kernel``)."""
    return _erf_kernel if mode == "compiled" else jax.lax.erf


def _ln_f32(x, gamma, beta, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * gamma + beta


def _decode_group_kernel(x_ref, kp_ref, vp_ref,
                         wq_ref, bq_ref, wk_ref, bk_ref, wv_ref, bv_ref,
                         wo_ref, bo_ref, w1_ref, b1_ref, w2_ref, b2_ref,
                         ln1g_ref, ln1b_ref, ln2g_ref, ln2b_ref,
                         meta_ref, pt_ref, len_ref,
                         kp_out, vp_out, x_out,
                         x_scr, *, cfg_tuple, erf):
    """One grid step = one decoder layer.  The activation carries in
    VMEM scratch; this layer's weights and page slab stream in via
    blocked specs; meta (wp/ws rows) sits in SMEM for the scalar page
    indices, the page table and lengths in VMEM for the vectorized key
    mask."""
    (B, H, KVH, D, C, S, P, pps) = cfg_tuple
    li = pl.program_id(0)
    g = H // KVH
    scale = 1.0 / (D ** 0.5)

    @pl.when(li == 0)
    def _():
        x_scr[...] = x_ref[...].astype(jnp.float32)

    # pages move whole-slab per layer; carry forward before mutating
    kp_out[...] = kp_ref[...]
    vp_out[...] = vp_ref[...]

    x = x_scr[...]                                     # (B, C) f32
    q = (jnp.dot(x, wq_ref[0].astype(jnp.float32).T,
                 preferred_element_type=jnp.float32)
         + bq_ref[0].astype(jnp.float32)).reshape(B, KVH, g, D)
    k = (jnp.dot(x, wk_ref[0].astype(jnp.float32).T,
                 preferred_element_type=jnp.float32)
         + bk_ref[0].astype(jnp.float32)).reshape(B, KVH, D)
    v = (jnp.dot(x, wv_ref[0].astype(jnp.float32).T,
                 preferred_element_type=jnp.float32)
         + bv_ref[0].astype(jnp.float32)).reshape(B, KVH, D)

    # KV append: scatter this step's k/v into the paged cache (scalar
    # page/slot indices from SMEM; inactive slots target the scratch
    # page 0 by construction)
    for b in range(B):
        wp_b = meta_ref[0, b]
        ws_b = meta_ref[1, b]
        kp_out[0, :, wp_b, ws_b, :] = k[b].astype(kp_out.dtype)
        vp_out[0, :, wp_b, ws_b, :] = v[b].astype(vp_out.dtype)

    # paged-attention read over the whole pool with a per-sequence
    # valid-key mask built from the page table (same masking contract as
    # paged_attention_reference: length-0 rows produce zeros)
    k_all = kp_out[0].astype(jnp.float32).reshape(KVH, P * S, D)
    v_all = vp_out[0].astype(jnp.float32).reshape(KVH, P * S, D)
    slot_page = jax.lax.broadcasted_iota(jnp.int32, (1, P * S), 1) // S
    slot_in = jax.lax.broadcasted_iota(jnp.int32, (1, P * S), 1) % S
    lengths = len_ref[...]                               # (B, 1)
    mask = jnp.zeros((B, P * S), jnp.bool_)
    for j in range(pps):
        pt_j = pt_ref[:, j].reshape(B, 1)                # page id per seq
        hit = (slot_page == pt_j) & (slot_in + j * S < lengths)
        mask = mask | hit
    # logits: (B,KVH,g,D) x (KVH,N,D) -> (B,KVH,g,N)
    logits = jax.lax.dot_general(
        q * scale, k_all,
        dimension_numbers=(((3,), (2,)), ((1,), (0,))),
        preferred_element_type=jnp.float32)              # (KVH,B,g,N)
    logits = jnp.where(mask[None, :, None, :], logits, -jnp.inf)
    m = jnp.max(logits, axis=-1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)               # length-0 rows
    p = jnp.exp(logits - m)
    p = jnp.where(mask[None, :, None, :], p, 0.0)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.where(denom == 0.0, 1.0, denom)
    att = jax.lax.dot_general(
        p, v_all, dimension_numbers=(((3,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)              # (KVH,B,g,D)
    merged = jnp.transpose(att, (1, 0, 2, 3)).reshape(B, C)

    # attention -> FFN epilogue chain (post-LN, erf GELU — the same math
    # as models/decoder._layer_tail + the fused bias_gelu epilogue)
    o = (jnp.dot(merged, wo_ref[0].astype(jnp.float32).T,
                 preferred_element_type=jnp.float32)
         + bo_ref[0].astype(jnp.float32))
    x = _ln_f32(x + o, ln1g_ref[0].astype(jnp.float32),
                ln1b_ref[0].astype(jnp.float32))
    h1 = _gelu_f32(jnp.dot(x, w1_ref[0].astype(jnp.float32).T,
                           preferred_element_type=jnp.float32)
                   + b1_ref[0].astype(jnp.float32), erf)
    f = (jnp.dot(h1, w2_ref[0].astype(jnp.float32).T,
                 preferred_element_type=jnp.float32)
         + b2_ref[0].astype(jnp.float32))
    x = _ln_f32(x + f, ln2g_ref[0].astype(jnp.float32),
                ln2b_ref[0].astype(jnp.float32))
    x_scr[...] = x

    @pl.when(li == pl.num_programs(0) - 1)
    def _():
        x_out[...] = x.astype(x_out.dtype)


def decode_layer_group(x, kp, vp, stacked, meta, page_tables, lengths,
                       cfg, mode):
    """Run ``Lg`` decoder layers as ONE persistent kernel launch.

    x:           (B, C) activations entering the group
    kp/vp:       (Lg, KVH, P, S, D) this group's page slabs (updated
                 in place via input_output_aliases)
    stacked:     dict of per-layer weights stacked on a leading Lg axis
                 (wq,bq,wk,bk,wv,bv,wo,bo,w1,b1,w2,b2,ln1g,ln1b,ln2g,ln2b)
    meta:        (2, B) int32 — rows: write page, write slot (SMEM)
    page_tables: (B, pages_per_seq) int32
    lengths:     (B, 1) int32 valid context lengths (0 = inactive slot)
    cfg:         DecoderConfig (units/heads geometry)

    Returns (kp, vp, x_out).
    """
    trace_counts["decode_layer_group"] += 1
    global last_path
    last_path = "pallas" if mode == "compiled" else "pallas-interpret"
    Lg, KVH, P, S, D = kp.shape
    B, C = x.shape
    H = cfg.num_heads
    pps = page_tables.shape[1]
    cfg_tuple = (B, H, KVH, D, C, S, P, pps)

    def layer_spec(a):
        shp = a.shape[1:]
        return pl.BlockSpec((1,) + shp,
                            lambda l, nd=len(shp): (l,) + (0,) * nd)

    worder = ("wq", "bq", "wk", "bk", "wv", "bv", "wo", "bo",
              "w1", "b1", "w2", "b2", "ln1g", "ln1b", "ln2g", "ln2b")
    # a stacked per-layer vector rides as (Lg, 1, C): Mosaic refuses a
    # (1, C) block of an (Lg, C) array (second-to-last block dim neither
    # a multiple of 8 nor the array's; v5e, PR 21)
    w_arrays = [stacked[k][:, None] if stacked[k].ndim == 2 else stacked[k]
                for k in worder]
    page_spec = pl.BlockSpec((1, KVH, P, S, D),
                             lambda l: (l, 0, 0, 0, 0))
    in_specs = ([pl.BlockSpec((B, C), lambda l: (0, 0)),
                 page_spec, page_spec]
                + [layer_spec(a) for a in w_arrays]
                + [pl.BlockSpec(memory_space=pltpu.SMEM),
                   pl.BlockSpec((B, pps), lambda l: (0, 0)),
                   pl.BlockSpec((B, 1), lambda l: (0, 0))])
    kernel = functools.partial(_decode_group_kernel, cfg_tuple=cfg_tuple,
                               erf=_erf_for(mode))
    kp2, vp2, x_out = pl.pallas_call(
        kernel,
        grid=(Lg,),
        in_specs=in_specs,
        out_specs=[page_spec, page_spec,
                   pl.BlockSpec((B, C), lambda l: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct(kp.shape, kp.dtype),
                   jax.ShapeDtypeStruct(vp.shape, vp.dtype),
                   jax.ShapeDtypeStruct((B, C), x.dtype)],
        scratch_shapes=[pltpu.VMEM((B, C), jnp.float32)],
        input_output_aliases={1: 0, 2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=(mode == "interpret"),
    )(x, kp, vp, *w_arrays, meta, page_tables, lengths)
    return kp2, vp2, x_out


# ---------------------------------------------------------------------------
# tensor-parallel phase kernels (the persistent decode step under tp)
# ---------------------------------------------------------------------------
# A Pallas body cannot carry a cross-chip collective, so under tensor
# parallelism the layer-group fusion splits at the two reduce points of
# a Megatron layer: an ATTENTION phase (qkv + KV append + paged read +
# local out-proj partial — everything left of the first all-reduce) and
# an FFN phase (ffn1 + erf GELU + local ffn2 partial — everything left
# of the second).  The caller (models/decoder) psums between them; the
# residual-LN glue runs in XLA where it fuses into the reduce epilogue.

def _decode_attn_phase_kernel(x_ref, kp_ref, vp_ref,
                              wq_ref, bq_ref, wk_ref, bk_ref,
                              wv_ref, bv_ref, wo_ref,
                              meta_ref, pt_ref, len_ref,
                              kp_out, vp_out, o_out, *, cfg_tuple):
    """One LOCAL layer shard: qkv over the shard's heads, KV append into
    the shard's page slab, paged-attention read, and the out-proj
    PARTIAL product (no bias — the bias is replicated and must be added
    after the tp all-reduce).  Same math as the first half of
    ``_decode_group_kernel`` with H/KVH the per-shard counts."""
    (B, H, KVH, D, C, S, P, pps) = cfg_tuple
    g = H // KVH
    scale = 1.0 / (D ** 0.5)

    kp_out[...] = kp_ref[...]
    vp_out[...] = vp_ref[...]

    x = x_ref[...].astype(jnp.float32)                 # (B, C) replicated
    q = (jnp.dot(x, wq_ref[...].astype(jnp.float32).T,
                 preferred_element_type=jnp.float32)
         + bq_ref[...].astype(jnp.float32)).reshape(B, KVH, g, D)
    k = (jnp.dot(x, wk_ref[...].astype(jnp.float32).T,
                 preferred_element_type=jnp.float32)
         + bk_ref[...].astype(jnp.float32)).reshape(B, KVH, D)
    v = (jnp.dot(x, wv_ref[...].astype(jnp.float32).T,
                 preferred_element_type=jnp.float32)
         + bv_ref[...].astype(jnp.float32)).reshape(B, KVH, D)

    for b in range(B):
        wp_b = meta_ref[0, b]
        ws_b = meta_ref[1, b]
        kp_out[:, wp_b, ws_b, :] = k[b].astype(kp_out.dtype)
        vp_out[:, wp_b, ws_b, :] = v[b].astype(vp_out.dtype)

    k_all = kp_out[...].astype(jnp.float32).reshape(KVH, P * S, D)
    v_all = vp_out[...].astype(jnp.float32).reshape(KVH, P * S, D)
    slot_page = jax.lax.broadcasted_iota(jnp.int32, (1, P * S), 1) // S
    slot_in = jax.lax.broadcasted_iota(jnp.int32, (1, P * S), 1) % S
    lengths = len_ref[...]                               # (B, 1)
    mask = jnp.zeros((B, P * S), jnp.bool_)
    for j in range(pps):
        pt_j = pt_ref[:, j].reshape(B, 1)
        hit = (slot_page == pt_j) & (slot_in + j * S < lengths)
        mask = mask | hit
    logits = jax.lax.dot_general(
        q * scale, k_all,
        dimension_numbers=(((3,), (2,)), ((1,), (0,))),
        preferred_element_type=jnp.float32)              # (KVH,B,g,N)
    logits = jnp.where(mask[None, :, None, :], logits, -jnp.inf)
    m = jnp.max(logits, axis=-1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(logits - m)
    p = jnp.where(mask[None, :, None, :], p, 0.0)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.where(denom == 0.0, 1.0, denom)
    att = jax.lax.dot_general(
        p, v_all, dimension_numbers=(((3,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)              # (KVH,B,g,D)
    merged = jnp.transpose(att, (1, 0, 2, 3)).reshape(B, H * D)
    o_out[...] = jnp.dot(merged, wo_ref[...].astype(jnp.float32).T,
                         preferred_element_type=jnp.float32)


def decode_attn_phase(x, kp, vp, lp, meta, page_tables, lengths, cfg,
                      mode):
    """Attention phase of one tensor-parallel decode layer: ONE launch
    per layer per shard, run INSIDE shard_map on per-shard operands.

    x:           (B, C) activations — C is the FULL model width
                 (replicated; the tail all-reduce restores it)
    kp/vp:       (KVH_local, P, S, D) this layer's LOCAL page slab
                 (updated in place via input_output_aliases)
    lp:          this layer's per-shard params (wq…wo used here)
    meta:        (2, B) int32 write page/slot rows (SMEM)
    page_tables: (B, pages_per_seq) int32
    lengths:     (B, 1) int32
    cfg:         the LOCAL DecoderConfig (per-shard head counts)

    Returns (kp, vp, o_partial (B, C) f32) — o_partial is the
    un-reduced, bias-less out-proj contribution of this shard.
    """
    trace_counts["decode_attn_phase"] += 1
    global last_path
    last_path = "pallas" if mode == "compiled" else "pallas-interpret"
    KVH, P, S, D = kp.shape
    B, C = x.shape
    pps = page_tables.shape[1]
    cfg_tuple = (B, cfg.num_heads, KVH, D, C, S, P, pps)
    kernel = functools.partial(_decode_attn_phase_kernel,
                               cfg_tuple=cfg_tuple)
    w_arrays = [lp[k] for k in ("wq", "bq", "wk", "bk", "wv", "bv", "wo")]
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    in_specs = ([vmem, vmem, vmem]
                + [vmem] * len(w_arrays)
                + [pl.BlockSpec(memory_space=pltpu.SMEM), vmem, vmem])
    kp2, vp2, o_part = pl.pallas_call(
        kernel,
        in_specs=in_specs,
        out_specs=[vmem, vmem, vmem],
        out_shape=[jax.ShapeDtypeStruct(kp.shape, kp.dtype),
                   jax.ShapeDtypeStruct(vp.shape, vp.dtype),
                   jax.ShapeDtypeStruct((B, C), jnp.float32)],
        input_output_aliases={1: 0, 2: 1},
        interpret=(mode == "interpret"),
    )(x, kp, vp, *w_arrays, meta, page_tables, lengths)
    return kp2, vp2, o_part


def _decode_ffn_phase_kernel(x_ref, w1_ref, b1_ref, w2_ref, f_out, *, erf):
    x = x_ref[...].astype(jnp.float32)
    h = _gelu_f32(jnp.dot(x, w1_ref[...].astype(jnp.float32).T,
                          preferred_element_type=jnp.float32)
                  + b1_ref[...].astype(jnp.float32), erf)
    f_out[...] = jnp.dot(h, w2_ref[...].astype(jnp.float32).T,
                         preferred_element_type=jnp.float32)


def decode_ffn_phase(x, w1, b1, w2, mode):
    """FFN phase of one tensor-parallel decode layer: ffn1 (column
    shard) + erf GELU + ffn2 PARTIAL (row shard, no bias) fused into one
    launch.  Returns the un-reduced (B, C) f32 contribution; the caller
    psums and adds the replicated b2."""
    trace_counts["decode_ffn_phase"] += 1
    global last_path
    last_path = "pallas" if mode == "compiled" else "pallas-interpret"
    B, C = x.shape
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    f_out = pl.pallas_call(
        functools.partial(_decode_ffn_phase_kernel, erf=_erf_for(mode)),
        in_specs=[vmem, vmem, vmem, vmem],
        out_specs=vmem,
        out_shape=jax.ShapeDtypeStruct((B, C), jnp.float32),
        interpret=(mode == "interpret"),
    )(x, w1, b1, w2)
    return f_out


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


def count_fn_launches(fn, *args, **kwargs):
    """Trace ``fn`` (un-jitted or jitted) and count its launches."""
    return count_launches(jax.make_jaxpr(fn)(*args, **kwargs))


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
