"""Persistent fused-cell Pallas kernel (ops/pallas/fused_cell):
LSTM fused-vs-scan parity (fwd + grads, fp32/bf16), wavefront
interaction, bidirectional fallback, hybridized end-to-end, trace
signatures, the launch-census gate, and the bounded decode/prefill
program cache.

The CPU lane runs the kernel in Pallas interpreter mode
(MXNET_RNN_FUSED_CELL=interpret) — the identical kernel code path the
TPU compiles.
"""
import os

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import np, autograd
from mxnet_tpu.gluon import rnn
from mxnet_tpu.ops import rnn as oprnn
from mxnet_tpu.ops.pallas import fused_cell as fc

pytestmark = pytest.mark.rnn


def _rand_lstm(T, B, I, H, L=1, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    x = jax.random.normal(ks[0], (T, B, I), jnp.float32).astype(dtype)
    params = (jax.random.normal(
        ks[1], (oprnn.param_size("lstm", I, H, L),), jnp.float32)
        * 0.2).astype(dtype)
    h0 = (jax.random.normal(ks[2], (L, B, H), jnp.float32)
          * 0.3).astype(dtype)
    c0 = (jax.random.normal(ks[3], (L, B, H), jnp.float32)
          * 0.3).astype(dtype)
    return x, params, h0, c0


def _forward(x, params, h0, c0, H, L, fused):
    return oprnn.rnn_forward(x, params, h0, c0, "lstm", H, L, fused=fused)


# ---------------------------------------------------------------------------
# forward + backward parity, fused vs scan
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype,rtol,atol", [
    (jnp.float32, 1e-5, 1e-5),
    (jnp.bfloat16, 4e-2, 4e-2),   # scan computes in bf16, kernel in f32
])
def test_fused_vs_scan_forward(dtype, rtol, atol):
    T, B, I, H = 9, 3, 5, 6
    x, params, h0, c0 = _rand_lstm(T, B, I, H, dtype=dtype)
    out_s, hT_s, cT_s = _forward(x, params, h0, c0, H, 1, fused=None)
    out_f, hT_f, cT_f = _forward(x, params, h0, c0, H, 1,
                                 fused="interpret")
    assert out_f.dtype == out_s.dtype
    for a, b in ((out_f, out_s), (hT_f, hT_s), (cT_f, cT_s)):
        onp.testing.assert_allclose(
            onp.asarray(a, onp.float32), onp.asarray(b, onp.float32),
            rtol=rtol, atol=atol)


@pytest.mark.parametrize("dtype,rtol,atol", [
    (jnp.float32, 1e-4, 1e-5),
    (jnp.bfloat16, 8e-2, 8e-2),
])
def test_fused_vs_scan_gradients(dtype, rtol, atol):
    T, B, I, H = 7, 2, 4, 5
    x, params, h0, c0 = _rand_lstm(T, B, I, H, dtype=dtype, seed=1)

    def loss(fused):
        def f(x, params, h0, c0):
            out, hT, cT = _forward(x, params, h0, c0, H, 1, fused)
            o32 = out.astype(jnp.float32)
            return ((o32 * o32).sum() + 2.0 * hT.astype(jnp.float32).sum()
                    + 3.0 * cT.astype(jnp.float32).sum())
        return f

    g_s = jax.grad(loss(None), argnums=(0, 1, 2, 3))(x, params, h0, c0)
    g_f = jax.grad(loss("interpret"), argnums=(0, 1, 2, 3))(
        x, params, h0, c0)
    for a, b in zip(g_f, g_s):
        onp.testing.assert_allclose(
            onp.asarray(a, onp.float32), onp.asarray(b, onp.float32),
            rtol=rtol, atol=atol)


def test_multilayer_fused_vs_wavefront():
    """Fused path outranks the wavefront for LSTM stacks; both must
    agree (the wavefront is numerically identical to the scan)."""
    T, B, I, H, L = 8, 3, 6, 6, 3
    x, params, h0, c0 = _rand_lstm(T, B, I, H, L=L, seed=2)
    assert os.environ.get("MXNET_RNN_WAVEFRONT", "1") != "0"
    out_w, hT_w, cT_w = _forward(x, params, h0, c0, H, L, fused=None)
    out_f, hT_f, cT_f = _forward(x, params, h0, c0, H, L,
                                 fused="interpret")
    onp.testing.assert_allclose(onp.asarray(out_f), onp.asarray(out_w),
                                rtol=1e-5, atol=1e-5)
    onp.testing.assert_allclose(onp.asarray(hT_f), onp.asarray(hT_w),
                                rtol=1e-5, atol=1e-5)
    onp.testing.assert_allclose(onp.asarray(cT_f), onp.asarray(cT_w),
                                rtol=1e-5, atol=1e-5)


def test_fused_interlayer_dropout_composes():
    """Dropout between layers runs OUTSIDE the per-layer kernels; the
    fused stack under a fixed dropout key must match the scan stack
    under the same key (identical mask draws)."""
    T, B, I, H, L = 6, 2, 4, 4, 2
    x, params, h0, c0 = _rand_lstm(T, B, I, H, L=L, seed=3)
    key = jax.random.key(7)
    out_s, _, _ = oprnn.rnn_forward(x, params, h0, c0, "lstm", H, L,
                                    dropout_rate=0.5, dropout_key=key,
                                    fused=None)
    out_f, _, _ = oprnn.rnn_forward(x, params, h0, c0, "lstm", H, L,
                                    dropout_rate=0.5, dropout_key=key,
                                    fused="interpret")
    onp.testing.assert_allclose(onp.asarray(out_f), onp.asarray(out_s),
                                rtol=1e-5, atol=1e-5)


def test_bidirectional_falls_back_to_scan():
    """The reverse direction has no fused kernel: a bidirectional stack
    must produce scan-identical output and trace ONE fused kernel per
    layer (forward direction only)."""
    T, B, I, H = 6, 2, 5, 4
    ks = jax.random.split(jax.random.key(4), 4)
    x = jax.random.normal(ks[0], (T, B, I))
    n = oprnn.param_size("lstm", I, H, 1, bidirectional=True)
    params = jax.random.normal(ks[1], (n,)) * 0.2
    h0 = jax.random.normal(ks[2], (2, B, H)) * 0.3
    c0 = jax.random.normal(ks[3], (2, B, H)) * 0.3
    out_s, hT_s, cT_s = oprnn.rnn_forward(
        x, params, h0, c0, "lstm", H, 1, bidirectional=True, fused=None)
    before = fc.trace_counts["lstm_sequence"]
    out_f, hT_f, cT_f = oprnn.rnn_forward(
        x, params, h0, c0, "lstm", H, 1, bidirectional=True,
        fused="interpret")
    assert fc.trace_counts["lstm_sequence"] == before + 1  # fwd dir only
    onp.testing.assert_allclose(onp.asarray(out_f), onp.asarray(out_s),
                                rtol=1e-5, atol=1e-5)
    onp.testing.assert_allclose(onp.asarray(hT_f), onp.asarray(hT_s),
                                rtol=1e-5, atol=1e-5)


def test_gru_ignores_fused_gate():
    """GRU falls back to scan even when the gate is forced."""
    T, B, I, H = 5, 2, 4, 4
    ks = jax.random.split(jax.random.key(5), 3)
    x = jax.random.normal(ks[0], (T, B, I))
    params = jax.random.normal(
        ks[1], (oprnn.param_size("gru", I, H),)) * 0.2
    h0 = jax.random.normal(ks[2], (1, B, H)) * 0.3
    before = fc.trace_counts["lstm_sequence"]
    out_s, _, _ = oprnn.rnn_forward(x, params, h0, None, "gru", H, 1,
                                    fused=None)
    out_f, _, _ = oprnn.rnn_forward(x, params, h0, None, "gru", H, 1,
                                    fused="interpret")
    assert fc.trace_counts["lstm_sequence"] == before
    onp.testing.assert_allclose(onp.asarray(out_f), onp.asarray(out_s),
                                rtol=1e-6, atol=1e-6)


def test_hybridized_lstm_layer_end_to_end(monkeypatch):
    """gluon rnn.LSTM, hybridized: gate off vs interpret must agree in
    forward AND parameter gradients."""
    mx.random.seed(11)
    layer = rnn.LSTM(hidden_size=8, num_layers=2)
    layer.initialize()
    x = np.random.uniform(-1, 1, size=(5, 3, 4))

    def run():
        with autograd.record():
            out = layer(x)
            loss = (out * out).sum()
        loss.backward()
        return (out.asnumpy(),
                layer.h2h_weight_l0.grad().asnumpy().copy(),
                layer.i2h_weight_l1.grad().asnumpy().copy())

    monkeypatch.setenv("MXNET_RNN_FUSED_CELL", "0")
    layer.hybridize()
    ref = run()
    monkeypatch.setenv("MXNET_RNN_FUSED_CELL", "interpret")
    before = fc.trace_counts["lstm_sequence"]
    got = run()
    assert fc.trace_counts["lstm_sequence"] > before  # actually fused
    for a, b in zip(got, ref):
        onp.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_env_gate_changes_trace_signature(monkeypatch):
    """Flipping MXNET_RNN_FUSED_CELL must change the HybridBlock trace
    signature (stale-cache guard, the MXNET_FUSE_EPILOGUE precedent)."""
    layer = rnn.LSTM(hidden_size=4)
    layer.initialize()
    x = np.random.uniform(size=(3, 2, 5))
    flat = [x._data if hasattr(x, "_data") else x]
    monkeypatch.setenv("MXNET_RNN_FUSED_CELL", "0")
    sig_off = layer._signature(flat)
    monkeypatch.setenv("MXNET_RNN_FUSED_CELL", "interpret")
    sig_on = layer._signature(flat)
    assert sig_off != sig_on


def test_rnn_mode_gate_grammar(monkeypatch):
    monkeypatch.setenv("MXNET_RNN_FUSED_CELL", "0")
    assert fc.rnn_mode() is None
    monkeypatch.setenv("MXNET_RNN_FUSED_CELL", "off")
    assert fc.rnn_mode() is None
    monkeypatch.setenv("MXNET_RNN_FUSED_CELL", "interpret")
    assert fc.rnn_mode() == "interpret"
    monkeypatch.delenv("MXNET_RNN_FUSED_CELL")
    # auto on CPU: the probe gate never turns the kernel on
    if jax.default_backend() == "cpu":
        assert fc.rnn_mode() is None


# ---------------------------------------------------------------------------
# scan-unroll remainder (satellite: ops/rnn.py audit)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("unroll", [2, 4, 8])
def test_scan_unroll_remainder_parity(monkeypatch, unroll):
    """bptt 35 is not divisible by 2/4/8: the scan remainder path must
    match unroll=1 exactly (fwd and grads)."""
    T, B, I, H = 35, 2, 4, 4
    x, params, h0, c0 = _rand_lstm(T, B, I, H, seed=6)

    def run():
        def loss(x, params, h0, c0):
            out, hT, cT = oprnn.rnn_forward(x, params, h0, c0, "lstm",
                                            H, 1, fused=None)
            return (out.astype(jnp.float32) ** 2).sum()
        val = loss(x, params, h0, c0)
        grad = jax.grad(loss, argnums=1)(x, params, h0, c0)
        return onp.asarray(val), onp.asarray(grad)

    monkeypatch.setenv("MXNET_RNN_SCAN_UNROLL", "1")
    v1, g1 = run()
    monkeypatch.setenv("MXNET_RNN_SCAN_UNROLL", str(unroll))
    vu, gu = run()
    onp.testing.assert_allclose(vu, v1, rtol=1e-6, atol=1e-6)
    onp.testing.assert_allclose(gu, g1, rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# bounded decode/prefill program cache (satellite)
# ---------------------------------------------------------------------------
def test_fn_cache_lru_eviction(monkeypatch):
    from mxnet_tpu.models import decoder as dec
    cfg = dec.decoder_tiny_lm(seed=0, vocab_size=64, num_layers=2,
                              units=32, hidden_size=64, num_heads=4,
                              num_kv_heads=2, max_length=64).config
    monkeypatch.setenv("MXNET_GEN_FN_CACHE", "2")
    dec._fn_cache.clear()
    try:
        f4 = dec.make_decode_step(cfg, 4)
        f8 = dec.make_decode_step(cfg, 8)
        assert dec.make_decode_step(cfg, 8) is f8       # hit
        assert dec.fn_cache_stats()["compiles"] == 2
        dec.make_decode_step(cfg, 16)                   # evicts ps=4
        st = dec.fn_cache_stats()
        assert st == {"size": 2, "cap": 2, "compiles": 3, "evictions": 1}
        assert dec.make_decode_step(cfg, 4) is not f4   # was evicted
        assert dec.fn_cache_stats()["compiles"] == 4
    finally:
        dec._fn_cache.clear()


# ---------------------------------------------------------------------------
# launch-census tier-1 gate (CI asserts launches, not timings)
# ---------------------------------------------------------------------------
def test_lstm_launch_gate():
    T, B, I, H, L = 12, 2, 8, 8, 2
    x, params, h0, c0 = _rand_lstm(T, B, I, H, L)

    def census(fused):
        jaxpr = jax.make_jaxpr(
            lambda *a: _forward(*a, H, L, fused)[0])(x, params, h0, c0)
        return fc.count_launches(jaxpr), fc.count_pallas_calls(jaxpr)

    scan_launches, scan_pallas = census(None)
    fused_launches, fused_pallas = census("interpret")
    # fused: exactly one persistent kernel per layer, and the launch
    # census collapses vs the scan tower
    assert (scan_pallas, fused_pallas) == (0, L)
    assert fused_launches * 2 <= scan_launches
