"""On-chip test lane (`python -m pytest -m tpu`).

Runs against the real TPU backend when one is present; every test skips
with a reason on CPU.  This is the backend-consistency half of the
reference's test strategy (SURVEY §4: the reference runs the same op suite
against CPU and GPU backends); here the pairs are (XLA reference path,
Pallas kernel) and (f32, bf16) on the actual chip.

What round-2's audit proved this lane is for: a Pallas kernel can compile
in CPU interpret mode yet be unreachable or broken on the real platform.
These tests fail loudly in that case — `test_flash_dispatch_uses_pallas`
asserts the dispatcher took the kernel path (no silent fallback), and the
grad test differentiates through the kernel's custom VJP on-chip.
"""
import numpy as onp
import pytest

pytestmark = pytest.mark.tpu

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

if jax.default_backend() == "cpu":
    pytest.skip("no TPU backend present (CPU only); on-chip lane skipped",
                allow_module_level=True)


def _rand(shape, dtype="float32", seed=0):
    return onp.random.RandomState(seed).randn(*shape).astype(dtype)


def test_flash_kernel_numerics_on_chip():
    from mxnet_tpu.ops.attention import attention_reference
    from mxnet_tpu.ops.pallas.flash_attention import flash_attention_tpu
    B, H, L, D = 2, 4, 512, 64
    q, k, v = (jnp.asarray(_rand((B, H, L, D), seed=s)) for s in range(3))
    for causal, window in [(False, None), (True, None), (True, 64)]:
        out = flash_attention_tpu(q, k, v, causal=causal, window=window)
        ref = attention_reference(q, k, v, causal=causal, window=window)
        # chip matmuls run at default (bf16-pass) precision: loose atol
        onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                    rtol=2e-2, atol=2e-2)


def test_flash_dispatch_uses_pallas():
    from mxnet_tpu.ops import attention
    B, H, L, D = 1, 2, 256, 64
    q, k, v = (jnp.asarray(_rand((B, H, L, D), seed=s)) for s in range(3))
    attention.last_path = None
    attention.flash_attention(q, k, v, causal=True)
    assert attention.last_path == "pallas", (
        f"dispatcher fell back to {attention.last_path!r} on a TPU backend")


def test_flash_grad_through_custom_vjp_on_chip():
    from mxnet_tpu.ops import attention
    from mxnet_tpu.ops.attention import attention_reference
    B, H, L, D = 1, 2, 256, 64
    q, k, v = (jnp.asarray(_rand((B, H, L, D), seed=s)) for s in range(3))

    def loss_fa(q, k, v):
        return (attention.flash_attention(q, k, v, causal=True) ** 2).sum()

    def loss_ref(q, k, v):
        return (attention_reference(q, k, v, causal=True) ** 2).sum()

    attention.last_path = None
    g1 = jax.grad(loss_fa, argnums=(0, 1, 2))(q, k, v)
    assert attention.last_path == "pallas"
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=5e-2, atol=5e-2)


def test_flash_long_context_bounded_memory():
    """L=4096 causal attention runs on-chip — the O(L^2) score matrix
    (64 heads x 4096^2 f32 = 4 GiB) would not fit VMEM-resident paths."""
    from mxnet_tpu.ops.pallas.flash_attention import flash_attention_tpu
    B, H, L, D = 2, 8, 4096, 64
    q, k, v = (jnp.asarray(_rand((B, H, L, D), seed=s), dtype=jnp.bfloat16)
               for s in range(3))
    out = flash_attention_tpu(q, k, v, causal=True)
    assert out.shape == (B, H, L, D)
    assert bool(jnp.isfinite(out.astype(jnp.float32)).all())


def test_bf16_parity_dense_on_chip():
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import nn
    net = nn.HybridSequential()
    net.add(nn.Dense(128, activation="relu"), nn.Dense(16))
    net.initialize()
    net.hybridize()
    x32 = mx.np.array(_rand((8, 64)))
    y32 = net(x32).asnumpy()
    y16 = onp.asarray(
        jnp.asarray(net(x32.astype("bfloat16")).asnumpy()).astype(jnp.float32))
    onp.testing.assert_allclose(y16, y32, rtol=5e-2, atol=5e-2)


def test_donation_on_chip():
    """jit with donate_argnums reuses the input buffer for the output on a
    real device (train-step update pattern: params donated to next params)."""
    @jax.jit
    def probe(x):
        return x + 1.0

    upd = jax.jit(lambda x: x * 2.0, donate_argnums=(0,))
    x = jnp.ones((1024, 1024))
    y = upd(x)
    assert float(y[0, 0]) == 2.0
    assert x.is_deleted()


def test_hybridized_train_step_on_chip():
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon import nn
    net = nn.HybridSequential()
    net.add(nn.Dense(64, activation="relu"), nn.Dense(10))
    net.initialize()
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    x = mx.np.array(_rand((32, 28)))
    y = mx.np.array(onp.arange(32) % 10)
    losses = []
    for _ in range(5):
        with autograd.record():
            loss = loss_fn(net(x), y).mean()
        loss.backward()
        trainer.step(1)
        losses.append(float(loss.asnumpy()))
    assert losses[-1] < losses[0], losses


# ---------------------------------------------------------------------------
# round-4 widening (VERDICT r3 #10): flash dropout/kvlen/window sweep,
# int8 MXU, bf16 BatchNorm, bulking dispatch counts, optimizer kernels
# ---------------------------------------------------------------------------
def test_flash_kv_length_on_chip():
    from mxnet_tpu.ops.attention import attention_reference
    from mxnet_tpu.ops.pallas.flash_attention import flash_attention_tpu
    B, H, L, D = 2, 4, 512, 64
    q, k, v = (jnp.asarray(_rand((B, H, L, D), seed=s)) for s in range(3))
    kv = jnp.asarray([200, 512], jnp.int32)
    out = flash_attention_tpu(q, k, v, kv_length=kv)
    ref = attention_reference(q, k, v, kv_length=kv)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=2e-2, atol=2e-2)
    assert bool(jnp.isfinite(out).all())


def test_flash_kv_length_grads_on_chip():
    from mxnet_tpu.ops.attention import attention_reference
    from mxnet_tpu.ops.pallas.flash_attention import flash_attention_tpu
    B, H, L, D = 1, 2, 256, 64
    q, k, v = (jnp.asarray(_rand((B, H, L, D), seed=s)) for s in range(3))
    kv = jnp.asarray([100], jnp.int32)
    g1 = jax.grad(lambda *a: (flash_attention_tpu(
        *a, causal=True, kv_length=kv) ** 2).sum(), (0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: (attention_reference(
        *a, causal=True, kv_length=kv) ** 2).sum(), (0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        assert bool(jnp.isfinite(a).all())
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=5e-2, atol=5e-2)


def test_flash_dropout_matches_hash_oracle_on_chip():
    from mxnet_tpu.ops.pallas.flash_attention import (flash_attention_tpu,
                                                      hash_keep_bits)
    B, H, L, D = 2, 2, 256, 64
    rate = 0.1
    seed = jnp.asarray([99], jnp.uint32)
    q, k, v = (jnp.asarray(_rand((B, H, L, D), seed=s)) for s in range(3))

    def oracle(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q / onp.sqrt(D), k)
        p = jax.nn.softmax(s, -1)
        gi = jnp.broadcast_to(jnp.arange(L)[:, None], (L, L))
        gj = jnp.broadcast_to(jnp.arange(L)[None, :], (L, L))
        bits = jax.vmap(lambda b: hash_keep_bits(seed[0], b, gi, gj))(
            jnp.arange(B * H))
        thr = jnp.uint32(int(round(rate * 2 ** 32)))
        keep = (bits >= thr).astype(jnp.float32).reshape(B, H, L, L)
        return jnp.einsum("bhqk,bhkd->bhqd", p * keep / (1 - rate), v)

    out = flash_attention_tpu(q, k, v, dropout=rate, seed=seed)
    ref = oracle(q, k, v)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=2e-2, atol=2e-2)


def test_flash_dropout_grads_finite_and_seeded_on_chip():
    from mxnet_tpu.ops.pallas.flash_attention import flash_attention_tpu
    B, H, L, D = 1, 2, 256, 64
    q, k, v = (jnp.asarray(_rand((B, H, L, D), seed=s)) for s in range(3))
    s1 = jnp.asarray([1], jnp.uint32)
    s2 = jnp.asarray([2], jnp.uint32)
    g = jax.grad(lambda *a: (flash_attention_tpu(
        *a, dropout=0.2, seed=s1) ** 2).sum(), (0, 1, 2))(q, k, v)
    for a in g:
        assert bool(jnp.isfinite(a).all())
    # determinism: same seed same output; different seed different mask
    o1 = flash_attention_tpu(q, k, v, dropout=0.2, seed=s1)
    o1b = flash_attention_tpu(q, k, v, dropout=0.2, seed=s1)
    o2 = flash_attention_tpu(q, k, v, dropout=0.2, seed=s2)
    onp.testing.assert_array_equal(onp.asarray(o1), onp.asarray(o1b))
    assert float(jnp.max(jnp.abs(o1 - o2))) > 1e-3


@pytest.mark.parametrize("window", [16, 128])
def test_flash_window_sweep_on_chip(window):
    from mxnet_tpu.ops.attention import attention_reference
    from mxnet_tpu.ops.pallas.flash_attention import flash_attention_tpu
    B, H, L, D = 1, 2, 512, 64
    q, k, v = (jnp.asarray(_rand((B, H, L, D), seed=s)) for s in range(3))
    out = flash_attention_tpu(q, k, v, causal=True, window=window)
    ref = attention_reference(q, k, v, causal=True, window=window)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=2e-2, atol=2e-2)


def test_flash_bf16_on_chip():
    from mxnet_tpu.ops.attention import attention_reference
    from mxnet_tpu.ops.pallas.flash_attention import flash_attention_tpu
    B, H, L, D = 2, 4, 512, 64
    q, k, v = (jnp.asarray(_rand((B, H, L, D), seed=s), jnp.bfloat16)
               for s in range(3))
    out = flash_attention_tpu(q, k, v, causal=True).astype(jnp.float32)
    ref = attention_reference(q, k, v, causal=True).astype(jnp.float32)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=8e-2, atol=8e-2)


def test_int8_mxu_matmul_numerics_on_chip():
    """int8 x int8 -> int32 accumulation on the MXU must be EXACT for
    integer inputs (the quantized-dense core, quantized_fully_connected
    parity)."""
    rng = onp.random.RandomState(0)
    a = rng.randint(-127, 128, (64, 256)).astype(onp.int8)
    b = rng.randint(-127, 128, (128, 256)).astype(onp.int8)
    acc = jax.lax.dot_general(jnp.asarray(a), jnp.asarray(b),
                              (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.int32)
    ref = a.astype(onp.int64) @ b.astype(onp.int64).T
    assert acc.dtype == jnp.int32
    onp.testing.assert_array_equal(onp.asarray(acc), ref.astype(onp.int32))


def test_quantized_dense_on_chip():
    import mxnet_tpu as mx
    from mxnet_tpu.contrib.quantization import QuantizedDense
    from mxnet_tpu.gluon import nn
    mx.random.seed(0)
    dense = nn.Dense(32, in_units=64)
    dense.initialize()
    x = mx.np.array(_rand((8, 64)) * 0.5)
    ref = dense(x).asnumpy()
    q = QuantizedDense(dense, float(x.min().asnumpy()),
                       float(x.max().asnumpy()))
    got = q(x).asnumpy()
    # int8 quantization error bound, not numerical noise
    assert onp.abs(got - ref).max() < 0.1
    assert onp.corrcoef(got.ravel(), ref.ravel())[0, 1] > 0.999


def test_bf16_batchnorm_on_chip():
    import mxnet_tpu as mx
    from mxnet_tpu import autograd
    from mxnet_tpu.gluon import nn
    mx.random.seed(0)
    bn32 = nn.BatchNorm(in_channels=16)
    bn32.initialize()
    x = mx.np.array(_rand((8, 16, 6, 6)))
    with autograd.record(train_mode=True):
        y32 = bn32(x)
    bn16 = nn.BatchNorm(in_channels=16)
    bn16.initialize()
    bn16.cast("bfloat16")
    with autograd.record(train_mode=True):
        y16 = bn16(x.astype("bfloat16"))
    onp.testing.assert_allclose(
        onp.asarray(jnp.asarray(y16.asnumpy()).astype(jnp.float32)),
        y32.asnumpy(), rtol=5e-2, atol=5e-2)
    # running stats updated in both dtypes
    assert float(onp.abs(bn32.running_var.data().asnumpy() - 1).max()) > 1e-4
    assert float(onp.abs(bn16.running_var.data().asnumpy()
                         .astype(onp.float32) - 1).max()) > 1e-4


def test_bulking_steady_state_dispatch_counts_on_chip():
    """The eager-bulking contract on the real chip: after warmup, an
    imperative train step costs a handful of flushes and ZERO compiles
    (VERDICT r3 weak #8: the bulking path had no on-chip assertions)."""
    import mxnet_tpu as mx
    from mxnet_tpu import _bulk, autograd, gluon
    from mxnet_tpu.gluon import nn
    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(64, activation="relu"), nn.Dense(10))
    net.initialize()
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05, "aggregate_num": 100})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    x = mx.np.array(_rand((16, 32)))
    y = mx.np.array(onp.arange(16) % 10)

    def step():
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(16)
        return loss

    for _ in range(4):
        loss = step()
    float(loss.mean())
    s0 = _bulk.stats()
    for _ in range(3):
        loss = step()
    float(loss.mean())
    s1 = _bulk.stats()
    assert s1["compiles"] - s0["compiles"] == 0, "steady state recompiled"
    assert s1["eager_fallbacks"] - s0["eager_fallbacks"] == 0
    assert (s1["flushes"] - s0["flushes"]) <= 12  # a handful per step


def test_deferred_vjp_backward_matches_jax_grad_on_chip():
    import mxnet_tpu as mx
    from mxnet_tpu import autograd
    xv = _rand((8, 8), seed=3)
    x = mx.np.array(xv)
    x.attach_grad()
    with autograd.record():
        loss = ((x @ x).tanh() ** 2).sum()
    loss.backward()
    ref = jax.grad(lambda a: (jnp.tanh(a @ a) ** 2).sum())(jnp.asarray(xv))
    onp.testing.assert_allclose(x.grad.asnumpy(), onp.asarray(ref),
                                rtol=2e-2, atol=2e-2)


def test_fused_multi_sgd_on_chip():
    from mxnet_tpu.ops.optimizer_ops import multi_sgd_mom_update
    ws = [jnp.asarray(_rand((64, 64), seed=i)) for i in range(4)]
    gs = [jnp.asarray(_rand((64, 64), seed=10 + i)) for i in range(4)]
    ms = [jnp.zeros((64, 64)) for _ in range(4)]
    out = multi_sgd_mom_update(ws, gs, ms, lrs=[0.1] * 4, momentum=0.9,
                               wds=[0.0] * 4)
    new_ws = out[0] if isinstance(out, tuple) else out
    for w0, g, w1 in zip(ws, gs, new_ws):
        onp.testing.assert_allclose(onp.asarray(w1),
                                    onp.asarray(w0) - 0.1 * onp.asarray(g),
                                    rtol=2e-2, atol=2e-4)


def test_adam_update_on_chip():
    from mxnet_tpu.ops.optimizer_ops import adam_update
    w = jnp.asarray(_rand((128,), seed=0))
    g = jnp.asarray(_rand((128,), seed=1))
    mean = jnp.zeros(128)
    var = jnp.zeros(128)
    out = adam_update(w, g, mean, var, lr=1e-3)
    w1 = out[0] if isinstance(out, (tuple, list)) else out
    assert bool(jnp.isfinite(w1).all())
    assert float(jnp.max(jnp.abs(w1 - w))) > 0  # moved


def test_lstm_fused_scan_on_chip():
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import rnn
    mx.random.seed(0)
    lstm = rnn.LSTM(32, num_layers=2, layout="NTC", input_size=16)
    lstm.initialize()
    x = mx.np.array(_rand((4, 12, 16)))
    out = lstm(x)
    assert out.shape == (4, 12, 32)
    assert onp.isfinite(out.asnumpy()).all()


def test_all_finite_on_chip():
    from mxnet_tpu import npx
    import mxnet_tpu as mx
    good = mx.np.array(_rand((64,)))
    bad = mx.np.array(onp.array([1.0, onp.inf], onp.float32))
    assert bool(npx.all_finite(good).asnumpy())
    assert not bool(npx.all_finite(good, bad).asnumpy())


def test_embedding_take_on_chip():
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import nn
    mx.random.seed(0)
    emb = nn.Embedding(100, 16)
    emb.initialize()
    tok = mx.np.array(onp.array([[1, 5, 99], [0, 2, 3]], onp.int32))
    out = emb(tok)
    w = emb.weight.data().asnumpy()
    onp.testing.assert_allclose(out.asnumpy(), w[tok.asnumpy()], rtol=1e-6)


def test_large_reduction_f32_accuracy_on_chip():
    """Big f32 sum must accumulate in f32 (not bf16) on the chip."""
    x = jnp.full((1 << 20,), 1.0e-3, jnp.float32)
    s = float(jnp.sum(x))
    assert abs(s - 1048.576) / 1048.576 < 1e-3, s


def test_device_memory_census_on_chip():
    from mxnet_tpu import profiler
    st0 = profiler.device_memory_stats()
    big = jnp.ones((2048, 2048), jnp.float32)  # 16 MB
    jax.block_until_ready(big)
    st1 = profiler.device_memory_stats()
    assert st1["bytes_in_use"] >= st0["bytes_in_use"] + (8 << 20)
    spec = profiler.chip_spec()
    assert spec["hbm_bytes"] and spec["peak_flops_bf16"]
    del big


# ---------------------------------------------------------------------------
# serving and RNN kernels (PR 21): each compiled (interpret=False) at a real
# width and compared with its XLA reference.  Tolerances are loose because
# the chip's fp32 matmuls run bf16 passes at default precision on the XLA
# side while Mosaic's in-kernel fp32 dots do not.
# ---------------------------------------------------------------------------
_PAGED_HD64 = (
    "jax's paged-attention kernel at head_dim=64 (v5e, jax 0.9.0, PR 21): "
    "ValueError: The Pallas TPU lowering currently requires that the last "
    "two dimensions of your block shape are divisible by 8 and 128 "
    "respectively, or be equal to the respective dimensions of the overall "
    "array. Block spec for outputs[1] in pallas_call "
    "paged_flash_attention_kernel_inline_seq_dim has block shape "
    "(Squeezed(), Blocked(block_size=1), Squeezed(), Blocked(block_size="
    "64)), array shape (8, 12, 1, 1)")


@pytest.mark.parametrize("head_dim", [
    pytest.param(64, marks=pytest.mark.xfail(strict=True,
                                             reason=_PAGED_HD64)),
    128])
def test_paged_attention_kernel_on_chip(head_dim):
    """jax's paged-attention kernel at GPT-2-small's cache geometry
    (12 heads, 8 slots x 64 pages of 16) against the gather reference,
    called inside a jit the way the decode step calls it."""
    import functools
    from jax.experimental.pallas.ops.tpu.paged_attention import (
        paged_attention as kernel)
    from mxnet_tpu.ops.pallas import paged_attention as paged
    rs = onp.random.RandomState(0)
    B, H, S, pps = 8, 12, 16, 64
    P = B * pps + 1
    q = jnp.asarray(rs.randn(B, H, head_dim).astype("float32"))
    kp = jnp.asarray(rs.randn(H, P, S, head_dim).astype("float32"))
    vp = jnp.asarray(rs.randn(H, P, S, head_dim).astype("float32"))
    lengths = jnp.asarray(rs.randint(1, pps * S, size=B), jnp.int32)
    tables = jnp.asarray(
        rs.permutation(onp.arange(1, P)).reshape(B, pps), jnp.int32)
    ref = paged.paged_attention_reference(q, kp, vp, lengths, tables)
    # the dispatcher takes the kernel exactly where the compiler does
    paged.last_path = None
    out = jax.jit(paged.paged_attention)(q, kp, vp, lengths, tables)
    assert paged.last_path == ("pallas" if head_dim % 128 == 0 else "xla")
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=2e-2, atol=2e-2)
    scale = 1.0 / onp.sqrt(head_dim)
    out = jax.jit(functools.partial(kernel, pages_per_compute_block=8))(
        q * scale, kp, vp, lengths, tables)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("heads, kv_heads, head_dim",
                         [(12, 12, 64), (32, 8, 128)])
def test_paged_attend_rows_kernel_on_chip(heads, kv_heads, head_dim):
    """The decode step's own kernel (PR 36) at the serving cells' cache
    geometry (32 slots x 64 pages of 16) against the gather it replaced,
    selected by ``_decode_attention`` itself: idle lanes give zeros, and
    a pool whose pages past every length hold NaN gives the same finite
    answer."""
    from mxnet_tpu.models import decoder
    from mxnet_tpu.ops.pallas import paged_attention as paged
    rs = onp.random.RandomState(0)
    B, S, pps, L, li = 32, 16, 64, 2, 1
    P = B * pps + 1
    pools = [jax.random.normal(jax.random.key(i),
                               (L, P, S, kv_heads * head_dim), jnp.float32)
             for i in range(2)]
    q = jnp.asarray(rs.randn(B, heads, head_dim).astype("float32"))
    lengths = rs.randint(1, pps * S, size=B).astype("int32")
    lengths[::5] = 0
    lengths[1], lengths[2] = pps * S, 1
    tables = onp.arange(1, P, dtype="int32").reshape(B, pps)
    args = (li, jnp.asarray(lengths), jnp.asarray(tables), kv_heads)
    ref = paged.attend_rows(
        q, decoder._gather_rows(pools[0], li, args[2]),
        decoder._gather_rows(pools[1], li, args[2]), args[1],
        1.0 / head_dim ** 0.5, kv_heads)
    paged.last_path = None
    out = jax.jit(decoder._decode_attention, static_argnums=(3, 6))(
        q, *pools, *args)
    assert paged.last_path == "pallas"
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=2e-2, atol=2e-2)
    assert not onp.asarray(out)[lengths == 0].any()
    dead = onp.concatenate(
        [[0]] + [tables[b, -(-int(n) // S):] for b, n in enumerate(lengths)])
    poisoned = [p.at[:, dead].set(jnp.nan) for p in pools]
    again = jax.jit(decoder._decode_attention, static_argnums=(3, 6))(
        q, *poisoned, *args)
    assert onp.asarray(again).tobytes() == onp.asarray(out).tobytes()


def test_lstm_sequence_kernel_on_chip():
    """The persistent LSTM cell at the word-LM width (H=650: not a
    multiple of the 128-lane tile), forward and backward against the
    scan path."""
    from mxnet_tpu.ops import rnn as oprnn
    T, B, I, H = 35, 32, 650, 650
    ks = jax.random.split(jax.random.key(0), 4)
    x = jax.random.normal(ks[0], (T, B, I), jnp.float32)
    params = jax.random.normal(
        ks[1], (oprnn.param_size("lstm", I, H, 1),), jnp.float32) * 0.05
    h0 = jax.random.normal(ks[2], (1, B, H), jnp.float32) * 0.3
    c0 = jax.random.normal(ks[3], (1, B, H), jnp.float32) * 0.3

    def loss(fused):
        def f(x, params):
            out, hT, cT = oprnn.rnn_forward(x, params, h0, c0, "lstm", H,
                                            1, fused=fused)
            return (out * out).sum() + hT.sum() + cT.sum()
        return f

    l_s, g_s = jax.value_and_grad(loss(None), argnums=(0, 1))(x, params)
    l_f, g_f = jax.value_and_grad(loss("compiled"), argnums=(0, 1))(
        x, params)
    onp.testing.assert_allclose(float(l_f), float(l_s), rtol=2e-2)
    for a, b in zip(g_f, g_s):
        scale = float(jnp.abs(b).max())
        onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                    rtol=5e-2, atol=5e-2 * scale)


@pytest.mark.parametrize("fmt", [
    "int8",
    pytest.param("int4", marks=pytest.mark.xfail(run=False, reason=(
        "the int4 kernel at (3072, 768) did not come back from the v5e's "
        "compiler in 35 minutes and cost the run its chip (PR 21); "
        "quant_matmul dequantizes int4 in XLA on the compiled lane")))])
@pytest.mark.parametrize("shape", [(3072, 768), (768, 3072)])
def test_quant_matmul_kernel_on_chip(fmt, shape):
    """The fused dequant-matmul at GPT-2-small's FFN GEMMs (8 decode
    rows), compiled, against dequantize-then-dot."""
    from mxnet_tpu.ops.pallas import quant_matmul as qmm
    rs = onp.random.RandomState(2)
    o, i = shape
    w = rs.randn(o, i).astype("float32") * 0.05
    qw = qmm.quantize_w8(w) if fmt == "int8" else qmm.quantize_w4(w)
    x = jnp.asarray(rs.randn(8, i).astype("float32"))
    qmm.last_path = None
    out = jax.jit(qmm.quant_matmul)(x, qw)
    assert qmm.last_path == "pallas"
    with jax.default_matmul_precision("highest"):
        ref = qmm.quant_matmul_reference(x, qw)
    scale = float(jnp.abs(ref).max())
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=2e-2, atol=2e-2 * scale)


def test_splash_causal_kernel_on_chip():
    """jax's splash-attention kernel, the causal per-shard route of the
    sharded flash entry, at the one head_dim its gate admits (128)."""
    from mxnet_tpu.ops import attention
    B, H, L, D = 2, 4, 512, 128
    q, k, v = (jnp.asarray(_rand((B, H, L, D), seed=s)) for s in range(3))
    assert attention._splash_ok(q)
    out = attention._splash_causal(q, k, v, None)
    ref = attention.attention_reference(q, k, v, causal=True)
    onp.testing.assert_allclose(onp.asarray(out), onp.asarray(ref),
                                rtol=2e-2, atol=2e-2)


def test_engine_runs_the_decode_program_it_selected_on_chip():
    """DecodeEngine on the chip: the program named in stats() is the one
    its step traced and ran, and its greedy tokens are the oracle's."""
    from mxnet_tpu.models import decoder
    from mxnet_tpu.ops.pallas import epilogue, paged_attention
    from mxnet_tpu.serving import DecodeEngine
    lm = decoder.decoder_tiny_lm(seed=0)
    prompt = list(range(1, 20))
    eng = DecodeEngine(lm, slots=2, page_size=8, max_ctx=64)
    try:
        out = eng.submit(prompt, 8).result(timeout=300)
        st = eng.stats()
    finally:
        eng.stop()
    # head_dim 16: attention reads through the gather, bias_gelu is Pallas
    assert paged_attention.last_path == "xla"
    assert epilogue.last_path == "pallas"
    assert st["launches"]["pallas_per_step"] == lm.config.num_layers
    assert len(out["tokens"]) == 8
