"""BERT model tests (reference config #3 slice)."""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import np, npx, gluon, autograd
from mxnet_tpu.models.bert import bert_tiny, MultiHeadAttention, TransformerLayer


def test_mha_shapes_and_consistency():
    mx.random.seed(0)
    mha = MultiHeadAttention(units=16, num_heads=4, use_flash=True)
    mha.initialize()
    x = np.random.uniform(size=(2, 8, 16))
    out = mha(x)
    assert out.shape == (2, 8, 16)
    # flash path vs explicit softmax path agree
    mha2 = MultiHeadAttention(units=16, num_heads=4, use_flash=False)
    mha2.initialize()
    for name, p in mha.collect_params().items():
        mha2.collect_params()[name].set_data(p.data())
    onp.testing.assert_allclose(out.asnumpy(), mha2(x).asnumpy(),
                                rtol=1e-4, atol=1e-5)


def test_bert_forward_shapes():
    mx.random.seed(0)
    net = bert_tiny()
    net.initialize()
    tokens = np.random.randint(0, 1000, size=(2, 12))
    types = np.zeros((2, 12), dtype="int32")
    mlm, nsp = net(tokens, types)
    assert mlm.shape == (2, 12, 1000)
    assert nsp.shape == (2, 2)


@pytest.mark.slow
def test_bert_mlm_trains():
    mx.random.seed(0)
    net = bert_tiny(dropout=0.0)
    net.initialize(mx.init.Normal(0.02))
    B, L = 4, 10
    tokens = np.random.randint(0, 1000, size=(B, L))
    labels = np.random.randint(0, 1000, size=(B, L))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 1e-3})
    losses = []
    for _ in range(8):
        with autograd.record():
            mlm, nsp = net(tokens)
            loss = loss_fn(mlm.reshape(-1, 1000), labels.reshape(-1))
        loss.backward()
        trainer.step(B)
        losses.append(float(loss.mean()))
    assert losses[-1] < losses[0]


def test_bert_hybridize_consistency():
    mx.random.seed(0)
    net = bert_tiny(dropout=0.0)
    net.initialize()
    tokens = np.random.randint(0, 1000, size=(2, 8))
    mlm_e, nsp_e = net(tokens)
    net.hybridize()
    mlm_h, nsp_h = net(tokens)
    onp.testing.assert_allclose(mlm_e.asnumpy(), mlm_h.asnumpy(),
                                rtol=1e-4, atol=1e-4)
    onp.testing.assert_allclose(nsp_e.asnumpy(), nsp_h.asnumpy(),
                                rtol=1e-4, atol=1e-4)


@pytest.mark.slow
def test_bert_amp_bf16():
    from mxnet_tpu import amp
    mx.random.seed(0)
    net = bert_tiny(dropout=0.0)
    net.initialize()
    tokens = np.random.randint(0, 1000, size=(2, 8))
    mlm32, _ = net(tokens)
    net16 = amp.convert_hybrid_block(net, "bfloat16", cast_params_offline=True)
    mlm16, _ = net16(tokens)
    # bf16 has ~3 decimal digits; logits should still correlate strongly
    a, b = mlm32.asnumpy().ravel(), onp.asarray(mlm16.asnumpy(), onp.float32).ravel()
    corr = onp.corrcoef(a, b)[0, 1]
    assert corr > 0.98, corr
