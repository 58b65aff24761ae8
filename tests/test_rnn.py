"""RNN tests (reference analog: tests/python/unittest/test_gluon_rnn.py):
fused layer vs cell-by-cell unroll consistency, shapes, gradients."""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import np, autograd, gluon
from mxnet_tpu.gluon import nn, rnn

pytestmark = pytest.mark.rnn


@pytest.mark.parametrize("cls,mode", [(rnn.LSTM, "lstm"), (rnn.GRU, "gru"),
                                      (rnn.RNN, "rnn")])
def test_rnn_layer_shapes(cls, mode):
    layer = cls(hidden_size=8, num_layers=2)
    layer.initialize()
    x = np.random.uniform(size=(5, 3, 4))  # TNC
    out = layer(x)
    assert out.shape == (5, 3, 8)
    states = layer.begin_state(3)
    out, new_states = layer(x, states)
    assert out.shape == (5, 3, 8)
    assert new_states[0].shape == (2, 3, 8)


def test_rnn_ntc_layout():
    layer = rnn.LSTM(hidden_size=8, layout="NTC")
    layer.initialize()
    x = np.random.uniform(size=(3, 5, 4))
    out = layer(x)
    assert out.shape == (3, 5, 8)


def test_bidirectional_shapes():
    layer = rnn.LSTM(hidden_size=8, bidirectional=True)
    layer.initialize()
    x = np.random.uniform(size=(5, 3, 4))
    out = layer(x)
    assert out.shape == (5, 3, 16)


def test_lstm_layer_vs_cell_unroll():
    """The fused lax.scan layer must match step-by-step LSTMCell math."""
    mx.random.seed(3)
    H, I, T, B = 6, 4, 5, 2
    layer = rnn.LSTM(hidden_size=H, num_layers=1)
    layer.initialize()
    x = np.random.uniform(-1, 1, size=(T, B, I))
    out = layer(x).asnumpy()

    # unpack the flat param vector the same way the kernel does
    from mxnet_tpu.ops.rnn import unpack_params
    params = layer._flat_params()._data
    p = unpack_params(params, "lstm", I, H)[0][0]
    w_i2h = onp.asarray(p["w_i2h"])
    w_h2h = onp.asarray(p["w_h2h"])
    b_i2h = onp.asarray(p["b_i2h"])
    b_h2h = onp.asarray(p["b_h2h"])

    def sigmoid(a):
        return 1 / (1 + onp.exp(-a))

    h = onp.zeros((B, H), "float32")
    c = onp.zeros((B, H), "float32")
    xs = x.asnumpy()
    ref = []
    for t in range(T):
        g = xs[t] @ w_i2h.T + b_i2h + h @ w_h2h.T + b_h2h
        i, f, u, o = onp.split(g, 4, axis=-1)
        c = sigmoid(f) * c + sigmoid(i) * onp.tanh(u)
        h = sigmoid(o) * onp.tanh(c)
        ref.append(h.copy())
    onp.testing.assert_allclose(out, onp.stack(ref), rtol=1e-4, atol=1e-5)


def test_rnn_gradients_flow():
    for cls in (rnn.LSTM, rnn.GRU, rnn.RNN):
        layer = cls(hidden_size=4, num_layers=2, bidirectional=True)
        layer.initialize()
        x = np.random.uniform(size=(3, 2, 5))
        with autograd.record():
            out = layer(x).sum()
        out.backward()
        g = layer.i2h_weight_l0.grad().asnumpy()
        assert onp.isfinite(g).all() and onp.abs(g).sum() > 0
        g2 = layer.h2h_weight_l1_r.grad().asnumpy()
        assert onp.isfinite(g2).all() and onp.abs(g2).sum() > 0


def test_rnn_hybridize_consistency():
    layer = rnn.GRU(hidden_size=8, num_layers=2)
    layer.initialize()
    x = np.random.uniform(size=(4, 2, 3))
    eager = layer(x).asnumpy()
    layer.hybridize()
    hybrid = layer(x).asnumpy()
    onp.testing.assert_allclose(eager, hybrid, rtol=1e-4, atol=1e-5)


def test_cells():
    for cell_cls, n_states in [(rnn.RNNCell, 1), (rnn.LSTMCell, 2),
                               (rnn.GRUCell, 1)]:
        cell = cell_cls(8)
        cell.initialize()
        x = np.random.uniform(size=(3, 5))
        states = cell.begin_state(3)
        assert len(states) == n_states
        out, new_states = cell(x, states)
        assert out.shape == (3, 8)
        assert len(new_states) == n_states


def test_cell_unroll():
    cell = rnn.LSTMCell(6)
    cell.initialize()
    x = np.random.uniform(size=(2, 5, 4))  # NTC
    outputs, states = cell.unroll(5, x, layout="NTC")
    assert outputs.shape == (2, 5, 6)
    assert states[0].shape == (2, 6)


def test_sequential_cell():
    stack = rnn.SequentialRNNCell()
    stack.add(rnn.LSTMCell(4))
    stack.add(rnn.LSTMCell(4))
    stack.initialize()
    x = np.random.uniform(size=(2, 3))
    states = stack.begin_state(2)
    assert len(states) == 4
    out, new_states = stack(x, states)
    assert out.shape == (2, 4)
    assert len(new_states) == 4


def test_dropout_residual_cells():
    base = rnn.GRUCell(5)
    res = rnn.ResidualCell(base)
    res.initialize()
    x = np.random.uniform(size=(2, 5))
    out, _ = res(x, res.begin_state(2))
    assert out.shape == (2, 5)

    dc = rnn.DropoutCell(0.5)
    out2, _ = dc(x, [])
    onp.testing.assert_array_equal(out2.asnumpy(), x.asnumpy())  # inference


def test_bidirectional_cell():
    bi = rnn.BidirectionalCell(rnn.GRUCell(4), rnn.GRUCell(4))
    bi.initialize()
    x = np.random.uniform(size=(2, 3, 5))  # NTC
    out, states = bi.unroll(3, x, layout="NTC")
    assert out.shape == (2, 3, 8)


@pytest.mark.slow
def test_lstm_lm_trains():
    """LSTM language-model slice (reference config #5 shape)."""
    V, E, H, T, B = 20, 8, 16, 6, 4
    net = nn.HybridSequential()

    class LM(nn.HybridBlock):
        def __init__(self):
            super().__init__()
            self.embed = nn.Embedding(V, E)
            self.lstm = rnn.LSTM(H, num_layers=1, layout="NTC")
            self.out = nn.Dense(V, flatten=False)

        def forward(self, x):
            return self.out(self.lstm(self.embed(x)))

    mx.random.seed(0)
    net = LM()
    net.initialize(mx.init.Xavier())
    data = np.random.randint(0, V, size=(B, T + 1))
    x, y = data[:, :-1], data[:, 1:]
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 0.01})
    losses = []
    for _ in range(15):
        with autograd.record():
            out = net(x)
            loss = loss_fn(out, y)
        loss.backward()
        trainer.step(B)
        losses.append(float(loss.mean()))
    assert losses[-1] < losses[0], (losses[0], losses[-1])
