"""RNN + Transformer layer tests.

Numerics cross-checked cell-vs-fused (the fused `rnn` primitive must agree
with the eager cell scan — the analogue of the reference's rnn-op vs python
cell parity tests in unittests/rnn/) and flash-attention-vs-XLA attention."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
import paddle_tpu.nn.functional as F


def _np(t):
    return np.asarray(t.numpy())


class TestCells:
    def test_simple_rnn_cell(self):
        paddle.seed(0)
        cell = nn.SimpleRNNCell(16, 32)
        x = paddle.randn((4, 16))
        h = paddle.randn((4, 32))
        y, h_new = cell(x, h)
        assert y.shape == [4, 32]
        # manual math
        w_ih, w_hh = _np(cell.weight_ih), _np(cell.weight_hh)
        b_ih, b_hh = _np(cell.bias_ih), _np(cell.bias_hh)
        ref = np.tanh(_np(x) @ w_ih.T + b_ih + _np(h) @ w_hh.T + b_hh)
        np.testing.assert_allclose(_np(y), ref, atol=1e-5)

    def test_lstm_cell_shapes(self):
        cell = nn.LSTMCell(16, 32)
        x = paddle.randn((4, 16))
        y, (h, c) = cell(x)
        assert y.shape == [4, 32] and h.shape == [4, 32] and c.shape == [4, 32]

    def test_gru_cell_matches_fused(self):
        paddle.seed(1)
        B, T, I, H = 2, 5, 8, 12
        gru = nn.GRU(I, H)
        x = paddle.randn((B, T, I))
        y, h_n = gru(x)
        assert y.shape == [B, T, H] and h_n.shape == [1, B, H]
        # replay with an eager GRUCell sharing weights
        cell = nn.GRUCell(I, H)
        cell.weight_ih.set_value(_np(gru.weight_ih_l0))
        cell.weight_hh.set_value(_np(gru.weight_hh_l0))
        cell.bias_ih.set_value(_np(gru.bias_ih_l0))
        cell.bias_hh.set_value(_np(gru.bias_hh_l0))
        h = paddle.zeros((B, H))
        outs = []
        for t in range(T):
            o, h = cell(x[:, t], h)
            outs.append(_np(o))
        np.testing.assert_allclose(_np(y), np.stack(outs, 1), atol=1e-5)
        np.testing.assert_allclose(_np(h_n)[0], _np(h), atol=1e-5)


class TestRNNClasses:
    def test_lstm_forward_backward(self):
        paddle.seed(0)
        lstm = nn.LSTM(8, 16, num_layers=2, direction="bidirectional")
        x = paddle.randn((3, 7, 8))
        y, (h, c) = lstm(x)
        assert y.shape == [3, 7, 32]
        assert h.shape == [4, 3, 16] and c.shape == [4, 3, 16]
        loss = y.mean()
        loss.backward()
        g = lstm.weight_ih_l0.grad
        assert g is not None and np.isfinite(_np(g)).all()

    def test_lstm_matches_cell_scan(self):
        paddle.seed(3)
        B, T, I, H = 2, 4, 6, 10
        lstm = nn.LSTM(I, H)
        cell = nn.LSTMCell(I, H)
        cell.weight_ih.set_value(_np(lstm.weight_ih_l0))
        cell.weight_hh.set_value(_np(lstm.weight_hh_l0))
        cell.bias_ih.set_value(_np(lstm.bias_ih_l0))
        cell.bias_hh.set_value(_np(lstm.bias_hh_l0))
        x = paddle.randn((B, T, I))
        y, (h_n, c_n) = lstm(x)
        rnn_wrap = nn.RNN(cell)
        y2, (h2, c2) = rnn_wrap(x)
        np.testing.assert_allclose(_np(y), _np(y2), atol=1e-5)
        np.testing.assert_allclose(_np(h_n)[0], _np(h2), atol=1e-5)

    def test_sequence_length_masking(self):
        paddle.seed(0)
        rnn = nn.SimpleRNN(4, 8)
        x = paddle.randn((2, 6, 4))
        seq = paddle.to_tensor(np.array([3, 6], np.int64))
        y, h_n = rnn(x, sequence_length=seq)
        # outputs past the valid length are zeros
        assert np.abs(_np(y)[0, 3:]).max() == 0.0
        assert np.abs(_np(y)[1]).max() > 0.0
        # final state of row 0 equals state at t=3
        y_full, _ = rnn(x)
        np.testing.assert_allclose(_np(h_n)[0, 0], _np(y_full)[0, 2],
                                   atol=1e-5)

    def test_birnn_wrapper(self):
        cf, cb = nn.GRUCell(4, 6), nn.GRUCell(4, 6)
        bi = nn.BiRNN(cf, cb)
        x = paddle.randn((2, 5, 4))
        y, (sf, sb) = bi(x)
        assert y.shape == [2, 5, 12]


class TestAttention:
    def test_mha_self_attention(self):
        paddle.seed(0)
        mha = nn.MultiHeadAttention(32, 4)
        x = paddle.randn((2, 6, 32))
        out = mha(x, x, x)
        assert out.shape == [2, 6, 32]
        out.mean().backward()
        assert mha.q_proj.weight.grad is not None

    def test_mha_mask_semantics(self):
        paddle.seed(0)
        mha = nn.MultiHeadAttention(16, 2)
        mha.eval()
        x = paddle.randn((1, 4, 16))
        # bool mask: False = masked. mask out last key entirely
        mask = np.ones((1, 1, 4, 4), bool)
        mask[..., 3] = False
        out_masked = mha(x, x, x, attn_mask=paddle.to_tensor(mask))
        # perturbing the masked key must not change the output
        xp = _np(x).copy()
        xp[0, 3] += 10.0
        out2 = mha(paddle.to_tensor(xp), x, x,
                   attn_mask=paddle.to_tensor(mask))
        np.testing.assert_allclose(_np(out_masked)[:, :3], _np(out2)[:, :3],
                                   atol=1e-4)

    def test_flash_vs_xla(self):
        from paddle_tpu.ops import pallas_kernels as pk
        q = np.random.RandomState(0).randn(1, 2, 32, 16).astype(np.float32)
        k = np.random.RandomState(1).randn(1, 2, 32, 16).astype(np.float32)
        v = np.random.RandomState(2).randn(1, 2, 32, 16).astype(np.float32)
        ref = pk._xla_attention(q, k, v, causal=True)
        out, _ = pk._flash_fwd(q, k, v, causal=True, block_q=16,
                               block_k=16, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-3, rtol=2e-3)

    def test_flash_causal_cross_length(self):
        # bottom-right alignment: Tq < Tk (cached decode) must match XLA
        from paddle_tpu.ops import pallas_kernels as pk
        r = np.random.RandomState(3)
        q = r.randn(1, 1, 16, 8).astype(np.float32)
        k = r.randn(1, 1, 48, 8).astype(np.float32)
        v = r.randn(1, 1, 48, 8).astype(np.float32)
        ref = pk._xla_attention(q, k, v, causal=True)
        out, _ = pk._flash_fwd(q, k, v, causal=True, block_q=8,
                               block_k=16, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-3, rtol=2e-3)

    def test_shapes_gate_rejects_misaligned(self):
        from paddle_tpu.ops import pallas_kernels as pk
        q = np.zeros((1, 1, 136, 64), np.float32)
        assert not pk._shapes_ok(q, q, causal=False, interpret=False)
        q2 = np.zeros((1, 1, 256, 64), np.float32)
        assert pk._shapes_ok(q2, q2, causal=False, interpret=False)
        # causal with Tk < Tq would fully mask leading rows -> XLA path
        qs = np.zeros((1, 1, 256, 64), np.float32)
        ks = np.zeros((1, 1, 128, 64), np.float32)
        assert not pk._shapes_ok(qs, ks, causal=True, interpret=False)

    def test_sdpa_causal(self):
        paddle.seed(0)
        q = paddle.randn((1, 2, 8, 4))
        out, w = F.scaled_dot_product_attention(q, q, q, is_causal=True,
                                                return_weights=True)
        wn = _np(w)
        assert np.allclose(np.triu(wn[0, 0], k=1), 0.0, atol=1e-6)


class TestTransformer:
    def test_encoder_layer(self):
        paddle.seed(0)
        enc = nn.TransformerEncoderLayer(32, 4, 64, dropout=0.0)
        x = paddle.randn((2, 5, 32))
        y = enc(x)
        assert y.shape == [2, 5, 32]

    def test_full_transformer(self):
        paddle.seed(0)
        model = nn.Transformer(d_model=32, nhead=4, num_encoder_layers=2,
                               num_decoder_layers=2, dim_feedforward=64,
                               dropout=0.0)
        src = paddle.randn((2, 6, 32))
        tgt = paddle.randn((2, 4, 32))
        out = model(src, tgt)
        assert out.shape == [2, 4, 32]
        out.mean().backward()

    def test_decoder_cache_incremental(self):
        paddle.seed(0)
        dec_layer = nn.TransformerDecoderLayer(16, 2, 32, dropout=0.0)
        dec = nn.TransformerDecoder(dec_layer, 2)
        dec.eval()
        memory = paddle.randn((1, 5, 16))
        # full pass with causal mask vs incremental decode must agree
        T = 3
        tgt = paddle.randn((1, T, 16))
        causal = np.triu(np.full((T, T), -1e9, np.float32), k=1)
        full = dec(tgt, memory, tgt_mask=paddle.to_tensor(causal))
        cache = dec.gen_cache(memory)
        steps = []
        for t in range(T):
            step_in = paddle.to_tensor(_np(tgt)[:, t:t + 1])
            out, cache = dec(step_in, memory, cache=cache)
            steps.append(_np(out)[:, 0])
        np.testing.assert_allclose(_np(full)[0], np.stack(steps, 0)[:, 0],
                                   atol=1e-4)

    def test_encoder_stack_independent_params(self):
        enc = nn.TransformerEncoder(
            nn.TransformerEncoderLayer(8, 2, 16), num_layers=3)
        p0 = enc.layers[0].linear1.weight
        p1 = enc.layers[1].linear1.weight
        assert p0 is not p1
        assert len(list(enc.parameters())) > 20
