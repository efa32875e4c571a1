"""Fused recurrent ops (TPU-native equivalent of the reference's cudnn
`rnn_op` — /root/reference/paddle/fluid/operators/rnn_op.cu — and the python
cell math in python/paddle/nn/layer/rnn.py:258-702).

Design: one `rnn` primitive per call covering SimpleRNN(tanh/relu)/LSTM/GRU,
multi-layer and bidirectional, lowered as a single XLA computation:
  * the input projection `x @ W_ih^T` is hoisted out of the time loop as one
    big batched matmul (seq*batch, gates*hidden) — this is the MXU-friendly
    layout; only the `h @ W_hh^T` recurrence stays inside `lax.scan`,
  * variable-length sequences use a step mask (dense tensors + masks instead
    of the reference's LoD runtime type, SURVEY §7),
  * inter-layer dropout takes an explicit PRNG key (functional randomness).

Gate conventions match the reference exactly (nn/layer/rnn.py:478,629):
LSTM chunks [i,f,g,o]; GRU chunks [r,z,c] with h' = (h - c)*z + c and the
reset gate applied after the hidden matmul.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..framework.dispatch import primitive


def _cell_new_state(mode, gates_x, h, c, w_hh, b_hh):
    """One recurrence step given precomputed input gates. Returns (out, h, c)."""
    if mode == "GRU":
        # reference applies the reset gate AFTER the hidden matmul
        # (nn/layer/rnn.py:680 "apply reset gate after mm")
        x_r, x_z, x_c = jnp.split(gates_x, 3, axis=-1)
        hg = jnp.matmul(h, w_hh.T)
        if b_hh is not None:
            hg = hg + b_hh
        h_r, h_z, h_c = jnp.split(hg, 3, axis=-1)
        r = jax.nn.sigmoid(x_r + h_r)
        z = jax.nn.sigmoid(x_z + h_z)
        cand = jnp.tanh(x_c + r * h_c)
        h_new = (h - cand) * z + cand
        return h_new, h_new, c
    g = gates_x + jnp.matmul(h, w_hh.T)
    if b_hh is not None:
        g = g + b_hh
    if mode == "LSTM":
        i, f, gg, o = jnp.split(g, 4, axis=-1)
        i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
        gg = jnp.tanh(gg)
        c_new = f * c + i * gg
        h_new = o * jnp.tanh(c_new)
        return h_new, h_new, c_new
    act = jnp.tanh if mode == "RNN_TANH" else jax.nn.relu
    h_new = act(g)
    return h_new, h_new, c


def _scan_direction(mode, x_tbi, h0, c0, w_ih, w_hh, b_ih, b_hh,
                    seq_len, reverse):
    """Scan one direction over time-major input [T, B, I]."""
    T = x_tbi.shape[0]
    # hoist the input projection out of the loop: one big MXU matmul
    gates_x = jnp.matmul(x_tbi, w_ih.T)
    if b_ih is not None:
        gates_x = gates_x + b_ih

    steps = jnp.arange(T)
    if reverse:
        gates_x = gates_x[::-1]
        steps = steps[::-1]

    def step(carry, inp):
        h, c = carry
        g_t, t = inp
        out, h_new, c_new = _cell_new_state(mode, g_t, h, c, w_hh, b_hh)
        if seq_len is not None:
            valid = (t < seq_len)[:, None]
            h_new = jnp.where(valid, h_new, h)
            c_new = jnp.where(valid, c_new, c)
            out = jnp.where(valid, out, jnp.zeros_like(out))
        return (h_new, c_new), out

    (h_f, c_f), outs = jax.lax.scan(step, (h0, c0), (gates_x, steps))
    if reverse:
        outs = outs[::-1]
    return outs, h_f, c_f


@primitive("rnn")
def rnn(x, h0, c0, seq_len, dropout_key, *weights, mode="LSTM",
        num_layers=1, num_directions=1, time_major=False, dropout=0.0,
        has_bias=True):
    """Returns (y, h_n) for RNN/GRU or (y, h_n, c_n) for LSTM.

    x: [B, T, I] (or [T, B, I] when time_major). h0/c0: [L*D, B, H].
    weights: per (layer, direction): w_ih, w_hh[, b_ih, b_hh].
    """
    if not time_major:
        x = jnp.swapaxes(x, 0, 1)
    per = 4 if has_bias else 2
    idx = 0
    layer_in = x
    h_finals, c_finals = [], []
    key = dropout_key
    for layer in range(num_layers):
        outs_dir = []
        for d in range(num_directions):
            w_ih, w_hh = weights[idx], weights[idx + 1]
            b_ih = weights[idx + 2] if has_bias else None
            b_hh = weights[idx + 3] if has_bias else None
            idx += per
            s = layer * num_directions + d
            outs, h_f, c_f = _scan_direction(
                mode, layer_in, h0[s], c0[s] if c0 is not None else h0[s] * 0,
                w_ih, w_hh, b_ih, b_hh, seq_len, reverse=(d == 1))
            outs_dir.append(outs)
            h_finals.append(h_f)
            c_finals.append(c_f)
        layer_in = outs_dir[0] if num_directions == 1 else jnp.concatenate(
            outs_dir, axis=-1)
        if dropout > 0.0 and key is not None and layer < num_layers - 1:
            key, sub = jax.random.split(key)
            keep = jax.random.bernoulli(sub, jnp.float32(1.0 - dropout),
                                        layer_in.shape)
            layer_in = jnp.where(keep, layer_in / (1.0 - dropout), 0.0)
    y = layer_in if time_major else jnp.swapaxes(layer_in, 0, 1)
    h_n = jnp.stack(h_finals)
    if mode == "LSTM":
        return y, h_n, jnp.stack(c_finals)
    return y, h_n
