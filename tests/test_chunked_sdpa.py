"""Blockwise (online-softmax) sdpa fallback: long sequences must not
materialise the [Tq, Tk] score matrix even where the Pallas flash kernel
cannot run (CPU; TPU with a broken Mosaic compile path). Parity is
checked against a hand-computed dense attention oracle, not against the
dense sdpa path, so the per-op jit cache cannot mask a routing bug."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.ops import nn_ops
from paddle_tpu.ops.pallas_kernels import attention_path_counts


@pytest.fixture(autouse=True)
def _low_threshold():
    paddle.set_flags({"FLAGS_sdpa_chunked_threshold": 128,
                      "FLAGS_use_flash_attention": False})
    try:
        yield
    finally:
        paddle.set_flags({"FLAGS_sdpa_chunked_threshold": 2048,
                          "FLAGS_use_flash_attention": True})


def _oracle(q, k, v, causal):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (q.shape[-1] ** -0.5)
    if causal:
        Tq, Tk = s.shape[-2], s.shape[-1]
        cm = jnp.tril(jnp.ones((Tq, Tk), bool), k=Tk - Tq)
        s = jnp.where(cm, s, -1e9)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v)


def _rand(shape, seed):
    return np.random.RandomState(seed).randn(*shape).astype(np.float32)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("t", [256, 640])  # 640: pad path (2 blocks of 512)
def test_forward_parity_and_routing(causal, t):
    q, k, v = (_rand((2, 3, t, 16), s) for s in (0, 1, 2))
    attention_path_counts(reset=True)
    out = nn_ops.sdpa(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                      None, None, causal=causal)
    counts = attention_path_counts()
    assert counts["xla_chunked"] >= 1, counts
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_oracle(q, k, v, causal)),
                               rtol=2e-5, atol=2e-5)


def test_grad_parity_through_functional():
    t = 256
    qn, kn, vn = (_rand((1, 2, t, 8), s) for s in (3, 4, 5))
    qt = paddle.to_tensor(qn, stop_gradient=False)
    kt = paddle.to_tensor(kn, stop_gradient=False)
    vt = paddle.to_tensor(vn, stop_gradient=False)
    attention_path_counts(reset=True)
    out, _ = F.scaled_dot_product_attention(qt, kt, vt, is_causal=True)
    (out ** 2).sum().backward()
    assert attention_path_counts()["xla_chunked"] >= 1

    def loss(q, k, v):
        return (_oracle(q, k, v, True) ** 2).sum()

    gq, gk, gv = jax.grad(loss, argnums=(0, 1, 2))(
        jnp.asarray(qn), jnp.asarray(kn), jnp.asarray(vn))
    for got, want in ((qt.grad, gq), (kt.grad, gk), (vt.grad, gv)):
        np.testing.assert_allclose(np.asarray(got.numpy()),
                                   np.asarray(want),
                                   rtol=3e-4, atol=3e-4)


def test_decode_shapes_stay_dense():
    # causal with Tq != Tk uses the END-aligned diagonal convention the
    # blockwise mask does not implement — must stay on the dense path
    q = jnp.asarray(_rand((1, 2, 4, 8), 6))
    kv = jnp.asarray(_rand((1, 2, 256, 8), 7))
    attention_path_counts(reset=True)
    out = nn_ops.sdpa(q, kv, kv, None, None, causal=True)
    assert attention_path_counts()["xla_chunked"] == 0
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_oracle(q, kv, kv, True)),
        rtol=2e-5, atol=2e-5)


def test_mask_and_weights_stay_dense():
    t = 256
    q = jnp.asarray(_rand((1, 1, t, 8), 8))
    attention_path_counts(reset=True)
    mask = jnp.zeros((1, 1, t, t), jnp.float32)
    nn_ops.sdpa(q, q, q, mask, None)
    nn_ops.sdpa(q, q, q, None, None, return_weights=True)
    nn_ops.sdpa(q, q, q, None, jax.random.PRNGKey(0), dropout_p=1.0)
    assert attention_path_counts()["xla_chunked"] == 0


def test_dropout_parity_exact():
    """Chunked attention dropout == dense attention with the SAME
    per-block fold_in masks applied to the normalized weights (dropout on
    the numerator only; denominator stays undropped)."""
    B, H, t, d, bk, p = 1, 2, 640, 8, 512, 0.3  # 640: two blocks + pad
    q, k, v = (jnp.asarray(_rand((B, H, t, d), s)) for s in (9, 10, 11))
    key = jax.random.PRNGKey(42)
    attention_path_counts(reset=True)
    out = nn_ops.sdpa(q, k, v, None, key, dropout_p=p, causal=True)
    assert attention_path_counts()["xla_chunked"] >= 1

    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (d ** -0.5)
    cm = jnp.tril(jnp.ones((t, t), bool))
    w = jax.nn.softmax(jnp.where(cm, s, -jnp.inf), axis=-1)
    keep = jnp.concatenate(
        [jax.random.bernoulli(jax.random.fold_in(key, i),
                              jnp.float32(1.0 - p),
                              (B, H, t, bk)) for i in range(2)],
        axis=-1)[..., :t]
    want = jnp.einsum("bhqk,bhkd->bhqd",
                      w * keep / (1.0 - p), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_dropout_grads_flow_chunked():
    t = 256
    qn = _rand((1, 2, t, 8), 12)
    qt = paddle.to_tensor(qn, stop_gradient=False)
    attention_path_counts(reset=True)
    out, _ = F.scaled_dot_product_attention(qt, qt, qt, dropout_p=0.25,
                                            is_causal=True)
    (out ** 2).sum().backward()
    assert attention_path_counts()["xla_chunked"] >= 1
    g = np.asarray(qt.grad.numpy())
    assert np.isfinite(g).all() and np.abs(g).sum() > 0
