"""Fused transformer functional APIs.

TPU-native equivalent of the reference's fused attention / FFN mega-ops
(reference: python/paddle/incubate/nn/functional/fused_transformer.py:31,
176 over paddle/fluid/operators/fused/fused_attention_op.cu and
fused_feedforward_op.cu). The reference hand-fuses qkv-matmul + bias +
transpose + fmha + out-proj + residual + dropout + layernorm into one CUDA
kernel chain; on TPU the SAME computation expressed as plain jnp ops
compiles into fused XLA fusions (and the attention core routes to the
Pallas flash kernel via F.scaled_dot_product_attention) — the API is kept
for source parity."""
from __future__ import annotations

from ....framework.dispatch import primitive
from ....framework.random import RNG
from ....framework.tensor import Tensor
from ....nn import functional as F
from ....ops import math as m
from ....ops import manipulation as mp
from ....ops import pallas_kernels as pk


@primitive("fused_bias_dropout_residual_layer_norm")
def _fbdrln_op(x, residual, bias, ln_scale, ln_bias, key, *, dropout_rate,
               ln_epsilon, training, mode):
    y, _ = pk.fused_bias_dropout_residual_ln_arrays(
        x, residual, bias, ln_scale, ln_bias, key, dropout_rate, ln_epsilon,
        training, mode)
    return y


@primitive("fused_bias_dropout_residual")
def _fbdr_op(x, residual, bias, key, *, dropout_rate, training, mode):
    """No-LN variant: z = residual + dropout(x + bias) in one Pallas pass —
    the pre-LN transformer residual tail (reference:
    fused_dropout_helper.h LaunchResidualDropoutBias)."""
    _, z = pk.fused_bias_dropout_residual_ln_arrays(
        x, residual, bias, None, None, key, dropout_rate, 1e-5, training,
        mode)
    return z


@primitive("fused_bias_dropout_residual_ln_pair")
def _fbdrln_pair_op(x, residual, bias, ln_scale, ln_bias, key, *,
                    dropout_rate, ln_epsilon, training, mode):
    """Two-output variant backing the decoder-block fusion
    (FLAGS_fused_block): ONE Pallas pass yields both
    z = residual + dropout(x + bias) (the residual stream) and
    y = LN(z) (the next sublayer's input), so the post-attention
    activation is read from HBM once instead of once for the residual
    add and again for the LN."""
    return pk.fused_bias_dropout_residual_ln_arrays(
        x, residual, bias, ln_scale, ln_bias, key, dropout_rate,
        ln_epsilon, training, mode)


def fused_bias_dropout_residual_ln_pair(
        x, residual, bias=None, ln_scale=None, ln_bias=None,
        dropout_rate=0.5, ln_epsilon=1e-5, training=True,
        mode="upscale_in_train", name=None):
    """(LN(z), z) with z = residual + dropout(x + bias), both outputs of
    one fused Pallas pass — the decoder-block tail used by
    GPTDecoderLayer under FLAGS_fused_block (y feeds the MLP, z carries
    the residual stream to the MLP's own residual add). Gated on kernel
    GEOMETRY only (not FLAGS_use_fused_dropout_ln — the caller's
    FLAGS_fused_block is the opt-in); rejected shapes/backends take the
    composed ops, which are also the parity oracle."""
    if not pk.fused_ln_geometry_ok(pk.raw(x)):
        h = x if bias is None else m.add(x, bias)
        h = F.dropout(h, dropout_rate, training=training, mode=mode)
        z = m.add(residual, h)
        d = x.shape[-1]
        return F.layer_norm(z, (d,), ln_scale, ln_bias, ln_epsilon), z
    if ln_scale is None:
        import paddle_tpu
        ln_scale = paddle_tpu.ones((x.shape[-1],), x.dtype)
    if ln_bias is None:
        import paddle_tpu
        ln_bias = paddle_tpu.zeros((x.shape[-1],), x.dtype)
    return _fbdrln_pair_op(x, residual, bias, ln_scale, ln_bias,
                           RNG.next_key(),
                           dropout_rate=float(dropout_rate),
                           ln_epsilon=float(ln_epsilon),
                           training=bool(training), mode=str(mode))


def fused_bias_dropout_residual(x, residual, bias=None, dropout_rate=0.5,
                                training=True, mode="upscale_in_train",
                                name=None):
    """residual + dropout(x + bias), fused (falls back to composed ops when
    the gate rejects the shape/backend)."""
    if not pk.fused_ln_shapes_ok(pk.raw(x)):
        h = x if bias is None else m.add(x, bias)
        h = F.dropout(h, dropout_rate, training=training, mode=mode)
        return m.add(residual, h)
    return _fbdr_op(x, residual, bias, RNG.next_key(),
                    dropout_rate=float(dropout_rate),
                    training=bool(training), mode=str(mode))


def fused_bias_dropout_residual_layer_norm(
        x, residual, bias=None, ln_scale=None, ln_bias=None,
        dropout_rate=0.5, ln_epsilon=1e-5, training=True,
        mode="upscale_in_train", name=None):
    """out = LayerNorm(residual + dropout(x + bias)) in ONE Pallas pass —
    the TPU equivalent of the reference's fused dropout chain
    (operators/fused/fused_dropout_helper.h LaunchLayernormResidualDropoutBias,
    used inside fused_attention_op.cu). The dropout mask is generated by the
    on-chip PRNG and never materialized in HBM; the backward recomputes LN
    statistics from the saved pre-norm activation."""
    if not pk.fused_ln_shapes_ok(pk.raw(x)):
        h = x if bias is None else m.add(x, bias)
        h = F.dropout(h, dropout_rate, training=training, mode=mode)
        z = m.add(residual, h)
        d = x.shape[-1]
        return F.layer_norm(z, (d,), ln_scale, ln_bias, ln_epsilon)
    if ln_scale is None:
        import paddle_tpu
        ln_scale = paddle_tpu.ones((x.shape[-1],), x.dtype)
    if ln_bias is None:
        import paddle_tpu
        ln_bias = paddle_tpu.zeros((x.shape[-1],), x.dtype)
    return _fbdrln_op(x, residual, bias, ln_scale, ln_bias, RNG.next_key(),
                      dropout_rate=float(dropout_rate),
                      ln_epsilon=float(ln_epsilon), training=bool(training),
                      mode=str(mode))


def fused_feedforward(x, linear1_weight, linear2_weight, linear1_bias=None,
                      linear2_bias=None, ln1_scale=None, ln1_bias=None,
                      ln2_scale=None, ln2_bias=None, dropout1_rate=0.5,
                      dropout2_rate=0.5, activation="relu",
                      ln1_epsilon=1e-5, ln2_epsilon=1e-5,
                      pre_layer_norm=False, training=True, mode
                      ="upscale_in_train", name=None):
    """residual + LN( x + dropout2( W2 act( dropout1( W1 ln(x) )))) —
    reference: fused_transformer.py:31 (fused_feedforward)."""
    d = x.shape[-1]
    residual = x
    if pre_layer_norm:
        x = F.layer_norm(x, (d,), ln1_scale, ln1_bias, ln1_epsilon)
    h = F.linear(x, linear1_weight, linear1_bias)
    h = getattr(F, activation)(h)
    h = F.dropout(h, dropout1_rate, training=training, mode=mode)
    h = F.linear(h, linear2_weight)
    if not pre_layer_norm:
        # tail rides the fused Pallas chain: bias+dropout+residual+LN
        return fused_bias_dropout_residual_layer_norm(
            h, residual, linear2_bias, ln2_scale, ln2_bias, dropout2_rate,
            ln2_epsilon, training, mode)
    if linear2_bias is not None:
        h = m.add(h, linear2_bias)
    h = F.dropout(h, dropout2_rate, training=training, mode=mode)
    out = m.add(residual, h)
    return out


def fused_multi_head_attention(x, qkv_weight, linear_weight,
                               pre_layer_norm=False, pre_ln_scale=None,
                               pre_ln_bias=None, ln_scale=None, ln_bias=None,
                               pre_ln_epsilon=1e-5, qkv_bias=None,
                               linear_bias=None, cache_kv=None,
                               attn_mask=None, dropout_rate=0.5,
                               attn_dropout_rate=0.5, ln_epsilon=1e-5,
                               training=True, mode="upscale_in_train",
                               ring_id=-1, name=None, is_causal=False):
    """Full MHA block with residual + dropout + layernorm.

    x: [B, T, E]; qkv_weight: [3, num_heads, head_dim, E] (the reference's
    fused layout, fused_attention_op.cu); linear_weight: [E, E].
    reference: fused_transformer.py:176.

    `is_causal` (an extension over the reference signature, which only
    offers a dense additive attn_mask): decoder blocks should pass
    is_causal=True INSTEAD of a materialized [T, T] triangular mask —
    an additive mask disqualifies the Pallas flash kernel (it has no
    mask operand; see flash_attention_or_none) and silently lands the
    block on xla_sdpa at O(T²) memory."""
    B, T, E = x.shape
    three, H, Dh, _ = qkv_weight.shape
    assert three == 3 and H * Dh == E
    residual = x
    if pre_layer_norm:
        x = F.layer_norm(x, (E,), pre_ln_scale, pre_ln_bias, pre_ln_epsilon)
    # qkv: [B, T, E] @ [E, 3*E] -> [B, T, 3, H, Dh]
    w = qkv_weight.reshape((3 * E, E)).transpose((1, 0))
    qkv = m.matmul(x, w)
    if qkv_bias is not None:
        qkv = m.add(qkv, qkv_bias.reshape((3 * E,)))
    qkv = qkv.reshape((B, T, 3, H, Dh)).transpose((2, 0, 3, 1, 4))
    q, k, v = qkv[0], qkv[1], qkv[2]
    if cache_kv is not None:
        k = mp.concat([cache_kv[0], k], axis=2)
        v = mp.concat([cache_kv[1], v], axis=2)
    out, _ = F.scaled_dot_product_attention(
        q, k, v, attn_mask=attn_mask, dropout_p=attn_dropout_rate,
        is_causal=is_causal, training=training)
    out = out.transpose((0, 2, 1, 3)).reshape((B, T, E))
    out = F.linear(out, linear_weight)
    if not pre_layer_norm:
        # tail rides the fused Pallas chain: bias+dropout+residual+LN
        return fused_bias_dropout_residual_layer_norm(
            out, residual, linear_bias, ln_scale, ln_bias, dropout_rate,
            ln_epsilon, training, mode)
    if linear_bias is not None:
        out = m.add(out, linear_bias)
    out = F.dropout(out, dropout_rate, training=training, mode=mode)
    out = m.add(residual, out)
    return out
