"""NN primitives: activations, softmax, conv/pool, norms, dropout, embedding,
losses. Replaces the reference's operators/activation_op.cc, conv_op.cc,
pool_op.cc, batch_norm_op, layer_norm_op, dropout_op, lookup_table_v2,
softmax_with_cross_entropy (/root/reference/paddle/fluid/operators/).
Convs/matmuls go through lax conv/dot → MXU; elementwise epilogues fuse in XLA.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..framework.dispatch import primitive
from ..framework.flags import flag

# ---------------------------------------------------------------------------
# activations (reference activation_op.cc:1240-)


@primitive("relu")
def relu(x):
    return jnp.maximum(x, 0)


@primitive("relu6")
def relu6(x, *, threshold=6.0):
    return jnp.clip(x, 0, threshold)


@primitive("leaky_relu")
def leaky_relu(x, *, negative_slope=0.01):
    return jnp.where(x >= 0, x, negative_slope * x)


@primitive("prelu_op")
def prelu(x, weight, *, data_format="NCHW"):
    if weight.size == 1:
        w = weight.reshape(())
    elif data_format == "NCHW" and x.ndim >= 2:
        w = weight.reshape((1, -1) + (1,) * (x.ndim - 2))
    else:
        w = weight.reshape((1,) * (x.ndim - 1) + (-1,))
    return jnp.where(x >= 0, x, w * x)


@primitive("elu")
def elu(x, *, alpha=1.0):
    safe = jnp.where(x > 0, 0.0, x)
    return jnp.where(x > 0, x, alpha * jnp.expm1(safe))


@primitive("selu")
def selu(x, *, scale=1.0507009873554805, alpha=1.6732632423543772):
    safe = jnp.where(x > 0, 0.0, x)
    return scale * jnp.where(x > 0, x, alpha * jnp.expm1(safe))


@primitive("celu")
def celu(x, *, alpha=1.0):
    return jnp.maximum(x, 0) + jnp.minimum(0, alpha * jnp.expm1(jnp.minimum(x, 0) / alpha))


@primitive("gelu")
def gelu(x, *, approximate=False):
    return jax.nn.gelu(x, approximate=approximate)


@primitive("sigmoid")
def sigmoid(x):
    return jax.nn.sigmoid(x)


@primitive("silu")
def silu(x):
    return x * jax.nn.sigmoid(x)


@primitive("swish")
def swish(x):
    return x * jax.nn.sigmoid(x)


@primitive("tanh")
def tanh(x):
    return jnp.tanh(x)


@primitive("hardtanh")
def hardtanh(x, *, min=-1.0, max=1.0):
    return jnp.clip(x, min, max)


@primitive("hardshrink")
def hardshrink(x, *, threshold=0.5):
    return jnp.where(jnp.abs(x) > threshold, x, 0.0)


@primitive("softshrink")
def softshrink(x, *, threshold=0.5):
    return jnp.where(x > threshold, x - threshold,
                     jnp.where(x < -threshold, x + threshold, 0.0))


@primitive("tanhshrink")
def tanhshrink(x):
    return x - jnp.tanh(x)


@primitive("hardsigmoid")
def hardsigmoid(x, *, slope=1.0 / 6, offset=0.5):
    return jnp.clip(slope * x + offset, 0.0, 1.0)


@primitive("hardswish")
def hardswish(x, *, threshold=6.0, scale=6.0, offset=3.0):
    return x * jnp.clip(x + offset, 0.0, threshold) / scale


@primitive("mish")
def mish(x):
    return x * jnp.tanh(jax.nn.softplus(x))


@primitive("softplus")
def softplus(x, *, beta=1.0, threshold=20.0):
    scaled = beta * x
    return jnp.where(scaled > threshold, x, jax.nn.softplus(scaled) / beta)


@primitive("softsign")
def softsign(x):
    return x / (1.0 + jnp.abs(x))


@primitive("thresholded_relu")
def thresholded_relu(x, *, threshold=1.0):
    return jnp.where(x > threshold, x, 0.0)


@primitive("log_sigmoid")
def log_sigmoid(x):
    return jax.nn.log_sigmoid(x)


@primitive("maxout_op")
def maxout(x, *, groups, axis=1):
    c = x.shape[axis]
    new_shape = list(x.shape)
    new_shape[axis] = c // groups
    new_shape.insert(axis + 1, groups)
    return jnp.max(x.reshape(new_shape), axis=axis + 1)


@primitive("glu_op")
def glu(x, *, axis=-1):
    a, b = jnp.split(x, 2, axis=axis)
    return a * jax.nn.sigmoid(b)


# ---------------------------------------------------------------------------
# softmax family


@primitive("softmax_op")
def softmax(x, *, axis=-1):
    return jax.nn.softmax(x, axis=axis)


@primitive("log_softmax_op")
def log_softmax(x, *, axis=-1):
    return jax.nn.log_softmax(x, axis=axis)


@primitive("gumbel_softmax_op")
def _gumbel_softmax(x, key, *, temperature=1.0, hard=False, axis=-1):
    g = jax.random.gumbel(key, x.shape, x.dtype)
    y = jax.nn.softmax((x + g) / temperature, axis=axis)
    if hard:
        idx = jnp.argmax(y, axis=axis, keepdims=True)
        y_hard = jnp.zeros_like(y)
        y_hard = jnp.put_along_axis(y_hard, idx, 1.0, axis=axis, inplace=False)
        y = y_hard + lax.stop_gradient(-y) + y  # straight-through
    return y


# ---------------------------------------------------------------------------
# conv / pool (reference conv_op.cc / pool_op.cc; lax → MXU)


def _conv_dn(ndim, channel_last):
    if ndim == 3:
        return ("NWC", "WIO", "NWC") if channel_last else ("NCW", "OIW", "NCW")
    if ndim == 4:
        return ("NHWC", "HWIO", "NHWC") if channel_last else ("NCHW", "OIHW", "NCHW")
    return ("NDHWC", "DHWIO", "NDHWC") if channel_last else ("NCDHW", "OIDHW", "NCDHW")


def _conv_im2col(x, w, stride, pad, dilation, channel_last):
    """Convolution as one big matmul: extract patches (a conv against an
    identity kernel — cheap, bandwidth-bound) then contract all (cin·kh·kw)
    taps in a single MXU-shaped dot. Flag-gated alternative to the direct
    lax.conv lowering (FLAGS_conv_algo=im2col): it answers whether a
    matmul-routed conv beats the direct lowering on a given chip (reference
    analogue: the im2col path in conv_op.cc / math/im2col.cc that cuDNN
    replaced)."""
    nd = x.ndim
    spec = _conv_dn(nd, channel_last)
    dn = lax.conv_dimension_numbers(x.shape, w.shape, spec)
    nsp = nd - 2
    k = [w.shape[dn.rhs_spec[2 + i]] for i in range(nsp)]
    patches = lax.conv_general_dilated_patches(
        x, filter_shape=k, window_strides=tuple(stride), padding=pad,
        rhs_dilation=tuple(dilation),
        dimension_numbers=dn)
    # patches features = (cin, *k) flattened, in the layout's feature dim
    cin = x.shape[dn.lhs_spec[1]]
    cout = w.shape[dn.rhs_spec[0]]
    # weight → [cout, cin*prod(k)]: move O first, I and taps after, in the
    # same (cin, *k) order as the patches features
    perm = (dn.rhs_spec[0], dn.rhs_spec[1]) + tuple(dn.rhs_spec[2:])
    w2 = jnp.transpose(w, perm).reshape(cout, -1)
    if channel_last:   # patches [N, *sp, cin*k]
        out = jnp.einsum("...f,of->...o", patches, w2,
                         preferred_element_type=jnp.float32)
    else:              # patches [N, cin*k, *sp]
        out = jnp.einsum("nf...,of->no...", patches, w2,
                         preferred_element_type=jnp.float32)
    # dtype contract matches the direct path below: bf16 convs return f32
    # (the explicit BN-stats upcast), every other dtype rounds back to
    # x.dtype after the f32 accumulation — flipping FLAGS_conv_algo must
    # never change a model's activation dtypes
    return out if x.dtype == jnp.bfloat16 else out.astype(x.dtype)


def _note_conv_path(algo):
    """Trace-time conv lowering counter (pt_conv_path_total{algo=}) —
    like attention's _note_attn_path, so BENCH artifacts and ptdoctor can
    show which lowering a run actually compiled, not just the flag."""
    try:
        from ..observability import metrics
        metrics.counter("pt_conv_path_total",
                        "conv lowerings traced, by algorithm",
                        labelnames=("algo",)).labels(algo).inc()
    except Exception:
        pass


def _conv_nhwc(x, w, stride, pad, dilation, groups):
    """4-D NCHW conv computed internally in NHWC/HWIO — XLA-TPU's native
    conv layout. The model keeps its NCHW activations; the explicit
    transposes bracket the conv so consecutive conv layers' NHWC→NCHW →
    NCHW→NHWC pairs cancel in XLA's algebraic simplifier, where the NCHW
    dimension-numbers form forced the TPU backend into a per-layer
    relayout of every activation AND filter (the r3 resnet50 "MFU 0.003"
    — a ~50x layout tax, not a conv-speed problem)."""
    xt = jnp.transpose(x, (0, 2, 3, 1))            # NCHW -> NHWC
    wt = jnp.transpose(w, (2, 3, 1, 0))            # OIHW -> HWIO
    dn = lax.conv_dimension_numbers(xt.shape, wt.shape,
                                    ("NHWC", "HWIO", "NHWC"))
    out = lax.conv_general_dilated(
        xt, wt, window_strides=tuple(stride), padding=pad,
        rhs_dilation=tuple(dilation), dimension_numbers=dn,
        feature_group_count=groups)
    return jnp.transpose(out, (0, 3, 1, 2))        # NHWC -> NCHW


@primitive("conv2d_op")
def conv(x, w, *, stride=(1, 1), padding=(0, 0), dilation=(1, 1), groups=1,
         channel_last=False, algo="direct"):
    nd = x.ndim
    spec = _conv_dn(nd, channel_last)
    if isinstance(padding, str):
        pad = padding  # 'SAME' / 'VALID'
    else:
        pad = [(p, p) if isinstance(p, int) else tuple(p) for p in padding]
    if algo == "auto":
        # NHWC-internal only where the layout tax exists: TPU, 4-D, model
        # in NCHW. Everywhere else (CPU tier-1, 3-D/5-D, channel_last
        # models already in the native layout) auto == direct.
        algo = ("nhwc" if nd == 4 and not channel_last
                and jax.default_backend() == "tpu" else "direct")
    _note_conv_path(algo)
    if algo == "im2col" and groups == 1:
        return _conv_im2col(x, w, stride, pad, dilation, channel_last)
    if algo == "nhwc":
        out = _conv_nhwc(x, w, stride, pad, dilation, groups)
        # same dtype contract as the direct path below
        return out.astype(jnp.float32) if x.dtype == jnp.bfloat16 else out
    dn = lax.conv_dimension_numbers(x.shape, w.shape, spec)
    out = lax.conv_general_dilated(
        x, w, window_strides=tuple(stride), padding=pad,
        rhs_dilation=tuple(dilation), dimension_numbers=dn,
        feature_group_count=groups)
    # bf16 convs feed f32 consumers (BN stats etc.); upcast via an explicit
    # convert rather than preferred_element_type=f32 — the latter makes the
    # conv TRANSPOSE rule mix an f32 cotangent with bf16 operands, which
    # lax rejects (verified: grad of preferred-f32 bf16 conv TypeErrors)
    if x.dtype == jnp.bfloat16:
        out = out.astype(jnp.float32)
    return out


@primitive("conv2d_transpose_op")
def conv_transpose(x, w, *, stride=(1, 1), padding=(0, 0),
                   output_padding=(0, 0), dilation=(1, 1), groups=1,
                   channel_last=False):
    nd = x.ndim
    spec = _conv_dn(nd, channel_last)
    dn = lax.conv_dimension_numbers(x.shape, w.shape, spec)
    nsp = nd - 2
    stride = tuple(stride)
    padding = [(p, p) if isinstance(p, int) else tuple(p) for p in padding]
    dilation = tuple(dilation)
    outpad = tuple(output_padding) if not isinstance(output_padding, int) \
        else (output_padding,) * nsp
    # transposed conv = lhs-dilated conv with flipped effective padding
    k = [(w.shape[dn.rhs_spec[2 + i]] - 1) * dilation[i] + 1 for i in range(nsp)]
    pads = [(k[i] - 1 - padding[i][0],
             k[i] - 1 - padding[i][1] + outpad[i]) for i in range(nsp)]
    if groups > 1:
        # w layout (paddle transpose): (in, out/groups, *k) -> grouped OIHW
        ci = w.shape[0]
        co_g = w.shape[1]
        wg = w.reshape((groups, ci // groups) + w.shape[1:])
        wg = jnp.swapaxes(wg, 1, 2)  # (g, out/g, in/g, *k)
        w2 = wg.reshape((groups * co_g, ci // groups) + w.shape[2:])
    else:
        w2 = jnp.swapaxes(w, 0, 1)
    w2 = jnp.flip(w2, axis=tuple(range(2, nd)))
    return lax.conv_general_dilated(
        x, w2, window_strides=(1,) * nsp, padding=pads,
        lhs_dilation=stride, rhs_dilation=dilation,
        dimension_numbers=dn, feature_group_count=groups)


@primitive("pool2d_op")
def pool(x, *, pool_type="max", kernel=(2, 2), stride=(2, 2), padding=(0, 0),
         ceil_mode=False, exclusive=True, channel_last=False):
    nsp = x.ndim - 2
    kernel = tuple(kernel)
    stride = tuple(stride)
    pads = [(p, p) if isinstance(p, int) else tuple(p) for p in padding]
    if channel_last:
        dims = (1,) + kernel + (1,)
        strides = (1,) + stride + (1,)
        padcfg = [(0, 0)] + pads + [(0, 0)]
    else:
        dims = (1, 1) + kernel
        strides = (1, 1) + stride
        padcfg = [(0, 0), (0, 0)] + pads
    if ceil_mode:
        # extend high padding so the last partial window is included
        sp_axes = range(1, 1 + nsp) if channel_last else range(2, 2 + nsp)
        newpad = list(padcfg)
        for i, ax in enumerate(sp_axes):
            size = x.shape[ax]
            k, s = kernel[i], stride[i]
            lo, hi = pads[i]
            out = -(-(size + lo + hi - k) // s) + 1
            need = (out - 1) * s + k - (size + lo + hi)
            j = ax
            newpad[j] = (lo, hi + max(need, 0))
        padcfg = newpad
    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        return lax.reduce_window(x, init, lax.max, dims, strides, padcfg)
    # avg pool
    s = lax.reduce_window(x, 0.0, lax.add, dims, strides, padcfg)
    if exclusive:
        # Per-position divisor (count of non-pad elements in each window) is a
        # static function of the shapes — build it with numpy at trace time so
        # XLA never has to fold a reduce_window over a ones tensor (which is
        # pathologically slow for the constant folder on large activations).
        sp_axes = (range(1, 1 + nsp) if channel_last
                   else range(2, 2 + nsp))
        per_axis = []
        for i, ax in enumerate(sp_axes):
            size = x.shape[ax]
            lo, hi = padcfg[ax]
            k, st = kernel[i], stride[i]
            n_out = (size + lo + hi - k) // st + 1
            start = np.arange(n_out) * st - lo
            c = np.minimum(start + k, size) - np.maximum(start, 0)
            per_axis.append(np.maximum(c, 1))
        cnt_sp = per_axis[0]
        for c in per_axis[1:]:
            cnt_sp = cnt_sp[..., None] * c
        shape = ((1,) + cnt_sp.shape + (1,) if channel_last
                 else (1, 1) + cnt_sp.shape)
        cnt = jnp.asarray(cnt_sp.reshape(shape).astype(np.float32),
                          dtype=s.dtype)
    else:
        cnt = float(np.prod(kernel))
    return s / cnt


@primitive("adaptive_pool2d_op")
def adaptive_pool(x, *, output_size, pool_type="avg", channel_last=False):
    nsp = x.ndim - 2
    out_sizes = tuple(output_size)
    sp_axes = tuple(range(1, 1 + nsp)) if channel_last else tuple(range(2, 2 + nsp))
    # when input divides evenly, use a plain pool; else mean over index buckets
    result = x
    for i, ax in enumerate(sp_axes):
        in_s, out_s = result.shape[ax], out_sizes[i]
        if out_s is None or out_s == in_s:
            continue
        if in_s % out_s == 0:
            k = in_s // out_s
            shape = result.shape[:ax] + (out_s, k) + result.shape[ax + 1:]
            r = result.reshape(shape)
            result = jnp.max(r, axis=ax + 1) if pool_type == "max" else jnp.mean(r, axis=ax + 1)
        else:
            starts = (np.arange(out_s) * in_s) // out_s
            ends = ((np.arange(out_s) + 1) * in_s + out_s - 1) // out_s
            pieces = []
            for s0, e0 in zip(starts, ends):
                seg = lax.slice_in_dim(result, int(s0), int(e0), axis=ax)
                red = jnp.max(seg, axis=ax, keepdims=True) if pool_type == "max" \
                    else jnp.mean(seg, axis=ax, keepdims=True)
                pieces.append(red)
            result = jnp.concatenate(pieces, axis=ax)
    return result


@primitive("unfold_op")
def unfold(x, *, kernel_sizes, strides=(1, 1), paddings=(0, 0), dilations=(1, 1)):
    n, c, h, w = x.shape
    kh, kw = kernel_sizes
    patches = lax.conv_general_dilated_patches(
        x, filter_shape=(kh, kw), window_strides=tuple(strides),
        padding=[(paddings[0], paddings[0]), (paddings[1], paddings[1])]
        if len(paddings) == 2 else [(paddings[0], paddings[1]), (paddings[2], paddings[3])],
        rhs_dilation=tuple(dilations),
        dimension_numbers=lax.conv_dimension_numbers(
            x.shape, (1, c, kh, kw), ("NCHW", "OIHW", "NCHW")))
    n2, ckk, oh, ow = patches.shape
    return patches.reshape(n2, ckk, oh * ow)


# ---------------------------------------------------------------------------
# normalization (reference batch_norm_op.cu, layer_norm_op.cu, group_norm)


@primitive("layer_norm_op")
def layer_norm(x, weight, bias, *, epsilon=1e-5, begin_norm_axis=-1):
    axes = tuple(range(begin_norm_axis % x.ndim, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=axes, keepdims=True)
    y = (x - mean) * lax.rsqrt(var + epsilon)
    if weight is not None:
        y = y * weight
    if bias is not None:
        y = y + bias
    return y


@primitive("batch_norm_infer")
def batch_norm_infer(x, weight, bias, mean, var, *, epsilon=1e-5,
                     channel_last=False):
    shape = ((1,) * (x.ndim - 1) + (-1,)) if channel_last \
        else ((1, -1) + (1,) * (x.ndim - 2))
    inv = lax.rsqrt(var.reshape(shape) + epsilon)
    y = (x - mean.reshape(shape)) * inv
    if weight is not None:
        y = y * weight.reshape(shape)
    if bias is not None:
        y = y + bias.reshape(shape)
    return y


@primitive("batch_norm_train")
def batch_norm_train(x, weight, bias, *, epsilon=1e-5, channel_last=False):
    """Returns (y, batch_mean, batch_var); running stats updated by the Layer
    (functional style — the reference mutates mean/var in-kernel)."""
    axes = tuple(i for i in range(x.ndim)
                 if i != (x.ndim - 1 if channel_last else 1))
    mean = jnp.mean(x, axis=axes)
    var = jnp.mean(jnp.square(x), axis=axes) - jnp.square(mean)
    shape = ((1,) * (x.ndim - 1) + (-1,)) if channel_last \
        else ((1, -1) + (1,) * (x.ndim - 2))
    inv = lax.rsqrt(var.reshape(shape) + epsilon)
    y = (x - mean.reshape(shape)) * inv
    if weight is not None:
        y = y * weight.reshape(shape)
    if bias is not None:
        y = y + bias.reshape(shape)
    return y, mean, var


@primitive("batch_norm_train_stats")
def batch_norm_train_stats(x, weight, bias, run_mean, run_var, *,
                           momentum=0.9, epsilon=1e-5, channel_last=False):
    """Training BN that also emits updated running stats — the static-graph
    form (reference: batch_norm op's MeanOut/VarianceOut outputs)."""
    axes = tuple(i for i in range(x.ndim)
                 if i != (x.ndim - 1 if channel_last else 1))
    mean = jnp.mean(x, axis=axes)
    var = jnp.mean(jnp.square(x), axis=axes) - jnp.square(mean)
    shape = ((1,) * (x.ndim - 1) + (-1,)) if channel_last \
        else ((1, -1) + (1,) * (x.ndim - 2))
    inv = lax.rsqrt(var.reshape(shape) + epsilon)
    y = (x - mean.reshape(shape)) * inv
    if weight is not None:
        y = y * weight.reshape(shape)
    if bias is not None:
        y = y + bias.reshape(shape)
    m = momentum
    new_rm = m * run_mean + (1 - m) * lax.stop_gradient(mean)
    new_rv = m * run_var + (1 - m) * lax.stop_gradient(var)
    return y, new_rm, new_rv


@primitive("instance_norm_op")
def instance_norm(x, weight, bias, *, epsilon=1e-5):
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    y = (x - mean) * lax.rsqrt(var + epsilon)
    shape = (1, -1) + (1,) * (x.ndim - 2)
    if weight is not None:
        y = y * weight.reshape(shape)
    if bias is not None:
        y = y + bias.reshape(shape)
    return y


@primitive("group_norm_op")
def group_norm(x, weight, bias, *, num_groups, epsilon=1e-5,
               channel_last=False):
    if channel_last:
        x_t = jnp.moveaxis(x, -1, 1)
    else:
        x_t = x
    n, c = x_t.shape[:2]
    g = num_groups
    xr = x_t.reshape((n, g, c // g) + x_t.shape[2:])
    axes = tuple(range(2, xr.ndim))
    mean = jnp.mean(xr, axis=axes, keepdims=True)
    var = jnp.var(xr, axis=axes, keepdims=True)
    y = ((xr - mean) * lax.rsqrt(var + epsilon)).reshape(x_t.shape)
    shape = (1, -1) + (1,) * (x_t.ndim - 2)
    if weight is not None:
        y = y * weight.reshape(shape)
    if bias is not None:
        y = y + bias.reshape(shape)
    if channel_last:
        y = jnp.moveaxis(y, 1, -1)
    return y


@primitive("l2_normalize_op")
def normalize(x, *, p=2.0, axis=1, epsilon=1e-12):
    norm = jnp.linalg.norm(x, ord=p, axis=axis, keepdims=True)
    return x / jnp.maximum(norm, epsilon)


@primitive("local_response_norm_op")
def local_response_norm(x, *, size, alpha=1e-4, beta=0.75, k=1.0):
    sq = jnp.square(x)
    half = size // 2
    c = x.shape[1]
    padded = jnp.pad(sq, [(0, 0), (half, size - 1 - half)] + [(0, 0)] * (x.ndim - 2))
    acc = sum(lax.slice_in_dim(padded, i, i + c, axis=1) for i in range(size))
    return x / jnp.power(k + alpha * acc / size, beta)


# ---------------------------------------------------------------------------
# dropout (functional PRNG — key threaded by dispatch wrapper)


@primitive("dropout_op")
def _dropout(x, key, *, p=0.5, mode="upscale_in_train"):
    keep = 1.0 - p
    # float32 p: under the package's global x64 a python float would make
    # bernoulli draw its uniforms in f64, which a TPU emulates in software
    mask = jax.random.bernoulli(key, jnp.float32(keep), x.shape)
    if mode == "upscale_in_train":
        return jnp.where(mask, x / keep, 0.0)
    return jnp.where(mask, x, 0.0)


@primitive("alpha_dropout_op")
def _alpha_dropout(x, key, *, p=0.5):
    alpha = 1.6732632423543772
    scale = 1.0507009873554805
    alpha_p = -alpha * scale
    keep = 1.0 - p
    a = (keep + alpha_p**2 * keep * (1 - keep)) ** -0.5
    b = -a * alpha_p * (1 - keep)
    mask = jax.random.bernoulli(key, jnp.float32(keep), x.shape)
    return a * jnp.where(mask, x, alpha_p) + b


# ---------------------------------------------------------------------------
# embedding (reference lookup_table_v2_op)


@primitive("lookup_table_v2")
def embedding_lookup(weight, ids, *, padding_idx=None):
    out = jnp.take(weight, ids.astype(jnp.int32), axis=0)
    if padding_idx is not None and padding_idx >= 0:
        mask = (ids == padding_idx)[..., None]
        out = jnp.where(mask, 0.0, out)
    return out


@primitive("lookup_table_v2_sparse")
def embedding_lookup_sparse(weight, ids, *, padding_idx=None):
    """Same forward as lookup_table_v2; its tape backward (registered in
    framework.autograd.SPARSE_VJPS) emits a row-sparse SelectedRows
    cotangent for `weight` instead of a dense [V, D] scatter — the
    reference's is_sparse branch of lookup_table_v2_grad
    (paddle/fluid/operators/lookup_table_v2_op.h)."""
    return embedding_lookup.fn(weight, ids, padding_idx=padding_idx)


def _embedding_sparse_vjp(in_arrays, cts, attrs):
    from ..framework.selected_rows import SelectedRows
    weight, ids = in_arrays
    ct = cts[0]
    padding_idx = attrs.get("padding_idx")
    rows = ids.astype(jnp.int32).reshape(-1)
    vals = ct.reshape(-1, ct.shape[-1]).astype(weight.dtype)
    if padding_idx is not None and padding_idx >= 0:
        vals = jnp.where((rows == padding_idx)[:, None], 0.0, vals)
    return (SelectedRows(rows, vals, weight.shape[0]), None)


def _register_sparse_vjps():
    from ..framework.autograd import SPARSE_VJPS
    SPARSE_VJPS["lookup_table_v2_sparse"] = _embedding_sparse_vjp


_register_sparse_vjps()


@primitive("one_hot_v2", nondiff=True)
def one_hot(x, *, num_classes):
    return jax.nn.one_hot(x.astype(jnp.int32), num_classes)


# ---------------------------------------------------------------------------
# losses (reference softmax_with_cross_entropy_op.cu, bce ops, etc.)


@primitive("softmax_with_cross_entropy")
def softmax_with_cross_entropy(logits, label, *, soft_label=False,
                               ignore_index=-100, axis=-1):
    logp = jax.nn.log_softmax(logits, axis=axis)
    if soft_label:
        loss = -jnp.sum(label * logp, axis=axis, keepdims=True)
    else:
        lab = label.astype(jnp.int32)
        if lab.ndim == logits.ndim and lab.shape[axis] == 1:
            lab = jnp.squeeze(lab, axis=axis)
        picked = jnp.take_along_axis(
            logp, jnp.expand_dims(jnp.clip(lab, 0, None), axis), axis=axis)
        loss = -picked
        if ignore_index >= 0 or True:
            mask = jnp.expand_dims(lab == ignore_index, axis)
            loss = jnp.where(mask, 0.0, loss)
    return loss


@primitive("bce_loss_op")
def bce_loss(input, label):
    eps = 1e-12
    x = jnp.clip(input, eps, 1.0 - eps)
    return -(label * jnp.log(x) + (1.0 - label) * jnp.log1p(-x))


@primitive("bce_with_logits_op")
def bce_with_logits(logit, label, pos_weight=None):
    max_val = jnp.clip(-logit, 0, None)
    if pos_weight is not None:
        log_w = (pos_weight - 1.0) * label + 1.0
        loss = (1.0 - label) * logit + log_w * (
            jnp.log1p(jnp.exp(-jnp.abs(logit))) + max_val)
    else:
        loss = (1.0 - label) * logit + max_val + jnp.log1p(
            jnp.exp(-jnp.abs(logit)))
    return loss


@primitive("kldiv_loss_op")
def kldiv_loss(x, target):
    safe_t = jnp.where(target > 0, target, 1.0)
    return jnp.where(target > 0, target * (jnp.log(safe_t) - x), 0.0)


@primitive("huber_loss_op")
def huber_loss(input, label, *, delta=1.0):
    r = jnp.abs(input - label)
    return jnp.where(r <= delta, 0.5 * r * r, delta * (r - 0.5 * delta))


@primitive("smooth_l1_op")
def smooth_l1(input, label, *, delta=1.0):
    r = jnp.abs(input - label)
    return jnp.where(r < delta, 0.5 * r * r / delta, r - 0.5 * delta)


@primitive("nll_loss_op")
def nll_loss(log_prob, label, *, ignore_index=-100):
    lab = label.astype(jnp.int32)
    picked = jnp.take_along_axis(log_prob, jnp.clip(lab, 0, None)[:, None], axis=1)[:, 0]
    loss = -picked
    return jnp.where(lab == ignore_index, 0.0, loss)


@primitive("margin_ranking_loss_op")
def margin_ranking_loss(input, other, label, *, margin=0.0):
    return jnp.clip(-label * (input - other) + margin, 0, None)


@primitive("cosine_similarity_op")
def cosine_similarity(x1, x2, *, axis=1, eps=1e-8):
    dot = jnp.sum(x1 * x2, axis=axis)
    n1 = jnp.linalg.norm(x1, axis=axis)
    n2 = jnp.linalg.norm(x2, axis=axis)
    return dot / jnp.maximum(n1 * n2, eps)


@primitive("hinge_embedding_loss_op")
def hinge_embedding_loss(input, label, *, margin=1.0):
    return jnp.where(label == 1.0, input,
                     jnp.clip(margin - input, 0, None))


@primitive("square_error_cost_op")
def square_error_cost(input, label):
    return jnp.square(input - label)


@primitive("label_smooth_op")
def label_smooth(label, *, epsilon=0.1):
    k = label.shape[-1]
    return (1.0 - epsilon) * label + epsilon / k


# ---------------------------------------------------------------------------
# interpolate / vision-adjacent


@primitive("interp_op")
def interpolate(x, *, size, mode="nearest", align_corners=False,
                channel_last=False):
    nsp = x.ndim - 2
    size = tuple(size)
    if channel_last:
        new_shape = (x.shape[0],) + size + (x.shape[-1],)
        sp_axes = tuple(range(1, 1 + nsp))
    else:
        new_shape = x.shape[:2] + size
        sp_axes = tuple(range(2, 2 + nsp))
    method = {"nearest": "nearest", "bilinear": "linear", "linear": "linear",
              "trilinear": "linear", "bicubic": "cubic", "area": "linear"}[mode]
    if align_corners and method != "nearest":
        out = x
        for i, ax in enumerate(sp_axes):
            in_s, out_s = x.shape[ax], size[i]
            idx = jnp.linspace(0.0, in_s - 1, out_s)
            lo = jnp.floor(idx).astype(jnp.int32)
            hi = jnp.clip(lo + 1, 0, in_s - 1)
            w = (idx - lo).reshape((-1,) + (1,) * (out.ndim - ax - 1))
            a = jnp.take(out, lo, axis=ax)
            b = jnp.take(out, hi, axis=ax)
            out = a * (1 - w) + b * w
        return out
    return jax.image.resize(x, new_shape, method=method)


@primitive("pixel_shuffle_op")
def pixel_shuffle(x, *, upscale_factor, channel_last=False):
    r = upscale_factor
    if channel_last:
        n, h, w, c = x.shape
        out = x.reshape(n, h, w, r, r, c // (r * r))
        out = out.transpose(0, 1, 3, 2, 4, 5)
        return out.reshape(n, h * r, w * r, c // (r * r))
    n, c, h, w = x.shape
    out = x.reshape(n, c // (r * r), r, r, h, w)
    out = out.transpose(0, 1, 4, 2, 5, 3)
    return out.reshape(n, c // (r * r), h * r, w * r)


@primitive("pixel_unshuffle_op")
def pixel_unshuffle(x, *, downscale_factor, channel_last=False):
    """Inverse of pixel_shuffle (reference: space_to_depth_op.cc /
    pixel_unshuffle): blocks of r x r pixels move into channels."""
    r = downscale_factor
    if channel_last:
        n, h, w, c = x.shape
        out = x.reshape(n, h // r, r, w // r, r, c)
        out = out.transpose(0, 1, 3, 2, 4, 5)
        return out.reshape(n, h // r, w // r, c * r * r)
    n, c, h, w = x.shape
    out = x.reshape(n, c, h // r, r, w // r, r)
    out = out.transpose(0, 1, 3, 5, 2, 4)
    return out.reshape(n, c * r * r, h // r, w // r)


@primitive("channel_shuffle_op")
def channel_shuffle(x, *, groups, channel_last=False):
    if channel_last:
        n, h, w, c = x.shape
        out = x.reshape(n, h, w, groups, c // groups)
        return jnp.swapaxes(out, -1, -2).reshape(n, h, w, c)
    n, c, h, w = x.shape
    out = x.reshape(n, groups, c // groups, h, w)
    return jnp.swapaxes(out, 1, 2).reshape(n, c, h, w)


@primitive("pad2d_zero_op")
def zero_pad(x, *, padding, channel_last=False):
    l, r, t, b = padding
    if channel_last:
        return jnp.pad(x, ((0, 0), (t, b), (l, r), (0, 0)))
    return jnp.pad(x, ((0, 0), (0, 0), (t, b), (l, r)))


# ---------------------------------------------------------------------------
# fused inference primitives emitted by the export-time fusion passes
# (static/passes.py fc_fuse_pass / fuse_elewise_add_act_pass — reference:
# ir/fc_fuse_pass.cc:1, ir/fuse_elewise_add_act_pass.cc:1). At run time XLA
# fuses these anyway; the win is a smaller exported artifact and a single
# quantizable matmul site for the int8 path.


@primitive("fc_op")
def fc(x, w, b, *, transpose_x=False, transpose_y=False):
    if transpose_x and x.ndim > 1:
        x = jnp.swapaxes(x, -1, -2)
    if transpose_y and w.ndim > 1:
        w = jnp.swapaxes(w, -1, -2)
    return jnp.matmul(x, w) + b


@primitive("fused_elemwise_add_act")
def fused_add_act(x, y, *, act="relu", act_attrs=None):
    from ..framework.dispatch import OPS

    return OPS[act].fn(jnp.add(x, y), **(act_attrs or {}))


# ---------------------------------------------------------------------------
# scaled dot-product attention (plain XLA path; the Pallas flash kernel in
# ops/pallas_kernels.py takes over on TPU for long sequences — reference
# analogue: operators/fused/fused_attention_op.cu / multihead_matmul_op.cu)


@primitive("scaled_dot_product_attention")
def sdpa(q, k, v, mask, key, *, dropout_p=0.0, causal=False,
         return_weights=False, chunked=None):
    """q/k/v: [B, H, T, D]; mask: additive float, broadcastable to
    [B, H, Tq, Tk].

    Long sequences with no additive mask / weights request / dropout
    route to the blockwise online-softmax path — O(Tq·block) live memory
    fwd AND bwd instead of the [Tq, Tk] matrix — so long-context stays
    usable even where the Pallas flash kernel does not run (CPU, masks,
    ineligible shapes). `chunked` is an ATTR (part of the jit cache
    key): callers decide per call, typically Tk >=
    FLAGS_sdpa_chunked_threshold (what chunked=None falls back to — but
    the fallback reads the flag at trace time, so flag changes do not
    invalidate already-compiled shapes; the functional gate passes a
    concrete bool for exactly that reason)."""
    d = q.shape[-1]
    if chunked is None:
        thr = flag("sdpa_chunked_threshold")
        chunked = bool(thr and k.shape[-2] >= thr)
    from .pallas_kernels import _note_attn_path
    if (chunked and mask is None
            and not return_weights
            # dropout rides the blockwise path (per-block fold_in masks,
            # numerator-only — see _blockwise_attention); p>=1 drops
            # everything and keeps the dense path's exact zeros-semantics
            and not (dropout_p >= 1.0 and key is not None)
            # blockwise causal masking assumes the self-attention Tq==Tk
            # alignment; the dense path's decode convention (diagonal
            # pinned at the END for Tq<Tk) stays on the dense path
            and (not causal or q.shape[-2] == k.shape[-2])):
        from .ring_attention import _blockwise_attention
        _note_attn_path("xla_chunked")
        return _blockwise_attention(q, k, v, causal=bool(causal),
                                    scale=float(d) ** -0.5,
                                    checkpoint_blocks=True,
                                    dropout_p=float(dropout_p),
                                    dropout_key=key)
    _note_attn_path("xla_sdpa")
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (float(d) ** -0.5)
    if causal:
        Tq, Tk = s.shape[-2], s.shape[-1]
        cm = jnp.tril(jnp.ones((Tq, Tk), bool), k=Tk - Tq)
        s = jnp.where(cm, s, jnp.asarray(-1e9, s.dtype))
    if mask is not None:
        s = s + mask
    w = jax.nn.softmax(s, axis=-1)
    if dropout_p > 0.0 and key is not None:
        keep = jax.random.bernoulli(key, jnp.float32(1.0 - dropout_p),
                                    w.shape)
        w = jnp.where(keep, w / (1.0 - dropout_p), 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", w, v)
    if return_weights:
        return out, w
    return out


@primitive("masked_sdpa")
def masked_sdpa(q, k, v, add_mask):
    """Dense attention with a precomputed ADDITIVE mask (used by
    F.sparse_attention; rows that are fully masked produce zeros, matching
    the reference sparse kernel's empty-row behavior)."""
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (float(d) ** -0.5) + add_mask
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - jax.lax.stop_gradient(m))
    e = jnp.where(add_mask <= -1e29, 0.0, e)
    denom = jnp.sum(e, axis=-1, keepdims=True)
    w = e / jnp.maximum(denom, 1e-30)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v)


@primitive("warpctc")
def ctc_loss_op(log_probs, labels, input_lengths, label_lengths, *,
                blank=0):
    """CTC loss, log-space forward algorithm via lax.scan
    (reference: operators/warpctc_op.* wrapping warp-ctc; here the DP runs
    as one compiled scan over time — TPU-friendly, differentiable by jax).

    Numerics: alpha is renormalized each step (per-sample max subtracted and
    accumulated separately), so values stay O(1) regardless of T/C and the
    masked-state surrogate (-1e4 relative) can never outweigh a real path.

    log_probs: [T, B, C] log-softmax scores; labels: [B, L] int padded;
    input_lengths/label_lengths: [B]. Returns per-sample negative log
    likelihood [B]."""
    T, B, C = log_probs.shape
    L = labels.shape[1]
    S = 2 * L + 1
    # "impossible" surrogate RELATIVE to the renormalized alpha (max 0):
    # finite so grads through masked paths are exactly 0 in f32
    neg_inf = jnp.asarray(-1e4, jnp.float32)

    # extended label sequence with blanks: [B, S]
    ext = jnp.full((B, S), blank, jnp.int32)
    ext = ext.at[:, 1::2].set(labels.astype(jnp.int32))
    # positions beyond 2*label_len+1 are invalid
    s_idx = jnp.arange(S)[None, :]
    valid = s_idx < (2 * label_lengths[:, None] + 1)

    # allow skip from s-2 when ext[s] != blank and ext[s] != ext[s-2]
    ext_m2 = jnp.concatenate([jnp.full((B, 2), -1, jnp.int32),
                              ext[:, :-2]], axis=1)
    can_skip = (ext != blank) & (ext != ext_m2)

    b_range = jnp.arange(B)

    alpha0 = jnp.full((B, S), neg_inf)
    lp0 = log_probs[0]                                # [B, C]
    alpha0 = alpha0.at[:, 0].set(lp0[b_range, ext[:, 0]])
    has_lab = (label_lengths > 0)
    alpha0 = alpha0.at[:, 1].set(
        jnp.where(has_lab, lp0[b_range, ext[:, 1]], neg_inf))
    m0 = jnp.max(alpha0, axis=1)
    alpha0 = jnp.where(valid, alpha0 - m0[:, None], neg_inf)
    shift0 = m0

    def lse3(a, b, c):
        m = jnp.maximum(jnp.maximum(a, b), c)
        return m + jnp.log(jnp.exp(a - m) + jnp.exp(b - m) +
                           jnp.exp(c - m))

    def masked_step(carry, lp_t):
        alpha, shift, t = carry
        shift1 = jnp.concatenate(
            [jnp.full((B, 1), neg_inf), alpha[:, :-1]], axis=1)
        shift2 = jnp.concatenate(
            [jnp.full((B, 2), neg_inf), alpha[:, :-2]], axis=1)
        shift2 = jnp.where(can_skip, shift2, neg_inf)
        em = lp_t[b_range[:, None], ext]              # [B, S]
        new = lse3(alpha, shift1, shift2) + em
        m = jnp.maximum(jnp.max(new, axis=1), neg_inf)  # renormalize
        new = jnp.where(valid, new - m[:, None], neg_inf)
        # freeze sequences past their input length
        keep = (t < input_lengths)
        alpha_out = jnp.where(keep[:, None], new, alpha)
        shift_out = jnp.where(keep, shift + m, shift)
        return (alpha_out, shift_out, t + 1), ()

    (alpha_T, shift_T, _), _ = jax.lax.scan(
        masked_step, (alpha0, shift0, jnp.int32(1)), log_probs[1:])
    # final: alpha at last blank + last label state
    endb = 2 * label_lengths                           # index of final blank
    endl = jnp.maximum(endb - 1, 0)
    a_b = alpha_T[b_range, endb]
    a_l = jnp.where(label_lengths > 0, alpha_T[b_range, endl], neg_inf)
    m = jnp.maximum(a_b, a_l)
    ll = shift_T + m + jnp.log(jnp.exp(a_b - m) + jnp.exp(a_l - m))
    return -ll


@primitive("max_pool2d_with_index")
def max_pool2d_with_index(x, *, kernel, stride, padding):
    """Max pool returning (values, flat spatial argmax indices) —
    reference: operators/max_pool_with_index_op (the mask consumed by
    unpool). `padding` is explicit (lo, hi) pairs per spatial dim (the
    functional layer resolves SAME/VALID/ceil_mode to pairs). Patch
    extraction + argmax keeps shapes static for XLA."""
    n, c, h, w = x.shape
    kh, kw = kernel
    sh, sw = stride
    (ph0, ph1), (pw0, pw1) = padding
    neg = jnp.finfo(x.dtype).min if jnp.issubdtype(x.dtype, jnp.floating) \
        else jnp.iinfo(x.dtype).min
    xp = jnp.pad(x, ((0, 0), (0, 0), (ph0, ph1), (pw0, pw1)),
                 constant_values=neg)
    patches = lax.conv_general_dilated_patches(
        xp, filter_shape=(kh, kw), window_strides=(sh, sw),
        padding=[(0, 0), (0, 0)],
        dimension_numbers=lax.conv_dimension_numbers(
            xp.shape, (1, c, kh, kw), ("NCHW", "OIHW", "NCHW")))
    _, ckk, oh, ow = patches.shape
    pr = patches.reshape(n, c, kh * kw, oh, ow)
    arg = jnp.argmax(pr, axis=2)                       # [n, c, oh, ow]
    vals = jnp.max(pr, axis=2)
    # window offset -> padded coords -> unpadded flat index
    dh = arg // kw
    dw = arg % kw
    base_h = jnp.arange(oh, dtype=jnp.int32)[None, None, :, None] * sh
    base_w = jnp.arange(ow, dtype=jnp.int32)[None, None, None, :] * sw
    src_h = base_h + dh.astype(jnp.int32) - ph0
    src_w = base_w + dw.astype(jnp.int32) - pw0
    flat = jnp.clip(src_h, 0, h - 1) * w + jnp.clip(src_w, 0, w - 1)
    return vals, flat.astype(jnp.int64)


@primitive("max_unpool2d_op")
def max_unpool2d_prim(x, indices, *, out_h, out_w):
    """Scatter pooled values back to their argmax positions (reference:
    operators/unpool_op.cc); non-selected positions are zero."""
    n, c, oh, ow = x.shape
    flat = indices.astype(jnp.int32).reshape(n, c, oh * ow)
    vals = x.reshape(n, c, oh * ow)
    out = jnp.zeros((n, c, out_h * out_w), x.dtype)
    out = jax.vmap(jax.vmap(
        lambda o, idx, v: o.at[idx].set(v)))(out, flat, vals)
    return out.reshape(n, c, out_h, out_w)


@primitive("bilinear_op")
def bilinear(x1, x2, weight, bias=None):
    """out[b,o] = x1[b,i] W[o,i,j] x2[b,j] (+ bias) — reference:
    operators/bilinear_tensor_product_op.h."""
    out = jnp.einsum("bi,oij,bj->bo", x1, weight, x2)
    if bias is not None:
        out = out + bias
    return out


@primitive("hsigmoid_loss_op")
def hsigmoid_loss(x, label, weight, bias=None, path_table=None,
                  path_code=None, *, num_classes):
    """Hierarchical sigmoid loss (reference: operators/hierarchical_
    sigmoid_op.h). Default tree: complete binary heap with num_classes
    leaves and num_classes-1 internal nodes; custom trees come in as
    (path_table, path_code) id/bit matrices padded with -1."""
    if path_table is None:
        # heap indexing: leaf id = label + (num_classes - 1); ancestors
        # (id-1)//2 ... 0 are the internal nodes whose weights are used
        depth = max(1, int(np.ceil(np.log2(max(num_classes, 2)))))
        ids = label.astype(jnp.int32) + (num_classes - 1)
        tables = []
        codes = []
        cur = ids
        for _ in range(depth):
            parent = (cur - 1) // 2
            code = (cur % 2 == 1)  # left child has odd heap index
            valid = cur > 0
            tables.append(jnp.where(valid, parent, -1))
            codes.append(jnp.where(valid, code, False))
            cur = jnp.maximum(parent, 0)
        path_table = jnp.stack(tables, axis=-1)     # [B, depth]
        path_code = jnp.stack(codes, axis=-1)
    else:
        path_table = path_table.astype(jnp.int32)
        path_code = path_code.astype(jnp.bool_)
    mask = path_table >= 0
    safe = jnp.maximum(path_table, 0)
    w = weight[safe]                                # [B, depth, D]
    logit = jnp.einsum("bd,bpd->bp", x, w)
    if bias is not None:
        logit = logit + bias.reshape(-1)[safe]
    # label bit 1 -> sigmoid(logit), 0 -> sigmoid(-logit)
    sign = jnp.where(path_code, 1.0, -1.0)
    losses = jnp.logaddexp(0.0, -sign * logit)
    losses = jnp.where(mask, losses, 0.0)
    return jnp.sum(losses, axis=-1, keepdims=True)


@primitive("affine_grid_op")
def affine_grid(theta, *, out_h, out_w, align_corners=True):
    """Sampling grid from batched 2x3 affines (reference:
    operators/affine_grid_op.h). Output [N, H, W, 2] in [-1, 1] coords."""
    n = theta.shape[0]

    def axis_coords(size):
        if align_corners:
            return jnp.linspace(-1.0, 1.0, size)
        step = 2.0 / size
        return jnp.linspace(-1.0 + step / 2, 1.0 - step / 2, size)

    ys = axis_coords(out_h)
    xs = axis_coords(out_w)
    gx, gy = jnp.meshgrid(xs, ys)                       # [H, W]
    ones = jnp.ones_like(gx)
    base = jnp.stack([gx, gy, ones], axis=-1)           # [H, W, 3]
    out = jnp.einsum("hwk,nck->nhwc", base.astype(theta.dtype), theta)
    return out                                          # [N, H, W, 2]


@primitive("grid_sample_op")
def grid_sample(x, grid, *, mode="bilinear", padding_mode="zeros",
                align_corners=True):
    """Bilinear/nearest sampling of NCHW x at [-1,1] grid locations
    (reference: operators/grid_sampler_op.h)."""
    if mode not in ("bilinear", "nearest"):
        raise NotImplementedError(
            f"grid_sample mode={mode!r}: bilinear/nearest only")
    if padding_mode not in ("zeros", "border"):
        raise NotImplementedError(
            f"grid_sample padding_mode={padding_mode!r}: zeros/border only "
            "(reflection is not implemented)")
    n, c, h, w = x.shape
    gx = grid[..., 0]
    gy = grid[..., 1]

    def unnorm(v, size):
        if align_corners:
            return (v + 1.0) * (size - 1) / 2.0
        return ((v + 1.0) * size - 1.0) / 2.0

    fx = unnorm(gx, w)
    fy = unnorm(gy, h)
    if padding_mode == "border":
        fx = jnp.clip(fx, 0, w - 1)
        fy = jnp.clip(fy, 0, h - 1)
    if mode == "nearest":
        ix = jnp.round(fx).astype(jnp.int32)
        iy = jnp.round(fy).astype(jnp.int32)
        valid = ((ix >= 0) & (ix < w) & (iy >= 0) & (iy < h))
        ixc = jnp.clip(ix, 0, w - 1)
        iyc = jnp.clip(iy, 0, h - 1)
        gathered = jax.vmap(lambda img, yy, xx: img[:, yy, xx])(
            x, iyc, ixc)                                 # [N, C, H', W']
        return jnp.where(valid[:, None], gathered, 0.0)

    x0 = jnp.floor(fx).astype(jnp.int32)
    y0 = jnp.floor(fy).astype(jnp.int32)
    x1 = x0 + 1
    y1 = y0 + 1
    wx = fx - x0
    wy = fy - y0

    def tap(ix, iy):
        valid = ((ix >= 0) & (ix < w) & (iy >= 0) & (iy < h))
        ixc = jnp.clip(ix, 0, w - 1)
        iyc = jnp.clip(iy, 0, h - 1)
        v = jax.vmap(lambda img, yy, xx: img[:, yy, xx])(x, iyc, ixc)
        return jnp.where(valid[:, None], v, 0.0)

    v00 = tap(x0, y0)
    v01 = tap(x1, y0)
    v10 = tap(x0, y1)
    v11 = tap(x1, y1)
    wx = wx[:, None]
    wy = wy[:, None]
    top = v00 * (1 - wx) + v01 * wx
    bot = v10 * (1 - wx) + v11 * wx
    return top * (1 - wy) + bot * wy


@primitive("margin_cross_entropy_op")
def margin_cross_entropy(logits, label, *, margin1=1.0, margin2=0.5,
                         margin3=0.0, scale=64.0, return_softmax=False):
    """ArcFace-family margin softmax CE (reference:
    operators/margin_cross_entropy_op.h): target-class cosine theta gets
    cos(m1*theta + m2) - m3 before scaled softmax."""
    lab = label.astype(jnp.int32).reshape(-1)
    onehot = jax.nn.one_hot(lab, logits.shape[-1], dtype=logits.dtype)
    cos = jnp.clip(logits, -1.0, 1.0)
    theta = jnp.arccos(cos)
    adjusted = jnp.cos(margin1 * theta + margin2) - margin3
    z = scale * jnp.where(onehot > 0, adjusted, cos)
    logp = jax.nn.log_softmax(z, axis=-1)
    loss = -jnp.take_along_axis(logp, lab[:, None], axis=-1)
    if return_softmax:
        return loss, jnp.exp(logp)
    return loss
