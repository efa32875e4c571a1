"""Plain float32 reference for the GPT family, and the weights from the seed.

Pure `jax.numpy`: imports nothing of `paddle_tpu` and takes nothing the
program has made. The harness makes the weights here from `--seed`, hands
the same values to the program and to this reference, and `correct` is the
comparison of what the timed path produced with what this file computes.

Configuration keys are those of the published GPT-2 `config.json`
(`n_layer`, `n_embd`, `n_head`, `n_inner`, `n_positions`, `vocab_size`,
`layer_norm_epsilon`; activation `gelu_new`, the tanh form). Layer weights
are stacked along a leading layer axis so that one `lax.scan` body is the
whole decoder block; a "leaf" of the comparison is one (name, layer) pair.

Departures from the published models, all also in the program: the output
head is tied to the token embedding, every bias and LayerNorm parameter is
drawn at random (0.02) instead of 0 / 1 so that each carries gradient, and
the vocabulary is padded to 50304.

`quant="int8"` is the control of "How `correct` is decided": the same
mathematics with both operands of every matrix product rounded to int8
under a per-tensor symmetric absmax scale — the nearest precision below
the configurations' bfloat16 that this chip computes in (a v5e multiplies
int8 at twice its bf16 rate and has no fp8 unit), so the step a later PR
would be tempted by.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
#: stacked per-layer leaves -> shape after the layer axis, as f(d, inner)
LAYER_LEAVES = {
    "ln_1.w": lambda d, f: (d,), "ln_1.b": lambda d, f: (d,),
    "qkv.w": lambda d, f: (d, 3 * d), "qkv.b": lambda d, f: (3 * d,),
    "out.w": lambda d, f: (d, d), "out.b": lambda d, f: (d,),
    "ln_2.w": lambda d, f: (d,), "ln_2.b": lambda d, f: (d,),
    "fc1.w": lambda d, f: (d, f), "fc1.b": lambda d, f: (f,),
    "fc2.w": lambda d, f: (f, d), "fc2.b": lambda d, f: (d,),
}


def dims(cfg):
    d = int(cfg["n_embd"])
    return dict(d=d, L=int(cfg["n_layer"]), nh=int(cfg["n_head"]),
                f=int(cfg.get("n_inner") or 4 * d),
                V=int(cfg["vocab_size"]), P=int(cfg["n_positions"]),
                eps=float(cfg.get("layer_norm_epsilon", 1e-5)))


def leaf_shapes(cfg):
    """Ordered {name: shape}; names starting "h." carry the layer axis."""
    m = dims(cfg)
    out = {"wte": (m["V"], m["d"]), "wpe": (m["P"], m["d"])}
    for k, fn in LAYER_LEAVES.items():
        out["h." + k] = (m["L"],) + fn(m["d"], m["f"])
    out["ln_f.w"] = (m["d"],)
    out["ln_f.b"] = (m["d"],)
    return out


def make_weights(cfg, seed, dtype="bfloat16"):
    """Every leaf from the seed in ONE jitted call, on the device, in the
    type it is served or trained in. N(0, 0.02); LayerNorm gains 1 + that."""
    shapes = leaf_shapes(cfg)
    dt = jnp.dtype(dtype)

    @jax.jit
    def _make(key):
        out = {}
        for i, (name, shape) in enumerate(shapes.items()):
            w = 0.02 * jax.random.normal(jax.random.fold_in(key, i), shape,
                                         F32)
            if name.endswith("ln_1.w") or name.endswith("ln_2.w") \
                    or name == "ln_f.w":
                w = 1.0 + w
            out[name] = w.astype(dt)
        return out

    # seeds are "a little over 2**31": fold the high bits in separately
    s = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(s & 0x7FFFFFFF), s >> 31)
    return _make(key)


def tokens(seed, n_rows, width, vocab):
    """`n_rows` rows of `width` token ids from the seed, all different
    (numpy, on the host: these are the feed's samples)."""
    rs = np.random.RandomState([int(seed) & 0x7FFFFFFF, int(seed) >> 31, 7])
    return rs.randint(1, vocab, (n_rows, width)).astype(np.int64)


# ---------------------------------------------------------------------------
# the mathematics


def _int8(x):
    """Round to int8 under a per-tensor symmetric absmax scale; gradient
    passes straight through."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    q = jnp.clip(jnp.round(x / s), -127.0, 127.0) * s
    return x + jax.lax.stop_gradient(q - x)


def _mm(a, b, quant):
    if quant == "int8":
        a, b = _int8(a), _int8(b)
    elif quant is not None:
        raise ValueError("unknown control precision %r" % quant)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _ln(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(m, quant, h, lw):
    """One pre-LN decoder block on h [b, T, d]; lw: this layer's leaves."""
    lw = {k: v.astype(F32) for k, v in lw.items()}
    b, T, d = h.shape
    nh, hd = m["nh"], d // m["nh"]
    x = _ln(h, lw["ln_1.w"], lw["ln_1.b"], m["eps"])
    qkv = _mm(x, lw["qkv.w"], quant) + lw["qkv.b"]
    qkv = qkv.reshape(b, T, 3, nh, hd).transpose(2, 0, 3, 1, 4)
    q, k, v = qkv[0], qkv[1], qkv[2]                    # [b, nh, T, hd]
    s = _mm(q, k.transpose(0, 1, 3, 2), quant) / math.sqrt(hd)
    mask = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
    a = _mm(p, v, quant).transpose(0, 2, 1, 3).reshape(b, T, d)
    h = h + _mm(a, lw["out.w"], quant) + lw["out.b"]
    x = _ln(h, lw["ln_2.w"], lw["ln_2.b"], m["eps"])
    x = _gelu_new(_mm(x, lw["fc1.w"], quant) + lw["fc1.b"])
    return h + _mm(x, lw["fc2.w"], quant) + lw["fc2.b"]


def _hidden(cfg, w, ids, quant):
    m = dims(cfg)
    T = ids.shape[1]
    h = w["wte"].astype(F32)[ids] + w["wpe"].astype(F32)[:T]
    layers = {k[2:]: v for k, v in w.items() if k.startswith("h.")}
    body = jax.checkpoint(functools.partial(_block, m, quant))
    h, _ = jax.lax.scan(lambda c, lw: (body(c, lw), None), h, layers)
    return _ln(h, w["ln_f.w"].astype(F32), w["ln_f.b"].astype(F32),
               m["eps"])


@functools.partial(jax.jit, static_argnums=(0, 3))
def _logits_jit(cfg_t, w, ids, quant):
    cfg = dict(cfg_t)
    h = _hidden(cfg, w, ids, quant)
    return _mm(h, w["wte"].astype(F32).T, quant)


def logits(cfg, w, ids, quant=None):
    """[b, T, V] float32 logits of ids [b, T] (full forward, no cache)."""
    return _logits_jit(_freeze(cfg), w, jnp.asarray(ids, jnp.int32), quant)


def _freeze(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str))))


def _block_loss_sum(cfg, quant, w, x, y):
    lg = _mm(_hidden(cfg, w, x, quant), w["wte"].astype(F32).T, quant)
    lse = jax.nn.logsumexp(lg, axis=-1)
    tgt = jnp.take_along_axis(lg, y[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - tgt)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _grad_block(cfg_t, quant, w, x, y):
    return jax.value_and_grad(
        functools.partial(_block_loss_sum, dict(cfg_t), quant))(w, x, y)


def loss_and_grads(cfg, w, x, y, rows_per_block=4, quant=None):
    """Mean next-token cross-entropy over all of x/y [B, T] and its
    gradient, accumulated over blocks of rows so that it fits."""
    B, T = x.shape
    cfg_t = _freeze(cfg)
    total, grads = 0.0, None
    for i in range(0, B, rows_per_block):
        ls, g = _grad_block(cfg_t, quant, w,
                            jnp.asarray(x[i:i + rows_per_block], jnp.int32),
                            jnp.asarray(y[i:i + rows_per_block], jnp.int32))
        total = total + ls
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    n = float(B * T)
    return total / n, jax.tree.map(lambda a: a / n, grads)


@functools.partial(jax.jit, static_argnums=(0,))
def _adamw(hp_t, w, g, m1, m2, t):
    lr, b1, b2, eps, wd = hp_t

    def one(p, g, m1, m2):
        m1 = b1 * m1 + (1 - b1) * g
        m2 = b2 * m2 + (1 - b2) * jnp.square(g)
        step = lr * (m1 / (1 - b1 ** t)) / (jnp.sqrt(m2 / (1 - b2 ** t))
                                            + eps)
        return p * (1.0 - lr * wd) - step, m1, m2

    out = {k: one(w[k], g[k], m1[k], m2[k]) for k in w}
    return ({k: v[0] for k, v in out.items()},
            {k: v[1] for k, v in out.items()},
            {k: v[2] for k, v in out.items()})


def split_leaves(tree):
    """The fused qkv bias as three leaves (".q", ".k", ".v"): a key's bias
    has no gradient under softmax and moves under Adam by round-off alone,
    so it has to be a leaf of its own for the rule that leaves it out."""
    out = {}
    for k, a in tree.items():
        if k.endswith("qkv.b"):
            d = a.shape[-1] // 3
            for i, part in enumerate("qkv"):
                out[k + "." + part] = a[..., i * d:(i + 1) * d]
        else:
            out[k] = a
    return out


def leaf_norms(tree):
    """{leaf: L2 norm}; a stacked "h." array gives one leaf per layer,
    named "h.<layer>.<rest>"; the qkv bias is split (split_leaves)."""
    out = {}
    for k, a in split_leaves(tree).items():
        if k.startswith("h."):
            n = np.asarray(jnp.sqrt(jnp.sum(jnp.square(
                a.astype(F32)).reshape(a.shape[0], -1), axis=1)))
            for i, v in enumerate(n):
                out["h.%d.%s" % (i, k[2:])] = float(v)
        else:
            out[k] = float(jnp.sqrt(jnp.sum(jnp.square(a.astype(F32)))))
    return out


def train_steps(cfg, w0, batches, hp, rows_per_block=4, quant=None,
                fault=None):
    """AdamW steps from weights w0 over `batches` [(x, y)], in float32
    throughout: parameters, moments and update (what mixed precision with
    float32 master weights keeps).

    hp: dict(lr, beta1, beta2, epsilon, weight_decay). Returns what the
    comparison reads: each step's loss, the per-leaf norm of the first
    gradient and of the parameters' change after the last step.

    fault plants, in this reference put in the program's place, one of the
    faults a training cell can have: "half_batch" (the second half of the
    rows left out, the mean taken over the rest), "shard_only" (one of
    four shards' rows only: the exchange between chips left out)."""
    hp_t = (float(hp["lr"]), float(hp["beta1"]), float(hp["beta2"]),
            float(hp["epsilon"]), float(hp["weight_decay"]))
    w = {k: v.astype(F32) for k, v in w0.items()}
    start = w
    m1 = {k: jnp.zeros_like(v) for k, v in w.items()}
    m2 = {k: jnp.zeros_like(v) for k, v in w.items()}
    losses, grad_norms = [], None
    for t, (x, y) in enumerate(batches, 1):
        if fault == "half_batch":
            x, y = x[:len(x) // 2], y[:len(y) // 2]
        elif fault == "shard_only":
            x, y = x[:len(x) // 4], y[:len(y) // 4]
        loss, g = loss_and_grads(cfg, w, x, y, rows_per_block, quant)
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = leaf_norms(g)
        w, m1, m2 = _adamw(hp_t, w, g, m1, m2, float(t))
    change = leaf_norms({k: w[k] - start[k] for k in w})
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}


# ---------------------------------------------------------------------------
# the comparisons


def compare_training(got, ref):
    """The numbers a training cell compares (each 0 when equal).

    loss_gap: widest |loss - reference loss| over the steps.
    grad_norm_gap / change_norm_gap: worst leaf of |norm - reference
      norm| over the larger of the reference's norm of that leaf and of
      the median leaf. Leaves whose reference gradient is under a
      thousandth of the median leaf's (a key's bias under softmax) move
      under Adam by round-off alone and are left out of the change.
    A leaf the program does not report counts as norm 0 (gap 1)."""
    out = {"loss_gap": max(abs(a - b) for a, b in
                           zip(got["losses"], ref["losses"]))}
    if len(got["losses"]) != len(ref["losses"]):
        out["loss_gap"] = float("inf")
    gmed = float(np.median(list(ref["grad_norms"].values())))
    keep = {k for k, v in ref["grad_norms"].items() if v >= 1e-3 * gmed}
    for name, leaves in (("grad_norm_gap", set(ref["grad_norms"])),
                         ("change_norm_gap", keep)):
        r = ref[name.replace("_gap", "s")]
        g = got[name.replace("_gap", "s")]
        med = float(np.median([r[k] for k in leaves]))
        out[name] = max(abs(g.get(k, 0.0) - r[k]) / max(r[k], med)
                        for k in leaves)
    return out


def served_gaps(cfg, w, seqs, n_prompt, quant=None):
    """For each served token of each sequence (prompt + served tokens),
    how far its float32 logit lies below the reference's best at that
    position, in units of the row's standard deviation.

    With quant set, the token judged at each position is instead the one
    the lower precision puts first there (the control need not decode).
    Returns the list of gaps, one per served token."""
    gaps = []
    for s, n in zip(seqs, n_prompt):
        s = np.asarray(s, np.int64)
        width = -(-len(s) // 128) * 128          # few shapes; causal => exact
        ids = np.zeros((1, width), np.int64)
        ids[0, :len(s)] = s
        lg = logits(cfg, w, ids)[0, n - 1:len(s) - 1]      # [n_out, V]
        if quant:
            tok = jnp.argmax(logits(cfg, w, ids, quant)[0, n - 1:len(s) - 1],
                             axis=-1)
        else:
            tok = jnp.asarray(s[n:], jnp.int32)
        got = jnp.take_along_axis(lg, tok[:, None], axis=-1)[:, 0]
        gap = (jnp.max(lg, axis=-1) - got) / jnp.std(lg, axis=-1)
        gaps.extend(float(v) for v in np.asarray(gap))
    return gaps
