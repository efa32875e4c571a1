"""Plain float32 reference for the `afmoe` family (Trinity), and its weights.

Pure `jax.numpy`: imports nothing of `paddle_tpu` and takes nothing the
program has made. No kernel, no cache, no batching: one sequence, the whole
forward pass, every matrix product in float32 at `highest`. Configuration
keys are those of the published `config.json` (`model_type` `afmoe`).

The layer, as this file computes it (d = `hidden_size`):

    h0   = E[ids] * sqrt(d)                                  (mup_enabled)
    a    = RMSNorm(h);  q, k, v, g = a.Wq, a.Wk, a.Wv, a.Wg
    q, k = RMSNorm_q(q), RMSNorm_k(k)        over each head's `head_dim`
    sliding layer: rotary (rope_theta, rotate-half over the whole head)
                   on q and k; mask j <= i and i - j < sliding_window
    full layer:    NO rotary; the causal mask alone
    o    = softmax(q.k^T / sqrt(head_dim) + mask).v   (grouped heads)
    h    = h + RMSNorm_post_attn((o * sigmoid(g)).Wo)
    m    = RMSNorm_pre_mlp(h);  h = h + RMSNorm_post_mlp(f(m))
    f    = SwiGLU of `intermediate_size`         (l < num_dense_layers)
    f    = SwiGLU_shared(m) + sum_{e in S} w_e SwiGLU_e(m)     otherwise:
           s = sigmoid(m.W_r) in float32; S = top-k(s + expert_bias);
           w_e = s_e / (sum_S s + 1e-20) * route_scale       (route_norm)
    logits = RMSNorm_f(h).W_head                             (untied)

DEPARTURE RISKS. `config.json` does not state these; they are what the
public `modeling_afmoe.py` of `transformers` does as far as the author
could state it without a network, and each is listed in the configuration
file's `assumed`: the place of the sqrt(d) factor; the output gate
(`Wg`, sigmoid, before `Wo`); RMSNorm on q and k per head; no rotary on
full-attention layers; four norms a layer (post-attention and post-MLP
norms inside the residual branch); the bias used for the choice only.

Departures from the published model, all also in the program: weights are
drawn at random from the seed (N(0, 0.02); norm gains N(1, 0.02);
`expert_bias` N(0, 0.02): wide enough that the choice by s + b and the
weight by s differ on some tokens, narrow enough that the load stays near
the balance the published bias is trained to keep), and the depth is cut
(`reduced`).

`experts_held=(first, count)` computes the part of an expert layer that
the experts [first, first + count) give: the router still scores all
`num_experts` and keeps its top-k; assignments to experts not held add
nothing; the shared expert is computed by every holder alike.

`quant` is the control of "how `correct` is decided": "int8" rounds both
operands of every matrix product to int8 under a per-tensor symmetric
absmax scale (the precision below bfloat16 that a v5e computes in);
"int8_experts" / "int8_router" do so in the expert products / the router
alone; "bf16" rounds them to bfloat16, the precision the configurations
state, and so reads what rounding alone does. `fault` plants what a
wrong program would compute: "no_shared" (the shared expert dropped),
"full_windowed" (the window's mask on the full-attention layers too),
and, in `served_gaps` alone, "one_token" (the last served token of a
request replaced).

No training: the family has no backward in the program yet, so
`train_steps` / `compare_training` are not here.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUANTS = (None, "int8", "int8_experts", "int8_router", "bf16")
FAULTS = (None, "no_shared", "full_windowed", "one_token")
#: widths a sequence is padded to (causal, so exact): four compiled shapes
#: up to 16 384 positions, each half a minute of compiling on the chip's host
PAD_TO = 4096
#: query rows and experts computed at a time (what fits beside the weights)
Q_ROWS = 256
EXPERT_BLOCK = 4
#: the widest served token's gap is divided by this before it stands beside
#: the mean gap under the cell's one limit (`served_gaps`)
TOKENS_A_MEAN = 100.0


def dims(cfg):
    L = int(cfg["num_hidden_layers"])
    kinds = tuple(cfg["layer_types"])
    if len(kinds) != L:
        raise ValueError("layer_types has %d entries for %d layers"
                         % (len(kinds), L))
    return dict(
        d=int(cfg["hidden_size"]), L=L, kinds=kinds,
        nq=int(cfg["num_attention_heads"]),
        nkv=int(cfg["num_key_value_heads"]), hd=int(cfg["head_dim"]),
        window=int(cfg["sliding_window"]),
        theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]),
        dense=int(cfg["num_dense_layers"]), F=int(cfg["intermediate_size"]),
        E=int(cfg["num_experts"]), k=int(cfg["num_experts_per_tok"]),
        f=int(cfg["moe_intermediate_size"]),
        fs=int(cfg["moe_intermediate_size"])
        * int(cfg["num_shared_experts"]),
        route_norm=bool(cfg["route_norm"]),
        route_scale=float(cfg["route_scale"]),
        V=int(cfg["vocab_size"]), P=int(cfg["max_position_embeddings"]))


def leaf_shapes(cfg):
    """Ordered {leaf: shape}. A leaf is one array of one layer
    ("l<i>.<name>"); the experts of a layer are one leaf [E, ., .]."""
    m = dims(cfg)
    d, qd, kd = m["d"], m["nq"] * m["hd"], m["nkv"] * m["hd"]
    out = {"embed": (m["V"], d)}
    for i in range(m["L"]):
        p = "l%d." % i
        out.update({
            p + "attn_norm": (d,), p + "wq": (d, qd), p + "wk": (d, kd),
            p + "wv": (d, kd), p + "wg": (d, qd), p + "q_norm": (m["hd"],),
            p + "k_norm": (m["hd"],), p + "wo": (qd, d),
            p + "post_attn_norm": (d,), p + "pre_mlp_norm": (d,)})
        if i < m["dense"]:
            out.update({p + "gate": (d, m["F"]), p + "up": (d, m["F"]),
                        p + "down": (m["F"], d)})
        else:
            out.update({
                p + "router": (d, m["E"]), p + "expert_bias": (m["E"],),
                p + "e_gate": (m["E"], d, m["f"]),
                p + "e_up": (m["E"], d, m["f"]),
                p + "e_down": (m["E"], m["f"], d),
                p + "s_gate": (d, m["fs"]), p + "s_up": (d, m["fs"]),
                p + "s_down": (m["fs"], d)})
        out[p + "post_mlp_norm"] = (d,)
    out["norm_f"] = (d,)
    out["head"] = (m["V"], d)
    return out


def leaf_draw(name):
    """(mean, std, float32?) a leaf is drawn with: every norm gain and the
    expert bias matter to the result, so none is left at 1 or 0."""
    if name.endswith("norm") or name == "norm_f":
        return 1.0, 0.02, False
    if name.endswith("expert_bias"):
        return 0.0, 0.02, True         # a buffer: float32 as published
    return 0.0, 0.02, False


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _draw(key, shape, dtype, mean, std):
    return (mean + std * jax.random.normal(key, shape, F32)).astype(dtype)


def seed_key(seed):
    # seeds are "a little over 2**31": fold the high bits in separately
    s = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(s & 0x7FFFFFFF), s >> 31)


def make_weights(cfg, seed, dtype="bfloat16"):
    """Every leaf from the seed, on the device, in the type it is served
    in; one jitted draw a leaf (compiled once a shape), so that no more
    than one leaf's float32 draw is alive beside the weights."""
    key, dt = seed_key(seed), jnp.dtype(dtype)
    out = {}
    for i, (name, shape) in enumerate(leaf_shapes(cfg).items()):
        mean, std, f32 = leaf_draw(name)
        out[name] = _draw(jax.random.fold_in(key, i), shape,
                          F32 if f32 else dt, mean, std)
    return out


def tokens(seed, n_rows, width, vocab):
    """`n_rows` rows of `width` token ids from the seed (numpy, host)."""
    rs = np.random.RandomState([int(seed) & 0x7FFFFFFF, int(seed) >> 31, 7])
    return rs.randint(1, vocab, (n_rows, width)).astype(np.int64)


def split_leaves(tree):
    """A leaf of this family is already one array of one layer."""
    return dict(tree)


# ---------------------------------------------------------------------------
# the mathematics


def _int8(x):
    """Round to int8 under a per-tensor symmetric absmax scale."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.clip(jnp.round(x / s), -127.0, 127.0) * s


def _mm(spec, a, b, int8):
    if int8 == "bf16":
        a, b = (x.astype(jnp.bfloat16).astype(F32) for x in (a, b))
    elif int8:
        a, b = _int8(a), _int8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w.astype(F32)


def _rotary(x, theta):
    """x [T, H, hd] at positions 0..T-1; rotate-half over the whole head."""
    T, _, hd = x.shape
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]     # [T, hd/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _swiglu(x, gate, up, down, int8):
    g = _mm("td,df->tf", x, gate.astype(F32), int8)
    u = _mm("td,df->tf", x, up.astype(F32), int8)
    return _mm("tf,fd->td", jax.nn.silu(g) * u, down.astype(F32), int8)


def _attention(m, lw, h, windowed, int8):
    T = h.shape[0]
    nq, nkv, hd = m["nq"], m["nkv"], m["hd"]
    a = _rms(h, lw["attn_norm"], m["eps"])
    q = _mm("td,df->tf", a, lw["wq"].astype(F32), int8).reshape(T, nq, hd)
    k = _mm("td,df->tf", a, lw["wk"].astype(F32), int8).reshape(T, nkv, hd)
    v = _mm("td,df->tf", a, lw["wv"].astype(F32), int8).reshape(T, nkv, hd)
    g = _mm("td,df->tf", a, lw["wg"].astype(F32), int8)
    q = _rms(q, lw["q_norm"], m["eps"])
    k = _rms(k, lw["k_norm"], m["eps"])
    if windowed["rotary"]:
        q, k = _rotary(q, m["theta"]), _rotary(k, m["theta"])
    grp = nq // nkv
    q = q.reshape(T, nkv, grp, hd)
    rows = min(Q_ROWS, T)
    if T % rows:
        raise ValueError("a sequence of %d rows is not whole blocks of %d"
                         % (T, rows))
    j = jnp.arange(T)[None, :]

    def block(i0):
        qb = jax.lax.dynamic_slice_in_dim(q, i0, rows, 0)
        s = _mm("tkgd,skd->kgts", qb, k, int8) / math.sqrt(hd)
        i = i0 + jnp.arange(rows)[:, None]
        ok = j <= i
        if windowed["mask"]:
            ok = ok & (i - j < m["window"])
        p = jax.nn.softmax(jnp.where(ok, s, -1e30), axis=-1)
        return _mm("kgts,skd->tkgd", p, v, int8)

    o = jax.lax.map(block, jnp.arange(0, T, rows)).reshape(T, nq * hd)
    o = o * jax.nn.sigmoid(g)
    return _mm("tf,fd->td", o, lw["wo"].astype(F32), int8)


def route(m, lw, x, int8=False):
    """(chosen [T, k] expert ids, weights [T, k]) of the tokens x [T, d]:
    sigmoid scores in float32, the choice by score + bias, the weight by
    the score alone, normalised over the chosen and scaled."""
    s = jax.nn.sigmoid(_mm("td,de->te", x.astype(F32),
                           lw["router"].astype(F32), int8))
    _, chosen = jax.lax.top_k(s + lw["expert_bias"].astype(F32), m["k"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if m["route_norm"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return chosen, w * m["route_scale"]


def moe(m, lw, x, quant=None, fault=None, experts_held=None):
    """The expert layer on x [T, d] (float32): shared expert + the held
    experts' weighted SwiGLUs. Experts are raised to float32 and computed
    a block at a time, every token through every expert of the block,
    the unchosen weighted 0: a plain mask, no sorting, no capacity."""
    x = x.astype(F32)
    T, d = x.shape
    i8e = "bf16" if quant == "bf16" else quant in ("int8", "int8_experts")
    chosen, w = route(m, lw, x, quant in ("int8", "int8_router"))
    first, count = experts_held or (0, m["E"])
    # wm[t, e]: the weight of expert e for token t, 0 where not chosen
    wm = jnp.zeros((T, m["E"]), F32).at[
        jnp.arange(T)[:, None], chosen].add(w)
    wm = wm[:, first:first + count]
    blk = math.gcd(EXPERT_BLOCK, count)

    def body(acc, e0):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(        # noqa: E731
            a, first + e0, blk, 0).astype(F32)
        g = _mm("td,edf->etf", x, sl(lw["e_gate"]), i8e)
        u = _mm("td,edf->etf", x, sl(lw["e_up"]), i8e)
        y = _mm("etf,efd->etd", jax.nn.silu(g) * u, sl(lw["e_down"]), i8e)
        we = jax.lax.dynamic_slice_in_dim(wm, e0, blk, 1)    # [T, blk]
        return acc + jnp.einsum("etd,te->td", y, we,
                                precision=jax.lax.Precision.HIGHEST), None

    out, _ = jax.lax.scan(body, jnp.zeros((T, d), F32),
                          jnp.arange(0, count, blk))
    if fault != "no_shared":
        out = out + _swiglu(x, lw["s_gate"], lw["s_up"], lw["s_down"], i8e)
    return out


def _layer(m, i, lw, h, quant, fault):
    int8 = "bf16" if quant == "bf16" else quant == "int8"
    sliding = m["kinds"][i] == "sliding_attention"
    windowed = {"rotary": sliding,
                "mask": sliding or fault == "full_windowed"}
    attn = _attention(m, lw, h, windowed, int8)
    h = h + _rms(attn, lw["post_attn_norm"], m["eps"])
    x = _rms(h, lw["pre_mlp_norm"], m["eps"])
    if i < m["dense"]:
        y = _swiglu(x, lw["gate"], lw["up"], lw["down"], int8)
    else:
        y = moe(m, lw, x, quant, fault)
    return h + _rms(y, lw["post_mlp_norm"], m["eps"])


def layer_leaves(w, i):
    p = "l%d." % i
    return {k[len(p):]: v for k, v in w.items() if k.startswith(p)}


def _freeze(cfg):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool, list))
                        and k not in ("reduced", "assumed")))


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def _hidden_jit(cfg_t, w, ids, quant, fault):
    m = dims(dict(cfg_t))
    h = w["embed"][ids].astype(F32) * math.sqrt(m["d"])
    for i in range(m["L"]):
        h = _layer(m, i, layer_leaves(w, i), h, quant, fault)
    return _rms(h, w["norm_f"], m["eps"])


@functools.partial(jax.jit, static_argnums=(2,))
def _head_jit(h, head, int8):
    return _mm("td,vd->tv", h, head.astype(F32), int8)


def hidden(cfg, w, ids, quant=None, fault=None):
    """[T, d] float32 final hidden states (after the last norm) of the one
    sequence ids [T]."""
    if quant not in QUANTS or fault not in FAULTS:
        raise ValueError("unknown control %r or fault %r" % (quant, fault))
    with jax.default_matmul_precision("highest"):
        return _hidden_jit(_freeze(cfg), w, jnp.asarray(ids, jnp.int32),
                           quant, fault)


def logits(cfg, w, ids, quant=None, fault=None, rows=None):
    """[T, V] float32 logits of ids [T] (full forward, no cache); with
    `rows` = (first, stop) only of those positions."""
    h = hidden(cfg, w, ids, quant, fault)
    if rows is not None:
        h = h[rows[0]:rows[1]]
    with jax.default_matmul_precision("highest"):
        return _head_jit(h, w["head"],
                         "bf16" if quant == "bf16" else quant == "int8")


def served_numbers(gaps):
    """The two numbers a run is judged by, from every served token's gap
    (one array a request): the MEAN gap over all of them, and the WIDEST
    of them over `TOKENS_A_MEAN`. See `served_gaps`."""
    flat = np.concatenate([np.asarray(g, np.float64).ravel() for g in gaps])
    return [float(np.mean(flat)), float(np.max(flat)) / TOKENS_A_MEAN]


def served_gaps(cfg, w, seqs, n_prompt, quant=None, fault=None,
                per_token=False):
    """TWO numbers for the sequences (each prompt + served tokens), from
    every served token's gap: how far the token's float32 logit lies below
    the reference's best at that position, in units of the row's standard
    deviation. The first is the MEAN gap over all served tokens of all the
    sequences; the second the WIDEST gap among them divided by
    `TOKENS_A_MEAN` (100). The harness compares the larger of the two
    (`served_gap_max`) with the cell's one limit, so a limit of 0.024 holds
    the mean under 0.024 and every token under 2.4.

    Why not, as for GPT, every token's gap under one small limit: with
    top-k routing a score rounded in bfloat16 flips a choice now and then,
    the token then goes through another expert, and ONE such token in a
    run reads a gap of 0.5-1.3 whatever the precision: the reference with
    its operands rounded to bfloat16 reads a per-token maximum of 1.30
    where rounding them to int8 reads 1.38-2.7 (chip, PERF.md section 2),
    so the maximum over tokens measures the rarest flip and cannot tell
    the precisions apart. The mean over the 1 400-2 300 tokens a run
    checks can: 0.009-0.016 for the program in bfloat16, 0.034-0.037 with
    int8 in the experts or the router alone, 0.42-0.45 with int8
    throughout. But a mean forgives one wrong token, so the widest token is
    held too, above what a flip reads (2.4 against 1.3) and where a token
    from elsewhere reads (the fault "one_token": 2.5-7.0).

    With quant set, or a fault of the forward pass, the token judged at
    each position is instead the one the altered forward puts first there
    (the control need not decode); the fault "one_token" judges the served
    tokens with the LAST of each request (so that no later position sees
    it) replaced by a token of its prompt: what a slot that reads another
    slot's row would serve. `per_token=True` returns every token's gap,
    one array a sequence."""
    out = []
    altered = quant or fault in ("no_shared", "full_windowed")
    for s, n in zip(seqs, n_prompt):
        s = np.asarray(s, np.int64)
        width = -(-len(s) // PAD_TO) * PAD_TO    # few shapes; causal => exact
        ids = np.zeros((width,), np.int64)
        ids[:len(s)] = s
        rows = (n - 1, len(s) - 1)
        lg = logits(cfg, w, ids, rows=rows)                  # [n_out, V]
        if altered:
            tok = jnp.argmax(logits(cfg, w, ids, quant, fault, rows=rows),
                             axis=-1)
        else:
            served = s[n:].copy()
            if fault == "one_token":
                served[-1] = s[(len(s) * 7919) % n]
            tok = jnp.asarray(served, jnp.int32)
        got = jnp.take_along_axis(lg, tok[:, None], axis=-1)[:, 0]
        out.append(np.asarray(
            (jnp.max(lg, axis=-1) - got) / jnp.std(lg, axis=-1)))
    return out if per_token else served_numbers(out)
