"""Plain float32 reference for the `mimo_v2` family (MiMo-V2.5's language
model), and its weights.

Pure `jax.numpy`: imports nothing of `paddle_tpu` and takes nothing the
program has made. No kernel, no cache, no batching: one sequence, the whole
forward pass, every matrix product in float32 at `highest`. Configuration
keys are those of the published `config.json` (`model_type` `mimo_v2`). From
`reference_afmoe` come the seed and comparison plumbing alone (the draw of a
leaf, the rounding of a control's operands, the two numbers a run is judged
by); the layer below is this file's own.

The layer, as this file computes it. For layer i of kind
`hybrid_layer_pattern[i]` (0 full, 1 window):

    (H_q, H_kv, d_k, d_v, theta) = (num_attention_heads, num_key_value_heads,
        head_dim, v_head_dim, rope_theta)                            full
      = (swa_num_attention_heads, swa_num_key_value_heads, swa_head_dim,
         swa_v_head_dim, swa_rope_theta)                             window
    r    = int(d_k * partial_rotary_factor)
    a    = RMSNorm(h; layernorm_epsilon)
    q, k = a.Wq [T, H_q, d_k], a.Wk [T, H_kv, d_k]
    v    = attention_value_scale * a.Wv [T, H_kv, d_v]
    rotary (theta, rotate-half) on the FIRST r dims of every head of q and
      k, on both kinds of layer; the other d_k - r pass unrotated
    s_tj = q_t.k_j / sqrt(d_k), j <= t, and on a window layer t - j <
      sliding_window
    full layer:   p = softmax_j(s)
    window layer: one learned scalar b_h a query head joins the denominator
      and takes no value:  p_tj = exp(s_tj - m) / (sum_j' exp(s_tj' - m)
      + exp(b_h - m)), m = max(max_j s_tj, b_h)
      (add_swa_attention_sink_bias)
    h    = h + (p.v).Wo;  x = RMSNorm(h)
    layer with moe_layer_freq 0:  h = h + SwiGLU(x) of intermediate_size
    otherwise:  s = sigmoid(x.W_r) in float32 over ALL `router_experts`;
      S = top-k(s + expert_bias);  w_e = s_e / (sum_S s + 1e-20)
      (norm_topk_prob; routed_scaling_factor null = 1);
      h = h + sum over e in S THAT ARE HELD HERE of w_e SwiGLU_e(x); no
      shared expert
    logits = RMSNorm_f(h).W_head over the rows of the vocabulary held here

No biases, no QK-norm, no output gate. Each point above that is not a key's
plain meaning is listed in the configuration file's `assumed`.

THE SHARE. The configuration gives this chip `n_routed_experts` experts,
`experts_held` = (first, count) of the `router_experts` the router scores:
the weights hold those experts alone, the router keeps its published width
and top-k, and what the absent experts would have added is left out here as
in the program. `moe(experts_held=)` computes a narrower share of what the
weights hold (the CPU test adds all shares up to the whole layer).

`quant` is the control of "how `correct` is decided": "int8" rounds both
operands of every matrix product to int8 under a per-tensor symmetric absmax
scale (the precision below bfloat16 that a v5e computes in); "bf16" rounds
them to bfloat16 and so reads what rounding alone does. `fault` plants what
a wrong program would compute: "no_sink" (the sink left out of the window
layers' denominator), "rotary_whole" (rotary over the whole head),
"one_theta" (rope_theta on both kinds), "no_value_scale", "window_127",
"window_129", "full_windowed" (the window's mask on the full layers too)
and, in `served_gaps` alone, "one_token".
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from reference_afmoe import (          # seed and comparison plumbing only
    _draw, _freeze, _int8, _mm, layer_leaves, seed_key, split_leaves, tokens)

__all__ = ["TOKENS_A_MEAN", "_int8", "dims", "hidden", "leaf_shapes",
           "logits", "make_weights", "moe", "route", "served_gaps",
           "served_numbers", "split_leaves", "tokens"]

F32 = jnp.float32
QUANTS = (None, "int8", "bf16")
FAULTS = (None, "no_sink", "rotary_whole", "one_theta", "no_value_scale",
          "window_127", "window_129", "full_windowed", "one_token")
#: widths a sequence is padded to (causal, so exact): two compiled shapes up
#: to the cell's 4096 positions
PAD_TO = 2048
#: query rows and experts computed at a time (what fits beside the weights)
Q_ROWS = 256
EXPERT_BLOCK = 4
#: the widest served token's gap is divided by this before it stands beside
#: the mean gap under the cell's one limit (`served_gaps`): with a sixteenth
#: of the experts held a flipped choice moves a logit little, the mean gap
#: reads 3e-4 where a model that holds them all reads 1e-2, and the widest
#: token of a sound run (0.09-0.15) is 300 times the mean, not 100
TOKENS_A_MEAN = 1000.0


def dims(cfg):
    L = int(cfg["num_hidden_layers"])
    kinds = tuple("window" if k else "full"
                  for k in cfg["hybrid_layer_pattern"])
    moe_at = tuple(bool(f) for f in cfg["moe_layer_freq"])
    if len(kinds) != L or len(moe_at) != L:
        raise ValueError("hybrid_layer_pattern / moe_layer_freq do not have "
                         "%d entries" % L)
    hd, vd = int(cfg["head_dim"]), int(cfg["v_head_dim"])
    geo = {"full": (int(cfg["num_attention_heads"]),
                    int(cfg["num_key_value_heads"]), hd, vd,
                    float(cfg["rope_theta"])),
           "window": (int(cfg["swa_num_attention_heads"]),
                      int(cfg["swa_num_key_value_heads"]),
                      int(cfg["swa_head_dim"]), int(cfg["swa_v_head_dim"]),
                      float(cfg["swa_rope_theta"]))}
    held = tuple(cfg.get("experts_held") or (0, int(cfg["n_routed_experts"])))
    if held[1] != int(cfg["n_routed_experts"]):
        raise ValueError("experts_held counts %d experts, n_routed_experts "
                         "%d" % (held[1], int(cfg["n_routed_experts"])))
    return dict(
        d=int(cfg["hidden_size"]), L=L, kinds=kinds, moe_at=moe_at, geo=geo,
        window=int(cfg["sliding_window"]),
        rot=float(cfg["partial_rotary_factor"]),
        vscale=float(cfg["attention_value_scale"]),
        sinks={"full": bool(cfg["add_full_attention_sink_bias"]),
               "window": bool(cfg["add_swa_attention_sink_bias"])},
        eps=float(cfg["layernorm_epsilon"]), F=int(cfg["intermediate_size"]),
        E=int(cfg.get("router_experts") or cfg["n_routed_experts"]),
        held=held, k=int(cfg["num_experts_per_tok"]),
        f=int(cfg["moe_intermediate_size"]),
        route_norm=bool(cfg["norm_topk_prob"]),
        route_scale=float(cfg.get("routed_scaling_factor") or 1.0),
        V=int(cfg["vocab_size"]), P=int(cfg["max_position_embeddings"]))


def leaf_shapes(cfg):
    """Ordered {leaf: shape}. A leaf is one array of one layer
    ("l<i>.<name>"); the experts HELD of a layer are one leaf [E_held, ., .],
    the router scores all `router_experts`."""
    m = dims(cfg)
    d, held = m["d"], m["held"][1]
    out = {"embed": (m["V"], d)}
    for i in range(m["L"]):
        p = "l%d." % i
        hq, hkv, dk, dv, _ = m["geo"][m["kinds"][i]]
        out.update({p + "attn_norm": (d,), p + "wq": (d, hq * dk),
                    p + "wk": (d, hkv * dk), p + "wv": (d, hkv * dv)})
        if m["sinks"][m["kinds"][i]]:
            out[p + "sink"] = (hq,)
        out.update({p + "wo": (hq * dv, d), p + "pre_mlp_norm": (d,)})
        if not m["moe_at"][i]:
            out.update({p + "gate": (d, m["F"]), p + "up": (d, m["F"]),
                        p + "down": (m["F"], d)})
        else:
            out.update({
                p + "router": (d, m["E"]), p + "expert_bias": (m["E"],),
                p + "e_gate": (held, d, m["f"]),
                p + "e_up": (held, d, m["f"]),
                p + "e_down": (held, m["f"], d)})
    out["norm_f"] = (d,)
    out["head"] = (m["V"], d)
    return out


def leaf_draw(name):
    """(mean, std, float32?) a leaf is drawn with: every norm gain, the
    correction bias and the sink matter to the result, so none is left at 1
    or 0. The sink is drawn N(5, 1): a window's 128 scores (standard
    deviation 1.6 at the published widths) sum to e^6 in the denominator,
    so a sink of N(0, 1) is a hundredth of it and dropping it read nothing
    on the chip (mean gap 0.0002 beside the program's 0.0004); at e^5 it
    takes a quarter of a head's weight, as a trained sink does.

    The correction bias is drawn N(0, 0.005), not N(0, 0.1). It is the term
    that BALANCES the experts' load in a trained checkpoint. Against these
    scores (sigmoids of logits of standard deviation 1.28: the eighth of 256
    lies at 0.92, where 0.1 is a whole standard deviation of the logit) a
    draw of N(0, 0.1) did the opposite: the experts with a bias below zero
    were all but never chosen, half of the 16 held here among them, WHICH
    half by the seed, and the grouped product skips an expert with no row:
    the decode step read 18.5 ms as drawn and 23.9 ms with the bias scaled
    by 0.05 or left out (one process, one seed, on the chip), and the cell's
    tokens a second followed the seed (16 250-18 390). At 0.005 the bias
    still moves the eighth choice of most tokens (one or two of 256 scores
    lie that close to the eighth) and every held expert has rows in every
    step, which is what a deployment's step reads."""
    if name.endswith("norm") or name == "norm_f":
        return 1.0, 0.02, False
    if name.endswith("expert_bias"):
        return 0.0, 0.005, True        # a buffer: float32 as published
    if name.endswith("sink"):
        return 5.0, 1.0, True
    return 0.0, 0.02, False


def make_weights(cfg, seed, dtype="bfloat16"):
    """Every leaf from the seed, on the device, in the type it is served
    in; one jitted draw a leaf, so that no more than one leaf's float32
    draw is alive beside the weights."""
    key, dt = seed_key(seed), jnp.dtype(dtype)
    out = {}
    for i, (name, shape) in enumerate(leaf_shapes(cfg).items()):
        mean, std, f32 = leaf_draw(name)
        out[name] = _draw(jax.random.fold_in(key, i), shape,
                          F32 if f32 else dt, mean, std)
    return out


# ---------------------------------------------------------------------------
# the mathematics


def _rms(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w.astype(F32)


def _rotary(x, theta, r):
    """x [T, H, d_k] at positions 0..T-1: rotate-half over the first r dims
    of every head, the rest as they are."""
    T = x.shape[0]
    inv = theta ** (-jnp.arange(0, r, 2, dtype=F32) / r)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]     # [T, r/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    xr, rest = x[..., :r], x[..., r:]
    x1, x2 = xr[..., :r // 2], xr[..., r // 2:]
    rot = xr * cos + jnp.concatenate([-x2, x1], -1) * sin
    return jnp.concatenate([rot, rest], -1)


def _swiglu(x, gate, up, down, int8):
    g = _mm("td,df->tf", x, gate.astype(F32), int8)
    u = _mm("td,df->tf", x, up.astype(F32), int8)
    return _mm("tf,fd->td", jax.nn.silu(g) * u, down.astype(F32), int8)


def _attention(m, kind, lw, h, int8, fault):
    T = h.shape[0]
    hq, hkv, dk, dv, theta = m["geo"][kind]
    if fault == "one_theta":
        theta = m["geo"]["full"][4]
    r = dk if fault == "rotary_whole" else int(dk * m["rot"])
    window = {"window_127": m["window"] - 1,
              "window_129": m["window"] + 1}.get(fault, m["window"])
    masked = kind == "window" or fault == "full_windowed"
    a = _rms(h, lw["attn_norm"], m["eps"])
    q = _mm("td,df->tf", a, lw["wq"].astype(F32), int8).reshape(T, hq, dk)
    k = _mm("td,df->tf", a, lw["wk"].astype(F32), int8).reshape(T, hkv, dk)
    v = _mm("td,df->tf", a, lw["wv"].astype(F32), int8).reshape(T, hkv, dv)
    if fault != "no_value_scale":
        v = v * m["vscale"]
    q, k = _rotary(q, theta, r), _rotary(k, theta, r)
    grp = hq // hkv
    q = q.reshape(T, hkv, grp, dk)
    sink = None
    if "sink" in lw and fault != "no_sink":
        sink = lw["sink"].astype(F32).reshape(hkv, grp, 1, 1)
    rows = min(Q_ROWS, T)
    if T % rows:
        raise ValueError("a sequence of %d rows is not whole blocks of %d"
                         % (T, rows))
    j = jnp.arange(T)[None, :]

    def block(i0):
        qb = jax.lax.dynamic_slice_in_dim(q, i0, rows, 0)
        s = _mm("tkgd,skd->kgts", qb, k, int8) / math.sqrt(dk)
        i = i0 + jnp.arange(rows)[:, None]
        ok = j <= i
        if masked:
            ok = ok & (i - j < window)
        s = jnp.where(ok, s, -1e30)
        if sink is None:
            p = jax.nn.softmax(s, axis=-1)
        else:
            big = jnp.maximum(jnp.max(s, -1, keepdims=True), sink)
            e = jnp.exp(s - big)
            p = e / (jnp.sum(e, -1, keepdims=True) + jnp.exp(sink - big))
        return _mm("kgts,skd->tkgd", p, v, int8)

    o = jax.lax.map(block, jnp.arange(0, T, rows)).reshape(T, hq * dv)
    return _mm("tf,fd->td", o, lw["wo"].astype(F32), int8)


def route(m, lw, x, int8=False):
    """(chosen [T, k] expert ids, weights [T, k]) of the tokens x [T, d]:
    sigmoid scores in float32 over ALL the router's experts, the choice by
    score + bias, the weight by the score alone, normalised over the
    chosen and scaled."""
    s = jax.nn.sigmoid(_mm("td,de->te", x.astype(F32),
                           lw["router"].astype(F32), int8))
    _, chosen = jax.lax.top_k(s + lw["expert_bias"].astype(F32), m["k"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if m["route_norm"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return chosen, w * m["route_scale"]


def moe(m, lw, x, quant=None, experts_held=None):
    """The expert layer on x [T, d] (float32): the weighted SwiGLUs of the
    experts [first, first + count) — by default all that the weights hold,
    `m["held"]`. Experts are raised to float32 and computed a block at a
    time, every token through every expert of the block, the unchosen
    weighted 0: a plain mask, no sorting, no capacity, no shared expert."""
    x = x.astype(F32)
    T, d = x.shape
    int8 = "bf16" if quant == "bf16" else quant == "int8"
    chosen, w = route(m, lw, x, int8)
    base = m["held"][0]                      # the weights' first expert
    first, count = experts_held or m["held"]
    # wm[t, e]: the weight of expert e for token t, 0 where not chosen
    wm = jnp.zeros((T, m["E"]), F32).at[
        jnp.arange(T)[:, None], chosen].add(w)
    wm = wm[:, first:first + count]
    blk = math.gcd(EXPERT_BLOCK, count)

    def body(acc, e0):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(        # noqa: E731
            a, first - base + e0, blk, 0).astype(F32)
        g = _mm("td,edf->etf", x, sl(lw["e_gate"]), int8)
        u = _mm("td,edf->etf", x, sl(lw["e_up"]), int8)
        y = _mm("etf,efd->etd", jax.nn.silu(g) * u, sl(lw["e_down"]), int8)
        we = jax.lax.dynamic_slice_in_dim(wm, e0, blk, 1)    # [T, blk]
        return acc + jnp.einsum("etd,te->td", y, we,
                                precision=jax.lax.Precision.HIGHEST), None

    out, _ = jax.lax.scan(body, jnp.zeros((T, d), F32),
                          jnp.arange(0, count, blk))
    return out


def _layer(m, i, lw, h, quant, fault):
    int8 = "bf16" if quant == "bf16" else quant == "int8"
    h = h + _attention(m, m["kinds"][i], lw, h, int8, fault)
    x = _rms(h, lw["pre_mlp_norm"], m["eps"])
    if m["moe_at"][i]:
        return h + moe(m, lw, x, quant)
    return h + _swiglu(x, lw["gate"], lw["up"], lw["down"], int8)


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def _hidden_jit(cfg_t, w, ids, quant, fault):
    m = dims(dict(cfg_t))
    h = w["embed"][ids].astype(F32)
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
    frozen = tuple((k, v) for k, v in _freeze(cfg)
                   if k not in ("published", "deployment"))
    with jax.default_matmul_precision("highest"):
        return _hidden_jit(frozen, w, jnp.asarray(ids, jnp.int32), quant,
                           fault)


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
    """TWO numbers for the sequences (each prompt + served tokens), as
    `reference_afmoe.served_gaps` gives them and for its reasons (a routed
    model: a score rounded in bfloat16 flips a top-k choice now and then):
    the MEAN, over all served tokens of all the sequences, of how far the
    token's float32 logit lies below the reference's best at its position,
    in units of the row's standard deviation; and the WIDEST such gap
    divided by `TOKENS_A_MEAN` (1000 here). The harness compares the larger
    of the two (`served_gap_max`) with the cell's one limit.

    With quant set, or a fault of the forward pass, the token judged at
    each position is instead the one the altered forward puts first there
    (the control need not decode); the fault "one_token" judges the served
    tokens with the LAST of each request replaced by a token of its
    prompt. `per_token=True` returns every token's gap, one array a
    sequence."""
    out = []
    altered = quant or fault not in (None, "one_token")
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
