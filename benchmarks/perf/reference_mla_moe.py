"""Plain float32 reference for the `mla_moe` family (the language model of
Kimi-VL-A3B: multi-head latent attention over routed experts with a shared
expert), and its weights.

Pure `jax.numpy`: imports nothing of `paddle_tpu` and takes nothing the
program has made. No kernel, no cache, no batching: one sequence, the whole
forward pass, every matrix product in float32 at `highest`, the latent
ALWAYS expanded into per-head keys and values (the program's decode never
expands it: that the two agree is what the cell's `correct` says). The
configuration's keys are those of the published `config.json`. From
`reference_afmoe` come the seed and comparison plumbing alone (the draw of a
leaf, the rounding of a control's operands, the two numbers a run is judged
by); the layer below is this file's own.

The layer, as this file computes it (H = `num_attention_heads`, r =
`kv_lora_rank`, dn = `qk_nope_head_dim`, dr = `qk_rope_head_dim`, dv =
`v_head_dim`):

    a      = RMSNorm(h; attn_norm, rms_norm_eps)
    q      = a.Wq [T, H, dn + dr] = (q_nope | q_rope)     (q_lora_rank null)
    a.Wkv_a [T, r + dr] = (c_raw | k_rope_raw);  c = RMSNorm(c_raw; kv_norm)
    rotary (rope_theta, no scaling) on q_rope and on k_rope alone, over
      ADJACENT pairs (x[2i], x[2i+1]) with angle pos * theta^(-2i/dr);
      k_rope is ONE head that all H share
    c.Wkv_b [T, H, dn + dv] = (k_nope | v);  k = (k_nope | k_rope)
    s_tj   = q_t.k_j / sqrt(dn + dr), j <= t;  p = softmax_j(s) in float32
    h      = h + (p.v).Wo;  x = RMSNorm(h; pre_mlp_norm)
    layer i < first_k_dense_replace:  h = h + SwiGLU(x) of intermediate_size
    otherwise (moe_layer_freq 1):  s = sigmoid(x.W_r) in float32 over ALL
      `router_experts`;  S = top-k(s + expert_bias)      (noaux_tc, n_group 1)
      w_e = routed_scaling_factor * s_e / (sum_S s + 1e-20)  (norm_topk_prob)
      h = h + sum over e in S THAT ARE HELD HERE of w_e SwiGLU_e(x)
            + SwiGLU_shared(x) of n_shared_experts * moe_intermediate_size
    logits = RMSNorm_f(h).W_head over the rows of the vocabulary held here

No biases. Each point above that is not a key's plain meaning is listed in
the configuration file's `assumed`.

THE SHARE. As in the `mimo_v2` family: the configuration gives this chip
`n_routed_experts` experts, `experts_held` = (first, count) of the
`router_experts` the router scores; the weights hold those alone, and what
the absent experts would add is left out here as in the program. The shared
expert is whole on every chip. `moe(experts_held=, shared=)` computes a
narrower share (the CPU test adds all shares up to the whole layer, the
shared expert counted once).

`quant` is the control of "how `correct` is decided": "int8" rounds both
operands of every matrix product to int8 under a per-tensor symmetric absmax
scale; "bf16" rounds them to bfloat16. `fault` plants what a wrong program
would compute: "scale_row" (the scores scaled by 1/sqrt(r + dr), the cached
row's width, in place of 1/sqrt(dn + dr)), "no_kv_norm" (`kv_norm` left
out), "no_k_rotary" (the shared key part unrotated), "rotary_halves" (halves
rotated in place of adjacent pairs), "no_shared" (the shared expert
dropped), "route_scale_1" (`routed_scaling_factor` 1) and, in `served_gaps`
alone, "one_token".
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
FAULTS = (None, "scale_row", "no_kv_norm", "no_k_rotary", "rotary_halves",
          "no_shared", "route_scale_1", "one_token")
#: widths a sequence is padded to (causal, so exact): four compiled shapes
#: up to the cell's 16 384 positions
PAD_TO = 4096
#: query rows and experts computed at a time (what fits beside the weights)
Q_ROWS = 256
EXPERT_BLOCK = 4
#: the widest served token's gap is divided by this before it stands beside
#: the mean gap under the cell's one limit (`served_gaps`): a sound run's
#: mean gap reads 0.0018-0.0027 and its widest token (a flipped choice)
#: 0.45-1.22, a dropped part of the mathematics a mean of 0.075 and more, a
#: stray token 4.0 (the chip's readings: the traffic file's `readings`)
TOKENS_A_MEAN = 200.0


def dims(cfg):
    L = int(cfg["num_hidden_layers"])
    if cfg.get("q_lora_rank") is not None or cfg.get("rope_scaling"):
        raise ValueError("q_lora_rank and rope_scaling are not in this "
                         "reference")
    freq, dense = int(cfg["moe_layer_freq"]), \
        int(cfg["first_k_dense_replace"])
    held = tuple(cfg.get("experts_held") or (0, int(cfg["n_routed_experts"])))
    if held[1] != int(cfg["n_routed_experts"]):
        raise ValueError("experts_held counts %d experts, n_routed_experts "
                         "%d" % (held[1], int(cfg["n_routed_experts"])))
    return dict(
        d=int(cfg["hidden_size"]), L=L, H=int(cfg["num_attention_heads"]),
        r=int(cfg["kv_lora_rank"]), dn=int(cfg["qk_nope_head_dim"]),
        dr=int(cfg["qk_rope_head_dim"]), dv=int(cfg["v_head_dim"]),
        theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]),
        moe_at=tuple(i >= dense and i % freq == 0 for i in range(L)),
        F=int(cfg["intermediate_size"]),
        E=int(cfg.get("router_experts") or cfg["n_routed_experts"]),
        held=held, k=int(cfg["num_experts_per_tok"]),
        f=int(cfg["moe_intermediate_size"]),
        fs=int(cfg["moe_intermediate_size"])
        * int(cfg.get("n_shared_experts") or 0),
        route_norm=bool(cfg["norm_topk_prob"]),
        route_scale=float(cfg.get("routed_scaling_factor") or 1.0),
        V=int(cfg["vocab_size"]), P=int(cfg["max_position_embeddings"]))


def leaf_shapes(cfg):
    """Ordered {leaf: shape}. A leaf is one array of one layer
    ("l<i>.<name>"); the experts HELD of a layer are one leaf [E_held, ., .],
    the router scores all `router_experts`."""
    m = dims(cfg)
    d, H, held = m["d"], m["H"], m["held"][1]
    out = {"embed": (m["V"], d)}
    for i in range(m["L"]):
        p = "l%d." % i
        out.update({
            p + "attn_norm": (d,), p + "wq": (d, H * (m["dn"] + m["dr"])),
            p + "wkv_a": (d, m["r"] + m["dr"]), p + "kv_norm": (m["r"],),
            p + "wkv_b": (m["r"], H * (m["dn"] + m["dv"])),
            p + "wo": (H * m["dv"], d), p + "pre_mlp_norm": (d,)})
        if not m["moe_at"][i]:
            out.update({p + "gate": (d, m["F"]), p + "up": (d, m["F"]),
                        p + "down": (m["F"], d)})
        else:
            out.update({
                p + "router": (d, m["E"]), p + "expert_bias": (m["E"],),
                p + "e_gate": (held, d, m["f"]),
                p + "e_up": (held, d, m["f"]),
                p + "e_down": (held, m["f"], d)})
            if m["fs"]:
                out.update({p + "s_gate": (d, m["fs"]),
                            p + "s_up": (d, m["fs"]),
                            p + "s_down": (m["fs"], d)})
    out["norm_f"] = (d,)
    out["head"] = (m["V"], d)
    return out


def leaf_draw(name):
    """(mean, std, float32?) a leaf is drawn with: every norm gain and the
    correction bias matter to the result, so none is left at 1 or 0. The
    bias is drawn N(0, 0.005): wide enough to move the last choice of most
    tokens, narrow enough that every held expert has rows in every step, as
    the bias of a trained checkpoint, which balances the load, leaves it
    (PERF.md section 7 (16): N(0, 0.1) switched half of a share's experts
    off, which half by the seed)."""
    if name.endswith("norm") or name == "norm_f":
        return 1.0, 0.02, False
    if name.endswith("expert_bias"):
        return 0.0, 0.005, True        # a buffer: float32 as published
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


def _pair_swap(d):
    """The d x d matrix S with (x.S)[2i] = -x[2i+1], (x.S)[2i+1] = x[2i]:
    a pair turned by a quarter."""
    s = np.zeros((d, d), np.float32)
    s[np.arange(1, d, 2), np.arange(0, d, 2)] = -1.0
    s[np.arange(0, d, 2), np.arange(1, d, 2)] = 1.0
    return jnp.asarray(s)


def _rotary(x, theta, halves=False):
    """x [T, H, d] at positions 0..T-1. Adjacent pairs (x[2i], x[2i+1])
    turned by pos * theta^(-2i/d): x cos + (x.S) sin with the angle of a
    pair on both of its entries. `halves` (the planted fault): the pairs
    (x[i], x[i + d/2]) instead, as `rotate_half` does without the
    published code's de-interleave."""
    T, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]     # [T, d/2]
    if halves:
        cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
        sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
        x1, x2 = x[..., :d // 2], x[..., d // 2:]
        return x * cos + jnp.concatenate([-x2, x1], -1) * sin
    # each pair's angle on both of its entries, by a 0/1 matrix (no array
    # with a minor dimension of 2)
    twice = jnp.asarray(np.repeat(np.eye(d // 2, dtype=np.float32), 2, 1))
    hi = jax.lax.Precision.HIGHEST
    cos = jnp.dot(jnp.cos(ang), twice, precision=hi)[:, None, :]
    sin = jnp.dot(jnp.sin(ang), twice, precision=hi)[:, None, :]
    return x * cos + jnp.einsum("thd,de->the", x, _pair_swap(d),
                                precision=hi) * sin


def _swiglu(x, gate, up, down, int8):
    g = _mm("td,df->tf", x, gate.astype(F32), int8)
    u = _mm("td,df->tf", x, up.astype(F32), int8)
    return _mm("tf,fd->td", jax.nn.silu(g) * u, down.astype(F32), int8)


def _attention(m, lw, h, int8, fault):
    T = h.shape[0]
    H, r, dn, dr, dv = m["H"], m["r"], m["dn"], m["dr"], m["dv"]
    a = _rms(h, lw["attn_norm"], m["eps"])
    q = _mm("td,df->tf", a, lw["wq"].astype(F32), int8).reshape(
        T, H, dn + dr)
    row = _mm("td,df->tf", a, lw["wkv_a"].astype(F32), int8)
    c = row[:, :r]
    if fault != "no_kv_norm":
        c = _rms(c, lw["kv_norm"], m["eps"])
    halves = fault == "rotary_halves"
    q_rope = _rotary(q[..., dn:], m["theta"], halves)
    k_rope = row[:, None, r:]                                  # [T, 1, dr]
    if fault != "no_k_rotary":
        k_rope = _rotary(k_rope, m["theta"], halves)
    kv = _mm("tr,rf->tf", c, lw["wkv_b"].astype(F32), int8).reshape(
        T, H, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        k_rope, (T, H, dr))], -1)
    v = kv[..., dn:]
    q = jnp.concatenate([q[..., :dn], q_rope], -1)
    scale = 1.0 / math.sqrt(r + dr if fault == "scale_row" else dn + dr)
    rows = min(Q_ROWS, T)
    if T % rows:
        raise ValueError("a sequence of %d rows is not whole blocks of %d"
                         % (T, rows))
    j = jnp.arange(T)[None, :]

    def block(i0):
        qb = jax.lax.dynamic_slice_in_dim(q, i0, rows, 0)
        s = _mm("thd,shd->hts", qb, k, int8) * scale
        i = i0 + jnp.arange(rows)[:, None]
        p = jax.nn.softmax(jnp.where(j <= i, s, -1e30), axis=-1)
        return _mm("hts,shd->thd", p, v, int8)

    o = jax.lax.map(block, jnp.arange(0, T, rows)).reshape(T, H * dv)
    return _mm("tf,fd->td", o, lw["wo"].astype(F32), int8)


def route(m, lw, x, int8=False, fault=None):
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
    return chosen, w * (1.0 if fault == "route_scale_1" else m["route_scale"])


def moe(m, lw, x, quant=None, fault=None, experts_held=None, shared=True):
    """The expert layer on x [T, d] (float32): the weighted SwiGLUs of the
    experts [first, first + count) — by default all that the weights hold,
    `m["held"]` — and, with `shared`, the shared expert. Experts are raised
    to float32 and computed a block at a time, every token through every
    expert of the block, the unchosen weighted 0: a plain mask, no sorting,
    no capacity."""
    x = x.astype(F32)
    T, d = x.shape
    int8 = "bf16" if quant == "bf16" else quant == "int8"
    chosen, w = route(m, lw, x, int8, fault)
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
    if shared and m["fs"] and fault != "no_shared":
        out = out + _swiglu(x, lw["s_gate"], lw["s_up"], lw["s_down"], int8)
    return out


def _layer(m, i, lw, h, quant, fault):
    int8 = "bf16" if quant == "bf16" else quant == "int8"
    h = h + _attention(m, lw, h, int8, fault)
    x = _rms(h, lw["pre_mlp_norm"], m["eps"])
    if m["moe_at"][i]:
        return h + moe(m, lw, x, quant, fault)
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
    divided by `TOKENS_A_MEAN`. The harness compares the larger of the two
    (`served_gap_max`) with the cell's one limit.

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
