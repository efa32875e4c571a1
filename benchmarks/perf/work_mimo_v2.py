"""Work counts of the `mimo_v2` family: the operations and bytes the algorithm
needs ON THIS CHIP, as functions of a configuration and of the traffic sent.
Nothing here imports the program. Each kernel function takes `(cfg, traffic,
counts)` and returns `(flops, bytes)` of what its metric file calls ONE call
(see each).

WHAT IS COUNTED OF THE EXPERTS. A token is routed to `num_experts_per_tok`
(8) of the router's `router_experts` (256); this chip holds
`n_routed_experts` (16) of them and computes only the assignments that fall
on those: under even routing a sixteenth, 0.5 expert a token a layer. Every
count below is of THAT share — `step.mfu.longgen` is the share of this
chip's peak that this chip's part of the model needs, not the whole
model's: counting all 8 experts would credit the chip with fifteen
sixteenths of work that other chips do.
"""
from __future__ import annotations

from work_afmoe import BF16, _window_keys


def dims(cfg):
    kinds = list(cfg["hybrid_layer_pattern"])
    freq = list(cfg["moe_layer_freq"])
    held = int(cfg["n_routed_experts"])
    return dict(
        d=int(cfg["hidden_size"]), L=int(cfg["num_hidden_layers"]),
        full=kinds.count(0), win=kinds.count(1),
        W=int(cfg["sliding_window"]),
        geo={"full": (int(cfg["num_attention_heads"]),
                      int(cfg["num_key_value_heads"]),
                      int(cfg["head_dim"]), int(cfg["v_head_dim"])),
             "win": (int(cfg["swa_num_attention_heads"]),
                     int(cfg["swa_num_key_value_heads"]),
                     int(cfg["swa_head_dim"]), int(cfg["swa_v_head_dim"]))},
        dense=freq.count(0), moe=freq.count(1),
        F=int(cfg["intermediate_size"]),
        E=int(cfg.get("router_experts") or held), held=held,
        k=int(cfg["num_experts_per_tok"]),
        f=int(cfg["moe_intermediate_size"]), V=int(cfg["vocab_size"]))


def attention_params(cfg, kind):
    m = dims(cfg)
    hq, hkv, dk, dv = m["geo"][kind]
    return m["d"] * (hq * dk + hkv * dk + hkv * dv) + hq * dv * m["d"]


def matmul_params_per_token(cfg):
    """Parameters ONE token multiplies ON THIS CHIP: every layer's
    attention matrices, the dense layers' SwiGLU, and on an expert layer
    the whole router and the `num_experts_per_tok * held / router_experts`
    routed experts that an evenly routed token finds here; then the rows
    of the head held here. The embedding is a lookup."""
    m = dims(cfg)
    here = m["k"] * m["held"] / float(m["E"])
    moe = m["d"] * m["E"] + here * 3 * m["d"] * m["f"]
    return m["full"] * attention_params(cfg, "full") \
        + m["win"] * attention_params(cfg, "win") \
        + m["dense"] * 3 * m["d"] * m["F"] + m["moe"] * moe \
        + m["V"] * m["d"]


def _key_flops(m, kind):
    """FLOPs one query position spends on one key of a layer of `kind`:
    the score product over the key size and the value product over the
    value size, every query head."""
    hq, _, dk, dv = m["geo"][kind]
    return 2.0 * hq * (dk + dv)


def forward_flops(cfg, n_tokens, ctx_sum):
    """This chip's model FLOPs of a forward pass over n_tokens new tokens
    whose context lengths are consecutive and add up to ctx_sum: 2 per
    multiplied parameter per token (`matmul_params_per_token`: the held
    share of the experts); per key 2 · heads · (key size + value size),
    over all the keys on a full layer and over min(ctx, sliding_window) on
    a window layer."""
    m = dims(cfg)
    return 2.0 * matmul_params_per_token(cfg) * n_tokens \
        + m["full"] * _key_flops(m, "full") * ctx_sum \
        + m["win"] * _key_flops(m, "win") \
        * _window_keys(n_tokens, ctx_sum, m["W"])


# -- kernels ------------------------------------------------------------------


def _row_bytes(m, kind):
    _, hkv, dk, dv = m["geo"][kind]
    return hkv * (dk + dv) * BF16


def ring_decode(cfg, traffic, counts):
    """The decode kernel over the window layers' rings in ONE decode step:
    one call a window layer, `events_per_call` = the window layers. Bytes:
    the ring rows the live slots hold, K and V: a slot holds min(context,
    sliding_window) rows, and only the SUM of the live contexts is counted
    (`live_rows_mean`), so the cap is taken on the mean slot, an upper
    bound; every prompt of the cell's traffic is at least a window long,
    so it reads `sliding_window` rows a slot."""
    m = dims(cfg)
    slots = int(traffic["max_batch"])
    rows = slots * min(float(counts["live_rows_mean"]) / slots,
                       float(m["W"]))
    return m["win"] * _key_flops(m, "win") * rows, \
        m["win"] * _row_bytes(m, "win") * rows


def full_decode(cfg, traffic, counts):
    """The decode kernel over the full layers' rows in ONE decode step:
    one call a full layer, `events_per_call` = the full layers. Bytes: the
    scheduler's live rows (`live_rows_mean`), K and V."""
    m = dims(cfg)
    rows = float(counts["live_rows_mean"])
    return m["full"] * _key_flops(m, "full") * rows, \
        m["full"] * _row_bytes(m, "full") * rows


def band_prefill(cfg, traffic, counts):
    """The band prefill kernel over ONE prefill: one call a layer of either
    kind under one name, `events_per_call` = the layers. FLOPs of the
    band, not of the square: T^2 / 2 keys on a full layer; on a window
    layer T·W - W^2/2 at a bucket T >= W. At the mean bucket the window
    dispatched. Bytes: q and o of all query heads, k and v of the
    key-value heads, once."""
    m = dims(cfg)
    t = float(counts["prefill_bucket_mean"])
    t_sq = float(counts["prefill_bucket_mean_sq"])
    band = t * m["W"] - m["W"] ** 2 / 2.0 if t > m["W"] else t_sq / 2.0
    flops = m["full"] * _key_flops(m, "full") * t_sq / 2.0 \
        + m["win"] * _key_flops(m, "win") * band
    by = 0.0
    for kind, n in (("full", m["full"]), ("win", m["win"])):
        hq, hkv, dk, dv = m["geo"][kind]
        by += n * t * (hq + hkv) * (dk + dv) * BF16
    return flops, by


def moe_grouped_decode(cfg, traffic, counts):
    """The three grouped products (gate, up, down) of ONE expert layer in
    one decode step — `events_per_call` 3 — as this chip's share needs
    them. FLOPs: 6 · d · f an assignment that falls on a held expert,
    max_batch · k · held / router_experts of them under even routing.
    Bytes: every held expert read once (192 tokens · 8 of 256: an expert
    is left untouched once in (1 - 1/32)^192 = 0.2 % of steps), plus the
    held assignments' rows in and out. The rows of the assignments that
    belong to experts held elsewhere are NOT counted: whatever the
    implementation spends on them reads as distance from the roofline."""
    m = dims(cfg)
    a = int(traffic["max_batch"]) * m["k"] * m["held"] / float(m["E"])
    weights = 3.0 * m["held"] * m["d"] * m["f"] * BF16
    rows = a * (2 * m["d"] + 3 * m["f"]) * BF16
    return 6.0 * m["d"] * m["f"] * a, weights + rows
