"""Work counts of the `afmoe` family: the operations and bytes the algorithm
needs, as functions of a configuration and of the traffic sent. Nothing here
imports the program. Each kernel function takes `(cfg, traffic, counts)` and
returns `(flops, bytes)` of what its metric file calls ONE call (see each).
"""
from __future__ import annotations

BF16 = 2


def dims(cfg):
    kinds = list(cfg["layer_types"])
    return dict(
        d=int(cfg["hidden_size"]), L=int(cfg["num_hidden_layers"]),
        full=kinds.count("full_attention"),
        win=kinds.count("sliding_attention"),
        W=int(cfg["sliding_window"]), nq=int(cfg["num_attention_heads"]),
        nkv=int(cfg["num_key_value_heads"]), hd=int(cfg["head_dim"]),
        dense=int(cfg["num_dense_layers"]), F=int(cfg["intermediate_size"]),
        E=int(cfg["num_experts"]), k=int(cfg["num_experts_per_tok"]),
        f=int(cfg["moe_intermediate_size"]),
        shared=int(cfg["num_shared_experts"]), V=int(cfg["vocab_size"]))


def attention_params(cfg):
    m = dims(cfg)
    qd, kd = m["nq"] * m["hd"], m["nkv"] * m["hd"]
    return m["d"] * (2 * qd + 2 * kd) + qd * m["d"]    # wq wg wk wv wo


def matmul_params_per_token(cfg):
    """Parameters ONE token multiplies: every layer's attention matrices,
    the dense layers' SwiGLU, and on an expert layer the router, the
    `num_experts_per_tok` routed experts and the shared expert — not the
    `num_experts` held; then the head. The embedding is a lookup."""
    m = dims(cfg)
    expert = 3 * m["d"] * m["f"]
    moe = m["d"] * m["E"] + (m["k"] + m["shared"]) * expert
    return m["L"] * attention_params(cfg) + m["dense"] * 3 * m["d"] * m["F"] \
        + (m["L"] - m["dense"]) * moe + m["V"] * m["d"]


def _window_keys(n, ctx_sum, W):
    """Sum of min(ctx, W) over n consecutive context lengths that add up
    to ctx_sum (a prefill's 1..p, a request's decoding p+1..p+o-1)."""
    if n <= 0:
        return 0.0
    first = ctx_sum / n - (n - 1) / 2.0
    below = min(max(int(W - first) + 1, 0), int(n))   # contexts <= W
    return below * first + below * (below - 1) / 2.0 + (n - below) * W


def forward_flops(cfg, n_tokens, ctx_sum):
    """Model FLOPs of a forward pass over n_tokens new tokens whose context
    lengths are consecutive and add up to ctx_sum: 2 per multiplied
    parameter per token; 4 · heads · head_dim per key, over all the keys on
    a full layer and over min(ctx, sliding_window) on a window layer."""
    m = dims(cfg)
    keys = m["full"] * ctx_sum \
        + m["win"] * _window_keys(n_tokens, ctx_sum, m["W"])
    return 2.0 * matmul_params_per_token(cfg) * n_tokens \
        + 4.0 * m["nq"] * m["hd"] * keys


# -- kernels ------------------------------------------------------------------


def moe_grouped_decode(cfg, traffic, counts):
    """The three grouped products (gate, up, down) of ONE expert layer in
    one decode step — `events_per_call` 3. FLOPs: 6 · d · f an assignment,
    max_batch · k assignments. Bytes: every expert that is touched read
    once, plus the tokens in and out. At 48 tokens · 8 of 128 experts the
    share touched under uniform routing is 1 - (1 - 8/128)^48 = 95.5 %;
    the routing's own skew (the bias) touches fewer, so the count is an
    upper bound of the bytes and the share reads a little high for it."""
    m = dims(cfg)
    a = int(traffic["max_batch"]) * m["k"]
    touched = 1.0 - (1.0 - m["k"] / float(m["E"])) ** int(traffic["max_batch"])
    weights = 3.0 * m["E"] * touched * m["d"] * m["f"] * BF16
    rows = a * (2 * m["d"] + 3 * m["f"]) * BF16
    return 6.0 * m["d"] * m["f"] * a, weights + rows


def paged_gqa_decode(cfg, traffic, counts):
    """The grouped-query paged decode kernel over ONE decode step: one call
    a layer, `events_per_call` = the layers (the calls of full and window
    layers bear one name). Bytes: the live cache rows (k and v, bf16): all
    of them on a full layer; on a window layer at most `sliding_window` a
    slot. Only the SUM of the live lengths is counted (`live_rows_mean`),
    so the cap is taken on the mean slot: min(mean, W) >= mean of min(len,
    W), an upper bound of the window layers' rows."""
    m = dims(cfg)
    rows = float(counts["live_rows_mean"])        # sum of live lengths
    slots = int(traffic["max_batch"])
    ring = slots * min(rows / slots, float(m["W"]))
    total = m["full"] * rows + m["win"] * ring
    return 4.0 * m["nq"] * m["hd"] * total, \
        2.0 * m["nkv"] * m["hd"] * BF16 * total


def prefill_band_flash(cfg, traffic, counts):
    """The band prefill kernel over ONE prefill: one call a layer,
    `events_per_call` = the layers. FLOPs of the band, not of the square:
    T^2 / 2 keys on a full layer; on a window layer T·W - W^2/2 at a bucket
    T >= W (never more than the band: (T - W)^2 >= 0, so a bucket under the
    window is counted low and the share never reads high for it). At the
    mean bucket the window dispatched. Bytes: q and o of all query heads,
    k and v of the key-value heads, once."""
    m = dims(cfg)
    t = float(counts["prefill_bucket_mean"])
    t_sq = float(counts["prefill_bucket_mean_sq"])
    band = t * m["W"] - m["W"] ** 2 / 2.0 if t > m["W"] else t_sq / 2.0
    keys = m["full"] * t_sq / 2.0 + m["win"] * band
    by = m["L"] * t * (2 * m["nq"] + 2 * m["nkv"]) * m["hd"] * BF16
    return 4.0 * m["nq"] * m["hd"] * keys, by
