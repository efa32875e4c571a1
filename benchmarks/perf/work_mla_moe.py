"""Work counts of the `mla_moe` family: the operations and bytes the algorithm
needs ON THIS CHIP, as functions of a configuration and of the traffic sent.
Nothing here imports the program. Each kernel function takes `(cfg, traffic,
counts)` and returns `(flops, bytes)` of what its metric file calls ONE call
(see each).

WHAT IS COUNTED OF THE EXPERTS, as in `work_mimo_v2`: a token is routed to
`num_experts_per_tok` (6) of the router's `router_experts` (64); this chip
holds `n_routed_experts` (8) of them and computes only the assignments that
fall on those: under even routing an eighth, 0.75 expert a token a layer,
beside the shared expert, which every chip computes whole.

WHAT IS COUNTED OF ATTENTION. Model FLOPs (`forward_flops`) are the
EXPANDED form's, whatever the program runs: per key 2 * heads * (key size +
value size), the up-projection of a token's latent once a token. The
absorbed decode does more arithmetic a key (heads * (2 * latent + rotary))
to read fewer bytes; that surplus is the implementation's and is counted
only where a kernel's own roofline is taken (`latent_decode`).
"""
from __future__ import annotations

from work_afmoe import BF16


def dims(cfg):
    L = int(cfg["num_hidden_layers"])
    freq, first = int(cfg["moe_layer_freq"]), \
        int(cfg["first_k_dense_replace"])
    moe = sum(1 for i in range(L) if i >= first and i % freq == 0)
    held = int(cfg["n_routed_experts"])
    return dict(
        d=int(cfg["hidden_size"]), L=L, H=int(cfg["num_attention_heads"]),
        r=int(cfg["kv_lora_rank"]), dn=int(cfg["qk_nope_head_dim"]),
        dr=int(cfg["qk_rope_head_dim"]), dv=int(cfg["v_head_dim"]),
        dense=L - moe, moe=moe, F=int(cfg["intermediate_size"]),
        E=int(cfg.get("router_experts") or held), held=held,
        k=int(cfg["num_experts_per_tok"]),
        f=int(cfg["moe_intermediate_size"]),
        shared=int(cfg.get("n_shared_experts") or 0),
        V=int(cfg["vocab_size"]))


def attention_params(cfg):
    """wq, wkv_a, wkv_b, wo of one layer."""
    m = dims(cfg)
    return m["d"] * m["H"] * (m["dn"] + m["dr"]) \
        + m["d"] * (m["r"] + m["dr"]) \
        + m["r"] * m["H"] * (m["dn"] + m["dv"]) + m["H"] * m["dv"] * m["d"]


def matmul_params_per_token(cfg):
    """Parameters ONE token multiplies ON THIS CHIP: every layer's
    attention matrices, the dense layers' SwiGLU, and on an expert layer
    the whole router, the shared expert and the `num_experts_per_tok *
    held / router_experts` routed experts that an evenly routed token finds
    here; then the rows of the head held here. The embedding is a lookup."""
    m = dims(cfg)
    expert = 3 * m["d"] * m["f"]
    here = m["k"] * m["held"] / float(m["E"])
    moe = m["d"] * m["E"] + (here + m["shared"]) * expert
    return m["L"] * attention_params(cfg) \
        + m["dense"] * 3 * m["d"] * m["F"] + m["moe"] * moe \
        + m["V"] * m["d"]


def forward_flops(cfg, n_tokens, ctx_sum):
    """This chip's model FLOPs of a forward pass over n_tokens new tokens
    whose context lengths add up to ctx_sum: 2 per multiplied parameter per
    token, and per key 2 * heads * (key size + value size) on every layer
    (the expanded form: see the module docstring)."""
    m = dims(cfg)
    key = 2.0 * m["H"] * (m["dn"] + m["dr"] + m["dv"])
    return 2.0 * matmul_params_per_token(cfg) * n_tokens \
        + m["L"] * key * ctx_sum


# -- kernels ------------------------------------------------------------------


def latent_decode(cfg, traffic, counts):
    """The decode attention over the latent cache in ONE decode step: one
    call a layer, `events_per_call` = the layers. Bytes: the scheduler's
    live rows (`live_rows_mean`), each (latent + rotary part) numbers read
    ONCE — it is key and value at once — plus the new rows written. FLOPs:
    every head's score over the whole row and its value product over the
    latent part, 2 * heads * (latent + rotary + latent) a live row: the
    work of attending latents, whatever implements it."""
    m = dims(cfg)
    rows = float(counts["live_rows_mean"])
    row_bytes = (m["r"] + m["dr"]) * BF16
    slots = int(traffic["max_batch"])
    return m["L"] * 2.0 * m["H"] * (2 * m["r"] + m["dr"]) * rows, \
        m["L"] * row_bytes * (rows + slots)


def expanded_prefill(cfg, traffic, counts):
    """The band prefill kernel over ONE prefill: one call a layer,
    `events_per_call` = the layers. FLOPs of the causal triangle at the
    mean bucket the window dispatched, T^2 / 2 keys a head at 2 * (key size
    + value size) each. Bytes: q, k, v and o of every head, once."""
    m = dims(cfg)
    t = float(counts["prefill_bucket_mean"])
    t_sq = float(counts["prefill_bucket_mean_sq"])
    dk = m["dn"] + m["dr"]
    return m["L"] * 2.0 * m["H"] * (dk + m["dv"]) * t_sq / 2.0, \
        m["L"] * t * m["H"] * (2 * dk + 2 * m["dv"]) * BF16


def moe_grouped_decode(cfg, traffic, counts):
    """The three grouped products (gate, up, down) of ONE expert layer in
    one decode step — `events_per_call` 3 — as this chip's share needs
    them. FLOPs: 6 * d * f an assignment that falls on a held expert,
    max_batch * k * held / router_experts of them under even routing.
    Bytes: the held experts THAT HAVE ROWS read once — an expert with no
    row is not read (PERF.md section 7 (16)): under even routing one is
    left out of a step with probability (1 - k / router_experts) ^
    max_batch, 0.9 % at 48 slots and 6 of 64 — plus the held assignments'
    rows in and out."""
    m = dims(cfg)
    slots = int(traffic["max_batch"])
    a = slots * m["k"] * m["held"] / float(m["E"])
    touched = 1.0 - (1.0 - m["k"] / float(m["E"])) ** slots
    weights = 3.0 * m["held"] * touched * m["d"] * m["f"] * BF16
    rows = a * (2 * m["d"] + 3 * m["f"]) * BF16
    return 6.0 * m["d"] * m["f"] * a, weights + rows
