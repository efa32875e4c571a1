"""The yardstick's arithmetic: chip peaks, and the operations and bytes the
algorithm needs, as functions of a configuration and of the tokens the
traffic actually sent. Nothing here imports the program.

A per-layer metric's file names one of these functions as
`"work": "work:<function>"`; each takes `(cfg, traffic, counts)` — the
configuration, the traffic mix, and what the window counted — and returns
`(flops, bytes)` for ONE call of the kernel or program.
"""
from __future__ import annotations

#: device_kind substring (lowercase, first match wins) -> per-chip peaks.
#: Source: Google Cloud documentation, "TPU v5e" system architecture:
#: 197 TFLOP/s bf16, 819 GB/s HBM, 16 GB. A kind not listed is an error.
_V5E = {"flops": 197e12, "bytes_per_s": 819e9, "hbm": 16e9}
PEAKS = (("v5 lite", _V5E), ("v5e", _V5E))   # JAX reports "TPU v5 lite"


def peaks(device_kind):
    low = (device_kind or "").lower()
    for sub, row in PEAKS:
        if sub in low:
            return row
    raise KeyError("no peaks on record for device kind %r" % device_kind)


def dims(cfg):
    d = int(cfg["n_embd"])
    return dict(d=d, L=int(cfg["n_layer"]), nh=int(cfg["n_head"]),
                hd=d // int(cfg["n_head"]),
                f=int(cfg.get("n_inner") or 4 * d),
                V=int(cfg["vocab_size"]), P=int(cfg["n_positions"]))


def n_params(cfg):
    """Parameters of the GPT decoder, the tied head counted once."""
    m = dims(cfg)
    d, f = m["d"], m["f"]
    layer = (2 * d) * 2 + d * 3 * d + 3 * d + d * d + d + d * f + f \
        + f * d + d
    return (m["V"] + m["P"]) * d + m["L"] * layer + 2 * d


def _matmul_params(cfg):
    """Parameters that a token multiplies: the blocks' matrices and the
    head; the embedding lookups and the position table do no arithmetic."""
    m = dims(cfg)
    return m["L"] * (4 * m["d"] * m["d"] + 2 * m["d"] * m["f"]) \
        + m["V"] * m["d"]


def train_flops_per_token(cfg, seq_len):
    """Model FLOPs of forward + backward per token: 6 per multiplied
    parameter, plus causal attention's QK^T and PV over the keys a token
    may see — T/2 on average, so 6·L·d·T (half the 12·L·d·T that
    benchmarks/train_bench.py counts, which is attention without the
    mask). Recomputed work does not count."""
    m = dims(cfg)
    return 6.0 * _matmul_params(cfg) + 6.0 * m["L"] * m["d"] * seq_len


def forward_flops(cfg, n_tokens, ctx_sum):
    """Model FLOPs of a forward pass over n_tokens new tokens that attend
    ctx_sum keys in all (sum over the new tokens of their context
    length): 2 per multiplied parameter per token, 4·d per key per layer."""
    m = dims(cfg)
    return 2.0 * _matmul_params(cfg) * n_tokens \
        + 4.0 * m["L"] * m["d"] * ctx_sum


# -- kernels (one call) -----------------------------------------------------


def flash_fwd(cfg, traffic, counts):
    """Causal flash attention forward over [B·nh, T, hd] of one layer:
    QK^T and PV over the lower triangle; reads q, k, v, writes o (bf16)."""
    m = dims(cfg)
    B = int(traffic["batch"]) // int(counts.get("chips", 1))
    T = int(traffic["seq_len"])
    flops = 4.0 * B * m["nh"] * T * T * m["hd"] / 2.0
    return flops, 4.0 * B * m["nh"] * T * m["hd"] * 2


def flash_bwd(cfg, traffic, counts):
    """dq and dk/dv kernels together: the five products the algorithm
    needs over the triangle (S, dP, dV, dK, dQ), 2.5 x the forward's two;
    a second recomputation of S is the kernels' choice and does not count.
    Reads q, k, v, o, do, writes dq, dk, dv."""
    fwd, _ = flash_fwd(cfg, traffic, counts)
    m = dims(cfg)
    B = int(traffic["batch"]) // int(counts.get("chips", 1))
    T = int(traffic["seq_len"])
    return 2.5 * fwd, 8.0 * B * m["nh"] * T * m["hd"] * 2


def prefill_flash_fwd(cfg, traffic, counts):
    """Causal flash forward of one layer of one prefill, at the mean
    bucket the window dispatched (padding is work the kernel does, so the
    bucket and not the prompt sets the count)."""
    m = dims(cfg)
    T = float(counts["prefill_bucket_mean_sq"]) ** 0.5
    Tb = float(counts["prefill_bucket_mean"])
    return 4.0 * m["nh"] * T * T * m["hd"] / 2.0, \
        4.0 * m["nh"] * Tb * m["hd"] * 2


def paged_decode(cfg, traffic, counts):
    """Paged decode attention of one layer for one step: every live slot
    reads its cache rows once (k and v, bf16) — bandwidth bound."""
    m = dims(cfg)
    rows = float(counts["live_rows_mean"])        # sum of live lengths
    by = 2.0 * rows * m["nh"] * m["hd"] * 2
    return 4.0 * rows * m["nh"] * m["hd"], by


def decode_step(cfg, traffic, counts):
    """One whole decode step: every weight read once (bf16) plus the live
    cache rows of every layer; FLOPs of max_batch tokens."""
    m = dims(cfg)
    rows = float(counts["live_rows_mean"])
    slots = int(traffic["max_batch"])
    by = 2.0 * n_params(cfg) + m["L"] * 2.0 * rows * m["nh"] * m["hd"] * 2
    return forward_flops(cfg, slots, rows), by


def flash_fwd_bwd(cfg, traffic, counts):
    """Forward, dq and dk/dv of one layer together (three kernel events):
    for a path whose kernels cannot be told apart by name, as under
    `shard_map`, where all three are `custom-call.shard_map`."""
    f, fb = flash_fwd(cfg, traffic, counts)
    b, bb = flash_bwd(cfg, traffic, counts)
    return f + b, fb + bb
