"""The `mla_moe` family cut to a CPU test's size: a `mla-tiny` configuration
and a tiny closed-loop mix ADDED to a temporary copy of the benchmark (beside
`tiny.py`'s, whose helpers this reuses), so that the real harness, windows
and readers run the new family with no edit to a file that is there."""
from __future__ import annotations

import json
import os
import shutil

import tiny

CELL = "kimi-vl-a3b-serve-doclong"        # whose metrics the tiny cell reads
TINY_CONFIG = {
    "family": "mla_moe", "source": "tests only", "hidden_size": 64,
    "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 4, "kv_lora_rank": 32, "q_lora_rank": None,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "rope_theta": 800000, "rope_scaling": None, "rms_norm_eps": 1e-05,
    "attention_bias": False, "hidden_act": "silu", "intermediate_size": 96,
    "moe_intermediate_size": 32, "moe_layer_freq": 1,
    "first_k_dense_replace": 1, "n_routed_experts": 4, "router_experts": 16,
    "experts_held": [4, 4], "n_shared_experts": 2, "num_experts_per_tok": 4,
    "norm_topk_prob": True, "routed_scaling_factor": 2.446,
    "scoring_func": "sigmoid", "n_group": 1, "topk_group": 1,
    "topk_method": "noaux_tc", "tie_word_embeddings": False,
    "vocab_size": 256, "max_position_embeddings": 512, "reduced": []}
TINY_MIX = {
    "kind": "serve_closed_loop", "max_batch": 4, "max_seq_len": 64,
    "kv_dtype": "float32", "weights_dtype": "float32",
    "prefix_cache_bytes": 0, "check_requests": 4, "trace_after_s": 0.0,
    "trace_seconds": 0.2, "prefill_buckets": [16, 32], "clients": 6,
    "pool": 8, "lead_in_completions": 4,
    "prompt_len": {"dist": "uniform", "min": 9, "max": 30},
    "output_len": {"dist": "uniform", "min": 4, "max": 10},
    # float32 on both sides: the sound program reads 0.0
    "limits": {"served_gap_max": 0.0002}, "why": "CPU tests"}


def wide_scores(monkeypatch, ref):
    """At the published widths a score q.k / sqrt(192) has a standard
    deviation near 1 (2048 products of a unit input with N(0, 0.02)
    weights a query entry, 512 a key entry behind a normed latent) and an
    expert's output stands beside the residual stream; at 64 wide the
    scores would be a hundredth of that, the softmax flat, the experts a
    rounding error, and where a key sits, how it is scaled or how the
    experts are weighted would hardly matter. The tiny size draws its
    attention projections and its experts wider to have both."""
    draw = ref.leaf_draw

    def wide(name):
        if name.endswith((".wq", ".wkv_a", ".wkv_b")):
            return 0.0, 0.2, False
        if ".e_" in name or ".s_" in name:
            return 0.0, 0.1, False
        return draw(name)
    monkeypatch.setattr(ref, "leaf_draw", wide)


def make_root(tmp, changes=None):
    """Copy BENCHMARK.json and benchmarks/perf to `tmp` and ADD the tiny
    configuration, the tiny mix and the cell `tiny.doclong`, which reports
    every metric the real cell does. Returns the root."""
    perf = os.path.join(tmp, "benchmarks", "perf")
    shutil.copytree(tiny.PERF, perf)
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(perf, "configs", "mla-tiny.json"), "w") as f:
        json.dump(TINY_CONFIG, f)
    with open(os.path.join(perf, "traffic", "tiny_doclong.json"), "w") as f:
        json.dump(dict(TINY_MIX, **(changes or {})), f)
    bench["configs"].append({
        "name": "mla-tiny", "source": "tests only",
        "file": "benchmarks/perf/configs/mla-tiny.json", "reduced": [],
        "why": "CPU tests"})
    bench["workloads"].append({
        "name": "tiny.doclong", "config": "mla-tiny",
        "traffic": "tiny_doclong", "chips": 1, "why": "CPU tests"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny.doclong")
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp
