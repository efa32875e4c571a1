"""The `mimo_v2` family cut to a CPU test's size: a `mimo-tiny` configuration
and a tiny closed-loop mix ADDED to a temporary copy of the benchmark (beside
`tiny.py`'s, whose helpers this reuses), so that the real harness, windows
and readers run the new family with no edit to a file that is there."""
from __future__ import annotations

import json
import os
import shutil

import tiny

CELL = "mimo-v2.5-serve-longgen"          # whose metrics the tiny cell reads
TINY_CONFIG = {
    "family": "mimo_v2", "source": "tests only", "model_type": "mimo_v2",
    "hidden_size": 64, "num_hidden_layers": 7,
    "hybrid_layer_pattern": [0, 1, 1, 1, 1, 0, 1],
    "moe_layer_freq": [0, 1, 1, 1, 1, 1, 1],
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 24,
    "v_head_dim": 16, "swa_num_attention_heads": 8,
    "swa_num_key_value_heads": 4, "swa_head_dim": 24, "swa_v_head_dim": 16,
    "sliding_window": 8, "sliding_window_size": 8,
    "attention_chunk_size": 8, "partial_rotary_factor": 0.334,
    "rope_theta": 10000000, "swa_rope_theta": 10000,
    "rope_scaling": {"rope_type": "default", "type": "default"},
    "attention_value_scale": 0.707, "attention_bias": False,
    "add_full_attention_sink_bias": False,
    "add_swa_attention_sink_bias": True, "layernorm_epsilon": 1e-05,
    "hidden_act": "silu", "intermediate_size": 96,
    "moe_intermediate_size": 32, "n_routed_experts": 4,
    "router_experts": 16, "experts_held": [4, 4], "n_shared_experts": None,
    "num_experts_per_tok": 4, "norm_topk_prob": True,
    "routed_scaling_factor": None, "scoring_func": "sigmoid", "n_group": 1,
    "topk_group": 1, "topk_method": "noaux_tc", "tie_word_embeddings": False,
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
    deviation of 1.6 (q and k entries 1.28: 4096 products of a unit input
    with N(0, 0.02) weights); at 64 wide it would be 0.03, the softmax
    flat, and where a key sits would hardly matter. The tiny size draws its
    q and k projections eight times wider to have the published scores."""
    draw = ref.leaf_draw
    monkeypatch.setattr(ref, "leaf_draw", lambda name: (
        (0.0, 0.16, False) if name.endswith((".wq", ".wk")) else draw(name)))


def make_root(tmp, changes=None):
    """Copy BENCHMARK.json and benchmarks/perf to `tmp` and ADD the tiny
    configuration, the tiny mix and the cell `tiny.longgen`, which reports
    every metric the real cell does. Returns the root."""
    perf = os.path.join(tmp, "benchmarks", "perf")
    shutil.copytree(tiny.PERF, perf)
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(perf, "configs", "mimo-tiny.json"), "w") as f:
        json.dump(TINY_CONFIG, f)
    with open(os.path.join(perf, "traffic", "tiny_longgen.json"), "w") as f:
        json.dump(dict(TINY_MIX, **(changes or {})), f)
    bench["configs"].append({
        "name": "mimo-tiny", "source": "tests only",
        "file": "benchmarks/perf/configs/mimo-tiny.json", "reduced": [],
        "why": "CPU tests"})
    bench["workloads"].append({
        "name": "tiny.longgen", "config": "mimo-tiny",
        "traffic": "tiny_longgen", "chips": 1, "why": "CPU tests"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny.longgen")
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp
