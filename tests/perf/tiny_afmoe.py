"""The `afmoe` family cut to a CPU test's size: an `afmoe-tiny`
configuration and a tiny closed-loop mix ADDED to a temporary copy of the
benchmark (beside `tiny.py`'s, whose helpers this reuses), so that the real
harness, windows and readers run the new family with no edit to a file
that is there."""
from __future__ import annotations

import json
import os
import shutil

import tiny

CELL = "trinity-mini-serve-longmix"          # whose metrics the tiny cell reads
TINY_CONFIG = {
    "family": "afmoe", "source": "tests only", "hidden_size": 64,
    "num_hidden_layers": 5, "num_dense_layers": 1,
    "layer_types": ["sliding_attention"] * 4 + ["full_attention"],
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "sliding_window": 8, "rope_theta": 10000, "rms_norm_eps": 1e-05,
    "intermediate_size": 96, "num_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "num_shared_experts": 1,
    "route_norm": True, "route_scale": 2.826, "mup_enabled": True,
    "vocab_size": 256, "max_position_embeddings": 512, "reduced": []}
TINY_MIX = {
    "kind": "serve_closed_loop", "max_batch": 4, "max_seq_len": 64,
    "kv_dtype": "float32", "weights_dtype": "float32",
    "prefix_cache_bytes": 0, "check_requests": 4, "trace_after_s": 0.0,
    "trace_seconds": 0.2, "prefill_buckets": [16, 32], "clients": 6,
    "pool": 8, "lead_in_completions": 4,
    "prompt_len": {"dist": "uniform", "min": 6, "max": 30},
    "output_len": {"dist": "uniform", "min": 4, "max": 10},
    # float32 on both sides: the sound program reads 0.0; under int8
    # experts two of the pool's eight requests serve another token here and
    # there and the mean gap of all served tokens reads 0.0008
    "limits": {"served_gap_max": 0.0002}, "why": "CPU tests"}


def make_root(tmp, changes=None):
    """Copy BENCHMARK.json and benchmarks/perf to `tmp` and ADD the tiny
    configuration, the tiny mix and the cell `tiny.longmix`, which reports
    every metric the real cell does. Returns the root."""
    perf = os.path.join(tmp, "benchmarks", "perf")
    shutil.copytree(tiny.PERF, perf)
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(perf, "configs", "afmoe-tiny.json"), "w") as f:
        json.dump(TINY_CONFIG, f)
    with open(os.path.join(perf, "traffic", "tiny_longmix.json"), "w") as f:
        json.dump(dict(TINY_MIX, **(changes or {})), f)
    bench["configs"].append({
        "name": "afmoe-tiny", "source": "tests only",
        "file": "benchmarks/perf/configs/afmoe-tiny.json", "reduced": [],
        "why": "CPU tests"})
    bench["workloads"].append({
        "name": "tiny.longmix", "config": "afmoe-tiny",
        "traffic": "tiny_longmix", "chips": 1, "why": "CPU tests"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny.longmix")
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp
