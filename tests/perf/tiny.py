"""A temporary copy of the benchmark cut to gpt-tiny, for the CPU tests:
the real harness, readers and windows over files ADDED beside the real
ones — which is also how a later PR adds a cell."""
from __future__ import annotations

import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
PERF = os.path.join(ROOT, "benchmarks", "perf")
for _p in (ROOT, PERF):
    if _p not in sys.path:
        sys.path.insert(0, _p)

TINY_CONFIG = {
    "family": "gpt", "source": "tests only", "activation_function":
    "gelu_new", "attn_pdrop": 0.0, "embd_pdrop": 0.0, "resid_pdrop": 0.0,
    "layer_norm_epsilon": 1e-05, "n_embd": 64, "n_head": 4, "n_inner": 256,
    "n_layer": 2, "n_positions": 128, "vocab_size": 128, "reduced": []}
OPT = {"lr": 6e-4, "beta1": 0.9, "beta2": 0.95, "epsilon": 1e-8,
       "weight_decay": 0.1}
TINY_TRAFFIC = {
    "tiny_train": {
        "kind": "train_window", "batch": 4, "seq_len": 64, "rows": 64,
        "amp_level": "O1", "amp_dtype": "bfloat16", "optimizer": OPT,
        "prefetch_to_device": 2, "in_flight": 2, "warm_steps": 1,
        "trace_after_s": 0.0, "trace_seconds": 0.2,
        "reference_rows_per_block": 2,
        "limits": {"grad_norm_gap": 0.1, "change_norm_gap": 0.1}},
    "tiny_chat": {
        "kind": "serve_open_loop", "max_batch": 4, "max_seq_len": 64,
        "kv_dtype": "bfloat16", "weights_dtype": "bfloat16",
        "prefix_cache_bytes": 0, "check_requests": 4, "trace_after_s": 0.0,
        "trace_seconds": 0.2, "prefill_buckets": [16, 32],
        "rate_per_s": 4.0, "lead_in_s": 0.5,
        "prompt_len": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                       "min": 4, "max": 32},
        "output_len": {"dist": "uniform", "min": 3, "max": 6},
        "limits": {"served_gap_max": 0.5}},
    "tiny_backlog": {
        "kind": "serve_closed_loop", "max_batch": 4, "max_seq_len": 64,
        "kv_dtype": "bfloat16", "weights_dtype": "bfloat16",
        "prefix_cache_bytes": 0, "check_requests": 4, "trace_after_s": 0.0,
        "trace_seconds": 0.2, "prefill_buckets": [16, 32], "clients": 6,
        "pool": 8, "lead_in_completions": 4,
        "prompt_len": {"dist": "uniform", "min": 8, "max": 30},
        "output_len": {"dist": "uniform", "min": 3, "max": 6},
        "limits": {"served_gap_max": 0.5}},
}


#: what a PR that brings the trainer's cells adds to BENCHMARK.json: the
#: end-to-end metric and per-layer metrics whose files are already there
TRAIN_E2E = {"name": "train_tokens_per_s_per_chip", "unit": "tokens/s",
             "better": "higher", "bound": 0.01, "source": "host_clock",
             "workloads": []}
TRAIN_LAYERS = [
    {"name": "step.mfu.train", "unit": "%", "better": "higher",
     "source": "host_clock", "layer": "compiled step",
     "moves": "train_tokens_per_s_per_chip", "workloads": []},
    {"name": "feed.stall_ms_per_step", "unit": "ms", "better": "lower",
     "source": "program_counter", "layer": "feed",
     "moves": "train_tokens_per_s_per_chip", "workloads": []}]


def make_root(tmp, cells=None, changes=None):
    """Copy BENCHMARK.json and benchmarks/perf to `tmp`, then ADD the tiny
    configuration, the tiny mixes (with `changes` to their keys) and one
    cell for each — no existing file is edited. Returns the root."""
    shutil.copytree(PERF, os.path.join(tmp, "benchmarks", "perf"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    perf = os.path.join(tmp, "benchmarks", "perf")
    with open(os.path.join(perf, "configs", "gpt-tiny.json"), "w") as f:
        json.dump(TINY_CONFIG, f)
    bench["configs"].append({
        "name": "gpt-tiny", "source": "tests only",
        "file": "benchmarks/perf/configs/gpt-tiny.json", "reduced": [],
        "why": "CPU tests"})
    if not any(m["name"] == TRAIN_E2E["name"] for m in bench["end_to_end"]):
        bench["end_to_end"].append(json.loads(json.dumps(TRAIN_E2E)))
        bench["per_layer"].extend(json.loads(json.dumps(TRAIN_LAYERS)))
    e2e = {"train_window": ["train_tokens_per_s_per_chip"],
           "serve_open_loop": ["ttft_p95_ms", "tpot_p95_ms"],
           "serve_closed_loop": ["serve_tokens_per_s"]}
    for mix, traffic in TINY_TRAFFIC.items():
        if cells and mix not in cells:
            continue
        with open(os.path.join(perf, "traffic", mix + ".json"), "w") as f:
            json.dump(dict(traffic, **(changes or {})), f)
        name = "tiny." + mix
        bench["workloads"].append({"name": name, "config": "gpt-tiny",
                                   "traffic": mix, "chips": 1,
                                   "why": "CPU tests"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if "workloads" in m and (m["name"] in e2e[traffic["kind"]] or
                                     m.get("moves") in e2e[traffic["kind"]]):
                m["workloads"].append(name)
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp


class FakeTPU:
    """A CPU device under a TPU's name, so that a test can skip the
    harness's look for a chip and drive the rest of a run."""

    platform = "tpu"
    device_kind = "TPU v5 lite"

    def __init__(self, dev):
        self._dev = dev

    def memory_stats(self):
        return self._dev.memory_stats() or {"peak_bytes_in_use": 1}
