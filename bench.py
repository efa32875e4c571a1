"""Benchmark driver entry. Prints ONE JSON line; needs a TPU.

Headline: GPT-2-small compiled train step, tokens/sec/chip with MFU
(benchmarks/train_bench.py holds the full suite incl. ResNet-50 static).
LeNet Model.fit is kept as an `extra` field. vs_baseline stays 0.0 while
the reference publishes no in-repo numbers (BASELINE.md: "published: {}").

One process: whoever touches jax holds the chip, so the bench body runs
here and not in a child. Without a TPU, or when any part of the bench
raises, the exit code is non-zero and no metric line is printed. The
serving benchmark is BENCHMARK.json's (`benchmarks/perf/run.py`)."""
from __future__ import annotations

import json
import os
import sys
import tempfile
import time

os.environ.setdefault("PADDLE_TPU_SYNTH_SAMPLES", "8192")

import numpy as np

_BENCH_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "benchmarks")
if _BENCH_DIR not in sys.path:
    sys.path.insert(0, _BENCH_DIR)


def bench_lenet_fit():
    import paddle_tpu as paddle
    from paddle_tpu.vision.models import LeNet
    from paddle_tpu.vision.datasets import MNIST

    paddle.seed(0)
    batch_size = 256
    model = paddle.Model(LeNet())
    opt = paddle.optimizer.Adam(parameters=model.parameters(),
                                learning_rate=1e-3)
    model.prepare(opt, paddle.nn.CrossEntropyLoss())
    train = MNIST(mode="train")

    x = np.stack([train[i][0] for i in range(batch_size)]).astype(np.float32)
    y = np.asarray([train[i][1] for i in range(batch_size)], np.int64)

    # warmup: compile the fused train step
    model.train_batch([x], [y])
    model.train_batch([x], [y])

    n_steps = 50
    t0 = time.perf_counter()
    for _ in range(n_steps):
        model.train_batch([x], [y])
    # train_batch returns host loss (blocks), so timing is accurate
    dt = time.perf_counter() - t0
    ips = n_steps * batch_size / dt
    return ips


_METRIC = "gpt2_small_train_tokens_per_sec_per_chip"


def _emit_bench_event(event, **fields):
    """Journal a bench-level event (e.g. bench_gate_failed) where the
    tooling can find it: journal-bench.jsonl under
    PADDLE_TPU_TELEMETRY_DIR, else <tempdir>/pt_bench_telemetry."""
    from paddle_tpu.observability.journal import RunJournal

    d = (os.environ.get("PADDLE_TPU_TELEMETRY_DIR")
         or os.path.join(tempfile.gettempdir(), "pt_bench_telemetry"))
    j = RunJournal(d, filename="journal-bench.jsonl")
    j.emit(event, **fields)
    j.close()


# Per-config compile-time / retrace budgets (ROADMAP item 5: compile time
# as a measured contract). Ceilings are deliberately generous — they catch
# pathological regressions (a recompile per step, a compile-time blowup),
# not run-to-run noise. `retraces` counts executable-cache misses across
# the whole bench (warmup included), so a cold run legitimately spends 1;
# a warm persistent-cache run spends 0.
BENCH_BUDGETS = {
    "gpt2_small_train": {"compile_s": 120.0, "retraces": 2},
    "gpt2_long8k_train": {"compile_s": 240.0, "retraces": 2},
    "ernie_base_amp_o2_train": {"compile_s": 120.0, "retraces": 2},
    "resnet50_static_train": {"compile_s": 240.0, "retraces": 4},
}


def _budget_gates(row):
    """compile_s / retraces vs the row's config budget. Returns {} when the
    config has no budget or the row lacks the field."""
    budget = BENCH_BUDGETS.get(str(row.get("config") or ""), {})
    gates = {}
    if "compile_s" in budget and isinstance(row.get("compile_s"),
                                            (int, float)):
        gates["compile_budget_%.0fs" % budget["compile_s"]] = \
            row["compile_s"] <= budget["compile_s"]
    if "retraces" in budget and isinstance(row.get("retraces"),
                                           (int, float)):
        gates["retrace_budget_%d" % budget["retraces"]] = \
            row["retraces"] <= budget["retraces"]
    if not all(gates.values()):
        _emit_bench_event(
            "bench_gate_failed", config=row.get("config"),
            gates=gates, compile_s=row.get("compile_s"),
            retraces=row.get("retraces"),
            compile_cache=row.get("compile_cache"))
    return gates


def _eval_gates(res):
    """Headline acceptance gates: the flash path must actually be on
    (`attn_paths.flash > 0`, `attn_paths.xla_sdpa == 0`) and GPT-2 MFU
    must clear 0.35, plus the compile/retrace budget. A miss is recorded
    in the result and emits a `bench_gate_failed` journal event."""
    ap = res.get("attn_paths") or {}
    flash = ap.get("flash", 0) + ap.get("flash_dropout", 0)
    gates = {
        "flash_used": flash > 0,
        "no_xla_sdpa": ap.get("xla_sdpa", 0) == 0,
        "mfu_ge_0.35": isinstance(res.get("mfu"), (int, float))
        and res["mfu"] >= 0.35,
    }
    gates.update(_budget_gates(res))
    gates["pass"] = all(gates.values())
    if not gates["pass"]:
        _emit_bench_event(
            "bench_gate_failed",
            gates={k: v for k, v in gates.items() if k != "pass"},
            mfu=res.get("mfu"), attn_paths=ap or None)
    return gates


def main():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            "bench.py: needs a TPU, but jax.devices()[0].platform is %r — "
            "not run (tests/test_chip_smoke.py drives the same path at "
            "gpt_tiny size on the CPU)" % dev.platform)
    import train_bench

    res = train_bench.bench_gpt2()
    out = {
        "metric": _METRIC,
        "value": res["throughput"],
        "unit": "tokens/sec/chip",
        "vs_baseline": 0.0,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
    }
    out.update({k: res.get(k) for k in (
        "config", "mfu", "step_ms", "step_ms_wall", "compile_s", "retraces",
        "feed_stall_ms", "compile_cache", "span_breakdown", "hbm_peak",
        "batch", "seq_len", "attn_paths")})
    out["gates"] = _eval_gates(out)
    out["extra"] = {
        "lenet_fit_images_per_sec": round(float(bench_lenet_fit()), 1)}
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
