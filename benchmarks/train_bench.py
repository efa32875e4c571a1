"""Training benchmarks with MFU: BASELINE.md configs 2 (ResNet-50 static)
and 5-family (GPT-2 small train step). Needs a TPU: every config runs at
its published size, and a CPU run of these sizes measures nothing anyone
deploys (tests/test_chip_smoke.py drives the GPT train path at gpt_tiny
size on the CPU).

Run:  python benchmarks/train_bench.py [resnet50|gpt2|all]
Prints one JSON line per config:
  {"config": ..., "throughput": ..., "unit": ..., "step_ms": ..., "mfu": ...}
and exits non-zero when any config failed.

MFU = analytic_train_flops_per_step / (step_time * chip peak FLOPs/s).
The peak comes from paddle_tpu/observability/device_peaks.py (bf16);
override with PADDLE_TPU_PEAK_FLOPS.
Analytic FLOPs follow the standard conventions (6·N·tokens + attention for
transformers; 3× forward GFLOPs for convnets) so numbers are comparable to
published MFU figures."""
from __future__ import annotations

import json
import os
import sys
import time
import traceback

import numpy as np

# standalone `python benchmarks/train_bench.py` runs put benchmarks/ (not the
# repo root) on sys.path[0]
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def peak_flops():
    """Peak dense bf16 FLOP/s of the chip, None off-TPU (a CPU has no
    published peak to hold a step against). A TPU that is not in the table
    raises: an MFU against a guessed peak is worse than none."""
    import jax

    from paddle_tpu.observability import device_peaks
    env = os.environ.get("PADDLE_TPU_PEAK_FLOPS")
    if env:
        return float(env)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return None
    row = device_peaks.lookup(dev.device_kind)
    if row is None:
        raise LookupError(
            "no published peak for device_kind %r in "
            "paddle_tpu/observability/device_peaks.py — add its row (with "
            "its source) or set PADDLE_TPU_PEAK_FLOPS" % dev.device_kind)
    return row[0] * 1e12


def _mfu(flops_per_step, step_s):
    pk = peak_flops()
    if pk is None:
        return None
    return round(flops_per_step / step_s / pk, 4)


def _gpt_train_bench(net, B, T, steps, warmup, config, next_batch):
    """Shared GPT train-bench harness: AdamW + AMP-O2 compiled step,
    warmup, attention-path counters (which attention impl the compiled
    step actually traced), timed loop, and
    the standard transformer train-FLOPs MFU report (6·N per token fwd+bwd
    + 12·L·T·d attention per token for QKᵀ/PV both directions).

    next_batch() -> (inputs, labels) lists for the compiled step."""
    import paddle_tpu as paddle
    from paddle_tpu.jit.engine import make_train_step
    from paddle_tpu.models import GPTPretrainingCriterion

    paddle.seed(0)
    crit = GPTPretrainingCriterion()
    opt = paddle.optimizer.AdamW(parameters=net.parameters(),
                                 learning_rate=1e-4, weight_decay=0.01)
    net, opt = paddle.amp.decorate(net, opt, level="O2", dtype="bfloat16")
    step = make_train_step(net, lambda o, l: crit(o, l), opt)

    # compile vs steady-state breakdown comes from the metrics registry
    # (observability/tracing.py): compile wall-time from the engine's
    # compile counter delta across warmup, steady-state step time from the
    # entry-to-entry interval histogram delta across the timed loop — the
    # number that stays honest under async dispatch
    from paddle_tpu.observability import tracing
    comp = tracing.COMPILE_SECONDS.labels("jit_train")
    ihist = tracing.STEP_INTERVAL.labels("jit_train")
    retr = tracing.RETRACES.labels("jit_train")
    comp0, retr0 = comp.value, retr.value
    # persistent-cache deltas: a run over a warm cache directory must
    # show hits>0 / retraces==0 (the PR-9 warm-cache contract)
    from paddle_tpu.jit import compile_cache
    cc0 = compile_cache.totals()

    # attn paths from the metrics registry (pt_attn_path_total deltas) —
    # the same series ptdoctor summary reads, so a BENCH row and a
    # post-mortem can never disagree about which attention impl traced
    # span breakdown: pt_span_ms deltas across the whole bench, so the
    # BENCH row carries the same "where did the time go" decomposition
    # ptdoctor profile renders (compile/dispatch/feed_wait/... ms + n)
    from paddle_tpu.observability import spans as obs_spans

    def _span_totals():
        out = {}
        for lbls, child in obs_spans.SPAN_MS._series():
            out[lbls.get("name", "")] = (child.sum, child.count)
        return out

    sp0 = _span_totals()

    from paddle_tpu.ops.pallas_kernels import attention_path_totals
    attn0 = attention_path_totals()
    for _ in range(warmup):
        loss, _ = step(*next_batch())
    float(loss.numpy())
    compile_s = comp.value - comp0
    attn_paths = {k: v - attn0.get(k, 0)
                  for k, v in attention_path_totals().items()}
    sum0, count0 = ihist.sum, ihist.count
    fs_sum0, fs_count0 = tracing.FEED_STALL.sum, tracing.FEED_STALL.count
    t0 = time.perf_counter()
    for _ in range(steps):
        loss, _ = step(*next_batch())
    float(loss.numpy())  # block
    dt_wall = (time.perf_counter() - t0) / steps
    d_count = ihist.count - count0
    dt = (ihist.sum - sum0) / d_count if d_count else dt_wall
    d_fs = tracing.FEED_STALL.count - fs_count0
    feed_stall_ms = (round((tracing.FEED_STALL.sum - fs_sum0) / d_fs, 3)
                     if d_fs else None)
    cc1 = compile_cache.totals()
    span_breakdown = {}
    for name, (s1, c1) in _span_totals().items():
        s0, c0 = sp0.get(name, (0.0, 0))
        if c1 > c0:
            span_breakdown[name] = {"ms": round(s1 - s0, 3), "n": c1 - c0}

    # gpt2_small()/gpt_tiny() return GPTForPretraining wrapping .gpt
    core = getattr(net, "gpt", net)
    n_params = sum(int(np.prod(p.shape)) for p in net.parameters())
    L = len(core.layers)
    dmodel = core.hidden_size
    tokens = B * T
    flops = 6 * n_params * tokens + 12 * L * dmodel * T * tokens
    from paddle_tpu.observability import metrics as obs_metrics
    obs_metrics.gauge("pt_tokens_per_sec",
                      "Bench throughput, tokens/sec/chip").set(tokens / dt)
    # HBM high-water mark for the trend table (ptdoctor bench hbm_peak
    # column): force one post-loop sample past the rate limiter, then
    # read the same gauge /statusz and the rollup report
    from paddle_tpu.observability import flight as obs_flight
    obs_flight.sample_hbm(force=True, phase="step")
    _g = obs_metrics.REGISTRY.get("pt_hbm_peak_bytes")
    hbm_peak = int(_g.value) if _g is not None and _g.value else None
    return {"config": config,
            "throughput": round(tokens / dt, 1),
            "unit": "tokens/sec/chip",
            "step_ms": round(dt * 1e3, 2),
            "step_ms_wall": round(dt_wall * 1e3, 2),
            "compile_s": round(compile_s, 3),
            "retraces": int(retr.value - retr0),
            "feed_stall_ms": feed_stall_ms,
            "compile_cache": {"hits": cc1[0] - cc0[0],
                              "misses": cc1[1] - cc0[1]},
            "span_breakdown": span_breakdown or None,
            "hbm_peak": hbm_peak,
            "batch": B, "seq_len": T, "params": n_params,
            "attn_paths": attn_paths,
            "mfu": _mfu(flops, dt)}


def bench_gpt2():
    """GPT-2 small dygraph compiled train step (AdamW), synthetic token
    stream fed through the DataLoader machinery (worker thread + batching +
    host->device transfer included in the measured step loop)."""
    from paddle_tpu.io import DataLoader, Dataset
    from paddle_tpu.models import gpt2_small

    # B=16 T=512, AMP O2 bf16: the BASELINE config-5 headline shape
    B, T, steps, warmup = 16, 512, 30, 3
    net = gpt2_small()
    core = getattr(net, "gpt", net)
    vocab = core.embeddings.word_embeddings.weight.shape[0]

    class TokenStream(Dataset):
        def __len__(self):
            return 100000

        def __getitem__(self, i):
            rs = np.random.RandomState(i)
            return rs.randint(0, vocab, (T + 1,)).astype(np.int64)

    # thread prefetch path: forking workers AFTER TPU backend init is
    # unsafe (libtpu threads); the mp loader has its own benchmark
    # (benchmarks/dataloader_bench.py). prefetch_to_device overlaps the
    # host->device copy with compute and makes per-batch feed starvation
    # measurable (feed_stall_ms rides next to step_ms in the bench row)
    loader = DataLoader(TokenStream(), batch_size=B, num_workers=0,
                        shuffle=False, prefetch_to_device=2)
    it = iter(loader)

    def next_batch():
        batch = next(it)
        ids = batch if not isinstance(batch, (list, tuple)) else batch[0]
        return [ids[:, :-1]], [ids[:, 1:]]

    try:
        return _gpt_train_bench(net, B, T, steps, warmup,
                                "gpt2_small_train", next_batch)
    finally:
        it.close()


def bench_gpt2_long():
    """Long-context GPT-2 train step: B=1, T=8192 (same tokens/step as the
    B=16/T=512 headline) on the Pallas flash kernel — the single-chip leg
    of the long-context story (ring/Ulysses cover the multi-chip leg,
    tests/test_sep_parallel.py)."""
    import paddle_tpu as paddle
    from paddle_tpu.models import gpt2_small

    B, T, steps, warmup = 1, 8192, 10, 2
    net = gpt2_small(max_position_embeddings=T + 1)
    core = getattr(net, "gpt", net)
    vocab = core.embeddings.word_embeddings.weight.shape[0]
    rs = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rs.randint(0, vocab, (B, T + 1)).astype(np.int64))
    args = ([ids[:, :-1]], [ids[:, 1:]])
    return _gpt_train_bench(net, B, T, steps, warmup, "gpt2_long8k_train",
                            lambda: args)


def bench_ernie():
    """ERNIE/BERT-base pretrain step, dygraph + AMP O2 (BASELINE config 3):
    MLM+NSP loss, bf16 autocast traced into the compiled step."""
    import paddle_tpu as paddle
    from paddle_tpu.jit.engine import make_train_step
    from paddle_tpu.models import BertPretrainingCriterion, bert_base

    B, T, steps, warmup = 32, 128, 20, 3
    net = bert_base()
    paddle.seed(0)
    crit = BertPretrainingCriterion()
    opt = paddle.optimizer.AdamW(parameters=net.parameters(),
                                 learning_rate=1e-4, weight_decay=0.01)
    step = make_train_step(net, lambda lg, nl, y1, y2: crit(lg, nl, y1, y2),
                           opt)
    core = getattr(net, "bert", net)
    vocab = core.embeddings.word_embeddings.weight.shape[0]
    rs = np.random.RandomState(0)
    ids = rs.randint(0, vocab, (B, T)).astype(np.int64)
    labels = ids.copy()
    labels[:, ::5] = -100
    nsp = rs.randint(0, 2, (B,)).astype(np.int64)
    args = ([paddle.to_tensor(ids)],
            [paddle.to_tensor(labels), paddle.to_tensor(nsp)])

    from paddle_tpu.ops.pallas_kernels import attention_path_totals
    import paddle_tpu.amp as amp
    attn0 = attention_path_totals()
    with amp.auto_cast(level="O2"):
        for _ in range(warmup):
            loss, _ = step(*args)
        float(loss.numpy())
        attn_paths = {k: v - attn0.get(k, 0)
                      for k, v in attention_path_totals().items()}
        t0 = time.perf_counter()
        for _ in range(steps):
            loss, _ = step(*args)
        float(loss.numpy())
    dt = (time.perf_counter() - t0) / steps

    n_params = sum(int(np.prod(p.shape)) for p in net.parameters())
    L = len(core.layers)
    dmodel = core.hidden_size
    tokens = B * T
    flops = 6 * n_params * tokens + 12 * L * dmodel * T * tokens
    return {"config": "ernie_base_amp_o2_train",
            "throughput": round(tokens / dt, 1),
            "unit": "tokens/sec/chip",
            "step_ms": round(dt * 1e3, 2),
            "batch": B, "seq_len": T, "params": n_params,
            "attn_paths": attn_paths,
            "mfu": _mfu(flops, dt)}


def bench_resnet50(conv_algo="auto"):
    """ResNet-50 static-graph Executor training (BASELINE config 2).

    conv_algo: 'auto', 'direct' or 'im2col' (FLAGS_conv_algo) — the
    comparison of conv lowerings ('auto' = NHWC-internal on TPU;
    benchmarks/conv_bench.py holds the per-layer sweep)."""
    import paddle_tpu as paddle
    from paddle_tpu import static
    from paddle_tpu.framework.flags import get_flags, set_flags
    from paddle_tpu.vision.models import resnet50

    prev_algo = get_flags(["FLAGS_conv_algo"])["FLAGS_conv_algo"]
    set_flags({"FLAGS_conv_algo": conv_algo})

    B, hw, steps, warmup = 64, 224, 20, 3

    paddle.enable_static()
    # fresh default programs: back-to-back runs in one process (the
    # direct-vs-im2col comparison) must not append to each other's graph
    static.reset_default_programs()
    try:
        paddle.seed(0)
        img = static.data("image", [-1, 3, hw, hw], "float32")
        label = static.data("label", [-1, 1], "int64")
        net = resnet50(num_classes=100)
        logits = net(img)
        loss = paddle.nn.functional.cross_entropy(logits, label)
        opt = paddle.optimizer.Momentum(learning_rate=0.01, momentum=0.9)
        opt.minimize(loss)
        # bf16 matmul/conv compute (MXU-native) via the static AMP
        # pass — f32 conv arithmetic is emulated and ~10x slower on TPU
        static.apply_pass(static.default_main_program(), "amp_bf16_pass")
        exe = static.Executor()
        exe.run(static.default_startup_program())

        rs = np.random.RandomState(0)
        x = rs.rand(B, 3, hw, hw).astype(np.float32)
        y = rs.randint(0, 100, (B, 1)).astype(np.int64)
        for _ in range(warmup):
            exe.run(feed={"image": x, "label": y}, fetch_list=[loss])
        # return_numpy=False: don't force a host sync on the loss every
        # step, so the next batch's host->device transfer overlaps the
        # current step's compute (the async-dispatch analogue of the
        # reference DataLoader's GPU prefetch)
        t0 = time.perf_counter()
        for _ in range(steps):
            (lv,) = exe.run(feed={"image": x, "label": y},
                            fetch_list=[loss], return_numpy=False)
        float(lv.numpy())  # block once at the end
        dt = (time.perf_counter() - t0) / steps
    finally:
        paddle.disable_static()
        set_flags({"FLAGS_conv_algo": prev_algo})
    # ResNet-50 fwd ≈ 4.1 GFLOPs / 224² image (scales with area);
    # train ≈ 3× fwd
    fwd = 4.1e9 * (hw * hw) / (224 * 224)
    flops = 3 * fwd * B
    return {"config": "resnet50_static_train",
            "conv_algo": conv_algo,
            "throughput": round(B / dt, 1),
            "unit": "images/sec/chip",
            "step_ms": round(dt * 1e3, 2),
            "batch": B, "image": hw,
            "mfu": _mfu(flops, dt)}


BENCH_CONFIGS = ("gpt2", "ernie", "resnet50", "gpt2_long")


def main():
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            "train_bench.py: needs a TPU, but jax.devices()[0].platform is "
            "%r — not run" % dev.platform)
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    device = {"platform": dev.platform, "device_kind": dev.device_kind,
              "device_count": len(jax.devices())}
    print(json.dumps(device), flush=True)
    failed = []
    for name in BENCH_CONFIGS:
        if which not in ("all", name):
            continue
        fn = globals()["bench_" + name]
        # resnet50 runs once per conv lowering: the comparison is the point
        runs = ([{"conv_algo": a} for a in ("auto", "direct", "im2col")]
                if name == "resnet50" else [{}])
        for kw in runs:
            try:
                print(json.dumps({**fn(**kw), **device}), flush=True)
            except Exception as e:
                traceback.print_exc()
                failed.append(name)
                print(json.dumps({"config": name, **kw, **device,
                                  "error": f"{type(e).__name__}: {e}"}),
                      flush=True)
    if failed:
        raise SystemExit("train_bench.py: failed configs: %s"
                         % ", ".join(failed))


if __name__ == "__main__":
    main()
