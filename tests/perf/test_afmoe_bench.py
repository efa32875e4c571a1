"""Tier-1 tests of the `afmoe` family in the benchmark (CPU, `afmoe-tiny`):
the real `run_cell` over files ADDED to a temporary copy (`tiny_afmoe.py`),
planted faults, the int8 control against the real cell's limit, the work
counts, and every new metric file through the reader its `source` names."""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny                                   # noqa: E402  (puts paths in)
import tiny_afmoe                             # noqa: E402
import reference_afmoe as ref                 # noqa: E402
import run                                    # noqa: E402
import work_afmoe                             # noqa: E402

ROOT, PERF = tiny.ROOT, tiny.PERF
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELL = tiny_afmoe.CELL
REAL = json.load(open(os.path.join(PERF, "configs", "trinity-mini.json")))
MIX = json.load(open(os.path.join(PERF, "traffic", "longmix_backlog.json")))


class Kept(run.Run):
    """The harness's own Run, kept for the test to read metrics from."""

    last = None

    def close_window(self):
        super().close_window()
        Kept.last = self


def _run(tmp_path, run_cls=Kept, trace=0, changes=None):
    root = tiny_afmoe.make_root(str(tmp_path), changes=changes)
    return run.run_cell("tiny.longmix", 2**31 + 5, 1.0, trace, root=root,
                        devices=[tiny.FakeTPU(jax.devices()[0])],
                        run_cls=run_cls)


def _spec(name):
    return json.load(open(os.path.join(PERF, "metrics", name + ".json")))


NEW_METRICS = [m["name"] for m in BENCH["per_layer"]
               if m.get("workloads") == [CELL]]


def test_the_sound_program_is_correct_and_every_counted_metric_is_read(
        tmp_path):
    res = _run(tmp_path)
    assert list(res)[-1] == "compared"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert res["compared"]["served_gap_max"] == [0.0, 0.0002]
    ctx = Kept.last
    assert ctx.harness["compiles_in_window"] == 0
    read = {n: ctx.read_metric(_spec(n)) for n in NEW_METRICS
            if _spec(n)["source"] != "trace"}
    assert set(read) == {
        "step.mfu.longmix", "decode.span_mean_ms.longmix",
        "prefill.span_mean_ms.longmix", "admission.occupancy_mean.longmix",
        "host.gap_decode_mean_ms.longmix", "host.gap_prefill_mean_ms.longmix",
        "host.decode_dispatch_mean_s.longmix",
        "moe.experts_touched_mean.longmix", "moe.load_max_over_mean.longmix"}
    assert all(v is not None and v > 0 for v in read.values()), read
    assert 1 <= read["moe.experts_touched_mean.longmix"] <= 8
    assert read["moe.load_max_over_mean.longmix"] >= 1.0
    # a trace reader with no trace returns nothing and does not raise
    for n in NEW_METRICS:
        if _spec(n)["source"] == "trace":
            assert ctx.read_metric(_spec(n)) is None
    for rel in ("benchmarks/perf/run.py", "benchmarks/perf/serve_window.py",
                "benchmarks/perf/traffic/longmix_backlog.json"):
        assert open(os.path.join(ROOT, rel)).read() == \
            open(os.path.join(str(tmp_path), rel)).read()


# -- planted faults: each computes something else in the program's place ----


def _int8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return (jnp.clip(jnp.round(x / s), -127.0, 127.0) * s).astype(x.dtype)


def plant_int8_experts(monkeypatch):
    from paddle_tpu.incubate import moe as moe_ops
    sound = moe_ops.grouped_experts

    def int8(x, chosen, weights, e_gate, e_up, e_down, first=0):
        return sound(_int8(x), chosen, weights, _int8(e_gate), _int8(e_up),
                     _int8(e_down), first=first)
    monkeypatch.setattr(moe_ops, "grouped_experts", int8)


def plant_int8_router(monkeypatch):
    from paddle_tpu.incubate import moe as moe_ops
    sound = moe_ops.sigmoid_topk_route

    def int8(x, router_w, *rest):
        return sound(_int8(x), _int8(router_w), *rest)
    monkeypatch.setattr(moe_ops, "sigmoid_topk_route", int8)


def plant_no_shared(monkeypatch):
    from paddle_tpu.models import decoder
    sound = decoder.moe_layer

    def no_shared(mc, p, x):
        return sound(mc, dict(p, s_down=jnp.zeros_like(p["s_down"])), x)
    monkeypatch.setattr(decoder, "moe_layer", no_shared)


def plant_full_windowed(monkeypatch):
    """The full-attention layer sees the sliding window's keys alone, in
    prefill and in decoding."""
    from paddle_tpu.models import decoder
    band, paged = decoder.band_attention, decoder.paged_attention
    W = tiny_afmoe.TINY_CONFIG["sliding_window"]

    def band_w(q, k, v, window):
        return band(q, k, v, window or W)

    def paged_w(q, k, v, view):
        if view.kind == "window":
            return paged(q, k, v, view)
        kv, layer = view.kv, view.layer
        lens = kv.lens
        slots = jnp.arange(lens.shape[0])
        kv.k = kv.k.at[layer, slots, :, lens].set(k[:, :, 0])
        kv.v = kv.v.at[layer, slots, :, lens].set(v[:, :, 0])
        pos = jnp.arange(kv.k.shape[3])[None, :]
        ok = (pos <= lens[:, None]) & (pos > lens[:, None] - W)
        s = jnp.einsum("bkgd,bksd->bkgs", q, kv.k[layer]) \
            / np.sqrt(q.shape[-1])
        p = jax.nn.softmax(jnp.where(ok[:, None, None, :], s, -1e30), -1)
        return jnp.einsum("bkgs,bksd->bkgd", p, kv.v[layer])

    monkeypatch.setattr(decoder, "band_attention", band_w)
    monkeypatch.setattr(decoder, "paged_attention", paged_w)


@pytest.mark.parametrize("plant", [plant_int8_experts, plant_int8_router,
                                   plant_no_shared, plant_full_windowed])
def test_a_planted_fault_comes_out_not_correct(tmp_path, monkeypatch, plant):
    """48 requests checked, not the mix's 4: at this size int8 experts flip
    a served token in two of the pool's eight requests only (the mean gap
    of all served tokens then reads 0.0008), and a sample of 4 misses both
    in two runs of five. The reference pads to 64 positions here so that
    48 forward passes cost what 4 do at its own 4 096."""
    plant(monkeypatch)
    monkeypatch.setattr(ref, "PAD_TO", 64)
    bad = _run(tmp_path, changes={"check_requests": 48})
    assert bad["failed"] == 0 and bad["attempted"] > 0
    assert bad["correct"] is False
    gap, limit = bad["compared"]["served_gap_max"]
    assert gap > limit


# -- the controls, against the REAL cell's limit ----------------------------

SMALL = dict(tiny_afmoe.TINY_CONFIG, hidden_size=128, vocab_size=1024,
             num_experts=16, num_experts_per_tok=4, sliding_window=32)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_faults_fail_the_cells_own_limit_and_int8_reads_over_bf16(seed):
    """At a size a test run can hold (the readings at the cell's own size
    are the chip's, in PERF.md and in the traffic file's `readings`: the
    mean gap of all served tokens 0.009-0.016 for the program, 0.034-0.037
    with int8 in the experts or the router alone, 0.42-0.45 with int8
    throughout; the widest token 0.73-1.22 against a stray token's
    2.5-7.0). The reference without its shared expert, or with the full
    layer windowed, puts tokens first that lie below the float32 best by
    more than the REAL cell's limit; int8 throughout reads far over the
    tiny mix's limit and over what rounding to bfloat16 alone reads; the
    reference's own greedy tokens read 0, and with the last of them
    replaced the run reads that one token's gap over `TOKENS_A_MEAN`."""
    limit = MIX["limits"]["served_gap_max"]
    assert 0.0163 < limit < 0.0339             # between the chip's readings
    # the widest token: above a bfloat16 flip (1.3), where a stray reads
    assert 1.3 * 1.5 < limit * ref.TOKENS_A_MEAN <= 2.5
    w = ref.make_weights(SMALL, seed, "bfloat16")
    seqs = [ref.tokens(seed + 10 * i, 1, 160, SMALL["vocab_size"])[0]
            for i in range(2)]
    read = {k: max(ref.served_gaps(SMALL, w, seqs, [100] * 2, **kw))
            for k, kw in (("int8", {"quant": "int8"}),
                          ("bf16", {"quant": "bf16"}),
                          ("no_shared", {"fault": "no_shared"}),
                          ("full_windowed", {"fault": "full_windowed"}))}
    assert read["no_shared"] > limit and read["full_windowed"] > limit, read
    assert read["int8"] > 2 * read["bf16"], read
    assert read["int8"] > 20 * tiny_afmoe.TINY_MIX["limits"][
        "served_gap_max"], read
    greedy = [np.concatenate([s[:100], np.asarray(jnp.argmax(
        ref.logits(SMALL, w, s)[99:159], -1))]) for s in seqs]
    first = ref.served_gaps(SMALL, w, [g[:101] for g in greedy], [100] * 2)
    assert max(first) == 0.0
    if seed == 1:      # one seed: eighty forward passes
        # 40 tokens the reference itself decodes, greedy, from each prompt
        own = []
        for q in seqs:
            ids = np.zeros((160,), np.int64)
            ids[:100] = q[:100]
            for t in range(100, 140):
                ids[t] = int(jnp.argmax(ref.logits(SMALL, w, ids)[t - 1]))
            own.append(ids[:140])
        assert max(ref.served_gaps(SMALL, w, own, [100] * 2)) == 0.0
        stray = ref.served_gaps(SMALL, w, own, [100] * 2, fault="one_token")
        tokens = ref.served_gaps(SMALL, w, own, [100] * 2, fault="one_token",
                                 per_token=True)
        assert [int((t > 0).sum()) for t in tokens] == [1, 1]
        last = [float(t[-1]) for t in tokens]
        assert stray == pytest.approx([sum(last) / 80,
                                       max(last) / ref.TOKENS_A_MEAN])
    assert ref.served_numbers([[0.0, 0.3], [0.0]]) == pytest.approx(
        [0.1, 0.003])
    every = ref.served_gaps(SMALL, w, seqs, [100] * 2, quant="int8",
                            per_token=True)
    assert [len(e) for e in every] == [60, 60]
    assert max(float(e.max()) for e in every) >= read["int8"]
    with pytest.raises(ValueError):
        ref.hidden(SMALL, w, seqs[0], quant="int4")


# -- configuration, traffic and work counts ---------------------------------


def test_the_configuration_is_the_catalogs_with_only_depth_cut():
    assert REAL["source"].endswith("arcee-ai/Trinity-Mini/blob/main/"
                                   "config.json")
    assert REAL["reduced"] == ["num_hidden_layers", "num_dense_layers",
                               "layer_types"]
    widths = {"hidden_size": 2048, "num_attention_heads": 32,
              "num_key_value_heads": 4, "head_dim": 128,
              "sliding_window": 2048, "num_experts": 128,
              "moe_intermediate_size": 1024, "num_experts_per_tok": 8,
              "num_shared_experts": 1, "intermediate_size": 6144,
              "vocab_size": 200192, "max_position_embeddings": 131072}
    assert {k: REAL[k] for k in widths} == widths
    assert REAL["layer_types"] == ["sliding_attention"] * 4 \
        + ["full_attention"]
    assert len(REAL["assumed"]) >= 7 and "eight stages" in REAL["deployment"]
    shapes = ref.leaf_shapes(REAL)
    nbytes = sum(int(np.prod(s)) * (4 if n.endswith("expert_bias") else 2)
                 for n, s in shapes.items())
    assert round(nbytes / 1e9, 2) == 8.48


def test_the_traffic_is_the_issues():
    assert MIX["kind"] == "serve_closed_loop"
    assert (MIX["max_batch"], MIX["max_seq_len"], MIX["clients"],
            MIX["lead_in_completions"]) == (48, 16384, 64, 48)
    assert MIX["pool"] == 96
    assert MIX["prefill_buckets"] == [1024, 2048, 4096, 8192, 14336]
    assert MIX["prompt_len"] == {"dist": "lognormal", "median": 3072,
                                 "sigma": 0.8, "min": 256, "max": 14336}
    assert MIX["output_len"] == {"dist": "uniform", "min": 64, "max": 256}
    assert MIX["prefix_cache_bytes"] == 0 and MIX["check_requests"] == 12
    import serve_window
    reqs, _ = serve_window.make_requests(MIX, 3, MIX["pool"], 1000)
    lens = np.array([len(p) for p, _ in reqs])
    assert 0.25 <= (lens <= 2048).mean() <= 0.35     # three in ten
    assert 3900 <= lens.mean() <= 4500
    assert all(p + o <= MIX["max_seq_len"] for (p, o) in
               ((len(p), o) for p, o in reqs))


def test_work_counts_of_the_published_widths():
    # 402 M multiplied parameters a token without the head (ISSUE 28):
    # 8 routed + 1 shared expert a token, not the 128 held
    per_token = work_afmoe.matmul_params_per_token(REAL)
    assert round((per_token - 200192 * 2048) / 1e6) == 402
    assert work_afmoe.attention_params(REAL) == 27262976
    # a 4 096-token prefill: 3.3 TFLOP of products without the head's
    flops = work_afmoe.forward_flops(REAL, 4096, 4096 * 4097 / 2.0)
    products = 2.0 * (per_token - 200192 * 2048) * 4096
    assert round(products / 1e12, 1) == 3.3
    # attention over min(ctx, 2048) keys on the four window layers
    full = 4.0 * 4096 * (4096 * 4097 / 2.0)
    band = 4.0 * 4096 * (2048 * 2049 / 2.0 + 2048 * 2048)
    assert flops == pytest.approx(
        2.0 * per_token * 4096 + full + 4 * band, rel=1e-9)
    assert work_afmoe._window_keys(10, 55.0, 4) == 1 + 2 + 3 + 4 * 7
    assert work_afmoe._window_keys(3, 3 * 11.0, 4) == 12      # 10, 11, 12
    assert work_afmoe._window_keys(3, 6.0, 4) == 6.0          # 1, 2, 3
    counts = {"live_rows_mean": 48 * 4400.0, "prefill_bucket_mean": 6000.0,
              "prefill_bucket_mean_sq": 6000.0 ** 2 * 1.3}
    f, b = work_afmoe.moe_grouped_decode(REAL, MIX, counts)
    assert f == 6.0 * 2048 * 1024 * 384
    assert 0.95 * 3 * 128 * 2048 * 1024 * 2 < b < 3 * 128 * 2048 * 1024 * 2
    f, b = work_afmoe.paged_gqa_decode(REAL, MIX, counts)
    assert b == 2048.0 * (48 * 4400 + 4 * 48 * 2048)
    f, b = work_afmoe.prefill_band_flash(REAL, MIX, counts)
    square = 4.0 * 4096 * 5 * counts["prefill_bucket_mean_sq"] / 2
    assert 0.3 * square < f < 0.6 * square


# -- the trace readers, on hand-made planes with the chip's own op names ----

#: (HLO text of one device event as the profiler names it, seconds a call,
#: calls) — names and times as the v5e gave them (my chip run, PR 28)
EVENTS = [
    ("%ragged-dot-none.7 = bf16[384,1024]{1,0:T(8,128)(2,1)} custom-call("
     "%a, %b), custom_call_target=\"tpu_custom_call\"", 835e-6, 8),
    ("%ragged-dot-none.9 = bf16[384,2048]{1,0:T(8,128)(2,1)} custom-call("
     "%a, %b), custom_call_target=\"tpu_custom_call\"", 836e-6, 4),
    ("%ragged-dot-none.21 = bf16[65536,1024]{1,0:T(8,128)(2,1)} "
     "custom-call(%a, %b), custom_call_target=\"tpu_custom_call\"",
     3596e-6, 2),
    ("%ragged-dot-metadata.3 = (s32[129]{0}, s32[130]{0}) custom-call(%g), "
     "custom_call_target=\"tpu_custom_call\"", 8e-6, 4),
    ("%sort.32 = (s32[65536]{0:T(1024)}, u32[65536]{0:T(1024)S(1)}) "
     "sort(%k, %i), dimensions={0}, is_stable=true", 81e-6, 2),
    ("%multiply_multiply_fusion.4 = bf16[65536,1024]{1,0:T(8,128)(2,1)} "
     "fusion(%g, %u), kind=kLoop", 600e-6, 1),
    ("%paged_gqa_decode.3 = (bf16[48,4,8,128]{3,2,1,0:T(8,128)(2,1)}, "
     "bf16[1,48,4,16384,128]{4,3,2,1,0}) custom-call(%q), "
     "custom_call_target=\"tpu_custom_call\"", 601e-6, 5),
    ("%prefill_band_flash.2 = bf16[4,8,8192,128]{3,2,1,0:T(8,128)(2,1)} "
     "custom-call(%q, %k, %v), custom_call_target=\"tpu_custom_call\"",
     3242e-6, 5),
    ("%fusion.5 = bf16[48,1]{1,0} fusion(%h), kind=kInput", 1171e-6, 1),
]


def _planes():
    ops, t = [], 0.0
    for name, secs, calls in EVENTS:
        for _ in range(calls):
            ops.append((name, t * 1e9, secs * 1e9))
            t += secs + 20e-6                 # a gap between two ops
    return [("/device:TPU:0", [("XLA Ops", ops),
                               ("XLA Modules", [("jit__decode_fn(1)", 0.0,
                                                 t * 1e9)])]),
            ("/host:CPU", [("python", [])])], t


def test_every_trace_metric_of_the_cell_reads_the_chips_op_names():
    import trace_reduce
    import work
    planes, total = _planes()
    ctx = object.__new__(run.Run)
    ctx.red = trace_reduce.reduce(planes)
    ctx.cfg, ctx.traffic = REAL, MIX
    ctx.peaks = work.peaks("TPU v5 lite")
    ctx.counts = {"live_rows_mean": 214258.0, "prefill_bucket_mean": 8192.0,
                  "prefill_bucket_mean_sq": 8192.0 ** 2}
    read = {n: ctx.read_metric(_spec(n)) for n in NEW_METRICS
            if _spec(n)["source"] == "trace"}
    assert set(read) == {
        "device.idle_share.longmix", "moe.time_share.longmix",
        "attn.time_share.longmix", "moe_grouped_roofline",
        "paged_gqa_decode_roofline", "prefill_band_flash_roofline"}
    assert all(v is not None for v in read.values()), read
    busy = sum(s * c for _, s, c in EVENTS)
    moe = sum(s * c for n, s, c in EVENTS[:6])
    attn = sum(s * c for n, s, c in EVENTS[6:8])
    assert read["moe.time_share.longmix"] == pytest.approx(
        100 * moe / busy, rel=1e-6)
    assert read["attn.time_share.longmix"] == pytest.approx(
        100 * attn / busy, rel=1e-6)
    assert read["device.idle_share.longmix"] == pytest.approx(
        100 * (1 - busy / (total - 20e-6)), rel=1e-6)   # first op to last
    # three grouped products of 384 assignments over 128 experts: a step's
    # bytes at 819 GB/s over the three calls' time
    f, b = work_afmoe.moe_grouped_decode(REAL, MIX, ctx.counts)
    assert read["moe_grouped_roofline"] == pytest.approx(
        100 * (b / 819e9) / ((8 * 835e-6 + 4 * 836e-6) / 4), rel=1e-6)
    for n in ("moe_grouped_roofline", "paged_gqa_decode_roofline",
              "prefill_band_flash_roofline"):
        assert 0 < read[n] < 100, (n, read[n])
    # the GPT cells' trace leaves every one of them silent, none raises
    fixture = os.path.join(os.path.dirname(__file__), "fixture.xplane.pb")
    if os.path.exists(fixture):
        ctx.red = trace_reduce.reduce(trace_reduce.load(fixture))
        for n in read:
            if n != "device.idle_share.longmix":
                assert ctx.read_metric(_spec(n)) is None, n
