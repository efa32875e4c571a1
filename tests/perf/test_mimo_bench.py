"""Tier-1 tests of the `mimo_v2` family in the benchmark (CPU, `mimo-tiny`):
the real `run_cell` over files ADDED to a temporary copy (`tiny_mimo.py`),
planted faults, the int8 control, the configuration, traffic and work
counts, and every new metric file through the reader its `source` names."""
from __future__ import annotations

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny                                   # noqa: E402  (puts paths in)
import tiny_mimo                              # noqa: E402
import family_mimo_v2 as fam                  # noqa: E402
import reference_mimo_v2 as ref               # noqa: E402
import run                                    # noqa: E402
import work_mimo_v2 as work_m                 # noqa: E402

ROOT, PERF = tiny.ROOT, tiny.PERF
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELL = tiny_mimo.CELL
REAL = json.load(open(os.path.join(PERF, "configs", "mimo-v2.5.json")))
MIX = json.load(open(os.path.join(PERF, "traffic", "longgen_backlog.json")))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


class Kept(run.Run):
    """The harness's own Run, kept for the test to read metrics from."""

    last = None

    def close_window(self):
        super().close_window()
        Kept.last = self


def _run(tmp_path, run_cls=Kept, trace=0, changes=None):
    root = tiny_mimo.make_root(str(tmp_path), changes=changes)
    return run.run_cell("tiny.longgen", 2**31 + 5, 1.0, trace, root=root,
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
    assert res["compared"]["served_gap_max"] == [
        0.0, tiny_mimo.TINY_MIX["limits"]["served_gap_max"]]
    ctx = Kept.last
    assert ctx.harness["compiles_in_window"] == 0
    read = {n: ctx.read_metric(_spec(n)) for n in NEW_METRICS
            if _spec(n)["source"] != "trace"}
    assert set(read) == {
        "step.mfu.longgen", "decode.span_mean_ms.longgen",
        "prefill.span_mean_ms.longgen", "admission.occupancy_mean.longgen",
        "host.gap_decode_mean_ms.longgen", "moe.here_share.longgen"}
    assert all(v is not None and np.isfinite(v) and v > 0
               for v in read.values()), read
    # 4 of the router's 16 experts are held: a quarter under even routing
    assert 5.0 < read["moe.here_share.longgen"] < 60.0
    assert ctx.read_metric(_spec("host.ahead_share")) is not None
    # a trace reader with no trace returns nothing and does not raise
    for n in NEW_METRICS:
        if _spec(n)["source"] == "trace":
            assert ctx.read_metric(_spec(n)) is None
    for rel in ("benchmarks/perf/run.py", "benchmarks/perf/serve_window.py",
                "benchmarks/perf/traffic/longgen_backlog.json"):
        assert open(os.path.join(ROOT, rel)).read() == \
            open(os.path.join(str(tmp_path), rel)).read()


def test_a_program_without_the_block_stops_before_any_weight_is_made(
        monkeypatch):
    """What the parent commit does with this cell: its `DecoderConfig` has
    no sink and no sizes by kind, and the family says so and exits."""
    from paddle_tpu.models import decoder

    @dataclasses.dataclass(frozen=True)
    class Older:
        vocab_size: int = 0
    monkeypatch.setattr(decoder, "DecoderConfig", Older)
    with pytest.raises(SystemExit, match="not run"):
        fam.build_model(tiny_mimo.TINY_CONFIG, train=False)


# -- planted faults: each computes something else in the program's place ----

W = tiny_mimo.TINY_CONFIG["sliding_window"]


def _replaced(monkeypatch, **changes):
    sound = fam.decoder_config
    monkeypatch.setattr(fam, "decoder_config", lambda cfg: dataclasses.replace(
        sound(cfg), **changes))


def plant_full_windowed(monkeypatch):
    """The full-attention layers see the sliding window's keys alone, in
    prefill and in decoding (K of this family's cache is by column)."""
    from paddle_tpu.models import decoder
    band, paged = decoder.band_attention, decoder.paged_attention

    def band_w(q, k, v, window, sink=None):
        return band(q, k, v, window or W, sink)

    def paged_w(q, k, v, view, sink=None):
        if view.kind == "window":
            return paged(q, k, v, view, sink)
        kv, layer = view.kv, view.layer
        lens = kv.lens
        slots = jnp.arange(lens.shape[0])
        kv.k = kv.k.at[layer, slots, :, :, lens].set(k[:, :, 0])
        kv.v = kv.v.at[layer, slots, :, lens].set(v[:, :, 0])
        pos = jnp.arange(kv.v.shape[3])[None, :]
        ok = (pos <= lens[:, None]) & (pos > lens[:, None] - W)
        s = jnp.einsum("bkgd,bkds->bkgs", q, kv.k[layer]) \
            / np.sqrt(q.shape[-1])
        p = jax.nn.softmax(jnp.where(ok[:, None, None, :], s, -1e30), -1)
        return jnp.einsum("bkgs,bksd->bkgd", p, kv.v[layer])

    monkeypatch.setattr(decoder, "band_attention", band_w)
    monkeypatch.setattr(decoder, "paged_attention", paged_w)


PLANTS = {
    "no_sink": dict(sink_kinds=()),
    "rotary_whole": dict(rope_dim=None),
    "one_theta": dict(window_rope_theta=None),
    "no_value_scale": dict(value_scale=1.0),
    "window_127": dict(window=W - 1),
    "window_129": dict(window=W + 1),
    "full_windowed": plant_full_windowed,
}


@pytest.mark.parametrize("fault", sorted(PLANTS))
def test_a_planted_fault_comes_out_not_correct(tmp_path, monkeypatch, fault):
    """48 requests checked, not the mix's 4: a part of the mathematics left
    out changes a served token in some of the pool's requests only. The
    reference pads to 64 positions here so that 48 forward passes cost
    what 4 do at its own 2 048."""
    tiny_mimo.wide_scores(monkeypatch, ref)
    plant = PLANTS[fault]
    if callable(plant):
        plant(monkeypatch)
    else:
        _replaced(monkeypatch, **plant)
    monkeypatch.setattr(ref, "PAD_TO", 64)
    bad = _run(tmp_path, changes={"check_requests": 48})
    assert bad["failed"] == 0 and bad["attempted"] > 0
    assert bad["correct"] is False
    gap, limit = bad["compared"]["served_gap_max"]
    assert gap > limit


# -- the controls and the reference's own faults ----------------------------

SMALL = dict(tiny_mimo.TINY_CONFIG, sliding_window=16, sliding_window_size=16)


def test_the_faults_and_int8_read_over_bf16_rounding(monkeypatch):
    """At a size a test run can hold (the readings at the cell's own size
    are the chip's, in PERF.md and in the traffic file's `readings`): each
    fault of the forward pass puts tokens first that lie below the float32
    best by more than int8 throughout does, and int8 by far more than
    rounding to bfloat16 alone; the reference's own greedy tokens read 0,
    and with the last of them replaced the run reads that one token's gap
    over `TOKENS_A_MEAN`."""
    tiny_mimo.wide_scores(monkeypatch, ref)
    monkeypatch.setattr(ref, "PAD_TO", 96)
    w = ref.make_weights(SMALL, 1, "bfloat16")
    seqs = [ref.tokens(1 + 10 * i, 1, 96, SMALL["vocab_size"])[0]
            for i in range(2)]
    read = {k: ref.served_gaps(SMALL, w, seqs, [48] * 2, **kw)
            for k, kw in [("int8", {"quant": "int8"}),
                          ("bf16", {"quant": "bf16"})]
            + [(f, {"fault": f}) for f in ref.FAULTS
               if f not in (None, "one_token")]}
    mean = {k: v[0] for k, v in read.items()}
    assert mean["bf16"] < 2e-4 < 1e-3 < mean["int8"], mean
    for name in ref.FAULTS[1:-1]:
        assert mean[name] > mean["int8"], (name, mean)
    greedy = [np.concatenate([s[:48], np.asarray(jnp.argmax(
        ref.logits(SMALL, w, s)[47:95], -1))]) for s in seqs]
    first = ref.served_gaps(SMALL, w, [g[:49] for g in greedy], [48] * 2)
    assert max(first) == 0.0
    tokens = ref.served_gaps(SMALL, w, [g[:49] for g in greedy], [48] * 2,
                             fault="one_token", per_token=True)
    assert [int((t > 0).sum()) for t in tokens] == [1, 1]
    assert ref.served_numbers([[0.0, 0.3], [0.0]]) == pytest.approx(
        [0.1, 0.0003])
    with pytest.raises(ValueError):
        ref.hidden(SMALL, w, seqs[0], quant="int4")
    with pytest.raises(ValueError):
        ref.hidden(SMALL, w, seqs[0], fault="no_shared")


# -- configuration, traffic and work counts ---------------------------------


def test_the_configuration_is_the_catalogs_with_the_stated_cut():
    assert REAL["source"] == ("https://huggingface.co/XiaomiMiMo/MiMo-V2.5/"
                              "blob/main/config.json")
    assert REAL["reduced"] == ["num_hidden_layers", "hybrid_layer_pattern",
                               "moe_layer_freq", "n_routed_experts",
                               "vocab_size"]
    entry = [c for c in BENCH["configs"] if c["name"] == "mimo-v2.5"][0]
    assert entry["reduced"] == REAL["reduced"]
    assert entry["source"] == REAL["source"]
    widths = {"hidden_size": 4096, "num_attention_heads": 64,
              "num_key_value_heads": 4, "swa_num_attention_heads": 64,
              "swa_num_key_value_heads": 8, "head_dim": 192,
              "v_head_dim": 128, "swa_head_dim": 192, "swa_v_head_dim": 128,
              "sliding_window": 128, "intermediate_size": 16384,
              "moe_intermediate_size": 2048, "num_experts_per_tok": 8,
              "partial_rotary_factor": 0.334, "attention_value_scale": 0.707,
              "rope_theta": 10000000, "swa_rope_theta": 10000}
    assert {k: REAL[k] for k in widths} == widths
    assert REAL["num_hidden_layers"] == 7
    assert REAL["hybrid_layer_pattern"] == [0, 1, 1, 1, 1, 0, 1]
    assert REAL["moe_layer_freq"] == [0, 1, 1, 1, 1, 1, 1]
    assert (REAL["n_routed_experts"], REAL["router_experts"],
            REAL["experts_held"], REAL["vocab_size"]) == (16, 256, [0, 16],
                                                          19072)
    pub = REAL["published"]
    assert (pub["num_hidden_layers"], pub["n_routed_experts"],
            pub["vocab_size"]) == (48, 256, 152576)
    assert len(pub["hybrid_layer_pattern"]) == 48 == len(
        pub["moe_layer_freq"])
    assert pub["hybrid_layer_pattern"][:7] == REAL["hybrid_layer_pattern"]
    assert pub["kept_layers"] == list(range(7))
    assert len(REAL["assumed"]) >= 8 and "16 chips" in REAL["deployment"]
    if os.path.exists(CATALOG):      # every key as the catalog has it
        row = [json.loads(l) for l in open(CATALOG)
               if '"MiMo-V2.5"' in l][0]
        assert row["source_url"] == REAL["source"]
        for key, value in row["config"].items():
            if key not in REAL["reduced"]:
                assert REAL[key] == value, key
            else:
                assert pub[key] == value, key
    shapes = ref.leaf_shapes(REAL)
    nbytes = sum(int(np.prod(s)) * (4 if n.endswith(("expert_bias", "sink"))
                                    else 2) for n, s in shapes.items())
    assert round(nbytes / 1e9, 2) == 6.86
    assert shapes["l1.router"] == (4096, 256)
    assert shapes["l1.e_gate"] == (16, 4096, 2048)
    assert shapes["l0.wk"] == (4096, 4 * 192)
    assert shapes["l1.wv"] == (4096, 8 * 128) and shapes["l1.sink"] == (64,)
    assert "l0.sink" not in shapes and "l5.sink" not in shapes
    c = fam.decoder_config(REAL)
    assert c.geometry("full") == (64, 4, 192, 128)
    assert c.geometry("window") == (64, 8, 192, 128)
    assert c.rotary_dim("full") == 64 and c.moe.held == (0, 16)
    assert c.moe.num_experts == 256 and c.moe.top_k == 8


def test_the_traffic_is_the_issues():
    assert MIX["kind"] == "serve_closed_loop"
    assert (MIX["max_batch"], MIX["max_seq_len"], MIX["clients"],
            MIX["pool"], MIX["lead_in_completions"]) == (192, 4096, 256, 384,
                                                         192)
    assert MIX["prefill_buckets"] == [512, 1024, 2048, 3072]
    assert MIX["prompt_len"] == {"dist": "lognormal", "median": 768,
                                 "sigma": 0.7, "min": 128, "max": 3072}
    assert MIX["output_len"] == {"dist": "uniform", "min": 256, "max": 768}
    assert MIX["prefix_cache_bytes"] == 0 and MIX["check_requests"] == 12
    assert (MIX["kv_dtype"], MIX["weights_dtype"]) == ("bfloat16",) * 2
    assert (MIX["trace_after_s"], MIX["trace_seconds"]) == (2.0, 3.0)
    # between the chip's readings (the file's `readings`, PERF.md section 2):
    # the program's mean gap 0.00035-0.00061, a window of 127 or 129 rows
    # 0.00117-0.00195; the widest token 0.09-0.19 against a stray's 4.9-6.5
    limit = MIX["limits"]["served_gap_max"]
    assert 0.00061 < limit < 0.00117
    assert 0.19 * 2 < limit * ref.TOKENS_A_MEAN < 4.9 / 2
    cell = [c for c in BENCH["workloads"] if c["name"] == CELL][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "mimo-v2.5", "longgen_backlog", 1)
    import serve_window
    reqs, _ = serve_window.make_requests(MIX, 3, MIX["pool"], 1000)
    lens = np.array([len(p) for p, _ in reqs])
    assert lens.min() >= 128 and 850 <= lens.mean() <= 1050
    assert all(len(p) + o <= MIX["max_seq_len"] for p, o in reqs)
    assert all(len(p) >= REAL["sliding_window"] for p, _ in reqs)


def test_work_counts_of_the_published_widths():
    m = work_m.dims(REAL)
    assert (m["full"], m["win"], m["dense"], m["moe"]) == (2, 5, 1, 6)
    assert round(work_m.attention_params(REAL, "full") / 1e6, 1) == 89.1
    assert round(work_m.attention_params(REAL, "win") / 1e6, 1) == 94.4
    # of 8 routed experts a token a sixteenth is computed here: 0.5 expert
    per_token = work_m.matmul_params_per_token(REAL)
    want = 2 * 89.128e6 + 5 * 94.372e6 + 201.327e6 \
        + 6 * (4096 * 256 + 0.5 * 3 * 4096 * 2048) + 19072 * 4096
    assert per_token == pytest.approx(want, rel=1e-4)
    flops = work_m.forward_flops(REAL, 1024, 1024 * 1025 / 2.0)
    full = 2.0 * 64 * 320 * (1024 * 1025 / 2.0)
    band = 2.0 * 64 * 320 * (128 * 129 / 2.0 + 896 * 128)
    assert flops == pytest.approx(
        2.0 * per_token * 1024 + 2 * full + 5 * band, rel=1e-9)
    assert work_m._window_keys(10, 55.0, 4) == 1 + 2 + 3 + 4 * 7
    counts = {"live_rows_mean": 192 * 1300.0, "prefill_bucket_mean": 1100.0,
              "prefill_bucket_mean_sq": 1100.0 ** 2 * 1.3}
    f, b = work_m.moe_grouped_decode(REAL, MIX, counts)
    assert f == 6.0 * 4096 * 2048 * 96          # 192 * 8 / 16 assignments
    assert 3 * 16 * 4096 * 2048 * 2 < b < 1.01 * 3 * 16 * 4096 * 2048 * 2
    f, b = work_m.ring_decode(REAL, MIX, counts)
    assert b == 5 * 192 * 128 * 8 * 320 * 2
    f, b = work_m.full_decode(REAL, MIX, counts)
    assert b == 2 * 192 * 1300 * 4 * 320 * 2
    assert f == 2 * 192 * 1300 * 2.0 * 64 * 320
    f, b = work_m.band_prefill(REAL, MIX, counts)
    square = 2.0 * 64 * 320 * 7 * counts["prefill_bucket_mean_sq"] / 2
    assert 0.3 * square < f < 0.6 * square


# -- the trace readers, on hand-made planes with the chip's own op names ----

#: (HLO text of one device event as the profiler names it, seconds a call,
#: calls) — the names as the v5e gave them (my chip run, PR 32)
EVENTS = [
    ("%ragged-dot-none.7 = bf16[1536,2048]{1,0:T(8,128)(2,1)} custom-call("
     "%a, %b), custom_call_target=\"tpu_custom_call\"", 400e-6, 12),
    ("%ragged-dot-none.9 = bf16[1536,4096]{1,0:T(8,128)(2,1)} custom-call("
     "%a, %b), custom_call_target=\"tpu_custom_call\"", 400e-6, 6),
    ("%ragged-dot-none.21 = bf16[8192,2048]{1,0:T(8,128)(2,1)} "
     "custom-call(%a, %b), custom_call_target=\"tpu_custom_call\"",
     900e-6, 2),
    ("%sort.32 = (s32[8192]{0:T(1024)}, u32[8192]{0:T(1024)S(1)}) "
     "sort(%k, %i), dimensions={0}, is_stable=true", 40e-6, 2),
    ("%multiply_multiply_fusion.4 = bf16[8192,2048]{1,0:T(8,128)(2,1)} "
     "fusion(%g, %u), kind=kLoop", 100e-6, 1),
    ("%paged_kv_ring_decode.3 = (bf16[192,8,8,128]{3,2,1,0:T(8,128)(2,1)}, "
     "bf16[5,192,8,192,128]{4,3,2,1,0}) custom-call(%q), "
     "custom_call_target=\"tpu_custom_call\"", 300e-6, 5),
    ("%paged_kv_rows_decode.3 = (bf16[192,4,16,128]{3,2,1,0:T(8,128)(2,1)}, "
     "bf16[2,192,4,192,4096]{4,3,2,1,0}) custom-call(%q), "
     "custom_call_target=\"tpu_custom_call\"", 1500e-6, 2),
    ("%prefill_kv_band_flash.2 = bf16[8,8,1024,128]{3,2,1,0:T(8,128)(2,1)} "
     "custom-call(%q, %k, %v), custom_call_target=\"tpu_custom_call\"",
     500e-6, 7),
    ("%fusion.5 = bf16[192,1]{1,0} fusion(%h), kind=kInput", 1171e-6, 1),
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
    ctx.counts = {"live_rows_mean": 192 * 1300.0,
                  "prefill_bucket_mean": 1024.0,
                  "prefill_bucket_mean_sq": 1024.0 ** 2}
    read = {n: ctx.read_metric(_spec(n)) for n in NEW_METRICS
            if _spec(n)["source"] == "trace"}
    assert set(read) == {
        "device.idle_share.longgen", "moe.time_share.longgen",
        "attn.time_share.longgen", "mimo_ring_decode_roofline",
        "mimo_full_decode_roofline", "mimo_band_prefill_roofline",
        "mimo_moe_grouped_roofline"}
    assert all(v is not None and np.isfinite(v) for v in read.values()), read
    busy = sum(s * c for _, s, c in EVENTS)
    moe = sum(s * c for n, s, c in EVENTS[:5])
    attn = sum(s * c for n, s, c in EVENTS[5:8])
    assert read["moe.time_share.longgen"] == pytest.approx(
        100 * moe / busy, rel=1e-6)
    assert read["attn.time_share.longgen"] == pytest.approx(
        100 * attn / busy, rel=1e-6)
    f, b = work_m.moe_grouped_decode(REAL, MIX, ctx.counts)
    assert read["mimo_moe_grouped_roofline"] == pytest.approx(
        100 * (b / 819e9) / (18 * 400e-6 / 6), rel=1e-6)
    f, b = work_m.ring_decode(REAL, MIX, ctx.counts)
    assert read["mimo_ring_decode_roofline"] == pytest.approx(
        100 * (b / 819e9) / (5 * 300e-6), rel=1e-6)
    f, b = work_m.full_decode(REAL, MIX, ctx.counts)
    assert read["mimo_full_decode_roofline"] == pytest.approx(
        100 * (b / 819e9) / (2 * 1500e-6), rel=1e-6)
    for n in read:
        if n.endswith("_roofline"):
            assert 0 < read[n] < 100, (n, read[n])
    # Trinity's and GPT's readers stay silent on this cell's names, and
    # this cell's on a GPT trace; none raises
    for n in ("paged_gqa_decode_roofline", "prefill_band_flash_roofline",
              "attn.time_share.longmix", "moe_grouped_roofline"):
        assert ctx.read_metric(_spec(n)) is None, n
    fixture = os.path.join(os.path.dirname(__file__), "fixture.xplane.pb")
    if os.path.exists(fixture):
        ctx.red = trace_reduce.reduce(trace_reduce.load(fixture))
        for n in read:
            if n != "device.idle_share.longgen":
                assert ctx.read_metric(_spec(n)) is None, n
