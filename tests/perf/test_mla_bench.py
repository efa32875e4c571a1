"""Tier-1 tests of the `mla_moe` family in the benchmark (CPU, `mla-tiny`):
the real `run_cell` over files ADDED to a temporary copy (`tiny_mla.py`),
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
import tiny_mla                               # noqa: E402
import family_mla_moe as fam                  # noqa: E402
import reference_mla_moe as ref               # noqa: E402
import run                                    # noqa: E402
import work_mla_moe as work_m                 # noqa: E402

ROOT, PERF = tiny.ROOT, tiny.PERF
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELL = tiny_mla.CELL
REAL = json.load(open(os.path.join(PERF, "configs", "kimi-vl-a3b.json")))
MIX = json.load(open(os.path.join(PERF, "traffic", "doclong_backlog.json")))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


class Kept(run.Run):
    """The harness's own Run, kept for the test to read metrics from."""

    last = None

    def close_window(self):
        super().close_window()
        Kept.last = self


def _run(tmp_path, run_cls=Kept, trace=0, changes=None):
    root = tiny_mla.make_root(str(tmp_path), changes=changes)
    return run.run_cell("tiny.doclong", 2**31 + 5, 1.0, trace, root=root,
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
        0.0, tiny_mla.TINY_MIX["limits"]["served_gap_max"]]
    ctx = Kept.last
    assert ctx.harness["compiles_in_window"] == 0
    read = {n: ctx.read_metric(_spec(n)) for n in NEW_METRICS
            if _spec(n)["source"] != "trace"}
    assert set(read) == {
        "step.mfu.doclong", "decode.span_mean_ms.doclong",
        "prefill.span_mean_ms.doclong", "admission.occupancy_mean.doclong",
        "host.gap_decode_mean_ms.doclong", "moe.here_share.doclong",
        "kv.latent_rows_live_mean.doclong"}
    assert all(v is not None and np.isfinite(v) and v > 0
               for v in read.values()), read
    # 4 of the router's 16 experts are held: a quarter under even routing
    assert 5.0 < read["moe.here_share.doclong"] < 60.0
    # at most 4 slots of at most 40 rows each
    assert 9.0 <= read["kv.latent_rows_live_mean.doclong"] <= 160.0
    assert ctx.read_metric(_spec("host.ahead_share")) is not None
    # a program with no latent cache has no such series: nothing is read
    assert ctx.read_metric(dict(_spec("kv.latent_rows_live_mean.doclong"),
                                labels={"kind": "no such kind"})) is None
    # a trace reader with no trace returns nothing and does not raise
    for n in NEW_METRICS:
        if _spec(n)["source"] == "trace":
            assert ctx.read_metric(_spec(n)) is None
    for rel in ("benchmarks/perf/run.py", "benchmarks/perf/serve_window.py",
                "benchmarks/perf/traffic/doclong_backlog.json"):
        assert open(os.path.join(ROOT, rel)).read() == \
            open(os.path.join(str(tmp_path), rel)).read()


def test_a_program_without_the_block_stops_before_any_weight_is_made(
        monkeypatch):
    """What the parent commit does with this cell: its `DecoderConfig` has
    no latent branch, and the family says so and exits."""
    from paddle_tpu.models import decoder

    @dataclasses.dataclass(frozen=True)
    class Older:
        vocab_size: int = 0
        sink_kinds: tuple = ()
    monkeypatch.setattr(decoder, "DecoderConfig", Older)
    with pytest.raises(SystemExit, match="not run"):
        fam.build_model(tiny_mla.TINY_CONFIG, train=False)


# -- planted faults: each computes something else in the program's place ----


def _replaced(monkeypatch, **changes):
    sound = fam.decoder_config

    def planted(cfg):
        c = sound(cfg)
        if "route_scale" in changes or "shared_width" in changes:
            return dataclasses.replace(c, moe=dataclasses.replace(
                c.moe, **changes))
        return dataclasses.replace(c, **changes)
    monkeypatch.setattr(fam, "decoder_config", planted)


def plant_scale_row(monkeypatch):
    """Scores scaled by 1/sqrt(the cached row's width), in prefill and in
    decoding, in place of 1/sqrt(the expanded key's)."""
    from paddle_tpu.inference.serving.cache import LayerCacheView
    from paddle_tpu.models import decoder
    c = fam.decoder_config(tiny_mla.TINY_CONFIG)
    f = (c.head_dim / float(c.latent_rank + c.latent_rope_dim)) ** 0.5
    band, attend = decoder.band_attention, LayerCacheView.attend
    monkeypatch.setattr(
        decoder, "band_attention", lambda q, k, v, window, sink=None:
        band((q * f).astype(q.dtype), k, v, window, sink))
    monkeypatch.setattr(
        LayerCacheView, "attend", lambda self, q, k, v, sink=None,
        scale=None: attend(self, q, k, v, sink, scale * f))


def plant_no_kv_norm(monkeypatch):
    """`kv_norm` left out: the raw latent is cached and expanded."""
    from paddle_tpu.models import decoder
    sound = decoder.rms_norm
    r = fam.decoder_config(tiny_mla.TINY_CONFIG).latent_rank
    monkeypatch.setattr(decoder, "rms_norm", lambda x, w, eps: (
        x.astype(jnp.float32) if x.shape[-1] == r else sound(x, w, eps)))


def plant_no_k_rotary(monkeypatch):
    """The shared key part is cached unrotated (the queries still turn)."""
    from paddle_tpu.models import decoder
    sound = decoder.rotary_pairs
    monkeypatch.setattr(decoder, "rotary_pairs", lambda x, pos, theta: (
        x if x.shape[1] == 1 else sound(x, pos, theta)))


def plant_rotary_halves(monkeypatch):
    from paddle_tpu.models import decoder
    monkeypatch.setattr(decoder, "rotary_pairs", decoder.rotary)


PLANTS = {
    "scale_row": plant_scale_row,
    "no_kv_norm": plant_no_kv_norm,
    "no_k_rotary": plant_no_k_rotary,
    "rotary_halves": plant_rotary_halves,
    "no_shared": dict(shared_width=0),
    "route_scale_1": dict(route_scale=1.0),
}


@pytest.mark.parametrize("fault", sorted(PLANTS))
def test_a_planted_fault_comes_out_not_correct(tmp_path, monkeypatch, fault):
    """48 requests checked, not the mix's 4: a part of the mathematics left
    out changes a served token in some of the pool's requests only. The
    reference pads to 64 positions here so that 48 forward passes cost
    what 4 do at its own 4 096."""
    tiny_mla.wide_scores(monkeypatch, ref)
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


def test_the_faults_and_int8_read_over_bf16_rounding(monkeypatch):
    """At a size a test run can hold (the readings at the cell's own size
    are the chip's, in PERF.md and in the traffic file's `readings`): each
    fault of the forward pass and int8 throughout put tokens first that lie
    below the float32 best by far more than rounding to bfloat16 alone; the
    reference's own greedy tokens read 0, and with the last of them
    replaced the run reads that one token's gap over `TOKENS_A_MEAN`."""
    tiny_mla.wide_scores(monkeypatch, ref)
    monkeypatch.setattr(ref, "PAD_TO", 96)
    cfg = tiny_mla.TINY_CONFIG
    w = ref.make_weights(cfg, 1, "bfloat16")
    seqs = [ref.tokens(1 + 10 * i, 1, 96, cfg["vocab_size"])[0]
            for i in range(2)]
    read = {k: ref.served_gaps(cfg, w, seqs, [48] * 2, **kw)
            for k, kw in [("int8", {"quant": "int8"}),
                          ("bf16", {"quant": "bf16"})]
            + [(f, {"fault": f}) for f in ref.FAULTS
               if f not in (None, "one_token")]}
    mean = {k: v[0] for k, v in read.items()}
    assert 3 * mean["bf16"] < mean["int8"], mean
    for name in ref.FAULTS[1:-1]:
        assert mean[name] > 3 * mean["bf16"], (name, mean)
    greedy = [np.concatenate([s[:48], np.asarray(jnp.argmax(
        ref.logits(cfg, w, s)[47:95], -1))]) for s in seqs]
    first = ref.served_gaps(cfg, w, [g[:49] for g in greedy], [48] * 2)
    assert max(first) == 0.0
    tokens = ref.served_gaps(cfg, w, [g[:49] for g in greedy], [48] * 2,
                             fault="one_token", per_token=True)
    assert [int((t > 0).sum()) for t in tokens] == [1, 1]
    assert ref.served_numbers([[0.0, 0.3], [0.0]]) == pytest.approx(
        [0.1, 0.3 / ref.TOKENS_A_MEAN])
    with pytest.raises(ValueError):
        ref.hidden(cfg, w, seqs[0], quant="int4")
    with pytest.raises(ValueError):
        ref.hidden(cfg, w, seqs[0], fault="no_sink")
    with pytest.raises(ValueError, match="q_lora_rank"):
        ref.dims(dict(cfg, q_lora_rank=1536))


# -- configuration, traffic and work counts ---------------------------------


def test_the_configuration_is_the_catalogs_with_the_stated_cut():
    assert REAL["source"] == ("https://huggingface.co/moonshotai/"
                              "Kimi-VL-A3B-Instruct/blob/main/config.json")
    assert REAL["family"] == "mla_moe"
    assert REAL["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"]
    entry = [c for c in BENCH["configs"] if c["name"] == "kimi-vl-a3b"][0]
    assert entry["reduced"] == REAL["reduced"]
    assert entry["source"] == REAL["source"]
    assert entry["file"] == "benchmarks/perf/configs/kimi-vl-a3b.json"
    widths = {"hidden_size": 2048, "num_attention_heads": 16,
              "num_key_value_heads": 16, "kv_lora_rank": 512,
              "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
              "v_head_dim": 128, "intermediate_size": 11264,
              "moe_intermediate_size": 1408, "num_experts_per_tok": 6,
              "n_shared_experts": 2, "routed_scaling_factor": 2.446,
              "rope_theta": 800000, "moe_layer_freq": 1,
              "first_k_dense_replace": 1}
    assert {k: REAL[k] for k in widths} == widths
    assert REAL["q_lora_rank"] is None and REAL["rope_scaling"] is None
    assert "head_dim" not in REAL and "layer_types" not in REAL
    assert (REAL["num_hidden_layers"], REAL["n_routed_experts"],
            REAL["router_experts"], REAL["experts_held"],
            REAL["vocab_size"]) == (9, 8, 64, [0, 8], 20480)
    pub = REAL["published"]
    assert (pub["num_hidden_layers"], pub["n_routed_experts"],
            pub["vocab_size"]) == (27, 64, 163840)
    assert pub["kept_layers"] == list(range(9))
    # the guide's floors: four expert layers behind the dense one, 8
    # experts a layer, an eighth of the vocabulary
    assert REAL["num_hidden_layers"] - REAL["first_k_dense_replace"] >= 4
    assert REAL["n_routed_experts"] >= 8
    assert REAL["vocab_size"] * 8 >= pub["vocab_size"]
    assert len(REAL["assumed"]) >= 7 and "8 chips" in REAL["deployment"]
    if os.path.exists(CATALOG):      # every key as the catalog has it
        row = [json.loads(l) for l in open(CATALOG)
               if '"Kimi-VL-A3B-Instruct"' in l][0]
        assert row["source_url"] == REAL["source"]
        for key, value in row["config"].items():
            if key not in REAL["reduced"]:
                assert REAL[key] == value, key
            else:
                assert pub[key] == value, key
    shapes = ref.leaf_shapes(REAL)
    n = sum(int(np.prod(s)) for s in shapes.values())
    assert round(n / 1e6) == 970
    nbytes = sum(int(np.prod(s)) * (4 if name.endswith("expert_bias") else 2)
                 for name, s in shapes.items())
    assert round(nbytes / 1e9, 2) == 1.94
    assert shapes["l0.wq"] == (2048, 3072)
    assert shapes["l0.wkv_a"] == (2048, 576)
    assert shapes["l0.wkv_b"] == (512, 4096)
    assert shapes["l0.gate"] == (2048, 11264) and "l0.router" not in shapes
    assert shapes["l1.router"] == (2048, 64)
    assert shapes["l1.e_gate"] == (8, 2048, 1408)
    assert shapes["l8.s_down"] == (2816, 2048)
    assert shapes["head"] == (20480, 2048)
    c = fam.decoder_config(REAL)
    assert c.layer_kinds == ("latent",) * 9
    assert c.mlp_kinds == ("dense",) + ("moe",) * 8
    assert c.geometry("latent") == (16, 16, 192, 128)
    assert (c.latent_rank, c.latent_rope_dim) == (512, 64)
    assert c.moe.held == (0, 8) and c.moe.num_experts == 64
    assert c.moe.top_k == 6 and c.moe.shared_width == 2816
    # the program's parameters are the reference's leaves, shape for shape
    net = fam.build_model(REAL, False, "bfloat16")
    assert {fam.program_leaf(name): tuple(p._data.shape)
            for name, p in net.named_parameters()} == shapes


def test_the_traffic_is_the_issues():
    assert MIX["kind"] == "serve_closed_loop"
    assert (MIX["max_batch"], MIX["max_seq_len"], MIX["clients"],
            MIX["lead_in_completions"]) == (48, 16384, 64, 48)
    # one turn of the pool a window (173-180 completions were read at the
    # pool of 96 as issued: PERF.md section 6 PR 34)
    assert MIX["pool"] == 176
    assert MIX["prefill_buckets"] == [2048, 4096, 8192, 12288, 15360]
    assert MIX["prompt_len"] == {"dist": "lognormal", "median": 6144,
                                 "sigma": 0.6, "min": 1024, "max": 15360}
    assert MIX["output_len"] == {"dist": "uniform", "min": 128, "max": 512}
    assert MIX["prefix_cache_bytes"] == 0 and MIX["check_requests"] == 12
    assert (MIX["kv_dtype"], MIX["weights_dtype"]) == ("bfloat16",) * 2
    assert (MIX["trace_after_s"], MIX["trace_seconds"]) == (2.0, 3.0)
    assert set(MIX["limits"]) == {"served_gap_max"}
    cell = [c for c in BENCH["workloads"] if c["name"] == CELL][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kimi-vl-a3b", "doclong_backlog", 1)
    assert BENCH["workloads"][-1] is cell and len(cell["why"]) <= 200
    for name in ("serve_tokens_per_s", "host.ahead_share"):
        m = [m for m in BENCH["end_to_end"] + BENCH["per_layer"]
             if m["name"] == name][0]
        assert m["workloads"][-1] == CELL
    import serve_window
    reqs, _ = serve_window.make_requests(MIX, 3, MIX["pool"],
                                         REAL["vocab_size"])
    lens = np.array([len(p) for p, _ in reqs])
    assert lens.min() >= 1024 and lens.max() <= 15360
    assert 6500 <= lens.mean() <= 7600
    assert all(len(p) + o <= MIX["max_seq_len"] for p, o in reqs)
    assert all(int(p.max()) < REAL["vocab_size"] for p, _ in reqs)
    assert all(l % 512 == 0 for l in MIX["prefill_buckets"])


def test_the_limit_lies_between_the_chips_readings():
    """The program's mean gap and the lowest planted fault's, as read on
    the chip and written into the mix's `readings` (PERF.md section 2)."""
    limit = MIX["limits"]["served_gap_max"]
    r = MIX["readings_numbers"]
    assert max(r["program_mean"]) < limit < min(
        r["lowest_fault_mean"], r["int8_mean"])
    mean, sd = np.mean(r["program_mean"]), np.std(r["program_mean"], ddof=1)
    assert limit >= mean + 5 * sd
    assert max(r["program_widest_token"]) * 2 < limit * ref.TOKENS_A_MEAN \
        < r["stray_token"]


def test_work_counts_of_the_published_widths():
    m = work_m.dims(REAL)
    assert (m["L"], m["dense"], m["moe"], m["held"], m["E"]) == (9, 1, 8, 8,
                                                                 64)
    attn = 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048
    assert work_m.attention_params(REAL) == attn == 13762560
    # of 6 routed experts a token an eighth is computed here: 0.75 expert,
    # beside the shared one (two experts wide)
    per_token = work_m.matmul_params_per_token(REAL)
    expert = 3 * 2048 * 1408
    want = 9 * attn + 3 * 2048 * 11264 \
        + 8 * (2048 * 64 + (0.75 + 2) * expert) + 20480 * 2048
    assert per_token == pytest.approx(want, rel=1e-12)
    flops = work_m.forward_flops(REAL, 1024, 1024 * 1025 / 2.0)
    assert flops == pytest.approx(
        2.0 * per_token * 1024
        + 9 * 2.0 * 16 * 320 * (1024 * 1025 / 2.0), rel=1e-12)
    counts = {"live_rows_mean": 48 * 7200.0, "prefill_bucket_mean": 8000.0,
              "prefill_bucket_mean_sq": 8000.0 ** 2 * 1.2}
    f, b = work_m.latent_decode(REAL, MIX, counts)
    assert b == 9 * 1152 * (48 * 7200 + 48)
    assert f == 9 * 48 * 7200 * 16 * (576 + 512) * 2
    f, b = work_m.expanded_prefill(REAL, MIX, counts)
    assert f == 9 * 2.0 * 16 * 320 * counts["prefill_bucket_mean_sq"] / 2
    assert b == 9 * 8000 * 16 * 640 * 2
    f, b = work_m.moe_grouped_decode(REAL, MIX, counts)
    assert f == 6.0 * 2048 * 1408 * 36          # 48 * 6 / 8 assignments
    held = 3 * 8 * 2048 * 1408 * 2
    assert 0.98 * held < b < 1.01 * held


# -- the trace readers, on hand-made planes with the chip's own op names ----

#: (HLO text of one device event as the profiler names it, seconds a call,
#: calls) — the names as the v5e gave them (my chip run, PR 34)
EVENTS = [
    ("%ragged-dot-none.7 = bf16[288,1408]{1,0:T(8,128)(2,1)} custom-call("
     "%a, %b), custom_call_target=\"tpu_custom_call\"", 60e-6, 16),
    ("%ragged-dot-none.9 = bf16[288,2048]{1,0:T(8,128)(2,1)} custom-call("
     "%a, %b), custom_call_target=\"tpu_custom_call\"", 60e-6, 8),
    ("%ragged-dot-none.21 = bf16[49152,1408]{1,0:T(8,128)(2,1)} "
     "custom-call(%a, %b), custom_call_target=\"tpu_custom_call\"",
     900e-6, 2),
    ("%sort.32 = (s32[288]{0:T(512)}, u32[288]{0:T(512)S(1)}) "
     "sort(%k, %i), dimensions={0}, is_stable=true", 10e-6, 2),
    ("%multiply_multiply_fusion.4 = bf16[49152,1408]{1,0:T(8,128)(2,1)} "
     "fusion(%g, %u), kind=kLoop", 100e-6, 1),
    ("%paged_latent_decode.3 = (bf16[48,16,512]{2,1,0:T(8,128)(2,1)}, "
     "bf16[9,48,16384,512]{3,2,1,0}, bf16[9,48,64,16384]{3,2,1,0}) "
     "custom-call(%q), custom_call_target=\"tpu_custom_call\"", 900e-6, 9),
    ("%prefill_kv_band_flash.2 = bf16[16,1,8192,128]{3,2,1,0:T(8,128)(2,1)} "
     "custom-call(%q, %k, %v), custom_call_target=\"tpu_custom_call\"",
     4000e-6, 9),
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
    ctx.counts = {"live_rows_mean": 48 * 7200.0,
                  "prefill_bucket_mean": 8192.0,
                  "prefill_bucket_mean_sq": 8192.0 ** 2}
    read = {n: ctx.read_metric(_spec(n)) for n in NEW_METRICS
            if _spec(n)["source"] == "trace"}
    assert set(read) == {
        "device.idle_share.doclong", "moe.time_share.doclong",
        "attn.time_share.doclong", "mla_latent_decode_roofline",
        "mla_prefill_flash_roofline", "mla_moe_grouped_roofline"}
    assert all(v is not None and np.isfinite(v) for v in read.values()), read
    busy = sum(s * c for _, s, c in EVENTS)
    moe = sum(s * c for n, s, c in EVENTS[:5])
    attn = sum(s * c for n, s, c in EVENTS[5:7])
    assert read["moe.time_share.doclong"] == pytest.approx(
        100 * moe / busy, rel=1e-6)
    assert read["attn.time_share.doclong"] == pytest.approx(
        100 * attn / busy, rel=1e-6)
    f, b = work_m.moe_grouped_decode(REAL, MIX, ctx.counts)
    assert read["mla_moe_grouped_roofline"] == pytest.approx(
        100 * (b / 819e9) / (24 * 60e-6 / 8), rel=1e-6)
    f, b = work_m.latent_decode(REAL, MIX, ctx.counts)
    assert read["mla_latent_decode_roofline"] == pytest.approx(
        100 * (b / 819e9) / (9 * 900e-6), rel=1e-6)
    f, b = work_m.expanded_prefill(REAL, MIX, ctx.counts)
    assert read["mla_prefill_flash_roofline"] == pytest.approx(
        100 * (f / 197e12) / (9 * 4000e-6), rel=1e-6)
    for n in read:
        if n.endswith("_roofline"):
            assert 0 < read[n] < 100, (n, read[n])
    # the other families' readers stay silent on this cell's names (MiMo's
    # band reader shares the kernel, so its name: it is not this cell's
    # metric), and this cell's on a GPT trace; none raises
    for n in ("paged_gqa_decode_roofline", "prefill_band_flash_roofline",
              "attn.time_share.longmix", "moe_grouped_roofline",
              "mimo_ring_decode_roofline", "mimo_full_decode_roofline",
              "mimo_moe_grouped_roofline"):
        assert ctx.read_metric(_spec(n)) is None, n
    fixture = os.path.join(os.path.dirname(__file__), "fixture.xplane.pb")
    if os.path.exists(fixture):
        ctx.red = trace_reduce.reduce(trace_reduce.load(fixture))
        for n in read:
            if n != "device.idle_share.doclong":
                assert ctx.read_metric(_spec(n)) is None, n
