"""The per-layer metrics that read the program's own spans and counters
(PERF.md §3): each is one added file read by the `registry` reader that
was there, and one appended `per_layer` entry. CPU, gpt-tiny: the numbers
are not device numbers, only that each reader finds its series."""
from __future__ import annotations

import json
import math
import os
import re

import pytest

import tiny                                   # noqa: E402  (puts paths in)
import run                                    # noqa: E402

PERF = tiny.PERF
BENCH = json.load(open(os.path.join(tiny.ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
#: metric -> (registry series, labels)
NEW = {
    "admission.queue_wait_mean_ms": ("pt_span_ms", {"name": "queue_wait"}),
    "prefill.span_mean_ms": ("pt_span_ms", {"name": "prefill"}),
    "decode.span_mean_ms": ("pt_span_ms", {"name": "decode_step"}),
    "decode.itl_mean_ms": ("pt_serve_itl_ms", None),
    "admission.occupancy_mean": ("pt_serve_occupancy_pct", None),
    "host.gap_decode_mean_ms": ("pt_span_ms", {"name": "host_gap_decode"}),
    "host.gap_prefill_mean_ms": ("pt_span_ms",
                                 {"name": "host_gap_prefill"}),
    "host.decode_dispatch_mean_s": ("pt_step_latency_seconds",
                                    {"engine": "serve_decode"}),
}
CHAT_ONLY = ("admission.queue_wait_mean_ms", "decode.itl_mean_ms")
METRICS = [m + ".chat" for m in NEW] + \
    [m + ".backlog" for m in NEW if m not in CHAT_ONLY]
MIX = {"chat": "tiny_chat", "backlog": "tiny_backlog"}
#: arrivals close enough that one request's prefill follows another's
#: decode step (a host gap before a prefill needs a program before it)
CHANGES = {"tiny_chat": {"rate_per_s": 30.0}}


def _spec(name):
    return json.load(open(os.path.join(PERF, "metrics", name + ".json")))


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """One `--trace 0` run of each tiny served mix, its `Run` kept."""
    import jax
    kept = {}

    def one(mix):
        if mix in kept:
            return kept[mix]
        ctxs = []

        class Kept(run.Run):
            def __init__(self, *a, **kw):
                super().__init__(*a, **kw)
                ctxs.append(self)

        root = tiny.make_root(str(tmp_path_factory.mktemp(mix)),
                              cells=[mix], changes=CHANGES.get(mix))
        os.environ["FLAGS_paged_flash_interpret"] = "1"
        try:
            res = run.run_cell(
                "tiny." + mix, 2**31 + 11, 1.0, 0, root=root,
                devices=[tiny.FakeTPU(jax.devices()[0])], run_cls=Kept)
        finally:
            del os.environ["FLAGS_paged_flash_interpret"]
        assert res["correct"] is True and res["failed"] == 0
        kept[mix] = ctxs[0]
        return kept[mix]

    return one


def test_there_are_fourteen_of_them_appended_after_what_was_there():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[-len(METRICS):] == [
        "admission.queue_wait_mean_ms.chat", "prefill.span_mean_ms.chat",
        "prefill.span_mean_ms.backlog", "decode.span_mean_ms.chat",
        "decode.span_mean_ms.backlog", "decode.itl_mean_ms.chat",
        "admission.occupancy_mean.chat", "admission.occupancy_mean.backlog",
        "host.gap_decode_mean_ms.chat", "host.gap_decode_mean_ms.backlog",
        "host.gap_prefill_mean_ms.chat", "host.gap_prefill_mean_ms.backlog",
        "host.decode_dispatch_mean_s.chat",
        "host.decode_dispatch_mean_s.backlog"]
    assert sorted(names[-len(METRICS):]) == sorted(METRICS)
    assert len(set(names)) == len(names)


@pytest.mark.parametrize("name", METRICS)
def test_entry_and_file_agree_and_read_the_programs_own_series(name):
    (m,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    stem, cell = name.rsplit(".", 1)
    spec = _spec(name)
    assert sorted(m) == sorted(["name", "unit", "better", "source",
                                "layer", "moves", "workloads"])
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["source"] == ("program_counter" if "occupancy" in name
                           else "program_span")
    assert m["better"] == ("higher" if "occupancy" in name else "lower")
    assert spec["layer"] == m["layer"] and spec["unit"] == m["unit"]
    assert spec["moves"] == m["moves"]
    assert (spec["source"], spec["stat"]) == ("registry", "mean")
    assert (spec["series"], spec.get("labels")) == NEW[stem]
    assert m["workloads"] == ["gpt3xl-serve-" + cell]
    e2e = {e["name"]: e for e in BENCH["end_to_end"]}
    assert m["workloads"][0] in e2e[m["moves"]]["workloads"]
    if cell == "backlog":
        assert m["moves"] == "serve_tokens_per_s"


@pytest.mark.parametrize("name", METRICS)
def test_the_registry_reader_finds_a_finite_number_after_a_run(name, runs):
    ctx = runs(MIX[name.rsplit(".", 1)[1]])
    value = ctx.read_metric(_spec(name))
    assert value is not None and math.isfinite(value) and value > 0
    if "occupancy" in name:
        assert value <= 100.0


def test_inside_and_outside_agree_where_they_bound_the_same_interval(runs):
    """The span round `engine.decode()` inside the batcher and the
    harness's wrapper round the same call count the same steps; the
    occupancy histogram and the counter arithmetic give the same share."""
    ctx = runs("tiny_backlog")
    steps = ctx.harness["decode_steps"]
    inside = run._series(ctx._reg1, "pt_span_ms", {"name": "decode_step"})
    before = run._series(ctx._reg0, "pt_span_ms", {"name": "decode_step"})
    assert inside["count"] - before["count"] == pytest.approx(steps, abs=1)
    occ = ctx.read_metric(_spec("admission.occupancy_mean.backlog"))
    assert occ == pytest.approx(ctx.harness["occupancy"], abs=5.0)


def test_a_program_without_the_series_reads_nothing_and_does_not_raise(
        runs):
    """What the parent commit gives the new readers: no such series."""
    ctx = runs("tiny_chat")
    spec = dict(_spec("decode.itl_mean_ms.chat"), series="pt_not_there")
    assert ctx.read_metric(spec) is None
    spec = dict(_spec("decode.span_mean_ms.chat"),
                labels={"name": "no_such_span"})
    assert ctx.read_metric(spec) is None
