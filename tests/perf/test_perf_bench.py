"""Tier-1 tests of the benchmark under benchmarks/perf (CPU, gpt-tiny,
Pallas in interpret mode). No topology or TPU library call at import."""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import tiny                                   # noqa: E402  (puts paths in)
import reference                              # noqa: E402
import run                                    # noqa: E402
import serve_window                           # noqa: E402
import trace_reduce                           # noqa: E402
import family_gpt                             # noqa: E402
import train_window                           # noqa: E402
import work                                   # noqa: E402

ROOT, PERF = tiny.ROOT, tiny.PERF
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _cfg(name):
    return json.load(open(os.path.join(PERF, "configs", name + ".json")))


# -- repair 1: exact window accounting, on a fake clock ---------------------


class FakeDevice:
    """A clock and a device that takes `unit` seconds a unit of work, in
    order; dispatch costs the host `host` seconds."""

    def __init__(self, unit=1.0, host=0.01):
        self.now, self.free, self.unit, self.host = 0.0, 0.0, unit, host

    def clock(self):
        return self.now

    def dispatch(self):
        self.now += self.host
        self.free = max(self.free, self.now) + self.unit
        return self.free

    def wait(self, done_at):
        self.now = max(self.now, done_at)


@pytest.mark.parametrize("shift", [-0.6, 0.0, 0.6])
def test_train_window_rate_ignores_where_the_deadline_falls(shift):
    dev = FakeDevice()
    n, elapsed = train_window.run_units(dev.dispatch, dev.wait, dev.clock,
                                        80.0 + shift)
    assert n >= 80
    assert abs(n / elapsed - 1.0) < 1e-3


def test_train_window_counts_nothing_unfinished():
    dev = FakeDevice()
    n, elapsed = train_window.run_units(dev.dispatch, dev.wait, dev.clock,
                                        10.3)
    assert dev.now >= dev.free            # every dispatched unit was waited
    assert elapsed >= n * dev.unit - 1e-9


@pytest.mark.parametrize("shift", [-0.6, 0.0, 0.6])
def test_open_loop_offers_its_rate_wherever_the_deadline_falls(shift):
    traffic = tiny.TINY_TRAFFIC["tiny_chat"]
    rate = traffic["rate_per_s"]
    seconds = 100.0 + shift / rate
    n = int(round(rate * seconds))
    _, offsets = serve_window.make_requests(traffic, 5, n, 128)
    assert len(offsets) == n
    # the window is the n arrivals, n / rate long: the last is due at its end
    assert abs(offsets[-1] - n / rate) < 1e-9
    assert abs(n / offsets[-1] - rate) / rate < 1e-3


@pytest.mark.parametrize("shift", [-0.6, 0.0, 0.6])
def test_closed_loop_rate_ignores_where_the_deadline_falls(shift):
    """Completions one unit apart: the window opens and closes AT
    completion instants, so the rate is whole units over whole units."""
    done = np.arange(1, 400) * 1.0
    lead_in, seconds = 24, 200.0 + shift
    t_open = done[lead_in - 1]
    t_close = done[done >= t_open + seconds][0]
    k = int(((done > t_open) & (done <= t_close)).sum())
    assert abs(k / (t_close - t_open) - 1.0) < 1e-3


# -- the generator ----------------------------------------------------------


def test_every_seed_gets_the_same_work_in_another_order():
    traffic = tiny.TINY_TRAFFIC["tiny_chat"]
    a, off_a = serve_window.make_requests(traffic, 1, 40, 128)
    b, off_b = serve_window.make_requests(traffic, 2**31 + 9, 40, 128)
    assert sorted(len(p) for p, _ in a) == sorted(len(p) for p, _ in b)
    assert sorted(o for _, o in a) == sorted(o for _, o in b)
    assert np.allclose(np.sort(np.diff(off_a, prepend=0)),
                       np.sort(np.diff(off_b, prepend=0)))
    assert [len(p) for p, _ in a] != [len(p) for p, _ in b]
    again, _ = serve_window.make_requests(traffic, 1, 40, 128)
    assert all((p == q).all() for (p, _), (q, _) in zip(a, again))


def test_lengths_follow_the_stated_distribution():
    spec = {"dist": "lognormal", "median": 96, "sigma": 0.8, "min": 16,
            "max": 512}
    x = serve_window._quantiles(spec, 401)
    assert x.min() >= 16 and x.max() <= 512
    assert abs(np.median(x) - 96) <= 1
    u = serve_window._quantiles({"dist": "uniform", "min": 384, "max": 960},
                                64)
    assert u.min() >= 384 and u.max() <= 960 and abs(u.mean() - 672) < 2


# -- trace reduction on a recorded trace ------------------------------------

FIXTURE = os.path.join(os.path.dirname(__file__), "fixture.xplane.pb")


def test_union_and_idle_share_on_hand_made_planes():
    ops = [("a", 0.0, 4e9), ("b", 2e9, 4e9), ("c", 8e9, 1e9)]
    mods = [("jit_f(1)", 0.0, 6e9), ("jit_g(2)", 8e9, 1e9)]
    planes = [("/device:TPU:0", [("XLA Ops", ops), ("XLA Modules", mods)]),
              ("/host:CPU", [("main", [(trace_reduce.WINDOW_ANNOTATION,
                                        0.0, 10e9)])])]
    red = trace_reduce.reduce(planes)
    assert red["window_s"] == pytest.approx(10.0)
    assert red["busy_s"] == pytest.approx(7.0)          # [0,6] and [8,9]
    assert red["idle_share"] == pytest.approx(0.3)
    assert trace_reduce.matched(red, ["^a$", "^c$"]) == (
        pytest.approx(5.0), 2)
    assert red["gaps"]["jit_f__jit_g"] == pytest.approx(2.0)
    assert red["gaps"]["jit_g__window_close"] == pytest.approx(1.0)
    top = trace_reduce.breakdown(red)
    assert top["device_ops"][0][0] in ("a", "b")
    with pytest.raises(ValueError):
        trace_reduce.reduce(planes[1:])                  # no device plane


@pytest.mark.skipif(not os.path.exists(FIXTURE),
                    reason="no recorded trace in the directory")
def test_reduction_of_the_recorded_v5e_trace():
    red = trace_reduce.reduce(trace_reduce.load(FIXTURE))
    assert red["devices"] == 1
    assert 0.0 < red["busy_s"] <= red["window_s"]
    assert 0.0 <= red["idle_share"] < 1.0
    total = sum(s for s, _ in red["ops"].values())
    assert total >= red["busy_s"] * 0.999              # union <= sum
    expected = json.load(open(FIXTURE.replace(".xplane.pb", ".json")))
    assert red["busy_s"] == pytest.approx(expected["busy_s"], rel=1e-4)
    assert red["window_s"] == pytest.approx(expected["window_s"], rel=1e-4)
    secs, calls = trace_reduce.matched(red, expected["patterns"])
    assert secs == pytest.approx(expected["matched_s"], rel=1e-4)
    assert calls == expected["matched_calls"]


# -- work counts against hand-worked numbers --------------------------------


def test_work_counts_gpt2_small():
    cfg = _cfg("gpt2-small")
    # 12 x (2304x768 + 768x768 + 2 x 768x3072 + biases and gains) + tables
    assert work.n_params(cfg) == 124_475_904
    mm = 12 * (4 * 768 * 768 + 2 * 768 * 3072) + 50304 * 768
    assert mm == 123_568_128
    assert work.train_flops_per_token(cfg, 1024) == pytest.approx(
        6 * mm + 6 * 12 * 768 * 1024)
    fl, by = work.flash_fwd(cfg, {"batch": 24, "seq_len": 1024}, {})
    assert fl == 4 * 24 * 12 * 1024 * 1024 * 64 / 2
    assert by == 4 * 24 * 12 * 1024 * 64 * 2
    fl4, _ = work.flash_fwd(cfg, {"batch": 96, "seq_len": 1024},
                            {"chips": 4})
    assert fl4 == fl                       # per chip under dp=4


def test_work_counts_gpt3_xl():
    cfg = _cfg("gpt3-1.3b")
    assert work.n_params(cfg) == 1_315_819_520
    counts = {"live_rows_mean": 24 * 200.0}
    fl, by = work.decode_step(cfg, {"max_batch": 24}, counts)
    cache = 24 * 2 * 4800 * 16 * 128 * 2           # L x (k, v) x rows x bf16
    assert by == 2 * 1_315_819_520 + cache
    mm = 24 * (4 * 2048 ** 2 + 2 * 2048 * 8192) + 50304 * 2048
    assert fl == 2 * mm * 24 + 4 * 24 * 2048 * 4800
    _, pby = work.paged_decode(cfg, {}, counts)
    assert pby == 2 * 4800 * 16 * 128 * 2
    assert work.peaks("TPU v5 lite")["flops"] == 197e12
    with pytest.raises(KeyError):
        work.peaks("TPU v9")


# -- the plain reference against the program's GPTModel ---------------------


def test_reference_logits_match_the_program_at_gpt_tiny():
    import paddle_tpu as paddle
    from paddle_tpu.jit.engine import make_eval_step
    cfg = tiny.TINY_CONFIG
    net = family_gpt.build_model(cfg, train=False, dtype="float32")
    w = reference.make_weights(cfg, 3, "float32")
    family_gpt.load_weights(net, w)
    ids = reference.tokens(3, 2, 32, cfg["vocab_size"])
    _, outs = make_eval_step(net)([paddle.to_tensor(ids)])
    got = np.asarray(outs[0].numpy(), np.float32)
    want = np.asarray(reference.logits(cfg, w, ids))
    assert np.abs(got - want).max() < 2e-3 * np.abs(want).max()
    # and the control is a different computation
    ctl = np.asarray(reference.logits(cfg, w, ids, "int8"))
    assert np.abs(ctl - want).max() > 10 * np.abs(got - want).max()


# -- the data files ---------------------------------------------------------


def _files(sub):
    d = os.path.join(PERF, sub)
    return sorted(f for f in os.listdir(d) if f.endswith(".json"))


@pytest.mark.parametrize("sub", ["configs", "traffic", "metrics"])
def test_every_data_file_loads_and_is_named_within_the_rules(sub):
    for f in _files(sub):
        assert NAME.match(f[:-5]), f
        assert isinstance(json.load(open(os.path.join(PERF, sub, f))), dict)


def test_benchmark_json_names_units_and_files():
    assert sorted(BENCH) == sorted([
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"])
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = {c["name"]: c for c in BENCH["workloads"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        for wl in m.get("workloads", []):
            assert wl in cells
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for c in BENCH["configs"]:
        assert NAME.match(c["name"])
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert c["reduced"] == _cfg(c["name"])["reduced"]
    for c in cells.values():
        assert NAME.match(c["name"]) and NAME.match(c["traffic"])
        assert len(c["why"]) <= 200 and c["chips"] in (1, 4)
        assert (c["traffic"] + ".json") in _files("traffic")
    four = sum(c["chips"] == 4 for c in cells.values())
    assert four <= max(1, len(cells) // 4)


def test_every_per_layer_metric_has_its_file_and_moves_what_its_cells_report():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = [c["name"] for c in BENCH["workloads"]]
    for m in BENCH["per_layer"]:
        spec = json.load(open(os.path.join(PERF, "metrics",
                                           m["name"] + ".json")))
        assert spec["layer"] == m["layer"] and spec["unit"] == m["unit"]
        assert spec["moves"] == m["moves"] in e2e
        assert spec["source"] in ("harness", "registry", "trace")
        mover = e2e[m["moves"]]
        for wl in m.get("workloads", cells):
            assert wl in mover.get("workloads", cells), (m["name"], wl)
    for c in BENCH["workloads"]:
        reported = [m["name"] for m in run.cell_metrics(BENCH, c,
                                                        "end_to_end")]
        assert "setup_s" in reported and len(reported) >= 2
        assert run.cell_metrics(BENCH, c, "per_layer")


def test_limits_and_readings_are_in_every_traffic_file():
    for f in _files("traffic"):
        tr = json.load(open(os.path.join(PERF, "traffic", f)))
        assert tr["kind"] in run.WINDOWS and tr["limits"] and tr["why"]


# -- run.py without a chip --------------------------------------------------


def test_run_py_exits_non_zero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(PERF, "run.py"), "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not p.stdout.strip().startswith("{")


# -- a whole run with the look for a chip skipped; faults planted -----------


def _run(tmp_path, mix, run_cls=run.Run, seconds=1.0, seed=2**31 + 5,
         changes=None):
    import jax
    root = tiny.make_root(str(tmp_path), cells=[mix], changes=changes)
    return run.run_cell("tiny." + mix, seed, seconds, 0, root=root,
                        devices=[tiny.FakeTPU(jax.devices()[0])],
                        run_cls=run_cls)


def test_a_cell_added_as_files_runs_with_no_edit_to_an_existing_file(
        tmp_path):
    """make_root adds one config file, one traffic file and one
    `workloads` entry to a copy; nothing that was there is edited."""
    res = _run(tmp_path, "tiny_train")
    assert list(res)[-1] == "compared"
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"train_tokens_per_s_per_chip", "setup_s"}
    assert res["metrics"]["train_tokens_per_s_per_chip"]["value"] > 0
    assert set(res["compared"]) == {"grad_norm_gap", "change_norm_gap"}
    for rel in ("benchmarks/perf/run.py",
                "benchmarks/perf/traffic/pretrain_t1024.json"):
        assert open(os.path.join(ROOT, rel)).read() == \
            open(os.path.join(str(tmp_path), rel)).read()


class StateUnchanged(run.Run):
    """The step computes its loss and returns its state unchanged."""

    def wrap_step(self, step):
        def broken(inputs, labels):
            params = step._params
            before = [p._data.copy() for p in params]
            out = step(inputs, labels)
            for p, b in zip(params, before):
                p._data = b
            return out
        broken._params = step._params
        return broken


class HalfBatch(run.Run):
    """Half of the batch left out, the mean taken over the rest (the
    other half stands in twice, so shapes hold)."""

    def wrap_step(self, step):
        import paddle_tpu as paddle

        def broken(inputs, labels):
            def half(t):
                h = t[: t.shape[0] // 2]
                return paddle.concat([h, h], axis=0)
            return step([half(t) for t in inputs],
                        [half(t) for t in labels])
        return broken


@pytest.mark.parametrize("fault", [StateUnchanged, HalfBatch])
def test_a_broken_train_step_comes_out_not_correct(tmp_path, fault):
    res = _run(tmp_path, "tiny_train", run_cls=fault)
    assert res["correct"] is False
    assert any(v > lim for v, lim in res["compared"].values())


class TokenAltered(run.Run):
    """Decode returns another token than the one it picked."""

    def wrap_engine(self, engine):
        decode = engine.decode

        def broken():
            return (decode() + 1) % 128
        engine.decode = broken
        return engine


@pytest.mark.parametrize("mix", ["tiny_chat", "tiny_backlog"])
def test_served_cells_run_and_an_altered_token_comes_out_not_correct(
        tmp_path, mix, monkeypatch):
    monkeypatch.setenv("FLAGS_paged_flash_interpret", "1")
    good = _run(tmp_path / "good", mix)
    assert good["correct"] is True and good["attempted"] > 0
    want = {"tiny_chat": {"ttft_p95_ms", "tpot_p95_ms", "setup_s"},
            "tiny_backlog": {"serve_tokens_per_s", "setup_s"}}[mix]
    assert set(good["metrics"]) == want
    assert all(v["value"] > 0 for v in good["metrics"].values())
    bad = _run(tmp_path / "bad", mix, run_cls=TokenAltered)
    assert bad["correct"] is False
    assert bad["compared"]["served_gap_max"][0] > \
        bad["compared"]["served_gap_max"][1]


def test_open_loop_times_from_the_due_instant_and_reports_lateness(
        tmp_path, monkeypatch):
    """A stall in front of the server shows in TTFT: the generator is held
    back 0.3 s once, and every request due meanwhile is timed from when it
    was DUE, not from when it was submitted."""
    monkeypatch.setenv("FLAGS_paged_flash_interpret", "1")
    seen = {}

    class Stalled(run.Run):
        def wrap_engine(self, engine):
            seen["ctx"] = self
            prefill, state = engine.prefill, {"n": 0}

            def slow(slot, prompt):
                state["n"] += 1
                if state["n"] == 9:
                    import time
                    time.sleep(0.5)
                return prefill(slot, prompt)
            engine.prefill = slow
            return engine

    res = _run(tmp_path, "tiny_chat", run_cls=Stalled, seconds=2.0, seed=7,
               changes={"rate_per_s": 8.0})
    assert res["metrics"]["ttft_p95_ms"]["value"] >= 250.0
    assert seen["ctx"].harness["gen_late_p95_ms"] < 50.0


# -- the control: the reference in int8, put in the program's place ---------

SMALL = {"n_embd": 128, "n_head": 4, "n_inner": 512, "n_layer": 4,
         "n_positions": 256, "vocab_size": 1024, "layer_norm_epsilon": 1e-5}


def _limits(mix):
    return json.load(open(os.path.join(PERF, "traffic", mix + ".json")))[
        "limits"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_int8_control_fails_the_cells_own_limits(seed):
    """At a size a test run can hold (the chip readings, at the cells' own
    sizes, are in PERF.md): lower precision in the program's place reads
    over the limit of a number of each kind of cell; the reference against
    itself reads 0."""
    w = reference.make_weights(SMALL, seed, "bfloat16")
    rows = reference.tokens(seed, 24, 129, SMALL["vocab_size"])
    batches = [(rows[i * 8:(i + 1) * 8, :-1], rows[i * 8:(i + 1) * 8, 1:])
               for i in range(3)]
    ref = reference.train_steps(SMALL, w, batches, tiny.OPT, 4)
    ctl = reference.train_steps(SMALL, w, batches, tiny.OPT, 4,
                                quant="int8")
    limits = _limits("pretrain_t1024")
    got = reference.compare_training(ctl, ref)
    assert any(got[k] > lim for k, lim in limits.items()), got
    # float32 master parameters: a LayerNorm gain near 1 moves by about
    # lr a step, which bfloat16 storage (spacing 0.0078) would round away
    assert ref["change_norms"]["ln_f.w"] > 3 * tiny.OPT["lr"]
    same = reference.compare_training(ref, ref)
    assert all(same[k] == 0.0 for k in limits)
    half = reference.compare_training(reference.train_steps(
        SMALL, w, batches, tiny.OPT, 4, fault="half_batch"), ref)
    assert half["grad_norm_gap"] > 10 * limits["grad_norm_gap"]
    seqs = [reference.tokens(seed + 10 * i, 1, 200, SMALL["vocab_size"])[0]
            for i in range(3)]
    served = reference.served_gaps(SMALL, w, seqs, [150] * 3, quant="int8")
    assert max(served) > _limits("chat_poisson")["served_gap_max"]
    assert max(served) > _limits("backlog_long_prompts")["served_gap_max"]
    greedy = [np.concatenate([s[:150], np.asarray(np.argmax(np.asarray(
        reference.logits(SMALL, w, s[None]))[0, 149:199], -1))])
        for s in seqs]
    # the reference's own greedy continuation of one step is gap 0
    assert reference.served_gaps(SMALL, w, [g[:151] for g in greedy],
                                 [150] * 3) == [0.0, 0.0, 0.0]
