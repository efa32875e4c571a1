"""Flight recorder + crash forensics: event ring, HBM gauges, crash
bundles, cross-rank aggregation, ptdoctor CLI, torn-journal tolerance,
and the bench probe-timeout fallback contract.

The 2-rank chaos drills (kill_rank / hang_rank -> exactly one crash
bundle + merged timeline) live in tests/test_multiprocess_dist.py; this
file covers everything that fits in one process. Everything runs on the
CPU mesh (JAX_PLATFORMS=cpu in the tier-1 gate).
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.hapi.callbacks import Callback
from paddle_tpu.observability import aggregate, flight, metrics
from paddle_tpu.observability import journal as run_journal
from paddle_tpu.resilience import chaos

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _isolate_flight():
    """The dump-once guard, configured dir and HBM sample clock are
    process-global; every test starts clean."""
    flight.reset()
    yield
    flight.reset()


def _fit(tmp_path, **kw):
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 8), nn.ReLU(), nn.Linear(8, 4))
    model = paddle.Model(net)
    opt = paddle.optimizer.SGD(learning_rate=0.01,
                               parameters=net.parameters())
    model.prepare(opt, nn.CrossEntropyLoss())
    X = np.random.RandomState(0).rand(16, 8).astype("float32")
    Y = np.zeros((16, 1), np.int64)
    ds = [(X[i], Y[i]) for i in range(16)]
    model.fit(ds, batch_size=8, epochs=1, verbose=0,
              telemetry_dir=str(tmp_path), **kw)
    return model


# ----------------------------------------------------------------- ring
class TestRing:
    def test_journal_emit_taps_ring(self, tmp_path):
        j = run_journal.RunJournal(str(tmp_path), rank=0)
        prev = run_journal.set_journal(j)
        try:
            run_journal.emit("custom_event", x=1)
        finally:
            run_journal.set_journal(prev)
            j.close()
        evs = [e for e in flight.ring_events()
               if e.get("event") == "custom_event"]
        assert evs and evs[0]["x"] == 1

    def test_journalless_emit_still_rings(self):
        assert run_journal.get_journal() is None
        run_journal.emit("orphan_event", y=2)
        evs = [e for e in flight.ring_events()
               if e.get("event") == "orphan_event"]
        assert evs and evs[0]["y"] == 2

    def test_ring_is_bounded(self):
        cap = flight._ring.maxlen
        for i in range(cap + 50):
            flight.record("spam", i=i)
        evs = flight.ring_events()
        assert len(evs) == cap
        assert evs[-1]["i"] == cap + 49   # newest kept, oldest evicted


# ---------------------------------------------------------- crash bundle
class TestCrashBundle:
    def test_dump_without_dir_is_noop(self):
        assert flight.dump_crash_bundle("nowhere") is None

    def test_bundle_layout_and_once_guard(self, tmp_path):
        flight.configure(str(tmp_path), rank=3)
        flight.note_dispatch("jit_train", 7)
        flight.record("something")
        try:
            raise RuntimeError("boom")
        except RuntimeError as e:
            p = flight.dump_crash_bundle("unit", exc=e, last_step=7)
        assert p and os.path.isdir(p)
        assert os.path.basename(os.path.dirname(p)) == "crash"
        man = json.load(open(os.path.join(p, "MANIFEST.json")))
        assert man["reason"] == "unit" and man["rank"] == 3
        assert man["last_step"] == 7
        assert man["last_dispatch"]["engine"] == "jit_train"
        assert "boom" in man["error"]
        for name in ("ring.jsonl", "stacks.txt", "metrics.json",
                     "env.json"):
            assert os.path.exists(os.path.join(p, name)), name
        stacks = open(os.path.join(p, "stacks.txt")).read()
        assert "boom" in stacks and "--- all threads ---" in stacks
        ring = run_journal.read_journal(os.path.join(p, "ring.jsonl"))
        assert any(e.get("event") == "something" for e in ring)
        env = json.load(open(os.path.join(p, "env.json")))
        assert "python" in env and isinstance(env["env"], dict)
        # second dump is swallowed by the once-guard...
        assert flight.dump_crash_bundle("again") == p
        # ...unless forced
        p2 = flight.dump_crash_bundle("forced", force=True)
        assert p2 != p and os.path.isdir(p2)

    def test_chaos_predeath_dump(self, tmp_path):
        """The kill_rank/hang_rank sites dump through chaos._flight_dump
        BEFORE the SIGKILL/sleep lands (SIGKILL is uncatchable — the
        pre-mortem dump is the only one there will ever be). The real
        2-rank drills assert the end-to-end behavior."""
        flight.configure(str(tmp_path), rank=1)
        chaos._flight_dump("chaos_kill", 2)
        mans = aggregate.load_events(str(tmp_path))
        found = [e for e in mans if e["event"] == "crash_bundle_found"]
        assert len(found) == 1
        assert found[0]["reason"] == "chaos_kill"
        assert found[0]["last_step"] == 2 and found[0]["rank"] == 1

    def test_fit_exception_dumps_bundle(self, tmp_path):
        class Boom(Callback):
            def on_train_batch_end(self, step, logs=None):
                if step == 1:
                    raise RuntimeError("injected step failure")

        with pytest.raises(RuntimeError, match="injected step failure"):
            _fit(tmp_path, callbacks=[Boom()])
        crash = os.path.join(str(tmp_path), "crash")
        dirs = os.listdir(crash)
        assert len(dirs) == 1
        man = json.load(open(os.path.join(crash, dirs[0],
                                          "MANIFEST.json")))
        assert man["reason"] == "fit_exception"
        assert "injected step failure" in man["error"]
        # ring captured the run's own journal stream via the tap
        ring = run_journal.read_journal(
            os.path.join(crash, dirs[0], "ring.jsonl"))
        assert any(e.get("event") == "run_start" for e in ring)
        # the journal recorded the bundle before the exception unwound
        evs = run_journal.read_journal(
            os.path.join(str(tmp_path), "journal-rank0.jsonl"))
        assert any(e["event"] == "crash_bundle" for e in evs)


# ------------------------------------------------------------ HBM gauges
class TestHbmGauges:
    def test_present_after_two_step_fit(self, tmp_path):
        _fit(tmp_path)
        snap = json.load(open(os.path.join(str(tmp_path), "metrics.json")))
        m = snap["metrics"]
        assert "pt_hbm_bytes_in_use" in m, sorted(m)
        in_use = m["pt_hbm_bytes_in_use"]["series"][0]["value"]
        peak = m["pt_hbm_peak_bytes"]["series"][0]["value"]
        assert in_use > 0
        assert peak >= in_use * 0  # peak present and numeric
        assert peak > 0

    def test_sample_without_jax_modules_is_noop(self, monkeypatch):
        monkeypatch.setitem(sys.modules, "jax", None)
        # sys.modules.get("jax") -> None: never imports, never raises
        assert flight.sample_hbm(force=True) is None


# ----------------------------------------------------- torn journal lines
class TestTornJournal:
    def test_torn_final_line_skipped_with_counter(self, tmp_path):
        j = run_journal.RunJournal(str(tmp_path), rank=0)
        j.emit("a", i=1)
        j.emit("b", i=2)
        j.close()
        with open(j.path, "a") as f:
            f.write('{"ts": 3, "event": "torn-mid-wr')   # SIGKILL here
        before = metrics.REGISTRY.counter(
            "pt_journal_torn_lines_total", "").value
        stats = {}
        evs = run_journal.read_journal(j.path, stats=stats)
        assert [e["event"] for e in evs] == ["a", "b"]
        assert stats["skipped"] == 1
        assert metrics.REGISTRY.counter(
            "pt_journal_torn_lines_total", "").value == before + 1

    def test_non_dict_and_binary_lines_skipped(self, tmp_path):
        path = os.path.join(str(tmp_path), "journal-rank0.jsonl")
        with open(path, "wb") as f:
            f.write(b'42\n')                      # valid JSON, not a dict
            f.write(b'{"ts": 1, "event": "ok"}\n')
            f.write(b'\xff\xfe garbage \xff\n')   # undecodable bytes
        stats = {}
        evs = run_journal.read_journal(path, stats=stats)
        assert [e["event"] for e in evs] == ["ok"]
        assert stats["skipped"] == 2


# ------------------------------------------------------- metrics guard env
class TestSeriesCapEnv:
    def test_env_sets_default_cap(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_METRICS_MAX_SERIES", "2")
        c = metrics.Counter("env_cap_total", labelnames=("k",))
        assert c.max_series == 2
        c.labels("a").inc()
        c.labels("b").inc()
        c.labels("c").inc()                       # dropped, no raise
        assert c.series_count == 2 and c.dropped_series == 1


# ------------------------------------------------------------- aggregation
def _synthetic_run(d):
    """A fake 2-rank run dir: interleaved journals (rank1's final line
    torn), launcher journal with one gang restart, one heartbeat, one
    crash bundle manifest, two metrics snapshots."""
    os.makedirs(d, exist_ok=True)

    def w(name, recs, torn=False):
        with open(os.path.join(d, name), "w") as f:
            for r in recs:
                f.write(json.dumps(r) + "\n")
            if torn:
                f.write('{"ts": 99.0, "event": "to')

    w("journal-rank0.jsonl", [
        {"ts": 1.0, "rank": 0, "event": "worker_start"},
        {"ts": 3.0, "rank": 0, "event": "step", "step": 1},
        {"ts": 5.0, "rank": 0, "event": "step", "step": 2},
        {"ts": 7.0, "rank": 0, "event": "worker_end"},
    ])
    w("journal-rank1.jsonl", [
        {"ts": 1.5, "rank": 1, "event": "worker_start"},
        {"ts": 3.5, "rank": 1, "event": "step", "step": 1},
        {"ts": 4.0, "rank": 1, "event": "retrace", "engine": "jit_train"},
    ], torn=True)
    w("journal-launch.jsonl", [
        {"ts": 0.5, "rank": 0, "event": "launch_start"},
        {"ts": 4.5, "rank": 0, "event": "gang_restart", "failed_rank": 1,
         "cause": "crash"},
        {"ts": 8.0, "rank": 0, "event": "launch_end", "restarts": 1},
    ])
    with open(os.path.join(d, "hb-rank0.json"), "w") as f:
        json.dump({"pid": 11, "rank": 0, "step": 2, "ts": 6.5}, f)
    bdir = os.path.join(d, "crash", "1-20260101T000000")
    os.makedirs(bdir)
    with open(os.path.join(bdir, "MANIFEST.json"), "w") as f:
        json.dump({"ts": 4.2, "rank": 1, "reason": "chaos_kill",
                   "last_step": 2, "pid": 12}, f)
    for rank, v in ((0, 10.0), (1, 30.0)):
        with open(os.path.join(d, "metrics-rank%d.json" % rank), "w") as f:
            json.dump({"ts": 7.0, "metrics": {
                "pt_train_steps_total": {"type": "counter", "series": [
                    {"labels": {}, "value": v}]}}}, f)


class TestAggregate:
    def test_timeline_monotonic_and_complete(self, tmp_path):
        d = str(tmp_path)
        _synthetic_run(d)
        res = aggregate.aggregate_run(d)
        assert res is not None
        evs = run_journal.read_journal(os.path.join(d, "timeline.jsonl"))
        ts = [e["ts"] for e in evs]
        assert ts == sorted(ts)
        srcs = {e["src"] for e in evs}
        assert {"journal-rank0.jsonl", "journal-rank1.jsonl",
                "journal-launch.jsonl", "hb-rank0.json"} <= srcs
        kinds = {e["event"] for e in evs}
        assert {"gang_restart", "heartbeat_last",
                "crash_bundle_found"} <= kinds
        # both ranks interleave: rank1's worker_start (1.5) sits between
        # rank0's worker_start (1.0) and rank0's first step (3.0)
        order = [(e["ts"], e.get("rank")) for e in evs]
        assert order.index((1.5, 1)) == order.index((1.0, 0)) + 1

    def test_reaggregation_is_idempotent(self, tmp_path):
        d = str(tmp_path)
        _synthetic_run(d)
        n1 = aggregate.merge_timeline(d)[1]
        n2 = aggregate.merge_timeline(d)[1]   # timeline must not feed itself
        assert n1 == n2

    def test_rollup_stats_across_ranks(self, tmp_path):
        d = str(tmp_path)
        _synthetic_run(d)
        aggregate.rollup_metrics(d)
        roll = json.load(open(os.path.join(d, "metrics-rollup.json")))
        s = roll["series"]["pt_train_steps_total"]
        assert s["count"] == 2
        assert s["min"] == 10.0 and s["max"] == 30.0
        assert s["mean"] == 20.0
        assert s["p50"] in (10.0, 30.0) and s["p95"] == 30.0

    def test_percentile_nearest_rank(self):
        vals = [1.0, 2.0, 3.0, 4.0]
        assert aggregate.percentile(vals, 0) == 1.0
        assert aggregate.percentile(vals, 100) == 4.0
        assert aggregate.percentile(vals, 50) == 3.0   # round-half-even idx


# ---------------------------------------------------------------- ptdoctor
class TestPtdoctor:
    def _run(self, *argv):
        return subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "ptdoctor.py"),
             *argv], capture_output=True, text=True, timeout=60)

    def test_summary_on_synthetic_run(self, tmp_path):
        d = str(tmp_path)
        _synthetic_run(d)
        r = self._run("summary", d)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "restarts=1" in r.stdout
        assert "reason=chaos_kill" in r.stdout
        assert "last-alive step=2" in r.stdout
        assert "torn_lines=1" in r.stdout

    def test_timeline_and_crash_commands(self, tmp_path):
        d = str(tmp_path)
        _synthetic_run(d)
        r = self._run("timeline", d, "--last", "5")
        assert r.returncode == 0 and "gang_restart" in r.stdout
        r = self._run("crash", d)
        assert r.returncode == 0 and "chaos_kill" in r.stdout

    def test_missing_dir_exits_2(self, tmp_path):
        r = self._run("summary", str(tmp_path / "nope"))
        assert r.returncode == 2
