"""Live telemetry plane (docs/OBSERVABILITY.md "Live endpoints").

The ISSUE 12 contracts:
  * parity — with PADDLE_TPU_HTTP_PORT unset and no explicit port, no
    socket is ever opened and nothing changes on disk;
  * the embedded server: /metrics stays a valid Prometheus exposition
    under concurrent scrapes WHILE a fit is stepping (no torn output),
    /statusz carries rank/trace/train blocks, /journal redacts
    secret-looking values before they leave the process;
  * /healthz flips 503 when the rank's heartbeat goes stale and when a
    serving worker loop crashes — and recovers when the condition
    clears (fresh heartbeat / clean stop());
  * fleet fan-out: endpoint-rank<N>.json discovery + merged /statusz,
    with a dead rank contributing an error entry, not a failure;
  * cross-rank Perfetto export (traceview.py): golden-file determinism
    over a fixed 2-rank journal fixture, >=2 tracks, flow arrows; the
    host profiler shares the same serializer;
  * `ptdoctor trace` / `ptdoctor bench` CLI surfaces.
"""
import json
import math
import os
import re
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.observability import (aggregate, httpd, metrics, spans,
                                      traceview)
from paddle_tpu.observability import journal as run_journal
from paddle_tpu.resilience import health

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "goldens", "traceview_2rank.json")


def _get(url, timeout=5.0):
    """(status, body) — HTTPError bodies (503s) read like any other."""
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, r.read().decode("utf-8")
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode("utf-8")


@pytest.fixture
def plane(monkeypatch):
    """Fresh plane on both sides: no singleton server, no leftover
    probes/providers, no ambient enablement or stale watchdog fires
    (test_resilience trips the process-global counter), and no rank
    from the environment: a server's rank names its endpoint file, and
    `health.configure(rank=)` exports one for the rest of the process."""
    for var in (httpd.ENV_PORT, httpd.ENV_HOST, httpd.ENV_STALE,
                health.ENV_DIR, "PADDLE_TPU_TELEMETRY_DIR",
                "PADDLE_TRAINER_ID"):
        monkeypatch.delenv(var, raising=False)
    metrics.REGISTRY.unregister("pt_watchdog_fires_total")
    httpd.shutdown()
    yield monkeypatch
    httpd.shutdown()
    for name in ("serve_loop", "workers", "boom", "always_down"):
        httpd.unregister_probe(name)
    for name in ("train_loop", "serving_workers", "launch", "extra"):
        httpd.unregister_status(name)


# ----------------------------------------------------------------- parity
class TestParity:
    def test_unset_env_opens_no_socket(self, plane, tmp_path):
        assert httpd.start_from_env(str(tmp_path)) is None
        assert httpd.ensure_server() is None
        assert httpd.active_server() is None
        assert os.listdir(str(tmp_path)) == []

    def test_empty_env_is_disabled(self, plane):
        plane.setenv(httpd.ENV_PORT, "")
        assert httpd.ensure_server() is None

    def test_malformed_port_never_raises(self, plane):
        plane.setenv(httpd.ENV_PORT, "not-a-port")
        assert httpd.ensure_server() is None


# ----------------------------------------------------------------- server
class TestServer:
    def test_routes_endpoint_file_and_stop(self, plane, tmp_path):
        plane.setenv("PADDLE_TRAINER_ID", "3")
        with httpd.TelemetryServer(port=0, rank=3,
                                   endpoint_dir=str(tmp_path)) as srv:
            assert srv.port != 0 and srv.url.startswith("http://127.0.0.1:")
            ep = json.load(open(httpd.endpoint_path(str(tmp_path), 3)))
            assert ep["port"] == srv.port and ep["rank"] == 3
            assert ep["url"] == srv.url

            code, body = _get(srv.url + "/")
            assert code == 200 and "/metrics" in body
            code, body = _get(srv.url + "/metrics")
            assert code == 200 and "pt_http_requests_total" in body
            code, body = _get(srv.url + "/nope")
            assert code == 404

            st = json.loads(_get(srv.url + "/statusz")[1])
            assert st["rank"] == 3 and st["pid"] == os.getpid()
            assert st["trace"] == spans.trace_id()
            assert st["uptime_s"] >= 0
        # stop(): endpoint file gone, socket closed
        assert not os.path.exists(httpd.endpoint_path(str(tmp_path), 3))
        with pytest.raises(urllib.error.URLError):
            urllib.request.urlopen(srv.url + "/", timeout=0.5)

    def test_statusz_providers_and_errors(self, plane, tmp_path):
        httpd.register_status("extra", lambda: {"custom": 42})
        st = httpd.build_status()
        assert st["extra"] == {"custom": 42}
        httpd.register_status("extra", lambda: 1 // 0)
        st = httpd.build_status()
        assert "error" in st["extra"]      # a broken provider, not a 500

    def test_journal_tail_is_redacted(self, plane, tmp_path):
        j = run_journal.RunJournal(str(tmp_path), rank=0)
        prev = run_journal.set_journal(j)
        try:
            run_journal.emit("config", api_key="sekrit-123",
                             lr=0.1, authorization="Bearer abc")
            with httpd.TelemetryServer(port=0, endpoint_dir=None) as srv:
                code, body = _get(srv.url + "/journal?n=10")
        finally:
            run_journal.set_journal(prev)
            j.close()
        assert code == 200
        assert "sekrit-123" not in body and "Bearer abc" not in body
        assert "[REDACTED]" in body
        assert '"lr": 0.1' in body         # non-secrets survive verbatim

    def test_journal_404_without_one(self, plane):
        with httpd.TelemetryServer(port=0, endpoint_dir=None) as srv:
            code, _ = _get(srv.url + "/journal")
        assert code == 404

    def test_redact_line_patterns(self):
        line = json.dumps({"event": "cfg", "hf_token": "abc",
                           "password": "p", "step": 3})
        red = httpd.redact_line(line)
        assert "abc" not in red and '"p"' not in red
        assert '"step": 3' in red

    def test_redact_bearer_and_cookie(self):
        line = json.dumps({"event": "cfg", "bearer": "b-sekrit",
                           "Cookie": "sid=deadbeef",
                           "session_cookie": "c-sekrit",
                           "bearer_auth": "x-sekrit", "step": 7})
        red = httpd.redact_line(line)
        assert "b-sekrit" not in red and "deadbeef" not in red
        assert "c-sekrit" not in red and "x-sekrit" not in red
        assert red.count("[REDACTED]") == 4
        assert '"step": 7' in red

    def test_redact_negative_lookalikes(self):
        # near-miss keys must survive verbatim: redaction is keyed on
        # the KEY, and none of these contain a secret pattern
        line = json.dumps({"event": "cfg", "barrier": "sync-1",
                           "cook_time_s": 12, "bear": "animal",
                           "lr": 0.1})
        assert httpd.redact_line(line) == line

    def test_singleton_ensure_and_shutdown(self, plane, tmp_path):
        srv = httpd.ensure_server(port=0, endpoint_dir=str(tmp_path))
        assert srv is not None
        assert httpd.ensure_server(port=0) is srv       # one per process
        assert httpd.active_server() is srv
        httpd.shutdown()
        assert httpd.active_server() is None


# ---------------------------------------------------------------- healthz
class TestHealthz:
    def test_missing_heartbeat_is_healthy(self, plane, tmp_path):
        plane.setenv(health.ENV_DIR, str(tmp_path))
        res = httpd.check_health()
        assert res["ok"] and res["checks"]["heartbeat"]["ok"]

    def test_stale_heartbeat_flips_503_and_recovers(self, plane, tmp_path):
        plane.setenv(health.ENV_DIR, str(tmp_path))
        plane.setenv("PADDLE_TRAINER_ID", "0")
        plane.setenv(httpd.ENV_STALE, "5")
        hb = health.heartbeat_path(str(tmp_path), 0)
        with open(hb, "w") as f:
            json.dump({"step": 7}, f)
        with httpd.TelemetryServer(port=0, endpoint_dir=None) as srv:
            code, body = _get(srv.url + "/healthz")
            assert code == 200, body
            # age the heartbeat past the threshold: the loop stopped
            old = time.time() - 60
            os.utime(hb, (old, old))
            code, body = _get(srv.url + "/healthz")
            assert code == 503
            checks = json.loads(body)["checks"]
            assert not checks["heartbeat"]["ok"]
            assert "stale" in checks["heartbeat"]["detail"]
            # a fresh tick recovers without a restart
            now = time.time()
            os.utime(hb, (now, now))
            code, _ = _get(srv.url + "/healthz")
            assert code == 200

    def test_watchdog_fire_is_unhealthy(self, plane):
        metrics.counter("pt_watchdog_fires_total",
                        "StepWatchdog timeouts").inc()
        res = httpd.check_health()
        assert not res["ok"] and not res["checks"]["watchdog"]["ok"]
        metrics.REGISTRY.unregister("pt_watchdog_fires_total")

    def test_raising_probe_reads_sick(self, plane):
        httpd.register_probe("boom", lambda: 1 // 0)
        res = httpd.check_health()
        assert not res["ok"]
        assert "probe error" in res["checks"]["boom"]["detail"]
        httpd.unregister_probe("boom")
        assert httpd.check_health()["ok"]


# ----------------------------------------------------- serving loop probe
class _StubEngine:
    def __init__(self, model, **kw):
        pass


class _CrashingBatcher:
    idle = False

    def __init__(self, engine):
        pass

    def step(self):
        raise RuntimeError("injected decode fault")

    def pending_requests(self):
        return []


class TestServingProbe:
    @pytest.mark.filterwarnings(
        "ignore::pytest.PytestUnhandledThreadExceptionWarning")
    def test_crashed_loop_flips_healthz_and_stop_clears(
            self, plane, tmp_path):
        plane.setenv("PADDLE_TPU_FLIGHT_DIR", str(tmp_path))
        from paddle_tpu.inference.serving import server as server_mod
        plane.setattr(server_mod, "GenerationEngine", _StubEngine)
        plane.setattr(server_mod, "ContinuousBatcher", _CrashingBatcher)
        srv = server_mod.InferenceServer(object(), http_port=0)
        srv.start()
        try:
            deadline = time.time() + 10
            while (any(t.is_alive() for t in srv._threads)
                   and time.time() < deadline):
                time.sleep(0.01)
            assert not any(t.is_alive() for t in srv._threads)
            code, body = _get(srv._http.url + "/healthz")
            assert code == 503
            checks = json.loads(body)["checks"]
            assert not checks["serve_loop"]["ok"]
            assert "dead serving worker" in checks["serve_loop"]["detail"]
            url = srv._http.url
        finally:
            srv.stop()
        # a cleanly-stopped server unregisters its probe: not "sick"
        code, _ = _get(url + "/healthz")
        assert code == 200


# ------------------------------------------------------------------ fleet
class TestFleet:
    def test_fleet_status_merges_and_marks_dead(self, plane, tmp_path):
        plane.setenv("PADDLE_TRAINER_ID", "0")
        with httpd.TelemetryServer(port=0,
                                   endpoint_dir=str(tmp_path)):
            # a rank that registered but died: connection refused
            with open(httpd.endpoint_path(str(tmp_path), 1), "w") as f:
                json.dump({"rank": 1, "url": "http://127.0.0.1:1"}, f)
            fl = httpd.fleet_status(str(tmp_path), timeout_s=1.0)
            assert fl["fleet"] and fl["world"] == 2
            assert fl["ranks"]["0"]["rank"] == 0
            assert "error" in fl["ranks"]["1"]
            # the launcher's server answers the same merged view
            with httpd.TelemetryServer(port=0, endpoint_dir=None,
                                       fleet_dir=str(tmp_path)) as fsrv:
                merged = json.loads(_get(fsrv.url + "/statusz")[1])
            assert merged["fleet"] and set(merged["ranks"]) == {"0", "1"}


# ------------------------------------------------------ periodic rollups
class TestPeriodicAggregator:
    def _seed_journal(self, d):
        j = run_journal.RunJournal(str(d), rank=0)
        prev = run_journal.set_journal(j)
        try:
            run_journal.emit("step", step=1)
        finally:
            run_journal.set_journal(prev)
            j.close()

    def test_interval_gating(self, tmp_path):
        self._seed_journal(tmp_path)
        pa = aggregate.PeriodicAggregator(str(tmp_path), interval_s=10,
                                          cause="test")
        assert pa.enabled
        t0 = pa._last
        assert pa.maybe(now=t0 + 5) is None          # too soon
        res = pa.maybe(now=t0 + 11)                  # due: real rollup
        assert res is not None and res["events"] >= 1
        assert os.path.exists(os.path.join(str(tmp_path),
                                           "timeline.jsonl"))
        assert pa.maybe(now=t0 + 12) is None         # interval re-armed

    def test_env_knob_and_disabled_default(self, tmp_path, monkeypatch):
        monkeypatch.delenv(aggregate.ENV_AGG_INTERVAL, raising=False)
        assert not aggregate.PeriodicAggregator(str(tmp_path)).enabled
        monkeypatch.setenv(aggregate.ENV_AGG_INTERVAL, "2.5")
        pa = aggregate.PeriodicAggregator(str(tmp_path))
        assert pa.enabled and pa.interval_s == 2.5
        monkeypatch.setenv(aggregate.ENV_AGG_INTERVAL, "junk")
        assert not aggregate.PeriodicAggregator(str(tmp_path)).enabled
        assert aggregate.PeriodicAggregator(None, interval_s=5).maybe() \
            is None                                  # no dir: never touches disk


# -------------------------------------------------------------- quantiles
class TestHistQuantile:
    def test_linear_interpolation(self):
        cum = [(0.1, 5), (1.0, 10), (math.inf, 10)]
        assert httpd.hist_quantile(cum, 0.5) == pytest.approx(0.1)
        assert httpd.hist_quantile(cum, 0.95) == pytest.approx(0.91)

    def test_inf_bucket_degrades_to_lower_edge(self):
        cum = [(0.1, 0), (math.inf, 10)]
        assert httpd.hist_quantile(cum, 0.5) == pytest.approx(0.1)

    def test_empty_and_zero(self):
        assert httpd.hist_quantile([], 0.5) is None
        assert httpd.hist_quantile([(1.0, 0)], 0.5) is None


# ------------------------------------------------------- trace export
def _write_fixture(d):
    """A fixed 2-rank journal: rank 0 trains (2 threads of spans), rank
    1 serves one request with admit/complete markers. Every timestamp
    is a literal so the export is byte-deterministic (the golden)."""
    r0 = [
        {"event": "span", "ts": 100.020, "dur_ms": 20.0, "name": "step",
         "trace": "gold", "rank": 0, "tid": 1, "attrs": {"step": 1}},
        {"event": "span", "ts": 100.012, "dur_ms": 10.0, "name": "compile",
         "trace": "gold", "rank": 0, "tid": 1, "parent": "step"},
        {"event": "span", "ts": 100.019, "dur_ms": 3.0, "name": "host",
         "trace": "gold", "rank": 0, "tid": 1, "parent": "step"},
        {"event": "span", "ts": 100.018, "dur_ms": 6.0, "name": "feed",
         "trace": "gold", "rank": 0, "tid": 4, "parent": "step"},
    ]
    r1 = [
        {"event": "serve_admit", "ts": 100.025, "rank": 1, "tid": 2,
         "rid": 7, "slot": 0, "prefill_bucket": 8},
        {"event": "span", "ts": 100.030, "dur_ms": 5.0,
         "name": "queue_wait", "trace": "gold", "rank": 1, "tid": 2,
         "parent": "serve_request", "attrs": {"rid": 7}},
        {"event": "span", "ts": 100.040, "dur_ms": 10.0, "name": "prefill",
         "trace": "gold", "rank": 1, "tid": 2, "parent": "serve_request",
         "attrs": {"rid": 7, "bucket": 8}},
        # a prefix-cache hit: serve_suffix covers the SAME interval as
        # prefill (parent=prefill), naming the suffix-only dispatch
        {"event": "span", "ts": 100.040, "dur_ms": 10.0,
         "name": "serve_suffix", "trace": "gold", "rank": 1, "tid": 2,
         "parent": "prefill", "attrs": {"rid": 7, "prefix_len": 8,
                                        "bucket": 8}},
        {"event": "span", "ts": 100.055, "dur_ms": 30.0,
         "name": "serve_request", "trace": "gold", "rank": 1, "tid": 2,
         "attrs": {"rid": 7, "outcome": "completed"}},
        {"event": "serve_complete", "ts": 100.055, "rank": 1, "tid": 3,
         "rid": 7, "ttft_s": 0.01, "latency_s": 0.03, "tokens": 5},
        # a request shed by admission control: serve_shed instant plus a
        # serve_request span with the shed outcome — rendered as an
        # instant WITHOUT a flow arrow (arrows = served traffic only)
        {"event": "serve_shed", "ts": 100.027, "rank": 1, "tid": 3,
         "rid": 9, "reason": "queue_full", "retry_after_s": 0.25,
         "state": "shedding", "queue_depth": 4},
        {"event": "span", "ts": 100.027, "dur_ms": 0.05,
         "name": "serve_request", "trace": "gold", "rank": 1, "tid": 3,
         "attrs": {"rid": 9, "outcome": "shed", "reason": "queue_full"}},
    ]
    for name, recs in (("journal-rank0.jsonl", r0),
                       ("journal-rank1.jsonl", r1)):
        with open(os.path.join(str(d), name), "w") as f:
            for rec in recs:
                f.write(json.dumps(rec) + "\n")


class TestTraceview:
    def test_golden_two_rank_export(self, tmp_path):
        _write_fixture(tmp_path)
        path, n_events, n_tracks = traceview.export_trace(str(tmp_path))
        assert n_tracks >= 2 and n_events > 0
        got = json.load(open(path))
        want = json.load(open(GOLDEN))
        assert got == want
        evs = got["traceEvents"]
        pids = {e["pid"] for e in evs if e["ph"] != "M"}
        assert pids == {0, 1}                    # one pid per rank
        names = {e["args"]["name"] for e in evs
                 if e["ph"] == "M" and e["name"] == "process_name"}
        assert names == {"rank 0", "rank 1"}
        # flow arrow start/finish for the served request — and ONLY the
        # served one: the shed request (rid 9) must not grow arrows
        flows = [e for e in evs if e["ph"] in ("s", "f")]
        assert {e["ph"] for e in flows} == {"s", "f"}
        assert all(e["id"] == 7 for e in flows)
        # the shed request renders as instants: the serve_shed journal
        # marker plus the serve_request span demoted to ph="i"
        shed_evs = [e for e in evs
                    if (e.get("args") or {}).get("rid") == 9
                    or (e.get("args") or {}).get("reason") == "queue_full"]
        assert shed_evs and all(e["ph"] == "i" for e in shed_evs)
        shed_span = [e for e in shed_evs if e["name"] == "serve_request"]
        assert shed_span and shed_span[0]["args"]["outcome"] == "shed"
        assert not any(e["name"] == "serve_shed" and e["ph"] != "i"
                       for e in evs)
        # suffix-prefill admission: serve_suffix slice in the serve cat,
        # nested under prefill over the identical interval
        (sx,) = [e for e in evs if e["name"] == "serve_suffix"]
        (pre,) = [e for e in evs if e["name"] == "prefill"]
        assert sx["ph"] == "X" and sx["cat"] == "serve"
        assert sx["args"]["prefix_len"] == 8
        assert sx["args"]["parent"] == "prefill"
        assert (sx["ts"], sx["dur"]) == (pre["ts"], pre["dur"])
        # slices rebased to t0: earliest start at ts=0
        slices = [e for e in evs if e["ph"] == "X"]
        assert min(e["ts"] for e in slices) == 0.0

    def test_export_empty_dir(self, tmp_path):
        path, n_events, n_tracks = traceview.export_trace(str(tmp_path))
        assert n_events == 0 and n_tracks == 0
        assert json.load(open(path)) == {"traceEvents": [],
                                         "displayTimeUnit": "ms"}

    def test_profiler_shares_the_serializer(self, monkeypatch):
        from paddle_tpu.utils import profiler
        monkeypatch.setattr(profiler, "_native_rec", False)
        monkeypatch.setattr(profiler, "_py_events",
                            [("fwd", 1.0, 0.5, 42, "op")])
        data = json.loads(profiler.export_chrome_trace())
        assert data["displayTimeUnit"] == "ms"
        (ev,) = data["traceEvents"]
        assert ev["name"] == "fwd" and ev["ph"] == "X"
        assert ev["ts"] == 1e6 and ev["dur"] == 5e5
        assert ev["tid"] == 42 and ev["cat"] == "op"


# ----------------------------------------------------------- ptdoctor CLI
class TestPtdoctorCLI:
    def _run(self, *argv):
        return subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "ptdoctor.py"),
             *argv], capture_output=True, text=True, timeout=60)

    def test_trace_exports_and_counts_tracks(self, tmp_path):
        _write_fixture(tmp_path)
        r = self._run("trace", str(tmp_path))
        assert r.returncode == 0, r.stdout + r.stderr
        assert "2 track(s)" in r.stdout or "track(s)" in r.stdout
        out = os.path.join(str(tmp_path), "trace.json")
        evs = json.load(open(out))["traceEvents"]
        assert len({(e["pid"], e["tid"]) for e in evs
                    if e["ph"] != "M"}) >= 2

    def test_trace_empty_dir_exits_2(self, tmp_path):
        r = self._run("trace", str(tmp_path))
        assert r.returncode == 2
        assert "no span events" in r.stdout

    def test_bench_flags_regressions(self, tmp_path):
        rows = [
            ("BENCH_r01.json", {"n": 1, "rc": 0, "parsed": {
                "metric": "toy_tokens_per_sec_per_chip", "value": 100.0,
                "unit": "tok/s", "step_ms": 100.0, "mfu": 0.5}}),
            ("BENCH_r02.json", {"n": 2, "rc": 0, "parsed": {
                "metric": "toy_tokens_per_sec_per_chip", "value": 40.0,
                "unit": "tok/s", "step_ms": 250.0, "mfu": 0.3}}),
            ("BENCH_r03.json", {"n": 3, "rc": 1, "parsed": None}),
        ]
        for name, payload in rows:
            with open(os.path.join(str(tmp_path), name), "w") as f:
                json.dump(payload, f)
        r = self._run("bench", str(tmp_path))
        assert r.returncode == 0, r.stdout + r.stderr
        assert "step_ms REGRESSED" in r.stdout
        assert "mfu REGRESSED" in r.stdout
        assert "failed/unparsed" in r.stdout and "r03" in r.stdout

    def test_bench_empty_dir_exits_2(self, tmp_path):
        assert self._run("bench", str(tmp_path)).returncode == 2


# ------------------------------------------------- live fit integration
_EXPOSITION = re.compile(
    r"^(#.*|[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? "
    r"[-+0-9.eEnaifNI]+)$")


class TestLiveFit:
    def test_concurrent_scrapes_during_fit(self, plane, tmp_path):
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(8, 8), nn.ReLU(), nn.Linear(8, 4))
        model = paddle.Model(net)
        opt = paddle.optimizer.SGD(learning_rate=0.01,
                                   parameters=net.parameters())
        model.prepare(opt, nn.CrossEntropyLoss())
        X = np.random.RandomState(0).rand(16, 8).astype("float32")
        Y = np.zeros((16, 1), np.int64)
        ds = [(X[i], Y[i]) for i in range(16)]

        errors = []

        def run_fit():
            try:
                model.fit(ds, batch_size=8, epochs=1, verbose=0,
                          telemetry_dir=str(tmp_path), telemetry_http=0)
            except BaseException as e:           # surfaced after join
                errors.append(e)

        fit_t = threading.Thread(target=run_fit, daemon=True)
        fit_t.start()
        deadline = time.time() + 30
        while httpd.active_server() is None and time.time() < deadline:
            time.sleep(0.005)
        srv = httpd.active_server()
        assert srv is not None, errors
        url = srv.url

        scraped = []

        def scrape():
            for _ in range(8):
                scraped.append(_get(url + "/metrics"))

        scrapers = [threading.Thread(target=scrape) for _ in range(4)]
        for t in scrapers:
            t.start()
        for t in scrapers:
            t.join(30)
        fit_t.join(120)
        assert not fit_t.is_alive() and not errors, errors

        assert len(scraped) == 32
        for code, body in scraped:
            assert code == 200
            assert body.endswith("\n")           # no torn exposition
            for line in body.rstrip("\n").split("\n"):
                assert _EXPOSITION.match(line), line
        # the span histogram is part of every scrape's exposition
        assert all("pt_span_ms" in body for _, body in scraped)

        # post-fit: endpoint discovery file + /statusz train block
        ep = json.load(open(httpd.endpoint_path(str(tmp_path), 0)))
        assert ep["port"] == srv.port
        st = json.loads(_get(url + "/statusz")[1])
        assert st["train"]["steps_total"] >= 2
        assert st["train_loop"]["active"] is False
        assert st["train_loop"]["step"] == 2
