"""Fault-tolerant training runtime (paddle_tpu/resilience/) — tier-1.

Every failure mode these tests exercise is INJECTED deterministically
(resilience.chaos, fake clocks, subprocess kills), so the whole
preemption/retry/watchdog/anomaly surface runs on the CPU mesh:

  * RetryPolicy / with_deadline: bounded tries, hard deadlines, backoff
    determinism;
  * bench.py without a chip exits non-zero and prints no metric line;
  * SIGTERM mid-epoch -> atomic checkpoint -> clean exit -> relaunch
    resumes with the SAME loss trajectory as an uninterrupted run;
  * non-finite loss -> compiled/eager step skipped, params stay finite,
    AnomalyGuard bounds the streak and couples the amp scaler;
  * StepWatchdog diagnostics on a hung dispatch;
  * launcher restart budget.
"""
from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import tempfile

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework.flags import set_flags
from paddle_tpu.resilience import (AnomalyGuard, DeadlineExceeded,
                                   NonFiniteLossError, PreemptionGuard,
                                   RetryExhausted, RetryPolicy, StepWatchdog,
                                   chaos, with_deadline)

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# retry / deadline primitives
# ---------------------------------------------------------------------------

class FakeClock:
    def __init__(self):
        self.t = 0.0
        self.sleeps = []

    def clock(self):
        return self.t

    def sleep(self, s):
        self.sleeps.append(s)
        self.t += s


class TestRetryPolicy:
    def test_unbounded_policy_refused(self):
        with pytest.raises(ValueError):
            RetryPolicy()

    def test_succeeds_after_transient_failures(self):
        fc = FakeClock()
        pol = RetryPolicy(max_tries=5, base_delay=1.0, jitter=0.0,
                          sleep=fc.sleep, clock=fc.clock)
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise OSError("transient")
            return "ok"

        assert pol.call(flaky, retry_on=(OSError,)) == "ok"
        assert len(calls) == 3
        assert fc.sleeps == [1.0, 2.0]   # exponential, deterministic

    def test_exhaustion_chains_last_error(self):
        pol = RetryPolicy(max_tries=3, base_delay=0.0, jitter=0.0,
                          sleep=lambda s: None)
        with pytest.raises(RetryExhausted) as ei:
            pol.call(lambda: (_ for _ in ()).throw(ValueError("root")),
                     retry_on=(ValueError,))
        assert isinstance(ei.value.last_error, ValueError)
        assert pol.tries == 3

    def test_deadline_bounds_total_wall_clock(self):
        fc = FakeClock()
        pol = RetryPolicy(max_tries=100, base_delay=10.0, multiplier=1.0,
                          jitter=0.0, deadline_s=35.0,
                          sleep=fc.sleep, clock=fc.clock)
        attempts = [a for a in pol.attempts()]
        # sleeps 10,10,10 land at t=30; the next retry would start past
        # the 35s budget (sleep clipped to 5 -> expired) => 4 attempts
        assert len(attempts) == 4
        assert fc.t <= 35.0 + 1e-9

    def test_sleep_clipped_to_remaining(self):
        fc = FakeClock()
        pol = RetryPolicy(max_tries=10, base_delay=100.0, jitter=0.0,
                          deadline_s=30.0, sleep=fc.sleep, clock=fc.clock)
        assert len(list(pol.attempts())) == 1  # second try never starts
        assert fc.sleeps and fc.sleeps[0] <= 30.0

    def test_backoff_jitter_deterministic_per_seed(self):
        a = [RetryPolicy(max_tries=5, seed=3).backoff(i) for i in (1, 2, 3)]
        b = [RetryPolicy(max_tries=5, seed=3).backoff(i) for i in (1, 2, 3)]
        assert a == b


class TestWithDeadline:
    def test_fast_call_returns(self):
        assert with_deadline(lambda: 7, 5.0) == 7

    def test_slow_call_raises(self):
        import time
        with pytest.raises(DeadlineExceeded):
            with_deadline(time.sleep, 0.15, 10.0, context="nap")

    def test_error_propagates(self):
        with pytest.raises(KeyError):
            with_deadline(lambda: {}["missing"], 5.0)


# ---------------------------------------------------------------------------
# chaos injection + bench resilience
# ---------------------------------------------------------------------------

class TestChaos:
    def setup_method(self):
        chaos.reset()

    def teardown_method(self):
        chaos.reset()

    def test_spec_parse(self):
        chaos.configure("sigterm_at_step:7;nan_at_step:3")
        assert chaos.enabled()
        assert chaos.nan_at_step() == 3
        assert chaos.get("sigterm_at_step") == (7.0,)

    def test_bad_spec_raises(self):
        with pytest.raises(ValueError):
            chaos.configure("nan_at_step:xyz")
        chaos.reset()


def test_bench_without_a_chip_exits_nonzero_and_prints_no_metric():
    """No probe campaign, no banked headline, no CPU fallback: without a
    TPU bench.py fails, and nothing a driver could parse as a result
    reaches stdout."""
    out = subprocess.run([sys.executable, os.path.join(_ROOT, "bench.py")],
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=120,
                         cwd=_ROOT)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert "{" not in out.stdout and "metric" not in out.stdout


# ---------------------------------------------------------------------------
# preemption: guard semantics + full kill/resume round trip
# ---------------------------------------------------------------------------

class TestPreemptionGuard:
    def test_sigterm_sets_flag_not_death(self):
        with PreemptionGuard() as guard:
            os.kill(os.getpid(), signal.SIGTERM)
            assert guard.triggered and guard.signum == signal.SIGTERM
        # handlers restored on exit
        assert PreemptionGuard._installed is None

    def test_callbacks_run_and_broken_hook_tolerated(self):
        seen = []
        with PreemptionGuard() as guard:
            guard.add_callback(lambda s: (_ for _ in ()).throw(OSError()))
            guard.add_callback(seen.append)
            guard.trigger()
        assert seen == [signal.SIGTERM]

    def test_nested_install_is_noop(self):
        with PreemptionGuard() as outer:
            inner = PreemptionGuard().install()
            assert PreemptionGuard._installed is outer
            inner.uninstall()   # must not steal the outer's handlers
            assert PreemptionGuard._installed is outer


def _run_trainee(ckpt_dir, log_path, chaos_spec=None, timeout=240):
    env = dict(os.environ, TRAINEE_EPOCHS="2", TRAINEE_BATCH="4")
    env.pop("PADDLE_TPU_CHAOS", None)
    if chaos_spec:
        env["PADDLE_TPU_CHAOS"] = chaos_spec
    return subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tests",
                                      "resilience_trainee.py"),
         ckpt_dir, log_path],
        env=env, capture_output=True, text=True, timeout=timeout,
        cwd=_ROOT)


def _losses(log_path):
    with open(log_path) as f:
        return [json.loads(ln)["loss"] for ln in f if ln.strip()]


def test_sigterm_kill_then_resume_keeps_loss_trajectory(tmp_path):
    """Acceptance: a Model.fit killed by SIGTERM mid-epoch exits cleanly
    with an auto-checkpoint; the relaunched fit resumes from it and the
    combined loss log EQUALS an uninterrupted run's — trajectory
    continuity, not just 'it restarted'."""
    # reference run: no faults
    ref_log = str(tmp_path / "ref.jsonl")
    ref = _run_trainee(str(tmp_path / "ck_ref"), ref_log)
    assert ref.returncode == 0 and "TRAINEE_DONE" in ref.stdout, \
        ref.stderr[-800:]
    ref_losses = _losses(ref_log)
    assert len(ref_losses) == 16   # 2 epochs x 8 steps

    # run B part 1: real SIGTERM injected at global step 5 (mid-epoch 0)
    ck = str(tmp_path / "ck_b")
    b_log = str(tmp_path / "b.jsonl")
    part1 = _run_trainee(ck, b_log, chaos_spec="sigterm_at_step:5")
    assert part1.returncode == 0, part1.stderr[-800:]      # CLEAN exit
    assert "TRAINEE_DONE" not in part1.stdout              # but not done
    from paddle_tpu.checkpoint import store as ckpt_store
    assert ckpt_store.is_complete(os.path.join(ck, "preempt_ckpt"))
    assert len(_losses(b_log)) == 6                        # steps 0..5

    # run B part 2: relaunch, auto-resume
    part2 = _run_trainee(ck, b_log)
    assert part2.returncode == 0 and "TRAINEE_DONE" in part2.stdout, \
        part2.stderr[-800:]
    b_losses = _losses(b_log)
    assert len(b_losses) == 16
    np.testing.assert_allclose(b_losses, ref_losses, rtol=1e-4)
    # completed run cleans its preemption checkpoint
    assert not os.path.exists(os.path.join(ck, "preempt_ckpt"))


def test_fit_in_process_preempt_and_resume():
    """In-process variant (exit_on_preempt=False): the same machinery
    without subprocesses, including checkpoint cleanup on completion."""
    paddle.seed(11)
    rs = np.random.RandomState(3)
    X = rs.randn(16, 4).astype(np.float32)
    Y = rs.randn(16, 2).astype(np.float32)
    ds = [(X[i], Y[i]) for i in range(16)]

    with tempfile.TemporaryDirectory() as d:
        net = paddle.nn.Linear(4, 2)
        opt = paddle.optimizer.SGD(learning_rate=0.05,
                                   parameters=net.parameters())
        m = paddle.Model(net)
        m.prepare(opt, paddle.nn.MSELoss(), jit=True)
        chaos.configure("sigterm_at_step:2")
        try:
            m.fit(ds, batch_size=4, epochs=2, shuffle=False, verbose=0,
                  auto_checkpoint_dir=d, exit_on_preempt=False)
        finally:
            chaos.reset()
        assert m.preempted
        from paddle_tpu.checkpoint import store as ckpt_store
        assert ckpt_store.is_complete(os.path.join(d, "preempt_ckpt"))

        m2 = paddle.Model(net)
        m2.prepare(opt, paddle.nn.MSELoss(), jit=True)
        m2.fit(ds, batch_size=4, epochs=2, shuffle=False, verbose=0,
               auto_checkpoint_dir=d, exit_on_preempt=False)
        assert not m2.preempted
        assert not os.path.exists(os.path.join(d, "preempt_ckpt"))


def test_train_epoch_range_stops_at_boundary_on_preempt(tmp_path):
    from paddle_tpu.incubate.checkpoint import TrainEpochRange
    tr = TrainEpochRange(5, "preempt_job", checkpoint_dir=str(tmp_path))
    net = paddle.nn.Linear(2, 2)
    done = []
    for e in tr.get():
        done.append(e)
        tr.save(layer=net)
        if e == 1:
            os.kill(os.getpid(), signal.SIGTERM)  # guard owned by tr.get()
    assert done == [0, 1]
    assert tr.preempted
    # relaunch resumes AFTER the last saved epoch
    tr2 = TrainEpochRange(5, "preempt_job", checkpoint_dir=str(tmp_path))
    assert tr2.restored_epoch == 1
    assert list(tr2.get()) == [2, 3, 4]


# ---------------------------------------------------------------------------
# non-finite step skip + anomaly guard
# ---------------------------------------------------------------------------

def _one_batch_model(jit):
    paddle.seed(5)
    net = paddle.nn.Linear(4, 2)
    opt = paddle.optimizer.SGD(learning_rate=0.05,
                               parameters=net.parameters())
    m = paddle.Model(net)
    m.prepare(opt, paddle.nn.MSELoss(), jit=jit)
    rs = np.random.RandomState(9)
    return m, net, rs.randn(4, 4).astype(np.float32), \
        rs.randn(4, 2).astype(np.float32)


@pytest.mark.parametrize("jit", [True, False])
def test_nan_step_skipped_params_survive(jit):
    m, net, X, Y = _one_batch_model(jit)
    set_flags({"skip_nonfinite_steps": True})
    chaos.configure("nan_at_step:2")  # second optimizer step goes NaN
    try:
        skips, losses = [], []
        for _ in range(4):
            if jit:
                logs = m.train_batch([X], [Y])
            else:
                # eager injection: poison the loss via the input instead
                if len(losses) == 1:
                    logs = m.train_batch([X * np.nan], [Y])
                else:
                    logs = m.train_batch([X], [Y])
            losses.append(logs["loss"])
            skips.append(m.last_step_skipped)
    finally:
        chaos.reset()
        set_flags({"skip_nonfinite_steps": False})
    assert skips[1] and not skips[0] and not skips[2]
    w = np.asarray(net.weight._data)
    assert np.isfinite(w).all()
    # training continued: loss after the skip keeps decreasing
    assert losses[3] < losses[0]


def test_anomaly_guard_bounds_streak_and_couples_scaler():
    class FakeScaler:
        _enable = True

        def __init__(self):
            self._found_inf = False
            self.updates = 0

        def update(self):
            self.updates += 1

    sc = FakeScaler()
    g = AnomalyGuard(max_consecutive=3, scaler=sc)
    assert not g.observe(1.0)
    assert g.observe(float("nan"))
    assert g.observe(2.0, skipped=True)   # explicit skip flag wins
    assert not g.observe(0.5)             # streak resets
    assert sc.updates == 2 and sc._found_inf
    g.observe(float("inf"))
    g.observe(float("nan"))
    with pytest.raises(NonFiniteLossError):
        g.observe(float("nan"))
    assert g.total_skipped == 5 and g.total_steps == 7


# ---------------------------------------------------------------------------
# step watchdog
# ---------------------------------------------------------------------------

class TestStepWatchdog:
    def test_fires_on_hang_and_dumps_diagnostics(self, tmp_path):
        import time
        diag = str(tmp_path / "wd.txt")
        fired = []
        with StepWatchdog(0.1, context="test hang", diag_path=diag,
                          on_fire=lambda: fired.append(1)) as wd:
            time.sleep(0.4)
        assert wd.fired and fired == [1]
        text = open(diag).read()
        assert "StepWatchdog" in text and "test hang" in text

    def test_quiet_on_fast_step(self):
        with StepWatchdog(30.0, context="fast") as wd:
            pass
        assert not wd.fired

    def test_engine_hang_injection_trips_watchdog(self, tmp_path,
                                                  monkeypatch):
        """chaos hang_at_step under FLAGS_step_watchdog_s: the compiled
        dispatch stalls and the watchdog reports it (action=warn keeps the
        step running; the dump lands in PADDLE_TPU_WATCHDOG_FILE)."""
        diag = str(tmp_path / "engine_wd.txt")
        monkeypatch.setenv("PADDLE_TPU_WATCHDOG_FILE", diag)
        m, net, X, Y = _one_batch_model(jit=True)
        set_flags({"step_watchdog_s": 0.2,
                   "step_watchdog_action": "warn"})
        chaos.configure("hang_at_step:2:0.6")
        try:
            m.train_batch([X], [Y])      # step 1: compile (may be slow)
            m.train_batch([X], [Y])      # step 2: hangs 0.6s > 0.2s
        finally:
            chaos.reset()
            set_flags({"step_watchdog_s": 0.0,
                       "step_watchdog_action": "warn"})
        assert os.path.exists(diag)
        assert "compiled train step 2" in open(diag).read()


# ---------------------------------------------------------------------------
# bootstrap + launcher
# ---------------------------------------------------------------------------

def test_init_parallel_env_bootstrap_retries_are_bounded(monkeypatch):
    from paddle_tpu.distributed import env as denv
    calls = []

    def always_down(**kw):
        calls.append(kw)
        raise RuntimeError("coordinator unreachable")

    import jax
    monkeypatch.setattr(jax.distributed, "initialize", always_down)
    monkeypatch.setenv("PADDLE_COORDINATOR_ADDRESS", "127.0.0.1:1")
    monkeypatch.setenv("PADDLE_TPU_BOOTSTRAP_TRIES", "2")
    monkeypatch.setenv("PADDLE_TPU_BOOTSTRAP_DEADLINE_S", "5")
    monkeypatch.setattr(denv, "_initialized", False)
    monkeypatch.setattr(denv, "_global_env", None)
    with pytest.raises(RetryExhausted):
        denv.init_parallel_env()
    assert len(calls) == 2
    assert not denv._initialized


def test_launcher_restart_budget(tmp_path):
    """A worker that crashes once is respawned (--max_restarts=1) and the
    launch then succeeds; with the budget exhausted the launch fails."""
    from paddle_tpu.distributed.launch import _parse_args, launch_collective
    marker = tmp_path / "crashed_once"
    script = tmp_path / "worker.py"
    script.write_text(
        "import os, sys\n"
        "m = %r\n"
        "if not os.path.exists(m):\n"
        "    open(m, 'w').close()\n"
        "    sys.exit(3)\n"
        "sys.exit(0)\n" % str(marker))

    os.environ["PADDLE_LAUNCH_MAX_RESTARTS"] = "1"
    try:
        args = _parse_args(["--nproc_per_node", "1", str(script)])
    finally:
        del os.environ["PADDLE_LAUNCH_MAX_RESTARTS"]
    assert args.max_restarts == 1
    rc = launch_collective(args)
    assert rc == 0 and marker.exists()

    marker.unlink()
    args = _parse_args(["--nproc_per_node", "1", "--max_restarts", "0",
                        str(script)])
    assert launch_collective(args) != 0


# ---------------------------------------------------------------------------
# distributed health protocol: backoff clamp, heartbeats, rank faults
# ---------------------------------------------------------------------------

class TestBackoffClamp:
    def test_attempt_index_clamped_to_schedule(self):
        """The launcher calls backoff(n) with n up to max_tries; indices
        past the schedule must saturate, not raise or overflow."""
        pol = RetryPolicy(max_tries=3, base_delay=1.0, multiplier=2.0,
                          max_delay=30.0, jitter=0.0)
        assert pol.backoff(10) == pol.backoff(3) == 4.0
        assert pol.backoff(10 ** 6) == 4.0      # no float-exponent overflow

    def test_unclamped_runaway_index_saturates_at_max_delay(self):
        pol = RetryPolicy(deadline_s=60.0, base_delay=1.0, multiplier=2.0,
                          max_delay=30.0, jitter=0.0)   # no max_tries
        assert pol.backoff(10 ** 6) == 30.0     # OverflowError swallowed


class TestHeartbeat:
    def teardown_method(self):
        from paddle_tpu.resilience import health
        health.reset()
        os.environ.pop(health.ENV_INTERVAL, None)

    def test_write_read_and_staleness(self, tmp_path):
        from paddle_tpu.resilience import health
        hb = health.HeartbeatWriter(str(tmp_path), rank=3, min_interval_s=0)
        assert hb.tick(step=17)
        rec = health.read_heartbeat(health.heartbeat_path(str(tmp_path), 3))
        assert rec == {"pid": os.getpid(), "rank": 3, "step": 17,
                       "ts": pytest.approx(rec["ts"])}
        stale = health.stale_seconds(hb.path)
        assert stale is not None and 0.0 <= stale < 5.0
        # missing file: no heartbeat yet is None, never "very stale"
        assert health.stale_seconds(str(tmp_path / "absent.json")) is None

    def test_rate_limit_and_force(self, tmp_path):
        from paddle_tpu.resilience import health
        hb = health.HeartbeatWriter(str(tmp_path), rank=0,
                                    min_interval_s=3600.0)
        assert hb.tick(step=1)              # first tick always writes
        assert not hb.tick(step=2)          # inside the interval: dropped
        assert hb.tick(step=3, force=True)  # force defeats the limiter
        rec = health.read_heartbeat(hb.path)
        assert rec["step"] == 3
        assert hb.ticks_written == 2

    def test_corrupt_file_reads_as_none(self, tmp_path):
        from paddle_tpu.resilience import health
        p = tmp_path / "hb-rank0.json"
        p.write_text("{not json")
        assert health.read_heartbeat(str(p)) is None

    def test_env_configured_module_tick(self, tmp_path, monkeypatch):
        from paddle_tpu.resilience import health
        health.reset()
        assert not health.tick(1)           # unset env: cheap no-op
        monkeypatch.setenv(health.ENV_INTERVAL, "0")
        health.configure(str(tmp_path), rank=5)
        assert health.tick(9)
        rec = health.read_heartbeat(health.heartbeat_path(str(tmp_path), 5))
        assert rec["rank"] == 5 and rec["step"] == 9
        # step carries over when a later tick has no step argument
        assert health.tick()
        assert health.read_heartbeat(health.heartbeat_path(
            str(tmp_path), 5))["step"] == 9


class TestRankFaults:
    def setup_method(self):
        chaos.reset()
        os.environ.pop("PADDLE_TPU_RESTART_ROUND", None)

    def teardown_method(self):
        chaos.reset()
        os.environ.pop("PADDLE_TPU_RESTART_ROUND", None)

    def test_wrong_rank_and_wrong_step_no_op(self):
        chaos.configure("kill_rank:1:2")
        # rank 0 never fires; rank 1 only fires at step 2 — were the hook
        # to fire here the test process would die, so surviving IS the
        # assertion
        chaos.rank_fault_hook(0, 2)
        chaos.rank_fault_hook(1, 1)
        chaos.rank_fault_hook(1, 3)

    def test_restart_round_guard_disarms_faults(self):
        chaos.configure("kill_rank:0:2;hang_rank:0:2:5")
        os.environ["PADDLE_TPU_RESTART_ROUND"] = "1"
        chaos.rank_fault_hook(0, 2)         # armed fault, disarmed round

    def test_hang_rank_sleeps_once(self):
        import time
        chaos.configure("hang_rank:0:1:0.05")
        t0 = time.monotonic()
        chaos.rank_fault_hook(0, 1)
        assert time.monotonic() - t0 >= 0.05
        t1 = time.monotonic()
        chaos.rank_fault_hook(0, 1)         # one-shot: consumed
        assert time.monotonic() - t1 < 0.05
