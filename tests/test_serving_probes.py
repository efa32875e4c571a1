"""What the serving loop says about the device's idle time (ISSUE 36):
the engine's two probes (`pt_serve_enqueue_idle_pct`,
`pt_serve_decode_device_ms`), the loop's line with no holes on the
profiler's clock, the `gc_full` span and the stall counter.

The engine's programs run here on a FAKE DEVICE: a program is enqueued at
once and is done `run_s` of wall time after it started, which is when the
program before it ended or, with nothing in flight, its enqueue. So an
enqueue meets a busy or an idle device, and a read waits or not, exactly
where a test puts it, with no compile. What the engine does around the
programs (`_run`, `_fetch`, the spans) is the real code."""
import collections
import gc
import glob
import os
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.serving import (ContinuousBatcher,
                                          GenerationEngine,
                                          InferenceServer, Request)
from paddle_tpu.inference.serving import engine as engine_mod
from paddle_tpu.inference.serving import server as server_mod
from paddle_tpu.models import gpt_tiny
from paddle_tpu.observability import journal, spans, tracing

RUN_S = 0.05            # one fake program: ten times a loaded CPU's jitter


class FakeArray:
    """A program's output on the fake device: ready at `end`."""

    def __init__(self, end, value, calls):
        self.end, self.value, self.calls = end, value, calls

    def is_ready(self):
        self.calls["is_ready"] += 1
        return time.perf_counter() >= self.end

    def __array__(self, dtype=None, copy=None):
        wait = self.end - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        return self.value


class FakeDevice:
    """Programs one after another, each `run_s` long; `slow[n]` seconds
    more for the n-th decode program (1-based)."""

    def __init__(self, max_batch, run_s=RUN_S, slow=None):
        self.max_batch, self.run_s = max_batch, run_s
        self.slow = dict(slow or {})
        self.free = 0.0
        self.decodes = 0
        self.calls = collections.Counter()

    def _out(self, extra, value):
        start = max(time.perf_counter(), self.free)
        self.free = start + self.run_s + extra
        return FakeArray(self.free, value, self.calls)

    def decode(self, arrs, bufs, key, state, last, live):
        self.decodes += 1
        out = self._out(self.slow.get(self.decodes, 0.0),
                        np.zeros(self.max_batch + 1, np.int32))
        # tokens, then the packed tokens + rows swept (GPT: no routing)
        return state, out, key, out

    def prefill(self, arrs, bufs, key, state, last, ids, true_len, slot):
        out = self._out(0.0, np.ones(1, np.int32))
        return state, out, out, key


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = gpt_tiny(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
                 intermediate_size=64, max_position_embeddings=64)
    m.eval()
    return m


def _on_fake(eng, **kw):
    dev = FakeDevice(eng.max_batch, **kw)
    eng._jit_decode, eng._jit_prefill = dev.decode, dev.prefill
    return dev


@pytest.fixture
def engine(model):
    eng = GenerationEngine(model, max_batch=3, max_seq_len=32,
                           prefill_buckets=(8, 16))
    return eng, _on_fake(eng)


@pytest.fixture
def ring(monkeypatch):
    fresh = collections.deque(maxlen=spans.RING_SIZE)
    monkeypatch.setattr(spans, "_ring", fresh)
    return fresh


@pytest.fixture
def device_ms(monkeypatch):
    """The decode device times observed, in order."""
    seen = []

    class Hist:
        def observe(self, v):
            seen.append(v)
    monkeypatch.setattr(engine_mod, "DECODE_DEVICE_MS", Hist())
    return seen


def _idle(program="decode"):
    h = engine_mod.ENQUEUE_IDLE_PCT.labels(program)
    return h.count, h.sum


# ------------------------------------------------------ the enqueue probe
@pytest.mark.parametrize("late", [False, True])
def test_a_late_loop_reads_idle_100_and_a_top_up_in_time_reads_0(
        engine, late):
    """The loop runs a step ahead: a top-up made while the step before it
    runs finds the device busy; a loop that sleeps past that step's end
    (the injected sleep) finds it idle at every top-up."""
    eng, _ = engine
    b = ContinuousBatcher(eng)
    b.submit(Request(prompt=[1, 2, 3], max_new_tokens=9))
    b.step()                    # admit, read the first token, start
    c0, s0 = _idle()
    n0 = b.steps
    for _ in range(6):
        if late:
            time.sleep(2.5 * RUN_S)
        b.step()
    c1, s1 = _idle()
    assert c1 - c0 == b.steps - n0 == 6       # one top-up a step read
    assert (s1 - s0) / (c1 - c0) == (100.0 if late else 0.0)


def test_an_enqueue_meets_an_idle_device_after_a_read_and_a_busy_one_behind_a_program(
        engine):
    eng, _ = engine
    pc0, ps0 = _idle("prefill")
    c0, s0 = _idle()
    tok = eng.prefill(0, [1, 2, 3])            # nothing in flight: idle
    eng.enqueue_decode()                       # behind the prefill: busy
    int(tok)
    eng.decode()
    eng.enqueue_decode()                       # all read: idle
    eng.enqueue_decode()                       # behind it: busy
    eng.decode()
    eng.decode()
    assert _idle("prefill") == (pc0 + 1, ps0 + 100.0)
    assert _idle() == (c0 + 3, s0 + 100.0)


# ---------------------------------------------- the decode device time
CASES = ["all_four", "read_did_not_wait", "read_before_did_not_wait",
         "program_between", "enqueued_after_the_step_before_ended",
         "enqueued_within_the_launch_time"]


@pytest.mark.parametrize("case", CASES)
def test_decode_device_ms_only_under_its_four_conditions(engine, device_ms,
                                                         case):
    """Step B after step A: observed only where both reads waited, no
    program came between them, B's enqueue returned `LAUNCH_S` or more
    before A was read (so B was on the device before A ended) and both
    are decode steps — and then it is B's read instant less A's."""
    eng, dev = engine
    int(eng.prefill(0, [1, 2, 3]))
    reads = []

    def read():
        eng.decode()
        reads.append(eng._fetched_ts)

    late = case == "enqueued_after_the_step_before_ended"
    eng.enqueue_decode()                               # A
    if late:
        read()                                         # A, waits
        time.sleep(0.5 * RUN_S)                        # A long done
        eng.enqueue_decode()                           # B, device idle
    elif case == "enqueued_within_the_launch_time":
        time.sleep(RUN_S - 0.5 * engine_mod.LAUNCH_S)
        eng.enqueue_decode()                           # B, A nearly done
        read()                                         # A
    else:
        if case == "program_between":
            first = eng.prefill(1, [4, 5])
        eng.enqueue_decode()                           # B, behind A
        if case == "read_before_did_not_wait":
            time.sleep(1.5 * RUN_S)                    # A done, B runs
        read()                                         # A
        if case == "program_between":
            int(first)
    eng.enqueue_decode()                               # C, behind B
    if case == "read_did_not_wait":
        time.sleep(2.5 * RUN_S)                        # B done
    read()                                             # B
    read()                                             # C
    if case == "all_four":
        # B's read less A's, and C's less B's: the fake device's run
        assert device_ms == pytest.approx(
            [(reads[1] - reads[0]) * 1e3, (reads[2] - reads[1]) * 1e3])
        assert device_ms == pytest.approx([RUN_S * 1e3] * 2,
                                          abs=0.2 * RUN_S * 1e3)
    elif case == "read_did_not_wait":
        assert device_ms == []           # B did not, and C's before it
    else:
        # B is not observed; C (behind B, both waited) still is
        assert device_ms == pytest.approx([(reads[2] - reads[1]) * 1e3])


# ------------------------------------------- the probes change nothing
def _schedule(model, on):
    eng = GenerationEngine(model, max_batch=3, max_seq_len=32,
                           prefill_buckets=(8, 16))
    _on_fake(eng, run_s=0.002)
    seen = []
    run, fetch = eng._run, eng._fetch

    def logged_run(jitted, *a):
        seen.append(("enqueue", "decode" if jitted is eng._jit_decode
                     else "prefill"))
        return run(jitted, *a)

    def logged_fetch(tok, parent=None):
        seen.append(("read", parent or "decode"))
        return fetch(tok, parent)
    eng._run, eng._fetch = logged_run, logged_fetch
    rs = np.random.RandomState(3)
    was = tracing.enabled()
    try:
        tracing.enable(on)
        b = ContinuousBatcher(eng)
        for n, m in [(3, 5), (6, 2), (4, 7), (9, 3), (5, 4)]:
            b.submit(Request(prompt=rs.randint(1, 64, (n,)),
                             max_new_tokens=m))
            b.step()
        b.run_until_idle()
    finally:
        tracing.enable(was)
    return seen


def test_probes_on_and_off_give_the_same_enqueues_and_reads(model):
    on, off = _schedule(model, True), _schedule(model, False)
    assert on == off
    assert ("enqueue", "prefill") in on and ("read", "decode") in on


def test_telemetry_off_calls_no_probe_and_registers_no_gc_hook(model):
    eng = GenerationEngine(model, max_batch=3, max_seq_len=32,
                           prefill_buckets=(8, 16))
    dev = _on_fake(eng, run_s=0.002)
    was = tracing.enabled()
    try:
        tracing.enable(False)
        assert spans._on_gc not in gc.callbacks
        b = ContinuousBatcher(eng)
        b.submit(Request(prompt=[1, 2, 3], max_new_tokens=6))
        b.run_until_idle()
        assert dev.calls["is_ready"] == 0
        tracing.enable(True)
        assert gc.callbacks.count(spans._on_gc) == 1
        b.submit(Request(prompt=[1, 2, 3], max_new_tokens=6))
        b.run_until_idle()
        assert dev.calls["is_ready"] > 0
    finally:
        tracing.enable(was)


# ----------------------------------------------------- garbage collection
@pytest.mark.parametrize("generation", [0, 1, 2])
def test_a_full_collection_is_one_gc_full_span_and_all_are_counted(
        ring, generation):
    assert gc.callbacks.count(spans._on_gc) == 1
    counted = spans.GC_SECONDS.labels(str(generation))
    c0 = counted.value
    with spans.span("t_around"):
        gc.collect(generation)
    full = [r for r in ring if r.name == "gc_full"]
    if generation == 2:
        (rec,) = full
        assert rec.parent == "t_around" and "collected" in rec.attrs
        assert counted.value - c0 == pytest.approx(rec.t1 - rec.t0,
                                                   abs=1e-3)
    else:
        assert full == []
    assert counted.value > c0
    assert spans.current() is None


# ---------------------------------------------------------------- stalls
def test_a_150ms_program_gives_one_serve_stall_and_its_seconds(
        model, ring, monkeypatch):
    events = []
    real = journal.emit

    def emit(event, **fields):
        if event == "serve_stall":
            events.append(fields)
        return real(event, **fields)
    monkeypatch.setattr(journal, "emit", emit)
    srv = InferenceServer(model, max_batch=2, max_seq_len=32,
                          prefill_buckets=(8,), workers=1)
    _on_fake(srv.engines[0], run_s=0.002, slow={6: 0.15})
    s0 = server_mod.STALL_SECONDS.value
    with srv:
        srv.submit([1, 2, 3], max_new_tokens=12).result(timeout=60)
    # the iteration that reads the slow step (a loaded CPU may add one)
    (ev,) = [ev for ev in events if ev["ms"] >= 140]
    assert ev["ms"] < 400 and ev["leaf"] == "fetch"
    assert ev["gc_full_ms"] >= 0
    assert server_mod.STALL_SECONDS.value - s0 == pytest.approx(
        sum(e["ms"] for e in events) / 1e3, abs=1e-4)


# --------------------------------------------- on the profiler's clock
def _trace_events(path):
    """{line: [(name, start, end)]} of the host planes on the epoch clock
    (ns), and the trace's start (ns)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    start = None
    for plane in pd.planes:
        start = dict(plane.stats).get("profile_start_time", start)
    out = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.setdefault((plane.name, line.name), []).append(
                    (ev.name, start + ev.start_ns,
                     start + ev.start_ns + ev.duration_ns))
    return out


def _profiled(tmp_path, body):
    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    return _trace_events(path)


def test_a_ring_record_plus_the_epoch_lands_on_its_annotation(
        ring, tmp_path):
    def body():
        for i in range(5):
            with spans.span("t_clock", step=i):
                time.sleep(0.002)
    lines = _profiled(tmp_path, body)
    anns = sorted(ev for evs in lines.values() for ev in evs
                  if ev[0] == "t_clock")
    recs = [r for r in ring if r.name == "t_clock"]
    assert len(anns) == len(recs) == 5
    for r, (_, s, e) in zip(recs, anns):
        assert abs((r.t0 + spans.EPOCH) * 1e9 - s) < 0.5e6
        assert abs((r.t1 + spans.EPOCH) * 1e9 - e) < 0.5e6


def test_the_loops_innermost_spans_cover_its_line(model, ring, tmp_path):
    """Every instant of the loop's thread, between its first annotation
    and its last, lies inside one: waits, admissions, holds and steps."""
    srv = InferenceServer(model, max_batch=2, max_seq_len=32,
                          prefill_buckets=(8,), workers=1)
    _on_fake(srv.engines[0], run_s=0.004)
    rs = np.random.RandomState(5)

    def body():
        hs = []
        for k in range(6):
            hs.append(srv.submit(rs.randint(1, 64, (5,)).tolist(),
                                 max_new_tokens=6))
            time.sleep(0.015 if k % 2 else 0.06)
        for h in hs:
            h.result(timeout=60)
        time.sleep(0.05)
    with srv:
        srv.submit([1, 2, 3], max_new_tokens=3).result(timeout=60)
        lines = _profiled(tmp_path, body)
    (loop,) = [evs for evs in lines.values()
               if any(ev[0] == "turn" for ev in evs)]
    names = {ev[0] for ev in loop}
    assert {"drain", "loop_idle", "submit", "turn", "decode_step",
            "fetch", "harvest"} <= names
    lo, hi = min(ev[1] for ev in loop), max(ev[2] for ev in loop)
    covered, end = 0.0, lo
    for _, s, e in sorted(loop, key=lambda ev: ev[1]):
        if e > end:
            covered += e - max(s, end)
            end = e
    assert hi - lo > 0.15e9
    assert covered / (hi - lo) >= 0.99
