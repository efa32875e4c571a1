"""The serving loop's spans and per-token instants (docs/OBSERVABILITY.md
"The serving loop's spans", docs/SERVING.md `Request.token_ts`).

On the tiny CPU model: every span is a record with a start and an end,
request spans carry their `rid` and sit inside their parent, loop spans
carry the batcher's step number, per-token instants reproduce TTFT,
latency and the inter-token histogram exactly on an injected clock, no
program's tokens are fetched before the program behind it is enqueued
(`pt_serve_ahead_pct` says so), the host gaps stop at an idle wait and
at a hold, the ring is bounded, the kill switch records nothing, and the
same names sit on the host line of a `jax.profiler` trace."""
import collections
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
from paddle_tpu.inference.serving import scheduler
from paddle_tpu.models import gpt_tiny
from paddle_tpu.observability import spans, tracing

LOOP_SPANS = {"decode_step", "harvest", "drain", "loop_idle", "hold",
              "first_tokens",
              "host_gap_decode", "host_gap_prefill"}


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = gpt_tiny(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
                 intermediate_size=64, max_position_embeddings=64)
    m.eval()
    return m


@pytest.fixture(scope="module")
def engine(model):
    return GenerationEngine(model, max_batch=3, max_seq_len=32,
                            prefill_buckets=(8, 16))


@pytest.fixture
def ring(monkeypatch):
    """A fresh ring of the real size, so a test sees only its own spans."""
    fresh = collections.deque(maxlen=spans.RING_SIZE)
    monkeypatch.setattr(spans, "_ring", fresh)
    return fresh


class TickClock:
    """Every reading is one millisecond after the one before."""

    def __init__(self):
        self.t = 100.0

    def __call__(self):
        self.t += 0.001
        return self.t


def _requests(n, max_new, seed=0):
    rs = np.random.RandomState(seed)
    return [Request(prompt=rs.randint(1, 64, (rs.randint(3, 12),)),
                    max_new_tokens=m)
            for m in (max_new if isinstance(max_new, list)
                      else [max_new] * n)]


def _run_batcher(engine, reqs, clock=None):
    engine.note_idle()
    kw = {} if clock is None else {"clock": clock}
    b = ContinuousBatcher(engine, **kw)
    for r in reqs:
        b.submit(r)
    b.run_until_idle()
    return b


def _hist(h):
    return h.count, h.sum


# ---------------------------------------------------- a span is a record
def test_request_spans_carry_rid_start_end_and_sit_inside_the_parent(
        engine, ring):
    reqs = _requests(5, [4, 2, 5, 3, 1], seed=1)
    _run_batcher(engine, reqs, clock=TickClock())
    recs = spans.recent()
    assert recs and all(r.t0 <= r.t1 for r in recs)
    assert len({r.trace for r in recs}) == 1
    by_rid = {}
    for r in recs:
        if "rid" in r.attrs:
            by_rid.setdefault(r.attrs["rid"], []).append(r)
    assert set(by_rid) == {q.rid for q in reqs}
    for q in reqs:
        mine = {r.name: r for r in by_rid[q.rid]}
        want = {"serve_request", "queue_wait", "prefill"}
        if q.max_new_tokens > 1:
            want.add("decode_steps")
        assert want <= set(mine)
        root = mine["serve_request"]
        assert root.parent is None
        assert (root.t0, root.t1) == (q.submit_ts, q.token_ts[-1])
        for name in want - {"serve_request"}:
            kid = mine[name]
            assert kid.parent == "serve_request"
            assert root.t0 <= kid.t0 <= kid.t1 <= root.t1, name
        # the children tile the request: wait, then prefill, then decode
        assert mine["queue_wait"].t1 == mine["prefill"].t0
        assert mine["prefill"].t1 == q.token_ts[0]
        if "decode_steps" in mine:
            assert mine["decode_steps"].t0 == mine["prefill"].t1
            assert mine["decode_steps"].t1 == root.t1
    # the engine's dispatch nests in the span that called it (a cold
    # start's first step is enqueued where its first tokens are read) and
    # inherits the loop step (and, under a prefill, the request)
    for r in recs:
        if r.name in ("dispatch", "compile"):
            assert r.parent in ("prefill", "decode_step", "first_tokens")
            assert "step" in r.attrs
            assert ("rid" in r.attrs) == (r.parent == "prefill")


def test_loop_spans_carry_the_step_and_count_the_batchers_steps(
        engine, ring):
    occ0 = _hist(scheduler.OCCUPANCY_PCT)
    b = _run_batcher(engine, _requests(4, [3, 6, 2, 4], seed=2))
    recs = spans.recent()
    assert b.steps > 0
    for name in ("decode_step", "harvest"):
        mine = [r for r in recs if r.name == name]
        assert [r.attrs["step"] for r in mine] == \
            list(range(1, b.steps + 1)), name
    occ1 = _hist(scheduler.OCCUPANCY_PCT)
    assert occ1[0] - occ0[0] == b.steps
    assert 0 < occ1[1] - occ0[1] <= 100.0 * b.steps
    assert all("step" in r.attrs for r in recs if r.name in LOOP_SPANS)
    # harvest starts at the instant its step's tokens were fetched
    dec = {r.attrs["step"]: r for r in recs if r.name == "decode_step"}
    for h in (r for r in recs if r.name == "harvest"):
        assert h.t0 == dec[h.attrs["step"]].t1
    # one blocking fetch a program, inside a span of the kind that ran
    # it (its own `decode_step`; a first token's `prefill`, which other
    # admissions' spans overlap) and after a dispatch of that kind
    n_pre = len([r for r in recs if r.name == "prefill"])
    fetches = [r for r in recs if r.name == "fetch"]
    assert len(fetches) == b.steps + n_pre
    assert sum(r.parent == "decode_step" for r in fetches) == b.steps
    assert sum(r.parent == "prefill" for r in fetches) == n_pre
    outer = [r for r in recs if r.name in ("decode_step", "prefill")]
    sent = [r for r in recs if r.name in ("dispatch", "compile")]
    for f in fetches:
        inside = sum(o.name == f.parent and o.t0 <= f.t0 <= f.t1 <= o.t1
                     for o in outer)
        assert inside == 1 if f.parent == "decode_step" else inside >= 1
        assert any(d.t1 <= f.t0 and d.parent in (f.parent, "first_tokens")
                   for d in sent)
    # the loop runs ahead: programs are read in the order enqueued, and
    # every program but the last is read AFTER the one behind it was
    # enqueued (the schedule still owes a token until the last step)
    assert len(sent) == len(fetches)
    sent.sort(key=lambda r: r.t0)
    fetches.sort(key=lambda r: r.t0)
    for k, f in enumerate(fetches[:-1]):
        assert sent[k].t1 <= sent[k + 1].t1 <= f.t0, k
    # a host gap runs from a fetch to the FIRST enqueue after it, named
    # by that program; a second enqueue with no fetch between has none.
    # Here: the second step, enqueued once the cold start's first tokens
    # are read; the iteration that admits the fourth request (its prefill
    # is the first enqueue); and the two that only top up
    gaps = [r for r in recs if r.name.startswith("host_gap_")]
    assert len({g.t0 for g in gaps}) == len(gaps) < len(fetches)
    assert {g.t0 for g in gaps} <= {f.t1 for f in fetches}
    assert sorted(g.name for g in gaps) == [
        "host_gap_decode"] * 3 + ["host_gap_prefill"]


def _ahead():
    return engine_mod.AHEAD_PCT.count, engine_mod.AHEAD_PCT.sum


def test_ahead_pct_is_100_while_a_program_is_behind_the_step_read(
        engine, ring):
    """One observation a `decode()` return. In a closed loop that always
    has a request waiting, every step is read with the next program in
    flight behind it; the last step of the drain, and a lone step after
    an idle wait, have nothing behind them."""
    engine.note_idle()
    b = ContinuousBatcher(engine)
    for q in _requests(9, 4, seed=7):
        b.submit(q)
    seen = []
    while not b.idle:
        c0, s0 = _ahead()
        steps0 = b.steps
        b.step()
        c1, s1 = _ahead()
        assert c1 - c0 == b.steps - steps0 <= 1
        if c1 > c0:
            seen.append(s1 - s0)
    assert len(seen) == b.steps >= 9
    assert seen[:-1] == [100.0] * (b.steps - 1) and seen[-1] == 0.0
    # after an idle wait: a request that needs one decode step
    engine.note_idle()
    c0, s0 = _ahead()
    (q,) = _requests(1, 2, seed=8)
    b.submit(q)
    b.run_until_idle()
    assert len(q.tokens) == 2
    assert _ahead() == (c0 + 1, s0)
    # the engine driven a program at a time (no `enqueue_decode()`)
    # reads every step with nothing behind it
    int(engine.prefill(0, q.prompt))
    engine.decode()
    assert _ahead() == (c0 + 2, s0)


# ------------------------------------------------- per-token instants
def test_token_instants_give_ttft_latency_and_itl_exactly(engine, ring):
    reqs = _requests(3, [5, 1, 7], seed=3)
    clock = TickClock()
    engine.note_idle()
    b = ContinuousBatcher(engine, clock=clock)
    for q in reqs:
        itl0 = _hist(scheduler.ITL_MS)
        b.submit(q)
        b.run_until_idle()
        assert len(q.token_ts) == len(q.tokens) == q.max_new_tokens
        assert q.token_ts == sorted(q.token_ts)
        assert q.token_ts[0] - q.submit_ts == q.ttft_s
        assert q.token_ts[-1] - q.submit_ts == q.latency_s
        itl1 = _hist(scheduler.ITL_MS)
        assert itl1[0] - itl0[0] == len(q.tokens) - 1
        assert (itl1[1] - itl0[1]) / 1e3 == pytest.approx(
            q.latency_s - q.ttft_s, abs=1e-9)


# ------------------------------------------------------- the host gaps
def test_no_host_gap_is_recorded_across_an_idle_wait(model, ring):
    srv = InferenceServer(model, max_batch=2, max_seq_len=32,
                          prefill_buckets=(8,), workers=1)
    rs = np.random.RandomState(4)
    with srv:
        for _ in range(3):          # the loop goes idle between these
            srv.submit(rs.randint(1, 64, (5,)).tolist(),
                       max_new_tokens=5).result(timeout=120)
            time.sleep(0.05)
    recs = spans.recent()
    idle = [r for r in recs if r.name == "loop_idle"]
    assert len(idle) >= 3
    assert [r for r in recs if r.name == "drain"]
    # nor across a hold: where one step is in flight and a slot is free
    # the loop may wait for an arrival before it tops up (`hold_s`)
    waits = idle + [r for r in recs if r.name == "hold"]
    gaps = [r for r in recs if r.name.startswith("host_gap_")]
    assert gaps
    for g in gaps:
        for i in waits:
            assert not (g.t0 < i.t1 and i.t0 < g.t1), (g, i)
    # each request was admitted straight after an idle wait: its prefill
    # and the two decode steps enqueued behind it follow no fetch, so the
    # only gaps are before the third and the fourth decode step, where
    # the loop did not hold (it cannot before a step's run is measured:
    # the very first third step has its gap)
    assert {g.name for g in gaps} == {"host_gap_decode"}
    steps = len([r for r in recs if r.name == "decode_step"])
    assert steps == 3 * 4 and 1 <= len(gaps) <= 3 * 2
    assert all(r.attrs["step"] >= 3 for r in recs if r.name == "hold")


def test_an_arrival_during_a_hold_goes_in_front_of_the_next_step(
        model, ring, monkeypatch):
    """The loop waits on its queue while `hold_s()` says the top-up can
    wait (here: 50 ms whenever one step is in flight alone, so the wait
    is sure to be met); a request that comes meanwhile ends the hold, is
    admitted, and its prefill is the next program enqueued."""
    def hold_s(self):
        alone = self._steps_ahead == 1 and len(self._flight) == 1
        return 0.05 if alone and any(self.slots) else 0.0
    monkeypatch.setattr(ContinuousBatcher, "hold_s", hold_s)
    srv = InferenceServer(model, max_batch=2, max_seq_len=32,
                          prefill_buckets=(8,), workers=1)
    rs = np.random.RandomState(9)
    with srv:
        # compile first: the holds below are timed against a sleep
        srv.submit(rs.randint(1, 64, (5,)).tolist(),
                   max_new_tokens=2).result(timeout=120)
        time.sleep(0.05)
        ring.clear()
        first = srv.submit(rs.randint(1, 64, (5,)).tolist(),
                           max_new_tokens=8)
        time.sleep(0.12)            # two or three holds in
        late = srv.submit(rs.randint(1, 64, (6,)).tolist(),
                          max_new_tokens=3)
        assert len(late.result(timeout=120)) == 3
        assert len(first.result(timeout=120)) == 8
    recs = spans.recent()
    holds = [r for r in recs if r.name == "hold"]
    assert len(holds) >= 3
    (ended,) = [h for h in holds
                if h.t0 < late.request.submit_ts <= h.t1]
    assert ended.t1 - late.request.submit_ts < 0.02     # it woke the loop
    assert ended.t1 - ended.t0 < 0.05
    sent = sorted((r for r in recs if r.name in ("dispatch", "compile")
                   and r.t0 >= ended.t1), key=lambda r: r.t0)
    assert sent[0].parent == "prefill"
    assert sent[0].attrs["rid"] == late.request.rid
    assert sent[1].parent == "decode_step"
    # no gap is counted across a hold
    for g in (r for r in recs if r.name.startswith("host_gap_")):
        for h in holds:
            assert not (g.t0 < h.t1 and h.t0 < g.t1), (g, h)


# ------------------------------------------------------------ the ring
def test_the_ring_is_bounded():
    assert spans.RING_SIZE >= 16384
    for i in range(spans.RING_SIZE + 50):
        spans.record("t_ring", 0.001, t0=float(i))
    recs = spans.recent()
    assert len(recs) == spans.RING_SIZE
    assert recs[-1].name == "t_ring" and recs[-1].t0 == \
        float(spans.RING_SIZE + 49)


# -------------------------------------------------------- kill switch
def test_telemetry_off_records_nothing_and_still_fills_token_ts(
        engine, ring):
    was = tracing.enabled()
    n0 = {n: spans.SPAN_MS.labels(n).count
          for n in ("decode_step", "harvest", "prefill", "queue_wait",
                    "host_gap_decode", "dispatch")}
    try:
        tracing.enable(False)
        reqs = _requests(2, [3, 4], seed=5)
        _run_batcher(engine, reqs)
    finally:
        tracing.enable(was)
    assert spans.recent() == []
    assert n0 == {n: spans.SPAN_MS.labels(n).count for n in n0}
    for q in reqs:
        assert len(q.token_ts) == len(q.tokens) == q.max_new_tokens
        assert q.token_ts[-1] - q.submit_ts == q.latency_s


# --------------------------------------------- on the profiler's clock
def test_the_same_names_sit_on_the_host_line_of_a_profiler_trace(
        model, ring, tmp_path):
    import jax
    from jax.profiler import ProfileData

    srv = InferenceServer(model, max_batch=2, max_seq_len=32,
                          prefill_buckets=(8,), workers=1)
    rs = np.random.RandomState(6)
    with srv:
        # compile outside the trace: traced dispatches are cache hits
        srv.submit(rs.randint(1, 64, (5,)).tolist(),
                   max_new_tokens=2).result(timeout=120)
        time.sleep(0.05)
        ring.clear()
        jax.profiler.start_trace(str(tmp_path))
        try:
            hs = [srv.submit(rs.randint(1, 64, (6,)).tolist(),
                             max_new_tokens=4) for _ in range(2)]
            for h in hs:
                h.result(timeout=120)
            time.sleep(0.05)
        finally:
            jax.profiler.stop_trace()
    steps = len([r for r in spans.recent() if r.name == "decode_step"])
    assert steps >= 3
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    host = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                host.setdefault(ev.name, []).append(
                    (line.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    for name in ("decode_step", "harvest", "drain", "prefill",
                 "step:serve_decode"):
        assert name in host, (name, sorted(host)[:40])
    assert len(host["decode_step"]) == steps
    assert len(host["harvest"]) == steps
    assert len(host["step:serve_decode"]) == steps
    for line, s, e in host["step:serve_decode"]:
        assert any(ln == line and ds <= s and e <= de
                   for ln, ds, de in host["decode_step"]
                   + host["first_tokens"])
    # a span a request caused is annotated with it
    assert len(host["prefill"]) == 2
