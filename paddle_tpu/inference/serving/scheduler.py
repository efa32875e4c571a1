"""Continuous-batching scheduler over the generation engine's slots.

Orca-style iteration-level scheduling: the decode batch is a fixed set
of `max_batch` slots; a finished sequence frees its slot and a queued
request is admitted into it on the next step via one bucketed prefill —
the batch stays full instead of draining to the slowest straggler.
`admit_mid_flight=False` degrades to classic static batching (fill the
batch, run it to empty, repeat), the baseline tests/test_serving.py
compares against.

All decode dispatches cost the same wall time regardless of how many
slots are live (the compiled program is shape-fixed), so throughput is
decided purely by how many useful tokens each step carries — which is
exactly what `pt_serve_batch_occupancy` measures.

**The loop runs one decode step ahead.** No token is read from the device
until the program that follows it has been enqueued (engine.py says why
it can be), so the device does not wait out the read, the host's
bookkeeping and the next enqueue. One iteration, `step()`:

  1. admit: one prefill a free slot, each enqueued behind whatever is in
     flight, its first token left pending;
  2. read the first tokens that are the oldest programs in flight (TTFT
     is stamped there); a cold start enqueues one decode step behind
     them first;
  3. top up: `enqueue_decode()` while fewer than `STEPS_AHEAD` decode
     steps are in flight and some request still needs a step that is not
     in flight. A step carries the (slot, request) pairs that were live
     when it was ENQUEUED: its tokens go to them and to no one else;
  4. read the OLDEST decode step's tokens (`token_ts`), and harvest.

Everything is read in the order it was enqueued. The serving loop takes
2 and 3–4 in two calls (`turn()`), and looks at its queue between them.

What running ahead costs is an arrival's wait: its prefill queues behind
the step already enqueued. So the serving loop does not top up at once
where it need not: while ONE step is in flight, a slot is free and no one
waits, it may wait `hold_s()` for an arrival — the first half of the
running step, measured from the last two steps that ran back to back —
and an arrival in that time gets its prefill in FRONT of the next step.
The second half is the room the top-up needs (several enqueues long).

A request is released **by count, at enqueue time**: once the step that
carries its last token is in flight its slot is free for the next
prefill, which the device orders after that step, so running ahead costs
no occupancy. A request with an `eos_id` may stop sooner, and the loop
learns it one step late: the token the step ahead computed for it is
dropped and never reaches `Request.tokens`. How deep the loop runs is
not set anywhere: it follows from what is owed by count and what is live.
"""
from __future__ import annotations

import itertools
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from ...observability import journal, metrics, spans
from .slo import AdmissionController, ShedError, SLOPolicy

__all__ = ["Request", "ContinuousBatcher", "run_open_loop"]

ADMITTED = metrics.counter(
    "pt_serve_admitted_total", "Requests admitted into a decode slot")
COMPLETED = metrics.counter(
    "pt_serve_completed_total",
    "Requests finished (max_new_tokens reached or eos emitted)")
TOKENS = metrics.counter(
    "pt_serve_tokens_total",
    "Tokens generated for live requests (prefill first tokens included)")
OCCUPANCY = metrics.gauge(
    "pt_serve_batch_occupancy",
    "Live slots in the decode batch after the latest scheduler step")
TTFT = metrics.histogram(
    "pt_serve_ttft_seconds", "Submit-to-first-token latency per request")
REQ_SECONDS = metrics.histogram(
    "pt_serve_request_seconds", "Submit-to-completion latency per request")
OCCUPANCY_PCT = metrics.histogram(
    "pt_serve_occupancy_pct",
    "Live slots / max_batch x 100, one observation per decode step",
    buckets=tuple(range(5, 101, 5)))
ITL_MS = metrics.histogram(
    "pt_serve_itl_ms",
    "Gap between consecutive tokens of one request, milliseconds (one "
    "observation per token after the first)",
    buckets=metrics.exponential_buckets(0.01, 2.0, 24))

_RID = itertools.count(1)

#: decode steps kept in flight: the one the device runs and the one
#: behind it, so that the device never waits for the host's read
STEPS_AHEAD = 2


@dataclass
class Request:
    """One generation request and its measured lifecycle."""

    prompt: Sequence[int]
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    rid: int = field(default_factory=lambda: next(_RID))
    tokens: List[int] = field(default_factory=list)
    # one instant per token, on the batcher's clock: the instant the
    # tokens of its step (the prefill, for the first) were fetched
    token_ts: List[float] = field(default_factory=list)
    submit_ts: Optional[float] = None     # set at batcher.submit()
    ttft_s: Optional[float] = None        # token_ts[0] - submit_ts
    latency_s: Optional[float] = None     # token_ts[-1] - submit_ts
    slot: Optional[int] = None
    prefix_len: int = 0                   # cached-prefix tokens reused
    on_complete: Optional[Callable[["Request"], None]] = None
    span: Optional[object] = None         # serve_request spans.begin handle
    outcome: Optional[str] = None         # completed|shed|deadline_expired
    error: Optional[BaseException] = None  # ShedError when shed/expired

    @property
    def done(self) -> bool:
        if len(self.tokens) >= self.max_new_tokens:
            return True
        return (self.eos_id is not None and len(self.tokens) > 0
                and self.tokens[-1] == self.eos_id)


class _First:
    """An admission in flight: the prefill is enqueued, its first token
    pending (`tok`), its `prefill` span open until the token is read."""

    __slots__ = ("req", "tok", "t_pre", "span", "bucket")

    def __init__(self, req, tok, t_pre, span, bucket):
        self.req, self.tok, self.t_pre = req, tok, t_pre
        self.span, self.bucket = span, bucket


class _Step:
    """A decode step in flight: the (slot, request) pairs live when it
    was enqueued, the instant that was, and whether it was enqueued
    straight behind the step before it (`timed`: then the time between
    their two reads is one step's run)."""

    __slots__ = ("pairs", "t_enq", "timed")

    def __init__(self, pairs, t_enq, timed):
        self.pairs, self.t_enq, self.timed = pairs, t_enq, timed


class ContinuousBatcher:
    """Slot scheduler driving one GenerationEngine.

    step() == admit waiting requests into free slots (one prefill each,
    enqueued; its first token / TTFT comes when it is read), top up the
    decode steps in flight, read the oldest one's tokens, harvest (the
    module docstring has the order and why).
    """

    def __init__(self, engine, admit_mid_flight: bool = True,
                 clock=time.perf_counter, slo=None):
        self.engine = engine
        self.admit_mid_flight = admit_mid_flight
        self._clock = clock
        # SLO admission control (ROADMAP item 4): an SLOPolicy (wrapped
        # in a controller on the batcher's own clock) or a shared
        # AdmissionController (the threaded server passes one across
        # all workers). None — the default — keeps submit/step behavior
        # byte-identical to a policy-free build: unbounded queue, no
        # deadlines, `serve_shed` never fires.
        if isinstance(slo, SLOPolicy):
            slo = AdmissionController(slo, clock=clock)
        self.slo: Optional[AdmissionController] = slo
        self.waiting: deque = deque()
        # a slot's owner while it still needs a decode step that is not
        # in flight; `_left` counts those steps
        self.slots: List[Optional[Request]] = [None] * engine.max_batch
        self._left = [0] * engine.max_batch
        # admitted and not complete, by rid: the slots' owners and the
        # requests released by count whose tokens are still in flight
        self._live = {}
        # what is enqueued and not read, oldest first: `_First`s and
        # `_Step`s
        self._flight: deque = deque()
        self._steps_ahead = 0            # the decode steps among them
        self.steps = 0                   # decode steps read
        # for `hold_s`: the instant of the last read (about when the
        # program behind it began) and a step's run as last measured
        self._read_ts = self._period = None

    # -- introspection ----------------------------------------------------

    @property
    def active(self) -> int:
        """Requests admitted and not complete, tokens in flight or not."""
        return len(self._live)

    @property
    def idle(self) -> bool:
        return not (self.waiting or self._live or self._flight)

    def hold_s(self) -> float:
        """How long the serving loop may still wait for an arrival before
        `step()` has to top up (0: do not wait). Only while exactly one
        decode step is in flight and nothing else, some request still
        needs another, a slot is free and no one waits: then until half
        of the running step's measured time is over."""
        if (self._period is None or self._steps_ahead != 1
                or len(self._flight) != 1 or self.waiting
                or not self.admit_mid_flight or None not in self.slots
                or not any(self.slots)):
            return 0.0
        started = max(self._flight[0].t_enq, self._read_ts)
        return max(started + self._period / 2 - self._clock(), 0.0)

    def pending_requests(self) -> List[Request]:
        return list(self._live.values()) + list(self.waiting)

    # -- lifecycle --------------------------------------------------------

    def submit(self, req: Request) -> Request:
        """Queue a request; validates it fits the engine's static shapes."""
        prompt = np.asarray(req.prompt, np.int64).reshape(-1)
        if prompt.shape[0] < 1:
            raise ValueError("empty prompt")
        if req.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        # single source of truth for bucketing lives in serving/cache.py;
        # the engine method is its thin delegate
        self.engine.bucket_for(int(prompt.shape[0]))
        if prompt.shape[0] + req.max_new_tokens > self.engine.max_seq_len:
            raise ValueError(
                "prompt (%d) + max_new_tokens (%d) exceeds max_seq_len %d"
                % (prompt.shape[0], req.max_new_tokens,
                   self.engine.max_seq_len))
        if req.submit_ts is None:
            req.submit_ts = self._clock()
        if req.span is None:
            # direct-batcher callers get the root span here; the threaded
            # server begins it earlier, in the submitter's own thread
            req.span = spans.begin("serve_request", t0=req.submit_ts,
                                   rid=req.rid)
        if self.slo is not None:
            err = self.slo.check_admit(len(self.waiting))
            if err is not None:
                self._shed(req, err, queued=False)
                raise err
        self.waiting.append(req)
        return req

    def _shed(self, req: Request, err: ShedError, queued: bool) -> None:
        """Reject a request (at submit) or drop it (expired in queue):
        end its span with the shed outcome and journal the decision —
        a named `serve_shed` beats a silent timeout."""
        req.outcome = err.reason if err.reason == "deadline_expired" \
            else "shed"
        req.error = err
        spans.end(req.span, outcome=req.outcome, reason=err.reason)
        journal.emit("serve_shed", rid=req.rid, reason=err.reason,
                     state=err.state,
                     retry_after_s=round(err.retry_after_s, 3),
                     queue_depth=len(self.waiting),
                     waited_s=round(self._clock() - req.submit_ts, 6))
        if queued and req.on_complete is not None:
            # a queued-then-expired request still owes its caller an
            # answer; submit-time rejects answer via the raised error
            req.on_complete(req)

    def _complete(self, req: Request, completed: List[Request]) -> None:
        done = req.token_ts[-1]
        req.latency_s = done - req.submit_ts
        if self.slots[req.slot] is req:   # stopped by its eos: not by count
            self.slots[req.slot] = None
        del self._live[req.rid]
        req.slot = None
        req.outcome = "completed"
        COMPLETED.inc()
        REQ_SECONDS.observe(req.latency_s)
        if len(req.tokens) > 1:
            # everything after the first token: latency - ttft by the
            # scheduler's own clock, so the three children sum to latency
            spans.record("decode_steps",
                         (req.latency_s - req.ttft_s) * 1e3,
                         parent="serve_request", t0=req.token_ts[0],
                         rid=req.rid, steps=len(req.tokens) - 1)
        spans.end(req.span, done, tokens=len(req.tokens),
                  outcome="completed")
        journal.emit("serve_complete", rid=req.rid,
                     tokens=len(req.tokens),
                     ttft_s=round(req.ttft_s, 6),
                     latency_s=round(req.latency_s, 6))
        completed.append(req)
        if req.on_complete is not None:
            req.on_complete(req)

    def _admit(self, completed: List[Request]) -> None:
        # static batching only refills once the whole batch has drained
        if not self.admit_mid_flight and self.active > 0:
            return
        for slot, r in enumerate(self.slots):
            if self.slo is not None:
                # drop expired waiters BEFORE spending a prefill on
                # them: past its deadline a request can only steal
                # decode steps from ones that could still make theirs
                while self.waiting and \
                        self.slo.expire(self.waiting[0].submit_ts):
                    expired = self.waiting.popleft()
                    self._shed(expired, ShedError(
                        "deadline_expired",
                        self.slo.retry_after_s(len(self.waiting)),
                        state=self.slo.state), queued=True)
                    completed.append(expired)
            if not self.waiting:
                return
            if r is not None:
                continue
            req = self.waiting.popleft()
            n = len(np.asarray(req.prompt).reshape(-1))
            t_pre = self._clock()
            # `step`: the decode step this admission runs ahead of
            with spans.span("prefill", parent="serve_request", t0=t_pre,
                            rid=req.rid,
                            step=self.steps + self._steps_ahead + 1) as sp:
                tok = self.engine.prefill(slot, req.prompt)
                sp.defer()          # it closes when the token is read
            # what THIS admission actually dispatched: on a prefix hit
            # the bucket is the (smaller) suffix bucket and prefix_len
            # counts the reused tokens
            info = getattr(self.engine, "admit_info", None) or \
                {"prefix_len": 0, "bucket": self.engine.bucket_for(n)}
            req.prefix_len = int(info.get("prefix_len", 0))
            # queue_wait + prefill == ttft_s exactly: same clock, same
            # instants — the TTFT decomposition SERVING.md documents
            spans.record("queue_wait", (t_pre - req.submit_ts) * 1e3,
                         parent="serve_request", t0=req.submit_ts,
                         rid=req.rid)
            req.slot = slot
            self._live[req.rid] = req
            self._flight.append(_First(req, tok, t_pre, sp, info["bucket"]))
            self._left[slot] = req.max_new_tokens - 1
            if self._left[slot]:      # else the prefill's token is its last
                self.slots[slot] = req
            ADMITTED.inc()
            if self.slo is not None:
                self.slo.observe_queue_wait(t_pre - req.submit_ts)
            journal.emit("serve_admit", rid=req.rid, slot=slot,
                         prompt_len=n, bucket=info["bucket"],
                         prefix_len=req.prefix_len)

    def _first_token(self, first: _First, completed: List[Request]) -> None:
        """Read an admission's first token (it blocks here): TTFT, the
        end of its `prefill` span, the SLO controller's sample."""
        req, t_pre = first.req, first.t_pre
        tok = int(first.tok)
        now = self._clock()
        first.span.close(now, bucket=first.bucket)
        req.ttft_s = now - req.submit_ts
        if req.prefix_len > 0:
            # prefix-cache hit: a serve_suffix child over the SAME
            # interval as prefill (parent="prefill", not a sibling
            # under serve_request), so queue_wait + prefill == ttft
            # stays exact while the trace shows which admissions ran
            # the suffix-only path
            spans.record("serve_suffix", (now - t_pre) * 1e3,
                         parent="prefill", t0=t_pre, rid=req.rid,
                         prefix_len=req.prefix_len,
                         bucket=first.bucket)
        req.token_ts.append(now)
        req.tokens.append(tok)
        TOKENS.inc()
        TTFT.observe(req.ttft_s)
        if self.slo is not None:
            # the measured TTFT of every admission IS the control
            # signal — no separate sampling path
            self.slo.observe_ttft(req.ttft_s)
        if req.done:              # max_new_tokens == 1 (or instant eos)
            self._complete(req, completed)

    def _enqueue_step(self) -> None:
        """One more decode step in flight, for the requests that hold a
        slot now; one whose last token it carries gives its slot up here
        (the release by count)."""
        # straight behind a step in flight: it is read one run after it
        # (or was enqueued as that one was read, where the loop held late)
        timed = bool(self._flight) and isinstance(self._flight[-1], _Step)
        # the slots that hold a request NOW: one released by count a step
        # ago, or by an eos read since, is empty to this step already
        self.engine.enqueue_decode([req is not None for req in self.slots])
        pairs = []
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            pairs.append((slot, req))
            self._left[slot] -= 1
            if not self._left[slot]:
                self.slots[slot] = None
        self._flight.append(_Step(pairs, self._clock(), timed))
        self._steps_ahead += 1

    def _top_up(self, ahead: int) -> None:
        while self._steps_ahead < ahead and any(self.slots):
            self._enqueue_step()

    def _read_firsts(self, completed: List[Request]) -> bool:
        """Read the first tokens that are the oldest programs in flight
        (-> whether there were any). Each has its follower enqueued by
        then: the program behind it in flight, else one decode step
        enqueued here — or it will never have one (its request needs no
        step)."""
        if not (self._flight and isinstance(self._flight[0], _First)):
            return False
        with spans.span("first_tokens", step=self.steps + 1):
            self._top_up(1)
            while self._flight and isinstance(self._flight[0], _First):
                self._first_token(self._flight.popleft(), completed)
        self._read_ts = self._clock()
        return True

    def _read_step(self, completed: List[Request]) -> None:
        """Top up, then read the oldest decode step in flight (which is
        the oldest program in flight) and harvest its tokens."""
        n = self.steps + 1
        with spans.span("decode_step", t0=self._clock(), step=n) as sp:
            self._top_up(STEPS_AHEAD)
            if not self._flight:
                sp.cancel()
                return
            step = self._flight.popleft()
            self._steps_ahead -= 1
            toks = self.engine.decode()
            now = self._clock()         # the step's tokens are fetched
            sp.close(now)
        if step.timed:
            self._period = now - self._read_ts
        self._read_ts = now
        self.steps = n
        with spans.span("harvest", t0=now, step=n) as sp:
            # a request its eos stopped a step ago is in this step too:
            # the token computed for it is dropped
            pairs = [(slot, req) for slot, req in step.pairs
                     if not req.done]
            # the rows each holds once this step's row is in: what the
            # step attended (`pt_kv_rows_given` is the device's own count
            # of the rows it swept before that row, for every slot)
            self.engine.kv.observe_live_rows(
                [len(r.prompt) + len(r.tokens) for _, r in pairs])
            for slot, req in pairs:
                req.tokens.append(int(toks[slot]))
                ITL_MS.observe((now - req.token_ts[-1]) * 1e3)
                req.token_ts.append(now)
                if req.done:
                    self._complete(req, completed)
            TOKENS.inc(len(pairs))
            OCCUPANCY_PCT.observe(100.0 * len(pairs) / len(self.slots))
            sp.close(self._clock())

    def _iterate(self, pause: bool) -> List[Request]:
        completed: List[Request] = []
        self._admit(completed)
        if not (self._read_firsts(completed) and pause):
            self._read_step(completed)
        OCCUPANCY.set(self.active)
        return completed

    def step(self) -> List[Request]:
        """One scheduler iteration: admit, read the first tokens that
        are due, top up, read one decode step; returns the requests
        completed by it."""
        return self._iterate(pause=False)

    def turn(self) -> List[Request]:
        """`step()` for a caller that watches a queue of its own between
        two reads (the serving loop): it stops once first tokens were
        read, before the next decode step is topped up and read, so that
        a request that came meanwhile is admitted in FRONT of that step."""
        return self._iterate(pause=True)

    def run_until_idle(self, max_steps: int = 1_000_000) -> List[Request]:
        completed: List[Request] = []
        for _ in range(max_steps):
            if self.idle:
                return completed
            completed.extend(self.step())
        raise RuntimeError("scheduler failed to drain in %d steps"
                           % max_steps)


def run_open_loop(batcher: ContinuousBatcher,
                  arrivals: Sequence[Tuple[float, Request]],
                  clock=time.perf_counter,
                  sleep=None) -> List[Request]:
    """Drive the batcher under an open-loop arrival process.

    `arrivals` is [(offset_seconds, request)]: each request is submitted
    once the wall clock passes its offset (independent of service rate —
    the open-loop property), the batcher steps whenever there is live
    work, and the call returns when everything has completed. TTFT and
    per-request latency are measured from each request's actual submit
    time, so queueing delay under load is included.

    With a fake clock (`slo.VirtualClock` or anything exposing
    `sleep()`), idle gaps advance the clock instead of the wall —
    no `time.sleep` in the hot loop, so overload benches and SLO tests
    replay an arrival schedule deterministically on CPU CI. Requests a
    bounded-queue batcher sheds at submit are returned too (their
    `outcome`/`error` name the shed) — an open-loop driver must not
    crash because the system under test protected itself."""
    if sleep is None:
        sleep = getattr(clock, "sleep", time.sleep)
    pend = deque(sorted(arrivals, key=lambda p: p[0]))
    completed: List[Request] = []
    t0 = clock()
    while pend or not batcher.idle:
        now = clock() - t0
        while pend and pend[0][0] <= now:
            req = pend.popleft()[1]
            try:
                batcher.submit(req)
            except ShedError:
                completed.append(req)
        if batcher.idle and pend:
            delay = pend[0][0] - (clock() - t0)
            if delay > 0:
                sleep(delay)
            continue
        completed.extend(batcher.step())
    return completed
