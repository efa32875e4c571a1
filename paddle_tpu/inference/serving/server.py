"""Minimal multi-worker serving front-end over the generation engine.

A thread-per-worker serving loop fed by one shared request queue: drain
the queue, wait on it for as long as `ContinuousBatcher.hold_s()` lets
the next decode step wait, `turn()` (scheduler.py has the order). Each
worker owns a GenerationEngine (its own paged KV cache and slots) but
all workers share the SAME loaded model — weights are read-only at
serve time and pass into the jitted steps as arguments (engine.py), so
N workers cost one copy of the weights plus N caches.

Reuses the existing production machinery instead of growing parallel
plumbing: every program the engine dispatches ticks
`resilience.health` through its `StepTelemetry`, telemetry on or off
(the launcher's heartbeat/hang detector watches serving like it
watches training), a crashed loop dumps a flight-recorder crash bundle before
failing its in-flight requests, and queue depth is exported through
the PR 2 metrics registry (`pt_serve_queue_depth`).

**The loop's line has no holes.** Every instant of `_loop` lies inside
one innermost stacked span on its thread (`drain`, `loop_idle`, `hold`,
`submit`, `turn` and what they hold: `prefill`, `first_tokens`,
`decode_step` → `dispatch` / `fetch`, `harvest`, a `gc_full`), so the
profiler's host line says what the host was doing over any idle stretch
of the device. An iteration that runs longer than `STALL_S`, its waits
for a request left out, is a stall: `pt_serve_stall_seconds_total` and a
`serve_stall` journal event naming the span it was in.
"""
from __future__ import annotations

import queue
import threading
import time
from typing import List, Optional, Sequence

from ...observability import flight, httpd, journal, metrics, spans, \
    tracing
from .engine import GenerationEngine
from .scheduler import ContinuousBatcher, Request
from .slo import AdmissionController, ShedError, SLOPolicy

__all__ = ["InferenceServer", "ServeHandle"]

QUEUE_DEPTH = metrics.gauge(
    "pt_serve_queue_depth",
    "Requests waiting in the server queue (not yet in a decode slot)")
STALL_SECONDS = metrics.counter(
    "pt_serve_stall_seconds_total",
    "Time of the serving loop's iterations that ran longer than STALL_S, "
    "their waits for a request (hold, loop_idle) left out")

#: an iteration of the loop longer than this, its waits left out, stalled
STALL_S = 0.1
#: the spans the loop's thread opens, nested as they are on the profiler's
#: line (a `prefill` record outlives its block: its enqueue is `dispatch`)
LOOP_SPANS = ("drain", "loop_idle", "hold", "submit", "turn",
              "first_tokens", "decode_step", "dispatch", "compile", "fetch",
              "harvest", "gc_full")
_WAITS = ("hold", "loop_idle")


class _Stalls:
    """One lap an iteration of the loop: one longer than `STALL_S`, its
    waits left out, is counted and journaled with the innermost span
    that took most of it (telemetry on only)."""

    __slots__ = ("t0", "waited")

    def __init__(self):
        self.t0, self.waited = time.perf_counter(), 0.0

    def lap(self) -> None:
        now = time.perf_counter()
        busy = now - self.t0 - self.waited
        if busy > STALL_S:
            self._report(self.t0, now, busy)
        self.t0, self.waited = now, 0.0

    @staticmethod
    def _report(t0: float, t1: float, busy: float) -> None:
        STALL_SECONDS.inc(busy)
        leaves = spans.innermost(t0, t1, LOOP_SPANS)
        work = {k: v for k, v in leaves.items() if k not in _WAITS}
        journal.emit("serve_stall", ms=round(busy * 1e3, 3),
                     leaf=max(work, key=work.get) if work else None,
                     gc_full_ms=round(leaves.get("gc_full", 0.0) * 1e3, 3))


class ServeHandle:
    """Future-like handle on a submitted request."""

    def __init__(self, request: Request):
        self.request = request
        self._event = threading.Event()
        self._error: Optional[BaseException] = None

    def _finish(self, error: Optional[BaseException] = None) -> None:
        self._error = error
        self._event.set()

    def _completed(self, req) -> None:
        # admission control answers through the same callback: a queued
        # request whose deadline expired carries its ShedError
        self._finish(getattr(req, "error", None))

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: Optional[float] = None) -> List[int]:
        """Block for the generated tokens. Raises ShedError (with
        `retry_after_s`) when admission control rejected the request —
        the replica is degraded but alive, retry later — and
        RuntimeError when the serving loop actually failed."""
        if not self._event.wait(timeout):
            raise TimeoutError("request %d not complete within %ss"
                               % (self.request.rid, timeout))
        if isinstance(self._error, ShedError):
            raise self._error
        if self._error is not None:
            raise RuntimeError(
                "serving loop failed while handling request %d"
                % self.request.rid) from self._error
        return list(self.request.tokens)


class InferenceServer:
    """Threaded continuous-batching server.

        with InferenceServer(model, max_batch=4) as srv:
            h = srv.submit([1, 2, 3], max_new_tokens=8)
            tokens = h.result(timeout=60)
    """

    def __init__(self, model, max_batch: int = 4, max_seq_len: int = 128,
                 prefill_buckets: Sequence[int] = (32, 64, 128),
                 pad_id: int = 0, workers: int = 1,
                 poll_s: float = 0.002, http_port=None,
                 kv_dtype: str = "float32", prefix_cache_bytes=None,
                 slo=None):
        if workers < 1:
            raise ValueError("workers must be >= 1")
        # SLO admission control: explicit SLOPolicy/AdmissionController,
        # or PADDLE_TPU_SLO_TTFT_MS from the environment; absent both,
        # None — submit/step behavior identical to a policy-free build.
        # ONE controller is shared across all workers so the live p99
        # and the admission state reflect the whole replica.
        if slo is None:
            slo = SLOPolicy.from_env()
        if isinstance(slo, SLOPolicy):
            slo = AdmissionController(slo)
        self._slo: Optional[AdmissionController] = slo
        # each worker gets its OWN prefix cache (an engine's stored K/V
        # slices must never outlive into another engine's donation
        # lifecycle); kv_dtype="int8" halves each worker's cache bytes
        self._engines = [
            GenerationEngine(model, max_batch=max_batch,
                             max_seq_len=max_seq_len,
                             prefill_buckets=prefill_buckets, pad_id=pad_id,
                             kv_dtype=kv_dtype,
                             prefix_cache_bytes=prefix_cache_bytes)
            for _ in range(workers)]
        self._queue: "queue.Queue[ServeHandle]" = queue.Queue()
        self._poll_s = poll_s
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self._started = False
        # live telemetry plane: socket opened ONLY when http_port or
        # $PADDLE_TPU_HTTP_PORT asks for one (parity contract)
        self._http_port = http_port
        self._http = None

    @property
    def engines(self) -> List[GenerationEngine]:
        return list(self._engines)

    # -- lifecycle --------------------------------------------------------

    def start(self) -> "InferenceServer":
        if self._started:
            return self
        self._started = True
        for i, eng in enumerate(self._engines):
            t = threading.Thread(target=self._loop, args=(eng,),
                                 name="pt-serve-%d" % i, daemon=True)
            t.start()
            self._threads.append(t)
        try:
            self._http = httpd.ensure_server(port=self._http_port)
        except Exception:
            self._http = None
        if self._http is not None:
            # a dead batcher loop must flip /healthz to 503 so a router
            # drains this replica instead of timing requests out
            httpd.register_probe("serve_loop", self._loop_alive)
            httpd.register_status("serving_workers", self._http_status)
        return self

    def _loop_alive(self):
        """/healthz probe: every worker thread of a started, not-yet-
        stopped server must be alive (a crashed loop leaves a dead
        thread behind — the raise in _loop ends it)."""
        dead = [t.name for t in self._threads if not t.is_alive()]
        if self._started and not self._stop.is_set() and dead:
            return False, "dead serving worker(s): %s" % ",".join(dead)
        detail = "%d/%d workers alive" % (
            sum(t.is_alive() for t in self._threads), len(self._threads))
        if self._slo is not None and self._slo.state != "healthy":
            # degraded-but-alive: shedding load is the replica WORKING,
            # not dying — stay 200 (a 503 here would make the router
            # drain exactly the replica that is protecting itself);
            # the detail names the brownout so operators see it
            detail += "; admission=%s (degraded, shedding load)" \
                % self._slo.state
        return True, detail

    def _http_status(self) -> dict:
        st = {"workers": len(self._threads),
              "alive": sum(t.is_alive() for t in self._threads),
              "queue_depth": self._queue.qsize(),
              "stopping": self._stop.is_set()}
        if self._slo is not None:
            st["degraded"] = self._slo.state != "healthy"
        return st

    def stop(self, timeout: float = 60.0) -> None:
        self._stop.set()
        for t in self._threads:
            t.join(timeout)
        if self._http is not None:
            # a cleanly-stopped server is not a sick one
            httpd.unregister_probe("serve_loop")
            httpd.unregister_status("serving_workers")
            self._http = None

    def __enter__(self) -> "InferenceServer":
        return self.start()

    def __exit__(self, exc_type, exc, tb) -> None:
        self.stop()

    # -- request path -----------------------------------------------------

    def submit(self, prompt, max_new_tokens: int = 16,
               eos_id: Optional[int] = None) -> ServeHandle:
        if not self._started:
            raise RuntimeError("server not started (use start() or `with`)")
        req = Request(prompt=list(prompt), max_new_tokens=max_new_tokens,
                      eos_id=eos_id, submit_ts=time.perf_counter())
        # root span begins on the SUBMITTER's thread (same instant as
        # submit_ts) and ends in the worker loop at _complete — the
        # begin/end cross-thread form exists for exactly this hand-off
        req.span = spans.begin("serve_request", t0=req.submit_ts,
                               rid=req.rid)
        handle = ServeHandle(req)
        req.on_complete = handle._completed
        self._queue.put(handle)
        QUEUE_DEPTH.set(self._queue.qsize())
        return handle

    def _drain_into(self, batcher: ContinuousBatcher) -> None:
        while True:
            try:
                handle = self._queue.get_nowait()
            except queue.Empty:
                break
            self._submit_or_fail(batcher, handle)
        QUEUE_DEPTH.set(self._queue.qsize())

    @staticmethod
    def _submit_or_fail(batcher: ContinuousBatcher,
                        handle: ServeHandle) -> None:
        try:
            batcher.submit(handle.request)
        except Exception as exc:   # invalid request must not kill the loop
            handle._finish(exc)

    def _wait_for_request(self) -> Optional[ServeHandle]:
        """Block until a request arrives; None once the server stops."""
        while not self._stop.is_set():
            try:
                return self._queue.get(timeout=self._poll_s)
            except queue.Empty:
                pass
        return None

    def _wait(self, engine: GenerationEngine, name: str, step: int,
              stalls: Optional[_Stalls],
              timeout: Optional[float] = None) -> Optional[ServeHandle]:
        """Wait on the queue inside a `name` span, `timeout` seconds or,
        without one, until a request comes (None once the server stops).
        A wait for a request is not the host's work: no host gap and no
        stall is counted across it."""
        t0 = time.perf_counter()
        with spans.span(name, step=step):
            if timeout is None:
                handle = self._wait_for_request()
            else:
                try:
                    handle = self._queue.get(timeout=timeout)
                except queue.Empty:
                    handle = None
        engine.note_idle()
        if stalls is not None:
            stalls.waited += time.perf_counter() - t0
        return handle

    def _submit(self, batcher: ContinuousBatcher, handle: ServeHandle,
                step: int) -> None:
        with spans.span("submit", step=step):
            self._submit_or_fail(batcher, handle)

    def _loop(self, engine: GenerationEngine) -> None:
        batcher = ContinuousBatcher(engine, slo=self._slo)
        stalls = _Stalls() if tracing.enabled() else None
        try:
            while True:
                if stalls is not None:
                    stalls.lap()
                step = batcher.steps + 1
                with spans.span("drain", step=step):
                    self._drain_into(batcher)
                if batcher.idle:
                    # the device sits idle for want of a request
                    handle = self._wait(engine, "loop_idle", step, stalls)
                    if handle is None:
                        return
                    self._submit(batcher, handle, step)
                    continue
                hold = batcher.hold_s()
                if hold > 0:
                    # a step is running and the next need not be enqueued
                    # yet: an arrival now gets its prefill in front of it
                    handle = self._wait(engine, "hold", step, stalls, hold)
                    if handle is not None:
                        self._submit(batcher, handle, step)
                with spans.span("turn", step=step):
                    batcher.turn()
        except BaseException as exc:
            flight.dump_crash_bundle("serve_loop", exc)
            self._fail_pending(batcher, exc)
            raise

    @staticmethod
    def _fail_pending(batcher: ContinuousBatcher,
                      exc: BaseException) -> None:
        # fail every handle this worker still owed an answer to; the
        # completion callback is a bound ServeHandle method, so the
        # handle is reachable from the request itself
        for req in batcher.pending_requests():
            handle = getattr(req.on_complete, "__self__", None)
            if isinstance(handle, ServeHandle):
                handle._finish(exc)
