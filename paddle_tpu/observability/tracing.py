"""Step/compile telemetry: retrace accounting + latency recording.

The reference's perf evidence comes from RecordEvent spans
(platform/profiler.h) stitched into chrome traces; on TPU the questions
that matter are different — *how many times did XLA recompile, how long
did compiles take, and what is the steady-state step time once the
executable cache is warm?* `StepTelemetry` answers them for one dispatch
engine (jit train/eval step, to_static TracedLayer, static Executor):

  * every executable-cache MISS (first trace included — a retrace is any
    signature the engine has not compiled yet) increments
    `pt_jit_retraces_total{engine=...}` and banks its wall time into
    `pt_jit_compile_seconds_total{engine=...}`;
  * cache HITS record in-call wall time into
    `pt_step_latency_seconds{engine=...}` and — the number that survives
    async dispatch, where a call returns before the device finishes —
    entry-to-entry gaps into `pt_step_interval_seconds{engine=...}`,
    whose mean IS the steady-state step time of a saturated loop.

Each dispatch is also one `spans.span` — `dispatch` on a hit, `compile`
on a miss, labelled `step:<engine>` / `compile:<engine>` on the profiler's
line — so the same boundary is a `pt_span_ms` series, a record in the
span ring and a `jax.profiler` host annotation, through one call.

Telemetry defaults ON and is cheap (a set lookup + two clock reads per
step); `PADDLE_TPU_TELEMETRY=0` or `enable(False)` turns the spans into
no-ops — the overhead contract (≤5% steady-state, asserted in
tests/test_observability.py) is measured against that switch.
"""
from __future__ import annotations

import os
import time
from typing import Optional

from . import flight, journal, metrics

__all__ = ["enabled", "enable", "StepTelemetry", "record_sync",
           "record_feed_stall", "set_compile_cache_probe",
           "SYNC_SECONDS", "TRAIN_STEPS", "FEED_STALL"]

_enabled = os.environ.get("PADDLE_TPU_TELEMETRY", "1") != "0"

# () -> (hits, misses) of the persistent compilation cache, installed by
# jit.compile_cache.configure(). Kept as an injected callable so this
# module stays stdlib-pure: tracing never imports jax, the jax side
# pushes its probe in. None == no persistent cache configured.
_cache_probe = None


def set_compile_cache_probe(fn) -> None:
    global _cache_probe
    _cache_probe = fn


def enabled() -> bool:
    return _enabled


def enable(on: bool = True):
    """Flip telemetry globally (tests and the overhead benchmark)."""
    global _enabled
    _enabled = bool(on)


RETRACES = metrics.counter(
    "pt_jit_retraces_total",
    "Executable-cache misses (first compile included) per engine",
    labelnames=("engine",))
COMPILE_SECONDS = metrics.counter(
    "pt_jit_compile_seconds_total",
    "Wall time spent tracing+compiling per engine", labelnames=("engine",))
STEP_LATENCY = metrics.histogram(
    "pt_step_latency_seconds",
    "In-call wall time of cache-hit dispatches (async: excludes device "
    "time still in flight)", labelnames=("engine",))
STEP_INTERVAL = metrics.histogram(
    "pt_step_interval_seconds",
    "Entry-to-entry gap between consecutive cache-hit dispatches; mean "
    "== steady-state step time of a saturated loop",
    labelnames=("engine",))
SYNC_SECONDS = metrics.counter(
    "pt_device_sync_seconds_total",
    "Wall time blocked on device sync (host reads of device values)")
TRAIN_STEPS = metrics.counter(
    "pt_train_steps_total", "Train steps dispatched")
FEED_STALL = metrics.histogram(
    "pt_feed_stall_ms",
    "Per-batch milliseconds the consumer waited on the input feed; mean "
    "~0 when prefetch keeps the device fed, ~decode time when starved")


class _Span:
    """One dispatch measurement; hand back via StepTelemetry.step()."""

    __slots__ = ("tel", "miss", "t0", "cache0", "_pspan")

    def __init__(self, tel: "StepTelemetry", miss: bool):
        self.tel = tel
        self.miss = miss
        self.cache0 = None
        self._pspan = None

    def __enter__(self):
        if self.tel is not None:
            # "compile" on a cache miss, "dispatch" on a hit — nested
            # under whatever span the caller holds open (fit's "step",
            # the batcher's "decode_step"), so step time decomposes.
            # Imported here: spans imports this module for enabled()
            from . import spans
            name, label = ("compile", "compile:") if self.miss \
                else ("dispatch", "step:")
            self._pspan = spans.span(name, engine=self.tel.engine,
                                     label=label + self.tel.engine)
            self._pspan.__enter__()
            if self.miss and _cache_probe is not None:
                try:
                    self.cache0 = _cache_probe()
                except Exception:
                    self.cache0 = None
            self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self.tel is not None:
            dt = time.perf_counter() - self.t0
            self._pspan.__exit__(exc_type, exc, tb)
            if exc_type is None:
                self.tel._finish(self, dt)
        return False


_NULL_SPAN = _Span(None, False)


class StepTelemetry:
    """Retrace + latency accounting for one dispatch engine.

        tel = StepTelemetry("jit_train")
        with tel.step(signature):      # signature: hashable aval key
            ...trace/compile/dispatch...
    """

    def __init__(self, engine: str):
        self.engine = engine
        self._seen = set()
        self._last_hit_entry: Optional[float] = None
        self._retraces = RETRACES.labels(engine)
        self._compile_s = COMPILE_SECONDS.labels(engine)
        self._latency = STEP_LATENCY.labels(engine)
        self._interval = STEP_INTERVAL.labels(engine)

    def step(self, signature) -> _Span:
        if not _enabled:
            # liveness does not go off with telemetry: the launcher's
            # hang detector still has to see this loop make progress
            _health_tick()
            return _NULL_SPAN
        miss = signature not in self._seen
        if miss:
            self._seen.add(signature)
            # the flight recorder keeps the last-compiled signature so a
            # crash bundle can answer "what was XLA building when it died"
            flight.note_compile(self.engine, signature)
        else:
            now = time.perf_counter()
            if self._last_hit_entry is not None:
                self._interval.observe(now - self._last_hit_entry)
            self._last_hit_entry = now
        return _Span(self, miss)

    def _finish(self, span: _Span, dt: float):
        if span.miss:
            cache_hits = cache_misses = 0
            if span.cache0 is not None and _cache_probe is not None:
                try:
                    h1, m1 = _cache_probe()
                    cache_hits = h1 - span.cache0[0]
                    cache_misses = m1 - span.cache0[1]
                except Exception:
                    pass
            # either way the stall breaks the steady-state run; restart
            # the interval chain so it doesn't pollute step time
            self._last_hit_entry = None
            self._compile_s.inc(dt)
            if cache_hits > 0 and cache_misses == 0:
                # every executable this dispatch needed came off the
                # persistent cache: XLA compiled nothing, so this is a
                # warm reload, not a retrace — the restart-tax number
                # the cache exists to drive to zero
                journal.emit("compile_cache", engine=self.engine,
                             hits=cache_hits, compile_s=round(dt, 6))
            else:
                self._retraces.inc()
                ev = dict(engine=self.engine, compile_s=round(dt, 6),
                          total=int(self._retraces.value))
                if cache_misses:
                    ev["cache_misses"] = cache_misses
                journal.emit("retrace", **ev)
        else:
            self._latency.observe(dt)
        flight.step_finished(self.engine, dt, span.miss)
        _health_tick()

    @property
    def retraces(self) -> int:
        return int(self._retraces.value)


_health_tick_fn = None


def _health_tick():
    """Any engine dispatch counts as liveness for the launcher's hang
    detector: the one tick source every loop shares (`Model.evaluate`, a
    hand-written loop over a compiled step, `Executor.run`, the server's
    decode loop). Lazy + cached: observability must not import resilience
    at module load (resilience imports observability back, best-effort)."""
    global _health_tick_fn
    if _health_tick_fn is None:
        try:
            from ..resilience import health
            _health_tick_fn = health.tick
        except Exception:
            _health_tick_fn = lambda: False  # noqa: E731
    try:
        _health_tick_fn()
    except Exception:
        pass


def record_sync(seconds: float):
    """Bank wall time a host thread spent blocked on device results."""
    if _enabled:
        SYNC_SECONDS.inc(seconds)


def record_feed_stall(ms: float):
    """Bank milliseconds a consumer waited on the input feed (io.prefetch
    observes every batch, 0 included, so the mean is per-batch stall)."""
    if _enabled:
        FEED_STALL.observe(ms)
