"""Span tracing: nested wall-time decomposition of steps and requests.

PR 2's StepTelemetry says *that* a step was slow; spans say *where the
time went*. A span is one named wall-clock interval with an optional
parent, so a train step decomposes into `feed` / `compile` / `dispatch`
/ `host` children, a serving request into `queue_wait` / `prefill` /
`decode_steps`, and the serving loop into `drain` / `hold` / `prefill` /
`first_tokens` / `decode_step` (`dispatch` + `fetch`) / `harvest` /
`loop_idle` — the
breakdown `ptdoctor profile` renders and the benchmark's `program_span`
metrics read.

Three entry points:

  * ``span(name, **attrs)`` — context manager for same-thread nesting.
    Parentage is a thread-local stack: a span opened inside another's
    block records that span's name as its parent and inherits its
    ``rid`` / ``step`` (the request and the loop step that caused it).
    ``t0=`` takes the caller's own start instant and ``close(t1)`` its
    end, for callers that time on an injected clock.
  * ``begin(name, ...)`` / ``end(handle, ...)`` — explicit pair for
    spans that START on one thread and FINISH on another (a serving
    request begins in the caller's ``submit()`` and ends in the worker
    loop). ``begin`` does NOT touch the thread-local stack — a handle is
    meant to travel.
  * ``record(name, dur_ms, t0=...)`` — bank an interval measured by the
    caller's own clock (the scheduler computes queue_wait from its
    injectable clock so children sum EXACTLY to ttft_s).

Every closed span is a record ``(name, t0, t1, parent, trace, attrs)``
with both instants on ``time.perf_counter`` (the clock
``Request.submit_ts`` carries): it goes into one bounded in-memory ring
read by ``recent()``, observes ``pt_span_ms{name=...}`` and, when a run
journal is active, emits a ``span`` journal event
(`name/t0/dur_ms/parent/trace/attrs`, `t0` moved onto the journal's
epoch clock). A same-thread span is also the ONE place that opens a
``jax.profiler.TraceAnnotation`` (only once `jax` is imported), so the
same boundaries sit on the host line of a profiler trace, between the
device programs. Trace ids come from ``PADDLE_TPU_TRACE_ID`` (exported
per-run by the launcher) so one multi-process run correlates;
standalone processes mint their own.

**One clock with the profiler.** A record's instants are
``time.perf_counter``; ``t + EPOCH`` is the same instant on the epoch
clock the journal and a ``jax.profiler`` trace use (an xplane event's
absolute time is the trace's ``profile_start_time`` plus its offset), so
a ring-only record (``host_gap_decode``, ``queue_wait``) can be laid on
the device trace beside the annotations (docs/OBSERVABILITY.md).

**Garbage collection.** While telemetry is on, a ``gc.callbacks`` hook
makes every full (generation 2) collection a stacked ``gc_full`` span on
the thread that ran it (``collected`` an attribute), and adds every
collection's time to ``pt_gc_seconds_total{generation}``.
``innermost(t0, t1)`` splits an interval by the innermost closed span
over each instant, from the ring: what a stalled loop was in.

Disabled-by-default-safe: with telemetry off (``PADDLE_TPU_TELEMETRY=0``
/ ``tracing.enable(False)``) every entry point returns a shared no-op
(no clock read, no observe, no ring append), and without an active
journal (``PADDLE_TPU_TELEMETRY_DIR`` unset) nothing is written anywhere
but the in-process ring and metrics registry — the same contract
metrics/journal already keep. Pure stdlib by contract.
"""
from __future__ import annotations

import atexit
import collections
import gc
import os
import sys
import threading
import time
import uuid
from typing import Dict, Iterable, List, NamedTuple, Optional

from . import journal, metrics, tracing

__all__ = ["span", "begin", "end", "record", "recent", "trace_id",
           "current", "innermost", "watch_gc", "Span", "SpanRecord",
           "SPAN_MS", "GC_SECONDS", "RING_SIZE", "EPOCH"]

# millisecond scale: 10us .. ~84s upper edges
SPAN_MS = metrics.histogram(
    "pt_span_ms",
    "Wall time of named trace spans, milliseconds",
    labelnames=("name",),
    buckets=metrics.exponential_buckets(0.01, 2.0, 24))
GC_SECONDS = metrics.counter(
    "pt_gc_seconds_total",
    "Wall time of the interpreter's garbage collections, by generation "
    "(a full one, generation 2, is also a `gc_full` span)",
    labelnames=("generation",))


class SpanRecord(NamedTuple):
    """One closed span; `t0`/`t1` are `time.perf_counter` instants."""

    name: str
    t0: float
    t1: float
    parent: Optional[str]
    trace: str
    attrs: dict


#: a 40 s chat window closes ≈ 46 k spans (PR 36): the ring holds its last ≈ 28 s
RING_SIZE = 32768
# appended and copied whole under the GIL: a deque's append and
# `list(deque)` run in C with no Python code between, so need no lock
_ring: "collections.deque[SpanRecord]" = collections.deque(maxlen=RING_SIZE)
_clock = time.perf_counter
#: perf_counter -> epoch, fixed at import: the journal's `ts`, every other
#: rank's events and a `jax.profiler` trace are on the epoch clock
EPOCH = time.time() - time.perf_counter()
# what a span hands down to the spans opened or recorded inside it
_INHERITED = ("rid", "step")

_trace_id: Optional[str] = None
_tls = threading.local()
_annotation_cls = None


def trace_id() -> str:
    """Run-scoped correlation id: launcher-exported env, else per-process."""
    global _trace_id
    if _trace_id is None:
        _trace_id = (os.environ.get("PADDLE_TPU_TRACE_ID")
                     or uuid.uuid4().hex[:12])
    return _trace_id


def _stack() -> list:
    s = getattr(_tls, "stack", None)
    if s is None:
        s = _tls.stack = []
    return s


def current() -> Optional[str]:
    """Name of the innermost open span on THIS thread (else None)."""
    s = getattr(_tls, "stack", None)
    return s[-1].name if s else None


def recent() -> List[SpanRecord]:
    """The closed spans the ring still holds, oldest first."""
    return list(_ring)


def _annotate(label: str, attrs: dict):
    """An entered `jax.profiler.TraceAnnotation`, or None while `jax` is
    not imported (this module never imports it). With no trace live the
    annotation is a flag check."""
    global _annotation_cls
    if _annotation_cls is None:
        jax = sys.modules.get("jax")
        if jax is None:
            return None
        _annotation_cls = jax.profiler.TraceAnnotation
    ann = _annotation_cls(
        label, **{k: attrs[k] for k in _INHERITED if k in attrs}) \
        if "rid" in attrs or "step" in attrs else _annotation_cls(label)
    ann.__enter__()
    return ann


def _inherit(attrs: dict, s: Optional[list] = None) -> Optional[str]:
    """Parent name from this thread's stack `s`; its rid/step fill
    `attrs`."""
    if s is None:
        s = getattr(_tls, "stack", None)
    if not s:
        return None
    top = s[-1]
    for k in _INHERITED:
        if k in top.attrs and k not in attrs:
            attrs[k] = top.attrs[k]
    return top.name


#: name -> the `pt_span_ms{name}` child's observe, looked up once a name
_observe: Dict[str, object] = {}
_observe_of = None          # the histogram `_observe` was filled from


def _observer(name: str):
    global _observe_of
    if _observe_of is not SPAN_MS:      # replaced (a test's counting double)
        _observe.clear()
        _observe_of = SPAN_MS
    obs = _observe[name] = SPAN_MS.labels(name).observe
    return obs


def _emit(name: str, t0: float, t1: float, parent: Optional[str],
          attrs: dict) -> None:
    dur_ms = (t1 - t0) * 1e3
    obs = _observe.get(name)
    if obs is None or _observe_of is not SPAN_MS:
        obs = _observer(name)
    obs(dur_ms)
    _ring.append(SpanRecord(name, t0, t1, parent,
                            _trace_id or trace_id(), attrs))
    # journal writes only when a run journal is live: journal.emit with no
    # journal still taps the flight ring, and per-step span events would
    # wash real dispatch history out of its 512 slots
    if journal.get_journal() is not None:
        # tid gives traceview one track per rank x thread (the envelope
        # already carries rank/pid); masked like profiler.RecordEvent's
        ev = {"name": name, "t0": round(t0 + EPOCH, 6),
              "dur_ms": round(dur_ms, 3), "trace": trace_id(),
              "tid": threading.get_ident() % 100000}
        if parent:
            ev["parent"] = parent
        if attrs:
            ev["attrs"] = attrs
        journal.emit("span", **ev)


class Span:
    """One open interval; context manager (stacked) or begin/end handle."""

    __slots__ = ("name", "parent", "attrs", "t0", "_stacked", "_done",
                 "_ann", "_deferred")

    def __init__(self, name: str, parent: Optional[str], attrs: dict,
                 stacked: Optional[list], t0: Optional[float] = None,
                 label: Optional[str] = None):
        self.name = name
        self.parent = parent
        self.attrs = attrs
        self._stacked = stacked         # this thread's stack, or None
        self._done = self._deferred = False
        # a handle that travels between threads is not annotated: the
        # profiler pairs an annotation's two ends on one thread's line
        self._ann = None if stacked is None else \
            _annotate(label or name, attrs)
        self.t0 = _clock() if t0 is None else t0

    def cancel(self) -> None:
        """Abandon without recording (e.g. the feed-exhausted last step)."""
        self._done = True

    def defer(self) -> None:
        """Leaving the `with` block will only unwind the nesting; the
        caller keeps the span and records it with a later `close(t1)`
        (a program enqueued in the block, its result read after it)."""
        self._deferred = True

    def close(self, t1: Optional[float] = None, **attrs) -> None:
        """Record the interval now, ending at the caller's instant `t1`
        (default: this module's clock); extra attrs merge in. Leaving
        the `with` block afterwards only unwinds the nesting."""
        if self._done:
            return
        self._done = True
        if attrs:
            self.attrs.update(attrs)
        _emit(self.name, self.t0, _clock() if t1 is None else t1,
              self.parent, self.attrs)

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, exc_type, exc, tb):
        # an exception unwinding through the block is not a measured
        # interval (mirrors StepTelemetry's _Span)
        if exc_type is not None:
            self._done = True
        elif not self._deferred:
            self.close()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        s = self._stacked
        if s and s[-1] is self:
            s.pop()
        return False


class _NullSpan:
    """Shared no-op for the disabled fast path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False

    def cancel(self) -> None:
        pass

    def defer(self) -> None:
        pass

    def close(self, t1=None, **attrs) -> None:
        pass


_NULL = _NullSpan()


def span(name: str, parent: Optional[str] = None,
         t0: Optional[float] = None, label: Optional[str] = None, **attrs):
    """Open a nested span on this thread: ``with spans.span("step"): ...``

    `parent` overrides the enclosing span's name (a request's children
    name `serve_request`, which lives on another thread), `t0` is the
    caller's own start instant, `label` the name on the profiler's line
    where it differs from the `pt_span_ms` series."""
    if not tracing.enabled():
        return _NULL
    s = _stack()
    inherited = _inherit(attrs, s)
    sp = Span(name, parent or inherited, attrs, stacked=s, t0=t0,
              label=label)
    s.append(sp)
    return sp


def begin(name: str, parent: Optional[str] = None,
          t0: Optional[float] = None, **attrs) -> Optional[Span]:
    """Start a cross-thread span; pair with ``end(handle)`` anywhere.

    Does not join this thread's nesting stack — the handle carries its
    own identity. Returns None when tracing is disabled (end(None) is a
    no-op), so call sites need no enabled() check of their own."""
    if not tracing.enabled():
        return None
    return Span(name, parent, attrs, stacked=None, t0=t0)


def end(handle: Optional[Span], t1: Optional[float] = None, **attrs) -> None:
    """Finish a begin() handle (any thread). Extra attrs merge in."""
    if handle is not None:
        handle.close(t1, **attrs)


def record(name: str, dur_ms: float, parent: Optional[str] = None,
           t0: Optional[float] = None, **attrs) -> None:
    """Bank a caller-measured interval as a span. `t0` is the caller's
    start instant; without it the interval is taken to end now. The
    interval need not lie inside the enclosing span, so `parent` is only
    what the caller names; rid/step are still inherited."""
    if not tracing.enabled():
        return
    _inherit(attrs)
    dur_s = float(dur_ms) / 1e3
    if t0 is None:
        t0 = _clock() - dur_s
    _emit(name, t0, t0 + dur_s, parent, attrs)


def innermost(t0: float, t1: float,
              names: Optional[Iterable[str]] = None) -> Dict[str, float]:
    """{span name: seconds} of [t0, t1] by the innermost closed span over
    each instant (of those the ring holds; of `names` alone where given):
    the one that started last, as nested spans do. Time no such span
    covers is under None. For a rare look back (a stalled loop), not a
    hot path: it scans the ring from its newest record."""
    keep = None if names is None else frozenset(names)
    recs = []
    for r in reversed(recent()):
        if r.t1 < t0 - 1.0:         # appended as closed: older ones too
            break
        if r.t1 > t0 and r.t0 < t1 and (keep is None or r.name in keep):
            recs.append(r)
    cuts = sorted({t0, t1} | {x for r in recs for x in (r.t0, r.t1)
                              if t0 < x < t1})
    out: Dict[str, float] = {}
    for a, b in zip(cuts, cuts[1:]):
        over = [r for r in recs if r.t0 <= a and b <= r.t1]
        name = max(over, key=lambda r: r.t0).name if over else None
        out[name] = out.get(name, 0.0) + (b - a)
    return out


# -- garbage collection ------------------------------------------------------
_gc_open = threading.local()
_gc_counted = [GC_SECONDS.labels(str(g)) for g in range(3)]


def _on_gc(phase: str, info: dict) -> None:
    """`gc.callbacks` hook: a full collection is a stacked `gc_full` span
    on the collecting thread; every collection's time is counted."""
    gen = info.get("generation", 0)
    if phase == "start":
        _gc_open.t0 = _clock()
        _gc_open.span = span("gc_full") if gen == 2 else None
        return
    t0 = getattr(_gc_open, "t0", None)
    if t0 is None:                    # switched on mid-collection
        return
    _gc_counted[gen].inc(_clock() - t0)
    sp, _gc_open.t0, _gc_open.span = _gc_open.span, None, None
    if sp is not None:
        sp.close(collected=info.get("collected", 0))
        sp.__exit__(None, None, None)


def watch_gc(on: bool) -> None:
    """Register (on) or remove the collection hook; `tracing.enable`
    calls it, so the hook is there exactly while telemetry is on."""
    if on and _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
    elif not on and _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)


watch_gc(tracing.enabled())
# the interpreter's last collections run with this module half torn down
atexit.register(watch_gc, False)
