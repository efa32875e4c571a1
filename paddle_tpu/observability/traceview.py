"""Cross-rank trace export: journal span events -> Chrome/Perfetto JSON.

PR 11's spans made "where did the time go" a recorded fact — but a
grep-able one. This module turns the per-rank ``journal-*.jsonl`` span
events of a run directory (already correlated by the launcher-exported
``PADDLE_TPU_TRACE_ID``) into one Chrome-trace-event JSON that
chrome://tracing and https://ui.perfetto.dev open directly:

  * one track per rank x thread (pid = rank, tid = the emitting
    thread), named via metadata events;
  * every span as a complete ("X") slice from its recorded start
    ``t0`` (journals older than that field: the emit time ``ts`` minus
    ``dur_ms``, which misplaces a span banked after the fact);
  * ``serve_admit`` / ``serve_complete`` / ``serve_shed`` as instant
    events and a flow arrow per request (id = rid) from the
    ``serve_request`` slice's start to its completion — the
    submit-to-finish line SERVING.md describes, drawn across threads.
    A shed request (``outcome`` of ``shed`` / ``deadline_expired``) is
    an instant only: no slice body, no flow arrow — the arrows stay
    reserved for traffic that actually served.

Also home to the ONE trace-event serializer in the tree:
``trace_event()`` / ``dump_trace()`` are shared with
``utils/profiler.py``'s ``export_chrome_trace`` (this module must stay
import-light so the profiler can lean on it, not vice versa).

Pure stdlib and standalone-loadable by file path — `ptdoctor trace`
runs on machines that have nothing but the run dir (same contract and
same journal fallback as aggregate.py).
"""
from __future__ import annotations

import glob
import json
import os
from typing import Dict, List, Optional, Tuple

try:                                    # package import (normal case)
    from . import journal as _journal
except ImportError:                     # standalone load by file path
    import importlib.util as _ilu

    _spec = _ilu.spec_from_file_location(
        "_pt_journal_standalone",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "journal.py"))
    _journal = _ilu.module_from_spec(_spec)
    _spec.loader.exec_module(_journal)

read_journal = _journal.read_journal

__all__ = ["trace_event", "dump_trace", "build_trace", "count_tracks",
           "export_trace", "TRACE_JSON"]

TRACE_JSON = "trace.json"

#: span name -> chrome trace category (colors group in the viewer)
_TRAIN = frozenset(("step", "feed", "feed_wait", "compile", "dispatch",
                    "host"))
_SERVE = frozenset(("serve_request", "queue_wait", "prefill",
                    "serve_suffix", "decode_steps", "decode_step", "fetch",
                    "harvest", "drain", "loop_idle", "host_gap_decode",
                    "host_gap_prefill"))


# ------------------------------------------------- shared serializer
def trace_event(name: str, ts_us: float, dur_us: Optional[float] = None,
                pid: int = 0, tid: int = 0, cat: Optional[str] = None,
                ph: str = "X", args: Optional[dict] = None,
                **extra) -> dict:
    """One chrome trace event dict (trace-event format). `extra` passes
    format fields like `id`/`bp`/`s` straight through."""
    ev = {"ph": ph, "name": name, "pid": int(pid), "tid": int(tid),
          "ts": round(float(ts_us), 3)}
    if dur_us is not None:
        ev["dur"] = round(float(dur_us), 3)
    if cat:
        ev["cat"] = cat
    if args:
        ev["args"] = args
    ev.update(extra)
    return ev


def dump_trace(events: List[dict], display_unit: str = "ms") -> str:
    """The one JSON envelope every exporter in the tree writes."""
    return json.dumps({"traceEvents": events,
                       "displayTimeUnit": display_unit})


# ------------------------------------------------- journal -> events
def _journal_files(directory: str) -> List[str]:
    """Rotated `.1` generation (older) before each live file — same
    read order as aggregate._journal_files."""
    out = []
    for path in sorted(glob.glob(os.path.join(directory,
                                              "journal-*.jsonl"))):
        if os.path.exists(path + ".1"):
            out.append(path + ".1")
        out.append(path)
    return out


def _rank_of(rec: dict) -> int:
    try:
        return int(rec.get("rank") or 0)
    except (TypeError, ValueError):
        return 0


def _tid_of(rec: dict) -> int:
    """Thread track within the rank; span events carry `tid` (spans.py)
    — older journals without it collapse onto track 0."""
    try:
        return int(rec.get("tid") or 0)
    except (TypeError, ValueError):
        return 0


def _start_of(rec: dict) -> float:
    """A span's start on the epoch clock."""
    t0 = rec.get("t0")
    if isinstance(t0, (int, float)):
        return float(t0)
    return rec["ts"] - rec["dur_ms"] / 1e3


def _cat_of(name: str) -> str:
    if name in _TRAIN:
        return "train"
    if name in _SERVE:
        return "serve"
    return "span"


def build_trace(records: List[dict]) -> List[dict]:
    """Merge journal records (any number of ranks) into a sorted chrome
    trace event list. Timestamps are rebased to the earliest span start
    so the viewer opens at t=0 rather than the epoch."""
    spans_ = [r for r in records if r.get("event") == "span"
              and isinstance(r.get("ts"), (int, float))
              and isinstance(r.get("dur_ms"), (int, float))]
    admits = [r for r in records if r.get("event") == "serve_admit"
              and isinstance(r.get("ts"), (int, float))]
    completes = [r for r in records if r.get("event") == "serve_complete"
                 and isinstance(r.get("ts"), (int, float))]
    sheds = [r for r in records if r.get("event") == "serve_shed"
             and isinstance(r.get("ts"), (int, float))]
    if not spans_ and not admits and not completes and not sheds:
        return []
    starts = [_start_of(r) for r in spans_]
    starts += [r["ts"] for r in admits + completes + sheds]
    t0 = min(starts)

    def us(ts: float) -> float:
        return (ts - t0) * 1e6

    events: List[dict] = []
    tracks: Dict[Tuple[int, int], None] = {}
    complete_by_rid = {}
    for r in completes:
        rid = r.get("rid")
        if rid is not None and rid not in complete_by_rid:
            complete_by_rid[rid] = r
    for r in spans_:
        pid, tid = _rank_of(r), _tid_of(r)
        tracks[(pid, tid)] = None
        name = str(r.get("name", "?"))
        start_us = us(_start_of(r))
        args = {}
        for key in ("parent", "trace"):
            if r.get(key):
                args[key] = r[key]
        if isinstance(r.get("attrs"), dict):
            args.update(r["attrs"])
        attrs = r.get("attrs") or {}
        if name == "serve_request" and attrs.get("outcome") in (
                "shed", "deadline_expired"):
            # a shed request never produced a token: an instant at the
            # shed point (no slice body, no flow arrow) keeps the lane
            # readable — the arrows stay reserved for served traffic
            events.append(trace_event(name, us(r["ts"]), pid=pid,
                                      tid=tid, cat="serve", ph="i",
                                      s="t", args=args or None))
            continue
        events.append(trace_event(name, start_us, r["dur_ms"] * 1e3,
                                  pid=pid, tid=tid, cat=_cat_of(name),
                                  args=args or None))
        if name == "serve_request":
            rid = attrs.get("rid")
            if rid is None:
                continue
            # flow arrow: submit (slice start) -> completion
            events.append(trace_event(
                "serve_request", start_us, pid=pid, tid=tid, cat="serve",
                ph="s", id=int(rid)))
            done = complete_by_rid.get(rid)
            if done is not None:
                fin_us, fin_pid, fin_tid = us(done["ts"]), \
                    _rank_of(done), _tid_of(done)
            else:
                fin_us, fin_pid, fin_tid = \
                    start_us + r["dur_ms"] * 1e3, pid, tid
            events.append(trace_event(
                "serve_request", fin_us, pid=fin_pid, tid=fin_tid,
                cat="serve", ph="f", bp="e", id=int(rid)))
    for r in admits + completes + sheds:
        pid, tid = _rank_of(r), _tid_of(r)
        tracks[(pid, tid)] = None
        args = {k: r[k] for k in ("rid", "slot", "prefill_bucket",
                                  "ttft_s", "latency_s", "tokens",
                                  "reason", "retry_after_s", "state")
                if r.get(k) is not None}
        events.append(trace_event(str(r["event"]), us(r["ts"]), pid=pid,
                                  tid=tid, cat="serve", ph="i", s="t",
                                  args=args or None))
    meta: List[dict] = []
    for pid in sorted({p for p, _ in tracks}):
        meta.append(trace_event("process_name", 0, pid=pid, ph="M",
                                args={"name": "rank %d" % pid}))
        meta.append(trace_event("process_sort_index", 0, pid=pid, ph="M",
                                args={"sort_index": pid}))
    for pid, tid in sorted(tracks):
        meta.append(trace_event("thread_name", 0, pid=pid, tid=tid,
                                ph="M", args={"name": "thread %d" % tid}))
    events.sort(key=lambda e: (e["ts"], e["pid"], e["tid"], e["ph"],
                               e["name"]))
    return meta + events


def count_tracks(events: List[dict]) -> int:
    """Distinct rank x thread tracks carrying real (non-metadata)
    events."""
    return len({(e["pid"], e["tid"]) for e in events
                if e.get("ph") != "M"})


def export_trace(directory: str, out_path: Optional[str] = None
                 ) -> Tuple[str, int, int]:
    """Merge every journal under `directory` into a Perfetto-loadable
    trace; returns (path, n_events, n_tracks). Atomic tmp+rename so a
    live viewer never reads a half-written file."""
    records: List[dict] = []
    for path in _journal_files(directory):
        records.extend(read_journal(path))
    events = build_trace(records)
    path = out_path or os.path.join(directory, TRACE_JSON)
    tmp = "%s.tmp.%d" % (path, os.getpid())
    with open(tmp, "w") as f:
        f.write(dump_trace(events))
    os.replace(tmp, path)
    return path, len(events), count_tracks(events)
