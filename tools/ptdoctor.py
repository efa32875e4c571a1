#!/usr/bin/env python
"""ptdoctor: post-mortem CLI for a paddle_tpu telemetry directory.

    python tools/ptdoctor.py summary  <telemetry_dir>
    python tools/ptdoctor.py timeline <telemetry_dir> [--last N]
    python tools/ptdoctor.py crash    <telemetry_dir>
    python tools/ptdoctor.py lint     <telemetry_dir>
    python tools/ptdoctor.py profile  <telemetry_dir>
    python tools/ptdoctor.py roofline <telemetry_dir>
    python tools/ptdoctor.py trace    <telemetry_dir> [--out trace.json]
    python tools/ptdoctor.py bench    <repo_or_results_dir>

`summary` answers "what happened to run X" from one command: per-rank
step counts/rates and last-alive step, retraces per engine, restart
count, the stalest rank, and a digest of every crash bundle. `timeline`
prints the merged cross-rank event stream (monotonic by ts).  `crash`
dumps each bundle's manifest, the tail of its flight ring, and the head
of its stack capture.  `profile` answers "where did the time go": the
per-span latency table (count/total/mean/p50/p95 over every `span`
journal event), the step and serve_request decompositions with a
critical-path share line (compute vs feed vs host vs unattributed), and
the static step card (analysis/cost_pass.py) when the run dir has one.
`roofline` answers "why is the achieved FLOP/s what it is": it joins
the static step card (FLOPs, unfused HBM bytes, collective operand
bytes) with the measured span timings and a per-device-kind peak table
(override with PADDLE_TPU_PEAK_TFLOPS / PADDLE_TPU_PEAK_GBPS) to
classify each card as compute-bound / memory-bound / exposed-collective
/ host-or-feed-bound, with achieved-vs-peak TFLOP/s and GB/s and the
measured exposed-collective headroom overlap work would burn down.
`trace` merges every rank's journal span events into one chrome-trace /
Perfetto JSON (open in ui.perfetto.dev or chrome://tracing — one track
per rank x thread, serve_request flow arrows across threads). `bench`
renders the BENCH_*.json files as a per-config trend table and flags
step_ms / MFU / compile_s / hbm_peak regressions against the best
prior row.

Stdlib only, and paddle_tpu is never imported (it pulls in jax — this
tool must run on a machine that has nothing but the run dir). The
aggregation logic is loaded straight from
paddle_tpu/observability/aggregate.py by file path.

Exit codes: 0 success, 2 bad usage / missing directory.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_aggregate():
    path = os.path.join(_REPO, "paddle_tpu", "observability", "aggregate.py")
    spec = importlib.util.spec_from_file_location("_pt_aggregate", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _load_traceview():
    path = os.path.join(_REPO, "paddle_tpu", "observability", "traceview.py")
    spec = importlib.util.spec_from_file_location("_pt_traceview", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _fmt_ts(ts) -> str:
    if not isinstance(ts, (int, float)):
        return "?"
    import time
    return time.strftime("%H:%M:%S", time.localtime(ts)) + \
        ("%.3f" % (ts % 1.0))[1:]


def _rank_of(rec) -> object:
    src = rec.get("src", "")
    if src.startswith("journal-rank"):
        try:
            return int(src[len("journal-rank"):].split(".")[0])
        except ValueError:
            pass
    return None


def _collect(events):
    """Per-rank stats from the merged event stream."""
    ranks = {}
    for rec in events:
        r = _rank_of(rec)
        if r is None:
            continue
        st = ranks.setdefault(r, {"events": 0, "steps": [], "first_ts": None,
                                  "last_ts": None, "last_step": None,
                                  "hb_step": None, "hb_ts": None})
        st["events"] += 1
        ts = rec.get("ts")
        if isinstance(ts, (int, float)):
            if st["first_ts"] is None:
                st["first_ts"] = ts
            st["last_ts"] = max(st["last_ts"] or ts, ts)
        if rec.get("event") == "step" and isinstance(ts, (int, float)):
            st["steps"].append(ts)
        step = rec.get("step")
        if isinstance(step, (int, float)):
            st["last_step"] = max(st["last_step"] or 0, int(step))
    for rec in events:
        if rec.get("event") == "heartbeat_last":
            st = ranks.get(rec.get("rank"))
            if st is not None:
                st["hb_step"] = rec.get("step")
                st["hb_ts"] = rec.get("ts")
                if isinstance(rec.get("step"), (int, float)):
                    st["last_step"] = max(st["last_step"] or 0,
                                          int(rec["step"]))
    return ranks


def _step_rate(steps):
    """(overall, first-half, second-half) steps/sec, or None."""
    if len(steps) < 2:
        return None
    span = steps[-1] - steps[0]
    if span <= 0:
        return None
    overall = (len(steps) - 1) / span
    mid = len(steps) // 2
    halves = []
    for part in (steps[:mid + 1], steps[mid:]):
        d = part[-1] - part[0]
        halves.append((len(part) - 1) / d if d > 0 and len(part) > 1
                      else overall)
    return overall, halves[0], halves[1]


def _manifests(directory):
    import glob
    out = []
    for path in sorted(glob.glob(
            os.path.join(directory, "crash", "*", "MANIFEST.json"))):
        try:
            with open(path) as f:
                man = json.load(f)
        except (OSError, ValueError):
            continue
        if isinstance(man, dict):
            man["_dir"] = os.path.dirname(path)
            out.append(man)
    return out


def _counter_by_label(agg, directory, name, label):
    """Sum a labelled counter across every metrics*.json snapshot in the
    run dir (rollup excluded): {label_value: total}. Counters are
    per-process cumulative, so summing across rank snapshots gives the
    run-wide total."""
    totals = {}
    for path in agg._snapshot_files(directory):
        try:
            with open(path) as f:
                snap = json.load(f)
        except (OSError, ValueError):
            continue
        meta = (snap.get("metrics") or {}).get(name) \
            if isinstance(snap, dict) else None
        if not isinstance(meta, dict):
            continue
        for s in meta.get("series", []):
            key = (s.get("labels") or {}).get(label)
            if key is None or not isinstance(s.get("value"), (int, float)):
                continue
            totals[key] = totals.get(key, 0) + s["value"]
    return totals


def _counter_total(agg, directory, name):
    """Sum an unlabelled counter across every metrics*.json snapshot
    (same contract as _counter_by_label, for label-free series)."""
    total = 0.0
    seen = False
    for path in agg._snapshot_files(directory):
        try:
            with open(path) as f:
                snap = json.load(f)
        except (OSError, ValueError):
            continue
        meta = (snap.get("metrics") or {}).get(name) \
            if isinstance(snap, dict) else None
        if not isinstance(meta, dict):
            continue
        for s in meta.get("series", []):
            if isinstance(s.get("value"), (int, float)):
                total += s["value"]
                seen = True
    return total if seen else None


def _gauge_worst(agg, directory, name):
    """MAX of a gauge across every metrics*.json snapshot (a level
    reading: the fleet value is its worst rank's), or None."""
    worst = None
    for path in agg._snapshot_files(directory):
        try:
            with open(path) as f:
                snap = json.load(f)
        except (OSError, ValueError):
            continue
        meta = (snap.get("metrics") or {}).get(name) \
            if isinstance(snap, dict) else None
        if not isinstance(meta, dict):
            continue
        for s in meta.get("series", []):
            if isinstance(s.get("value"), (int, float)):
                v = float(s["value"])
                worst = v if worst is None else max(worst, v)
    return worst


_SLO_STATES = {0: "healthy", 1: "shedding", 2: "brownout"}


def _slo_section(agg, directory, events) -> None:
    """Print the slo block of `summary`: shed counters, live p99 vs
    budget, and the overload verdict. Silent when no SLO policy ever
    ran (the budget gauge is the controller's registration mark)."""
    budget = _gauge_worst(agg, directory, "pt_slo_ttft_budget_ms")
    shed_by = _counter_by_label(agg, directory,
                                "pt_serve_shed_total", "reason")
    shed_events = sum(1 for e in events if e.get("event") == "serve_shed")
    if budget is None and not shed_by and not shed_events:
        return
    p99 = _gauge_worst(agg, directory, "pt_slo_ttft_p99_ms")
    state = _gauge_worst(agg, directory, "pt_admission_state")
    expired = _counter_total(agg, directory,
                             "pt_serve_deadline_expired_total") or 0
    shed_total = sum(shed_by.values()) or shed_events
    crashes = len(_manifests(directory))
    line = "  slo:"
    if budget is not None:
        line += " budget=%.0fms" % budget
    if p99 is not None:
        line += "  live_p99=%.1fms" % p99
    if state is not None:
        line += "  state=%s" % _SLO_STATES.get(int(state), "?")
    line += "  shed=%d  deadline_expired=%d" % (int(shed_total),
                                                int(expired))
    print(line)
    if shed_by:
        print("    shed by reason: " + "  ".join(
            "%s=%d" % (k, int(v)) for k, v in sorted(shed_by.items())))
    # the overload verdict: collapsed (p99 blew the budget — shedding
    # absent or insufficient), shed-and-held (load was rejected and the
    # admitted traffic kept its SLO), or under-budget (never pressured)
    if budget is not None and p99 is not None and p99 > budget:
        verdict = "collapsed (live p99 %.1fms > budget %.0fms%s)" % (
            p99, budget, "" if shed_total else ", no shedding configured")
    elif shed_total:
        verdict = "shed-and-held (%d shed, admitted traffic %s)" % (
            int(shed_total),
            "p99 %.1fms <= budget %.0fms" % (p99, budget)
            if budget is not None and p99 is not None else "within SLO")
    else:
        verdict = "under-budget (no shedding needed)"
    if crashes and shed_total:
        verdict += " — but %d crash bundle(s): shed-never-crash VIOLATED" \
            % crashes
    print("    verdict: %s" % verdict)


def cmd_summary(agg, directory) -> int:
    stats = {}
    events = agg.load_events(directory, stats=stats)
    if not events:
        print("ptdoctor: no telemetry events under %s" % directory)
        return 2
    ts0 = next((e["ts"] for e in events
                if isinstance(e.get("ts"), (int, float))), None)
    ts1 = next((e["ts"] for e in reversed(events)
                if isinstance(e.get("ts"), (int, float))), None)
    span = (ts1 - ts0) if ts0 is not None and ts1 is not None else 0.0
    restarts = sum(1 for e in events
                   if e.get("event") in ("gang_restart", "worker_restart"))
    hangs = sum(1 for e in events if e.get("event") == "worker_hang")
    retraces = {}
    for e in events:
        if e.get("event") == "retrace":
            eng = e.get("engine", "?")
            retraces[eng] = retraces.get(eng, 0) + 1
    ranks = _collect(events)

    print("run: %s" % os.path.abspath(directory))
    print("  events=%d  span=%.1fs  ranks=%s" %
          (len(events), span, sorted(ranks) or "none"))
    print("  restarts=%d  hangs=%d  torn_lines=%d" %
          (restarts, hangs, stats.get("skipped", 0)))
    # topology: world size per restart round — launch_start opens round 0;
    # a gang_shrink moves the run to a smaller world and any
    # checkpoint_reshard shows the restore crossing the topology change
    # (docs/RESILIENCE.md "Elastic topology changes")
    worlds = []
    for e in events:
        ev = e.get("event")
        if ev == "launch_start" and e.get("world") is not None:
            worlds.append((0, int(e["world"])))
        elif ev == "gang_restart" and e.get("world") is not None:
            worlds.append((int(e.get("round", len(worlds))),
                           int(e["world"])))
        elif ev == "gang_shrink" and e.get("to_world") is not None:
            worlds.append((int(e.get("round", len(worlds))),
                           int(e["to_world"])))
    shrink_evs = [e for e in events if e.get("event") == "gang_shrink"]
    reshard_evs = [e for e in events
                   if e.get("event") == "checkpoint_reshard"]
    if len(worlds) > 1 or shrink_evs or reshard_evs:
        print("  topology: " + "  ".join(
            "round%d=world%d" % (rnd, w) for rnd, w in worlds))
        for e in shrink_evs:
            print("    shrink: world %s -> %s (rank %s %s x%s, round %s)"
                  % (e.get("from_world"), e.get("to_world"),
                     e.get("failed_rank"), e.get("cause"),
                     e.get("streak"), e.get("round")))
        for e in reshard_evs:
            print("    reshard: world %s -> %s (%s) %s" %
                  (e.get("from_world"), e.get("to_world"), e.get("mode"),
                   e.get("path", "")))
    if retraces:
        print("  retraces: " + "  ".join(
            "%s=%d" % kv for kv in sorted(retraces.items())))
    # compile section: persistent-cache effectiveness + the restart tax.
    # Counters from rank snapshots when present, else the compile_cache /
    # retrace journal events (a journal-only dir still gets an answer).
    cc_hits = _counter_total(agg, directory, "pt_compile_cache_hits_total")
    cc_miss = _counter_total(agg, directory, "pt_compile_cache_misses_total")
    if cc_hits is None and cc_miss is None:
        ev_hits = sum(int(e.get("hits", 0) or 0) for e in events
                      if e.get("event") == "compile_cache")
        ev_miss = sum(int(e.get("cache_misses", 0) or 0) for e in events
                      if e.get("event") == "retrace")
        if ev_hits or ev_miss:
            cc_hits, cc_miss = ev_hits, ev_miss
    compile_s = _counter_by_label(agg, directory,
                                  "pt_jit_compile_seconds_total", "engine")
    if cc_hits is not None or cc_miss is not None or compile_s:
        line = "  compile:"
        if cc_hits is not None or cc_miss is not None:
            line += "  cache hits=%d misses=%d" % (int(cc_hits or 0),
                                                   int(cc_miss or 0))
        if compile_s:
            line += "  compile_s " + "  ".join(
                "%s=%.2f" % (k, v) for k, v in sorted(compile_s.items()))
        print(line)
    # restart-to-first-step per gang round: did the warm compile cache
    # actually shrink the restart tax? Flag rounds slower than round 0.
    r2fs = agg.restart_to_first_step(events)
    if len(r2fs) > 1 or (r2fs and restarts):
        parts = []
        base = next((e.get("seconds") for e in r2fs
                     if e["round"] == 0 and "seconds" in e), None)
        for entry in r2fs:
            if "seconds" not in entry:
                parts.append("round%d=never-stepped" % entry["round"])
                continue
            part = "round%d=%.1fs" % (entry["round"], entry["seconds"])
            if (base is not None and entry["round"] != 0
                    and entry["seconds"] > base):
                part += " REGRESSED(+%.1fs vs round0)" % (
                    entry["seconds"] - base)
            parts.append(part)
        print("  restart-to-first-step: " + "  ".join(parts))
    # attention / conv lowering mix — "is the fast path actually on?" from
    # the same counters bench.py reports (pt_attn_path_total etc.)
    attn = _counter_by_label(agg, directory, "pt_attn_path_total", "path")
    if attn:
        print("  attn paths: " + "  ".join(
            "%s=%d" % (k, int(v)) for k, v in sorted(attn.items())))
    convp = _counter_by_label(agg, directory, "pt_conv_path_total", "algo")
    if convp:
        print("  conv paths: " + "  ".join(
            "%s=%d" % (k, int(v)) for k, v in sorted(convp.items())))
    upd = _counter_by_label(agg, directory,
                            "pt_optimizer_update_path_total", "path")
    if upd:
        print("  optimizer update paths: " + "  ".join(
            "%s=%d" % (k, int(v)) for k, v in sorted(upd.items())))
    # serving: request/token counters + the prefill bucket mix from the
    # generation engine's pt_serve_* series (docs/SERVING.md)
    admitted = _counter_total(agg, directory, "pt_serve_admitted_total")
    completed = _counter_total(agg, directory, "pt_serve_completed_total")
    serve_toks = _counter_total(agg, directory, "pt_serve_tokens_total")
    serve_buckets = _counter_by_label(
        agg, directory, "pt_serve_prefill_bucket_total", "bucket")
    if admitted is not None or completed is not None or serve_buckets:
        print("  serving: admitted=%d  completed=%d  tokens=%d" % (
            int(admitted or 0), int(completed or 0), int(serve_toks or 0)))
        if serve_buckets:
            print("    prefill buckets: " + "  ".join(
                "%s=%d" % (k, int(v)) for k, v in sorted(
                    serve_buckets.items(), key=lambda kv: int(kv[0]))))
        # shared-prefix KV reuse: hit rate is the serving-cost story
        # (a hit prefills only the suffix — docs/SERVING.md)
        pfx_hits = _counter_total(agg, directory,
                                  "pt_prefix_cache_hits_total")
        pfx_miss = _counter_total(agg, directory,
                                  "pt_prefix_cache_misses_total")
        pfx_evic = _counter_total(agg, directory,
                                  "pt_prefix_cache_evictions_total")
        if pfx_hits is not None or pfx_miss is not None:
            total = (pfx_hits or 0) + (pfx_miss or 0)
            rate = (100.0 * (pfx_hits or 0) / total) if total else 0.0
            print("    prefix cache: hits=%d  misses=%d  evictions=%d"
                  "  hit_rate=%.0f%%" % (int(pfx_hits or 0),
                                         int(pfx_miss or 0),
                                         int(pfx_evic or 0), rate))
        # per-replica view from the rollup's serving block (written by
        # rollup_metrics; regenerate with aggregate_run if stale)
        serving_roll = None
        rollup_path = os.path.join(directory, "metrics-rollup.json")
        if os.path.exists(rollup_path):
            try:
                with open(rollup_path) as f:
                    serving_roll = (json.load(f) or {}).get("serving")
            except (OSError, ValueError):
                serving_roll = None
        for src in sorted((serving_roll or {}).get("per_source") or {}):
            vals = serving_roll["per_source"][src]
            parts = []
            for key in ("pt_serve_admitted_total",
                        "pt_serve_completed_total",
                        "pt_serve_tokens_total"):
                v = vals.get(key)
                if isinstance(v, (int, float)):
                    parts.append("%s=%d" % (
                        key[len("pt_serve_"):-len("_total")], int(v)))
            ttft = vals.get("pt_serve_ttft_seconds")
            if isinstance(ttft, dict) and ttft.get("count"):
                parts.append("ttft_mean=%.0fms" %
                             (1e3 * ttft["sum"] / ttft["count"]))
            if parts:
                print("    %s: %s" % (src, "  ".join(parts)))
    # SLO control plane (serving/slo.py): shed counters + the live
    # p99-vs-budget gauges reduce to an overload verdict — did the
    # engine collapse, shed-and-hold, or never come under pressure?
    _slo_section(agg, directory, events)
    # static-analysis findings recorded into this run dir (ptlint
    # --telemetry-dir, or emit_findings from a test harness)
    lint = _counter_by_label(agg, directory, "pt_lint_findings_total",
                             "rule")
    lint_sev = _counter_by_label(agg, directory, "pt_lint_findings_total",
                                 "severity")
    stale_sup = sum(1 for e in events
                    if e.get("event") == "lint_stale_suppression")
    if lint or stale_sup:
        line = "  lint findings: " + ("  ".join(
            "%s=%d" % (k, int(v)) for k, v in sorted(lint.items()))
            or "none")
        if lint_sev:
            line += "  (" + " ".join(
                "%s=%d" % (k, int(v))
                for k, v in sorted(lint_sev.items())) + ")"
        if stale_sup:
            line += "  stale-suppressions=%d" % stale_sup
        print(line)
        print("    (ptdoctor lint %s for details)" % directory)
    stalest = None
    for r in sorted(ranks):
        st = ranks[r]
        line = "  rank %s: events=%d" % (r, st["events"])
        rate = _step_rate(st["steps"])
        if rate:
            line += "  step-rate=%.2f/s (%.2f -> %.2f)" % rate
        if st["last_step"] is not None:
            line += "  last-alive step=%d" % st["last_step"]
        if st["last_ts"] is not None and ts1 is not None:
            behind = ts1 - st["last_ts"]
            line += "  last-seen %s (-%.1fs)" % (_fmt_ts(st["last_ts"]),
                                                 behind)
            if stalest is None or behind > stalest[1]:
                stalest = (r, behind)
        print(line)
    if stalest is not None and len(ranks) > 1:
        print("  stalest rank: %d (%.1fs behind run end)" % stalest)
    for man in _manifests(directory):
        line = "  crash bundle: rank=%s reason=%s" % (
            man.get("rank"), man.get("reason"))
        if man.get("last_step") is not None:
            line += " last-alive step=%s" % man["last_step"]
        if man.get("error"):
            line += " error=%r" % man["error"]
        print(line)
        print("    %s (%d ring events)" %
              (man["_dir"], man.get("ring_events", 0)))
    return 0


def cmd_timeline(agg, directory, last=None) -> int:
    events = agg.load_events(directory)
    if not events:
        print("ptdoctor: no telemetry events under %s" % directory)
        return 2
    if last:
        events = events[-last:]
    for rec in events:
        rank = rec.get("rank", _rank_of(rec))
        extra = {k: v for k, v in rec.items()
                 if k not in ("ts", "rank", "event", "src", "run_id",
                              "host", "pid")}
        print("%s  r%-2s %-20s %s" % (
            _fmt_ts(rec.get("ts")),
            "?" if rank is None else rank,
            rec.get("event", "?"),
            json.dumps(extra, default=str) if extra else ""))
    return 0


def cmd_crash(agg, directory) -> int:
    mans = _manifests(directory)
    if not mans:
        print("ptdoctor: no crash bundles under %s" %
              os.path.join(directory, "crash"))
        return 0
    for man in mans:
        bdir = man.pop("_dir")
        print("== %s" % bdir)
        for k in ("reason", "rank", "pid", "host", "iso", "last_step",
                  "error", "last_dispatch", "last_compile"):
            if man.get(k) is not None:
                print("  %-13s %s" % (k, man[k]))
        ring = os.path.join(bdir, "ring.jsonl")
        if os.path.exists(ring):
            tail = agg.read_journal(ring)[-10:]
            print("  last %d ring events:" % len(tail))
            for rec in tail:
                print("    %s %s" % (_fmt_ts(rec.get("ts")),
                                     rec.get("event", "?")))
        stacks = os.path.join(bdir, "stacks.txt")
        if os.path.exists(stacks):
            with open(stacks, errors="replace") as f:
                head = f.read(2000)
            print("  stacks.txt (head):")
            for line in head.splitlines()[:20]:
                print("    " + line)
    return 0


def cmd_lint(agg, directory) -> int:
    """Every lint_finding / lint_stale_suppression event in the run dir,
    rendered like ptlint's own output (docs/STATIC_ANALYSIS.md)."""
    events = agg.load_events(directory)
    finds = [e for e in events if e.get("event") == "lint_finding"]
    stale = [e for e in events
             if e.get("event") == "lint_stale_suppression"]
    if not finds and not stale:
        print("ptdoctor: no lint events under %s" % directory)
        return 0
    finds.sort(key=lambda e: (str(e.get("path", "")), e.get("line", 0)
                              if isinstance(e.get("line"), (int, float))
                              else 0))
    for e in finds:
        loc = str(e.get("path", "?"))
        if e.get("line"):
            loc += ":%s" % e["line"]
        sym = " (%s)" % e["symbol"] if e.get("symbol") else ""
        print("%s: %s: [%s] %s%s" % (loc, e.get("severity", "?"),
                                     e.get("rule", "?"),
                                     e.get("message", ""), sym))
    for e in stale:
        print("STALE suppression: [%s] %s %s" %
              (e.get("rule"), e.get("path"), e.get("fingerprint")))
    sev = {}
    for e in finds:
        sev[e.get("severity", "?")] = sev.get(e.get("severity", "?"), 0) + 1
    print("lint: %d finding(s)%s, %d stale suppression(s)" %
          (len(finds),
           " (" + " ".join("%s=%d" % kv for kv in sorted(sev.items()))
           + ")" if sev else "",
           len(stale)))
    return 0


def _fmt_qty(v) -> str:
    """1234567 -> '1.23M' (flops / bytes at step-card granularity)."""
    if not isinstance(v, (int, float)):
        return str(v)
    for unit, div in (("T", 1e12), ("G", 1e9), ("M", 1e6), ("K", 1e3)):
        if abs(v) >= div:
            return "%.2f%s" % (v / div, unit)
    return "%g" % v


def _decomposition(title, total, n, kids, shares=None):
    """Render one parent-span breakdown: each child's total and share of
    the parent total, the unattributed remainder, and (optionally) a
    critical-path line over coarse categories."""
    print("== %s (%d, %.1f ms total)" % (title, n, total))
    attributed = 0.0
    for name, tot in sorted(kids.items(), key=lambda kv: -kv[1]):
        attributed += tot
        print("  %-18s %12.1f ms  %5.1f%%" % (name, tot,
                                              100.0 * tot / total))
    print("  %-18s %12.1f ms  %5.1f%%" % (
        "(unattributed)", total - attributed,
        100.0 * (total - attributed) / total))
    if shares:
        print("  critical path: " + "  ".join(
            "%s %.1f%%" % (k, 100.0 * v / total) for k, v in shares))


def cmd_profile(agg, directory) -> int:
    """Where did the time go: per-span latency table from the `span`
    journal events, step / serve_request decompositions, and the static
    step card (analysis/cost_pass.py) when the run dir has one."""
    events = agg.load_events(directory)
    sp = [e for e in events if e.get("event") == "span"
          and isinstance(e.get("dur_ms"), (int, float))]
    if not sp:
        print("ptdoctor: no span events under %s (spans are emitted "
              "when PADDLE_TPU_TELEMETRY_DIR is set at run time)"
              % directory)
        return 2
    by_name = {}
    children = {}          # parent name -> {child name: summed dur_ms}
    for e in sp:
        name = e.get("name", "?")
        by_name.setdefault(name, []).append(float(e["dur_ms"]))
        par = e.get("parent")
        if par:
            kids = children.setdefault(par, {})
            kids[name] = kids.get(name, 0.0) + float(e["dur_ms"])
    print("== spans (%d events)" % len(sp))
    print("  %-18s %6s %12s %10s %10s %10s" %
          ("name", "n", "total_ms", "mean_ms", "p50_ms", "p95_ms"))
    for name in sorted(by_name, key=lambda n: -sum(by_name[n])):
        vs = by_name[name]
        print("  %-18s %6d %12.1f %10.2f %10.2f %10.2f" % (
            name, len(vs), sum(vs), sum(vs) / len(vs),
            agg.percentile(vs, 50), agg.percentile(vs, 95)))
    step_total = sum(by_name.get("step", []))
    if step_total > 0:
        kids = children.get("step", {})
        compute = kids.get("compile", 0.0) + kids.get("dispatch", 0.0)
        feed = kids.get("feed", 0.0) + kids.get("feed_wait", 0.0)
        host = kids.get("host", 0.0)
        other = max(0.0, step_total - compute - feed - host)
        _decomposition("step decomposition", step_total,
                       len(by_name["step"]), kids,
                       shares=[("compute", compute), ("feed", feed),
                               ("host", host), ("other", other)])
    serve_total = sum(by_name.get("serve_request", []))
    if serve_total > 0:
        kids = children.get("serve_request", {})
        _decomposition("serve_request decomposition", serve_total,
                       len(by_name["serve_request"]), kids)
        ttft = kids.get("queue_wait", 0.0) + kids.get("prefill", 0.0)
        n = len(by_name["serve_request"])
        print("  ttft (queue_wait + prefill): %.1f ms total, "
              "%.1f ms/request" % (ttft, ttft / n))
    import glob
    for path in sorted(glob.glob(os.path.join(directory,
                                              "step_card*.json"))):
        try:
            with open(path) as f:
                card = json.load(f)
        except (OSError, ValueError):
            continue
        if not isinstance(card, dict):
            continue
        print("== step card: %s (%s)" % (card.get("label", "?"),
                                         os.path.basename(path)))
        print("  eqns=%s  flops=%s  hbm_bytes=%s  intensity=%s" % (
            card.get("eqns"), _fmt_qty(card.get("flops")),
            _fmt_qty(card.get("hbm_bytes")),
            card.get("arithmetic_intensity")))
        col = card.get("collectives") or {}
        if col.get("count"):
            print("  collectives: %d ops, %s bytes" % (
                col["count"], _fmt_qty(col.get("bytes", 0))))
            for c in (col.get("inventory") or [])[:5]:
                print("    %s %s%s (%s)" % (
                    c.get("primitive"), c.get("dtype"), c.get("shape"),
                    _fmt_qty(c.get("bytes", 0))))
        for r in (card.get("dominant_eqns") or [])[:5]:
            print("  top: %-22s out=%-16s flops=%-8s bytes=%s" % (
                r.get("primitive"), r.get("out_shape"),
                _fmt_qty(r.get("flops", 0)), _fmt_qty(r.get("bytes", 0))))
        xc = card.get("xla_cost")
        if isinstance(xc, dict) and xc:
            print("  xla: " + "  ".join(
                "%s=%s" % (k, _fmt_qty(v))
                for k, v in sorted(xc.items())))
    return 0


def _load_device_peaks():
    path = os.path.join(_REPO, "paddle_tpu", "observability",
                        "device_peaks.py")
    spec = importlib.util.spec_from_file_location("_pt_device_peaks", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _roofline_peaks(kind):
    """(peak_tflops, peak_gbps, source) for a device kind. Env overrides
    PADDLE_TPU_PEAK_TFLOPS / PADDLE_TPU_PEAK_GBPS win over the table;
    either value may be None (honest "unknown device" — never guessed)."""
    tf = gb = None
    env_tf = os.environ.get("PADDLE_TPU_PEAK_TFLOPS")
    env_gb = os.environ.get("PADDLE_TPU_PEAK_GBPS")
    try:
        tf = float(env_tf) if env_tf else None
    except ValueError:
        tf = None
    try:
        gb = float(env_gb) if env_gb else None
    except ValueError:
        gb = None
    if tf is not None and gb is not None:
        return tf, gb, "env"
    row = _load_device_peaks().lookup(kind)
    if row is not None:
        return (tf if tf is not None else row[0],
                gb if gb is not None else row[1],
                "env+table" if (tf is not None or gb is not None)
                else "table")
    if tf is not None or gb is not None:
        return tf, gb, "env"
    return None, None, None


def cmd_roofline(agg, directory) -> int:
    """Name the limiter: join each static step card (FLOPs, unfused HBM
    bytes, collective operand bytes — analysis/cost_pass.py) with the
    measured step spans and the per-device-kind peak table, and say
    whether the config is compute-bound, memory-bound,
    exposed-collective, or host-or-feed-bound — with achieved vs peak
    TFLOP/s and GB/s so "MFU is low" becomes a named cause."""
    import glob
    cards = []
    for path in sorted(glob.glob(os.path.join(directory,
                                              "step_card*.json"))):
        try:
            with open(path) as f:
                card = json.load(f)
        except (OSError, ValueError):
            continue
        if isinstance(card, dict) and card.get("flops"):
            cards.append((os.path.basename(path), card))
    if not cards:
        print("ptdoctor: no step_card*.json with a flops count under %s "
              "(emit one with analysis.cost_pass.write_step_card)"
              % directory)
        return 2
    events = agg.load_events(directory)
    steps = [float(e["dur_ms"]) for e in events
             if e.get("event") == "span" and e.get("name") == "step"
             and isinstance(e.get("dur_ms"), (int, float))]
    if not steps:
        print("ptdoctor: no measured `step` spans under %s — roofline "
              "needs both the static card and a measured run "
              "(set PADDLE_TPU_TELEMETRY_DIR at run time)" % directory)
        return 2
    # steady-state step time: p50 when there is history, min for tiny
    # smoke runs where the compile-bearing first step would skew p50
    step_ms = (agg.percentile(steps, 50) if len(steps) >= 4
               else min(steps))
    # host/feed share from the span tree, with compile excluded — the
    # question is what limits the steady-state step, not the first one
    kids = {}
    for e in events:
        if e.get("event") == "span" and e.get("parent") == "step" \
                and isinstance(e.get("dur_ms"), (int, float)):
            name = e.get("name", "?")
            kids[name] = kids.get(name, 0.0) + float(e["dur_ms"])
    step_total = sum(steps)
    noncompile = max(step_total - kids.get("compile", 0.0), 1e-9)
    hostfeed = (kids.get("feed", 0.0) + kids.get("feed_wait", 0.0)
                + kids.get("host", 0.0))
    hostfeed_share = min(hostfeed / noncompile, 1.0)
    rc = 0
    for fname, card in cards:
        flops = float(card.get("flops") or 0)
        hbm = float(card.get("hbm_bytes") or 0)
        col = card.get("collectives") or {}
        col_bytes = float(col.get("bytes") or 0)
        kind = card.get("device_kind") or "unknown"
        tf, gb, src = _roofline_peaks(kind)
        step_s = step_ms / 1e3
        ach_tf = flops / step_s / 1e12
        ach_gb = hbm / step_s / 1e9
        print("== roofline: %s (%s)" % (card.get("label", "?"), fname))
        print("  static: flops=%s  hbm_bytes=%s  collective_bytes=%s  "
              "intensity=%.2f flop/byte" % (
                  _fmt_qty(flops), _fmt_qty(hbm), _fmt_qty(col_bytes),
                  flops / hbm if hbm else float("inf")))
        fused = float(card.get("hbm_bytes_fused") or 0)
        if fused and hbm and fused < hbm:
            print("  fusion headroom: %s of %s HBM bytes (%.1f%%) are "
                  "elementwise chain round-trips a fused kernel removes "
                  "-> fused intensity %.2f flop/byte" % (
                      _fmt_qty(hbm - fused), _fmt_qty(hbm),
                      100.0 * (hbm - fused) / hbm,
                      flops / fused if fused else float("inf")))
        print("  measured: step=%.3f ms (n=%d)  feed+host share=%.1f%% "
              "of non-compile step time" % (step_ms, len(steps),
                                            100.0 * hostfeed_share))
        if tf is not None and gb is not None:
            ideal_comp_ms = flops / (tf * 1e12) * 1e3
            ideal_mem_ms = hbm / (gb * 1e9) * 1e3
            headroom_ms = max(0.0, step_ms - max(ideal_comp_ms,
                                                 ideal_mem_ms))
            print("  peaks (%s, device %r): %.1f TFLOP/s, %.0f GB/s"
                  % (src, kind, tf, gb))
            print("  achieved: %.3f TFLOP/s (%.1f%% of peak)  "
                  "%.2f GB/s (%.1f%% of peak)" % (
                      ach_tf, 100.0 * ach_tf / tf,
                      ach_gb, 100.0 * ach_gb / gb))
            if col_bytes:
                print("  exposed-collective headroom: %.3f ms/step "
                      "(measured %.3f - ideal %.3f)" % (
                          headroom_ms, step_ms,
                          max(ideal_comp_ms, ideal_mem_ms)))
            if hostfeed_share >= 0.4:
                print("  limiter: host-or-feed-bound — feed+host is "
                      "%.1f%% of non-compile step time"
                      % (100.0 * hostfeed_share))
            elif col_bytes and headroom_ms / step_ms >= 0.25:
                print("  limiter: exposed-collective — %.1f%% of the "
                      "step is neither ideal compute nor ideal HBM "
                      "traffic and the card carries %s collective bytes"
                      % (100.0 * headroom_ms / step_ms,
                         _fmt_qty(col_bytes)))
            elif ideal_comp_ms >= ideal_mem_ms:
                print("  limiter: compute-bound — ideal compute %.3f ms "
                      ">= ideal HBM %.3f ms at this intensity" % (
                          ideal_comp_ms, ideal_mem_ms))
            else:
                print("  limiter: memory-bound — ideal HBM %.3f ms > "
                      "ideal compute %.3f ms at this intensity" % (
                          ideal_mem_ms, ideal_comp_ms))
        else:
            print("  peaks: unknown device %r — no table entry; set "
                  "PADDLE_TPU_PEAK_TFLOPS and PADDLE_TPU_PEAK_GBPS to "
                  "calibrate" % kind)
            print("  achieved: %.3f TFLOP/s  %.2f GB/s (no peak to "
                  "compare against)" % (ach_tf, ach_gb))
            if hostfeed_share >= 0.4:
                print("  limiter: host-or-feed-bound — feed+host is "
                      "%.1f%% of non-compile step time"
                      % (100.0 * hostfeed_share))
            elif col_bytes and hbm and col_bytes >= 0.2 * hbm:
                print("  limiter: exposed-collective (static) — "
                      "collectives move %s of %s total HBM bytes"
                      % (_fmt_qty(col_bytes), _fmt_qty(hbm)))
            elif hbm and flops / hbm < 50.0:
                print("  limiter: memory-bound (static heuristic — "
                      "intensity %.2f flop/byte is below typical "
                      "machine balance; peaks unknown)"
                      % (flops / hbm))
            else:
                print("  limiter: compute-bound (static heuristic — "
                      "intensity %.2f flop/byte; peaks unknown)"
                      % (flops / hbm if hbm else float("inf")))
    return rc


def cmd_trace(directory, out=None) -> int:
    """Export the run dir's journals as one Perfetto/chrome-trace JSON
    (observability/traceview.py — same serializer the host profiler
    uses, so the two artifacts open identically)."""
    tv = _load_traceview()
    path, n_events, n_tracks = tv.export_trace(directory, out_path=out)
    if not n_events:
        print("ptdoctor: no span events under %s (spans are emitted "
              "when PADDLE_TPU_TELEMETRY_DIR is set at run time)"
              % directory)
        return 2
    print("wrote %s  (%d events, %d track(s))" % (path, n_events, n_tracks))
    print("open in https://ui.perfetto.dev or chrome://tracing")
    return 0


def _fused_kernel_row(r):
    """Trend row for a fused_kernels_bench result: value column is the
    speedup-vs-XLA ratio; pallas_ms/speedup get regression flags."""
    return {"config": r["config"], "value": r.get("speedup"),
            "unit": "x vs xla",
            "pallas_ms": r.get("pallas_ms"),
            "speedup": r.get("speedup")}


def _bench_rows(directory):
    """((sort_key, label, rows), ...) per BENCH_*.json file, oldest
    first. Each row: {config, value, unit, step_ms, mfu, compile_s,
    hbm_peak} with absent fields None. Failed runs yield rows=None
    (listed, not trended)."""
    import glob
    out = []
    for path in glob.glob(os.path.join(directory, "BENCH_*.json")):
        base = os.path.basename(path)[len("BENCH_"):-len(".json")]
        if base.startswith("r") and base[1:].isdigit():
            key = (0, int(base[1:]), base)      # r01..rNN: oldest history
        else:
            key = (1, 0, base)                  # then TPU_<ts> by name
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, ValueError):
            out.append((key, base, None))
            continue
        if not isinstance(data, dict):
            continue
        rows = []
        if "results" in data:                   # tools/bench.py --save shape
            for r in data.get("results") or []:
                if not isinstance(r, dict) or not r.get("config"):
                    continue
                if "pallas_ms" in r:            # fused_kernels_bench row
                    rows.append(_fused_kernel_row(r))
                    continue
                rows.append({"config": r["config"],
                             "value": r.get("throughput"),
                             "unit": r.get("unit"),
                             "step_ms": r.get("step_ms"),
                             "mfu": r.get("mfu"),
                             "compile_s": r.get("compile_s"),
                             "hbm_peak": r.get("hbm_peak")})
            # serving rows (the `inference` rows of banked results) trend
            # alongside training: throughput column = tokens_per_s, and
            # ttft p95 gets its own column + regression flag
            for r in data.get("inference") or []:
                if isinstance(r, dict) and r.get("config"):
                    rows.append({"config": r["config"],
                                 "value": r.get("tokens_per_s"),
                                 "unit": r.get("unit") or "tok/s",
                                 "tokens_per_s": r.get("tokens_per_s"),
                                 "ttft_ms_p95": r.get("ttft_ms_p95")})
        else:                                   # driver round shape
            parsed = data.get("parsed")
            if data.get("rc") not in (0, None) or not isinstance(
                    parsed, dict):
                out.append((key, base, None))   # failed / unparsed round
                continue
            config = str(parsed.get("metric", base))
            for suffix in ("_tokens_per_sec_per_chip",
                           "_images_per_sec_per_chip"):
                if config.endswith(suffix):
                    config = config[:-len(suffix)]
            rows.append({"config": config, "value": parsed.get("value"),
                         "unit": parsed.get("unit"),
                         "step_ms": parsed.get("step_ms"),
                         "mfu": parsed.get("mfu"),
                         "compile_s": parsed.get("compile_s"),
                         "hbm_peak": parsed.get("hbm_peak")})
            # fused_kernels_bench headline carries its per-kernel rows
            # inline; trend each kernel as its own config block
            for r in parsed.get("results") or []:
                if isinstance(r, dict) and r.get("config") \
                        and "pallas_ms" in r:
                    rows.append(_fused_kernel_row(r))
        out.append((key, base, rows))
    out.sort(key=lambda e: e[0])
    return out


def cmd_bench(directory) -> int:
    """Trend table over the checked-in BENCH_*.json results: one block
    per config, rows oldest->newest, each compared against the BEST
    prior row (not the previous one — a single slow round must not
    reset the bar). Flags: step_ms >110% of best, MFU <90% of best,
    compile_s >110% of best, hbm_peak >110% of best; serving rows
    (`inference`) flag tokens_per_s <90% of best and ttft_ms_p95
    >110% of best; fused-kernel rows (fused_kernels_bench) flag
    pallas_ms >110% of best and speedup <90% of best."""
    files = _bench_rows(directory)
    if not files:
        print("ptdoctor: no BENCH_*.json under %s" % directory)
        return 2
    failed = [label for _, label, rows in files if rows is None]
    by_config = {}
    for _, label, rows in files:
        for row in rows or []:
            by_config.setdefault(row["config"], []).append((label, row))
    for config in sorted(by_config):
        hist = by_config[config]
        unit = next((r.get("unit") for _, r in hist if r.get("unit")), "")
        print("== %s%s" % (config, "  (%s)" % unit if unit else ""))
        print("  %-22s %12s %10s %7s %10s %9s %9s  %s" %
              ("run", "value", "step_ms", "mfu", "compile_s", "hbm_peak",
               "ttft_p95", "flags"))
        best = {}                   # metric -> best value over PRIOR rows
        for label, row in hist:
            flags = []
            for metric, better_low, tol in (("step_ms", True, 1.10),
                                            ("mfu", False, 0.90),
                                            ("compile_s", True, 1.10),
                                            ("hbm_peak", True, 1.10),
                                            ("tokens_per_s", False, 0.90),
                                            ("ttft_ms_p95", True, 1.10),
                                            ("pallas_ms", True, 1.10),
                                            ("speedup", False, 0.90)):
                v = row.get(metric)
                if not isinstance(v, (int, float)):
                    continue
                b = best.get(metric)
                if b is not None and (
                        v > b * tol if better_low else v < b * tol):
                    flags.append("%s REGRESSED (%.4g vs best %.4g)"
                                 % (metric, v, b))
                if b is None or (v < b if better_low else v > b):
                    best[metric] = v
            print("  %-22s %12s %10s %7s %10s %9s %9s  %s" % (
                label,
                "%.4g" % row["value"]
                if isinstance(row.get("value"), (int, float)) else "-",
                "%.4g" % row["step_ms"]
                if isinstance(row.get("step_ms"), (int, float)) else "-",
                "%.3f" % row["mfu"]
                if isinstance(row.get("mfu"), (int, float)) else "-",
                "%.4g" % row["compile_s"]
                if isinstance(row.get("compile_s"), (int, float)) else "-",
                _fmt_qty(row["hbm_peak"])
                if isinstance(row.get("hbm_peak"),
                              (int, float)) else "-",
                "%.4g" % row["ttft_ms_p95"]
                if isinstance(row.get("ttft_ms_p95"),
                              (int, float)) else "-",
                "; ".join(flags)))
    if failed:
        print("failed/unparsed runs (not trended): " + "  ".join(failed))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="ptdoctor", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    sub = ap.add_subparsers(dest="cmd", required=True)
    for name in ("summary", "timeline", "crash", "lint", "profile",
                 "roofline", "trace", "bench"):
        p = sub.add_parser(name)
        p.add_argument("dir", help="telemetry directory (--log_dir / "
                                   "telemetry_dir of the run); for "
                                   "`bench`, the dir with BENCH_*.json")
        if name == "timeline":
            p.add_argument("--last", type=int, default=None,
                           help="only the last N events")
        if name == "trace":
            p.add_argument("--out", default=None,
                           help="output path (default <dir>/trace.json)")
    args = ap.parse_args(argv)
    if not os.path.isdir(args.dir):
        print("ptdoctor: not a directory: %s" % args.dir, file=sys.stderr)
        return 2
    if args.cmd == "trace":
        return cmd_trace(args.dir, out=args.out)
    if args.cmd == "bench":
        return cmd_bench(args.dir)
    agg = _load_aggregate()
    if args.cmd == "summary":
        return cmd_summary(agg, args.dir)
    if args.cmd == "timeline":
        return cmd_timeline(agg, args.dir, last=args.last)
    if args.cmd == "lint":
        return cmd_lint(agg, args.dir)
    if args.cmd == "profile":
        return cmd_profile(agg, args.dir)
    if args.cmd == "roofline":
        return cmd_roofline(agg, args.dir)
    return cmd_crash(agg, args.dir)


if __name__ == "__main__":
    sys.exit(main())
