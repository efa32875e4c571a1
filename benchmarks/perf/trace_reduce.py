"""From a profiler trace (`.xplane.pb`) to numbers, with nothing but JAX.

    planes = load(path)              # plain lists, picklable, testable
    red = reduce(planes)             # busy, idle, per-op seconds, gaps

A device plane is one named "/device:TPU:<n>"; its "XLA Ops" line holds
one event per executed HLO operation and its "XLA Modules" line one event
per executed program. Busy time is the union of the op intervals; the
traced window is the host's `perf_trace_window` annotation where the trace
has it (the harness wraps the traced part of the run in it), otherwise the
extent of the device events. All times are averaged over the device planes.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

WINDOW_ANNOTATION = "perf_trace_window"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return paths[-1]


def load(path):
    """[(plane name, [(line name, [(event name, start_ns, dur_ns)])])]"""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            lines.append((line.name, [
                (ev.name, float(ev.start_ns), float(ev.duration_ns))
                for ev in line.events]))
        planes.append((plane.name, lines))
    return planes


def clean(name):
    """An event name as a metric-safe token."""
    return re.sub(r"[^A-Za-z0-9_.-]+", "_", name)[:64]


_HLO = re.compile(r"^%?([\w\-.]+?)(?:\.\d+)? = (.*?) ?([\w\-]+)\(")
_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")


def op_key(name):
    """An op event's name is its HLO text, `%jvp__.23 = (bf16[288,1024,64]
    {...}, ...) custom-call(...)`. The key the patterns and the breakdown
    see is `<opcode>.<instruction name without its number>.<first shape>`,
    so that the twelve layers' instances of one kernel are one row."""
    m = _HLO.match(re.sub(r"\{[^{}]*\}", "", name))
    if not m:
        return clean(name)
    shape = _SHAPE.search(m.group(2))
    return clean("%s.%s.%s" % (m.group(3), m.group(1),
                               shape.group(0) if shape else ""))


def _union(intervals):
    """Merged, sorted [(start, end)] of possibly overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _window(planes):
    for name, lines in planes:
        if name.startswith("/device:"):
            continue
        for _, events in lines:
            for ev, start, dur in events:
                if ev == WINDOW_ANNOTATION:
                    return start, start + dur
    return None


def reduce(planes):
    """{"window_s", "busy_s", "idle_share", "devices", "ops": {name:
    [seconds, calls]}, "modules": {name: [seconds, calls]}, "gaps": {name:
    seconds}} — seconds and calls are per device (averaged over the device
    planes); "modules" counts the programs that lie whole in the window."""
    dev = [(n, dict(ls)) for n, ls in planes
           if n.startswith("/device:") and dict(ls).get(OPS_LINE)]
    if not dev:
        raise ValueError("the trace has no device plane with an %r line: "
                         "no operation ran on the device" % OPS_LINE)
    win = _window(planes)
    if win is None:
        starts = [e[1] for _, ls in dev for e in ls[OPS_LINE]]
        ends = [e[1] + e[2] for _, ls in dev for e in ls[OPS_LINE]]
        win = (min(starts), max(ends))
    lo, hi = win
    ops, gaps, modules, busy = {}, {}, {}, 0.0
    for _, lines in dev:
        clipped = []
        for name, start, dur in lines[OPS_LINE]:
            s, e = max(start, lo), min(start + dur, hi)
            if e <= s:
                continue
            clipped.append((s, e))
            rec = ops.setdefault(op_key(name), [0.0, 0])
            rec[0] += (e - s) * 1e-9
            rec[1] += 1
        merged = _union(clipped)
        busy += sum(e - s for s, e in merged) * 1e-9
        # programs run one after another on a device: sorted by start they
        # are sorted by end too
        mods = sorted((s, s + d, n) for n, s, d in
                      lines.get(MODULES_LINE, []))
        starts = [m[0] for m in mods]
        ends = [m[1] for m in mods]
        for s, e, name in mods:
            if lo <= s and e <= hi:             # whole programs only
                rec = modules.setdefault(clean(_strip_id(name)), [0.0, 0])
                rec[0] += (e - s) * 1e-9
                rec[1] += 1
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                i = bisect.bisect_right(ends, g0 + 1) - 1
                j = bisect.bisect_left(starts, g1 - 1)
                key = "%s__%s" % (
                    clean(_strip_id(mods[i][2])) if i >= 0
                    else "window_open",
                    clean(_strip_id(mods[j][2])) if j < len(mods)
                    else "window_close")
                gaps[key] = gaps.get(key, 0.0) + (g1 - g0) * 1e-9
    n = float(len(dev))
    window_s = (hi - lo) * 1e-9
    return {"window_s": window_s, "busy_s": busy / n,
            "idle_share": 1.0 - busy / n / window_s, "devices": len(dev),
            "ops": {k: [v[0] / n, v[1] / n] for k, v in ops.items()},
            "modules": {k: [v[0] / n, v[1] / n] for k, v in modules.items()},
            "gaps": {k: v / n for k, v in gaps.items()}}


def _strip_id(name):
    return re.sub(r"\(\d+\)$", "", name)


def matched(red, patterns, line="ops"):
    """(seconds, calls) per device of the ops (or, with line="modules",
    of the whole programs) whose key matches any regular expression."""
    rx = [re.compile(p) for p in patterns]
    secs = calls = 0.0
    for name, (s, c) in red[line].items():
        if any(r.search(name) for r in rx):
            secs += s
            calls += c
    return secs, calls


def breakdown(red, top=10):
    ops = sorted(((k, v[0]) for k, v in red["ops"].items()),
                 key=lambda kv: -kv[1])[:top]
    gaps = sorted(red["gaps"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}
