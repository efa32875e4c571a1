"""One run of one cell of BENCHMARK.json.

    python3 benchmarks/perf/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Loads the cell's configuration and traffic files by name, refuses to run
without the TPU chips the cell asks for, warms only that cell's shapes,
measures one window, decides `correct` against the plain reference, and
prints one JSON object as the last line of standard output. With
`--trace 0` the metrics are the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics. See README.md beside this file.
"""
from __future__ import annotations

import time
T_START = time.perf_counter()       # set-up is counted from process start

import argparse
import importlib
import json
import math
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
WINDOWS = {"train_window": "train_window", "serve_open_loop": "serve_window",
           "serve_closed_loop": "serve_window"}


def _load(path):
    with open(path) as f:
        return json.load(f)


def data_file(root, conf, kind, name):
    """configs/, traffic/ and metrics/ sit side by side: a traffic mix or a
    metric is found by its name beside the configuration's own file."""
    return os.path.join(root, os.path.dirname(os.path.dirname(conf["file"])),
                        kind, name + ".json")


def load_cell(workload, root=ROOT):
    """(benchmark, cell, configuration, traffic) of a workload, each from
    the file its name points at."""
    bench = _load(os.path.join(root, "BENCHMARK.json"))
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise SystemExit("run.py: no workload %r in BENCHMARK.json (has: %s)"
                         % (workload, ", ".join(sorted(cells))))
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = _load(os.path.join(root, conf["file"]))
    traffic = _load(data_file(root, conf, "traffic", cell["traffic"]))
    return bench, cell, conf, cfg, traffic


def cell_metrics(bench, cell, group):
    """The metrics of `group` this cell reports: those without a
    `workloads` list, and those that list the cell."""
    return [m for m in bench[group]
            if "workloads" not in m or cell["name"] in m["workloads"]]


def require_tpu(chips):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit("run.py: needs a TPU, but jax.devices()[0].platform "
                         "is %r — not run" % devs[0].platform)
    if len(devs) < chips:
        raise SystemExit("run.py: the cell asks for %d chips, JAX finds %d "
                         "— not run" % (chips, len(devs)))
    return devs


class Run:
    """What a window needs of the harness: the cell's data, the clock, the
    configuration's family (family_<name>.py: the program's model, the
    reference, the work counts), and the instants, counters and trace of
    the window."""

    clock = staticmethod(time.perf_counter)

    def __init__(self, cell, cfg, traffic, seed, seconds, trace, devices,
                 t_start=T_START):
        import work
        self.cell, self.cfg, self.traffic = cell, cfg, traffic
        self.family = importlib.import_module("family_" + cfg["family"])
        self.seed, self.seconds, self.trace = int(seed), float(seconds), \
            bool(trace)
        self.chips = int(cell["chips"])
        self.devices = devices
        self.peaks = work.peaks(devices[0].device_kind)
        self.t_start = t_start
        self.harness, self.counts = {}, {}
        self.setup_s = self.memory_peak = self.red = None
        self._compiles = 0
        self._trace_dir = self._annotation = None
        self._listen()

    def mark(self, what):
        """Where set-up's time goes, on standard error."""
        print("setup %7.2f s  %s" % (self.clock() - self.t_start, what),
              file=sys.stderr, flush=True)

    def wrap_step(self, step):
        return step

    def wrap_engine(self, engine):
        return engine

    # -- the window's instants and counters ------------------------------

    def _listen(self):
        import jax

        def on_duration(event, _secs, **_kw):
            if event.endswith("backend_compile_duration"):
                self._compiles += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def _registry(self):
        from paddle_tpu.observability import metrics
        return metrics.REGISTRY.snapshot()

    def open_window(self, t_open=None):
        self.t_open = self.clock() if t_open is None else t_open
        self.setup_s = self.t_open - self.t_start
        self.mark("window opens")
        self._reg0, self._compiles0 = self._registry(), self._compiles

    def close_window(self):
        from paddle_tpu.jit import compile_cache
        self._reg1, self.t_closed = self._registry(), self.clock()
        hits, misses = compile_cache.totals()
        self.harness.update({
            "compiles_in_window": self._compiles - self._compiles0,
            "cache_hits": hits, "cache_misses": misses})
        peak = 0
        for d in self.devices[:self.chips]:
            peak = max(peak, int((d.memory_stats() or {}).get(
                "peak_bytes_in_use", 0)))
        self.memory_peak = peak

    def registry_delta(self, series):
        """What a registry series grew by between window open and close."""
        a, b = _series(self._reg0, series), _series(self._reg1, series)
        return (b["sum"] if b else 0.0) - (a["sum"] if a else 0.0)

    def trace_start(self):
        import jax
        from trace_reduce import WINDOW_ANNOTATION
        self._trace_dir = tempfile.mkdtemp(prefix="perf_trace_")
        jax.profiler.start_trace(self._trace_dir)
        self._annotation = jax.profiler.TraceAnnotation(WINDOW_ANNOTATION)
        self._annotation.__enter__()

    def trace_stop(self):
        import jax
        self._annotation.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def reduce_trace(self):
        import shutil
        import trace_reduce
        if self._trace_dir is None:
            return None
        try:
            self.red = trace_reduce.reduce(trace_reduce.load(
                trace_reduce.find_xplane(self._trace_dir)))
        finally:
            shutil.rmtree(self._trace_dir, ignore_errors=True)
        return self.red

    # -- per-layer metrics: one small reader per `source` kind -----------

    def read_metric(self, spec):
        """The value of one per-layer metric, or None when its reader
        finds nothing to read."""
        kind = spec["source"]
        if kind == "harness":
            return self.harness.get(spec["key"])
        if kind == "registry":
            a = _series(self._reg0, spec["series"], spec.get("labels"))
            b = _series(self._reg1, spec["series"], spec.get("labels"))
            if b is None:
                return None
            a = a or {"sum": 0.0, "count": 0}
            if spec["stat"] == "mean":
                n = b["count"] - a["count"]
                return (b["sum"] - a["sum"]) / n if n else None
            return b["sum"] - a["sum"]
        if kind == "trace":
            return self._read_trace(spec)
        raise ValueError("unknown metric source %r" % kind)

    def _read_trace(self, spec):
        import trace_reduce
        red = self.red
        if red is None:
            return None
        if spec["stat"] == "idle_share":
            return 100.0 * red["idle_share"]
        secs, calls = trace_reduce.matched(red, spec["patterns"],
                                           spec.get("line", "ops"))
        if not calls:
            return None
        if spec["stat"] == "time_share":
            return 100.0 * secs / red["busy_s"]
        if spec["stat"] == "roofline":
            mod, fn = spec["work"].split(":")
            try:
                flops, nbytes = getattr(importlib.import_module(mod), fn)(
                    self.cfg, self.traffic, self.counts)
            except KeyError:            # the window counted no such work
                return None
            calls /= float(spec.get("events_per_call", 1))
            least = max(flops / self.peaks["flops"],
                        nbytes / self.peaks["bytes_per_s"])
            return 100.0 * least / (secs / calls)
        raise ValueError("unknown trace stat %r" % spec["stat"])


def _series(snapshot, name, labels=None):
    """{"sum", "count"} of a registry series (a counter's value is its
    sum), summed over the label sets that match."""
    m = snapshot.get(name)
    if m is None:
        return None
    out = {"sum": 0.0, "count": 0}
    for s in m["series"]:
        if labels and any(s["labels"].get(k) != v for k, v in labels.items()):
            continue
        out["sum"] += s.get("sum", s.get("value", 0.0))
        out["count"] += s.get("count", 0)
    return out


def _finite(x):
    return float(x) if math.isfinite(x) else 1e30


def run_cell(workload, seed, seconds, trace, root=ROOT, run_cls=Run,
             devices=None):
    """One run of a cell; returns the result object. `run_cls` and
    `devices` let a test drive everything but the look for a chip, with
    the timed path broken underneath."""
    bench, cell, conf, cfg, traffic = load_cell(workload, root)
    if devices is None:
        devices = require_tpu(int(cell["chips"]))
    ctx = run_cls(cell, cfg, traffic, seed, seconds, trace, devices)
    window = importlib.import_module(WINDOWS[traffic["kind"]])
    out = window.run(ctx)
    ctx.reduce_trace()
    numbers = window.compare(ctx, out["evidence"])
    limits = traffic["limits"]
    compared = {k: [_finite(v), limits[k]] for k, v in numbers.items()
                if k in limits}
    correct = bool(compared) and out["failed"] == 0 and all(
        v <= lim for v, lim in compared.values())

    e2e = dict(out["end_to_end"], setup_s=ctx.setup_s)
    metrics = {}
    if not trace:
        for m in cell_metrics(bench, cell, "end_to_end"):
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    else:
        for m in cell_metrics(bench, cell, "per_layer"):
            value = ctx.read_metric(_load(data_file(root, conf, "metrics",
                                                    m["name"])))
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": ctx.memory_peak}
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if trace and ctx.red is not None:
        import trace_reduce
        device["busy_s"] = ctx.red["busy_s"]
        device["window_s"] = ctx.red["window_s"]
        result["breakdown"] = trace_reduce.breakdown(ctx.red)
    result["compared"] = compared
    return result


def report(result):
    """The numbers compared, each beside its limit, as the last lines of
    standard error; the result as the last line of standard output."""
    for name, (value, limit) in result["compared"].items():
        print("compared %s = %.6g (limit %.6g) %s" % (
            name, value, limit, "ok" if value <= limit else "FAILS"),
            file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result, default=float), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # the compile cache: where the environment says, else a fixed path
    # inside the checkout (the path is part of the cache's key)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    for p in (ROOT, HERE):
        if p not in sys.path:
            sys.path.insert(0, p)
    report(run_cell(args.workload, args.seed, args.seconds, args.trace))


if __name__ == "__main__":
    main()
