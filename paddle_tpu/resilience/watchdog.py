"""Step watchdog: bound a dispatch that may hang, dumping diagnostics.

A hung device or collective makes a compiled-step dispatch block forever
inside PJRT with no Python-level signal delivery. An in-process watchdog
cannot CANCEL a stuck C++ call, but it can make the hang observable and
actionable: after `timeout_s` it dumps every thread's stack (faulthandler)
plus the caller's context to stderr and an optional file, then either keeps
waiting (action="warn") or hard-exits with a distinctive code so a
supervisor — the launcher, the elastic manager, a cron watcher — restarts
the process (action="abort", exit code 124 to match `timeout(1)`).

Reference analogue: the trainer watchdog in the reference's fleet elastic
manager (manager.py watches heartbeat staleness and relaunches) — moved
down to the single-step granularity the paper's runtime needs.
"""
from __future__ import annotations

import faulthandler
import os
import sys
import threading
import time

ABORT_EXIT_CODE = 124


class StepWatchdog:
    """Context manager: dump diagnostics if the body outlives `timeout_s`.

        with StepWatchdog(30.0, context="compiled train step 812"):
            out = jitted(*args)      # may hang in PJRT

    `action`: "warn" (default) dumps once and lets the body keep waiting;
    "abort" dumps then os._exit(124) — for supervised processes where a
    restart beats an indefinite hang. `diag_path` additionally appends the
    dump to a file (env PADDLE_TPU_WATCHDOG_FILE when unset) so diagnostics
    survive a supervisor's stderr truncation."""

    def __init__(self, timeout_s: float, context: str = "",
                 action: str = "warn", diag_path: str = None,
                 on_fire=None):
        if action not in ("warn", "abort"):
            raise ValueError("action must be 'warn' or 'abort', got %r"
                             % (action,))
        self.timeout_s = float(timeout_s)
        self.context = context
        self.action = action
        self.diag_path = diag_path if diag_path is not None else \
            os.environ.get("PADDLE_TPU_WATCHDOG_FILE")
        self.on_fire = on_fire
        self.fired = False
        self._timer = None
        self._t0 = None

    def _dump(self, stream):
        stream.write(
            "\n=== paddle_tpu StepWatchdog: %r exceeded %.1fs "
            "(started %.1fs ago, pid %d, action=%s) ===\n"
            % (self.context or "step", self.timeout_s,
               time.monotonic() - self._t0, os.getpid(), self.action))
        faulthandler.dump_traceback(file=stream, all_threads=True)
        stream.write("=== end watchdog dump ===\n")
        stream.flush()

    def _fire(self):
        self.fired = True
        try:
            # stderr faulthandler dump stays — it is the artifact that
            # matters when the process is about to be killed; the journal
            # line makes the firing greppable across a fleet's runs
            self._dump(sys.stderr)
            if self.diag_path:
                with open(self.diag_path, "a") as f:
                    self._dump(f)
        except Exception:
            pass  # diagnostics must never mask the original condition
        try:
            from ..observability import flight, journal, metrics
            metrics.counter("pt_watchdog_fires_total",
                            "StepWatchdog timeouts").inc()
            journal.emit("watchdog", context=self.context,
                         timeout_s=self.timeout_s, action=self.action)
            # a firing watchdog means the dispatch is wedged: bundle the
            # flight ring NOW — with action="abort" this process is gone
            # two lines from here
            flight.dump_crash_bundle("watchdog", context=self.context,
                                     timeout_s=self.timeout_s,
                                     action=self.action)
        except Exception:
            pass
        if self.on_fire is not None:
            try:
                self.on_fire()
            except Exception:
                pass
        if self.action == "abort":
            os._exit(ABORT_EXIT_CODE)

    def __enter__(self):
        self._t0 = time.monotonic()
        if self.timeout_s > 0:
            self._timer = threading.Timer(self.timeout_s, self._fire)
            self._timer.daemon = True
            self._timer.start()
        return self

    def __exit__(self, *exc):
        if self._timer is not None:
            self._timer.cancel()
        return False
