"""Persistent compilation cache (jit/compile_cache.py): the warm-restart
contract (second process over the same cache dir reloads instead of
recompiling), the retrace-vs-warm-reload reclassification inside
StepTelemetry, and where the cache lives.

The contract test is the CI teeth of PR 9's tentpole: run the SAME tiny
fit twice in fresh subprocesses sharing one JAX_COMPILATION_CACHE_DIR;
the second run must see cache hits, zero retraces and strictly less
compile wall time — and its journal must say `compile_cache`, not
`retrace`.

tests/conftest.py switches the cache off for the test process (jax's own
JAX_ENABLE_COMPILATION_CACHE=false); the children here switch it back on."""
import glob
import json
import os
import subprocess
import sys

import paddle_tpu  # noqa: F401  (conftest pins the cpu platform)
from paddle_tpu.jit import compile_cache
from paddle_tpu.observability import journal as run_journal
from paddle_tpu.observability import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# fresh interpreter: the is_cache_used latch and executable caches are
# per-process, so only a subprocess can model a gang restart
CHILD = """
import json, sys
import numpy as np
import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.jit import compile_cache
from paddle_tpu.observability import tracing

paddle.seed(0)
net = nn.Sequential(nn.Linear(8, 8), nn.ReLU(), nn.Linear(8, 4))
opt = paddle.optimizer.SGD(learning_rate=0.01, parameters=net.parameters())
m = paddle.Model(net)
m.prepare(opt, nn.CrossEntropyLoss())
X = np.random.RandomState(0).rand(16, 8).astype("float32")
Y = np.zeros((16, 1), np.int64)
ds = [(X[i], Y[i]) for i in range(16)]
m.fit(ds, batch_size=8, epochs=1, verbose=0, telemetry_dir=sys.argv[1])
hits, misses = compile_cache.totals()
print(json.dumps({
    "enabled": compile_cache.enabled(),
    "hits": hits, "misses": misses,
    "retraces": tracing.RETRACES.labels("jit_train").value,
    "compile_s": tracing.COMPILE_SECONDS.labels("jit_train").value,
}))
"""


def _events(tdir):
    evs = []
    for path in sorted(glob.glob(os.path.join(tdir, "journal-*.jsonl"))):
        evs.extend(run_journal.read_journal(path))
    return evs


class TestWarmCacheContract:
    def _fit_child(self, tmp_path, tag, cache_dir):
        script = tmp_path / "child.py"
        script.write_text(CHILD)
        tdir = str(tmp_path / ("telemetry_" + tag))
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
                   JAX_ENABLE_COMPILATION_CACHE="true",
                   JAX_COMPILATION_CACHE_DIR=str(cache_dir))
        r = subprocess.run([sys.executable, str(script), tdir],
                           capture_output=True, text=True, timeout=240,
                           env=env, cwd=REPO)
        assert r.returncode == 0, r.stdout + r.stderr
        lines = [ln for ln in r.stdout.splitlines()
                 if ln.strip().startswith("{")]
        return json.loads(lines[-1]), tdir

    def test_second_process_reloads_instead_of_recompiling(self, tmp_path):
        cache = tmp_path / "xla_cache"
        cold, cold_dir = self._fit_child(tmp_path, "cold", cache)
        assert cold["enabled"]
        assert cold["hits"] == 0
        assert cold["misses"] >= 1          # populated the cache
        assert cold["retraces"] >= 1        # first compile is a retrace
        assert os.listdir(cache)            # entries actually on disk
        cold_evs = _events(cold_dir)
        assert any(e["event"] == "retrace" for e in cold_evs)

        warm, warm_dir = self._fit_child(tmp_path, "warm", cache)
        assert warm["hits"] >= 1            # the contract
        assert warm["misses"] == 0
        assert warm["retraces"] == 0        # reclassified, not counted
        assert warm["compile_s"] < cold["compile_s"]
        warm_evs = _events(warm_dir)
        cc = [e for e in warm_evs if e["event"] == "compile_cache"]
        assert cc and cc[0]["hits"] >= 1 and cc[0]["engine"] == "jit_train"
        assert not any(e["event"] == "retrace" for e in warm_evs)


class TestReclassification:
    """StepTelemetry must journal a miss-span as `compile_cache` exactly
    when the persistent cache served everything (hits>0, misses==0) —
    and keep byte-identical retrace accounting otherwise. Every
    miss-span also closes a `compile` profiling span (observability/
    spans.py), which rides in the same journal as a `span` event."""

    @staticmethod
    def _classified(evs):
        """(non-span events, span events) — the dispatch profiling span
        is part of the journal but not of the retrace classification."""
        return ([e for e in evs if e["event"] != "span"],
                [e for e in evs if e["event"] == "span"])

    def _miss_span(self, tmp_path, engine, probe_seq):
        j = run_journal.RunJournal(str(tmp_path))
        prev_j = run_journal.set_journal(j)
        seq = iter(probe_seq) if probe_seq is not None else None
        tracing.set_compile_cache_probe(
            (lambda: next(seq)) if seq is not None else None)
        try:
            tel = tracing.StepTelemetry(engine)
            r0 = tel.retraces
            with tel.step(("sig", 0)):
                pass
            return run_journal.read_journal(j.path), tel.retraces - r0
        finally:
            tracing.set_compile_cache_probe(compile_cache.totals)
            run_journal.set_journal(prev_j)

    def test_warm_reload_is_not_a_retrace(self, tmp_path):
        # probe read at span entry then at finish: 2 hits, 0 misses
        evs, dr = self._miss_span(tmp_path, "eng_warm", [(0, 0), (2, 0)])
        assert dr == 0
        evs, spans = self._classified(evs)
        assert [e["event"] for e in evs] == ["compile_cache"]
        assert evs[0]["hits"] == 2 and evs[0]["engine"] == "eng_warm"
        assert evs[0]["compile_s"] >= 0
        # the reload still stalls the loop, so it still profiles as a
        # compile span
        assert [s["name"] for s in spans] == ["compile"]
        assert spans[0]["attrs"]["engine"] == "eng_warm"

    def test_cache_miss_stays_a_retrace(self, tmp_path):
        evs, dr = self._miss_span(tmp_path, "eng_miss", [(0, 0), (0, 1)])
        assert dr == 1
        evs, spans = self._classified(evs)
        assert [e["event"] for e in evs] == ["retrace"]
        assert evs[0]["cache_misses"] == 1
        assert [s["name"] for s in spans] == ["compile"]

    def test_partial_hit_stays_a_retrace(self, tmp_path):
        # some executables reloaded, one still compiled: that dispatch
        # paid real XLA time, so it counts
        evs, dr = self._miss_span(tmp_path, "eng_part", [(0, 0), (3, 1)])
        assert dr == 1
        evs, _ = self._classified(evs)
        assert evs[0]["event"] == "retrace"

    def test_no_probe_keeps_legacy_accounting(self, tmp_path):
        evs, dr = self._miss_span(tmp_path, "eng_nop", None)
        assert dr == 1
        evs, _ = self._classified(evs)
        assert evs[0]["event"] == "retrace"
        assert "cache_misses" not in evs[0]


WHERE = """
import json, os, sys
import jax
calls = []
real_update = jax.config.update
def spy(name, value):
    calls.append(name)
    return real_update(name, value)
jax.config.update = spy
import paddle_tpu
from paddle_tpu.jit import compile_cache
print(json.dumps({"dir": compile_cache.cache_dir(),
                  "enabled": compile_cache.enabled(),
                  "set_dir_calls": calls.count("jax_compilation_cache_dir"),
                  "min_secs": jax.config.jax_persistent_cache_min_compile_time_secs,
                  "min_bytes": jax.config.jax_persistent_cache_min_entry_size_bytes}))
"""


class TestWhereTheCacheLives:
    """JAX_COMPILATION_CACHE_DIR set: jax reads it and no code sets a
    directory. Unset: one fixed path inside the checkout, the same in
    every process (a directory that moves never hits)."""

    def _child(self, cwd, **env_over):
        env = {k: v for k, v in os.environ.items()
               if k not in ("JAX_COMPILATION_CACHE_DIR",
                            "JAX_ENABLE_COMPILATION_CACHE")}
        env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO, **env_over)
        r = subprocess.run([sys.executable, "-c", WHERE],
                           capture_output=True, text=True, timeout=120,
                           env=env, cwd=cwd)
        assert r.returncode == 0, r.stdout + r.stderr
        return json.loads(r.stdout.strip().splitlines()[-1])

    def test_env_var_set_means_code_sets_no_directory(self, tmp_path):
        got = self._child(REPO, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
        assert got["dir"] == str(tmp_path)
        assert got["set_dir_calls"] == 0
        assert got["enabled"]
        # the thresholds are zeroed either way: sub-second CPU compiles
        # must be cacheable or CI could not prove the warm contract
        assert got["min_secs"] == 0 and got["min_bytes"] == -1

    def test_unset_means_one_fixed_path_in_the_checkout(self, tmp_path):
        a = self._child(REPO)
        b = self._child(str(tmp_path))      # another cwd, another process
        assert a["dir"] == b["dir"] == os.path.join(REPO, ".jax_cache")
        assert a["set_dir_calls"] == b["set_dir_calls"] == 1
        assert a["enabled"] and b["enabled"]    # on by default


class TestConfigure:
    def test_configure_installs_accounting_once(self, monkeypatch):
        import jax

        registered = []
        monkeypatch.setattr(compile_cache, "_listener_installed", False)
        monkeypatch.setattr(
            "jax._src.monitoring.register_event_listener",
            registered.append)
        before = jax.config.jax_compilation_cache_dir
        compile_cache.configure()
        compile_cache.configure()
        assert len(registered) == 1                 # idempotent
        assert jax.config.jax_compilation_cache_dir == before  # dir untouched
        # the listener folds jax's events into totals()
        h0, m0 = compile_cache.totals()
        registered[0]("/jax/compilation_cache/cache_hits")
        registered[0]("/jax/compilation_cache/cache_misses")
        assert compile_cache.totals() == (h0 + 1, m0 + 1)
