"""Span tracing + jaxpr step-cost profiler (docs/OBSERVABILITY.md
"Spans & step profiling").

Covers the ISSUE 11 contracts:
  * span nesting / parent attribution / attrs through the thread-local
    stack, and the `span` journal events they emit;
  * disabled-by-default safety — no journal installed means nothing is
    written anywhere but the in-process registry, and tracing disabled
    means the shared null-span fast path;
  * the cross-thread serving request span: `serve_request` begins on the
    submitter thread, ends in the worker, and its queue_wait + prefill
    children reproduce `serve_complete.ttft_s` within 10%;
  * the tracing-overhead contract, counted (mirrors PR 2's TestOverhead);
  * the exposed-collective rule on positive/negative shard_map fixtures
    (a bare psum vs. one with an adjacent independent dot);
  * step-card static cost accounting (exact dot_general FLOPs) and the
    `ptdoctor profile` rendering of a synthetic run dir.
"""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.observability import journal as run_journal
from paddle_tpu.observability import spans, tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _span_events(path):
    return [e for e in run_journal.read_journal(path)
            if e["event"] == "span"]


# ------------------------------------------------------------ span basics
class TestSpanBasics:
    def test_nesting_parents_attrs_and_journal(self, tmp_path):
        j = run_journal.RunJournal(str(tmp_path), filename="j.jsonl")
        prev = run_journal.set_journal(j)
        try:
            with spans.span("t_outer", phase="fit"):
                assert spans.current() == "t_outer"
                with spans.span("t_inner"):
                    assert spans.current() == "t_inner"
                    time.sleep(0.002)
                assert spans.current() == "t_outer"
            assert spans.current() is None
        finally:
            run_journal.set_journal(prev)
            j.close()
        evs = _span_events(str(tmp_path / "j.jsonl"))
        by = {e["name"]: e for e in evs}
        assert set(by) == {"t_outer", "t_inner"}
        assert by["t_inner"]["parent"] == "t_outer"
        assert "parent" not in by["t_outer"]
        assert by["t_outer"]["attrs"] == {"phase": "fit"}
        assert by["t_inner"]["dur_ms"] >= 2.0
        assert by["t_outer"]["dur_ms"] >= by["t_inner"]["dur_ms"]
        # one trace id correlates the whole process
        assert by["t_outer"]["trace"] == by["t_inner"]["trace"]

    def test_begin_end_crosses_threads_without_stack(self, tmp_path):
        j = run_journal.RunJournal(str(tmp_path), filename="j.jsonl")
        prev = run_journal.set_journal(j)
        try:
            h = spans.begin("t_xthread", rid=7)
            assert spans.current() is None       # begin() is unstacked
            t = threading.Thread(target=spans.end, args=(h,),
                                 kwargs={"ok": 1})
            t.start()
            t.join()
            spans.end(h)                          # double-end is a no-op
        finally:
            run_journal.set_journal(prev)
            j.close()
        evs = _span_events(str(tmp_path / "j.jsonl"))
        assert len(evs) == 1
        assert evs[0]["name"] == "t_xthread"
        assert evs[0]["attrs"] == {"rid": 7, "ok": 1}

    def test_record_banks_caller_measured_interval(self, tmp_path):
        j = run_journal.RunJournal(str(tmp_path), filename="j.jsonl")
        prev = run_journal.set_journal(j)
        try:
            spans.record("t_record", 12.5, parent="t_root", k="v")
        finally:
            run_journal.set_journal(prev)
            j.close()
        (ev,) = _span_events(str(tmp_path / "j.jsonl"))
        assert ev["dur_ms"] == 12.5
        assert ev["parent"] == "t_root"
        assert ev["attrs"] == {"k": "v"}

    def test_exception_pops_stack_and_skips_emit(self):
        c = spans.SPAN_MS.labels("t_exc")
        n0 = c.count
        with pytest.raises(ValueError):
            with spans.span("t_exc"):
                raise ValueError("boom")
        assert spans.current() is None
        assert c.count == n0        # an unwound block is not an interval

    def test_cancel_skips_emit(self):
        c = spans.SPAN_MS.labels("t_cancel")
        n0 = c.count
        with spans.span("t_cancel") as sp:
            sp.cancel()
        assert c.count == n0
        assert spans.current() is None

    def test_no_journal_means_metrics_only(self):
        # satellite 6: without a run journal (PADDLE_TPU_TELEMETRY_DIR
        # unset) spans still time into the registry but write no files
        assert run_journal.get_journal() is None
        c = spans.SPAN_MS.labels("t_nojournal")
        n0 = c.count
        with spans.span("t_nojournal"):
            pass
        assert c.count == n0 + 1

    def test_disabled_fast_path_is_a_shared_noop(self):
        was = tracing.enabled()
        c = spans.SPAN_MS.labels("t_disabled")
        n0 = c.count
        try:
            tracing.enable(False)
            with spans.span("t_disabled") as sp:
                assert spans.current() is None
            assert sp is spans.span("also_disabled")   # shared singleton
            assert spans.begin("t_disabled") is None
            spans.end(None)
            spans.record("t_disabled", 1.0)
        finally:
            tracing.enable(was)
        assert c.count == n0


# --------------------------------------------- serving request decomposition
class TestServingSpanParity:
    def test_serve_request_span_decomposes_ttft(self, tmp_path):
        """serve_request begins on the submitter thread, ends in the
        worker; queue_wait + prefill must reproduce serve_complete's
        ttft_s within 10% (they are computed from the same clock, so in
        practice they match exactly)."""
        from paddle_tpu.inference.serving import InferenceServer
        from paddle_tpu.models import gpt_tiny

        paddle.seed(0)
        m = gpt_tiny(vocab_size=64, hidden_size=32, num_layers=2,
                     num_heads=4, intermediate_size=64,
                     max_position_embeddings=64)
        m.eval()
        j = run_journal.RunJournal(str(tmp_path), filename="j.jsonl")
        prev = run_journal.set_journal(j)
        try:
            srv = InferenceServer(m, max_batch=2, max_seq_len=32,
                                  prefill_buckets=(8,), workers=1)
            with srv:
                rs = np.random.RandomState(0)
                handles = [srv.submit(rs.randint(0, 64, (4,)).tolist(),
                                      max_new_tokens=3) for _ in range(2)]
                for h in handles:
                    h.result(timeout=120)
        finally:
            run_journal.set_journal(prev)
            j.close()
        evs = run_journal.read_journal(str(tmp_path / "j.jsonl"))
        sp = [e for e in evs if e["event"] == "span"]
        completes = {e["rid"]: e for e in evs
                     if e["event"] == "serve_complete"}
        roots = {e["attrs"]["rid"]: e for e in sp
                 if e["name"] == "serve_request"}
        assert len(completes) == 2
        # one root span per completed request, same rid namespace
        assert set(roots) == set(completes)
        kids = {}
        for e in sp:
            if e.get("parent") == "serve_request":
                kids.setdefault(e["attrs"]["rid"], {})[e["name"]] = \
                    e["dur_ms"]
        for rid, done in completes.items():
            root = roots[rid]
            assert root["attrs"]["tokens"] == done["tokens"]
            ch = kids[rid]
            assert "queue_wait" in ch and "prefill" in ch
            ttft_ms = done["ttft_s"] * 1e3
            assert (ch["queue_wait"] + ch["prefill"]) == \
                pytest.approx(ttft_ms, rel=0.10, abs=0.5)
            # the root span covers its children
            assert root["dur_ms"] >= ch["queue_wait"]

    def test_suffix_prefill_span_rides_the_ttft_decomposition(
            self, tmp_path):
        """A prefix-cache hit admission records a `serve_suffix` child
        UNDER prefill (same interval) — so the trace names the
        suffix-only dispatches while queue_wait + prefill == ttft stays
        exact — and the Perfetto export carries the slice plus the
        request's flow arrows."""
        from paddle_tpu.inference.serving import (ContinuousBatcher,
                                                  GenerationEngine,
                                                  Request)
        from paddle_tpu.models import gpt_tiny
        from paddle_tpu.observability import traceview

        paddle.seed(0)
        m = gpt_tiny(vocab_size=64, hidden_size=32, num_layers=2,
                     num_heads=4, intermediate_size=64,
                     max_position_embeddings=64)
        m.eval()
        j = run_journal.RunJournal(str(tmp_path),
                                   filename="journal-rank0.jsonl")
        prev = run_journal.set_journal(j)
        try:
            eng = GenerationEngine(m, max_batch=2, max_seq_len=32,
                                   prefill_buckets=(8, 16),
                                   prefix_cache_bytes=32 << 20)
            rs = np.random.RandomState(7)
            head = rs.randint(0, 64, (8,)).astype(np.int64)
            cold = np.concatenate([head, rs.randint(0, 64, (4,))])
            hot = np.concatenate([head, rs.randint(0, 64, (3,))])
            b = ContinuousBatcher(eng)
            b.submit(Request(prompt=cold, max_new_tokens=2))
            b.run_until_idle()                # stores the 8-token prefix
            hit = b.submit(Request(prompt=hot, max_new_tokens=2))
            b.run_until_idle()
            assert hit.prefix_len == 8
        finally:
            run_journal.set_journal(prev)
            j.close()
        sp = _span_events(str(tmp_path / "journal-rank0.jsonl"))
        suffix = [e for e in sp if e["name"] == "serve_suffix"]
        # exactly the hit admission ran the suffix path
        assert len(suffix) == 1
        (sx,) = suffix
        assert sx["parent"] == "prefill"
        assert sx["attrs"]["rid"] == hit.rid
        assert sx["attrs"]["prefix_len"] == 8
        # same interval as the hit's prefill: the decomposition parity
        # queue_wait + prefill == ttft is untouched by the extra span
        pre = {e["attrs"]["rid"]: e for e in sp if e["name"] == "prefill"}
        qw = {e["attrs"]["rid"]: e for e in sp
              if e["name"] == "queue_wait"}
        assert sx["dur_ms"] == pre[hit.rid]["dur_ms"]
        assert (qw[hit.rid]["dur_ms"] + pre[hit.rid]["dur_ms"]) == \
            pytest.approx(hit.ttft_s * 1e3, rel=0.10, abs=0.5)
        # the Perfetto export carries the slice (cat=serve) and the
        # request's flow arrows survive alongside it
        path, n_events, _ = traceview.export_trace(str(tmp_path))
        evs = json.load(open(path))["traceEvents"]
        sx_slices = [e for e in evs if e["name"] == "serve_suffix"
                     and e["ph"] == "X"]
        assert len(sx_slices) == 1 and sx_slices[0]["cat"] == "serve"
        assert sx_slices[0]["args"]["prefix_len"] == 8
        flow_ids = {e["id"] for e in evs if e["ph"] in ("s", "f")}
        assert hit.rid in flow_ids


# ------------------------------------------------------- overhead contract
class _Counting:
    """Stand-ins that count the three things a span costs: a clock
    read, a locked histogram observe, a ring append."""

    def __init__(self, monkeypatch):
        self.clock = self.observe = self.append = 0
        counts = self

        class Ring:
            def append(self, _rec):
                counts.append += 1

        class Hist:
            def labels(self, *_a):
                return self

            def observe(self, _v):
                counts.observe += 1

        def clock():
            counts.clock += 1
            return time.perf_counter()

        monkeypatch.setattr(spans, "_clock", clock)
        monkeypatch.setattr(spans, "_ring", Ring())
        monkeypatch.setattr(spans, "SPAN_MS", Hist())

    def totals(self):
        return self.clock, self.observe, self.append


class TestSpanOverhead:
    #: what ONE decode step of the serving loop may spend in the span
    #: system when nothing completes: decode_step, its dispatch and its
    #: fetch, host_gap_decode and harvest (the batcher and the engine
    #: hand their own instants in, so only `dispatch` reads the span
    #: clock twice and `fetch` once, for its start)
    STEP_RECORDS = 5
    STEP_CLOCK_READS = 3

    def test_span_overhead_under_5pct(self, monkeypatch):
        """What tracing costs is a property of the code, counted here;
        the wall-clock comparison (telemetry on against off, on the
        chip) is in PERF.md. Off: every entry point is the shared no-op
        — no clock read, no observe, no ring append. On: a decode step
        stays within a stated number of each."""
        from paddle_tpu.inference.serving import (ContinuousBatcher,
                                                  GenerationEngine,
                                                  Request)
        from paddle_tpu.models import gpt_tiny

        paddle.seed(0)
        m = gpt_tiny(vocab_size=64, hidden_size=32, num_layers=2,
                     num_heads=4, intermediate_size=64,
                     max_position_embeddings=64)
        m.eval()
        eng = GenerationEngine(m, max_batch=2, max_seq_len=32,
                               prefill_buckets=(8,))
        b = ContinuousBatcher(eng)
        b.submit(Request(prompt=[1, 2, 3], max_new_tokens=12))
        b.step()                       # admit + compile, uncounted
        b.step()
        cnt = _Counting(monkeypatch)
        was = tracing.enabled()
        try:
            tracing.enable(False)
            null = spans.span("t_off")
            with null as sp:
                sp.close(1.0, k=1)
            assert null is spans.span("t_off_too", t0=1.0, label="x")
            assert spans.begin("t_off", t0=1.0) is None
            spans.end(None, 2.0)
            spans.record("t_off", 1.0, t0=1.0)
            b.step()
            b.step()
            assert cnt.totals() == (0, 0, 0)
            tracing.enable(True)
            for k in (1, 2, 3):
                b.step()
                assert cnt.observe == cnt.append == k * self.STEP_RECORDS
                assert cnt.clock == k * self.STEP_CLOCK_READS
        finally:
            tracing.enable(was)
        assert b.steps == 7 and b.active == 1


# ------------------------------------------------- exposed-collective rule
class TestExposedCollective:
    def _mesh(self):
        import jax
        from jax.sharding import Mesh
        return Mesh(np.array(jax.devices()[:1]), ("x",))

    def test_bare_psum_is_flagged(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from paddle_tpu.analysis import exposed_collective_findings

        def body(x):
            return jax.lax.psum(x, "x") + 1.0

        fn = jax.shard_map(body, mesh=self._mesh(), in_specs=(P("x"),),
                           out_specs=P("x"), check_vma=False)
        jx = jax.make_jaxpr(fn)(jnp.zeros((128, 256), jnp.float32))
        fs = exposed_collective_findings(jx, "pos")
        assert [f.rule for f in fs] == ["exposed-collective"]
        assert "psum" in fs[0].message
        assert fs[0].severity == "warning"

    def test_psum_with_adjacent_independent_dot_passes(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from paddle_tpu.analysis import exposed_collective_findings

        def body(x, y, z):
            s = jax.lax.psum(x, "x")
            k = z @ y              # independent of the psum: overlappable
            return s + k

        fn = jax.shard_map(body, mesh=self._mesh(),
                           in_specs=(P("x"), P(), P("x")),
                           out_specs=P("x"), check_vma=False)
        jx = jax.make_jaxpr(fn)(
            jnp.zeros((128, 256), jnp.float32),
            jnp.zeros((256, 256), jnp.float32),
            jnp.zeros((128, 256), jnp.float32))
        assert exposed_collective_findings(jx, "neg") == []

    def test_small_psum_is_latency_noise_not_flagged(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from paddle_tpu.analysis import exposed_collective_findings

        def body(x):
            return jax.lax.psum(x, "x") + 1.0

        fn = jax.shard_map(body, mesh=self._mesh(), in_specs=(P("x"),),
                           out_specs=P("x"), check_vma=False)
        jx = jax.make_jaxpr(fn)(jnp.zeros((16, 16), jnp.float32))
        assert exposed_collective_findings(jx, "small") == []

    def test_dependent_dot_does_not_count_as_overlap(self):
        # a dot CONSUMING the psum result cannot hide it
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from paddle_tpu.analysis import exposed_collective_findings

        def body(x, y):
            s = jax.lax.psum(x, "x")
            return s @ y

        fn = jax.shard_map(body, mesh=self._mesh(),
                           in_specs=(P("x"), P()), out_specs=P("x"),
                           check_vma=False)
        jx = jax.make_jaxpr(fn)(
            jnp.zeros((128, 256), jnp.float32),
            jnp.zeros((256, 64), jnp.float32))
        fs = exposed_collective_findings(jx, "dep")
        assert [f.rule for f in fs] == ["exposed-collective"]


# ----------------------------------------------------------- step card
class TestStepCard:
    def test_dot_flops_exact_and_inventory(self):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.analysis import step_card_from_jaxpr

        jx = jax.make_jaxpr(lambda a, b: a @ b)(
            jnp.zeros((128, 256), jnp.float32),
            jnp.zeros((256, 64), jnp.float32))
        card = step_card_from_jaxpr(jx, "mm")
        assert card["label"] == "mm"
        assert card["flops"] == 2 * 128 * 64 * 256
        assert card["hbm_bytes"] == 4 * (128 * 256 + 256 * 64 + 128 * 64)
        assert card["collectives"]["count"] == 0
        assert card["dominant_eqns"][0]["primitive"] == "dot_general"
        assert card["arithmetic_intensity"] > 0

    def test_collective_inventory_records_operand(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from paddle_tpu.analysis import step_card_from_jaxpr

        mesh = Mesh(np.array(jax.devices()[:1]), ("x",))

        def body(x):
            return jax.lax.psum(x, "x")

        fn = jax.shard_map(body, mesh=mesh, in_specs=(P("x"),),
                           out_specs=P("x"), check_vma=False)
        jx = jax.make_jaxpr(fn)(jnp.zeros((64, 64), jnp.float32))
        card = step_card_from_jaxpr(jx, "col")
        assert card["collectives"]["count"] == 1
        (rec,) = card["collectives"]["inventory"]
        assert rec["primitive"] == "psum"
        assert rec["bytes"] == 64 * 64 * 4

    def test_step_card_via_analysis_handle(self, tmp_path):
        from paddle_tpu.analysis import step_card, write_step_card
        from paddle_tpu.jit.engine import make_train_step

        paddle.seed(0)
        net = nn.Linear(16, 4)
        opt = paddle.optimizer.SGD(learning_rate=0.01,
                                   parameters=net.parameters())
        step = make_train_step(net, nn.MSELoss(), opt)
        x = paddle.to_tensor(np.ones((8, 16), np.float32))
        y = paddle.to_tensor(np.ones((8, 4), np.float32))
        card = step_card(step, [x], [y], label="linear_train",
                         with_xla=False)
        assert card["eqns"] > 0 and card["flops"] > 0
        out = str(tmp_path / "step_card.json")
        write_step_card(card, out)
        assert json.load(open(out))["label"] == "linear_train"


# ------------------------------------------------------- ptdoctor profile
class TestPtdoctorProfile:
    def _run(self, *argv):
        return subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "ptdoctor.py"),
             *argv], capture_output=True, text=True, timeout=60)

    def test_profile_renders_decomposition_and_card(self, tmp_path):
        d = str(tmp_path)
        j = run_journal.RunJournal(d, rank=0)
        prev = run_journal.set_journal(j)
        try:
            spans.record("step", 100.0)
            spans.record("compile", 60.0, parent="step")
            spans.record("dispatch", 30.0, parent="step")
            spans.record("feed", 5.0, parent="step")
            spans.record("host", 1.0, parent="step")
        finally:
            run_journal.set_journal(prev)
            j.close()
        with open(os.path.join(d, "step_card.json"), "w") as f:
            json.dump({"label": "synthetic", "eqns": 3, "flops": 2048,
                       "hbm_bytes": 1024, "arithmetic_intensity": 2.0,
                       "collectives": {"count": 1, "bytes": 512,
                                       "inventory": [{"primitive": "psum",
                                                      "dtype": "float32",
                                                      "shape": [8, 16],
                                                      "bytes": 512}]},
                       "dominant_eqns": [{"primitive": "dot_general",
                                          "out_shape": [8, 4],
                                          "flops": 2048, "bytes": 512}]},
                      f)
        r = self._run("profile", d)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "step decomposition" in r.stdout
        assert "compile" in r.stdout and "dispatch" in r.stdout
        assert "critical path" in r.stdout
        assert "step card: synthetic" in r.stdout
        assert "psum" in r.stdout

    def test_profile_without_spans_exits_2(self, tmp_path):
        r = self._run("profile", str(tmp_path))
        assert r.returncode == 2
        assert "no span events" in r.stdout


# -------------------------------------------------- fit span integration
class TestFitSpans:
    def test_fit_emits_nested_step_spans(self, tmp_path):
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(8, 8), nn.ReLU(), nn.Linear(8, 4))
        model = paddle.Model(net)
        opt = paddle.optimizer.SGD(learning_rate=0.01,
                                   parameters=net.parameters())
        model.prepare(opt, nn.CrossEntropyLoss())
        X = np.random.RandomState(0).rand(16, 8).astype("float32")
        Y = np.zeros((16, 1), np.int64)
        ds = [(X[i], Y[i]) for i in range(16)]
        model.fit(ds, batch_size=8, epochs=1, verbose=0,
                  telemetry_dir=str(tmp_path))
        sp = _span_events(os.path.join(str(tmp_path),
                                       "journal-rank0.jsonl"))
        steps = [e for e in sp if e["name"] == "step"]
        assert len(steps) == 2
        kid_names = {e["name"] for e in sp if e.get("parent") == "step"}
        # compile on the first step, dispatch on the steady-state one
        assert {"feed", "compile", "dispatch", "host"} <= kid_names
        # the acceptance decomposition: children cover >=90% of step time
        step_total = sum(e["dur_ms"] for e in steps)
        child_total = sum(e["dur_ms"] for e in sp
                          if e.get("parent") == "step")
        assert child_total >= 0.9 * step_total, (child_total, step_total)
        # one trace id across every span of the run
        assert len({e["trace"] for e in sp}) == 1


# -------------------------------------------------------- serving rollup
class TestServingRollup:
    def test_rollup_folds_pt_serve_series_per_source(self, tmp_path):
        from paddle_tpu.observability import aggregate

        def snap(path, admitted, ttft_count, ttft_sum):
            with open(path, "w") as f:
                json.dump({"ts": 1.0, "metrics": {
                    "pt_serve_admitted_total": {
                        "kind": "counter", "series": [
                            {"labels": {}, "value": admitted}]},
                    "pt_serve_ttft_seconds": {
                        "kind": "histogram", "series": [
                            {"labels": {}, "count": ttft_count,
                             "sum": ttft_sum, "buckets": {}}]},
                }}, f)

        snap(str(tmp_path / "metrics-rank0.json"), 3, 3, 0.3)
        snap(str(tmp_path / "metrics-rank1.json"), 5, 5, 1.0)
        _, n = aggregate.rollup_metrics(str(tmp_path))
        roll = json.load(open(str(tmp_path / "metrics-rollup.json")))
        serving = roll["serving"]
        assert serving["per_source"]["metrics-rank0.json"][
            "pt_serve_admitted_total"] == 3
        assert serving["per_source"]["metrics-rank1.json"][
            "pt_serve_admitted_total"] == 5
        assert serving["totals"]["pt_serve_admitted_total"]["value"] == 8
        t = serving["totals"]["pt_serve_ttft_seconds"]
        # exact cross-rank mean: (0.3 + 1.0) / 8, not mean-of-means
        assert t["count"] == 8
        assert t["mean"] == pytest.approx(1.3 / 8)

    def test_summary_surfaces_per_replica_serving(self, tmp_path):
        from paddle_tpu.observability import aggregate

        d = str(tmp_path)
        j = run_journal.RunJournal(d, rank=0)
        j.emit("step", step=1)
        j.close()
        with open(os.path.join(d, "metrics-rank0.json"), "w") as f:
            json.dump({"ts": 1.0, "metrics": {
                "pt_serve_admitted_total": {
                    "kind": "counter",
                    "series": [{"labels": {}, "value": 4}]},
                "pt_serve_completed_total": {
                    "kind": "counter",
                    "series": [{"labels": {}, "value": 4}]},
                "pt_serve_tokens_total": {
                    "kind": "counter",
                    "series": [{"labels": {}, "value": 12}]},
            }}, f)
        aggregate.rollup_metrics(d)
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "ptdoctor.py"),
             "summary", d], capture_output=True, text=True, timeout=60)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "serving: admitted=4  completed=4  tokens=12" in r.stdout
        assert "metrics-rank0.json: admitted=4  completed=4  tokens=12" \
            in r.stdout
